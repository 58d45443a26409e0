"""Stateless single-input executors: Receiver, Project, Filter.

Reference parity:
- ReceiverExecutor: src/stream/src/executor/receiver.rs (single upstream
  channel as an executor).
- ProjectExecutor: src/stream/src/executor/project.rs — eval expressions
  over the chunk, emit new columns; watermarks pass through with column
  remapping when derivable.
- FilterExecutor: src/stream/src/executor/filter.rs — predicate masks
  visibility; UpdateDelete/UpdateInsert pairs whose halves diverge under
  the predicate degrade to plain Delete/Insert (one half hidden).

TPU notes: both operators are pure vectorized passes over the padded chunk;
no per-row host work. Filter's pair-degradation is a shifted-mask trick,
one fused VPU pass.
"""

from __future__ import annotations

from typing import AsyncIterator, List, Optional, Sequence

from risingwave_tpu.common.chunk import Op, StreamChunk, get_xp
from risingwave_tpu.common.types import Field, Schema
from risingwave_tpu.expr.expr import Expression
from risingwave_tpu.stream.exchange import ChannelClosed, Receiver
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.utils.ledger import actor_clock
from risingwave_tpu.stream.message import (
    Message, Watermark, is_barrier, is_chunk,
)


class ReceiverExecutor(Executor):
    """Adapts one exchange Receiver into an Executor (receiver.rs)."""

    def __init__(self, info: ExecutorInfo, rx: Receiver, actor_id: int = 0):
        super().__init__(info)
        self.rx = rx
        self.actor_id = actor_id
        # wall time parked on the channel waiting for the next message
        # — idle, not processing; the monitor subtracts it from this
        # node's exclusive busy (same contract as SourceExecutor and
        # RemoteInput: a chain edge waiting out a slow upstream must
        # not read as the downstream chain's straggler)
        self.idle_wait_s = 0.0

    async def execute(self) -> AsyncIterator[Message]:
        # NOTE: no rx.close() on teardown here — the chain edge may
        # still be attached to a live upstream dispatcher (a close
        # would turn its next dispatch into ChannelClosed and kill the
        # healthy upstream); the session's _stop_job closes the rx via
        # close_receivers AFTER detaching the edge
        while True:
            t0 = actor_clock()
            try:
                msg = await self.rx.recv()
            except ChannelClosed:
                return
            finally:
                self.idle_wait_s += actor_clock() - t0
            yield msg
            if is_barrier(msg) and msg.is_stop(self.actor_id):
                return


class ProjectExecutor(Executor):
    """Vectorized projection (project.rs analog)."""

    def __init__(self, input_: Executor, exprs: Sequence[Expression],
                 names: Optional[Sequence[str]] = None,
                 watermark_derivations: Optional[dict] = None,
                 span_args: Optional[dict] = None):
        self.input = input_
        self.exprs = list(exprs)
        names = list(names) if names else [
            f"expr{i}" for i in range(len(exprs))]
        out_fields: List[Field] = []
        for name, e in zip(names, self.exprs):
            out_fields.append(Field(name, e.return_type))
        info = ExecutorInfo(Schema(out_fields), [], "ProjectExecutor")
        super().__init__(info)
        # input col idx -> output col idx OR (output col idx, transform)
        # for a monotone expression over the watermark column (the
        # reference derives output watermarks through monotone exprs,
        # watermark.rs::transform_with_expr — e.g. tumble_start maps a
        # date_time watermark to a window_start watermark)
        self.watermark_derivations = dict(watermark_derivations or {})
        # attributes for this executor's span of the epoch trace
        # (stream/monitor.py), from the planner
        self.span_args = dict(span_args or {})

    @staticmethod
    def _drop_noop_updates(cols, vis, ops):
        """Mask out U-/U+ pairs whose halves are identical AFTER the
        projection (project.rs noop-update elimination): when a
        projection drops a changing column (e.g. a dedup agg's hidden
        _cnt), every duplicate otherwise becomes a full update churning
        join chains and state tables downstream. Dropping an identical
        pair is multiset-exact regardless of keys."""
        import numpy as np
        ud = np.flatnonzero(vis[:-1] & vis[1:]
                            & (ops[:-1] == int(Op.UPDATE_DELETE))
                            & (ops[1:] == int(Op.UPDATE_INSERT)))
        if not len(ud):
            return vis
        same = np.ones(len(ud), dtype=bool)
        for c in cols:
            v = np.asarray(c.values)
            eq = np.asarray(v[ud] == v[ud + 1], dtype=bool)
            if c.validity is not None:
                ok = np.asarray(c.validity)
                both_null = ~ok[ud] & ~ok[ud + 1]
                eq = (eq & ok[ud] & ok[ud + 1]) | both_null
            same &= eq
            if not same.any():
                return vis
        drop = ud[same]
        vis = vis.copy()
        vis[drop] = False
        vis[drop + 1] = False
        return vis

    async def execute(self) -> AsyncIterator[Message]:
        import numpy as np
        async for msg in self.input.execute():
            if is_chunk(msg):
                cols = [e.eval(msg) for e in self.exprs]
                vis = msg.visibility
                ops_np = np.asarray(msg.ops)
                if (ops_np == int(Op.UPDATE_DELETE)).any():
                    vis = self._drop_noop_updates(cols, np.asarray(vis),
                                                  ops_np)
                    if not np.asarray(vis).any():
                        continue   # all pairs were noops: emit nothing
                yield StreamChunk(self.schema, cols, vis, msg.ops)
            elif isinstance(msg, Watermark):
                # one input watermark may derive SEVERAL outputs (the
                # raw column plus a windowed image of it)
                for wm in msg.derived(self.watermark_derivations):
                    yield wm
                # underivable watermarks are dropped (reference behavior)
            else:
                yield msg


class FilterExecutor(Executor):
    """Visibility-mask filter with update-pair degradation (filter.rs)."""

    def __init__(self, input_: Executor, predicate: Expression):
        self.input = input_
        self.predicate = predicate
        info = ExecutorInfo(input_.schema, list(input_.pk_indices),
                            "FilterExecutor")
        super().__init__(info)

    async def execute(self) -> AsyncIterator[Message]:
        import numpy as np
        async for msg in self.input.execute():
            if is_chunk(msg):
                out = self._apply(msg)
                # a fully-filtered chunk is dead weight downstream
                # (empty-message suppression, end to end)
                if np.asarray(out.visibility).any():
                    yield out
            else:
                yield msg

    def _apply(self, chunk: StreamChunk) -> StreamChunk:
        return self.apply_predicate(chunk, self.predicate)

    @staticmethod
    def apply_predicate(chunk: StreamChunk,
                        predicate: Expression) -> StreamChunk:
        """THE filter transform — xp-generic, so the interpretive path
        (numpy), the fused traced path (jit tracers, ops/fused.py) and
        an inner join's own condition on its matched pairs
        (HashJoinExecutor._pairs_chunk, numpy) run the same
        implementation: visibility mask plus U-/U+ pair degradation by
        shifted compares."""
        pcol = predicate.eval(chunk)
        xp = get_xp(pcol.values, chunk.ops)
        pred = pcol.values.astype(bool)
        if pcol.validity is not None:  # NULL predicate = not satisfied
            pred = pred & pcol.validity
        ops = chunk.ops
        is_ud = ops == xp.int8(int(Op.UPDATE_DELETE))
        is_ui = ops == xp.int8(int(Op.UPDATE_INSERT))
        # pair (i, i+1): U- at i, U+ at i+1
        next_is_ui = xp.roll(is_ui, -1)
        prev_is_ud = xp.roll(is_ud, 1)
        next_pred = xp.roll(pred, -1)
        prev_pred = xp.roll(pred, 1)
        # U- whose U+ half fails the predicate → plain DELETE
        degrade_del = is_ud & next_is_ui & pred & ~next_pred
        # U+ whose U- half fails the predicate → plain INSERT
        degrade_ins = is_ui & prev_is_ud & pred & ~prev_pred
        new_ops = xp.where(degrade_del, xp.int8(int(Op.DELETE)), ops)
        new_ops = xp.where(degrade_ins, xp.int8(int(Op.INSERT)), new_ops)
        return StreamChunk(chunk.schema, chunk.columns,
                           chunk.visibility & pred, new_ops)
