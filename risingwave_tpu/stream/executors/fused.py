"""FusedFragmentExecutor: a filter/project run as ONE traced step.

Reference departure (TiLT, arxiv 2301.12030): the reference interprets
its operator graph — each executor a separate async stage; this
executor collapses a maximal fusable run (frontend/opt/fusion.py marks
them) into a single ``jax.jit`` program per chunk. Two deployment
shapes share the machinery (ops/fused.py):

- **standalone** (this executor): the run feeds a join input side,
  materialize, or any non-agg consumer. The chunk's referenced device
  columns enter one jitted chain step (filters + projection + noop-pair
  drop), host-typed passthrough columns ride around the trace, and the
  output materializes back to host numpy for the consumer. N vectorized
  host passes become one compiled program; semantics are bit-identical
  to the sequential executors (see FusedStages docstring).
- **agg-prelude** (stream/executors/hash_agg.py): the same composed run
  inlines INTO the agg kernel's jitted apply with donated state — no
  host materialization at all; this executor never appears, the
  HashAggExecutor absorbs the stages.

Watermarks and barriers are per-message host work and flow through the
composed derivation chain (FusedStages.derive_watermarks) exactly as
the sequential ProjectExecutors would have derived them.
"""

from __future__ import annotations

from typing import AsyncIterator, List

import numpy as np

from risingwave_tpu.common.chunk import Column, Op, StreamChunk
from risingwave_tpu.ops.fused import FusedStages, build_chain_step
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.utils.ledger import staged
from risingwave_tpu.stream.message import (
    Message, Watermark, is_barrier, is_chunk,
)


# The chain step is one compiled program per input capacity. A source
# hands it chunks of one capacity; a join hands it chunks as large as
# the epoch's matches, and a program per size the data ever takes is a
# compile at any barrier, for as long as the view runs. So the step
# runs at the capacities of a fixed ladder, the powers of four up to
# CHAIN_CAP_TOP, and an executor keeps to the largest rung it has
# needed: smaller chunks are padded up to it with invisible rows, a
# larger one takes it a rung up (a new program, at most eight times in
# an executor's life) and one above the top is cut into pieces. What
# comes out is cut back to the rows that went in.
CHAIN_CAP_TOP = 1 << 16


def chain_rung(capacity: int) -> int:
    """The ladder's smallest rung that holds `capacity` rows."""
    rung = 1
    while rung < min(capacity, CHAIN_CAP_TOP):
        rung <<= 2
    return rung


class FusedFragmentExecutor(Executor):
    """One jitted dataflow step for a fused filter/project run."""

    def __init__(self, input_: Executor, stages: FusedStages):
        self.input = input_
        self.fused_stages = stages
        assert len(stages.in_schema) == len(input_.schema), \
            "fused stage chain planned against a different input"
        info = ExecutorInfo(
            stages.out_schema, [],
            f"FusedFragmentExecutor[{stages.describe()}]")
        super().__init__(info)
        self._step = None            # lazy: plan-only processes must
        self._ref = list(stages.ref_cols)   # not init a JAX backend
        self._cap = 0                # the rung the step runs at

    # MonitoredExecutor drains this at each barrier: per-LOGICAL-stage
    # row/chunk attribution inside the fused block
    def drain_stage_metrics(self):
        return self.fused_stages.drain_stage_metrics()

    @staticmethod
    def _pieces(chunk: StreamChunk):
        """The chunk itself, or its rows in runs of at most
        CHAIN_CAP_TOP where it is larger; a U-/U+ pair stays in one
        piece."""
        cap = chunk.capacity
        if cap <= CHAIN_CAP_TOP:
            yield chunk
            return
        ops = np.asarray(chunk.ops)
        vis = np.asarray(chunk.visibility)
        lo = 0
        while lo < cap:
            hi = min(lo + CHAIN_CAP_TOP, cap)
            if hi < cap and ops[hi] == int(Op.UPDATE_INSERT):
                hi -= 1
            yield StreamChunk(
                chunk.schema,
                [Column(c.data_type, np.asarray(c.values)[lo:hi],
                        None if c.validity is None
                        else np.asarray(c.validity)[lo:hi])
                 for c in chunk.columns], vis[lo:hi], ops[lo:hi])
            lo = hi

    def _run_step(self, chunk: StreamChunk):
        if self._step is None:
            self._step = build_chain_step(self.fused_stages)
        cap = chunk.capacity
        self._cap = rung = max(self._cap, chain_rung(cap))

        def padded(a, fill):
            if cap == rung:
                return a
            out = np.full(rung, fill, dtype=a.dtype)
            out[:cap] = a
            return out

        vals, oks = [], []
        for i in self._ref:
            c = chunk.columns[i]
            vals.append(padded(np.asarray(c.values), 0))
            oks.append(np.ones(rung, dtype=bool)
                       if c.validity is None
                       else padded(np.asarray(c.validity), False))
        # host passthrough columns bypass the trace, but the noop-pair
        # drop must still see their adjacent equality
        host_same = self.fused_stages.host_noop_eq(chunk)
        host_same = np.ones(rung, dtype=bool) if host_same is None \
            else padded(host_same, True)
        # one jitted chain step per chunk IS a device dispatch — count
        # it (absorbing a run into a keyed executor's epoch
        # dispatches must show up as a drop here)
        from risingwave_tpu.utils.metrics import STREAMING
        card = float(chunk.cardinality())
        STREAMING.device_dispatch.inc(1, executor=self.identity)
        STREAMING.rows_per_dispatch.observe(card,
                                            executor=self.identity)
        self.fused_stages.note_rows_in(int(card))
        from risingwave_tpu.stream.trace_ctx import dispatch_span
        with dispatch_span(self.identity, card):
            flat_vals, flat_ok, vis, ops, stage_rows = self._step(
                tuple(vals), tuple(oks),
                padded(np.asarray(chunk.visibility), False),
                padded(np.asarray(chunk.ops), int(Op.INSERT)),
                host_same)
        if cap == rung:
            return flat_vals, flat_ok, vis, ops, stage_rows
        # an absorbed hop lays its copies out one after the other,
        # each as long as the step's input (the validity of a column
        # that has none stays one copy long)

        def cut(a):
            return np.asarray(a).reshape(-1, rung)[:, :cap].reshape(-1)

        return (tuple(cut(a) for a in flat_vals),
                tuple(cut(a) for a in flat_ok), cut(vis), cut(ops),
                stage_rows)

    @staged("fused.chunk")
    def _run_chunk(self, msg: StreamChunk):
        """One chunk through the step: the output chunk, or None where
        no row is left (the empty-suppression contract, end to end:
        the sequential filter/project would have emitted nothing
        either, and an all-late chunk emits no watermark —
        WatermarkFilterExecutor parity). One `fused.chunk` stage of
        host_emit: the padding, the output chunk, and the blocking read
        of the step's result (`np.asarray` on device arrays, not
        `jaxtools.fetch`: the wait for the device is here, in no
        `device.wait.*`); the launch inside keeps its own phase."""
        fs = self.fused_stages
        out_schema = fs.out_schema
        # synthetic runtime columns (absorbed row_id_gen ids,
        # watermark thresholds) append host-side and enter the trace
        # as ordinary device inputs
        aug = fs.augment(msg)
        flat_vals, flat_ok, vis, ops, stage_rows = self._run_step(aug)
        vis = np.asarray(vis)
        fs.note_stage_rows(np.asarray(stage_rows), 1)
        if not vis.any():
            return None
        cols: List[Column] = []
        k = 0
        units = 1 if fs.hop is None else fs.hop.units
        for j, f in enumerate(out_schema):
            host_src = fs.host_out.get(j)
            if host_src is not None:
                src = msg.columns[host_src]
                if units > 1:
                    # absorbed hop: the trace expanded rows units× —
                    # host passthrough columns tile copy-major to stay
                    # positionally aligned
                    cols.append(Column(
                        f.data_type,
                        np.tile(np.asarray(src.values), units),
                        None if src.validity is None else
                        np.tile(np.asarray(src.validity), units)))
                else:
                    cols.append(Column(f.data_type, src.values,
                                       src.validity))
                continue
            okc = np.asarray(flat_ok[k])
            cols.append(Column(
                f.data_type, np.asarray(flat_vals[k]),
                None if okc.all() else okc))
            k += 1
        return StreamChunk(out_schema, cols, vis, np.asarray(ops))

    async def execute(self) -> AsyncIterator[Message]:
        fs = self.fused_stages
        wm_cols = set(fs.wm_time_cols())
        first_seen = False
        async for msg in self.input.execute():
            if is_chunk(msg):
                for piece in self._pieces(msg):
                    out = self._run_chunk(piece)
                    if out is None:
                        continue
                    yield out
                    # the absorbed watermark_filter announces its
                    # advanced watermark after every forwarded chunk,
                    # derived through the later projection stages
                    for wm in fs.post_chunk_watermarks():
                        for d in fs.derive_watermarks(wm):
                            yield d
            elif isinstance(msg, Watermark):
                if msg.col_idx in wm_cols:
                    # an absorbed watermark_filter owns this column —
                    # upstream watermarks on it are superseded
                    continue
                for wm in fs.derive_watermarks(msg):
                    yield wm
            elif is_barrier(msg):
                wms = fs.on_barrier(msg, first=not first_seen)
                first_seen = True
                yield msg
                for wm in wms:
                    for d in fs.derive_watermarks(wm):
                        yield d
            else:
                yield msg
