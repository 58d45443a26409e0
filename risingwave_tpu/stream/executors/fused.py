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

from risingwave_tpu.common.chunk import Column, StreamChunk
from risingwave_tpu.ops.fused import FusedStages, build_chain_step
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.message import (
    Message, Watermark, is_barrier, is_chunk,
)


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

    # MonitoredExecutor drains this at each barrier: per-LOGICAL-stage
    # row/chunk attribution inside the fused block
    def drain_stage_metrics(self):
        return self.fused_stages.drain_stage_metrics()

    def _run_step(self, chunk: StreamChunk):
        if self._step is None:
            self._step = build_chain_step(self.fused_stages)
        vals, oks = [], []
        for i in self._ref:
            c = chunk.columns[i]
            vals.append(np.asarray(c.values))
            oks.append(np.ones(chunk.capacity, dtype=bool)
                       if c.validity is None
                       else np.asarray(c.validity))
        # host passthrough columns bypass the trace, but the noop-pair
        # drop must still see their adjacent equality
        host_same = self.fused_stages.host_noop_eq(chunk)
        if host_same is None:
            host_same = np.ones(chunk.capacity, dtype=bool)
        # one jitted chain step per chunk IS a device dispatch — count
        # it (absorbing a run into a keyed executor's epoch
        # dispatches must show up as a drop here)
        from risingwave_tpu.utils.metrics import STREAMING
        card = float(chunk.cardinality())
        STREAMING.device_dispatch.inc(1, executor=self.identity)
        STREAMING.rows_per_dispatch.observe(card,
                                            executor=self.identity)
        from risingwave_tpu.stream.trace_ctx import dispatch_span
        with dispatch_span(self.identity, card):
            return self._step(tuple(vals), tuple(oks),
                              np.asarray(chunk.visibility),
                              np.asarray(chunk.ops), host_same)

    async def execute(self) -> AsyncIterator[Message]:
        fs = self.fused_stages
        out_schema = fs.out_schema
        wm_cols = set(fs.wm_time_cols())
        first_seen = False
        async for msg in self.input.execute():
            if is_chunk(msg):
                # synthetic runtime columns (absorbed row_id_gen ids,
                # watermark thresholds) append host-side and enter the
                # trace as ordinary device inputs
                aug = fs.augment(msg)
                flat_vals, flat_ok, vis, ops, stage_rows = \
                    self._run_step(aug)
                vis = np.asarray(vis)
                fs.note_stage_rows(np.asarray(stage_rows), 1)
                if not vis.any():
                    # empty-suppression contract, end to end: the
                    # sequential filter/project would have emitted
                    # nothing either (and an all-late chunk emits no
                    # watermark — WatermarkFilterExecutor parity)
                    continue
                cols: List[Column] = []
                k = 0
                units = 1 if fs.hop is None else fs.hop.units
                for j, f in enumerate(out_schema):
                    host_src = fs.host_out.get(j)
                    if host_src is not None:
                        src = msg.columns[host_src]
                        if units > 1:
                            # absorbed hop: the trace expanded rows
                            # units× — host passthrough columns tile
                            # copy-major to stay positionally aligned
                            cols.append(Column(
                                f.data_type,
                                np.tile(np.asarray(src.values), units),
                                None if src.validity is None else
                                np.tile(np.asarray(src.validity),
                                        units)))
                        else:
                            cols.append(Column(f.data_type, src.values,
                                               src.validity))
                        continue
                    okc = np.asarray(flat_ok[k])
                    cols.append(Column(
                        f.data_type, np.asarray(flat_vals[k]),
                        None if okc.all() else okc))
                    k += 1
                yield StreamChunk(out_schema, cols, vis,
                                  np.asarray(ops))
                # the absorbed watermark_filter announces its advanced
                # watermark after every forwarded chunk, derived
                # through the later projection stages
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
