"""WatermarkFilterExecutor: generate event-time watermarks, drop late rows.

Reference parity: src/stream/src/executor/watermark_filter.rs:48 — the
watermark is max(event_time) - delay, monotonically advanced; rows with
event_time < current watermark are filtered out; the watermark value is
persisted in a state table at checkpoints and restored on recovery
(reference stores one row per vnode; a single-shard executor persists
one row — the vnode split returns with the dispatch layer).

TPU notes: the max() reduction and the lateness mask are one fused
vectorized pass over the padded chunk.
"""

from __future__ import annotations

from typing import AsyncIterator, Optional

import numpy as np

from risingwave_tpu.common.chunk import StreamChunk
from risingwave_tpu.common.types import DataType, Field, Interval, Schema
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.message import (
    Message, Watermark, is_barrier, is_chunk,
)
from risingwave_tpu.utils.metrics import STREAMING as _METRICS

WATERMARK_STATE_SCHEMA = Schema([Field("pk", DataType.INT16),
                                 Field("watermark", DataType.TIMESTAMP)])


class WatermarkRuntime:
    """The watermark value + its persistence alone — the runtime of a
    `watermark_filter` stage absorbed into a fused run (ops/fused.py).
    WatermarkFilterExecutor IS one (plus the executor loop); worker-
    side IR rebuilds construct the bare runtime."""

    def __init__(self, state: Optional[StateTable] = None):
        self.state = state
        self.current: Optional[int] = None

    def _persist(self) -> None:
        if self.state is None or self.current is None:
            return
        old = self.state.get_row((0,))
        row = (0, int(self.current))
        if old is None:
            self.state.insert(row)
        elif tuple(old) != row:
            self.state.update(tuple(old), row)


class WatermarkFilterExecutor(WatermarkRuntime, Executor):
    """Event-time watermark generator + late-row filter."""

    def __init__(self, input_: Executor, time_col: int, delay: Interval,
                 state: Optional[StateTable] = None, source: str = ""):
        Executor.__init__(self, ExecutorInfo(
            input_.schema, list(input_.pk_indices),
            "WatermarkFilterExecutor"))
        WatermarkRuntime.__init__(self, state)
        self.input = input_
        self.time_col = time_col
        self.delay = delay.usecs
        # rows dropped as late, by the source the planner names
        self._late_rows = _METRICS.watermark_late_rows.labeled(
            source=source or "?")

    async def execute(self) -> AsyncIterator[Message]:
        first_seen = False
        async for msg in self.input.execute():
            if is_barrier(msg):
                if not first_seen:
                    first_seen = True
                    if self.state is not None:
                        self.state.init_epoch(msg.epoch)
                        row = self.state.get_row((0,))
                        if row is not None:
                            self.current = int(row[1])
                    yield msg
                    if self.current is not None:
                        yield Watermark(self.time_col, DataType.TIMESTAMP,
                                        self.current)
                    continue
                self._persist()
                if self.state is not None:
                    self.state.commit(msg.epoch)
                yield msg
            elif is_chunk(msg):
                out = self._apply(msg)
                if out is not None:
                    yield out
                    wm = self.current
                    if wm is not None:
                        yield Watermark(self.time_col, DataType.TIMESTAMP,
                                        wm)
            elif isinstance(msg, Watermark):
                # upstream watermarks on other columns pass through
                if msg.col_idx != self.time_col:
                    yield msg

    def _apply(self, chunk: StreamChunk) -> Optional[StreamChunk]:
        c = chunk.columns[self.time_col]
        ts = np.asarray(c.values).astype(np.int64)
        vis = np.asarray(chunk.visibility)
        ok = vis if c.validity is None else \
            vis & np.asarray(c.validity)
        # a row is late only relative to the watermark already EMITTED
        # (before this chunk) — filtering against the watermark derived
        # from this very chunk's max would drop every in-chunk row that
        # precedes the max, i.e. nearly everything under a small delay
        prev_wm = self.current
        if ok.any():
            mx = int(ts[ok].max()) - self.delay
            if self.current is None or mx > self.current:
                self.current = mx
        if prev_wm is None:
            return chunk
        late = ok & (ts < prev_wm)
        self._late_rows.inc(float(late.sum()))
        if not late.any():
            return chunk
        new_vis = vis & ~late
        if not new_vis.any():
            return None
        return StreamChunk(chunk.schema, chunk.columns, new_vis, chunk.ops)
