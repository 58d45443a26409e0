"""HopWindowExecutor: expand rows into their sliding (hop) windows.

Reference parity: src/stream/src/executor/hop_window.rs:91 — with
`units = window_size / window_slide` (must divide exactly), each input
chunk yields `units` output chunks; copy i carries the i-th covering
window's [window_start, window_end]. Window starts covering ts are
  floor(ts / slide) * slide - i * slide,   i in 0..units-1
(one tumble by `slide`, then shifted copies) — all vectorized.

Rows whose timestamp is NULL are dropped (reference behavior: the window
expression evaluates to NULL and downstream grouping would discard them;
we mask them out up front).
"""

from __future__ import annotations

from typing import AsyncIterator, List

import numpy as np

from risingwave_tpu.common.chunk import Column, StreamChunk
from risingwave_tpu.common.types import DataType, Field, Interval, Schema
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.message import Message, Watermark, is_chunk


class HopWindowExecutor(Executor):
    """Sliding-window expansion (hop_window.rs:91 analog)."""

    def __init__(self, input_: Executor, time_col: int,
                 window_slide: Interval, window_size: Interval,
                 pk_indices: List[int] = ()):
        slide, size = window_slide.usecs, window_size.usecs
        if slide <= 0 or size % slide != 0:
            raise ValueError(
                f"window_size {size}us not divisible by slide {slide}us")
        self.units = size // slide
        self.slide = slide
        self.size = size
        self.time_col = time_col
        fields = [Field(f.name, f.data_type) for f in input_.schema]
        fields.append(Field("window_start", DataType.TIMESTAMP))
        fields.append(Field("window_end", DataType.TIMESTAMP))
        super().__init__(ExecutorInfo(Schema(fields), list(pk_indices),
                                      "HopWindowExecutor"))
        self.input = input_
        # the planner's mark: the aggregate it put over this HOP
        # (`t<state table id>`), the expansion's name in the books
        self.books_table = ""

    async def execute(self) -> AsyncIterator[Message]:
        ws_idx = len(self.input.schema)
        async for msg in self.input.execute():
            if isinstance(msg, Watermark):
                if msg.col_idx == self.time_col:
                    # a bound on ts is a bound on the last window's start
                    base = (int(msg.value) // self.slide) * self.slide
                    yield Watermark(ws_idx, DataType.TIMESTAMP,
                                    base - (self.units - 1) * self.slide)
                continue
            if not is_chunk(msg):
                yield msg
                continue
            c = msg.columns[self.time_col]
            ts = np.asarray(c.values)
            vis = np.asarray(msg.visibility)
            if c.validity is not None:
                vis = vis & np.asarray(c.validity)
            base = (ts.astype(np.int64) // self.slide) * self.slide
            if self.books_table:
                from risingwave_tpu.utils.metrics import note_hop_rows
                rows = int(vis.sum())
                note_hop_rows(self.books_table, rows, rows * self.units)
            # Batched expansion (ISSUE 12): pow2 GROUPS of copy-major
            # replicas — ⌈log2⌉ chunks per input chunk instead of
            # `units` (5 windows → one 4×-copy chunk + one 1×-copy
            # chunk), so the downstream spine (exchange frames,
            # coalescer, monitor, join ingest) pays ~2 chunks of
            # overhead instead of 5 while every emitted capacity stays
            # a power of two — kernel backlogs (BATCH_ROWS slabs) keep
            # packing tight, which a single `units`×-cap chunk broke.
            # Copy-major tiling keeps U-/U+ pairs adjacent inside every
            # copy, group boundaries land exactly on copy boundaries,
            # and a well-formed chunk never ends with a dangling U-,
            # so pair scans never marry rows across copies.
            host_cols = [(np.asarray(c.values),
                          None if c.validity is None
                          else np.asarray(c.validity))
                         for c in msg.columns]
            ops = np.asarray(msg.ops)
            i = 0
            units = self.units
            while i < units:
                g = 1 << ((units - i).bit_length() - 1)
                starts = base - i * self.slide if g == 1 else \
                    np.concatenate([base - (i + j) * self.slide
                                    for j in range(g)])
                cols = [Column(c.data_type,
                               vals if g == 1 else np.tile(vals, g),
                               ok if ok is None or g == 1
                               else np.tile(ok, g))
                        for c, (vals, ok) in zip(msg.columns,
                                                 host_cols)]
                cols.append(Column(DataType.TIMESTAMP, starts, None))
                cols.append(Column(DataType.TIMESTAMP,
                                   starts + self.size, None))
                yield StreamChunk(
                    self.schema, cols,
                    vis if g == 1 else np.tile(vis, g),
                    ops if g == 1 else np.tile(ops, g))
                i += g
