"""SourceExecutor: turn a split reader into a barrier-respecting stream.

Reference parity: src/stream/src/executor/source/source_executor.rs:42 —
the barrier-select loop (:358-428): between two barriers the executor pulls
data from its reader; an arriving barrier always wins the select, so barrier
latency is bounded by one chunk's generation time. Split offsets persist in
a split-state table (source/state_table_handler.rs) at every checkpoint so
recovery resumes exactly where the committed epoch left off.

TPU notes: readers produce whole vectorized chunks (see connectors/), so the
per-message Python overhead is O(chunks), not O(rows). Pause/Resume
mutations gate generation without blocking barrier flow.
"""

from __future__ import annotations

import asyncio
import time
from typing import AsyncIterator, Optional, Protocol

from risingwave_tpu.common.chunk import StreamChunk
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.stream.exchange import ChannelClosed, Receiver
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.message import (
    Barrier, Message, SourceChangeSplitMutation, is_barrier,
)
from risingwave_tpu.utils.ledger import LEDGER, actor_clock
from risingwave_tpu.utils.metrics import STREAMING as _METRICS


class SplitReader(Protocol):
    """What a source executor needs from any connector reader."""

    split_id: str
    offset: int
    schema: object

    def seek(self, offset: int) -> None: ...

    def next_chunk(self) -> Optional[StreamChunk]: ...


SPLIT_STATE_PK = [0]  # split_id


class SourceExecutor(Executor):
    """Drives one split reader; barriers arrive via an injected channel."""

    def __init__(self, reader: SplitReader, barrier_rx: Receiver,
                 split_state: Optional[StateTable] = None,
                 actor_id: int = 0,
                 rate_limit_chunks_per_barrier: Optional[int] = None,
                 min_chunks_per_barrier: Optional[int] = None,
                 identity: str = "SourceExecutor",
                 freshness_key: Optional[str] = None):
        info = ExecutorInfo(reader.schema, [], identity)
        super().__init__(info)
        self.reader = reader
        self.barrier_rx = barrier_rx
        self.split_state = split_state
        self.actor_id = actor_id
        # freshness accounting key (stream/freshness.py): the SOURCE
        # name MVs register against (planner passes the catalog name;
        # hand-built pipelines default to the reader's split id), plus
        # the event-time column the ingest high-watermark reads
        from risingwave_tpu.stream.freshness import event_time_index
        self.freshness_key = freshness_key or getattr(
            reader, "split_id", identity)
        self._event_ts_idx = event_time_index(reader.schema)
        # optional throttle: max chunks generated per barrier interval
        # (FlowControlExecutor analog, keeps stepped runs deterministic)
        self.rate_limit = rate_limit_chunks_per_barrier
        # optional floor: generate this many chunks per epoch BEFORE
        # letting a waiting barrier win the select. The reference's
        # "barrier always wins" rule assumes barriers arrive on a wall
        # interval; under back-to-back injection (stepped driving) it
        # starves epochs down to one chunk. The floor restores real
        # epoch sizes deterministically. None = reference behavior.
        self.min_chunks = min_chunks_per_barrier
        self.paused = False
        # cumulative wall time parked on the barrier channel with
        # nothing to generate (on the actors' clock: less the loop time
        # a checkpoint build, commit or compaction held meanwhile). The monitor subtracts this from the
        # source's exclusive busy time: a source waiting out a slow
        # downstream epoch is IDLE, and counting the park as busy
        # would crown every source the straggler (trace diagnosis)
        self.idle_wait_s = 0.0

    # -- split-state persistence (state_table_handler.rs analog) --------
    def _recover_offset(self) -> None:
        if self.split_state is None:
            return
        splits = getattr(self.reader, "splits", None)
        if splits is not None:
            # multi-split reader (split rebalancing, ISSUE 15): one
            # durable row PER split — after a rescale moved this
            # split's row into our namespace, the byte offset resumes
            # exactly where the previous owner checkpointed
            for split_id, _off in splits():
                row = self.split_state.get_row((split_id,))
                if row is not None:
                    self.reader.seek_split(split_id, row[1])
            return
        row = self.split_state.get_row((self.reader.split_id,))
        if row is not None:
            self.reader.seek(row[1])

    def _persist_one(self, split_id: str, offset: int) -> None:
        row = (split_id, offset)
        old = self.split_state.get_row((split_id,))
        if old is None:
            self.split_state.insert(row)
        elif tuple(old) != row:
            self.split_state.update(old, row)

    def _persist_offset(self) -> None:
        if self.split_state is None:
            return
        splits = getattr(self.reader, "splits", None)
        if splits is not None:
            for split_id, off in splits():
                self._persist_one(split_id, off)
            return
        self._persist_one(self.reader.split_id, self.reader.offset)

    def _handle_barrier(self, barrier: Barrier) -> None:
        if barrier.is_pause():
            self.paused = True
        elif barrier.is_resume():
            self.paused = False
        m = barrier.mutation
        if isinstance(m, SourceChangeSplitMutation) and \
                self.actor_id in m.assignments:
            # v0: single split per actor; reassignment seeks it
            pass
        self._persist_offset()
        if self.split_state is not None:
            self.split_state.commit(barrier.epoch)
        # epoch frontier: everything ingested so far precedes this
        # barrier — the hwm recorded here IS the MV-visible event
        # frontier once materialize passes the same barrier
        from risingwave_tpu.stream.freshness import FRESHNESS
        FRESHNESS.note_source_barrier(self.freshness_key,
                                      barrier.epoch.curr.value)

    async def execute(self) -> AsyncIterator[Message]:
        # (barrier_rx teardown lives in Actor.run's close_receivers —
        # the owning actor's exit point, which runs deterministically
        # instead of waiting on async-generator finalization)
        # protocol: first message is the init barrier (source_executor.rs
        # waits for the first barrier before opening the reader)
        t0 = actor_clock()
        first = await self.barrier_rx.recv()
        self.idle_wait_s += actor_clock() - t0
        assert is_barrier(first), f"source got {first!r} before init barrier"
        if self.split_state is not None:
            self.split_state.init_epoch(first.epoch)
        self._recover_offset()
        from risingwave_tpu.stream.freshness import FRESHNESS
        FRESHNESS.note_source_barrier(self.freshness_key,
                                      first.epoch.curr.value)
        self.paused = first.is_pause()
        yield first
        if first.is_stop(self.actor_id):
            return

        exhausted = False
        idle = False
        chunks_this_epoch = 0
        while True:
            # barrier wins the select — except for the FIRST chunk of an
            # epoch, which is generated before looking at the channel.
            # Without that progress guarantee, back-to-back barrier
            # injection (collect → inject with no interval, the stepped
            # driving pattern) can starve the stream forever: every
            # try_recv finds the next barrier already waiting.
            barrier: Optional[Barrier] = None
            can_generate = not (self.paused or exhausted or idle or (
                self.rate_limit is not None
                and chunks_this_epoch >= self.rate_limit))
            if not can_generate:
                t0 = actor_clock()
                try:
                    barrier = await self.barrier_rx.recv()  # blocking
                except ChannelClosed:
                    return
                finally:
                    self.idle_wait_s += actor_clock() - t0
            elif chunks_this_epoch > 0 and (
                    self.min_chunks is None
                    or chunks_this_epoch >= self.min_chunks):
                try:
                    barrier = self.barrier_rx.try_recv()
                except ChannelClosed:
                    return
            if barrier is not None:
                assert is_barrier(barrier)
                self._handle_barrier(barrier)
                chunks_this_epoch = 0
                idle = False            # log sources re-poll per epoch
                yield barrier
                if barrier.is_stop(self.actor_id):
                    return
                continue
            # a scope, so that generating is on the profiler's clock too
            # (what the connector's own scopes leave is the same phase)
            with LEDGER.phase("host_ingest"):
                chunk = self.reader.next_chunk()
            if chunk is None:
                if getattr(self.reader, "unbounded", False):
                    # log-style source with no complete records yet:
                    # park on the barrier channel (not a busy-poll)
                    idle = True
                else:
                    exhausted = True
                continue
            chunks_this_epoch += 1
            _METRICS.source_rows.inc(chunk.cardinality(),
                                     source=self.reader.split_id)
            from risingwave_tpu.stream import freshness as _fresh
            # ingest high-watermark: one vectorized max over the
            # chunk's event-time column (arrival-clock fallback
            # when the schema has none)
            _fresh.FRESHNESS.note_ingest(
                self.freshness_key,
                _fresh.chunk_event_hwm(chunk, self._event_ts_idx))
            yield chunk
            # yield to the event loop so the barrier injector can run
            await asyncio.sleep(0)
