"""Exchange channels: bounded, permit-based message passing between actors.

Reference parity: src/stream/src/executor/exchange/permit.rs:35,75,111,152 —
bounded channels with *separate* budgets for data chunks (cost = row
cardinality, so big chunks consume proportional credit) and barriers (their
own small budget so backpressure on data never blocks checkpoints for long).

TPU re-design: asyncio is the tokio analog. The same Sender/Receiver pair is
the local exchange; a remote exchange (multi-host DCN) would put a serializer
behind the same interface — collectives over ICI replace hash-exchange
*within* a mesh (see parallel/), so these channels only carry host-edge
traffic: source ingestion, cross-fragment pipes, sink output.
"""

from __future__ import annotations

import asyncio
import time
from contextvars import ContextVar
from typing import AsyncIterator, List, Optional, Tuple

from risingwave_tpu.common.chunk import StreamChunk
from risingwave_tpu.stream.message import Barrier, Message, Watermark
from risingwave_tpu.utils.ledger import actor_clock
from risingwave_tpu.utils.metrics import STREAMING as _METRICS


class ChannelClosed(Exception):
    """Send on a channel whose receiver is gone, or recv after close+drain."""


# -- sender-side backpressure accounting (ISSUE 14) -----------------------
# Credit park time used to disappear into whoever awaited the send: a
# straggler diagnosis then blames the VICTIM of a slow consumer. Every
# park is now (a) metered per channel (stream_backpressure_wait_seconds)
# and (b) charged to the context's accumulator so the utilization
# tricolor can subtract it from busy. Two ContextVar scopes:
#   _PARK  — innermost MonitoredExecutor pull (stream/monitor.py pushes
#            its cell around each inner __anext__, exactly like the
#            phase-ledger cells), for sends that happen INSIDE a pull;
#   _METER — the owning actor's task-scoped meter (stream/actor.py sets
#            it for the whole run), for dispatch sends between pulls.
# ContextVars are asyncio-task aware, so interleaved actors never
# cross-charge; merge pumps inherit their parent actor's context.
_PARK: ContextVar[Optional[List[float]]] = ContextVar(
    "exchange_park_cell", default=None)
_METER: ContextVar[Optional[List[float]]] = ContextVar(
    "exchange_actor_meter", default=None)


def set_actor_meter(meter: Optional[List[float]]):
    """Bind the actor-task backpressure meter (stream/actor.py)."""
    return _METER.set(meter)


def current_actor_meter() -> Optional[List[float]]:
    """The running actor task's meter (the monitor's root wrapper
    drains it at each barrier flush)."""
    return _METER.get()


def push_park_cell(cell: List[float]):
    return _PARK.set(cell)


def pop_park_cell(token) -> None:
    _PARK.reset(token)


def note_backpressure(seconds: float,
                      channel: Optional[str] = None) -> None:
    """Record one sender park: per-channel Prometheus counter plus the
    context's tricolor accumulator (shared with stream/remote.py)."""
    if seconds <= 0:
        return
    if channel:
        _METRICS.backpressure_wait.inc(seconds, channel=channel)
    cell = _PARK.get()
    if cell is not None:
        cell[0] += seconds
        return
    meter = _METER.get()
    if meter is not None:
        meter[0] += seconds


class _Shared:
    def __init__(self, chunk_permits: int, barrier_permits: int,
                 max_chunk_cost: int, edge: Optional[str] = None):
        self.queue: asyncio.Queue = asyncio.Queue()
        self.chunk_permits = chunk_permits
        self.barrier_permits = barrier_permits
        self.max_chunk_cost = max_chunk_cost
        self.cond = asyncio.Condition()
        self.closed = False
        # labeled edges feed the back-pressure/queue-depth series
        # (stream_exchange_backpressure analog); anonymous channels
        # (unit-test plumbing) skip the metric path entirely. Series
        # handles cache the label key — sends are per-message.
        self.edge = edge
        if edge:
            self.m_backpressure = \
                _METRICS.exchange_backpressure.labeled(edge=edge)
            self.m_sends = _METRICS.exchange_send_count.labeled(
                edge=edge)
            self.m_depth = _METRICS.exchange_queue_depth.labeled(
                edge=edge)


def _chunk_cost(shared: _Shared, chunk: StreamChunk) -> int:
    # Compacted/coalesced chunks KNOW their visible cardinality
    # (dense_rows, no host sum) — charge the true row count so a
    # post-dispatch sliver no longer burns capacity-x credit and
    # stalls its upstream early. For unestablished chunks cardinality()
    # would be a host sync per send; capacity is free and is the true
    # memory footprint of the padded arrays, so those keep paying
    # capacity.
    cost = chunk.dense_rows if chunk.dense_rows is not None \
        else chunk.capacity
    return max(1, min(cost, shared.max_chunk_cost))


class Sender:
    def __init__(self, shared: _Shared):
        self._s = shared

    async def send(self, msg: Message) -> None:
        s = self._s
        t0 = time.perf_counter() if s.edge else 0.0
        if isinstance(msg, StreamChunk):
            cost = _chunk_cost(s, msg)
            park0 = 0.0
            async with s.cond:
                if not (s.closed or s.chunk_permits >= cost):
                    # the sender is about to PARK for credits: that
                    # wall time is backpressure, not processing — meter
                    # it per channel and charge the context's tricolor
                    # accumulator (the fast path pays only this branch)
                    park0 = actor_clock()
                    await s.cond.wait_for(
                        lambda: s.closed or s.chunk_permits >= cost)
                if s.closed:
                    if park0:
                        note_backpressure(actor_clock() - park0,
                                          s.edge)
                    raise ChannelClosed
                s.chunk_permits -= cost
            if park0:
                note_backpressure(actor_clock() - park0, s.edge)
            s.queue.put_nowait(("chunk", cost, msg))
        elif isinstance(msg, Barrier):
            park0 = 0.0
            async with s.cond:
                if not (s.closed or s.barrier_permits >= 1):
                    park0 = actor_clock()
                    await s.cond.wait_for(
                        lambda: s.closed or s.barrier_permits >= 1)
                if s.closed:
                    if park0:
                        note_backpressure(actor_clock() - park0,
                                          s.edge)
                    raise ChannelClosed
                s.barrier_permits -= 1
            if park0:
                note_backpressure(actor_clock() - park0, s.edge)
            s.queue.put_nowait(("barrier", 1, msg))
        else:  # watermarks are control-plane: unmetered
            if s.closed:
                raise ChannelClosed
            s.queue.put_nowait(("watermark", 0, msg))
        if s.edge:
            # permit-acquisition time IS the back-pressure signal: a
            # full downstream queue shows up as senders parked here
            s.m_backpressure.inc(time.perf_counter() - t0)
            s.m_sends.inc()
            s.m_depth.set(s.queue.qsize())

    def close(self) -> None:
        self._s.queue.put_nowait(("eos", 0, None))


class Receiver:
    def __init__(self, shared: _Shared):
        self._s = shared

    async def recv(self) -> Message:
        s = self._s
        kind, cost, msg = await s.queue.get()
        if kind == "eos":
            if s.edge:     # the edge is dead: no stale gauge series
                _METRICS.exchange_queue_depth.remove(edge=s.edge)
            raise ChannelClosed
        if s.edge and not s.closed:
            s.m_depth.set(s.queue.qsize())
        if cost:
            async with s.cond:
                if kind == "chunk":
                    s.chunk_permits += cost
                else:
                    s.barrier_permits += 1
                s.cond.notify_all()
        return msg

    def try_recv(self) -> Optional[Message]:
        """Non-blocking recv: None if empty (source barrier-select path)."""
        s = self._s
        try:
            kind, cost, msg = s.queue.get_nowait()
        except asyncio.QueueEmpty:
            return None
        if kind == "eos":
            if s.edge:
                _METRICS.exchange_queue_depth.remove(edge=s.edge)
            raise ChannelClosed
        if cost:
            # return permits without blocking: schedule the notify
            if kind == "chunk":
                s.chunk_permits += cost
            else:
                s.barrier_permits += 1
            try:
                loop = asyncio.get_running_loop()
                loop.create_task(self._notify())
            except RuntimeError:
                pass
        return msg

    async def _notify(self) -> None:
        async with self._s.cond:
            self._s.cond.notify_all()

    def close(self) -> None:
        """Receiver drop: unblock any sender waiting for permits."""
        s = self._s

        async def _close():
            async with s.cond:
                s.closed = True
                s.cond.notify_all()

        s.closed = True
        if s.edge:
            # stale gauge series would keep reporting a dead edge
            _METRICS.exchange_queue_depth.remove(edge=s.edge)
        try:
            loop = asyncio.get_running_loop()
            loop.create_task(_close())
        except RuntimeError:
            pass  # no loop: flag alone is enough

    async def __aiter__(self) -> AsyncIterator[Message]:
        while True:
            try:
                yield await self.recv()
            except ChannelClosed:
                return


def channel(chunk_permits: int = 32768, barrier_permits: int = 4,
            max_chunk_cost: Optional[int] = None,
            edge: Optional[str] = None) -> Tuple[Sender, Receiver]:
    """Bounded exchange channel (permit.rs:35 `channel` analog).

    max_chunk_cost caps a single chunk's cost below the full budget so one
    oversized chunk can always eventually pass. `edge` names the channel
    in the exchange metric families (back-pressure time, send count,
    queue depth); unnamed channels are unmetered.
    """
    if max_chunk_cost is None:
        max_chunk_cost = max(1, chunk_permits // 2)
    shared = _Shared(chunk_permits, barrier_permits, max_chunk_cost,
                     edge=edge)
    return Sender(shared), Receiver(shared)


def channel_for_test(edge: Optional[str] = None
                     ) -> Tuple[Sender, Receiver]:
    return channel(chunk_permits=1 << 20, barrier_permits=64, edge=edge)
