"""Utilization tricolor tests (ISSUE 14): exclusive-time/idle
subtraction with nested idle-exposing children, sender-side credit
park accounting, and the busy+backpressure+idle ≤ 1 identity."""

import asyncio
import time

import pytest

from risingwave_tpu.common.chunk import StreamChunk
from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.common.types import DataType, Field, Schema
from risingwave_tpu.stream.exchange import (
    channel, note_backpressure, pop_park_cell, push_park_cell,
    set_actor_meter,
)
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.merge import barrier_align_n
from risingwave_tpu.stream.message import (
    Barrier, BarrierKind, is_barrier, is_chunk,
)
from risingwave_tpu.stream.monitor import (
    TOPOLOGY, UTILIZATION, install_monitoring,
)
from risingwave_tpu.utils.metrics import STREAMING

SCH = Schema([Field("a", DataType.INT64)])


def _barrier(e: int) -> Barrier:
    return Barrier(EpochPair(Epoch(e + 1), Epoch(e)),
                   BarrierKind.BARRIER)


def _chunk(n: int = 4) -> StreamChunk:
    return StreamChunk.from_pydict(SCH, {"a": list(range(n))})


class IdleFeed(Executor):
    """Source/RemoteInput-shaped node: parks (accruing idle_wait_s)
    before each scripted message — the input-starved shape whose park
    must NOT read as busy."""

    def __init__(self, msgs, idle_s: float, ident: str):
        super().__init__(ExecutorInfo(SCH, [], ident))
        self.msgs = list(msgs)
        self.idle_s = idle_s
        self.idle_wait_s = 0.0

    async def execute(self):
        for msg in self.msgs:
            t0 = time.monotonic()
            await asyncio.sleep(self.idle_s)
            self.idle_wait_s += time.monotonic() - t0
            yield msg


class BusyPass(Executor):
    """Burns host CPU per chunk — the chain's true straggler."""

    def __init__(self, input_, busy_s: float):
        super().__init__(ExecutorInfo(SCH, [], "BusyPass"))
        self.input = input_
        self.busy_s = busy_s

    async def execute(self):
        async for msg in self.input.execute():
            if is_chunk(msg):
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < self.busy_s:
                    pass
            yield msg


class CheapPass(Executor):
    def __init__(self, input_):
        super().__init__(ExecutorInfo(SCH, [], "CheapPass"))
        self.input = input_

    async def execute(self):
        async for msg in self.input.execute():
            yield msg


class AlignTwo(Executor):
    """Minimal 2-input fan-in over barrier_align_n (the join shape)."""

    def __init__(self, left, right):
        super().__init__(ExecutorInfo(SCH, [], "AlignTwo"))
        self.inputs = [left, right]

    async def execute(self):
        async for tag, msg in barrier_align_n(
                [i.execute() for i in self.inputs]):
            yield msg


async def _drive(consumer, n_barriers: int) -> None:
    seen = 0
    async for msg in consumer.execute():
        if is_barrier(msg):
            seen += 1
            if seen >= n_barriers:
                return


def test_nested_idle_subtraction_source_and_remote_shape():
    """The PR-7 attribution path, directly: a chain whose SOURCE and a
    RemoteInput-shaped sibling both expose idle_wait_s, under a busy
    middle node and a cheap root. Exclusive busy must land on the busy
    node; the idle feeds must read idle, not busy; every triple sums
    to ≤ 1."""
    script = [_barrier(0), _chunk(), _barrier(2), _chunk(),
              _barrier(4), _chunk(), _barrier(6)]
    left = IdleFeed(script, idle_s=0.03, ident="MockSource")
    right = IdleFeed(list(script), idle_s=0.03,
                     ident="RemoteInput(1->2)")
    chain = CheapPass(BusyPass(AlignTwo(left, right), busy_s=0.05))
    consumer = install_monitoring(chain, fragment="tri-nested",
                                  actor_id=41)
    asyncio.run(_drive(consumer, 4))

    rows = {(node, ex): (busy, bp, idle)
            for a, frag, node, ex, _e, _i, busy, bp, idle
            in UTILIZATION.rows() if frag == "tri-nested"}
    assert rows, "no utilization rows published"
    # node ids: 0 CheapPass, 1 BusyPass, 2 AlignTwo, 3/4 the feeds
    busy_node = rows[(1, "BusyPass")]
    assert busy_node[0] > 0.3, busy_node
    for (node, ex), (busy, bp, idle) in rows.items():
        assert busy + bp + idle <= 1.0 + UTILIZATION.EPSILON, \
            (node, ex, busy, bp, idle)
        if ex in ("MockSource", "RemoteInput(1->2)"):
            assert idle > 0.2, (ex, busy, bp, idle)
            assert busy < idle, (ex, busy, bp, idle)
    # the cheap root's EXCLUSIVE busy excludes its whole subtree
    assert rows[(0, "CheapPass")][0] < 0.2, rows[(0, "CheapPass")]
    # cumulative counters agree: the busy node out-earns the feeds
    busy_mid = STREAMING.executor_busy.get(
        fragment="tri-nested", actor="41", executor="BusyPass",
        node="1")
    busy_src = STREAMING.executor_busy.get(
        fragment="tri-nested", actor="41", executor="MockSource",
        node="3")
    assert busy_mid > busy_src
    assert not UTILIZATION.gate_violations()
    TOPOLOGY.drop_actor(41)


def test_sender_park_charges_channel_and_context():
    """A sender blocked for credits records the park (a) in the
    per-channel counter and (b) in the innermost park cell when one is
    pushed, else the actor meter."""
    async def run():
        tx, rx = channel(chunk_permits=4, max_chunk_cost=4,
                         edge="tri:park")
        before = STREAMING.backpressure_wait.get(channel="tri:park")
        meter = [0.0]
        mtok = set_actor_meter(meter)

        async def consume_later():
            await asyncio.sleep(0.08)
            while True:
                try:
                    await asyncio.wait_for(rx.recv(), timeout=0.2)
                except (asyncio.TimeoutError, Exception):
                    return

        task = asyncio.ensure_future(consume_later())
        await tx.send(_chunk(4))          # fills the budget, no park
        await tx.send(_chunk(4))          # parks until the consumer
        meter_after_send = meter[0]
        # in-pull sends charge the pushed cell INSTEAD of the meter
        cell = [0.0]
        ptok = push_park_cell(cell)
        await tx.send(_chunk(4))
        pop_park_cell(ptok)
        set_actor_meter(None)
        await task
        parked = STREAMING.backpressure_wait.get(
            channel="tri:park") - before
        return meter_after_send, cell[0], parked

    meter_s, cell_s, parked = asyncio.run(run())
    assert meter_s > 0.04, meter_s          # the actor-meter park
    assert cell_s > 0.0, cell_s             # the in-pull park
    assert parked >= meter_s + cell_s - 1e-6


def test_actor_dispatch_park_lands_in_root_backpressure():
    """Full actor shape: the chain is fast, but its dispatcher feeds a
    credit-starved downstream — the park must surface as the ROOT
    node's backpressure share (and be absent from busy), so the
    straggler story names the slow consumer, not this actor."""
    from risingwave_tpu.meta.barrier import BarrierLoop
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import Actor, LocalBarrierManager
    from risingwave_tpu.stream.dispatch import Output, SimpleDispatcher
    from risingwave_tpu.stream.executors.test_utils import MockSource

    async def run():
        store = MemoryStateStore()
        local = LocalBarrierManager()
        tx, src = MockSource.channel(SCH)
        local.register_sender(9, tx)
        consumer = install_monitoring(CheapPass(src),
                                      fragment="tri-actor",
                                      actor_id=9)
        out_tx, out_rx = channel(chunk_permits=4, max_chunk_cost=4,
                                 barrier_permits=64,
                                 edge="tri:actor-out")
        actor = Actor(9, consumer,
                      dispatchers=[SimpleDispatcher(
                          Output(10, out_tx))],
                      barrier_manager=local, fragment="tri-actor")
        local.set_expected_actors([9])
        loop = BarrierLoop(local, store)
        task = actor.spawn()

        async def slow_drain():
            while True:
                try:
                    await asyncio.wait_for(out_rx.recv(), timeout=1.0)
                except asyncio.TimeoutError:
                    return
                await asyncio.sleep(0.02)

        drain = asyncio.ensure_future(slow_drain())
        await loop.inject_and_collect(force_checkpoint=True)
        for e in range(3):
            # 3 full chunks per epoch >> the 4-permit budget: the
            # dispatch send must park on the drainer's cadence
            for _ in range(3):
                await src._tx.send(_chunk(4))
            await loop.inject_and_collect(force_checkpoint=True)
        row = UTILIZATION.get("tri-actor", 9, 0)
        from risingwave_tpu.stream.message import StopMutation
        await loop.inject_and_collect(
            mutation=StopMutation(frozenset({9})))
        await task
        drain.cancel()
        assert actor.failure is None
        return row

    row = asyncio.run(run())
    assert row is not None
    _ex, _e, _i, busy, bp, idle = row
    assert bp > 0.1, (busy, bp, idle)
    assert busy + bp + idle <= 1.0 + UTILIZATION.EPSILON
    parked = STREAMING.backpressure_wait.get(channel="tri:actor-out")
    assert parked > 0.0


def test_metric_families_sorted_with_help():
    """ctl metrics exposition: families render in sorted order and
    every ISSUE-14 family carries a HELP line, so round-over-round
    dumps diff cleanly."""
    from risingwave_tpu.utils.metrics import GLOBAL
    # touch the new families so they render at least one series
    STREAMING.backpressure_wait.inc(0.001, channel="helptest")
    STREAMING.executor_utilization.set(
        0.5, state="busy", fragment="helptest", actor="1",
        executor="X", node="0")
    STREAMING.mv_freshness_lag.set(0.1, mv="helptest")
    STREAMING.mv_freshness_wall_lag.set(0.1, mv="helptest")
    STREAMING.bottleneck_streak.set(1, domain="helptest", operator="X")
    text = GLOBAL.render()
    fams = [ln.split()[2] for ln in text.splitlines()
            if ln.startswith("# TYPE ")]
    assert fams == sorted(fams), "families must render sorted"
    assert len(fams) == len(set(fams))
    for fam in ("stream_backpressure_wait_seconds",
                "stream_executor_utilization_ratio",
                "stream_mv_freshness_lag_seconds",
                "stream_mv_freshness_wall_lag_seconds",
                "stream_bottleneck_streak"):
        assert f"# HELP {fam} " in text, fam
        assert f"# TYPE {fam} " in text, fam
    # cleanup the touched series
    STREAMING.executor_utilization.remove(
        state="busy", fragment="helptest", actor="1", executor="X",
        node="0")
    STREAMING.mv_freshness_lag.remove(mv="helptest")
    STREAMING.mv_freshness_wall_lag.remove(mv="helptest")
    STREAMING.bottleneck_streak.remove(domain="helptest", operator="X")


def test_note_backpressure_without_context_is_safe():
    note_backpressure(0.01, channel=None)
    note_backpressure(0.0, channel="zero")
    assert STREAMING.backpressure_wait.get(channel="zero") == 0.0
