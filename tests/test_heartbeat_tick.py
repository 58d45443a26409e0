"""ISSUE 32: the serving heartbeat ticks on a clock.

`Frontend.run_heartbeat` injects a barrier every `interval_s` measured
from inject to inject (meta/barrier.py `HeartbeatTick`), lets the sealed
checkpoint commit before the next inject, passes through one suspension
outside the barrier lock between rounds, and files its waits on the
history row of the epoch they preceded.

Nothing here asserts a wall-clock duration (ROADMAP D12: no test that
passes alone and fails by load). The cases about *when* a beat comes run
the tick on a `VirtualClock`, where a round "takes" the virtual seconds
the test gives it and every stamp is exact; the cases about *order*
(commit before inject, a cancel outside a round, who gets the lock) run
on the wall clock and compare sequences only.
"""

import asyncio

import pytest

from risingwave_tpu.frontend.session import Frontend
from risingwave_tpu.meta.barrier import HeartbeatTick, VirtualClock
from risingwave_tpu.storage.hummock import HummockLite
from risingwave_tpu.storage.object_store import (
    DelayedObjectStore, MemObjectStore,
)
from risingwave_tpu.utils import spans as spans_mod
from risingwave_tpu.utils.ledger import LEDGER
from risingwave_tpu.utils.metrics import HISTORY

INTERVAL = 0.25

BID_SOURCE = (
    "CREATE SOURCE bid WITH (connector='nexmark', "
    "nexmark.table.type='bid', nexmark.event.num={n}, "
    "nexmark.max.chunk.size=256, nexmark.min.event.gap.in.ns=50000000)")

MV = (
    "CREATE MATERIALIZED VIEW v AS "
    "SELECT window_start, MAX(price) AS max_price, COUNT(*) AS cnt "
    "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
    "GROUP BY window_start")


@pytest.fixture(autouse=True)
def _fresh():
    LEDGER.clear()
    HISTORY.clear()
    spans_mod.set_current_epoch(0)
    yield
    LEDGER.clear()
    HISTORY.clear()


@pytest.fixture
def clock(monkeypatch):
    """The tick on virtual time: `sleep` advances it and yields once."""
    vc = VirtualClock()
    monkeypatch.setattr(HeartbeatTick, "monotonic",
                        staticmethod(vc.monotonic))
    monkeypatch.setattr(HeartbeatTick, "sleep", staticmethod(vc.sleep))
    return vc


class Rounds:
    """Wraps the session's barrier engine: stamps every round's inject
    on the virtual clock, lets it take the virtual seconds the test
    queued for it, and says what was true when it was injected."""

    def __init__(self, fe, clock=None, takes=(), lags=()):
        self.fe, self.clock = fe, clock
        self.takes = list(takes)
        self.lags = list(lags)    # virtual seconds from lock to inject
        self.stamps = []          # virtual time of the heartbeat's injects
        self.log = []             # (kind of caller, uploader depth)
        self.epochs = []          # epoch every heartbeat round injected
        self.n_beats = 0          # heartbeat rounds injected
        self.in_round = False
        self.cut = 0              # rounds a cancel landed inside
        self._real = fe.loop.inject_and_collect
        fe.loop.inject_and_collect = self

    async def __call__(self, **kw):
        who = "heartbeat" if kw.get("drain_uploader") is False else \
            "ddl" if "mutation" in kw else "flush"
        self.log.append((who, self.fe.loop.uploader.depth))
        if who == "heartbeat":
            self.n_beats += 1
            if self.clock is not None:
                # stamped where the engine injects, as the tick is
                tick_injected = kw["on_inject"]

                def injected():
                    self.stamps.append(self.clock.monotonic())
                    tick_injected()

                kw["on_inject"] = injected
        self.in_round = True
        try:
            if self.lags and self.clock is not None:
                await self.clock.sleep(self.lags.pop(0))
            barrier = await self._real(**kw)
            if self.takes and self.clock is not None:
                await self.clock.sleep(self.takes.pop(0))
        except asyncio.CancelledError:
            self.cut += 1
            raise
        finally:
            self.in_round = False
        if who == "heartbeat":
            self.epochs.append(barrier.epoch.curr.value)
        return barrier

    def beats(self):
        return self.n_beats

    async def until(self, n, what=None):
        """Yield the loop until `n` heartbeat rounds have been injected
        (and `what()` holds); bounded, so a heartbeat that stopped
        fails the test instead of hanging it."""
        for _ in range(200_000):
            if self.beats() >= n and (what is None or what()):
                return
            await asyncio.sleep(0)
        raise AssertionError(f"{self.beats()} beats, wanted {n}")


async def _pause(fe, hb):
    """What the benchmark's `Heartbeat.pause` does."""
    async with fe._barrier_lock:
        hb.cancel()
    await asyncio.gather(hb, return_exceptions=True)
    assert hb.cancelled()


def _gaps(stamps):
    return [round(b - a, 9) for a, b in zip(stamps, stamps[1:])]


# -- (a) the tick is inject to inject ----------------------------------------


def _beat_through(clock, takes, epoch_pipeline=True):
    """A bare session's heartbeat over rounds that take `takes` virtual
    seconds each, and one more; returns the gaps between its injects."""
    async def run():
        fe = Frontend(epoch_pipeline=epoch_pipeline)
        rounds = Rounds(fe, clock, takes)
        hb = asyncio.ensure_future(fe.run_heartbeat(INTERVAL))
        await rounds.until(len(takes) + 1)
        await _pause(fe, hb)
        await fe.close()
        return rounds

    rounds = asyncio.run(run())
    assert rounds.cut == 0
    return _gaps(rounds.stamps)[:len(takes)]


@pytest.mark.parametrize("takes", [
    [0.1] * 6,                              # q7's plain cycle
    [0.02, 0.2, 0.11, 0.24, 0.0, 0.07],     # any round under the tick
    [0.0] * 6,                              # nothing to do at all
], ids=["steady", "varying", "empty"])
@pytest.mark.parametrize("engine", ["plane", "single_loop"])
def test_rounds_under_the_tick_are_injected_an_interval_apart(
        clock, takes, engine):
    # interval_s from inject to inject, not interval_s + the round,
    # under the domain plane and under the one global BarrierLoop
    assert _beat_through(clock, takes, engine == "plane") == \
        [INTERVAL] * len(takes)


# -- (b) a late tick delays, it never bursts ---------------------------------


@pytest.mark.parametrize("takes,want", [
    # one round of 0.6 s: the next inject follows it at once, and the
    # one after is a whole interval from that late inject
    ([0.1, 0.6, 0.1, 0.1], [0.25, 0.6, 0.25, 0.25]),
    # three ticks missed in one round: one inject follows, not three
    ([0.9, 0.0, 0.0, 0.0], [0.9, 0.25, 0.25, 0.25]),
    # saturated: every round over the tick, back to back
    ([0.3, 0.4, 0.26, 0.5], [0.3, 0.4, 0.26, 0.5]),
    # a round that ends on the tick to the digit
    ([0.25, 0.1, 0.25, 0.1], [0.25, 0.25, 0.25, 0.25]),
], ids=["one_late", "three_missed", "saturated", "on_the_tick"])
def test_a_late_round_is_followed_at_once_and_never_by_a_burst(
        clock, takes, want):
    gaps = _beat_through(clock, takes)
    assert gaps == want
    assert min(gaps) >= INTERVAL            # never two injects closer


def test_the_tick_counts_from_the_engines_inject_not_from_the_lock(clock):
    """The loop can hold a round between the lock and the inject (the
    domain's round starts a loop iteration later, behind whatever is
    ready: 5-7 ms after a compaction on the chip). The next tick is an
    interval from the barrier's own inject stamp."""
    lags = [0.0, 0.05, 0.0, 0.02, 0.0]

    async def run():
        fe = Frontend()
        rounds = Rounds(fe, clock, lags=lags)
        hb = asyncio.ensure_future(fe.run_heartbeat(INTERVAL))
        await rounds.until(len(lags) + 1)
        await _pause(fe, hb)
        await fe.close()
        return _gaps(rounds.stamps)[:len(lags) - 1]

    # a tick counted from the lock would put the inject after the
    # lagging one 0.2 s and 0.23 s behind it
    assert asyncio.run(run()) == [0.3, 0.25, 0.27, 0.25]


# -- (c) the first beat waits a whole interval -------------------------------


@pytest.mark.parametrize("restarts", [0, 1, 3],
                         ids=["start", "restart", "restarts"])
def test_the_first_beat_waits_a_whole_interval(clock, restarts):
    async def run():
        fe = Frontend()
        rounds = Rounds(fe, clock)
        firsts = []
        for _ in range(restarts + 1):
            # a round of the task before took 0.2 s and ended just now:
            # a tick kept across the restart would be due in 0.05 s
            rounds.takes = [0.2]
            started = clock.monotonic()
            seen = rounds.beats()
            hb = asyncio.ensure_future(fe.run_heartbeat(INTERVAL))
            await rounds.until(seen + 1, lambda: not rounds.in_round)
            firsts.append(round(rounds.stamps[seen] - started, 9))
            await _pause(fe, hb)
        await fe.close()
        return firsts

    assert asyncio.run(run()) == [INTERVAL] * (restarts + 1)


# -- (d) barrier N commits before barrier N+1 is injected --------------------


@pytest.mark.parametrize("put_s", [0.002, 0.05],
                         ids=["put_under_the_tick", "put_over_the_tick"])
def test_the_sealed_checkpoint_commits_before_the_next_inject(put_s):
    async def run():
        store = HummockLite(DelayedObjectStore(MemObjectStore(),
                                               delay_s=put_s))
        fe = Frontend(store, min_chunks=4)
        await fe.execute(BID_SOURCE.format(n=200000))
        await fe.execute(MV)
        rounds = Rounds(fe)
        commits = []
        note = fe.loop.uploader._note_commit

        def noted(epoch, upload_s, stages):
            commits.append((rounds.beats(), epoch))
            note(epoch, upload_s, stages)

        fe.loop.uploader._note_commit = noted
        hb = asyncio.ensure_future(fe.run_heartbeat(0.01))
        await rounds.until(8)
        await _pause(fe, hb)
        await fe.execute("FLUSH")
        await fe.close()
        return rounds, commits

    rounds, commits = asyncio.run(run())
    # no heartbeat round was injected over a checkpoint still in the
    # uploader, slow PUT or not
    assert [d for who, d in rounds.log if who == "heartbeat"] == \
        [0] * rounds.beats()
    # and every round's checkpoint did land, in the beat that sealed it
    assert [beat for beat, _e in commits][:7] == list(range(1, 8))


# -- (e) a cancel under the lock -------------------------------------------


@pytest.mark.parametrize("where", ["waiting_for_the_tick",
                                   "waiting_for_the_uploader",
                                   "queued_on_the_lock"])
def test_a_cancel_under_the_lock_cuts_no_round_and_no_upload(where):
    async def run():
        store = HummockLite(DelayedObjectStore(MemObjectStore(),
                                               delay_s=0.1))
        fe = Frontend(store, min_chunks=4)
        await fe.execute(BID_SOURCE.format(n=200000))
        await fe.execute(MV)
        rounds = Rounds(fe)
        up = fe.loop.uploader
        if where == "waiting_for_the_tick":
            hb = asyncio.ensure_future(fe.run_heartbeat(3600.0))
            for _ in range(50):
                await asyncio.sleep(0)
            await _pause(fe, hb)
            assert rounds.beats() == 0
        elif where == "waiting_for_the_uploader":
            hb = asyncio.ensure_future(fe.run_heartbeat(0.001))
            # the first round sealed a checkpoint whose PUT takes 0.1 s
            # and the heartbeat has left the round: it waits for it
            await rounds.until(1, lambda: not rounds.in_round
                               and not fe._barrier_lock.locked())
            assert up.depth == 1
            uploads = list(up._tasks.values())
            await _pause(fe, hb)
            assert not any(t.cancelled() for t in uploads)
            await up.drain()
            assert all(t.done() and not t.cancelled() and
                       t.exception() is None for t in uploads)
            assert up.committed_epoch == up.commit_log[-1]
        else:
            async with fe._barrier_lock:
                hb = asyncio.ensure_future(fe.run_heartbeat(0.001))
                # long enough for the tick to come due: the heartbeat
                # queues behind this block (or still waits: either way
                # the cancel finds it outside a round)
                await asyncio.sleep(0.05)
                hb.cancel()
            await asyncio.gather(hb, return_exceptions=True)
            assert hb.cancelled()
            assert rounds.beats() == 0
        assert rounds.cut == 0 and not rounds.in_round
        up.raise_if_failed()
        await fe.execute("FLUSH")           # the session still serves
        await fe.close()

    asyncio.run(run())


# -- (f) who gets the lock at saturation -------------------------------------


@pytest.mark.parametrize("statement,who", [
    ("FLUSH", "flush"),
    ("CREATE MATERIALIZED VIEW v2 AS SELECT auction, price FROM bid "
     "WHERE price > 100", "ddl"),
], ids=["FLUSH", "DDL"])
def test_a_statement_queued_at_saturation_waits_at_most_one_round(
        statement, who):
    async def run():
        fe = Frontend(HummockLite(MemObjectStore()), min_chunks=4)
        await fe.execute(BID_SOURCE.format(n=200000))
        await fe.execute(MV)
        rounds = Rounds(fe)
        # a tick that is always overdue: rounds back to back
        hb = asyncio.ensure_future(fe.run_heartbeat(1e-6))
        await rounds.until(3, lambda: rounds.in_round)
        # from the moment the statement asks for the lock (a DDL plans
        # first), with a round under way or not
        asked = []
        acquire = fe._barrier_lock.acquire

        def asking():
            if asyncio.current_task() is not hb and not asked:
                asked.append(len(rounds.log))
            return acquire()

        fe._barrier_lock.acquire = asking
        await fe.execute(statement)
        fe._barrier_lock.acquire = acquire
        after = [w for w, _d in rounds.log[asked[0]:]]
        await _pause(fe, hb)
        await fe.close()
        return after

    after = asyncio.run(run())
    assert who in after
    # the round under way ends; at most one more goes first
    assert after.index(who) <= 1, after


# -- (g) an exhausted source does not make it spin ---------------------------


def test_an_idle_session_still_injects_an_interval_apart(clock):
    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute(BID_SOURCE.format(n=2000))
        await fe.execute(MV)
        rounds = Rounds(fe, clock)
        hb = asyncio.ensure_future(fe.run_heartbeat(INTERVAL))
        await rounds.until(12)
        await _pause(fe, hb)
        rows = await fe.execute("SELECT SUM(cnt) FROM v")
        await fe.close()
        return rounds, rows

    rounds, rows = asyncio.run(run())
    assert int(rows[0][0]) == 1840          # every bid of 2,000 events
    # rounds that collect at once, and still four barriers a second
    assert _gaps(rounds.stamps) == [INTERVAL] * (len(rounds.stamps) - 1)
    assert rounds.stamps[-1] - rounds.stamps[0] == \
        pytest.approx(INTERVAL * (len(rounds.stamps) - 1))


# -- (h) the heartbeat's books ------------------------------------------------


def test_the_waits_are_filed_on_the_row_of_the_epoch_they_preceded(clock):
    takes = [0.1, 0.6, 0.3, 0.05]
    tails = [0.0, 0.03, 0.0, 0.07, 0.0]

    async def run():
        fe = Frontend()
        rounds = Rounds(fe, clock, takes)
        drain = fe.loop.uploader.drain
        waited = list(tails)

        async def slow_drain():
            if waited:
                await clock.sleep(waited.pop(0))
            await drain()

        fe.loop.uploader.drain = slow_drain
        hb = asyncio.ensure_future(fe.run_heartbeat(INTERVAL))
        await rounds.until(len(takes) + 1, lambda: not rounds.in_round
                           and not fe._barrier_lock.locked())
        await _pause(fe, hb)
        fe.loop.uploader.drain = drain
        rows = await fe.execute("SELECT * FROM rw_metrics_history")
        await fe.close()
        return rounds, rows

    rounds, rows = asyncio.run(run())
    books = {}
    for _seq, epoch, _ts, _interval_s, name, value, _dom in rows:
        if name.startswith("heartbeat."):
            books.setdefault(epoch, {})[name] = round(value, 9)
    # the first beat waits the interval; 0.1 s of work leaves 0.15 of
    # the tick (less what the tail took of it); 0.6 s leaves nothing
    # and the tick is overdue, and so does 0.3 s; then 0.05 s leaves
    # 0.2
    wait = [0.25, 0.15, 0.0, 0.0, 0.2]
    overdue = [0.0, 0.0, 1.0, 1.0, 0.0]
    assert len(set(rounds.epochs)) == len(rounds.epochs) >= 5
    assert [books.get(e) for e in rounds.epochs[:5]] == [
        {"heartbeat.wait_s": w, "heartbeat.tail_wait_s": t,
         "heartbeat.overdue": o}
        for w, t, o in zip(wait, tails, overdue)]
    assert set(books) == set(rounds.epochs)     # and on no other row
