"""Elastic control loop (ISSUE 15): autoscaler policy units over a
fake cluster, and the distributed end-to-end — a real 2-worker session
where an injected sustained-bottleneck signal drives a guarded rescale
1→2 with zero human ALTERs, filelog splits rebalance on manual
scale-out/in with byte offsets handing off exactly, a mid-redeploy
fault rolls the topology back (visible in rw_recovery), and concurrent
topology changes serialize with a clear error.
"""

import asyncio
import json
import os
import types

import pytest

from risingwave_tpu.meta.autoscaler import (
    AUTOSCALE_LOG, Autoscaler, AutoscalerConfig, _AdmitGate,
    autoscaler_rows, clear_autoscale_log,
)
from risingwave_tpu.meta.supervisor import (
    RECOVERY_LOG, RecoverySupervisor, clear_recovery_log,
)
from risingwave_tpu.stream.bottleneck import BOTTLENECKS
from risingwave_tpu.stream.monitor import UTILIZATION


@pytest.fixture(autouse=True)
def _fresh_ledgers():
    clear_autoscale_log()
    clear_recovery_log()
    yield
    clear_autoscale_log()
    clear_recovery_log()


# -- fakes ---------------------------------------------------------------


class FakeCluster:
    """Mechanism stub: policy under test lives in the Autoscaler."""

    def __init__(self, n: int = 2):
        self.n = n
        self.supervisor = RecoverySupervisor()
        self.store = types.SimpleNamespace(committed_epoch=lambda: 7)
        frag = types.SimpleNamespace(parallelism=1, nodes=[],
                                     inputs=[{}])
        self.frag = frag
        job = types.SimpleNamespace(
            name="hot",
            graph=types.SimpleNamespace(fragments=[frag]),
            placements=[[(1001, 0)]],
            split_assignments={})
        self.jobs = {"hot": job}
        self.rescales = []          # (name, fi, to_slots)
        self.steps = 0
        self.fail_rescale = None    # exception to raise on rescale
        self.fail_step_at = None    # step index (1-based) to fail at

    def _rescalable(self, frag):
        return frag is self.frag

    def _source_rescalable(self, frag):
        return False

    def domain_of_job(self, name):
        return "dom"

    async def drain_signals(self, light=False):
        return 0

    async def drain_freshness(self):
        return 0

    async def step(self, n=1):
        self.steps += 1
        if self.fail_step_at is not None \
                and self.steps >= self.fail_step_at:
            raise ConnectionError("worker died during verify")

    async def rescale_fragment(self, name, fi, to_slots):
        if self.fail_rescale is not None:
            exc, self.fail_rescale = self.fail_rescale, None
            raise exc
        self.rescales.append((name, fi, list(to_slots)))
        # keep stable actor ids so injected signal rows stay resolvable
        self.jobs[name].placements[fi] = [
            (1001 + k, s) for k, s in enumerate(to_slots)]

    async def rescale_source_fragment(self, name, fi, to_slots):
        await self.rescale_fragment(name, fi, to_slots)


def _sustained_row(mv="hot", actor=1001, busy=0.9, streak=5,
                   sustained=1):
    return (("dom", "HashAggExecutor(...)", mv, actor, 2, busy, 0.0,
             streak, sustained, 99, "sustained diag"))


def _busy_util(mv="hot", actor=1001, busy=0.9):
    return [(actor, mv, 2, "HashAggExecutor(...)", 99, 1.0, busy,
             0.0, 0.05)]


def _mk(cluster, **cfg):
    # backoff_s=0: policy units tick back-to-back — the deferred
    # backoff window (its own tests below) would otherwise swallow
    # the tick after any failed action
    defaults = dict(cooldown_s=0.0, verify_barriers=2,
                    up_busy_mean=0.3, backoff_s=0.0)
    defaults.update(cfg)
    return Autoscaler(cluster, AutoscalerConfig(**defaults))


def _tick(a):
    return asyncio.run(a.tick())


# -- policy units --------------------------------------------------------


def test_non_sustained_rows_are_ignored():
    """One-barrier anecdotes (sustained=0) never trigger a decision."""
    c = FakeCluster()
    a = _mk(c)
    BOTTLENECKS.ingest([_sustained_row(streak=1, sustained=0)], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    assert _tick(a) is None
    assert c.rescales == []
    assert autoscaler_rows() == []


def test_sustained_bottleneck_scales_up_and_verifies():
    c = FakeCluster()
    a = _mk(c)
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    ev = _tick(a)
    assert ev is not None and ev.outcome == "applied"
    assert c.rescales == [("hot", 0, [0, 1])]
    assert c.steps == 2                 # post-rescale verify rounds
    (row,) = autoscaler_rows()
    assert row[1] == "hot" and row[4] == "up" \
        and row[5] == 1 and row[6] == 2 and row[7] == "applied"
    from risingwave_tpu.utils.metrics import CLUSTER
    assert CLUSTER.autoscaler_decision.get(mv="hot",
                                           direction="up") >= 1


def test_per_mv_cooldown_suppresses_refire():
    c = FakeCluster()
    a = _mk(c, cooldown_s=60.0)
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    assert _tick(a).outcome == "applied"
    # signal still sustained — the per-MV cooldown wins
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    assert _tick(a) is None
    assert len(c.rescales) == 1


def test_tricolor_cross_check_blocks_idle_fragment():
    """A sustained row whose fragment's actors are NOT busy-dominated
    (stale walk, skew) does not scale."""
    c = FakeCluster()
    a = _mk(c)
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util(busy=0.1))
    assert _tick(a) is None
    assert c.rescales == []


def test_freshness_trend_cross_check():
    """A lag already clearly recovering vetoes the scale-up; a rising
    lag does not."""
    c = FakeCluster()
    a = _mk(c)
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    a._lag["hot"] = __import__("collections").deque(
        [10.0, 8.0, 6.0, 1.0], maxlen=32)       # recovering
    assert _tick(a) is None
    a._lag["hot"] = __import__("collections").deque(
        [1.0, 2.0, 4.0, 8.0], maxlen=32)        # rising
    assert _tick(a).outcome == "applied"


def test_load_step_jumps_two_rungs():
    """A ≥4x load step — saturated busy mean AND a steeply rising
    wall-lag trend — jumps parallelism +2 in ONE guarded rescale."""
    import collections
    c = FakeCluster(n=4)
    a = _mk(c)
    BOTTLENECKS.ingest([_sustained_row(busy=0.95)], "sig")
    UTILIZATION.ingest_rows(_busy_util(busy=0.95))
    # last sample ≥ jump_lag_slope (2.0) × window median
    a._lag["hot"] = collections.deque([1.0, 1.0, 2.0, 8.0], maxlen=32)
    ev = _tick(a)
    assert ev is not None and ev.outcome == "applied"
    assert ev.from_parallelism == 1 and ev.to_parallelism == 3
    assert len(c.rescales) == 1          # one rescale, not two
    assert "jump +2" in ev.reason


def test_gentle_load_keeps_single_step():
    """Busy-but-not-saturated, or a flat lag trend, walks +1."""
    import collections
    c = FakeCluster(n=4)
    a = _mk(c)
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util(busy=0.5))    # < jump_busy_mean
    a._lag["hot"] = collections.deque([1.0, 1.0, 2.0, 8.0], maxlen=32)
    ev = _tick(a)
    assert ev is not None and ev.to_parallelism == 2
    # saturated but the lag trend is flat (rising enough to pass the
    # veto, nowhere near the jump slope) → still +1
    c2 = FakeCluster(n=4)
    a2 = _mk(c2)
    BOTTLENECKS.ingest([_sustained_row(busy=0.95)], "sig")
    UTILIZATION.ingest_rows(_busy_util(busy=0.95))
    a2._lag["hot"] = collections.deque([4.0, 4.1, 4.0, 4.2],
                                       maxlen=32)
    ev2 = _tick(a2)
    assert ev2 is not None and ev2.to_parallelism == 2


def test_jump_clamps_to_max_parallelism():
    """The jump is bounded: +2 from cur=1 on a 2-slot cluster lands
    on 2, never past the cap."""
    import collections
    c = FakeCluster(n=2)
    a = _mk(c)
    BOTTLENECKS.ingest([_sustained_row(busy=0.95)], "sig")
    UTILIZATION.ingest_rows(_busy_util(busy=0.95))
    a._lag["hot"] = collections.deque([1.0, 1.0, 2.0, 8.0], maxlen=32)
    ev = _tick(a)
    assert ev is not None and ev.to_parallelism == 2


def test_failed_rescale_rolls_back_and_records_both_ledgers():
    c = FakeCluster()
    a = _mk(c)
    c.fail_rescale = RuntimeError("deploy exploded")
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    ev = _tick(a)
    assert ev.outcome == "rolled_back"
    # the compensating rescale went back to the prior single slot
    assert c.rescales == [("hot", 0, [0])]
    # rw_recovery carries the rollback; the recovery STORM budget is
    # untouched (satellite: no double-count against the supervisor)
    assert [(e.cause, e.action) for e in RECOVERY_LOG] == \
        [("rescale_failed", "rollback")]
    assert c.supervisor.attempts == 0
    from risingwave_tpu.utils.metrics import CLUSTER
    assert CLUSTER.autoscaler_rollback.get(mv="hot") >= 1


def test_verify_failure_rolls_back_and_surfaces_fault():
    """A recovery-worthy fault during the verify window rolls the
    parallelism back; if even the rollback cannot complete, the error
    surfaces to the serving loop's supervised ladder."""
    c = FakeCluster()
    a = _mk(c)
    c.fail_step_at = 1                  # first verify barrier dies
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    ev = _tick(a)
    assert ev.outcome == "rolled_back"
    assert [r[2] for r in c.rescales] == [[0, 1], [0]]
    assert RECOVERY_LOG[-1].cause == "rescale_failed"


def test_note_healthy_closes_window_only_after_success():
    c = FakeCluster()
    a = _mk(c)
    c.fail_rescale = RuntimeError("boom")
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    _tick(a)
    assert a.gate.attempts == 1
    a.note_healthy()                    # clean round after a ROLLBACK
    assert a.gate.attempts == 1         # backoff stays armed
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    assert _tick(a).outcome == "applied"
    a.note_healthy()                    # clean round after a SUCCESS
    assert a.gate.attempts == 0


def test_storm_gate_disables_loop_loudly():
    async def run():
        c = FakeCluster()
        a = _mk(c, max_attempts=2)
        a.gate.sleep = lambda _d: asyncio.sleep(0)
        for _ in range(2):
            c.fail_rescale = RuntimeError("persistent")
            BOTTLENECKS.ingest([_sustained_row()], "sig")
            UTILIZATION.ingest_rows(_busy_util())
            await a.tick()
        c.fail_rescale = RuntimeError("persistent")
        BOTTLENECKS.ingest([_sustained_row()], "sig")
        UTILIZATION.ingest_rows(_busy_util())
        ev = await a.tick()
        return a, ev

    a, ev = asyncio.run(run())
    assert ev.outcome == "storm_disabled"
    assert a.enabled is False
    assert asyncio.run(a.tick()) is None     # stays off until SET


def test_metric_families_have_help_lines():
    """The autoscaler counter families render HELP lines in the
    Prometheus exposition (`ctl metrics` dumps the same registry)."""
    from risingwave_tpu.utils.metrics import GLOBAL
    text = GLOBAL.render()
    assert "# HELP autoscaler_decision_total" in text
    assert "# HELP autoscaler_rollback_total" in text


def test_admit_gate_jitter_is_seeded():
    async def delays(seed):
        out = []

        async def sleep(d):
            out.append(d)

        g = _AdmitGate(8, 0.5, 16.0, seed, sleep=sleep)
        for _ in range(5):
            await g.admit()
        return out

    a = asyncio.run(delays(5))
    b = asyncio.run(delays(5))
    assert a == b and len(a) == 4          # attempt 1 is immediate
    assert a != asyncio.run(delays(6))


def test_failed_action_defers_backoff_between_ticks():
    """The storm-gate backoff never sleeps under the barrier lock:
    a failed action arms a not-before deadline and tick() no-ops
    until it passes — the delay runs between heartbeats."""
    clock = [100.0]
    c = FakeCluster()
    a = Autoscaler(c, AutoscalerConfig(cooldown_s=0.0,
                                       verify_barriers=1,
                                       up_busy_mean=0.3,
                                       backoff_s=0.5),
                   monotonic=lambda: clock[0])
    c.fail_rescale = RuntimeError("boom")
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    assert _tick(a).outcome == "rolled_back"
    assert a._not_before > clock[0]        # window armed
    deadline = a._not_before
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    assert _tick(a) is None                # inside the window: no-op
    assert not c.rescales[1:]              # ...and no rescale driven
    clock[0] = deadline + 0.01
    assert _tick(a).outcome == "applied"   # window passed: acts again


def test_reset_storm_reopens_the_gate():
    """SET stream_autoscale=on after a storm must clear the exhausted
    budget too — a still-maxed gate would re-raise the storm on the
    next decision without attempting a single rescale."""
    async def run():
        c = FakeCluster()
        a = _mk(c, max_attempts=1)
        c.fail_rescale = RuntimeError("persistent")
        BOTTLENECKS.ingest([_sustained_row()], "sig")
        UTILIZATION.ingest_rows(_busy_util())
        await a.tick()                         # burns the one attempt
        BOTTLENECKS.ingest([_sustained_row()], "sig")
        UTILIZATION.ingest_rows(_busy_util())
        ev = await a.tick()
        assert ev.outcome == "storm_disabled" and a.enabled is False
        a.reset_storm()                        # the SET handler's path
        assert a.enabled and a.gate.attempts == 0
        BOTTLENECKS.ingest([_sustained_row()], "sig")
        UTILIZATION.ingest_rows(_busy_util())
        return await a.tick()

    assert asyncio.run(run()).outcome == "applied"


def test_target_slots_derive_from_current_placement():
    """Scale-out extends the fragment's CURRENT placement (surviving
    actors stay put — the handoff moves only the rebalanced share);
    scale-in drops the tail. A formula-derived set would relocate the
    whole fragment when its placement doesn't match the formula."""
    c = FakeCluster(n=3)
    a = _mk(c)
    job = c.jobs["hot"]
    job.placements[0] = [(1001, 2)]        # round-robin put it on 2
    assert a._target_slots(job, 0, 2) == [2, 0]
    job.placements[0] = [(1001, 2), (1002, 0)]
    assert a._target_slots(job, 0, 3) == [2, 0, 1]
    assert a._target_slots(job, 0, 1) == [2]   # shrink drops the tail
    # parallelism past the worker count: slots repeat rather than wedge
    assert len(a._target_slots(job, 0, 5)) == 5


def test_cancelled_mid_action_reraises():
    """A heartbeat cancellation landing inside a guarded action must
    escape _act after the unwind — swallowing it would leave the
    serving task uncancellable (and hang anyone awaiting it)."""
    async def run():
        c = FakeCluster()
        a = _mk(c)

        async def cancelled_step(n=1):
            raise asyncio.CancelledError()

        c.step = cancelled_step                # cancel lands in verify
        BOTTLENECKS.ingest([_sustained_row()], "sig")
        UTILIZATION.ingest_rows(_busy_util())
        with pytest.raises(asyncio.CancelledError):
            await a.tick()
        return autoscaler_rows()

    rows = asyncio.run(run())
    # the unwind completed and was recorded before the re-raise
    assert [r[7] for r in rows] == ["rolled_back"]


def test_scale_down_after_quiet_window():
    c = FakeCluster()
    a = _mk(c, down_quiet_rounds=3, down_busy_max=0.2)
    BOTTLENECKS.ingest([_sustained_row()], "sig")
    UTILIZATION.ingest_rows(_busy_util())
    assert _tick(a).outcome == "applied"       # 1 -> 2, baseline 1
    # demand evaporates: no sustained row, actors idle
    BOTTLENECKS.ingest([("dom", None, "", 0, 0, 0.0, 0.0, 0, 0, 99,
                         "no sustained bottleneck")], "sig")
    UTILIZATION.ingest_rows([(1001, "hot", 2, "Hash", 99, 1.0, 0.01,
                              0.0, 0.9),
                             (1002, "hot", 2, "Hash", 99, 1.0, 0.01,
                              0.0, 0.9)])
    for _ in range(2):
        assert _tick(a) is None                # quiet rounds accrue
    ev = _tick(a)
    assert ev is not None and ev.direction == "down" \
        and ev.to_parallelism == 1
    assert c.rescales[-1] == ("hot", 0, [0])
    # never below the recorded baseline
    BOTTLENECKS.ingest([("dom", None, "", 0, 0, 0.0, 0.0, 0, 0, 99,
                         "")], "sig")
    for _ in range(5):
        assert _tick(a) is None


# -- distributed end-to-end ---------------------------------------------


def _produce(path, parts, start, n_per_part, keys=40):
    os.makedirs(path, exist_ok=True)
    for p in range(parts):
        with open(os.path.join(path, f"imps-{p}.log"), "ab") as f:
            for i in range(n_per_part):
                j = start + p * n_per_part + i
                f.write(json.dumps(
                    {"k": j % keys, "v": j}).encode() + b"\n")


def _topic_bytes(path, parts):
    return sum(os.path.getsize(os.path.join(path, f"imps-{p}.log"))
               for p in range(parts))


def _oracle(path, total_hint):
    """In-process single-reader oracle over ALL partitions."""
    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(
            f"CREATE SOURCE imp (k BIGINT, v BIGINT) WITH "
            f"(connector='filelog', path='{path}', topic='imps', "
            f"partitions='0,1,2', max.chunk.size=256)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW hot AS SELECT k, "
            "count(*) AS c, sum(v) AS s, approx_count_distinct(v) "
            "AS d FROM imp GROUP BY k")
        for _ in range(60):
            await fe.step(1)
            rows = await fe.execute("SELECT * FROM hot")
            if sum(r[1] for r in rows) >= total_hint:
                break
        rows = await fe.execute("SELECT * FROM hot")
        await fe.close()
        return sorted(tuple(r) for r in rows)

    return asyncio.run(run())


async def _drain_until(fe, total):
    for _ in range(80):
        await fe.step(1)
        rows = await fe.execute("SELECT * FROM hot")
        if sum(r[1] for r in rows) >= total:
            break
    return sorted(tuple(r) for r in await fe.execute(
        "SELECT * FROM hot"))


def test_autoscaler_and_split_rebalance_e2e(tmp_path):
    """The acceptance path on a real 2-worker cluster: an injected
    sustained signal makes the loop rescale the hot fragment 1→2
    (guarded, verified, ledgered; the healthy neighbor records zero
    decisions), filelog splits rebalance across actors on manual
    scale-out and back in with per-split byte offsets handing off
    exactly, a mid-redeploy fault rolls back to the prior topology
    with the cause in rw_recovery, and a concurrent topology change
    gets the clear serialization error — MV bit-identical to the
    single-reader oracle throughout."""
    from risingwave_tpu.cluster.scheduler import (
        RescaleError, RescaleInProgressError,
    )
    from risingwave_tpu.cluster.session import DistFrontend
    from risingwave_tpu.utils.failpoint import arm_specs

    data = str(tmp_path / "logs")
    _produce(data, 3, 0, 500)

    async def run():
        # parallelism 2 so the fragmenter cuts at the hash exchange
        # (the rescalable topology); 3 workers give the loop headroom
        # to scale 2 -> 3. approx_count_distinct keeps the agg
        # single-phase — a two-phase LOCAL agg rides the source
        # fragment, whose durable partials make it deliberately NOT
        # split-rescalable (the split handoff moves offset rows only).
        fe = DistFrontend(str(tmp_path / "root"), n_workers=3,
                          parallelism=2, barrier_timeout_s=60.0)
        await fe.start()
        out = {}
        try:
            await fe.execute("SET stream_autoscale = 'on'")
            fe.autoscaler.cfg.cooldown_s = 0.0
            fe.autoscaler.cfg.verify_barriers = 1
            fe.autoscaler.cfg.up_busy_mean = 0.0   # signal-injected
            await fe.execute(
                f"CREATE SOURCE imp (k BIGINT, v BIGINT) WITH "
                f"(connector='filelog', path='{data}', topic='imps', "
                f"max.chunk.size=256)")
            await fe.execute(
                "CREATE MATERIALIZED VIEW hot AS SELECT k, "
                "count(*) AS c, sum(v) AS s, "
                "approx_count_distinct(v) AS d "
                "FROM imp GROUP BY k")
            await fe.execute(
                f"CREATE SOURCE bid WITH (connector='nexmark', "
                f"nexmark.table.type='bid', nexmark.event.num=2000, "
                f"nexmark.max.chunk.size=512)")
            await fe.execute(
                "CREATE MATERIALIZED VIEW q7n AS SELECT auction, "
                "count(*) AS c FROM bid GROUP BY auction")
            out["phase1"] = await _drain_until(fe, 1500)

            job = fe.cluster.jobs["hot"]
            agg_fi = next(
                fi for fi, f in enumerate(job.graph.fragments)
                if fe.cluster._rescalable(f))
            src_fi = next(
                fi for fi, f in enumerate(job.graph.fragments)
                if fe.cluster._source_rescalable(f))
            out["src_assign0"] = list(
                job.split_assignments[src_fi])
            aid = job.placements[agg_fi][0][0]
            # inject the sustained signal under a synthetic worker
            # tag (real worker drains replace only their own rows)
            BOTTLENECKS.ingest(
                [("hot", "HashAggExecutor(...)", "hot", aid, 2,
                  0.9, 0.0, 5, 1, 99, "injected sustained")], "sig")
            ev = await fe.autoscaler.tick()
            out["tick"] = (ev.outcome, ev.mv, ev.from_parallelism,
                           ev.to_parallelism)
            out["agg_par"] = len(job.placements[agg_fi])
            out["ledger_sql"] = await fe.execute(
                "SELECT mv, direction, outcome FROM rw_autoscaler")
            # hot MV still exact after the autoscaler's rescale
            _produce(data, 3, 1500, 300)
            out["phase2"] = await _drain_until(fe, 2400)

            # manual scale-out rebalances the SOURCE splits too
            await fe.execute(
                "ALTER MATERIALIZED VIEW hot SET PARALLELISM = 2")
            assert len(job.placements[src_fi]) == 2
            out["src_assign2"] = list(job.split_assignments[src_fi])
            _produce(data, 3, 2400, 300)
            out["phase3"] = await _drain_until(fe, 3300)
            # ...and scale-in hands every split back to one actor
            await fe.execute(
                "ALTER MATERIALIZED VIEW hot SET PARALLELISM = 1")
            assert len(job.placements[src_fi]) == 1
            _produce(data, 3, 3300, 200)
            out["phase4"] = await _drain_until(fe, 3900)
            await fe.execute("FLUSH")

            # per-split byte offsets hand off exactly: 3 rows, and
            # their sum equals the topic's total byte size (every
            # record consumed once, none re-read)
            src_node = next(
                n for n in job.graph.fragments[src_fi].nodes
                if n["op"] == "source")
            srows = await fe.cluster.scan_table(
                int(src_node["split_table_id"]))
            offs = {v[0]: v[1] for _k, v in srows}
            out["split_offsets"] = offs
            out["topic_bytes"] = _topic_bytes(data, 3)

            # forced-failure rescale: the cohort redeploy explodes;
            # the guarded protocol must land back on parallelism 1
            arm_specs({"rescale.redeploy": {
                "raise": "RuntimeError", "msg": "chaos redeploy",
                "times": 1}})
            try:
                with pytest.raises(RescaleError) as ei:
                    await fe.execute(
                        "ALTER MATERIALIZED VIEW hot "
                        "SET PARALLELISM = 2")
            finally:
                arm_specs({"rescale.redeploy": None})
            out["rolled_back"] = ei.value.rolled_back
            out["post_rollback_par"] = (
                len(job.placements[src_fi]),
                len(job.placements[agg_fi]))
            out["recovery_sql"] = await fe.execute(
                "SELECT cause, action, ok FROM rw_recovery")

            # the AUTOSCALER-driven forced failure: its verify window
            # dies, the compensating rescale restores the prior
            # parallelism, and the rollback is queryable over SQL
            BOTTLENECKS.ingest(
                [("hot", "HashAggExecutor(...)", "hot",
                  job.placements[agg_fi][0][0], 2, 0.9, 0.0, 5, 1,
                  99, "injected again")], "sig")
            arm_specs({"rescale.redeploy": {
                "raise": "RuntimeError", "msg": "chaos redeploy 2",
                "times": 1}})
            try:
                ev2 = await fe.autoscaler.tick()
            finally:
                arm_specs({"rescale.redeploy": None})
            out["tick2"] = (ev2.outcome, ev2.from_parallelism,
                            ev2.to_parallelism)
            out["tick2_par"] = len(job.placements[agg_fi])
            out["rollback_sql"] = await fe.execute(
                "SELECT mv, outcome FROM rw_autoscaler")
            _produce(data, 3, 3900, 100)
            out["phase5"] = await _drain_until(fe, 4200)

            # concurrent topology changes serialize with a clear error
            fe.cluster._topology_busy = "test-held"
            with pytest.raises(RescaleInProgressError):
                await fe.execute(
                    "ALTER MATERIALIZED VIEW hot "
                    "SET PARALLELISM = 2")
            fe.cluster._topology_busy = None
            return out
        finally:
            await fe.close()

    out = asyncio.run(run())
    assert out["tick"] == ("applied", "hot", 2, 3)
    assert out["agg_par"] == 3
    assert ("hot", "up", "applied") in [tuple(r) for r
                                        in out["ledger_sql"]]
    # the healthy neighbor saw ZERO decisions
    assert not [r for r in autoscaler_rows() if r[1] == "q7n"]
    # split assignment: all 3 partitions on one actor, then split 2/1,
    # then back to one
    assert sorted(p for ps in out["src_assign0"] for p in ps) \
        == [0, 1, 2]
    assert sorted(len(ps) for ps in out["src_assign2"]) == [1, 2]
    # offsets: one row per split, summing to the topic's exact bytes
    # at snapshot time (every record consumed once, none re-read)
    data_dir = str(tmp_path / "logs")
    assert len(out["split_offsets"]) == 3
    assert sum(out["split_offsets"].values()) == out["topic_bytes"]
    assert out["rolled_back"] is True
    assert out["post_rollback_par"] == (1, 1)
    assert ("rescale_failed", "rollback", 1) in [
        tuple(r) for r in out["recovery_sql"]]
    # the autoscaler's own forced failure rolled back to the prior
    # parallelism and the event is visible in rw_autoscaler
    assert out["tick2"] == ("rolled_back", 1, 2)
    assert out["tick2_par"] == 1
    assert ("hot", "rolled_back") in [tuple(r)
                                      for r in out["rollback_sql"]]
    # bit-identity vs the single-reader oracle over the full topic
    # (the final state subsumes every phase: counts/sums per key)
    assert out["phase5"] == _oracle(data_dir, 4200)
    # and each phase's snapshot saw exactly the records produced so
    # far — no loss, no duplication across any rescale boundary
    for phase, hint in (("phase1", 1500), ("phase2", 2400),
                        ("phase3", 3300), ("phase4", 3900),
                        ("phase5", 4200)):
        assert sum(r[1] for r in out[phase]) == hint, phase


def test_mid_rescale_chaos_converges(tmp_path):
    """ISSUE 15 acceptance: a seeded schedule injecting faults
    MID-RESCALE — SIGKILL during cohort redeploy, storage fault during
    the state handoff, straggler across the rescale's stop barrier —
    with the autoscaler enabled converges oracle-bit-identical, and
    the rollbacks/recoveries land in rw_recovery."""
    from risingwave_tpu.cluster.chaos import run_chaos
    from risingwave_tpu.cluster.session import DistFrontend
    from risingwave_tpu.frontend.session import Frontend

    events = 3000
    src = ("CREATE SOURCE bid WITH (connector='nexmark', "
           f"nexmark.table.type='bid', nexmark.event.num={events}, "
           "nexmark.max.chunk.size=256, "
           "nexmark.min.event.gap.in.ns=50000000)")
    mv = ("CREATE MATERIALIZED VIEW q7 AS SELECT window_start, "
          "MAX(price) AS max_price, COUNT(*) AS cnt "
          "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
          "GROUP BY window_start")

    async def oracle():
        fe = Frontend(min_chunks=8)
        await fe.execute(src)
        await fe.execute(mv)
        await fe.step(30)
        rows = {tuple(r) for r in await fe.execute(
            "SELECT * FROM q7")}
        await fe.close()
        return rows

    async def chaos():
        fe = DistFrontend(str(tmp_path), n_workers=2, parallelism=2,
                          barrier_timeout_s=8.0)
        await fe.start()
        try:
            await fe.execute("SET stream_autoscale = 'on'")
            await fe.execute(src)
            await fe.execute(mv)
            report = await run_chaos(
                fe, seed=11, settle_steps=50,
                kinds=["kill_mid_rescale", "fault_mid_handoff",
                       "straggler_mid_rescale"],
                rescale_mv="q7")
            rows = {tuple(r) for r in await fe.execute(
                "SELECT * FROM q7")}
            rec = await fe.execute(
                "SELECT cause, action FROM rw_recovery")
            return report, rows, rec
        finally:
            await fe.close()

    expect = asyncio.run(oracle())
    report, rows, rec = asyncio.run(chaos())
    assert rows == expect
    assert report.rescale_rollbacks        # at least one unwound
    causes = {c for c, _a in rec}
    assert "rescale_failed" in causes
