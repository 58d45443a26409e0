"""Serving cost & skew attribution tests (ISSUE 16): the per-MV
resource ledger, the per-(table, vnode) state topology, heavy-hitter
sketches, the skew verdict in the bottleneck walker's diagnosis, and
the series-lifecycle purge on DROP / failed CREATE."""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from risingwave_tpu.state.topology import (
    TOPOLOGY, StateTopology, fixed_row_nbytes, row_nbytes,
)
from risingwave_tpu.stream.costs import (
    COSTS, CompileCache, MVCosts, purge_mv_series,
)
from risingwave_tpu.stream.hotkeys import HOTKEYS, K, HotKeys, _Sketch

NEXMARK_BID = (
    "CREATE SOURCE bid WITH (connector='nexmark', "
    "nexmark.table.type='bid', nexmark.event.num=4000)")


def _lanes(values):
    """(n, 3) int32 key lanes for a single-BIGINT-column key — the
    (hi, lo, valid) shape the codec emits."""
    v = np.asarray(values, dtype=np.int64)
    return np.stack([(v >> 32).astype(np.int32),
                     (v & 0xFFFFFFFF).astype(np.int32),
                     np.ones(len(v), dtype=np.int32)], axis=1)


# -- space-saving sketch ---------------------------------------------------

def test_sketch_90_10_share_within_5pp():
    """The acceptance bound: a seeded 90/10 stream's hot key surfaces
    with share error ≤ 5pp, even with more distinct cold keys than
    sketch counters (evictions churn only the cold tail)."""
    rng = np.random.default_rng(7)
    n = 20_000
    keys = np.where(rng.random(n) < 0.9, 777,
                    rng.integers(1000, 1000 + 4 * K, n))
    sk = _Sketch()
    for lo in range(0, n, 512):          # chunked like the hot path
        sk.observe(_lanes(keys[lo:lo + 512]), None, None)
    true_share = float(np.mean(keys == 777))
    h, est, err = sk.top(1)[0]
    assert abs(est / sk.total - true_share) <= 0.05
    # guaranteed (lower-bound) share also within the bound
    assert true_share - 0.05 <= (est - err) / sk.total <= true_share + 0.05


def test_sketch_estimates_bound_true_counts():
    """Space-saving invariants under forced eviction: est ≥ true and
    est − err ≤ true for every surviving counter."""
    n_keys = 3 * K
    per = 5
    hot_reps = 200
    seq = list(range(n_keys)) * per + [42] * hot_reps
    rng = np.random.default_rng(0)
    rng.shuffle(seq)
    sk = _Sketch()
    sk.observe(_lanes(seq), None, None)
    true = {k: per for k in range(n_keys)}
    true[42] += hot_reps
    for h, est, err in sk.top(K):
        # recover the original key via its stored representative lane
        lane = sk.lanes[h]
        key = (int(lane[0]) << 32) | int(lane[1])
        assert est >= true[key]
        assert est - err <= true[key]
    # the hot key is rank 1
    top_lane = sk.lanes[sk.top(1)[0][0]]
    assert (int(top_lane[0]) << 32) | int(top_lane[1]) == 42


def test_sketch_respects_visibility_and_display_fallback():
    sk = _Sketch()
    lanes = _lanes([5, 5, 9, 9])
    vis = np.array([True, True, True, False])
    sk.observe(lanes, vis, None)
    assert sk.total == 3
    h, est, _err = sk.top(1)[0]
    assert est == 2
    assert sk.display(h).startswith("#")   # no codec → hash fallback


def test_hotkeys_join_suffix_resolution_and_unregister():
    """Join inputs observe under 'identity/side' while the fragment
    binding is on the base identity: rows() must resolve the MV, and
    unregister_fragment must drop the suffixed sketches too."""
    hk = HotKeys()
    hk.bind_fragment("HashJoinExecutor-3", "mv_a")
    hk.observe("HashJoinExecutor-3/0", _lanes([1] * 9 + [2]), None,
               None)
    hk.observe("HashJoinExecutor-3/1", _lanes([4] * 10), None, None)
    rows = hk.rows()
    assert rows and all(r[0] == "mv_a" for r in rows)
    hot = hk.hot_share("HashJoinExecutor-3", min_share=0.25)
    assert hot is not None and hot[1] >= 0.25
    hk.unregister_fragment("mv_a")
    assert hk.rows() == []
    assert hk.hot_share("HashJoinExecutor-3") is None


# -- state topology --------------------------------------------------------

def test_topology_incremental_matches_recount():
    topo = StateTopology()
    keys = [bytes([0, i, 7]) for i in range(10)]
    vals = [(1, 2)] * 10
    topo.record(9, keys, vals, fixed_nbytes=18)      # append-fast
    # overwrite half (same unit), delete two, then a varchar batch
    topo.record(9, keys[:5], vals[:5], fixed_nbytes=18)
    topo.record(9, keys[:2], [None, None], fixed_nbytes=18)
    topo.record(9, [b"\x01\x00zz", b"\x01\x01w"],
                [("abc",), ("defgh",)])               # slow path
    assert topo.gate_violations() == []
    stats = {t: (nrows, nbytes) for t, _mv, nrows, nbytes, _v, _i
             in topo.table_stats()}
    assert stats[9][0] == 8 + 2
    # per-vnode split: first batch lands in vnodes (0,0..9); varchar
    # rows in vnodes 256 and 257
    vns = {vn for _t, _mv, vn, _r, _b in topo.rows()}
    assert {256, 257} <= vns
    assert topo.top_vnodes(9, 4)


def test_topology_mixed_batches_and_byte_model():
    topo = StateTopology()
    topo.record(3, [b"ab"], [("xy", 5)])
    assert row_nbytes(("xy", 5)) == 3 + 9
    _t, _mv, nrows, nbytes, _v, _i = topo.table_stats()[0]
    assert (nrows, nbytes) == (1, 2 + 12)
    # a delete mixed into a fixed-width batch falls to the slow path
    topo.record(3, [b"ab", b"cd"], [None, (1, 2)], fixed_nbytes=18)
    assert topo.gate_violations() == []
    _t, _mv, nrows, nbytes, _v, _i = topo.table_stats()[0]
    assert (nrows, nbytes) == (1, 2 + 18)


def test_topology_width_change_overwrite_stays_exact():
    """Regression: re-planning the same table id with a different row
    width (column pruning narrows a varchar table to all-fixed) must
    not ride the append-fast bulk merge — blind overwrites of entries
    that hold a DIFFERENT size would change the map without touching
    the delta totals, and the recount gate would fire."""
    topo = StateTopology()
    keys = [bytes([0, i]) for i in range(6)]
    # first plan: varchar rows via the slow path (variable widths)
    topo.record(5, keys, [("x" * (i + 1),) for i in range(6)])
    # re-planned: same keys, all-fixed schema → fast-path candidate
    topo.record(5, keys, [(1, 2)] * 6, fixed_nbytes=18)
    assert topo.gate_violations() == []
    _t, _mv, nrows, nbytes, _v, _i = topo.table_stats()[0]
    assert (nrows, nbytes) == (6, 6 * (2 + 18))
    # and the reverse order: fast-path first, then a different unit
    topo2 = StateTopology()
    topo2.record(7, keys, [(1,)] * 6, fixed_nbytes=9)
    topo2.record(7, keys, [(1, 2)] * 6, fixed_nbytes=18)
    assert topo2.gate_violations() == []
    _t, _mv, nrows, nbytes, _v, _i = topo2.table_stats()[0]
    assert (nrows, nbytes) == (6, 6 * (2 + 18))
    # once mixed, the table stays on the exact per-entry loop
    topo2.record(7, keys, [(3, 4)] * 6, fixed_nbytes=18)
    assert topo2.gate_violations() == []
    # a never-mixed table keeps riding the fast path across
    # same-unit overwrites (the steady-state upsert shape)
    topo3 = StateTopology()
    topo3.record(8, keys, [(1,)] * 6, fixed_nbytes=9)
    topo3.record(8, keys, [(2,)] * 6, fixed_nbytes=9)
    assert topo3._unit[8] == 11 and topo3.gate_violations() == []


def test_topology_checkpoint_verify_arming():
    topo = StateTopology()
    topo.record(1, [b"aa"], [(1,)], fixed_nbytes=9)
    topo.checkpoint_verify()                 # unarmed: no-op
    topo.arm_checkpoint_verify(True)
    # sabotage the delta book to prove the recount catches drift
    topo._totals[1][1] += 5
    topo.checkpoint_verify()
    assert topo.gate_violations()
    topo.clear()
    assert topo.gate_violations() == []


def test_topology_unbind_mv_drops_books_and_remote():
    topo = StateTopology()
    topo.bind(4, "mv_x")
    topo.record(4, [b"aa"], [(1,)], fixed_nbytes=9)
    topo.ingest([(8, "mv_x", 0, 2, 40), (9, "mv_y", 0, 1, 20)],
                worker="w1")
    topo.unbind_mv("mv_x")
    assert all(r[1] != "mv_x" for r in topo.rows())
    assert topo.bytes_by_mv().get("mv_y") == 20


def test_fixed_row_nbytes_gates_on_device_types():
    from risingwave_tpu.common.types import DataType, Field, Schema
    dev = Schema([Field("a", DataType.INT64),
                  Field("b", DataType.FLOAT64)])
    host = Schema([Field("a", DataType.INT64),
                   Field("s", DataType.VARCHAR)])
    assert fixed_row_nbytes(dev) == 18
    assert fixed_row_nbytes(host) is None


# -- per-MV resource ledger ------------------------------------------------

def _seal_rec(epoch, device_s, domain="", distributed=False):
    from risingwave_tpu.utils.ledger import LedgerRecord
    return LedgerRecord(epoch=epoch, kind="checkpoint", interval_s=1.0,
                        seconds={"device_compute": device_s},
                        h2d_bytes=0, d2h_bytes=0, warmup=False,
                        distributed=distributed, domain=domain)


def test_mvcosts_split_conserves_and_feeds_history():
    c = MVCosts()
    c.observe_cell("mv_a", 11, 0.03, 100, 10)
    c.observe_cell("mv_b", 11, 0.01, 0, 0)
    extra = c.history_extra(_seal_rec(11, 0.05, domain="d1"))
    assert extra == {"mv_device_s.mv_a": 0.03,
                     "mv_device_s.mv_b": 0.01}
    assert c.gate_violations() == []
    rows = {r[0]: r for r in c.rows()}
    assert rows["mv_a"][1] == "d1"
    assert rows["mv_a"][2] == pytest.approx(0.03)
    assert rows["mv_a"][3] == 100 and rows["mv_a"][4] == 10
    # a split that MINTS device time (sum > domain + 1%) trips the gate
    c.observe_cell("mv_a", 12, 0.08, 0, 0)
    c.history_extra(_seal_rec(12, 0.05))
    assert c.gate_violations()


def test_mvcosts_coverage_windows_both_sides():
    """coverage() sums attributed AND ledgered device time over the
    same sealed-epoch window — including epochs that sealed with NO
    attributed cells (their device time belongs in the denominator,
    or unattributed work would inflate the coverage claim)."""
    c = MVCosts()
    c.observe_cell("mv_a", 21, 0.04, 0, 0)
    c.history_extra(_seal_rec(21, 0.05))
    # a cell-less epoch still lands in the window with 0.0 attributed
    c.history_extra(_seal_rec(22, 0.05))
    att, led = c.coverage()
    assert att == pytest.approx(0.04)
    assert led == pytest.approx(0.10)
    # distributed epochs stay out of the window entirely (their books
    # merge later — the coordinator's own seal undercounts by design)
    c.history_extra(_seal_rec(23, 9.0, distributed=True))
    assert c.coverage() == (pytest.approx(0.04), pytest.approx(0.10))


def test_mvcosts_distributed_epochs_exempt_from_gate():
    c = MVCosts()
    c.observe_cell("mv_a", 5, 0.5, 0, 0)
    c.history_extra(_seal_rec(5, 0.01, distributed=True))
    assert c.gate_violations() == []
    assert c.summary()["mv_a"]["device_s"] == pytest.approx(0.5)


def test_mvcosts_worker_drain_ingest_merges():
    w = MVCosts()
    w.observe_cell("mv_a", 3, 0.2, 50, 0)
    w.history_extra(_seal_rec(3, 0.2, distributed=True))
    w.observe_cell("mv_a", 4, 0.1, 0, 0)     # still pending
    parts = w.drain_dict()
    assert w.summary() == {}                 # a true drain
    coord = MVCosts()
    assert coord.ingest(parts, worker="w0") >= 1
    s = coord.summary()["mv_a"]
    assert s["device_s"] == pytest.approx(0.3)
    assert s["h2d_bytes"] == 50
    # idempotent across rounds: the next drain ships nothing
    assert coord.ingest(w.drain_dict(), worker="w0") == 0


def test_compile_cache_bills_pulling_mv():
    from risingwave_tpu.stream import costs as costs_mod
    cache = CompileCache("test_kind")
    tok = costs_mod.push_mv("mv_first")
    assert cache.get(("k",)) is None
    cache[("k",)] = object()                 # mv_first pays the trace
    assert cache.get(("k",)) is not None     # own hit
    costs_mod.pop_mv(tok)
    tok = costs_mod.push_mv("mv_second")
    assert cache.get(("k",)) is not None     # shared hit
    costs_mod.pop_mv(tok)
    s = COSTS.summary()
    assert s["mv_first"]["compile_misses"] == 1
    assert s["mv_first"]["compile_hits"] == 1
    assert s["mv_first"]["shared_hits"] == 0
    assert s["mv_second"]["compile_hits"] == 1
    assert s["mv_second"]["shared_hits"] == 1


def test_purge_mv_series_clears_every_registry():
    from risingwave_tpu.stream.freshness import FRESHNESS
    from risingwave_tpu.utils.metrics import STREAMING
    FRESHNESS.register_mv("doomed", ["src"])
    COSTS.observe_cell("doomed", 1, 0.01, 1, 1)
    COSTS.history_extra(_seal_rec(1, 0.01))
    HOTKEYS.bind_fragment("Agg-1", "doomed")
    HOTKEYS.observe("Agg-1", _lanes([1, 1, 2]), None, None)
    TOPOLOGY.bind(77, "doomed")
    TOPOLOGY.record(77, [b"aa"], [(1,)], fixed_nbytes=9)
    COSTS.publish_state_bytes()
    assert any(r[0] == "doomed" for r in COSTS.rows())
    purge_mv_series("doomed")
    assert all(r[0] != "doomed" for r in COSTS.rows())
    assert all(r[0] != "doomed" for r in HOTKEYS.rows())
    assert all(r[1] != "doomed" for r in TOPOLOGY.rows())
    assert "doomed" not in FRESHNESS.summary()
    for fam in (STREAMING.mv_device_seconds, STREAMING.mv_state_bytes,
                STREAMING.mv_transfer_bytes):
        assert all(l.get("mv") != "doomed" for l, *_ in fam.series())


# -- skew verdict in the walker --------------------------------------------

def test_skew_verdict_names_hot_key():
    """Synthetic 90%-one-key stream: the walked bottleneck's diagnosis
    gains a skew:<key> clause (the autoscaler's parallelism veto)."""
    from risingwave_tpu.common.chunk import StreamChunk
    from risingwave_tpu.common.types import DataType, Field, Schema
    from risingwave_tpu.meta.barrier import BarrierLoop
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import Actor, LocalBarrierManager
    from risingwave_tpu.stream.bottleneck import (
        BOTTLENECKS, SUSTAINED_STREAK,
    )
    from risingwave_tpu.stream.executor import Executor, ExecutorInfo
    from risingwave_tpu.stream.executors.keys import KeyCodec
    from risingwave_tpu.stream.executors.test_utils import MockSource
    from risingwave_tpu.stream.message import (
        StopMutation, is_chunk,
    )
    from risingwave_tpu.stream.monitor import install_monitoring

    sch = Schema([Field("a", DataType.INT64)])
    codec = KeyCodec([DataType.INT64])
    rng = np.random.default_rng(1)
    skewed = np.where(rng.random(256) < 0.9, 7,
                      rng.integers(100, 200, 256))

    class HotAgg(Executor):
        """Burns CPU and sketches its input keys — a hash agg whose
        group key is 90% one value."""

        def __init__(self, input_):
            super().__init__(ExecutorInfo(sch, [0], "HotAgg"))
            self.input = input_

        async def execute(self):
            async for msg in self.input.execute():
                if is_chunk(msg):
                    HOTKEYS.observe(self.identity, _lanes(skewed),
                                    None, codec)
                    t0 = time.perf_counter()
                    while time.perf_counter() - t0 < 0.3:
                        pass
                yield msg

    async def run():
        store = MemoryStateStore()
        local = LocalBarrierManager()
        tx, src = MockSource.channel(sch)
        local.register_sender(5, tx)
        consumer = install_monitoring(HotAgg(src),
                                      fragment="skew-mv", actor_id=5)
        local.set_expected_actors([5])
        actor = Actor(5, consumer, dispatchers=[],
                      barrier_manager=local, fragment="skew-mv")
        loop = BarrierLoop(local, store)
        task = actor.spawn()
        await loop.inject_and_collect(force_checkpoint=True)
        for _ in range(SUSTAINED_STREAK + 1):
            for _ in range(2):      # push each epoch past the walker's
                await src._tx.send(StreamChunk.from_pydict(
                    sch, {"a": [1, 2, 3, 4]}))   # SLOW_INTERVAL_S floor
            await loop.inject_and_collect(force_checkpoint=True)
        summary = BOTTLENECKS.summary().get("(global)", {})
        await loop.inject_and_collect(
            mutation=StopMutation(frozenset({5})))
        await task
        assert actor.failure is None
        return summary

    summary = asyncio.run(run())
    assert summary.get("operator") == "HotAgg", summary
    diag = summary.get("diagnosis", "")
    assert "skew:7" in diag, diag
    assert "parallelism won't help" in diag
    # the surfaced share tracks the seeded 90% within 5pp
    share = HOTKEYS.hot_share("HotAgg", min_share=0.25)[1]
    true_share = float(np.mean(skewed == 7))
    assert abs(share - true_share) <= 0.05


def test_cold_keys_never_fire_skew():
    """A uniform key distribution must not earn a skew clause: the
    guaranteed-share test uses the sketch's LOWER bound."""
    hk = HotKeys()
    hk.observe("Even", _lanes(list(range(500)) * 4), None, None)
    assert hk.hot_share("Even", min_share=0.25) is None


# -- SQL surfaces end-to-end -----------------------------------------------

def test_session_costs_end_to_end():
    """Front door: rw_mv_costs attributes device time and state bytes
    to the MV, rw_state_topology serves per-vnode rows, per-barrier
    history carries mv_device_s.<mv>, and DROP purges every
    surface."""
    from risingwave_tpu.frontend import Frontend

    async def run():
        fe = Frontend(min_chunks=2)
        await fe.execute(NEXMARK_BID)
        await fe.execute(
            "CREATE MATERIALIZED VIEW cost_mv AS SELECT auction, "
            "count(*) AS c FROM bid GROUP BY auction")
        await fe.step(4)
        costs = await fe.execute("SELECT * FROM rw_mv_costs")
        topo = await fe.execute("SELECT * FROM rw_state_topology")
        hist = await fe.execute("SELECT * FROM rw_metrics_history")
        await fe.execute("DROP MATERIALIZED VIEW cost_mv")
        after = await fe.execute("SELECT * FROM rw_mv_costs")
        await fe.close()
        return costs, topo, hist, after

    costs, topo, hist, after = asyncio.run(run())
    row = next(r for r in costs if r[0] == "cost_mv")
    assert row[2] >= 0.0                       # device_seconds
    assert row[5] > 0                          # state_bytes
    # topology rows exist for the MV and their bytes reconcile with
    # the cost row's state_bytes column (same books)
    mv_topo = [r for r in topo if r[1] == "cost_mv"]
    assert mv_topo and sum(r[4] for r in mv_topo) == row[5]
    names = {r[4] for r in hist}
    assert "mv_device_s.cost_mv" in names
    assert all(r[0] != "cost_mv" for r in after)


def test_skewed_source_surfaces_hot_key_share(tmp_path):
    """The ad-ctr acceptance shape: a 90/10-skewed filelog stream's
    GROUP BY surfaces the hot ad in rw_hot_keys with share error
    ≤ 5pp."""
    from risingwave_tpu.frontend import Frontend

    path = str(tmp_path)
    n = 1200
    rng = np.random.default_rng(3)
    ads = np.where(rng.random(n) < 0.9, 7, rng.integers(100, 160, n))
    with open(os.path.join(path, "imp-0.log"), "wb") as f:
        for i in range(n):
            f.write(json.dumps({
                "bid_id": i, "ad_id": int(ads[i]),
                "its": 1_700_000_000_000_000 + i * 10_000,
            }).encode() + b"\n")

    async def run():
        fe = Frontend(rate_limit=8, min_chunks=2)
        await fe.execute(
            f"CREATE SOURCE imp (bid_id BIGINT, ad_id BIGINT, "
            f"its TIMESTAMP) WITH (connector='filelog', "
            f"path='{path}', topic='imp')")
        await fe.execute(
            "CREATE MATERIALIZED VIEW ctr AS SELECT ad_id, "
            "count(*) AS c FROM imp GROUP BY ad_id")
        for _ in range(24):
            await fe.step()
            total = (await fe.execute(
                "SELECT sum(c) FROM ctr"))[0][0]
            if total is not None and int(total) >= n:
                break
        hot = await fe.execute("SELECT * FROM rw_hot_keys")
        await fe.close()
        return hot

    hot = asyncio.run(run())
    true_share = float(np.mean(ads == 7))
    agg_rows = [r for r in hot if r[0] == "ctr" and r[2] == 0]
    assert agg_rows, hot
    r = max(agg_rows, key=lambda r: r[5])
    assert r[3] == "7"                          # decoded key
    assert abs(r[5] - true_share) <= 0.05, r


def test_failed_create_purges_series():
    """A CREATE that deploys far enough to register {mv=...} series
    and THEN fails must purge them before surfacing the failure."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.stream.freshness import FRESHNESS
    from risingwave_tpu.utils.failpoint import failpoints
    from risingwave_tpu.utils.metrics import STREAMING

    async def run():
        fe = Frontend(rate_limit=2)
        await fe.execute(NEXMARK_BID)
        with failpoints({"trace.slow.MaterializeExecutor":
                         RuntimeError("deploy sabotaged")}):
            with pytest.raises(Exception):
                await fe.execute(
                    "CREATE MATERIALIZED VIEW doomed_mv AS SELECT "
                    "auction FROM bid")
        summary = FRESHNESS.summary()
        series = [l for l, *_ in
                  STREAMING.mv_device_seconds.series()]
        try:
            await fe.close()
        except Exception:
            # the sabotaged actor died mid-deploy and its channels are
            # closed — the stop barrier can't reach it. The purge
            # contract (asserted above) is what this test guards.
            pass
        return summary, series

    summary, series = asyncio.run(run())
    assert "doomed_mv" not in summary
    assert all(l.get("mv") != "doomed_mv" for l in series)


# -- ctl cost --------------------------------------------------------------

def test_ctl_cost_verb(tmp_path, capsys):
    """`ctl cost` prints the per-MV cost table and hot keys against a
    recovered data dir."""
    from risingwave_tpu.__main__ import main as cli_main
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    d = str(tmp_path / "rw")

    async def seed():
        fe = Frontend(HummockLite(LocalFsObjectStore(d)), min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=1000, "
            "nexmark.min.event.gap.in.ns=100000000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW agg AS SELECT auction, "
            "count(*) AS c FROM bid GROUP BY auction")
        await fe.step(4)
        await fe.close()

    asyncio.run(seed())
    with pytest.raises(SystemExit) as e:
        cli_main(["ctl", "--data-dir", d, "cost", "--steps", "2"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    assert "per-MV serving cost" in out
    assert "agg" in out
