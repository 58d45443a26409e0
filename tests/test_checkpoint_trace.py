"""ISSUE 25: the checkpoint path and the event loop, traced from inside.

Stage spans from seal to durable commit (storage/uploader.py), stolen
loop time out of the executors' books (utils/ledger.py "Stolen loop
time"), the program's spans on the profiler's clock, device programs
named after their kernel label, per-executor seconds in the history,
and the benchmark's readers of all of it.
"""

import asyncio
import glob
import importlib.util
import json
import os
import time

import pytest

from risingwave_tpu.utils import ledger as ledger_mod
from risingwave_tpu.utils import spans as spans_mod
from risingwave_tpu.utils.ledger import LEDGER, LOOP_PHASES, PHASES
from risingwave_tpu.utils.metrics import HISTORY, MetricsHistory

BID_SOURCE = (
    "CREATE SOURCE bid WITH (connector='nexmark', "
    "nexmark.table.type='bid', nexmark.event.num=200000, "
    "nexmark.max.chunk.size=256, nexmark.min.event.gap.in.ns=50000000)")

MV = (
    "CREATE MATERIALIZED VIEW v AS "
    "SELECT window_start, MAX(price) AS max_price, COUNT(*) AS cnt "
    "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
    "GROUP BY window_start")

STAGES = ("ckpt.queue_s", "ckpt.build_s", "ckpt.put_s", "ckpt.commit_s",
          "ckpt.compact_s", "ckpt.sink_stage_s")


@pytest.fixture(autouse=True)
def _fresh():
    LEDGER.clear()
    HISTORY.clear()
    spans_mod.set_current_epoch(0)
    yield
    LEDGER.clear()
    HISTORY.clear()


def _frontend():
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore
    return Frontend(HummockLite(MemObjectStore()), min_chunks=4)


def _history_by_epoch(rows):
    out = {}
    for _seq, epoch, _ts, interval_s, name, value, _dom in rows:
        out.setdefault(epoch, {"interval_s": interval_s})[name] = value
    return out


# -- 1. stage spans ----------------------------------------------------------


def test_stage_durations_add_up_to_upload_s_and_land_in_history():
    async def run():
        # the flight recorder is process-wide: a compaction of whatever
        # test ran before in this worker must not be counted below
        spans_mod.EPOCH_TRACER.clear()
        fe = _frontend()
        await fe.execute(BID_SOURCE)
        await fe.execute(MV)
        compacted = []
        for _ in range(10):
            l0_before = fe.store.levels[0]
            await fe.step(1)
            # L0 falls back to nothing exactly when the commit compacted
            compacted.append(fe.store.levels[0] < l0_before)
        lat = await fe.execute("SELECT * FROM rw_barrier_latency")
        hist = await fe.execute("SELECT * FROM rw_metrics_history")
        trace = spans_mod.EPOCH_TRACER.rows()
        await fe.close()
        return compacted, lat[-10:], _history_by_epoch(hist), trace

    compacted, lat, hist, trace = asyncio.run(run())
    assert sum(compacted) >= 2, compacted
    for did_compact, row in zip(compacted, lat):
        epoch, upload_s = row[0], row[8]
        h = hist[epoch]
        assert upload_s > 0
        assert sum(h.get(k, 0.0) for k in STAGES) == pytest.approx(
            upload_s, abs=1e-6)
        assert h["ckpt.sst_bytes"] > 0 and h["ckpt.build_s"] > 0
        assert (h["ckpt.compact_s"] > 0) == did_compact, (epoch, h)
        assert (h["ckpt.compact_read_bytes"] > 0) == did_compact
        assert (h["ckpt.compact_write_bytes"] > 0) == did_compact
    # the stages are spans of the sealing barrier's epoch, below its
    # checkpoint.upload
    epoch = lat[-1][0]
    spans = {r[3]: r for r in trace if r[0] == epoch}
    upload = spans["checkpoint.upload"]
    for name in ("checkpoint.queue", "checkpoint.build", "checkpoint.put",
                 "checkpoint.commit"):
        assert spans[name][2] == upload[1], name      # parent_id
    build = json.loads(spans["checkpoint.build"][10])
    assert build["entries"] > 0 and build["tables"] >= 1
    compact = [r for r in trace if r[3] == "checkpoint.compact"]
    assert len(compact) == sum(compacted)
    detail = json.loads(compact[-1][10])
    assert detail["mode"] == "inline" and detail["ssts_read"] >= 4
    assert detail["entries_dropped"] >= 0 and detail["read_bytes"] > 0


def test_history_amend_adds_names_to_a_sealed_row():
    h = MetricsHistory(capacity=4)
    h.observe(7, 0.5, extra={"phase.host_emit": 0.1})
    h.observe(8, 0.5)
    h.amend(7, {"ckpt.build_s": 0.25})
    h.amend(99, {"ckpt.build_s": 1.0})       # rolled past: left alone
    by = _history_by_epoch(h.rows())
    assert by[7]["ckpt.build_s"] == 0.25 and by[7]["phase.host_emit"] == 0.1
    assert "ckpt.build_s" not in by[8] and 99 not in by


def test_dedicated_compactor_leaves_the_same_span_off_the_loop():
    from risingwave_tpu.storage.compactor import execute_task
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore

    obj = MemObjectStore()
    h = HummockLite(obj)
    h.compaction_mode = "dedicated"
    for e in range(1, 5):
        h.ingest_batch(1, [(b"k%d" % i, (i, e)) for i in range(20)], e)
        h.seal_epoch(e)
        h.sync(e)
    snap = h.level_snapshot()
    ids = [i["id"] for i in snap["l0"]]
    grant = h.reserve_task(ids, 4)
    spans_mod.EPOCH_TRACER.clear()
    stolen = ledger_mod.stolen_s()
    result = execute_task(obj, {"inputs_l0": snap["l0"], "inputs_l1": [],
                                "safe_epoch": h.committed_epoch(),
                                "bottom": True, **grant})
    assert "entries_dropped" not in result
    [span] = [r for r in spans_mod.EPOCH_TRACER.rows()
              if r[3] == "checkpoint.compact"]
    detail = json.loads(span[10])
    assert detail["mode"] == "dedicated" and detail["ssts_read"] == 4
    assert detail["entries_dropped"] == 60     # 3 shadowed versions x 20
    # off the loop: it is no LOOP phase and steals nothing
    assert ledger_mod.stolen_s() == stolen


def _merge_entries():
    from risingwave_tpu.utils.metrics import STORAGE
    return {path: STORAGE.compaction_merge_entries.get(path=path)
            for path in ("native", "python")}


def test_compact_says_which_merge_ran_and_what_it_read():
    """ISSUE 26: `compact()`'s counts and the `checkpoint.compact` span
    carry `merge` (which path: storage/merge.py) and `entries_in`, and
    `compaction_merge_entries{path}` adds up to the spans' entries."""
    from risingwave_tpu import native

    path = "native" if native.lib() is not None else "python"
    spans_mod.EPOCH_TRACER.clear()
    before = _merge_entries()

    async def run():
        fe = _frontend()
        await fe.execute(BID_SOURCE)
        await fe.execute(MV)
        await fe.step(9)
        direct = fe.store.compact()             # a manual pass on top
        trace = spans_mod.EPOCH_TRACER.rows()
        await fe.close()
        return direct, trace

    direct, trace = asyncio.run(run())
    details = [json.loads(r[10]) for r in trace
               if r[3] == "checkpoint.compact"]
    assert len(details) >= 2
    for d in details:
        assert d["merge"] == path and d["mode"] == "inline"
        assert d["entries_in"] >= d["entries_dropped"] >= 0
        assert d["entries_in"] > 0
    assert direct["merge"] == path and direct["entries_in"] > 0
    after = _merge_entries()
    other = "python" if path == "native" else "native"
    assert after[other] == before[other]
    assert after[path] - before[path] \
        == sum(d["entries_in"] for d in details) + direct["entries_in"]


def test_dedicated_span_carries_merge_and_entries_in():
    from risingwave_tpu import native
    from risingwave_tpu.storage.compactor import execute_task
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore

    obj = MemObjectStore()
    h = HummockLite(obj)
    h.compaction_mode = "dedicated"
    for e in range(1, 5):
        h.ingest_batch(1, [(b"k%d" % i, (i, e)) for i in range(20)], e)
        h.seal_epoch(e)
        h.sync(e)
    snap = h.level_snapshot()
    grant = h.reserve_task([i["id"] for i in snap["l0"]], 4)
    spans_mod.EPOCH_TRACER.clear()
    before = _merge_entries()
    result = execute_task(obj, {"inputs_l0": snap["l0"], "inputs_l1": [],
                                "safe_epoch": h.committed_epoch(),
                                "bottom": True, **grant})
    assert "merge" not in result and "entries_in" not in result
    [span] = [r for r in spans_mod.EPOCH_TRACER.rows()
              if r[3] == "checkpoint.compact"]
    detail = json.loads(span[10])
    path = "native" if native.lib() is not None else "python"
    assert detail["merge"] == path and detail["entries_in"] == 80
    assert _merge_entries()[path] - before[path] == 80


# -- 2. stolen loop time -----------------------------------------------------


def test_phases_grew_by_the_two_loop_phases():
    assert PHASES[-2:] == ("checkpoint", "compaction")
    assert LOOP_PHASES == {"checkpoint", "compaction"}


def test_actor_clock_stands_still_while_a_loop_phase_holds_the_loop():
    async def parked():
        t0, w0 = ledger_mod.actor_clock(), time.perf_counter()
        await asyncio.sleep(0.05)
        return ledger_mod.actor_clock() - t0, time.perf_counter() - w0

    async def run():
        waiter = asyncio.ensure_future(parked())
        await asyncio.sleep(0)               # let it park
        with LEDGER.phase("compaction"):
            time.sleep(0.3)                  # foreign, synchronous
        return await waiter

    before = ledger_mod.stolen_s()
    mine, wall = asyncio.run(run())
    assert ledger_mod.stolen_s() - before == pytest.approx(0.3, abs=0.05)
    assert wall >= 0.3
    assert mine == pytest.approx(wall - 0.3, abs=0.05)


def test_loop_phase_nested_in_another_is_stolen_once():
    before = ledger_mod.stolen_s()
    with LEDGER.phase("checkpoint"):
        time.sleep(0.02)
        with LEDGER.phase("compaction"):
            time.sleep(0.05)
    spans_mod.set_current_epoch(5)
    rec = LEDGER.seal(5, 0.2, warmup=True)
    assert ledger_mod.stolen_s() - before == pytest.approx(0.07, abs=0.02)
    assert rec.seconds["compaction"] == pytest.approx(0.05, abs=0.02)
    assert rec.seconds["checkpoint"] == pytest.approx(0.02, abs=0.015)


def test_loop_sections_wait_for_the_epoch_in_whose_interval_they_ran():
    t = time.monotonic()
    LEDGER._loop_pending = [
        [t + 0.0, t + 1.0, "compaction", 1.0],   # before the wake gap
        [t + 1.5, t + 2.5, "checkpoint", 1.0],   # half inside it
        [t + 2.2, t + 2.8, "compaction", 0.6],   # inside it
    ]
    rec = LEDGER.seal(1, 3.0, warmup=True, wake_gap=(t + 2.0, t + 3.0))
    assert rec.seconds["compaction"] == pytest.approx(1.0)
    assert rec.seconds["checkpoint"] == pytest.approx(0.5)
    # the rest is the next epoch's; a section that ran between the two
    # epochs' intervals stretches the second one's
    rec2 = LEDGER.seal(2, 1.0, warmup=True, between=(t + 2.0, t + 2.5))
    assert rec2.seconds["checkpoint"] == pytest.approx(0.5)
    assert rec2.seconds["compaction"] == pytest.approx(0.6)
    assert rec2.interval_s == pytest.approx(1.0 + 0.5 + 0.3)
    assert not LEDGER._loop_pending


def test_scope_after_the_newest_epoch_sealed_goes_to_the_next_seal():
    spans_mod.set_current_epoch(11)
    LEDGER.seal(11, 0.1, warmup=True)
    with LEDGER.phase("host_pack"):
        time.sleep(0.01)
    assert 11 not in LEDGER._open          # no books that never close
    spans_mod.set_current_epoch(12)
    rec = LEDGER.seal(12, 0.1, warmup=True)
    assert rec.seconds["host_pack"] >= 0.01


def test_a_park_is_barrier_wait_only_where_nothing_else_worked():
    LEDGER.attribute("host_emit", 0.7, epoch=3)
    LEDGER.attribute_idle(0.9, epoch=3, source="s")
    rec = LEDGER.seal(3, 1.0, warmup=True)
    assert rec.seconds["barrier_wait"] == pytest.approx(0.3)
    assert rec.attributed_s == pytest.approx(1.0)


def test_injected_stalls_land_in_checkpoint_and_compaction():
    """A sleep in build_ssts and one in compact() are the LOOP phases'
    seconds on the epoch in whose interval they ran, and nobody else's:
    the sources parked meanwhile and the executors waiting on them keep
    them out of barrier_wait and host_emit."""
    from risingwave_tpu.utils.failpoint import failpoints

    async def run():
        fe = _frontend()
        await fe.execute(BID_SOURCE)
        await fe.execute(MV)
        await fe.step(4)                      # warm; the 4th compacts
        n0 = len(LEDGER.records)
        with failpoints({"hummock.sync": {"sleep_s": 0.5, "times": 1},
                         "hummock.compact": {"sleep_s": 0.6}}):
            await fe.step(5)                  # one build stall, one compaction
        await fe.step(1)
        await fe.close()
        return n0

    n0 = asyncio.run(run())
    recs = [r for r in list(LEDGER.records)[n0:] if not r.warmup]
    ck = max(recs, key=lambda r: r.seconds.get("checkpoint", 0.0))
    co = max(recs, key=lambda r: r.seconds.get("compaction", 0.0))
    assert ck.seconds["checkpoint"] >= 0.5
    assert co.seconds["compaction"] >= 0.6
    for rec in (ck, co):
        assert rec.seconds.get("host_emit", 0.0) < 0.3, rec.to_dict()
        assert rec.seconds.get("barrier_wait", 0.0) < 0.3, rec.to_dict()
        assert rec.attributed_s <= rec.interval_s + 0.05, rec.to_dict()
        assert rec.unattributed_s < 0.25, rec.to_dict()
    # the history carries both phases and the executors' seconds by kind
    by = _history_by_epoch(HISTORY.rows())
    h = by[co.epoch]
    assert h["phase.compaction"] >= 0.6 and "phase.checkpoint" in h
    kinds = {k for k in h if k.startswith("exec_s.")}
    assert "exec_s.HashAggExecutor" in kinds or any(
        "Agg" in k for k in kinds), kinds
    assert sum(h[k] for k in kinds) <= h["interval_s"] + 0.05


def test_a_join_keeps_its_own_seconds_beside_concurrent_inputs():
    """A join pulls both inputs concurrently; their busy times overlap
    and used to swallow the join's whole exclusive time."""
    from risingwave_tpu.utils.failpoint import failpoints

    auction = BID_SOURCE.replace("SOURCE bid", "SOURCE auction").replace(
        "'bid'", "'auction'")
    person = BID_SOURCE.replace("SOURCE bid", "SOURCE person").replace(
        "'bid'", "'person'")

    async def run():
        fe = _frontend()
        await fe.execute(auction)
        await fe.execute(person)
        await fe.execute(
            "CREATE MATERIALIZED VIEW j AS SELECT a.id AS aid, p.name "
            "FROM auction AS a JOIN person AS p ON a.seller = p.id")
        await fe.step(3)
        with failpoints({"trace.slow.HashJoinExecutor":
                         {"sleep_s": 0.3, "times": 1}}):
            await fe.step(1)
        await fe.close()

    asyncio.run(run())
    by = _history_by_epoch(HISTORY.rows())
    assert max(h.get("exec_s.HashJoinExecutor", 0.0)
               for h in by.values()) >= 0.3


# -- 3. one clock ------------------------------------------------------------


def _contains(outer, inner) -> bool:
    return outer[0] <= inner[0] and inner[0] + inner[1] <= outer[0] + outer[1]


def test_program_spans_are_in_the_profilers_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    async def run():
        fe = _frontend()
        await fe.execute(BID_SOURCE)
        await fe.execute(MV)
        await fe.step(3)                  # warm; L0 holds three runs
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            await fe.step(2)              # the first of them compacts
        finally:
            jax.profiler.stop_trace()
        await fe.close()

    asyncio.run(run())
    [path] = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    by_name = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                by_name.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    for name in ("phase.host_ingest", "phase.device_compute.launch",
                 "phase.checkpoint", "phase.compaction", "barrier.inject",
                 "barrier.collect", "barrier.commit", "checkpoint.queue",
                 "checkpoint.build", "checkpoint.put", "checkpoint.commit",
                 "checkpoint.compact"):
        assert name in by_name, (name, sorted(by_name))
    assert all("epoch" in stats for _s, _d, stats
               in by_name["checkpoint.build"])
    # nested as they ran: the compaction inside the commit that
    # triggered it, its ledger phase inside that; the build's ledger
    # phase inside the build; a dispatch's device_compute inside the
    # dispatch, under its kernel label
    [compact] = by_name["checkpoint.compact"]
    assert any(_contains(c, compact) for c in by_name["checkpoint.commit"])
    assert any(_contains(compact, p) for p in by_name["phase.compaction"])
    for build in by_name["checkpoint.build"]:
        assert any(_contains(build, p) for p in by_name["phase.checkpoint"])
    labels = [n for n in by_name
              if n.startswith(("hash_agg", "fused", "hash_table"))
              or "Executor" in n]
    assert labels, sorted(by_name)
    assert any(_contains(d, p) for n in labels for d in by_name[n]
               for p in by_name["phase.device_compute.launch"])


# -- 4. device programs carry their label ------------------------------------


@pytest.mark.parametrize("label,module", [
    ("a.b", "jit_a_b"),
    ("hash_join.epoch_apply", "jit_hash_join_epoch_apply"),
    # a fused prelude's signature can run to hundreds of characters,
    # and the compile cache names a file after the program
    ("hash_join.epoch_apply[" + "(bigint),(varchar)," * 40 + "]",
     "jit_hash_join_epoch_apply_09a2f8f5"),
])
def test_instrumented_jit_names_the_program_after_its_label(label, module):
    import jax.numpy as jnp
    from risingwave_tpu.utils.jaxtools import instrumented_jit

    def ap(x):
        return x + 1

    jitted = instrumented_jit(ap, label=label)
    text = jitted._jit.lower(jnp.ones(4)).as_text()
    assert f"module @{module} " in text.splitlines()[0]
    assert float(jitted(jnp.ones(4))[0]) == 2.0


def test_a_cancelled_heartbeat_ends_cancelled():
    """The benchmark pauses the heartbeat by cancelling it under the
    barrier lock and takes a task that is done but NOT cancelled for a
    heartbeat that died (`benchmark/run.py` `Heartbeat.check`). Its
    traced run keeps checking for the 12 s it profiles, so once q7's
    window closes before that span does (PR 26: 10 s), a heartbeat
    that swallowed its cancellation failed every traced run."""
    async def run():
        fe = _frontend()
        hb = asyncio.ensure_future(fe.run_heartbeat(0.01))
        await asyncio.sleep(0.05)
        async with fe._barrier_lock:
            hb.cancel()
        await asyncio.gather(hb, return_exceptions=True)
        await fe.close()
        return hb

    hb = asyncio.run(run())
    assert hb.done() and hb.cancelled()


# -- the benchmark's readers -------------------------------------------------


def _reader(name):
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


RECORD = {
    "window": {"wall_s": 20.0},
    "phase_seconds": {"compaction": 5.0, "checkpoint": 2.0,
                      "unattributed": 0.5, "host_emit": 1.0},
    "history": {
        1: {"ckpt.build_s": 0.1, "ckpt.put_s": 0.01, "ckpt.queue_s": 0.0,
            "exec_s.HashAggExecutor": 1.0, "exec_s.HashJoinExecutor": 0.5,
            "exec_s.SourceExecutor": 3.0},
        2: {"ckpt.build_s": 0.3, "ckpt.put_s": 0.03, "ckpt.queue_s": 2.0,
            "exec_s.HashAggExecutor": 2.0, "exec_s.FusedAggExecutor": 1.0,
            "exec_s.HashJoinExecutor": 0.5},
        3: {"ckpt.build_s": 0.2, "ckpt.put_s": 0.02, "ckpt.queue_s": 1.0},
    },
}
# what a program without this PR leaves: none of the names
BARE = {"window": {"wall_s": 20.0}, "phase_seconds": {"host_emit": 1.0},
        "history": {1: {"phase.host_emit": 1.0, "source_rows": 4.0}}}


@pytest.mark.parametrize("name,value", [
    ("compaction_share", 25.0),
    ("ckpt_loop_share", 10.0),
    ("unattributed_share", 2.5),
    ("ckpt_build_p50_ms", 200.0),
    ("ckpt_put_p50_ms", 20.0),
    ("ckpt_queue_p50_ms", 1000.0),
    ("agg_host_share", 20.0),
    ("join_host_share", 5.0),
])
def test_layer_metric_reader(name, value):
    read = _reader(name)
    assert read(RECORD) == pytest.approx(value)
    assert read(BARE) is None
