"""Epoch phase ledger (ISSUE 11): scoped-phase accounting units, the
conservation gate, transfer-bytes exactness, worker-merge on a real
2-worker cluster, the ledger-on-vs-off q7 oracle, and the
rw_metrics_history per-barrier feed over SQL.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from risingwave_tpu.utils import spans as spans_mod
from risingwave_tpu.utils.ledger import (
    LEDGER, AttributionCell, PhaseLedger, UNATTRIBUTED,
)
from risingwave_tpu.utils.metrics import HISTORY, STREAMING

EVENTS = 4000

BID_SOURCE = (
    "CREATE SOURCE bid WITH (connector='nexmark', "
    "nexmark.table.type='bid', nexmark.event.num={n}, "
    "nexmark.max.chunk.size=256, nexmark.min.event.gap.in.ns=50000000)")

Q7ISH_MV = (
    "CREATE MATERIALIZED VIEW q7 AS "
    "SELECT window_start, MAX(price) AS max_price, COUNT(*) AS cnt "
    "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
    "GROUP BY window_start")


@pytest.fixture(autouse=True)
def _fresh_ledger():
    """Each test starts with an empty ledger/history (the conftest
    conservation gate also clears records around every test; this
    additionally resets the history ring and the epoch key)."""
    LEDGER.clear()
    HISTORY.clear()
    spans_mod.set_current_epoch(0)
    yield
    LEDGER.clear()
    HISTORY.clear()


# -- scoping / nesting units ----------------------------------------------


def test_phase_scopes_are_exclusive_under_nesting():
    """A nested scope's time is charged to the INNER phase only; phase
    totals never double-count a wall-clock second."""
    led = PhaseLedger()
    spans_mod.set_current_epoch(42)
    with led.phase("host_pack"):
        time.sleep(0.03)
        with led.phase("h2d"):
            time.sleep(0.03)
    rec = led.seal(42, 0.08)
    pack, h2d = rec.seconds["host_pack"], rec.seconds["h2d"]
    assert 0.02 <= pack <= 0.06, rec.seconds
    assert 0.02 <= h2d <= 0.06, rec.seconds
    # exclusivity: the two phases together cover ~the elapsed wall
    # clock once, not the outer scope twice
    assert pack + h2d <= 0.08 + 0.02


def test_cell_commit_routes_epoch_exactly_and_tracks_bytes():
    """Scopes fired under an executor cell land in the cell (not the
    newest injected epoch) and commit to the BARRIER's epoch — the
    pipelined-inject attribution fix."""
    led = PhaseLedger()
    spans_mod.set_current_epoch(99)     # newest injected
    cell = AttributionCell()
    tok = led.push_cell(cell)
    try:
        with led.phase("host_pack"):
            time.sleep(0.01)
        led.add_bytes("h2d", 1234, kernel="unit-cell")
    finally:
        led.pop_cell(tok)
    assert cell.seconds["host_pack"] > 0
    assert cell.h2d_bytes == 1234
    # nothing leaked into epoch 99's open accumulator
    led.commit_cell(7, cell)            # the barrier's CURR epoch
    rec99 = led.seal(99, 0.001)
    assert "host_pack" not in rec99.seconds
    rec7 = led.seal(7, 0.02)
    assert rec7.seconds["host_pack"] > 0
    assert rec7.h2d_bytes == 1234
    # the cell drained at commit
    assert cell.named_total() == 0 and cell.h2d_bytes == 0


def test_conservation_residual_and_gate_exemptions():
    led = PhaseLedger()
    led.attribute("device_compute", 0.1, epoch=1)
    rec = led.seal(1, 1.0)
    assert rec.seconds[UNATTRIBUTED] == pytest.approx(0.9)
    assert rec.coverage() == pytest.approx(0.1)
    assert len(led.gate_violations()) == 1
    # a compile-bearing (warmup) epoch is exempt
    spans_mod.set_current_epoch(2)
    led.note_compile()
    led.seal(2, 1.0)
    # a mutation/topology barrier is exempt via the warmup flag
    led.seal(3, 1.0, warmup=True)
    # an unmerged distributed record is exempt (conservation defers
    # to the worker-ledger merge)
    led.seal(4, 1.0, distributed=True)
    assert len(led.gate_violations()) == 1


def test_worker_merge_recomputes_residual():
    """ingest() folds a drained worker accumulator into the sealed
    record of the same epoch and re-derives `unattributed`."""
    led = PhaseLedger()
    rec = led.seal(11, 1.0, distributed=True)
    assert rec.unattributed_s == pytest.approx(1.0)
    n = led.ingest([{"epoch": 11,
                     "seconds": {"host_emit": 0.7},
                     "h2d_bytes": 10, "d2h_bytes": 20}],
                   worker="worker-0")
    assert n == 1
    assert rec.seconds["host_emit"] == pytest.approx(0.7)
    assert rec.unattributed_s == pytest.approx(0.3)
    assert rec.workers == ["worker-0"]
    assert not rec.distributed          # conservation now checkable
    assert rec.h2d_bytes == 10 and rec.d2h_bytes == 20


# -- transfer bytes exactness ----------------------------------------------


def test_transfer_bytes_exact_for_known_upload_and_fetch():
    from risingwave_tpu.utils import jaxtools

    arr = np.arange(512, dtype=np.int32).reshape(128, 4)   # 2048 B
    spans_mod.set_current_epoch(21)
    h0 = STREAMING.transfer_bytes.get(dir="h2d", kernel="unit-xfer")
    d0 = STREAMING.transfer_bytes.get(dir="d2h", kernel="unit-xfer")
    dev = jaxtools.upload(arr, kernel="unit-xfer")
    assert STREAMING.transfer_bytes.get(
        dir="h2d", kernel="unit-xfer") - h0 == arr.nbytes
    with LEDGER.kernel_scope("unit-xfer"):
        [back] = jaxtools.fetch(dev)
    assert np.array_equal(back, arr)
    assert STREAMING.transfer_bytes.get(
        dir="d2h", kernel="unit-xfer") - d0 == arr.nbytes
    # host numpy pass-throughs never count as transfers
    with LEDGER.kernel_scope("unit-xfer"):
        jaxtools.fetch(arr)
    assert STREAMING.transfer_bytes.get(
        dir="d2h", kernel="unit-xfer") - d0 == arr.nbytes
    # and the per-epoch accumulators carry the same exact bytes
    rec = LEDGER.seal(21, 1.0, warmup=True)
    assert rec.h2d_bytes == arr.nbytes
    assert rec.d2h_bytes == arr.nbytes


def test_kernel_cost_analysis_surfaces():
    """instrumented_jit captures call shapes; cost_analysis serves the
    compiled program's flops/bytes (the device_compute yardstick)."""
    import jax.numpy as jnp

    from risingwave_tpu.utils import jaxtools

    f = jaxtools.instrumented_jit(lambda x: x * 2 + 1,
                                  "unit.cost_kernel")
    f(jnp.arange(64))
    ca = f.cost_analysis()
    assert ca is not None and ca["flops"] > 0
    rows = jaxtools.kernel_cost_rows()
    assert any(label == "unit.cost_kernel" for label, _f, _b in rows)
    assert jaxtools.publish_kernel_costs() >= 1
    assert STREAMING.kernel_flops.get(kernel="unit.cost_kernel") > 0


# -- perfetto counter tracks ----------------------------------------------


def test_seal_emits_phase_lanes_and_counter_tracks():
    from risingwave_tpu.utils.spans import EPOCH_TRACER

    EPOCH_TRACER.clear()
    spans_mod.set_current_epoch(33)
    t_before = time.time()
    with LEDGER.phase("device_compute"):
        time.sleep(0.004)
    with LEDGER.phase("host_pack"):
        pass                    # under SPAN_MIN_S: books only, no span
    LEDGER.add_bytes("h2d", 4096, kernel="unit-track")
    LEDGER.seal(33, 0.01, warmup=True)
    out = json.loads(json.dumps(EPOCH_TRACER.export_chrome(
        epochs=[33])))
    cs = [e for e in out["traceEvents"] if e["ph"] == "C"]
    names = {e["name"] for e in cs}
    assert "transfer_h2d_bytes" in names, names
    assert "uploader_queue_depth" in names
    [h2d] = [e for e in cs if e["name"] == "transfer_h2d_bytes"]
    assert h2d["args"]["value"] == 4096.0
    # a scoped phase rides as an ordinary X span under cat=phase, where
    # and for as long as it ran (no lane stacked from the interval's
    # start at the seal any more)
    xs = [e for e in out["traceEvents"]
          if e["ph"] == "X" and e["cat"] == "phase"]
    [dc] = [e for e in xs if e["name"] == "phase.device_compute"]
    assert dc["dur"] >= 4000.0
    assert t_before * 1e6 <= dc["ts"] <= time.time() * 1e6
    assert not any(e["name"] == "phase.host_pack" for e in xs)
    EPOCH_TRACER.clear()


# -- conservation under an injected stall (end-to-end) ---------------------


def test_sleep_failpoint_surfaces_as_unattributed():
    """A sleep failpoint on the barrier's commit path is wall time NO
    phase can claim: the sealed epoch publishes it as `unattributed`
    and the strict gate flags it."""
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.utils.failpoint import failpoints

    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute(BID_SOURCE.format(n=EVENTS))
        await fe.execute(Q7ISH_MV)
        await fe.step(3)                 # warmup: compiles done
        with failpoints({"barrier.collect": {"sleep_s": 0.8,
                                             "times": 1}}):
            await fe.step(1)
        rows = await fe.execute("SELECT * FROM q7")
        await fe.close()
        return rows

    asyncio.run(run())
    stalled = [r for r in LEDGER.records
               if not r.warmup and r.unattributed_s > 0.5]
    assert stalled, [r.to_dict() for r in LEDGER.records]
    assert stalled[0].coverage() < 0.5
    # the gate catches exactly this rot
    assert LEDGER.gate_violations()
    # clear before the conftest strict gate reads the records — this
    # test INJECTED the violation on purpose
    LEDGER.clear()


# -- q7: a session's epochs and bytes reach the books ----------------------


def test_q7_ledger_seals_epochs_and_counts_bytes():
    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(BID_SOURCE.format(n=EVENTS))
        await fe.execute(Q7ISH_MV)
        await fe.step(10)
        rows = await fe.execute("SELECT * FROM q7")
        await fe.close()
        return rows

    assert asyncio.run(run())
    assert len(LEDGER.records) >= 8
    steady = [r for r in LEDGER.records if not r.warmup]
    assert steady
    # the flagship kernel moved bytes BOTH directions while on
    kernels_h2d = {l.get("kernel") for l, _v in
                   STREAMING.transfer_bytes.series()
                   if l.get("dir") == "h2d"}
    kernels_d2h = {l.get("kernel") for l, _v in
                   STREAMING.transfer_bytes.series()
                   if l.get("dir") == "d2h"}
    assert any("HashAgg" in k for k in kernels_h2d), kernels_h2d
    assert any("HashAgg" in k for k in kernels_d2h), kernels_d2h


# -- rw_metrics_history over SQL -------------------------------------------


def test_metrics_history_over_sql_32_barriers():
    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend(min_chunks=2)
        await fe.execute(BID_SOURCE.format(n=EVENTS))
        await fe.execute(Q7ISH_MV)
        for _ in range(34):
            await fe.step(1)
        rows = await fe.execute("SELECT * FROM rw_metrics_history")
        await fe.close()
        return rows

    rows = asyncio.run(run())
    # long format: (seq, epoch, ts, interval_s, name, value)
    seqs = {r[0] for r in rows}
    assert len(seqs) >= 32, len(seqs)
    names = {r[4] for r in rows}
    # tracked registry series + the ledger's phase extras ride along
    assert {"source_rows", "device_dispatches", "h2d_bytes",
            "d2h_bytes", "uploader_queue_depth",
            "coverage"} <= names, names
    assert any(n.startswith("phase.") for n in names)
    # per-barrier deltas: source rows moved on data-bearing barriers
    moved = [r[5] for r in rows if r[4] == "source_rows"]
    assert sum(moved) > 0
    # coverage per barrier is a fraction
    for r in rows:
        if r[4] == "coverage":
            assert 0.0 <= r[5] <= 1.0


# -- 2-worker cluster merge ------------------------------------------------


def test_cluster_two_worker_ledger_merge(tmp_path):
    """Worker-side phase time folds into the coordinator's sealed
    records: before the drain a distributed record is coordinator-only
    (conservation deferred); after, worker tags appear, attributed
    time grows, and the residual is recomputed."""
    from risingwave_tpu.cluster.session import DistFrontend

    async def run():
        fe = DistFrontend(str(tmp_path), n_workers=2, parallelism=2)
        await fe.start()
        try:
            await fe.execute(BID_SOURCE.format(n=EVENTS))
            await fe.execute(Q7ISH_MV)
            await fe.step(6)
            pre = {r.epoch: (r.attributed_s, r.distributed,
                             list(r.workers))
                   for r in LEDGER.records}
            n = await fe.drain_ledger()
            rows = await fe.execute("SELECT * FROM q7")
            return pre, n, rows
        finally:
            await fe.close()

    pre, n, rows = asyncio.run(run())
    assert rows, "q7 produced no rows on the cluster"
    assert n > 0, "workers shipped no ledger accumulators"
    assert all(dist for _a, dist, _w in pre.values()), \
        "pre-merge records must be marked distributed"
    merged = [r for r in LEDGER.records if r.workers]
    assert merged, "no record absorbed worker phase time"
    grew = [r for r in merged
            if r.attributed_s > pre[r.epoch][0] + 1e-9]
    assert grew, "merge did not add worker-side attributed time"
    assert all(not r.distributed for r in merged)
    # a second drain is a no-op (drained accumulators left the worker)
    # — checked implicitly: records/workers are stable because the
    # drain above popped everything; the conftest gate then audits the
    # merged records' conservation like any other test's.


# -- parallel-source idle dedup (ISSUE 12 satellite) -----------------------


def test_parallel_idle_sources_cannot_exceed_share_one():
    """Regression for an ad-ctr phase breakdown: four
    sources each parked ~the whole epoch summed to barrier_wait share
    1.05. Idle is keyed per source and the seal folds the MAX (the
    parks are concurrent), capped at the interval — the share can
    never exceed 1.0."""
    led = PhaseLedger()
    epoch = 0x1000
    for actor in range(4):
        led.attribute_idle(0.95, epoch, source=f"actor-{actor}/src")
    rec = led.seal(epoch, 1.0)
    assert rec.seconds["barrier_wait"] == pytest.approx(0.95)
    share = rec.seconds["barrier_wait"] / rec.interval_s
    assert share <= 1.0
    # and a single source longer than the interval still caps
    led2 = PhaseLedger()
    led2.attribute_idle(3.0, epoch, source="a")
    rec2 = led2.seal(epoch, 1.0)
    assert rec2.seconds["barrier_wait"] == pytest.approx(1.0)


def test_worker_idle_merges_as_max_not_sum():
    """Cross-process merge: each worker ships its own idle_max; the
    sealed record folds max-then-cap, never the sum."""
    led = PhaseLedger()
    epoch = 0x2000
    led.attribute_idle(0.4, epoch, source="coord-src")
    rec = led.seal(epoch, 1.0, distributed=True)
    assert rec.seconds["barrier_wait"] == pytest.approx(0.4)
    led.ingest([{"epoch": epoch, "seconds": {}, "idle_max": 0.9}],
               worker="w0")
    led.ingest([{"epoch": epoch, "seconds": {}, "idle_max": 0.7}],
               worker="w1")
    assert rec.seconds["barrier_wait"] == pytest.approx(0.9)
    # a worker idling past the interval caps at the interval
    led.ingest([{"epoch": epoch, "seconds": {}, "idle_max": 5.0}],
               worker="w2")
    assert rec.seconds["barrier_wait"] == pytest.approx(1.0)
