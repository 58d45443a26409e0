"""Epoch-causal tracing (ISSUE 7): flight recorder, span propagation,
slow-barrier promotion + straggler diagnosis, Perfetto export, SQL/ctl
surfaces, and the steady-state recompile guard.

The acceptance case: a forced-slow barrier in a 2-worker cluster yields
ONE causally-linked trace — coordinator inject → worker actor spans →
cross-worker exchange edge → device dispatch → commit — exported as
valid Chrome trace-event JSON, with the straggler diagnosis naming the
injected laggard (a sleep-spec failpoint on the agg executor).
"""

import asyncio
import json
import os
import struct

import pytest

from risingwave_tpu.utils.spans import EPOCH_TRACER, EpochTracer

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
def _fresh_tracer():
    """Each test starts with an empty flight recorder and leaves it
    that way."""
    EPOCH_TRACER.clear()
    yield
    EPOCH_TRACER.clear()


# -- span model / flight recorder -----------------------------------------


def test_flight_recorder_bounds_and_roots():
    t = EpochTracer(epoch_window=4, max_spans=8, retain_slots=2)
    root = t.record("barrier.inject", "barrier", epoch=1)
    t.set_root(1, root)
    child = t.record("HashAggExecutor", "actor", epoch=1, dur_s=0.5,
                     actor=7)
    [s] = [s for s in t.spans_for(1) if s.span_id == child]
    assert s.parent_id == root          # default parent = epoch root
    # per-epoch span cap: overflow is counted, not silently grown
    for i in range(20):
        t.record(f"s{i}", "dispatch", epoch=2)
    assert len(t.spans_for(2)) == 8
    assert t.dropped == 12
    # epoch window: only the newest 4 epochs stay
    for e in range(3, 9):
        t.record("x", "barrier", epoch=e)
    assert 1 not in t.epochs() and 8 in t.epochs()
    # promotion survives the ring rolling past the epoch
    t.record("slow", "actor", epoch=9, dur_s=1.0, actor=3)
    t.promote(9, "diag-line", total_s=1.0)
    for e in range(10, 20):
        t.record("x", "barrier", epoch=e)
    assert any(s.name == "slow" for s in t.spans_for(9))
    assert t.diagnosis_for(9) == "diag-line"
    # retain_slots bound
    t.promote(18, "a", 1.0)
    t.promote(19, "b", 1.0)
    assert 9 not in t.retained_epochs()


def test_diagnose_names_largest_actor_span():
    t = EpochTracer()
    r = t.record("barrier.inject", "barrier", epoch=5)
    t.set_root(5, r)
    t.record("FilterExecutor", "actor", epoch=5, dur_s=0.1, actor=1)
    t.record("HashAggExecutor(actor=2)", "actor", epoch=5, dur_s=1.6,
             actor=2)
    d = t.diagnose(5, 2.0)
    assert "HashAggExecutor(actor=2)" in d
    assert "actor 2" in d and "80%" in d
    # merged worker spans can retake the diagnosis after promotion
    t.promote(5, t.diagnose(5, 2.0), total_s=2.0)
    t.ingest([{"name": "SlowJoin", "cat": "actor", "epoch": 5,
               "start_s": 0.0, "dur_s": 1.9, "span_id": 999,
               "actor": 9}], worker="worker-1")
    t.refresh_diagnoses()
    assert "SlowJoin" in t.diagnosis_for(5)
    assert "@worker-1" in t.diagnosis_for(5)


def test_chrome_export_is_valid_and_causal():
    t = EpochTracer()
    r = t.record("barrier.inject", "barrier", epoch=3)
    t.set_root(3, r)
    t.record("MaterializeExecutor", "actor", epoch=3, dur_s=0.2,
             actor=4)
    out = json.loads(json.dumps(t.export_chrome()))
    evs = out["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        assert {"name", "cat", "ph", "ts", "dur", "pid",
                "tid"} <= set(e)
    # the causal edge: one s/f flow pair sharing the CHILD's span id,
    # 's' leaving the parent's lane, 'f' landing on the child's lane
    # at its start, start never after finish (Perfetto drops flows
    # whose start postdates their finish)
    child = next(e for e in xs if e["name"] == "MaterializeExecutor")
    cid = child["args"]["span_id"]
    root = next(e for e in xs if e["name"] == "barrier.inject")
    [fs] = [e for e in evs if e["ph"] == "s" and e["id"] == cid]
    [ff] = [e for e in evs if e["ph"] == "f" and e["id"] == cid]
    assert (fs["pid"], fs["tid"]) == (root["pid"], root["tid"])
    assert (ff["pid"], ff["tid"]) == (child["pid"], child["tid"])
    assert fs["ts"] <= ff["ts"] == child["ts"]


def test_p99_breakdown_returns_zeros_on_empty_profiles():
    """Satellite: an empty/fully-warmup-trimmed profile deque yields
    all-zero phases, never a raise."""
    from risingwave_tpu.meta.barrier import EpochProfiler
    p = EpochProfiler()
    zeros = {"inject_to_collect_s": 0.0, "collect_to_commit_s": 0.0,
             "upload_s": 0.0}
    assert p.p99_breakdown() == zeros
    p.record(1, "checkpoint", 0.5, 0.1, 1, {})
    assert p.p99_breakdown()["inject_to_collect_s"] == 0.5
    p.drop_first(10)               # trim past everything recorded
    assert p.p99_breakdown() == zeros


# -- remote-exchange span context ------------------------------------------


def _mk_barrier(mutation=None):
    from risingwave_tpu.common.epoch import Epoch, EpochPair
    from risingwave_tpu.stream.message import Barrier, BarrierKind
    return Barrier(EpochPair(Epoch(0x30000), Epoch(0x20000)),
                   BarrierKind.CHECKPOINT, mutation)


def test_barrier_trailer_roundtrip():
    from risingwave_tpu.stream.message import StopMutation
    from risingwave_tpu.stream.remote import encode_barrier
    from risingwave_tpu.stream.trace_ctx import (
        barrier_trailer, decode_trailer,
    )
    b = _mk_barrier()
    root = EPOCH_TRACER.record("barrier.inject", "barrier",
                               epoch=0x30000)
    EPOCH_TRACER.set_root(0x30000, root)
    payload = encode_barrier(b) + barrier_trailer(b)
    epoch, parent, ts = decode_trailer(payload)
    assert epoch == 0x30000 and parent == root and ts > 0
    # the trailer must survive next to a stop mutation's actor list
    bs = _mk_barrier(StopMutation(frozenset({7, 9})))
    payload = encode_barrier(bs) + barrier_trailer(bs)
    from risingwave_tpu.stream.remote import decode_barrier
    decoded = decode_barrier(payload)
    assert decoded.mutation.actors == frozenset({7, 9})
    assert decode_trailer(payload)[0] == 0x30000
    # the trailer follows the bare barrier frame, which is unchanged
    bare = struct.pack(">BQQB", 2, 0x30000, 0x20000, 0)
    assert encode_barrier(b) == bare
    assert decode_trailer(bare) is None


def test_remote_exchange_propagates_span_context():
    """Round trip over a real TCP exchange edge: the receiver records
    an exchange-transfer span parented to the sender's inject span."""
    from risingwave_tpu.common.types import DataType, Schema
    from risingwave_tpu.stream.remote import ExchangeServer, RemoteInput

    schema = Schema.of(v=DataType.INT64)

    async def run():
        srv = ExchangeServer()
        await srv.serve()
        out = srv.register_edge(11, 22)
        inp = RemoteInput("127.0.0.1", srv.port, 11, 22, schema)
        b = _mk_barrier()
        root = EPOCH_TRACER.record("barrier.inject", "barrier",
                                   epoch=b.epoch.curr.value)
        EPOCH_TRACER.set_root(b.epoch.curr.value, root)

        async def pump():
            await out.send(b)
            out.close()

        task = asyncio.ensure_future(pump())
        got = [m async for m in inp.execute()]
        await task
        await srv.close()
        return got, root, b.epoch.curr.value

    got, root, epoch = asyncio.run(run())
    assert len(got) == 1
    edges = [s for s in EPOCH_TRACER.spans_for(epoch)
             if s.cat == "exchange"]
    assert len(edges) == 1
    assert edges[0].parent_id == root
    assert edges[0].args["edge"] == "11->22"


# -- end-to-end: one process ----------------------------------------------


def _run_q7ish(slow_threshold: float = 1.0, failpoints_armed=None):
    """Frontend + q7-shaped MV; returns (mv rows, promoted epochs,
    diagnoses, trace rows via SQL)."""
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.utils.failpoint import failpoints

    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(BID_SOURCE.format(n=EVENTS))
        await fe.execute(Q7ISH_MV)
        fe.loop.profiler.slow_threshold_s = slow_threshold
        await fe.step(8)
        rows = await fe.execute("SELECT * FROM q7")
        trace_rows = await fe.execute(
            "SELECT * FROM rw_epoch_trace")
        await fe.close()
        return rows, trace_rows

    if failpoints_armed:
        with failpoints(failpoints_armed):
            rows, trace_rows = asyncio.run(run())
    else:
        rows, trace_rows = asyncio.run(run())
    retained = list(EPOCH_TRACER.retained_epochs())
    diags = [EPOCH_TRACER.diagnosis_for(e) for e in retained]
    return {tuple(r) for r in rows}, retained, diags, trace_rows


def test_frontend_trace_end_to_end():
    """A session's barriers leave inject→actor→dispatch→commit spans
    reachable over SQL."""
    rows, _retained, _d, trace_rows = _run_q7ish()
    assert rows
    cats = {r[4] for r in trace_rows}
    assert {"barrier", "actor", "dispatch", "commit"} <= cats, cats
    # warmup compiles are visible events
    assert "compile" in cats
    # causal linkage: every actor span parents to its epoch's root
    by_id = {r[1]: r for r in trace_rows if r[1] != 0}
    actor_rows = [r for r in trace_rows if r[4] == "actor"]
    assert actor_rows
    for r in actor_rows:
        parent = by_id.get(r[2])
        assert parent is not None and parent[0] == r[0], \
            (r, "actor span must parent into its own epoch")
    # dispatch spans carry kernel identity + rows
    disp = [r for r in trace_rows if r[4] == "dispatch"]
    assert any("HashAgg" in r[3] for r in disp)
    assert any(json.loads(r[10] or "{}").get("rows", 0) > 0
               for r in disp)


def test_slow_barrier_promotes_trace_with_straggler_diagnosis(capfd):
    """A forced-slow agg (sleep failpoint) trips the watchdog: the
    epoch's full trace lands in the retained store and the one-line
    diagnosis names the laggard executor."""
    _rows, retained, diags, trace_rows = _run_q7ish(
        slow_threshold=0.05,
        failpoints_armed={"trace.slow.HashAggExecutor":
                          {"sleep_s": 0.12}})
    assert retained, "no slow barrier was promoted"
    assert any("HashAggExecutor" in d for d in diags), diags
    err = capfd.readouterr().err
    assert "slow barrier:" in err and "straggler" in err
    # the diagnosis also rides the system table
    assert any(r[4] == "diagnosis" and "HashAggExecutor" in r[3]
               for r in trace_rows)


# -- steady-state recompile guard (satellite) ------------------------------


def test_q7_steady_state_never_retraces(recompile_guard):
    """Tier-1 shape-stability oracle: after the warmup epochs of a q7
    run have compiled every shape bucket, further steady-state epochs
    must not retrace a single jitted kernel."""
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    from risingwave_tpu.models.nexmark import build_q7
    from risingwave_tpu.state.store import MemoryStateStore

    cfg = NexmarkConfig(event_num=6000, max_chunk_size=256,
                        generate_strings=False)
    p = build_q7(MemoryStateStore(), cfg, rate_limit=4, min_chunks=4)

    async def drive(epochs):
        for _ in range(epochs):
            await p.loop.inject_and_collect(force_checkpoint=True)

    async def run():
        from risingwave_tpu.stream.message import StopMutation
        task = p.actor.spawn()
        t0 = recompile_guard.total()
        await drive(6)                       # warmup: compiles land
        warm = recompile_guard.total() - t0
        t1 = recompile_guard.total()
        await drive(6)                       # steady state
        steady = recompile_guard.total() - t1
        await p.loop.inject_and_collect(
            mutation=StopMutation(frozenset({p.actor.actor_id})))
        await task
        return warm, steady

    warm, steady = asyncio.run(run())
    assert warm > 0, "warmup should have traced the agg kernels"
    recompile_guard.check_steady(steady)
    # the compile events are also visible in the trace
    assert any(s.cat == "compile"
               for e in EPOCH_TRACER.epochs()
               for s in EPOCH_TRACER.spans_for(e))


# -- the 2-worker acceptance case ------------------------------------------


def test_cluster_two_worker_slow_barrier_causal_trace(tmp_path):
    """Forced-slow barrier on a 2-worker cluster: one causally-linked
    trace (coordinator inject → worker actor spans → cross-worker
    exchange edge → device dispatch → commit), valid Chrome JSON, and
    a straggler diagnosis naming the injected laggard."""
    from risingwave_tpu.cluster.session import DistFrontend

    os.environ["RW_TPU_FAILPOINTS"] = json.dumps(
        {"trace.slow.HashAggExecutor": {"sleep_s": 0.4}})
    try:
        async def run():
            fe = DistFrontend(str(tmp_path), n_workers=2,
                              parallelism=2)
            await fe.start()
            try:
                fe.cluster.loop.profiler.slow_threshold_s = 0.1
                await fe.execute(BID_SOURCE.format(n=EVENTS))
                await fe.execute(Q7ISH_MV)
                await fe.step(6)
                n = await fe.drain_trace()
                rows = await fe.execute(
                    "SELECT * FROM rw_epoch_trace")
                # close() promotes one more (undrained) stop-barrier
                # epoch — snapshot the drained ones now
                return n, rows, EPOCH_TRACER.retained_epochs()
            finally:
                await fe.close()

        n_spans, trace_rows, retained = asyncio.run(run())
    finally:
        del os.environ["RW_TPU_FAILPOINTS"]

    assert n_spans > 0, "workers shipped no spans"
    assert retained, "the forced-slow barrier was not promoted"
    epoch = retained[-1]
    spans = EPOCH_TRACER.spans_for(epoch)
    by_cat = {}
    for s in spans:
        by_cat.setdefault(s.cat, []).append(s)
    # the full causal chain is present in ONE epoch's trace
    assert "barrier" in by_cat          # coordinator + worker inject
    assert "actor" in by_cat            # worker executor spans
    assert "exchange" in by_cat         # cross-worker edge
    assert "dispatch" in by_cat         # agg kernel dispatch
    assert "commit" in by_cat
    workers = {s.worker for s in spans}
    assert {"worker-0", "worker-1"} <= workers, workers
    # causal linkage, coordinator → worker: every worker inject span
    # parents to the coordinator's inject root for the same epoch
    root = next(s for s in by_cat["barrier"]
                if s.name == "barrier.inject")
    winjects = [s for s in by_cat["barrier"]
                if s.name == "barrier.inject.worker"]
    assert winjects
    assert all(s.parent_id == root.span_id for s in winjects)
    # exchange edges parent to a worker-side inject span
    winject_ids = {s.span_id for s in winjects}
    assert any(s.parent_id in winject_ids
               for s in by_cat["exchange"])
    # the diagnosis names the injected laggard
    diag = EPOCH_TRACER.diagnosis_for(epoch)
    assert "HashAggExecutor" in diag, diag
    assert any(r[4] == "diagnosis" and "HashAggExecutor" in r[3]
               for r in trace_rows)
    # and the whole thing exports as valid Chrome trace JSON
    out = json.loads(json.dumps(
        EPOCH_TRACER.export_chrome(epochs=[epoch])))
    xs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    # counter-sample spans (phase-ledger byte/queue tracks) render as
    # 'C' counter events, not slices
    assert len(xs) == len([s for s in spans if s.cat != "counter"])
    assert {e["pid"] for e in xs} >= {"coordinator", "worker-0",
                                      "worker-1"}
