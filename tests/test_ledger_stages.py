"""The ledger's second coordinate (ISSUE 35): each phase by the executor
kind that spent it (`exec_phase.<Kind>.<phase>`), `host_emit` by stage
(`stage.host_emit.<stage>`), `device_compute` by launch and wait under
the kernel label the host stood in (`device.<launch|wait>.<kernel>`),
and the serving heartbeat's waits as `phase.heartbeat_wait`.

The units run a private `PhaseLedger` on a clock that moves only when
the test moves it, so sums are compared exactly. The views are the
benchmark's `nexmark-q8` and `nexmark-q5` texts, read from the files,
cut small by the source's chunk size only and driven by `step`.
"""

import asyncio
import collections
import glob
import importlib.util
import json
import os
import re
import types

import pytest

from risingwave_tpu.utils import ledger as ledger_mod
from risingwave_tpu.utils import spans as spans_mod
from risingwave_tpu.utils.ledger import (
    LEDGER, UNATTRIBUTED, AttributionCell, PhaseLedger, staged,
)
from risingwave_tpu.utils.metrics import HISTORY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 3500000035


@pytest.fixture(autouse=True)
def _fresh_books():
    LEDGER.clear()
    HISTORY.clear()
    spans_mod.set_current_epoch(0)
    yield
    LEDGER.clear()
    HISTORY.clear()


class Clock:
    """`time` as the ledger sees it, moved by hand."""

    def __init__(self):
        self.now = 100.0

    def advance(self, s: float) -> None:
        self.now += s

    def perf_counter(self) -> float:
        return self.now

    monotonic = time = perf_counter


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(ledger_mod, "time", c)
    return c


def _in_cell(led: PhaseLedger, body) -> AttributionCell:
    cell = AttributionCell()
    tok = led.push_cell(cell)
    try:
        body()
    finally:
        led.pop_cell(tok)
    return cell


# -- the mechanism ------------------------------------------------------------


def test_a_staged_scope_keeps_its_phase_and_gains_the_stage(clock):
    led = PhaseLedger()

    def body():
        with led.phase("host_emit", stage="agg.emit"):
            clock.advance(0.25)
        with led.phase("host_emit"):             # no stage: phase only
            clock.advance(0.5)

    cell = _in_cell(led, body)
    assert cell.seconds == {"host_emit": pytest.approx(0.75)}
    assert cell.stages == {
        ("stage", "host_emit", "agg.emit"): pytest.approx(0.25)}
    led.commit_cell(7, cell, kind="HashAggExecutor")
    rec = led.seal(7, 1.0)
    assert rec.seconds["host_emit"] == pytest.approx(0.75)
    assert rec.second == {
        ("stage", "host_emit", "agg.emit"): pytest.approx(0.25),
        ("exec_phase", "HashAggExecutor", "host_emit"):
            pytest.approx(0.75)}
    assert cell.stages == {} and cell.named_total() == 0


def test_a_state_write_inside_agg_persist_is_counted_once(clock):
    """Exclusive nesting holds between stages as between phases, and a
    phase scope nested in a stage takes its seconds out of it too."""
    led = PhaseLedger()

    def body():
        with led.phase("host_emit", stage="agg.persist"):
            clock.advance(0.125)
            with led.phase("host_emit", stage="state.write"):
                clock.advance(0.5)
                with led.phase("h2d"):
                    clock.advance(0.0625)
            clock.advance(0.125)

    cell = _in_cell(led, body)
    assert cell.stages == {
        ("stage", "host_emit", "agg.persist"): pytest.approx(0.25),
        ("stage", "host_emit", "state.write"): pytest.approx(0.5)}
    assert cell.seconds == {"host_emit": pytest.approx(0.75),
                            "h2d": pytest.approx(0.0625)}
    assert sum(cell.stages.values()) <= cell.seconds["host_emit"]


def test_device_compute_is_filed_by_launch_and_wait_under_the_label(clock):
    """A staged `device_compute` scope is filed under the kernel label
    in force: the enclosing dispatch's for a wait, its own for a
    launch scope that names one. Together they are the cell's
    `device_compute`."""
    led = PhaseLedger()

    def body():
        with led.phase("device_compute", kernel="Agg(actor=1).flush",
                       stage="launch"):
            clock.advance(0.125)
            with led.phase("device_compute", stage="wait"):
                clock.advance(1.0)
            with led.phase("d2h"):
                clock.advance(0.25)
        with led.phase("device_compute", stage="wait"):   # no dispatch
            clock.advance(0.5)
        with led.kernel_scope("Join(actor=2)"):
            with led.phase("device_compute", kernel="hash_join",
                           stage="launch"):
                clock.advance(0.0625)

    cell = _in_cell(led, body)
    assert cell.stages == {
        ("device", "launch", "Agg(actor=1).flush"): pytest.approx(0.125),
        ("device", "wait", "Agg(actor=1).flush"): pytest.approx(1.0),
        ("device", "wait", "unlabeled"): pytest.approx(0.5),
        ("device", "launch", "hash_join"): pytest.approx(0.0625)}
    assert sum(cell.stages.values()) == pytest.approx(
        cell.seconds["device_compute"])


def test_outside_a_cell_the_stage_goes_to_the_newest_epoch(clock):
    led = PhaseLedger()
    spans_mod.set_current_epoch(5)
    with led.phase("host_emit", stage="state.write"):
        clock.advance(0.25)
    rec = led.seal(5, 1.0)
    assert rec.second == {
        ("stage", "host_emit", "state.write"): pytest.approx(0.25)}
    # after the newest injected epoch sealed, the next seal takes it
    with led.phase("host_emit", stage="state.write"):
        clock.advance(0.5)
    spans_mod.set_current_epoch(6)
    assert led.seal(6, 1.0).second == {
        ("stage", "host_emit", "state.write"): pytest.approx(0.5)}


def test_a_loop_phase_takes_no_stage(clock):
    led = PhaseLedger()
    spans_mod.set_current_epoch(3)
    with led.phase("checkpoint", stage="build"):
        clock.advance(0.25)
    rec = led.seal(3, 1.0)
    assert rec.seconds["checkpoint"] == pytest.approx(0.25)
    assert rec.second == {}


def _script(led: PhaseLedger, clock: Clock, stages: bool):
    """One executor's epoch, with or without the second coordinate."""
    def st(name):
        return name if stages else None

    def body():
        with led.phase("host_emit", stage=st("agg.ingest")):
            clock.advance(0.0625)
            with led.phase("host_pack"):
                clock.advance(0.03125)
        with led.phase("device_compute", kernel="k", stage=st("launch")):
            clock.advance(0.015625)
            with led.phase("device_compute", stage=st("wait")):
                clock.advance(0.125)
        clock.advance(0.25)                      # the unnamed residue

    cell = _in_cell(led, body)
    busy = 0.0625 + 0.03125 + 0.015625 + 0.125 + 0.25
    named = cell.named_total()
    led.attribute_exec("HashAggExecutor", busy, 9)
    led.commit_cell(9, cell, kind="HashAggExecutor" if stages else None)
    led.attribute("host_emit", busy - named, 9,
                  kind="HashAggExecutor" if stages else None)
    return led.seal(9, 2.0)


def test_the_gate_reads_the_same_residual_with_and_without_stages(clock):
    """The second coordinate is filed beside the phases, never added
    to them: the strict-mode conservation gate sees the same books."""
    plain = PhaseLedger()
    staged_ = PhaseLedger()
    a = _script(plain, clock, stages=False)
    b = _script(staged_, clock, stages=True)
    assert a.seconds == b.seconds
    assert a.seconds[UNATTRIBUTED] == pytest.approx(2.0 - 0.484375)
    assert plain.gate_violations() == staged_.gate_violations() != []
    assert a.second == {} and b.second
    # and the cuts cross: over the phases the kind's exec_s, over the
    # stages no more than their phase
    by_kind = sum(s for (fam, kind, _p), s in b.second.items()
                  if fam == "exec_phase" and kind == "HashAggExecutor")
    assert by_kind == pytest.approx(b.exec_s["HashAggExecutor"])
    assert b.second[("device", "launch", "k")] \
        + b.second[("device", "wait", "k")] \
        == pytest.approx(b.seconds["device_compute"])


def test_the_history_row_and_the_counters_carry_the_names(clock):
    from risingwave_tpu.utils.metrics import STREAMING
    before = STREAMING.phase_stage_seconds.get(phase="host_emit",
                                               stage="agg.ingest")
    _script(LEDGER, clock, stages=True)
    [row] = [r for r in _rows_by_epoch(HISTORY.rows()).values()]
    assert row["stage.host_emit.agg.ingest"] == pytest.approx(0.0625)
    assert row["device.launch.k"] == pytest.approx(0.015625)
    assert row["device.wait.k"] == pytest.approx(0.125)
    assert row["exec_phase.HashAggExecutor.host_emit"] == pytest.approx(
        0.0625 + 0.25)
    assert row["exec_phase.HashAggExecutor.host_pack"] == pytest.approx(
        0.03125)
    # only names with seconds are written
    assert not any(k.startswith(("stage.", "device.", "exec_phase."))
                   and v == 0 for k, v in row.items())
    assert STREAMING.phase_stage_seconds.get(
        phase="host_emit", stage="agg.ingest") - before \
        == pytest.approx(0.0625)
    assert STREAMING.exec_phase_seconds.get(
        kind="HashAggExecutor", phase="host_pack") > 0
    assert STREAMING.device_host_seconds.get(kernel="k",
                                             stage="wait") > 0


def test_staged_wraps_a_function_in_one_scope(clock):
    @staged("join.pairs")
    def build(n):
        """doc"""
        clock.advance(0.5)
        return n + 1

    cell = AttributionCell()
    tok = LEDGER.push_cell(cell)
    try:
        assert build(1) == 2
    finally:
        LEDGER.pop_cell(tok)
    assert build.__name__ == "build" and build.__doc__ == "doc"
    assert cell.stages == {
        ("stage", "host_emit", "join.pairs"): pytest.approx(0.5)}


# -- the views ----------------------------------------------------------------


def _rows_by_epoch(rows) -> dict:
    out = {}
    for _seq, epoch, ts, interval_s, name, value, _dom in rows:
        out.setdefault(epoch, {"ts": ts, "interval_s": interval_s})[
            name] = value
    return out


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


async def _drive(config_name: str, chunk: int, barriers: int,
                 count_scopes: bool = False) -> dict:
    """The configuration's view at `chunk` rows a source chunk, two
    chunks a reader a barrier; the history of the barriers after the
    first three, and (on request) the staged scopes each opened."""
    from risingwave_tpu.frontend.session import Frontend
    config = _config(config_name)
    HISTORY.clear()
    scopes = collections.Counter()
    fe = Frontend()
    real = PhaseLedger.phase

    def counting(self, name, kernel=None, stage=None):
        if stage is not None:
            scopes[f"{name}.{stage}"] += 1
        return real(self, name, kernel=kernel, stage=stage)

    try:
        await fe.execute("SET streaming_rate_limit = 2")
        await fe.execute("SET streaming_min_chunks = 2")
        for ddl in config["ddl"]:
            ddl = re.sub(r"max\.chunk\.size=\d+",
                         f"max.chunk.size={chunk}", ddl)
            await fe.execute(ddl.format(seed=SEED))
        await fe.step(3)
        warm = set(_rows_by_epoch(HISTORY.rows()))
        if count_scopes:
            PhaseLedger.phase = counting
        await fe.step(barriers)
    finally:
        PhaseLedger.phase = real
        rows = _rows_by_epoch(HISTORY.rows())
        await fe.close()
    return {"history": {e: h for e, h in rows.items() if e not in warm},
            "scopes": scopes}


@pytest.fixture(scope="module")
def views():
    return {name: asyncio.run(_drive(name, 512, 6))
            for name in ("nexmark-q8", "nexmark-q5")}


def _family(h: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in h.items()
            if k.startswith(prefix)}


@pytest.mark.parametrize("config", ["nexmark-q8", "nexmark-q5"])
def test_the_two_cuts_cross_in_every_epoch(views, config):
    """Σ over phases of `exec_phase.<Kind>.*` is `exec_s.<Kind>`, and
    Σ over kinds of `exec_phase.*.<phase>` is the part of
    `phase.<phase>` that came through cells: no more than it."""
    history = views[config]["history"]
    assert len(history) == 6
    kinds = set()
    for h in history.values():
        by_kind = collections.Counter()
        by_phase = collections.Counter()
        for name, s in _family(h, "exec_phase.").items():
            kind, phase = name.split(".", 1)
            by_kind[kind] += s
            by_phase[phase] += s
        assert set(by_kind) == set(_family(h, "exec_s."))
        for kind, s in _family(h, "exec_s.").items():
            assert by_kind[kind] == pytest.approx(s, rel=1e-6, abs=1e-9)
        for phase, s in by_phase.items():
            assert s <= h["phase." + phase] + 1e-9, (phase, h)
        kinds |= set(by_kind)
    assert {"HashAggExecutor", "HashJoinExecutor",
            "SourceExecutor"} <= kinds
    # the residue goes by the executor's fallback phase
    assert all("exec_phase.SourceExecutor.host_ingest" in h
               and "exec_phase.HashAggExecutor.host_emit" in h
               for h in history.values())


@pytest.mark.parametrize("config", ["nexmark-q8", "nexmark-q5"])
def test_stages_stay_inside_their_phase(views, config):
    history = views[config]["history"]
    seen = collections.Counter()
    for h in history.values():
        stages = _family(h, "stage.host_emit.")
        assert sum(stages.values()) <= h["phase.host_emit"] + 1e-9
        assert set(_family(h, "stage.")) \
            == {"host_emit." + s for s in stages}
        seen.update(stages)
    expected = {"agg.ingest", "agg.decode", "agg.persist", "agg.emit",
                "join.ingest", "join.split", "join.pairs", "state.write",
                "state.commit"}
    if config == "nexmark-q5":
        # the retractable MAX rescans; the join evaluates its own
        # `>=` inside `join.pairs`: no block stands above it
        expected.add("agg.extremes")
        assert "fused.chunk" not in seen
    else:
        expected.add("mv.write")         # q5's `>=` lets few rows by
    assert expected <= set(seen), sorted(seen)
    # most of the residue has a name now
    assert sum(seen.values()) >= 0.5 * sum(
        h["phase.host_emit"] for h in history.values())


@pytest.mark.parametrize("config", ["nexmark-q8", "nexmark-q5"])
def test_launch_and_wait_are_the_cells_device_compute(views, config):
    history = views[config]["history"]
    for h in history.values():
        device = _family(h, "device.")
        assert device and all(
            k.startswith(("launch.", "wait.")) for k in device)
        through_cells = sum(
            s for name, s in _family(h, "exec_phase.").items()
            if name.endswith(".device_compute"))
        assert sum(device.values()) == pytest.approx(
            through_cells, rel=1e-6, abs=1e-9)
        assert sum(device.values()) <= h["phase.device_compute"] + 1e-9
    labels = {k.split(".", 1)[1] for h in history.values()
              for k in _family(h, "device.")}
    assert any("HashAggExecutor" in x and x.endswith(".flush")
               for x in labels), labels
    assert any("Join" in x or x == "hash_join" for x in labels), labels


@pytest.mark.parametrize("config", ["nexmark-q8", "nexmark-q5"])
def test_the_value_multisets_books_are_in_every_row(views, config):
    """`agg_multiset.*` beside `state_pk.*`: q5's retractable MAX writes
    its multiset through on every barrier and rescans retracted windows
    in memory, reading no row back; q8 has no such aggregate."""
    for h in views[config]["history"].values():
        books = _family(h, "agg_multiset.")
        assert set(books) == {"point_reads", "rows_written",
                              "extreme_scans", "values_scanned"}
        assert books["point_reads"] == 0
        if config == "nexmark-q5":
            assert books["rows_written"] > 0
            assert 0 < books["extreme_scans"] <= books["values_scanned"]
        else:
            assert not any(books.values())


def test_the_scopes_a_barrier_do_not_follow_the_rows():
    """The guard against a scope in a row loop: with twice the rows in
    every chunk (and the same chunks a barrier) each stage opens as
    many scopes a barrier as before. `join.pairs`, `join.degrees`,
    `agg.extremes` and `mv.write` open where the data
    has a match, a retraction or a row for the view to show, so they
    are held to the chunks, not to equality."""
    small = asyncio.run(_drive("nexmark-q5", 256, 4, count_scopes=True))
    large = asyncio.run(_drive("nexmark-q5", 512, 4, count_scopes=True))
    by_data = ("host_emit.join.pairs", "host_emit.join.degrees",
               "host_emit.join.split", "host_emit.agg.extremes",
               "host_emit.mv.write",
               # opened only where a fetch finds its arrays not ready
               "device_compute.wait")

    def fixed(scopes):
        # the `>=` lets a row through to the view in some barriers
        # only: a chunk there is one mv.write and its state.write
        out = {k: v for k, v in scopes.items() if k not in by_data}
        out["host_emit.state.write"] -= scopes["host_emit.mv.write"]
        return out

    assert fixed(small["scopes"]) == fixed(large["scopes"])
    assert {"host_emit.agg.ingest", "host_emit.state.write",
            "host_emit.state.commit",
            "device_compute.launch"} <= set(fixed(small["scopes"]))
    for k in by_data:
        assert large["scopes"][k] <= small["scopes"][k] + 4 * 8, k
    # under the budget of 200 scopes a barrier
    assert sum(large["scopes"].values()) / 4 < 200


# -- the heartbeat's waits as a name ------------------------------------------


def test_heartbeat_wait_is_on_the_row_and_in_ctl_phases():
    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend()
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.max.chunk.size=256)")
        await fe.execute("CREATE MATERIALIZED VIEW m AS "
                         "SELECT auction, count(*) AS n FROM bid "
                         "GROUP BY auction")
        hb = asyncio.ensure_future(fe.run_heartbeat(0.02))
        while HISTORY.barriers() < 8:
            await asyncio.sleep(0.01)
        async with fe._barrier_lock:
            hb.cancel()
        await asyncio.gather(hb, return_exceptions=True)
        rows = _rows_by_epoch(HISTORY.rows())
        report = LEDGER.report(last_n=6)
        await fe.close()
        return rows, report

    rows, report = asyncio.run(run())
    beats = [h for h in rows.values() if "heartbeat.wait_s" in h]
    assert len(beats) >= 4
    for h in beats:
        waited = h["heartbeat.wait_s"] + h["heartbeat.tail_wait_s"]
        # the waits less the loop time a checkpoint took inside them
        assert 0.0 <= h["phase.heartbeat_wait"] <= waited + 1e-9
    assert any(h["phase.heartbeat_wait"] > 0 for h in beats)
    # outside the interval and the gate: the ledger's records have no
    # such phase; `ctl phases` prints it under the epoch's table
    assert all("heartbeat_wait" not in r.seconds for r in LEDGER.records)
    assert "heartbeat_wait" in report and "in no interval" in report


# -- the annotations ----------------------------------------------------------


def _contains(outer, inner) -> bool:
    return outer[0] <= inner[0] \
        and inner[0] + inner[1] <= outer[0] + outer[1]


def test_staged_scopes_are_annotations_nested_in_the_kernel_label(
        tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from risingwave_tpu.utils import jaxtools

    @jax.jit
    def spin(x):
        return jax.lax.fori_loop(
            0, 400, lambda _i, a: jnp.sin(a) + jnp.cos(a), x)

    x = jnp.ones((512, 512), dtype=jnp.float32)
    jaxtools.fetch1(spin(x))                     # compiled before the trace
    spans_mod.set_current_epoch(77)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with spans_mod.dispatch_span("Agg(actor=1).flush", 1.0):
            jaxtools.fetch1(spin(x))
        with LEDGER.phase("host_emit", stage="agg.persist"):
            with LEDGER.phase("host_emit", stage="state.write"):
                pass
    finally:
        jax.profiler.stop_trace()
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
    [label] = by_name["Agg(actor=1).flush"]
    [launch] = by_name["phase.device_compute.launch"]
    [wait] = by_name["phase.device_compute.wait"]
    [persist] = by_name["phase.host_emit.agg.persist"]
    [write] = by_name["phase.host_emit.state.write"]
    assert _contains(label, launch) and _contains(launch, wait)
    assert _contains(persist, write)
    for ev in (launch, wait, persist, write):
        assert int(ev[2]["epoch"]) == 77
    # how many polls slept the coarse quantum rides the wait
    assert int(wait[2]["coarse_polls"]) >= 0
    assert "phase.device_compute" not in by_name


# -- the benchmark's readers --------------------------------------------------


def _reader(name: str):
    folder = os.path.join(BENCH, "layer_metrics")
    import sys
    if folder not in sys.path:       # as run.load_module does: the
        sys.path.insert(0, folder)   # readers import stage_span
    spec = importlib.util.spec_from_file_location(
        "layer_" + name, os.path.join(folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# three window epochs and the closing one, which the span leaves out:
# the span runs from 100 - 2 = 98 to 108, 10 s
RECORD = {
    "window": {"wall_s": 30.0},
    "phase_seconds": {},
    "history": {
        1: {"ts": 100.0, "interval_s": 2.0, "phase.host_emit": 1.0,
            "phase.device_compute": 0.5, "phase.unattributed": 0.25,
            "phase.heartbeat_wait": 9.0,
            "exec_phase.HashAggExecutor.host_emit": 0.5,
            "exec_phase.HashJoinExecutor.host_emit": 0.25,
            "exec_phase.HashAggExecutor.device_compute": 0.125,
            "exec_phase.HashJoinExecutor.device_compute": 0.375,
            "stage.host_emit.agg.persist": 0.25,
            "stage.host_emit.state.write": 0.125,
            "stage.host_emit.join.pairs": 0.0625,
            "device.wait.Agg(actor=1).flush": 0.25,
            "device.launch.Agg(actor=1).flush": 0.125,
            "device.launch.hash_join": 0.125},
        2: {"ts": 104.0, "interval_s": 3.0, "phase.host_emit": 1.5,
            "phase.heartbeat_wait": 1.0,
            "exec_phase.HashAggExecutor.host_emit": 1.0,
            "exec_phase.FusedAggExecutor.host_emit": 0.5,
            "exec_phase.SourceExecutor.host_ingest": 0.5,
            "stage.host_emit.agg.persist": 0.75,
            "stage.host_emit.state.write": 0.375,
            "device.wait.hash_join": 0.75},
        3: {"ts": 108.0, "interval_s": 3.5, "phase.host_emit": 0.5,
            "phase.unattributed": 0.5, "phase.heartbeat_wait": 0.5,
            "exec_phase.HashJoinExecutor.host_emit": 0.25,
            "stage.host_emit.join.pairs": 0.4375},
        4: {"ts": 130.0, "interval_s": 20.0, "phase.host_emit": 8.0,
            "phase.heartbeat_wait": 0.0,
            "exec_phase.HashAggExecutor.host_emit": 8.0,
            "stage.host_emit.agg.persist": 8.0,
            "device.wait.hash_join": 8.0},
    },
}
# what the parent leaves: the phases and exec_s, none of the new names
BARE = {"window": {"wall_s": 30.0}, "phase_seconds": {"host_emit": 1.0},
        "history": {1: {"ts": 100.0, "interval_s": 2.0,
                        "phase.host_emit": 1.0, "phase.unattributed": 0.1,
                        "exec_s.HashAggExecutor": 1.0},
                    2: {"ts": 104.0, "interval_s": 3.0,
                        "phase.host_emit": 1.0,
                        "heartbeat.wait_s": 0.1}}}


@pytest.mark.parametrize("name,value", [
    ("agg_emit_share", 20.0),            # 0.5 + 1.0 + 0.5 of 10 s
    ("join_emit_share", 5.0),
    ("agg_device_share", 1.25),
    ("join_device_share", 3.75),
    ("device_wait_share", 10.0),
    ("device_launch_share", 2.5),
    # window sums, the closing epoch too: 10 of 11
    ("host_emit_named_share", 100.0 * 10.0 / 11.0),
    ("state_write_share", 5.0),
    ("join_pairs_share", 5.0),
    ("agg_persist_share", 10.0),
    # intervals 8.5 less 0.75 unattributed, and the waits before the
    # second and third inject
    ("window_named_share", 92.5),
])
def test_a_reader_of_the_second_coordinate(name, value):
    read = _reader(name)
    assert read(RECORD) == pytest.approx(value)
    assert read(BARE) is None


def test_every_new_reader_is_in_the_benchmark_for_all_five_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the five cells the benchmark had when these readers came (a
    # later cell lists the readers it reports itself)
    cells = [w["name"] for w in bench["workloads"]][:5]
    names = {
        "agg_emit_share", "join_emit_share", "agg_device_share",
        "join_device_share", "device_wait_share", "device_launch_share",
        "host_emit_named_share", "state_write_share", "join_pairs_share",
        "agg_persist_share", "window_named_share"}
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in names}
    assert set(new) == names
    for m in new.values():
        assert m["workloads"][:5] == cells \
            and m["moves"] == "events_per_s"
        assert m["source"] == "program_span" and m["unit"] == "%"
        assert isinstance(_reader(m["name"]), types.FunctionType)
