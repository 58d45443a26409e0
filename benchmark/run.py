#!/usr/bin/env python3
"""benchmark/run.py: one cell of the benchmark, one process, one result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse] [--control <name>]

The process holds the chip. It starts the single-process server through
`risingwave_tpu.__main__.serving` (what `serve --data-dir D` runs:
HummockLite on the local FS, pgwire, the 0.25 s barrier heartbeat) and
speaks pgwire to it over TCP. A run has three phases:

  set-up  the configuration's SETs and DDL, then the stream runs through
          the served view until every source reader's checkpointed offset
          has passed the cell file's `preload_events`. The preload is the
          warm-up. It meets the programs of the table sizes it passes, so
          a cell's sizes keep its window between two growth rungs of the
          device tables (PERF.md section 4); `compiles_in_window` and the
          kernel traces the run prints say whether they did.
          `setup_s` is process start to window open.
  window  FLUSH (the opening checkpoint), the heartbeat until the readers
          have added the cell file's `window_rows` (a fixed amount of
          work sized to take about `--seconds`, scaled with it), FLUSH
          (the closing checkpoint). Rows, barriers and time are counted
          between the two checkpoints. A `--trace 1` run profiles exactly
          that: `Profile` starts before the heartbeat resumes and stops
          after the closing FLUSH has returned.
  check   the view is read back over pgwire and compared, as a multiset,
          with the configuration's plain reference over exactly the
          prefixes the closing checkpoint covers; the rows of the view's
          largest state table (`rw_state_topology`) are compared with
          the rows the reference says the deployment keeps; every barrier
          of the window has to be a checkpoint whose commit landed; no
          rewrite rule may have fallen back. Untimed.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file found by its name in `BENCHMARK.json`
(`benchmark/README.md`). Without `--rehearse` the run fails where JAX
finds no TPU; nothing a `--rehearse` run prints is a device number.
The last line of standard output is the result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()        # process start, as near as Python lets us

import argparse                     # noqa: E402
import asyncio                      # noqa: E402
import collections                  # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import math                         # noqa: E402
import os                           # noqa: E402
import statistics                   # noqa: E402
import sys                          # noqa: E402
import tempfile                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONTROLS = ("rare_checkpoint", "short_reference")
# what `correct` compares, each a count with the limit 0 (PERF.md section 2)
COMPARED = ("differing", "state_rows_off", "not_durable", "behind_manifest",
            "fallbacks")
KEEP_ENV = "BENCH_KEEP_DIR"  # by hand: leave the barrier rows and the trace


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    """The module `<HERE>/<directory>/<name>.py`, loaded once. A module
    of `reference/` goes by its plain name and its directory is on the
    path, so that a reference finds the generator copy beside it."""
    folder = os.path.join(HERE, directory)
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path}: named in BENCHMARK.json or a "
                                "configuration, not there")
    if folder not in sys.path:
        sys.path.insert(0, folder)
    modname = name if directory == "reference" else f"{directory}_{name}"
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
    return sys.modules[modname]


def find_cell(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    sizes = load_json(HERE, "cells", cell["name"] + ".json")
    return cell, config, traffic, sizes


def metrics_of(bench: dict, group: str, cell: str):
    """The metrics of `end_to_end` or `per_layer` this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least q of the
    sample at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


# -- what the process counts ------------------------------------------------


class Counts:
    """jax monitoring events (copied from chip_smoke.Counts): every XLA
    backend compile with its time of day, persistent-cache hits and
    misses; and the program's own counters, read as deltas."""

    def __init__(self):
        from jax import monitoring
        self.compiles = []             # (monotonic, seconds) per compile
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_dur(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.monotonic(), secs))

    @staticmethod
    def traces_by_kernel() -> collections.Counter:
        """stream_kernel_recompile_count: jit (re)traces per kernel."""
        from risingwave_tpu.utils.metrics import STREAMING
        out = collections.Counter()
        for labels, v in STREAMING.kernel_recompile.series():
            # a label carries the kernel's whole signature: keep its name
            out[labels.get("kernel", "?").split("[")[0]] += int(v)
        return out

    def kernel_traces(self) -> int:
        return sum(self.traces_by_kernel().values())

    def snapshot(self) -> dict:
        return {"xla_compiles": len(self.compiles),
                "compile_s": sum(s for _t, s in self.compiles),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "kernel_traces": self.kernel_traces()}


# -- reaching into the served session ---------------------------------------


def walk_executors(ex, path: str = ""):
    """(path, executor) down an actor's chain (chip_smoke.walk_executors,
    with the path, which says on which side of a join a source sits)."""
    if ex is None:
        return
    yield path, ex
    for attr in ("input", "left_in", "right_in"):
        yield from walk_executors(getattr(ex, attr, None),
                                  f"{path}/{attr}")


def source_readers(fe, view: str):
    """The source executors under the view's actor: (source name, side
    of the topmost join or None, executor)."""
    actor_id = fe.catalog.mvs[view].actor_id
    found = []
    for path, ex in walk_executors(fe.actors[actor_id].consumer):
        if getattr(ex, "reader", None) is None or \
                getattr(ex, "split_state", None) is None:
            continue
        side = next((p.split("_")[0] for p in path.split("/")
                     if p in ("left_in", "right_in")), None)
        found.append((ex.freshness_key, side, ex))
    if not found:
        raise RuntimeError(f"no source reader under view {view!r}")
    return found


def checkpointed_rows(readers):
    """What each reader's split-state row holds: the offset the source
    wrote at the last barrier it passed. With the heartbeat paused after
    a FLUSH that is the offset the committed checkpoint covers, whatever
    the reader has read ahead since."""
    out = []
    for name, side, ex in readers:
        row = ex.split_state.get_row((ex.reader.split_id,))
        out.append({"table": name, "side": side,
                    "rows": int(row[1]) if row is not None else 0})
    return out


def device_tables(fe):
    """[(kernel type, occupied slots, capacity)] of the device hash
    tables (chip_smoke.device_tables). Informational: a change to the
    kernels' attributes turns it into a note, not a failed run."""
    import jax.numpy as jnp
    seen, out = set(), []
    for actor in fe.actors.values():
        for _path, ex in walk_executors(actor.consumer):
            kernels = [getattr(ex, a) for a in ("kernel", "_kernel")
                       if getattr(ex, a, None) is not None]
            kernels += [s.kernel for s in getattr(ex, "sides", ())]
            for k in kernels:
                if id(k) in seen:
                    continue
                seen.add(id(k))
                table = k.state.table if hasattr(k, "state") \
                    else k.table.state
                out.append((type(k).__name__, int(jnp.sum(table.occ)),
                            int(table.occ.shape[0])))
    return out


def say_tables(fe, when: str) -> None:
    try:
        say(f"device hash tables at {when} (occupied/capacity): "
            + ", ".join(f"{kind} {occ}/{cap}"
                        for kind, occ, cap in device_tables(fe)))
    except Exception as e:   # noqa: BLE001 - informational only
        say(f"device hash tables at {when}: not read ({e!r})")


class Heartbeat:
    """The barrier heartbeat, owned by the benchmark for the run. It is
    the task `serving` starts (`Frontend.run_heartbeat`, 0.25 s). Pausing
    cancels it while holding the session's barrier lock, so that it is
    asleep or queued on the lock and no barrier round is cut in two."""

    def __init__(self, fe, task):
        self.fe, self.task = fe, task

    async def pause(self) -> None:
        async with self.fe._barrier_lock:
            self.task.cancel()
        await asyncio.gather(self.task, return_exceptions=True)

    def resume(self) -> None:
        self.task = asyncio.ensure_future(self.fe.run_heartbeat())

    def check(self) -> None:
        """Called while the heartbeat runs only: a task that is done has
        stopped, whatever ended it."""
        if self.task.done():
            raise RuntimeError("the barrier heartbeat stopped") from (
                None if self.task.cancelled() else self.task.exception())


async def run_to(heartbeat: Heartbeat, readers, targets: dict,
                 deadline: float, what: str, counts: Counts):
    """Let the heartbeat run until the NEXT checkpoint will cover
    `targets` rows of every reader, then pause it. The caller's FLUSH is
    that next checkpoint. The readers' rows are watched, not the clock,
    so that every run of a cell opens and closes its window on the same
    barrier of the stream: the same work in every run. A reader's
    split-state row changes when the source passes a barrier; what the
    next barrier adds is taken from the last step seen. Every kernel
    (re)trace on the way is said with the rows it came at: a growth rung
    of a device table, or a new epoch size."""
    last = [None] * len(readers)
    step = [0] * len(readers)
    traced = counts.traces_by_kernel()
    while True:
        heartbeat.check()
        at = checkpointed_rows(readers)
        now = counts.traces_by_kernel()
        if now != traced:
            say(f"{what}: kernel (re)traces {dict(now - traced)} with the "
                f"readers' checkpoints at {[r['rows'] for r in at]}")
            traced = now
        reached = True
        for i, r in enumerate(at):
            if last[i] is not None and r["rows"] > last[i]:
                step[i] = r["rows"] - last[i]
            last[i] = r["rows"]
            # before a step has been seen only the rows themselves count
            reached = reached and r["rows"] + step[i] >= \
                targets[r["table"]]
        if reached:
            await heartbeat.pause()
            return
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what}: not reached, readers at {at}, "
                               f"wanted {targets}")
        await asyncio.sleep(0.02)


BARRIER_COLUMNS = ("epoch", "kind", "inject_to_collect_s",
                   "collect_to_commit_s", "total_s", "in_flight",
                   "slowest_actor", "slowest_actor_lag_s", "upload_s",
                   "queue_depth", "domain")


async def barrier_rows(pg):
    rows = await pg.query("SELECT * FROM rw_barrier_latency")
    return [dict(zip(BARRIER_COLUMNS, r)) for r in rows]


async def history_by_epoch(pg) -> dict:
    """rw_metrics_history, long format, folded to {epoch: {name: value,
    "ts": seal time of day, "interval_s": ...}}."""
    out = {}
    for _seq, epoch, ts, interval_s, name, value, _dom in await pg.query(
            "SELECT * FROM rw_metrics_history"):
        rec = out.setdefault(epoch, {"ts": ts, "interval_s": interval_s})
        rec[name] = value
    return out


def manifest_epoch(data_dir: str):
    """committed_epoch of the version the object store's CURRENT names,
    read from the files."""
    with open(os.path.join(data_dir, "meta", "CURRENT")) as f:
        vid = int(f.read())
    return load_json(data_dir, "meta", f"v{vid}.json")["committed_epoch"]


# -- the traced span ----------------------------------------------------------


class Profile:
    """jax.profiler over the window, whatever its length: started at
    window open while the heartbeat is still paused, so that the
    profiler's own start-up is in no barrier, and stopped once the
    closing FLUSH has returned, so that writing the trace is in no
    share's denominator. A host annotation made at a known time of day
    ties the trace's clock to the clock the program stamps its barriers
    with. `limit_s` (the traffic file's `trace.seconds`) is an upper
    limit on the profile's length, kept for the size of the trace: a
    window that outlasts it is profiled up to the limit, and the log
    says so."""

    def __init__(self, trace_dir: str, limit_s: float):
        import jax
        from trace_reduce import MARK
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        self.mark_wall = time.time()
        with jax.profiler.TraceAnnotation(MARK):
            time.sleep(0.001)
        self.end_wall = None
        self._limit = asyncio.get_running_loop().call_later(
            limit_s, self.stop, f"profile: stopped at the traffic file's "
            f"limit of {limit_s:g} s, before the window closed")

    def stop(self, why: str = "") -> None:
        """Stamps the span's end, then stops the profiler. Idempotent."""
        import jax
        if self.end_wall is not None:
            return
        self.end_wall = time.time()
        self._limit.cancel()
        if why:
            say(why)
        jax.profiler.stop_trace()


def reduce_span(trace_dir: str, span: dict, history: dict,
                uploads: dict) -> dict:
    """The reduced trace the per-layer readers get (`trace_reduce`) over
    the span (the mark at window open, the return of the closing FLUSH),
    with the epochs sealed inside it, which are the window's barriers,
    and the idle gaps named by the ledger phase that held most of the
    epoch each fell in."""
    import trace_reduce
    xplane = trace_reduce.newest_xplane(trace_dir)
    keep = os.environ.get(KEEP_ENV)
    if keep:
        import gzip
        import shutil
        os.makedirs(keep, exist_ok=True)
        with open(xplane, "rb") as src, gzip.open(os.path.join(
                keep, os.path.basename(xplane) + ".gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
    loaded = trace_reduce.load_xplane(xplane)
    for plane, line, n in loaded["summary"]:
        if n and (plane.startswith("/device:") or line == "python"):
            say(f"trace: plane {plane} line {line!r}: {n} events")
    for plane, lines in loaded["devices"].items():
        for line, events in lines.items():
            top = collections.Counter()
            for name, _s, dur in events:
                top[name] += dur / 1e9
            say(f"trace: {plane} {line!r} by name: " + ", ".join(
                f"{n[:60]} {s:.4f}s" for n, s in top.most_common(6)))
    if not loaded["devices"]:
        say("trace: no device plane (a rehearsal on the CPU has none)")
        return {}
    return reduce_loaded(loaded, span, history, uploads)


def reduce_loaded(loaded: dict, span: dict, history: dict,
                  uploads: dict) -> dict:
    """`reduce_span` on a trace as `trace_reduce.load_xplane` gives it,
    so that `selfcheck/test_trace_reduce.py` can hold it against a
    synthetic one."""
    import trace_reduce
    length_ns = (span["end_wall"] - span["mark_wall"]) * 1e9
    mark_ns = loaded["mark_ns"]
    if mark_ns is None:
        say("trace: the host annotation is missing; the span is the "
            "device events' own extent and epochs are not placed in it")
        starts = [s for lines in loaded["devices"].values()
                  for evs in lines.values() for _n, s, _d in evs]
        ends = [s + d for lines in loaded["devices"].values()
                for evs in lines.values() for _n, s, d in evs]
        lo, hi = min(starts), max(ends)
    else:
        lo, hi = mark_ns, mark_ns + length_ns
    reduced = trace_reduce.reduce_trace(loaded, (lo, hi))

    def to_ns(wall: float) -> float:
        return lo + (wall - span["mark_wall"]) * 1e9

    epochs = sorted((to_ns(h["ts"]), e, h) for e, h in history.items())
    inside = [(ns, e, h) for ns, e, h in epochs if lo <= ns <= hi] \
        if mark_ns is not None else []
    reduced["epochs_in_span"] = len(inside)
    if inside:
        # The span opens with the window, so every epoch sealed inside it
        # is whole: the device's busy time from the span's start to the
        # last seal, over the source rows of all of them.
        cut = trace_reduce.reduce_trace(loaded, (lo, inside[-1][0]))
        reduced["whole_epochs"] = {
            "busy_s": cut["busy_s"],
            "source_rows": sum(h.get("source_rows", 0.0)
                               for _ns, _e, h in inside)}
    # A gap is named by what the host was doing, as far as the program
    # says: the checkpoint's upload and commit where the gap's middle
    # falls between a barrier's seal and seal + upload_s; else the ledger
    # phase that held most of the epoch sealed next. An approximation,
    # until the program writes its phases into the profiler's trace.
    by_phase = collections.Counter()
    for g0, g1 in reduced["gaps"]:
        mid = (g0 + g1) / 2
        label = "outside_any_epoch" if mark_ns is None or not epochs \
            else "after_the_last_seal"
        for ns, e, h in epochs:
            if ns <= mid <= ns + uploads.get(e, 0.0) * 1e9:
                label = "checkpoint_upload_commit"
                break
            if mid <= ns and mark_ns is not None:
                phases = {k[6:]: v for k, v in h.items()
                          if k.startswith("phase.")}
                label = max(phases, key=phases.get) if phases else label
                break
        by_phase[label] += (g1 - g0) / 1e9
    reduced["idle_gaps"] = [[n, s] for n, s in by_phase.most_common(10)]
    return reduced


# -- one run ------------------------------------------------------------------


async def run_cell(args, config: dict, traffic: dict, sizes: dict,
                   counts: Counts) -> dict:
    """Set-up, window and check of one cell; returns the run's record."""
    from pgclient import PgClient
    from risingwave_tpu.__main__ import serving

    with tempfile.TemporaryDirectory(prefix="rw_bench_") as tmp:
        data_dir = os.path.join(tmp, "data")
        trace_dir = os.path.join(tmp, "trace")
        async with serving(data_dir, port=0,
                           parallelism=traffic.get("parallelism", 1)) \
                as (fe, srv, hb_task), \
                await PgClient.connect(srv.port) as pg:
            heartbeat = Heartbeat(fe, hb_task)
            try:
                record = await _drive(args, config, traffic, sizes, counts,
                                      fe, pg, heartbeat, data_dir,
                                      trace_dir)
            finally:
                heartbeat.task.cancel()
        await fe.close()
    return record


async def _drive(args, config, traffic, sizes, counts, fe, pg, heartbeat,
                 data_dir, trace_dir) -> dict:
    if args.rehearse:
        sizes, sets = sizes["rehearse"], config["rehearse"]["sets"]
    else:
        sets = config["sets"]
    preload = sizes["preload_events"]
    view = config["view"]
    for stmt in sets:
        await pg.query(stmt)
    say("session settings: " + "; ".join(sets) + "; every other at its "
        "default")
    for ddl in config["ddl"]:
        await pg.query(ddl.format(seed=args.seed))
        say("ran " + " ".join(ddl.split()[:3]))
    readers = source_readers(fe, view)
    targets = {name: preload * num // den
               for name, (num, den) in config["rows_per_event"].items()}
    say(f"preload: {preload} events of the global sequence, i.e. "
        + ", ".join(f"{n} >= {t} rows" for n, t in targets.items()))

    deadline = time.monotonic() + traffic.get("preload_deadline_s", 600)
    await run_to(heartbeat, readers, targets, deadline, "preload", counts)

    # -- window open: the opening checkpoint
    if args.control == "rare_checkpoint":
        # the control: a durable checkpoint on every second barrier only
        await pg.query("SET stream_checkpoint_frequency = 2")
        say("CONTROL rare_checkpoint: SET stream_checkpoint_frequency = 2")
    await pg.query("FLUSH")
    say_tables(fe, "window open")
    open_rows = checkpointed_rows(readers)
    open_epoch = (await barrier_rows(pg))[-1]["epoch"]
    open_counts = counts.snapshot()
    open_traces = counts.traces_by_kernel()
    # a traced run profiles the window and nothing else: the profiler
    # starts while the heartbeat is still paused, and `t_open` is stamped
    # once it has
    profile = Profile(trace_dir, traffic["trace"]["seconds"]) \
        if args.trace else None
    t_open = time.monotonic()
    setup_s = t_open - T0
    try:
        heartbeat.resume()
        say(f"window open: set-up took {setup_s:.1f} s; checkpoint covers "
            f"{open_rows}; so far {open_counts}")

        # The window is a fixed amount of work: the rows every reader has
        # to add, sized in the cell's file to take about `seconds`, and
        # scaled with --seconds. Every run of the cell closes on the same
        # barrier of the stream.
        work = sizes["window_rows"]
        add = int(work["rows_per_reader"] * args.seconds / work["seconds"])
        close_targets = {r["table"]: r["rows"] + add for r in open_rows}
        say(f"the window closes on the first checkpoint that covers {add} "
            f"more rows of every reader ({close_targets})")
        await run_to(heartbeat, readers, close_targets,
                     t_open + args.seconds + traffic.get(
                         "window_deadline_s", 300), "window", counts)

        # -- window close: the closing checkpoint
        await pg.query("FLUSH")
        t_close = time.monotonic()
    finally:
        if profile:
            profile.stop()
    close_rows = checkpointed_rows(readers)
    close_counts = counts.snapshot()
    in_window = [c for c in counts.compiles if t_open <= c[0] <= t_close]
    say_tables(fe, "window close")

    # -- check (untimed)
    t_check = time.monotonic()
    barriers = [b for b in await barrier_rows(pg)
                if b["epoch"] > open_epoch]
    history = await history_by_epoch(pg)
    span = None
    if profile:
        span = {"mark_wall": profile.mark_wall,
                "end_wall": profile.end_wall,
                "epochs_in_span": sum(
                    profile.mark_wall <= h["ts"] <= profile.end_wall
                    for h in history.values())}
        say(f"profile: {span['end_wall'] - span['mark_wall']:.3f} s from "
            f"the mark at window open to the closing FLUSH's return; "
            f"{span['epochs_in_span']} epochs sealed inside it, the window "
            f"has {len(barriers)} barriers")
    rewrites = await pg.query("SELECT job, rule, fired, detail "
                              "FROM rw_plan_rewrites")
    got = collections.Counter(await pg.query(f"SELECT * FROM {view}"))
    state_rows = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in await pg.query(
            "SELECT * FROM rw_state_topology"):
        if mv == view:
            state_rows[table_id] += n
    t_read = time.monotonic()
    durable_epoch = manifest_epoch(data_dir)

    generator = load_module("reference", "nexmark_gen").GeneratorConfig(
        seed=args.seed, **config.get("generator", {}))
    ref_module = load_module("reference", config["reference"])
    reference = ref_module.reference
    ref_rows = [dict(r) for r in close_rows]
    if args.control == "short_reference":
        sound = reference(ref_rows, generator)
        say(f"CONTROL short_reference: against the sound reference "
            f"{sum(((got - sound) + (sound - got)).values())} rows "
            f"differ; the reference is now computed "
            f"{config['chunk_rows']} rows short of every reader")
        for r in ref_rows:
            r["rows"] = max(0, r["rows"] - config["chunk_rows"])
    want = reference(ref_rows, generator)
    differing = sum(((got - want) + (want - got)).values())
    # rows the deployment keeps for the view, by the reference, against
    # the rows of the view's largest state table at the closing checkpoint
    kept = max(state_rows.values(), default=0)
    state_off = abs(kept - ref_module.resident_rows(ref_rows, generator))
    not_durable = [b for b in barriers
                   if b["kind"] != "checkpoint" or not b["upload_s"] > 0]
    fallbacks = [r for r in rewrites if str(r[3]).startswith("FALLBACK")]
    sealed = max((b["epoch"] for b in barriers), default=open_epoch)
    behind = [b for b in barriers[:-1] if b["epoch"] > durable_epoch]
    check_s = time.monotonic() - t_check
    say(f"check: view {view} has {sum(got.values())} rows "
        f"({len(got)} distinct), reference {sum(want.values())} over "
        f"{ref_rows}; read in {t_read - t_check:.1f} s, whole check "
        f"{check_s:.1f} s")
    say(f"compared: rows differing from the reference {differing} "
        f"(limit 0); rows of the view's largest state table {kept}, off "
        f"the reference's by {state_off} (limit 0); window barriers that are not durable checkpoints "
        f"{len(not_durable)} of {len(barriers)} (limit 0); barriers "
        f"before the closing one above the manifest's committed epoch "
        f"{len(behind)} (limit 0; manifest {durable_epoch}, closing "
        f"barrier {sealed}); FALLBACK rewrites {len(fallbacks)} "
        f"(limit 0)")
    if differing:
        say(f"  missing {list((want - got).items())[:3]}, unexpected "
            f"{list((got - want).items())[:3]}")

    window_epochs = {b["epoch"] for b in barriers}
    phase_seconds = collections.Counter()
    for epoch, h in history.items():
        if epoch in window_epochs:
            for k, v in h.items():
                if k.startswith("phase."):
                    phase_seconds[k[6:]] += v
    wall_s = t_close - t_open
    rows = sum(r["rows"] for r in close_rows) \
        - sum(r["rows"] for r in open_rows)
    per_barrier = [h.get("source_rows", 0.0) for e, h in history.items()
                   if e in window_epochs]
    retraced = [(i, int(history[b["epoch"]].get("kernel_recompiles", 0)))
                for i, b in enumerate(barriers) if b["epoch"] in history]
    say("kernel (re)traces by barrier of the window: "
        + (", ".join(f"#{i}: {n}" for i, n in retraced if n) or "none"))
    say(f"window: {wall_s:.3f} s between the two checkpoints, {rows} "
        f"durable source rows, {len(barriers)} barriers; source rows "
        f"per barrier min/median/max "
        + ("/".join(f"{f(per_barrier):.0f}" for f in
                    (min, statistics.median, max))
           if per_barrier else "none")
        + f"; XLA compiles in the window {len(in_window)} "
        f"({sum(s for _t, s in in_window):.1f} s), persistent-cache "
        f"hits {close_counts['cache_hits'] - open_counts['cache_hits']}"
        f", kernel traces "
        f"{close_counts['kernel_traces'] - open_counts['kernel_traces']}"
        f" {dict(counts.traces_by_kernel() - open_traces)}")
    return {
        "setup_s": setup_s,
        "window": {"wall_s": wall_s, "rows": rows, "open": open_rows,
                   "close": close_rows},
        "barriers": barriers,
        "history": {e: h for e, h in history.items()
                    if e in window_epochs},
        "phase_seconds": dict(phase_seconds),
        "counters": {k: close_counts[k] - open_counts[k]
                     for k in close_counts},
        "compiles_in_window": len(in_window),
        "compile_times": [(t - t_open, secs) for t, secs in
                          counts.compiles],
        "span": span,
        "trace": reduce_span(
            trace_dir, span, history,
            {b["epoch"]: b["upload_s"] for b in barriers})
        if span else None,
        "check": {"differing": differing, "state_rows_off": state_off,
                  "not_durable": len(not_durable),
                  "behind_manifest": len(behind),
                  "fallbacks": len(fallbacks), "seconds": check_s},
    }


def end_to_end(record: dict) -> dict:
    """The end-to-end metrics, taken by the benchmark itself."""
    lat_ms = [(b["total_s"] + b["upload_s"]) * 1e3
              for b in record["barriers"]]
    say(f"barrier latency sample (inject to durable commit): "
        f"{len(lat_ms)} barriers")
    return {
        "events_per_s": record["window"]["rows"]
        / record["window"]["wall_s"],
        "barrier_p50_ms": statistics.median(lat_ms),
        "barrier_p90_ms": quantile(lat_ms, 0.9),
        "setup_s": record["setup_s"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, no TPU needed; no device number")
    ap.add_argument("--control", choices=CONTROLS,
                    help="run a control that has to come out not correct")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic, sizes = find_cell(bench, args.workload)
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import risingwave_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the system under test is not in this checkout "
              f"({e})", file=sys.stderr)
        return 2

    import jax

    from risingwave_tpu.utils.jaxtools import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    if dev["platform"] != "tpu" and not args.rehearse:
        print(f"run.py: the platform is {dev['platform']!r}, not a TPU; "
              "the benchmark measures on the chip only (--rehearse runs "
              "the control flow at a tiny size elsewhere)",
              file=sys.stderr)
        return 2
    if dev["count"] < cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} "
              f"devices, JAX reports {dev['count']}", file=sys.stderr)
        return 2
    say(f"cell {cell['name']}: configuration {config['name']}, traffic "
        f"{traffic['name']}, seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}; device {dev['kind']} x{dev['count']} "
        f"({dev['platform']})"
        + (" REHEARSAL: nothing below is a device number"
           if args.rehearse else ""))
    say(f"compile cache {cache_dir}: {cached} entries at start "
        f"({'warm' if cached else 'cold'})")

    counts = Counts()
    record = asyncio.run(run_cell(args, config, traffic, sizes, counts))

    keep = os.environ.get(KEEP_ENV)
    if keep:
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(
                keep, f"{cell['name']}.{args.seed}.t{args.trace}."
                f"{int(time.time())}.json"), "w") as f:
            json.dump({k: record[k] for k in (
                "setup_s", "window", "barriers", "history", "counters",
                "compile_times", "check", "span")}, f, indent=1)
    check = record["check"]
    correct = not any(check[k] for k in COMPARED)
    if args.trace:
        metrics = {}
        for m in metrics_of(bench, "per_layer", cell["name"]):
            value = load_module("layer_metrics", m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(record)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in metrics_of(bench, "end_to_end", cell["name"])}
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices[:cell["chips"]]), default=0)
    dev["memory_peak_bytes"] = int(peak)
    result = {"correct": correct, "attempted": len(record["barriers"]),
              "failed": check["not_durable"], "metrics": metrics,
              "device": dev}
    trace = record["trace"]
    if trace:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    after = counts.snapshot()
    say(f"whole process: {time.monotonic() - T0:.1f} s; XLA compiles "
        f"{after['xla_compiles']} ({after['compile_s']:.1f} s), "
        f"persistent-cache hits {after['cache_hits']}, misses "
        f"{after['cache_misses']}, kernel traces "
        f"{after['kernel_traces']}; check {check['seconds']:.1f} s")
    # every number compared beside its limit: last on standard error, and
    # last in the result's line
    result["compared"] = {k: {"value": check[k], "limit": 0}
                          for k in COMPARED}
    print("compared (value / limit): " + ", ".join(
        f"{k} {check[k]} / 0" for k in COMPARED), file=sys.stderr,
        flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
