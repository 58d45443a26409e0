"""CLI: `python -m risingwave_tpu` — the unified-binary analog.

Reference parity: src/cmd_all/src/bin/risingwave.rs playground /
standalone modes — one process hosting frontend (pgwire), meta (barrier
loop + catalog/DDL log) and compute (actors + device kernels), with
hummock-on-local-FS persistence when --data-dir is given — plus the
risectl verb family (src/ctl/) for offline cluster inspection and
backup operations against a data directory:

    python -m risingwave_tpu playground                # in-memory
    python -m risingwave_tpu serve --data-dir ./rwdata # durable
    python -m risingwave_tpu serve-cluster --data-dir ./rw \
        --workers 2                                    # N-worker
    python -m risingwave_tpu ctl --data-dir D meta catalog
    python -m risingwave_tpu ctl --data-dir D hummock version
    python -m risingwave_tpu ctl --data-dir D hummock list-ssts
    python -m risingwave_tpu ctl --data-dir D table scan <name> [-n N]
    python -m risingwave_tpu ctl --data-dir D metrics [--steps K]
    python -m risingwave_tpu ctl --data-dir D trace [--steps K] \
        [--out trace.json]    # Chrome trace-event JSON (Perfetto):
                              # X/s/f span+flow events, phase lanes,
                              # and 'C' counter tracks (transfer
                              # bytes, uploader queue depth, backlog
                              # rows) sampled at each epoch seal
    python -m risingwave_tpu ctl --data-dir D phases [--steps K]
                              # epoch phase ledger: per-epoch
                              # host/device time+bytes breakdown,
                              # conservation coverage, kernel costs
    python -m risingwave_tpu ctl --data-dir D top [--steps K] \
        [--watch N]           # live-ops view: actor utilization
                              # tricolor (busy/backpressure/idle,
                              # sorted busiest first), per-MV
                              # event-time freshness, and each
                              # domain's current bottleneck with its
                              # one-line diagnosis
    python -m risingwave_tpu ctl --data-dir D autoscale [--steps K]
                              # elastic control loop: the
                              # rw_autoscaler decision ledger plus
                              # the bottleneck/freshness signals a
                              # decision would read (live decisions
                              # ride the serving coordinator — SET
                              # stream_autoscale=on there)
    python -m risingwave_tpu ctl --data-dir D compaction [--steps K] \
        [--watch N]           # leveled-compaction view: per-level
                              # topology (L0 run count, L1 runs,
                              # tombstone density), space amp, and
                              # the dedicated-arm task ledger
                              # (rw_compaction) over a recovered
                              # clone driven with the off-path arm
    python -m risingwave_tpu ctl --data-dir D sinks [--steps K]
                              # exactly-once sink view (rw_sinks):
                              # per-sink committed epoch, staged-but-
                              # uncommitted epochs/bytes, writer lag —
                              # listing-driven from each sink's root
    python -m risingwave_tpu ctl --data-dir D backup create|list|
        delete <id> | restore <id> --target T
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import sys


@contextlib.asynccontextmanager
async def serving(data_dir, host: str = "127.0.0.1", port: int = 4566,
                  parallelism: int = 1):
    """The single-process server, started: state store (hummock-lite
    on the local FS under ``data_dir``, in-memory when None),
    Frontend, DDL-log recovery, pgwire listener and the barrier
    heartbeat (0.25 s from inject to inject). Yields ``(frontend,
    pg_server, heartbeat_task)``; leaving the block stops the
    heartbeat and the listener. ``serve`` / ``playground`` and
    ``chip_smoke.py`` all start through here."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.frontend.pgwire import PgServer

    if data_dir:
        from risingwave_tpu.storage.hummock import HummockLite
        from risingwave_tpu.storage.object_store import (
            LocalFsObjectStore, RetryingObjectStore,
        )
        # serving deployments absorb transient PUT/GET faults in place
        # (jittered-backoff retries) instead of failing a barrier round
        store = HummockLite(
            RetryingObjectStore(LocalFsObjectStore(data_dir)))
        # this process compacts: one level of allocator behaviour, not
        # the one its allocation history happens to leave it
        from risingwave_tpu.utils.memory import (
            freeze_startup_heap, keep_freed_heap,
        )
        keep_freed_heap()
        # and one length of the collector's full pass: the jobs'
        # objects, not the imports' beside them
        freeze_startup_heap()
    else:
        from risingwave_tpu.state.store import MemoryStateStore
        store = MemoryStateStore()
    fe = Frontend(store, parallelism=parallelism)
    replayed = await fe.recover()
    if replayed:
        print(f"recovered {replayed} DDL statements", file=sys.stderr)
    srv = PgServer(fe)
    await srv.serve(host, port)
    print(f"listening on {host}:{srv.port} "
          f"(psql -h {host} -p {srv.port})", file=sys.stderr)
    hb = asyncio.ensure_future(fe.run_heartbeat())
    try:
        yield fe, srv, hb
    finally:
        hb.cancel()
        await srv.close()


async def _serve(args) -> None:
    async with serving(args.data_dir, args.host, args.port) \
            as (_fe, _srv, hb):
        # serve until the heartbeat dies — a failed heartbeat means
        # checkpoints stopped; better to crash than serve stale MVs
        await asyncio.wait({hb}, return_when=asyncio.FIRST_COMPLETED)
        hb.result()


async def _serve_cluster(args) -> None:
    """pgwire over the DISTRIBUTED session: N worker processes under
    one data dir, MVs fragment across them, psql talks to the
    coordinator (frontend-node shape)."""
    from risingwave_tpu.cluster.session import DistFrontend
    from risingwave_tpu.frontend.pgwire import PgServer

    fe = DistFrontend(args.data_dir, n_workers=args.workers,
                      parallelism=args.parallelism or args.workers)
    srv = PgServer(fe)
    hb = None
    try:
        await fe.start()
        # inside the try: a bind failure must still stop the worker
        # subprocesses fe.start() just spawned
        await srv.serve(args.host, args.port)
        print(f"cluster of {args.workers} workers; listening on "
              f"{args.host}:{srv.port} "
              f"(psql -h {args.host} -p {srv.port})", file=sys.stderr)
        hb = asyncio.ensure_future(fe.run_heartbeat())
        await asyncio.wait({hb}, return_when=asyncio.FIRST_COMPLETED)
        hb.result()
    finally:
        if hb is not None:
            hb.cancel()
        await srv.close()
        await fe.close()


def _ctl(args) -> int:
    """Offline inspection/ops against a data directory (risectl)."""
    import json
    import os

    # ctl is an offline tool and needs no device kernels: it defaults
    # to the CPU so that inspecting a data dir never takes the chip
    # from a running server (a chip belongs to one process at a time;
    # the operator can still export JAX_PLATFORMS to override)
    if "JAX_PLATFORMS" not in os.environ:
        import jax
        jax.config.update("jax_platforms", "cpu")
    if not os.path.isdir(args.data_dir):
        # an inspection tool must refuse to MINT a cluster: a typo'd
        # path reporting an empty-but-healthy catalog is worse than
        # an error
        print(f"error: data dir {args.data_dir!r} does not exist",
              file=sys.stderr)
        return 1

    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    obj = LocalFsObjectStore(args.data_dir)
    verb = args.ctl_cmd

    if verb == "meta" and args.what == "catalog":
        if obj.exists("meta/ddl.json"):
            for line in json.loads(obj.read("meta/ddl.json").decode()):
                print(line)
        return 0
    if verb == "hummock" and args.what == "version":
        if not obj.exists("meta/CURRENT"):
            print("no committed version")
            return 1
        vid = int(obj.read("meta/CURRENT").decode())
        print(json.dumps(json.loads(
            obj.read(f"meta/v{vid}.json").decode()), indent=2))
        return 0
    if verb == "hummock" and args.what == "list-ssts":
        for path in obj.list("data/"):
            print(f"{path}\t{obj.size(path)}B")
        return 0
    if verb == "table":
        return asyncio.run(_ctl_scan(obj, args))
    if verb == "metrics":
        return asyncio.run(_ctl_metrics(obj, args))
    if verb == "memory":
        return asyncio.run(_ctl_memory(obj, args))
    if verb == "trace":
        return asyncio.run(_ctl_trace(obj, args))
    if verb == "phases":
        return asyncio.run(_ctl_phases(obj, args))
    if verb == "top":
        return asyncio.run(_ctl_top(obj, args))
    if verb == "autoscale":
        return asyncio.run(_ctl_autoscale(obj, args))
    if verb == "cost":
        return asyncio.run(_ctl_cost(obj, args))
    if verb == "compaction":
        return asyncio.run(_ctl_compaction(obj, args))
    if verb == "sinks":
        return asyncio.run(_ctl_sinks(obj, args))
    if verb == "backup":
        from risingwave_tpu.meta.backup import (
            create_backup, delete_backup, list_backups, restore_backup,
        )
        if args.what in ("delete", "restore") and not args.ident:
            print(f"error: backup {args.what} needs a backup id",
                  file=sys.stderr)
            return 2
        if args.what == "create":
            print(create_backup(obj))
        elif args.what == "list":
            for b in list_backups(obj):
                print(b)
        elif args.what == "delete":
            if args.ident not in list_backups(obj):
                print(f"error: no backup {args.ident!r}",
                      file=sys.stderr)
                return 1
            print(delete_backup(obj, args.ident), "objects deleted")
        elif args.what == "restore":
            if not args.target:
                print("error: backup restore needs --target",
                      file=sys.stderr)
                return 2
            if args.ident not in list_backups(obj):
                print(f"error: no backup {args.ident!r}",
                      file=sys.stderr)
                return 1
            try:
                restore_backup(obj, args.ident,
                               LocalFsObjectStore(args.target))
            except ValueError as e:      # non-empty target
                print(f"error: {e}", file=sys.stderr)
                return 1
            print(f"restored backup {args.ident} into {args.target}")
        return 0
    return 2


def _snapshot_clone(obj):
    """In-memory clone of the CURRENT version's CLOSURE (the backup
    helper's consistency argument: versions are immutable and vacuum
    is deferred), so it is a true snapshot even beside a live serve
    process racing compactions — a bare list-then-read-all could see
    a torn CURRENT or a just-vacuumed SST. The copy runs unmetered:
    the tooling traffic must not inflate the object-store op counters
    a later metrics dump reports."""
    from risingwave_tpu.meta.backup import _closure
    from risingwave_tpu.storage.object_store import (
        MemObjectStore, unmetered,
    )

    clone = MemObjectStore()
    with unmetered():
        for path in _closure(obj):
            clone.upload(path, obj.read(path))
    return clone


async def _ctl_scan(obj, args) -> int:
    """READ-ONLY scan: recovery replays DDL through deploy, which
    commits checkpoint versions — so recover over an in-memory
    snapshot clone."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.storage.hummock import HummockLite

    fe = Frontend(HummockLite(_snapshot_clone(obj)))
    await fe.recover()
    try:
        rows = await fe.execute(
            f"SELECT * FROM {args.ident} LIMIT {args.limit}")
    finally:
        await fe.close()
    for r in rows:
        print("\t".join("NULL" if v is None else str(v) for v in r))
    return 0


async def _ctl_metrics(obj, args) -> int:
    """Recover the cluster into an in-memory clone (same snapshot
    discipline as `table scan`), drive a couple of checkpoints so
    every metric family has live series, and dump the Prometheus text
    exposition — what a scraper would see on a serving node."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.utils.metrics import GLOBAL

    fe = Frontend(HummockLite(_snapshot_clone(obj)))
    await fe.recover()
    try:
        await fe.step(args.steps)
        # render BEFORE teardown: close() removes the liveness series
        # (stream_actor_count, queue depths) the dump is for
        text = GLOBAL.render()
    finally:
        await fe.close()
    print(text, end="")
    return 0


async def _ctl_memory(obj, args) -> int:
    """Recover into an in-memory clone (same snapshot discipline as
    `table scan`), drive a couple of checkpoints, and dump the host-
    memory accounting: MemoryContext.sizes() per cache plus per-
    executor state-tier residency (cap / resident / evicted / reloads
    / bytes) — what the memory manager and the tier see on a serving
    node."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.state.tier import GLOBAL as TIER
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.utils.memory import GLOBAL as MEM

    fe = Frontend(HummockLite(_snapshot_clone(obj)))
    await fe.recover()
    try:
        await fe.step(args.steps)
        sizes = MEM.sizes()
        total = sum(sizes.values())
        limit = MEM.soft_limit
        print(f"accounted host state: {total}B"
              + ("" if limit is None else f" (soft limit {limit}B)"))
        for name in sorted(sizes, key=lambda n: -sizes[n]):
            print(f"  {sizes[name]:>12}B  {name}")
        rows = sorted(TIER.stats_rows())
        if rows:
            print("state tier (cap/resident/evicted/reloads/bytes):")
            for name, cap, res, ev, rl, nb in rows:
                cap_s = "-" if cap < 0 else str(cap)
                print(f"  {name}: cap={cap_s} resident={res} "
                      f"evicted={ev} reloads={rl} bytes={nb}")
        stats = TOPOLOGY.table_stats()
        if stats:
            print("state topology (per-table, hottest vnodes):")
            for t, mv, nrows, nbytes, vns, imb in stats:
                print(f"  table {t} ({mv or '?'}): {nrows} rows, "
                      f"{nbytes}B over {vns} vnodes, "
                      f"imbalance {imb:.2f}")
                for vn, vrows, vbytes in TOPOLOGY.top_vnodes(t, 8):
                    print(f"    vnode {vn:>5}: {vrows:>8} rows "
                          f"{vbytes:>12}B")
    finally:
        await fe.close()
    return 0


async def _ctl_trace(obj, args) -> int:
    """Recover into an in-memory clone (same snapshot discipline as
    `table scan`), drive a few checkpoints so the flight recorder
    holds live epoch traces, and export them as Chrome trace-event
    JSON — open the file at ui.perfetto.dev (or chrome://tracing) to
    walk an epoch from barrier inject to commit."""
    import json

    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.utils.spans import EPOCH_TRACER

    fe = Frontend(HummockLite(_snapshot_clone(obj)))
    await fe.recover()
    try:
        await fe.step(args.steps)
        trace = EPOCH_TRACER.export_chrome()
    finally:
        await fe.close()
    text = json.dumps(trace, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        n = sum(1 for e in trace["traceEvents"] if e["ph"] == "X")
        print(f"wrote {n} spans across "
              f"{len(EPOCH_TRACER.epochs())} epochs to {args.out}",
              file=sys.stderr)
    else:
        print(text)
    return 0


async def _ctl_phases(obj, args) -> int:
    """Recover into an in-memory clone (same snapshot discipline as
    `table scan`), drive a few checkpoints so the phase ledger holds
    sealed epochs, and print the per-epoch breakdown: how every
    millisecond of each barrier interval splits across host_ingest /
    host_pack / h2d / device_compute / d2h / host_emit / barrier_wait,
    the conservation coverage, transfer bytes, and the compiled
    kernels' cost-analysis yardsticks."""
    import json

    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.utils.jaxtools import (
        kernel_cost_rows, publish_kernel_costs,
    )
    from risingwave_tpu.utils.ledger import LEDGER

    fe = Frontend(HummockLite(_snapshot_clone(obj)))
    await fe.recover()
    try:
        await fe.step(args.steps)
        report = LEDGER.report(last_n=args.steps + 2)
        agg = LEDGER.phase_breakdown()
        publish_kernel_costs()
        costs = kernel_cost_rows()
    finally:
        await fe.close()
    print(report)
    print("aggregate (steady epochs):")
    print(json.dumps(agg, indent=1))
    if costs:
        print("compiled kernel costs (flops / bytes accessed):")
        for label, flops, nbytes in costs:
            print(f"  {label}: {flops:.3g} flops, {nbytes:.3g} B")
    return 0


async def _ctl_top(obj, args) -> int:
    """Recover into an in-memory clone (same snapshot discipline as
    `table scan`), drive a few checkpoints per refresh, and print the
    live-ops view: actor utilization tricolor sorted busiest first,
    per-MV event-time freshness, and each barrier domain's current
    walked bottleneck. ``--watch N`` repeats the drive+print cycle N
    times (a poor man's `top` refresh over the recovered pipelines)."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.stream.bottleneck import BOTTLENECKS
    from risingwave_tpu.stream.freshness import FRESHNESS
    from risingwave_tpu.stream.monitor import UTILIZATION

    fe = Frontend(HummockLite(_snapshot_clone(obj)))
    await fe.recover()
    try:
        for cycle in range(max(1, args.watch)):
            await fe.step(args.steps)
            if cycle:
                print()
            print(f"== refresh {cycle + 1} — actor utilization "
                  f"(share of last barrier) ==")
            print(f"{'actor':>6} {'node':>4} {'busy':>6} {'bp':>6} "
                  f"{'idle':>6}  fragment / executor")
            for (a, frag, node, ex, _e, _i, busy, bp,
                 idle) in UTILIZATION.rows():
                print(f"{a:>6} {node:>4} {busy:>6.1%} {bp:>6.1%} "
                      f"{idle:>6.1%}  {frag} / {ex}")
            print("== per-MV freshness ==")
            print(f"{'lag_s':>8} {'wall_s':>8} {'p99_s':>8} "
                  f"{'n':>5}  mv (domain)")
            for (mv, dom, n, _e, lag, wall, _p50, p99,
                 _wp99) in FRESHNESS.rows():
                if not n:
                    continue
                print(f"{lag:>8.3f} {wall:>8.3f} {p99:>8.3f} "
                      f"{n:>5}  {mv}"
                      + (f" ({dom})" if dom else ""))
            print("== bottlenecks ==")
            for (dom, op, _frag, actor, _node, busy, bp, streak,
                 sustained, _e, diag) in BOTTLENECKS.rows():
                label = dom or "(global)"
                if op is None:
                    print(f"{label}: no sustained bottleneck")
                else:
                    print(f"{label}: {op} (actor {actor}) busy "
                          f"{busy:.0%}, downstream bp {bp:.0%}, "
                          f"streak {streak}"
                          + (" [SUSTAINED]" if sustained else ""))
                    if diag:
                        print(f"    {diag}")
    finally:
        await fe.close()
    return 0


async def _ctl_autoscale(obj, args) -> int:
    """Recover into an in-memory clone (same snapshot discipline as
    `table scan`), drive a few checkpoints, and print the elastic
    control loop's view: the decision ledger (rw_autoscaler — on a
    serving cluster this holds the live history; offline it shows what
    this inspection process decided, normally nothing) and the signals
    a decision would read — per-domain bottleneck verdicts and per-MV
    freshness. The live workflow: ``SET stream_autoscale = on`` on the
    serving session, then ``SELECT * FROM rw_autoscaler`` /
    ``rw_recovery`` over pgwire."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.meta.autoscaler import autoscaler_rows
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.stream.bottleneck import BOTTLENECKS
    from risingwave_tpu.stream.freshness import FRESHNESS

    fe = Frontend(HummockLite(_snapshot_clone(obj)))
    await fe.recover()
    try:
        await fe.step(args.steps)
        rows = autoscaler_rows()
        print("== autoscaler decision ledger ==")
        if not rows:
            print("(empty — decisions live on the serving "
                  "coordinator; query rw_autoscaler there)")
        for (seq, mv, frag, op, direction, fp, tp, outcome, reason,
             _e, dur, detail) in rows:
            print(f"#{seq} {mv}/f{frag} {direction} {fp}->{tp} "
                  f"[{outcome}] {dur:.2f}s  {reason}"
                  + (f"  ({detail})" if detail else ""))
        print("== signals a decision would read ==")
        for (dom, op, _frag, actor, _node, busy, bp, streak,
             sustained, _e, diag) in BOTTLENECKS.rows():
            label = dom or "(global)"
            if op is None:
                print(f"{label}: no sustained bottleneck")
            else:
                print(f"{label}: {op} busy {busy:.0%} streak {streak}"
                      + (" [SUSTAINED — actionable]" if sustained
                         else " (not sustained — ignored)"))
        for (mv, dom, n, _e, lag, wall, _p50, _p99,
             wp99) in FRESHNESS.rows():
            if n:
                print(f"freshness {mv}: lag {lag:.3f}s wall "
                      f"{wall:.3f}s wall_p99 {wp99:.3f}s")
    finally:
        await fe.close()
    return 0


async def _ctl_cost(obj, args) -> int:
    """Recover into an in-memory clone (same snapshot discipline as
    `table scan`), drive a few checkpoints per refresh, and print the
    serving-cost attribution view: the per-MV resource ledger
    (device-seconds, transfer bytes, resident state, compile-cache
    economics, rescale/recovery charge-back), each MV's worst
    hot-vnode imbalance, and the hottest keys per executor input.
    ``--watch N`` repeats the drive+print cycle N times. On a serving
    cluster, ``SELECT * FROM rw_mv_costs`` / ``rw_hot_keys`` /
    ``rw_state_topology`` over pgwire see the live books."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.stream.costs import COSTS
    from risingwave_tpu.stream.hotkeys import HOTKEYS

    fe = Frontend(HummockLite(_snapshot_clone(obj)))
    await fe.recover()
    try:
        for cycle in range(max(1, args.watch)):
            await fe.step(args.steps)
            if cycle:
                print()
            imb = TOPOLOGY.imbalance_by_mv()
            print(f"== refresh {cycle + 1} — per-MV serving cost ==")
            print(f"{'device_s':>10} {'h2d_B':>12} {'d2h_B':>12} "
                  f"{'state_B':>12} {'compile':>12} {'charge_s':>9} "
                  f"{'imb':>5}  mv (domain)")
            rows = sorted(COSTS.rows(), key=lambda r: -r[2])
            for (mv, dom, dev, h2d, d2h, state, hits, misses,
                 shared, rescale_s, recovery_s) in rows:
                comp = f"{hits}h/{misses}m"
                if shared:
                    comp += f"/{shared}s"
                print(f"{dev:>10.4f} {h2d:>12} {d2h:>12} "
                      f"{state:>12} {comp:>12} "
                      f"{rescale_s + recovery_s:>9.2f} "
                      f"{imb.get(mv, 1.0):>5.2f}  {mv}"
                      + (f" ({dom})" if dom else ""))
            if not rows:
                print("(no attributed epochs yet)")
            hot = HOTKEYS.rows()
            if hot:
                print("== hot keys (top rank per input) ==")
                for (mv, ex, rank, key, est, share, err) in hot:
                    if rank:
                        continue
                    print(f"  {share:>6.1%} (±{err:.1%}) "
                          f"{key!r}  {mv} / {ex}")
    finally:
        await fe.close()
    return 0


async def _ctl_compaction(obj, args) -> int:
    """Recover into an in-memory clone (same snapshot discipline as
    `table scan`), flip the DEDICATED arm on, drive a few checkpoints
    per refresh, and print the compaction view: per-level topology
    (L0 run count, L1 runs with tombstone density), the space-amp
    gauge, and the task ledger (rw_compaction) the clone's manager
    produced. ``--watch N`` repeats the drive+print cycle N times. On
    a serving cluster, ``SET storage_compaction='dedicated'`` there
    and ``SELECT * FROM rw_compaction`` over pgwire see the live
    ledger."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.meta.compaction import compaction_rows
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.utils.metrics import STORAGE

    store = HummockLite(_snapshot_clone(obj))
    fe = Frontend(store)
    await fe.recover()
    try:
        await fe.execute("SET storage_compaction = 'dedicated'")
        for cycle in range(max(1, args.watch)):
            await fe.step(args.steps)
            if cycle:
                print()
            snap = store.level_snapshot()
            l0, l1 = snap["l0"], snap["l1"]
            print(f"== refresh {cycle + 1} — level topology "
                  f"(version {snap['version_id']}) ==")
            print(f"L0: {len(l0)} runs, "
                  f"{sum(i.get('size', 0) for i in l0)}B")
            for i in l1:
                n = i.get("count", 0) or 1
                print(f"L1 sst {i['id']}: {i.get('size', 0)}B "
                      f"{i.get('count', 0)} keys, tombstones "
                      f"{i.get('tombstones', 0) / n:.0%}")
            if snap.get("reserved"):
                print(f"reserved under in-flight tasks: "
                      f"{snap['reserved']}")
            print(f"space_amp {STORAGE.storage_space_amp.get():.3f}  "
                  f"pending "
                  f"{STORAGE.compaction_pending_tasks.get():.0f}")
            rows = compaction_rows()
            print("== compaction task ledger ==")
            if not rows:
                print("(no tasks — levels below every picker's "
                      "trigger)")
            for (tid, ns, picker, state, ins, outs, br, bw, att,
                 dur, detail) in rows:
                print(f"#{tid} [{ns}] {picker} {state} in=[{ins}] "
                      f"out=[{outs}] read {br}B wrote {bw}B "
                      f"attempts {att} {dur:.2f}s"
                      + (f"  ({detail})" if detail else ""))
    finally:
        await fe.close()
    return 0


async def _ctl_sinks(obj, args) -> int:
    """Recover into an in-memory clone (same snapshot discipline as
    `table scan`) and print the sink view (rw_sinks): per-sink mode,
    committed epoch, staged-but-uncommitted epochs/bytes, and writer
    lag — all listing-driven from each sink's own object-store root,
    so the numbers are the REAL sink's, not the clone's. Note: DDL
    replay runs the standard recovery sweep on each epochlog sink
    (promote floor-covered staging, truncate the rest), exactly as a
    serving restart would. ``--steps K`` additionally drives K
    checkpoints, which APPENDS real rows to the sinks — default 0
    keeps inspection read-only."""
    from risingwave_tpu.frontend import Frontend
    from risingwave_tpu.storage.hummock import HummockLite

    store = HummockLite(_snapshot_clone(obj))
    fe = Frontend(store)
    await fe.recover()
    try:
        if args.steps:
            await fe.step(args.steps)
        rows = await fe.execute("SELECT * FROM rw_sinks")
        print("== sinks ==")
        if not rows:
            print("(no sinks)")
        for (name, connector, mode, epoch, staged, nbytes, lag) in rows:
            print(f"{name} [{connector}/{mode or 'legacy'}] "
                  f"committed_epoch {int(epoch):#x} "
                  f"staged_epochs {staged} staged {nbytes}B "
                  f"writer_lag {lag}")
    finally:
        await fe.close()
    return 0


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="risingwave_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)
    sc = sub.add_parser("serve-cluster",
                        help="pgwire over an N-worker cluster")
    sc.add_argument("--data-dir", required=True)
    sc.add_argument("--workers", type=int, default=2)
    sc.add_argument("--parallelism", type=int, default=None)
    sc.add_argument("--host", default="127.0.0.1")
    sc.add_argument("--port", type=int, default=4566)
    for name in ("playground", "serve"):
        sp = sub.add_parser(name)
        sp.add_argument("--host", default="127.0.0.1")
        sp.add_argument("--port", type=int, default=4566)
        if name == "serve":               # playground is in-memory only
            sp.add_argument("--data-dir", required=True)
    ctl = sub.add_parser("ctl")
    ctl.add_argument("--data-dir", required=True)
    csub = ctl.add_subparsers(dest="ctl_cmd", required=True)
    meta = csub.add_parser("meta")
    meta.add_argument("what", choices=["catalog"])
    hm = csub.add_parser("hummock")
    hm.add_argument("what", choices=["version", "list-ssts"])
    tb = csub.add_parser("table")
    tb.add_argument("what", choices=["scan"])
    tb.add_argument("ident")
    tb.add_argument("-n", "--limit", type=int, default=20)
    mt = csub.add_parser(
        "metrics", help="recover + dump the Prometheus exposition")
    mt.add_argument("--steps", type=int, default=2,
                    help="checkpoint barriers to drive before the dump")
    mm = csub.add_parser(
        "memory",
        help="recover + dump host-memory accounting and state-tier "
             "residency")
    mm.add_argument("--steps", type=int, default=2,
                    help="checkpoint barriers to drive before the dump")
    tr = csub.add_parser(
        "trace",
        help="recover + export epoch-causal traces as Chrome "
             "trace-event JSON (Perfetto-loadable; includes phase "
             "lanes and byte/queue-depth counter tracks)")
    tr.add_argument("--steps", type=int, default=4,
                    help="checkpoint barriers to drive before export")
    tr.add_argument("--out", default=None,
                    help="write the JSON here instead of stdout")
    ph = csub.add_parser(
        "phases",
        help="recover + print the epoch phase ledger: per-barrier "
             "host/device time+bytes breakdown, conservation "
             "coverage, compiled-kernel cost yardsticks")
    ph.add_argument("--steps", type=int, default=4,
                    help="checkpoint barriers to drive before the "
                         "report")
    tp = csub.add_parser(
        "top",
        help="recover + print the live-ops view: actor utilization "
             "tricolor (busy/backpressure/idle), per-MV event-time "
             "freshness, and each domain's walked bottleneck")
    tp.add_argument("--steps", type=int, default=4,
                    help="checkpoint barriers to drive per refresh")
    tp.add_argument("--watch", type=int, default=1,
                    help="refresh cycles to print (drive+print each)")
    asc = csub.add_parser(
        "autoscale",
        help="recover + print the elastic control loop's view: the "
             "rw_autoscaler decision ledger and the bottleneck/"
             "freshness signals a decision would read")
    asc.add_argument("--steps", type=int, default=4,
                     help="checkpoint barriers to drive before the "
                          "report")
    co = csub.add_parser(
        "cost",
        help="recover + print the serving-cost attribution view: "
             "per-MV device-seconds / transfer / state / compile-"
             "cache ledger, hot-vnode imbalance, and heavy-hitter "
             "keys")
    co.add_argument("--steps", type=int, default=4,
                    help="checkpoint barriers to drive per refresh")
    co.add_argument("--watch", type=int, default=1,
                    help="refresh cycles to print (drive+print each)")
    cp = csub.add_parser(
        "compaction",
        help="recover + print the leveled-compaction view: per-level "
             "topology, tombstone density, space amp, and the "
             "dedicated-arm task ledger (rw_compaction)")
    cp.add_argument("--steps", type=int, default=4,
                    help="checkpoint barriers to drive per refresh")
    cp.add_argument("--watch", type=int, default=1,
                    help="refresh cycles to print (drive+print each)")
    sk = csub.add_parser(
        "sinks",
        help="recover + print the sink view (rw_sinks): per-sink "
             "committed epoch, staged-but-uncommitted epochs/bytes, "
             "writer lag — listing-driven from each sink's root")
    sk.add_argument("--steps", type=int, default=0,
                    help="checkpoint barriers to drive first (writes "
                         "real sink rows; default 0 = read-only)")
    bk = csub.add_parser("backup")
    bk.add_argument("what",
                    choices=["create", "list", "delete", "restore"])
    bk.add_argument("ident", nargs="?")
    bk.add_argument("--target")
    args = p.parse_args(argv)
    if args.cmd == "ctl":
        sys.exit(_ctl(args))
    # every server start would otherwise recompile the whole kernel
    # zoo (about thirty jit labels times their shape buckets)
    from risingwave_tpu.utils.jaxtools import enable_compilation_cache
    enable_compilation_cache()
    if not hasattr(args, "data_dir"):
        args.data_dir = None
    if args.cmd == "serve-cluster":
        asyncio.run(_serve_cluster(args))
        return
    asyncio.run(_serve(args))


if __name__ == "__main__":
    main()
