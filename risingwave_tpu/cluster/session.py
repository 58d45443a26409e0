"""DistFrontend: SQL session over an N-worker cluster.

Reference parity: the frontend node talking to meta + compute nodes —
handler/create_mv.rs:147 (plan → fragment → deploy via DdlService) and
the distributed batch read path (scheduler/distributed/stage.rs,
RowSeqScan per node + exchange-gather). TPU re-design: CREATE
MATERIALIZED VIEW plans on the coordinator with the SAME StreamPlanner
the in-process session uses, then the fragmenter serializes the
executor tree to plan IR, cuts it at hash exchanges, and the cluster
scheduler lands the fragments on worker processes. SELECT gathers each
referenced MV's committed rows from every worker namespace into a
snapshot view and runs the ordinary batch planner over it.
"""

from __future__ import annotations

import asyncio
import bisect
from typing import Dict, List, Optional, Union

from risingwave_tpu.cluster.scheduler import Cluster
from risingwave_tpu.frontend import ast
from risingwave_tpu.meta.supervisor import RecoveryStormError
from risingwave_tpu.frontend.catalog import Catalog, MvCatalog
from risingwave_tpu.frontend.fragmenter import Fragmenter
from risingwave_tpu.frontend.planner import (
    PlanError, StreamPlanner, plan_batch, source_schema,
)
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.stream.actor import LocalBarrierManager

Rows = List[tuple]


class ClusterStoreView:
    """Read-only store over rows gathered from worker namespaces —
    batch executors (RowSeqScan via StorageTable) read it like any
    state store. Tables must be prefetched before the sync read."""

    def __init__(self, cluster: Cluster):
        self.cluster = cluster
        self._tables: Dict[int, List[tuple]] = {}   # tid → [(k, row)]

    async def prefetch(self, table_id: int) -> None:
        self._tables[table_id] = await self.cluster.scan_table(table_id)

    def committed_epoch(self) -> int:
        return self.cluster.store.committed_epoch()

    def get(self, table_id: int, key: bytes, epoch: int):
        rows = self._tables.get(table_id, [])
        i = bisect.bisect_left(rows, (key,))
        if i < len(rows) and rows[i][0] == key:
            return rows[i][1]
        return None

    def iter(self, table_id: int, epoch: int, start=None, end=None,
             reverse: bool = False):
        rows = self._tables.get(table_id, [])
        out = [(k, v) for k, v in rows
               if (start is None or k >= start)
               and (end is None or k < end)]
        return iter(reversed(out) if reverse else out)


class DistFrontend:
    """One SQL session driving an N-worker cluster."""

    def __init__(self, root: str, n_workers: int = 2,
                 parallelism: Optional[int] = None,
                 rate_limit: Optional[int] = 8,
                 min_chunks: Optional[int] = None,
                 barrier_timeout_s: Optional[float] = None,
                 epoch_pipeline: bool = True):
        self.cluster = Cluster(root, n_workers,
                               barrier_timeout_s=barrier_timeout_s,
                               epoch_pipeline=epoch_pipeline)
        self.catalog = Catalog()
        self.parallelism = parallelism or n_workers
        self.rate_limit = rate_limit
        self.min_chunks = min_chunks
        self.last_select_schema = None
        # chunk coalescing knobs — same surface as the in-process
        # session (no-drift contract): the planner's keyed-input
        # coalescers AND the scheduler's merge-node re-coalescing both
        # read them (SET stream_chunk_target_rows = 0 disables both)
        from risingwave_tpu.stream.coalesce import (
            DEFAULT_MAX_CHUNKS, DEFAULT_TARGET_ROWS,
        )
        self.chunk_target_rows = DEFAULT_TARGET_ROWS
        self.coalesce_linger_chunks = DEFAULT_MAX_CHUNKS
        # unified state-tiering cap (state/tier.py): the planner stamps
        # it on agg executors and the fragmenter ships it in the IR, so
        # WORKER fragments rebuild with the same memory governance.
        # (The soft-limit var governs the coordinator process only —
        # each worker process has its own MemoryContext.)
        self.state_tier_cap = None
        # name → (select AST, eowc): FROM <mv> inlines the view's
        # definition (distributed MV-on-MV by view expansion)
        self._mv_selects = {}
        # session vars (shared impl with the in-process session —
        # session_vars.py; parallelism is the distributed knob).
        # stream_rewrite_rules rides the same surface as
        # stream_chunk_target_rows: SET here, honored at CREATE time
        from risingwave_tpu.frontend.opt import parse_fusion, parse_rules
        from risingwave_tpu.frontend.session_vars import SessionVars
        from risingwave_tpu.meta.autoscaler import parse_autoscale
        from risingwave_tpu.meta.compaction import (
            parse_compaction as _parse_compaction,
        )
        self.session_vars = SessionVars(
            self, {"streaming_rate_limit": "rate_limit",
                   "streaming_min_chunks": "min_chunks",
                   "parallelism": "parallelism",
                   "state_tier_cap": "state_tier_cap",
                   "state_tier_soft_limit_mb":
                       "state_tier_soft_limit_mb",
                   "stream_chunk_target_rows": "chunk_target_rows",
                   "stream_coalesce_linger_chunks":
                       "coalesce_linger_chunks"},
            {"stream_rewrite_rules": "all",
             # elastic control loop (meta/autoscaler.py): off by
             # default — scaling actions are topology changes an
             # operator opts into; the serving heartbeat ticks the
             # loop while this is on
             "stream_autoscale": "off",
             # fragment fusion (opt/fusion.py). Distributed deploys
             # fuse at ANY parallelism (ISSUE 10): the hash-exchange
             # cut ships raw rows dispatched on key columns mapped
             # back through the absorbed run; runs whose keys don't
             # map to raw refs stay interpretive (rule-side refusal)
             "stream_fusion": "on",
             # compaction arm (ISSUE 19): 'dedicated' provisions the
             # compactor role + CompactionManager (one namespace per
             # worker slot) and moves every merge off the serving path
             "storage_compaction": "inline"},
            validators={"stream_rewrite_rules": parse_rules,
                        "stream_fusion": parse_fusion,
                        "storage_compaction": _parse_compaction,
                        "stream_autoscale": parse_autoscale})
        # the elastic control loop (created lazily on SET
        # stream_autoscale=on; ticked by run_heartbeat while on)
        self.autoscaler = None
        # fragment-graph stats of the last deployed job (exchange
        # hops, exchanged lane widths): what the rewrite engine bought
        self.last_plan_stats: Optional[dict] = None
        # serializes barrier rounds between DDL, step(), SELECT
        # snapshots and the background heartbeat (inject_and_collect
        # is not reentrant; a heartbeat between per-table scans would
        # tear a cross-MV snapshot)
        self._barrier_lock = asyncio.Lock()

    # same surface as the in-process session (no-drift contract);
    # governs the COORDINATOR process's MemoryContext
    @property
    def state_tier_soft_limit_mb(self) -> int:
        from risingwave_tpu.utils import memory as _mem
        sl = _mem.GLOBAL.soft_limit
        return 0 if sl is None else int(sl) >> 20

    @state_tier_soft_limit_mb.setter
    def state_tier_soft_limit_mb(self, v) -> None:
        from risingwave_tpu.utils import memory as _mem
        _mem.GLOBAL.soft_limit = None if not v else int(v) << 20

    async def start(self) -> None:
        await self.cluster.start()

    async def close(self) -> None:
        await self.cluster.stop()

    async def step(self, n: int = 1) -> None:
        async with self._barrier_lock:
            await self.cluster.step(n)
            # dedicated compaction: settle/dispatch under the same
            # lock a rescale or recovery would hold — an apply never
            # interleaves a topology change
            await self.cluster.compaction_tick()

    async def recover(self) -> None:
        async with self._barrier_lock:
            await self.cluster.recover()

    async def supervised_recover(self, exc: BaseException):
        """Classify `exc` and run the graduated recovery ladder (the
        chaos harness and external drivers share the serving loop's
        path); returns the recorded RecoveryEvent."""
        async with self._barrier_lock:
            return await self.cluster.supervised_recover(exc)

    def _autoscale_on(self) -> bool:
        from risingwave_tpu.meta.autoscaler import parse_autoscale
        return (self.autoscaler is not None
                and self.autoscaler.enabled
                and parse_autoscale(
                    self.session_vars.get("stream_autoscale")))

    async def run_heartbeat(self, interval_s: float = 0.25) -> None:
        """Supervised serving loop (server deployments): each beat
        steps one barrier and ticks worker liveness; a failed round
        feeds the RecoverySupervisor — classify, then the cheapest
        graduated response (absorb / respawn dead slots in place /
        full kill-and-redeploy), with bounded attempts and jittered
        backoff between consecutive recoveries. The only way out is a
        RecoveryStormError: the recovery budget exhausted without a
        healthy round — loud and terminal, never a silent loop and
        never the old recover-once-then-die."""
        import sys
        import traceback
        self.cluster.enable_liveness()
        try:
            while True:
                await asyncio.sleep(interval_s)
                async with self._barrier_lock:
                    try:
                        await self.cluster.step(1)
                        self.cluster.supervisor.note_healthy()
                        if self.autoscaler is not None:
                            # a clean round closes the autoscaler's
                            # storm window too (only after a SUCCESSFUL
                            # action — rollbacks keep the backoff)
                            self.autoscaler.note_healthy()
                        if self._autoscale_on():
                            # elastic control loop (ISSUE 15): signals
                            # → decision → guarded rescale, inside the
                            # barrier lock so a concurrent ALTER queues
                            # behind the action instead of interleaving
                            await self.autoscaler.tick()
                        await self.cluster.compaction_tick()
                    except asyncio.CancelledError:
                        raise
                    except Exception as e:  # noqa: BLE001 — classified
                        try:
                            await self.cluster.supervised_recover(e)
                        except asyncio.CancelledError:
                            raise
                        except RecoveryStormError:
                            raise
                        except Exception as rexc:  # noqa: BLE001
                            # a recovery that itself failed is already
                            # recorded (ok=False); the next beat
                            # reclassifies the still-broken state —
                            # the storm gate bounds this loop, not
                            # first-failure death
                            print("recovery attempt failed "
                                  f"(will reclassify): {rexc!r}",
                                  file=sys.stderr)
                await self.cluster.liveness_tick()
        except asyncio.CancelledError:
            raise
        except BaseException:
            print("serving heartbeat terminated:", file=sys.stderr)
            traceback.print_exc()
            raise

    # -- statements -------------------------------------------------------
    async def execute(self, sql: str) -> Union[Rows, str]:
        from risingwave_tpu.frontend.parser import parse_many

        result: Union[Rows, str] = "OK"
        for _text, stmt in parse_many(sql):
            result = await self._run(stmt)
        return result

    async def _run(self, stmt) -> Union[Rows, str]:
        self.last_select_schema = None
        if isinstance(stmt, ast.CreateSource):
            schema = source_schema(stmt.options, stmt.columns)
            self.catalog.add_source(stmt.name, schema, stmt.options,
                                    watermark=stmt.watermark)
            return "CREATE_SOURCE"
        if isinstance(stmt, ast.CreateMaterializedView):
            return await self._create_mv(stmt)
        if isinstance(stmt, ast.DropMaterializedView):
            return await self._drop_mv(stmt)
        if isinstance(stmt, ast.CreateSink):
            return await self._create_sink(stmt)
        if isinstance(stmt, ast.DropSink):
            return await self._drop_sink(stmt)
        if isinstance(stmt, ast.SetVar):
            self.session_vars.set(stmt.name, stmt.value)
            if stmt.name == "storage_compaction":
                # fans to every worker + (de)provisions the compactor
                # role; serialized with barrier rounds so the flip
                # cannot interleave a commit with a manager drain
                async with self._barrier_lock:
                    await self.cluster.set_compaction(
                        self.session_vars.get("storage_compaction"))
            if stmt.name == "stream_autoscale":
                from risingwave_tpu.meta.autoscaler import (
                    Autoscaler, parse_autoscale,
                )
                if parse_autoscale(
                        self.session_vars.get("stream_autoscale")):
                    if self.autoscaler is None:
                        self.autoscaler = Autoscaler(self.cluster)
                    # re-enabling after a storm is an explicit
                    # operator decision — reset the disabled latch
                    # AND the exhausted backoff budget (a still-maxed
                    # gate would re-raise the storm on the next
                    # decision without attempting a single rescale)
                    self.autoscaler.reset_storm()
            return "SET"
        if isinstance(stmt, ast.Show):
            if stmt.what == "var:all":
                return self.session_vars.show_all()
            if stmt.what.startswith("var:"):
                name = stmt.what[4:].lower()
                if not self.session_vars.known(name):
                    raise PlanError("unrecognized configuration "
                                    f"parameter {name!r}")
                return [(self.session_vars.get(name),)]
            if stmt.what == "sources":
                return [(n,) for n in sorted(self.catalog.sources)]
            if stmt.what == "sinks":
                return [(n,) for n in sorted(self.catalog.sinks)]
            if stmt.what == "tables":
                return [(n,) for n, m in sorted(self.catalog.mvs.items())
                        if m.is_table]
            return [(n,) for n, m in sorted(self.catalog.mvs.items())
                    if not m.is_table]
        if isinstance(stmt, ast.Explain):
            from risingwave_tpu.frontend.opt import explain_with_rewrite
            planner = StreamPlanner(
                self.catalog, MemoryStateStore(),
                LocalBarrierManager(), definition="", mesh=None,
                actors={}, dist_parallelism=self.parallelism,
                inline_mvs=self._mv_selects,
                chunk_target_rows=self.chunk_target_rows,
                coalesce_linger_chunks=self.coalesce_linger_chunks)
            plan = planner.plan("__explain__", stmt.select, actor_id=0,
                                rate_limit=self.rate_limit,
                                min_chunks=self.min_chunks)
            from risingwave_tpu.frontend.opt import parse_fusion
            return explain_with_rewrite(
                plan.consumer,
                self.session_vars.get("stream_rewrite_rules"),
                fusion=parse_fusion(
                    self.session_vars.get("stream_fusion")),
                dist_parallelism=self.parallelism)
        if isinstance(stmt, ast.AlterParallelism):
            return await self._alter_parallelism(stmt)
        if isinstance(stmt, ast.Flush):
            await self.step(1)
            return "FLUSH"
        if isinstance(stmt, ast.Select):
            return await self._select(stmt)
        raise PlanError(
            f"unhandled statement on the distributed session: {stmt!r}")

    async def _create_mv(self, stmt: ast.CreateMaterializedView) -> str:
        """Plan with the ordinary StreamPlanner (against throwaway
        runtime objects), fragment the executor tree, deploy across the
        cluster, then run the activation barrier."""
        self.catalog._check_free(stmt.name)
        if getattr(stmt, "emit_on_window_close", False):
            raise PlanError("EMIT ON WINDOW CLOSE is not distributed "
                            "yet — use the in-process session")
        planner = StreamPlanner(self.catalog, MemoryStateStore(),
                                LocalBarrierManager(), definition="",
                                mesh=None, actors={},
                                dist_parallelism=self.parallelism,
                                inline_mvs=self._mv_selects,
                                chunk_target_rows=self.chunk_target_rows,
                                coalesce_linger_chunks=self
                                .coalesce_linger_chunks,
                                state_tier_cap=self.state_tier_cap
                                or None)
        plan = planner.plan(stmt.name, stmt.select, actor_id=0,
                            rate_limit=self.rate_limit,
                            min_chunks=self.min_chunks)
        # executor-graph rewrite before lowering (same engine as the
        # in-process session); the fragment-graph pass below then
        # elides exchanges on the shipped plan IR
        from risingwave_tpu.frontend.opt import (
            apply_rewrites, parse_fusion,
        )
        rules = self.session_vars.get("stream_rewrite_rules")
        # fusion at ANY parallelism since ISSUE 10: the fragmenter cuts
        # below an absorbed run on raw-mapped key columns, and the rule
        # refuses runs whose keys don't map (opt/fusion.py)
        fusion = parse_fusion(self.session_vars.get("stream_fusion"))
        apply_rewrites(plan, rules, label=stmt.name, fusion=fusion,
                       dist_parallelism=self.parallelism)
        if plan.attaches:
            # every FROM <mv> should have inlined (the dict holds all
            # session-created views); a chain attach here means a
            # catalog/selects mismatch — refuse rather than ship a
            # graph with dangling attach edges
            raise PlanError(
                "internal: distributed plan produced chain attaches "
                "(view not inlined?) — cannot deploy")
        graph = Fragmenter(
            self.parallelism,
            merge_coalesce_rows=self.chunk_target_rows,
            merge_coalesce_chunks=self.coalesce_linger_chunks
        ).lower(plan.consumer)
        from risingwave_tpu.frontend.opt import (
            fragment_plan_stats, rewrite_fragment_graph,
        )
        graph, _elided = rewrite_fragment_graph(graph, rules,
                                                label=stmt.name)
        self.last_plan_stats = fragment_plan_stats(graph)
        async with self._barrier_lock:
            # domain anchors: the job's own name + every source/MV it
            # reads — shared-source fan-outs and view-expanded chains
            # align in one barrier domain, disjoint jobs in their own
            await self.cluster.deploy_graph(
                stmt.name, graph,
                domain_keys={stmt.name, *plan.mv.dependent_sources})
            await self.cluster.step(1)     # activation barrier
        self.catalog.add_mv(plan.mv)
        # freshness lineage on the COORDINATOR tracker: the worker
        # fragments report raw parts; the merge joins them under this
        # registration (drain_freshness). MV deps resolve to their
        # SOURCES transitively, same as the in-process session — a
        # chained MV bound to no frontier would report constant
        # zero-lag samples
        from risingwave_tpu.stream.freshness import FRESHNESS
        srcs, seen = [], set()

        def _walk_dep(d):
            if d in seen:
                return
            seen.add(d)
            if d in self.catalog.sources:
                srcs.append(d)
            elif d in self.catalog.mvs:
                for dd in self.catalog.mvs[d].dependent_sources:
                    _walk_dep(dd)

        for dep in plan.mv.dependent_sources:
            _walk_dep(dep)
        FRESHNESS.register_mv(stmt.name, srcs,
                              self.cluster.domain_of_job(stmt.name))
        self._mv_selects[stmt.name] = (
            stmt.select, getattr(stmt, "emit_on_window_close", False))
        return "CREATE_MATERIALIZED_VIEW"

    async def _alter_parallelism(self, stmt) -> str:
        """ALTER MATERIALIZED VIEW <name> SET PARALLELISM n on the
        cluster: every vnode-rescalable fragment of the job rescales
        to n actors round-robined over the worker slots with the
        vnode-sliced state handoff (scale.rs:717 across processes),
        and filelog SOURCE fragments rescale by split reassignment
        (partitions rebalance over the new actors; offsets resume
        exactly). Both paths run the guarded-rescale protocol: a
        mid-way failure rolls the domain back to the prior topology
        (visible in rw_recovery) instead of leaving it half-deployed,
        and a concurrent topology change gets a clear 'rescale in
        progress' error, never an interleaved redeploy."""
        name, n = stmt.name, stmt.parallelism
        job = self.cluster.jobs.get(name)
        if job is None:
            raise PlanError(f"unknown materialized view {name!r}")
        targets = [
            (fi, self.cluster._source_rescalable(f))
            for fi, f in enumerate(job.graph.fragments)
            if self.cluster._rescalable(f)
            or self.cluster._source_rescalable(f)]
        if not targets:
            raise PlanError(
                f"{name!r} has no rescalable fragment")
        async with self._barrier_lock:
            # one stop-the-world cycle per fragment; jobs today carry
            # at most a couple of rescalable fragments — batch into a
            # single stop/handoff/redeploy if that changes
            for fi, is_source in targets:
                to_slots = [(fi + k) % self.cluster.n for k in range(n)]
                if is_source:
                    await self.cluster.rescale_source_fragment(
                        name, fi, to_slots)
                else:
                    await self.cluster.rescale_fragment(name, fi,
                                                        to_slots)
        if name in self.catalog.sinks:
            # sink jobs rescale through the same guarded path (the
            # sink node is stateless; redeploy re-stamps writer=rank
            # and n_writers on every actor) — keep the coordinator's
            # writer count and the catalog in step for telemetry
            self.catalog.sinks[name].n_writers = n
            sk = self.cluster.sinks.sink(name)
            if sk is not None:
                sk.n_writers = n
        return "ALTER_MATERIALIZED_VIEW"

    async def _drop_mv(self, stmt: ast.DropMaterializedView) -> str:
        if stmt.name not in self.catalog.mvs:
            if stmt.if_exists:
                return "DROP_MATERIALIZED_VIEW"
            raise PlanError(f"unknown materialized view {stmt.name!r}")
        dependents = [m.name for m in self.catalog.mvs.values()
                      if stmt.name in m.dependent_sources]
        if dependents:
            raise PlanError(f"cannot drop MV {stmt.name!r}: depended "
                            f"on by {dependents}")
        async with self._barrier_lock:
            await self.cluster.drop_job(stmt.name)
        del self.catalog.mvs[stmt.name]
        self._mv_selects.pop(stmt.name, None)
        # central series-lifecycle purge (freshness, costs, hot keys,
        # topology): coordinator-side books — including drained worker
        # copies — die with the job so no {mv=...} series lingers
        from risingwave_tpu.stream.costs import purge_mv_series
        purge_mv_series(stmt.name)
        return "DROP_MATERIALIZED_VIEW"

    async def _create_sink(self, stmt: ast.CreateSink) -> str:
        """CREATE SINK on the cluster: plan with the ordinary
        StreamPlanner (FROM <mv> inlines by view expansion, same as
        distributed MVs), lower the sink as a colocated fragment node,
        and register the encoder on the COORDINATOR's SinkCoordinator
        with deferred=False — workers stage their own segments
        synchronously at barrier passage (plan_ir builds inline
        CoordinatedSinkExecutors), the coordinator only runs the
        commit/recovery half off the checkpoint floor."""
        from risingwave_tpu.frontend.catalog import SinkCatalog
        from risingwave_tpu.frontend.planner import validate_sink_options
        self.catalog._check_free(stmt.name)
        validate_sink_options(stmt.options)
        connector = stmt.options.get("connector", "filelog").lower()
        if connector != "epochlog":
            raise PlanError(
                "distributed sinks require connector='epochlog' (the "
                "epoch-segment exactly-once sink); legacy writer sinks "
                "are in-process only")
        planner = StreamPlanner(self.catalog, MemoryStateStore(),
                                LocalBarrierManager(), definition="",
                                mesh=None, actors={},
                                dist_parallelism=self.parallelism,
                                inline_mvs=self._mv_selects,
                                chunk_target_rows=self.chunk_target_rows,
                                coalesce_linger_chunks=self
                                .coalesce_linger_chunks,
                                state_tier_cap=self.state_tier_cap
                                or None)
        plan = planner.plan_sink(stmt.select, stmt.options, actor_id=0,
                                 rate_limit=self.rate_limit,
                                 min_chunks=self.min_chunks,
                                 sink_name=stmt.name,
                                 append_only=stmt.append_only,
                                 coordinator=None)
        from risingwave_tpu.frontend.opt import (
            apply_rewrites, parse_fusion,
        )
        rules = self.session_vars.get("stream_rewrite_rules")
        fusion = parse_fusion(self.session_vars.get("stream_fusion"))
        apply_rewrites(plan, rules, label=stmt.name, fusion=fusion,
                       dist_parallelism=self.parallelism)
        if plan.attaches:
            raise PlanError(
                "internal: distributed sink plan produced chain "
                "attaches (view not inlined?) — cannot deploy")
        graph = Fragmenter(
            self.parallelism,
            merge_coalesce_rows=self.chunk_target_rows,
            merge_coalesce_chunks=self.coalesce_linger_chunks
        ).lower(plan.consumer)
        from risingwave_tpu.frontend.opt import (
            fragment_plan_stats, rewrite_fragment_graph,
        )
        graph, _elided = rewrite_fragment_graph(graph, rules,
                                                label=stmt.name)
        self.last_plan_stats = fragment_plan_stats(graph)
        n_writers = max(
            (f.parallelism for f in graph.fragments
             if any(n.get("op") == "sink" for n in f.nodes)),
            default=1)
        # register BEFORE the activation barrier: the first checkpoint
        # after deploy may already carry sink rows, and commit_upto on
        # the coordinator must know the sink exists to manifest them.
        # floor=-1: a fresh CREATE truncates any leftover staging under
        # the same path (prior generation's uncommitted epochs) and
        # promotes nothing.
        self.cluster.sinks.register(stmt.name, plan.encoder,
                                    n_writers=n_writers,
                                    deferred=False, floor=-1)
        try:
            async with self._barrier_lock:
                await self.cluster.deploy_graph(
                    stmt.name, graph,
                    domain_keys={stmt.name, *plan.deps})
                await self.cluster.step(1)     # activation barrier
        except BaseException:
            self.cluster.sinks.unregister(stmt.name)
            raise
        self.catalog.add_sink(SinkCatalog(
            stmt.name, 0, dict(stmt.options),
            dependent_sources=plan.deps, mode=plan.mode,
            n_writers=n_writers))
        return "CREATE_SINK"

    async def _drop_sink(self, stmt: ast.DropSink) -> str:
        if stmt.name not in self.catalog.sinks:
            if stmt.if_exists:
                return "DROP_SINK"
            raise PlanError(f"unknown sink {stmt.name!r}")
        async with self._barrier_lock:
            await self.cluster.drop_job(stmt.name)
        # committed manifests + segments stay on disk (the sink's
        # output is the product); only the coordinator registration
        # dies with the job
        self.cluster.sinks.unregister(stmt.name)
        del self.catalog.sinks[stmt.name]
        return "DROP_SINK"

    async def drain_trace(self) -> int:
        """Merge every worker's recorded epoch-trace spans into the
        coordinator's flight recorder (tagged worker-k); returns the
        number of spans ingested."""
        return await self.cluster.drain_trace()

    async def drain_ledger(self) -> int:
        """Merge every worker's phase-ledger accumulators into the
        coordinator's sealed records (the distributed conservation
        story: worker host/device time folds into the epoch intervals
        the coordinator measured); returns epochs ingested."""
        return await self.cluster.drain_ledger()

    async def _select(self, sel: ast.Select) -> Rows:
        from risingwave_tpu.batch import collect

        referenced = self._referenced_system_tables(sel)
        if "rw_epoch_trace" in referenced:
            # the trace table serves the MERGED cluster view: pull
            # worker spans in before the batch scan reads the tracer
            await self.drain_trace()
        if referenced & {"rw_metrics_history", "rw_kernel_costs"}:
            # same discipline for the phase ledger: fold worker books
            # into the sealed records before anything reads them (the
            # conservation residuals recompute on merge)
            await self.drain_ledger()
        if referenced & {"rw_mv_freshness", "rw_metrics_history"}:
            # freshness parts live on the workers (source + materialize
            # fragments): merge them before the tracker serves rows
            await self.cluster.drain_freshness()
        if referenced & {"rw_bottlenecks", "rw_actor_utilization",
                         "rw_mv_costs", "rw_hot_keys",
                         "rw_state_topology"}:
            # the tricolor + walker + attribution surfaces live where
            # the chains live (worker processes): pull their
            # snapshots/books before the read
            await self.cluster.drain_signals()
        if "rw_mv_costs" in referenced:
            # cost rows join the ledgered device books — fold worker
            # ledgers too so the per-MV split reads against merged
            # totals
            await self.drain_ledger()
        view = ClusterStoreView(self.cluster)
        # one consistent snapshot: the barrier lock keeps the
        # heartbeat from committing an epoch between per-table scans
        async with self._barrier_lock:
            await asyncio.gather(
                *(view.prefetch(tid)
                  for tid in self._referenced_table_ids(sel)))
        loop = getattr(self.cluster, "loop", None)
        ex = plan_batch(sel, self.catalog, view,
                        view.committed_epoch(),
                        profiler=getattr(loop, "profiler", None))
        self.last_select_schema = ex.schema
        return collect(ex)

    @staticmethod
    def _referenced_system_tables(sel: ast.Select) -> set:
        """Lower-cased table names a SELECT touches (FROM + JOINs +
        subqueries) — the drain-before-read triggers."""
        names = set()

        def from_item(item):
            if item is None:
                return
            if isinstance(item, ast.Subquery):
                walk(item.select)
                return
            name = getattr(item, "name", None) or getattr(
                getattr(item, "table", None), "name", None)
            if name is not None:
                names.add(str(name).lower())

        def walk(s):
            from_item(s.from_item)
            for jn in getattr(s, "joins", []):
                from_item(jn.item)

        walk(sel)
        return names

    def _referenced_table_ids(self, sel: ast.Select) -> List[int]:
        """MV table ids a SELECT touches (FROM + JOINs + subqueries)."""
        out: List[int] = []

        def from_item(item):
            if item is None:
                return
            if isinstance(item, ast.Subquery):
                walk(item.select)
                return
            name = getattr(item, "name", None) or getattr(
                getattr(item, "table", None), "name", None)
            if name is None:
                return
            obj = self.catalog.mvs.get(name)
            if isinstance(obj, MvCatalog):
                out.append(obj.table_id)

        def walk(s):
            from_item(s.from_item)
            for jn in getattr(s, "joins", []):
                from_item(jn.item)

        walk(sel)
        return out
