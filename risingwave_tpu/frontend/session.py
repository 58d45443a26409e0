"""Frontend session: the run-one-query loop + streaming-job deployment.

Reference parity: src/utils/pgwire/src/pg_server.rs:53
(`Session::run_one_query`), src/frontend/src/handler/ (per-statement
handlers) and the meta-side DdlController + GlobalStreamManager
(create job → build actors → activate via barrier) — collapsed into
one in-process object for the single-node deployment shape. The
barrier loop is the session's heartbeat; FLUSH forces a checkpoint
(handler/flush.rs analog).
"""

from __future__ import annotations

import asyncio
from typing import Dict, List, Optional, Union

from risingwave_tpu.frontend import ast
from risingwave_tpu.frontend.catalog import Catalog, MvCatalog
from risingwave_tpu.frontend.planner import (
    PlanError, StreamPlanner, plan_batch, source_schema,
)
from risingwave_tpu.meta.barrier import BarrierLoop, HeartbeatTick
from risingwave_tpu.state.store import MemoryStateStore, StateStore
from risingwave_tpu.stream.actor import Actor, LocalBarrierManager
from risingwave_tpu.stream.message import (
    PauseMutation, ResumeMutation, StopMutation,
)

Rows = List[tuple]

# the recorders' on/off options of earlier builds. The first rode the
# DDL log, so an old data dir may hold a SET of it: replay skips the
# statement; live, the names are unknown like any other.
_RETIRED_VARS = ("stream_trace", "stream_ledger", "stream_tricolor",
                 "stream_costs")


class Frontend:
    """One session over one in-process cluster.

    If the state store is object-store-backed (HummockLite), the DDL
    log persists at meta/ddl.json — the MetaStore analog. A fresh
    Frontend over the same objects replays it on boot: the catalog
    rebuilds, every MV's pipeline redeploys, and state/offsets resume
    from the committed epoch (recovery.rs semantics, collapsed to DDL
    replay + StateTable recovery)."""

    def __init__(self, store: Optional[StateStore] = None,
                 rate_limit: Optional[int] = 8,
                 min_chunks: Optional[int] = None,
                 parallelism: int = 1,
                 join_state_cap: Optional[int] = None,
                 epoch_pipeline: bool = True):
        self.store = store if store is not None else MemoryStateStore()
        # parallelism > 1: GROUP BY plans run on the vnode-sharded SPMD
        # kernel over a device mesh (the fragmenter's hash-exchange
        # parallelism, §2.12, as one all_to_all program)
        self.mesh = self._mesh_for(parallelism)
        self.catalog = Catalog()
        self.local = LocalBarrierManager()
        # pipelined epochs (ISSUE 13): with epoch_pipeline on (the
        # default, SET stream_epoch_pipeline = off to opt out), a
        # BarrierPlane partitions deployed jobs into alignment domains
        # — each domain's barriers flow independently, checkpoints stay
        # cross-domain aligned on their own cadence. Off reproduces the
        # single global BarrierLoop bit-identically (the oracle arm).
        self._epoch_pipeline = bool(epoch_pipeline)
        self._plane = None
        self._legacy_loop = None
        # exactly-once sinks (ISSUE 20): ONE coordinator per frontend
        # (= per barrier engine) — its commit authority is THIS
        # engine's checkpoint floor, so two frontends in one process
        # (oracle arm beside arm under test) never cross-commit
        from risingwave_tpu.meta.sink_coordinator import SinkCoordinator
        self.sinks = SinkCoordinator()
        self._rebuild_barrier_engine()
        self.actors: Dict[int, Actor] = {}
        self.tasks: Dict[int, asyncio.Task] = {}
        self.readers: Dict[str, Dict[int, object]] = {}   # mv → readers
        self.rate_limit = rate_limit
        self.min_chunks = min_chunks
        # resident join-state cap (cold-tier eviction; None = unbounded)
        self.join_state_cap = join_state_cap
        # unified state-tiering cap (state/tier.py): resident-KEY cap
        # per stateful executor cache — agg groups, join sides, TopN
        # group caches. None/0 = unbounded. Recorded per MV at CREATE
        # (the cap shapes join state-table pks) and replayed at
        # reschedule, like _mv_rules.
        self.state_tier_cap: Optional[int] = None
        self._mv_tier_caps: Dict[str, Optional[int]] = {}
        # adaptive chunk coalescing in front of keyed executors
        # (stream/coalesce.py): target cardinality per device dispatch
        # (0 disables) and the linger bound in buffered chunks
        from risingwave_tpu.stream.coalesce import (
            DEFAULT_MAX_CHUNKS, DEFAULT_TARGET_ROWS,
        )
        self.chunk_target_rows = DEFAULT_TARGET_ROWS
        self.coalesce_linger_chunks = DEFAULT_MAX_CHUNKS
        # session configuration (src/common/src/session_config/
        # analog): typed knobs bind to REAL planner inputs, the rest
        # are pg-compatibility strings (shared impl: session_vars.py)
        from risingwave_tpu.frontend.opt import parse_fusion, parse_rules
        from risingwave_tpu.frontend.session_vars import SessionVars
        self.session_vars = SessionVars(
            self, {"streaming_rate_limit": "rate_limit",
                   "streaming_min_chunks": "min_chunks",
                   "join_state_cap": "join_state_cap",
                   "state_tier_cap": "state_tier_cap",
                   "state_tier_soft_limit_mb":
                       "state_tier_soft_limit_mb",
                   "stream_chunk_target_rows": "chunk_target_rows",
                   # decoupled checkpoint cadence (ISSUE 13): durable
                   # checkpoints every k-th round; plain barriers
                   # advance per-domain in between
                   "stream_checkpoint_frequency":
                       "checkpoint_frequency",
                   "stream_coalesce_linger_chunks":
                       "coalesce_linger_chunks"},
            {"application_name": "", "timezone": "UTC",
             # plan-rewrite toggles (frontend/opt): 'all' | 'none' |
             # comma-list of rule names, validated at SET time
             "stream_rewrite_rules": "all",
             # fragment fusion (opt/fusion.py): compile each
             # fragment's filter/project run into the keyed kernel's
             # jitted step (one dispatch, donated state); 'off'
             # restores the interpretive chain
             "stream_fusion": "on",
             # barrier domains (meta/domains.py): 'off' restores one
             # global BarrierLoop — today's lockstep, bit-identical
             # (the oracle arm). Only changeable with no live jobs.
             "stream_epoch_pipeline":
                 "on" if self._epoch_pipeline else "off",
             # compaction arm (ISSUE 19): 'inline' compacts on the
             # commit path (oracle arm); 'dedicated' moves every merge
             # off-path through the CompactionManager + a background
             # compactor — zero compact() frames on the barrier path
             "storage_compaction": "inline"},
            validators={"stream_rewrite_rules": parse_rules,
                        "stream_fusion": parse_fusion,
                        "storage_compaction":
                            self._validate_compaction,
                        "stream_epoch_pipeline":
                            self._validate_epoch_pipeline})
        # rules spec each MV was created under: reschedule replans +
        # re-rewrites with the SAME spec so state-table schemas from
        # the original rewrite reproduce exactly (id-base contract)
        self._mv_rules: Dict[str, str] = {}
        # fusion setting each MV was created under — reschedule
        # re-fuses (or not) exactly as the CREATE did
        self._mv_fusion: Dict[str, bool] = {}
        self._next_actor = 1000
        self.chain_edges: Dict[str, list] = {}   # job → [(uid, Output)]
        # name → CREATE MV select AST (reschedule replans from this —
        # the DDL log may hold stale same-name CREATEs after drops)
        self._mv_selects: Dict[str, object] = {}
        # catalog-change broadcast (meta notification service analog):
        # observers get a snapshot then versioned deltas
        from risingwave_tpu.meta.notification import NotificationService
        self.notifications = NotificationService(
            snapshot_fn=self._catalog_snapshot)
        self._ddl_log: List[str] = []
        self._replaying = False
        # table name → (DmlReader, schema, pk, RowIdSeq|None, tid):
        # the DML write path into each CREATE TABLE job
        self._tables: Dict[str, tuple] = {}
        # serializes barrier rounds between DDL handlers, step() and the
        # background heartbeat (inject_and_collect is not reentrant)
        self._barrier_lock = asyncio.Lock()
        # dedicated-compaction arm (SET storage_compaction): the
        # manager ticks after each barrier round; merges run on the
        # InProcessCompactor's background thread
        self._compaction_mgr = None
        self._compactor = None

    # -- barrier engine (ISSUE 13) ---------------------------------------
    def _rebuild_barrier_engine(self) -> None:
        """Swap between the domain plane and the legacy global loop
        (only legal with no live jobs — the SET validator enforces)."""
        freq = self.checkpoint_frequency if (
            self._plane is not None or self._legacy_loop is not None) \
            else 1
        if self._epoch_pipeline:
            from risingwave_tpu.meta.domains import BarrierPlane
            self._plane = BarrierPlane(self.local, self.store,
                                       checkpoint_frequency=freq)
            self._legacy_loop = None
        else:
            self._legacy_loop = BarrierLoop(self.local, self.store,
                                            checkpoint_frequency=freq)
            self._plane = None
        # sink staging/commit ride the engine's checkpoint pipeline:
        # stage before the floor's durable commit, manifest after it
        self.loop.uploader.sinks = self.sinks

    @property
    def loop(self):
        """The barrier engine: a BarrierPlane (domains) or a single
        BarrierLoop (off arm) — same driving surface either way."""
        return self._plane if self._plane is not None \
            else self._legacy_loop

    @property
    def checkpoint_frequency(self) -> int:
        """SET stream_checkpoint_frequency: durable checkpoints land
        every k-th barrier round (aligned across domains); plain
        rounds advance per-domain. 1 = every round (the historical
        default)."""
        eng = self.loop
        return eng.checkpoint_frequency if eng is not None else 1

    @checkpoint_frequency.setter
    def checkpoint_frequency(self, v) -> None:
        eng = self.loop
        if eng is not None:
            eng.checkpoint_frequency = max(1, int(v))

    def _validate_epoch_pipeline(self, spec: str) -> bool:
        from risingwave_tpu.meta.domains import parse_epoch_pipeline
        want = parse_epoch_pipeline(spec)
        if want != self._epoch_pipeline and self.actors:
            raise PlanError(
                "stream_epoch_pipeline cannot change with live jobs — "
                "drop them first")
        return want

    # -- dedicated compaction (ISSUE 19) ---------------------------------
    def _validate_compaction(self, spec: str) -> str:
        from risingwave_tpu.meta.compaction import parse_compaction
        mode = parse_compaction(spec)
        if mode == "dedicated" and not hasattr(self.store,
                                               "level_snapshot"):
            raise PlanError(
                "storage_compaction='dedicated' requires an object-"
                "store-backed state store (HummockLite)")
        return mode

    async def _set_compaction_mode(self, mode: str) -> None:
        """Flip the arm at runtime. Dedicated wires the store into a
        CompactionManager over an InProcessCompactor (ONE background
        merge thread); inline tears both down — the L0 backlog then
        drains at the next commit trigger."""
        if not hasattr(self.store, "compaction_mode"):
            return                       # memory store: inline only
        if mode == self.store.compaction_mode:
            return
        self.store.compaction_mode = mode
        if mode == "dedicated":
            from risingwave_tpu.meta.compaction import (
                CompactionManager, CompactorHooks,
            )
            from risingwave_tpu.storage.compactor import (
                InProcessCompactor,
            )
            self._compactor = InProcessCompactor(self.store.obj)
            self._compaction_mgr = CompactionManager()
            self._compaction_mgr.add_namespace("local", CompactorHooks(
                snapshot=self.store.level_snapshot,
                reserve=self.store.reserve_task,
                apply=self.store.apply_version_delta,
                abort=self.store.abort_task,
                execute=self._compactor.submit))
        else:
            mgr, self._compaction_mgr = self._compaction_mgr, None
            comp, self._compactor = self._compactor, None
            if mgr is not None:
                await mgr.drain()    # land a finished merge, don't leak it
            if comp is not None:
                comp.close()

    # -- state-tier pressure knob (SET state_tier_soft_limit_mb) ---------
    @property
    def state_tier_soft_limit_mb(self) -> int:
        """Pressure watermark for the state tier: the MemoryContext
        soft limit (utils/memory.py) in MB; 0 = unlimited. Process-
        global — the checkpoint tick sweeps ONE context per process."""
        from risingwave_tpu.utils import memory as _mem
        sl = _mem.GLOBAL.soft_limit
        return 0 if sl is None else int(sl) >> 20

    @state_tier_soft_limit_mb.setter
    def state_tier_soft_limit_mb(self, v) -> None:
        from risingwave_tpu.utils import memory as _mem
        _mem.GLOBAL.soft_limit = None if not v else int(v) << 20

    # -- DDL-log durability (MetaStore analog) ---------------------------
    @property
    def _meta_obj(self):
        return getattr(self.store, "obj", None)

    def _persist_ddl(self) -> None:
        if self._meta_obj is not None and not self._replaying:
            import json
            self._meta_obj.upload(
                "meta/ddl.json", json.dumps(self._ddl_log).encode())

    async def recover(self) -> int:
        """Replay the persisted DDL log (boot path). Returns #stmts."""
        if self._meta_obj is None or not self._meta_obj.exists(
                "meta/ddl.json"):
            return 0
        import json
        log = json.loads(self._meta_obj.read("meta/ddl.json").decode())
        # restore the durable history FIRST — the next DDL statement
        # re-persists the whole log, so losing it here would truncate
        # the catalog on the following recovery
        self._ddl_log = list(log)
        # the previous generation is dead (single-writer recovery):
        # clear its crash residue — uploaded-but-uncommitted SSTs no
        # version references would otherwise accumulate forever across
        # kill/recover generations
        if hasattr(self.store, "vacuum_orphans"):
            self.store.vacuum_orphans()
        self._replaying = True
        try:
            for sql in log:
                await self.execute(sql)
        finally:
            self._replaying = False
        if self.actors:
            await self._barrier(mutation=ResumeMutation())
        return len(log)

    # -- public API -------------------------------------------------------
    async def execute(self, sql: str) -> Union[Rows, str]:
        """Run one or more ';'-separated statements; returns the last
        statement's result (rows for SELECT/SHOW, status otherwise)."""
        from risingwave_tpu.frontend.parser import parse_many

        result: Union[Rows, str] = "OK"
        for text, stmt in parse_many(sql):
            result = await self._run(stmt)
            if isinstance(stmt, ast.SetVar) and \
                    stmt.name in ("stream_rewrite_rules",
                                  "stream_fusion",
                                  "state_tier_cap",
                                  "state_tier_soft_limit_mb") and \
                    not self._replaying:
                # these SETs shape what CREATE produces — the rewrite
                # spec shapes STATE-TABLE schemas (pruned joins persist
                # narrowed rows) and the tier cap shapes join
                # state-table pks (key-prefixed for prefix-scan
                # reload); recovery must replay CREATEs under the same
                # values, so the SET itself rides the DDL log
                self._ddl_log.append(text)
                self._persist_ddl()
            if isinstance(stmt, (ast.CreateSource,
                                 ast.CreateMaterializedView,
                                 ast.CreateSink, ast.DropSink,
                                 ast.DropMaterializedView,
                                 ast.DropSource, ast.CreateTable,
                                 ast.DropTable,
                                 ast.AlterParallelism)) and \
                    not self._replaying:
                # replayed DDL publishes nothing: observers' snapshots
                # already contain the replayed catalog
                from risingwave_tpu.meta.notification import (
                    Notification,
                )
                self._ddl_log.append(text)
                self._persist_ddl()
                self.notifications.publish(Notification(
                    type(stmt).__name__, {
                        "name": getattr(stmt, "name", None),
                        "version_hint": len(self._ddl_log)}))
        return result

    def execute_sync(self, sql: str) -> Union[Rows, str]:
        return asyncio.get_event_loop().run_until_complete(
            self.execute(sql))

    async def _barrier(self, **kw):
        """One serialized barrier round — the ONLY way any session code
        may call inject_and_collect (the lock also guards actor-topology
        mutations; see _create_mv/_drop_mv)."""
        async with self._barrier_lock:
            r = await self.loop.inject_and_collect(**kw)
        if self._compaction_mgr is not None:
            # dedicated arm: the manager settles finished merges
            # (cheap manifest swaps) and dispatches new ones to the
            # background thread — no compact() frame ever runs here
            await self._compaction_mgr.tick()
        return r

    async def step(self, n: int = 1) -> None:
        """Drive n checkpoint barriers (deterministic test mode)."""
        for _ in range(n):
            await self._barrier(force_checkpoint=True)

    async def run_heartbeat(self, interval_s: float = 0.25) -> None:
        """Background barrier heartbeat for server deployments
        (GlobalBarrierManager::run analog; serialized with DDL): one
        barrier round every ``interval_s``, 0.25 s from inject to
        inject, and the sealed checkpoint commits before the next
        inject. Where a round and its tail take longer than the tick
        the next round follows at once; no two injects are closer than
        ``interval_s`` and the first one is a whole interval after the
        start (meta/barrier.py ``HeartbeatTick``, which also files the
        waits in ``rw_metrics_history``). A failure is loud: it
        propagates out of this task — the server entry point watches
        it and dies rather than serving a cluster whose checkpoints
        silently stopped. A cancelled heartbeat ends cancelled, as the
        cluster's does: whoever owns the task can tell the pause it
        asked for (``task.cancelled()``) from a heartbeat that stopped
        by itself. A cancel issued under ``_barrier_lock`` finds the
        heartbeat waiting or queued on the lock, never inside a round,
        and leaves the uploader's tasks alone."""
        import sys
        import traceback
        try:
            tick = HeartbeatTick(interval_s)
            while True:
                await tick.wait()
                # the sealed checkpoint's tail goes before the next
                # inject: an inject ahead of it would put the next
                # barrier's flush slices on the loop before this
                # commit's continuation (inject → durable grows by
                # them). A PUT shorter than the tick hides in it and
                # this returns at once; a longer one is back-pressure
                # of one checkpoint behind a saturated heartbeat. A
                # failed upload surfaces here or on the next collect.
                await tick.tail(self.loop.uploader)
                # one real suspension outside the lock, always: a DDL,
                # a FLUSH or a pause that became ready during the
                # round gets to queue on the (FIFO) lock ahead of a
                # saturated heartbeat
                await asyncio.sleep(0)
                barrier = await self._barrier(on_inject=tick.injected,
                                              drain_uploader=False)
                tick.file(barrier.epoch.curr.value)
        except asyncio.CancelledError:
            raise
        except BaseException:
            print("barrier heartbeat failed:", file=sys.stderr)
            traceback.print_exc()
            raise

    async def close(self) -> None:
        if self._compactor is not None:
            mgr, self._compaction_mgr = self._compaction_mgr, None
            comp, self._compactor = self._compactor, None
            if mgr is not None:
                await mgr.drain()
            comp.close()
        if self.actors:
            async with self._barrier_lock:
                stop_ids = set(self.actors)
                for readers in self.readers.values():
                    stop_ids |= set(readers)
                await self.loop.inject_and_collect(
                    mutation=StopMutation(frozenset(stop_ids)))
                for t in self.tasks.values():
                    await t
        for aid, a in self.actors.items():
            if a.failure is not None:
                raise a.failure

    # -- dispatch ---------------------------------------------------------
    async def _run(self, stmt) -> Union[Rows, str]:
        self.last_select_schema = None
        if isinstance(stmt, ast.CreateSource):
            schema = source_schema(stmt.options, stmt.columns)
            self.catalog.add_source(stmt.name, schema, stmt.options,
                                    watermark=stmt.watermark)
            return "CREATE_SOURCE"
        if isinstance(stmt, ast.CreateMaterializedView):
            return await self._create_mv(stmt)
        if isinstance(stmt, ast.AlterParallelism):
            return await self._alter_parallelism(stmt)
        if isinstance(stmt, ast.Explain):
            return self._explain(stmt.select)
        if isinstance(stmt, ast.CreateSink):
            return await self._create_sink(stmt)
        if isinstance(stmt, ast.DropSink):
            return await self._drop_job(
                stmt.name, self.catalog.sinks, stmt.if_exists,
                "DROP_SINK")
        if isinstance(stmt, ast.DropMaterializedView):
            return await self._drop_mv(stmt)
        if isinstance(stmt, ast.DropSource):
            if stmt.name not in self.catalog.sources:
                if stmt.if_exists:
                    return "DROP_SOURCE"
                raise PlanError(f"unknown source {stmt.name!r}")
            dependents = (list(self.catalog.mvs.values())
                          + list(self.catalog.sinks.values()))
            for job in dependents:
                if stmt.name in job.dependent_sources:
                    raise PlanError(
                        f"source {stmt.name!r} is used by {job.name!r}")
            del self.catalog.sources[stmt.name]
            return "DROP_SOURCE"
        if isinstance(stmt, ast.CreateTable):
            return await self._create_table(stmt)
        if isinstance(stmt, ast.DropTable):
            return await self._drop_table(stmt)
        if isinstance(stmt, ast.Insert):
            return await self._insert(stmt)
        if isinstance(stmt, ast.Delete):
            return await self._delete(stmt)
        if isinstance(stmt, ast.Update):
            return await self._update(stmt)
        if isinstance(stmt, ast.SetVar):
            if self._replaying and stmt.name in _RETIRED_VARS:
                return "SET"
            self.session_vars.set(stmt.name, stmt.value)
            if stmt.name == "storage_compaction":
                # runtime arm flip (validated above): wires/tears the
                # dedicated compactor — never rides the DDL log
                await self._set_compaction_mode(
                    self.session_vars.get("storage_compaction"))
            if stmt.name == "stream_epoch_pipeline":
                from risingwave_tpu.meta.domains import (
                    parse_epoch_pipeline,
                )
                want = parse_epoch_pipeline(
                    self.session_vars.get("stream_epoch_pipeline"))
                if want != self._epoch_pipeline:
                    # validator already refused with live jobs
                    self._epoch_pipeline = want
                    self._rebuild_barrier_engine()
            return "SET"
        if isinstance(stmt, ast.Show):
            if stmt.what == "var:all":
                return self.session_vars.show_all()
            if stmt.what.startswith("var:"):
                name = stmt.what[4:].lower()
                if not self.session_vars.known(name):
                    raise PlanError(
                        f"unrecognized configuration parameter "
                        f"{name!r}")
                return [(self.session_vars.get(name),)]
            if stmt.what == "sources":
                return [(n,) for n in sorted(self.catalog.sources)]
            if stmt.what == "sinks":
                return [(n,) for n in sorted(self.catalog.sinks)]
            if stmt.what == "tables":
                return [(n,) for n in sorted(self._tables)]
            return [(n,) for n in sorted(self.catalog.mvs)
                    if n not in self._tables]
        if isinstance(stmt, ast.Flush):
            await self._barrier(force_checkpoint=True)
            return "FLUSH"
        if isinstance(stmt, ast.Select):
            return await self._select(stmt)
        raise PlanError(f"unhandled statement {stmt!r}")

    # -- handlers ---------------------------------------------------------
    def _freshness_sources(self, deps) -> list:
        """Resolve a job's dependency anchors to the SOURCE names whose
        ingest frontiers bound its freshness (MV-on-MV deps resolve
        transitively — chained materializations preserve the barrier
        cut, so the original source frontier is still the honest
        visible-data bound)."""
        out, seen = [], set()

        def walk(d):
            if d in seen:
                return
            seen.add(d)
            if d in self.catalog.sources or d in self._tables:
                out.append(d)
            elif d in self.catalog.mvs:
                for dd in self.catalog.mvs[d].dependent_sources:
                    walk(dd)

        for d in deps:
            walk(d)
        return out

    async def _deploy_job(self, name: str, actor_id: int, consumer,
                          readers, register, attaches=(),
                          deps=(), freshness_sources=None) -> None:
        """Shared deployment tail for MVs and sinks — runs UNDER the
        barrier lock the caller holds: topology mutations (sender
        registration in plan(), expected-actor set, spawn) racing a
        heartbeat epoch would leave it collecting against actors that
        never received it. ``deps`` (source/MV names the job reads)
        are the job's barrier-domain reachability anchors: jobs that
        share a dep — a source fan-out, an MV-on-MV chain, a temporal
        dim read — align in one domain; disjoint jobs get their own."""
        register()                    # catalog entry (duplicate check)
        # every deployed chain is instrumented node-by-node: row/chunk
        # throughput and exclusive processing time per (fragment,
        # actor, executor), feeding rw_actor_metrics + the profiler
        from risingwave_tpu.stream.monitor import install_monitoring
        consumer = install_monitoring(consumer, fragment=name,
                                      actor_id=actor_id)
        # every MV actor carries an (initially empty) broadcast
        # dispatcher so later MV-on-MV chains can attach outputs at a
        # barrier boundary (Mutation::Add analog)
        from risingwave_tpu.stream.dispatch import BroadcastDispatcher
        actor = Actor(actor_id, consumer,
                      dispatchers=[BroadcastDispatcher([])],
                      barrier_manager=self.local, fragment=name)
        self.actors[actor_id] = actor
        self.readers[name] = readers
        self.local.set_expected_actors(list(self.actors))
        self.tasks[actor_id] = actor.spawn()
        if self._plane is not None:
            # domain derivation BEFORE the activation barrier: the new
            # job's first barrier must already flow through its domain
            self._plane.assign_job(name, set(deps),
                                   sender_ids=set(readers),
                                   expected_ids={actor_id})
        # freshness lineage (stream/freshness.py): which source
        # frontiers bound this job's visible data, keyed by the domain
        # its barriers flow through
        from risingwave_tpu.stream.freshness import FRESHNESS
        domain = ""
        if self._plane is not None:
            domain = self._plane.domain_of_job(name) or ""
        FRESHNESS.register_mv(
            name,
            self._freshness_sources(deps)
            if freshness_sources is None else list(freshness_sources),
            domain)
        if self._plane is not None:
            # a new job can MERGE domains (shared reachability): keep
            # every registered job's freshness domain key current
            for dom in self._plane.domains():
                for job in self._plane.jobs_of_domain(dom):
                    FRESHNESS.set_domain(job, dom)
        # attach MV-on-MV chain edges now that the plan validated and
        # the downstream actor exists — the activation barrier below
        # must flow through these channels
        self.chain_edges[name] = list(attaches)
        for uid, out in attaches:
            d = self.actors[uid].dispatchers[0]
            d.update_outputs(d.outputs() + [out])
        # activation barrier (Command::CreateStreamingJob analog).
        # During DDL replay, sources stay PAUSED so no upstream data
        # flows before every downstream chain has re-attached — a
        # revived MV-on-MV chain with completed backfill would miss
        # deltas emitted in earlier replayed jobs' activation epochs
        # (recovery.rs: rebuild paused, resume at the end).
        mutation = PauseMutation() if self._replaying else None
        await self.loop.inject_and_collect(force_checkpoint=True,
                                           mutation=mutation)
        self._deployed_actor = actor

    def _explain(self, sel: ast.Select) -> Rows:
        """EXPLAIN <select>: the streaming plan as indented text —
        BOTH the planner's tree and the rewritten tree, with per-rule
        annotations and carried-lane stats in between. Plans against a
        throwaway barrier manager so no senders or channels leak from
        a statement that deploys nothing."""
        from risingwave_tpu.frontend.planner import explain_tree
        planner = StreamPlanner(self.catalog, self.store,
                                LocalBarrierManager(), definition="",
                                mesh=self.mesh, actors=self.actors,
                                chunk_target_rows=self.chunk_target_rows,
                                coalesce_linger_chunks=self
                                .coalesce_linger_chunks)
        plan = planner.plan("__explain__", sel, actor_id=0,
                            rate_limit=self.rate_limit,
                            min_chunks=self.min_chunks)
        from risingwave_tpu.frontend.opt import (
            explain_with_rewrite, parse_fusion,
        )
        rules = self.session_vars.get("stream_rewrite_rules")
        return explain_with_rewrite(
            plan.consumer, rules,
            fusion=parse_fusion(self.session_vars.get("stream_fusion")))

    def _catalog_snapshot(self) -> list:
        """Current catalog as notification payloads (observers get
        this before any live delta — snapshot-then-delta contract)."""
        out = []
        for s in self.catalog.sources.values():
            out.append({"kind": "source", "name": s.name})
        for m in self.catalog.mvs.values():
            out.append({"kind": "mv", "name": m.name,
                        "table_id": m.table_id})
        for sk in self.catalog.sinks.values():
            out.append({"kind": "sink", "name": sk.name})
        return out

    @staticmethod
    def _mesh_for(parallelism: int):
        """n-device mesh for a parallel plan (None = single-chip)."""
        if parallelism <= 1:
            return None
        import jax
        from jax.sharding import Mesh

        import numpy as _np
        devs = jax.devices()
        if len(devs) < parallelism:
            raise ValueError(
                f"parallelism {parallelism} > {len(devs)} devices")
        return Mesh(_np.asarray(devs[:parallelism]), ("d",))

    async def _create_mv(self, stmt: ast.CreateMaterializedView) -> str:
        self.catalog._check_free(stmt.name)    # validate BEFORE planning
        async with self._barrier_lock:
            planner = StreamPlanner(self.catalog, self.store, self.local,
                                    definition="", mesh=self.mesh,
                                    actors=self.actors,
                                    join_state_cap=self.join_state_cap,
                                    state_tier_cap=self.state_tier_cap
                                    or None,
                                    chunk_target_rows=self
                                    .chunk_target_rows,
                                    coalesce_linger_chunks=self
                                    .coalesce_linger_chunks)
            actor_id = self._next_actor
            self._next_actor += 1
            id_base = self.catalog._next_id
            rules = self.session_vars.get("stream_rewrite_rules")
            from risingwave_tpu.frontend.opt import parse_fusion
            fusion = parse_fusion(self.session_vars.get("stream_fusion"))
            try:
                plan = planner.plan(
                    stmt.name, stmt.select, actor_id,
                    rate_limit=self.rate_limit,
                    min_chunks=self.min_chunks,
                    emit_on_window_close=getattr(
                        stmt, "emit_on_window_close", False))
                # plan-rewrite pass (frontend/opt): runs between the
                # planner and deployment; the checker falls back to
                # the unrewritten plan on any invariant violation
                from risingwave_tpu.frontend.opt import apply_rewrites
                apply_rewrites(plan, rules, label=stmt.name,
                               fusion=fusion)
            except BaseException:
                # a failed plan must leak nothing: source senders were
                # registered during planning and would wedge the next
                # barrier round (messages pile into unconsumed channels)
                for sid in planner.registered_senders:
                    self.local.drop_actor(sid)
                raise
            plan.mv.id_base = id_base
            await self._deploy_job(
                stmt.name, actor_id, plan.consumer, plan.readers,
                lambda: self.catalog.add_mv(plan.mv),
                attaches=plan.attaches,
                deps=plan.mv.dependent_sources)
        self._mv_selects[stmt.name] = (
            stmt.select, getattr(stmt, "emit_on_window_close", False))
        self._mv_rules[stmt.name] = rules
        self._mv_fusion[stmt.name] = fusion
        # CREATE-time tier cap: reschedule replans under it (the cap
        # shapes join state-table pk layouts — id-base contract)
        self._mv_tier_caps[stmt.name] = self.state_tier_cap or None
        if self._deployed_actor.failure is not None:
            # a failed CREATE deployed far enough to register {mv=...}
            # series — purge them before surfacing the failure, or the
            # dead job haunts the exposition (series-lifecycle rule)
            from risingwave_tpu.stream.costs import purge_mv_series
            purge_mv_series(stmt.name)
            raise self._deployed_actor.failure
        return "CREATE_MATERIALIZED_VIEW"

    async def _create_table(self, stmt: ast.CreateTable) -> str:
        """CREATE TABLE: a DML-fed streaming job (DmlReader source →
        materialize) so table writes ride the barrier pipeline and MV
        chains over tables work like MV-on-MV (handler/create_table.rs
        + dml_manager.rs analog). No PRIMARY KEY → hidden _row_id."""
        from risingwave_tpu.common.types import DataType, Field, Schema
        from risingwave_tpu.connectors.dml import DmlReader, RowIdSeq
        from risingwave_tpu.state.state_table import StateTable
        from risingwave_tpu.stream.exchange import channel_for_test
        from risingwave_tpu.stream.executors.materialize import (
            MaterializeExecutor,
        )
        from risingwave_tpu.stream.executors.source import SourceExecutor

        self.catalog._check_free(stmt.name)
        fields = []
        for cname, tname in stmt.columns:
            if any(f.name == cname for f in fields):
                raise PlanError(f"duplicate column {cname!r}")
            try:
                fields.append(Field(cname, DataType.from_sql(tname)))
            except KeyError:
                raise PlanError(f"unknown type {tname!r}")
        names = [f.name for f in fields]
        for c in stmt.pk_cols:
            if c not in names:
                raise PlanError(f"PRIMARY KEY column {c!r} not found")
        if stmt.pk_cols:
            schema = Schema(fields)
            pk = [names.index(c) for c in stmt.pk_cols]
            rowid = None
        else:
            schema = Schema(fields + [Field("_row_id",
                                            DataType.SERIAL)])
            pk = [len(fields)]
            rowid = RowIdSeq()
        async with self._barrier_lock:
            actor_id = self._next_actor
            self._next_actor += 1
            id_base = self.catalog._next_id
            sid = self.catalog.next_id()
            table_id = self.catalog.next_id()
            reader = DmlReader(schema)
            tx, rx = channel_for_test(edge=f"dml:{stmt.name}")
            self.local.register_sender(sid, tx)
            try:
                src = SourceExecutor(reader, rx, None, actor_id=sid,
                                     freshness_key=stmt.name)
                table = StateTable(table_id, schema, pk, self.store)
                mat = MaterializeExecutor(src, table,
                                          mv_name=stmt.name)
                mv = MvCatalog(stmt.name, table_id, schema, pk,
                               definition="", actor_id=actor_id,
                               id_base=id_base,
                               n_visible=len(fields) if rowid is not None
                               else None, is_table=True)
                await self._deploy_job(stmt.name, actor_id, mat,
                                       {sid: reader},
                                       lambda: self.catalog.add_mv(mv),
                                       freshness_sources=[stmt.name])
            except BaseException:
                self.local.drop_actor(sid)
                raise
        self._tables[stmt.name] = (reader, schema, pk, rowid,
                                   table_id)
        if self._deployed_actor.failure is not None:
            from risingwave_tpu.stream.costs import purge_mv_series
            purge_mv_series(stmt.name)
            raise self._deployed_actor.failure
        return "CREATE_TABLE"

    async def _drop_table(self, stmt: ast.DropTable) -> str:
        if stmt.name not in self._tables:
            if stmt.if_exists and stmt.name not in self.catalog.mvs:
                return "DROP_TABLE"
            if stmt.name not in self.catalog.mvs:
                raise PlanError(f"unknown table {stmt.name!r}")
            raise PlanError(f"{stmt.name!r} is not a table")
        dependents = [m.name for m in self.catalog.mvs.values()
                      if stmt.name in m.dependent_sources] + \
                     [s.name for s in self.catalog.sinks.values()
                      if stmt.name in s.dependent_sources]
        if dependents:
            raise PlanError(f"cannot drop table {stmt.name!r}: "
                            f"depended on by {dependents}")
        status = await self._drop_job(stmt.name, self.catalog.mvs,
                                      stmt.if_exists, "DROP_TABLE")
        self._tables.pop(stmt.name, None)
        return status

    def _table_job(self, name: str):
        job = self._tables.get(name)
        if job is None:
            raise PlanError(f"{name!r} is not a table")
        return job

    async def _insert(self, stmt: ast.Insert) -> str:
        """INSERT ... VALUES: evaluate rows, push one chunk through
        the table's DML channel, and return only after the checkpoint
        that makes it durable+visible commits (batch insert.rs)."""
        from risingwave_tpu.common.chunk import DataChunk, StreamChunk
        from risingwave_tpu.common.types import Schema
        from risingwave_tpu.expr.expr import Cast
        from risingwave_tpu.frontend.binder import Binder, Scope

        reader, schema, _pk, rowid, _tid = self._table_job(stmt.table)
        data_fields = list(schema)[:-1] if rowid is not None \
            else list(schema)
        if stmt.select is not None:
            # INSERT INTO t SELECT …: batch-evaluate over the latest
            # committed snapshot, then coerce column-wise
            from risingwave_tpu.batch import collect
            ex = plan_batch(stmt.select, self.catalog, self.store,
                            self.store.committed_epoch(),
                            profiler=self.loop.profiler)
            if len(ex.schema) != len(data_fields):
                raise PlanError(
                    f"INSERT SELECT has {len(ex.schema)} columns, "
                    f"table has {len(data_fields)}")
            rows = self._coerce_rows(collect(ex), ex.schema,
                                     data_fields)
        else:
            from risingwave_tpu.common.types import Field
            binder = Binder(Scope.of(Schema([]), None))
            one = DataChunk.empty(Schema([]), capacity=8)
            one.visibility[0] = True
            tmp_sch = Schema([Field(f"_c{i}", f.data_type)
                              for i, f in enumerate(data_fields)])
            rows = []
            for r in stmt.rows:
                if len(r) != len(data_fields):
                    raise PlanError(
                        f"INSERT row has {len(r)} values, table has "
                        f"{len(data_fields)} columns")
                cols = []
                for e_ast, f in zip(r, data_fields):
                    b = binder.bind(e_ast)
                    if b.return_type != f.data_type:
                        b = Cast(b, f.data_type)
                    cols.append(b.eval(one))
                # to_pylist converts physical->LOGICAL (DECIMAL
                # unscales, bools); from_pydict at the push site
                # expects logical values
                rows.append(DataChunk(tmp_sch, cols,
                                      one.visibility).to_pylist()[0])
        if not rows:
            return "INSERT 0 0"
        if rowid is not None:
            ids = rowid.take(self.store.committed_epoch(), len(rows))
            rows = [r + (i,) for r, i in zip(rows, ids)]
        data = {f.name: [r[i] for r in rows]
                for i, f in enumerate(schema)}
        reader.push(StreamChunk.from_pydict(schema, data))
        await self._dml_flush()
        return f"INSERT 0 {len(rows)}"

    async def _dml_flush(self) -> None:
        """Make a just-pushed DML chunk durable AND visible before the
        statement returns. Two barrier rounds: the table's source is
        parked on its barrier channel, so the first barrier always
        precedes the chunk (it re-arms generation for the next epoch)
        and the second seals + checkpoints the epoch that carried
        it."""
        await self._barrier(force_checkpoint=True)
        await self._barrier(force_checkpoint=True)

    @staticmethod
    def _coerce_rows(rows, src_schema, dst_fields) -> List[tuple]:
        """Column-wise cast of batch-select output (LOGICAL rows)
        onto table types; returns logical rows for the DML channel.
        Positional temp names, NOT the real ones: a SELECT output may
        carry duplicate column names (aliases, join sides) and a
        name-keyed build would silently collapse them. The chunk
        round trip keeps the value domain honest — from_pydict takes
        logical values physical, to_pylist brings the cast results
        back logical (DECIMAL scale, bools)."""
        from risingwave_tpu.common.chunk import DataChunk
        from risingwave_tpu.common.types import Field, Schema
        from risingwave_tpu.expr.expr import Cast, InputRef

        if not rows:
            return []
        if all(s.data_type == d.data_type
               for s, d in zip(src_schema, dst_fields)):
            return [tuple(r) for r in rows]
        tmp_src = Schema([Field(f"_c{i}", f.data_type)
                          for i, f in enumerate(src_schema)])
        chunk = DataChunk.from_pydict(
            tmp_src, {f"_c{i}": [r[i] for r in rows]
                      for i in range(len(src_schema))})
        cols = [Cast(InputRef(i, s.data_type),
                     d.data_type).eval(chunk)
                for i, (s, d) in enumerate(zip(src_schema,
                                               dst_fields))]
        tmp_dst = Schema([Field(f"_c{i}", d.data_type)
                          for i, d in enumerate(dst_fields)])
        return DataChunk(tmp_dst, cols, chunk.visibility).to_pylist()

    def _snapshot_rows(self, table_id: int, schema, pk) -> List[tuple]:
        from risingwave_tpu.common.epoch import Epoch, EpochPair
        from risingwave_tpu.state.state_table import StateTable

        from risingwave_tpu.batch.storage_table import rows_to_chunk

        t = StateTable(table_id, schema, pk, self.store,
                       sanity_check=False)
        ce = self.store.committed_epoch()
        t.init_epoch(EpochPair(Epoch(ce + 1), Epoch(ce)))
        phys = [tuple(row) for _pk, row in t.iter_rows()]
        if not phys:
            return []
        # state rows are PHYSICAL (DECIMAL = scaled int64); everything
        # the DML channel re-ingests via from_pydict must be LOGICAL,
        # so convert through a chunk round trip
        return rows_to_chunk(schema, phys).to_pylist()

    def _match_rows(self, stmt_where, schema, rows):
        """The subset of rows a DML WHERE clause selects."""
        import numpy as np

        from risingwave_tpu.common.chunk import DataChunk
        from risingwave_tpu.frontend.binder import Binder, Scope

        if not rows:
            return []
        if stmt_where is None:
            return rows
        chunk = DataChunk.from_pydict(
            schema, {f.name: [r[i] for r in rows]
                     for i, f in enumerate(schema)})
        pred = Binder(Scope.of(schema, None)).bind(stmt_where)
        col = pred.eval(chunk)
        keep = np.asarray(col.values)[:len(rows)].astype(bool)
        if col.validity is not None:
            keep &= np.asarray(col.validity)[:len(rows)]
        return [r for r, k in zip(rows, keep) if k]

    async def _delete(self, stmt: ast.Delete) -> str:
        """DELETE: snapshot-scan the committed rows, push their
        retractions through the DML channel (batch delete.rs)."""
        from risingwave_tpu.common.chunk import Op, StreamChunk

        reader, schema, pk, _rowid, tid = self._table_job(stmt.table)
        rows = self._match_rows(
            stmt.where, schema, self._snapshot_rows(tid, schema, pk))
        if rows:
            data = {f.name: [r[i] for r in rows]
                    for i, f in enumerate(schema)}
            reader.push(StreamChunk.from_pydict(
                schema, data, ops=[Op.DELETE] * len(rows)))
            await self._dml_flush()
        return f"DELETE {len(rows)}"

    async def _update(self, stmt: ast.Update) -> str:
        """UPDATE: snapshot-scan, re-evaluate SET expressions over the
        matching rows, push UpdateDelete/UpdateInsert pairs."""
        from risingwave_tpu.common.chunk import DataChunk, Op, StreamChunk
        from risingwave_tpu.expr.expr import Cast
        from risingwave_tpu.frontend.binder import Binder, Scope

        reader, schema, pk, rowid, tid = self._table_job(stmt.table)
        names = [f.name for f in schema]
        settable = names[:-1] if rowid is not None else names
        sets = []
        binder = Binder(Scope.of(schema, None))
        for col, e_ast in stmt.sets:
            if col not in settable:
                raise PlanError(f"column {col!r} not found")
            b = binder.bind(e_ast)
            dt = schema[names.index(col)].data_type
            if b.return_type != dt:
                b = Cast(b, dt)
            sets.append((names.index(col), b))
        rows = self._match_rows(
            stmt.where, schema, self._snapshot_rows(tid, schema, pk))
        if rows:
            chunk = DataChunk.from_pydict(
                schema, {f.name: [r[i] for r in rows]
                         for i, f in enumerate(schema)})
            from risingwave_tpu.common.types import Field, Schema
            new_cols = {}
            for idx, b in sets:
                col = b.eval(chunk)
                one_sch = Schema([Field("_v",
                                        schema[idx].data_type)])
                new_cols[idx] = [r[0] for r in DataChunk(
                    one_sch, [col], chunk.visibility).to_pylist()]
            out_rows, ops = [], []
            new_pks = set()
            pk_touched = any(idx in pk for idx, _b in sets)
            for i, old in enumerate(rows):
                new = list(old)
                for idx, _b in sets:
                    new[idx] = new_cols[idx][i]
                if pk_touched:
                    kp = tuple(new[j] for j in pk)
                    if kp in new_pks:
                        # two updated rows landing on one key would
                        # collide inside a single chunk and kill the
                        # table's actor — fail the STATEMENT instead
                        raise PlanError(
                            "UPDATE would assign the primary key "
                            f"{kp!r} to more than one row")
                    new_pks.add(kp)
                out_rows += [old, tuple(new)]
                ops += [Op.UPDATE_DELETE, Op.UPDATE_INSERT]
            data = {f.name: [r[i] for r in out_rows]
                    for i, f in enumerate(schema)}
            reader.push(StreamChunk.from_pydict(schema, data,
                                                ops=ops))
            await self._dml_flush()
        return f"UPDATE {len(rows)}"

    async def _alter_parallelism(self, stmt: ast.AlterParallelism) -> str:
        """Runtime reschedule (meta/stream/scale.rs:717
        reschedule_actors analog, collapsed to the TPU design): pause
        the job at a stop barrier, replan the SAME definition over an
        n-device mesh FROM THE SAME TABLE-ID BASE (state tables keep
        their ids, so the redeployed executors recover every group/row
        through the normal recovery path), then resume. The sharded
        kernels' vnode routing makes the moved state land on its new
        owner shard automatically at rebuild."""
        name, n = stmt.name, stmt.parallelism
        mv = self.catalog.mvs.get(name)
        if mv is None:
            raise PlanError(f"unknown materialized view {name!r}")
        deps_on_me = [m.name for m in self.catalog.mvs.values()
                      if name in m.dependent_sources] + \
                     [s.name for s in self.catalog.sinks.values()
                      if name in s.dependent_sources]
        if deps_on_me or any(d in self.catalog.mvs
                             for d in mv.dependent_sources):
            raise PlanError(
                "ALTER ... SET PARALLELISM on chained MVs is not "
                "supported yet")
        if mv.id_base < 0:
            raise PlanError(f"{name!r} predates reschedule support")
        stored = self._mv_selects.get(name)
        if stored is None:
            raise PlanError(f"no CREATE statement on record for "
                            f"{name!r}")
        sel, eowc = stored
        mesh = self._mesh_for(n)
        async with self._barrier_lock:
            # 1) stop this job's actors at a barrier (keep state +
            # catalog — this is a pause, not a drop)
            old_actor = await self._stop_job(name, mv.actor_id)
            try:
                if old_actor is not None and \
                        old_actor.failure is not None:
                    raise old_actor.failure
                # 2) replan from the recorded id base → same state
                # tables (the id sequence is deterministic in the
                # definition; mesh choice allocates no ids)
                saved = self.catalog._next_id
                self.catalog._next_id = mv.id_base
                planner = StreamPlanner(
                    self.catalog, self.store, self.local,
                    definition="", mesh=mesh, actors=self.actors,
                    join_state_cap=self.join_state_cap,
                    state_tier_cap=self._mv_tier_caps.get(name),
                    chunk_target_rows=self.chunk_target_rows,
                    coalesce_linger_chunks=self
                    .coalesce_linger_chunks)
                actor_id = self._next_actor
                self._next_actor += 1
                try:
                    # same flags as the CREATE: the id-base replay
                    # contract requires the identical allocation
                    # sequence (an EOWC gate allocates a table id)
                    plan = planner.plan(name, sel, actor_id,
                                        rate_limit=self.rate_limit,
                                        min_chunks=self.min_chunks,
                                        emit_on_window_close=eowc)
                    # re-rewrite under the CREATE-time rule spec: the
                    # kept state tables carry the schemas that rewrite
                    # produced (e.g. pruned join sides)
                    from risingwave_tpu.frontend.opt import (
                        apply_rewrites,
                    )
                    apply_rewrites(plan,
                                   self._mv_rules.get(name, "all"),
                                   label=name,
                                   fusion=self._mv_fusion.get(
                                       name, False) and mesh is None)
                except BaseException:
                    for sid in planner.registered_senders:
                        self.local.drop_actor(sid)
                    self.catalog._next_id = saved
                    raise
                self.catalog._next_id = max(saved,
                                            self.catalog._next_id)
                plan.mv.id_base = mv.id_base
                del self.catalog.mvs[name]
                # 3) redeploy; executors recover from the kept tables
                await self._deploy_job(
                    name, actor_id, plan.consumer, plan.readers,
                    lambda: self.catalog.add_mv(plan.mv),
                    attaches=plan.attaches,
                    deps=plan.mv.dependent_sources)
            except BaseException as e:
                # the old pipeline is gone and cannot be restored:
                # degrade to DROPPED (state tables kept) rather than
                # leaving a catalog entry that serves frozen results
                self.catalog.mvs.pop(name, None)
                self._mv_selects.pop(name, None)
                self._mv_rules.pop(name, None)
                self._mv_fusion.pop(name, None)
                self._mv_tier_caps.pop(name, None)
                raise PlanError(
                    f"reschedule of {name!r} failed after teardown — "
                    f"the MV was dropped (state retained): {e}") from e
        if self._deployed_actor.failure is not None:
            raise self._deployed_actor.failure
        return "ALTER_MATERIALIZED_VIEW"

    async def _create_sink(self, stmt: ast.CreateSink) -> str:
        from risingwave_tpu.frontend.catalog import SinkCatalog
        from risingwave_tpu.frontend.planner import validate_sink_options
        # validate BEFORE planning registers any barrier sender: a
        # planner failure after registration would orphan the channel
        # and wedge every later barrier once its permits run out
        self.catalog._check_free(stmt.name)
        validate_sink_options(stmt.options)
        async with self._barrier_lock:
            planner = StreamPlanner(self.catalog, self.store, self.local,
                                    definition="", mesh=self.mesh,
                                    actors=self.actors,
                                    chunk_target_rows=self
                                    .chunk_target_rows,
                                    coalesce_linger_chunks=self
                                    .coalesce_linger_chunks)
            actor_id = self._next_actor
            self._next_actor += 1
            try:
                plan = planner.plan_sink(
                    stmt.select, stmt.options, actor_id,
                    rate_limit=self.rate_limit,
                    min_chunks=self.min_chunks,
                    sink_name=stmt.name,
                    append_only=stmt.append_only,
                    coordinator=self.sinks)
                from risingwave_tpu.frontend.opt import (
                    apply_rewrites, parse_fusion,
                )
                apply_rewrites(
                    plan,
                    self.session_vars.get("stream_rewrite_rules"),
                    label=stmt.name,
                    fusion=parse_fusion(
                        self.session_vars.get("stream_fusion")))
            except BaseException:
                for sid in planner.registered_senders:
                    self.local.drop_actor(sid)
                raise
            if plan.encoder is not None:
                # register only after the WHOLE plan validated. Fresh
                # create: truncate any uncommitted staging leftover at
                # the path (floor=-1 promotes nothing). Recovery
                # replay: sweep against the recovered checkpoint floor
                # — staged epochs the floor covers are durable
                # upstream, so the sweep PROMOTES them (completes the
                # manifest); younger staging truncates and replays
                self.sinks.register(
                    stmt.name, plan.encoder, n_writers=1,
                    deferred=True,
                    floor=(self.store.committed_epoch()
                           if self._replaying else -1))
            try:
                await self._deploy_job(
                    stmt.name, actor_id, plan.consumer, plan.readers,
                    lambda: self.catalog.add_sink(SinkCatalog(
                        stmt.name, actor_id, dict(stmt.options),
                        dependent_sources=plan.deps, mode=plan.mode,
                        n_writers=1)),
                    attaches=plan.attaches, deps=plan.deps)
            except BaseException:
                self.sinks.unregister(stmt.name)
                raise
        if self._deployed_actor.failure is not None:
            from risingwave_tpu.stream.costs import purge_mv_series
            purge_mv_series(stmt.name)
            self.sinks.unregister(stmt.name)
            raise self._deployed_actor.failure
        return "CREATE_SINK"

    async def _stop_job(self, name: str, actor_id: int):
        """Stop one job's actors at a barrier and remove its topology
        (caller holds the barrier lock). Returns the stopped Actor (or
        None) — shared by drop and reschedule; the sequence is delicate
        (a heartbeat between steps would hang on the stopped actor)."""
        stop_ids = frozenset(self.readers.get(name, {}).keys()
                             | {actor_id})
        await self.loop.inject_and_collect(
            mutation=StopMutation(stop_ids))
        task = self.tasks.pop(actor_id, None)
        if task is not None:
            await task
        actor = self.actors.pop(actor_id, None)
        for sid in self.readers.pop(name, {}):
            self.local.drop_actor(sid)
        self.local.drop_actor(actor_id)
        # detach this job's chain edges from upstream dispatchers: an
        # orphan output would block the upstream on exhausted channel
        # permits a few barriers later
        for uid, out in self.chain_edges.pop(name, []):
            up = self.actors.get(uid)
            if up is not None and up.dispatchers:
                d = up.dispatchers[0]
                d.update_outputs(
                    [o for o in d.outputs() if o is not out])
        # with the edges detached, release the stopped chain's input
        # receivers — drops their queue-depth series deterministically
        if actor is not None:
            from risingwave_tpu.stream.actor import close_receivers
            close_receivers(actor.consumer)
        self.local.set_expected_actors(list(self.actors))
        if self._plane is not None:
            # drop the job from its alignment domain (an empty domain
            # retires — its frontier epoch stops blocking the fence)
            self._plane.remove_job(name)
        # central series-lifecycle purge: freshness, costs, hot-key
        # and topology books (and their {mv=...} series) all die with
        # the job — stream/costs.py owns the fan-out
        from risingwave_tpu.stream.costs import purge_mv_series
        purge_mv_series(name)
        return actor

    async def _drop_job(self, name: str, registry, if_exists: bool,
                        status: str) -> str:
        """Shared drop path for MVs and sinks: stop barrier + topology
        removal as ONE locked unit."""
        entry = registry.get(name)
        if entry is None:
            if if_exists:
                return status
            raise PlanError(f"unknown object {name!r}")
        async with self._barrier_lock:
            actor = await self._stop_job(name, entry.actor_id)
        del registry[name]
        # epoch-segment sinks: deregister from the coordinator —
        # committed manifests stay durable at the path; any pending
        # (non-checkpointed) tail is dropped with the registration,
        # consistent with manifests never outrunning the floor
        self.sinks.unregister(name)
        self._mv_selects.pop(name, None)
        self._mv_rules.pop(name, None)
        self._mv_fusion.pop(name, None)
        self._mv_tier_caps.pop(name, None)
        if actor is not None and actor.failure is not None:
            raise actor.failure
        return status

    async def _drop_mv(self, stmt: ast.DropMaterializedView) -> str:
        if stmt.name in self._tables:
            # tables share catalog.mvs; dropping one here would orphan
            # its DML channel (writes then vanish into a dead reader)
            raise PlanError(
                f"{stmt.name!r} is a table — use DROP TABLE")
        dependents = [
            m.name for m in self.catalog.mvs.values()
            if stmt.name in m.dependent_sources
        ] + [
            sk.name for sk in self.catalog.sinks.values()
            if stmt.name in sk.dependent_sources
        ]
        if dependents:
            raise PlanError(
                f"cannot drop MV {stmt.name!r}: depended on by "
                f"{dependents}")
        return await self._drop_job(stmt.name, self.catalog.mvs,
                                    stmt.if_exists,
                                    "DROP_MATERIALIZED_VIEW")

    async def _select(self, sel: ast.Select) -> Rows:
        from risingwave_tpu.batch import collect
        epoch = self.store.committed_epoch()
        ex = plan_batch(sel, self.catalog, self.store, epoch,
                        profiler=self.loop.profiler)
        # one plan serves both rows and result typing (pgwire reads
        # this right after execute instead of re-planning)
        self.last_select_schema = ex.schema
        return collect(ex)
