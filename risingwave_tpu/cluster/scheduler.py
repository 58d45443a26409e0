"""Cluster: N workers + fragment scheduling + coordinated barriers.

Reference parity: GlobalStreamManager + the actor-graph scheduler
(src/meta/src/stream/stream_manager.rs:161,
src/meta/src/stream/stream_graph/schedule.rs:195-251 — fragments are
scheduled onto parallel units across compute nodes, hash fragments get
the 256-vnode bitmap split among their actors) and GlobalBarrierManager
fan-out (barrier/mod.rs:558 — one InjectBarrier per compute node,
collect-all, then HummockManager::commit_epoch). TPU re-design: each
worker slot owns a hummock namespace under one root; the coordinator
owns the BarrierLoop, pipelines its commit decision onto the next
barrier (two-phase worker stores), and recovery = restart every slot
over its namespace, replay the deployed jobs, resume from the
coordinator's committed epoch (barrier/recovery.rs:110 collapsed).
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

from risingwave_tpu.cluster.coordinator import (
    Heartbeater, WorkerBarrierSender, WorkerClient, WorkerHandle,
)
from risingwave_tpu.frontend.fragmenter import Fragment, FragmentGraph
from risingwave_tpu.meta.barrier import BarrierLoop
from risingwave_tpu.meta.supervisor import (
    ACTION_REQUEUE, ACTION_RESPAWN, ACTION_ROLLBACK,
    CAUSE_COMPACTOR_DEAD, CAUSE_RESCALE_FAILED, RecoveryEvent,
    RecoverySupervisor, trace_recovery_phase, trace_recovery_root,
)
from risingwave_tpu.stream.actor import LocalBarrierManager
from risingwave_tpu.stream.message import StopMutation
from risingwave_tpu.stream.plan_ir import remap_node_refs
from risingwave_tpu.utils.failpoint import fail_point

_PSEUDO_BASE = 1 << 20          # pseudo-actor ids for worker handles


class RescaleError(RuntimeError):
    """A guarded rescale failed. ``rolled_back=True`` means the prior
    topology (and state placement) was restored — the cluster is
    consistent and serving; False means the unwind itself failed (or
    the failure struck before any change, ``phase="stop"``, where the
    domain's health is unknown) and the supervised-recovery ladder
    owns what happens next. Either way the event is in rw_recovery."""

    def __init__(self, msg: str, phase: str, rolled_back: bool):
        super().__init__(msg)
        self.phase = phase
        self.rolled_back = rolled_back


class RescaleInProgressError(RuntimeError):
    """A topology change is already in flight for this cluster —
    concurrent rescales of one domain must serialize, never
    interleave (arxiv 1904.03800's concurrent-state discipline)."""


class _CoordEpochStore:
    """BarrierLoop's store shim: epochs COMMIT on the workers (staged
    SSTs adopted via commit_through); the coordinator only tracks the
    committed watermark — the HummockManager version counter without
    the SST bookkeeping."""

    def __init__(self, floor: int = 0):
        self._committed = floor

    def committed_epoch(self) -> int:
        return self._committed

    def seal_epoch(self, epoch: int, is_checkpoint: bool = True) -> None:
        pass

    def sync(self, epoch: int) -> None:
        self._committed = max(self._committed, epoch)


@dataclass
class JobDeployment:
    """One deployed streaming job: its fragment graph + placements.
    placements[fi] = [(actor_id, worker_slot), ...] per fragment.
    ``domain_keys`` are the job's barrier-domain reachability anchors
    (its source/MV dependency names — jobs sharing one align in a
    single domain; recorded so recovery rebuilds the same domains)."""

    name: str
    graph: FragmentGraph
    placements: List[List[tuple]] = field(default_factory=list)
    domain_keys: frozenset = frozenset()
    # fragment idx → per-actor-RANK partition lists (filelog sources):
    # the split/offsets contract — deploys stamp each source actor's
    # partition subset into its plan, rescales recompute it, and the
    # split-state handoff moves each split's offset row to its new
    # owner's namespace so reads resume exactly
    split_assignments: Dict[int, List[List[int]]] = \
        field(default_factory=dict)

    def actor_ids(self) -> List[int]:
        return [aid for frag in self.placements for aid, _slot in frag]


class Cluster:
    """Coordinator-side handle on N worker processes."""

    def __init__(self, root: str, n_workers: int = 2,
                 platform: str = "cpu",
                 barrier_timeout_s: Optional[float] = None,
                 supervisor: Optional[RecoverySupervisor] = None,
                 epoch_pipeline: bool = True):
        self.root = root
        self.n = n_workers
        self.platform = platform
        self.handles: List[Optional[WorkerHandle]] = [None] * n_workers
        self.clients: List[Optional[WorkerClient]] = [None] * n_workers
        self.jobs: Dict[str, JobDeployment] = {}
        self.local: Optional[LocalBarrierManager] = None
        self.loop = None        # BarrierLoop (off arm) or BarrierPlane
        self.store = _CoordEpochStore()
        # pipelined epochs (ISSUE 13): per-job barrier domains with
        # their own control connections per worker (two domains'
        # injects must not serialize behind one request-response
        # channel); off = the legacy single global loop, bit-identical
        self.epoch_pipeline = bool(epoch_pipeline)
        self._plane = None
        # domain → {"pids": [per-slot pseudo ids], "clients": [...]}
        self._domain_wiring: Dict[str, dict] = {}
        self._domain_seq = 0
        self._next_actor = 1000
        self._rr = 0                      # placement cursor
        # supervised recovery (meta/supervisor.py): classification +
        # storm gate; barrier_timeout_s arms wedged-barrier detection
        self.supervisor = supervisor or RecoverySupervisor()
        self.barrier_timeout_s = barrier_timeout_s
        # heartbeat-expiry detection (enable_liveness): lease-expired
        # slots feed the supervisor's dead set even while their
        # subprocess is technically alive (wedged, not exited)
        self._manager = None
        self._heartbeater: Optional[Heartbeater] = None
        self._expired_slots: Set[int] = set()
        self._wid_slot: Dict[int, int] = {}
        # topology-change serialization (ISSUE 15): one rescale/move at
        # a time per cluster — a second caller gets a clear
        # RescaleInProgressError, never an interleaved redeploy
        self._topology_busy: Optional[str] = None
        # (job, fragment) → "vnode"|"source": a rescale whose ROLLBACK
        # failed leaves state possibly straddling namespaces; the next
        # recovery re-routes it to the recorded placements (repair)
        self._pending_repair: Dict[Tuple[str, int], str] = {}
        # chaos seam: one-shot (phase, fn) fired at that rescale phase
        # — how the harness kills a worker mid-redeploy deterministically
        self.rescale_fault_hook: Optional[tuple] = None
        # dedicated compaction (ISSUE 19): one compactor-role
        # subprocess + a CompactionManager with one namespace per
        # worker slot; 'inline' = workers compact on their own commit
        # path (the oracle arm)
        self._compaction_mode = "inline"
        self._compaction_mgr = None
        self._compactor_handle: Optional[WorkerHandle] = None
        self._compactor_client: Optional[WorkerClient] = None
        self.compactor_respawns = 0
        # exactly-once sinks (ISSUE 20): the meta-side coordinator —
        # workers stage INLINE at barrier passage (deferred=False
        # registrations), this side owns manifest commits at the
        # checkpoint floor and the recovery promote/truncate sweep
        from risingwave_tpu.meta.sink_coordinator import SinkCoordinator
        self.sinks = SinkCoordinator()

    # -- lifecycle --------------------------------------------------------
    async def start(self) -> None:
        await asyncio.gather(*(self._start_slot(k)
                               for k in range(self.n)))
        await self._fresh_barrier_plane()

    async def _start_slot(self, k: int) -> None:
        h = WorkerHandle(os.path.join(self.root, f"w{k}"),
                         platform=self.platform)
        self.clients[k] = await h.start()
        self.handles[k] = h

    async def _fresh_barrier_plane(self) -> None:
        """(Re)build the barrier fan-out. Off arm: one global loop,
        one pseudo-actor per worker slot. Plane arm: one BarrierPlane
        whose domains rebuild from the deployed jobs' recorded
        ``domain_keys`` — after a recovery every domain's initial
        barrier recovers ``prev = the committed floor``, re-aligning
        all domains to the same durable point."""
        self.local = LocalBarrierManager()
        # release the previous generation's per-domain control
        # connections (reset-in-place recoveries keep the worker
        # processes alive — without the abort every recovery round
        # would leak domains × workers open sockets)
        for w in self._domain_wiring.values():
            for c in w["clients"]:
                if c is not None:
                    c.abort()
        self._domain_wiring = {}
        if not self.epoch_pipeline:
            # distributed=True: the ledger's sealed records cover only
            # coordinator-side phases until drain_ledger merges the
            # worker accumulators in (conservation defers to the merge)
            self.loop = BarrierLoop(
                self.local, self.store,
                collect_timeout_s=self.barrier_timeout_s,
                distributed=True)
            self._plane = None
            for k in range(self.n):
                pid = _PSEUDO_BASE + k
                self.local.register_sender(
                    pid, WorkerBarrierSender(
                        self.clients[k], self.local, pid,
                        committed_fn=lambda:
                        self.store.committed_epoch()))
            self.local.set_expected_actors(
                [_PSEUDO_BASE + k for k in range(self.n)])
            self.loop.uploader.sinks = self.sinks
            return
        from risingwave_tpu.meta.domains import BarrierPlane
        self._plane = BarrierPlane(
            self.local, self.store,
            collect_timeout_s=self.barrier_timeout_s,
            distributed=True)
        self._plane.aligned_hook = self._seal_sync_workers
        self.loop = self._plane
        # sink manifests commit in the uploader hooks: strictly after
        # the floor is durable (and the aligned_hook has sealed every
        # worker — inline staging is already on disk by collection)
        self.loop.uploader.sinks = self.sinks
        for name, job in self.jobs.items():
            self._plane.assign_job(name, set(job.domain_keys),
                                   sender_ids=(), expected_ids=(),
                                   actor_ids=job.actor_ids())
        await self._rewire_domains()

    def _domain_extras_fn(self, domain: str):
        """Builds the per-barrier domain frame: the actor filter the
        worker scopes the barrier to, and the cross-domain write floor
        it may fence the store to."""
        def extras(_barrier) -> dict:
            actors = sorted(a for a in
                            self._plane.domain_actors(domain)
                            if a < _PSEUDO_BASE)
            # "domain" rides along so the WORKER can run the
            # bottleneck walk over its own chains per barrier (the
            # autoscaler's signal on a distributed session)
            return {"actors": actors, "domain": domain,
                    "seal": self._plane.allocator.write_floor()}
        return extras

    async def _wire_domain(self, domain: str) -> None:
        """Open one control connection per worker slot for a new
        domain and register its barrier senders. Separate connections
        are the point: two domains' inject RPCs on one request-
        response channel would serialize — the slow domain's collect
        would block the fast domain's inject, resurrecting the global
        lockstep at the transport layer."""
        self._domain_seq += 1
        pids, clients = [], []
        for k in range(self.n):
            base = self.clients[k]
            if base is None:
                pids.append(None)
                clients.append(None)
                continue
            c = WorkerClient(base.host, base.control_port,
                             base.exchange_port)
            await c.connect()
            pid = _PSEUDO_BASE + self._domain_seq * 256 + k
            self.local.register_sender(
                pid, WorkerBarrierSender(
                    c, self.local, pid,
                    committed_fn=lambda: self.store.committed_epoch(),
                    extras_fn=self._domain_extras_fn(domain)))
            pids.append(pid)
            clients.append(c)
        self._domain_wiring[domain] = {"pids": pids,
                                       "clients": clients}
        self._plane.set_domain_channel(
            domain, [p for p in pids if p is not None])

    async def _rewire_domains(self) -> None:
        """Reconcile per-domain wiring with the plane's live domains
        (deploys create domains; merges absorb them; drops retire
        them)."""
        live = {d for d in self._plane.domains()
                if self._plane.domain_actors(d)
                or d in {self._plane.domain_of_job(j)
                         for j in self.jobs}}
        for dom in list(self._domain_wiring):
            if dom not in live:
                w = self._domain_wiring.pop(dom)
                for pid in w["pids"]:
                    if pid is not None:
                        self.local.drop_actor(pid)
                for c in w["clients"]:
                    if c is not None:
                        c.abort()
        for dom in live:
            if dom not in self._domain_wiring:
                await self._wire_domain(dom)
            else:
                # a merge may have folded an absorbed domain's pseudo
                # actors into the survivor's member sets — scrub them
                # back to exactly this domain's wired channel, or the
                # next barrier would wait on dead pseudo actors
                self._plane.set_domain_channel(
                    dom, [p for p in self._domain_wiring[dom]["pids"]
                          if p is not None])

    async def _seal_sync_workers(self, floor: int) -> None:
        """Aligned-checkpoint push: every worker seals + stage-syncs
        to the floor BEFORE the coordinator watermark advances — the
        committed epoch recovery trusts is durable on every slot."""
        await asyncio.gather(*(
            c.call_idempotent({"cmd": "seal_sync", "epoch": floor},
                              io_timeout=60.0)
            for c in self.clients if c is not None))

    def _all_pseudo(self) -> Set[int]:
        if self._plane is None:
            return {_PSEUDO_BASE + k for k in range(self.n)}
        return {pid for w in self._domain_wiring.values()
                for pid in w["pids"] if pid is not None}

    def _stop_set(self, *jobs: JobDeployment) -> frozenset:
        """Actor ids to stop (plus every worker pseudo-actor — the
        stop barrier must still collect on every slot)."""
        ids = {a for j in jobs for a in j.actor_ids()}
        return frozenset(ids | self._all_pseudo())

    async def stop(self) -> None:
        if self._compaction_mgr is not None:
            mgr, self._compaction_mgr = self._compaction_mgr, None
            await mgr.drain()
        if self.loop is not None:
            await self.loop.inject_and_collect(
                force_checkpoint=True,
                mutation=StopMutation(
                    self._stop_set(*self.jobs.values())))
        for h in self.handles:
            if h is not None:
                await h.stop()
        await self._stop_compactor()

    def kill_slot(self, k: int) -> None:
        """SIGKILL one worker (chaos path: no goodbye, no flush).
        Deliberately does NOT reap: the corpse stays visible to
        dead_slots() until a recovery handles it, like a real crash."""
        if self.handles[k] is not None and self.handles[k].proc \
                is not None:
            self.handles[k].proc.kill()

    # -- failure detection ------------------------------------------------
    def dead_slots(self) -> List[int]:
        """The supervisor's dead set: slots whose subprocess exited
        (poll) plus slots whose heartbeat lease expired (alive but
        wedged — enable_liveness feeds these)."""
        out = {k for k, h in enumerate(self.handles)
               if h is None or not h.alive()}
        out |= self._expired_slots
        return sorted(out)

    def enable_liveness(self, max_interval_s: float = 5.0) -> None:
        """Heartbeat-expiry detection: register every slot in a
        ClusterManager and ping through a Heartbeater whose ticks the
        serving loop drives explicitly (no background task — ticks are
        deterministic under test drivers). Expired leases land in the
        supervisor's dead set via ``dead_slots()``. Re-invoked after
        every recovery (clients change)."""
        from risingwave_tpu.meta.cluster import ClusterManager

        self._manager = ClusterManager(
            max_heartbeat_interval_s=max_interval_s)
        self._wid_slot = {}
        self._heartbeater = Heartbeater(
            self._manager, on_expired=self._note_expired)
        for k, c in enumerate(self.clients):
            if c is None:
                continue
            w = self._manager.add_worker("127.0.0.1", c.control_port)
            self._wid_slot[w.worker_id] = k
            self._heartbeater.register(w.worker_id, c)

    def _note_expired(self, dead_nodes) -> None:
        for w in dead_nodes:
            slot = self._wid_slot.get(w.worker_id)
            if slot is not None:
                self._expired_slots.add(slot)

    async def liveness_tick(self) -> list:
        """One heartbeat round (serving loops call this per beat)."""
        if self._heartbeater is None:
            return []
        return await self._heartbeater.tick()

    # -- scheduling (schedule.rs analog) ----------------------------------
    def _place(self, graph: FragmentGraph) -> List[List[tuple]]:
        """Round-robin actors over worker slots; each hash fragment's
        actor list order defines its vnode mapping order."""
        placements = []
        for frag in graph.fragments:
            actors = []
            for _ in range(frag.parallelism):
                slot = self._rr % self.n
                self._rr += 1
                actors.append((self._next_actor, slot))
                self._next_actor += 1
            placements.append(actors)
        return placements

    def _expand_nodes(self, frag: Fragment, actor_id: int,
                      placements: List[List[tuple]],
                      splits: Optional[List[int]] = None,
                      rank: int = 0,
                      n_actors: int = 1) -> List[dict]:
        """Resolve exchange_in placeholders into per-upstream-actor
        remote_input nodes + a merge, and pin the source actor id.
        ``splits`` (filelog fragments) is THIS actor's partition
        subset, stamped into the connector options so the worker
        builds a reader over exactly those splits. ``rank`` /
        ``n_actors`` stamp sink nodes with their writer identity —
        each parallel actor is one of the N exactly-once writers."""
        out: List[dict] = []
        remap: Dict[int, int] = {}
        for idx, node in enumerate(frag.nodes):
            if node["op"] == "exchange_in":
                inp = frag.inputs[node["port"]]
                r_idxs = []
                for up_aid, up_slot in placements[inp.up_frag]:
                    out.append({
                        "op": "remote_input", "host": "127.0.0.1",
                        "port": self.clients[up_slot].exchange_port,
                        "up_actor": up_aid, "schema": inp.schema})
                    r_idxs.append(len(out) - 1)
                from risingwave_tpu.stream.coalesce import (
                    DEFAULT_MAX_CHUNKS,
                )
                out.append({"op": "merge", "inputs": r_idxs,
                            # session knobs ride the cut edge: rows=0
                            # disables fan-in re-coalescing end to end
                            "coalesce_rows": int(getattr(
                                inp, "coalesce_rows", 0)),
                            "coalesce_chunks": int(getattr(
                                inp, "coalesce_chunks",
                                DEFAULT_MAX_CHUNKS))})
                remap[idx] = len(out) - 1
                continue
            n2 = remap_node_refs(node, remap)
            if n2["op"] == "source":
                n2["actor_id"] = actor_id
                if splits is not None:
                    conn = dict(n2.get("connector") or {})
                    conn["partitions"] = ",".join(str(p)
                                                  for p in splits)
                    n2["connector"] = conn
            elif n2["op"] == "sink":
                n2["writer"] = int(rank)
                n2["n_writers"] = int(n_actors)
            out.append(n2)
            remap[idx] = len(out) - 1
        return out

    def _wiring(self, fi: int, graph: FragmentGraph,
                placements: List[List[tuple]]) -> tuple:
        """(outputs, dispatch) for fragment fi's actors — hash over the
        consumer's actors with a uniform vnode mapping, simple when the
        consumer is a single actor."""
        consumers = graph.consumers_of(fi)
        if not consumers:
            return [], None
        assert len(consumers) == 1, "tree plans have one consumer"
        down_fi, inp = consumers[0]
        outs = [aid for aid, _slot in placements[down_fi]]
        if inp.mode == "broadcast" and len(outs) > 1:
            return outs, {"type": "broadcast"}
        if len(outs) == 1:
            return outs, {"type": "simple"}
        from risingwave_tpu.common.hash import VnodeMapping
        mapping = VnodeMapping.new_uniform(len(outs))
        return outs, {"type": "hash", "keys": inp.keys,
                      "mapping": [int(o) for o in mapping.owners]}

    async def deploy_graph(self, name: str, graph: FragmentGraph,
                           domain_keys=()) -> JobDeployment:
        """Schedule + deploy one job's fragments (upstream first so
        exchange edges exist before consumers connect), then leave
        activation to the caller's next barrier. A partial failure
        unwinds: already-deployed actors stop at a barrier — left
        running, a source feeding an edge nobody consumes would block
        on the credit window and wedge every later barrier.
        ``domain_keys`` (source/MV names the job reads) anchor its
        barrier domain: jobs sharing one align together."""
        if name in self.jobs:
            raise ValueError(f"job {name!r} already deployed")
        job = JobDeployment(name, graph, self._place(graph),
                            domain_keys=frozenset(domain_keys))
        for fi, frag in enumerate(graph.fragments):
            if self._source_rescalable(frag):
                # Kafka-parity split assignment: ALL of the topic's
                # partitions round-robin over the fragment's actors
                # (one actor owns them all at parallelism 1)
                job.split_assignments[fi] = self._round_robin_splits(
                    self._source_partitions(frag),
                    len(job.placements[fi]))
        try:
            await self._deploy_job(job)
        except BaseException:
            if self.loop is not None:
                await self.loop.inject_and_collect(
                    force_checkpoint=True,
                    mutation=StopMutation(self._stop_set(job)))
            raise
        self.jobs[name] = job
        if self._plane is not None:
            self._plane.assign_job(name, set(job.domain_keys),
                                   sender_ids=(), expected_ids=(),
                                   actor_ids=job.actor_ids())
            await self._rewire_domains()
        return job

    async def _deploy_job(self, job: JobDeployment) -> None:
        # fragments deploy upstream-first (edges must exist before
        # consumers connect); a fragment's actors deploy concurrently
        for fi, frag in enumerate(job.graph.fragments):
            outputs, dispatch = self._wiring(fi, job.graph,
                                             job.placements)
            assign = job.split_assignments.get(fi)
            await asyncio.gather(*(
                self.clients[slot].deploy_plan(
                    self._expand_nodes(
                        frag, aid, job.placements,
                        splits=assign[rank] if assign is not None
                        else None, rank=rank,
                        n_actors=len(job.placements[fi])),
                    actor_id=aid, outputs=outputs, dispatch=dispatch,
                    job=job.name)
                for rank, (aid, slot)
                in enumerate(job.placements[fi])))

    async def drop_job(self, name: str) -> None:
        job = self.jobs.pop(name, None)
        if job is None:
            raise KeyError(name)
        await self.loop.inject_and_collect(
            force_checkpoint=True,
            mutation=StopMutation(self._stop_set(job)))
        if self._plane is not None:
            self._plane.remove_job(name)
            await self._rewire_domains()

    # -- barriers ---------------------------------------------------------
    async def step(self, n: int = 1) -> None:
        for _ in range(n):
            await self.loop.inject_and_collect(force_checkpoint=True)

    # -- dedicated compaction (ISSUE 19) ----------------------------------
    async def set_compaction(self, mode: str) -> None:
        """Fan the compaction arm to every worker namespace and
        (de)provision the compactor role. 'dedicated' spawns ONE
        compactor subprocess plus a CompactionManager with one
        namespace per worker slot; 'inline' drains in-flight tasks,
        reverts workers to commit-path compaction and stops the
        compactor. Remembered across respawns/recoveries."""
        from risingwave_tpu.meta.compaction import parse_compaction
        mode = parse_compaction(mode)
        self._compaction_mode = mode
        await asyncio.gather(*(
            c.call_idempotent({"cmd": "set_compaction", "mode": mode},
                              io_timeout=20.0)
            for c in self.clients if c is not None))
        if mode == "dedicated":
            if self._compactor_handle is None:
                await self._start_compactor()
            if self._compaction_mgr is None:
                from risingwave_tpu.meta.compaction import (
                    CompactionManager,
                )
                self._compaction_mgr = CompactionManager(
                    on_fault=self._on_compactor_fault)
                for k in range(self.n):
                    self._compaction_mgr.add_namespace(
                        f"w{k}", self._compaction_hooks(k))
        else:
            mgr, self._compaction_mgr = self._compaction_mgr, None
            if mgr is not None:
                await mgr.drain()
            await self._stop_compactor()

    async def _start_compactor(self) -> None:
        h = WorkerHandle(os.path.join(self.root, "compactor"),
                         platform=self.platform, role="compactor")
        self._compactor_client = await h.start()
        self._compactor_handle = h

    async def _stop_compactor(self) -> None:
        h, self._compactor_handle = self._compactor_handle, None
        self._compactor_client = None
        if h is None:
            return
        try:
            await h.stop()
        except BaseException:  # noqa: BLE001 — a chaos-killed corpse
            h.kill()           # cannot answer the stop verb; reap it

    def kill_compactor(self) -> None:
        """SIGKILL the compactor role (chaos path). Serving is
        untouched by design: the in-flight task's lease expires, the
        manager aborts + requeues, compaction_tick respawns the
        process."""
        h = self._compactor_handle
        if h is not None and h.proc is not None:
            h.proc.kill()

    def _compaction_hooks(self, k: int):
        """Hooks for slot k's namespace. snapshot/reserve/apply/abort
        run on the OWNING worker over its control channel — resolved
        at call time, because recoveries swap ``clients[k]``; execute
        dispatches the merge to the compactor role pointed at the
        worker's namespace directory."""
        from risingwave_tpu.meta.compaction import CompactorHooks

        def client() -> WorkerClient:
            c = self.clients[k]
            if c is None:
                raise ConnectionError(f"worker slot {k} down")
            return c

        async def snapshot():
            r = await client().call_idempotent(
                {"cmd": "level_snapshot"}, io_timeout=20.0)
            return r["snapshot"]

        async def reserve(input_ids, id_block):
            return await client().call(
                {"cmd": "compact_reserve", "inputs": input_ids,
                 "id_block": id_block}, io_timeout=20.0)

        async def apply(input_ids, outputs):
            return await client().call(
                {"cmd": "compact_apply", "inputs": input_ids,
                 "outputs": outputs}, io_timeout=20.0)

        async def abort(input_ids, output_ids):
            return await client().call_idempotent(
                {"cmd": "compact_abort", "inputs": input_ids,
                 "outputs": output_ids}, io_timeout=20.0)

        async def execute(task):
            c = self._compactor_client
            if c is None:
                raise ConnectionError("compactor down")
            return await c.call(
                {"cmd": "compact_task",
                 "store": os.path.join(self.root, f"w{k}"),
                 "task": task}, io_timeout=60.0)

        return CompactorHooks(snapshot=snapshot, reserve=reserve,
                              apply=apply, abort=abort,
                              execute=execute)

    def _on_compactor_fault(self, ns: str, kind: str, exc) -> None:
        """A compactor fault costs a TASK, never a serving domain:
        record the requeue in rw_recovery directly — NEVER through
        supervisor.admit(), whose storm budget belongs to serving
        recoveries."""
        detail = f"{ns}: {kind}"
        if exc is not None:
            detail = f"{detail}: {exc!r}"
        self.supervisor.record(
            CAUSE_COMPACTOR_DEAD, ACTION_REQUEUE, (),
            self.store.committed_epoch(), 0.0, True, 1,
            detail=detail[:200])

    async def compaction_tick(self) -> Optional[dict]:
        """One manager round (the distributed session calls this after
        each barrier). Heals a dead compactor process FIRST: task
        recovery must not wait on a corpse that can never finish."""
        mgr = self._compaction_mgr
        if mgr is None:
            return None
        h = self._compactor_handle
        if h is not None and not h.alive():
            h.kill()                     # reap (idempotent)
            await self._start_compactor()
            self.compactor_respawns += 1
        return await mgr.tick()

    async def drain_trace(self) -> int:
        """Pull every worker's recorded spans into the coordinator's
        flight recorder, tagged by worker slot — a drained span leaves
        the worker, so repeated drains never duplicate."""
        from risingwave_tpu.utils.spans import EPOCH_TRACER
        # keep the REAL slot index next to each reply: enumerating the
        # None-filtered list would shift every tag left of a dead slot
        # and attribute a live worker's spans to the wrong process
        live = [(k, c) for k, c in enumerate(self.clients)
                if c is not None]
        replies = await asyncio.gather(*(
            c.call({"cmd": "drain_trace"}) for _k, c in live))
        n = 0
        for (k, _c), reply in zip(live, replies):
            n += EPOCH_TRACER.ingest(reply.get("spans", ()),
                                     worker=f"worker-{k}")
        # the watchdog promoted slow barriers BEFORE these spans
        # arrived: recompute their straggler lines over the full view
        EPOCH_TRACER.refresh_diagnoses()
        return n

    async def drain_ledger(self) -> int:
        """Pull every worker's open phase-ledger accumulators into the
        coordinator's ledger (merged into the sealed records of the
        same epochs — this is what makes a distributed epoch's
        conservation residual meaningful). Drained accumulators leave
        the worker, so repeated drains never double-count."""
        from risingwave_tpu.utils.ledger import LEDGER
        live = [(k, c) for k, c in enumerate(self.clients)
                if c is not None]
        replies = await asyncio.gather(*(
            c.call({"cmd": "drain_ledger"}) for _k, c in live))
        # conservation resolves only when EVERY worker's books arrived
        # — with a dead slot the record's residual would be a phantom
        # of the missing process, so the exemption stands
        complete = len(live) == self.n
        n = 0
        for (k, _c), reply in zip(live, replies):
            n += LEDGER.ingest(reply.get("epochs", ()),
                               worker=f"worker-{k}",
                               resolve=complete)
        return n

    async def drain_freshness(self) -> int:
        """Pull every worker's raw freshness parts (ingest hwms, epoch
        frontiers, visibility events) into the coordinator's tracker —
        a source fragment on worker 0 and its materialize on worker 1
        resolve into one per-MV lag series here. Returns visibility
        events resolved."""
        from risingwave_tpu.stream.freshness import FRESHNESS
        live = [c for c in self.clients if c is not None]
        replies = await asyncio.gather(*(
            c.call({"cmd": "drain_freshness"}) for c in live))
        n = 0
        for reply in replies:
            n += FRESHNESS.ingest(reply.get("parts") or {})
        return n

    async def drain_signals(self, light: bool = False) -> int:
        """Pull every worker's autoscaler signal snapshot — the
        utilization tricolor rows and the worker-side bottleneck-walker
        state — into the coordinator's process-global views. Actor ids
        are cluster-unique, so worker rows merge collision-free; the
        walker merge keeps the strongest per-domain candidate across
        processes. Feeds rw_actor_utilization / rw_bottlenecks on the
        distributed session and the autoscaler's tick."""
        from risingwave_tpu.state.topology import TOPOLOGY
        from risingwave_tpu.stream.bottleneck import BOTTLENECKS
        from risingwave_tpu.stream.costs import COSTS
        from risingwave_tpu.stream.hotkeys import HOTKEYS
        from risingwave_tpu.stream.monitor import UTILIZATION
        live = [(k, c) for k, c in enumerate(self.clients)
                if c is not None]
        replies = await asyncio.gather(*(
            c.call_idempotent({"cmd": "signals", "light": light},
                              io_timeout=20.0)
            for _k, c in live))
        n = 0
        for (k, _c), reply in zip(live, replies):
            n += UTILIZATION.ingest_rows(reply.get("utilization")
                                         or ())
            n += BOTTLENECKS.ingest(reply.get("bottlenecks") or (),
                                    worker=f"worker-{k}")
            # attribution surfaces (ISSUE 16): topology/hot-key
            # snapshots replace per worker (absent on a light drain —
            # replacing with () would wipe the cached snapshot); cost
            # books fold as true-drain deltas every time
            if "topology" in reply:
                n += TOPOLOGY.ingest(reply["topology"] or (),
                                     worker=f"worker-{k}")
            if "hot_keys" in reply:
                n += HOTKEYS.ingest(reply["hot_keys"] or (),
                                    worker=f"worker-{k}")
            n += COSTS.ingest(reply.get("mv_costs") or {},
                              worker=f"worker-{k}")
        # evict rows for actors no rescale/recovery kept: ingested
        # copies have no worker-side drop to mirror, and every
        # redeploy mints fresh actor ids
        UTILIZATION.prune(a for j in self.jobs.values()
                          for a in j.actor_ids())
        return n

    def domain_of_job(self, name: str) -> str:
        """The barrier domain a deployed job's epochs flow through
        ("" = the global loop / off arm)."""
        if self._plane is None:
            return ""
        return self._plane.domain_of_job(name) or ""

    # -- distributed reads ------------------------------------------------
    async def scan_table(self, table_id: int) -> List[tuple]:
        """Union a table's committed rows across every namespace
        (vnode-disjoint, so plain concatenation then key-sort). The
        scan pins the COORDINATOR's committed epoch: workers lag one
        barrier behind (the commit decision pipelines), but their
        staged SSTs are readable at any epoch — this keeps FLUSH →
        SELECT read-your-writes like the in-process session."""
        epoch = self.store.committed_epoch()
        parts = await asyncio.gather(
            *(c.scan_table(table_id, epoch=epoch)
              for c in self.clients if c is not None))
        rows: List[tuple] = [kv for part in parts for kv in part]
        rows.sort(key=lambda kv: kv[0])
        return rows

    # -- recovery (recovery.rs:110 collapsed) -----------------------------
    async def recover(self) -> None:
        """Full-cluster recovery to the coordinator's committed epoch:
        kill every slot, restart over the same namespaces, discard
        uncommitted staged state, redeploy all jobs. The next barrier
        resumes sources from their recovered offsets."""
        floor = self.store.committed_epoch()
        for k in range(self.n):
            if self.handles[k] is not None:
                self.handles[k].kill()
        await asyncio.gather(*(self._start_slot(k)
                               for k in range(self.n)))
        await asyncio.gather(*(
            self.clients[k].call({"cmd": "recover_store",
                                  "epoch": floor})
            for k in range(self.n)))
        # sink sweep BEFORE any writer redeploys: epochs the floor
        # covers promote (their staging was durable before the floor
        # advanced), younger staging truncates — replayed rows
        # re-stage under fresh epochs, never duplicating
        self.sinks.recover(floor)
        if self._compaction_mode != "inline":
            await asyncio.gather(*(
                self.clients[k].call_idempotent(
                    {"cmd": "set_compaction",
                     "mode": self._compaction_mode}, io_timeout=20.0)
                for k in range(self.n)))
        await self._fresh_barrier_plane()
        await self._run_pending_repairs()
        for job in self.jobs.values():
            await self._deploy_job(job)
        if self._heartbeater is not None:
            self.enable_liveness(self._manager.max_interval)

    async def _respawn_slot(self, k: int) -> None:
        """Restart one DEAD slot's subprocess over its namespace."""
        if self.handles[k] is not None:
            self.handles[k].kill()       # reap the corpse (idempotent)
        await self._start_slot(k)
        if self._compaction_mode != "inline":
            # a fresh process boots inline — without this re-apply the
            # respawned worker would compact on its own commit path,
            # racing (and conflicting with) the manager's reservations
            await self.clients[k].call_idempotent(
                {"cmd": "set_compaction",
                 "mode": self._compaction_mode}, io_timeout=20.0)

    async def _reset_slot(self, k: int) -> None:
        """Rejoin one LIVE slot in place: fresh control connection
        (the old one may be desynced or holding a wedged RPC), then
        the worker drops its actors and exchange edges while keeping
        the process — and its warm jit caches — alive."""
        old = self.clients[k]
        c = WorkerClient(old.host, old.control_port,
                         old.exchange_port)
        await c.connect()
        old.abort()
        self.clients[k] = c
        if self.handles[k] is not None:
            self.handles[k].client = c
        # bounded: a worker wedged in a blocking call would otherwise
        # hang the recovery itself — past the bound the reset fails,
        # the event records ok=False, and the next round classifies
        # the still-broken state (ending in the storm gate if it
        # never heals)
        await c.call_idempotent({"cmd": "reset"}, io_timeout=20.0,
                                retries=1)

    async def respawn_recover(self, dead: List[int]) -> None:
        """Rung-2 recovery: restart ONLY the dead slots' processes;
        live slots reset in place. Everyone rejoins through the same
        ``recover_store`` handshake at the coordinator's committed
        floor, the barrier plane rebuilds, and every job redeploys —
        all actors were dropped everywhere, because a fragment's
        exchange peers span slots and actor state cannot survive
        partially. With ``dead == []`` (a desynced control channel)
        this degrades to reset-everything-in-place: zero process
        restarts."""
        floor = self.store.committed_epoch()
        dead_set = set(dead)
        await asyncio.gather(*(
            self._respawn_slot(k) if k in dead_set
            else self._reset_slot(k)
            for k in range(self.n)))
        await asyncio.gather(*(
            self.clients[k].call_idempotent(
                {"cmd": "recover_store", "epoch": floor},
                io_timeout=20.0)
            for k in range(self.n)))
        # same promote/truncate sweep as full recovery — a writer
        # killed mid-stage may have left segments above the floor
        self.sinks.recover(floor)
        await self._fresh_barrier_plane()
        await self._run_pending_repairs()
        for job in self.jobs.values():
            await self._deploy_job(job)
        if self._heartbeater is not None:
            self.enable_liveness(self._manager.max_interval)

    async def supervised_recover(self, exc: BaseException
                                 ) -> RecoveryEvent:
        """One supervised recovery round: detect (dead subprocesses +
        expired leases) → classify → admit through the storm gate →
        graduated response → record (rw_recovery row, recovery_total/
        recovery_duration_seconds, recovery.* span chain). Raises
        RecoveryStormError past the consecutive budget; a recovery
        that itself fails records ok=False and re-raises — the next
        beat classifies the new failure."""
        dead = self.dead_slots()
        self._expired_slots.clear()          # consumed into this round
        cause = self.supervisor.classify(exc, dead_workers=dead)
        action = self.supervisor.action_for(cause)
        attempt = await self.supervisor.admit(cause)
        floor = self.store.committed_epoch()
        workers = tuple(dead) if (action == ACTION_RESPAWN and dead) \
            else tuple(range(self.n))
        root = trace_recovery_root(cause, action, floor, attempt)
        t0_wall, t0 = time.time(), time.monotonic()
        ok = False
        try:
            if action == ACTION_RESPAWN:
                await self.respawn_recover(dead)
            else:
                await self.recover()
            ok = True
        finally:
            dur = time.monotonic() - t0
            trace_recovery_phase(
                action, floor, root, t0_wall, dur,
                workers=",".join(str(w) for w in workers))
            ev = self.supervisor.record(
                cause, action, workers, floor, dur, ok, attempt,
                detail=repr(exc)[:200])
        return ev

    # -- reschedule (scale.rs:717 + rebalance_actor_vnode :174) -----------
    # ops whose state is either vnode-partitioned by the exchange keys
    # or derivable from it — fragments of ONLY these ops can rescale
    # with a vnode-sliced state handoff
    # "sink" is trivially rescalable: the epoch-segment writer is
    # STATELESS (visibility is manifest-existence; staged epochs above
    # the recovery floor truncate) — the handoff moves nothing, and
    # the redeploy re-stamps writer ranks for the new actor count
    _RESCALABLE_OPS = frozenset({"exchange_in", "hash_agg", "project",
                                 "filter", "materialize", "sink"})

    def _rescalable(self, frag: Fragment) -> bool:
        if not frag.inputs or any(i.mode != "hash" for i in frag.inputs):
            return False
        for n in frag.nodes:
            if n["op"] not in self._RESCALABLE_OPS:
                return False
            if n["op"] == "materialize" and not n.get("dist_key"):
                return False
        return True

    @contextlib.contextmanager
    def _topology_change(self, desc: str):
        """Serialize topology changes: a second rescale/move arriving
        while one is in flight gets a clear error, never an
        interleaved redeploy of the same domain. (Callers going
        through the session's barrier lock additionally QUEUE —
        this guard is the explicit backstop for direct API use.)"""
        if self._topology_busy is not None:
            raise RescaleInProgressError(
                f"rescale in progress ({self._topology_busy}) — "
                f"topology changes serialize; retry when it completes")
        self._topology_busy = desc
        try:
            yield
        finally:
            self._topology_busy = None

    def _fire_rescale_hook(self, phase: str) -> None:
        if self.rescale_fault_hook is not None \
                and self.rescale_fault_hook[0] == phase:
            _ph, fn = self.rescale_fault_hook
            self.rescale_fault_hook = None
            fn()

    async def rescale_fragment(self, name: str, frag_idx: int,
                               to_slots: List[int]) -> None:
        """Change one fragment's actor set (count AND placement) at a
        stopped barrier: every state row moves to its vnode's NEW
        owner (the 2-byte key prefix IS the vnode — scale.rs's bitmap
        rebalance, made explicit as a scan/slice/ingest handoff across
        per-slot namespaces). Guarded (ISSUE 15): a failure mid-way
        rolls the domain back to the prior topology and state
        placement instead of leaving it half-deployed — see
        ``_guarded_rescale``."""
        from risingwave_tpu.common.hash import VnodeMapping

        job = self.jobs[name]
        frag = job.graph.fragments[frag_idx]
        old = job.placements[frag_idx]
        if len(to_slots) == len(old) and \
                [s for _a, s in old] == list(to_slots):
            return
        if not self._rescalable(frag):
            raise ValueError(
                "fragment is not vnode-rescalable (needs hash inputs "
                "and only exchange_in/hash_agg/project/filter/"
                "materialize-with-dist_key nodes)")
        mapping = VnodeMapping.new_uniform(len(to_slots))

        def owner_of(_tid: int, k: bytes, _v) -> int:
            return to_slots[mapping.owner_of(
                int.from_bytes(k[:2], "big"))]

        with self._topology_change(
                f"{name}/f{frag_idx} -> slots {list(to_slots)}"):
            await self._guarded_rescale(job, frag_idx, list(to_slots),
                                        owner_of, source_assign=None)

    async def rescale_source_fragment(self, name: str, frag_idx: int,
                                      to_slots: List[int]) -> None:
        """Rescale a SOURCE fragment by split reassignment (the
        filelog splits/offsets contract): the topic's partitions
        round-robin over the new actor set, each split's offset row
        migrates to its new owner's namespace, and the redeployed
        readers resume from those byte offsets exactly — no record
        lost, none re-read. Guarded like the vnode path."""
        job = self.jobs[name]
        frag = job.graph.fragments[frag_idx]
        if not self._source_rescalable(frag):
            raise ValueError(
                "fragment is not split-rescalable (needs a filelog "
                "source with a topic and only source/project/filter/"
                "coalesce/row_id_gen nodes)")
        old = job.placements[frag_idx]
        if len(to_slots) == len(old) and \
                [s for _a, s in old] == list(to_slots):
            return
        parts = self._source_partitions(frag)
        assign = self._round_robin_splits(parts, len(to_slots))
        owner_of = self._split_owner_fn(assign, list(to_slots))
        with self._topology_change(
                f"{name}/f{frag_idx} splits -> slots {list(to_slots)}"):
            await self._guarded_rescale(job, frag_idx, list(to_slots),
                                        owner_of,
                                        source_assign=assign)

    @staticmethod
    def _round_robin_splits(parts: List[int],
                            n_actors: int) -> List[List[int]]:
        return [[p for j, p in enumerate(parts)
                 if j % n_actors == rank] for rank in range(n_actors)]

    @staticmethod
    def _split_owner_fn(assign: List[List[int]],
                        to_slots: List[int]) -> Callable:
        part_rank = {p: r for r, ps in enumerate(assign) for p in ps}

        def owner_of(_tid: int, _k: bytes, v) -> int:
            # split rows are (split_id, offset); the partition number
            # is the split id's suffix ("filelog-<topic>-<N>")
            try:
                part = int(str(v[0]).rsplit("-", 1)[1])
            except (ValueError, IndexError, TypeError):
                part = 0
            return to_slots[part_rank.get(part, 0)]
        return owner_of

    async def _guarded_rescale(self, job: JobDeployment, fi: int,
                               to_slots: List[int],
                               owner_of: Callable,
                               source_assign) -> None:
        """The guarded-rescale protocol shared by the vnode and
        split paths: stop the world → route state (copy-at-
        destination FIRST, tombstone second, so no crash point ever
        destroys the only copy of a row) → redeploy the cohort. ANY
        failure past the stop barrier unwinds from the in-memory moved
        log — rows restored at their source, destination copies
        tombstoned, prior topology redeployed — and records the
        rollback in rw_recovery. A rollback that itself fails leaves a
        repair marker the next recovery consumes (re-routing the
        fragment's state to the recorded placements)."""
        frag = job.graph.fragments[fi]
        old_slots = [s for _a, s in job.placements[fi]]
        old_assign = job.split_assignments.get(fi)
        # the rescale cohort is EVERY deployed job, not just the
        # rescaled job's barrier domain: the handoff's worker-side
        # seal fences the whole per-worker store, and a live job in
        # ANY domain would have its next buffered flush rejected under
        # that fence (write at epoch ≤ sealed). Stop-the-world is the
        # scale.rs-parity mechanism; the stall is bounded and recorded
        # (the autoscaler ledger's duration).
        cohort = list(self.jobs.values())
        moved_log: List[tuple] = []
        phase = "stop"
        try:
            await self._stop_and_align_all()
            phase = "handoff"
            self._fire_rescale_hook("handoff")
            fail_point("rescale.handoff")
            handoff_max = await self._route_fragment_state(
                frag, owner_of, sorted(set(old_slots) | set(to_slots)),
                moved_log)
            if handoff_max:
                self.loop.advance_epoch_to(handoff_max)
            phase = "redeploy"
            if source_assign is not None:
                job.split_assignments[fi] = source_assign
            frag.parallelism = len(to_slots)
            self._fire_rescale_hook("redeploy")
            fail_point("rescale.redeploy")
            await self._redeploy_with_fresh_actors(job, {fi: to_slots})
            for j in cohort:
                if j is not job:
                    # stopped-with-the-world siblings come back too
                    await self._redeploy_with_fresh_actors(j, {})
        except BaseException as exc:  # noqa: BLE001 — unwind + rethrow
            await self._rollback_rescale(
                job, fi, old_slots, old_assign,
                source_assign is not None, cohort, moved_log,
                phase, exc)

    async def _route_fragment_state(self, frag: Fragment,
                                    owner_of: Callable,
                                    scan_slots: List[int],
                                    moved_log: List[tuple],
                                    min_epoch: Optional[int] = None
                                    ) -> int:
        """Move every state row of ``frag``'s tables to its owner slot
        (``owner_of(tid, key, row)``). Destination copies ingest
        BEFORE source tombstones: at any interruption point every row
        still exists in at least one namespace, which is what makes
        both the rollback and the post-recovery repair pass sound.
        Appends (tid, src, dst, key, row) per moved row to
        ``moved_log``; returns the highest handoff epoch."""
        if min_epoch is None:
            min_epoch = self.loop.frontier_epoch()
        handoff_max = 0
        for tid in _fragment_table_ids(frag):
            slices: Dict[int, list] = {}
            removals: Dict[int, list] = {}
            for slot in scan_slots:
                if self.clients[slot] is None:
                    continue
                for k, v in await self.clients[slot].scan_table(tid):
                    dst = owner_of(tid, k, v)
                    if dst != slot:
                        slices.setdefault(dst, []).append((k, v))
                        removals.setdefault(slot, []).append(k)
                        moved_log.append((tid, slot, dst, k, v))
            for dst, rows in slices.items():
                r = await self.clients[dst].ingest_table(
                    tid, rows, min_epoch=max(handoff_max, min_epoch))
                handoff_max = max(handoff_max, int(r["epoch"]))
            for slot, keys in removals.items():
                r = await self.clients[slot].ingest_table(
                    tid, [(k, None) for k in keys],
                    min_epoch=max(handoff_max, min_epoch))
                handoff_max = max(handoff_max, int(r["epoch"]))
        return handoff_max

    async def _reverse_handoff(self, moved_log: List[tuple]) -> int:
        """Undo a (possibly partial) handoff from its in-memory moved
        log: restore each moved row at its source slot FIRST, then
        tombstone the destination copy — idempotent at any
        interruption point of the forward pass."""
        min_epoch = self.loop.frontier_epoch()
        handoff_max = 0
        by_src: Dict[tuple, list] = {}
        by_dst: Dict[tuple, list] = {}
        for tid, src, dst, k, v in moved_log:
            by_src.setdefault((src, tid), []).append((k, v))
            by_dst.setdefault((dst, tid), []).append((k, None))
        for (slot, tid), rows in by_src.items():
            r = await self.clients[slot].ingest_table(
                tid, rows, min_epoch=max(handoff_max, min_epoch))
            handoff_max = max(handoff_max, int(r["epoch"]))
        for (slot, tid), rows in by_dst.items():
            r = await self.clients[slot].ingest_table(
                tid, rows, min_epoch=max(handoff_max, min_epoch))
            handoff_max = max(handoff_max, int(r["epoch"]))
        return handoff_max

    async def _rollback_rescale(self, job: JobDeployment, fi: int,
                                old_slots: List[int], old_assign,
                                is_source: bool, cohort,
                                moved_log: List[tuple], phase: str,
                                exc: BaseException) -> None:
        """Unwind a failed rescale to the prior topology, record the
        event in rw_recovery, and raise RescaleError. Failures at the
        ``stop`` phase changed nothing (but the domain's health is
        unknown — a wedged stop barrier needs the supervisor), so only
        the later phases unwind state."""
        name = job.name
        floor = self.store.committed_epoch()
        t0 = time.monotonic()
        rolled = False
        detail = f"phase={phase}: {exc!r}"[:160]
        if phase in ("handoff", "redeploy"):
            # bookkeeping FIRST: whatever recovery runs next must
            # route state and deploy against the PRIOR topology
            if is_source:
                if old_assign is not None:
                    job.split_assignments[fi] = old_assign
                else:
                    job.split_assignments.pop(fi, None)
            job.graph.fragments[fi].parallelism = len(old_slots)
            try:
                handoff_max = await self._reverse_handoff(moved_log)
                if handoff_max:
                    self.loop.advance_epoch_to(handoff_max)
                await self._redeploy_with_fresh_actors(
                    job, {fi: old_slots})
                for j in cohort:
                    if j is not job:
                        await self._redeploy_with_fresh_actors(j, {})
                rolled = True
            except BaseException as rexc:  # noqa: BLE001
                detail += f"; rollback failed: {rexc!r}"[:100]
                # repair marker: state may straddle namespaces — the
                # next recovery re-routes it to the recorded prior
                # placements before redeploying
                self._pending_repair[(name, fi)] = \
                    "source" if is_source else "vnode"
                job.placements[fi] = [(self._fresh_actor(), s)
                                      for s in old_slots]
        self.supervisor.record(
            CAUSE_RESCALE_FAILED, ACTION_ROLLBACK,
            tuple(sorted(set(old_slots))), floor,
            time.monotonic() - t0, rolled, 1,
            detail=f"{name}/f{fi} {detail}")
        if rolled:
            tail = " (rolled back to the prior parallelism)"
        elif phase == "stop":
            tail = " (before any change; domain health unknown)"
        else:
            tail = " (rollback FAILED — the next recovery repairs " \
                   "state placement)"
        raise RescaleError(
            f"rescale of {name!r} fragment {fi} failed during "
            f"{phase}{tail}: {exc!r}", phase, rolled) from exc

    async def _run_pending_repairs(self) -> None:
        """Post-recovery repair pass for rescales whose rollback
        failed: re-route each marked fragment's state to the CURRENT
        recorded placements (dst-first, so the pass is idempotent and
        crash-safe itself), then clear the marker."""
        from risingwave_tpu.common.hash import VnodeMapping
        for (name, fi), kind in list(self._pending_repair.items()):
            job = self.jobs.get(name)
            if job is None or fi >= len(job.placements):
                self._pending_repair.pop((name, fi), None)
                continue
            frag = job.graph.fragments[fi]
            slots = [s for _a, s in job.placements[fi]]
            if kind == "source":
                assign = job.split_assignments.get(
                    fi, self._round_robin_splits(
                        self._source_partitions(frag), len(slots)))
                owner_of = self._split_owner_fn(assign, slots)
            else:
                mapping = VnodeMapping.new_uniform(len(slots))

                def owner_of(_tid, k, _v, _m=mapping, _s=slots):
                    return _s[_m.owner_of(
                        int.from_bytes(k[:2], "big"))]
            handoff_max = await self._route_fragment_state(
                frag, owner_of, list(range(self.n)), [],
                min_epoch=self.store.committed_epoch())
            if handoff_max:
                self.loop.advance_epoch_to(handoff_max)
            self._pending_repair.pop((name, fi), None)

    # source fragments rescalable by split reassignment: root
    # fragments whose only durable state is the source's split/offset
    # table (the filelog contract) — everything else in the chain is
    # stateless
    _SOURCE_RESCALABLE_OPS = frozenset({"source", "project", "filter",
                                        "coalesce", "row_id_gen",
                                        "sink"})

    def _source_rescalable(self, frag: Fragment) -> bool:
        if frag.inputs:
            return False
        src = None
        for n in frag.nodes:
            if n["op"] not in self._SOURCE_RESCALABLE_OPS:
                return False
            if n["op"] == "source":
                src = n
        if src is None or src.get("split_table_id") is None:
            return False
        conn = src.get("connector") or {}
        if str(conn.get("connector", "")).lower() != "filelog":
            return False
        if str(conn.get("segmented", "")).lower() in ("true", "1"):
            return False
        return bool(conn.get("topic"))

    def _source_partitions(self, frag: Fragment) -> List[int]:
        """The topic's current partition set (enumerated from the log
        directory — the coordinator shares the filesystem with the
        workers). Falls back to the single configured partition when
        the directory lists none yet."""
        from risingwave_tpu.connectors.filelog import FileLogEnumerator
        src = next(n for n in frag.nodes if n["op"] == "source")
        conn = src["connector"]
        splits = FileLogEnumerator(conn["path"],
                                   conn["topic"]).list_splits()
        parts = sorted(int(s.split_id.rsplit("-", 1)[1])
                       for s in splits)
        return parts or [int(conn.get("partition", 0))]

    async def move_fragment(self, name: str, frag_idx: int,
                            to_slots: List[int]) -> None:
        """Move one fragment's actors to new worker slots at a stopped
        barrier, shipping its state tables between namespaces (the
        reference's shared storage makes this step implicit; per-slot
        namespaces make it an explicit scan+ingest handoff)."""
        job = self.jobs[name]
        frag = job.graph.fragments[frag_idx]
        if len(to_slots) != len(job.placements[frag_idx]):
            raise ValueError("move keeps the actor count; use "
                             "rescale_fragment for true rescale")
        old = job.placements[frag_idx]
        if len(old) != 1:
            # a whole-namespace scan mixes sibling actors' slices; the
            # vnode-sliced path handles multi-actor fragments
            return await self.rescale_fragment(name, frag_idx,
                                               to_slots)
        if [s for _a, s in old] == list(to_slots):
            return
        # whole-table move through the same guarded protocol the
        # rescales use (dst-first handoff + rollback on failure):
        # every row of the fragment's tables is owned by the one
        # destination slot
        dst = int(to_slots[0])

        def owner_of(_tid: int, _k: bytes, _v) -> int:
            return dst

        with self._topology_change(
                f"move {name}/f{frag_idx} -> slot {dst}"):
            await self._guarded_rescale(job, frag_idx, list(to_slots),
                                        owner_of, source_assign=None)

    async def _stop_and_align_all(self) -> None:
        """Stop EVERY deployed job at one aligned barrier and push the
        commit decision to every worker — the guarded rescale's stop
        phase. Cluster-wide (not just the rescaled job's domain): the
        handoff's worker-side seal fences the whole per-worker store,
        and a still-running job in ANY domain would have its next
        buffered flush rejected under that fence. Stopped jobs have
        nothing pending, so the fence is safe; everyone redeploys with
        the rescaled cohort."""
        await self.loop.inject_and_collect(
            force_checkpoint=True,
            mutation=StopMutation(
                self._stop_set(*self.jobs.values())))
        floor = self.store.committed_epoch()
        for c in self.clients:
            await c.call({"cmd": "recover_store", "epoch": floor})

    async def _redeploy_with_fresh_actors(
            self, job: JobDeployment,
            replaced: Dict[int, List[int]]) -> None:
        """Redeploy every fragment with fresh actor ids (the stopped
        ones are gone from the workers); `replaced` overrides slot
        lists per fragment index."""
        for fi in range(len(job.graph.fragments)):
            slots = replaced.get(
                fi, [s for _a, s in job.placements[fi]])
            job.placements[fi] = [(self._fresh_actor(), s)
                                  for s in slots]
        await self._deploy_job(job)
        if self._plane is not None:
            # the domain's actor filter must name the FRESH actor ids
            # or the redeployed fragments never see another barrier
            self._plane.remove_job(job.name)
            dom = self._plane.assign_job(job.name,
                                         set(job.domain_keys),
                                         sender_ids=(),
                                         expected_ids=(),
                                         actor_ids=job.actor_ids())
            # the handoff ingests committed worker-side ABOVE the
            # coordinator floor — the fresh domain's first barrier
            # must read at/above them, not at the stale floor
            self._plane.advance_domain_to(
                dom, self._plane.last_allocated)
            await self._rewire_domains()

    def _fresh_actor(self) -> int:
        aid = self._next_actor
        self._next_actor += 1
        return aid


def _fragment_table_ids(frag: Fragment) -> List[int]:
    """Every state-table id a fragment's nodes own (the state that must
    move with it)."""
    out: List[int] = []
    for n in frag.nodes:
        op = n["op"]
        if op == "source" and n.get("split_table_id") is not None:
            out.append(int(n["split_table_id"]))
        elif op == "hash_agg":
            out.append(int(n["table_id"]))
            out += [int(v) for v in
                    (n.get("dedup_table_ids") or {}).values()]
            out += [int(v) for v in
                    (n.get("minput_table_ids") or {}).values()]
        elif op == "hash_join":
            out += [int(n["left_table_id"]), int(n["right_table_id"])]
        elif op == "materialize":
            out.append(int(n["table_id"]))
        elif op in ("top_n", "over_window", "eowc_gate", "dedup",
                    "dynamic_filter"):
            out.append(int(n["table_id"]))
        elif op == "backfill":
            out.append(int(n["progress_table_id"]))
        elif op == "watermark_filter" and n.get("table_id") is not None:
            out.append(int(n["table_id"]))
    return out
