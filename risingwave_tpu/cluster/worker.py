"""Worker node: actors + exchange server + control channel.

Reference parity: the compute node (src/compute/src/server.rs:85) —
hosts actors, serves its outputs over the exchange (stream/remote.py),
executes barrier injections from the coordinator and reports collection
(stream_service.proto InjectBarrier/BarrierComplete), owns a local
state-store namespace whose checkpoints commit at the SAME epochs the
coordinator drives, so a recovering cluster resumes consistently from
the coordinator's committed epoch.

Fragments deploy by SHIPPED PLAN IR only (``deploy_plan`` — the
stream_plan.proto analog; stream/plan_ir.py nodes build into executors
here, so any expressible plan runs on any worker). Each deployed actor
may fan out through a dispatcher spec — simple / broadcast / hash with
an explicit vnode→downstream-actor mapping (dispatch.rs:582; the
coordinator's scheduler computes the mapping like
meta/stream/stream_graph/schedule.rs:195-251 assigns vnode bitmaps).

The batch data plane for distributed SELECT: ``scan_table`` streams a
table's committed rows back over control (ExchangeService.GetData +
RowSeqScan over the local store, task_service.proto:114), and
``ingest_table`` bulk-loads rows at a fresh epoch (the state-migration
half of a cross-worker reschedule).

Run as a process:  python -m risingwave_tpu.cluster.worker --store DIR
(prints one JSON line {"control_port": N, "exchange_port": N}).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Dict, List, Optional

from risingwave_tpu.cluster.coordinator import (
    CONTROL_LINE_LIMIT, CONTROL_PAGE_BYTES,
)
from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.stream.actor import Actor, LocalBarrierManager
from risingwave_tpu.stream.dispatch import (
    BroadcastDispatcher, HashDispatcher, Output, SimpleDispatcher,
)
from risingwave_tpu.stream.exchange import channel_for_test
from risingwave_tpu.stream.message import (
    Barrier, BarrierKind, PauseMutation, ResumeMutation, StopMutation,
)
from risingwave_tpu.stream.remote import ExchangeServer


class WorkerServer:
    """One worker process: control + exchange + actors + local store."""

    def __init__(self, store):
        self.store = store
        self.local = LocalBarrierManager()
        self.exchange = ExchangeServer()
        self.actors: Dict[int, Actor] = {}
        self.tasks: Dict[int, asyncio.Task] = {}
        self._control: Optional[asyncio.AbstractServer] = None
        self._stopping = asyncio.Event()
        # per-domain stamp of the last non-mutation inject handled
        # here: successive stamps bound the barrier interval the
        # worker-side bottleneck walk observes (the coordinator hosts
        # no monitored actors on a distributed session — the walker
        # must run where the chains run)
        self._domain_stamp: Dict[str, float] = {}

    async def serve(self, host: str = "127.0.0.1") -> dict:
        await self.exchange.serve(host, 0)
        self._control = await asyncio.start_server(
            self._handle_control, host, 0, limit=CONTROL_LINE_LIMIT)
        return {"control_port":
                self._control.sockets[0].getsockname()[1],
                "exchange_port": self.exchange.port}

    # -- control protocol: one JSON object per line ----------------------
    async def _handle_control(self, reader: asyncio.StreamReader,
                              writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                line = await reader.readline()
                if not line:
                    return
                cmd = json.loads(line)
                try:
                    reply = await self._dispatch(cmd)
                except BaseException as e:  # noqa: BLE001 — report,
                    # don't kill the control channel: the coordinator
                    # needs the REAL failure, not a closed socket
                    reply = {"ok": False, "error": repr(e)}
                writer.write((json.dumps(reply) + "\n").encode())
                await writer.drain()
                if cmd.get("cmd") == "stop":
                    self._stopping.set()
                    return
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        finally:
            writer.close()

    async def _dispatch(self, cmd: dict) -> dict:
        verb = cmd.get("cmd")
        # chaos seam: delay (sleep spec) or fail (raise spec) one
        # control RPC by verb — how the harness injects an RPC timeout
        # without killing the worker
        from risingwave_tpu.utils.failpoint import fail_point
        fail_point(f"worker.rpc.{verb}")
        if verb == "deploy_plan":
            return await self._deploy_plan(cmd)
        if verb == "inject":
            return await self._inject(cmd)
        if verb == "scan_table":
            return self._scan_table(cmd)
        if verb == "ingest_table":
            return self._ingest_table(cmd)
        if verb == "seal_sync":
            # cross-domain aligned checkpoint (ISSUE 13): the
            # coordinator pushes the write floor once EVERY domain of
            # the round collected — seal + stage-sync everything at or
            # below it in one absolute-state (idempotent) step; the
            # commit decision still pipelines on the next barrier's
            # "committed" field
            epoch = int(cmd["epoch"])
            sealed = max(self.store.committed_epoch(),
                         getattr(self.store, "_sealed_epoch", 0))
            if epoch > sealed:
                self.store.seal_epoch(epoch, True)
            self.store.sync(epoch)
            return {"ok": True,
                    "committed": self.store.committed_epoch()}
        if verb == "recover_store":
            # recovery handshake: adopt everything the coordinator
            # committed, discard the half-epoch a crash may have left
            # staged (recovery.rs: the committed epoch is the truth)
            epoch = int(cmd["epoch"])
            dropped = 0
            if getattr(self.store, "two_phase", False):
                dropped = self.store.discard_staged_above(epoch)
                self.store.commit_through(epoch)
            return {"ok": True, "dropped": dropped,
                    "committed": self.store.committed_epoch()}
        if verb == "reset":
            return await self._reset()
        if verb == "arm_failpoints":
            # live chaos injection: arm/disarm JSON-able dict specs in
            # THIS process (the env path only covers boot time)
            from risingwave_tpu.utils.failpoint import arm_specs
            return {"ok": True,
                    "armed": arm_specs(cmd.get("points") or {})}
        if verb == "metrics":
            # this process's Prometheus exposition — how tests and
            # tooling observe worker-side absorption counters
            # (object_store_retry_total lives here, not on the
            # coordinator)
            from risingwave_tpu.utils.metrics import GLOBAL
            return {"ok": True, "text": GLOBAL.render()}
        if verb == "drain_trace":
            # pop this process's recorded spans for the coordinator to
            # merge (tagged with the worker slot on the other side)
            from risingwave_tpu.utils.spans import EPOCH_TRACER
            return {"ok": True, "spans": EPOCH_TRACER.drain_dicts()}
        if verb == "drain_ledger":
            # pop this process's open phase-ledger accumulators —
            # workers never seal (the coordinator owns the barrier
            # interval); the other side merges them into its records
            from risingwave_tpu.utils.ledger import LEDGER
            return {"ok": True, "epochs": LEDGER.drain_dicts()}
        if verb == "signals":
            # autoscaler signal snapshot (ISSUE 15/16): this process's
            # utilization tricolor + bottleneck-walker state, plus the
            # attribution surfaces (state topology + hot-key sketches
            # as snapshots, per-MV cost books as a true drain — the
            # coordinator owns the merged totals), merged
            # coordinator-side by Cluster.drain_signals
            from risingwave_tpu.state.topology import TOPOLOGY
            from risingwave_tpu.stream.bottleneck import BOTTLENECKS
            from risingwave_tpu.stream.costs import COSTS
            from risingwave_tpu.stream.hotkeys import HOTKEYS
            from risingwave_tpu.stream.monitor import UTILIZATION
            out = {"ok": True,
                   "utilization": UTILIZATION.rows(),
                   "bottlenecks": BOTTLENECKS.rows(),
                   "mv_costs": COSTS.drain_dict()}
            if not cmd.get("light"):
                # the per-vnode topology snapshot walks the per-key
                # map — serve it only to query-driven drains, never
                # the per-tick heartbeat (light=True)
                out["topology"] = TOPOLOGY.drain_rows()
                out["hot_keys"] = HOTKEYS.drain_rows()
            return out
        if verb == "drain_freshness":
            # pop this process's raw freshness parts (ingest hwms,
            # epoch frontiers, visibility events) — the coordinator
            # joins source and materialize fragments that landed on
            # different workers into one per-MV lag series
            from risingwave_tpu.stream.freshness import FRESHNESS
            return {"ok": True, "parts": FRESHNESS.drain_dict()}
        if verb == "awaits":
            # wedge diagnostics: where every registered coroutine in
            # THIS process is parked (the PR-1 AwaitRegistry) plus the
            # local barrier manager's open epochs — how a coordinator
            # names the actor holding a barrier open on a live worker
            # instead of guessing from the outside
            from risingwave_tpu.utils.trace import GLOBAL_AWAITS
            local = self.local
            return {"ok": True, "text": GLOBAL_AWAITS.dump(),
                    "actors": sorted(self.actors),
                    "open_epochs": {
                        f"{e:#x}": sorted(
                            local._collected.get(e, ()))
                        for e in getattr(local, "_complete", {})
                        if not local._complete[e].is_set()}}
        if verb == "set_compaction":
            # absolute-state toggle: inline (commits compact in place)
            # vs dedicated (commits never compact; version deltas land
            # via compact_apply below)
            mode = str(cmd.get("mode", "inline"))
            self.store.compaction_mode = mode
            return {"ok": True, "mode": mode}
        if verb == "level_snapshot":
            # pure read: per-level topology for the CompactionManager's
            # pickers (L0 run count, sizes, tombstone density) + the
            # ids frozen under in-flight tasks
            return {"ok": True, "snapshot": self.store.level_snapshot()}
        if verb == "compact_reserve":
            # freeze a task's inputs + burn it a durable output-id
            # block; a ValueError (inputs gone / already reserved) is
            # an expected conflict the manager skips, not a fault
            grant = self.store.reserve_task(
                [int(i) for i in cmd["inputs"]],
                int(cmd.get("id_block", 16)))
            return {"ok": True, "grant": grant}
        if verb == "compact_apply":
            # compare-and-commit version delta: swap exactly the
            # reserved inputs for the compactor's outputs
            r = self.store.apply_version_delta(
                [int(i) for i in cmd["inputs"]], cmd["outputs"])
            return {"ok": True, **r}
        if verb == "compact_abort":
            self.store.abort_task(
                [int(i) for i in cmd["inputs"]],
                [int(i) for i in cmd.get("outputs") or []])
            return {"ok": True}
        if verb == "ping":
            # heartbeat probe (cluster.rs heartbeat RPC): liveness +
            # a cheap resource summary for the membership table (actor
            # failures ride along so a dead-epoch diagnosis can name
            # the culprit without waiting for the next inject)
            return {"ok": True, "info": {
                "actors": len(self.actors),
                "failures": {str(aid): repr(a.failure)
                             for aid, a in self.actors.items()
                             if a.failure is not None}}}
        if verb == "stop":
            return {"ok": True}
        return {"ok": False, "error": f"unknown cmd {verb!r}"}

    async def _reset(self) -> dict:
        """Supervised-recovery rung 2 for a LIVE worker: drop every
        actor in place (no stop barriers — the barrier plane is the
        thing that failed), release the exchange plane, and present a
        fresh LocalBarrierManager. The process — and its warm jit
        caches — survives, which is exactly what makes respawn cheaper
        than full recovery. Staged store state is NOT touched here:
        the coordinator's ``recover_store`` handshake that follows is
        the single source of truth for what rolls back."""
        n = len(self.actors)
        for t in self.tasks.values():
            t.cancel()
        if self.tasks:
            await asyncio.gather(*self.tasks.values(),
                                 return_exceptions=True)
        self.actors.clear()
        self.tasks.clear()
        self._domain_stamp.clear()
        old = self.local
        self.local = LocalBarrierManager()
        # wake any control handler stuck awaiting an epoch on the old
        # plane (e.g. a wedged inject on a torn connection): resolving
        # its await with the failure beats leaking the coroutine
        old.notify_failure(-1, RuntimeError(
            "worker reset (supervised recovery)"))
        self.exchange.reset_edges()
        return {"ok": True, "dropped_actors": n}

    # -- exchange fan-out -------------------------------------------------
    def _make_dispatchers(self, actor_id: int, outputs: List[int],
                          dispatch: Optional[dict]) -> list:
        """Downstream edges on THIS worker's exchange server; remote
        peers connect in and pull (exchange_service.rs). The spec picks
        the dispatcher (dispatch.rs:343): simple needs exactly one
        output; hash carries dist keys + an explicit vnode mapping."""
        outs = [Output(d, self.exchange.register_edge(actor_id, d))
                for d in outputs]
        if not outs:
            return []
        spec = dispatch or {"type": "simple"}
        typ = spec.get("type", "simple")
        if typ == "simple":
            if len(outs) != 1:
                raise ValueError(
                    f"simple dispatch needs 1 output, got {len(outs)}")
            return [SimpleDispatcher(outs[0])]
        if typ == "broadcast":
            return [BroadcastDispatcher(outs)]
        if typ == "hash":
            from risingwave_tpu.common.hash import VnodeMapping
            import numpy as np
            keys = [int(i) for i in spec["keys"]]
            raw = spec.get("mapping")
            mapping = (VnodeMapping(np.asarray(raw, dtype=np.int32))
                       if raw is not None else None)
            return [HashDispatcher(outs, keys, mapping)]
        raise ValueError(f"unknown dispatch type {typ!r}")

    def _spawn_actor(self, actor_id: int, outputs: List[int],
                     dispatch: Optional[dict], consumer,
                     fragment: str = "") -> dict:
        """Shared deploy tail: exchange edges + actor + spawn.
        outputs=[]: terminal fragment (e.g. a materialize) — no
        exchange edge; an edge nobody consumes would buffer chunks
        until the credit window blocks the actor."""
        from risingwave_tpu.stream.monitor import install_monitoring
        dispatchers = self._make_dispatchers(actor_id, outputs, dispatch)
        # worker-side instrumentation feeds THIS process's registry
        # (a worker-local scrape); the coordinator's rw_actor_metrics
        # only sees coordinator-process actors — cross-process metric
        # aggregation is future work
        consumer = install_monitoring(consumer, fragment=fragment,
                                      actor_id=actor_id)
        actor = Actor(actor_id, consumer, dispatchers=dispatchers,
                      barrier_manager=self.local, fragment=fragment)
        self.actors[actor_id] = actor
        self.local.set_expected_actors(list(self.actors))
        self.tasks[actor_id] = actor.spawn()
        return {"ok": True, "actor_id": actor_id}

    async def _deploy_plan(self, cmd: dict) -> dict:
        """Materialize a SHIPPED plan-IR fragment (from_proto/ analog):
        the coordinator sends the node tree over the control channel
        and this worker builds + spawns it — no per-query fragment
        registry, any plan the IR expresses deploys anywhere.

        The fragment's actor id comes from the PLAN's source node (one
        source of truth — a divergent params id would register the
        barrier sender under a key the stop path never drops). A build
        failure after sender registration unregisters it: an undrained
        barrier channel would wedge every later injection."""
        from risingwave_tpu.stream.plan_ir import build_fragment

        plan = cmd["plan"]
        params = cmd["params"]
        sources = [n for n in plan if n.get("op") == "source"]
        remote_fed = any(n.get("op") == "remote_input" for n in plan)
        if len(sources) > 1 or (not sources and not remote_fed):
            return {"ok": False,
                    "error": "plan must have exactly one source node "
                             "or be fed by remote_input nodes"}
        try:
            # validate EVERYTHING that could fail before building:
            # build_fragment registers the source's barrier sender,
            # and a post-build failure would leave it undrained.
            # Terminal fragments (no exchange edge) must say so with
            # an EXPLICIT outputs=[] / down_actor=None — a merely
            # omitted key is a wiring typo that would otherwise deploy
            # ok and then starve the downstream actor silently
            if "outputs" in params:
                outputs = [int(o) for o in params["outputs"]]
            else:
                raw_down = params["down_actor"]
                outputs = [] if raw_down is None else [int(raw_down)]
            dispatch = params.get("dispatch")
            if dispatch is not None and dispatch.get("type") == "hash":
                _ = [int(i) for i in dispatch["keys"]]
        except (KeyError, TypeError, ValueError) as e:
            return {"ok": False, "error": f"bad output spec: {e}"}
        sent = params.get("actor_id")
        if sources:
            actor_id = int(sources[0]["actor_id"])
            if sent is not None and int(sent) != actor_id:
                # the PLAN is the source of truth; silently deploying
                # under a different id than the caller thinks would
                # wedge its stop/tracking path with no diagnostic
                return {"ok": False,
                        "error": f"params actor_id {sent} != plan "
                                 f"source actor_id {actor_id}"}
        elif sent is None:
            return {"ok": False,
                    "error": "a remote-fed plan needs params "
                             "actor_id (no source node carries one)"}
        else:
            actor_id = int(sent)
        if actor_id in self.actors:
            return {"ok": False,
                    "error": f"actor {actor_id} already deployed"}
        try:
            consumer = build_fragment(plan, self.store, self.local,
                                      channel_for_test,
                                      actor_id=actor_id)[1]
            return self._spawn_actor(
                actor_id, outputs, dispatch, consumer,
                fragment=str(params.get("job") or f"actor-{actor_id}"))
        except BaseException as e:     # noqa: BLE001 — report upstream
            self.local.drop_actor(actor_id)
            return {"ok": False, "error": f"plan build failed: {e}"}

    _PAGE_BYTES = CONTROL_PAGE_BYTES

    # -- batch data plane -------------------------------------------------
    def _scan_table(self, cmd: dict) -> dict:
        """Stream one table's committed rows back to the coordinator
        (RowSeqScan over the local store + GetData, collapsed to the
        control channel). Rows are value-codec encoded — the
        coordinator holds the schema; this side needs none. PAGED:
        ``after`` (hex key, exclusive) resumes a scan and the reply
        stops past a byte budget with ``done=False`` — one giant
        table must not overflow the JSON-line framing."""
        from risingwave_tpu.storage.value_codec import encode_row

        tid = int(cmd["table_id"])
        epoch = cmd.get("epoch")
        epoch = (self.store.committed_epoch() if epoch is None
                 else int(epoch))
        after = (bytes.fromhex(cmd["after"])
                 if cmd.get("after") else None)
        rows = []
        nbytes = 0
        done = True
        # resume at the store level (start is inclusive; after+\x00 is
        # the exclusive successor) so a P-page scan stays O(N), not
        # O(P*N); the guard below keeps correctness if a store ever
        # ignores start
        start = None if after is None else after + b"\x00"
        for k, v in self.store.iter(tid, epoch, start=start):
            if after is not None and k <= after:
                continue
            kx, vx = k.hex(), encode_row(tuple(v)).hex()
            rows.append([kx, vx])
            nbytes += len(kx) + len(vx)
            if nbytes >= self._PAGE_BYTES:
                done = False
                break
        return {"ok": True, "epoch": epoch, "rows": rows,
                "done": done}

    def _ingest_table(self, cmd: dict) -> dict:
        """Bulk-load rows into a table at a fresh sealed+synced epoch —
        the receiving half of cross-worker state migration (the
        reference moves no state because storage is shared; with
        per-worker namespaces the reschedule barrier ships it)."""
        from risingwave_tpu.storage.value_codec import decode_row

        tid = int(cmd["table_id"])
        batch = [(bytes.fromhex(k),
                  None if r is None else decode_row(bytes.fromhex(r)))
                 for k, r in cmd["rows"]]
        # min_epoch: the coordinator's last-injected epoch — sealing
        # at or below an in-flight barrier's curr would make OTHER
        # jobs' buffered flushes at that epoch fail the sealed guard
        epoch = max(self.store.committed_epoch(),
                    getattr(self.store, "_sealed_epoch", 0),
                    int(cmd.get("min_epoch") or 0)) + 1
        self.store.ingest_batch(tid, batch, epoch)
        self.store.seal_epoch(epoch, True)
        self.store.sync(epoch)
        if getattr(self.store, "two_phase", False):
            # a coordinator-driven bulk load IS the commit decision:
            # leaving it staged would let a recovery in the next two
            # barriers discard freshly-migrated state
            self.store.commit_through(epoch)
        return {"ok": True, "rows": len(batch), "epoch": epoch}

    async def _inject(self, cmd: dict) -> dict:
        pair = EpochPair(Epoch(int(cmd["curr"])),
                         Epoch(int(cmd["prev"])))
        kind = BarrierKind(cmd["kind"])
        mutation = None
        m = cmd.get("mutation")
        if m:
            if m["type"] == "stop":
                mutation = StopMutation(frozenset(m["actors"]))
            elif m["type"] == "pause":
                mutation = PauseMutation()
            elif m["type"] == "resume":
                mutation = ResumeMutation()
        barrier = Barrier(pair, kind, mutation)
        from risingwave_tpu.utils import spans as _spans
        _spans.set_current_epoch(pair.curr.value)
        # worker-side inject marker, parented to the coordinator's
        # inject span when the injection shipped one: every span
        # this process records for the epoch links under it
        parent = (cmd.get("trace") or {}).get("span")
        wroot = _spans.EPOCH_TRACER.record(
            "barrier.inject.worker", "barrier",
            epoch=pair.curr.value, parent=parent,
            kind=kind.value)
        _spans.EPOCH_TRACER.set_root(pair.curr.value, wroot)
        actors = cmd.get("actors")
        if "seal" in cmd:
            # domain-protocol marker: a coordinator-side domain merge
            # can re-anchor live chains on THIS worker monotonely —
            # commit() must accept prev > curr from here on
            from risingwave_tpu.state.state_table import (
                allow_monotone_reanchor,
            )
            allow_monotone_reanchor(True)
        if actors is None:
            await self.local.send_barrier(barrier)
        else:
            # barrier-domain frame (ISSUE 13): the barrier flows only
            # through this domain's actors on this worker; sibling
            # domains' actors neither receive nor block it. An empty
            # intersection collects trivially — the worker simply
            # hosts none of the domain's fragments.
            acts = {int(a) for a in actors}
            await self.local.send_barrier(
                barrier, sender_ids=sorted(acts),
                expected=[a for a in self.actors if a in acts])
        collected = await self.local.await_epoch_complete(
            pair.curr.value)
        sealed = max(self.store.committed_epoch(),
                     getattr(self.store, "_sealed_epoch", 0))
        if "seal" in cmd:
            # domain-plane protocol: per-domain prevs interleave
            # globally, so the worker fences only to the cross-domain
            # write floor the coordinator computed; durability arrives
            # via the aligned seal_sync push, never inline here
            s = int(cmd.get("seal") or 0)
            if s > sealed:
                self.store.seal_epoch(s, kind.is_checkpoint)
        elif pair.prev.value > sealed:
            # legacy global-lockstep protocol: seal+stage the epoch
            # that ENDED. The guard makes re-injection after recovery
            # a no-op rather than an assertion failure.
            self.store.seal_epoch(pair.prev.value, kind.is_checkpoint)
            if kind.is_checkpoint:
                self.store.sync(pair.prev.value)
        if getattr(self.store, "two_phase", False):
            # the coordinator's commit decision rides on this barrier
            # (HummockManager::commit_epoch pipelined one barrier
            # behind). Absent — a legacy driver — self-commit through
            # the epoch just SYNCED, and only on checkpoint barriers:
            # committing a merely-sealed epoch would write a durable
            # version that claims data still sitting in the imms
            committed = cmd.get("committed")
            if committed is not None:
                self.store.commit_through(int(committed))
            elif kind.is_checkpoint:
                self.store.commit_through(pair.prev.value)
        # worker-side bottleneck walk (ISSUE 15): the tricolor rows
        # this barrier just published decompose THIS process's chains;
        # the inject frame's domain name + actor filter scope the walk,
        # and successive inject stamps bound the interval. Mutation
        # barriers (deploy/stop/reschedule) do topology work, not
        # epoch work — they neither tick nor reset the streaks.
        dom = cmd.get("domain")
        if dom is not None:
            now = time.monotonic()
            last = self._domain_stamp.get(dom)
            self._domain_stamp[dom] = now
            if mutation is None and last is not None:
                from risingwave_tpu.stream.bottleneck import BOTTLENECKS
                BOTTLENECKS.observe(
                    domain=dom, epoch=pair.curr.value,
                    interval_s=now - last,
                    actors={int(a) for a in actors}
                    if actors is not None else None)
        # stopped actors are gone after this barrier
        if isinstance(mutation, StopMutation):
            for aid in list(self.actors):
                if aid in mutation.actors:
                    t = self.tasks.pop(aid, None)
                    if t is not None:
                        await t
                    self.actors.pop(aid, None)
                    self.local.drop_actor(aid)
            self.local.set_expected_actors(list(self.actors))
        for aid, a in self.actors.items():
            if a.failure is not None:
                return {"ok": False,
                        "error": f"actor {aid} ({a.fragment}): "
                                 f"{a.failure!r}"}
        return {"ok": True, "collected": collected is not None,
                "committed": pair.prev.value}

    async def run_until_stopped(self) -> None:
        await self._stopping.wait()
        await self.exchange.close()
        if self._control is not None:
            self._control.close()
            await self._control.wait_closed()


class CompactorServer:
    """Dedicated compactor role (``--role compactor``): a heartbeat-
    leased subprocess that executes compaction merges against worker
    object-store namespaces, OFF every serving path. It hosts no
    actors and owns no store of its own — each ``compact_task`` names
    the namespace directory and the frozen task; the merge runs on a
    thread so the control loop keeps answering pings mid-task.
    Compactor death mid-task surfaces as a torn control channel (or a
    lease expiry) and the manager requeues the task — the merge wrote
    only into its reserved id block, so a half-finished task leaves
    nothing a vacuum pass cannot reclaim."""

    def __init__(self) -> None:
        self._control: Optional[asyncio.AbstractServer] = None
        self._stopping = asyncio.Event()
        self._running = 0            # tasks in flight (ping visibility)
        self._done = 0

    async def serve(self, host: str = "127.0.0.1") -> dict:
        self._control = await asyncio.start_server(
            self._handle_control, host, 0, limit=CONTROL_LINE_LIMIT)
        return {"control_port":
                self._control.sockets[0].getsockname()[1],
                "exchange_port": 0}

    async def _handle_control(self, reader: asyncio.StreamReader,
                              writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                line = await reader.readline()
                if not line:
                    return
                cmd = json.loads(line)
                try:
                    reply = await self._dispatch(cmd)
                except BaseException as e:  # noqa: BLE001 — report
                    reply = {"ok": False, "error": repr(e)}
                writer.write((json.dumps(reply) + "\n").encode())
                await writer.drain()
                if cmd.get("cmd") == "stop":
                    self._stopping.set()
                    return
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        finally:
            writer.close()

    async def _dispatch(self, cmd: dict) -> dict:
        verb = cmd.get("cmd")
        from risingwave_tpu.utils.failpoint import fail_point
        fail_point(f"compactor.rpc.{verb}")
        if verb == "ping":
            return {"ok": True, "info": {"role": "compactor",
                                         "running": self._running,
                                         "done": self._done}}
        if verb == "compact_task":
            return await self._compact_task(cmd)
        if verb == "arm_failpoints":
            from risingwave_tpu.utils.failpoint import arm_specs
            return {"ok": True,
                    "armed": arm_specs(cmd.get("points") or {})}
        if verb == "metrics":
            from risingwave_tpu.utils.metrics import GLOBAL
            return {"ok": True, "text": GLOBAL.render()}
        if verb == "stop":
            return {"ok": True}
        return {"ok": False, "error": f"unknown cmd {verb!r}"}

    async def _compact_task(self, cmd: dict) -> dict:
        from risingwave_tpu.storage.compactor import execute_task
        from risingwave_tpu.storage.object_store import (
            LocalFsObjectStore, RetryingObjectStore,
        )
        store = RetryingObjectStore(LocalFsObjectStore(cmd["store"]))
        self._running += 1
        try:
            result = await asyncio.to_thread(
                execute_task, store, cmd["task"])
        finally:
            self._running -= 1
        self._done += 1
        return {"ok": True, **result}

    async def run_until_stopped(self) -> None:
        await self._stopping.wait()
        if self._control is not None:
            self._control.close()
            await self._control.wait_closed()


def main(argv=None) -> None:
    import argparse

    from risingwave_tpu.utils.jaxtools import enable_compilation_cache
    enable_compilation_cache()

    # chaos/trace tests arm sleep-spec failpoints in worker
    # subprocesses via the environment (utils/failpoint.py)
    from risingwave_tpu.utils.failpoint import arm_from_env
    arm_from_env()

    ap = argparse.ArgumentParser()
    ap.add_argument("--store", required=True,
                    help="object-store directory for this worker's "
                         "hummock namespace (compactor role: unused "
                         "default root — tasks name their namespace)")
    ap.add_argument("--role", default="worker",
                    choices=["worker", "compactor"],
                    help="worker: actors + local store; compactor: "
                         "dedicated off-path merge executor")
    args = ap.parse_args(argv)

    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import (
        LocalFsObjectStore, RetryingObjectStore,
    )

    async def amain():
        if args.role == "compactor":
            c = CompactorServer()
            ports = await c.serve()
            print(json.dumps(ports), flush=True)
            await c.run_until_stopped()
            return
        # transient-fault absorption at the bottom rung: a flaky
        # PUT/GET retries with jittered backoff inside the worker
        # before any error can fail a barrier round
        store = HummockLite(
            RetryingObjectStore(LocalFsObjectStore(args.store)),
            two_phase=True)
        w = WorkerServer(store)
        ports = await w.serve()
        print(json.dumps(ports), flush=True)
        await w.run_until_stopped()

    asyncio.run(amain())


if __name__ == "__main__":
    main()
