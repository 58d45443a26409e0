"""Coordinator side: worker control client + cross-process barriers.

Reference parity: the meta service's GlobalBarrierManager talking to
compute nodes (barrier/mod.rs:558 inject → stream_service
InjectBarrier → BarrierComplete) and GlobalStreamManager's actor
deployment (stream_manager.rs:161) — the coordinator drives its OWN
BarrierLoop and the worker participates as one more "actor": a
registered barrier sender forwards each injection over the control
channel, and the worker's completion reply collects the pseudo-actor.
Everything the single-process session does (epochs, checkpoint
frequency, in-flight window, stats) is reused unchanged.
"""

from __future__ import annotations

import asyncio
import json
import subprocess
import sys
from typing import Optional

from risingwave_tpu.stream.message import (
    Barrier, PauseMutation, ResumeMutation, StopMutation,
)
from risingwave_tpu.utils.metrics import CLUSTER as _METRICS

# control-channel line framing, BOTH ends of every worker socket
# (replies are one JSON line each; scan_table/metrics payloads
# overflow asyncio's 64KB default, surfacing as an opaque
# ValueError), and the request/reply page budget derived from it:
# pages stay comfortably under the frame even with huge rows (an
# approx_count_distinct sketch row hex-encodes to ~100KB — whole-
# table replies broke the channel at real MV sizes). One constant
# pair so the two ends can never drift apart.
CONTROL_LINE_LIMIT = 1 << 24
CONTROL_PAGE_BYTES = 4 << 20

# verbs safe to RE-SEND after a reconnect: each is a pure read or an
# absolute-state write (recover_store/arm_failpoints set a target
# state, so applying twice equals applying once). inject /
# deploy_plan / ingest_table / drain_trace are NOT here — replaying
# them changes cluster state, and their failures belong to the
# recovery supervisor, not a silent retry.
_IDEMPOTENT_VERBS = frozenset({
    "ping", "scan_table", "recover_store",
    "arm_failpoints", "metrics", "reset",
    # pure reads: the autoscaler signal snapshot (tricolor + walker)
    # and the wedge-diagnostic await dump
    "signals", "awaits",
    # absolute-state write: sealing/syncing to an epoch twice equals
    # once (the aligned-checkpoint floor push, ISSUE 13)
    "seal_sync",
    # compaction plane: mode toggle is absolute state, the level
    # snapshot is a pure read, and aborting a task twice equals once
    # (reservation release + delete-if-present). compact_reserve /
    # compact_apply / compact_task are NOT here — replaying them
    # allocates ids or commits versions.
    "set_compaction", "level_snapshot", "compact_abort",
})


class WorkerClient:
    """JSON-lines control channel to one worker (MetaClient analog)."""

    def __init__(self, host: str, control_port: int,
                 exchange_port: int):
        self.host = host
        self.control_port = control_port
        self.exchange_port = exchange_port
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._lock = asyncio.Lock()

    async def connect(self) -> None:
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.control_port, limit=CONTROL_LINE_LIMIT)

    async def call_idempotent(self, cmd: dict,
                              io_timeout: Optional[float] = None,
                              retries: int = 2,
                              backoff_s: float = 0.05) -> dict:
        """Transient-fault absorption for idempotent verbs: a torn or
        timed-out channel reconnects and re-sends instead of staying
        poisoned (the graduated-response ladder's RPC rung — a single
        timeout must not cost a full-cluster recovery). Out-of-retries
        errors surface to the caller/supervisor; each retry increments
        ``rpc_retry_total{verb=...}``."""
        verb = str(cmd.get("cmd"))
        if verb not in _IDEMPOTENT_VERBS:
            raise ValueError(
                f"refusing to auto-retry non-idempotent verb {verb!r}")
        delay = backoff_s
        for attempt in range(retries + 1):
            used = None
            try:
                # reconnect under the channel lock: two concurrent
                # callers on one shared client must not double-connect
                # (leaking a socket) — re-check after the await
                async with self._lock:
                    if self._writer is None:
                        await self.connect()
                    used = self._writer
                return await self.call(cmd, io_timeout=io_timeout)
            except (ConnectionError, OSError):
                if attempt >= retries:
                    raise
                _METRICS.rpc_retry.inc(verb=verb)
                # only tear down the channel WE failed on — a peer may
                # have already reconnected it while we were failing
                if self._writer is used:
                    self.abort()
                await asyncio.sleep(delay)
                delay *= 2
        raise AssertionError("unreachable")

    async def call(self, cmd: dict,
                   io_timeout: Optional[float] = None) -> dict:
        """One framed RPC. `io_timeout` bounds the round trip AFTER
        the channel lock is held (waiting behind another in-flight RPC
        is not evidence of a dead worker); an expired timeout leaves a
        desynchronized stream, so the channel is hard-closed."""
        if self._writer is None:
            raise ConnectionError("worker control channel closed")
        async with self._lock:
            if self._writer is None:
                raise ConnectionError("worker control channel closed")
            self._writer.write((json.dumps(cmd) + "\n").encode())
            await self._writer.drain()
            if io_timeout is None:
                line = await self._reader.readline()
            else:
                try:
                    line = await asyncio.wait_for(
                        self._reader.readline(), io_timeout)
                except asyncio.TimeoutError:
                    self.abort()
                    raise ConnectionError(
                        "worker control RPC timed out") from None
        if not line or not line.endswith(b"\n"):
            # closed, or a torn reply from a worker killed mid-write
            raise ConnectionError("worker control channel closed")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise RuntimeError(f"worker error: {reply.get('error')}")
        return reply

    async def deploy_plan(self, plan: list, **params) -> dict:
        """Ship a plan-IR fragment (stream/plan_ir.py) — the typed
        StreamNode-shipping path (stream_plan.proto analog)."""
        return await self.call({"cmd": "deploy_plan", "plan": plan,
                                "params": params})

    _PAGE_BYTES = CONTROL_PAGE_BYTES

    async def scan_table(self, table_id: int,
                         epoch: Optional[int] = None) -> list:
        """Pull one table's committed rows (value-codec decoded) from
        the worker's namespace — the distributed-SELECT data plane.
        Pages through the worker's byte-budgeted replies (all pages
        pinned to the FIRST page's epoch) so one giant table never
        overflows the JSON-line channel."""
        from risingwave_tpu.storage.value_codec import decode_row
        out = []
        after = None
        while True:
            reply = await self.call_idempotent(
                {"cmd": "scan_table", "table_id": table_id,
                 "epoch": epoch, "after": after})
            out += [(bytes.fromhex(k), decode_row(bytes.fromhex(r)))
                    for k, r in reply["rows"]]
            if reply.get("done", True) or not reply["rows"]:
                return out
            epoch = reply["epoch"]        # later pages pin the snapshot
            after = reply["rows"][-1][0]

    async def ingest_table(self, table_id: int, rows: list,
                           min_epoch: Optional[int] = None) -> dict:
        """Bulk-load (key_bytes, row_tuple) pairs — state migration.
        `min_epoch` keeps the ingest epoch above in-flight barriers.
        Large batches split into byte-budgeted requests (each commits
        at its own fresh epoch; the returned epoch is the highest)."""
        from risingwave_tpu.storage.value_codec import encode_row
        batch, nbytes = [], 0
        total = 0
        top = None
        for k, v in rows:
            kx = k.hex()
            vx = None if v is None else encode_row(tuple(v)).hex()
            batch.append([kx, vx])
            nbytes += len(kx) + (len(vx) if vx else 0)
            if nbytes >= self._PAGE_BYTES:
                top = await self.call({
                    "cmd": "ingest_table", "table_id": table_id,
                    "min_epoch": max(min_epoch or 0,
                                     int(top["epoch"]) if top else 0),
                    "rows": batch})
                total += int(top["rows"])
                batch, nbytes = [], 0
        if batch or top is None:
            top = await self.call({
                "cmd": "ingest_table", "table_id": table_id,
                "min_epoch": max(min_epoch or 0,
                                 int(top["epoch"]) if top else 0),
                "rows": batch})
            total += int(top["rows"])
        return {"ok": True, "rows": total, "epoch": int(top["epoch"])}

    async def inject(self, barrier: Barrier,
                     committed: Optional[int] = None,
                     extras: Optional[dict] = None) -> dict:
        m = None
        if isinstance(barrier.mutation, StopMutation):
            m = {"type": "stop",
                 "actors": sorted(barrier.mutation.actors)}
        elif isinstance(barrier.mutation, PauseMutation):
            m = {"type": "pause"}
        elif isinstance(barrier.mutation, ResumeMutation):
            m = {"type": "resume"}
        cmd = {
            "cmd": "inject",
            "curr": barrier.epoch.curr.value,
            "prev": barrier.epoch.prev.value,
            "kind": barrier.kind.value,
            "mutation": m,
            # the coordinator's commit decision pipelined on this
            # barrier (two-phase workers adopt staged SSTs ≤ this)
            "committed": committed,
        }
        if extras:
            # barrier-domain frame (ISSUE 13): "actors" scopes the
            # barrier to one domain's actors on the worker; "seal"
            # carries the cross-domain write floor the worker may
            # fence to (per-domain prevs interleave globally, so the
            # worker must never seal to its own prev eagerly)
            cmd.update(extras)
        from risingwave_tpu.utils import spans as _spans
        # span context rides the injection: worker-side spans of
        # this barrier round parent to the coordinator's inject
        # span — the cross-process causal edge
        cmd["trace"] = {
            "span": _spans.EPOCH_TRACER.root_id(
                barrier.epoch.curr.value)}
        return await self.call(cmd)

    async def ping(self, io_timeout: float = 2.0,
                   retries: int = 1) -> dict:
        """Heartbeat probe (cluster.rs heartbeat RPC round trip). One
        timed-out or torn round trip reconnects and retries: a single
        slow reply is a transient, not a death certificate — the lease
        in ClusterManager is what decides expiry."""
        return await self.call_idempotent({"cmd": "ping"},
                                          io_timeout=io_timeout,
                                          retries=retries)

    def abort(self) -> None:
        """Hard-close the channel. The JSON-lines protocol has no
        correlation ids, so once a framed call is cancelled mid-read
        (ping timeout) the stream is desynchronized — a late reply
        would be read as the NEXT call's response. Closing makes every
        later call fail loudly instead."""
        if self._writer is not None:
            self._writer.close()
            self._writer = None
            self._reader = None

    async def stop(self) -> None:
        try:
            await self.call({"cmd": "stop"})
        except (ConnectionError, RuntimeError):
            pass
        if self._writer is not None:
            self._writer.close()


class Heartbeater:
    """Coordinator-side liveness loop: ping every registered worker on
    an interval, feed the ClusterManager, expire the silent ones
    (meta/src/manager/cluster.rs:360 check loop + the compute node's
    heartbeat sender, combined at the meta side since the coordinator
    owns the control channel)."""

    def __init__(self, cluster, interval_s: float = 1.0,
                 on_expired=None):
        self.cluster = cluster
        self.interval = interval_s
        self._clients: dict = {}          # worker_id → WorkerClient
        self._task = None
        # owner callback invoked with the evicted WorkerNode list —
        # the supervisor's heartbeat-expiry detection input (tick used
        # to compute the dead set and drop it on the floor)
        self.on_expired = on_expired

    def register(self, worker_id: int, client: WorkerClient) -> None:
        self._clients[worker_id] = client

    async def tick(self) -> list:
        """One round: ping all CONCURRENTLY (a dead worker's timeout
        must not consume a healthy worker's lease), heartbeat the
        responders, expire the rest. Returns the evicted workers.
        The ping's io-timeout starts after the channel lock is held —
        waiting behind a long barrier RPC never counts against the
        worker, and call() closes a genuinely desynced channel itself."""
        async def one(wid, client):
            try:
                reply = await client.ping()
            except (ConnectionError, RuntimeError, OSError,
                    ValueError):            # incl. torn-reply JSON
                return                     # no heartbeat → may expire
            if not self.cluster.heartbeat(wid, reply.get("info")):
                # expired/removed outside this loop: stop pinging it
                stale = self._clients.pop(wid, None)
                if stale is not None:
                    stale.abort()

        await asyncio.gather(*(one(w, c)
                               for w, c in list(self._clients.items())))
        dead = self.cluster.expire_stale()
        for w in dead:
            _METRICS.worker_expired.inc(worker=str(w.worker_id))
            client = self._clients.pop(w.worker_id, None)
            if client is not None:
                client.abort()             # no leaked half-open socket
        if dead and self.on_expired is not None:
            self.on_expired(dead)
        return dead

    def start(self) -> None:
        async def loop():
            while True:
                await asyncio.sleep(self.interval)
                await self.tick()

        self._task = asyncio.ensure_future(loop())

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None


class WorkerBarrierSender:
    """Shaped like an exchange Sender: the coordinator's barrier
    manager 'sends' each barrier to the worker over control, and the
    worker's completion reply collects the pseudo-actor — InjectBarrier
    + BarrierComplete as one round trip."""

    # phase-ledger hint (meta/barrier.py seal): actor work behind this
    # sender runs in ANOTHER process, so coordinator-side conservation
    # is meaningless until drain_ledger merges the worker's books
    remote = True

    def __init__(self, client: WorkerClient, local, pseudo_actor: int,
                 committed_fn=None, extras_fn=None):
        self.client = client
        self.local = local
        self.pseudo = pseudo_actor
        # reads the coordinator's committed epoch at send time (the
        # commit decision pipelined onto each barrier); None = legacy
        # self-committing workers
        self.committed_fn = committed_fn
        # barrier-domain frame builder (ISSUE 13): called per send
        # with the barrier, returns the domain actor filter + seal
        # floor to ride the inject cmd; None = legacy global frames
        self.extras_fn = extras_fn
        self._tasks: set = set()   # strong refs: the loop holds tasks
        #                            weakly and could drop one mid-RPC

    async def send(self, barrier: Barrier) -> None:
        committed = (self.committed_fn()
                     if self.committed_fn is not None else None)
        extras = (self.extras_fn(barrier)
                  if self.extras_fn is not None else None)

        async def roundtrip():
            try:
                await self.client.inject(barrier, committed, extras)
                self.local.collect(self.pseudo, barrier)
            except BaseException as e:  # noqa: BLE001 — fail the epoch
                self.local.notify_failure(self.pseudo, e)

        t = asyncio.ensure_future(roundtrip())
        self._tasks.add(t)
        t.add_done_callback(self._tasks.discard)

    def close(self) -> None:
        pass


class WorkerHandle:
    """Spawn + own a worker subprocess (GlobalStreamManager's node).
    ``role="compactor"`` spawns the dedicated merge executor instead —
    same boot/heartbeat/kill lifecycle, no exchange plane."""

    def __init__(self, store_dir: str, platform: str = "cpu",
                 role: str = "worker"):
        self.store_dir = store_dir
        self.platform = platform
        self.role = role
        self.proc: Optional[subprocess.Popen] = None
        self.client: Optional[WorkerClient] = None

    async def start(self, timeout_s: float = 60.0) -> WorkerClient:
        import os
        env = dict(os.environ)
        # pin, don't setdefault: an ambient JAX_PLATFORMS naming an
        # accelerator would otherwise leak into every worker, and a
        # chip belongs to one process at a time — N workers cannot
        # share it. Callers opt INTO an accelerator via platform=; the
        # default worker is a CPU host process.
        env["JAX_PLATFORMS"] = self.platform
        argv = [sys.executable, "-m", "risingwave_tpu.cluster.worker",
                "--store", self.store_dir]
        if self.role != "worker":
            argv += ["--role", self.role]
        self.proc = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, cwd=None, text=True)
        loop = asyncio.get_event_loop()
        try:
            line = await asyncio.wait_for(
                loop.run_in_executor(None, self.proc.stdout.readline),
                timeout_s)
        except (asyncio.TimeoutError, TimeoutError):
            self.kill()                 # no orphan on a hung boot
            raise
        ports = json.loads(line)
        self.client = WorkerClient("127.0.0.1", ports["control_port"],
                                   ports["exchange_port"])
        await self.client.connect()
        return self.client

    def alive(self) -> bool:
        """Subprocess liveness (the supervisor's cheapest detection
        input): started, not yet reaped, and not exited."""
        return self.proc is not None and self.proc.poll() is None

    def kill(self) -> None:
        """SIGKILL — the chaos path (no goodbye, no flush)."""
        if self.proc is not None:
            self.proc.kill()
            self.proc.wait()
            self.proc = None

    async def stop(self) -> None:
        if self.client is not None:
            await self.client.stop()
        if self.proc is not None:
            loop = asyncio.get_event_loop()
            try:
                await asyncio.wait_for(
                    loop.run_in_executor(None, self.proc.wait), 20)
            except (asyncio.TimeoutError, TimeoutError):
                self.kill()             # wedged worker: no orphan
            self.proc = None
