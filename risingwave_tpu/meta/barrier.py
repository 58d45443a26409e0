"""The barrier/checkpoint loop: the system heartbeat.

Reference parity: src/meta/src/barrier/mod.rs:128,558,652 —
GlobalBarrierManager ticks every `barrier_interval_ms`, pairs the tick with
a scheduled command, issues the next epoch, injects the barrier at sources,
keeps at most `in_flight_barrier_nums` barriers un-collected, and on
collection commits the epoch to the state store (HummockManager::commit_epoch
analog). `checkpoint_frequency` makes only every k-th barrier durable
(BarrierKind::{Barrier,Checkpoint}).

TPU notes: barrier collection is the device sync point — an epoch completes
only after every actor flushed device state for it. The loop never blocks
data flow: injection is pipelined up to the in-flight window.
"""

from __future__ import annotations

import asyncio
import contextlib
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.state.store import StateStore
from risingwave_tpu.storage.uploader import CheckpointUploader
from risingwave_tpu.stream.actor import LocalBarrierManager
from risingwave_tpu.stream.message import Barrier, BarrierKind, Mutation
from risingwave_tpu.utils import ledger as _ledger
from risingwave_tpu.utils import spans as _spans
from risingwave_tpu.utils.failpoint import fail_point
from risingwave_tpu.utils.metrics import (
    HISTORY, STREAMING, exact_quantile,
)
from risingwave_tpu.utils.trace import GLOBAL_AWAITS


class BarrierWedgedError(RuntimeError):
    """Barrier collection exceeded the configured collect timeout —
    the wedged-barrier failure class: some participant holds the epoch
    open (a stuck executor, a starved exchange edge) without dying.
    The recovery supervisor classifies this as unrecoverable in place
    and escalates to full recovery."""


@dataclass
class BarrierStats:
    """Collected per-epoch latencies (meta barrier_latency metric
    analog). A multi-domain plane shares ONE stats object so the
    aggregate list keeps its historical meaning (warm-up trims
    assign it in place); per-domain p99 lives on the PROFILER
    (``EpochProfiler.p99_by_domain`` — ``drop_first`` trims it in
    step with the aggregate), never here, so the two views cannot
    desync."""

    completed_epochs: List[int] = field(default_factory=list)
    latencies_s: List[float] = field(default_factory=list)

    def observe(self, latency_s: float, domain: str = "") -> None:
        self.latencies_s.append(latency_s)

    def p99_latency_s(self) -> float:
        return exact_quantile(self.latencies_s, 0.99)

    def mean_latency_s(self) -> float:
        return (sum(self.latencies_s) / len(self.latencies_s)
                if self.latencies_s else 0.0)


@dataclass
class EpochProfile:
    """One barrier's breakdown + per-actor attribution snapshot."""

    epoch: int
    kind: str                         # "barrier" | "checkpoint"
    inject_to_collect_s: float
    collect_to_commit_s: float
    in_flight: int                    # window depth at collection
    actor_rows: Dict[int, float]      # rows moved this epoch, per actor
    slowest_actor: Optional[int] = None
    slowest_actor_lag_s: float = 0.0  # first-collect → last-collect
    await_dump: str = ""              # attached only on slow barriers
    # async checkpoint tail: seal→durable-commit time (patched in by
    # the uploader when the commit lands — OVERLAPPED with younger
    # barriers, so it is deliberately NOT part of total_s) and the
    # uploading-window depth right after this epoch was submitted
    upload_s: float = 0.0
    queue_depth: int = 0
    # alignment domain that ran this barrier ("" = the global domain —
    # single-loop deployments and the stream_epoch_pipeline=off arm)
    domain: str = ""

    @property
    def total_s(self) -> float:
        return self.inject_to_collect_s + self.collect_to_commit_s

    def format(self) -> str:
        lines = [
            f"epoch {self.epoch:#x} "
            f"({self.kind}"
            f"{', domain ' + self.domain if self.domain else ''}): "
            f"inject→collect {self.inject_to_collect_s * 1e3:.2f}ms, "
            f"collect→commit {self.collect_to_commit_s * 1e3:.2f}ms, "
            f"in-flight {self.in_flight}"]
        if self.upload_s > 0.0 or self.queue_depth:
            lines.append(
                f"  async upload: {self.upload_s * 1e3:.2f}ms "
                f"(queue depth {self.queue_depth})")
        if self.slowest_actor is not None:
            lines.append(
                f"  slowest actor: {self.slowest_actor} "
                f"(+{self.slowest_actor_lag_s * 1e3:.2f}ms after "
                f"first collect)")
        if self.actor_rows:
            rows = ", ".join(f"{a}={int(n)}" for a, n in
                             sorted(self.actor_rows.items()))
            lines.append(f"  rows/actor: {rows}")
        if self.await_dump:
            lines.append("  await states at collect:")
            lines += [f"    {ln}" for ln in
                      self.await_dump.splitlines()]
        return "\n".join(lines)


class EpochProfiler:
    """Barrier-aligned metric snapshots (the attribution layer).

    At every collection the profiler diffs the per-actor row counters
    (MonitoredExecutor series), splits the barrier into inject→collect
    and collect→commit, and — when the barrier exceeds the slow
    threshold — attaches the AwaitRegistry dump plus the slowest-actor
    attribution, so a p99 outlier names its culprit instead of being
    one opaque number.
    """

    def __init__(self, slow_threshold_s: float = 1.0,
                 capacity: int = 1 << 16):
        self.slow_threshold_s = slow_threshold_s
        # bounded: profiles carry dicts and await dumps, and a 250ms
        # heartbeat would append ~345k/day unbounded. 64k epochs keep
        # rw_barrier_latency 1:1 with BarrierStats for any test run
        # (they trim warmup from the front of both) while a
        # long-lived server just loses the oldest profiles.
        self.profiles: Deque[EpochProfile] = deque(maxlen=capacity)
        # baseline at profiler birth: the registry is process-global,
        # so an earlier pipeline's totals must not bleed into this
        # loop's first epoch delta
        self._last_rows: Dict[tuple, float] = {}
        self._actor_row_deltas()

    def _actor_row_deltas(self) -> Dict[int, float]:
        """Per-actor rows moved this epoch: the MAX over the actor's
        monitored executor nodes — every wrapped node counts the same
        rows flowing through, so summing would inflate by the chain
        depth; the busiest node is the actor's true data volume."""
        totals: Dict[tuple, float] = {}
        for labels, v in STREAMING.executor_rows.series():
            a = labels.get("actor")
            if a is not None:
                totals[(a, labels.get("node", ""))] = v
        per_actor: Dict[int, float] = {}
        for (a, node), v in totals.items():
            d = v - self._last_rows.get((a, node), 0.0)
            if d > 0:
                try:
                    aid = int(a)
                except ValueError:
                    continue
                per_actor[aid] = max(per_actor.get(aid, 0.0), d)
        self._last_rows = totals
        return per_actor

    def record(self, epoch: int, kind: str, inject_to_collect_s: float,
               collect_to_commit_s: float, in_flight: int,
               collect_times: Dict[int, float],
               domain: str = "") -> EpochProfile:
        prof = EpochProfile(epoch, kind, inject_to_collect_s,
                            collect_to_commit_s, in_flight,
                            self._actor_row_deltas(), domain=domain)
        if collect_times:
            slowest = max(collect_times, key=collect_times.get)
            prof.slowest_actor = slowest
            prof.slowest_actor_lag_s = (collect_times[slowest]
                                        - min(collect_times.values()))
        if prof.total_s >= self.slow_threshold_s:
            prof.await_dump = GLOBAL_AWAITS.dump()
        self.profiles.append(prof)
        STREAMING.barrier_inject_to_collect.observe(inject_to_collect_s)
        STREAMING.barrier_collect_to_commit.observe(collect_to_commit_s)
        return prof

    def drop_first(self, n: int) -> None:
        """Discard the oldest n profiles (warmup epochs: the
        trace-compile outliers must not masquerade as the steady-state
        p99 the same result line reports)."""
        for _ in range(min(n, len(self.profiles))):
            self.profiles.popleft()

    def rows(self) -> List[tuple]:
        """(epoch, kind, i2c, c2c, total, in_flight, slowest_actor,
        slowest_lag, upload_s, queue_depth, domain) per profiled
        barrier — the rw_barrier_latency system-table payload (new
        columns appended so existing positional consumers keep their
        indices)."""
        return [(p.epoch, p.kind, p.inject_to_collect_s,
                 p.collect_to_commit_s, p.total_s, p.in_flight,
                 p.slowest_actor, p.slowest_actor_lag_s,
                 p.upload_s, p.queue_depth, p.domain)
                for p in self.profiles]

    def p99_by_domain(self) -> Dict[str, float]:
        """Per-domain p99 barrier total over the retained profiles
        (the warmup trim via ``drop_first`` applies to this view
        too)."""
        by: Dict[str, List[float]] = {}
        for p in self.profiles:
            by.setdefault(p.domain, []).append(p.total_s)
        return {d: exact_quantile(v, 0.99) for d, v in by.items()}

    def report(self, last_n: int = 10) -> str:
        return "\n".join(p.format()
                         for p in list(self.profiles)[-last_n:])

    def p99_breakdown(self) -> Dict[str, float]:
        """Per-phase p99 over the profiled barriers. An EMPTY deque —
        a fresh loop, or one whose warmup trim consumed every profile
        (drop_first(n) with n ≥ len) — yields all-zero phases, never
        an exception."""
        profs = list(self.profiles)
        if not profs:
            return {"inject_to_collect_s": 0.0,
                    "collect_to_commit_s": 0.0, "upload_s": 0.0}
        return {
            "inject_to_collect_s": exact_quantile(
                [p.inject_to_collect_s for p in profs], 0.99),
            "collect_to_commit_s": exact_quantile(
                [p.collect_to_commit_s for p in profs], 0.99),
            # the overlapped async tail — NOT part of barrier latency;
            # reported so the overlap is visible, not invisible
            "upload_s": exact_quantile(
                [p.upload_s for p in profs], 0.99),
        }


def record_checkpoint_tail(profs: List[EpochProfile],
                           committed_epoch: int, upload_s: float,
                           stages: List[tuple]) -> None:
    """The uploader's commit callback, shared by the loop and the
    plane: patch ``upload_s`` into the profile(s) of the barrier(s)
    that sealed the checkpoint, and file the stages the uploader cut
    it into (storage/uploader.py, "Stages") under the same barrier:
    spans below its ``checkpoint.upload`` in the epoch trace, and the
    ``ckpt.*`` names of its ``rw_metrics_history`` row."""
    for prof in profs:
        prof.upload_s = upload_s
    if not profs:
        return
    if stages:
        values = dict.fromkeys(
            ("ckpt.queue_s", "ckpt.build_s", "ckpt.put_s",
             "ckpt.commit_s", "ckpt.compact_s", "ckpt.sst_bytes",
             "ckpt.build_columnar_entries", "ckpt.build_row_entries",
             "ckpt.compact_read_bytes", "ckpt.compact_write_bytes"), 0.0)
        for name, _start, dur, counts in stages:
            key = "ckpt." + name.partition(".")[2]
            values[key + "_s"] = values.get(key + "_s", 0.0) + dur
            if name == "checkpoint.build":
                values["ckpt.sst_bytes"] = float(counts["sst_bytes"])
                values["ckpt.build_columnar_entries"] = \
                    float(counts["columnar_entries"])
                values["ckpt.build_row_entries"] = \
                    float(counts["row_entries"])
            elif name == "checkpoint.compact":
                values["ckpt.compact_read_bytes"] = \
                    float(counts["read_bytes"])
                values["ckpt.compact_write_bytes"] = \
                    float(counts["write_bytes"])
        for prof in profs:
            HISTORY.amend(prof.epoch, values)
    # the async checkpoint tail (seal→durable commit), overlapped
    # with younger barriers — traced under the barrier that SEALED
    # it so the overlap is visible, its stages below it
    epoch = profs[0].epoch
    tail = _spans.EPOCH_TRACER.record(
        "checkpoint.upload", "upload", epoch=epoch,
        start_s=time.time() - upload_s, dur_s=upload_s,
        committed_epoch=committed_epoch)
    for name, start, dur, counts in stages:
        _spans.EPOCH_TRACER.record(
            name, "upload", epoch=epoch, start_s=start, dur_s=dur,
            parent=tail, **counts)


class VirtualClock:
    """Deterministic time source (the madsim stance, SURVEY §4:
    replace time, keep the program): `sleep` advances virtual time and
    yields once so actors run — a whole barrier schedule executes
    deterministically at full speed. ``install()`` also rebinds the
    EPOCH clock (common/epoch.py), so epoch values — and thus SST keys
    and committed_epoch — are identical across runs, not wall-clock
    residue."""

    def __init__(self, start_s: float = 1_700_000_000.0) -> None:
        self.t = 0.0
        self.start_s = start_s

    def monotonic(self) -> float:
        return self.t

    def time(self) -> float:
        return self.start_s + self.t

    async def sleep(self, delay: float) -> None:
        # yield FIRST: a sleep cancelled by the barrier loop's
        # first-completed race must not have consumed its interval
        await asyncio.sleep(0)
        self.t += delay

    @contextlib.contextmanager
    def install(self):
        """Bind the global epoch clock to virtual time for the block."""
        from risingwave_tpu.common.epoch import set_clock
        prev = set_clock(self.time)
        try:
            yield self
        finally:
            set_clock(prev)


class HeartbeatTick:
    """The serving heartbeat's interval timer and its books
    (``Frontend.run_heartbeat`` drives one per task).

    A tick is due ``interval_s`` after the previous INJECT, not after
    the previous collect: upstream's ``barrier_interval_ms``
    (GlobalBarrierManager::run ticks an interval timer, SURVEY §3.2).
    A round that overran its tick is followed at once, and the tick
    after that one is a whole interval from the late inject — a missed
    tick delays, it never bursts — so no two injects are closer than
    ``interval_s``. The first tick is one interval from construction.

    What the driver waited before a round goes on the history row of
    the epoch that round injected (``file``), and each wait is an
    annotation on the profiler's clock, its ``epoch`` stat the newest
    epoch injected before it:

      ``heartbeat.wait_s``       seconds waited for the tick
                                 (annotation ``heartbeat.wait``)
      ``heartbeat.tail_wait_s``  seconds then waited for the sealed
                                 checkpoint's commit (``heartbeat.tail``)
      ``heartbeat.overdue``      1 where the tick was already due when
                                 the round before collected, else 0

    The waits lie between one epoch's books closing and the next
    inject, so no ledger phase holds them: their sum, less the loop
    time a checkpoint took inside them, goes on the same row as
    ``phase.heartbeat_wait``, outside the row's ``interval_s`` and
    outside the ledger's conservation gate (``ctl phases`` prints
    it under the epoch's table). The clock and the sleeper are class
    attributes: a test puts a ``VirtualClock`` under them."""

    monotonic = staticmethod(time.monotonic)
    sleep = staticmethod(asyncio.sleep)

    def __init__(self, interval_s: float) -> None:
        self.interval_s = interval_s
        self.next_at = self.monotonic() + interval_s
        self._books: Dict[str, float] = {}
        # seconds the ledger's LOOP phases ran inside the round's waits
        self._stolen = 0.0

    async def wait(self) -> None:
        """Return once the tick is due."""
        t0 = self.monotonic()
        stolen0 = _ledger.stolen_s()
        overdue = t0 >= self.next_at
        with _spans.annotation("heartbeat.wait"):
            while (left := self.next_at - self.monotonic()) > 0:
                await self.sleep(left)
        self._books = {"heartbeat.wait_s": self.monotonic() - t0,
                       "heartbeat.overdue": float(overdue)}
        self._stolen = _ledger.stolen_s() - stolen0

    async def tail(self, uploader: CheckpointUploader) -> None:
        """Return once every sealed checkpoint has committed. Waits on
        the uploader's tasks without owning them: a cancel that lands
        here leaves them running."""
        t0 = self.monotonic()
        stolen0 = _ledger.stolen_s()
        with _spans.annotation("heartbeat.tail"):
            await uploader.drain()
        self._books["heartbeat.tail_wait_s"] = self.monotonic() - t0
        self._stolen += _ledger.stolen_s() - stolen0

    def injected(self) -> None:
        """The round's barrier is being injected now (the engine calls
        this where it stamps the inject; with several domains the last
        one counts): the next tick counts from here."""
        self.next_at = self.monotonic() + self.interval_s

    def file(self, epoch: int) -> None:
        """The round's waits onto `epoch`'s history row, and as
        ``phase.heartbeat_wait`` their sum less the seconds a LOOP
        phase held the loop meanwhile (the checkpoint the tail waits
        for is built and committed inside it; the next epoch's
        ``phase.checkpoint`` / ``phase.compaction`` hold those): time
        between two epochs' books that nothing else names, so a reader
        that sums the ``phase.*`` names over a span of the history's
        own stamps names each second once."""
        waited = self._books.get("heartbeat.wait_s", 0.0) \
            + self._books.get("heartbeat.tail_wait_s", 0.0)
        HISTORY.amend(epoch, {
            **self._books,
            "phase.heartbeat_wait": max(0.0, waited - self._stolen)})


class BarrierLoop:
    """GlobalBarrierManager-lite driving one LocalBarrierManager.

    Two driving modes:
    - `run()`: background task ticking `interval_ms` on the (injectable)
      clock + sleeper — production shape on the wall clock, the
      deterministic simulation under a VirtualClock.
    - `inject_and_collect()` / `checkpoint()`: explicit stepping for tests
      and benchmarks (deterministic; no timers).
    """

    def __init__(self, local: LocalBarrierManager, store: StateStore,
                 interval_ms: int = 250, checkpoint_frequency: int = 1,
                 in_flight_barrier_nums: int = 10,
                 monotonic: Callable[[], float] = time.monotonic,
                 sleep=asyncio.sleep,
                 slow_barrier_threshold_s: float = 1.0,
                 max_uploading: int = 4,
                 collect_timeout_s: Optional[float] = None,
                 distributed: bool = False,
                 domain: str = "",
                 plane=None,
                 stats: Optional[BarrierStats] = None,
                 profiler: Optional[EpochProfiler] = None):
        self.local = local
        self.store = store
        self.interval_ms = interval_ms
        self.checkpoint_frequency = max(1, checkpoint_frequency)
        self.in_flight_barrier_nums = max(1, in_flight_barrier_nums)
        self.monotonic = monotonic
        self.sleep = sleep
        # barrier-domain membership (ISSUE 13): under a BarrierPlane
        # this loop drives ONE alignment domain — epochs mint from the
        # plane's shared allocator (globally unique, always above the
        # committed floor), barriers flow only through the domain's
        # senders/actors, the store's seal fence advances at the
        # cross-domain low watermark, and checkpoint submission is the
        # plane's (cross-domain aligned) job. With plane=None the loop
        # is exactly the historical global-lockstep engine — the
        # stream_epoch_pipeline=off oracle arm.
        self.domain = domain
        self._plane = plane
        # distributed coordinator: actor work runs in worker processes,
        # so a sealed phase record covers only coordinator-side time
        # until drain_ledger merges the workers' accumulators —
        # conservation is deferred until then (utils/ledger.py)
        self.distributed = distributed
        # None: wait forever (the historical behavior — tests that
        # step explicitly own their own timeouts). Set: a barrier that
        # fails to collect within the bound raises BarrierWedgedError
        # instead of wedging the whole control loop silently.
        self.collect_timeout_s = collect_timeout_s
        # a plane shares ONE stats/profiler across its domain loops so
        # the aggregate surfaces (warm-up trim, rw_barrier_latency)
        # keep working; standalone loops own theirs as before
        self.stats = stats if stats is not None else BarrierStats()
        self.profiler = profiler if profiler is not None \
            else EpochProfiler(slow_barrier_threshold_s)
        self._epoch: Optional[Epoch] = None
        self._barriers_since_checkpoint = 0
        self._inject_times: Dict[int, float] = {}
        self._in_flight: List[int] = []       # injected, not yet collected
        self._committed_epoch = store.committed_epoch()
        self._pending_mutations: List[Mutation] = []
        self._stopped = False
        # async checkpoint pipeline: collect_next only seals + submits;
        # epochs commit in order when their uploads land. The sealed-
        # but-uncommitted window (`uploading_count`) is bounded by
        # max_uploading — submit back-pressures, collection stalls,
        # the in-flight window fills, injection stops: total staging is
        # bounded by in_flight_barrier_nums + max_uploading epochs.
        if plane is not None:
            # ONE checkpoint pipeline per store: domains share the
            # plane's uploader (the imm drain is cumulative — two
            # uploaders on one store would race each other's builds),
            # and submission happens only at cross-domain aligned
            # checkpoints (the plane's decoupled cadence).
            self.uploader = plane.uploader
        else:
            self.uploader = CheckpointUploader(
                store, max_uploading=max_uploading, monotonic=monotonic,
                on_commit=self._on_epoch_committed)
        self._upload_profiles: Dict[int, EpochProfile] = {}
        # previous epoch's collect stamp (wall monotonic): the phase
        # ledger starts each epoch's conservation interval here, so
        # pipelined in-flight barriers PARTITION wall time instead of
        # overlapping — time queued behind an older epoch belongs to
        # that epoch's books, not to this one's as `unattributed`.
        # (rw_barrier_latency keeps the overlapping inject→collect
        # semantics: queueing IS part of user-visible latency.)
        self._last_seal_stamp: Optional[float] = None

    # -- command scheduling (BarrierScheduler analog) -------------------
    def schedule_mutation(self, mutation: Mutation) -> None:
        self._pending_mutations.append(mutation)

    @property
    def committed_epoch(self) -> int:
        if self._plane is not None:
            return self.store.committed_epoch()
        return self._committed_epoch

    def frontier_epoch(self) -> int:
        """The newest epoch this loop issued (0 before the first
        barrier) — reschedule/state-handoff paths read this instead of
        poking the private cursor."""
        return self._epoch.value if self._epoch is not None else 0

    @property
    def in_flight_count(self) -> int:
        """Injected-but-uncollected barriers (drivers pipelining against
        the window should read this, not the private list)."""
        return len(self._in_flight)

    @property
    def uploading_count(self) -> int:
        """Sealed-but-uncommitted checkpoint epochs (the async upload
        window alongside in_flight)."""
        return self.uploader.depth

    def _on_epoch_committed(self, epoch: int, upload_s: float,
                            stages: List[tuple]) -> None:
        """Uploader commit callback — epochs arrive strictly in order,
        so committed_epoch never skips past an unfinished older one."""
        self._committed_epoch = epoch
        prof = self._upload_profiles.pop(epoch, None)
        record_checkpoint_tail([prof] if prof is not None else [],
                               epoch, upload_s, stages)

    # -- one step -------------------------------------------------------
    def _next_kind(self, force_checkpoint: bool) -> BarrierKind:
        if self._epoch is None:
            return BarrierKind.INITIAL
        if self._plane is not None:
            # decoupled cadence: the plane alone decides when a durable
            # checkpoint happens (a cross-domain aligned event); plain
            # domain barriers never auto-promote on a local counter
            return (BarrierKind.CHECKPOINT if force_checkpoint
                    else BarrierKind.BARRIER)
        self._barriers_since_checkpoint += 1
        if force_checkpoint or (self._barriers_since_checkpoint
                                >= self.checkpoint_frequency):
            return BarrierKind.CHECKPOINT
        return BarrierKind.BARRIER

    async def inject(self, mutation: Optional[Mutation] = None,
                     force_checkpoint: bool = False,
                     on_inject: Optional[Callable[[], None]] = None
                     ) -> Barrier:
        """Issue the next epoch and send its barrier to source actors.
        ``on_inject`` is called where the inject is stamped (the
        heartbeat's tick counts from there)."""
        kind = self._next_kind(force_checkpoint)
        if self._plane is not None:
            # shared allocator: globally-unique, monotone epochs above
            # the committed floor — concurrent domains can never mint
            # colliding epoch values or write under the seal fence
            curr = self._plane.allocator.allocate(self.domain)
            prev = self._epoch if self._epoch is not None \
                else Epoch(self.store.committed_epoch())
            pair = EpochPair(curr=curr, prev=prev)
        elif self._epoch is None:
            curr = Epoch.now()
            # recovery: the initial barrier's prev is the committed epoch,
            # so state-table reads see the checkpointed data (recovery.rs)
            recovered = Epoch(self.store.committed_epoch())
            if curr.value <= recovered.value:
                curr = Epoch(recovered.value + 1)
            pair = EpochPair(curr=curr, prev=recovered)
        else:
            curr = self._epoch.next()
            pair = EpochPair(curr=curr, prev=self._epoch)
        self._epoch = curr
        if mutation is None and self._pending_mutations:
            mutation = self._pending_mutations.pop(0)
        barrier = Barrier(pair, kind, mutation)
        # epoch-causal trace root: every span of this barrier round
        # (actor processing, exchange edges, dispatches, commit) parents
        # here. Dispatch spans recorded between barriers attribute to
        # the newest injected epoch (utils/spans.py docstring).
        _spans.set_current_epoch(curr.value)
        root = _spans.EPOCH_TRACER.record(
            "barrier.inject", "barrier", epoch=curr.value,
            kind=kind.value)
        _spans.EPOCH_TRACER.set_root(curr.value, root)
        self._inject_times[curr.value] = self.monotonic()
        if on_inject is not None:
            on_inject()
        self._in_flight.append(curr.value)
        STREAMING.barrier_in_flight.set(len(self._in_flight))
        if kind.is_checkpoint:
            self._barriers_since_checkpoint = 0
        with _spans.annotation("barrier.inject", curr.value):
            if self._plane is not None:
                sender_ids, expected = self._plane.scope(self.domain)
                await self.local.send_barrier(barrier,
                                              sender_ids=sender_ids,
                                              expected=expected)
            else:
                await self.local.send_barrier(barrier)
        return barrier

    def advance_epoch_to(self, value: int) -> None:
        """Reserve every epoch ≤ `value` (out-of-band bulk ingest, e.g.
        reschedule state handoff): the next barrier's curr will exceed
        it, so no in-flight flush can collide with the reserved epoch."""
        assert not self._in_flight, "advance with barriers in flight"
        if self._plane is not None:
            self._plane.allocator.reserve_to(value)
        if self._epoch is None or self._epoch.value < value:
            self._epoch = Epoch(value)

    async def _await_complete_or_upload_failure(self, epoch: int
                                                ) -> Barrier:
        """Race epoch completion against a terminal uploader failure,
        so a dead checkpoint pipeline fails the barrier promptly — and
        as the ORIGINAL error (e.g. the object store's OSError), not a
        later symptom."""
        self.uploader.bind_loop()
        waiter = asyncio.ensure_future(
            self.local.await_epoch_complete(epoch))
        failer = asyncio.ensure_future(self.uploader.failed.wait())
        timer = (asyncio.ensure_future(
            self.sleep(self.collect_timeout_s))
            if self.collect_timeout_s is not None else None)
        waits = {waiter, failer} | ({timer} if timer else set())
        try:
            done, _ = await asyncio.wait(
                waits, return_when=asyncio.FIRST_COMPLETED)
        except asyncio.CancelledError:
            waiter.cancel()
            raise
        finally:
            failer.cancel()
            if timer is not None:
                timer.cancel()
        if waiter in done:
            return waiter.result()
        waiter.cancel()
        with contextlib.suppress(asyncio.CancelledError):
            await waiter
        if timer is not None and timer in done:
            # wedged-barrier detection: the epoch is still collectible
            # by a retry (await_epoch_complete is cancellation-safe),
            # but the supervisor treats the wedge as terminal in place
            raise BarrierWedgedError(
                f"barrier collect for epoch {epoch:#x} exceeded "
                f"{self.collect_timeout_s}s — wedged barrier")
        self.uploader.raise_if_failed()
        raise RuntimeError("uploader failure event without a failure")

    async def collect_next(self) -> Barrier:
        """Await the oldest in-flight epoch; seal it and hand the flush
        to the checkpoint uploader. SST build and object-store upload
        run OFF this path — the commit lands asynchronously, in epoch
        order, once the uploads are durable (uploader.rs:567 analog)."""
        assert self._in_flight, "nothing in flight"
        # a failed upload fails the barrier here, after its retries
        self.uploader.raise_if_failed()
        epoch = self._in_flight.pop(0)
        with _spans.annotation("barrier.collect", epoch):
            barrier = await self._await_complete_or_upload_failure(epoch)
        t_collect = self.monotonic()
        with _spans.annotation("barrier.commit", epoch):
            await self._seal_and_submit(epoch, barrier, t_collect)
        self.stats.completed_epochs.append(epoch)
        return barrier

    async def _seal_and_submit(self, epoch: int, barrier: Barrier,
                               t_collect: float) -> None:
        """The commit half of a collection: seal the store and the
        epoch's books, hand a checkpoint to the uploader, run the
        checkpoint-time sweeps."""
        # ledger-test seam: a sleep spec here lands inside the commit
        # half of the measured interval as wall time NO phase can
        # claim — the conservation residual must surface it as
        # `unattributed`
        fail_point("barrier.collect")
        STREAMING.barrier_in_flight.set(len(self._in_flight))
        # the epoch whose data this barrier flushed is the one that ENDED:
        # barrier.epoch.prev (meta commits prev_epoch — barrier/mod.rs:652).
        # The INITIAL barrier has prev=INVALID: nothing to commit yet.
        prev = barrier.epoch.prev.value
        if prev > 0:
            if self._plane is not None:
                # domain epochs interleave globally: the store's seal
                # fence may only advance at the cross-domain low
                # watermark (an eager per-domain seal would fence out
                # a sibling domain's still-open epoch)
                self._plane.allocator.note_ended(
                    prev, barrier.is_checkpoint)
            else:
                self.store.seal_epoch(prev, barrier.is_checkpoint)
        t0 = self._inject_times.pop(epoch, None)
        prof = None
        seal_rec = None
        if t0 is not None:
            lat = self.monotonic() - t0
            self.stats.observe(lat, self.domain)
            STREAMING.barrier_latency.observe(lat)
            collect_times = self.local.take_collect_times(epoch)
            prof = self.profiler.record(
                epoch,
                "checkpoint" if barrier.is_checkpoint else "barrier",
                inject_to_collect_s=t_collect - t0,
                collect_to_commit_s=self.monotonic() - t_collect,
                in_flight=len(self._in_flight),
                collect_times=collect_times,
                domain=self.domain)
            now = time.time()
            _spans.EPOCH_TRACER.record(
                "barrier.collect", "barrier", epoch=epoch,
                start_s=now - prof.total_s,
                dur_s=prof.inject_to_collect_s,
                in_flight=prof.in_flight,
                **({"domain": self.domain} if self.domain else {}))
            _spans.EPOCH_TRACER.record(
                "barrier.commit", "commit", epoch=epoch,
                start_s=now - prof.collect_to_commit_s,
                dur_s=prof.collect_to_commit_s, kind=prof.kind,
                **({"domain": self.domain} if self.domain else {}))
            if prof.total_s >= self.profiler.slow_threshold_s:
                # slow-barrier watchdog: the flight ring rolls in
                # EPOCH_WINDOW barriers — promote the outlier's
                # full trace into the retained store NOW, with its
                # one-line straggler attribution
                diag = _spans.EPOCH_TRACER.diagnose(
                    epoch, prof.total_s)
                _spans.EPOCH_TRACER.promote(epoch, diag,
                                            prof.total_s)
                print(f"slow barrier: {diag}", file=sys.stderr)
            # seal the epoch's phase books against the measured
            # interval (residual → unattributed, metrics history
            # row, Perfetto phase lanes). Virtual-clock loops
            # DISCARD instead: the simulated interval and the
            # wall-clock phases live on different clocks, so a
            # conservation check there would be noise
            if self.monotonic is time.monotonic:
                # the conservation interval ends when the LAST
                # actor collected (its wall stamp), not when this
                # coroutine got scheduled — the wake gap is event-
                # loop time during which actors already run the
                # NEXT epoch's pulls, which the ledger rightly
                # attributes to the next epoch. It STARTS at the
                # previous epoch's collect stamp when that is
                # later than this inject (pipelined injection:
                # queueing behind an older epoch is that epoch's
                # wall time, already on its books).
                t_true = max(collect_times.values(),
                             default=t_collect)
                last = self._last_seal_stamp
                start = t0 if last is None else max(t0, last)
                interval = max(0.0, t_true - start) \
                    + prof.collect_to_commit_s
                # the next epoch's books open where this one's
                # close — AFTER the commit half, which this
                # interval already claims (a stall there must not
                # land on two epochs' books)
                self._last_seal_stamp = \
                    t_true + prof.collect_to_commit_s
                seal_rec = _ledger.LEDGER.seal(
                    epoch, interval, prof.kind,
                    # the wake gap is the next epoch's wall time:
                    # LOOP-phase sections in it wait for its seal
                    wake_gap=(t_true, t_collect),
                    # and what held the loop between the last
                    # books closing and this inject is this epoch's
                    between=(last, t0)
                    if last is not None and t0 > last else None,
                    # remote pseudo-actors ⇒ actor work ran in
                    # other processes: conservation defers to the
                    # drain_ledger merge (auto-detected so bare
                    # coordinator loops in tests behave too)
                    distributed=self.distributed
                    or self.local.has_remote_participants(),
                    # mutation barriers (deploy/stop/reschedule)
                    # do topology work no phase claims — exempt
                    warmup=barrier.mutation is not None,
                    domain=self.domain)
            else:
                _ledger.LEDGER.discard(epoch)
            # bottleneck walk (ISSUE 14): one candidate per domain per
            # barrier off the just-published utilization tricolor,
            # cross-checked against the sealed phase record. Wall-clock
            # loops only — virtual-clock ratios would be meaningless.
            if barrier.mutation is None \
                    and self.monotonic is time.monotonic:
                # mutation barriers (deploy/stop/reschedule) do
                # topology work, not epoch work — walking them would
                # reset every streak right before a teardown report
                from risingwave_tpu.stream.bottleneck import BOTTLENECKS
                fragments = None
                if self._plane is not None:
                    jobs = self._plane.jobs_of_domain(self.domain)
                    fragments = set(jobs) if jobs else None
                BOTTLENECKS.observe(
                    epoch=epoch, domain=self.domain,
                    interval_s=seal_rec.interval_s,
                    phase_seconds=seal_rec.seconds,
                    fragments=fragments)
        if prev > 0 and barrier.is_checkpoint:
            if self._plane is not None:
                # checkpoint durability is a CROSS-DOMAIN aligned
                # event: this loop only reports its sealed prev; the
                # plane submits ONE floor epoch to the shared uploader
                # once every domain of the round has collected
                self._plane.note_checkpoint_sealed(self.domain, prev,
                                                   prof)
            else:
                if prof is not None:
                    # registered BEFORE submit: the inline fallback
                    # commits inside submit and patches upload_s right
                    # away
                    self._upload_profiles[prev] = prof
                if not await self.uploader.submit(prev):
                    # no flush needed (recovery-initial epoch): drop
                    # the registration or it pins the profile forever
                    self._upload_profiles.pop(prev, None)
                if prof is not None:
                    prof.queue_depth = self.uploader.depth
        if barrier.is_checkpoint:
            STREAMING.checkpoint_count.inc()
            # host-memory accounting/eviction sweep piggybacks on the
            # checkpoint (memory_manager.rs watermark-loop analog)
            from risingwave_tpu.state.topology import TOPOLOGY
            from risingwave_tpu.stream.costs import COSTS
            from risingwave_tpu.utils.memory import GLOBAL as _MEM
            # synchronous on the loop, in no actor: the ledger's LOOP
            # phase `checkpoint`, out of the books of whoever is parked
            with _ledger.LEDGER.phase("checkpoint"):
                _MEM.tick()
                # topology two-book recount (armed by the tier-1 gate
                # fixture only — a no-op in production) and the per-MV
                # state-bytes gauge refresh both ride the checkpoint:
                # state only moves at checkpoints
                TOPOLOGY.checkpoint_verify()
                COSTS.publish_state_bytes()

    async def inject_and_collect(
            self, mutation: Optional[Mutation] = None,
            force_checkpoint: bool = False,
            drain_uploader: bool = True,
            on_inject: Optional[Callable[[], None]] = None) -> Barrier:
        await self.inject(mutation, force_checkpoint, on_inject)
        # drain everything in flight, oldest first
        barrier = None
        while self._in_flight:
            barrier = await self.collect_next()
        assert barrier is not None
        # explicit stepping keeps its synchronous contract: the barrier
        # this returns is DURABLY committed (tests/DDL read
        # committed_epoch right after). The serving heartbeat passes
        # drain_uploader=False: it waits for the tail itself, outside
        # the session's barrier lock and inside its tick, before its
        # next inject (HeartbeatTick.tail). Pipelined drivers use
        # inject()/collect_next() directly, draining only at the end.
        if drain_uploader:
            await self.uploader.drain()
        return barrier

    async def checkpoint(self) -> Barrier:
        """Force a durable checkpoint barrier and wait for it — the
        uploader is drained, so every collected epoch has committed."""
        return await self.inject_and_collect(force_checkpoint=True)

    # -- background loop -------------------------------------------------
    async def run(self, stop_after: Optional[int] = None) -> None:
        """Tick-inject-collect until `stop()` (or `stop_after` barriers).

        Injection and collection are pipelined: a new barrier is injected
        on schedule as long as the in-flight window has room.
        """
        n = 0
        collector = None
        interval = self.interval_ms / 1000
        next_tick = self.monotonic()      # first barrier fires immediately
        try:
            while not self._stopped and (stop_after is None
                                         or n < stop_after):
                if self.monotonic() >= next_tick:
                    # the tick schedule survives fast collections: barriers
                    # are injected at interval rate, not collection rate
                    if len(self._in_flight) < self.in_flight_barrier_nums:
                        await self.inject()
                        n += 1
                    next_tick = max(next_tick + interval, self.monotonic())
                if collector is None and self._in_flight:
                    collector = asyncio.ensure_future(self.collect_next())
                delay = max(0.0, next_tick - self.monotonic())
                sleeper = asyncio.ensure_future(self.sleep(delay))
                waits = {sleeper} | ({collector} if collector else set())
                done, _ = await asyncio.wait(
                    waits, return_when=asyncio.FIRST_COMPLETED)
                if collector in done:
                    collector.result()
                    collector = None
                if sleeper not in done:
                    sleeper.cancel()
            # drain: a running collector holds an epoch already popped from
            # _in_flight — await it too, or the last epoch never commits
            while collector is not None or self._in_flight:
                if collector is not None:
                    await collector
                    collector = None
                else:
                    await self.collect_next()
            # and the async tail: uploads still in flight at stop()
            # must land (in order) before run() returns, or the last
            # collected epochs never commit
            await self.uploader.drain()
        finally:
            if collector is not None:
                collector.cancel()

    def stop(self) -> None:
        self._stopped = True
