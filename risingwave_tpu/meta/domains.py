"""Barrier domains: per-fragment alignment + cross-domain checkpoints.

The pipelined-epoch redesign (ISSUE 13; ROADMAP item 2a — the
Hazelcast-Jet stance of arxiv 2103.10169 that p99 is a pipeline-
occupancy problem): the deployed actor graph is partitioned into
independent **alignment domains** by dataflow reachability — jobs that
share actors, chain edges, MV dependencies or a source stay joined;
everything else gets its own domain. Each domain runs its own
``BarrierLoop`` (own epoch cursor, own in-flight window), so a slow
fragment's barrier holds only its own domain instead of every actor in
the deployment, while **checkpoint barriers stay a cross-domain aligned
event on their own cadence** — durability no longer forces the global
lockstep that plain barriers just escaped.

Three mechanisms keep the shared store honest under concurrent epochs:

- **Shared epoch allocation.** All domains mint epochs from ONE
  monotone ``EpochAllocator``, so epoch values are globally unique,
  globally ordered, and always above the committed floor. A domain's
  barrier pair is consecutive *within its domain*; across domains the
  values interleave.
- **Low-watermark sealing.** The store's seal fence (`seal_epoch`) is
  a single watermark: writes at or below it are rejected and imms
  drain cumulatively. A per-domain eager seal would fence out a
  sibling domain's still-open epoch, so the allocator advances the
  fence only to the **cross-domain low watermark** — the largest epoch
  below every outstanding (allocated-but-unfinished) epoch.
- **Aligned checkpoint submission.** ONE checkpoint uploader serves
  the store. At a checkpoint round every domain injects a CHECKPOINT
  barrier; once all domains collected, everything at or below
  ``min(outstanding) - 1`` is sealed, and the plane submits that floor
  as one epoch to the async uploader. Recovery therefore aligns every
  domain to the same committed floor — each rebuilt domain's initial
  barrier recovers ``prev = committed``.

The ``stream_epoch_pipeline=off`` arm bypasses this module entirely
(one plain ``BarrierLoop``), reproducing the historical global
lockstep bit-identically as the oracle.
"""

from __future__ import annotations

import asyncio
import bisect
import time
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from risingwave_tpu.common.epoch import Epoch
from risingwave_tpu.common.errors import PlanError
from risingwave_tpu.meta.barrier import (
    BarrierLoop, BarrierStats, EpochProfile, EpochProfiler,
    record_checkpoint_tail,
)
from risingwave_tpu.storage.uploader import CheckpointUploader
from risingwave_tpu.stream.message import Barrier, Mutation, StopMutation


def parse_epoch_pipeline(spec: str) -> bool:
    """'on'|'off' → bool (SET stream_epoch_pipeline validator)."""
    s = str(spec).strip().lower()
    if s in ("on", "true", "1"):
        return True
    if s in ("off", "false", "0"):
        return False
    raise PlanError(
        f"stream_epoch_pipeline must be on|off, got {spec!r}")


class EpochAllocator:
    """Shared monotone epoch source + low-watermark seal gate.

    ``allocate`` hands out globally-unique epoch values (physical time
    when it advances, +1 sequence otherwise — the epoch.rs shape) and
    tracks them as *outstanding* until the domain that owns them
    reports the epoch ended (its successor barrier collected, so no
    more writes can land there). The store's seal fence advances to
    ``min(outstanding) - 1`` — the largest epoch no open writer can
    still touch."""

    def __init__(self, store):
        self.store = store
        committed = int(store.committed_epoch())
        self._last = committed
        self._sealed = max(committed,
                           int(getattr(store, "_sealed_epoch", 0) or 0))
        self._outstanding: List[int] = []      # sorted, allocated+open
        self._domain_of: Dict[int, str] = {}
        # merge re-anchoring: absorbed domains' frontier epochs end
        # together with the target frontier that superseded them
        # (their last writes flush during the first merged round)
        self._end_with: Dict[int, List[int]] = {}

    # -- allocation ----------------------------------------------------
    def allocate(self, domain: str = "") -> Epoch:
        e = Epoch.now()
        v = max(e.value, self._last + 1)
        self._last = v
        bisect.insort(self._outstanding, v)
        self._domain_of[v] = domain
        return Epoch(v)

    def reserve_to(self, value: int) -> None:
        """Burn every epoch ≤ value (out-of-band bulk ingest)."""
        if value > self._last:
            self._last = value

    def domain_of(self, value: int) -> Optional[str]:
        return self._domain_of.get(value)

    # -- lifecycle -----------------------------------------------------
    def note_ended(self, value: int, is_checkpoint: bool = False) -> None:
        """The epoch's writes are complete (its successor barrier
        collected). Unknown values — recovered/committed prevs that
        were never allocated here — are ignored."""
        for alias in self._end_with.pop(value, ()):
            self._pop(alias)
        if self._pop(value):
            self._advance_seal(is_checkpoint)

    def _pop(self, value: int) -> bool:
        i = bisect.bisect_left(self._outstanding, value)
        if i < len(self._outstanding) and self._outstanding[i] == value:
            self._outstanding.pop(i)
            self._domain_of.pop(value, None)
            return True
        return False

    def alias_end(self, value: int, with_value: int) -> None:
        """End ``value`` together with ``with_value`` (domain merge:
        the absorbed frontier's last writes flush during the first
        merged barrier round, which ends ``with_value``)."""
        if value == with_value:
            return
        self._end_with.setdefault(with_value, []).append(value)

    def write_floor(self) -> int:
        """Largest epoch no open writer can still touch."""
        return (self._outstanding[0] - 1) if self._outstanding \
            else self._last

    def _advance_seal(self, is_checkpoint: bool) -> None:
        floor = self.write_floor()
        if floor > self._sealed:
            self._sealed = floor
            self.store.seal_epoch(floor, is_checkpoint)

    def outstanding(self) -> List[int]:
        return list(self._outstanding)


class _Domain:
    """One alignment domain: its loop + member bookkeeping."""

    __slots__ = ("name", "loop", "senders", "expected", "actors",
                 "jobs", "rounds_since_checkpoint")

    def __init__(self, name: str, loop: BarrierLoop):
        self.name = name
        self.loop = loop
        self.senders: Set[int] = set()     # barrier-sender actor ids
        self.expected: Set[int] = set()    # collection-expected ids
        self.actors: Set[int] = set()      # every actor id (routing)
        self.jobs: Set[str] = set()
        # pipelined-driver cadence counter (the facade inject()/drive
        # paths promote every k-th injection to a checkpoint barrier;
        # aligned rounds use the plane-global counter instead)
        self.rounds_since_checkpoint = 0


class BarrierPlane:
    """Per-domain barrier engine with cross-domain checkpoint cadence.

    Exposes the ``BarrierLoop`` driving surface (``inject_and_collect``
    / ``inject`` / ``collect_next`` / ``stats`` / ``profiler`` /
    ``uploader`` / ``committed_epoch``) so sessions and tests
    that held a loop hold a plane unchanged. Plain rounds run every
    domain CONCURRENTLY — a slow domain's collect no longer serializes
    its neighbors' rounds — and every ``checkpoint_frequency``-th round
    (or any forced/mutation round) is an aligned checkpoint."""

    def __init__(self, local, store,
                 checkpoint_frequency: int = 1,
                 in_flight_barrier_nums: int = 10,
                 slow_barrier_threshold_s: float = 1.0,
                 max_uploading: int = 4,
                 collect_timeout_s: Optional[float] = None,
                 distributed: bool = False,
                 monotonic: Callable[[], float] = time.monotonic):
        self.local = local
        self.store = store
        self.monotonic = monotonic
        # a plane in the process means domain merges can monotonely
        # re-anchor live chains — state tables must accept prev > curr
        # (sticky: the strict guard returns only in plane-free procs)
        from risingwave_tpu.state.state_table import (
            allow_monotone_reanchor,
        )
        allow_monotone_reanchor(True)
        self.allocator = EpochAllocator(store)
        self.stats = BarrierStats()
        self.profiler = EpochProfiler(slow_barrier_threshold_s)
        self.slow_barrier_threshold_s = slow_barrier_threshold_s
        self.uploader = CheckpointUploader(
            store, max_uploading=max_uploading, monotonic=monotonic,
            on_commit=self._on_epoch_committed)
        self.checkpoint_frequency = max(1, checkpoint_frequency)
        self.in_flight_barrier_nums = max(1, in_flight_barrier_nums)
        self.collect_timeout_s = collect_timeout_s
        self.distributed = distributed
        self._domains: Dict[str, _Domain] = {}
        self._job_domain: Dict[str, str] = {}
        self._job_keys: Dict[str, Set[str]] = {}
        self._job_members: Dict[str, Tuple[Set[int], Set[int],
                                           Set[int]]] = {}
        self._key_owner: Dict[str, str] = {}
        self._rounds_since_checkpoint = 0
        # domain → (sealed prev, profile) of checkpoint barriers whose
        # durability submission is still pending; consumed by
        # _maybe_submit once the write floor covers them
        self._pending_ckpt: Dict[str, Tuple[int,
                                            Optional[EpochProfile]]] = {}
        self._upload_profiles: Dict[int, List[EpochProfile]] = {}
        self._submitted = int(store.committed_epoch())
        # distributed hook: awaited with the aligned floor BEFORE the
        # coordinator watermark advances (the Cluster fans seal_sync
        # out to every worker here, so the floor is durable everywhere
        # before recovery could ever trust it)
        self.aligned_hook = None

    # -- BarrierLoop-compatible surface --------------------------------
    @property
    def committed_epoch(self) -> int:
        return self.store.committed_epoch()

    @property
    def in_flight_count(self) -> int:
        return max((d.loop.in_flight_count
                    for d in self._domains.values()), default=0)

    @property
    def uploading_count(self) -> int:
        return self.uploader.depth

    def frontier_epoch(self) -> int:
        return max([self.allocator._last]
                   + [d.loop.frontier_epoch()
                      for d in self._domains.values()])

    def advance_epoch_to(self, value: int) -> None:
        """Reserve every epoch ≤ value in the shared allocator. Unlike
        the single-loop version this must NOT touch domain cursors: a
        live domain's frontier epoch still has flushes pending, and
        overwriting the cursor would orphan it in the outstanding set
        — the write floor (and with it every later commit) would
        freeze below the leaked epoch forever."""
        for d in self._domains.values():
            assert not d.loop.in_flight_count, \
                "advance with barriers in flight"
        self.allocator.reserve_to(value)

    def advance_domain_to(self, domain: str, value: int) -> None:
        """Pin one domain's cursor past out-of-band committed epochs
        (reschedule state handoff: the redeployed domain's first
        barrier must READ at/above the handoff ingest epochs, which
        land above the coordinator's committed floor). A redeployed
        job may have joined a LIVE shared domain (sibling jobs on the
        same source): the live frontier still has the siblings'
        pending flushes, so it ends together with the advanced epoch
        (the next barrier's prev) rather than being orphaned in the
        outstanding set."""
        loop = self._domains[domain].loop
        assert not loop.in_flight_count, \
            "advance with barriers in flight"
        f = loop.frontier_epoch()
        if 0 < f < value:
            self.allocator.alias_end(f, value)
        self.allocator.reserve_to(value)
        loop.advance_epoch_to(value)

    @property
    def last_allocated(self) -> int:
        return self.allocator._last

    # -- domain membership ---------------------------------------------
    def scope(self, domain: str) -> Tuple[Optional[Sequence[int]],
                                          Optional[Sequence[int]]]:
        """(sender_ids, expected) for one domain's barriers — what its
        loop passes to ``LocalBarrierManager.send_barrier``."""
        d = self._domains.get(domain)
        if d is None:
            return (), ()
        return sorted(d.senders), sorted(d.expected)

    def domains(self) -> List[str]:
        return list(self._domains)

    def domain_of_job(self, job: str) -> Optional[str]:
        return self._job_domain.get(job)

    def jobs_of_domain(self, domain: str) -> List[str]:
        """Jobs aligned in one domain (the reschedule path stops and
        redeploys a domain's whole cohort together)."""
        d = self._domains.get(domain)
        return sorted(d.jobs) if d is not None else []

    def domain_actors(self, domain: str) -> Set[int]:
        d = self._domains.get(domain)
        return set(d.actors) if d is not None else set()

    def set_domain_channel(self, domain: str,
                           sender_ids: Sequence[int]) -> None:
        """Distributed wiring (cluster/scheduler.py): a domain's
        barriers flow through per-domain worker channels — one pseudo
        actor per (domain, slot) — rather than per-job source senders.
        Replaces the domain's sender/expected sets wholesale."""
        d = self._domains[domain]
        d.senders = set(sender_ids)
        d.expected = set(sender_ids)
        d.actors |= set(sender_ids)

    def _new_loop(self, name: str) -> BarrierLoop:
        return BarrierLoop(
            self.local, self.store,
            in_flight_barrier_nums=self.in_flight_barrier_nums,
            slow_barrier_threshold_s=self.slow_barrier_threshold_s,
            collect_timeout_s=self.collect_timeout_s,
            distributed=self.distributed,
            monotonic=self.monotonic,
            domain=name, plane=self,
            stats=self.stats, profiler=self.profiler)

    def _ensure_default(self) -> _Domain:
        """Zero-job sessions still heartbeat: a default domain with no
        members collects trivially (the legacy zero-actor shape)."""
        if not self._domains:
            self._domains[""] = _Domain("", self._new_loop(""))
        return next(iter(self._domains.values()))

    def assign_job(self, job: str, keys: Sequence[str],
                   sender_ids: Sequence[int],
                   expected_ids: Sequence[int],
                   actor_ids: Optional[Sequence[int]] = None) -> str:
        """Place one deployed job into its alignment domain.

        ``keys`` are the job's reachability anchors (its own name, its
        source names, its MV dependencies). Any existing domain owning
        one of the keys absorbs the job; keys spanning several domains
        merge them (dataflow turned out to be connected after all).
        Returns the domain id."""
        keys = set(keys) | {job}
        owners = {self._key_owner[k] for k in keys
                  if k in self._key_owner}
        owners = {o for o in owners if o in self._domains}
        if not owners:
            name = job
            # never collide with a live domain name (job names are
            # unique in the catalog, but a default "" domain exists)
            while name in self._domains:
                name += "+"
            d = self._domains[name] = _Domain(name, self._new_loop(name))
        elif len(owners) == 1:
            d = self._domains[next(iter(owners))]
        else:
            d = self._merge(sorted(owners))
        senders = set(sender_ids)
        expected = set(expected_ids)
        actors = set(actor_ids) if actor_ids is not None else set()
        d.senders |= senders
        d.expected |= expected
        d.actors |= senders | expected | actors
        d.jobs.add(job)
        self._job_domain[job] = d.name
        self._job_keys[job] = keys
        self._job_members[job] = (senders, expected,
                                  actors | senders | expected)
        for k in keys:
            self._key_owner[k] = d.name
        # a lone empty default domain is superseded by the first real
        # one (it never flowed data; dropping it keeps rounds tight)
        empty = self._domains.get("")
        if empty is not None and not empty.jobs \
                and len(self._domains) > 1:
            self._retire("")
        return d.name

    def _merge(self, names: List[str]) -> _Domain:
        """Collapse several live domains into one. The survivor is the
        domain with the LARGEST epoch frontier: after the merge its
        next barrier carries ``prev = max frontier``, which every
        absorbed chain's state tables accept (monotone re-anchor —
        state_table.commit's ``prev >= curr`` contract) while their
        final writes land at their old frontiers, still under the seal
        fence until the first merged round ends them."""
        doms = [self._domains[n] for n in names]
        for d in doms:
            assert not d.loop.in_flight_count, \
                f"domain merge with barriers in flight in {d.name!r}"
        target = max(doms, key=lambda d: d.loop.frontier_epoch())
        t_front = target.loop.frontier_epoch()
        for d in doms:
            if d is target:
                continue
            f = d.loop.frontier_epoch()
            # survivor selection guarantees the target carries the
            # max frontier, so an absorbed f > 0 implies t_front >= f
            assert f <= t_front, (f, t_front)
            if 0 < f < t_front:
                self.allocator.alias_end(f, t_front)
            target.senders |= d.senders
            target.expected |= d.expected
            target.actors |= d.actors
            target.jobs |= d.jobs
            for j in d.jobs:
                self._job_domain[j] = target.name
            del self._domains[d.name]
        for j, ks in self._job_keys.items():
            if self._job_domain.get(j) == target.name:
                for k in ks:
                    self._key_owner[k] = target.name
        return target

    def remove_job(self, job: str) -> None:
        """Drop one job's members; retire its domain when empty (the
        frontier epoch is released so the seal fence never waits on a
        dead domain)."""
        name = self._job_domain.pop(job, None)
        self._job_keys.pop(job, None)
        members = self._job_members.pop(job, None)
        if name is None or name not in self._domains:
            return
        d = self._domains[name]
        d.jobs.discard(job)
        if members is not None:
            senders, expected, actors = members
            d.senders -= senders
            d.expected -= expected
            d.actors -= actors
        if not d.jobs:
            self._retire(name)
        self._rebuild_key_owner()

    def _retire(self, name: str) -> None:
        d = self._domains.pop(name, None)
        if d is None:
            return
        assert not d.loop.in_flight_count, \
            f"retiring domain {name!r} with barriers in flight"
        f = d.loop.frontier_epoch()
        if f > 0:
            # the stop barrier collected ⇒ its actors flushed and
            # terminated: nothing can write at the frontier anymore
            self.allocator.note_ended(f)

    def _rebuild_key_owner(self) -> None:
        self._key_owner = {}
        for j, ks in self._job_keys.items():
            dom = self._job_domain.get(j)
            if dom is not None:
                for k in ks:
                    self._key_owner[k] = dom

    # -- checkpoint plumbing -------------------------------------------
    def note_checkpoint_sealed(self, domain: str, prev: int,
                               prof: Optional[EpochProfile]) -> None:
        """A domain collected its checkpoint barrier of the current
        aligned round (called from its loop's collect path)."""
        self._pending_ckpt[domain] = (prev, prof)

    def _on_epoch_committed(self, epoch: int, upload_s: float,
                            stages: List[tuple]) -> None:
        record_checkpoint_tail(self._upload_profiles.pop(epoch, []),
                               epoch, upload_s, stages)

    async def _maybe_submit(self) -> None:
        """Submit the durability floor to the shared uploader once a
        sealed checkpoint is covered by it. After an aligned round the
        floor covers every domain's prev; under pipelined per-domain
        checkpoint driving it covers them as sibling windows drain —
        either way ONE floor epoch rides the uploader, and everything
        at or below it is sealed by construction."""
        floor = self.allocator.write_floor()
        if floor <= max(self.store.committed_epoch(), self._submitted):
            return
        covered = [d for d, (prev, _p) in self._pending_ckpt.items()
                   if prev <= floor]
        if not covered:
            return
        profs = [p for p in (self._pending_ckpt.pop(d)[1]
                             for d in covered) if p is not None]
        self._submitted = floor
        if self.aligned_hook is not None:
            # distributed: the floor becomes durable on every worker
            # BEFORE the coordinator watermark can advance to it
            await self.aligned_hook(floor)
        self._upload_profiles[floor] = profs
        if not await self.uploader.submit(floor):
            self._upload_profiles.pop(floor, None)
        else:
            depth = self.uploader.depth
            for p in profs:
                p.queue_depth = depth

    # -- rounds --------------------------------------------------------
    def _route_mutation(self, mutation: Optional[Mutation]
                        ) -> Dict[str, Optional[Mutation]]:
        """Which domains carry the mutation. Stop barriers ride only
        the domains owning the stopped actors (a foreign domain must
        not wait on actors it never drives); pause/resume and everything
        else broadcast."""
        doms = list(self._domains.values())
        if isinstance(mutation, StopMutation):
            out = {}
            for d in doms:
                hit = bool(d.actors & mutation.actors) \
                    or bool(d.expected & mutation.actors)
                out[d.name] = mutation if hit else None
            if not any(out.values()) and doms:
                # unknown actors (e.g. pure pseudo-actor stop sets):
                # broadcast rather than silently dropping the command
                out = {d.name: mutation for d in doms}
            return out
        return {d.name: mutation for d in doms}

    async def _domain_round(self, d: _Domain,
                            mutation: Optional[Mutation],
                            force_checkpoint: bool,
                            on_inject=None) -> Barrier:
        await d.loop.inject(mutation, force_checkpoint, on_inject)
        barrier = None
        while d.loop.in_flight_count:
            barrier = await d.loop.collect_next()
        assert barrier is not None
        return barrier

    async def _gather_rounds(self, routed: Dict[str,
                                                Optional[Mutation]],
                             force_checkpoint: bool,
                             on_inject=None) -> Barrier:
        tasks = [self._domain_round(self._domains[n], m,
                                    force_checkpoint, on_inject)
                 for n, m in routed.items() if n in self._domains]
        results = await asyncio.gather(*tasks, return_exceptions=True)
        barrier = None
        failure = None
        for r in results:
            if isinstance(r, BaseException):
                failure = failure or r
            else:
                barrier = r
        if failure is not None:
            raise failure
        assert barrier is not None
        return barrier

    async def inject_and_collect(
            self, mutation: Optional[Mutation] = None,
            force_checkpoint: bool = False,
            drain_uploader: bool = True,
            on_inject=None) -> Barrier:
        """One barrier round. Plain rounds run per-domain concurrently;
        forced/mutation rounds — and every ``checkpoint_frequency``-th
        plain round — align every domain on a checkpoint. ``on_inject``
        is called as each domain's barrier is injected
        (``BarrierLoop.inject``)."""
        self._ensure_default()
        checkpoint = force_checkpoint or mutation is not None
        if not checkpoint:
            self._rounds_since_checkpoint += 1
            if self._rounds_since_checkpoint >= self.checkpoint_frequency:
                checkpoint = True
        if checkpoint:
            self._rounds_since_checkpoint = 0
            # drain stragglers a pipelining driver may have left in
            # domain windows: an aligned round starts clean
            for d in self._domains.values():
                while d.loop.in_flight_count:
                    await d.loop.collect_next()
            routed = self._route_mutation(mutation)
            barrier = await self._gather_rounds(
                routed, force_checkpoint=True, on_inject=on_inject)
            await self._maybe_submit()
        else:
            routed = {d.name: None for d in self._domains.values()}
            barrier = await self._gather_rounds(
                routed, force_checkpoint=False, on_inject=on_inject)
        if drain_uploader:
            await self.uploader.drain()
        return barrier

    async def checkpoint(self) -> Barrier:
        return await self.inject_and_collect(force_checkpoint=True)

    # -- pipelined driving (tests) -------------------------------------
    def _cadence_checkpoint(self, d: _Domain,
                            force_checkpoint: bool) -> bool:
        """Per-domain checkpoint cadence for pipelined injection:
        every ``checkpoint_frequency``-th barrier of a domain is a
        checkpoint even without global alignment — the floor-based
        submit makes unaligned checkpoint prevs durable as sibling
        windows drain, so pipelined drivers keep the same durability
        cadence the single-loop engine had (frequency 1 = every
        barrier, the historical default)."""
        if force_checkpoint:
            d.rounds_since_checkpoint = 0
            return True
        d.rounds_since_checkpoint += 1
        if d.rounds_since_checkpoint >= self.checkpoint_frequency:
            d.rounds_since_checkpoint = 0
            return True
        return False

    async def inject(self, mutation: Optional[Mutation] = None,
                     force_checkpoint: bool = False) -> Barrier:
        """Widen every domain's in-flight window by one barrier (the
        pipelined-driver facade: ``while in_flight < W: inject`` keeps
        every domain's window full). Checkpoint cadence applies
        per-domain."""
        self._ensure_default()
        barrier = None
        for d in self._domains.values():
            barrier = await d.loop.inject(
                mutation, self._cadence_checkpoint(d, force_checkpoint))
        assert barrier is not None
        return barrier

    async def collect_next(self) -> Barrier:
        """Collect the oldest in-flight barrier of EVERY domain that
        has one, concurrently — the pipelined driver's collect step."""
        pending = [d.loop.collect_next()
                   for d in self._domains.values()
                   if d.loop.in_flight_count]
        assert pending, "nothing in flight"
        results = await asyncio.gather(*pending,
                                       return_exceptions=True)
        barrier = None
        failure = None
        for r in results:
            if isinstance(r, BaseException):
                failure = failure or r
            else:
                barrier = r
        if failure is not None:
            raise failure
        assert barrier is not None
        # pipelined checkpoint driving (inject(force_checkpoint=True)
        # + collect_next) must still reach durability
        await self._maybe_submit()
        return barrier

    async def drive(self, done_fn: Callable[[], bool],
                    in_flight: int = 2,
                    max_epochs_per_domain: int = 500,
                    progress_fn: Optional[Callable[[], object]] = None
                    ) -> int:
        """Drive every domain INDEPENDENTLY until ``done_fn()``: each
        domain keeps its own window full and collects at its own pace —
        the intra-plane overlap a shared round-robin driver cannot
        express (a fast domain ticks at its own rate while a slow
        neighbor's epoch is still in flight). ``progress_fn`` (e.g.
        total source rows) resets the per-domain stall guard whenever
        it changes: an exhausted domain idling while a sibling still
        works is not a stall. Returns barriers driven."""
        self._ensure_default()
        total = [0]
        progress = [progress_fn() if progress_fn is not None else None]

        async def pump(d: _Domain) -> None:
            injected = 0
            while not done_fn():
                if progress_fn is not None:
                    p = progress_fn()
                    if p != progress[0]:
                        progress[0] = p
                        injected = 0
                if injected >= max_epochs_per_domain:
                    raise RuntimeError(
                        f"domain {d.name!r}: sources stalled after "
                        f"{injected} epochs without progress")
                t0 = time.perf_counter()
                while d.loop.in_flight_count < max(1, in_flight):
                    await d.loop.inject(
                        force_checkpoint=self._cadence_checkpoint(
                            d, False))
                    injected += 1
                await d.loop.collect_next()
                await self._maybe_submit()
                total[0] += 1
                if time.perf_counter() - t0 < 0.002:
                    # exhausted domain: its sources are drained and
                    # rounds collect trivially — idle instead of
                    # busy-spinning the shared event loop (which would
                    # both steal CPU from working siblings and flood
                    # the stats with junk sub-millisecond epochs)
                    await asyncio.sleep(0.01)
            while d.loop.in_flight_count:
                await d.loop.collect_next()
                await self._maybe_submit()
                total[0] += 1

        results = await asyncio.gather(
            *(pump(d) for d in list(self._domains.values())),
            return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException):
                raise r
        return total[0]

    # -- introspection --------------------------------------------------
    def p99_by_domain(self) -> Dict[str, float]:
        return self.profiler.p99_by_domain()

    def describe(self) -> List[dict]:
        """One dict per domain."""
        return [{
            "domain": d.name,
            "jobs": sorted(d.jobs),
            "actors": len(d.actors),
            "frontier_epoch": d.loop.frontier_epoch(),
            "in_flight": d.loop.in_flight_count,
        } for d in self._domains.values()]
