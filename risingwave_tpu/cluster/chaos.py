"""Deterministic chaos harness: a seeded fault schedule replayed
against a distributed session.

The madsim stance (SURVEY §4, already adopted by utils/failpoint.py):
fault schedules are DETERMINISTIC and reproducible — a chaos run is an
experiment you can replay, not a dice roll you describe. A seed fully
determines the schedule (which faults, at which barrier steps, on
which worker slots), every fault is injected at a step boundary, and
the recovery supervisor's classification of each induced failure is a
function of the fault — so the same seed reproduces the same
(cause, action) recovery sequence, which tests assert literally.

Fault vocabulary (each exercises one rung of the response ladder):

- ``flake_object_store`` — one transient PUT failure inside a worker,
  UNDER the RetryingObjectStore budget: absorbed in place, retry
  metrics move, NO recovery event.
- ``kill_worker`` — SIGKILL one worker subprocess mid-epoch: the next
  barrier round fails, the supervisor classifies ``dead_worker`` and
  respawns only the dead slot (live slots reset in place).
- ``fail_upload`` — a worker's checkpoint upload fails PAST the retry
  budget: surfaces as a worker-side OSError, classified
  ``storage_fault``, full recovery (which also replaces the faulty
  process, healing the injected fault — like swapping a dying disk).
- ``straggler`` — one executor sleeps past the barrier collect
  timeout: ``BarrierWedgedError``, classified ``wedged_barrier``,
  full recovery.

Mid-rescale faults (ISSUE 15 — every scaling action chaos-tested the
same way the recovery ladder was proven; each event arms the fault
THEN drives a guarded rescale through the session's ALTER path, the
same protocol the autoscaler drives):

- ``kill_mid_rescale`` — SIGKILL one worker exactly at the cohort
  REDEPLOY phase (the cluster's one-shot rescale fault hook). The
  rollback cannot complete against a dead slot, so the supervised
  ladder finishes the job: ``dead_worker``/respawn at the prior
  topology, with the rollback attempt in ``rw_recovery``.
- ``fault_mid_handoff`` — one worker's ``ingest_table`` RPC raises
  during the STATE HANDOFF (worker.rpc failpoint, times=1): the
  guarded rescale reverses the moved rows from its in-memory log and
  rolls back to the prior parallelism — no recovery needed, the
  domain keeps serving.
- ``straggler_mid_rescale`` — an executor sleeps past the collect
  timeout under the rescale's STOP barrier: the failure lands before
  any change (``phase="stop"``), the domain's health is unknown, and
  the supervisor answers ``wedged_barrier``/full.

Compactor-domain faults (ISSUE 19 — the dedicated compaction subsystem
rides the same ladder; both kinds require ``storage_compaction =
'dedicated'`` on the session under test):

- ``kill_compactor_mid_task`` — SIGKILL the compactor subprocess while
  a leased task may be in flight: the next ``compaction_tick``
  respawns the role, the orphaned lease expires and the task REQUEUES
  against the current version. Classified ``compactor_dead``/requeue
  in ``rw_recovery`` — a COMPACTOR-domain entry, never a serving
  recovery (the storm gate doesn't budget it, serving never stalls).
- ``storage_fault_during_vacuum`` — a worker's ``hummock.vacuum``
  failpoint raises during retired-SST deletion: pin-exact GC is
  delay-only (each entry deletes under its own try), so garbage
  lingers until a later vacuum pass and NO recovery of any kind is
  recorded.

Sink-domain faults (ISSUE 20 — the exactly-once epoch-segment sink
chaos-proven on both halves of its visibility rule; the schedule's
``rescale_mv`` names the SINK job when these kinds are present):

- ``kill_writer_mid_stage`` — wedge one writer INSIDE its synchronous
  segment stage (``sink.stage.mid`` sleep, fired at barrier passage
  before collection), then SIGKILL the slot while it sleeps there.
  The epoch's segment is absent or torn and UNMANIFESTED, the barrier
  round fails, ``dead_worker``/respawn — and the recovery sweep
  truncates the orphaned staging, so the epoch's rows replay under a
  fresh epoch. Exactly-once half one: nothing uncommitted is visible.
- ``fault_manifest_commit`` — the COORDINATOR's manifest PUT raises
  once during ``commit_upto`` (in-process failpoint: the commit half
  runs on the barrier owner, not in workers). The checkpoint floor
  has already advanced past the epoch, so recovery PROMOTES it from
  the durable staged listing. Exactly-once half two: a floor-covered
  epoch is never lost, and the idempotent manifest re-PUT never
  duplicates.
- ``rescale_sink_fragment`` — a clean guarded rescale of the sink
  job's fragment mid-stream (the session ALTER path): stop-and-align
  forces a checkpoint (staged + committed through the stop barrier),
  redeploy re-stamps writer ranks, and the output must stay oracle-
  identical across the N-writers → M-writers handoff.

Faults inject into LIVE worker processes over the control channel's
``arm_failpoints`` verb (exception specs are JSON — the failpoint
env/wire restriction), so a respawned worker always comes back clean.
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from risingwave_tpu.meta.supervisor import RecoveryEvent

# absorbable flake: strictly under RetryingObjectStore's default
# retry budget (3) so the bottom rung provably swallows it
_FLAKE_TIMES = 1
# terminal upload fault: strictly past the same budget
_FAULT_TIMES = 16


@dataclass(frozen=True)
class ChaosEvent:
    """One scheduled fault: injected right before barrier `step`."""

    step: int
    kind: str          # flake_object_store|kill_worker|fail_upload|straggler
    slot: int

    def row(self) -> tuple:
        return (self.step, self.kind, self.slot)


def generate_schedule(seed: int, n_workers: int = 2,
                      steps: int = 24,
                      kinds: Optional[List[str]] = None
                      ) -> List[ChaosEvent]:
    """Seeded schedule with guaranteed coverage: one fault of every
    kind (default: flake + SIGKILL + upload fault + straggler — the
    acceptance mix), at distinct PRNG-drawn steps/slots. Same seed ⇒
    same schedule, byte for byte."""
    rng = random.Random(seed)
    kinds = list(kinds if kinds is not None else (
        "flake_object_store", "kill_worker", "fail_upload",
        "straggler"))
    # termination bound for the rejection sampling below: each accepted
    # pick blocks at most 3 candidate steps (itself ±1), so the
    # candidate range (steps - 2 values) must outlast
    # 3 * (len(kinds) - 1) blocked ones with one to spare
    if steps < 3 * len(kinds):
        raise ValueError(
            f"schedule too dense: {len(kinds)} fault kinds need "
            f"steps >= {3 * len(kinds)}, got {steps}")
    # distinct steps, ≥2 apart, leaving step 0/1 for pipeline spin-up:
    # two faults in the same round would make WHICH failure surfaces
    # first racy, and determinism of the recovery sequence is the point
    picks: List[int] = []
    while len(picks) < len(kinds):
        s = rng.randrange(2, steps)
        if all(abs(s - p) >= 2 for p in picks):
            picks.append(s)
    rng.shuffle(kinds)
    return sorted(
        (ChaosEvent(s, k, rng.randrange(n_workers))
         for s, k in zip(picks, kinds)),
        key=lambda e: (e.step, e.kind))


# mid-rescale fault kinds: each arms its fault, then drives a guarded
# rescale (the session ALTER path — the same protocol the autoscaler
# drives) so the fault lands inside the named rescale phase
RESCALE_KINDS = frozenset({"kill_mid_rescale", "fault_mid_handoff",
                           "straggler_mid_rescale"})

# compactor-domain fault kinds (ISSUE 19): only meaningful when the
# session under test runs storage_compaction='dedicated'
COMPACTOR_KINDS = frozenset({"kill_compactor_mid_task",
                             "storage_fault_during_vacuum"})

# sink-domain fault kinds (ISSUE 20): exercise both halves of the
# epoch-segment visibility rule plus the rescale handoff; the schedule
# needs rescale_mv = the SINK job's name for the rescale kind
SINK_KINDS = frozenset({"kill_writer_mid_stage",
                        "fault_manifest_commit",
                        "rescale_sink_fragment"})

# how long the wedged writer sleeps inside stage() vs. how long the
# harness waits before SIGKILLing the slot: the kill must land while
# the writer is provably INSIDE the staging window
_STAGE_WEDGE_S = 1.5
_STAGE_KILL_AFTER_S = 0.4


@dataclass
class ChaosReport:
    """What a chaos run produced — the determinism assertion's
    subject."""

    seed: int
    events: List[tuple] = field(default_factory=list)    # applied
    recoveries: List[tuple] = field(default_factory=list)  # (cause, action)
    mttr_s: List[float] = field(default_factory=list)
    absorbed_retries: Dict[str, float] = field(default_factory=dict)
    # guarded rescales that unwound in place: (phase, rolled_back) —
    # rolled_back=True means no recovery was needed
    rescale_rollbacks: List[tuple] = field(default_factory=list)
    wall_s: float = 0.0

    def summary(self) -> dict:
        return {
            "seed": self.seed,
            "wall_s": self.wall_s,
            "events": [list(e) for e in self.events],
            "recoveries": [list(r) for r in self.recoveries],
            "recovery_count": len(self.recoveries),
            "mttr_mean_s": (sum(self.mttr_s) / len(self.mttr_s)
                            if self.mttr_s else 0.0),
            "mttr_max_s": max(self.mttr_s, default=0.0),
            "absorbed_retries": dict(self.absorbed_retries),
            "rescale_rollbacks": [list(r)
                                  for r in self.rescale_rollbacks],
        }


class ChaosRunner:
    """Replay a schedule against a DistFrontend: inject each fault at
    its step boundary, drive barriers, feed every failure to the
    supervised-recovery path, then settle the pipeline to completion.
    The caller owns the oracle comparison (and the frontend)."""

    def __init__(self, fe, schedule: List[ChaosEvent], seed: int,
                 steps: int = 24, settle_steps: int = 40,
                 rescale_mv: Optional[str] = None):
        self.fe = fe
        self.schedule = list(schedule)
        self.seed = seed
        self.steps = steps
        self.settle_steps = settle_steps
        # the MV whose guarded rescale the mid-rescale faults target
        # (required when the schedule contains RESCALE_KINDS)
        self.rescale_mv = rescale_mv
        # delayed-SIGKILL task for kill_writer_mid_stage: fired during
        # the NEXT barrier step (while the wedged writer sleeps inside
        # stage()); awaited before the report returns
        self._pending_kill = None
        if any(e.kind in RESCALE_KINDS
               or e.kind == "rescale_sink_fragment"
               for e in self.schedule):
            assert rescale_mv is not None, (
                "a mid-rescale fault schedule needs rescale_mv")
        if any(e.kind in COMPACTOR_KINDS for e in self.schedule):
            assert fe.cluster._compaction_mode == "dedicated", (
                "a compactor fault schedule needs the session under "
                "test to SET storage_compaction = 'dedicated' first")
        if any(e.kind in ("straggler", "straggler_mid_rescale")
               for e in self.schedule):
            assert fe.cluster.barrier_timeout_s is not None, (
                "a straggler fault needs wedged-barrier detection: "
                "construct the DistFrontend with barrier_timeout_s")

    async def _arm(self, slot: int, points: dict) -> None:
        await self.fe.cluster.clients[slot].call_idempotent(
            {"cmd": "arm_failpoints", "points": points})

    def _alter_target(self) -> int:
        """Deterministic rescale target: shrink a scaled job, grow a
        single-actor one (the first rescalable fragment decides)."""
        job = self.fe.cluster.jobs[self.rescale_mv]
        for fi, f in enumerate(job.graph.fragments):
            if self.fe.cluster._rescalable(f) \
                    or self.fe.cluster._source_rescalable(f):
                return 1 if len(job.placements[fi]) >= 2 else 2
        return 2

    async def _alter_supervised(self, report: ChaosReport) -> None:
        """Drive the guarded rescale with the fault armed. A clean
        rollback needs no recovery (the protocol's point); a rollback
        that could not complete feeds the supervised ladder like any
        other failure."""
        from risingwave_tpu.cluster.scheduler import RescaleError
        n = self._alter_target()
        try:
            await self.fe.execute(
                f"ALTER MATERIALIZED VIEW {self.rescale_mv} "
                f"SET PARALLELISM = {n}")
        except RescaleError as e:
            report.rescale_rollbacks.append((e.phase, e.rolled_back))
            if not e.rolled_back:
                rec = await self.fe.supervised_recover(e)
                report.recoveries.append((rec.cause, rec.action))
                report.mttr_s.append(rec.duration_s)
        except Exception as e:  # noqa: BLE001 — the supervisor's job
            rec = await self.fe.supervised_recover(e)
            report.recoveries.append((rec.cause, rec.action))
            report.mttr_s.append(rec.duration_s)

    async def _apply(self, ev: ChaosEvent,
                     report: ChaosReport) -> None:
        if ev.kind == "kill_worker":
            self.fe.cluster.kill_slot(ev.slot)
        elif ev.kind == "flake_object_store":
            await self._arm(ev.slot, {"object_store.upload": {
                "raise": "OSError", "msg": "chaos flake",
                "times": _FLAKE_TIMES}})
        elif ev.kind == "fail_upload":
            await self._arm(ev.slot, {"object_store.upload": {
                "raise": "OSError", "msg": "chaos upload fault",
                "times": _FAULT_TIMES}})
        elif ev.kind == "straggler":
            timeout = self.fe.cluster.barrier_timeout_s
            await self._arm(ev.slot, {"trace.slow.HashAggExecutor": {
                "sleep_s": timeout * 2.5, "times": 1}})
        elif ev.kind == "kill_mid_rescale":
            slot = ev.slot
            self.fe.cluster.rescale_fault_hook = (
                "redeploy", lambda: self.fe.cluster.kill_slot(slot))
            try:
                await self._alter_supervised(report)
            finally:
                # the hook disarms when it FIRES; if the ALTER failed
                # before reaching the redeploy phase it would stay
                # armed and fire during a later, unscheduled rescale —
                # decoupling the fault from its seeded ChaosEvent step
                self.fe.cluster.rescale_fault_hook = None
        elif ev.kind == "fault_mid_handoff":
            await self._arm(ev.slot, {"worker.rpc.ingest_table": {
                "raise": "OSError", "msg": "chaos handoff fault",
                "times": 1}})
            await self._alter_supervised(report)
        elif ev.kind == "kill_compactor_mid_task":
            # the slot is irrelevant — there is ONE compactor role; a
            # kill between tasks (nothing leased) must also converge,
            # so the event never waits for a task to be in flight
            self.fe.cluster.kill_compactor()
        elif ev.kind == "storage_fault_during_vacuum":
            await self._arm(ev.slot, {"hummock.vacuum": {
                "raise": "OSError", "msg": "chaos vacuum fault",
                "times": 4}})
        elif ev.kind == "kill_writer_mid_stage":
            # arm the wedge on the worker, then SIGKILL it a beat into
            # the next barrier step — the writer dies INSIDE stage(),
            # leaving an unmanifested (possibly torn) segment that the
            # recovery sweep must truncate before the rows replay
            import asyncio
            await self._arm(ev.slot, {"sink.stage.mid": {
                "sleep_s": _STAGE_WEDGE_S, "times": 1}})
            slot = ev.slot

            async def _delayed_kill():
                await asyncio.sleep(_STAGE_KILL_AFTER_S)
                self.fe.cluster.kill_slot(slot)

            self._pending_kill = asyncio.create_task(_delayed_kill())
        elif ev.kind == "fault_manifest_commit":
            # the manifest commit runs on the COORDINATOR (this
            # process), not in a worker — arm the local registry, not
            # the control channel. times=1: the re-derived commit
            # after recovery must succeed
            from risingwave_tpu.utils.failpoint import arm_specs
            arm_specs({"sink.manifest_commit": {
                "raise": "OSError", "msg": "chaos manifest fault",
                "times": 1}})
        elif ev.kind == "rescale_sink_fragment":
            # no fault armed: the guarded rescale ITSELF is the event
            # (stop-and-align checkpoint → writer-rank re-stamp) and
            # exactly-once across the handoff is the assertion
            await self._alter_supervised(report)
        elif ev.kind == "straggler_mid_rescale":
            timeout = self.fe.cluster.barrier_timeout_s
            await self._arm(ev.slot, {"trace.slow.HashAggExecutor": {
                "sleep_s": timeout * 2.5, "times": 1}})
            await self._alter_supervised(report)
        else:
            raise ValueError(f"unknown chaos event kind {ev.kind!r}")

    async def _step_supervised(self, report: ChaosReport) -> None:
        try:
            await self.fe.step(1)
            self.fe.cluster.supervisor.note_healthy()
        except Exception as e:  # noqa: BLE001 — the supervisor's job
            rec: RecoveryEvent = await self.fe.supervised_recover(e)
            report.recoveries.append((rec.cause, rec.action))
            report.mttr_s.append(rec.duration_s)

    async def run(self) -> ChaosReport:
        report = ChaosReport(self.seed)
        by_step: Dict[int, List[ChaosEvent]] = {}
        for ev in self.schedule:
            by_step.setdefault(ev.step, []).append(ev)
        for i in range(self.steps):
            for ev in by_step.get(i, ()):
                await self._apply(ev, report)
                report.events.append(ev.row())
            await self._step_supervised(report)
        # settle: drain the sources to completion so the MV is final
        # (recoveries rewind to the committed epoch — later faults cost
        # re-processing, so the settle budget is generous)
        for _ in range(self.settle_steps):
            await self._step_supervised(report)
        if self._pending_kill is not None:
            await self._pending_kill
            self._pending_kill = None
        if any(e.kind == "fault_manifest_commit"
               for e in self.schedule):
            # the manifest fault arms the LOCAL registry (times=1); if
            # the schedule landed it after the last commit it never
            # fired — disarm so it cannot leak into unrelated runs
            from risingwave_tpu.utils.failpoint import arm_specs
            arm_specs({"sink.manifest_commit": None})
        report.absorbed_retries = await worker_retry_totals(self.fe)
        return report


async def worker_retry_totals(fe) -> Dict[str, float]:
    """Sum object_store_retry_total across live worker processes
    (absorption happens inside workers; the coordinator's registry
    never sees it)."""
    totals: Dict[str, float] = {}
    for c in fe.cluster.clients:
        if c is None:
            continue
        text = (await c.call_idempotent({"cmd": "metrics"}))["text"]
        for line in text.splitlines():
            if line.startswith("object_store_retry_total{"):
                name, val = line.rsplit(" ", 1)
                totals[name] = totals.get(name, 0.0) + float(val)
    return totals


async def run_chaos(fe, seed: int, steps: int = 24,
                    settle_steps: int = 40,
                    kinds: Optional[List[str]] = None,
                    rescale_mv: Optional[str] = None) -> ChaosReport:
    """Generate + replay one seeded schedule.
    Wall-clock MTTR is recorded per recovery by the supervisor.
    ``rescale_mv`` names the job the mid-rescale fault kinds drive
    their guarded ALTER against."""
    schedule = generate_schedule(seed, n_workers=fe.cluster.n,
                                 steps=steps, kinds=kinds)
    t0 = time.monotonic()
    report = await ChaosRunner(fe, schedule, seed, steps=steps,
                               settle_steps=settle_steps,
                               rescale_mv=rescale_mv).run()
    report.wall_s = time.monotonic() - t0
    return report
