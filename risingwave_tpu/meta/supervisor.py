"""RecoverySupervisor: failure classification + graduated recovery.

Reference parity: src/meta/src/barrier/recovery.rs — recovery as a
first-class control loop (SURVEY #39, #52: epoch rollback + rebuild),
not a crash. The meta service detects a failed barrier round,
classifies it, and drives the cheapest response that restores the
invariants, with bounded retries so a persistent fault dies loudly
instead of looping a recovery storm.

The detection→classify→respond ladder (cheapest rung first):

1. ABSORB (below this module): transient faults never reach the
   supervisor — object-store ops retry with jittered backoff
   (``RetryingObjectStore``), idempotent worker-control RPCs
   reconnect a desynced channel and retry (``WorkerClient.
   call_idempotent``), the SST uploader retries PUTs. Metrics:
   ``object_store_retry_total`` / ``rpc_retry_total``; recovery_total
   does NOT move.
2. RESPAWN: dead worker subprocesses restart over their namespaces;
   LIVE workers reset in place (actors dropped, staged state
   discarded, jit caches kept warm) and rejoin through the existing
   ``recover_store`` handshake — process restarts only where a
   process actually died.
3. FULL: kill-and-redeploy every slot (the old total response), now
   reserved for faults that poison whole-cluster state: a wedged
   barrier (collect timeout), a storage fault past its retries, or an
   unclassifiable failure.

Every recovery is admitted through a storm gate: consecutive
recoveries back off exponentially (jitter from a seeded PRNG — the
madsim stance: chaos runs are reproducible) and a bounded attempt
budget turns a recovery loop into one loud ``RecoveryStormError``.
A completed recovery appends a ``RecoveryEvent`` to the process-global
``RECOVERY_LOG`` (the ``rw_recovery`` system table payload), bumps
``recovery_total{cause,action}`` / ``recovery_duration_seconds``, and
leaves a ``recovery.*`` span chain in the epoch trace recorder.
"""

from __future__ import annotations

import asyncio
import random
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Deque, List, Optional, Sequence, Tuple

from risingwave_tpu.utils import spans as _spans
from risingwave_tpu.utils.metrics import CLUSTER as _METRICS

# -- failure causes (the classifier's output vocabulary) ----------------
CAUSE_DEAD_WORKER = "dead_worker"        # subprocess gone / lease expired
CAUSE_WORKER_DESYNC = "worker_desync"    # alive, but control channel torn
CAUSE_STORAGE_FAULT = "storage_fault"    # object-store error past retries
CAUSE_WEDGED_BARRIER = "wedged_barrier"  # collect exceeded its timeout
CAUSE_WORKER_FAULT = "worker_fault"      # worker-side executor/plan error
CAUSE_UNKNOWN = "unknown"

CAUSE_RESCALE_FAILED = "rescale_failed"  # guarded rescale unwound

# compactor-role faults (dedicated compaction, ISSUE 19): a dead or
# lease-expired compactor costs a TASK, never a serving domain —
# recorded via record() directly, NEVER admitted through the storm
# gate (the gate budgets serving recoveries; background hygiene must
# not spend it)
CAUSE_COMPACTOR_DEAD = "compactor_dead"

# -- graduated responses ------------------------------------------------
ACTION_RESPAWN = "respawn"   # restart dead slots, reset live ones in place
ACTION_FULL = "full"         # kill-and-redeploy every slot
ACTION_ROLLBACK = "rollback"  # rescale reverted to the prior topology
ACTION_REQUEUE = "requeue"   # compaction task aborted + re-picked

# causes a respawn (rung 2) can repair; everything else escalates to
# full recovery (rung 3)
_RESPAWNABLE = frozenset({CAUSE_DEAD_WORKER, CAUSE_WORKER_DESYNC})


class RecoveryStormError(RuntimeError):
    """The bounded recovery budget is exhausted — the fault persists
    across recoveries and the cluster must stop serving, loudly,
    rather than loop kill-and-redeploy forever."""


@dataclass
class RecoveryEvent:
    """One recovery, as recorded in the rw_recovery system table."""

    seq: int
    cause: str
    action: str
    workers: Tuple[int, ...]      # slots restarted/reset by the response
    epoch: int                    # committed floor recovered to
    duration_s: float             # detection → cluster serving again
    ok: bool
    attempt: int                  # consecutive-recovery counter (1-based)
    detail: str = ""

    def row(self) -> tuple:
        return (self.seq, self.cause, self.action,
                ",".join(str(w) for w in self.workers), self.epoch,
                self.duration_s, int(self.ok), self.attempt,
                self.detail)


# process-global event log (EPOCH_TRACER shape): the supervisor appends,
# the rw_recovery system table reads — bounded, oldest dropped
RECOVERY_LOG: Deque[RecoveryEvent] = deque(maxlen=1 << 12)
_SEQ = 0


def recovery_rows() -> List[tuple]:
    """rw_recovery payload: one row per recorded recovery event."""
    return [e.row() for e in RECOVERY_LOG]


def clear_recovery_log() -> None:
    """Test isolation: the log is process-global."""
    global _SEQ
    RECOVERY_LOG.clear()
    _SEQ = 0


def _exc_chain(exc: BaseException) -> List[BaseException]:
    """The exception plus its __cause__/__context__ ancestry (bounded):
    a barrier failure surfaces as RuntimeError('actor failure during
    epoch …') FROM the ConnectionError that actually names the fault."""
    out: List[BaseException] = []
    seen = set()
    cur: Optional[BaseException] = exc
    while cur is not None and id(cur) not in seen and len(out) < 16:
        out.append(cur)
        seen.add(id(cur))
        cur = cur.__cause__ or cur.__context__
    return out


class RecoverySupervisor:
    """Classify failures, gate recoveries, and record the outcome.

    The supervisor owns POLICY (what kind of fault, which rung, how
    many attempts); the cluster owns MECHANISM (how to respawn or
    redeploy). ``note_healthy()`` after a clean barrier round resets
    the consecutive-attempt counter, so the budget bounds recovery
    *storms*, not total recoveries over a long-lived server."""

    def __init__(self, max_attempts: int = 5, backoff_s: float = 0.25,
                 backoff_cap_s: float = 8.0, seed: int = 0,
                 sleep=asyncio.sleep,
                 monotonic: Callable[[], float] = time.monotonic):
        self.max_attempts = max(1, max_attempts)
        self.backoff_s = backoff_s
        self.backoff_cap_s = backoff_cap_s
        self.sleep = sleep
        self.monotonic = monotonic
        self.attempts = 0            # consecutive, reset on note_healthy
        self._rng = random.Random(seed)

    # -- detection → classification ------------------------------------
    def classify(self, exc: BaseException,
                 dead_workers: Sequence[int] = ()) -> str:
        """Name the failure class. ``dead_workers`` (slots whose
        subprocess is gone or whose heartbeat lease expired) dominates:
        a dead worker explains every downstream symptom."""
        if dead_workers:
            return CAUSE_DEAD_WORKER
        from risingwave_tpu.meta.barrier import BarrierWedgedError
        chain = _exc_chain(exc)
        for e in chain:
            if isinstance(e, BarrierWedgedError):
                return CAUSE_WEDGED_BARRIER
        for e in chain:
            # ConnectionError/TimeoutError subclass OSError — check the
            # channel faults before the storage bucket
            if isinstance(e, (ConnectionError, TimeoutError,
                              asyncio.TimeoutError)):
                return CAUSE_WORKER_DESYNC
        for e in chain:
            if isinstance(e, (OSError, IOError)):
                return CAUSE_STORAGE_FAULT
        for e in chain:
            # a worker-side failure crosses the control channel as
            # RuntimeError("worker error: <repr>") — sniff the repr for
            # the original class
            msg = str(e)
            if "worker error" in msg:
                if ("OSError" in msg or "IOError" in msg
                        or "FileNotFoundError" in msg):
                    return CAUSE_STORAGE_FAULT
                return CAUSE_WORKER_FAULT
        return CAUSE_UNKNOWN

    @staticmethod
    def action_for(cause: str) -> str:
        return ACTION_RESPAWN if cause in _RESPAWNABLE else ACTION_FULL

    # -- storm gate -----------------------------------------------------
    async def admit(self, cause: str) -> int:
        """Admit one recovery attempt: raises RecoveryStormError past
        the consecutive budget, otherwise sleeps the jittered
        exponential backoff (attempt 1 is immediate — the first
        recovery after a healthy period must not add latency) and
        returns the 1-based attempt number."""
        if self.attempts >= self.max_attempts:
            raise RecoveryStormError(
                f"recovery storm: {self.attempts} consecutive "
                f"recoveries without a healthy barrier round (latest "
                f"cause: {cause}) — refusing to loop; fix the fault")
        self.attempts += 1
        if self.attempts > 1:
            delay = min(self.backoff_s * (2 ** (self.attempts - 2)),
                        self.backoff_cap_s)
            # full jitter (0.5–1.5×): concurrent supervisors recovering
            # against one shared fault domain must not stampede; the
            # seeded PRNG keeps a chaos replay's timing reproducible
            await self.sleep(delay * (0.5 + self._rng.random()))
        return self.attempts

    def note_healthy(self) -> None:
        """A barrier round committed cleanly: the storm window closes."""
        self.attempts = 0

    # -- outcome --------------------------------------------------------
    def record(self, cause: str, action: str,
               workers: Sequence[int], epoch: int, duration_s: float,
               ok: bool, attempt: int, detail: str = ""
               ) -> RecoveryEvent:
        """Append the event to RECOVERY_LOG + metrics + trace spans."""
        global _SEQ
        _SEQ += 1
        ev = RecoveryEvent(_SEQ, cause, action, tuple(workers), epoch,
                           duration_s, ok, attempt, detail)
        RECOVERY_LOG.append(ev)
        _METRICS.recovery_total.inc(cause=cause, action=action)
        _METRICS.recovery_duration.observe(duration_s)
        return ev


def trace_recovery_root(cause: str, action: str, epoch: int,
                        attempt: int) -> int:
    """Open the recovery.* span chain under the recovered-to epoch —
    the causal trace a post-mortem walks from rw_recovery into
    rw_epoch_trace. Returns the root span id; phases record
    children with parent=root."""
    return _spans.EPOCH_TRACER.record(
        "recovery.supervised", "recovery", epoch=epoch,
        cause=cause, action=action, attempt=attempt)


def trace_recovery_phase(name: str, epoch: int, parent: int,
                         start_s: float, dur_s: float, **args) -> None:
    """One recovery phase span (recovery.respawn / recovery.reset /
    recovery.handshake / recovery.redeploy), parented to the root."""
    _spans.EPOCH_TRACER.record(
        f"recovery.{name}", "recovery", epoch=epoch, parent=parent,
        start_s=start_s, dur_s=dur_s, **args)
