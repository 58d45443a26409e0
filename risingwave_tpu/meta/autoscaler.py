"""Elastic control loop: a meta-side autoscaler over the reschedule path.

ROADMAP item 3's missing piece: PR 14 built the input signals — the
``rw_bottlenecks`` walker (act only on ``sustained=1``; one-barrier
anecdotes are noise), the per-(actor, executor) utilization tricolor
and the per-MV freshness-lag series — and the domain-cohort reschedule
path already replays fusion, rewrite rules and tier caps, so a rescale
preserves every optimization. This module closes the loop: consume the
signals each serving heartbeat, decide, and drive
``Cluster.rescale_fragment`` / ``rescale_source_fragment``.

Robustness is the headline, not a rider (the PR-8 stance: an
autoscaler that can wedge a domain under fault is worse than no
autoscaler; concurrent-state discipline per arxiv 1904.03800):

- **Hysteresis.** A decision needs a *sustained* bottleneck row
  (contiguous slow-barrier streak from the walker), cross-checked
  against the live tricolor (the target fragment's actors must
  actually be busy-dominated) and the per-MV freshness-lag trend (a
  lag already recovering on its own is not scaled). Healthy domains
  produce zero decisions.
- **Per-MV cooldown.** After any completed action (applied OR rolled
  back) the MV is untouchable for ``cooldown_s`` — scaling decisions
  must observe their own consequences before acting again.
- **Storm gate.** Every action passes ``admit()`` (the PR-8 pattern:
  consecutive *failed* actions back off exponentially with seeded
  jitter, bounded by ``max_attempts`` → one loud refusal that disables
  the loop until an operator re-enables it). A clean round after a
  successful action closes the window; rollbacks keep it open.
- **Verify + rollback.** A rescale is not done when the RPCs return:
  the loop drives ``verify_barriers`` post-rescale rounds and rolls
  back to the prior parallelism when the rescale failed, timed out, or
  the verification rounds fail — recorded in ``rw_autoscaler`` AND
  ``rw_recovery`` (the cluster's own guarded-rescale rollback records
  there too; the two ledgers join on wall time and detail).

Every decision lands in the process-global ``AUTOSCALE_LOG`` (the
``rw_autoscaler`` system table payload) and bumps
``autoscaler_decision_total{mv,direction}`` /
``autoscaler_rollback_total{mv}``.
"""

from __future__ import annotations

import asyncio
import random
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Tuple

from risingwave_tpu.common.errors import PlanError
from risingwave_tpu.utils.metrics import CLUSTER as _METRICS

# outcomes recorded in the decision ledger
OUTCOME_APPLIED = "applied"
OUTCOME_ROLLED_BACK = "rolled_back"
OUTCOME_ROLLBACK_FAILED = "rollback_failed"
OUTCOME_STORM = "storm_disabled"


def parse_autoscale(spec: str) -> bool:
    """'on'|'off' → bool (SET stream_autoscale validator)."""
    s = str(spec).strip().lower()
    if s in ("on", "true", "1"):
        return True
    if s in ("off", "false", "0"):
        return False
    raise PlanError(f"stream_autoscale must be on|off, got {spec!r}")


class AutoscaleStormError(RuntimeError):
    """Consecutive failed scaling actions exhausted the bounded budget
    — the loop disables itself loudly instead of thrashing a domain
    that cannot hold a rescale."""


@dataclass
class AutoscaleEvent:
    """One decision, as recorded in the rw_autoscaler system table."""

    seq: int
    mv: str
    fragment: int                 # fragment index within the job
    operator: str                 # walker-named operator identity
    direction: str                # "up" | "down"
    from_parallelism: int
    to_parallelism: int
    outcome: str                  # applied|rolled_back|rollback_failed|…
    reason: str                   # the signal that triggered it
    epoch: int                    # committed floor at decision time
    duration_s: float             # decide → verified (or rolled back)
    detail: str = ""

    def row(self) -> tuple:
        return (self.seq, self.mv, self.fragment, self.operator,
                self.direction, self.from_parallelism,
                self.to_parallelism, self.outcome, self.reason,
                self.epoch, self.duration_s, self.detail)


# process-global decision ledger (RECOVERY_LOG shape): the autoscaler
# appends, the rw_autoscaler system table reads — bounded
AUTOSCALE_LOG: Deque[AutoscaleEvent] = deque(maxlen=1 << 12)
_SEQ = 0


def autoscaler_rows() -> List[tuple]:
    """rw_autoscaler payload: one row per recorded decision."""
    return [e.row() for e in AUTOSCALE_LOG]


def clear_autoscale_log() -> None:
    """Test isolation: the log is process-global."""
    global _SEQ
    AUTOSCALE_LOG.clear()
    _SEQ = 0


def _record(mv: str, fragment: int, operator: str, direction: str,
            from_p: int, to_p: int, outcome: str, reason: str,
            epoch: int, duration_s: float, detail: str = ""
            ) -> AutoscaleEvent:
    global _SEQ
    _SEQ += 1
    ev = AutoscaleEvent(_SEQ, mv, fragment, operator, direction,
                        from_p, to_p, outcome, reason, epoch,
                        round(duration_s, 4), detail[:200])
    AUTOSCALE_LOG.append(ev)
    _METRICS.autoscaler_decision.inc(mv=mv, direction=direction)
    if outcome in (OUTCOME_ROLLED_BACK, OUTCOME_ROLLBACK_FAILED):
        _METRICS.autoscaler_rollback.inc(mv=mv)
    return ev


@dataclass
class AutoscalerConfig:
    """Policy knobs (mechanism lives on the Cluster)."""

    max_parallelism: Optional[int] = None   # default: cluster.n
    min_parallelism: int = 1
    # hysteresis: seconds an MV is untouchable after a completed action
    cooldown_s: float = 15.0
    # post-rescale health verification rounds
    verify_barriers: int = 3
    # hard bound on one rescale's wall time (stop + handoff + redeploy)
    rescale_timeout_s: float = 120.0
    # tricolor cross-check: the target fragment's actors must average
    # at least this busy share for a scale-UP to proceed
    up_busy_mean: float = 0.30
    # scale-down: a fragment scaled above its baseline whose actors
    # stay under this busy share while its domain reports no sustained
    # bottleneck for `down_quiet_rounds` consecutive ticks shrinks by 1
    down_busy_max: float = 0.12
    down_quiet_rounds: int = 40
    # freshness cross-check: scale up only while the MV's wall lag is
    # not already recovering (last sample ≥ trend_ratio × window
    # median) or the MV publishes no freshness samples at all
    trend_ratio: float = 0.8
    # multi-step jump (ISSUE 19): a LOAD STEP (≥~4x input rate) shows
    # up as a near-saturated busy mean AND a steeply rising wall-lag
    # trend — jump +2 parallelism per decision (still ONE guarded
    # rescale, still capped) instead of walking +1 per cooldown
    # window while the backlog outruns each rung
    jump_busy_mean: float = 0.85
    jump_lag_slope: float = 2.0
    # storm gate (PR-8 admit() shape)
    max_attempts: int = 4
    backoff_s: float = 0.5
    backoff_cap_s: float = 16.0
    seed: int = 0


class _AdmitGate:
    """The PR-8 ``admit()`` pattern for scaling actions: consecutive
    FAILED actions back off exponentially with seeded jitter and a
    bounded budget; a successful, verified action closes the window.

    ``defer=True`` (the Autoscaler's mode) moves the backoff out of
    ``admit()``: the tick runs under the serving barrier lock, where a
    multi-second inline sleep would stall barrier stepping and every
    queued SELECT/ALTER — the caller spreads the same ``next_delay()``
    schedule as a not-before deadline between heartbeats instead."""

    def __init__(self, max_attempts: int, backoff_s: float,
                 backoff_cap_s: float, seed: int, sleep=asyncio.sleep,
                 defer: bool = False):
        self.max_attempts = max(1, max_attempts)
        self.backoff_s = backoff_s
        self.backoff_cap_s = backoff_cap_s
        self.sleep = sleep
        self.defer = defer
        self.attempts = 0
        self._rng = random.Random(seed)

    def next_delay(self) -> float:
        """Seeded-jitter exponential backoff after ``attempts``
        consecutive failures (0 failures → no delay). THE one copy of
        the schedule — admit()'s inline sleep and the deferred
        deadline both draw from it."""
        if self.attempts < 1:
            return 0.0
        delay = min(self.backoff_s * (2 ** (self.attempts - 1)),
                    self.backoff_cap_s)
        return delay * (0.5 + self._rng.random())

    async def admit(self) -> int:
        if self.attempts >= self.max_attempts:
            raise AutoscaleStormError(
                f"autoscaler storm: {self.attempts} consecutive "
                f"failed scaling actions — disabling the loop; "
                f"investigate before re-enabling stream_autoscale")
        delay = 0.0 if self.defer else self.next_delay()
        self.attempts += 1
        if delay:
            await self.sleep(delay)
        return self.attempts

    def note_success(self) -> None:
        self.attempts = 0


class Autoscaler:
    """The control loop: signals → decision → guarded rescale →
    verify/rollback. Owned by a DistFrontend; ``tick()`` runs inside
    the serving heartbeat (under the barrier lock, so a manual ALTER
    queues behind an in-flight action instead of interleaving)."""

    def __init__(self, cluster, config: Optional[AutoscalerConfig]
                 = None, monotonic: Callable[[], float] = time.monotonic):
        self.cluster = cluster
        self.cfg = config or AutoscalerConfig()
        self.monotonic = monotonic
        self.gate = _AdmitGate(self.cfg.max_attempts,
                               self.cfg.backoff_s,
                               self.cfg.backoff_cap_s, self.cfg.seed,
                               defer=True)
        self.enabled = True
        # deferred storm-gate backoff: failed actions arm a not-before
        # deadline and tick() no-ops until it passes — the delay runs
        # BETWEEN heartbeats instead of inside the barrier lock
        self._not_before = 0.0
        # per-MV cooldown stamps (hysteresis half 2)
        self._cooldown_until: Dict[str, float] = {}
        # (mv, fragment) → parallelism when this loop first saw it —
        # scale-down never shrinks below the operator's own baseline
        self._baseline: Dict[Tuple[str, int], int] = {}
        # (mv, fragment) → consecutive quiet ticks (scale-down input)
        self._quiet: Dict[Tuple[str, int], int] = {}
        # recent per-MV wall-lag samples for the trend cross-check
        self._lag: Dict[str, Deque[float]] = {}
        # last completed action's outcome ("" = none yet): a clean
        # serving round closes the storm window only after a SUCCESS —
        # a rollback keeps the backoff armed (note_healthy contract)
        self._last_outcome = ""

    # -- serving-loop hooks --------------------------------------------
    def note_healthy(self) -> None:
        """A barrier round committed cleanly. Closes the storm window
        only when the last action SUCCEEDED (or none ran): consecutive
        rollbacks must keep backing off even though the cluster steps
        cleanly between them — post-rollback health is the rollback
        working, not the rescale."""
        if self._last_outcome in ("", OUTCOME_APPLIED):
            self.gate.note_success()

    def reset_storm(self) -> None:
        """Operator re-enable (an explicit ``SET stream_autoscale=on``
        after a storm): clear the disabled latch AND the exhausted
        budget — a still-maxed gate would re-raise the storm on the
        next decision without attempting a single rescale."""
        self.enabled = True
        self.gate.note_success()
        self._last_outcome = ""
        self._not_before = 0.0

    # -- signal plumbing -----------------------------------------------
    async def _refresh_signals(self) -> None:
        """Pull worker-side signal snapshots (utilization tricolor +
        bottleneck walks + freshness parts) into the coordinator's
        process-global views. The walker runs per barrier inside each
        worker (the coordinator hosts no monitored actors); this merge
        is what rw_bottlenecks / rw_actor_utilization serve on the
        distributed session too."""
        # one round-trip's latency for both sweeps: the verbs hit
        # disjoint worker-side state, so they overlap safely. Light
        # drain: the decision reads utilization/bottlenecks/costs —
        # never the per-vnode topology, whose worker-side snapshot
        # walks the whole per-key map
        await asyncio.gather(self.cluster.drain_signals(light=True),
                             self.cluster.drain_freshness())
        from risingwave_tpu.stream.freshness import FRESHNESS
        for (mv, _dom, n, _e, _lag, wall_lag, _p50, _p99,
             _wp99) in FRESHNESS.rows():
            if not n or wall_lag is None:
                continue
            self._lag.setdefault(mv, deque(maxlen=32)).append(wall_lag)

    def _lag_still_rising(self, mv: str) -> bool:
        """Freshness cross-check: True unless the MV's wall lag is
        already clearly recovering (last sample under ``trend_ratio``
        of the window median). MVs with no samples pass — absence of
        the signal must not veto the walker's direct evidence."""
        window = self._lag.get(mv)
        if not window or len(window) < 4:
            return True
        ordered = sorted(window)
        median = ordered[len(ordered) // 2]
        return window[-1] >= self.cfg.trend_ratio * median

    def _step_size(self, busy_mean: float, mv: str) -> int:
        """+1 normally; +2 when the signals say LOAD STEP rather than
        drift: the fragment is saturated (busy mean ≥ jump_busy_mean)
        and the MV's wall lag is growing steeply (last sample ≥
        jump_lag_slope × window median). Under a 4x input step the +1
        ladder accumulates more backlog per cooldown window than each
        rung retires — the jump halves the rungs to reach the needed
        parallelism."""
        if busy_mean < self.cfg.jump_busy_mean:
            return 1
        window = self._lag.get(mv)
        if not window or len(window) < 4:
            return 1
        ordered = sorted(window)
        median = ordered[len(ordered) // 2]
        if median <= 0:
            return 1
        return 2 if window[-1] >= self.cfg.jump_lag_slope * median \
            else 1

    def _fragment_of_actor(self, job, actor_id: int) -> Optional[int]:
        for fi, placed in enumerate(job.placements):
            if any(aid == actor_id for aid, _slot in placed):
                return fi
        return None

    def _fragment_busy_mean(self, job_name: str, job,
                            fi: int) -> float:
        """Mean busy share across the target fragment's actors (the
        tricolor cross-check: scaling helps a fragment that is busy
        everywhere, not one with a single skewed straggler)."""
        from risingwave_tpu.stream.monitor import UTILIZATION
        best: Dict[int, float] = {}
        for (a, f, _node, _ex, _e, _i, busy, _bp,
             _idle) in UTILIZATION.rows():
            if f == job_name:
                best[a] = max(best.get(a, 0.0), busy)
        vals = [best.get(aid, 0.0)
                for aid, _slot in job.placements[fi]]
        return sum(vals) / len(vals) if vals else 0.0

    def _target_slots(self, job, fi: int, n: int) -> List[int]:
        """Derive the target slot set from the fragment's CURRENT
        placement: grow by appending unused slots round-robin, shrink
        by dropping the tail (the most recently added actors). Keeping
        the surviving actors where they are bounds the stop-the-world
        handoff to the rebalanced share — a formula-derived set could
        relocate the fragment's entire state cross-worker."""
        cur = [s for _a, s in job.placements[fi]]
        if n <= len(cur):
            return cur[:n]
        out = list(cur)
        used = set(out)
        c = (out[-1] + 1) if out else fi
        while len(out) < n:
            for k in range(self.cluster.n):
                cand = (c + k) % self.cluster.n
                if cand not in used:
                    out.append(cand)
                    used.add(cand)
                    c = cand + 1
                    break
            else:
                # parallelism past the worker count: slots repeat
                out.append(c % self.cluster.n)
                c += 1
        return out

    # -- decision ------------------------------------------------------
    def _decide(self) -> Optional[dict]:
        """At most ONE action per tick, scale-ups first (a saturated
        fragment outranks trimming an idle one)."""
        from risingwave_tpu.stream.bottleneck import BOTTLENECKS
        now = self.monotonic()
        sustained_domains = set()
        for (domain, op, fragment, actor, _node, busy, _bp, _streak,
             sustained, _epoch, diag) in BOTTLENECKS.rows():
            if not sustained or op is None:
                continue
            sustained_domains.add(domain)
            job = self.cluster.jobs.get(fragment)
            if job is None:
                continue
            if now < self._cooldown_until.get(fragment, 0.0):
                continue
            fi = self._fragment_of_actor(job, actor)
            if fi is None:
                continue                     # stale row (redeployed)
            frag = job.graph.fragments[fi]
            source_kind = self.cluster._source_rescalable(frag)
            if not source_kind and not self.cluster._rescalable(frag):
                continue                     # nothing to drive here
            cur = len(job.placements[fi])
            cap = self.cfg.max_parallelism or self.cluster.n
            if cur >= cap:
                continue
            busy_mean = self._fragment_busy_mean(fragment, job, fi)
            if busy_mean < self.cfg.up_busy_mean:
                continue                     # tricolor cross-check
            if not self._lag_still_rising(fragment):
                continue                     # freshness cross-check
            self._baseline.setdefault((fragment, fi), cur)
            step = self._step_size(busy_mean, fragment)
            to_p = min(cur + step, cap)      # bounded, ONE rescale
            reason = (f"sustained bottleneck: {diag}" if diag
                      else "sustained bottleneck")
            if to_p - cur > 1:
                reason += (f" (load step: busy {busy_mean:.0%}, "
                           f"lag slope — jump +{to_p - cur})")
            return {"mv": fragment, "fi": fi, "operator": op,
                    "direction": "up", "from_p": cur, "to_p": to_p,
                    "source": source_kind, "reason": reason}
        # scale-down sweep: fragments this loop scaled up whose demand
        # evaporated (quiet domain + idle actors for a long window)
        for (mv, fi), base in list(self._baseline.items()):
            job = self.cluster.jobs.get(mv)
            if job is None or fi >= len(job.placements):
                self._baseline.pop((mv, fi), None)
                continue
            cur = len(job.placements[fi])
            if cur <= max(base, self.cfg.min_parallelism):
                self._quiet.pop((mv, fi), None)
                continue
            dom = self.cluster.domain_of_job(mv)
            busy = self._fragment_busy_mean(mv, job, fi)
            if dom in sustained_domains or busy > self.cfg.down_busy_max:
                self._quiet[(mv, fi)] = 0
                continue
            q = self._quiet.get((mv, fi), 0) + 1
            self._quiet[(mv, fi)] = q
            if q < self.cfg.down_quiet_rounds:
                continue
            if self.monotonic() < self._cooldown_until.get(mv, 0.0):
                continue
            frag = job.graph.fragments[fi]
            return {"mv": mv, "fi": fi,
                    "operator": "", "direction": "down",
                    "from_p": cur, "to_p": cur - 1,
                    "source": self.cluster._source_rescalable(frag),
                    "reason": f"quiet {q} rounds, busy {busy:.0%}"}
        return None

    # -- the guarded action --------------------------------------------
    async def _rescale(self, job_name: str, fi: int, to_slots,
                       source: bool) -> None:
        if source:
            await self.cluster.rescale_source_fragment(
                job_name, fi, list(to_slots))
        else:
            await self.cluster.rescale_fragment(
                job_name, fi, list(to_slots))

    async def _act(self, d: dict) -> AutoscaleEvent:
        """Guarded-rescale protocol: admit → rescale (bounded) →
        verify N barriers → on ANY failure, roll back to the prior
        parallelism and record it in rw_autoscaler + rw_recovery."""
        from risingwave_tpu.meta.supervisor import (
            ACTION_ROLLBACK, CAUSE_RESCALE_FAILED,
        )
        await self.gate.admit()
        mv, fi = d["mv"], d["fi"]
        job = self.cluster.jobs[mv]
        prior_slots = [s for _a, s in job.placements[fi]]
        floor = self.cluster.store.committed_epoch()
        t0 = self.monotonic()
        outcome, detail = OUTCOME_APPLIED, ""
        try:
            await asyncio.wait_for(
                self._rescale(mv, fi,
                              self._target_slots(job, fi, d["to_p"]),
                              d["source"]),
                self.cfg.rescale_timeout_s)
            # post-rescale health verification: the rescale is done
            # when the redeployed domain holds N clean rounds, not
            # when the RPCs return
            for _ in range(self.cfg.verify_barriers):
                await self.cluster.step(1)
        except BaseException as exc:  # noqa: BLE001 — rollback path
            detail = repr(exc)[:160]
            from risingwave_tpu.cluster.scheduler import RescaleError
            already_rolled = (isinstance(exc, RescaleError)
                              and exc.rolled_back)
            # a RescaleError with rolled_back=False means the
            # CLUSTER's own unwind failed: the cohort is stopped and
            # possibly half-deployed, so a compensating rescale here
            # would no-op against the already-reverted placements and
            # MASK a wedged-idle cluster — record and re-raise so the
            # serving loop's supervised recovery redeploys (and runs
            # the pending state-placement repair)
            cluster_unrolled = (isinstance(exc, RescaleError)
                                and not exc.rolled_back)
            rolled = already_rolled
            if not already_rolled and not cluster_unrolled:
                try:
                    await asyncio.wait_for(
                        self._rescale(mv, fi, prior_slots, d["source"]),
                        self.cfg.rescale_timeout_s)
                    rolled = True
                except BaseException as rexc:  # noqa: BLE001
                    detail += f"; rollback failed: {rexc!r}"[:100]
                # the compensating rescale is an autoscaler decision,
                # not a cluster-internal unwind — record it in
                # rw_recovery so both ledgers tell the story
                self.cluster.supervisor.record(
                    CAUSE_RESCALE_FAILED, ACTION_ROLLBACK,
                    tuple(sorted(set(prior_slots))), floor,
                    self.monotonic() - t0, rolled, 1,
                    detail=f"autoscaler {mv}/f{fi}: {detail}")
            outcome = (OUTCOME_ROLLED_BACK if rolled
                       else OUTCOME_ROLLBACK_FAILED)
            if not rolled or isinstance(exc, asyncio.CancelledError):
                # broken beyond the compensating action (supervised
                # recovery owns the underlying fault), or the serving
                # task itself was cancelled mid-action — swallowing
                # the CancelledError here would make the heartbeat
                # uncancellable. Record, then re-raise.
                self._finish(d, outcome, floor, t0, detail)
                raise
        return self._finish(d, outcome, floor, t0, detail)

    def _finish(self, d: dict, outcome: str, floor: int, t0: float,
                detail: str) -> AutoscaleEvent:
        dur = self.monotonic() - t0
        self._cooldown_until[d["mv"]] = \
            self.monotonic() + self.cfg.cooldown_s
        self._last_outcome = outcome
        if outcome == OUTCOME_APPLIED:
            self.gate.note_success()
            self._quiet.pop((d["mv"], d["fi"]), None)
        else:
            # deferred storm-gate backoff (the gate's own schedule):
            # the next action waits out the window between heartbeats,
            # not under the barrier lock
            self._not_before = (self.monotonic()
                                + self.gate.next_delay())
        return _record(d["mv"], d["fi"], d["operator"], d["direction"],
                       d["from_p"], d["to_p"], outcome, d["reason"],
                       floor, dur, detail)

    async def tick(self) -> Optional[AutoscaleEvent]:
        """One control-loop round (each serving heartbeat): refresh
        signals, decide, and run at most one guarded action. Raises
        only when a failed action could not be rolled back — the
        serving loop's supervised-recovery ladder owns that."""
        if not self.enabled:
            return None
        if self.monotonic() < self._not_before:
            return None          # deferred backoff window still open
        await self._refresh_signals()
        d = self._decide()
        if d is None:
            return None
        try:
            return await self._act(d)
        except AutoscaleStormError as e:
            self.enabled = False
            self._last_outcome = OUTCOME_STORM
            return _record(d["mv"], d["fi"], d["operator"],
                           d["direction"], d["from_p"], d["to_p"],
                           OUTCOME_STORM, d["reason"],
                           self.cluster.store.committed_epoch(), 0.0,
                           str(e))
