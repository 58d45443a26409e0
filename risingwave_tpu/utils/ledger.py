"""Epoch phase ledger: host/device time-and-bytes accounting.

Every barrier interval is classified into named phases (the Hazelcast
Jet stance, arxiv 2103.10169: a p99 tail you cannot attribute is a tail
you cannot fix — make every microsecond and every byte of an epoch
attributable, continuously, not in one-off cProfile runs):

- ``host_ingest``   — connector decode (JsonRowParser/CsvRowParser) and
                      source-side chunk building.
- ``host_pack``     — chunk codecs, epoch staging (backlog assembly).
- ``exchange_route``— the sharded kernels' host routing in front of
                      their all_to_all (parallel/exchange.py): every
                      row's owner shard, the skew-exact bucket, the
                      bucket choice. Only a mesh plan has it: a row of
                      ``rw_metrics_history`` carries the name only
                      where the epoch routed.
- ``h2d``           — host→device upload of packed/raw matrices
                      (``jaxtools.upload``), with exact byte counts.
- ``device_compute``— the real ``instrumented_jit``/``shard_map``
                      launch sites (dispatch_span) plus ready-wait time
                      in ``jaxtools.fetch`` — under async dispatch the
                      wait-until-ready segment IS the device's compute
                      tail as seen from the host.
- ``d2h``           — materializing packed results through
                      ``jaxtools.fetch``/``start_fetch`` DMAs, with
                      exact byte counts.
- ``host_emit``     — downstream host processing: packed-matrix
                      reassembly, arena gathers, state-table writes and
                      dispatch. Measured as each non-source executor's
                      EXCLUSIVE busy time minus the named phases
                      recorded during its pulls (the residue that is
                      provably host work but not pack/transfer).
- ``barrier_wait``  — source executors parked on the barrier channel
                      while nothing else of the epoch worked: the park
                      (across-source max) fills only what the other
                      phases leave of the interval.
- ``backpressure_wait`` — senders parked for exchange credits (a slow
                      consumer's wall time, subtracted from the parking
                      executor's busy share — stream/monitor.py's
                      utilization tricolor carries the per-actor view).
- ``checkpoint``    — synchronous checkpoint work on the event loop,
                      outside any actor: the SST build
                      (``HummockLite.build_ssts``), the manifest commit
                      (``commit_ssts`` less the compaction) and the
                      checkpoint-time sweeps of ``collect_next``.
- ``compaction``    — the inline ``HummockLite.compact()`` a commit
                      triggers, synchronous on the event loop.

Two disciplines keep the ledger honest:

- **Exclusive nesting.** Scopes may nest arbitrarily (a fetch inside a
  dispatch span inside an executor pull); each scope records only its
  exclusive time, so phase totals never double-count a wall-clock
  second. Executor-level residue subtracts the named time recorded
  during that executor's own pulls (an asyncio-context cell, so
  interleaved actors never cross-charge).
- **Stolen loop time.** ``checkpoint`` and ``compaction`` are the LOOP
  phases: synchronous sections that block the event loop from a task
  that is no actor (the uploader's, the barrier loop's). While one
  runs, every actor parked in an ``await`` still has its wall clock
  running. Each loop scope therefore adds its exclusive time to one
  process-wide monotone accumulator (``stolen_s()``), and every
  wall-clock interval taken around an await (``MonitoredExecutor``'s
  pulls, the ``idle_wait_s`` parks of sources and channel receivers,
  the senders' credit parks) is taken with ``actor_clock()``, which
  subtracts the accumulator's delta over that interval. On one event
  loop a synchronous foreign section cannot overlap actor code, so the
  subtraction is exact. The seconds are booked on the epoch in whose
  interval they ran: they wait in ``_loop_pending`` with their wall
  stamps and the next ``seal`` takes what lies before its interval's
  end (the newest INJECTED epoch may be several barriers ahead of the
  one being collected, or already sealed).
- **Conservation.** At barrier collection the loop seals the epoch
  against its measured interval; the uncovered remainder is published
  as ``unattributed`` — and gated in tier-1 strict mode (conftest), so
  the ledger can never silently rot: a new uninstrumented stall shows
  up as residual, not as silence.

Attribution is epoch-exact for executor work (cells flush with the
barrier that ends the epoch, the same CURR-epoch key rw_barrier_latency
uses); scopes outside any executor attribute to the newest injected
epoch (the utils/spans approximation).

Output surfaces: ``stream_epoch_phase_seconds{phase}`` and
``stream_transfer_bytes_total{dir,kernel}`` Prometheus families; a
``phase.<name>`` span where and for as long as a scope ran (from
``SPAN_MIN_S`` up) + byte counter tracks in the Perfetto export
(utils/spans); every scope as a ``TraceAnnotation`` in a running
``jax.profiler`` trace; the ``rw_metrics_history`` per-barrier ring
(utils/metrics.HISTORY — the feed the elastic-serving control loop
reads: ``phase.<name>`` seconds, and ``exec_s.<Kind>``, each executor
kind's exclusive busy seconds of the epoch, a second cut of the same
wall time); and ``ctl phases``.

**The second coordinate.** The seconds above keep their phase; some of
them are filed a second time, never added to the phases:

- ``exec_phase.<Kind>.<phase>`` (``stream_exec_phase_seconds{kind,
  phase}``): where the two cuts cross. At an executor's barrier flush
  (stream/monitor.py) its cell's named phases and its residue, under
  the fallback phase, are filed by its kind: over the phases they add
  up to ``exec_s.<Kind>``, over the kinds to the part of
  ``phase.<phase>`` that came through cells.
- ``stage.<phase>.<stage>`` (``stream_phase_stage_seconds{phase,
  stage}``): a scope opened with ``stage=`` books its exclusive
  seconds in its phase as ever and also under (phase, stage); its
  annotation is ``phase.<name>.<stage>``. The stages of ``host_emit``
  (``agg.ingest``, ``join.pairs``, ``state.write``, ...) name what was
  a residue; what no stage holds is ``phase.host_emit`` less their
  sum, computed by the reader.
- ``device.<launch|wait>.<kernel>`` (``stream_device_host_seconds{
  kernel, stage}``): a staged scope of ``device_compute`` is filed by
  the kernel label in force instead, the enclosing ``dispatch_span``'s:
  where the host stood, not what the device ran. ``launch`` is the
  call that enqueues a program, ``wait`` the host standing still in
  ``jaxtools.fetch``; together they are ``phase.device_compute``.

All three ride the executor's cell and flush with its barrier, so a
row of ``rw_metrics_history`` holds its own epoch's seconds. A name is
written only where the epoch has seconds of it.

``phase.heartbeat_wait`` (``HeartbeatTick.file``) is no ledger phase:
the serving heartbeat's waits lie between one epoch's books closing
and the next inject, outside every ``interval_s`` and outside the
conservation gate; ``ctl phases`` prints it under the table.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from collections import OrderedDict, deque
from contextvars import ContextVar
from typing import Deque, Dict, Iterable, List, Optional, Tuple

from risingwave_tpu.utils import spans as _spans

PHASES = ("host_ingest", "host_pack", "exchange_route", "h2d",
          "device_compute", "d2h", "host_emit", "barrier_wait",
          "backpressure_wait", "checkpoint", "compaction")
# published per barrier only where the epoch has seconds of them
MESH_PHASES = frozenset(("exchange_route",))
# synchronous on the event loop, outside any actor (module docstring,
# "Stolen loop time")
LOOP_PHASES = frozenset(("checkpoint", "compaction"))
UNATTRIBUTED = "unattributed"

# scoped phases shorter than this keep their seconds in the books and
# leave no span of their own in the epoch trace (the profiler's trace
# has every one of them as a TraceAnnotation)
SPAN_MIN_S = 0.001

# open-epoch accumulators kept (epochs are injected faster than sealed
# only up to the in-flight window; the bound guards leaks on epochs
# that never collect, e.g. recovery rollbacks)
OPEN_WINDOW = 64

# active scope's child-duration accumulator (exclusive-nesting math);
# ContextVars are asyncio-task aware, so interleaved actors keep
# separate stacks
_SCOPE: ContextVar[Optional[list]] = ContextVar("ledger_scope",
                                                default=None)
# active executor attribution cell (stream/monitor.py pushes around
# each inner pull; named phases recorded during the pull land here and
# flush epoch-exactly at the barrier)
_CELL: ContextVar[Optional["AttributionCell"]] = ContextVar(
    "ledger_cell", default=None)
# current kernel identity for transfer/compute attribution
_KERNEL: ContextVar[str] = ContextVar("ledger_kernel", default="")


# seconds the LOOP phases have held the event loop so far: monotone,
# process-wide, written from the loop's thread only
_STOLEN = [0.0]


def stolen_s() -> float:
    """The stolen-loop-time accumulator (module docstring)."""
    return _STOLEN[0]


def actor_clock() -> float:
    """``time.perf_counter`` less the stolen loop time: a clock that
    stands still while a LOOP phase holds the event loop. A wall-clock
    interval taken with it around an ``await`` is free of the foreign
    sections that ran inside the wait."""
    return time.perf_counter() - _STOLEN[0]


def current_kernel() -> str:
    return _KERNEL.get()


@contextlib.contextmanager
def kernel_scope(label: str):
    """Stamp transfers/compute recorded in the block with `label`."""
    tok = _KERNEL.set(label)
    try:
        yield
    finally:
        _KERNEL.reset(tok)


def note_backlog(kernel: str, rows: float) -> None:
    """Record one epoch-batch dispatch's staged-row volume (the
    stream_epoch_backlog_rows gauge behind the Perfetto backlog
    counter track) — the ONE copy all four epoch-batching kernels
    call at their backlog flush."""
    from risingwave_tpu.utils.metrics import STREAMING
    STREAMING.backlog_rows.set(float(rows), kernel=kernel)


class AttributionCell:
    """Named-phase seconds + transfer bytes recorded during one
    executor's pulls since the last barrier (stream/monitor.py owns
    one per wrapped executor and flushes it epoch-exactly)."""

    __slots__ = ("seconds", "stages", "h2d_bytes", "d2h_bytes")

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}
        # the staged scopes' seconds a second time: ("stage", phase,
        # stage), or ("device", launch|wait, kernel) for device_compute
        self.stages: Dict[tuple, float] = {}
        self.h2d_bytes = 0
        self.d2h_bytes = 0

    def named_total(self) -> float:
        return sum(self.seconds.values())

    def take(self):
        """Pop the accumulated contents (flush-at-barrier)."""
        out = (self.seconds, self.stages, self.h2d_bytes, self.d2h_bytes)
        self.seconds = {}
        self.stages = {}
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        return out


class _EpochAcc:
    """Open accumulator for one epoch (pre-seal)."""

    __slots__ = ("seconds", "h2d_bytes", "d2h_bytes", "warmup", "idle",
                 "exec_s", "second")

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}
        # exclusive busy seconds by executor kind (stream/monitor.py)
        self.exec_s: Dict[str, float] = {}
        # the second coordinate (module docstring), by key:
        # ("exec_phase", kind, phase), ("stage", phase, stage),
        # ("device", launch|wait, kernel)
        self.second: Dict[tuple, float] = {}
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.warmup = False     # saw a kernel (re)compile this epoch
        # per-SOURCE idle seconds (barrier_wait) — kept keyed so the
        # seal can take the across-source MAX instead of the sum:
        # parallel sources park CONCURRENTLY, and summing their idle
        # against one wall-clock interval double-counts it (a share
        # above 1.0)
        self.idle: Dict[str, float] = {}

    def add(self, phase: str, s: float) -> None:
        if s > 0:
            self.seconds[phase] = self.seconds.get(phase, 0.0) + s

    def add_second(self, key: tuple, s: float) -> None:
        if s > 0:
            self.second[key] = self.second.get(key, 0.0) + s

    def add_idle(self, key: str, s: float) -> None:
        if s > 0:
            self.idle[key] = self.idle.get(key, 0.0) + s

    def idle_max(self) -> float:
        return max(self.idle.values()) if self.idle else 0.0


class LedgerRecord:
    """One sealed epoch's phase breakdown."""

    __slots__ = ("epoch", "kind", "interval_s", "seconds", "h2d_bytes",
                 "d2h_bytes", "warmup", "distributed", "workers",
                 "idle_max", "domain", "exec_s", "second")

    def __init__(self, epoch: int, kind: str, interval_s: float,
                 seconds: Dict[str, float], h2d_bytes: int,
                 d2h_bytes: int, warmup: bool, distributed: bool,
                 domain: str = ""):
        self.epoch = epoch
        self.kind = kind
        # barrier domain whose loop sealed this epoch ("" = global):
        # domains partition wall time INDEPENDENTLY — two domains'
        # records legitimately cover the same wall-clock second, and
        # conservation holds per record because epochs are domain-
        # unique (the shared allocator)
        self.domain = domain
        self.interval_s = interval_s
        self.seconds = seconds          # includes UNATTRIBUTED
        # executor kind → exclusive busy seconds of the epoch: a second
        # cut of the same wall time the phases partition, never added
        # to them
        self.exec_s: Dict[str, float] = {}
        # the same seconds by their second coordinate; a key's parts
        # joined by dots is the name the history row carries
        # (`exec_phase.<Kind>.<phase>`, `stage.<phase>.<stage>`,
        # `device.<launch|wait>.<kernel>`)
        self.second: Dict[tuple, float] = {}
        self.h2d_bytes = h2d_bytes
        self.d2h_bytes = d2h_bytes
        self.warmup = warmup
        # sealed on a cluster coordinator BEFORE worker ledgers merged:
        # conservation is not checkable until drain_ledger folds them in
        self.distributed = distributed
        self.workers: List[str] = []    # merged-in worker tags
        # largest single-source idle folded into barrier_wait so far
        # (worker merges take max-then-cap, never sum — see
        # attribute_idle)
        self.idle_max = 0.0

    @property
    def attributed_s(self) -> float:
        return sum(s for p, s in self.seconds.items()
                   if p != UNATTRIBUTED)

    @property
    def unattributed_s(self) -> float:
        return self.seconds.get(UNATTRIBUTED, 0.0)

    def coverage(self) -> float:
        """Attributed fraction of the barrier interval (capped at 1:
        concurrent host threads can oversum wall clock)."""
        if self.interval_s <= 0:
            return 1.0
        return min(1.0, self.attributed_s / self.interval_s)

    def recompute_unattributed(self) -> None:
        named = self.attributed_s
        resid = max(0.0, self.interval_s - named)
        if resid > 0:
            self.seconds[UNATTRIBUTED] = resid
        else:
            self.seconds.pop(UNATTRIBUTED, None)

    def to_dict(self) -> dict:
        return {"epoch": self.epoch, "kind": self.kind,
                "domain": self.domain,
                "interval_s": self.interval_s,
                "seconds": dict(self.seconds),
                "exec_s": dict(self.exec_s),
                "second": {".".join(k): s
                           for k, s in self.second.items()},
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "warmup": self.warmup,
                "distributed": self.distributed,
                "workers": list(self.workers)}


class PhaseLedger:
    """Process-global phase ledger (worker processes drain theirs to
    the coordinator over the control channel, like the span tracer)."""

    # conservation gate (tier-1 strict mode, conftest): a steady-state
    # epoch longer than GATE_MIN_INTERVAL_S whose residual exceeds
    # BOTH the fraction and the absolute floor is a violation. The
    # floor absorbs fixed per-barrier machinery (event loop, barrier
    # send/collect) that dominates micro-epochs; the fraction is the
    # rot detector on real epochs.
    GATE_MIN_INTERVAL_S = 0.4
    GATE_RESIDUAL_FRAC = 0.35
    GATE_RESIDUAL_MIN_S = 0.25

    def __init__(self, window: int = 512):
        self.window = window
        self._open: "OrderedDict[int, _EpochAcc]" = OrderedDict()
        self.records: Deque[LedgerRecord] = deque(maxlen=window)
        # cell commits race the uploader's worker threads' scopes
        self._lock = threading.Lock()
        # LOOP-phase sections not yet on an epoch's books, with their
        # time.monotonic stamps: [start, end, phase, exclusive seconds]
        self._loop_pending: List[list] = []
        # epochs sealed lately, and the books of scopes that named no
        # epoch after the newest injected one had sealed: the next
        # seal takes them (books opened for a sealed epoch never close)
        self._sealed: Deque[int] = deque(maxlen=OPEN_WINDOW)
        self._carry = _EpochAcc()

    # module-level kernel-context scope, re-exported on the instance
    # (call sites hold LEDGER, not the module)
    kernel_scope = staticmethod(kernel_scope)

    # -- recording -----------------------------------------------------
    def _acc(self, epoch: Optional[int] = None) -> _EpochAcc:
        if epoch is None:
            epoch = _spans.current_epoch()
            if epoch in self._sealed:
                return self._carry
        acc = self._open.get(epoch)
        if acc is None:
            acc = self._open[epoch] = _EpochAcc()
            while len(self._open) > OPEN_WINDOW:
                self._open.popitem(last=False)
        return acc

    @contextlib.contextmanager
    def phase(self, name: str, kernel: Optional[str] = None,
              stage: Optional[str] = None):
        """Scoped timer: the block's EXCLUSIVE wall time (minus nested
        scopes) lands in `name` — in the active executor cell when one
        is set (epoch-exact flush at the barrier), else directly in the
        newest injected epoch's accumulator. A LOOP phase instead adds
        to the stolen-time accumulator and waits for the next seal
        (module docstring), and takes no stage. With a `stage` the same
        seconds are also filed under (`name`, `stage`), or for
        ``device_compute`` under (`stage`, the kernel label in force):
        the second coordinate of the module docstring. Nesting is
        exclusive between stages as between phases. The scope is a
        ``TraceAnnotation`` for its duration (``phase.<name>`` or
        ``phase.<name>.<stage>``; the block gets it, for
        ``set_metadata``), and a ``phase.<name>`` span of the epoch
        trace where it lasts ``SPAN_MIN_S`` or more."""
        loop = name in LOOP_PHASES
        parent = _SCOPE.get()
        mine = [0.0]
        tok = _SCOPE.set(mine)
        ktok = _KERNEL.set(kernel) if kernel else None
        ann = _spans.annotation(
            "phase." + name if stage is None
            else f"phase.{name}.{stage}")
        ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield ann
        finally:
            dur = time.perf_counter() - t0
            ann.__exit__(None, None, None)
            _SCOPE.reset(tok)
            key = None
            if stage is not None and not loop:
                key = ("device", stage, _KERNEL.get() or "unlabeled") \
                    if name == "device_compute" \
                    else ("stage", name, stage)
            if ktok is not None:
                _KERNEL.reset(ktok)
            if parent is not None:
                parent[0] += dur
            excl = max(0.0, dur - mine[0])
            if loop:
                _STOLEN[0] += excl
                end = time.monotonic()
                with self._lock:
                    self._loop_pending.append(
                        [end - dur, end, name, excl])
                    if len(self._loop_pending) > 4096:
                        # a process that never seals (a cluster
                        # worker): nothing reads these
                        del self._loop_pending[:2048]
            else:
                cell = _CELL.get()
                if cell is not None:
                    cell.seconds[name] = cell.seconds.get(name, 0.0) \
                        + excl
                    if key is not None:
                        cell.stages[key] = cell.stages.get(key, 0.0) \
                            + excl
                else:
                    with self._lock:
                        acc = self._acc()
                        acc.add(name, excl)
                        if key is not None:
                            acc.add_second(key, excl)
            if dur >= SPAN_MIN_S:
                _spans.EPOCH_TRACER.record(
                    "phase." + name, "phase",
                    start_s=time.time() - dur, dur_s=dur,
                    exclusive_s=round(excl, 6))

    def attribute(self, name: str, seconds: float,
                  epoch: Optional[int] = None,
                  kind: Optional[str] = None) -> None:
        """Direct (non-scoped) attribution — executor residue, source
        barrier_wait, barrier-loop commit work. With the executor's
        `kind` the seconds are also filed under
        ``exec_phase.<kind>.<name>``."""
        if seconds <= 0:
            return
        with self._lock:
            acc = self._acc(epoch)
            acc.add(name, seconds)
            if kind:
                acc.add_second(("exec_phase", kind, name), seconds)

    def attribute_idle(self, seconds: float,
                       epoch: Optional[int] = None,
                       source: str = "") -> None:
        """Source park time (barrier_wait), keyed per source. Parallel
        sources idle CONCURRENTLY — the seal folds the across-source
        MAX (the union approximation) into ``barrier_wait`` instead of
        the sum, so N idle sources can never claim N× the epoch
        (share > 1.0 is definitionally noise)."""
        if seconds <= 0:
            return
        with self._lock:
            self._acc(epoch).add_idle(source, seconds)

    def attribute_exec(self, kind: str, seconds: float,
                       epoch: int) -> None:
        """One executor node's exclusive busy seconds of the epoch,
        folded by executor kind (published as ``exec_s.<Kind>``)."""
        if seconds <= 0:
            return
        with self._lock:
            ex = self._acc(epoch).exec_s
            ex[kind] = ex.get(kind, 0.0) + seconds

    def add_bytes(self, direction: str, nbytes: int,
                  kernel: Optional[str] = None) -> None:
        """One host↔device transfer's payload: live Prometheus counter
        (stream_transfer_bytes_total{dir,kernel}) plus the per-epoch
        byte accumulators behind the Perfetto counter tracks."""
        if nbytes <= 0:
            return
        from risingwave_tpu.utils.metrics import STREAMING
        STREAMING.transfer_bytes.inc(
            float(nbytes), dir=direction,
            kernel=kernel or _KERNEL.get() or "unlabeled")
        cell = _CELL.get()
        if cell is not None:
            if direction == "h2d":
                cell.h2d_bytes += int(nbytes)
            else:
                cell.d2h_bytes += int(nbytes)
            return
        with self._lock:
            acc = self._acc()
            if direction == "h2d":
                acc.h2d_bytes += int(nbytes)
            else:
                acc.d2h_bytes += int(nbytes)

    def note_compile(self) -> None:
        """A kernel (re)trace marks the epoch warmup: compile stalls
        are expected to blow the conservation budget and are exempt
        from the strict gate (the RecompileGuard polices them)."""
        with self._lock:
            self._acc().warmup = True

    # -- executor cells (stream/monitor.py) ----------------------------
    def push_cell(self, cell: AttributionCell):
        return _CELL.set(cell)

    def pop_cell(self, token) -> None:
        _CELL.reset(token)

    def commit_cell(self, epoch: int, cell: AttributionCell,
                    kind: Optional[str] = None) -> None:
        """Fold one executor's cell into the epoch it just finished
        (called at barrier passage with the barrier's CURR epoch):
        its named phases, the same by the executor's `kind`
        (``exec_phase.<kind>.<phase>``), and its staged scopes."""
        seconds, stages, h2d, d2h = cell.take()
        if not seconds and not h2d and not d2h:
            return
        with self._lock:
            acc = self._acc(epoch)
            for name, s in seconds.items():
                acc.add(name, s)
                if kind:
                    acc.add_second(("exec_phase", kind, name), s)
            for key, s in stages.items():
                acc.add_second(key, s)
            acc.h2d_bytes += h2d
            acc.d2h_bytes += d2h

    # -- sealing -------------------------------------------------------
    def seal(self, epoch: int, interval_s: float, kind: str = "barrier",
             distributed: bool = False,
             warmup: bool = False,
             domain: str = "",
             wake_gap: Optional[Tuple[float, float]] = None,
             between: Optional[Tuple[float, float]] = None
             ) -> LedgerRecord:
        """Close the epoch's books against its measured barrier
        interval: residual → ``unattributed``, publish the Prometheus
        phase family, the trace phase lanes + counter tracks, and the
        rw_metrics_history row. ``warmup=True`` force-exempts the
        epoch from the conservation gate (callers pass it for
        mutation/topology barriers — deploy work is not epoch work).
        ``domain`` keys the record (and its history row) by the barrier
        domain that ran the epoch — overlapped domains each partition
        their OWN wall timeline, so per-record conservation survives
        the compute/ingest overlap. ``wake_gap`` (time.monotonic
        stamps) is the stretch between the last actor's collect and
        the collecting coroutine's wake-up: the caller's interval
        leaves it to the NEXT epoch, so LOOP-phase sections that ran in
        it stay pending for that epoch's seal. ``between`` is the
        stretch from the previous epoch's books closing to this one's
        inject, where the two do not touch: it lies in no epoch's
        interval, and the LOOP-phase seconds that ran in it (a
        checkpoint built right after the seal holds up the next
        inject) are added to this interval with their phase."""
        with self._lock:
            acc = self._open.pop(epoch, None) or _EpochAcc()
            self._sealed.append(epoch)
            carry, self._carry = self._carry, _EpochAcc()
            stolen, outside = self._take_loop_sections(wake_gap,
                                                       between)
        interval_s = float(interval_s) + outside
        for name, s in list(carry.seconds.items()) + list(stolen.items()):
            acc.add(name, s)
        for key, s in carry.idle.items():
            acc.add_idle(key, s)
        for key, s in carry.second.items():
            acc.add_second(key, s)
        acc.h2d_bytes += carry.h2d_bytes
        acc.d2h_bytes += carry.d2h_bytes
        acc.warmup = acc.warmup or carry.warmup
        seconds = dict(acc.seconds)
        idle = acc.idle_max()
        if idle > 0:
            # across-source MAX (concurrent parks overlap), capped at
            # what the epoch's other phases leave of its interval: a
            # source parks WHILE the actors downstream work its rows
            # and a checkpoint holds the loop, and a second of wall
            # clock that has a worker belongs to the work, not to
            # whoever waited meanwhile
            if interval_s > 0:
                idle = min(idle, max(
                    0.0, float(interval_s) - sum(seconds.values())))
            if idle > 0:
                seconds["barrier_wait"] = seconds.get(
                    "barrier_wait", 0.0) + idle
        rec = LedgerRecord(epoch, kind, float(interval_s),
                           seconds, acc.h2d_bytes,
                           acc.d2h_bytes, acc.warmup or warmup,
                           distributed, domain=domain)
        rec.idle_max = idle
        rec.exec_s = acc.exec_s
        rec.second = acc.second
        rec.recompute_unattributed()
        self.records.append(rec)
        self._publish(rec)
        return rec

    def discard(self, epoch: int) -> None:
        """Drop an open epoch without sealing (virtual-clock loops:
        the measured interval is simulated time, which the wall-clock
        phases can never cover)."""
        with self._lock:
            self._open.pop(epoch, None)
            self._loop_pending.clear()

    def _take_loop_sections(self, wake_gap, between=None):
        """Pending LOOP-phase seconds by phase, less what ran inside
        `wake_gap`, which stays pending, and how many of the seconds
        taken ran inside `between` (a section across an edge of
        either stretch is split by wall time). Caller holds the
        lock."""
        def share(start, end, stretch) -> float:
            if stretch is None or end <= start:
                return 0.0
            return max(0.0, min(end, stretch[1])
                       - max(start, stretch[0])) / (end - start)

        taken: Dict[str, float] = {}
        outside = 0.0
        keep = []
        for start, end, name, excl in self._loop_pending:
            inside = share(start, end, wake_gap)
            if inside > 0:
                keep.append([max(start, wake_gap[0]),
                             min(end, wake_gap[1]), name, excl * inside])
            if inside < 1:
                taken[name] = taken.get(name, 0.0) \
                    + excl * (1.0 - inside)
                outside += excl * share(start, end, between)
        self._loop_pending = keep
        return taken, outside

    def _publish(self, rec: LedgerRecord) -> None:
        from risingwave_tpu.utils.metrics import HISTORY, STREAMING
        for name, s in rec.seconds.items():
            STREAMING.epoch_phase_seconds.inc(s, phase=name)
        extra = {f"phase.{p}": rec.seconds.get(p, 0.0)
                 for p in PHASES + (UNATTRIBUTED,)
                 if p in rec.seconds or p not in MESH_PHASES}
        for kind, s in rec.exec_s.items():
            extra["exec_s." + kind] = s
        for (family, a, b), s in rec.second.items():
            extra[f"{family}.{a}.{b}"] = s
            if family == "exec_phase":
                STREAMING.exec_phase_seconds.inc(s, kind=a, phase=b)
            elif family == "stage":
                STREAMING.phase_stage_seconds.inc(s, phase=a, stage=b)
            else:
                STREAMING.device_host_seconds.inc(s, stage=a, kernel=b)
        extra["coverage"] = rec.coverage()
        extra["epoch_h2d_bytes"] = float(rec.h2d_bytes)
        extra["epoch_d2h_bytes"] = float(rec.d2h_bytes)
        # per-MV freshness of this domain's barrier (ISSUE 14): the
        # materialize passages keyed by the same CURR epoch — so the
        # autoscaler's rw_metrics_history feed carries event-time lag
        # next to the phase shares it must explain
        from risingwave_tpu.stream.freshness import FRESHNESS
        extra.update(FRESHNESS.history_extra(rec.epoch, rec.domain))
        # per-MV cost split of the same sealed epoch (ISSUE 16): the
        # executor cells committed for this epoch roll up by owning MV
        # here, so rw_metrics_history carries mv_device_s.<mv> columns
        # next to the phase shares they partition
        from risingwave_tpu.stream import costs as _costs
        extra.update(_costs.COSTS.history_extra(rec))
        HISTORY.observe(rec.epoch, rec.interval_s, extra=extra,
                        domain=rec.domain)
        now = time.time()
        # counter-track sample (export_chrome renders 'C' events)
        _spans.EPOCH_TRACER.record(
            "ledger.counters", "counter", epoch=rec.epoch, start_s=now,
            transfer_h2d_bytes=rec.h2d_bytes,
            transfer_d2h_bytes=rec.d2h_bytes,
            uploader_queue_depth=STREAMING.uploader_queue_depth.get(),
            backlog_rows=sum(v for _l, v in
                             STREAMING.backlog_rows.series()))

    # -- conservation gate ---------------------------------------------
    def gate_violations(self) -> List[tuple]:
        """(epoch, interval_s, unattributed_s, coverage, domain) per
        sealed steady-state epoch over budget — the tier-1 strict-mode
        gate, domain-keyed so a multi-domain violation names the
        alignment domain whose books leaked."""
        out = []
        for rec in self.records:
            if rec.warmup or rec.distributed:
                continue
            if rec.interval_s < self.GATE_MIN_INTERVAL_S:
                continue
            resid = rec.unattributed_s
            if resid > max(self.GATE_RESIDUAL_FRAC * rec.interval_s,
                           self.GATE_RESIDUAL_MIN_S):
                out.append((rec.epoch, rec.interval_s, resid,
                            rec.coverage(), rec.domain))
        return out

    # -- cross-process merge (cluster drain, like spans.drain_dicts) ---
    def drain_dicts(self) -> List[dict]:
        """Pop every OPEN accumulator as plain dicts (worker →
        coordinator: workers never seal — the coordinator owns the
        barrier interval)."""
        with self._lock:
            stolen, _outside = self._take_loop_sections(None)
            if stolen:
                acc = self._acc(_spans.current_epoch())
                for name, secs in stolen.items():
                    acc.add(name, secs)
            out = [{"epoch": e, "seconds": dict(a.seconds),
                    "h2d_bytes": a.h2d_bytes, "d2h_bytes": a.d2h_bytes,
                    "warmup": a.warmup, "idle_max": a.idle_max()}
                   for e, a in self._open.items()]
            self._open.clear()
        return out

    def ingest(self, dicts: Iterable[dict], worker: str = "",
               resolve: bool = True) -> int:
        """Merge drained worker accumulators: into the sealed record
        of the same epoch when one exists (recomputing the residual —
        this is what resolves a distributed record's conservation),
        else into the open accumulator. ``resolve=False`` keeps the
        record conservation-exempt: the caller knows some worker's
        books never arrived (a dead slot), so the residual would be
        a phantom of the missing process, not rot.

        Merged seconds are also published into the
        stream_epoch_phase_seconds family so the cluster's Prometheus
        view carries worker time, not just the coordinator's (the
        residual correction, in contrast, lives only in the records —
        a counter cannot un-count the already-published coordinator
        `unattributed`; rw_metrics_history rows likewise keep their
        seal-time coordinator view)."""
        from risingwave_tpu.utils.metrics import STREAMING
        by_epoch = {r.epoch: r for r in self.records}
        n = 0
        for d in dicts:
            e = int(d["epoch"])
            rec = by_epoch.get(e)
            if rec is not None:
                for name, s in (d.get("seconds") or {}).items():
                    rec.seconds[name] = rec.seconds.get(name, 0.0) \
                        + float(s)
                    STREAMING.epoch_phase_seconds.inc(
                        float(s), phase=name)
                w_idle = float(d.get("idle_max", 0.0))
                if w_idle > 0:
                    # barrier_wait merges as MAX-then-cap across
                    # processes (their sources park over the same wall
                    # interval), never as a sum
                    cap = rec.interval_s if rec.interval_s > 0 \
                        else float("inf")
                    new_max = max(rec.idle_max, w_idle)
                    delta = min(new_max, cap) - min(rec.idle_max, cap)
                    rec.idle_max = new_max
                    if delta > 0:
                        rec.seconds["barrier_wait"] = \
                            rec.seconds.get("barrier_wait", 0.0) + delta
                rec.h2d_bytes += int(d.get("h2d_bytes", 0))
                rec.d2h_bytes += int(d.get("d2h_bytes", 0))
                rec.warmup = rec.warmup or bool(d.get("warmup"))
                if worker and worker not in rec.workers:
                    rec.workers.append(worker)
                if resolve:
                    rec.distributed = False  # conservation checkable
                rec.recompute_unattributed()
            else:
                with self._lock:
                    acc = self._acc(e)
                    for name, s in (d.get("seconds") or {}).items():
                        acc.add(name, float(s))
                    w_idle = float(d.get("idle_max", 0.0))
                    if w_idle > 0:
                        acc.add_idle(worker or "remote", w_idle)
                    acc.h2d_bytes += int(d.get("h2d_bytes", 0))
                    acc.d2h_bytes += int(d.get("d2h_bytes", 0))
                    acc.warmup = acc.warmup or bool(d.get("warmup"))
            n += 1
        return n

    # -- reads ---------------------------------------------------------
    # epochs shorter than this carry only fixed barrier machinery (an
    # empty heartbeat is ~sub-ms of inject/collect bookkeeping): they
    # hold no meaningful share of a run and are excluded from the
    # coverage statistics (still counted, still summed into phases)
    MICRO_EPOCH_S = 0.005

    def phase_breakdown(self, steady_only: bool = True) -> dict:
        """Aggregate share view over sealed epochs (the ``ctl phases``
        totals). ``steady_only`` drops warmup (compile-bearing)
        epochs."""
        recs = [r for r in self.records
                if not (steady_only and r.warmup)]
        if not recs:
            return {"epochs": 0}
        total = sum(r.interval_s for r in recs)
        phases = {}
        for name in PHASES + (UNATTRIBUTED,):
            s = sum(r.seconds.get(name, 0.0) for r in recs)
            if s > 0 or name == UNATTRIBUTED:
                phases[name] = {
                    "seconds": round(s, 6),
                    "share": round(s / total, 4) if total > 0 else 0.0}
        full = [r for r in recs if r.interval_s >= self.MICRO_EPOCH_S]
        covs = [r.coverage() for r in (full or recs)]
        return {
            "epochs": len(recs),
            "micro_epochs": len(recs) - len(full),
            "interval_s": round(total, 6),
            "phases": phases,
            "coverage_mean": round(sum(covs) / len(covs), 4),
            "coverage_min": round(min(covs), 4),
            "h2d_bytes": int(sum(r.h2d_bytes for r in recs)),
            "d2h_bytes": int(sum(r.d2h_bytes for r in recs)),
        }

    def report(self, last_n: int = 16) -> str:
        """Human-readable per-epoch table (``ctl phases``); under an
        epoch's table the serving heartbeat's wait before its inject,
        where the history row has one (time between epochs)."""
        from risingwave_tpu.utils.metrics import HISTORY
        lines = []
        for rec in list(self.records)[-last_n:]:
            head = (f"epoch {rec.epoch:#x} ({rec.kind}"
                    f"{', warmup' if rec.warmup else ''}): "
                    f"{rec.interval_s * 1e3:.2f}ms, coverage "
                    f"{rec.coverage() * 100:.0f}%")
            lines.append(head)
            for name in PHASES + (UNATTRIBUTED,):
                s = rec.seconds.get(name, 0.0)
                if s <= 0:
                    continue
                share = (100.0 * s / rec.interval_s
                         if rec.interval_s > 0 else 0.0)
                lines.append(f"  {name:<15} {s * 1e3:9.2f}ms "
                             f"{share:5.1f}%")
            if rec.h2d_bytes or rec.d2h_bytes:
                lines.append(f"  bytes: h2d={rec.h2d_bytes} "
                             f"d2h={rec.d2h_bytes}")
            between = HISTORY.value(rec.epoch, "phase.heartbeat_wait")
            if between:
                lines.append(f"  before its inject, in no interval: "
                             f"heartbeat_wait {between * 1e3:.2f}ms")
        return "\n".join(lines)

    def clear(self) -> None:
        with self._lock:
            self._open.clear()
            self.records.clear()
            self._loop_pending.clear()
            self._sealed.clear()
            self._carry = _EpochAcc()


# the process-global ledger (worker processes drain to the coordinator)
LEDGER = PhaseLedger()


def staged(stage: str):
    """Decorator: the function's body is one stage of ``host_emit``,
    a ``LEDGER.phase("host_emit", stage=stage)`` scope. For a
    per-chunk or per-barrier function; never one a row loop calls."""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kw):
            with LEDGER.phase("host_emit", stage=stage):
                return fn(*args, **kw)
        return scoped
    return deco
