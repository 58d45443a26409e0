"""Epoch-causal tracing: the flight recorder behind rw_epoch_trace.

Reference parity: the tracing-crate spans the reference threads from
barrier inject through every executor (TracingContext on Barrier,
src/stream/src/executor/mod.rs:253) plus the await-tree dumps — grown
into what arxiv 2103.10169 (Hazelcast Jet) treats as table stakes for
a p99 latency discipline: every epoch's barrier round leaves a causal
timeline (inject → per-actor executor processing → exchange transfer →
device dispatch → async upload → commit), so a slow barrier is a
navigable trace, not one opaque number.

Design:

- **Always on, bounded.** Recording is a dict append; the flight
  recorder keeps the last `EPOCH_WINDOW` epochs, each capped at
  `MAX_SPANS_PER_EPOCH` spans (drops are counted, never silent).
- **Keyed by the barrier's CURR epoch** — the same key
  rw_barrier_latency rows use, so a profile row and its trace join
  trivially. Spans recorded between barriers (device dispatches)
  attribute to the most recently *injected* epoch; with a deep
  in-flight window that is an approximation, exact under the
  stepping drivers (in_flight drains before the next inject).
- **Wall-clock timestamps** (`time.time()`): spans merge across
  worker processes on one host, where monotonic clocks don't compare.
- **Promotion.** The slow-barrier watchdog (meta/barrier.py) moves an
  over-threshold epoch's spans into a retained store (`RETAIN_SLOTS`
  traces) with a one-line straggler diagnosis, surviving after the
  flight ring has rolled past the epoch.
- Export: `export_chrome()` renders Chrome trace-event JSON (Perfetto
  loads it directly); `rows()` backs the rw_epoch_trace system table.
- **One clock with the device.** The barrier plane, the ledger's
  phases, the dispatches and the checkpoint's stages also enter
  ``annotation(name)`` for their duration, so a ``jax.profiler`` trace
  holds them in its host plane beside the device's operations.

Span ids embed the process id in their high bits so traces drained
from worker processes merge without collisions.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

EPOCH_WINDOW = 64          # epochs kept in the flight ring
MAX_SPANS_PER_EPOCH = 2048  # per-epoch span cap (overflow is counted)
RETAIN_SLOTS = 32          # promoted (slow-barrier) traces kept


@dataclass
class TraceSpan:
    """One timed event in an epoch's causal timeline."""

    name: str                       # e.g. "HashAggExecutor(actor=7)"
    cat: str                        # barrier|actor|exchange|dispatch|
    #                                 compile|upload|commit|diagnosis
    epoch: int                      # barrier CURR epoch value
    start_s: float                  # wall clock (time.time())
    dur_s: float
    span_id: int
    parent_id: Optional[int] = None
    worker: str = ""                # "" = this process / coordinator
    actor: Optional[int] = None
    args: Dict[str, object] = field(default_factory=dict)

    def to_dict(self) -> dict:
        d = {"name": self.name, "cat": self.cat, "epoch": self.epoch,
             "start_s": self.start_s, "dur_s": self.dur_s,
             "span_id": self.span_id}
        if self.parent_id is not None:
            d["parent_id"] = self.parent_id
        if self.worker:
            d["worker"] = self.worker
        if self.actor is not None:
            d["actor"] = self.actor
        if self.args:
            d["args"] = self.args
        return d

    @staticmethod
    def from_dict(d: dict) -> "TraceSpan":
        return TraceSpan(
            d["name"], d["cat"], int(d["epoch"]), float(d["start_s"]),
            float(d["dur_s"]), int(d["span_id"]),
            parent_id=(None if d.get("parent_id") is None
                       else int(d["parent_id"])),
            worker=d.get("worker", ""),
            actor=(None if d.get("actor") is None
                   else int(d["actor"])),
            args=dict(d.get("args") or {}))


_CURRENT_EPOCH = 0        # newest INJECTED epoch (see module docstring)


def set_current_epoch(value: int) -> None:
    global _CURRENT_EPOCH
    _CURRENT_EPOCH = int(value)


def current_epoch() -> int:
    return _CURRENT_EPOCH


def annotation(name: str, epoch: Optional[int] = None) -> TraceAnnotation:
    """The program's spans on the profiler's clock: a context manager
    that puts `name` into the host plane of a running
    ``jax.profiler`` trace, on the same clock as the device's ``XLA
    Ops``, nested with the other annotations of its thread as they ran.
    With no profiler session it is a flag check in C++."""
    return TraceAnnotation(
        name, epoch=_CURRENT_EPOCH if epoch is None else epoch)


class EpochTracer:
    """Per-epoch span ring (flight recorder) + retained slow traces."""

    def __init__(self, epoch_window: int = EPOCH_WINDOW,
                 max_spans: int = MAX_SPANS_PER_EPOCH,
                 retain_slots: int = RETAIN_SLOTS):
        self.epoch_window = epoch_window
        self.max_spans = max_spans
        self.retain_slots = retain_slots
        # epoch -> [TraceSpan] in record order (ring by insertion)
        self._flight: "OrderedDict[int, List[TraceSpan]]" = OrderedDict()
        # epoch -> [spans, diagnosis, barrier total_s] promoted by the
        # watchdog (total_s kept so a later cross-process span merge
        # can recompute the straggler line over the full picture)
        self._retained: "OrderedDict[int, list]" = OrderedDict()
        self._roots: Dict[int, int] = {}     # epoch -> root span id
        self.dropped = 0                     # spans over the epoch cap
        # pid in the high bits: ids minted in a worker process never
        # collide with the coordinator's when traces merge
        self._ids = itertools.count((os.getpid() & 0xFFFF) << 32 | 1)
        # appends race the uploader's commit callback thread; one
        # uncontended acquire per span is noise next to the work the
        # span describes
        self._lock = threading.Lock()

    # -- recording -----------------------------------------------------
    def next_id(self) -> int:
        return next(self._ids)

    def record(self, name: str, cat: str, epoch: Optional[int] = None,
               start_s: Optional[float] = None, dur_s: float = 0.0,
               parent: Optional[int] = None, actor: Optional[int] = None,
               worker: str = "", span_id: Optional[int] = None,
               **args) -> int:
        """Append one completed span; returns its id."""
        e = _CURRENT_EPOCH if epoch is None else int(epoch)
        if parent is None:
            parent = self._roots.get(e)
        s = TraceSpan(name, cat, e,
                      time.time() if start_s is None else start_s,
                      dur_s, span_id if span_id is not None
                      else self.next_id(),
                      parent_id=parent, worker=worker, actor=actor,
                      args=args)
        self._append(s)
        return s.span_id

    def _append(self, s: TraceSpan) -> None:
        with self._lock:
            bucket = self._flight.get(s.epoch)
            if bucket is None:
                bucket = self._flight[s.epoch] = []
                while len(self._flight) > self.epoch_window:
                    old, spans = self._flight.popitem(last=False)
                    self._roots.pop(old, None)
            if len(bucket) >= self.max_spans:
                self.dropped += 1
                from risingwave_tpu.utils.metrics import STREAMING
                STREAMING.trace_spans_dropped.inc()
                return
            bucket.append(s)

    def set_root(self, epoch: int, span_id: int) -> None:
        """The epoch's inject span: default parent for every span
        recorded into that epoch without an explicit parent."""
        self._roots[epoch] = span_id

    def root_id(self, epoch: int) -> Optional[int]:
        return self._roots.get(epoch)

    # -- promotion (slow-barrier watchdog) -----------------------------
    def promote(self, epoch: int, diagnosis: str = "",
                total_s: float = 0.0) -> None:
        """Retain the epoch's full trace past the flight ring's life."""
        with self._lock:
            spans = list(self._flight.get(epoch, ()))
            self._retained[epoch] = [spans, diagnosis, total_s]
            while len(self._retained) > self.retain_slots:
                self._retained.popitem(last=False)

    def refresh_diagnoses(self) -> None:
        """Recompute each retained trace's straggler line — called
        after a worker-span merge, when the coordinator-side diagnosis
        predates the per-actor spans that name the real laggard."""
        for e in list(self._retained):
            entry = self._retained.get(e)
            if entry is not None and entry[2] > 0:
                entry[1] = self.diagnose(e, entry[2])

    def diagnose(self, epoch: int, total_s: float) -> str:
        """One-line straggler attribution: the largest actor-phase span
        of the epoch as actor/executor/phase/% of the barrier round."""
        spans = self.spans_for(epoch)
        # upload spans are excluded: the async checkpoint tail is
        # overlapped with younger barriers and deliberately NOT part
        # of barrier total_s (EpochProfile) — naming it as the
        # straggler would misdirect the operator from the real laggard
        work = [s for s in spans
                if s.cat in ("actor", "dispatch", "exchange")]
        if not work or total_s <= 0:
            return (f"epoch {epoch:#x}: no per-actor spans recorded "
                    f"({total_s * 1e3:.1f}ms barrier)")
        top = max(work, key=lambda s: s.dur_s)
        who = f"actor {top.actor} " if top.actor is not None else ""
        where = f"@{top.worker} " if top.worker else ""
        return (f"epoch {epoch:#x}: straggler {who}{where}"
                f"{top.name} phase={top.cat} "
                f"{top.dur_s * 1e3:.1f}ms = "
                f"{min(100.0, 100.0 * top.dur_s / total_s):.0f}% of "
                f"{total_s * 1e3:.1f}ms barrier")

    # -- reads ---------------------------------------------------------
    def epochs(self) -> List[int]:
        with self._lock:
            return sorted(set(self._flight) | set(self._retained))

    def spans_for(self, epoch: int) -> List[TraceSpan]:
        """Flight + retained spans of one epoch (retained wins on
        overlap — it was snapshotted from the same bucket)."""
        with self._lock:
            if epoch in self._retained:
                spans = self._retained[epoch][0]
                flight = self._flight.get(epoch, ())
                seen = {s.span_id for s in spans}
                return spans + [s for s in flight
                                if s.span_id not in seen]
            return list(self._flight.get(epoch, ()))

    def diagnosis_for(self, epoch: int) -> str:
        entry = self._retained.get(epoch)
        return entry[1] if entry else ""

    def retained_epochs(self) -> List[int]:
        return list(self._retained)

    def rows(self) -> List[tuple]:
        """(epoch, span_id, parent_id, name, cat, worker, actor,
        start_s, dur_s, retained, detail) per span — the rw_epoch_trace
        payload. Retained traces contribute one extra cat='diagnosis'
        row carrying the straggler line."""
        out = []
        for e in self.epochs():
            retained = 1 if e in self._retained else 0
            for s in self.spans_for(e):
                out.append((s.epoch, s.span_id,
                            s.parent_id if s.parent_id is not None
                            else 0,
                            s.name, s.cat, s.worker,
                            s.actor if s.actor is not None else -1,
                            s.start_s, s.dur_s, retained,
                            json.dumps(s.args) if s.args else ""))
            diag = self.diagnosis_for(e)
            if diag:
                out.append((e, 0, 0, diag, "diagnosis", "", -1,
                            0.0, 0.0, 1, ""))
        return out

    # -- cross-process merge -------------------------------------------
    def drain_dicts(self) -> List[dict]:
        """Pop every span as plain dicts (worker → coordinator drain;
        a second drain returns only spans recorded since)."""
        with self._lock:
            out = [s.to_dict() for spans in self._flight.values()
                   for s in spans]
            seen = {d["span_id"] for d in out}
            for entry in self._retained.values():
                out += [s.to_dict() for s in entry[0]
                        if s.span_id not in seen]
            self._flight.clear()
            self._retained.clear()
        return out

    def ingest(self, dicts: Iterable[dict], worker: str = "") -> int:
        """Merge drained spans (tagging their origin process)."""
        n = 0
        for d in dicts:
            s = TraceSpan.from_dict(d)
            if worker and not s.worker:
                s.worker = worker
            # re-promote into retained if this epoch was promoted here
            self._append(s)
            with self._lock:
                entry = self._retained.get(s.epoch)
                if entry is not None and \
                        all(x.span_id != s.span_id for x in entry[0]):
                    entry[0].append(s)
            n += 1
        return n

    # -- export --------------------------------------------------------
    def export_chrome(self, epochs: Optional[Iterable[int]] = None
                      ) -> dict:
        """Chrome trace-event JSON (Perfetto-loadable): one 'X' event
        per span (pid = worker, tid = actor or category) plus 's'/'f'
        flow events binding each span to its parent — the causal edges
        survive across process lanes."""
        def lane(s: TraceSpan) -> Tuple[str, str]:
            return (s.worker or "coordinator",
                    f"actor-{s.actor}" if s.actor is not None
                    else s.cat)

        events = []
        want = self.epochs() if epochs is None else sorted(set(epochs))
        for e in want:
            spans = self.spans_for(e)
            by_id = {s.span_id: s for s in spans}
            for s in spans:
                if s.cat == "counter":
                    # counter tracks ('C' events): one per numeric arg
                    # — transfer bytes, uploader queue depth, backlog
                    # rows sampled at each epoch seal render as value-
                    # over-time lanes next to the span timeline
                    for key, val in s.args.items():
                        if not isinstance(val, (int, float)):
                            continue
                        events.append({
                            "name": key, "cat": "counter", "ph": "C",
                            "ts": s.start_s * 1e6,
                            "pid": s.worker or "coordinator",
                            "args": {"value": float(val)}})
                    continue
                pid, tid = lane(s)
                ts = s.start_s * 1e6
                dur = max(s.dur_s * 1e6, 1.0)
                args = {"epoch": f"{s.epoch:#x}",
                        "span_id": s.span_id, **s.args}
                if s.parent_id is not None:
                    args["parent_id"] = s.parent_id
                events.append({"name": s.name, "cat": s.cat, "ph": "X",
                               "ts": ts, "dur": dur, "pid": pid,
                               "tid": tid, "args": args})
                parent = (by_id.get(s.parent_id)
                          if s.parent_id is not None else None)
                if parent is not None:
                    # one flow id per causal edge (the child's span
                    # id): 's' leaves the PARENT's slice, 'f' lands on
                    # the child's start — Perfetto draws parent→child.
                    # The start is clamped to never postdate the
                    # finish (a zero-duration root would otherwise
                    # make the flow invalid and get dropped).
                    ppid, ptid = lane(parent)
                    ts_s = min(parent.start_s * 1e6, ts)
                    events.append({"name": "causal", "cat": "flow",
                                   "ph": "s", "ts": ts_s, "pid": ppid,
                                   "tid": ptid, "id": s.span_id,
                                   "bp": "e"})
                    events.append({"name": "causal", "cat": "flow",
                                   "ph": "f", "ts": ts, "pid": pid,
                                   "tid": tid, "id": s.span_id,
                                   "bp": "e"})
            diag = self.diagnosis_for(e)
            if diag:
                events.append({"name": diag, "cat": "diagnosis",
                               "ph": "i", "ts": 0, "pid": "coordinator",
                               "tid": "diagnosis", "s": "g"})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def clear(self) -> None:
        with self._lock:
            self._flight.clear()
            self._retained.clear()
            self._roots.clear()
            self.dropped = 0


# the process-global flight recorder (every hook records here; worker
# processes drain theirs to the coordinator over the control channel)
EPOCH_TRACER = EpochTracer()


from contextlib import contextmanager as _contextmanager

from risingwave_tpu.utils import failpoint as _failpoint


@_contextmanager
def dispatch_span(kernel: str, rows: float, **args):
    """Time one device dispatch (the host-side call: pack + transfer +
    launch enqueue) into the current epoch's trace, stamped with kernel
    identity and row payload. A retrace during the call shows up as a
    sibling compile span (note_compile).

    Phase ledger: the span's EXCLUSIVE time (minus nested h2d/d2h
    scopes and a fetch's wait) is the launch's device_compute share
    (stage ``launch``: history ``device.launch.<kernel>``), stamped
    with the kernel label so transfers and waits recorded inside
    inherit it."""
    from risingwave_tpu.utils.ledger import LEDGER
    t0 = time.time()
    try:
        with annotation(kernel), \
                LEDGER.phase("device_compute", kernel=kernel,
                             stage="launch"):
            # ledger-test seam: a sleep spec here is wall time INSIDE
            # one kernel's dispatch — it must land in the dispatching
            # domain's device_compute books only (the per-domain
            # overlap oracle). Guarded so the unarmed hot path pays
            # one dict-truthiness check, not an f-string per dispatch.
            if _failpoint._ARMED:
                _failpoint.fail_point(f"ledger.dispatch.{kernel}")
            yield
    finally:
        EPOCH_TRACER.record(kernel, "dispatch", start_s=t0,
                            dur_s=time.time() - t0,
                            rows=float(rows), **args)


def note_compile(label: str) -> None:
    """Called from INSIDE a jitted function's Python body — which runs
    only while jax traces it — so every call IS a (re)trace event:
    first-compile at warmup, shape-churn recompiles in steady state.
    Counts stream_kernel_recompile_count, drops a compile span into
    the current epoch's trace, and marks the epoch warmup in the phase
    ledger (compile stalls are exempt from the conservation gate)."""
    from risingwave_tpu.utils.metrics import STREAMING
    STREAMING.kernel_recompile.inc(1, kernel=label)
    from risingwave_tpu.utils.ledger import LEDGER
    LEDGER.note_compile()
    EPOCH_TRACER.record(f"compile:{label}", "compile", kernel=label)
