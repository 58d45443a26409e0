"""MonitoredExecutor: per-(fragment, actor, executor) instrumentation.

Reference parity: src/stream/src/executor/monitor/streaming_stats.rs —
every executor in a deployed chain is wrapped so row/chunk throughput
and processing time land in the process registry under a
`fragment/actor/executor` label scheme, and the await-registry always
knows which executor an actor is currently parked in (the await-tree
dump a stalled barrier attributes against).

Exclusive processing time: in a pull pipeline, awaiting an inner
executor's `__anext__` includes the whole upstream chain's work. Every
node in the chain is wrapped, so a wrapper's *exclusive* time is its
own cumulative pull time minus its wrapped inputs' — computed per
epoch at each barrier passage (both sides of the subtraction observe
the same barrier boundary: an input's clock only advances while its
consumer awaits it).
"""

from __future__ import annotations

import re
import threading
import time
from typing import AsyncIterator, Dict, List, Optional, Tuple

from risingwave_tpu.stream import exchange as _xchg
from risingwave_tpu.stream import merge as _merge
from risingwave_tpu.stream.executor import (
    Executor, ExecutorInfo, executor_children,
)
from risingwave_tpu.stream.message import (
    Barrier, Message, is_barrier, is_chunk,
)
from risingwave_tpu.stream import costs as _costs
from risingwave_tpu.stream import hotkeys as _hotkeys
from risingwave_tpu.utils import ledger as _ledger
from risingwave_tpu.utils import spans as _spans
from risingwave_tpu.utils.failpoint import fail_point
from risingwave_tpu.utils.metrics import STREAMING as _METRICS
from risingwave_tpu.utils.trace import GLOBAL_AWAITS as _AWAITS


# assertion mode for zero-visible-row emissions: the spine suppresses
# empty chunks end-to-end (dispatchers, filters, coalescers), so a
# monitored executor emitting one is a regression. Tests flip this on
# (tests/conftest.py) to REJECT empties; production only counts them.
STRICT_EMPTY_CHUNKS = False


def set_strict_empty_chunks(on: bool) -> None:
    global STRICT_EMPTY_CHUNKS
    STRICT_EMPTY_CHUNKS = bool(on)


class UtilizationTable:
    """Last-barrier utilization tricolor per (fragment, actor, node):
    busy / backpressure / idle shares of the barrier interval — the
    Flink-style triple, kept as a process-global snapshot the
    bottleneck walker, ``rw_actor_utilization`` and ``ctl top`` read.

    Accounting identity (gated in tier-1 strict mode, like the phase
    ledger's conservation check): each triple sums to ≤ 1.0 + ε. Busy
    is the node's EXCLUSIVE pull time minus its idle park (source /
    RemoteInput / Receiver input waits) minus its credit park
    (exchange backpressure), so the three parts partition disjoint
    wall time inside one interval by construction — a sum above 1 is
    a double-count bug, not noise."""

    EPSILON = 0.05

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (fragment, actor_id, node) → (executor, epoch, interval_s,
        #                               busy, backpressure, idle)
        self._rows: Dict[Tuple[str, int, int], tuple] = {}
        self._violations: List[tuple] = []

    def observe(self, labels: Dict[str, str], epoch: int,
                interval_s: float, busy_s: float, bp_s: float,
                idle_s: float) -> None:
        if interval_s <= 0:
            return
        busy = busy_s / interval_s
        bp = bp_s / interval_s
        idle = idle_s / interval_s
        key = (labels["fragment"], int(labels["actor"]),
               int(labels["node"]))
        with self._lock:
            if busy + bp + idle > 1.0 + self.EPSILON:
                self._violations.append(
                    (key, labels["executor"], epoch,
                     round(busy, 4), round(bp, 4), round(idle, 4)))
            self._rows[key] = (labels["executor"], int(epoch),
                               interval_s, busy, bp, idle)
        for state, v in (("busy", busy), ("backpressure", bp),
                         ("idle", idle)):
            _METRICS.executor_utilization.set(v, state=state, **labels)

    def get(self, fragment: str, actor_id: int, node: int
            ) -> Optional[tuple]:
        with self._lock:
            return self._rows.get((fragment, actor_id, node))

    def ingest_rows(self, rows) -> int:
        """Merge another process's utilization snapshot (the worker
        ``signals`` drain): rows in the ``rows()`` wire shape land in
        this table keyed exactly like local ones — actor ids are
        cluster-unique, so worker and coordinator rows never collide.
        Ratios arrive pre-computed; the accounting gate ran in the
        process that measured them, so no re-validation here."""
        n = 0
        with self._lock:
            for (a, f, node, ex, e, interval, busy, bp, idle) in rows:
                self._rows[(str(f), int(a), int(node))] = (
                    str(ex), int(e), float(interval), float(busy),
                    float(bp), float(idle))
                n += 1
        return n

    def prune(self, keep_actors) -> int:
        """Drop rows for actors outside ``keep_actors`` — the merged
        coordinator view's eviction path: workers drop their own rows
        at actor exit, but ingested copies would otherwise outlive
        every rescale/recovery (fresh actor ids each redeploy) and
        grow the table without bound."""
        keep = set(keep_actors)
        with self._lock:
            dead = [k for k in self._rows if k[1] not in keep]
            for k in dead:
                del self._rows[k]
        return len(dead)

    def rows(self) -> List[tuple]:
        """(actor_id, fragment, node, executor, epoch, interval_s,
        busy_ratio, backpressure_ratio, idle_ratio) sorted by busy
        desc — the rw_actor_utilization payload and ctl top's sort."""
        with self._lock:
            out = [(a, f, n, ex, e, round(i, 6), round(b, 6),
                    round(bp, 6), round(idl, 6))
                   for (f, a, n), (ex, e, i, b, bp, idl)
                   in self._rows.items()]
        return sorted(out, key=lambda r: -r[6])

    def drop_actor(self, actor_id: int) -> None:
        with self._lock:
            dead = [k for k in self._rows if k[1] == actor_id]
            for k in dead:
                ex = self._rows.pop(k)[0]
                for state in ("busy", "backpressure", "idle"):
                    _METRICS.executor_utilization.remove(
                        state=state, fragment=k[0],
                        actor=str(actor_id), node=str(k[2]),
                        executor=ex)

    def gate_violations(self) -> List[tuple]:
        with self._lock:
            return list(self._violations)

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()
            self._violations.clear()


UTILIZATION = UtilizationTable()


class Topology:
    """Deployed monitored chains by actor: (fragment, root wrapper) —
    the graph the bottleneck walker descends (wrapper .children edges
    are exactly the dataflow's upstream edges, input-channel nodes
    included). Registered by install_monitoring, dropped at actor
    exit."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._actors: Dict[int, Tuple[str, "MonitoredExecutor"]] = {}

    def register(self, actor_id: int, fragment: str,
                 root: "MonitoredExecutor") -> None:
        with self._lock:
            self._actors[actor_id] = (fragment, root)

    def drop_actor(self, actor_id: int) -> None:
        with self._lock:
            self._actors.pop(actor_id, None)
        UTILIZATION.drop_actor(actor_id)

    def roots(self, fragments=None, actors=None) -> List[tuple]:
        """[(actor_id, fragment, root wrapper)]; ``fragments`` (a set
        of job names) restricts to one barrier domain's chains, and
        ``actors`` (a set of actor ids — the barrier-domain frame's
        actor filter) restricts to that domain's actors on THIS
        process (a worker hosts several domains' chains in one
        registry)."""
        with self._lock:
            items = list(self._actors.items())
        return [(a, f, r) for a, (f, r) in items
                if (fragments is None or f in fragments)
                and (actors is None or a in actors)]

    def clear(self) -> None:
        with self._lock:
            self._actors.clear()


TOPOLOGY = Topology()


class MonitoredExecutor(Executor):
    """Transparent metrics wrapper around one executor node."""

    def __init__(self, inner: Executor, fragment: str, actor_id: int,
                 node: int,
                 children: Optional[List["MonitoredExecutor"]] = None):
        super().__init__(ExecutorInfo(inner.schema,
                                      list(inner.pk_indices),
                                      inner.identity))
        self.inner = inner
        self.children = list(children or [])
        self.labels = {"fragment": fragment, "actor": str(actor_id),
                       "executor": inner.identity, "node": str(node)}
        self.total_busy_s = 0.0     # cumulative time inside inner pulls
        self._mark_own = 0.0        # totals at the last barrier
        self._mark_kids = 0.0
        self._mark_idle = 0.0       # inner.idle_wait_s at last barrier
        # exchange-credit park time recorded during THIS node's pulls
        # (stream/exchange.py cell contract, mirroring the ledger
        # cells) — subtracted from busy and published as the tricolor's
        # backpressure share
        self._park_cell = [0.0]
        self._mark_park = 0.0
        # time THIS node's pulls spent parked in barrier_align_n for
        # inputs it pulls concurrently (stream/merge.py): what comes
        # out of its busy time in place of the inputs' overlapping sum
        self._align_cell = [0.0]
        self._mark_align = 0.0
        self._mark_meter = 0.0      # actor-loop meter mark (root only)
        self._last_flush_pc: Optional[float] = None
        self._who = f"actor-{actor_id}/{node}:{inner.identity}"
        # executor kind: the identity up to its first non-letter
        # ("HashAggExecutor(actor=7)" → "HashAggExecutor"), the key of
        # the per-epoch exec_s.<Kind> history names
        self._kind = re.match(r"[A-Za-z]*", inner.identity).group() \
            or type(inner).__name__
        # phase-ledger attribution cell: named phases recorded during
        # THIS executor's pulls land here (asyncio-context scoped, so
        # interleaved actors never cross-charge); the barrier flush
        # commits it epoch-exactly and classifies the residue
        self._cell = _ledger.AttributionCell()
        self._fallback_phase = (
            "host_ingest"
            if "Source" in inner.identity
            or "Source" in type(inner).__name__ else "host_emit")

    def __getattr__(self, name: str):
        # transparent introspection: chain walkers (tests, debuggers)
        # reach the inner executor's attributes (.input, .kernel,
        # .sides, .table, …) through the wrapper
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)

    def _flush_epoch(self, barrier: Barrier) -> None:
        epoch = barrier.epoch.curr.value
        own = self.total_busy_s
        kids = sum(c.total_busy_s for c in self.children)
        # an aligner's inputs ran while it waited in barrier_align_n;
        # anyone else's, one at a time, inside its own pulls
        waited = (self._align_cell[0] - self._mark_align) \
            or (kids - self._mark_kids)
        excl = max(0.0, (own - self._mark_own) - waited)
        self._mark_own, self._mark_kids = own, kids
        self._mark_align = self._align_cell[0]
        # sources (no wrapped inputs to subtract) expose the time they
        # spent PARKED on the barrier channel — idle, not processing:
        # without this, a source waiting out a slow downstream epoch
        # reads as the busiest executor in the chain
        idle = getattr(self.inner, "idle_wait_s", None)
        idle_delta = 0.0
        if idle is not None:
            idle_delta = max(0.0, idle - self._mark_idle)
            excl = max(0.0, excl - idle_delta)
            self._mark_idle = idle
        # sender-side credit park (ISSUE 14): time this node's pulls
        # spent BLOCKED for exchange credits is backpressure, not
        # processing — without the subtraction a straggler diagnosis
        # blames the victim of a slow consumer. Only the IN-PULL park
        # (the cell) comes out of busy: the actor-loop meter's
        # dispatch parks happen BETWEEN pulls and were never in
        # total_busy_s — subtracting them too would deflate the
        # root's real work. The root (node 0) still drains the meter
        # into its backpressure share (the park is that actor's wall
        # time either way).
        park_pull = max(0.0, self._park_cell[0] - self._mark_park)
        self._mark_park = self._park_cell[0]
        if park_pull > 0:
            excl = max(0.0, excl - park_pull)
        park_delta = park_pull
        if self.labels["node"] == "0":
            meter = _xchg.current_actor_meter()
            if meter is not None:
                park_delta += max(0.0, meter[0] - self._mark_meter)
                self._mark_meter = meter[0]
        _METRICS.executor_busy.inc(excl, **self.labels)
        _METRICS.executor_epoch_seconds.observe(excl, **self.labels)
        # utilization tricolor: busy / backpressure / idle shares
        # of THIS node's barrier-to-barrier interval (its own
        # flush-to-flush wall clock — all three parts are disjoint
        # wall time inside it, so the triple sums to ≤ 1)
        now_pc = time.perf_counter()
        if self._last_flush_pc is not None:
            UTILIZATION.observe(
                self.labels, epoch,
                interval_s=now_pc - self._last_flush_pc,
                busy_s=excl, bp_s=park_delta, idle_s=idle_delta)
        self._last_flush_pc = now_pc
        # phase ledger: named phases recorded during this
        # executor's pulls commit epoch-exactly; the exclusive
        # residue is host work that is provably NOT pack/transfer/
        # compute — source decode loops (host_ingest) or downstream
        # reassembly/state writes/dispatch (host_emit); the barrier
        # park is barrier_wait. Both are also filed by this
        # executor's kind (exec_phase.<Kind>.<phase>): over the phases
        # they add up to exec_s.<Kind>
        named = self._cell.named_total()
        _ledger.LEDGER.attribute_exec(self._kind, excl, epoch)
        # per-MV split of the SAME cell the ledger is about to
        # commit: the fragment label is the MV/job name, and
        # cells nest exclusively, so summing fragments can
        # never mint device time the domain didn't ledger
        _costs.COSTS.observe_cell(
            self.labels["fragment"], epoch,
            self._cell.seconds.get("device_compute", 0.0),
            self._cell.h2d_bytes, self._cell.d2h_bytes)
        _ledger.LEDGER.commit_cell(epoch, self._cell, kind=self._kind)
        resid = excl - named
        if resid > 0:
            _ledger.LEDGER.attribute(self._fallback_phase, resid,
                                     epoch, kind=self._kind)
        if park_delta > 0:
            # credit parks are their own ledger phase: the wall
            # time subtracted from busy must still be conserved
            _ledger.LEDGER.attribute("backpressure_wait",
                                     park_delta, epoch)
        if idle_delta > 0:
            # keyed per source: parallel sources park CONCURRENTLY
            # and the ledger folds the across-source max, not the
            # sum, into barrier_wait at seal (N idle sources must
            # not claim N times the epoch)
            _ledger.LEDGER.attribute_idle(idle_delta, epoch,
                                          source=self._who)
        # one actor-phase span per (executor, barrier): exclusive
        # processing time for the epoch this barrier ends, keyed by
        # the barrier's CURR epoch (the rw_barrier_latency key) and
        # parented to its inject span — the causal timeline the
        # straggler diagnosis reads
        _spans.EPOCH_TRACER.record(
            self.labels["executor"], "actor", epoch=epoch,
            start_s=time.time() - excl, dur_s=excl,
            actor=int(self.labels["actor"]),
            node=self.labels["node"],
            fragment=self.labels["fragment"],
            **getattr(self.inner, "span_args", {}))
        # per-LOGICAL-executor attribution inside fused blocks
        # (ops/fused.py): a fused run is ONE node in the chain, but
        # rw_actor_metrics keeps a row per absorbed stage — visible-row
        # counts come from the traced step itself (filter selectivity
        # stays observable after fusion)
        drain = getattr(self.inner, "drain_stage_metrics", None)
        if drain is None:
            return
        for ident, rows, chunks in drain():
            labels = dict(self.labels)
            labels["executor"] = f"{self.labels['executor']}::{ident}"
            _METRICS.executor_rows.inc(rows, **labels)
            if chunks:
                _METRICS.executor_chunks.inc(chunks, **labels)

    async def execute(self) -> AsyncIterator[Message]:
        it = self.inner.execute()
        try:
            while True:
                # loop time a foreign synchronous section (checkpoint
                # build, commit, compaction) holds while this pull is
                # parked in an await is not this executor's: the
                # stolen-time rule of utils/ledger.py
                t0 = _ledger.actor_clock()
                _AWAITS.enter(self._who, "poll_next")
                # ledger cell: scopes fired while the INNER executor
                # works (pack/h2d/dispatch/d2h inside this pull) are
                # charged to this node — a nested wrapped child swaps
                # its own cell in for its pulls, mirroring exactly how
                # exclusive busy time nests
                ctok = _ledger.LEDGER.push_cell(self._cell)
                # compile-cache ownership: anything traced while this
                # pull runs bills the pulling MV (first tracer pays,
                # later MVs record shared hits — stream/costs.py)
                mtok = _costs.push_mv(self.labels["fragment"])
                # park cell: exchange-credit parks fired while the
                # inner executor works charge THIS node (a nested
                # wrapped child swaps its own cell in for its pulls,
                # mirroring the ledger cells)
                ptok = _xchg.push_park_cell(self._park_cell)
                atok = _merge.push_align_cell(self._align_cell) \
                    if self.children else None
                try:
                    msg = await it.__anext__()
                except StopAsyncIteration:
                    break
                finally:
                    if atok is not None:
                        _merge.pop_align_cell(atok)
                    _xchg.pop_park_cell(ptok)
                    _costs.pop_mv(mtok)
                    _ledger.LEDGER.pop_cell(ctok)
                    _AWAITS.exit(self._who)
                    self.total_busy_s += _ledger.actor_clock() - t0
                if is_chunk(msg):
                    card = msg.cardinality()
                    if card == 0:
                        _METRICS.executor_empty_chunks.inc(
                            1, **self.labels)
                        if STRICT_EMPTY_CHUNKS:
                            raise AssertionError(
                                f"{self._who} emitted a zero-visible-"
                                "row chunk (the spine suppresses "
                                "empties end-to-end)")
                    _METRICS.executor_rows.inc(card, **self.labels)
                    _METRICS.executor_chunks.inc(1, **self.labels)
                elif is_barrier(msg):
                    # armable per-executor-class delay (sleep-spec
                    # failpoint): chaos/trace tests inject a laggard
                    # here; the slept time counts as THIS executor's
                    # busy time so attribution names the right actor
                    t1 = time.perf_counter()
                    fail_point("trace.slow."
                               + type(self.inner).__name__)
                    self.total_busy_s += time.perf_counter() - t1
                    self._flush_epoch(msg)
                yield msg
        finally:
            _AWAITS.exit(self._who)


def install_monitoring(root: Executor, fragment: str,
                       actor_id: int) -> Executor:
    """Wrap every node of an executor tree in a MonitoredExecutor.

    Walks the chain with the shared `executor_children` helper (the
    same walk explain_tree renders with), REPLACES each child
    reference with its wrapper (executors pull from whatever their
    attribute points at), and returns the wrapped root for the actor
    to drive.
    """
    counter = [0]

    def wrap(ex: Executor) -> MonitoredExecutor:
        node = counter[0]
        counter[0] += 1
        children: List[MonitoredExecutor] = []
        for attr, idx, child in executor_children(ex):
            w = wrap(child)
            if idx is None:
                setattr(ex, attr, w)
            else:
                getattr(ex, attr)[idx] = w
            children.append(w)
        # hot-key sketches key by executor identity; the fragment
        # binding is what lets rw_hot_keys name the owning MV
        _hotkeys.HOTKEYS.bind_fragment(ex.identity, fragment)
        return MonitoredExecutor(ex, fragment, actor_id, node,
                                 children)

    wrapped = wrap(root)
    # the wrapped chain IS the dataflow graph the bottleneck walker
    # descends — register it (actor teardown drops the entry)
    TOPOLOGY.register(actor_id, fragment, wrapped)
    return wrapped
