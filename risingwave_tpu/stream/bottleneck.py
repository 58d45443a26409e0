"""Backpressure-graph bottleneck walker: name the sustained culprit.

The utilization tricolor (stream/monitor.py) says how every (actor,
executor) spent each barrier; this module turns those per-node shares
into ONE name per barrier domain — the operator a capacity change
should target, which is exactly the input signal the ROADMAP-item-3
autoscaler consumes (the per-operator saturation evidence arxiv
1904.03800 argues scaling needs, not aggregate throughput).

The walk, per domain per barrier (Flink's backpressure diagnosis
adapted to a pull pipeline):

- Within an actor chain, pull edges carry implicit backpressure: a
  parent pulling a slow child shows near-zero exclusive busy while the
  child's subtree absorbs the interval. The walk therefore descends
  from the materialize root toward the child subtree holding the most
  busy time until the current node's own busy share dominates every
  input subtree — the first busy-dominated operator walking upstream.
- Across actor chains (MV-on-MV chain edges, remote exchange), the
  explicit signal takes over: a sender whose tricolor shows credit
  park time is the VICTIM of its consumer — chains fed by parked
  senders are implicated first, and the walk runs in the implicated
  chain (never blaming the parked upstream).

The streak machine only ticks on SLOW barriers (``SLOW_INTERVAL_S``):
a domain holding sub-half-second barriers is healthy — its hottest
operator is a fact, not a problem. On a slow barrier a candidate must
hold ``busy ≥ BUSY_DOMINANT`` to count (an evenly-spread slow domain
has no single bottleneck), and the same operator must repeat for
``SUSTAINED_STREAK`` contiguous slow barriers to be called
*sustained* — one hot barrier is an anecdote, a streak is a target.
Each row carries a one-line human diagnosis, cross-checked against the
phase ledger: a device_compute-dominated domain whose walk names an
operator that never dispatches kernels is flagged as a mismatch
(either the walk or the ledger is lying — say so instead of papering
over it).

Surfaces: the ``rw_bottlenecks`` system table,
``stream_bottleneck_streak{domain,operator}``, and ``ctl top``.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

# a node only qualifies as a bottleneck while it holds at least this
# share of its barrier interval busy
BUSY_DOMINANT = 0.35
# a sender counts as backpressured (its consumer implicated) above
# this credit-park share of the interval
EDGE_BP = 0.10
# contiguous SLOW barriers naming one operator before it is "sustained"
SUSTAINED_STREAK = 3
# the streak machine only ticks on barriers at least this long: a
# domain holding sub-half-second barriers is HEALTHY — its hottest
# operator is a fact, not a problem, and naming it would page the
# autoscaler on every fast pipeline. Fast and idle barriers leave the
# machine frozen (a drained domain keeps the verdict its last slow
# barrier earned; the `epoch` column dates it).
SLOW_INTERVAL_S = 0.5
# a single key above this guaranteed input share earns the diagnosis a
# skew:<key> clause (stream/hotkeys.py sketches; the share used is the
# sketch's LOWER bound, so an overcounted cold key cannot fire it)
SKEW_SHARE = 0.25


class _DomainState:
    __slots__ = ("op", "fragment", "actor", "node", "streak", "busy",
                 "downstream_bp", "diagnosis", "epoch", "barriers")

    def __init__(self) -> None:
        self.op: Optional[str] = None
        self.fragment = ""
        self.actor = 0
        self.node = 0
        self.streak = 0
        self.busy = 0.0
        self.downstream_bp = 0.0
        self.diagnosis = ""
        self.epoch = 0
        self.barriers = 0


def _dispatches_kernels(wrapper) -> bool:
    """Does this (monitored) operator launch device kernels? Checked
    against the live dispatch counters first, falling back to the
    executor carrying a sharded kernel object (mesh kernels label
    dispatches by kernel, not executor)."""
    from risingwave_tpu.utils.metrics import STREAMING
    ident = wrapper.labels["executor"]
    for labels, v in STREAMING.device_dispatch.series():
        ex = labels.get("executor", "")
        if v > 0 and (ex == ident or ex.startswith(ident)):
            return True
    inner = wrapper.inner
    if getattr(inner, "kernel", None) is not None:
        return True
    return "Fused" in type(inner).__name__


class BottleneckAnalyzer:
    """Process-global walker state (one streak machine per domain)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._domains: Dict[str, _DomainState] = {}
        # (worker, domain) → remote row in the rows() wire shape:
        # worker processes run their own walkers per barrier (the
        # coordinator hosts no monitored actors on a distributed
        # session); Cluster.drain_signals lands their snapshots here
        self._remote: Dict[tuple, tuple] = {}

    # -- per-barrier observation ---------------------------------------
    def observe(self, domain: str, epoch: int, interval_s: float,
                phase_seconds: Optional[dict] = None,
                fragments=None, actors=None) -> None:
        """One sealed barrier of ``domain``: walk its chains and
        advance/reset the streak machine. ``fragments`` restricts the
        topology to the domain's jobs (None = every registered chain
        — the single-loop pipelines), ``actors`` to the domain's actor
        ids (the worker-side walk, where the barrier frame carries the
        actor filter but not the job list); ``phase_seconds`` is the
        sealed ledger record's phase dict for the cross-check."""
        from risingwave_tpu.stream.monitor import TOPOLOGY, UTILIZATION

        roots = TOPOLOGY.roots(fragments, actors=actors)
        if not roots:
            return
        cand = None
        if interval_s >= SLOW_INTERVAL_S:
            cand = self._walk_domain(roots, UTILIZATION)
        with self._lock:
            st = self._domains.setdefault(domain, _DomainState())
            st.barriers += 1
            if interval_s < SLOW_INTERVAL_S:
                # fast/idle barrier: the domain is keeping up — freeze
                # the machine (don't advance, don't forget)
                return
            st.epoch = int(epoch)
            if cand is None or cand["busy"] < BUSY_DOMINANT:
                self._reset_locked(domain, st)
                return
            same = (st.op == cand["op"]
                    and st.actor == cand["actor"]
                    and st.node == cand["node"])
            if not same and st.op is not None:
                self._drop_gauge(domain, st.op)
            st.streak = st.streak + 1 if same else 1
            st.op = cand["op"]
            st.fragment = cand["fragment"]
            st.actor = cand["actor"]
            st.node = cand["node"]
            st.busy = cand["busy"]
            st.downstream_bp = cand["downstream_bp"]
            st.diagnosis = self._diagnose(st, cand, interval_s,
                                          phase_seconds)
            from risingwave_tpu.utils.metrics import STREAMING
            STREAMING.bottleneck_streak.set(st.streak, domain=domain,
                                            operator=st.op)

    def _reset_locked(self, domain: str, st: _DomainState) -> None:
        if st.op is not None:
            self._drop_gauge(domain, st.op)
        st.op = None
        st.streak = 0
        st.busy = 0.0
        st.downstream_bp = 0.0
        st.diagnosis = ""

    @staticmethod
    def _drop_gauge(domain: str, op: str) -> None:
        from risingwave_tpu.utils.metrics import STREAMING
        STREAMING.bottleneck_streak.remove(domain=domain, operator=op)

    # -- the walk ------------------------------------------------------
    def _walk_domain(self, roots, util) -> Optional[dict]:
        """Pick the domain's candidate: chains fed by backpressured
        senders are implicated first; the walk then descends the
        implicated (else every) chain from its materialize root."""
        by_fragment = {f: (a, r) for a, f, r in roots}
        # sender-side park share per chain root — the explicit
        # cross-chain backpressure evidence
        root_bp: Dict[str, float] = {}
        for a, f, r in roots:
            row = util.get(f, a, 0)
            root_bp[f] = row[4] if row is not None else 0.0
        max_bp = max(root_bp.values(), default=0.0)
        implicated = set(by_fragment)
        if max_bp >= EDGE_BP:
            # some sender parks: only chains that CONSUME a parked
            # upstream (identified by the chain hop below) — or, when
            # the hop graph is invisible, every chain that is not
            # itself parked — stay implicated
            consumers = {f for f, (a, r) in by_fragment.items()
                         if self._consumes_parked(r, root_bp)}
            if consumers:
                implicated = consumers
            else:
                implicated = {f for f, bp in root_bp.items()
                              if bp < EDGE_BP}
                if not implicated:
                    implicated = set(by_fragment)
        best = None
        for f in implicated:
            a, r = by_fragment[f]
            cand = self._walk_chain(f, a, r, util)
            if cand is not None and (best is None
                                     or cand["busy"] > best["busy"]):
                best = cand
        if best is not None:
            best["downstream_bp"] = round(max_bp, 4)
        return best

    @staticmethod
    def _consumes_parked(root, root_bp: Dict[str, float]) -> bool:
        """Does this chain read (Chain/Backfill hop) an upstream
        fragment whose sender is parked?"""
        hops: List[str] = []

        def scan(w) -> None:
            ident = w.labels["executor"]
            for tag in ("Chain(", "Backfill("):
                if tag in ident:
                    hops.append(
                        ident.split(tag, 1)[1].split(")", 1)[0])
            for c in w.children:
                scan(c)

        scan(root)
        return any(root_bp.get(h, 0.0) >= EDGE_BP for h in hops)

    def _walk_chain(self, fragment: str, actor_id: int, root,
                    util) -> Optional[dict]:
        """Descend from the materialize root toward the busiest input
        subtree until the current node's own busy share dominates every
        input — the first busy-dominated operator walking upstream
        along the pull graph's implicit backpressure."""
        def busy_of(w) -> float:
            row = util.get(fragment, actor_id, int(w.labels["node"]))
            return row[3] if row is not None else 0.0

        def subtree_busy(w) -> float:
            return busy_of(w) + sum(subtree_busy(c)
                                    for c in w.children)

        cur = root
        while cur.children:
            kid = max(cur.children, key=subtree_busy)
            if busy_of(cur) >= subtree_busy(kid):
                break
            cur = kid
        # the dominated stop may overshoot into a cheap leaf whose
        # subtree carried the time in a MIDDLE node — take the busiest
        # node on the walked spine instead of the stop point alone
        spine = []
        w = root
        while True:
            spine.append(w)
            if w is cur or not w.children:
                break
            w = max(w.children, key=subtree_busy)
        top = max(spine, key=busy_of)
        b = busy_of(top)
        if b <= 0.0:
            return None
        return {"op": top.labels["executor"], "fragment": fragment,
                "actor": actor_id, "node": int(top.labels["node"]),
                "busy": round(b, 4), "downstream_bp": 0.0,
                "wrapper": top}

    # -- diagnosis -----------------------------------------------------
    def _diagnose(self, st: _DomainState, cand: dict,
                  interval_s: float,
                  phase_seconds: Optional[dict]) -> str:
        parts = [f"{st.op} (actor {st.actor}) busy "
                 f"{st.busy:.0%} of the barrier"]
        if st.downstream_bp >= EDGE_BP:
            parts.append(f"upstream senders parked "
                         f"{st.downstream_bp:.0%} for credits")
        kernels = _dispatches_kernels(cand["wrapper"])
        if phase_seconds and interval_s > 0:
            # capped at 1: pipelined/overlapped epochs can attribute
            # more than one barrier's compute to one interval
            dc = min(1.0, phase_seconds.get("device_compute", 0.0)
                     / interval_s)
            if dc >= 0.25:
                if kernels:
                    parts.append(
                        f"consistent with the ledger: device_compute "
                        f"{dc:.0%} and the operator dispatches kernels")
                else:
                    parts.append(
                        f"LEDGER MISMATCH: device_compute {dc:.0%} "
                        f"but the walked operator dispatches no "
                        f"kernels")
        if st.streak >= SUSTAINED_STREAK:
            parts.append(f"sustained {st.streak} barriers — scale "
                         f"this operator first")
        # skew verdict (ISSUE 16): a hot key holding ≥ SKEW_SHARE of
        # the walked operator's input concentrates its work on ONE
        # shard — name the key so the autoscaler can veto a futile
        # parallelism scale-up instead of rescaling into the wall
        from risingwave_tpu.stream.hotkeys import HOTKEYS
        hot = HOTKEYS.hot_share(cand["wrapper"].labels["executor"],
                                min_share=SKEW_SHARE)
        if hot is not None:
            key, share = hot
            parts.append(f"skew:{key} ({share:.0%} of input keys — "
                         f"parallelism won't help)")
        return "; ".join(parts)

    # -- cross-process merge -------------------------------------------
    def ingest(self, rows, worker: str) -> int:
        """Merge one worker's walker snapshot (rows in the ``rows()``
        wire shape). Streak machines live where the chains live — each
        worker sustains its own candidates; ``rows()`` then reports
        the strongest candidate per domain across processes. Replaces
        the worker's previous snapshot wholesale (the rows are
        last-barrier state, not a log), dropping domains the worker no
        longer reports."""
        with self._lock:
            for key in [k for k in self._remote if k[0] == worker]:
                del self._remote[key]
            n = 0
            for r in rows:
                if len(r) != 11:
                    continue
                self._remote[(worker, str(r[0]))] = tuple(r)
                n += 1
        return n

    # -- reads ---------------------------------------------------------
    def rows(self) -> List[tuple]:
        """(domain, operator, fragment, actor_id, node, busy_ratio,
        downstream_backpressure, streak, sustained, epoch, diagnosis)
        ranked most-suspect first — the rw_bottlenecks payload. Local
        walker state and ingested worker snapshots merge per domain:
        the row with the longest streak (busy share breaking ties)
        wins — the strongest sustained evidence across processes."""
        with self._lock:
            cand: Dict[str, tuple] = {}
            for domain in sorted(self._domains):
                st = self._domains[domain]
                if st.op is None:
                    cand[domain] = (domain, None, "", 0, 0, 0.0, 0.0,
                                    0, 0, st.epoch,
                                    "no sustained bottleneck")
                    continue
                cand[domain] = (domain, st.op, st.fragment, st.actor,
                                st.node, st.busy, st.downstream_bp,
                                st.streak,
                                int(st.streak >= SUSTAINED_STREAK),
                                st.epoch, st.diagnosis)
            for (_w, domain), r in self._remote.items():
                cur = cand.get(domain)
                if cur is None or (r[7], r[5]) > (cur[7], cur[5]):
                    cand[domain] = tuple(r)
            out = list(cand.values())
        return sorted(out, key=lambda r: (-(r[7] * max(r[5], 1e-9)),
                                          r[0]))

    def summary(self) -> Dict[str, dict]:
        """Per-domain block for ctl top."""
        out: Dict[str, dict] = {}
        for (domain, op, fragment, actor, node, busy, bp, streak,
             sustained, epoch, diag) in self.rows():
            out[domain or "(global)"] = {
                "operator": op, "fragment": fragment, "actor": actor,
                "busy_ratio": busy, "downstream_backpressure": bp,
                "streak": streak, "sustained": bool(sustained),
                "diagnosis": diag}
        return out

    def clear(self) -> None:
        from risingwave_tpu.utils.metrics import STREAMING
        with self._lock:
            for domain, st in self._domains.items():
                if st.op is not None:
                    STREAMING.bottleneck_streak.remove(
                        domain=domain, operator=st.op)
            self._domains.clear()
            self._remote.clear()


# the process-global analyzer (coordinator-side: the walker reads the
# coordinator's topology/utilization views)
BOTTLENECKS = BottleneckAnalyzer()
