"""Per-MV event-time freshness: what an MV's consumer experiences.

The phase ledger (utils/ledger.py) explains where a barrier's wall
time went; nothing there measures what a *reader* of the MV sees —
how far the materialized result lags the data's own timestamps. This
module closes that gap with barrier-lineage freshness accounting
(the Hazelcast-Jet stance of arxiv 2103.10169 applied to staleness:
a lag you cannot attribute per barrier is a lag you cannot budget):

- **Ingest high-watermark.** Every source executor reports, per chunk,
  the max event-time it has ingested (the first TIMESTAMP column of
  its schema; sources without one fall back to arrival wall-clock, so
  freshness degrades to processing lag instead of vanishing).
- **Epoch frontiers.** When a source passes barrier X, it stamps
  ``frontier[source][X] = (hwm, wall)``: everything ingested before
  barrier X carries event-time ≤ hwm and entered by ``wall``.
- **Visibility.** When a MaterializeExecutor passes barrier X, all
  data ingested before X has been applied and commits with X's
  collection — the MV's visible event frontier IS the source frontier
  at X. Per-barrier lag samples follow:

      freshness_lag_s  = current ingest hwm − frontier hwm at X
      wall_lag_s       = now − frontier wall stamp at X

  (event-time seconds and wall seconds respectively; multi-source MVs
  take the worst source). This is lineage freshness: an EOWC gate's
  deliberate watermark holdback is not counted against the pipeline.

Cross-process merge: workers drain their RAW parts (hwms, frontiers,
visibility events) to the coordinator — ``drain_dict``/``ingest`` —
which resolves pending visibility events against merged frontiers, so
a source fragment on worker 0 and its materialize on worker 1 still
produce one coherent per-MV lag series.

Output surfaces: ``stream_mv_freshness_lag_seconds{mv}`` +
``stream_mv_freshness_wall_lag_seconds{mv}`` gauges, the
``rw_mv_freshness`` system table, per-barrier ``freshness_lag_s.<mv>``
rows in ``rw_metrics_history`` (folded in at ledger seal), and
``ctl top``.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

# bounded per-source epoch-frontier window: epochs outlive their
# usefulness once the MV passed them; the bound guards epochs that
# never materialize (dropped jobs, recovery rollbacks)
FRONTIER_WINDOW = 512
SAMPLE_WINDOW = 1024
PENDING_WINDOW = 256

class _MvState:
    __slots__ = ("sources", "domain", "samples", "last")

    def __init__(self, sources: Tuple[str, ...], domain: str):
        self.sources = sources
        self.domain = domain
        # (epoch, lag_s, wall_lag_s, ts) rings — percentile source
        self.samples: deque = deque(maxlen=SAMPLE_WINDOW)
        self.last: Optional[Tuple[int, float, float, float]] = None


class FreshnessTracker:
    """Process-global freshness registry (workers drain theirs to the
    coordinator, like the span tracer and the phase ledger)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # source → (hwm_us, wall_s at last ingest)
        self._hwm: Dict[str, Tuple[int, float]] = {}
        # source → OrderedDict(epoch → (hwm_us, wall_s))
        self._frontiers: Dict[str, "OrderedDict[int, Tuple[int, float]]"] = {}
        self._mvs: Dict[str, _MvState] = {}
        # visibility events whose frontiers haven't arrived yet
        # (cross-process: materialize on a different worker than the
        # source) — resolved during ingest()
        self._pending: deque = deque(maxlen=PENDING_WINDOW)
        # strict-mode evidence (tests/conftest.py): lag samples must be
        # finite and non-negative once the first frontier resolves
        self._violations: List[tuple] = []

    # -- source side ---------------------------------------------------
    def note_ingest(self, source: str, hwm_us: Optional[int],
                    wall_s: Optional[float] = None) -> None:
        """One chunk ingested: advance the source's event-time high
        watermark (None = no event-time column: arrival wall-clock
        stands in, microseconds)."""
        now = time.time() if wall_s is None else wall_s
        if hwm_us is None:
            hwm_us = int(now * 1e6)
        with self._lock:
            prev = self._hwm.get(source)
            if prev is None or hwm_us > prev[0]:
                self._hwm[source] = (int(hwm_us), now)
            else:                       # hwm monotone; wall still moves
                self._hwm[source] = (prev[0], now)

    def note_source_barrier(self, source: str, epoch: int) -> None:
        """The source passed barrier ``epoch``: everything it ingested
        so far precedes that barrier. Parallel splits of one source
        each call this — the frontier keeps the MINIMUM hwm (the
        conservative cross-split frontier)."""
        now = time.time()
        with self._lock:
            hwm = self._hwm.get(source)
            if hwm is None:
                # nothing ingested yet: an EMPTY frontier, marked with
                # hwm=None — NOT an arrival-clock stand-in, which would
                # compare a wall-clock microsecond value against later
                # historical event times and mint a huge negative lag
                hwm = (None, now)
            fr = self._frontiers.setdefault(source, OrderedDict())
            cur = fr.get(epoch)
            if cur is None or (hwm[0] is not None
                               and (cur[0] is None or hwm[0] < cur[0])):
                # the frontier's wall stamp is when its NEWEST data
                # was ingested (the hwm's stamp), so wall_lag measures
                # ingest→visible latency, not barrier bookkeeping time.
                # A real hwm replaces an empty sibling-split marker,
                # never the other way around (approximation: one empty
                # split must not zero a populated source's frontier).
                fr[epoch] = hwm
            while len(fr) > FRONTIER_WINDOW:
                fr.popitem(last=False)

    # -- MV side -------------------------------------------------------
    def register_mv(self, mv: str, sources, domain: str = "") -> None:
        """Associate one materialized job with the sources whose
        frontiers bound its visible data (called at deploy; re-register
        on reschedule overwrites)."""
        with self._lock:
            self._mvs[mv] = _MvState(tuple(sources), domain)

    def unregister_mv(self, mv: str) -> None:
        with self._lock:
            self._mvs.pop(mv, None)
        from risingwave_tpu.utils.metrics import STREAMING
        STREAMING.mv_freshness_lag.remove(mv=mv)
        STREAMING.mv_freshness_wall_lag.remove(mv=mv)

    def set_domain(self, mv: str, domain: str) -> None:
        with self._lock:
            st = self._mvs.get(mv)
            if st is not None:
                st.domain = domain

    def note_visible(self, mv: str, epoch: int,
                     wall_s: Optional[float] = None) -> None:
        """The MV's materialize executor passed barrier ``epoch``:
        every chunk ingested before that barrier is applied (and
        commits with the barrier's collection)."""
        now = time.time() if wall_s is None else wall_s
        with self._lock:
            if not self._resolve_locked(mv, epoch, now):
                self._pending.append((mv, int(epoch), now))

    def _resolve_locked(self, mv: str, epoch: int, now: float) -> bool:
        """Compute one lag sample if every source frontier for the
        epoch is known. Returns False when a frontier is missing (the
        cross-process case — ingest() retries it)."""
        st = self._mvs.get(mv)
        if st is None:
            # not registered HERE: park it — on a worker process the
            # registration lives on the coordinator, and dropping the
            # event would make the whole drain/merge chain a no-op
            # (bounded ring; never-registered test pipelines just age
            # out of it)
            return False
        if st.last is not None and st.last[0] == epoch:
            # N distributed slices of one MV each pass the barrier:
            # one sample per (mv, epoch), not one per slice
            return True
        lag = wall_lag = 0.0
        for src in st.sources or ():
            fr = self._frontiers.get(src, {}).get(epoch)
            if fr is None:
                return False
            f_hwm, f_wall = fr
            if f_hwm is not None:
                cur = self._hwm.get(src, (f_hwm, f_wall))
                lag = max(lag, (cur[0] - f_hwm) / 1e6)
            # empty frontier (nothing ingested before the barrier):
            # the MV is behind by no visible event-time span — only
            # the wall clock moves
            wall_lag = max(wall_lag, now - f_wall)
        if not (lag >= 0.0 and wall_lag >= 0.0
                and lag == lag and wall_lag == wall_lag
                and lag != float("inf") and wall_lag != float("inf")):
            self._violations.append((mv, epoch, lag, wall_lag))
            lag, wall_lag = max(lag, 0.0), max(wall_lag, 0.0)
        st.samples.append((int(epoch), lag, wall_lag, now))
        st.last = (int(epoch), lag, wall_lag, now)
        from risingwave_tpu.utils.metrics import STREAMING
        STREAMING.mv_freshness_lag.set(lag, mv=mv)
        STREAMING.mv_freshness_wall_lag.set(wall_lag, mv=mv)
        return True

    # -- reads ---------------------------------------------------------
    def history_extra(self, epoch: int, domain: str) -> Dict[str, float]:
        """Per-barrier rw_metrics_history payload: the freshness
        samples of the sealed domain's MVs at this epoch (folded into
        the ledger seal's ``extra`` dict)."""
        out: Dict[str, float] = {}
        with self._lock:
            for mv, st in self._mvs.items():
                if st.domain != domain or st.last is None:
                    continue
                e, lag, wall_lag, _ts = st.last
                if e == epoch:
                    out[f"freshness_lag_s.{mv}"] = round(lag, 6)
                    out[f"freshness_wall_lag_s.{mv}"] = round(wall_lag, 6)
        return out

    def percentile(self, mv: str, q: float,
                   wall: bool = False) -> Optional[float]:
        from risingwave_tpu.utils.metrics import exact_quantile
        with self._lock:
            st = self._mvs.get(mv)
            if st is None or not st.samples:
                return None
            idx = 2 if wall else 1
            return exact_quantile([s[idx] for s in st.samples], q)

    def rows(self) -> List[tuple]:
        """(mv, domain, samples, epoch, lag_s, wall_lag_s, lag_p50_s,
        lag_p99_s, wall_lag_p99_s) — the rw_mv_freshness payload."""
        from risingwave_tpu.utils.metrics import exact_quantile
        out = []
        with self._lock:
            for mv in sorted(self._mvs):
                st = self._mvs[mv]
                if st.last is None:
                    out.append((mv, st.domain, 0, 0, None, None,
                                None, None, None))
                    continue
                e, lag, wall_lag, _ts = st.last
                lags = [s[1] for s in st.samples]
                walls = [s[2] for s in st.samples]
                out.append((mv, st.domain, len(st.samples), e,
                            round(lag, 6), round(wall_lag, 6),
                            round(exact_quantile(lags, 0.5), 6),
                            round(exact_quantile(lags, 0.99), 6),
                            round(exact_quantile(walls, 0.99), 6)))
        return out

    def summary(self) -> Dict[str, dict]:
        """Per-MV freshness block (ctl top)."""
        out: Dict[str, dict] = {}
        for (mv, domain, n, _e, lag, wall_lag, p50, p99,
             wall_p99) in self.rows():
            if not n:
                continue
            out[mv] = {"domain": domain, "samples": n,
                       "lag_s": lag, "wall_lag_s": wall_lag,
                       "lag_p50_s": p50, "lag_p99_s": p99,
                       "wall_lag_p99_s": wall_p99}
        return out

    # -- strict-mode gate (tests/conftest.py) --------------------------
    def gate_violations(self) -> List[tuple]:
        with self._lock:
            return list(self._violations)

    # -- cross-process merge -------------------------------------------
    def drain_dict(self) -> dict:
        """Pop this process's raw parts for the coordinator (samples
        stay local — the coordinator recomputes them from the parts, so
        repeated drains never double-count)."""
        with self._lock:
            out = {
                "hwm": {s: [h, w] for s, (h, w) in self._hwm.items()},
                "frontiers": {
                    s: {str(e): [h, w] for e, (h, w) in fr.items()}
                    for s, fr in self._frontiers.items()},
                "visible": [[mv, e, w] for mv, e, w in self._pending],
                "mvs": {mv: {"sources": list(st.sources),
                             "domain": st.domain}
                        for mv, st in self._mvs.items()},
            }
            self._pending.clear()
        return out

    def ingest(self, d: dict, default_now: Optional[float] = None
               ) -> int:
        """Merge one worker's drained parts; resolve any visibility
        events (theirs and ours) the merged frontiers now cover."""
        n = 0
        now = time.time() if default_now is None else default_now
        with self._lock:
            for mv, spec in (d.get("mvs") or {}).items():
                if mv not in self._mvs:
                    self._mvs[mv] = _MvState(
                        tuple(spec.get("sources") or ()),
                        spec.get("domain", ""))
            for s, (h, w) in (d.get("hwm") or {}).items():
                cur = self._hwm.get(s)
                if cur is None or int(h) > cur[0]:
                    self._hwm[s] = (int(h), float(w))
            for s, fr in (d.get("frontiers") or {}).items():
                mine = self._frontiers.setdefault(s, OrderedDict())
                for e, (h, w) in fr.items():
                    e = int(e)
                    cur = mine.get(e)
                    # same min-merge as note_source_barrier: reals
                    # keep the minimum, a real replaces an empty
                    # (None) marker, an empty never replaces a real
                    if cur is None or (h is not None
                                       and (cur[0] is None
                                            or int(h) < cur[0])):
                        mine[e] = (None if h is None else int(h),
                                   float(w))
                while len(mine) > FRONTIER_WINDOW:
                    mine.popitem(last=False)
            pend = list(self._pending)
            self._pending.clear()
            for mv, e, w in (d.get("visible") or ()):
                pend.append((mv, int(e), float(w)))
            for mv, e, w in pend:
                if self._resolve_locked(mv, e, w if w else now):
                    n += 1
                else:
                    self._pending.append((mv, e, w))
        return n

    def clear(self) -> None:
        with self._lock:
            self._hwm.clear()
            self._frontiers.clear()
            self._mvs.clear()
            self._pending.clear()
            self._violations.clear()


# the process-global tracker (workers drain to the coordinator)
FRESHNESS = FreshnessTracker()


def event_time_index(schema) -> Optional[int]:
    """First TIMESTAMP/TIMESTAMPTZ column of a source schema — the
    event-time heuristic sources derive their ingest hwm from (None:
    arrival-clock fallback)."""
    from risingwave_tpu.common.types import DataType
    for i, f in enumerate(schema):
        if f.data_type in (DataType.TIMESTAMP, DataType.TIMESTAMPTZ):
            return i
    return None


def chunk_event_hwm(chunk, col_idx: Optional[int]) -> Optional[int]:
    """Max event-time (microseconds) over a chunk's visible rows; None
    when the schema has no event-time column or nothing is visible."""
    if col_idx is None:
        return None
    import numpy as np
    vis = np.asarray(chunk.visibility)
    if not vis.any():
        return None
    vals = np.asarray(chunk.columns[col_idx].values)
    validity = chunk.columns[col_idx].validity
    if validity is not None:
        vis = vis & np.asarray(validity)
        if not vis.any():
            return None
    return int(vals[vis].max())
