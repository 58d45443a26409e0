"""Per-MV resource ledger (ISSUE 16): where device-seconds, transfer
bytes, state bytes and compile traces actually go, per MV.

The phase ledger (utils/ledger.py) conserves a barrier interval's wall
clock across phases; this module splits the device-facing share of
those books BY OWNER. The split costs no new timers: every
MonitoredExecutor already scopes an AttributionCell around its pulls
(exclusive nesting — a wrapped child swaps its own cell in), and the
wrapper's ``fragment`` label IS the MV/job name. At barrier flush the
cell's device_compute seconds and h2d/d2h bytes are recorded here
against that MV before the cell folds into the phase ledger — so
Σ per-MV device-seconds ≤ the domain's device_compute by construction
(the ledger gets the same cells plus everything uncelled), which the
tier-1 attribution gate asserts per sealed epoch.

Ownership rules for shared compile caches: the module-level
``_STEP_CACHE``/``_PROG_CACHE`` dicts (parallel/join.py, parallel/agg.py)
are wrapped in :class:`CompileCache`, which bills the MV *currently
pulling* (a ContextVar the monitor sets around pulls): the first MV to
trace a program pays the miss; later MVs that reuse the entry record a
hit — a ``shared`` hit when somebody else paid the trace. That is the
marginal-compile-cost question ROADMAP item 5 asks.

Recovery/rescale charge-back is read, not hooked: ``rw_autoscaler``
rows carry their MV and duration; ``rw_recovery`` durations split
evenly across registered MVs (a documented approximation — recovery
replays every job).
"""

from __future__ import annotations

import threading
from collections import deque
from contextvars import ContextVar
from typing import Dict, List, Optional, Tuple

# the MV whose executor chain is currently pulling (set by
# MonitoredExecutor around inner pulls — asyncio-context scoped, so
# interleaved actors never cross-bill a compile)
_MV: ContextVar[Optional[str]] = ContextVar("rw_costs_mv",
                                            default=None)


def push_mv(mv: str):
    return _MV.set(mv)


def pop_mv(token) -> None:
    _MV.reset(token)


def current_mv() -> Optional[str]:
    return _MV.get()


class CompileCache(dict):
    """A module compile cache that bills hits/misses to the pulling MV.

    Drop-in for the plain dicts: ``get`` notes a hit when it finds a
    compiled step; ``__setitem__`` notes the miss (a fresh trace was
    paid). The key records which MV first paid each entry, so a later
    hit by a different MV counts as *shared* — compiled-program reuse
    across tenants, the serving-density win."""

    def __init__(self, kind: str):
        super().__init__()
        self.kind = kind

    def get(self, key, default=None):
        step = super().get(key, default)
        if step is not None:
            COSTS.note_compile(self.kind, key, hit=True)
        return step

    def __setitem__(self, key, step) -> None:
        COSTS.note_compile(self.kind, key, hit=False)
        super().__setitem__(key, step)


class MVCosts:
    """Process-global per-MV resource totals + per-epoch pending cells."""

    # retained sealed-epoch attribution rows (the gate's evidence)
    SEALED_WINDOW = 512

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # mv -> {device_s, h2d_bytes, d2h_bytes, compile_hits,
        #         compile_misses, shared_hits, domain}
        self._mvs: Dict[str, dict] = {}
        # epoch -> mv -> [device_s, h2d_bytes, d2h_bytes] (cells
        # committed at barrier flush, folded into totals at seal)
        self._pending: Dict[int, Dict[str, List[float]]] = {}
        # (kind, key) -> first MV that paid the trace
        self._cache_owner: Dict[tuple, str] = {}
        # sealed (epoch, domain, sum_mv_device_s, domain_device_s)
        self._sealed = deque(maxlen=self.SEALED_WINDOW)

    def _mv(self, mv: str) -> dict:
        d = self._mvs.get(mv)
        if d is None:
            d = {"device_s": 0.0, "h2d_bytes": 0, "d2h_bytes": 0,
                 "compile_hits": 0, "compile_misses": 0,
                 "shared_hits": 0, "domain": ""}
            self._mvs[mv] = d
        return d

    # -- hot-path hooks -------------------------------------------------
    def observe_cell(self, mv: str, epoch: int, device_s: float,
                     h2d_bytes: int, d2h_bytes: int) -> None:
        """One executor cell's device share at barrier flush (called
        by MonitoredExecutor BEFORE the cell commits to the phase
        ledger — same numbers, split by owner)."""
        if device_s <= 0 and not h2d_bytes and not d2h_bytes:
            return
        with self._lock:
            acc = self._pending.setdefault(epoch, {}) \
                .setdefault(mv, [0.0, 0, 0])
            acc[0] += device_s
            acc[1] += h2d_bytes
            acc[2] += d2h_bytes
            while len(self._pending) > 256:
                # discarded epochs never seal — drop their cells
                # rather than hold them forever
                self._pending.pop(next(iter(self._pending)))

    def note_compile(self, kind: str, key, hit: bool) -> None:
        mv = _MV.get() or ""
        with self._lock:
            d = self._mv(mv)
            if hit:
                d["compile_hits"] += 1
                owner = self._cache_owner.get((kind, key))
                if owner is not None and owner != mv:
                    d["shared_hits"] += 1
            else:
                d["compile_misses"] += 1
                self._cache_owner.setdefault((kind, key), mv)

    # -- seal-time rollup (phase-ledger _publish) ------------------------
    def history_extra(self, rec) -> Dict[str, float]:
        """Fold the record's epoch's pending cells into the per-MV
        totals, publish the Prometheus families, retain the gate row,
        and return ``mv_device_s.<mv>`` entries for the
        rw_metrics_history row the seal is about to write."""
        with self._lock:
            cells = self._pending.pop(rec.epoch, None) or {}
            extra: Dict[str, float] = {}
            total_dev = 0.0
            for mv, (dev, h2d, d2h) in cells.items():
                d = self._mv(mv)
                d["device_s"] += dev
                d["h2d_bytes"] += h2d
                d["d2h_bytes"] += d2h
                if rec.domain:
                    d["domain"] = rec.domain
                total_dev += dev
                extra[f"mv_device_s.{mv}"] = round(dev, 6)
            if not rec.distributed:
                # distributed epochs merge worker books later — the
                # coordinator's own seal undercounts by design. A
                # cell-less epoch still lands (0.0 attributed): its
                # device time belongs in the coverage denominator
                self._sealed.append(
                    (rec.epoch, rec.domain, total_dev,
                     rec.seconds.get("device_compute", 0.0)))
            if not cells:
                return {}
        from risingwave_tpu.utils.metrics import STREAMING
        for mv, (dev, h2d, d2h) in cells.items():
            STREAMING.mv_device_seconds.inc(dev, mv=mv)
            if h2d:
                STREAMING.mv_transfer_bytes.inc(h2d, mv=mv,
                                                direction="h2d")
            if d2h:
                STREAMING.mv_transfer_bytes.inc(d2h, mv=mv,
                                                direction="d2h")
        return extra

    def publish_state_bytes(self) -> None:
        """Refresh the stream_mv_state_bytes gauge from the topology
        books (checkpoint cadence — state only moves at checkpoints)."""
        from risingwave_tpu.state.topology import TOPOLOGY
        from risingwave_tpu.utils.metrics import STREAMING
        for mv, nbytes in TOPOLOGY.bytes_by_mv().items():
            if mv:
                STREAMING.mv_state_bytes.set(float(nbytes), mv=mv)

    # -- recovery / rescale charge-back ---------------------------------
    def _chargeback(self) -> Dict[str, List[float]]:
        """mv -> [rescale_s, recovery_s] read from the autoscaler and
        supervisor event logs (not hooked: the logs are already
        per-event, re-derived on read so the books can't drift)."""
        out: Dict[str, List[float]] = {}
        try:
            from risingwave_tpu.meta.autoscaler import autoscaler_rows
            for row in autoscaler_rows():
                mv, dur = str(row[1]), float(row[10] or 0.0)
                out.setdefault(mv, [0.0, 0.0])[0] += dur
        except Exception:               # noqa: BLE001 — log optional
            pass
        try:
            from risingwave_tpu.meta.supervisor import recovery_rows
            rec_total = sum(float(r[5] or 0.0) for r in recovery_rows())
        except Exception:               # noqa: BLE001
            rec_total = 0.0
        if rec_total > 0:
            with self._lock:
                mvs = [m for m in self._mvs if m]
            # recovery replays every registered job: split evenly (a
            # documented approximation — per-job replay time is not
            # individually measured)
            for mv in mvs:
                out.setdefault(mv, [0.0, 0.0])[1] += \
                    rec_total / len(mvs)
        return out

    # -- read side ------------------------------------------------------
    def rows(self) -> List[tuple]:
        """rw_mv_costs payload: (mv, domain, device_seconds,
        h2d_bytes, d2h_bytes, state_bytes, compile_hits,
        compile_misses, shared_compile_hits, rescale_s, recovery_s)."""
        from risingwave_tpu.state.topology import TOPOLOGY
        state = TOPOLOGY.bytes_by_mv()
        charge = self._chargeback()
        with self._lock:
            items = [(mv, dict(d)) for mv, d in self._mvs.items()]
        rows = []
        for mv, d in sorted(items):
            rs, cs = charge.get(mv, (0.0, 0.0))
            rows.append((mv, d["domain"], round(d["device_s"], 6),
                         int(d["h2d_bytes"]), int(d["d2h_bytes"]),
                         int(state.get(mv, 0)),
                         int(d["compile_hits"]),
                         int(d["compile_misses"]),
                         int(d["shared_hits"]),
                         round(rs, 4), round(cs, 4)))
        return rows

    def summary(self) -> Dict[str, dict]:
        """mv -> totals dict."""
        from risingwave_tpu.state.topology import TOPOLOGY
        state = TOPOLOGY.bytes_by_mv()
        with self._lock:
            items = [(mv, dict(d)) for mv, d in self._mvs.items()]
        return {mv: {**d, "state_bytes": int(state.get(mv, 0))}
                for mv, d in items}

    def coverage(self) -> Tuple[float, float]:
        """(attributed_device_s, ledgered_device_s) summed over the
        sealed-epoch window — BOTH sides windowed identically
        (``SEALED_WINDOW`` epochs), so the ratio is the share of the
        ledgered device time an MV owns. Comparing the cumulative per-MV
        totals against the ledger's bounded record deque instead
        would inflate past 1.0 as records age out."""
        with self._lock:
            att = sum(r[2] for r in self._sealed)
            led = sum(r[3] for r in self._sealed)
        return att, led

    # -- attribution-conservation gate ----------------------------------
    def gate_violations(self) -> List[tuple]:
        """(epoch, domain, sum_mv_device_s, domain_device_s) for every
        sealed epoch where the per-MV split exceeds the domain's
        ledgered device_compute + ε — an owner split can redistribute
        the books but never mint device time."""
        out = []
        with self._lock:
            for epoch, domain, mv_sum, dom_dev in self._sealed:
                eps = 1e-6 + 0.01 * dom_dev
                if mv_sum > dom_dev + eps:
                    out.append((epoch, domain, mv_sum, dom_dev))
        return out

    # -- series lifecycle (DROP MV / failed CREATE) ----------------------
    def unregister_mv(self, mv: str) -> None:
        from risingwave_tpu.utils.metrics import STREAMING
        with self._lock:
            self._mvs.pop(mv, None)
            for epoch in list(self._pending):
                self._pending[epoch].pop(mv, None)
        STREAMING.mv_device_seconds.remove(mv=mv)
        STREAMING.mv_state_bytes.remove(mv=mv)
        for direction in ("h2d", "d2h"):
            STREAMING.mv_transfer_bytes.remove(mv=mv,
                                               direction=direction)

    # -- cross-process merge (cluster `signals` drain) -------------------
    def drain_dict(self) -> dict:
        """Pop this worker's totals and pending cells (a drain:
        deltas ship once; the coordinator owns the merged books)."""
        with self._lock:
            mvs = {mv: dict(d) for mv, d in self._mvs.items()}
            pending = {e: {mv: list(acc) for mv, acc in cells.items()}
                       for e, cells in self._pending.items()}
            self._mvs.clear()
            self._pending.clear()
        return {"mvs": mvs, "pending": pending}

    def ingest(self, parts: dict, worker: str = "") -> int:
        """Fold one worker's drained books into this process's totals
        (pending worker cells fold directly — their epochs sealed on
        the coordinator already, under the distributed exemption)."""
        if not parts:
            return 0
        n = 0
        from risingwave_tpu.utils.metrics import STREAMING
        deltas: Dict[str, List[float]] = {}
        with self._lock:
            for mv, d in (parts.get("mvs") or {}).items():
                t = self._mv(mv)
                for k in ("device_s", "h2d_bytes", "d2h_bytes",
                          "compile_hits", "compile_misses",
                          "shared_hits"):
                    t[k] += d.get(k, 0)
                if d.get("domain"):
                    t["domain"] = d["domain"]
                acc = deltas.setdefault(mv, [0.0, 0, 0])
                acc[0] += d.get("device_s", 0.0)
                acc[1] += d.get("h2d_bytes", 0)
                acc[2] += d.get("d2h_bytes", 0)
                n += 1
            for _e, cells in (parts.get("pending") or {}).items():
                for mv, (dev, h2d, d2h) in cells.items():
                    t = self._mv(mv)
                    t["device_s"] += dev
                    t["h2d_bytes"] += h2d
                    t["d2h_bytes"] += d2h
                    acc = deltas.setdefault(mv, [0.0, 0, 0])
                    acc[0] += dev
                    acc[1] += h2d
                    acc[2] += d2h
                    n += 1
        for mv, (dev, h2d, d2h) in deltas.items():
            if dev:
                STREAMING.mv_device_seconds.inc(dev, mv=mv)
            if h2d:
                STREAMING.mv_transfer_bytes.inc(h2d, mv=mv,
                                                direction="h2d")
            if d2h:
                STREAMING.mv_transfer_bytes.inc(d2h, mv=mv,
                                                direction="d2h")
        return n

    def clear(self) -> None:
        with self._lock:
            self._mvs.clear()
            self._pending.clear()
            self._cache_owner.clear()
            self._sealed.clear()


COSTS = MVCosts()


def purge_mv_series(mv: str) -> None:
    """Central series-lifecycle teardown for one MV: DROP MATERIALIZED
    VIEW and failed CREATE both route here so no `{mv=...}` labeled
    series — freshness, costs, hot keys, topology — outlives the job
    in the exposition."""
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.stream.freshness import FRESHNESS
    from risingwave_tpu.stream.hotkeys import HOTKEYS
    FRESHNESS.unregister_mv(mv)
    COSTS.unregister_mv(mv)
    HOTKEYS.unregister_fragment(mv)
    TOPOLOGY.unbind_mv(mv)
