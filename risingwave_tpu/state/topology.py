"""Per-(table, vnode) state topology, maintained at flush (ISSUE 16).

The incremental-rescale planner (ROADMAP item 3) needs to know which
vnodes' state would move and how big they are BEFORE committing to a
handoff — and the serving-cost ledger (stream/costs.py) needs state
bytes attributed to the MV that owns them. Both reads come from here.

Maintenance invariant: the per-key size map is updated incrementally at
``StateTable.commit`` — the one write-through point every operator's
flush funnels into — and NEVER by scanning the store. Per-vnode
breakdowns (hot-vnode imbalance, ``ctl memory``) derive from the map
at EXPLICIT read time only; the per-MV byte rollup — which runs at
every checkpoint (``costs.publish_state_bytes``) — reads the O(#tables)
delta totals and never walks the map. The hot path pays only the map
upkeep:

- the append-fast case (uniform fixed-width keys, fixed-width rows, no
  deletes — the materialize/join staged-batch shape) is one C-speed
  ``dict.update`` plus delta arithmetic, mirroring the store's own
  ``ingest_keyed`` fast form;
- mixed batches (deletes, varchar rows) fall back to a per-entry loop.

Two independently-maintained books cross-check each other: the
authoritative per-key map vs. delta-arithmetic per-table totals. The
tier-1 gate (``gate_violations``) recounts the map and fails on drift —
Σ per-table topology bytes must equal the accounted resident bytes.
"""

from __future__ import annotations

import threading
from itertools import repeat
from typing import Dict, Iterable, List, Optional, Tuple

# value-size model (EstimateSize analog): fixed-width physical scalars
# are 8B + 1B tag; host-typed values charge their length. The model is
# stable across insert/overwrite of the same schema, which is what
# makes the append-fast delta arithmetic exact.
_FIXED_NBYTES = 9


def row_nbytes(row: tuple) -> int:
    """Estimated bytes of one physical row tuple."""
    n = 0
    for v in row:
        if isinstance(v, (str, bytes)):
            n += len(v) + 1
        else:
            n += _FIXED_NBYTES
    return n


def fixed_row_nbytes(schema) -> Optional[int]:
    """Schema-constant row size, or None when any field is host-typed
    (varchar/bytea rows are sized per value)."""
    for f in schema:
        if not f.data_type.is_device:
            return None
    return _FIXED_NBYTES * len(schema)


class StateTopology:
    """Process-global per-(table, vnode) row/byte accounting."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # table_id -> key -> accounted bytes (key + value estimate):
        # the authoritative book, maintained incrementally at flush
        self._sizes: Dict[int, Dict[bytes, int]] = {}
        # table_id -> [rows, bytes]: delta-arithmetic totals kept NEXT
        # TO the map — the gate recounts the map against these
        self._totals: Dict[int, List[int]] = {}
        # table_id -> owning MV/fragment (frontend binds at deploy)
        self._mv_of: Dict[int, str] = {}
        # table_id -> append-fast unit, or -1 once any mixed-shape
        # flush touched the table. The fast path's bulk overwrite is
        # only delta-exact when every resident entry carries the same
        # unit it is about to write (old == new → replacement is a
        # totals no-op); this book proves that precondition in O(1)
        self._unit: Dict[int, int] = {}
        # table_id -> the watermark the table was last cleaned to
        # (tables a watermark cleans only; rw_watermarks)
        self._cleaned_to: Dict[int, int] = {}
        # worker -> drained remote rows (coordinator merge; a state
        # table lives in exactly one process, so rows union cleanly)
        self._remote: Dict[str, List[tuple]] = {}
        # armed by the tier-1 conftest gate: checkpoint_verify() then
        # recounts at every checkpoint instead of only at teardown
        self._verify_each_checkpoint = False
        self._violations: List[tuple] = []

    # -- maintenance (StateTable.commit hot path) -----------------------
    def record(self, table_id: int, keys: List[bytes], vals: List,
               fixed_nbytes: Optional[int] = None) -> None:
        if not keys:
            return
        with self._lock:
            if table_id not in self._mv_of:
                # lazy ownership bind: commit runs inside the owning
                # MV's pull (the costs ContextVar the monitor pushes),
                # so the first attributed flush names the table's MV —
                # no table-registry plumbing needed
                from risingwave_tpu.stream.costs import current_mv
                mv = current_mv()
                if mv:
                    self._mv_of[table_id] = mv
            m = self._sizes.setdefault(table_id, {})
            tot = self._totals.setdefault(table_id, [0, 0])
            if fixed_nbytes is not None:
                try:
                    vals.index(None)       # C-speed delete probe
                except ValueError:
                    unit = len(keys[0]) + fixed_nbytes
                    u = self._unit.get(table_id)
                    # uniform-key check is one C-speed pass (NULL pk
                    # slots take the short null-tag encoding). The
                    # unit check guards overwrites: the bulk merge
                    # replaces existing entries blind, which is only
                    # a totals no-op when they already hold `unit` —
                    # i.e. every prior flush was fast-path at the
                    # same unit (a schema-width change, e.g. column
                    # pruning re-planning the same table id, must
                    # take the per-entry loop below)
                    if (u == unit or (u is None and not m)) and \
                            sum(map(len, keys)) == \
                            len(keys[0]) * len(keys):
                        # append-fast form: uniform keys + constant
                        # row size → one bulk dict merge, exact deltas
                        self._unit[table_id] = unit
                        before = len(m)
                        m.update(zip(keys, repeat(unit)))
                        fresh = len(m) - before
                        tot[0] += fresh
                        tot[1] += fresh * unit
                        return
            self._unit[table_id] = -1      # mixed shapes from here on
            for key, val in zip(keys, vals):
                old = m.pop(key, None)
                if old is not None:
                    tot[0] -= 1
                    tot[1] -= old
                if val is None:            # delete
                    continue
                nb = len(key) + (fixed_nbytes if fixed_nbytes
                                 is not None else row_nbytes(val))
                m[key] = nb
                tot[0] += 1
                tot[1] += nb

    # -- ownership ------------------------------------------------------
    def bind(self, table_id: int, mv: str) -> None:
        with self._lock:
            self._mv_of[table_id] = mv

    def unbind_mv(self, mv: str) -> None:
        """Drop a dropped MV's tables from the books (series lifecycle:
        no `{mv=...}` topology rows may outlive the MV)."""
        with self._lock:
            dead = [t for t, m in self._mv_of.items() if m == mv]
            for t in dead:
                self._mv_of.pop(t, None)
                self._sizes.pop(t, None)
                self._totals.pop(t, None)
                self._unit.pop(t, None)
                self._cleaned_to.pop(t, None)
            self._remote = {
                w: [r for r in rows if r[1] != mv]
                for w, rows in self._remote.items()}

    def mv_of(self, table_id: int) -> str:
        with self._lock:
            return self._mv_of.get(table_id, "")

    # -- watermark cleaning ---------------------------------------------
    def note_cleaned(self, table_id: int, watermark: int) -> None:
        with self._lock:
            self._cleaned_to[table_id] = int(watermark)

    def cleaned_rows_of(self, table_id: int) -> Optional[int]:
        """The table's rows by the totals, where a watermark cleans
        it; None for a table no watermark has cleaned."""
        with self._lock:
            if table_id not in self._cleaned_to:
                return None
            return self._totals.get(table_id, [0, 0])[0]

    def watermark_rows(self) -> List[tuple]:
        """rw_watermarks payload: (table_id, mv, watermark, rows) of
        every state table a watermark has cleaned: the value it was
        last cleaned to and the rows it keeps, O(#tables)."""
        with self._lock:
            return sorted(
                (t, self._mv_of.get(t, ""), wm,
                 self._totals.get(t, [0, 0])[0])
                for t, wm in self._cleaned_to.items())

    # -- read side (system tables / ctl — off the hot path) -------------
    @staticmethod
    def _vnode_of(key: bytes) -> int:
        return (key[0] << 8) | key[1] if len(key) >= 2 else 0

    def _local_rows(self) -> List[tuple]:
        with self._lock:
            items = [(t, dict(m)) for t, m in self._sizes.items()]
            mv_of = dict(self._mv_of)
        rows: List[tuple] = []
        for t, m in items:
            per_vnode: Dict[int, List[int]] = {}
            for key, nb in m.items():
                c = per_vnode.setdefault(self._vnode_of(key), [0, 0])
                c[0] += 1
                c[1] += nb
            mv = mv_of.get(t, "")
            for vn, (nrows, nbytes) in per_vnode.items():
                rows.append((t, mv, vn, nrows, nbytes))
        return rows

    def rows(self) -> List[tuple]:
        """rw_state_topology payload: (table_id, mv, vnode, rows,
        bytes) — local tables plus drained worker rows."""
        rows = self._local_rows()
        with self._lock:
            for remote in self._remote.values():
                rows.extend(remote)
        return sorted(rows)

    def table_stats(self) -> List[tuple]:
        """(table_id, mv, rows, bytes, vnodes, imbalance): per-table
        rollup with the hot-vnode max/mean ratio — the rescale
        planner's move-cost input."""
        agg: Dict[int, list] = {}
        for t, mv, _vn, nrows, nbytes in self.rows():
            a = agg.setdefault(t, [mv, 0, 0, []])
            a[1] += nrows
            a[2] += nbytes
            a[3].append(nbytes)
        out = []
        for t, (mv, nrows, nbytes, per_vn) in sorted(agg.items()):
            mean = nbytes / len(per_vn) if per_vn else 0.0
            imb = (max(per_vn) / mean) if mean > 0 else 1.0
            out.append((t, mv, nrows, nbytes, len(per_vn),
                        round(imb, 3)))
        return out

    def top_vnodes(self, table_id: int, n: int = 8) -> List[tuple]:
        """(vnode, rows, bytes) for the table's n biggest vnodes —
        the `ctl memory` breakdown."""
        per = [(vn, nrows, nbytes) for t, _mv, vn, nrows, nbytes
               in self.rows() if t == table_id]
        return sorted(per, key=lambda r: -r[2])[:n]

    def bytes_by_mv(self) -> Dict[str, int]:
        """Per-MV resident-byte rollup from the delta-arithmetic
        totals — O(#tables), NOT a key scan: this runs at every
        checkpoint (costs.publish_state_bytes) and must never walk
        the per-key map (the map holds one entry per state row)."""
        out: Dict[str, int] = {}
        with self._lock:
            for t, (_nrows, nbytes) in self._totals.items():
                mv = self._mv_of.get(t, "")
                out[mv] = out.get(mv, 0) + nbytes
            for remote in self._remote.values():
                for _t, mv, _vn, _nrows, nbytes in remote:
                    out[mv] = out.get(mv, 0) + nbytes
        return out

    def imbalance_by_mv(self) -> Dict[str, float]:
        """Worst per-table hot-vnode ratio per MV (`ctl cost`'s
        aggregate skew signal)."""
        out: Dict[str, float] = {}
        for _t, mv, _nrows, _nbytes, _vns, imb in self.table_stats():
            out[mv] = max(out.get(mv, 1.0), imb)
        return out

    # -- conservation gate ----------------------------------------------
    def arm_checkpoint_verify(self, on: bool = True) -> None:
        self._verify_each_checkpoint = bool(on)

    def checkpoint_verify(self) -> None:
        """Checkpoint-time recount (meta/barrier.py piggyback): armed
        by the tier-1 gate fixture, a no-op in production."""
        if not self._verify_each_checkpoint:
            return
        with self._lock:
            self._violations.extend(self._recount_locked())

    def _recount_locked(self) -> List[tuple]:
        out = []
        for t, m in self._sizes.items():
            rows_inc, bytes_inc = self._totals.get(t, [0, 0])
            rows_true, bytes_true = len(m), sum(m.values())
            if rows_inc != rows_true or bytes_inc != bytes_true:
                out.append((t, rows_inc, rows_true,
                            bytes_inc, bytes_true))
        return out

    def gate_violations(self) -> List[tuple]:
        """(table_id, rows_incremental, rows_recount,
        bytes_incremental, bytes_recount) wherever the two books
        disagree — Σ per-table topology bytes must equal the accounted
        resident bytes (the map recount) exactly."""
        with self._lock:
            return self._violations + self._recount_locked()

    # -- cross-process merge (cluster `signals` drain) -------------------
    def drain_rows(self) -> List[tuple]:
        """Snapshot this process's local rows for the coordinator (a
        snapshot, not a drain — upkeep continues here)."""
        return self._local_rows()

    def ingest(self, rows: Iterable[tuple], worker: str = "") -> int:
        rows = [tuple(r) for r in rows]
        with self._lock:
            self._remote[worker] = rows
        return len(rows)

    def clear(self) -> None:
        with self._lock:
            self._sizes.clear()
            self._totals.clear()
            self._mv_of.clear()
            self._unit.clear()
            self._cleaned_to.clear()
            self._remote.clear()
            self._violations.clear()
            self._verify_each_checkpoint = False


TOPOLOGY = StateTopology()
