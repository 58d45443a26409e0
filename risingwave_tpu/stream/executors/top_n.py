"""TopN executors: streaming ORDER BY ... OFFSET ... LIMIT maintenance.

Reference parity: src/stream/src/executor/top_n/ — top_n_plain.rs
(TopNExecutor), group_top_n.rs (GroupTopNExecutor), top_n_appendonly.rs
(AppendOnlyTopNExecutor); state layout managed state = all candidate
rows keyed by [group key +] order key + pk (top_n_state.rs).

Re-design notes: the reference replays each row against a btree cache
and emits per-row deltas. Here each *chunk* applies as a batch and the
executor emits the NET delta of the visible window [offset, offset+limit)
per group — equivalent eventual output with one sorted-structure pass
per chunk. Ordering is host-side (control-heavy small-N work, same as
the reference's CPU btree — nothing here wants the MXU).

NULLS ordering follows PostgreSQL: NULLS LAST for ASC, NULLS FIRST for
DESC.

A chunk is three stages of ``host_emit`` (utils/ledger.py):
``topn.apply`` (the walk over its rows and the diff of the touched
groups' windows), ``topn.state`` (what the diff writes to and deletes
from the state table; the table's own ``state.write`` nests in it and
keeps its own name) and ``topn.emit`` (the delta chunk). The books, by state table, in every row of
``rw_metrics_history``: ``topn.t<id>.rows_in`` / ``.rows_out`` (rows of
the chunks in and of the deltas out), ``.state_writes`` /
``.state_deletes`` (rows written to and deleted from the table),
``.groups`` (groups that hold a row, at the barrier) and
``.cached_rows`` (rows held in memory, at the barrier).
"""

from __future__ import annotations

import bisect
from collections import Counter
from typing import AsyncIterator, Dict, List, Optional, Sequence, Tuple

import numpy as np

from risingwave_tpu.common.chunk import Column, Op, StreamChunk
from risingwave_tpu.common.types import Schema
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.message import (
    Message, is_barrier, is_chunk, is_watermark,
)
from risingwave_tpu.utils.ledger import staged
from risingwave_tpu.utils.metrics import STREAMING as _METRICS


class _Key:
    """None-aware, per-column asc/desc comparable sort key."""

    __slots__ = ("vals", "descs")

    def __init__(self, vals: Tuple, descs: Tuple[bool, ...]):
        self.vals = vals
        self.descs = descs

    def __lt__(self, other: "_Key") -> bool:
        for a, b, d in zip(self.vals, other.vals, self.descs):
            if a is None and b is None:
                continue
            if a is None:               # NULLS LAST asc / FIRST desc
                return d
            if b is None:
                return not d
            if a == b:
                continue
            return (a > b) if d else (a < b)
        return False

    def __eq__(self, other) -> bool:
        return self.vals == other.vals

    def __repr__(self) -> str:
        return f"_Key({self.vals})"


def _delete_entry(entries: list, key, row: tuple) -> bool:
    i = bisect.bisect_left(entries, (key, row))
    if i < len(entries) and entries[i][1] == row:
        del entries[i]
        return True
    return False


class GroupTopNExecutor(Executor):
    """Streaming [group] top-n (top_n_plain.rs / group_top_n.rs analog).

    `group_indices=[]` gives plain TopN. The two arms keep different
    state:

    - retractable (group_top_n.rs): EVERY input row is a candidate, in
      the state table and in the group's sorted cache, because a
      delete of a window row brings the runner-up back. A chunk is one
      ``write_chunk`` of its rows as they come.
    - append-only (top_n_appendonly.rs): nothing leaves but by being
      pushed out, so only the rows ranked ``[0, offset + limit)`` of a
      group are kept, in memory and in the table alike: a row that
      does not enter that range is not written, the row it pushes out
      is deleted, and nothing else touches the table (limit 1: one row
      a group). A chunk is one ``insert_rows`` of the rows that
      entered and one ``delete_rows`` of the rows that left, net over
      the chunk. In memory the range is a tuple of plain tuples, made
      anew when a row enters: the cyclic collector untracks it, so a
      hundred thousand groups add nothing to its full pass (a list a
      group added a third to a pass that stalls a barrier).
    """

    def __init__(self, input_: Executor, order_by: Sequence[Tuple[int, bool]],
                 offset: int, limit: Optional[int], state: StateTable,
                 group_indices: Sequence[int] = (),
                 append_only: bool = False,
                 pk_indices: Optional[Sequence[int]] = None,
                 tier_cap: Optional[int] = None):
        # planner chains sometimes know the pk better than the input
        # executor advertises (e.g. a projection over an agg)
        pk = list(pk_indices if pk_indices is not None
                  else input_.pk_indices)
        super().__init__(ExecutorInfo(
            input_.schema, pk,
            "GroupTopNExecutor" if group_indices else "TopNExecutor"))
        self.input = input_
        self.order_by = list(order_by)
        self.offset = int(offset)
        self.limit = limit
        self.state = state
        self.group_indices = list(group_indices)
        self.append_only = append_only
        self.tier_cap = tier_cap
        # sort = order cols, then pk for a total (deterministic) order
        self._sort_cols = [i for i, _ in self.order_by] + [
            i for i in pk
            if i not in {j for j, _ in self.order_by}]
        self._descs = tuple([d for _, d in self.order_by] +
                            [False] * (len(self._sort_cols)
                                       - len(self.order_by)))
        # group → its candidates as (sort key, row), sorted: a list on
        # the retractable arm, a tuple on the append-only arm
        self.groups: Dict[tuple, Sequence[Tuple[object, tuple]]] = {}
        self._cached_rows = 0       # rows in the groups' caches
        self._label = f"t{state.table_id}"     # the books' table label
        # fast-key eligibility: native tuples compare in C (an order of
        # magnitude over _Key.__lt__'s per-column Python loop, q5's
        # single hottest path); DESC needs numeric negation, so
        # any DESC column with a non-numeric physical type falls back
        from risingwave_tpu.common.types import DataType
        numeric = {DataType.INT16, DataType.INT32, DataType.INT64,
                   DataType.SERIAL, DataType.DECIMAL, DataType.DATE,
                   DataType.TIME, DataType.TIMESTAMP,
                   DataType.TIMESTAMPTZ, DataType.FLOAT32,
                   DataType.FLOAT64, DataType.BOOLEAN}
        self._fast_keys = all(
            (not d) or input_.schema[i].data_type in numeric
            for i, d in zip(self._sort_cols, self._descs))
        # host-state accounting (EstimateSize analog): sorted group
        # caches are exactly the kind of unbounded host cache the
        # memory manager wants on its books
        import weakref

        from risingwave_tpu.utils import memory as _mem
        mem_name = f"{self.identity}#{id(self)}"
        wref = weakref.ref(self)
        row_est = 96 + 16 * len(input_.schema)

        def _nbytes() -> int:
            s = wref()
            if s is None:
                _mem.GLOBAL.unregister(mem_name)
                return 0
            return row_est * s._cached_rows + 120 * len(s._cold_groups)

        _mem.GLOBAL.register(mem_name, _nbytes)
        # cold tier (state/tier.py): whole GROUP caches evict — the
        # sorted candidate rows drop from memory but stay durable in
        # the state table (pk leads with the group key, so reload is
        # one prefix scan); a chunk touching an evicted group reloads
        # it BEFORE the old-window capture, so emitted deltas stay
        # exact. Grouped TopN only: plain TopN is one window — nothing
        # to tier.
        self._tier = None
        self._tier_part = None
        self._cold_groups: set = set()
        self._tier_seq = 0
        if tier_cap is not None:
            g = len(self.group_indices)
            if not g:
                raise ValueError("tier_cap needs a grouped TopN")
            if state.pk_indices[:g] != self.group_indices:
                raise ValueError(
                    "tier_cap needs the state-table pk prefixed by "
                    "the group key (reload prefix-scans by group): "
                    f"pk={state.pk_indices} group={self.group_indices}")
            for i in state.dist_key_indices:
                if state.pk_indices.index(i) >= g:
                    raise ValueError(
                        "tier_cap needs dist keys inside the group "
                        "prefix")
            from risingwave_tpu.state import tier as _tier
            self._tier = _tier.GLOBAL
            # registration deferred to execute(): plan-only executors
            # must leave no ghost entries in the global registry
            self._tier_cap = int(tier_cap)
            self._tier_name = mem_name
            self._tier_nbytes = _nbytes

    # -- helpers ---------------------------------------------------------
    def _key_of(self, row: tuple):
        if self._fast_keys:
            # per-column (null_rank, value) pairs; physical rows make
            # every DESC value negatable. NULLS LAST asc / FIRST desc.
            return tuple(
                ((1, 0) if not d else (-1, 0)) if row[i] is None
                else (0, -row[i] if d else row[i])
                for i, d in zip(self._sort_cols, self._descs))
        return _Key(tuple(row[i] for i in self._sort_cols), self._descs)

    def _group_of(self, row: tuple) -> tuple:
        return tuple(row[i] for i in self.group_indices)

    def _recover(self) -> None:
        # rows are PHYSICAL end to end (DECIMAL = scaled int64): order
        # is preserved under the physical encoding, state-table writes
        # expect it, and chunk rebuild must not lossily convert
        for _pk, row in self.state.iter_rows():
            g = self._group_of(row)
            bisect.insort(self.groups.setdefault(g, []),
                          (self._key_of(row), row))
            self._cached_rows += 1
        if self.append_only:
            self.groups = {g: tuple(e) for g, e in self.groups.items()}
        if self._tier is not None and self.groups:
            # everything recovers resident (cold markers do not survive
            # a crash); seed the tier clock so the first checkpoint
            # sweep re-applies the cap
            self._tier.touch(self._tier_part, list(self.groups),
                             self._tier_seq)

    # -- cold tier (state/tier.py) ---------------------------------------
    def _tier_register(self) -> None:
        """Register at execute() start — only executors that actually
        RUN appear in the global registry."""
        import weakref
        tref = weakref.ref(self)

        def _evict_cb(keys):
            s = tref()
            return 0 if s is None else s._tier_evict(keys)

        self._tier_part = self._tier.register(
            self._tier_name, _evict_cb, cap=self._tier_cap,
            nbytes=self._tier_nbytes)

    def _tier_evict(self, groups: List[tuple]) -> int:
        """Tier sweep callback (checkpoint barriers, post-commit): drop
        the given groups' sorted caches; their candidate rows stay
        durable in the state table."""
        n = 0
        for g in groups:
            rows = self.groups.pop(g, None)
            if rows is not None:
                self._cached_rows -= len(rows)
                self._cold_groups.add(g)
                n += 1
        return n

    def _reload_group(self, g: tuple) -> None:
        """Reload an evicted group's candidates with one prefix scan —
        runs BEFORE the old-window capture, so the emitted delta is
        computed against the true pre-chunk window."""
        self._cold_groups.discard(g)
        rows = sorted((self._key_of(tuple(row)), tuple(row))
                      for _pk, row in self.state.iter_prefix(list(g)))
        if rows:
            self.groups[g] = tuple(rows) if self.append_only else rows
            self._cached_rows += len(rows)
        self._tier.note_reload(self._tier_part, 1)

    # -- chunk path ------------------------------------------------------
    def _apply(self, chunk: StreamChunk) -> Optional[StreamChunk]:
        n_in, n_ins, deletes, inserts, entered, left = self._walk(chunk)
        if self.append_only:
            self._persist_window(entered, left)
            wrote, deleted = len(entered), len(left)
        else:
            # every row is a candidate: the chunk goes to the table as
            # it came, one vectorized apply
            self.state.write_chunk(chunk)
            wrote, deleted = n_ins, n_in - n_ins
        books = _METRICS.topn_rows
        books.inc(float(n_in), table=self._label, event="rows_in")
        books.inc(float(len(deletes) + len(inserts)), table=self._label,
                  event="rows_out")
        books.inc(float(wrote), table=self._label, event="state_writes")
        books.inc(float(deleted), table=self._label,
                  event="state_deletes")
        if not deletes and not inserts:
            return None
        return self._delta_chunk(deletes, inserts)

    @staged("topn.apply")
    def _walk(self, chunk: StreamChunk):
        """A chunk's rows against the groups' sorted caches, then the
        net delta of every touched group's window. Returns (rows in,
        inserts among them, window deletes, window inserts, entered,
        left): the last two are the rows that entered and left the
        append-only arm's kept range, what its table is to be told
        (empty on the retractable arm, whose table takes the chunk)."""
        _idx, prows, pops = chunk.to_physical_records()
        # cold groups this chunk touches reload BEFORE anything is
        # applied: the reload prefix-scan must see PRE-chunk state
        # only, or the old-window capture would already contain this
        # chunk's rows (suppressing deltas) and the loop would
        # double-insert them
        if self._cold_groups:
            for row in prows:
                g = self._group_of(row)
                if g in self._cold_groups:
                    self._reload_group(g)
        is_ins = (pops == int(Op.INSERT)) | (pops == int(Op.UPDATE_INSERT))
        lo = self.offset
        hi = None if self.limit is None else lo + self.limit
        # group → the entries ranked [0, offset + limit) it held before
        # the chunk touched it (append-only: its whole cache)
        touched: Dict[tuple, List[tuple]] = {}
        if self.append_only:
            if not is_ins.all():
                raise ValueError("delete on append-only TopN input")
            self._walk_append_only(prows, touched)
        else:
            for ins, row in zip(is_ins.tolist(), prows):
                g = self._group_of(row)
                rows = self.groups.get(g)
                if rows is None:
                    rows = self.groups[g] = []
                if g not in touched:
                    touched[g] = rows[:hi]
                if ins:
                    bisect.insort(rows, (self._key_of(row), row))
                    self._cached_rows += 1
                else:
                    self._cached_rows -= _delete_entry(
                        rows, self._key_of(row), row)
        if self._tier is not None and touched:
            self._tier.touch(self._tier_part, list(touched),
                             self._tier_seq)
        # net window delta per touched group, and append-only the net
        # change of the kept range [0, offset + limit)
        deletes: List[tuple] = []
        inserts: List[tuple] = []
        entered: List[tuple] = []
        left: List[tuple] = []
        for g, old in touched.items():
            new = self.groups[g]
            if not new:
                del self.groups[g]
            old_c = Counter(r for _k, r in old[lo:])
            new_c = Counter(r for _k, r in new[lo:hi])
            for r, cnt in (old_c - new_c).items():
                deletes.extend([r] * cnt)
            for r, cnt in (new_c - old_c).items():
                inserts.extend([r] * cnt)
            if self.append_only:
                was = {r for _k, r in old}
                now = {r for _k, r in new}
                entered.extend(r for _k, r in new if r not in was)
                left.extend(r for _k, r in old if r not in now)
        self._cached_rows += len(entered) - len(left)
        return (len(prows), int(is_ins.sum()), deletes, inserts, entered,
                left)

    def _walk_append_only(self, prows: List[tuple],
                          touched: Dict[tuple, List[tuple]]) -> None:
        """top_n_appendonly.rs: a row enters its group's kept range
        [0, offset + limit) or is dropped where it stands; the row it
        pushes past the end goes. Only a group whose range changed is
        touched."""
        cap = None if self.limit is None else self.offset + self.limit
        groups = self.groups
        for row in prows:
            g = self._group_of(row)
            rows = groups.get(g, ())
            entry = (self._key_of(row), row)
            if cap is not None and len(rows) >= cap \
                    and not entry < rows[-1]:
                continue
            if g not in touched:
                touched[g] = rows
            i = bisect.bisect_right(rows, entry)
            groups[g] = (rows[:i] + (entry,) + rows[i:])[:cap]

    @staged("topn.state")
    def _persist_window(self, entered: List[tuple],
                        left: List[tuple]) -> None:
        """The append-only arm's table writes: the rows that entered
        the kept range and the rows that left it, one batch call each
        (their ``state.write`` nests here and is filed under its own
        name)."""
        if entered:
            self.state.insert_rows(entered)
        if left:
            self.state.delete_rows(left)

    @staged("topn.emit")
    def _delta_chunk(self, deletes: List[tuple],
                     inserts: List[tuple]) -> StreamChunk:
        rows = deletes + inserts
        n = len(rows)
        ops = np.asarray([int(Op.DELETE)] * len(deletes)
                         + [int(Op.INSERT)] * len(inserts), dtype=np.int8)
        cols: List[Column] = []
        for j, f in enumerate(self.schema):
            vals_l = [r[j] for r in rows]
            ok = np.asarray([v is not None for v in vals_l])
            if f.data_type.is_device:
                vals = np.asarray([0 if v is None else v for v in vals_l],
                                  dtype=f.data_type.np_dtype)
            else:
                vals = np.asarray(vals_l, dtype=object)
            cols.append(Column(f.data_type, vals,
                               None if ok.all() else ok))
        return StreamChunk(self.schema, cols, np.ones(n, dtype=bool), ops)

    async def execute(self) -> AsyncIterator[Message]:
        it = self.input.execute()
        first = await it.__anext__()
        assert is_barrier(first)
        if self._tier is not None:
            self._tier_register()
        self.state.init_epoch(first.epoch)
        self._recover()
        yield first
        try:
            async for msg in it:
                if is_chunk(msg):
                    out = self._apply(msg)
                    if out is not None:
                        yield out
                elif is_barrier(msg):
                    _METRICS.topn_resident.set(
                        float(len(self.groups) + len(self._cold_groups)),
                        table=self._label, what="groups")
                    _METRICS.topn_resident.set(
                        float(self._cached_rows), table=self._label,
                        what="cached_rows")
                    self.state.commit(msg.epoch)
                    if self._tier is not None:
                        # sweep at checkpoints, post-commit: evicted
                        # groups' rows are durable and no chunk is in
                        # flight (tier.py epoch-sequencing argument)
                        self._tier_seq += 1
                        if msg.kind.is_checkpoint:
                            self._tier.sweep(self._tier_part,
                                             self._tier_seq)
                    yield msg
                elif is_watermark(msg):
                    if msg.col_idx in self.group_indices:
                        yield msg   # group-key watermarks pass through
        finally:
            # executor teardown: release this table's gauge series
            for what in ("groups", "cached_rows"):
                _METRICS.topn_resident.remove(table=self._label,
                                              what=what)
            if self._tier_part is not None:
                self._tier.unregister(self._tier_part)


def TopNExecutor(input_: Executor, order_by, offset, limit,
                 state: StateTable, append_only: bool = False
                 ) -> GroupTopNExecutor:
    """Plain (ungrouped) TopN — top_n_plain.rs / top_n_appendonly.rs."""
    return GroupTopNExecutor(input_, order_by, offset, limit, state,
                             group_indices=(), append_only=append_only)
