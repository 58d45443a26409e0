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


class _SortedRows:
    """One group's candidates: rows sorted by order key + pk tiebreak."""

    __slots__ = ("entries",)

    def __init__(self):
        self.entries: List[Tuple[_Key, tuple]] = []

    def insert(self, key: _Key, row: tuple) -> None:
        bisect.insort(self.entries, (key, row))

    def delete(self, key: _Key, row: tuple) -> None:
        i = bisect.bisect_left(self.entries, (key, row))
        if i < len(self.entries) and self.entries[i][1] == row:
            del self.entries[i]

    def window(self, offset: int, limit: Optional[int]) -> List[tuple]:
        hi = None if limit is None else offset + limit
        return [r for _k, r in self.entries[offset:hi]]

    def truncate_beyond(self, n: int) -> List[tuple]:
        """Drop rows ranked >= n (append-only pruning); returns dropped."""
        dropped = [r for _k, r in self.entries[n:]]
        del self.entries[n:]
        return dropped


class GroupTopNExecutor(Executor):
    """Streaming [group] top-n (top_n_plain.rs / group_top_n.rs analog).

    `group_indices=[]` gives plain TopN; `append_only=True` prunes
    managed state beyond the window (top_n_appendonly.rs analog).
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
        # sort = order cols, then pk for a total (deterministic) order
        self._sort_cols = [i for i, _ in self.order_by] + [
            i for i in pk
            if i not in {j for j, _ in self.order_by}]
        self._descs = tuple([d for _, d in self.order_by] +
                            [False] * (len(self._sort_cols)
                                       - len(self.order_by)))
        self.groups: Dict[tuple, _SortedRows] = {}
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
            entries = sum(len(sr.entries) for sr in s.groups.values())
            return row_est * entries + 120 * len(s._cold_groups)

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

    def _window(self, g: tuple) -> List[tuple]:
        rows = self.groups.get(g)
        return rows.window(self.offset, self.limit) if rows else []

    def _recover(self) -> None:
        # rows are PHYSICAL end to end (DECIMAL = scaled int64): order
        # is preserved under the physical encoding, state-table writes
        # expect it, and chunk rebuild must not lossily convert
        for _pk, row in self.state.iter_rows():
            g = self._group_of(row)
            self.groups.setdefault(g, _SortedRows()).insert(
                self._key_of(row), row)
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
            if self.groups.pop(g, None) is not None:
                self._cold_groups.add(g)
                n += 1
        return n

    def _reload_group(self, g: tuple) -> None:
        """Reload an evicted group's candidates with one prefix scan —
        runs BEFORE the old-window capture, so the emitted delta is
        computed against the true pre-chunk window."""
        self._cold_groups.discard(g)
        rows = _SortedRows()
        for _pk, row in self.state.iter_prefix(list(g)):
            row = tuple(row)
            rows.insert(self._key_of(row), row)
        if rows.entries:
            self.groups[g] = rows
        self._tier.note_reload(self._tier_part, 1)

    # -- chunk path ------------------------------------------------------
    def _apply(self, chunk: StreamChunk) -> Optional[StreamChunk]:
        touched: Dict[tuple, List[tuple]] = {}
        _idx, prows, pops = chunk.to_physical_records()
        # cold groups this chunk touches reload BEFORE write_chunk:
        # the reload prefix-scan must see PRE-chunk state only, or the
        # old-window capture would already contain this chunk's rows
        # (suppressing deltas) and the loop would double-insert them
        if self._cold_groups:
            for row in prows:
                g = self._group_of(row)
                if g in self._cold_groups:
                    self._reload_group(g)
        # state writes batch as ONE vectorized chunk apply (the same
        # insert/delete multiset the loop below maintains in memory) —
        # a per-row insert() pays a full pk encode each (the other q5
        # hot path); only append-only truncation drops need row calls
        self.state.write_chunk(chunk)
        for op_i, row in zip(pops.tolist(), prows):
            is_ins = Op(op_i).is_insert
            g = self._group_of(row)
            if g not in touched:
                touched[g] = self._window(g)
            rows = self.groups.setdefault(g, _SortedRows())
            key = self._key_of(row)
            if is_ins:
                rows.insert(key, row)
                if self.append_only and self.limit is not None:
                    for dropped in rows.truncate_beyond(
                            self.offset + self.limit):
                        self.state.delete(dropped)
            else:
                if self.append_only:
                    raise ValueError(
                        "delete on append-only TopN input")
                rows.delete(key, row)
        if self._tier is not None and touched:
            self._tier.touch(self._tier_part, list(touched),
                             self._tier_seq)
        # net window delta per touched group
        deletes: List[tuple] = []
        inserts: List[tuple] = []
        for g, old_window in touched.items():
            new_window = self._window(g)
            old_c, new_c = Counter(old_window), Counter(new_window)
            for r, cnt in (old_c - new_c).items():
                deletes.extend([r] * cnt)
            for r, cnt in (new_c - old_c).items():
                inserts.extend([r] * cnt)
        if not deletes and not inserts:
            return None
        return self._delta_chunk(deletes, inserts)

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
            if self._tier_part is not None:
                self._tier.unregister(self._tier_part)


def TopNExecutor(input_: Executor, order_by, offset, limit,
                 state: StateTable, append_only: bool = False
                 ) -> GroupTopNExecutor:
    """Plain (ungrouped) TopN — top_n_plain.rs / top_n_appendonly.rs."""
    return GroupTopNExecutor(input_, order_by, offset, limit, state,
                             group_indices=(), append_only=append_only)
