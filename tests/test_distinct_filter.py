"""`agg(DISTINCT x) FILTER (WHERE c)` in the hash aggregate (ISSUE 41):
one dedup table per distinct input column, one count per DISTINCT call
on that column, the filter a call's own.

The executor is driven by scripted chunks (MockSource), as the join-type
matrix is: inserts, deletes and U-/U+ pairs over a small domain, so a
pair's counts reach zero and come back and an update flips a row's
filter. Every barrier's materialized output and every dedup table's
rows are compared with a recount over the rows then live. The equations
held (ISSUE 41, Motivation 2): for a row `(op, g, v)` with `v` not NULL
and a call `j` on that column with filter `f_j`: if `f_j(row)` then
`cnt_j[g, v] += sign(op)`; the row is visible to call `j` iff
`cnt_j[g, v]` crossed between 0 and 1 in the direction of `op`; a pair
whose counts are all 0 leaves the table; a negative count is an error.
"""

import asyncio

import numpy as np
import pytest

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.ops.hash_agg import AggKind
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.stream.executors.hash_agg import (
    AggCall, HashAggExecutor, agg_aux_tables, agg_state_schema,
    distinct_state_schema, minput_state_schema,
)
from risingwave_tpu.stream.executors.test_utils import MockSource
from risingwave_tpu.stream.message import (
    Barrier, BarrierKind, Watermark, is_barrier, is_chunk,
)

# g: the group; v, w: the two distinct columns; r1..r3: 0/1 images of the
# three rank filters (the CASE rewrite of a `count(*) FILTER`); f1..f3:
# the filters themselves, BOOLEAN, NULL where the price is NULL
S = Schema.of(g=DataType.INT64, v=DataType.INT64, w=DataType.INT64,
              r1=DataType.INT64, r2=DataType.INT64, r3=DataType.INT64,
              f1=DataType.BOOLEAN, f2=DataType.BOOLEAN,
              f3=DataType.BOOLEAN)
G, V, W, R1, F1 = 0, 1, 2, 3, 6
FILTERS = (None, F1, F1 + 1, F1 + 2)


def thirteen_calls():
    """q15's twelve aggregates over this schema, in upstream's order."""
    calls = [AggCall(AggKind.COUNT)]
    calls += [AggCall(AggKind.SUM, R1 + k) for k in range(3)]
    for col in (V, W):
        calls += [AggCall(AggKind.COUNT, col, distinct=True, filter_idx=f)
                  for f in FILTERS]
    return calls


def barrier(n: int) -> Barrier:
    prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
    return Barrier(EpochPair(Epoch.from_physical(n), prev),
                   BarrierKind.CHECKPOINT)


def row_of(g, v, w, price):
    """One input row from a price (None: NULL, every filter NULL)."""
    ranks = (None, None, None) if price is None else \
        (price < 10, 10 <= price < 100, price >= 100)
    ints = tuple(0 if r is None else int(r) for r in ranks)
    return (g, v, w) + ints + ranks


def chunk(rows, ops):
    cols = {f.name: [r[i] for r in rows] for i, f in enumerate(S)}
    return StreamChunk.from_pydict(S, cols, ops=ops)


def script(seed, n_epochs=8, rows=40):
    """(messages, live rows after each barrier). An update is a U-/U+
    pair side by side that changes the price (so the filter a row
    passes) or a distinct value."""
    rng = np.random.default_rng(seed)
    live, msgs, states = [], [barrier(1)], []
    pick = lambda xs: xs[int(rng.integers(0, len(xs)))]  # noqa: E731
    for e in range(2, 2 + n_epochs):
        out, ops = [], []
        while len(out) < rows:
            u = rng.random()
            if live and u < 0.25:
                out.append(live.pop(int(rng.integers(0, len(live)))))
                ops.append(Op.DELETE)
            elif live and u < 0.5:
                old = live.pop(int(rng.integers(0, len(live))))
                new = row_of(old[0], pick([old[1], 1, 2, None]), old[2],
                             pick([None, 3, 50, 500]))
                out += [old, new]
                ops += [Op.UPDATE_DELETE, Op.UPDATE_INSERT]
                live.append(new)
            else:
                new = row_of(pick([None, 1, 2, 3]),
                             pick([None, 1, 2, 3, -7]),
                             pick([None, 10, 11, 12]),
                             pick([None, 3, 50, 500]))
                out.append(new)
                ops.append(Op.INSERT)
                live.append(new)
        msgs += [chunk(out, ops), barrier(e)]
        states.append(list(live))
    return msgs, states


def recount(live, calls):
    """group -> output row of `calls` over the live rows."""
    out = {}
    for g in {r[G] for r in live}:
        rows = [r for r in live if r[G] == g]
        vals = []
        for c in calls:
            if c.input_idx is None:
                vals.append(len(rows))
                continue
            xs = [r[c.input_idx] for r in rows
                  if r[c.input_idx] is not None
                  and (c.filter_idx is None or r[c.filter_idx])]
            if c.distinct:
                xs = sorted(set(xs))
            if c.kind == AggKind.COUNT:
                vals.append(len(xs))
            else:
                vals.append(sum(xs) if xs else None)
        out[g] = (g,) + tuple(vals)
    return out


def recount_pairs(live, col, filters):
    """The rows a dedup table must hold: (g, value, a count a filter)."""
    out = {}
    for r in live:
        if r[col] is None:
            continue
        cnt = out.setdefault((r[G], r[col]), [0] * len(filters))
        for s, f in enumerate(filters):
            cnt[s] += 1 if f is None else bool(r[f])
    return sorted(((g, v) + tuple(c) for (g, v), c in out.items()),
                  key=repr)


def build(store, msgs, calls, cls=HashAggExecutor, **kw):
    sch, pk = agg_state_schema(S, [G], calls)
    table = StateTable(10, sch, pk, store, dist_key_indices=[0])
    distinct, minput = agg_aux_tables(
        S, [G], calls, False, store,
        dedup_table_id=lambda col: 200 + col,
        minput_table_id=lambda j: 100 + j)
    return cls(MockSource(S, msgs), [G], calls, table,
               minput_tables=minput, distinct_tables=distinct, **kw)


def table_rows(table):
    return sorted((tuple(row) for _pk, row in table.iter_rows()), key=repr)


async def follow(ex, at_barrier, view=None):
    """Materialize the executor's changelog onto `view` (what a
    recovered executor's downstream already holds); call
    `at_barrier(view, n)` behind every barrier but the first."""
    view, n = dict(view or {}), 0
    async for m in ex.execute():
        if is_chunk(m):
            for op, row in m.to_records():
                if op in (Op.INSERT, Op.UPDATE_INSERT):
                    view[row[0]] = row
                else:
                    assert view.pop(row[0]) == row
        elif is_barrier(m):
            if n:
                at_barrier(dict(view), n - 1)
            n += 1
    return view


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_thirteen_calls_over_a_retracting_input_equal_a_recount(seed):
    calls = thirteen_calls()
    msgs, states = script(seed)
    ex = build(MemoryStateStore(), msgs, calls)
    assert sorted(ex.distinct_tables) == [V, W]      # two, not eight
    seen = []

    def check(view, i):
        live = states[i]
        assert view == recount(live, calls), f"barrier {i}"
        for col in (V, W):
            want = recount_pairs(live, col, FILTERS)
            assert table_rows(ex.distinct_tables[col]) == want
            assert sorted(ex._distinct_mult[col].rows(), key=repr) == want
            # a pair is a row while any count is not zero, no longer
            assert all(any(r[2:]) for r in want)
        seen.append(i)

    asyncio.run(follow(ex, check))
    assert seen == list(range(len(states)))
    # the script did what the case is for: pairs left, and an update
    # flipped a row's filter in place
    pairs = [{r[:2] for r in recount_pairs(s, V, FILTERS)} for s in states]
    assert any(a - b for a, b in zip(pairs, pairs[1:]))
    flips = [m for m in msgs if is_chunk(m) for (o1, a), (o2, b)
             in zip(m.to_records(), m.to_records()[1:])
             if o1 == Op.UPDATE_DELETE and o2 == Op.UPDATE_INSERT
             and a[V] == b[V] and a[F1:] != b[F1:]]
    assert flips


def test_the_layout_is_one_table_a_column_and_one_count_a_call():
    calls = thirteen_calls()
    for col in (V, W):
        js = [j for j, c in enumerate(calls)
              if c.distinct and c.input_idx == col]
        sch, pk, dk = distinct_state_schema(S, [G], calls, js)
        assert [f.name for f in sch] == ["g", "_value"] \
            + [f"_cnt{j}" for j in js]
        assert (pk, dk) == ([0, 1], [0])
    # a watermark column leads the key, as in the value state (PR 37)
    calls2 = [AggCall(AggKind.COUNT, V, distinct=True, filter_idx=F1)]
    _sch, pk, _dk = distinct_state_schema(S, [G, W], calls2, [0],
                                          key_lead=1)
    assert pk == [1, 0, 2]


def test_without_a_filter_the_table_is_the_parents_byte_for_byte():
    """count(DISTINCT v) + sum(DISTINCT v), no filter: one `_cnt`, the
    schema `minput_state_schema` gave the dedup table before a call
    could carry a filter, and the same bytes in the store as a writer
    of the parent's kind leaves."""
    calls = [AggCall(AggKind.COUNT, V, distinct=True),
             AggCall(AggKind.SUM, V, distinct=True), AggCall(AggKind.COUNT)]
    got = distinct_state_schema(S, [G], calls, [0, 1])
    want = minput_state_schema(S, [G], calls[0])
    assert [(f.name, f.data_type) for f in got[0]] == \
        [(f.name, f.data_type) for f in want[0]]
    assert got[1:] == want[1:]

    msgs, states = script(5, n_epochs=4)
    store = MemoryStateStore()
    ex = build(store, msgs, calls)
    asyncio.run(follow(ex, lambda *_: None))
    # the parent's writer: (group, value, net count) rows
    plain = MemoryStateStore()
    table = StateTable(200 + V, want[0], want[1], plain,
                       dist_key_indices=want[2])
    table.init_epoch(barrier(1).epoch)
    prev = {}
    for e, live in enumerate(states, start=2):
        now = {r[:2]: r for r in recount_pairs(live, V, (None,))}
        table.insert_rows([now[k] for k in now if k not in prev])
        table.update_rows([prev[k] for k in now
                           if k in prev and prev[k] != now[k]],
                          [now[k] for k in now
                           if k in prev and prev[k] != now[k]])
        table.delete_rows([prev[k] for k in prev if k not in now])
        table.commit(barrier(e).epoch)
        prev = now
    at = 1 << 62                 # past every epoch written
    got_bytes = list(store.iter(200 + V, at))
    assert got_bytes and got_bytes == list(plain.iter(200 + V, at))


class ParentLayoutAgg(HashAggExecutor):
    """Writes the dedup table the way the parent commit did: one net
    delta a pair, the old count taken from memory."""

    def _write_distinct_pending(self):
        for col, table in self.distinct_tables.items():
            mult = self._distinct_mult[col]
            for (group, value), (old,) in self._distinct_pending.pop(
                    col, {}).items():
                new, = mult.count(group, value)
                key = group + (value,)
                if old == new:
                    continue
                if old == 0:
                    table.insert(key + (new,))
                elif new == 0:
                    table.delete(key + (old,))
                else:
                    table.update(key + (old,), key + (new,))


def _split(msgs, n_barriers):
    """The script cut behind its `n_barriers`-th barrier: the second
    half starts with that barrier again, as a recovered actor's does."""
    at = [i for i, m in enumerate(msgs) if is_barrier(m)][n_barriers - 1]
    return msgs[:at + 1], msgs[at:]


@pytest.mark.parametrize("first", [ParentLayoutAgg, HashAggExecutor])
def test_a_state_of_the_parents_layout_recovers(first):
    calls = [AggCall(AggKind.COUNT, V, distinct=True),
             AggCall(AggKind.SUM, V, distinct=True), AggCall(AggKind.COUNT)]
    msgs, states = script(9, n_epochs=6)
    head, tail = _split(msgs, 4)
    store = MemoryStateStore()
    then = asyncio.run(follow(build(store, head, calls, cls=first),
                              lambda *_: None))
    ex = build(store, tail, calls)
    final = asyncio.run(follow(ex, lambda *_: None, then))
    assert table_rows(ex.distinct_tables[V]) == \
        recount_pairs(states[-1], V, (None,))
    assert final == recount(states[-1], calls)


def test_recovery_mid_run_continues_to_the_same_rows_and_counts():
    calls = thirteen_calls()
    msgs, states = script(4, n_epochs=8)
    head, tail = _split(msgs, 5)
    store = MemoryStateStore()
    then = asyncio.run(follow(build(store, head, calls),
                              lambda *_: None))
    ex = build(store, tail, calls)
    checked = []

    def check(view, i):
        live = states[4 + i]
        assert view == recount(live, calls)
        for col in (V, W):
            assert table_rows(ex.distinct_tables[col]) == \
                recount_pairs(live, col, FILTERS)
        checked.append(i)

    asyncio.run(follow(ex, check, then))
    assert checked == [0, 1, 2, 3]
    whole = build(MemoryStateStore(), msgs, calls)
    asyncio.run(follow(whole, lambda *_: None))
    for col in (V, W):
        assert table_rows(ex.distinct_tables[col]) == \
            table_rows(whole.distinct_tables[col])


def test_null_values_and_null_filters_count_for_nothing():
    calls = [AggCall(AggKind.COUNT, V, distinct=True),
             AggCall(AggKind.COUNT, V, distinct=True, filter_idx=F1),
             AggCall(AggKind.COUNT)]
    rows = [row_of(1, None, 10, 3),      # NULL value: no pair
            row_of(1, 7, 10, None),      # NULL filter: pair, f1 count 0
            row_of(1, 7, 10, 3),         # the same pair passes f1 once
            row_of(1, 8, 10, 500)]       # fails f1
    msgs = [barrier(1), chunk(rows, [Op.INSERT] * 4), barrier(2),
            chunk(rows[2:3], [Op.DELETE]), barrier(3)]
    ex = build(MemoryStateStore(), msgs, calls)
    views = []
    asyncio.run(follow(ex, lambda view, i: views.append(
        (view, table_rows(ex.distinct_tables[V])))))
    assert views[0] == ({1: (1, 2, 1, 4)},
                        [(1, 7, 2, 1), (1, 8, 1, 0)])
    # the only row of (1, 7) that passed f1 is retracted: its filtered
    # count crosses back to 0 and the pair stays for the other call
    assert views[1] == ({1: (1, 2, 0, 3)},
                        [(1, 7, 1, 0), (1, 8, 1, 0)])


def test_sum_distinct_under_a_filter_shares_the_counts_table():
    calls = [AggCall(AggKind.SUM, V, distinct=True, filter_idx=F1),
             AggCall(AggKind.COUNT, V, distinct=True, filter_idx=F1),
             AggCall(AggKind.SUM, V, distinct=True),
             AggCall(AggKind.COUNT)]
    msgs, states = script(6, n_epochs=6)
    ex = build(MemoryStateStore(), msgs, calls)
    assert list(ex.distinct_tables) == [V]
    assert [f.name for f in ex.distinct_tables[V].schema][-3:] == \
        ["_cnt0", "_cnt1", "_cnt2"]

    def check(view, i):
        assert view == recount(states[i], calls)
        assert table_rows(ex.distinct_tables[V]) == \
            recount_pairs(states[i], V, (F1, F1, None))

    asyncio.run(follow(ex, check))


def test_a_retraction_of_an_unseen_pair_is_an_error():
    calls = [AggCall(AggKind.COUNT, V, distinct=True, filter_idx=F1),
             AggCall(AggKind.COUNT)]
    msgs = [barrier(1), chunk([row_of(1, 7, 10, 500)], [Op.INSERT]),
            barrier(2),
            # the live row of (1, 7) failed f1; this one claims it passed
            chunk([row_of(1, 7, 10, 3)], [Op.DELETE]), barrier(3)]
    ex = build(MemoryStateStore(), msgs, calls)
    with pytest.raises(ValueError, match="distinct retract below zero"):
        asyncio.run(follow(ex, lambda *_: None))


def test_the_cold_tier_takes_a_groups_pairs_and_brings_them_back():
    calls = [AggCall(AggKind.COUNT, V, distinct=True),
             AggCall(AggKind.COUNT, V, distinct=True, filter_idx=F1),
             AggCall(AggKind.COUNT)]
    n = 60
    first = [row_of(g, v, 10, p) for g in range(n)
             for v, p in ((g, 3), (g + 100, 500))]
    msgs = [barrier(1), chunk(first, [Op.INSERT] * len(first)), barrier(2),
            chunk([row_of(g, 7, 10, 3) for g in range(n // 2, n)],
                  [Op.INSERT] * (n // 2)), barrier(3),
            # retract, from cold groups, the row that passed the filter
            chunk([row_of(g, g, 10, 3) for g in range(10)],
                  [Op.DELETE] * 10), barrier(4)]
    capped = build(MemoryStateStore(), msgs, calls, tier_cap=16,
                   kernel_capacity=1 << 10)
    cold = {}

    def check(view, i):
        if i == 1:
            cold.update({vt[0]: dict(capped._distinct_mult[V].values(vt))
                         for vt in capped._cold_groups.values()})

    final_c = asyncio.run(follow(capped, check))
    assert len(cold) >= n - 16 and set(range(10)) <= set(cold)
    assert all(held == {} for held in cold.values())
    for g in range(10):
        assert dict(capped._distinct_mult[V].values((g,))) == \
            {g + 100: (1, 0)}
    whole = build(MemoryStateStore(), msgs, calls)
    final_w = asyncio.run(follow(whole, lambda *_: None))
    assert final_c == final_w and final_c[3] == (3, 1, 0, 1)
    assert table_rows(capped.distinct_tables[V]) == \
        table_rows(whole.distinct_tables[V])


def test_a_watermark_cuts_the_pairs_with_their_rows():
    calls = [AggCall(AggKind.COUNT, V, distinct=True),
             AggCall(AggKind.COUNT, V, distinct=True, filter_idx=F1),
             AggCall(AggKind.COUNT)]
    rows = [row_of(g, v, 10, 3) for g in range(8) for v in (g, g + 10)]
    msgs = [barrier(1), chunk(rows, [Op.INSERT] * len(rows)), barrier(2),
            Watermark(G, DataType.INT64, 5), barrier(3),
            chunk([row_of(2, 1, 10, 500)], [Op.INSERT]), barrier(4)]
    ex = build(MemoryStateStore(), msgs, calls)
    asyncio.run(follow(ex, lambda *_: None))
    got = table_rows(ex.distinct_tables[V])
    assert got == sorted(ex._distinct_mult[V].rows(), key=repr)
    assert {r[0] for r in got} == {2, 5, 6, 7}
    # the late row starts its retired group from nothing
    assert [r for r in got if r[0] == 2] == [(2, 1, 1, 0)]
