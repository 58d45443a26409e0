"""Unified state-tiering subsystem (state/tier.py).

The cold tier bounds resident keyed state for EVERY stateful executor
— agg groups, outer-join degree state, TopN group caches — by evicting
least-recently-touched keys to the durable state table and reloading
them on touch. "Dies at high cardinality" becomes "degrades to reload
traffic": every oracle here compares a hard-capped run bit-identically
against an uncapped one.

Reference parity: managed_state/join/mod.rs:379-420 (LRU over the
StateTable), cache/managed_lru.rs, memory_management/memory_manager.rs.
"""

import asyncio
import collections

import numpy as np
import pytest

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.state.tier import StateTier
from risingwave_tpu.stream.executors.hash_agg import (
    AggCall, HashAggExecutor, agg_state_schema,
)
from risingwave_tpu.ops.hash_agg import AggKind
from risingwave_tpu.stream.executors.hash_join import (
    HashJoinExecutor, JoinType,
)
from risingwave_tpu.stream.executors.test_utils import (
    MockSource, collect_until_n_barriers,
)
from risingwave_tpu.stream.message import Barrier, BarrierKind

AGG_S = Schema.of(g=DataType.INT64, v=DataType.INT64)
L_S = Schema.of(k=DataType.INT64, lv=DataType.INT64, lid=DataType.INT64)
R_S = Schema.of(k=DataType.INT64, rv=DataType.INT64, rid=DataType.INT64)


def _barrier(n):
    curr = Epoch.from_physical(n)
    prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
    return Barrier(EpochPair(curr, prev), BarrierKind.CHECKPOINT)


def _chunk(schema, rows, ops=None):
    names = [f.name for f in schema]
    return StreamChunk.from_pydict(
        schema, {nm: [r[i] for r in rows]
                 for i, nm in enumerate(names)}, ops=ops)


def _final_rows(outs):
    """Fold a change stream into pk→row (pk = first column)."""
    st = {}
    for m in outs:
        if isinstance(m, StreamChunk):
            for op, row in m.to_records():
                if op in (Op.INSERT, Op.UPDATE_INSERT):
                    st[row[0]] = row
                elif op == Op.DELETE:
                    st.pop(row[0], None)
    return st


# -- tier policy units ----------------------------------------------------

def test_tier_lru_and_cap():
    """Oldest-touched keys evict first; re-touch rescues a key."""
    evicted = []
    tier = StateTier(memory=type("M", (), {"soft_limit": None,
                                           "last_total": 0})())
    part = tier.register("p", lambda ks: evicted.extend(ks) or len(ks),
                         cap=4)
    tier.touch(part, ["a", "b", "c", "d"], 1)
    tier.touch(part, ["a"], 2)            # a is now the NEWEST
    tier.touch(part, ["e", "f"], 3)       # 6 resident > cap 4
    n = tier.sweep(part, 4)               # target = 4 * 0.75 = 3
    assert n == 3 and evicted == ["b", "c", "d"]
    assert list(part.keys) == ["a", "e", "f"]


def test_tier_pressure_watermark():
    """MemoryContext over its soft limit halves every participant at
    its next sweep, cap or no cap."""
    mem = type("M", (), {"soft_limit": 100, "last_total": 500})()
    evicted = []
    tier = StateTier(memory=mem)
    part = tier.register("p", lambda ks: evicted.extend(ks) or len(ks))
    tier.touch(part, list(range(10)), 1)
    assert tier.sweep(part, 2) == 5       # PRESSURE_KEEP_RATIO = 0.5
    assert evicted == [0, 1, 2, 3, 4]
    mem.last_total = 50                   # back under the limit
    assert tier.sweep(part, 3) == 0


def test_tier_insert_false_refreshes_only():
    tier = StateTier(memory=type("M", (), {"soft_limit": None,
                                           "last_total": 0})())
    part = tier.register("p", lambda ks: len(ks), cap=None)
    tier.touch(part, ["a"], 1)
    tier.touch(part, ["a", "b"], 2, insert=False)
    assert list(part.keys) == ["a"]       # b never minted


# -- hash-agg consumer ----------------------------------------------------

def _agg_calls():
    return [AggCall(AggKind.SUM, 1), AggCall(AggKind.COUNT)]


def _build_agg(store, msgs, tier_cap):
    sch, pk = agg_state_schema(AGG_S, [0], _agg_calls())
    t = StateTable(1, sch, pk, store, dist_key_indices=[0])
    return HashAggExecutor(MockSource(AGG_S, msgs), [0], _agg_calls(),
                           t, append_only=False, tier_cap=tier_cap,
                           kernel_capacity=1 << 10)


def _agg_script(n_keys=300, wave=100):
    """q7-shaped: waves of fresh groups (old ones go cold), then every
    group re-touched — including RETRACTIONS against evicted groups."""
    msgs = [_barrier(1)]
    epoch = 2
    for lo in range(0, n_keys, wave):
        msgs += [_chunk(AGG_S, [(g, g * 2)
                                for g in range(lo, lo + wave)]),
                 _barrier(epoch)]
        epoch += 1
    for lo in range(0, n_keys, wave):
        msgs += [_chunk(AGG_S, [(g, 5)
                                for g in range(lo, lo + wave)]),
                 _barrier(epoch)]
        epoch += 1
    retr = [(g, g * 2) for g in range(0, 50)]
    msgs += [_chunk(AGG_S, retr, ops=[Op.DELETE] * len(retr)),
             _barrier(epoch)]
    return msgs, epoch


def test_agg_high_cardinality_oracle():
    """Groups ≫ cap: the capped run (cap = 1/18th of cardinality) is
    bit-identical to the uncapped one, through evictions, reloads AND
    retractions of evicted groups — agg state is fully durable, so
    reload-on-touch is retraction-safe."""
    msgs, epoch = _agg_script()
    capped = _build_agg(MemoryStateStore(), msgs, 16)
    outs_c = asyncio.run(collect_until_n_barriers(capped, epoch - 1))
    uncapped = _build_agg(MemoryStateStore(), msgs, None)
    outs_u = asyncio.run(collect_until_n_barriers(uncapped, epoch - 1))
    assert _final_rows(outs_c) == _final_rows(outs_u)
    part = capped._tier_part
    assert part.evicted_total > 0 and part.reload_total > 0
    # the cap held at the last sweep
    assert len(part.keys) <= 16


def test_agg_crash_recovery_with_evicted_keys():
    """Crash with most groups evicted: a fresh executor over the same
    store recovers the COMMITTED durable state — evicted and resident
    alike — and further touches stay oracle-exact."""
    store = MemoryStateStore()
    msgs, epoch = [_barrier(1)], 2
    for lo in range(0, 300, 100):
        msgs += [_chunk(AGG_S, [(g, g) for g in range(lo, lo + 100)]),
                 _barrier(epoch)]
        epoch += 1
    first = _build_agg(store, msgs, 16)
    asyncio.run(collect_until_n_barriers(first, epoch - 1))
    assert len(first._cold_groups) > 200          # most groups cold

    # restart: touch every third group (evicted before the crash)
    touch = [(g, 1) for g in range(0, 300, 3)]
    msgs2 = [_barrier(epoch), _chunk(AGG_S, touch), _barrier(epoch + 1)]
    second = _build_agg(store, msgs2, 16)
    outs = asyncio.run(collect_until_n_barriers(second, 2))
    got = _final_rows(outs)
    # every touched group emits an UPDATE pair with sum = g + 1
    assert len(got) == len(touch)
    for g, _one in touch:
        assert got[g] == (g, g + 1, 2)


def _build_max_agg(store, msgs, tier_cap):
    """A retractable MAX and a DISTINCT sum: a value multiset each, in
    memory and in an aux table."""
    from risingwave_tpu.stream.executors.hash_agg import agg_aux_tables
    calls = [AggCall(AggKind.MAX, 1), AggCall(AggKind.COUNT),
             AggCall(AggKind.SUM, 1, distinct=True)]
    sch, pk = agg_state_schema(AGG_S, [0], calls)
    t = StateTable(1, sch, pk, store, dist_key_indices=[0])
    distinct, minput = agg_aux_tables(
        AGG_S, [0], calls, False, store,
        dedup_table_id=lambda col: 30 + col,
        minput_table_id=lambda j: 20 + j)
    return HashAggExecutor(MockSource(AGG_S, msgs), [0], calls, t,
                           minput_tables=minput, distinct_tables=distinct,
                           tier_cap=tier_cap, kernel_capacity=1 << 10)


def _prefix_rows(table, group):
    return sorted(row for _pk, row in table.iter_prefix([group]))


def test_agg_multiset_leaves_with_its_group_and_comes_back():
    """The value multisets in memory follow the cold tier: an evicted
    group's values are dropped (its rows stay in the aux tables), and a
    delete that touches the group reloads them before it retracts, so
    the MAX that survives is the uncapped run's."""
    n = 60
    msgs = [_barrier(1),
            _chunk(AGG_S, [(g, v) for g in range(n) for v in (g, g + 100)]),
            _barrier(2),
            # touch only the upper half: the lower half goes cold
            _chunk(AGG_S, [(g, 7) for g in range(n // 2, n)]),
            _barrier(3),
            # retract the maximum of cold groups 0..9
            _chunk(AGG_S, [(g, g + 100) for g in range(10)],
                   ops=[Op.DELETE] * 10),
            _barrier(4)]
    capped = _build_max_agg(MemoryStateStore(), msgs, 16)
    seen = {}

    async def run():
        out = []
        async for m in capped.execute():
            out.append(m)
            if isinstance(m, Barrier) and m.epoch.curr == \
                    Epoch.from_physical(3):
                seen["cold"] = dict(capped._cold_groups)
                seen["max"] = {g: dict(capped._minput_mult[0].values((g,)))
                               for g in range(n)}
                seen["sum"] = {g: dict(capped._distinct_mult[1].values((g,)))
                               for g in range(n)}
        return out

    outs_c = asyncio.run(run())
    cold = {vt[0] for vt in seen["cold"].values()}
    assert len(cold) >= n - 16 and set(range(10)) <= cold
    for g in range(n):
        held = {} if g in cold else {g: 1, g + 100: 1}
        if g not in cold and g >= n // 2:
            held[7] = 1
        assert seen["max"][g] == held
        # a dedup pair holds a count per count column of its table
        assert seen["sum"][g] == {v: (c,) for v, c in held.items()}
    # the aux tables kept every group's rows
    assert 20 in cold and _prefix_rows(capped.minput[0], 20) \
        == [(20, 20, 1), (20, 120, 1)]
    # after the reload and the retraction: memory is the table again
    for g in range(10):
        assert dict(capped._minput_mult[0].values((g,))) == {g: 1}
        assert _prefix_rows(capped.minput[0], g) == [(g, g, 1)]
        assert _prefix_rows(capped.distinct_tables[1], g) == [(g, g, 1)]
    assert capped._tier_part.reload_total >= 10
    uncapped = _build_max_agg(MemoryStateStore(), msgs, None)
    outs_u = asyncio.run(collect_until_n_barriers(uncapped, 4))
    assert _final_rows(outs_c) == _final_rows(outs_u)
    assert _final_rows(outs_c)[3] == (3, 3, 1, 3)


def test_agg_multiset_is_cut_with_the_tables_rows_below_a_watermark():
    """Watermark cleaning on the first group column: the groups leave
    the multisets in memory with their rows in the aux tables, and a
    late row for a retired group starts it from nothing."""
    from risingwave_tpu.stream.message import Watermark
    from risingwave_tpu.utils.metrics import STREAMING
    msgs = [_barrier(1),
            _chunk(AGG_S, [(g, v) for g in range(8) for v in (g, g + 10)]),
            _barrier(2),
            Watermark(0, DataType.INT64, 5),
            _chunk(AGG_S, [(6, 16)], ops=[Op.DELETE]),
            _barrier(3),
            _chunk(AGG_S, [(2, 1)]),
            _barrier(4)]
    ex = _build_max_agg(MemoryStateStore(), msgs, None)
    reads0 = STREAMING.agg_multiset.get(event="point_reads")
    outs = asyncio.run(collect_until_n_barriers(ex, 4))
    for mult, table in ((ex._minput_mult[0], ex.minput[0]),
                        (ex._distinct_mult[1], ex.distinct_tables[1])):
        rows = sorted(row for _pk, row in table.iter_rows())
        assert sorted(mult.rows()) == rows
        assert {r[0] for r in rows} == {2, 5, 6, 7}
        assert [r for r in rows if r[0] == 2] == [(2, 1, 1)]
    # each table's first range delete read it once, to seed its clean
    # index: eight groups of two values, in two tables (five groups of
    # two values left each)
    assert STREAMING.agg_multiset.get(event="point_reads") - reads0 == 32
    final = _final_rows(outs)
    assert final[2] == (2, 1, 1, 1) and final[6] == (6, 6, 1, 6)


def test_agg_sql_front_door_with_rw_state_tier():
    """SET state_tier_cap on the session: a GROUP BY with cardinality
    ≫ cap stays bit-identical to the uncapped run, and rw_state_tier
    accounts residency/evictions under the cap-derived bound."""
    from risingwave_tpu.frontend.session import Frontend

    async def run(cap):
        fe = Frontend(min_chunks=8)
        if cap:
            await fe.execute(f"SET state_tier_cap = {cap}")
            await fe.execute("SET state_tier_soft_limit_mb = 256")
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=6000, "
            "nexmark.min.event.gap.in.ns=100000000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW agg AS SELECT auction, "
            "count(*) AS c, max(price) AS mx FROM bid "
            "GROUP BY auction")
        await fe.step(10)
        rows = await fe.execute("SELECT * FROM agg")
        tier = await fe.execute("SELECT * FROM rw_state_tier")
        await fe.close()
        return collections.Counter(map(tuple, rows)), tier

    capped, tier = asyncio.run(run(16))
    uncapped, _ = asyncio.run(run(None))
    assert capped == uncapped
    assert len(capped) > 10 * 16          # cardinality ≫ cap
    agg_rows = [r for r in tier if r[0].startswith("HashAggExecutor")]
    assert agg_rows, tier
    _name, cap, resident, evicted, _reloads, _nb = agg_rows[0]
    assert cap == 16 and evicted > 0
    assert resident <= 16                 # post-sweep bound held


def test_tier_cap_rides_ddl_log():
    """SET state_tier_cap rides the DDL log: recovery replays the
    CREATE under the recorded cap (join state-table pk layouts depend
    on it), and the replayed session shows the value."""
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore

    obj = MemObjectStore()

    async def first():
        fe = Frontend(HummockLite(obj), min_chunks=4)
        await fe.execute("SET state_tier_cap = 8")
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=2000, "
            "nexmark.min.event.gap.in.ns=100000000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW agg AS SELECT auction, "
            "count(*) AS c FROM bid GROUP BY auction")
        await fe.step(6)
        rows = await fe.execute("SELECT * FROM agg")
        await fe.close()
        return rows

    async def recovered():
        fe = Frontend(HummockLite(obj), min_chunks=4)
        await fe.recover()
        shown = await fe.execute("SHOW state_tier_cap")
        await fe.step(4)
        rows = await fe.execute("SELECT * FROM agg")
        await fe.close()
        return shown, rows

    rows1 = asyncio.run(first())
    shown, rows2 = asyncio.run(recovered())
    assert shown == [("8",)]
    # recovery resumed the job (sources continue; counts only grow)
    c1 = dict(map(tuple, rows1))
    c2 = dict(map(tuple, rows2))
    assert set(c1) <= set(c2)
    assert all(c2[k] >= c1[k] for k in c1)


def test_alter_parallelism_agg_with_tier():
    """ALTER ... SET PARALLELISM on a tier-capped agg MV drives a full
    reschedule cycle — stop barrier, replan from the recorded id base
    UNDER THE CREATE-TIME TIER CAP (_mv_tier_caps), recovery from the
    kept state tables (evicted groups included — they are just rows
    there) — and the MV stays oracle-exact while the new executor
    re-caps residency at its next sweeps. (Parallelism 1→1 keeps the
    cycle on the single-chip kernel; the mesh path is exercised by
    test_reschedule.)"""
    from risingwave_tpu.frontend.session import Frontend

    src = ("CREATE SOURCE bid WITH (connector='nexmark', "
           "nexmark.table.type='bid', nexmark.event.num=4000, "
           "nexmark.max.chunk.size=256)")
    mv = ("CREATE MATERIALIZED VIEW v AS SELECT auction, "
          "count(*) AS c, max(price) AS m FROM bid GROUP BY auction")

    async def with_alter():
        fe = Frontend(rate_limit=4, min_chunks=4)
        await fe.execute("SET state_tier_cap = 16")
        await fe.execute(src)
        await fe.execute(mv)
        for _ in range(12):
            await fe.step()
        await fe.execute(
            "ALTER MATERIALIZED VIEW v SET PARALLELISM = 1")
        for _ in range(40):
            await fe.step()
        rows = await fe.execute("SELECT * FROM v")
        tier = await fe.execute("SELECT * FROM rw_state_tier")
        await fe.close()
        # the REPLANNED executor registered with the CREATE-time cap
        agg_rows = [r for r in tier
                    if r[0].startswith("HashAggExecutor")]
        assert agg_rows and agg_rows[0][1] == 16
        assert agg_rows[0][2] <= 16       # re-capped after recovery
        return sorted(rows)

    async def plain():
        fe = Frontend(rate_limit=4, min_chunks=4)
        await fe.execute(src)
        await fe.execute(mv)
        for _ in range(60):
            await fe.step()
        rows = await fe.execute("SELECT * FROM v")
        await fe.close()
        return sorted(rows)

    assert asyncio.run(with_alter()) == asyncio.run(plain())


# -- outer-join consumer --------------------------------------------------

def _join_outer(store, lmsgs, rmsgs, cap):
    lt = StateTable(11, L_S, [0, 2], store, dist_key_indices=[0])
    rt = StateTable(12, R_S, [0, 2], store, dist_key_indices=[0])
    return HashJoinExecutor(
        MockSource(L_S, lmsgs), MockSource(R_S, rmsgs),
        [0], [0], lt, rt, join_type=JoinType.LEFT_OUTER,
        state_cap=cap)


def test_outer_join_eviction_then_retraction_oracle():
    """LEFT OUTER: unmatched left rows evict (their padded emissions
    already downstream), then right rows match them — the reload
    recomputes degrees and the padded-row RETRACTIONS (Delete) emit
    exactly as in the uncapped run."""
    def script():
        lmsgs, rmsgs = [_barrier(1)], [_barrier(1)]
        epoch = 2
        for lo in range(0, 300, 100):
            rows = [(k, k * 2, k) for k in range(lo, lo + 100)]
            lmsgs += [_chunk(L_S, rows), _barrier(epoch)]
            rmsgs += [_barrier(epoch)]
            epoch += 1
        rrows = [(k, k * 7, 1000 + k) for k in range(0, 100)]
        rmsgs += [_chunk(R_S, rrows), _barrier(epoch)]
        lmsgs += [_barrier(epoch)]
        epoch += 1
        return lmsgs, rmsgs, epoch

    def run(cap):
        lm, rm, epoch = script()
        j = _join_outer(MemoryStateStore(), lm, rm, cap)
        outs = asyncio.run(collect_until_n_barriers(j, epoch - 1))
        got = collections.Counter()
        for m in outs:
            if isinstance(m, StreamChunk):
                for op, row in m.to_records():
                    got[(row, op in (Op.INSERT, Op.UPDATE_INSERT))] += 1
        return j, got

    jc, got_c = run(32)
    _ju, got_u = run(None)
    assert got_c == got_u
    evicted = sum(p.evicted_total for p in jc._tier_parts)
    reloads = sum(p.reload_total for p in jc._tier_parts)
    assert evicted > 0 and reloads > 0
    # the padded row for key 0 was emitted, then RETRACTED after its
    # (previously evicted) left row matched
    assert got_u[((0, 0, 0, None, None, None), True)] == 1
    assert got_u[((0, 0, 0, None, None, None), False)] == 1
    assert got_c[((0, 0, 0, None, None, None), False)] == 1


def test_outer_join_matched_then_evicted_no_spurious_flip():
    """A MATCHED left row (degree > 0) evicts ON BOTH SIDES; a second
    matching right row arrives later. The reload must recompute
    degree 1 — NOT 0 (the cross-cold-twin union reload in _reload_cold)
    — or a spurious padded Delete would emit for a padding that is not
    on. Oracle: bit-identical to the uncapped run."""
    def script():
        lmsgs, rmsgs = [_barrier(1)], [_barrier(1)]
        epoch = 2
        # key 0 matched immediately (left + right in epoch 2)
        lmsgs += [_chunk(L_S, [(0, 5, 0)]), _barrier(epoch)]
        rmsgs += [_chunk(R_S, [(0, 50, 100)]), _barrier(epoch)]
        epoch += 1
        # flood both sides so key 0 evicts everywhere
        for lo in range(1, 301, 100):
            lrows = [(k, k, k) for k in range(lo, lo + 100)]
            rrows = [(k, k, 500 + k) for k in range(lo, lo + 100)]
            lmsgs += [_chunk(L_S, lrows), _barrier(epoch)]
            rmsgs += [_chunk(R_S, rrows), _barrier(epoch)]
            epoch += 1
        # second right row for key 0
        rmsgs += [_chunk(R_S, [(0, 51, 101)]), _barrier(epoch)]
        lmsgs += [_barrier(epoch)]
        epoch += 1
        return lmsgs, rmsgs, epoch

    def run(cap):
        lm, rm, epoch = script()
        j = _join_outer(MemoryStateStore(), lm, rm, cap)
        outs = asyncio.run(collect_until_n_barriers(j, epoch - 1))
        got = collections.Counter()
        for m in outs:
            if isinstance(m, StreamChunk):
                for op, row in m.to_records():
                    got[(row, op in (Op.INSERT, Op.UPDATE_INSERT))] += 1
        return j, got

    jc, got_c = run(32)
    _ju, got_u = run(None)
    assert got_c == got_u
    assert sum(p.evicted_total for p in jc._tier_parts) > 0
    # both matched pairs present exactly once in the capped run
    assert got_c[((0, 5, 0, 0, 50, 100), True)] == 1
    assert got_c[((0, 5, 0, 0, 51, 101), True)] == 1
    # padded emissions for key 0 are BALANCED (insert count == delete
    # count): a degree-recompute bug would leave an extra Delete
    pad = (0, 5, 0, None, None, None)
    assert got_c[(pad, True)] == got_c[(pad, False)]


# -- GroupTopN consumer ---------------------------------------------------

def test_group_topn_tier_oracle_q5():
    """q5 pipeline (hop → agg → group top-n) with the tier capping
    BOTH stateful stages at a handful of resident groups: the
    materialized MV is bit-identical to the uncapped run."""
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    from risingwave_tpu.models.nexmark import (
        build_q5, drive_to_completion,
    )

    def run(cap):
        cfg = NexmarkConfig(event_num=4000, max_chunk_size=512,
                            generate_strings=False)
        p = build_q5(MemoryStateStore(), cfg, rate_limit=8,
                     min_chunks=8, tier_cap=cap)
        asyncio.run(drive_to_completion(p, {1: 4000 * 46 // 50},
                                        in_flight=2))
        return sorted(r for _pk, r in p.mv_table.iter_rows())

    assert run(8) == run(None)


def test_group_topn_cold_touch_reloads_pre_chunk_state():
    """A COLD group touched by a later chunk must reload PRE-chunk
    state: the emitted delta replaces the old top with the new one
    (and a delete against a cold group retracts, not no-ops)."""
    from risingwave_tpu.stream.executors.top_n import GroupTopNExecutor

    sch = Schema.of(g=DataType.INT64, v=DataType.INT64)
    store = MemoryStateStore()
    msgs = [_barrier(1),
            _chunk(sch, [(g, 10 + g) for g in range(8)]), _barrier(2)]
    # enough checkpoints for group 0 to age out of a 4-group cap
    for e in range(3, 8):
        msgs.append(_chunk(sch, [(7, 100 + e)]))
        msgs.append(_barrier(e))
    # touch cold group 0: a better row, then DELETE the original top
    msgs += [_chunk(sch, [(0, 99)]), _barrier(8)]
    msgs += [_chunk(sch, [(0, 10)], ops=[Op.DELETE]), _barrier(9)]
    msgs += [_chunk(sch, [(0, 99)], ops=[Op.DELETE]), _barrier(10)]

    def run(cap):
        state = StateTable(7, sch, [0, 1], store if cap else
                           MemoryStateStore())
        topn = GroupTopNExecutor(
            MockSource(sch, list(msgs)), [(1, True)], 0, 1, state,
            group_indices=[0], pk_indices=[0, 1], tier_cap=cap)
        outs = asyncio.run(collect_until_n_barriers(topn, 10))
        got = collections.Counter()
        for m in outs:
            if isinstance(m, StreamChunk):
                for op, row in m.to_records():
                    got[(row, op in (Op.INSERT,
                                     Op.UPDATE_INSERT))] += 1
        return topn, got

    tc, got_c = run(4)
    _tu, got_u = run(None)
    assert got_c == got_u, (
        sorted((k, got_c[k], got_u[k]) for k in set(got_c) | set(got_u)
               if got_c[k] != got_u[k]))
    assert tc._tier_part.evicted_total > 0
    assert tc._tier_part.reload_total > 0
    # final top for group 0: (0,99) arrived, then both rows deleted —
    # the window ends EMPTY, so the (0,99) insert must be retracted
    assert got_u[((0, 99), True)] == got_u[((0, 99), False)]


def test_group_topn_guards():
    from risingwave_tpu.stream.executors.top_n import GroupTopNExecutor

    store = MemoryStateStore()
    state = StateTable(5, AGG_S, [1], store)   # pk NOT group-prefixed
    with pytest.raises(ValueError, match="prefixed"):
        GroupTopNExecutor(MockSource(AGG_S, []), [(1, True)], 0, 1,
                          state, group_indices=[0], pk_indices=[0, 1],
                          tier_cap=4)
    with pytest.raises(ValueError, match="grouped"):
        GroupTopNExecutor(MockSource(AGG_S, []), [(1, True)], 0, 1,
                          StateTable(6, AGG_S, [0, 1], store),
                          tier_cap=4)


# -- ctl memory -----------------------------------------------------------

def test_ctl_memory_verb(tmp_path, capsys):
    """`ctl memory` dumps MemoryContext.sizes() + tier residency
    against a recovered data dir."""
    from risingwave_tpu.__main__ import main as cli_main
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    d = str(tmp_path / "rw")

    async def seed():
        fe = Frontend(HummockLite(LocalFsObjectStore(d)), min_chunks=4)
        await fe.execute("SET state_tier_cap = 8")
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=1000, "
            "nexmark.min.event.gap.in.ns=100000000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW agg AS SELECT auction, "
            "count(*) AS c FROM bid GROUP BY auction")
        await fe.step(4)
        await fe.close()

    asyncio.run(seed())
    with pytest.raises(SystemExit) as e:
        cli_main(["ctl", "--data-dir", d, "memory", "--steps", "2"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    assert "accounted host state:" in out
    assert "state tier" in out and "HashAggExecutor" in out
