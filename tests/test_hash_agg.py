"""HashAggExecutor vs host oracles: retractions, nulls, recovery, q7 shape.

Mirrors the reference's hash_agg tests (src/stream/src/executor/
hash_agg.rs test mod): scripted chunks through MockSource, change-chunk
emission asserted per barrier, state table contents asserted at commit.
"""

import asyncio
from collections import defaultdict

import numpy as np
import pytest

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.ops.hash_agg import AggKind
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.stream.executors.hash_agg import (
    AggCall, HashAggExecutor, agg_state_schema,
)
from risingwave_tpu.stream.executors.test_utils import (
    MockSource, collect_until_n_barriers,
)
from risingwave_tpu.stream.message import Barrier, BarrierKind, is_chunk

SCHEMA = Schema.of(g=DataType.INT64, v=DataType.INT64)


def barrier(n: int) -> Barrier:
    curr = Epoch.from_physical(n)
    prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
    return Barrier(EpochPair(curr, prev), BarrierKind.CHECKPOINT)


def chunk(gs, vs, ops=None) -> StreamChunk:
    return StreamChunk.from_pydict(SCHEMA, {"g": gs, "v": vs}, ops=ops)


def build(messages, agg_calls, append_only=False, store=None):
    store = store if store is not None else MemoryStateStore()
    src = MockSource(SCHEMA, messages)
    sschema, spk = agg_state_schema(SCHEMA, [0], agg_calls)
    table = StateTable(10, sschema, spk, store, dist_key_indices=[0])
    minput = {}
    if not append_only:
        from risingwave_tpu.stream.executors.hash_agg import (
            minput_state_schema,
        )
        from risingwave_tpu.ops.hash_agg import AggKind as _K
        for j, call in enumerate(agg_calls):
            if call.kind in (_K.MIN, _K.MAX):
                msch, mpk, mdk = minput_state_schema(SCHEMA, [0], call)
                minput[j] = StateTable(100 + j, msch, mpk, store,
                                       dist_key_indices=mdk)
    ex = HashAggExecutor(src, [0], agg_calls, table,
                         append_only=append_only, minput_tables=minput)
    return ex, table, store


class Oracle:
    """Reference semantics: per-group count/sum/min/max over a changelog."""

    def __init__(self):
        self.rows = defaultdict(list)   # group → multiset of values

    def apply(self, records):
        for op, (g, v) in records:
            if op.is_insert:
                self.rows[g].append(v)
            else:
                self.rows[g].remove(v)
                if not self.rows[g]:
                    del self.rows[g]

    def result(self, kinds):
        out = {}
        for g, vals in self.rows.items():
            nn = [v for v in vals if v is not None]
            row = []
            for k in kinds:
                if k == "count*":
                    row.append(len(vals))
                elif k == "count":
                    row.append(len(nn))
                elif k == "sum":
                    row.append(sum(nn) if nn else None)
                elif k == "min":
                    row.append(min(nn) if nn else None)
                elif k == "max":
                    row.append(max(nn) if nn else None)
            out[g] = tuple(row)
        return out


def materialized_view(messages):
    """Replay emitted agg chunks into a dict (group → outputs)."""
    view = {}
    for m in messages:
        if not is_chunk(m):
            continue
        for op, row in m.to_records():
            g, outs = row[0], tuple(row[1:])
            if op.is_insert:
                view[g] = outs
            else:
                assert view.get(g) == outs, \
                    f"delete of non-current row {g}: {outs} vs {view.get(g)}"
                if op == Op.DELETE:
                    del view[g]
    return view


def run_case(script, agg_calls, kinds, append_only=False, n_barriers=None):
    """Drive executor over the script; after each barrier the materialized
    emission must equal the oracle."""
    n_barriers = n_barriers or sum(
        1 for m in script if isinstance(m, Barrier))
    ex, table, store = build(script, agg_calls, append_only)
    msgs = asyncio.run(collect_until_n_barriers(ex, n_barriers))
    oracle = Oracle()
    for m in script:
        if isinstance(m, StreamChunk):
            oracle.apply(m.to_records())
    assert materialized_view(msgs) == oracle.result(kinds)
    return msgs, table


def test_count_sum_insert_only():
    script = [barrier(1),
              chunk([1, 1, 2], [10, 20, 5]),
              barrier(2),
              chunk([2, 3], [7, 100]),
              barrier(3)]
    msgs, _ = run_case(script, [AggCall(AggKind.COUNT),
                                AggCall(AggKind.SUM, 1)],
                       ["count*", "sum"])
    # first barrier emits pure inserts
    chunks = [m for m in msgs if is_chunk(m)]
    assert {r[1][0] for r in chunks[0].to_records()} == {1, 2}
    assert all(op == Op.INSERT for op, _ in chunks[0].to_records())
    # second barrier: group 2 updates (pair), group 3 inserts
    recs = chunks[1].to_records()
    by_op = defaultdict(list)
    for op, row in recs:
        by_op[op].append(row)
    assert [r[0] for r in by_op[Op.INSERT]] == [3]
    assert [r[0] for r in by_op[Op.UPDATE_DELETE]] == [2]
    assert by_op[Op.UPDATE_DELETE][0][1:] == (1, 5)
    assert by_op[Op.UPDATE_INSERT][0][1:] == (2, 12)


def test_retraction_to_zero_emits_delete():
    script = [barrier(1),
              chunk([1, 1], [10, 20]),
              barrier(2),
              chunk([1, 1], [10, 20], ops=[Op.DELETE, Op.DELETE]),
              barrier(3)]
    msgs, table = run_case(script, [AggCall(AggKind.COUNT),
                                    AggCall(AggKind.SUM, 1)],
                           ["count*", "sum"])
    chunks = [m for m in msgs if is_chunk(m)]
    assert [op for op, _ in chunks[-1].to_records()] == [Op.DELETE]
    # state table row is gone too
    assert list(table.iter_rows()) == []


def test_group_create_delete_within_epoch_emits_nothing():
    script = [barrier(1),
              chunk([9], [1]),
              chunk([9], [1], ops=[Op.DELETE]),
              barrier(2)]
    msgs, _ = run_case(script, [AggCall(AggKind.COUNT)], ["count*"])
    assert [m for m in msgs if is_chunk(m)] == []


def test_null_inputs_and_null_group_key():
    script = [barrier(1),
              StreamChunk.from_pydict(
                  SCHEMA, {"g": [1, 1, None], "v": [None, 3, 8]}),
              barrier(2)]
    msgs, _ = run_case(script,
                       [AggCall(AggKind.COUNT),          # count(*)
                        AggCall(AggKind.COUNT, 1),       # count(v)
                        AggCall(AggKind.SUM, 1)],
                       ["count*", "count", "sum"])
    view = materialized_view(msgs)
    assert view[1] == (2, 1, 3)
    assert view[None] == (1, 1, 8)


def test_max_append_only_q7_shape():
    rng = np.random.default_rng(3)
    script = [barrier(1)]
    for e in range(5):
        for _ in range(3):
            g = rng.integers(0, 6, 64).tolist()
            v = rng.integers(0, 10_000, 64).tolist()
            script.append(chunk(g, v))
        script.append(barrier(e + 2))
    msgs, _ = run_case(script,
                       [AggCall(AggKind.MAX, 1), AggCall(AggKind.COUNT)],
                       ["max", "count*"], append_only=True)


def test_retractable_max_with_deletes_matches_oracle():
    """The minput path: deletes that remove the current extreme force a
    recompute from the materialized value multiset."""
    script = [
        barrier(1),
        chunk([1, 1, 1, 2], [5, 9, 7, 3]),
        barrier(2),
        # delete the max of group 1 (9) and the only row of group 2
        chunk([1, 2], [9, 3], ops=[2, 2]),
        barrier(3),
        # delete ANOTHER max (7) and add a smaller value
        chunk([1, 1], [7, 6], ops=[2, 1]),
        barrier(4),
    ]
    run_case(script, [AggCall(AggKind.MAX, 1), AggCall(AggKind.MIN, 1),
                      AggCall(AggKind.COUNT)],
             ["max", "min", "count*"])


def test_retractable_minmax_random_oracle():
    rng = np.random.default_rng(31)
    live = []
    script = [barrier(1)]
    for e in range(2, 8):
        gs, vs, ops = [], [], []
        for _ in range(40):
            if live and rng.random() < 0.4:
                i = rng.integers(0, len(live))
                g, v = live.pop(int(i))
                gs.append(g); vs.append(v); ops.append(2)
            else:
                g = int(rng.integers(0, 5))
                v = int(rng.integers(-50, 50))
                live.append((g, v))
                gs.append(g); vs.append(v); ops.append(1)
        script.append(chunk(gs, vs, ops=ops))
        script.append(barrier(e))
    run_case(script, [AggCall(AggKind.MAX, 1), AggCall(AggKind.MIN, 1),
                      AggCall(AggKind.SUM, 1)], ["max", "min", "sum"])


def test_retractable_max_recovers_from_state():
    """Recovery mid-stream: minput + value state rebuild, then a delete
    of the pre-recovery max must still recompute correctly."""
    store = MemoryStateStore()
    ex, table, store = build(
        [barrier(1), chunk([1, 1], [10, 20]), barrier(2)],
        [AggCall(AggKind.MAX, 1)], store=store)
    asyncio.run(collect_until_n_barriers(ex, 2))
    store.seal_epoch(Epoch.from_physical(1).value, True)
    store.sync(Epoch.from_physical(1).value)
    # "restart": fresh executor over the same store; delete the max
    ex2, table2, _ = build(
        [barrier(2), chunk([1], [20], ops=[2]),
         barrier(3)],
        [AggCall(AggKind.MAX, 1)], store=store)
    msgs = asyncio.run(collect_until_n_barriers(ex2, 2))
    # recovery marked the group emitted, so the delete emits an update
    # pair retracting the stale max; the corrected value persists
    from risingwave_tpu.common.chunk import Op as _Op
    recs = [(op, row) for m in msgs if is_chunk(m)
            for op, row in m.to_records()]
    assert (_Op.UPDATE_DELETE, (1, 20)) in recs
    assert (_Op.UPDATE_INSERT, (1, 10)) in recs
    rows = {pk[0]: row for pk, row in table2.iter_rows()}
    assert rows[1][2] == 10


def test_random_stream_oracle_sum_count():
    """Randomized insert/delete stream with duplicates across chunks."""
    rng = np.random.default_rng(11)
    live = []                  # (g, v) multiset for valid deletes
    script = [barrier(1)]
    b = 2
    for _ in range(8):
        for _ in range(2):
            gs, vs, ops = [], [], []
            for _ in range(32):
                if live and rng.random() < 0.4:
                    i = rng.integers(0, len(live))
                    g, v = live.pop(int(i))
                    gs.append(g)
                    vs.append(v)
                    ops.append(Op.DELETE)
                else:
                    g = int(rng.integers(0, 10))
                    v = int(rng.integers(-50, 50))
                    live.append((g, v))
                    gs.append(g)
                    vs.append(v)
                    ops.append(Op.INSERT)
            script.append(chunk(gs, vs, ops=ops))
        script.append(barrier(b))
        b += 1
    run_case(script, [AggCall(AggKind.COUNT), AggCall(AggKind.SUM, 1),
                      AggCall(AggKind.COUNT, 1)],
             ["count*", "sum", "count"])


def test_recovery_resumes_from_state_table():
    store = MemoryStateStore()
    calls = [AggCall(AggKind.COUNT), AggCall(AggKind.SUM, 1)]
    sschema, spk = agg_state_schema(SCHEMA, [0], calls)

    script1 = [barrier(1), chunk([1, 2], [10, 20]), barrier(2)]
    src1 = MockSource(SCHEMA, script1)
    t1 = StateTable(10, sschema, spk, store, dist_key_indices=[0])
    ex1 = HashAggExecutor(src1, [0], calls, t1)
    asyncio.run(collect_until_n_barriers(ex1, 2))

    # new executor over the same store: must see groups 1,2 and emit
    # UPDATE (not INSERT) when they change
    script2 = [barrier(3), chunk([1, 3], [5, 7]), barrier(4)]
    src2 = MockSource(SCHEMA, script2)
    t2 = StateTable(10, sschema, spk, store, dist_key_indices=[0])
    ex2 = HashAggExecutor(src2, [0], calls, t2)
    msgs = asyncio.run(collect_until_n_barriers(ex2, 2))
    chunks = [m for m in msgs if is_chunk(m)]
    assert len(chunks) == 1
    ops = defaultdict(list)
    for op, row in chunks[0].to_records():
        ops[op].append(row)
    assert [r[0] for r in ops[Op.INSERT]] == [3]
    assert [r[0] for r in ops[Op.UPDATE_DELETE]] == [1]
    assert ops[Op.UPDATE_INSERT][0][1:] == (2, 15)


def test_growth_under_many_groups():
    """More groups than MIN_CAPACITY*load forces rehash mid-stream."""
    from risingwave_tpu.ops.hash_table import MIN_CAPACITY
    n = MIN_CAPACITY  # > 0.7*cap ⇒ at least one growth
    script = [barrier(1)]
    for start in range(0, n, 256):
        gs = list(range(start, start + 256))
        script.append(chunk(gs, [1] * 256))
    script.append(barrier(2))
    ex, table, _ = build(script, [AggCall(AggKind.SUM, 1)])
    msgs = asyncio.run(collect_until_n_barriers(ex, 2))
    assert ex.kernel.capacity > MIN_CAPACITY
    view = materialized_view(msgs)
    assert len(view) == n
    assert all(view[g] == (1,) for g in range(n))


def test_flush_buffer_overflow_retries():
    """flush_capacity=1 forces the header-compare/double/refetch path on
    every barrier with >1 dirty group."""
    from risingwave_tpu.ops import lanes
    from risingwave_tpu.ops.hash_agg import (
        AggKind as K, AggSpec, GroupedAggKernel,
    )
    specs = (AggSpec(K.SUM, np.dtype(np.int64)), AggSpec(K.COUNT))
    kern = GroupedAggKernel(key_width=2, specs=specs, flush_capacity=1)
    n = 64
    gk = (np.arange(n, dtype=np.int64) % 13) * 1_000_000
    hi, lo = lanes.split_i64(gk)
    vals = np.arange(n, dtype=np.int64)
    kern.apply(np.stack([hi, lo], axis=1),
               np.ones(n, dtype=np.int32), np.ones(n, dtype=bool),
               ((specs[0].encode_input(vals), np.ones(n, dtype=bool)),
                ((), None)))
    fr = kern.flush()
    assert fr.n == 13
    assert kern._flush_cap >= 13
    # decoded sums must match a host oracle despite the retry
    want = {g: int(vals[gk == g * 1_000_000].sum()) for g in range(13)}
    got = {int(lanes.merge_i64(fr.keys[r, 0:1], fr.keys[r, 1:2])[0])
           // 1_000_000: int(fr.outs[0][r]) for r in range(fr.n)}
    assert got == want
    kern.advance()
    assert not bool(np.asarray(kern.state.dirty).any())


def test_retractable_max_rejected_without_minput():
    src = MockSource(SCHEMA, [])
    sschema, spk = agg_state_schema(SCHEMA, [0], [AggCall(AggKind.MAX, 1)])
    t = StateTable(10, sschema, spk, MemoryStateStore(),
                   dist_key_indices=[0])
    with pytest.raises(ValueError):
        HashAggExecutor(src, [0], [AggCall(AggKind.MAX, 1)], t)


def test_varchar_group_keys_streaming_tpch_q1_shape():
    """Streaming TPC-H q1's GROUP BY l_returnflag, l_linestatus —
    varchar group keys through the interning KeyCodec (VERDICT r2 #5:
    previously rejected outright). Checked against a host oracle,
    including NULL keys as their own group."""
    import asyncio
    from collections import defaultdict

    from risingwave_tpu.common.types import DataType, Schema
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.executors.hash_agg import (
        AggCall, HashAggExecutor, agg_state_schema,
    )
    from risingwave_tpu.stream.executors.test_utils import (
        MockSource, collect_until_n_barriers,
    )
    from risingwave_tpu.common.chunk import StreamChunk
    from tests.test_operators import barrier

    schema = Schema.of(flag=DataType.VARCHAR, status=DataType.VARCHAR,
                       qty=DataType.INT64)
    rng = np.random.default_rng(3)
    flags = ["A", "N", "R", None]
    statuses = ["F", "O"]
    rows = [(flags[rng.integers(0, 4)], statuses[rng.integers(0, 2)],
             int(rng.integers(1, 100))) for _ in range(500)]
    script = [barrier(1)]
    for lo in range(0, 500, 100):
        part = rows[lo:lo + 100]
        script.append(StreamChunk.from_pydict(schema, {
            "flag": [r[0] for r in part],
            "status": [r[1] for r in part],
            "qty": [r[2] for r in part]}))
        script.append(barrier(lo // 100 + 2))
    store = MemoryStateStore()
    calls = [AggCall(AggKind.SUM, 2), AggCall(AggKind.COUNT)]
    sch, pk = agg_state_schema(schema, [0, 1], calls)
    table = StateTable(31, sch, pk, store)
    ex = HashAggExecutor(MockSource(schema, script), [0, 1], calls,
                         table, append_only=True)
    msgs = asyncio.run(collect_until_n_barriers(ex, 6))
    # accumulate the changelog into final rows
    final = {}
    for m in msgs:
        if hasattr(m, "to_records"):
            for op, row in m.to_records():
                if op.is_insert:
                    final[row[:2]] = row[2:]
                elif row[:2] in final and final[row[:2]] == row[2:]:
                    del final[row[:2]]
    oracle = defaultdict(lambda: [0, 0])
    for f, s, q in rows:
        oracle[(f, s)][0] += q
        oracle[(f, s)][1] += 1
    assert final == {k: (v[0], v[1]) for k, v in oracle.items()}
    # the state table persisted the string keys durably
    assert len(list(table.iter_rows())) == len(oracle)
    assert {pk[:2] for pk, _r in table.iter_rows()} == set(oracle)


def test_bytea_group_keys_with_nulls():
    """BYTEA keys intern with a type-consistent fill (str fill would
    crash np.unique's sort)."""
    from risingwave_tpu.common.types import DataType
    from risingwave_tpu.stream.executors.keys import KeyCodec

    codec = KeyCodec([DataType.BYTEA])
    vals = np.asarray([b"a", None, b"b", b"a"], dtype=object)
    lanes_ = codec.build_arrays([(vals, None)])
    assert lanes_[0].tolist() == lanes_[3].tolist()   # b"a" == b"a"
    assert lanes_[1][2] == 0                          # NULL lane
    decoded = codec.decode(lanes_)
    v, ok = decoded[0]
    assert v[0] == b"a" and v[2] == b"b" and not ok[1]


def _run_distinct_case(script, n_barriers, store=None):
    from risingwave_tpu.stream.executors.hash_agg import (
        minput_state_schema,
    )
    store = store if store is not None else MemoryStateStore()
    calls = [AggCall(AggKind.COUNT, 1, distinct=True),
             AggCall(AggKind.SUM, 1, distinct=True),
             AggCall(AggKind.COUNT, 1)]
    sschema, spk = agg_state_schema(SCHEMA, [0], calls)
    table = StateTable(50, sschema, spk, store, dist_key_indices=[0])
    dsch, dpk, ddk = minput_state_schema(SCHEMA, [0], calls[0])
    dt_tables = {1: StateTable(51, dsch, dpk, store,
                               dist_key_indices=ddk)}
    ex = HashAggExecutor(MockSource(SCHEMA, script), [0], calls, table,
                         append_only=False, distinct_tables=dt_tables)
    msgs = asyncio.run(collect_until_n_barriers(ex, n_barriers))
    return msgs, store


def test_distinct_count_sum():
    """count(DISTINCT v), sum(DISTINCT v) vs plain count(v), with
    duplicates within and across chunks (distinct.rs semantics)."""
    script = [barrier(1),
              chunk([1, 1, 1, 2], [10, 10, 20, 10]),
              barrier(2),
              chunk([1, 2], [10, 10]),     # more duplicates
              barrier(3)]
    msgs, _ = _run_distinct_case(script, 3)
    view = materialized_view(msgs)
    assert view[1] == (2, 30, 4)    # distinct {10,20}; 4 raw rows
    assert view[2] == (1, 10, 2)


def test_distinct_retraction_and_recovery():
    """Retracting one duplicate keeps the distinct count; retracting
    the last occurrence drops it. A fresh executor over the same store
    reloads the dedup multiset."""
    store = MemoryStateStore()
    script = [barrier(1),
              chunk([1, 1, 1], [10, 10, 20]),
              barrier(2),
              chunk([1], [10], ops=[Op.DELETE]),     # dup remains
              barrier(3)]
    msgs, store = _run_distinct_case(script, 3, store=store)
    view = materialized_view(msgs)
    assert view[1] == (2, 30, 2)
    # restart: new executor, retract the last 10 — distinct drops to 1
    script2 = [barrier(4),
               chunk([1], [10], ops=[Op.DELETE]),
               barrier(5)]
    _msgs2, store = _run_distinct_case(script2, 2, store=store)
    # final value state: (g, rows, cnt_distinct, sum_distinct, nn, cnt)
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.common.types import DataType, Schema
    calls = [AggCall(AggKind.COUNT, 1, distinct=True),
             AggCall(AggKind.SUM, 1, distinct=True),
             AggCall(AggKind.COUNT, 1)]
    sschema, spk = agg_state_schema(SCHEMA, [0], calls)
    t = StateTable(50, sschema, spk, store, dist_key_indices=[0])
    rows = {pk[0]: row for pk, row in _state_rows_of(t)}
    assert rows[1][2] == 1 and rows[1][3] == 20   # distinct {20}


def _state_rows_of(table):
    from risingwave_tpu.common.epoch import Epoch, EpochPair
    table.init_epoch(EpochPair(Epoch.from_physical(99),
                               Epoch.from_physical(98)))
    return list(table.iter_rows())


# -- approx_count_distinct (HyperLogLog) ----------------------------------


def test_hll_primitives_dense_accuracy():
    """Dense 2^14-register sketch (VERDICT r4 #8): error < 2% at 1M
    distinct keys (standard error 1.04/sqrt(2^14) ≈ 0.8%), and the
    small-range linear-counting correction stays tight."""
    from risingwave_tpu.ops.hash_agg import (
        HLL_M, _clz64, hll_estimate_dense, hll_lanes,
    )

    assert HLL_M >= 1 << 14
    assert _clz64(np.asarray([1], np.uint64))[0] == 63
    assert _clz64(np.asarray([0], np.uint64))[0] == 64
    assert _clz64(np.asarray([1 << 63], np.uint64))[0] == 0
    for n, tol in ((100, 0.05), (10_000, 0.03), (1_000_000, 0.02)):
        reg, rho = hll_lanes(np.arange(n, dtype=np.int64))
        arr = np.zeros(HLL_M, dtype=np.uint8)
        np.maximum.at(arr, reg, rho.astype(np.uint8))
        est = int(hll_estimate_dense(arr)[0])
        assert abs(est - n) / n < tol, (n, est)


def test_approx_count_distinct_sql_and_recovery():
    """ACD from SQL: per-group estimates near exact distincts, and the
    packed registers recover exactly across a restart."""
    import asyncio

    from risingwave_tpu.connectors.nexmark import NexmarkConfig, gen_bids
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore

    obj = MemObjectStore()
    n_events = 6000

    async def phase1():
        fe = Frontend(store=HummockLite(obj), min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            f"nexmark.table.type='bid', nexmark.event.num={n_events}, "
            "nexmark.max.chunk.size=256)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW a AS SELECT auction, "
            "approx_count_distinct(bidder) AS acd, count(*) AS c "
            "FROM bid GROUP BY auction")
        for _ in range(4):
            await fe.step()
        await fe.close()

    async def phase2():
        fe = Frontend(store=HummockLite(obj), min_chunks=4)
        await fe.recover()
        for _ in range(16):
            await fe.step()
        rows = await fe.execute("SELECT * FROM a")
        await fe.close()
        return rows

    asyncio.run(phase1())
    rows = asyncio.run(phase2())
    cfg = NexmarkConfig(event_num=n_events, max_chunk_size=256)
    bids = gen_bids(np.arange(n_events * 46 // 50, dtype=np.int64), cfg)
    import collections
    d = collections.defaultdict(set)
    c = collections.Counter()
    for a, b in zip(bids["auction"].tolist(), bids["bidder"].tolist()):
        d[a].add(b)
        c[a] += 1
    bad = 0
    for a, acd, cnt in rows:
        assert cnt == c[a]          # exact counts survive recovery
        exact = len(d[a])
        if abs(acd - exact) > max(3, 0.7 * exact):
            bad += 1
    assert len(rows) == len(d) and bad < 0.05 * len(rows)


def test_approx_count_distinct_rejects_retracting_upstream():
    import asyncio

    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=2000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW m1 AS SELECT auction, count(*) "
            "AS c FROM bid GROUP BY auction")
        with pytest.raises(Exception, match="append-only"):
            await fe.execute(
                "CREATE MATERIALIZED VIEW m2 AS SELECT c, "
                "approx_count_distinct(auction) AS n FROM m1 "
                "GROUP BY c")
        await fe.close()

    asyncio.run(run())


# -- string_agg / array_agg (host-path aggs) ------------------------------


def test_string_agg_array_agg_sql_oracle_and_retraction():
    """Host aggs over the value multiset, from SQL, incl. a RETRACTING
    upstream (GROUP BY over an updating MV): the composed string/list
    must drop retracted members (VERDICT r3 #9: string_agg/array_agg
    were wholly missing)."""
    import asyncio

    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=4000, "
            "nexmark.max.chunk.size=256)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW m1 AS SELECT auction, count(*) "
            "AS c FROM bid GROUP BY auction")
        # string_agg over a RETRACTING upstream: auctions move between
        # c-groups as counts grow
        await fe.execute(
            "CREATE MATERIALIZED VIEW m2 AS SELECT c, "
            "array_agg(auction) AS members FROM m1 GROUP BY c")
        for _ in range(20):
            await fe.step()
        m1 = await fe.execute("SELECT * FROM m1")
        m2 = await fe.execute("SELECT * FROM m2")
        await fe.close()
        return m1, m2

    m1, m2 = asyncio.run(run())
    want = {}
    for a, c in m1:
        want.setdefault(c, []).append(a)
    got = {c: members for c, members in m2}
    assert got == {c: tuple(sorted(v)) for c, v in want.items()}


def test_string_agg_recovery():
    import asyncio

    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore

    obj = MemObjectStore()

    async def phase1():
        fe = Frontend(store=HummockLite(obj), min_chunks=2)
        await fe.execute(
            "CREATE SOURCE p WITH (connector='nexmark', "
            "nexmark.table.type='person', nexmark.event.num=4000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW s AS SELECT state, "
            "string_agg(city, '|') AS cities FROM p GROUP BY state")
        for _ in range(3):
            await fe.step()
        await fe.close()

    async def phase2():
        fe = Frontend(store=HummockLite(obj), min_chunks=2)
        await fe.recover()
        for _ in range(12):
            await fe.step()
        rows = await fe.execute("SELECT * FROM s")
        await fe.close()
        return rows

    asyncio.run(phase1())
    rows = asyncio.run(phase2())
    from risingwave_tpu.connectors.nexmark import (
        NexmarkConfig, gen_persons,
    )
    cfg = NexmarkConfig(table_type="person", event_num=4000)
    ps = gen_persons(np.arange(4000 // 50, dtype=np.int64), cfg)
    want = {}
    for st, city in zip(ps["state"].tolist(), ps["city"].tolist()):
        want.setdefault(st, []).append(city)
    assert {st: c for st, c in rows} == {
        st: "|".join(sorted(v)) for st, v in want.items()}


def test_approx_count_distinct_varchar_group_key():
    """ACD grouped by an interned VARCHAR column — the flush path must
    handle decoded (plain python str) group keys."""
    import asyncio

    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend(min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=3000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW a AS SELECT channel, "
            "approx_count_distinct(bidder) AS acd FROM bid "
            "GROUP BY channel")
        for _ in range(6):
            await fe.step()
        rows = await fe.execute("SELECT * FROM a")
        await fe.close()
        return rows

    rows = asyncio.run(run())
    from risingwave_tpu.connectors.nexmark import NexmarkConfig, gen_bids
    cfg = NexmarkConfig(event_num=3000)
    bids = gen_bids(np.arange(3000 * 46 // 50, dtype=np.int64), cfg)
    import collections
    d = collections.defaultdict(set)
    for ch, b in zip(bids["channel"], bids["bidder"].tolist()):
        d[ch].add(b)
    got = {ch: acd for ch, acd in rows}
    assert set(got) == set(d)
    for ch, exact in ((k, len(v)) for k, v in d.items()):
        assert abs(got[ch] - exact) <= max(2, 0.05 * exact), \
            (ch, got[ch], exact)


# -- the value multisets in memory (PR 36) ----------------------------------
#
# A retractable MIN/MAX, a string_agg / array_agg and a DISTINCT column
# keep their value multiset in the executor (value_multiset.py) and write
# it through once a barrier. The loops they replaced read the table for
# every changed count and for every retracted group; they stay here as
# the plain reference.

MS_SCHEMA = Schema.of(g=DataType.INT64, v=DataType.INT64,
                      s=DataType.VARCHAR)


def _ms_calls():
    return [AggCall(AggKind.MAX, 1), AggCall(AggKind.MIN, 1),
            AggCall(AggKind.STRING_AGG, 2, delimiter="|"),
            AggCall(AggKind.COUNT), AggCall(AggKind.ARRAY_AGG, 1),
            AggCall(AggKind.SUM, 1, distinct=True)]


class TableLoopAgg(HashAggExecutor):
    """The loops of the parent commit: the multiset is read back from
    its table, a point read a changed count and a prefix scan a group."""

    @staticmethod
    def _write_multiset_pending(pending, tables, mults):
        for j, deltas in pending.items():
            table = tables[j]
            for (group, value), d in deltas.items():
                if d == 0:
                    continue
                key = group + (value,)
                cur = table.get_row(key)
                cnt = (0 if cur is None else cur[-1]) + d
                row = key + (cnt,)
                if cur is None:
                    assert cnt > 0, f"retract of unseen value {key}"
                    table.insert(row)
                elif cnt == 0:
                    table.delete(cur)
                else:
                    table.update(cur, row)
        pending.clear()

    def _write_distinct_pending(self):
        """A dedup table's old row is read back, a point read a moved
        pair; its new counts are the gating's."""
        for col, table in self.distinct_tables.items():
            mult = self._distinct_mult[col]
            for group, value in self._distinct_pending.pop(col, {}):
                key = group + (value,)
                cur = table.get_row(key)
                new = mult.count(group, value)
                if cur is None:
                    assert any(new), f"retract of unseen value {key}"
                    table.insert(key + new)
                elif not any(new):
                    table.delete(cur)
                elif tuple(cur) != key + new:
                    table.update(cur, key + new)

    @staticmethod
    def _group(gk, r):
        return tuple(
            None if not ok[r]
            else (vals[r].item() if hasattr(vals[r], "item") else vals[r])
            for vals, ok in gk)

    def _recompute_extremes(self, fr, gk):
        from risingwave_tpu.ops.hash_agg import HOST_AGG_KINDS
        need = [r for r in range(fr.n)
                if tuple(fr.keys[r].tolist()) in self._deleted_lanes]
        if not need:
            return
        for r in need:
            group = self._group(gk, r)
            for j, table in self.minput.items():
                if self.specs[j].kind in HOST_AGG_KINDS:
                    continue
                is_max = self.specs[j].kind == AggKind.MAX
                best = None
                for _pk, row in table.iter_prefix(group):
                    v = row[-2]
                    if best is None or (v > best if is_max else v < best):
                        best = v
                nn = fr.nns[j][r]
                if nn == 0 or best is None:
                    fr.nulls[j][r] = True
                    fr.nns[j][r] = 0
                else:
                    fr.outs[j][r] = best
                    fr.nulls[j][r] = False
        decoded = [
            (fr.outs[j], fr.nns[j])
            if j in self.minput
            and self.specs[j].kind not in HOST_AGG_KINDS else None
            for j in range(len(self.specs))]
        self.kernel.patch_accs(decoded, raw_accs=fr.raw_accs)

    def _host_agg_outputs(self, fr, gk):
        out = {}
        for j in self._host_calls:
            call = self.agg_calls[j]
            table = self.minput[j]
            vals_col = np.empty(fr.n, dtype=object)
            nulls_col = np.zeros(fr.n, dtype=bool)
            for r in range(fr.n):
                items = []
                for _pk, row in table.iter_prefix(self._group(gk, r)):
                    items.extend([row[-2]] * int(row[-1]))
                if not items:
                    nulls_col[r] = True
                elif call.kind == AggKind.STRING_AGG:
                    vals_col[r] = call.delimiter.join(
                        str(v) for v in items if v is not None)
                else:
                    vals_col[r] = tuple(items)
            out[j] = (vals_col, nulls_col)
        return out


def _ms_build(cls, messages, store, calls=None, schema=MS_SCHEMA):
    from risingwave_tpu.stream.executors.hash_agg import agg_aux_tables
    calls = calls or _ms_calls()
    sschema, spk = agg_state_schema(schema, [0], calls)
    table = StateTable(10, sschema, spk, store, dist_key_indices=[0])
    distinct, minput = agg_aux_tables(
        schema, [0], calls, False, store,
        dedup_table_id=lambda col: 200 + col,
        minput_table_id=lambda j: 100 + j)
    return cls(MockSource(schema, messages), [0], calls, table,
               minput_tables=minput, distinct_tables=distinct)


def _ms_script(seed, n_epochs=7, rows=48):
    """Inserts and retractions over a small value domain (a count goes
    to zero and comes back), NULL values and a NULL group."""
    rng = np.random.default_rng(seed)
    live = []
    script = [barrier(1)]
    for e in range(2, 2 + n_epochs):
        gs, vs, ss, ops = [], [], [], []
        for _ in range(rows):
            if live and rng.random() < 0.45:
                g, v, s = live.pop(int(rng.integers(0, len(live))))
                ops.append(2)
            else:
                g = [None, 1, 2, 3][int(rng.integers(0, 4))]
                v = [None, 0, 1, 2, 3, -7][int(rng.integers(0, 6))]
                s = [None, "a", "b", "é", ""][int(rng.integers(0, 5))]
                live.append((g, v, s))
                ops.append(1)
            gs.append(g), vs.append(v), ss.append(s)
        script.append(StreamChunk.from_pydict(
            MS_SCHEMA, {"g": gs, "v": vs, "s": ss}, ops=ops))
        script.append(barrier(e))
    return script


def _table_rows(table):
    return sorted((row for _pk, row in table.iter_rows()), key=repr)


def _mirrors(ex):
    return [(ex._minput_mult[j], t) for j, t in ex.minput.items()] + [
        (ex._distinct_mult[c], t) for c, t in ex.distinct_tables.items()]


def _drive_checking_mirrors(ex):
    """Run to the script's end; at every barrier each multiset in memory
    must be a scan of its table."""
    async def run():
        out = []
        async for msg in ex.execute():
            out.append(msg)
            if isinstance(msg, Barrier):
                for mult, t in _mirrors(ex):
                    rows = _table_rows(t)
                    assert sorted(mult.rows(), key=repr) == rows
                    assert len(mult) == len(rows)
        return out
    return asyncio.run(run())


def _records(msgs):
    return [m.to_records() if is_chunk(m) else "barrier" for m in msgs
            if is_chunk(m) or isinstance(m, Barrier)]


@pytest.mark.parametrize("seed", [3, 36, 360])
def test_multiset_in_memory_is_the_table_and_emits_the_table_loops_chunks(
        seed):
    script = _ms_script(seed)
    ex = _ms_build(HashAggExecutor, script, MemoryStateStore())
    assert len(_mirrors(ex)) == 5         # MAX, MIN, two host aggs, v
    got = _drive_checking_mirrors(ex)
    ref = _ms_build(TableLoopAgg, script, MemoryStateStore())
    want = asyncio.run(collect_until_n_barriers(ref, 8))
    assert _records(got) == _records(want)
    assert any(r != "barrier" for r in _records(got))
    for t, t_ref in zip(
            [ex.table, *ex.minput.values(), *ex.distinct_tables.values()],
            [ref.table, *ref.minput.values(),
             *ref.distinct_tables.values()]):
        assert _table_rows(t) == _table_rows(t_ref)


def test_multiset_restart_rebuilds_the_mirror_and_emits_the_same():
    """A restart on the same store fills the multisets from the tables;
    the next barrier's chunk is the one an executor that never stopped
    emits."""
    script = _ms_script(11, n_epochs=5)
    head, tail = script[:-2], script[-3:]       # tail opens on a barrier
    store = MemoryStateStore()
    first = _ms_build(HashAggExecutor, head, store)
    _drive_checking_mirrors(first)
    for e in range(1, 5):
        store.seal_epoch(Epoch.from_physical(e).value, True)
    store.sync(Epoch.from_physical(4).value)
    second = _ms_build(HashAggExecutor, tail, store)
    got = _drive_checking_mirrors(second)
    whole = _ms_build(HashAggExecutor, script, MemoryStateStore())
    want = _drive_checking_mirrors(whole)
    assert _records(got)[1:] == _records(want)[-2:]
    assert _records(got)[1] != "barrier"
    for (m1, _t1), (m2, _t2) in zip(_mirrors(second), _mirrors(whole)):
        assert sorted(m1.rows(), key=repr) == sorted(m2.rows(), key=repr)


def test_multiset_restart_reads_the_mirror_from_the_table_at_init():
    script = _ms_script(12, n_epochs=3)
    store = MemoryStateStore()
    first = _ms_build(HashAggExecutor, script, store)
    _drive_checking_mirrors(first)
    for e in range(1, 4):
        store.seal_epoch(Epoch.from_physical(e).value, True)
    store.sync(Epoch.from_physical(3).value)
    second = _ms_build(HashAggExecutor, [barrier(4)], store)
    asyncio.run(collect_until_n_barriers(second, 1))
    for (m1, _t1), (m2, _t2) in zip(_mirrors(first), _mirrors(second)):
        assert len(m1) and sorted(m1.rows(), key=repr) \
            == sorted(m2.rows(), key=repr)


def test_retract_of_an_unseen_value_still_raises():
    script = [barrier(1), chunk([1, 1], [5, 9]), barrier(2),
              chunk([1], [7], ops=[2]), barrier(3)]
    ex, _table, _store = build(script, [AggCall(AggKind.MAX, 1)])
    with pytest.raises(AssertionError, match="retract of unseen value"):
        asyncio.run(collect_until_n_barriers(ex, 3))


def test_a_nan_is_one_value_of_the_multiset():
    """No two NaN objects are equal; the table's key for them is one."""
    schema = Schema.of(g=DataType.INT64, v=DataType.FLOAT64)
    nan = float("nan")

    def fchunk(gs, vs, ops=None):
        return StreamChunk.from_pydict(schema, {"g": gs, "v": vs}, ops=ops)

    script = [barrier(1), fchunk([1, 1, 1], [nan, 2.5, nan]), barrier(2),
              fchunk([1, 1], [nan, 2.5], ops=[2, 2]), barrier(3),
              fchunk([1], [nan], ops=[2]), barrier(4)]
    calls = [AggCall(AggKind.COUNT), AggCall(AggKind.MAX, 1)]
    ex = _ms_build(HashAggExecutor, script, MemoryStateStore(),
                   calls=calls, schema=schema)
    msgs = _drive_checking_mirrors(ex)
    assert len(ex._minput_mult[1]) == 0
    last = [m for m in msgs if is_chunk(m)][-1].to_records()
    assert [op for op, _row in last] == [Op.DELETE]


def test_multiset_counters_in_a_steady_run():
    """Past the init barrier no multiset table is read: `point_reads`
    stays 0, and `rows_written` is the rows the three batch calls took."""
    import collections

    from risingwave_tpu.utils.metrics import STREAMING

    def counts():
        return {e: STREAMING.agg_multiset.get(event=e)
                for e in ("point_reads", "rows_written", "extreme_scans",
                          "values_scanned")}

    ex = _ms_build(HashAggExecutor, _ms_script(5), MemoryStateStore())
    took = collections.Counter()

    def refuse(*_a, **_k):
        raise AssertionError("a multiset table was read")

    for _mult, t in _mirrors(ex):
        for name in ("insert_rows", "update_rows", "delete_rows"):
            def counted(rows, *a, _real=getattr(t, name), _name=name):
                took[_name] += len(rows)
                return _real(rows, *a)
            setattr(t, name, counted)
        for name in ("insert", "update", "delete"):
            setattr(t, name, refuse)     # no single-row write either
        t.get_row = t.iter_prefix = refuse
    before = counts()
    asyncio.run(collect_until_n_barriers(ex, 8))
    moved = {e: v - before[e] for e, v in counts().items()}
    assert moved["point_reads"] == 0
    assert moved["rows_written"] == sum(took.values()) > 0
    assert all(took[n] for n in ("insert_rows", "update_rows",
                                 "delete_rows")), took
    # MAX and MIN rescan the same retracted groups, a few values each
    assert moved["extreme_scans"] > 0 and moved["extreme_scans"] % 2 == 0
    assert moved["values_scanned"] >= moved["extreme_scans"]
