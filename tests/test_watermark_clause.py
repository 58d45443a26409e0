"""The `WATERMARK FOR` clause of `CREATE SOURCE` (ISSUE 37): what the
parser takes and refuses, what the binder holds the clause and a
declared column list to, that the clause and the older
`watermark.column` / `watermark.delay` options are one catalog entry and
plan one `WatermarkFilterExecutor`; which group column leads an
aggregate's state key; and that none of it leaks into a plan that
declares no watermark: the five configurations the benchmark had
before this clause deploy the executor chains they deployed then, with
every aggregate's state keyed in the written order.
"""

import asyncio
import json
import os
import sys

import numpy as np
import pytest

from risingwave_tpu.frontend import ast
from risingwave_tpu.frontend.binder import BindError
from risingwave_tpu.frontend.parser import ParseError, parse_many

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

BID_COLUMNS = ("auction BIGINT, bidder BIGINT, price BIGINT, "
               "channel VARCHAR, url VARCHAR, date_time TIMESTAMP, "
               "extra VARCHAR")
NEXMARK = "WITH (connector='nexmark', nexmark.table.type='bid')"
CLAUSE = ("WATERMARK FOR date_time AS date_time - INTERVAL '4' SECOND")


def _parse(sql: str):
    [(_text, stmt)] = parse_many(sql)
    return stmt


@pytest.mark.parametrize("clause, want", [
    (CLAUSE, ("date_time", 4_000_000)),
    ("WATERMARK FOR date_time AS date_time", ("date_time", 0)),
    ("watermark for Date_Time as date_time - interval '500' "
     "milliseconds", ("date_time", 500_000)),
])
def test_the_parser_takes_the_clause(clause, want):
    stmt = _parse(f"CREATE SOURCE bid ({BID_COLUMNS}, {clause}) {NEXMARK}")
    assert isinstance(stmt, ast.CreateSource)
    assert stmt.watermark == want
    assert [c for c, _t in stmt.columns] == [
        "auction", "bidder", "price", "channel", "url", "date_time",
        "extra"]


def test_the_clause_may_stand_anywhere_in_the_list_and_alone():
    stmt = _parse(f"CREATE SOURCE bid ({CLAUSE}, {BID_COLUMNS}) {NEXMARK}")
    assert stmt.watermark == ("date_time", 4_000_000)
    assert len(stmt.columns) == 7
    # a list that declares nothing but the watermark: the connector's
    # own schema, as without a list
    stmt = _parse(f"CREATE SOURCE bid ({CLAUSE}) {NEXMARK}")
    assert stmt.watermark == ("date_time", 4_000_000)
    assert stmt.columns is None


def test_a_column_may_still_be_called_watermark():
    stmt = _parse("CREATE SOURCE t (watermark BIGINT, ts TIMESTAMP) "
                  "WITH (connector='filelog', path='/x', topic='t')")
    assert stmt.watermark is None
    assert stmt.columns == [("watermark", "bigint"), ("ts", "timestamp")]


@pytest.mark.parametrize("clause", [
    "WATERMARK FOR date_time AS price - INTERVAL '4' SECOND",
    "WATERMARK FOR date_time AS date_time + INTERVAL '4' SECOND",
    "WATERMARK FOR date_time AS date_time - 4",
    "WATERMARK FOR date_time AS bid.date_time",
    "WATERMARK FOR date_time",
    f"{CLAUSE}, {CLAUSE}",
])
def test_the_parser_refuses(clause):
    with pytest.raises(ParseError):
        _parse(f"CREATE SOURCE bid ({BID_COLUMNS}, {clause}) {NEXMARK}")


def _frontend_run(*statements):
    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend()
        try:
            for sql in statements:
                await fe.execute(sql)
            return fe.catalog
        finally:
            await fe.close()
    return asyncio.run(run())


@pytest.mark.parametrize("columns, clause, message", [
    (BID_COLUMNS, "WATERMARK FOR price AS price", "must be a timestamp"),
    (BID_COLUMNS, "WATERMARK FOR seen AS seen", "no such column"),
    (BID_COLUMNS.replace("price BIGINT", "price INT"), CLAUSE,
     "must be the connector's own"),
    (BID_COLUMNS.replace("bidder", "buyer"), CLAUSE,
     "must be the connector's own"),
    (BID_COLUMNS.replace(", extra VARCHAR", ""), CLAUSE,
     "must be the connector's own"),
])
def test_the_binder_refuses(columns, clause, message):
    with pytest.raises(BindError, match=message):
        _frontend_run(f"CREATE SOURCE bid ({columns}, {clause}) {NEXMARK}")


def test_clause_and_option_together_are_refused():
    with pytest.raises(BindError, match="not both"):
        _frontend_run(
            f"CREATE SOURCE bid ({BID_COLUMNS}, {CLAUSE}) WITH ("
            "connector='nexmark', nexmark.table.type='bid', "
            "watermark.column='date_time')")


def test_the_options_are_the_clause_s_older_spelling():
    """One catalog entry, one executor, whichever way it was written."""
    from risingwave_tpu.frontend.planner import StreamPlanner
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.executors.watermark_filter import (
        WatermarkFilterExecutor,
    )

    catalog = _frontend_run(
        f"CREATE SOURCE a ({BID_COLUMNS}, {CLAUSE}) {NEXMARK}",
        "CREATE SOURCE b WITH (connector='nexmark', "
        "nexmark.table.type='bid', watermark.column='date_time', "
        "watermark.delay='4 seconds')",
        "CREATE SOURCE c WITH (connector='nexmark', "
        "nexmark.table.type='bid')")
    assert catalog.sources["a"].watermark == ("date_time", 4_000_000)
    assert catalog.sources["b"].watermark == ("date_time", 4_000_000)
    assert catalog.sources["c"].watermark is None
    assert [f.name for f in catalog.sources["a"].schema] == \
        [f.name for f in catalog.sources["c"].schema]

    def filters(source):
        sel = _parse(f"CREATE MATERIALIZED VIEW v AS SELECT auction, "
                     f"count(*) FROM {source} GROUP BY auction").select
        plan = StreamPlanner(catalog, MemoryStateStore(),
                             LocalBarrierManager(), definition="").plan(
            "v", sel, actor_id=1)
        found, ex = [], plan.consumer
        while ex is not None:
            if isinstance(ex, WatermarkFilterExecutor):
                found.append((ex.time_col, ex.delay))
            ex = getattr(ex, "input", None)
        return found

    assert filters("a") == filters("b") == [(5, 4_000_000)]
    assert filters("c") == []


# -- the state key's leading column -------------------------------------

def _plan(catalog, sql: str):
    from risingwave_tpu.frontend.planner import StreamPlanner
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    sel = _parse(f"CREATE MATERIALIZED VIEW v AS {sql}").select
    return StreamPlanner(catalog, MemoryStateStore(),
                         LocalBarrierManager(), definition="").plan(
        "v", sel, actor_id=1).consumer


def _aggs(ex):
    from risingwave_tpu.stream.executor import executor_children
    from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
    out = [ex] if isinstance(ex, HashAggExecutor) else []
    for _attr, _i, child in executor_children(ex):
        out += _aggs(child)
    return out


HOP = "HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)"


@pytest.mark.parametrize("group_by, lead", [
    ("window_start, auction", 0),
    ("auction, window_start", 1),
    ("auction, bidder, window_start", 2),
    ("auction", 0),                   # no watermark column in the key
])
def test_the_watermark_column_leads_the_state_key(group_by, lead):
    catalog = _frontend_run(
        f"CREATE SOURCE bid ({BID_COLUMNS}, {CLAUSE}) {NEXMARK}")
    (agg,) = _aggs(_plan(
        catalog, f"SELECT count(*), max(price), count(DISTINCT bidder) "
                 f"FROM {HOP} GROUP BY {group_by}"))
    g = len(agg.group_indices)
    order = [lead] + [i for i in range(g) if i != lead]
    assert agg.key_lead == lead
    assert agg.table.pk_indices == order
    # the value multisets (the HOP's stream is append-only, so only
    # the DISTINCT column has one) follow the value state's order
    (distinct,) = agg.distinct_tables.values()
    assert distinct.pk_indices == order + [g]
    # the output, and so the view's key, is in the written order
    assert [f.name for f in agg.schema][:g] == [f"_g{i}" for i in range(g)]
    assert agg.pk_indices == list(range(g))
    assert agg.plan_note is None


def test_without_a_watermark_the_key_is_in_the_written_order():
    catalog = _frontend_run(f"CREATE SOURCE bid {NEXMARK}")
    (agg,) = _aggs(_plan(
        catalog, f"SELECT count(*) FROM {HOP} "
                 "GROUP BY auction, window_start"))
    assert agg.key_lead == 0 and agg.table.pk_indices == [0, 1]


def test_a_watermark_through_a_derived_table_and_a_join_is_followed():
    """`watermark_columns` follows what each executor forwards: the
    MAX over a derived counting aggregate, and an aggregate over a
    join on the watermark column, both get it in front."""
    from risingwave_tpu.frontend.planner import watermark_columns
    catalog = _frontend_run(
        f"CREATE SOURCE bid ({BID_COLUMNS}, {CLAUSE}) {NEXMARK}")
    counts = (f"SELECT auction, count(*) AS num, window_start AS ws "
              f"FROM {HOP} GROUP BY auction, window_start")
    top = _plan(catalog, f"SELECT c.auction, max(c.num) FROM ({counts}) "
                         "AS c GROUP BY c.auction, c.ws")
    outer, inner = _aggs(top)
    assert inner.key_lead == 1 and outer.key_lead == 1
    joined = _plan(
        catalog, f"SELECT a.num, a.ws, count(*) FROM ({counts}) AS a "
                 f"JOIN ({counts.replace('ws', 'ws2')}) AS b "
                 "ON a.ws = b.ws2 GROUP BY a.num, a.ws")
    over_join = _aggs(joined)[0]
    assert over_join.key_lead == 1
    assert watermark_columns(over_join) == {1}


def test_a_watermark_no_key_type_can_order_is_said_at_plan_time():
    """A float cannot lead a cleaned key (`cleanable_type`). No SQL
    gives a float column a watermark today, so the planner's rule is
    held to its own words: the note names the column and its type."""
    from risingwave_tpu.common.types import DataType
    from risingwave_tpu.frontend.planner import explain_tree
    from risingwave_tpu.stream.executors.hash_agg import cleanable_type
    assert cleanable_type(DataType.TIMESTAMP)
    assert cleanable_type(DataType.INT64)
    assert not cleanable_type(DataType.FLOAT64)
    catalog = _frontend_run(
        f"CREATE SOURCE bid ({BID_COLUMNS}, {CLAUSE}) {NEXMARK}")
    top = _plan(catalog, f"SELECT count(*) FROM {HOP} "
                         "GROUP BY window_start")
    (agg,) = _aggs(top)
    assert agg.plan_note is None
    assert not any("--" in line for line in explain_tree(top))
    agg.plan_note = "state not cleaned: the watermark is on group key 0"
    assert any(line.strip().startswith("HashAggExecutor") and
               line.endswith("-- " + agg.plan_note)
               for line in explain_tree(top))


# -- the five accepted configurations plan as they did -------------------

# `walk_executors` of each deployed view, taken on the commit before
# this clause existed (037078e)
GOLDEN = {
    "nexmark-q7": [
        "/ MaterializeExecutor",
        "/input ProjectExecutor",
        "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor→RowIdGenExecutor; R:ProjectExecutor→join]",
        "/input/input/left_in SourceExecutor",
        "/input/input/right_in HashAggExecutor(actor=0)[fused:ProjectExecutor]",
        "/input/input/right_in/input SourceExecutor"
    ],
    "nexmark-q8": [
        "/ MaterializeExecutor",
        "/input ProjectExecutor",
        "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor; R:ProjectExecutor→join]",
        "/input/input/left_in HashAggExecutor(actor=0)",
        "/input/input/left_in/input CoalesceExecutor",
        "/input/input/left_in/input/input ProjectExecutor",
        "/input/input/left_in/input/input/input SourceExecutor",
        "/input/input/right_in HashAggExecutor(actor=0)[fused:ProjectExecutor]",
        "/input/input/right_in/input SourceExecutor"
    ],
    "nexmark-q8-mesh4": [
        "/ MaterializeExecutor",
        "/input ProjectExecutor",
        "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor; R:ProjectExecutor→join]",
        "/input/input/left_in HashAggExecutor(actor=0)",
        "/input/input/left_in/input CoalesceExecutor",
        "/input/input/left_in/input/input ProjectExecutor",
        "/input/input/left_in/input/input/input SourceExecutor",
        "/input/input/right_in HashAggExecutor(actor=0)[fused:ProjectExecutor]",
        "/input/input/right_in/input SourceExecutor"
    ],
    "nexmark-q4": [
        "/ MaterializeExecutor",
        "/input ProjectExecutor",
        "/input/input HashAggExecutor(actor=0)[fused:ProjectExecutor]",
        "/input/input/input HashAggExecutor(actor=0)[fused:ProjectExecutor]",
        "/input/input/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor→RowIdGenExecutor; R:ProjectExecutor→RowIdGenExecutor→join]",
        "/input/input/input/input/left_in SourceExecutor",
        "/input/input/input/input/right_in SourceExecutor"
    ],
    "nexmark-q5": [
        "/ MaterializeExecutor",
        "/input ProjectExecutor",
        "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor; R:ProjectExecutor→join]",
        "/input/input/left_in HashAggExecutor(actor=0)[fused:HopWindowExecutor→ProjectExecutor]",
        "/input/input/left_in/input SourceExecutor",
        "/input/input/right_in HashAggExecutor(actor=0)",
        "/input/input/right_in/input CoalesceExecutor",
        "/input/input/right_in/input/input ProjectExecutor",
        "/input/input/right_in/input/input/input HashAggExecutor(actor=0)[fused:HopWindowExecutor→ProjectExecutor]",
        "/input/input/right_in/input/input/input/input SourceExecutor"
    ]
}
PARALLELISM = {"nexmark-q8-mesh4": 4}


@pytest.mark.parametrize("name", list(GOLDEN))
def test_a_configuration_without_a_watermark_plans_as_before(name):
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.stream.executors.watermark_filter import (
        WatermarkFilterExecutor,
    )
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    assert "WATERMARK" not in " ".join(config["ddl"]).upper()

    async def deploy():
        par = PARALLELISM.get(name, 1)
        fe = Frontend(parallelism=par) if par > 1 else Frontend()
        try:
            for stmt in config["sets"]:
                await fe.execute(stmt)
            for ddl in config["ddl"]:
                await fe.execute(ddl.format(seed=7))
            assert all(s.watermark is None
                       for s in fe.catalog.sources.values())
            actor = fe.actors[fe.catalog.mvs[config["view"]].actor_id]
            chain, keys = [], []
            for path, ex in run.walk_executors(actor.consumer):
                ex = getattr(ex, "inner", ex)
                chain.append(f"{path or '/'} {ex.identity}")
                assert not isinstance(ex, WatermarkFilterExecutor)
                if isinstance(ex, HashAggExecutor):
                    g = len(ex.group_indices)
                    keys.append((ex.key_lead, ex.table.pk_indices,
                                 list(range(g))))
                    for t in (*ex.minput.values(),
                              *ex.distinct_tables.values()):
                        assert t.pk_indices == list(range(g + 1))
            return chain, keys
        finally:
            await fe.close()

    chain, keys = asyncio.run(deploy())
    assert chain == GOLDEN[name]
    assert keys and all(lead == 0 and pk == written
                        for lead, pk, written in keys)


# -- the six accepted configurations plan as they did (ISSUE 41) ----------

# `walk_executors` of each deployed view and, per aggregate, its calls
# (kind, input column, DISTINCT) and the id, columns and key of its value
# state table and of every materialized-input and dedup table, taken on
# the commit before an `AggCall` could carry a filter and a dedup table
# more than one count (2ecb2be). None of the six has a DISTINCT or a
# filtered call, so nothing of that may show in their plans.
GOLDEN_SIX = {
    "nexmark-q7": {
        "chain": [
            "/ MaterializeExecutor",
            "/input ProjectExecutor",
            "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor→RowIdGenExecutor; R:ProjectExecutor→join]",
            "/input/input/left_in SourceExecutor",
            "/input/input/right_in HashAggExecutor(actor=0)[fused:ProjectExecutor]",
            "/input/input/right_in/input SourceExecutor",
        ],
        "aggs": [
            {"calls": [['max', 1, False]],
             "state": [6, ['_g0:TIMESTAMP', '_group_rows:INT64', '_acc0:INT64', '_acc1:INT64'], [0]],
             "minput": {}, "distinct": {}},
        ]},
    "nexmark-q8": {
        "chain": [
            "/ MaterializeExecutor",
            "/input ProjectExecutor",
            "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor; R:ProjectExecutor→join]",
            "/input/input/left_in HashAggExecutor(actor=0)",
            "/input/input/left_in/input CoalesceExecutor",
            "/input/input/left_in/input/input ProjectExecutor",
            "/input/input/left_in/input/input/input SourceExecutor",
            "/input/input/right_in HashAggExecutor(actor=0)[fused:ProjectExecutor]",
            "/input/input/right_in/input SourceExecutor",
        ],
        "aggs": [
            {"calls": [],
             "state": [5, ['_g0:INT64', '_g1:VARCHAR', '_g2:TIMESTAMP', '_group_rows:INT64'], [0, 1, 2]],
             "minput": {}, "distinct": {}},
            {"calls": [],
             "state": [8, ['_g0:INT64', '_g1:TIMESTAMP', '_group_rows:INT64'], [0, 1]],
             "minput": {}, "distinct": {}},
        ]},
    "nexmark-q8-mesh4": {
        "chain": [
            "/ MaterializeExecutor",
            "/input ProjectExecutor",
            "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor; R:ProjectExecutor→join]",
            "/input/input/left_in HashAggExecutor(actor=0)",
            "/input/input/left_in/input CoalesceExecutor",
            "/input/input/left_in/input/input ProjectExecutor",
            "/input/input/left_in/input/input/input SourceExecutor",
            "/input/input/right_in HashAggExecutor(actor=0)[fused:ProjectExecutor]",
            "/input/input/right_in/input SourceExecutor",
        ],
        "aggs": [
            {"calls": [],
             "state": [5, ['_g0:INT64', '_g1:VARCHAR', '_g2:TIMESTAMP', '_group_rows:INT64'], [0, 1, 2]],
             "minput": {}, "distinct": {}},
            {"calls": [],
             "state": [8, ['_g0:INT64', '_g1:TIMESTAMP', '_group_rows:INT64'], [0, 1]],
             "minput": {}, "distinct": {}},
        ]},
    "nexmark-q4": {
        "chain": [
            "/ MaterializeExecutor",
            "/input ProjectExecutor",
            "/input/input HashAggExecutor(actor=0)[fused:ProjectExecutor]",
            "/input/input/input HashAggExecutor(actor=0)[fused:ProjectExecutor]",
            "/input/input/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor→RowIdGenExecutor; R:ProjectExecutor→RowIdGenExecutor→join]",
            "/input/input/input/input/left_in SourceExecutor",
            "/input/input/input/input/right_in SourceExecutor",
        ],
        "aggs": [
            {"calls": [['sum', 1, False], ['count', 1, False]],
             "state": [10, ['_g0:INT64', '_group_rows:INT64', '_acc0:INT64', '_acc1:INT64', '_acc2:INT64'], [0]],
             "minput": {}, "distinct": {}},
            {"calls": [['max', 2, False]],
             "state": [9, ['_g0:INT64', '_g1:INT64', '_group_rows:INT64', '_acc0:INT64', '_acc1:INT64'], [0, 1]],
             "minput": {}, "distinct": {}},
        ]},
    "nexmark-q5": {
        "chain": [
            "/ MaterializeExecutor",
            "/input ProjectExecutor",
            "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor; R:ProjectExecutor→join]",
            "/input/input/left_in HashAggExecutor(actor=0)[fused:HopWindowExecutor→ProjectExecutor]",
            "/input/input/left_in/input SourceExecutor",
            "/input/input/right_in HashAggExecutor(actor=0)",
            "/input/input/right_in/input CoalesceExecutor",
            "/input/input/right_in/input/input ProjectExecutor",
            "/input/input/right_in/input/input/input HashAggExecutor(actor=0)[fused:HopWindowExecutor→ProjectExecutor]",
            "/input/input/right_in/input/input/input/input SourceExecutor",
        ],
        "aggs": [
            {"calls": [['count', None, False]],
             "state": [4, ['_g0:TIMESTAMP', '_g1:INT64', '_group_rows:INT64', '_acc0:INT64'], [0, 1]],
             "minput": {}, "distinct": {}},
            {"calls": [['max', 1, False]],
             "state": [8, ['_g0:TIMESTAMP', '_group_rows:INT64', '_acc0:INT64', '_acc1:INT64'], [0]],
             "minput": {'0': [9, ['_g0:TIMESTAMP', '_value:INT64', '_cnt:INT64'], [0, 1]]}, "distinct": {}},
            {"calls": [['count', None, False]],
             "state": [7, ['_g0:INT64', '_g1:TIMESTAMP', '_group_rows:INT64', '_acc0:INT64'], [0, 1]],
             "minput": {}, "distinct": {}},
        ]},
    "nexmark-q5-wm": {
        "chain": [
            "/ MaterializeExecutor",
            "/input ProjectExecutor",
            "/input/input HashJoinExecutor(inner, actor=1000)[fused:L:ProjectExecutor; R:ProjectExecutor→join]",
            "/input/input/left_in HashAggExecutor(actor=0)[fused:HopWindowExecutor→ProjectExecutor]",
            "/input/input/left_in/input WatermarkFilterExecutor",
            "/input/input/left_in/input/input SourceExecutor",
            "/input/input/right_in HashAggExecutor(actor=0)",
            "/input/input/right_in/input CoalesceExecutor",
            "/input/input/right_in/input/input ProjectExecutor",
            "/input/input/right_in/input/input/input HashAggExecutor(actor=0)[fused:HopWindowExecutor→ProjectExecutor]",
            "/input/input/right_in/input/input/input/input WatermarkFilterExecutor",
            "/input/input/right_in/input/input/input/input/input SourceExecutor",
        ],
        "aggs": [
            {"calls": [['count', None, False]],
             "state": [5, ['_g0:TIMESTAMP', '_g1:INT64', '_group_rows:INT64', '_acc0:INT64'], [0, 1]],
             "minput": {}, "distinct": {}},
            {"calls": [['max', 1, False]],
             "state": [10, ['_g0:TIMESTAMP', '_group_rows:INT64', '_acc0:INT64', '_acc1:INT64'], [0]],
             "minput": {'0': [11, ['_g0:TIMESTAMP', '_value:INT64', '_cnt:INT64'], [0, 1]]}, "distinct": {}},
            {"calls": [['count', None, False]],
             "state": [9, ['_g0:INT64', '_g1:TIMESTAMP', '_group_rows:INT64', '_acc0:INT64'], [1, 0]],
             "minput": {}, "distinct": {}},
        ]},
}


@pytest.mark.parametrize("name", list(GOLDEN_SIX))
def test_an_accepted_configuration_plans_and_keeps_state_as_before(name):
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)

    def table(t):
        return [t.table_id,
                [f"{f.name}:{f.data_type.name}" for f in t.schema],
                list(t.pk_indices)]

    async def deploy():
        par = PARALLELISM.get(name, 1)
        fe = Frontend(parallelism=par) if par > 1 else Frontend()
        try:
            for stmt in config["sets"]:
                await fe.execute(stmt)
            for ddl in config["ddl"]:
                await fe.execute(ddl.format(seed=7))
            actor = fe.actors[fe.catalog.mvs[config["view"]].actor_id]
            chain, aggs = [], []
            for path, ex in run.walk_executors(actor.consumer):
                ex = getattr(ex, "inner", ex)
                chain.append(f"{path or '/'} {ex.identity}")
                if isinstance(ex, HashAggExecutor):
                    assert all(c.filter_idx is None for c in ex.agg_calls)
                    aggs.append({
                        "calls": [[c.kind.value, c.input_idx, c.distinct]
                                  for c in ex.agg_calls],
                        "state": table(ex.table),
                        "minput": {str(j): table(t)
                                   for j, t in ex.minput.items()},
                        "distinct": {str(j): table(t) for j, t
                                     in ex.distinct_tables.items()}})
            return {"chain": chain, "aggs": aggs}
        finally:
            await fe.close()

    assert asyncio.run(deploy()) == GOLDEN_SIX[name]


# -- the rung of the cleaning paths' batches ------------------------------

def test_a_batch_rung_never_steps_down_and_pages_above_its_top():
    from risingwave_tpu.ops.hash_join import BatchRung
    rung = BatchRung()
    assert rung.pages(0) == [] and rung.rows == 64
    assert rung.pages(3) == [(0, 3)] and rung.rows == 64
    assert rung.pages(6_000) == [(0, 6_000)] and rung.rows == 16_384
    assert rung.pages(100) == [(0, 100)] and rung.rows == 16_384
    a = np.arange(10, dtype=np.int32)
    assert rung.padded(a, 2, 5).tolist()[:4] == [2, 3, 4, 0]
    assert rung.padded(a, 2, 5).shape == (16_384,)
    assert rung.mask(2, 5).sum() == 3
    assert rung.pages(150_000) == [(0, 65_536), (65_536, 131_072),
                                   (131_072, 150_000)]
    assert rung.rows == BatchRung.TOP == 65_536


def test_a_join_side_rebuilt_in_pages_probes_like_one_batch():
    """A rebuild above the rung's top is paged, the last page first: a
    key's rows stand in its chain as one batch would link them, so a
    probe returns the same pairs in the same order."""
    import jax.numpy as jnp
    from risingwave_tpu.ops import hash_join as hj
    n, keys = 700, 37
    lanes = np.zeros((n, 3), dtype=np.int32)
    lanes[:, 1] = np.arange(n) % keys
    lanes[:, 2] = 1
    refs = np.arange(n, dtype=np.int32)

    def probe(kernel):
        kernel.rebuild(lanes, refs)
        out = kernel.probe(jnp.asarray(lanes[:keys]),
                           jnp.ones(keys, dtype=bool))
        return [np.asarray(a).tolist() for a in out]

    whole = hj.JoinSideKernel(key_width=3)
    paged = hj.JoinSideKernel(key_width=3)
    paged._bulk.TOP = 256          # this kernel's rung tops out early
    want, got = probe(whole), probe(paged)
    assert whole._bulk.rows == 1024 and paged._bulk.rows == 256
    assert got == want
    _degrees, probe_rows, matched = want
    assert len(matched) == n
    # a key's rows come back in the order the rebuild was given them
    assert matched[:probe_rows.count(0)] == list(range(0, n, keys))
