"""Fragment fusion (ISSUE 6): traced-stage units, fused-vs-unfused
oracles, dispatch accounting, session plumbing, IR round-trips.

Covers the acceptance points: the composed filter/project chain traces
bit-identically to the sequential executors (including update-pair
degradation, noop-pair drops and NULL handling), fused nexmark
q1/q4/q7/q8 + TPC-H q3/q5 runs are bit-identical to unfused through the
SQL front door, a fused hand-built q7/q3/q8 run shows STRICTLY fewer
device dispatches at higher rows-per-dispatch (conftest dispatch-budget
guard), SET stream_fusion rides the DDL log and reschedule replay, the
checker falls back on a broken fusion, and the {"op":"fused"} /
hash_agg["fused_stages"] IR rebuilds on cluster workers.
"""

import asyncio

import numpy as np
import pytest

from risingwave_tpu.common.chunk import Column, Op, StreamChunk
from risingwave_tpu.common.types import DataType, Field, Interval, Schema
from risingwave_tpu.expr.expr import (
    BinaryOp, Cast, InputRef, lit, tumble_start,
)
from risingwave_tpu.frontend.session import Frontend
from risingwave_tpu.ops.fused import (
    FusedStage, FusedStages, encode_raw_chunk, key_lanes_traced,
    traceable_reason,
)


def run(coro):
    return asyncio.run(coro)


SCHEMA = Schema([Field("k", DataType.INT64),
                 Field("v", DataType.INT64),
                 Field("f", DataType.FLOAT64),
                 Field("s", DataType.VARCHAR)])


# -- eligibility walker ----------------------------------------------------


def test_traceable_reason_units():
    dev = BinaryOp("+", InputRef(0, DataType.INT64),
                   InputRef(1, DataType.INT64))
    assert traceable_reason(dev, SCHEMA) is None
    host_ref = InputRef(3, DataType.VARCHAR)
    assert "host-typed" in traceable_reason(host_ref, SCHEMA)
    host_cmp = BinaryOp("=", InputRef(3, DataType.VARCHAR),
                        lit("x"))
    assert traceable_reason(host_cmp, SCHEMA) is not None
    dec_cast = Cast(InputRef(2, DataType.FLOAT64), DataType.DECIMAL)
    assert "DECIMAL" in traceable_reason(dec_cast, SCHEMA)
    # tumble over a timestamp is the flagship traceable function
    ts = tumble_start(InputRef(0, DataType.INT64),
                      Interval(usecs=10))
    assert traceable_reason(ts, SCHEMA) is None


# -- composed chain vs sequential executors --------------------------------


def _chunk(n=32, seed=0, with_pairs=True):
    rng = np.random.default_rng(seed)
    cap = n
    k = rng.integers(-50, 50, size=cap).astype(np.int64)
    v = rng.integers(-1000, 1000, size=cap).astype(np.int64)
    f = rng.normal(size=cap)
    f[0] = 0.0
    if cap > 4:
        f[4] = -0.0
    s = np.empty(cap, dtype=object)
    s[:] = [f"s{int(x) % 5}" for x in k]
    vis = rng.random(cap) > 0.15
    ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
    if with_pairs:
        for i in range(0, cap - 1, 6):
            ops[i] = int(Op.UPDATE_DELETE)
            ops[i + 1] = int(Op.UPDATE_INSERT)
            vis[i] = vis[i + 1] = True
            k[i + 1] = k[i]              # same key, maybe same value
            if i % 12 == 0:
                v[i + 1] = v[i]          # noop pair after projection
    val = rng.random(cap) > 0.1
    cols = [Column(DataType.INT64, k, None),
            Column(DataType.INT64, v,
                   None if val.all() else val.copy()),
            Column(DataType.FLOAT64, f, None),
            Column(DataType.VARCHAR, s, None)]
    return StreamChunk(SCHEMA, cols, vis, ops)


def _sequential(chunk, pred, exprs, names):
    """Reference semantics: real FilterExecutor + ProjectExecutor math."""
    from risingwave_tpu.stream.executors.simple import (
        FilterExecutor, ProjectExecutor,
    )
    c = chunk if pred is None \
        else FilterExecutor.apply_predicate(chunk, pred)
    cols = [e.eval(c) for e in exprs]
    vis = np.asarray(c.visibility)
    ops_np = np.asarray(c.ops)
    if (ops_np == int(Op.UPDATE_DELETE)).any():
        vis = ProjectExecutor._drop_noop_updates(cols, vis.copy(),
                                                 ops_np)
    out_schema = Schema([Field(nm, e.return_type)
                         for nm, e in zip(names, exprs)])
    return StreamChunk(out_schema, cols, vis, c.ops)


def _rows(schema, cols, vis, ops):
    out = []
    vis = np.asarray(vis)
    ops = np.asarray(ops)
    for i in np.flatnonzero(vis):
        row = [int(ops[i])]
        for c in cols:
            val = c.validity
            if val is not None and not np.asarray(val)[i]:
                row.append(None)
            else:
                x = np.asarray(c.values)[i]
                row.append(x.item() if hasattr(x, "item") else x)
        out.append(tuple(row))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chain_step_bit_identical_to_sequential(seed):
    """filter→project composed into one trace == sequential executors,
    on visible rows (ops included), under numpy AND under jit."""
    pred = InputRef(0, DataType.INT64) > lit(-10)
    exprs = [InputRef(0, DataType.INT64),
             BinaryOp("+", InputRef(1, DataType.INT64), lit(7)),
             InputRef(2, DataType.FLOAT64)]
    names = ["k", "v7", "f"]
    fs = FusedStages(SCHEMA, [
        FusedStage("filter", "FilterExecutor", exprs=(pred,)),
        FusedStage("project", "ProjectExecutor", exprs=tuple(exprs),
                   names=tuple(names))])
    assert fs.fusable_reason() is None
    chunk = _chunk(seed=seed)
    ref = _sequential(chunk, pred, exprs, names)
    want = _rows(ref.schema, ref.columns, ref.visibility, ref.ops)

    # numpy path of the composed normal form
    out_cols, vis, ops, stage_rows = fs.chain_body(
        list(chunk.columns), np.asarray(chunk.visibility),
        np.asarray(chunk.ops), np)
    got = _rows(fs.out_schema, out_cols, vis, ops)
    assert got == want

    # traced path (the standalone executor's jitted step)
    from risingwave_tpu.ops.fused import build_chain_step
    step = build_chain_step(fs)
    vals = tuple(np.asarray(chunk.columns[i].values)
                 for i in fs.ref_cols)
    oks = tuple(np.ones(chunk.capacity, dtype=bool)
                if chunk.columns[i].validity is None
                else np.asarray(chunk.columns[i].validity)
                for i in fs.ref_cols)
    fv, fo, vis2, ops2, srows = step(vals, oks,
                                     np.asarray(chunk.visibility),
                                     np.asarray(chunk.ops),
                                     np.ones(chunk.capacity,
                                             dtype=bool))
    cols2 = [Column(f.data_type, np.asarray(a), np.asarray(o))
             for f, a, o in zip(fs.out_schema, fv, fo)]
    got2 = _rows(fs.out_schema, cols2, np.asarray(vis2),
                 np.asarray(ops2))
    assert got2 == want
    # per-stage attribution: filter rows ≤ input, project == final
    sr = np.asarray(srows)
    assert sr[1] == int(np.asarray(vis2).sum())


def test_noop_pair_drop_sees_host_passthrough_columns():
    """Regression (review finding): a U-/U+ pair whose ONLY change is
    in a varchar passthrough column must NOT be dropped — the host
    columns bypass the trace, so their adjacent equality rides in via
    host_noop_eq."""
    exprs = [InputRef(0, DataType.INT64),
             InputRef(3, DataType.VARCHAR)]
    fs = FusedStages(SCHEMA, [
        FusedStage("project", "ProjectExecutor", exprs=tuple(exprs),
                   names=("k", "s"))])
    assert fs.fusable_reason() is None and fs.host_out == {1: 3}
    k = np.array([7, 7, 5, 5], dtype=np.int64)
    s = np.empty(4, dtype=object)
    s[:] = ["old", "new", "same", "same"]   # pair 0-1 differs ONLY in s
    cols = [Column(DataType.INT64, k, None),
            Column(DataType.INT64, np.zeros(4, dtype=np.int64), None),
            Column(DataType.FLOAT64, np.zeros(4), None),
            Column(DataType.VARCHAR, s, None)]
    ops = np.array([int(Op.UPDATE_DELETE), int(Op.UPDATE_INSERT),
                    int(Op.UPDATE_DELETE), int(Op.UPDATE_INSERT)],
                   dtype=np.int8)
    chunk = StreamChunk(SCHEMA, cols, np.ones(4, dtype=bool), ops)
    out_cols, vis, _o, _sr = fs.chain_body(
        cols, np.asarray(chunk.visibility), ops, np,
        host_same=fs.host_noop_eq(chunk))
    vis = np.asarray(vis)
    assert vis[0] and vis[1], "varchar-only update pair was dropped"
    assert not vis[2] and not vis[3], "true noop pair survived"
    # and the sequential oracle agrees
    ref = _sequential(chunk, None, exprs, ["k", "s"])
    assert np.array_equal(vis, np.asarray(ref.visibility))


def test_filter_only_run_passes_all_columns_through():
    """Regression (review finding): a filter-only run has no output
    projection, so EVERY column passes through — device columns via
    the trace, host columns around it. Omitting them from ref_cols
    handed the consumer dummy zero columns."""
    pred = InputRef(0, DataType.INT64) > lit(0)
    fs = FusedStages(SCHEMA, [
        FusedStage("filter", "FilterExecutor", exprs=(pred,))])
    assert fs.fusable_reason() is None
    assert fs.ref_cols == [0, 1, 2]          # all device columns
    assert fs.host_out == {3: 3}             # varchar rides around
    chunk = _chunk(seed=5)
    out_cols, vis, ops, _sr = fs.chain_body(
        list(chunk.columns), np.asarray(chunk.visibility),
        np.asarray(chunk.ops), np)
    keep = np.asarray(vis)
    assert keep.any()
    # column 1 (never referenced by the predicate) keeps real values
    assert np.array_equal(np.asarray(out_cols[1].values)[keep],
                          np.asarray(chunk.columns[1].values)[keep])
    assert out_cols[3] is None               # host placeholder


def test_filter_only_fused_agg_front_door_oracle():
    """End-to-end shape of the same regression: the fused agg groups
    on a column the filter never references."""
    mv = ("CREATE MATERIALIZED VIEW q AS SELECT bidder, "
          "COUNT(*) AS c, SUM(price) AS s FROM bid "
          "WHERE price > 100 GROUP BY bidder")
    rows_off = _front_door_rows(NEXMARK_SOURCES, mv, False)
    rows_on = _front_door_rows(NEXMARK_SOURCES, mv, True)
    assert rows_on == rows_off and len(rows_on) > 1


def test_key_lanes_traced_match_keycodec():
    """Traced key-lane builder == KeyCodec.build_arrays, including
    float keys with -0.0 normalization and NULLs. The float column
    enters the trace as the int64 bit image the raw upload carries
    (the TPU compiler has no f64→int64 bitcast)."""
    import jax
    from risingwave_tpu.ops.fused import key_i64_traced
    from risingwave_tpu.stream.executors.keys import KeyCodec
    rng = np.random.default_rng(7)
    k = rng.integers(-9, 9, size=64).astype(np.int64)
    f = np.where(rng.random(64) < 0.2, 0.0, rng.normal(size=64))
    f[3] = -0.0
    ok = rng.random(64) > 0.3
    import jax.numpy as jnp
    codec = KeyCodec([DataType.INT64, DataType.FLOAT64])
    want = codec.build_arrays([(k, None), (f, ok)])

    def traced(a, img, m):
        fcol = Column(DataType.FLOAT64,
                      jax.lax.bitcast_convert_type(img, jnp.float64), m)
        return key_lanes_traced(
            [(key_i64_traced(Column(DataType.INT64, a, None), None),
              None),
             (key_i64_traced(fcol, img), m)], jnp)

    got = jax.jit(traced)(k, f.view(np.int64), ok)
    assert np.array_equal(np.asarray(got), want)


def test_lane_codecs_trace_bit_identical():
    """ops/lanes.py order/sum codecs under jit == numpy (the fused
    prelude calls the SAME implementations)."""
    import jax
    from risingwave_tpu.ops import lanes
    v = np.array([0, 1, -1, 2**40, -(2**40), 2**62, -(2**62)],
                 dtype=np.int64)
    f = np.array([0.0, -0.0, 1.5, -3.25, 1e300, -1e-300, 7.0])
    # a float's order lanes trace from its uploaded int64 bit image
    # (no f64→int64 bitcast in-trace); the numpy codec is the oracle
    for arr, fn, traced, targ in (
            (v, lanes.sum_limbs, lanes.sum_limbs, v),
            (v, lanes.order_lanes, lanes.order_lanes, v),
            (f, lanes.order_lanes, lanes.order_lanes_from_image,
             f.view(np.int64))):
        want = fn(arr)
        got = jax.jit(traced)(targ)
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), b), fn.__name__
    with pytest.raises(TypeError, match="bit image"):
        jax.jit(lanes.order_lanes)(f)


# -- fused agg oracle + dispatch budget (hand-built q7) --------------------


def _q7_rows(fusion: bool, steps=6):
    from risingwave_tpu.models.nexmark import build_q7
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    from risingwave_tpu.state.store import MemoryStateStore

    async def main():
        cfg = NexmarkConfig(event_num=40_000, max_chunk_size=256,
                            generate_strings=False)
        p = build_q7(MemoryStateStore(), cfg, rate_limit=24,
                     min_chunks=24, fusion=fusion)
        task = p.actor.spawn()
        for _ in range(steps):
            await p.loop.inject_and_collect(force_checkpoint=True)
        from risingwave_tpu.stream.message import StopMutation
        await p.loop.inject_and_collect(
            mutation=StopMutation(frozenset({1})))
        await task
        if p.actor.failure is not None:
            raise p.actor.failure
        return sorted(
            tuple(row) for _pk, row in _iter_mv(p.mv_table))

    return run(main())


def _iter_mv(table):
    from risingwave_tpu.common.epoch import Epoch, EpochPair
    ce = table.store.committed_epoch() if hasattr(
        table.store, "committed_epoch") else None
    t = type(table)(table.table_id, table.schema,
                    list(table.pk_indices), table.store,
                    sanity_check=False)
    ce = table.store.committed_epoch()
    t.init_epoch(EpochPair(Epoch(ce + 1), Epoch(ce)))
    return t.iter_rows()


def test_q7_fused_oracle_and_dispatch_budget(dispatch_budget):
    """THE acceptance test shape: bit-identical MV rows, strictly
    fewer device dispatches, rows-per-dispatch at least the unfused
    baseline's (conftest dispatch-budget guard)."""
    rows_off, d_off, rpd_off = dispatch_budget.measure(
        lambda: _q7_rows(False))
    rows_on, d_on, rpd_on = dispatch_budget.measure(
        lambda: _q7_rows(True))
    assert rows_on == rows_off and rows_on
    dispatch_budget.check(d_off, rpd_off, d_on, rpd_on)


def test_q3_fused_oracle(dispatch_budget):
    """TPC-H q3 (3-way join → DECIMAL-revenue project → agg → topn):
    the revenue projection fuses into the agg kernel."""
    from risingwave_tpu.models.nexmark import drive_to_completion
    from risingwave_tpu.models.tpch import build_q3
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.connectors.tpch import LINES_PER_ORDER

    def go(fusion):
        p = build_q3(MemoryStateStore(), customers=120, orders=1200,
                     rate_limit=4, min_chunks=8, fusion=fusion)
        targets = {1: 120, 2: 1200, 3: 1200 * LINES_PER_ORDER}
        run(drive_to_completion(p, targets, in_flight=1))
        return sorted(tuple(r) for _pk, r in _iter_mv(p.mv_table))

    rows_off, d_off, rpd_off = dispatch_budget.measure(
        lambda: go(False))
    rows_on, d_on, rpd_on = dispatch_budget.measure(lambda: go(True))
    assert rows_on == rows_off and rows_on
    dispatch_budget.check(d_off, rpd_off, d_on, rpd_on)


def test_q8_fused_oracle(dispatch_budget):
    """q8's auction-side dedup agg absorbs its tumble projection."""
    from risingwave_tpu.models.nexmark import (
        build_q8, drive_to_completion,
    )
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    from risingwave_tpu.state.store import MemoryStateStore

    def go(fusion):
        base = NexmarkConfig(event_num=40_000, max_chunk_size=256,
                             generate_strings=False)
        cfg_p = NexmarkConfig(**{**base.__dict__,
                                 "table_type": "person"})
        cfg_a = NexmarkConfig(**{**base.__dict__,
                                 "table_type": "auction"})
        p = build_q8(MemoryStateStore(), cfg_p, cfg_a, rate_limit=16,
                     min_chunks=16, fusion=fusion)
        targets = {1: 40_000 // 50, 2: 40_000 * 3 // 50}
        run(drive_to_completion(p, targets, in_flight=1))
        return sorted(tuple(r) for _pk, r in _iter_mv(p.mv_table))

    rows_off, d_off, rpd_off = dispatch_budget.measure(
        lambda: go(False))
    rows_on, d_on, rpd_on = dispatch_budget.measure(lambda: go(True))
    assert rows_on == rows_off and rows_on
    dispatch_budget.check(d_off, rpd_off, d_on, rpd_on)


# -- rewrite-rule units ----------------------------------------------------


def _mini_agg_chain(distinct=False):
    from risingwave_tpu.ops.hash_agg import AggKind
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.executors import MockSource
    from risingwave_tpu.stream.executors.hash_agg import (
        AggCall, HashAggExecutor, agg_aux_tables, agg_state_schema,
    )
    from risingwave_tpu.stream.executors.materialize import (
        MaterializeExecutor,
    )
    from risingwave_tpu.stream.executors.simple import (
        FilterExecutor, ProjectExecutor,
    )
    store = MemoryStateStore()
    src = MockSource(Schema.of(k=DataType.INT64, v=DataType.INT64), [])
    filt = FilterExecutor(src, InputRef(1, DataType.INT64) > lit(0))
    proj = ProjectExecutor(
        filt, [InputRef(0, DataType.INT64),
               BinaryOp("*", InputRef(1, DataType.INT64), lit(2))],
        ["k", "v2"])
    calls = [AggCall(AggKind.SUM, 1, distinct=distinct)]
    sch, pk = agg_state_schema(proj.schema, [0], calls)
    distinct_tables, minput = agg_aux_tables(
        proj.schema, [0], calls, False, store,
        dedup_table_id=lambda c: 90 + c,
        minput_table_id=lambda j: 95 + j)
    agg = HashAggExecutor(proj, [0], calls,
                          StateTable(2, sch, pk, store),
                          distinct_tables=distinct_tables,
                          minput_tables=minput)
    mv = StateTable(3, agg.schema, [0], store)
    return MaterializeExecutor(agg, mv)


def test_fusion_rule_absorbs_run_into_agg():
    from risingwave_tpu.frontend.opt import rewrite_stream_plan
    root = _mini_agg_chain()
    new_root, report = rewrite_stream_plan(root, "none", record=False,
                                           fusion=True)
    assert report.fired.get("fusion_grouping") == 1
    agg = new_root.input
    assert agg.fused_stages is not None
    assert agg.fused_stages.describe() == \
        "FilterExecutor→ProjectExecutor"
    from risingwave_tpu.stream.executors import MockSource
    assert isinstance(agg.input, MockSource)
    # without the fusion flag the rule never runs
    _root2, report2 = rewrite_stream_plan(_mini_agg_chain(), "all",
                                          record=False)
    assert "fusion_grouping" not in report2.fired


def test_fusion_rule_refuses_distinct_agg():
    """A DISTINCT agg cannot absorb the run (host dedup multisets read
    post-stage chunks) — the run still fuses as a STANDALONE block
    feeding the interpretive agg."""
    from risingwave_tpu.frontend.opt import rewrite_stream_plan
    from risingwave_tpu.stream.executors.fused import (
        FusedFragmentExecutor,
    )
    root = _mini_agg_chain(distinct=True)
    new, report = rewrite_stream_plan(root, "none", record=False,
                                      fusion=True)
    agg = new.input
    assert agg.fused_stages is None, \
        "DISTINCT agg must not absorb a prelude"
    assert isinstance(agg.input, FusedFragmentExecutor)
    assert report.fired.get("fusion_grouping") == 1


def test_checker_catches_broken_fused_block():
    """A fused run planned against the wrong input schema must trip
    the plan-property checker (fallback off-strict, raise in strict)."""
    from risingwave_tpu.frontend.opt import (
        rewrite_stream_plan, set_strict_checker,
    )
    from risingwave_tpu.stream.executors.fused import (
        FusedFragmentExecutor,
    )

    def broken_rule(root):
        import copy
        agg = root.input
        wrong = Schema.of(a=DataType.INT64)   # NOT the real base schema
        fs = FusedStages(wrong, [FusedStage(
            "filter", "FilterExecutor",
            exprs=(InputRef(0, DataType.INT64) > lit(0),))])
        bad = FusedFragmentExecutor.__new__(FusedFragmentExecutor)
        # hand-assemble to bypass the constructor's own assertion —
        # the checker must not depend on constructor diligence
        from risingwave_tpu.stream.executor import ExecutorInfo
        base = agg.input.input            # below the project
        bad.input = base
        bad.fused_stages = fs
        bad._info = ExecutorInfo(fs.out_schema, [], "FusedFragment")
        bad._step = None
        bad._ref = list(fs.ref_cols)
        new_agg = copy.copy(agg)
        new_agg.input = bad
        new_root = copy.copy(root)
        new_root.input = new_agg
        return new_root, 1, "broken"

    root = _mini_agg_chain()
    set_strict_checker(False)
    try:
        _new, report = rewrite_stream_plan(
            root, "none", record=False,
            extra_rules={"broken_fusion": broken_rule})
        assert any(r == "broken_fusion" for r, _ in report.fallbacks)
    finally:
        set_strict_checker(True)
    with pytest.raises(AssertionError):
        rewrite_stream_plan(root, "none", record=False,
                            extra_rules={"broken_fusion": broken_rule})


# -- SQL front door: oracle + plumbing -------------------------------------


NEXMARK_SOURCES = [
    ("CREATE SOURCE {t} WITH (connector='nexmark', "
     "nexmark.table.type='{t}', nexmark.event.num=2000, "
     "nexmark.max.chunk.size=128, "
     "nexmark.generate.strings='false')").format(t=t)
    for t in ("bid", "auction", "person")
]

TPCH_SOURCES = [
    ("CREATE SOURCE {t} WITH (connector='tpch', tpch.table='{t}', "
     "tpch.customers=150, tpch.orders=1500)").format(t=t)
    for t in ("customer", "orders", "lineitem", "supplier", "nation",
              "region")
]

QUERIES = {
    "nexmark_q1": (NEXMARK_SOURCES,
                   "CREATE MATERIALIZED VIEW q AS SELECT auction, "
                   "bidder, price * 89 AS price_dol, date_time "
                   "FROM bid"),
    "nexmark_q4": (NEXMARK_SOURCES,
                   "CREATE MATERIALIZED VIEW q AS "
                   "SELECT category, AVG(final) AS avg_final FROM ("
                   "  SELECT a.category AS category, "
                   "         MAX(b.price) AS final"
                   "  FROM auction AS a JOIN bid AS b "
                   "  ON a.id = b.auction"
                   "  WHERE b.date_time BETWEEN a.date_time "
                   "  AND a.expires"
                   "  GROUP BY a.id, a.category) AS q4i "
                   "GROUP BY category"),
    "nexmark_q7": (NEXMARK_SOURCES,
                   "CREATE MATERIALIZED VIEW q AS "
                   "SELECT window_start, MAX(price) AS max_price, "
                   "COUNT(*) AS cnt "
                   "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
                   "GROUP BY window_start"),
    "nexmark_q8": (NEXMARK_SOURCES,
                   "CREATE MATERIALIZED VIEW q AS "
                   "SELECT p.id, p.name, p.window_start "
                   "FROM TUMBLE(person, date_time, INTERVAL '10' "
                   "SECOND) AS p "
                   "JOIN TUMBLE(auction, date_time, INTERVAL '10' "
                   "SECOND) AS a "
                   "ON p.id = a.seller "
                   "AND p.window_start = a.window_start"),
    "tpch_q3": (TPCH_SOURCES,
                "CREATE MATERIALIZED VIEW q AS SELECT "
                "o.o_orderkey, o.o_orderdate, o.o_shippriority, "
                "sum(l.l_extendedprice * (1 - l.l_discount)) "
                "AS revenue "
                "FROM customer AS c "
                "JOIN orders AS o ON c.c_custkey = o.o_custkey "
                "JOIN lineitem AS l ON o.o_orderkey = l.l_orderkey "
                "WHERE c.c_mktsegment = 'BUILDING' "
                "AND o.o_orderdate < 9204 AND l.l_shipdate > 9204 "
                "GROUP BY o.o_orderkey, o.o_orderdate, "
                "o.o_shippriority "
                "ORDER BY revenue DESC, o_orderdate ASC LIMIT 10"),
    "tpch_q5": (TPCH_SOURCES,
                "CREATE MATERIALIZED VIEW q AS SELECT n.n_name, "
                "sum(l.l_extendedprice * (1 - l.l_discount)) "
                "AS revenue "
                "FROM customer AS c "
                "JOIN orders AS o ON c.c_custkey = o.o_custkey "
                "JOIN lineitem AS l ON o.o_orderkey = l.l_orderkey "
                "JOIN supplier AS s ON l.l_suppkey = s.s_suppkey "
                "AND c.c_nationkey = s.s_nationkey "
                "JOIN nation AS n ON s.s_nationkey = n.n_nationkey "
                "JOIN region AS r ON n.n_regionkey = r.r_regionkey "
                "WHERE r.r_name = 'ASIA' AND o.o_orderdate < 9500 "
                "GROUP BY n.n_name"),
}


def _front_door_rows(sources, mv_sql, fusion, steps=16):
    async def main():
        fe = Frontend(rate_limit=16, min_chunks=16)
        await fe.execute(
            f"SET stream_fusion = '{'on' if fusion else 'off'}'")
        for s in sources:
            await fe.execute(s)
        await fe.execute(mv_sql)
        await fe.step(steps)
        rows = await fe.execute("SELECT * FROM q")
        await fe.close()
        return sorted(tuple(r) for r in rows)
    return run(main())


@pytest.mark.parametrize("name", list(QUERIES))
def test_front_door_oracle_fusion_on_vs_off(name):
    sources, mv = QUERIES[name]
    rows_off = _front_door_rows(sources, mv, False)
    rows_on = _front_door_rows(sources, mv, True)
    assert rows_on == rows_off, name
    assert rows_on, f"{name} produced no output at this scale"


def test_set_stream_fusion_validates():
    from risingwave_tpu.frontend.planner import PlanError

    async def main():
        fe = Frontend()
        await fe.execute("SET stream_fusion = 'off'")
        assert (await fe.execute(
            "SHOW stream_fusion")) == [("off",)]
        with pytest.raises(PlanError):
            await fe.execute("SET stream_fusion = 'sideways'")
        await fe.close()
    run(main())


def test_explain_shows_fusion_group_annotation():
    async def main():
        fe = Frontend(rate_limit=4)
        for s in NEXMARK_SOURCES:
            await fe.execute(s)
        rows = await fe.execute(
            "EXPLAIN SELECT window_start, MAX(price) AS m, "
            "COUNT(*) AS c "
            "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
            "GROUP BY window_start")
        text = "\n".join(r[0] for r in rows)
        assert "fusion_grouping" in text
        assert "[fused:" in text
        await fe.close()
    run(main())


# -- float columns: the uploaded bit image, or the host path ---------------

FLOAT_SOURCES = [
    "CREATE SOURCE ticks WITH (connector='datagen', "
    "datagen.event.num=3000, "
    "fields.k.type='bigint', fields.k.kind='sequence', "
    "fields.k.start=0, fields.k.end=11, "
    "fields.level.type='double', fields.level.kind='sequence', "
    "fields.level.start='-3', fields.level.end=4, "
    "fields.px.type='double', fields.px.kind='random', "
    "fields.px.min='-50', fields.px.max=50)",
    "CREATE SOURCE fees WITH (connector='datagen', "
    "datagen.event.num=11, "
    "fields.k.type='bigint', fields.k.kind='sequence', "
    "fields.fee.type='double', fields.fee.kind='random', "
    "fields.fee.min='-1', fields.fee.max=1)"]

FLOAT_QUERIES = {
    # plain column references: fused, lanes from the uploaded image
    "key": ("SELECT level, COUNT(*) AS n FROM ticks GROUP BY level",
            "agg absorbed"),
    "minmax": ("SELECT k, MIN(px) AS lo, MAX(px) AS hi FROM ticks "
               "GROUP BY k", "agg absorbed"),
    "payload": ("SELECT t.k, t.px, f.fee FROM ticks AS t "
                "JOIN fees AS f ON t.k = f.k", "join side 0 absorbed"),
    # computed floats: refused at plan time, with the reason
    "computed_key": ("SELECT level * 2 AS l2, COUNT(*) AS n FROM ticks "
                     "GROUP BY level * 2",
                     "NOT fused (computed double precision group key"),
    "computed_minmax": ("SELECT k, MAX(px + 1) AS hi FROM ticks "
                        "GROUP BY k",
                        "NOT fused (computed double precision group key"),
    "computed_payload": ("SELECT t.k, t.p2, f.fee FROM "
                         "(SELECT k, px * 2 AS p2 FROM ticks) AS t "
                         "JOIN fees AS f ON t.k = f.k",
                         "NOT fused (computed double precision join key"),
}


@pytest.mark.parametrize("name", list(FLOAT_QUERIES))
def test_float_columns_fuse_by_image_or_stay_on_the_host(name):
    """A DOUBLE group key, MIN/MAX argument or join column fuses when
    it is a plain column reference (its lanes come from the int64 image
    the raw upload carries — no f64→int64 bitcast in-trace, which the
    TPU compiler refuses); a COMPUTED one is refused by the rule with a
    reason EXPLAIN shows, on every platform. Either way the result is
    bit-identical to the unfused plan."""
    select, expect = FLOAT_QUERIES[name]

    async def explain():
        fe = Frontend()
        for s in FLOAT_SOURCES:
            await fe.execute(s)
        rows = await fe.execute("EXPLAIN " + select)
        await fe.close()
        return "\n".join(r[0] for r in rows)

    text = run(explain())
    assert expect in text, text
    mv = "CREATE MATERIALIZED VIEW q AS " + select
    rows_on = _front_door_rows(FLOAT_SOURCES, mv, True, steps=6)
    rows_off = _front_door_rows(FLOAT_SOURCES, mv, False, steps=6)
    assert rows_on == rows_off and rows_on


def test_ddl_log_replays_create_time_fusion_setting(tmp_path):
    """SET stream_fusion rides the DDL log: a recovery replays the
    CREATE under the recorded setting, not the current default."""
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore
    from risingwave_tpu.stream.executors.hash_agg import (
        HashAggExecutor,
    )
    from risingwave_tpu.stream.executor import executor_children

    def find_fused_agg(ex):
        ex = getattr(ex, "inner", ex)       # unwrap monitoring
        if isinstance(ex, HashAggExecutor) and \
                ex.fused_stages is not None:
            return True
        return any(find_fused_agg(c)
                   for _a, _i, c in executor_children(ex))

    async def main():
        store = HummockLite(LocalFsObjectStore(str(tmp_path)))
        fe = Frontend(store=store, rate_limit=4, min_chunks=4)
        await fe.execute("SET stream_fusion = 'off'")
        for s in NEXMARK_SOURCES:
            await fe.execute(s)
        await fe.execute(QUERIES["nexmark_q7"][1])
        await fe.step(4)
        rows1 = sorted(await fe.execute("SELECT * FROM q"))
        assert not any(find_fused_agg(a.consumer)
                       for a in fe.actors.values())
        await fe.close()

        store2 = HummockLite(LocalFsObjectStore(str(tmp_path)))
        fe2 = Frontend(store=store2, rate_limit=4, min_chunks=4)
        await fe2.recover()
        # the replayed CREATE ran under the RECORDED 'off', even
        # though a fresh session defaults to 'on'
        assert fe2.session_vars.get("stream_fusion") == "off"
        assert not any(find_fused_agg(a.consumer)
                       for a in fe2.actors.values())
        rows2 = sorted(await fe2.execute("SELECT * FROM q"))
        assert rows2 == rows1
        await fe2.step(3)
        await fe2.close()
    run(main())


def test_reschedule_replays_fusion(tmp_path):
    """ALTER SET PARALLELISM back to 1 re-fuses exactly as the CREATE
    did (the _mv_fusion replay map)."""
    from risingwave_tpu.stream.executors.hash_agg import (
        HashAggExecutor,
    )
    from risingwave_tpu.stream.executor import executor_children

    def fused_aggs(fe):
        out = []

        def walk(ex):
            ex = getattr(ex, "inner", ex)   # unwrap monitoring
            if isinstance(ex, HashAggExecutor):
                out.append(ex.fused_stages is not None)
            for _a, _i, c in executor_children(ex):
                walk(c)
        for a in fe.actors.values():
            walk(a.consumer)
        return out

    async def main():
        fe = Frontend(rate_limit=8, min_chunks=8)
        for s in NEXMARK_SOURCES:
            await fe.execute(s)
        await fe.execute(QUERIES["nexmark_q7"][1])
        await fe.step(4)
        assert any(fused_aggs(fe))
        rows1 = sorted(await fe.execute("SELECT * FROM q"))
        # flip the session default OFF: the replay must still fuse
        await fe.execute("SET stream_fusion = 'off'")
        await fe.execute(
            "ALTER MATERIALIZED VIEW q SET PARALLELISM = 1")
        await fe.step(4)
        assert any(fused_aggs(fe)), \
            "reschedule lost the CREATE-time fusion setting"
        rows2 = sorted(await fe.execute("SELECT * FROM q"))
        assert [r for r in rows1 if r in rows2]  # state survived
        await fe.close()
    run(main())


# -- IR / cluster ----------------------------------------------------------


def test_fragmenter_lowers_and_rebuilds_fused_agg():
    """plan → fuse → fragment → {hash_agg + fused_stages} IR →
    build_fragment reconstructs a fused executor (coordinator/worker
    parity)."""
    from risingwave_tpu.frontend.catalog import Catalog
    from risingwave_tpu.frontend.fragmenter import Fragmenter
    from risingwave_tpu.frontend.parser import parse_many
    from risingwave_tpu.frontend.planner import (
        StreamPlanner, source_schema,
    )
    from risingwave_tpu.frontend.opt import rewrite_stream_plan
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.exchange import channel_for_test
    from risingwave_tpu.stream.plan_ir import build_fragment
    from risingwave_tpu.stream.executor import executor_children
    from risingwave_tpu.stream.executors.hash_agg import (
        HashAggExecutor,
    )

    opts = {"connector": "nexmark", "nexmark.table.type": "bid",
            "nexmark.event.num": "1000",
            "nexmark.generate.strings": "false"}
    catalog = Catalog()
    catalog.add_source("bid", source_schema(opts, None), opts)
    [(_t, stmt)] = parse_many(
        "CREATE MATERIALIZED VIEW v AS SELECT auction, "
        "COUNT(*) AS c, SUM(price) AS s FROM bid "
        "WHERE price > 100 GROUP BY auction")
    planner = StreamPlanner(catalog, MemoryStateStore(),
                            LocalBarrierManager(), definition="")
    plan = planner.plan("v", stmt.select, 7, rate_limit=4)
    consumer, report = rewrite_stream_plan(plan.consumer, "all",
                                           record=False, fusion=True)
    assert report.fired.get("fusion_grouping")
    graph = Fragmenter(1).lower(consumer)
    nodes = [n for f in graph.fragments for n in f.nodes]
    agg_node = next(n for n in nodes if n["op"] == "hash_agg")
    assert agg_node.get("fused_stages"), \
        "fused run missing from the shipped IR"
    _src, rebuilt = build_fragment(
        graph.fragments[-1].nodes, MemoryStateStore(),
        LocalBarrierManager(), channel_for_test)

    def find_agg(ex):
        if isinstance(ex, HashAggExecutor):
            return ex
        for _a, _i, c in executor_children(ex):
            got = find_agg(c)
            if got is not None:
                return got
        return None

    agg = find_agg(rebuilt)
    assert agg is not None and agg.fused_stages is not None
    assert agg.fused_stages.describe() == \
        consumer.input.fused_stages.describe() \
        if hasattr(consumer.input, "fused_stages") else True


def test_cluster_session_fused_matches_inprocess(tmp_path):
    """DistFrontend at parallelism 1 ships fused IR to a worker; rows
    must equal the in-process unfused oracle."""
    from risingwave_tpu.cluster.session import DistFrontend

    sources = (
        "CREATE SOURCE bid WITH (connector='nexmark', "
        "nexmark.table.type='bid', nexmark.event.num=2000, "
        "nexmark.max.chunk.size=128, "
        "nexmark.generate.strings='false')",)
    mv = ("CREATE MATERIALIZED VIEW q AS "
          "SELECT window_start, MAX(price) AS max_price, "
          "COUNT(*) AS cnt "
          "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
          "GROUP BY window_start")

    want = set(_front_door_rows(list(sources), mv, False, steps=20))

    async def main():
        fe = DistFrontend(str(tmp_path), n_workers=1, parallelism=1)
        await fe.start()
        try:
            assert (await fe.execute(
                "SHOW stream_fusion")) == [("on",)]
            for s in sources:
                await fe.execute(s)
            await fe.execute(mv)
            await fe.step(20)
            return {tuple(r)
                    for r in await fe.execute("SELECT * FROM q")}
        finally:
            await fe.close()

    got = asyncio.run(main())
    assert got == want and got


def test_cluster_session_approx_count_distinct(tmp_path):
    """Regression (ADVICE r5 medium): distributed
    approx_count_distinct MVs ship their HLL sketch-table ids in
    minput_table_ids — the worker-side rebuild must succeed and serve
    the same estimates as the in-process session."""
    from risingwave_tpu.cluster.session import DistFrontend

    sources = (
        "CREATE SOURCE bid WITH (connector='nexmark', "
        "nexmark.table.type='bid', nexmark.event.num=2000, "
        "nexmark.max.chunk.size=128, "
        "nexmark.generate.strings='false')",)
    mv = ("CREATE MATERIALIZED VIEW q AS SELECT auction, "
          "approx_count_distinct(bidder) AS d FROM bid "
          "GROUP BY auction")

    want = set(_front_door_rows(list(sources), mv, False, steps=16))

    async def main():
        fe = DistFrontend(str(tmp_path), n_workers=1, parallelism=1)
        await fe.start()
        try:
            for s in sources:
                await fe.execute(s)
            await fe.execute(mv)
            await fe.step(16)
            return {tuple(r)
                    for r in await fe.execute("SELECT * FROM q")}
        finally:
            await fe.close()

    got = asyncio.run(main())
    assert got == want and got


# -- monitor attribution ---------------------------------------------------


def test_fused_block_stage_metrics_attribution():
    """rw_actor_metrics keeps a row per LOGICAL executor inside a
    fused block: the absorbed filter/project stages stay observable."""
    from risingwave_tpu.utils.metrics import STREAMING

    async def main():
        fe = Frontend(rate_limit=8, min_chunks=8)
        for s in NEXMARK_SOURCES:
            await fe.execute(s)
        await fe.execute(
            "CREATE MATERIALIZED VIEW q AS SELECT auction, "
            "COUNT(*) AS c FROM bid WHERE price > 100 "
            "GROUP BY auction")
        await fe.step(6)
        await fe.close()

    run(main())
    stage_series = [(labels, v) for labels, v in
                    STREAMING.executor_rows.series()
                    if "::FilterExecutor" in labels.get("executor", "")]
    assert stage_series, \
        "no per-stage rows attributed inside the fused block"
    assert sum(v for _l, v in stage_series) > 0


# -- watermark_filter absorption (ISSUE 9 satellite) -----------------------


def test_watermark_filter_absorbed_oracle():
    """A wm→filter→project run fuses into ONE block whose traced late
    mask, runtime watermark advancement, persistence and post-chunk
    watermark emission are bit-identical to the sequential executors —
    including actually-late rows and the no-watermark-yet chunk."""
    from risingwave_tpu.frontend.opt.fusion import fuse_fragments
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.executors import MockSource
    from risingwave_tpu.stream.executors.simple import (
        FilterExecutor, ProjectExecutor,
    )
    from risingwave_tpu.stream.executors.test_utils import (
        collect_until_n_barriers,
    )
    from risingwave_tpu.stream.executors.fused import (
        FusedFragmentExecutor,
    )
    from risingwave_tpu.stream.executors.watermark_filter import (
        WATERMARK_STATE_SCHEMA, WatermarkFilterExecutor,
    )
    from risingwave_tpu.stream.message import (
        Barrier, BarrierKind, Watermark, is_chunk,
    )
    from risingwave_tpu.common.epoch import Epoch, EpochPair

    S = Schema.of(ts=DataType.TIMESTAMP, v=DataType.INT64,
                  s=DataType.VARCHAR)

    def barrier(n):
        prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
        return Barrier(EpochPair(Epoch.from_physical(n), prev),
                       BarrierKind.CHECKPOINT)

    def script():
        # chunk 1 has no watermark yet (nothing late by contract);
        # chunk 2's 5_000 and chunk 3's 2_000 are late once the
        # watermark passes them; NULL ts rows are never late
        data = [([10_000, 20_000, 15_000], [1, 2, 3]),
                ([5_000, 25_000, None], [4, 5, 6]),
                ([30_000, 2_000, 26_000], [7, 8, 9])]
        out = [barrier(1)]
        for b, (ts, v) in enumerate(data, start=2):
            out.append(StreamChunk.from_pydict(S, {
                "ts": ts, "v": v,
                "s": [f"x{x}" for x in v]}))
            out.append(barrier(b))
        return out, 4

    def arm(fused):
        msgs_script, nb = script()
        store = MemoryStateStore()
        wm_state = StateTable(191, WATERMARK_STATE_SCHEMA, [0], store)
        src = MockSource(S, msgs_script)
        wm = WatermarkFilterExecutor(src, 0, Interval(usecs=4_000),
                                     wm_state)
        filt = FilterExecutor(
            wm, InputRef(1, DataType.INT64) > lit(0))
        proj = ProjectExecutor(
            filt,
            exprs=[InputRef(0, DataType.TIMESTAMP),
                   InputRef(1, DataType.INT64) * lit(3),
                   InputRef(2, DataType.VARCHAR)],
            names=["ts", "v3", "s"],
            watermark_derivations={0: 0})
        top = proj
        if fused:
            top, fired, _details = fuse_fragments(proj)
            assert fired == 1
            assert isinstance(top, FusedFragmentExecutor)
            kinds = [st.kind for st in top.fused_stages.stages]
            assert kinds == ["watermark_filter", "filter", "project"]
        msgs = asyncio.run(collect_until_n_barriers(top, nb))
        out = []
        for m in msgs:
            if is_chunk(m):
                out.extend(("row", r) for r in m.to_records())
            elif isinstance(m, Watermark):
                out.append(("wm", m.col_idx, m.value))
        # the persisted watermark must round-trip identically too
        row = wm_state.get_row((0,))
        return out, None if row is None else tuple(row)

    on, wm_on = arm(True)
    off, wm_off = arm(False)
    assert on == off, "absorbed watermark_filter diverged"
    assert wm_on == wm_off and wm_on is not None
    assert any(t[0] == "wm" for t in on), "no watermarks observed"


def test_fragmenter_lowers_and_rebuilds_fused_join():
    """plan → fuse (join absorbs its input runs) → fragment →
    hash_join IR with left_fused/right_fused → build_fragment
    reconstructs the fused join (coordinator/worker parity), with
    row_id_gen stage runtimes rebuilt as bare counters."""
    from risingwave_tpu.frontend.catalog import Catalog
    from risingwave_tpu.frontend.fragmenter import Fragmenter
    from risingwave_tpu.frontend.parser import parse_many
    from risingwave_tpu.frontend.planner import (
        StreamPlanner, source_schema,
    )
    from risingwave_tpu.frontend.opt import rewrite_stream_plan
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.exchange import channel_for_test
    from risingwave_tpu.stream.plan_ir import build_fragment
    from risingwave_tpu.stream.executor import executor_children
    from risingwave_tpu.stream.executors.hash_join import (
        HashJoinExecutor,
    )

    opts_p = {"connector": "nexmark", "nexmark.table.type": "person",
              "nexmark.event.num": "500",
              "nexmark.generate.strings": "false"}
    opts_a = {"connector": "nexmark", "nexmark.table.type": "auction",
              "nexmark.event.num": "500",
              "nexmark.generate.strings": "false"}
    catalog = Catalog()
    catalog.add_source("person", source_schema(opts_p, None), opts_p)
    catalog.add_source("auction", source_schema(opts_a, None), opts_a)
    [(_t, stmt)] = parse_many(
        "CREATE MATERIALIZED VIEW v AS SELECT p.id, a.seller "
        "FROM person AS p JOIN auction AS a ON p.id = a.seller "
        "WHERE a.seller > 0")
    planner = StreamPlanner(catalog, MemoryStateStore(),
                            LocalBarrierManager(), definition="")
    plan = planner.plan("v", stmt.select, 7, rate_limit=4)
    consumer, report = rewrite_stream_plan(plan.consumer, "all",
                                           record=False, fusion=True)
    assert report.fired.get("fusion_grouping")

    def find_join(ex):
        if isinstance(ex, HashJoinExecutor):
            return ex
        for _a, _i, c in executor_children(ex):
            got = find_join(c)
            if got is not None:
                return got
        return None

    j0 = find_join(consumer)
    fused_sides = [i for i, s in enumerate(j0.sides)
                   if s.fused_input is not None]
    assert fused_sides, "join fusion did not fire on the planned query"

    graph = Fragmenter(1).lower(consumer)
    nodes = [n for f in graph.fragments for n in f.nodes]
    join_node = next(n for n in nodes if n["op"] == "hash_join")
    assert any(join_node.get(k) for k in ("left_fused", "right_fused")), \
        "fused input runs missing from the shipped hash_join IR"
    join_fi = next(i for i, f in enumerate(graph.fragments)
                   if any(n["op"] == "hash_join" for n in f.nodes))
    frag = graph.fragments[join_fi]
    # splice the upstream source fragments over the exchange_in
    # placeholders (the scheduler's expansion, single-actor case)
    nodes: list = []
    up_tail = {}
    for inp in frag.inputs:
        up_nodes = graph.fragments[inp.up_frag].nodes
        base = len(nodes)
        from risingwave_tpu.stream.plan_ir import remap_node_refs
        for n in up_nodes:
            nodes.append(remap_node_refs(
                n, {i: base + i for i in range(len(up_nodes))}))
        up_tail[inp.node_idx] = len(nodes) - 1
    base = len(nodes)
    remap = {}
    for i, n in enumerate(frag.nodes):
        if n["op"] == "exchange_in":
            remap[i] = up_tail[i]
        else:
            remap[i] = base + len(
                [j for j in range(i) if frag.nodes[j]["op"]
                 != "exchange_in"])
    for i, n in enumerate(frag.nodes):
        if n["op"] == "exchange_in":
            continue
        from risingwave_tpu.stream.plan_ir import remap_node_refs
        nodes.append(remap_node_refs(n, remap))
    _src, rebuilt = build_fragment(
        nodes, MemoryStateStore(),
        LocalBarrierManager(), channel_for_test, actor_id=1)
    j1 = find_join(rebuilt)
    assert j1 is not None
    for i in fused_sides:
        fs0, fs1 = j0.sides[i].fused_input, j1.sides[i].fused_input
        assert fs1 is not None
        assert fs1.describe() == fs0.describe()
        assert [f.data_type for f in fs1.out_schema] == \
            [f.data_type for f in fs0.out_schema]
        for st in fs1.stages:
            if st.kind == "row_id_gen":
                assert st.runtime is not None and \
                    hasattr(st.runtime, "_rebase")


def test_watermark_sentinel_narrow_int_time_col():
    """Regression: the no-watermark-yet sentinel must be the time
    column's OWN dtype-min — np.full would silently wrap int64-min to
    0 on an INT32 column and late every negative timestamp."""
    from risingwave_tpu.stream.executors.watermark_filter import (
        WatermarkRuntime,
    )

    S32 = Schema.of(t=DataType.INT32, v=DataType.INT64)
    rt = WatermarkRuntime()
    st = FusedStage("watermark_filter", "WatermarkFilterExecutor",
                    time_col=0, delay_usecs=0, runtime=rt)
    fs = FusedStages(S32, [st, FusedStage(
        "filter", "FilterExecutor",
        exprs=(InputRef(1, DataType.INT64) >= lit(0),))])
    assert fs.fusable_reason() is None
    chunk = StreamChunk.from_pydict(
        S32, {"t": [-5, -1, 3], "v": [1, 2, 3]})
    aug = fs.augment(chunk)
    thr = np.asarray(aug.columns[2].values)
    assert thr.dtype == np.int32
    assert (thr == np.iinfo(np.int32).min).all(), thr
    # and the traced mask keeps every row (no watermark yet)
    out_cols, vis2, _ops, _sr = fs.chain_body(
        list(aug.columns), np.asarray(aug.visibility),
        np.asarray(aug.ops), np)
    assert (vis2 == np.asarray(aug.visibility)).all(), \
        "negative timestamps dropped with no watermark"


# -- hop-window absorption (ISSUE 12 tentpole c) ---------------------------


HOP_MV = ("CREATE MATERIALIZED VIEW q AS SELECT window_start, "
          "COUNT(*) AS c, MAX(price) AS m "
          "FROM HOP(bid, date_time, INTERVAL '2' SECOND, "
          "INTERVAL '10' SECOND) GROUP BY window_start")


def test_hop_absorbed_vs_sequential_sql_oracle():
    """The agg's traced prelude replicates rows units× in-trace; the
    sequential HopWindowExecutor survives as the off arm — results
    must be bit-identical, and the fused plan must actually absorb
    the hop (EXPLAIN annotation)."""
    rows_off = _front_door_rows(NEXMARK_SOURCES, HOP_MV, False)
    rows_on = _front_door_rows(NEXMARK_SOURCES, HOP_MV, True)
    assert rows_on == rows_off and len(rows_on) > 1

    async def explain():
        fe = Frontend(rate_limit=4)
        for s in NEXMARK_SOURCES:
            await fe.execute(s)
        rows = await fe.execute(
            "EXPLAIN SELECT window_start, COUNT(*) AS c "
            "FROM HOP(bid, date_time, INTERVAL '2' SECOND, "
            "INTERVAL '10' SECOND) GROUP BY window_start")
        await fe.close()
        return "\n".join(r[0] for r in rows)
    text = run(explain())
    assert "absorbed HopWindowExecutor" in text, text


def test_hop_chain_body_matches_sequential_executor():
    """Unit oracle: the composed hop+filter chain on numpy equals the
    sequential HopWindowExecutor + FilterExecutor over random chunks —
    NULL timestamps dropped, update pairs preserved per copy."""
    from risingwave_tpu.common.types import Interval as Iv
    from risingwave_tpu.stream.executors.hop_window import (
        HopWindowExecutor,
    )
    from risingwave_tpu.stream.executors.simple import FilterExecutor

    S = Schema.of(ts=DataType.TIMESTAMP, v=DataType.INT64)
    rng = np.random.default_rng(11)
    cap = 32
    ts = rng.integers(0, 40_000_000, size=cap).astype(np.int64)
    v = rng.integers(-10, 10, size=cap).astype(np.int64)
    ok = rng.random(cap) > 0.2           # NULL timestamps
    vis = rng.random(cap) > 0.1
    ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
    ops[6] = int(Op.UPDATE_DELETE)
    ops[7] = int(Op.UPDATE_INSERT)
    chunk = StreamChunk(
        S, [Column(DataType.TIMESTAMP, ts, ok.copy()),
            Column(DataType.INT64, v, None)], vis, ops)

    hop_st = FusedStage("hop_window", "HopWindowExecutor",
                        time_col=0, slide_usecs=10_000_000,
                        size_usecs=30_000_000)
    pred = InputRef(1, DataType.INT64) >= lit(0)
    fs = FusedStages(S, [hop_st,
                         FusedStage("filter", "FilterExecutor",
                                    exprs=(pred,))])
    assert fs.fusable_reason() is None
    out_cols, vis2, ops2, _sr = fs.chain_body(
        list(chunk.columns), np.asarray(chunk.visibility),
        np.asarray(chunk.ops), np)
    got = StreamChunk(fs.out_schema,
                      [c for c in out_cols if c is not None],
                      np.asarray(vis2), np.asarray(ops2))

    class _Src:
        schema = S
        async def execute(self):
            from risingwave_tpu.common.epoch import Epoch, EpochPair
            from risingwave_tpu.stream.message import Barrier
            yield Barrier(EpochPair.new_initial(Epoch.from_physical(1)))
            yield chunk
            yield Barrier(EpochPair(
                Epoch.from_physical(2), Epoch.from_physical(1)))
        @property
        def pk_indices(self):
            return []
        identity = "mock"

    async def seq_records():
        hop = HopWindowExecutor(_Src(), 0, Iv(usecs=10_000_000),
                                Iv(usecs=30_000_000))
        filt = FilterExecutor(hop, pred)
        out = []
        async for m in filt.execute():
            from risingwave_tpu.stream.message import is_chunk
            if is_chunk(m):
                out.extend(m.to_records())
        return out

    want = run(seq_records())
    assert got.to_records() == want


def test_hop_watermark_rederivation_through_absorbed_stage():
    """A watermark on the time column re-derives to the window_start
    column (floor to slide, minus (units-1)*slide); all other
    watermarks are consumed — HopWindowExecutor's exact rule."""
    from risingwave_tpu.stream.message import Watermark
    S = Schema.of(ts=DataType.TIMESTAMP, v=DataType.INT64)
    fs = FusedStages(S, [FusedStage(
        "hop_window", "HopWindowExecutor", time_col=0,
        slide_usecs=10_000_000, size_usecs=30_000_000)])
    out = fs.derive_watermarks(
        Watermark(0, DataType.TIMESTAMP, 25_000_000))
    assert [(w.col_idx, w.value) for w in out] == [(2, 0)]
    assert fs.derive_watermarks(
        Watermark(1, DataType.INT64, 5)) == []


def test_hop_refuses_bad_shapes():
    S = Schema.of(ts=DataType.TIMESTAMP, v=DataType.INT64)
    hop = FusedStage("hop_window", "HopWindowExecutor", time_col=0,
                     slide_usecs=10, size_usecs=30)
    # non-head hop never composes
    with pytest.raises(ValueError):
        FusedStages(S, [FusedStage(
            "filter", "FilterExecutor",
            exprs=(InputRef(1, DataType.INT64) >= lit(0),)), hop])
    # float time column refuses
    SF = Schema.of(ts=DataType.FLOAT64, v=DataType.INT64)
    fsf = FusedStages(SF, [FusedStage(
        "hop_window", "HopWindowExecutor", time_col=0,
        slide_usecs=10, size_usecs=30)])
    assert "non-integer" in fsf.fusable_reason()
    # hop group keys (window_start) never map to raw input columns —
    # the parallel cut must refuse to dispatch on them
    fs = FusedStages(S, [hop])
    assert fs.input_positions([2]) is None
    assert fs.input_positions([1]) == [1]


def test_hop_executor_emits_pow2_copy_groups():
    """The rewritten HopWindowExecutor emits pow2 COPY-GROUP chunks
    (popcount(units) of them — e.g. 3 windows → a 2×-copy chunk + a
    1×-copy chunk), not `units` chunks, and every capacity stays a
    power of two so kernel backlogs pack tight."""
    from risingwave_tpu.common.types import Interval as Iv
    from risingwave_tpu.stream.executors.hop_window import (
        HopWindowExecutor,
    )
    from risingwave_tpu.stream.message import is_chunk
    S = Schema.of(ts=DataType.TIMESTAMP, v=DataType.INT64)
    chunk = StreamChunk.from_pydict(
        S, {"ts": [25_000_000, None], "v": [7, 8]})

    class _Src:
        schema = S
        async def execute(self):
            from risingwave_tpu.common.epoch import Epoch, EpochPair
            from risingwave_tpu.stream.message import Barrier
            yield Barrier(EpochPair.new_initial(Epoch.from_physical(1)))
            yield chunk
            yield Barrier(EpochPair(
                Epoch.from_physical(2), Epoch.from_physical(1)))
        @property
        def pk_indices(self):
            return []
        identity = "mock"

    async def main():
        hop = HopWindowExecutor(_Src(), 0, Iv(usecs=10_000_000),
                                Iv(usecs=30_000_000))
        chunks = []
        async for m in hop.execute():
            if is_chunk(m):
                chunks.append(m)
        return chunks

    chunks = run(main())
    assert len(chunks) == bin(3).count("1")     # 3 = 2 + 1 copies
    for c in chunks:
        cap = c.capacity
        assert cap & (cap - 1) == 0, "capacity must stay pow2"
    recs = [r for c in chunks for _op, r in c.to_records()]
    # NULL ts dropped; 3 windows for the valid row
    assert sorted(r[2] for r in recs) == [0, 10_000_000, 20_000_000]
    assert all(r[1] == 7 for r in recs)
