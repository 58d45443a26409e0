"""An inner hash join evaluates its own condition (ISSUE 46).

The conjuncts of an inner join's ON / WHERE that are no hash keys are
the join's own condition: the `filter_pushdown` rule sinks a filter
that reads both sides of an inner join into it, and the join runs it
on the pairs it has just matched
(`HashJoinExecutor._pairs_chunk`, through
`FilterExecutor.apply_predicate`). Held here:

- the executor: a join with a condition emits what a `FilterExecutor`
  above the same join without one emits, chunk for chunk, row for row
  and op for op;
- the plan: `EXPLAIN` of the benchmark's four configurations with such
  a condition, the IR round trip, and the view at parallelism 2 over
  the cluster and 4 on the CPU mesh.
"""

import asyncio
import collections
import json
import os
import re
import sys

import numpy as np
import pytest

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.expr.expr import InputRef
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.stream.executors.hash_join import (
    HashJoinExecutor, JoinType,
)
from risingwave_tpu.stream.executors.simple import FilterExecutor
from risingwave_tpu.stream.executors.test_utils import (
    MockSource, collect_until_n_barriers,
)
from risingwave_tpu.stream.message import (
    Barrier, BarrierKind, is_barrier, is_chunk,
)
from risingwave_tpu.utils.metrics import STREAMING as S

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 4600000046

# -- the executor -----------------------------------------------------------

L = Schema.of(lk=DataType.INT64, x=DataType.INT64, lid=DataType.INT64)
R = Schema.of(rk=DataType.INT64, y=DataType.INT64, name=DataType.VARCHAR,
              rid=DataType.INT64)
I, D, UD, UI = Op.INSERT, Op.DELETE, Op.UPDATE_DELETE, Op.UPDATE_INSERT


def _condition():
    """l.x >= r.y over the join's output (lk, x, lid, rk, y, name, rid)."""
    return InputRef(1, DataType.INT64) >= InputRef(4, DataType.INT64)


def barrier(n: int) -> Barrier:
    prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
    return Barrier(EpochPair(Epoch.from_physical(n), prev),
                   BarrierKind.CHECKPOINT)


def lrows(rows, ops=None):
    return StreamChunk.from_pydict(
        L, {"lk": [r[0] for r in rows], "x": [r[1] for r in rows],
            "lid": [r[2] for r in rows]}, ops=ops)


def rrows(rows, ops=None):
    return StreamChunk.from_pydict(
        R, {"rk": [r[0] for r in rows], "y": [r[1] for r in rows],
            "name": [f"n{r[2]}" for r in rows],
            "rid": [r[2] for r in rows]}, ops=ops)


# side -> the chunks of each epoch (one list an epoch)
SCRIPTS = {
    # inserts alone: pairs on both sides of the condition in one chunk
    "append_only": {
        "l": [[lrows([(1, 5, 0), (1, 1, 1), (2, 7, 2)])],
              [lrows([(2, 9, 3), (3, 4, 4)])],
              [lrows([(1, 3, 5)])]],
        "r": [[rrows([(1, 3, 0), (2, 9, 1)])],
              [rrows([(3, 0, 2), (1, 4, 3)])],
              [rrows([(2, 1, 4)])]]},
    # q5's shape: both inputs send U-/U+ pairs, and the halves of a
    # pair fall on both sides of the condition, either way round
    "both_retract": {
        "l": [[lrows([(1, 5, 0), (2, 1, 1), (3, 4, 2)])],
              [lrows([(1, 5, 0), (1, 1, 0), (2, 1, 1), (2, 8, 1)],
                     ops=[UD, UI, UD, UI])],
              [lrows([(3, 4, 2), (3, 6, 2)], ops=[UD, UI]),
               lrows([(1, 1, 0)], ops=[D])]],
        "r": [[rrows([(1, 3, 0), (2, 3, 1), (3, 5, 2)])],
              [rrows([(3, 5, 2), (3, 2, 2)], ops=[UD, UI])],
              [rrows([(2, 3, 1), (2, 9, 1), (1, 3, 0), (1, 0, 0)],
                     ops=[UD, UI, UD, UI])]]},
    # every pair of the second epoch's chunks fails the condition
    "emptied": {
        "l": [[lrows([(1, 5, 0)])],
              [lrows([(1, 0, 1), (1, 2, 2)])],
              [lrows([(1, 9, 3)])]],
        "r": [[rrows([(1, 3, 0)])],
              [rrows([(1, 7, 1)])],
              [rrows([(1, 1, 2)])]]},
    # NULL in a compared column: not satisfied, on either side
    "null_compared": {
        "l": [[lrows([(1, None, 0), (1, 5, 1), (2, None, 2)])],
              [lrows([(2, 6, 3)])],
              [lrows([(1, None, 0)], ops=[D])]],
        "r": [[rrows([(1, 3, 0), (2, None, 1)])],
              [rrows([(1, None, 2), (2, 1, 3)])],
              [rrows([(2, None, 1)], ops=[D])]]},
}
_TABLE_IDS = iter(range(4600, 4800, 2))


def _script(epochs):
    out = [barrier(1)]
    for n, chunks in enumerate(epochs, start=2):
        out += list(chunks) + [barrier(n)]
    return out


def _run(case: str, own_condition: bool, device_payload: bool):
    """(messages, the join's name in the books) of one run: the join
    with its own condition, or a FilterExecutor above the plain join."""
    store = MemoryStateStore()
    tid = next(_TABLE_IDS)
    lt = StateTable(tid, L, [2], store, dist_key_indices=[])
    rt = StateTable(tid + 1, R, [3], store, dist_key_indices=[])
    ex = join = HashJoinExecutor(
        MockSource(L, _script(SCRIPTS[case]["l"])),
        MockSource(R, _script(SCRIPTS[case]["r"])),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt,
        device_payload=device_payload,
        condition=_condition() if own_condition else None)
    if not own_condition:
        ex = FilterExecutor(join, _condition())
    n = len(SCRIPTS[case]["l"]) + 1
    return asyncio.run(collect_until_n_barriers(ex, n)), f"t{tid}"


def _books(table: str) -> dict:
    def series(metric, **labels):
        return sum(v for l, v in metric.series()
                   if all(l.get(k) == w for k, w in labels.items()))
    return {"out": series(S.join_output_rows, table=table),
            "kept": series(S.join_condition_rows, table=table,
                           result="kept"),
            "dropped": series(S.join_condition_rows, table=table,
                              result="dropped"),
            "seconds": series(S.join_condition_seconds, table=table)}


@pytest.mark.parametrize("device_payload", [True, False],
                         ids=["payload_lanes", "arena"])
@pytest.mark.parametrize("case", list(SCRIPTS))
def test_the_joins_own_condition_is_the_filter_above_it(
        case, device_payload):
    got, table = _run(case, True, device_payload)
    want, plain = _run(case, False, device_payload)
    assert [is_barrier(m) for m in got] == [is_barrier(m) for m in want]
    for g, w in zip(got, want):
        if not is_chunk(g):
            continue
        # the same chunk: capacity, visibility, and op and row of
        # every visible position
        assert g.capacity == w.capacity
        vis = np.asarray(w.visibility)
        assert vis.any()                   # neither emits an empty chunk
        np.testing.assert_array_equal(np.asarray(g.visibility), vis)
        np.testing.assert_array_equal(np.asarray(g.ops)[vis],
                                      np.asarray(w.ops)[vis])
        assert g.to_records() == w.to_records()
    books, plain_books = _books(table), _books(plain)
    # the pairs matched on the keys, before the condition, either way
    assert books["out"] == plain_books["out"] > 0
    assert books["kept"] + books["dropped"] == books["out"]
    assert books["kept"] == sum(
        m.cardinality() for m in got if is_chunk(m))
    assert books["seconds"] > 0
    assert plain_books["kept"] == plain_books["seconds"] == 0
    if case == "emptied":
        # the second epoch matched pairs and emitted no chunk
        second = got[[i for i, m in enumerate(got) if is_barrier(m)][1]:
                     [i for i, m in enumerate(got) if is_barrier(m)][2]]
        assert not [m for m in second if is_chunk(m)]
        assert books["dropped"] >= 3
    if case == "null_compared":
        rows = [row for m in got if is_chunk(m)
                for _op, row in m.to_records()]
        assert rows and all(r[1] is not None and r[4] is not None
                            for r in rows)


@pytest.mark.parametrize("join_type", [
    JoinType.LEFT_OUTER, JoinType.RIGHT_OUTER, JoinType.FULL_OUTER,
    JoinType.LEFT_SEMI, JoinType.LEFT_ANTI])
def test_only_an_inner_join_takes_a_condition(join_type):
    store = MemoryStateStore()
    with pytest.raises(ValueError, match="only an INNER join"):
        HashJoinExecutor(
            MockSource(L, []), MockSource(R, []), [0], [0],
            StateTable(4590, L, [2], store, dist_key_indices=[]),
            StateTable(4591, R, [3], store, dist_key_indices=[]),
            join_type=join_type, condition=_condition())


# -- the plan ---------------------------------------------------------------

def _bench():
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    return run


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _small(config: dict, more: str = "") -> list:
    """The configuration's DDL at 512-row chunks (67 for auctions)."""
    out = []
    for ddl in config["ddl"]:
        rows = 67 if "'auction'" in ddl else 512
        ddl, n = re.subn(r"max\.chunk\.size=\d+",
                         f"max.chunk.size={rows}{more}", ddl)
        assert n == ("CREATE SOURCE" in ddl)
        out.append(ddl.format(seed=SEED))
    return out


def _joins(consumer):
    return [getattr(ex, "inner", ex)
            for _p, ex in _bench().walk_executors(consumer)
            if isinstance(getattr(ex, "inner", ex), HashJoinExecutor)]


# configuration -> what stands directly above the join, what the
# rule's line says it moved
PLANS = {
    "nexmark-q9": ("GroupTopNExecutor", 2),
    "nexmark-q5": ("ProjectExecutor", 1),
    "nexmark-q5-wm": ("ProjectExecutor", 1),
    "nexmark-q4": ("HashAggExecutor(actor=0)[fused:ProjectExecutor]", 2),
}


@pytest.mark.parametrize("name", list(PLANS))
def test_explain_prints_the_condition_on_the_joins_line(name):
    from risingwave_tpu.frontend.session import Frontend
    config = _config(name)

    async def explained():
        fe = Frontend()
        try:
            for ddl in _small(config)[:-1]:
                await fe.execute(ddl)
            select = config["ddl"][-1].split(" AS", 1)[1]
            return "\n".join(
                r[0] for r in await fe.execute("EXPLAIN " + select))
        finally:
            await fe.close()

    text = asyncio.run(explained())
    pre, post = text.split("-- rewritten plan")
    post = post.split("-- compiled kernel costs")[0]
    above, moved = PLANS[name]
    # before the rewrite the conjuncts are filters above the join
    assert len(re.findall(r"FilterExecutor\n(?:\s+FilterExecutor\n)?"
                          r"\s+HashJoinExecutor", pre)) == 1
    rule, = [ln for ln in post.splitlines()
             if ln.startswith("--   rule filter_pushdown:")]
    assert rule.split(": ", 1)[1].startswith(
        "0 filter(s) pushed below joins; the join's own condition "
        "took ($")
    assert rule.count(" into HashJoinExecutor(inner") == moved
    assert f"filter_pushdown={moved}" in post.splitlines()[0]
    # after it: no filter and no block anywhere, the consumer directly
    # above the join, the condition on the join's line
    assert not re.search(r"^\s*FilterExecutor", post, re.M)
    assert "FusedFragmentExecutor" not in post
    lines = post.splitlines()
    at, = [i for i, ln in enumerate(lines)
           if ln.lstrip().startswith("HashJoinExecutor(inner")]
    assert lines[at - 1].lstrip().startswith(above)
    assert re.search(r"  -- condition: \(.*\$\d+:\w+ >= \$\d+:\w+.*\)$",
                     lines[at])
    assert lines[at].count(" and ") == moved - 1


@pytest.mark.parametrize("name", ["nexmark-q9", "nexmark-q5"])
@pytest.mark.parametrize("parallelism", [1, 2])
def test_the_ir_round_trip_keeps_the_condition(name, parallelism):
    """fragmenter -> plan_ir: the `hash_join` node carries the
    condition and a fragment rebuilt from it holds a join with the
    same one, so a cluster's workers evaluate it too."""
    from risingwave_tpu.frontend.catalog import Catalog
    from risingwave_tpu.frontend.fragmenter import Fragmenter
    from risingwave_tpu.frontend.opt import apply_rewrites
    from risingwave_tpu.frontend.parser import parse_many
    from risingwave_tpu.frontend.planner import (
        StreamPlanner, source_schema,
    )
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.exchange import channel_for_test
    from risingwave_tpu.stream.plan_ir import build_fragment

    config = _config(name)
    catalog = Catalog()
    for t in ("auction", "bid"):
        opts = {"connector": "nexmark", "nexmark.table.type": t}
        catalog.add_source(t, source_schema(opts, None), opts)
    [(_text, stmt)] = parse_many(config["ddl"][-1])
    planner = StreamPlanner(catalog, MemoryStateStore(),
                            LocalBarrierManager(), definition="")
    plan = planner.plan(config["view"], stmt.select, 7, rate_limit=4)
    assert _joins(plan.consumer)[0].condition is None
    report = apply_rewrites(plan, "all", fusion=True,
                            dist_parallelism=parallelism)
    assert not report.fallbacks
    planned, = _joins(plan.consumer)
    assert planned.condition is not None
    graph = Fragmenter(parallelism).lower(plan.consumer)
    frag, node = next((f, n) for f in graph.fragments for n in f.nodes
                      if n["op"] == "hash_join")
    assert node["condition"]["t"] == "bin"
    # through JSON, as the scheduler ships it; the exchanges'
    # placeholders become remote inputs (never connected here)
    nodes = json.loads(json.dumps(frag.nodes))
    for inp in frag.inputs:
        nodes[inp.node_idx] = {
            "op": "remote_input", "host": "127.0.0.1", "port": 1,
            "up_actor": 1, "schema": inp.schema}
    _src, consumer = build_fragment(
        nodes, MemoryStateStore(), LocalBarrierManager(),
        channel_for_test, actor_id=9)
    rebuilt, = _joins(consumer)
    assert repr(rebuilt.condition) == repr(planned.condition)
    assert rebuilt.plan_note == planned.plan_note
    assert rebuilt._condition_cols == planned._condition_cols


async def _reference_view(config: dict, fe, view: str):
    run = _bench()
    ref = run.load_module("reference", config["reference"])
    gen = run.load_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **config["generator"])
    readers = run.checkpointed_rows(run.source_readers(fe, view))
    got = collections.Counter(
        tuple(r) for r in await fe.execute(f"SELECT * FROM {view}"))
    return got, ref.reference([dict(r) for r in readers], gen)


@pytest.mark.parametrize("name", ["nexmark-q9", "nexmark-q5"])
def test_on_the_cpu_mesh_at_parallelism_4_the_view_is_the_references(name):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from risingwave_tpu.frontend.session import Frontend
    config = _config(name)

    async def run():
        fe = Frontend(rate_limit=1, min_chunks=1, parallelism=4)
        try:
            for ddl in _small(config):
                await fe.execute(ddl)
            await fe.step(12)
            await fe.execute("FLUSH")
            view = config["view"]
            join, = _joins(fe.actors[fe.catalog.mvs[view].actor_id]
                           .consumer)
            assert join.condition is not None
            assert join.rebuild_opts["mesh"] is not None
            return await _reference_view(config, fe, view)
        finally:
            await fe.close()

    got, want = asyncio.run(run())
    assert got == want
    assert sum(got.values()) >= 5


@pytest.mark.parametrize("name", ["nexmark-q9", "nexmark-q5"])
def test_over_the_cluster_at_parallelism_2_the_view_is_the_single_process_s(
        name, tmp_path):
    """Two workers, the join's fragment on both: the workers' joins
    hold the condition (it crossed the IR) and the view equals the
    in-process session's over the same bounded stream."""
    from risingwave_tpu.cluster.session import DistFrontend
    from risingwave_tpu.frontend.session import Frontend
    config = _config(name)
    ddl = _small(config, more=", nexmark.event.num=6000")
    view = config["view"]

    async def single():
        fe = Frontend(min_chunks=8)
        try:
            for stmt in ddl:
                await fe.execute(stmt)
            await fe.step(30)
            return collections.Counter(
                tuple(r) for r in await fe.execute(f"SELECT * FROM {view}"))
        finally:
            await fe.close()

    async def cluster():
        fe = DistFrontend(str(tmp_path), n_workers=2, parallelism=2)
        await fe.start()
        try:
            for stmt in ddl:
                await fe.execute(stmt)
            await fe.step(30)
            job = fe.cluster.jobs[view]
            fi, node = next(
                (fi, n) for fi, f in enumerate(job.graph.fragments)
                for n in f.nodes if n["op"] == "hash_join")
            assert node.get("condition")
            assert {s for _a, s in job.placements[fi]} == {0, 1}
            return collections.Counter(
                tuple(r) for r in await fe.execute(f"SELECT * FROM {view}"))
        finally:
            await fe.close()

    got, want = asyncio.run(cluster()), asyncio.run(single())
    assert got == want
    assert sum(got.values()) >= 5
