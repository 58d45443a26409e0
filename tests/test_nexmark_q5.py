"""NEXmark q5 as upstream writes it (ISSUE 33): the text of the
benchmark's `nexmark-q5` configuration, read from the file, through a
SQL session, compared as a multiset with the benchmark's plain
reference (`benchmark/reference/nexmark_q5.py`); the front end's split
of a `JOIN ... ON` into hash keys and condition; the books the query
keeps on the way (rows into and out of the join, through its
condition and through the HOP, the longest chain a probe walked); and
the two places where a program's shape must not follow the join's
output: a fused chain above a join and the probe's pair buffer.

Epochs are a fixed number of chunks per reader, so nothing here waits
on a clock. Only the source's chunk size is rewritten, to cut the
stream small: the view's text is the file's.
"""

import asyncio
import collections
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 3300000033
CHUNK = 1024

# The reader that runs ahead, by three chunks or more.
# 1,024 bids are 0.11 s of event time: a group's count rises across
# barriers and a window's largest count changes hands every second
# barrier (the generator's hot auction moves on every 1,533 bids), so
# both inputs of the join retract. With a reader ahead the join is
# driven from the other side: the newest windows' maximum is known
# before their counts (`max_ahead`) or after (`counts_ahead`).
CASES = {"lockstep": None, "counts_ahead": "left", "max_ahead": "right"}
SMALL, LARGE = 14, 5            # barriers at 1 chunk, then at 8


def _bench_module(directory: str, name: str):
    """A module of `benchmark/`, loaded the way `run.py` loads it."""
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    return run.load_module(directory, name)


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "nexmark-q5.json")) as f:
        return json.load(f)


def _history(rows) -> dict:
    out = {}
    for _seq, epoch, ts, interval_s, name, value, _dom in rows:
        out.setdefault(epoch, {"ts": ts, "interval_s": interval_s})[
            name] = value
    # the first row after HISTORY.clear() (a DDL barrier, before any
    # data) takes the registry's process-wide counter totals, other
    # tests' among them, as its delta
    del out[min(out)]
    return out


async def _drive(config: dict, case: str) -> dict:
    import run
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.stream.executors.hash_join import HashJoinExecutor
    from risingwave_tpu.utils.metrics import HISTORY

    HISTORY.clear()
    TOPOLOGY.clear()            # process-wide books of state rows
    fe = Frontend()
    try:
        await fe.execute("SET streaming_rate_limit = 1")
        await fe.execute("SET streaming_min_chunks = 1")
        for ddl in config["ddl"]:
            ddl, n = re.subn(r"max\.chunk\.size=\d+",
                             f"max.chunk.size={CHUNK}", ddl)
            assert n == ("CREATE SOURCE" in ddl)
            await fe.execute(ddl.format(seed=SEED))
        view = config["view"]

        def pace(chunks_by_side):
            for _name, side, source in run.source_readers(fe, view):
                source = getattr(source, "inner", source)  # the monitor
                source.rate_limit = source.min_chunks = \
                    chunks_by_side[side]

        def lead():
            rows = {r["side"]: r["rows"] for r in run.checkpointed_rows(
                run.source_readers(fe, view))}
            return rows["left"] - rows["right"]

        if CASES[case] is not None:
            # a reader's pace takes hold a barrier or two after it is
            # set: run one side at two chunks a barrier for a while,
            # then both at one until the lead stands still
            pace({side: 1 + (side == CASES[case])
                  for side in ("left", "right")})
            await fe.step(4)
            pace({"left": 1, "right": 1})
            await fe.step(2)
        lead_then = lead()
        await fe.step(SMALL)
        pace({"left": 8, "right": 8})
        await fe.step(LARGE)
        await fe.execute("FLUSH")
        actor = fe.actors[fe.catalog.mvs[view].actor_id]
        join, = [ex for _p, ex in run.walk_executors(actor.consumer)
                 if isinstance(getattr(ex, "inner", ex),
                               HashJoinExecutor)]
        join = getattr(join, "inner", join)
        return {
            "view": collections.Counter(
                tuple(r) for r in await fe.execute(
                    f"SELECT * FROM {view}")),
            "readers": run.checkpointed_rows(
                run.source_readers(fe, view)),
            "history": _history(
                await fe.execute("SELECT * FROM rw_metrics_history")),
            "rewrites": await fe.execute(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites"),
            "topology": await fe.execute(
                "SELECT * FROM rw_state_topology"),
            "lead_then": lead_then,
            "join": join._books_table,
            "sides": [f"join.t{s.table.table_id}" for s in join.sides],
            "plan": [type(getattr(ex, "inner", ex)).__name__ for _p, ex
                     in run.walk_executors(actor.consumer)],
        }
    finally:
        await fe.close()


@pytest.fixture(scope="module")
def q5():
    config = _config()
    _bench_module("reference", "nexmark_gen")
    return {"config": config,
            **{case: asyncio.run(_drive(config, case)) for case in CASES}}


def _sum(run_: dict, prefix: str, suffix: str = "") -> float:
    return sum(v for h in run_["history"].values() for k, v in h.items()
               if k.startswith(prefix) and k.endswith(suffix))


@pytest.mark.parametrize("case", list(CASES))
def test_view_equals_the_benchmarks_reference(q5, case):
    config, got = q5["config"], q5[case]
    gen = _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **config["generator"])
    ref = _bench_module("reference", config["reference"])
    readers = [dict(r) for r in got["readers"]]
    want = ref.reference(readers, gen)
    assert sum(want.values()) >= 5           # a row a window, and ties
    assert got["view"] == want
    by_table = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in got["topology"]:
        if mv == config["view"]:
            by_table[table_id] += n
    assert max(by_table.values()) == ref.resident_rows(readers, gen)
    assert not [r for r in got["rewrites"]
                if str(r[3]).startswith("FALLBACK")]


def test_the_text_is_upstreams_and_plans_on_the_device_path(q5):
    text = q5["config"]["ddl"][-1]
    assert "JOIN (" in text and "WHERE" not in text
    assert "ON AuctionBids.starttime = MaxBids.starttime_c AND " \
        "AuctionBids.num >= MaxBids.maxn" in text
    assert text.count("HOP(bid, date_time, INTERVAL '2' SECOND, "
                      "INTERVAL '10' SECOND)") == 2
    run_ = q5["lockstep"]
    # the registry is the process's: other files' jobs are in it too
    detail = " ".join(str(r[3]) for r in run_["rewrites"]
                      if r[0] == "q5")
    # both HOPs fused into their aggregates, the condition into the
    # join itself, both join sides on their preludes; nothing but the
    # selection of two columns between the join and the view
    assert detail.count(
        "agg absorbed HopWindowExecutor→ProjectExecutor") == 2
    assert "the join's own condition took ($1:int64 >= $3:int64) into " \
        "HashJoinExecutor(inner" in detail
    assert "block " not in detail
    assert "join side 0 absorbed" in detail
    assert "join side 1 absorbed" in detail
    assert run_["plan"].count("HashAggExecutor") == 3
    assert run_["plan"].count("SourceExecutor") == 2
    assert "FusedFragmentExecutor" not in run_["plan"]
    assert "FilterExecutor" not in run_["plan"]
    at = run_["plan"].index("HashJoinExecutor")
    assert run_["plan"][at - 2:at] == ["MaterializeExecutor",
                                       "ProjectExecutor"]


def test_the_readers_stood_as_the_cases_say(q5):
    for case, ahead in CASES.items():
        rows = {r["side"]: r["rows"] for r in q5[case]["readers"]}
        lead = rows["left"] - rows["right"]
        assert lead == q5[case]["lead_then"]     # from then on, lockstep
        if ahead is None:
            assert lead == 0
        else:
            assert lead * (1 if ahead == "left" else -1) >= 3 * CHUNK
        assert min(rows.values()) >= (SMALL + 8 * LARGE) * CHUNK


@pytest.mark.parametrize("case", list(CASES))
def test_both_inputs_of_the_join_retract(q5, case):
    run_, join = q5[case], q5[case]["join"]
    for side in ("left", "right"):
        pre = f"join_input_rows.{join}.{side}."
        pairs = _sum(run_, pre, "update_delete")
        assert pairs > 0
        assert pairs == _sum(run_, pre, "update_insert")
        assert _sum(run_, pre, "insert") > 0
    # the count side takes thousands of rows, the max side a handful
    assert _sum(run_, f"join_input_rows.{join}.left.") > \
        50 * _sum(run_, f"join_input_rows.{join}.right.")
    bench = _bench_module("layer_metrics", "join_retract_share")
    taken_back = _sum(run_, "join_input_rows.", "delete")
    assert bench.read({"history": run_["history"]}) == pytest.approx(
        100.0 * taken_back / _sum(run_, "join_input_rows."))
    # the retractable MAX takes the count aggregate's update pairs
    assert _sum(run_, "agg_input_rows.", ".update_delete") > 0


@pytest.mark.parametrize("case", list(CASES))
def test_the_joins_output_and_its_condition_are_on_the_books(q5, case):
    run_, join = q5[case], q5[case]["join"]
    out = _sum(run_, f"join_output.{join}.rows")
    kept = _sum(run_, f"join_condition.{join}.kept")
    dropped = _sum(run_, f"join_condition.{join}.dropped")
    # the join files the pairs it matched on the keys, and what its
    # own condition made of each of them: it keeps few
    assert out == kept + dropped
    assert 0 < kept < dropped
    # what the condition kept, inserts less deletes, is the view
    assert kept >= sum(run_["view"].values())
    # the join evaluates it while it builds its pairs' chunk: the
    # seconds lie inside its `join.pairs` stage, barrier by barrier
    seconds = _sum(run_, f"join_condition.{join}.seconds")
    assert 0 < seconds < _sum(run_, "stage.host_emit.join.pairs")
    for h in run_["history"].values():
        assert h.get(f"join_condition.{join}.seconds", 0) <= \
            h.get("stage.host_emit.join.pairs", 0)
    bids = sum(r["rows"] for r in run_["readers"])
    bench = _bench_module("layer_metrics", "join_out_rows_per_source_row")
    assert bench.read({"history": run_["history"]}) == pytest.approx(
        out / bids)
    bench = _bench_module("layer_metrics", "join_condition_share")
    assert 0 < bench.read({"history": run_["history"]}) < 100


@pytest.mark.parametrize("case", list(CASES))
def test_a_hop_of_ten_by_two_seconds_makes_five_rows_of_one(q5, case):
    run_ = q5[case]
    tables = {k.split(".")[1] for h in run_["history"].values()
              for k in h if k.startswith("hop_rows.")
              and _sum(run_, k) > 0}
    assert len(tables) == 2                  # the two counting GROUP BYs
    rows_in = sorted(_sum(run_, f"hop_rows.{t}.in") for t in tables)
    assert rows_in == sorted(r["rows"] for r in run_["readers"])
    for t in tables:
        assert _sum(run_, f"hop_rows.{t}.out") == \
            5 * _sum(run_, f"hop_rows.{t}.in")


def test_the_longest_chain_is_a_windows_groups(q5):
    """A change of a window's maximum probes the count side on that
    window: the probe walks every group the window held by then,
    hundreds of rows on one key."""
    run_ = q5["lockstep"]
    left, right = run_["sides"]
    longest = max(h.get(f"join_probe.{left}.longest_chain", 0)
                  for h in run_["history"].values())
    ref = _bench_module("reference", "nexmark_q5")
    gen = _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **q5["config"]["generator"])
    rows = next(r["rows"] for r in run_["readers"] if r["side"] == "left")
    ws, _auction, _num = ref.window_counts(rows, gen)
    fullest = int(np.max(np.unique(ws, return_counts=True)[1]))
    # chains keep their tombstoned rows (an updated count is a new
    # row); a window's maximum changes while it fills, not at its end
    assert 500 <= longest <= 3 * fullest
    # the max side holds one live row a window, and its updates
    assert 1 <= max(h.get(f"join_probe.{right}.longest_chain", 0)
                    for h in run_["history"].values()) < fullest


def test_the_walk_steps_a_windows_runs_not_its_rows(q5):
    """The probe steps a key's runs (the barriers that brought the
    window rows), not its rows: where the count side's chain holds 500
    rows and more, the walk takes a few dozen steps at most; what it
    expands and what it keeps are counted beside it."""
    run_ = q5["lockstep"]
    left, _right = run_["sides"]
    long_ones = [h for h in run_["history"].values()
                 if h.get(f"join_probe.{left}.longest_chain", 0) >= 500]
    assert long_ones
    for h in long_ones:
        assert 1 <= h[f"join_probe.{left}.walk_steps"] < 64
    candidates = _sum(run_, f"join_probe.{left}.candidates")
    assert candidates >= _sum(run_, f"join_probe.{left}.pairs") > 0
    assert candidates >= 500


def test_readers_of_a_program_without_the_books_read_nothing():
    record = {"history": {1: {"ts": 1.0, "interval_s": 0.5,
                              "source_rows": 10.0,
                              "phase.host_emit": 0.1}}}
    for name in ("join_out_rows_per_source_row", "join_retract_share",
                 "join_condition_share"):
        assert _bench_module("layer_metrics", name).read(record) is None


def test_the_where_form_is_the_same_plan(q5):
    """Upstream's text with the `>=` moved to a WHERE: the same
    executors and rewrites, and the same rows."""
    import run
    from risingwave_tpu.frontend.session import Frontend
    config = q5["config"]
    where = config["ddl"][-1].replace(
        " AND AuctionBids.num >=", "\nWHERE AuctionBids.num >=")
    assert where != config["ddl"][-1]

    async def deployed():
        fe = Frontend()
        try:
            await fe.execute(config["ddl"][0].format(seed=SEED))
            await fe.execute(where)
            actor = fe.actors[fe.catalog.mvs["q5"].actor_id]
            return ([type(getattr(ex, "inner", ex)).__name__ for _p, ex
                     in run.walk_executors(actor.consumer)],
                    {(r[1], r[2], r[3]) for r in await fe.execute(
                        "SELECT job, rule, fired, detail "
                        "FROM rw_plan_rewrites") if r[0] == "q5"})
        finally:
            await fe.close()

    plan, rewrites = asyncio.run(deployed())
    assert plan == q5["lockstep"]["plan"]
    # the registry keeps the rows of every q5 this process deployed:
    # the WHERE form adds none of its own
    assert rewrites == {(r[1], r[2], r[3])
                        for r in q5["lockstep"]["rewrites"]
                        if r[0] == "q5"}


# -- JOIN ... ON: hash keys and condition -----------------------------------

TABLES = ("CREATE TABLE l (k bigint, x bigint, s bigint)",
          "CREATE TABLE r (k bigint, y bigint)")


async def _session(*statements):
    from risingwave_tpu.frontend.session import Frontend
    fe = Frontend()
    try:
        for stmt in TABLES + statements:
            await fe.execute(stmt)
    except BaseException:
        await fe.close()
        raise
    return fe


def _chain(fe, view="m"):
    import run
    _bench_module("reference", "nexmark_gen")
    actor = fe.actors[fe.catalog.mvs[view].actor_id]
    return [getattr(ex, "inner", ex)
            for _p, ex in run.walk_executors(actor.consumer)]


ON_CASES = {
    # ON text: (left keys, right keys, conjuncts of the join's condition)
    "l.k = r.k": ([0], [0], 0),
    "l.k = r.k AND l.x >= r.y": ([0], [0], 1),
    "l.x >= r.y AND r.k = l.k": ([0], [0], 1),
    # a conjunct of one side is sunk below that side
    "l.k = r.k AND l.x >= r.y AND l.s = r.y AND r.y <> 3":
        ([0, 2], [0, 1], 1),
    # a column = column of one side is no hash key either
    "l.k = r.k AND l.x = l.s": ([0], [0], 0),
}


def _conjuncts(e) -> list:
    from risingwave_tpu.expr.expr import BinaryOp
    if isinstance(e, BinaryOp) and e.op == "and":
        return _conjuncts(e.left) + _conjuncts(e.right)
    return [] if e is None else [e]


@pytest.mark.parametrize("fusion", ["on", "off"])
@pytest.mark.parametrize("on", list(ON_CASES))
def test_on_splits_into_hash_keys_and_condition(on, fusion):
    """The planner takes the ON's hash keys; the pushdown rule hands
    the join the other conjuncts that read both sides and sinks the
    rest below the side they read, fusion on or off: no filter is left
    above the join."""
    from risingwave_tpu.frontend.opt.checker import expr_refs
    from risingwave_tpu.stream.executors.fused import (
        FusedFragmentExecutor,
    )
    from risingwave_tpu.stream.executors.hash_join import HashJoinExecutor
    from risingwave_tpu.stream.executors.simple import FilterExecutor

    async def planned():
        fe = await _session(
            f"SET stream_fusion = {fusion}",
            f"CREATE MATERIALIZED VIEW m AS SELECT l.x, r.y "
            f"FROM l JOIN r ON {on}")
        try:
            chain = _chain(fe)
            at, = [i for i, ex in enumerate(chain)
                   if isinstance(ex, HashJoinExecutor)]
            join = chain[at]
            assert not [ex for ex in chain[:at] if isinstance(
                ex, (FilterExecutor, FusedFragmentExecutor))]
            conjuncts = _conjuncts(join.condition)
            assert all(min(expr_refs(c)) < join.n_left <= max(
                expr_refs(c)) for c in conjuncts)
            return ([list(s.key_indices) for s in join.sides],
                    len(conjuncts))
        finally:
            await fe.close()

    lkeys, rkeys, conjuncts = ON_CASES[on]
    assert asyncio.run(planned()) == ([lkeys, rkeys], conjuncts)


def test_on_with_equalities_alone_plans_as_before():
    """`JOIN ... ON a = b` carries no condition: no filter, marked or
    not, and the plan of the comma join with the same WHERE."""
    async def plans():
        out = []
        for frm in ("l JOIN r ON l.k = r.k", "l, r WHERE l.k = r.k"):
            fe = await _session(
                f"CREATE MATERIALIZED VIEW m AS SELECT l.x, r.y FROM {frm}")
            try:
                out.append([type(ex).__name__ for ex in _chain(fe)])
            finally:
                await fe.close()
        return out

    on, comma = asyncio.run(plans())
    assert on == comma
    assert not [k for k in on if "Filter" in k or "Fused" in k]


@pytest.mark.parametrize("kind", ["LEFT", "RIGHT", "FULL"])
def test_an_outer_joins_condition_is_refused_not_moved(kind):
    from risingwave_tpu.frontend.binder import BindError

    async def planned(on):
        fe = await _session(
            f"CREATE MATERIALIZED VIEW m AS SELECT l.x, r.y "
            f"FROM l {kind} JOIN r ON {on}")
        await fe.close()

    with pytest.raises(BindError, match="NULL-padded"):
        asyncio.run(planned("l.k = r.k AND l.x >= r.y"))
    asyncio.run(planned("l.k = r.k"))        # equalities alone: planned


@pytest.mark.parametrize("on", ["l.x >= r.y", "l.x = l.s", "l.x = 3"])
def test_on_without_an_equality_across_the_sides_is_an_error(on):
    from risingwave_tpu.common.errors import PlanError

    async def planned():
        fe = await _session(
            f"CREATE MATERIALIZED VIEW m AS SELECT l.x, r.y "
            f"FROM l JOIN r ON {on}")
        await fe.close()

    with pytest.raises(PlanError, match="column = column"):
        asyncio.run(planned())


def test_batch_join_takes_the_condition_too():
    async def rows():
        fe = await _session(
            "INSERT INTO l VALUES (1, 5, 0), (1, 1, 0), (2, 7, 0)",
            "INSERT INTO r VALUES (1, 3), (2, 9), (3, 0)")
        try:
            return sorted(await fe.execute(
                "SELECT l.x, r.y FROM l JOIN r "
                "ON l.k = r.k AND l.x >= r.y"))
        finally:
            await fe.close()

    assert [tuple(r) for r in asyncio.run(rows())] == [(5, 3)]


# -- the books, against hand counts -----------------------------------------

def _series(metric, **labels) -> float:
    return sum(v for l, v in metric.series()
               if all(l.get(k) == want for k, want in labels.items()))


@pytest.mark.parametrize("fusion", ["on", "off"])
def test_the_joins_books_against_hand_counts(fusion):
    """Three rows a side: keys 1 (two left rows) and 2 match, the
    join matches three pairs, the `>=` keeps (5, 3) only; taking
    back r's row of key 1 deletes two pairs, one of which the
    condition had kept. The join evaluates it, fusion on or off, and
    the books read the same."""
    from risingwave_tpu.stream.executors.hash_join import HashJoinExecutor
    from risingwave_tpu.utils.metrics import STREAMING as S

    async def run():
        fe = await _session(
            f"SET stream_fusion = {fusion}",
            "CREATE MATERIALIZED VIEW m AS SELECT l.x + 0 AS x, r.y "
            "FROM l JOIN r ON l.k = r.k AND l.x >= r.y")
        try:
            chain = _chain(fe)
            kinds = [type(ex).__name__ for ex in chain]
            assert "FusedFragmentExecutor" not in kinds
            assert "FilterExecutor" not in kinds
            join, = [ex for ex in chain
                     if isinstance(ex, HashJoinExecutor)]
            assert repr(join.condition) == "($1:int64 >= $4:int64)"
            t = join._books_table

            def books():
                return {
                    "in": {(side, op): _series(S.join_input_rows, table=t,
                                               side=side, op=op)
                           for side in ("left", "right")
                           for op in ("insert", "delete")},
                    "out": _series(S.join_output_rows, table=t),
                    "kept": _series(S.join_condition_rows, table=t,
                                    result="kept"),
                    "dropped": _series(S.join_condition_rows, table=t,
                                       result="dropped"),
                    "seconds": _series(S.join_condition_seconds, table=t),
                }

            def since(base, now):
                return {k: ({i: v[i] - base[k][i] for i in v}
                            if isinstance(v, dict) else v - base[k])
                        for k, v in now.items()}

            # the registry is process-wide and table ids repeat from
            # session to session: count from here
            base = books()
            await fe.execute(
                "INSERT INTO l VALUES (1, 5, 0), (1, 1, 0), (2, 7, 0)")
            await fe.execute("INSERT INTO r VALUES (1, 3), (2, 9), (3, 0)")
            await fe.execute("FLUSH")
            first = since(base, books())
            view = sorted(tuple(r) for r in
                          await fe.execute("SELECT x, y FROM m"))
            await fe.execute("DELETE FROM r WHERE k = 1")
            await fe.execute("FLUSH")
            return first, since(base, books()), view, sorted(
                tuple(r) for r in await fe.execute("SELECT x, y FROM m"))
        finally:
            await fe.close()

    first, second, view, view_after = asyncio.run(run())
    assert view == [(5, 3)] and view_after == []
    assert first["in"] == {("left", "insert"): 3, ("left", "delete"): 0,
                           ("right", "insert"): 3, ("right", "delete"): 0}
    assert (first["out"], first["kept"], first["dropped"]) == (3, 1, 2)
    assert second["in"][("right", "delete")] == 1
    assert (second["out"], second["kept"], second["dropped"]) == (5, 2, 3)
    assert second["seconds"] > first["seconds"] > 0


def test_an_unfused_hop_is_on_the_books():
    """The HOP under an aggregate, where fusion is off: the executor
    itself files its rows, five out for one in."""
    from risingwave_tpu.utils.metrics import STREAMING as S

    async def run():
        from risingwave_tpu.frontend.session import Frontend
        from risingwave_tpu.stream.executors.hop_window import (
            HopWindowExecutor,
        )
        fe = Frontend()
        try:
            await fe.execute("SET stream_fusion = off")
            await fe.execute("SET streaming_rate_limit = 1")
            await fe.execute("SET streaming_min_chunks = 1")
            await fe.execute(
                "CREATE SOURCE bid WITH (connector='nexmark', "
                "nexmark.table.type='bid', nexmark.max.chunk.size=256, "
                f"nexmark.seed={SEED})")
            await fe.execute(
                "CREATE MATERIALIZED VIEW m AS SELECT auction, "
                "count(*) AS num, window_start FROM HOP(bid, date_time, "
                "INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
                "GROUP BY window_start, auction")
            hop, = [ex for ex in _chain(fe)
                    if isinstance(ex, HopWindowExecutor)]
            assert hop.books_table
            before = {d: _series(S.hop_rows, table=hop.books_table, dir=d)
                      for d in ("in", "out")}
            await fe.step(3)
            return {d: _series(S.hop_rows, table=hop.books_table, dir=d)
                    - before[d] for d in ("in", "out")}
        finally:
            await fe.close()

    assert asyncio.run(run()) == {"in": 3 * 256, "out": 5 * 3 * 256}


# -- shapes that must not follow the join's output --------------------------

def test_the_chains_ladder():
    from risingwave_tpu.stream.executors.fused import (
        CHAIN_CAP_TOP, chain_rung,
    )
    assert CHAIN_CAP_TOP == 65_536
    assert [chain_rung(n) for n in (1, 2, 8, 16, 17, 4096, 4097, 16_384,
                                    16_385, 65_536, 1 << 20)] == \
        [1, 4, 16, 16, 64, 4096, 16_384, 16_384, 65_536, 65_536, 65_536]


def _chain_traces() -> float:
    from risingwave_tpu.utils.metrics import STREAMING
    return sum(v for l, v in STREAMING.kernel_recompile.series()
               if l.get("kernel", "").startswith("fused.chain_step"))


@pytest.mark.parametrize("top", [65_536, 64])
def test_the_chain_above_a_join_keeps_to_its_largest_rung(
        top, monkeypatch):
    """A join's output chunks are as large as its matches; a chain
    above it runs at the ladder's rungs and never steps down, so its
    programs are as many as the rungs it climbed. Above the top a
    chunk is cut into pieces. The rows are those of the executors run
    one by one. (The join is an outer one: an inner join takes such a
    WHERE as its own condition and leaves no chain above it.)"""
    from risingwave_tpu.stream.executors import fused

    monkeypatch.setattr(fused, "CHAIN_CAP_TOP", top)
    # matches of l's rows of key k with its one r row: 3, 100, 2, 300, 5
    sizes = {1: 3, 2: 100, 3: 2, 4: 300, 5: 5}

    async def run(fusion):
        fe = await _session(
            f"SET stream_fusion = {fusion}",
            "CREATE MATERIALIZED VIEW m AS SELECT l.x + 1 AS x1, r.y "
            "FROM l LEFT JOIN r ON l.k = r.k WHERE l.x >= r.y")
        try:
            block = [ex for ex in _chain(fe) if isinstance(
                ex, fused.FusedFragmentExecutor)]
            assert len(block) == (fusion == "on")
            await fe.execute("INSERT INTO r VALUES " + ", ".join(
                f"({k}, 1)" for k in sizes))
            await fe.execute("FLUSH")
            caps, traces = [], []
            for k, n in sizes.items():
                before = _chain_traces()
                await fe.execute("INSERT INTO l VALUES " + ", ".join(
                    f"({k}, {x}, 0)" for x in range(n)))
                await fe.execute("FLUSH")
                traces.append(_chain_traces() - before)
                caps.append(block[0]._cap if block else None)
            return caps, traces, sorted(
                tuple(r) for r in await fe.execute("SELECT x1, y FROM m"))
        finally:
            await fe.close()

    caps, traces, rows = asyncio.run(run("on"))
    _none, _zero, want = asyncio.run(run("off"))
    assert rows == want == sorted(
        (x + 1, 1) for n in sizes.values() for x in range(1, n))
    if top == 65_536:
        # 3 pairs in a chunk of 8, 100 in 128, 2 in 8, 300 in 512, 5
        assert caps == [16, 256, 256, 1024, 1024]
        assert traces == [1, 1, 0, 1, 0]
    else:
        # 128 rows are two pieces of 64, 512 are eight: one program
        assert caps == [16, 64, 64, 64, 64]
        assert traces == [1, 1, 0, 0, 0]


def _epoch_rows(keys, refs, seq, flags, width):
    """(up, aux) of one side's epoch: a key lane, payload lanes that
    repeat the ref, and the aux columns of ops/hash_join.py."""
    from risingwave_tpu.ops import hash_join as hj
    n = len(keys)
    up = np.zeros((n, width), dtype=np.int32)
    up[:, 0] = keys
    up[:, 1:] = np.asarray(refs)[:, None]
    aux = np.zeros((n, 4), dtype=np.int32)
    aux[:, hj.AUX_INS_REF] = refs
    aux[:, hj.AUX_FLAGS] = flags
    aux[:, hj.AUX_SEQ] = seq
    return up, aux


@pytest.mark.parametrize("first,top", [(8, 32), (32, 32), (16_384, 65_536)])
def test_a_probe_past_the_top_rung_is_read_in_pages(first, top):
    """40 rows on one key and 5 on another, probed three times: 85
    pairs. The pair buffer starts at `first`, an overflow takes it to
    `top`, and what lies beyond comes in pages of the same program:
    the same pairs, payload and all, as one large buffer gives."""
    from risingwave_tpu.ops import hash_join as hj

    def probed(kernel):
        keys = [7] * 40 + [9] * 5
        up, aux = _epoch_rows(keys, np.arange(45), seq=1,
                              flags=hj.FLAG_INS, width=4)
        up_d, aux_d, _b = kernel.stage_epoch(up, aux, 45, 44)
        kernel.apply_epoch(up_d, aux_d, 45, 44)
        pup, paux = _epoch_rows([7, 9, 7, 8], [0] * 4, seq=5,
                                flags=hj.FLAG_PROBE, width=4)
        pup_d, paux_d, _b = kernel.stage_epoch(pup, paux, 4, -1)
        return kernel.probe_epoch(pup_d, paux_d, False).collect()

    small = hj.JoinSideKernel(key_width=1, payload_width=3,
                              probe_capacity=first)
    small.PROBE_CAP_TOP = top
    large = hj.JoinSideKernel(key_width=1, payload_width=3,
                              probe_capacity=1 << 10)
    got, want = probed(small), probed(large)
    assert got[0] is None and want[0] is None      # no degrees
    assert len(got[1]) == 85
    for a, b in zip(got[1:4], want[1:4]):
        np.testing.assert_array_equal(a, b)
    assert collections.Counter(got[1].tolist()) == {0: 40, 1: 5, 2: 40}
    np.testing.assert_array_equal(got[3][:, 0], got[2])   # payload = ref
    # the buffer climbed to the top at most, never to the 128 pairs
    # a doubling would have bought
    assert small._probe_cap == max(first, top if first < 85 else first)
    assert small.take_longest_chain() == 40
    assert small.take_longest_chain() == 0


def test_a_padded_step_with_an_absorbed_hop_cuts_every_copy_back():
    """A block that absorbed a HOP lays its copies out one after the
    other, each as long as the step's input: chunks of 24 and of 8
    rows run at the rung of 64, and what comes out is, copy by copy,
    what the executors give one by one."""
    from risingwave_tpu.common.chunk import Column, Op, StreamChunk
    from risingwave_tpu.common.epoch import Epoch, EpochPair
    from risingwave_tpu.common.types import DataType, Interval, Schema
    from risingwave_tpu.expr.expr import InputRef, lit
    from risingwave_tpu.ops.fused import FusedStage, FusedStages
    from risingwave_tpu.stream.executors.fused import FusedFragmentExecutor
    from risingwave_tpu.stream.executors.hop_window import (
        HopWindowExecutor,
    )
    from risingwave_tpu.stream.executors.simple import FilterExecutor
    from risingwave_tpu.stream.message import Barrier, is_chunk

    schema = Schema.of(ts=DataType.TIMESTAMP, v=DataType.INT64)
    rng = np.random.default_rng(33)

    def chunk_of(cap):
        ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
        ops[2], ops[3] = int(Op.UPDATE_DELETE), int(Op.UPDATE_INSERT)
        return StreamChunk(
            schema,
            [Column(DataType.TIMESTAMP,
                    rng.integers(0, 40_000_000, size=cap), None),
             Column(DataType.INT64, rng.integers(-9, 9, size=cap), None)],
            rng.random(cap) > 0.1, ops)

    chunks = [chunk_of(24), chunk_of(8)]

    class Source:
        identity = "mock"
        pk_indices = []

        def __init__(self):
            self.schema = schema

        async def execute(self):
            yield Barrier(EpochPair.new_initial(Epoch.from_physical(1)))
            for c in chunks:
                yield c
            yield Barrier(EpochPair(Epoch.from_physical(2),
                                    Epoch.from_physical(1)))

    pred = InputRef(1, DataType.INT64) >= lit(0)
    block = FusedFragmentExecutor(Source(), FusedStages(schema, [
        FusedStage("hop_window", "HopWindowExecutor", time_col=0,
                   slide_usecs=10_000_000, size_usecs=30_000_000),
        FusedStage("filter", "FilterExecutor", exprs=(pred,))]))
    one_by_one = FilterExecutor(
        HopWindowExecutor(Source(), 0, Interval(usecs=10_000_000),
                          Interval(usecs=30_000_000)), pred)

    async def records(ex):
        return sorted([r async for m in ex.execute() if is_chunk(m)
                       for r in m.to_records()], key=repr)

    got = asyncio.run(records(block))
    assert block._cap == 64
    assert got == asyncio.run(records(one_by_one)) and got
