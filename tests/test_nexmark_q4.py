"""NEXmark q4 as upstream writes it (ISSUE 31): the text of the
benchmark's `nexmark-q4` configuration, read from the file, through a
SQL session, compared as a multiset with the benchmark's plain
reference (`benchmark/reference/nexmark_q4.py`); the front end's comma
join; AVG over integers as the exact quotient; and the books the query
keeps on the way (rows into an aggregate by op, the join -> aggregate
hand-off, the key skew of a staged batch, the rounds of probe_insert's
loop, where the AVG's division ran).

Epochs are a fixed number of chunks per reader, so nothing here waits
on a clock. Only the two sources' chunk sizes are rewritten, to cut the
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
SEED = 3100000031

# (auction chunk rows, bid chunk rows, barriers at 1 chunk, at 8 chunks)
# auctions_ahead: the stream's 3:46, so an auction is there before its
#   bids; 12 epochs of 1,024 bids (an auction takes bids over some 1,500
#   of them, so its MAX rises across barriers), then 8 of 8,192, which
#   cross the bid side's row store rung at 65,536 rows.
# bids_ahead: 16 auctions to 1,024 bids a chunk: the bid reader runs
#   three times ahead, the join is driven from the auction side and the
#   BETWEEN decides on auctions that come after their bids.
CASES = {"auctions_ahead": (67, 1024, 12, 8),
         "bids_ahead": (16, 1024, 6, 3)}


def _bench_module(directory: str, name: str):
    """A module of `benchmark/`, loaded the way `run.py` loads it."""
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    return run.load_module(directory, name)


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "nexmark-q4.json")) as f:
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
    from risingwave_tpu.utils.metrics import HISTORY
    from risingwave_tpu.utils.spans import EPOCH_TRACER

    auction_rows, bid_rows, small, large = CASES[case]
    from risingwave_tpu.state.topology import TOPOLOGY
    HISTORY.clear()
    EPOCH_TRACER.clear()
    TOPOLOGY.clear()            # process-wide books of state rows
    fe = Frontend()
    try:
        await fe.execute("SET streaming_rate_limit = 1")
        await fe.execute("SET streaming_min_chunks = 1")
        for ddl in config["ddl"]:
            rows = auction_rows if "'auction'" in ddl else bid_rows
            ddl, n = re.subn(r"max\.chunk\.size=\d+",
                             f"max.chunk.size={rows}", ddl)
            assert n == ("CREATE SOURCE" in ddl)
            await fe.execute(ddl.format(seed=SEED))
        await fe.step(small)
        for _name, _side, source in run.source_readers(fe, config["view"]):
            source = getattr(source, "inner", source)  # the monitor's wrap
            source.rate_limit = source.min_chunks = 8
        await fe.step(large)
        await fe.execute("FLUSH")
        epochs = EPOCH_TRACER.epochs()
        return {
            "view": collections.Counter(
                tuple(r) for r in await fe.execute(
                    f"SELECT * FROM {config['view']}")),
            "readers": run.checkpointed_rows(
                run.source_readers(fe, config["view"])),
            "history": _history(
                await fe.execute("SELECT * FROM rw_metrics_history")),
            "rewrites": await fe.execute(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites"),
            "topology": await fe.execute(
                "SELECT * FROM rw_state_topology"),
            "spans": [s for e in epochs
                      for s in EPOCH_TRACER.spans_for(e)],
        }
    finally:
        await fe.close()


@pytest.fixture(scope="module")
def q4():
    config = _config()
    _bench_module("reference", "nexmark_gen")
    return {"config": config,
            **{case: asyncio.run(_drive(config, case)) for case in CASES}}


def _window_sum(run_: dict, prefix: str, suffix: str = "") -> float:
    return sum(v for h in run_["history"].values() for k, v in h.items()
               if k.startswith(prefix) and k.endswith(suffix))


@pytest.mark.parametrize("case", list(CASES))
def test_view_equals_the_benchmarks_reference(q4, case):
    config, got = q4["config"], q4[case]
    gen = _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **config["generator"])
    ref = _bench_module("reference", config["reference"])
    readers = [dict(r) for r in got["readers"]]
    want = ref.reference(readers, gen)
    assert len(want) == 5                    # the generator's categories
    assert got["view"] == want               # the float64 averages too
    by_table = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in got["topology"]:
        if mv == config["view"]:
            by_table[table_id] += n
    assert max(by_table.values()) == ref.resident_rows(readers, gen)
    assert not [r for r in got["rewrites"]
                if str(r[3]).startswith("FALLBACK")]


def test_the_stream_crossed_what_the_cases_say(q4):
    ahead = {r["table"]: r["rows"] for r in q4["auctions_ahead"]["readers"]}
    # past the bid side's row store rung (65,536 rows, x4), the
    # auctions never behind the bids that name them (3:46)
    assert ahead["bid"] > 65_536
    assert ahead["auction"] * 46 >= ahead["bid"] * 3
    behind = {r["table"]: r["rows"] for r in q4["bids_ahead"]["readers"]}
    assert behind["auction"] * 46 * 3 < behind["bid"] * 3


@pytest.mark.parametrize("case", list(CASES))
def test_the_max_retracts_into_the_avg(q4, case):
    """An auction's MAX rises across barriers, so the AVG takes `U-`
    and `U+`; the inner MAX, fed by an inner join of two append-only
    sources, takes inserts only."""
    run_ = q4[case]
    pairs = _window_sum(run_, "agg_input_rows.", ".update_delete")
    assert pairs > 0
    assert pairs == _window_sum(run_, "agg_input_rows.", ".update_insert")
    assert _window_sum(run_, "agg_input_rows.", ".delete") == 0
    inserts = _window_sum(run_, "agg_input_rows.", ".insert")
    handed = _window_sum(run_, "join_to_agg.rows")
    assert 0 < handed < inserts              # the rest went into the AVG
    bench = _bench_module("layer_metrics", "agg_retract_share")
    share = bench.read({"history": run_["history"]})
    assert share == pytest.approx(100.0 * pairs / (inserts + 2 * pairs))


def test_the_hand_off_is_on_the_books(q4):
    run_ = q4["auctions_ahead"]
    bids = next(r["rows"] for r in run_["readers"] if r["table"] == "bid")
    # every bid finds its auction (they are ahead) and comes out of the
    # join, whether or not the BETWEEN then keeps it
    assert _window_sum(run_, "join_to_agg.rows") == bids
    assert _window_sum(run_, "join_to_agg.seconds") > 0
    bench = _bench_module("layer_metrics", "join_to_agg_share")
    assert 0 < bench.read({"history": run_["history"]}) < 100


def test_batch_skew_and_probe_rounds(q4):
    run_ = q4["auctions_ahead"]
    names = {k for h in run_["history"].values() for k in h}
    # the registry is process-wide: kernels of views other tests ran
    # in this process are in the rows too, with nothing staged
    kernels = {k.split(".")[1] + "." + k.split(".")[2]
               for k in names if k.startswith("batch_skew.")}
    kernels = {k for k in kernels
               if _window_sum(run_, f"batch_skew.{k}.rows") > 0}
    assert len([k for k in kernels if k.startswith("join.")]) == 2
    assert len([k for k in kernels if k.startswith("agg.")]) == 2
    hot = 0
    for kernel in kernels:
        rows = _window_sum(run_, f"batch_skew.{kernel}.rows")
        distinct = _window_sum(run_, f"batch_skew.{kernel}.distinct")
        assert 0 < distinct <= rows
        hot = max(hot, max(h.get(f"batch_skew.{kernel}.max_key", 0)
                           for h in run_["history"].values()))
    # half of the bids go to one auction in a hundred: some 770 a key
    assert hot > 300
    for kernel in kernels:
        batches = _window_sum(run_, f"probe_insert.{kernel}.batches")
        rounds = _window_sum(run_, f"probe_insert.{kernel}.rounds")
        assert batches > 0 and rounds >= batches
    bench = _bench_module("layer_metrics", "probe_rounds_per_epoch")
    assert bench.read({"history": run_["history"]}) >= 1.0


MARKS = {
    # view: (aggregates marked fed_by_join, joins marked feeds_agg)
    "upstream_q4": (None, (1, 1)),
    "join_alone": ("SELECT A.id, B.price FROM auction A, bid B "
                   "WHERE A.id = B.auction", (0, 0)),
    "aggregate_alone": ("SELECT auction, MAX(price) AS m FROM bid "
                        "GROUP BY auction", (0, 0)),
    "aggregate_over_filtered_join": (
        "SELECT A.category, COUNT(*) AS n FROM auction A, bid B "
        "WHERE A.id = B.auction AND B.price > 100 GROUP BY A.category",
        (1, 1)),
}


@pytest.mark.parametrize("case", list(MARKS))
def test_the_planner_marks_the_join_and_its_aggregate(case):
    """The hand-off's two ends are the planner's to name: the inner MAX
    and the join under it, not the AVG over the MAX, and nothing where
    a join or an aggregate stands alone."""
    import run
    from risingwave_tpu.frontend.session import Frontend
    select, want = MARKS[case]
    ddl = _config()["ddl"][-1] if select is None \
        else "CREATE MATERIALIZED VIEW q4 AS " + select

    async def deployed():
        fe = Frontend()
        try:
            for t in ("person", "auction", "bid"):
                await fe.execute(NEXMARK.format(t=t))
            await fe.execute(ddl)
            actor = fe.actors[fe.catalog.mvs["q4"].actor_id]
            found = [ex for _p, ex in run.walk_executors(actor.consumer)]
            return (sum(getattr(ex, "fed_by_join", False) for ex in found),
                    sum(getattr(ex, "feeds_agg", False) for ex in found))
        finally:
            await fe.close()
    _bench_module("reference", "nexmark_gen")
    assert asyncio.run(deployed()) == want


def test_adopting_an_exact_count_waits_for_no_counter():
    """PendingCounters.reset is on every kernel's flush: it reads the
    rounds of the counters that have landed and drops the others."""
    from risingwave_tpu.utils.jaxtools import PendingCounters

    class InFlight:
        ndim = 1

        def is_ready(self):
            return False

        def __array__(self, *a, **k):
            raise AssertionError("reset read a counter in flight")

    class Landed(InFlight):
        def is_ready(self):
            return True

        def __array__(self, *a, **k):
            return np.array([5, 7], dtype=np.int32)

    books = PendingCounters()
    for counter in (Landed(), InFlight()):
        books.push(counter, 16)
    books.reset(40)
    assert books.count() == 40 and books.pending_rows() == 0
    assert books.take_rounds() == (7, 1)


def test_the_avg_divides_on_the_host(q4):
    where = {s.args["avg_division"] for s in q4["auctions_ahead"]["spans"]
             if "avg_division" in s.args}
    assert where == {"host"}


def test_readers_of_a_program_without_the_books_read_nothing():
    record = {"history": {1: {"ts": 1.0, "interval_s": 0.5,
                              "phase.host_emit": 0.1}}}
    for name in ("join_to_agg_share", "agg_retract_share",
                 "probe_rounds_per_epoch"):
        assert _bench_module("layer_metrics", name).read(record) is None


# -- AVG over integers ------------------------------------------------------

BIG = 1 << 53
AVG_CASES = {
    "past_2_53": [BIG + 1, BIG + 3, 7],
    "double_rounding": [BIG * 4 + 2, BIG * 4 + 6, 1, 1, 1, 1, 1],
    "negative": [-(BIG * 8) - 5, 3, 4],
    "small": [1, 2, 4],
    "prices": [10 ** 8] * 3 + [99_999_999, 1],
}


@pytest.mark.parametrize("case", list(AVG_CASES))
def test_avg_of_bigints_is_the_exact_quotient(case):
    """AVG(bigint) is the float64 nearest to sum / count, which is what
    Python's int / int gives, also where float64(sum) would round first."""
    values = AVG_CASES[case]

    async def run():
        from risingwave_tpu.frontend.session import Frontend
        fe = Frontend()
        try:
            await fe.execute("CREATE TABLE t (k bigint, v bigint)")
            await fe.execute(
                "CREATE MATERIALIZED VIEW m AS SELECT k, AVG(v) AS a, "
                "SUM(v) AS s, COUNT(v) AS c FROM t GROUP BY k")
            await fe.execute("INSERT INTO t VALUES " + ", ".join(
                f"(1, {v})" for v in values))
            return await fe.execute("SELECT a, s, c FROM m")
        finally:
            await fe.close()

    (avg, total, count), = asyncio.run(run())
    assert (total, count) == (sum(values), len(values))
    assert isinstance(avg, float)
    assert avg == sum(values) / len(values)


def test_avg_quotient_function():
    from risingwave_tpu.common.chunk import Column
    from risingwave_tpu.common.types import DataType
    from risingwave_tpu.expr.expr import _avg_quotient
    sums = [BIG * 4 + 9, 10, 0, -(BIG * 2) - 3, 5]
    counts = [7, 4, 0, 3, 1]
    out = _avg_quotient(
        DataType.FLOAT64,
        Column(DataType.INT64, np.asarray(sums, dtype=np.int64), None),
        Column(DataType.INT64, np.asarray(counts, dtype=np.int64), None))
    assert out.values.dtype == np.float64
    valid = np.asarray(out.validity)
    assert valid.tolist() == [True, True, False, True, True]
    for got, s, c, ok in zip(out.values.tolist(), sums, counts, valid):
        if ok:
            assert got == s / c
    # the cast that rounds first is off by an ulp on the first row
    assert float(sums[0]) / 7.0 != sums[0] / 7


# -- the front end: a comma-separated FROM list -------------------------------

NEXMARK = ("CREATE SOURCE {t} WITH (connector='nexmark', "
           "nexmark.table.type='{t}', nexmark.event.num=2000)")


def _explain(sql: str) -> str:
    async def run():
        from risingwave_tpu.frontend.session import Frontend
        fe = Frontend()
        try:
            for t in ("person", "auction", "bid"):
                await fe.execute(NEXMARK.format(t=t))
            return await fe.execute("EXPLAIN " + sql)
        finally:
            await fe.close()
    return repr(asyncio.run(run()))


PLAN_PAIRS = {
    "two_items": (
        "SELECT A.id, B.price FROM auction A, bid B "
        "WHERE A.id = B.auction",
        "SELECT A.id, B.price FROM auction AS A JOIN bid AS B "
        "ON A.id = B.auction"),
    "equality_and_residual": (
        "SELECT A.id, B.price FROM auction A, bid AS B WHERE "
        "B.date_time BETWEEN A.date_time AND A.expires "
        "AND B.auction = A.id AND B.price > 100",
        "SELECT A.id, B.price FROM auction AS A JOIN bid AS B "
        "ON A.id = B.auction WHERE B.date_time BETWEEN A.date_time "
        "AND A.expires AND B.price > 100"),
    "three_items": (
        "SELECT P.name, B.price FROM person P, auction A, bid B "
        "WHERE P.id = A.seller AND A.id = B.auction",
        "SELECT P.name, B.price FROM person AS P "
        "JOIN auction AS A ON P.id = A.seller "
        "JOIN bid AS B ON A.id = B.auction"),
    "two_keys": (
        "SELECT A.id FROM auction A, bid B "
        "WHERE A.id = B.auction AND A.seller = B.bidder",
        "SELECT A.id FROM auction AS A JOIN bid AS B "
        "ON A.id = B.auction AND A.seller = B.bidder"),
    "upstream_q4": (
        None,          # the configuration file's view, filled in below
        "SELECT Q.category, AVG(Q.final) as avg FROM (SELECT "
        "MAX(B.price) AS final, A.category FROM auction AS A "
        "JOIN bid AS B ON A.id = B.auction WHERE B.date_time BETWEEN "
        "A.date_time AND A.expires GROUP BY A.id, A.category) AS Q "
        "GROUP BY Q.category"),
}


@pytest.mark.parametrize("case", list(PLAN_PAIRS))
def test_comma_join_plans_as_join_on(case):
    comma, join_on = PLAN_PAIRS[case]
    if comma is None:
        view = _config()["ddl"][-1]
        assert "FROM auction A, bid B" in view
        comma = view.split(" AS\n", 1)[1]
    plan = _explain(comma)
    assert "HashJoinExecutor" in plan
    assert plan == _explain(join_on)


def test_parser_from_list_and_aliases():
    from risingwave_tpu.frontend import ast
    from risingwave_tpu.frontend.parser import parse
    s = parse("SELECT 1 FROM a x, b AS y, (SELECT 1 FROM c) z, d "
              "JOIN e ON d.k = e.k WHERE x.k = y.k")
    assert s.from_item == ast.TableRef("a", "x")
    assert [j.kind for j in s.joins] == ["inner"] * 4
    assert [j.on is None for j in s.joins] == [True, True, True, False]
    assert s.joins[0].item == ast.TableRef("b", "y")
    assert isinstance(s.joins[1].item, ast.Subquery)
    assert s.joins[1].item.alias == "z"
    assert s.joins[2].item == ast.TableRef("d", None)


@pytest.mark.parametrize("where", [
    "", "WHERE A.id > 5", "WHERE A.id = A.seller",
    "WHERE A.id = B.auction + 1", "WHERE A.id < B.auction"])
def test_from_list_without_an_equality_is_refused(where):
    """No equality across the two items: said so, not planned as a
    cross product."""
    from risingwave_tpu.frontend.binder import BindError
    with pytest.raises(BindError, match="comma-separated FROM list "
                       "needs a WHERE equality.*'b'.*cross product"):
        _explain(f"SELECT A.id FROM auction A, bid B {where}")


def test_from_list_batch_select():
    """The batch planner takes the same list: a SELECT over two MVs."""
    async def run():
        from risingwave_tpu.frontend.session import Frontend
        fe = Frontend(min_chunks=4)
        try:
            for t in ("auction", "bid"):
                await fe.execute(NEXMARK.format(t=t))
            await fe.execute("CREATE MATERIALIZED VIEW a AS SELECT id, "
                             "category FROM auction")
            await fe.execute("CREATE MATERIALIZED VIEW b AS SELECT "
                             "auction, price FROM bid")
            await fe.step(4)
            comma = await fe.execute(
                "SELECT x.id, y.price FROM a x, b y "
                "WHERE x.id = y.auction AND y.price > 1000")
            join_on = await fe.execute(
                "SELECT x.id, y.price FROM a AS x JOIN b AS y "
                "ON x.id = y.auction WHERE y.price > 1000")
            return comma, join_on
        finally:
            await fe.close()
    comma, join_on = asyncio.run(run())
    assert len(comma) > 100
    assert sorted(comma) == sorted(join_on)
