"""NEXmark q5 under the benchmark's own watermark (ISSUE 37): the DDL of
the `nexmark-q5-wm` configuration, read from the file, through a SQL
session, held at EVERY checkpoint to the benchmark's plain reference
(`benchmark/reference/nexmark_q5_wm.py`): the view as a multiset, and
each state table of the view (both counting aggregates, the MAX and
its value multiset, both join sides) to the rows the watermark has not
closed, in both orders `GROUP BY` can be written in. A table that is
not cleaned reads above the reference, one cleaned too far below it.
Then: the state is level and no kernel traces once it is; a crash in
the middle of the run restores the cleaned state and the watermark
filter's watermark and continues to the same rows. Since ISSUE 38 also:
a range-cleaned table reads the store at its first clean (the scan
that seeds its clean index) and never after, recovery included.

Epochs are a fixed number of chunks per reader, so nothing here waits
on a clock. The source is cut small and stretched in event time: 1,024
bids a chunk, 1 ms between events, so that a barrier carries 1.1 s of
event time and a 2 s window closes on every second one. The view's
text is the file's.
"""

import asyncio
import collections
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 3700000037
CHUNK = 1024
GAP_NS = 1_000_000
BARRIERS = 36
LEVEL_AT = 15           # barriers: 14 s of event time close a window

AS_WRITTEN = ("GROUP BY window_start, bid.auction",
              "GROUP BY bid.auction, window_start")
# case -> (the two GROUP BYs, the reader that runs ahead)
CASES = {
    "as_written": (AS_WRITTEN, None),
    "swapped": (AS_WRITTEN[::-1], None),
    "counts_ahead": (AS_WRITTEN, "left"),
}


def _bench_module(directory: str, name: str):
    """A module of `benchmark/`, loaded the way `run.py` loads it."""
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    return run.load_module(directory, name)


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "nexmark-q5-wm.json")) as f:
        return json.load(f)


def _ddl(config: dict, group_bys) -> list:
    source, view = config["ddl"]
    source, n = re.subn(r"max\.chunk\.size=\d+",
                        f"max.chunk.size={CHUNK}, "
                        f"nexmark.min.event.gap.in.ns={GAP_NS}", source)
    assert n == 1
    assert view.count(AS_WRITTEN[0]) == view.count(AS_WRITTEN[1]) == 1
    view = view.replace(AS_WRITTEN[0], "\0").replace(
        AS_WRITTEN[1], group_bys[1]).replace("\0", group_bys[0])
    return [source.format(seed=SEED), view]


def _generator(config: dict):
    return _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **{**config["generator"],
                      "min_event_gap_in_ns": GAP_NS})


def _bare(ex):
    return getattr(ex, "inner", ex)         # under the monitor


def _tables(fe, view: str) -> dict:
    """The state tables of the view by the reference's names."""
    import run
    from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.stream.executors.hash_join import HashJoinExecutor
    actor = fe.actors[fe.catalog.mvs[view].actor_id]
    aggs, join = {}, None
    for path, ex in run.walk_executors(actor.consumer):
        ex = _bare(ex)
        if isinstance(ex, HashJoinExecutor):
            join = ex
        elif isinstance(ex, HashAggExecutor):
            side = next(p for p in path.split("/")
                        if p in ("left_in", "right_in"))
            aggs.setdefault(side, []).append(ex)
    (left,), (max_agg, count_agg) = aggs["left_in"], aggs["right_in"]
    (values,) = max_agg.minput.values()
    return {"AuctionBids": left.table, "CountBids": count_agg.table,
            "MaxBids": max_agg.table, "MaxBids.values": values,
            "join.left": join.sides[0].table,
            "join.right": join.sides[1].table}


def _filters(fe, view: str) -> list:
    import run
    from risingwave_tpu.stream.executors.watermark_filter import (
        WatermarkFilterExecutor,
    )
    actor = fe.actors[fe.catalog.mvs[view].actor_id]
    return [_bare(ex) for _p, ex in run.walk_executors(actor.consumer)
            if isinstance(_bare(ex), WatermarkFilterExecutor)]


CLEANED = ("AuctionBids", "CountBids", "MaxBids", "MaxBids.values")


def _clean_books() -> dict:
    """t<table> -> (rows range-deleted, rows read to do so, seeding
    scans) so far in this process: the counters the history's
    `state_clean.t<table>.cleaned` / `.reads` and
    `state_clean_index.t<table>.seeds` are the deltas of."""
    from risingwave_tpu.utils.metrics import STREAMING
    books = collections.defaultdict(lambda: [0.0, 0.0, 0.0])
    for i, metric in enumerate((STREAMING.state_cleaned_rows,
                                STREAMING.state_clean_reads,
                                STREAMING.state_clean_seeds)):
        for labels, value in metric.series():
            books[labels["table"]][i] = value
    return {label: tuple(v) for label, v in books.items()}


def _reads_by_table(run_: dict) -> dict:
    """Per range-cleaned table, over the run's checkpoints: the index
    of the first one that cleaned it, the scans that seeded it, and
    (rows cleaned, rows read) summed over the checkpoints AFTER that
    one."""
    out = {}
    for name in CLEANED:
        label = f"t{run_['table_ids'][name]}"
        books = [b.get(label, (0.0, 0.0, 0.0)) for b in
                 [run_["clean_books_before"]]
                 + [cp["clean_books"] for cp in run_["checkpoints"]]]
        first = next(i for i in range(1, len(books))
                     if books[i][0] > books[0][0]
                     or books[i][2] > books[0][2])
        out[name] = {"first": first - 1,
                     "seeds": books[-1][2] - books[0][2],
                     "seed_reads": books[first][1] - books[0][1],
                     "cleaned_after": books[-1][0] - books[first][0],
                     "reads_after": books[-1][1] - books[first][1]}
    return out


async def _checkpoint(fe, view: str, tables: dict) -> dict:
    """What one checkpoint holds: the view, the readers' offsets, the
    rows of each state table by the topology's books (what the
    benchmark reads), the kernels traced so far, the range deletes'
    books."""
    import run
    by_id = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in await fe.execute(
            "SELECT * FROM rw_state_topology"):
        if mv == view:
            by_id[table_id] += n
    return {
        "view": collections.Counter(
            tuple(r) for r in await fe.execute(f"SELECT * FROM {view}")),
        "readers": run.checkpointed_rows(run.source_readers(fe, view)),
        "rows": {name: by_id[t.table_id] for name, t in tables.items()},
        "largest": max(by_id.values()),
        "traces": run.Counts.traces_by_kernel(),
        "clean_books": _clean_books(),
    }


def _pace(fe, view: str, chunks_by_side: dict) -> None:
    import run
    for _name, side, source in run.source_readers(fe, view):
        source = _bare(source)
        source.rate_limit = source.min_chunks = chunks_by_side[side]


async def _start(store, config: dict, case: str):
    from risingwave_tpu.frontend.session import Frontend
    fe = Frontend(store, rate_limit=1, min_chunks=1)
    for ddl in _ddl(config, CASES[case][0]):
        await fe.execute(ddl)
    ahead = CASES[case][1]
    if ahead is not None:
        # a reader's pace takes hold a barrier or two after it is set:
        # one side at three chunks a barrier for a while, then both at
        # one: the lead stays
        _pace(fe, config["view"], {s: 1 + 2 * (s == ahead)
                                   for s in ("left", "right")})
        await fe.step(2)
        _pace(fe, config["view"], {"left": 1, "right": 1})
    return fe


def _history(rows) -> list:
    """`rw_metrics_history` as one dict a barrier, oldest first, less
    the first: the first row after HISTORY.clear() takes the registry's
    process-wide counter totals, other tests' among them, as its delta
    (here a DDL round's, before any data)."""
    out = {}
    for _seq, epoch, ts, _interval_s, name, value, _dom in rows:
        out.setdefault(epoch, {"ts": ts})[name] = value
    return sorted(out.values(), key=lambda h: h["ts"])[1:]


async def _drive(config: dict, case: str) -> dict:
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.utils.metrics import HISTORY
    TOPOLOGY.clear()            # process-wide books of state rows
    HISTORY.clear()
    clean_books_before = _clean_books()
    fe = await _start(None, config, case)
    try:
        view = config["view"]
        tables = _tables(fe, view)
        checkpoints = []
        for _ in range(BARRIERS):
            await fe.step()
            checkpoints.append(await _checkpoint(fe, view, tables))
        return {
            "checkpoints": checkpoints,
            "clean_books_before": clean_books_before,
            # the store's own rows against the books', once
            "stored": {name: sum(1 for _ in t.iter_rows())
                       for name, t in tables.items()},
            "key_lead": {name: t.pk_indices[0]
                         for name, t in tables.items()},
            "rewrites": await fe.execute(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites"),
            "watermarks": await fe.execute("SELECT * FROM rw_watermarks"),
            "history": _history(
                await fe.execute("SELECT * FROM rw_metrics_history")),
            "table_ids": {name: t.table_id for name, t in tables.items()},
        }
    finally:
        await fe.close()


@pytest.fixture(scope="module")
def q5wm():
    config = _config()
    _bench_module("reference", "nexmark_gen")
    return {"config": config,
            **{case: asyncio.run(_drive(config, case)) for case in CASES}}


@pytest.mark.parametrize("case", list(CASES))
def test_view_equals_the_reference_at_every_checkpoint(q5wm, case):
    config = q5wm["config"]
    ref = _bench_module("reference", config["reference"])
    gen = _generator(config)
    for i, cp in enumerate(q5wm[case]["checkpoints"]):
        want = ref.reference([dict(r) for r in cp["readers"]], gen)
        assert cp["view"] == want, f"checkpoint {i}"
    assert sum(want.values()) >= 10          # a row a window, and ties
    assert not [r for r in q5wm[case]["rewrites"]
                if str(r[3]).startswith("FALLBACK")]


@pytest.mark.parametrize("case", list(CASES))
def test_every_state_table_holds_the_reference_s_rows(q5wm, case):
    """Each of the six, at each of the 36 checkpoints: under-cleaning
    and over-cleaning both fail it."""
    config = q5wm["config"]
    ref = _bench_module("reference", config["reference"])
    gen = _generator(config)
    closed = 0
    for i, cp in enumerate(q5wm[case]["checkpoints"]):
        readers = [dict(r) for r in cp["readers"]]
        want = ref.resident_by_table(readers, gen)
        assert cp["rows"] == want, f"checkpoint {i}"
        # what the benchmark compares
        assert cp["largest"] == ref.resident_rows(readers, gen)
        closed += ref.bound(readers[0]["rows"], gen) > 0
    assert closed >= BARRIERS - LEVEL_AT     # windows did close
    assert q5wm[case]["stored"] == cp["rows"]
    # ... and were found without reading the store: each table's first
    # clean seeds its index with one scan, no clean after it reads
    for name, got in _reads_by_table(q5wm[case]).items():
        assert got["seeds"] == 1 and got["first"] < LEVEL_AT, (name, got)
        assert got["reads_after"] == 0 < got["cleaned_after"], (name, got)
    # without the watermark's reference the same run fails the check
    plain = _bench_module("reference", "nexmark_q5")
    assert plain.resident_rows(readers, gen) > 2 * cp["largest"]


def test_the_watermark_column_leads_every_aggregate_s_key(q5wm):
    """`window_start` is group column 0 of one counting aggregate and 1
    of the other as upstream writes q5, and the other way round when
    the two are swapped; it leads the state key in all four."""
    assert q5wm["as_written"]["key_lead"] == {
        "AuctionBids": 0, "CountBids": 1, "MaxBids": 0,
        "MaxBids.values": 0,
        **{k: v for k, v in q5wm["as_written"]["key_lead"].items()
           if k.startswith("join.")}}
    assert q5wm["swapped"]["key_lead"]["AuctionBids"] == 1
    assert q5wm["swapped"]["key_lead"]["CountBids"] == 0


def test_the_reader_ahead_makes_the_join_wait(q5wm):
    """With the counts' reader ahead the join keeps the rows of the
    windows the slower input has not closed: its bound is the smaller
    of the two, and the test above held it to that."""
    config = q5wm["config"]
    ref = _bench_module("reference", config["reference"])
    gen = _generator(config)
    cp = q5wm["counts_ahead"]["checkpoints"][-1]
    rows = {r["side"]: r["rows"] for r in cp["readers"]}
    assert rows["left"] - rows["right"] >= 2 * CHUNK
    assert ref.bound(rows["left"], gen) > ref.bound(rows["right"], gen)
    assert cp["rows"]["join.left"] > cp["rows"]["AuctionBids"]


@pytest.mark.parametrize("case", list(CASES))
def test_the_state_is_level_and_nothing_traces_once_it_is(q5wm, case):
    cps = q5wm[case]["checkpoints"]
    then, now = cps[LEVEL_AT - 1], cps[2 * LEVEL_AT - 1]
    assert abs(now["largest"] - then["largest"]) <= 0.15 * then["largest"]
    # level, and no scan seeded an index again once it was
    for name in CLEANED:
        label = f"t{q5wm[case]['table_ids'][name]}"
        assert cps[-1]["clean_books"][label][1:] == \
            then["clean_books"][label][1:], name
    for name in then["rows"]:
        assert now["rows"][name] <= 1.15 * then["rows"][name] + 2, name
    assert cps[-1]["traces"] == then["traces"], \
        dict(cps[-1]["traces"] - then["traces"])


@pytest.mark.parametrize("case", list(CASES))
def test_the_join_s_expiry_is_counted_by_the_side_in_the_history(
        q5wm, case):
    """`join_expire.t<side's table>.rows` (counter
    `stream_join_expired_rows{table}`) is in the history's row of every
    barrier from the first on which the join's watermark moved, and
    over the run it is the rows that left the side's state table: what
    the side was given, less what its input took back, less what the
    store still holds."""
    run_ = q5wm[case]
    history = run_["history"]
    join = f"t{run_['table_ids']['join.left']}"
    for side in ("left", "right"):
        name = f"join_expire.t{run_['table_ids']['join.' + side]}.rows"
        rows = [h.get(name) for h in history]
        first = next(i for i, v in enumerate(rows) if v is not None)
        assert first < LEVEL_AT and None not in rows[first:], (side, rows)
        # a window closes on every second barrier
        assert sum(1 for v in rows[first:] if v) >= (BARRIERS - first) // 3
        given = {op: sum(h.get(f"join_input_rows.{join}.{side}.{op}", 0)
                         for h in history)
                 for op in ("insert", "update_insert", "delete",
                            "update_delete")}
        left_the_table = given["insert"] + given["update_insert"] \
            - given["delete"] - given["update_delete"] \
            - run_["stored"]["join." + side]
        assert sum(rows[first:]) == left_the_table > 0, (side, given)


def test_rw_watermarks_names_every_cleaned_table(q5wm):
    run_ = q5wm["as_written"]
    got = {r[0]: r for r in run_["watermarks"]}
    assert set(got) == set(run_["table_ids"].values())
    last = run_["checkpoints"][-1]["rows"]
    for name, table_id in run_["table_ids"].items():
        _tid, mv, watermark, rows = got[table_id]
        assert mv == q5wm["config"]["view"] and rows == last[name]
    # one watermark for all: the readers ran in lockstep
    assert len({r[2] for r in got.values()}) == 1


def test_recovery_restores_the_cleaned_state_and_the_watermark():
    """A crash after 20 barriers (no close, no goodbye): the recovered
    session's watermark filters announce the watermark they had, every
    state table holds what the reference says, and twelve barriers
    later view and tables are the reference's still."""
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore

    config = _config()
    view = config["view"]
    ref = _bench_module("reference", config["reference"])
    gen = _generator(config)
    obj = MemObjectStore()
    TOPOLOGY.clear()

    async def before():
        fe = await _start(HummockLite(obj), config, "as_written")
        await fe.step(20)
        cp = await _checkpoint(fe, view, _tables(fe, view))
        return cp, [f.current for f in _filters(fe, view)]

    async def after():
        from risingwave_tpu.frontend.session import Frontend
        fe = Frontend(HummockLite(obj), rate_limit=1, min_chunks=1)
        await fe.recover()
        try:
            tables = _tables(fe, view)
            table_ids.update({n: t.table_id for n, t in tables.items()})
            restored = [f.current for f in _filters(fe, view)]
            await fe.step()
            cps = [await _checkpoint(fe, view, tables)]
            for _ in range(11):
                await fe.step()
                cps.append(await _checkpoint(fe, view, tables))
            return restored, cps, {
                name: sum(1 for _ in t.iter_rows())
                for name, t in tables.items()}
        finally:
            await fe.close()

    cp20, wm20 = asyncio.run(before())
    assert len(wm20) == 2 and all(w is not None for w in wm20)
    TOPOLOGY.clear()            # the crashed process's books
    table_ids = {}
    clean_books_before = _clean_books()
    restored, cps, stored = asyncio.run(after())
    assert restored == wm20
    assert cps[0]["readers"][0]["rows"] == \
        cp20["readers"][0]["rows"] + CHUNK
    for i, cp in enumerate(cps):
        readers = [dict(r) for r in cp["readers"]]
        assert cp["view"] == ref.reference(readers, gen), i
        # the store's rows: the recovered process's books hold only
        # what it wrote since, so the tables are counted themselves
    assert stored == ref.resident_by_table(readers, gen)
    assert cps[-1]["readers"][0]["rows"] == (20 + 12) * CHUNK
    # a recovered table starts without an index: its first clean seeds
    # it with one scan of the recovered rows, the next reads nothing
    for name, got in _reads_by_table({
            "table_ids": table_ids, "checkpoints": cps,
            "clean_books_before": clean_books_before}).items():
        assert got["seeds"] == 1 and got["seed_reads"] > 0, (name, got)
        assert got["reads_after"] == 0 < got["cleaned_after"], (name, got)
