"""NEXmark q15 as upstream writes it (ISSUE 41): the text of the
benchmark's `nexmark-q15` configuration, read from the file, through a
SQL session, compared at every checkpoint with the benchmark's plain
reference (`benchmark/reference/nexmark_q15.py`): the view's thirteen
columns and the rows of its two dedup tables. Twelve aggregates per day
of bids, eight of them `count(DISTINCT ...) FILTER (WHERE ...)`: the
binder keeps such a filter as the call's own, the planner gives each
distinct column ONE dedup table with a count per call, and the executor
gates a chunk once per column.

Epochs are a fixed number of chunks, so nothing here waits on a clock.
Only the source's chunk size (and, for the case with two live days, the
generator's event gap) is rewritten, to cut the stream small: the view's
text is the file's.
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
SEED = 4100000041
CHUNK = 1024
BARRIERS = 24
# 8.64 s between events: midnight after 10,000 events, 9,200 bids, so
# the 24 barriers of 1,024 bids see three days and two midnights, and
# more than one group is live and changing in the barriers around them
GAP_TWO_DAYS_NS = 8_640_000_000
CASES = {"one_day": None, "two_days": GAP_TWO_DAYS_NS}
DISTINCT_CALLS = 4          # per distinct column: plain and three ranks


def _run():
    """`benchmark/run.py`, with `benchmark/` on the path."""
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    return run


def _bench_module(directory: str, name: str):
    """A module of `benchmark/`, loaded the way `run.py` loads it."""
    return _run().load_module(directory, name)


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "nexmark-q15.json")) as f:
        return json.load(f)


def _ddl(config: dict, gap_ns) -> list:
    source, view = config["ddl"]
    options = f"max.chunk.size={CHUNK}" + (
        "" if gap_ns is None
        else f", nexmark.min.event.gap.in.ns={gap_ns}")
    source, n = re.subn(r"max\.chunk\.size=\d+", options, source)
    assert n == 1
    return [source.format(seed=SEED), view]


def _generator(config: dict, gap_ns):
    over = dict(config["generator"])
    if gap_ns is not None:
        over["min_event_gap_in_ns"] = gap_ns
    return _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **over)


def _bare(ex):
    return getattr(ex, "inner", ex)         # under the monitor


def _agg(fe, view: str):
    run = _run()
    from risingwave_tpu.stream.executors.hash_agg import HashAggExecutor
    actor = fe.actors[fe.catalog.mvs[view].actor_id]
    agg, = [_bare(ex) for _p, ex in run.walk_executors(actor.consumer)
            if isinstance(_bare(ex), HashAggExecutor)]
    return agg


def _dedup_rows(agg) -> dict:
    """input column name -> the dedup table's rows as the store holds
    them."""
    in_schema = agg.input.schema
    return {in_schema[col].name: sorted(
        tuple(row) for _pk, row in t.iter_rows())
        for col, t in agg.distinct_tables.items()}


def _history(rows) -> dict:
    out = {}
    for _seq, epoch, ts, interval_s, name, value, _dom in rows:
        out.setdefault(epoch, {"ts": ts, "interval_s": interval_s})[
            name] = value
    # the first row after HISTORY.clear() takes the registry's
    # process-wide counter totals, other tests' among them, as its delta
    del out[min(out)]
    return out


async def _checkpoint(fe, view: str) -> dict:
    run = _run()
    by_id = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in await fe.execute(
            "SELECT * FROM rw_state_topology"):
        if mv == view:
            by_id[table_id] += n
    agg = _agg(fe, view)
    return {
        "view": collections.Counter(
            tuple(r) for r in await fe.execute(f"SELECT * FROM {view}")),
        "readers": run.checkpointed_rows(run.source_readers(fe, view)),
        "by_id": dict(by_id),
        "dedup": {t.table_id: by_id[t.table_id]
                  for t in agg.distinct_tables.values()},
        # the store's own rows (the topology's books start empty in a
        # recovered process: they count what it wrote)
        "stored": [sum(1 for _ in t.iter_rows())
                   for t in agg.distinct_tables.values()],
    }


async def _drive(config: dict, case: str, store=None, barriers=BARRIERS,
                 recover=False) -> dict:
    run = _run()
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.utils.metrics import HISTORY

    HISTORY.clear()
    TOPOLOGY.clear()            # process-wide books of state rows
    fe = Frontend(store, rate_limit=1, min_chunks=1)
    try:
        if recover:
            await fe.recover()
        else:
            for ddl in _ddl(config, CASES[case]):
                await fe.execute(ddl)
        view = config["view"]
        checkpoints = []
        for _ in range(barriers):
            await fe.step()
            checkpoints.append(await _checkpoint(fe, view))
        agg = _agg(fe, view)
        actor = fe.actors[fe.catalog.mvs[view].actor_id]
        return {
            "checkpoints": checkpoints,
            "dedup_rows": _dedup_rows(agg),
            "dedup_schemas": {
                agg.input.schema[col].name:
                [(f.name, f.data_type.name) for f in t.schema]
                for col, t in agg.distinct_tables.items()},
            "dedup_ids": {agg.input.schema[col].name: t.table_id
                          for col, t in agg.distinct_tables.items()},
            "calls": list(agg.agg_calls),
            "state_tables": sorted(checkpoints[-1]["by_id"]),
            "history": _history(
                await fe.execute("SELECT * FROM rw_metrics_history")),
            "rewrites": await fe.execute(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites"),
            "plan": [type(_bare(ex)).__name__ for _p, ex
                     in run.walk_executors(actor.consumer)],
        }
    finally:
        await fe.close()


@pytest.fixture(scope="module")
def q15():
    config = _config()
    _bench_module("reference", "nexmark_gen")
    return {"config": config,
            **{case: asyncio.run(_drive(config, case)) for case in CASES}}


@pytest.mark.parametrize("case", list(CASES))
def test_view_equals_the_reference_at_every_checkpoint(q15, case):
    config = q15["config"]
    ref = _bench_module("reference", config["reference"])
    gen = _generator(config, CASES[case])
    cps = q15[case]["checkpoints"]
    assert len(cps) >= 20
    for i, cp in enumerate(cps):
        want = ref.reference([dict(r) for r in cp["readers"]], gen)
        assert cp["view"] == want, f"checkpoint {i}"
    days = sorted(row[0] for row in want)
    assert days == {"one_day": ["2015-07-15"],
                    "two_days": ["2015-07-15", "2015-07-16",
                                 "2015-07-17"]}[case]
    assert not [r for r in q15[case]["rewrites"]
                if str(r[3]).startswith("FALLBACK")]
    # every column carries something to get wrong
    assert all(v > 0 for row in want for v in row[1:])


@pytest.mark.parametrize("case", list(CASES))
def test_two_dedup_tables_hold_the_reference_s_pairs(q15, case):
    """Exactly two dedup tables, bidder's and auction's, each with one
    count per DISTINCT call on its column, and at every checkpoint as
    many rows as the prefix has distinct (day, value) pairs."""
    config, run_ = q15["config"], q15[case]
    ref = _bench_module("reference", config["reference"])
    gen = _generator(config, CASES[case])
    assert len(run_["dedup_ids"]) == 2
    (bidder_id, auction_id) = run_["dedup_ids"].values()
    for i, cp in enumerate(run_["checkpoints"]):
        readers = [dict(r) for r in cp["readers"]]
        assert (cp["dedup"][bidder_id], cp["dedup"][auction_id]) == \
            ref.pair_counts(readers, gen), f"checkpoint {i}"
        # what the benchmark compares
        assert max(cp["by_id"].values()) == ref.resident_rows(readers, gen)
    for name, schema in run_["dedup_schemas"].items():
        counts = [n for n, _t in schema if n.startswith("_cnt")]
        assert len(counts) == DISTINCT_CALLS, (name, schema)
        assert [n for n, _t in schema][:2] == ["_g0", "_value"]
    # the view keeps three state tables under the aggregate (value
    # state and the two dedup tables) beside the source's offsets and
    # the materialized rows: five, where a table a filtered call gave
    # eleven
    assert len(run_["state_tables"]) == 5
    # the store holds what the books say, and a pair's first count is
    # its rows: no pair with every count 0 is kept
    for rows in run_["dedup_rows"].values():
        assert all(r[-DISTINCT_CALLS] > 0 for r in rows)
    assert sorted(len(r) for r in run_["dedup_rows"].values()) == sorted(
        run_["checkpoints"][-1]["dedup"].values())


def test_the_dedup_rows_are_a_recount_per_call(q15):
    """The counts themselves, per pair and call, against numpy."""
    config = q15["config"]
    gen = _generator(config, CASES["two_days"])
    nexmark_gen = _bench_module("reference", "nexmark_gen")
    run_ = q15["two_days"]
    n = run_["checkpoints"][-1]["readers"][0]["rows"]
    bids = nexmark_gen.prefix("bid", n, gen)
    day = (bids["date_time"] // 86_400_000_000).tolist()
    price = bids["price"]
    ranks = np.stack([np.ones(n, dtype=bool), price < 10000,
                      (price >= 10000) & (price < 1000000),
                      price >= 1000000], axis=1).astype(int).tolist()
    for column, rows in zip(("bidder", "auction"),
                            run_["dedup_rows"].values()):
        want = collections.defaultdict(lambda: [0, 0, 0, 0])
        for d, v, r in zip(day, bids[column].tolist(), ranks):
            cnt = want[(str(np.datetime64(d, "D")), v)]
            for s in range(4):
                cnt[s] += r[s]
        assert rows == sorted(k + tuple(c) for k, c in want.items())


def test_the_text_is_upstreams_and_plans_one_aggregate(q15):
    text = q15["config"]["ddl"][-1]
    assert text.count("count(distinct ") == 8
    assert text.count(" filter (where ") == 9
    assert "TO_CHAR(date_time, 'YYYY-MM-DD') AS day" in text
    assert text.endswith("GROUP BY to_char(date_time, 'YYYY-MM-DD')")
    run_ = q15["one_day"]
    assert run_["plan"].count("HashAggExecutor") == 1
    assert run_["plan"].count("SourceExecutor") == 1
    calls = run_["calls"]
    assert len(calls) == 12
    distinct = [c for c in calls if c.distinct]
    assert len(distinct) == 8
    # the filter is the call's own, not folded into the argument: two
    # distinct input columns, and the same three filter columns under
    # both
    assert len({c.input_idx for c in distinct}) == 2
    assert [c.filter_idx is None for c in distinct] == \
        [True, False, False, False] * 2
    assert [c.filter_idx for c in distinct[1:4]] == \
        [c.filter_idx for c in distinct[5:8]]
    assert all(c.filter_idx is None for c in calls if not c.distinct)


def test_the_books_name_the_dedup_s_pairs_changes_and_seconds(q15):
    """`rw_metrics_history` by dedup table: resident pairs at the seal,
    pairs changed, rows made visible, the write-through's seconds; and
    the gating's stage."""
    config, run_ = q15["config"], q15["one_day"]
    ref = _bench_module("reference", config["reference"])
    gen = _generator(config, None)
    labels = [f"t{i}" for i in run_["dedup_ids"].values()]
    rows = sorted(run_["history"].values(), key=lambda h: h["ts"])
    data = [h for h in rows if h.get("source_rows")]
    assert len(data) >= 20
    for h in data:
        for label in labels:
            for field in ("pairs", "changed", "crossings", "persist_s",
                          "write_s"):
                assert f"agg_distinct.{label}.{field}" in h, (label, field)
        assert h["stage.host_emit.agg.distinct"] > 0
    last = run_["checkpoints"][-1]
    readers = [dict(r) for r in last["readers"]]
    assert tuple(data[-1][f"agg_distinct.{label}.pairs"]
                 for label in labels) == ref.pair_counts(readers, gen)
    # a new pair is a changed pair: the window's changes cover the
    # pairs it added, and a pair changed is at most a row
    for label, pairs in zip(labels, ref.pair_counts(readers, gen)):
        changed = sum(h[f"agg_distinct.{label}.changed"] for h in data)
        shown = sum(h[f"agg_distinct.{label}.crossings"] for h in data)
        assert pairs <= changed <= readers[0]["rows"]
        # the unfiltered call sees each pair once; each pair also
        # crosses at least one rank's count
        assert 2 * pairs <= shown <= 4 * pairs


def test_to_char_formats_once_a_live_day_of_a_chunk(q15):
    """The GROUP BY key's `to_char` (ISSUE 42): a barrier here is one
    chunk, so it formats once a day the chunk holds, two in the chunks
    that hold a midnight; the groups are the reference's all the
    same."""
    config, run_ = q15["config"], q15["two_days"]
    nexmark_gen = _bench_module("reference", "nexmark_gen")
    gen = _generator(config, CASES["two_days"])
    rows = sorted(run_["history"].values(), key=lambda h: h["ts"])
    data = [h for h in rows if h.get("source_rows")]
    assert len(data) >= 20
    # the history's newest row holds the chunk the source had read past
    # the last checkpoint
    n = run_["checkpoints"][-1]["readers"][0]["rows"] + CHUNK
    day = nexmark_gen.prefix("bid", n, gen)["date_time"] // 86_400_000_000
    days = [len(set(day[i:i + CHUNK].tolist())) for i in range(0, n, CHUNK)]
    assert [h["expr_to_char.formats"] for h in data] == days[-len(data):]
    assert sorted(set(days[-len(data):])) == [1, 2]
    assert all(h["expr_to_char.rows"] == CHUNK for h in data)
    ref = _bench_module("reference", config["reference"])
    last = run_["checkpoints"][-1]
    assert last["view"] == ref.reference(
        [dict(r) for r in last["readers"]], gen)
    assert sorted(row[0] for row in last["view"]) == [
        "2015-07-15", "2015-07-16", "2015-07-17"]


def test_a_chunk_that_straddles_midnight_by_a_microsecond_is_two_days():
    """One chunk whose rows lie 1 µs before midnight, on it and 1 µs
    after: two groups, split where the day turns, and two formats."""
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.utils.metrics import STREAMING as S
    config = _config()
    view = config["ddl"][-1].replace("FROM bid", "FROM t")
    rows = [(1, 10, 5, "2015-07-15 23:59:59.999999"),
            (2, 10, 50000, "2015-07-15 23:59:59.999999"),
            (1, 11, 5, "2015-07-16 00:00:00.000000"),
            (3, 12, 5000000, "2015-07-16 00:00:00.000001"),
            (3, 10, 5, "2015-07-16 00:00:00.000001"),
            (4, 13, 50000, "2015-07-15 23:59:59.999999"),
            (4, 13, 50000, "2015-07-16 00:00:00.000000"),
            (5, 10, 5, "2015-07-16 00:00:00.000001")]   # 8: no padding

    async def drive():
        fe = Frontend()
        try:
            await fe.execute(
                "CREATE TABLE t (auction BIGINT, bidder BIGINT, "
                "price BIGINT, date_time TIMESTAMP)")
            await fe.execute(view)
            formats = S.expr_to_char_formats.get()
            values = ", ".join(f"({a}, {b}, {p}, '{ts}')"
                               for a, b, p, ts in rows)
            await fe.execute(f"INSERT INTO t VALUES {values}")
            await fe.execute("FLUSH")
            return (await fe.execute(f"SELECT * FROM {config['view']}"),
                    S.expr_to_char_formats.get() - formats)
        finally:
            await fe.close()

    got, formats = asyncio.run(drive())
    assert collections.Counter(tuple(r) for r in got) == _recount(rows)
    assert sorted((r[0], r[1]) for r in got) == [("2015-07-15", 3),
                                                 ("2015-07-16", 5)]
    assert formats == 2


def test_recovery_mid_run_continues_to_the_same_rows_and_counts(q15):
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore
    config = q15["config"]
    obj = MemObjectStore()
    head = asyncio.run(_drive(config, "two_days", HummockLite(obj),
                              barriers=11))
    tail = asyncio.run(_drive(config, "two_days", HummockLite(obj),
                              barriers=BARRIERS - 12, recover=True))
    whole = q15["two_days"]
    # a checkpoint is named by the rows it covers (the recovered
    # session's first barrier carries a chunk of its own)
    by_rows = {cp["readers"][0]["rows"]: cp for cp in whole["checkpoints"]}
    assert head["checkpoints"][-1]["view"] == by_rows[
        head["checkpoints"][-1]["readers"][0]["rows"]]["view"]
    matched = 0
    for got in tail["checkpoints"]:
        want = by_rows.get(got["readers"][0]["rows"])
        if want is None:
            continue
        matched += 1
        assert got["view"] == want["view"]
        assert got["stored"] == want["stored"] == \
            list(want["dedup"].values())
    assert matched >= 10
    assert got["readers"] == whole["checkpoints"][-1]["readers"]
    # per pair and call, the counts of the uninterrupted run
    assert list(tail["dedup_rows"].values()) == \
        list(whole["dedup_rows"].values())


def test_at_parallelism_4_the_view_is_exact_or_refused_by_name():
    """On the CPU mesh: equal to the reference, or an error that names
    the gap. Never a silent wrong answer."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from risingwave_tpu.frontend.session import Frontend
    _bench_module("reference", "nexmark_gen")    # benchmark/ on the path
    config = _config()
    ref = _bench_module("reference", config["reference"])
    gen = _generator(config, GAP_TWO_DAYS_NS)

    async def drive():
        fe = Frontend(rate_limit=1, min_chunks=1, parallelism=4)
        try:
            for ddl in _ddl(config, GAP_TWO_DAYS_NS):
                await fe.execute(ddl)
            await fe.step(12)
            return await _checkpoint(fe, config["view"])
        finally:
            await fe.close()

    try:
        cp = asyncio.run(drive())
    except Exception as e:   # noqa: BLE001 - the message is the test
        assert re.search(r"DISTINCT|distinct|parallelism|mesh", str(e)), e
        return
    readers = [dict(r) for r in cp["readers"]]
    assert cp["view"] == ref.reference(readers, gen)
    assert sorted(cp["dedup"].values()) == \
        sorted(ref.pair_counts(readers, gen))


def test_through_serving_and_pgwire_with_the_session_s_defaults(tmp_path):
    """The DDL as the file has it, over pgwire to the served process, no
    SET: it parses, binds, plans and stays exact."""
    run = _run()
    from pgclient import PgClient
    from risingwave_tpu import __main__ as main
    config = _config()
    ref = _bench_module("reference", config["reference"])

    async def drive():
        async with main.serving(str(tmp_path), port=0) as (fe, srv, hb), \
                await PgClient.connect(srv.port) as pg:
            heartbeat = run.Heartbeat(fe, hb)
            await heartbeat.pause()          # barriers by FLUSH only
            for ddl in config["ddl"]:
                await pg.query(ddl.format(seed=SEED))
            for _ in range(3):
                await pg.query("FLUSH")
            got = collections.Counter(
                await pg.query(f"SELECT * FROM {config['view']}"))
            readers = run.checkpointed_rows(
                run.source_readers(fe, config["view"]))
            rewrites = await pg.query(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites")
        await fe.close()
        return got, readers, rewrites

    got, readers, rewrites = asyncio.run(drive())
    gen = _generator(config, None)
    assert readers[0]["rows"] > 0
    assert got == ref.reference([dict(r) for r in readers], gen)
    assert not [r for r in rewrites if str(r[3]).startswith("FALLBACK")]


# -- a retracting input, through SQL ----------------------------------------

def _recount(rows):
    """q15 over (auction, bidder, price, date_time text) rows."""
    out = collections.Counter()
    for day in {r[3][:10] for r in rows}:
        of_day = [r for r in rows if r[3][:10] == day]
        ranks = [lambda p: True, lambda p: p < 10000,
                 lambda p: 10000 <= p < 1000000, lambda p: p >= 1000000]
        priced = [r for r in of_day if r[2] is not None]
        row = [day, len(of_day)]
        row += [sum(1 for r in priced if f(r[2])) for f in ranks[1:]]
        for col in (1, 0):
            row += [len({r[col] for r in of_day if r[col] is not None})]
            row += [len({r[col] for r in priced
                         if r[col] is not None and f(r[2])})
                    for f in ranks[1:]]
        out[tuple(row)] += 1
    return out


def test_the_view_over_a_table_that_updates_and_deletes_is_a_recount():
    """The configuration's view over a table instead of the source:
    UPDATEs arrive as U-/U+ and flip the filter a row passes, DELETEs
    take pairs out, a NULL bidder and a NULL price count for nothing."""
    from risingwave_tpu.frontend.session import Frontend
    config = _config()
    view = config["ddl"][-1]
    assert view.count("FROM bid") == 1
    view = view.replace("FROM bid", "FROM t")
    rng = np.random.default_rng(41)
    days = ["2015-07-15 23:59:5", "2015-07-16 00:00:0"]

    async def drive():
        fe = Frontend()
        seen = []
        try:
            await fe.execute(
                "CREATE TABLE t (auction BIGINT, bidder BIGINT, "
                "price BIGINT, date_time TIMESTAMP)")
            await fe.execute(view)
            agg = _agg(fe, config["view"])
            rows = []
            for step in range(14):
                new = [(int(rng.integers(1, 6)),
                        [None, 10, 11, 12][int(rng.integers(0, 4))],
                        [None, 5, 50000, 5000000][int(rng.integers(0, 4))],
                        days[int(rng.integers(0, 2))]
                        + str(int(rng.integers(0, 10))))
                       for _ in range(6)]
                values = ", ".join(
                    "(" + ", ".join(
                        "NULL" if v is None else
                        (f"'{v}'" if isinstance(v, str) else str(v))
                        for v in r) + ")" for r in new)
                await fe.execute(f"INSERT INTO t VALUES {values}")
                rows += new
                a = int(rng.integers(1, 6))
                if step % 3 == 1:
                    price = [5, 50000, 5000000][int(rng.integers(0, 3))]
                    await fe.execute(
                        f"UPDATE t SET price = {price} WHERE auction = {a}")
                    rows = [(r[0], r[1], price, r[3]) if r[0] == a else r
                            for r in rows]
                if step % 3 == 2:
                    await fe.execute(f"DELETE FROM t WHERE auction = {a}")
                    rows = [r for r in rows if r[0] != a]
                await fe.execute("FLUSH")
                got = collections.Counter(tuple(r) for r in await fe.execute(
                    f"SELECT * FROM {config['view']}"))
                assert got == _recount(rows), f"step {step}"
                pairs = [len(list(t.iter_rows()))
                         for t in agg.distinct_tables.values()]
                assert pairs == [
                    len({(r[3][:10], r[c]) for r in rows
                         if r[c] is not None}) for c in (1, 0)]
                seen.append((len(rows), pairs))
            # the same text as a batch SELECT over the table's snapshot:
            # the batch aggregate takes a call's filter the same way
            select = view.split(" AS\n", 1)[1]
            assert collections.Counter(
                tuple(r) for r in await fe.execute(select)) == got
            return seen
        finally:
            await fe.close()

    seen = asyncio.run(drive())
    # rows left and pairs left with them
    assert any(b[0] < a[0] for a, b in zip(seen, seen[1:]))
    assert any(sum(b[1]) < sum(a[1]) for a, b in zip(seen, seen[1:]))


# -- the binder: what is taken, what is still refused ---------------------

TAKEN = [
    "count(DISTINCT bidder) FILTER (WHERE price < 10000)",
    "sum(DISTINCT price) filter (where bidder > 1000)",
    "avg(DISTINCT price) FILTER (WHERE price < 10000)",
    "count(bidder) FILTER (WHERE price < 10000)",
    "count(*) filter (where price >= 10000 and price < 1000000)",
    "sum(price) FILTER (WHERE price < 10000)",
    "min(DISTINCT price) FILTER (WHERE bidder > 1000)",
    "max(price) FILTER (WHERE bidder > 1000)",
]
REFUSED = [
    ("string_agg(channel, ',') FILTER (WHERE price < 10000)",
     r"FILTER \(WHERE \.\.\.\) on string_agg\(\.\.\.\) is not supported"),
    ("array_agg(bidder) FILTER (WHERE price < 10000)",
     r"FILTER \(WHERE \.\.\.\) on array_agg\(\.\.\.\) is not supported"),
    ("approx_count_distinct(bidder) FILTER (WHERE price < 10000)",
     r"on approx_count_distinct\(\.\.\.\) is not supported"),
    ("count(DISTINCT bidder) FILTER (WHERE price)",
     r"FILTER \(WHERE \.\.\.\) must be a boolean expression"),
    ("lower(channel) FILTER (WHERE price < 10000)",
     r"FILTER specified, but lower\(\) is not an aggregate"),
    ("string_agg(DISTINCT channel, ',')",
     r"string_agg\(DISTINCT \.\.\.\) is not supported yet"),
]


def _bind(item: str):
    from risingwave_tpu.frontend.session import Frontend

    async def run_():
        fe = Frontend()
        try:
            await fe.execute(
                "CREATE SOURCE bid WITH (connector='nexmark', "
                "nexmark.table.type='bid', nexmark.event.num=2000)")
            await fe.execute(
                f"CREATE MATERIALIZED VIEW v AS SELECT auction, {item} "
                "AS x FROM bid GROUP BY auction")
            await fe.step(2)
            return await fe.execute("SELECT * FROM v")
        finally:
            await fe.close()
    return asyncio.run(run_())


@pytest.mark.parametrize("item", TAKEN)
def test_the_binder_takes(item):
    from risingwave_tpu.connectors.nexmark import NexmarkConfig, gen_bids
    rows = _bind(item)
    assert rows
    if "DISTINCT bidder" in item:
        bids = gen_bids(np.arange(2000 * 46 // 50, dtype=np.int64),
                        NexmarkConfig(event_num=2000))
        want = collections.defaultdict(set)
        for a, b, p in zip(bids["auction"].tolist(),
                           bids["bidder"].tolist(),
                           bids["price"].tolist()):
            want[a].update([b] if p < 10000 else [])
        got = dict(rows)
        assert got == {a: len(s) for a, s in want.items()
                       if a in got}


@pytest.mark.parametrize("item,message", REFUSED)
def test_the_binder_still_refuses(item, message):
    with pytest.raises(Exception, match=message):
        _bind(item)
