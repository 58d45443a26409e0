"""FROM-subqueries, HAVING, BETWEEN, expressions over aggregates.

Reference parity: derived-table binding (src/frontend/src/binder/ bind
of Query in FROM), HAVING planning (logical_agg.rs filters over the
agg), and nexmark q4 — the named baseline config whose SQL needs all
three (e2e_test/streaming/nexmark/views/q4.slt.part:1-15).
"""

import asyncio

import numpy as np
import pytest

from risingwave_tpu.frontend import Frontend
from risingwave_tpu.frontend.parser import ParseError, parse
from risingwave_tpu.frontend.planner import PlanError


# -- parser ---------------------------------------------------------------


def test_parser_subquery_having_between():
    s = parse("SELECT x, count(*) FROM (SELECT a AS x FROM t) q "
              "GROUP BY x HAVING count(*) > 5")
    from risingwave_tpu.frontend.ast import Bin, Subquery
    assert isinstance(s.from_item, Subquery)
    assert s.from_item.alias == "q"
    assert s.having is not None

    s = parse("SELECT * FROM t WHERE a BETWEEN 1 AND 5 AND b = 2")
    # BETWEEN desugars to (a>=1 AND a<=5), ANDed with b=2
    assert isinstance(s.where, Bin) and s.where.op == "and"

    # the alias may be left off, as upstream's nexmark views leave it
    # (since PR 45); what may not is the closing parenthesis
    assert parse("SELECT * FROM (SELECT a FROM t)").from_item.alias is None
    with pytest.raises(ParseError):
        parse("SELECT * FROM (SELECT a FROM t")


# -- streaming e2e --------------------------------------------------------


def _bid_source(n=20000, gap_ns=100_000_000):
    return ("CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', "
            f"nexmark.event.num={n}, nexmark.max.chunk.size=1024, "
            f"nexmark.min.event.gap.in.ns={gap_ns})")


def test_having_filters_groups():
    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(_bid_source())
        await fe.execute(
            "CREATE MATERIALIZED VIEW hot AS SELECT bidder, COUNT(*) "
            "AS cnt FROM bid GROUP BY bidder HAVING COUNT(*) > 10")
        await fe.execute(
            "CREATE MATERIALIZED VIEW all_b AS SELECT bidder, COUNT(*) "
            "AS cnt FROM bid GROUP BY bidder")
        await fe.step(8)
        hot = await fe.execute("SELECT bidder, cnt FROM hot")
        allb = await fe.execute("SELECT bidder, cnt FROM all_b")
        await fe.close()
        return hot, allb

    hot, allb = asyncio.run(run())
    expect = sorted(r for r in allb if r[1] > 10)
    assert 0 < len(hot) < len(allb)
    assert sorted(hot) == expect


def test_expression_over_aggregates():
    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(_bid_source())
        await fe.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT auction, "
            "SUM(price) + COUNT(*) AS mix, MAX(price) - MIN(price) "
            "AS spread FROM bid GROUP BY auction")
        await fe.execute(
            "CREATE MATERIALIZED VIEW raw AS SELECT auction, price "
            "FROM bid")
        await fe.step(8)
        mix = await fe.execute(
            "SELECT auction, mix, spread FROM m ORDER BY auction")
        raw = await fe.execute("SELECT auction, price FROM raw")
        await fe.close()
        return mix, raw

    mix, raw = asyncio.run(run())
    by_auction = {}
    for a, p in raw:
        by_auction.setdefault(a, []).append(p)
    expect = sorted((a, sum(ps) + len(ps), max(ps) - min(ps))
                    for a, ps in by_auction.items())
    assert len(mix) > 10
    assert mix == expect


Q4_FROM = {
    # the rewritten text this test pinned before ISSUE 31
    "join_on": "FROM auction AS a JOIN bid AS b ON a.id = b.auction"
               "  WHERE b.date_time BETWEEN a.date_time AND a.expires",
    # upstream's form: a comma-separated FROM list, aliases without AS
    "comma": "FROM auction a, bid b WHERE a.id = b.auction AND"
             "  b.date_time BETWEEN a.date_time AND a.expires",
}


@pytest.mark.parametrize("form", list(Q4_FROM))
def test_nexmark_q4_subquery_avg(form):
    """q4: average final (=max) bid price per category, via a derived
    table — the baseline-config query the frontend previously could
    not express (VERDICT r4 item 4). Upstream's own text, from the
    benchmark's configuration file, runs in tests/test_nexmark_q4.py."""
    async def run():
        fe = Frontend(min_chunks=8)
        n = 20000
        gap = 100_000_000
        for t in ("auction", "bid"):
            await fe.execute(
                f"CREATE SOURCE {t} WITH (connector='nexmark', "
                f"nexmark.table.type='{t}', nexmark.event.num={n}, "
                f"nexmark.min.event.gap.in.ns={gap})")
        await fe.execute(
            "CREATE MATERIALIZED VIEW q4 AS "
            "SELECT category, AVG(final) AS avg_final FROM ("
            "  SELECT a.category AS category, MAX(b.price) AS final"
            f"  {Q4_FROM[form]}"
            "  GROUP BY a.id, a.category) AS q "
            "GROUP BY category")
        await fe.step(10)
        rows = await fe.execute(
            "SELECT category, avg_final FROM q4 ORDER BY category")
        await fe.close()
        return rows

    rows = asyncio.run(run())

    # oracle: numpy recompute from the deterministic generators
    from risingwave_tpu.connectors.nexmark import (
        AUCTION_PROPORTION, BID_PROPORTION, NexmarkConfig,
        gen_auctions, gen_bids,
    )
    n = 20000
    cfg_a = NexmarkConfig(table_type="auction", event_num=n,
                          min_event_gap_in_ns=100_000_000)
    cfg_b = NexmarkConfig(table_type="bid", event_num=n,
                          min_event_gap_in_ns=100_000_000)
    n_auc = n * AUCTION_PROPORTION // 50
    n_bid = n * BID_PROPORTION // 50
    auctions = gen_auctions(np.arange(n_auc, dtype=np.int64), cfg_a)
    bids = gen_bids(np.arange(n_bid, dtype=np.int64), cfg_b)
    finals = {}            # (auction id) -> (category, max price)
    a_by_id = {int(i): k for k, i in enumerate(auctions["id"])}
    for auc, price, ts in zip(bids["auction"], bids["price"],
                              bids["date_time"]):
        k = a_by_id.get(int(auc))
        if k is None:
            continue
        if not (auctions["date_time"][k] <= ts
                <= auctions["expires"][k]):
            continue
        cat = int(auctions["category"][k])
        key = int(auc)
        if key not in finals or finals[key][1] < int(price):
            finals[key] = (cat, int(price))
    per_cat = {}
    for cat, price in finals.values():
        per_cat.setdefault(cat, []).append(price)
    expect = sorted((c, sum(ps) / len(ps))
                    for c, ps in per_cat.items())
    assert len(rows) >= 2
    got = [(c, v) for c, v in rows]
    assert [c for c, _ in got] == [c for c, _ in expect]
    for (_, gv), (_, ev) in zip(got, expect):
        assert abs(gv - ev) < 1e-9 * max(1.0, abs(ev))


def test_subquery_plain_projection():
    """Non-agg derived table: hidden pk carries through."""
    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(_bid_source())
        await fe.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT p2, bidder FROM "
            "(SELECT price * 2 AS p2, bidder, auction FROM bid) q "
            "WHERE p2 > 2000")
        await fe.execute(
            "CREATE MATERIALIZED VIEW o AS SELECT price, bidder "
            "FROM bid WHERE price * 2 > 2000")
        await fe.step(6)
        m = await fe.execute("SELECT p2, bidder FROM m")
        o = await fe.execute("SELECT price, bidder FROM o")
        await fe.close()
        return m, o

    m, o = asyncio.run(run())
    assert len(m) > 0
    assert sorted(m) == sorted((p * 2, b) for p, b in o)


# -- batch ----------------------------------------------------------------


def test_batch_having_and_subquery():
    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(_bid_source())
        await fe.execute(
            "CREATE MATERIALIZED VIEW raw AS SELECT auction, bidder, "
            "price FROM bid")
        await fe.step(6)
        h = await fe.execute(
            "SELECT auction, COUNT(*) AS c FROM raw GROUP BY auction "
            "HAVING COUNT(*) > 3 ORDER BY auction")
        base = await fe.execute(
            "SELECT auction, COUNT(*) AS c FROM raw GROUP BY auction "
            "ORDER BY auction")
        sq = await fe.execute(
            "SELECT q.c + 1 AS c1 FROM (SELECT auction, COUNT(*) AS c "
            "FROM raw GROUP BY auction) q ORDER BY c1 LIMIT 3")
        await fe.close()
        return h, base, sq

    h, base, sq = asyncio.run(run())
    assert h == [r for r in base if r[1] > 3]
    assert sq == sorted([(r[1] + 1,) for r in base])[:3]


def test_having_without_group_key_projected():
    """Inner-q4 shape standalone: GROUP BY keys absent from SELECT."""
    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(_bid_source())
        await fe.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT MAX(price) AS mp "
            "FROM bid GROUP BY auction")
        await fe.step(6)
        rows = await fe.execute("SELECT mp FROM m")
        star = await fe.execute("SELECT * FROM m")
        await fe.close()
        return rows, star

    rows, star = asyncio.run(run())
    assert len(rows) > 10
    # the hidden _g0 group key must NOT leak through SELECT *
    assert all(len(r) == 1 for r in star)


def test_eowc_over_subquery_rejected():
    """The inner query's EOWC watermark column is meaningless against
    the outer schema — gate on it and the MV never emits (code-review
    r5 finding); a clean PlanError is the correct behavior."""
    async def run():
        fe = Frontend(min_chunks=8)
        await fe.execute(_bid_source())
        with pytest.raises(PlanError):
            await fe.execute(
                "CREATE MATERIALIZED VIEW e AS SELECT c FROM ("
                "SELECT window_start, COUNT(*) AS c FROM TUMBLE(bid, "
                "date_time, INTERVAL '10' SECOND) GROUP BY "
                "window_start) q EMIT ON WINDOW CLOSE")
        await fe.close()

    asyncio.run(run())
