"""Query corpus: Nexmark + TPC-H streaming MVs, oracle-checked.

Reference parity: e2e_test/streaming/nexmark/*.slt.part and
e2e_test/streaming/tpch/ — each entry runs CREATE SOURCE + CREATE
MATERIALIZED VIEW + SELECT on the in-process session and compares
against a numpy oracle computed from the deterministic generators
(the .slt expected-rows discipline with computed snapshots).

Queries whose reference form needs surface we lack are listed at the
bottom with the blocking feature, so the corpus table stays honest.
Other corpus entries live in their own files: q1 (test_e2e_q1), q4
(test_nexmark_q4, test_subquery_having), q5-lite (test_e2e_q5), q7-core
(test_e2e_q7),
q8 (test_e2e_q8, test_cluster_sql), TPC-H q3 (test_tpch).
"""

import asyncio
import collections

import numpy as np
import pytest

from risingwave_tpu.connectors.nexmark import (
    NexmarkConfig, gen_auctions, gen_bids, gen_persons,
)
from risingwave_tpu.frontend.session import Frontend

N_EVENTS = 4000
GAP_NS = 100_000_000
WINDOW_US = 10_000_000

NEXMARK_SOURCES = [
    "CREATE SOURCE {t} WITH (connector='nexmark', "
    "nexmark.table.type='{t}', nexmark.event.num={n}, "
    "nexmark.min.event.gap.in.ns={gap})".format(t=t, n=N_EVENTS,
                                                gap=GAP_NS)
    for t in ("bid", "auction", "person")
]


def _gen(n=N_EVENTS):
    cfg = NexmarkConfig(event_num=n, min_event_gap_in_ns=GAP_NS)
    bids = gen_bids(np.arange(n * 46 // 50, dtype=np.int64), cfg)
    aucs = gen_auctions(np.arange(n * 3 // 50, dtype=np.int64), cfg)
    pers = gen_persons(np.arange(n // 50, dtype=np.int64), cfg)
    return bids, aucs, pers


def _run(mv_sql, select_sql, sources=NEXMARK_SOURCES, steps=12):
    async def run():
        fe = Frontend(min_chunks=8)
        for s in sources:
            await fe.execute(s)
        await fe.execute(mv_sql)
        await fe.step(steps)
        rows = await fe.execute(select_sql)
        await fe.close()
        return rows

    return asyncio.run(run())


def _explain(mv_sql, sources=NEXMARK_SOURCES):
    """EXPLAIN of a CREATE MATERIALIZED VIEW's select."""
    async def run():
        fe = Frontend()
        for s in sources:
            await fe.execute(s)
        rows = await fe.execute("EXPLAIN " + mv_sql.split(" AS ", 1)[1])
        await fe.close()
        return "\n".join(r[0] for r in rows)

    return asyncio.run(run())


# -- Nexmark ---------------------------------------------------------------


def test_nexmark_q0_passthrough():
    rows = _run(
        "CREATE MATERIALIZED VIEW q0 AS SELECT auction, bidder, "
        "price, date_time FROM bid",
        "SELECT * FROM q0")
    bids, _a, _p = _gen()
    expect = collections.Counter(zip(
        bids["auction"].tolist(), bids["bidder"].tolist(),
        bids["price"].tolist(), bids["date_time"].tolist()))
    assert collections.Counter(map(tuple, rows)) == expect


def test_nexmark_q2_filtered_auctions():
    rows = _run(
        "CREATE MATERIALIZED VIEW q2 AS SELECT auction, price FROM bid "
        "WHERE auction = 1007 OR auction = 1020 OR auction = 1040 "
        "OR auction = 1087",
        "SELECT * FROM q2")
    bids, _a, _p = _gen()
    keep = {1007, 1020, 1040, 1087}
    expect = collections.Counter(
        (a, p) for a, p in zip(bids["auction"].tolist(),
                               bids["price"].tolist()) if a in keep)
    assert collections.Counter(map(tuple, rows)) == expect
    assert len(rows) > 0


def test_nexmark_q3_local_item_suggestion():
    rows = _run(
        "CREATE MATERIALIZED VIEW q3 AS SELECT p.name, p.city, "
        "p.state, a.id FROM auction AS a JOIN person AS p "
        "ON a.seller = p.id WHERE a.category = 10 AND "
        "(p.state = 'OR' OR p.state = 'ID' OR p.state = 'CA')",
        "SELECT * FROM q3")
    _b, aucs, pers = _gen()
    pmap = {int(i): (nm, c, s) for i, nm, c, s in zip(
        pers["id"], pers["name"], pers["city"], pers["state"])}
    expect = collections.Counter(
        (pmap[int(s)][0], pmap[int(s)][1], pmap[int(s)][2], int(i))
        for i, s, cat in zip(aucs["id"], aucs["seller"],
                             aucs["category"])
        if cat == 10 and int(s) in pmap
        and pmap[int(s)][2] in ("OR", "ID", "CA"))
    assert collections.Counter(map(tuple, rows)) == expect
    assert len(rows) > 0


def test_nexmark_q7_highest_bid_per_window():
    """Full q7 (not just the MAX core): bids matching their window's
    max price, via an equi-join against the windowed-max derived table
    — a join over a RETRACTING aggregate (the arrangement-keyed join
    the planner previously refused)."""
    rows = _run(
        "CREATE MATERIALIZED VIEW q7 AS "
        "SELECT b.auction, b.price, b.bidder, b.date_time "
        "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) AS b "
        "JOIN (SELECT MAX(price) AS maxprice, window_start AS ws "
        "      FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
        "      GROUP BY window_start) AS m "
        "ON b.window_start = m.ws AND b.price = m.maxprice",
        "SELECT * FROM q7")
    bids, _a, _p = _gen()
    win = (bids["date_time"] // WINDOW_US) * WINDOW_US
    wmax = collections.defaultdict(int)
    for w, p in zip(win.tolist(), bids["price"].tolist()):
        wmax[w] = max(wmax[w], p)
    expect = collections.Counter(
        (a, p, bd, t) for a, bd, p, t, w in zip(
            bids["auction"].tolist(), bids["bidder"].tolist(),
            bids["price"].tolist(), bids["date_time"].tolist(),
            win.tolist())
        if p == wmax[w])
    assert collections.Counter(map(tuple, rows)) == expect
    assert len(rows) > 0


def test_nexmark_q9_auction_top_bid_row_number():
    """q9 shape: ROW_NUMBER() OVER (PARTITION BY auction ORDER BY
    price DESC, date_time ASC), filtered to rn = 1 in an outer query
    over the derived table."""
    mv = ("CREATE MATERIALIZED VIEW q9 AS SELECT auction, price, "
          "date_time FROM ("
          "  SELECT auction, price, date_time, row_number() OVER ("
          "    PARTITION BY auction ORDER BY price DESC, date_time ASC"
          "  ) AS rn FROM bid) AS t WHERE rn = 1")
    rows = _run(mv, "SELECT * FROM q9")
    # a grouped top-N since PR 45 (frontend/opt/over_window_to_topn.py)
    plan = _explain(mv)
    assert "GroupTopNExecutor  -- group: [auction], order: [price DESC, " \
        "date_time ASC], limit: 1, append_only: true" in plan
    assert "OverWindow" not in plan
    bids, _a, _p = _gen()
    best = {}
    for a, p, t in zip(bids["auction"].tolist(), bids["price"].tolist(),
                       bids["date_time"].tolist()):
        cur = best.get(a)
        if cur is None or (-p, t) < cur:
            best[a] = (-p, t)
    assert len(rows) == len(best)
    for a, p, t in rows:
        assert best[a] == (-p, t), (a, p, t, best[a])


def test_nexmark_q20_bid_with_auction_details():
    rows = _run(
        "CREATE MATERIALIZED VIEW q20 AS SELECT b.auction, b.bidder, "
        "b.price, a.item_name, a.category FROM bid AS b "
        "JOIN auction AS a ON b.auction = a.id WHERE a.category = 12",
        "SELECT * FROM q20")
    bids, aucs, _p = _gen()
    amap = {int(i): (nm, int(c)) for i, nm, c in zip(
        aucs["id"], aucs["item_name"], aucs["category"])}
    expect = collections.Counter(
        (a, bd, p, amap[a][0], amap[a][1])
        for a, bd, p in zip(bids["auction"].tolist(),
                            bids["bidder"].tolist(),
                            bids["price"].tolist())
        if a in amap and amap[a][1] == 12)
    assert collections.Counter(map(tuple, rows)) == expect
    assert len(rows) > 0


def _bid_counts(bids):
    return collections.Counter(bids["auction"].tolist())


def test_nexmark_q101_auction_max_bid():
    rows = _run(
        "CREATE MATERIALIZED VIEW q101 AS SELECT a.id, a.item_name, "
        "b.max_price FROM auction AS a JOIN ("
        "  SELECT auction, MAX(price) AS max_price FROM bid "
        "  GROUP BY auction) AS b ON a.id = b.auction",
        "SELECT * FROM q101")
    bids, aucs, _p = _gen()
    mx = collections.defaultdict(int)
    for a, p in zip(bids["auction"].tolist(), bids["price"].tolist()):
        mx[a] = max(mx[a], p)
    names = dict(zip(aucs["id"].tolist(), aucs["item_name"].tolist()))
    expect = {(i, names[i], mx[i]) for i in names if i in mx}
    assert set(map(tuple, rows)) == expect
    assert len(rows) > 0


def test_nexmark_q103_popular_auctions_having():
    rows = _run(
        "CREATE MATERIALIZED VIEW q103 AS SELECT a.id, a.item_name "
        "FROM auction AS a JOIN ("
        "  SELECT auction FROM bid GROUP BY auction "
        "  HAVING count(*) >= 15) AS b ON a.id = b.auction",
        "SELECT * FROM q103")
    bids, aucs, _p = _gen()
    counts = _bid_counts(bids)
    names = dict(zip(aucs["id"].tolist(), aucs["item_name"].tolist()))
    expect = {(i, names[i]) for i in names if counts.get(i, 0) >= 15}
    assert set(map(tuple, rows)) == expect
    assert len(rows) > 0


def test_nexmark_q105_top_auctions_by_bid_count():
    rows = _run(
        "CREATE MATERIALIZED VIEW q105 AS SELECT auction, count(*) "
        "AS num FROM bid GROUP BY auction ORDER BY num DESC LIMIT 10",
        "SELECT auction, num FROM q105 ORDER BY num DESC")
    bids, _a, _p = _gen()
    counts = _bid_counts(bids)
    top = sorted(counts.values(), reverse=True)[:10]
    assert len(rows) == 10
    assert sorted((n for _a2, n in rows), reverse=True) == top
    for a, n in rows:
        assert counts[a] == n


@pytest.mark.parametrize("from_where", [
    # the rewritten text this test pinned before ISSUE 31, and
    # upstream's comma-separated FROM list with aliases without AS
    "FROM auction AS a JOIN bid AS b ON a.id = b.auction "
    "WHERE b.date_time BETWEEN a.date_time AND a.expires",
    "FROM auction a, bid b WHERE a.id = b.auction "
    "AND b.date_time BETWEEN a.date_time AND a.expires",
], ids=["join_on", "comma"])
def test_nexmark_q106_min_final_price(from_where):
    rows = _run(
        "CREATE MATERIALIZED VIEW q106 AS SELECT MIN(final) AS m "
        "FROM ("
        "  SELECT a.id AS id, MAX(b.price) AS final "
        f"  {from_where} "
        "  GROUP BY a.id) AS q",
        "SELECT m FROM q106")
    bids, aucs, _p = _gen()
    window = {}
    for i, dt, exp in zip(aucs["id"].tolist(),
                          aucs["date_time"].tolist(),
                          aucs["expires"].tolist()):
        window[i] = (dt, exp)
    finals = {}
    for a, p, t in zip(bids["auction"].tolist(), bids["price"].tolist(),
                       bids["date_time"].tolist()):
        if a in window and window[a][0] <= t <= window[a][1]:
            finals[a] = max(finals.get(a, 0), p)
    assert len(rows) == 1
    assert rows[0][0] == min(finals.values())


# -- TPC-H -----------------------------------------------------------------

TPCH_CUSTOMERS, TPCH_ORDERS = 300, 2000

TPCH_SOURCES = [
    "CREATE SOURCE {t} WITH (connector='tpch', tpch.table='{t}', "
    "tpch.customers={c}, tpch.orders={o})".format(
        t=t, c=TPCH_CUSTOMERS, o=TPCH_ORDERS)
    for t in ("customer", "orders", "lineitem")
]


def _tpch_lineitem():
    from risingwave_tpu.connectors.tpch import (
        LINES_PER_ORDER, TpchConfig, gen_lineitem,
    )
    cfg = TpchConfig(customers=TPCH_CUSTOMERS, orders=TPCH_ORDERS)
    return gen_lineitem(
        np.arange(TPCH_ORDERS * LINES_PER_ORDER, dtype=np.int64), cfg)


def test_tpch_q1_pricing_summary():
    """q1: the pricing-summary aggregates per (returnflag, linestatus)
    (e2e_test/streaming/tpch/q1 shape; no date filter — the generator
    domain is fully in range)."""
    rows = _run(
        "CREATE MATERIALIZED VIEW q1 AS SELECT l_returnflag, "
        "l_linestatus, sum(l_quantity) AS sum_qty, "
        "sum(l_extendedprice) AS sum_base, count(*) AS cnt "
        "FROM lineitem GROUP BY l_returnflag, l_linestatus",
        "SELECT * FROM q1 ORDER BY l_returnflag, l_linestatus",
        sources=TPCH_SOURCES)
    li = _tpch_lineitem()
    import decimal
    agg = {}
    for rf, ls, q, ep in zip(li["l_returnflag"], li["l_linestatus"],
                             li["l_quantity"].tolist(),
                             li["l_extendedprice"].tolist()):
        k = (rf, ls)
        a = agg.setdefault(k, [0, 0, 0])
        a[0] += q
        a[1] += ep          # physical scaled int
        a[2] += 1
    expect = sorted(
        (rf, ls, q, decimal.Decimal(ep).scaleb(-4), c)
        for (rf, ls), (q, ep, c) in agg.items())
    got = [tuple(r) for r in rows]
    assert len(got) == len(expect)
    for g, e in zip(got, expect):
        assert g[0] == e[0] and g[1] == e[1] and g[2] == e[2] \
            and g[4] == e[4]
        assert decimal.Decimal(g[3]) == e[3], (g, e)


def test_tpch_q6_forecast_revenue():
    """q6: global revenue sum under discount/quantity filters
    (e2e_test/streaming/tpch/q6 shape)."""
    rows = _run(
        "CREATE MATERIALIZED VIEW q6 AS SELECT "
        "sum(l_extendedprice * l_discount) AS revenue FROM lineitem "
        "WHERE l_discount BETWEEN 0.03 AND 0.07 AND l_quantity < 24",
        "SELECT revenue FROM q6", sources=TPCH_SOURCES)
    li = _tpch_lineitem()
    import decimal
    rev = decimal.Decimal(0)
    for ep, d, q in zip(li["l_extendedprice"].tolist(),
                        li["l_discount"].tolist(),
                        li["l_quantity"].tolist()):
        dd = decimal.Decimal(d).scaleb(-4)
        if decimal.Decimal("0.03") <= dd <= decimal.Decimal("0.07") \
                and q < 24:
            rev += decimal.Decimal(ep).scaleb(-4) * dd
    assert len(rows) == 1
    got = decimal.Decimal(rows[0][0])
    assert got == rev.quantize(decimal.Decimal(10) ** -4), (got, rev)


# -- honest gaps -----------------------------------------------------------
# Reference queries NOT in this corpus and why (checked against
# /root/reference/e2e_test/streaming/nexmark/). q4 is no gap: upstream's
# own text (comma join, aliases without AS) runs from the benchmark's
# configuration file in tests/test_nexmark_q4.py, against
# benchmark/reference/nexmark_q4.py.
# q5 is no gap either: upstream's q5.slt.part is the JOIN form (two
# HOP + count subplans, a MAX over one, `JOIN ... ON starttime =
# starttime_c AND num >= maxn`), not the Beam form with a scalar
# subquery; it runs from the benchmark's configuration file in
# tests/test_nexmark_q5.py, against benchmark/reference/nexmark_q5.py
# (test_e2e_q5 tests models/nexmark.build_q5, the hand-built top-1 core).
# q15 is no gap either: upstream's text (per day, twelve aggregates,
# eight of them `count(DISTINCT ...) FILTER (WHERE ...)`) runs from the
# benchmark's configuration file in tests/test_nexmark_q15.py, against
# benchmark/reference/nexmark_q15.py; test_nexmark_q15_per_minute_stats
# below keeps the older per-minute shape without filters.
# q9 is no gap either: upstream's text (`A.*`, the alias-less derived
# table, the join feeding `ROW_NUMBER() ... <= 1`) runs from the
# benchmark's configuration file in tests/test_nexmark_q9.py, against
# benchmark/reference/nexmark_q9.py, planned as a grouped top-N; the
# q9, q18 and q19 shapes above (over `bid` alone) are planned as one
# too since PR 45. Upstream's q19 also selects its rank
# (`SELECT * ... WHERE rank_number <= 10`): that form stays on the
# general over-window executor, a top-N does not number its rows.
# q101 is no gap either: upstream's text (`LEFT OUTER JOIN` of auction
# to a derived `MAX(price) ... GROUP BY auction`, aliases without AS)
# runs from the benchmark's configuration file in
# tests/test_nexmark_q101.py, against benchmark/reference/
# nexmark_q101.py, NULL rows included; test_nexmark_q101_auction_max_bid
# above keeps the INNER form.
#   q6          per-seller average of the last 10 closing prices:
#               q9's winners feeding AVG(...) OVER (PARTITION BY seller
#               ORDER BY date_time ROWS BETWEEN 10 PRECEDING AND CURRENT
#               ROW); needs a window frame, and a window over a
#               retracting input below an aggregate in one MV
#   q21         needs regexp_extract (split_part-only form runs as
#               part of q22's coverage)
#   q12         processing-time tumble (proctime())
#   q13         side-input (bounded table) join
#   (q19 runs above: rn <= 10 over the q18-style window)
#   q102/q104   scalar subquery over a grouped aggregate (avg of
#               counts) in WHERE/HAVING


def test_tpch_q10_returned_item_revenue():
    """q10 shape: revenue per customer over returned items — 3-way
    join + group + order/limit (e2e_test/streaming/tpch/q10)."""
    rows = _run(
        "CREATE MATERIALIZED VIEW q10 AS SELECT c.c_custkey, "
        "c.c_name, sum(l.l_extendedprice * (1.0 - l.l_discount)) "
        "AS revenue FROM customer AS c "
        "JOIN orders AS o ON c.c_custkey = o.o_custkey "
        "JOIN lineitem AS l ON o.o_orderkey = l.l_orderkey "
        "WHERE l.l_returnflag = 'R' "
        "GROUP BY c.c_custkey, c.c_name "
        "ORDER BY revenue DESC LIMIT 20",
        "SELECT * FROM q10 ORDER BY revenue DESC",
        sources=TPCH_SOURCES, steps=16)
    import decimal
    from risingwave_tpu.connectors.tpch import (
        TpchConfig, gen_customer, gen_orders,
    )
    cfg = TpchConfig(customers=TPCH_CUSTOMERS, orders=TPCH_ORDERS)
    cust = gen_customer(np.arange(TPCH_CUSTOMERS, dtype=np.int64), cfg)
    orders = gen_orders(np.arange(TPCH_ORDERS, dtype=np.int64), cfg)
    li = _tpch_lineitem()
    order_cust = dict(zip(orders["o_orderkey"].tolist(),
                          orders["o_custkey"].tolist()))
    rev = collections.defaultdict(decimal.Decimal)
    for ok, ep, d, rf in zip(li["l_orderkey"].tolist(),
                             li["l_extendedprice"].tolist(),
                             li["l_discount"].tolist(),
                             li["l_returnflag"]):
        if rf == "R":
            rev[order_cust[ok]] += (
                decimal.Decimal(ep).scaleb(-4)
                * (1 - decimal.Decimal(d).scaleb(-4)))
    names = dict(zip(cust["c_custkey"].tolist(),
                     cust["c_name"].tolist()))
    top = sorted(rev.items(), key=lambda kv: -kv[1])[:20]
    assert len(rows) == 20
    got_revs = [decimal.Decimal(r[2]) for r in rows]
    exp_revs = [v.quantize(decimal.Decimal(10) ** -8)
                for _k, v in top]
    assert sorted(got_revs, reverse=True) == sorted(
        (decimal.Decimal(x) for x in got_revs), reverse=True)
    for (ck, nm, rv) in rows:
        assert names[ck] == nm
        assert decimal.Decimal(rv) == rev[ck].quantize(
            decimal.Decimal(rv).as_tuple().exponent
            and decimal.Decimal(10)
            ** decimal.Decimal(rv).as_tuple().exponent
            or decimal.Decimal(1)), (ck, rv, rev[ck])


def test_tpch_q18_large_volume_orders():
    """q18 shape: orders whose total quantity exceeds a threshold,
    via a HAVING derived table joined back (the IN-subquery rewrite;
    e2e_test/streaming/tpch/q18)."""
    rows = _run(
        "CREATE MATERIALIZED VIEW q18 AS SELECT o.o_orderkey, "
        "o.o_orderdate, b.total FROM orders AS o JOIN ("
        "  SELECT l_orderkey, sum(l_quantity) AS total FROM lineitem "
        "  GROUP BY l_orderkey HAVING sum(l_quantity) > 140"
        ") AS b ON o.o_orderkey = b.l_orderkey",
        "SELECT * FROM q18", sources=TPCH_SOURCES, steps=16)
    from risingwave_tpu.connectors.tpch import TpchConfig, gen_orders
    cfg = TpchConfig(customers=TPCH_CUSTOMERS, orders=TPCH_ORDERS)
    orders = gen_orders(np.arange(TPCH_ORDERS, dtype=np.int64), cfg)
    li = _tpch_lineitem()
    total = collections.Counter()
    for ok, q in zip(li["l_orderkey"].tolist(),
                     li["l_quantity"].tolist()):
        total[ok] += q
    odate = dict(zip(orders["o_orderkey"].tolist(),
                     orders["o_orderdate"].tolist()))
    expect = {(ok, odate[ok], t) for ok, t in total.items() if t > 140}
    assert set(map(tuple, rows)) == expect
    assert len(rows) > 0


def test_nexmark_q101_small_epochs_no_stale_rows():
    """q101 with MANY small epochs: per-epoch MAX updates retract
    through the join by the derived table's pk — a fresh-row-id wrap
    would leave stale max rows (regression: derived-table pk
    stamping)."""
    async def run():
        fe = Frontend(min_chunks=2, rate_limit=2)
        for t in ("bid", "auction"):
            await fe.execute(
                f"CREATE SOURCE {t} WITH (connector='nexmark', "
                f"nexmark.table.type='{t}', nexmark.event.num={N_EVENTS}, "
                f"nexmark.max.chunk.size=64, "
                f"nexmark.min.event.gap.in.ns={GAP_NS})")
        await fe.execute(
            "CREATE MATERIALIZED VIEW q101s AS SELECT a.id, b.m "
            "FROM auction AS a JOIN ("
            "  SELECT auction, MAX(price) AS m FROM bid "
            "  GROUP BY auction) AS b ON a.id = b.auction")
        await fe.step(40)
        rows = await fe.execute("SELECT * FROM q101s")
        await fe.close()
        return rows

    rows = asyncio.run(run())
    bids, aucs, _p = _gen()
    mx = {}
    for a, p in zip(bids["auction"].tolist(), bids["price"].tolist()):
        mx[a] = max(mx.get(a, 0), p)
    ids = set(aucs["id"].tolist())
    expect = {(a, m) for a, m in mx.items() if a in ids}
    assert set(map(tuple, rows)) == expect


def test_nexmark_q10_formatted_log():
    rows = _run(
        "CREATE MATERIALIZED VIEW q10 AS SELECT auction, bidder, "
        "price, date_time, to_char(date_time, 'YYYY-MM-DD') AS dt, "
        "to_char(date_time, 'HH24:MI') AS dm FROM bid",
        "SELECT * FROM q10")
    import datetime
    bids, _a, _p = _gen()
    epoch = datetime.datetime(1970, 1, 1,
                              tzinfo=datetime.timezone.utc)

    def fmt(us, f):
        return (epoch + datetime.timedelta(
            microseconds=int(us))).strftime(f)
    expect = collections.Counter(
        (a, b, p, t, fmt(t, "%Y-%m-%d"), fmt(t, "%H:%M"))
        for a, b, p, t in zip(
            bids["auction"].tolist(), bids["bidder"].tolist(),
            bids["price"].tolist(), bids["date_time"].tolist()))
    assert collections.Counter(map(tuple, rows)) == expect
    assert len(rows) > 0


def test_nexmark_q14_calculated_fields():
    rows = _run(
        "CREATE MATERIALIZED VIEW q14 AS SELECT auction, bidder, "
        "0.908 * price AS price, CASE "
        "WHEN date_part('hour', date_time) >= 8 AND "
        "date_part('hour', date_time) <= 18 THEN 'dayTime' "
        "WHEN date_part('hour', date_time) <= 6 OR "
        "date_part('hour', date_time) >= 20 THEN 'nightTime' "
        "ELSE 'otherTime' END AS bid_time_type, date_time "
        "FROM bid WHERE 0.908 * price > 1000000",
        "SELECT auction, bidder, price, bid_time_type FROM q14")
    import decimal
    bids, _a, _p = _gen()
    rate = decimal.Decimal("0.908")

    def btype(us):
        h = (int(us) // 3_600_000_000) % 24
        if 8 <= h <= 18:
            return "dayTime"
        if h <= 6 or h >= 20:
            return "nightTime"
        return "otherTime"
    expect = collections.Counter()
    for a, b, p, t in zip(bids["auction"].tolist(),
                          bids["bidder"].tolist(),
                          bids["price"].tolist(),
                          bids["date_time"].tolist()):
        adj = (rate * p).quantize(decimal.Decimal("0.0001"))
        if adj > 1_000_000:
            expect[(a, b, adj, btype(t))] += 1
    got = collections.Counter(
        (a, b, decimal.Decimal(p), bt) for a, b, p, bt in rows)
    assert got == expect
    assert len(rows) > 0


def test_nexmark_q15_per_minute_stats():
    """q15 shape: per-bucket bid stats with COUNT(DISTINCT ...) over a
    to_char projection of the event time, by the minute and without
    filters. Upstream's q15 itself (by the day, with `FILTER (WHERE
    ...)` on the counts and on the `count(DISTINCT ...)`s) runs from the
    benchmark's configuration file in tests/test_nexmark_q15.py."""
    rows = _run(
        "CREATE MATERIALIZED VIEW q15 AS SELECT "
        "to_char(date_time, 'HH24:MI') AS minute, count(*) AS bids, "
        "count(DISTINCT bidder) AS bidders, "
        "count(DISTINCT auction) AS auctions FROM bid "
        "GROUP BY to_char(date_time, 'HH24:MI')",
        "SELECT * FROM q15")
    import datetime
    bids, _a, _p = _gen()
    epoch = datetime.datetime(1970, 1, 1,
                              tzinfo=datetime.timezone.utc)
    per = {}
    for a, b, t in zip(bids["auction"].tolist(),
                       bids["bidder"].tolist(),
                       bids["date_time"].tolist()):
        m = (epoch + datetime.timedelta(
            microseconds=int(t))).strftime("%H:%M")
        e = per.setdefault(m, [0, set(), set()])
        e[0] += 1
        e[1].add(b)
        e[2].add(a)
    expect = {(m, c, len(bs), len(as_))
              for m, (c, bs, as_) in per.items()}
    assert set(map(tuple, rows)) == expect
    assert len(rows) > 1


def test_nexmark_q22_url_dirs():
    rows = _run(
        "CREATE MATERIALIZED VIEW q22 AS SELECT auction, bidder, "
        "price, channel, split_part(url, '/', 4) AS dir1, "
        "split_part(url, '/', 5) AS dir2, "
        "split_part(url, '/', 6) AS dir3 FROM bid",
        "SELECT * FROM q22")
    bids, _a, _p = _gen()

    def part(u, k):
        parts = u.split("/")
        return parts[k - 1] if 1 <= k <= len(parts) else ""
    expect = collections.Counter(
        (a, b, p, ch, part(u, 4), part(u, 5), part(u, 6))
        for a, b, p, ch, u in zip(
            bids["auction"].tolist(), bids["bidder"].tolist(),
            bids["price"].tolist(), bids["channel"].tolist(),
            bids["url"].tolist()))
    assert collections.Counter(map(tuple, rows)) == expect
    assert len(rows) > 0


def test_nexmark_q16_filtered_aggregates():
    """q16 shape: per-channel stats with FILTER (WHERE ...) aggregate
    clauses (rank buckets), rewritten to CASE at bind time."""
    rows = _run(
        "CREATE MATERIALIZED VIEW q16 AS SELECT channel, "
        "count(*) AS total, "
        "count(*) FILTER (WHERE price < 10000) AS rank1, "
        "count(*) FILTER (WHERE price >= 10000 AND price < 1000000) "
        "AS rank2, "
        "count(*) FILTER (WHERE price >= 1000000) AS rank3, "
        "max(price) FILTER (WHERE price < 10000) AS max1, "
        "avg(price) FILTER (WHERE price < 150) AS avg_tiny "
        "FROM bid GROUP BY channel",
        "SELECT * FROM q16")
    bids, _a, _p = _gen()
    per = {}
    for ch, p in zip(bids["channel"].tolist(), bids["price"].tolist()):
        e = per.setdefault(ch, [0, 0, 0, 0, None, []])
        e[0] += 1
        if p < 10_000:
            e[1] += 1
            e[4] = p if e[4] is None else max(e[4], p)
        elif p < 1_000_000:
            e[2] += 1
        else:
            e[3] += 1
        if p < 150:
            e[5].append(p)
    got = {r[:6] for r in map(tuple, rows)}
    expect = {(ch, t, r1, r2, r3, m)
              for ch, (t, r1, r2, r3, m, _tiny) in per.items()}
    assert got == expect
    # avg FILTER: empty-match buckets must be NULL, not NaN/0
    import decimal
    for r in map(tuple, rows):
        tiny = per[r[0]][5]
        if not tiny:
            assert r[6] is None, r
        else:
            want = (decimal.Decimal(sum(tiny)) / len(tiny))
            assert abs(decimal.Decimal(r[6]) - want) < \
                decimal.Decimal("0.01"), (r, want)
    assert len(rows) > 2


def test_nexmark_q17_auction_day_stats():
    """q17: per-(auction, day) bid statistics — rank-bucket FILTER
    counts plus min/max/avg/sum."""
    rows = _run(
        "CREATE MATERIALIZED VIEW q17 AS SELECT auction, "
        "to_char(date_time, 'YYYY-MM-DD') AS day, count(*) AS total, "
        "count(*) FILTER (WHERE price < 10000) AS r1, "
        "count(*) FILTER (WHERE price >= 10000 AND price < 1000000) "
        "AS r2, count(*) FILTER (WHERE price >= 1000000) AS r3, "
        "min(price) AS mn, max(price) AS mx, sum(price) AS sm "
        "FROM bid GROUP BY auction, to_char(date_time, 'YYYY-MM-DD')",
        "SELECT * FROM q17")
    import datetime
    bids, _a, _p = _gen()
    epoch = datetime.datetime(1970, 1, 1,
                              tzinfo=datetime.timezone.utc)
    per = {}
    for a, p, t in zip(bids["auction"].tolist(),
                       bids["price"].tolist(),
                       bids["date_time"].tolist()):
        day = (epoch + datetime.timedelta(
            microseconds=int(t))).strftime("%Y-%m-%d")
        e = per.setdefault((a, day), [0, 0, 0, 0, None, None, 0])
        e[0] += 1
        if p < 10_000:
            e[1] += 1
        elif p < 1_000_000:
            e[2] += 1
        else:
            e[3] += 1
        e[4] = p if e[4] is None else min(e[4], p)
        e[5] = p if e[5] is None else max(e[5], p)
        e[6] += p
    expect = {(a, d, t, r1, r2, r3, mn, mx, sm)
              for (a, d), (t, r1, r2, r3, mn, mx, sm) in per.items()}
    assert set(map(tuple, rows)) == expect
    assert len(rows) > 5


def test_nexmark_q18_last_bid_per_bidder_auction():
    """q18: each (bidder, auction)'s most recent bid via
    ROW_NUMBER() = 1 over a derived table."""
    mv = ("CREATE MATERIALIZED VIEW q18 AS SELECT auction, bidder, "
          "price, date_time FROM (SELECT auction, bidder, price, "
          "date_time, row_number() OVER (PARTITION BY bidder, auction "
          "ORDER BY date_time DESC) AS rn FROM bid) AS t WHERE rn = 1")
    rows = _run(mv, "SELECT * FROM q18")
    plan = _explain(mv)
    assert "GroupTopNExecutor  -- group: [bidder, auction], order: " \
        "[date_time DESC], limit: 1, append_only: true" in plan
    assert "OverWindow" not in plan
    bids, _a, _p = _gen()
    last = {}
    for a, b, p, t in zip(bids["auction"].tolist(),
                          bids["bidder"].tolist(),
                          bids["price"].tolist(),
                          bids["date_time"].tolist()):
        cur = last.get((b, a))
        if cur is None or t > cur[3]:
            last[(b, a)] = (a, b, p, t)
    assert len(rows) == len(last)
    src = {(a, b, p, t) for a, b, p, t in zip(
        bids["auction"].tolist(), bids["bidder"].tolist(),
        bids["price"].tolist(), bids["date_time"].tolist())}
    for a, b, p, t in rows:
        assert last[(b, a)][3] == t, (a, b, t)
        # the whole ROW must be a real source bid (not just the time)
        assert (a, b, p, t) in src, (a, b, p, t)
    assert len(rows) > 10


def test_nexmark_q19_top10_bids_per_auction():
    """q19: the 10 highest bids per auction via ROW_NUMBER() <= 10
    over a derived table (per-group LIMIT)."""
    mv = ("CREATE MATERIALIZED VIEW q19 AS SELECT auction, bidder, "
          "price FROM (SELECT auction, bidder, price, row_number() "
          "OVER (PARTITION BY auction ORDER BY price DESC) AS rn "
          "FROM bid) AS t WHERE rn <= 10")
    rows = _run(mv, "SELECT * FROM q19")
    plan = _explain(mv)
    assert "GroupTopNExecutor  -- group: [auction], order: [price " \
        "DESC], limit: 10, append_only: true" in plan
    assert "OverWindow" not in plan
    bids, _a, _p = _gen()
    by_auction = collections.defaultdict(list)
    for a, b, p in zip(bids["auction"].tolist(),
                       bids["bidder"].tolist(),
                       bids["price"].tolist()):
        by_auction[a].append(p)
    # the returned price MULTISET per auction must equal the exact
    # top-10 multiset (counts + thresholds alone would accept a
    # duplicated rank-1 row)
    got_prices = collections.defaultdict(list)
    for a, _b, p in rows:
        got_prices[a].append(p)
    assert set(got_prices) == set(by_auction)
    for a, prices in by_auction.items():
        top = sorted(prices, reverse=True)[:10]
        assert sorted(got_prices[a], reverse=True) == top, a
    assert len(rows) > 20
