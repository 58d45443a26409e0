"""NEXmark q9 as upstream writes it (ISSUE 45): the text of the
benchmark's `nexmark-q9` configuration, read from the file, through a
SQL session, compared exactly with the benchmark's plain reference
(`benchmark/reference/nexmark_q9.py`) at several barrier cuts; the
over-window-to-top-N rule (`frontend/opt/over_window_to_topn.py`), what
it takes and what it leaves; the append-only top-N's state layout and
books; the same view over tables that update and delete; recovery, the
shipped plan, parallelism 4 and the served path; the parser's `t.*` and
alias-less derived table.

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

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 4500000011
BARRIERS = 10

# (auction chunk rows, bid chunk rows) at one chunk a reader a barrier.
# auctions_ahead: the stream's 3:46, an auction is there before its
#   bids and takes bids over some 1,500 of them, so its winner changes
#   across barriers.
# bids_ahead: the bid reader runs three times ahead: the join is driven
#   from the auction side, whose chunk brings all of an auction's
#   waiting bids at once.
CASES = {"auctions_ahead": (67, 1024), "bids_ahead": (16, 1024)}


def _run():
    for path in (BENCH, os.path.join(BENCH, "reference")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run
    return run


def _bench_module(directory: str, name: str):
    return _run().load_module(directory, name)


def _config(name: str = "nexmark-q9") -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _ddl(config: dict, case: str) -> list:
    auction_rows, bid_rows = CASES[case]
    out = []
    for ddl in config["ddl"]:
        rows = auction_rows if "'auction'" in ddl else bid_rows
        ddl, n = re.subn(r"max\.chunk\.size=\d+",
                         f"max.chunk.size={rows}", ddl)
        assert n == ("CREATE SOURCE" in ddl)
        out.append(ddl.format(seed=SEED))
    return out


def _generator(config: dict):
    return _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **config["generator"])


def _select(ddl: str) -> str:
    return ddl.split(" AS", 1)[1]


def _bare(ex):
    return getattr(ex, "inner", ex)         # under the monitor


def _topn(fe, view: str):
    from risingwave_tpu.stream.executors.top_n import GroupTopNExecutor
    actor = fe.actors[fe.catalog.mvs[view].actor_id]
    found = [_bare(ex) for _p, ex in _run().walk_executors(actor.consumer)
             if isinstance(_bare(ex), GroupTopNExecutor)]
    assert len(found) == 1, found
    return found[0]


def _shape(topn) -> dict:
    return {"group": topn.group_indices, "order": topn.order_by,
            "offset": topn.offset, "limit": topn.limit,
            "append_only": topn.append_only, "pk": topn.pk_indices,
            "state_pk": topn.state.pk_indices,
            "dist_key": topn.state.dist_key_indices,
            "table_id": topn.state.table_id,
            "columns": [f.name for f in topn.schema]}


def _history(rows) -> dict:
    out = {}
    for _seq, epoch, ts, interval_s, name, value, _dom in rows:
        out.setdefault(epoch, {"ts": ts, "interval_s": interval_s})[
            name] = value
    # the first row after HISTORY.clear() takes the registry's
    # process-wide counter totals, other tests' among them, as its delta
    del out[min(out)]
    return out


async def _explain(fe, select: str) -> str:
    text = "\n".join(r[0] for r in await fe.execute("EXPLAIN " + select))
    # less the footer of what this process has compiled so far
    return text.split("-- compiled kernel costs")[0]


async def _checkpoint(fe, view: str) -> dict:
    run = _run()
    topn = _topn(fe, view)
    by_id = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in await fe.execute(
            "SELECT * FROM rw_state_topology"):
        if mv == view:
            by_id[table_id] += n
    return {
        "view": collections.Counter(
            tuple(r) for r in await fe.execute(f"SELECT * FROM {view}")),
        "readers": run.checkpointed_rows(run.source_readers(fe, view)),
        "by_id": dict(by_id),
        # the store's own rows (the topology's books start empty in a
        # recovered process: they count what it wrote)
        "stored": [tuple(row) for _pk, row in topn.state.iter_rows()],
        "cached": sorted(r for rows in topn.groups.values()
                         for _k, r in rows),
    }


async def _drive(config: dict, case: str, store=None, barriers=BARRIERS,
                 recover=False, parallelism=None) -> dict:
    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.state.topology import TOPOLOGY
    from risingwave_tpu.utils.metrics import HISTORY

    HISTORY.clear()
    TOPOLOGY.clear()            # process-wide books of state rows
    more = {} if parallelism is None else {"parallelism": parallelism}
    fe = Frontend(store, rate_limit=1, min_chunks=1, **more)
    try:
        if recover:
            await fe.recover()
        else:
            for ddl in _ddl(config, case):
                await fe.execute(ddl)
        view = config["view"]
        checkpoints = []
        for _ in range(barriers):
            await fe.step()
            checkpoints.append(await _checkpoint(fe, view))
        return {
            "checkpoints": checkpoints,
            "shape": _shape(_topn(fe, view)),
            "history": _history(
                await fe.execute("SELECT * FROM rw_metrics_history")),
            "rewrites": await fe.execute(
                "SELECT job, rule, fired, detail FROM rw_plan_rewrites"),
            "explain": await _explain(fe, _select(config["ddl"][-1])),
        }
    finally:
        await fe.close()


@pytest.fixture(scope="module")
def q9():
    config = _config()
    _bench_module("reference", "nexmark_gen")
    return {"config": config,
            **{case: asyncio.run(_drive(config, case)) for case in CASES}}


def _window_sum(run_: dict, prefix: str, suffix: str = "") -> float:
    return sum(v for h in run_["history"].values() for k, v in h.items()
               if k.startswith(prefix) and k.endswith(suffix))


# -- the view against the reference -----------------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_view_equals_the_reference_at_every_checkpoint(q9, case):
    config = q9["config"]
    gen = _generator(config)
    ref = _bench_module("reference", config["reference"])
    views = set()
    for cp in q9[case]["checkpoints"]:
        readers = [dict(r) for r in cp["readers"]]
        want = ref.reference(readers, gen)
        assert cp["view"] == want            # all thirteen columns
        assert len(next(iter(want))) == 13
        assert max(cp["by_id"].values()) == ref.resident_rows(readers, gen)
        views.add(frozenset(cp["view"]))
    assert len(views) == BARRIERS            # every cut another view
    assert not [r for r in q9[case]["rewrites"]
                if str(r[3]).startswith("FALLBACK")]


def test_the_winner_of_an_auction_changes_across_barriers(q9):
    """So the top-N emits a delete and an insert, not inserts alone."""
    cps = q9["auctions_ahead"]["checkpoints"]
    changed = 0
    for a, b in zip(cps, cps[1:]):
        was = {r[0]: r for r in a["view"]}
        changed += sum(1 for r in b["view"]
                       if r[0] in was and was[r[0]] != r)
    assert changed > 20


def test_the_reference_breaks_a_price_tie_by_the_earlier_bid():
    """Prices here are drawn from 10^8 values and seldom tie: the rule
    is held on winners computed the slow way over a prefix in which
    the prices are folded to a few values."""
    _run()
    import nexmark_gen
    import nexmark_q9
    cfg = nexmark_gen.GeneratorConfig(seed=SEED)
    real = nexmark_gen.GENERATORS["bid"]

    def folded(k, c):
        out = dict(real(k, c))
        out["price"] = out["price"] % 7 + 1
        return out

    nexmark_gen.GENERATORS["bid"] = folded
    try:
        a, b = nexmark_q9.winners(300, 4000, cfg)
        bids = nexmark_gen.prefix("bid", 4000, cfg)
        aucs = nexmark_gen.prefix("auction", 300, cfg)
    finally:
        nexmark_gen.GENERATORS["bid"] = real
    expires = nexmark_q9.auction_window(300, cfg)["expires"]
    best = {}
    for i, (auc, price, ts) in enumerate(zip(
            bids["auction"].tolist(), bids["price"].tolist(),
            bids["date_time"].tolist())):
        k = auc - nexmark_gen.FIRST_AUCTION_ID
        if not (0 <= k < 300 and aucs["date_time"][k] <= ts <= expires[k]):
            continue
        if k not in best or (-price, ts) < best[k][0]:
            best[k] = ((-price, ts), i)
    assert dict(zip(a.tolist(), b.tolist())) == \
        {k: i for k, (_key, i) in best.items()}
    ties = collections.Counter(
        (auc, price) for auc, price in zip(bids["auction"].tolist(),
                                           bids["price"].tolist()))
    assert max(ties.values()) > 1            # the tie was exercised


# -- the plan ---------------------------------------------------------------

def test_the_text_is_upstreams_and_plans_a_join_and_a_group_top_n(q9):
    config, run_ = q9["config"], q9["auctions_ahead"]
    text = config["ddl"][-1]
    for piece in ("SELECT A.*, B.auction, B.bidder, B.price, "
                  "B.date_time AS bid_date_time,",
                  "ROW_NUMBER() OVER (PARTITION BY A.id ORDER BY B.price "
                  "DESC, B.date_time ASC) AS rownum",
                  "FROM auction A, bid B",
                  "B.date_time BETWEEN A.date_time AND A.expires\n)\n"
                  "WHERE rownum <= 1"):
        assert piece in text, piece
    explain = run_["explain"]
    assert "OverWindow" not in explain
    assert explain.count(
        "GroupTopNExecutor  -- group: [id], order: [price DESC, "
        "bid_date_time ASC], limit: 1, append_only: true") == 2
    assert "HashJoinExecutor(inner" in explain
    # the BETWEEN's two halves are planned as filters above the join
    # and sink into it: the join evaluates them on its pairs, and the
    # top-N stands directly above the join
    pre, post = explain.split("-- rewritten plan")
    assert re.search(r"GroupTopNExecutor.*\n\s+FilterExecutor\n\s+"
                     r"FilterExecutor\n\s+HashJoinExecutor", pre)
    assert re.search(
        r"GroupTopNExecutor.*\n\s+HashJoinExecutor\(inner.*  -- "
        r"condition: \(\(\$13:timestamp >= \$5:timestamp\) and "
        r"\(\$13:timestamp <= \$6:timestamp\)\)\n", post)
    assert "FilterExecutor" not in post
    assert "FusedFragmentExecutor" not in post
    # bid.channel, bid.url and both extra columns are read by nothing:
    # pruning sees through the top-N and the join carries 15 lanes
    assert "rule column_pruning: 4 column lane(s) pruned" in explain
    assert "max_width=15" in explain.split("post-rewrite plan stats")[1]
    shape = run_["shape"]
    names = shape["columns"]
    assert not {"extra", "channel", "url"} & set(names)
    assert [names[i] for i in shape["group"]] == ["id"]
    assert [(names[i], d) for i, d in shape["order"]] == \
        [("price", True), ("date_time", False)]
    assert shape["limit"] == 1 and shape["offset"] == 0
    assert shape["append_only"] is True
    # keyed group | order | the two row ids, distributed by the group
    assert shape["state_pk"][:3] == shape["group"] + \
        [i for i, _d in shape["order"]]
    assert sorted(shape["state_pk"][3:]) == sorted(shape["pk"])
    assert shape["dist_key"] == shape["group"]


# -- the append-only arm's layout and books ---------------------------------

@pytest.mark.parametrize("case", list(CASES))
def test_the_state_table_holds_one_row_an_auction(q9, case):
    run_ = q9[case]
    table = run_["shape"]["table_id"]
    for cp in run_["checkpoints"]:
        # the window's rows and nothing else, in the store and in
        # memory: the winner of every auction that has one
        assert len(cp["stored"]) == sum(cp["view"].values())
        assert sorted(cp["stored"]) == cp["cached"]
        assert len({r[0] for r in cp["stored"]}) == len(cp["stored"])
        assert cp["by_id"][table] == len(cp["stored"])
    label = f"topn.t{table}."
    rows_in = _window_sum(run_, label + "rows_in")
    rows_out = _window_sum(run_, label + "rows_out")
    writes = _window_sum(run_, label + "state_writes")
    deletes = _window_sum(run_, label + "state_deletes")
    # every row of a delta is one row entering or leaving the table
    assert writes + deletes == rows_out
    assert writes - deletes == len(run_["checkpoints"][-1]["stored"])
    assert deletes > 0
    assert rows_out < 0.5 * rows_in          # most bids never enter
    last = run_["history"][max(run_["history"])]
    assert last[label + "groups"] == last[label + "cached_rows"] == \
        len(run_["checkpoints"][-1]["stored"])


def test_the_books_name_the_stages_and_the_readers_read_them(q9):
    run_ = q9["auctions_ahead"]
    names = {k for h in run_["history"].values() for k in h}
    for stage in ("topn.apply", "topn.state", "topn.emit"):
        assert "stage.host_emit." + stage in names
        assert _window_sum(run_, "stage.host_emit." + stage) > 0
    assert "exec_phase.GroupTopNExecutor.host_emit" in names
    record = {"history": run_["history"]}
    share = _bench_module("layer_metrics", "topn_host_share").read(record)
    assert 0 < share < 100
    per_row = _bench_module(
        "layer_metrics", "topn_state_rows_per_row").read(record)
    out_per_row = _bench_module(
        "layer_metrics", "topn_out_rows_per_row").read(record)
    assert per_row == out_per_row
    assert 0.02 < out_per_row < 0.5


def test_readers_of_a_program_without_the_books_read_nothing():
    record = {"history": {1: {"ts": 1.0, "interval_s": 0.5,
                              "stage.host_emit.join.pairs": 0.1},
                          2: {"ts": 1.5, "interval_s": 0.5}}}
    for name in ("topn_host_share", "topn_state_rows_per_row",
                 "topn_out_rows_per_row"):
        assert _bench_module("layer_metrics", name).read(record) is None


# -- the rule: what it takes and what it leaves -----------------------------

INNER = ("SELECT auction, bidder, price, date_time, {calls} FROM bid")
WINDOW = "OVER (PARTITION BY auction ORDER BY price DESC, date_time ASC)"
OUT = "SELECT auction, bidder, price FROM ({inner}) WHERE {where}"


def _case(where, calls="row_number() " + WINDOW + " AS rn", out=OUT):
    return out.format(inner=INNER.format(calls=calls), where=where)


FIRES = {
    "rn <= 3": (_case("rn <= 3"), 3),
    "rn < 3": (_case("rn < 3"), 2),
    "rn = 1": (_case("rn = 1"), 1),
    "3 >= rn": (_case("3 >= rn"), 3),
    "1 = rn": (_case("1 = rn"), 1),
    "2 > rn": (_case("2 > rn"), 1),
    "two bounds": (_case("rn <= 5 AND rn < 3"), 2),
    "an extra conjunct": (_case("rn <= 2 AND price > 100000"), 2),
}
DECLINES = {
    "rank()": (_case("rn <= 2", "rank() " + WINDOW + " AS rn"),
               "rank() numbers ties alike"),
    "dense_rank()": (_case("rn <= 2", "dense_rank() " + WINDOW + " AS rn"),
                     "dense_rank() numbers ties alike"),
    "the rank in the output": (
        _case("rn <= 2", out="SELECT auction, price, rn FROM ({inner}) "
              "WHERE {where}"), "rn is read above the filter"),
    "a star above": (
        _case("rn <= 2", out="SELECT * FROM ({inner}) WHERE {where}"),
        "rn is read above the filter"),
    "rn >= 2": (_case("rn >= 2"), "more than a bound from above"),
    "rn = 2": (_case("rn = 2"), "more than a bound from above"),
    "an expression over the rank": (_case("rn + 1 <= 3"),
                                    "more than a bound from above"),
    "no bound": (_case("price > 100000"), "no conjunct of the WHERE bounds"),
    "two calls": (
        _case("rn <= 2", "row_number() " + WINDOW + " AS rn, sum(price) "
              + WINDOW + " AS total"), "2 window calls share the window"),
    "rn <= 0": (_case("rn <= 0"), "the bound keeps 0 rows"),
}


@pytest.fixture(scope="module")
def matrix():
    """Every case as two views over the same bounded bid stream: as the
    planner plans it, and with the rule switched off (the general
    over-window executor under a filter)."""
    from risingwave_tpu.frontend.opt import over_window_to_topn as rule
    from risingwave_tpu.frontend.session import Frontend

    cases = {**{k: v[0] for k, v in FIRES.items()},
             **{k: v[0] for k, v in DECLINES.items()}}

    async def drive():
        fe = Frontend(min_chunks=4)
        out = {}
        try:
            await fe.execute(
                "CREATE SOURCE bid WITH (connector='nexmark', "
                "nexmark.table.type='bid', nexmark.event.num=3000, "
                "nexmark.max.chunk.size=256, "
                "nexmark.min.event.gap.in.ns=100000000)")
            real = rule.over_window_to_topn
            for i, (name, select) in enumerate(cases.items()):
                out[name] = {"explain": await _explain(fe, select)}
                await fe.execute(
                    f"CREATE MATERIALIZED VIEW planned{i} AS {select}")
                rule.over_window_to_topn = \
                    lambda _p, ex, scope, _sel, conj: (ex, scope, conj)
                try:
                    await fe.execute(
                        f"CREATE MATERIALIZED VIEW general{i} AS {select}")
                    out[name]["general_explain"] = \
                        await _explain(fe, select)
                finally:
                    rule.over_window_to_topn = real
            await fe.step(8)
            for i, name in enumerate(cases):
                for which in ("planned", "general"):
                    out[name][which] = collections.Counter(
                        tuple(r) for r in await fe.execute(
                            f"SELECT * FROM {which}{i}"))
            return out
        finally:
            await fe.close()

    return asyncio.run(drive())


@pytest.mark.parametrize("case", list(FIRES))
def test_the_rule_fires(matrix, case):
    got, limit = matrix[case], FIRES[case][1]
    assert "OverWindow" not in got["explain"]
    assert (f"GroupTopNExecutor  -- group: [auction], order: [price DESC, "
            f"date_time ASC], limit: {limit}, append_only: true") \
        in got["explain"]
    assert "OverWindowExecutor" in got["general_explain"]
    assert got["planned"] == got["general"]
    assert sum(got["planned"].values()) > 20


@pytest.mark.parametrize("case", list(DECLINES))
def test_the_rule_declines_and_says_why(matrix, case):
    got, why = matrix[case], DECLINES[case][1]
    assert "TopN" not in got["explain"]
    line, = [ln for ln in got["explain"].splitlines()
             if "OverWindowExecutor" in ln][:1]
    assert "-- not planned as a top-N: " in line and why in line
    # the plan is the planner's own: the same tree with the rule off
    strip = re.compile(r"  -- not planned as a top-N.*")
    assert strip.sub("", got["explain"]) == got["general_explain"]
    assert got["planned"] == got["general"]


def test_a_window_in_a_plain_select_is_left_alone():
    """No derived table, no filter on a rank: the rule does not look."""
    from risingwave_tpu.frontend.session import Frontend

    async def drive():
        fe = Frontend()
        try:
            await fe.execute(
                "CREATE SOURCE bid WITH (connector='nexmark', "
                "nexmark.table.type='bid', nexmark.event.num=100)")
            return await _explain(fe, INNER.format(
                calls="row_number() " + WINDOW + " AS rn"))
        finally:
            await fe.close()

    explain = asyncio.run(drive())
    assert "OverWindowExecutor" in explain
    assert "TopN" not in explain and "not planned" not in explain


def test_a_window_without_order_by_never_reaches_the_rule():
    """`row_number() OVER (PARTITION BY g)` would give the rule an order
    that is the pk alone: the binder refuses the window first, with the
    rule on or off, so neither executor numbers such a partition."""
    from risingwave_tpu.frontend.binder import BindError
    from risingwave_tpu.frontend.session import Frontend

    async def drive():
        fe = Frontend()
        try:
            await fe.execute(
                "CREATE SOURCE bid WITH (connector='nexmark', "
                "nexmark.table.type='bid', nexmark.event.num=100)")
            await fe.execute("CREATE MATERIALIZED VIEW v AS " + _case(
                "rn <= 2", "row_number() OVER (PARTITION BY auction) "
                "AS rn"))
        finally:
            await fe.close()

    with pytest.raises(BindError, match="need ORDER BY"):
        asyncio.run(drive())


# -- the retractable arm, through SQL ---------------------------------------

def _winners(auctions, bids):
    out = collections.Counter()
    for a in auctions:
        inside = [b for b in bids
                  if b[0] == a[0] and a[5] <= b[5] <= a[6]]
        if inside:
            b = min(inside, key=lambda b: (-b[2], b[5]))
            out[a[:9] + (b[0], b[1], b[2], b[5])] += 1
    return out


def test_q9_over_tables_that_update_and_delete():
    """The configuration's view over tables instead of the sources: the
    top-N is planned retractable and keeps every joined row, a winner
    that is deleted gives way to the runner-up, a raised price takes
    the lead, a deleted auction takes its row out."""
    import numpy as np
    from risingwave_tpu.frontend.session import Frontend
    view = _config()["ddl"][-1]
    assert view.count("FROM auction A, bid B") == 1
    view = view.replace("FROM auction A, bid B", "FROM auc A, offer B")
    rng = np.random.default_rng(45)
    day = 1_436_918_400_000_000

    def ts(us):
        return "2015-07-15 00:00:%02d.%06d" % divmod(us, 1_000_000)

    async def drive():
        fe = Frontend()
        seen = []
        try:
            await fe.execute(
                "CREATE TABLE auc (id BIGINT PRIMARY KEY, item_name VARCHAR, "
                "description VARCHAR, initial_bid BIGINT, reserve BIGINT, "
                "date_time TIMESTAMP, expires TIMESTAMP, seller BIGINT, "
                "category BIGINT, extra VARCHAR)")
            await fe.execute(
                "CREATE TABLE offer (auction BIGINT, bidder BIGINT, "
                "price BIGINT, channel VARCHAR, url VARCHAR, "
                "date_time TIMESTAMP, extra VARCHAR, n BIGINT PRIMARY KEY)")
            explain = await _explain(fe, _select(view))
            await fe.execute(view)
            topn = _topn(fe, "q9")
            auctions, bids, n = [], [], 0
            for step in range(12):
                if step < 5:
                    a = (step + 1, f"item{step}", f"Nice item{step}",
                         10 * step, 20 * step, day + step * 1000,
                         day + step * 1000 + 40_000, 7, 10 + step % 3)
                    auctions.append(a)
                    await fe.execute(
                        "INSERT INTO auc VALUES (%d, '%s', '%s', %d, %d, "
                        "'%s', '%s', %d, %d, 'x')" % (
                            a[:5] + (ts(a[5] - day), ts(a[6] - day))
                            + a[7:]))
                new = []
                for _ in range(6):
                    n += 1
                    a = int(rng.integers(1, 7))     # 6: no such auction
                    # some fall outside their auction's 40 ms
                    us = int(rng.integers(0, 60_000)) + (a - 1) * 1000
                    new.append((a, int(rng.integers(1, 4)),
                                int(rng.integers(1, 6)) * 100, None, None,
                                day + us, n))
                await fe.execute("INSERT INTO offer VALUES " + ", ".join(
                    "(%d, %d, %d, 'c', 'u', '%s', 'e', %d)" % (
                        b[0], b[1], b[2], ts(b[5] - day), b[6])
                    for b in new))
                bids += new
                if step % 3 == 1:       # the winner of an auction goes
                    a = int(rng.integers(1, 6))
                    won = [w for w in _winners(auctions, bids)
                           if w[0] == a]
                    if won:
                        gone = [b for b in bids if b[0] == a
                                and b[2] == won[0][11]
                                and b[5] == won[0][12]]
                        await fe.execute(
                            f"DELETE FROM offer WHERE n = {gone[0][6]}")
                        bids = [b for b in bids if b[6] != gone[0][6]]
                if step % 3 == 2:       # a bid is raised past the rest
                    b = bids[int(rng.integers(0, len(bids)))]
                    await fe.execute(
                        f"UPDATE offer SET price = 900 WHERE n = {b[6]}")
                    bids = [x[:2] + (900,) + x[3:] if x[6] == b[6] else x
                            for x in bids]
                if step == 9:           # an auction goes, with its row
                    await fe.execute("DELETE FROM auc WHERE id = 2")
                    auctions = [a for a in auctions if a[0] != 2]
                await fe.execute("FLUSH")
                got = collections.Counter(
                    tuple(r) for r in await fe.execute("SELECT * FROM q9"))
                assert got == _winners(auctions, bids), f"step {step}"
                # every joined row is kept: a runner-up can come back
                joined = sum(1 for b in bids for a in auctions
                             if b[0] == a[0] and a[5] <= b[5] <= a[6])
                assert sum(1 for _ in topn.state.iter_rows()) == joined
                seen.append((sum(got.values()), joined))
            return explain, topn.append_only, seen
        finally:
            await fe.close()

    explain, append_only, seen = asyncio.run(drive())
    assert append_only is False
    assert "limit: 1, append_only: false" in explain
    assert "OverWindow" not in explain
    assert seen[-1][0] == 4 and max(j for _v, j in seen) > 30


# -- recovery, the shipped plan, parallelism 4, the served path --------------

def test_recovery_after_a_checkpoint_reads_back_equal(q9):
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import MemObjectStore
    config = q9["config"]
    obj = MemObjectStore()
    head = asyncio.run(_drive(config, "auctions_ahead", HummockLite(obj),
                              barriers=4))
    tail = asyncio.run(_drive(config, "auctions_ahead", HummockLite(obj),
                              barriers=BARRIERS - 5, recover=True))
    whole = q9["auctions_ahead"]
    # the recovered plan is the same plan
    assert tail["shape"] == head["shape"] == whole["shape"]
    by_rows = {cp["readers"][1]["rows"]: cp
               for cp in whole["checkpoints"]}
    ids = [i for i, name in enumerate(whole["shape"]["columns"])
           if name == "_row_id"]
    assert len(ids) == 2

    def less_ids(rows):
        return sorted(tuple(v for i, v in enumerate(r) if i not in ids)
                      for r in rows)

    matched = 0
    for got in tail["checkpoints"]:
        want = by_rows.get(got["readers"][1]["rows"])
        if want is None or want["readers"] != got["readers"]:
            continue
        matched += 1
        assert got["view"] == want["view"]
        # the table and the cache hold the winners (a recovered
        # process numbers its rows anew: less the two row ids)
        assert less_ids(got["stored"]) == less_ids(want["stored"])
        assert less_ids(got["cached"]) == less_ids(want["cached"])
        assert len(got["stored"]) == sum(got["view"].values())
    assert matched >= 3
    # and where the cuts differ the view still equals the reference
    gen = _generator(config)
    ref = _bench_module("reference", config["reference"])
    last = tail["checkpoints"][-1]
    assert last["view"] == ref.reference(
        [dict(r) for r in last["readers"]], gen)


def test_the_fragmenter_ships_the_rule_s_top_n_and_hashes_it_by_group():
    """The IR node carries group, append_only, the output pk and the
    state table's own key and distribution, and a fragment rebuilt
    from it holds the same top-N; at parallelism 4 the top-N's
    fragment is hashed by the group key, as the over-window's is by
    its partition: never a silent single shard."""
    from risingwave_tpu.frontend.catalog import Catalog
    from risingwave_tpu.frontend.fragmenter import Fragmenter
    from risingwave_tpu.frontend.parser import parse_many
    from risingwave_tpu.frontend.planner import (
        StreamPlanner, source_schema,
    )
    from risingwave_tpu.state.store import MemoryStateStore
    from risingwave_tpu.stream.actor import LocalBarrierManager
    from risingwave_tpu.stream.exchange import channel_for_test
    from risingwave_tpu.stream.executors.top_n import GroupTopNExecutor
    from risingwave_tpu.stream.plan_ir import build_fragment

    catalog = Catalog()
    for t in ("auction", "bid"):
        opts = {"connector": "nexmark", "nexmark.table.type": t}
        catalog.add_source(t, source_schema(opts, None), opts)
    [(_text, stmt)] = parse_many(_config()["ddl"][-1])
    planner = StreamPlanner(catalog, MemoryStateStore(),
                            LocalBarrierManager(), definition="")
    plan = planner.plan("q9", stmt.select, 7, rate_limit=4)
    planned, = [ex for _p, ex in _run().walk_executors(plan.consumer)
                if isinstance(ex, GroupTopNExecutor)]
    shape = _shape(planned)
    for p in (1, 4):
        graph = Fragmenter(p).lower(plan.consumer)
        frag, node = next((f, n) for f in graph.fragments for n in f.nodes
                          if n["op"] == "top_n")
        assert node["group"] == shape["group"]
        assert node["append_only"] is True and node["limit"] == 1
        assert node["pk"] == shape["pk"]
        assert node["state_pk"] == shape["state_pk"]
        assert node["dist_key"] == shape["dist_key"] == shape["group"]
        if p == 4:
            assert frag.parallelism == 4
            assert [i.keys for i in frag.inputs] == [shape["group"]]
            assert [i.mode for i in frag.inputs] == ["hash"]
            # the scheduler's part: the exchange's placeholder becomes
            # a remote input (never connected here)
            nodes = json.loads(json.dumps(frag.nodes))
            nodes[frag.inputs[0].node_idx] = {
                "op": "remote_input", "host": "127.0.0.1", "port": 1,
                "up_actor": 1, "schema": frag.inputs[0].schema}
            _src, consumer = build_fragment(
                nodes, MemoryStateStore(), LocalBarrierManager(),
                channel_for_test, actor_id=9)
            rebuilt, = [ex for _p, ex in _run().walk_executors(consumer)
                        if isinstance(ex, GroupTopNExecutor)]
            assert _shape(rebuilt) == shape


def test_at_parallelism_4_the_view_is_exact():
    """On the CPU mesh the join is sharded and the top-N, a host
    executor, sees every row: equal to the reference."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    config = _config()
    _bench_module("reference", "nexmark_gen")
    ref = _bench_module("reference", config["reference"])
    run_ = asyncio.run(_drive(config, "auctions_ahead", barriers=6,
                              parallelism=4))
    cp = run_["checkpoints"][-1]
    assert cp["view"] == ref.reference(
        [dict(r) for r in cp["readers"]], _generator(config))
    assert sum(cp["view"].values()) > 300
    assert run_["shape"]["append_only"] is True


def test_through_serving_and_pgwire_with_the_session_s_defaults(tmp_path):
    """The DDL as the file has it, over pgwire to the served process, no
    SET: it parses, binds, plans a grouped top-N and stays exact."""
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
            explain = "\n".join(r[0] for r in await pg.query(
                "EXPLAIN " + _select(config["ddl"][-1])))
            topology = await pg.query("SELECT * FROM rw_state_topology")
            table = _topn(fe, config["view"]).state.table_id
        await fe.close()
        return got, readers, rewrites, explain, topology, table

    got, readers, rewrites, explain, topology, table = asyncio.run(drive())
    config_gen = _bench_module("reference", "nexmark_gen").GeneratorConfig(
        seed=SEED, **config["generator"])
    assert readers[0]["rows"] > 0
    assert got == ref.reference([dict(r) for r in readers], config_gen)
    assert not [r for r in rewrites if str(r[3]).startswith("FALLBACK")]
    assert "limit: 1, append_only: true" in explain
    assert "OverWindow" not in explain
    # rw_state_topology lists the top-N's table: one row an auction
    assert sum(n for t, mv, _v, n, _b in topology
               if t == table and mv == "q9") == sum(got.values())


# -- the seven older configurations ------------------------------------------

@pytest.mark.parametrize("name", [
    "nexmark-q7", "nexmark-q8", "nexmark-q8-mesh4", "nexmark-q4",
    "nexmark-q5", "nexmark-q5-wm", "nexmark-q15"])
def test_no_older_configuration_plans_a_top_n_or_an_over_window(name):
    """None of their views holds a window function, so the rule cannot
    have moved a cell that is there."""
    from risingwave_tpu.frontend.session import Frontend
    config = _config(name)

    async def drive():
        fe = Frontend()
        try:
            for ddl in config["ddl"][:-1]:
                await fe.execute(ddl.format(seed=SEED))
            return await _explain(fe, _select(config["ddl"][-1]))
        finally:
            await fe.close()

    explain = asyncio.run(drive())
    assert "MaterializeExecutor" in explain
    assert "TopN" not in explain and "OverWindow" not in explain
    assert "not planned as a top-N" not in explain


# -- the parser --------------------------------------------------------------

def test_parser_qualified_star_beside_other_items():
    from risingwave_tpu.frontend import ast
    from risingwave_tpu.frontend.parser import parse
    sel = parse("SELECT A.*, B.price, B.date_time AS t, 2 * B.price "
                "FROM auction A, bid B WHERE A.id = B.auction")
    items = sel.projections
    assert items[0] == (ast.ColRef("*", table="a"), None)
    assert items[1] == (ast.ColRef("price", table="b"), None)
    assert items[2] == (ast.ColRef("date_time", table="b"), "t")
    assert isinstance(items[3][0], ast.Bin) and items[3][0].op == "*"
    assert parse("SELECT * FROM bid").projections == \
        [(ast.ColRef("*"), None)]


def test_parser_derived_table_without_an_alias():
    from risingwave_tpu.frontend import ast
    from risingwave_tpu.frontend.parser import parse
    sel = parse("SELECT price FROM (SELECT price FROM bid) WHERE price > 1")
    assert isinstance(sel.from_item, ast.Subquery)
    assert sel.from_item.alias is None and sel.where is not None
    for text, alias in (("(SELECT price FROM bid) AS t", "t"),
                        ("(SELECT price FROM bid) t", "t")):
        assert parse("SELECT price FROM " + text).from_item.alias == alias
    joined = parse("SELECT a.price FROM (SELECT price FROM bid) "
                   "JOIN bid a ON a.price = price")
    assert joined.from_item.alias is None and len(joined.joins) == 1


def test_qualified_star_binds_to_one_from_item():
    from risingwave_tpu.frontend.planner import PlanError
    from risingwave_tpu.frontend.session import Frontend

    async def drive():
        fe = Frontend(min_chunks=2)
        try:
            for t in ("auction", "bid"):
                await fe.execute(
                    f"CREATE SOURCE {t} WITH (connector='nexmark', "
                    f"nexmark.table.type='{t}', nexmark.event.num=2000)")
            await fe.execute(
                "CREATE MATERIALIZED VIEW v AS SELECT B.*, A.seller "
                "FROM auction A, bid B WHERE A.id = B.auction")
            await fe.step(4)
            cols = [f.name for f in fe.catalog.mvs["v"].visible_schema]
            rows = await fe.execute("SELECT * FROM v")
            with pytest.raises(PlanError, match="no FROM item 'c'"):
                await fe.execute(
                    "CREATE MATERIALIZED VIEW w AS SELECT C.* "
                    "FROM auction A, bid B WHERE A.id = B.auction")
            return cols, rows
        finally:
            await fe.close()

    cols, rows = asyncio.run(drive())
    assert cols == ["auction", "bidder", "price", "channel", "url",
                    "date_time", "extra", "seller"]
    assert len(rows) > 100
