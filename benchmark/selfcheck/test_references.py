"""The plain references against brute-force loops at a tiny size; q7 with
two unequal prefixes, as a checkpoint may leave its two readers."""

import collections

import pytest

import nexmark_gen
import nexmark_q7
import nexmark_q8

# a short event gap puts several 10 s windows into a few thousand rows
CFG = nexmark_gen.GeneratorConfig(seed=4_000_000_007,
                                  min_event_gap_in_ns=5_000_000)
W = nexmark_q7.WINDOW_US


def brute_q7(n_join: int, n_agg: int) -> collections.Counter:
    bids = nexmark_gen.prefix("bid", max(n_join, n_agg), CFG)
    rows = list(zip(*(bids[c].tolist() for c in
                      ("auction", "price", "bidder", "date_time"))))
    best = {}
    for _a, price, _b, ts in rows[:n_agg]:
        w = ts // W * W
        best[w] = max(best.get(w, price), price)
    out = collections.Counter()
    for a, price, b, ts in rows[:n_join]:
        if best.get(ts // W * W) == price:
            out[(a, price, b, ts)] += 1
    return out


@pytest.mark.parametrize("n_join,n_agg", [
    (6000, 6000), (6000, 4500), (4500, 6000), (4096, 8192), (1, 1),
    (0, 100), (100, 0)])
def test_q7_prefixes(n_join, n_agg):
    got = nexmark_q7.reference(
        [{"table": "bid", "side": "left", "rows": n_join},
         {"table": "bid", "side": "right", "rows": n_agg}], CFG)
    assert got == brute_q7(n_join, n_agg)
    if n_join >= 4096 and n_agg >= 4096:
        assert len(got) >= 3          # several windows, not a trivial case


def test_q7_unequal_prefixes_differ_from_equal_ones():
    def ref(j, a):
        return nexmark_q7.reference(
            [{"table": "bid", "side": "left", "rows": j},
             {"table": "bid", "side": "right", "rows": a}], CFG)
    assert ref(6000, 4500) != ref(6000, 6000)
    assert ref(4500, 6000) != ref(6000, 6000)


def test_q7_refuses_other_readers():
    with pytest.raises(ValueError):
        nexmark_q7.reference([{"table": "bid", "side": "left",
                               "rows": 10}], CFG)


def brute_q8(n_person: int, n_auction: int) -> collections.Counter:
    pers = nexmark_gen.prefix("person", n_person, CFG)
    aucs = nexmark_gen.prefix("auction", n_auction, CFG)
    out = collections.Counter()
    for pid, name, pts in zip(pers["id"].tolist(), pers["name"].tolist(),
                              pers["date_time"].tolist()):
        for seller, ats in zip(aucs["seller"].tolist(),
                               aucs["date_time"].tolist()):
            if seller == pid and ats // W * W == pts // W * W:
                out[(pid, name, pts // W * W)] = 1     # deduplicated
    return out


@pytest.mark.parametrize("n_person,n_auction", [(300, 900), (900, 300),
                                                (500, 500)])
def test_q8(n_person, n_auction):
    got = nexmark_q8.reference(
        [{"table": "person", "side": "left", "rows": n_person},
         {"table": "auction", "side": "right", "rows": n_auction}], CFG)
    assert got == brute_q8(n_person, n_auction)
    assert sum(got.values()) > 20
