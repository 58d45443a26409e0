"""`nexmark-q9` / `q9_steady` (PR 45): the four auction columns the
reference derives itself against the connector, the reference against a
brute-force loop (a hand-made prefix with a price tie among them), a
rehearsal of the cell coming out correct with the three readers in its
traced line, and `correct` coming out false when it should: whole runs
of `run.py` in this process with `--rehearse` (tiny sizes, the CPU)."""

import collections
import json

import numpy as np
import pytest

import nexmark_gen
import nexmark_q4
import nexmark_q9
import run
from risingwave_tpu.connectors import nexmark as conn

READERS = ("topn_host_share", "topn_state_rows_per_row",
           "topn_out_rows_per_row")


@pytest.mark.parametrize("seed", [7, 2_147_483_659, 4_500_000_031])
def test_derived_auction_columns_match_the_connector(seed):
    """`item_name` and `description` (this file's), `expires` and
    `category` (`nexmark_q4.auction_window`'s, reused by import), over
    the first auctions and a stretch far in; the driver's seeds are
    above 2**31."""
    n = 405_000
    k = np.concatenate([np.arange(0, 5000), np.arange(400_000, n)]
                       ).astype(np.int64)
    theirs = conn.gen_auctions(k, conn.NexmarkConfig(seed=seed))
    cfg = nexmark_gen.GeneratorConfig(seed=seed)
    ours = {**nexmark_q9.auction_strings(n, cfg),
            **nexmark_q4.auction_window(n, cfg)}
    for col in ("item_name", "description", "expires", "category"):
        assert np.array_equal(ours[col][k], theirs[col]), col
    assert nexmark_q9.ITEMS.tolist() == conn._ITEMS.tolist()
    assert set(ours["description"].tolist()) == {
        "Nice " + item for item in conn._ITEMS.tolist()}
    assert [f.name for f in conn.TABLE_SCHEMAS["auction"]][:9] == \
        list(nexmark_q9.COLUMNS[:9])


def brute(auctions, bids) -> collections.Counter:
    """Row tuples in, the view out: every bid against every auction."""
    out = collections.Counter()
    for a in auctions:
        best = None
        for b in bids:
            if b[0] == a[0] and a[5] <= b[3] <= a[6] and (
                    best is None or (-b[2], b[3]) < (-best[2], best[3])):
                best = b
        if best is not None:
            out[tuple(a) + tuple(best)] += 1
    return out


def rows_of(n_auction: int, n_bid: int, cfg):
    aucs = dict(nexmark_gen.prefix("auction", n_auction, cfg))
    aucs.update(nexmark_q4.auction_window(n_auction, cfg))
    aucs.update(nexmark_q9.auction_strings(n_auction, cfg))
    bids = nexmark_gen.prefix("bid", n_bid, cfg)
    return (list(zip(*(aucs[c].tolist() for c in nexmark_q9.COLUMNS[:9]))),
            list(zip(*(bids[c].tolist() for c in
                       ("auction", "bidder", "price", "date_time")))))


@pytest.mark.parametrize("n_auction,n_bid", [
    (268, 4096), (80, 4096), (268, 1000), (1, 100), (0, 100), (100, 0)])
def test_reference_against_a_loop(n_auction, n_bid):
    """Equal prefixes, bids whose auction the prefix does not hold yet,
    auctions with no bid; a long event gap makes auctions expire while
    bids still name them, so the BETWEEN cuts both ways."""
    for gap in (100_000, 20_000_000):
        cfg = nexmark_gen.GeneratorConfig(seed=4_500_000_007,
                                          min_event_gap_in_ns=gap)
        readers = [{"table": "auction", "side": "left", "rows": n_auction},
                   {"table": "bid", "side": "right", "rows": n_bid}]
        got = nexmark_q9.reference(readers, cfg)
        assert got == brute(*rows_of(n_auction, n_bid, cfg))
        assert all(len(r) == 13 and n == 1 for r, n in got.items())
        assert nexmark_q9.resident_rows(readers, cfg) == n_bid
        if n_auction >= 268 and n_bid >= 4096:
            assert len(got) > 200


def test_a_price_tie_goes_to_the_earlier_bid():
    """A hand-made prefix: the generator's prices seldom tie, so they
    are folded to seven values and the winners compared with the loop;
    and two bids that shared an event time would be refused."""
    cfg = nexmark_gen.GeneratorConfig(seed=4_500_000_007)
    real = nexmark_gen.GENERATORS["bid"]

    def folded(k, c):
        out = dict(real(k, c))
        out["price"] = out["price"] % 7 + 1
        return out

    def same_time(k, c):
        out = dict(real(k, c))
        out["date_time"] = out["date_time"] // 1000 * 1000
        return out

    readers = [{"table": "auction", "side": "left", "rows": 268},
               {"table": "bid", "side": "right", "rows": 4096}]
    nexmark_gen.GENERATORS["bid"] = folded
    try:
        got = nexmark_q9.reference(readers, cfg)
        auctions, bids = rows_of(268, 4096, cfg)
        tied = collections.Counter((b[0], b[2]) for b in bids)
        winners = {(r[9], r[11]) for r in got}
        assert sum(1 for w in winners if tied[w] > 1) > 100
        assert got == brute(auctions, bids)
        nexmark_gen.GENERATORS["bid"] = same_time
        with pytest.raises(AssertionError, match="share an event time"):
            nexmark_q9.reference(readers, cfg)
    finally:
        nexmark_gen.GENERATORS["bid"] = real


def test_reference_refuses_other_readers():
    with pytest.raises(ValueError):
        nexmark_q9.reference([{"table": "bid", "side": "left",
                               "rows": 10}], nexmark_gen.GeneratorConfig())


def drive(capsys, *extra):
    rc = run.main(["--workload", "q9_steady", "--seed", "4500000019",
                   "--seconds", "3", "--rehearse", *extra])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    return json.loads(lines[-1]), lines


def test_sound_run_is_correct(capsys):
    result, lines = drive(capsys, "--trace", "0")
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"events_per_s", "barrier_p50_ms",
                                      "barrier_p90_ms", "setup_s"}
    assert any("view q9 has " in ln and "reference" in ln for ln in lines)


def test_traced_run_prints_the_three_readers(capsys):
    result, _ = drive(capsys, "--trace", "1")
    assert result["correct"] is True
    assert set(READERS) <= set(result["metrics"])
    m = {k: result["metrics"][k]["value"] for k in READERS}
    assert 0 < m["topn_host_share"] < 100
    # every row of a delta is one table write or one table delete
    assert m["topn_state_rows_per_row"] == m["topn_out_rows_per_row"]
    assert 0.05 < m["topn_out_rows_per_row"] < 0.2


def test_control_rare_checkpoint_is_not_correct(capsys):
    result, _ = drive(capsys, "--trace", "0", "--control",
                      "rare_checkpoint")
    assert result["correct"] is False
    assert result["failed"] >= 1


def test_control_short_reference_is_not_correct(capsys):
    # 4,096 rows short of each reader: auctions lose their winner or
    # their row, and the bid side's state table holds 4,096 rows more
    # than the reference says
    result, lines = drive(capsys, "--trace", "0", "--control",
                          "short_reference")
    assert result["correct"] is False
    assert result["failed"] == 0
    assert any("off the reference's by 4096 (limit 0)" in ln
               for ln in lines)
    differing = next(int(ln.split("differing from the reference ")[1]
                         .split(" ")[0]) for ln in lines
                     if "differing from the reference" in ln
                     and "compared:" in ln)
    assert differing > 100
