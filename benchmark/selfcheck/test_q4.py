"""`nexmark-q4` / `q4_steady` (PR 31): the two auction columns the
reference derives itself against the connector, the reference against a
brute-force loop, and `correct` coming out false when it should: whole
runs of `run.py` in this process with `--rehearse` (tiny sizes, the CPU)."""

import collections
import json

import numpy as np
import pytest

import nexmark_gen
import nexmark_q4
import run
from risingwave_tpu.connectors import nexmark as conn


@pytest.mark.parametrize("seed", [7, 2_147_483_659, 3_100_000_031])
def test_derived_auction_columns_match_the_connector(seed):
    """`expires` and `category`, which the generator copy lacks, over
    the first auctions and a stretch far in; the driver's seeds are
    above 2**31."""
    n = 405_000
    k = np.concatenate([np.arange(0, 5000), np.arange(400_000, n)]
                       ).astype(np.int64)
    theirs = conn.gen_auctions(k, conn.NexmarkConfig(seed=seed))
    ours = nexmark_q4.auction_window(
        n, nexmark_gen.GeneratorConfig(seed=seed))
    for col in ("expires", "category"):
        assert np.array_equal(ours[col][k], theirs[col]), col
    assert set(np.unique(ours["category"]).tolist()) == set(range(
        nexmark_q4.FIRST_CATEGORY_ID,
        nexmark_q4.FIRST_CATEGORY_ID + nexmark_q4.NUM_CATEGORIES))
    life = theirs["expires"] - theirs["date_time"]
    assert life.min() >= 1_000_000 and life.max() <= 1_100_000


def brute(n_auction: int, n_bid: int, cfg) -> collections.Counter:
    aucs = nexmark_gen.prefix("auction", n_auction, cfg)
    extra = nexmark_q4.auction_window(n_auction, cfg)
    bids = nexmark_gen.prefix("bid", n_bid, cfg)
    by_id = {a: i for i, a in enumerate(aucs["id"].tolist())}
    final = {}
    for a, price, ts in zip(bids["auction"].tolist(),
                            bids["price"].tolist(),
                            bids["date_time"].tolist()):
        i = by_id.get(a)
        if i is None or not (aucs["date_time"][i] <= ts
                             <= extra["expires"][i]):
            continue
        final[i] = max(final.get(i, price), price)
    by_cat = collections.defaultdict(list)
    for i, price in final.items():
        by_cat[int(extra["category"][i])].append(price)
    return collections.Counter(
        {(cat, sum(ps) / len(ps)): 1 for cat, ps in by_cat.items()})


@pytest.mark.parametrize("n_auction,n_bid", [
    (1072, 16384), (300, 16384), (1072, 4000), (1, 100), (0, 100),
    (100, 0)])
def test_reference_against_a_loop(n_auction, n_bid):
    """Equal prefixes, bids whose auction the prefix does not hold yet,
    auctions with no bid; a short event gap makes auctions expire while
    bids still name them, so the BETWEEN cuts both ways."""
    for gap in (100_000, 20_000_000):
        cfg = nexmark_gen.GeneratorConfig(seed=4_000_000_007,
                                          min_event_gap_in_ns=gap)
        readers = [{"table": "auction", "side": "left", "rows": n_auction},
                   {"table": "bid", "side": "right", "rows": n_bid}]
        got = nexmark_q4.reference(readers, cfg)
        assert got == brute(n_auction, n_bid, cfg)
        assert nexmark_q4.resident_rows(readers, cfg) == n_bid
        if n_auction >= 300 and n_bid >= 4000:
            assert len(got) == nexmark_q4.NUM_CATEGORIES


def test_reference_refuses_other_readers():
    with pytest.raises(ValueError):
        nexmark_q4.reference([{"table": "bid", "side": "left",
                               "rows": 10}], nexmark_gen.GeneratorConfig())


def drive(capsys, *extra):
    rc = run.main(["--workload", "q4_steady", "--seed", "3100000019",
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
    assert any("view q4 has 5 rows" in ln for ln in lines)


def test_traced_run_prints_the_three_readers(capsys):
    result, _ = drive(capsys, "--trace", "1")
    assert result["correct"] is True
    # `>=`: later PRs list this cell on further readers
    assert set(result["metrics"]) >= {"join_to_agg_share",
                                      "agg_retract_share",
                                      "probe_rounds_per_epoch"}
    assert result["metrics"]["agg_retract_share"]["value"] > 0


def test_control_rare_checkpoint_is_not_correct(capsys):
    result, _ = drive(capsys, "--trace", "0", "--control",
                      "rare_checkpoint")
    assert result["correct"] is False
    assert result["failed"] >= 1


def test_control_short_reference_is_not_correct(capsys):
    # 4,096 rows short of each reader: the averages move and the bid
    # side's state table holds 4,096 rows more than the reference says
    result, lines = drive(capsys, "--trace", "0", "--control",
                          "short_reference")
    assert result["correct"] is False
    assert result["failed"] == 0
    assert any("off the reference's by 4096 (limit 0)" in ln
               for ln in lines)
    assert any("rows differing from the reference 10 (limit 0)" in ln
               for ln in lines)
