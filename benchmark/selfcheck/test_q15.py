"""`nexmark-q15` / `q15_steady` (PR 41): the reference against a
brute-force recount row by row; a rehearsal of the cell coming out
correct, with exactly two dedup tables under the view; and `correct`
coming out false when it should: whole runs of `run.py` in this process
with `--rehearse` (tiny sizes, the CPU)."""

import collections
import datetime
import json

import pytest

import nexmark_gen
import nexmark_q15
import run

READERS = ("distinct_dedup_share", "distinct_pairs_changed_per_row",
           "distinct_state_rows_per_row")


def brute(n: int, cfg):
    """(view rows, (day, bidder) pairs, (day, auction) pairs): every bid
    put into Python sets one at a time, the day by `datetime`."""
    bids = nexmark_gen.prefix("bid", n, cfg)
    epoch = datetime.datetime(1970, 1, 1)
    days = collections.defaultdict(lambda: {
        "bids": [0, 0, 0, 0],
        "bidder": [set(), set(), set(), set()],
        "auction": [set(), set(), set(), set()]})
    for auction, bidder, price, ts in zip(
            bids["auction"].tolist(), bids["bidder"].tolist(),
            bids["price"].tolist(), bids["date_time"].tolist()):
        day = (epoch + datetime.timedelta(microseconds=ts)).strftime(
            "%Y-%m-%d")
        rank = 1 if price < 10000 else 2 if price < 1000000 else 3
        for s in (0, rank):
            days[day]["bids"][s] += 1
            days[day]["bidder"][s].add(bidder)
            days[day]["auction"][s].add(auction)
    view = collections.Counter(
        (day, *d["bids"], *map(len, d["bidder"]), *map(len, d["auction"]))
        for day, d in days.items())
    return (view, sum(len(d["bidder"][0]) for d in days.values()),
            sum(len(d["auction"][0]) for d in days.values()))


@pytest.mark.parametrize("n", [0, 1, 4096, 20000])
def test_reference_against_a_recount(n):
    """One day at the cell's event gap; with 8.64 s between events the
    same bids cross midnight every 9,200 rows: a pair is per day."""
    for gap in (100_000, 8_640_000_000):
        cfg = nexmark_gen.GeneratorConfig(seed=4_100_000_041,
                                          min_event_gap_in_ns=gap)
        readers = [{"table": "bid", "side": None, "rows": n}]
        view, bidders, auctions = brute(n, cfg)
        assert nexmark_q15.reference(readers, cfg) == view
        assert nexmark_q15.pair_counts(readers, cfg) == (bidders, auctions)
        assert nexmark_q15.resident_rows(readers, cfg) == auctions
        if n == 20000:
            assert len(view) == (1 if gap == 100_000 else 3)
            assert auctions > bidders > 0


def test_reference_refuses_other_readers():
    with pytest.raises(ValueError):
        nexmark_q15.reference(
            [{"table": "bid", "side": "left", "rows": 10},
             {"table": "bid", "side": "right", "rows": 10}],
            nexmark_gen.GeneratorConfig())
    with pytest.raises(ValueError):
        nexmark_q15.reference([{"table": "auction", "side": None,
                                "rows": 10}], nexmark_gen.GeneratorConfig())


def drive(capsys, *extra, seed="4100000041"):
    rc = run.main(["--workload", "q15_steady", "--seed", seed,
                   "--seconds", "3", "--rehearse", *extra])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    return json.loads(lines[-1]), lines


def test_sound_run_is_correct_and_keeps_two_dedup_tables(capsys):
    from risingwave_tpu.state.topology import TOPOLOGY
    result, lines = drive(capsys, "--trace", "0")
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"events_per_s", "barrier_p50_ms",
                                      "barrier_p90_ms", "setup_s"}
    assert any("rows differing from the reference 0 (limit 0)" in ln
               and "by 0 (limit 0)" in ln for ln in lines)
    # The check's `state_rows_off` reads the largest state table only,
    # and that is auction's dedup table under any layout: the layout is
    # held here. The view's state tables, by the topology's books the
    # run left behind: the source's offsets, the value state and the
    # view's own row (one row each), and exactly two that grow.
    by_table = collections.Counter()
    for table_id, mv, _vnode, n, _bytes in TOPOLOGY.rows():
        if mv == "nexmark_q15":
            by_table[table_id] += n
    assert sorted(by_table.values())[:3] == [1, 1, 1]
    assert len(by_table) == 5
    bidders, auctions = sorted(by_table.values())[3:]
    assert auctions > 2 * bidders > 100


def test_traced_run_prints_at_least_the_three_readers(capsys):
    result, _ = drive(capsys, "--trace", "1")
    assert result["correct"] is True
    # `>=`: a later PR may list this cell on further readers
    assert set(result["metrics"]) >= set(READERS)
    assert 0 < result["metrics"]["distinct_dedup_share"]["value"] < 100
    # 0.087 pairs a bid under one table a distinct column; 0.33 where
    # every filtered call keeps its own
    assert 0.07 < result["metrics"]["distinct_state_rows_per_row"][
        "value"] < 0.11
    assert 0.05 < result["metrics"]["distinct_pairs_changed_per_row"][
        "value"] < 0.3


def test_control_rare_checkpoint_is_not_correct(capsys):
    result, _ = drive(capsys, "--trace", "0", "--control",
                      "rare_checkpoint")
    assert result["correct"] is False
    assert result["failed"] >= 1


@pytest.mark.parametrize("seed", ["4100000041", "7", "2147483659"])
def test_control_short_reference_is_not_correct(capsys, seed):
    """4,096 rows short of the reader: the day's `total_bids` is off by
    the chunk, so the one row differs, and the chunk's new auctions are
    missing from the state count, on every seed."""
    result, lines = drive(capsys, "--trace", "0", "--control",
                          "short_reference", seed=seed)
    assert result["correct"] is False
    assert result["failed"] == 0
    compared = next(ln for ln in lines if "compared:" in ln)
    assert "rows differing from the reference 2 (limit 0)" in compared
    off = int(compared.split("off the reference's by ")[1].split()[0])
    assert off > 100
