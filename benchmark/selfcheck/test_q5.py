"""`nexmark-q5` / `q5_steady` (PR 33): the reference against a
brute-force recount row by row, and `correct` coming out false when it
should: whole runs of `run.py` in this process with `--rehearse` (tiny
sizes, the CPU)."""

import collections
import json

import pytest

import nexmark_gen
import nexmark_q5
import run

READERS = ("join_out_rows_per_source_row", "join_retract_share",
           "join_condition_share")


def brute(n_left: int, n_right: int, cfg):
    """(view rows, distinct groups of the left prefix): every bid
    counted into its five windows one at a time."""
    def counts(n):
        bids = nexmark_gen.prefix("bid", n, cfg)
        out = collections.Counter()
        for auction, ts in zip(bids["auction"].tolist(),
                               bids["date_time"].tolist()):
            start = ts - ts % nexmark_q5.SLIDE_US
            for i in range(nexmark_q5.UNITS):
                out[(start - i * nexmark_q5.SLIDE_US, auction)] += 1
        return out
    left, right = counts(n_left), counts(n_right)
    maxn = {}
    for (ws, _auction), num in right.items():
        maxn[ws] = max(maxn.get(ws, 0), num)
    view = collections.Counter()
    for (ws, auction), num in left.items():
        if ws in maxn and num >= maxn[ws]:
            view[(auction, num)] += 1
    return view, len(left), len(right)


@pytest.mark.parametrize("n_left,n_right", [
    (4096, 4096), (6000, 2000), (2000, 6000), (5000, 1), (1, 5000),
    (0, 100), (100, 0)])
def test_reference_against_a_recount(n_left, n_right):
    """Equal prefixes and either reader ahead (windows the right
    prefix has not reached give no row; a left prefix that is behind
    holds smaller counts than the maximum it is held to). A long
    event gap spreads the few thousand bids over hundreds of windows,
    with ties among their auctions."""
    for gap in (100_000, 20_000_000):
        cfg = nexmark_gen.GeneratorConfig(seed=4_000_000_007,
                                          min_event_gap_in_ns=gap)
        readers = [{"table": "bid", "side": "left", "rows": n_left},
                   {"table": "bid", "side": "right", "rows": n_right}]
        view, groups_left, groups_right = brute(n_left, n_right, cfg)
        assert nexmark_q5.reference(readers, cfg) == view
        assert nexmark_q5.resident_rows(readers, cfg) == \
            max(groups_left, groups_right)
        if n_left == n_right == 4096:
            # a row a window at least, more where auctions tie
            windows = len({ws for ws in nexmark_q5.window_counts(
                n_left, cfg)[0].tolist()})
            assert sum(view.values()) >= windows >= 5


def test_reference_refuses_other_readers():
    with pytest.raises(ValueError):
        nexmark_q5.reference([{"table": "bid", "side": "left",
                               "rows": 10}], nexmark_gen.GeneratorConfig())
    with pytest.raises(ValueError):
        nexmark_q5.reference(
            [{"table": "auction", "side": "left", "rows": 10},
             {"table": "bid", "side": "right", "rows": 10}],
            nexmark_gen.GeneratorConfig())


def drive(capsys, *extra, seed="3300000019"):
    rc = run.main(["--workload", "q5_steady", "--seed", seed,
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
    assert any("view q5 has" in ln and "rows differing" not in ln
               for ln in lines)
    assert any("rows differing from the reference 0 (limit 0)" in ln
               and "by 0 (limit 0)" in ln for ln in lines)


def test_traced_run_prints_at_least_the_three_readers(capsys):
    result, _ = drive(capsys, "--trace", "1")
    assert result["correct"] is True
    # `>=`: a later PR may list this cell on further readers
    assert set(result["metrics"]) >= set(READERS)
    assert 0 < result["metrics"]["join_retract_share"]["value"] < 50
    assert result["metrics"]["join_out_rows_per_source_row"]["value"] > 0
    assert 0 < result["metrics"]["join_condition_share"]["value"] < 100


def test_control_rare_checkpoint_is_not_correct(capsys):
    result, _ = drive(capsys, "--trace", "0", "--control",
                      "rare_checkpoint")
    assert result["correct"] is False
    assert result["failed"] >= 1


@pytest.mark.parametrize("seed", ["3300000019", "7", "2147483659"])
def test_control_short_reference_is_not_correct(capsys, seed):
    """4,096 rows short of each reader. The view holds a row a window:
    the newest windows' counts fall with the lost chunk, so the view
    usually shows it; the state count shows it on every seed, for
    every chunk adds groups (about a third of a group a bid)."""
    result, lines = drive(capsys, "--trace", "0", "--control",
                          "short_reference", seed=seed)
    assert result["correct"] is False
    assert result["failed"] == 0
    compared = next(ln for ln in lines if "compared:" in ln)
    off = int(compared.split("off the reference's by ")[1].split()[0])
    assert off > 1000
