"""`nexmark-q5-wm` / `q5_wm_steady` (PR 37): the reference's resident
rows against a brute-force recount window by window, and `correct`
coming out false when it should, the watermark's blind spot among the
cases: whole runs of `run.py` in this process with `--rehearse` (tiny
epochs, the CPU; about a minute each)."""

import ast
import collections
import json

import pytest

import nexmark_gen
import nexmark_q5
import nexmark_q5_wm
import run

READERS = ("state_clean_share", "state_clean_reads_per_cleaned_row",
           "state_resident_growth")
SEED = "3700000019"


def brute(n_left: int, n_right: int, cfg) -> dict:
    """Every bid counted into its five windows one at a time, every
    window dropped that ends at or under its reader's watermark."""
    slide, size = nexmark_q5.SLIDE_US, nexmark_q5.SIZE_US

    def counts(n):
        bids = nexmark_gen.prefix("bid", n, cfg)
        out, newest = collections.Counter(), None
        for auction, ts in zip(bids["auction"].tolist(),
                               bids["date_time"].tolist()):
            newest = ts if newest is None else max(newest, ts)
            for i in range(nexmark_q5.UNITS):
                out[(ts - ts % slide - i * slide, auction)] += 1
        wm = None if newest is None else newest - nexmark_q5_wm.DELAY_US
        return out, wm

    def open_at(ws, wm):
        return wm is None or ws + size > wm

    (left, lwm), (right, rwm) = counts(n_left), counts(n_right)
    jwm = None if lwm is None or rwm is None else min(lwm, rwm)
    rkeep = {k: v for k, v in right.items() if open_at(k[0], rwm)}
    windows = {ws for ws, _a in rkeep}
    return {
        "AuctionBids": sum(open_at(ws, lwm) for ws, _a in left),
        "CountBids": len(rkeep),
        "MaxBids": len(windows),
        "MaxBids.values": len({(ws, num)
                               for (ws, _a), num in rkeep.items()}),
        "join.left": sum(open_at(ws, jwm) for ws, _a in left),
        "join.right": sum(open_at(ws, jwm) for ws in windows),
    }


@pytest.mark.parametrize("n_left,n_right", [
    (4096, 4096), (6000, 2000), (2000, 6000), (5000, 1), (1, 5000),
    (0, 100), (100, 0), (3333, 3334)])
def test_resident_rows_against_a_recount(n_left, n_right):
    """Equal prefixes and either reader ahead (the join keeps what the
    slower input has not closed). At the cell's gap the few thousand
    bids close nothing; at 5 and 20 ms they span dozens of windows, so
    the watermark has closed most."""
    for gap in (100_000, 5_000_000, 20_000_000):
        cfg = nexmark_gen.GeneratorConfig(seed=4_000_000_007,
                                          min_event_gap_in_ns=gap)
        readers = [{"table": "bid", "side": "left", "rows": n_left},
                   {"table": "bid", "side": "right", "rows": n_right}]
        want = brute(n_left, n_right, cfg)
        assert nexmark_q5_wm.resident_by_table(readers, cfg) == want
        assert nexmark_q5_wm.resident_rows(readers, cfg) == \
            max(want.values())
        # the view is q5's: a closed window keeps its rows
        assert nexmark_q5_wm.reference(readers, cfg) == \
            nexmark_q5.reference(readers, cfg)
        if gap == 20_000_000 and n_left == n_right:
            kept_all = nexmark_q5.resident_rows(readers, cfg)
            assert max(want.values()) < kept_all / 3


def test_the_reference_takes_no_late_bid(monkeypatch):
    """The generator is in order; were it not, the module would say so
    rather than compare against an answer that counts dropped rows."""
    cfg = nexmark_gen.GeneratorConfig(seed=7)
    real = nexmark_gen.prefix

    def shuffled(table, rows, c):
        out = dict(real(table, rows, c))
        out["date_time"] = out["date_time"][::-1].copy()
        return out
    monkeypatch.setattr(nexmark_q5_wm, "prefix", shuffled)
    with pytest.raises(AssertionError, match="under the watermark"):
        nexmark_q5_wm.reference(
            [{"table": "bid", "side": "left", "rows": 50_000},
             {"table": "bid", "side": "right", "rows": 50_000}], cfg)


def drive(capsys, *extra, seed=SEED):
    rc = run.main(["--workload", "q5_wm_steady", "--seed", seed,
                   "--seconds", "4", "--rehearse", *extra])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    return json.loads(lines[-1]), lines


def test_sound_run_is_correct_and_the_unwatermarked_count_is_not(capsys):
    """The cell's own check passes; the same run held to
    `nexmark_q5.resident_rows` (every group ever seen: what the check
    of `nexmark-q5` compares, and what an uncleaned table would read)
    is off by tens of thousands of rows, so the two checks cannot be
    mistaken for one another again."""
    result, lines = drive(capsys, "--trace", "0")
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 6
    assert set(result["metrics"]) == {"events_per_s", "barrier_p50_ms",
                                      "barrier_p90_ms", "setup_s"}
    compared = next(ln for ln in lines if "compared:" in ln)
    assert "rows differing from the reference 0 (limit 0)" in compared
    assert "by 0 (limit 0)" in compared
    assert "kernel (re)traces by barrier of the window: none" in \
        "\n".join(lines)
    kept = int(compared.split("largest state table ")[1].split(",")[0])
    checked = next(ln for ln in lines if "check: view q5 has" in ln)
    readers = ast.literal_eval(
        checked.split(" over ")[1].split("; read in")[0])
    config = run.load_json(run.HERE, "configs", "nexmark-q5-wm.json")
    gen = nexmark_gen.GeneratorConfig(seed=int(SEED),
                                      **config["generator"])
    assert kept == nexmark_q5_wm.resident_rows(readers, gen)
    state_rows_off = abs(kept - nexmark_q5.resident_rows(readers, gen))
    assert state_rows_off > kept


def test_traced_run_prints_the_host_readers(capsys):
    result, _ = drive(capsys, "--trace", "1")
    assert result["correct"] is True
    # `>=`: a later PR may list this cell on further readers; the
    # fourth, `retire_device_ms_per_barrier`, needs a device plane
    assert set(result["metrics"]) >= set(READERS)
    assert "retire_device_ms_per_barrier" not in result["metrics"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < metrics["state_clean_share"] < 100
    # since PR 38 the clean index names the keys: nothing is read back
    assert metrics["state_clean_reads_per_cleaned_row"] == 0.0
    assert 0.85 <= metrics["state_resident_growth"] <= 1.15


def test_control_rare_checkpoint_is_not_correct(capsys):
    result, _ = drive(capsys, "--trace", "0", "--control",
                      "rare_checkpoint")
    assert result["correct"] is False
    assert result["failed"] >= 1


@pytest.mark.parametrize("seed", [SEED, "7"])
def test_control_short_reference_is_not_correct(capsys, seed):
    """4,096 bids short of each reader: the state count is off by the
    groups those bids add, or by a window's where the shorter prefix
    has one more open."""
    result, lines = drive(capsys, "--trace", "0", "--control",
                          "short_reference", seed=seed)
    assert result["correct"] is False
    assert result["failed"] == 0
    compared = next(ln for ln in lines if "compared:" in ln)
    off = int(compared.split("off the reference's by ")[1].split()[0])
    assert off > 500
