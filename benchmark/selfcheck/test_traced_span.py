"""The `--trace 1` run profiles the window it is about (PR 51): whole
runs of `run.py` in this process with `--rehearse` (tiny sizes, the CPU),
their records kept with `BENCH_KEEP_DIR` and read back. The CPU's trace
has no device plane, so what is held here is the span: where the mark
and the end fall against the window's barriers, whatever the window's
length, and that profiling adds no wait to the window."""

import glob
import json
import os

import pytest

import run

SEED = "5100000019"


def drive(capsys, monkeypatch, keep, cell, seconds, trace):
    monkeypatch.setenv(run.KEEP_ENV, str(keep))
    rc = run.main(["--workload", cell, "--seed", SEED, "--seconds",
                   str(seconds), "--trace", str(trace), "--rehearse"])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    kept, = glob.glob(os.path.join(keep, f"{cell}.{SEED}.t{trace}.*.json"))
    with open(kept) as f:
        record = json.load(f)
    os.remove(kept)
    return json.loads(lines[-1]), record, lines


@pytest.mark.parametrize("cell,seconds", [("q8_steady", 1), ("q8_steady", 4),
                                          ("q7_steady", 3)])
def test_the_span_brackets_the_window(capsys, monkeypatch, tmp_path, cell,
                                      seconds):
    """A window of two barriers and one of several are both covered
    whole: the mark is made before the first window barrier seals, the
    span ends after the closing one has, every window barrier is sealed
    inside it and no other epoch is."""
    result, record, lines = drive(capsys, monkeypatch, tmp_path, cell,
                                  seconds, 1)
    assert result["correct"] is True
    span, barriers = record["span"], record["barriers"]
    seals = sorted(h["ts"] for h in record["history"].values())
    assert len(seals) == len(barriers) == result["attempted"] >= 2
    assert span["mark_wall"] <= seals[0] and seals[-1] <= span["end_wall"]
    assert span["epochs_in_span"] == len(barriers)
    # the span is the window: nothing waits for the profiler inside it
    length = span["end_wall"] - span["mark_wall"]
    assert 0 <= length - record["window"]["wall_s"] < 0.1
    assert not any("stopped at the traffic file's limit" in ln
                   for ln in lines)


def test_a_traced_window_is_as_long_as_an_untraced_one(capsys, monkeypatch,
                                                       tmp_path):
    """Until PR 51 a traced run's `wall_s` held 12 s of profile whatever
    the window took. The first run warms the compile cache and is not
    compared."""
    drive(capsys, monkeypatch, tmp_path, "q8_steady", 4, 0)
    _, plain, _ = drive(capsys, monkeypatch, tmp_path, "q8_steady", 4, 0)
    _, traced, _ = drive(capsys, monkeypatch, tmp_path, "q8_steady", 4, 1)
    assert plain["span"] is None
    assert len(traced["barriers"]) == len(plain["barriers"])
    assert abs(traced["window"]["wall_s"] - plain["window"]["wall_s"]) < 1.0


def test_the_limit_stops_the_profile_and_says_so(capsys, monkeypatch,
                                                 tmp_path):
    """`trace.seconds` is an upper limit: a window that outlasts it is
    profiled up to it, the run goes on to its end and stays correct."""
    real = run.load_json

    def short_limit(*parts):
        loaded = real(*parts)
        if parts[-2:] == ("traffic", "steady.json"):
            loaded["trace"]["seconds"] = 0.5
        return loaded
    monkeypatch.setattr(run, "load_json", short_limit)
    result, record, lines = drive(capsys, monkeypatch, tmp_path,
                                  "q8_steady", 4, 1)
    assert result["correct"] is True
    assert any("stopped at the traffic file's limit of 0.5 s" in ln
               for ln in lines)
    span = record["span"]
    assert 0.5 <= span["end_wall"] - span["mark_wall"] < \
        record["window"]["wall_s"]
    assert span["epochs_in_span"] < len(record["barriers"])
