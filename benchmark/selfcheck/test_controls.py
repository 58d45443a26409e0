"""`correct` has to come out false when it should. Each test drives a
whole run of `run.py` in this process with `--rehearse` (tiny sizes, the
CPU, no look for a chip):

  - the controls: a checkpoint on every second barrier only (the
    guarantee "every barrier is a durable checkpoint" broken), and a
    reference computed one chunk short;
  - the timed path broken underneath: the source alters every bid's
    price from some ordinal on, where the row is produced; and every
    reader reads one chunk that never reaches the view's state.
"""

import json

import pytest

import run


def drive(capsys, *extra):
    rc = run.main(["--seed", "3000000019", "--seconds", "3", "--trace", "0",
                   "--rehearse", *extra])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("cell", ["q7_steady", "q8_steady"])
def test_sound_run_is_correct(capsys, cell):
    result, _ = drive(capsys, "--workload", cell)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 3
    # `q8_steady` holds `barrier_p90_ms` per layer only, as
    # `barrier_tail_ms` (PERF.md section 2)
    assert set(result["metrics"]) == {
        "events_per_s", "barrier_p50_ms", "setup_s"} | (
            {"barrier_p90_ms"} if cell == "q7_steady" else set())
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["q7_steady", "q8_steady"])
def test_control_rare_checkpoint_is_not_correct(capsys, cell):
    result, _ = drive(capsys, "--workload", cell, "--control",
                      "rare_checkpoint")
    assert result["correct"] is False
    assert result["failed"] >= 1


@pytest.mark.parametrize("cell", ["q7_steady", "q8_steady"])
def test_control_short_reference_is_not_correct(capsys, cell):
    # q7's view holds one row per window, so a chunk short seldom shows
    # in it; the rows of the join's state table always do
    result, lines = drive(capsys, "--workload", cell, "--control",
                          "short_reference")
    assert result["correct"] is False
    assert result["failed"] == 0
    assert any("off the reference's by 4096 (limit 0)" in ln
               for ln in lines)


def test_altered_rows_at_the_source_are_not_correct(capsys, monkeypatch):
    from risingwave_tpu.connectors import nexmark as conn
    sound = conn._GENERATORS["bid"]

    def altered(k, cfg):
        cols = sound(k, cfg)
        cols["price"] = cols["price"] + (k >= 20_000)
        return cols

    monkeypatch.setitem(conn._GENERATORS, "bid", altered)
    result, _ = drive(capsys, "--workload", "q7_steady")
    assert result["correct"] is False
    assert result["failed"] == 0      # the barriers were sound: rows were not


def test_a_chunk_lost_behind_the_reader_is_not_correct(capsys, monkeypatch):
    from risingwave_tpu.connectors.nexmark import NexmarkSplitReader
    sound = NexmarkSplitReader.next_chunk
    calls = {}

    def lossy(self):
        calls[id(self)] = calls.get(id(self), 0) + 1
        if calls[id(self)] == 5:
            sound(self)               # read, offset advanced, and dropped
        return sound(self)

    monkeypatch.setattr(NexmarkSplitReader, "next_chunk", lossy)
    result, lines = drive(capsys, "--workload", "q7_steady")
    assert result["correct"] is False
    assert any("off the reference's by 4096 (limit 0)" in ln
               for ln in lines)
