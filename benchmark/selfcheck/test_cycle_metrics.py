"""The readers of `cycle_p50_ms` and `heartbeat_wait_p50_ms`, against
made-up records with known answers. A record whose history has no
`heartbeat.*` name is what the parent of the PR that added the tick
gives: the cycle still reads (it is the before), the wait is `None`
and the line leaves it out."""

import pytest

import run


def record(history):
    return {"window": {"wall_s": 6.0}, "history": history,
            "phase_seconds": {}, "barriers": [], "trace": None}


def read(name, rec):
    return run.load_module("layer_metrics", name).read(rec)


def rows(seals, **names):
    """{epoch: row}, epochs out of order on purpose: the reader sorts by
    the stamps, not by the keys."""
    return {100 - i: {"ts": ts, "interval_s": 0.1,
                      **{k: v[i] for k, v in names.items()}}
            for i, ts in enumerate(seals)}


def test_the_cycle_is_the_median_seal_to_seal_gap_less_the_closing_epoch():
    # plain cycles of 0.25 s, one compaction cycle of 0.46 s, and a
    # closing epoch that sealed 3 s late (the pause and the FLUSH)
    seals = [10.0, 10.25, 10.5, 10.96, 11.21, 14.21]
    assert read("cycle_p50_ms", record(rows(seals))) == pytest.approx(250.0)


def test_the_parent_reads_a_cycle_and_no_wait():
    # collect 0.111 s + the 0.25 s sleep, no heartbeat.* name
    seals = [5.0, 5.361, 5.722, 6.083, 9.0]
    parent = record(rows(seals, source_rows=[32768.0] * 5))
    assert read("cycle_p50_ms", parent) == pytest.approx(361.0)
    assert read("heartbeat_wait_p50_ms", parent) is None


def test_the_wait_is_the_median_of_the_epochs_that_have_one():
    history = rows([1.0, 1.25, 1.5, 1.96, 2.4],
                   **{"heartbeat.wait_s": [0.25, 0.14, 0.13, 0.0, 0.2],
                      "heartbeat.overdue": [0.0, 0.0, 0.0, 1.0, 0.0]})
    history[7] = {"ts": 0.5, "interval_s": 0.1}     # a row from before
    assert read("heartbeat_wait_p50_ms", record(history)) == \
        pytest.approx(140.0)


def test_a_saturated_heartbeat_waits_nothing():
    history = rows([1.0, 1.7, 2.4, 3.1, 3.8],
                   **{"heartbeat.wait_s": [0.25, 0.0, 0.0, 0.0, 0.0]})
    assert read("heartbeat_wait_p50_ms", record(history)) == 0.0
    assert read("cycle_p50_ms", record(history)) == pytest.approx(700.0)


@pytest.mark.parametrize("history", [
    {}, {1: {"ts": 1.0}}, {1: {"ts": 1.0}, 2: {"ts": 1.3}}])
def test_too_few_epochs_to_have_a_cycle(history):
    # one gap needs two seals beside the closing one
    assert read("cycle_p50_ms", record(history)) is None
    assert read("heartbeat_wait_p50_ms", record(history)) is None
