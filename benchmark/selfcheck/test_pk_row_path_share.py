"""The reader of `pk_row_path_share`, against made-up records with known
answers. A record whose history has neither `state_pk.*` name is what
the parent of the PR that added the counter gives: `None`, and the line
leaves the metric out."""

import run


def record(history):
    return {"window": {"wall_s": 6.0}, "history": history,
            "phase_seconds": {}, "trace": None}


def read(rec):
    return run.load_module("layer_metrics", "pk_row_path_share").read(rec)


def test_window_sums_not_a_mean_of_shares():
    history = {1: {"state_pk.row": 10.0, "state_pk.columnar": 90.0},
               2: {"state_pk.row": 0.0, "state_pk.columnar": 300.0},
               3: {"source_rows": 32768.0}}
    # 10 of 400 keys over the window, though the first epoch's share is 10%
    assert read(record(history)) == 2.5


def test_every_key_by_the_row_or_by_the_column():
    assert read(record({1: {"state_pk.row": 7.0,
                            "state_pk.columnar": 0.0}})) == 100.0
    assert read(record({1: {"state_pk.row": 0.0,
                            "state_pk.columnar": 16384.0}})) == 0.0


def test_nothing_to_read():
    assert read(record({1: {"source_rows": 32768.0,
                            "exec_s.HashAggExecutor": 3.5}})) is None
    assert read(record({})) is None
    # the names are there and no key was encoded: no share to give
    assert read(record({1: {"state_pk.row": 0.0,
                            "state_pk.columnar": 0.0}})) is None
