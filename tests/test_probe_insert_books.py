"""The books of probe_insert's ladder (PR 34): `row_rounds`, the rows
the claim loop's rounds worked, beside the rounds, from the kernel's
counter through `PendingCounters` and `note_batch_books` to the names
`rw_metrics_history` files them under."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.stream import hotkeys
from risingwave_tpu.utils.jaxtools import PendingCounters
from risingwave_tpu.utils.metrics import STREAMING, MetricsHistory
from test_ops_hash_table import _FIRST, _single_loop_jit  # the reference


def _keys(rng, rows, kw):
    return rng.integers(-2**31, 2**31 - 1, (rows, kw),
                        dtype=np.int64).astype(np.int32)


_counted = jax.jit(ht.probe_insert_counted)


def _table(cap, kw, load, rng):
    resident = _keys(rng, int(cap * load), kw)
    state, _slots, _books = _counted(
        ht.make_state(cap, kw), jnp.asarray(resident),
        jnp.ones(len(resident), bool))
    return state, resident


@pytest.mark.parametrize("n", [64, ht.LADDER_FLOOR - 1, _FIRST - 1])
def test_below_the_first_rung_every_round_works_the_whole_batch(n):
    rng = np.random.default_rng(n)
    state, resident = _table(1 << 18, 2, 0.5, rng)
    batch = resident[rng.integers(0, len(resident), n)]
    batch[::3] = _keys(rng, len(batch[::3]), 2)
    _state, _slots, books = _counted(state, jnp.asarray(batch),
                                     jnp.ones(n, bool))
    _ins, rounds, row_rounds, rows = (int(v) for v in books)
    assert rows == n and rounds > 1
    assert row_rounds == rounds * n


def test_a_16384_row_batch_of_new_keys_works_under_a_quarter_of_its_rounds():
    """q8's person aggregate: 16,384 new keys into 2^18 slots at load
    0.6. The rounds are the single loop's (the longest probe chain
    decides them); the rows they work are not: nearly every row is
    placed before the second rung, so the tens of rounds left run over
    4,096 and 1,024 rows."""
    rng = np.random.default_rng(52)
    n, cap, kw = 16_384, 1 << 18, 3
    state, _resident = _table(cap, kw, 0.6, rng)
    batch, valid = jnp.asarray(_keys(rng, n, kw)), jnp.ones(n, bool)
    _table_ref, _slots_ref, books_ref = _single_loop_jit(state, batch, valid)
    _state, _slots, books = _counted(state, batch, valid)
    ins, rounds, row_rounds, rows = (int(v) for v in books)
    assert (ins, rounds) == tuple(int(v) for v in books_ref)
    assert rows == n and ins == n and rounds >= 20
    assert row_rounds < rounds * rows / 4


def test_a_q5_shaped_batch_works_under_six_batches_of_rows():
    """163,840 staged rows of which the first half is valid, half of
    those on 50 hot keys, into 2^19 slots x 6 lanes at load 0.6: the
    single loop paid `rounds` batches (46 here), the ladder a few."""
    rng = np.random.default_rng(34)
    n, cap, kw = 163_840, 1 << 19, 6
    state, resident = _table(cap, kw, 0.6, rng)
    valid = np.arange(n) < n // 2
    batch = resident[rng.integers(0, len(resident), n)]
    hot = resident[rng.choice(len(resident), 50, replace=False)]
    on_hot = rng.random(n) < 0.5
    batch[on_hot] = hot[rng.integers(0, 50, int(on_hot.sum()))]
    new = rng.random(n) < 0.066          # ~5.4K new groups
    batch[new] = _keys(rng, int(new.sum()), kw)
    _state, slots, books = _counted(state, jnp.asarray(batch),
                                    jnp.asarray(valid))
    ins, rounds, row_rounds, rows = (int(v) for v in books)
    assert rows == n and 4_000 < ins < 7_000
    assert rounds >= 20
    assert row_rounds <= rounds * n
    assert row_rounds < 6 * n
    assert bool((np.asarray(slots)[n // 2:] == -1).all())


def test_take_rounds_gives_the_rows_beside_the_rounds():
    books = PendingCounters()
    books.push(jnp.asarray([5, 7, 900, 300], dtype=jnp.int32), 300)
    books.push(jnp.asarray([1, 2, 600, 300], dtype=jnp.int32), 300)
    assert books.drain_all() == 6
    assert books.take_rounds() == (9, 2, 1500, 600)
    assert books.take_rounds() == (0, 0, 0, 0)


def test_a_bare_count_folds_and_leaves_no_books():
    """The sharded steps return the count alone (`probe_insert` inside
    shard_map): it folds beside the single-chip steps' books."""
    books = PendingCounters()
    books.push(jnp.asarray([5, 7, 48, 16], dtype=jnp.int32), 16)
    books.push(jnp.asarray(3, dtype=jnp.int32), 16)     # a bare count
    assert books.drain_all() == 8
    assert books.take_rounds() == (7, 1, 48, 16)


def test_note_batch_books_files_row_rounds_and_rows_by_kernel():
    kernel = "agg.t_books_test"
    before = {name: v for name, v, _k in MetricsHistory()._batch_books()
              if kernel in name}
    hotkeys.note_batch_books(kernel, "no-such-identity",
                             lambda: (46, 2, 1_000_000, 327_680))
    hotkeys.note_batch_books(kernel, "no-such-identity",
                             lambda: (19, 1, 16_384, 16_384))
    # a kernel none of whose counters has landed files nothing
    hotkeys.note_batch_books(kernel, "no-such-identity",
                             lambda: (0, 0, 0, 0))
    after = {name: v for name, v, _k in MetricsHistory()._batch_books()
             if kernel in name}
    delta = {name: v - before.get(name, 0) for name, v in after.items()}
    assert delta == {
        f"probe_insert.{kernel}.rounds": 65,
        f"probe_insert.{kernel}.batches": 3,
        f"probe_insert_rows.{kernel}.row_rounds": 1_016_384,
        f"probe_insert_rows.{kernel}.rows": 344_064,
    }
    assert STREAMING.probe_insert_row_rounds.get(kernel=kernel) >= 1_000_000


def _bench_reader(name):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_reader_takes_the_kernel_where_the_ratio_is_largest():
    history = {
        1: {"probe_insert_rows.agg.t5.row_rounds": 500_000.0,
            "probe_insert_rows.agg.t5.rows": 163_840.0,
            "probe_insert_rows.join.t9.row_rounds": 40_000.0,
            "probe_insert_rows.join.t9.rows": 16_384.0,
            "probe_insert.agg.t5.rounds": 50.0,
            "probe_insert.agg.t5.batches": 1.0},
        2: {"probe_insert_rows.agg.t5.row_rounds": 400_000.0,
            "probe_insert_rows.agg.t5.rows": 163_840.0,
            "probe_insert_rows.join.t7.row_rounds": 0.0,
            "probe_insert_rows.join.t7.rows": 0.0},
    }
    reader = _bench_reader("probe_rows_worked_per_row")
    assert reader.read({"history": history}) == \
        pytest.approx(900_000 / 327_680)
    # the rounds' reader, which may not be edited, takes every
    # `probe_insert.<kernel>.*` but `.rounds` for its batches: the new
    # names must stay out of its way
    rounds = _bench_reader("probe_rounds_per_epoch")
    assert rounds.read({"history": history}) == 50.0


def test_the_reader_reads_nothing_from_a_program_without_the_names():
    reader = _bench_reader("probe_rows_worked_per_row")
    parent = {1: {"ts": 1.0, "probe_insert.agg.t5.rounds": 57.0,
                  "probe_insert.agg.t5.batches": 1.0}}
    assert reader.read({"history": parent}) is None
    assert reader.read({"history": {}}) is None
