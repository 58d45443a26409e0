"""Device hash table kernel vs a host-dict oracle.

Mirrors the testing stance of the reference's hash-map-backed operators:
random batches incl. heavy duplicate keys, asserted slot-consistency
against a Python dict (SURVEY.md §4 — executor tests vs host oracles).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.ops.hash_table import (
    DeviceHashTable, MIN_CAPACITY, lookup, make_state, probe_insert,
)


def _oracle_slots(all_batches):
    """key tuple → first-seen order id (identity of the group)."""
    ids = {}
    for batch, valid in all_batches:
        for row, v in zip(batch, valid):
            if v and tuple(row) not in ids:
                ids[tuple(row)] = len(ids)
    return ids


def _assert_consistent(state, batches_and_slots):
    """Same key ⇒ same slot; different keys ⇒ different slots."""
    seen = {}
    for batch, valid, slots in batches_and_slots:
        slots = np.asarray(slots)
        for row, v, s in zip(batch, valid, slots):
            if not v:
                assert s == -1
                continue
            k = tuple(row)
            assert s >= 0, f"valid row {k} got slot -1"
            if k in seen:
                assert seen[k] == s, f"key {k}: slots {seen[k]} != {s}"
            else:
                assert s not in seen.values(), f"slot {s} reused across keys"
                seen[k] = s
        # table keys at those slots hold the batch keys
        tkeys = np.asarray(state.keys)
        for row, v, s in zip(batch, valid, slots):
            if v:
                assert tuple(tkeys[s]) == tuple(row)


def test_probe_insert_basic():
    state = make_state(64, 2)
    batch = jnp.asarray([[1, 10], [2, 20], [1, 10], [3, 30]], dtype=jnp.int32)
    valid = jnp.asarray([True, True, True, True])
    state, slots, ins = probe_insert(state, batch, valid)
    slots = np.asarray(slots)
    assert int(ins) == 3                      # one duplicate in the batch
    assert slots[0] == slots[2]               # duplicate keys share a slot
    assert len({slots[0], slots[1], slots[3]}) == 3
    # re-probing finds, not re-inserts
    state2, slots2, ins2 = probe_insert(state, batch, valid)
    assert int(ins2) == 0
    assert np.array_equal(np.asarray(slots2), slots)


def test_invalid_rows_untouched():
    state = make_state(64, 1)
    batch = jnp.asarray([[7], [8]], dtype=jnp.int32)
    valid = jnp.asarray([True, False])
    state, slots, ins = probe_insert(state, batch, valid)
    assert int(ins) == 1
    assert np.asarray(slots)[1] == -1
    assert int(np.sum(np.asarray(state.occ))) == 1


def test_lookup_absent_and_present():
    state = make_state(64, 1)
    ins_batch = jnp.asarray([[5], [6]], dtype=jnp.int32)
    state, slots, _ = probe_insert(state, ins_batch,
                                   jnp.ones(2, dtype=bool))
    q = jnp.asarray([[6], [42], [5]], dtype=jnp.int32)
    got = np.asarray(lookup(state, q, jnp.ones(3, dtype=bool)))
    assert got[0] == np.asarray(slots)[1]
    assert got[1] == -1
    assert got[2] == np.asarray(slots)[0]


def test_collision_heavy_random_oracle():
    """Tiny capacity + skewed keys: every batch collides hard."""
    rng = np.random.default_rng(7)
    state = make_state(128, 2)
    batches = []
    for _ in range(6):
        n = 32
        batch = np.stack([rng.integers(0, 10, n),      # heavy duplicates
                          rng.integers(0, 5, n)], axis=1).astype(np.int32)
        valid = rng.random(n) > 0.2
        state, slots, _ = probe_insert(
            state, jnp.asarray(batch), jnp.asarray(valid))
        batches.append((batch, valid, slots))
    _assert_consistent(state, batches)
    n_keys = len(_oracle_slots([(b, v) for b, v, _ in batches]))
    assert int(np.sum(np.asarray(state.occ))) == n_keys


def test_wrapper_growth_preserves_slots_mapping():
    t = DeviceHashTable(key_width=1, capacity=MIN_CAPACITY)
    moves = []
    t.on_grow(lambda old_to_new, old_cap: moves.append(
        (np.asarray(old_to_new), old_cap)))
    n = MIN_CAPACITY  # force at least one growth past MAX_LOAD
    keys = np.arange(n, dtype=np.int32).reshape(-1, 1)
    slots_before = {}
    for start in range(0, n, 256):
        b = jnp.asarray(keys[start:start + 256])
        s = np.asarray(t.probe_insert(b, jnp.ones(256, dtype=bool)))
        for k, sl in zip(range(start, start + 256), s):
            slots_before[k] = sl
    assert t.capacity > MIN_CAPACITY
    assert moves, "growth hooks must fire"
    assert t.sync_count() == n
    # every key still findable, exactly once
    got = np.asarray(t.lookup(jnp.asarray(keys), jnp.ones(n, dtype=bool)))
    assert (got >= 0).all()
    assert len(set(got.tolist())) == n


def test_full_table_contract():
    """reserve() grows before a batch could overflow MAX_LOAD."""
    t = DeviceHashTable(key_width=1)
    cap0 = t.capacity
    t.reserve(int(cap0 * 0.9))
    assert t.capacity >= cap0 * 2


# -- the ladder against the single loop ------------------------------------
#
# `probe_insert_counted` runs its claim rounds down a ladder of static
# sizes (hash_table._ladder). The single loop it replaced is kept here
# as the plain reference: every round over the whole batch, the claim
# array refilled each round. Table, slots, inserts and rounds must be
# the reference's bit for bit.

def _single_loop(state, batch_keys, valid):
    cap = state.capacity
    mask = jnp.int32(cap - 1)
    n = batch_keys.shape[0]
    row_ids = jnp.arange(n, dtype=jnp.int32)
    slot0 = (ht.hash_key_lanes(batch_keys).astype(jnp.int32)) & mask

    def cond(carry):
        _slot, done, _keys, _occ, steps, _ins = carry
        return (~jnp.all(done)) & (steps < cap)

    def body(carry):
        slot, done, keys, occ, steps, ins = carry
        done = done | ht._match_at(keys, occ, slot, batch_keys)
        want = ~done & ~occ[slot]
        claim_idx = jnp.where(want, slot, cap)
        claim = jnp.full((cap,), n, dtype=jnp.int32) \
            .at[claim_idx].min(row_ids, mode="drop")
        won = want & (claim[slot] == row_ids)
        scat = jnp.where(won, slot, cap)
        keys = keys.at[scat].set(batch_keys, mode="drop")
        occ = occ.at[scat].set(True, mode="drop")
        ins = ins + jnp.sum(won, dtype=jnp.int32)
        done = done | ht._match_at(keys, occ, slot, batch_keys)
        slot = jnp.where(done, slot, (slot + 1) & mask)
        return slot, done, keys, occ, steps + 1, ins

    init = (slot0, ~valid, state.keys, state.occ, jnp.int32(0),
            jnp.int32(0))
    slot, done, keys, occ, steps, ins = jax.lax.while_loop(cond, body, init)
    slots = jnp.where(valid, slot, jnp.int32(-1))
    return ht.TableState(keys, occ), slots, jnp.stack([ins, steps])


_single_loop_jit = jax.jit(_single_loop)
_laddered_jit = jax.jit(ht.probe_insert_counted)


def _random_keys(rng, rows, kw):
    return rng.integers(-2**31, 2**31 - 1, (rows, kw),
                        dtype=np.int64).astype(np.int32)


def _table_at(cap, kw, load, rng):
    """(state at `load` of `cap`, its resident keys), built by the
    reference so that the table under test owes nothing to the ladder."""
    resident = _random_keys(rng, int(cap * load), kw)
    state = make_state(cap, kw)
    if len(resident):
        state, _s, _b = _single_loop_jit(
            state, jnp.asarray(resident), jnp.ones(len(resident), bool))
    return state, resident


def _assert_same_as_single_loop(state, batch, valid):
    batch, valid = jnp.asarray(batch), jnp.asarray(valid)
    want = _single_loop_jit(state, batch, valid)
    got = _laddered_jit(state, batch, valid)
    for name, w, g in (("keys", want[0].keys, got[0].keys),
                       ("occ", want[0].occ, got[0].occ),
                       ("slots", want[1], got[1])):
        assert np.array_equal(np.asarray(w), np.asarray(g)), name
    n_inserted, rounds, row_rounds, rows = (int(v) for v in got[2])
    assert (n_inserted, rounds) == tuple(int(v) for v in want[2])
    n = batch.shape[0]
    assert rows == n
    assert row_rounds <= rounds * n
    if len(ht._ladder(n)) == 1:
        assert row_rounds == rounds * n
    return got


_FIRST = ht.LADDER_FLOOR * ht.LADDER_RATIO   # smallest n with a rung
_Q5 = 163_840       # q5's staged batch: four rungs under it
_RUNG = 65_536      # a batch with three rungs under it
_CAP = 1 << (2 * _RUNG - 1).bit_length()    # a table that holds two


def _mixed_batch(rng, resident, n, share_new=0.2):
    batch = resident[rng.integers(0, len(resident), n)] if len(resident) \
        else _random_keys(rng, n, 2)
    fresh = rng.random(n) < share_new
    batch[fresh] = _random_keys(rng, int(fresh.sum()), batch.shape[1])
    return batch


def _case_sizes(n):
    def build(rng):
        state, resident = _table_at(1 << 17, 2, 0.4, rng)
        return state, _mixed_batch(rng, resident, n), rng.random(n) < 0.9
    return build


def _case_load(load):
    def build(rng):
        cap, n = _CAP, _RUNG
        # the batch may add at most what MAX_LOAD leaves free
        state, resident = _table_at(cap, 3, load, rng)
        room = int(cap * ht.MAX_LOAD) - len(resident)
        share_new = min(0.3, 0.9 * room / n)
        return (state, _mixed_batch(rng, resident, n, share_new),
                np.ones(n, bool))
    return build


def _case_all_invalid(rng):
    state, resident = _table_at(1 << 15, 2, 0.5, rng)
    return (state, _mixed_batch(rng, resident, _RUNG),
            np.zeros(_RUNG, bool))


def _case_half_padding(rng):
    # the staged batch of a fused HOP aggregate: the second half of
    # the matrix was never valid
    n = _Q5
    state, resident = _table_at(1 << (2 * n - 1).bit_length(), 6, 0.6,
                                rng)
    return (state, _mixed_batch(rng, resident, n, 0.07),
            np.arange(n) < n // 2)


def _case_one_new_key(rng):
    state, _resident = _table_at(1 << 15, 2, 0.5, rng)
    key = _random_keys(rng, 1, 2)
    return state, np.repeat(key, _RUNG, axis=0), np.ones(_RUNG, bool)


def _case_hot_keys(rng):
    state, resident = _table_at(_CAP, 4, 0.55, rng)
    n = _RUNG
    batch = _mixed_batch(rng, resident, n, 0.1)
    hot = resident[rng.choice(len(resident), 50, replace=False)]
    on_hot = rng.random(n) < 0.5
    batch[on_hot] = hot[rng.integers(0, 50, int(on_hot.sum()))]
    return state, batch, rng.random(n) < 0.95


def _case_wraps_past_last_slot(rng):
    # a run of occupied slots up to the table's last one, and a batch
    # whose keys all start inside it: their chains wrap to slot 0
    cap, kw = 2 * _CAP, 2
    pool = _random_keys(rng, 1 << 22, kw)
    slot0 = np.asarray(ht.hash_key_lanes(jnp.asarray(pool))
                       .astype(jnp.int32)) & (cap - 1)
    tail = pool[slot0 >= cap - 64]
    resident, batch = tail[:600], tail[600:]
    state = make_state(cap, kw)
    state, _s, _b = _single_loop_jit(state, jnp.asarray(resident),
                                     jnp.ones(len(resident), bool))
    assert bool(np.asarray(state.occ)[-64:].all()) \
        and bool(np.asarray(state.occ)[:64].any())
    n = _RUNG
    rows = np.concatenate([batch, _random_keys(rng, n, kw)])[:n]
    return state, rows[rng.permutation(n)], np.ones(n, bool)


def _case_fills_to_max_load(rng):
    # every row a new key, and after the batch the table holds exactly
    # what the MAX_LOAD contract allows
    cap = 2 * _CAP
    n = _RUNG
    room = int(cap * ht.MAX_LOAD)
    state, _resident = _table_at(cap, 2, (room - n) / cap, rng)
    batch = _random_keys(rng, n, 2)
    return state, batch, np.ones(n, bool)


LADDER_CASES = {
    "n_below_the_floor": _case_sizes(ht.LADDER_FLOOR - 1),
    "n_below_the_first_rung": _case_sizes(_FIRST - 1),
    "n_on_the_first_rung": _case_sizes(_FIRST),
    "n_one_above_the_first_rung": _case_sizes(_FIRST + 1),
    "n_163840_on_the_fourth_rung": _case_sizes(_Q5),
    "n_one_above_the_fourth_rung": _case_sizes(_Q5 + 1),
    "n_69889_rungs_rounded_up_to_tiles": _case_sizes(69_889),
    "n_32768_runs_three_rungs": _case_sizes(32_768),
    "n_16384_a_cells_staged_batch": _case_sizes(16_384),
    "n_4096_a_mesh_shards_batch": _case_sizes(4_096),
    "n_65536_on_the_third_rung": _case_sizes(_RUNG),
    "load_0.1": _case_load(0.1),
    "load_0.5": _case_load(0.5),
    "load_0.69": _case_load(0.69),
    "all_rows_invalid": _case_all_invalid,
    "half_padding": _case_half_padding,
    "every_row_the_same_new_key": _case_one_new_key,
    "50_hot_keys_over_half_the_rows": _case_hot_keys,
    "chain_wraps_past_the_last_slot": _case_wraps_past_last_slot,
    "fills_the_table_to_max_load": _case_fills_to_max_load,
}


RUNGS_OF_CASE = {
    "n_32768_runs_three_rungs": 3,
    "n_16384_a_cells_staged_batch": 3,
    "n_4096_a_mesh_shards_batch": 2,
    "n_on_the_first_rung": 2,
}


@pytest.mark.parametrize("case", list(LADDER_CASES))
def test_ladder_is_the_single_loop_bit_for_bit(case):
    rng = np.random.default_rng(sorted(LADDER_CASES).index(case))
    state, batch, valid = LADDER_CASES[case](rng)
    stat = np.asarray(_assert_same_as_single_loop(state, batch, valid)[2])
    if case in RUNGS_OF_CASE:
        # the cells' own sizes step down, and pay for it in fewer rows
        assert len(ht._ladder(len(batch))) == RUNGS_OF_CASE[case]
        assert stat[1] > 2 and stat[2] < stat[1] * len(batch)
    if case == "all_rows_invalid":
        assert stat.tolist() == [0, 0, 0, len(batch)]
    if case == "half_padding":
        # the padding leaves the full size with the first collisions
        assert stat[2] < 6 * len(batch)
    if case == "every_row_the_same_new_key":
        # one winner, and its losers match it in the round after
        assert stat[0] == 1
    if case == "fills_the_table_to_max_load":
        occupied = int(np.asarray(_laddered_jit(
            state, jnp.asarray(batch), jnp.asarray(valid))[0].occ).sum())
        assert occupied == int(state.capacity * ht.MAX_LOAD)


def test_ladder_sizes_follow_n_alone():
    assert ht._ladder(ht.LADDER_FLOOR - 1) == (ht.LADDER_FLOOR - 1,)
    assert ht._ladder(_FIRST - 1) == (2_559,)
    assert ht._ladder(_FIRST) == (2_560, 640)
    assert ht._ladder(4_096) == (4_096, 1_024)
    assert ht._ladder(16_384) == (16_384, 4_096, 1_024)
    assert ht._ladder(32_768) == (32_768, 8_192, 2_048)
    assert ht._ladder(65_536) == (65_536, 16_384, 4_096, 1_024)
    assert ht._ladder(69_889) == (69_889, 17_536, 4_480, 1_152)
    assert ht._ladder(163_840) == (163_840, 40_960, 10_240, 2_560, 640)
    for n in (163_840, 1 << 19, 69_889, _RUNG + 1, _FIRST + 1):
        rungs = ht._ladder(n)
        assert rungs[0] == n and rungs[-1] >= ht.LADDER_FLOOR
        assert all(r % 128 == 0 for r in rungs[1:])
        assert all(a > b >= a // ht.LADDER_RATIO
                   for a, b in zip(rungs, rungs[1:]))


def test_grow_rehash_is_the_single_loops():
    """DeviceHashTable._grow: a probe_insert of `cap` rows, the old
    table's occupied slots valid, into a fresh table twice the size."""
    rng = np.random.default_rng(11)
    assert len(ht._ladder(_CAP)) > 2
    old, _resident = _table_at(_CAP, 2, 0.68, rng)
    want = _single_loop_jit(make_state(old.capacity * 2, 2), old.keys,
                            old.occ)
    t = DeviceHashTable(key_width=2, capacity=old.capacity)
    t.state = old
    moved = []
    t.on_grow(lambda slots, old_cap: moved.append(np.asarray(slots)))
    t._grow()
    assert t.capacity == old.capacity * 2
    assert np.array_equal(np.asarray(t.state.keys), np.asarray(want[0].keys))
    assert np.array_equal(np.asarray(t.state.occ), np.asarray(want[0].occ))
    assert np.array_equal(moved[0], np.asarray(want[1]))


def test_rebuild_live_with_a_mask_is_the_single_loops():
    """hash_agg._rebuild_live re-inserts the slots `live` keeps."""
    from risingwave_tpu.ops import hash_agg
    rng = np.random.default_rng(12)
    assert len(ht._ladder(_CAP)) > 1
    kernel = hash_agg.GroupedAggKernel(
        key_width=2, specs=[hash_agg.AggSpec(hash_agg.AggKind.COUNT)],
        capacity=_CAP)
    table, _resident = _table_at(_CAP, 2, 0.6, rng)
    state = kernel.state._replace(table=table)
    live = table.occ & jnp.asarray(rng.random(table.capacity) < 0.7)
    fills = tuple(f for _dt, f in hash_agg.dev_layout(kernel.specs))
    want = _single_loop_jit(make_state(table.capacity, 2), table.keys, live)
    got, n_live = jax.jit(hash_agg._rebuild_live, static_argnums=(2, 3))(
        state, live, table.capacity, fills)
    assert np.array_equal(np.asarray(got.table.keys),
                          np.asarray(want[0].keys))
    assert np.array_equal(np.asarray(got.table.occ), np.asarray(want[0].occ))
    assert int(n_live) == int(want[2][0]) == int(np.asarray(live).sum())
