"""HBM-resident open-addressing hash table with whole-batch jitted kernels.

Reference parity: the *role* of src/common/src/hash/key.rs (HashKey) plus
the in-memory halves of JoinHashMap (src/stream/src/executor/managed_state/
join/mod.rs:228) and the hash_agg group map (hash_agg.rs:67). The design is
NOT a port: the reference probes a CPU hashbrown map row by row; here the
whole chunk probes in parallel as one XLA computation.

Design (TPU-first):

- State is a pair of device arrays: ``keys: int64[cap, K]`` and
  ``occ: bool[cap]``. Capacity is a power of two; the jit cache is keyed by
  (cap, K, N) so growth or a new chunk bucket compiles once and is cached.
- ``probe_insert`` finds-or-inserts a whole batch in one call. Collisions
  *within* the batch (several rows landing on one empty slot) are resolved
  with a claim round: an int32 scatter-min elects one winner per slot, the
  winner writes its key, and every loser re-checks for a key match before
  advancing — so duplicate keys in one batch converge on one slot.
- A round's work follows the rows still unplaced, not the batch. Most
  rows are placed in the first two or three rounds and the other tens of
  rounds exist for the few on the longest chains, so the rounds run down
  a ladder of static sizes (``_ladder``: a function of ``n`` alone, so
  of the program's shapes): over the whole batch while more rows are
  unplaced than the next rung holds, then the unplaced rows are packed
  to the front of arrays of that rung (a cumsum, one scatter of row
  ids, a gather of their keys and slots) and the same rounds go on
  there. The election's priority stays the ORIGINAL row id, so table,
  slots and rounds are those of one loop over the whole batch, bit for
  bit. The claim array is still filled anew each round: on the chip a
  fill of ``cap`` words costs a small round less than un-marking it by
  the rows that marked it (PERF.md section 6, PR 34).
- Linear probing, stride 1: probe chains stay contiguous in HBM which is
  exactly what the vector units want; the host wrapper keeps load factor
  under ``MAX_LOAD`` so chains stay short.
- Deletion is logical (the aggregation layer zeroes its per-group counts);
  slots are reclaimed on growth rehash. Tombstone-free probing keeps the
  kernel branchless.
- All functions are pure: they take and return ``TableState``. The host
  wrapper ``DeviceHashTable`` owns growth scheduling with a *sync-free*
  occupancy upper bound (exact count is only synced at barriers, mirroring
  the "no host round-trip inside the hot loop" rule).

Keys are **int32 lanes** — the TPU has no native int64, and emulated
64-bit scatters are ~1000x slower (see ops/lanes.py). Callers map key
columns to lanes: 64-bit values split bijectively into (hi, lo) int32
pairs (lanes.split_i64); narrower ints cast; varchar keys hash on the host
(common/hash.py:hash_strings_host) and feed the hash lane — equality on the
lane is then *hash* equality, which is the same contract the reference's
``HashKey`` serialization provides for its Key8..Key256 fast paths.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.hash import hash_columns
from risingwave_tpu.utils import jaxtools

MAX_LOAD = 0.70          # grow when occupancy upper bound crosses this
MIN_CAPACITY = 1 << 10
# probe_insert's ladder (_ladder): each rung holds this many times fewer
# rows than the one above, and no rung is smaller than the floor, under
# which a round costs its fixed overhead whatever it holds (on a v5e
# about 0.05 ms a round, what 550 rows cost). A rung is also a `while`
# of its own in every program that inserts: 30-40 ms of tracing and
# lowering on the host, paid again at every growth rung of a table.
# Hence quarters: halves down to 256 rows are a fifth faster on the chip
# for a batch of 163,840 rows and cost a cell with a short preload a
# quarter more set-up (PERF.md section 6, PR 34). The schedule follows
# the batch's static row count and nothing else: a batch of fewer than
# LADDER_RATIO * LADDER_FLOOR = 2,560 rows has no rung that would hold
# the floor and keeps one loop; every other batch steps down.
LADDER_RATIO = 4
LADDER_FLOOR = 640


class TableState(NamedTuple):
    """Functional hash-table state (all device arrays)."""

    keys: jnp.ndarray    # int32[cap, K]
    occ: jnp.ndarray     # bool[cap]

    @property
    def capacity(self) -> int:
        return int(self.keys.shape[0])

    @property
    def key_width(self) -> int:
        return int(self.keys.shape[1])


def make_state(capacity: int, key_width: int) -> TableState:
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    return TableState(
        keys=jnp.zeros((capacity, key_width), dtype=jnp.int32),
        occ=jnp.zeros((capacity,), dtype=bool),
    )


def hash_key_lanes(batch_keys: jnp.ndarray) -> jnp.ndarray:
    """uint32[N] hash of int32[N, K] key lanes (shared with dispatch)."""
    cols = [batch_keys[:, i] for i in range(batch_keys.shape[1])]
    return hash_columns(cols)


def _match_at(keys: jnp.ndarray, occ: jnp.ndarray, slot: jnp.ndarray,
              batch_keys: jnp.ndarray) -> jnp.ndarray:
    return occ[slot] & jnp.all(keys[slot] == batch_keys, axis=1)


def probe_insert(state: TableState, batch_keys: jnp.ndarray,
                 valid: jnp.ndarray
                 ) -> Tuple[TableState, jnp.ndarray, jnp.ndarray]:
    """Find-or-insert every valid row of the batch.

    Returns (new_state, slots int32[N], n_inserted int32). Rows with
    ``valid=False`` get slot -1 and do not touch the table. The caller must
    guarantee a free slot exists for every valid row (load-factor contract
    enforced by DeviceHashTable) — under that contract the loop terminates
    before ``cap`` steps.
    """
    table, slots, stat = probe_insert_counted(state, batch_keys, valid)
    return table, slots, stat[0]


def _ladder(n: int) -> Tuple[int, ...]:
    """The static sizes the claim loop's row arrays step down through
    for a batch of `n` rows: `n`, then rungs a `LADDER_RATIO`-th of the
    one above (rounded up to whole 128-row tiles) while they hold at
    least `LADDER_FLOOR` rows. A batch of fewer than `LADDER_RATIO *
    LADDER_FLOOR` rows gets `(n,)`: the single loop."""
    rungs = [n]
    while rungs[-1] // LADDER_RATIO >= LADDER_FLOOR:
        rungs.append(-(-rungs[-1] // (LADDER_RATIO * 128)) * 128)
    return tuple(rungs)


def _claim_rounds(rows, table, books, left, below: int, n: int):
    """Rounds of the claim loop over the row arrays it is handed, while
    more than `below` of their rows are unplaced. `rows` = (slot, done,
    key lanes, original row id) of one length, the rung's; `table` =
    (keys, occ); `books` = (rounds, inserted, row_rounds) so far;
    `left` = rows of `rows` not done. The election's priority is the
    ORIGINAL row id (`n` = no row), so a round places the rows it would
    place over the whole batch, whatever rung holds them."""
    _slot, _done, lanes, rid = rows
    cap = table[0].shape[0]
    mask = jnp.int32(cap - 1)
    m = rid.shape[0]

    def cond(carry):
        _slot, _done, _table, books, left = carry
        return (left > below) & (books[0] < cap)

    def body(carry):
        slot, done, (keys, occ), (steps, ins, worked), _left = carry
        # 1) key already present (from the table or an earlier round)?
        done = done | _match_at(keys, occ, slot, lanes)
        # 2) claim round for empty slots: scatter-min elects one winner.
        want = ~done & ~occ[slot]
        claim_idx = jnp.where(want, slot, cap)  # cap = out-of-bounds, dropped
        claim = jnp.full((cap,), n, dtype=jnp.int32) \
            .at[claim_idx].min(rid, mode="drop")
        won = want & (claim[slot] == rid)
        scat = jnp.where(won, slot, cap)
        keys = keys.at[scat].set(lanes, mode="drop")
        occ = occ.at[scat].set(True, mode="drop")
        ins = ins + jnp.sum(won, dtype=jnp.int32)
        # 3) re-check: winners match their own write; a loser whose key was
        #    just written by its winner matches too (no duplicate chains).
        done = done | _match_at(keys, occ, slot, lanes)
        slot = jnp.where(done, slot, (slot + 1) & mask)
        left = jnp.sum(~done, dtype=jnp.int32)
        return (slot, done, (keys, occ), (steps + 1, ins, worked + m),
                left)

    slot, done, table, books, left = jax.lax.while_loop(
        cond, body, (rows[0], rows[1], table, books, left))
    return (slot, done, lanes, rid), table, books, left


def _pack_unplaced(rows, size: int, n: int):
    """The unplaced rows of `rows`, in order, at the front of arrays of
    `size` rows (which holds them: the stage above ran until it did);
    the rest of the buffer is rows already done that belong to no row
    of the batch (row id `n`)."""
    slot, done, lanes, rid = rows
    m = rid.shape[0]
    live = ~done
    at = jnp.where(live, jnp.cumsum(live, dtype=jnp.int32) - 1, size)
    src = jnp.full((size,), m, dtype=jnp.int32) \
        .at[at].set(jnp.arange(m, dtype=jnp.int32), mode="drop")
    held = src < m
    src = jnp.where(held, src, 0)
    return (slot[src], ~held, lanes[src], jnp.where(held, rid[src], n))


def probe_insert_counted(state: TableState, batch_keys: jnp.ndarray,
                         valid: jnp.ndarray
                         ) -> Tuple[TableState, jnp.ndarray, jnp.ndarray]:
    """``probe_insert`` with its loop's books: the third result is
    int32[4] = [n_inserted, rounds, row_rounds, rows]. A round is one
    pass of the claim loop over the rows still unplaced, held in arrays
    of a static size (a rung of `_ladder(n)`); a batch needs as many
    rounds as its longest probe chain, and a key that many rows of the
    batch share costs one more where it is new (the losers of its claim
    match it in the round after). `row_rounds` is the sum over the
    rounds of the rung each ran at (what the device paid), `rows` the
    batch's `n`: a single loop over the whole batch would read
    `row_rounds` = rounds x rows. `n` rides with the books because only
    the program knows it: a prelude fused into the step (a HOP) makes
    several rows of each row the host staged. jaxtools.PendingCounters
    takes the books wherever it takes the count.

    The rounds start over the whole batch; once a rung holds the rows
    still unplaced, they are packed to the front of arrays of that size
    with their original row ids and the same rounds go on there, and so
    down the ladder. A stage whose next rung already holds them runs no
    round, so a batch that is mostly padding leaves the full size before
    round one. The table, the slots and the rounds are those of the
    single loop, bit for bit."""
    assert batch_keys.dtype == jnp.int32, \
        "keys must be int32 lanes (lanes.split_i64 for 64-bit values)"
    cap = state.capacity
    n = batch_keys.shape[0]
    rungs = _ladder(n)
    slot0 = (hash_key_lanes(batch_keys).astype(jnp.int32)) & jnp.int32(cap - 1)
    rows = (slot0, ~valid, batch_keys, jnp.arange(n, dtype=jnp.int32))
    table = (state.keys, state.occ)
    books = (jnp.int32(0),) * 3
    left = jnp.sum(valid, dtype=jnp.int32)
    found = []               # (slots, original row ids) of each rung
    for size, below in zip(rungs, rungs[1:] + (0,)):
        if found:
            rows = _pack_unplaced(rows, size, n)
        rows, table, books, left = _claim_rounds(rows, table, books, left,
                                                 below, n)
        found.append((rows[0], rows[3]))
    slot = found[0][0]
    for at, rid in found[1:]:
        slot = slot.at[rid].set(at, mode="drop")      # row id n: dropped
    slots = jnp.where(valid, slot, jnp.int32(-1))
    rounds, ins, worked = books
    return (TableState(*table), slots,
            jnp.stack([ins, rounds, worked, jnp.int32(n)]))


def lookup(state: TableState, batch_keys: jnp.ndarray,
           valid: jnp.ndarray) -> jnp.ndarray:
    """Slots of existing keys; -1 for absent/invalid rows. Read-only."""
    assert batch_keys.dtype == jnp.int32, \
        "keys must be int32 lanes (lanes.split_i64 for 64-bit values)"
    cap = state.capacity
    mask = jnp.int32(cap - 1)
    slot0 = (hash_key_lanes(batch_keys).astype(jnp.int32)) & mask
    found0 = jnp.zeros(batch_keys.shape[0], dtype=bool)

    def cond(carry):
        _slot, done, _found, steps = carry
        return (~jnp.all(done)) & (steps < cap)

    def body(carry):
        slot, done, found, steps = carry
        m = _match_at(state.keys, state.occ, slot, batch_keys)
        empty = ~state.occ[slot]
        found = found | (~done & m)
        done = done | m | empty          # empty slot ⇒ key absent
        slot = jnp.where(done, slot, (slot + 1) & mask)
        return slot, done, found, steps + 1

    init = (slot0, ~valid, found0, jnp.int32(0))
    slot, _done, found, _steps = jax.lax.while_loop(cond, body, init)
    return jnp.where(valid & found, slot, jnp.int32(-1))


_probe_insert_jit = jaxtools.instrumented_jit(
    probe_insert, "hash_table.probe_insert", donate_argnums=(0,))
_lookup_jit = jaxtools.instrumented_jit(lookup, "hash_table.lookup")


class DeviceHashTable:
    """Host wrapper: owns growth scheduling and the sync-free load bound.

    ``probe_insert`` never syncs; occupancy is tracked as an upper bound
    (each batch can insert at most its row count). ``sync_count()`` — called
    at barriers, where a device round-trip is already happening — collapses
    the bound to the true count.
    """

    def __init__(self, key_width: int, capacity: int = MIN_CAPACITY,
                 grow_floor=None):
        self.state = make_state(max(capacity, MIN_CAPACITY), key_width)
        self._counters = jaxtools.PendingCounters()
        # `grow_floor()`: the least capacity (a power of two) a rehash
        # may grow to; None: a rehash doubles
        self._grow_floor = grow_floor

    @property
    def capacity(self) -> int:
        return self.state.capacity

    def _count_upper_bound(self) -> int:
        return self._counters.bound()

    def probe_insert(self, batch_keys: jnp.ndarray,
                     valid: jnp.ndarray) -> jnp.ndarray:
        n = int(batch_keys.shape[0])
        self.reserve(n)
        self.state, slots, ins = _probe_insert_jit(
            self.state, batch_keys, valid)
        self._counters.push(ins, n)
        return slots

    def lookup(self, batch_keys: jnp.ndarray,
               valid: jnp.ndarray) -> jnp.ndarray:
        return _lookup_jit(self.state, batch_keys, valid)

    def reserve(self, n: int) -> bool:
        """Grow (rehash) until `n` more insertions respect MAX_LOAD.

        Returns True if a rehash happened (slots from before are invalid —
        callers that cache slots must subscribe via on_grow).
        """
        grew = False
        self._counters.drain_ready()
        while self._count_upper_bound() + n > MAX_LOAD * self.capacity:
            if self._counters.pending_rows():
                self.sync_count()      # bound too loose? sync before paying
                if self._count_upper_bound() + n <= MAX_LOAD * self.capacity:
                    break              # for a rehash we may not need
            self._grow()
            grew = True
        return grew

    def _grow(self) -> None:
        old = self.state
        cap = old.capacity * 2
        if self._grow_floor is not None:
            cap = max(cap, self._grow_floor())
        new = make_state(cap, old.key_width)
        # Rehash: one batched probe_insert of every occupied slot.
        occ = old.occ
        new, slots, ins = _probe_insert_jit(new, old.keys, occ)
        self.state = new
        for hook in getattr(self, "_on_grow", []):
            hook(slots, old.capacity)

    def on_grow(self, hook) -> None:
        """Register `hook(old_to_new_slots, old_capacity)` called on rehash."""
        if not hasattr(self, "_on_grow"):
            self._on_grow = []
        self._on_grow.append(hook)

    def sync_count(self) -> int:
        """Collapse the occupancy bound to the exact device count (syncs;
        the DMAs were started at dispatch, so the wait is short)."""
        return self._counters.drain_all()
