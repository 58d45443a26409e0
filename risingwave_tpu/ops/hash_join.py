"""Device-resident join-side state (the q8 kernel's matcher).

Reference parity: JoinHashMap (src/stream/src/executor/managed_state/
join/mod.rs:228) — join key → multiset of rows — and the probe loop of
hash_join.rs:990 (``eq_join_oneside``). TPU re-design: the reference
walks a CPU hashbrown map row by row; here MATCHING runs on device as
whole-batch kernels, while row payloads stay in host arenas (varchar can
never live in HBM anyway — the device's job is the equality/match
structure, the host's job is materialization):

    table    DeviceHashTable   join-key lanes → key slot
    head     int32[cap]        key slot → position of the key's newest
                               run in `store` (-1: no row)
    store    int32[row_cap]    row refs in link order, behind a bump
                               pointer (`fill`): the rows one batch
                               gave one key are a RUN, contiguous
    run_len  int32[row_cap]    at a run's first position: its rows
    run_next int32[row_cap]    at a run's first position: the first
                               position of the key's next older run
                               (-1 end)
    ins_seq  int32[row_cap]    by ref: message sequence that inserted
                               the row
    del_seq  int32[row_cap]    by ref: message sequence that deleted
                               it (MAX=∞)

SEQUENCE-VERSIONED state (the load-bearing TPU design choice): every
message carries a monotone sequence number, and a probe at sequence s
sees exactly the rows with ``ins_seq < s <= del_seq`` — i.e. the state
as of message s, regardless of when the probe's RESULT is read. That
makes probes pure functions of (end-of-epoch state, s), so the host can
dispatch every chunk's probe asynchronously, fetch ALL results in one
DMA round at the barrier, and safely RE-dispatch any probe whose pair
buffer overflowed — per-epoch instead of per-chunk synchronization
(what a blocking read costs on a local chip is not measured).
(The reference's hashbrown map reads are synchronous
CPU lookups and need none of this.)

- ``insert``: whole-batch: one key probe-insert, then one chain-link
  kernel. A stable sort by key slot puts the rows a batch gives one key
  side by side; they go into `store` in that order as one run, linked
  in front of the key's older runs — no per-row host loop.
- ``delete``: sets del_seq. A run keeps the row until a rebuild;
  probes at later sequences skip it.
- ``probe``: ONE fused kernel. A ``lax.while_loop`` steps the probed
  keys' RUNS (its trip count is the most runs any probed key has, i.e.
  the batches that touched it, not its rows), the rows of the runs met
  are expanded in parallel into a candidate buffer of static size,
  each is tested against its probe row's sequence, and one cumsum
  places the (probe_row, matched_ref) pairs, all returned as one
  packed matrix with a header.

All lanes int32 (ops/lanes.py rationale).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import next_pow2
from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.utils import jaxtools
from risingwave_tpu.utils.ledger import LEDGER


I32_MAX = (1 << 31) - 1


class ChainState(NamedTuple):
    """Functional chain arrays (the non-key half of a join side)."""

    head: jnp.ndarray      # int32[cap]: newest run's position, -1 none
    store: jnp.ndarray     # int32[row_cap]: row refs in link order
    run_len: jnp.ndarray   # int32[row_cap], at a run's first position
    run_next: jnp.ndarray  # int32[row_cap], at a run's first position
    fill: jnp.ndarray      # int32[]: positions of `store` in use
    ins_seq: jnp.ndarray   # int32[row_cap] (I32_MAX = never inserted)
    del_seq: jnp.ndarray   # int32[row_cap] (I32_MAX = live)


# the fields of a ChainState that grow with the rows
ROW_ARRAYS = ("store", "run_len", "run_next", "ins_seq", "del_seq")


def empty_chains(key_capacity: int, row_capacity: int) -> ChainState:
    return ChainState(
        head=jnp.full(key_capacity, -1, dtype=jnp.int32),
        store=jnp.full(row_capacity, -1, dtype=jnp.int32),
        run_len=jnp.zeros(row_capacity, dtype=jnp.int32),
        run_next=jnp.full(row_capacity, -1, dtype=jnp.int32),
        fill=jnp.int32(0),
        ins_seq=jnp.full(row_capacity, I32_MAX, dtype=jnp.int32),
        del_seq=jnp.full(row_capacity, I32_MAX, dtype=jnp.int32))


def link_rows(chains: ChainState, slots: jnp.ndarray,
              row_refs: jnp.ndarray, vis: jnp.ndarray,
              cap: int, seq: jnp.ndarray = None) -> ChainState:
    """Link a batch of rows in front of their keys' older rows.

    `slots` comes from the key table's probe_insert for the same batch.
    A stable sort by slot puts the rows that share a key side by side,
    in BATCH ORDER; in that order they take the next positions of
    `store`, so the rows a batch gives a key are one run, and the run
    becomes the key's newest. A ref is linked once (refs are bump
    allocated and never reused before a rebuild), so `store` never
    holds more than row_cap rows. `seq` may be a per-row vector (epoch
    batching: each row carries its message sequence) or a scalar."""
    row_cap = int(chains.ins_seq.shape[0])
    n = slots.shape[0]
    skey = jnp.where(vis & (slots >= 0), slots, cap)
    order = jnp.argsort(skey, stable=True)
    s = skey[order]
    r = row_refs[order]
    valid = s < cap                             # a prefix of the order
    first = jnp.concatenate([jnp.ones(1, bool), s[1:] != s[:-1]])
    last = jnp.concatenate([s[1:] != s[:-1], jnp.ones(1, bool)])
    idx = jnp.arange(n, dtype=jnp.int32)
    pos = chains.fill + idx
    # a run's rows: from its first row to the next `last` at or after it
    run_end = jax.lax.cummin(jnp.where(last, idx, jnp.int32(n)),
                             reverse=True)
    at_first = jnp.where(valid & first, pos, row_cap)
    store = chains.store.at[jnp.where(valid, pos, row_cap)].set(
        r, mode="drop")
    run_len = chains.run_len.at[at_first].set(
        run_end - idx + 1, mode="drop")
    run_next = chains.run_next.at[at_first].set(
        chains.head[jnp.minimum(s, cap - 1)], mode="drop")
    head = chains.head.at[jnp.where(valid & first, s, cap)].set(
        pos, mode="drop")
    if seq is None:
        sv = jnp.int32(0)
    elif jnp.ndim(seq) == 0:
        sv = seq
    else:
        sv = seq[order]                         # per-row seq follows r
    ins = chains.ins_seq.at[jnp.where(valid, r, row_cap)].set(
        sv, mode="drop")
    return ChainState(head, store, run_len, run_next,
                      chains.fill + jnp.sum(valid, dtype=jnp.int32),
                      ins, chains.del_seq)


def tombstone_rows(chains: ChainState, row_refs: jnp.ndarray,
                   vis: jnp.ndarray,
                   seq: jnp.ndarray = None) -> ChainState:
    """Tombstone deletes; probes at sequences > seq skip the node."""
    row_cap = int(chains.del_seq.shape[0])
    del_ = chains.del_seq.at[jnp.where(vis, row_refs, row_cap)].set(
        jnp.int32(0) if seq is None else seq, mode="drop")
    return chains._replace(del_seq=del_)


# rows of a probe matrix before its degree and pair rows:
#   [pairs in the buffer, rows of the longest chain probed]
#   [candidates of the whole probe, steps the walk took]
HEADER_ROWS = 2


class _Matches(NamedTuple):
    """What `_match_runs` found in one page of a probe's candidates."""

    probe: jnp.ndarray     # int32[cap]: probe row of each pair, -1 pad
    ref: jnp.ndarray       # int32[cap]: matched ref of each pair
    deg: jnp.ndarray       # int32[n]: pairs per probe row, in the page
    header: jnp.ndarray    # int32[HEADER_ROWS, 2]


def _match_runs(table: ht.TableState, chains: ChainState,
                key_lanes: jnp.ndarray, vis: jnp.ndarray,
                seq: jnp.ndarray, cap: int, start) -> _Matches:
    """The probe both kernels share: look the keys up, step their runs,
    expand the runs' rows in parallel, keep the visible ones.

    A probe's CANDIDATES are the rows of the probed keys' runs, visible
    or not, by probe row and within a row newest run first (a chain,
    front to back). This call works candidates ``[start, start + cap)``
    (``start`` a traced scalar, ``cap`` a shape) and packs the pairs
    among them at the front of the buffer, in candidate order, so pages
    read one after the other give the probe's pairs in order.

    The walks step a RUN at a time: the first sums each row's
    candidates, the second writes, at the page position of a run's
    first candidate, the probe row and the `store` position it stands
    for. A running maximum over the written positions then tells every
    candidate of the page its run, and nothing else is a loop."""
    n = key_lanes.shape[0]
    row_cap = chains.store.shape[0]
    slots = ht.lookup(table, key_lanes, vis)
    cur0 = jnp.where(slots >= 0,
                     chains.head[jnp.maximum(slots, 0)], jnp.int32(-1))
    start = jnp.asarray(start, dtype=jnp.int32)

    def cond(c):
        return jnp.any(c[0] >= 0)

    def run_of(cur):
        safe = jnp.maximum(cur, 0)
        live = cur >= 0
        return (jnp.where(live, chains.run_next[safe], jnp.int32(-1)),
                jnp.where(live, chains.run_len[safe], jnp.int32(0)))

    def count(c):
        cur, cnt, steps = c
        nxt, ln = run_of(cur)
        return nxt, cnt + ln, steps + 1

    _cur, cnt, steps = jax.lax.while_loop(
        cond, count, (cur0, jnp.zeros(n, dtype=jnp.int32), jnp.int32(0)))
    candidates = jnp.sum(cnt, dtype=jnp.int32)
    row_ids = jnp.arange(n, dtype=jnp.int32)

    def record(c):
        cur, at, run_row, run_pos = c
        nxt, ln = run_of(cur)
        # `at`: the page position of the run's first candidate
        hit = (ln > 0) & (at < cap) & (at + ln > 0)
        dest = jnp.where(hit, jnp.maximum(at, 0), cap)
        run_row = run_row.at[dest].set(row_ids, mode="drop")
        run_pos = run_pos.at[dest].set(cur - jnp.minimum(at, 0),
                                       mode="drop")
        return nxt, at + ln, run_row, run_pos

    _cur, _at, run_row, run_pos = jax.lax.while_loop(
        cond, record,
        (cur0, jnp.cumsum(cnt, dtype=jnp.int32) - cnt - start,
         jnp.full(cap, -1, dtype=jnp.int32),
         jnp.zeros(cap, dtype=jnp.int32)))
    j = jnp.arange(cap, dtype=jnp.int32)
    seg = jax.lax.cummax(jnp.where(run_row >= 0, j, jnp.int32(-1)))
    real = (seg >= 0) & (j < candidates - start)
    safe = jnp.maximum(seg, 0)
    row = jnp.where(real, run_row[safe], jnp.int32(0))
    ref = chains.store[jnp.clip(run_pos[safe] + j - safe, 0,
                                row_cap - 1)]
    ref = jnp.where(real, ref, jnp.int32(0))
    at_seq = seq if jnp.ndim(seq) == 0 else seq[row]
    m = real & (chains.ins_seq[ref] < at_seq) \
        & (chains.del_seq[ref] >= at_seq)
    kept = jnp.cumsum(m, dtype=jnp.int32)
    dest = jnp.where(m, kept - 1, cap)
    out_probe = jnp.full(cap, -1, dtype=jnp.int32).at[dest].set(
        row, mode="drop")
    out_ref = jnp.full(cap, -1, dtype=jnp.int32).at[dest].set(
        ref, mode="drop")
    deg = jnp.zeros(n, dtype=jnp.int32).at[
        jnp.where(m, row, n)].add(1, mode="drop")
    header = jnp.stack([
        jnp.stack([kept[-1], jnp.max(cnt)]),
        jnp.stack([candidates, steps])])
    return _Matches(out_probe, out_ref, deg, header)


def _packed(header: jnp.ndarray, width: int, *blocks) -> jnp.ndarray:
    """[header | blocks...] as one matrix ``width`` columns wide."""
    head = jnp.zeros((HEADER_ROWS, width), dtype=jnp.int32) \
        .at[:, :2].set(header)
    return jnp.concatenate((head,) + blocks, axis=0)


def probe_pairs(table: ht.TableState, chains: ChainState,
                key_lanes: jnp.ndarray, vis: jnp.ndarray,
                seq: jnp.ndarray, out_cap: int,
                with_degrees: bool = True) -> jnp.ndarray:
    """Fused walk + expansion + emit: ONE kernel, ONE packed d2h array.

    Returns int32[HEADER_ROWS + n + out_cap, 2]: the header
    (HEADER_ROWS); rows of degrees (col 0), one a probe row; then
    (probe_row_idx, ref) pairs, packed. A separate degrees fetch + host
    cumsum + emit fetch would be three round-trips per chunk; this is
    one (the host retries with a larger out_cap if the header says the
    probe's candidates outgrew the buffer).

    `seq` may be a per-row vector (epoch batching: every row probes at
    its own message sequence). `with_degrees=False` drops the n degree
    rows from the output — inner joins never read them, so they are
    not fetched.
    """
    n = key_lanes.shape[0]
    m = _match_runs(table, chains, key_lanes, vis, seq, out_cap, 0)
    pairs = jnp.stack([m.probe, m.ref], axis=1)
    if not with_degrees:
        return _packed(m.header, 2, pairs)
    degs = jnp.stack([m.deg, jnp.zeros(n, dtype=jnp.int32)], axis=1)
    return _packed(m.header, 2, degs, pairs)


_link_jit = jaxtools.instrumented_jit(
    link_rows, "hash_join.link", donate_argnums=(0,),
    static_argnums=(4,))
_tombstone_jit = jaxtools.instrumented_jit(
    tombstone_rows, "hash_join.tombstone", donate_argnums=(0,))
_probe_pairs_jit = jaxtools.instrumented_jit(
    probe_pairs, "hash_join.probe", static_argnums=(5, 6))


# -- epoch batching --------------------------------------------------------
# One packed aux matrix rides along with the upload matrix (key lanes
# concatenated with payload lanes) and feeds BOTH the apply and the
# probe of a whole epoch: the executor concatenates every chunk of the
# epoch and ships each side as exactly two uploads + one apply dispatch
# + one probe dispatch (whether transfer count or compute bounds a
# local chip is not measured).
AUX_INS_REF, AUX_DEL_REF, AUX_FLAGS, AUX_SEQ = 0, 1, 2, 3
FLAG_PROBE, FLAG_INS, FLAG_DEL = 1, 2, 4
# probe row's op sign is negative (DELETE / UPDATE_DELETE) — the
# device-side degree scatter needs it (see epoch_probe)
FLAG_NEG = 8


def epoch_apply(table: ht.TableState, chains: ChainState,
                pay: jnp.ndarray, up: jnp.ndarray, aux: jnp.ndarray,
                key_width: int):
    """Apply a whole epoch's inserts + tombstones in one dispatch.

    ``up`` is [key_lanes | payload_lanes] int32[n, key_width + P]: the
    payload lanes of inserted rows scatter into the device payload
    store in the SAME dispatch that links their chains. Rows carry
    their message sequence in aux[:, AUX_SEQ]; sequence visibility
    makes application order irrelevant (probes reconstruct any
    interleaving exactly), so one batched apply per side per epoch is
    semantically identical to per-chunk applies."""
    key_lanes = up[:, :key_width]
    flags = aux[:, AUX_FLAGS]
    ins_mask = (flags & FLAG_INS) != 0
    del_mask = (flags & FLAG_DEL) != 0
    seq = aux[:, AUX_SEQ]
    # ins: [n_inserted, the claim loop's books] (PendingCounters)
    table2, slots, ins = ht.probe_insert_counted(table, key_lanes,
                                                 ins_mask)
    chains2 = link_rows(chains, slots, aux[:, AUX_INS_REF], ins_mask,
                        table2.capacity, seq)
    chains2 = tombstone_rows(chains2, aux[:, AUX_DEL_REF], del_mask, seq)
    if pay.shape[1]:
        row_cap = pay.shape[0]
        dest = jnp.where(ins_mask, aux[:, AUX_INS_REF],
                         jnp.int32(row_cap))
        pay = pay.at[dest].set(up[:, key_width:], mode="drop")
    return table2, chains2, pay, ins


_epoch_apply_jit = jaxtools.instrumented_jit(
    epoch_apply, "hash_join.epoch_apply", donate_argnums=(0, 1, 2),
    static_argnums=(5,))


def epoch_probe(table: ht.TableState, chains: ChainState,
                pay: jnp.ndarray, deg_self: jnp.ndarray,
                deg_sink: jnp.ndarray, up: jnp.ndarray,
                aux: jnp.ndarray, key_width: int, out_cap: int,
                with_degrees: bool, start=0):
    """Probe a whole epoch's rows (each at its own sequence) in one
    dispatch against post-apply state — exact by sequence visibility.

    ``start`` (a traced scalar, not a shape) is the first CANDIDATE
    this dispatch works (`_match_runs`): the pairs among candidates
    [start, start + out_cap) land packed in the buffer, and the header
    carries the candidates of the whole probe. A probe whose
    candidates outgrow the largest buffer is read in pages of one
    program instead of a larger program per size
    (JoinSideKernel.PROBE_CAP_TOP). Degree maintenance reads the
    buffer, so ``with_degrees`` pages never.

    Fused walk + expansion + emit + payload gather + degree
    maintenance: ONE kernel, ONE packed d2h matrix of width
    W = 2 + P + (1 if with_degrees). Layout:

      HEADER_ROWS rows           [pairs in the buffer, rows of the
                                 longest chain probed (visible or
                                 not)], [candidates, steps the walk
                                 over the runs took]
      n rows (deg only)          per-probe-row match degrees (col 0)
      out_cap pair rows          [probe_row, ref, pay lanes..., old]

    ``pay`` is THIS side's payload store: each matched ref's lanes are
    gathered ON DEVICE, so the host materializes matched
    rows from the one packed fetch instead of arena-gathering
    column-by-column per chunk. With ``with_degrees``:

    - ``old`` is deg_self[ref] BEFORE this epoch's updates — the host
      replays per-chunk degree transitions from it without keeping a
      host degrees array;
    - deg_self gets one scatter-add of every pair's probe-row sign
      (FLAG_NEG), i.e. the stored side's degree transitions;
    - deg_sink (the PROBING side's degree array) gets one scatter-add
      of each inserted row's probe-time match count at its ref — the
      initial degree of rows stored this epoch. Adds commute, so the
      two sides' probes may run in either order; fresh refs start at
      zero by the bump-allocation invariant.

    deg arrays are NOT donated: an overflow redispatch re-runs this
    exact computation from the original arrays, and the host installs
    the outputs only after a successful collect."""
    key_lanes = up[:, :key_width]
    flags = aux[:, AUX_FLAGS]
    vis = (flags & FLAG_PROBE) != 0
    n = key_lanes.shape[0]
    P = pay.shape[1]
    row_cap = chains.store.shape[0]
    m = _match_runs(table, chains, key_lanes, vis, aux[:, AUX_SEQ],
                    out_cap, start)
    pair_mask = m.ref >= 0
    safe = jnp.maximum(m.ref, 0)
    parts = [m.probe[:, None], m.ref[:, None]]
    if P:
        parts.append(jnp.where(pair_mask[:, None], pay[safe], 0))
    if with_degrees:
        parts.append(jnp.where(pair_mask, deg_self[safe], 0)[:, None])
    pairs = jnp.concatenate(parts, axis=1)
    W = pairs.shape[1]
    if with_degrees:
        # stored-side transitions: one scatter-add of pair signs
        sgn_row = jnp.where((flags & FLAG_NEG) != 0,
                            jnp.int32(-1), jnp.int32(1))
        pair_sgn = jnp.where(
            pair_mask, sgn_row[jnp.maximum(m.probe, 0)], 0)
        deg_self = deg_self.at[
            jnp.where(pair_mask, m.ref, row_cap)].add(
                pair_sgn, mode="drop")
        # probing-side initial degrees: probe-time count at each
        # inserted row's ref (add, not set — commutes with the other
        # probe's transition adds; fresh slots are zero)
        ins_mask = (flags & FLAG_INS) != 0
        sink_cap = deg_sink.shape[0]
        deg_sink = deg_sink.at[
            jnp.where(ins_mask, aux[:, AUX_INS_REF], sink_cap)].add(
                jnp.where(ins_mask, m.deg, 0), mode="drop")
        degs = jnp.zeros((n, W), dtype=jnp.int32).at[:, 0].set(m.deg)
        return _packed(m.header, W, degs, pairs), deg_self, deg_sink
    # degree-free (inner) probes return only the matrix: passing the
    # untouched deg arrays through would force XLA output copies
    return _packed(m.header, W, pairs)


_epoch_probe_jit = jaxtools.instrumented_jit(
    epoch_probe, "hash_join.epoch_probe", static_argnums=(7, 8, 9))


def _masked_scatter(arr: jnp.ndarray, refs: jnp.ndarray,
                    vis: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Masked write-by-ref into a donated device array (payload rows
    AND degree values share this one scatter; masked rows drop on the
    out-of-range sentinel)."""
    cap = arr.shape[0]
    dest = jnp.where(vis, refs, jnp.int32(cap))
    return arr.at[dest].set(vals, mode="drop")


_masked_scatter_jit = jaxtools.instrumented_jit(
    _masked_scatter, "hash_join.masked_scatter", donate_argnums=(0,))


class BatchRung:
    """The row count of a bulk program that must not follow the data:
    a state rebuilt, compacted or expired behind a watermark works a
    batch as large as the rows that happen to live or die, and a
    program per size the data ever takes is a compile at any barrier,
    for as long as the view runs (a flat state, cleaned every barrier,
    is the case that shows it). So such a batch runs at the powers of
    four from FLOOR up to TOP and keeps to the largest rung it has
    needed: a smaller batch is padded up to it with masked rows, a
    larger one takes it a rung up, one above TOP is cut into pages of
    TOP. The fused chain's ladder (stream/executors/fused.py) and the
    probe's pages (PROBE_CAP_TOP), for the cleaning paths."""

    FLOOR = 1 << 6
    TOP = 1 << 16

    def __init__(self) -> None:
        self.rows = self.FLOOR

    def pages(self, n: int):
        """(lo, hi) runs that cover ``n`` rows, each at most the rung
        and to be padded up to it (``self.rows``, raised here where
        ``n`` asks for it)."""
        while self.rows < min(n, self.TOP):
            self.rows <<= 2
        return [(lo, min(lo + self.rows, n))
                for lo in range(0, n, self.rows)]

    def padded(self, a: np.ndarray, lo: int, hi: int,
               fill=0) -> np.ndarray:
        """Rows ``lo:hi`` of ``a``, padded with ``fill`` to the rung."""
        out = np.full((self.rows,) + a.shape[1:], fill, dtype=a.dtype)
        out[:hi - lo] = a[lo:hi]
        return out

    def mask(self, lo: int, hi: int) -> np.ndarray:
        m = np.zeros(self.rows, dtype=bool)
        m[:hi - lo] = True
        return m


def make_prelude_epoch_jits(prelude, label: str):
    """Jitted epoch apply/probe with a fused input run inlined: the
    upload is the RAW int64 chunk matrix and ``prelude`` (ops/fused.py
    build_join_prelude) computes the [key_lanes | payload_lanes]
    matrix INSIDE the dispatch — projection exprs, key normalization
    and payload bit-encode all trace into the same program that
    scatters state (donated, exactly like the direct-upload twins)."""
    def ap(table, chains, pay, raw, aux, key_width):
        return epoch_apply(table, chains, pay, prelude(raw), aux,
                           key_width)

    def pr(table, chains, pay, deg_self, deg_sink, raw, aux,
           key_width, out_cap, with_degrees, start=0):
        return epoch_probe(table, chains, pay, deg_self, deg_sink,
                           prelude(raw), aux, key_width, out_cap,
                           with_degrees, start)

    return (jaxtools.instrumented_jit(
                ap, f"hash_join.epoch_apply[{label}]",
                donate_argnums=(0, 1, 2), static_argnums=(5,)),
            jaxtools.instrumented_jit(
                pr, f"hash_join.epoch_probe[{label}]",
                static_argnums=(7, 8, 9)))


def _remap_head(head: jnp.ndarray, old_to_new: jnp.ndarray,
                new_cap: int) -> jnp.ndarray:
    safe = jnp.where(old_to_new >= 0, old_to_new, new_cap)
    return jnp.full(new_cap, -1, dtype=jnp.int32).at[safe].set(
        head, mode="drop")


_remap_head_jit = jaxtools.instrumented_jit(
    _remap_head, "hash_join.remap_head", static_argnums=(2,))


def _rebase_jit(chains: ChainState) -> ChainState:
    mx = jnp.int32(I32_MAX)
    return chains._replace(
        ins_seq=jnp.where(chains.ins_seq == mx, mx, jnp.int32(0)),
        del_seq=jnp.where(chains.del_seq == mx, mx, jnp.int32(0)))


_rebase_jit = jaxtools.instrumented_jit(_rebase_jit, "hash_join.rebase")


def _degrees_and_pairs(mat: np.ndarray, n: int, with_degrees: bool):
    """(degrees | None, the pair rows in use) of a fetched probe
    matrix over ``n`` probe rows."""
    at = HEADER_ROWS
    deg = None
    if with_degrees:
        deg = np.ascontiguousarray(mat[at:at + n, 0])
        at += n
    return deg, mat[at:at + int(mat[0, 0])]


class PendingProbe:
    """An in-flight probe: dispatched, DMA started, not yet read.

    Sequence versioning makes collect() safe at any later point — the
    kernel may have applied more messages, and a re-dispatch after a
    pair-buffer overflow still returns the probe-time result.
    `redispatch(cap)` re-runs the probe against the kernel's CURRENT
    state at a larger pair capacity; `bump(cap)` records the grown
    capacity on the owning kernel."""

    def __init__(self, mat, n: int, cap: int, redispatch, bump):
        self.mat = mat
        self.n = n
        self.cap = cap
        self.redispatch = redispatch
        self.bump = bump

    def collect(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(degrees, probe_idx[pairs], refs[pairs]). Pairs are
        sorted by probe row index (packed in candidate order)."""
        with LEDGER.kernel_scope("hash_join"):
            while True:
                mat = jaxtools.fetch1(self.mat)
                candidates = int(mat[1, 0])
                if candidates <= self.cap:
                    break
                from risingwave_tpu.common.chunk import next_pow2
                self.cap = max(self.cap * 2, next_pow2(candidates))
                self.bump(self.cap)
                self.mat = self.redispatch(self.cap)
                jaxtools.start_fetch(self.mat)
        deg, pairs = _degrees_and_pairs(mat, self.n, True)
        return (deg, np.ascontiguousarray(pairs[:, 0]),
                np.ascontiguousarray(pairs[:, 1]))


class PendingEpochProbe:
    """An in-flight epoch probe over the payload-widened matrix.

    Like PendingProbe, but parses the packed layout of `epoch_probe`
    (pair rows carry the probed side's payload lanes and, with
    degrees, the pre-epoch degree per ref) and installs the updated
    degree arrays into their owning kernels only once the collect
    succeeds — an overflow redispatch recomputes them from the
    original arrays, so a retry never double-counts a transition.

    The buffer's size is a shape of the program, so it must not follow
    the data for ever. It holds a page of the probe's CANDIDATES (the
    rows of the probed keys' runs, visible or not) and the pairs among
    them. A degree-free probe (``top`` given) whose candidates overflow
    it takes the buffer to at most ``top`` rows, the ladder's one
    further rung, and reads whatever lies beyond in pages of the same
    program (`redispatch(cap, start)`). A degree-tracking probe keeps
    doubling: its degree adds read the buffer."""

    def __init__(self, mat, n: int, cap: int, redispatch,
                 pay_width: int, with_degrees: bool, install, bump,
                 top: Optional[int] = None, note_probe=None):
        self.mat = mat
        self.n = n
        self.cap = cap
        self.redispatch = redispatch  # (cap, start=0) -> matrix
        self.pay_width = pay_width
        self.with_degrees = with_degrees
        self.install = install        # (deg_self, deg_sink) -> None
        self.bump = bump
        self.top = None if with_degrees else top
        # (rows of the longest chain, walk steps, candidates, pairs)
        self.note_probe = note_probe
        # times the buffer grew and the program ran again (a probe
        # that pages runs the same program, and counts none)
        self.redispatches = 0
        self._degs = None             # latest (deg_self, deg_sink)

    def set_degs(self, deg_self, deg_sink) -> None:
        self._degs = (deg_self, deg_sink)

    def collect(self):
        """(degrees | None, probe_idx, refs, pay_rows | None,
        old_deg | None); pairs sorted by probe row index."""
        from risingwave_tpu.common.chunk import next_pow2
        pages = []
        with LEDGER.kernel_scope("hash_join"):
            while True:
                mat = jaxtools.fetch1(self.mat)
                candidates = int(mat[1, 0])
                if candidates <= self.cap:
                    break
                if self.top is not None and self.cap >= self.top:
                    # the last rung: the same program, once per page
                    later = [self.redispatch(self.cap, start) for start
                             in range(self.cap, candidates, self.cap)]
                    for m in later:
                        jaxtools.start_fetch(m)
                    pages = [jaxtools.fetch1(m) for m in later]
                    break
                self.cap = self.top if self.top is not None \
                    else max(self.cap * 2, next_pow2(candidates))
                if self.bump is not None:
                    self.bump(self.cap)
                self.redispatches += 1
                self.mat = self.redispatch(self.cap)
                jaxtools.start_fetch(self.mat)
        if self.with_degrees and self._degs is not None:
            self.install(*self._degs)
        deg, pairs = _degrees_and_pairs(mat, self.n, self.with_degrees)
        if pages:
            pairs = np.concatenate(
                [pairs] + [_degrees_and_pairs(m, 0, False)[1]
                           for m in pages])
        if self.note_probe is not None:
            self.note_probe(int(mat[0, 1]), int(mat[1, 1]), candidates,
                            len(pairs))
        P = self.pay_width
        pay = np.ascontiguousarray(pairs[:, 2:2 + P]) if P else None
        old = np.ascontiguousarray(pairs[:, 2 + P]) \
            if self.with_degrees else None
        return (deg, np.ascontiguousarray(pairs[:, 0]),
                np.ascontiguousarray(pairs[:, 1]), pay, old)


class JoinSideKernel:
    """Host wrapper: key table + chain arrays + arena growth.

    The key table is a DeviceHashTable (growth, load factor, sync-free
    occupancy bound all live there); on rehash its on_grow hook remaps
    `head` from old slots to new. The executor assigns row refs (host
    pk→ref map); tombstoned refs are NOT recycled — a dead ref keeps
    its place in its run, so a reused one would stand in two runs and
    `store` would outgrow the row arrays. Dead refs are reclaimed
    wholesale by `rebuild` (recovery / compaction)."""

    # pre-sized like GroupedAggKernel.DEFAULT_CAPACITY: the growth
    # ladder costs a rehash + retrace per doubling, and the sync-free
    # occupancy bound drains (a blocking read) whenever an epoch's
    # rows outrun the key table
    DEFAULT_CAPACITY = 1 << 16
    # the epoch probe's largest buffer (candidates worked and pairs
    # returned a dispatch). Its size is a shape of the probe program:
    # it starts at `probe_capacity`, an inner join's first overflow
    # takes it here, and a probe with still more candidates is read in
    # pages (PendingEpochProbe), so no program is ever compiled for a
    # size the data chose
    PROBE_CAP_TOP = 1 << 16

    def __init__(self, key_width: int,
                 key_capacity: int = DEFAULT_CAPACITY,
                 row_capacity: int = DEFAULT_CAPACITY,
                 probe_capacity: int = 1 << 14,
                 payload_width: int = 0):
        self.key_width = key_width
        # payload lanes per stored row (3 int32 lanes per device-typed
        # column — ops/lanes.py payload_i64): written at insert time in
        # the same dispatch that links chains, gathered ON DEVICE by
        # the probe's emit walk so matched rows materialize from the
        # one packed fetch instead of a host arena gather per column
        self.payload_width = payload_width
        self._new_table(key_capacity)
        # buffer rows of the fused probe (candidates and pairs); grows
        # on overflow (kept generous: each size is a fresh XLA compile)
        self._probe_cap = probe_capacity
        # of the epoch probes of THIS side collected since they were
        # last taken, from the probes' own headers: rows of the longest
        # chain probed; [most steps a walk took, candidates, pairs]
        self._longest_chain = 0
        self._probe_books = [0, 0, 0]
        self.chains = empty_chains(self.table.capacity, row_capacity)
        self.pay = jnp.zeros((row_capacity, payload_width),
                             dtype=jnp.int32)
        # device-resident per-ref match degrees (outer/semi/anti
        # bookkeeping): maintained inside the epoch probe dispatches;
        # unallocated refs are 0 by the bump-allocation invariant
        self.deg = jnp.zeros(row_capacity, dtype=jnp.int32)
        # the batch size of the bulk writes (rebuild's insert, payload
        # and degree scatters): one program per rung, not per row count
        self._bulk = BatchRung()
        # fused-input epoch jits, keyed by prelude label: this kernel
        # may serve two preludes (its OWN side's on apply, the PROBING
        # side's on probe)
        self._prelude_jits: dict = {}

    def _epoch_jits(self, prelude, key: str):
        jits = self._prelude_jits.get(key)
        if jits is None:
            jits = make_prelude_epoch_jits(prelude, key)
            self._prelude_jits[key] = jits
        return jits

    @property
    def row_capacity(self) -> int:
        return int(self.chains.store.shape[0])

    @property
    def device_payload_bytes(self) -> int:
        """HBM bytes held by the payload lane store + degree array
        (the residency metric's device half)."""
        return int(self.pay.size + self.deg.size) * 4

    # -- growth ----------------------------------------------------------
    def _new_table(self, capacity: int) -> None:
        self.table = ht.DeviceHashTable(
            self.key_width, capacity, grow_floor=self._key_floor)
        self.table.on_grow(self._on_table_grow)

    def _key_floor(self) -> int:
        """Where the key table goes when it has to grow: to what the
        row arrays, full of distinct keys, would need. A side whose
        keys never fill its first table never grows it and pays
        nothing; a side with a row a key would else cross a rung
        of its own (a rehash, a retrace and a compile of the apply and
        probe programs) between every two of the row arrays'."""
        return next_pow2(int(self.row_capacity / ht.MAX_LOAD) + 1)

    def _on_table_grow(self, old_to_new: jnp.ndarray,
                       old_capacity: int) -> None:
        self.chains = self.chains._replace(
            head=_remap_head_jit(self.chains.head, old_to_new,
                                 self.table.capacity))

    def reserve_rows(self, max_ref: int) -> None:
        row_cap = self.row_capacity
        if max_ref < row_cap:
            return
        new_cap = row_cap
        while new_cap <= max_ref:
            # 4x, not 2x: every growth step retraces/recompiles the
            # apply+probe programs at the new row shape (a trace on
            # the host plus a compile); chains are 5 int32
            # arrays, so the overshoot is cheap HBM
            new_cap *= 4
        pad = new_cap - row_cap
        fresh = empty_chains(0, pad)
        self.chains = self.chains._replace(**{
            f: jnp.concatenate([getattr(self.chains, f),
                                getattr(fresh, f)])
            for f in ROW_ARRAYS})
        self.pay = jnp.concatenate(
            [self.pay, jnp.zeros((pad, self.payload_width),
                                 dtype=jnp.int32)])
        self.deg = jnp.concatenate(
            [self.deg, jnp.zeros(pad, dtype=jnp.int32)])

    # -- ops --------------------------------------------------------------
    # seq=0 defaults keep kernel-level tests/recovery simple: probes at
    # seq 0 use I32_MAX and see everything inserted at seq 0.
    def insert(self, key_lanes: jnp.ndarray, row_refs: np.ndarray,
               vis: jnp.ndarray, seq: int = 0) -> None:
        if len(row_refs):
            self.reserve_rows(int(np.max(row_refs)))
        slots = self.table.probe_insert(key_lanes, vis)
        self.chains = _link_jit(self.chains, slots,
                                jnp.asarray(row_refs), vis,
                                self.table.capacity, jnp.int32(seq))

    def delete(self, row_refs: np.ndarray, vis: jnp.ndarray,
               seq: int = 0, key_lanes=None) -> None:
        # key_lanes: routing info for the SHARDED kernel's API twin
        # (parallel/join.py); a single chip tombstones by ref directly
        self.chains = _tombstone_jit(self.chains, jnp.asarray(row_refs),
                                     vis, jnp.int32(seq))

    def probe_submit(self, key_lanes: jnp.ndarray, vis: jnp.ndarray,
                     seq: Optional[int] = None) -> "PendingProbe":
        """Dispatch the fused probe and kick its DMA; no blocking.
        The result is a pure function of (state, seq): collect() may
        run after later applies and may re-dispatch on overflow."""
        s = jnp.int32(I32_MAX if seq is None else seq)
        lanes_d = jnp.asarray(key_lanes)
        vis_d = jnp.asarray(vis)
        with LEDGER.phase("device_compute", kernel="hash_join",
                          stage="launch"):
            mat = _probe_pairs_jit(self.table.state, self.chains,
                                   lanes_d, vis_d, s, self._probe_cap,
                                   True)
        jaxtools.start_fetch(mat)

        def redispatch(cap):
            return _probe_pairs_jit(self.table.state, self.chains,
                                    lanes_d, vis_d, s, cap, True)

        def bump(cap):
            self._probe_cap = max(self._probe_cap, cap)

        return PendingProbe(mat, int(lanes_d.shape[0]),
                            self._probe_cap, redispatch, bump)

    # -- epoch batching ---------------------------------------------------
    def stage_epoch(self, up: np.ndarray, aux: np.ndarray, total: int,
                    max_ins_ref: int, owners=None) -> tuple:
        """Host→device staging of one side's epoch matrices (the
        sharded kernel's twin additionally pads to the mesh width,
        derives the skew-exact routing bucket from ``owners`` and
        row-shards the upload; a single chip just device_puts and has
        no routing bucket)."""
        del max_ins_ref, owners
        from risingwave_tpu.utils.ledger import note_backlog
        note_backlog("hash_join", total)
        return (jaxtools.upload(up, kernel="hash_join"),
                jaxtools.upload(aux, kernel="hash_join"), None)

    def apply_epoch(self, up_dev, aux_dev, n_rows: int,
                    max_ins_ref: int, prelude=None,
                    prelude_key: str = "", bucket=None) -> None:
        """Apply a whole epoch's concatenated inserts/tombstones (and
        their payload lanes) in one dispatch. ``up_dev`` is the
        [key_lanes | payload_lanes] upload matrix — or, with a fused
        input ``prelude``, the raw int64 chunk matrix the prelude
        turns into that layout in-trace. aux layout AUX_*. The up/aux
        device arrays are shared with probe_epoch — upload once."""
        if max_ins_ref >= 0:
            self.reserve_rows(max_ins_ref)
        self.table.reserve(n_rows)
        jit = _epoch_apply_jit if prelude is None else \
            self._epoch_jits(prelude, prelude_key)[0]
        self.table.state, self.chains, self.pay, ins = jit(
            self.table.state, self.chains, self.pay, up_dev, aux_dev,
            self.key_width)
        self.table._counters.push(ins, n_rows)

    def take_probe_rounds(self) -> tuple:
        """(rounds of probe_insert's loop, applies, rows the rounds
        worked, rows of the applies' batches) since the last call, of
        the applies whose counters have landed: all of them
        once a probe dispatched after them has been collected."""
        self.table._counters.drain_ready()
        return self.table._counters.take_rounds()

    def take_longest_chain(self) -> int:
        """Rows of the longest chain the epoch probes collected since
        the last call probed, visible or not (0: none collected)."""
        longest, self._longest_chain = self._longest_chain, 0
        return longest

    def take_probe_books(self) -> tuple:
        """(most steps a walk over the runs took, candidates expanded,
        pairs kept) of the epoch probes collected since the last
        call."""
        books, self._probe_books = self._probe_books, [0, 0, 0]
        return tuple(books)

    def _note_probe(self, longest: int, steps: int, candidates: int,
                    pairs: int) -> None:
        self._longest_chain = max(self._longest_chain, longest)
        books = self._probe_books
        books[0] = max(books[0], steps)
        books[1] += candidates
        books[2] += pairs

    def probe_epoch(self, up_dev, aux_dev, with_degrees: bool,
                    sink: "JoinSideKernel" = None, prelude=None,
                    prelude_key: str = "",
                    bucket=None) -> "PendingEpochProbe":
        """Probe a whole epoch's rows against THIS side, each row at
        its aux sequence; call after both sides' apply_epoch. With
        degrees, ``sink`` is the PROBING side's kernel: this side's
        degree transitions and the sink's inserted-row initial degrees
        both update on device in this dispatch (installed at collect —
        see PendingEpochProbe). ``prelude`` is the PROBING side's
        fused-input prelude (the uploaded rows are that side's raw
        matrix)."""
        out_cap = self._probe_cap
        sink = sink if sink is not None else self
        probe_jit = _epoch_probe_jit if prelude is None else \
            self._epoch_jits(prelude, prelude_key)[1]
        # capture the degree arrays at ENTRY: an overflow redispatch
        # must recompute from the same pre-probe state (the truncated
        # first dispatch's adds are discarded wholesale)
        deg0_self, deg0_sink = self.deg, sink.deg

        def dispatch(cap, start=0):
            out = probe_jit(
                self.table.state, self.chains, self.pay, deg0_self,
                deg0_sink, up_dev, aux_dev, self.key_width, cap,
                with_degrees, np.int32(start))
            return out if with_degrees else (out, None, None)

        def install(d_self, d_sink):
            self.deg = d_self
            sink.deg = d_sink

        def bump(cap):
            self._probe_cap = max(self._probe_cap, cap)

        with LEDGER.phase("device_compute", kernel="hash_join",
                          stage="launch"):
            mat, d_self, d_sink = dispatch(out_cap)
        jaxtools.start_fetch(mat)

        def redispatch(cap, start=0):
            m, ds, dk = dispatch(cap, start)
            pending.set_degs(ds, dk)
            return m

        pending = PendingEpochProbe(
            mat, int(up_dev.shape[0]), out_cap, redispatch,
            pay_width=self.payload_width, with_degrees=with_degrees,
            install=install, bump=bump,
            top=max(self.PROBE_CAP_TOP, out_cap),
            note_probe=self._note_probe)
        if with_degrees:
            pending.set_degs(d_self, d_sink)
        return pending

    # -- degrees (device-resident; recovery/reload writes) ---------------
    def write_degrees(self, refs: np.ndarray, vals: np.ndarray) -> None:
        """Scatter exact degree values (recovery / cold-tier reload:
        the degree of a stored row is a pure function of both sides'
        state, recomputed by one batch probe of the other side)."""
        self.deg = self._scatter_paged(self.deg, refs, vals)

    def _scatter_paged(self, arr, refs: np.ndarray, vals: np.ndarray):
        """``arr[refs] = vals`` in pages of the bulk rung."""
        refs = np.asarray(refs, dtype=np.int32)
        vals = np.asarray(vals, dtype=np.int32)
        for lo, hi in self._bulk.pages(len(refs)):
            arr = _masked_scatter_jit(
                arr, jnp.asarray(self._bulk.padded(refs, lo, hi)),
                jnp.asarray(self._bulk.mask(lo, hi)),
                jnp.asarray(self._bulk.padded(vals, lo, hi)))
        return arr

    def read_degrees(self, refs: np.ndarray) -> np.ndarray:
        """Degree values by ref (host fetch; compaction-only path)."""
        return np.asarray(self.deg)[refs].astype(np.int64)

    def probe(self, key_lanes: jnp.ndarray, vis: jnp.ndarray,
              seq: Optional[int] = None
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Synchronous submit+collect (tests, recovery)."""
        return self.probe_submit(key_lanes, vis, seq).collect()

    def rebase_seq(self) -> None:
        """Reset every finite ins/del sequence to 0 (a safe point with
        no probes in flight) so the int32 message counter can restart
        instead of wrapping."""
        self.chains = _rebase_jit(self.chains)

    # -- recovery ---------------------------------------------------------
    def rebuild(self, key_lanes: np.ndarray, row_refs: np.ndarray,
                payload: Optional[np.ndarray] = None) -> None:
        """Reload all live rows (recovery, compaction): a batched
        insert in pages of the bulk rung, the last page first, so that
        a key's rows stand in its chain in the order they were given,
        as one batch would link them (a run a key a page). ``payload`` (int32[n,
        payload_width]) rebuilds the device payload lanes exactly
        where the chains rebuild; degrees reset to zero and are
        recomputed by the caller's batch probe."""
        n = len(row_refs)
        key_cap = max(self.table.capacity,
                      ht.MIN_CAPACITY if n == 0 else
                      1 << int(np.ceil(np.log2(max(n / ht.MAX_LOAD, 1)))))
        row_cap = max(self.row_capacity,
                      1 << int(np.ceil(np.log2(max(n + 1, 2)))))
        self._new_table(key_cap)
        self.chains = empty_chains(self.table.capacity, row_cap)
        self.pay = jnp.zeros((row_cap, self.payload_width),
                             dtype=jnp.int32)
        self.deg = jnp.zeros(row_cap, dtype=jnp.int32)
        key_lanes = np.asarray(key_lanes, dtype=np.int32)
        row_refs = np.asarray(row_refs, dtype=np.int32)
        for lo, hi in reversed(self._bulk.pages(n)):
            self.insert(jnp.asarray(self._bulk.padded(key_lanes, lo, hi)),
                        self._bulk.padded(row_refs, lo, hi),
                        jnp.asarray(self._bulk.mask(lo, hi)), seq=0)
        if payload is not None and self.payload_width:
            self.pay = self._scatter_paged(self.pay, row_refs, payload)
