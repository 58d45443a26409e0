"""Device-resident grouped aggregation state (the q7 kernel).

Reference parity: src/stream/src/executor/hash_agg.rs:67 (executor state),
:329 (``apply_chunk``), :445 (``flush_data``); value-state accumulators
src/stream/src/executor/aggregation/agg_group.rs. Re-designed TPU-first:
the reference updates one `AggGroup` at a time through a hashbrown map —
here the entire chunk is one XLA step: batch probe-insert into the HBM
table, then scatter the per-row contributions into accumulator arrays.
Python cost per chunk is O(1).

Everything on device is **int32/float32** (see ops/lanes.py — emulated
64-bit scatter on TPU is ~1000x slower than native int32):

    keys        int32[cap, K]   group-key lanes        (hash_table)
    occ         bool[cap]                              (hash_table)
    group_rows  int32[cap]      net row count (Σ signs) — group liveness
                                (int32 bound: 2^31 rows PER GROUP; the
                                 flush guards against wraparound)
    accs        per call:       COUNT → [cnt i32]
                                SUM(int) → [4 limb i32] + nn   (exact)
                                SUM(float) → [hi f32, lo f32] + nn
                                  (paired-f32: per-value residual kept in
                                   lo, but cross-chunk accumulation is
                                   f32 — large/cancellation-heavy float
                                   sums lose precision vs the reference's
                                   f64 accumulator. DECIMAL/int money
                                   sums use the exact limb path; an exact
                                   float superaccumulator is backlogged.)
                                MIN/MAX → [hi i32, lo i32] + nn
    dirty       bool[cap]       touched since last barrier flush
    emitted_*   device snapshot of (group_rows, accs) at last flush — the
                flush derives Insert/Update/Delete and the old state row
                with zero host-side group maps.

Retraction rules (Op sign semantics, stream_chunk.rs):
  COUNT/SUM are sign-linear — limb scatter-adds of ``sign * x``.
  MIN/MAX are not invertible: supported on device for *append-only* input
  (two-pass lexicographic scatter-max on order lanes); with retractions
  the executor layers the reference's materialized-input strategy
  (aggregation/minput.rs) on top.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import next_pow2
from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.ops import lanes
from risingwave_tpu.utils import jaxtools, spans
from risingwave_tpu.utils.ledger import LEDGER

I32_MIN = -(1 << 31)
I32_MAX = (1 << 31) - 1


class AggKind(enum.Enum):
    COUNT = "count"        # count(col) or count(*) when input is None
    SUM = "sum"
    MIN = "min"
    MAX = "max"
    # HyperLogLog cardinality sketch (append-only; see HLL_* below)
    APPROX_COUNT_DISTINCT = "approx_count_distinct"
    # HOST-ONLY aggs (string/list outputs can never live in HBM): the
    # device keeps one dummy lane for dirty-tracking arity; outputs
    # recompute from the minput value multiset at flush
    # (expr/src/aggregate string_agg.rs / array_agg.rs parity)
    STRING_AGG = "string_agg"
    ARRAY_AGG = "array_agg"


HOST_AGG_KINDS = (AggKind.STRING_AGG, AggKind.ARRAY_AGG)


# -- HyperLogLog (approx_count_distinct) ----------------------------------
# Reference parity: src/expr/src/aggregate/approx_count_distinct/mod.rs
# :35-42 — the reference keeps 2^16 buckets; this build keeps a DENSE
# 2^16-register sketch per group (standard error 1.04/sqrt(2^16) ≈
# 0.4%) maintained host-side on the executor's host-agg path (one
# uint8 register array per group, vectorized scatter-max per chunk)
# and persisted as one BYTEA row per group. The device kernel carries
# only the dummy lane (grouping/dirtiness); a register file this wide
# does not fit the per-call scalar-accumulator layout. 2^16 registers
# matches the reference's bucket count (theirs are u64 counters —
# 512KB/group; one byte per register keeps ours at 64KB).
HLL_B = 16              # index bits
HLL_M = 1 << HLL_B      # registers (65536)
HLL_RHO_MAX = 65 - HLL_B
HLL_ALPHA = 0.7213 / (1 + 1.079 / HLL_M)   # bias constant, m >= 128


def _clz64(x: np.ndarray) -> np.ndarray:
    """Vectorized count-leading-zeros over uint64 (0 → 64)."""
    x = x.astype(np.uint64)
    n = np.full(x.shape, 64, dtype=np.int64)
    cur = x
    for s in (32, 16, 8, 4, 2, 1):
        big = cur >= (np.uint64(1) << np.uint64(s))
        n = np.where(big, n - s, n)
        cur = np.where(big, cur >> np.uint64(s), cur)
    return n - 1 * (x > 0)          # exact clz: 64-bitlen, 64 for 0


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — uniform 64-bit hash of the i64 image."""
    with np.errstate(over="ignore"):
        z = x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def hll_lanes(v64: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """i64 value image → (register index, rho) int32 input lanes."""
    h = _mix64(v64)
    reg = (h >> np.uint64(64 - HLL_B)).astype(np.int32)
    w = (h << np.uint64(HLL_B)).astype(np.uint64)
    rho = np.where(w == 0, HLL_RHO_MAX,
                   _clz64(w) + 1).astype(np.int32)
    return reg, np.minimum(rho, HLL_RHO_MAX).astype(np.int32)


def hll_estimate_dense(mat: np.ndarray) -> np.ndarray:
    """Estimates for stacked register files: (G, HLL_M) uint8 → int64
    per group, with linear-counting small-range correction."""
    mat = np.atleast_2d(mat)
    m = float(HLL_M)
    inv = np.power(2.0, -mat.astype(np.float64)).sum(axis=1)
    zeros = (mat == 0).sum(axis=1)
    e = HLL_ALPHA * m * m / inv
    small = (e <= 2.5 * m) & (zeros > 0)
    with np.errstate(divide="ignore"):
        lin = m * np.log(np.where(zeros > 0, m / np.maximum(zeros, 1),
                                  1.0))
    return np.where(small, lin, e).round().astype(np.int64)


@dataclass(frozen=True)
class AggSpec:
    """One aggregate call, physical view (numpy dtypes)."""

    kind: AggKind
    in_dtype: Optional[np.dtype] = None   # None ⇒ count(*)

    @property
    def out_dtype(self) -> np.dtype:
        if self.kind in HOST_AGG_KINDS:
            return np.dtype(object)
        if self.kind in (AggKind.COUNT,
                         AggKind.APPROX_COUNT_DISTINCT):
            return np.dtype(np.int64)
        assert self.in_dtype is not None
        if self.kind == AggKind.SUM:
            if np.issubdtype(self.in_dtype, np.floating):
                return np.dtype(np.float64)
            return np.dtype(np.int64)     # ints + scaled DECIMAL
        return np.dtype(self.in_dtype)    # MIN/MAX

    @property
    def is_float_sum(self) -> bool:
        return (self.kind == AggKind.SUM and self.in_dtype is not None
                and np.issubdtype(self.in_dtype, np.floating))

    @property
    def orders_by_lanes(self) -> bool:
        """MIN/MAX: the input encodes as order-preserving lanes."""
        return self.kind in (AggKind.MIN, AggKind.MAX)

    # device-array layout of this call's accumulators: [(dtype, fill)]
    def dev_layout(self) -> List[Tuple[np.dtype, object]]:
        i32 = np.dtype(np.int32)
        f32 = np.dtype(np.float32)
        if self.kind == AggKind.COUNT:
            return [(i32, 0)]
        if self.kind in HOST_AGG_KINDS:
            return [(i32, 0)]             # dummy lane (arity only)
        if self.kind == AggKind.APPROX_COUNT_DISTINCT:
            return [(i32, 0)]   # dummy lane: the dense sketch is host
        if self.kind == AggKind.SUM:
            if self.is_float_sum:
                return [(f32, 0.0), (f32, 0.0), (i32, 0)]
            return [(i32, 0)] * lanes.N_LIMBS + [(i32, 0)]
        fill = I32_MIN if self.kind == AggKind.MAX else I32_MAX
        return [(i32, fill), (i32, fill), (i32, 0)]

    # -- host codecs -----------------------------------------------------
    def encode_input(self, vals: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Host value column → device input lanes (numpy, vectorized)."""
        if self.kind == AggKind.COUNT or self.kind in HOST_AGG_KINDS:
            return ()
        if self.kind == AggKind.APPROX_COUNT_DISTINCT:
            return ()           # sketch updates are host-side
        if self.kind == AggKind.SUM:
            if self.is_float_sum:
                hi = vals.astype(np.float32)
                lo = (vals.astype(np.float64)
                      - hi.astype(np.float64)).astype(np.float32)
                return (hi, lo)
            return lanes.sum_limbs(vals)
        return lanes.order_lanes(vals)

    def decode_acc(self, cols: Sequence[np.ndarray]
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Gathered device acc columns → (value hostarray, is_null)."""
        if self.kind == AggKind.COUNT:
            cnt = cols[0].astype(np.int64)
            assert (cnt >= 0).all(), \
                "COUNT wrapped int32 — a group exceeded 2^31 rows"
            return cnt, np.zeros(cnt.shape, dtype=bool)
        if self.kind in HOST_AGG_KINDS:
            # placeholder: the executor overwrites these from the
            # minput multiset at flush (host path)
            n = len(cols[0])
            return (np.full(n, None, dtype=object),
                    np.ones(n, dtype=bool))
        if self.kind == AggKind.APPROX_COUNT_DISTINCT:
            # placeholder: the executor overwrites from the host
            # sketch registry at flush
            n = len(cols[0])
            return np.zeros(n, dtype=np.int64), np.ones(n, dtype=bool)
        nn = cols[-1]
        assert (nn >= 0).all(), \
            "non-null count wrapped int32 — a group exceeded 2^31 rows"
        null = nn == 0
        if self.kind == AggKind.SUM:
            if self.is_float_sum:
                v = cols[0].astype(np.float64) + cols[1].astype(np.float64)
            else:
                v = lanes.merge_limbs(*cols[:-1])
            return v, null
        v = lanes.inv_order_lanes(cols[0], cols[1], self.out_dtype)
        return v, null

    # -- host (state-row) accumulator layout ------------------------------
    def host_acc_dtypes(self) -> List[np.dtype]:
        """Columns this call persists in the value-state row."""
        i64 = np.dtype(np.int64)
        if self.kind == AggKind.COUNT:
            return [i64]
        if self.kind in HOST_AGG_KINDS:
            # nothing to persist: outputs recompute from the minput
            # multiset; one placeholder keeps the row arity stable
            return [i64]
        if self.kind == AggKind.APPROX_COUNT_DISTINCT:
            # nothing to persist here: the sketch lives in its own
            # BYTEA aux table; one placeholder keeps row arity stable
            return [i64]
        return [self.out_dtype, i64]

    def host_acc_cols(self, vals: np.ndarray, nulls: np.ndarray,
                      nn: Optional[np.ndarray],
                      raw_cols: Optional[List[np.ndarray]]
                      ) -> List[list]:
        """Decoded flush columns (+ raw device accs) → per-column
        python lists for state rows, NULLs as None."""
        if self.kind == AggKind.COUNT:
            return [vals.tolist()]
        if self.kind in HOST_AGG_KINDS:
            return [[0] * len(vals)]
        if self.kind == AggKind.APPROX_COUNT_DISTINCT:
            return [[0] * len(vals)]
        value_col = [None if bad else v
                     for v, bad in zip(vals.tolist(), nulls.tolist())]
        return [value_col, nn.tolist()]

    def host_to_dev(self, host_cols: Sequence[np.ndarray]
                    ) -> Tuple[np.ndarray, ...]:
        """Recovered host acc columns → device-layout columns."""
        if self.kind == AggKind.COUNT:
            return (host_cols[0].astype(np.int32),)
        if self.kind in HOST_AGG_KINDS:
            return (host_cols[0].astype(np.int32),)   # dummy lane
        if self.kind == AggKind.APPROX_COUNT_DISTINCT:
            return (host_cols[0].astype(np.int32),)   # dummy lane
        return self.encode_acc(host_cols[0], host_cols[1])

    def encode_acc(self, value: np.ndarray, nn: Optional[np.ndarray]
                   ) -> Tuple[np.ndarray, ...]:
        """(decoded value, nn) → device acc columns (recovery path).

        NULL slots (nn == 0) re-encode as the identity fill."""
        if self.kind == AggKind.COUNT:
            return (value.astype(np.int32),)
        assert nn is not None
        nn32 = nn.astype(np.int32)
        if self.kind == AggKind.SUM:
            if self.is_float_sum:
                hi = value.astype(np.float32)
                lo = (value.astype(np.float64)
                      - hi.astype(np.float64)).astype(np.float32)
                return (hi, lo, nn32)
            return lanes.sum_limbs(value.astype(np.int64)) + (nn32,)
        hi, lo = lanes.order_lanes(
            np.asarray(value, dtype=self.out_dtype))
        fill = I32_MIN if self.kind == AggKind.MAX else I32_MAX
        dead = nn32 == 0
        hi = np.where(dead, np.int32(fill), hi).astype(np.int32)
        lo = np.where(dead, np.int32(fill), lo).astype(np.int32)
        return (hi, lo, nn32)


def encode_host_accs(specs: Sequence[AggSpec],
                     acc_cols: Sequence[np.ndarray]) -> List[np.ndarray]:
    """HOST state-row acc columns (host_acc_dtypes layout) →
    device-layout columns, for recovery rebuilds (shared by the
    single-chip and sharded kernels)."""
    out: List[np.ndarray] = []
    j = 0
    for s in specs:
        k = len(s.host_acc_dtypes())
        out.extend(s.host_to_dev(acc_cols[j:j + k]))
        j += k
    return out


def acc_dtypes(specs: Sequence[AggSpec]) -> List[np.dtype]:
    """HOST (state-row) accumulator columns, per call."""
    out: List[np.dtype] = []
    for s in specs:
        out.extend(s.host_acc_dtypes())
    return out


def dev_layout(specs: Sequence[AggSpec]) -> List[Tuple[np.dtype, object]]:
    out: List[Tuple[np.dtype, object]] = []
    for s in specs:
        out.extend(s.dev_layout())
    return out


def n_input_lanes(spec: AggSpec) -> int:
    """Device input lanes per row for this call (encode_input arity)."""
    if spec.kind == AggKind.COUNT or spec.kind in HOST_AGG_KINDS:
        return 0
    if spec.kind == AggKind.SUM:
        return 2 if spec.is_float_sum else lanes.N_LIMBS
    return 2              # MIN/MAX order lanes; HLL (register, rho)


def _call_slices(specs: Sequence[AggSpec]) -> List[slice]:
    """Flat device-acc array index range per call."""
    out, j = [], 0
    for s in specs:
        n = len(s.dev_layout())
        out.append(slice(j, j + n))
        j += n
    return out


def decode_outputs(specs: Sequence[AggSpec],
                   dev_cols: Sequence[np.ndarray]
                   ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Gathered device acc columns → per-call (value, is_null) host cols."""
    outs, nulls = [], []
    for s, sl in zip(specs, _call_slices(specs)):
        v, nu = s.decode_acc(dev_cols[sl])
        outs.append(v)
        nulls.append(nu)
    return outs, nulls


class AggState(NamedTuple):
    """Functional device state for one grouped-agg operator."""

    table: ht.TableState
    group_rows: jnp.ndarray            # int32[cap]
    dirty: jnp.ndarray                 # bool[cap]
    accs: Tuple[jnp.ndarray, ...]      # flat device accumulators
    emitted_valid: jnp.ndarray         # bool[cap] — live at last flush
    emitted_rows: jnp.ndarray          # int32[cap]
    emitted_accs: Tuple[jnp.ndarray, ...]


def make_agg_state(capacity: int, key_width: int,
                   specs: Sequence[AggSpec]) -> AggState:
    lay = dev_layout(specs)
    accs = tuple(jnp.full(capacity, f, dtype=dt) for dt, f in lay)
    return AggState(
        table=ht.make_state(capacity, key_width),
        group_rows=jnp.zeros(capacity, dtype=jnp.int32),
        dirty=jnp.zeros(capacity, dtype=bool),
        accs=accs,
        emitted_valid=jnp.zeros(capacity, dtype=bool),
        emitted_rows=jnp.zeros(capacity, dtype=jnp.int32),
        emitted_accs=tuple(jnp.full(capacity, f, dtype=dt)
                           for dt, f in lay),
    )


def _update_call(spec: AggSpec, accs: List[jnp.ndarray], sl: slice,
                 in_lanes, valid_ok, slots, vis, sign, cap) -> None:
    """Trace one call's accumulator updates in place (list mutation)."""
    live = vis & valid_ok
    scat = jnp.where(live, slots, cap)
    if spec.kind in HOST_AGG_KINDS:
        return                              # host path owns the value
    if spec.kind == AggKind.COUNT:
        accs[sl.start] = accs[sl.start].at[scat].add(sign, mode="drop")
        return
    if spec.kind == AggKind.APPROX_COUNT_DISTINCT:
        return          # dense sketch is host-side (see HLL_B above)
    nn_i = sl.stop - 1
    accs[nn_i] = accs[nn_i].at[scat].add(sign, mode="drop")
    if spec.kind == AggKind.SUM:
        if spec.is_float_sum:
            sf = sign.astype(jnp.float32)
            for k in range(2):
                accs[sl.start + k] = accs[sl.start + k].at[scat].add(
                    in_lanes[k] * sf, mode="drop")
        else:
            # limb scatter-adds overflow int32 past MAX_CHUNK_ROWS rows;
            # batched applies slice the batch and carry-normalize per
            # slice (static unroll — still ONE dispatched program)
            n = int(scat.shape[0])
            for lo in range(0, n, lanes.MAX_CHUNK_ROWS):
                hi = min(lo + lanes.MAX_CHUNK_ROWS, n)
                s_ = slice(lo, hi)
                for k in range(lanes.N_LIMBS):
                    accs[sl.start + k] = accs[sl.start + k] \
                        .at[scat[s_]].add(in_lanes[k][s_] * sign[s_],
                                          mode="drop")
                for k in range(lanes.N_LIMBS - 1):
                    carry = accs[sl.start + k] >> lanes.LIMB_BITS
                    accs[sl.start + k] = accs[sl.start + k] - \
                        (carry << lanes.LIMB_BITS)
                    accs[sl.start + k + 1] = accs[sl.start + k + 1] + carry
        return
    # MIN/MAX (append-only device path: sign > 0 rows only): lexicographic
    # (hi, lo) two-pass — pass 1 settles hi; pass 2 rebases lo wherever hi
    # moved (a stale lo from a smaller hi must not win) and maxes in the
    # lo of rows whose hi ties the new hi.
    is_max = spec.kind == AggKind.MAX
    ident = jnp.int32(I32_MIN if is_max else I32_MAX)
    ins = live & (sign > 0)
    iscat = jnp.where(ins, slots, cap)
    hi_i, lo_i = sl.start, sl.start + 1
    v_hi, v_lo = in_lanes
    old_hi = accs[hi_i]
    if is_max:
        new_hi = old_hi.at[iscat].max(v_hi, mode="drop")
    else:
        new_hi = old_hi.at[iscat].min(v_hi, mode="drop")
    lo_base = jnp.where(old_hi == new_hi, accs[lo_i], ident)
    lo_contrib = jnp.where(v_hi == new_hi[jnp.where(ins, slots, 0)],
                           v_lo, ident)
    lscat = jnp.where(ins, slots, cap)
    if is_max:
        new_lo = lo_base.at[lscat].max(lo_contrib, mode="drop")
    else:
        new_lo = lo_base.at[lscat].min(lo_contrib, mode="drop")
    accs[hi_i], accs[lo_i] = new_hi, new_lo


def _has_valid_col(spec: AggSpec) -> bool:
    """count(*) is the only call with no input → no non-null mask.
    count(col) has zero value lanes but still needs its valid column."""
    return spec.in_dtype is not None or spec.kind != AggKind.COUNT


def packed_layout(key_width: int, specs: Sequence[AggSpec]
                  ) -> List[Tuple[List[int], Optional[int]]]:
    """Per-call (value-lane columns, valid column | None) of the packed
    per-chunk input matrix — the ONE place the column cursor lives;
    pack_chunk, build_apply and packed_width all consume it.

    Layout: key lanes | signs | vis | per call with input: lanes + valid.
    Everything is int32 (f32 lanes travel bitcast) so the whole chunk is
    ONE host→device transfer instead of one per lane (what a transfer
    costs on a local chip is not measured).
    """
    out: List[Tuple[List[int], Optional[int]]] = []
    c = key_width + 2
    for s in specs:
        if _has_valid_col(s):
            nl = n_input_lanes(s)
            out.append((list(range(c, c + nl)), c + nl))
            c += nl + 1
        else:
            out.append(([], None))
    return out


def packed_width(key_width: int, specs: Sequence[AggSpec]) -> int:
    lay = packed_layout(key_width, specs)
    last = key_width + 1
    for cols, vc in lay:
        for i in cols:
            last = max(last, i)
        if vc is not None:
            last = max(last, vc)
    return last + 1


def pack_chunk(key_width: int, specs: Sequence[AggSpec],
               key_lanes: np.ndarray, signs: np.ndarray, vis: np.ndarray,
               inputs: Sequence) -> np.ndarray:
    """Host-side chunk → one int32[N, W] matrix (vectorized column writes).

    `inputs` is per call (value lane arrays, valid mask); count(*) calls
    contribute no columns.
    """
    n = len(signs)
    m = np.empty((n, packed_width(key_width, specs)), dtype=np.int32)
    m[:, :key_width] = key_lanes
    m[:, key_width] = signs
    m[:, key_width + 1] = vis
    for (cols, vc), (in_lanes, valid) in zip(
            packed_layout(key_width, specs), inputs):
        for c, a in zip(cols, in_lanes):
            a = np.asarray(a)
            m[:, c] = a.view(np.int32) if a.dtype == np.float32 else a
        if vc is not None:
            m[:, vc] = np.asarray(valid)
    return m


def build_apply(key_width: int, specs: Sequence[AggSpec],
                prelude=None):
    """Compile the per-chunk step for a fixed agg plan.

    step(state, packed int32[N, W]) → (state, int32[4] = [n_inserted,
    rounds of probe_insert's loop, the rows they worked, N]).
    The packed matrix comes from ``pack_chunk``; jit-cached per (cap, N).
    The insert counter is the sync-free occupancy feed: the host wrapper
    fetches it asynchronously (jaxtools.fetch) so growth decisions never
    block on the device queue.

    With ``prelude`` (ops/fused.py build_agg_prelude), the step takes
    the RAW int64 chunk matrix instead and the whole fragment chain —
    filter, project, key/lane encode — inlines ahead of the accumulator
    updates: ONE jitted dataflow step per dispatch, state donated. The
    fused step additionally returns per-logical-stage visible-row
    counts (int64[n_stages]) for metrics attribution.
    """
    specs = tuple(specs)
    slices = _call_slices(specs)
    call_cols = packed_layout(key_width, specs)

    def core(state: AggState, key_lanes, s32, vis, call_inputs):
        cap = state.table.capacity
        # ins: [n_inserted, the claim loop's books] (PendingCounters)
        table, slots, ins = ht.probe_insert_counted(state.table,
                                                    key_lanes, vis)
        scat = jnp.where(vis, slots, cap)   # invisible rows dropped
        group_rows = state.group_rows.at[scat].add(s32, mode="drop")
        dirty = state.dirty.at[scat].set(True, mode="drop")
        accs = list(state.accs)
        all_true = jnp.ones(key_lanes.shape[0], dtype=bool)
        for spec, sl, (in_lanes, val_ok) in zip(specs, slices,
                                                call_inputs):
            _update_call(spec, accs, sl, in_lanes,
                         all_true if val_ok is None else val_ok,
                         slots, vis, s32, cap)
        new_state = AggState(table, group_rows, dirty, tuple(accs),
                             state.emitted_valid, state.emitted_rows,
                             state.emitted_accs)
        return new_state, ins

    if prelude is not None:
        def step(state: AggState, raw):
            key_lanes, s32, vis, call_inputs, stage_rows = prelude(raw)
            new_state, ins = core(state, key_lanes, s32, vis,
                                  call_inputs)
            return new_state, ins, stage_rows

        return jaxtools.instrumented_jit(step, "hash_agg.apply_fused",
                                         donate_argnums=(0,))

    def step(state: AggState, packed):
        key_lanes = packed[:, :key_width]
        s32 = packed[:, key_width]
        vis = packed[:, key_width + 1].astype(bool)
        call_inputs = []
        for spec, (lc, vc) in zip(specs, call_cols):
            if spec.is_float_sum:
                in_lanes = tuple(jax.lax.bitcast_convert_type(
                    packed[:, i], jnp.float32) for i in lc)
            else:
                in_lanes = tuple(packed[:, i] for i in lc)
            call_inputs.append(
                (in_lanes,
                 None if vc is None else packed[:, vc].astype(bool)))
        return core(state, key_lanes, s32, vis, tuple(call_inputs))

    return jaxtools.instrumented_jit(step, "hash_agg.apply",
                                     donate_argnums=(0,))


def _col_i32(a: jnp.ndarray) -> jnp.ndarray:
    if a.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(a, jnp.int32)
    if a.dtype == jnp.bool_:
        return a.astype(jnp.int32)
    return a


def gather_packed(state: AggState, flush_cap: int) -> jnp.ndarray:
    """Traced barrier-flush gather: ONE packed device→host array.

    → int32[1 + flush_cap, W]. Row 0 is the header [n_dirty, n_groups,
    0…]; rows 1..1+n are the dirty slots: slot idx | keys | group_rows |
    accs | emitted_valid | emitted_rows | emitted accs (f32 accs
    bitcast). Dirty-slot compaction happens ON DEVICE (cumsum positions)
    so the host never fetches the dirty bitmap; the whole barrier costs
    one transfer. If n_dirty > flush_cap the host retries with a doubled
    flush_cap (header tells it so). Module-level so the sharded kernel
    can wrap it in shard_map (one gather per shard, one fetch total).
    """
    cap = state.table.capacity
    key_width = state.table.key_width
    dirty = state.dirty
    d32 = dirty.astype(jnp.int32)
    pos = jnp.cumsum(d32, dtype=jnp.int32) - 1
    n_dirty = jnp.sum(d32, dtype=jnp.int32)
    scat = jnp.where(dirty & (pos < flush_cap), pos, flush_cap)
    slot_ids = jnp.arange(cap, dtype=jnp.int32)
    idx = jnp.zeros(flush_cap, dtype=jnp.int32) \
        .at[scat].set(slot_ids, mode="drop")
    cols = [idx]
    for k in range(key_width):
        cols.append(state.table.keys[idx, k])
    cols.append(state.group_rows[idx])
    for a in state.accs:
        cols.append(_col_i32(a[idx]))
    cols.append(state.emitted_valid[idx].astype(jnp.int32))
    cols.append(state.emitted_rows[idx])
    for a in state.emitted_accs:
        cols.append(_col_i32(a[idx]))
    mat = jnp.stack(cols, axis=1)
    n_groups = jnp.sum(state.table.occ, dtype=jnp.int32)
    header = jnp.zeros((1, mat.shape[1]), dtype=jnp.int32) \
        .at[0, 0].set(n_dirty).at[0, 1].set(n_groups)
    return jnp.concatenate([header, mat], axis=0)


def build_gather_packed(key_width: int):
    del key_width   # derived from the state shape at trace time
    return jaxtools.instrumented_jit(gather_packed,
                                     "hash_agg.flush_gather",
                                     static_argnums=(1,))


def _rebuild_live(state: AggState, live: jnp.ndarray, new_cap: int,
                  fills) -> Tuple[AggState, jnp.ndarray]:
    """Traced same-or-larger-capacity rehash keeping only ``live`` slots.

    Open-addressing linear probing cannot free slots in place — an
    emptied slot truncates the probe chain of every key that collided
    past it, orphaning live groups — so both growth and watermark
    retirement rebuild the table by re-inserting survivors.
    """
    new_table = ht.make_state(new_cap, state.table.key_width)
    new_table, old_to_new, n_live = ht.probe_insert(
        new_table, state.table.keys, live)
    new_state = AggState(
        table=new_table,
        group_rows=remap_slots(state.group_rows, old_to_new, new_cap, 0),
        dirty=remap_slots(state.dirty, old_to_new, new_cap, 0),
        accs=tuple(remap_slots(a, old_to_new, new_cap, f)
                   for a, f in zip(state.accs, fills)),
        emitted_valid=remap_slots(state.emitted_valid, old_to_new,
                                  new_cap, 0),
        emitted_rows=remap_slots(state.emitted_rows, old_to_new,
                                 new_cap, 0),
        emitted_accs=tuple(remap_slots(a, old_to_new, new_cap, f)
                           for a, f in zip(state.emitted_accs, fills)),
    )
    return new_state, n_live


# int constant, NOT jnp.int32: a module-level jnp scalar initializes
# the JAX backend at IMPORT — and a plan-only process (the distributed
# frontend) must never touch the TPU. XLA folds the Python int the same.
_I32_SIGN_FLIP = -0x80000000


def retire_state(state: AggState, wm_hi, wm_lo, lane_off: int,
                 fills) -> Tuple[AggState, jnp.ndarray]:
    """Traced watermark retirement (state_table.rs:894 state-cleaning
    analog, device side): drop every group whose watermark key column is
    strictly below the watermark, by rebuilding the table from survivors
    in ONE device step (no host transfer; the count refreshes at the
    next flush).

    The key columns are 3 lanes each (keys.py): (hi = v>>32,
    lo = uint32 image, valid). Order compare is (hi signed, lo
    unsigned); the sign-flip XOR makes int32 compares act unsigned.
    NULL keys (valid=0) are never below a watermark.
    """
    keys = state.table.keys
    hi = keys[:, lane_off]
    lo = keys[:, lane_off + 1] ^ _I32_SIGN_FLIP
    nonnull = keys[:, lane_off + 2] != 0
    wlo = wm_lo ^ _I32_SIGN_FLIP
    below = (hi < wm_hi) | ((hi == wm_hi) & (lo < wlo))
    closed = state.table.occ & nonnull & below
    live = state.table.occ & ~closed & (
        (state.group_rows != 0) | state.dirty | state.emitted_valid)
    return _rebuild_live(state, live, state.table.capacity, fills)


def build_retire(key_width: int, specs: Sequence[AggSpec]):
    del key_width
    fills = tuple(f for _dt, f in dev_layout(specs))
    jitted = jaxtools.instrumented_jit(
        retire_state, "hash_agg.retire", static_argnums=(3, 4),
        donate_argnums=(0,))

    def retire(state, wm_hi, wm_lo, lane_off):
        return jitted(state, wm_hi, wm_lo, lane_off, fills)

    return retire


def evict_state(state: AggState, key_lanes: jnp.ndarray,
                valid: jnp.ndarray, fills
                ) -> Tuple[AggState, jnp.ndarray]:
    """Traced cold-tier eviction (state/tier.py): drop the given keys'
    groups by rebuilding the table from the survivors in ONE device
    step — the same rebuild path watermark retirement uses. The caller
    guarantees the evicted groups are CLEAN (flushed + advanced), so
    dropping their device slots loses nothing the state table does not
    hold."""
    slots = ht.lookup(state.table, key_lanes, valid)
    cap = state.table.capacity
    scat = jnp.where(slots >= 0, slots, cap)
    dropped = jnp.zeros(cap, dtype=bool).at[scat].set(True, mode="drop")
    live = state.table.occ & ~dropped & (
        (state.group_rows != 0) | state.dirty | state.emitted_valid)
    return _rebuild_live(state, live, cap, fills)


def build_evict(specs: Sequence[AggSpec]):
    fills = tuple(f for _dt, f in dev_layout(specs))
    jitted = jaxtools.instrumented_jit(
        evict_state, "hash_agg.evict", static_argnums=(3,),
        donate_argnums=(0,))

    def evict(state, key_lanes, valid):
        return jitted(state, key_lanes, valid, fills)

    return evict


def advance_state(state: AggState) -> AggState:
    """Traced post-flush snapshot advance — fully on device, no host
    index round-trip: emitted := current for every dirty slot."""
    d = state.dirty
    ev = jnp.where(d, state.group_rows > 0, state.emitted_valid)
    er = jnp.where(d, state.group_rows, state.emitted_rows)
    ea = tuple(jnp.where(d, a, e)
               for a, e in zip(state.accs, state.emitted_accs))
    return AggState(state.table, state.group_rows,
                    jnp.zeros_like(d), state.accs, ev, er, ea)


def build_advance():
    return jaxtools.instrumented_jit(advance_state, "hash_agg.advance",
                                     donate_argnums=(0,))


def encode_patch_cols(specs: Sequence[AggSpec], decoded,
                      raw_accs) -> List[np.ndarray]:
    """Corrected (value, nn) pairs → device acc columns for a patch.

    `decoded[j]` is (value, nn) for a corrected call, or None for an
    untouched one — untouched calls pass their RAW gathered device
    columns through bit-for-bit (re-encoding a float sum through the
    decoded f64 would perturb the (hi, lo) pair). Shared by the
    single-chip and sharded kernels so the encoding can never drift."""
    slices = _call_slices(specs)
    dev_cols: List[np.ndarray] = []
    for j, (s, d) in enumerate(zip(specs, decoded)):
        if d is None:
            assert raw_accs is not None, \
                "raw accs needed for passthrough"
            dev_cols.extend(raw_accs[slices[j]])
        else:
            v, nn = d
            dev_cols.extend(s.encode_acc(v, nn))
    return dev_cols


def build_patch(specs: Sequence[AggSpec]):
    """Compile the host→device acc patch (retractable MIN/MAX recompute
    writes corrected extremes back before the snapshot advances)."""

    def patch(state: AggState, idx, new_accs):
        accs = tuple(a.at[idx].set(v, mode="drop")
                     for a, v in zip(state.accs, new_accs))
        return state._replace(accs=accs)

    return jaxtools.instrumented_jit(patch, "hash_agg.patch")


def remap_slots(arr: jnp.ndarray, old_to_new: jnp.ndarray,
                new_cap: int, fill) -> jnp.ndarray:
    """Re-scatter a slot-indexed array after a table rehash.

    `old_to_new[i]` is the new slot of old slot i (-1 for unoccupied)."""
    if arr.dtype == jnp.bool_:
        init = jnp.full(new_cap, bool(fill), dtype=arr.dtype)
    else:
        init = jnp.full(new_cap, fill, dtype=arr.dtype)
    safe = jnp.where(old_to_new >= 0, old_to_new, new_cap)
    return init.at[safe].set(arr, mode="drop")




@dataclass
class FlushResult:
    """Host view of the dirty groups at a barrier (decoded values)."""

    n: int
    keys: np.ndarray                 # int32[n, K] raw key lanes
    group_rows: np.ndarray           # int64[n] — current
    outs: List[np.ndarray]           # per call decoded output value
    nulls: List[np.ndarray]          # per call output-is-NULL
    nns: List[Optional[np.ndarray]]  # per call non-null count (None: cnt*)
    was_emitted: np.ndarray          # bool[n]
    prev_rows: np.ndarray
    prev_outs: List[np.ndarray]
    prev_nulls: List[np.ndarray]
    prev_nns: List[Optional[np.ndarray]]
    # device-layout acc columns from the flush gather (None on empty)
    raw_accs: Optional[List[np.ndarray]] = None
    prev_raw_accs: Optional[List[np.ndarray]] = None

    @staticmethod
    def empty(specs: Sequence[AggSpec], key_width: int) -> "FlushResult":
        z = np.zeros(0, dtype=np.int64)
        zb = np.zeros(0, dtype=bool)
        vals = [np.zeros(0, dtype=s.out_dtype) for s in specs]
        nns = [None if (s.kind in (AggKind.COUNT,
                                   AggKind.APPROX_COUNT_DISTINCT)
                        or s.kind in HOST_AGG_KINDS)
               else z.copy() for s in specs]
        return FlushResult(
            0, np.zeros((0, key_width), dtype=np.int32), z.copy(),
            list(vals), [zb.copy() for _ in specs], list(nns),
            zb.copy(), z.copy(),
            [v.copy() for v in vals], [zb.copy() for _ in specs],
            [None if n is None else n.copy() for n in nns])


def _unpack_acc_cols(specs: Sequence[AggSpec], data: np.ndarray,
                     c0: int) -> List[np.ndarray]:
    """Packed i32 matrix columns → device-layout acc arrays."""
    out = []
    for dt, _fill in dev_layout(specs):
        col = np.ascontiguousarray(data[:, c0])
        if dt == np.dtype(np.float32):
            col = col.view(np.float32)
        out.append(col)
        c0 += 1
    return out


def decode_flush_data(specs: Sequence[AggSpec], key_width: int,
                      data: np.ndarray) -> FlushResult:
    """Decode gathered dirty-slot rows (gather_packed layout minus the
    header) into a host FlushResult. Shared by the single-chip and
    sharded kernels — sharded flushes concatenate per-shard segments
    first (keys never span shards, so concat is a disjoint union)."""
    p = data.shape[0]
    k = key_width
    keys = data[:, 1:1 + k]
    rows = np.ascontiguousarray(data[:, 1 + k])
    if not (rows >= 0).all():
        raise RuntimeError(
            "group_rows wrapped int32 — a group exceeded 2^31 rows")
    n_acc = len(dev_layout(specs))
    accs = _unpack_acc_cols(specs, data, 2 + k)
    was = np.ascontiguousarray(data[:, 2 + k + n_acc]).astype(bool)
    prows = np.ascontiguousarray(data[:, 3 + k + n_acc])
    paccs = _unpack_acc_cols(specs, data, 4 + k + n_acc)
    outs, nulls = decode_outputs(specs, accs)
    pouts, pnulls = decode_outputs(specs, paccs)
    return FlushResult(
        n=p, keys=keys,
        group_rows=rows.astype(np.int64),
        outs=outs, nulls=nulls, nns=_nns_of(specs, accs),
        was_emitted=was,
        prev_rows=prows.astype(np.int64),
        prev_outs=pouts, prev_nulls=pnulls,
        prev_nns=_nns_of(specs, paccs),
        raw_accs=accs, prev_raw_accs=paccs)


def _nns_of(specs, dev_cols) -> List[Optional[np.ndarray]]:
    out = []
    for s, sl in zip(specs, _call_slices(specs)):
        plain = s.kind in (AggKind.COUNT,
                           AggKind.APPROX_COUNT_DISTINCT) \
            or s.kind in HOST_AGG_KINDS
        out.append(None if plain
                   else dev_cols[sl][-1].astype(np.int64))
    return out


class GroupedAggKernel:
    """Host wrapper: growth scheduling, flush bookkeeping, jit caches.

    The executor drives it: ``apply`` per chunk (ONE host→device transfer,
    no syncs), ``flush`` per barrier (ONE device→host transfer),
    ``rebuild`` on recovery.

    Occupancy accounting is **sync-free**: every apply step returns its
    exact device-side insert count, fetched asynchronously (the DMA is
    kicked at dispatch; ``_drain_ready`` folds in whichever counters have
    landed without blocking). The growth bound is then
    ``exact_count_of_drained + rows_of_undrained`` — tight within a few
    in-flight chunks, so a table sized for its group count never blocks,
    and a genuinely-filling table blocks only on counters whose DMA is
    already in flight. What a blocking read costs on a local chip is
    not measured (chip_smoke.py prints one reading).
    """

    # pressure growth (see _reserve) stops doubling past this capacity:
    # ~15 int32 arrays × 2^21 ≈ 125MB HBM, far under a v5e's 16GB but
    # enough to absorb million-row epochs without a mid-epoch drain
    PRESSURE_GROW_CEILING = 1 << 21

    # Default table size: big enough that typical epochs never hit the
    # pessimistic-bound drain or the growth ladder (each growth step
    # costs a rehash + fresh trace/compile of every program — ~0.5s even
    # warm). Sized for TWO in-flight 32K batches of pessimistic inserts
    # plus real occupancy: 2^18 slots ≈ 16MB HBM for a 2-call plan.
    DEFAULT_CAPACITY = 1 << 18

    def __init__(self, key_width: int, specs: Sequence[AggSpec],
                 capacity: Optional[int] = None,
                 flush_capacity: int = 1 << 10,
                 prelude=None, raw_width: Optional[int] = None,
                 metrics_label: Optional[str] = None,
                 expand_units: int = 1):
        if capacity is None:
            capacity = self.DEFAULT_CAPACITY
        capacity = max(next_pow2(capacity), ht.MIN_CAPACITY)
        # expand_units (hop-absorbing preludes) is advisory: the
        # traced step multiplies raw rows `units`× before the scatter.
        # Shrinking the raw backlog to match was measured SLOWER on
        # the CPU; not measured on a local chip — kept as a parameter
        # so device rounds can tune it.
        self._expand_units = expand_units
        self.specs = tuple(specs)
        self.key_width = key_width
        self.state = make_agg_state(capacity, key_width, self.specs)
        # fused-fragment mode (ops/fused.py): the backlog holds RAW
        # int64 chunk matrices and the jitted step runs the whole
        # filter→project→encode→update chain in one dispatch
        self._prelude = prelude
        self._raw_width = raw_width
        # real-dispatch metrics attribution (fused mode counts at the
        # ACTUAL jit-invocation sites — one per backlog flush)
        self.metrics_label = metrics_label
        # epoch-trace identity stamped on every dispatch span
        self._span_label = metrics_label or "GroupedAggKernel"
        self._apply = build_apply(key_width, self.specs,
                                  prelude=prelude)
        self._gather = build_gather_packed(key_width)
        self._advance = build_advance()
        self._patch = build_patch(self.specs)
        self._retire = build_retire(key_width, self.specs)
        self._evict = build_evict(self.specs)
        fills = tuple(f for _dt, f in dev_layout(self.specs))
        self._grow_step = jaxtools.instrumented_jit(
            lambda st, cap: _rebuild_live(
                st, st.table.occ & ((st.group_rows != 0) | st.dirty
                                    | st.emitted_valid), cap, fills),
            "hash_agg.grow", static_argnums=(1,), donate_argnums=(0,))
        self._flush_cap = next_pow2(flush_capacity)
        self._counters = jaxtools.PendingCounters()
        self._backlog: List[np.ndarray] = []   # packed, not yet shipped
        self._backlog_rows = 0
        self._backlog_vis = 0                  # visible rows (raw mode)
        # per-stage visible-row vectors from fused dispatches (DMA'd
        # alongside the insert counters; drained at flush)
        self._stage_pending: List = []
        self._flush_idx: Optional[np.ndarray] = None

    @property
    def capacity(self) -> int:
        return self.state.table.capacity

    # -- hot path -------------------------------------------------------
    # Chunks accumulate host-side and dispatch as ONE padded device step:
    # one upload and one dispatch per BATCH_ROWS instead of per chunk
    # (the fixed costs this amortizes are not measured on a local chip).
    # The fixed BATCH_ROWS shape also means exactly one compiled
    # (cap, N) program. Correctness is
    # unaffected — aggregation state is only observed at barrier flush,
    # which drains the backlog first.
    BATCH_ROWS = 1 << 15

    def apply(self, key_lanes: np.ndarray, signs: np.ndarray,
              vis: np.ndarray, inputs: Sequence) -> None:
        assert self._prelude is None, \
            "fused kernel takes raw chunks (apply_raw)"
        with LEDGER.phase("host_pack", kernel=self._span_label):
            packed = pack_chunk(self.key_width, self.specs,
                                np.asarray(key_lanes),
                                np.asarray(signs),
                                np.asarray(vis), inputs)
        # split-fill the batch slab (ISSUE 12): accumulator scatters
        # are row-independent (U-/U+ halves are just ±1 rows — pair
        # adjacency only matters in fused raw mode, which keeps chunk
        # boundaries), so a packed chunk may straddle two dispatches.
        # Without this, chunk sizes that don't divide BATCH_ROWS
        # (hop-expanded 4-copy groups, coalesced odd sizes) quantize
        # each dispatch to ~60% fill and pad the rest on device.
        n = len(signs)
        at = 0
        while at < n:
            room = self.BATCH_ROWS - self._backlog_rows
            if room <= 0:
                self.dispatch_backlog()
                continue
            take = min(n - at, room)
            self._backlog.append(
                packed if take == n else packed[at:at + take])
            self._backlog_rows += take
            at += take
            if self._backlog_rows >= self.BATCH_ROWS:
                self.dispatch_backlog()

    def apply_raw(self, raw: np.ndarray, n_visible: int) -> None:
        """Fused-fragment hot path: backlog one RAW chunk matrix
        (ops/fused.py encode_raw_chunk) plus an always-invisible
        separator row — the traced chain's shifted compares must never
        marry rows across chunk boundaries. Dispatch granularity and
        padding match `apply` exactly."""
        assert self._prelude is not None, \
            "apply_raw needs a fused (prelude) kernel"
        n = raw.shape[0] + 1
        if self._backlog_rows + n > self.BATCH_ROWS:
            self.dispatch_backlog()
        self._backlog.append(raw)
        self._backlog.append(np.zeros((1, raw.shape[1]),
                                      dtype=np.int64))   # separator
        self._backlog_rows += n
        self._backlog_vis += int(n_visible)
        if self._backlog_rows >= self.BATCH_ROWS:
            self.dispatch_backlog()

    def dispatch_backlog(self) -> None:
        if not self._backlog:
            return
        mats, n = self._backlog, self._backlog_rows
        n_vis = self._backlog_vis
        self._backlog, self._backlog_rows = [], 0
        self._backlog_vis = 0
        self._reserve(n)
        raw_mode = self._prelude is not None
        # epoch-staging codec: backlog reassembly into the fixed-shape
        # batch matrix is host_pack; the upload that follows is h2d
        with LEDGER.phase("host_pack", kernel=self._span_label):
            w = mats[0].shape[1]
            cap_rows = self.BATCH_ROWS if n <= self.BATCH_ROWS \
                else next_pow2(n)
            packed = np.zeros((cap_rows, w),
                              dtype=np.int64 if raw_mode else np.int32)
            at = 0                   # pad rows: vis=0
            for m in mats:
                packed[at:at + m.shape[0]] = m
                at += m.shape[0]
        from risingwave_tpu.utils.ledger import note_backlog
        note_backlog(self._span_label, n)
        if raw_mode:
            with spans.dispatch_span(self._span_label, n_vis,
                                     batch_rows=n):
                self.state, ins, stage_rows = self._apply(
                    self.state,
                    jaxtools.upload(packed, kernel=self._span_label))
            jaxtools.start_fetch(stage_rows)
            self._stage_pending.append(stage_rows)
            if self.metrics_label is not None:
                # REAL dispatch accounting: the fused path launches one
                # traced program per backlog flush — count it there,
                # with the batch's true visible-row density
                from risingwave_tpu.utils.metrics import STREAMING
                STREAMING.device_dispatch.inc(
                    1, executor=self.metrics_label)
                STREAMING.rows_per_dispatch.observe(
                    float(n_vis), executor=self.metrics_label)
        else:
            with spans.dispatch_span(self._span_label, n,
                                     batch_rows=n):
                self.state, ins = self._apply(
                    self.state,
                    jaxtools.upload(packed, kernel=self._span_label))
        self._counters.push(ins, n)

    def take_probe_rounds(self) -> tuple:
        """(rounds of probe_insert's loop, steps, rows the rounds
        worked, rows of the steps' batches) since the last call.
        After a flush it covers every step of the epoch: the flush
        adopted the gather's exact count and read the steps' counters."""
        return self._counters.take_rounds()

    def drain_stage_rows(self) -> Optional[np.ndarray]:
        """Sum of per-stage visible-row counts since the last drain
        (fused mode; call at barrier flush — the gather already
        synchronized the queue, so these fetches are landed DMAs)."""
        if not self._stage_pending:
            return None
        total = None
        for v in self._stage_pending:
            a = jaxtools.fetch1(v)
            total = a if total is None else total + a
        self._stage_pending = []
        return np.asarray(total)

    # -- growth ---------------------------------------------------------
    def _reserve(self, n: int) -> None:
        self._counters.drain_ready()
        if self._counters.bound() + n <= ht.MAX_LOAD * self.capacity:
            return
        # bound crossed: collapse it exactly, then grow as needed
        self._counters.drain_all()
        grew = False
        while self._counters.count() + n > ht.MAX_LOAD * self.capacity:
            self._grow()
            grew = True
        if not grew and self.capacity < self.PRESSURE_GROW_CEILING:
            # pressure growth: the blocking drain was caused by the
            # LOOSE bound (the counters' DMAs had not landed yet), not
            # by real occupancy. Doubling the table lets the bound
            # absorb a whole epoch of pessimistic inserts, trading HBM
            # for blocked host reads. Converges in log2
            # steps to a capacity that never drains mid-epoch (the
            # ceiling bounds HBM for adversarially huge epochs).
            self._grow()

    def _grow(self) -> None:
        """Rehash into a doubled table, reclaiming dead groups.

        A slot is live iff its group has rows OR a flush hasn't retired
        it yet (dirty / still-emitted) — tumbling-window churn leaves
        fully retracted groups behind, and carrying them forever would
        grow the table without bound.

        Occupancy accounting: rehash can only RECLAIM (live ⊆ occupied),
        so the pre-grow count stays a valid upper bound — keeping it
        avoids a blocking n_live readback; the next flush header
        collapses it to exact for free."""
        self.state, _n_live = self._grow_step(
            self.state, self.state.table.capacity * 2)

    def retire_below(self, group_pos: int, wm_i64: int) -> None:
        """Watermark state cleaning: drop groups whose ``group_pos``-th
        key column is strictly below the watermark (device-side rebuild,
        no transfers). Call after ``advance`` — a dirty group must emit
        before it can be retired."""
        if self._backlog_rows:
            raise RuntimeError("retire_below with undispatched backlog")
        hi, lo = lanes.split_i64(np.asarray([wm_i64], dtype=np.int64))
        with spans.dispatch_span(f"{self._span_label}.retire", 0):
            self.state, _n_live = self._retire(
                self.state, jnp.int32(hi[0]), jnp.int32(lo[0]),
                group_pos * 3)

    # -- cold tier (state/tier.py) ---------------------------------------
    def evict_keys(self, key_lanes: np.ndarray) -> None:
        """Drop the given groups' device slots (cold-tier eviction;
        their rows stay durable in the value-state table). Call only at
        a barrier, after flush+advance, with no backlog — the tier
        sweeps only there, so the evicted groups are provably clean."""
        if self._backlog_rows:
            raise RuntimeError("evict_keys with undispatched backlog")
        n = len(key_lanes)
        if n == 0:
            return
        cap_n = next_pow2(n)
        lanes = np.zeros((cap_n, self.key_width), dtype=np.int32)
        lanes[:n] = key_lanes
        valid = np.zeros(cap_n, dtype=bool)
        valid[:n] = True
        self.state, _n_live = self._evict(self.state,
                                          jnp.asarray(lanes),
                                          jnp.asarray(valid))
        # occupancy: the rebuild can only RECLAIM (live ⊆ occupied), so
        # the standing upper bound stays valid — same argument as _grow;
        # the next flush header collapses it to exact for free

    def load_groups(self, keys: np.ndarray, group_rows: np.ndarray,
                    acc_cols: Sequence[np.ndarray]) -> None:
        """Reload evicted groups from committed state rows into the
        LIVE table (cold-tier reload-on-touch). Mirrors ``rebuild``'s
        insert without resetting resident state; reloaded groups are
        marked emitted — their outputs were committed downstream before
        eviction, so the next flush derives update pairs, not fresh
        inserts. Dispatches BEFORE the touching chunk's apply (the
        caller drains the backlog via this call)."""
        n = len(group_rows)
        if n == 0:
            return
        # the reload must land before any buffered chunk that may touch
        # the same (still-cold-looking) keys could dispatch after it
        self.dispatch_backlog()
        self._reserve(n)
        dev_cols = encode_host_accs(self.specs, acc_cols)
        table, slots, ins = ht._probe_insert_jit(
            self.state.table, jnp.asarray(keys),
            jnp.ones(n, dtype=bool))
        self._counters.push(ins, n)
        rows32 = jnp.asarray(group_rows, dtype=jnp.int32)
        accs = tuple(a.at[slots].set(jnp.asarray(col))
                     for a, col in zip(self.state.accs, dev_cols))
        self.state = AggState(
            table=table,
            group_rows=self.state.group_rows.at[slots].set(rows32),
            dirty=self.state.dirty,
            accs=accs,
            emitted_valid=self.state.emitted_valid.at[slots].set(True),
            emitted_rows=self.state.emitted_rows.at[slots].set(rows32),
            emitted_accs=tuple(
                a.at[slots].set(jnp.asarray(col))
                for a, col in zip(self.state.emitted_accs, dev_cols)),
        )

    # -- barrier flush ---------------------------------------------------
    def flush(self) -> FlushResult:
        """Gather dirty groups to host and decode — ONE device→host
        transfer. Call ``advance`` after consuming (optionally
        ``patch_accs`` in between)."""
        self.dispatch_backlog()
        while True:
            with spans.dispatch_span(f"{self._span_label}.flush",
                                     self._counters.bound()):
                mat = jaxtools.fetch1(
                    self._gather(self.state, self._flush_cap))
            p = int(mat[0, 0])
            # the gather runs after every queued apply, so its header
            # count subsumes all pending insert counters
            self._counters.reset(int(mat[0, 1]))
            if p <= self._flush_cap:
                break
            self._flush_cap = max(self._flush_cap * 2, next_pow2(p))
        if p == 0:
            self._flush_idx = np.zeros(0, dtype=np.int32)
            return FlushResult.empty(self.specs, self.key_width)
        with LEDGER.phase("host_emit", kernel=self._span_label,
                          stage="agg.decode"):
            data = mat[1:1 + p]
            self._flush_idx = np.ascontiguousarray(data[:, 0])
            return decode_flush_data(self.specs, self.key_width, data)

    def patch_accs(self, decoded: List[Optional[
            Tuple[np.ndarray, np.ndarray]]],
                   raw_accs: Optional[List[np.ndarray]] = None) -> None:
        """Overwrite flushed groups' accumulators (minput recompute).

        See encode_patch_cols for the passthrough contract."""
        idx = self._flush_idx
        assert idx is not None and len(idx) > 0
        dev_cols = encode_patch_cols(self.specs, decoded, raw_accs)
        pad = next_pow2(len(idx))
        idx_padded = np.full(pad, self.capacity, dtype=np.int32)
        idx_padded[:len(idx)] = idx
        padded = tuple(
            np.concatenate([c, np.zeros(pad - len(idx), dtype=c.dtype)])
            for c in dev_cols)
        self.state = self._patch(self.state, jnp.asarray(idx_padded),
                                 padded)

    def advance(self) -> None:
        """Snapshot emitted := current for every dirty slot; clear dirty.
        Fully on device — no transfers."""
        assert self._flush_idx is not None, "flush() first"
        self._flush_idx = None
        self.state = self._advance(self.state)

    # -- recovery ---------------------------------------------------------
    def rebuild(self, keys: np.ndarray, group_rows: np.ndarray,
                acc_cols: Sequence[np.ndarray]) -> None:
        """Reload from committed value-state rows (boot/recovery).

        `acc_cols` uses the HOST layout (acc_dtypes: per call value
        [+ nn]). Restored groups are marked emitted — their outputs were
        committed downstream before the recovery epoch.
        """
        n = len(group_rows)
        cap = max(self.capacity, next_pow2(int(n / ht.MAX_LOAD) + 1))
        self.state = make_agg_state(cap, self.key_width, self.specs)
        self._counters.reset(n)
        self._backlog = []
        self._backlog_rows = 0
        self._backlog_vis = 0
        self._stage_pending = []
        if n == 0:
            return
        dev_cols = encode_host_accs(self.specs, acc_cols)
        table, slots, _ = ht._probe_insert_jit(
            self.state.table, jnp.asarray(keys), jnp.ones(n, dtype=bool))
        accs = tuple(a.at[slots].set(jnp.asarray(col))
                     for a, col in zip(self.state.accs, dev_cols))
        rows_dev = self.state.group_rows.at[slots].set(
            jnp.asarray(group_rows, dtype=jnp.int32))
        self.state = AggState(
            table=table, group_rows=rows_dev, dirty=self.state.dirty,
            accs=accs,
            emitted_valid=self.state.emitted_valid.at[slots].set(True),
            # distinct buffers: the apply step donates the state, and a
            # buffer may be donated at most once per call
            emitted_rows=jnp.copy(rows_dev),
            emitted_accs=tuple(jnp.copy(a) for a in accs),
        )
