"""32-bit lane codecs: how 64-bit logical values live on the TPU.

TPU v5e has no native int64; XLA's x64-rewrite emulates it, and emulated
64-bit *scatter* is catastrophically slow (measured ~1000x vs int32 on
that chip). The device side of every stateful kernel
therefore speaks int32/float32 exclusively; 64-bit logical values are
(de)composed on the host with vectorized numpy. Three codecs:

- **key lanes** (`split_i64`): bijective (hi, lo) int32 pair. Equality of
  pairs == equality of values; that's all a hash key needs.
- **sum limbs** (`sum_limbs`): signed base-2^17 positional decomposition
  into 4 int32 limbs. Limb scatter-adds of a ≤2^13-row chunk stay within
  int32 (17+13 < 31); a per-chunk carry pass renormalizes so limbs never
  overflow across chunks. Exact for |Σ| < 2^63 — money aggregation keeps
  reference semantics (sum of scaled-int64 DECIMAL is exact).
- **order lanes** (`order_lanes_*`): order-preserving (hi, lo) int32 pair —
  lexicographic (hi, lo) comparison == value comparison — so MIN/MAX run
  as two int32 scatter-min/max passes. Works for ints and floats (floats
  use the standard total-order bit trick).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

LIMB_BITS = 17
N_LIMBS = 4
# chunk row-count bound that keeps limb scatter-adds inside int32
MAX_CHUNK_ROWS = 1 << (31 - LIMB_BITS - 1)       # 8192

_BIAS32 = np.int64(1) << np.int64(31)
_MASK32 = np.int64(0xFFFFFFFF)


# -- bijective key lanes ----------------------------------------------------

def split_i64(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64[N] → (hi, lo) int32[N], bijective."""
    v = v.astype(np.int64, copy=False)
    hi = (v >> np.int64(32)).astype(np.int32)
    lo = (v & _MASK32).astype(np.uint32).view(np.int32)
    return hi, lo


def merge_i64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (hi.astype(np.int64) << np.int64(32)) | \
        lo.view(np.uint32).astype(np.int64)


# -- exact integer sums: signed base-2^17 limbs -----------------------------

def sum_limbs(v: np.ndarray) -> Tuple[np.ndarray, ...]:
    """int64[N] → N_LIMBS int32 limb arrays; v = Σ limb_i << (17*i).

    Limbs 0..2 ∈ [0, 2^17); limb 3 carries the sign (arithmetic shift)."""
    v = v.astype(np.int64, copy=False)
    out = []
    for i in range(N_LIMBS - 1):
        out.append(((v >> np.int64(LIMB_BITS * i))
                    & np.int64((1 << LIMB_BITS) - 1)).astype(np.int32))
    out.append((v >> np.int64(LIMB_BITS * (N_LIMBS - 1))).astype(np.int32))
    return tuple(out)


def merge_limbs(*limbs: np.ndarray) -> np.ndarray:
    """Inverse of sum_limbs for arbitrary (possibly unnormalized) limbs."""
    acc = np.zeros(limbs[0].shape, dtype=np.int64)
    for i, l in enumerate(limbs):
        acc += l.astype(np.int64) << np.int64(LIMB_BITS * i)
    return acc


# -- payload lanes: bit-preserving i64 image for stored row columns ---------
# The join's device-resident payload store (ops/hash_join.py) keeps one
# (hi, lo, valid) int32 lane triple per device-typed column, indexed by
# row ref. Unlike key lanes (to_i64 normalizes -0.0 so it GROUPS with
# 0.0), payload values must round-trip bit-exactly — the device-emit
# path has to be indistinguishable from a host arena gather.


def payload_i64(v: np.ndarray) -> np.ndarray:
    """Column values → int64, bit-preserving (host numpy; a traced
    program takes a float column's image as it was uploaded — see
    "float columns from their int64 bit image" below)."""
    dt = np.dtype(v.dtype)
    if dt == np.float64:
        return v.view(np.int64)
    if dt == np.float32:
        return v.astype(np.float64).view(np.int64)
    return v.astype(np.int64)


def payload_lanes(pairs):
    """[(values, validity | None)] → int32[N, 3p] payload lanes on the
    host (_JoinSide payload_rows / payload_from_arena)."""
    return payload_lanes_i64(
        [(payload_i64(vals), ok) for vals, ok in pairs], np)


def payload_lanes_i64(pairs, xp=np):
    """[(int64 bit image, validity | None)] → int32[N, 3p] payload
    lanes — (hi, lo, valid) per column, NULL values zeroed. THE one
    layout serving the host paths (through payload_lanes) and the
    traced join prelude (xp=jnp, images straight from the raw upload)
    — the device scatter and the emit decode both depend on it, so
    there is exactly one copy."""
    out = []
    for v64, ok in pairs:
        n = v64.shape[0]
        okm = xp.ones(n, dtype=bool) if ok is None else ok
        hi, lo = split_i64(xp.where(okm, v64, xp.int64(0)))
        out.append(hi)
        out.append(lo)
        out.append(okm.astype(xp.int32))
    if not out:
        return xp.zeros((0, 0), dtype=xp.int32)
    return xp.stack(out, axis=1)


def decode_payload_i64(v64: np.ndarray, dtype: np.dtype) -> np.ndarray:
    """Host inverse of payload_i64 (numpy only; runs on the fetched
    packed probe matrix)."""
    dtype = np.dtype(dtype)
    if dtype == np.float64:
        return v64.view(np.float64)
    if dtype == np.float32:
        return v64.view(np.float64).astype(np.float32)
    if dtype == np.bool_:
        return v64 != 0
    return v64.astype(dtype)


# -- float columns from their int64 bit image -------------------------------
# The TPU compiler's 64-bit rewrite has no f64 → s64/u64 bitcast (the
# other direction, f64 arithmetic and integer bitcasts compile), so a
# traced program never takes a float's bits. The host ships every float
# column as the int64 image of its f64 value (ops/fused.encode_raw_chunk)
# and the traced key / order / payload codecs work on that image with
# integer ops — bit-identical to the numpy float codecs, which are
# written in terms of the same functions.

_I64_MIN = np.int64(-(1 << 63))         # the bit image of -0.0


def float_key_image(img):
    """f64 bit image → the key image: -0.0 folds into 0.0 so the two
    group (and order) together; every other value keeps its bits."""
    from risingwave_tpu.common.chunk import get_xp
    xp = get_xp(img)
    return xp.where(img == _I64_MIN, xp.int64(0), img)


def order_lanes_from_image(img) -> Tuple[np.ndarray, np.ndarray]:
    """f64 bit image → order-preserving (hi, lo) int32 — order_lanes of
    the float values, without touching a float."""
    return _lanes_from_u64(_order_u64_from_image(img))


# -- order-preserving lanes for MIN/MAX -------------------------------------

def _order_u64_from_i64(v: np.ndarray) -> np.ndarray:
    """int64 → uint64 where unsigned order == signed order."""
    return (v.astype(np.int64) ^ (np.int64(1) << np.int64(63))) \
        .view(np.uint64)


def _order_u64_from_f64(v: np.ndarray) -> np.ndarray:
    """float64 → uint64 total order (IEEE bit trick; -0.0 == 0.0).
    Host numpy; its traced twin is order_lanes_from_image."""
    from risingwave_tpu.common.chunk import get_xp
    if get_xp(v) is not np:
        raise TypeError(
            "float order lanes under jit: take the column's uploaded "
            "bit image (order_lanes_from_image), not a bitcast")
    return _order_u64_from_image(
        v.astype(np.float64, copy=False).view(np.int64))


def _order_u64_from_image(img):
    """The same total order from the f64 value's int64 bit image:
    integer ops only, xp-generic."""
    from risingwave_tpu.common.chunk import get_xp
    xp = get_xp(img)
    bits = float_key_image(img).astype(xp.uint64)
    neg = (bits >> np.uint64(63)) == 1
    return xp.where(neg, ~bits, bits | (np.uint64(1) << np.uint64(63)))


def _lanes_from_u64(m: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """uint64 order key → (hi, lo) int32 with lexicographic int32 order."""
    hi = ((m >> np.uint64(32)).astype(np.int64) - _BIAS32).astype(np.int32)
    lo = ((m & np.uint64(0xFFFFFFFF)).astype(np.int64)
          - _BIAS32).astype(np.int32)
    return hi, lo


def _u64_from_lanes(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    h = (hi.astype(np.int64) + _BIAS32).astype(np.uint64)
    l = (lo.astype(np.int64) + _BIAS32).astype(np.uint64)
    return (h << np.uint64(32)) | l


def order_lanes(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """value array (any device dtype) → order-preserving (hi, lo) int32."""
    if np.issubdtype(v.dtype, np.floating):
        return _lanes_from_u64(_order_u64_from_f64(v))
    if v.dtype == np.bool_:
        v = v.astype(np.int64)
    return _lanes_from_u64(_order_u64_from_i64(v))


def inv_order_lanes(hi: np.ndarray, lo: np.ndarray,
                    dtype: np.dtype) -> np.ndarray:
    m = _u64_from_lanes(hi, lo)
    if np.issubdtype(dtype, np.floating):
        neg = (m >> np.uint64(63)) == 0
        bits = np.where(neg, ~m, m & ~(np.uint64(1) << np.uint64(63)))
        return bits.view(np.float64).astype(dtype)
    v = (m.view(np.int64) ^ (np.int64(1) << np.int64(63)))
    if dtype == np.bool_:
        return v != 0
    return v.astype(dtype)
