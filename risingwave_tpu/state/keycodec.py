"""Memcomparable primary-key encoding.

Reference parity: src/common/src/util/ordered/ and the memcomparable crate —
encoded bytes compare (as unsigned byte strings) in the same order as the
SQL values they encode. Re-designed minimal: we encode host python values
(the state store is host-side; device state flushes through it at barriers).

Values are PHYSICAL: DECIMAL is its scaled-int64 payload, timestamps are µs
ints — the same representation device kernels and state-table rows use, so
the columnar encoder (state_table._encode_key_columns, over this module's
encode_fixed_column / encode_host_column) and the scalar codec produce
identical bytes. Logical→physical normalization happens once,
at chunk ingest (chunk._make_column / types.decimal_to_scaled).

Layout per value:
  0x00                      NULL (nulls sort first, matching our iter tests)
  0x01 <payload>            non-null value

Payloads:
  bool        1 byte 0/1
  int         8 bytes big-endian with sign bit flipped (order-preserving)
  float       IEEE-754 bits; >=0: flip sign bit, <0: invert all bits
  str/bytes   utf-8/raw with 0x00 escaped as 0x00 0xFF, terminated 0x00 0x00
  Decimal     scaled int64 (exact fixed point), same as int
"""

from __future__ import annotations

import struct
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from risingwave_tpu.common.types import DataType

_NULL = b"\x00"
NULL_KEY = _NULL      # a NULL is its tag and no payload, whatever the type
_NONNULL = b"\x01"
_STR_TERM = b"\x00\x00"


def _encode_int(v: int) -> bytes:
    return struct.pack(">Q", (v + (1 << 63)) & ((1 << 64) - 1))


def _decode_int(b: bytes) -> int:
    return struct.unpack(">Q", b)[0] - (1 << 63)


def _encode_float(v: float) -> bytes:
    if v == 0.0:
        v = 0.0  # normalize -0.0: one SQL value, one key (matches hash.py)
    bits = struct.unpack(">Q", struct.pack(">d", v))[0]
    if bits & (1 << 63):
        bits = ~bits & ((1 << 64) - 1)   # negative: invert all
    else:
        bits |= 1 << 63                  # positive: flip sign bit
    return struct.pack(">Q", bits)


def _decode_float(b: bytes) -> float:
    bits = struct.unpack(">Q", b)[0]
    if bits & (1 << 63):
        bits &= ~(1 << 63) & ((1 << 64) - 1)
    else:
        bits = ~bits & ((1 << 64) - 1)
    return struct.unpack(">d", struct.pack(">Q", bits))[0]


def _encode_bytes(v: bytes) -> bytes:
    return v.replace(b"\x00", b"\x00\xff") + _STR_TERM


def _scan_bytes(buf: bytes, pos: int) -> Tuple[bytes, int]:
    out = bytearray()
    while True:
        i = buf.index(b"\x00", pos)
        out += buf[pos:i]
        nxt = buf[i + 1]
        if nxt == 0xFF:
            out += b"\x00"
            pos = i + 2
        elif nxt == 0x00:
            return bytes(out), i + 2
        else:
            raise ValueError("malformed escaped byte string")


def encode_value(v, dt: DataType) -> bytes:
    if v is None:
        return _NULL
    if dt == DataType.BOOLEAN:
        return _NONNULL + (b"\x01" if v else b"\x00")
    if dt in (DataType.FLOAT32, DataType.FLOAT64):
        return _NONNULL + _encode_float(float(v))
    if dt == DataType.DECIMAL:
        # physical scaled-int64 payload (already scaled at chunk ingest)
        return _NONNULL + _encode_int(int(v))
    if dt == DataType.VARCHAR:
        return _NONNULL + _encode_bytes(str(v).encode("utf-8"))
    if dt == DataType.BYTEA:
        return _NONNULL + _encode_bytes(bytes(v))
    # all remaining device types are integral (ints, dates, timestamps)
    return _NONNULL + _encode_int(int(v))


def encode_fixed_column(vals: np.ndarray, dt: DataType) -> np.ndarray:
    """``encode_value`` over the non-null values of one fixed-width
    (device) column → uint8 [n, 1 + width]: the non-null tag and the
    order-preserving payload, as whole-column arithmetic."""
    n = len(vals)
    if dt == DataType.BOOLEAN:
        m = np.ones((n, 2), dtype=np.uint8)
        m[:, 1] = vals.astype(np.uint8)
        return m
    with np.errstate(over="ignore"):
        if dt in (DataType.FLOAT32, DataType.FLOAT64):
            f = vals.astype(np.float64)
            f = np.where(f == 0, 0.0, f)  # -0.0 → 0.0
            bits = f.view(np.uint64)
            neg = (bits >> np.uint64(63)) == 1
            bits = np.where(neg, ~bits, bits | np.uint64(1 << 63))
        else:
            bits = vals.astype(np.int64).view(np.uint64) \
                + np.uint64(1 << 63)
    m = np.ones((n, 9), dtype=np.uint8)
    m[:, 1:] = bits.astype(">u8").view(np.uint8).reshape(n, 8)
    return m


def encode_host_column(values: Iterable, dt: DataType) -> List[bytes]:
    """``encode_value`` over one host-typed column (None is NULL): the
    type is dispatched once for the column, not once per value."""
    if dt == DataType.VARCHAR:
        return [_NULL if v is None else
                _NONNULL + _encode_bytes(str(v).encode("utf-8"))
                for v in values]
    if dt == DataType.BYTEA:
        return [_NULL if v is None else _NONNULL + _encode_bytes(bytes(v))
                for v in values]
    return [encode_value(v, dt) for v in values]


def decode_value(buf: bytes, pos: int, dt: DataType):
    tag = buf[pos]
    pos += 1
    if tag == 0x00:
        return None, pos
    if dt == DataType.BOOLEAN:
        return buf[pos] == 1, pos + 1
    if dt in (DataType.FLOAT32, DataType.FLOAT64):
        return _decode_float(buf[pos:pos + 8]), pos + 8
    if dt == DataType.DECIMAL:
        return _decode_int(buf[pos:pos + 8]), pos + 8
    if dt == DataType.VARCHAR:
        raw, pos = _scan_bytes(buf, pos)
        return raw.decode("utf-8"), pos
    if dt == DataType.BYTEA:
        return _scan_bytes(buf, pos)
    return _decode_int(buf[pos:pos + 8]), pos + 8


def encoded_width(dt: DataType) -> Optional[int]:
    """Bytes a non-null value of the type takes in a key, tag included;
    None where the value says it itself (varchar, bytea)."""
    if dt in (DataType.VARCHAR, DataType.BYTEA):
        return None
    return 2 if dt == DataType.BOOLEAN else 9


def encode_memcomparable(values: Sequence, types: Sequence[DataType]) -> bytes:
    """Encode a pk tuple → order-preserving bytes."""
    return b"".join(encode_value(v, t) for v, t in zip(values, types))


def decode_memcomparable(buf: bytes, types: Sequence[DataType]) -> tuple:
    out: List = []
    pos = 0
    for t in types:
        v, pos = decode_value(buf, pos, t)
        out.append(v)
    return tuple(out)


def encode_vnode_prefix(vnode: int) -> bytes:
    """2-byte big-endian vnode prefix (state_table.rs pk layout)."""
    return struct.pack(">H", vnode)
