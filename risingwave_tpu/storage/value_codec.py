"""Compact row value encoding for SSTs.

Reference parity: the *role* of src/common/src/util/value_encoding/ —
a schema-light byte encoding of physical rows for storage values. The
encoding is tag-per-value (rows are small; SST blocks amortize), with
zigzag varints for ints: physical rows in this framework are host
tuples of int / float / str / bool / None (DECIMAL is its scaled int64,
timestamps are µs ints — see state/state_table.py).

``encode_row`` is the codec: one row, value by value. ``encode_values``
is its twin for a whole batch of one table's values where the native
library is loaded: the rows are held by the column and one native pass
(``rw_encode_rows``) writes the bytes ``encode_row`` would, with no
Python call per value. What a column holds decides whether the batch
goes that way, nothing else does.
"""

from __future__ import annotations

import struct
import ctypes
from typing import List, Optional, Sequence, Tuple

import numpy as np

_INT64_MIN = -(1 << 63)
_INT64_MAX = (1 << 63) - 1

_T_NULL = 0
_T_INT = 1       # zigzag varint
_T_FLOAT = 2     # 8-byte little-endian double
_T_STR = 3       # varint len + utf8
_T_TRUE = 4
_T_FALSE = 5
_T_BYTES = 6


def write_uvarint(out: bytearray, v: int) -> None:
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)


def read_uvarint(buf: bytes, pos: int) -> Tuple[int, int]:
    shift = 0
    v = 0
    while True:
        b = buf[pos]
        pos += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, pos
        shift += 7


def _zigzag(v: int) -> int:
    return (v << 1) ^ (v >> 63) if v >= 0 else ((-v) << 1) - 1


def _unzigzag(v: int) -> int:
    return (v >> 1) if (v & 1) == 0 else -((v + 1) >> 1)


def encode_row(row: Tuple) -> bytes:
    out = bytearray()
    write_uvarint(out, len(row))
    for v in row:
        if v is None:
            out.append(_T_NULL)
        elif isinstance(v, (bool, np.bool_)):
            out.append(_T_TRUE if v else _T_FALSE)
        elif isinstance(v, int) or hasattr(v, "__index__"):
            iv = int(v)
            if not (_INT64_MIN <= iv <= _INT64_MAX):
                raise TypeError(f"int out of int64 range: {iv}")
            out.append(_T_INT)
            write_uvarint(out, _zigzag(iv))
        elif isinstance(v, float) or (hasattr(v, "dtype")
                                      and v.dtype.kind == "f"):
            out.append(_T_FLOAT)
            out.extend(struct.pack("<d", float(v)))
        elif isinstance(v, str):
            out.append(_T_STR)
            b = v.encode("utf-8")
            write_uvarint(out, len(b))
            out.extend(b)
        elif isinstance(v, (bytes, bytearray)):
            out.append(_T_BYTES)
            write_uvarint(out, len(v))
            out.extend(v)
        else:
            raise TypeError(f"unencodable value {v!r} ({type(v)})")
    return bytes(out)


def decode_row(buf: bytes) -> Tuple:
    n, pos = read_uvarint(buf, 0)
    out: List[Optional[object]] = []
    for _ in range(n):
        tag = buf[pos]
        pos += 1
        if tag == _T_NULL:
            out.append(None)
        elif tag == _T_TRUE:
            out.append(True)
        elif tag == _T_FALSE:
            out.append(False)
        elif tag == _T_INT:
            z, pos = read_uvarint(buf, pos)
            out.append(_unzigzag(z))
        elif tag == _T_FLOAT:
            out.append(struct.unpack_from("<d", buf, pos)[0])
            pos += 8
        elif tag == _T_STR:
            ln, pos = read_uvarint(buf, pos)
            out.append(buf[pos:pos + ln].decode("utf-8"))
            pos += ln
        elif tag == _T_BYTES:
            ln, pos = read_uvarint(buf, pos)
            out.append(bytes(buf[pos:pos + ln]))
            pos += ln
        else:
            raise ValueError(f"bad value tag {tag}")
    return tuple(out)


# -- a batch by the column (native library only) ------------------------------

# rw_encode_rows' column kinds
_K_NULL, _K_INT, _K_FLOAT, _K_STR, _K_BYTES, _K_BOOL = range(6)
# what rw_encode_rows wants free before a row's header and before each
# value, whatever its kind (blob bytes apart): tag and a 64-bit varint
_ITEM_MAX_BYTES = 11

# the exact types a column may hold to go as one array: what
# ``encode_row`` gives the kind's tag, and np.array converts to the
# kind's dtype without a change of value. Anything else (an int
# subclass, np.uint64, a mix of kinds) is encode_row's to judge.
_NONE = type(None)
_KIND_TYPES = (
    (_K_INT, frozenset((int, np.int8, np.int16, np.int32, np.int64,
                        np.uint8, np.uint16, np.uint32)), np.int64, 0),
    (_K_FLOAT, frozenset((float, np.float16, np.float32, np.float64)),
     np.float64, 0.0),
    (_K_STR, frozenset((str,)), None, ""),
    (_K_BYTES, frozenset((bytes, bytearray)), None, b""),
    (_K_BOOL, frozenset((bool, np.bool_)), np.uint8, False),
)


def _column(col: Sequence) -> Optional[tuple]:
    """One column of a batch as ``rw_encode_rows`` reads it: (kind,
    data array, validity bytes or None, int32 lengths or None); None
    where the column cannot go as a whole."""
    types = set(map(type, col))
    nulls = _NONE in types
    types.discard(_NONE)
    if not types:
        return _K_NULL, None, None, None
    for kind, accepted, dtype, fill in _KIND_TYPES:
        if types <= accepted:
            break
    else:
        return None
    valid = None
    if nulls:
        valid = np.array([v is not None for v in col], dtype=np.uint8)
        col = [fill if v is None else v for v in col]
    if dtype is not None:
        # an int outside int64 raises OverflowError: the caller's
        return kind, np.array(col, dtype=dtype), valid, None
    if kind == _K_STR:
        col = [v.encode("utf-8") for v in col]
    lens = np.fromiter(map(len, col), dtype=np.int32, count=len(col))
    return kind, np.frombuffer(b"".join(col), dtype=np.uint8), valid, lens


def _columns(rows: list) -> Optional[List[tuple]]:
    try:
        columns = [_column(col) for col in zip(*rows, strict=True)]
    except (TypeError, ValueError, OverflowError):
        # rows of differing arity, a row that is no sequence, an int
        # outside int64, a str that is not UTF-8: row by row, which
        # encodes what can be and raises what encode_row raises
        return None
    return None if any(c is None for c in columns) else columns


def encode_values(nat, values: Sequence[Optional[Tuple]]
                  ) -> Tuple[np.ndarray, np.ndarray, bool]:
    """The stored values of one table's batch, laid back to back: per
    value the tombstone flag byte (1 for ``None``, and no row) and the
    row's ``encode_row`` bytes. Returns the uint8 blob, the int32
    lengths, and whether the batch went by the column (one
    ``rw_encode_rows`` pass) or row by row through ``encode_row``,
    which is the reference: the same bytes either way."""
    n = len(values)
    rows = [v for v in values if v is not None]
    columns = _columns(rows)
    if columns is None:
        stored = [b"\x01" if v is None else b"\x00" + encode_row(v)
                  for v in values]
        return (np.frombuffer(b"".join(stored), dtype=np.uint8),
                np.fromiter(map(len, stored), dtype=np.int32, count=n),
                False)
    if len(rows) < n:
        tombs = np.fromiter((v is None for v in values), dtype=np.uint8,
                            count=n)
    else:
        tombs = np.zeros(n, dtype=np.uint8)
    k = len(columns)
    cap = _ITEM_MAX_BYTES * (n + k * len(rows)) + sum(
        len(data) for _kind, data, _valid, lens in columns
        if lens is not None)
    out = np.empty(cap, dtype=np.uint8)
    out_lens = np.empty(n, dtype=np.int32)
    kinds = np.array([c[0] for c in columns], dtype=np.int32)

    def pointers(arrays):
        return (ctypes.c_void_p * k)(
            *[None if a is None else a.ctypes.data for a in arrays])

    size = nat.rw_encode_rows(
        n, tombs.ctypes.data, k, kinds.ctypes.data,
        pointers([c[1] for c in columns]),
        pointers([c[2] for c in columns]),
        pointers([c[3] for c in columns]),
        out.ctypes.data, cap, out_lens.ctypes.data)
    if size < 0:
        raise RuntimeError(f"rw_encode_rows overran its own bound ({size})")
    return out[:size], out_lens, True
