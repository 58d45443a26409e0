"""Loader for the native (C++) runtime kernels.

Compiles native/rw_native.cpp with g++ on first use (cached as a .so
next to the source, untracked) and exposes ctypes wrappers. Every entry
point has a byte-identical pure-Python twin (tested): `lib()` returns
None when the library cannot be had and callers take the twin. That is
never silent: a build or load that was tried and failed prints one
line to stderr with the compiler's (or loader's) message.

  rw_block_encode, rw_block_decode,      twins in storage/sst.py
  rw_bloom_build, rw_bloom_may_contain   (_BlockBuilder, _iter_block_py,
                                         _BloomBuilder, bloom_may_contain)
  rw_merge_gc, rw_key_columns,           the compaction merge over
  rw_gather                              columnar runs (keys blob, key
                                         lengths, values blob, value
                                         lengths) and its run-at-a-time
                                         writer; one twin for the three:
                                         storage/merge._merge_python, the
                                         row-at-a-time loop over
                                         SstBuilder (byte-identical SSTs,
                                         tests/test_compaction_merge.py)
  rw_full_keys                           the checkpoint build's keys, a
                                         (table, epoch) batch at a time;
                                         twin storage/sst.full_key
  rw_encode_rows                         its stored values, one table's
                                         rows by the column; twin
                                         storage/value_codec.encode_row
  rw_argsort_keys                        its order; twin: sorting the
                                         full keys as Python bytes
                                         (the three: byte-identical SSTs,
                                         tests/test_checkpoint_build.py)
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import Optional

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native",
    "rw_native.cpp")
_SO = os.path.join(os.path.dirname(_SRC), "librw_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _say_python(why: str) -> None:
    print(f"rw_native: SST codec falls back to Python: {why}",
          file=sys.stderr)


def _compile() -> bool:
    # link under a name of this process's own, then rename: processes
    # that start together (pytest workers, a cluster's roles) each
    # load a whole library, never one another's half-written file
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except subprocess.CalledProcessError as e:
        lines = e.stderr.decode(errors="replace").strip().splitlines()
        _say_python("g++ failed: " + next(
            (ln for ln in lines if "error" in ln),
            lines[-1] if lines else f"exit code {e.returncode}"))
    except (OSError, subprocess.SubprocessError) as e:
        _say_python(f"could not run g++: {e}")
    return False


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None (pure-Python fallback)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("RW_TPU_DISABLE_NATIVE"):
            return None
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                if not _compile():
                    return None
            l = ctypes.CDLL(_SO)
        except OSError as e:
            _say_python(f"could not load {_SO}: {e}")
            return None
        try:
            # pointer arguments are c_void_p: bytes, ctypes buffers and
            # plain addresses (a numpy buffer at an offset: the compaction
            # merge, storage/merge.py) all pass
            vp = ctypes.c_void_p
            l.rw_block_encode.restype = ctypes.c_long
            l.rw_block_encode.argtypes = [
                vp, vp, vp, vp, ctypes.c_int32, ctypes.c_int32,
                vp, ctypes.c_long]
            l.rw_block_decode.restype = ctypes.c_long
            l.rw_block_decode.argtypes = [
                vp, ctypes.c_long, vp, ctypes.c_long, vp,
                vp, ctypes.c_long, vp, ctypes.c_long]
            l.rw_bloom_build.restype = None
            l.rw_bloom_build.argtypes = [
                vp, vp, ctypes.c_int32, ctypes.c_int32, vp,
                ctypes.c_long]
            l.rw_bloom_may_contain.restype = ctypes.c_int32
            l.rw_bloom_may_contain.argtypes = [
                ctypes.c_char_p, ctypes.c_int32,
                ctypes.c_char_p, ctypes.c_long, ctypes.c_int32]
            l.rw_gather.restype = ctypes.c_long
            l.rw_gather.argtypes = [vp, vp, vp, ctypes.c_long, vp]
            l.rw_key_columns.restype = ctypes.c_long
            l.rw_key_columns.argtypes = [
                vp, vp, ctypes.c_long, vp, ctypes.c_long, vp, vp]
            l.rw_merge_gc.restype = ctypes.c_long
            l.rw_merge_gc.argtypes = [
                ctypes.c_int32, vp, vp, vp, vp, vp, vp,
                vp, ctypes.c_long, ctypes.c_uint64, ctypes.c_int32,
                vp, ctypes.c_long, vp, vp, ctypes.c_long, vp,
                ctypes.c_long, ctypes.POINTER(ctypes.c_int64)]
            l.rw_full_keys.restype = ctypes.c_long
            l.rw_full_keys.argtypes = [
                vp, vp, ctypes.c_long, vp, vp, vp, ctypes.c_long, vp]
            l.rw_encode_rows.restype = ctypes.c_long
            l.rw_encode_rows.argtypes = [
                ctypes.c_long, vp, ctypes.c_int32, vp, vp, vp, vp,
                vp, ctypes.c_long, vp]
            l.rw_argsort_keys.restype = ctypes.c_long
            l.rw_argsort_keys.argtypes = [vp, vp, ctypes.c_long, vp]
        except AttributeError as e:
            # a library built from an older source that the mtime
            # check took for current
            _say_python(f"{_SO} is stale ({e}): delete it to rebuild")
            return None
        _lib = l
        return _lib
