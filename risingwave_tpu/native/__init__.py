"""Loader for the native (C++) runtime kernels.

Compiles native/rw_native.cpp with g++ on first use (cached as a .so
next to the source, untracked) and exposes ctypes wrappers. Every entry
point has a byte-identical pure-Python twin in
risingwave_tpu/storage/sst.py (tested): `lib()` returns None when the
library cannot be had and callers take the twin. That is never silent:
a build or load that was tried and failed prints one line to stderr
with the compiler's (or loader's) message.
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
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", _SO, _SRC],
            check=True, capture_output=True, timeout=120)
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
        l.rw_block_encode.restype = ctypes.c_long
        l.rw_block_encode.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_long]
        l.rw_block_decode.restype = ctypes.c_long
        l.rw_block_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_long,
            ctypes.c_char_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_long]
        l.rw_bloom_build.restype = None
        l.rw_bloom_build.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_long]
        l.rw_bloom_may_contain.restype = ctypes.c_int32
        l.rw_bloom_may_contain.argtypes = [
            ctypes.c_char_p, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_long, ctypes.c_int32]
        _lib = l
        return _lib
