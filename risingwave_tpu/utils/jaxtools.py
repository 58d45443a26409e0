"""JAX runtime knobs shared by every entry point that runs kernels.

The stateful kernels compile one XLA program per (table capacity, chunk
rows) shape pair; growth doublings therefore trigger a handful of
compiles per process lifetime. The persistent compilation cache
(``enable_compilation_cache``) makes those a one-time cost per machine
instead of per run; chip_smoke.py prints the cold and the warm count.

``fetch``: device reads go through fetch()/fetch_async, which start the
device→host copy (``copy_to_host_async()``), wait for it without
holding the GIL and only then materialize — so a read never blocks the
event loop on the device. What a plain blocking read (``np.asarray`` /
``int()`` on a jax array) costs on a local chip is the number
chip_smoke.py prints; the rule "every read through fetch" awaits a
trace before it is kept or dropped (ROADMAP D1).
"""

from __future__ import annotations

import os
import re
import zlib
from typing import Dict, List, Optional

import numpy as np

from risingwave_tpu.utils import ledger as _ledger

_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


# every InstrumentedJit by label (last construction wins): the
# compiled-program cost-analysis registry behind EXPLAIN's kernel-cost
# footer, ctl phases and the device_kernel_* gauges
KERNELS: Dict[str, "InstrumentedJit"] = {}

# True while cost_analysis() lowers a kernel: the traced body re-runs
# during that lowering, and its note_compile/mark_stale side effects
# (recompile counter, ledger warmup mark, shape recapture) must NOT
# fire — a report read is not a compile event (RecompileGuard would
# trip on an EXPLAIN otherwise)
_COST_LOWERING = False


class InstrumentedJit:
    """A jitted kernel plus the bookkeeping the observability layer
    needs: (re)trace counting (note_compile inside the traced body)
    and compiled-program cost analysis. Whenever a call (re)traces —
    the traced body marks the instance stale — the call's argument
    SHAPES are captured (jax.ShapeDtypeStruct leaves, no array
    pinning), so the analysis tracks the LATEST compiled shape bucket
    through capacity growth. ``cost_analysis()`` lowers against them
    on demand, which hits the in-process/persistent compilation cache
    instead of re-running XLA, and returns the HLO cost model's
    flops / bytes-accessed — the yardstick device_compute
    measurements are sanity-checked against."""

    __slots__ = ("label", "_jit", "_args", "_kw", "_cost", "_stale")

    # sentinel: analysis attempted and unavailable on this backend —
    # cached so an EXPLAIN never re-lowers per statement
    _UNAVAILABLE = object()

    def __init__(self, jitted, label: str):
        self.label = label
        self._jit = jitted
        self._args = None
        self._kw = None
        self._cost = None
        self._stale = True             # first call always captures
        KERNELS[label] = self

    def __call__(self, *args, **kw):
        out = self._jit(*args, **kw)
        if self._stale:
            # capture AFTER the call: a retrace flips the flag while
            # jax traces, so the shapes recorded always belong to a
            # program that actually compiled (donated args keep their
            # aval — .shape/.dtype stay readable past the buffer)
            import jax

            def _abstract(x):
                if not (hasattr(x, "shape") and hasattr(x, "dtype")):
                    return x
                # keep the sharding when the aval supports it: a
                # mesh kernel's cost lowering then matches the LIVE
                # executable's cache entry instead of compiling a
                # default-sharded twin on the reporting path
                try:
                    sh = getattr(x, "sharding", None)
                except Exception:      # noqa: BLE001 — donated buffer
                    sh = None
                if sh is not None:
                    try:
                        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                    sharding=sh)
                    except TypeError:   # older jax: no sharding param
                        pass
                return jax.ShapeDtypeStruct(x.shape, x.dtype)

            self._args = jax.tree.map(_abstract, args)
            self._kw = jax.tree.map(_abstract, kw)
            self._cost = None
            self._stale = False
        return out

    def mark_stale(self) -> None:
        """A (re)trace happened: recapture shapes at this call."""
        self._stale = True

    def cost_analysis(self) -> Optional[dict]:
        """{'flops': f, 'bytes_accessed': b} for the latest-captured
        shapes, or None (never called yet / backend without a cost
        model). Both outcomes cache — repeated reads never re-lower."""
        if self._cost is self._UNAVAILABLE:
            return None
        if self._cost is not None:
            return self._cost
        if self._args is None:
            return None
        global _COST_LOWERING
        _COST_LOWERING = True
        try:
            ca = self._jit.lower(*self._args,
                                 **self._kw).compile().cost_analysis()
        except Exception:              # noqa: BLE001 — backend-dependent
            self._cost = self._UNAVAILABLE
            return None
        finally:
            _COST_LOWERING = False
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            self._cost = self._UNAVAILABLE
            return None
        self._cost = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed",
                                           ca.get("bytes_accessed",
                                                  0.0))),
        }
        return self._cost


def kernel_cost_rows() -> List[tuple]:
    """(label, flops, bytes_accessed) per registered kernel with an
    available cost analysis, sorted by label."""
    out = []
    for label in sorted(KERNELS):
        ca = KERNELS[label].cost_analysis()
        if ca is not None:
            out.append((label, ca["flops"], ca["bytes_accessed"]))
    return out


def publish_kernel_costs() -> int:
    """Refresh the device_kernel_flops/bytes_accessed gauges from the
    registry (lazy by design: cost analysis compiles on first read, so
    it runs at report points — ctl phases — not on the
    hot path). Returns the number of kernels published."""
    from risingwave_tpu.utils.metrics import STREAMING
    rows = kernel_cost_rows()
    for label, flops, nbytes in rows:
        STREAMING.kernel_flops.set(flops, kernel=label)
        STREAMING.kernel_bytes_accessed.set(nbytes, kernel=label)
    return len(rows)


def program_name(label: str) -> str:
    """The name a kernel's program goes by in the device trace and in
    the compile cache's file names: the label's stem with every
    non-word character an underscore, and, where the label carries a
    bracketed variant (a fused prelude's signature, which can run to
    hundreds of characters; a cache file's name may not), eight hex
    digits of it. The same label gives the same name in every run."""
    stem, bracket, variant = label.partition("[")
    name = re.sub(r"\W", "_", stem)[:64]
    if bracket:
        name += "_%08x" % zlib.crc32(variant.encode())
    return name


def instrumented_jit(fn, label: str | None = None, **jit_kw):
    """``jax.jit`` with (re)trace visibility: the wrapper's Python body
    runs only while jax TRACES it — once per new input shape bucket —
    so each execution of the hook is exactly one compile event. It
    lands in ``stream_kernel_recompile_count{kernel=label}`` and as a
    compile span in the current epoch's trace (utils/spans.py), making
    warmup compiles and steady-state shape-churn recompiles visible
    instead of silent multi-second stalls. Steady state pays nothing:
    jit dispatches the cached executable without entering the body.

    Returns an InstrumentedJit: call it like the jitted function; its
    ``cost_analysis()`` serves the compiled program's flops/bytes."""
    import functools

    import jax

    name = label or getattr(fn, "__name__", "kernel")
    inst_box: list = []

    @functools.wraps(fn)
    def traced(*a, **k):
        if not _COST_LOWERING:
            from risingwave_tpu.utils.spans import note_compile
            note_compile(name)
            if inst_box:
                # this call is (re)tracing: the wrapper recaptures the
                # call's shapes so cost_analysis follows growth
                inst_box[0].mark_stale()
        return fn(*a, **k)

    # the program carries the kernel's label: the profiler's `XLA
    # Modules` line and the compile cache read jit_hash_join_epoch_apply
    # where they read jit_ap, the inner function's name
    traced.__name__ = traced.__qualname__ = program_name(name)
    inst = InstrumentedJit(jax.jit(traced, **jit_kw), name)
    inst_box.append(inst)
    return inst


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its
    directory. The directory is placed from OUTSIDE: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code; otherwise it is the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so a
    directory that moves never hits). Every entry point that runs
    kernels calls this once before its first compile: ``serve`` /
    ``playground`` / ``serve-cluster``, the cluster worker,
    benchmark/run.py, chip_smoke.py and tests/conftest.py."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _DEFAULT_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache what costs a quarter of a second or more to compile (JAX's
    # own floor is a second; the kernels of the served path compile for
    # seconds each on the chip). NOT every program: JAX writes a cache
    # entry in place, not atomically, so the more tiny programs a dozen
    # processes sharing one directory write (pytest workers and their
    # cluster children), the likelier one of them reads another's
    # half-written entry.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)
    return cache_dir


def start_fetch(*arrays) -> None:
    """Kick the device→host DMA without waiting (no-op on host arrays)."""
    for a in arrays:
        f = getattr(a, "copy_to_host_async", None)
        if f is not None:
            f()


def _not_ready(arrays) -> List:
    """Arrays still computing/in DMA (host numpy is always ready)."""
    out = []
    for a in arrays:
        ready = getattr(a, "is_ready", None)
        if ready is not None and not ready():
            out.append(a)
    return out


def _ledger_d2h(arrays, out) -> None:
    """Count the device→host payload of a completed fetch (host numpy
    pass-throughs excluded — they never crossed the bus)."""
    nbytes = sum(o.nbytes for a, o in zip(arrays, out)
                 if hasattr(a, "copy_to_host_async"))
    if nbytes:
        _ledger.LEDGER.add_bytes("d2h", nbytes)


def _wait_ready(pending, poll_s: float) -> int:
    """The ONE copy of the ready-wait ladder: GIL-yield spins first
    (XLA host compute lands in µs — a fixed 2ms quantum was the q8
    hot path's single biggest cost on CPU), then sub-ms naps, then
    the coarse `poll_s`. Returns how many polls slept the coarse
    quantum: a wait of few of them is mostly quantisation."""
    import time

    spins = 0
    while pending:
        if spins < 50:
            time.sleep(0)              # yield the GIL; compute runs
        elif spins < 80:
            time.sleep(0.0002)
        else:
            time.sleep(poll_s)
        spins += 1
        pending = _not_ready(pending)
    return max(0, spins - 80)


def fetch(*arrays, poll_s: float = 0.002) -> List[np.ndarray]:
    """Read device arrays via the async-DMA path (see module docstring).

    Starts all copies first so transfers overlap, polls readiness
    (yielding the GIL while the device computes), then materializes.
    Host numpy arrays pass through untouched.

    Phase ledger: the ready-wait segment is the device's compute tail
    as the host observes it under async dispatch (device_compute,
    stage ``wait``: history ``device.wait.<kernel>`` under the label of
    the enclosing dispatch, and annotation ``phase.device_compute.wait``
    with the coarse polls as its ``coarse_polls`` stat); the
    materialization is the d2h transfer, with exact bytes.
    """
    start_fetch(*arrays)
    pending = _not_ready(arrays)
    if pending:
        with _ledger.LEDGER.phase("device_compute", stage="wait") as ann:
            ann.set_metadata(
                coarse_polls=_wait_ready(pending, poll_s))
    with _ledger.LEDGER.phase("d2h"):
        out = [np.asarray(a) for a in arrays]
    _ledger_d2h(arrays, out)
    return out


def fetch1(array) -> np.ndarray:
    return fetch(array)[0]


async def fetch_async(*arrays, poll_s: float = 0.001) -> List[np.ndarray]:
    """fetch() that yields to the event loop during the wait, so
    barrier/actor coroutines keep flowing during the DMA. Same wait
    ladder as fetch(): zero-delay yields first (they still run other
    ready coroutines), timed naps only once the wait is clearly long.

    Ledger note: the wait here is NOT attributed to device_compute —
    other coroutines run during the yields and their own phases own
    that wall time; only the materialization (d2h, with bytes) is."""
    import asyncio

    start_fetch(*arrays)
    pending = _not_ready(arrays)
    spins = 0
    while pending:
        await asyncio.sleep(0 if spins < 50 else poll_s)
        spins += 1
        pending = _not_ready(pending)
    with _ledger.LEDGER.phase("d2h"):
        out = [np.asarray(a) for a in arrays]
    _ledger_d2h(arrays, out)
    return out


def upload(host, sharding=None, kernel: Optional[str] = None):
    """``jax.device_put`` with h2d ledger accounting (phase time +
    exact payload bytes under ``stream_transfer_bytes_total``). EVERY
    hot-path host→device matrix upload should go through here — it is
    the h2d half of the epoch phase ledger's conservation argument."""
    import jax

    with _ledger.LEDGER.phase("h2d", kernel=kernel):
        out = jax.device_put(host) if sharding is None \
            else jax.device_put(host, sharding)
    _ledger.LEDGER.add_bytes("h2d", int(getattr(host, "nbytes", 0)),
                             kernel=kernel)
    return out


class PendingCounters:
    """Sync-free occupancy accounting for device hash structures.

    Every insert step returns an exact device-side insert count; the
    DMA for it is kicked at dispatch (start_fetch) and folded into the
    running count when it lands. The load bound callers should use is
    ``count() + pending_rows()`` — exact once all counters drain, and a
    tight upper bound (count + rows of undrained batches) meanwhile.
    Shared by GroupedAggKernel and DeviceHashTable so the drain
    ordering/readiness subtleties live in exactly one place.
    """

    def __init__(self, initial: int = 0):
        self._count = initial
        self._pending: List[tuple] = []   # (device counter, n_rows)
        self._rows = 0
        # steps that returned [count, rounds of probe_insert's loop,
        # row_rounds, rows] (hash_table.probe_insert_counted): the
        # rounds, the steps, the rows the rounds' arrays held and the
        # rows of the batches, folded in since the last take_rounds()
        self._rounds = 0
        self._batches = 0
        self._row_rounds = 0
        self._batch_rows = 0

    def _fold(self, ins) -> int:
        """One landed counter's insert count; a counter that carries
        the loop's books beside it (the single-chip steps'; a sharded
        step returns the bare count) leaves them in these."""
        a = np.asarray(ins).reshape(-1)
        if a.size > 1:
            self._rounds += int(a[1])
            self._batches += 1
            self._row_rounds += int(a[2])
            self._batch_rows += int(a[3])
        return int(a[0])

    def take_rounds(self) -> tuple:
        """(rounds, steps, row_rounds, rows) folded in since the last
        call."""
        out = (self._rounds, self._batches, self._row_rounds,
               self._batch_rows)
        self._rounds = self._batches = 0
        self._row_rounds = self._batch_rows = 0
        return out

    def push(self, ins, n_rows: int) -> None:
        start_fetch(ins)
        self._pending.append((ins, n_rows))
        self._rows += n_rows

    def count(self) -> int:
        return self._count

    def pending_rows(self) -> int:
        return self._rows

    def bound(self) -> int:
        return self._count + self._rows

    def drain_ready(self) -> None:
        """Fold in landed counters; never blocks. FIFO: counters land
        in dispatch order (single device stream)."""
        while self._pending and self._pending[0][0].is_ready():
            ins, n = self._pending.pop(0)
            self._count += self._fold(ins)
            self._rows -= n

    def drain_all(self) -> int:
        """Fold in every counter (blocks; DMAs already in flight)."""
        if self._pending:
            counts = fetch(*[i for i, _n in self._pending])
            self._count += sum(self._fold(c) for c in counts)
            self._pending = []
            self._rows = 0
        return self._count

    def reset(self, exact: int) -> None:
        """Adopt an externally-observed exact count (flush header,
        rebuild) that subsumes all in-flight counters. Their steps ran
        before the step that gave the exact count, so their DMAs have
        as a rule landed: the rounds of those that have are read, the
        rest dropped from the books, and nothing here waits."""
        for ins, _n in self._pending:
            # a bare count carries no rounds
            if getattr(ins, "ndim", 0) and ins.is_ready():
                self._fold(ins)
        self._count = exact
        self._pending = []
        self._rows = 0
