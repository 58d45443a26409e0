"""Host-memory accounting: EstimateSize + a central context.

Reference parity: src/common/src/estimate_size/ (EstimateSize derive)
and src/compute/src/memory_management/memory_manager.rs:33-70 (the
LRU-watermark memory manager). TPU re-design: device state is
pre-sized and grows explicitly (kernel capacity ladders), so the
reference's malloc-pressure eviction loop maps to (a) SIZE ACCOUNTING
for every host-resident cache — join arenas, interners, partition
caches, memtables — surfaced through metrics, and (b) an eviction
sweep over the caches that are evictable (clean snapshot caches),
triggered when the accounted total crosses a soft limit. State that
is NOT evictable (arenas, interners) is bounded by live rows via
compaction/GC instead — see hash_join._maybe_gc_interner.

Reporters are CONSTANT-TIME estimators hand-rolled per cache (array
nbytes + per-entry constants) — tick() runs every checkpoint, so a
recursive deep-size walk would cost O(state) per barrier.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from risingwave_tpu.utils.metrics import STREAMING as _METRICS


def keep_freed_heap() -> bool:
    """Pin glibc malloc's two dynamic thresholds for this process:
    requests up to 32 MiB come from the heap, and the heap's free top
    is not given back. Left to themselves both thresholds follow the
    largest mmapped chunk freed so far, which differs from run to run:
    the store's merges and SST builds (4 MiB pieces, some 100 MB a
    compaction pass, every fourth barrier) then either reuse warm heap
    or map and fault fresh pages on every pass. On the chip host that
    was two levels of compaction speed, 107-134 MB/s, and of every
    compaction barrier's latency (PERF.md section 6, PR 31). Setting
    either threshold turns glibc's adjustment off. A server keeps what
    it has touched. False where the C library has no mallopt."""
    import ctypes
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    m_trim_threshold, m_top_pad, m_mmap_threshold = -1, -2, -3
    ok = mallopt(m_mmap_threshold, 32 << 20)
    ok &= mallopt(m_trim_threshold, 1 << 30)
    ok &= mallopt(m_top_pad, 64 << 20)
    return bool(ok)


_startup_frozen = False


def freeze_startup_heap() -> int:
    """Move what the process holds when the server starts (the imports:
    jax, numpy and this package are some 65K tracked objects, none of
    them ever garbage) into the cyclic collector's permanent
    generation, once a process. A full pass then walks the jobs' own
    objects only. It comes wherever an allocation happens to trigger
    it, one or two in a few seconds of a busy barrier loop, and stops
    every thread: on the chip host 21-30 ms a pass where it took 52-75
    with the imports in it, and a window of six barriers swung with
    each (PERF.md section 6, PR 45). What dies later by its reference
    count is freed as ever. Returns the objects set aside by this
    call."""
    global _startup_frozen
    if _startup_frozen:
        return 0
    import gc
    _startup_frozen = True
    gc.collect()
    before = gc.get_freeze_count()
    gc.freeze()
    return gc.get_freeze_count() - before


class MemoryContext:
    """Central registry of host-state size reporters + evictors.

    Operators register a `nbytes` callable (accounting) and optionally
    an `evict` callable (frees what it safely can, returns bytes
    freed). `tick()` refreshes metrics and, when the soft limit is
    crossed, sweeps evictors largest-first — the memory_manager.rs
    watermark loop with explicit evictability instead of LRU epochs."""

    def __init__(self, soft_limit_bytes: Optional[int] = None):
        self.soft_limit = soft_limit_bytes
        # last accounted total, refreshed at every tick() — the state
        # tier (state/tier.py) reads this at its barrier sweeps instead
        # of re-walking every reporter per executor per barrier
        self.last_total = 0
        self._reporters: Dict[str, Callable[[], int]] = {}
        self._evictors: Dict[str, Callable[[], int]] = {}

    def register(self, name: str, nbytes: Callable[[], int],
                 evict: Optional[Callable[[], int]] = None) -> None:
        self._reporters[name] = nbytes
        if evict is not None:
            self._evictors[name] = evict

    def unregister(self, name: str) -> None:
        self._reporters.pop(name, None)
        self._evictors.pop(name, None)
        # drop the gauge series too: names embed object ids, so a
        # stale series per dead executor is unbounded label cardinality
        _METRICS.host_state_bytes.remove(cache=name)

    def sizes(self) -> Dict[str, int]:
        # snapshot first: dead-executor reporters unregister themselves
        # when called (weakref pattern), mutating the registry
        return {n: int(f()) for n, f in list(self._reporters.items())}

    def total_bytes(self) -> int:
        total = sum(self.sizes().values())
        self.last_total = total
        return total

    def tick(self) -> int:
        """Refresh metrics; evict if over the soft limit. Returns the
        accounted total after any eviction."""
        sizes = self.sizes()
        for name, b in sizes.items():
            _METRICS.host_state_bytes.set(b, cache=name)
        total = sum(sizes.values())
        self.last_total = total
        if self.soft_limit is None or total <= self.soft_limit:
            return total
        for name in sorted(self._evictors,
                           key=lambda n: -sizes.get(n, 0)):
            freed = int(self._evictors[name]())
            total -= freed
            if total <= self.soft_limit:
                break
        # deferred evictors (the state tier) see the over-limit total
        # via last_total and sweep at their own barriers
        self.last_total = max(total, 0)
        return total


GLOBAL = MemoryContext()
