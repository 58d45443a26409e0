"""The compaction merge: sorted runs in, key-disjoint sorted runs out.

The one merge both compaction arms run (``HummockLite.compact`` on the
serving loop, ``compactor.execute_task`` off it). A caller picks the
inputs and owns the ids and the version; this module reads the input
SSTs, orders their entries, applies the GC rule, and cuts and uploads
the output SSTs.

**Order and rule.** Inputs have a rank: the L0 runs newest first, then
the overlapping L1 runs in level order. Entries merge in bytewise
full-key order (table, user key ascending, epoch descending), ties by
rank. An equal full key in a later rank is dropped (the newer layer
wins). Every version above ``safe_epoch`` is kept. Of the versions at
or below it only the newest per ``table ++ user key`` is kept, and
that one is dropped too if it is a tombstone and ``bottom`` (a
non-bottom merge must keep it: levels below the destination may still
hold the key it deletes). Outputs are cut only where the user key
changes once ``target_bytes`` is reached, so every version of a key
lives in one run (the L1 disjoint-run binary search depends on it).

**Two paths, one result.** Where the native library is loaded
(``native.lib()``), a run is never taken apart into rows: each input
SST is decoded block by block into a columnar ``sst.Run`` (keys blob,
key lengths, values blob, value lengths), ``rw_merge_gc`` merges the
runs and applies the rule inside one call that holds no GIL, and
``sst.RunWriter`` encodes the survivors a block at a time. Where it
is not, ``_merge_python`` runs the row-at-a-time loop over
``Sst.iter_from`` and ``SstBuilder``. That loop is the specification:
the native path returns byte-identical SSTs and equal infos for the
same inputs (``tests/test_compaction_merge.py``).

**Memory.** The L1 inputs are key-disjoint and ascending, so the
native path holds the L0 runs and ONE L1 run decoded at a time: it
merges each L1 run with the L0 entries below the next run's smallest
user key, and uploads every output SST as soon as it is cut. A pass over a large level costs memory in proportion to the L0
delta, not to the level.
"""

from __future__ import annotations

import ctypes
import heapq
from typing import Callable, List, Optional, Tuple

import numpy as np

from risingwave_tpu import native as _native
from risingwave_tpu.storage.object_store import ObjectStore
from risingwave_tpu.storage.sst import (
    Run, RunWriter, Sst, SstBuilder, decode_run, offsets, split_full_key,
    user_prefix,
)
from risingwave_tpu.utils.metrics import STORAGE as _METRICS


def merge_runs(obj: ObjectStore, l0: List[dict], l1: List[dict], *,
               safe_epoch: int, bottom: bool, target_bytes: int,
               new_sst_id: Callable[[], int]) -> Tuple[List[dict], dict]:
    """Merge the SSTs ``l0`` (infos, NEWEST FIRST) and ``l1``
    (overlapping L1 runs in level order) of ``obj`` into key-disjoint
    ascending SSTs (module docstring: order, rule, cuts), each
    uploaded as ``data/<id>.sst`` as soon as it is cut, under the id
    ``new_sst_id()`` gave (which may raise: the dedicated arm's id
    block).

    Returns the outputs' infos in order and ``{"merge": "native" |
    "python", "entries_in": n, "entries_out": n}``: which path ran,
    decided by ``native.lib()`` alone, and what it read and kept."""
    outputs: List[dict] = []

    def read(info: dict) -> bytes:
        # one-shot whole-bytes read: a compaction consumes every block
        # exactly once, caching would only evict the hot read path
        return obj.read(f"data/{info['id']}.sst")

    def emit(data: bytes, info: dict) -> None:
        obj.upload(f"data/{info['id']}.sst", data)
        _METRICS.sst_upload_count.inc(source="compact")
        _METRICS.sst_upload_bytes.inc(len(data), source="compact")
        outputs.append(info)

    nat = _native.lib()
    if nat is not None:
        path = "native"
        entries_in, entries_out = _merge_native(
            nat, l0, l1, read, safe_epoch, bottom, target_bytes,
            new_sst_id, emit)
    else:
        path = "python"
        entries_in, entries_out = _merge_python(
            l0 + l1, read, safe_epoch, bottom, target_bytes,
            new_sst_id, emit)
    _METRICS.compaction_merge_entries.inc(entries_in, path=path)
    return outputs, {"merge": path, "entries_in": entries_in,
                     "entries_out": entries_out}


# -- the Python twin: the specification ---------------------------------------


def _merge_python(ranked: List[dict], read, safe: int, bottom: bool,
                  target_bytes: int, new_sst_id, emit):
    def source(info: dict, r: int):
        for fk, tomb, row in Sst(read(info), info).iter_from(b""):
            yield (fk, r, tomb, row)

    merged = heapq.merge(*[source(info, r)
                           for r, info in enumerate(ranked)],
                         key=lambda t: (t[0], t[1]))
    builder: Optional[SstBuilder] = None

    def out(fk: bytes, tomb: bool, row: bytes) -> None:
        nonlocal builder
        # cut ONLY at user-key boundaries (module docstring)
        if (builder is not None
                and builder._off + builder.block.size() >= target_bytes
                and builder.largest is not None
                and builder.largest[:-8] != fk[:-8]):
            emit(*builder.finish())
            builder = None
        if builder is None:
            builder = SstBuilder(new_sst_id())
        builder.add(fk, tomb, row)

    seen_fk: Optional[bytes] = None
    last_tu: Optional[bytes] = None
    kept_le_safe = False
    entries_in = entries_out = 0
    for fk, _r, tomb, row in merged:
        entries_in += 1
        if fk == seen_fk:
            continue               # same key+epoch: newer layer wins
        seen_fk = fk
        tu = fk[:-8]
        _t, _u, e = split_full_key(fk)
        if tu != last_tu:
            last_tu = tu
            kept_le_safe = False
        if e > safe:
            entries_out += 1
            out(fk, tomb, row)
            continue
        if kept_le_safe:
            continue               # older shadowed version: drop
        kept_le_safe = True
        if tomb and bottom:
            continue               # newest ≤ safe is a delete: gone
        # non-bottom merges KEEP a ≤-safe tombstone: levels below the
        # destination may still hold the key it deletes
        entries_out += 1
        out(fk, tomb, row)
    if builder is not None:
        emit(*builder.finish())
    return entries_in, entries_out


# -- the native path: whole runs ------------------------------------------------


class _Cursor:
    """A decoded input run with its offsets, as ``rw_merge_gc`` reads
    it."""

    def __init__(self, run: Run) -> None:
        self.run = run
        self.koff = offsets(run.key_lens)
        self.voff = offsets(run.val_lens)
        self.count = len(run.key_lens)


def _merge_native(nat, l0: List[dict], l1: List[dict], read, safe: int,
                  bottom: bool, target_bytes: int, new_sst_id, emit):
    if any(user_prefix(a["largest"]) >= user_prefix(b["smallest"])
           for a, b in zip(l1, l1[1:])):
        l0, l1 = l0 + l1, []       # not a level: hold every run
    held = [_Cursor(decode_run(nat, read(info))) for info in l0]
    pos = np.zeros(len(held) + 1, dtype=np.int64)
    writer = RunWriter(nat, target_bytes, new_sst_id, emit)
    entries_in = 0
    # one window per L1 run: that run, and what the held runs have
    # below the next run's smallest user key
    for w, info in enumerate(l1 or [None]):
        runs = list(held)
        if info is not None:
            runs.append(_Cursor(decode_run(nat, read(info))))
            pos[len(held)] = 0
        bound = (user_prefix(l1[w + 1]["smallest"])
                 if w + 1 < len(l1) else b"")
        merged, n_in = _merge_window(nat, runs, pos, bound, safe, bottom)
        entries_in += n_in
        writer.feed(merged, last=w + 1 >= len(l1))
    return entries_in, writer.entries


def _merge_window(nat, runs: List[_Cursor], pos: np.ndarray, bound: bytes,
                  safe: int, bottom: bool):
    """``rw_merge_gc`` over ``runs`` from ``pos`` (advanced in place)
    up to ``bound``: the survivors as one ``Run``, and the entries
    read."""
    k = len(runs)
    left = [int(pos[i]) for i in range(k)]
    max_out = sum(c.count - at for c, at in zip(runs, left))
    key_cap = sum(int(c.koff[-1] - c.koff[at]) for c, at in zip(runs, left))
    val_cap = sum(int(c.voff[-1] - c.voff[at]) for c, at in zip(runs, left))
    keys = np.empty(key_cap, dtype=np.uint8)
    vals = np.empty(val_cap, dtype=np.uint8)
    key_lens = np.empty(max_out, dtype=np.int32)
    val_lens = np.empty(max_out, dtype=np.int32)
    counts = np.array([c.count for c in runs], dtype=np.int64)
    entries_in = ctypes.c_int64(0)

    def pointers(arrays):
        return (ctypes.c_void_p * k)(*[a.ctypes.data for a in arrays])

    n = nat.rw_merge_gc(
        k, pointers([c.run.keys for c in runs]),
        pointers([c.koff for c in runs]),
        pointers([c.run.vals for c in runs]),
        pointers([c.voff for c in runs]),
        pos.ctypes.data, counts.ctypes.data, bound, len(bound),
        safe, int(bottom), keys.ctypes.data, key_cap,
        key_lens.ctypes.data, vals.ctypes.data, val_cap,
        val_lens.ctypes.data, max_out, ctypes.byref(entries_in))
    if n < 0:
        raise ValueError(
            "compaction input is not an SST's entries (rw_merge_gc "
            f"returned {n})")
    key_lens, val_lens = key_lens[:n], val_lens[:n]
    return Run(keys[:int(key_lens.sum())], key_lens,
               vals[:int(val_lens.sum())], val_lens), entries_in.value
