"""Dedicated compactor: off-path compaction merge execution.

Reference parity: src/storage/src/hummock/compactor/compactor_runner.rs
— the compactor node receives a task naming a FROZEN input SST set and
a reserved output-id block, merges against the object store, uploads
the outputs, and reports back; the version change happens elsewhere
(meta's compare-and-commit version delta — here
``HummockLite.apply_version_delta``). Because ``execute_task`` never
touches the owning store's in-memory state, it can run on a background
thread (``InProcessCompactor``, the single-process session's arm) or
in a dedicated subprocess (``role="compactor"`` in cluster/worker.py)
while serving commits keep landing new L0 runs concurrently — the
arxiv 1904.03800 concurrent-state stance: the merge reads an immutable
snapshot, reconciliation is a single atomic swap.

Merge semantics mirror ``HummockLite.compact`` exactly (the inline arm
is the oracle): newest layer wins per (key, epoch); versions shadowed
below the task's safe epoch drop; a tombstone that is the newest
surviving version ≤ safe drops ONLY on bottom-level merges (``bottom``
flag) — a non-bottom merge must keep it or data in lower levels would
resurrect.
"""

from __future__ import annotations

import heapq
import time
from typing import List, Optional

from risingwave_tpu.storage.object_store import ObjectStore
from risingwave_tpu.storage.sst import Sst, SstBuilder, split_full_key
from risingwave_tpu.utils import spans as _spans
from risingwave_tpu.utils.failpoint import fail_point
from risingwave_tpu.utils.metrics import STORAGE as _METRICS

# default output cut size — re-declared (not imported from hummock) so
# this module has no import cycle with the store it serves
TARGET_SST_BYTES = 4 * 1024 * 1024


def execute_task(obj: ObjectStore, task: dict) -> dict:
    """Run one compaction task against the object store and return
    ``{"outputs": [sst infos], "bytes_read": n, "bytes_written": n}``.

    The task dict carries ``inputs_l0`` (in L0 order, newest LAST, as
    the level stores them), ``inputs_l1`` (overlapping runs in L1
    order), ``safe_epoch``, ``bottom``, and the reserved id block
    ``output_base``/``output_cap`` from ``reserve_task``. Outputs cut
    at user-key boundaries at ``target_bytes`` — all versions of one
    key stay in one run (the L1 disjoint-run binary search depends on
    it). Exhausting the id block raises (the manager aborts and
    requeues with a bigger grant) rather than minting unreserved ids.

    The merge leaves the same ``checkpoint.compact`` span the inline
    arm leaves (``HummockLite.compact``), with ``mode=dedicated``: it
    runs off the serving loop, so no ledger phase claims it.
    """
    t0 = time.time()
    with _spans.annotation("checkpoint.compact"):
        result = _execute_task(obj, task)
    _spans.EPOCH_TRACER.record(
        "checkpoint.compact", "upload", start_s=t0,
        dur_s=time.time() - t0, mode="dedicated",
        ssts_read=len(task.get("inputs_l0") or [])
        + len(task.get("inputs_l1") or []),
        read_bytes=result["bytes_read"],
        ssts_written=len(result["outputs"]),
        write_bytes=result["bytes_written"],
        entries_dropped=result.pop("entries_dropped"))
    return result


def _execute_task(obj: ObjectStore, task: dict) -> dict:
    fail_point("compactor.execute")
    inputs_l0: List[dict] = list(task.get("inputs_l0") or [])
    inputs_l1: List[dict] = list(task.get("inputs_l1") or [])
    safe = int(task.get("safe_epoch", 0))
    bottom = bool(task.get("bottom", True))
    base = int(task["output_base"])
    cap = int(task.get("output_cap", 16))
    target = int(task.get("target_bytes", TARGET_SST_BYTES))

    def source(info: dict, r: int):
        # one-shot sequential scan: whole-bytes read, no cache churn
        sst = Sst(obj.read(f"data/{info['id']}.sst"), info)
        for fk, tomb, row in sst.iter_from(b""):
            yield (fk, r, tomb, row)

    # rank order mirrors HummockLite.compact: L0 newest first (newest
    # is LAST in the level list), then the overlapping L1 runs
    ranked = [source(info, r)
              for r, info in enumerate(reversed(inputs_l0))]
    ranked += [source(info, len(inputs_l0) + r)
               for r, info in enumerate(inputs_l1)]
    merged = heapq.merge(*ranked, key=lambda t: (t[0], t[1]))

    outputs: List[dict] = []
    next_id = base
    builder: Optional[SstBuilder] = None
    bytes_written = 0

    def flush() -> None:
        nonlocal builder, bytes_written
        if builder is None:
            return
        data, info = builder.finish()
        obj.upload(f"data/{info['id']}.sst", data)
        _METRICS.sst_upload_count.inc(source="compact")
        _METRICS.sst_upload_bytes.inc(len(data), source="compact")
        bytes_written += len(data)
        outputs.append(info)
        builder = None

    def out(fk: bytes, tomb: bool, row: bytes) -> None:
        nonlocal builder, next_id
        # cut ONLY at user-key boundaries (see docstring)
        if (builder is not None
                and builder._off + builder.block.size() >= target
                and builder.largest is not None
                and builder.largest[:-8] != fk[:-8]):
            flush()
        if builder is None:
            if next_id >= base + cap:
                raise RuntimeError(
                    f"compaction output overflow: reserved id block "
                    f"[{base}, {base + cap}) exhausted")
            builder = SstBuilder(next_id)
            next_id += 1
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
    flush()
    bytes_read = sum(i.get("size", 0) for i in inputs_l0 + inputs_l1)
    return {"outputs": outputs, "bytes_read": bytes_read,
            "bytes_written": bytes_written,
            "entries_dropped": entries_in - entries_out}


class InProcessCompactor:
    """The single-process session's dedicated arm: merges run on ONE
    background thread so the barrier/commit path never carries a
    ``compact()`` frame. Speaks the same reserve → execute → apply
    protocol as the cluster compactor role, minus the subprocess:
    ``submit`` returns a Future the CompactionManager polls at its
    next tick and resolves into ``apply_version_delta``."""

    def __init__(self, obj: ObjectStore):
        import concurrent.futures
        self.obj = obj
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="compactor")

    def submit(self, task: dict):
        return self._pool.submit(execute_task, self.obj, task)

    def close(self) -> None:
        self._pool.shutdown(wait=True)
