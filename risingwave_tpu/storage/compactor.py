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

The merge is ``storage/merge.merge_runs``, the same function the
inline arm (``HummockLite.compact``) calls: newest layer wins per
(key, epoch); versions shadowed below the task's safe epoch drop; a
tombstone that is the newest surviving version ≤ safe drops ONLY on
bottom-level merges (``bottom`` flag) — a non-bottom merge must keep
it or data in lower levels would resurrect. Where the native library
is loaded it merges whole columnar runs inside one call that holds no
GIL, so a merge on the compactor thread leaves the serving loop
running; its row-at-a-time Python twin would not. What is this
module's own: reading the task, the reserved id block and its
overflow error, the uploads, the span.
"""

from __future__ import annotations

import time
from typing import List

from risingwave_tpu.storage.merge import merge_runs
from risingwave_tpu.storage.object_store import ObjectStore
from risingwave_tpu.utils import spans as _spans
from risingwave_tpu.utils.failpoint import fail_point

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
        entries_in=result.pop("entries_in"),
        entries_dropped=result.pop("entries_dropped"),
        merge=result.pop("merge"))
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

    next_id = base

    def new_sst_id() -> int:
        nonlocal next_id
        if next_id >= base + cap:
            raise RuntimeError(
                f"compaction output overflow: reserved id block "
                f"[{base}, {base + cap}) exhausted")
        next_id += 1
        return next_id - 1

    # rank order as in HummockLite.compact: L0 newest first (newest is
    # LAST in the level list), then the overlapping L1 runs
    outputs, merged = merge_runs(
        obj, inputs_l0[::-1], inputs_l1, safe_epoch=safe, bottom=bottom,
        target_bytes=target, new_sst_id=new_sst_id)
    bytes_read = sum(i.get("size", 0) for i in inputs_l0 + inputs_l1)
    return {"outputs": outputs, "bytes_read": bytes_read,
            "bytes_written": sum(i["size"] for i in outputs),
            "entries_in": merged["entries_in"],
            "entries_dropped": (merged["entries_in"]
                                - merged["entries_out"]),
            "merge": merged["merge"]}


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
