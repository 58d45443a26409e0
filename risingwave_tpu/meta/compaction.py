"""CompactionManager: meta-side compaction control plane.

Reference parity: src/meta/src/hummock/manager/compaction.rs + the
compaction pickers (picker/*.rs) — the meta service watches each
namespace's level topology, picks tasks with multi-level pickers
(L0→L1 overlap, size-ratio, tombstone-reclaim), freezes each task's
inputs behind a reservation (``HummockLite.reserve_task``), dispatches
the merge to a compactor executor OFF the serving path, and lands the
result as a compare-and-commit version delta
(``apply_version_delta``). Serving commits proceed concurrently — new
L0 runs simply aren't in a frozen input set.

Task recovery is lease-based, like streaming workers: an executor that
dies mid-task (SIGKILL, storage fault, torn channel) or outlives its
lease gets its task ABORTED (reservation released, any uploaded
outputs deleted — their ids stay burned) and the trigger re-picks on a
later tick. Compactor faults never touch the serving recovery ladder:
they are recorded (``CAUSE_COMPACTOR_DEAD`` → ``ACTION_REQUEUE``)
without charging the storm gate — zero serving-domain recoveries is
the chaos invariant.

Executors are pluggable per namespace (``CompactorHooks``): the
single-process session wires ``InProcessCompactor`` (a background
thread); the cluster wires the ``role="compactor"`` subprocess over
its control channel. Hooks may be sync or async — ``tick()`` awaits
what needs awaiting.
"""

from __future__ import annotations

import asyncio
import inspect
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

from risingwave_tpu.common.errors import PlanError
from risingwave_tpu.utils.metrics import STORAGE as _METRICS

# -- picker thresholds --------------------------------------------------
L0_TRIGGER = 4            # L0 run count (hummock.L0_COMPACT_THRESHOLD)
SIZE_RATIO = 4            # L0 within 1/ratio of L1 bytes → early merge
TOMBSTONE_DENSITY = 0.3   # tombstones/entries in a run → reclaim rewrite


def _up(hex_key: str) -> bytes:
    """User-key prefix of a hex SST boundary (strips the 8-byte
    inverted-epoch suffix, which would mis-order comparisons)."""
    return bytes.fromhex(hex_key)[:-8]


def _overlapping(l1: List[dict], lo: bytes, hi: bytes) -> List[dict]:
    return [i for i in l1
            if not (_up(i["largest"]) < lo or _up(i["smallest"]) > hi)]


# -- pickers (pure: snapshot dict in, proto-task dict out) --------------
def pick_l0(snap: dict, threshold: int = L0_TRIGGER) -> Optional[dict]:
    """L0→L1 overlap picker: too many time-ordered L0 runs (read
    amplification — every run is a merge source on every read) →
    absorb ALL of L0 plus the overlapping L1 runs. Bottom merge: the
    destination is the terminal level, so ≤-safe tombstones drop."""
    reserved = set(snap.get("reserved") or ())
    l0 = snap.get("l0") or []
    if len(l0) < threshold or any(i["id"] in reserved for i in l0):
        return None
    lo = min(_up(i["smallest"]) for i in l0)
    hi = max(_up(i["largest"]) for i in l0)
    l1 = _overlapping(snap.get("l1") or [], lo, hi)
    if any(i["id"] in reserved for i in l1):
        return None
    return {"picker": "l0", "inputs_l0": list(l0), "inputs_l1": l1,
            "bottom": True}


def pick_size_ratio(snap: dict, ratio: int = SIZE_RATIO
                    ) -> Optional[dict]:
    """Size-ratio Ln→Ln+1 picker: the young level's bytes have grown
    to within 1/ratio of the level below — merge early, before the
    count trigger, so one giant flush cannot sit on the read path
    until three more land."""
    reserved = set(snap.get("reserved") or ())
    l0 = snap.get("l0") or []
    l1_all = snap.get("l1") or []
    if len(l0) < 2 or any(i["id"] in reserved for i in l0):
        return None
    l0_bytes = sum(i.get("size", 0) for i in l0)
    l1_bytes = sum(i.get("size", 0) for i in l1_all)
    if l1_bytes <= 0 or l0_bytes * ratio < l1_bytes:
        return None
    lo = min(_up(i["smallest"]) for i in l0)
    hi = max(_up(i["largest"]) for i in l0)
    l1 = _overlapping(l1_all, lo, hi)
    if any(i["id"] in reserved for i in l1):
        return None
    return {"picker": "size_ratio", "inputs_l0": list(l0),
            "inputs_l1": l1, "bottom": True}


def pick_tombstone(snap: dict, density: float = TOMBSTONE_DENSITY
                   ) -> Optional[dict]:
    """Tombstone-reclaim picker: rewrite a single bottom-level run
    whose delete markers exceed the density threshold — space reclaim
    with no L0 involvement. Safe as a lone-run bottom merge: L1 runs
    are key-disjoint and every L0 run is strictly newer, so a dropped
    ≤-safe tombstone can shadow nothing it should not."""
    reserved = set(snap.get("reserved") or ())
    for info in snap.get("l1") or []:
        if info["id"] in reserved:
            continue
        n = info.get("count", 0)
        if n > 0 and info.get("tombstones", 0) / n >= density:
            return {"picker": "tombstone", "inputs_l0": [],
                    "inputs_l1": [info], "bottom": True}
    return None


def pick_task(snap: dict) -> Optional[dict]:
    """Priority order: read-amp first (L0 count), then size ratio,
    then space reclaim."""
    return (pick_l0(snap) or pick_size_ratio(snap)
            or pick_tombstone(snap))


# -- task ledger (rw_compaction payload) --------------------------------
@dataclass
class CompactionTask:
    """One compaction task's lifecycle row. Mutated in place as the
    manager drives it: pending → running → applied | aborted |
    requeued | failed."""

    task_id: int
    namespace: str
    picker: str
    input_ids: List[int]
    bottom: bool = True
    state: str = "pending"
    attempts: int = 1
    safe_epoch: int = 0
    read_version: int = 0
    output_base: int = 0
    output_cap: int = 0
    outputs: List[int] = field(default_factory=list)
    bytes_read: int = 0
    bytes_written: int = 0
    duration_s: float = 0.0
    detail: str = ""

    def row(self) -> tuple:
        return (self.task_id, self.namespace, self.picker, self.state,
                ",".join(str(i) for i in self.input_ids),
                ",".join(str(i) for i in self.outputs),
                self.bytes_read, self.bytes_written, self.attempts,
                round(self.duration_s, 6), self.detail)


COMPACTION_LOG: Deque[CompactionTask] = deque(maxlen=1 << 12)
_SEQ = 0


def compaction_rows() -> List[tuple]:
    """rw_compaction payload: one row per task, current state."""
    return [t.row() for t in COMPACTION_LOG]


def clear_compaction_log() -> None:
    """Test isolation: the log is process-global."""
    global _SEQ
    COMPACTION_LOG.clear()
    _SEQ = 0


@dataclass
class CompactorHooks:
    """Per-namespace plumbing the manager drives. ``snapshot``/
    ``reserve``/``apply``/``abort`` run on the owning store (local
    calls or worker RPCs); ``execute`` dispatches the merge and
    returns a handle with done()/result() — a concurrent Future
    (thread arm) or an asyncio Task (subprocess arm)."""

    snapshot: Callable[[], object]
    reserve: Callable[[List[int], int], object]
    apply: Callable[[List[int], List[dict]], object]
    abort: Callable[[List[int], List[int]], object]
    execute: Callable[[dict], object]


async def _maybe(x):
    return await x if inspect.isawaitable(x) else x


class CompactionManager:
    """Watch level topology, pick + lease tasks, apply version deltas.

    One task in flight per namespace: compaction is a background
    hygiene loop, not a throughput race — and the single-flight rule
    makes conflict analysis trivial (a reservation can only collide
    with serving-side inline compaction, which `apply` detects as a
    compare-and-commit conflict). Requeue is re-pick: an aborted or
    expired task releases its reservation and the unchanged trigger
    fires again on a later tick with a fresh id grant."""

    def __init__(self, lease_s: float = 30.0, max_attempts: int = 5,
                 monotonic: Callable[[], float] = time.monotonic,
                 on_fault: Optional[Callable] = None):
        self.lease_s = lease_s
        self.max_attempts = max_attempts
        self.monotonic = monotonic
        # on_fault(namespace, kind, exc_or_None): the cluster wires
        # this to supervisor.record(CAUSE_COMPACTOR_DEAD, requeue) +
        # compactor respawn — NEVER through the serving storm gate
        self.on_fault = on_fault
        self.namespaces: Dict[str, CompactorHooks] = {}
        self._inflight: Dict[str, dict] = {}
        self._fails: Dict[str, int] = {}    # consecutive, per namespace
        self.applied_total = 0
        self.requeued_total = 0

    def add_namespace(self, name: str, hooks: CompactorHooks) -> None:
        self.namespaces[name] = hooks

    def remove_namespace(self, name: str) -> None:
        self.namespaces.pop(name, None)
        entry = self._inflight.pop(name, None)
        if entry is not None:
            entry["handle"].cancel()

    def inflight(self) -> Dict[str, CompactionTask]:
        return {ns: e["task"] for ns, e in self._inflight.items()}

    async def tick(self) -> dict:
        """One control round: settle finished/expired tasks, then
        dispatch new ones. Cheap when idle — a snapshot per namespace
        and no dispatch unless a picker fires."""
        applied = requeued = dispatched = 0
        for ns in list(self.namespaces):
            if ns in self._inflight:
                a, r = await self._settle(ns)
                applied += a
                requeued += r
            if ns not in self._inflight:
                dispatched += await self._maybe_dispatch(ns)
        _METRICS.compaction_pending_tasks.set(float(len(self._inflight)))
        return {"applied": applied, "requeued": requeued,
                "dispatched": dispatched,
                "inflight": len(self._inflight)}

    async def drain(self, timeout_s: float = 30.0) -> int:
        """Settle every in-flight task WITHOUT dispatching new ones —
        the graceful-shutdown path (session close, arm flip back to
        inline). Waits out running executors up to ``timeout_s``; a
        straggler is lease-expired and aborted. Returns tasks applied."""
        deadline = self.monotonic() + timeout_s
        applied = 0
        for ns in list(self._inflight):
            entry = self._inflight.get(ns)
            if entry is None:
                continue
            handle = entry["handle"]
            while not handle.done() and self.monotonic() < deadline:
                await asyncio.sleep(0.01)
            if not handle.done():
                entry["deadline"] = float("-inf")
            a, _ = await self._settle(ns)
            applied += a
        _METRICS.compaction_pending_tasks.set(float(len(self._inflight)))
        return applied

    # -- lifecycle ------------------------------------------------------
    async def _settle(self, ns: str):
        entry = self._inflight[ns]
        task: CompactionTask = entry["task"]
        handle = entry["handle"]
        hooks: CompactorHooks = entry["hooks"]
        if not handle.done():
            if self.monotonic() < entry["deadline"]:
                return 0, 0
            # lease expired: the executor is wedged or gone — abort
            # the reservation (outputs, if any, die with it) and let
            # the trigger re-pick
            handle.cancel()
            await self._abort(ns, task, hooks, "lease_expired", None)
            return 0, 1
        try:
            result = handle.result()
        except asyncio.CancelledError:
            await self._abort(ns, task, hooks, "cancelled", None)
            return 0, 1
        except BaseException as e:  # noqa: BLE001 — executor died
            await self._abort(ns, task, hooks, "executor_fault", e)
            return 0, 1
        outputs = result.get("outputs") or []
        try:
            await _maybe(hooks.apply(task.input_ids, outputs))
        except BaseException as e:  # noqa: BLE001 — CAS conflict or
            # a dead worker; either way the reservation must release
            await self._abort(ns, task, hooks, "apply_conflict", e,
                              uploaded=[i["id"] for i in outputs])
            return 0, 1
        task.state = "applied"
        task.outputs = [i["id"] for i in outputs]
        task.bytes_read = int(result.get("bytes_read", 0))
        task.bytes_written = int(result.get("bytes_written", 0))
        task.duration_s = self.monotonic() - entry["started"]
        self._inflight.pop(ns, None)
        self._fails[ns] = 0
        self.applied_total += 1
        return 1, 0

    async def _abort(self, ns: str, task: CompactionTask,
                     hooks: CompactorHooks, kind: str,
                     exc: Optional[BaseException],
                     uploaded: Optional[List[int]] = None) -> None:
        # delete the whole reserved id range: we cannot know which
        # outputs a dead executor managed to upload (ids stay burned)
        out_ids = uploaded if uploaded is not None else list(
            range(task.output_base,
                  task.output_base + task.output_cap))
        try:
            await _maybe(hooks.abort(task.input_ids, out_ids))
        except BaseException as e:  # noqa: BLE001 — the namespace
            # owner may itself be mid-recovery; vacuum_orphans cleans
            # what this abort could not
            task.detail = f"abort failed: {e!r}"
        fails = self._fails.get(ns, 0) + 1
        self._fails[ns] = fails
        task.state = ("failed" if fails >= self.max_attempts
                      else "requeued")
        task.duration_s = self.monotonic() - self._inflight[ns]["started"]
        if not task.detail:
            task.detail = kind if exc is None else f"{kind}: {exc!r}"
        self._inflight.pop(ns, None)
        self.requeued_total += 1
        if self.on_fault is not None:
            self.on_fault(ns, kind, exc)

    async def _maybe_dispatch(self, ns: str) -> int:
        global _SEQ
        hooks = self.namespaces[ns]
        try:
            snap = await _maybe(hooks.snapshot())
        except BaseException:  # noqa: BLE001 — owner unreachable
            # (mid-recovery worker): try again next tick
            return 0
        proto = pick_task(snap)
        if proto is None:
            return 0
        inputs = proto["inputs_l0"] + proto["inputs_l1"]
        input_ids = [i["id"] for i in inputs]
        # generous output grant: a merge never fans one input out to
        # more than ~2x runs (it only compresses), +8 slack
        id_block = 2 * len(inputs) + 8
        try:
            grant = await _maybe(hooks.reserve(input_ids, id_block))
        except BaseException:  # noqa: BLE001 — raced an inline
            # compact or a concurrent reservation: skip this tick
            return 0
        grant = grant.get("grant", grant)  # RPC replies nest it
        _SEQ += 1
        task = CompactionTask(
            task_id=_SEQ, namespace=ns, picker=proto["picker"],
            input_ids=input_ids, bottom=proto["bottom"],
            attempts=self._fails.get(ns, 0) + 1,
            safe_epoch=int(grant["safe_epoch"]),
            read_version=int(grant["read_version"]),
            output_base=int(grant["output_base"]),
            output_cap=int(grant["output_cap"]))
        task_dict = {
            "task_id": task.task_id,
            "inputs_l0": proto["inputs_l0"],
            "inputs_l1": proto["inputs_l1"],
            "bottom": proto["bottom"],
            "safe_epoch": task.safe_epoch,
            "output_base": task.output_base,
            "output_cap": task.output_cap,
        }
        handle = hooks.execute(task_dict)
        if inspect.isawaitable(handle):
            handle = asyncio.ensure_future(handle)
        task.state = "running"
        COMPACTION_LOG.append(task)
        self._inflight[ns] = {
            "task": task, "handle": handle, "hooks": hooks,
            "started": self.monotonic(),
            "deadline": self.monotonic() + self.lease_s,
        }
        return 1


def parse_compaction(spec: str) -> str:
    """SET storage_compaction validator: 'inline' | 'dedicated'
    (PlanError so a typo fails the SET, not a later commit)."""
    s = str(spec).strip().lower()
    if s not in ("inline", "dedicated"):
        raise PlanError(
            f"storage_compaction must be 'inline' or 'dedicated', "
            f"got {spec!r}")
    return s
