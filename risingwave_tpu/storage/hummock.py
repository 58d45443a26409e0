"""HummockLite: LSM state store over an object store.

Reference parity (semantics, not format):
- shared buffer / imms / upload-at-checkpoint:
  src/storage/src/hummock/event_handler/uploader.rs:567 — unsealed
  writes buffer per epoch; seal turns them immutable; ``sync(epoch)``
  builds one SST from all imms ≤ epoch and uploads it (the barrier
  commit's durability point, meta commit_epoch analog).
- version: L0 (time-ordered whole SSTs, newest last) + L1
  (key-disjoint sorted runs), persisted as a JSON version snapshot in
  the object store with a CURRENT pointer (HummockVersion/-Delta,
  src/meta/src/hummock/manager/mod.rs:1335). Restart loads CURRENT —
  recovery reads resume at the committed epoch.
- reads: merge shared-buffer → imms → L0 (newest first) → L1 with
  bloom-filter pruning for point gets (hummock_storage.rs read path).
- compaction: when L0 grows past a threshold, a merge of L0 with the
  overlapping L1 runs rewrites key-disjoint L1 runs, dropping versions
  shadowed below the committed epoch (compactor/compactor_runner.rs).
  The merge is ``storage/merge.merge_runs`` for both arms: whole
  columnar runs through the native library, or its row-at-a-time
  Python twin where the library cannot be had, byte-identical.
  Two arms, ``compaction_mode``:
    * ``"inline"`` (default): ``commit_ssts``/``commit_through`` call
      ``compact()`` synchronously — the single-process/test arm.
    * ``"dedicated"``: commits NEVER compact; a CompactionManager
      (meta/compaction.py) picks tasks off level snapshots, a
      compactor role executes the merge off the serving path
      (storage/compactor.py), and the result lands here as a
      compare-and-commit **version delta** (``reserve_task`` →
      ``apply_version_delta``/``abort_task``).
- GC: replaced objects are RETIRED, not deleted — a vacuum pass frees
  them only once no pinned version still references them
  (``pin_version``/``unpin_version``; every ``iter()`` pins at first
  next()). This is exact pin-counting (vacuum.rs analog), replacing
  the old "one compaction cycle of grace" heuristic.
"""

from __future__ import annotations

import heapq
import json
import time
from collections import OrderedDict
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from risingwave_tpu import native as _native
from risingwave_tpu.state.store import StateStore, Value
from risingwave_tpu.utils import spans as _spans
from risingwave_tpu.utils.failpoint import fail_point
from risingwave_tpu.utils.ledger import LEDGER as _LEDGER
from risingwave_tpu.utils.metrics import STORAGE as _METRICS
from risingwave_tpu.storage.merge import merge_runs
from risingwave_tpu.storage.object_store import ObjectStore
from risingwave_tpu.storage.sst import (
    EPOCH_MASK, NO_CUT, LazySst, Run, RunWriter, Sst, build_sst,
    concat_runs, full_key, full_keys, sort_run, split_full_key,
    user_prefix,
)
from risingwave_tpu.storage.value_codec import (
    decode_row, encode_row, encode_values,
)

L0_COMPACT_THRESHOLD = 4
L1_TARGET_SST_BYTES = 4 * 1024 * 1024

Imm = Tuple[int, Dict[int, Dict[bytes, Value]]]


def _build_by_row(take: List[Imm], new_sst_id) -> Optional[tuple]:
    """The drained imms as one SST, entry by entry: ``full_key`` and
    ``encode_row`` per entry, a sort of the tuples, ``build_sst``.
    The reference of the checkpoint build, and the whole of it where
    the native library cannot be had. Returns (bytes, info, entries
    by path), or None for an empty drain."""
    entries: List[Tuple[bytes, bool, bytes]] = []
    for e, tables in take:
        for table_id, kv in tables.items():
            for key, value in kv.items():
                fk = full_key(table_id, key, e)
                tomb = value is None
                entries.append(
                    (fk, tomb, b"" if tomb else encode_row(value)))
    if not entries:
        return None
    entries.sort(key=lambda t: t[0])
    data, info = build_sst(new_sst_id(), entries)
    return data, info, {"columnar": 0, "row": len(entries)}


def _build_by_column(nat, take: List[Imm], new_sst_id) -> Optional[tuple]:
    """What ``_build_by_row`` returns, the same bytes, with no Python
    object per entry: per (epoch, table) the full keys in one native
    pass and the values by the column (``value_codec.encode_values``,
    which sends a table whose columns cannot go as arrays through
    ``encode_row`` and says so: the ``row`` count), one native sort of
    the lot, and ``RunWriter`` with no SST cut."""
    batches: List[Run] = []
    by_path = {"columnar": 0, "row": 0}
    for e, tables in take:
        for table_id, kv in tables.items():
            if not kv:
                continue
            keys, key_lens = full_keys(nat, table_id, kv, e)
            vals, val_lens, columnar = encode_values(
                nat, list(kv.values()))
            batches.append(Run(keys, key_lens, vals, val_lens))
            by_path["columnar" if columnar else "row"] += len(kv)
    if not batches:
        return None
    out: List[Tuple[bytes, dict]] = []
    RunWriter(nat, NO_CUT, new_sst_id,
              lambda data, info: out.append((data, info))
              ).feed(sort_run(nat, concat_runs(batches)), last=True)
    (data, info), = out
    return data, info, by_path


class HummockLite(StateStore):
    """Single-process LSM store: StateStore for every table id.

    ``two_phase=True`` (cluster workers): ``sync(epoch)`` only STAGES
    the uploaded SST in a durable side manifest; the version advances
    when the coordinator's commit decision arrives via
    ``commit_through(epoch)`` — the HummockManager::commit_epoch split
    (src/meta/src/hummock/manager/mod.rs:1335): compute nodes upload,
    meta owns the version. This is what makes a cluster checkpoint
    atomic: a worker that crashed after staging an epoch the
    coordinator never committed discards it on recovery
    (``discard_staged_above``) instead of resurrecting half an epoch.
    Staged SSTs stay readable (they are the newest layer) so the
    in-flight epoch's reads see the data it just flushed.
    """

    def __init__(self, obj: ObjectStore, two_phase: bool = False) -> None:
        self.obj = obj
        self.two_phase = two_phase
        self._staged: List[dict] = []   # [{"epoch": e, "sst": info}]
        # built-but-not-yet-committed checkpoint SSTs (the async
        # uploader's in-flight window): each entry carries the SST's
        # BYTES so reads keep seeing the flushed data between
        # ``build_ssts`` and ``commit_ssts`` without touching the
        # object store. In-memory only — a crash here loses nothing
        # the manifest ever referenced (recovery resumes at the last
        # committed version). Newest last, like L0.
        self._uploading: List[dict] = []
        # unsealed writes: epoch → table → key → (tombstone, row)
        self._mem: Dict[int, Dict[int, Dict[bytes, Value]]] = {}
        # sealed, not yet synced: newest last
        self._imms: List[Tuple[int, Dict[int, Dict[bytes, Value]]]] = []
        self._sealed_epoch = 0
        self._committed_epoch = 0
        self._version_id = 0
        self._next_sst_id = 1
        self._l0: List[dict] = []       # SST infos, newest LAST
        self._l1: List[dict] = []       # key-disjoint, sorted by smallest
        # block-granular cache (sstable_store.rs block_cache analog):
        # reads fetch byte ranges per block; hot blocks stay resident
        # under a byte budget. SST HANDLES (index+bloom only) cache
        # separately — they are small and bound the metadata round
        # trips. Compaction's one-shot sequential scans bypass both.
        from risingwave_tpu.storage.block_cache import BlockCache
        self._blocks = BlockCache()
        self._handles: OrderedDict[int, LazySst] = OrderedDict()
        self._handles_max = 256
        # -- compaction arms + pin-exact GC -----------------------------
        # "inline": commits compact synchronously (test/oracle arm);
        # "dedicated": commits never compact — the compactor subsystem
        # applies version deltas through reserve/apply/abort below.
        self.compaction_mode = "inline"
        # version pins: pin id → version_id the reader opened against.
        # A retired object is deletable only when every live pin is at
        # or past the version that replaced it.
        self._pins: Dict[int, int] = {}
        self._next_pin = 1
        # retired-but-not-deleted objects: {"id", "size", "since"}
        # (since = first version_id that no longer references the id)
        self._retired: List[dict] = []
        # in-flight dedicated tasks: frozenset(input ids) → reserved
        # output id block (base, cap)
        self._reservations: Dict[frozenset, Tuple[int, int]] = {}
        self._load_current()

    # -- manifest ---------------------------------------------------------
    def _load_current(self) -> None:
        if self.obj.exists("meta/STAGED.json"):
            self._staged = json.loads(
                self.obj.read("meta/STAGED.json").decode())
            # staged maxima apply even with no committed version yet:
            # a worker that crashed before its FIRST commit_through
            # must not reuse a staged SST's id or re-seal its epoch
            self._sealed_epoch = max(
                (s["epoch"] for s in self._staged), default=0)
            self._next_sst_id = max(
                (s["sst"]["id"] + 1 for s in self._staged),
                default=self._next_sst_id)
        if not self.obj.exists("meta/CURRENT"):
            return
        vid = int(self.obj.read("meta/CURRENT").decode())
        v = json.loads(self.obj.read(f"meta/v{vid}.json").decode())
        self._version_id = v["version_id"]
        self._committed_epoch = v["committed_epoch"]
        self._sealed_epoch = max(v["committed_epoch"],
                                 self._sealed_epoch)
        self._next_sst_id = max(v["next_sst_id"], self._next_sst_id)
        self._l0 = v["l0"]
        self._l1 = v["l1"]

    def _persist_staged(self) -> None:
        self.obj.upload("meta/STAGED.json",
                        json.dumps(self._staged).encode())

    def _commit_version(self) -> None:
        self._version_id += 1
        v = {
            "version_id": self._version_id,
            "committed_epoch": self._committed_epoch,
            "next_sst_id": self._next_sst_id,
            "l0": self._l0,
            "l1": self._l1,
        }
        self.obj.upload(f"meta/v{self._version_id}.json",
                        json.dumps(v).encode())
        self.obj.upload("meta/CURRENT", str(self._version_id).encode())
        old = f"meta/v{self._version_id - 2}.json"
        if self.obj.exists(old):
            self.obj.delete(old)

    # -- write path -------------------------------------------------------
    def ingest_batch(self, table_id: int,
                     batch: Iterable[Tuple[bytes, Value]],
                     epoch: int) -> int:
        if epoch <= self._sealed_epoch:
            raise ValueError(
                f"write at epoch {epoch} <= sealed {self._sealed_epoch}")
        t = self._mem.setdefault(epoch, {}).setdefault(table_id, {})
        n = 0
        for key, value in batch:
            t[key] = value
            n += 1
        return n

    def seal_epoch(self, epoch: int, is_checkpoint: bool = True) -> None:
        assert epoch >= self._sealed_epoch, (epoch, self._sealed_epoch)
        self._sealed_epoch = epoch
        for e in sorted(self._mem):
            if e <= epoch:
                self._imms.append((e, self._mem.pop(e)))
        self._imms.sort(key=lambda t: t[0])

    def sync(self, epoch: int) -> dict:
        """Make all data ≤ epoch durable: build → upload → commit,
        inline. The async checkpoint pipeline (storage/uploader.py)
        calls the three phases separately so only the build mutates
        loop-confined state and the upload runs off the event loop."""
        payloads = self.build_ssts(epoch)
        with _LEDGER.phase("checkpoint"):
            # inline, the PUT holds the caller's loop like the rest
            for p in payloads:
                self.upload_payload(p)
        return self.commit_ssts(epoch, payloads)

    def build_ssts(self, epoch: int) -> List[dict]:
        """CPU half of a checkpoint flush: drain every imm ≤ epoch into
        built-but-unpublished SSTs. The built SSTs join the in-memory
        ``_uploading`` read layer (newest above L0), so the flushed
        data stays readable while its upload is in flight. Returns the
        payloads to hand to ``upload_payload`` then ``commit_ssts``.

        Builds MUST run in epoch order (the imm drain is cumulative:
        a younger epoch's build would swallow an older epoch's imms) —
        the CheckpointUploader chains them.

        Two builds, one SST: ``_build_by_row`` (``full_key`` and
        ``encode_row`` per entry, a sort of the tuples, ``build_sst``)
        is the reference and the whole build where ``native.lib()`` is
        None; ``_build_by_column`` returns the same bytes and ``info``
        with no Python object per entry, and falls back to
        ``encode_row`` for a table whose columns cannot go as arrays
        (``tests/test_checkpoint_build.py`` holds the two together).
        Counter ``state_store_sst_build_entries{path}`` says which
        took how many.

        Synchronous on the caller's event loop: ledger phase
        ``checkpoint``. The payload carries ``entries``,
        ``columnar_entries``, ``row_entries`` and ``tables`` (state
        tables touched) for the ``checkpoint.build`` span."""
        with _LEDGER.phase("checkpoint"):
            return self._build_ssts(epoch)

    def _build_ssts(self, epoch: int) -> List[dict]:
        fail_point("hummock.sync")
        take = [im for im in self._imms if im[0] <= epoch]
        self._imms = [im for im in self._imms if im[0] > epoch]
        touched = set()
        for _e, tables in take:
            touched.update(tables)

        def new_sst_id() -> int:
            self._next_sst_id += 1
            return self._next_sst_id - 1

        nat = _native.lib()
        if nat is None:
            built = _build_by_row(take, new_sst_id)
        else:
            built = _build_by_column(nat, take, new_sst_id)
        if built is None:
            return []
        data, info, by_path = built
        for path, n in by_path.items():
            _METRICS.sst_build_entries.inc(n, path=path)
        payload = {"epoch": epoch, "sst": info, "data": data,
                   "entries": info["count"], "tables": len(touched),
                   "columnar_entries": by_path["columnar"],
                   "row_entries": by_path["row"]}
        self._uploading.append(payload)
        return [payload]

    def upload_payload(self, payload: dict) -> None:
        """Durably store one built SST. Object-store I/O only — no
        store state is touched, so the uploader may run this in a
        worker thread (and retry it) while the event loop proceeds."""
        data = payload["data"]
        self.obj.upload(f"data/{payload['sst']['id']}.sst", data)
        _METRICS.sst_upload_count.inc(source="sync")
        _METRICS.sst_upload_bytes.inc(len(data), source="sync")

    def commit_ssts(self, epoch: int, payloads: List[dict]) -> dict:
        """Manifest-publish half: adopt the uploaded SSTs into the
        version (or the durable staged manifest in two-phase mode) and
        advance the committed epoch. Must be called in epoch order,
        only after every payload's upload durably landed — the
        version must never reference an object that may not exist.

        Synchronous on the caller's event loop: ledger phase
        ``checkpoint``, less the inline compaction where this commit
        triggers one (phase ``compaction``, nested; its counts come
        back under ``"compaction"``)."""
        with _LEDGER.phase("checkpoint"):
            return self._commit_ssts(epoch, payloads)

    def _commit_ssts(self, epoch: int, payloads: List[dict]) -> dict:
        ids = {p["sst"]["id"] for p in payloads}
        self._uploading = [u for u in self._uploading
                           if u["sst"]["id"] not in ids]
        info = None
        for p in payloads:
            info = p["sst"]
            if self.two_phase:
                self._staged.append({"epoch": p["epoch"], "sst": info})
            else:
                self._l0.append(info)
        if self.two_phase:
            if payloads:
                self._persist_staged()
            return {"sst": info}
        self._committed_epoch = max(self._committed_epoch, epoch)
        compaction = None
        if (self.compaction_mode == "inline"
                and len(self._l0) >= L0_COMPACT_THRESHOLD):
            compaction = self.compact()
        else:
            self._commit_version()
        return {"sst": info, "compaction": compaction}

    # -- two-phase commit plane (coordinator-driven) ----------------------
    def commit_through(self, epoch: int) -> None:
        """Adopt every staged SST ≤ epoch into the committed version —
        the commit decision the coordinator pipelines on the next
        barrier (HummockManager::commit_epoch)."""
        if epoch <= self._committed_epoch and not any(
                s["epoch"] <= epoch for s in self._staged):
            return
        adopt = [s for s in self._staged if s["epoch"] <= epoch]
        self._staged = [s for s in self._staged if s["epoch"] > epoch]
        for s in adopt:
            self._l0.append(s["sst"])
        self._committed_epoch = max(self._committed_epoch, epoch)
        if (self.compaction_mode == "inline"
                and len(self._l0) >= L0_COMPACT_THRESHOLD):
            self.compact()
        else:
            self._commit_version()
        if adopt:
            self._persist_staged()

    def discard_staged_above(self, epoch: int) -> int:
        """Recovery: drop staged SSTs the coordinator never committed
        (a crashed cluster's half-epoch must not resurrect)."""
        drop = [s for s in self._staged if s["epoch"] > epoch]
        self._staged = [s for s in self._staged if s["epoch"] <= epoch]
        for s in drop:
            self.obj.delete(f"data/{s['sst']['id']}.sst")
            self._handles.pop(s["sst"]["id"], None)
            self._blocks.drop_sst(s["sst"]["id"])
        if drop:
            self._persist_staged()
        # writes restart above what remains
        self._sealed_epoch = max(self._committed_epoch,
                                 max((s["epoch"] for s in self._staged),
                                     default=0))
        return len(drop)

    def committed_epoch(self) -> int:
        return self._committed_epoch

    def vacuum_orphans(self) -> int:
        """Recovery-time GC: delete data objects no manifest layer
        references — the async pipeline's crash residue (a kill with
        uploads in flight can strand up to max_uploading
        uploaded-but-uncommitted SSTs per generation, plus any
        deferred-vacuum garbage the dead generation never deleted).
        Single-writer assumption: call ONLY when this instance owns
        the namespace (the session recovery path; ctl inspects
        in-memory snapshot clones, where this is harmless). Returns
        the number of objects deleted."""
        live = {info["id"] for info in self._l0 + self._l1}
        live |= {s["sst"]["id"] for s in self._staged}
        live |= {u["sst"]["id"] for u in self._uploading}
        # retired objects vacuum through maybe_vacuum (pin-gated);
        # reserved output blocks belong to in-flight compaction tasks
        live |= {ent["id"] for ent in self._retired}
        for base, cap in self._reservations.values():
            live |= set(range(base, base + cap))
        dropped = 0
        for path in self.obj.list("data/"):
            name = path[len("data/"):]
            if not name.endswith(".sst"):
                continue
            try:
                sst_id = int(name[:-4])
            except ValueError:
                continue
            if sst_id not in live:
                self.obj.delete(path)
                self._handles.pop(sst_id, None)
                self._blocks.drop_sst(sst_id)
                dropped += 1
        return dropped

    # -- version pins + exact-count vacuum --------------------------------
    def pin_version(self) -> int:
        """Pin the CURRENT version: objects it references stay on disk
        until ``unpin_version``. Every ``iter()`` takes one at its
        first next(); the uploader window and staged layers are
        protected structurally (they are in the live set)."""
        pid = self._next_pin
        self._next_pin += 1
        self._pins[pid] = self._version_id
        return pid

    def unpin_version(self, pin: int) -> None:
        self._pins.pop(pin, None)
        self.maybe_vacuum()

    def pinned_versions(self) -> List[int]:
        return sorted(self._pins.values())

    def _retire(self, infos: List[dict], since: int) -> None:
        """Mark replaced objects for the pin-gated vacuum. ``since`` is
        the first version_id that no longer references them."""
        for info in infos:
            self._retired.append({"id": info["id"],
                                  "size": info.get("size", 0),
                                  "since": since})

    def maybe_vacuum(self) -> int:
        """Delete retired objects no pinned version can still read:
        deletable iff every live pin is ≥ the retiring version. A
        storage fault here only DELAYS GC (the entry stays retired and
        the next pass retries) — vacuum must never fail a commit or a
        version-delta apply."""
        if not self._retired:
            return 0
        floor = min(self._pins.values(), default=None)
        keep: List[dict] = []
        dropped = 0
        for ent in self._retired:
            if floor is not None and floor < ent["since"]:
                keep.append(ent)
                continue
            try:
                fail_point("hummock.vacuum")
                self.obj.delete(f"data/{ent['id']}.sst")
            except FileNotFoundError:
                pass               # already gone (recovery vacuumed it)
            except OSError:
                keep.append(ent)
                continue
            self._handles.pop(ent["id"], None)
            self._blocks.drop_sst(ent["id"])
            dropped += 1
        self._retired = keep
        self._update_space_amp()
        return dropped

    def _update_space_amp(self) -> None:
        """storage_space_amp gauge: (manifest-live + retired-on-disk)
        bytes over manifest-live bytes — 1.0 when GC is caught up, the
        honest measure of vacuum lag under pinned readers."""
        logical = sum(i.get("size", 0) for i in self._l0 + self._l1)
        dead = sum(ent.get("size", 0) for ent in self._retired)
        if logical > 0:
            _METRICS.storage_space_amp.set(
                round((logical + dead) / logical, 4))

    # -- dedicated-compaction plane (reserve → execute → apply) -----------
    def level_snapshot(self) -> dict:
        """Topology the CompactionManager's pickers read: per-level SST
        infos + the ids already frozen under an in-flight task."""
        reserved: set = set()
        for key in self._reservations:
            reserved |= set(key)
        return {
            "version_id": self._version_id,
            "committed_epoch": self._committed_epoch,
            "l0": [dict(i) for i in self._l0],
            "l1": [dict(i) for i in self._l1],
            "reserved": sorted(reserved),
        }

    def reserve_task(self, input_ids: List[int],
                     id_block: int = 16) -> dict:
        """Freeze a task's inputs and burn it a durable output-id
        block. Serving commits proceed concurrently — new L0 runs are
        simply not in the frozen input set. The id block commits to the
        manifest NOW so a compactor crash after uploading outputs can
        never race a later allocation onto the same ids."""
        inset = frozenset(input_ids)
        current = {i["id"] for i in self._l0 + self._l1}
        missing = sorted(inset - current)
        if missing:
            raise ValueError(
                f"compaction inputs not in current version: {missing}")
        for key in self._reservations:
            busy = sorted(inset & key)
            if busy:
                raise ValueError(
                    f"compaction inputs already reserved: {busy}")
        cap = max(1, id_block)
        base = self._next_sst_id
        self._next_sst_id += cap
        self._commit_version()
        self._reservations[inset] = (base, cap)
        return {"read_version": self._version_id,
                "safe_epoch": self._committed_epoch,
                "output_base": base, "output_cap": cap}

    def apply_version_delta(self, input_ids: List[int],
                            outputs: List[dict]) -> dict:
        """Compare-and-commit: swap EXACTLY the reserved inputs for the
        task's outputs. Raises ValueError (conflict) if any input is no
        longer in the current version — e.g. an inline compact ran in
        between — leaving levels untouched; the manager aborts and
        requeues. Inputs retire under the new version; vacuum frees
        them once no pin predates the swap."""
        inset = frozenset(input_ids)
        olds = [i for i in self._l0 + self._l1 if i["id"] in inset]
        if len(olds) != len(inset):
            have = {i["id"] for i in olds}
            self._reservations.pop(inset, None)
            raise ValueError(
                f"version delta conflict: inputs "
                f"{sorted(inset - have)} no longer current")
        keep = [i for i in self._l1 if i["id"] not in inset]
        merged = sorted(keep + [dict(i) for i in outputs],
                        key=lambda i: user_prefix(i["smallest"]))
        for a, b in zip(merged, merged[1:]):
            if user_prefix(a["largest"]) >= user_prefix(b["smallest"]):
                self._reservations.pop(inset, None)
                raise ValueError(
                    f"version delta conflict: outputs overlap L1 run "
                    f"{b['id']} — task inputs were not range-complete")
        self._l0 = [i for i in self._l0 if i["id"] not in inset]
        self._l1 = merged
        self._commit_version()
        self._reservations.pop(inset, None)
        self._retire(olds, self._version_id)
        _METRICS.compaction_bytes_read.inc(
            sum(i.get("size", 0) for i in olds), arm="dedicated")
        _METRICS.compaction_bytes_written.inc(
            sum(i.get("size", 0) for i in outputs), arm="dedicated")
        self.maybe_vacuum()
        self._update_space_amp()
        return {"version_id": self._version_id}

    def abort_task(self, input_ids: List[int],
                   output_ids: List[int]) -> None:
        """Release a failed/expired task: unfreeze its inputs and
        delete any outputs it managed to upload (their ids stay
        burned — never reused)."""
        self._reservations.pop(frozenset(input_ids), None)
        for sid in output_ids:
            try:
                self.obj.delete(f"data/{sid}.sst")
            except OSError:
                pass
            self._handles.pop(sid, None)
            self._blocks.drop_sst(sid)

    # -- SST access -------------------------------------------------------
    def _sst(self, info: dict) -> LazySst:
        s = self._handles.get(info["id"])
        if s is None:
            s = LazySst(self.obj, f"data/{info['id']}.sst", info,
                        cache=self._blocks)
            self._handles[info["id"]] = s
            while len(self._handles) > self._handles_max:
                self._handles.popitem(last=False)
        else:
            self._handles.move_to_end(info["id"])
        return s

    def _upload_sst(self, entry: dict) -> Sst:
        """Read handle over a built-but-uncommitted SST: the bytes are
        still in memory, so no object-store round trip."""
        s = entry.get("handle")
        if s is None:
            s = entry["handle"] = Sst(entry["data"], entry["sst"])
        return s

    # -- read path --------------------------------------------------------
    def get(self, table_id: int, key: bytes, epoch: int) -> Value:
        # 1) unsealed epochs, newest first
        for e in sorted(self._mem, reverse=True):
            if e > epoch:
                continue
            kv = self._mem[e].get(table_id)
            if kv is not None and key in kv:
                return kv[key]
        # 2) imms, newest first
        for e, tables in reversed(self._imms):
            if e > epoch:
                continue
            kv = tables.get(table_id)
            if kv is not None and key in kv:
                return kv[key]
        # 3) built-but-uncommitted checkpoint SSTs (async upload in
        # flight — newer than anything committed), newest first
        for u in reversed(self._uploading):
            if u["sst"]["min_epoch"] > epoch:
                continue
            hit = self._upload_sst(u).get(table_id, key, epoch)
            if hit is not None:
                _found, tomb, row = hit
                return None if tomb else decode_row(row)
        # 4) staged (two-phase, newest layer) → L0 newest → oldest,
        # then L1 (bloom-pruned point lookups)
        for s in reversed(self._staged):
            info = s["sst"]
            if info["min_epoch"] > epoch:
                continue
            hit = self._sst(info).get(table_id, key, epoch)
            if hit is not None:
                _found, tomb, row = hit
                return None if tomb else decode_row(row)
        for info in reversed(self._l0):
            if info["min_epoch"] > epoch:
                continue
            hit = self._sst(info).get(table_id, key, epoch)
            if hit is not None:
                _found, tomb, row = hit
                return None if tomb else decode_row(row)
        lo = self._l1_candidate(table_id, key)
        if lo is not None:
            hit = self._sst(self._l1[lo]).get(table_id, key, epoch)
            if hit is not None:
                _found, tomb, row = hit
                return None if tomb else decode_row(row)
        return None

    def _l1_candidate(self, table_id: int, key: bytes) -> Optional[int]:
        """Run that could hold (table, key) — compare USER-key prefixes;
        the inverted-epoch suffix would mis-order full-key compares."""
        if not self._l1:
            return None
        target = full_key(table_id, key, 0)[:-8]
        lo, hi, ans = 0, len(self._l1) - 1, None
        while lo <= hi:
            mid = (lo + hi) // 2
            if user_prefix(self._l1[mid]["smallest"]) <= target:
                ans = mid
                lo = mid + 1
            else:
                hi = mid - 1
        if ans is None:
            return None
        # key beyond this run's largest user key ⇒ in no run (disjoint)
        if user_prefix(self._l1[ans]["largest"]) < target:
            return None
        return ans

    def iter(self, table_id: int, epoch: int,
             start: Optional[bytes] = None, end: Optional[bytes] = None,
             reverse: bool = False) -> Iterator[Tuple[bytes, tuple]]:
        """Snapshot range scan: newest version ≤ epoch per key, no
        tombstones — a k-way merge across all layers. `reverse=True`
        scans keys DESCENDING (backward iterator; the merge key flips
        the user key but keeps newest-version-first within a key).

        The scan PINS the version at its first next() and unpins when
        exhausted or closed: compactions committing mid-scan retire the
        replaced objects but the vacuum cannot free them until this
        reader finishes — an iterator opened before a compaction reads
        its snapshot to completion, however many compactions land."""
        def gen():
            pin = self.pin_version()
            try:
                yield from self._iter_impl(table_id, epoch, start, end,
                                           reverse)
            finally:
                self.unpin_version(pin)
        return gen()

    def _iter_impl(self, table_id: int, epoch: int,
                   start: Optional[bytes], end: Optional[bytes],
                   reverse: bool) -> Iterator[Tuple[bytes, tuple]]:
        start = start or b""
        sources = []
        rank = 0

        def mem_source(e: int, kv: Dict[bytes, Value], r: int):
            inv = (~e) & EPOCH_MASK
            for k in sorted(kv, reverse=reverse):
                if k < start or (end is not None and k >= end):
                    continue
                yield (k, inv, r, kv[k])

        for e in sorted(self._mem, reverse=True):
            if e <= epoch:
                kv = self._mem[e].get(table_id)
                if kv:
                    sources.append(mem_source(e, kv, rank))
                    rank += 1
        for e, tables in reversed(self._imms):
            if e <= epoch:
                kv = tables.get(table_id)
                if kv:
                    sources.append(mem_source(e, kv, rank))
                    rank += 1

        def sst_source(sst, r: int):
            sfk = full_key(table_id, start, EPOCH_MASK)
            for fk, tomb, row in sst.iter_from(sfk):
                t, uk, e = split_full_key(fk)
                if t != table_id:
                    break
                if end is not None and uk >= end:
                    break
                if e > epoch:
                    continue
                yield (uk, (~e) & EPOCH_MASK, r,
                       None if tomb else decode_row(row))

        def sst_source_rev(sst, r: int):
            # descending keys; within one user key iter_rev yields
            # versions oldest-first (fk order), so buffer the tiny
            # same-key run and re-emit newest-first
            import struct as _s
            ufk = _s.pack(">I", table_id + 1) if end is None else \
                full_key(table_id, end, EPOCH_MASK)
            run: List[tuple] = []
            run_uk: Optional[bytes] = None
            for fk, tomb, row in sst.iter_rev(ufk):
                t, uk, e = split_full_key(fk)
                if t != table_id or uk < start:
                    break
                if end is not None and uk >= end:
                    continue
                if e > epoch:
                    continue
                item = (uk, (~e) & EPOCH_MASK, r,
                        None if tomb else decode_row(row))
                if uk != run_uk:
                    yield from reversed(run)
                    run, run_uk = [], uk
                run.append(item)
            yield from reversed(run)

        mk = sst_source_rev if reverse else sst_source
        for u in reversed(self._uploading):
            sources.append(mk(self._upload_sst(u), rank))
            rank += 1
        for s in reversed(self._staged):
            sources.append(mk(self._sst(s["sst"]), rank))
            rank += 1
        for info in reversed(self._l0):
            sources.append(mk(self._sst(info), rank))
            rank += 1
        for info in self._l1:
            sources.append(mk(self._sst(info), rank))
            rank += 1

        if reverse:
            # descending user keys; within a key newest version first
            # (EPOCH_MASK - inv descends with reverse=True ⇢ inv
            # ascends), lowest rank breaking ties
            merged = heapq.merge(
                *sources, reverse=True,
                key=lambda t: (t[0], EPOCH_MASK - t[1], -t[2]))
        else:
            merged = heapq.merge(
                *sources, key=lambda t: (t[0], t[1], t[2]))
        last_key: Optional[bytes] = None
        for uk, _inv, _r, value in merged:
            if uk == last_key:
                continue
            last_key = uk
            if value is not None:
                yield uk, value

    # -- compaction -------------------------------------------------------
    def compact(self) -> Optional[dict]:
        """Leveled compaction (level picker): merge L0 with ONLY the
        L1 runs whose user-key range overlaps L0's — untouched runs
        carry over unread (manager/compaction picker analog; the r3
        build rewrote the whole L1 every trigger, O(total LSM) write
        amplification per compaction instead of O(overlap)).

        Within the compacted range every level participates, so the
        old full-merge GC rules hold unchanged there: versions
        shadowed below the committed epoch drop, and a tombstone that
        is the newest surviving version drops with its key. Replaced
        objects retire into the pin-gated vacuum (an in-flight scan
        that pinned an older version keeps them readable).

        The merge itself is ``storage/merge.merge_runs``, shared with
        the dedicated arm (``compactor.execute_task``): this method
        picks the inputs, hands out ids from ``_next_sst_id``, swaps
        the version and retires what it replaced.

        Synchronous on the caller's event loop: ledger phase
        ``compaction`` and the ``checkpoint.compact`` annotation.
        Returns what it read, wrote and dropped, ``entries_in`` and
        which merge ran (``merge``: ``"native"`` over columnar runs,
        ``"python"`` the row-at-a-time twin), with its wall-clock
        start and duration (None where there was nothing to merge).
        """
        t0 = time.time()
        with _spans.annotation("checkpoint.compact"), \
                _LEDGER.phase("compaction"):
            fail_point("hummock.compact")
            counts = self._compact()
        if counts is not None:
            counts.update(start_s=t0, dur_s=time.time() - t0,
                          mode="inline")
        return counts

    def _compact(self) -> Optional[dict]:
        # key range of the L0 files being absorbed (user-key compare:
        # the inverted-epoch suffix would mis-order full keys)
        if self._l0:
            lo = min(user_prefix(i["smallest"]) for i in self._l0)
            hi = max(user_prefix(i["largest"]) for i in self._l0)
            overlap, keep_lo, keep_hi = [], [], []
            for info in self._l1:
                if user_prefix(info["largest"]) < lo:
                    keep_lo.append(info)
                elif user_prefix(info["smallest"]) > hi:
                    keep_hi.append(info)
                else:
                    overlap.append(info)
        else:
            # manual full compaction (ctl / tests): absorb everything
            overlap, keep_lo, keep_hi = list(self._l1), [], []
        olds = list(self._l0) + overlap
        if not olds:
            self._commit_version()
            return None

        def new_sst_id() -> int:
            self._next_sst_id += 1
            return self._next_sst_id - 1

        # the one compaction merge (storage/merge.py): rank order is
        # L0 newest first (newest is LAST in the level list), then the
        # overlapping L1 runs
        new_infos, merged = merge_runs(
            self.obj, self._l0[::-1], overlap,
            safe_epoch=self._committed_epoch, bottom=True,
            target_bytes=L1_TARGET_SST_BYTES, new_sst_id=new_sst_id)
        self._l0 = []
        # splice: untouched runs below + rewritten range + above stays
        # key-disjoint and sorted (the picker chose by range)
        self._l1 = keep_lo + new_infos + keep_hi
        self._commit_version()
        # pin-exact GC (vacuum.rs analog): retire the replaced objects
        # under the new version; the vacuum frees each only once no
        # pinned reader (in-flight scan) predates the swap
        self._retire(olds, self._version_id)
        read_bytes = sum(i.get("size", 0) for i in olds)
        write_bytes = sum(i.get("size", 0) for i in new_infos)
        _METRICS.compaction_bytes_read.inc(read_bytes, arm="inline")
        _METRICS.compaction_bytes_written.inc(write_bytes, arm="inline")
        self.maybe_vacuum()
        self._update_space_amp()
        return {"ssts_read": len(olds), "read_bytes": read_bytes,
                "ssts_written": len(new_infos),
                "write_bytes": write_bytes,
                "entries_in": merged["entries_in"],
                "entries_dropped": (merged["entries_in"]
                                    - merged["entries_out"]),
                "merge": merged["merge"]}

    # -- test/debug helpers ----------------------------------------------
    def table_size(self, table_id: int, epoch: int) -> int:
        return sum(1 for _ in self.iter(table_id, epoch))

    @property
    def levels(self) -> Tuple[int, int]:
        return len(self._l0), len(self._l1)
