"""CheckpointUploader: the asynchronous, ordered checkpoint-commit
pipeline between the barrier loop and the state store.

Reference parity: src/storage/src/hummock/event_handler/uploader.rs:567
— compute nodes build and upload checkpoint SSTs in a background
uploader; meta commits the epoch once the uploads land. Hazelcast Jet
(PAPERS.md) attributes its tail latencies to the same decoupling:
snapshotting never rides the processing path.

The barrier loop's ``collect_next`` only SEALS an epoch and submits it
here. The pipeline then, per epoch:

  1. BUILDS the epoch's SSTs (``store.build_ssts``) — strictly in
     epoch order, because the shared-buffer drain is cumulative (a
     younger epoch's build would swallow an older epoch's imms). The
     build mutates store state, so it stays on the event loop, just
     off the barrier's critical path.
  2. UPLOADS the built SSTs (``store.upload_payload``) through a
     bounded-concurrency queue, each object-store PUT offloaded via
     ``asyncio.to_thread`` so the event loop never blocks on I/O, with
     exponential-backoff retries for transient failures.
  3. COMMITS the epoch (``store.commit_ssts``) strictly in order once
     its uploads durably landed — ``committed_epoch`` NEVER skips past
     an unfinished older epoch, so the manifest only ever references
     objects that exist.

The sealed-but-uncommitted window is bounded (``max_uploading``):
``submit`` back-pressures the barrier loop instead of letting staging
grow without bound. A failed upload (out of retries) poisons the
pipeline: younger epochs never commit past it, ``failed`` wakes the
barrier loop immediately, and the original error surfaces from the
next ``submit``/``drain``/``raise_if_failed``.

Stores without the build/commit split (MemoryStateStore, the cluster
coordinator's epoch shim) take the inline ``sync()`` fallback — same
ordering and callbacks, no overlap.

**Stages.** ``upload_s`` (seal → durable commit) is cut where it
happens into stages that add up to it, handed to ``on_commit`` as
``(name, wall-clock start, seconds, counts)`` and entered as
``TraceAnnotation``s of the same names while they run:

  ``checkpoint.queue``       waiting behind the older epoch's build and
                             commit (two stretches, one name)
  ``checkpoint.build``       ``build_ssts``, synchronous on the loop;
                             entries, columnar_entries, row_entries
                             (which build path took them:
                             storage/hummock.py), sst_bytes, tables
  ``checkpoint.put``         the object-store PUTs, off the loop;
                             objects, bytes, retries
  ``checkpoint.sink_stage``  sink staging, where a sink is attached
  ``checkpoint.commit``      ``commit_ssts`` less its compaction
  ``checkpoint.compact``     the inline ``compact()`` this commit
                             triggered, else absent; ssts_read,
                             read_bytes, ssts_written, write_bytes,
                             entries_in, entries_dropped, merge
                             (native | python: storage/merge.py)

The barrier plane (``meta/barrier.record_checkpoint_tail``) turns them
into spans under the sealing barrier's ``checkpoint.upload`` and into
the ``ckpt.*`` names of that barrier's ``rw_metrics_history`` row:
``ckpt.queue_s``, ``ckpt.build_s``, ``ckpt.put_s``, ``ckpt.commit_s``,
``ckpt.compact_s`` (0 where the commit did not compact),
``ckpt.sst_bytes``, ``ckpt.build_columnar_entries``,
``ckpt.build_row_entries``, ``ckpt.compact_read_bytes``,
``ckpt.compact_write_bytes``, and ``ckpt.sink_stage_s`` with a sink.
The synchronous stages are also the ledger's LOOP phases
(``checkpoint``, ``compaction``: utils/ledger.py, "Stolen loop time"),
booked where they run, in storage/hummock.py.
"""

from __future__ import annotations

import asyncio
import time
from collections import OrderedDict, deque
from typing import Callable, Deque, List, Optional

from risingwave_tpu.utils import spans as _spans
from risingwave_tpu.utils.metrics import (
    STORAGE as _STORAGE, STREAMING as _STREAMING,
)


class CheckpointUploader:
    """Ordered async build→upload→commit pipeline for one store."""

    def __init__(self, store,
                 max_uploading: int = 4,
                 upload_concurrency: int = 2,
                 upload_retries: int = 2,
                 retry_backoff_s: float = 0.05,
                 monotonic: Callable[[], float] = time.monotonic,
                 on_commit: Optional[
                     Callable[[int, float, List[tuple]], None]] = None):
        self.store = store
        self._split = (hasattr(store, "build_ssts")
                       and hasattr(store, "commit_ssts"))
        self.max_uploading = max(1, max_uploading)
        self.upload_retries = max(0, upload_retries)
        self.retry_backoff_s = retry_backoff_s
        self.monotonic = monotonic
        self.on_commit = on_commit
        # epoch → task, insertion (= epoch) order; the back-pressure
        # wait rides the OLDEST entry because commits are ordered
        self._tasks: "OrderedDict[int, asyncio.Task]" = OrderedDict()
        # build/commit chains: each submitted epoch awaits its
        # predecessor's future before building / committing
        self._built_chain: Optional[asyncio.Future] = None
        self._commit_chain: Optional[asyncio.Future] = None
        self._concurrency = max(1, upload_concurrency)
        self._sem = asyncio.Semaphore(self._concurrency)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self.committed_epoch = store.committed_epoch()
        # ordered commit history — bounded like EpochProfiler.profiles
        # (a long-lived server just loses the oldest entries)
        self.commit_log: Deque[int] = deque(maxlen=1 << 16)
        self.failed = asyncio.Event()        # set on terminal failure
        self._failure: Optional[BaseException] = None
        # exactly-once sinks (meta/sink_coordinator.py): the owner of
        # this uploader attaches its SinkCoordinator here. Deferred
        # sink payloads stage in the epoch's async tail BEFORE the
        # durable commit (the floor never advances past unstaged
        # rows), and manifests commit strictly AFTER it (a manifest
        # never outruns the floor) — the two crash-window invariants
        # of connectors/sink.py live in this ordering
        self.sinks = None

    # -- introspection ----------------------------------------------------
    @property
    def depth(self) -> int:
        """Epochs sealed but not yet durably committed (the uploading
        window the barrier loop reports alongside in_flight)."""
        return len(self._tasks)

    def raise_if_failed(self) -> None:
        if self._failure is not None:
            raise self._failure

    def _set_depth(self) -> None:
        _STREAMING.uploader_queue_depth.set(len(self._tasks))

    def bind_loop(self) -> None:
        """Re-bind the loop-bound primitives (Semaphore/Event) to the
        CURRENT running loop. asyncio primitives latch onto the loop
        they are first awaited on; a BarrierLoop driven across
        separate asyncio.run() calls (each a fresh loop) worked before
        this pipeline existed and must keep working — recreating the
        idle primitives restores that. Only legal with no epochs in
        flight (they would hold futures of the dead loop)."""
        loop = asyncio.get_running_loop()
        if self._loop is loop:
            return
        assert not self._tasks, \
            "checkpoint uploader moved event loops with epochs in flight"
        self._loop = loop
        self._sem = asyncio.Semaphore(self._concurrency)
        was_failed = self.failed.is_set()
        self.failed = asyncio.Event()
        if was_failed:
            self.failed.set()
        self._built_chain = None
        self._commit_chain = None

    # -- the pipeline -----------------------------------------------------
    async def submit(self, epoch: int) -> bool:
        """Hand a sealed epoch to the pipeline. Returns as soon as the
        flush task is queued (True), blocking only when the uploading
        window is full (back-pressure) or on the inline fallback;
        False when the epoch needs no flush (caller drops per-epoch
        bookkeeping it registered ahead of the call)."""
        self.raise_if_failed()
        self.bind_loop()
        if epoch <= self.committed_epoch:
            # the recovery-initial barrier's prev IS the recovered
            # committed epoch — nothing new can be staged at or below
            # it (writes are rejected below the sealed epoch)
            return False
        if not self._split:
            t0 = self.monotonic()
            if self.sinks is not None:
                self.sinks.stage_upto_sync(epoch)
            self.store.sync(epoch)
            self._note_commit(epoch, self.monotonic() - t0, [])
            if self.sinks is not None:
                self.sinks.commit_upto(epoch)
            return True
        while len(self._tasks) >= self.max_uploading:
            await asyncio.wait({next(iter(self._tasks.values()))})
            self.raise_if_failed()
        loop = asyncio.get_running_loop()
        prev_built, prev_committed = self._built_chain, self._commit_chain
        built = loop.create_future()
        committed = loop.create_future()
        self._built_chain, self._commit_chain = built, committed
        self._tasks[epoch] = asyncio.ensure_future(self._run_epoch(
            epoch, prev_built, built, prev_committed, committed))
        self._set_depth()
        return True

    async def drain(self) -> None:
        """Await every in-flight epoch's durable commit (checkpoint()/
        shutdown barrier semantics); raises the pipeline's failure."""
        while self._tasks:
            await asyncio.wait(set(self._tasks.values()))
        self.raise_if_failed()

    async def _run_epoch(self, epoch: int,
                         prev_built: Optional[asyncio.Future],
                         built: asyncio.Future,
                         prev_committed: Optional[asyncio.Future],
                         committed: asyncio.Future) -> None:
        t0 = self.monotonic()
        # stage boundaries: each stage ends where the next begins, so
        # the stages add up to upload_s by construction
        edge = [t0, time.time()]
        stages: List[tuple] = []

        def mark(name: str, **counts) -> float:
            now = self.monotonic()
            dur = now - edge[0]
            stages.append((name, edge[1], dur, counts))
            edge[0], edge[1] = now, time.time()
            return dur

        try:
            with _spans.annotation("checkpoint.queue", epoch):
                if prev_built is not None:
                    await prev_built
            mark("checkpoint.queue")
            if self._failure is not None:
                # an older epoch died mid-build: draining imms past it
                # could orphan its data — abort before touching state
                raise self._failure
            try:
                with _spans.annotation("checkpoint.build", epoch):
                    payloads = self.store.build_ssts(epoch)
            finally:
                if not built.done():
                    built.set_result(None)
            nbytes = sum(len(p["data"]) for p in payloads)
            mark("checkpoint.build", sst_bytes=nbytes,
                 **{name: sum(p.get(name, 0) for p in payloads)
                    for name in ("entries", "columnar_entries",
                                 "row_entries", "tables")})
            retries = 0
            with _spans.annotation("checkpoint.put", epoch):
                for p in payloads:
                    retries += await self._upload(p)
            mark("checkpoint.put", objects=len(payloads), bytes=nbytes,
                 retries=retries)
            if self.sinks is not None:
                # sink staging is part of the epoch's durability set:
                # it must land before the commit below advances the
                # floor, and it rides the same async tail the SST
                # uploads do (upload_s, never barrier_wait)
                with _spans.annotation("checkpoint.sink_stage", epoch):
                    await self.sinks.stage_upto(epoch)
                mark("checkpoint.sink_stage")
            with _spans.annotation("checkpoint.queue", epoch):
                if prev_committed is not None:
                    await prev_committed
            mark("checkpoint.queue")
            if self._failure is not None:
                raise self._failure      # NEVER commit past a failure
            with _spans.annotation("checkpoint.commit", epoch):
                done = self.store.commit_ssts(epoch, payloads)
            whole = mark("checkpoint.commit")
            counts = dict((done or {}).get("compaction") or {})
            if counts:
                # the compaction ran inside the commit: give it its
                # own stage and take it out of the commit's
                at = counts.pop("start_s")
                took = min(counts.pop("dur_s"), whole)
                stages[-1] = stages[-1][:2] + (whole - took, {})
                stages.append(("checkpoint.compact", at, took, counts))
            self._note_commit(epoch, edge[0] - t0, stages)
            if self.sinks is not None:
                await asyncio.to_thread(self.sinks.commit_upto, epoch)
        except asyncio.CancelledError:
            raise
        except BaseException as e:  # noqa: BLE001 — recorded, not lost
            if self._failure is None:
                self._failure = e
                self.failed.set()
        finally:
            # complete the chains even on failure/cancellation so
            # younger epochs wake up (they re-check _failure and abort
            # instead of committing)
            if not built.done():
                built.set_result(None)
            if not committed.done():
                committed.set_result(None)
            self._tasks.pop(epoch, None)
            self._set_depth()

    async def _upload(self, payload: dict) -> int:
        """One payload's durable upload: thread-offloaded PUT under the
        concurrency bound, retried with exponential backoff before the
        failure poisons the pipeline (fails the barrier). Returns the
        retries it took."""
        delay = self.retry_backoff_s
        for attempt in range(self.upload_retries + 1):
            async with self._sem:
                try:
                    await asyncio.to_thread(self.store.upload_payload,
                                            payload)
                    return attempt
                except asyncio.CancelledError:
                    raise
                except BaseException:
                    if attempt >= self.upload_retries:
                        raise
                    _STORAGE.sst_upload_retries.inc()
            await asyncio.sleep(delay)
            delay *= 2

    def _note_commit(self, epoch: int, upload_s: float,
                     stages: List[tuple]) -> None:
        assert epoch > self.committed_epoch, \
            (epoch, self.committed_epoch)    # ordered, never skips
        self.committed_epoch = epoch
        self.commit_log.append(epoch)
        _STREAMING.barrier_upload.observe(upload_s)
        if self.on_commit is not None:
            self.on_commit(epoch, upload_s, stages)
