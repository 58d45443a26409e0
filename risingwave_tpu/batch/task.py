"""Batch task manager: staged, partitioned query execution.

Reference parity: src/batch/src/task/ (task_manager.rs, the per-task
execution contexts) and the batch exchange operators
(src/batch/src/executor/generic_exchange.rs + the hash-shuffle the
scheduler inserts between stages). TPU re-design: a STAGE runs N
partition tasks concurrently; between stages an EXCHANGE re-partitions
rows by hash of the distribution keys (the same vnode hash the
streaming dispatch uses, so batch and streaming agree on ownership).
Tasks are asyncio coroutines; stages MATERIALIZE their output before
the exchange runs (no streaming backpressure yet — batch inputs are
committed snapshots, bounded by the MV size). The stage/partition/
exchange protocol shape is what the distributed deployment reuses:
the coordinator's credit TCP exchange carries the same chunks
between processes.

v1 covers the canonical two-stage shape the reference scheduler emits
for aggregations: parallel vnode-range scans → hash exchange on the
group keys → per-partition HashAgg → gather. Arbitrary plans still run
single-task through plan_batch.
"""

from __future__ import annotations

import asyncio
from typing import Iterator, List, Optional, Sequence

import numpy as np

from risingwave_tpu.batch.executors import (
    BatchExecutor, BatchHashAgg,
)
from risingwave_tpu.batch.storage_table import (
    StorageTable, rows_to_chunk,
)
from risingwave_tpu.common.chunk import DataChunk
from risingwave_tpu.common.hash import VNODE_COUNT, vnodes_of_host
from risingwave_tpu.state.keycodec import encode_vnode_prefix


class VnodeRangeScan(BatchExecutor):
    """Scan one vnode range of a table — a leaf partition task's input
    (row_seq_scan with a vnode bitmap in the reference)."""

    def __init__(self, table: StorageTable, epoch: int,
                 vnode_lo: int, vnode_hi: int, chunk_size: int = 1024):
        self.table = table
        self.schema = table.schema
        self.epoch = epoch
        self.lo, self.hi = vnode_lo, vnode_hi
        self.chunk_size = chunk_size

    def execute(self) -> Iterator[DataChunk]:
        start = encode_vnode_prefix(self.lo)
        end = encode_vnode_prefix(self.hi) if self.hi < VNODE_COUNT \
            else None
        # materialize the store scan EAGERLY: the task yields to the
        # event loop between chunks, and a barrier-triggered compaction
        # could vacuum a lazily-held SST mid-scan (bounded by the MV
        # snapshot size, same stance as StateTable._iter_range_raw)
        all_rows = [row for _k, row in self.table.store.iter(
            self.table.table_id, self.epoch, start, end)]
        for at in range(0, len(all_rows), self.chunk_size):
            yield rows_to_chunk(self.schema,
                                all_rows[at:at + self.chunk_size])


class _StageSource(BatchExecutor):
    """Stage input fed by an exchange (generic_exchange.rs source)."""

    def __init__(self, schema, chunks: List[DataChunk]):
        self.schema = schema
        self._chunks = chunks

    def execute(self) -> Iterator[DataChunk]:
        yield from self._chunks


def _hash_partition(chunk: DataChunk, key_indices: Sequence[int],
                    n: int) -> List[List[tuple]]:
    """Rows → n buckets by the vnode hash of the keys — the typed
    lane-building of the streaming dispatch (dispatch.py _route /
    state_table._encode_key_columns pattern: branch on the column TYPE,
    hash the numpy arrays directly, NULLs as the zero lane)."""
    rows = chunk.to_pylist()
    if not rows:
        return [[] for _ in range(n)]
    if not key_indices:
        return [list(rows)] + [[] for _ in range(n - 1)]
    vis = np.asarray(chunk.visibility)
    idx = np.flatnonzero(vis)
    lanes = []
    for i in key_indices:
        c = chunk.columns[i]
        vals = np.asarray(c.values)[idx]
        if c.data_type.is_device:
            if c.validity is not None:
                vals = np.where(np.asarray(c.validity)[idx], vals,
                                np.zeros((), dtype=vals.dtype))
            lanes.append(vals)
        else:
            from risingwave_tpu.common.hash import hash_strings_host
            lanes.append(hash_strings_host(
                np.asarray(vals, dtype=object), len(idx)))
    vn = vnodes_of_host(lanes)
    owner = (vn * n // VNODE_COUNT).astype(np.int64)
    out: List[List[tuple]] = [[] for _ in range(n)]
    for row, o in zip(rows, owner.tolist()):
        out[o].append(row)
    return out


class BatchTaskManager:
    """Run staged partitioned batch plans (task_manager.rs analog)."""

    def __init__(self, parallelism: int = 4):
        assert parallelism >= 1
        self.parallelism = parallelism

    async def _run_stage(self, factories) -> List[List[DataChunk]]:
        """Execute one stage's partition tasks concurrently."""
        async def one(factory):
            ex = factory()
            out = []
            for chunk in ex.execute():
                out.append(chunk)
                await asyncio.sleep(0)     # cooperative scheduling
            return out

        return list(await asyncio.gather(*(one(f) for f in factories)))

    async def run_agg(self, table: StorageTable, epoch: int,
                      group_indices: Sequence[int], agg_calls,
                      names: Optional[Sequence[str]] = None
                      ) -> List[tuple]:
        """The two-stage scheduler shape: parallel scan → hash
        exchange on the group keys → per-partition agg → gather.
        Result rows equal the single-task plan exactly (groups never
        span partitions: ownership is a function of the key hash; a
        grouping-free global agg routes to one partition)."""
        n = self.parallelism
        # stage 1: vnode-range scans
        step = (VNODE_COUNT + n - 1) // n
        scans = [
            (lambda lo=lo: VnodeRangeScan(
                table, epoch, lo, min(lo + step, VNODE_COUNT)))
            for lo in range(0, VNODE_COUNT, step)]
        scanned = await self._run_stage(scans)
        # exchange: hash-partition every scanned chunk by group key
        parts: List[List[tuple]] = [[] for _ in range(n)]
        for chunks in scanned:
            for chunk in chunks:
                for o, rows in enumerate(
                        _hash_partition(chunk, group_indices, n)):
                    parts[o].extend(rows)
        # stage 2: per-partition agg over its routed rows
        aggs = [
            (lambda p=p: BatchHashAgg(
                _StageSource(table.schema,
                             [] if not parts[p] else
                             [rows_to_chunk(table.schema, parts[p])]),
                list(group_indices), list(agg_calls), names))
            for p in range(n)]
        agged = await self._run_stage(aggs)
        # gather (exchange to the root, merge-free: disjoint groups)
        out: List[tuple] = []
        for chunks in agged:
            for chunk in chunks:
                out.extend(chunk.to_pylist())
        return out
