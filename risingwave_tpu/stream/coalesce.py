"""Chunk compaction + adaptive coalescing for the streaming spine.

Motivation: masked dispatch and per-chunk device dispatch drown the
hot path in sparse slivers — a parallelism-4 hash dispatch hands every
downstream a full-capacity chunk that is ~1/4 visible, which then pays
full exchange credit, full wire bytes and a full ~2ms pjit dispatch
per sliver. Hazelcast Jet
(arXiv:2103.10169) and TiLT (arXiv:2301.12030) both land on the same
discipline: amortize per-item overheads by keeping every batch dense
and right-sized. This module is that discipline for StreamChunks:

- ``compact(chunk)``: drop invisible rows (one vectorized gather),
  keeping UpdateDelete/UpdateInsert pairs atomic — a pair whose halves
  are split by visibility degrades to plain Delete/Insert, the same
  invariant HashDispatcher enforces across outputs. Output capacity is
  the next pow-2 bucket, so downstream jit caches see the same small
  shape set they already compile for.
- ``ChunkCoalescer``: a barrier-bounded accumulator that merges
  consecutive small chunks up to a target cardinality. It NEVER holds
  a chunk across a Barrier/Mutation — callers must flush() before
  forwarding any barrier, so checkpoint semantics and p99 barrier
  latency are never traded for throughput. Watermarks RE-SEQUENCE to
  the next flush point instead of forcing one: a watermark is a
  monotone lower bound, so buffered rows (which preceded it) emit
  first and later rows already satisfy it — watermark-per-chunk
  generators (WatermarkFilterExecutor) would otherwise force a flush
  per chunk and neutralize the whole layer. A watermark still never
  crosses a barrier.
- ``CoalesceExecutor``: the executor-chain form, inserted in front of
  keyed executors (hash_join/hash_agg) whose per-chunk device dispatch
  is what coalescing amortizes.

The coalescer only ever merges WHOLE compacted chunks (no splits), so
update pairs that survived compaction stay adjacent by construction.
"""

from __future__ import annotations

from typing import AsyncIterator, List, Optional, Sequence

import numpy as np

from risingwave_tpu.common.chunk import Column, Op, StreamChunk, next_pow2
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.message import Message, is_chunk
from risingwave_tpu.utils.metrics import STREAMING as _METRICS

# default target cardinality of a coalesced chunk (session var
# stream_chunk_target_rows; 0 disables coalescing) — matches the
# sources' max.chunk.size ballpark so a healthy dense stream passes
# through untouched
DEFAULT_TARGET_ROWS = 4096
# linger bound: a buffer holding this many chunks flushes even below
# the row target (session var stream_coalesce_linger_chunks) — bounds
# host memory and per-flush merge work, NOT latency (the barrier does
# that; this is the pathological-many-tiny-chunks backstop)
DEFAULT_MAX_CHUNKS = 64


def is_empty(chunk: StreamChunk) -> bool:
    """Zero visible rows — THE emptiness predicate (dispatchers and
    the remote send path share it so dense_rows semantics cannot
    drift). Compacted chunks answer from dense_rows; others pay one
    host .any() over the (host-resident on these paths) visibility."""
    if chunk.dense_rows is not None:
        return chunk.dense_rows == 0
    return not np.asarray(chunk.visibility).any()


def compact(chunk: StreamChunk) -> Optional[StreamChunk]:
    """Dense copy of a chunk's visible rows; None when none are.

    One vectorized host pass: visible rows gather into a fresh
    next-pow-2-capacity chunk whose visibility is a full prefix.
    UpdateDelete/UpdateInsert pairs whose halves straddle the
    visibility mask degrade to Delete/Insert (dispatch.rs:640
    invariant: nobody may see half an update pair); pairs that survive
    whole stay adjacent because the gather preserves row order.

    Already-dense chunks (visible rows form a full prefix) return the
    ORIGINAL object with ``dense_rows`` stamped — the fast path for
    healthy streams.
    """
    vis = np.asarray(chunk.visibility)
    idx = np.flatnonzero(vis)
    t = int(len(idx))
    if t == 0:
        return None
    ops = np.asarray(chunk.ops)
    # fast path: dense prefix in a right-sized bucket. A fully-visible
    # chunk cannot straddle a pair; a masked-tail prefix can ONLY
    # straddle at the boundary (U- at t-1, its U+ at t masked) — that
    # one case must take the degrade path below.
    if t == chunk.capacity or (
            int(idx[-1]) == t - 1
            and next_pow2(t) == chunk.capacity
            and not (ops[t - 1] == int(Op.UPDATE_DELETE)
                     and ops[t] == int(Op.UPDATE_INSERT))):
        chunk.dense_rows = t
        return chunk
    is_ud = ops == int(Op.UPDATE_DELETE)
    is_ui = ops == int(Op.UPDATE_INSERT)
    next_vis = np.roll(vis, -1)
    next_vis[-1] = False
    prev_vis = np.roll(vis, 1)
    prev_vis[0] = False
    next_is_ui = np.roll(is_ui, -1)
    next_is_ui[-1] = False
    prev_is_ud = np.roll(is_ud, 1)
    prev_is_ud[0] = False
    # U- whose U+ half is invisible → plain DELETE; U+ whose U- half
    # is invisible → plain INSERT
    degrade_del = vis & is_ud & next_is_ui & ~next_vis
    degrade_ins = vis & is_ui & prev_is_ud & ~prev_vis
    if degrade_del.any() or degrade_ins.any():
        ops = ops.copy()
        ops[degrade_del] = int(Op.DELETE)
        ops[degrade_ins] = int(Op.INSERT)
    cap = next_pow2(t)
    cols: List[Column] = []
    for c in chunk.columns:
        vals = np.asarray(c.values)
        if c.is_device:
            out = np.zeros(cap, dtype=vals.dtype)
        else:
            out = np.empty(cap, dtype=object)
        out[:t] = vals[idx]
        validity = None
        if c.validity is not None:
            v = np.ones(cap, dtype=bool)
            v[:t] = np.asarray(c.validity)[idx]
            validity = v
        cols.append(Column(c.data_type, out, validity))
    new_vis = np.zeros(cap, dtype=bool)
    new_vis[:t] = True
    new_ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
    new_ops[:t] = ops[idx]
    out_chunk = StreamChunk(chunk.schema, cols, new_vis, new_ops)
    out_chunk.dense_rows = t
    if chunk.capacity > cap:
        _METRICS.compaction_rows_saved.inc(chunk.capacity - cap)
    return out_chunk


def merge_chunks(chunks: Sequence[StreamChunk]) -> StreamChunk:
    """Concatenate COMPACTED chunks (dense prefixes) into one dense
    chunk. Whole-chunk concatenation only — update pairs never split."""
    assert chunks, "merge_chunks needs at least one chunk"
    if len(chunks) == 1:
        return chunks[0]
    schema = chunks[0].schema
    sizes = [c.dense_rows if c.dense_rows is not None
             else c.cardinality() for c in chunks]
    total = int(sum(sizes))
    cap = next_pow2(max(total, 1))
    ncols = len(schema)
    cols: List[Column] = []
    for j in range(ncols):
        dt = schema[j].data_type
        if dt.is_device:
            first = np.asarray(chunks[0].columns[j].values)
            out = np.zeros(cap, dtype=first.dtype)
        else:
            out = np.empty(cap, dtype=object)
        has_validity = any(c.columns[j].validity is not None
                           for c in chunks)
        validity = np.ones(cap, dtype=bool) if has_validity else None
        at = 0
        for c, n in zip(chunks, sizes):
            col = c.columns[j]
            out[at:at + n] = np.asarray(col.values)[:n]
            if has_validity and col.validity is not None:
                validity[at:at + n] = np.asarray(col.validity)[:n]
            at += n
        cols.append(Column(dt, out, validity))
    vis = np.zeros(cap, dtype=bool)
    vis[:total] = True
    ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
    at = 0
    for c, n in zip(chunks, sizes):
        ops[at:at + n] = np.asarray(c.ops)[:n]
        at += n
    out_chunk = StreamChunk(schema, cols, vis, ops)
    out_chunk.dense_rows = total
    return out_chunk


class ChunkCoalescer:
    """Barrier-bounded accumulator of small chunks.

    ``push(chunk)`` returns the chunks ready to emit NOW (possibly
    empty); ``flush()`` drains whatever is buffered. The OWNER is
    responsible for calling flush() before forwarding ANY control
    message (Barrier/Watermark/Mutation) — that call is what makes the
    linger barrier-bounded.
    """

    def __init__(self, target_rows: int = DEFAULT_TARGET_ROWS,
                 max_chunks: int = DEFAULT_MAX_CHUNKS):
        self.target_rows = max(1, int(target_rows))
        self.max_chunks = max(1, int(max_chunks))
        self._buf: List[StreamChunk] = []
        self._rows = 0
        # col_idx → latest held watermark (monotone per col, so the
        # newest value subsumes older ones)
        self._held_wms: dict = {}

    def __len__(self) -> int:
        return len(self._buf)

    @property
    def buffered_rows(self) -> int:
        return self._rows

    def push(self, chunk: StreamChunk) -> List[StreamChunk]:
        _METRICS.coalesce_chunks_in.inc()
        c = compact(chunk)
        if c is None:
            return []                       # empty chunks vanish here
        t = c.dense_rows
        out: List[StreamChunk] = []
        if t >= self.target_rows:
            # big chunk passes through; buffered older rows go FIRST
            # (emission order == arrival order)
            f = self.flush()
            if f is not None:
                out.append(f)
            _METRICS.coalesce_chunks_out.inc()
            out.append(c)
            return out
        self._buf.append(c)
        self._rows += t
        if self._rows >= self.target_rows or \
                len(self._buf) >= self.max_chunks:
            out.append(self.flush())
        return out

    def push_watermark(self, wm) -> List[Message]:
        """Re-sequence a watermark to the next flush point. With an
        empty buffer it passes straight through; otherwise it is held
        (latest per column wins — watermarks are monotone) and
        released by drain_watermarks() right after the buffered rows.
        Sound because held rows PRECEDED the watermark and rows that
        arrive later already satisfy the (monotone) bound."""
        if not self._buf:
            return [wm]
        self._held_wms[wm.col_idx] = wm
        return []

    def drain_watermarks(self) -> List[Message]:
        """Held watermarks, to emit right after a flushed batch (and
        always before a barrier)."""
        if not self._held_wms:
            return []
        out = list(self._held_wms.values())
        self._held_wms.clear()
        return out

    def flush(self) -> Optional[StreamChunk]:
        if not self._buf:
            return None
        merged = merge_chunks(self._buf)
        self._buf = []
        self._rows = 0
        _METRICS.coalesce_chunks_out.inc()
        return merged


class CoalesceExecutor(Executor):
    """Executor-chain coalescing in front of keyed executors.

    Every device dispatch downstream (hash_join/hash_agg kernels) then
    carries a dense, right-sized batch. Control messages flush the
    buffer FIRST and are never delayed — a dedicated test
    (tests/test_coalesce.py) proves a barrier cannot be held back."""

    def __init__(self, input_: Executor,
                 target_rows: int = DEFAULT_TARGET_ROWS,
                 max_chunks: int = DEFAULT_MAX_CHUNKS):
        self.input = input_
        self.target_rows = int(target_rows)
        self.max_chunks = int(max_chunks)
        super().__init__(ExecutorInfo(
            input_.schema, list(input_.pk_indices), "CoalesceExecutor"))

    async def execute(self) -> AsyncIterator[Message]:
        from risingwave_tpu.stream.message import Watermark
        co = ChunkCoalescer(self.target_rows, self.max_chunks)
        async for msg in self.input.execute():
            if is_chunk(msg):
                outs = co.push(msg)
                for out in outs:
                    yield out
                if outs:
                    # a flush happened: release watermarks that were
                    # re-sequenced behind the buffered rows
                    for wm in co.drain_watermarks():
                        yield wm
            elif isinstance(msg, Watermark):
                for out in co.push_watermark(msg):
                    yield out
            else:
                # barrier-bound invariant: whatever lingers goes out
                # BEFORE the barrier (same epoch, same order)
                f = co.flush()
                if f is not None:
                    yield f
                for wm in co.drain_watermarks():
                    yield wm
                yield msg
        # upstream ended without a trailing barrier (bounded source /
        # test pipeline): buffered rows are data, not linger — flush
        f = co.flush()
        if f is not None:
            yield f
        for wm in co.drain_watermarks():
            yield wm
