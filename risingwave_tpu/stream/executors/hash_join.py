"""HashJoinExecutor: streaming two-sided equi-join (inner, q8 kernel).

Reference parity: src/stream/src/executor/hash_join.rs:227 (executor),
:697 (main loop over barrier-aligned sides), :990 (``eq_join_oneside``);
state layout managed_state/join/mod.rs:228 (JoinHashMap). TPU re-design
(ops/hash_join.py): the device owns the MATCH structure — key table +
row chains probed as whole-batch kernels; the host owns row payloads
(typed column arenas; varchar never ships to HBM) and materializes
output chunks with vectorized gathers.

Chunk lifecycle on side S (probing side O), mirroring eq_join_oneside
but batched by the EPOCH (sequence-versioned state, see
ops/hash_join.py):
  1. ingest: host bookkeeping only — the chunk takes the next message
     sequence, inserts allocate arena refs, deletes find theirs, and
     its rows join S's epoch buffer; nothing is dispatched
  2. barrier (or a watermark that must trail the data): each side's
     buffer ships as ONE apply to S's state and ONE probe of O, every
     row at its own sequence — each result is exact for its sequence
     no matter how much state the epoch applied — and emission runs in
     message order: matched pairs (S columns from the chunk, O columns
     from O's payload lanes or arena) through the join's own
     condition where an inner join has one (the conjuncts of its ON /
     WHERE that are no hash keys: hash_join.rs `cond`), outer
     NULL-padding, semi/anti rows, and degree-transition flips. Update
     pairs degrade to Delete+Insert, as the reference degrades split
     pairs.
  3. both sides' StateTables commit; watermark expiry and compaction
     run AFTER the sweep (they rewrite device state that a re-
     dispatched probe would need); recovery rebuilds arena + chains
     and recomputes degrees with one batch probe.

Inner-join NULL semantics: rows whose join key contains NULL can never
match and are not stored (the reference's null-safe flag is per-column;
non-null-safe is the SQL default).
"""

from __future__ import annotations

import enum
from itertools import compress
from typing import AsyncIterator, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


# smallest row capacity of a side's staged epoch (_dispatch_epoch)
_EPOCH_ROWS_FLOOR = 64


class JoinType(enum.Enum):
    """The 8 streaming join types (hash_join.rs:61-71 const generics).

    Outer sides track per-stored-row match DEGREES. The reference
    persists degree state tables (managed_state/join/mod.rs:228); here
    degrees are a host int64 array parallel to the arena, recomputed on
    recovery by ONE batch probe of the recovered keys against the other
    side — the degree is a pure function of both sides' state, so
    persisting it buys nothing but write amplification.
    """

    INNER = "inner"
    LEFT_OUTER = "left_outer"
    RIGHT_OUTER = "right_outer"
    FULL_OUTER = "full_outer"
    LEFT_SEMI = "left_semi"
    LEFT_ANTI = "left_anti"
    RIGHT_SEMI = "right_semi"
    RIGHT_ANTI = "right_anti"

    @property
    def is_semi_or_anti(self) -> bool:
        return self in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                        JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI)

    @property
    def is_anti(self) -> bool:
        return self in (JoinType.LEFT_ANTI, JoinType.RIGHT_ANTI)

    @property
    def subject(self) -> Optional[int]:
        """Side whose rows a semi/anti join emits (0=left, 1=right)."""
        if self in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            return 0
        if self in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            return 1
        return None

    @property
    def tracked_sides(self) -> tuple:
        """Sides whose stored rows need degree maintenance."""
        if self == JoinType.LEFT_OUTER:
            return (0,)
        if self == JoinType.RIGHT_OUTER:
            return (1,)
        if self == JoinType.FULL_OUTER:
            return (0, 1)
        if self.is_semi_or_anti:
            return (self.subject,)
        return ()

    def outer_on(self, side: int) -> bool:
        """Does `side` emit NULL-padded rows when unmatched?"""
        if self == JoinType.FULL_OUTER:
            return True
        return (self == JoinType.LEFT_OUTER and side == 0) or \
            (self == JoinType.RIGHT_OUTER and side == 1)

from risingwave_tpu.common.chunk import Column, Op, StreamChunk, next_pow2
from risingwave_tpu.common.types import DataType, Field, Schema
from risingwave_tpu.expr.expr import BinaryOp, Expression, expr_refs
from risingwave_tpu.ops.hash_join import BatchRung, JoinSideKernel
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.stream.executor import Executor, ExecutorInfo
from risingwave_tpu.stream.merge import barrier_align_2
from risingwave_tpu.stream.executors.keys import (
    LANES_PER_KEY, KeyCodec,
)
from risingwave_tpu.stream.executors.simple import FilterExecutor
from risingwave_tpu.stream.message import Message, Watermark, is_barrier
from risingwave_tpu.stream.trace_ctx import (
    dispatch_span, join_condition_span, join_to_agg_handoff,
)
from risingwave_tpu.stream import costs as _costs
from risingwave_tpu.stream import hotkeys as _hotkeys
from risingwave_tpu.utils.ledger import LEDGER, staged
from risingwave_tpu.utils.metrics import STREAMING as _METRICS
from risingwave_tpu.utils.metrics import note_join_condition


class _Arena:
    """Host row store: typed column arrays indexed by device row refs."""

    def __init__(self, schema: Schema, capacity: int = 1024):
        self.schema = schema
        self.cap = capacity
        self.cols: List[np.ndarray] = []
        self.valid: List[np.ndarray] = []
        for f in schema:
            dt = f.data_type
            self.cols.append(
                np.zeros(capacity, dtype=dt.np_dtype) if dt.is_device
                else np.empty(capacity, dtype=object))
            self.valid.append(np.ones(capacity, dtype=bool))

    def ensure(self, max_ref: int) -> None:
        if max_ref < self.cap:
            return
        new_cap = self.cap
        while new_cap <= max_ref:
            new_cap *= 2
        for i, c in enumerate(self.cols):
            grown = np.zeros(new_cap, dtype=c.dtype) if c.dtype != object \
                else np.empty(new_cap, dtype=object)
            grown[:self.cap] = c
            self.cols[i] = grown
            v = np.ones(new_cap, dtype=bool)
            v[:self.cap] = self.valid[i]
            self.valid[i] = v
        self.cap = new_cap

    def store(self, refs: np.ndarray, chunk: StreamChunk,
              row_idx: np.ndarray) -> None:
        if not len(refs):
            return
        self.ensure(int(refs.max()))
        for i, c in enumerate(chunk.columns):
            vals = np.asarray(c.values)[row_idx]
            self.cols[i][refs] = vals
            self.valid[i][refs] = True if c.validity is None else \
                np.asarray(c.validity)[row_idx]

    def gather(self, refs: np.ndarray, out_cap: int
               ) -> List[Column]:
        return [self.gather_col(i, refs, out_cap)
                for i in range(len(self.schema))]

    def gather_col(self, i: int, refs: np.ndarray,
                   out_cap: int) -> Column:
        f, c, v = self.schema[i], self.cols[i], self.valid[i]
        vals = np.zeros(out_cap, dtype=c.dtype) if c.dtype != object \
            else np.empty(out_cap, dtype=object)
        vals[:len(refs)] = c[refs]
        ok = np.ones(out_cap, dtype=bool)
        ok[:len(refs)] = v[refs]
        return Column(f.data_type, vals, None if ok.all() else ok)


class _JoinSide:
    """One side's state: device matcher + host arena + durability.

    With a mesh, the matcher is the vnode-sharded SPMD kernel
    (parallel/join.ShardedJoinKernel) — same API, rows routed to their
    key's owner shard by an in-program all_to_all (the reference's
    hash dispatch to N parallel join actors, dispatch.rs:582)."""

    def __init__(self, schema: Schema, key_indices: Sequence[int],
                 pk_indices: Sequence[int], table: StateTable,
                 key_codec: KeyCodec, mesh=None,
                 shard_opts: Optional[dict] = None,
                 device_payload: bool = True):
        self.schema = schema
        self.key_indices = list(key_indices)
        self.pk_indices = list(pk_indices)
        self.key_types = [schema[i].data_type for i in self.key_indices]
        # SHARED with the other side: equal values must get equal
        # interned ids or varchar keys would never match
        self.key_codec = key_codec
        self.table = table
        # device-resident payload lanes (ops/hash_join.py): every
        # device-typed column of a stored row lives in HBM as a
        # (hi, lo, valid) int32 triple indexed by row ref, written in
        # the same dispatch that links chains and gathered ON DEVICE
        # by the probe's emit walk. Varchar/host-typed columns can
        # never ship to HBM — they stay arena-gathered by ref from the
        # same packed header. Single-chip only (the sharded kernel's
        # epoch probe returns refs and the host gathers every column).
        self.device_payload = bool(device_payload) and mesh is None
        self.pay_indices: List[int] = [
            i for i, f in enumerate(schema) if f.data_type.is_device
        ] if self.device_payload else []
        self.pay_pos: Dict[int, int] = {
            c: k for k, c in enumerate(self.pay_indices)}
        # fused input run (frontend/opt/fusion.py try_fuse_join):
        # `schema` above is the run's OUTPUT space; chunks arrive raw,
        # the composed chain runs once on numpy for host bookkeeping,
        # and the device prelude re-derives the upload lanes inside
        # the epoch dispatches (ops/fused.build_join_prelude)
        self.fused_input = None
        self._prelude = None
        self._prelude_cache_key = None
        # device kernel is built LAZILY (first data touch): building it
        # here would initialize the JAX backend — and claim the TPU —
        # in processes that only PLAN (the distributed frontend
        # serializes the executor tree to IR and discards it)
        self._mesh = mesh
        self._shard_opts = dict(shard_opts or {})
        self._kernel = None
        self.arena = _Arena(schema)
        self.pk_to_ref: Dict[tuple, int] = {}
        self.free: List[int] = []
        self.next_ref = 0
        # cold-state tier (managed_state/join/mod.rs:379-420 LRU-over-
        # StateTable analog, driven by state/tier.py): the tier's sweep
        # hands this side the coldest keys — their rows leave the arena
        # + device (see evict_keys) but stay durable in the state
        # table; a later probe of an evicted key reloads it first (see
        # HashJoinExecutor._reload_cold). cold_keys: key LANES tuple →
        # key VALUES tuple (the values drive the state-table prefix
        # scan on reload)
        self.state_cap: Optional[int] = None
        self.cold_keys: Dict[tuple, tuple] = {}
        # lanes of keys watermark-expiry dropped (resident AND cold) —
        # the executor drains these into tier.forget after each sweep
        self.expired_lanes: List[tuple] = []
        # the batch size of a watermark expiry's tombstones
        self._expire_rung = BatchRung()
        # per-ref match degree (outer/semi/anti bookkeeping; see
        # JoinType docstring). On the single-chip epoch path the
        # AUTHORITATIVE copy is the kernel's device array, maintained
        # inside the probe dispatches (ops/hash_join.epoch_probe) —
        # this host array then stays empty and emission replays
        # per-chunk transitions from the packed matrix's old-degree
        # column. The sharded kernel keeps the host array.
        self.dev_degrees = mesh is None
        self.track_degrees = False      # set by the executor (tracked
        self.degrees = np.zeros(         # sides only)
            0 if self.dev_degrees else self.arena.cap, dtype=np.int64)

    @property
    def prelude(self):
        """Traced lane builder for the fused input run (lazy — builds
        against the jnp expression layer on first dispatch)."""
        if self._prelude is None and self.fused_input is not None:
            from risingwave_tpu.ops.fused import build_join_prelude
            self._prelude = build_join_prelude(
                self.fused_input, self.key_indices, self.pay_indices)
        return self._prelude

    @property
    def kernel(self):
        if self._kernel is None:
            if self._mesh is not None:
                from risingwave_tpu.parallel.join import ShardedJoinKernel
                self._kernel = ShardedJoinKernel(
                    self._mesh,
                    key_width=LANES_PER_KEY * len(self.key_indices),
                    **self._shard_opts)
                self._kernel.table_id = self.table.table_id
            else:
                # capacity presize hints ride in shard_opts for the
                # single-chip kernel too: every growth doubling costs
                # a rehash + a fresh XLA trace/compile of the epoch
                # programs, so a builder that knows its cardinality
                # should say so
                opts = {k: v for k, v in self._shard_opts.items()
                        if k in ("key_capacity", "row_capacity",
                                 "probe_capacity")}
                self._kernel = JoinSideKernel(
                    key_width=LANES_PER_KEY * len(self.key_indices),
                    payload_width=3 * len(self.pay_indices),
                    **opts)
        return self._kernel

    def _row_key_lanes(self, chunk: StreamChunk, r: int
                       ) -> Optional[tuple]:
        """One row's join-key lanes tuple (the cold_keys key), or None
        when any key column is NULL — null keys are never stored, so
        they cannot be cold. Miss-path only (rare)."""
        vals = []
        for i in self.key_indices:
            c = chunk.columns[i]
            v = np.asarray(c.values)[r]
            if c.validity is not None and \
                    not bool(np.asarray(c.validity)[r]):
                return None
            vals.append(v.item() if hasattr(v, "item") else v)
        if any(v is None for v in vals):
            return None
        return tuple(self.key_codec.lanes_of_values(vals).tolist())

    def ensure_degrees(self, max_ref: int) -> None:
        if self.dev_degrees or max_ref < len(self.degrees):
            return
        grown = np.zeros(self.arena.cap, dtype=np.int64)
        grown[:len(self.degrees)] = self.degrees
        self.degrees = grown

    def nbytes(self) -> int:
        """Accounted host state (EstimateSize analog): arena columns,
        degree array, pk→ref map."""
        arena = sum(
            c.nbytes if c.dtype != object else c.size * 8
            for c in self.arena.cols)
        return arena + self.degrees.nbytes + 120 * len(self.pk_to_ref)

    def host_arena_bytes(self) -> int:
        """The residency metric's host half (arena columns only)."""
        return sum(c.nbytes if c.dtype != object else c.size * 8
                   for c in self.arena.cols)

    # -- device payload lanes (ops/lanes.py payload codecs) ---------------
    def payload_rows(self, chunk: StreamChunk) -> np.ndarray:
        """int32[cap, 3*len(pay_indices)] payload lanes for every slot
        (the device scatter masks non-inserted rows itself)."""
        from risingwave_tpu.ops.lanes import payload_lanes
        return payload_lanes(
            [(np.asarray(chunk.columns[i].values),
              None if chunk.columns[i].validity is None
              else np.asarray(chunk.columns[i].validity))
             for i in self.pay_indices])

    def payload_from_arena(self, refs: np.ndarray) -> np.ndarray:
        """Payload lanes of stored rows (recovery / compaction /
        cold-tier reload rebuild the device store from the durable
        host copy)."""
        from risingwave_tpu.ops.lanes import payload_lanes
        return payload_lanes(
            [(self.arena.cols[i][refs], self.arena.valid[i][refs])
             for i in self.pay_indices])

    def matched_col(self, i: int, pay_rows: Optional[np.ndarray],
                    refs: np.ndarray, out_cap: int) -> Column:
        """Column `i` of the matched stored rows: a device-typed
        column decodes from the payload lanes the probe gathered ON
        DEVICE (the packed probe matrix); a varchar/host-typed column
        gathers from the arena by ref (the only host gathers left on
        the emit path), and so does every column where `pay_rows` is
        None (the sharded kernel, device_payload off)."""
        k = self.pay_pos.get(i)
        if pay_rows is None or k is None:
            return self.arena.gather_col(i, refs, out_cap)
        from risingwave_tpu.ops import lanes as _lanes
        f = self.schema[i]
        t = len(refs)
        hi = pay_rows[:, 3 * k].astype(np.int64)
        lo = pay_rows[:, 3 * k + 1]
        v64 = (hi << np.int64(32)) | \
            lo.view(np.uint32).astype(np.int64)
        dt = np.dtype(f.data_type.np_dtype)
        vals = np.zeros(out_cap, dtype=dt)
        vals[:t] = _lanes.decode_payload_i64(v64, dt)
        ok = np.ones(out_cap, dtype=bool)
        ok[:t] = pay_rows[:, 3 * k + 2] != 0
        return Column(f.data_type, vals, None if ok.all() else ok)

    def alloc_refs(self, k: int) -> np.ndarray:
        """Bump allocation ONLY: a tombstoned ref stays linked in its
        chain (deletes unlink lazily), so reusing it would splice its
        node into a second chain and create cycles. Dead refs are
        reclaimed wholesale when the arena is rebuilt (recovery /
        future compaction); `self.free` tracks the reclaimable count."""
        out = np.arange(self.next_ref, self.next_ref + k, dtype=np.int32)
        self.next_ref += k
        return out

    def key_nonnull_mask(self, chunk: StreamChunk) -> np.ndarray:
        m = np.ones(chunk.capacity, dtype=bool)
        for i in self.key_indices:
            c = chunk.columns[i]
            if c.validity is not None:
                m &= np.asarray(c.validity)
            if not c.data_type.is_device:
                # host-typed columns carry NULL as the None object
                vals = np.asarray(c.values)
                m &= np.fromiter(
                    (isinstance(v, (str, bytes)) for v in vals.tolist()),
                    dtype=bool, count=chunk.capacity)
        return m

    def apply_chunk_host(self, chunk: StreamChunk,
                         nonnull: Optional[np.ndarray] = None
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray, np.ndarray, np.ndarray]:
        """HOST half of a chunk apply: pk→ref/arena bookkeeping only.
        Returns (ins_idx, ins_refs, full_refs, ins_mask, del_refs,
        del_mask) for the side's epoch applies (ops/hash_join.py
        epoch_apply / epoch_probe; parallel/join.py's twins) — the
        executor buffers every chunk of an epoch into one dispatch.

        pk→ref bookkeeping runs in ROW ORDER (a delete refers to the
        latest same-pk version, which may be an insert earlier in this
        very chunk — update pairs land as [U-, U+] with one pk); an
        all-insert chunk (append-only sources — the common case) takes
        a bulk dict.update instead of the per-row loop."""
        vis = np.asarray(chunk.visibility)
        if nonnull is None:
            nonnull = self.key_nonnull_mask(chunk)
        storable = vis & nonnull
        ops = np.asarray(chunk.ops)
        is_ins = (ops == int(Op.INSERT)) | (ops == int(Op.UPDATE_INSERT))
        ins_idx = np.flatnonzero(storable & is_ins)
        # pk extraction: one vectorized pass (tolist + zip run in C;
        # a per-row generator here dominated the q8 host profile)
        st_idx = np.flatnonzero(storable)
        pk_lists = []
        for i in self.pk_indices:
            c = chunk.columns[i]
            vals = np.asarray(c.values)[st_idx]
            col = vals.tolist()
            if c.validity is not None:
                okv = np.asarray(c.validity)[st_idx]
                col = [None if not o else v
                       for v, o in zip(col, okv.tolist())]
            pk_lists.append(col)
        pk_tuples = list(zip(*pk_lists)) if pk_lists \
            else [()] * len(st_idx)

        ins_refs = self.alloc_refs(len(ins_idx))
        del_refs = np.zeros(chunk.capacity, dtype=np.int32)
        del_mask = np.zeros(chunk.capacity, dtype=bool)
        if len(ins_idx) == len(st_idx):
            # append-only fast path: no deletes, refs align with pks
            self.pk_to_ref.update(zip(pk_tuples, ins_refs.tolist()))
        else:
            pks = dict(zip(st_idx.tolist(), pk_tuples))
            ins_pos = {int(r): j for j, r in enumerate(ins_idx)}
            for r in st_idx.tolist():
                if r in ins_pos:
                    self.pk_to_ref[pks[r]] = int(ins_refs[ins_pos[r]])
                else:
                    ref = self.pk_to_ref.pop(pks[r], None)
                    if ref is None:
                        # unseen pk: either an inconsistent delete
                        # (ignore, reference behavior) or — with the
                        # cold tier on — a retraction for an EVICTED
                        # key, whose device bookkeeping cannot be
                        # applied. The planner only enables state_cap
                        # on provably append-only inputs; failing loud
                        # here beats leaving already-emitted join
                        # outputs permanently stale (ADVICE r5 high).
                        if self.cold_keys and \
                                self._row_key_lanes(chunk, r) \
                                in self.cold_keys:
                            raise RuntimeError(
                                "join cold-state tier got a retraction "
                                "for an evicted key — state_cap "
                                "requires append-only inputs (the "
                                "planner disables the cap when it "
                                "cannot prove them)")
                        continue
                    del_refs[r] = ref
                    del_mask[r] = True
                    self.free.append(ref)
        full_refs = np.zeros(chunk.capacity, dtype=np.int32)
        ins_mask = np.zeros(chunk.capacity, dtype=bool)
        if len(ins_idx):
            self.arena.store(ins_refs, chunk, ins_idx)
            self.ensure_degrees(int(ins_refs.max()))
            full_refs[ins_idx] = ins_refs
            ins_mask[ins_idx] = True
        # append-only epochs stage past the memtable (ISSUE 12): join
        # state pks are upstream row identities, distinct per epoch by
        # the changelog contract; mixed-op chunks spill and merge
        self.table.write_chunk(chunk, defer=True)
        return ins_idx, ins_refs, full_refs, ins_mask, del_refs, del_mask

    # dead-ref fraction of the arena that triggers a compaction; dead
    # refs cannot be recycled in place (see alloc_refs), so churn-heavy
    # streams (update pairs every epoch) reclaim them wholesale here
    COMPACT_DEAD_RATIO = 0.5
    COMPACT_MIN_REFS = 4096

    def maybe_compact(self) -> bool:
        if (self.next_ref < self.COMPACT_MIN_REFS
                or len(self.free) < self.COMPACT_DEAD_RATIO * self.next_ref):
            return False
        self.compact()
        return True

    def compact(self) -> None:
        """Rebuild arena + device state with only live rows (dense refs)."""
        live = np.fromiter(self.pk_to_ref.values(), dtype=np.int64,
                           count=len(self.pk_to_ref))
        n = len(live)
        # degrees survive a pure compaction: snapshot before the device
        # rebuild resets them (device mode reads the kernel array; the
        # cold-tier evict path recomputes on reload instead, but a
        # stale value at a never-again-probed ref is unobservable)
        if self.dev_degrees:
            live_deg = self.kernel.read_degrees(live) \
                if (self.track_degrees and n) else None
        new_arena = _Arena(self.schema,
                           capacity=max(1024, next_pow2(max(n, 1))))
        for i in range(len(self.schema)):
            new_arena.cols[i][:n] = self.arena.cols[i][live]
            new_arena.valid[i][:n] = self.arena.valid[i][live]
        if not self.dev_degrees:
            new_degrees = np.zeros(new_arena.cap, dtype=np.int64)
            new_degrees[:n] = self.degrees[live]
            self.degrees = new_degrees
        self.arena = new_arena
        new_refs = np.arange(n, dtype=np.int32)
        self.pk_to_ref = dict(zip(self.pk_to_ref.keys(), new_refs.tolist()))
        self.free = []
        self.next_ref = n
        if n:
            key_cols = [(self.arena.cols[i][:n], self.arena.valid[i][:n])
                        for i in self.key_indices]
            kw = {"payload": self.payload_from_arena(new_refs)} \
                if self.pay_indices else {}
            self.kernel.rebuild(
                self.key_codec.build_arrays(key_cols), new_refs, **kw)
        else:
            self.kernel.rebuild(
                np.zeros((0, LANES_PER_KEY * len(self.key_indices)),
                         dtype=np.int32),
                new_refs)
        if self.dev_degrees and live_deg is not None:
            self.kernel.write_degrees(new_refs, live_deg)

    def expire_below(self, key_pos: int, wm_physical,
                     seq: int = 0) -> int:
        """Watermark state expiry (hash_join.rs:860-945 analog): drop
        every stored row whose ``key_pos``-th join-key column is below
        the watermark. Host side: vectorized scan of live refs → dead
        pks removed from the map, rows deleted from the state table,
        refs tombstoned on device (the existing compaction reclaims the
        arena/chain slots when the dead ratio crosses its threshold).
        Cost is O(live) per call — the executor only calls this when the
        combined watermark actually advances. Cold (evicted) keys below
        the watermark expire too: their durable rows delete and the
        cold marker drops — otherwise the state table would grow
        without bound on exactly the keys-drift workloads the cold
        tier exists for."""
        n_cold = 0
        if self.cold_keys:
            dead_cold = [
                (lt, vt) for lt, vt in self.cold_keys.items()
                if vt[key_pos] is not None
                and int(vt[key_pos]) < int(wm_physical)]
            for lt, vt in dead_cold:
                del self.cold_keys[lt]
                self.expired_lanes.append(lt)
                # a row that came after its key went cold is resident
                # (reload_keys skips it likewise): the pass below
                # deletes it, once
                dead_rows = [tuple(row) for _pk, row
                             in self.table.iter_prefix(list(vt))
                             if tuple(row[i] for i in self.pk_indices)
                             not in self.pk_to_ref]
                if dead_rows:
                    self.table.delete_rows(dead_rows)
                    n_cold += len(dead_rows)
        if not self.pk_to_ref:
            return n_cold
        col = self.key_indices[key_pos]
        refs = np.fromiter(self.pk_to_ref.values(), dtype=np.int64,
                           count=len(self.pk_to_ref))
        vals = self.arena.cols[col][refs]
        ok = self.arena.valid[col][refs]
        dead = ok & (vals.astype(np.int64) < int(wm_physical))
        dead_refs = refs[dead]
        n_dead = len(dead_refs)
        if n_dead == 0:
            return n_cold
        # by the column from here to the memtable, and no row is
        # built. The map loses its dead under its own keys (the arena's
        # pk columns hold the same values, but a NaN there would not
        # find itself, and the map's own tuples compare by identity)
        for pk in list(compress(self.pk_to_ref, dead.tolist())):
            del self.pk_to_ref[pk]
        self.free.extend(dead_refs.tolist())
        # the state table takes the dead rows' pk columns as the arena
        # holds them, and encodes its tombstones' keys from those
        self.table.delete_keys(
            [(self.arena.cols[i][dead_refs], self.arena.valid[i][dead_refs])
             for i in self.pk_indices], n_dead)
        dead_refs = dead_refs.astype(np.int32)
        # key lanes of the dead refs: the sharded kernel routes the
        # tombstone to the key's owner shard (single-chip ignores them)
        key_cols = [(self.arena.cols[i][dead_refs],
                     self.arena.valid[i][dead_refs])
                    for i in self.key_indices]
        dead_lanes = self.key_codec.build_arrays(key_cols)
        if self.state_cap is not None:
            # tier bookkeeping only: an uncapped side must not grow
            # this list forever (the executor drains it per barrier,
            # but only tiered sides have anything to forget)
            self.expired_lanes.extend(
                map(tuple, np.unique(dead_lanes, axis=0).tolist()))
        # the tombstone's batch keeps to a rung: the rows a watermark
        # closes differ every barrier, its program must not
        rung = self._expire_rung
        with LEDGER.phase("device_compute", kernel="hash_join.expire",
                          stage="launch"):
            for lo, hi in rung.pages(n_dead):
                self.kernel.delete(
                    rung.padded(dead_refs, lo, hi), rung.mask(lo, hi),
                    seq=seq, key_lanes=rung.padded(dead_lanes, lo, hi))
        return n_dead + n_cold

    def evict_keys(self, lanes_ts: Sequence[tuple]
                   ) -> Tuple[int, int]:
        """Targeted cold-tier eviction (state/tier.py sweep callback):
        every row of each given key leaves the arena + device together
        — a probe must see all or none — but stays durable in the
        state table. Returns (keys evicted, rows evicted): the tier's
        counters are in KEYS; the join_rows_evicted metric wants rows.
        Caller (the tier, at this executor's own checkpoint barrier)
        guarantees no in-flight probes."""
        want = set(lanes_ts)
        if not want or not self.pk_to_ref:
            return 0, 0
        pks = list(self.pk_to_ref.keys())
        refs = np.fromiter(self.pk_to_ref.values(), dtype=np.int64,
                           count=len(pks))
        key_cols = [(self.arena.cols[i][refs],
                     self.arena.valid[i][refs])
                    for i in self.key_indices]
        lane_rows = list(map(tuple,
                             self.key_codec.build_arrays(key_cols)
                             .tolist()))
        evicted = 0
        vt_by_lane: Dict[tuple, tuple] = {}
        for j, lt in enumerate(lane_rows):
            if lt not in want:
                continue
            if lt not in vt_by_lane:
                vt = tuple(
                    None if not ok[j] else
                    (v[j].item() if hasattr(v[j], "item") else v[j])
                    for v, ok in key_cols)
                if any(x is None for x in vt):
                    continue       # null-key rows are never stored
                vt_by_lane[lt] = vt
            ref = self.pk_to_ref.pop(pks[j])
            self.free.append(ref)
            evicted += 1
        self.cold_keys.update(vt_by_lane)
        if evicted:
            # compaction rebuilds arena + device from the survivors —
            # evicted rows leave the kernel wholesale (degree state of
            # evicted rows drops too: a degree is a pure function of
            # both sides' durable state, recomputed on reload)
            self.compact()
        return len(vt_by_lane), evicted

    def reload_keys(self, need: Dict[tuple, tuple]) -> tuple:
        """Reload evicted keys' rows from the state table (arena +
        pk_to_ref + a batched device insert at seq 0, visible to every
        probe). Returns (lanes, aux, n, max_ref) for the device apply,
        or None when nothing reloaded."""
        from risingwave_tpu.ops.hash_join import FLAG_INS

        rows: List[tuple] = []
        lanes_rows: List[tuple] = []
        for lanes_t, values_t in need.items():
            if lanes_t not in self.cold_keys:
                continue
            del self.cold_keys[lanes_t]
            for _pk, row in self.table.iter_prefix(list(values_t)):
                row = tuple(row)
                if tuple(row[i] for i in self.pk_indices) \
                        in self.pk_to_ref:
                    # a row inserted AFTER the key went cold is already
                    # resident — re-adding it would double its matches
                    continue
                rows.append(row)
                lanes_rows.append(lanes_t)
        if not rows:
            return None
        n = len(rows)
        refs = self.alloc_refs(n)
        self.arena.ensure(int(refs.max()))
        for i, f in enumerate(self.schema):
            col_vals = [r[i] for r in rows]
            if f.data_type.is_device:
                ok = np.asarray([v is not None for v in col_vals])
                vals = np.asarray(
                    [0 if v is None else v for v in col_vals],
                    dtype=f.data_type.np_dtype)
                self.arena.cols[i][refs] = vals
                self.arena.valid[i][refs] = ok
            else:
                self.arena.cols[i][refs] = np.asarray(col_vals,
                                                      dtype=object)
                self.arena.valid[i][refs] = True
        self.ensure_degrees(int(refs.max()))
        for row, ref in zip(rows, refs.tolist()):
            self.pk_to_ref[tuple(row[i] for i in self.pk_indices)] = ref
        cap = next_pow2(n)
        w = LANES_PER_KEY * len(self.key_indices)
        up = np.zeros((cap, w + 3 * len(self.pay_indices)),
                      dtype=np.int32)
        up[:n, :w] = np.asarray(lanes_rows, dtype=np.int32)
        if self.pay_indices:
            # reloaded rows' payload lanes rebuild from the arena copy
            # just stored above — the same scatter shape as a live
            # insert
            up[:n, w:] = self.payload_from_arena(refs)
        aux = np.zeros((cap, 4), dtype=np.int32)
        aux[:n, 0] = refs
        aux[:n, 2] = FLAG_INS
        # seq 0: reloaded rows predate every live sequence, so every
        # probe of this epoch sees them
        return up, aux, n, int(refs.max())

    def recover(self) -> None:
        keys_l, refs_l = [], []
        rows: List[tuple] = []
        for _pk, row in self.table.iter_rows():
            rows.append(row)
        if not rows:
            return
        n = len(rows)
        refs = self.alloc_refs(n)
        self.arena.ensure(int(refs.max()))
        for i, f in enumerate(self.schema):
            col_vals = [r[i] for r in rows]
            if f.data_type.is_device:
                ok = np.asarray([v is not None for v in col_vals])
                vals = np.asarray(
                    [0 if v is None else v for v in col_vals],
                    dtype=f.data_type.np_dtype)
                self.arena.cols[i][refs] = vals
                self.arena.valid[i][refs] = ok
            else:
                self.arena.cols[i][refs] = np.asarray(col_vals,
                                                      dtype=object)
        for row, ref in zip(rows, refs.tolist()):
            pk = tuple(row[i] for i in self.pk_indices)
            self.pk_to_ref[pk] = ref
            keys_l.append(self.key_codec.lanes_of_values(
                [row[i] for i in self.key_indices]))
        # rows with NULL join keys were never stored on device
        keep = [j for j, row in enumerate(rows)
                if all(row[i] is not None for i in self.key_indices)]
        if keep:
            # device payload lanes rebuild exactly where the chains
            # rebuild — from the recovered arena rows (the sharded
            # kernel has no payload store; don't pass the kwarg)
            kw = {"payload": self.payload_from_arena(refs[keep])} \
                if self.pay_indices else {}
            self.kernel.rebuild(np.stack([keys_l[j] for j in keep]),
                                refs[keep], **kw)


class HashJoinExecutor(Executor):
    """Streaming inner equi-join (hash_join.rs:227, device matcher)."""

    def __init__(self, left: Executor, right: Executor,
                 left_keys: Sequence[int], right_keys: Sequence[int],
                 left_table: StateTable, right_table: StateTable,
                 actor_id: int = 0,
                 output_names: Optional[Sequence[str]] = None,
                 join_type: JoinType = JoinType.INNER,
                 mesh=None, shard_opts: Optional[dict] = None,
                 state_cap: Optional[int] = None,
                 device_payload: bool = True,
                 condition: Optional[Expression] = None):
        assert len(left_keys) == len(right_keys)
        self.left_in, self.right_in = left, right
        self.join_type = join_type
        # rebuild recipe for plan rewrites (frontend/opt): the
        # column-pruning rule reconstructs the join over narrowed
        # inputs and must reproduce this exact configuration
        self.rebuild_opts = {"actor_id": actor_id, "mesh": mesh,
                             "shard_opts": shard_opts,
                             "state_cap": state_cap,
                             "device_payload": device_payload}
        key_codec = KeyCodec(
            [left.schema[i].data_type for i in left_keys])
        # device_payload=False forces the host-gather emit path (the
        # bit-identity oracle's off arm; also exposed for debugging)
        self.sides = (
            _JoinSide(left.schema, left_keys, left_table.pk_indices,
                      left_table, key_codec, mesh=mesh,
                      shard_opts=shard_opts,
                      device_payload=device_payload),
            _JoinSide(right.schema, right_keys, right_table.pk_indices,
                      right_table, key_codec, mesh=mesh,
                      shard_opts=shard_opts,
                      device_payload=device_payload),
        )
        for i, side in enumerate(self.sides):
            side.track_degrees = i in join_type.tracked_sides
        n_left = len(left.schema)
        names = list(output_names) if output_names else None
        subj = join_type.subject
        if subj is not None:
            # semi/anti: output is the subject side's schema alone
            src = (left if subj == 0 else right).schema
            fields = [Field(names[i] if names else f.name, f.data_type)
                      for i, f in enumerate(src)]
            pk = list((left_table if subj == 0
                       else right_table).pk_indices)
        else:
            fields = []
            k = 0
            for sch in (left.schema, right.schema):
                for f in sch:
                    fields.append(Field(names[k] if names else f.name,
                                        f.data_type))
                    k += 1
            # output pk: both sides' pks (joined row identity)
            pk = list(left_table.pk_indices) + \
                [n_left + i for i in right_table.pk_indices]
        out_schema = Schema(fields)
        super().__init__(ExecutorInfo(
            out_schema, pk,
            f"HashJoinExecutor({join_type.value}, actor={actor_id})"))
        self.n_left = n_left
        # join-key watermarks (hash_join.rs:860-945): per side, latest
        # watermark per key POSITION; the forwarded/cleaning watermark
        # is the min across sides, monotone
        self._side_wm: List[Dict[int, int]] = [{}, {}]
        self._combined_wm: Dict[int, int] = {}
        self._expired_wm: Dict[int, int] = {}
        # message sequence (sequence-versioned device state; see
        # ops/hash_join.py) + per-epoch in-flight probe list
        self._seq = 1
        self._pending: List[tuple] = []
        # the epoch is the only dispatch shape, on both kernel shapes:
        # chunks buffer host-side and the whole epoch ships as 2
        # uploads + 2 dispatches per side at the barrier (ops/
        # hash_join.py AUX_*; parallel/join.py epoch twins)
        # the planner's mark: an aggregate was planned over this join,
        # so the chunk build below is a leg of the join -> aggregate
        # hand-off
        self.feeds_agg = False
        # the join's name in the books (rows in by side and op, rows
        # out, its condition's rows): its left side's state table
        self._books_table = f"t{left_table.table_id}"
        # an inner join's own condition (a boolean expression over
        # the output schema; hash_join.rs `cond`), the output columns
        # it reads, and the pairs it dropped this epoch
        self.condition: Optional[Expression] = None
        self._condition_cols: Tuple[int, ...] = ()
        self._condition_dropped = 0
        if condition is not None:
            self.adopt_condition(condition)
        self._tier = None
        self._tier_parts: Tuple = (None, None)
        self._tier_seq = 0
        if state_cap is not None:
            # cold-state tier prerequisites: the single-chip kernel
            # (reload hooks its epoch dispatch), a non-semi/anti
            # join (semi/anti emission depends on degree TRANSITIONS
            # whose history an eviction would lose; outer degrees are
            # pure functions of both sides' durable state and recompute
            # on reload — see _reload_cold), and key-prefixed
            # state-table pks (reload prefix-scans by key)
            if join_type.is_semi_or_anti or mesh is not None:
                raise ValueError(
                    "state_cap needs an INNER or OUTER join on the "
                    "single-chip kernel (semi/anti "
                    "degree-transition history cannot be evicted)")
            for side in self.sides:
                k = len(side.key_indices)
                if side.table.pk_indices[:k] != side.key_indices:
                    raise ValueError(
                        "state_cap needs state-table pks prefixed by "
                        "the join keys (reload prefix-scans by key): "
                        f"pk={side.table.pk_indices} "
                        f"keys={side.key_indices}")
                side.state_cap = int(state_cap)
            # tier participation (state/tier.py): one participant per
            # side; the sweep at this executor's checkpoint barrier
            # picks the least-recently-touched keys. Registration is
            # DEFERRED to execute() — plan-only executors (EXPLAIN,
            # distributed CREATEs that serialize to IR and discard)
            # must leave no ghost entries in the global registry.
            from risingwave_tpu.state import tier as _tier_mod
            self._tier = _tier_mod.GLOBAL
            self._tier_cap = int(state_cap)
        self._epoch_buf: tuple = ([], [])
        self._epoch_rows = [0, 0]
        # host-state accounting (memory_manager.rs analog): weakref so
        # a dropped executor unregisters itself on the next tick
        import weakref

        from risingwave_tpu.utils import memory as _mem
        name = f"{self.identity}#{id(self)}"
        ref = weakref.ref(self)

        def _nbytes() -> int:
            s = ref()
            if s is None:
                _mem.GLOBAL.unregister(name)
                return 0
            return sum(sd.nbytes() for sd in s.sides) + \
                s.sides[0].key_codec.interner_nbytes()

        _mem.GLOBAL.register(name, _nbytes)

    # -- emission ---------------------------------------------------------
    @staticmethod
    def _chunk_col(f: Field, c: Column, idx: np.ndarray,
                   cap: int) -> Column:
        """One column gathered from incoming-chunk rows `idx`."""
        t = len(idx)
        src = np.asarray(c.values)[idx]
        vals = np.zeros(cap, dtype=src.dtype) if src.dtype != object \
            else np.empty(cap, dtype=object)
        vals[:t] = src
        ok = np.ones(cap, dtype=bool)
        if c.validity is not None:
            ok[:t] = np.asarray(c.validity)[idx]
        return Column(f.data_type, vals, None if ok.all() else ok)

    @classmethod
    def _chunk_cols(cls, schema: Schema, chunk: StreamChunk,
                    idx: np.ndarray, cap: int) -> List[Column]:
        """Columns gathered from incoming-chunk rows `idx`."""
        return [cls._chunk_col(f, c, idx, cap)
                for f, c in zip(schema, chunk.columns)]

    @staticmethod
    def _null_cols(schema: Schema, cap: int) -> List[Column]:
        out: List[Column] = []
        for f in schema:
            dt = f.data_type
            vals = np.zeros(cap, dtype=dt.np_dtype) if dt.is_device \
                else np.empty(cap, dtype=object)
            out.append(Column(dt, vals, np.zeros(cap, dtype=bool)))
        return out

    def _compose(self, side_idx: int, my_cols: List[Column],
                 other_cols: List[Column], ops: np.ndarray,
                 t: int, cap: int) -> StreamChunk:
        columns = my_cols + other_cols if side_idx == 0 \
            else other_cols + my_cols
        out_vis = np.zeros(cap, dtype=bool)
        out_vis[:t] = True
        full_ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
        full_ops[:t] = ops[:t]
        return StreamChunk(self.schema, columns, out_vis, full_ops)

    @staticmethod
    def _ops_of(chunk: StreamChunk, idx: np.ndarray) -> np.ndarray:
        """Degrade update pairs (split halves) to Delete/Insert — the
        reference degrades split pairs the same way."""
        in_ops = np.asarray(chunk.ops)[idx]
        is_ins = (in_ops == int(Op.INSERT)) | \
            (in_ops == int(Op.UPDATE_INSERT))
        return np.where(is_ins, int(Op.INSERT),
                        int(Op.DELETE)).astype(np.int8)

    # -- fragment fusion (frontend/opt/fusion.py mutates a copy) ----------
    def drain_stage_metrics(self):
        """Per-logical-stage attribution of the fused input runs for
        the monitor (side-tagged — both sides may absorb a same-kind
        stage)."""
        out = []
        for tag, side in (("L", self.sides[0]), ("R", self.sides[1])):
            if side.fused_input is not None:
                out.extend(
                    (f"{tag}:{ident}", rows, chunks)
                    for ident, rows, chunks
                    in side.fused_input.drain_stage_metrics())
        return out

    def adopt_fused_input(self, side_idx: int, fs, base) -> None:
        """Absorb a filter/project/row_id_gen run on one input side:
        ``base`` becomes the direct input and ``fs`` (whose out_schema
        must equal the side schema this join was planned against)
        runs as a numpy composed pass for host bookkeeping plus a
        traced prelude inside the side's epoch dispatches. Only valid
        on the single-chip epoch path before any data flows."""
        from risingwave_tpu.frontend.opt.fusion import (
            join_side_fusable_reason,
        )
        r = join_side_fusable_reason(self, side_idx)
        if r is not None:
            raise ValueError(f"join side is not fusion-eligible: {r}")
        side = self.sides[side_idx]
        got = [f.data_type for f in fs.out_schema]
        want = [f.data_type for f in side.schema]
        if got != want:
            raise ValueError(
                f"fused input run emits {got}, join side planned on "
                f"{want}")
        side.fused_input = fs
        if side_idx == 0:
            self.left_in = base
        else:
            self.right_in = base

    def _run_fused_input(self, side_idx: int, chunk: StreamChunk):
        """The host half of a fused input side: augment (runtime
        columns), run the composed chain ONCE on numpy (the same
        implementation the device prelude traces — no drifting twin),
        reattach host passthrough columns, and encode the raw matrix
        the epoch dispatches consume. Returns (post_chunk, raw) or
        None when every row filtered out (empty-suppression
        contract)."""
        from risingwave_tpu.ops.fused import encode_raw_chunk
        fs = self.sides[side_idx].fused_input
        aug = fs.augment(chunk)
        host_same = fs.host_noop_eq(aug)
        out_cols, vis2, ops2, stage_rows = fs.chain_body(
            list(aug.columns), np.asarray(aug.visibility),
            np.asarray(aug.ops), np, host_same=host_same)
        fs.note_rows_in(chunk.cardinality())
        fs.note_stage_rows(np.asarray(stage_rows), 1)
        if not vis2.any():
            return None
        cols: List[Column] = []
        for j, f in enumerate(fs.out_schema):
            host_src = fs.host_out.get(j)
            if host_src is not None:
                src = aug.columns[host_src]
                cols.append(Column(f.data_type, src.values,
                                   src.validity))
            else:
                cols.append(out_cols[j])
        post = StreamChunk(fs.out_schema, cols, vis2, ops2)
        return post, encode_raw_chunk(aug, fs.ref_cols)

    def adopt_condition(self, predicate: Expression) -> None:
        """Take `predicate` (boolean, over the output schema) as a
        conjunct of this join's own condition: every matched pair goes
        through it before it is emitted (`_pairs_chunk`). An inner
        join only: an outer, semi or anti join's condition decides
        which rows are NULL-padded or counted as matched, which
        masking the pairs cannot do. The pushdown rule calls this
        on a copy (frontend/opt/rules.py push_filters)."""
        if self.join_type is not JoinType.INNER:
            raise ValueError(
                f"a {self.join_type.value} join evaluates no condition: "
                "only an INNER join's is a filter of its pairs")
        if predicate.return_type != DataType.BOOLEAN:
            raise ValueError("a join's condition is a boolean expression")
        self.condition = predicate if self.condition is None \
            else BinaryOp("and", self.condition, predicate)
        self._condition_cols = tuple(sorted(expr_refs(self.condition)))

    @property
    def plan_note(self) -> Optional[str]:
        """EXPLAIN's note on the join's line."""
        return None if self.condition is None \
            else f"condition: {self.condition!r}"

    @staged("join.pairs")
    def _pairs_chunk(self, side_idx: int, chunk: StreamChunk,
                     probe_idx: np.ndarray, refs: np.ndarray,
                     pay: Optional[np.ndarray] = None
                     ) -> Optional[StreamChunk]:
        """The matched pairs as one chunk: this side's columns from
        the incoming chunk, the other's from its payload lanes or its
        arena (`_JoinSide.matched_col`). Where the join has a condition
        the columns it reads are built first and the pairs go through
        THE filter transform (`FilterExecutor.apply_predicate`) on
        them: what a FilterExecutor directly above the join emitted,
        visibility, ops and the suppressed empty chunk (None) alike;
        the other columns are built only for a chunk that keeps a
        row."""
        t = len(probe_idx)
        cap = next_pow2(t)
        me = self.sides[side_idx]
        other = self.sides[1 - side_idx]

        def column(j: int) -> Column:
            """Output column `j`: the left side's come first."""
            side, i = (0, j) if j < self.n_left else (1, j - self.n_left)
            if side == side_idx:
                return self._chunk_col(me.schema[i], chunk.columns[i],
                                       probe_idx, cap)
            return other.matched_col(i, pay, refs, cap)

        vis = np.zeros(cap, dtype=bool)
        vis[:t] = True
        ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
        ops[:t] = self._ops_of(chunk, probe_idx)
        cols: Dict[int, Column] = {
            j: column(j) for j in self._condition_cols}
        if self.condition is not None:
            with join_condition_span(self._books_table):
                # columns the condition does not read are never looked
                # at: one blank array stands in for all of them
                blank = np.zeros(cap, dtype=bool)
                kept = FilterExecutor.apply_predicate(StreamChunk(
                    self.schema,
                    [cols[j] if j in cols else Column(f.data_type, blank)
                     for j, f in enumerate(self.schema)], vis, ops),
                    self.condition)
                vis, ops = kept.visibility, kept.ops
                n_kept = int(np.count_nonzero(vis))
                note_join_condition(self._books_table, t, n_kept)
            self._condition_dropped += t - n_kept
            if not n_kept:
                return None
        return StreamChunk(
            self.schema,
            [cols[j] if j in cols else column(j)
             for j in range(len(self.schema))], vis, ops)

    def _note_outer(self, event: str, rows: int) -> None:
        """File `rows` of the outer half's books under this join
        (`join_outer.t<join>.<event>`)."""
        if rows:
            _METRICS.join_outer_rows.inc(
                float(rows), table=self._books_table, event=event)

    @staged("join.pad")
    def _padded_from_chunk(self, side_idx: int, chunk: StreamChunk,
                           idx: np.ndarray) -> StreamChunk:
        """(row, NULLs) for unmatched rows of an outer incoming side."""
        t = len(idx)
        cap = next_pow2(t)
        me = self.sides[side_idx]
        other = self.sides[1 - side_idx]
        ops = self._ops_of(chunk, idx)
        inserts = int(np.count_nonzero(ops == int(Op.INSERT)))
        self._note_outer("padded_insert", inserts)
        self._note_outer("padded_delete", t - inserts)
        return self._compose(
            side_idx, self._chunk_cols(me.schema, chunk, idx, cap),
            self._null_cols(other.schema, cap), ops, t, cap)

    @staged("join.pad")
    def _padded_from_arena(self, side_idx: int, refs: np.ndarray,
                           op: Op) -> StreamChunk:
        """(stored row, NULLs) for degree transitions of an outer side."""
        t = len(refs)
        cap = next_pow2(t)
        me = self.sides[side_idx]
        other = self.sides[1 - side_idx]
        ops = np.full(cap, int(op), dtype=np.int8)
        self._note_outer("padded_insert" if op == Op.INSERT
                         else "padded_delete", t)
        return self._compose(
            side_idx, me.arena.gather(refs, cap),
            self._null_cols(other.schema, cap), ops, t, cap)

    @staged("join.pairs")
    def _subject_from_chunk(self, chunk: StreamChunk,
                            idx: np.ndarray) -> StreamChunk:
        t = len(idx)
        cap = next_pow2(t)
        cols = self._chunk_cols(
            self.sides[self.join_type.subject].schema, chunk, idx, cap)
        vis = np.zeros(cap, dtype=bool)
        vis[:t] = True
        ops = np.full(cap, int(Op.INSERT), dtype=np.int8)
        ops[:t] = self._ops_of(chunk, idx)
        return StreamChunk(self.schema, cols, vis, ops)

    @staged("join.pairs")
    def _subject_from_arena(self, refs: np.ndarray, op: Op
                            ) -> StreamChunk:
        subj = self.join_type.subject
        t = len(refs)
        cap = next_pow2(t)
        cols = self.sides[subj].arena.gather(refs, cap)
        vis = np.zeros(cap, dtype=bool)
        vis[:t] = True
        ops = np.full(cap, int(op), dtype=np.int8)
        return StreamChunk(self.schema, cols, vis, ops)

    @staged("join.ingest")
    def _ingest_chunk(self, side_idx: int, chunk: StreamChunk,
                      key_lanes, nonnull: np.ndarray,
                      raw: Optional[np.ndarray] = None) -> None:
        """Ingest side: host bookkeeping per chunk; device work
        buffers for the ONE epoch dispatch at the barrier (sequence
        versioning makes the batched probes exact per-row)."""
        me = self.sides[side_idx]
        seq = self._seq
        self._seq += 1
        vis_ops = np.asarray(chunk.ops)[np.asarray(chunk.visibility)]
        for op, n_op in enumerate(np.bincount(vis_ops, minlength=5)):
            if n_op:
                _METRICS.join_input_rows.inc(
                    float(n_op), table=self._books_table,
                    side=("left", "right")[side_idx],
                    op=Op(op).name.lower())
        probe_vis = np.asarray(chunk.visibility) & nonnull
        # heavy-hitter sketch per join input ("/0" build, "/1"
        # probe): unfused sides already built the lanes for the
        # kernel — the sketch adds one hash+unique pass; a fused
        # input side derives lanes in-kernel, so the sketch builds
        # its own host copy from the post-filter chunk
        sk_lanes = key_lanes if key_lanes is not None \
            else me.key_codec.build(chunk, me.key_indices)
        _hotkeys.HOTKEYS.observe(f"{self.identity}/{side_idx}",
                                 sk_lanes, probe_vis,
                                 me.key_codec)
        if self._tier is not None and key_lanes is not None:
            rows = np.flatnonzero(probe_vis)
            if len(rows):
                uniq = list(map(tuple, np.unique(
                    np.asarray(key_lanes)[rows], axis=0).tolist()))
                # stored here → full touch; the probe only REFRESHES
                # the other side's recency (insert=False: a probed key
                # the other side never stored must not mint a phantom)
                self._tier.touch(self._tier_parts[side_idx], uniq,
                                 self._tier_seq)
                self._tier.touch(self._tier_parts[1 - side_idx], uniq,
                                 self._tier_seq, insert=False)
        (ins_idx, ins_refs, full_refs, ins_mask, del_refs,
         del_mask) = me.apply_chunk_host(chunk, nonnull)
        from risingwave_tpu.ops.hash_join import (
            FLAG_DEL, FLAG_INS, FLAG_NEG, FLAG_PROBE,
        )
        n = chunk.capacity
        # dense-prefix slice (ISSUE 12): compacted chunks stamp their
        # visible-row count — buffering only the dense prefix keeps
        # chunk PADDING out of the epoch's concatenated row space (a
        # 62%-full hop-expanded chunk was inflating every routed epoch
        # shape by ~1.6×); rows past the prefix are invisible and
        # contribute nothing but routed zeros
        dn = chunk.dense_rows if chunk.dense_rows is not None else n
        ops = np.asarray(chunk.ops)
        neg = (ops != int(Op.INSERT)) & (ops != int(Op.UPDATE_INSERT))
        aux = np.zeros((dn, 4), dtype=np.int32)
        aux[:, 0] = full_refs[:dn]
        aux[:, 1] = del_refs[:dn]
        aux[:, 2] = (probe_vis[:dn] * FLAG_PROBE
                     + ins_mask[:dn] * FLAG_INS
                     + del_mask[:dn] * FLAG_DEL + neg[:dn] * FLAG_NEG)
        aux[:, 3] = seq
        off = self._epoch_rows[side_idx]
        self._pending.append(
            (side_idx, chunk, nonnull, ins_idx, ins_refs, off, dn))
        if raw is not None:
            # fused input side: the RAW int64 matrix is the upload —
            # the side's prelude rebuilds [key | payload] lanes inside
            # the epoch dispatches
            up = raw[:dn]
        elif me.pay_indices:
            # [key lanes | payload lanes]: ONE upload matrix per side
            # per epoch carries both — the apply scatter writes the
            # payload rows where it links the chains
            up = np.concatenate(
                [np.asarray(key_lanes)[:dn],
                 me.payload_rows(chunk)[:dn]], axis=1)
        else:
            up = np.asarray(key_lanes)[:dn]
        owners = None
        if me._mesh is not None:
            # per-row owner shards for the skew-exact routing bucket
            # (parallel/join.stage_epoch): the fused path derives key
            # lanes from the POST chunk here — the raw matrix only
            # carries them in-trace
            lanes_o = np.asarray(key_lanes) if key_lanes is not None \
                else me.key_codec.build(chunk, me.key_indices)
            owners = me.kernel.owners_of(lanes_o[:dn])
        self._epoch_buf[side_idx].append(
            (up, aux, int(ins_refs.max()) if len(ins_refs) else -1,
             owners))
        self._epoch_rows[side_idx] = off + dn

    def _dispatch_epoch(self) -> Dict[int, tuple]:
        """Ship each side's buffered epoch as 2 uploads + 1 apply + 1
        probe dispatch, then collect both probes (overlapped DMAs).
        Returns {side: (deg|None, probe_idx, refs, pay, old_deg)} in
        the CONCATENATED row space; _emit_pending slices per chunk by
        offset."""
        self._reload_cold()
        devs: Dict[int, tuple] = {}
        for s in (0, 1):
            buf = self._epoch_buf[s]
            if not buf:
                continue
            total = self._epoch_rows[s]
            # a side fed by an aggregate's few changed groups stages a
            # handful of rows, 8 in one epoch and 16 in the next: under
            # the floor they are one shape, not a program per count
            cap = next_pow2(total, floor=_EPOCH_ROWS_FLOOR)
            w = buf[0][0].shape[1]
            # fused input sides buffer int64 RAW matrices; direct
            # sides buffer int32 [key | payload] lanes
            up = np.zeros((cap, w), dtype=buf[0][0].dtype)
            aux = np.zeros((cap, 4), dtype=np.int32)
            owners = None if buf[0][3] is None else \
                np.zeros(cap, dtype=np.int64)
            at = 0
            max_ref = -1
            for lan, a, mr, ow in buf:
                up[at:at + lan.shape[0]] = lan
                aux[at:at + a.shape[0]] = a
                if owners is not None:
                    owners[at:at + lan.shape[0]] = ow
                at += lan.shape[0]
                max_ref = max(max_ref, mr)
            # staging is the kernel's job: the sharded kernel pads to
            # the mesh width, runs its growth guards, computes the
            # skew-exact routing bucket and row-shards the upload; a
            # single chip device_puts (bucket None)
            up_dev, aux_dev, bucket = self.sides[s].kernel.stage_epoch(
                up, aux, total, max_ref, owners=owners)
            devs[s] = (up_dev, aux_dev, total, max_ref, bucket)

        def _prelude_kw(s: int) -> dict:
            """The UPLOADING side's fused-input prelude (if any),
            for both its apply and its probe of the other side. The
            key is STRUCTURAL (FusedStages.trace_key + the lane
            positions): equal runs trace equal programs, so jit caches
            keyed by it survive session restarts and shared shapes."""
            side = self.sides[s]
            if side.fused_input is None:
                return {}
            if side._prelude_cache_key is None:
                side._prelude_cache_key = (
                    f"{side.fused_input.trace_key()}"
                    f"|k={side.key_indices}|p={side.pay_indices}")
            return {"prelude": side.prelude,
                    "prelude_key": side._prelude_cache_key}

        # both applies land before either probe dispatches: a probe at
        # seq s must see the other side's same-epoch rows with seq < s
        for s, (ld, ad, total, max_ref, bkt) in devs.items():
            # apply + probe below = 2 device dispatches per side/epoch,
            # each carrying the epoch's rows (observe twice so the
            # histogram's count matches the dispatch counter and
            # sum/count stays the true per-dispatch density). Sharded
            # kernels count at their own jit sites (kernel="sharded_
            # join") — counting here too would double the totals.
            if self.sides[s]._mesh is None:
                _METRICS.device_dispatch.inc(2, executor=self.identity)
                for _ in range(2):
                    _METRICS.rows_per_dispatch.observe(
                        float(total), executor=self.identity)
            with dispatch_span(self.identity, float(total),
                               site="epoch_apply", side=s):
                self.sides[s].kernel.apply_epoch(ld, ad, total,
                                                 max_ref, bucket=bkt,
                                                 **_prelude_kw(s))
        with_deg = self.join_type != JoinType.INNER
        if not with_deg:
            # inner (the hot path): both probes dispatch before either
            # collects, so the two d2h DMAs overlap
            probes = {s: self.sides[1 - s].kernel.probe_epoch(
                ld, ad, False, sink=self.sides[s].kernel,
                bucket=bkt, **_prelude_kw(s))
                for s, (ld, ad, _t, _m, bkt) in devs.items()}
            return {s: p.collect() for s, p in probes.items()}
        # degree-tracked joins: each probe updates BOTH sides' device
        # degree arrays (transitions on the probed side, inserted-row
        # inits on the probing side), and a pair-buffer overflow
        # truncates the first dispatch's adds — so probe 2 must only
        # dispatch after probe 1's collect has installed its final
        # arrays. One sync point per epoch, tracked joins only.
        out: Dict[int, tuple] = {}
        for s, (ld, ad, _t, _m, bkt) in devs.items():
            probed = self.sides[1 - s]
            pending = probed.kernel.probe_epoch(
                ld, ad, True, sink=self.sides[s].kernel,
                bucket=bkt, **_prelude_kw(s))
            out[s] = pending.collect()
            if pending.redispatches:
                _METRICS.join_degree_redispatches.inc(
                    float(pending.redispatches),
                    kernel=f"join.t{probed.table.table_id}")
        return out

    def _tier_register(self) -> None:
        """Register both sides with the global tier at execute() start
        — only executors that actually RUN appear in the registry."""
        import weakref
        sref = weakref.ref(self)
        parts = []
        for i in (0, 1):
            def _evict_cb(keys, _i=i):
                s = sref()
                if s is None:
                    return 0
                n_keys, n_rows = s.sides[_i].evict_keys(keys)
                if n_rows:
                    _METRICS.join_rows_evicted.inc(
                        n_rows, executor=s.identity)
                return n_keys

            def _nbytes_cb(_i=i):
                s = sref()
                return 0 if s is None else s.sides[_i].nbytes()

            parts.append(self._tier.register(
                f"{self.identity}/side{i}#{id(self)}", _evict_cb,
                cap=self._tier_cap, nbytes=_nbytes_cb))
        self._tier_parts = tuple(parts)

    def _reload_cold(self) -> None:
        """Reload evicted keys this epoch's probes will need, BEFORE
        the epoch's applies/probes dispatch (managed_state/join reload-
        on-miss, batched per barrier). The reload insert applies at
        seq 0 so every probe of the epoch sees the reloaded rows.

        Tracked (outer) joins reload a needed key on BOTH sides: the
        reloaded rows' degrees recompute by probing the opposite
        kernel, and a cold twin there would undercount. The recompute
        runs after both sides' reload applies, against pre-epoch state
        — this epoch's own chunks then layer their degree deltas on
        top in message order (_emit_one step 3), exactly as if the
        rows had never left."""
        from risingwave_tpu.ops.hash_join import FLAG_PROBE
        kw = LANES_PER_KEY * len(self.sides[0].key_indices)
        need: List[Dict[tuple, tuple]] = [{}, {}]
        for s in (0, 1):
            other = self.sides[1 - s]
            if not other.cold_keys or not self._epoch_buf[s]:
                continue
            for lan, aux, _mr, _ow in self._epoch_buf[s]:
                rows = np.flatnonzero(aux[:, 2] & FLAG_PROBE)
                # the buffered upload matrix is [key lanes | payload
                # lanes]: cold-key lookups read the key slice only
                for t in map(tuple, lan[rows, :kw].tolist()):
                    v = other.cold_keys.get(t)
                    if v is not None:
                        need[1 - s][t] = v
        if self.join_type.tracked_sides:
            for s in (0, 1):
                twin = self.sides[s]
                if not twin.cold_keys:
                    continue
                for t in need[1 - s]:
                    v = twin.cold_keys.get(t)
                    if v is not None:
                        need[s][t] = v
        reloaded: List[Optional[tuple]] = [None, None]
        for s in (0, 1):
            if not need[s]:
                continue
            loaded = self.sides[s].reload_keys(need[s])
            if loaded is not None:
                up, aux2, n, max_ref = loaded
                from risingwave_tpu.utils import jaxtools as _jt
                self.sides[s].kernel.apply_epoch(
                    _jt.upload(up, kernel="hash_join"),
                    _jt.upload(aux2, kernel="hash_join"), n,
                    max_ref)
                reloaded[s] = (up, aux2, n)
                if self._tier is not None:
                    part = self._tier_parts[s]
                    uniq = np.unique(up[:n, :kw], axis=0)
                    self._tier.touch(part,
                                     map(tuple, uniq.tolist()),
                                     self._tier_seq)
                    # units contract: reload counters are in KEYS
                    self._tier.note_reload(part, len(uniq))
        for t_side in self.join_type.tracked_sides:
            rl = reloaded[t_side]
            if rl is None:
                continue
            up, aux2, n = rl
            refs = aux2[:n, 0].astype(np.int64)
            deg, _pi, _refs = self.sides[1 - t_side].kernel.probe(
                up[:n, :kw], np.ones(n, dtype=bool))
            side = self.sides[t_side]
            if side.dev_degrees:
                # reloaded rows' degrees recompute by one batch probe
                # and scatter straight into the device degree array
                side.kernel.write_degrees(
                    refs.astype(np.int32), deg[:n])
            else:
                side.ensure_degrees(int(refs.max()))
                side.degrees[refs] = deg[:n]

    def _emit_pending(self) -> List[StreamChunk]:
        """Barrier sweep: collect the epoch's probes and run emission
        in message order. Degree bookkeeping happens here, in the same
        order the chunks were applied — on the epoch path it replays
        from the packed matrix's old-degree column (the device array
        is the store; see _emit_one)."""
        results = self._dispatch_epoch() \
            if self._epoch_buf[0] or self._epoch_buf[1] else {}
        # the hand-off's first leg: from the probe result on the host
        # to the chunks the aggregate ingests
        with join_to_agg_handoff(self.feeds_agg):
            outs: List[StreamChunk] = []
            # per-epoch replay of stored-row degrees, per side: a value
            # array + written mask indexed by ref (ISSUE 12 — the dict it
            # replaces cost a python get/set per matched pair), seeded
            # lazily from the matrix old column, written through by
            # inserted-row inits and per-chunk transition deltas
            self._deg_replay = [None, None]
            for (side_idx, chunk, nonnull, ins_idx, ins_refs, off,
                 dn) in self._pending:
                deg = pay = old = None
                # a pending chunk's rows are in its side's buffer
                d_s, p_s, r_s, pay_s, old_s = results[side_idx]
                # the buffered epoch carries only this chunk's dense
                # prefix (dn rows at offset off); degrees re-pad to
                # the chunk's capacity for the chunk-relative masks
                with LEDGER.phase("host_emit", stage="join.split"):
                    lo = np.searchsorted(p_s, off)
                    hi = np.searchsorted(p_s, off + dn)
                    probe_idx = (p_s[lo:hi] - off).astype(np.int32)
                    refs = r_s[lo:hi]
                    if pay_s is not None:
                        pay = pay_s[lo:hi]
                    if old_s is not None:
                        old = old_s[lo:hi].astype(np.int64)
                    if d_s is not None:
                        deg = np.zeros(chunk.capacity, dtype=np.int64)
                        deg[:dn] = d_s[off:off + dn]
                outs.extend(self._emit_one(side_idx, chunk, nonnull, deg,
                                           probe_idx, refs, ins_idx,
                                           ins_refs, pay, old))
            self._pending.clear()
            self._epoch_buf = ([], [])
            self._epoch_rows = [0, 0]
            self._deg_replay = [None, None]
        handed = float(sum(c.cardinality() for c in outs))
        # the pairs matched on the keys, before the condition
        n_out = handed + self._condition_dropped
        self._condition_dropped = 0
        if n_out:
            _METRICS.join_output_rows.inc(n_out, table=self._books_table)
        if self.feeds_agg:
            _METRICS.join_to_agg_rows.inc(
                handed, view=_costs.current_mv() or "")
        return outs

    def _note_batch_books(self) -> None:
        """Each side's per-epoch books (hotkeys.note_batch_books). This
        epoch's apply ran before the probe whose result was just
        collected, so its counter has landed."""
        for i, side in enumerate(self.sides):
            _hotkeys.note_batch_books(
                f"join.t{side.table.table_id}", f"{self.identity}/{i}",
                getattr(side._kernel, "take_probe_rounds", None))
            kernel = side._kernel
            if hasattr(kernel, "take_probe_books"):
                # from the header of the probe matrix just collected:
                # no read of its own
                label = f"join.t{side.table.table_id}"
                steps, candidates, pairs = kernel.take_probe_books()
                _METRICS.join_probe_chain.set(
                    float(kernel.take_longest_chain()), kernel=label)
                _METRICS.join_probe_walk_steps.set(float(steps),
                                                   kernel=label)
                _METRICS.join_probe_candidates.inc(float(candidates),
                                                   kernel=label)
                _METRICS.join_probe_pairs.inc(float(pairs), kernel=label)

    def _deg_replay_arrays(self, side_idx: int, max_ref: int):
        """(values, written) replay arrays for `side_idx`, grown to
        cover `max_ref` — the vectorized stand-in for the old
        (side, ref)→degree dict."""
        pair = self._deg_replay[side_idx]
        need = max_ref + 1
        if pair is None:
            cap = max(next_pow2(need), 1024)
            pair = (np.zeros(cap, dtype=np.int64),
                    np.zeros(cap, dtype=bool))
            self._deg_replay[side_idx] = pair
        elif len(pair[0]) < need:
            cap = next_pow2(need)
            vals = np.zeros(cap, dtype=np.int64)
            wr = np.zeros(cap, dtype=bool)
            vals[:len(pair[0])] = pair[0]
            wr[:len(pair[1])] = pair[1]
            pair = (vals, wr)
            self._deg_replay[side_idx] = pair
        return pair

    def _emit_one(self, side_idx: int, chunk: StreamChunk,
                  nonnull: np.ndarray, deg: Optional[np.ndarray],
                  probe_idx: np.ndarray, refs: np.ndarray,
                  ins_idx: np.ndarray, ins_refs: np.ndarray,
                  pay: Optional[np.ndarray] = None,
                  old: Optional[np.ndarray] = None
                  ) -> List[StreamChunk]:
        """Emission per eq_join_oneside (hash_join.rs:990) generalized
        to the degree-transition rule: a stored outer row flips its
        NULL-padded emission exactly when its match degree crosses zero
        (net per-chunk delta vs the old degree — intermediate flips
        within one chunk cancel, leaving the same multiset).

        `deg` is None exactly when the join is INNER (the slim probe
        skips degrees; no emission rule below reads them). On the
        epoch path `pay` carries the matched refs' device-gathered
        payload lanes and `old` their pre-epoch degrees — the replay
        dict in _emit_pending reconstructs each chunk's old/new
        exactly as the host degrees array used to."""
        jt = self.join_type
        me = self.sides[side_idx]
        vis = np.asarray(chunk.visibility)
        outs: List[StreamChunk] = []
        # 1) matched pairs (all types except semi/anti)
        if jt.subject is None and len(probe_idx):
            pairs = self._pairs_chunk(side_idx, chunk, probe_idx,
                                      refs, pay)
            if pairs is not None:
                outs.append(pairs)
        # 2) incoming-row direct emissions
        if jt.outer_on(side_idx):
            # NULL-key rows of an outer side always emit padded
            unmatched = np.flatnonzero(vis & ((deg == 0) | ~nonnull))
            if len(unmatched):
                outs.append(self._padded_from_chunk(side_idx, chunk,
                                                    unmatched))
        elif jt.subject == side_idx:
            if jt.is_anti:
                sel = np.flatnonzero(vis & ((deg == 0) | ~nonnull))
            else:
                sel = np.flatnonzero(vis & nonnull & (deg > 0))
            if len(sel):
                outs.append(self._subject_from_chunk(chunk, sel))
        # 3) stored-row degree transitions on the other side
        if (1 - side_idx) in jt.tracked_sides and len(refs):
            outs.extend(self._degree_transitions(side_idx, chunk,
                                                 probe_idx, refs, old))
        # 4) initial degrees for the rows this chunk stored (the state
        # apply already ran at dispatch; deg is the probe-time count;
        # the device array already took the same init via the probe's
        # scatter-add — only the replay dict needs the values here)
        if side_idx in jt.tracked_sides and len(ins_idx):
            if me.dev_degrees:
                vals, wr = self._deg_replay_arrays(
                    side_idx, int(ins_refs.max()))
                vals[ins_refs] = deg[ins_idx]
                wr[ins_refs] = True
            else:
                # degrees array already grown by apply_chunk at dispatch
                me.degrees[ins_refs] = deg[ins_idx]
        return outs

    @staged("join.degrees")
    def _degree_transitions(self, side_idx: int, chunk: StreamChunk,
                            probe_idx: np.ndarray, refs: np.ndarray,
                            old: Optional[np.ndarray]
                            ) -> List[StreamChunk]:
        """The other side's stored rows whose match degree crossed zero
        under this chunk's pairs, as the chunks that flip their
        NULL-padded (outer) or subject (semi/anti) emission."""
        jt = self.join_type
        other = self.sides[1 - side_idx]
        outs: List[StreamChunk] = []
        sgn = np.where(self._ops_of(chunk, probe_idx)
                       == int(Op.INSERT), 1, -1)
        uref, inv = np.unique(refs, return_inverse=True)
        delta = np.zeros(len(uref), dtype=np.int64)
        np.add.at(delta, inv, sgn)
        if other.dev_degrees:
            # seed from the matrix's pre-epoch value on first
            # touch; later chunks read the replay arrays (exactly
            # the running value the host array used to hold) —
            # whole-column gathers/scatters, no per-pair python
            seed = np.zeros(len(uref), dtype=np.int64)
            if old is not None and len(old):
                first = np.zeros(len(uref), dtype=np.int64)
                # inv maps pair → uref slot; any pair of the ref
                # carries the same old value
                first[inv] = old
                seed = first
            vals, wr = self._deg_replay_arrays(
                1 - side_idx, int(uref.max()))
            cur = np.where(wr[uref], vals[uref], seed)
            new = cur + delta
            vals[uref] = new
            wr[uref] = True
            old_v = cur
        else:
            old_v = other.degrees[uref]
            new = old_v + delta
            other.degrees[uref] = new
        flip_on = uref[(old_v == 0) & (new > 0)]
        flip_off = uref[(old_v > 0) & (new == 0)]
        self._note_outer("flip_on", len(flip_on))
        self._note_outer("flip_off", len(flip_off))
        if jt.subject is not None:       # semi/anti subject = other
            on_op = Op.DELETE if jt.is_anti else Op.INSERT
            off_op = Op.INSERT if jt.is_anti else Op.DELETE
            if len(flip_on):
                outs.append(self._subject_from_arena(flip_on, on_op))
            if len(flip_off):
                outs.append(self._subject_from_arena(flip_off,
                                                     off_op))
        else:                            # outer side: padded flips
            if len(flip_on):
                outs.append(self._padded_from_arena(
                    1 - side_idx, flip_on, Op.DELETE))
            if len(flip_off):
                outs.append(self._padded_from_arena(
                    1 - side_idx, flip_off, Op.INSERT))
        return outs

    # -- watermarks -------------------------------------------------------
    def _on_watermark(self, side_idx: int, msg: "Watermark"):
        """Join-key watermarks combine as min across sides and forward
        for BOTH output columns of the key pair (they are equal by the
        join predicate); non-key watermarks are dropped (reference
        behavior). The combined watermark also drives state expiry at
        the next barrier."""
        me = self.sides[side_idx]
        if msg.col_idx not in me.key_indices:
            return
        pos = me.key_indices.index(msg.col_idx)
        self._side_wm[side_idx][pos] = msg.value
        other_wm = self._side_wm[1 - side_idx].get(pos)
        if other_wm is None:
            return
        combined = min(msg.value, other_wm)
        prev = self._combined_wm.get(pos)
        if prev is not None and combined <= prev:
            return
        self._combined_wm[pos] = combined
        subj = self.join_type.subject
        if subj is not None:
            # semi/anti output is the subject schema alone: one
            # watermark at the subject's key column index
            yield Watermark(self.sides[subj].key_indices[pos],
                            msg.data_type, combined)
        else:
            left_col = self.sides[0].key_indices[pos]
            right_col = self.n_left + self.sides[1].key_indices[pos]
            yield Watermark(left_col, msg.data_type, combined)
            yield Watermark(right_col, msg.data_type, combined)

    def _expire_state(self) -> None:
        """Expire both sides to the join-key watermarks that advanced
        in this epoch (the smaller of the two inputs', per key
        position), at the barrier that seals it."""
        for pos, wm in self._combined_wm.items():
            done = self._expired_wm.get(pos)
            if done is not None and wm <= done:
                continue
            dt = np.dtype(
                self.sides[0].key_types[pos].np_dtype)
            if not np.issubdtype(dt, np.integer):
                continue       # float keys: no order-safe expiry
            self._expire_to(pos, int(wm))

    @staged("join.expire")
    def _expire_to(self, pos: int, wm: int) -> None:
        for side in self.sides:
            expired = side.expire_below(pos, wm, seq=self._seq)
            _METRICS.join_expired_rows.inc(
                float(expired), table=f"t{side.table.table_id}")
            # the side now holds no row below the watermark on this
            # key column
            side.table.note_cleaned(wm)
        # bump: visibility is del_seq >= probe_seq, so the NEXT
        # chunk's sequence must exceed the tombstones' del_seq
        self._seq += 1
        self._expired_wm[pos] = wm

    # interner GC gate: skip below this many entries, and skip while
    # entries ≤ 2× live refs (GC cost is O(live), so only run it when
    # at least half the entries are provably dead)
    INTERNER_GC_MIN = 4096

    def _maybe_gc_interner(self) -> None:
        """Retire interner entries no stored row references (bounded-
        by-live-state contract, VERDICT r3 weak #6). Runs at barriers,
        gated so amortized cost stays O(churn)."""
        codec = self.sides[0].key_codec
        if not codec.interners:
            return
        total = codec.interner_entries()
        # COLD keys count as live in the gate: their values are pinned
        # below, so running GC while they dominate would scan O(cold)
        # every barrier to retire almost nothing
        live_refs = sum(len(s.pk_to_ref) + len(s.cold_keys)
                        for s in self.sides)
        if total < self.INTERNER_GC_MIN or \
                total <= 2 * live_refs * len(codec.interners):
            return
        for pos, it in codec.interners.items():
            vals: List[object] = []
            for side in self.sides:
                col = side.key_indices[pos]
                if not side.pk_to_ref:
                    continue
                refs = np.fromiter(side.pk_to_ref.values(),
                                   dtype=np.int64,
                                   count=len(side.pk_to_ref))
                ok = side.arena.valid[col][refs]
                vals.extend(side.arena.cols[col][refs][ok].tolist())
            for side in self.sides:
                # COLD keys pin their interned values: retiring an id
                # a cold marker holds would dangle it (a re-intern
                # under a new id misses reload; id reuse cross-matches
                # unrelated keys). vt is ordered by key position, like
                # the codec's interners.
                for vt in side.cold_keys.values():
                    if vt[pos] is not None:
                        vals.append(vt[pos])
            it.gc(vals)

    def _recover_degrees(self) -> None:
        """Degrees are a pure function of both sides' recovered state:
        ONE batch probe of the tracked side's keys against the other
        side's matcher (instead of persisting degree tables — see
        JoinType docstring)."""
        for t in self.join_type.tracked_sides:
            side = self.sides[t]
            other = self.sides[1 - t]
            if not side.pk_to_ref:
                continue
            refs = np.fromiter(side.pk_to_ref.values(), dtype=np.int64,
                               count=len(side.pk_to_ref))
            key_cols = [(side.arena.cols[i][refs],
                         side.arena.valid[i][refs])
                        for i in side.key_indices]
            lanes_ = side.key_codec.build_arrays(key_cols)
            nonnull = np.ones(len(refs), dtype=bool)
            for _vals, ok in key_cols:
                nonnull &= ok
            deg, _pi, _refs = other.kernel.probe(lanes_, nonnull)
            if side.dev_degrees:
                side.kernel.write_degrees(
                    refs.astype(np.int32), np.where(nonnull, deg, 0))
            else:
                side.ensure_degrees(int(refs.max()))
                side.degrees[refs] = np.where(nonnull, deg, 0)
        # NOTE: host-typed arena key cols may contain None for NULL keys
        # — build_arrays handles them (interner sanitization)

    # -- main loop --------------------------------------------------------
    async def execute(self) -> AsyncIterator[Message]:
        lit = self.left_in.execute()
        rit = self.right_in.execute()
        first_l = await lit.__anext__()
        first_r = await rit.__anext__()
        assert is_barrier(first_l) and is_barrier(first_r)
        assert first_l.epoch == first_r.epoch
        if self._tier is not None:
            self._tier_register()
        for i, side in enumerate(self.sides):
            side.table.init_epoch(first_l.epoch)
            side.recover()
            if self._tier_parts[i] is not None and side.pk_to_ref:
                # recovery rebuilds everything RESIDENT (cold markers
                # do not survive a crash); seed the tier clock so the
                # first checkpoint sweep re-applies the cap
                refs = np.fromiter(side.pk_to_ref.values(),
                                   dtype=np.int64,
                                   count=len(side.pk_to_ref))
                key_cols = [(side.arena.cols[j][refs],
                             side.arena.valid[j][refs])
                            for j in side.key_indices]
                lanes_all = side.key_codec.build_arrays(key_cols)
                self._tier.touch(
                    self._tier_parts[i],
                    map(tuple, np.unique(lanes_all, axis=0).tolist()),
                    self._tier_seq)
        self._recover_degrees()
        yield first_l
        try:
            async for tag, msg in barrier_align_2(lit, rit):
                if tag == "barrier":
                    # consume pending probes FIRST — expiry/compaction
                    # rebuild device state and would invalidate a
                    # re-dispatched probe's sequence view
                    for out in self._emit_pending():
                        yield out
                    self._note_batch_books()
                    self._expire_state()
                    self._tier_seq += 1
                    for i, side in enumerate(self.sides):
                        side.table.commit(msg.epoch)
                        swept = 0
                        part = self._tier_parts[i]
                        if side.expired_lanes:
                            if part is not None:
                                self._tier.forget(part,
                                                  side.expired_lanes)
                            side.expired_lanes = []
                        if part is not None:
                            if msg.kind.is_checkpoint:
                                # sweep at checkpoints only, after the
                                # commit above: evicted rows are durable
                                # and no probe is in flight (tier.py
                                # epoch-sequencing argument)
                                swept = self._tier.sweep(part,
                                                         self._tier_seq)
                        if not swept:
                            side.maybe_compact()
                    self._maybe_gc_interner()
                    # payload residency: device lane bytes vs host
                    # arena bytes, refreshed once per barrier (the
                    # auditable half of "ship refs, not rows")
                    dev_b = sum(
                        s.kernel.device_payload_bytes
                        for s in self.sides
                        if s._kernel is not None and s._mesh is None)
                    _METRICS.join_device_bytes.set(
                        dev_b, executor=self.identity)
                    _METRICS.join_host_bytes.set(
                        sum(s.host_arena_bytes() for s in self.sides),
                        executor=self.identity)
                    for side in self.sides:
                        if side.fused_input is not None:
                            # absorbed-runtime barrier work (row-id
                            # counters rebase to the epoch floor; join
                            # runs carry no watermark stages)
                            side.fused_input.on_barrier(msg)
                    if self._seq > (1 << 30):
                        # int32 sequence headroom: with no probes in
                        # flight, rebase every finite seq to 0 and restart
                        # (a wrap would blank every probe's visibility)
                        for side in self.sides:
                            side.kernel.rebase_seq()
                        self._seq = 1
                    yield msg
                elif tag in ("left", "right"):
                    i = 0 if tag == "left" else 1
                    side = self.sides[i]
                    if isinstance(msg, StreamChunk):
                        if side.fused_input is not None:
                            # fused input run: composed numpy pass for
                            # bookkeeping, raw matrix buffered for the
                            # in-dispatch prelude
                            r = self._run_fused_input(i, msg)
                            if r is None:
                                continue
                            post, raw = r
                            self._ingest_chunk(
                                i, post, None,
                                side.key_nonnull_mask(post), raw=raw)
                            continue
                        # one host→device upload of the key lanes (inside
                        # the kernel's fused dispatch), shared by the probe
                        # and this side's insert; the nonnull mask falls
                        # out of the same pass
                        lanes_np, nonnull = \
                            side.key_codec.build_with_mask(
                                msg, side.key_indices)
                        self._ingest_chunk(i, msg, lanes_np, nonnull)
                    elif isinstance(msg, Watermark):
                        # a fused input side receives watermarks in the
                        # RUN's input space — derive them through the
                        # absorbed projection stages first
                        derived = [msg] if side.fused_input is None \
                            else side.fused_input.derive_watermarks(msg)
                        wms: List = []
                        for one in derived:
                            wms.extend(self._on_watermark(i, one))
                        if wms:
                            # buffered join outputs must precede any
                            # watermark that could close windows over them
                            for out in self._emit_pending():
                                yield out
                        for wm in wms:
                            yield wm
        finally:
            if self._tier is not None:
                for p in self._tier_parts:
                    if p is not None:
                        self._tier.unregister(p)
