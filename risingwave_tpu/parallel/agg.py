"""Vnode-sharded grouped aggregation over a device mesh.

Reference parity: N parallel HashAggExecutor actors fed by a HASH
dispatcher (SURVEY §2.12 data parallelism; hash_agg.rs:67 +
dispatch.rs:582). TPU re-design: ONE SPMD program under ``shard_map`` —
each mesh shard owns a contiguous vnode range (VnodeMapping semantics)
and a private slice of the hash-table/accumulator arrays; rows hop to
their owner via the bucketized all_to_all (parallel/exchange.py) and are
then aggregated with the exact same kernel math as the single-chip path
(ops/hash_agg._update_call — one code path, two launch shapes).

State is the single-chip ``AggState`` with a leading [n_dev] axis,
sharded ``P('d')``. The barrier flush gathers per-shard dirty slots the
same way the single-chip kernel does; shards never share groups because
ownership is a function of the key hash.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from risingwave_tpu.common.chunk import next_pow2
from risingwave_tpu.common.hash import VNODE_COUNT
from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.ops import lanes
from risingwave_tpu.ops.hash_agg import (
    AggSpec, AggState, FlushResult, _call_slices, _rebuild_live,
    _update_call, advance_state, decode_flush_data, decode_outputs,
    dev_layout, encode_host_accs, gather_packed, make_agg_state,
    n_input_lanes, pack_chunk, packed_layout, retire_state,
)
from risingwave_tpu.parallel.exchange import (
    MESH_KERNELS, bucketize_by_owner, exchange, note_launch,
    note_routed, owners_host, received_by_shard, route_label,
    route_phase, skew_bucket, vnodes_from_lanes,
)
from risingwave_tpu.utils import jaxtools, spans
from risingwave_tpu.utils.ledger import LEDGER

AXIS = "d"

# Compiled SPMD programs shared ACROSS kernel instances (fresh
# sessions and twin MVs reuse traces instead of paying
# warmup compiles on the p99 tail — the join's _STEP_CACHE scheme).
# Keyed by (mesh device ids, program kind + statics, key_width,
# specs); jit shape-keys per state capacity internally. A CompileCache
# (stream/costs.py) so hits/misses bill the pulling MV.
from risingwave_tpu.stream.costs import CompileCache as _CompileCache

_PROG_CACHE: Dict[tuple, object] = _CompileCache("agg_prog")


def _note_dispatch(rows: float) -> None:
    """Real-SPMD-dispatch accounting at the jit sites (the sharded agg
    counts its own launches — one per backlog flush / barrier gather —
    so the executor layer must not also count per-chunk requests;
    exactly one site counts each dispatch and the registry totals
    stay launch-for-launch honest)."""
    from risingwave_tpu.utils.metrics import STREAMING
    STREAMING.device_dispatch.inc(1, kernel="sharded_agg")
    STREAMING.rows_per_dispatch.observe(float(rows),
                                        kernel="sharded_agg")


class _ShardedCounters:
    """Per-shard sync-free occupancy accounting + deferred overflow.

    The vector twin of jaxtools.PendingCounters: each SPMD apply returns
    int32[n_dev] insert counts and a bucket-overflow flag; both ride the
    async DMA and are folded in when they land, so the hot path never
    blocks on a read. Overflow raises when observed (barrier at the
    latest) — the barrier rolls back, same contract as the reference's
    error channel.
    """

    def __init__(self, n_dev: int):
        self._count = np.zeros(n_dev, dtype=np.int64)
        # ((ins[n_dev], overflow[, received[n_dev]]), rows)
        self._pending: List[tuple] = []
        self._rows = 0

    def push(self, ins, overflow, n_rows: int, received=None) -> None:
        """``received`` is the step's per-shard count of routed rows,
        where only the device knows it (a fused prelude filters and
        keys the rows in-trace): it lands with the insert counts and
        goes to the exchange's books then."""
        arrays = (ins, overflow) if received is None \
            else (ins, overflow, received)
        jaxtools.start_fetch(*arrays)
        self._pending.append((arrays, n_rows))
        self._rows += n_rows

    def _fold(self, arrays, n_rows: int) -> None:
        ins, overflow = arrays[:2]
        if bool(np.asarray(overflow).any()):
            raise RuntimeError(
                "bucket overflow: routed rows dropped — raise `bucket`")
        self._count += np.asarray(ins, dtype=np.int64)
        self._rows -= n_rows
        if len(arrays) > 2:
            got = np.asarray(arrays[2], dtype=np.int64)
            note_routed(int(got.sum()), got)

    def drain_ready(self) -> None:
        while self._pending and all(
                a.is_ready() for a in self._pending[0][0]):
            self._fold(*self._pending.pop(0))

    def drain_all(self) -> None:
        pending, self._pending = self._pending, []
        for arrays, n_rows in pending:
            jaxtools.fetch(*arrays)
            self._fold(arrays, n_rows)

    def bound(self) -> int:
        """Upper bound on the FULLEST shard's occupancy: every pending
        row could in principle route to one shard."""
        return int(self._count.max(initial=0)) + self._rows

    def worst_exact(self) -> int:
        return int(self._count.max(initial=0))

    def reset(self, per_shard_counts: np.ndarray) -> None:
        self._count = np.asarray(per_shard_counts, dtype=np.int64)
        self._pending = []
        self._rows = 0


def _pad_rows(a: np.ndarray, m: int) -> np.ndarray:
    """Zero/False-pad the leading axis to m rows (pad rows are routed
    nowhere: the caller pads `vis` with False)."""
    out = np.zeros((m,) + a.shape[1:], dtype=a.dtype)
    out[:a.shape[0]] = a
    return out


def _stack_state(n_dev: int, capacity: int, key_width: int,
                 specs: Sequence[AggSpec]) -> AggState:
    """AggState with a leading device axis on every leaf."""
    one = make_agg_state(capacity, key_width, specs)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n_dev,) + a.shape), one)


class ShardedAggKernel:
    """Multi-chip grouped aggregation (fixed capacity v1 — growth and
    elastic resharding land with the reschedule path).

    apply(): one jitted SPMD step — vnode routing, all_to_all, local
    probe+scatter per shard. snapshot(): host-side decode of all live
    groups (test/flush support).
    """

    # one inc per shard_map launch, at the launch (metrics contract
    # shared with the fused kernels): the executor layer checks this
    # and skips its per-chunk request counting
    counts_own_dispatches = True

    # epoch batch bound, mirroring GroupedAggKernel.BATCH_ROWS: the
    # backlog dispatches at this many rows mid-epoch (bounds host
    # buffering and the int32 limb math), else once at the barrier
    # flush — O(1) SPMD dispatches per epoch, the only dispatch shape.
    # The FIXED batch shape also means one compiled program instead of
    # per-chunk-shape churn — the RecompileGuard's sharded contract.
    BATCH_ROWS = 1 << 15

    def __init__(self, mesh: Mesh, key_width: int,
                 specs: Sequence[AggSpec], capacity: int = 1 << 12,
                 bucket: Optional[int] = None,
                 flush_capacity: int = 1 << 10):
        self.mesh = mesh
        self.n_dev = mesh.devices.size
        self.specs = tuple(specs)
        self.key_width = key_width
        self.capacity = capacity
        self.bucket = bucket
        self._backlog: List[np.ndarray] = []
        self._backlog_owners: List[Optional[np.ndarray]] = []
        self._backlog_rows = 0
        self._backlog_vis = 0
        self._stage_pending: List = []
        # fused-fragment mode (ops/fused.py build_agg_prelude): set via
        # set_prelude BEFORE any data; the absorbed filter/project run
        # traces ahead of the vnode routing inside the same SPMD step
        self._prelude = None
        self._raw_width: Optional[int] = None
        self.metrics_label: Optional[str] = None
        self._span_label = "ShardedAggKernel"
        self._touched = False
        # the state table this kernel's groups persist to (the
        # executor sets it): names the kernel in the exchange's books
        # and in rw_mesh_tables
        self.table_id: Optional[int] = None
        MESH_KERNELS.add(self)
        # vnode → owning shard: contiguous even split (VnodeMapping)
        owners = np.repeat(np.arange(self.n_dev, dtype=np.int32),
                           VNODE_COUNT // self.n_dev)
        pad = VNODE_COUNT - len(owners)
        if pad:
            owners = np.concatenate(
                [owners, np.full(pad, self.n_dev - 1, np.int32)])
        self.owner_map = jnp.asarray(owners)
        self._owner_map_host = owners
        sharding = NamedSharding(mesh, P(AXIS))
        self.state: AggState = jax.tree.map(
            lambda a: jax.device_put(a, sharding),
            _stack_state(self.n_dev, capacity, key_width, self.specs))
        self._step_cache: Dict[Tuple[int, int], object] = {}
        self._fills = tuple(f for _dt, f in dev_layout(self.specs))
        self._flush_cap = next_pow2(flush_capacity)
        self._flush_idx: Optional[List[np.ndarray]] = None
        self._counters = _ShardedCounters(self.n_dev)
        self._state_spec = jax.tree.map(lambda _: P(AXIS), self.state)
        self._advance_jit = self._shardwise(advance_state, donate=True,
                                            cache_key=("advance",))
        self._retire_jit = None        # built lazily (lane_off static)
        self._patch_step = None        # built lazily (col count static)
        self._gather_cache: Dict[int, object] = {}

    def _prog_key(self, *parts) -> tuple:
        return (tuple(int(d.id) for d in self.mesh.devices.flat),
                self.key_width, self.specs) + parts

    def _prog_label(self, stem: str, *variant) -> str:
        """A program's label: `stem[signature]`, the signature being
        what `_prog_key` shares a compiled program by (key width, agg
        specs, and a fused prelude's key). `jaxtools.program_name`
        turns it into `jit_<stem>_<8 hex>`, so two aggregates of one
        view go by different names in the device trace."""
        return f"{stem}[{(self.key_width, self.specs) + variant!r}]"

    @property
    def route_label(self) -> str:
        return route_label("sharded_agg", self.table_id)

    def shard_tables(self) -> List[tuple]:
        """[(part, occupied per shard, capacity per shard)] of the
        device tables (rw_mesh_tables); one blocking read."""
        occ = np.asarray(jnp.sum(self.state.table.occ, axis=1,
                                 dtype=jnp.int32))
        return [("groups", occ, self.capacity)]

    def _shardwise(self, fn, donate: bool, out_spec=None,
                   extra_specs=(), cache_key=None):
        """Wrap a single-chip traced state transform in shard_map: each
        shard applies `fn` to its slice (leading axis dropped/restored).
        The single-chip and sharded kernels literally share programs.
        ``cache_key`` (structural statics) shares the COMPILED program
        across kernel instances via the module cache."""
        key = None
        if cache_key is not None:
            key = self._prog_key(*cache_key)
            step = _PROG_CACHE.get(key)
            if step is not None:
                return step

        def local(state, *args):
            state = jax.tree.map(lambda a: a[0], state)
            out = fn(state, *args)
            return jax.tree.map(lambda a: a[None], out)

        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(self._state_spec,) + tuple(extra_specs),
            out_specs=out_spec if out_spec is not None
            else self._state_spec,
            check_vma=False)
        step = jaxtools.instrumented_jit(
            mapped, self._prog_label("parallel_agg.sharded"),
            donate_argnums=(0,) if donate else ())
        if key is not None:
            _PROG_CACHE[key] = step
        return step

    # -- fused-fragment prelude (ops/fused.py) ----------------------------
    @property
    def supports_prelude(self) -> bool:
        """Fusion eligibility hook (opt/fusion.agg_ineligible_reason):
        the sharded apply traces an absorbed filter/project run BEFORE
        vnode routing inside the same SPMD step — but only a kernel
        that has not yet seen data can adopt one."""
        return not self._touched

    def set_prelude(self, prelude, raw_width: int,
                    metrics_label: Optional[str] = None,
                    prelude_key: Optional[str] = None) -> None:
        """Install the fused-input prelude (build_agg_prelude). Must
        run before any data touches the kernel — the raw codec changes
        the upload layout. ``prelude_key`` is the run's STRUCTURAL
        identity (FusedStages.trace_key): equal runs share compiled
        steps across kernel instances and sessions."""
        assert not self._touched, "set_prelude after data flowed"
        self._prelude = prelude
        self._raw_width = int(raw_width)
        self._prelude_key = prelude_key or f"id:{id(prelude)}"
        self.metrics_label = metrics_label
        if metrics_label:
            self._span_label = metrics_label

    # -- the SPMD step ----------------------------------------------------
    # The step consumes the single-chip PACKED chunk matrix
    # (ops/hash_agg.pack_chunk: keys | sign | vis | per call lanes +
    # valid) — ONE routed payload through the all_to_all instead of a
    # flat array per lane, and the same host codec as the single-chip
    # kernel (no drifting twin). With a prelude, the upload is the RAW
    # int64 matrix and the absorbed run traces ahead of the routing.
    def _build_packed_step(self, bucket: int):
        specs = self.specs
        slices = _call_slices(specs)
        call_cols = packed_layout(self.key_width, specs)
        n_dev = self.n_dev
        kw = self.key_width

        def local_step(state: AggState, packed, owner_map):
            # shard_map hands each shard a [1, ...] block: drop the axis
            state = jax.tree.map(lambda a: a[0], state)
            key_lanes = packed[:, :kw]
            vis = packed[:, kw + 1].astype(bool)
            vn = vnodes_from_lanes(key_lanes)
            owner = owner_map[vn]
            buckets, bvalid, overflow = bucketize_by_owner(
                owner, vis, [packed], n_dev, bucket)
            recv, rvalid = exchange(buckets, bvalid, AXIS)
            m = n_dev * bucket
            rp = recv[0].reshape(m, packed.shape[1])
            rvis = rvalid.reshape(m)
            rkeys = rp[:, :kw]
            table, slots, ins = ht.probe_insert(state.table, rkeys,
                                                rvis)
            cap = state.table.capacity
            scat = jnp.where(rvis, slots, cap)
            s32 = rp[:, kw]
            group_rows = state.group_rows.at[scat].add(s32, mode="drop")
            dirty = state.dirty.at[scat].set(True, mode="drop")
            accs = list(state.accs)
            for spec, sl, (lc, vc) in zip(specs, slices, call_cols):
                if spec.is_float_sum:
                    in_lanes = tuple(jax.lax.bitcast_convert_type(
                        rp[:, i], jnp.float32) for i in lc)
                else:
                    in_lanes = tuple(rp[:, i] for i in lc)
                val_ok = jnp.ones(m, dtype=bool) if vc is None \
                    else rp[:, vc].astype(bool)
                _update_call(spec, accs, sl, in_lanes, val_ok, slots,
                             rvis, s32, cap)
            new = AggState(table, group_rows, dirty, tuple(accs),
                           state.emitted_valid, state.emitted_rows,
                           state.emitted_accs)
            new = jax.tree.map(lambda a: a[None], new)
            return new, ins[None], overflow[None]

        state_spec = jax.tree.map(lambda _: P(AXIS), self.state)
        mapped = jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(state_spec, P(AXIS), P()),
            out_specs=(state_spec, P(AXIS), P(AXIS)),
            check_vma=False)
        return jaxtools.instrumented_jit(
            mapped, self._prog_label("parallel_agg.step"),
            donate_argnums=(0,))

    def _build_raw_step(self, bucket: int):
        """The prelude (fused) twin: raw int64 rows → the absorbed
        filter/project run → key/lane encode — all traced BEFORE the
        vnode routing, per shard, in the same SPMD step (ISSUE 10:
        `fusion_grouping` stops refusing mesh plans)."""
        specs = self.specs
        slices = _call_slices(specs)
        n_dev = self.n_dev
        prelude = self._prelude

        def local_step(state: AggState, raw, owner_map):
            state = jax.tree.map(lambda a: a[0], state)
            key_lanes, s32, vis, call_inputs, stage_rows = prelude(raw)
            local_n = key_lanes.shape[0]
            vn = vnodes_from_lanes(key_lanes)
            owner = owner_map[vn]
            payloads = [key_lanes, s32.astype(jnp.int32)]
            for spec, (in_lanes, val_ok) in zip(specs, call_inputs):
                payloads.extend(in_lanes)
                payloads.append(
                    jnp.ones(local_n, dtype=bool) if val_ok is None
                    else val_ok)
            buckets, bvalid, overflow = bucketize_by_owner(
                owner, vis, payloads, n_dev, bucket)
            recv, rvalid = exchange(buckets, bvalid, AXIS)
            m = n_dev * bucket
            rkeys = recv[0].reshape(m, key_lanes.shape[1])
            rsigns = recv[1].reshape(m)
            rflat = [r.reshape(m) for r in recv[2:]]
            rvis = rvalid.reshape(m)
            table, slots, ins = ht.probe_insert(state.table, rkeys,
                                                rvis)
            cap = state.table.capacity
            scat = jnp.where(rvis, slots, cap)
            group_rows = state.group_rows.at[scat].add(rsigns,
                                                       mode="drop")
            dirty = state.dirty.at[scat].set(True, mode="drop")
            accs = list(state.accs)
            k = 0
            for spec, sl in zip(specs, slices):
                n_in = n_input_lanes(spec)
                in_lanes = tuple(rflat[k:k + n_in])
                val_ok = rflat[k + n_in]
                k += n_in + 1
                _update_call(spec, accs, sl, in_lanes, val_ok, slots,
                             rvis, rsigns, cap)
            new = AggState(table, group_rows, dirty, tuple(accs),
                           state.emitted_valid, state.emitted_rows,
                           state.emitted_accs)
            new = jax.tree.map(lambda a: a[None], new)
            # rows this shard received: the prelude filters and keys
            # the rows in-trace, so the host cannot count them
            received = jnp.sum(rvis, dtype=jnp.int32)
            return (new, ins[None], overflow[None],
                    stage_rows[None], received[None])

        state_spec = jax.tree.map(lambda _: P(AXIS), self.state)
        mapped = jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(state_spec, P(AXIS), P()),
            out_specs=(state_spec, P(AXIS), P(AXIS), P(AXIS),
                       P(AXIS)),
            check_vma=False)
        return jaxtools.instrumented_jit(
            mapped, self._prog_label("parallel_agg.step_fused",
                                     self._prelude_key),
            donate_argnums=(0,))

    def apply(self, key_lanes: np.ndarray, signs: np.ndarray,
              vis: np.ndarray,
              inputs: Sequence[Tuple[Sequence[np.ndarray], np.ndarray]]
              ) -> None:
        """Buffer one host chunk for the epoch's SPMD step.

        ISSUE 10: chunks accumulate host-side (the single-chip packed
        codec) and the whole epoch ships as ONE routed SPMD dispatch at
        the barrier flush (or per BATCH_ROWS slab mid-epoch) — signs
        and visibility ride the packed aux columns, and the adds
        commute across the epoch fold (limb/count adds exactly;
        MIN/MAX idempotently), so the batched application equals the
        per-chunk one. `inputs` is per call (value lanes, valid mask).
        """
        assert self._prelude is None, \
            "fused kernel takes raw chunks (apply_raw)"
        self._touched = True
        with LEDGER.phase("host_pack", kernel=self._span_label):
            packed = pack_chunk(self.key_width, self.specs,
                                np.asarray(key_lanes),
                                np.asarray(signs),
                                np.asarray(vis), inputs)
        n = packed.shape[0]
        if self._backlog_rows + n > self.BATCH_ROWS:
            self.dispatch_backlog()
        self._backlog.append(packed)
        self._backlog_rows += n
        # growth decisions run per buffered chunk (pessimistic bound
        # over the whole backlog): the rehash happens off the dispatch
        # path, and a table sized for its stream never re-checks
        self._reserve(self._backlog_rows)
        if self._backlog_rows >= self.BATCH_ROWS:
            self.dispatch_backlog()

    def owners_of(self, key_lanes: np.ndarray) -> np.ndarray:
        """Host twin of the device vnode routing (the executor feeds
        per-row owners back for the skew-exact bucket on the fused
        path, where the trace alone holds the derived lanes) — the
        shared exchange helper, one copy with the join kernel."""
        with route_phase(self._span_label):
            return owners_host(key_lanes, self._owner_map_host)

    def apply_raw(self, raw: np.ndarray, n_visible: int,
                  owners: Optional[np.ndarray] = None) -> None:
        """Fused-fragment hot path: backlog one RAW int64 chunk matrix
        (ops/fused.encode_raw_chunk) plus an always-invisible separator
        row — the traced chain's shifted compares must never marry rows
        across chunk boundaries (the separator-row codec of
        ops/fused.py, reused as the epoch buffer's chunk-boundary aux
        marker). ``owners`` (host-derived when the group keys map to
        raw columns) rides along for the skew-exact routing bucket —
        a PRE-filter superset of the routed rows, so the bound stays
        safe when the traced filter drops rows."""
        assert self._prelude is not None, \
            "apply_raw needs a fused (set_prelude) kernel"
        self._touched = True
        n = raw.shape[0] + 1
        if self._backlog_rows + n > self.BATCH_ROWS:
            self.dispatch_backlog()
        self._backlog.append(raw)
        self._backlog.append(np.zeros((1, raw.shape[1]),
                                      dtype=np.int64))   # separator
        if owners is not None:
            ow = np.full(n, -1, dtype=np.int64)
            vis = raw[:, 1] != 0
            ow[:n - 1][vis] = np.asarray(owners)[vis]
            self._backlog_owners.append(ow)
        else:
            self._backlog_owners.append(None)
        self._backlog_rows += n
        self._backlog_vis += int(n_visible)
        self._reserve(self._backlog_rows)
        if self._backlog_rows >= self.BATCH_ROWS:
            self.dispatch_backlog()

    def dispatch_backlog(self) -> None:
        """Ship the buffered epoch rows as ONE SPMD dispatch: pad to
        the fixed batch shape (one compiled program; pad rows are
        invisible and route nowhere), route every row to its vnode
        owner, apply locally."""
        if not self._backlog:
            return
        mats, n = self._backlog, self._backlog_rows
        n_vis = self._backlog_vis
        owner_chunks = self._backlog_owners
        self._backlog, self._backlog_rows = [], 0
        self._backlog_owners = []
        self._backlog_vis = 0
        raw_mode = self._prelude is not None
        # per-shard post-exchange batch is n_dev*bucket rows in ONE
        # traced step; limb sums stay exact past MAX_CHUNK_ROWS
        # because _update_call slices the batch and carry-normalizes
        # per slab (the single-chip 32K backlog rides the same path)
        self._reserve(n)
        # epoch staging is host_pack (the ledger's phase classes); the
        # routing in front of the exchange is exchange_route; the
        # sharded upload below is h2d
        with LEDGER.phase("host_pack", kernel=self._span_label):
            # pow2-bucketed batch shape (the join epoch path's
            # convention): steady-state epochs repeat a handful of
            # shapes — the RecompileGuard's sharded contract — without
            # padding every small epoch to the full 32K slab
            cap_rows = max(next_pow2(n), self.n_dev)
            if cap_rows % self.n_dev:
                cap_rows += self.n_dev - (cap_rows % self.n_dev)
            w = mats[0].shape[1]
            packed = np.zeros((cap_rows, w),
                              dtype=np.int64 if raw_mode else np.int32)
            at = 0                   # pad rows: vis=0
            for m_ in mats:
                packed[at:at + m_.shape[0]] = m_
                at += m_.shape[0]
        local = cap_rows // self.n_dev
        bucket = self.bucket or local
        # rows each shard will receive, where the host can tell: the
        # packed path always can; the fused raw path only where the
        # executor fed the owners back (else its lanes only exist
        # in-trace and the step counts what it received)
        received = None
        with route_phase(self._span_label):
            owner = None
            if not raw_mode:
                kw_ = self.key_width
                routed = packed[:, kw_ + 1] != 0
                owner = owners_host(packed[:, :kw_],
                                    self._owner_map_host)
            elif owner_chunks and \
                    all(o is not None for o in owner_chunks):
                owner = np.full(cap_rows, -1, dtype=np.int64)
                owner[:n] = np.concatenate(owner_chunks)
                routed = owner >= 0
            if owner is not None and self.bucket is None:
                # skew-exact routing bucket (the join's stage_epoch
                # scheme): the default (= local rows) makes every shard
                # process the WHOLE batch post-exchange — n_dev× the
                # single-chip compute; exact per-(sender, target)
                # counts from the host key lanes collapse it to the
                # real skew, pow2-quantized for shape stability. A
                # fused raw path without owners keeps the worst case.
                bucket = skew_bucket(owner, routed, self.n_dev, local)
            if not raw_mode:
                # (fused: `owner` is a PRE-filter superset, good for a
                # bound and no count; the step's own count is taken)
                received = received_by_shard(owner, routed, self.n_dev)
        key = (cap_rows, bucket, raw_mode)
        step = self._step_cache.get(key)
        if step is None:
            if raw_mode:
                # structural prelude key (set_prelude): equal fused
                # runs share the compiled step across instances
                mkey = self._prog_key("step_fused", bucket,
                                      self._prelude_key)
                step = _PROG_CACHE.get(mkey)
                if step is None:
                    step = self._build_raw_step(bucket)
                    _PROG_CACHE[mkey] = step
            else:
                mkey = self._prog_key("step", bucket)
                step = _PROG_CACHE.get(mkey)
                if step is None:
                    step = self._build_packed_step(bucket)
                    _PROG_CACHE[mkey] = step
            self._step_cache[key] = step
        from risingwave_tpu.utils.ledger import note_backlog
        # same kernel label as the phase scopes/transfer bytes above,
        # so one kernel's series correlate across families
        note_backlog(self._span_label, n)
        up = jaxtools.upload(packed, NamedSharding(self.mesh, P(AXIS)),
                             kernel=self._span_label)
        _note_dispatch(n_vis if raw_mode else n)
        note_launch(self.route_label, self.n_dev, bucket)
        got = None
        if raw_mode:
            with spans.dispatch_span(self._span_label, n_vis,
                                     batch_rows=n):
                self.state, ins, overflow, stage_rows, got = step(
                    self.state, up, self.owner_map)
            jaxtools.start_fetch(stage_rows)
            self._stage_pending.append(stage_rows)
        else:
            with spans.dispatch_span(self._span_label, n,
                                     batch_rows=n):
                self.state, ins, overflow = step(self.state, up,
                                                 self.owner_map)
            note_routed(int(routed.sum()), received)
        # overflow/insert counters (and the fused step's count of the
        # rows it received) fold in asynchronously instead of one
        # blocking read per dispatch
        self._counters.push(ins, overflow, n, received=got)

    def drain_stage_rows(self) -> Optional[np.ndarray]:
        """Sum of per-stage visible-row counts since the last drain
        (fused mode; per-shard vectors sum across the mesh — each raw
        row is counted by exactly one shard pre-routing)."""
        if not self._stage_pending:
            return None
        total = None
        for v in self._stage_pending:
            a = np.asarray(jaxtools.fetch1(v)).sum(axis=0)
            total = a if total is None else total + a
        self._stage_pending = []
        return np.asarray(total)

    def _reserve(self, n: int) -> None:
        """Grow (per-shard rehash) until the fullest shard keeps room
        for `n` pessimistic inserts — the fatal-on-overflow contract of
        v1 is gone (VERDICT r3 #5): state may exceed the initial device
        capacity by any factor; each doubling costs one SPMD rebuild +
        a retrace, amortized like the single-chip growth ladder."""
        self._counters.drain_ready()
        if self._counters.bound() + n <= ht.MAX_LOAD * self.capacity:
            return
        self._counters.drain_all()
        worst = self._counters.worst_exact()
        if worst + n > ht.MAX_LOAD * self.capacity:
            self.grow(next_pow2(int((worst + n) / ht.MAX_LOAD) + 1))

    def grow(self, new_capacity: int) -> None:
        """Per-shard same-membership rehash into larger tables — ONE
        SPMD step reusing the single-chip rebuild (_rebuild_live with
        every occupied slot live), preserving dirty flags and emitted
        snapshots so in-epoch growth never disturbs flush diffs."""
        new_capacity = next_pow2(max(new_capacity, self.capacity * 2))
        fills = self._fills
        step = self._shardwise(
            lambda st: _rebuild_live(st, st.table.occ, new_capacity,
                                     fills),
            donate=True, out_spec=(self._state_spec, P(AXIS)))
        self.state, n_live = step(self.state)
        self.capacity = new_capacity
        # exact per-shard occupancy falls out of the rebuild for free
        self._counters.reset(
            np.asarray(jaxtools.fetch1(n_live)).reshape(self.n_dev))

    # -- barrier flush (GroupedAggKernel surface) -------------------------
    def flush(self) -> FlushResult:
        """Gather every shard's dirty groups — ONE [n_dev, 1+fc, W]
        fetch — and decode the concatenation. Keys never span shards
        (ownership is a function of the key hash), so the merged result
        is a disjoint union and HashAggExecutor's emission/persistence
        logic runs unchanged on it."""
        # the epoch's buffered rows ship as ONE SPMD dispatch here —
        # the barrier IS the sharded batch boundary (ISSUE 10)
        self.dispatch_backlog()
        # drain next: reset() would discard pending bucket-overflow
        # flags, and an overflow MUST surface before this barrier's
        # results are treated as complete. The host waits here for
        # the queued apply steps: the wait goes by this kernel's label
        with LEDGER.kernel_scope(f"{self._span_label}.drain"):
            self._counters.drain_all()
        fc = self._flush_cap
        while True:
            if fc not in self._gather_cache:
                self._gather_cache[fc] = self._shardwise(
                    partial(gather_packed, flush_cap=fc), donate=False,
                    out_spec=P(AXIS), cache_key=("gather", fc))
            with spans.dispatch_span(f"{self._span_label}.flush",
                                     self._counters.bound()):
                mats = jaxtools.fetch1(
                    self._gather_cache[fc](self.state))
            ps = mats[:, 0, 0]
            _note_dispatch(float(ps.sum()))
            self._counters.reset(mats[:, 0, 1])
            worst = int(ps.max())
            if worst <= fc:
                break
            fc = max(fc * 2, next_pow2(worst))
        self._flush_cap = fc
        if int(ps.sum()) == 0:
            self._flush_idx = [np.zeros(0, dtype=np.int32)
                               for _ in range(self.n_dev)]
            return FlushResult.empty(self.specs, self.key_width)
        with LEDGER.phase("host_emit", kernel=self._span_label,
                          stage="agg.decode"):
            segs = [mats[d, 1:1 + int(ps[d])]
                    for d in range(self.n_dev)]
            self._flush_idx = [np.ascontiguousarray(s[:, 0])
                               for s in segs]
            data = np.concatenate(segs, axis=0)
            return decode_flush_data(self.specs, self.key_width, data)

    def advance(self) -> None:
        assert self._flush_idx is not None, "flush() first"
        self._flush_idx = None
        self.state = self._advance_jit(self.state)

    def patch_accs(self, decoded, raw_accs=None) -> None:
        """Overwrite flushed groups' accumulators across all shards
        (retractable MIN/MAX minput recompute — the single-chip
        patch_accs, shard-mapped). The flush's per-shard slot indices
        (self._flush_idx) route each corrected row back to its owning
        shard; untouched calls pass their raw gathered columns through
        bit-for-bit."""
        idxs = self._flush_idx
        assert idxs is not None and any(len(ix) for ix in idxs), \
            "flush() first"
        from risingwave_tpu.ops.hash_agg import encode_patch_cols
        dev_cols = encode_patch_cols(self.specs, decoded, raw_accs)
        counts = [len(ix) for ix in idxs]
        m = next_pow2(max(counts))
        bidx = np.full((self.n_dev, m), self.capacity, dtype=np.int32)
        bcols = [np.zeros((self.n_dev, m), dtype=c.dtype)
                 for c in dev_cols]
        at = 0
        for d_i, ix in enumerate(idxs):
            c = len(ix)
            bidx[d_i, :c] = ix
            for bc, col in zip(bcols, dev_cols):
                bc[d_i, :c] = col[at:at + c]
            at += c

        if self._patch_step is None:
            from risingwave_tpu.ops.hash_agg import build_patch
            patch = build_patch(self.specs)
            n_cols = len(dev_cols)
            self._patch_step = self._shardwise(
                lambda st, ix, *cols: patch(st, ix, tuple(cols)),
                donate=True,
                extra_specs=(P(AXIS),) * (1 + n_cols),
                cache_key=("patch", n_cols))
        self.state = self._patch_step(
            self.state, jnp.asarray(bidx),
            *(jnp.asarray(b) for b in bcols))

    def retire_below(self, group_pos: int, wm_i64: int) -> None:
        """Watermark state cleaning, every shard in one SPMD step.
        Runs post-flush only — a buffered epoch batch here would apply
        rows to already-retired groups out of order."""
        if self._backlog_rows:
            raise RuntimeError("retire_below with undispatched backlog")
        if self._retire_jit is None:
            fills = self._fills
            off = group_pos * 3
            self._retire_jit = self._shardwise(
                lambda st, hi, lo: retire_state(st, hi, lo, off, fills),
                donate=True,
                out_spec=(self._state_spec, P(AXIS)),
                extra_specs=(P(), P()),
                cache_key=("retire", off))
            self._retire_off = off
        assert self._retire_off == group_pos * 3, \
            "one watermark column per kernel"
        hi, lo = lanes.split_i64(np.asarray([wm_i64], dtype=np.int64))
        self.state, _n_live = self._retire_jit(
            self.state, jnp.int32(hi[0]), jnp.int32(lo[0]))

    def rebuild(self, keys: np.ndarray, group_rows: np.ndarray,
                acc_cols: Sequence[np.ndarray]) -> None:
        """Reload committed value-state rows (recovery), routing each
        group to its owning shard on the host (recovery is cold path;
        the steady-state exchange stays on device)."""
        n = len(group_rows)
        self._backlog = []
        self._backlog_owners = []
        self._backlog_rows = 0
        self._backlog_vis = 0
        self._stage_pending = []
        self.state = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(self.mesh, P(AXIS))),
            _stack_state(self.n_dev, self.capacity, self.key_width,
                         self.specs))
        self._counters.reset(np.zeros(self.n_dev, dtype=np.int64))
        if n == 0:
            return
        dev_cols = encode_host_accs(self.specs, acc_cols)
        vn = np.asarray(vnodes_from_lanes(jnp.asarray(keys)))
        owner = np.asarray(self.owner_map)[vn]
        per_shard = np.bincount(owner, minlength=self.n_dev)
        worst = int(per_shard.max(initial=0))
        if worst > ht.MAX_LOAD * self.capacity:
            # probe_insert's free-slot contract: an over-full shard
            # would scatter rows into other groups' slots silently —
            # size the fresh state to fit instead
            self.capacity = next_pow2(int(worst / ht.MAX_LOAD) + 1)
            self.state = jax.tree.map(
                lambda a: jax.device_put(
                    a, NamedSharding(self.mesh, P(AXIS))),
                _stack_state(self.n_dev, self.capacity, self.key_width,
                             self.specs))
        m = next_pow2(int(per_shard.max(initial=1)))
        # stack into [n_dev, m, ...] padded blocks
        order = np.argsort(owner, kind="stable")
        pos_in_shard = np.empty(n, dtype=np.int64)
        at = 0
        for d in range(self.n_dev):
            c = int(per_shard[d])
            pos_in_shard[order[at:at + c]] = np.arange(c)
            at += c

        def blocks(col, fill=0):
            out = np.full((self.n_dev, m) + col.shape[1:], fill,
                          dtype=col.dtype)
            out[owner, pos_in_shard] = col
            return out

        bkeys = blocks(keys)
        brows = blocks(group_rows.astype(np.int32))
        baccs = [blocks(np.asarray(c)) for c in dev_cols]
        bvalid = np.zeros((self.n_dev, m), dtype=bool)
        bvalid[owner, pos_in_shard] = True

        def local(state, keys_b, rows_b, valid_b, *accs_b):
            state = jax.tree.map(lambda a: a[0], state)
            keys_l, rows_l, valid_l = keys_b[0], rows_b[0], valid_b[0]
            table, slots, _ins = ht.probe_insert(
                state.table, keys_l, valid_l)
            scat = jnp.where(valid_l, slots, state.table.capacity)
            accs = tuple(
                a.at[scat].set(c[0], mode="drop")
                for a, c in zip(state.accs, accs_b))
            rows_dev = state.group_rows.at[scat].set(rows_l, mode="drop")
            new = AggState(
                table=table, group_rows=rows_dev, dirty=state.dirty,
                accs=accs,
                emitted_valid=state.emitted_valid.at[scat].set(
                    True, mode="drop"),
                emitted_rows=jnp.copy(rows_dev),
                emitted_accs=tuple(jnp.copy(a) for a in accs),
            )
            return jax.tree.map(lambda a: a[None], new)

        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(self._state_spec,) + (P(AXIS),) * (3 + len(baccs)),
            out_specs=self._state_spec, check_vma=False)
        self.state = jax.jit(mapped, donate_argnums=(0,))(
            self.state, bkeys, brows, bvalid, *baccs)
        self._counters.reset(per_shard.astype(np.int64))

    # -- elastic resharding (scale.rs:174 / Mutation::Update analog) ------
    def reshard(self, new_owner_map: np.ndarray) -> None:
        """Move device state to a new vnode→shard mapping at a barrier.

        The reference reschedules by swapping vnode bitmaps and lazily
        reloading state from Hummock (state_table.rs:650); the TPU-
        native equivalent moves the HBM-resident groups directly: one
        SPMD step routes every live slot's (key, counters, accs,
        emitted snapshot) to its new owner via the bucketized
        all_to_all, then rebuilds each shard's table with the same
        probe-insert kernel. No host round-trip for the state itself.
        """
        new_map = jnp.asarray(np.asarray(new_owner_map, dtype=np.int32))
        n_dev = self.n_dev
        cap = self.capacity
        specs = self.specs
        key_width = self.key_width

        def local(state: AggState, owner_map):
            state = jax.tree.map(lambda a: a[0], state)
            live = state.table.occ & ((state.group_rows != 0)
                                      | state.dirty | state.emitted_valid)
            owner = owner_map[vnodes_from_lanes(state.table.keys)]
            payloads = [state.table.keys, state.group_rows,
                        state.dirty.astype(jnp.int32),
                        state.emitted_valid.astype(jnp.int32),
                        state.emitted_rows,
                        *state.accs, *state.emitted_accs]
            # bucket = cap: a shard can never receive more rows than
            # fit in one table, so routing is overflow-free
            buckets, bvalid, _overflow = bucketize_by_owner(
                owner, live, payloads, n_dev, cap)
            recv, rvalid = exchange(buckets, bvalid, AXIS)
            m = n_dev * cap
            rvis = rvalid.reshape(m)
            n_received = jnp.sum(rvis, dtype=jnp.int32)
            rkeys = recv[0].reshape(m, key_width)
            fresh = make_agg_state(cap, key_width, specs)
            table, slots, _ins = ht.probe_insert(fresh.table, rkeys,
                                                 rvis)
            scat = jnp.where(rvis, slots, cap)

            def put(dst, src, cast=None):
                v = src.reshape(m)
                if cast is not None:
                    v = v.astype(cast)
                return dst.at[scat].set(v, mode="drop")

            na = len(state.accs)
            new = AggState(
                table=table,
                group_rows=put(fresh.group_rows, recv[1]),
                dirty=put(fresh.dirty, recv[2], jnp.bool_),
                accs=tuple(put(f, r) for f, r in
                           zip(fresh.accs, recv[5:5 + na])),
                emitted_valid=put(fresh.emitted_valid, recv[3],
                                  jnp.bool_),
                emitted_rows=put(fresh.emitted_rows, recv[4]),
                emitted_accs=tuple(put(f, r) for f, r in
                                   zip(fresh.emitted_accs,
                                       recv[5 + na:])),
            )
            return jax.tree.map(lambda a: a[None], new), n_received[None]

        state_spec = jax.tree.map(lambda _: P(AXIS), self.state)
        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(state_spec, P()), out_specs=(state_spec, P(AXIS)),
            check_vma=False)
        step = jax.jit(mapped, donate_argnums=(0,))
        new_state, received = step(self.state, new_map)
        note_launch(self.route_label, n_dev, cap)
        received = np.asarray(received, dtype=np.int64)
        note_routed(int(received.sum()), received)
        # destination-table contract: probe_insert needs a free slot
        # per routed row; an overfull shard would silently corrupt
        # accumulators — fail loudly instead
        worst = int(received.max())
        if worst > ht.MAX_LOAD * cap:
            raise RuntimeError(
                f"reshard overfills a shard: {worst} live groups vs "
                f"{cap} slots — raise capacity before rescaling")
        self.state = new_state
        self.owner_map = new_map   # apply steps take it as a runtime arg
        # host twin follows (the skew-exact bucket counts against it)
        self._owner_map_host = np.asarray(new_owner_map,
                                          dtype=np.int32)

    # -- host-side full decode (tests + dryrun assertions) ---------------
    def snapshot(self) -> Dict[tuple, tuple]:
        """group key lanes tuple → decoded outputs, across all shards."""
        self.dispatch_backlog()
        self._counters.drain_all()
        st = jax.device_get(self.state)
        out: Dict[tuple, tuple] = {}
        for d in range(self.n_dev):
            occ = st.table.occ[d]
            live = occ & (st.group_rows[d] > 0)
            idx = np.flatnonzero(live)
            if not len(idx):
                continue
            keys = st.table.keys[d][idx]
            accs = [a[d][idx] for a in st.accs]
            outs, nulls = decode_outputs(self.specs, accs)
            for r in range(len(idx)):
                kt = tuple(keys[r].tolist())
                out[kt] = tuple(
                    None if nulls[c][r] else outs[c][r].item()
                    for c in range(len(self.specs)))
        return out
