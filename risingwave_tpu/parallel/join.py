"""Vnode-sharded join matcher over a device mesh (multi-chip q8).

Reference parity: N parallel HashJoinExecutor actors fed by HASH
dispatchers on both inputs (dispatch.rs:582; hash_join.rs:227). TPU
re-design: each mesh shard owns the join-key vnode range's slice of
BOTH sides' key tables and row chains; a chunk routes to owners via the
bucketized all_to_all (parallel/exchange.py) and then runs the exact
single-chip kernels (ops/hash_join.py probe_pairs / link_rows /
tombstone_rows, sequence-versioned) locally — one code path, two
launch shapes, matching ShardedAggKernel's construction so the whole
q8 plan shards the same way the q7 plan does.

Host contract: row refs are GLOBAL (the host arena's); a ref lives
only on its key's owner shard, so each shard's chain arrays index by
global ref directly and probe results need no re-translation. The
executor (stream/executors/hash_join.py) cannot tell this kernel from
the single-chip JoinSideKernel — same stage_epoch / apply_epoch /
probe_epoch / probe / insert / delete / rebuild / rebase_seq API, same
async PendingProbe contract.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from risingwave_tpu.common.chunk import next_pow2
from risingwave_tpu.common.hash import VNODE_COUNT
from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.ops.hash_join import (
    AUX_DEL_REF, AUX_FLAGS, AUX_INS_REF, AUX_SEQ, FLAG_DEL, FLAG_INS,
    FLAG_PROBE, HEADER_ROWS, I32_MAX, ROW_ARRAYS, _remap_head,
    empty_chains, link_rows, probe_pairs, tombstone_rows,
)
from risingwave_tpu.parallel.exchange import (
    MESH_KERNELS, bucketize_by_owner, exchange, note_launch,
    note_routed, owners_host, received_by_shard, route_label,
    route_phase, skew_bucket, vnodes_from_lanes,
)
from risingwave_tpu.utils import jaxtools
from risingwave_tpu.utils.ledger import LEDGER

AXIS = "d"

# Compiled SPMD steps, shared ACROSS kernel instances (both sides of a
# join share shapes; capacity growth keys fresh entries instead of
# clearing): keyed by (mesh device ids, program kind, every static the
# closure bakes in). Before this cache, each _JoinSide's kernel rebuilt
# — and re-traced — its own steps on any shape churn, which the
# RecompileGuard now polices on the sharded path too. A CompileCache
# (stream/costs.py) so hits/misses bill the pulling MV: the first MV
# to trace an entry pays the compile, later tenants record shared hits.
from risingwave_tpu.stream.costs import CompileCache as _CompileCache

_STEP_CACHE: Dict[tuple, object] = _CompileCache("join_step")


def _step_key(mesh: Mesh, kind: str, *statics) -> tuple:
    return ((kind,) + tuple(int(d.id) for d in mesh.devices.flat)
            + statics)


def _note_dispatch(rows: float, kernel: str) -> None:
    """Real-SPMD-dispatch accounting at the jit sites (the sharded
    twin of the fused kernels' metrics_label counting): one inc per
    `shard_map` launch, with true row density — the executor layer
    does NOT count for sharded kernels, so totals never double."""
    from risingwave_tpu.utils.metrics import STREAMING
    STREAMING.device_dispatch.inc(1, kernel=kernel)
    STREAMING.rows_per_dispatch.observe(float(rows), kernel=kernel)


class ShardedPendingProbe:
    """In-flight sharded probe (DMA started at dispatch).

    Mirrors ops/hash_join.PendingProbe: sequence versioning makes
    collect() exact however late it runs, and an overflowed per-shard
    pair buffer re-dispatches a probe-only step at the recorded seq."""

    def __init__(self, kernel: "ShardedJoinKernel", mats, key_lanes,
                 vis, seq: int, out_cap: int, n: int, overflow=None):
        self.kernel = kernel
        self.mats = mats
        self.key_lanes = key_lanes      # host arrays (padded)
        self.vis = vis
        self.seq = seq
        self.out_cap = out_cap
        self.n = n                      # caller rows (pre-padding)
        # routing-overflow flag, checked lazily at collect: a sync here
        # would block the dispatch hot path, and the condition is
        # impossible by construction (bucket = local row count) — this
        # is an assertion, not a retry point
        self.overflow = overflow

    def collect(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(degrees[n], probe_idx[pairs], refs[pairs]) — pairs sorted
        by probe row so same-pk delete/insert halves stay ordered."""
        k = self.kernel
        with LEDGER.kernel_scope("sharded_join"):
            while True:
                if self.overflow is not None and \
                        bool(np.asarray(self.overflow).any()):
                    raise RuntimeError(
                        "bucket overflow routing join rows")
                mats = np.asarray(jaxtools.fetch1(self.mats))
                # a shard's candidates (ops/hash_join._match_runs)
                # have to fit its buffer
                worst = int(mats[:, 1, 0].max())
                if worst <= self.out_cap:
                    break
                while k.probe_capacity < worst:
                    k.probe_capacity *= 2
                self.out_cap = k.probe_capacity
                self.mats, self.overflow = k._dispatch_probe(
                    self.key_lanes, self.vis, self.seq, self.out_cap)
        at = HEADER_ROWS
        m = mats.shape[1] - at - self.out_cap
        deg = np.zeros(self.n, dtype=np.int32)
        probes, refs = [], []
        for d in range(mats.shape[0]):
            blk = mats[d, at:at + m]
            rid, dg = blk[:, 1], blk[:, 0]
            sel = rid >= 0
            deg[rid[sel]] = dg[sel]
            total = int(mats[d, 0, 0])
            pairs = mats[d, at + m:at + m + total]
            probes.append(pairs[:, 0])
            refs.append(pairs[:, 1])
        probe_idx = np.concatenate(probes) if probes else \
            np.zeros(0, np.int32)
        ref_arr = np.concatenate(refs) if refs else np.zeros(0, np.int32)
        order = np.argsort(probe_idx, kind="stable")
        return deg, probe_idx[order], ref_arr[order]


class ShardedPendingEpochProbe:
    """In-flight sharded EPOCH probe (ops/hash_join.PendingEpochProbe
    parity over the per-shard packed matrices).

    collect() parses each shard's [HEADER_ROWS + (m) + out_cap, 2]
    block — header, per-routed-row degree rows (with_degrees only), then
    (global probe row, ref) pairs — scatters degrees back to the
    global epoch row space and concatenates pairs sorted stably by
    probe row. A probe row's key routes to exactly ONE owner shard, so
    per-row match order is that shard's chain walk, preserved by the
    stable sort. Payload lanes and device old-degrees are None: the
    sharded path materializes rows from the host arena and keeps
    degrees in the executor's host arrays."""

    def __init__(self, kernel: "ShardedJoinKernel", mats, n_rows: int,
                 out_cap: int, with_degrees: bool, redispatch,
                 overflow=None):
        self.kernel = kernel
        self.mats = mats
        self.n = n_rows               # padded epoch rows
        self.out_cap = out_cap
        self.with_degrees = with_degrees
        self.redispatch = redispatch
        self.overflow = overflow
        # times the per-shard buffer grew and the program ran again
        self.redispatches = 0

    def collect(self):
        """(degrees | None, probe_idx, refs, None, None) over the
        CONCATENATED epoch row space, pairs sorted by probe row."""
        k = self.kernel
        with LEDGER.kernel_scope("sharded_join"):
            k.drain_overflows()
            while True:
                if self.overflow is not None and \
                        bool(np.asarray(jaxtools.fetch1(
                            self.overflow)).any()):
                    raise RuntimeError(
                        "bucket overflow routing epoch join probes")
                mats = np.asarray(jaxtools.fetch1(self.mats))
                worst = int(mats[:, 1, 0].max())     # candidates
                if worst <= self.out_cap:
                    break
                while k.probe_capacity < worst:
                    k.probe_capacity *= 2
                self.out_cap = k.probe_capacity
                self.redispatches += 1
                self.mats, self.overflow = self.redispatch(self.out_cap)
        at = HEADER_ROWS
        m = mats.shape[1] - at - self.out_cap
        deg = None
        if self.with_degrees:
            deg = np.zeros(self.n, dtype=np.int32)
        probes, refs = [], []
        for d in range(mats.shape[0]):
            if self.with_degrees:
                blk = mats[d, at:at + m]
                rid, dg = blk[:, 1], blk[:, 0]
                sel = rid >= 0
                deg[rid[sel]] = dg[sel]
            total = int(mats[d, 0, 0])
            pairs = mats[d, at + m:at + m + total]
            probes.append(pairs[:, 0])
            refs.append(pairs[:, 1])
        probe_idx = np.concatenate(probes) if probes else \
            np.zeros(0, np.int32)
        ref_arr = np.concatenate(refs) if refs else np.zeros(0, np.int32)
        order = np.argsort(probe_idx, kind="stable")
        return (deg, probe_idx[order].astype(np.int64),
                ref_arr[order], None, None)


class ShardedJoinKernel:
    """JoinSideKernel's API over a device mesh (multi-chip join side).

    Fixed-capacity v1: over-capacity is a loud error, growth is future
    work. Key-table occupancy is tracked as an upper bound (per-batch
    unique keys over-count keys recurring across batches); when the
    bound crosses the load limit it collapses to the true worst-shard
    occupancy with one device sync — GroupedAggKernel._reserve's
    scheme. The bound is GLOBAL while the limit is PER-SHARD, so it is
    conservative: a false trip costs one sync, never a false pass."""

    # pre-sized like JoinSideKernel.DEFAULT_CAPACITY: every growth
    # doubling rehashes AND re-keys every compiled SPMD step (a fresh
    # trace per program — multi-second stalls on the p99 tail), so the
    # defaults absorb typical runs and growth multiplies by 4x
    def __init__(self, mesh: Mesh, key_width: int,
                 key_capacity: int = 1 << 15,
                 row_capacity: int = 1 << 17,
                 probe_capacity: int = 1 << 13):
        self.mesh = mesh
        self.n_dev = mesh.devices.size
        self.key_width = key_width
        self.key_capacity = key_capacity
        self._row_capacity = row_capacity
        self.probe_capacity = probe_capacity
        owners = np.repeat(np.arange(self.n_dev, dtype=np.int32),
                           VNODE_COUNT // self.n_dev)
        pad = VNODE_COUNT - len(owners)
        if pad:
            owners = np.concatenate(
                [owners, np.full(pad, self.n_dev - 1, np.int32)])
        self.owner_map = jnp.asarray(owners)
        self._owner_map_host = owners
        self._sharding = NamedSharding(mesh, P(AXIS))
        self._fresh_state()
        # per-shard distinct-key upper bound (host)
        self._keys_upper = np.zeros(self.n_dev, dtype=np.int64)
        # apply-step overflow flags, checked lazily at the next probe
        # collect (impossible by construction — an assertion, never a
        # retry point; a sync here would block the dispatch hot path)
        self._apply_overflows: list = []
        # fused-input preludes by key (the epoch jits bake them in)
        self._preludes: Dict[str, object] = {}
        # epoch-trace identity stamped on dispatch metrics
        self._span_label = "ShardedJoinKernel"
        # the state table this side's rows persist to (the executor
        # sets it): names the side in the exchange's books and in
        # rw_mesh_tables
        self.table_id: Optional[int] = None
        # what stage_epoch counted for the batch it staged last, by the
        # staged aux array: its apply (this kernel) and its probe (the
        # OTHER side's kernel) each book a launch from it
        self._staged: Optional[tuple] = None
        MESH_KERNELS.add(self)

    @property
    def row_capacity(self) -> int:
        return self._row_capacity

    @property
    def route_label(self) -> str:
        return route_label("sharded_join", self.table_id)

    def shard_tables(self) -> list:
        """[(part, occupied per shard, capacity per shard)] of the
        device tables (rw_mesh_tables): the key table, and the row
        chains (indexed by GLOBAL ref; a shard links its own keys'
        rows only, tombstoned ones included until a rebuild)."""
        keys = np.asarray(jnp.sum(self.table.occ, axis=1,
                                  dtype=jnp.int32))
        rows = np.asarray(jnp.sum(
            self.chains.ins_seq != jnp.int32(I32_MAX), axis=1,
            dtype=jnp.int32))
        return [("keys", keys, self.key_capacity),
                ("rows", rows, self._row_capacity)]

    def _stack(self, a):
        return jax.device_put(
            jnp.broadcast_to(a[None], (self.n_dev,) + a.shape),
            self._sharding)

    def _fresh_state(self) -> None:
        table = ht.make_state(self.key_capacity, self.key_width)
        self.table = ht.TableState(self._stack(table.keys),
                                   self._stack(table.occ))
        self.chains = jax.tree.map(
            self._stack,
            empty_chains(self.key_capacity, self._row_capacity))

    # -- capacity management (state > device: grows, never fatal) ---------
    def _owners_host(self, key_lanes: np.ndarray) -> np.ndarray:
        """Host twin of the device routing (same hash → same owner) —
        the shared exchange helper, so device and host routing live in
        one place."""
        return owners_host(key_lanes, self._owner_map_host)

    def _guard_keys(self, key_lanes: np.ndarray, vis: np.ndarray) -> None:
        """PER-SHARD distinct-key upper bound; grows the key tables
        when the fullest shard runs out (VERDICT r3 #5: the fatal
        contract is gone). Growth is SEQ-PRESERVING — the chain arrays
        are row-indexed and untouched; only the key table + head remap
        — so it is safe mid-epoch with probes in flight."""
        kv = key_lanes[vis]
        if len(kv):
            uniq, idx = np.unique(kv, axis=0, return_index=True)
            add = np.bincount(self._owners_host(kv[idx]),
                              minlength=self.n_dev)
            self._keys_upper = self._keys_upper + add
        limit = ht.MAX_LOAD * self.key_capacity
        if int(self._keys_upper.max()) <= limit:
            return
        # collapse the bound to exact occupancy (one sync), then grow
        per_shard = np.asarray(jnp.sum(self.table.occ, axis=1)) \
            .astype(np.int64)
        headroom = 0 if not len(kv) else np.bincount(
            self._owners_host(kv), minlength=self.n_dev)
        need = per_shard + headroom
        self._keys_upper = need
        worst = int(need.max())
        if worst > limit:
            self._grow_keys(next_pow2(int(worst / ht.MAX_LOAD) + 1))

    def _grow_keys(self, new_capacity: int) -> None:
        # 4x, not 2x: each growth re-traces every step at the new
        # capacity statics (see _STEP_CACHE) — same amortization as
        # JoinSideKernel.reserve_rows
        new_capacity = max(new_capacity, self.key_capacity * 4)
        key_width = self.key_width
        n_dev = self.n_dev

        def local(t, c):
            t = jax.tree.map(lambda a: a[0], t)
            c = jax.tree.map(lambda a: a[0], c)
            nt = ht.make_state(new_capacity, key_width)
            nt, slots, _ins = ht.probe_insert(nt, t.keys, t.occ)
            head = _remap_head(c.head, jnp.where(t.occ, slots, -1),
                               new_capacity)
            nc = c._replace(head=head)
            return (jax.tree.map(lambda a: a[None], nt),
                    jax.tree.map(lambda a: a[None], nc))

        tspec, cspec = self._specs()
        mapped = jax.shard_map(
            local, mesh=self.mesh, in_specs=(tspec, cspec),
            out_specs=(tspec, cspec), check_vma=False)
        step = jax.jit(mapped, donate_argnums=(0, 1))
        self.table, self.chains = step(self.table, self.chains)
        self.key_capacity = new_capacity
        # no jit-cache clearing: the module-level _STEP_CACHE keys on
        # the capacities, so the grown shapes simply compile fresh
        # entries while the old ones stay valid for other kernels

    def _guard_refs(self, refs: np.ndarray, mask: np.ndarray) -> None:
        if mask.any():
            mx = int(refs[mask].max())
            if mx >= self._row_capacity:
                self._grow_rows(next_pow2(mx + 1))

    def _grow_rows(self, new_capacity: int) -> None:
        """Row-array growth: concat padding along the per-shard axis
        (refs index rows directly; nothing remaps)."""
        new_capacity = max(new_capacity, self._row_capacity * 4)
        pad = new_capacity - self._row_capacity

        fresh = empty_chains(0, pad)
        self.chains = self.chains._replace(**{
            f: jnp.concatenate([getattr(self.chains, f),
                                self._stack(getattr(fresh, f))], axis=1)
            for f in ROW_ARRAYS})
        self._row_capacity = new_capacity

    def reserve_rows(self, max_ref: int) -> None:
        if max_ref >= self._row_capacity:
            self._grow_rows(next_pow2(max_ref + 1))

    # -- SPMD step builders ----------------------------------------------
    def _specs(self):
        tspec = jax.tree.map(lambda _: P(AXIS), self.table)
        cspec = jax.tree.map(lambda _: P(AXIS), self.chains)
        return tspec, cspec

    @staticmethod
    def _route(owner_map, lanes, payloads, valid, n_dev, bucket):
        """Shared bucketize+exchange prologue of every local step.

        `lanes` etc. are the LOCAL shard's slice (bucket rows); after
        the all_to_all each shard holds up to n_dev*bucket routed rows
        (worst case: every row keyed to one shard)."""
        owner = owner_map[vnodes_from_lanes(lanes)]
        buckets, bvalid, overflow = bucketize_by_owner(
            owner, valid, [lanes] + payloads, n_dev, bucket)
        recv, rvalid = exchange(buckets, bvalid, AXIS)
        m = n_dev * bucket
        rlanes = recv[0].reshape(m, lanes.shape[1])
        flat = [r.reshape(m) for r in recv[1:]]
        return rlanes, flat, rvalid.reshape(m), overflow

    @staticmethod
    def _global_rows(mat, m: int, rids, rvalid):
        """A shard's `probe_pairs` matrix over its ``m`` routed rows
        (0: it carries no degree rows), in the GLOBAL row ids they were
        routed with: header; (deg, rid) block; (global probe row, ref)
        pairs."""
        at = HEADER_ROWS
        pairs = mat[at + m:]
        gprobe = jnp.where(pairs[:, 0] >= 0,
                           rids[jnp.maximum(pairs[:, 0], 0)],
                           jnp.int32(-1))
        parts = [mat[:at]]
        if m:
            parts.append(jnp.stack(
                [mat[at:at + m, 0],
                 jnp.where(rvalid, rids, jnp.int32(-1))], axis=1))
        parts.append(jnp.stack([gprobe, pairs[:, 1]], axis=1))
        return jnp.concatenate(parts, axis=0)

    def _statics(self) -> tuple:
        """The closure-baked shape statics every step key carries."""
        return (self.key_width, self.key_capacity, self._row_capacity)

    def _build_probe_only(self, bucket: int, out_cap: int):
        key = _step_key(self.mesh, "probe_only", bucket, out_cap,
                        *self._statics())
        step = _STEP_CACHE.get(key)
        if step is not None:
            return step
        n_dev = self.n_dev

        def local(t, c, lanes, rowids, vis, seq, owner_map):
            t = jax.tree.map(lambda a: a[0], t)
            c = jax.tree.map(lambda a: a[0], c)
            rlanes, (rids,), rvalid, ovf = ShardedJoinKernel._route(
                owner_map, lanes, [rowids], vis, n_dev, bucket)
            m = n_dev * bucket
            mat = probe_pairs(t, c, rlanes, rvalid, seq, out_cap)
            out = ShardedJoinKernel._global_rows(mat, m, rids, rvalid)
            return out[None], ovf[None]

        tspec, cspec = self._specs()
        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(tspec, cspec, P(AXIS), P(AXIS), P(AXIS), P(),
                      P()),
            out_specs=(P(AXIS), P(AXIS)),
            check_vma=False)
        step = jaxtools.instrumented_jit(mapped,
                                         "parallel_join.probe")
        _STEP_CACHE[key] = step
        return step

    def _build_delete(self, bucket: int):
        key = _step_key(self.mesh, "delete", bucket, *self._statics())
        step = _STEP_CACHE.get(key)
        if step is not None:
            return step
        n_dev = self.n_dev

        def local(c, lanes, drefs, dmask, seq, owner_map):
            c = jax.tree.map(lambda a: a[0], c)
            _rl, (rdrefs,), rvalid, ovf = ShardedJoinKernel._route(
                owner_map, lanes, [drefs], dmask, n_dev, bucket)
            ch = tombstone_rows(c, rdrefs, rvalid, seq)
            return jax.tree.map(lambda a: a[None], ch), ovf[None]

        tspec, cspec = self._specs()
        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(cspec, P(AXIS), P(AXIS), P(AXIS), P(), P()),
            out_specs=(cspec, P(AXIS)),
            check_vma=False)
        step = jaxtools.instrumented_jit(
            mapped, "parallel_join.delete", donate_argnums=(0,))
        _STEP_CACHE[key] = step
        return step

    def _build_insert(self, bucket: int):
        """Insert-only step (rebuild/insert): route+probe_insert+link."""
        key = _step_key(self.mesh, "insert", bucket, *self._statics())
        step = _STEP_CACHE.get(key)
        if step is not None:
            return step
        n_dev = self.n_dev
        cap = self.key_capacity

        def local(t, c, lanes, refs, vis, seq, owner_map):
            t = jax.tree.map(lambda a: a[0], t)
            c = jax.tree.map(lambda a: a[0], c)
            rlanes, (rrefs,), rvalid, ovf = ShardedJoinKernel._route(
                owner_map, lanes, [refs], vis, n_dev, bucket)
            t2, slots, _ins = ht.probe_insert(t, rlanes, rvalid)
            ch = link_rows(c, slots, rrefs, rvalid, cap, seq)
            return (jax.tree.map(lambda a: a[None], t2),
                    jax.tree.map(lambda a: a[None], ch), ovf[None])

        tspec, cspec = self._specs()
        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(tspec, cspec, P(AXIS), P(AXIS), P(AXIS), P(),
                      P()),
            out_specs=(tspec, cspec, P(AXIS)),
            check_vma=False)
        step = jaxtools.instrumented_jit(
            mapped, "parallel_join.insert", donate_argnums=(0, 1))
        _STEP_CACHE[key] = step
        return step

    # -- epoch batching (ISSUE 10 tentpole) -------------------------------
    # One SPMD dispatch per side per epoch instead of one per chunk:
    # the executor concatenates every chunk of the epoch into the same
    # [key_lanes] + aux matrices the single-chip epoch path ships, and
    # the apply/probe steps below route the WHOLE epoch's rows to their
    # vnode owners in one all_to_all, then run the exact single-chip
    # kernels locally with PER-ROW sequences (sequence visibility makes
    # the batched application order-equivalent to per-chunk applies).

    def _guard_keys_blind(self, n_ins: int) -> None:
        """Conservative key guard when host key lanes are unavailable
        (fused raw uploads: lanes derive in-trace). Every insert could
        route to one shard; a false trip costs one exact-occupancy
        sync, never a false pass — same contract as _guard_keys."""
        if n_ins == 0:
            return
        self._keys_upper = self._keys_upper + n_ins
        limit = ht.MAX_LOAD * self.key_capacity
        if int(self._keys_upper.max()) <= limit:
            return
        per_shard = np.asarray(jnp.sum(self.table.occ, axis=1)) \
            .astype(np.int64)
        need = per_shard + n_ins
        self._keys_upper = need
        worst = int(need.max())
        if worst > limit:
            self._grow_keys(next_pow2(int(worst / ht.MAX_LOAD) + 1))

    def owners_of(self, key_lanes: np.ndarray) -> np.ndarray:
        """Host twin of the device routing, public (the executor
        computes per-epoch owner counts for the skew-exact bucket)."""
        with route_phase("sharded_join"):
            return self._owners_host(np.asarray(key_lanes))

    def stage_epoch(self, up: np.ndarray, aux: np.ndarray, total: int,
                    max_ins_ref: int,
                    owners: Optional[np.ndarray] = None) -> tuple:
        """Host→device staging of one side's epoch batch: run the
        growth guards against the HOST matrices (the device steps are
        fixed-capacity programs), pad rows to a multiple of n_dev
        (pad rows carry flags=0 — routed nowhere, probed never), and
        upload row-sharded. Returns (up_dev, aux_dev, bucket) — the
        arrays feed BOTH this side's apply_epoch and the probe_epoch
        against the other side, exactly two uploads per side per
        epoch.

        ``owners`` (per-row owner shard, from owners_of) makes the
        routing bucket SKEW-EXACT instead of worst-case: the receive
        shape per shard is n_dev*bucket rows, and the default bucket
        (= local rows) has every shard process the WHOLE epoch — n_dev
        times the single-chip compute, which on the CPU virtual mesh
        (devices share one host) was the post-dispatch-tax half of the
        ad-ctr tail. With exact per-(sender, target) counts the bucket
        collapses to ~local/n_dev·(1+skew), pow2-quantized so steady
        state reuses a handful of compiled shapes. Overflow stays
        impossible: the bound is computed, not guessed."""
        n = up.shape[0]
        ins_mask = (aux[:, AUX_FLAGS] & FLAG_INS) != 0
        if up.dtype == np.int64:
            # fused raw matrix: key lanes only exist in-trace
            self._guard_keys_blind(int(ins_mask.sum()))
        else:
            self._guard_keys(up[:, :self.key_width], ins_mask)
        if max_ins_ref >= 0:
            self.reserve_rows(max_ins_ref)
        # mesh-width padding is epoch staging (host_pack); the
        # skew-exact bucket is exchange_route; the row-sharded upload
        # below is h2d
        with LEDGER.phase("host_pack", kernel="sharded_join"):
            m = max(n, self.n_dev)
            if m % self.n_dev:
                m += self.n_dev - (m % self.n_dev)
            if m != n:
                up2 = np.zeros((m, up.shape[1]), dtype=up.dtype)
                up2[:n] = up
                aux2 = np.zeros((m, 4), dtype=np.int32)
                aux2[:n] = aux
                up, aux = up2, aux2
        local = m // self.n_dev
        bucket = local
        with route_phase("sharded_join"):
            flags = aux[:, AUX_FLAGS]
            ow = None
            if owners is not None:
                ow = np.full(m, -1, dtype=np.int64)
                ow[:total] = np.asarray(owners)[:total]
                ow[flags == 0] = -1
                bucket = skew_bucket(ow, ow >= 0, self.n_dev, local)
            # what this batch's two launches route: its apply (this
            # kernel) and its probe (against the OTHER side's kernel).
            # A caller without per-row owners keeps the worst-case
            # bucket and books its launches without rows.
            counts = tuple(
                None if ow is None else
                (int(v.sum()), received_by_shard(ow, v, self.n_dev))
                for v in ((flags & (FLAG_INS | FLAG_DEL)) != 0,
                          (flags & FLAG_PROBE) != 0))
        from risingwave_tpu.utils.ledger import note_backlog
        note_backlog("sharded_join", total)
        up_dev = jaxtools.upload(up, self._sharding,
                                 kernel="sharded_join")
        aux_dev = jaxtools.upload(aux, self._sharding,
                                  kernel="sharded_join")
        self._staged = (aux_dev, counts)
        return up_dev, aux_dev, bucket

    def _book_staged(self, aux_dev, which: int, bucket: int) -> None:
        """Book one exchange launch over a batch THIS kernel staged
        (`which`: 0 its apply, 1 its probe of the other side)."""
        note_launch(self.route_label, self.n_dev, bucket)
        staged = self._staged
        if staged is not None and staged[0] is aux_dev \
                and staged[1][which] is not None:
            note_routed(*staged[1][which])

    def _book_host(self, lanes: np.ndarray, valid: np.ndarray,
                   bucket: int) -> None:
        """Book one exchange launch of the reload and recovery paths
        (probe / insert / delete), whose key lanes are on the host."""
        note_launch(self.route_label, self.n_dev, bucket)
        with route_phase("sharded_join"):
            valid = np.asarray(valid, dtype=bool)
            note_routed(int(valid.sum()), received_by_shard(
                self._owners_host(lanes), valid, self.n_dev))

    def _prelude_for(self, prelude, prelude_key: str):
        """Pin the prelude under its key so cached steps stay valid
        (the step cache closes over the callable via the key)."""
        if prelude is not None:
            self._preludes[prelude_key] = prelude
        return self._preludes.get(prelude_key)

    def _build_epoch_apply(self, bucket: int, width: int, raw: bool,
                           prelude=None, prelude_key: str = ""):
        key = _step_key(self.mesh, "epoch_apply", bucket, width, raw,
                        prelude_key, *self._statics())
        step = _STEP_CACHE.get(key)
        if step is not None:
            return step
        n_dev = self.n_dev
        cap = self.key_capacity
        kw = self.key_width

        def local(t, c, up, aux, owner_map):
            t = jax.tree.map(lambda a: a[0], t)
            c = jax.tree.map(lambda a: a[0], c)
            # the prelude (ops/fused.build_join_prelude) traces the
            # absorbed filter/project run BEFORE vnode routing: the
            # raw local rows become key lanes here, inside the same
            # SPMD step that routes and applies them
            lanes = up[:, :kw] if prelude is None else \
                prelude(up)[:, :kw]
            flags = aux[:, AUX_FLAGS]
            valid = (flags & (FLAG_INS | FLAG_DEL)) != 0
            rlanes, (rins, rdel, rflags, rseq), rvalid, ovf = \
                ShardedJoinKernel._route(
                    owner_map, lanes,
                    [aux[:, AUX_INS_REF], aux[:, AUX_DEL_REF], flags,
                     aux[:, AUX_SEQ]],
                    valid, n_dev, bucket)
            rim = rvalid & ((rflags & FLAG_INS) != 0)
            rdm = rvalid & ((rflags & FLAG_DEL) != 0)
            t2, slots, _ins = ht.probe_insert(t, rlanes, rim)
            ch = link_rows(c, slots, rins, rim, cap, rseq)
            ch = tombstone_rows(ch, rdel, rdm, rseq)
            return (jax.tree.map(lambda a: a[None], t2),
                    jax.tree.map(lambda a: a[None], ch), ovf[None])

        tspec, cspec = self._specs()
        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(tspec, cspec, P(AXIS), P(AXIS), P()),
            out_specs=(tspec, cspec, P(AXIS)),
            check_vma=False)
        step = jaxtools.instrumented_jit(
            mapped, "parallel_join.epoch_apply", donate_argnums=(0, 1))
        _STEP_CACHE[key] = step
        return step

    def apply_epoch(self, up_dev, aux_dev, n_rows: int,
                    max_ins_ref: int, prelude=None,
                    prelude_key: str = "", bucket=None) -> None:
        """Apply a whole epoch's concatenated inserts/tombstones in ONE
        SPMD dispatch (JoinSideKernel.apply_epoch parity; growth guards
        already ran in stage_epoch). Rows carry their message sequence
        in aux[:, AUX_SEQ]; link_rows/tombstone_rows take it per-row.
        ``bucket`` is stage_epoch's skew-exact routing bound (None →
        the overflow-free worst case)."""
        del n_rows, max_ins_ref       # guards ran at stage_epoch
        prelude = self._prelude_for(prelude, prelude_key)
        m = int(up_dev.shape[0])
        if bucket is None:
            bucket = m // self.n_dev
        step = self._build_epoch_apply(
            bucket, int(up_dev.shape[1]), up_dev.dtype == jnp.int64,
            prelude=prelude, prelude_key=prelude_key)
        _note_dispatch(m, "sharded_join")
        self._book_staged(aux_dev, 0, bucket)
        with LEDGER.phase("device_compute", kernel="sharded_join",
                          stage="launch"):
            self.table, self.chains, ovf = step(
                self.table, self.chains, up_dev, aux_dev,
                self.owner_map)
        jaxtools.start_fetch(ovf)
        self._apply_overflows.append(ovf)

    def _build_epoch_probe(self, bucket: int, width: int,
                           out_cap: int, with_degrees: bool,
                           prelude=None, prelude_key: str = ""):
        key = _step_key(self.mesh, "epoch_probe", bucket, width,
                        out_cap, with_degrees, prelude_key,
                        *self._statics())
        step = _STEP_CACHE.get(key)
        if step is not None:
            return step
        n_dev = self.n_dev
        kw = self.key_width

        def local(t, c, up, aux, owner_map):
            t = jax.tree.map(lambda a: a[0], t)
            c = jax.tree.map(lambda a: a[0], c)
            lanes = up[:, :kw] if prelude is None else \
                prelude(up)[:, :kw]
            local_n = lanes.shape[0]
            # global epoch row ids: the executor slices results back
            # into per-chunk order by these (rows are row-sharded
            # before routing, so id = shard offset + local position)
            rowids = (jax.lax.axis_index(AXIS) * local_n
                      + jnp.arange(local_n, dtype=jnp.int32)) \
                .astype(jnp.int32)
            flags = aux[:, AUX_FLAGS]
            pvis = (flags & FLAG_PROBE) != 0
            rlanes, (rids, rseq), rvalid, ovf = \
                ShardedJoinKernel._route(
                    owner_map, lanes, [rowids, aux[:, AUX_SEQ]],
                    pvis, n_dev, bucket)
            m = n_dev * bucket
            mat = probe_pairs(t, c, rlanes, rvalid, rseq, out_cap,
                              with_degrees=with_degrees)
            out = ShardedJoinKernel._global_rows(
                mat, m if with_degrees else 0, rids, rvalid)
            return out[None], ovf[None]

        tspec, cspec = self._specs()
        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(tspec, cspec, P(AXIS), P(AXIS), P()),
            out_specs=(P(AXIS), P(AXIS)),
            check_vma=False)
        step = jaxtools.instrumented_jit(
            mapped, "parallel_join.epoch_probe")
        _STEP_CACHE[key] = step
        return step

    def probe_epoch(self, up_dev, aux_dev, with_degrees: bool,
                    sink=None, prelude=None, prelude_key: str = "",
                    bucket=None) -> "ShardedPendingEpochProbe":
        """Probe a whole epoch's rows against THIS side — each row at
        its aux sequence — in one SPMD dispatch. `sink` is the PROBING
        side's kernel (JoinSideKernel API parity): the sharded path
        keeps degrees host-side (the executor's replay arrays), so the
        probe only RETURNS per-row degrees and maintains no device
        store; the sink staged the batch, so the launch goes into the
        exchange's books under its name.
        ``bucket`` is the PROBING side's stage_epoch bound (the same
        rows route by the same keys)."""
        prelude = self._prelude_for(prelude, prelude_key)
        m = int(up_dev.shape[0])
        if bucket is None:
            bucket = m // self.n_dev
        out_cap = self.probe_capacity
        width = int(up_dev.shape[1])

        def dispatch(cap):
            step = self._build_epoch_probe(
                bucket, width, cap, with_degrees,
                prelude=prelude, prelude_key=prelude_key)
            _note_dispatch(m, "sharded_join")
            (sink or self)._book_staged(aux_dev, 1, bucket)
            with LEDGER.phase("device_compute", stage="launch",
                              kernel="sharded_join"):
                mats, ovf = step(self.table, self.chains, up_dev,
                                 aux_dev, self.owner_map)
            jaxtools.start_fetch(mats)
            return mats, ovf

        mats, ovf = dispatch(out_cap)
        return ShardedPendingEpochProbe(self, mats, m, out_cap,
                                        with_degrees, dispatch,
                                        overflow=ovf)

    def drain_overflows(self) -> None:
        """Fold in the lazily-checked apply-step overflow flags (the
        condition is impossible by construction — bucket = local rows
        — so this is an assertion, surfaced at the barrier)."""
        flags, self._apply_overflows = self._apply_overflows, []
        for f in flags:
            if bool(np.asarray(jaxtools.fetch1(f)).any()):
                raise RuntimeError(
                    "bucket overflow routing epoch join rows")

    # -- host API (JoinSideKernel parity) ---------------------------------
    def _pad(self, arrs, n: int):
        """Pad host arrays to a multiple of n_dev rows."""
        m = max(self.n_dev, n)
        if m % self.n_dev:
            m += self.n_dev - (m % self.n_dev)
        if m == n:
            return arrs, n
        out = []
        for a in arrs:
            a = np.asarray(a)
            pad_shape = (m - n,) + a.shape[1:]
            out.append(np.concatenate(
                [a, np.zeros(pad_shape, dtype=a.dtype)]))
        return out, m

    def _dispatch_probe(self, lanes: np.ndarray, vis: np.ndarray,
                        seq: int, out_cap: int):
        m = int(lanes.shape[0])
        bucket = m // self.n_dev
        step = self._build_probe_only(bucket, out_cap)
        _note_dispatch(m, "sharded_join")
        self._book_host(lanes, vis, bucket)
        with LEDGER.phase("device_compute", kernel="sharded_join",
                          stage="launch"):
            mats, overflow = step(self.table, self.chains,
                                  jnp.asarray(lanes),
                                  jnp.arange(m, dtype=jnp.int32),
                                  jnp.asarray(vis), jnp.int32(seq),
                                  self.owner_map)
        # overflow is impossible by construction (bucket = local rows)
        # but still checked lazily at collect — never synced here
        jaxtools.start_fetch(mats)
        return mats, overflow

    def probe_submit(self, key_lanes, vis,
                     seq: Optional[int] = None) -> ShardedPendingProbe:
        n = int(np.asarray(key_lanes).shape[0])
        s = I32_MAX if seq is None else seq
        (lanes, pv), _m = self._pad(
            [np.asarray(key_lanes), np.asarray(vis)], n)
        mats, overflow = self._dispatch_probe(lanes, pv, s,
                                              self.probe_capacity)
        return ShardedPendingProbe(self, mats, lanes, pv, s,
                                   self.probe_capacity, n,
                                   overflow=overflow)

    def probe(self, key_lanes, vis, seq: Optional[int] = None):
        return self.probe_submit(key_lanes, vis, seq).collect()

    def insert(self, key_lanes: np.ndarray, row_refs: np.ndarray,
               vis: np.ndarray, seq: int = 0) -> None:
        """Routed batch insert (recovery/rebuild; tests)."""
        key_lanes = np.asarray(key_lanes)
        vis = np.asarray(vis)
        n = int(key_lanes.shape[0])
        self._guard_keys(key_lanes, vis)
        self._guard_refs(np.asarray(row_refs), vis)
        (lanes, refs_, mask), m = self._pad(
            [key_lanes, np.asarray(row_refs, np.int32), vis], n)
        bucket = m // self.n_dev
        step = self._build_insert(bucket)
        _note_dispatch(m, "sharded_join")
        self._book_host(lanes, mask, bucket)
        self.table, self.chains, overflow = step(
            self.table, self.chains, jnp.asarray(lanes),
            jnp.asarray(refs_), jnp.asarray(mask), jnp.int32(seq),
            self.owner_map)
        if bool(np.asarray(overflow).any()):
            raise RuntimeError("bucket overflow inserting join rows")

    def delete(self, row_refs: np.ndarray, vis,
               seq: int = 0, key_lanes=None) -> None:
        """Tombstone by ref. Sharded routing needs the refs' KEY lanes
        (the owner shard is a function of the key) — callers pass them
        (the single-chip kernel ignores its optional param)."""
        assert key_lanes is not None, \
            "sharded delete requires key_lanes for routing"
        vis = np.asarray(vis)
        n = int(np.asarray(key_lanes).shape[0])
        (lanes, drefs, dm), m = self._pad(
            [np.asarray(key_lanes), np.asarray(row_refs, np.int32),
             vis], n)
        bucket = m // self.n_dev
        step = self._build_delete(bucket)
        _note_dispatch(m, "sharded_join")
        self._book_host(lanes, dm, bucket)
        self.chains, overflow = step(
            self.chains, jnp.asarray(lanes), jnp.asarray(drefs),
            jnp.asarray(dm), jnp.int32(seq), self.owner_map)
        if bool(np.asarray(overflow).any()):
            raise RuntimeError("bucket overflow routing join deletes")

    def rebase_seq(self) -> None:
        mx = jnp.int32(I32_MAX)
        self.chains = self.chains._replace(
            ins_seq=jnp.where(self.chains.ins_seq == mx, mx,
                              jnp.int32(0)),
            del_seq=jnp.where(self.chains.del_seq == mx, mx,
                              jnp.int32(0)))

    def rebuild(self, key_lanes: np.ndarray,
                row_refs: np.ndarray) -> None:
        """Reload all live rows (recovery/compaction): fresh sharded
        state + one routed batch insert at seq 0.

        Per-shard key capacity is sized to hold ALL n keys (worst-case
        skew: one shard owns every key) — a per-shard table that only
        fits n/n_dev keys would corrupt chains under adversarial key
        distributions, and the capacity guard compares a GLOBAL unique
        bound against the per-shard limit anyway."""
        n = len(row_refs)
        while n and int(np.max(row_refs)) >= self._row_capacity:
            self._row_capacity *= 2
        need_keys = ht.MIN_CAPACITY if n == 0 else 1 << int(np.ceil(
            np.log2(max(n / ht.MAX_LOAD, 1))))
        self.key_capacity = max(self.key_capacity, need_keys,
                                ht.MIN_CAPACITY)
        self._fresh_state()
        self._keys_upper = np.zeros(self.n_dev, dtype=np.int64)
        if n == 0:
            return
        self.insert(key_lanes, row_refs, np.ones(n, dtype=bool), seq=0)
