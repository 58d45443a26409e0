"""On-device hash dispatch: vnode bucketize + all_to_all.

Reference parity: DispatcherType::HASH (src/stream/src/executor/
dispatch.rs:582-690) — rows route by hash(dist key) → vnode → owner. The
reference serializes per-downstream chunks onto gRPC; here the exchange is
a single ``jax.lax.all_to_all`` over ICI: each shard bucketizes its rows
by target shard into a fixed [n_dev, bucket] send tensor, the collective
transposes it, and every shard receives exactly the rows it owns.

Static shapes (XLA contract): `bucket` bounds rows-per-target per step.
The default bucket (local row count) makes overflow impossible by
construction; a caller shrinking it trades bandwidth for a fatal-on-skew
contract — the overflow flag fires AFTER the step has applied the
surviving rows, so it is an assertion, not a retry point. All lanes are
int32 (ops/lanes.py rationale).

The exchange keeps books (ISSUE 27). The HOST routing in front of it
(`owners_host`, `skew_bucket`, the bucket choice) runs under the ledger
phase ``exchange_route`` (`route_phase`), and every SPMD launch that
holds an exchange is counted where it is launched (`note_launch`,
`note_routed`): launches, the row slots its all_to_alls carried
(n_dev x n_dev x bucket, rows plus padding), the rows the senders
routed, the rows each shard received, and the bucket in use per
kernel. They reach `rw_metrics_history` per barrier as
``mesh_exchange.*`` (utils/metrics.MetricsHistory). Every live sharded
kernel is in `MESH_KERNELS`; `mesh_table_rows` is the payload of
`rw_mesh_tables` (per-shard occupancy and capacity of its tables).
"""

from __future__ import annotations

import weakref
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import next_pow2
from risingwave_tpu.common.hash import VNODE_COUNT
from risingwave_tpu.ops.hash_table import hash_key_lanes
from risingwave_tpu.utils.ledger import LEDGER

# every live sharded kernel (they add themselves when built): what
# `rw_mesh_tables` reads
MESH_KERNELS: "weakref.WeakSet" = weakref.WeakSet()


def route_phase(kernel: str):
    """Ledger scope of the host routing in front of an exchange: the
    owner of every row, the skew-exact bucket, the bucket choice."""
    return LEDGER.phase("exchange_route", kernel=kernel)


def route_label(kind: str, table_id: Optional[int]) -> str:
    """What a sharded kernel's exchanges go by in the books: its kind
    and the state table it serves (`rw_state_topology`'s table_id),
    which tells two aggregates, or two join sides, of one view apart."""
    return kind if table_id is None else f"{kind}.t{table_id}"


def note_launch(kernel: str, n_dev: int, bucket: int) -> None:
    """One SPMD launch holding an exchange, counted at the launch."""
    from risingwave_tpu.utils.metrics import STREAMING as S
    S.mesh_exchange_launches.inc(1, kernel=kernel)
    S.mesh_exchange_slots.inc(float(n_dev * n_dev * bucket),
                              kernel=kernel)
    S.mesh_exchange_bucket.set(float(bucket), kernel=kernel)


def note_routed(routed: int, received: np.ndarray) -> None:
    """The rows the senders of one launch routed, and the rows each
    shard received of them. Two counts from two sides of the same
    exchange: they add up to the same number, or rows were lost."""
    from risingwave_tpu.utils.metrics import STREAMING as S
    S.mesh_exchange_rows_routed.inc(float(routed))
    for shard, n in enumerate(np.asarray(received).tolist()):
        if n:
            S.mesh_exchange_rows_received.inc(float(n),
                                              shard=str(shard))


def received_by_shard(owner: np.ndarray, valid: np.ndarray,
                      n_dev: int) -> np.ndarray:
    """Rows each shard receives of a staged batch, from the host twin
    of the device routing."""
    return np.bincount(np.asarray(owner)[valid], minlength=n_dev)


def mesh_table_rows() -> List[tuple]:
    """`rw_mesh_tables` payload: (table_id, mv, kernel, part, shard,
    occupied, capacity) per shard of every live sharded kernel's
    device tables. Reading blocks on the device once per table."""
    from risingwave_tpu.state.topology import TOPOLOGY
    rows = []
    for k in list(MESH_KERNELS):
        tid = -1 if k.table_id is None else int(k.table_id)
        mv = TOPOLOGY.mv_of(tid) if tid >= 0 else ""
        for part, occupied, capacity in k.shard_tables():
            for shard, occ in enumerate(np.asarray(occupied).tolist()):
                rows.append((tid, mv, k.route_label, part, shard,
                             int(occ), int(capacity)))
    return sorted(rows)


def vnodes_from_lanes(key_lanes: jnp.ndarray) -> jnp.ndarray:
    """int32 vnode in [0, 256) from int32 key lanes (device twin of
    common.hash.vnodes_of for pre-split lanes)."""
    return (hash_key_lanes(key_lanes)
            & jnp.uint32(VNODE_COUNT - 1)).astype(jnp.int32)


def owners_host(key_lanes: np.ndarray,
                owner_map_host: np.ndarray) -> np.ndarray:
    """HOST twin of the device routing above (same hash → same owner)
    — the ONE copy both sharded kernels use for capacity guards and
    the skew-exact bucket; drifting from `vnodes_from_lanes` would
    silently break the overflow-impossible contract."""
    from risingwave_tpu.common.hash import hash_columns_host
    lanes = np.asarray(key_lanes)
    h = hash_columns_host([lanes[:, i] for i in range(lanes.shape[1])])
    return owner_map_host[
        (h & np.uint32(VNODE_COUNT - 1)).astype(np.int64)]


def skew_bucket(owner: np.ndarray, mask: np.ndarray, n_dev: int,
                local: int) -> int:
    """Skew-exact per-(sender, target) routing bound for one staged
    batch of n_dev*local row-sharded rows: the all_to_all receive
    shape is n_dev*bucket rows per shard, and the conservative
    default (bucket = local) makes every shard process the WHOLE
    batch — n_dev× the single-chip compute. Exact bincounts collapse
    it to the real skew; the result is pow2-quantized on a coarse
    3-step ladder (local/n_dev … local) so steady state reuses a
    handful of compiled shapes. Overflow stays impossible: the bound
    is computed, not guessed."""
    worst = 1
    for s in range(n_dev):
        sl = owner[s * local:(s + 1) * local]
        sl = sl[mask[s * local:(s + 1) * local]]
        if len(sl):
            worst = max(worst, int(np.bincount(
                sl, minlength=n_dev).max()))
    return min(local, max(local // n_dev, next_pow2(worst)))


def bucketize_by_owner(owner: jnp.ndarray, valid: jnp.ndarray,
                       payloads: Sequence[jnp.ndarray], n_dev: int,
                       bucket: int
                       ) -> Tuple[List[jnp.ndarray], jnp.ndarray,
                                  jnp.ndarray]:
    """Pack rows into per-target buckets for an all_to_all.

    owner: int32[N] target shard per row; valid: bool[N].
    payloads: arrays [N] or [N, K] to route alongside.
    Returns (bucketized payloads each [n_dev, bucket, ...],
             valid [n_dev, bucket], overflowed bool scalar).
    Row order within a bucket preserves input order (determinism).
    """
    n = owner.shape[0]
    onehot = (owner[:, None] == jnp.arange(n_dev, dtype=jnp.int32)[None, :]
              ) & valid[:, None]                          # [N, n_dev]
    pos_all = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    row_pos = jnp.sum(jnp.where(onehot, pos_all, 0), axis=1)   # [N]
    fits = valid & (row_pos < bucket)
    dest = jnp.where(fits, owner * bucket + row_pos, n_dev * bucket)
    out = []
    for p in payloads:
        flat_shape = (n_dev * bucket,) + p.shape[1:]
        buf = jnp.zeros(flat_shape, dtype=p.dtype).at[dest].set(
            p, mode="drop")
        out.append(buf.reshape((n_dev, bucket) + p.shape[1:]))
    vbuf = jnp.zeros(n_dev * bucket, dtype=bool).at[dest].set(
        valid, mode="drop").reshape(n_dev, bucket)
    overflowed = jnp.any(valid & ~fits)
    return out, vbuf, overflowed


def exchange(bucketized: Sequence[jnp.ndarray], valid: jnp.ndarray,
             axis_name: str
             ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """The ICI collective: transpose [n_dev, bucket, ...] buckets so
    shard i receives every shard's bucket-for-i (dispatch.rs's gRPC
    exchange as one all_to_all)."""
    out = [jax.lax.all_to_all(p, axis_name, split_axis=0, concat_axis=0)
           for p in bucketized]
    v = jax.lax.all_to_all(valid, axis_name, split_axis=0, concat_axis=0)
    return out, v
