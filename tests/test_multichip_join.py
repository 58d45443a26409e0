"""Vnode-sharded join matcher over the 8-device virtual mesh ==
single-chip kernel results (the q8 analog of test_multichip_agg)."""

from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from risingwave_tpu.ops import lanes
from risingwave_tpu.ops.hash_join import JoinSideKernel
from risingwave_tpu.parallel.join import ShardedJoinKernel


def test_sharded_join_matches_single_chip(eight_devices):
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    sharded = ShardedJoinKernel(mesh, key_width=2, key_capacity=1 << 10,
                              row_capacity=1 << 10,
                              probe_capacity=1 << 10)
    single = JoinSideKernel(key_width=2)

    rng = np.random.default_rng(11)
    next_ref = 0
    for _round in range(3):
        n = 64
        keys = rng.integers(0, 23, n).astype(np.int64) * 5_000_000_017
        hi, lo = lanes.split_i64(keys)
        kl = np.stack([hi, lo], axis=1)
        refs = np.arange(next_ref, next_ref + n, dtype=np.int32)
        next_ref += n
        vis = rng.random(n) > 0.15
        sharded.insert(kl, refs, vis)
        single.insert(jnp.asarray(kl), refs, jnp.asarray(vis))

        pk = rng.integers(0, 30, 64).astype(np.int64) * 5_000_000_017
        phi, plo = lanes.split_i64(pk)
        pkl = np.stack([phi, plo], axis=1)
        pvis = np.ones(64, dtype=bool)
        _gdeg, gp, gr = sharded.probe(pkl, pvis)
        deg, sp, sr = single.probe(jnp.asarray(pkl), jnp.asarray(pvis))

        got = defaultdict(set)
        for p, r in zip(gp.tolist(), gr.tolist()):
            got[p].add(r)
        want = defaultdict(set)
        for p, r in zip(sp.tolist(), sr.tolist()):
            want[p].add(r)
        assert got == want
        assert sum(len(v) for v in got.values()) == int(deg.sum())


def test_sharded_join_walks_a_hot_keys_runs_in_chain_order(eight_devices):
    """A hot key among quiet ones, linked in three batches and partly
    tombstoned: every shard's probe steps its keys' runs, and the pairs
    come back as the single-chip kernel gives them, order and all
    (newest batch first, batch order inside a batch); a buffer too
    small for a shard's candidates doubles until they fit."""
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    sharded = ShardedJoinKernel(mesh, key_width=2, key_capacity=1 << 10,
                                row_capacity=1 << 10, probe_capacity=8)
    single = JoinSideKernel(key_width=2)
    rng = np.random.default_rng(13)

    def lanes_of(keys):
        hi, lo = lanes.split_i64(np.asarray(keys, dtype=np.int64)
                                 * 5_000_000_017)
        return np.stack([hi, lo], axis=1)

    ref = 0
    for seq in (1, 2, 3):
        keys = np.where(rng.random(160) < 0.6, 11,
                        rng.integers(100, 140, 160))
        refs = np.arange(ref, ref + 160, dtype=np.int32)
        ref += 160
        vis = np.ones(160, dtype=bool)
        sharded.insert(lanes_of(keys), refs, vis, seq=seq)
        single.insert(jnp.asarray(lanes_of(keys)), refs,
                      jnp.asarray(vis), seq=seq)
        dead = rng.choice(160, size=40, replace=False)
        sharded.delete(refs[dead], np.ones(40, dtype=bool), seq=seq + 1,
                       key_lanes=lanes_of(keys[dead]))
        single.delete(refs[dead], jnp.ones(40, dtype=bool), seq=seq + 1)
    probes = lanes_of([11, 100, 11, 999] + list(range(100, 140)))
    pvis = np.ones(len(probes), dtype=bool)
    for seq in (2, 3, 5):
        gdeg, gp, gr = sharded.probe(probes, pvis, seq=seq)
        deg, sp, sr = single.probe(jnp.asarray(probes),
                                   jnp.asarray(pvis), seq=seq)
        np.testing.assert_array_equal(gp, sp)
        np.testing.assert_array_equal(gr, sr)
        np.testing.assert_array_equal(gdeg, deg)
    assert sharded.probe_capacity > 8
    assert len(sp) > 100


def test_sharded_join_state_is_sharded(eight_devices):
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    s = ShardedJoinKernel(mesh, key_width=2, key_capacity=1 << 10)
    specs = {str(a.sharding.spec) for a in
             [s.table.keys, s.chains.head, s.chains.store]}
    assert all("'d'" in x for x in specs), specs


def test_sharded_join_recurring_keys_do_not_trip_guard(eight_devices):
    """Keys recurring across many batches must NOT hit the capacity
    guard: the bound collapses to true occupancy on overflow."""
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    s = ShardedJoinKernel(mesh, key_width=2, key_capacity=256,
                        row_capacity=1 << 14)
    ref = 0
    for _ in range(40):                  # 40*64 rows, only 10 keys
        keys = (np.arange(64, dtype=np.int64) % 10) * 999_999_937
        hi, lo = lanes.split_i64(keys)
        kl = np.stack([hi, lo], axis=1)
        refs = np.arange(ref, ref + 64, dtype=np.int32)
        ref += 64
        s.insert(kl, refs, np.ones(64, dtype=bool))
    _d, gp, _gr = s.probe(kl, np.ones(64, dtype=bool))
    assert len(gp) > 0
