"""HashJoinExecutor over the 8-device virtual mesh (ShardedJoinKernel)
must be indistinguishable from the single-chip kernel — the wiring
VERDICT r3 #3 required: sharded joins reachable from the executor (and
through planner.mesh from SQL), including retractions, outer degrees,
watermark expiry, and recovery.
"""

import asyncio
from collections import Counter

import numpy as np
import pytest
from jax.sharding import Mesh

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.parallel.join import ShardedJoinKernel
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.stream.executors.hash_join import (
    HashJoinExecutor, JoinType,
)
from risingwave_tpu.stream.executors.test_utils import (
    MockSource, collect_until_n_barriers,
)
from risingwave_tpu.stream.message import is_chunk

from test_hash_join import (  # noqa: F401  (reuse the harness)
    JoinOracle, L_SCHEMA, R_SCHEMA, barrier, lchunk, materialize_join,
    rchunk,
)


def run_join_mesh(mesh, script_l, script_r, n_barriers,
                  join_type=JoinType.INNER, store=None):
    store = store or MemoryStateStore()
    lt = StateTable(21, L_SCHEMA, [1], store, dist_key_indices=[])
    rt = StateTable(22, R_SCHEMA, [1], store, dist_key_indices=[])
    ex = HashJoinExecutor(
        MockSource(L_SCHEMA, script_l), MockSource(R_SCHEMA, script_r),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt,
        join_type=join_type, mesh=mesh)
    msgs = asyncio.run(collect_until_n_barriers(ex, n_barriers))
    return msgs, (lt, rt, store), ex


def _random_scripts(seed):
    rng = np.random.default_rng(seed)
    oracle = JoinOracle()
    script_l, script_r = [barrier(1)], [barrier(1)]
    b = 2
    lpk, rpk = 0, 0
    for _ in range(5):
        ks, vs, ops = [], [], []
        for _ in range(24):
            if oracle.left and rng.random() < 0.3:
                i = int(rng.integers(0, len(oracle.left)))
                k_, v_ = oracle.left.pop(i)
                ks.append(k_); vs.append(v_); ops.append(Op.DELETE)
            else:
                k_, v_ = int(rng.integers(0, 8)), lpk
                lpk += 1
                oracle.left.append((k_, v_))
                ks.append(k_); vs.append(v_); ops.append(Op.INSERT)
        script_l.append(lchunk(ks, vs, ops=ops))
        ks, vs, ops = [], [], []
        for _ in range(16):
            if oracle.right and rng.random() < 0.3:
                i = int(rng.integers(0, len(oracle.right)))
                k_, v_ = oracle.right.pop(i)
                ks.append(k_); vs.append(v_); ops.append(Op.DELETE)
            else:
                k_, v_ = int(rng.integers(0, 8)), f"r{rpk}"
                rpk += 1
                oracle.right.append((k_, v_))
                ks.append(k_); vs.append(v_); ops.append(Op.INSERT)
        script_r.append(rchunk(ks, vs, ops=ops))
        script_l.append(barrier(b))
        script_r.append(barrier(b))
        b += 1
    return script_l, script_r, b - 1, oracle


def test_sharded_join_executor_random_oracle(eight_devices):
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    script_l, script_r, nb, oracle = _random_scripts(23)
    msgs, _t, ex = run_join_mesh(mesh, script_l, script_r, nb)
    assert isinstance(ex.sides[0].kernel, ShardedJoinKernel)
    assert materialize_join(msgs) == oracle.view()


def test_sharded_left_outer_degrees(eight_devices):
    """Degree transitions (NULL-padding flips) through the sharded
    matcher: the deg block rides the same packed matrix."""
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    script_l = [barrier(1), lchunk([1, 2], [10, 20]), barrier(2),
                barrier(3)]
    script_r = [barrier(1), barrier(2), rchunk([1], ["a"]), barrier(3)]
    msgs, _t, _ex = run_join_mesh(mesh, script_l, script_r, 3,
                                  join_type=JoinType.LEFT_OUTER)
    got = materialize_join(msgs)
    assert got == Counter({(1, 10, 1, "a"): 1,
                           (2, 20, None, None): 1})


def test_sharded_join_watermark_expiry(eight_devices):
    """State expiry routes tombstones to the owning shard by key."""
    from risingwave_tpu.stream.message import Watermark

    mesh = Mesh(np.asarray(eight_devices), ("d",))
    wm = lambda v: Watermark(0, DataType.INT64, v)  # noqa: E731
    script_l = [barrier(1), lchunk([1, 5, 9], [10, 50, 90]), wm(6),
                barrier(2),
                lchunk([], []), barrier(3)]
    script_r = [barrier(1), rchunk([9], ["i"]), wm(8), barrier(2),
                rchunk([1, 5, 9], ["a2", "e2", "i2"]), barrier(3)]
    msgs, (lt, rt, _s), _ex = run_join_mesh(mesh, script_l, script_r, 3)
    got = materialize_join(msgs)
    # keys 1 and 5 expired at barrier 2 (combined wm=6): the epoch-3
    # right rows for them find nothing; key 9 still matches
    assert got == Counter({(9, 90, 9, "i"): 1, (9, 90, 9, "i2"): 1})
    assert sorted(r[0] for _pk, r in lt.iter_rows()) == [9]


def test_sharded_join_recovery_resumes(eight_devices):
    """Mirror of tests/test_multichip_agg recovery: kill the executor,
    rebuild from the state tables onto the SHARDED kernel, degrees
    recomputed by one routed batch probe."""
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    store = MemoryStateStore()

    def build(sl, sr, jt):
        lt = StateTable(21, L_SCHEMA, [1], store, dist_key_indices=[])
        rt = StateTable(22, R_SCHEMA, [1], store, dist_key_indices=[])
        return HashJoinExecutor(
            MockSource(L_SCHEMA, sl), MockSource(R_SCHEMA, sr),
            left_keys=[0], right_keys=[0], left_table=lt,
            right_table=rt, join_type=jt, mesh=mesh)

    ex1 = build([barrier(1), lchunk([1, 2], [10, 20]), barrier(2)],
                [barrier(1), rchunk([1], ["a"]), barrier(2)],
                JoinType.LEFT_OUTER)
    msgs1 = asyncio.run(collect_until_n_barriers(ex1, 2))
    view = materialize_join(msgs1)
    assert view == Counter({(1, 10, 1, "a"): 1, (2, 20, None, None): 1})
    # restart: new right rows — recovered left rows must match, and the
    # recovered DEGREE of row (1,10) must suppress a duplicate padded
    # retraction while (2,20) flips off its NULL padding
    ex2 = build([barrier(3), barrier(4)],
                [barrier(3), rchunk([2], ["b"]), barrier(4)],
                JoinType.LEFT_OUTER)
    assert isinstance(ex2.sides[0].kernel, ShardedJoinKernel)
    msgs2 = asyncio.run(collect_until_n_barriers(ex2, 2))
    for m in msgs2:
        if is_chunk(m):
            view.update({tuple(r): (1 if op.is_insert else -1)
                         for op, r in m.to_records()})
    view = +Counter({k: v for k, v in view.items() if v})
    assert view == Counter({(1, 10, 1, "a"): 1, (2, 20, 2, "b"): 1})


def test_sharded_probe_overflow_retries(eight_devices):
    """Tiny per-shard pair buffer forces the double/retry re-dispatch."""
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    k = ShardedJoinKernel(mesh, key_width=1, probe_capacity=1)
    lanes = np.asarray([[3]] * 9 + [[4]] * 7, dtype=np.int32)
    refs = np.arange(16, dtype=np.int32)
    k.insert(lanes, refs, np.ones(16, dtype=bool), seq=1)
    probe = np.asarray([[3], [4], [5], [6]], dtype=np.int32)
    deg, pidx, prefs = k.probe(probe, np.ones(4, dtype=bool))
    # doubled from 1 until the longest chain's candidates fit
    assert k.probe_capacity == 16
    assert deg.tolist() == [9, 7, 0, 0]
    assert {int(r) for p, r in zip(pidx, prefs) if p == 0} == \
        set(range(9))
    assert {int(r) for p, r in zip(pidx, prefs) if p == 1} == \
        set(range(9, 16))


def test_sql_join_runs_sharded(eight_devices):
    """The SQL path reaches the sharded JOIN kernel (VERDICT r3 #3): a
    parallelism=8 session plans q8-shaped joins onto ShardedJoinKernel
    and the MV matches the parallelism=1 result exactly."""
    from risingwave_tpu.frontend.session import Frontend

    sql = [
        "CREATE SOURCE person WITH (connector='nexmark', "
        "nexmark.table.type='person', nexmark.event.num=20000, "
        "nexmark.min.event.gap.in.ns=100000000)",
        "CREATE SOURCE auction WITH (connector='nexmark', "
        "nexmark.table.type='auction', nexmark.event.num=20000, "
        "nexmark.min.event.gap.in.ns=100000000)",
        "CREATE MATERIALIZED VIEW q8 AS SELECT p.id, p.name, a.seller "
        "FROM person AS p JOIN auction AS a ON p.id = a.seller",
    ]

    def _walk(ex):
        out = []
        if hasattr(ex, "sides"):
            out.append(ex)
        for attr in ("input", "left_in", "right_in"):
            child = getattr(ex, attr, None)
            if child is not None:
                out.extend(_walk(child))
        return out

    async def run(parallelism):
        f = Frontend(rate_limit=4, min_chunks=8,
                     parallelism=parallelism)
        for s in sql:
            await f.execute(s)
        for _ in range(10):
            await f.step()
        rows = await f.execute("SELECT * FROM q8")
        if parallelism > 1:
            joins = [j for actor in f.actors.values()
                     for j in _walk(actor.consumer)]
            assert joins and all(
                isinstance(j.sides[0].kernel, ShardedJoinKernel)
                for j in joins), "join plan was not sharded"
        await f.close()
        return sorted({r[:3] for r in rows})

    got = asyncio.run(run(8))
    want = asyncio.run(run(1))
    assert got == want
    assert len(got) > 0


def test_sharded_join_grows_past_initial_capacity(eight_devices):
    """Join state 10x the initial sharded key capacity: barrier-time
    compact-with-growth replaces the fatal guard (VERDICT r3 #5)."""
    mesh = Mesh(np.asarray(eight_devices), ("d",))
    store = MemoryStateStore()
    lt = StateTable(61, L_SCHEMA, [1], store, dist_key_indices=[])
    rt = StateTable(62, R_SCHEMA, [1], store, dist_key_indices=[])
    script_l, script_r = [barrier(1)], [barrier(1)]
    oracle = JoinOracle()
    b = 2
    n_keys = 1280                    # 10x key_capacity=128
    for r in range(10):
        ks = list(range(r * 128, (r + 1) * 128))
        vs = ks
        oracle.left += list(zip(ks, vs))
        script_l.append(lchunk(ks, vs))
        script_l.append(barrier(b))
        # right side joins a few of this round's keys
        rk = ks[:4]
        rv = [f"r{x}" for x in rk]
        oracle.right += list(zip(rk, rv))
        script_r.append(rchunk(rk, rv))
        script_r.append(barrier(b))
        b += 1
    ex = HashJoinExecutor(
        MockSource(L_SCHEMA, script_l), MockSource(R_SCHEMA, script_r),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt,
        mesh=mesh,
        shard_opts=dict(key_capacity=128, row_capacity=1 << 12,
                        probe_capacity=256))
    msgs = asyncio.run(collect_until_n_barriers(ex, b - 1))
    assert ex.sides[0].kernel.key_capacity > 128      # grew
    assert materialize_join(msgs) == oracle.view()
