"""Device-resident join payloads (ISSUE 9): the device-emit path must
be indistinguishable from the host arena gather it replaces.

The on arm stores every device-typed payload column as HBM lanes
(ops/hash_join.py pay) and materializes matched rows from the packed
probe matrix; the off arm (``device_payload=False``) forces the
pre-existing arena gather. Both arms run the same scripts and their
EMITTED MESSAGE STREAMS must be bit-identical — not just the final
materialization — across all 8 join types, NULL-padded outer rows,
degree flips, retractions, float bit-patterns, NULL payload values,
and a varchar payload column forcing the mixed device/host emit.
Crash-recovery must rebuild the payload lanes exactly where it
rebuilds chains, and the cold tier must evict/reload a device-resident
side bit-identically.
"""

import asyncio

import numpy as np
import pytest

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.stream.executors.hash_join import (
    HashJoinExecutor, JoinType,
)
from risingwave_tpu.stream.executors.test_utils import (
    MockSource, collect_until_n_barriers,
)
from risingwave_tpu.stream.message import Barrier, BarrierKind, is_chunk

# varchar column forces the MIXED emit (device lanes for lv/lf, arena
# gather for ls); float64 column checks bit-preserving payload codecs
L = Schema.of(lk=DataType.INT64, lv=DataType.INT64,
              ls=DataType.VARCHAR, lf=DataType.FLOAT64)
R = Schema.of(rk=DataType.INT64, rv=DataType.INT64,
              rs=DataType.VARCHAR)


def barrier(n: int) -> Barrier:
    prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
    return Barrier(EpochPair(Epoch.from_physical(n), prev),
                   BarrierKind.CHECKPOINT)


def lchunk(ks, vs, ss=None, fs=None, ops=None):
    n = len(ks)
    return StreamChunk.from_pydict(L, {
        "lk": ks, "lv": vs,
        "ls": ss if ss is not None else [f"s{v}" for v in vs],
        "lf": fs if fs is not None else [float(v) for v in vs],
    }, ops=ops)


def rchunk(ks, vs, ss=None, ops=None):
    return StreamChunk.from_pydict(R, {
        "rk": ks, "rv": vs,
        "rs": ss if ss is not None else [f"r{v}" for v in vs],
    }, ops=ops)


def records(msgs):
    out = []
    for m in msgs:
        if is_chunk(m):
            out.extend(m.to_records())
    return out


def run(jt, script_l, script_r, n_barriers, store=None, ids=(161, 162),
        device_payload=True, state_cap=None):
    store = store or MemoryStateStore()
    # state-table pks prefixed by the join key (the cold-tier contract)
    lt = StateTable(ids[0], L, [0, 1], store, dist_key_indices=[])
    rt = StateTable(ids[1], R, [0, 1], store, dist_key_indices=[])
    ex = HashJoinExecutor(
        MockSource(L, script_l), MockSource(R, script_r),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt,
        join_type=jt, device_payload=device_payload,
        state_cap=state_cap)
    msgs = asyncio.run(collect_until_n_barriers(ex, n_barriers))
    return msgs, store


def scripts_scripted():
    """Every transition: unmatched insert, late match (0→1 flip), N:M
    growth, retraction back to unmatched (→0 flip), NULL join keys,
    NULL payload values, float bit-patterns (-0.0), update pairs."""
    script_l = [
        barrier(1),
        lchunk([1, 2, None], [10, 20, 30],
               ss=["a", None, "c"], fs=[-0.0, 1.5, float("inf")]),
        barrier(2),
        lchunk([1, 1], [10, 11], ss=["a", "a2"], fs=[-0.0, 2.5],
               ops=[Op.UPDATE_DELETE, Op.UPDATE_INSERT]),
        barrier(3),
        lchunk([2], [20], ss=[None], fs=[1.5], ops=[Op.DELETE]),
        barrier(4),
    ]
    script_r = [
        barrier(1),
        rchunk([3, None], [90, 91], ss=[None, "x"]),
        barrier(2),
        rchunk([1, 1], [70, 71]),                # flips left 1: 0→2
        barrier(3),
        rchunk([1], [70], ops=[Op.DELETE]),      # degree 2→1 (no flip)
        barrier(4),
    ]
    return script_l, script_r, 4


ALL_TYPES = list(JoinType)


@pytest.mark.parametrize("jt", ALL_TYPES, ids=[t.value for t in ALL_TYPES])
def test_device_emit_bit_identical_scripted(jt):
    sl, sr, nb = scripts_scripted()
    on, _ = run(jt, sl, sr, nb, device_payload=True)
    sl, sr, nb = scripts_scripted()
    off, _ = run(jt, sl, sr, nb, device_payload=False)
    assert records(on) == records(off), jt


@pytest.mark.parametrize("jt", ALL_TYPES, ids=[t.value for t in ALL_TYPES])
def test_device_emit_bit_identical_random(jt):
    def scripts():
        rng = np.random.default_rng(hash(jt.value) % 2**32)
        rows = [[], []]
        script_l, script_r = [barrier(1)], [barrier(1)]
        pk = [0, 0]
        for b in range(2, 7):
            for side, script, mk in ((0, script_l, lchunk),
                                     (1, script_r, rchunk)):
                ks, vs, ops = [], [], []
                for _ in range(20):
                    if rows[side] and rng.random() < 0.3:
                        i = int(rng.integers(0, len(rows[side])))
                        k_, v_ = rows[side].pop(i)
                        ks.append(k_)
                        vs.append(v_)
                        ops.append(Op.DELETE)
                    else:
                        k_ = int(rng.integers(0, 6))
                        if rng.random() < 0.1:
                            k_ = None
                        v_ = pk[side]
                        pk[side] += 1
                        rows[side].append((k_, v_))
                        ks.append(k_)
                        vs.append(v_)
                        ops.append(Op.INSERT)
                script.append(mk(ks, vs, ops=ops))
                script.append(barrier(b))
        return script_l, script_r

    sl, sr = scripts()
    on, _ = run(jt, sl, sr, 6, device_payload=True)
    sl, sr = scripts()
    off, _ = run(jt, sl, sr, 6, device_payload=False)
    assert records(on) == records(off), jt


@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.FULL_OUTER,
                                JoinType.LEFT_ANTI],
                         ids=lambda t: t.value)
def test_recovery_rebuilds_payload_lanes(jt):
    """Kill-and-rebuild mid-stream: the fresh executor reloads the
    arena AND the device payload lanes from the state tables, and the
    resumed device-emit stream stays bit-identical to the host-gather
    arm resumed the same way."""
    def phase1():
        return ([barrier(1), lchunk([1, 2], [10, 20],
                                    ss=["a", None], fs=[-0.0, 2.5]),
                 barrier(2)],
                [barrier(1), rchunk([1], [70]), barrier(2)])

    def phase2():
        return ([barrier(3), lchunk([1], [10], ss=["a"], fs=[-0.0],
                                    ops=[Op.DELETE]), barrier(4)],
                [barrier(3), rchunk([2, 1], [80, 71], ss=[None, "z"]),
                 barrier(4)])

    streams = {}
    for arm in (True, False):
        store = MemoryStateStore()
        sl, sr = phase1()
        m1, _ = run(jt, sl, sr, 2, store=store, device_payload=arm)
        sl, sr = phase2()
        m2, _ = run(jt, sl, sr, 2, store=store, device_payload=arm)
        streams[arm] = records(m1) + records(m2)
    assert streams[True] == streams[False], jt


def test_recovery_payload_matches_arena():
    """White-box: after recovery, decoding the rebuilt device lanes by
    ref reproduces the arena columns exactly."""
    store = MemoryStateStore()
    sl = [barrier(1), lchunk([1, 2, 7], [10, 20, 30],
                             ss=["a", None, "c"],
                             fs=[-0.0, 1.25, float("-inf")]),
          barrier(2)]
    sr = [barrier(1), rchunk([1], [70]), barrier(2)]
    run(JoinType.INNER, sl, sr, 2, store=store)
    # fresh executor recovers from the tables
    lt = StateTable(161, L, [0, 1], store, dist_key_indices=[])
    rt = StateTable(162, R, [0, 1], store, dist_key_indices=[])
    ex = HashJoinExecutor(
        MockSource(L, [barrier(3), barrier(4)]),
        MockSource(R, [barrier(3), barrier(4)]),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt)
    asyncio.run(collect_until_n_barriers(ex, 2))
    side = ex.sides[0]
    refs = np.fromiter(side.pk_to_ref.values(), dtype=np.int64,
                       count=len(side.pk_to_ref))
    assert len(refs) == 3
    want = side.payload_from_arena(refs)
    got = np.asarray(side.kernel.pay)[refs]
    assert (want == got).all(), "device payload lanes drifted from arena"


def _run_chain(ex, n_barriers):
    return records(asyncio.run(collect_until_n_barriers(ex, n_barriers)))


def _join_with_run(kind):
    """join→agg-shape pipeline whose left input is a filter+project
    run, in three arms: 'interp' (sequential executors), 'block'
    (standalone FusedFragmentExecutor — the pre-ISSUE-9 fusion shape,
    1 jit dispatch per chunk), 'join' (the run absorbed into the
    join's epoch dispatches)."""
    from risingwave_tpu.expr.expr import InputRef, Literal
    from risingwave_tpu.stream.executors.simple import (
        FilterExecutor, ProjectExecutor,
    )

    def scripts():
        sl, sr = [barrier(1)], [barrier(1)]
        for b in range(2, 8):
            ks = list(range(8))
            sl.append(lchunk(ks, [b * 100 + k for k in ks]))
            sr.append(rchunk(ks, [b * 200 + k for k in ks]))
            sl.append(barrier(b))
            sr.append(barrier(b))
        return sl, sr, 7

    sl, sr, nb = scripts()
    store = MemoryStateStore()
    src = MockSource(L, sl)
    pred = InputRef(1, DataType.INT64) >= \
        Literal(0, DataType.INT64)
    filt = FilterExecutor(src, pred)
    proj = ProjectExecutor(
        filt,
        exprs=[InputRef(0, DataType.INT64),
               InputRef(1, DataType.INT64) * Literal(2, DataType.INT64),
               InputRef(2, DataType.VARCHAR),
               InputRef(3, DataType.FLOAT64)],
        names=["lk", "lv", "ls", "lf"])
    run_top = proj
    if kind == "block":
        from risingwave_tpu.ops.fused import FusedStage, FusedStages
        from risingwave_tpu.stream.executors.fused import (
            FusedFragmentExecutor,
        )
        fs = FusedStages(L, [
            FusedStage("filter", "FilterExecutor", exprs=(pred,)),
            FusedStage("project", "ProjectExecutor",
                       exprs=tuple(proj.exprs),
                       names=("lk", "lv", "ls", "lf"))])
        run_top = FusedFragmentExecutor(src, fs)
    lt = StateTable(171, run_top.schema, [0, 1], store,
                    dist_key_indices=[])
    rt = StateTable(172, R, [0, 1], store, dist_key_indices=[])
    ex = HashJoinExecutor(run_top, MockSource(R, sr),
                          left_keys=[0], right_keys=[0],
                          left_table=lt, right_table=rt)
    if kind == "join":
        from risingwave_tpu.frontend.opt.fusion import fuse_fragments
        ex, fired, _details = fuse_fragments(ex)
        assert fired >= 1
        assert ex.sides[0].fused_input is not None, \
            "join did not absorb its input run"
    return ex, nb


def test_fused_join_dispatch_budget(dispatch_budget):
    """CI guard (ISSUE 9): absorbing a join's input run into its epoch
    dispatches must not exceed — and must beat — the standalone
    fused-block shape's dispatch count, bit-identically."""
    out_i = _run_chain(*_join_with_run("interp"))
    ex, nb = _join_with_run("block")
    out_b, d_b, rpd_b = dispatch_budget.measure(
        lambda: _run_chain(ex, nb))
    ex, nb = _join_with_run("join")
    out_j, d_j, rpd_j = dispatch_budget.measure(
        lambda: _run_chain(ex, nb))
    assert out_i == out_b == out_j and out_j
    # the absorbed run dispatches strictly less than the block shape
    # (its per-chunk chain step disappears into the epoch jits) and
    # never exceeds it (the r08-ceiling analog at test scale)
    dispatch_budget.check(d_b, rpd_b, d_j, rpd_j)
    dispatch_budget.check_ceiling(d_j, d_b, what="fused-block arm")


def test_join_kernels_steady_state_no_retrace(recompile_guard):
    """The new join epoch kernels (payload scatter, device-degree
    probe, fused-input prelude jits) stay shape-stable: uniform
    chunks after warmup must retrace nothing."""
    def phase(b0, nb):
        sl, sr = [], []
        for b in range(b0, b0 + nb):
            # 80 rows a side: past the floor of a staged epoch (64,
            # which the small epochs of the tests above all share), so
            # the warmup compiles this test's own programs
            ks = list(range(80))
            sl.append(lchunk(ks, [b * 100 + k for k in ks]))
            sl.append(barrier(b))
            sr.append(rchunk(ks, [b * 200 + k for k in ks]))
            sr.append(barrier(b))
        return sl, sr

    store = MemoryStateStore()
    lt = StateTable(181, L, [0, 1], store, dist_key_indices=[])
    rt = StateTable(182, R, [0, 1], store, dist_key_indices=[])
    w1l, w1r = phase(2, 6)
    w2l, w2r = phase(8, 6)
    ex = HashJoinExecutor(
        MockSource(L, [barrier(1)] + w1l + w2l),
        MockSource(R, [barrier(1)] + w1r + w2r),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt,
        join_type=JoinType.LEFT_OUTER)

    from risingwave_tpu.stream.message import is_barrier
    agen = ex.execute()

    async def drain(n):
        seen = 0
        while seen < n:
            if is_barrier(await agen.__anext__()):
                seen += 1

    # drive warmup + steady on ONE loop (the generator is stateful)
    loop = asyncio.new_event_loop()
    try:
        _, n_warm = recompile_guard.measure(
            lambda: loop.run_until_complete(drain(7)))
        _, n_steady = recompile_guard.measure(
            lambda: loop.run_until_complete(drain(6)))
    finally:
        loop.close()
    assert n_warm > 0, "warmup compiled nothing — dead test"
    recompile_guard.check_steady(
        n_steady, what="steady-state join epochs")


def test_cold_tier_eviction_reload_device_resident():
    """state_cap over a device-resident side: rows leave the payload
    lanes with the arena on eviction and reload together; the emitted
    stream stays bit-identical to the host-gather arm under the same
    cap, and evictions actually happened."""
    from risingwave_tpu.utils.metrics import STREAMING

    def evicted_total():
        return sum(v for _l, v in STREAMING.state_tier_evicted.series())

    def scripts():
        sl, sr = [barrier(1)], [barrier(1)]
        b = 2
        for phase in range(5):
            ks = [phase * 4 + j for j in range(4)]
            sl.append(lchunk(ks, [100 + k for k in ks]))
            sr.append(rchunk(ks, [200 + k for k in ks]))
            sl.append(barrier(b))
            sr.append(barrier(b))
            b += 1
        # revisit the OLDEST keys: forces a reload of evicted state
        sl.append(lchunk([0, 1], [900, 901]))
        sr.append(rchunk([2, 3], [902, 903]))
        sl.append(barrier(b))
        sr.append(barrier(b))
        return sl, sr, b

    streams = {}
    evicted = {}
    for arm in (True, False):
        before = evicted_total()
        sl, sr, nb = scripts()
        msgs, _ = run(JoinType.INNER, sl, sr, nb, device_payload=arm,
                      state_cap=6)
        streams[arm] = records(msgs)
        evicted[arm] = evicted_total() - before
    assert evicted[True] > 0, "cap 6 over 20 keys must evict"
    assert streams[True] == streams[False]
