"""HashJoin: device matcher vs dict oracle; executor vs a changelog oracle.

Mirrors the inner-join cases of the reference's hash_join tests
(src/stream/src/executor/hash_join.rs test mod): scripted chunks on both
sides through barrier alignment, emitted changelog asserted against a
recomputed join, including retractions and N:M matches.
"""

import asyncio
from collections import Counter, defaultdict

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common.chunk import Op, StreamChunk
from risingwave_tpu.common.epoch import Epoch, EpochPair
from risingwave_tpu.common.types import DataType, Schema
from risingwave_tpu.ops import hash_join as hj
from risingwave_tpu.ops.hash_join import JoinSideKernel
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.stream.executors.hash_join import HashJoinExecutor
from risingwave_tpu.stream.executors.test_utils import (
    MockSource, collect_until_n_barriers,
)
from risingwave_tpu.stream.message import Barrier, BarrierKind, is_chunk

L_SCHEMA = Schema.of(lk=DataType.INT64, lv=DataType.INT64)
R_SCHEMA = Schema.of(rk=DataType.INT64, rv=DataType.VARCHAR)


def barrier(n: int) -> Barrier:
    prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
    return Barrier(EpochPair(Epoch.from_physical(n), prev),
                   BarrierKind.CHECKPOINT)


# -- kernel-level oracle ------------------------------------------------


def test_join_kernel_chains_and_probe():
    k = JoinSideKernel(key_width=1)
    keys = jnp.asarray([[5], [5], [7], [5]], dtype=jnp.int32)
    refs = np.asarray([0, 1, 2, 3], dtype=np.int32)
    k.insert(keys, refs, jnp.ones(4, dtype=bool))
    deg, _pidx, _prefs = k.probe(
        jnp.asarray([[5], [7], [9]], dtype=jnp.int32),
        jnp.ones(3, dtype=bool))
    assert deg.tolist() == [3, 1, 0]
    # tombstone one of the key-5 rows
    k.delete(np.asarray([1], dtype=np.int32), jnp.ones(1, dtype=bool))
    deg, _pidx, _prefs = k.probe(
        jnp.asarray([[5]], dtype=jnp.int32), jnp.ones(1, dtype=bool))
    assert deg.tolist() == [2]


def test_join_kernel_random_oracle():
    rng = np.random.default_rng(9)
    k = JoinSideKernel(key_width=1)
    oracle = defaultdict(set)       # key → set of live refs
    ref_of_row = {}
    next_ref = 0
    for _round in range(6):
        n = 64
        keys = rng.integers(0, 12, n).astype(np.int32).reshape(-1, 1)
        ins_mask = np.ones(n, dtype=bool)
        refs = np.arange(next_ref, next_ref + n, dtype=np.int32)
        next_ref += n
        k.insert(jnp.asarray(keys), refs, jnp.asarray(ins_mask))
        for i in range(n):
            oracle[int(keys[i, 0])].add(int(refs[i]))
            ref_of_row[int(refs[i])] = int(keys[i, 0])
        # random deletes
        live = [r for s in oracle.values() for r in s]
        kill = rng.choice(live, size=min(20, len(live)), replace=False)
        k.delete(np.asarray(kill, dtype=np.int32),
                 jnp.ones(len(kill), dtype=bool))
        for r in kill:
            oracle[ref_of_row[int(r)]].discard(int(r))
        probe_keys = np.arange(14, dtype=np.int32).reshape(-1, 1)
        deg, pidx, prefs = k.probe(jnp.asarray(probe_keys),
                                   jnp.ones(14, dtype=bool))
        assert deg.tolist() == [len(oracle[int(q)]) for q in range(14)]
        got = defaultdict(set)
        for p, r in zip(pidx.tolist(), prefs.tolist()):
            got[int(probe_keys[p, 0])].add(r)
        for q in range(14):
            assert got[q] == oracle[q], f"key {q}"


# -- the probe over runs vs a plain reference ------------------------------


class ChainModel:
    """A join side as plain lists: a key's chain is its rows, newest
    batch first and batch order inside a batch; a probe at sequence s
    sees a row when ``ins_seq < s <= del_seq``."""

    def __init__(self):
        self.batches = []            # (keys, refs, seqs), oldest first
        self.del_seq = {}

    def link(self, keys, refs, seqs):
        self.batches.append((list(keys), list(refs), list(seqs)))

    def tombstone(self, refs, seqs):
        self.del_seq.update(zip(refs, seqs))

    def probe(self, keys, seqs):
        """(probe_idx, refs) of the pairs in order, pairs per probe
        row, chain rows (visible or not) per probe row."""
        chains = defaultdict(list)
        for bkeys, brefs, bseqs in reversed(self.batches):
            for k, r, s in zip(bkeys, brefs, bseqs):
                chains[k].append((r, s))
        pidx, prefs, deg, rows = [], [], [], []
        for i, (k, s) in enumerate(zip(keys, seqs)):
            chain = chains.get(k, [])
            seen = [r for r, ins in chain
                    if ins < s <= self.del_seq.get(r, (1 << 31) - 1)]
            pidx += [i] * len(seen)
            prefs += seen
            deg.append(len(seen))
            rows.append(len(chain))
        return pidx, prefs, deg, rows


def _side(**kw):
    return JoinSideKernel(key_width=1, payload_width=3, **kw)


def _epoch(kernel, keys, refs, seqs, flags):
    """Stage one side's epoch on `kernel`: a key lane, payload lanes
    that repeat the ref, and the aux columns of ops/hash_join.py (a
    FLAG_DEL row names its ref in the delete column)."""
    n = len(keys)
    flags = np.broadcast_to(np.asarray(flags, dtype=np.int32), (n,))
    refs = np.asarray(refs, dtype=np.int32)
    up = np.zeros((n, 4), dtype=np.int32)
    up[:, 0] = keys
    up[:, 1:] = refs[:, None]
    aux = np.zeros((n, 4), dtype=np.int32)
    aux[:, hj.AUX_INS_REF] = refs
    aux[:, hj.AUX_DEL_REF] = refs
    aux[:, hj.AUX_FLAGS] = flags
    aux[:, hj.AUX_SEQ] = seqs
    ins = refs[(flags & hj.FLAG_INS) != 0]
    max_ref = int(ins.max()) if len(ins) else -1
    up_d, aux_d, _b = kernel.stage_epoch(up, aux, n, max_ref)
    return up_d, aux_d, n, max_ref


def _link(kernel, model, keys, refs, seqs):
    up_d, aux_d, n, max_ref = _epoch(kernel, keys, refs, seqs,
                                     hj.FLAG_INS)
    kernel.apply_epoch(up_d, aux_d, n, max_ref)
    model.link(keys, refs, seqs)


def _tombstone(kernel, model, refs, seqs):
    up_d, aux_d, n, max_ref = _epoch(kernel, [0] * len(refs), refs, seqs,
                                     hj.FLAG_DEL)
    kernel.apply_epoch(up_d, aux_d, n, max_ref)
    model.tombstone(refs, seqs)


def _three_batches(kernel, model, keys_of_batch, first_seq=1):
    ref = 0
    for b, keys in enumerate(keys_of_batch):
        refs = np.arange(ref, ref + len(keys))
        _link(kernel, model, keys, refs, [first_seq + b] * len(keys))
        ref += len(keys)
    return ref


def _case_one_key_of_5000_rows():
    k, m = _side(), ChainModel()
    _three_batches(k, m, [[7] * 2000, [7] * 2500, [7] * 500])
    return k, m, [7, 8, 7], [9, 9, 3], dict(steps=3, longest=5000)


def _case_4096_keys_of_one_row():
    k, m = _side(), ChainModel()
    keys = np.random.default_rng(3).permutation(4096) * 3
    _link(k, m, keys.tolist(), np.arange(4096), [1] * 4096)
    probes = np.concatenate([keys[::-1], [1, 2]]).tolist()
    return k, m, probes, [5] * len(probes), dict(steps=1, longest=1)


def _hot_and_quiet(rng, n):
    return np.where(rng.random(n) < 0.6, 11,
                    rng.integers(100, 160, n)).tolist()


def _case_a_hot_key_among_quiet_ones():
    k, m = _side(), ChainModel()
    rng = np.random.default_rng(5)
    _three_batches(k, m, [_hot_and_quiet(rng, 700) for _ in range(3)])
    probes = [11, 100, 11, 999] + list(range(100, 160))
    return k, m, probes, [9] * len(probes), dict(steps=3)


def _case_rows_of_a_later_sequence():
    """q4's case: the probing rows come before the stored rows of
    their own epoch, so every candidate is invisible."""
    k, m = _side(), ChainModel()
    rng = np.random.default_rng(6)
    keys = _hot_and_quiet(rng, 900)
    _link(k, m, keys, np.arange(900), np.arange(100, 1000).tolist())
    return k, m, [11, 100, 101], [50, 60, 100], \
        dict(steps=1, pairs=0, some_candidates=True)


def _case_tombstones_before_and_after_the_probe():
    k, m = _side(), ChainModel()
    rng = np.random.default_rng(7)
    n = _three_batches(
        k, m, [_hot_and_quiet(rng, 300) for _ in range(3)], first_seq=2)
    dead = rng.choice(n, size=400, replace=False)
    _tombstone(k, m, dead.tolist(), rng.integers(3, 12, 400).tolist())
    probes = [11] * 6 + [100, 120, 140]
    return k, m, probes, [2, 3, 5, 8, 11, 12, 6, 7, 30], dict(steps=3)


def _case_candidates_past_the_buffer():
    """300 candidates through a buffer of 8: the rung to 32, then
    pages of 32 by their first candidate."""
    k, m = _side(probe_capacity=8), ChainModel()
    k.PROBE_CAP_TOP = 32
    n = _three_batches(k, m, [[7] * 40, [7, 9] * 20, [7] * 20])
    _tombstone(k, m, list(range(0, n, 3)), [4] * len(range(0, n, 3)))
    return k, m, [7, 9, 7, 7, 5], [9, 9, 3, 4, 9], \
        dict(steps=3, longest=80, cap=32)


def _case_a_key_table_growth_between_batches():
    k, m = _side(key_capacity=1 << 10), ChainModel()
    rng = np.random.default_rng(8)
    cap0 = k.table.capacity
    _three_batches(k, m, [rng.integers(0, 3000, 900).tolist()
                          for _ in range(3)])
    assert k.table.capacity > cap0
    probes = list(range(0, 3000, 7))
    return k, m, probes, [9] * len(probes), {}


def _case_a_row_growth_between_batches():
    k, m = _side(row_capacity=64), ChainModel()
    rng = np.random.default_rng(9)
    _three_batches(k, m, [_hot_and_quiet(rng, 200) for _ in range(3)])
    assert k.row_capacity >= 600
    probes = [11] + list(range(100, 160))
    return k, m, probes, [9] * len(probes), dict(steps=3)


@pytest.mark.parametrize("keys_of, grows", [
    (lambda refs: refs, True),            # a row a key (q101's sides)
    (lambda refs: refs % 50, False),      # few keys, many rows (q4's)
])
def test_a_key_table_that_has_to_grow_goes_to_what_the_row_arrays_need(
        keys_of, grows):
    """No rung of the key table's own between two of the row arrays':
    one growth takes it to where rows, all of distinct keys, fit; a
    side whose keys are few never grows it."""
    k, m = _side(key_capacity=1 << 10, row_capacity=1 << 12), ChainModel()
    caps = []
    for lo in range(0, 4000, 500):
        refs = np.arange(lo, lo + 500)
        _link(k, m, keys_of(refs).tolist(), refs.tolist(), [1] * 500)
        caps.append(k.table.capacity)
    assert k.row_capacity == 1 << 12
    if grows:
        assert sorted(set(caps)) == [1 << 10, 1 << 13]
        assert 4000 <= hj.ht.MAX_LOAD * caps[-1]
    else:
        assert set(caps) == {1 << 10}
    probes = keys_of(np.arange(0, 4000, 37)).tolist()
    want = m.probe(probes, [9] * len(probes))
    _deg, pidx, prefs = k.probe(
        jnp.asarray(np.asarray(probes, np.int32)[:, None]),
        jnp.ones(len(probes), dtype=bool), seq=9)
    assert Counter(zip(np.asarray(pidx).tolist(),
                       np.asarray(prefs).tolist())) == \
        Counter(zip(want[0], want[1]))


def _case_rebuild_then_probe():
    """A rebuild links its pages last page first: a key's rows stand
    in the order given, a run a key a page."""
    k, m = _side(), ChainModel()
    rng = np.random.default_rng(10)
    _three_batches(k, m, [_hot_and_quiet(rng, 100) for _ in range(2)])
    keys = np.asarray(_hot_and_quiet(rng, 150), dtype=np.int32)
    refs = np.arange(150, dtype=np.int32)
    k._bulk.TOP = 64                       # pages of 64 rows
    k.rebuild(keys[:, None], refs, payload=np.repeat(refs[:, None], 3, 1))
    m = ChainModel()
    for lo in (128, 64, 0):                # oldest run first
        m.link(keys[lo:lo + 64].tolist(), refs[lo:lo + 64].tolist(),
               [0] * len(refs[lo:lo + 64]))
    probes = [11] + list(range(100, 160))
    return k, m, probes, [1] * len(probes), dict(steps=3)


RUN_CASES = [_case_one_key_of_5000_rows, _case_4096_keys_of_one_row,
             _case_a_hot_key_among_quiet_ones,
             _case_rows_of_a_later_sequence,
             _case_tombstones_before_and_after_the_probe,
             _case_candidates_past_the_buffer,
             _case_a_key_table_growth_between_batches,
             _case_a_row_growth_between_batches, _case_rebuild_then_probe]


@pytest.mark.parametrize("with_degrees", [False, True],
                         ids=["inner", "degrees"])
@pytest.mark.parametrize("case", RUN_CASES,
                         ids=[c.__name__[6:] for c in RUN_CASES])
def test_probe_over_runs_gives_the_reference_pairs_in_order(
        case, with_degrees):
    """The epoch probe against `ChainModel`: the same pairs in the same
    order, the payload and, with degrees, the per-row degrees, each
    pair's old degree and both sides' degree arrays; the header's books
    say what the walk did."""
    kernel, model, keys, seqs, want = case()
    n = len(keys)
    sink = _side(row_capacity=max(64, n))
    # every other probing row retracts; each is stored on the sink
    flags = hj.FLAG_PROBE | hj.FLAG_INS \
        | np.where(np.arange(n) % 2 == 1, hj.FLAG_NEG, 0)
    up_d, aux_d, _n, _max = _epoch(kernel, keys, np.arange(n), seqs,
                                   flags)
    deg0 = np.asarray(kernel.deg).copy()
    deg, pidx, prefs, pay, old = kernel.probe_epoch(
        up_d, aux_d, with_degrees, sink=sink).collect()
    w_pidx, w_prefs, w_deg, w_rows = model.probe(keys, seqs)
    np.testing.assert_array_equal(pidx, w_pidx)
    np.testing.assert_array_equal(prefs, w_prefs)
    np.testing.assert_array_equal(pay, np.repeat(prefs[:, None], 3, 1))
    if with_degrees:
        np.testing.assert_array_equal(deg, w_deg)
        sign = np.where(np.arange(n) % 2 == 1, -1, 1)
        after, w_old = deg0.copy(), []
        for p, r in zip(w_pidx, w_prefs):     # the host's replay
            w_old.append(deg0[r])
            after[r] += sign[p]
        np.testing.assert_array_equal(old, w_old)
        np.testing.assert_array_equal(np.asarray(kernel.deg), after)
        np.testing.assert_array_equal(np.asarray(sink.deg)[:n], w_deg)
    else:
        assert deg is None and old is None
        np.testing.assert_array_equal(np.asarray(kernel.deg), deg0)
    steps, candidates, pairs = kernel.take_probe_books()
    assert kernel.take_longest_chain() == max(w_rows)
    assert (candidates, pairs) == (sum(w_rows), len(w_pidx))
    assert steps == want.get("steps", steps)
    assert max(w_rows) == want.get("longest", max(w_rows))
    assert pairs == want.get("pairs", pairs)
    if want.get("some_candidates"):
        assert candidates > 0
    if "cap" in want and not with_degrees:
        # one further rung, then pages: never a size the data chose
        assert kernel._probe_cap == want["cap"] < candidates
    assert kernel.take_probe_books() == (0, 0, 0)


def test_chunk_probe_over_runs_keeps_chain_order():
    """The per-chunk probe (`probe_pairs`, one sequence a call) walks
    the same runs: a hot key's pairs come newest batch first, batch
    order inside a batch, and a buffer of one row doubles until the
    candidates fit."""
    k, m = JoinSideKernel(key_width=1, probe_capacity=1), ChainModel()
    rng = np.random.default_rng(12)
    ref = 0
    for seq in (1, 2, 3):
        keys = _hot_and_quiet(rng, 200)
        refs = np.arange(ref, ref + 200, dtype=np.int32)
        ref += 200
        k.insert(jnp.asarray(keys, dtype=jnp.int32)[:, None], refs,
                 jnp.ones(200, dtype=bool), seq=seq)
        m.link(keys, refs.tolist(), [seq] * 200)
    dead = rng.choice(600, size=150, replace=False).astype(np.int32)
    k.delete(dead, jnp.ones(150, dtype=bool), seq=3)
    m.tombstone(dead.tolist(), [3] * 150)
    probes = [11, 100, 11, 777] + list(range(100, 130))
    for seq in (2, 3, 4):
        deg, pidx, prefs = k.probe(
            jnp.asarray(probes, dtype=jnp.int32)[:, None],
            jnp.ones(len(probes), dtype=bool), seq=seq)
        w_pidx, w_prefs, w_deg, _rows = m.probe(probes,
                                                [seq] * len(probes))
        np.testing.assert_array_equal(pidx, w_pidx)
        np.testing.assert_array_equal(prefs, w_prefs)
        np.testing.assert_array_equal(deg, w_deg)


# -- executor-level oracle ----------------------------------------------


class JoinOracle:
    """Maintains both sides + the expected inner-join multiset."""

    def __init__(self):
        self.left = []     # (lk, lv)
        self.right = []    # (rk, rv)

    def view(self) -> Counter:
        out = Counter()
        for lk, lv in self.left:
            if lk is None:
                continue
            for rk, rv in self.right:
                if rk == lk:
                    out[(lk, lv, rk, rv)] += 1
        return out


def materialize_join(msgs) -> Counter:
    view = Counter()
    for m in msgs:
        if not is_chunk(m):
            continue
        for op, row in m.to_records():
            if op.is_insert:
                view[row] += 1
            else:
                view[row] -= 1
                assert view[row] >= 0, f"negative count for {row}"
    return +view


def run_join(script_l, script_r, n_barriers):
    store = MemoryStateStore()
    lt = StateTable(21, L_SCHEMA, [1], store, dist_key_indices=[])
    rt = StateTable(22, R_SCHEMA, [1], store, dist_key_indices=[])
    ex = HashJoinExecutor(
        MockSource(L_SCHEMA, script_l), MockSource(R_SCHEMA, script_r),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt)
    msgs = asyncio.run(collect_until_n_barriers(ex, n_barriers))
    return msgs, (lt, rt, store)


def lchunk(ks, vs, ops=None):
    return StreamChunk.from_pydict(L_SCHEMA, {"lk": ks, "lv": vs}, ops=ops)


def rchunk(ks, vs, ops=None):
    return StreamChunk.from_pydict(R_SCHEMA, {"rk": ks, "rv": vs}, ops=ops)


def test_inner_join_basic_both_sides():
    script_l = [barrier(1), lchunk([1, 2], [10, 20]), barrier(2),
                lchunk([1], [11]), barrier(3)]
    script_r = [barrier(1), rchunk([1, 3], ["a", "c"]), barrier(2),
                rchunk([2], ["b"]), barrier(3)]
    msgs, _ = run_join(script_l, script_r, 3)
    oracle = JoinOracle()
    oracle.left = [(1, 10), (2, 20), (1, 11)]
    oracle.right = [(1, "a"), (3, "c"), (2, "b")]
    assert materialize_join(msgs) == oracle.view()


def test_inner_join_retraction():
    script_l = [barrier(1), lchunk([1, 1], [10, 11]), barrier(2),
                lchunk([1], [10], ops=[Op.DELETE]), barrier(3)]
    script_r = [barrier(1), rchunk([1], ["a"]), barrier(2),
                rchunk([], []), barrier(3)]
    msgs, _ = run_join(script_l, script_r, 3)
    view = materialize_join(msgs)
    assert view == Counter({(1, 11, 1, "a"): 1})


def test_inner_join_null_keys_never_match():
    script_l = [barrier(1),
                StreamChunk.from_pydict(
                    L_SCHEMA, {"lk": [None, 1], "lv": [1, 2]}),
                barrier(2)]
    script_r = [barrier(1),
                StreamChunk.from_pydict(
                    R_SCHEMA, {"rk": [None, 1], "rv": ["x", "y"]}),
                barrier(2)]
    msgs, _ = run_join(script_l, script_r, 2)
    assert materialize_join(msgs) == Counter({(1, 2, 1, "y"): 1})


def test_inner_join_random_stream_oracle():
    rng = np.random.default_rng(17)
    oracle = JoinOracle()
    script_l, script_r = [barrier(1)], [barrier(1)]
    b = 2
    lpk, rpk = 0, 0
    for _ in range(6):
        # left chunk: inserts + deletes of existing rows
        ks, vs, ops = [], [], []
        for _ in range(24):
            if oracle.left and rng.random() < 0.35:
                i = int(rng.integers(0, len(oracle.left)))
                k_, v_ = oracle.left.pop(i)
                ks.append(k_)
                vs.append(v_)
                ops.append(Op.DELETE)
            else:
                k_, v_ = int(rng.integers(0, 8)), lpk
                lpk += 1
                oracle.left.append((k_, v_))
                ks.append(k_)
                vs.append(v_)
                ops.append(Op.INSERT)
        script_l.append(lchunk(ks, vs, ops=ops))
        ks, vs, ops = [], [], []
        for _ in range(16):
            if oracle.right and rng.random() < 0.35:
                i = int(rng.integers(0, len(oracle.right)))
                k_, v_ = oracle.right.pop(i)
                ks.append(k_)
                vs.append(v_)
                ops.append(Op.DELETE)
            else:
                k_, v_ = int(rng.integers(0, 8)), f"r{rpk}"
                rpk += 1
                oracle.right.append((k_, v_))
                ks.append(k_)
                vs.append(v_)
                ops.append(Op.INSERT)
        script_r.append(rchunk(ks, vs, ops=ops))
        script_l.append(barrier(b))
        script_r.append(barrier(b))
        b += 1
    msgs, _ = run_join(script_l, script_r, b - 1)
    assert materialize_join(msgs) == oracle.view()


def test_inner_join_update_pair_same_pk_one_chunk():
    """An update pair [U-, U+] sharing a pk inside ONE chunk must
    retract the old row and register the new one (regression: inserts
    applied before deletes corrupted the pk→ref map)."""
    script_l = [barrier(1), lchunk([1], [10]), barrier(2),
                lchunk([1, 2], [10, 10],
                       ops=[Op.UPDATE_DELETE, Op.UPDATE_INSERT]),
                barrier(3),
                # post-update probes: key 1 must be gone, key 2 must hit
                lchunk([], []), barrier(4)]
    script_r = [barrier(1), rchunk([1], ["a"]), barrier(2),
                rchunk([], []), barrier(3),
                rchunk([1, 2], ["a2", "b2"]), barrier(4)]
    msgs, _ = run_join(script_l, script_r, 4)
    assert materialize_join(msgs) == Counter({(2, 10, 2, "b2"): 1})


def test_join_forwards_key_watermarks_and_expires_state():
    """hash_join.rs:860-945: join-key watermarks forward as the min
    across sides (for BOTH output key columns) and expire stored rows
    below the combined watermark at the barrier."""
    from risingwave_tpu.stream.message import Watermark, is_watermark

    wm = lambda v: Watermark(0, DataType.INT64, v)  # noqa: E731
    script_l = [barrier(1),
                lchunk([1, 5, 9], [10, 50, 90]), wm(6),
                barrier(2), barrier(3)]
    script_r = [barrier(1),
                rchunk([1, 5, 9], ["a", "e", "i"]), wm(8),
                barrier(2), barrier(3)]
    msgs, (lt, rt, _store) = run_join(script_l, script_r, 3)
    wms = [m for m in msgs if is_watermark(m)]
    # combined = min(6, 8) = 6, emitted for left col 0 and right col 2
    assert {(m.col_idx, m.value) for m in wms} == {(0, 6), (2, 6)}
    # rows with key < 6 expired from both state tables at the barrier
    assert sorted(r[0] for _pk, r in lt.iter_rows()) == [9]
    assert sorted(r[0] for _pk, r in rt.iter_rows()) == [9]
    # ...and from the device matcher: a new left probe for key 1 or 5
    # finds nothing, key 9 still matches
    # (watermark semantics: those keys can no longer arrive; this just
    # verifies the matcher state is really gone)


def test_join_expiry_then_survivor_still_matches():
    from risingwave_tpu.stream.message import Watermark

    wm = lambda v: Watermark(0, DataType.INT64, v)  # noqa: E731
    script_l = [barrier(1), lchunk([1, 9], [10, 90]), wm(9),
                barrier(2),
                lchunk([9], [91]),   # second row for surviving key
                barrier(3)]
    script_r = [barrier(1), rchunk([1, 9], ["a", "i"]), wm(9),
                barrier(2), barrier(3)]
    msgs, _tables = run_join(script_l, script_r, 3)
    got = materialize_join(msgs)
    # key 1 joined before expiry (epoch 2 emission), key 9 both rows
    assert got == Counter({(1, 10, 1, "a"): 1, (9, 90, 9, "i"): 1,
                           (9, 91, 9, "i"): 1})


def test_join_compaction_reclaims_dead_refs(monkeypatch):
    """Update churn leaves dead refs; the barrier-time compaction must
    reclaim them without changing join results."""
    from risingwave_tpu.stream.executors.hash_join import _JoinSide
    monkeypatch.setattr(_JoinSide, "COMPACT_MIN_REFS", 8)
    script_l, script_r = [barrier(1)], [barrier(1)]
    script_l.append(lchunk([0], [5]))
    script_r.append(rchunk([3], ["z"]))
    b = 2
    k_cur = 0
    for _ in range(20):   # 20 update pairs → 21 refs, ≥10 dead
        script_l.append(barrier(b))
        script_r.append(barrier(b))
        b += 1
        k_new = (k_cur + 1) % 4
        script_l.append(lchunk([k_cur, k_new], [5, 5],
                               ops=[Op.UPDATE_DELETE, Op.UPDATE_INSERT]))
        k_cur = k_new
    script_l.append(barrier(b))
    script_r.append(barrier(b))
    store = MemoryStateStore()
    lt = StateTable(21, L_SCHEMA, [1], store, dist_key_indices=[])
    rt = StateTable(22, R_SCHEMA, [1], store, dist_key_indices=[])
    ex = HashJoinExecutor(
        MockSource(L_SCHEMA, script_l), MockSource(R_SCHEMA, script_r),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt)
    msgs = asyncio.run(collect_until_n_barriers(ex, b))
    view = materialize_join(msgs)
    expect = Counter({(k_cur, 5, 3, "z"): 1}) if k_cur == 3 else Counter()
    assert view == expect
    left = ex.sides[0]
    # 21 refs were allocated over the run; compaction must have rebuilt
    # to ~1 live row (plus post-compaction churn), not 21
    assert left.next_ref < 21
    assert len(left.free) < left.next_ref


def test_join_recovery_resumes():
    store = MemoryStateStore()

    def build(sl, sr):
        lt = StateTable(21, L_SCHEMA, [1], store, dist_key_indices=[])
        rt = StateTable(22, R_SCHEMA, [1], store, dist_key_indices=[])
        return HashJoinExecutor(
            MockSource(L_SCHEMA, sl), MockSource(R_SCHEMA, sr),
            left_keys=[0], right_keys=[0], left_table=lt, right_table=rt)

    ex1 = build([barrier(1), lchunk([1], [10]), barrier(2)],
                [barrier(1), rchunk([1], ["a"]), barrier(2)])
    asyncio.run(collect_until_n_barriers(ex1, 2))
    # restart: right side gets a new matching row — the recovered left
    # row must produce the match
    ex2 = build([barrier(3), barrier(4)],
                [barrier(3), rchunk([1], ["b"]), barrier(4)])
    msgs = asyncio.run(collect_until_n_barriers(ex2, 2))
    assert materialize_join(msgs) == Counter({(1, 10, 1, "b"): 1})


def test_probe_pair_buffer_overflow_retries():
    """probe_capacity=1 forces the pair-buffer double/retry path."""
    k = JoinSideKernel(key_width=1, probe_capacity=1)
    keys = jnp.asarray([[3]] * 9 + [[4]] * 7, dtype=jnp.int32)
    refs = np.arange(16, dtype=np.int32)
    k.insert(keys, refs, jnp.ones(16, dtype=bool))
    deg, pidx, prefs = k.probe(
        jnp.asarray([[3], [4], [5]], dtype=jnp.int32),
        jnp.ones(3, dtype=bool))
    assert deg.tolist() == [9, 7, 0]
    assert k._probe_cap >= 16
    assert {int(r) for p, r in zip(pidx, prefs) if p == 0} == set(range(9))
    assert {int(r) for p, r in zip(pidx, prefs) if p == 1} == \
        set(range(9, 16))


def test_varchar_join_keys_exact_equality():
    """Varchar join keys through the SHARED interning codec (VERDICT r2
    #5): equal strings match across sides, distinct strings never merge,
    NULL keys never match, recovery reintern-rebuilds."""
    S_L = Schema.of(name=DataType.VARCHAR, lv=DataType.INT64)
    S_R = Schema.of(rname=DataType.VARCHAR, rv=DataType.INT64)

    def lc(names, vs, ops=None):
        return StreamChunk.from_pydict(S_L, {"name": names, "lv": vs},
                                       ops=ops)

    def rc(names, vs, ops=None):
        return StreamChunk.from_pydict(S_R, {"rname": names, "rv": vs},
                                       ops=ops)

    store = MemoryStateStore()
    lt = StateTable(41, S_L, [1], store, dist_key_indices=[])
    rt = StateTable(42, S_R, [1], store, dist_key_indices=[])
    ex = HashJoinExecutor(
        MockSource(S_L, [barrier(1),
                         lc(["apple", "pear", None, "plum"],
                            [1, 2, 3, 4]),
                         barrier(2)]),
        MockSource(S_R, [barrier(1),
                         rc(["pear", "apple", "apple", None],
                            [10, 20, 21, 30]),
                         barrier(2)]),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt)
    msgs = asyncio.run(collect_until_n_barriers(ex, 2))
    got = Counter(tuple(r) for m in msgs if is_chunk(m)
                  for _op, r in m.to_records())
    assert got == Counter({("apple", 1, "apple", 20): 1,
                           ("apple", 1, "apple", 21): 1,
                           ("pear", 2, "pear", 10): 1})

    # recovery: fresh executor over the same tables, new rows still join
    ex2 = HashJoinExecutor(
        MockSource(S_L, [barrier(3), lc(["apple"], [5]), barrier(4)]),
        MockSource(S_R, [barrier(3), barrier(4)]),
        left_keys=[0], right_keys=[0], left_table=lt, right_table=rt)
    msgs2 = asyncio.run(collect_until_n_barriers(ex2, 2))
    got2 = Counter(tuple(r) for m in msgs2 if is_chunk(m)
                   for _op, r in m.to_records())
    assert got2 == Counter({("apple", 5, "apple", 20): 1,
                            ("apple", 5, "apple", 21): 1})


# -- the watermark expiry by the column, against a row at a time -----------


def _row_at_a_time_expire_below(self, key_pos, wm_physical, seq=0):
    """``_JoinSide.expire_below`` as it read before it went by the
    column, kept here as the reference: a Python row per dead row
    (``row_tuple``), the dead pks out of the map's own keys, and a
    scalar ``StateTable.delete`` a row, which carries the old row."""
    def row_tuple(ref):
        return tuple(
            None if not self.arena.valid[i][ref]
            else (self.arena.cols[i][ref].item()
                  if self.schema[i].data_type.is_device
                  else self.arena.cols[i][ref])
            for i in range(len(self.schema)))

    n_cold = 0
    if self.cold_keys:
        dead_cold = [(lt, vt) for lt, vt in self.cold_keys.items()
                     if vt[key_pos] is not None
                     and int(vt[key_pos]) < int(wm_physical)]
        for lt, vt in dead_cold:
            del self.cold_keys[lt]
            self.expired_lanes.append(lt)
            for _pk, row in list(self.table.iter_prefix(list(vt))):
                # (not as it read: a row that came after its key went
                # cold is resident, and the second delete below raised)
                if tuple(row[i] for i in self.pk_indices) \
                        not in self.pk_to_ref:
                    self.table.delete(tuple(row))
                    n_cold += 1
    if not self.pk_to_ref:
        return n_cold
    col = self.key_indices[key_pos]
    pks = list(self.pk_to_ref.keys())
    dead = [j for j, ref in enumerate(self.pk_to_ref.values())
            if self.arena.valid[col][ref]
            and int(self.arena.cols[col][ref]) < int(wm_physical)]
    if not dead:
        return n_cold
    dead_refs = []
    for j in dead:
        ref = self.pk_to_ref.pop(pks[j])
        self.free.append(ref)
        dead_refs.append(ref)
        self.table.delete(row_tuple(ref))
    dead_refs = np.asarray(dead_refs, dtype=np.int32)
    dead_lanes = self.key_codec.build_arrays(
        [(self.arena.cols[i][dead_refs], self.arena.valid[i][dead_refs])
         for i in self.key_indices])
    if self.state_cap is not None:
        self.expired_lanes.extend(
            map(tuple, np.unique(dead_lanes, axis=0).tolist()))
    rung = self._expire_rung
    for lo, hi in rung.pages(len(dead)):
        self.kernel.delete(
            rung.padded(dead_refs, lo, hi), rung.mask(lo, hi),
            seq=seq, key_lanes=rung.padded(dead_lanes, lo, hi))
    return len(dead) + n_cold


class _RecordingStore(MemoryStateStore):
    """Keeps every flush it is handed, as handed."""

    def __init__(self):
        super().__init__()
        self.flushes = []

    def ingest_keyed(self, table_id, keys, values, epoch):
        self.flushes.append((table_id, list(keys), list(values), epoch))
        return super().ingest_keyed(table_id, keys, values, epoch)


X_L = Schema.of(lk=DataType.INT64, lid=DataType.INT64, lx=DataType.INT64)
X_R = Schema.of(rk=DataType.INT64, rid=DataType.INT64,
                rs=DataType.VARCHAR)


def _xl(ks, ids, xs, ops=None):
    return StreamChunk.from_pydict(X_L, {"lk": ks, "lid": ids, "lx": xs},
                                   ops=ops)


def _xr(ks, ids, ss, ops=None):
    return StreamChunk.from_pydict(X_R, {"rk": ks, "rid": ids, "rs": ss},
                                   ops=ops)


def _xwm(v):
    from risingwave_tpu.stream.message import Watermark
    return Watermark(0, DataType.INT64, v)


def _epochs(first, *per_epoch):
    """One side's script: barrier `first`, then each epoch's messages
    and the barrier that seals it."""
    out = [barrier(first)]
    for n, msgs in enumerate(per_epoch, start=first + 1):
        out += list(msgs) + [barrier(n)]
    return out


def _xcase(left, right, first=1, **kw):
    """One executor's life: both sides' epochs from barrier `first`."""
    return dict(left=_epochs(first, *left), right=_epochs(first, *right),
                n=len(left) + 1, kw=kw)


_KS = list(range(40, 60))          # a watermark of 50 closes half


def _expiry_a_null_in_a_non_key_column():
    xs = [None if k % 3 == 0 else k * 7 for k in _KS]
    return [_xcase(
        left=[[_xl(_KS, [100 + k for k in _KS], xs)],
              [_xwm(50)],
              [_xl([45, 55], [900, 901], [None, 1])]],
        right=[[_xr(_KS, [200 + k for k in _KS], ["s"] * len(_KS))],
               [_xwm(50)],
               [_xr([45, 55], [910, 911], ["p", "q"])]])]


def _expiry_a_varchar_payload_column():
    ss = [None if k % 4 == 0 else "v%d" % k for k in _KS]
    return [_xcase(
        left=[[_xl(_KS, _KS, _KS)], [_xwm(50)], [_xl([41, 59], [1, 2],
                                                     [3, 4])]],
        right=[[_xr(_KS, _KS, ss), _xr(_KS, [300 + k for k in _KS], ss)],
               [_xwm(51)], [_xr([41, 59], [5, 6], ["a", None])]])]


def _expiry_an_insert_still_in_the_memtable():
    # the rows and the watermark that closes them come in ONE epoch:
    # the inserts are spilled from the stage and annihilate
    return [_xcase(
        left=[[_xl(_KS, _KS, _KS), _xwm(50)], [_xl([42, 58], [1, 2],
                                                   [3, 4])]],
        right=[[_xr(_KS, _KS, ["s"] * len(_KS)), _xwm(50)],
               [_xr([42, 58], [5, 6], ["a", "b"])]])]


def _expiry_a_row_updated_earlier_in_the_epoch():
    upd = [Op.UPDATE_DELETE, Op.UPDATE_INSERT] * 2
    return [_xcase(
        left=[[_xl(_KS, _KS, _KS)],
              [_xl([43, 43, 57, 57], [43, 43, 57, 57], [43, -1, 57, -2],
                   ops=upd), _xwm(50)],
              [_xl([43, 57], [1, 2], [3, 4])]],
        right=[[_xr(_KS, _KS, ["s"] * len(_KS))],
               [_xr([44, 44, 56, 56], [44, 44, 56, 56],
                    ["s", "t", "s", "u"], ops=upd), _xwm(50)],
               [_xr([44, 56, 43, 57], [5, 6, 7, 8], ["a"] * 4)]])]


def _expiry_after_a_compaction():
    # update pairs leave dead refs; with COMPACT_MIN_REFS down the
    # sides compact at the barriers before the watermark comes
    churn = []
    for r in range(6):
        churn.append([_xl([k for k in _KS for _ in (0, 1)],
                          [k for k in _KS for _ in (0, 1)],
                          [x for k in _KS for x in (k + r, k + r + 1)],
                          ops=[Op.UPDATE_DELETE, Op.UPDATE_INSERT]
                          * len(_KS))])
    return [_xcase(
        left=[[_xl(_KS, _KS, _KS)]] + churn + [[_xwm(50)],
                                               [_xl([41, 59], [1, 2],
                                                    [3, 4])]],
        right=[[_xr(_KS, _KS, ["s"] * len(_KS))]] + [[]] * 6
        + [[_xwm(50)], [_xr([41, 59], [5, 6], ["a", "b"])]],
        compact_min_refs=8)]


def _expiry_after_a_recovery():
    ss = [None if k % 5 == 0 else "v%d" % k for k in _KS]
    xs = [None if k % 3 == 0 else k for k in _KS]
    return [
        _xcase(left=[[_xl(_KS, _KS, xs)]], right=[[_xr(_KS, _KS, ss)]]),
        _xcase(first=3,
               left=[[_xl([48, 52], [1, 2], [3, 4]), _xwm(50)],
                     [_xl([48, 52], [7, 8], [9, 10])]],
               right=[[_xwm(50)], [_xr([48, 52], [5, 6], ["a", "b"])]])]


N_L = Schema.of(lk=DataType.INT64, lid=DataType.FLOAT64, lx=DataType.INT64)


def _expiry_a_nan_in_a_float_pk_column():
    # a NaN never equals itself: the map has to lose such a row under
    # its own key, not under one built again from the arena
    def nl(ks, ids, xs):
        return StreamChunk.from_pydict(N_L, {"lk": ks, "lid": ids,
                                             "lx": xs})
    ids = [float("nan") if k == 44 else k + 0.5 for k in _KS]
    return [_xcase(
        left=[[nl(_KS, ids, _KS)], [_xwm(50)], [nl([44, 56], [1.5, 2.5],
                                                   [3, 4])]],
        right=[[_xr(_KS, _KS, ["s"] * len(_KS))], [_xwm(50)],
               [_xr([44, 56], [5, 6], ["a", "b"])]],
        schemas=(N_L, X_R))]


def _expiry_without_device_payload():
    return [dict(c, kw=dict(device_payload=False))
            for c in _expiry_a_null_in_a_non_key_column()]


def _expiry_of_a_cold_tier_side():
    # three floods of 100 keys a side under a cap of 32: most keys go
    # cold at the checkpoints; a probe reloads some, then a watermark
    # closes resident, reloaded and cold keys alike
    floods = [list(range(lo, lo + 100)) for lo in (0, 100, 200)]
    return [_xcase(
        left=[[_xl(f, f, f)] for f in floods]
        + [[_xl([5, 150, 250], [900, 901, 902], [1, 2, 3])],
           [_xwm(180)], [_xl([10, 170, 190, 290], [1, 2, 3, 4], [0] * 4)]],
        right=[[_xr(f, f, ["s%d" % k for k in f])] for f in floods]
        + [[], [_xwm(180)], [_xr([10, 170, 190, 290], [5, 6, 7, 8],
                                 ["a"] * 4)]],
        state_cap=32, pk=[0, 1])]


def _expiry_on_the_sharded_kernel():
    return [dict(c, kw=dict(mesh=True))
            for c in _expiry_a_varchar_payload_column()]


@pytest.mark.parametrize("case", [
    _expiry_a_null_in_a_non_key_column,
    _expiry_a_varchar_payload_column,
    _expiry_an_insert_still_in_the_memtable,
    _expiry_a_row_updated_earlier_in_the_epoch,
    _expiry_after_a_compaction,
    _expiry_after_a_recovery,
    _expiry_a_nan_in_a_float_pk_column,
    _expiry_without_device_payload,
    _expiry_of_a_cold_tier_side,
    _expiry_on_the_sharded_kernel,
], ids=lambda f: f.__name__[len("_expiry_"):])
def test_expire_below_by_the_column_is_the_row_at_a_time_expiry(
        case, monkeypatch, eight_devices):
    """What a watermark's expiry leaves behind does not depend on how
    it walks the dead rows: the flushes the store is handed (keys and
    values, in order), each side's pk map and free list, every count
    returned and every row emitted afterwards are those of the
    row-at-a-time reference above."""
    from jax.sharding import Mesh

    from risingwave_tpu.stream.executors.hash_join import _JoinSide
    by_the_column = _JoinSide.expire_below

    def run(expire):
        calls = []

        def recorded(self, key_pos, wm, seq=0):
            # the arena's pk columns are the map's keys, whatever built
            # them (a chunk, a compaction, a recovery, a cold reload)
            refs = np.fromiter(self.pk_to_ref.values(), dtype=np.int64,
                               count=len(self.pk_to_ref))
            cols = [np.where(self.arena.valid[i][refs],
                             self.arena.cols[i][refs].astype(object),
                             None).tolist() for i in self.pk_indices]
            assert repr(list(zip(*cols))) == repr(list(self.pk_to_ref))
            cold = len(self.cold_keys)
            n = expire(self, key_pos, wm, seq=seq)
            calls.append((self.table.table_id, int(wm), n, cold))
            return n

        monkeypatch.setattr(_JoinSide, "expire_below", recorded)
        store = _RecordingStore()
        emitted, sides = [], None
        for life in case():
            kw = dict(life["kw"])
            pk = kw.pop("pk", [1])
            l_schema, r_schema = kw.pop("schemas", (X_L, X_R))
            monkeypatch.setattr(_JoinSide, "COMPACT_MIN_REFS",
                                kw.pop("compact_min_refs", 4096))
            if kw.pop("mesh", False):
                kw["mesh"] = Mesh(np.asarray(eight_devices), ("d",))
            lt = StateTable(21, l_schema, pk, store, dist_key_indices=[])
            rt = StateTable(22, r_schema, pk, store, dist_key_indices=[])
            ex = HashJoinExecutor(
                MockSource(l_schema, life["left"]),
                MockSource(r_schema, life["right"]),
                left_keys=[0], right_keys=[0], left_table=lt,
                right_table=rt, **kw)
            msgs = asyncio.run(collect_until_n_barriers(ex, life["n"]))
            emitted += [m.to_records() for m in msgs if is_chunk(m)]
            sides = [(list(s.pk_to_ref.items()), list(s.free),
                      sorted(s.cold_keys)) for s in ex.sides]
        return store.flushes, sides, calls, emitted

    want = run(_row_at_a_time_expire_below)
    got = run(by_the_column)
    flushes, _sides, calls, emitted = got
    assert sum(n for _t, _wm, n, _cold in calls) > 0 and emitted
    if case is _expiry_of_a_cold_tier_side:
        assert any(cold for _t, _wm, _n, cold in calls)
    # (by their text where a NaN keeps equal rows from comparing equal)
    assert flushes == want[0] or repr(flushes) == repr(want[0])
    assert got[1:] == want[1:] or repr(got[1:]) == repr(want[1:])
