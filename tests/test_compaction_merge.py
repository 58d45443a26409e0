"""The one compaction merge (ISSUE 26): native path against its twin.

``storage/merge.merge_runs`` runs whole columnar runs through the
native library where it is loaded and the row-at-a-time Python loop
where it is not. The loop is the specification: for the same inputs
the native path must return byte-identical SSTs and equal infos, which
every case below holds it to, next to a brute-force model of the GC
rule that holds the twin itself to the rule as written. Both
compaction arms call the same function and must write the same bytes.
"""

import struct

import numpy as np
import pytest

from risingwave_tpu import native
from risingwave_tpu.storage import merge as merge_mod
from risingwave_tpu.storage.compactor import execute_task
from risingwave_tpu.storage.hummock import HummockLite
from risingwave_tpu.storage.object_store import (
    LocalFsObjectStore, MemObjectStore,
)
from risingwave_tpu.storage.sst import (
    Sst, build_sst, full_key, split_full_key, user_prefix,
)
from risingwave_tpu.utils import spans
from risingwave_tpu.utils.metrics import STORAGE

requires_native = pytest.mark.skipif(
    native.lib() is None,
    reason="no native library (g++ missing): only the twin exists here")


@pytest.fixture(autouse=True)
def _leave_no_spans():
    """`execute_task` files its `checkpoint.compact` span in the
    process-wide flight recorder; tests that count those spans may
    run after these in one process."""
    yield
    spans.EPOCH_TRACER.clear()


def E(n: int) -> int:
    return n << 16


SAFE = E(6)

# keys with 0x00 inside and at the end, byte-prefixes of one another,
# the empty key: what sst._esc_user exists for
TRICKY = [b"", b"\x00", b"\x00\x00", b"\x00\xff", b"a", b"a\x00",
          b"a\x00\x00", b"a\x00\xff", b"a\xff", b"ab", b"ab\x00", b"b"]


def _key_pool(rng, n, tricky):
    pool = set(TRICKY) if tricky else set()
    while len(pool) < n:
        ln = int(rng.integers(1, 10))
        alphabet = [0, 0, 1, 97, 98, 255] if tricky else [97, 98, 99, 100]
        pool.add(bytes(rng.choice(alphabet, ln).astype(np.uint8)))
    return sorted(pool)


def _entries(rng, keys, tables, rank, *, share, versions, row_len,
             tomb_share=0.2):
    """One run's entries {full key: (tombstone, row)}: `share` of the
    (table, key) pairs, 1..versions epochs each out of 1..12 (so the
    same full key turns up in several runs); the row names the rank."""
    out = {}
    for t in tables:
        for k in keys:
            if rng.random() >= share:
                continue
            n = int(rng.integers(1, versions + 1))
            for e in rng.choice(np.arange(1, 13), n, replace=False):
                tomb = bool(rng.random() < tomb_share)
                row = b"" if tomb else (
                    b"r%d." % rank
                    + bytes(rng.integers(0, 256, int(rng.integers(
                        0, row_len))).astype(np.uint8)))
                out[full_key(t, k, E(int(e)))] = (tomb, row)
    return out


def _split_at_user_keys(entries, parts):
    """Sorted entries → `parts` key-disjoint ascending runs."""
    fks = sorted(entries)
    runs, start = [], 0
    for p in range(1, parts + 1):
        end = len(fks) * p // parts
        while 0 < end < len(fks) and fks[end][:-8] == fks[end - 1][:-8]:
            end += 1
        if end > start:
            runs.append({fk: entries[fk] for fk in fks[start:end]})
        start = max(start, end)
    return runs


class Case:
    """Input SSTs in an object store, as both arms see them."""

    def __init__(self, l0_runs, l1_runs, target_bytes):
        self.obj = MemObjectStore()
        self.target = target_bytes
        next_id = iter(range(1, 10_000))
        self.l0 = [self._put(next(next_id), r) for r in l0_runs]
        self.l1 = [self._put(next(next_id), r) for r in l1_runs]
        self.ranked = l0_runs + l1_runs

    def _put(self, sst_id, entries):
        data, info = build_sst(
            sst_id, [(fk, t, row) for fk, (t, row) in sorted(
                entries.items())])
        self.obj.upload(f"data/{sst_id}.sst", data)
        return info

    def read(self, info):
        return self.obj.read(f"data/{info['id']}.sst")

    def run(self, path, bottom):
        outs = []
        ids = iter(range(50_000, 60_000))
        args = (self.read, SAFE, bottom, self.target,
                lambda: next(ids), lambda d, i: outs.append((d, i)))
        if path == "python":
            counts = merge_mod._merge_python(self.l0 + self.l1, *args)
        else:
            counts = merge_mod._merge_native(
                native.lib(), self.l0, self.l1, *args)
        return outs, counts


def _model(ranked, bottom):
    """The rule as ISSUE 26 states it, over plain dicts: the survivors
    in order as (full key, tombstone, row)."""
    newest = {}
    for entries in reversed(ranked):        # lowest rank written last
        newest.update(entries)
    out, last_tu, kept = [], None, False
    for fk in sorted(newest):
        tomb, row = newest[fk]
        if fk[:-8] != last_tu:
            last_tu, kept = fk[:-8], False
        epoch = split_full_key(fk)[2]
        if epoch > SAFE:
            out.append((fk, tomb, row))
        elif not kept:
            kept = True
            if not (tomb and bottom):
                out.append((fk, tomb, row))
    return out


def _rows(outs):
    return [e for data, info in outs for e in Sst(data, info).iter_from(b"")]


def _check_cuts(outs):
    """Every cut at a user-key boundary; runs disjoint and ascending."""
    for (_d, a), (_d2, b) in zip(outs, outs[1:]):
        assert user_prefix(a["largest"]) < user_prefix(b["smallest"])


# -- seeded random inputs, by what they contain --------------------------


def _random(rng, *, keys=60, tricky=False, tables=(7,), l0=3, l1=2,
            share=0.6, versions=4, row_len=24, target=4 << 20):
    pool = _key_pool(rng, keys, tricky)
    l0_runs = [_entries(rng, pool, tables, r, share=share,
                        versions=versions, row_len=row_len)
               for r in range(l0)]
    level = _entries(rng, pool, tables, l0, share=0.9, versions=versions,
                     row_len=row_len)
    l1_runs = _split_at_user_keys(level, l1) if l1 else []
    return Case([r for r in l0_runs if r], l1_runs, target)


def _long_key(rng):
    # a 5000-byte user key: past the native block decoder's window, so
    # that block takes the Python decoder on the way in
    pool = [b"k" * 5000, b"k" * 5000 + b"\x00", b"j", b"l"]
    runs = [_entries(rng, pool, (3,), r, share=1.0, versions=3,
                     row_len=16) for r in range(3)]
    return Case(runs[:2], [runs[2]], 4 << 20)


def _not_a_level(rng):
    # "L1" runs that overlap: the native path must hold them all
    # rather than stream them one at a time
    pool = _key_pool(rng, 40, False)
    runs = [_entries(rng, pool, (7,), r, share=0.7, versions=3,
                     row_len=24) for r in range(4)]
    return Case(runs[:2], runs[2:], 4 << 20)


SCENARIOS = {
    # the same full key in two ranks (12 epochs, 4 versions a key, 5
    # runs), several versions on both sides of SAFE, tombstones as the
    # newest version at or below it: all of them, in every case below
    "plain": lambda rng: _random(rng),
    "nul_and_prefix_keys": lambda rng: _random(rng, tricky=True, keys=40),
    "several_tables": lambda rng: _random(
        rng, tricky=True, keys=25, tables=(1, 2, 256, 2 ** 31)),
    "two_or_more_ssts": lambda rng: _random(
        rng, keys=1500, versions=3, row_len=120, l0=4, l1=3,
        target=120_000),
    "every_user_key_its_own_sst": lambda rng: _random(
        rng, keys=30, tricky=True, target=1),
    "cut_inside_a_block": lambda rng: _random(
        rng, keys=300, row_len=60, target=9_000),
    "empty_l0_manual_full": lambda rng: _random(rng, l0=0, l1=3),
    "no_l1_yet": lambda rng: _random(rng, l0=4, l1=0),
    "single_run": lambda rng: _random(rng, l0=1, l1=0),
    "single_l1_run": lambda rng: _random(rng, l0=0, l1=1),
    "many_l0_runs": lambda rng: _random(rng, l0=9, l1=4, keys=120),
    "key_past_the_native_decoders_window": _long_key,
    "l1_inputs_that_overlap": _not_a_level,
}


@requires_native
@pytest.mark.parametrize("bottom", [True, False], ids=["bottom", "upper"])
@pytest.mark.parametrize("seed", [11, 2026])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_native_merge_is_byte_identical_to_the_twin(scenario, seed, bottom):
    case = SCENARIOS[scenario](np.random.default_rng(seed))
    twin, twin_counts = case.run("python", bottom)
    nat, nat_counts = case.run("native", bottom)
    assert nat_counts == twin_counts
    assert [i for _d, i in nat] == [i for _d, i in twin]
    assert [d for d, _i in nat] == [d for d, _i in twin]
    _check_cuts(twin)
    if scenario == "two_or_more_ssts":
        assert len(twin) >= 2
        # blocks of more than one kind: full ones and the cut's tail
        assert max(len(Sst(d).index) for d, _i in twin) >= 2


@pytest.mark.parametrize("bottom", [True, False], ids=["bottom", "upper"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_twin_keeps_exactly_what_the_rule_says(scenario, bottom):
    case = SCENARIOS[scenario](np.random.default_rng(5))
    outs, (entries_in, entries_out) = case.run("python", bottom)
    want = _model(case.ranked, bottom)
    assert _rows(outs) == want
    assert entries_in == sum(len(r) for r in case.ranked)
    assert entries_out == len(want) == sum(i["count"] for _d, i in outs)
    assert sum(i["tombstones"] for _d, i in outs) \
        == sum(1 for _fk, tomb, _row in want if tomb)


def _one_key(versions):
    """versions: [(rank, epoch number, row or None for a delete)]"""
    runs = {}
    for rank, e, row in versions:
        runs.setdefault(rank, {})[full_key(1, b"k", E(e))] = \
            (row is None, row or b"")
    return [runs[r] for r in sorted(runs)]


@pytest.mark.parametrize("path", [
    "python", pytest.param("native", marks=requires_native)])
def test_the_rule_case_by_case(path):
    def survivors(versions, bottom):
        runs = _one_key(versions)
        outs, _counts = Case(runs[:-1], runs[-1:], 4 << 20).run(
            path, bottom)
        return [(split_full_key(fk)[2] >> 16, None if tomb else row)
                for fk, tomb, row in _rows(outs)]

    # the same full key in two ranks: the newer rank wins
    assert survivors([(0, 9, b"new"), (1, 9, b"old")], True) \
        == [(9, b"new")]
    # every version above SAFE stays; of those at or below it, the
    # newest only (SAFE is epoch 6)
    many = [(0, 9, b"v9"), (0, 7, b"v7"), (1, 6, b"v6"), (1, 4, b"v4"),
            (2, 2, b"v2")]
    assert survivors(many, True) == [(9, b"v9"), (7, b"v7"), (6, b"v6")]
    # a tombstone as the newest version at or below SAFE: gone at the
    # bottom with everything under it, kept (alone) above the bottom
    dead = [(0, 8, b"v8"), (0, 5, None), (1, 3, b"v3")]
    assert survivors(dead, True) == [(8, b"v8")]
    assert survivors(dead, False) == [(8, b"v8"), (5, None)]
    # a tombstone ABOVE SAFE is a version like any other
    assert survivors([(0, 8, None), (1, 3, b"v3")], True) \
        == [(8, None), (3, b"v3")]


# -- both arms, one result -------------------------------------------------


def _loaded_store(obj, mode):
    """An L1 of several runs under four L0 runs, compaction pending:
    the state `compact()` and a dedicated task both start from."""
    h = HummockLite(obj)
    h.compaction_mode = "dedicated"        # commits never compact
    rng = np.random.default_rng(3)
    for e in range(1, 5):
        h.ingest_batch(1, [(b"key%05d" % i, (i, "x" * 40, e))
                           for i in range(0, 4000)], E(e))
        h.seal_epoch(E(e))
        h.sync(E(e))
    h.compaction_mode = "inline"
    h.compact()
    for e in range(5, 9):
        ks = rng.integers(0, 6000, 700)
        h.compaction_mode = "dedicated"
        h.ingest_batch(1, [(b"key%05d" % i, None if i % 7 == 0
                            else (int(i), "y" * 30, e)) for i in ks], E(e))
        h.seal_epoch(E(e))
        h.sync(E(e))
    h.compaction_mode = mode
    return h


def test_inline_and_dedicated_arms_write_identical_ssts(monkeypatch):
    import risingwave_tpu.storage.hummock as hummock_mod
    monkeypatch.setattr(hummock_mod, "L1_TARGET_SST_BYTES", 100_000)
    inline_obj, dedicated_obj = MemObjectStore(), MemObjectStore()
    a = _loaded_store(inline_obj, "inline")
    b = _loaded_store(dedicated_obj, "dedicated")
    assert a.levels == b.levels and a.levels[0] == 4 and a.levels[1] >= 2

    counts = a.compact()
    snap = b.level_snapshot()
    grant = b.reserve_task(
        [i["id"] for i in snap["l0"] + snap["l1"]], id_block=32)
    result = execute_task(dedicated_obj, {
        "inputs_l0": snap["l0"], "inputs_l1": snap["l1"],
        "bottom": True, "target_bytes": 100_000, **grant})
    b.apply_version_delta(
        [i["id"] for i in snap["l0"] + snap["l1"]], result["outputs"])

    assert len(a._l1) >= 3
    assert [dict(i, id=0) for i in a._l1] \
        == [dict(i, id=0) for i in result["outputs"]]
    assert [inline_obj.read(f"data/{i['id']}.sst") for i in a._l1] \
        == [dedicated_obj.read(f"data/{i['id']}.sst")
            for i in result["outputs"]]
    assert counts["write_bytes"] == result["bytes_written"]
    assert list(a.iter(1, E(8))) == list(b.iter(1, E(8)))


def test_dedicated_arm_id_block_overflow_still_raises():
    obj = MemObjectStore()
    h = _loaded_store(obj, "dedicated")
    snap = h.level_snapshot()
    ids = [i["id"] for i in snap["l0"] + snap["l1"]]
    grant = h.reserve_task(ids, id_block=2)
    with pytest.raises(RuntimeError, match="compaction output overflow"):
        execute_task(obj, {
            "inputs_l0": snap["l0"], "inputs_l1": snap["l1"],
            "bottom": True, "target_bytes": 50_000, **grant})
    # the two SSTs it could name were cut and uploaded before the third
    # asked for an id outside the block
    base = grant["output_base"]
    assert obj.exists(f"data/{base}.sst")
    assert obj.exists(f"data/{base + 1}.sst")
    assert not obj.exists(f"data/{base + 2}.sst")


# -- the format did not change ---------------------------------------------------


@requires_native
def test_a_data_dir_written_by_the_twin_is_read_compacted_and_served(
        tmp_path, monkeypatch):
    """Every SST of this data dir, checkpoints and compaction outputs,
    comes from the row-at-a-time code with no native library (what the
    parent commit wrote, and any host without g++ writes); the native
    path then opens it, compacts it and serves it."""
    def load(h, epochs):
        for e in epochs:
            h.ingest_batch(2, [(struct.pack(">I", i) + b"\x00k",
                                None if (i + e) % 5 == 0 else (i, e))
                               for i in range(e, 3000, 3)], E(e))
            h.seal_epoch(E(e))
            h.sync(E(e))

    with monkeypatch.context() as m:
        m.setattr(native, "_lib", None)
        m.setattr(native, "_tried", True)
        old = HummockLite(LocalFsObjectStore(str(tmp_path)))
        load(old, range(1, 7))
        assert old.levels[1] >= 1           # one inline compaction ran
        before = STORAGE.compaction_merge_entries.get(path="python")
        old.compact()
        assert STORAGE.compaction_merge_entries.get(path="python") > before
        want = list(old.iter(2, E(6)))
    assert native.lib() is not None

    h = HummockLite(LocalFsObjectStore(str(tmp_path)))
    assert list(h.iter(2, E(6))) == want
    load(h, range(7, 10))
    counts = h.compact()
    assert counts["merge"] == "native" and counts["entries_in"] > 0
    served = dict(h.iter(2, E(9)))
    for i in (6, 7, 8, 2999):
        assert h.get(2, struct.pack(">I", i) + b"\x00k", E(9)) \
            == served.get(struct.pack(">I", i) + b"\x00k")
    # and the twin reads what the native path wrote
    with monkeypatch.context() as m:
        m.setattr(native, "_lib", None)
        m.setattr(native, "_tried", True)
        again = HummockLite(LocalFsObjectStore(str(tmp_path)))
        assert dict(again.iter(2, E(9))) == served
