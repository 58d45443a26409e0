"""ISSUE 30: the checkpoint SST, built by the column.

``HummockLite._build_ssts`` turns the imms it drains into one sorted
columnar run and writes it with ``sst.RunWriter`` where the native
library is loaded. The row path (``full_key`` and ``encode_row`` per
entry, a sort of the tuples, ``build_sst``) is the reference: the same
SST bytes and the same ``info`` for the same imms, with and without the
library. What a column holds decides which path a table's batch takes,
and a counter says how many entries took which.
"""

import asyncio
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from risingwave_tpu import native
from risingwave_tpu.storage import sst
from risingwave_tpu.storage import value_codec as vc
from risingwave_tpu.storage.hummock import HummockLite
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.utils import spans as spans_mod
from risingwave_tpu.utils.ledger import LEDGER
from risingwave_tpu.utils.metrics import HISTORY, STORAGE

I64_MIN, I64_MAX = -(1 << 63), (1 << 63) - 1


@pytest.fixture(scope="module")
def nat():
    lib = native.lib()
    if lib is None:
        pytest.skip("the native library is not loaded here")
    return lib


def _key(i: int) -> bytes:
    return (i % 256).to_bytes(2, "big") + (i * 2654435761 % (1 << 40)) \
        .to_bytes(8, "big")


def _ints(n: int, width: int = 4) -> dict:
    return {_key(i): tuple(i * 31 + c - 1000 for c in range(width))
            for i in range(n)}


# Each drain: a list of (epoch, {table_id: {user key: row tuple | None}})
# and the entries it must file under ``row`` (the rest go ``columnar``).
DRAINS = {
    "empty_drain": ([], 0),
    "empty_tables": ([(5, {1: {}, 2: {}})], 0),
    "one_table_ints": ([(5, {7: _ints(3000, 6)})], 0),
    # more than one block, several tables and epochs in one drain, a
    # key written in two epochs (the newer version sorts first)
    "tables_and_epochs": ([
        (5, {9: _ints(900), 3: _ints(40, 2), 70000: _ints(5, 1)}),
        (6, {3: {_key(1): (1, 2), _key(77): (3, 4)}, 4: _ints(3, 3)}),
        (8, {3: {_key(1): None}, 9: _ints(20)}),
    ], 0),
    "tombstones": ([(5, {1: {_key(i): None if i % 3 == 0 else (i, "r")
                             for i in range(100)}})], 0),
    "only_tombstones": ([(5, {1: {_key(i): None for i in range(9)},
                              2: {b"k": None}})], 0),
    "keys_with_zero_bytes_and_prefixes": ([(5, {1: {
        b"": (0,), b"\x00": (1,), b"\x00\x00": (2,), b"\x00\xff": (3,),
        b"\x00\x01": (4,), b"a": (5,), b"a\x00": (6,), b"a\x00b": (7,),
        b"ab": (8,), b"a\x00\x00": (9,), b"\xff": (10,),
        b"\xff\x00": (11,), b"abc" * 50: (12,), b"\x01": (13,),
    }, 2: {b"\x00": (1,), b"": (0,)}}),
        (6, {1: {b"a": None, b"\x00": (14,)}})], 0),
    "int_edges": ([(5, {1: {
        b"a": (0, -1, 1), b"b": (I64_MIN, I64_MAX, -(1 << 62)),
        b"c": (127, 128, -128), b"d": (1 << 32, -(1 << 32), 63),
        b"e": (I64_MAX - 1, I64_MIN + 1, 64)}})], 0),
    "numpy_ints": ([(5, {1: {
        b"a": (np.int64(-5), np.int32(7), np.uint8(255), 1),
        b"b": (np.int64(I64_MIN), np.int32(-(1 << 31)), np.uint8(0), 2),
        b"c": (3, np.int16(-9), np.uint32((1 << 32) - 1), np.int8(-1)),
    }})], 0),
    "floats": ([(5, {1: {
        b"a": (1.5, float("nan"), -0.0), b"b": (0.0, float("inf"), 1e-320),
        b"c": (-2.25, float("-inf"), np.float64(3.5)),
        b"d": (np.float32(0.1), np.float64("nan"), None),
        b"e": (1e308, 5e-324, np.float16(2.0))}})], 0),
    "str_non_ascii": ([(5, {1: {
        b"a": ("", "plain"), b"b": ("héllo", "日本語"), b"c": ("𝄞", "\x00"),
        b"d": ("a" * 300, "ß"), b"e": (None, "x")}})], 0),
    "bytes_values": ([(5, {1: {
        b"a": (b"", b"\x00\x01"), b"b": (b"x" * 200, bytearray(b"ba")),
        b"c": (None, b"\xff")}})], 0),
    "bools": ([(5, {1: {
        b"a": (True, np.bool_(False)), b"b": (False, np.bool_(True)),
        b"c": (None, True)}})], 0),
    "nulls_in_every_column": ([(5, {1: {
        b"a": (None, 1.5, "s", b"b", True, None),
        b"b": (1, None, "t", b"c", False, None),
        b"c": (2, 2.5, None, b"d", True, None),
        b"d": (3, 3.5, "u", None, None, None),
        b"e": (None, None, None, None, None, None)}})], 0),
    "empty_rows": ([(5, {1: {b"a": (), b"b": (), b"c": None}})], 0),
    # a batch of one row whose values are shorter than the bound the
    # native encoder wants free before each: its buffer holds them
    "one_row_bool": ([(5, {1: {b"k": (True,)}})], 0),
    "one_row_null": ([(5, {1: {b"k": (None,)}})], 0),
    "one_row_nulls": ([(5, {1: {b"k": (None, None)}})], 0),
    "one_row_float_null": ([(5, {1: {b"k": (1.5, None)}})], 0),
    "one_row_int_min_bool": ([(5, {1: {b"k": (I64_MIN, False)}})], 0),
    "one_row_int_min_null": ([(5, {1: {b"k": (I64_MIN, None)}})], 0),
    "one_row_wide_bools": ([(5, {1: {b"k": (True,) * 200},
                                 2: {b"k": (None,) * 200, b"t": None}})], 0),
    # what the columns cannot take as arrays goes row by row, the same
    # bytes: rows of differing arity, a column of mixed types, an int
    # subclass, np.uint64; the other table of the drain stays columnar
    "differing_arity": ([(5, {1: {b"a": (1,), b"b": (1, 2), b"c": None},
                              2: _ints(10)})], 3),
    "mixed_type_column": ([(5, {1: {b"a": (1, "x"), b"b": (2.5, "y"),
                                    b"c": ("z", True)},
                               2: _ints(10)})], 3),
    "int_and_bool_column": ([(5, {1: {b"a": (1,), b"b": (True,)}})], 2),
    "uint64_column": ([(5, {1: {b"a": (np.uint64(7),),
                                b"b": (np.uint64(1 << 62),)}})], 2),
}


def _row_entries(take):
    """The reference: every entry by ``full_key`` and ``encode_row``,
    sorted as tuples."""
    entries = []
    for epoch, tables in take:
        for table_id, kv in tables.items():
            for key, value in kv.items():
                entries.append((sst.full_key(table_id, key, epoch),
                                value is None,
                                b"" if value is None
                                else vc.encode_row(value)))
    entries.sort(key=lambda t: t[0])
    return entries


def _store_with(take) -> HummockLite:
    store = HummockLite(MemObjectStore())
    for epoch, tables in take:
        for table_id, kv in tables.items():
            store.ingest_batch(table_id, kv.items(), epoch)
        store.seal_epoch(epoch)
    return store


def _built(take):
    """``_build_ssts`` over the drain: the payloads and what the build
    counter gained by path."""
    store = _store_with(take)
    before = {p: STORAGE.sst_build_entries.get(path=p)
              for p in ("columnar", "row")}
    payloads = store.build_ssts(max((e for e, _t in take), default=1))
    gained = {p: STORAGE.sst_build_entries.get(path=p) - before[p]
              for p in before}
    return payloads, gained


def digests() -> dict:
    """name → sha256 of the built SST and its info, for every drain:
    what the subprocess without the native library prints."""
    out = {}
    for name, (take, _row) in DRAINS.items():
        payloads, _gained = _built(take)
        out[name] = [[hashlib.sha256(p["data"]).hexdigest(), p["sst"]]
                     for p in payloads]
    return out


# -- 1. the same bytes as the row build --------------------------------------


@pytest.mark.parametrize("name", sorted(DRAINS))
def test_columnar_build_is_the_row_build_byte_for_byte(nat, name):
    take, row = DRAINS[name]
    payloads, gained = _built(take)
    entries = _row_entries(take)
    if not entries:
        assert payloads == [] and gained == {"columnar": 0, "row": 0}
        return
    (p,) = payloads
    data, info = sst.build_sst(1, entries)
    assert p["data"] == data
    assert p["sst"] == info
    assert p["entries"] == len(entries)
    assert (p["columnar_entries"], p["row_entries"]) == \
        (len(entries) - row, row)
    assert gained == {"columnar": len(entries) - row, "row": row}
    assert p["tables"] == len({t for _e, ts in take for t in ts})


@pytest.fixture(scope="module")
def without_native():
    """``digests()`` in a process where RW_TPU_DISABLE_NATIVE=1."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = ("import json, sys; sys.path.insert(0, %r); "
            "import test_checkpoint_build as t; "
            "from risingwave_tpu import native; "
            "assert native.lib() is None; "
            "print(json.dumps(t.digests()))" % here)
    env = dict(os.environ, RW_TPU_DISABLE_NATIVE="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(here))
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", sorted(DRAINS))
def test_same_sst_without_the_native_library(nat, without_native, name):
    take, _row = DRAINS[name]
    payloads, _gained = _built(take)
    assert without_native[name] == [
        [hashlib.sha256(p["data"]).hexdigest(), p["sst"]]
        for p in payloads]


def test_without_the_library_every_entry_is_a_row_entry(monkeypatch):
    monkeypatch.setattr(native, "lib", lambda: None)
    take, _row = DRAINS["tables_and_epochs"]
    (p,), gained = _built(take)
    n = len(_row_entries(take))
    assert (p["columnar_entries"], p["row_entries"]) == (0, n)
    assert gained == {"columnar": 0, "row": n}


def test_built_sst_reads_back_through_the_store(nat):
    take, _row = DRAINS["tables_and_epochs"]
    store = _store_with(take)
    store.sync(8)
    assert store.get(3, _key(1), 8) is None          # the tombstone
    assert store.get(3, _key(1), 6) == (1, 2)
    assert store.get(3, _key(77), 8) == (3, 4)
    assert store.get(9, _key(5), 5) == _ints(900)[_key(5)]
    assert store.get(70000, _key(4), 8) == _ints(5, 1)[_key(4)]


# -- 2. the native entry points against their twins --------------------------


KEY_SETS = {
    "none": [],
    "plain": [_key(i) for i in range(500)],
    "zeros_and_prefixes": [b"", b"\x00", b"\x00\x00", b"a", b"a\x00",
                           b"a\x00b", b"\x00\xff", b"\xff" * 40,
                           b"\x00" * 33],
}


@pytest.mark.parametrize("table_id,epoch", [
    (0, 0), (1, 1), (70000, 1234567890123), ((1 << 32) - 1, sst.EPOCH_MASK)])
@pytest.mark.parametrize("keys", sorted(KEY_SETS))
def test_full_keys_is_full_key(nat, keys, table_id, epoch):
    users = KEY_SETS[keys]
    blob, lens = sst.full_keys(nat, table_id, users, epoch)
    want = [sst.full_key(table_id, k, epoch) for k in users]
    assert blob.tobytes() == b"".join(want)
    assert lens.tolist() == [len(k) for k in want]


def test_full_keys_refuses_a_table_id_full_key_refuses(nat):
    import struct
    for bad in (-1, 1 << 32):
        with pytest.raises(struct.error):
            sst.full_key(bad, b"k", 1)
        with pytest.raises(struct.error):
            sst.full_keys(nat, bad, [b"k"], 1)


VALUE_BATCHES = {
    name: [v for _e, tables in take for kv in tables.values()
           for v in kv.values()]
    for name, (take, row) in DRAINS.items()
    if row == 0 and len(take) == 1 and len(take[0][1]) == 1
}


@pytest.mark.parametrize("name", sorted(VALUE_BATCHES))
def test_encode_values_is_encode_row(nat, name):
    values = VALUE_BATCHES[name]
    assert values, name
    blob, lens, columnar = vc.encode_values(nat, values)
    want = [b"\x01" if v is None else b"\x00" + vc.encode_row(v)
            for v in values]
    assert columnar
    assert blob.tobytes() == b"".join(want)
    assert lens.tolist() == [len(w) for w in want]
    for v, w in zip(values, want):
        if v is not None:       # and what was written decodes
            got = vc.decode_row(w[1:])
            assert len(got) == len(v)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sort_run_is_the_sort_of_the_tuples(nat, seed):
    rng = np.random.default_rng(seed)
    keys = {bytes(rng.integers(0, 3, size=rng.integers(8, 14),
                               dtype=np.uint8)) for _ in range(2000)}
    pairs = [(k, bytes(rng.integers(0, 256, size=rng.integers(1, 9),
                                    dtype=np.uint8))) for k in keys]
    run = sst.Run(
        np.frombuffer(b"".join(k for k, _v in pairs), dtype=np.uint8),
        np.array([len(k) for k, _v in pairs], dtype=np.int32),
        np.frombuffer(b"".join(v for _k, v in pairs), dtype=np.uint8),
        np.array([len(v) for _k, v in pairs], dtype=np.int32))
    got = sst.sort_run(nat, run)
    want = sorted(pairs)
    assert got.keys.tobytes() == b"".join(k for k, _v in want)
    assert got.key_lens.tolist() == [len(k) for k, _v in want]
    assert got.vals.tobytes() == b"".join(v for _k, v in want)
    assert got.val_lens.tolist() == [len(v) for _k, v in want]


def test_sort_run_refuses_one_key_twice(nat):
    run = sst.Run(np.frombuffer(b"abcdefgh" * 2, dtype=np.uint8),
                  np.array([8, 8], dtype=np.int32),
                  np.frombuffer(b"\x00\x00", dtype=np.uint8),
                  np.array([1, 1], dtype=np.int32))
    with pytest.raises(ValueError, match="twice"):
        sst.sort_run(nat, run)


# -- 3. what cannot be encoded raises what it raised -------------------------


@pytest.mark.parametrize("bad,message", [
    (I64_MAX + 1, "int out of int64 range"),
    (I64_MIN - 1, "int out of int64 range"),
    (np.uint64(1 << 63), "int out of int64 range"),
    (object, "unencodable value"),
    ([1, 2], "unencodable value"),
    ({"a": 1}, "unencodable value"),
])
def test_unencodable_values_raise_what_encode_row_raises(nat, bad, message):
    with pytest.raises(TypeError, match=message):
        vc.encode_row((1, bad))
    take = [(5, {1: {b"a": (1, 2), b"b": (1, bad), b"c": (3, 4)}})]
    with pytest.raises(TypeError, match=message):
        _store_with(take).build_ssts(5)


def test_a_str_that_is_not_utf8_raises_what_encode_row_raises(nat):
    take = [(5, {1: {b"a": ("ok",), b"b": ("\ud800",)}})]
    with pytest.raises(UnicodeEncodeError):
        vc.encode_row(("\ud800",))
    with pytest.raises(UnicodeEncodeError):
        _store_with(take).build_ssts(5)


# -- 4. the counter, the span, the history -----------------------------------


@pytest.fixture
def _fresh_books():
    LEDGER.clear()
    HISTORY.clear()
    spans_mod.set_current_epoch(0)
    yield
    LEDGER.clear()
    HISTORY.clear()


def test_a_mixed_type_column_goes_by_row_and_reads_back_equal(nat):
    mixed = {b"a": (1, "x"), b"b": (2.5, None), b"c": ("s", b"raw")}
    plain = _ints(50)
    store = _store_with([(5, {1: mixed, 2: plain})])
    before = {p: STORAGE.sst_build_entries.get(path=p)
              for p in ("columnar", "row")}
    store.sync(5)
    assert STORAGE.sst_build_entries.get(path="row") - before["row"] == 3
    assert STORAGE.sst_build_entries.get(path="columnar") \
        - before["columnar"] == 50
    fresh = HummockLite(store.obj)              # from the object store
    for key, row in mixed.items():
        assert fresh.get(1, key, 5) == row
    assert dict(fresh.iter(2, epoch=5)) == plain


def test_view_over_int_and_string_columns_builds_by_the_column(
        nat, _fresh_books):
    from risingwave_tpu.frontend.session import Frontend

    async def run():
        fe = Frontend(HummockLite(MemObjectStore()), min_chunks=4)
        await fe.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', "
            "nexmark.table.type='bid', nexmark.event.num=200000, "
            "nexmark.max.chunk.size=256, "
            "nexmark.min.event.gap.in.ns=50000000)")
        await fe.execute(
            "CREATE MATERIALIZED VIEW v AS SELECT channel, "
            "MAX(price) AS top, COUNT(*) AS n FROM bid GROUP BY channel")
        before = {p: STORAGE.sst_build_entries.get(path=p)
                  for p in ("columnar", "row")}
        for _ in range(5):
            await fe.step(1)
        hist = await fe.execute("SELECT * FROM rw_metrics_history")
        trace = spans_mod.EPOCH_TRACER.rows()
        await fe.close()
        gained = {p: STORAGE.sst_build_entries.get(path=p) - before[p]
                  for p in before}
        return hist, trace, gained

    hist, trace, gained = asyncio.run(run())
    by_epoch = {}
    for _seq, epoch, _ts, _iv, name, value, _dom in hist:
        by_epoch.setdefault(epoch, {})[name] = value
    rows = [h for h in by_epoch.values() if "ckpt.build_s" in h]
    assert len(rows) >= 5
    assert all(h["ckpt.build_row_entries"] == 0 for h in rows)
    assert sum(h["ckpt.build_columnar_entries"] for h in rows) > 0
    builds = [json.loads(r[10]) for r in trace
              if r[3] == "checkpoint.build"]
    assert builds and all(b["row_entries"] == 0 for b in builds)
    assert all(b["columnar_entries"] == b["entries"] for b in builds)
    # the counter and the spans agree
    assert gained["row"] == 0
    assert gained["columnar"] >= sum(b["entries"] for b in builds[-5:]) > 0
