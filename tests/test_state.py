"""State layer tests.

Mirrors the core cases of the reference's
src/stream/src/common/table/test_state_table.rs (write-read across commit,
iteration order, update-pair atomicity) plus mem_table.rs op-merge rules and
keycodec ordering properties.
"""

import decimal

import numpy as np
import pytest

from risingwave_tpu.common import (
    DataType, Epoch, EpochPair, Op, Schema, StreamChunk,
)
from risingwave_tpu.common.hash import hash_columns, hash_columns_host
from risingwave_tpu.state import (
    KeyOp, MemTable, MemTableError, MemoryStateStore, StateTable,
    decode_memcomparable, encode_memcomparable,
)


# -- key codec ---------------------------------------------------------------

def test_memcomparable_ordering():
    types = [DataType.INT64]
    vals = [-(10**12), -5, -1, 0, 1, 7, 10**12]
    encs = [encode_memcomparable((v,), types) for v in vals]
    assert encs == sorted(encs)
    ftypes = [DataType.FLOAT64]
    fvals = [float("-inf"), -2.5, -0.0, 0.0, 1e-300, 3.7, float("inf")]
    fencs = [encode_memcomparable((v,), ftypes) for v in fvals]
    assert sorted(fencs) == fencs
    stypes = [DataType.VARCHAR]
    svals = ["", "a", "a\x00b", "ab", "b"]
    sencs = [encode_memcomparable((v,), stypes) for v in svals]
    assert sorted(sencs) == sencs


def test_memcomparable_roundtrip():
    types = [DataType.INT64, DataType.VARCHAR, DataType.FLOAT64,
             DataType.BOOLEAN, DataType.DECIMAL]
    # DECIMAL is physical: the scaled-int64 payload (9.5001 → 95001)
    row = (42, "hello\x00world", -3.25, True, 95001)
    enc = encode_memcomparable(row, types)
    assert decode_memcomparable(enc, types) == row
    nonerow = (None, None, None, None, None)
    assert decode_memcomparable(
        encode_memcomparable(nonerow, types), types) == nonerow
    # composite ordering: first column dominates
    a = encode_memcomparable((1, "z"), [DataType.INT64, DataType.VARCHAR])
    b = encode_memcomparable((2, "a"), [DataType.INT64, DataType.VARCHAR])
    assert a < b


def test_hash_host_device_consistency():
    """Host state partitioning must agree with device dispatch bit-for-bit."""
    import jax.numpy as jnp
    ints = np.arange(-500, 500, dtype=np.int64) * 997
    floats = np.linspace(-5, 5, 1000)
    small = np.arange(1000, dtype=np.int32)
    dev = np.asarray(hash_columns([jnp.asarray(ints), jnp.asarray(floats),
                                   jnp.asarray(small)]))
    host = hash_columns_host([ints, floats, small])
    assert np.array_equal(dev, host)


# -- mem table ---------------------------------------------------------------

def test_mem_table_merge_rules():
    mt = MemTable()
    mt.insert(b"k1", (1,))
    mt.delete(b"k1", (1,))          # insert+delete annihilate
    assert not mt.is_dirty()
    mt.insert(b"k2", (2,))
    with pytest.raises(MemTableError):
        mt.insert(b"k2", (2,))      # double insert
    mt.update(b"k2", (2,), (3,))    # update over buffered insert folds in
    assert mt.get(b"k2") == (True, (3,))
    mt.delete(b"k3", (9,))
    with pytest.raises(MemTableError):
        mt.delete(b"k3", (9,))      # double delete
    mt.insert(b"k3", (10,))         # delete+insert → update
    ops = dict(mt.iter_ops())
    assert ops[b"k3"][0] == KeyOp.UPDATE
    drained = dict(mt.drain())
    assert drained == {b"k2": (3,), b"k3": (10,)}
    assert not mt.is_dirty()


def _buffer_with(ops, sanity_check=True):
    mt = MemTable(sanity_check=sanity_check)
    for op, *args in ops:
        getattr(mt, op)(*args)
    return mt


# what the buffer holds before the batch, and the keys the batch deletes
_TOMBSTONE_BATCHES = {
    "fresh keys": ([], [b"a", b"b", b"c"]),
    "fresh keys beside other keys' ops":
        ([("insert", b"x", (1,)), ("delete", b"y", (2,))],
         [b"b", b"a"]),
    "a buffered insert annihilates":
        ([("insert", b"a", (1,)), ("insert", b"b", (2,))],
         [b"c", b"a", b"d"]),
    "a delete over an update keeps the first old row":
        ([("update", b"a", (1,), (2,)), ("insert", b"z", (0,))],
         [b"a", b"b"]),
    "a delete over a delete-then-insert":
        ([("delete", b"a", (1,)), ("insert", b"a", (3,))],
         [b"b", b"a"]),
    "every key buffered":
        ([("insert", b"a", (1,)), ("update", b"b", (1,), (2,))],
         [b"b", b"a"]),
    "no key": ([("insert", b"a", (1,))], []),
}


@pytest.mark.parametrize("case", list(_TOMBSTONE_BATCHES))
def test_mem_table_batch_tombstone_is_a_delete_a_key(case):
    """`delete_batch` leaves the buffer as `delete(key, None)` a key, in
    order, leaves it: the same ops under the same keys in the same
    places, so the same flush."""
    before, keys = _TOMBSTONE_BATCHES[case]
    one, many = _buffer_with(before), _buffer_with(before)
    for key in keys:
        one.delete(key, None)
    many.delete_batch(keys)
    assert list(many.items()) == list(one.items())
    assert many.drain_bulk() == one.drain_bulk()


@pytest.mark.parametrize("keys, before", [
    ([b"a", b"b"], [("delete", b"b", (1,))]),       # buffered tombstone
    ([b"a", b"b", b"a"], []),                       # twice in the batch
], ids=["over a buffered delete", "twice in one batch"])
def test_mem_table_batch_tombstone_raises_on_a_double_delete(keys, before):
    mt = _buffer_with(before)
    with pytest.raises(MemTableError):
        mt.delete_batch(keys)
    # without the sanity check the second delete is dropped, as
    # `delete` drops it
    lax, one = _buffer_with(before, False), _buffer_with(before, False)
    lax.delete_batch(keys)
    for key in keys:
        one.delete(key, None)
    assert list(lax.items()) == list(one.items())


def test_state_table_delete_keys_is_delete_rows_less_the_old_rows():
    """`delete_keys` takes the pk columns and builds no row: the flush
    is `delete_rows`' of the same rows, key for key."""
    rows = [(k, None if k == 4 else "s%d" % k, k * 2) for k in range(12)]
    schema = Schema.of(k=DataType.INT64, s=DataType.VARCHAR,
                       v=DataType.INT64)
    flushes = []
    for by_key in (False, True):
        t = StateTable(table_id=7, schema=schema, pk_indices=[0, 1],
                       store=MemoryStateStore(), dist_key_indices=[0])
        t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
        t.insert_rows(rows[:8])
        _advance(t)
        t.insert_rows(rows[8:])             # buffered: these annihilate
        doomed = rows[2:6] + rows[9:11]
        if by_key:
            t.delete_keys(
                [(np.asarray([r[0] for r in doomed], dtype=np.int64),
                  None),
                 (np.asarray([r[1] for r in doomed], dtype=object),
                  np.asarray([r[1] is not None for r in doomed]))],
                len(doomed))
        else:
            t.delete_rows(doomed)
        flushes.append(t.flush()[:2])
    assert flushes[0] == flushes[1]
    assert flushes[0][1].count(None) == 4 and len(flushes[0][0]) == 6


# -- state store MVCC --------------------------------------------------------

def test_memory_state_store_mvcc():
    st = MemoryStateStore()
    st.ingest_batch(1, [(b"a", (1,)), (b"b", (2,))], epoch=100)
    st.ingest_batch(1, [(b"a", (10,)), (b"b", None)], epoch=200)
    assert st.get(1, b"a", 100) == (1,)
    assert st.get(1, b"a", 150) == (1,)
    assert st.get(1, b"a", 200) == (10,)
    assert st.get(1, b"b", 100) == (2,)
    assert st.get(1, b"b", 200) is None          # tombstone
    assert st.get(1, b"a", 50) is None           # before first write
    assert [k for k, _ in st.iter(1, 200)] == [b"a"]
    assert [k for k, _ in st.iter(1, 100)] == [b"a", b"b"]
    st.seal_epoch(200)
    with pytest.raises(ValueError):
        st.ingest_batch(1, [(b"c", (3,))], epoch=150)  # write below seal


# -- state table -------------------------------------------------------------

def _table(sanity=True, dist=None):
    schema = Schema.of(k=DataType.INT64, s=DataType.VARCHAR, v=DataType.INT64)
    store = MemoryStateStore()
    t = StateTable(table_id=7, schema=schema, pk_indices=[0], store=store,
                   dist_key_indices=dist, sanity_check=sanity)
    e1 = Epoch.from_physical(1)
    t.init_epoch(EpochPair.new_initial(e1))
    return t, store


def _advance(t):
    new = EpochPair(curr=t.epoch.curr.next(), prev=t.epoch.curr)
    t.commit(new)
    return new


def test_state_table_write_read_across_commit():
    t, _ = _table()
    t.insert((1, "a", 10))
    t.insert((2, "b", 20))
    # uncommitted rows visible through the memtable
    assert t.get_row((1,)) == (1, "a", 10)
    _advance(t)
    assert t.get_row((1,)) == (1, "a", 10)       # now from committed store
    t.delete((1, "a", 10))
    assert t.get_row((1,)) is None               # buffered delete wins
    _advance(t)
    assert t.get_row((1,)) is None
    assert t.get_row((2,)) == (2, "b", 20)


def test_state_table_iteration_order_and_merge():
    t, _ = _table()
    for k in (5, 1, 9):
        t.insert((k, "x", k * 10))
    _advance(t)
    t.insert((3, "y", 30))          # buffered
    t.delete((9, "x", 90))          # buffered delete of committed row
    pks = [pk for pk, _ in t.iter_rows()]
    assert pks == [(1,), (3,), (5,)]
    rows = [r for _, r in t.iter_rows()]
    assert rows[1] == (3, "y", 30)


def test_state_table_update_pair_atomicity():
    t, _ = _table()
    t.insert((1, "a", 10))
    _advance(t)
    t.update((1, "a", 10), (1, "a", 11))
    assert t.get_row((1,)) == (1, "a", 11)
    _advance(t)
    assert t.get_row((1,)) == (1, "a", 11)
    # inconsistent update (wrong old row) caught by sanity check after insert
    t2, _ = _table()
    t2.insert((5, "q", 1))
    with pytest.raises(MemTableError):
        t2.update((5, "q", 999), (5, "q", 2))


def test_state_table_write_chunk_and_vnode_partitioning():
    t, store = _table(dist=[0])
    s = t.schema
    c = StreamChunk.from_pydict(
        s, {"k": [1, 2, 1], "s": ["a", "b", "a"], "v": [10, 20, 10]},
        ops=[Op.INSERT, Op.INSERT, Op.DELETE])
    t.write_chunk(c)
    assert t.get_row((2,)) == (2, "b", 20)
    assert t.get_row((1,)) is None               # insert+delete annihilated
    _advance(t)
    # row landed in the vnode derived from the dist key
    vnodes_with_data = {pk[0]: True for pk, _ in t.iter_rows()}
    assert vnodes_with_data == {2: True}
    from risingwave_tpu.common.hash import vnodes_of_host
    vn = int(vnodes_of_host([np.asarray([2], dtype=np.int64)])[0])
    assert [pk for pk, _ in t.iter_rows(vnode=vn)] == [(2,)]
    assert list(t.iter_rows(vnode=(vn + 1) % 256)) == []


def test_state_table_commit_epoch_progression():
    t, store = _table()
    t.insert((1, "a", 1))
    e_first = t.epoch.curr
    _advance(t)
    # data written at the sealed epoch
    assert store.get(7, t._encode_pk((1,)), e_first.value) == (1, "a", 1)
    assert store.get(7, t._encode_pk((1,)), e_first.value - 1) is None
    # commit with wrong epoch pair rejected
    with pytest.raises(AssertionError):
        t.commit(EpochPair(curr=t.epoch.curr.next(), prev=Epoch(1)))


def test_state_table_vnode_bitmap_swap():
    t, _ = _table()
    t.insert((1, "a", 1))
    with pytest.raises(AssertionError):
        t.update_vnode_bitmap(np.zeros(256, dtype=bool))
    _advance(t)
    prev = t.update_vnode_bitmap(np.arange(256) < 128)
    assert prev.all() and len(t.owned_vnodes()) == 128


def test_decimal_pk_physical_consistency():
    """StateTable rows/keys are physical: DECIMAL pk = scaled int64.

    Logical→physical normalization happens once at chunk ingest
    (types.decimal_to_scaled); the state layer never re-scales.
    """
    import decimal as _d
    from risingwave_tpu.common.types import decimal_to_scaled
    from risingwave_tpu.state.keycodec import encode_value
    phys = decimal_to_scaled(_d.Decimal("5"))
    assert phys == decimal_to_scaled(5) == decimal_to_scaled(5.0) == 50000
    assert encode_value(phys, DataType.DECIMAL) == \
        encode_value(50000, DataType.DECIMAL)

    schema = Schema.of(d=DataType.DECIMAL, v=DataType.INT64)
    store = MemoryStateStore()
    t = StateTable(9, schema, pk_indices=[0], store=store,
                   dist_key_indices=[0])
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    t.insert((phys, 1))
    assert t.get_row((phys,)) == (phys, 1)
    from risingwave_tpu.state.state_table import to_logical_row
    assert to_logical_row(t.get_row((phys,)), schema) == \
        (_d.Decimal("5"), 1)


def test_bulk_and_scalar_key_encoding_agree():
    """write_chunk's vectorized keys must equal the row-API's keys."""
    from risingwave_tpu.common.chunk import StreamChunk

    schema = Schema.of(a=DataType.INT64, f=DataType.FLOAT64,
                       b=DataType.BOOLEAN, d=DataType.DECIMAL,
                       v=DataType.VARCHAR)
    data = {
        "a": [-5, 0, 7, 2**40],
        "f": [-2.5, 0.0, 3.75, 1e300],
        "b": [True, False, True, False],
        "d": [decimal.Decimal("1.5"), decimal.Decimal("-2"),
              decimal.Decimal("0"), decimal.Decimal("99.9999")],
        "v": ["x", "y", "z", "w"],
    }
    chunk = StreamChunk.from_pydict(schema, data)
    store = MemoryStateStore()
    ta = StateTable(21, schema, pk_indices=[0, 1, 2, 3], store=store,
                    dist_key_indices=[0])
    tb = StateTable(22, schema, pk_indices=[0, 1, 2, 3], store=store,
                    dist_key_indices=[0])
    ta.write_chunk(chunk)
    _idx, rows, _ops = chunk.to_physical_records()
    for row in rows:
        tb.insert(row)
    keys_a = sorted(k for k, _ in ta.mem_table.iter_ops())
    keys_b = sorted(k for k, _ in tb.mem_table.iter_ops())
    assert keys_a == keys_b
    # a varchar pk takes the same columnar encoder: the string column
    # through the codec once, the fixed-width ones as byte matrices on
    # either side of it, the vnode from the string's hash and the int
    for tid, (pk, dist) in enumerate([([4, 0], None), ([0, 4, 1], [4, 0]),
                                      ([4], [4])]):
        tc = StateTable(23 + 2 * tid, schema, pk_indices=pk, store=store,
                        dist_key_indices=dist)
        td = StateTable(24 + 2 * tid, schema, pk_indices=pk, store=store,
                        dist_key_indices=dist)
        te = StateTable(40 + tid, schema, pk_indices=pk, store=store,
                        dist_key_indices=dist)
        tc.write_chunk(chunk)
        te.insert_rows(rows)
        for row in rows:
            td.insert(row)
        want = [td._encode_pk(td.pk_of(row)) for row in rows]
        assert tc._encode_pk_rows(rows) == want
        assert dict(tc.mem_table.iter_ops()) == \
            dict(td.mem_table.iter_ops()) == dict(te.mem_table.iter_ops())


def test_negative_zero_and_null_distkey_key_consistency():
    """Code-review regressions: -0.0 pk and NULL dist-key rows must be
    addressable identically through write_chunk and the row API."""
    from risingwave_tpu.common.chunk import StreamChunk

    # -0.0 and 0.0 are one SQL value → one key on both paths
    schema = Schema.of(f=DataType.FLOAT64, v=DataType.INT64)
    store = MemoryStateStore()
    t = StateTable(31, schema, pk_indices=[0], store=store)
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    chunk = StreamChunk.from_pydict(
        schema, {"f": np.asarray([-0.0]), "v": np.asarray([1])})
    t.write_chunk(chunk)
    assert t.get_row((-0.0,)) == (-0.0, 1) or t.get_row((0.0,)) == (-0.0, 1)
    t.delete((0.0, 1))          # scalar delete reaches the bulk-written row
    assert not t.mem_table.is_dirty()

    # NULL dist-key value: row lands in a vnode and stays addressable
    t2 = StateTable(32, schema, pk_indices=[0], store=store,
                    dist_key_indices=[0])
    t2.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    c2 = StreamChunk.from_pydict(
        schema, {"f": [None, 2.5], "v": [7, 8]})
    t2.write_chunk(c2)
    assert t2.get_row((None,)) == (None, 7)
    assert t2.get_row((2.5,)) == (2.5, 8)
    t2.delete((None, 7))
    assert t2.get_row((None,)) is None


def test_interner_gc_bounds_entries_to_live_state():
    """Interner entries retire with their last referencing value; ids
    stay stable for survivors and retired ids are reused only after GC
    proves them dead (VERDICT r3 weak #6)."""
    from risingwave_tpu.stream.executors.keys import Interner

    it = Interner()
    ids = {v: it.intern_one(v) for v in ("a", "b", "c", "d")}
    assert it.gc(["b", "d"]) == 2
    assert len(it) == 2
    # survivors keep their ids
    assert it.intern_one("b") == ids["b"]
    assert it.intern_one("d") == ids["d"]
    # dead ids are reused for NEW values
    new_id = it.intern_one("e")
    assert new_id in (ids["a"], ids["c"])
    # lookup of a retired id (defensive decode) yields None
    import numpy as np
    dead = [i for i in (ids["a"], ids["c"]) if i != new_id][0]
    assert it.lookup(np.asarray([dead]))[0] is None


def test_memory_context_accounting_and_eviction():
    from risingwave_tpu.utils.memory import MemoryContext

    m = MemoryContext(soft_limit_bytes=100)
    state = {"big": 200, "small": 10}
    m.register("big", lambda: state["big"],
               evict=lambda: state.__setitem__("big", 40) or 160)
    m.register("small", lambda: state["small"])
    assert m.total_bytes() == 210
    total = m.tick()
    assert state["big"] == 40          # evictor ran
    assert total <= 100


def test_serving_a_data_dir_pins_the_allocators_thresholds(
        tmp_path, monkeypatch):
    """The process that compacts keeps its freed heap (PR 31): the
    served entry point asks once, and glibc says yes."""
    import asyncio
    from risingwave_tpu import __main__ as main
    from risingwave_tpu.utils import memory

    asked = []
    pin = memory.keep_freed_heap
    monkeypatch.setattr(memory, "keep_freed_heap",
                        lambda: asked.append(pin()))

    async def up():
        async with main.serving(str(tmp_path), port=0):
            pass
    asyncio.run(up())
    assert asked == [True]


def test_serving_a_data_dir_sets_the_imports_aside_once_a_process(
        tmp_path):
    """The collector's full pass walks the jobs' objects only (PR 45):
    the served entry point freezes what the process holds at its first
    start and nothing at a later one."""
    import asyncio
    import gc
    from risingwave_tpu import __main__ as main
    from risingwave_tpu.utils import memory

    async def up():
        async with main.serving(str(tmp_path), port=0):
            pass
    asyncio.run(up())
    frozen = gc.get_freeze_count()
    assert frozen > 10_000
    assert memory.freeze_startup_heap() == 0
    asyncio.run(up())
    # nothing joins them; what dies by its reference count leaves
    assert gc.get_freeze_count() <= frozen


# -- staged all-insert writes (ISSUE 12 emit path) ---------------------------


def _epoch(n):
    return EpochPair(Epoch.from_physical(n + 1), Epoch.from_physical(n))


def test_deferred_write_chunk_skips_memtable_and_commits():
    schema = Schema.of(k=DataType.INT64, v=DataType.INT64)
    store = MemoryStateStore()
    t = StateTable(61, schema, pk_indices=[0], store=store)
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    chunk = StreamChunk.from_pydict(schema, {"k": [1, 2], "v": [10, 20]})
    t.write_chunk(chunk, defer=True)
    # the fast path bypasses the memtable entirely…
    assert not t.mem_table.is_dirty() and t.is_dirty()
    t.commit(_epoch(1))
    # …and the rows are durable at commit
    assert t.get_row((1,)) == (1, 10) and t.get_row((2,)) == (2, 20)
    assert not t.is_dirty()


def test_deferred_stage_spills_on_interleaved_delete():
    """An insert staged this epoch then deleted this epoch must
    annihilate exactly as the memtable path would."""
    schema = Schema.of(k=DataType.INT64, v=DataType.INT64)
    store = MemoryStateStore()
    t = StateTable(62, schema, pk_indices=[0], store=store)
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    t.write_chunk(StreamChunk.from_pydict(
        schema, {"k": [1, 2], "v": [10, 20]}), defer=True)
    t.delete((1, 10))              # spills the stage, then annihilates
    t.commit(_epoch(1))
    assert t.get_row((1,)) is None
    assert t.get_row((2,)) == (2, 20)


def test_deferred_stage_read_your_writes_mid_epoch():
    schema = Schema.of(k=DataType.INT64, v=DataType.INT64)
    store = MemoryStateStore()
    t = StateTable(63, schema, pk_indices=[0], store=store)
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    t.write_chunk(StreamChunk.from_pydict(
        schema, {"k": [5], "v": [50]}), defer=True)
    # a read mid-epoch spills the stage and sees the buffered row
    assert t.get_row((5,)) == (5, 50)
    t.commit(_epoch(1))
    assert t.get_row((5,)) == (5, 50)


def test_deferred_mixed_op_chunk_falls_back():
    """A chunk carrying deletes never stages — it takes the memtable
    merge path even under defer=True."""
    schema = Schema.of(k=DataType.INT64, v=DataType.INT64)
    store = MemoryStateStore()
    t = StateTable(64, schema, pk_indices=[0], store=store)
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    t.write_chunk(StreamChunk.from_pydict(
        schema, {"k": [1], "v": [10]}), defer=True)
    t.commit(_epoch(1))
    mixed = StreamChunk.from_pydict(
        schema, {"k": [1, 2], "v": [10, 20]},
        ops=[Op.DELETE, Op.INSERT])
    t.write_chunk(mixed, defer=True)
    assert t.mem_table.is_dirty()
    t.commit(_epoch(2))
    assert t.get_row((1,)) is None
    assert t.get_row((2,)) == (2, 20)


def test_deferred_multi_chunk_epoch_bit_identical_to_memtable_path():
    schema = Schema.of(k=DataType.INT64, v=DataType.FLOAT64)
    rng = np.random.default_rng(3)
    chunks = []
    k0 = 0
    for _ in range(4):
        n = int(rng.integers(3, 9))
        chunks.append(StreamChunk.from_pydict(
            schema, {"k": list(range(k0, k0 + n)),
                     "v": rng.normal(size=n).tolist()}))
        k0 += n
    stores = []
    for defer in (True, False):
        store = MemoryStateStore()
        t = StateTable(65, schema, pk_indices=[0], store=store)
        t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
        for c in chunks:
            t.write_chunk(c, defer=defer)
        t.commit(_epoch(1))
        stores.append(sorted(t.iter_rows()))
    assert stores[0] == stores[1]


def test_deferred_duplicate_pks_never_duplicate_scan_rows():
    """Review regression: duplicate pks staged in one epoch resolve
    last-wins in the store AND keep the key index unique — a scan must
    yield the pk once."""
    schema = Schema.of(k=DataType.INT64, v=DataType.INT64)
    store = MemoryStateStore()
    t = StateTable(66, schema, pk_indices=[0], store=store)
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    t.write_chunk(StreamChunk.from_pydict(
        schema, {"k": [1, 1, 2], "v": [10, 11, 20]}), defer=True)
    t.commit(_epoch(1))
    rows = sorted(r for _pk, r in t.iter_rows())
    assert rows == [(1, 11), (2, 20)]
    assert store.table_size(66, 2 ** 40) == 2
