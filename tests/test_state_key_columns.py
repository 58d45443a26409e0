"""The state table's one columnar key encoder, held to the scalar codec.

Every bulk entry point (``insert_rows``, ``update_rows``, ``delete_rows``,
``write_chunk``) makes its keys by the column, whatever the pk's types;
``_encode_pk`` is the point operations' encoder and the reference. The
keys are the on-disk format, the scan order and the vnode partition, so
they are compared byte for byte, over every pk shape a deployment can
declare, and once against literals taken from ``_encode_pk`` at the
commit before the columnar encoder (PR 27's tree).
"""

import asyncio
import decimal

import numpy as np
import pytest

from risingwave_tpu.common import (
    DataType, Epoch, EpochPair, Schema, StreamChunk,
)
from risingwave_tpu.common.chunk import Column
from risingwave_tpu.state import MemoryStateStore, StateTable
from risingwave_tpu.utils.metrics import STREAMING, MetricsHistory

D = DataType
Dec = decimal.Decimal

# name → (schema, pk, dist key or None, the columns' values; ``n`` is the
# non-key column every shape carries)
SHAPES = {
    "q8_person": (
        Schema.of(id=D.INT64, name=D.VARCHAR, ws=D.TIMESTAMP, n=D.INT64),
        [0, 1, 2], [0, 1, 2],
        {"id": [1001, -7, 2**40, 5],
         "name": ["Peter Jones", "Paul Smith", "vicky noris", "Deiter"],
         "ws": [1_700_000_010_000_000, 0, 10_000_000, -1]}),
    "varchar_alone": (
        Schema.of(s=D.VARCHAR, n=D.INT64), [0], [0],
        {"s": ["a", "b", "ab", "ba", "z" * 9]}),
    "bytea": (
        Schema.of(b=D.BYTEA, k=D.INT64, n=D.INT64), [0, 1], [1],
        {"b": [b"", b"\x00", b"\x00\x01\xff", b"abc", b"\xff\xff"],
         "k": [1, 2, 3, 4, 5]}),
    "bytea_dist_key": (
        # ``hash_strings_host`` reads bytes as ASCII (both encoders)
        Schema.of(b=D.BYTEA, n=D.INT64), [0], [0],
        {"b": [b"", b"\x00", b"a\x00b", b"abc", None]}),
    "varchar_nul_inside": (
        Schema.of(s=D.VARCHAR, k=D.INT32, n=D.INT64), [0, 1], [0],
        {"s": ["na\x00me", "\x00", "a\x00\x00b", "plain"],
         "k": [1, 2, 3, 4]}),
    "empty_string": (
        Schema.of(s=D.VARCHAR, k=D.INT64, n=D.INT64), [0, 1], [0, 1],
        {"s": ["", "", "x", ""], "k": [0, 1, 2, -1]}),
    "non_ascii": (
        Schema.of(s=D.VARCHAR, n=D.INT64), [0], [0],
        {"s": ["Zoë", "漢字", "naïve café", "\U0001f600 smile", "ß"]}),
    "longer_than_16_codepoints": (
        # the vnode hash reads 16 codepoints and the length: the first
        # two share a vnode and differ in their bytes
        Schema.of(s=D.VARCHAR, n=D.INT64), [0], [0],
        {"s": ["sixteen codepoints and then A",
               "sixteen codepoints and then B",
               "x" * 300, "exactly 16 chars"]}),
    "null_in_varchar_pk": (
        Schema.of(k=D.INT64, s=D.VARCHAR, n=D.INT64), [0, 1], [0],
        {"k": [1, 1, 2, 3], "s": ["a", None, None, "b"]}),
    "null_in_fixed_width_pk": (
        Schema.of(s=D.VARCHAR, k=D.INT64, t=D.TIMESTAMP, n=D.INT64),
        [0, 1, 2], [0],
        {"s": ["a", "b", "c", "d"], "k": [1, None, 3, None],
         "t": [None, 5, 6, None]}),
    "null_in_fixed_width_pk_alone": (
        Schema.of(k=D.INT64, f=D.FLOAT64, n=D.INT64), [0, 1], [0],
        {"k": [1, None, 3, 4], "f": [0.5, 1.5, None, 2.5]}),
    "null_in_dist_key": (
        Schema.of(s=D.VARCHAR, k=D.INT64, n=D.INT64), [0, 1], [0, 1],
        {"s": [None, "a", None, "b"], "k": [1, None, None, 2]}),
    "negative_zero": (
        Schema.of(f=D.FLOAT64, g=D.FLOAT32, n=D.INT64), [0, 1], [0, 1],
        {"f": [-0.0, 0.0, -2.5, 1e300, float("inf")],
         "g": [-0.0, 0.5, 0.0, -1.25, float("-inf")]}),
    "decimal": (
        Schema.of(d=D.DECIMAL, s=D.VARCHAR, n=D.INT64), [0, 1], [0],
        {"d": [Dec("1.5"), Dec("-2"), Dec("0"), Dec("99.9999")],
         "s": ["w", "x", "y", "z"]}),
    "boolean": (
        Schema.of(o=D.BOOLEAN, s=D.VARCHAR, n=D.INT64), [0, 1], [0, 1],
        {"o": [True, False, None, True], "s": ["t", "f", "n", None]}),
    "singleton_distribution": (
        Schema.of(s=D.VARCHAR, k=D.INT64, n=D.INT64), [0, 1], None,
        {"s": ["q", "r", None, "s"], "k": [3, 2, 1, None]}),
    "dist_key_subset_of_pk": (
        Schema.of(a=D.INT64, s=D.VARCHAR, dt=D.DATE, h=D.INT16,
                  n=D.INT64), [3, 1, 0, 2], [1, 2],
        {"a": [1, 2, 3, 4], "s": ["u", "v", "u", "w"],
         "dt": [19000, -1, 19000, 0], "h": [-3, 7, 0, 9]}),
    "all_fixed_width": (
        # the shape q7's tables have: one byte matrix, no join
        Schema.of(a=D.INT64, t=D.TIMESTAMP, n=D.INT64), [0, 1], [0],
        {"a": [5, -5, 2**62, 0], "t": [0, 1, -1, 10**15]}),
}


def shape(name, table_id=1):
    schema, pk, dist, data = SHAPES[name]
    rows_n = len(next(iter(data.values())))
    data = dict(data, n=list(range(rows_n)))
    chunk = StreamChunk.from_pydict(schema, data)
    _idx, rows, _ops = chunk.to_physical_records()

    def table():
        t = StateTable(table_id, schema, pk, MemoryStateStore(),
                       dist_key_indices=dist)
        t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
        return t
    return table, chunk, rows


def ops_of(t):
    return dict(t.mem_table.iter_ops())


def bump(row):
    return row[:-1] + (row[-1] + 100,)


def by_insert_rows(bulk, scalar, chunk, rows):
    bulk.insert_rows(rows)
    for r in rows:
        scalar.insert(r)


def by_delete_rows(bulk, scalar, chunk, rows):
    bulk.delete_rows(rows)
    for r in rows:
        scalar.delete(r)


def by_update_rows(bulk, scalar, chunk, rows):
    bulk.update_rows(rows, [bump(r) for r in rows])
    for r in rows:
        scalar.update(r, bump(r))


def by_update_rows_moving_pk(bulk, scalar, chunk, rows):
    # every row takes its neighbour's pk: the two sides' columns differ,
    # so each side is encoded for itself
    moved = [rows[(j + 1) % len(rows)][:-1] + (r[-1],)
             for j, r in enumerate(rows)]
    bulk.update_rows(rows, moved)
    for old, new in zip(rows, moved):
        scalar.update(old, new)


def by_write_chunk(bulk, scalar, chunk, rows):
    bulk.write_chunk(chunk)
    for r in rows:
        scalar.insert(r)


ENTRY_POINTS = {
    "insert_rows": by_insert_rows, "delete_rows": by_delete_rows,
    "update_rows": by_update_rows,
    "update_rows_moving_pk": by_update_rows_moving_pk,
    "write_chunk": by_write_chunk,
}


@pytest.mark.parametrize("entry", list(ENTRY_POINTS))
@pytest.mark.parametrize("name", list(SHAPES))
def test_columnar_keys_equal_scalar_keys(name, entry):
    table, chunk, rows = shape(name)
    bulk, scalar = table(), table()
    want = [scalar._encode_pk(scalar.pk_of(r)) for r in rows]
    assert len(set(want)) == len(rows)
    # key for key, in the rows' order
    assert bulk._encode_pk_rows(rows) == want
    idx = np.flatnonzero(np.asarray(chunk.visibility))
    assert bulk._encode_pks_bulk(chunk, idx) == want
    # and what each entry point leaves in the memtable: same keys, same
    # ops, same rows as the scalar API row by row
    ENTRY_POINTS[entry](bulk, scalar, chunk, rows)
    assert ops_of(bulk) == ops_of(scalar)
    assert set(ops_of(bulk)) <= set(want)


def test_pk_columns_given_by_the_caller():
    """What ``HashAggExecutor._persist`` hands over: the pk columns as
    arrays with a validity each; an invalid slot may hold anything."""
    table, _chunk, rows = shape("q8_person")
    t = table()
    want = [t._encode_pk(t.pk_of(r)) for r in rows]
    ok = np.ones(len(rows), dtype=bool)
    cols = [(np.asarray([r[0] for r in rows], dtype=np.int64), ok),
            (np.asarray([r[1] for r in rows], dtype=object), ok),
            (np.asarray([r[2] for r in rows], dtype=np.int64), ok)]
    assert t._encode_pk_rows(rows, cols) == want
    # NULLs by validity, garbage underneath: the keys are the NULLs'
    holes = [(None, "Paul Smith", 0), (4, None, 7), (None, None, None)]
    want = [t._encode_pk(pk) for pk in holes]
    cols = [(np.asarray([99, 4, 98], dtype=np.int64),
             np.asarray([False, True, False])),
            (np.asarray(["Paul Smith", "junk", "junk"], dtype=object),
             np.asarray([True, False, False])),
            (np.asarray([0, 7, 12345], dtype=np.int64),
             np.asarray([True, True, False]))]
    assert t._encode_pk_rows([h + (0,) for h in holes], cols) == want
    t.update_rows([h + (0,) for h in holes], [h + (1,) for h in holes], cols)
    assert sorted(ops_of(t)) == sorted(want)


def test_invalid_chunk_slots_hash_as_null():
    """A chunk's invalid slot keeps whatever the buffer held; its vnode
    and bytes are the NULL's all the same (host-typed dist key too)."""
    schema = Schema.of(s=D.VARCHAR, k=D.INT64, n=D.INT64)
    chunk = StreamChunk.from_pydict(
        schema, {"s": ["left over", "a"], "k": [77, 2], "n": [0, 1]})
    cols = list(chunk.columns)
    cols[0] = Column(D.VARCHAR, cols[0].values, np.asarray([False, True]))
    cols[1] = Column(D.INT64, cols[1].values, np.asarray([False, True]))
    chunk = StreamChunk(schema, cols, chunk.visibility, chunk.ops)
    t = StateTable(3, schema, [0, 1], MemoryStateStore(),
                   dist_key_indices=[0, 1])
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    t.write_chunk(chunk)
    assert sorted(ops_of(t)) == sorted(
        [t._encode_pk((None, None)), t._encode_pk(("a", 2))])
    assert t.get_row((None, None)) == (None, None, 0)


# keys of ``_encode_pk`` at the commit before this encoder existed
# (9bb87fa, PR 27), written out: the on-disk format, not only the two
# encoders' agreement
GOLDEN = {
    "q8_person": (
        Schema.of(id=D.INT64, name=D.VARCHAR, ws=D.TIMESTAMP, n=D.INT64),
        [0, 1, 2], [0, 1, 2],
        [(1001, "Peter Jones", 1_700_000_010_000_000), (-7, "", 0),
         (2**40, "vicky noris walton", 10_000_000), (5, "na\x00me", -1),
         (6, "Zoë 漢字", 20_000_000)],
        ["00080180000000000003e9015065746572204a6f6e657300000180060a2418"
         "b6d680",
         "003e017ffffffffffffff9010000018000000000000000",
         "00c7018000010000000000017669636b79206e6f7269732077616c746f6e00"
         "00018000000000989680",
         "00a0018000000000000005016e6100ff6d650000017fffffffffffffff",
         "008a018000000000000006015a6fc3ab20e6bca2e5ad970000018000000001"
         "312d00"]),
    "nulls": (
        Schema.of(id=D.INT64, name=D.VARCHAR, n=D.INT64), [0, 1], [1, 0],
        [(None, "a"), (3, None), (None, None), (4, "b")],
        ["00020001610000", "008801800000000000000300", "00780000",
         "003201800000000000000401620000"]),
    "bytea_singleton": (
        Schema.of(b=D.BYTEA, f=D.FLOAT64, n=D.INT64), [0, 1], None,
        [(b"\x00\x01\xff", -0.0), (b"", 2.5), (b"abc", -1e300),
         (b"abc", float("inf"))],
        ["00000100ff01ff0000018000000000000000",
         "000001000001c004000000000000",
         "00000161626300000101c81bc377ff8a63",
         "000001616263000001fff0000000000000"]),
    "bool_decimal_subset": (
        Schema.of(o=D.BOOLEAN, d=D.DECIMAL, dt=D.DATE, s=D.INT16,
                  n=D.INT64), [0, 1, 2, 3], [1, 3],
        [(True, 15000, 19000, -3), (False, -20000, -1, 7),
         (None, 0, None, 0)],
        ["008f0101018000000000003a98018000000000004a38017ffffffffffffffd",
         "00170100017fffffffffffb1e0017fffffffffffffff018000000000000007",
         "00780001800000000000000000018000000000000000"]),
}


@pytest.mark.parametrize("name", list(GOLDEN))
def test_golden_key_bytes(name):
    schema, pk, dist, pks, want = GOLDEN[name]
    t = StateTable(2, schema, pk, MemoryStateStore(), dist_key_indices=dist)
    t.init_epoch(EpochPair.new_initial(Epoch.from_physical(1)))
    want = [bytes.fromhex(h) for h in want]
    assert [t._encode_pk(p) for p in pks] == want
    rows = [p + (j,) for j, p in enumerate(pks)]
    assert t._encode_pk_rows(rows) == want
    t.insert_rows(rows)
    assert sorted(ops_of(t)) == sorted(want)


# -- the counter ------------------------------------------------------------


def pk_counts():
    return (STREAMING.state_pk_keys.get(path="columnar"),
            STREAMING.state_pk_keys.get(path="row"))


def test_counter_says_which_encoder_made_the_keys():
    """A GROUP BY on a varchar key, through one barrier: every key of the
    flush is the columnar encoder's, none the scalar codec's; a point
    read is one scalar key; both are in the barrier's history row."""
    from risingwave_tpu.ops.hash_agg import AggKind
    from risingwave_tpu.stream.executors.hash_agg import (
        AggCall, HashAggExecutor, agg_state_schema,
    )
    from risingwave_tpu.stream.executors.test_utils import (
        MockSource, collect_until_n_barriers,
    )
    from risingwave_tpu.stream.message import Barrier, BarrierKind

    def barrier(n):
        prev = Epoch.from_physical(n - 1) if n > 1 else Epoch.INVALID
        return Barrier(EpochPair(Epoch.from_physical(n), prev),
                       BarrierKind.CHECKPOINT)

    schema = Schema.of(id=D.INT64, name=D.VARCHAR, v=D.INT64)
    names = [f"person {j}" for j in range(40)] + [None]
    chunk = StreamChunk.from_pydict(
        schema, {"id": list(range(41)) * 2, "name": names * 2,
                 "v": list(range(82))})
    calls = [AggCall(AggKind.COUNT), AggCall(AggKind.SUM, 2)]
    sschema, spk = agg_state_schema(schema, [0, 1], calls)
    table = StateTable(10, sschema, spk, MemoryStateStore(),
                       dist_key_indices=[0, 1])
    ex = HashAggExecutor(
        MockSource(schema, [barrier(1), chunk, barrier(2)]), [0, 1],
        calls, table, append_only=True)
    history = MetricsHistory()         # the ring's own rows, not the
    history.observe(0, 0.0)            # process's; settles the deltas
    col0, row0 = pk_counts()
    asyncio.run(collect_until_n_barriers(ex, 2))
    col1, row1 = pk_counts()
    assert (col1 - col0, row1 - row0) == (41, 0)
    assert len(list(table.iter_rows())) == 41
    assert table.get_row((7, "person 7"))[:3] == (7, "person 7", 2)
    assert table.get_row((40, None))[:3] == (40, None, 2)
    assert pk_counts() == (col1, row1 + 2)
    history.observe(1, 0.25)
    got = {r[4]: r[5] for r in history.rows() if r[1] == 1}
    assert got["state_pk.columnar"] == 41.0
    assert got["state_pk.row"] == 2.0


# -- a data dir written by the scalar path, under the columnar one ----------


def test_recovery_across_the_encoders(tmp_path):
    """Keys the scalar codec wrote are the keys the columnar encoder
    writes: a data dir from before recovers, reads back equal, and the
    same rows written again shadow the old ones (no second scan row)."""
    from risingwave_tpu.storage.hummock import HummockLite
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    schema = Schema.of(id=D.INT64, name=D.VARCHAR, ws=D.TIMESTAMP,
                       n=D.INT64)
    rows = [(j % 50, None if j % 17 == 0 else f"na\x00me {j} Zoë",
             None if j % 23 == 0 else 10_000_000 * (j % 3), j)
            for j in range(300)]

    def open_table(prev, curr):
        store = HummockLite(LocalFsObjectStore(str(tmp_path)))
        t = StateTable(5, schema, [0, 1, 2], store,
                       dist_key_indices=[0, 1, 2], sanity_check=False)
        t.init_epoch(EpochPair(Epoch.from_physical(curr),
                               Epoch.from_physical(prev)))
        return store, t

    def checkpoint(store, t, nxt):
        sealed = t.epoch.curr
        t.commit(EpochPair(Epoch.from_physical(nxt), sealed))
        store.seal_epoch(sealed.value)
        store.sync(sealed.value)

    store, t = open_table(1, 2)
    before = pk_counts()
    for r in rows:
        t.insert(r)                      # the scalar path, row by row
    assert pk_counts() == (before[0], before[1] + len(rows))
    checkpoint(store, t, 3)
    want = sorted(rows, key=lambda r: t._encode_pk(t.pk_of(r)))

    store, t = open_table(2, 3)          # a new process on the same dir
    assert [row for _pk, row in t.iter_rows()] == want
    newer = [r[:-1] + (r[-1] + 1000,) for r in rows]
    before = pk_counts()
    t.insert_rows(newer)                 # the same pks, by the column
    assert pk_counts() == (before[0] + len(rows), before[1])
    checkpoint(store, t, 4)

    store, t = open_table(3, 4)
    got = [row for _pk, row in t.iter_rows()]
    assert len(got) == len(rows)
    assert got == [r[:-1] + (r[-1] + 1000,) for r in want]
    assert t.get_row(rows[0][:3]) == newer[0]
