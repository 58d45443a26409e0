"""Expression framework tests (ref: src/expr/src/expr tests)."""

import decimal

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common import DataChunk, DataType, Interval, Schema
from risingwave_tpu.expr import (
    Case, InputRef, and_, col, lit, or_, tumble_start, tumble_end,
)


def _chunk():
    s = Schema.of(a=DataType.INT64, b=DataType.INT64, f=DataType.FLOAT64,
                  d=DataType.DECIMAL)
    return DataChunk.from_pydict(s, {
        "a": [1, 2, None, 4],
        "b": [10, 0, 30, 40],
        "f": [0.5, 1.5, 2.5, 3.5],
        "d": ["1.10", "2.20", "3.30", "4.40"],
    })


def _vals(colmn, n=4):
    out = []
    v = np.asarray(colmn.values)
    val = None if colmn.validity is None else np.asarray(colmn.validity)
    for i in range(n):
        out.append(None if (val is not None and not val[i]) else v[i].item())
    return out


def test_arith_and_null_propagation():
    c = _chunk()
    s = c.schema
    e = col(s, "a") + col(s, "b")
    assert e.return_type == DataType.INT64
    assert _vals(e.eval(c)) == [11, 2, None, 44]
    e2 = col(s, "a") * lit(3)
    assert _vals(e2.eval(c)) == [3, 6, None, 12]


def test_comparison_and_logic():
    c = _chunk()
    s = c.schema
    e = (col(s, "b") > lit(5)) | (col(s, "a") == lit(2))
    r = _vals(e.eval(c))
    assert r == [True, True, True, True]
    e2 = and_(col(s, "b") >= lit(10), col(s, "f") < lit(3.0))
    assert _vals(e2.eval(c)) == [True, False, True, False]
    # Kleene: null AND false = false, null AND true = null
    e3 = (col(s, "a") > lit(0)) & (col(s, "b") > lit(100))
    assert _vals(e3.eval(c)) == [False, False, False, False]
    e4 = (col(s, "a") > lit(0)) & (col(s, "b") >= lit(0))
    assert _vals(e4.eval(c)) == [True, True, None, True]


def test_decimal_exact_math():
    c = _chunk()
    s = c.schema
    e = col(s, "d") * lit(decimal.Decimal("0.908"))
    out = e.eval(c)
    assert out.data_type == DataType.DECIMAL
    # 1.10 * 0.908 = 0.9988 exactly at scale 4
    assert _vals(out)[0] == 9988
    e2 = col(s, "d") + col(s, "d")
    assert _vals(e2.eval(c))[1] == 44000  # 2.20 + 2.20 = 4.40 → 44000 raw


def test_division_by_zero_is_null():
    c = _chunk()
    s = c.schema
    e = col(s, "a") / col(s, "b")
    out = _vals(e.eval(c))
    assert out[1] is None           # 2 / 0 → NULL
    assert out[0] == 1000           # 1/10 = 0.1 → decimal raw 1000
    e2 = col(s, "b") % lit(0)
    assert _vals(e2.eval(c)) == [None] * 4


def test_int_division_becomes_decimal():
    c = _chunk()
    s = c.schema
    e = col(s, "b") / lit(4)
    out = e.eval(c)
    assert out.data_type == DataType.DECIMAL
    assert _vals(out)[0] == 25000   # 10/4 = 2.5


def test_unary_and_is_null():
    from risingwave_tpu.expr.expr import UnaryOp
    c = _chunk()
    s = c.schema
    assert _vals(UnaryOp("is_null", col(s, "a")).eval(c)) == \
        [False, False, True, False]
    assert _vals(UnaryOp("neg", col(s, "b")).eval(c)) == [-10, 0, -30, -40]
    assert _vals(UnaryOp("not", col(s, "b") > lit(5)).eval(c)) == \
        [False, True, False, False]


def test_tumble_window():
    s = Schema.of(ts=DataType.TIMESTAMP)
    c = DataChunk.from_pydict(s, {"ts": [0, 5_000_000, 12_345_678, 59_999_999]})
    w = Interval.from_duration(seconds=10)  # 10s windows
    st = tumble_start(col(s, "ts"), w).eval(c)
    en = tumble_end(col(s, "ts"), w).eval(c)
    assert _vals(st) == [0, 0, 10_000_000, 50_000_000]
    assert _vals(en) == [10_000_000, 10_000_000, 20_000_000, 60_000_000]


def test_case_expression():
    c = _chunk()
    s = c.schema
    e = Case([(col(s, "b") < lit(15), lit(1)),
              (col(s, "b") < lit(35), lit(2))], lit(3))
    assert _vals(e.eval(c)) == [1, 1, 2, 3]


def test_literal_null_and_varchar():
    c = _chunk()
    out = lit(None).eval(c)
    assert _vals(out) == [None] * 4
    v = lit("hello").eval(c)
    assert np.asarray(v.values)[0] == "hello"


def test_float_promotion():
    c = _chunk()
    s = c.schema
    e = col(s, "a") + col(s, "f")
    assert e.return_type == DataType.FLOAT64
    r = _vals(e.eval(c))
    assert r[0] == 1.5 and r[2] is None


def test_varchar_comparison_host():
    s = Schema.of(name=DataType.VARCHAR, x=DataType.INT64)
    c = DataChunk.from_pydict(s, {"name": ["alice", "bob", None, "alice"],
                                  "x": [1, 2, 3, 4]})
    e = col(s, "name") == lit("alice")
    assert _vals(e.eval(c)) == [True, False, None, True]
    e2 = col(s, "name") < lit("b")
    assert _vals(e2.eval(c)) == [True, False, None, True]
    with pytest.raises(TypeError):
        (col(s, "name") + lit("x")).eval(c)


def test_decimal_mul_truncates_toward_zero():
    s = Schema.of(d=DataType.DECIMAL)
    c = DataChunk.from_pydict(s, {"d": ["-0.0001", "0.0001"]})
    e = col(s, "d") * lit(decimal.Decimal("0.5"))
    assert _vals(e.eval(c), 2) == [0, 0]   # both truncate to zero


def test_tumble_null_window():
    from risingwave_tpu.expr.expr import FuncCall, Literal
    s = Schema.of(ts=DataType.TIMESTAMP)
    c = DataChunk.from_pydict(s, {"ts": [100]})
    e = FuncCall("tumble_start",
                 [col(s, "ts"), Literal(None, DataType.INTERVAL)],
                 DataType.TIMESTAMP)
    assert _vals(e.eval(c), 1) == [None]


# -- round-2 review-fix regressions -----------------------------------------


def test_decimal_to_float_cast():
    import decimal as _d
    s = Schema.of(d=DataType.DECIMAL, f=DataType.FLOAT64)
    c = DataChunk.from_pydict(s, {"d": [_d.Decimal("1.5")], "f": [2.0]})
    out = (col(s, "d") + col(s, "f")).eval(c)
    assert out.data_type == DataType.FLOAT64
    assert abs(float(out.values[0]) - 3.5) < 1e-9


def test_modulo_truncated_sign():
    s = Schema.of(a=DataType.INT64, b=DataType.INT64)
    c = DataChunk.from_pydict(s, {"a": [-7, 7, -7, 7], "b": [3, 3, -3, -3]})
    out = (col(s, "a") % col(s, "b")).eval(c)
    assert [int(v) for v in out.values[:4]] == [-1, 1, -1, 1]


def test_host_cmp_interval_with_padding():
    from risingwave_tpu.common.types import Interval
    s = Schema.of(iv=DataType.INTERVAL)
    c = DataChunk.from_pydict(s, {"iv": [Interval(days=1)]})  # capacity 8
    out = (col(s, "iv") < lit(Interval(usecs=360_000_000_000),
                              DataType.INTERVAL)).eval(c)
    # 1 day < 100 hours under justified comparison
    assert bool(out.values[0])


def test_interval_justified_ordering():
    from risingwave_tpu.common.types import Interval
    assert Interval(days=1) < Interval(usecs=360_000_000_000)
    assert Interval(months=1) == Interval(days=30)
    assert Interval(months=1) > Interval(days=29)


def test_scalar_function_library_semantics():
    """pg semantics of the new string/date scalars: substr window
    clamping, split_part from-the-end, to_char, extract_epoch without
    int64 overflow."""
    import decimal

    import numpy as np

    from risingwave_tpu.common.chunk import DataChunk
    from risingwave_tpu.common.types import DataType, Schema
    from risingwave_tpu.expr.expr import FuncCall, InputRef, lit

    sch = Schema.of(s=DataType.VARCHAR, ts=DataType.TIMESTAMP)
    chunk = DataChunk.from_pydict(
        sch, {"s": ["hello", "a/b/c"],
              "ts": [1_436_918_400_000_000, 0]})
    sref = InputRef(0, DataType.VARCHAR)
    tref = InputRef(1, DataType.TIMESTAMP)

    def run(fc):
        col = fc.eval(chunk)
        return list(np.asarray(col.values)[:2])

    # substr clamps the WINDOW, not the length (pg)
    assert run(FuncCall("substr", [sref, lit(0, DataType.INT64),
                                   lit(3, DataType.INT64)],
                        DataType.VARCHAR))[0] == "he"
    assert run(FuncCall("substr", [sref, lit(-2, DataType.INT64),
                                   lit(5, DataType.INT64)],
                        DataType.VARCHAR))[0] == "he"
    # split_part counts negative positions from the end
    assert run(FuncCall("split_part",
                        [sref, lit("/", DataType.VARCHAR),
                         lit(-1, DataType.INT64)],
                        DataType.VARCHAR))[1] == "c"
    assert run(FuncCall("to_char",
                        [tref, lit("YYYY-MM-DD", DataType.VARCHAR)],
                        DataType.VARCHAR))[0] == "2015-07-15"
    ep = run(FuncCall("extract_epoch", [tref], DataType.DECIMAL))[0]
    assert int(ep) == 1_436_918_400 * 10_000   # scaled decimal seconds


_DAY_US = 86_400_000_000
# pattern, the strftime string it stands for, and the µs within which
# its text cannot change: that of the finest token where the pattern
# proves it (a day where it holds no token), else 1
TO_CHAR_CASES = [
    ("YYYY-MM-DD", "%Y-%m-%d", _DAY_US), ("HH24:MI", "%H:%M", 60_000_000),
    ("YYYY-MM-DD HH24:MI:SS", "%Y-%m-%d %H:%M:%S", 1_000_000),
    ("DD/MM HH24", "%d/%m %H", 3_600_000_000), ("day", "day", 1),
    ("SS", "%S", 1_000_000), ("MI", "%M", 60_000_000),
    ("HH24", "%H", 3_600_000_000), ("DD", "%d", _DAY_US),
    ("MM", "%m", _DAY_US), ("YYYY", "%Y", _DAY_US),
    ("YYYY/MM", "%Y/%m", _DAY_US), ("MI:SS", "%M:%S", 1_000_000),
    ("YYYY, MI", "%Y, %M", 60_000_000),
    ("SS.MI.HH24 DD-MM-YYYY", "%S.%M.%H %d-%m-%Y", 1_000_000),
    ("YYYYMMDD", "%Y%m%d", _DAY_US), ("-- : /", "-- : /", _DAY_US),
    ("", "", _DAY_US),
    ("MS", "MS", 1), ("US", "US", 1), ("HH12:MI", "HH12:%M", 1),
    ("Mon DD", "Mon %d", 1), ("DDD", "%dD", 1),
    ('YYYY-MM-DD"T"HH24', '%Y-%m-%d"T"%H', 1),
    ("HH24:MI:SS.%f", "%H:%M:%S.%f", 1), ("%S", "%S", 1),
    ("YYYY-MM-DD 0", "%Y-%m-%d 0", 1)]


def _to_char_chunks():
    """2,000 seeded instants within three days of a day, an hour and a
    minute boundary and of the epoch, the boundaries' own neighbours,
    NULLs scattered; an all-NULL chunk; an empty one."""
    from risingwave_tpu.common.chunk import DataChunk
    from risingwave_tpu.common.types import DataType, Schema

    rng = np.random.default_rng(42)
    base = 1_436_918_400_000_000             # 2015-07-15 00:00:00
    centres = [base, base + 13 * 3_600_000_000,
               base + 13 * 3_600_000_000 + 17 * 60_000_000, 0]
    ts = [c + d for c in centres
          for d in (-1_000_001, -1_000_000, -1, 0, 1, 999_999, 1_000_000,
                    59_999_999, 60_000_000, 3_599_999_999, 3_600_000_000,
                    _DAY_US - 1, _DAY_US, -_DAY_US, -_DAY_US - 1)]
    for c in centres:
        ts += (c + rng.integers(-3 * _DAY_US, 3 * _DAY_US, 400)).tolist()
        ts += (c + rng.integers(-2_000_000, 2_000_000, 100)).tolist()
    ts = [None if rng.random() < 0.05 else int(v) for v in ts]
    schema = Schema.of(ts=DataType.TIMESTAMP)
    return [DataChunk.from_pydict(schema, {"ts": rows})
            for rows in (ts, [None] * 5, [])]


def _valid_rows(column):
    """(row index, µs) of a TIMESTAMP column's valid rows, padding
    included: what to_char is given."""
    vals = np.asarray(column.values).tolist()
    ok = np.ones(len(vals), dtype=bool) if column.validity is None \
        else np.asarray(column.validity)
    return [(i, vals[i]) for i in np.flatnonzero(ok).tolist()]


def _to_char(fmt):
    from risingwave_tpu.common.types import DataType
    from risingwave_tpu.expr.expr import FuncCall, InputRef, lit
    return FuncCall("to_char", [InputRef(0, DataType.TIMESTAMP),
                                lit(fmt, DataType.VARCHAR)],
                    DataType.VARCHAR)


@pytest.mark.parametrize("fmt,strf", [c[:2] for c in TO_CHAR_CASES])
def test_to_char_formats_a_row_as_strftime_does(fmt, strf):
    """`to_char` gives the text a strftime of the row gives, row for
    row (q15's GROUP BY key, ISSUE 41), whatever unit the pattern
    proves (ISSUE 42): rows on both sides of a second, a minute, an
    hour, a day and the epoch; NULLs stay NULL. `strf` is what the
    chain of `str.replace` before ISSUE 42 made of the pattern."""
    import datetime

    chain = fmt
    for token, directive in (("YYYY", "%Y"), ("MM", "%m"), ("DD", "%d"),
                             ("HH24", "%H"), ("MI", "%M"), ("SS", "%S")):
        chain = chain.replace(token, directive)
    assert chain == strf
    epoch = datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc)
    chunks = _to_char_chunks()
    assert sum(c.cardinality() for c in chunks) > 2_000
    for chunk in chunks:
        given = chunk.columns[0]
        col = _to_char(fmt).eval(chunk)
        valid = _valid_rows(given)
        ok = np.ones(chunk.capacity, dtype=bool) if col.validity is None \
            else np.asarray(col.validity)
        assert np.flatnonzero(ok).tolist() == [i for i, _v in valid]
        got = np.asarray(col.values)
        for i, v in valid:
            assert got[i] == (epoch + datetime.timedelta(
                microseconds=v)).strftime(strf), (fmt, v)


@pytest.mark.parametrize("fmt,unit", [c[::2] for c in TO_CHAR_CASES])
def test_to_char_counts_its_formats(fmt, unit):
    """Counters `expr_to_char_rows` / `_formats`: a pattern of the six
    tokens and of literals that are no letter, digit or `%` formats
    once a distinct value of the finest field it prints; any other
    pattern formats once a distinct µs."""
    from risingwave_tpu.utils.metrics import STREAMING as S

    def books():
        return (S.expr_to_char_rows.get(), S.expr_to_char_formats.get())
    for chunk in _to_char_chunks():
        valid = [v for _i, v in _valid_rows(chunk.columns[0])]
        rows, formats = books()
        _to_char(fmt).eval(chunk)
        assert books() == (rows + len(valid),
                           formats + len({v // unit for v in valid}))


def test_to_char_takes_a_host_column_of_python_ints():
    """An object-dtype TIMESTAMP column (what a host-side expression
    may hand on) formats as the int64 one does, and the validity that
    comes back is not the input's array."""
    from risingwave_tpu.common.chunk import Column
    from risingwave_tpu.common.types import DataType
    from risingwave_tpu.expr.expr import _FUNCTIONS

    ok = np.array([True, False, True, True])
    ts = Column(DataType.TIMESTAMP,
                np.array([-1, None, 0, 86_400_000_000], dtype=object), ok)
    fmt = Column(DataType.VARCHAR, np.array(["YYYY-MM-DD"], dtype=object),
                 None)
    out = _FUNCTIONS["to_char"](DataType.VARCHAR, ts, fmt)
    assert out.values.tolist() == ["1969-12-31", None, "1970-01-01",
                                   "1970-01-02"]
    assert out.validity is not ok and out.validity.tolist() == ok.tolist()
