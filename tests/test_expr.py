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


@pytest.mark.parametrize("fmt,strf", [
    ("YYYY-MM-DD", "%Y-%m-%d"), ("HH24:MI", "%H:%M"),
    ("YYYY-MM-DD HH24:MI:SS", "%Y-%m-%d %H:%M:%S"),
    ("DD/MM HH24", "%d/%m %H"), ("day", "day")])
def test_to_char_formats_a_row_as_strftime_does(fmt, strf):
    """`to_char` gives the text a strftime of the row gives, row for
    row (q15's GROUP BY key, ISSUE 41): rows on both sides of a minute,
    an hour, a day and the epoch; NULLs stay NULL. It formats once a
    row today; whatever formats fewer times has to pass this."""
    import datetime

    import numpy as np

    from risingwave_tpu.common.chunk import DataChunk
    from risingwave_tpu.common.types import DataType, Schema
    from risingwave_tpu.expr.expr import FuncCall, InputRef, lit

    base = 1_436_918_400_000_000             # 2015-07-15 00:00:00
    ts = [base - 1, base, base + 59_999_999, base + 60_000_000,
          base + 3_599_999_999, base + 3_600_000_000,
          base + 86_399_999_999, base + 86_400_000_000, -1, 0, 1, None,
          base + 1_500_000]
    chunk = DataChunk.from_pydict(Schema.of(ts=DataType.TIMESTAMP),
                                  {"ts": ts})
    col = FuncCall("to_char", [InputRef(0, DataType.TIMESTAMP),
                               lit(fmt, DataType.VARCHAR)],
                   DataType.VARCHAR).eval(chunk)
    epoch = datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc)
    ok = np.ones(len(ts), dtype=bool) if col.validity is None \
        else np.asarray(col.validity)[:len(ts)]
    for i, v in enumerate(ts):
        if v is None:
            assert not ok[i]
        else:
            assert ok[i] and np.asarray(col.values)[i] == (
                epoch + datetime.timedelta(microseconds=v)).strftime(strf)
