"""Expression compiler tests — golden-checked against pyarrow.compute where
practical, mirroring the reference's test_internal_functions.cpp /
test_arrow_compute.cpp coverage."""

import numpy as np
import pyarrow as pa
import pytest

from baikaldb_tpu import ColumnBatch, LType, col, lit, call
from baikaldb_tpu.expr.compile import eval_expr, eval_predicate, infer_type


def make_batch():
    t = pa.table({
        "a": pa.array([1, 2, None, 4, 5], type=pa.int64()),
        "b": pa.array([10.0, None, 30.0, 40.0, 50.0], type=pa.float64()),
        "s": pa.array(["apple", "banana", None, "cherry", "apple"], type=pa.string()),
        "d": pa.array([18000, 18001, 18031, None, 19000], type=pa.int32()).cast(pa.date32()),
    })
    return ColumnBatch.from_arrow(t)


def test_arithmetic_nulls():
    b = make_batch()
    r = eval_expr(col("a") + col("b"), b)
    data, valid = r.to_numpy()
    assert valid.tolist() == [True, False, False, True, True]
    assert data[0] == 11.0 and data[3] == 44.0

    r = eval_expr(col("a") * lit(3), b)
    data, valid = r.to_numpy()
    assert data[0] == 3 and data[3] == 12
    assert valid.tolist() == [True, True, False, True, True]


def test_division_null_on_zero():
    b = make_batch()
    r = eval_expr(col("a") / (col("a") - lit(2)), b)
    data, valid = r.to_numpy()
    assert valid.tolist() == [True, False, False, True, True]  # a==2 -> /0 -> NULL
    assert data[0] == pytest.approx(-1.0)
    assert data[3] == pytest.approx(2.0)


def test_comparisons_and_kleene_logic():
    b = make_batch()
    # (a > 1) AND (b < 45): NULL AND TRUE -> NULL -> filtered out
    m = eval_predicate((col("a") > 1) & (col("b") < 45.0), b)
    assert np.asarray(m).tolist() == [False, False, False, True, False]
    # NULL OR TRUE -> TRUE
    r = eval_expr((col("a") > 100) | (col("b") < 45.0), b)
    data, valid = r.to_numpy()
    assert data[1].item() is np.False_ or data[1] == False  # noqa: E712
    assert valid.tolist() == [True, False, True, True, True]


def test_string_compare_literal():
    b = make_batch()
    m = eval_predicate(col("s") == "apple", b)
    assert np.asarray(m).tolist() == [True, False, False, False, True]
    m = eval_predicate(col("s") > "apple", b)
    assert np.asarray(m).tolist() == [False, True, False, True, False]
    m = eval_predicate(col("s") <= "banana", b)
    assert np.asarray(m).tolist() == [True, True, False, False, True]


def test_like():
    b = make_batch()
    m = eval_predicate(call("like", col("s"), lit("a%")), b)
    assert np.asarray(m).tolist() == [True, False, False, False, True]
    m = eval_predicate(call("like", col("s"), lit("%an%")), b)
    assert np.asarray(m).tolist() == [False, True, False, False, False]
    m = eval_predicate(call("like", col("s"), lit("_pple")), b)
    assert np.asarray(m).tolist() == [True, False, False, False, True]


def test_in():
    b = make_batch()
    m = eval_predicate(call("in", col("s"), lit("apple"), lit("cherry")), b)
    assert np.asarray(m).tolist() == [True, False, False, True, True]
    m = eval_predicate(call("in", col("a"), lit(1), lit(4), lit(9)), b)
    assert np.asarray(m).tolist() == [True, False, False, True, False]
    m = eval_predicate(call("not_in", col("a"), lit(1)), b)
    assert np.asarray(m).tolist() == [False, True, False, True, True]


def test_null_handling_fns():
    b = make_batch()
    r = eval_expr(call("ifnull", col("a"), lit(-1)), b)
    data, valid = r.to_numpy()
    assert data.tolist()[:3] == [1, 2, -1]
    assert valid is None or valid.all()

    r = eval_expr(call("coalesce", col("a"), col("b"), lit(0)), b)
    data, _ = r.to_numpy()
    assert data.tolist() == [1.0, 2.0, 30.0, 4.0, 5.0]

    m = eval_predicate(call("is_null", col("a")), b)
    assert np.asarray(m).tolist() == [False, False, True, False, False]


def test_case_when():
    b = make_batch()
    e = call("case_when", col("a") > 3, lit(100), col("a") > 1, lit(50), lit(0))
    r = eval_expr(e, b)
    data, valid = r.to_numpy()
    assert data.tolist() == [0, 50, 0, 100, 100]


def test_datetime_parts():
    b = make_batch()
    # 18000 days after epoch = 2019-04-14; 18031 = 2019-05-15; 19000 = 2022-01-08
    y = eval_expr(call("year", col("d")), b).to_numpy()[0]
    m = eval_expr(call("month", col("d")), b).to_numpy()[0]
    d = eval_expr(call("day", col("d")), b).to_numpy()[0]
    import datetime
    for i, days in enumerate([18000, 18001, 18031]):
        dt = datetime.date(1970, 1, 1) + datetime.timedelta(days=days)
        assert (y[i], m[i], d[i]) == (dt.year, dt.month, dt.day)
    dow = eval_expr(call("dayofweek", col("d")), b).to_numpy()[0]
    assert dow[0] == datetime.date(2019, 4, 14).isoweekday() % 7 + 1


def test_string_functions_on_dict():
    b = make_batch()
    r = eval_expr(call("length", col("s")), b)
    data, valid = r.to_numpy()
    assert data.tolist()[:2] == [5, 6]
    assert valid.tolist() == [True, True, False, True, True]

    r = eval_expr(call("upper", col("s")), b)
    assert r.dictionary.values.tolist() == ["APPLE", "BANANA", "CHERRY"]
    m = eval_predicate(call("upper", col("s")) == "APPLE", b)
    assert np.asarray(m).tolist() == [True, False, False, False, True]

    r = eval_expr(call("substr", col("s"), lit(1), lit(3)), b)
    m = eval_predicate(r is not None and call("substr", col("s"), lit(1), lit(3)) == "app", b)
    assert np.asarray(m).tolist() == [True, False, False, False, True]


def test_concat_with_literal():
    b = make_batch()
    m = eval_predicate(call("concat", lit("x_"), col("s")) == "x_apple", b)
    assert np.asarray(m).tolist() == [True, False, False, False, True]


def test_round_mysql_semantics():
    t = pa.table({"x": pa.array([2.5, -2.5, 1.25, 1.35])})
    b = ColumnBatch.from_arrow(t)
    r = eval_expr(call("round", col("x")), b)
    data, _ = r.to_numpy()
    assert data.tolist()[:2] == [3.0, -3.0]  # away from zero, not banker's


def test_infer_type():
    b = make_batch()
    s = b.schema()
    assert infer_type(col("a") + col("b"), s) == LType.FLOAT64
    assert infer_type(col("a") / lit(2), s) == LType.FLOAT64
    assert infer_type(col("a") > lit(2), s) == LType.BOOL
    assert infer_type(call("year", col("d")), s) == LType.INT32


def test_between():
    b = make_batch()
    m = eval_predicate(call("between", col("a"), lit(2), lit(4)), b)
    assert np.asarray(m).tolist() == [False, True, False, True, False]


def test_cast():
    b = make_batch()
    r = eval_expr(call("cast", col("a"), lit(LType.FLOAT64)), b)
    assert r.ltype == LType.FLOAT64
    r = eval_expr(call("cast", col("s"), lit(LType.FLOAT64)), b)
    data, _ = r.to_numpy()
    assert data.tolist()[0] == 0.0  # 'apple' -> 0 per MySQL


def test_mod_sign_semantics():
    t = pa.table({"x": pa.array([7, -7, 7, -7], type=pa.int64()),
                  "y": pa.array([3, 3, -3, -3], type=pa.int64())})
    b = ColumnBatch.from_arrow(t)
    data, _ = eval_expr(col("x") % col("y"), b).to_numpy()
    assert data.tolist() == [1, -1, 1, -1]  # C fmod / MySQL, dividend sign


def test_temporal_literal_compare():
    t = pa.table({"d": pa.array([19722, 19723, 19724], type=pa.int32()).cast(pa.date32())})
    b = ColumnBatch.from_arrow(t)  # 19723 days = 2024-01-01
    m = eval_predicate(col("d") >= "2024-01-01", b)
    assert np.asarray(m).tolist() == [False, True, True]
    m = eval_predicate(col("d") == "2024-01-01", b)
    assert np.asarray(m).tolist() == [False, True, False]


def test_round_negative_digits():
    t = pa.table({"x": pa.array([15, 14, -15], type=pa.int64())})
    b = ColumnBatch.from_arrow(t)
    data, _ = eval_expr(call("round", col("x"), lit(-1)), b).to_numpy()
    assert data.tolist() == [20, 10, -20]


def test_in_mixed_types():
    t = pa.table({"x": pa.array([1, 2, 3], type=pa.int64())})
    b = ColumnBatch.from_arrow(t)
    m = eval_predicate(call("in", col("x"), lit(1), lit(2.5)), b)
    assert np.asarray(m).tolist() == [True, False, False]


def test_infer_cast_type():
    t = pa.table({"x": pa.array([1], type=pa.int64())})
    b = ColumnBatch.from_arrow(t)
    assert infer_type(call("cast", col("x"), lit(LType.FLOAT64)), b.schema()) == LType.FLOAT64


@pytest.mark.parametrize("other, want", [
    (LType.INT64, LType.FLOAT64), (LType.INT32, LType.FLOAT64),
    (LType.BOOL, LType.FLOAT64), (LType.FLOAT64, LType.FLOAT64),
    (LType.FLOAT32, LType.FLOAT32)])
def test_float_against_a_non_float_is_double(other, want):
    """MySQL does FLOAT arithmetic in double precision: a FLOAT against an
    integer promotes to DOUBLE, either way round (PR 35; a FLOAT against a
    FLOAT stays one)."""
    from baikaldb_tpu.types import promote

    assert promote(LType.FLOAT32, other) is want
    assert promote(other, LType.FLOAT32) is want
    assert promote(LType.FLOAT64, other) is LType.FLOAT64


def test_float_column_filter_keeps_the_rows_float64_keeps():
    """``v*2+1 > x`` over a FLOAT column: the value next above the boundary
    passes, as it does in float64; in float32 the sum rounds onto x.  One
    such row in 1e8 was BASELINE's filter's whole disagreement with a
    float64 reference on the chip (PERF.md section 6, PR 35)."""
    edge = np.float32(-0.25)
    v = np.array([edge, np.nextafter(edge, np.float32(0)),
                  np.nextafter(edge, np.float32(-1)), 2.0 ** -30, -2.0 ** -30,
                  0.0], np.float32)
    b = ColumnBatch.from_arrow(pa.table({"v": v}))
    e = call("add", call("mul", col("v"), lit(2)), lit(1))
    assert infer_type(e, b.schema()) is LType.FLOAT64
    got = np.asarray(eval_expr(e, b).data)
    assert got.dtype == np.float64
    assert np.array_equal(got, v.astype(np.float64) * 2 + 1)
    for x in (0.5, 1.0):
        keep = np.asarray(eval_predicate(call("gt", e, lit(x)), b))
        assert np.array_equal(keep[:len(v)],
                              v.astype(np.float64) * 2 + 1 > x)
    assert not np.array_equal(v * np.float32(2) + np.float32(1) > 0.5,
                              v.astype(np.float64) * 2 + 1 > 0.5)
