"""Expression -> jax lowering (the expr->XLA compiler).

This module replaces the reference's per-builtin Arrow translation table
(``src/expr/arrow_function.cpp`` + ``arrow_string_function.cpp`` +
``arrow_time_function.cpp``, registered in ArrowFunctionManager) and its
row-wise interpreter (``src/expr/internal_functions.cpp``).  ``eval_expr`` is
called at *trace time* inside the jitted query pipeline: every scalar builtin
becomes a handful of jnp ops that XLA fuses into the surrounding kernels, so a
``WHERE a > 5 AND b < 3`` costs one fused elementwise pass over HBM instead of
an interpreted tree per row.

MySQL NULL semantics: values are (data, validity) pairs; the default rule makes
a result row NULL if any input is NULL, with Kleene logic for AND/OR and
explicit handlers for IS NULL / COALESCE / CASE / IFNULL, mirroring the
reference's ExprValue null propagation.

String ops run on dictionary codes (column/dictionary.py): comparisons against
literals become integer range tests; per-value functions become host-side maps
over the *distinct* values, gathered by code on device.
"""

from __future__ import annotations

import math
import re
from typing import Optional
from dataclasses import replace

import jax.numpy as jnp
import numpy as np

from ..column.batch import Column, ColumnBatch
from ..column.dictionary import NULL_CODE, Dictionary, merge as dict_merge, \
    order_data
from ..types import LType, promote
from ..utils import datetime_kernels as dtk
from .ast import AggCall, Call, ColRef, Expr, Lit, Param
from .params import ParamStrBounds, current_param


class HostStr(str):
    """A string literal travelling through the compiler (host-side value)."""


class ExprError(ValueError):
    pass


# ----------------------------------------------------------------------
# entry points


def eval_expr(e: Expr, batch: ColumnBatch) -> Column:
    """Lower expression to jax ops over `batch`; returns a Column (data may be
    scalar-shaped for constant expressions)."""
    r = _eval(e, batch)
    if isinstance(r, HostStr):
        raise ExprError(f"string-valued expression {e!r} must be consumed by a "
                        "string-aware operator (comparison/LIKE/IN) or egress")
    if isinstance(r, ParamStrBounds):
        raise ExprError(f"string param in {e!r} is only valid as a direct "
                        "comparison operand (plan/paramize.py must pin it)")
    return r


def eval_output(e: Expr, batch: ColumnBatch) -> Column:
    """Like eval_expr, but a string-literal result becomes a constant
    dictionary column (for SELECT 'x' projections / egress)."""
    r = _eval(e, batch)
    if isinstance(r, HostStr):
        d = Dictionary(np.asarray([str(r)], dtype=str))
        return Column(jnp.zeros((), jnp.int32), None, LType.STRING, d)
    return r


def eval_predicate(e: Expr, batch: ColumnBatch):
    """Lower a predicate to a bool mask; NULL -> False (MySQL WHERE)."""
    c = eval_expr(e, batch)
    m = jnp.asarray(c.data, dtype=bool)
    if c.validity is not None:
        m = jnp.logical_and(m, c.validity)
    if m.ndim == 0:
        m = jnp.broadcast_to(m, (len(batch),))
    return m


def infer_type(e: Expr, schema) -> LType:
    """Static result type of e against a Schema (no device work)."""
    if isinstance(e, ColRef):
        return schema.field(e.name).ltype
    if isinstance(e, Lit):
        return _lit_type(e)
    if isinstance(e, Param):
        if e.ltype is None:
            raise ExprError(f"untyped param {e!r}")
        return e.ltype
    if isinstance(e, AggCall):
        from ..ops.hashagg import agg_result_type
        at = infer_type(e.args[0], schema) if e.args else LType.INT64
        return agg_result_type(e.op, at)
    if isinstance(e, Call):
        if e.op == "cast":
            t = e.args[1]
            assert isinstance(t, Lit)
            return t.value if isinstance(t.value, LType) else LType(t.value)
        rule = _TYPE_RULES.get(e.op)
        argts = [infer_type(a, schema) for a in e.args]
        if rule is None:
            return _default_type_rule(e.op, argts)
        return rule(argts) if callable(rule) else rule
    raise ExprError(f"cannot infer type of {e!r}")


# ----------------------------------------------------------------------
# internals


def _lit_type(e: Lit) -> LType:
    if e.ltype is not None:
        return e.ltype
    v = e.value
    if v is None:
        return LType.NULL
    if isinstance(v, bool):
        return LType.BOOL
    if isinstance(v, int):
        return LType.INT64
    if isinstance(v, float):
        return LType.FLOAT64
    if isinstance(v, str):
        return LType.STRING
    raise ExprError(f"unsupported literal {v!r}")


def _eval(e: Expr, batch: ColumnBatch):
    if isinstance(e, ColRef):
        return batch.column(e.name)
    if isinstance(e, Lit):
        lt = _lit_type(e)
        if lt is LType.NULL:
            return Column(jnp.zeros((), jnp.int32), jnp.zeros((), bool), LType.NULL)
        if lt is LType.STRING and e.ltype is None:
            return HostStr(e.value)
        v = e.value
        if lt is LType.STRING:
            return HostStr(v)
        return Column(jnp.asarray(v, lt.np_dtype), None, lt)
    if isinstance(e, Param):
        v = current_param(e.index)
        if e.kind == "strcmp":
            # (lo, hi) dictionary-code bounds, computed at bind time against
            # the compared column's dictionary (exec/session.py _bind_params)
            return ParamStrBounds(v[0], v[1])
        return Column(v, None, e.ltype)
    if isinstance(e, AggCall):
        raise ExprError(f"aggregate {e!r} must be hoisted by the planner")
    if isinstance(e, Call):
        h = _RAW.get(e.op)
        if h is not None:
            return h(e, batch)
        h = _SIMPLE.get(e.op)
        if h is None:
            raise ExprError(f"unknown function {e.op!r}")
        args = [_eval(a, batch) for a in e.args]
        args = [_devalue_hoststr(a, e.op) for a in args]
        return _with_null_prop(h, args)
    raise ExprError(f"cannot evaluate {e!r}")


# functions whose arguments MySQL implicitly casts string->temporal; the
# cast must not leak into plain arithmetic ('2024-01-10' + 1 is a NUMERIC
# prefix cast in MySQL, not a date)
_TEMPORAL_ARG_FNS = {
    "year", "month", "day", "dayofmonth", "quarter", "dayofweek", "weekday",
    "dayofyear", "last_day", "week", "yearweek", "weekofyear", "datediff",
    "date", "to_days", "unix_timestamp", "time_to_sec", "date_add_days",
    "date_sub_days", "date_add_months", "date_sub_months", "date_add_us",
    "microsecond", "to_seconds", "greatest", "least",
}


def _devalue_hoststr(a, op):
    if isinstance(a, ParamStrBounds):
        raise ExprError(f"string param not supported as argument of {op!r}; "
                        "valid only as a direct comparison operand")
    if isinstance(a, HostStr):
        if op in _TEMPORAL_ARG_FNS:
            c = _temporal_hoststr(a)
            if c is not None:
                return c    # MySQL implicit string->temporal cast
        raise ExprError(f"string literal not supported as argument of {op!r} "
                        "(device path); handled only in comparisons/LIKE/IN")
    return a


def _temporal_hoststr(a) -> Optional[Column]:
    """A date/datetime-shaped string literal as a temporal scalar Column
    (MySQL's implicit cast in temporal contexts), else None."""
    s = str(a).strip()
    lt = LType.DATE if len(s) <= 10 else LType.DATETIME
    try:
        v = parse_temporal(s, lt)
    except (ValueError, ExprError):
        return None
    return Column(jnp.asarray(v, lt.np_dtype), None, lt)


def _with_null_prop(h, args: list[Column]) -> Column:
    out = h(*args)
    vs = [a.validity for a in args if a.validity is not None]
    if out.validity is not None:
        vs.append(out.validity)
    validity = None
    for v in vs:
        validity = v if validity is None else jnp.logical_and(validity, v)
    return replace(out, validity=validity)


def _num(c: Column, lt: LType) -> jnp.ndarray:
    """Cast data to physical dtype of lt."""
    return jnp.asarray(c.data).astype(lt.np_dtype)


def cast_column(c: Column, lt: LType) -> Column:
    """Implicit/explicit cast (reference: build_arrow_expr_with_cast,
    src/expr/arrow_function.cpp)."""
    if c.ltype == lt:
        return c
    if c.ltype is LType.STRING:
        if lt.is_numeric:
            if c.dictionary is None:
                raise ExprError("cast string->numeric requires a dictionary")
            table = jnp.asarray(c.dictionary.map_values(_mysql_str_to_num, lt.np_dtype))
            data = jnp.take(table, jnp.clip(c.data, 0, None), mode="clip")
            return Column(data, c.validity, lt)
            # NULL codes clip to 0 but validity already marks them invalid
        raise ExprError(f"unsupported cast string->{lt}")
    if lt is LType.STRING:
        raise ExprError("cast ->string is egress-only (host)")
    if c.ltype is LType.DATE and lt in (LType.DATETIME, LType.TIMESTAMP):
        return Column(c.data.astype(jnp.int64) * dtk.US_PER_DAY, c.validity, lt)
    if c.ltype in (LType.DATETIME, LType.TIMESTAMP) and lt is LType.DATE:
        return Column(dtk.dt_days(c.data), c.validity, lt)
    return Column(_num(c, lt), c.validity, lt)


def _mysql_str_to_num(s: str):
    """MySQL-style leading-numeric parse ('12abc' -> 12, 'x' -> 0)."""
    m = re.match(r"\s*[-+]?\d*\.?\d+(e[-+]?\d+)?", s, re.I)
    return float(m.group(0)) if m and m.group(0).strip() else 0.0


def parse_temporal(s: str, lt: LType) -> int:
    """'YYYY-MM-DD[ HH:MM:SS[.ffffff]]' -> epoch days (DATE) or micros."""
    import datetime as _dt

    s = s.strip()
    try:
        if len(s) <= 10:
            d = _dt.date.fromisoformat(s)
            t = _dt.datetime(d.year, d.month, d.day)
        else:
            t = _dt.datetime.fromisoformat(s.replace("/", "-"))
    except ValueError as exc:
        raise ExprError(f"cannot parse temporal literal {s!r}") from exc
    days = (t.date() - _dt.date(1970, 1, 1)).days
    if lt is LType.DATE:
        return days
    us = days * dtk.US_PER_DAY + (t.hour * 3600 + t.minute * 60 + t.second) * dtk.US_PER_SEC \
        + t.microsecond
    return us


_parse_temporal_literal = parse_temporal


# ----------------------------------------------------------------------
# simple (null-propagating) builtins


def _binary_arith(op_name, fn, force_type=None):
    def h(a: Column, b: Column) -> Column:
        lt = force_type or promote(a.ltype, b.ltype)
        if op_name in ("add", "sub", "mul") and lt.is_integer and lt is not LType.UINT64:
            lt = LType.INT64 if _RANKED(lt) else lt
        x, y = _num(a, lt), _num(b, lt)
        return Column(fn(x, y), None, lt)
    return h


def _RANKED(lt):
    return lt in (LType.INT8, LType.INT16, LType.INT32, LType.BOOL)


def _div(a: Column, b: Column) -> Column:
    y = _num(b, LType.FLOAT64)
    x = _num(a, LType.FLOAT64)
    nz = y != 0
    return Column(x / jnp.where(nz, y, 1.0), nz, LType.FLOAT64)


def _int_div(a: Column, b: Column) -> Column:
    lt = LType.INT64
    x, y = _num(a, lt), _num(b, lt)
    nz = y != 0
    return Column(jnp.floor_divide(x, jnp.where(nz, y, 1)), nz, lt)


def _mod(a: Column, b: Column) -> Column:
    """MySQL MOD: C fmod semantics — result takes the dividend's sign."""
    lt = promote(a.ltype, b.ltype)
    if lt.is_integer:
        lt = LType.INT64
    x, y = _num(a, lt), _num(b, lt)
    nz = y != 0
    safe = jnp.where(nz, y, jnp.ones((), y.dtype))
    if lt.is_float:
        q = jnp.trunc(x / safe)
    else:
        q = jnp.sign(x) * jnp.sign(safe) * (jnp.abs(x) // jnp.abs(safe))
    return Column(x - q * safe, nz, lt)


def _unary_math(fn, out=LType.FLOAT64, domain=None):
    def h(a: Column) -> Column:
        x = _num(a, out if out.is_float else a.ltype)
        ok = domain(x) if domain is not None else None
        if ok is not None:
            x = jnp.where(ok, x, 1.0)
        return Column(fn(x), ok, out)
    return h


def _round_half_away(x, d):
    s = 10.0 ** d
    y = x * s
    return jnp.trunc(y + jnp.sign(y) * 0.5) / s


_SIMPLE = {}
_TYPE_RULES = {}


def _reg(name, h, trule=None):
    _SIMPLE[name] = h
    if trule is not None:
        _TYPE_RULES[name] = trule


_reg("add", _binary_arith("add", jnp.add))
_reg("sub", _binary_arith("sub", jnp.subtract))
_reg("mul", _binary_arith("mul", jnp.multiply))
_reg("div", _div, LType.FLOAT64)
_reg("int_div", _int_div, LType.INT64)
_reg("mod", _mod)
_reg("neg", lambda a: Column(-jnp.asarray(a.data) if a.ltype.is_float
                             else -_num(a, LType.INT64),
                             None, a.ltype if a.ltype.is_float else LType.INT64))
_reg("abs", lambda a: Column(jnp.abs(a.data), None, a.ltype))
_reg("ceil", lambda a: Column(jnp.ceil(_num(a, LType.FLOAT64)).astype(jnp.int64), None, LType.INT64), LType.INT64)
_reg("floor", lambda a: Column(jnp.floor(_num(a, LType.FLOAT64)).astype(jnp.int64), None, LType.INT64), LType.INT64)
_reg("sqrt", _unary_math(jnp.sqrt, domain=lambda x: x >= 0), LType.FLOAT64)
_reg("exp", _unary_math(jnp.exp), LType.FLOAT64)
_reg("ln", _unary_math(jnp.log, domain=lambda x: x > 0), LType.FLOAT64)
_reg("log10", _unary_math(jnp.log10, domain=lambda x: x > 0), LType.FLOAT64)
_reg("log2", _unary_math(jnp.log2, domain=lambda x: x > 0), LType.FLOAT64)
_reg("sin", _unary_math(jnp.sin), LType.FLOAT64)
_reg("cos", _unary_math(jnp.cos), LType.FLOAT64)
_reg("tan", _unary_math(jnp.tan), LType.FLOAT64)
_reg("sign", lambda a: Column(jnp.sign(_num(a, LType.FLOAT64)).astype(jnp.int32), None, LType.INT32), LType.INT32)
_reg("pow", lambda a, b: Column(jnp.power(_num(a, LType.FLOAT64), _num(b, LType.FLOAT64)), None, LType.FLOAT64), LType.FLOAT64)


def _round(a: Column, d: Column | None = None) -> Column:
    if a.ltype.is_integer:
        if d is None:
            return Column(a.data, None, a.ltype)
        # ROUND(int, -n) buckets to powers of ten (MySQL: ROUND(15,-1)=20)
        r = _round_half_away(_num(a, LType.FLOAT64), jnp.asarray(d.data))
        return Column(r.astype(jnp.int64), None, LType.INT64)
    nd = jnp.asarray(d.data) if d is not None else 0
    return Column(_round_half_away(_num(a, LType.FLOAT64), nd), None, LType.FLOAT64)


def _truncate(a: Column, d: Column) -> Column:
    s = 10.0 ** jnp.asarray(d.data)
    x = _num(a, LType.FLOAT64)
    return Column(jnp.trunc(x * s) / s, None, LType.FLOAT64)


_reg("round", _round)
_reg("truncate", _truncate, LType.FLOAT64)
_reg("greatest", lambda *cs: _varargs_minmax(cs, jnp.maximum))
_reg("least", lambda *cs: _varargs_minmax(cs, jnp.minimum))


def _varargs_minmax(cs, fn):
    lt = cs[0].ltype
    for c in cs[1:]:
        lt = promote(lt, c.ltype)
    out = _num(cs[0], lt)
    for c in cs[1:]:
        out = fn(out, _num(c, lt))
    return Column(out, None, lt)


# temporal ---------------------------------------------------------------


def _as_days(c: Column):
    if c.ltype is LType.DATE:
        return c.data.astype(jnp.int32)
    if c.ltype in (LType.DATETIME, LType.TIMESTAMP):
        return dtk.dt_days(c.data)
    raise ExprError(f"temporal function on non-temporal {c.ltype}")


def _dt_part(fn):
    return lambda a: Column(fn(_as_days(a)), None, LType.INT32)


_reg("year", _dt_part(dtk.year_of_days), LType.INT32)
_reg("month", _dt_part(dtk.month_of_days), LType.INT32)
_reg("day", _dt_part(dtk.day_of_days), LType.INT32)
_reg("dayofmonth", _dt_part(dtk.day_of_days), LType.INT32)
_reg("quarter", _dt_part(dtk.quarter_of_days), LType.INT32)
_reg("dayofweek", _dt_part(dtk.day_of_week), LType.INT32)
_reg("weekday", _dt_part(dtk.weekday), LType.INT32)
_reg("dayofyear", _dt_part(dtk.day_of_year), LType.INT32)
_reg("last_day", lambda a: Column(dtk.last_day(_as_days(a)), None, LType.DATE), LType.DATE)
_reg("to_days", lambda a: Column(_as_days(a) + 719528, None, LType.INT64), LType.INT64)
_reg("date", lambda a: Column(_as_days(a), None, LType.DATE), LType.DATE)
_reg("datediff", lambda a, b: Column((_as_days(a) - _as_days(b)).astype(jnp.int64), None, LType.INT64), LType.INT64)


def _hour(a):
    return Column((dtk.dt_time_of_day_us(a.data) // dtk.US_PER_HOUR).astype(jnp.int32), None, LType.INT32)


def _minute(a):
    return Column(((dtk.dt_time_of_day_us(a.data) // dtk.US_PER_MIN) % 60).astype(jnp.int32), None, LType.INT32)


def _second(a):
    return Column(((dtk.dt_time_of_day_us(a.data) // dtk.US_PER_SEC) % 60).astype(jnp.int32), None, LType.INT32)


_reg("hour", _hour, LType.INT32)
_reg("minute", _minute, LType.INT32)
_reg("second", _second, LType.INT32)


def _date_add(a: Column, n: Column) -> Column:
    if a.ltype is LType.DATE:
        return Column(a.data + n.data.astype(jnp.int32), None, LType.DATE)
    return Column(a.data + n.data.astype(jnp.int64) * dtk.US_PER_DAY, None, a.ltype)


def _date_sub(a: Column, n: Column) -> Column:
    if a.ltype is LType.DATE:
        return Column(a.data - n.data.astype(jnp.int32), None, LType.DATE)
    return Column(a.data - n.data.astype(jnp.int64) * dtk.US_PER_DAY, None, a.ltype)


_reg("date_add_days", _date_add)
_reg("date_sub_days", _date_sub)
_reg("unix_timestamp", lambda a: Column(
    (a.data.astype(jnp.int64) * dtk.US_PER_DAY if a.ltype is LType.DATE else a.data)
    // dtk.US_PER_SEC, None, LType.INT64), LType.INT64)
_reg("from_unixtime", lambda a: Column(_num(a, LType.INT64) * dtk.US_PER_SEC, None, LType.DATETIME), LType.DATETIME)

_TYPE_RULES.update({
    "div": LType.FLOAT64, "int_div": LType.INT64,
    "add": lambda ts: promote(ts[0], ts[1]),
    "sub": lambda ts: promote(ts[0], ts[1]),
    "mul": lambda ts: promote(ts[0], ts[1]),
    "mod": lambda ts: promote(ts[0], ts[1]),
    "neg": lambda ts: ts[0] if ts[0].is_float else LType.INT64,
    "abs": lambda ts: ts[0],
    "round": lambda ts: ts[0] if ts[0].is_integer else LType.FLOAT64,
    "greatest": lambda ts: _fold_promote(ts), "least": lambda ts: _fold_promote(ts),
    "date_add_days": lambda ts: ts[0], "date_sub_days": lambda ts: ts[0],
})


def _fold_promote(ts):
    lt = ts[0]
    for t in ts[1:]:
        lt = promote(lt, t)
    return lt


def _default_type_rule(op, argts):
    rules = {
        "eq": LType.BOOL, "ne": LType.BOOL, "lt": LType.BOOL, "le": LType.BOOL,
        "gt": LType.BOOL, "ge": LType.BOOL, "and": LType.BOOL, "or": LType.BOOL,
        "not": LType.BOOL, "xor": LType.BOOL, "is_null": LType.BOOL,
        "is_not_null": LType.BOOL, "like": LType.BOOL, "not_like": LType.BOOL,
        "__row_index": LType.INT64,
        "in": LType.BOOL, "not_in": LType.BOOL, "between": LType.BOOL,
        "match_against": LType.FLOAT32,
        "case_when": argts[1] if len(argts) > 1 else LType.NULL,
        "if": argts[1] if len(argts) > 1 else LType.NULL,
        "ifnull": argts[0] if argts else LType.NULL,
        "nullif": argts[0] if argts else LType.NULL,
        "coalesce": argts[0] if argts else LType.NULL,
        "length": LType.INT64, "char_length": LType.INT64,
        "upper": LType.STRING, "lower": LType.STRING, "trim": LType.STRING,
        "ltrim": LType.STRING, "rtrim": LType.STRING, "reverse": LType.STRING,
        "substr": LType.STRING, "concat": LType.STRING,
        "hash": LType.INT64,
    }
    if op in rules:
        return rules[op]
    raise ExprError(f"no type rule for {op!r}")


# ----------------------------------------------------------------------
# raw handlers (custom null semantics / string-aware / host literals)

_RAW = {}


def _raw(name):
    def deco(fn):
        _RAW[name] = fn
        return fn
    return deco


_CMP = {"eq": jnp.equal, "ne": jnp.not_equal, "lt": jnp.less,
        "le": jnp.less_equal, "gt": jnp.greater, "ge": jnp.greater_equal}


def _make_cmp(op):
    def h(e: Call, batch: ColumnBatch) -> Column:
        a = _eval(e.args[0], batch)
        b = _eval(e.args[1], batch)
        return _compare(op, a, b, batch)
    return h


for _op in _CMP:
    _RAW[_op] = _make_cmp(_op)


def _compare(op, a, b, batch) -> Column:
    if isinstance(a, ParamStrBounds) or isinstance(b, ParamStrBounds):
        flip = isinstance(a, ParamStrBounds)
        colc, pb = (b, a) if flip else (a, b)
        if flip:
            op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(op, op)
        if not (isinstance(colc, Column) and colc.ltype is LType.STRING
                and colc.dictionary is not None):
            raise ExprError("string param requires a dictionary-encoded "
                            "string column operand")
        return _cmp_code_bounds(op, colc, pb.lo, pb.hi)
    if isinstance(a, HostStr) and isinstance(b, HostStr):
        r = {"eq": a == b, "ne": a != b, "lt": a < b, "le": a <= b,
             "gt": a > b, "ge": a >= b}[op]
        return Column(jnp.asarray(r), None, LType.BOOL)
    if isinstance(b, HostStr) or isinstance(a, HostStr):
        flip = isinstance(a, HostStr)
        colc, s = (b, a) if flip else (a, b)
        if flip:
            op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(op, op)
        if colc.ltype is LType.STRING and colc.dictionary is not None:
            return _cmp_code_literal(op, colc, str(s))
        if colc.ltype.is_temporal:
            # WHERE date_col >= '2024-01-01': parse the literal as a date
            litv = _parse_temporal_literal(str(s), colc.ltype)
            b = Column(jnp.asarray(litv, colc.ltype.np_dtype), None, colc.ltype)
            a = colc
        else:
            # MySQL: numeric vs string compares as double
            litv = _mysql_str_to_num(str(s))
            b = Column(jnp.asarray(litv, jnp.float64), None, LType.FLOAT64)
            a = colc
    if a.ltype is LType.STRING or b.ltype is LType.STRING:
        if a.ltype is LType.STRING and b.ltype is LType.STRING:
            a, b = _align_string_columns(a, b)
            x, y = order_data(a), order_data(b)
        else:
            sc = a if a.ltype is LType.STRING else b
            oc = b if a.ltype is LType.STRING else a
            sc = cast_column(sc, LType.FLOAT64)
            a, b = (sc, oc) if a.ltype is LType.STRING else (oc, sc)
            x, y = _num(a, LType.FLOAT64), _num(b, LType.FLOAT64)
    else:
        lt = promote(a.ltype, b.ltype)
        x, y = _num(a, lt), _num(b, lt)
    out = Column(_CMP[op](x, y), None, LType.BOOL)
    return _with_null_prop(lambda *_: out, [a, b])


def _cmp_code_literal(op, c: Column, s: str) -> Column:
    d = c.dictionary
    return _cmp_code_bounds(op, c, d.lower_bound(s), d.upper_bound(s))


def _cmp_code_bounds(op, c: Column, lo, hi) -> Column:
    """Range test over dictionary codes in order space (their order keys
    once the dictionary has a tail); lo/hi may be trace-time host ints
    (baked literal) or traced scalars (strcmp param)."""
    codes = order_data(c)
    if op == "eq":
        data = (codes >= lo) & (codes < hi)
    elif op == "ne":
        data = (codes < lo) | (codes >= hi)
    elif op == "lt":
        data = codes < lo
    elif op == "le":
        data = codes < hi
    elif op == "gt":
        data = codes >= hi
    else:  # ge
        data = codes >= lo
    return Column(data, c.validity, LType.BOOL)


def _align_string_columns(a: Column, b: Column) -> tuple[Column, Column]:
    if a.dictionary is b.dictionary or (a.dictionary and b.dictionary and
                                        a.dictionary._id == b.dictionary._id):
        return a, b
    if a.dictionary is None or b.dictionary is None:
        raise ExprError("string column without dictionary in comparison")
    m, ra, rb = dict_merge(a.dictionary, b.dictionary)
    ta, tb = jnp.asarray(ra), jnp.asarray(rb)
    da = jnp.where(a.data >= 0, jnp.take(ta, jnp.clip(a.data, 0, None), mode="clip"), NULL_CODE)
    db = jnp.where(b.data >= 0, jnp.take(tb, jnp.clip(b.data, 0, None), mode="clip"), NULL_CODE)
    return (replace(a, data=da, dictionary=m), replace(b, data=db, dictionary=m))


@_raw("and")
def _and(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    av, bv = a.valid_mask(), b.valid_mask()
    at = jnp.asarray(a.data, bool)
    bt = jnp.asarray(b.data, bool)
    data = at & bt
    # Kleene: NULL unless (false present) or both valid
    f = (av & ~at) | (bv & ~bt)
    validity = f | (av & bv)
    return Column(data & av & bv, validity, LType.BOOL)


@_raw("or")
def _or(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    av, bv = a.valid_mask(), b.valid_mask()
    at = jnp.asarray(a.data, bool) & av
    bt = jnp.asarray(b.data, bool) & bv
    data = at | bt
    validity = data | (av & bv)
    return Column(data, validity, LType.BOOL)


@_raw("not")
def _not(e, batch):
    a = eval_expr(e.args[0], batch)
    return Column(~jnp.asarray(a.data, bool), a.validity, LType.BOOL)


@_raw("xor")
def _xor(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    out = Column(jnp.asarray(a.data, bool) ^ jnp.asarray(b.data, bool), None, LType.BOOL)
    return _with_null_prop(lambda *_: out, [a, b])


@_raw("is_null")
def _is_null(e, batch):
    a = _eval(e.args[0], batch)
    if isinstance(a, HostStr):
        return Column(jnp.asarray(False), None, LType.BOOL)
    v = a.valid_mask()
    data = ~v if a.validity is not None else jnp.zeros(jnp.shape(a.data), bool)
    return Column(data, None, LType.BOOL)


@_raw("is_not_null")
def _is_not_null(e, batch):
    c = _is_null(e, batch)
    return Column(~jnp.asarray(c.data, bool), None, LType.BOOL)


@_raw("ifnull")
def _ifnull(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    lt = promote(a.ltype, b.ltype)
    av = a.valid_mask()
    data = jnp.where(av, _num(a, lt), _num(b, lt))
    validity = jnp.where(av, True, b.valid_mask())
    return Column(data, validity, lt)


@_raw("nullif")
def _nullif(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    lt = promote(a.ltype, b.ltype)
    equal = _num(a, lt) == _num(b, lt)
    validity = a.valid_mask() & ~(equal & b.valid_mask())
    # result keeps a's data AND a's type (only validity changes)
    return Column(a.data, validity, a.ltype, a.dictionary)


@_raw("coalesce")
def _coalesce(e, batch):
    cols = [eval_expr(a, batch) for a in e.args]
    lt = cols[0].ltype
    for c in cols[1:]:
        lt = promote(lt, c.ltype)
    data = _num(cols[-1], lt)
    validity = cols[-1].valid_mask()
    for c in reversed(cols[:-1]):
        v = c.valid_mask()
        data = jnp.where(v, _num(c, lt), data)
        validity = v | validity
    return Column(data, validity, lt)


@_raw("if")
def _if(e, batch):
    cond = eval_predicate(e.args[0], batch)
    a = eval_expr(e.args[1], batch)
    b = eval_expr(e.args[2], batch)
    lt = promote(a.ltype, b.ltype)
    data = jnp.where(cond, _num(a, lt), _num(b, lt))
    validity = jnp.where(cond, a.valid_mask(), b.valid_mask())
    return Column(data, validity, lt)


@_raw("case_when")
def _case_when(e, batch):
    """args = [cond1, val1, cond2, val2, ..., (else_val)?]"""
    args = list(e.args)
    else_e = args.pop() if len(args) % 2 == 1 else None
    raw_vals = [_eval(args[i + 1], batch) for i in range(0, len(args), 2)]
    raw_else = _eval(else_e, batch) if else_e is not None else None
    if any(isinstance(v, HostStr) for v in raw_vals + [raw_else]):
        # string-valued CASE: branch values become codes into a synthetic
        # sorted dictionary (device work stays integer)
        conds = [eval_predicate(args[i], batch) for i in range(0, len(args), 2)]
        branch_vals = raw_vals + ([raw_else] if else_e is not None else [])
        if not all(isinstance(v, HostStr) for v in branch_vals):
            raise ExprError("CASE mixing string literals and non-strings")
        values = np.unique(np.asarray([str(v) for v in branch_vals], dtype=str))
        d = Dictionary(values)
        codes = [int(np.searchsorted(values, str(v))) for v in raw_vals]
        if raw_else is not None:
            data = jnp.asarray(int(np.searchsorted(values, str(raw_else))), jnp.int32)
            validity = jnp.asarray(True)
        else:
            data = jnp.asarray(NULL_CODE)
            validity = jnp.asarray(False)
        for cond, code in zip(reversed(conds), reversed(codes)):
            data = jnp.where(cond, jnp.int32(code), data)
            validity = jnp.where(cond, True, validity)
        n = len(batch)
        data = jnp.broadcast_to(data, (n,)) if jnp.ndim(data) == 0 else data
        validity = jnp.broadcast_to(validity, (n,)) if jnp.ndim(validity) == 0 else validity
        return Column(data, validity, LType.STRING, d)
    pairs = [(eval_predicate(args[i], batch), eval_expr(args[i + 1], batch))
             for i in range(0, len(args), 2)]
    lt = pairs[0][1].ltype
    for _, v in pairs[1:]:
        lt = promote(lt, v.ltype)
    if else_e is not None:
        ec = eval_expr(else_e, batch)
        lt = promote(lt, ec.ltype)
        data, validity = _num(ec, lt), ec.valid_mask()
    else:
        data = jnp.zeros((), lt.np_dtype)
        validity = jnp.asarray(False)
    for cond, v in reversed(pairs):
        data = jnp.where(cond, _num(v, lt), data)
        validity = jnp.where(cond, v.valid_mask(), validity)
    return Column(data, validity, lt)


@_raw("between")
def _between(e, batch):
    x, lo, hi = e.args
    return _and(Call("and", (Call("ge", (x, lo)), Call("le", (x, hi)))), batch)


@_raw("in")
def _in(e, batch):
    return _in_impl(e, batch, negate=False)


@_raw("not_in")
def _not_in(e, batch):
    return _in_impl(e, batch, negate=True)


def _in_impl(e, batch, negate):
    a = _eval(e.args[0], batch)
    items = e.args[1:]
    if isinstance(a, Column) and a.ltype is LType.STRING and a.dictionary is not None:
        codes = []
        for it in items:
            if not isinstance(it, Lit) or not isinstance(it.value, str):
                raise ExprError("IN on string column requires string literals")
            c = a.dictionary.code_of(it.value)
            if c is not None:
                codes.append(c)
        if codes:
            table = jnp.asarray(np.asarray(sorted(codes), np.int32))
            pos = jnp.searchsorted(table, a.data)
            hit = jnp.take(table, jnp.clip(pos, 0, len(codes) - 1)) == a.data
        else:
            hit = jnp.zeros(jnp.shape(a.data), bool)
        data = ~hit if negate else hit
        return Column(data, a.validity, LType.BOOL)
    vals = []
    lt = a.ltype
    for it in items:
        if not isinstance(it, Lit):
            raise ExprError("IN requires literal list (round 1)")
        vals.append(it.value)
        lt = promote(lt, _lit_type(it))
    arr = jnp.asarray(np.sort(np.asarray(vals, lt.np_dtype)))
    x = _num(a, lt)
    pos = jnp.searchsorted(arr, x)
    hit = jnp.take(arr, jnp.clip(pos, 0, len(vals) - 1), mode="clip") == x
    data = ~hit if negate else hit
    return Column(data, a.validity, LType.BOOL)


def _like_to_regex(p: str) -> str:
    out = []
    i = 0
    while i < len(p):
        ch = p[i]
        if ch == "\\" and i + 1 < len(p):
            out.append(re.escape(p[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return "^" + "".join(out) + "$"


def _like_impl(e, batch, negate):
    a = _eval(e.args[0], batch)
    pat = e.args[1]
    if not isinstance(pat, Lit) or not isinstance(pat.value, str):
        raise ExprError("LIKE pattern must be a string literal")
    if not (isinstance(a, Column) and a.ltype is LType.STRING and a.dictionary is not None):
        raise ExprError("LIKE requires a dictionary-encoded string column")
    p = pat.value
    plain = p.replace("\\%", "").replace("\\_", "")
    if "%" not in plain.rstrip("%") and "_" not in plain and p.endswith("%") and not p.endswith("\\%"):
        lo, hi = a.dictionary.prefix_range(p[:-1].replace("\\%", "%").replace("\\_", "_"))
        x = order_data(a)
        hit = (x >= lo) & (x < hi)
    else:
        rx = re.compile(_like_to_regex(p), re.S)
        mask = a.dictionary.match_mask(lambda v: rx.match(v) is not None)
        hit = jnp.take(jnp.asarray(mask), jnp.clip(a.data, 0, None), mode="clip")
    data = ~hit if negate else hit
    return Column(data, a.validity, LType.BOOL)


@_raw("__row_index")
def _row_index(e, batch):
    """Internal: a globally-unique row identity (planner-injected for
    EXISTS-with-residual decorrelation).  Inside a shard_map each shard
    offsets by its mesh position so identities stay unique across devices."""
    import jax

    n = len(batch)
    idx = jnp.arange(n, dtype=jnp.int64)
    try:
        from ..parallel.mesh import AXIS
        idx = idx + jnp.int64(n) * jax.lax.axis_index(AXIS).astype(jnp.int64)
    except NameError:       # not running under shard_map
        pass
    return Column(idx, None, LType.INT64)


@_raw("like")
def _like(e, batch):
    return _like_impl(e, batch, False)


@_raw("not_like")
def _not_like(e, batch):
    return _like_impl(e, batch, True)


@_raw("match_against")
def _match_against(e, batch):
    """MATCH(col) AGAINST('query' [IN BOOLEAN MODE]) — fulltext search.

    Compiles exactly like LIKE: the inverted index (index/fulltext.py) over
    the column's dictionary answers the query host-side as a per-code
    BM25 relevance array, gathered by code on device (reference: reverse
    index + weighted boolean executor, include/reverse/).  The value is the
    MySQL relevance FLOAT — >0 means match, so WHERE truth falls out of
    eval_predicate's nonzero coercion and ORDER BY MATCH(..) ranks."""
    a = _eval(e.args[0], batch)
    q = e.args[1]
    if not (isinstance(q, Lit) and isinstance(q.value, str)):
        raise ExprError("AGAINST requires a string literal")
    boolean_mode = bool(e.args[2].value) if len(e.args) > 2 else False
    if not (isinstance(a, Column) and a.ltype is LType.STRING
            and a.dictionary is not None):
        raise ExprError("MATCH requires a dictionary-encoded string column")
    from ..index.fulltext import match_scores

    scores = match_scores(a.dictionary, q.value, boolean_mode=boolean_mode)
    hit = jnp.take(jnp.asarray(scores), jnp.clip(a.data, 0, None),
                   mode="clip")
    hit = jnp.where(a.data >= 0, hit, jnp.float32(0.0))
    return Column(hit, a.validity, LType.FLOAT32)


@_raw("cast")
def _cast(e, batch):
    # args = [value, Lit(type-name)]
    target = e.args[1]
    assert isinstance(target, Lit)
    lt = LType(target.value) if not isinstance(target.value, LType) else target.value
    a = _eval(e.args[0], batch)
    if isinstance(a, HostStr):
        v = _mysql_str_to_num(str(a))
        return Column(jnp.asarray(v, lt.np_dtype), None, lt)
    return cast_column(a, lt)


# string functions via dictionary transforms --------------------------------


def _dict_transform(c: Column, fn) -> Column:
    """Apply a host string->string fn over distinct values; re-sort + remap."""
    if c.dictionary is None:
        raise ExprError("string function requires dictionary")
    if len(c.dictionary.values) == 0:
        # every row is NULL (e.g. a 1-row slice whose value is NULL):
        # nothing to transform, and jnp.take on an empty axis would throw
        return Column(jnp.full_like(c.data, NULL_CODE), c.validity,
                      LType.STRING, Dictionary(np.asarray([], dtype=str)))
    new_vals = np.asarray([fn(v) for v in c.dictionary.values], dtype=str)
    uniq, inv = np.unique(new_vals, return_inverse=True)
    remap = jnp.asarray(inv.astype(np.int32))
    data = jnp.where(c.data >= 0,
                     jnp.take(remap, jnp.clip(c.data, 0, None), mode="clip"),
                     NULL_CODE)
    return Column(data, c.validity, LType.STRING, Dictionary(uniq))


def _dict_scalar(c: Column, fn, lt: LType) -> Column:
    if c.dictionary is None:
        raise ExprError("string function requires dictionary")
    if len(c.dictionary.values) == 0:
        return Column(jnp.zeros(c.data.shape, lt.np_dtype), c.validity, lt)
    table = jnp.asarray(c.dictionary.map_values(fn, lt.np_dtype))
    data = jnp.take(table, jnp.clip(c.data, 0, None), mode="clip")
    return Column(data, c.validity, lt)


def _str_fn(name, fn):
    @_raw(name)
    def h(e, batch, fn=fn):
        a = _eval(e.args[0], batch)
        if isinstance(a, HostStr):
            return HostStr(fn(str(a)))
        return _dict_transform(a, fn)
    return h


_str_fn("upper", str.upper)
_str_fn("lower", str.lower)
_str_fn("trim", str.strip)
_str_fn("ltrim", str.lstrip)
_str_fn("rtrim", str.rstrip)
_str_fn("reverse", lambda s: s[::-1])


@_raw("length")
def _length(e, batch):
    a = _eval(e.args[0], batch)
    if isinstance(a, HostStr):
        return Column(jnp.asarray(len(str(a).encode()), jnp.int64), None, LType.INT64)
    return _dict_scalar(a, lambda s: len(s.encode()), LType.INT64)


@_raw("char_length")
def _char_length(e, batch):
    a = _eval(e.args[0], batch)
    if isinstance(a, HostStr):
        return Column(jnp.asarray(len(str(a)), jnp.int64), None, LType.INT64)
    return _dict_scalar(a, len, LType.INT64)


@_raw("substr")
def _substr(e, batch):
    a = _eval(e.args[0], batch)
    pos = e.args[1]
    ln = e.args[2] if len(e.args) > 2 else None
    if not isinstance(pos, Lit) or (ln is not None and not isinstance(ln, Lit)):
        raise ExprError("SUBSTR pos/len must be literals (round 1)")
    p = int(pos.value)
    n = None if ln is None else int(ln.value)

    def f(s: str) -> str:
        i = p - 1 if p > 0 else len(s) + p
        if i < 0:
            return ""
        return s[i:] if n is None else s[i:i + n]

    if isinstance(a, HostStr):
        return HostStr(f(str(a)))
    return _dict_transform(a, f)


@_raw("concat")
def _concat(e, batch):
    parts = [_eval(a, batch) for a in e.args]
    col_idx = [i for i, p in enumerate(parts) if isinstance(p, Column)]
    if not col_idx:
        return HostStr("".join(str(p) for p in parts))
    if len(col_idx) > 1:
        raise ExprError("CONCAT of multiple columns is egress-only (round 1)")
    i = col_idx[0]
    pre = "".join(str(p) for p in parts[:i])
    post = "".join(str(p) for p in parts[i + 1:])
    return _dict_transform(parts[i], lambda s: pre + s + post)


@_raw("hash")
def _hash(e, batch):
    from ..utils.hashing import hash_columns
    cols = [eval_expr(a, batch) for a in e.args]
    return Column(hash_columns([c.data for c in cols]), None, LType.INT64)


# extended builtin library registers itself into the tables above
from . import builtins_ext  # noqa: E402,F401  (import for side effects)
