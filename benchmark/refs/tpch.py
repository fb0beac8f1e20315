"""TPC-H Q1 and Q6 over the generated ``lineitem``, in numpy.

The configuration states DOUBLE (IEEE float64) columns.  The references sum
in float64 with numpy's pairwise ``sum`` over the rows of one group (a
``bincount`` accumulates row by row and loses ~1e-13 over a million rows,
which is more than the program loses); with ``lower`` every column, product
and sum is float32, the control.
"""

import datetime

import numpy as np

from benchmark.refs import Ref

_EPOCH = datetime.date(1970, 1, 1)
Q1_SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
Q1_COLUMNS = ("l_returnflag", "l_linestatus", *Q1_SUMS, "avg_qty",
              "avg_price", "avg_disc", "count_order")
VALUE_COLUMNS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def _lineitem(ctx: dict, lower: bool) -> dict:
    """lineitem's columns as numpy, whole and split by (returnflag,
    linestatus) group; made once per precision."""
    key = "lineitem.f32" if lower else "lineitem.f64"
    if key not in ctx:
        t = ctx["tables"]["lineitem"]
        dtype = np.float32 if lower else np.float64
        cols = {c: t.column(c).to_numpy().astype(dtype, copy=False)
                for c in VALUE_COLUMNS}
        cols["l_shipdate"] = t.column("l_shipdate").cast("int32").to_numpy()
        flag = t.column("l_returnflag").to_numpy(zero_copy_only=False)
        status = t.column("l_linestatus").to_numpy(zero_copy_only=False)
        groups = {}
        for f in sorted(set(flag.tolist())):
            for s in sorted(set(status.tolist())):
                rows = np.flatnonzero((flag == f) & (status == s))
                if len(rows):
                    groups[(f, s)] = {c: v[rows] for c, v in cols.items()}
        ctx[key] = {"all": cols, "groups": groups, "dtype": dtype}
    return ctx[key]


def _q1_answer(ctx: dict, params: dict, lower: bool = False):
    li = _lineitem(ctx, lower)
    one = li["dtype"](1)
    cutoff = _days(params["cutoff"])
    rows = []
    for (f, s), g in li["groups"].items():
        m = g["l_shipdate"] <= cutoff
        n = int(m.sum())
        if not n:
            continue
        qty, price = g["l_quantity"][m], g["l_extendedprice"][m]
        disc, tax = g["l_discount"][m], g["l_tax"][m]
        disc_price = price * (one - disc)
        sums = [qty.sum(), price.sum(), disc_price.sum(),
                (disc_price * (one + tax)).sum()]
        avgs = [sums[0] / n, sums[1] / n, disc.sum() / n]
        rows.append((f, s, *map(float, sums), *map(float, avgs), n))
    return Q1_COLUMNS, rows


def _rel(got, want: float) -> float:
    return abs(float(got) - want) / abs(want)


def _q1_gaps(columns, rows, want) -> dict:
    """Worst relative gap of the seven float aggregates, and how many group
    keys, counts or rows differ (exact)."""
    want_cols, want_rows = want
    mismatch = abs(len(rows) - len(want_rows))
    gap = 0.0
    if tuple(columns) != tuple(want_cols):
        return {"q1_rel_gap": float("inf"), "q1_mismatch": 1 + mismatch}
    for r, w in zip(rows, want_rows):
        if (r[0], r[1]) != (w[0], w[1]) or int(r[-1]) != w[-1]:
            mismatch += 1
            continue
        gap = max(gap, *(_rel(a, b) for a, b in zip(r[2:-1], w[2:-1])))
    return {"q1_rel_gap": gap, "q1_mismatch": mismatch}


def _q6_answer(ctx: dict, params: dict, lower: bool = False):
    li = _lineitem(ctx, lower)
    c, dtype = li["all"], li["dtype"]
    # the literals as the SQL text carries them: two decimals
    lo, hi = dtype(params["disc_lo"]), dtype(params["disc_hi"])
    m = ((c["l_shipdate"] >= _days(f"{params['year']}-01-01"))
         & (c["l_shipdate"] < _days(f"{params['next_year']}-01-01"))
         & (c["l_discount"] >= lo) & (c["l_discount"] <= hi)
         & (c["l_quantity"] < params["quantity"]))
    return ("revenue",), [(float((c["l_extendedprice"][m]
                                  * c["l_discount"][m]).sum()),)]


def _q6_gaps(columns, rows, want) -> dict:
    want_rows = want[1]
    if len(rows) != 1 or len(rows[0]) != 1 or rows[0][0] is None:
        return {"q6_rel_gap": float("inf"), "q6_mismatch": 1}
    return {"q6_rel_gap": _rel(rows[0][0], want_rows[0][0]),
            "q6_mismatch": 0}


q1 = Ref(_q1_answer, _q1_gaps)
q6 = Ref(_q6_answer, _q6_gaps)
