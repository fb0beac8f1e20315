"""TPC-H Q3 (clause 2.4.3) over the generated tables, in numpy.

``customer`` filtered by market segment, semi-joined to ``orders`` on
``o_custkey`` with ``o_orderdate < DATE``, joined to ``lineitem`` on
``l_orderkey`` with ``l_shipdate > DATE``; ``SUM(l_extendedprice * (1 -
l_discount))`` per ``(l_orderkey, o_orderdate, o_shippriority)``, ordered by
revenue descending then order date, the first 10.  An order key decides its
date and priority, so the groups are the orders.

The configuration states DOUBLE (IEEE float64): products and sums are
float64, added line by line in the table's order (an order has at most seven
lines); with ``lower`` every column, product and sum is float32, the control.
The traffic generator draws no string, so each market segment has a ``Ref``
of its own, all made by ``_q3`` and all reporting the same two numbers.
"""

import datetime

import numpy as np

from benchmark.refs import Ref

_EPOCH = datetime.date(1970, 1, 1)
Q3_COLUMNS = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
LIMIT = 10


def _days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def _np(table, column: str) -> np.ndarray:
    return table.column(column).to_numpy(zero_copy_only=False)


def _joined(ctx: dict, lower: bool) -> dict:
    """The three tables as numpy with the two key joins resolved once per
    precision: every order's customer row, and every line's order row, the
    lines sorted by that row so that one order's lines are adjacent."""
    key = "q3.f32" if lower else "q3.f64"
    if key not in ctx:
        t = ctx["tables"]
        dtype = np.float32 if lower else np.float64
        cust, orders, li = t["customer"], t["orders"], t["lineitem"]
        c_key = _np(cust, "c_custkey")
        by_ckey = np.argsort(c_key, kind="stable")
        o_cust = by_ckey[np.searchsorted(c_key[by_ckey],
                                         _np(orders, "o_custkey"))]
        o_key = _np(orders, "o_orderkey")
        by_okey = np.argsort(o_key, kind="stable")
        l_order = by_okey[np.searchsorted(o_key[by_okey],
                                          _np(li, "l_orderkey"))]
        by_order = np.argsort(l_order, kind="stable")
        ctx[key] = {
            "dtype": dtype,
            "c_segment": _np(cust, "c_mktsegment"),
            "o_cust": o_cust,
            "o_key": o_key,
            "o_date": orders.column("o_orderdate").cast("int32").to_numpy(),
            "o_prio": _np(orders, "o_shippriority"),
            "l_order": l_order[by_order],
            "l_ship": li.column("l_shipdate").cast("int32")
                        .to_numpy()[by_order],
            "l_price": _np(li, "l_extendedprice").astype(dtype)[by_order],
            "l_disc": _np(li, "l_discount").astype(dtype)[by_order],
        }
    return ctx[key]


def _q3(segment: str) -> Ref:
    def answer(ctx: dict, params: dict, lower: bool = False):
        j = _joined(ctx, lower)
        day = _days(params["date"])
        order_in = (j["o_date"] < day) \
            & (j["c_segment"] == segment)[j["o_cust"]]
        rows = np.flatnonzero(order_in[j["l_order"]] & (j["l_ship"] > day))
        if not len(rows):
            return Q3_COLUMNS, []
        order = j["l_order"][rows]
        first = np.flatnonzero(np.r_[True, order[1:] != order[:-1]])
        revenue = np.add.reduceat(
            j["l_price"][rows] * (j["dtype"](1) - j["l_disc"][rows]), first)
        order = order[first]
        # revenue descending, then order date, then key: the last only to
        # make the reference's own order of ties a fixed one
        top = np.lexsort((j["o_key"][order], j["o_date"][order],
                          -revenue))[:LIMIT]
        return Q3_COLUMNS, [
            (int(j["o_key"][o]), float(r),
             (_EPOCH + datetime.timedelta(days=int(j["o_date"][o])))
             .isoformat(), int(j["o_prio"][o]))
            for o, r in zip(order[top].tolist(), revenue[top].tolist())]
    return Ref(answer, _q3_gaps)


def _q3_gaps(columns, rows, want) -> dict:
    """Worst relative gap of ``revenue`` over the rows, and how many rows
    differ in key, date, priority or place (or are missing or too many).
    Rows whose revenue and date tie in the reference may stand in any
    order: each is looked up among the reference's rows of its tie."""
    want_cols, want_rows = want
    mismatch = abs(len(rows) - len(want_rows))
    if tuple(columns) != tuple(want_cols):
        return {"q3_rel_gap": float("inf"), "q3_mismatch": 1 + mismatch}
    gap = 0.0
    for r, w in zip(rows, want_rows):
        got = (int(r[0]), str(r[2]), int(r[3]))
        tie = [x for x in want_rows if (x[1], x[2]) == (w[1], w[2])]
        match = [x for x in tie if (x[0], x[2], x[3]) == got]
        if not match or r[1] is None:
            mismatch += 1
            continue
        gap = max(gap, abs(float(r[1]) - match[0][1]) / abs(match[0][1]))
    return {"q3_rel_gap": gap, "q3_mismatch": mismatch}


q3_automobile = _q3("AUTOMOBILE")
q3_building = _q3("BUILDING")
q3_furniture = _q3("FURNITURE")
q3_machinery = _q3("MACHINERY")
q3_household = _q3("HOUSEHOLD")
