"""TPC-H Q18 (clause 2.4.18) over the generated tables, in numpy.

``SUM(l_quantity)`` per ``l_orderkey`` over all of ``lineitem``; the orders
whose sum is over QUANTITY, each with its customer by ``o_custkey``; one row
``(c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum)`` an
order (an order key decides the other four, so the statement's five-key
GROUP BY has the orders as its groups), ordered by ``o_totalprice``
descending then ``o_orderdate``, the first 100.  At SF1 with TPC-H's
QUANTITY (312-315) a handful of orders qualify, and none may: the answer is
then no rows.

The configuration states DOUBLE (IEEE float64): the quantities are added
line by line in the table's order (an order has at most seven lines) and
``o_totalprice`` is the column as loaded; with ``lower`` both are float32,
the control.  The per-order sums are made once per precision and kept in
``ctx``: a run asks for four values of QUANTITY.
"""

import datetime

import numpy as np

from benchmark.refs import Ref

_EPOCH = datetime.date(1970, 1, 1)
# the sixth is the sum, which the statement does not name: a server captions
# it as it likes, so only the first five names are compared
Q18_COLUMNS = ("c_name", "c_custkey", "o_orderkey", "o_orderdate",
               "o_totalprice", "sum(l_quantity)")
LIMIT = 100


def _np(table, column: str) -> np.ndarray:
    return table.column(column).to_numpy(zero_copy_only=False)


def _orders(ctx: dict, lower: bool) -> dict:
    """``orders`` as numpy with every order's customer row and its
    ``SUM(l_quantity)``; made once per precision."""
    key = "q18.f32" if lower else "q18.f64"
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
        l_order = l_order[by_order]
        first = np.flatnonzero(np.r_[True, l_order[1:] != l_order[:-1]])
        qty = np.zeros(len(o_key), dtype)
        qty[l_order[first]] = np.add.reduceat(
            _np(li, "l_quantity").astype(dtype)[by_order], first)
        ctx[key] = {
            "dtype": dtype,
            "c_name": _np(cust, "c_name"),
            "c_key": c_key,
            "o_cust": o_cust,
            "o_key": o_key,
            "o_date": orders.column("o_orderdate").cast("int32").to_numpy(),
            "o_total": _np(orders, "o_totalprice").astype(dtype),
            "o_qty": qty,
        }
    return ctx[key]


def _answer(ctx: dict, params: dict, lower: bool = False):
    j = _orders(ctx, lower)
    big = np.flatnonzero(j["o_qty"] > j["dtype"](params["quantity"]))
    # total price descending, then order date, then key: the last only to
    # make the reference's own order of ties a fixed one
    top = big[np.lexsort((j["o_key"][big], j["o_date"][big],
                          -j["o_total"][big]))[:LIMIT]]
    return Q18_COLUMNS, [
        (str(j["c_name"][j["o_cust"][o]]), int(j["c_key"][j["o_cust"][o]]),
         int(j["o_key"][o]),
         (_EPOCH + datetime.timedelta(days=int(j["o_date"][o]))).isoformat(),
         float(j["o_total"][o]), float(j["o_qty"][o]))
        for o in top.tolist()]


def _rel(got, want: float) -> float:
    return abs(float(got) - want) / abs(want) if want else abs(float(got))


def _gaps(columns, rows, want) -> dict:
    """Worst relative gap of ``o_totalprice`` and the sum over the rows, and
    how many rows differ in name, customer key, order key, date or place
    (or are missing or too many).  Rows whose total price and date tie in
    the reference may stand in any order: each is looked up among the
    reference's rows of its tie.  No rows against no rows is no gap."""
    want_cols, want_rows = want
    mismatch = abs(len(rows) - len(want_rows))
    if tuple(columns[:5]) != tuple(want_cols[:5]) \
            or len(columns) != len(want_cols):
        return {"q18_rel_gap": float("inf"), "q18_mismatch": 1 + mismatch}
    gap = 0.0
    for r, w in zip(rows, want_rows):
        got = (str(r[0]), int(r[1]), int(r[2]), str(r[3]))
        match = [x for x in want_rows
                 if (x[4], x[3]) == (w[4], w[3]) and x[:4] == got]
        if not match or r[4] is None or r[5] is None:
            mismatch += 1
            continue
        gap = max(gap, _rel(r[4], match[0][4]), _rel(r[5], match[0][5]))
    return {"q18_rel_gap": gap, "q18_mismatch": mismatch}


q18 = Ref(_answer, _gaps)
