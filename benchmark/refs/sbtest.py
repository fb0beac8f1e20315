"""sysbench's read-only statements over the generated ``sbtest1``.

Every answer is exact: the strings as loaded (sorted, and made distinct,
where the statement says so), and a BIGINT sum.  A range that runs past the
table's end holds the rows there are, as sysbench sends it.  ``lower``
(the control) sums ``k`` in float32, the nearest precision below the BIGINT
the configuration states that still holds one ``k``; the strings have no
lower precision and stay as they are.
"""

import numpy as np

from benchmark.refs import Ref


def _table(ctx: dict) -> dict:
    if "sbtest1" not in ctx:
        t = ctx["tables"]["sbtest1"]
        ctx["sbtest1"] = {"c": t.column("c").to_numpy(zero_copy_only=False),
                          "k": t.column("k").to_numpy()}
    return ctx["sbtest1"]


def _point_answer(ctx, params, lower=False):
    return ("c",), [(_table(ctx)["c"][params["id"] - 1],)]


def _range_answer(ctx, params, lower=False):
    c = _table(ctx)["c"][params["lo"] - 1:params["hi"]]
    return ("c",), [(v,) for v in c]


def _sorted_answer(distinct: bool):
    def answer(ctx, params, lower=False):
        c = _table(ctx)["c"][params["lo"] - 1:params["hi"]].tolist()
        return ("c",), [(v,) for v in sorted(set(c) if distinct else c)]
    return answer


def _sum_answer(ctx, params, lower=False):
    k = _table(ctx)["k"][params["lo"] - 1:params["hi"]]
    total = int(k.astype(np.float32).sum(dtype=np.float32)) if lower \
        else int(k.sum())
    return ("SUM(k)",), [(total,)]


def _rows_differ(name: str, ordered: bool):
    def gaps(columns, rows, want) -> dict:
        got = [tuple(map(str, r)) for r in rows]
        exp = [tuple(map(str, r)) for r in want[1]]
        if not ordered:
            got, exp = sorted(got), sorted(exp)
        return {name: int(got != exp)}
    return gaps


point = Ref(_point_answer, _rows_differ("point_mismatch", True))
# sysbench's simple range has no ORDER BY: the rows compare as a multiset
simple_range = Ref(_range_answer, _rows_differ("range_mismatch", False))
sum_range = Ref(_sum_answer, _rows_differ("sum_mismatch", True))
# digits and '-' only: every collation orders them as their bytes do
order_range = Ref(_sorted_answer(False), _rows_differ("order_mismatch", True))
distinct_range = Ref(_sorted_answer(True),
                     _rows_differ("distinct_mismatch", True))
