"""The north-star filter + GROUP BY over the generated table ``t``, in numpy.

``SELECT <key>, COUNT(*), SUM(v), AVG(v), MIN(v) FROM t WHERE v*2+1 > x
GROUP BY <key>`` for one of the three key columns.  The filter is taken as
the engine states it evaluates it, over ``v`` widened to float64, in
cache-sized pieces (whole-array temporaries of 100 M rows cost seconds
each).  The configuration states that SUM and AVG of the FLOAT column
accumulate in DOUBLE: every piece's per-group sums are a float64
``bincount`` (float32 addends keep all their bits in a float64 running sum
of this size, so row-by-row accumulation loses ~1e-15 here) and the pieces
add up in float64.  With ``lower`` every piece's sums are rounded to
float32 and add up in float32: the least a float32 accumulation can lose,
so a limit that fails it fails any.  COUNT is exact and MIN a pick, in
both.

A window holds a few hundred answers and 12 distinct statements (three
keys, four values of x), so answers are kept in ``ctx`` by (key, x,
precision): the check after the window costs 12 passes whatever the rate.
"""

import numpy as np

from benchmark.refs import Ref

PIECE = 1 << 22
COLUMNS = ("n", "s", "a", "mn")


def _compute(table, key: str, x: float, lower: bool) -> dict:
    """-> {key value: (count, sum, avg, min)} over the rows that pass."""
    keys = table.column(key).to_numpy()
    v = table.column("v").to_numpy()
    ng = int(keys.max()) + 1 if len(keys) else 0
    acc = np.float32 if lower else np.float64
    count = np.zeros(ng, np.int64)
    total = np.zeros(ng, acc)
    least = np.full(ng, np.inf, np.float32)
    for i in range(0, len(v), PIECE):
        piece = v[i:i + PIECE]
        keep = piece.astype(np.float64) * 2 + 1 > x
        k, p = keys[i:i + PIECE][keep], piece[keep]
        count += np.bincount(k, minlength=ng)
        total += np.bincount(k, weights=p, minlength=ng).astype(acc)
        np.minimum.at(least, k, p)
    return {g: (int(count[g]), float(total[g]),
                float(total[g] / acc(count[g])), float(least[g]))
            for g in np.flatnonzero(count).tolist()}


def _ref(name: str, key: str) -> Ref:
    gap_name = f"{name}_rel_gap"

    def answer(ctx: dict, params: dict, lower: bool = False):
        kept = ctx.setdefault("northstar", {})
        at = (key, params["x"], lower)
        if at not in kept:
            kept[at] = _compute(ctx["tables"]["t"], key,
                                float(params["x"]), lower)
        groups = kept[at]
        return (key, *COLUMNS), [(g, *groups[g]) for g in sorted(groups)]

    def gaps(columns, rows, want) -> dict:
        """Worst relative gap of SUM and AVG over the groups, and how many
        rows differ in key, count or MIN, are missing, too many or twice
        (rows are looked up by key: the statement states no order)."""
        want_cols, want_rows = want
        if tuple(columns) != tuple(want_cols):
            return {gap_name: float("inf"),
                    "groupby_mismatch": 1 + len(want_rows)}
        by_key = {w[0]: w for w in want_rows}
        mismatch = abs(len(rows) - len(want_rows))
        gap = 0.0
        seen = set()
        for r in rows:
            w = by_key.get(int(r[0])) if r[0] is not None else None
            if w is None or w[0] in seen or None in r[1:] \
                    or int(r[1]) != w[1] \
                    or np.float32(float(r[4])) != np.float32(w[4]):
                mismatch += 1
                continue
            seen.add(w[0])
            gap = max(gap, _rel(r[2], w[2]), _rel(r[3], w[3]))
        return {gap_name: gap, "groupby_mismatch": mismatch}

    return Ref(answer, gaps)


def _rel(got, want: float) -> float:
    return abs(float(got) - want) / abs(want) if want else abs(float(got))


g16 = _ref("g16", "g")
g1000 = _ref("g1000", "g1000")
g4000 = _ref("g4000", "g4000")
