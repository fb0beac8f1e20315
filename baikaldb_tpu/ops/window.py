"""Window function kernels (reference: src/expr/window_fn_call.cpp — rank /
row_number / ntile / lead / lag / aggregates; src/exec/window_node.cpp runs
them over sorted partitions).

TPU re-design: one stable multi-key sort puts rows in (partition, order)
order; every window function is then O(n) vectorized prefix math —
``cumsum`` + segment-start gathers — and results scatter back to the original
row order through the inverse permutation.  No per-partition loops: a million
tiny partitions cost the same as one big one.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp

from ..column.batch import Column, ColumnBatch
from ..types import LType
from .segments import seg_max, seg_min, seg_sum
from .sort import SortKey


@dataclass(frozen=True)
class WinSpec:
    op: str                      # row_number | rank | dense_rank | ntile |
    #                              lead | lag | first_value | last_value |
    #                              sum | count | avg | min | max (partition or
    #                              running)
    input: Optional[str] = None
    out_name: str = ""
    offset: int = 1              # lead/lag
    default: Optional[float] = None
    n: int = 1                   # ntile buckets
    running: bool = False        # ROWS UNBOUNDED PRECEDING .. CURRENT ROW
    # explicit frame (reference: window frame specs of window_fn_call.cpp):
    # ("rows"|"range", lo_bound, hi_bound); bounds as in expr/ast.WindowCall.
    # Executed as O(n log n) prefix/sparse-table math — no per-row loops.
    frame: Optional[tuple] = None


def window_compute(batch: ColumnBatch, partition_names: list[str],
                   order_keys: list[SortKey], specs: list[WinSpec]) -> ColumnBatch:
    """Append window-function columns (aligned to the batch's row order)."""
    n = len(batch)
    sel = batch.sel_mask()

    # ---- sort rows: partition keys (primary) then order keys; dead rows
    # last — one stable multi-key sort, shared with ORDER BY (ops/sort.py)
    from .sort import sort_permutation

    perm = sort_permutation(batch, [SortKey(p, True) for p in partition_names]
                            + list(order_keys))
    pkey_data = []
    for pn in partition_names:
        c = batch.column(pn)
        d = c.data
        if d.dtype == jnp.bool_:
            d = d.astype(jnp.int32)
        if c.validity is not None:
            d = jnp.where(c.validity, d, jnp.zeros((), d.dtype))
        pkey_data.append((c, d))

    inv = jnp.zeros(n, perm.dtype).at[perm].set(
        jnp.arange(n, dtype=perm.dtype))
    sel_s = sel[perm]
    idx = jnp.arange(n)

    # partition boundaries (NULL keys canonicalized above)
    flags = idx == 0
    for c, d in pkey_data:
        ds = d[perm]
        flags = flags | (ds != jnp.roll(ds, 1))
        if c.validity is not None:
            v = c.validity[perm]
            flags = flags | (v != jnp.roll(v, 1))
    flags = flags | (sel_s != jnp.roll(sel_s, 1))

    # order-key tie boundaries (for rank/dense_rank)
    tie = flags
    for k in order_keys:
        c = batch.column(k.name)
        ds = c.data[perm]
        tie = tie | (ds != jnp.roll(ds, 1))
        if c.validity is not None:
            v = c.validity[perm]
            tie = tie | (v != jnp.roll(v, 1))

    start_idx = jnp.maximum.accumulate(jnp.where(flags, idx, 0))
    row_number = idx - start_idx + 1
    sid = jnp.cumsum(flags.astype(jnp.int32)) - 1
    nseg = n + 1
    import jax

    seg_size = seg_sum(sel_s.astype(jnp.int64),
                       jnp.where(sel_s, sid, n),
                       num_segments=nseg)[:n]
    size_here = jnp.take(seg_size, jnp.clip(sid, 0, n - 1))
    end_idx = start_idx + jnp.maximum(size_here, 1) - 1

    names = list(batch.names)
    cols = list(batch.columns)
    fctx = None
    if any(s.frame for s in specs):
        # tie (peer) group bounds, shared by RANGE CURRENT ROW bounds
        tstart = jnp.maximum.accumulate(jnp.where(tie, idx, 0))
        tid = jnp.cumsum(tie.astype(jnp.int32)) - 1
        tsize = seg_sum(sel_s.astype(jnp.int64),
                        jnp.where(sel_s, tid, n), num_segments=nseg)[:n]
        tsize_here = jnp.take(tsize, jnp.clip(tid, 0, n - 1))
        tend = tstart + jnp.maximum(tsize_here, 1) - 1
        fctx = {"tstart": tstart, "tend": tend, "sid": sid,
                "start": start_idx, "end": end_idx, "idx": idx,
                "sel_s": sel_s, "nseg": nseg, "order_keys": order_keys,
                "perm": perm}
    for s in specs:
        if s.frame is not None:
            res = _one_framed(s, batch, fctx)
        else:
            res = _one(s, batch, perm, idx, sel_s, flags, tie, sid,
                       start_idx, end_idx, row_number, size_here, nseg)
        if len(res) == 4:
            out_sorted, validity_sorted, lt, dct = res
        else:
            out_sorted, validity_sorted, lt = res
            dct = None
            if s.input is not None and lt is LType.STRING:
                dct = batch.column(s.input).dictionary
        data = jnp.take(out_sorted, inv)
        validity = None if validity_sorted is None else jnp.take(validity_sorted, inv)
        names.append(s.out_name)
        cols.append(Column(data, validity, lt, dct))
    return ColumnBatch(tuple(names), cols, batch.sel, batch.num_rows)


def _one(s: WinSpec, batch, perm, idx, sel_s, flags, tie, sid, start_idx,
         end_idx, row_number, size_here, nseg):
    import jax

    n = idx.shape[0]
    if s.op == "row_number":
        return row_number.astype(jnp.int64), None, LType.INT64
    if s.op == "rank":
        tstart = jnp.maximum.accumulate(jnp.where(tie, idx, 0))
        return (tstart - start_idx + 1).astype(jnp.int64), None, LType.INT64
    if s.op == "dense_rank":
        c = jnp.cumsum(tie.astype(jnp.int64))
        c_start = jnp.take(c, start_idx)
        return c - c_start + 1, None, LType.INT64
    if s.op == "ntile":
        t = ((row_number - 1) * s.n) // jnp.maximum(size_here, 1) + 1
        return t.astype(jnp.int64), None, LType.INT64
    if s.op == "count" and s.input is None:
        # COUNT(*) OVER: all live rows count
        if s.running:
            return row_number.astype(jnp.int64), None, LType.INT64
        return size_here.astype(jnp.int64), None, LType.INT64

    c = batch.column(s.input)
    x = c.data[perm]
    xv = (c.valid_mask()[perm]) & sel_s

    if s.op in ("lead", "lag"):
        off = s.offset if s.op == "lead" else -s.offset
        src = idx + off
        in_range = (src >= 0) & (src < n)
        src_c = jnp.clip(src, 0, n - 1)
        same = jnp.take(sid, src_c) == sid
        ok = in_range & same & sel_s
        data = jnp.take(x, src_c)
        validity = jnp.take(xv, src_c) & ok
        if s.default is not None:
            if c.ltype is LType.STRING:
                if not isinstance(s.default, str):
                    raise ValueError("lead/lag default on a string column "
                                     "must be a string")
                # default becomes a code in an extended dictionary
                import numpy as np
                from ..column.dictionary import Dictionary
                values = np.union1d(c.dictionary.values,
                                    np.asarray([s.default], dtype=str))
                remap = jnp.asarray(np.searchsorted(values, c.dictionary.values)
                                    .astype(np.int32))
                data = jnp.where(data >= 0,
                                 jnp.take(remap, jnp.clip(data, 0, None),
                                          mode="clip"), data)
                dcode = int(np.searchsorted(values, s.default))
                data = jnp.where(ok, data, jnp.int32(dcode))
                validity = jnp.where(ok, validity, True)
                return data, validity, c.ltype, Dictionary(values)
            if isinstance(s.default, str):
                raise ValueError("string default on a non-string column")
            if isinstance(s.default, float) and not float(s.default).is_integer() \
                    and x.dtype.kind in "iu":
                # float default on int column: widen output to f64
                data = data.astype(jnp.float64)
                data = jnp.where(ok, data, jnp.float64(s.default))
                validity = jnp.where(ok, validity, True)
                return data, validity, LType.FLOAT64, None
            data = jnp.where(ok, data, jnp.asarray(s.default, x.dtype))
            validity = jnp.where(ok, validity, True)
        return data, validity, c.ltype
    if s.op == "first_value":
        return jnp.take(x, start_idx), jnp.take(xv, start_idx), c.ltype
    if s.op == "last_value":
        if s.running:
            # default ordered frame (UNBOUNDED PRECEDING..CURRENT ROW):
            # LAST_VALUE is the current row's value
            return x, xv, c.ltype
        return jnp.take(x, end_idx), jnp.take(xv, end_idx), c.ltype

    # aggregates (partition-wide or running)
    dt = jnp.int64 if c.ltype.is_integer else jnp.float64
    xa = jnp.where(xv, x.astype(dt), 0)
    ones = xv.astype(jnp.int64)
    if s.running:
        cs = jnp.cumsum(xa)
        cs0 = cs - xa
        run_sum = cs - jnp.take(cs0, start_idx)
        cn = jnp.cumsum(ones)
        run_cnt = cn - jnp.take(cn - ones, start_idx)
        if s.op == "sum":
            return run_sum, run_cnt > 0, LType.INT64 if dt == jnp.int64 else LType.FLOAT64
        if s.op == "count":
            return run_cnt, None, LType.INT64
        if s.op == "avg":
            return (run_sum.astype(jnp.float64) /
                    jnp.maximum(run_cnt, 1)), run_cnt > 0, LType.FLOAT64
        if s.op in ("min", "max"):
            # segmented running min/max: associative scan that resets at
            # partition boundaries (carries (segment id, running extreme))
            big = (jnp.iinfo if x.dtype.kind in "iu" else jnp.finfo)(x.dtype)
            ident = big.max if s.op == "min" else big.min
            xm = jnp.where(xv, x, ident)
            import jax.lax as lax

            def combine(a, b):
                asid, aval = a
                bsid, bval = b
                take_b = bsid != asid
                val = jnp.where(take_b, bval,
                                jnp.minimum(aval, bval) if s.op == "min"
                                else jnp.maximum(aval, bval))
                return (bsid, val)

            _, vals = lax.associative_scan(combine, (sid, xm))
            return vals, run_cnt > 0, c.ltype
        raise ValueError(f"unsupported running window aggregate {s.op}")
    # partition-wide
    gid = jnp.where(sel_s, sid, n)
    if s.op == "count":
        t = seg_sum(ones, gid, num_segments=nseg)[:n]
        return jnp.take(t, jnp.clip(sid, 0, n - 1)), None, LType.INT64
    if s.op == "sum":
        t = seg_sum(xa, gid, num_segments=nseg)[:n]
        tc = seg_sum(ones, gid, num_segments=nseg)[:n]
        sd = jnp.take(t, jnp.clip(sid, 0, n - 1))
        vc = jnp.take(tc, jnp.clip(sid, 0, n - 1)) > 0
        return sd, vc, LType.INT64 if dt == jnp.int64 else LType.FLOAT64
    if s.op == "avg":
        t = seg_sum(xa.astype(jnp.float64), gid, num_segments=nseg)[:n]
        tc = seg_sum(ones, gid, num_segments=nseg)[:n]
        sd = jnp.take(t, jnp.clip(sid, 0, n - 1))
        cd = jnp.take(tc, jnp.clip(sid, 0, n - 1))
        return sd / jnp.maximum(cd, 1), cd > 0, LType.FLOAT64
    if s.op in ("min", "max"):
        big = (jnp.iinfo if x.dtype.kind in "iu" else jnp.finfo)(x.dtype)
        ident = big.max if s.op == "min" else big.min
        xm = jnp.where(xv, x, ident)
        f = seg_min if s.op == "min" else seg_max
        t = f(xm, gid, num_segments=nseg)[:n]
        tc = seg_sum(ones, gid, num_segments=nseg)[:n]
        sd = jnp.take(t, jnp.clip(sid, 0, n - 1))
        vc = jnp.take(tc, jnp.clip(sid, 0, n - 1)) > 0
        return sd, vc, c.ltype
    raise ValueError(f"unsupported window op {s.op}")


def _first_true(a, b, pred_at, n: int):
    """Vectorized monotone binary search: per row, the smallest j in
    [a, b+1) with pred_at(j) True (b+1 when none).  pred must be monotone
    (False..False True..True) over each row's range — the frame-bound
    invariant over (partition, order)-sorted values."""
    lo, hi = a, b + 1
    for _ in range(max(n, 2).bit_length() + 1):
        cont = lo < hi
        mid = (lo + hi) >> 1
        p = pred_at(jnp.clip(mid, 0, n - 1))
        hi = jnp.where(cont & p, mid, hi)
        lo = jnp.where(cont & ~p, mid + 1, lo)
    return lo


def _sparse_table(xm, combine, n: int):
    """Doubling (sparse) table for O(1) range min/max queries: level k
    holds combine over [i, i+2^k) (clamped).  n log n memory, built with
    static shapes at trace time."""
    levels = [xm]
    shift = 1
    while shift < n:
        prev = levels[-1]
        nxt = jnp.concatenate([combine(prev[:n - shift], prev[shift:]),
                               prev[n - shift:]])
        levels.append(nxt)
        shift *= 2
    return jnp.stack(levels)              # (K+1, n)


def _range_query(table, combine_take, lo, hi, n: int):
    """combine over [lo, hi] via two overlapping power-of-two blocks."""
    length = jnp.maximum(hi - lo + 1, 1)
    k = jnp.log2(length.astype(jnp.float64)).astype(jnp.int32)
    k = jnp.clip(k, 0, table.shape[0] - 1)
    flat = table.reshape(-1)
    left = jnp.take(flat, k * n + jnp.clip(lo, 0, n - 1))
    right_pos = hi - (1 << k.astype(jnp.int64)) + 1
    right = jnp.take(flat, k * n + jnp.clip(right_pos, 0, n - 1))
    return combine_take(left, right)


def _one_framed(s: WinSpec, batch, fctx):
    """Aggregates / first_value / last_value over an explicit ROWS or
    RANGE frame (reference: src/exec/window_node.cpp frame execution).
    Per-row frame bounds [lo, hi] come from clamped index arithmetic
    (ROWS) or vectorized binary search over the single order key (RANGE
    n PRECEDING/FOLLOWING); aggregation is prefix-sum differences, with a
    sparse table for min/max — no per-partition loops."""
    idx = fctx["idx"]
    n = idx.shape[0]
    start_idx, end_idx = fctx["start"], fctx["end"]
    tstart, tend = fctx["tstart"], fctx["tend"]
    sid, sel_s, nseg = fctx["sid"], fctx["sel_s"], fctx["nseg"]
    perm = fctx["perm"]
    unit, lo_b, hi_b = s.frame

    def rows_bound(b, is_lo):
        if b == ("up",):
            return start_idx
        if b == ("uf",):
            return end_idx
        if b == ("c",):
            return idx
        off = int(b[1])
        return idx - off if b[0] == "p" else idx + off

    def range_bound(b, is_lo):
        if b == ("up",):
            return start_idx
        if b == ("uf",):
            return end_idx
        if b == ("c",):
            # RANGE CURRENT ROW means the current row's PEER group
            return tstart if is_lo else tend
        # n PRECEDING / n FOLLOWING over the single numeric order key
        ks = fctx["order_keys"]
        if len(ks) != 1:
            raise ValueError("RANGE n PRECEDING/FOLLOWING needs exactly "
                             "one ORDER BY key")
        oc = batch.column(ks[0].name)
        if oc.ltype is LType.STRING:
            raise ValueError("RANGE frames need a numeric or temporal "
                             "ORDER BY key")
        asc = ks[0].asc
        ov = oc.data[perm]
        ovalid = oc.valid_mask()[perm] & sel_s
        delta = b[1]
        dt = jnp.float64 if (ov.dtype.kind == "f"
                             or isinstance(delta, float)) else jnp.int64
        sv = ov.astype(dt)
        sv = sv if asc else -sv               # ascending in sort order
        # the order key's non-NULL run inside each partition (NULL rows
        # are peers of each other only; their frame is their peer group)
        first_valid = jnp.take(
            seg_min(jnp.where(ovalid, idx, n),
                    jnp.where(sel_s, sid, n), num_segments=nseg)[:n],
            jnp.clip(sid, 0, n - 1))
        last_valid = jnp.take(
            seg_max(jnp.where(ovalid, idx, -1),
                    jnp.where(sel_s, sid, n), num_segments=nseg)[:n],
            jnp.clip(sid, 0, n - 1))
        # target in ascending sv space: PRECEDING = -delta, FOLLOWING = +d;
        # the search DIRECTION comes from which end of the frame this
        # bound is — lo wants the first index >= target, hi the last
        # index <= target (they differ for p-as-hi / f-as-lo frames)
        d = jnp.asarray(delta, dt)
        target = sv - d if b[0] == "p" else sv + d
        if is_lo:
            pos = _first_true(first_valid, last_valid,
                              lambda j: jnp.take(sv, j) >= target, n)
        else:
            pos = _first_true(first_valid, last_valid,
                              lambda j: jnp.take(sv, j) > target, n) - 1
        # NULL-ordered rows: peer-group frame
        return jnp.where(ovalid, pos, tstart if is_lo else tend)

    bound = rows_bound if unit == "rows" else range_bound
    lo = jnp.maximum(bound(lo_b, True), start_idx)
    hi = jnp.minimum(bound(hi_b, False), end_idx)
    nonempty = (hi >= lo) & sel_s
    lo_c = jnp.clip(lo, 0, n - 1)
    hi_c = jnp.clip(hi, 0, n - 1)

    if s.op == "count" and s.input is None:
        return (jnp.where(nonempty, hi - lo + 1, 0).astype(jnp.int64),
                None, LType.INT64)

    c = batch.column(s.input)
    x = c.data[perm]
    xv = (c.valid_mask()[perm]) & sel_s

    if s.op == "first_value":
        return (jnp.take(x, lo_c), jnp.take(xv, lo_c) & nonempty, c.ltype)
    if s.op == "last_value":
        return (jnp.take(x, hi_c), jnp.take(xv, hi_c) & nonempty, c.ltype)

    dt = jnp.int64 if c.ltype.is_integer else jnp.float64
    xa = jnp.where(xv, x.astype(dt), 0)
    ones = xv.astype(jnp.int64)
    cs = jnp.cumsum(xa)
    cn = jnp.cumsum(ones)

    def span(prefix):
        head = jnp.take(prefix, hi_c)
        tail = jnp.where(lo > 0, jnp.take(prefix, jnp.clip(lo - 1, 0, n - 1)),
                         jnp.zeros((), prefix.dtype))
        return jnp.where(nonempty, head - tail, 0)

    cnt = span(cn)
    if s.op == "count":
        return cnt, None, LType.INT64
    if s.op == "sum":
        return (span(cs), cnt > 0,
                LType.INT64 if dt == jnp.int64 else LType.FLOAT64)
    if s.op == "avg":
        return (span(cs).astype(jnp.float64) / jnp.maximum(cnt, 1),
                cnt > 0, LType.FLOAT64)
    if s.op in ("min", "max"):
        big = (jnp.iinfo if x.dtype.kind in "iu" else jnp.finfo)(x.dtype)
        ident = big.max if s.op == "min" else big.min
        xm = jnp.where(xv, x, ident)
        comb = jnp.minimum if s.op == "min" else jnp.maximum
        # frames anchored at a partition edge (the default frame shape)
        # use an O(n) segmented scan + gather; the n-log-n sparse table is
        # only built when BOTH bounds slide
        if lo_b == ("up",):
            vals = jnp.take(_seg_running(xm, sid, comb), hi_c)
        elif hi_b == ("uf",):
            vals = jnp.take(
                _seg_running(xm[::-1], sid[::-1], comb)[::-1], lo_c)
        else:
            table = _sparse_table(xm, comb, n)
            vals = _range_query(table, comb, lo_c, hi_c, n)
        return vals, (cnt > 0) & nonempty, c.ltype
    raise ValueError(f"unsupported framed window op {s.op}")


def _seg_running(xm, sid, comb):
    """Running min/max from each segment's start: associative scan that
    resets at segment boundaries (same shape as the running path in
    _one)."""
    import jax.lax as lax

    def combine(a, b):
        asid, aval = a
        bsid, bval = b
        return (bsid, jnp.where(bsid != asid, bval, comb(aval, bval)))

    _, vals = lax.associative_scan(combine, (sid, xm))
    return vals
