"""Aggregation kernels: scalar, sort-grouped, and dense-domain group-by.

Reference: src/exec/agg_node.cpp (hash aggregation with partial mode on stores
and MERGE_AGG on the coordinator) + src/expr/agg_fn_call.cpp (the per-function
update/merge protocol).  On TPU a pointer-chasing hash table would serialize
the VPU, so grouping is re-expressed as data-parallel primitives:

- **dense path**: when every group key has a known dense domain (dictionary
  codes are dense by construction; small-range ints are detected by the
  planner), the combined group id is a mixed-radix fold and aggregation is one
  ``segment_sum`` per aggregate — zero sorts, the TPU-optimal plan for
  GROUP BY over categorical keys (the BASELINE.json north-star config #2).
- **sort path**: general fallback — multi-key stable sort, boundary detection,
  ``cumsum`` group ids, then segment reductions into a static ``max_groups``
  table.
- **stream path**: when the planner can show that the live rows arrive in
  non-decreasing order of the one integer / DATE group key (a table stored in
  key order, carried up through filters, shrinks and the probe side of
  unique-build joins), equal keys are adjacent and every aggregate is a
  segmented scan over the lanes as they come: no scatter, no sort, no
  domain-sized buffer; the output keeps the input's lanes.

The dense and sort paths emit *mergeable partials* (SUM/COUNT pairs for AVG etc.), so the
distributed layer can ``psum``/re-reduce them across mesh shards exactly like
the reference merges per-region partial aggregates.
"""

from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..column.batch import Column, ColumnBatch
from . import segments
from .segments import (scan_identity, seg_max, seg_min, seg_scan, seg_sum,
                       shift_lanes)
from .sort import argsort
from ..types import LType


def agg_result_type(op: str, input_type: LType) -> LType:
    if op in ("count", "count_star", "approx_count_distinct"):
        return LType.INT64
    if op == "sum":
        return LType.INT64 if input_type.is_integer else LType.FLOAT64
    if op in ("avg", "sumsq", "stddev", "stddev_samp", "variance", "var_samp",
              "percentile"):
        return LType.FLOAT64
    if op in ("min", "max"):
        return input_type
    raise ValueError(f"unknown aggregate {op}")


# aggregates whose state cannot merge as a single psum/pmin/pmax lane; the
# distribute pass co-locates each group's rows (repartition/gather) instead
ROW_AGGS = {"approx_count_distinct", "percentile"}

# HyperLogLog register count for APPROX_COUNT_DISTINCT (the reference keeps
# 16384-register HLLs in src/common/hll_common.cpp; 512 keeps the dense
# group table small at <2% typical error)
HLL_REGISTERS = 512


@dataclass(frozen=True)
class AggSpec:
    op: str                 # count | count_star | sum | avg | min | max |
    #                         stddev/variance family | approx_count_distinct |
    #                         percentile
    input: Optional[str]    # column name; None for count_star
    out_name: str
    distinct: bool = False
    param: Optional[float] = None   # percentile fraction


def _sum_dtype(c: Column):
    return jnp.int64 if c.ltype.is_integer else jnp.float64


def _minmax_identity(c: Column, is_min: bool):
    info = (jnp.iinfo if c.data.dtype.kind in "iu" else jnp.finfo)(c.data.dtype)
    return info.max if is_min else info.min


# ----------------------------------------------------------------------
# scalar aggregation (no GROUP BY)


def scalar_aggregate(batch: ColumnBatch, specs: list[AggSpec]) -> ColumnBatch:
    sel = batch.sel_mask()
    names, cols = [], []
    for s in specs:
        names.append(s.out_name)
        cols.append(_scalar_one(batch, s, sel))
    return ColumnBatch(tuple(names), cols)


def _scalar_one(batch: ColumnBatch, s: AggSpec, sel) -> Column:
    if s.op == "count_star":
        return Column(jnp.sum(sel).astype(jnp.int64)[None], None, LType.INT64)
    c = batch.column(s.input)
    live = sel & c.valid_mask()
    if s.distinct and s.op in ("count", "sum", "avg"):
        return _scalar_distinct(c, live, s)
    if s.op == "count":
        return Column(jnp.sum(live).astype(jnp.int64)[None], None, LType.INT64)
    if s.op == "sum":
        dt = _sum_dtype(c)
        v = jnp.sum(jnp.where(live, c.data.astype(dt), 0))[None]
        any_ = jnp.any(live)[None]
        return Column(v, any_, agg_result_type("sum", c.ltype))
    if s.op == "avg":
        dt = jnp.float64
        sm = jnp.sum(jnp.where(live, c.data.astype(dt), 0))
        ct = jnp.sum(live)
        any_ = ct > 0
        return Column((sm / jnp.maximum(ct, 1))[None], any_[None], LType.FLOAT64)
    if s.op in ("min", "max"):
        ident = _minmax_identity(c, s.op == "min")
        v = jnp.where(live, c.data, ident)
        r = (jnp.min(v) if s.op == "min" else jnp.max(v))[None]
        return Column(r, jnp.any(live)[None], c.ltype, c.dictionary)
    if s.op == "sumsq":
        x = c.data.astype(jnp.float64)
        v = jnp.sum(jnp.where(live, x * x, 0.0))[None]
        return Column(v, jnp.any(live)[None], LType.FLOAT64)
    if s.op in ("stddev", "stddev_samp", "variance", "var_samp"):
        x = jnp.where(live, c.data.astype(jnp.float64), 0.0)
        n = jnp.sum(live).astype(jnp.float64)
        n1 = jnp.maximum(n, 1.0)
        mean = jnp.sum(x) / n1
        var = jnp.sum(jnp.where(live, (c.data.astype(jnp.float64) - mean) ** 2, 0.0))
        denom = n1 if s.op in ("stddev", "variance") else jnp.maximum(n - 1.0, 1.0)
        v = var / denom
        if s.op.startswith("stddev"):
            v = jnp.sqrt(v)
        return Column(v[None], (n > 0)[None], LType.FLOAT64)
    if s.op == "approx_count_distinct":
        regs = _hll_registers(c, live, jnp.zeros_like(c.data, jnp.int32), 1)
        return Column(_hll_estimate(regs)[:1], None, LType.INT64)
    if s.op == "percentile":
        gid = jnp.where(live, 0, 1)
        v, ok = _segment_percentile(c, gid, 1, s.param,
                                    jnp.sum(live, dtype=jnp.int32)[None])
        return Column(v, ok, LType.FLOAT64)
    raise ValueError(f"unknown aggregate {s.op}")


# -- sketch aggregates --------------------------------------------------


def _hll_registers(c: Column, live, gid, ng: int):
    """Per-group HyperLogLog register table [ng, m] via ONE segment_max —
    the reference's HLL sketches (src/common/hll_common.cpp) re-expressed as
    a segment reduction (TPU-native: no per-row register RMW)."""
    from ..utils.hashing import hash_columns, mix32

    m = HLL_REGISTERS
    h1 = hash_columns([c.data])
    h2 = mix32(h1 ^ jnp.uint32(0x9E3779B9))     # independent second stream
    reg = (h1 % jnp.uint32(m)).astype(jnp.int32)
    # rho = 1 + leading zeros of the second stream (32-bit)
    nz = 32 - jnp.ceil(jnp.log2(h2.astype(jnp.float64) + 1.0)).astype(jnp.int32)
    rho = jnp.clip(nz + 1, 1, 33)
    slot = jnp.where(live, gid * m + reg, ng * m)
    regs = seg_max(jnp.where(live, rho, 0), slot,
                               num_segments=ng * m + 1)[:ng * m]
    return jnp.maximum(regs, 0).reshape(ng, m)


def _hll_estimate(regs):
    """[ng, m] registers -> cardinality estimate with small-range correction."""
    m = float(HLL_REGISTERS)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    z = jnp.sum(2.0 ** (-regs.astype(jnp.float64)), axis=1)
    e = alpha * m * m / z
    zeros = jnp.sum(regs == 0, axis=1).astype(jnp.float64)
    small = m * jnp.log(m / jnp.maximum(zeros, 1.0))
    est = jnp.where((e <= 2.5 * m) & (zeros > 0), small, e)
    return jnp.round(est).astype(jnp.int64)


def _segment_percentile(c: Column, gid_v, ng: int, p: float, counts):
    """Exact percentile per group: sort by (group, value), index into each
    group's run with linear interpolation (PERCENTILE_CONT semantics).  The
    reference approximates with t-digest (src/common/tdigest.cpp) because
    CPU sorts are expensive; on TPU the sort IS the cheap primitive.
    ``counts``: the rows of each group (``gid_v < ng``), int32 [ng]."""
    x = c.data.astype(jnp.float64)
    order = argsort(x)
    order = order[argsort(gid_v[order])]
    v = x[order]
    starts = jnp.cumsum(counts) - counts
    tpos = starts.astype(jnp.float64) + p * jnp.maximum(counts - 1, 0)
    lo = jnp.floor(tpos).astype(jnp.int32)
    hi = jnp.ceil(tpos).astype(jnp.int32)
    n = v.shape[0]
    vlo = jnp.take(v, jnp.clip(lo, 0, max(n - 1, 0)), mode="clip")
    vhi = jnp.take(v, jnp.clip(hi, 0, max(n - 1, 0)), mode="clip")
    frac = tpos - lo
    return vlo + (vhi - vlo) * frac, counts > 0


def _scalar_distinct(c: Column, live, s: AggSpec) -> Column:
    """COUNT/SUM/AVG(DISTINCT x): sort + boundary count.  Dead/NULL lanes get
    the +max sentinel so they sort past the live prefix."""
    d = jnp.where(live, c.data, _minmax_identity(c, is_min=True))
    srt = jnp.sort(d)
    live_n = jnp.sum(live)
    idx = jnp.arange(d.shape[0])
    new = (idx == 0) | (srt != jnp.roll(srt, 1))
    uniq = new & (idx < live_n)
    if s.op == "count":
        return Column(jnp.sum(uniq).astype(jnp.int64)[None], None, LType.INT64)
    dt = _sum_dtype(c)
    sm = jnp.sum(jnp.where(uniq, srt.astype(dt), 0))
    if s.op == "sum":
        return Column(sm[None], jnp.any(uniq)[None], agg_result_type("sum", c.ltype))
    ct = jnp.maximum(jnp.sum(uniq), 1)
    return Column((sm.astype(jnp.float64) / ct)[None], jnp.any(uniq)[None], LType.FLOAT64)


# ----------------------------------------------------------------------
# dense-domain group-by (segment_sum fast path)


def combined_dense_id(key_cols: list[Column], domains: list[int]):
    """Mixed-radix fold of dense key codes -> single group id, plus validity.

    NULL keys get their own slot: each radix is domain+1 with NULL -> domain."""
    cid = None
    for c, dom in zip(key_cols, domains):
        code = c.data.astype(jnp.int32)
        if c.validity is not None:
            code = jnp.where(c.validity, code, dom)
        code = jnp.clip(code, 0, dom)
        cid = code if cid is None else cid * (dom + 1) + code
    return cid


def dense_num_groups(domains: list[int]) -> int:
    n = 1
    for d in domains:
        n *= d + 1
    return n


def group_aggregate_dense(batch: ColumnBatch, key_names: list[str],
                          domains: list[int], specs: list[AggSpec]) -> ColumnBatch:
    """GROUP BY over dense-coded keys: one segment reduction per aggregate.

    Output capacity = prod(domain+1); absent groups are masked via sel.

    The rows of each group are counted once, in the arm that runs, and
    ``present`` (rows > 0), ``COUNT(*)`` (rows) and the count of every
    column without a validity array (its live lanes are the selected ones)
    are read off that one vector; a nullable column is counted once more,
    by name.  On the segment arms the vector is one ``seg_sum`` of int32
    ones (:func:`_group_rows`); on the Pallas arm it is the ``cnt`` a fused
    kernel already returns for a column without NULLs, else one
    ``partition_histogram``.  At 2^27 lanes on the v5e (PR 35's ledger
    line, this PR's traces) a count of its own costs: the int32 scatter of
    ones past ONEHOT_MAX_SEGMENTS 0.9-1.0 s, the histogram 0.13 s at 1,000
    groups and 0.25 s at 4,000, a select+reduce lane ~5.5 ms at 16 groups:
    COUNT(*), SUM, AVG, MIN of a FLOAT column made five such passes and
    makes one (none on the Pallas arm).  ``noting_lowerings`` is told how
    many were traced: ``agg_count_passes``."""
    key_cols = [batch.column(k) for k in key_names]
    ng = dense_num_groups(domains)
    gid = combined_dense_id(key_cols, domains)
    sel = batch.sel_mask()
    # reconstruct key columns from slot index
    out_names, out_cols = [], []
    slot = jnp.arange(ng, dtype=jnp.int32)
    rem = slot
    strides = []
    st = 1
    for dom in reversed(domains):
        strides.append(st)
        st *= dom + 1
    strides = list(reversed(strides))
    for name, c, dom, stride in zip(key_names, key_cols, domains, strides):
        code = (rem // stride) % (dom + 1)
        validity = code < dom if c.validity is not None else None
        code = jnp.where(code >= dom, 0, code)
        out_names.append(name)
        out_cols.append(Column(code.astype(c.data.dtype), validity, c.ltype, c.dictionary))
    lowering = dense_lowering(specs, lambda n: batch.column(n).ltype, ng)
    out_names.extend(s.out_name for s in specs)
    if lowering == "pallas":
        rows, cols, count_passes = _pallas_dense_cols(batch, specs, gid, ng,
                                                      sel)
    else:
        gid_live = jnp.where(sel, gid, ng)  # dead rows -> overflow bucket
        counts: dict = {}
        cols = [_segment_one(batch, s, gid_live, ng, sel, counts)
                for s in specs]
        rows = _group_rows(counts, None, gid_live, ng)
        count_passes = len(counts)
    out_cols.extend(cols)
    noted = _NOTED.get()
    if noted is not None:
        noted.append((lowering, count_passes))
    return ColumnBatch(tuple(out_names), out_cols, rows > 0, None)


# the three lowerings of a dense aggregate's reductions, as EXPLAIN names
# them and utils/metrics counts them (agg_<lowering>_runs)
LOWERINGS = ("select_reduce", "pallas", "scatter")
_NOTED: ContextVar = ContextVar("dense_lowerings", default=None)


@contextmanager
def noting_lowerings():
    """While a program is traced under this, every ``group_aggregate_dense``
    appends ``(lowering, count_passes)`` to the list yielded — the lowering
    it chose and the passes over its input lanes it traced only to count
    rows: the trace-time facts, kept with the compiled program by the
    tracer (the executor's ``compile_plan``) and counted once an
    execution."""
    noted: list = []
    token = _NOTED.set(noted)
    try:
        yield noted
    finally:
        _NOTED.reset(token)


def dense_lowering(specs: list, ltype_of: Callable, ng: int) -> str:
    """Which lowering a dense aggregate over ``ng`` groups takes, from what
    is known before a row is read: the backend, the group count, the
    aggregates and the types of their inputs (``ltype_of(column)``).

    ``pallas``: the MXU one-hot kernels (ops/pallas_kernels.py), when they
    are exact enough for the spec list —

    - only COUNT/COUNT(*)/SUM/AVG/MIN/MAX, no DISTINCT;
    - value columns must be floats (counts are exact; a float sum leaves
      the kernel as a compensated f32 pair a block row and is added up in
      DOUBLE outside it); MIN/MAX additionally need FLOAT32 columns (f64
      values would be rounded by the f32 pipeline);
    - group count in (select+reduce crossover, PALLAS_MAX_GROUPS];
    - TPU backend.

    Else the segment reductions (ops/segments.py): ``select_reduce`` on the
    TPU up to ONEHOT_MAX_SEGMENTS, ``scatter`` beyond and on the CPU."""
    from .pallas_kernels import PALLAS_MAX_GROUPS

    if segments._use_onehot(ng + 1):
        return "select_reduce"
    if not (segments._onehot_backend() and ng + 1 <= PALLAS_MAX_GROUPS):
        return "scatter"
    for s in specs:
        if s.distinct or s.op not in ("count_star", "count", "sum", "avg",
                                      "min", "max"):
            return "scatter"
        if s.op != "count_star":
            lt = ltype_of(s.input)
            if lt not in (LType.FLOAT32, LType.FLOAT64):
                return "scatter"
            if s.op in ("min", "max") and lt is not LType.FLOAT32:
                return "scatter"
    return "pallas"


def _pallas_dense_cols(batch, specs, gid, ng: int, sel):
    """A dense group-by that :func:`dense_lowering` put on the Pallas MXU
    kernels.  -> (rows per group [ng] as whole-number f64, the aggregate
    Columns in spec order, the passes made only to count rows: 0 or 1).

    Every value column goes through one fused kernel, whose ``cnt`` counts
    the selected lanes that hold a value.  For a column without a validity
    array those are the selected lanes, so its ``cnt`` is the rows of each
    group; only where every value column is nullable (or there is none)
    does a ``partition_histogram`` count them.  A group whose selected
    rows all hold NULL keeps its rows and an empty ``cnt``: present,
    COUNT(*) its rows, SUM NULL."""
    from .pallas_kernels import (filtered_group_sum, fused_group_aggregate,
                                 partition_histogram)

    fused: dict = {}          # input name -> (cnt, sm, mn, mx)
    rows = None
    for s in specs:
        if s.op == "count_star" or s.input in fused:
            continue
        c = batch.column(s.input)
        live = sel if c.validity is None else c.validity & sel
        # min/max lanes cost extra VPU work per group: only the full
        # kernel when some spec on this column asks for them
        if any(x.op in ("min", "max") and x.input == s.input for x in specs):
            fused[s.input] = fused_group_aggregate(gid, c.data, live, ng)
        else:
            fused[s.input] = (*filtered_group_sum(gid, c.data, live, ng),
                              None, None)
        if rows is None and c.validity is None:
            rows = fused[s.input][0]
    count_passes = 0
    if rows is None:
        rows, count_passes = partition_histogram(gid, sel, ng), 1
    cols = []
    for s in specs:
        if s.op == "count_star":
            cols.append(Column(rows.astype(jnp.int64), None, LType.INT64))
            continue
        c = batch.column(s.input)
        cnt, sm, mn, mx = fused[s.input]
        nonempty = cnt > 0
        if s.op == "count":
            cols.append(Column(cnt.astype(jnp.int64), None, LType.INT64))
        elif s.op == "sum":
            cols.append(Column(sm, nonempty,
                               agg_result_type("sum", c.ltype)))
        elif s.op == "avg":
            cols.append(Column(sm / jnp.maximum(cnt, 1.0), nonempty,
                               LType.FLOAT64))
        elif s.op == "min":
            cols.append(Column(mn.astype(c.data.dtype), nonempty, c.ltype))
        else:
            cols.append(Column(mx.astype(c.data.dtype), nonempty, c.ltype))
    return rows, cols, count_passes


def _group_rows(counts: dict, name: Optional[str], gid, ng: int):
    """Rows per group over ``gid`` (``ng``, the dead-row bucket, on every
    lane that does not count), reduced once a ``name`` and kept in
    ``counts``: the cache the aggregates of one GROUP BY share, ``None`` ->
    the selected rows, a nullable column's name -> those that hold a value
    in it.  int32: a group holds fewer rows than the batch has lanes.
    ``len(counts)`` is the passes traced only to count rows."""
    if name not in counts:
        counts[name] = seg_sum(jnp.ones_like(gid, jnp.int32), gid,
                               num_segments=ng + 1)[:ng]
    return counts[name]


def _segment_one(batch: ColumnBatch, s: AggSpec, gid, ng: int, sel,
                 counts: dict) -> Column:
    """One aggregate via segment reduction; gid==ng is the dead-row bucket.
    ``counts``: :func:`_group_rows`' cache over the same ``gid``."""
    if s.op == "count_star":
        return Column(_group_rows(counts, None, gid, ng).astype(jnp.int64),
                      None, LType.INT64)
    c = batch.column(s.input)
    live = c.valid_mask() & sel
    gid_v = jnp.where(live, gid, ng)
    if s.distinct:
        return _segment_distinct(c, gid_v, ng, s)
    if s.op == "approx_count_distinct":
        regs = _hll_registers(c, live, gid_v, ng)
        return Column(_hll_estimate(regs), None, LType.INT64)
    # a column without a validity array holds a value on every selected row
    ct = _group_rows(counts, None, gid, ng) if c.validity is None \
        else _group_rows(counts, s.input, gid_v, ng)
    if s.op == "count":
        return Column(ct.astype(jnp.int64), None, LType.INT64)
    if s.op == "sum":
        dt = _sum_dtype(c)
        v = seg_sum(c.data.astype(dt), gid_v, num_segments=ng + 1)[:ng]
        return Column(v, ct > 0, agg_result_type("sum", c.ltype))
    if s.op == "avg":
        sm = seg_sum(c.data.astype(jnp.float64), gid_v, num_segments=ng + 1)[:ng]
        return Column(sm / jnp.maximum(ct, 1), ct > 0, LType.FLOAT64)
    if s.op in ("min", "max"):
        is_min = s.op == "min"
        v = (seg_min if is_min else seg_max)(
            jnp.where(live, c.data, _minmax_identity(c, is_min)), gid_v,
            num_segments=ng + 1)[:ng]
        return Column(v, ct > 0, c.ltype, c.dictionary)
    if s.op == "sumsq":
        x = c.data.astype(jnp.float64)
        v = seg_sum(jnp.where(live, x * x, 0.0), gid_v, num_segments=ng + 1)[:ng]
        return Column(v, ct > 0, LType.FLOAT64)
    if s.op in ("stddev", "stddev_samp", "variance", "var_samp"):
        x = c.data.astype(jnp.float64)
        sm = seg_sum(jnp.where(live, x, 0.0), gid_v, num_segments=ng + 1)[:ng]
        s2 = seg_sum(jnp.where(live, x * x, 0.0), gid_v, num_segments=ng + 1)[:ng]
        n = ct.astype(jnp.float64)
        n1 = jnp.maximum(n, 1.0)
        var = s2 / n1 - (sm / n1) ** 2
        denom_n = n1 if s.op in ("stddev", "variance") else jnp.maximum(n - 1.0, 1.0)
        var = jnp.maximum(var * (n1 / denom_n), 0.0)
        v = jnp.sqrt(var) if s.op.startswith("stddev") else var
        return Column(v, ct > 0, LType.FLOAT64)
    if s.op == "percentile":
        v, ok = _segment_percentile(c, gid_v, ng, s.param, ct)
        return Column(v, ok, LType.FLOAT64)
    raise ValueError(f"unknown aggregate {s.op}")


def _segment_distinct(c: Column, gid, ng: int, s: AggSpec) -> Column:
    """Per-group DISTINCT via (gid, value) sort + boundary dedup."""
    order = argsort(c.data)
    order = order[argsort(gid[order])]
    g = gid[order]
    v = c.data[order]
    idx = jnp.arange(g.shape[0])
    new = (idx == 0) | (g != jnp.roll(g, 1)) | (v != jnp.roll(v, 1))
    live = g < ng
    w = new & live
    if s.op == "count":
        out = seg_sum(w.astype(jnp.int64), jnp.where(live, g, ng),
                                  num_segments=ng + 1)[:ng]
        return Column(out, None, LType.INT64)
    dt = _sum_dtype(c)
    sm = seg_sum(jnp.where(w, v.astype(dt), 0), jnp.where(live, g, ng),
                             num_segments=ng + 1)[:ng]
    if s.op == "sum":
        ct = seg_sum(w.astype(jnp.int32), jnp.where(live, g, ng),
                                 num_segments=ng + 1)[:ng]
        return Column(sm, ct > 0, agg_result_type("sum", c.ltype))
    ct = seg_sum(w.astype(jnp.int32), jnp.where(live, g, ng),
                             num_segments=ng + 1)[:ng]
    return Column(sm.astype(jnp.float64) / jnp.maximum(ct, 1), ct > 0, LType.FLOAT64)


# ----------------------------------------------------------------------
# sort-based group-by (general fallback)


def group_aggregate_sorted(batch: ColumnBatch, key_names: list[str],
                           specs: list[AggSpec], max_groups: int,
                           with_overflow: bool = False, order=None):
    """General GROUP BY: lexicographic stable sort, boundary cumsum group ids,
    segment reductions into a static max_groups-slot table.

    ``max_groups`` must upper-bound the true group count (the planner supplies
    it from statistics or len(batch)); groups fill slots densely, output
    carries num_rows = group count."""
    n = len(batch)
    key_cols = [batch.column(k) for k in key_names]
    sel = batch.sel_mask()
    # canonicalize NULL lanes to 0 so all NULL keys form ONE group regardless
    # of the garbage data under the invalid lanes (MySQL: NULLs group together)
    key_data = []
    for c in key_cols:
        d = c.data
        if d.dtype == jnp.bool_:
            d = d.astype(jnp.int32)
        if c.validity is not None:
            d = jnp.where(c.validity, d, jnp.zeros((), d.dtype))
        key_data.append(d)
    if order is not None:
        # host-precomputed per-version key order (the secondary-index
        # read): only the query-dependent liveness partition remains, and
        # a stable boolean partition is O(n) prefix-sum arithmetic — no
        # on-device sort at all
        live_o = sel[order]
        n_live = jnp.sum(live_o)
        dest = jnp.where(live_o, jnp.cumsum(live_o) - 1,
                         n_live + jnp.cumsum(~live_o) - 1)
        perm = jnp.zeros(n, order.dtype).at[dest].set(order)
    else:
        perm = jnp.arange(n, dtype=jnp.int32)
        for c, d in zip(reversed(key_cols), reversed(key_data)):
            perm = perm[argsort(d[perm])]
            if c.validity is not None:
                perm = perm[argsort(c.validity[perm])]  # NULLs first
        perm = perm[argsort(~sel[perm])]  # dead rows last

    sel_s = sel[perm]
    idx = jnp.arange(n)
    boundary = idx == 0
    for c, dd in zip(key_cols, key_data):
        d = dd[perm]
        boundary = boundary | (d != jnp.roll(d, 1))
        if c.validity is not None:
            v = c.validity[perm]
            boundary = boundary | (v != jnp.roll(v, 1))
    flags = boundary & sel_s
    gid = jnp.cumsum(flags.astype(jnp.int32)) - 1
    gid = jnp.where(sel_s & (gid >= 0) & (gid < max_groups), gid, max_groups)
    ngroups = jnp.minimum(jnp.sum(flags), max_groups).astype(jnp.int32)

    # scatter first-occurrence key values into group slots
    out_names, out_cols = [], []
    scatter_to = jnp.where(flags, jnp.clip(gid, 0, max_groups - 1), max_groups)
    for name, c in zip(key_names, key_cols):
        d = c.data[perm]
        buf = jnp.zeros((max_groups + 1,), d.dtype).at[scatter_to].set(d)[:max_groups]
        validity = None
        if c.validity is not None:
            vb = jnp.zeros((max_groups + 1,), bool).at[scatter_to].set(c.validity[perm])[:max_groups]
            validity = vb
        out_names.append(name)
        out_cols.append(Column(buf, validity, c.ltype, c.dictionary))

    sorted_batch = batch.gather(perm)
    sorted_batch.sel = sel_s
    for s in specs:
        out_names.append(s.out_name)
        # a cache of its own: each aggregate counts for itself here;
        # sharing the counts is the dense strategy's, whose count
        # reductions are the ones measured at 2^27 lanes
        out_cols.append(_segment_one(sorted_batch, s, gid, max_groups,
                                     sel_s, {}))
    present = jnp.arange(max_groups) < ngroups
    out = ColumnBatch(tuple(out_names), out_cols, present, ngroups)
    if with_overflow:
        return out, jnp.sum(flags) > max_groups
    return out


# ----------------------------------------------------------------------
# stream group-by (input already in key order: segmented scans)

# what _segment_one knows as an associative fold; DISTINCT, percentile and
# HLL specs need each group's rows and keep the planner off this path
STREAM_OPS = frozenset({"count_star", "count", "sum", "avg", "min", "max",
                        "sumsq", "stddev", "stddev_samp", "variance",
                        "var_samp"})


def stream_supported(specs: list[AggSpec]) -> bool:
    return all(s.op in STREAM_OPS and not s.distinct for s in specs)


def group_aggregate_stream(batch: ColumnBatch, key_name: str,
                           specs: list[AggSpec]):
    """GROUP BY one key whose live lanes arrive in non-decreasing key order
    (the planner's claim, from the store's ``ordered`` statistic and the
    operator chain).  -> (out, unordered).

    A live lane starts a run when its key differs from the previous LIVE
    lane's; each aggregate is one inclusive segmented scan over
    (starts_run, value); the group's row is the run's last live lane.  The
    output keeps the input's lanes: ``sel`` marks each run's last live
    lane, the key column is the input's own, the aggregates are the scans'
    values there.  Dead lanes (padding, filtered rows, a shrink's tail)
    take part as identities whatever they hold.

    ``unordered`` (int32 0/1) is the check of the claim: some live lane's
    key is below the previous live lane's, or NULL.  It rides the flag
    channel; raised, the answer of this program is not used (exec/caps.py
    gives the node the strategy it would have had, and it runs again)."""
    key = batch.column(key_name)
    sel = batch.sel_mask()
    k = key.data
    # the neighbouring live lanes' keys: a fill from each side, moved one
    # lane on so that a lane sees its neighbour and not itself
    def neighbour(reverse: bool):
        seen, (nk,) = seg_scan(sel, (k,), ("left",), reverse=reverse)
        return (shift_lanes(seen, 1, False, reverse=reverse),
                shift_lanes(nk, 1, 0, reverse=reverse))

    has_prev, prev_k = neighbour(False)
    has_next, next_k = neighbour(True)
    starts = sel & ~(has_prev & (prev_k == k))
    last = sel & ~(has_next & (next_k == k))
    unordered = jnp.any(sel & has_prev & (k < prev_k))
    if key.validity is not None:
        unordered = unordered | jnp.any(sel & ~key.validity)

    # AVG and the variance family are finalized from SUM / SUMSQ / COUNT
    # partials, as the streamed fold and the mesh merge do; one scan lane
    # per distinct (fold, input)
    parts, finalize = partial_specs(specs)
    lanes: dict = {}

    def lane(kind: str, name: Optional[str]):
        c = None if name is None else batch.column(name)
        if kind == "n" and c is not None and c.validity is None:
            name = c = None     # no NULLs: the count of the live lanes
        if (kind, name) not in lanes:
            live = sel if c is None or c.validity is None \
                else sel & c.validity
            if kind == "n":
                op, v = "add", live.astype(jnp.int32)
            elif kind in ("sum", "sumsq"):
                x = c.data.astype(_sum_dtype(c) if kind == "sum"
                                  else jnp.float64)
                op, v = "add", jnp.where(live, x * x if kind == "sumsq"
                                         else x, 0)
            else:
                x = c.data.astype(jnp.int8) if c.data.dtype == jnp.bool_ \
                    else c.data
                op, v = kind, jnp.where(live, x, scan_identity(kind, x.dtype))
            lanes[(kind, name)] = (op, v)
        return (kind, name)

    wanted = [(p, lane("n", p.input)) if p.op in ("count", "count_star")
              else (p, lane(p.op, p.input), lane("n", p.input))
              for p in parts]
    order = list(lanes)
    _, scanned = seg_scan(starts, tuple(lanes[i][1] for i in order),
                          tuple(lanes[i][0] for i in order))
    got = dict(zip(order, scanned))

    names, cols = [key_name], [key]
    for p, *at in wanted:
        names.append(p.out_name)
        if p.op in ("count", "count_star"):
            cols.append(Column(got[at[0]].astype(jnp.int64), None,
                               LType.INT64))
            continue
        c = batch.column(p.input)
        # a group of a column without NULLs always holds a value
        some = None if c.validity is None else got[at[1]] > 0
        if p.op in ("min", "max"):
            cols.append(Column(got[at[0]].astype(c.data.dtype), some,
                               c.ltype, c.dictionary))
        else:
            cols.append(Column(got[at[0]], some,
                               agg_result_type(p.op, c.ltype)))
    out = finalize_partials(ColumnBatch(tuple(names), cols, last, None),
                            finalize, [key_name])
    return out, unordered.astype(jnp.int32)


# ----------------------------------------------------------------------
# partial-aggregate merge protocol (for distributed / multi-shard merge)

MERGE_OP = {
    "count": "sum", "count_star": "sum", "sum": "sum", "sumsq": "sum",
    "min": "min", "max": "max",
}


def partial_specs(specs: list[AggSpec]) -> tuple[list[AggSpec], dict]:
    """Rewrite aggregates into mergeable partials (AVG -> SUM+COUNT, STDDEV ->
    SUM+SUMSQ+COUNT), the analog of the reference's AGG partial/MERGE_AGG split
    (plan.proto:14-16).  Returns (partial specs, finalize plan)."""
    parts: list[AggSpec] = []
    finalize: dict[str, tuple] = {}
    seen = {}

    def add(op, inp, distinct=False):
        key = (op, inp, distinct)
        if key in seen:
            return seen[key]
        name = f"__p{len(parts)}_{op}"
        parts.append(AggSpec(op, inp, name, distinct))
        seen[key] = name
        return name

    for s in specs:
        if s.op in ROW_AGGS:
            # these need each group's ROWS, not a mergeable scalar partial;
            # the distribute pass must have routed them via repartition
            raise ValueError(f"{s.op} has no scalar partial form")
        if s.op == "avg":
            finalize[s.out_name] = ("avg", add("sum", s.input, s.distinct),
                                    add("count", s.input, s.distinct))
        elif s.op in ("stddev", "stddev_samp", "variance", "var_samp"):
            sq = add("sumsq", s.input)
            finalize[s.out_name] = (s.op, add("sum", s.input), sq, add("count", s.input))
        elif s.distinct:
            # distinct cannot merge from partials; executed post-shuffle
            finalize[s.out_name] = ("passthrough", add(s.op, s.input, True))
        else:
            finalize[s.out_name] = ("passthrough", add(s.op, s.input))
    return parts, finalize


def finalize_partials(batch: ColumnBatch, finalize: dict, key_names: list[str]) -> ColumnBatch:
    """Apply the finalize plan from partial_specs to a merged-partials batch."""
    names = list(key_names)
    cols = [batch.column(k) for k in key_names]
    for out_name, plan in finalize.items():
        kind = plan[0]
        if kind == "passthrough":
            c = batch.column(plan[1])
        elif kind == "avg":
            sm, ct = batch.column(plan[1]), batch.column(plan[2])
            ctv = ct.data.astype(jnp.float64)
            c = Column(sm.data.astype(jnp.float64) / jnp.maximum(ctv, 1), ctv > 0, LType.FLOAT64)
        else:  # stddev family from (op, sum, sumsq, count)
            op, sm, sq, ct = plan[0], batch.column(plan[1]), batch.column(plan[2]), batch.column(plan[3])
            n = ct.data.astype(jnp.float64)
            n1 = jnp.maximum(n, 1.0)
            var = sq.data / n1 - (sm.data.astype(jnp.float64) / n1) ** 2
            denom = n1 if op in ("stddev", "variance") else jnp.maximum(n - 1.0, 1.0)
            var = jnp.maximum(var * (n1 / denom), 0.0)
            v = jnp.sqrt(var) if op.startswith("stddev") else var
            c = Column(v, n > 0, LType.FLOAT64)
        names.append(out_name)
        cols.append(c)
    return ColumnBatch(tuple(names), cols, batch.sel, batch.num_rows)
