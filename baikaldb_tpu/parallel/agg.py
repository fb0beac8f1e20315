"""Distributed aggregation: per-shard partials + mesh collectives.

The reference pushes partial AggNodes to every region and merges on the
coordinator (MERGE_AGG_NODE, plan.proto:14-16; src/exec/agg_node.cpp), moving
partial states over brpc.  Here each mesh shard computes the SAME fixed-size
partial table (dense group domain), and the merge is a single XLA collective
over ICI: psum for sum/count partials, pmin/pmax for min/max — the
BASELINE.json north-star config #2 ("per-region partial agg + psum").

Cardinality-adaptive partial aggregation (the Partial Partial Aggregates
policy, PAPERS.md): pre-reducing locally only pays when the group-key
cardinality is small relative to each shard's row count — a near-unique
group key makes the local pre-pass pure overhead (every "partial" holds one
row).  ``choose_strategy`` picks per query from the index/stats ndv
estimate: "local" = pre-reduce before the psum/all-to-all, "raw" = shuffle
raw rows and aggregate once.  plan/distribute.py records the decision on
the AggNode (EXPLAIN ANALYZE ``-- exchange:`` surfaces it).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..column.batch import Column, ColumnBatch
from ..ops.hashagg import (AggSpec, MERGE_OP, finalize_partials,
                           group_aggregate_dense, group_aggregate_sorted,
                           partial_specs)
from ..utils.flags import FLAGS, define
from .mesh import AXIS

define("adaptive_agg", True,
       "choose per query between local pre-aggregation and raw-row shuffle "
       "for distributed GROUP BY, from the stats distinct-count estimate "
       "(off: the pre-round-7 static policy — dense pre-reduces, sorted "
       "shuffles raw)")
define("adaptive_agg_selectivity", True,
       "feed the bound-value WHERE selectivity (index/stats histograms "
       "over THIS execution's literals) into the local-vs-raw decision: a "
       "highly selective predicate shrinks effective rows-per-shard and "
       "can flip local -> raw per execution.  0 restores the "
       "selectivity-blind threshold")

# pre-reduce locally when estimated groups <= ratio * rows-per-shard: above
# it the partial pass moves more data than it saves
AGG_LOCAL_RATIO = 0.5


def choose_strategy(est_groups: Optional[int], rows_per_shard: int,
                    selectivity: Optional[float] = None) -> str:
    """-> "local" | "raw".  Pre-reduction shrinks each shard's exchange
    payload from ~rows_per_shard rows to ~min(groups, rows_per_shard)
    partials; it pays exactly when groups is well under rows_per_shard.
    Unknown cardinality (no stats) keeps the conservative raw shuffle —
    a wrong "local" costs a wasted O(n log n) pre-pass on every shard.

    ``selectivity`` is the bound-value WHERE selectivity estimate for the
    rows feeding this aggregate (index/stats over the literals of THIS
    execution; None = no basis): the pre-pass only summarizes rows the
    filter keeps, so effective rows-per-shard scales by it — a WHERE that
    keeps 0.1% of rows makes even a 3-value group key not worth a local
    pre-reduce pass over the full shard."""
    if not FLAGS.adaptive_agg or est_groups is None:
        return "raw"
    if selectivity is not None and FLAGS.adaptive_agg_selectivity:
        rows_per_shard = max(1, int(rows_per_shard * float(selectivity)))
    return "local" \
        if est_groups <= max(1, int(rows_per_shard * AGG_LOCAL_RATIO)) \
        else "raw"


def merge_partial_agg_specs(parts: list[AggSpec]) -> list[AggSpec]:
    """Specs that re-aggregate shuffled PARTIAL rows into final partials:
    each partial column merges under its MERGE_OP (sum-of-sums,
    min-of-mins, ...) keeping its name so the finalize plan still binds."""
    return [AggSpec(MERGE_OP[p.op], p.out_name, p.out_name) for p in parts]


# wire-partial kind -> merge op, the host mirror of MERGE_OP: pushed-down
# fragment partials (plan/fragment.py) coming back from store daemons
# combine under the identical discipline the device applies to partial
# columns — COUNT partials are sums, SUM partials sum-of-sums, MIN/MAX
# idempotent extremes.  AVG never appears: build_push_query decomposes it
# into sum + count at extraction, exactly like partial_specs does on
# device.
WIRE_MERGE = {"count": "sum", "count_star": "sum", "sum": "sum",
              "min": "min", "max": "max"}


def merge_host_partial(kind: str, a, b):
    """Combine two wire-format fragment partials (host Python values).
    NULL partials (an all-NULL or empty region input) are merge
    identities, matching the device's masked-lane behavior.  Raises
    KeyError on an unknown kind (callers type it for their plane)."""
    op = WIRE_MERGE[kind]
    if kind in ("count", "count_star"):
        return int(a) + int(b)
    if a is None:
        return b
    if b is None:
        return a
    if op == "sum":
        return a + b
    return min(a, b) if op == "min" else max(a, b)


def rewrap_partial(part: ColumnBatch) -> ColumnBatch:
    """Partial rows as a PLAIN batch: drop the kernel's traced group count
    (the next aggregate recomputes liveness from sel) and make the mask
    explicit — every partial-merge consumer (the shuffled local arm here,
    exec/streaming.py's chunk fold) needs the same uniform structure."""
    sel = part.sel if part.sel is not None \
        else jnp.ones(len(part), dtype=bool)
    return ColumnBatch(part.names, part.columns, sel, None)


def _pextremum(x, axis_name: str, is_min: bool):
    """pmin/pmax the TPU can lower for BIGINT/DOUBLE lanes too.  Its x64
    rewriter implements only the Sum all-reduce over 64-bit element types
    ("Supported lowering only of Sum all reduce"), so 64-bit partials are
    all_gathered — data movement, which it does rewrite: the broadcast join
    gathers BIGINT/DOUBLE columns the same way — and reduced locally."""
    if x.dtype.itemsize < 8:
        return (jax.lax.pmin if is_min else jax.lax.pmax)(x, axis_name)
    gathered = jax.lax.all_gather(x, axis_name)
    return (jnp.min if is_min else jnp.max)(gathered, axis=0)


def merge_collective(op: str, x, axis_name: str = AXIS):
    """In-network merge of one partial-aggregate lane under its MERGE_OP."""
    if op == "sum":
        return jax.lax.psum(x, axis_name)
    if op in ("min", "max"):
        return _pextremum(x, axis_name, op == "min")
    raise ValueError(f"no collective merge for {op}")


def dist_group_aggregate_dense(batch: ColumnBatch, key_names: list[str],
                               domains: list[int], specs: list[AggSpec],
                               mesh) -> ColumnBatch:
    """GROUP BY over a row-sharded batch; dense key domains.

    Inside shard_map every device reduces its local rows into the
    [prod(domains+1)] partial table, then the tables merge in-network
    (psum/pmin/pmax over ICI).  Output is replicated (small)."""
    parts, fin = partial_specs(specs)
    for s in parts:
        if s.distinct:
            raise ValueError("DISTINCT aggregates need a shuffle "
                             "(use dist_group_aggregate_shuffled)")

    in_specs = jax.tree.map(lambda _: P(AXIS), batch)

    def local(b: ColumnBatch) -> ColumnBatch:
        part = group_aggregate_dense(b, key_names, domains, parts)
        cols = []
        for name, c in zip(part.names, part.columns):
            if name in key_names:
                cols.append(c)
                continue
            spec = next(s for s in parts if s.out_name == name)
            merged = merge_collective(MERGE_OP[spec.op], c.data)
            validity = c.validity
            if validity is not None:
                validity = jax.lax.psum(validity.astype(jnp.int32), AXIS) > 0
            cols.append(Column(merged, validity, c.ltype, c.dictionary))
        present = jax.lax.psum(part.sel_mask().astype(jnp.int32), AXIS) > 0
        return ColumnBatch(part.names, cols, present, None)

    out_specs = jax.tree.map(lambda _: P(), _shape_probe(batch, key_names,
                                                         domains, parts))
    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_specs,),
                       out_specs=out_specs, check_vma=False)
    merged = fn(batch)
    return finalize_partials(merged, fin, key_names)


def _shape_probe(batch, key_names, domains, parts):
    """Eval-shape the local fn output to build a matching out_specs pytree."""
    import jax

    def probe(b):
        return group_aggregate_dense(b, key_names, domains, parts)

    out = jax.eval_shape(probe, batch)
    return out


def dist_group_aggregate_partial_shuffled(batch: ColumnBatch,
                                          key_names: list[str],
                                          specs: list[AggSpec], mesh,
                                          max_groups_per_shard: int,
                                          shuffle_cap: int | None = None):
    """Low-cardinality GROUP BY over the sorted strategy: each shard
    pre-reduces its rows into partial-aggregate rows (AVG -> SUM+COUNT,
    ...), shuffles only the PARTIALS on the key hash, and merges co-located
    partials once — the "local" arm of the adaptive policy.  Exchange
    payload is O(groups) per shard instead of O(rows).

    Returns (out, (shuffle_overflow, group_overflow)) matching the raw-arm
    kernel's contract (dist_group_aggregate_shuffled)."""
    from ..parallel.shuffle import repartition_collective

    parts, fin = partial_specs(specs)
    merge_specs = merge_partial_agg_specs(parts)
    n = mesh.devices.size
    per_shard = max(1, len(batch) // n)
    mg_part = min(max_groups_per_shard, per_shard)
    cap = shuffle_cap if shuffle_cap is not None \
        else max(1, 2 * mg_part // n)
    in_specs = jax.tree.map(lambda _: P(AXIS), batch)

    def local(b: ColumnBatch):
        part, p_ovf = group_aggregate_sorted(b, key_names, parts, mg_part,
                                             with_overflow=True)
        part = rewrap_partial(part)
        shuf, needed = repartition_collective(part, key_names, n, cap)
        final, f_ovf = group_aggregate_sorted(shuf, key_names, merge_specs,
                                              len(shuf), with_overflow=True)
        out = finalize_partials(final, fin, key_names)
        out = ColumnBatch(out.names, out.columns, out.sel, None)
        g_ovf = jax.lax.psum((p_ovf | f_ovf).astype(jnp.int32), AXIS) > 0
        s_ovf = jax.lax.pmax(needed, AXIS) > cap
        return out, s_ovf, g_ovf

    def probe_fn(b):
        part = group_aggregate_sorted(b, key_names, parts, mg_part)
        part = rewrap_partial(part)
        shuf = ColumnBatch(
            part.names,
            [Column(jnp.zeros((n * cap,), c.data.dtype),
                    None if c.validity is None else jnp.zeros((n * cap,),
                                                              bool),
                    c.ltype, c.dictionary) for c in part.columns],
            jnp.zeros((n * cap,), bool), None)
        final = group_aggregate_sorted(shuf, key_names, merge_specs,
                                       len(shuf))
        out = finalize_partials(final, fin, key_names)
        return ColumnBatch(out.names, out.columns, out.sel, None)

    probe = jax.eval_shape(probe_fn, _shard_view(batch, n))
    out_specs = (jax.tree.map(lambda _: P(AXIS), probe), P(), P())
    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_specs,),
                       out_specs=out_specs, check_vma=False)
    out, s_ovf, g_ovf = fn(batch)
    return out, (s_ovf, g_ovf)


def _shard_view(batch: ColumnBatch, n: int) -> ColumnBatch:
    """Shape-only per-shard view (for eval_shape)."""
    def slc(x):
        return jax.ShapeDtypeStruct((x.shape[0] // n,) + x.shape[1:],
                                    x.dtype)

    return jax.tree.map(slc, batch)


def dist_scalar_aggregate(batch: ColumnBatch, specs: list[AggSpec],
                          mesh) -> ColumnBatch:
    """Global aggregates (no GROUP BY) over a row-sharded batch."""
    from ..ops.hashagg import scalar_aggregate

    parts, fin = partial_specs(specs)
    for s in parts:
        if s.distinct:
            raise ValueError("DISTINCT scalar aggregates need a gather")
    in_specs = jax.tree.map(lambda _: P(AXIS), batch)

    def local(b: ColumnBatch) -> ColumnBatch:
        part = scalar_aggregate(b, parts)
        cols = []
        for name, c in zip(part.names, part.columns):
            spec = next(s for s in parts if s.out_name == name)
            merged = merge_collective(MERGE_OP[spec.op], c.data)
            validity = c.validity
            if validity is not None:
                validity = jax.lax.psum(validity.astype(jnp.int32), AXIS) > 0
            cols.append(Column(merged, validity, c.ltype, c.dictionary))
        return ColumnBatch(part.names, cols, None, None)

    out_probe = jax.eval_shape(lambda b: scalar_aggregate(b, parts), batch)
    out_specs = jax.tree.map(lambda _: P(), out_probe)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_specs,),
                       out_specs=out_specs, check_vma=False)
    merged = fn(batch)
    return finalize_partials(merged, fin, [])
