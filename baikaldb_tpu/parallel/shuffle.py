"""MPP shuffle as in-program collectives: hash repartition + distributed join.

The reference's MPP plane shuffles Arrow RecordBatches between worker dbs over
brpc (`ExchangeSenderNode` hash-partitions batches into per-channel
`transmit_data` RPCs, src/exec/exchange_sender_node.cpp; receivers queue them
in DataStreamManager).  On a TPU mesh the entire exchange is ONE
`lax.all_to_all` over ICI inside the jitted program:

  1. each shard computes dest = hash(key) % n for its rows,
  2. sorts rows by dest and scatters them into an [n, cap] padded send
     buffer (cap = per-destination capacity, static),
  3. all_to_all swaps the leading axis, giving every shard the [n, cap] rows
     hashed to it,
  4. rows flatten back into a local batch with a validity sel mask.

Per-destination overflow (a skewed key exceeding cap) sets a flag the caller
retries on with a larger cap — the analog of exchange backpressure.
After repartition, keys are disjoint across shards, so joins and group-bys
complete locally with no further communication (the reference's reason for
hash repartition, mpp_analyzer.cpp).
"""

from __future__ import annotations

from dataclasses import replace as dreplace

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..column.batch import Column, ColumnBatch
from ..ops import join as join_ops
from ..ops.hashagg import AggSpec, group_aggregate_sorted
from ..ops.sort import argsort
from ..utils.hashing import partition_ids
from .mesh import AXIS


def partition_key_arrays(b: ColumnBatch, key_names: list[str]) -> list:
    """Key columns -> hashable lanes for shuffle partitioning.

    String columns hash by VALUE (codes mapped through the dictionary's
    per-value hash table), so two tables with different dictionaries still
    co-locate equal strings.  NULL lanes canonicalize to 0 so every NULL-key
    row routes to one shard (validity still separates NULL from key 0 in the
    local group-by/join)."""
    from ..types import LType

    keys = []
    for k in key_names:
        c = b.column(k)
        d = c.data
        if c.ltype is LType.STRING and c.dictionary is not None:
            if len(c.dictionary) == 0:
                d = jnp.zeros(d.shape, jnp.uint32)
            else:
                table = jnp.asarray(c.dictionary.value_hashes())
                d = jnp.take(table, jnp.clip(d, 0, len(c.dictionary) - 1),
                             mode="clip")
        if c.validity is not None:
            d = jnp.where(c.validity, d, jnp.zeros((), d.dtype))
        keys.append(d)
    return keys


def _local_repartition(b: ColumnBatch, key_names: list[str], n: int, cap: int):
    """Shard-local: -> ([n, cap]-shaped batch pytree, valid [n, cap], overflow)."""
    dest = partition_ids(partition_key_arrays(b, key_names), n)
    sel = b.sel_mask()
    dest = jnp.where(sel, dest, n)                    # dead rows -> bucket n
    order = argsort(dest)
    dest_s = dest[order]
    # rank within destination bucket
    idx = jnp.arange(dest_s.shape[0])
    start = jnp.searchsorted(dest_s, jnp.arange(n + 1))
    rank = idx - start[jnp.clip(dest_s, 0, n)]
    counts = start[1:] - start[:-1]                   # per-dest counts [n]
    needed = counts.max().astype(jnp.int32) if n else jnp.int32(0)
    # scatter into [n, cap] send buffer (dest-major)
    slot = jnp.where((dest_s < n) & (rank < cap), dest_s * cap + rank, n * cap)
    valid = jnp.zeros((n * cap + 1,), bool).at[slot].set(True)[:n * cap]

    def scatter_col(data):
        buf = jnp.zeros((n * cap + 1,), data.dtype).at[slot].set(data[order])
        return buf[:n * cap].reshape(n, cap)

    cols = []
    for c in b.columns:
        data = scatter_col(c.data)
        validity = None if c.validity is None else scatter_col(c.validity)
        cols.append(Column(data, validity, c.ltype, c.dictionary))
    return cols, valid.reshape(n, cap), needed


def _all_to_all(x):
    return jax.lax.all_to_all(x, AXIS, split_axis=0, concat_axis=0, tiled=True)


def repartition_collective(b: ColumnBatch, key_names: list[str], n: int,
                           cap: int):
    """Shard-local body of the exchange: hash-partition + ONE all_to_all.

    -> (repartitioned local batch [n*cap rows], needed: per-shard max bucket
    size, int32).  Usable only inside shard_map; shared by the standalone
    dist_* kernels below and the SQL executor's ExchangeNode lowering."""
    cols, valid, needed = _local_repartition(b, key_names, n, cap)
    out_cols = []
    for c in cols:
        data = _all_to_all(c.data).reshape(n * cap)
        validity = None if c.validity is None else \
            _all_to_all(c.validity).reshape(n * cap)
        out_cols.append(Column(data, validity, c.ltype, c.dictionary))
    sel = _all_to_all(valid).reshape(n * cap)
    return ColumnBatch(b.names, out_cols, sel, None), needed


def repartition_fn(names, key_names: list[str], n: int, cap: int):
    """Build the shard-local repartition function (for use inside shard_map)."""

    def fn(b: ColumnBatch):
        out, needed = repartition_collective(b, key_names, n, cap)
        any_overflow = jax.lax.pmax(needed, AXIS) > cap
        return ColumnBatch(names, out.columns, out.sel, None), any_overflow

    return fn


def dist_hash_repartition(batch: ColumnBatch, key_names: list[str], mesh,
                          cap: int | None = None):
    """Repartition a row-sharded batch so equal keys land on one shard.

    Returns (sharded batch [rows = n*cap per shard], overflow flag)."""
    n = mesh.devices.size
    per_shard = len(batch) // n
    if cap is None:
        cap = max(1, 2 * per_shard // n)
    in_specs = jax.tree.map(lambda _: P(AXIS), batch)
    local = repartition_fn(batch.names, key_names, n, cap)

    # output pytree structure == input batch structure (cols+sel), so reuse it
    # as the out_specs template (eval_shape can't trace the collectives)
    out_specs = (jax.tree.map(lambda _: P(AXIS), batch), P())
    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_specs,),
                       out_specs=out_specs, check_vma=False)
    return fn(batch)


def _local_view(batch: ColumnBatch, n: int) -> ColumnBatch:
    """Shape-only view of one shard's slice (for eval_shape)."""
    import numpy as np

    def slc(x):
        return jax.ShapeDtypeStruct((x.shape[0] // n,) + x.shape[1:], x.dtype)

    return jax.tree.map(slc, batch)


def dist_join(probe: ColumnBatch, probe_keys: list[str],
              build: ColumnBatch, build_keys: list[str], mesh,
              how: str = "inner", cap: int | None = None,
              shuffle_cap: int | None = None):
    """Distributed equi-join: all_to_all both sides on the key hash, then one
    local sort-join per shard (BASELINE config #3: 'all-to-all shuffle on the
    join key')."""
    n = mesh.devices.size
    pshard, ovf_p = dist_hash_repartition(probe, probe_keys, mesh, shuffle_cap)
    bshard, ovf_b = dist_hash_repartition(build, build_keys, mesh, shuffle_cap)

    local_cap = cap or len(pshard) // n
    in_p = jax.tree.map(lambda _: P(AXIS), pshard)
    in_b = jax.tree.map(lambda _: P(AXIS), bshard)

    def local(pb: ColumnBatch, bb: ColumnBatch):
        out, needed = join_ops.join(pb, probe_keys, bb, build_keys, how=how,
                                    cap=local_cap)
        any_ovf = jax.lax.pmax(needed, AXIS) > local_cap
        return out, any_ovf

    probe_local = _local_view(pshard, n)
    build_local = _local_view(bshard, n)
    # probe shapes via the collective-free join kernel only
    out_probe = jax.eval_shape(
        lambda a, b: join_ops.join(a, probe_keys, b, build_keys, how=how,
                                   cap=local_cap)[0],
        probe_local, build_local)
    out_specs = (jax.tree.map(lambda _: P(AXIS), out_probe), P())
    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_p, in_b),
                       out_specs=out_specs, check_vma=False)
    out, ovf_j = fn(pshard, bshard)
    return out, (ovf_p, ovf_b, ovf_j)


def dist_multiway_join(probe: ColumnBatch, probe_keys: list[str],
                       builds: list, hows: list[str], mesh,
                       cap: int | None = None,
                       shuffle_cap: int | None = None,
                       level_keys: list | None = None,
                       packs: list | None = None):
    """Distributed fused multiway equi-join on ONE shared key (the MPP
    exchange v2 shape): every input — the probe and each build in
    ``builds`` = [(batch, key_names), ...] — radix-partitions and
    ``all_to_all``s ONCE on its key hash, then a single fused multi-build
    probe pass (ops/join.multiway_join) runs per shard.  One exchange
    round total, versus one per binary join in the chained plan; the
    intermediate join results never exist, so they are never re-shuffled.

    Returns (out, (probe_shuffle_needed, [build_shuffle_needed...],
    join_overflow)) — every flag rides the standard retry protocol.
    ``level_keys`` (per-level probe key columns, keyed-exchange-scheduler
    segments) passes through to the kernel; the probe still partitions on
    ``probe_keys``, the segment's class representative."""
    n = mesh.devices.size
    pshard, ovf_p = dist_hash_repartition(probe, probe_keys, mesh,
                                          shuffle_cap)
    bshards, ovf_b = [], []
    for bb, bkeys in builds:
        bs, ob = dist_hash_repartition(bb, bkeys, mesh, shuffle_cap)
        bshards.append(bs)
        ovf_b.append(ob)

    local_cap = cap or len(pshard) // n
    build_keys = [bkeys for _, bkeys in builds]
    in_specs = tuple(jax.tree.map(lambda _: P(AXIS), b)
                     for b in [pshard] + bshards)

    def local(pb: ColumnBatch, *bbs):
        out, needed = join_ops.multiway_join(
            pb, probe_keys, list(zip(bbs, build_keys)), hows, cap=local_cap,
            level_keys=level_keys, packs=packs)
        any_ovf = jax.lax.pmax(needed, AXIS) > local_cap
        return out, any_ovf

    locals_ = [_local_view(b, n) for b in [pshard] + bshards]
    out_probe = jax.eval_shape(
        lambda pb, *bbs: join_ops.multiway_join(
            pb, probe_keys, list(zip(bbs, build_keys)), hows,
            cap=local_cap, level_keys=level_keys, packs=packs)[0],
        *locals_)
    out_specs = (jax.tree.map(lambda _: P(AXIS), out_probe), P())
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out, ovf_j = fn(pshard, *bshards)
    return out, (ovf_p, ovf_b, ovf_j)


def dist_group_aggregate_shuffled(batch: ColumnBatch, key_names: list[str],
                                  specs: list[AggSpec], mesh,
                                  max_groups_per_shard: int,
                                  shuffle_cap: int | None = None):
    """High-cardinality GROUP BY: repartition rows by key hash, then one local
    sort-based group-by per shard (keys disjoint across shards — the MPP
    hash-agg plan the reference picks for big group counts)."""
    n = mesh.devices.size
    shard, ovf = dist_hash_repartition(batch, key_names, mesh, shuffle_cap)
    in_specs = jax.tree.map(lambda _: P(AXIS), shard)

    def local(b: ColumnBatch):
        out, g_ovf = group_aggregate_sorted(b, key_names, specs,
                                            max_groups_per_shard,
                                            with_overflow=True)
        any_ovf = jax.lax.psum(g_ovf.astype(jnp.int32), AXIS) > 0
        # num_rows is a per-shard scalar: drop it (sel carries liveness) so
        # every output leaf shards over AXIS uniformly
        return ColumnBatch(out.names, out.columns, out.sel, None), any_ovf

    # probe shapes via the collective-free kernel only
    probe = jax.eval_shape(
        lambda b: group_aggregate_sorted(b, key_names, specs,
                                         max_groups_per_shard),
        _local_view(shard, n))
    probe = ColumnBatch(probe.names, probe.columns, probe.sel, None)
    out_specs = (jax.tree.map(lambda _: P(AXIS), probe), P())
    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_specs,),
                       out_specs=out_specs, check_vma=False)
    out, group_ovf = fn(shard)
    return out, (ovf, group_ovf)
