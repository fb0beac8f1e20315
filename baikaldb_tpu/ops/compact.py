"""Selection-mask materialization (mask -> dense prefix).

XLA requires static shapes, so filters refine a bool ``sel`` mask instead of
shrinking batches (SURVEY.md §7 hard part #3: dynamic result cardinality).
``compact`` stable-partitions live rows to the front and returns the same-
capacity batch plus a traced live count — the pattern the reference never
needs (Acero emits variable-length batches) but which keeps every downstream
kernel shape-static on TPU.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..column.batch import ColumnBatch


def compact(batch: ColumnBatch) -> ColumnBatch:
    """Move live rows to the front (stable); sets num_rows, clears sel."""
    if batch.sel is None and batch.num_rows is None:
        return batch
    if batch.sel is None:
        return batch
    if batch.live_prefix:
        # bucket-padded batches promise live rows already form a leading
        # prefix (sel == arange < live), so the argsort+gather is the
        # identity — just surface the count
        n = batch.live_count()
        return ColumnBatch(batch.names, batch.columns,
                           jnp.arange(len(batch)) < n, n, live_prefix=True)
    sel = batch.sel
    n = jnp.sum(sel).astype(jnp.int32)
    if len(batch) == 0:
        out = batch.gather(jnp.zeros((0,), jnp.int32))
        out.num_rows = n
        out.sel = jnp.zeros((0,), bool)
        return out
    # O(n) prefix-sum partition, not an O(n log n) stable argsort — same
    # live-first stable order, and the dominant cost of a selective point
    # read's final compact at full capacity
    order = stable_partition(sel)
    out = batch.gather(order)
    out.num_rows = n
    # rows past n keep stale data; mark them dead for any mask-aware consumer
    out.sel = jnp.arange(len(batch)) < n
    return out


def stable_partition(live) -> "jnp.ndarray":
    """Permutation moving live rows to the front, STABLY, via prefix sums
    and one scatter — O(n), no sort.  order[j] = source index of output
    row j; the live prefix preserves input order (so an input sorted over
    its live rows stays sorted)."""
    n = live.shape[0]
    if n == 0:      # an index gather that found no row: nothing to move
        return jnp.zeros(0, jnp.int32)
    nl = jnp.cumsum(live)
    dest = jnp.where(live, nl - 1, nl[-1] + jnp.cumsum(~live) - 1)
    return jnp.zeros(n, jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32))


def shrink(batch: ColumnBatch, cap: int):
    """Pack live rows into a batch of STATIC capacity ``cap`` (smaller than
    the input's), returning (packed batch, needed live count).

    The sel-mask architecture never compacts, so a selective join chain
    drags the base table's full capacity through every downstream operator
    — a 1.2M-lane gather/searchsorted per op for 10k live rows (the TPC-H
    q21 profile).  ``shrink`` is the capacity cut: one nonzero+gather pass,
    then everything above runs at ``cap``.  When the live count exceeds
    ``cap`` the caller's overflow-retry protocol re-traces with a bigger
    cap (same contract as the join cap flags).
    """
    if cap >= len(batch):
        return batch, jnp.int32(0)        # no cut possible: pass through
    sel = batch.sel
    if sel is None:
        n = jnp.int32(len(batch)) if batch.num_rows is None \
            else jnp.asarray(batch.num_rows, jnp.int32)
        sel = jnp.arange(len(batch)) < n
    # the j-th live row is where the running count first reaches j + 1: a
    # vectorized binary search of cap probes, not jnp.nonzero(size=cap),
    # whose bincount is a scatter-add of every input row (on the TPU a sort
    # of them all, and 78 s of compile at 8,388,608 rows against 2.5 s —
    # read on the CPU sandbox for a described v5e, PR 33)
    cs = jnp.cumsum(sel, dtype=jnp.int32)
    n = cs[-1]
    slot = jnp.arange(cap, dtype=jnp.int32)
    idx = jnp.where(slot < n,
                    jnp.searchsorted(cs, slot + 1).astype(jnp.int32), 0)
    out = batch.gather(idx)
    out.sel = jnp.arange(cap) < jnp.minimum(n, cap)
    out.num_rows = None
    return out, n


def head(batch: ColumnBatch, limit: int, offset: int = 0) -> ColumnBatch:
    """LIMIT/OFFSET over live rows (reference: src/exec/limit_node.cpp)."""
    b = compact(batch)
    n = b.live_count()
    idx = jnp.arange(len(b))
    keep = (idx >= offset) & (idx < jnp.minimum(n, offset + limit))
    return ColumnBatch(b.names, b.columns, keep, None)
