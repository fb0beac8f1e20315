"""Backend-adaptive segment reductions: the group-by primitive.

``jax.ops.segment_sum`` lowers to a row-serialized ``scatter-add`` on TPU —
measured ~880ms for 100M rows x 16 segments on v5e, ~1000x off the HBM
roofline — while on CPU the scatter loop is the *right* lowering.  The
reference hits the same fork: row-wise hash-table aggregation on the OLTP
path vs Arrow's vectorized hash-agg on the Acero path (src/exec/agg_node.cpp
vs the arrow declaration in the same file).  Here the fork is by backend,
decided at trace time:

- **TPU, num_segments <= ONEHOT_MAX_SEGMENTS**: a fused select+reduce — each
  segment's lane reduces ``where(gid == k, x, identity)`` over the row axis.
  XLA fuses the compare into the reduction (nothing materializes in HBM; an
  einsum against a one-hot does NOT fuse — XLA allocates the full
  ``[n, k]`` one-hot, 54GB at 100M x 17 x f64), so the pass is one
  bandwidth-bound read of the data plus ~1.5ms of VPU work per segment per
  100M rows.  Accumulation is exact-width (int sums in the integer dtype,
  wrapping exactly like the scatter path; float sums in f64), so results are
  in the same rounding class as ``jax.ops.segment_*``.
- **CPU or large num_segments**: ``jax.ops.segment_*`` scatter, unchanged.
  The ~512-segment crossover is where per-segment VPU work meets the
  scatter's fixed ~8.8ns/row cost (both measured on v5e).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ONEHOT_MAX_SEGMENTS = 512


def _onehot_backend() -> bool:
    return jax.default_backend() not in ("cpu",)


def _use_onehot(num_segments: int) -> bool:
    return _onehot_backend() and num_segments <= ONEHOT_MAX_SEGMENTS


def seg_sum(x, gid, num_segments: int):
    """Drop-in ``jax.ops.segment_sum(x, gid, num_segments=...)``.

    Out-of-range ids drop, matching scatter-mode="drop" semantics.  The
    select+reduce path handles 1-D data; multi-dim ``x`` (e.g. kmeans
    centroid sums over [n, d] vectors) stays on the scatter path."""
    if x.ndim != 1 or not _use_onehot(num_segments):
        return jax.ops.segment_sum(x, gid, num_segments=num_segments)
    dt = x.dtype
    acc = jnp.float64 if dt.kind == "f" else dt
    if dt == jnp.bool_:
        acc = jnp.int64
    k = jax.lax.broadcasted_iota(jnp.int32, (1, num_segments), 1)
    hit = gid[:, None] == k
    out = jnp.sum(jnp.where(hit, x[:, None].astype(acc),
                            jnp.zeros((), acc)), axis=0)
    return out.astype(dt) if dt != jnp.bool_ else out.astype(jnp.int32)


def _seg_extremum(x, gid, num_segments: int, is_min: bool):
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    if jnp.issubdtype(x.dtype, jnp.integer):
        info = jnp.iinfo(x.dtype)
        ident = info.max if is_min else info.min
    else:
        ident = jnp.inf if is_min else -jnp.inf
    if not _use_onehot(num_segments):
        f = jax.ops.segment_min if is_min else jax.ops.segment_max
        return f(x, gid, num_segments=num_segments)
    ident = jnp.asarray(ident, x.dtype)
    k = jax.lax.broadcasted_iota(jnp.int32, (1, num_segments), 1)
    masked = jnp.where(gid[:, None] == k, x[:, None], ident)
    return (jnp.min if is_min else jnp.max)(masked, axis=0)


def seg_min(x, gid, num_segments: int):
    """Drop-in ``jax.ops.segment_min`` (empty segments get dtype max/+inf)."""
    return _seg_extremum(x, gid, num_segments, True)


def seg_max(x, gid, num_segments: int):
    """Drop-in ``jax.ops.segment_max`` (empty segments get dtype min/-inf)."""
    return _seg_extremum(x, gid, num_segments, False)
