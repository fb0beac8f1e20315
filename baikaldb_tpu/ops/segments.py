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
  bandwidth-bound read of the data plus VPU work per segment: at 2^27
  lanes the north-star statement (COUNT(*), SUM, AVG, MIN of a FLOAT) is
  72 ms + 2.06 ms a segment (PR 35's chip runs, 16 and 1,000 groups
  forced).  Accumulation is exact-width (int sums in the integer dtype,
  wrapping exactly like the scatter path; float sums in f64), so results are
  in the same rounding class as ``jax.ops.segment_*``.
- **CPU or large num_segments**: ``jax.ops.segment_*`` scatter, unchanged.
  At 2^27 lanes an int32 scatter of ones costs 0.9-1.0 s alone in a
  program (the Pallas arm's ``present`` until PR 36: PR 35's ledger line)
  and the north-star statement's eight scatters (five of them counts; one
  since PR 36) ~4.6 s each, 37-43 s a statement at any segment count
  (PR 35's forced runs), so the crossover
  is where the per-segment line meets the sum of a statement's scatters;
  512 is stated, not derived (ROADMAP S8 (3)).

Both of those index by group id.  The third GROUP BY strategy, ``stream``
(``ops/hashagg.group_aggregate_stream``, chosen by the planner when an
aggregate's live rows arrive in non-decreasing order of its one integer /
DATE key and the key's domain is past the select+reduce and Pallas sizes),
needs no id and no domain-sized output at all: equal keys are adjacent, so
every aggregate is a *segmented scan* over the lanes as they come —
:func:`seg_scan` below, elementwise passes only, on either backend.

Counting rows is the reduction every aggregate shares: ``present``,
``COUNT(*)``, every SUM / AVG / MIN / MAX's "has a value" mask.  The callers
in ops/hashagg.py reduce ones once a GROUP BY (``_group_rows``: ``seg_sum`` of
int32 ones over the selected lanes, and once more for each nullable column)
and read every count off that; this module reduces what it is handed.
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


# ----------------------------------------------------------------------
# segmented scan (the ``stream`` GROUP BY's primitive)

# lanes of one row of the blocked scan: the shifted passes run inside rows
# of this many lanes, a carry runs over the rows' totals
SCAN_BLOCK = 1024

_SCAN_OPS = {
    "add": jnp.add, "min": jnp.minimum, "max": jnp.maximum,
    # fill: a lane without a flag takes what the lanes before it hold
    "left": lambda prev, cur: prev,
}


def scan_identity(op: str, dtype):
    """What a lane that takes no part holds, for ``op`` over ``dtype``."""
    if op in ("add", "left"):
        return jnp.zeros((), dtype)
    info = (jnp.iinfo if jnp.issubdtype(dtype, jnp.integer)
            else jnp.finfo)(dtype)
    return jnp.asarray(info.max if op == "min" else info.min, dtype)


def shift_lanes(x, d: int, fill, axis: int = 0, reverse: bool = False):
    """``x`` moved ``d`` lanes along ``axis`` towards the higher index (the
    lower with ``reverse``): lane i holds what lane i-d held, the vacated
    lanes hold ``fill``."""
    cfg = [(0, 0, 0)] * x.ndim
    cfg[axis] = (-d, d, 0) if reverse else (d, -d, 0)
    return jax.lax.pad(x, jnp.asarray(fill, x.dtype), cfg)


def _scan_rows(flags, vals, ops, axis: int, reverse: bool):
    """Hillis-Steele passes along ``axis``: log2(lanes) shifted combines."""
    n = flags.shape[axis]
    d = 1
    while d < n:
        vals = tuple(
            jnp.where(flags, v, _SCAN_OPS[op](
                shift_lanes(v, d, scan_identity(op, v.dtype), axis, reverse),
                v))
            for v, op in zip(vals, ops))
        flags = flags | shift_lanes(flags, d, False, axis, reverse)
        d *= 2
    return flags, vals


def seg_scan(flags, vals, ops, reverse: bool = False):
    """Inclusive segmented scan over 1-D lanes, no gather and no scatter.

    ``flags[i]`` starts a segment at lane i; ``vals`` is a tuple of arrays
    scanned together, ``ops[j]`` in ``add | min | max | left`` combines
    ``vals[j]`` (``left``: an unflagged lane copies the lane before it, a
    fill).  Lane i of the result folds the lanes from its segment's flag
    to i; with ``reverse`` a segment runs from its flag down to lower
    indices.  A lane that should not count holds the op's
    :func:`scan_identity` and no flag.  Returns ``(seen, outs)``:
    ``seen[i]`` says some flag lies at or before lane i, which is what
    makes ``outs`` meaningful there.

    Blocked: the shifted passes run inside rows of ``SCAN_BLOCK`` lanes,
    then the rows' totals are scanned the same way and carried into the
    rows after them — 10 passes over the data whatever its length, where
    one flat Hillis-Steele scan would make 23 over 8.4 M lanes."""
    vals = tuple(vals)
    n = flags.shape[0]
    if n <= SCAN_BLOCK:
        return _scan_rows(flags, vals, ops, 0, reverse)
    rows = -(-n // SCAN_BLOCK)
    tail = rows * SCAN_BLOCK - n

    def blocked(x, fill):
        if tail:
            x = jnp.pad(x, (0, tail), constant_values=fill)
        return x.reshape(rows, SCAN_BLOCK)

    f2 = blocked(flags, False)
    v2 = tuple(blocked(v, scan_identity(op, v.dtype))
               for v, op in zip(vals, ops))
    f2, v2 = _scan_rows(f2, v2, ops, 1, reverse)
    edge = 0 if reverse else SCAN_BLOCK - 1
    cf, cv = seg_scan(f2[:, edge], tuple(v[:, edge] for v in v2), ops,
                      reverse)
    # what reaches a row is the scan up to the row before it
    cf = shift_lanes(cf, 1, False, 0, reverse)[:, None]
    outs = tuple(
        jnp.where(f2, v, _SCAN_OPS[op](
            shift_lanes(c, 1, scan_identity(op, c.dtype), 0,
                        reverse)[:, None], v))
        for v, c, op in zip(v2, cv, ops))
    seen = f2 | cf
    return seen.reshape(-1)[:n], tuple(o.reshape(-1)[:n] for o in outs)
