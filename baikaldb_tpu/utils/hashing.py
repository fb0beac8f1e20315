"""Vectorized integer hashing for join/shuffle/group keys.

The reference hashes join keys row-wise via ExprValue::hash (byte-wise
MurmurHash, include/common/expr_value.h) and partitions MPP exchange batches by
``hash(key) % partition_num`` (src/exec/exchange_sender_node.cpp).  Here keys
are already fixed-width lanes, so we use a murmur3-finalizer — a few int ops
per lane, fully vectorized on the VPU — and combine multiple key columns with
an xor-mix fold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def split64(x):
    """Split a 64-bit lane array to (lo, hi) uint32 halves, never touching a
    64-bit ``bitcast_convert``.

    TPU's X64-elimination pass cannot rewrite ``bitcast_convert`` involving
    64-bit element types AT ALL (it aborts compilation) — and ``jnp.frexp``
    / ``jnp.signbit`` of a float64 lower to exactly that.  So integers split
    arithmetically (mask + shift — ops the eliminator does rewrite) and
    float64 splits into its nearest float32 plus the float32 of what that
    left over: two 32-bit bitcasts.  For integers the result is bit-
    identical to a 64-bit bitcast; for floats it is a deterministic image of
    the top ~48 significand bits (all a v5e keeps of a DOUBLE anyway), which
    is all hashing needs — equal values map alike.  The limit: a value past
    the float32 range shares its sign's infinity image, and one below it
    (~1e-38) loses its remainder or shares the zero image.  The v5e holds no
    such DOUBLE; on the CPU a key column made of them repartitions onto one
    shard and counts as one value in the HLL sketch (APPROX_COUNT_DISTINCT
    under-counts them).  Exact joins and group-bys compare the keys
    themselves, so for them it is skew, never a wrong answer.
    """
    x = jnp.asarray(x)
    if x.dtype.kind == "f":
        hi = x.astype(jnp.float32)
        hi = jnp.where(jnp.isnan(hi), jnp.float32(jnp.nan), hi)  # one NaN image
        lo = jnp.where(jnp.isfinite(hi), x - hi.astype(x.dtype),
                       jnp.zeros((), x.dtype)).astype(jnp.float32)
        return lo.view(jnp.uint32), hi.view(jnp.uint32)
    lo = (x & jnp.asarray(0xFFFFFFFF, x.dtype)).astype(jnp.uint32)
    hi = ((x >> 32) & jnp.asarray(0xFFFFFFFF, x.dtype)).astype(jnp.uint32)
    return lo, hi


def _fold64(x):
    """Fold a 64-bit lane array to uint32 via split64 + xor-mix."""
    lo, hi = split64(x)
    return lo ^ hi * jnp.uint32(0x9E3779B9)


def _as_u32(x):
    """Reduce any fixed-width lane to uint32 (canonicalizing -0.0 and widths)."""
    x = jnp.asarray(x)
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    if x.dtype.kind == "f":
        x = jnp.where(x == 0, jnp.zeros_like(x), x)  # -0.0 == 0.0
        if x.dtype.itemsize == 8:
            return _fold64(x)
        return x.view(jnp.uint32)
    if x.dtype.itemsize == 8:
        return _fold64(x)
    return x.astype(jnp.uint32)


def mix32(x):
    """murmur3 fmix32: bijective avalanche on uint32 lanes."""
    x = jnp.asarray(x, jnp.uint32)
    x ^= x >> 16
    x = x * jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x = x * jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    return x


def hash_columns(arrays, seed: int = 0x12345678):
    """Combine N key arrays -> uint32 hash per row."""
    h = jnp.broadcast_to(jnp.uint32(seed & 0xFFFFFFFF), jnp.shape(arrays[0]))
    for a in arrays:
        h = mix32(h ^ mix32(_as_u32(a)))
    return h


def partition_ids(arrays, num_partitions: int):
    """Row -> partition id in [0, num_partitions), for MPP-style shuffle."""
    h = hash_columns(arrays)
    return (h % jnp.uint32(num_partitions)).astype(jnp.int32)
