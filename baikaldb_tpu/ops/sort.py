"""Sort / Top-K kernels (reference: src/exec/sort_node.cpp,
src/runtime/sorter.cpp, topn_sorter.cpp, Acero order_by declarations in
src/exec/select_manager_node.cpp:259-265).

Multi-key ORDER BY is a composition of stable single-key argsorts from the
least-significant key to the most-significant one (classic LSD radix-style
composition).  NULL ordering follows MySQL: NULLs first under ASC, last under
DESC.  Dead rows (sel=False) always sort to the end, so LIMIT after ORDER BY
is a static slice.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..column.batch import Column, ColumnBatch
from .compact import stable_partition

_I32_MAX = 0x7FFFFFFF


def _f32_word(x):
    """float32 -> int32 in the same order (IEEE bits, the magnitude of a
    negative flipped); -0 counts as 0 and every NaN as the one past +inf,
    as ``jnp.argsort`` has them."""
    x = jnp.where(x == 0, jnp.zeros((), x.dtype), x)
    x = jnp.where(jnp.isnan(x), jnp.full((), jnp.nan, x.dtype), x)
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ _I32_MAX, b)


def _i64_words(x):
    """int64 -> (high, low) int32 words, lexicographically in the same
    order: the low word is unsigned, so its top bit is flipped."""
    hi = (x >> 32).astype(jnp.int32)
    lo = (x & 0xFFFFFFFF).astype(jnp.uint32)
    return [hi, jax.lax.bitcast_convert_type(lo ^ jnp.uint32(0x80000000),
                                             jnp.int32)]


def _f64_is_a_pair() -> bool:
    return jax.default_backend() != "cpu"


def _f64_pair_words(x):
    """The words of a DOUBLE held as an f32 pair (an accelerator's float64):
    the float32 nearest ``x`` and what is left, each as :func:`_f32_word`.
    Exact for every value such a pair can hold."""
    hi = x.astype(jnp.float32)
    lo = jnp.where(jnp.isfinite(hi), x - hi.astype(jnp.float64), 0.0)
    return [_f32_word(hi), _f32_word(lo.astype(jnp.float32))]


def sort_words(x) -> list:
    """``x`` as int32 words, most significant first, whose lexicographic
    order is ``x``'s: what :func:`argsort` sorts by.  A sort whose
    comparator reads one int32 compiles for the TPU in ~12 s at 524,288
    rows where ``jnp.argsort`` takes 32 s for an int32 key (its index is
    an int64, which the chip carries as two u32 operands beside the
    stable sort's own), 171 s for a DOUBLE (compared as an emulated f32
    pair inside the comparator) — compile seconds read on the CPU sandbox
    for a described v5e, PR 33.  A DOUBLE on an accelerator is that f32
    pair, so its two halves are its words, exactly; the CPU, whose doubles
    are doubles, takes the words of the IEEE bits."""
    dt = x.dtype
    if dt == jnp.bool_ or (jnp.issubdtype(dt, jnp.integer)
                           and dt.itemsize < 4):
        return [x.astype(jnp.int32)]
    if dt == jnp.int32:
        return [x]
    if dt == jnp.uint32:
        return [jax.lax.bitcast_convert_type(x ^ jnp.uint32(0x80000000),
                                             jnp.int32)]
    if dt == jnp.int64:
        return _i64_words(x)
    if dt == jnp.uint64:
        return _i64_words(jax.lax.bitcast_convert_type(
            x ^ jnp.uint64(1 << 63), jnp.int64))
    if dt == jnp.float64:
        if not _f64_is_a_pair():
            x = jnp.where(x == 0, 0.0, x)
            x = jnp.where(jnp.isnan(x), jnp.nan, x)
            b = jax.lax.bitcast_convert_type(x, jnp.int64)
            return _i64_words(jnp.where(b < 0, b ^ jnp.int64((1 << 63) - 1),
                                        b))
        return _f64_pair_words(x)
    if jnp.issubdtype(dt, jnp.floating):
        return [_f32_word(x.astype(jnp.float32))]
    raise TypeError(f"argsort: no sort words for {dt}")


def argsort(x, descending: bool = False):
    """Stable argsort of a 1-D array -> int32 permutation (ties keep their
    order, ascending or descending, as ``jnp.argsort(stable=True)``).  One
    pass a word from the least significant up, each an unstable sort of
    (word, row) with the row as second key; a boolean key is a partition by
    prefix sums, no sort."""
    n = x.shape[0]
    if x.dtype == jnp.bool_:
        return stable_partition(x if descending else ~x)
    perm = None
    for w in reversed(sort_words(x)):
        if descending:
            w = ~w
        if perm is not None:
            w = w[perm]
        _, order = jax.lax.sort((w, jax.lax.iota(jnp.int32, n)), num_keys=2,
                                is_stable=False)
        perm = order if perm is None else perm[order]
    return perm


def lexsort(keys):
    """``jnp.lexsort`` (the last key is the primary one) with
    :func:`argsort`'s passes."""
    perm = None
    for k in keys:
        perm = argsort(k) if perm is None else perm[argsort(k[perm])]
    return perm


@dataclass(frozen=True)
class SortKey:
    name: str
    asc: bool = True


def _orderable(c: Column):
    d = c.data
    if d.dtype == jnp.bool_:
        d = d.astype(jnp.int32)
    return d


def sort_permutation(batch: ColumnBatch, keys: list[SortKey]):
    """Permutation putting rows in ORDER BY order, dead rows last."""
    n = len(batch)
    perm = jnp.arange(n, dtype=jnp.int32)
    for k in reversed(keys):
        c = batch.column(k.name)
        d = _orderable(c)[perm]
        # descending argsort (not negation: negation breaks for unsigned 0
        # wraparound and INT_MIN overflow)
        perm = perm[argsort(d, descending=not k.asc)]
        if c.validity is not None:
            v = c.validity[perm]
            # ASC: nulls first -> sort by validity ascending=False first
            keyv = v if k.asc else ~v
            perm = perm[argsort(keyv)]
    if batch.sel is not None:
        dead = ~batch.sel[perm]
        perm = perm[argsort(dead)]
    return perm


def sort_batch(batch: ColumnBatch, keys: list[SortKey]) -> ColumnBatch:
    perm = sort_permutation(batch, keys)
    out = batch.gather(perm)
    if batch.sel is not None:
        n = jnp.sum(batch.sel).astype(jnp.int32)
        out.sel = jnp.arange(len(batch)) < n
        out.num_rows = n
    return out


def top_k(batch: ColumnBatch, keys: list[SortKey], k: int) -> ColumnBatch:
    """ORDER BY + LIMIT k (reference: TopNSorter).  Full sort then static
    slice; the gather after slicing touches only k rows per column, so for
    k << N the HBM traffic is the sort keys, not the payload."""
    perm = sort_permutation(batch, keys)
    k = min(k, len(batch))
    perm_k = perm[:k]
    live = jnp.arange(k) < batch.live_count() if (batch.sel is not None) else None
    out = batch.gather(perm_k)
    if live is not None:
        out.sel = live
    return out
