"""Equi-join kernels (reference: src/exec/join_node.cpp + joiner.cpp — hash
join build/probe, index nested-loop join; Acero hashjoin declaration).

A chasing hash table is hostile to the VPU, so the TPU design is a *sort
join*: sort the build side by key once, then probe with vectorized binary
search (``jnp.searchsorted``) — O(log n) fully-unrolled compare ladders that
XLA vectorizes across all probe rows.  Duplicate build keys are handled by
[lo, hi) match ranges plus an offset-inversion expansion (the static-shape
analog of emitting one output row per match).

Join keys: one column of any fixed-width type, or two int32-ish columns packed
into one int64.  String keys join on dictionary codes: ``join`` aligns the two
sides' dictionaries host-side (column/dictionary.merge) at trace time before
comparing codes.

NULL keys never match (SQL semantics); dead rows (sel=False) never match.
Output cardinality is static: ``cap`` rows (planner-estimated); an overflow
flag is returned so the executor can retry with a larger cap.
"""

from __future__ import annotations

from dataclasses import replace

import jax.numpy as jnp

from ..column.batch import Column, ColumnBatch
from ..column.dictionary import NULL_CODE, merge as dict_merge
from ..types import LType
from .sort import lexsort


def _align_string_keys(probe: ColumnBatch, probe_keys: list[str],
                       build: ColumnBatch, build_keys: list[str]):
    """Remap string key columns of both sides onto merged dictionaries so code
    equality == string equality.  Host work is O(|dict|), done at trace time."""

    def retag(batch, name, col):
        cols = list(batch.columns)
        cols[batch.names.index(name)] = col
        return ColumnBatch(batch.names, cols, batch.sel, batch.num_rows)

    for pk, bk in zip(probe_keys, build_keys):
        pc, bc = probe.column(pk), build.column(bk)
        if pc.ltype is not LType.STRING and bc.ltype is not LType.STRING:
            continue
        if pc.dictionary is None or bc.dictionary is None:
            raise ValueError(f"string join key {pk}/{bk} lacks a dictionary")
        if pc.dictionary is bc.dictionary or pc.dictionary._id == bc.dictionary._id:
            continue
        m, ra, rb = dict_merge(pc.dictionary, bc.dictionary)
        ta, tb = jnp.asarray(ra), jnp.asarray(rb)
        pd = jnp.where(pc.data >= 0, jnp.take(ta, jnp.clip(pc.data, 0, None), mode="clip"),
                       NULL_CODE)
        bd = jnp.where(bc.data >= 0, jnp.take(tb, jnp.clip(bc.data, 0, None), mode="clip"),
                       NULL_CODE)
        probe = retag(probe, pk, replace(pc, data=pd, dictionary=m))
        build = retag(build, bk, replace(bc, data=bd, dictionary=m))
    return probe, build


_PACK32_TYPES = (LType.BOOL, LType.INT8, LType.INT16, LType.INT32,
                 LType.UINT32, LType.DATE, LType.STRING)


def _key_array(batch: ColumnBatch, names: list[str],
               wide_keys_ok: bool = False):
    """Pack 1-2 key columns into a single sortable array + validity.

    ``wide_keys_ok``: the PLANNER verified (from statistics) that wider
    integer values fit 32-bit packing; without it, only types whose every
    value packs losslessly are accepted — an unbounded int64 must fail
    loudly, not alias silently."""
    cols = [batch.column(n) for n in names]
    valid = None
    for c in cols:
        if c.validity is not None:
            valid = c.validity if valid is None else (valid & c.validity)
    if len(cols) == 1:
        d = cols[0].data
        if d.dtype == jnp.bool_:
            d = d.astype(jnp.int32)
        return d, valid
    if len(cols) == 2:
        for c in cols:
            ok = c.ltype in _PACK32_TYPES or \
                (wide_keys_ok and c.ltype.is_integer)
            if not ok:
                raise ValueError(
                    "2-key sort-join requires 32-bit-packable keys "
                    "(or planner-verified bounds); demote to residual "
                    "equality otherwise")
        a = cols[0].data.astype(jnp.int64)
        b = cols[1].data.astype(jnp.int64)
        return (a << 32) | (b & jnp.int64(0xFFFFFFFF)), valid
    raise ValueError(">2 join key columns: planner must demote extras to "
                     "residual equality")


def _sentinel_max(dtype):
    return (jnp.iinfo if dtype.kind in "iu" else jnp.finfo)(dtype).max


def _build_dead(build: ColumnBatch, bvalid):
    """Dead mask for the build side: sel-dead or NULL-key rows."""
    dead = jnp.zeros(len(build), bool)
    if build.sel is not None:
        dead = dead | ~build.sel
    if bvalid is not None:
        dead = dead | ~bvalid
    return dead


def _probe_dead(probe: ColumnBatch, pvalid):
    """(sel_dead, dead): sel-dead alone, and sel-dead-or-NULL-key."""
    sel_dead = ~probe.sel if probe.sel is not None \
        else jnp.zeros(len(probe), bool)
    dead = sel_dead
    if pvalid is not None:
        dead = dead | ~pvalid
    return sel_dead, dead


def semi_join_neq(probe: ColumnBatch, probe_keys: list[str],
                  build: ColumnBatch, build_keys: list[str],
                  neq_probe: str, neq_build: str, how: str = "semi",
                  order=None):
    """[NOT] EXISTS with equality keys plus ONE ``build_col <> probe_col``
    residual — the TPC-H q21 shape — WITHOUT expanding the many-to-many
    match space.  For each probe row the residual-satisfying match count is

        #(key matches with build_col NOT NULL)  -  #(key, build_col=probe_col)

    both computable as range counts over ONE build array sorted by the
    packed (key, residual column): two extra binary searches instead of an
    output-cardinality join (the reference runs this as an expanded hash
    join + dedup, join_node.cpp — this path beats it asymptotically).
    Returns (out_batch, 0).  Key and residual columns must be 32-bit-safe
    (the planner checks)."""
    probe, build = _align_string_keys(probe, probe_keys, build, build_keys)
    pk, pvalid = _key_array(probe, probe_keys)
    bk, bvalid = _key_array(build, build_keys)
    a = probe.column(neq_probe)
    b = build.column(neq_build)

    bdead = _build_dead(build, bvalid)
    # rows whose residual column is NULL can never satisfy b <> a (NULL
    # comparisons are not TRUE): dead for BOTH counts
    if b.validity is not None:
        bdead = bdead | ~b.validity

    mask32 = jnp.int64(0xFFFFFFFF)
    pk2 = (bk.astype(jnp.int64) << 32) | (b.data.astype(jnp.int64) & mask32)
    base = pk.astype(jnp.int64) << 32
    pp = base | (a.data.astype(jnp.int64) & mask32)
    if order is not None:
        # host-precomputed per-version sort of the base table (the
        # secondary-index read): NO on-device sort.  Dead rows (filtered /
        # NULL) sit interspersed at their value positions; a prefix sum of
        # deadness converts value-range counts into LIVE counts
        pk2_sorted = pk2[order]
        dead_sorted = bdead[order].astype(jnp.int32)
        cum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(dead_sorted)])

        def live_range(lo_v, hi_v, lo_side, hi_side):
            lo = jnp.searchsorted(pk2_sorted, lo_v, side=lo_side)
            hi = jnp.searchsorted(pk2_sorted, hi_v, side=hi_side)
            return (hi - lo) - (cum[hi] - cum[lo])

        key_cnt = live_range(base, base | mask32, "left", "right")
        eq_cnt = live_range(pp, pp, "left", "right")
    else:
        order2 = lexsort((pk2, bdead))
        n_live = jnp.sum(~bdead).astype(jnp.int32)
        pk2_sorted = jnp.where(jnp.arange(len(build)) < n_live,
                               pk2[order2], _sentinel_max(pk2.dtype))
        first_dead = n_live.astype(jnp.int32)
        clamp = lambda x: jnp.minimum(x.astype(jnp.int32), first_dead)  # noqa: E731
        key_lo = clamp(jnp.searchsorted(pk2_sorted, base, side="left"))
        # upper bound via side="right" on the all-ones low word: adding
        # 2^32 would overflow int64 for a key at dtype max (the clamp
        # keeps a live key whose packed value EQUALS the sentinel correct)
        key_hi = clamp(jnp.searchsorted(pk2_sorted, base | mask32,
                                        side="right"))
        pp_lo = clamp(jnp.searchsorted(pk2_sorted, pp, side="left"))
        pp_hi = clamp(jnp.searchsorted(pk2_sorted, pp, side="right"))
        key_cnt = key_hi - key_lo
        eq_cnt = pp_hi - pp_lo

    psel_dead, pdead = _probe_dead(probe, pvalid)
    if a.validity is not None:
        pdead = pdead | ~a.validity      # a NULL: residual never TRUE
    counts = jnp.where(pdead, 0, key_cnt - eq_cnt)
    if how == "semi":
        return probe.and_sel(counts > 0), jnp.int32(0)
    if how == "anti":
        return probe.and_sel(counts == 0), jnp.int32(0)
    raise ValueError(f"semi_join_neq: unsupported how {how!r}")


def join(probe: ColumnBatch, probe_keys: list[str],
         build: ColumnBatch, build_keys: list[str],
         how: str = "inner", cap: int | None = None,
         suffix: str = "_r", wide_keys_ok: bool = False,
         build_sorted: bool = False, order=None):
    """Returns (out_batch, needed_rows).

    ``needed_rows`` (traced int32) is the true output cardinality; the caller
    retries with cap >= needed_rows when it exceeds ``cap`` (the static-shape
    overflow protocol — one exact retry instead of blind growth).

    how: inner | left | semi | anti.
    - semi/anti keep probe's capacity and just refine sel (no expansion;
      needed_rows is 0).
    - inner/left emit up to ``cap`` rows (default: probe capacity), pairing
      each probe row with every matching build row.
    Column names: probe names keep their own; clashing build names get suffix.
    """
    probe, build = _align_string_keys(probe, probe_keys, build, build_keys)
    pk, pvalid = _key_array(probe, probe_keys, wide_keys_ok)
    bk, bvalid = _key_array(build, build_keys, wide_keys_ok)

    # build side: order by (is_dead, key) — liveness primary — so live rows
    # form a contiguous sorted prefix of exactly n_live entries.  A sentinel
    # replaces the dead tail's keys to keep the array globally sorted; a LIVE
    # key equal to dtype-max still sorts before every dead row, so the
    # first-dead clamp below is exact for all key values
    bdead = _build_dead(build, bvalid)
    if order is not None:
        # host-precomputed per-version key permutation of the base table
        # (the secondary-index read): compose with a stable deadness
        # partition so filtered/NULL rows land in the tail — no on-device
        # sort at all
        from .compact import stable_partition

        o = jnp.asarray(order)
        order = o[stable_partition(~bdead[o])]
    elif build_sorted:
        # the planner proved the build side arrives key-sorted over its
        # LIVE rows (e.g. the output of a sorted group-by on exactly these
        # keys): a STABLE partition by deadness — O(n) prefix sums, no
        # bitonic sort — yields the same layout lexsort would
        from .compact import stable_partition

        order = stable_partition(~bdead)
    else:
        order = lexsort((bk, bdead))
    n_live = jnp.sum(~bdead).astype(jnp.int32)
    bk_sorted = jnp.where(jnp.arange(len(build)) < n_live,
                          bk[order], _sentinel_max(bk.dtype))

    lo = jnp.searchsorted(bk_sorted, pk, side="left")
    hi = jnp.searchsorted(bk_sorted, pk, side="right")
    psel_dead, pdead = _probe_dead(probe, pvalid)
    counts = jnp.where(pdead, 0, hi - lo)
    # drop matches that land in the dead tail (probe key == sentinel value)
    first_dead = n_live.astype(lo.dtype)
    counts = jnp.where(lo >= first_dead, 0, jnp.minimum(counts, first_dead - lo))

    if how == "semi":
        return probe.and_sel(counts > 0), jnp.int32(0)
    if how == "anti":
        return probe.and_sel(counts == 0), jnp.int32(0)

    def bidx_of(pi_c, k):
        bpos = lo[pi_c] + k                    # index into sorted build
        return order[jnp.clip(bpos, 0, len(build) - 1)]

    return _expand_matches(probe, build, how, cap, counts, psel_dead,
                           bidx_of, suffix)


def _expand_matches(probe: ColumnBatch, build: ColumnBatch, how: str,
                    cap: int | None, counts, psel_dead, bidx_of,
                    suffix: str):
    """Shared match-expansion machinery of every join kernel: per-probe
    match counts -> cumsum offsets -> output rows up to ``cap`` with the
    exact total reported for the retry protocol.  ``bidx_of(pi_c, k)``
    maps (probe row, match ordinal) -> build row index — the only part
    that differs between the globally-sorted and radix layouts."""
    if how == "left":
        # NULL-key probe rows still survive a LEFT JOIN (with NULL build side);
        # only sel-dead rows are dropped
        out_counts = jnp.maximum(counts, jnp.where(psel_dead, 0, 1))
    elif how == "inner":
        out_counts = counts
    else:
        raise ValueError(f"unknown join type {how}")

    if cap is None:
        cap = len(probe)
    offsets = jnp.cumsum(out_counts)
    total = (offsets[-1] if len(probe) else jnp.int32(0)).astype(jnp.int32)
    starts = offsets - out_counts
    # output row j -> probe row i = searchsorted(offsets, j, 'right')
    j = jnp.arange(cap)
    pi = jnp.searchsorted(offsets, j, side="right")
    pi_c = jnp.clip(pi, 0, len(probe) - 1)
    k = j - starts[pi_c]                      # match ordinal within probe row
    live_out = j < total
    matched = k < counts[pi_c]
    bidx = bidx_of(pi_c, k)

    out_p = probe.gather(pi_c, valid=None)
    bvalid_out = jnp.where(matched, True, False) & live_out
    out_b = build.gather(bidx, valid=None)

    names = list(out_p.names)
    cols = list(out_p.columns)
    for n, c in zip(out_b.names, out_b.columns):
        if how == "left":
            v = c.validity & bvalid_out if c.validity is not None else bvalid_out
            c = replace(c, validity=v)
        name = n if n not in names else n + suffix
        names.append(name)
        cols.append(c)
    out = ColumnBatch(tuple(names), cols, live_out, None)
    return out, total


def radix_join(probe: ColumnBatch, probe_keys: list[str],
               build: ColumnBatch, build_keys: list[str],
               how: str = "inner", cap: int | None = None,
               suffix: str = "_r", wide_keys_ok: bool = False,
               n_buckets: int = 256, width: int = 1024):
    """Hash-partitioned variant of ``join`` (reference: hash join,
    src/exec/join_node.cpp; ops/radix.py for the partition machinery).

    The build side partitions into ``n_buckets`` by key hash and sorts
    per-bucket (batched log^2(width) stages instead of one global
    log^2(n) bitonic); probes binary-search only their bucket.  Returns
    (out_batch, needed_rows, needed_width): ``needed_width`` reports the
    true max bucket occupancy — when it exceeds ``width`` (skew), the
    caller re-traces with a bigger width, the same contract as join caps.
    Semantics identical to ``join`` (inner/left/semi/anti, NULL handling,
    name suffixing)."""
    from .radix import radix_build, radix_probe

    probe, build = _align_string_keys(probe, probe_keys, build, build_keys)
    pk, pvalid = _key_array(probe, probe_keys, wide_keys_ok)
    bk, bvalid = _key_array(build, build_keys, wide_keys_ok)
    bdead = _build_dead(build, bvalid)
    sort_src, sort_keys, needed_width = radix_build(bk, bdead, n_buckets,
                                                    width)
    psel_dead, pdead = _probe_dead(probe, pvalid)
    b, lo, hi = radix_probe(pk, pdead, sort_keys, n_buckets)
    # clamp to each bucket's LIVE occupancy: live rows sort to the front of
    # their bucket row, so a probe key equal to the padding sentinel can't
    # overcount into the pad
    live_w = jnp.sum(sort_src < len(build), axis=1).astype(jnp.int32)
    lo = jnp.minimum(lo, live_w[b])
    hi = jnp.minimum(hi, live_w[b])
    counts = jnp.where(pdead, 0, hi - lo)

    if how == "semi":
        return probe.and_sel(counts > 0), jnp.int32(0), needed_width
    if how == "anti":
        return probe.and_sel(counts == 0), jnp.int32(0), needed_width

    flat_src = sort_src.reshape(-1)

    def bidx_of(pi_c, k):
        bpos = (b[pi_c].astype(jnp.int64) * width
                + lo[pi_c].astype(jnp.int64) + k)
        return jnp.clip(flat_src[jnp.clip(bpos, 0, flat_src.shape[0] - 1)],
                        0, len(build) - 1)

    out, total = _expand_matches(probe, build, how, cap, counts, psel_dead,
                                 bidx_of, suffix)
    return out, total, needed_width


def _align_multiway_strings(probe: ColumnBatch, level_keys: list[list[str]],
                            builds: list):
    """Align string key columns of the probe and EVERY build side onto one
    shared code space.  ``level_keys[i]`` holds side i's probe key columns
    (identical lists in the one-shared-key shape; per-level columns under
    the keyed exchange scheduler — sides on different probe columns simply
    never interact).  Two passes: the first grows the probe's dictionary
    to the union of all sides; the second re-aligns each build against that
    union (a second merge with a subset is value-stable, so every side ends
    up comparing codes in the same space — a single probe column compared
    against N independently-dictionaried builds must not stop at pairwise
    merges, or build_1's codes would be stale after build_2 widened the
    probe's dictionary)."""
    for i, (bb, bk) in enumerate(builds):
        probe, bb = _align_string_keys(probe, level_keys[i], bb, bk)
        builds[i] = (bb, bk)
    for i, (bb, bk) in enumerate(builds):
        probe, bb = _align_string_keys(probe, level_keys[i], bb, bk)
        builds[i] = (bb, bk)
    return probe, builds


def multiway_join(probe: ColumnBatch, probe_keys: list[str],
                  builds: list, hows: list[str],
                  cap: int | None = None, suffix: str = "_r",
                  wide_keys_ok: bool = False,
                  level_keys: list[list[str]] | None = None,
                  packs: list[bool] | None = None):
    """Fused multiway equi-join: ONE probe stream joined against N build
    sides in a single pass (the Efficient Multiway Hash Join shape;
    PAPERS.md).  Every level's key columns live ON THE PROBE STREAM:
    by default all levels share ``probe_keys`` (the PR 7 one-shared-key
    shape); ``level_keys[i]`` gives level i its own probe columns (the
    keyed exchange scheduler's mixed-key segments — co-location across
    levels is the SCHEDULER's proof, via equality classes, not this
    kernel's concern).

    ``builds``: list of (build_batch, build_key_names); ``hows[i]``:
    inner | left per level.  Semantically identical to the left-deep chain
    ``((probe ⋈ build_1) ⋈ build_2) ⋈ ...`` — each build side sorts by
    (deadness, key) once, the probe binary-searches every side, and the
    output expansion enumerates the cross product of per-side match ranges
    via one mixed-radix decode (last build fastest-varying, matching the
    chained expansion order).  The probe's key columns are packed/searched
    once per side but the probe rows themselves are materialized ONCE —
    no intermediate join result exists.

    Returns (out_batch, needed_rows): ``needed_rows`` is the exact fused
    output cardinality for the overflow retry protocol (int64 — a chain of
    expansions can overflow int32 counts)."""
    builds = list(builds)
    if level_keys is None:
        level_keys = [list(probe_keys)] * len(builds)
    if packs is None:
        packs = [wide_keys_ok] * len(builds)
    probe, builds = _align_multiway_strings(probe, level_keys, builds)
    psel_dead = ~probe.sel if probe.sel is not None \
        else jnp.zeros(len(probe), bool)

    per_side = []       # (oc, counts, lo, order, nbuild) per build
    pk_cache: dict = {}  # shared-key levels pack the probe columns ONCE
    for (bb, bkeys), how, pkeys, wide in zip(builds, hows, level_keys,
                                             packs):
        ck = (tuple(pkeys), bool(wide))
        if ck not in pk_cache:
            pk_cache[ck] = _key_array(probe, pkeys, wide)
        pk, pvalid = pk_cache[ck]
        pdead = psel_dead if pvalid is None else (psel_dead | ~pvalid)
        bk, bvalid = _key_array(bb, bkeys, wide)
        bdead = _build_dead(bb, bvalid)
        order = lexsort((bk, bdead))
        n_live = jnp.sum(~bdead).astype(jnp.int32)
        bk_sorted = jnp.where(jnp.arange(len(bb)) < n_live,
                              bk[order], _sentinel_max(bk.dtype))
        lo = jnp.searchsorted(bk_sorted, pk, side="left")
        hi = jnp.searchsorted(bk_sorted, pk, side="right")
        counts = jnp.where(pdead, 0, hi - lo)
        first_dead = n_live.astype(lo.dtype)
        counts = jnp.where(lo >= first_dead, 0,
                           jnp.minimum(counts, first_dead - lo))
        if how == "left":
            # NULL-key probe rows still survive (NULL build side); only
            # sel-dead probe rows are dropped — the binary-join contract
            oc = jnp.maximum(counts, jnp.where(psel_dead, 0, 1))
        elif how == "inner":
            oc = counts
        else:
            raise ValueError(f"multiway_join: unsupported how {how!r}")
        per_side.append((oc, counts, lo, order, len(bb)))

    out_counts = jnp.ones(len(probe), jnp.int64)
    for oc, _c, _lo, _o, _n in per_side:
        out_counts = out_counts * oc.astype(jnp.int64)

    if cap is None:
        cap = len(probe)
    if cap > 0x7FFF0000:
        # the overflow-retry loop feeds the int64 needed_rows back as the
        # next cap; the int32 expansion below cannot index past 2^31 (and
        # a 2-billion-row static batch would not fit regardless) — fail
        # with a clear message instead of wrapped indices
        raise ValueError(f"multiway_join cap {cap} exceeds the int32 "
                         "expansion range")
    offsets = jnp.cumsum(out_counts)
    total = (offsets[-1] if len(probe) else jnp.int64(0)).astype(jnp.int64)
    starts = offsets - out_counts
    # the EXPANSION arithmetic runs in int32: every live ordinal is
    # bounded by cap (rem = j - start < cap < 2^31), and per-side counts
    # are bounded by the build length.  Only the cumulative offsets /
    # ``total`` (the overflow flag — a chain of expansions can genuinely
    # exceed int32) stay int64; an output slot corrupted by the int32
    # clamp can only occur on a run whose flag already reports overflow,
    # and the session discards that output and retries.
    off32 = jnp.minimum(offsets, jnp.int64(0x7FFFFFF0)).astype(jnp.int32)
    st32 = jnp.minimum(starts, jnp.int64(0x7FFFFFF0)).astype(jnp.int32)
    j = jnp.arange(cap, dtype=jnp.int32)
    pi = jnp.searchsorted(off32, j, side="right")
    pi_c = jnp.clip(pi, 0, len(probe) - 1)
    k = j - st32[pi_c]
    live_out = j.astype(jnp.int64) < total

    # mixed-radix decode of the per-probe-row match ordinal: last build
    # varies fastest (== the chained left-deep expansion order)
    ordinals = [None] * len(per_side)
    rem = k
    for i in reversed(range(len(per_side))):
        oc_i = per_side[i][0][pi_c].astype(jnp.int32)
        d = jnp.maximum(oc_i, 1)
        ordinals[i] = rem % d
        rem = rem // d

    out_p = probe.gather(pi_c, valid=None)
    names = list(out_p.names)
    cols = list(out_p.columns)
    for (oc, counts, lo, order, nbuild), how, ki, (bb, _bk) in zip(
            per_side, hows, ordinals, builds):
        matched = ki < counts[pi_c].astype(jnp.int32)
        bpos = lo[pi_c].astype(jnp.int32) + ki
        bidx = order[jnp.clip(bpos, 0, max(nbuild - 1, 0))]
        out_b = bb.gather(jnp.clip(bidx, 0, max(nbuild - 1, 0)), valid=None)
        bvalid_out = matched & live_out
        for n, c in zip(out_b.names, out_b.columns):
            if how == "left":
                v = c.validity & bvalid_out if c.validity is not None \
                    else bvalid_out
                c = replace(c, validity=v)
            names.append(n if n not in names else n + suffix)
            cols.append(c)
    out = ColumnBatch(tuple(names), cols, live_out, None)
    return out, total


def _dense_slots(batch: ColumnBatch, keys: list[str],
                 los: list[int], spans: list[int]):
    """Row -> slot in the row-major product space of the key domains,
    plus an in-domain/valid mask (NULL or out-of-bounds keys excluded)."""
    slot = jnp.zeros(len(batch), jnp.int32)
    ok = jnp.ones(len(batch), bool)
    stride = 1
    for k, lo, sp in reversed(list(zip(keys, los, spans))):
        c = batch.column(k)
        # bounds-check in int64 BEFORE narrowing: a value beyond int32 (or
        # an int32 subtraction that would wrap) must fall out of domain,
        # not alias a slot after truncation
        wide = c.data.astype(jnp.int64) - lo
        ok = ok & (wide >= 0) & (wide < sp)
        if c.validity is not None:
            ok = ok & c.validity
        slot = slot + jnp.where(ok, wide, 0).astype(jnp.int32) * stride
        stride *= sp
    return slot, ok


def dense_join(probe: ColumnBatch, probe_keys: list[str],
               build: ColumnBatch, build_keys: list[str],
               los: list[int], spans: list[int], how: str = "inner",
               suffix: str = "_r"):
    """PK-FK join over a dense integer key domain — the TPU-native hash
    join.  When the build side's key (or composite key) is UNIQUE
    (primary/unique index) with host statistics bounding each column to
    [lo, lo+span), the hash table degenerates to a dense position table
    over the product space: one scatter builds it, one gather probes it.
    No sort, no binary-search ladder, and — because a unique build key
    means at most one match per probe row — the output keeps the probe's
    static shape: no expansion, no overflow/retry protocol.  This is the
    join the MXU-era plan wants for every TPC-H PK-FK edge (the
    reference's JoinTypeAnalyzer picking index-join over hash-join,
    src/physical_plan/join_type_analyzer.cpp).

    Returns (out_batch, 0) — the 0 matching the no-retry contract of
    semi/anti in ``join``.
    """
    probe, build = _align_string_keys(probe, probe_keys, build, build_keys)
    size = 1
    for sp in spans:
        size *= sp

    slot_b, ok_b = _dense_slots(build, build_keys, los, spans)
    if build.sel is not None:
        ok_b = ok_b & build.sel
    # dead / out-of-domain rows scatter into the spillway slot `size`
    table = jnp.full((size + 1,), -1, jnp.int32)
    table = table.at[jnp.where(ok_b, slot_b, size)].set(
        jnp.arange(len(build), dtype=jnp.int32), mode="drop")

    psel_dead = ~probe.sel if probe.sel is not None \
        else jnp.zeros(len(probe), bool)
    slot_p, ok_p = _dense_slots(probe, probe_keys, los, spans)
    in_dom = ok_p & ~psel_dead
    bidx = table[jnp.clip(slot_p, 0, size - 1)]
    matched = in_dom & (bidx >= 0)

    if how == "semi":
        return probe.and_sel(matched), jnp.int32(0)
    if how == "anti":
        return probe.and_sel(~matched), jnp.int32(0)
    if how == "inner":
        sel = probe.sel_mask() & matched
    elif how == "left":
        # NULL-key probe rows survive a LEFT JOIN (with NULL build side);
        # only sel-dead rows are dropped
        sel = probe.sel_mask()
    else:
        raise ValueError(f"unknown dense join type {how}")

    out_b = build.gather(jnp.clip(bidx, 0, max(len(build) - 1, 0)),
                         valid=None)
    names = list(probe.names)
    cols = list(probe.columns)
    for n, c in zip(out_b.names, out_b.columns):
        v = c.validity & matched if c.validity is not None else matched
        cols.append(replace(c, validity=v))
        names.append(n if n not in names else n + suffix)
    return ColumnBatch(tuple(names), cols, sel, None), jnp.int32(0)


def cross_join(probe: ColumnBatch, build: ColumnBatch, cap: int | None = None,
               suffix: str = "_r"):
    """Cartesian product with static cap (reference: JoinNode without
    equality conditions falls back to nested loop)."""
    np_, nb = len(probe), len(build)
    if cap is None:
        cap = np_ * nb
    j = jnp.arange(cap)
    pi = j // nb
    bi = j % nb
    live = (j < np_ * nb)
    live = live & probe.sel_mask()[jnp.clip(pi, 0, np_ - 1)] & build.sel_mask()[jnp.clip(bi, 0, nb - 1)]
    out_p = probe.gather(jnp.clip(pi, 0, np_ - 1))
    out_b = build.gather(jnp.clip(bi, 0, nb - 1))
    names = list(out_p.names)
    cols = list(out_p.columns)
    for n, c in zip(out_b.names, out_b.columns):
        names.append(n if n not in names else n + suffix)
        cols.append(c)
    needed = jnp.int64(np_ * nb)     # full capacity, not live count: the
    # positional pi/bi mapping above needs cap >= np_*nb rows to be exact
    # (int64: a runaway cross product must report, not overflow, its size)
    return ColumnBatch(tuple(names), cols, live, None), needed
