"""Hand-written Pallas TPU kernels for mid-cardinality dense group-by.

Three lowerings cover the dense group-by (measured on v5e, 100M rows):

- ``num_groups <= 512``: XLA fused select+reduce (ops/segments.py) — one
  bandwidth-bound pass, ~1.5ms per segment.
- ``512 < num_groups <= PALLAS_MAX_GROUPS``: THESE kernels.
- beyond: scatter / sort strategies.

Cost model.  A grid step reads one block of 8 x 128 rows (codes, and values
where a column is aggregated).  Group ``g`` is ``(hi, lo) = (g // 128, g %
128)``: ``nh`` tiles of 128 groups, padded to ``hi_rows``, a multiple of
eight sublanes.  For each block row, COUNT and SUM are ONE MXU dot of a
two-level one-hot — ``[H; H*a; H*b; H*c] @ L``, where ``H`` is the
``(hi_rows, 128)`` one-hot of the row's tiles (sublane ``hi`` of lane ``j``
is 1 where row ``j`` falls in tile ``hi``), ``a``, ``b``, ``c`` the value's
bf16 components and ``L`` the ``(128, 128)`` one-hot of ``lo`` — whose
``(hi, lo)`` entry is the rows (the sum) of group ``hi * 128 + lo`` in that
block row.  The VPU builds ~``hi_rows / 2 + 32`` vregs a block row, and the
result lands lane-dense in ``(hi_rows, 128)`` tiles.  Nothing
``(128, num_groups)``-sized exists.  MIN and MAX are not linear: each one a
statement asks for is a dense compare-select-min over every (row, group)
pair, one ``(128, 128)`` group tile at a time (a static loop over ``hi``,
the tile's eight block rows folded before one sublane reduce) — ~3 vreg ops
for each of ``128 * 128 * nh`` pairs a block row, which is what the kernel
costs at 4,096 groups.  ``fused_group_aggregate`` is told statically which
of the two it computes; ``hashagg`` counts them (``agg_minmax_passes``).

Mosaic constraints discovered on real hardware (every one of these failed
the remote compile until restructured):
- no 1-D intermediates: a ``(R,128)`` tile cannot reshape/broadcast through
  a flat ``(R*128,)`` vector; a block row's codes go down the sublanes
  through a ``(128, R)`` transpose instead, and every partial is a 2-D
  tile or row of out_ref.
- no 64-bit types anywhere in the traced kernel — the enclosing program
  runs in jax x64 mode, so the launcher traces under ``enable_x64(False)``
  and every constant of the kernel is a typed i32 / f32 (a weak python
  number becomes 64-bit where the kernel is lowered).
- ``precision=HIGHEST`` is IGNORED by the Mosaic dot: f32 operands truncate
  to bf16 (relative error ~2^-8 per product).  Values are split into three
  bf16-exact components (8+8+8 significand bits) and contracted separately
  — products against a 0/1 one-hot are then exact, and so is each
  component's dot (a group meets a handful of the 128 lanes of a row).

A SUM over a FLOAT column is DOUBLE in this SQL, and the kernel has no
64-bit type: each block row's running sum is an f32 *pair*.  The leading
component's dot is added by an error-free TwoSum (Knuth: the add's rounding
error comes out exact), and that error, with the two small components'
dots, is gathered in a second f32 tile, an output like the sum and not
scratch.  (A Kahan accumulator is not enough: it rounds ``delta -
compensation`` at every add, ~3e-8 of each value, which leaves 1e-9 over
the 15,000 rows of a group: PR 35 measured it.)  The pair's second tile
stays accurate while it stays small, so the accumulators start afresh every
``CHUNK_STEPS`` grid steps: the output holds one block of tiles a chunk.
The launcher widens every tile to float64 and adds chunks and block rows up
outside the kernel: ~1e-12 of a group's sum, the precision class the chip's
DOUBLE (an f32 pair) has everywhere else.  Counts leave as float64 too
(exact to 2^24 rows a block row, chunk and group inside the kernel).

The public entry points pad rows to full blocks with a code past the last
tile (its one-hot rows are all zero).  There is no other lowering behind
them: off the TPU the caller (ops/hashagg.py) stays on the segment
reductions, and ``interpret=True`` runs the same kernels on CPU for tests
only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

LANE = 128
LOG2_LANE = 7
SUBLANE = 8                  # a tile's rows come in multiples of this
R_BLOCK = 8                  # block rows per grid step
PALLAS_MAX_GROUPS = 4096
# grid steps (of R_BLOCK * LANE = 1,024 rows) folded into one block of
# output tiles: 2 M rows a chunk, 64 chunks over a 2^27-lane table
CHUNK_STEPS = 2048

_BIG = 3.4e38                # python float (a jnp constant would be captured
#                              by the kernel closure, which pallas_call rejects)


def _bf16_split3(v):
    """Split f32 lanes into three bf16-exact f32 components (v = a+b+c).

    The Mosaic dot truncates f32 operands to bf16; contracting each
    component separately keeps every product against a 0/1 one-hot exact."""
    a = v.astype(jnp.bfloat16).astype(jnp.float32)
    r = v - a
    b = r.astype(jnp.bfloat16).astype(jnp.float32)
    c = r - b
    return a, b, c


def _i32(x: int):
    return jnp.asarray(x, jnp.int32)


def _contract(g_row, lo_col, v_row, hi_rows: int):
    """One block row's two-level one-hot contraction.  g_row: (1, LANE)
    codes; lo_col: (LANE, 1) the same codes' ``lo`` down the sublanes;
    v_row: (1, LANE) values or None.  -> (k * hi_rows, LANE) f32: rows of
    group ``hi * LANE + lo`` at ``[hi, lo]``, then (with values) the dots of
    the three bf16 components at ``[hi_rows + hi, lo]`` and so on."""
    tiles = jax.lax.broadcasted_iota(jnp.int32, (hi_rows, LANE), 0)
    hi = (tiles == jnp.right_shift(g_row, _i32(LOG2_LANE))).astype(
        jnp.float32)                                      # (hi_rows, LANE)
    lhs = hi if v_row is None else jnp.concatenate(
        [hi] + [hi * part for part in _bf16_split3(v_row)], axis=0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1)
    lo = (lo_col == lanes).astype(jnp.float32)            # (LANE, LANE)
    return jnp.dot(lhs, lo, preferred_element_type=jnp.float32)


def _init(o_ref, zeros: int, fills=()):
    """Start a chunk's accumulators: rows [0, zeros) at 0, then each
    ``(rows, value)`` of ``fills`` in turn."""
    @pl.when(pl.program_id(0) % CHUNK_STEPS == 0)
    def _():
        o_ref[0:zeros, :] = jnp.zeros((zeros, LANE), jnp.float32)
        at = zeros
        for rows, value in fills:
            o_ref[at:at + rows, :] = jnp.full((rows, LANE), value,
                                              jnp.float32)
            at += rows


def _count_sum(g_ref, v_ref, o_ref, hi_rows: int):
    """Every block row's counts -> tile r of o[0 : 8h], its sums -> tile r
    of o[8h : 16h] and the pairs' second tiles -> o[16h : 24h] (h =
    hi_rows); without v_ref the counts alone.  -> the codes and values
    transposed, (LANE, R_BLOCK) each (values None without v_ref)."""
    gt = jnp.transpose(g_ref[:, :])                       # (LANE, R)
    vt = None if v_ref is None else jnp.transpose(v_ref[:, :])
    lo_t = jnp.bitwise_and(gt, _i32(LANE - 1))
    h, blk = hi_rows, R_BLOCK * hi_rows
    for r in range(R_BLOCK):
        v_row = None if v_ref is None else v_ref[r:r + 1, :]
        res = _contract(g_ref[r:r + 1, :], lo_t[:, r:r + 1], v_row, h)
        at = r * h
        o_ref[at:at + h, :] += res[0:h]
        if v_ref is None:
            continue
        # TwoSum of the leading component into the running sum; its exact
        # rounding error and the two small components' dots (2^-8 and
        # 2^-16 of the value: their own roundings there are below 1e-12 of
        # the sum) go to the pair's second tile
        a = res[h:2 * h]
        s = o_ref[blk + at:blk + at + h, :]
        t = s + a
        bp = t - s
        err = (s - (t - bp)) + (a - bp)
        o_ref[2 * blk + at:2 * blk + at + h, :] += \
            err + (res[2 * h:3 * h] + res[3 * h:4 * h])
        o_ref[blk + at:blk + at + h, :] = t
    return gt, vt


def _minmax(gt, vt, o_ref, at: int, nh: int, is_min: bool):
    """One dense pass for MIN (or MAX) over the step's rows: for each of
    the ``nh`` group tiles, ``where(hit, v, empty)`` over its (row, group)
    pairs, the eight block rows folded elementwise, one sublane reduce,
    into row ``at + hi`` of o."""
    pick, fold = (jnp.minimum, jnp.min) if is_min else (jnp.maximum, jnp.max)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1)
    # typed f32 sentinel: the weak python float would promote the select
    # to f64 under the enclosing x64 program (Mosaic verifier rejects it)
    empty = jnp.asarray(_BIG if is_min else -_BIG, jnp.float32)
    for hi in range(nh):
        groups = lanes + _i32(hi * LANE)                  # this tile's codes
        acc = None
        for r in range(R_BLOCK):
            part = jnp.where(gt[:, r:r + 1] == groups, vt[:, r:r + 1], empty)
            acc = part if acc is None else pick(acc, part)
        row = o_ref[at + hi:at + hi + 1, :]
        o_ref[at + hi:at + hi + 1, :] = pick(
            row, fold(acc, axis=0, keepdims=True))


def _sum_kernel(g_ref, v_ref, o_ref, *, hi_rows: int):
    """counts, sums, the pairs' second tiles (``_count_sum``)."""
    _init(o_ref, 3 * R_BLOCK * hi_rows)
    _count_sum(g_ref, v_ref, o_ref, hi_rows)


def _agg_kernel(g_ref, v_ref, o_ref, *, nh: int, hi_rows: int,
                want_min: bool, want_max: bool):
    """``_sum_kernel``'s tiles, then one tile of mins (if asked) and one of
    maxs (if asked), hi_rows rows each."""
    at = 3 * R_BLOCK * hi_rows
    fills = [(hi_rows, v) for v, want in ((_BIG, want_min),
                                          (-_BIG, want_max)) if want]
    _init(o_ref, at, fills)
    gt, vt = _count_sum(g_ref, v_ref, o_ref, hi_rows)
    if want_min:
        _minmax(gt, vt, o_ref, at, nh, True)
        at += hi_rows
    if want_max:
        _minmax(gt, vt, o_ref, at, nh, False)


def _hist_kernel(g_ref, o_ref, *, hi_rows: int):
    _init(o_ref, R_BLOCK * hi_rows)
    _count_sum(g_ref, None, o_ref, hi_rows)


def _prep(codes, mask, num_groups, values=None):
    """Mask/pad to (steps*R_BLOCK, LANE) blocks.  -> (inputs, steps, nh,
    hi_rows): ``nh`` tiles of LANE groups, padded to ``hi_rows``.  Dead
    rows get the first code past the padded tiles: its ``hi`` matches no
    tile of the one-hot, and the MIN/MAX passes compare no tile past
    ``nh``."""
    nh = -(-num_groups // LANE)
    hi_rows = -(-nh // SUBLANE) * SUBLANE
    flat = R_BLOCK * LANE
    n = codes.shape[0]
    target = max(flat, -(-n // flat) * flat)
    g = codes.astype(jnp.int32)
    live = mask & (g >= 0) & (g < num_groups)
    # the dead code must be a typed i32 constant: a weak python int promotes
    # to i64 under the enclosing x64 program, and Mosaic's verifier rejects
    # the mixed-width select
    dead = jnp.asarray(hi_rows * LANE, jnp.int32)
    g = jnp.where(live, g, dead)
    if target != n:
        g = jnp.concatenate([g, jnp.full((target - n,), dead, jnp.int32)])
    rows = target // LANE
    out = [g.reshape(rows, LANE)]
    if values is not None:
        v = jnp.where(live, values.astype(jnp.float32),
                      jnp.zeros((), jnp.float32))
        if target != n:
            v = jnp.concatenate([v, jnp.zeros((target - n,), jnp.float32)])
        out.append(v.reshape(rows, LANE))
    return out, rows // R_BLOCK, nh, hi_rows


def _launch(kernel, tiles: int, ins, steps: int, hi_rows: int,
            interpret: bool, **static):
    """Run ``kernel`` over ``steps`` blocks of the prepared inputs.  ->
    [chunks, tiles * hi_rows, LANE] f32: one block of ``tiles`` output
    tiles for each ``CHUNK_STEPS`` steps, resident in VMEM while its chunk
    runs."""
    chunks = -(-steps // CHUNK_STEPS)
    rows_out = tiles * hi_rows
    out = pl.pallas_call(
        functools.partial(kernel, hi_rows=hi_rows, **static),
        grid=(steps,),
        in_specs=[pl.BlockSpec((R_BLOCK, LANE), lambda i: (i, 0))] * len(ins),
        out_specs=pl.BlockSpec((rows_out, LANE),
                               lambda i: (i // CHUNK_STEPS, 0)),
        out_shape=jax.ShapeDtypeStruct((chunks * rows_out, LANE),
                                       jnp.float32),
        interpret=interpret,
    )(*ins)
    return out.reshape(chunks, rows_out, LANE)


def _total(out, hi_rows: int, *firsts: int):
    """The R_BLOCK tiles from tile ``first`` on (a quantity's block rows),
    for each of ``firsts``, of every chunk, widened and added up in DOUBLE:
    a count's block rows, or a sum's pairs.  -> [hi_rows * LANE]."""
    chunks = out.shape[0]
    total = 0
    for first in firsts:
        at = first * hi_rows
        part = out[:, at:at + R_BLOCK * hi_rows].reshape(
            chunks, R_BLOCK, hi_rows * LANE)
        total = total + part.astype(jnp.float64).sum(axis=(0, 1))
    return total


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def filtered_group_sum(codes, values, mask, num_groups: int,
                       interpret: bool = False):
    """Fused filter + dense group-by COUNT/SUM.

    codes: int [N]; values: [N] (read as f32); mask: bool [N].
    -> (counts [num_groups] f64, sums [num_groups] f64: the block rows'
    f32 pairs added up in DOUBLE).  Rows failing the mask or with
    out-of-range codes drop."""
    with jax.enable_x64(False):
        ins, steps, _, hi_rows = _prep(codes, mask, num_groups, values)
        out = _launch(_sum_kernel, 3 * R_BLOCK, ins, steps, hi_rows,
                      interpret)
    counts = _total(out, hi_rows, 0)
    sums = _total(out, hi_rows, R_BLOCK, 2 * R_BLOCK)
    return counts[:num_groups], sums[:num_groups]


@functools.partial(jax.jit, static_argnames=("num_groups", "want_min",
                                             "want_max", "interpret"))
def fused_group_aggregate(codes, values, mask, num_groups: int,
                          want_min: bool = True, want_max: bool = True,
                          interpret: bool = False):
    """Fused filter + dense group-by COUNT/SUM, and MIN / MAX where asked,
    in ONE VMEM pass.

    -> (counts f64, sums f64, mins f32 or None, maxs f32 or None)
    [num_groups]; a MIN / MAX not asked for is None (and costs nothing);
    min/max lanes of empty groups hold +/-3.4e38 (count==0 marks them)."""
    with jax.enable_x64(False):
        ins, steps, nh, hi_rows = _prep(codes, mask, num_groups, values)
        tiles = 3 * R_BLOCK + int(want_min) + int(want_max)
        out = _launch(_agg_kernel, tiles, ins, steps, hi_rows, interpret,
                      nh=nh, want_min=want_min, want_max=want_max)
    counts = _total(out, hi_rows, 0)
    sums = _total(out, hi_rows, R_BLOCK, 2 * R_BLOCK)
    at = 3 * R_BLOCK * hi_rows
    found = []
    for want, fold in ((want_min, jnp.min), (want_max, jnp.max)):
        if want:
            found.append(fold(out[:, at:at + hi_rows], axis=0)
                         .reshape(-1)[:num_groups])
            at += hi_rows
        else:
            found.append(None)
    return (counts[:num_groups], sums[:num_groups], *found)


@functools.partial(jax.jit, static_argnames=("num_partitions", "interpret"))
def partition_histogram(dest, mask, num_partitions: int,
                        interpret: bool = False):
    """Rows per code (a dense group-by's COUNT(*), a hash shuffle's
    per-destination counts) as one MXU pass.  -> [num_partitions] f64,
    whole numbers."""
    with jax.enable_x64(False):
        ins, steps, _, hi_rows = _prep(dest, mask, num_partitions)
        out = _launch(_hist_kernel, R_BLOCK, ins, steps, hi_rows, interpret)
    return _total(out, hi_rows, 0)[:num_partitions]
