"""Hand-written Pallas TPU kernels for mid-cardinality dense group-by.

Three lowerings cover the dense group-by (measured on v5e, 100M rows):

- ``num_groups <= 512``: XLA fused select+reduce (ops/segments.py) — one
  bandwidth-bound pass, ~1.5ms per segment.
- ``512 < num_groups <= PALLAS_MAX_GROUPS``: THESE kernels — the one-hot
  lives in VMEM as an MXU operand, so cost grows ~4x slower with group count
  than the select+reduce (~200ms at 512 groups where select+reduce takes
  ~850ms).
- beyond: scatter / sort strategies.

Mosaic constraints discovered on real hardware (every one of these failed
the remote compile until restructured):
- no 1-D intermediates: a ``(R,128)`` tile cannot reshape/broadcast through
  a flat ``(R*128,)`` vector; the one-hot is built per sublane-row from a
  ``(128, R)`` transpose instead, and each row's partials go to a distinct
  out_ref sublane.
- no 64-bit types anywhere in the traced kernel — the enclosing program
  runs in jax x64 mode, so the launcher traces under ``enable_x64(False)``.
- ``precision=HIGHEST`` is IGNORED by the Mosaic dot: f32 operands truncate
  to bf16 (relative error ~2^-8 per product).  Values are split into three
  bf16-exact components (8+8+8 significand bits) and contracted separately
  — products against a 0/1 one-hot are then exact, and so is each
  component's dot (a group meets a handful of the 128 lanes of a row).

A SUM over a FLOAT column is DOUBLE in this SQL, and the kernel has no
64-bit type: each block row's running sum is an f32 *pair*.  The leading
component's dot is added by an error-free TwoSum (Knuth: the add's rounding
error comes out exact), and that error, with the two small components'
dots, is gathered in a second f32 row, an output like the sum and not
scratch.  (A Kahan accumulator is not enough: it rounds ``delta -
compensation`` at every add, ~3e-8 of each value, which leaves 1e-9 over
the 15,000 rows of a group: PR 35 measured it.)  The pair's second row
stays accurate while it stays small, so the accumulators start afresh every
``CHUNK_STEPS`` grid steps: the output holds one block of rows a chunk.  The
launcher widens every row to float64 and adds chunks and block rows up
outside the kernel: ~1e-12 of a group's sum, the precision class the chip's
DOUBLE (an f32 pair) has everywhere else.  Counts leave as float64 too
(exact to 2^24 rows a block row, chunk and group inside the kernel).

The public entry points pad rows to full blocks with out-of-range codes
(their one-hot rows are all zero).  There is no other lowering behind them:
off the TPU the caller (ops/hashagg.py) stays on the segment reductions, and
``interpret=True`` runs the same kernels on CPU for tests only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

LANE = 128
R_BLOCK = 8                  # sublane rows per grid step = out_ref sublanes
PALLAS_MAX_GROUPS = 4096
# grid steps (of R_BLOCK * LANE = 1,024 rows) folded into one block of
# output rows: 2 M rows a chunk, 64 chunks over a 2^27-lane table
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


def _pair_sum(o_ref, row, erow, v_row, oh):
    """out[row] + out[erow] += v_row @ oh, as an f32 pair: the leading
    bf16-exact component's dot is TwoSum-added to out[row] and the add's
    exact rounding error goes to out[erow] with the two small components'
    dots (2^-8 and 2^-16 of the value: their own roundings there are
    below 1e-12 of the sum)."""
    a, b, c = (jnp.dot(part, oh, preferred_element_type=jnp.float32)
               for part in _bf16_split3(v_row))
    s = o_ref[row:row + 1, :]
    t = s + a
    bp = t - s
    err = (s - (t - bp)) + (a - bp)
    o_ref[erow:erow + 1, :] += err + (b + c)
    o_ref[row:row + 1, :] = t


def _sum_kernel(g_ref, v_ref, o_ref, *, ng: int):
    """counts -> o[0:8], sums -> o[8:16], the pairs' second rows ->
    o[16:24] (one sublane per block row)."""
    i = pl.program_id(0)

    @pl.when(i % CHUNK_STEPS == 0)
    def _init():
        o_ref[:, :] = jnp.zeros_like(o_ref)

    it = jax.lax.broadcasted_iota(jnp.int32, (LANE, ng), 1)
    gt = jnp.transpose(g_ref[:, :])                    # (LANE, R)
    ones = jnp.ones((1, LANE), jnp.float32)
    for r in range(R_BLOCK):
        oh = (gt[:, r:r + 1] == it).astype(jnp.float32)   # (LANE, ng)
        o_ref[r:r + 1, :] += jnp.dot(ones, oh,
                                     preferred_element_type=jnp.float32)
        _pair_sum(o_ref, 8 + r, 16 + r, v_ref[r:r + 1, :], oh)


def _agg_kernel(g_ref, v_ref, o_ref, *, ng: int):
    """counts/sums as _sum_kernel with the pairs' second rows -> o[32:40],
    plus mins -> o[16:24], maxs -> o[24:32]."""
    i = pl.program_id(0)

    @pl.when(i % CHUNK_STEPS == 0)
    def _init():
        o_ref[0:16, :] = jnp.zeros_like(o_ref[0:16, :])
        o_ref[16:24, :] = jnp.full_like(o_ref[16:24, :], _BIG)
        o_ref[24:32, :] = jnp.full_like(o_ref[24:32, :], -_BIG)
        o_ref[32:40, :] = jnp.zeros_like(o_ref[32:40, :])

    it = jax.lax.broadcasted_iota(jnp.int32, (LANE, ng), 1)
    gt = jnp.transpose(g_ref[:, :])
    vt = jnp.transpose(v_ref[:, :])
    ones = jnp.ones((1, LANE), jnp.float32)
    for r in range(R_BLOCK):
        hit = gt[:, r:r + 1] == it                        # (LANE, ng)
        oh = hit.astype(jnp.float32)
        o_ref[r:r + 1, :] += jnp.dot(ones, oh,
                                     preferred_element_type=jnp.float32)
        _pair_sum(o_ref, 8 + r, 32 + r, v_ref[r:r + 1, :], oh)
        vcol = vt[:, r:r + 1]                             # (LANE, 1)
        # typed f32 sentinel: the weak python float would promote the select
        # to f64 under the enclosing x64 program (Mosaic verifier rejects it)
        big = jnp.asarray(_BIG, jnp.float32)
        mins = jnp.min(jnp.where(hit, vcol, big), axis=0, keepdims=True)
        maxs = jnp.max(jnp.where(hit, vcol, -big), axis=0, keepdims=True)
        o_ref[16 + r:17 + r, :] = jnp.minimum(o_ref[16 + r:17 + r, :], mins)
        o_ref[24 + r:25 + r, :] = jnp.maximum(o_ref[24 + r:25 + r, :], maxs)


def _hist_kernel(g_ref, o_ref, *, ng: int):
    i = pl.program_id(0)

    @pl.when(i % CHUNK_STEPS == 0)
    def _init():
        o_ref[:, :] = jnp.zeros_like(o_ref)

    it = jax.lax.broadcasted_iota(jnp.int32, (LANE, ng), 1)
    gt = jnp.transpose(g_ref[:, :])
    ones = jnp.ones((1, LANE), jnp.float32)
    for r in range(R_BLOCK):
        oh = (gt[:, r:r + 1] == it).astype(jnp.float32)
        o_ref[r:r + 1, :] += jnp.dot(ones, oh,
                                     preferred_element_type=jnp.float32)


def _prep(codes, mask, num_groups, values=None):
    """Mask/pad to (steps*R_BLOCK, LANE) blocks; dead rows get code ng_pad
    (matches no one-hot lane, incl. the padding lanes we slice off)."""
    ng_pad = -(-num_groups // LANE) * LANE
    flat = R_BLOCK * LANE
    n = codes.shape[0]
    target = max(flat, -(-n // flat) * flat)
    g = codes.astype(jnp.int32)
    live = mask & (g >= 0) & (g < num_groups)
    # ng_pad must be a typed i32 constant: a weak python int promotes to i64
    # under the enclosing x64 program, and Mosaic's verifier rejects the
    # mixed-width select
    g = jnp.where(live, g, jnp.asarray(ng_pad, jnp.int32))
    if target != n:
        g = jnp.concatenate([g, jnp.full((target - n,), ng_pad, jnp.int32)])
    rows = target // LANE
    out = [g.reshape(rows, LANE)]
    if values is not None:
        v = jnp.where(live, values.astype(jnp.float32),
                      jnp.zeros((), jnp.float32))
        if target != n:
            v = jnp.concatenate([v, jnp.zeros((target - n,), jnp.float32)])
        out.append(v.reshape(rows, LANE))
    return out, rows // R_BLOCK, ng_pad


def _launch(kernel, rows_out: int, ins, steps: int, ng_pad: int,
            interpret: bool):
    """Run ``kernel`` over ``steps`` blocks of the prepared inputs.  ->
    [chunks, rows_out, ng_pad] f32: one block of output rows for each
    ``CHUNK_STEPS`` steps, resident in VMEM while its chunk runs."""
    chunks = -(-steps // CHUNK_STEPS)
    out = pl.pallas_call(
        functools.partial(kernel, ng=ng_pad),
        grid=(steps,),
        in_specs=[pl.BlockSpec((R_BLOCK, LANE), lambda i: (i, 0))] * len(ins),
        out_specs=pl.BlockSpec((rows_out, ng_pad),
                               lambda i: (i // CHUNK_STEPS, 0)),
        out_shape=jax.ShapeDtypeStruct((chunks * rows_out, ng_pad),
                                       jnp.float32),
        interpret=interpret,
    )(*ins)
    return out.reshape(chunks, rows_out, ng_pad)


def _total(*rows):
    """Rows of every chunk ([chunks, 8, ng] each) widened and added up in
    DOUBLE: a count's eight block rows, or a sum's pairs."""
    return sum(r.astype(jnp.float64).sum(axis=(0, 1)) for r in rows)


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def filtered_group_sum(codes, values, mask, num_groups: int,
                       interpret: bool = False):
    """Fused filter + dense group-by COUNT/SUM.

    codes: int [N]; values: [N] (read as f32); mask: bool [N].
    -> (counts [num_groups] f64, sums [num_groups] f64: the block rows'
    f32 pairs added up in DOUBLE).  Rows failing the mask or with
    out-of-range codes drop."""
    with jax.enable_x64(False):
        ins, steps, ng_pad = _prep(codes, mask, num_groups, values)
        out = _launch(_sum_kernel, 24, ins, steps, ng_pad, interpret)
    counts = _total(out[:, 0:8])
    sums = _total(out[:, 8:16], out[:, 16:24])
    return counts[:num_groups], sums[:num_groups]


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def fused_group_aggregate(codes, values, mask, num_groups: int,
                          interpret: bool = False):
    """Fused filter + dense group-by COUNT/SUM/MIN/MAX in ONE VMEM pass.

    -> (counts f64, sums f64, mins f32, maxs f32) [num_groups]; min/max
    lanes of empty groups hold +/-3.4e38 (count==0 marks them)."""
    with jax.enable_x64(False):
        ins, steps, ng_pad = _prep(codes, mask, num_groups, values)
        out = _launch(_agg_kernel, 40, ins, steps, ng_pad, interpret)
    counts = _total(out[:, 0:8])
    sums = _total(out[:, 8:16], out[:, 32:40])
    mins = jnp.minimum(out[:, 16:24].min(axis=(0, 1)), _BIG)
    maxs = jnp.maximum(out[:, 24:32].max(axis=(0, 1)), -_BIG)
    return (counts[:num_groups], sums[:num_groups],
            mins[:num_groups], maxs[:num_groups])


@functools.partial(jax.jit, static_argnames=("num_partitions", "interpret"))
def partition_histogram(dest, mask, num_partitions: int,
                        interpret: bool = False):
    """Rows per code (a dense group-by's COUNT(*), a hash shuffle's
    per-destination counts) as one MXU pass.  -> [num_partitions] f64,
    whole numbers."""
    with jax.enable_x64(False):
        ins, steps, ng_pad = _prep(dest, mask, num_partitions)
        out = _launch(_hist_kernel, 8, ins, steps, ng_pad, interpret)
    return _total(out)[:num_partitions]
