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
  — products against a 0/1 one-hot are then exact; a Kahan accumulator row
  in VMEM scratch compensates the cross-step f32 adds.

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
from jax.experimental.pallas import tpu as pltpu

LANE = 128
R_BLOCK = 8                  # sublane rows per grid step = out_ref sublanes
PALLAS_MAX_GROUPS = 4096

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


def _kahan_add(o_ref, comp_ref, row, crow, delta):
    """out[row] += delta, compensation tracked in scratch row ``crow``."""
    y = delta - comp_ref[crow:crow + 1, :]
    t = o_ref[row:row + 1, :] + y
    comp_ref[crow:crow + 1, :] = (t - o_ref[row:row + 1, :]) - y
    o_ref[row:row + 1, :] = t


def _sum_kernel(g_ref, v_ref, o_ref, comp_ref, *, ng: int):
    """counts -> o[0:8], sums -> o[8:16] (one sublane per block row)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[:, :] = jnp.zeros_like(o_ref)
        comp_ref[:, :] = jnp.zeros_like(comp_ref)

    it = jax.lax.broadcasted_iota(jnp.int32, (LANE, ng), 1)
    gt = jnp.transpose(g_ref[:, :])                    # (LANE, R)
    ones = jnp.ones((1, LANE), jnp.float32)
    for r in range(R_BLOCK):
        oh = (gt[:, r:r + 1] == it).astype(jnp.float32)   # (LANE, ng)
        o_ref[r:r + 1, :] += jnp.dot(ones, oh,
                                     preferred_element_type=jnp.float32)
        va, vb, vc = _bf16_split3(v_ref[r:r + 1, :])
        sm = (jnp.dot(va, oh, preferred_element_type=jnp.float32)
              + jnp.dot(vb, oh, preferred_element_type=jnp.float32)
              + jnp.dot(vc, oh, preferred_element_type=jnp.float32))
        _kahan_add(o_ref, comp_ref, 8 + r, r, sm)


def _agg_kernel(g_ref, v_ref, o_ref, comp_ref, *, ng: int):
    """counts/sums as _sum_kernel, plus mins -> o[16:24], maxs -> o[24:32]."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[0:16, :] = jnp.zeros_like(o_ref[0:16, :])
        o_ref[16:24, :] = jnp.full_like(o_ref[16:24, :], _BIG)
        o_ref[24:32, :] = jnp.full_like(o_ref[24:32, :], -_BIG)
        comp_ref[:, :] = jnp.zeros_like(comp_ref)

    it = jax.lax.broadcasted_iota(jnp.int32, (LANE, ng), 1)
    gt = jnp.transpose(g_ref[:, :])
    vt = jnp.transpose(v_ref[:, :])
    ones = jnp.ones((1, LANE), jnp.float32)
    for r in range(R_BLOCK):
        hit = gt[:, r:r + 1] == it                        # (LANE, ng)
        oh = hit.astype(jnp.float32)
        o_ref[r:r + 1, :] += jnp.dot(ones, oh,
                                     preferred_element_type=jnp.float32)
        va, vb, vc = _bf16_split3(v_ref[r:r + 1, :])
        sm = (jnp.dot(va, oh, preferred_element_type=jnp.float32)
              + jnp.dot(vb, oh, preferred_element_type=jnp.float32)
              + jnp.dot(vc, oh, preferred_element_type=jnp.float32))
        _kahan_add(o_ref, comp_ref, 8 + r, r, sm)
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

    @pl.when(i == 0)
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


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def filtered_group_sum(codes, values, mask, num_groups: int,
                       interpret: bool = False):
    """Fused filter + dense group-by COUNT/SUM.

    codes: int [N]; values: [N] (contracted as f32); mask: bool [N].
    -> (counts [num_groups] f32, sums [num_groups] f32).  Rows failing the
    mask or with out-of-range codes drop."""
    with jax.enable_x64(False):
        (g2, v2), steps, ng_pad = _prep(codes, mask, num_groups, values)
        out = pl.pallas_call(
            functools.partial(_sum_kernel, ng=ng_pad),
            grid=(steps,),
            in_specs=[pl.BlockSpec((R_BLOCK, LANE), lambda i: (i, 0))] * 2,
            out_specs=pl.BlockSpec((16, ng_pad), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((16, ng_pad), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, ng_pad), jnp.float32)],
            interpret=interpret,
        )(g2, v2)
    counts = out[0:8].astype(jnp.float64).sum(axis=0).astype(jnp.float32)
    sums = out[8:16].astype(jnp.float64).sum(axis=0).astype(jnp.float32)
    return counts[:num_groups], sums[:num_groups]


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def fused_group_aggregate(codes, values, mask, num_groups: int,
                          interpret: bool = False):
    """Fused filter + dense group-by COUNT/SUM/MIN/MAX in ONE VMEM pass.

    -> (counts, sums, mins, maxs) [num_groups] f32; min/max lanes of empty
    groups hold +/-3.4e38 (count==0 marks them)."""
    with jax.enable_x64(False):
        (g2, v2), steps, ng_pad = _prep(codes, mask, num_groups, values)
        out = pl.pallas_call(
            functools.partial(_agg_kernel, ng=ng_pad),
            grid=(steps,),
            in_specs=[pl.BlockSpec((R_BLOCK, LANE), lambda i: (i, 0))] * 2,
            out_specs=pl.BlockSpec((32, ng_pad), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((32, ng_pad), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, ng_pad), jnp.float32)],
            interpret=interpret,
        )(g2, v2)
    counts = out[0:8].astype(jnp.float64).sum(axis=0).astype(jnp.float32)
    sums = out[8:16].astype(jnp.float64).sum(axis=0).astype(jnp.float32)
    mins = jnp.minimum(out[16:24].min(axis=0), _BIG)
    maxs = jnp.maximum(out[24:32].max(axis=0), -_BIG)
    return (counts[:num_groups], sums[:num_groups],
            mins[:num_groups], maxs[:num_groups])


@functools.partial(jax.jit, static_argnames=("num_partitions", "interpret"))
def partition_histogram(dest, mask, num_partitions: int,
                        interpret: bool = False):
    """Per-destination row counts for a hash shuffle, as one MXU pass (sizes
    exchange capacities exactly so the repartition compiles with the right
    cap on the FIRST attempt)."""
    with jax.enable_x64(False):
        (g2,), steps, ng_pad = _prep(dest, mask, num_partitions)
        out = pl.pallas_call(
            functools.partial(_hist_kernel, ng=ng_pad),
            grid=(steps,),
            in_specs=[pl.BlockSpec((R_BLOCK, LANE), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, ng_pad), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, ng_pad), jnp.float32),
            interpret=interpret,
        )(g2)
    return out.astype(jnp.float64).sum(axis=0).astype(jnp.float32)[:num_partitions]
