"""Pallas kernel tests (interpret mode on the CPU; chip_smoke.py compiles
the same kernels on the TPU through SQL) — golden-checked against numpy."""

import numpy as np
import pytest

import jax.numpy as jnp

from baikaldb_tpu.ops.pallas_kernels import filtered_group_sum


def test_filtered_group_sum_matches_numpy():
    rng = np.random.default_rng(0)
    n, ng = 5000, 37
    codes = rng.integers(0, ng, n).astype(np.int32)
    values = rng.normal(size=n).astype(np.float32)
    mask = rng.random(n) > 0.4
    c1, s1 = filtered_group_sum(jnp.asarray(codes), jnp.asarray(values),
                                jnp.asarray(mask), ng,
                                interpret=True)
    c2 = np.bincount(codes[mask], minlength=ng)
    s2 = np.bincount(codes[mask], weights=values[mask], minlength=ng)
    assert np.array_equal(np.asarray(c1), c2)
    np.testing.assert_allclose(np.asarray(s1), s2, rtol=1e-4, atol=1e-4)


def test_all_filtered_and_empty_groups():
    codes = jnp.asarray(np.zeros(100, np.int32))
    values = jnp.asarray(np.ones(100, np.float32))
    mask = jnp.asarray(np.zeros(100, bool))
    c, s = filtered_group_sum(codes, values, mask, 4,
                              interpret=True)
    assert np.asarray(c).sum() == 0 and np.asarray(s).sum() == 0


def test_padding_rows_not_counted():
    # 100 rows, block 8*128=1024 -> heavy padding; all live
    codes = jnp.asarray(np.arange(100, dtype=np.int32) % 3)
    values = jnp.asarray(np.ones(100, np.float32))
    mask = jnp.asarray(np.ones(100, bool))
    c, s = filtered_group_sum(codes, values, mask, 3,
                              interpret=True)
    assert np.asarray(c).sum() == 100
    assert np.asarray(s).tolist() == np.asarray(c).tolist()


def test_fused_group_aggregate_interpret():
    from baikaldb_tpu.ops.pallas_kernels import fused_group_aggregate

    rng = np.random.default_rng(9)
    n, ng = 5000, 37
    codes = rng.integers(0, ng, n).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    mask = rng.random(n) < 0.7
    c, s, mn, mx = fused_group_aggregate(jnp.asarray(codes), jnp.asarray(vals),
                                         jnp.asarray(mask), ng,
                                         interpret=True)
    c, s, mn, mx = map(np.asarray, (c, s, mn, mx))
    for g in range(ng):
        live = vals[(codes == g) & mask]
        assert c[g] == len(live)
        assert abs(s[g] - live.sum()) < 1e-2
        if len(live):
            assert mn[g] == pytest.approx(live.min(), rel=1e-6)
            assert mx[g] == pytest.approx(live.max(), rel=1e-6)


def test_partition_histogram_interpret():
    from baikaldb_tpu.ops.pallas_kernels import partition_histogram

    rng = np.random.default_rng(4)
    n, p = 4000, 16
    dest = rng.integers(0, p, n).astype(np.int32)
    mask = rng.random(n) < 0.6
    h = np.asarray(partition_histogram(jnp.asarray(dest), jnp.asarray(mask),
                                       p, interpret=True))
    want = np.bincount(dest[mask], minlength=p)
    assert np.array_equal(h.astype(np.int64), want)


@pytest.mark.parametrize("chunk_steps, n", [(2048, 120_000), (16, 120_001),
                                            (7, 50_003)])
@pytest.mark.parametrize("kernel", ["sum", "fused"])
def test_float_sums_leave_the_kernel_as_f32_pairs(monkeypatch, kernel,
                                                  chunk_steps, n):
    """SUM over a FLOAT column is DOUBLE: each block row's running sum is
    an f32 pair (an error-free TwoSum and its error row), both outputs, one
    block of rows a chunk of grid steps, widened and added up outside the
    kernel (PR 35).  Values chosen so that a float32 running sum loses
    seven digits: the pairs keep twelve, over one chunk, over several with
    a ragged last one, and with the accumulators started afresh often."""
    from baikaldb_tpu.ops import pallas_kernels
    from baikaldb_tpu.ops.pallas_kernels import fused_group_aggregate

    monkeypatch.setattr(pallas_kernels, "CHUNK_STEPS", chunk_steps)
    rng = np.random.default_rng(35)
    ng = 29
    codes = rng.integers(0, ng, n).astype(np.int32)
    vals = (rng.standard_normal(n) * 3e3 + 1e4).astype(np.float32)
    mask = rng.random(n) < 0.8
    f = filtered_group_sum if kernel == "sum" else fused_group_aggregate
    c, s = f(jnp.asarray(codes), jnp.asarray(vals), jnp.asarray(mask), ng,
             interpret=True)[:2]
    assert c.dtype == jnp.float64 and s.dtype == jnp.float64
    want_c = np.bincount(codes[mask], minlength=ng)
    want_s = np.bincount(codes[mask], weights=vals[mask].astype(np.float64),
                         minlength=ng)
    assert np.array_equal(np.asarray(c), want_c)
    gap = np.abs(np.asarray(s) - want_s) / np.abs(want_s)
    assert gap.max() < 1e-12, gap.max()
    # the control: the same adds in one float32 a group
    low = np.zeros(ng, np.float32)
    np.add.at(low, codes[mask], vals[mask])
    assert (np.abs(low - want_s) / np.abs(want_s)).max() > 1e-8
