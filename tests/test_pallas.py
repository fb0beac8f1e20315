"""Pallas kernel tests (interpret mode on the CPU; chip_smoke.py compiles
the same kernels on the TPU through SQL) — golden-checked against numpy.

The group counts cover one tile of 128 groups (37), tiles padded to a
multiple of eight sublanes (600: 5 -> 8), and full 8 and 32 tiles (1000,
4000, 4096)."""

import numpy as np
import pytest

import jax.numpy as jnp

from baikaldb_tpu.ops.pallas_kernels import (filtered_group_sum,
                                             fused_group_aggregate,
                                             partition_histogram)

GROUP_COUNTS = [37, 600, 1000, 4000, 4096]


def _rows(ng: int, n: int = 3000, seed: int = 38):
    """Codes with every tile edge the group count has (127, 128, 4095, the
    last group), out-of-range codes (-1, ng, 4096), dead rows (the mask),
    and a row count that leaves the last block padded.  Values ~1e4, so a
    sum's relative gap is a fair test.  -> (codes, values, mask, live)."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, ng, n).astype(np.int32)
    edges = [c for c in (0, 127, 128, 4095, ng - 1) if c < ng]
    codes[:len(edges)] = edges
    codes[-3:] = [-1, ng, 4096]
    values = (rng.standard_normal(n) * 3e3 + 1e4).astype(np.float32)
    mask = rng.random(n) < 0.7
    mask[:len(edges)] = True
    mask[-3:] = True
    live = mask & (codes >= 0) & (codes < ng)
    return codes, values, mask, live


def _want(ng, codes, values, live):
    c = np.bincount(codes[live], minlength=ng)
    s = np.bincount(codes[live], weights=values[live].astype(np.float64),
                    minlength=ng)
    return c, s


def _sum_gap(got, want, counts):
    ok = counts > 0
    assert not np.asarray(got)[~ok].any()
    return (np.abs(np.asarray(got)[ok] - want[ok]) / want[ok]).max()


@pytest.mark.parametrize("ng", GROUP_COUNTS)
def test_filtered_group_sum_matches_numpy(ng):
    codes, values, mask, live = _rows(ng)
    c, s = filtered_group_sum(jnp.asarray(codes), jnp.asarray(values),
                              jnp.asarray(mask), ng, interpret=True)
    want_c, want_s = _want(ng, codes, values, live)
    assert np.array_equal(np.asarray(c), want_c)
    assert _sum_gap(s, want_s, want_c) < 1e-12


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


@pytest.mark.parametrize("want_min, want_max",
                         [(False, False), (True, False), (False, True),
                          (True, True)],
                         ids=["neither", "min", "max", "both"])
@pytest.mark.parametrize("ng", GROUP_COUNTS)
def test_fused_group_aggregate_interpret(ng, want_min, want_max):
    """Counts exact, sums within 1e-12 of numpy's float64, MIN and MAX the
    float32 they are — each computed only where asked (else None)."""
    codes, vals, mask, live = _rows(ng)
    c, s, mn, mx = fused_group_aggregate(
        jnp.asarray(codes), jnp.asarray(vals), jnp.asarray(mask), ng,
        want_min=want_min, want_max=want_max, interpret=True)
    want_c, want_s = _want(ng, codes, vals, live)
    assert np.array_equal(np.asarray(c), want_c)
    assert _sum_gap(s, want_s, want_c) < 1e-12
    assert (mn is None) == (not want_min) and (mx is None) == (not want_max)
    for got, fold, empty in ((mn, np.minimum, 3.4e38),
                             (mx, np.maximum, -3.4e38)):
        if got is None:
            continue
        want = np.full(ng, empty, np.float32)
        fold.at(want, codes[live], vals[live])
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("ng", GROUP_COUNTS + [16])
def test_partition_histogram_interpret(ng):
    codes, _, mask, live = _rows(ng, n=4000, seed=4)
    h = np.asarray(partition_histogram(jnp.asarray(codes), jnp.asarray(mask),
                                       ng, interpret=True))
    want = np.bincount(codes[live], minlength=ng)
    assert np.array_equal(h.astype(np.int64), want)


@pytest.mark.parametrize("chunk_steps, n, ng", [
    (2048, 120_000, 29), (16, 120_001, 29), (7, 50_003, 29),
    (16, 60_001, 4000)])
@pytest.mark.parametrize("kernel", ["sum", "fused"])
def test_float_sums_leave_the_kernel_as_f32_pairs(monkeypatch, kernel,
                                                  chunk_steps, n, ng):
    """SUM over a FLOAT column is DOUBLE: each block row's running sum is
    an f32 pair (an error-free TwoSum and its error tile), both outputs,
    one block of tiles a chunk of grid steps, widened and added up outside
    the kernel (PR 35).  Values chosen so that a float32 running sum loses
    seven digits: the pairs keep twelve, over one chunk, over several with
    a ragged last one, with the accumulators started afresh often, and
    over 32 group tiles."""
    from baikaldb_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "CHUNK_STEPS", chunk_steps)
    rng = np.random.default_rng(35)
    codes = rng.integers(0, ng, n).astype(np.int32)
    vals = (rng.standard_normal(n) * 3e3 + 1e4).astype(np.float32)
    mask = rng.random(n) < 0.8
    if kernel == "sum":
        f = filtered_group_sum
    else:
        def f(*a, **k):
            return fused_group_aggregate(*a, want_max=False, **k)
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
