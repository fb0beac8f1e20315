"""Histogram/MCV statistics feeding the planner (VERDICT r04 missing #6).

Reference: ANALYZE-time CM-sketch + equi-depth histograms consumed by the
IndexSelector and join sizing (include/common/cmsketch.h:243,
include/common/histogram.h).  Done bar: a skewed-predicate plan flip —
the join order changes with stats on vs off — and no TPC-H regression
(covered by the existing TPC-H suites running with the flag default-on).
"""

import numpy as np
import pytest

from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.index.stats import (collect, conjunct_selectivity)
from baikaldb_tpu.utils.flags import set_flag


def test_equi_depth_histogram_range_estimates():
    rng = np.random.RandomState(0)
    vals = rng.exponential(100.0, 50_000)       # skewed distribution
    st = collect(vals, len(vals), 0, True)
    for cut in (10.0, 50.0, 200.0, 700.0):
        true = float((vals < cut).mean())
        est = conjunct_selectivity(st, "lt", cut)
        assert est is not None and abs(est - true) < 0.05, (cut, est, true)
    assert conjunct_selectivity(st, "ge", float(vals.max()) + 1) \
        <= 1.0 / 64 + 0.02


def test_mcv_equality_estimates_heavy_hitters():
    vals = np.concatenate([np.full(9_000, 7), np.arange(1_000)])
    st = collect(vals, len(vals), 0, True)
    hot = conjunct_selectivity(st, "eq", 7)
    cold = conjunct_selectivity(st, "eq", 123)
    assert hot == pytest.approx(0.9, abs=0.05)
    assert cold < 0.01                          # rest spread over ndv
    # defaults said 0.1 for both — the skew failure mode


def test_null_fraction_discounts_ranges():
    vals = np.arange(1_000, dtype=np.float64)
    st = collect(vals, 2_000, 1_000, True)      # half the column is NULL
    est = conjunct_selectivity(st, "lt", 1_000.0)
    assert est == pytest.approx(0.5, abs=0.05)


def test_skewed_predicate_flips_join_order():
    """With fixed constants the eq-on-a-heavy-value table looks tiny and
    joins first; the MCV estimate sees 90% survival and defers it."""
    s = Session(Database())
    s.execute("CREATE TABLE a (id BIGINT, PRIMARY KEY (id))")
    s.execute("CREATE TABLE b (aid BIGINT, k BIGINT)")
    s.execute("CREATE TABLE c (aid BIGINT, v BIGINT)")
    s.execute("INSERT INTO a VALUES " +
              ", ".join(f"({i})" for i in range(200)))
    rows_b = [(i % 200, 7 if i < 1800 else i) for i in range(2000)]
    s.execute("INSERT INTO b VALUES " +
              ", ".join(f"({a}, {k})" for a, k in rows_b))
    rows_c = [(i % 200, i % 1000) for i in range(2000)]
    s.execute("INSERT INTO c VALUES " +
              ", ".join(f"({a}, {v})" for a, v in rows_c))
    sql = ("EXPLAIN SELECT COUNT(*) FROM a, b, c "
           "WHERE a.id = b.aid AND a.id = c.aid "
           "AND b.k = 7 AND c.v < 50")

    def order(plan_text):
        return (plan_text.index(" as b "), plan_text.index(" as c "))

    with_stats = s.execute(sql).plan_text
    set_flag("histogram_stats", False)
    try:
        without = s.execute(sql).plan_text
    finally:
        set_flag("histogram_stats", True)
    pb1, pc1 = order(with_stats)
    pb0, pc0 = order(without)
    # fixed constants: b (eq, "0.1") joins before c (range, "0.3");
    # histograms: b survives at 90%, c at 5% -> c joins first
    assert pb0 < pc0, without
    assert pc1 < pb1, with_stats


# ---- HLL in cache-sized pieces (PR 35) vs the whole-array reduction --------

def _hll_ndv_whole_array(values: np.ndarray, p: int = 12) -> int:
    """``index/stats.hll_ndv`` as it was before PR 35, kept here as the
    reference: every step over the whole array at once, ``np.frexp`` for
    the bit length, one ``np.maximum.at`` for the registers."""
    v = np.ascontiguousarray(values)
    if v.dtype.kind == "f":
        v = v + 0.0
        v = v.view(np.uint64 if v.dtype.itemsize == 8
                   else np.uint32).astype(np.uint64)
    else:
        v = v.astype(np.int64).view(np.uint64)
    with np.errstate(over="ignore"):
        h = v * np.uint64(0x9E3779B97F4A7C15)
        h ^= h >> np.uint64(29)
        h *= np.uint64(0xBF58476D1CE4E5B9)
        h ^= h >> np.uint64(32)
    m = 1 << p
    idx = (h >> np.uint64(64 - p)).astype(np.int64)
    nz = 64 - p
    rem = h & np.uint64((1 << nz) - 1)
    _, exp = np.frexp(rem.astype(np.float64))
    rho = np.where(rem == 0, nz + 1, nz - exp + 1).astype(np.int64)
    reg = np.zeros(m, np.int64)
    np.maximum.at(reg, idx, rho)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    est = alpha * m * m / np.sum(np.exp2(-reg.astype(np.float64)))
    zeros = int((reg == 0).sum())
    if est <= 2.5 * m and zeros:
        est = m * np.log(m / zeros)
    return max(1, int(round(est)))


@pytest.mark.parametrize("seed", [0, 35, 2**31 + 35])
@pytest.mark.parametrize("kind", ["int32_16", "int32_4000", "int64_wide",
                                  "float32", "float64", "bool", "zeros"])
def test_hll_in_pieces_gives_the_whole_array_estimate(kind, seed):
    """Same registers, same estimate, bit for bit: over several pieces
    with a ragged tail, signed zeros, and a length under one piece."""
    from baikaldb_tpu.index import stats

    rng = np.random.default_rng(seed)
    n = 3 * stats._HLL_PIECE + 1234
    values = {
        "int32_16": lambda: rng.integers(0, 16, n, dtype=np.int32),
        "int32_4000": lambda: rng.integers(0, 4000, n, dtype=np.int32),
        "int64_wide": lambda: rng.integers(-2**62, 2**62, n),
        "float32": lambda: rng.standard_normal(n, dtype=np.float32),
        "float64": lambda: np.where(rng.random(n) < .1, -0.0,
                                    rng.standard_normal(n)),
        "bool": lambda: rng.random(n) < .5,
        "zeros": lambda: np.zeros(n, np.int64),
    }[kind]()
    for arr in (values, values[:1000], values[:stats._HLL_PIECE],
                values[:0]):
        assert stats.hll_ndv(arr) == _hll_ndv_whole_array(arr)
    assert stats.hll_ndv(np.array(["a", "b"], dtype=object)) is None
    assert stats.hll_ndv(np.ones(4, np.float16)) is None


def test_column_stats_miss_is_a_span_and_a_counter():
    """A first touch feeds ``column_stats_ms`` and leaves a ``stats.column``
    span in the statement's log row; a second touch is a hit and feeds
    neither."""
    from baikaldb_tpu.utils import metrics

    s = Session()
    s.execute("CREATE TABLE ns (g INT, v FLOAT)")
    s.execute("INSERT INTO ns VALUES (1, 0.5), (2, 1.5), (1, 2.5)")
    before = metrics.column_stats_ms.value
    s.db.query_log.clear()
    assert len(s.query("SELECT g, SUM(v) s FROM ns GROUP BY g")) == 2
    grew = metrics.column_stats_ms.value - before
    assert grew > 0
    assert s.db.query_log[-1][5].get("stats.column", 0) > 0
    assert s.query("SELECT g, SUM(v) s FROM ns GROUP BY g")
    assert metrics.column_stats_ms.value - before == grew
    assert "stats.column" not in s.db.query_log[-1][5]
    store = [v for k, v in s.db.stores.items() if k.endswith(".ns")][0]
    assert store.column_stats("g")["ordered"] is False
