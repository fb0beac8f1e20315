"""A dense GROUP BY counts its rows once (PR 36): ``present``, ``COUNT(*)``
and the count of every column without a validity array are read off one
per-group vector, in whichever arm runs, and ``agg_count_passes`` says how
many passes over the input lanes were traced only to count rows.

The Pallas arm is driven on the CPU: ``segments._onehot_backend`` says yes
and the three kernels run with ``interpret=True``; the scatter arm of the
same batch is the reference (the CPU's own lowering).
"""

import functools

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from baikaldb_tpu.column.batch import Column, ColumnBatch
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.ops import pallas_kernels, segments
from baikaldb_tpu.ops.hashagg import (AggSpec, group_aggregate_dense,
                                      group_aggregate_sorted,
                                      noting_lowerings)
from baikaldb_tpu.types import LType
from baikaldb_tpu.utils import metrics

KERNELS = ("fused_group_aggregate", "filtered_group_sum",
           "partition_histogram")
NG = 600                  # past ONEHOT_MAX_SEGMENTS: Pallas on the chip
ALL_NULL, UNSELECTED, NO_ROWS = 7, 11, 13   # the groups the cases are about


@pytest.fixture
def pallas_arm(monkeypatch):
    """The chip's choice of lowering on the CPU; -> calls made, by kernel."""
    calls = dict.fromkeys(KERNELS, 0)

    def interpreted(name):
        kernel = functools.partial(getattr(pallas_kernels, name).__wrapped__,
                                   interpret=True)

        def run(*a, **k):
            calls[name] += 1
            return kernel(*a, **k)
        return run

    monkeypatch.setattr(segments, "_onehot_backend", lambda: True)
    for name in KERNELS:
        monkeypatch.setattr(pallas_kernels, name, interpreted(name))
    return calls


def _batch(nullable: bool, n: int = 6000, seed: int = 36):
    """``(g, v)`` with a filter: group ALL_NULL's selected rows all hold NULL
    in ``v`` (where ``v`` is nullable), group UNSELECTED has rows and none
    selected, group NO_ROWS has none, and one row in twenty has a NULL key
    (the ``domain`` slot).  -> (batch, numpy views of it)."""
    rng = np.random.default_rng(seed)
    g = rng.integers(0, NG, n).astype(np.int32)
    g[g == NO_ROWS] = NO_ROWS + 1
    v = rng.standard_normal(n).astype(np.float32)
    sel = (rng.random(n) < 0.8) & (g != UNSELECTED)
    key_ok = rng.random(n) >= 0.05
    v_ok = (rng.random(n) >= 0.3) & (g != ALL_NULL) if nullable \
        else np.ones(n, bool)
    batch = ColumnBatch(("g", "v"), [
        Column(jnp.asarray(g), jnp.asarray(key_ok), LType.INT32),
        Column(jnp.asarray(v), jnp.asarray(v_ok) if nullable else None,
               LType.FLOAT32)], jnp.asarray(sel), None)
    slot = np.where(key_ok, g, NG)
    return batch, dict(slot=slot, v=v, sel=sel, v_ok=v_ok)


def _specs(count_star: bool, ops=("sum", "avg", "min", "count")):
    return ([AggSpec("count_star", None, "n")] if count_star else []) + [
        AggSpec(op, "v", op) for op in ops]


@pytest.mark.parametrize("count_star", [True, False], ids=["star", "nostar"])
@pytest.mark.parametrize("nullable", [False, True], ids=["dense_v", "null_v"])
@pytest.mark.parametrize("ops", [("sum", "avg", "min", "count"),
                                 ("sum", "count")], ids=["agg", "sum"])
def test_pallas_arm_counts_as_the_scatter_arm(pallas_arm, monkeypatch,
                                              nullable, count_star, ops):
    batch, at = _batch(nullable)
    specs = _specs(count_star, ops)
    with monkeypatch.context() as m:        # the CPU's own lowering
        m.setattr(segments, "_onehot_backend", lambda: False)
        with noting_lowerings() as ref_noted:
            ref = group_aggregate_dense(batch, ["g"], [NG], specs)
    assert ref_noted == [("scatter", 1 + nullable)]
    assert not any(pallas_arm.values())

    def traced(b):
        return group_aggregate_dense(b, ["g"], [NG], specs)

    with noting_lowerings() as noted:
        jaxpr = str(jax.make_jaxpr(traced)(batch))
    # one fused kernel; a histogram only where v's own count cannot serve
    assert noted == [("pallas", int(nullable))]
    fused = "fused_group_aggregate" if "min" in ops else "filtered_group_sum"
    assert pallas_arm == {**dict.fromkeys(KERNELS, 0), fused: 1,
                          "partition_histogram": int(nullable)}
    assert "scatter" not in jaxpr and "segment_sum" not in jaxpr
    got = traced(batch)

    rows = np.bincount(at["slot"][at["sel"]], minlength=NG + 1)
    vals = np.bincount(at["slot"][at["sel"] & at["v_ok"]], minlength=NG + 1)
    present = np.asarray(got.sel)
    np.testing.assert_array_equal(present, rows > 0)
    np.testing.assert_array_equal(present, np.asarray(ref.sel))
    assert present[ALL_NULL] and present[NG]       # NULL keys are a group
    assert not present[UNSELECTED] and not present[NO_ROWS]
    assert rows[ALL_NULL] > 0 and vals[ALL_NULL] == (0 if nullable
                                                     else rows[ALL_NULL])
    key = got.column("g")
    assert not bool(key.validity[NG]) and bool(key.validity[:NG].all())
    if count_star:
        n = got.column("n")
        assert n.data.dtype == jnp.int64 and n.validity is None
        np.testing.assert_array_equal(np.asarray(n.data), rows)
        np.testing.assert_array_equal(np.asarray(n.data),
                                      np.asarray(ref.column("n").data))
    c = got.column("count")
    assert c.data.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(c.data), vals)
    np.testing.assert_array_equal(np.asarray(c.data),
                                  np.asarray(ref.column("count").data))
    for op in set(ops) - {"count"}:
        a, b = got.column(op), ref.column(op)
        # SUM / AVG / MIN of a group with no value is NULL, in both arms
        np.testing.assert_array_equal(np.asarray(a.validity), vals > 0)
        np.testing.assert_array_equal(np.asarray(a.validity),
                                      np.asarray(b.validity))
        ok = vals > 0
        if op == "min":
            np.testing.assert_array_equal(np.asarray(a.data)[ok],
                                          np.asarray(b.data)[ok])
        else:
            np.testing.assert_allclose(np.asarray(a.data)[ok],
                                       np.asarray(b.data)[ok], rtol=1e-9,
                                       atol=1e-9)


def test_pallas_arm_with_no_value_column_is_one_histogram(pallas_arm):
    batch, at = _batch(False)
    with noting_lowerings() as noted:
        got = group_aggregate_dense(batch, ["g"], [NG], _specs(True, ()))
    assert noted == [("pallas", 1)]
    assert pallas_arm == {**dict.fromkeys(KERNELS, 0),
                          "partition_histogram": 1}
    rows = np.bincount(at["slot"][at["sel"]], minlength=NG + 1)
    np.testing.assert_array_equal(np.asarray(got.column("n").data), rows)
    np.testing.assert_array_equal(np.asarray(got.sel), rows > 0)


def test_a_second_nullable_column_is_counted_by_itself(pallas_arm):
    """``rows`` come from the column without NULLs whichever comes first in
    the spec list; the nullable one keeps its own count for its own NULLs."""
    batch, at = _batch(True)
    w = jnp.asarray(at["v"] * 2)
    batch = ColumnBatch(("g", "v", "w"), [*batch.columns, Column(
        w, None, LType.FLOAT32)], batch.sel, None)
    specs = [AggSpec("sum", "v", "sv"), AggSpec("count", "v", "cv"),
             AggSpec("count_star", None, "n"), AggSpec("sum", "w", "sw")]
    with noting_lowerings() as noted:
        got = group_aggregate_dense(batch, ["g"], [NG], specs)
    assert noted == [("pallas", 0)]
    assert pallas_arm["filtered_group_sum"] == 2
    assert pallas_arm["partition_histogram"] == 0
    rows = np.bincount(at["slot"][at["sel"]], minlength=NG + 1)
    vals = np.bincount(at["slot"][at["sel"] & at["v_ok"]], minlength=NG + 1)
    np.testing.assert_array_equal(np.asarray(got.column("n").data), rows)
    np.testing.assert_array_equal(np.asarray(got.column("cv").data), vals)
    np.testing.assert_array_equal(np.asarray(got.column("sv").validity),
                                  vals > 0)
    np.testing.assert_array_equal(np.asarray(got.column("sw").validity),
                                  rows > 0)


# -- the segment arms ---------------------------------------------------------

Q1_SPECS = [AggSpec("sum", "q", "sum_q"), AggSpec("sum", "p", "sum_p"),
            AggSpec("avg", "q", "avg_q"), AggSpec("avg", "d", "avg_d"),
            AggSpec("min", "p", "min_p"), AggSpec("max", "d", "max_d"),
            AggSpec("stddev", "p", "sd_p"), AggSpec("count", "d", "cnt_d"),
            AggSpec("count_star", None, "n")]


def _q1_batch(n: int = 5000):
    """Q1's shape: two small keys, a filter, DOUBLE measures; ``d`` holds
    NULLs, ``q`` and ``p`` do not."""
    rng = np.random.default_rng(1)
    cols = {"rf": rng.integers(0, 3, n).astype(np.int32),
            "ls": rng.integers(0, 2, n).astype(np.int32),
            "q": rng.integers(1, 51, n).astype(np.float64),
            "p": rng.random(n) * 1e5, "d": rng.random(n) / 10}
    d_ok = rng.random(n) >= 0.2
    sel = rng.random(n) < 0.97
    lt = {"rf": LType.INT32, "ls": LType.INT32}
    batch = ColumnBatch(tuple(cols), [
        Column(jnp.asarray(a), jnp.asarray(d_ok) if k == "d" else None,
               lt.get(k, LType.FLOAT64)) for k, a in cols.items()],
        jnp.asarray(sel), None)
    return batch, cols, d_ok, sel


@pytest.mark.parametrize("arm", ["scatter", "select_reduce"])
def test_q1_shaped_answers_and_one_count_a_nullable_column(monkeypatch, arm):
    monkeypatch.setattr(segments, "_onehot_backend",
                        lambda: arm == "select_reduce")
    batch, cols, d_ok, sel = _q1_batch()
    with noting_lowerings() as noted:
        jaxpr = str(jax.make_jaxpr(lambda b: group_aggregate_dense(
            b, ["rf", "ls"], [3, 2], Q1_SPECS))(batch))
    # rows once, d's own count once: 2 passes where nine specs made ten
    assert noted == [(arm, 2)]
    assert ("scatter" in jaxpr) == (arm == "scatter")
    got = group_aggregate_dense(batch, ["rf", "ls"], [3, 2], Q1_SPECS)
    slot = cols["rf"] * 3 + cols["ls"]
    present = np.asarray(got.sel)
    rows = np.bincount(slot[sel], minlength=12)
    np.testing.assert_array_equal(present, rows > 0)
    np.testing.assert_array_equal(np.asarray(got.column("n").data), rows)
    np.testing.assert_array_equal(
        np.asarray(got.column("cnt_d").data),
        np.bincount(slot[sel & d_ok], minlength=12))
    for k in np.nonzero(present)[0]:
        m = sel & (slot == k)
        q, p, d = cols["q"][m], cols["p"][m], cols["d"][m & d_ok]
        want = {"sum_q": q.sum(), "sum_p": p.sum(), "avg_q": q.mean(),
                "avg_d": d.mean(), "min_p": p.min(), "max_d": d.max(),
                "sd_p": p.std()}
        for name, w in want.items():
            c = got.column(name)
            assert bool(c.validity[k])
            np.testing.assert_allclose(float(c.data[k]), w, rtol=1e-6)


def test_both_segment_arms_agree_bit_for_bit_on_counts(monkeypatch):
    batch, *_ = _q1_batch()
    out = {}
    for arm in ("scatter", "select_reduce"):
        monkeypatch.setattr(segments, "_onehot_backend",
                            lambda: arm == "select_reduce")
        out[arm] = group_aggregate_dense(batch, ["rf", "ls"], [3, 2], Q1_SPECS)
    a, b = out["scatter"], out["select_reduce"]
    np.testing.assert_array_equal(np.asarray(a.sel), np.asarray(b.sel))
    for name in ("n", "cnt_d", "sum_q", "min_p", "max_d"):
        np.testing.assert_array_equal(np.asarray(a.column(name).data),
                                      np.asarray(b.column(name).data))
        va, vb = a.column(name).validity, b.column(name).validity
        assert (va is None) == (vb is None)
        if va is not None:
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


def test_the_sorted_strategy_counts_as_it_did():
    """``_segment_one`` with counts of its own: the sorted GROUP BY's answers
    over the Q1-shaped batch are the dense one's."""
    batch, *_ = _q1_batch()
    with noting_lowerings() as noted:
        srt = group_aggregate_sorted(batch, ["rf", "ls"], Q1_SPECS, 16)
    assert noted == []                      # dense aggregates only
    dense = group_aggregate_dense(batch, ["rf", "ls"], [3, 2], Q1_SPECS)
    live = np.asarray(dense.sel)
    k = int(srt.num_rows)
    assert k == live.sum()
    # sorted slots fill in key order, as the dense slots are laid out
    for name in ("n", "cnt_d", "sum_q", "avg_d", "min_p", "sd_p"):
        np.testing.assert_allclose(np.asarray(srt.column(name).data)[:k],
                                   np.asarray(dense.column(name).data)[live],
                                   rtol=1e-12)


# -- the counter ----------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(36)
    n = 20_000
    s = Session(db=Database())
    s.execute("CREATE TABLE t (g INT, g1000 INT, v FLOAT, w FLOAT)")
    w = rng.standard_normal(n, dtype=np.float32)
    s.load_arrow("t", pa.table({
        "g": rng.integers(0, 16, n, dtype=np.int32),
        "g1000": rng.integers(0, 1000, n, dtype=np.int32),
        "v": rng.standard_normal(n, dtype=np.float32),
        "w": pa.array(w, mask=rng.random(n) < 0.1)}))
    return s


@pytest.mark.parametrize("key", ["g", "g1000"])
@pytest.mark.parametrize("aggs, passes", [
    ("COUNT(*) n, SUM(v) s, AVG(v) a, MIN(v) mn", 1),      # the north star's
    ("COUNT(*) n", 1),
    ("SUM(v) s", 1),                        # present alone needs the rows
    ("COUNT(*) n, SUM(w) s, AVG(w) a, COUNT(w) c", 2),     # w holds NULLs
    ("SUM(v) s, MIN(w) m", 2)])
def test_an_execution_adds_its_count_passes(served, key, aggs, passes):
    sql = f"SELECT {key}, {aggs} FROM t WHERE v*2+1 > {{x}} GROUP BY {key}"
    served.query(sql.format(x="0.25"))          # traced here at the latest
    before = metrics.agg_count_passes.value
    rows = served.query(sql.format(x="0.50"))
    assert rows and metrics.agg_count_passes.value == before + passes
    served.query(sql.format(x="0.75"))
    assert metrics.agg_count_passes.value == before + 2 * passes


def test_show_status_has_the_counter(served):
    served.query("SELECT g, COUNT(*) n FROM t GROUP BY g")
    status = {str(r[0]).partition(".")[0]: r[1]
              for r in served.execute("SHOW STATUS LIKE 'agg_%'").rows}
    assert int(status["agg_count_passes"]) == metrics.agg_count_passes.value
    assert int(status["agg_count_passes"]) >= 1
    before = metrics.agg_count_passes.value
    served.query("SELECT COUNT(*) c, SUM(v) s FROM t WHERE v > 0")   # scalar
    served.query("SELECT v, COUNT(*) c FROM t GROUP BY v")           # sorted
    assert metrics.agg_count_passes.value == before
