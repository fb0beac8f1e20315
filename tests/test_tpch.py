"""TPC-H query tests at tiny scale, golden-checked against pandas — the
functional-suite analog of the reference's test/fun SQL scripts, plus the
OLAP-path exercises (multi-join, group-by strategies, top-k)."""

import numpy as np
import pandas as pd
import pytest

from baikaldb_tpu.exec.session import Session
from baikaldb_tpu.models import tpch


@pytest.fixture(scope="module", params=["single", "mesh"])
def env(request):
    """Every TPC-H golden check runs twice: single-device and distributed
    over the 8-virtual-device mesh (VERDICT r1 #1 'done when')."""
    if request.param == "mesh":
        from baikaldb_tpu.parallel.mesh import make_mesh
        s = Session(mesh=make_mesh(8))
    else:
        s = Session()
    tables = tpch.load_into(s, scale=0.002, seed=7)
    dfs = {k: t.to_pandas() for k, t in tables.items()}
    return s, dfs


def _d(iso):
    return pd.Timestamp(iso).date()


def test_q1(env):
    s, dfs = env
    rows = s.query(tpch.QUERIES["q1"])
    li = dfs["lineitem"]
    f = li[li.l_shipdate <= _d("1998-09-02")].copy()
    f["disc_price"] = f.l_extendedprice * (1 - f.l_discount)
    f["charge"] = f.disc_price * (1 + f.l_tax)
    g = f.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"), sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"), count_order=("l_quantity", "count"),
    ).reset_index().sort_values(["l_returnflag", "l_linestatus"])
    assert len(rows) == len(g)
    for r, (_, w) in zip(rows, g.iterrows()):
        assert r["l_returnflag"] == w.l_returnflag
        assert r["l_linestatus"] == w.l_linestatus
        assert abs(r["sum_disc_price"] - w.sum_disc_price) < 1e-4
        assert abs(r["avg_disc"] - w.avg_disc) < 1e-9
        assert r["count_order"] == w.count_order


def test_q3(env):
    s, dfs = env
    rows = s.query(tpch.QUERIES["q3"])
    c, o, li = dfs["customer"], dfs["orders"], dfs["lineitem"]
    j = (c[c.c_mktsegment == "BUILDING"]
         .merge(o[o.o_orderdate < _d("1995-03-15")], left_on="c_custkey",
                right_on="o_custkey")
         .merge(li[li.l_shipdate > _d("1995-03-15")], left_on="o_orderkey",
                right_on="l_orderkey"))
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = (j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])["rev"]
         .sum().reset_index().sort_values(["rev", "o_orderdate"],
                                          ascending=[False, True]).head(10))
    assert len(rows) == len(g)
    for r, (_, w) in zip(rows, g.iterrows()):
        assert r["l_orderkey"] == w.l_orderkey
        assert abs(r["revenue"] - w.rev) < 1e-6


def test_q5(env):
    s, dfs = env
    rows = s.query(tpch.QUERIES["q5"])
    c, o, li = dfs["customer"], dfs["orders"], dfs["lineitem"]
    su, n, re = dfs["supplier"], dfs["nation"], dfs["region"]
    j = (c.merge(o, left_on="c_custkey", right_on="o_custkey")
          .merge(li, left_on="o_orderkey", right_on="l_orderkey")
          .merge(su, left_on="l_suppkey", right_on="s_suppkey"))
    j = j[j.c_nationkey == j.s_nationkey]
    j = j.merge(n, left_on="s_nationkey", right_on="n_nationkey") \
         .merge(re, left_on="n_regionkey", right_on="r_regionkey")
    j = j[(j.r_name == "ASIA") & (j.o_orderdate >= _d("1994-01-01"))
          & (j.o_orderdate < _d("1995-01-01"))]
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby("n_name")["rev"].sum().reset_index() \
         .sort_values("rev", ascending=False)
    assert len(rows) == len(g)
    for r, (_, w) in zip(rows, g.iterrows()):
        assert r["n_name"] == w.n_name
        assert abs(r["revenue"] - w.rev) < 1e-6


def test_q6(env):
    s, dfs = env
    got = s.query(tpch.QUERIES["q6"])[0]["revenue"]
    li = dfs["lineitem"]
    f = li[(li.l_shipdate >= _d("1994-01-01")) & (li.l_shipdate < _d("1995-01-01"))
           & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
           & (li.l_quantity < 24)]
    want = (f.l_extendedprice * f.l_discount).sum()
    assert abs(got - want) < 1e-6


def test_q12(env):
    s, dfs = env
    rows = s.query(tpch.QUERIES["q12"])
    o, li = dfs["orders"], dfs["lineitem"]
    j = o.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j = j[j.l_shipmode.isin(["MAIL", "SHIP"])
          & (j.l_commitdate < j.l_receiptdate)
          & (j.l_shipdate < j.l_commitdate)
          & (j.l_receiptdate >= _d("1994-01-01"))
          & (j.l_receiptdate < _d("1995-01-01"))]
    hi = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    g = j.assign(hi=hi.astype(int), lo=(~hi).astype(int)) \
         .groupby("l_shipmode")[["hi", "lo"]].sum().reset_index() \
         .sort_values("l_shipmode")
    assert len(rows) == len(g)
    for r, (_, w) in zip(rows, g.iterrows()):
        assert r["l_shipmode"] == w.l_shipmode
        assert r["high_line_count"] == w.hi and r["low_line_count"] == w.lo


def test_q10(env):
    s, dfs = env
    rows = s.query(tpch.QUERIES["q10"])
    c, o, li, n = dfs["customer"], dfs["orders"], dfs["lineitem"], dfs["nation"]
    j = (c.merge(o, left_on="c_custkey", right_on="o_custkey")
          .merge(li, left_on="o_orderkey", right_on="l_orderkey")
          .merge(n, left_on="c_nationkey", right_on="n_nationkey"))
    j = j[(j.o_orderdate >= _d("1993-10-01")) & (j.o_orderdate < _d("1994-01-01"))
          & (j.l_returnflag == "R")]
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = (j.groupby(["c_custkey", "c_acctbal", "n_name"])["rev"].sum()
          .reset_index().sort_values("rev", ascending=False).head(20))
    assert len(rows) == len(g)
    got_rev = [round(r["revenue"], 4) for r in rows]
    want_rev = [round(v, 4) for v in g.rev]
    assert got_rev == want_rev


def test_q14(env):
    s, dfs = env
    got = s.query(tpch.QUERIES["q14"])[0]["promo_revenue"]
    li, p = dfs["lineitem"], dfs["part"]
    f = li[(li.l_shipdate >= _d("1995-09-01")) & (li.l_shipdate < _d("1995-10-01"))]
    j = f.merge(p, left_on="l_partkey", right_on="p_partkey")
    dp = j.l_extendedprice * (1 - j.l_discount)
    want = 100.0 * dp[j.p_type.str.startswith("PROMO")].sum() / dp.sum()
    assert abs(got - want) < 1e-6


def test_tpch_shuffle_rounds_pinned(env, monkeypatch):
    """Executed shuffle rounds for the multi-join shapes (q5/q7/q8/q9),
    pinned per query in the forced-shuffle MPP regime so a keyed-exchange-
    scheduler regression fails loudly.  Counted from the per-execution
    metric, so a reused partition that still showed up in the plan tree
    would inflate these numbers — the counter must report EXECUTED
    repartitions only.  (q9 reused one until the broadcast rule counted
    rows moved, build * (n - 1) <= probe: its partsupp build is gathered
    now, 2 rounds and nothing saved.)  Plan-level pins incl. the per-edge baseline
    live in tests/test_keyed_exchange.py::test_tpch_rounds_manifest."""
    s, dfs = env
    if s.mesh is None:
        pytest.skip("shuffle rounds exist on the mesh only")
    import baikaldb_tpu.plan.distribute as dist_mod
    from baikaldb_tpu.utils import metrics
    from baikaldb_tpu.utils.flags import set_flag

    monkeypatch.setattr(dist_mod, "BROADCAST_ROWS", 0)
    set_flag("dense_join_span_max", 0)
    try:
        from baikaldb_tpu.exec.session import Session
        fresh = Session(db=s.db, mesh=s.mesh)
        pinned = {"q5": 2, "q7": 4, "q8": 2, "q9": 2}
        saved = {}
        for q, want in pinned.items():
            fresh.query(tpch.QUERIES[q])        # settle caps/compiles
            r0 = metrics.shuffle_rounds.value
            s0 = metrics.shuffle_rounds_saved.value
            fresh.query(tpch.QUERIES[q])
            assert metrics.shuffle_rounds.value - r0 == want, q
            assert metrics.shuffle_rounds_saved.value - s0 == \
                saved.get(q, 0), q
    finally:
        set_flag("dense_join_span_max", 1 << 24)


def test_q4(env):
    s, dfs = env
    rows = s.query(tpch.QUERIES["q4"])
    o, li = dfs["orders"], dfs["lineitem"]
    late = li[li.l_commitdate < li.l_receiptdate].l_orderkey.unique()
    f = o[(o.o_orderdate >= _d("1993-07-01")) & (o.o_orderdate < _d("1993-10-01"))
          & o.o_orderkey.isin(late)]
    g = f.groupby("o_orderpriority").size().reset_index(name="n") \
         .sort_values("o_orderpriority")
    assert len(rows) == len(g)
    for r, (_, w) in zip(rows, g.iterrows()):
        assert r["o_orderpriority"] == w.o_orderpriority
        assert r["order_count"] == w.n
