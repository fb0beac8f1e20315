"""Keyed exchange scheduler satellites: equality classes, constant
propagation into BOTH join sides' scans (pinned pruned-region counts),
and the pinned TPC-H q5/q7/q8/q9 exchange manifest (the fast tier-1 rounds check — plan-level only, no wall-clock).
"""

import json
import os

import pytest

import baikaldb_tpu.plan.distribute as dist_mod
from baikaldb_tpu.exec.session import Session
from baikaldb_tpu.utils import metrics
from baikaldb_tpu.utils.flags import FLAGS, set_flag


# -- equality classes -------------------------------------------------------

def test_classmap_union_find():
    from baikaldb_tpu.plan.eqclasses import ClassMap

    cm = ClassMap()
    cm.union("f.k", "a.k")
    cm.union("a.k", "b.k")
    assert cm.cls("f.k") == ("a.k", "b.k", "f.k")     # canonical sorted
    assert cm.same("f.k", "b.k")
    assert not cm.same("f.k", "c.k")
    assert cm.cls("zzz") == ("zzz",)                  # singleton fallback


def test_region_classes_from_plan():
    """Inner-join keys + filter equalities union; LEFT-join keys must NOT
    (their ON holds only for matched rows)."""
    from baikaldb_tpu.plan.eqclasses import region_classes

    s = Session()
    s.execute("CREATE TABLE ea (k BIGINT, j BIGINT)")
    s.execute("CREATE TABLE eb (k BIGINT)")
    s.execute("CREATE TABLE ec (k BIGINT)")
    s.execute("INSERT INTO ea VALUES (1, 1)")
    s.execute("INSERT INTO eb VALUES (1)")
    s.execute("INSERT INTO ec VALUES (1)")
    from baikaldb_tpu.sql.parser import parse_sql

    plan = s._plan_select(parse_sql(
        "SELECT ea.j FROM ea JOIN eb ON ea.k = eb.k "
        "LEFT JOIN ec ON ea.j = ec.k")[0])
    cm = region_classes(plan)
    assert cm.same("ea.k", "eb.k")
    assert not cm.same("ea.j", "ec.k")      # left ON never feeds a class


# -- equality-class constant propagation + zonemap pruning ------------------

@pytest.fixture()
def zoned():
    """Two region-organized tables with monotone keys so zone maps are
    tight: an eq constant prunes 4 of 5 regions on whichever scan it
    reaches."""
    s = Session()
    s.execute("CREATE TABLE za (k BIGINT, v DOUBLE)")
    s.db.stores["default.za"].region_rows = 200
    s.execute("INSERT INTO za VALUES " +
              ", ".join(f"({i}, {i * 0.5})" for i in range(1000)))
    s.execute("CREATE TABLE zb (k BIGINT, w DOUBLE)")
    s.db.stores["default.zb"].region_rows = 200
    s.execute("INSERT INTO zb VALUES " +
              ", ".join(f"({i}, {i * 1.5})" for i in range(1000)))
    for t in ("za", "zb"):
        assert len(s.db.stores[f"default.{t}"].regions) == 5
    return s


SQL_ZONED = ("SELECT za.v, zb.w FROM za, zb "
             "WHERE za.k = zb.k AND zb.k = 950")


def test_eqclass_const_pushdown_prunes_both_sides(zoned):
    s = zoned
    plan = s.execute("EXPLAIN " + SQL_ZONED).plan_text
    # the derived za.k = 950 reaches za's scan; both sides prune
    assert plan.count("zonemap(4/5 regions pruned)") == 2
    r0 = metrics.regions_pruned.value
    c0 = metrics.eqclass_consts_pushed.value
    rows = s.query(SQL_ZONED)
    assert rows == [{"v": 475.0, "w": 1425.0}]
    # pinned pruned-batch counts: 4 regions on EACH side = 8
    assert metrics.regions_pruned.value - r0 == 8
    assert metrics.eqclass_consts_pushed.value > c0


def test_eqclass_const_pushdown_off_switch(zoned):
    s = zoned
    set_flag("eqclass_pushdown", False)
    try:
        plan = s.execute("EXPLAIN " + SQL_ZONED).plan_text
        # only zb's own conjunct prunes
        assert plan.count("zonemap(4/5 regions pruned)") == 1
        r0 = metrics.regions_pruned.value
        rows = s.query(SQL_ZONED)
        assert rows == [{"v": 475.0, "w": 1425.0}]
        assert metrics.regions_pruned.value - r0 == 4
    finally:
        set_flag("eqclass_pushdown", True)


def test_eqclass_const_never_crosses_left_join(zoned):
    """zb on the preserved side of a LEFT join: its constant must not
    derive a filter on the NULL-extended side's scan."""
    s = zoned
    sql = ("SELECT za.v, zb.w FROM za LEFT JOIN zb ON za.k = zb.k "
           "WHERE za.k = 950")
    plan = s.execute("EXPLAIN " + sql).plan_text
    # za prunes on its own conjunct; zb (left-join right side) must NOT
    # receive a derived filter
    assert plan.count("zonemap(4/5 regions pruned)") == 1
    rows = s.query(sql)
    assert rows == [{"v": 475.0, "w": 1425.0}]


def test_eqclass_const_pushdown_param_path(zoned):
    """The derived conjunct rides the SAME hoisted param slot: literal
    variants of the statement share one plan and still prune."""
    s = zoned
    r0 = metrics.regions_pruned.value
    assert s.query("SELECT za.v FROM za, zb "
                   "WHERE za.k = zb.k AND zb.k = 150") == [{"v": 75.0}]
    first = metrics.regions_pruned.value - r0
    assert first == 8
    h0 = metrics.plan_cache_param_hits.value
    r0 = metrics.regions_pruned.value
    assert s.query("SELECT za.v FROM za, zb "
                   "WHERE za.k = zb.k AND zb.k = 750") == [{"v": 375.0}]
    assert metrics.plan_cache_param_hits.value - h0 == 1
    assert metrics.regions_pruned.value - r0 == 8


# -- pinned TPC-H exchange manifest (fast tier-1 rounds check) --------------

def _plan_metrics(s, sql):
    from baikaldb_tpu.exec.executor import exchange_summary
    from baikaldb_tpu.plan.nodes import JoinNode, MultiJoinNode
    from baikaldb_tpu.sql.parser import parse_sql

    plan = s._plan_select(parse_sql(sql)[0])
    x = exchange_summary(plan)
    seen, steps = set(), [0]

    def walk(n):
        if id(n) in seen:
            return
        seen.add(id(n))
        if isinstance(n, (JoinNode, MultiJoinNode)):
            steps[0] += 1
        for c in n.children:
            walk(c)
    walk(plan)
    return {"rounds": x["rounds"], "collectives": x["collectives"],
            "reused": x["reused"], "join_steps": steps[0]}


def test_tpch_rounds_manifest(monkeypatch):
    """Pinned per-query exchange accounting for the TPC-H q5/q7/q8/q9
    shapes, fused vs the per-edge (multiway off) baseline, in both the
    natural regime (small dims broadcast and fuse as riders) and the
    pure-MPP force-shuffle regime.  A planner/scheduler change that
    shifts ANY of these numbers fails loudly; update the manifest only
    with a line in `CHANGES.md`.  Rounds only — wall-clock
    never gates tier-1."""
    import jax

    from baikaldb_tpu.models import tpch
    from baikaldb_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) >= 8
    with open(os.path.join(os.path.dirname(__file__),
                           "tpch_rounds_manifest.json")) as f:
        manifest = json.load(f)
    cfg = manifest["config"]
    monkeypatch.setattr(dist_mod, "BROADCAST_ROWS", cfg["broadcast_rows"])
    set_flag("dense_join_span_max", cfg["dense_join_span_max"])
    try:
        s = Session(mesh=make_mesh(cfg["mesh"]))
        tpch.load_into(s, scale=cfg["scale"], seed=cfg["seed"])
        for regime in ("natural", "force_shuffle"):
            set_flag("mpp_force_shuffle", regime == "force_shuffle")
            for q, want in manifest[regime].items():
                got = _plan_metrics(s, tpch.QUERIES[q])
                set_flag("multiway_join", False)
                try:
                    base = _plan_metrics(s, tpch.QUERIES[q])
                finally:
                    set_flag("multiway_join", True)
                for k in ("rounds", "collectives", "reused", "join_steps"):
                    assert got[k] == want[k], (regime, q, k, got)
                assert base["rounds"] == want["baseline_rounds"], (regime, q)
                assert base["collectives"] == \
                    want["baseline_collectives"], (regime, q)
                assert base["join_steps"] == \
                    want["baseline_join_steps"], (regime, q)
                # the scheduler never regresses the per-edge baseline
                assert got["rounds"] <= base["rounds"]
                assert got["collectives"] <= base["collectives"]
                assert got["join_steps"] <= base["join_steps"]
        # the headline wins, asserted structurally (not just via pins):
        # pure-MPP regime: q5 (transitive nationkey merge) and q9
        # (suppkey/partkey subset merge) pay strictly fewer rounds
        fs = manifest["force_shuffle"]
        for q in ("q5", "q9"):
            assert fs[q]["rounds"] < fs[q]["baseline_rounds"]
            assert fs[q]["collectives"] < fs[q]["baseline_collectives"]
        # natural regime: q5/q8/q9 fuse to strictly fewer join stages
        # (q7's rider chain is a strictly sequential dependency ladder —
        # the one shape nothing can compress; it pins at parity, never
        # worse).  q9 no longer reuses a partition here: since the
        # broadcast rule counts rows moved (build * (n - 1) <= probe) its
        # partsupp build is gathered, one round and one collective fewer
        # than the reuse saved (tests/test_multiway.py pins reuse itself)
        nat = manifest["natural"]
        assert nat["q9"]["rounds"] == 2 and nat["q9"]["reused"] == 0
        for q in ("q5", "q8", "q9"):
            assert nat[q]["join_steps"] < nat[q]["baseline_join_steps"]
        assert nat["q7"]["join_steps"] <= nat["q7"]["baseline_join_steps"]
    finally:
        set_flag("mpp_force_shuffle", False)
        set_flag("dense_join_span_max", 1 << 24)
        set_flag("multiway_join", True)
