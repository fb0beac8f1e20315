"""Adaptive capacity cuts (ops/compact.shrink + planner ShrinkNode).

A selective join chain otherwise drags the base table's full capacity
through every downstream operator (the TPC-H q21 profile: 10k live rows on
1.2M-lane kernels).  Shrink packs live rows into a smaller static batch;
when the live count exceeds the cap, the session's overflow-retry loop
re-traces with the exact needed capacity — the same contract as join caps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baikaldb_tpu import ColumnBatch
from baikaldb_tpu.column.batch import Column
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.ops.compact import shrink
from baikaldb_tpu.sql.parser import parse_sql
from baikaldb_tpu.plan.nodes import ShrinkNode
from baikaldb_tpu.types import LType


def _batch(n, live_mask):
    return ColumnBatch(
        ("v",), [Column(jnp.arange(n, dtype=jnp.int32), None, LType.INT32)],
        jnp.asarray(live_mask), None)


def test_shrink_packs_live_rows_and_reports_count():
    mask = np.zeros(64, bool)
    mask[[3, 17, 40, 63]] = True
    out, n = shrink(_batch(64, mask), 8)
    assert int(n) == 4
    vals = np.asarray(out.column("v").data)[np.asarray(out.sel)]
    assert vals.tolist() == [3, 17, 40, 63]
    assert len(out) == 8


def test_shrink_overflow_reports_exact_need():
    mask = np.ones(64, bool)
    out, n = shrink(_batch(64, mask), 8)
    assert int(n) == 64                      # caller must retry with >= 64
    assert len(out) == 8                     # truncated until then


def test_shrink_passthrough_when_cap_covers():
    mask = np.ones(16, bool)
    out, n = shrink(_batch(16, mask), 16)
    assert int(n) == 0 and len(out) == 16    # no cut: pass-through


def _selective_join_session(n=5000):
    s = Session(Database())
    s.execute("CREATE TABLE big (id BIGINT, k BIGINT, PRIMARY KEY (id))")
    s.execute("CREATE TABLE dim (k BIGINT, tag BIGINT, PRIMARY KEY (k))")
    s.load_arrow("big", _arrow_big(n))
    s.execute("INSERT INTO dim VALUES (1, 10), (2, 20)")
    return s


def _arrow_big(n):
    import pyarrow as pa

    rng = np.random.default_rng(3)
    return pa.table({"id": np.arange(n, dtype=np.int64),
                     "k": rng.integers(0, 500, n).astype(np.int64)})


def test_plan_inserts_shrink_and_results_are_exact():
    """A semi-join over a join-filtered probe gets a Shrink; results match
    the unshrunk semantics exactly even across the cap-retry path."""
    s = _selective_join_session()
    q = ("SELECT COUNT(*) n FROM big JOIN dim ON big.k = dim.k "
         "WHERE big.id IN (SELECT id FROM big WHERE k < 100)")
    plan = s._plan_select(parse_sql(q)[0])
    labels = plan.tree_repr()
    assert "Shrink" in labels
    got = s.query(q)[0]["n"]
    # golden: host-side recomputation
    t = _arrow_big(5000).to_pandas()
    want = int(((t.k.isin((1, 2))) & (t.id.isin(t[t.k < 100].id))).sum())
    assert got == want


def test_shrink_cap_retry_grows_to_exact_need():
    """Force a tiny initial cap: the first run truncates, the flag carries
    the true live count, and the retry recompiles with a sufficient cap."""
    s = _selective_join_session()
    q = ("SELECT COUNT(*) n FROM big JOIN dim ON big.k = dim.k "
         "WHERE big.id IN (SELECT id FROM big WHERE k < 400)")
    stmt = parse_sql(q)[0]
    plan = s._plan_select(stmt)

    def clamp(n):
        if isinstance(n, ShrinkNode):
            n.cap = 16                      # deliberately far too small
        for c in n.children:
            clamp(c)
    clamp(plan)
    entry = {"plan": plan, "compiled": {}, "versions": {}}
    batches, shape_key, _full = s._collect_batches(plan)
    out = s._run_plan(entry, batches, shape_key)
    got = int(out.to_arrow().to_pylist()[0]["n"])
    t = _arrow_big(5000).to_pandas()
    want = int(((t.k.isin((1, 2))) & (t.id.isin(t[t.k < 400].id))).sum())
    assert got == want
    # and the caps actually grew past the clamp
    caps = []

    def collect(n):
        if isinstance(n, ShrinkNode):
            caps.append(n.cap)
        for c in n.children:
            collect(c)
    collect(plan)
    assert caps and all(c > 16 for c in caps)


def _sorted_build_session(n=4000, mesh=None):
    s = Session(Database(), mesh=mesh)
    s.execute("CREATE TABLE fact (id BIGINT, k BIGINT, v DOUBLE, "
              "PRIMARY KEY (id))")
    import pyarrow as pa

    rng = np.random.default_rng(11)
    s.load_arrow("fact", pa.table({
        "id": np.arange(n, dtype=np.int64),
        "k": rng.integers(0, 1 << 30, n).astype(np.int64),
        "v": rng.normal(size=n)}))
    return s


SORTED_BUILD_Q = ("SELECT COUNT(*) n, SUM(a.sv) s FROM fact "
                  "LEFT JOIN (SELECT k, SUM(v) sv FROM fact GROUP BY k) a "
                  "ON fact.k = a.k WHERE fact.v > 0")


def test_sorted_build_join_marked_and_exact():
    """A join whose build is a group-by on exactly the join keys skips the
    lexsort (interesting-order reuse); results must be exact."""
    from baikaldb_tpu.plan.nodes import JoinNode
    from baikaldb_tpu.sql.parser import parse_sql

    s = _sorted_build_session()
    plan = s._plan_select(parse_sql(SORTED_BUILD_Q)[0])
    marked = []

    def walk(n):
        if isinstance(n, JoinNode):
            marked.append(n.build_sorted)
        for c in n.children:
            walk(c)
    walk(plan)
    assert any(marked)
    got = s.query(SORTED_BUILD_Q)[0]
    t = None
    import pandas as pd

    # host golden
    import pyarrow as pa
    rng = np.random.default_rng(11)
    n = 4000
    df = pd.DataFrame({"id": np.arange(n), "k": rng.integers(0, 1 << 30, n),
                       "v": rng.normal(size=n)})
    sv = df.groupby("k").v.sum()
    m = df[df.v > 0]
    want_n = len(m)
    want_s = float(m.k.map(sv).sum())
    assert got["n"] == want_n
    assert abs(got["s"] - want_s) < 1e-6


def test_sorted_build_join_exact_under_mesh():
    """Mesh mode: exchanges on the build side destroy the proved order —
    the fast path must disengage and results stay exact: the same rows
    join (the count is an integer and equal), and the float sum agrees to
    1e-12 relative — four shards' partial sums merged by psum add in
    another order than one device does, so the last bit may differ."""
    from baikaldb_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device mesh")
    s1 = _sorted_build_session(2000)
    want = s1.query(SORTED_BUILD_Q)
    s2 = _sorted_build_session(2000, mesh=make_mesh(4))
    got = s2.query(SORTED_BUILD_Q)
    assert len(got) == len(want) == 1
    assert got[0]["n"] == want[0]["n"]
    assert got[0]["s"] == pytest.approx(want[0]["s"], rel=1e-12, abs=0)


def test_shrink_under_mesh():
    """Shrink inside the shard_map program: per-shard cut, pmax'd caps."""
    from baikaldb_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device mesh")
    s = Session(Database(), mesh=make_mesh(4))
    s.execute("CREATE TABLE big (id BIGINT, k BIGINT, PRIMARY KEY (id))")
    s.execute("CREATE TABLE dim (k BIGINT, tag BIGINT, PRIMARY KEY (k))")
    s.load_arrow("big", _arrow_big(2000))
    s.execute("INSERT INTO dim VALUES (1, 10), (2, 20)")
    q = ("SELECT COUNT(*) n FROM big JOIN dim ON big.k = dim.k "
         "WHERE big.id IN (SELECT id FROM big WHERE k < 100)")
    got = s.query(q)[0]["n"]
    t = _arrow_big(2000).to_pandas()
    want = int(((t.k.isin((1, 2))) & (t.id.isin(t[t.k < 100].id))).sum())
    assert got == want
