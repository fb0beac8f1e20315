"""The deployment's mesh on the served path: ``SET GLOBAL mesh_devices = N``
makes every wire connection run its SELECTs as one shard_map program over N
devices (here 4 of the CPU's 8 virtual ones), with one sharded copy of a
table for all connections.  Answers are held to the benchmark's plain
references (``benchmark/refs``) within the limits of the cell
``tpch_sf1_mesh4.q1q3``, on data from the benchmark's generator at 1%.
"""

import json
from pathlib import Path

import jax
import pytest

from baikaldb_tpu.client.mysql_client import Connection, MySQLError
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.server.mysql_server import MySQLServer
from baikaldb_tpu.utils import metrics
from baikaldb_tpu.utils.flags import FLAGS, set_flag
from benchmark import trafficgen
from benchmark.loaders import tpch as tpch_loader
from benchmark.run import resolve

ROOT = Path(__file__).resolve().parents[1]
SEEDS = (11, 2800000007, 3000000019)
SEGMENTS = ("automobile", "building", "furniture", "machinery", "household")
TRAFFIC = trafficgen.load_traffic("q1q3")
LIMITS = json.loads((ROOT / "benchmark" / "workloads"
                     / "tpch_sf1_mesh4.q1q3.json").read_text())["limits"]
COUNTERS = ("mesh_programs", "shuffle_rounds", "exchange_bytes",
            "join_cap_retries")

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs four devices")


@pytest.fixture(autouse=True)
def _flag_back_to_default():
    """The flag is the process's: no test may leave it set."""
    yield
    set_flag("mesh_devices", 0)


class Served:
    """One Database behind the wire with TPC-H at 1% from ``seed``."""

    def __init__(self, seed: int):
        self.db = Database()
        self.srv = MySQLServer(self.db, port=0).start()
        self.conns: list = []
        self.tables = tpch_loader.load({"scale": {"scale_factor": 1.0}},
                                       seed, 0.01, Session(db=self.db))
        self.rng_seed = seed

    def connect(self) -> Connection:
        self.conns.append(Connection(port=self.srv.port))
        return self.conns[-1]

    def close(self):
        for c in self.conns:
            c.close()
        self.srv.stop()
        self.db.close()


@pytest.fixture(scope="module", params=SEEDS)
def served(request):
    s = Served(request.param)
    yield s
    s.close()


def _statement(served: Served, name: str) -> trafficgen.Statement:
    """The traffic file's statement ``name`` with parameters drawn from the
    seed, as the benchmark's client would send it."""
    import numpy as np

    spec = TRAFFIC["statements"][name]
    p = trafficgen.draw_params(
        spec["params"], np.random.default_rng([served.rng_seed, len(name)]),
        served.tables["vars"])
    return trafficgen.Statement(name, spec["sql"].format(**p), p)


def _gaps(served: Served, st, res, lower: bool = False) -> dict:
    ref = resolve(TRAFFIC["statements"][st.name]["ref"])
    ctx = served.__dict__.setdefault("ref_ctx",
                                     {"tables": served.tables["tables"]})
    want = ref.answer(ctx, st.params)
    if lower:
        res_cols, res_rows = ref.answer(ctx, st.params, lower=True)
        return ref.gaps(res_cols, res_rows, want)
    return ref.gaps(res.columns, res.rows, want)


def _grew(before: dict) -> dict:
    return {k: getattr(metrics, k).value - before[k] for k in COUNTERS}


def _snap() -> dict:
    return {k: getattr(metrics, k).value for k in COUNTERS}


@pytest.mark.parametrize("name", ["q1"] + [f"q3_{s}" for s in SEGMENTS])
def test_served_mesh_matches_reference(served, name):
    """(a) over the wire on four devices Q1 and Q3 agree with the plain
    references within the cell's limits, and (d) each runs as ONE mesh
    program: a Q3 pays the plan's one shuffle round and moves bytes, a Q1
    merges its four groups by psum."""
    conn = served.connect()
    conn.query("SET GLOBAL mesh_devices = 4")
    st = _statement(served, name)
    conn.query(st.sql)                      # settle caps, compile
    before = _snap()
    res = conn.query(st.sql)
    grew = _grew(before)
    got = _gaps(served, st, res)
    assert set(got) <= set(LIMITS)
    assert all(got[k] <= LIMITS[k] for k in got), got
    assert len(res.rows) == (4 if name == "q1" else 10)
    assert grew["mesh_programs"] == 1
    assert grew["join_cap_retries"] == 0
    assert grew["shuffle_rounds"] == (0 if name == "q1" else 1)
    # Q1's partial aggregates merge by psum (a few hundred bytes, not
    # counted) and come out replicated: no repartition, no gather
    assert (grew["exchange_bytes"] > 0) == (name != "q1")
    # the float32 control fails the float limit of its statement
    low = _gaps(served, st, None, lower=True)
    gap = "q1_rel_gap" if name == "q1" else "q3_rel_gap"
    assert low[gap] > LIMITS[gap]


@pytest.mark.parametrize("name", ["q1", "q3_building"])
def test_mesh_off_gives_the_same_rows(served, name):
    """(b) the same statement on one device and on four: the same keys,
    counts, dates and order; float sums to 1e-12."""
    conn = served.connect()
    st = _statement(served, name)
    conn.query("SET GLOBAL mesh_devices = 0")
    before = _snap()
    one = conn.query(st.sql)
    assert _grew(before)["mesh_programs"] == 0
    conn.query("SET GLOBAL mesh_devices = 4")
    four = conn.query(st.sql)
    assert _grew(before)["mesh_programs"] == 1
    assert one.columns == four.columns and len(one.rows) == len(four.rows)
    for a, b in zip(one.rows, four.rows):
        for x, y in zip(a, b):
            if "." in str(x):
                assert float(y) == pytest.approx(float(x), rel=1e-12)
            else:
                assert x == y


def test_connections_share_one_sharded_copy(served):
    """(c) the sharded batches live on the Database: a second connection
    finds the first one's copy (one entry a table, the same arrays)."""
    a, b = served.connect(), served.connect()
    a.query("SET GLOBAL mesh_devices = 4")
    st = _statement(served, "q3_machinery")
    a.query(st.sql)
    held = dict(served.db._mesh_batches)
    b.query(st.sql)
    assert sorted(k[0] for k in served.db._mesh_batches) == [
        "default.customer", "default.lineitem", "default.orders"]
    assert all(served.db._mesh_batches[k] is v for k, v in held.items())


def test_dml_through_a_mesh_session_is_read_back(served):
    """DML on a connection of a mesh deployment works, and the next SELECT
    reads it: the table's version moved, so it is sharded again and the
    stale copy is dropped."""
    conn = served.connect()
    conn.query("SET GLOBAL mesh_devices = 4")
    conn.query("CREATE TABLE IF NOT EXISTS kv (k INT PRIMARY KEY, v DOUBLE)")
    conn.query("DELETE FROM kv")
    conn.query("INSERT INTO kv VALUES (1, 1.5), (2, 2.5), (3, 4.0)")
    assert conn.query("SELECT COUNT(*), SUM(v) FROM kv").rows \
        == [("3", "8.0")]
    conn.query("UPDATE kv SET v = 10.0 WHERE k = 2")
    conn.query("DELETE FROM kv WHERE k = 3")
    before = _snap()
    assert conn.query("SELECT COUNT(*), SUM(v) FROM kv").rows \
        == [("2", "11.5")]
    assert _grew(before)["mesh_programs"] == 1
    assert sum(k[0] == "default.kv" for k in served.db._mesh_batches) == 1


def test_more_devices_than_the_process_has_is_an_error():
    """(e) 16 devices on 8: an SQL error, and the setting stays."""
    db = Database()
    srv = MySQLServer(db, port=0).start()
    conn = Connection(port=srv.port)
    try:
        conn.query("SET GLOBAL mesh_devices = 4")
        with pytest.raises(MySQLError, match="mesh_devices"):
            conn.query(f"SET GLOBAL mesh_devices = {2 * len(jax.devices())}")
        assert int(FLAGS.mesh_devices) == 4
        with pytest.raises(MySQLError, match="mesh_devices"):
            conn.query("SET GLOBAL mesh_devices = -1")
    finally:
        conn.close()
        srv.stop()
        db.close()


@pytest.mark.parametrize("n", [0, 1])
def test_flag_off_leaves_no_mesh(n):
    """(f) at 0 (the default) and 1 a session has no mesh: the one-device
    path is what it was.  An explicit ``Session(mesh=...)`` keeps its own."""
    from baikaldb_tpu.parallel.mesh import make_mesh

    assert int(FLAGS.mesh_devices) == 0
    set_flag("mesh_devices", n)
    db = Database()
    assert db.mesh is None and Session(db=db).mesh is None
    own = make_mesh(2)
    assert Session(db=db, mesh=own).mesh is own
    set_flag("mesh_devices", 4)
    assert Session(db=db).mesh is db.mesh
    assert int(db.mesh.devices.size) == 4
    assert Session(db=db, mesh=own).mesh is own
    db.close()


def test_mesh_spans_land_in_the_query_log(served):
    """The exchange layer's seams: ``plan.distribute`` on a plan-cache
    miss, ``exec.run`` / ``exec.flags`` on the mesh arm, and ``mesh.shard``
    with its time also kept in the counter ``mesh_shard_ms``."""
    db = Database()
    s = Session(db=db)
    s.execute("CREATE TABLE t (id INT PRIMARY KEY, g INT, v DOUBLE)")
    s.execute("INSERT INTO t VALUES " + ", ".join(
        f"({i}, {i % 7}, {i * 0.5})" for i in range(300)))
    set_flag("mesh_devices", 4)
    shard_ms = metrics.mesh_shard_ms.value
    s.execute("SELECT g, SUM(v) FROM t WHERE id > 5 GROUP BY g ORDER BY g")
    phases = db.query_log[-1][5]
    for name in ("plan.distribute", "mesh.shard", "exec.run", "exec.flags"):
        assert phases.get(name, 0) > 0, (name, phases)
    assert metrics.mesh_shard_ms.value - shard_ms \
        == pytest.approx(phases["mesh.shard"])
    s.execute("SELECT g, SUM(v) FROM t WHERE id > 9 GROUP BY g ORDER BY g")
    again = db.query_log[-1][5]
    assert "plan.distribute" not in again and "mesh.shard" not in again
    assert "exec.cap_retry" not in again
    db.close()


def test_cap_retry_is_a_span_and_a_counter():
    """A join whose first capacity guess overflows recompiles inside
    ``exec.cap_retry`` and counts in ``join_cap_retries``."""
    db = Database()
    s = Session(db=db)
    s.execute("CREATE TABLE a (id INT PRIMARY KEY, k INT)")
    s.execute("CREATE TABLE b (id INT PRIMARY KEY, k INT)")
    s.execute("INSERT INTO a VALUES " + ", ".join(
        f"({i}, {i % 3})" for i in range(120)))
    s.execute("INSERT INTO b VALUES " + ", ".join(
        f"({i}, {i % 3})" for i in range(120)))
    set_flag("mesh_devices", 4)
    before = metrics.join_cap_retries.value
    rows = s.query("SELECT COUNT(*) n FROM a JOIN b ON a.k = b.k")
    assert rows[0]["n"] == 3 * 40 * 40
    grew = metrics.join_cap_retries.value - before
    phases = db.query_log[-1][5]
    assert grew >= 1 and phases.get("exec.cap_retry", 0) > 0, phases
    db.close()


@pytest.mark.parametrize("fact_rows,build_rows,want", [
    (5_994_000, 1_500_000, "gather"), (6_006_000, 1_500_000, "gather"),
    (6_000_000, 2_000_000, "gather"), (6_000_000, 2_000_001, "repartition"),
    (6_000_000, 60_000, "gather")])
def test_broadcast_or_shuffle_does_not_turn_on_the_seed(fact_rows,
                                                        build_rows, want):
    """A join of two sharded tables gathers the build while that moves
    fewer rows than repartitioning both sides, ``build * (n - 1) <=
    probe``.  On four shards TPC-H's own ratio (lineitem = 4 x orders,
    give or take 0.1% by the seed) lies well inside the gather's side, so
    Q3's plan no longer flips with the data."""
    from baikaldb_tpu.plan.distribute import distribute
    from baikaldb_tpu.plan.nodes import ExchangeNode
    from baikaldb_tpu.sql.parser import parse_sql

    s = Session(db=Database())
    s.execute("CREATE TABLE fact (id INT PRIMARY KEY, k INT, v DOUBLE)")
    s.execute("CREATE TABLE dim (k INT PRIMARY KEY, w DOUBLE)")
    plan = s._planner().plan_select(parse_sql(
        "SELECT SUM(v * w) FROM fact JOIN dim ON fact.k = dim.k")[0])
    rows = {"default.fact": fact_rows, "default.dim": build_rows}
    plan = distribute(plan, 4, rows.__getitem__)
    kinds = set()

    def walk(n):
        if isinstance(n, ExchangeNode):
            kinds.add(n.kind)
        for c in n.children:
            walk(c)
    walk(plan)
    assert kinds == {want}
    s.db.close()
