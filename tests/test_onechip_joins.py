"""TPC-H Q3 and Q18 on the resident one-chip path, as the benchmark cell
``tpch_sf1_onechip_joins.q3q18`` serves them (the loader at ~1% scale, the
traffic file's own SQL over the wire, the references' ``gaps`` at the cell's
limits), and what that deployment forced in the engine: QUANTITY a hoisted
parameter (one Q18 program), a plan that settles its capacities in one
recompile (``exec/caps.py``), a publisher that compiles nothing the
query's thread compiled (``utils/compilecache.ExportedProgram``), and
programs that compile in seconds (``ops/sort.argsort``, the shrink's search).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baikaldb_tpu import ColumnBatch
from baikaldb_tpu.client.mysql_client import Connection
from baikaldb_tpu.column.batch import Column
from baikaldb_tpu.exec import caps
from baikaldb_tpu.exec.executor import _CapBox
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.ops import sort as sort_ops
from baikaldb_tpu.ops.compact import shrink
from baikaldb_tpu.plan.nodes import (AggNode, ExchangeNode, FilterNode,
                                     JoinNode, PlanNode, ScanNode,
                                     ShrinkNode)
from baikaldb_tpu.server.mysql_server import MySQLServer
from baikaldb_tpu.types import LType
from baikaldb_tpu.utils import compilecache, metrics
from baikaldb_tpu.utils.flags import FLAGS, set_flag
from benchmark import trafficgen
from benchmark.run import resolve

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CELL = "tpch_sf1_onechip_joins.q3q18"
SEED, SCALE = 33, 0.01


def _count(recorder) -> int:
    return recorder.stats()["count"]


def _json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    c = _json("workloads", CELL)
    return {"cell": c, "config": _json("configs", c["config"]),
            "traffic": trafficgen.load_traffic(c["traffic"])}


@pytest.fixture(scope="module")
def served(cell):
    """The configuration's loader into a Database behind MySQLServer."""
    db = Database()
    srv = MySQLServer(db, port=0).start()
    loaded = resolve(cell["config"]["loader"])(
        cell["config"], SEED, SCALE, Session(db=db))
    conn = Connection(port=srv.port)
    try:
        yield {"db": db, "conn": conn, "ctx": {"tables": loaded["tables"]}}
    finally:
        conn.close()
        srv.stop()


def _compare(served, cell, statement: str, params: dict) -> tuple:
    s = cell["traffic"]["statements"][statement]
    res = served["conn"].query(s["sql"].format(**params))
    ref = resolve(s["ref"])
    want = ref.answer(served["ctx"], params)
    gaps = ref.gaps(res.columns, res.rows, want)
    for name, v in gaps.items():
        assert v <= cell["cell"]["limits"][name], (name, v, res.rows[:3],
                                                   want[1][:3])
    return res, want


@pytest.mark.parametrize("date", ["1995-03-31", "1995-03-05"])
@pytest.mark.parametrize("segment", ["building", "machinery"])
def test_q3_over_the_wire_matches_the_reference(served, cell, segment, date):
    res, want = _compare(served, cell, f"q3_{segment}", {"date": date})
    assert len(res.rows) == len(want[1]) == 10


@pytest.mark.parametrize("quantity,some", [(312, False), (250, True),
                                           (280, True), (400, False)])
def test_q18_over_the_wire_matches_the_reference(served, cell, quantity,
                                                 some):
    res, want = _compare(served, cell, "q18", {"quantity": quantity})
    assert bool(res.rows) == bool(want[1]) == some
    assert len(res.rows) == len(want[1])


def test_q18_quantity_is_one_program(served, cell):
    """QUANTITY is a runtime argument like Q3's date: a new value neither
    plans nor traces again."""
    sql = cell["traffic"]["statements"]["q18"]["sql"]
    served["conn"].query(sql.format(quantity=313))
    r0, m0 = metrics.xla_retraces.value, metrics.plan_cache_misses.value
    for q in (314, 315, 260, 290):
        served["conn"].query(sql.format(quantity=q))
    assert metrics.xla_retraces.value == r0
    assert metrics.plan_cache_misses.value == m0


@pytest.mark.parametrize("quantity", [200, 150])
def test_overflowing_chain_settles_in_one_recompile(served, cell, quantity):
    """A fresh plan of Q18 whose shrink above the semi join overflows its
    first guess (a low QUANTITY lets thousands of lines through a 16x cut),
    under two more capacities downstream: one retry, whatever the chain's
    length, and the answer is the reference's."""
    s = Session(db=served["db"])
    st = cell["traffic"]["statements"]["q18"]
    ref = resolve(st["ref"])
    r0 = metrics.join_cap_retries.value
    t0 = metrics.xla_retraces.value
    slots0 = metrics.join_cap_slots.value
    live0 = metrics.join_live_rows.value
    res = s.execute(st["sql"].format(quantity=quantity))
    assert metrics.join_cap_retries.value == r0 + 1
    assert metrics.xla_retraces.value == t0 + 2     # first trace + one more
    rows = [tuple(r) for r in res.rows]
    want = ref.answer(served["ctx"], {"quantity": quantity})
    for name, v in ref.gaps(list(res.columns), rows, want).items():
        assert v <= cell["cell"]["limits"][name], (name, v)
    # settled: the same statement again neither retries nor traces
    s.execute(st["sql"].format(quantity=quantity))
    assert metrics.join_cap_retries.value == r0 + 1
    assert metrics.xla_retraces.value == t0 + 2
    slots = metrics.join_cap_slots.value - slots0
    live = metrics.join_live_rows.value - live0
    assert slots >= live > 0


def test_unfiltered_probe_is_not_cut(served, cell):
    """The shrink over lineitem-orders-customer, which no predicate
    filters, is sized from the row count: no cut, no flag, no retry (the
    16x guess overflowed it in every first run)."""
    s = Session(db=served["db"])
    r0 = metrics.join_cap_retries.value
    s.execute(cell["traffic"]["statements"]["q18"]["sql"]
              .format(quantity=312))
    assert metrics.join_cap_retries.value == r0
    plan = next(iter(s._plan_cache.values()))["plan"]
    shrinks = []

    def walk(n):
        if isinstance(n, ShrinkNode):
            shrinks.append(n)
        for c in n.children:
            walk(c)
    walk(plan)
    lineitem_rows = served["ctx"]["tables"]["lineitem"].num_rows
    assert any(getattr(n, "live_rows", None) == lineitem_rows
               and n.cap >= lineitem_rows for n in shrinks)
    assert any(getattr(n, "live_rows", None) is None for n in shrinks)


# -- exec/caps.settle on plan trees (host logic only) -----------------------

def _chain():
    """scan -> shrink a -> semi join -> shrink b -> filter -> agg ->
    shrink c, and a many-to-many join and an exchange above."""
    scan = ScanNode(table_key="d.t")
    a = ShrinkNode(children=[scan], cap=1024)
    semi = JoinNode(children=[a, ScanNode(table_key="d.u")], how="semi")
    b = ShrinkNode(children=[semi], cap=64)
    agg = AggNode(children=[FilterNode(children=[b])])
    c = ShrinkNode(children=[agg], cap=16)
    many = JoinNode(children=[c, ScanNode(table_key="d.v")], how="inner",
                    cap=32)
    exch = ExchangeNode(children=[many], kind="repartition", cap=8)
    return a, b, c, many, exch


def test_settle_grows_downstream_to_the_implied_bound():
    a, b, c, many, exch = _chain()
    out = caps.settle(exch, [a, b, c, many, exch], [5000, 60, 9, 9, 3])
    assert out.grew and out.slots == 1024 + 64 + 16 + 32 + 8
    assert out.live == 1024 + 60 + 9 + 9 + 3
    assert a.cap == 8192                        # its need, a power of two
    # at most a's 5,000 rows pass a semi join, a filter, a group-by
    assert b.cap == 8192 and c.cap == 8192
    # no row bound crosses a many-to-many join or an exchange: traced
    # again at the input's static size, never below what they had
    assert many.cap is None and many.cap_full == 32
    assert exch.cap is None and exch.cap_full == 8
    assert caps.first_cap(many, 8192, 4) == 8192
    assert caps.first_cap(many, 16, 4) == 32


def test_settle_leaves_upstream_and_siblings_alone():
    a, b, c, many, exch = _chain()
    out = caps.settle(exch, [a, b, c, many, exch], [700, 200, 9, 9, 3])
    assert out.grew
    assert a.cap == 1024                        # upstream of the overflow
    assert b.cap == 256
    assert c.cap == 256                         # b's exact need bounds it
    assert many.cap is None


def test_settle_without_overflow_changes_nothing():
    a, b, c, many, exch = _chain()
    out = caps.settle(exch, [a, b, c, many, exch], [700, 60, 9, None, 3])
    assert not out.grew and out.slots == 1024 + 64 + 16 + 8
    assert (a.cap, b.cap, c.cap, many.cap, exch.cap) == (1024, 64, 16, 32, 8)


def test_settle_marks_a_nodes_own_knobs():
    scan = ScanNode(table_key="d.t")
    a = ShrinkNode(children=[scan], cap=16)
    agg = AggNode(children=[a])
    agg.agg_exch_cap = _CapBox(cap=4, kind="shuffle", site="agg")
    out = caps.settle(agg, [a, agg.agg_exch_cap], [100, 2])
    assert out.grew and a.cap == 128
    assert agg.agg_exch_cap.cap is None and agg.agg_exch_cap.cap_full == 4


def test_settle_of_an_artifacts_shims_only_grows():
    class Shim:
        cap = 16
    sh = Shim()
    assert caps.settle(None, [sh], [40]).grew and sh.cap == 64


def test_shrink_guess_from_rows_or_a_16x_cut():
    n = ShrinkNode(children=[PlanNode()])
    assert caps.shrink_guess(n, 1 << 20) == 1 << 16
    n.live_rows = 600_000
    assert caps.shrink_guess(n, 1 << 20) == 1 << 20
    n.live_rows = 100
    assert caps.shrink_guess(n, 1 << 20) == 128


# -- the publisher ----------------------------------------------------------

@pytest.fixture
def aot(tmp_path):
    prev = str(FLAGS.aot_cache_dir)
    set_flag("aot_cache", True)
    set_flag("aot_cache_dir", str(tmp_path / "aot"))
    yield compilecache.AOT
    compilecache.AOT.drain(120)
    set_flag("aot_cache", False)
    set_flag("aot_cache_dir", prev)


def test_publisher_compiles_nothing_the_query_compiled(served, cell, aot):
    """A settled executable is compiled once in a process: the query's
    thread traces it through jax.export and compiles that module; the
    publisher serialises it — no trace, no backend compile — and says how
    long it took."""
    compiles = []

    def on_event(name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        s = Session(db=served["db"])
        sql = cell["traffic"]["statements"]["q3_furniture"]["sql"]
        p0 = metrics.aot_cache_publishes.value
        c0 = _count(metrics.compile_ms)
        ms0 = metrics.aot_publish_ms.value
        res = s.execute(sql.format(date="1995-03-11"))
        assert _count(metrics.compile_ms) == c0 + 1
        in_query = len(compiles)
        assert in_query >= 1
        t0 = metrics.xla_retraces.value
        assert aot.drain(120)
        assert metrics.aot_cache_publishes.value == p0 + 1
        assert len(compiles) == in_query, "the publisher compiled"
        assert metrics.xla_retraces.value == t0
        assert _count(metrics.compile_ms) == c0 + 1
        assert metrics.aot_publish_ms.value > ms0
        # another session of the process runs the compiled program as it
        # is (the in-process tier): no trace, no compile, nothing read back
        h0 = metrics.aot_cache_hits.value
        d0 = _count(metrics.aot_cache_deser_ms)
        again = Session(db=served["db"]).execute(sql.format(
            date="1995-03-11"))
        assert metrics.aot_cache_hits.value == h0 + 1
        assert _count(metrics.aot_cache_deser_ms) == d0
        assert len(compiles) == in_query
        assert again.rows == res.rows
        # and the artifact is the program: a restarted process loads it
        # and answers alike without a trace
        aot.forget_live()
        restarted = Session(db=served["db"]).execute(sql.format(
            date="1995-03-11"))
        assert metrics.aot_cache_hits.value == h0 + 2
        assert _count(metrics.aot_cache_deser_ms) == d0 + 1
        assert metrics.xla_retraces.value == t0
        assert restarted.rows == res.rows
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


# -- what made the join programs compile in seconds -------------------------

def _sort_cases():
    rng = np.random.default_rng(33)
    n = 4000
    wide = rng.normal(size=n) * 10.0 ** rng.integers(-300, 300, n)
    wide[:10] = [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, 1.0, -1.0,
                 5e-324, -5e-324]
    edges = np.r_[rng.integers(-3, 3, n - 4),
                  [np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 0]]
    return {
        "int32 with ties": rng.integers(-50, 50, n).astype(np.int32),
        "int64": rng.integers(-2**62, 2**62, n),
        "int64 edges": edges.astype(np.int64),
        "uint32": rng.integers(0, 2**32, n, dtype=np.uint64)
        .astype(np.uint32),
        "bool": rng.integers(0, 2, n).astype(bool),
        "float64": wide,
        "float32": wide[np.abs(wide) < 1e30].astype(np.float32),
        "money": np.round(rng.uniform(0, 5e5, n), 2),
    }


@pytest.mark.parametrize("descending", [False, True])
@pytest.mark.parametrize("case", sorted(_sort_cases()))
def test_argsort_is_the_stable_argsort(case, descending):
    """``ops.sort.argsort`` (int32 words, one two-operand sort a word) puts
    rows where ``jnp.argsort(stable=True)`` puts them, ties, zeros, NaNs
    and all, and its permutation is int32."""
    x = jnp.asarray(_sort_cases()[case])
    got = sort_ops.argsort(x, descending=descending)
    want = jnp.argsort(x, stable=True, descending=descending)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("descending", [False, True])
def test_argsort_by_the_words_of_an_f32_pair(descending, monkeypatch):
    """An accelerator's DOUBLE is an f32 pair; its two halves are its sort
    words, exactly, for every value such a pair can hold."""
    rng = np.random.default_rng(34)
    n = 4000
    hi = rng.normal(size=n).astype(np.float32) * 1e5
    lo = (rng.normal(size=n) * np.spacing(hi) * 0.4).astype(np.float32)
    x = hi.astype(np.float64) + lo.astype(np.float64)
    x[:10] = [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, 1.0, -1.0, 2.5, 2.5]
    x[10:20] = x[20:30]                                     # ties
    monkeypatch.setattr(sort_ops, "_f64_is_a_pair", lambda: True)
    got = sort_ops.argsort(jnp.asarray(x), descending=descending)
    want = jnp.argsort(jnp.asarray(x), stable=True, descending=descending)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_lexsort_is_jnp_lexsort():
    rng = np.random.default_rng(35)
    k1 = jnp.asarray(rng.integers(0, 5, 500))
    k2 = jnp.asarray(rng.integers(0, 2, 500).astype(bool))
    assert np.array_equal(np.asarray(sort_ops.lexsort((k1, k2))),
                          np.asarray(jnp.lexsort((k1, k2))))


@pytest.mark.parametrize("live,cap", [(0, 16), (5, 16), (16, 16), (40, 16),
                                      (300, 512)])
def test_shrink_by_search_is_nonzero(live, cap):
    """The shrink's binary search of the running count picks the rows
    ``jnp.nonzero(size=cap)`` picks, and reports the same need."""
    rng = np.random.default_rng(live)
    mask = np.zeros(1024, bool)
    mask[rng.choice(1024, live, replace=False)] = True
    batch = ColumnBatch(("v",), [Column(jnp.arange(1024, dtype=jnp.int32),
                                        None, LType.INT32)],
                        jnp.asarray(mask), None)
    out, need = shrink(batch, cap)
    assert int(need) == live
    (want,) = jnp.nonzero(jnp.asarray(mask), size=cap, fill_value=0)
    keep = min(live, cap)
    assert np.array_equal(np.asarray(out.column("v").data)[:keep],
                          np.asarray(want)[:keep])
    assert int(np.asarray(out.sel).sum()) == keep
