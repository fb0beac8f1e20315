"""The ``stream`` GROUP BY (ops/hashagg.group_aggregate_stream over
ops/segments.seg_scan, chosen by plan/planner._streams from the store's
``ordered`` statistic and the operator chain, checked by the program itself
through exec/caps.settle): a GROUP BY whose live rows arrive in key order is
segmented scans over the lanes as they come.  The kernel against the dense
and the sorted kernels, the planner's choice on the join cell's own plans,
the check's fallback, and the cell's SQL over the wire."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from baikaldb_tpu import ColumnBatch
from baikaldb_tpu.client.mysql_client import Connection
from baikaldb_tpu.column.batch import Column
from baikaldb_tpu.exec import caps
from baikaldb_tpu.exec.executor import AotFlagShim
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.ops import segments
from baikaldb_tpu.ops.hashagg import (STREAM_OPS, AggSpec,
                                      group_aggregate_dense,
                                      group_aggregate_sorted,
                                      group_aggregate_stream,
                                      stream_supported)
from baikaldb_tpu.parallel.mesh import make_mesh
from baikaldb_tpu.plan.nodes import AggNode
from baikaldb_tpu.server.mysql_server import MySQLServer
from baikaldb_tpu.storage.column_store import TableStore
from baikaldb_tpu.types import LType
from baikaldb_tpu.utils import metrics
from baikaldb_tpu.utils.flags import FLAGS, set_flag
from benchmark import trafficgen
from benchmark.run import resolve

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CELL = "tpch_sf1_onechip_joins.q3q18"
SEED, SCALE = 34, 0.01
B = segments.SCAN_BLOCK


# ---- the kernel -----------------------------------------------------------

def _lanes(shape: str, rng):
    """-> (key, sel): int32 keys whose LIVE lanes are non-decreasing."""
    if shape == "dead_lanes_and_tail":
        # live rows in runs of 1-6, every fifth lane dead and holding a key
        # far out of order, then a padded tail of zeros
        keys = np.repeat(np.arange(5, 5 + 300) * 3, rng.integers(1, 7, 300))
        sel = rng.random(len(keys)) < 0.8
        keys = np.where(sel, keys, rng.integers(-10**6, 10**6, len(keys)))
        pad = 64
        return (np.concatenate([keys, np.zeros(pad, np.int64)]),
                np.concatenate([sel, np.zeros(pad, bool)]))
    if shape == "one_run":
        return np.full(200, 42), rng.random(200) < 0.7
    if shape == "all_dead":
        return rng.integers(0, 50, 100), np.zeros(100, bool)
    if shape == "empty":
        return np.zeros(0, np.int64), np.zeros(0, bool)
    if shape == "straddles_blocks":
        # runs of ~700 lanes over 5 blocks: every block boundary is inside
        # a run, and one run covers a whole block
        keys = np.repeat(np.arange(7) * 11, [700, 700, 700, 1500, 700, 3,
                                             700])
        return keys, rng.random(len(keys)) < 0.9
    if shape == "two_carry_levels":
        # more rows of SCAN_BLOCK lanes than one row holds: the carry over
        # the row totals is itself blocked
        n = B * B + 3 * B + 17
        keys = np.sort(rng.integers(0, n // 900, n))
        return keys, rng.random(n) < 0.95
    raise AssertionError(shape)


def _batch(shape: str, seed: int = 0) -> ColumnBatch:
    rng = np.random.default_rng(seed)
    keys, sel = _lanes(shape, rng)
    n = len(keys)
    null = rng.random(n) < 0.2          # NULLs in the nullable inputs
    words = np.array(sorted({f"w{i:03d}" for i in range(40)}), dtype=object)
    cols = {
        "k": Column(jnp.asarray(keys, jnp.int32), None, LType.INT32),
        "f": Column(jnp.asarray(rng.normal(size=n) * 1e3), None,
                    LType.FLOAT64),
        "fn": Column(jnp.asarray(rng.normal(size=n)), jnp.asarray(~null),
                     LType.FLOAT64),
        "i64": Column(jnp.asarray(rng.integers(-2**40, 2**40, n)), None,
                      LType.INT64),
        "i32n": Column(jnp.asarray(rng.integers(-999, 999, n), jnp.int32),
                       jnp.asarray(~null), LType.INT32),
        "s": Column(jnp.asarray(rng.integers(0, len(words), n), jnp.int32),
                    None, LType.STRING, tuple(words)),
    }
    return ColumnBatch(tuple(cols), list(cols.values()), jnp.asarray(sel),
                       None)


def _groups(out: ColumnBatch, names) -> dict:
    """key -> tuple of (value | None) over the live rows of an output."""
    sel = np.asarray(out.sel_mask())
    if out.num_rows is not None:
        sel = sel & (np.arange(len(sel)) < int(out.num_rows))
    cols = []
    for n in names:
        c = out.column(n)
        data = np.asarray(c.data)[sel]
        valid = np.asarray(c.valid_mask())[sel]
        cols.append([d if v else None for d, v in zip(data.tolist(),
                                                      valid.tolist())])
    keys = np.asarray(out.column("k").data)[sel].tolist()
    assert len(set(keys)) == len(keys), "a key came out twice"
    return dict(zip(keys, zip(*cols))) if names else dict.fromkeys(keys)


def _same(got: dict, want: dict) -> None:
    assert got.keys() == want.keys()
    for k, row in want.items():
        for g, w in zip(got[k], row):
            if w is None or g is None:
                assert g is w, (k, g, w)
            else:
                assert g == pytest.approx(w, rel=1e-12, abs=1e-9), (k, g, w)


def _dense_and_sorted(batch: ColumnBatch, specs):
    keys = np.asarray(batch.column("k").data)
    lo = int(keys.min()) if len(keys) else 0
    span = (int(keys.max()) - lo + 1) if len(keys) else 1
    shifted = ColumnBatch(
        batch.names, [Column(c.data - lo, c.validity, c.ltype, c.dictionary)
                      if n == "k" else c
                      for n, c in zip(batch.names, batch.columns)],
        batch.sel, None)
    dense = group_aggregate_dense(shifted, ["k"], [span], specs)
    dense = ColumnBatch(
        dense.names, [Column(c.data + lo, c.validity, c.ltype)
                      if n == "k" else c
                      for n, c in zip(dense.names, dense.columns)],
        dense.sel, None)
    return dense, group_aggregate_sorted(batch, ["k"], specs,
                                         max(1, len(batch)))


INPUTS = {"f64": "f", "f64_nullable": "fn", "int64": "i64",
          "int32_nullable": "i32n", "dictionary": "s"}
SHAPES = ["dead_lanes_and_tail", "one_run", "all_dead", "empty",
          "straddles_blocks"]


# no arithmetic over dictionary codes
FOLDS = [(op, inp) for op in sorted(STREAM_OPS) for inp in sorted(INPUTS)
         if inp != "dictionary" or op in ("count", "count_star", "min", "max")]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("op,inp", FOLDS)
def test_kernel_equals_dense_and_sorted(op, inp, shape):
    col = None if op == "count_star" else INPUTS[inp]
    specs = [AggSpec(op, col, "a")]
    batch = _batch(shape, seed=len(op) + len(inp))
    if shape == "empty":
        out, flag = group_aggregate_stream(batch, "k", specs)
        assert len(out) == 0 and int(flag) == 0
        return
    out, flag = group_aggregate_stream(batch, "k", specs)
    assert int(flag) == 0
    assert len(out) == len(batch) and out.num_rows is None
    dense, srt = _dense_and_sorted(batch, specs)
    got = _groups(out, ["a"])
    _same(got, _groups(dense, ["a"]))
    _same(got, _groups(srt, ["a"]))
    # the group's row is the run's last live lane, the key the input's own
    sel, key = np.asarray(batch.sel_mask()), np.asarray(batch.column("k").data)
    live = np.flatnonzero(sel)
    last = live[np.append(key[live][1:] != key[live][:-1], True)] \
        if len(live) else live
    assert np.array_equal(np.flatnonzero(np.asarray(out.sel)), last)
    assert out.column("k").data is batch.column("k").data


def test_kernel_every_aggregate_at_once_shares_its_scans():
    """One spec list with every fold over one column, jitted: AVG and the
    variance family ride SUM's and COUNT's scans (one scan a distinct
    fold), and the answers are the dense kernel's."""
    specs = [AggSpec(op, None if op == "count_star" else "fn", op)
             for op in sorted(STREAM_OPS)]
    batch = _batch("straddles_blocks", seed=3)
    seen = []
    real = segments.seg_scan

    def counting(flags, vals, ops, reverse=False):
        seen.append(tuple(ops))
        return real(flags, vals, ops, reverse)

    import baikaldb_tpu.ops.hashagg as hashagg
    hashagg.seg_scan = counting
    try:
        out, flag = jax.jit(
            lambda b: group_aggregate_stream(b, "k", specs))(batch)
    finally:
        hashagg.seg_scan = real
    # two key fills, then ONE scan of the folds: live count, valid count,
    # sum, sumsq, min, max
    assert seen[:2] == [("left",), ("left",)]
    assert len(seen) == 3 and sorted(seen[2]) == sorted(
        ["add", "add", "add", "add", "min", "max"])
    names = [s.out_name for s in specs]
    dense, _ = _dense_and_sorted(batch, specs)
    _same(_groups(out, names), _groups(dense, names))
    assert int(flag) == 0


def test_kernel_carry_over_two_levels_of_blocks():
    batch = _batch("two_carry_levels", seed=5)
    specs = [AggSpec("sum", "i64", "s"), AggSpec("min", "f", "m"),
             AggSpec("count", "i32n", "c")]
    out, flag = jax.jit(lambda b: group_aggregate_stream(b, "k", specs))(batch)
    dense, _ = _dense_and_sorted(batch, specs)
    _same(_groups(out, ["s", "m", "c"]), _groups(dense, ["s", "m", "c"]))
    assert int(flag) == 0


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("n", [1, 7, B, B + 1, 3 * B + 5])
def test_seg_scan_against_a_loop(n, reverse):
    rng = np.random.default_rng(n)
    flags = rng.random(n) < 0.01 if n > 100 else rng.random(n) < 0.3
    add = rng.integers(-5, 5, n)
    mx = rng.integers(-100, 100, n).astype(np.int32)
    seen, (a, m, f) = segments.seg_scan(
        jnp.asarray(flags), (jnp.asarray(add), jnp.asarray(mx),
                             jnp.asarray(add)), ("add", "max", "left"),
        reverse=reverse)
    order = range(n - 1, -1, -1) if reverse else range(n)
    any_, acc, top, held = False, 0, None, 0
    for i in order:
        if flags[i]:
            any_, acc, top, held = True, add[i], mx[i], add[i]
        else:
            acc += add[i]
            top = mx[i] if top is None else max(top, mx[i])
        assert bool(seen[i]) == any_
        if any_:
            assert (int(a[i]), int(m[i]), int(f[i])) == (acc, top, held)


@pytest.mark.parametrize("fault", ["steps_down", "null_key"])
def test_kernel_raises_its_flag(fault):
    batch = _batch("dead_lanes_and_tail", seed=9)
    k = batch.column("k")
    live = np.flatnonzero(np.asarray(batch.sel_mask()))
    if fault == "steps_down":
        data = np.asarray(k.data).copy()
        data[live[40]] = data[live[39]] - 1
        k = Column(jnp.asarray(data), None, k.ltype)
    else:
        valid = np.ones(len(batch), bool)
        valid[live[40]] = False
        k = Column(k.data, jnp.asarray(valid), k.ltype)
    batch = ColumnBatch(batch.names, [k] + list(batch.columns[1:]),
                        batch.sel, None)
    _, flag = group_aggregate_stream(batch, "k", [AggSpec("sum", "f", "a")])
    assert int(flag) == 1


def test_what_keeps_the_arm_off():
    assert stream_supported([AggSpec("sum", "x", "a"),
                             AggSpec("stddev_samp", "x", "b")])
    assert not stream_supported([AggSpec("count", "x", "a", distinct=True)])
    assert not stream_supported([AggSpec("percentile", "x", "a", param=0.5)])
    assert not stream_supported([AggSpec("approx_count_distinct", "x", "a")])


# ---- the statistic --------------------------------------------------------

def _table(s: Session, rows, name="t") -> None:
    s.execute(f"CREATE TABLE {name} (id BIGINT PRIMARY KEY, g BIGINT, "
              f"d DATE, v DOUBLE)")
    for at in range(0, len(rows), 2000):
        s.execute(f"INSERT INTO {name} VALUES " + ",".join(
            f"({i},{g},'{d}',{v})" for i, g, d, v in rows[at:at + 2000]))


def _rows(n: int, per: int = 3):
    import datetime
    d0 = datetime.date(1995, 1, 1)
    return [(i, 100 + (i // per) * 5, d0 + datetime.timedelta(days=i // 40),
             float(i % 17)) for i in range(n)]


@pytest.fixture
def ordered_sess():
    s = Session()
    _table(s, _rows(6000))
    return s


def _explain(s: Session, sql: str) -> str:
    return "\n".join(r[0] for r in s.execute("EXPLAIN " + sql).rows)


def _q(s: Session, sql: str) -> list:
    return [tuple(r) for r in s.execute(sql).rows]


def _agg_lines(text: str) -> list:
    return [ln.strip() for ln in text.splitlines()
            if ln.strip().startswith("Agg(")]


def test_ordered_statistic(ordered_sess):
    store = ordered_sess.db.stores["default.t"]
    assert store.column_stats("id")["ordered"] is True
    assert store.column_stats("g")["ordered"] is True      # runs of equals
    assert store.column_stats("d")["ordered"] is True      # a DATE
    assert "ordered" not in store.column_stats("v")        # no group key
    v = store.version
    ordered_sess.execute("INSERT INTO t VALUES (100000, 7, '1990-01-01', 1.0)")
    assert store.version > v
    assert store.column_stats("id")["ordered"] is True
    assert store.column_stats("g")["ordered"] is False
    assert store.column_stats("d")["ordered"] is False


def test_ordered_statistic_is_false_over_nulls():
    s = Session()
    s.execute("CREATE TABLE n (id BIGINT PRIMARY KEY, g BIGINT)")
    s.execute("INSERT INTO n VALUES (1, 1), (2, NULL), (3, 5)")
    assert s.db.stores["default.n"].column_stats("g")["ordered"] is False


# ---- the planner ----------------------------------------------------------

GROUP_SQL = "SELECT g, SUM(v), COUNT(*), MIN(d) FROM t GROUP BY g"


def test_ordered_key_with_a_large_domain_streams(ordered_sess):
    assert "stream" in _agg_lines(_explain(ordered_sess, GROUP_SQL))[0]
    # a filter is a mask, a LIMIT-less sort above changes nothing below
    assert "stream" in _agg_lines(_explain(
        ordered_sess, "SELECT g, AVG(v) FROM t WHERE v > 3 GROUP BY g "
                      "ORDER BY g"))[0]
    # a DATE key
    s = Session()
    _table(s, [(i, g, d, v) for i, g, d, v in _rows(6000)])
    s.execute("CREATE TABLE wide (id BIGINT PRIMARY KEY, d DATE)")
    import datetime
    s.execute("INSERT INTO wide VALUES " + ",".join(
        f"({i},'{datetime.date(1970, 1, 1) + datetime.timedelta(days=3 * i)}')"
        for i in range(5000)))
    assert "stream" in _agg_lines(_explain(
        s, "SELECT d, COUNT(*) FROM wide GROUP BY d"))[0]


@pytest.mark.parametrize("sql", [
    # DISTINCT and percentile aggregates need each group's rows
    "SELECT g, COUNT(DISTINCT v) FROM t GROUP BY g",
    "SELECT g, MEDIAN(v) FROM t GROUP BY g",
    # two keys that no functional dependency reduces
    "SELECT g, d, SUM(v) FROM t GROUP BY g, d",
    # a DOUBLE key
    "SELECT v, COUNT(*) FROM t GROUP BY v",
])
def test_what_the_planner_leaves_to_the_other_strategies(ordered_sess, sql):
    assert "stream" not in _agg_lines(_explain(ordered_sess, sql))[0]


def test_small_domain_keeps_its_one_pass_reduce():
    """Q1's shape: a key whose dense domain fits select+reduce / Pallas is
    ordered here too, and stays dense."""
    s = Session()
    _table(s, [(i, i // 2000, d, v) for i, _, d, v in _rows(6000)])
    assert s.db.stores["default.t"].column_stats("g")["ordered"] is True
    line = _agg_lines(_explain(s, GROUP_SQL))[0]
    assert "dense[" in line and "stream" not in line


def test_a_span_past_the_dense_limit_streams_instead_of_sorting():
    s = Session()
    _table(s, [(i, g * 10**7, d, v) for i, g, d, v in _rows(3000)])
    assert "stream" in _agg_lines(_explain(s, GROUP_SQL))[0]
    rows = _q(s, GROUP_SQL + " ORDER BY g LIMIT 2")
    assert rows[0][0] == 100 * 10**7 and rows[0][2] == 3


def test_out_of_order_insert_turns_it_off_at_the_next_plan(ordered_sess):
    s = ordered_sess
    before = sorted(_q(s, GROUP_SQL))
    assert "stream" in _agg_lines(_explain(s, GROUP_SQL))[0]
    s.execute("INSERT INTO t VALUES (100000, 100, '1995-01-01', 2.0)")
    assert "stream" not in _agg_lines(_explain(s, GROUP_SQL))[0]
    r0, f0 = metrics.stream_agg_runs.value, metrics.stream_agg_fallbacks.value
    after = sorted(_q(s, GROUP_SQL))
    assert (metrics.stream_agg_runs.value, metrics.stream_agg_fallbacks.value) \
        == (r0, f0)
    assert after[0][0] == before[0][0] == 100
    assert after[0][1] == before[0][1] + 2.0 and after[0][2] == before[0][2] + 1
    assert after[1:] == before[1:]


def test_a_mesh_session_never_streams(ordered_sess):
    mesh = Session(db=ordered_sess.db, mesh=make_mesh(8))
    assert "stream" not in _agg_lines(_explain(mesh, GROUP_SQL))[0]
    r0 = metrics.stream_agg_runs.value
    assert sorted(_q(mesh, GROUP_SQL)) == sorted(_q(ordered_sess, GROUP_SQL))
    # only the one-chip session's execution counted
    assert metrics.stream_agg_runs.value == r0 + 1


def test_answers_equal_the_dense_strategy(ordered_sess, monkeypatch):
    sql = ("SELECT g, SUM(v), AVG(v), COUNT(*), MIN(d), MAX(v), STDDEV(v) "
           "FROM t WHERE v <> 5 GROUP BY g HAVING COUNT(*) > 1 ORDER BY g")
    r0 = metrics.stream_agg_runs.value
    got = _q(ordered_sess, sql)
    assert metrics.stream_agg_runs.value == r0 + 1
    plain = Session(db=ordered_sess.db)
    monkeypatch.setattr(type(plain._planner()), "_streams",
                        lambda *a, **k: False)
    assert "stream" not in _explain(plain, sql)
    want = _q(plain, sql)
    assert len(got) == len(want) > 1000
    for g, w in zip(got, want):
        assert g == pytest.approx(w, rel=1e-12)


def test_a_pk_range_gather_feeds_it(ordered_sess):
    """An access path is the execution's choice, after the plan: a pk range
    gathers its rows in key order, and the aggregate above streams over the
    gathered bucket."""
    sql = "SELECT g, SUM(v), COUNT(*) FROM t WHERE id BETWEEN {} AND {} GROUP BY g"
    assert "stream" in _agg_lines(_explain(ordered_sess, sql.format(30, 80)))[0]
    p0, r0 = metrics.pk_range_scans.value, metrics.stream_agg_runs.value
    rows = sorted(_q(ordered_sess, sql.format(30, 80)))
    assert metrics.pk_range_scans.value == p0 + 1
    assert metrics.stream_agg_runs.value == r0 + 1
    want = {}
    for i, g, _, v in _rows(6000)[30:81]:
        s_, c_ = want.get(g, (0.0, 0))
        want[g] = (s_ + v, c_ + 1)
    assert rows == sorted((g, s_, c_) for g, (s_, c_) in want.items())


# ---- the check ------------------------------------------------------------

def test_forced_statistic_on_an_unordered_table_falls_back(monkeypatch):
    """The statistic chooses, the program verifies: with ``ordered`` forced
    true over a shuffled table the first run's flag takes the node off the
    strategy, the plan is traced once more, and the answer is right."""
    rows = _rows(6000)
    rng = np.random.default_rng(1)
    shuffled = [rows[i] for i in rng.permutation(len(rows))]
    s = Session()
    _table(s, [(n, g, d, v) for n, (_, g, d, v) in enumerate(shuffled)])
    want = sorted(_q(s, GROUP_SQL))
    assert "stream" not in _explain(s, GROUP_SQL)

    real = TableStore.column_stats

    def forced(self, column):
        st = dict(real(self, column))
        if "ordered" in st:
            st["ordered"] = True
        return st

    monkeypatch.setattr(TableStore, "column_stats", forced)
    lied = Session(db=s.db)
    assert "stream" in _agg_lines(_explain(lied, GROUP_SQL))[0]
    r0, f0 = metrics.stream_agg_runs.value, metrics.stream_agg_fallbacks.value
    t0 = metrics.xla_retraces.value
    assert sorted(_q(lied, GROUP_SQL)) == want
    assert metrics.stream_agg_fallbacks.value == f0 + 1
    assert metrics.stream_agg_runs.value == r0
    assert metrics.xla_retraces.value == t0 + 2     # the cap loop's one more
    # the node keeps the strategy it fell back to: no third trace
    assert sorted(_q(lied, GROUP_SQL)) == want
    assert metrics.stream_agg_fallbacks.value == f0 + 1
    assert metrics.xla_retraces.value == t0 + 2


def test_settle_takes_a_raised_node_off_the_strategy():
    node = AggNode(key_names=["k"], strategy="stream",
                   unordered=("dense", [700000], {"k": 3}))
    r0, f0 = metrics.stream_agg_runs.value, metrics.stream_agg_fallbacks.value
    done = caps.settle(node, [node], [0])
    assert not done.grew and (done.slots, done.live) == (0, 0)
    assert node.strategy == "stream"
    assert metrics.stream_agg_runs.value == r0 + 1
    done = caps.settle(node, [node], [1])
    assert done.grew and (done.slots, done.live) == (0, 0)
    assert (node.strategy, node.domains, node.key_shift, node.unordered) \
        == ("dense", [700000], {"k": 3}, None)
    assert "dense[700000]" in node._label()
    assert metrics.stream_agg_fallbacks.value == f0 + 1
    # a loaded executable's shim has no node: its caller compiles afresh
    shim = AotFlagShim(None, False, "AggNode")
    assert not caps.settle(None, [shim], [0]).grew
    assert caps.settle(None, [shim], [1]).grew
    assert metrics.stream_agg_fallbacks.value == f0 + 1


def test_the_chunk_fold_takes_the_strategy_it_would_have_had(tmp_path):
    """An out-of-core scan folds partials by group id: a statement planned
    as a stream folds as the dense aggregate it would have been, and equals
    the resident run."""
    flags = ("streaming_scan", "streaming_min_rows", "streaming_chunk_rows")
    prev = {k: getattr(FLAGS, k) for k in flags}
    set_flag("streaming_scan", True)
    set_flag("streaming_min_rows", 1)
    set_flag("streaming_chunk_rows", 512)
    try:
        s = Session(Database(cold_dir=str(tmp_path / "afs")))
        _table(s, _rows(3000))
        assert "stream" in _agg_lines(_explain(s, GROUP_SQL))[0]
        c0, r0 = metrics.stream_chunks.value, metrics.stream_agg_runs.value
        streamed = sorted(_q(s, GROUP_SQL))
        assert metrics.stream_chunks.value > c0
        assert metrics.stream_agg_runs.value == r0
        set_flag("streaming_scan", False)
        assert sorted(_q(s, GROUP_SQL)) == streamed
        assert len(streamed) == 1000
    finally:
        for k, v in prev.items():
            set_flag(k, v)


# ---- the join cell's own plans and SQL, at 1% -----------------------------

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


def _sql(cell, statement: str, **params) -> str:
    return cell["traffic"]["statements"][statement]["sql"].format(**params)


def test_q18_plans_three_streams_and_q3_one(served, cell):
    s = Session(db=served["db"])
    q18 = _agg_lines(_explain(s, _sql(cell, "q18", quantity=313)))
    assert [("stream" in ln) for ln in q18] == [True, True]
    # the outer aggregate's key is the BUILD side's: ordered through the
    # inner join's equality with the probe's l_orderkey
    assert "orders.o_orderkey" in q18[0] and "lineitem.l_orderkey" in q18[1]
    q3 = _agg_lines(_explain(s, _sql(cell, "q3_building", date="1995-03-15")))
    assert len(q3) == 1 and "stream" in q3[0]
    # Q1's two dictionary keys: four groups, select+reduce
    q1 = _agg_lines(_explain(
        s, "SELECT l_returnflag, l_linestatus, SUM(l_quantity), COUNT(*) "
           "FROM lineitem GROUP BY l_returnflag, l_linestatus"))
    assert "dense[" in q1[0] and "stream" not in q1[0]


def test_lineitem_loaded_shuffled_does_not_stream(served, cell):
    tables = served["ctx"]["tables"]
    li = tables["lineitem"]
    perm = np.random.default_rng(2).permutation(li.num_rows)
    db = Database()
    s = Session(db=db)
    resolve(cell["config"]["loader"])(cell["config"], SEED, 0.002, s)
    s.execute("DROP TABLE lineitem")
    from benchmark.loaders.tpch import DDL
    s.execute(DDL["lineitem"])
    s.load_arrow("lineitem", li.take(pa.array(perm[:12000])))
    assert db.stores["default.lineitem"].column_stats(
        "l_orderkey")["ordered"] is False
    for ln in _agg_lines(_explain(s, _sql(cell, "q18", quantity=313))):
        assert "stream" not in ln


def _compare(served, cell, statement: str, params: dict):
    s = cell["traffic"]["statements"][statement]
    res = served["conn"].query(s["sql"].format(**params))
    ref = resolve(s["ref"])
    want = ref.answer(served["ctx"], params)
    for name, v in ref.gaps(res.columns, res.rows, want).items():
        assert v <= cell["cell"]["limits"][name], (name, v, res.rows[:3])
    return res, want


@pytest.mark.parametrize("segment,date,quantity", [
    ("building", "1995-03-31", 313), ("machinery", "1995-03-05", 250),
    ("household", "1995-03-17", 280)])
def test_a_pair_over_the_wire_runs_three_streams(served, cell, segment, date,
                                                 quantity):
    r0, f0 = metrics.stream_agg_runs.value, metrics.stream_agg_fallbacks.value
    res, want = _compare(served, cell, f"q3_{segment}", {"date": date})
    assert len(res.rows) == len(want[1]) == 10
    res, want = _compare(served, cell, "q18", {"quantity": quantity})
    assert len(res.rows) == len(want[1])
    assert bool(res.rows) == (quantity < 300)
    assert metrics.stream_agg_runs.value == r0 + 3
    assert metrics.stream_agg_fallbacks.value == f0
    status = {str(r[0]).partition(".")[0]: r[1] for r in
              served["conn"].query("SHOW STATUS").rows}
    assert float(status["stream_agg_runs"]) >= 3
    assert "stream_agg_fallbacks" in status
