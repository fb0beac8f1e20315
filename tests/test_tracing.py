"""Query-lifecycle tracing (obs/trace.py): span-tree shape, cross-RPC
stitching, sampling + slow-query always-keep, SHOW PROFILE round-trip, the
EXPLAIN ANALYZE single-timing-truth contract, and the pinned zero-span
assertion with tracing=off.
"""

import json
import os
import signal
import sys
import time
from contextlib import contextmanager

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from baikaldb_tpu.exec.session import Database, Session  # noqa: E402
from baikaldb_tpu.obs import trace  # noqa: E402
from baikaldb_tpu.obs.trace import TRACER  # noqa: E402
from baikaldb_tpu.utils import metrics  # noqa: E402
from baikaldb_tpu.utils.flags import FLAGS, set_flag  # noqa: E402


@pytest.fixture
def traced():
    """tracing on, clean store, flags restored."""
    prev_n = int(FLAGS.trace_sample_n)
    prev_slow = float(FLAGS.slow_query_ms)
    set_flag("tracing", True)
    TRACER.clear()
    yield
    set_flag("tracing", False)
    set_flag("trace_sample_n", prev_n)
    set_flag("slow_query_ms", prev_slow)
    TRACER.clear()


def _session():
    s = Session()
    s.execute("CREATE TABLE tt (id BIGINT, v DOUBLE)")
    s.execute("INSERT INTO tt VALUES (1, 1.5), (2, 2.5), (3, 0.5)")
    return s


def _names(rec):
    return [sp["name"] for sp in rec["spans"]]


def _by_name(rec, name):
    return [sp for sp in rec["spans"] if sp["name"] == name]


# ---- span tree shape -------------------------------------------------------

def test_select_span_tree_shape(traced):
    s = _session()
    TRACER.clear()
    s.query("SELECT id, v FROM tt WHERE v > 1 ORDER BY id")
    rec = TRACER.last()
    assert rec is not None and rec["kind"] == "query"
    names = _names(rec)
    # the full lifecycle: parse -> plan -> execute -> egress
    for expected in ("parse.sql", "plan.build", "plan.cache", "exec.batches",
                     "exec.run", "egress.compact", "egress.arrow", "query"):
        assert expected in names, f"missing span {expected}: {names}"
    # nesting: every stage hangs under the one root
    by_id = {sp["span_id"]: sp for sp in rec["spans"]}
    root = _by_name(rec, "query")[0]
    assert root["parent_id"] == ""
    for nm in ("parse.sql", "exec.run"):
        sp = _by_name(rec, nm)[0]
        # walk to the root
        cur = sp
        seen = set()
        while cur["parent_id"]:
            assert cur["span_id"] not in seen
            seen.add(cur["span_id"])
            cur = by_id[cur["parent_id"]]
        assert cur is root
    # plan.logical nests under plan.build
    pl = _by_name(rec, "plan.logical")[0]
    assert by_id[pl["parent_id"]]["name"] == "plan.build"
    # first run pays a compile: the exec.run span says so
    assert _by_name(rec, "exec.run")[0]["attrs"].get("compiled") is True


def test_steady_state_run_has_no_compile_attr(traced):
    s = _session()
    q = "SELECT SUM(v) FROM tt WHERE v > 1"
    s.query(q)
    TRACER.clear()
    s.query(q)                    # cached plan, cached executable
    rec = TRACER.last()
    runs = _by_name(rec, "exec.run")
    assert runs and all("compiled" not in sp["attrs"] for sp in runs)
    assert _by_name(rec, "plan.cache")[0]["attrs"]["outcome"] \
        in ("hit", "param_hit")


# ---- tracing=off: pinned zero-span assertion -------------------------------

def test_tracing_off_records_nothing():
    assert not bool(FLAGS.tracing)
    TRACER.clear()
    before = metrics.traces_sampled.value
    s = _session()
    s.query("SELECT COUNT(*) FROM tt")
    assert TRACER.list() == []
    assert metrics.traces_sampled.value == before
    # the off-path is the shared no-op singleton: no allocation per span
    assert trace.span("anything") is trace._NOOP
    assert trace.root("query", "SELECT 1") is trace._NOOP
    assert trace.wire_context() is None


# ---- sampling + slow-query always-keep -------------------------------------

def test_head_sampling_keeps_one_in_n(traced):
    s = _session()
    set_flag("trace_sample_n", 3)
    TRACER.clear()
    before = metrics.traces_sampled.value
    for i in range(6):
        s.query(f"SELECT id FROM tt WHERE id = {i % 3}")
    kept = metrics.traces_sampled.value - before
    assert kept == 2, kept     # 6 roots / sample 1-in-3


def test_slow_query_always_kept(traced):
    s = _session()
    set_flag("trace_sample_n", 1_000_000)   # sampler keeps ~nothing
    set_flag("slow_query_ms", 0.000001)     # ...but everything is "slow"
    TRACER.clear()
    s.query("SELECT COUNT(*) FROM tt")
    assert len(TRACER.list()) >= 1


# ---- bounded store + per-trace cap ----------------------------------------

def test_store_is_bounded(traced):
    prev = int(FLAGS.trace_store_max)
    set_flag("trace_store_max", 4)
    try:
        s = _session()
        TRACER.clear()
        for i in range(8):
            s.query(f"SELECT id FROM tt WHERE id = {i % 3}")
        recs = TRACER.list()
        assert len(recs) == 4
        # oldest evicted: ids strictly increasing, newest survives
        qids = [r["query_id"] for r in recs]
        assert qids == sorted(qids)
    finally:
        set_flag("trace_store_max", prev)


def test_per_trace_span_cap(traced):
    prev = int(FLAGS.trace_max_spans)
    set_flag("trace_max_spans", 16)
    try:
        before = metrics.trace_spans_dropped.value
        with trace.root("query", "synthetic", force=True):
            for _ in range(64):
                with trace.span("noise"):
                    pass
        rec = TRACER.last()
        assert len(rec["spans"]) <= 16
        assert metrics.trace_spans_dropped.value > before
        assert rec["dropped"] > 0
    finally:
        set_flag("trace_max_spans", prev)


# ---- SHOW PROFILE round-trip -----------------------------------------------

def test_show_profile_round_trip(traced):
    s = _session()
    TRACER.clear()
    s.query("SELECT SUM(v) FROM tt")
    profiles = s.execute("SHOW PROFILES")
    assert profiles.columns[0] == "Query_ID"
    assert len(profiles.rows) == 1
    qid = profiles.rows[0][0]
    assert "SUM(v)" in profiles.rows[0][3]
    prof = s.execute(f"SHOW PROFILE FOR QUERY {qid}")
    stages = [r[0].strip() for r in prof.rows]
    assert "query" in stages and "exec.run" in stages
    # indentation encodes the tree: the root is column 0, stages are deeper
    raw = [r[0] for r in prof.rows]
    assert raw[0] == "query" and any(r.startswith("  ") for r in raw[1:])
    # bare SHOW PROFILE = most recent kept trace (and the SHOW statements
    # themselves never pollute the store they read)
    prof2 = s.execute("SHOW PROFILE")
    assert [r[0] for r in prof2.rows] == raw
    assert len(s.execute("SHOW PROFILES").rows) == 1


def test_show_profile_unknown_query_id(traced):
    s = _session()
    with pytest.raises(Exception, match="no kept trace"):
        s.execute("SHOW PROFILE FOR QUERY 999999")


# ---- EXPLAIN ANALYZE reads the same span store -----------------------------

def test_explain_analyze_single_timing_truth(traced):
    s = _session()
    TRACER.clear()
    txt = s.execute("EXPLAIN ANALYZE SELECT id, SUM(v) FROM tt "
                    "GROUP BY id").plan_text
    assert "rows=" in txt and "-- run:" in txt and "-- batch:" in txt
    rec = TRACER.last()
    assert rec is not None
    steady = _by_name(rec, "exec.steady")
    first = _by_name(rec, "exec.first")
    assert steady and first
    # the -- run: line is RENDERED from these spans — same numbers
    line = next(ln for ln in txt.split("\n") if ln.startswith("-- run:"))
    assert f"{steady[-1]['dur_ms']:.2f} ms" in line
    assert f"{first[-1]['dur_ms']:.2f} ms" in line
    # per-operator rows render from the op events in the same trace
    ops = _by_name(rec, "op")
    assert ops and any("rows" in sp["attrs"] for sp in ops)


def test_explain_analyze_survives_span_cap_exhaustion(traced):
    """A forced section renders FROM its span records: when the enclosing
    trace already spent its span budget, EXPLAIN ANALYZE must still get
    headroom for its events — not silently lose its timing lines."""
    prev = int(FLAGS.trace_max_spans)
    set_flag("trace_max_spans", 16)
    try:
        s = _session()
        s.query("SELECT COUNT(*) FROM tt")   # warm plan+executable
        with trace.root("query", "batch"):
            for _ in range(64):              # exhaust the cap
                with trace.span("noise"):
                    pass
            txt = s.execute(
                "EXPLAIN ANALYZE SELECT COUNT(*) FROM tt").plan_text
        assert "-- run:" in txt and "-- xla:" in txt and "rows=" in txt
    finally:
        set_flag("trace_max_spans", prev)


def test_explain_analyze_traces_even_when_tracing_off():
    assert not bool(FLAGS.tracing)
    TRACER.clear()
    s = _session()
    txt = s.execute("EXPLAIN ANALYZE SELECT COUNT(*) FROM tt").plan_text
    assert "-- run:" in txt and "-- xla:" in txt
    rec = TRACER.last()     # forced trace: EXPLAIN ANALYZE always keeps
    assert rec is not None and rec["kind"] == "explain_analyze"
    TRACER.clear()


# ---- information_schema surfaces -------------------------------------------

def test_trace_spans_virtual_table(traced):
    s = _session()
    TRACER.clear()
    s.query("SELECT COUNT(*) FROM tt")
    rows = s.query("SELECT name, node, duration_ms FROM "
                   "information_schema.trace_spans")
    names = {r["name"] for r in rows}
    assert "query" in names and "exec.run" in names
    assert all(r["node"] == "frontend" for r in rows)


def test_query_log_enriched_with_cache_outcome(traced):
    s = _session()
    q = "SELECT v FROM tt WHERE id = 1"
    s.query(q)
    s.query("SELECT v FROM tt WHERE id = 2")   # param-cache variant
    rows = s.query("SELECT query, cache, capacity_bucket FROM "
                   "information_schema.query_log")
    mine = [r for r in rows if "FROM tt WHERE id" in r["query"]]
    assert len(mine) >= 2
    assert mine[0]["cache"] == "miss"
    assert mine[-1]["cache"] in ("hit", "param_hit")
    # capacity bucket names the scan batch shape the query compiled against
    assert "default.tt=" in mine[0]["capacity_bucket"]


# ---- chrome trace export ---------------------------------------------------

def test_chrome_export(traced, tmp_path):
    s = _session()
    TRACER.clear()
    s.query("SELECT COUNT(*) FROM tt")
    path = str(tmp_path / "trace.json")
    n = TRACER.export_chrome(path)
    assert n > 0
    with open(path) as f:
        doc = json.load(f)
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert evs and all({"name", "ts", "dur", "pid", "tid"} <= set(e)
                       for e in evs)
    assert any(e["name"] == "exec.run" for e in evs)
    procs = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert any(p["args"]["name"] == "frontend" for p in procs)


# ---- cross-RPC stitching ---------------------------------------------------

def test_rpc_spans_stitch_under_one_trace(traced):
    """The three-binary story at protocol level: a traced frontend call
    carries trace_id/parent_span over utils/net.py; the daemon's handler
    spans ship back on the response and stitch under the rpc span."""
    from baikaldb_tpu.utils.net import RpcClient, RpcServer

    srv = RpcServer()

    def handler(x):
        with trace.span("raft.append", region=7):
            return x + 1

    srv.register("bump", handler)
    srv.start()
    try:
        cli = RpcClient(f"{srv.host}:{srv.port}")
        TRACER.clear()
        with trace.root("query", "rpc stitch"):
            assert cli.call("bump", x=41) == 42
        rec = TRACER.last()
        flat = trace.span_tree(rec)
        path = {sp["name"]: (depth, sp) for depth, sp in flat}
        assert set(path) >= {"query", "rpc.bump", "serve.bump",
                             "raft.append"}
        # one trace id; daemon spans labeled with the daemon's node
        daemon = path["raft.append"][1]["node"]
        assert daemon and daemon != "frontend"
        assert path["serve.bump"][1]["node"] == daemon
        # nesting depth: query < rpc.bump < serve.bump < raft.append
        assert path["query"][0] < path["rpc.bump"][0] \
            < path["serve.bump"][0] < path["raft.append"][0]
    finally:
        srv.stop()


def test_untraced_rpc_carries_no_header(traced):
    from baikaldb_tpu.utils.net import RpcClient, RpcServer

    seen = {}
    srv = RpcServer()

    def probe():
        seen["ctx"] = trace.wire_context()
        return 1

    srv.register("probe", probe)
    srv.start()
    try:
        cli = RpcClient(f"{srv.host}:{srv.port}")
        assert cli.call("probe") == 1      # no live trace at the client
        assert seen["ctx"] is None
    finally:
        srv.stop()


# ---- fleet mode: distributed write under one trace -------------------------

def test_fleet_distributed_write_trace(traced):
    from baikaldb_tpu.raft.core import raft_available
    if not raft_available():
        pytest.skip("native raft core unavailable")
    from baikaldb_tpu.meta.service import MetaService
    from baikaldb_tpu.raft.fleet import StoreFleet

    fleet = StoreFleet(MetaService(peer_count=3),
                       ["s1:1", "s2:1", "s3:1"], seed=7)
    s = Session(Database(fleet=fleet))
    s.execute("CREATE DATABASE trf")
    s.execute("USE trf")
    s.execute("CREATE TABLE t (a BIGINT, b BIGINT)")
    TRACER.clear()
    s.execute("BEGIN")
    s.execute("INSERT INTO t VALUES (1, 10), (2, 20)")
    s.execute("COMMIT")
    commit = next(r for r in TRACER.list() if r["text"] == "COMMIT")
    names = _names(commit)
    # frontend dispatch + store-tier raft append + binlog flush, one trace
    assert "query" in names
    assert "replicated.write" in names
    assert "raft.append" in names
    assert "binlog.flush" in names and "binlog.append" in names
    tids = {commit["trace_id"]}
    assert len(tids) == 1


# ---- metrics + accounting --------------------------------------------------

def test_traces_sampled_counter_moves(traced):
    s = _session()
    before = metrics.traces_sampled.value
    s.query("SELECT COUNT(*) FROM tt")
    assert metrics.traces_sampled.value == before + 1


def test_trace_flags_visible_in_show_variables(traced):
    s = Session()
    rows = s.execute("SHOW VARIABLES LIKE 'tracing'").rows
    assert rows and str(rows[0][1]).lower() in ("true", "1")


# ---- one seam marker, three sinks (always-on record, profiler, tree) -------

COARSE = {"parse", "plan", "exec", "egress"}
STREAMED_SPANS = {
    "parse.sql", "select.route", "snapshot.pin", "mvcc.dirty_check",
    "plan.paramize", "plan.cache", "exec.batches", "mvcc.visibility",
    "access.path", "stream.source", "plan.bind", "stream.prefetch",
    "stream.stage.read", "stream.stage.decode", "stream.stage.h2d",
    "stream.fold", "stream.sync", "stream.finalize", "egress.compact",
    "egress.arrow"}
RESIDENT_SPANS = {
    "parse.sql", "select.route", "plan.paramize", "plan.cache",
    "exec.batches", "access.path", "stream.source", "stage.resident",
    "plan.bind", "exec.run", "exec.flags", "egress.compact", "egress.arrow"}
STREAMED_Q = "SELECT g, SUM(v) FROM st WHERE v > {} GROUP BY g"
RESIDENT_Q = "SELECT v FROM st WHERE id BETWEEN {} AND 60 ORDER BY v"


@pytest.fixture
def stream_sess(tmp_path):
    """A session whose 500-row table streams in 8 chunks (the default
    flags but for the streaming size gates), both statements warmed."""
    gates = ("streaming_min_rows", "streaming_chunk_rows")
    prev = {k: getattr(FLAGS, k) for k in gates}
    set_flag("streaming_min_rows", 1)
    set_flag("streaming_chunk_rows", 64)
    s = Session(Database(cold_dir=str(tmp_path / "afs")))
    s.execute("CREATE TABLE st (id BIGINT, g BIGINT, v DOUBLE, "
              "PRIMARY KEY (id))")
    s.execute("INSERT INTO st VALUES " + ", ".join(
        f"({i}, {i % 7}, {float(i % 101)})" for i in range(500)))
    for q in (STREAMED_Q, RESIDENT_Q):
        s.query(q.format(1))
        s.query(q.format(2))
    try:
        yield s
    finally:
        set_flag("tracing", False)
        for k, v in prev.items():
            set_flag(k, v)
        TRACER.clear()


def _logged(s, sql):
    """Run ``sql``; -> the dict of its query_log row."""
    s.db.query_log.clear()
    s.query(sql)
    (row,) = s.db.query_log
    assert row[0] == sql
    return row[5]


def _top_level(rec):
    """Names of the seams directly under the root of a kept trace: the
    depth-0 spans that tile the statement (events last 0 ms; the stager's
    seams are credited from another thread and overlap the rest)."""
    (root,) = [sp for sp in rec["spans"] if sp["parent_id"] == ""]
    return {sp["name"] for sp in rec["spans"]
            if sp["parent_id"] == root["span_id"] and sp["dur_ms"] > 0
            and not sp["name"].startswith("stream.stage.")}


@pytest.mark.parametrize("sql,spans", [(STREAMED_Q, STREAMED_SPANS),
                                       (RESIDENT_Q, RESIDENT_SPANS)],
                         ids=["streamed", "resident"])
def test_span_keys_always_on_and_one_timing_truth(stream_sess, sql, spans):
    s = stream_sess
    before = metrics.traces_sampled.value
    off = _logged(s, sql.format(3))
    # default flags: nothing reaches the span store, the keys are there
    assert TRACER.list() == [] and metrics.traces_sampled.value == before
    assert COARSE <= set(off) and spans <= set(off)
    assert all("." in k for k in set(off) - COARSE
               - {"starting", "query", "untraced"})
    set_flag("tracing", True)
    on = _logged(s, sql.format(4))

    def walls(d):
        return {k for k in d if not k.endswith("/wait")}

    assert walls(on) == walls(off)
    rec = TRACER.last()
    top = _top_level(rec)
    for d in (off, on):
        # the depth-0 spans and ``untraced`` tile the statement
        assert d["untraced"] >= 0
        assert abs(sum(d[k] for k in top) + d["untraced"] - d["query"]) < 1.0
        assert d["query"] >= d["parse"] + d["plan"] + d["exec"] + d["egress"]
    # the tree and the record were fed by the same clock reads
    for name in spans:
        tree = [sp["dur_ms"] for sp in rec["spans"] if sp["name"] == name]
        assert tree, name
        assert abs(sum(tree) - on[name]) < 1e-4 * len(tree) + 1e-9, name
    # SHOW PROFILE renders those records
    shown = {r[0].strip(): r[1] for r in
             s.execute(f"SHOW PROFILE FOR QUERY {rec['query_id']}").rows}
    assert abs(float(shown["exec.batches"]) - on["exec.batches"]) < 1e-3


def test_stager_thread_times_reach_the_statement(stream_sess):
    s = stream_sess
    set_flag("tracing", True)
    d = _logged(s, STREAMED_Q.format(5))
    (ev,) = _by_name(TRACER.last(), "stream")
    staged = sum(d[f"stream.stage.{k}"] for k in ("read", "decode", "h2d"))
    assert staged > 0
    assert abs(staged - ev["attrs"]["stage_ms"]) < 1e-3
    # with the tree live the stager splits its chunks too: a wait, and no
    # second record of the seams' wall time
    assert "stream.stage" not in d
    assert 0 <= d["stream.stage/wait"] <= staged * 1.1 + 1.0
    # stager time overlaps the fold: it is no part of the tiling
    assert d["untraced"] >= 0


@contextmanager
def _time_limit(seconds: int):
    def expired(signum, frame):
        raise TimeoutError(f"no end after {seconds} s")
    prev = signal.signal(signal.SIGALRM, expired)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def test_spans_land_on_the_profilers_host_plane(stream_sess, tmp_path):
    import jax
    from jax.profiler import ProfileData

    from benchmark import trace_reduce
    from tools.span_profile import by_program_span, program_spans

    s = stream_sess
    assert not bool(FLAGS.tracing)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with _time_limit(120):
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            s.query(STREAMED_Q.format(6))
            s.query(RESIDENT_Q.format(6))
        finally:
            jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(str(tmp_path))
        planes = list(ProfileData.from_file(path).planes)
        names = {ev.name for plane in planes
                 if plane.name == trace_reduce.HOST_PLANE
                 for line in plane.lines for ev in line.events}
    want = {"db." + n for n in STREAMED_SPANS | RESIDENT_SPANS}
    assert want <= names, want - names
    assert not [n for n in names if n.startswith("client.")]
    # the stager's seams are on a thread of their own, and the reduction
    # gives every moment of a thread to its innermost span
    segs = program_spans(planes)
    assert {"db.mvcc.visibility", "db.stream.stage.decode"} <= \
        {n for _, _, n in segs}
    assert all(e > b for b, e, _ in segs)
    # no chip in this trace: nothing is busy, nothing idle, no module ran
    reduced = by_program_span(str(tmp_path), 1, (0.0, 1.0))
    assert reduced["busy_s"] == reduced["idle_s"] == reduced["modules"] == []
    # outside a profiler session a seam opens no annotation
    assert trace._annotation() is None


def test_txn_counters_count():
    s = _session()
    c0, r0 = metrics.txn_commits.value, metrics.txn_rollbacks.value
    s.execute("COMMIT")                     # outside a txn: counts nothing
    s.execute("BEGIN")
    s.execute("INSERT INTO tt VALUES (9, 9.5)")
    s.execute("COMMIT")
    s.execute("BEGIN")
    s.execute("BEGIN")                      # implicit commit of the first
    s.execute("ROLLBACK")
    assert metrics.txn_commits.value - c0 == 2
    assert metrics.txn_rollbacks.value - r0 == 1
    assert not hasattr(metrics, "wal_appends")


def test_point_lookup_and_wire_feed_their_counters():
    from baikaldb_tpu.client.mysql_client import Connection
    from baikaldb_tpu.server.mysql_server import MySQLServer

    db = Database()
    s = Session(db=db)
    s.execute("CREATE TABLE pk (id BIGINT, v DOUBLE, PRIMARY KEY (id))")
    s.execute("INSERT INTO pk VALUES (1, 1.5), (2, 2.5)")
    srv = MySQLServer(db, port=0).start()
    try:
        conn = Connection(port=srv.port)
        p0 = metrics.point_lookup_ms.value
        w0 = metrics.wire_result_set_ms.value
        n0 = metrics.point_lookups.value
        assert conn.query("SELECT v FROM pk WHERE id = 2").rows
        conn.close()
    finally:
        srv.stop()
    assert metrics.point_lookups.value == n0 + 1
    assert metrics.point_lookup_ms.value > p0
    # the server thread feeds the counter after its last socket write
    deadline = time.monotonic() + 10
    while metrics.wire_result_set_ms.value <= w0 \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert metrics.wire_result_set_ms.value > w0


def test_explain_analyze_streamed_has_no_device_line(stream_sess):
    """A streamed statement has no executable under its plan's signature:
    the line used to show the newest OTHER program's cost."""
    s = stream_sess
    resident = s.execute(
        "EXPLAIN ANALYZE " + RESIDENT_Q.format(7)).plan_text
    assert "-- device:" in resident
    streamed = s.execute(
        "EXPLAIN ANALYZE " + STREAMED_Q.format(7)).plan_text
    assert "-- stream: chunks=8/8" in streamed
    assert "-- device:" not in streamed


# ---- work and wait: the thread's CPU clock beside the wall clock ----------

def _spin(ms: float) -> None:
    """~``ms`` of pure Python on this thread's CPU clock."""
    end = time.thread_time() + ms / 1e3
    while time.thread_time() < end:
        pass


def test_span_wait_is_the_threads_off_cpu_time():
    from baikaldb_tpu.obs import progress

    with progress.track("wait test") as qp:
        with trace.root("query", "wait test", force=True):
            with trace.timed("t.sleep") as slept:
                time.sleep(0.05)
            # pure Python: what it did not run is descheduling alone, so
            # the least of three tries (a loaded test host preempts some)
            worked = []
            for _ in range(3):
                with trace.span("t.work") as sp:
                    _spin(50)
                worked.append(sp)
        # the tree has closed: the record's own spans keep wall time alone
        with trace.timed("t.after") as after:
            pass
        got = qp.phase_ms()
    assert slept.ms >= 50 and slept.wait_ms >= 40
    assert slept.wait_ms <= slept.ms
    assert min(sp.wait_ms for sp in worked) <= 10
    assert all(sp.ms >= 50 and 0 <= sp.wait_ms <= sp.ms for sp in worked)
    # the record keeps the wait beside the wall time, under a key no span
    # name can take
    assert got["t.sleep/wait"] == slept.wait_ms
    assert got["t.work"] == pytest.approx(sum(sp.ms for sp in worked))
    assert got["t.work/wait"] == pytest.approx(
        sum(sp.wait_ms for sp in worked))
    assert after.wait_ms is None
    assert "t.after" in got and "t.after/wait" not in got
    TRACER.clear()


def test_query_log_row_splits_only_under_a_tree(stream_sess):
    s = stream_sess
    d = _logged(s, STREAMED_Q.format(8))
    # default flags: no key splits, and no CPU clock was read for one
    assert not [k for k in d if k.endswith("/wait")]
    assert COARSE | STREAMED_SPANS | {"query", "untraced"} <= set(d)
    # a live tree splits the statement, the stager and every span
    set_flag("tracing", True)
    on = _logged(s, STREAMED_Q.format(9))
    assert 0 <= on["query/wait"] <= on["query"]
    stager = {"stream.stage.read", "stream.stage.decode", "stream.stage.h2d"}
    assert 0 <= on["stream.stage/wait"] <= \
        sum(on[k] for k in stager) * 1.1 + 1.0
    for name in STREAMED_SPANS - stager:
        assert 0 <= on[f"{name}/wait"] <= on[name] + 1e-6, name
    assert not {f"{k}/wait" for k in stager} & set(on)
    rec = TRACER.last()
    for sp in _by_name(rec, "stream.prefetch"):
        assert 0 <= sp["wait_ms"] <= sp["dur_ms"] + 1e-3


def test_add_wait_lands_under_wait_alone(traced):
    from baikaldb_tpu.obs import progress

    with progress.track("add test") as qp:
        with trace.root("query", "add test"):
            trace.add_wait("stream.stage", 2.0)
            trace.add_wait("stream.stage", 0.5)
            trace.add("other.thread", 1.0)
        got = qp.phase_ms()
    assert got["stream.stage/wait"] == 2.5
    assert "stream.stage" not in got
    assert "other.thread" in got and "other.thread/wait" not in got
    # the wait makes no record of the tree: its wall time is the seams'
    rec = TRACER.last()
    assert not _by_name(rec, "stream.stage")
    assert "wait_ms" not in _by_name(rec, "other.thread")[0]
    # and no statement, nothing to credit
    trace.add_wait("stream.stage", 1.0)


@pytest.mark.parametrize("tracing", [False, True])
def test_wait_counters_grow_only_under_a_tree(monkeypatch, tracing):
    import threading

    from baikaldb_tpu.client.mysql_client import Connection
    from baikaldb_tpu.server.mysql_server import MySQLServer
    from baikaldb_tpu.storage.column_store import TableStore

    # a read that blocks for 2 ms: off the CPU inside point.lookup
    lookup = TableStore.point_lookup
    monkeypatch.setattr(TableStore, "point_lookup", lambda self, pk: (
        time.sleep(0.002), lookup(self, pk))[1])
    db = Database()
    s = Session(db=db)
    s.execute("CREATE TABLE pw (id BIGINT, v DOUBLE, PRIMARY KEY (id))")
    s.execute("INSERT INTO pw VALUES (1, 1.5), (2, 2.5)")
    srv = MySQLServer(db, port=0).start()
    # a thread that never drops the interpreter lock of its own accord:
    # every other thread waits up to a switch interval to take it back
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    spinner = threading.Thread(target=spin, daemon=True)
    counters = (metrics.point_lookups, metrics.point_lookup_ms,
                metrics.point_lookup_wait_ms, metrics.wire_result_set_ms,
                metrics.wire_result_set_wait_ms)
    set_flag("tracing", tracing)
    try:
        conn = Connection(port=srv.port)
        before = [c.value for c in counters]
        spinner.start()
        for i in range(20):
            assert conn.query(f"SELECT v FROM pw WHERE id = {1 + i % 2}").rows
        conn.close()
    finally:
        set_flag("tracing", False)
        TRACER.clear()
        stop.set()
        spinner.join()
        srv.stop()
    reads, read_ms, read_wait, wire_ms, wire_wait = (
        c.value - b for c, b in zip(counters, before))
    assert reads == 20 and read_ms >= 20 * 1.5 and wire_ms > 0
    if not tracing:
        assert read_wait == 0 and wire_wait == 0
        return
    assert 20 * 1.5 <= read_wait <= read_ms
    # the send drops the interpreter lock, and the spinner holds it then
    assert 0 < wire_wait <= wire_ms


def test_process_cpu_ms_is_a_status_row_that_never_falls():
    s = Session()

    def read():
        (row,) = s.execute("SHOW STATUS LIKE 'process_cpu_ms%'").rows
        assert row[0] == "process_cpu_ms.value"
        return float(row[1])

    first = read()
    _spin(20)
    second = read()
    assert 0 < first and second >= first + 15


def test_show_profile_has_the_wait_column(traced):
    s = _session()
    TRACER.clear()
    s.query("SELECT SUM(v) FROM tt")
    prof = s.execute("SHOW PROFILE")
    assert prof.columns == ["Status", "Duration_ms", "Node", "Wait_ms"]
    waits = {status.strip(): wait for status, _, _, wait in prof.rows}
    assert {"query", "parse.sql", "exec.run", "egress.arrow"} <= set(waits)
    for status, dur, _node, wait in prof.rows:
        # an event (no duration) has no wait; a span of the tree has one
        assert wait is None or 0 <= wait <= dur + 1e-3, status
    rec = TRACER.last()
    assert all("wait_ms" in sp for sp in rec["spans"]
               if sp["dur_ms"] > 0), _names(rec)


def test_the_cpu_clock_is_read_only_under_a_tree(monkeypatch):
    from baikaldb_tpu.obs import progress

    reads = []
    real = trace._cpu
    monkeypatch.setattr(trace, "_cpu", lambda: reads.append(1) or real())
    monkeypatch.setattr(progress.time, "thread_time_ns",
                        lambda: reads.append(1) or real())
    assert progress.live() is None and not trace.active()
    with trace.span("nothing.live") as sp:
        pass
    assert sp is trace._NOOP and reads == []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __exit__(self, *exc):
            return False

    with monkeypatch.context() as m:
        m.setattr(trace, "_annotation", lambda: Annotation)
        with trace.span("profiler.only") as sp:
            pass
        assert sp is not trace._NOOP and sp.wait_ms is None
    with progress.track("record live") as qp:
        with trace.span("record.only"):
            pass
        with trace.timed("stopwatch.only") as sp:
            pass
        assert "query/wait" not in qp.logged_ms()
        assert reads == [] and sp.wait_ms is None
        # a forced root makes the tree live: the statement marks its
        # clocks once, and each span reads the CPU clock twice
        with trace.root("explain_analyze", force=True):
            assert len(reads) == 2
            with trace.timed("under.tree") as sp:
                pass
            assert len(reads) == 4 and sp.wait_ms >= 0
            assert qp.logged_ms()["query/wait"] >= 0
    TRACER.clear()
