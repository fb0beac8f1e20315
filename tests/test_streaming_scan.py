"""Out-of-core streaming scans (exec/streaming.py + storage/streamchunks.py).

The contract under test: for every plan streaming accepts, the chunk fold
is BIT-IDENTICAL to the resident path — the off-switch is a no-op on
results.  All fixtures use integer-valued doubles so sums/sumsq are exact
in f64 regardless of fold order (the partial-merge protocol changes the
addition order; exactness makes order irrelevant, which is what makes
"bit-identical" testable).

Matrix: grouped SUM/COUNT/AVG/STDDEV over int, string and NULL keys with
groups spanning chunk boundaries; scalar aggregates; zone-map chunk skip;
non-dividing chunk sizes; the off-switch; overflow-restart of the sorted
accumulator; the observability surfaces (EXPLAIN ANALYZE ``-- stream:``
line, access path, processlist columns, stream_* metrics); and projected
staging (a chunk carries only the columns its plan reads).
"""

import datetime
import re
import sys
import threading

import pytest

from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.exec.streaming import StreamRunner
from baikaldb_tpu.obs.trace import TRACER
from baikaldb_tpu.storage.streamchunks import StreamChunkSet
from baikaldb_tpu.utils import metrics
from baikaldb_tpu.utils.flags import FLAGS, set_flag

CHUNK = 64
ROWS = 500                      # ~8 chunks: >= 4x the per-chunk budget

_STREAM_FLAGS = ("streaming_scan", "streaming_min_rows",
                 "streaming_chunk_rows")


@pytest.fixture
def sess(tmp_path):
    prev = {k: getattr(FLAGS, k) for k in _STREAM_FLAGS}
    set_flag("streaming_scan", True)
    set_flag("streaming_min_rows", 1)       # every table is "too big"
    set_flag("streaming_chunk_rows", CHUNK)
    s = Session(Database(cold_dir=str(tmp_path / "afs")))
    try:
        yield s
    finally:
        for k, v in prev.items():
            set_flag(k, v)


def _load(s, n=ROWS, batch=100):
    """id 0..n-1 in insert order (zone maps see monotone id ranges);
    g cycles 0..6 and sv cycles 'a'..'d'/NULL so every group's rows span
    every chunk; v/w integer-valued doubles."""
    s.execute("CREATE TABLE t (id BIGINT, g BIGINT, sv VARCHAR(8), "
              "v DOUBLE, w DOUBLE, PRIMARY KEY (id))")
    svs = ["'a'", "'b'", "'c'", "'d'", "NULL"]
    for lo in range(0, n, batch):
        rows = ", ".join(
            f"({i}, {i % 7}, {svs[i % 5]}, {float(i % 101)}, "
            f"{float((i * 3) % 53)})"
            for i in range(lo, min(lo + batch, n)))
        s.execute(f"INSERT INTO t VALUES {rows}")


def _both(s, sql):
    """(streamed, resident) results for ``sql`` with the same cached plan;
    returns them with the stream_chunks delta of the streamed run."""
    c0 = metrics.stream_chunks.value
    streamed = s.query(sql)
    folded = metrics.stream_chunks.value - c0
    set_flag("streaming_scan", False)
    try:
        resident = s.query(sql)
    finally:
        set_flag("streaming_scan", True)
    return streamed, resident, folded


# ---- bit-identity ---------------------------------------------------------

def test_grouped_agg_bit_identical(sess):
    _load(sess)
    streamed, resident, folded = _both(
        sess,
        "SELECT g, SUM(v) s, COUNT(*) n, COUNT(w) nw, AVG(v) a, "
        "STDDEV(v) sd, MIN(w) mn, MAX(w) mx "
        "FROM t WHERE id < 400 GROUP BY g ORDER BY g")
    assert streamed == resident
    assert len(streamed) == 7
    assert folded >= 4          # the whole table folded chunk by chunk


def test_scalar_agg_bit_identical(sess):
    _load(sess)
    streamed, resident, folded = _both(
        sess,
        "SELECT SUM(v) s, COUNT(*) n, COUNT(sv) ns, AVG(w) a, "
        "MIN(v) mn, MAX(v) mx FROM t WHERE v > 10.0")
    assert streamed == resident
    assert folded >= 4


def test_string_and_null_keys_span_chunks(sess):
    """sv cycles with period 5 against a 64-row chunk: every group
    (including the NULL group) has members in every chunk, so the merge
    must fold the same key across chunk boundaries."""
    _load(sess)
    streamed, resident, folded = _both(
        sess,
        "SELECT sv, COUNT(*) n, SUM(v) s, AVG(w) a FROM t "
        "GROUP BY sv ORDER BY n DESC, sv")
    assert streamed == resident
    assert len(streamed) == 5           # 'a'..'d' + the NULL key group
    assert folded >= 4


def test_multi_key_grouped_stddev(sess):
    _load(sess)
    streamed, resident, _ = _both(
        sess,
        "SELECT g, sv, COUNT(*) n, STDDEV(v) sd, VARIANCE(w) vr "
        "FROM t GROUP BY g, sv ORDER BY g, n, sv")
    assert streamed == resident


def test_non_dividing_chunk_size(sess):
    _load(sess, n=100)                  # 64 + 36: a ragged tail chunk
    streamed, resident, folded = _both(
        sess, "SELECT g, SUM(v) s, COUNT(*) n FROM t GROUP BY g ORDER BY g")
    assert streamed == resident
    assert folded == 2


def test_scalar_stddev_falls_back_resident(sess):
    """Keyless STDDEV uses the mean-centered kernel formula — no
    bit-identical partial form, so eligibility must reject it (the query
    still answers, on the resident path)."""
    _load(sess, n=100)
    c0 = metrics.stream_chunks.value
    got = sess.query("SELECT STDDEV(v) sd FROM t")
    assert metrics.stream_chunks.value == c0    # nothing folded
    set_flag("streaming_scan", False)
    try:
        assert got == sess.query("SELECT STDDEV(v) sd FROM t")
    finally:
        set_flag("streaming_scan", True)


# ---- zone maps ------------------------------------------------------------

def test_zonemap_skips_chunks(sess):
    """id is monotone in insert order, so chunk zone maps carry disjoint
    id ranges: WHERE id >= 384 keeps only the last two chunks — the rest
    skip BEFORE any host->device transfer."""
    _load(sess)
    skip0 = metrics.stream_chunks_skipped.value
    streamed, resident, folded = _both(
        sess,
        "SELECT g, COUNT(*) n, SUM(v) s FROM t WHERE id >= 384 "
        "GROUP BY g ORDER BY g")
    assert streamed == resident
    assert metrics.stream_chunks_skipped.value - skip0 >= 4
    assert folded <= 2


def test_zonemap_prunes_everything(sess):
    """No chunk survives: the fold still runs once over a dead chunk so
    COUNT renders 0 (a row), not an empty result set."""
    _load(sess, n=100)
    streamed, resident, folded = _both(
        sess, "SELECT COUNT(*) n, SUM(v) s FROM t WHERE id > 100000")
    assert streamed == resident == [{"n": 0, "s": None}]
    assert folded == 0                  # dead folds don't count chunks


# ---- the off-switch -------------------------------------------------------

def test_off_switch_resident_path(sess):
    _load(sess, n=100)
    set_flag("streaming_scan", False)
    c0 = metrics.stream_chunks.value
    got = sess.query("SELECT g, SUM(v) s FROM t GROUP BY g ORDER BY g")
    assert metrics.stream_chunks.value == c0
    assert len(got) == 7


def test_min_rows_gate(sess):
    set_flag("streaming_min_rows", 10_000)
    _load(sess, n=100)
    c0 = metrics.stream_chunks.value
    sess.query("SELECT SUM(v) s FROM t")
    assert metrics.stream_chunks.value == c0    # table under the floor


# ---- overflow restart -----------------------------------------------------

def test_sorted_overflow_restart(sess, monkeypatch):
    """Clamp the sorted accumulator to 4 slots after the first compile:
    500 (sv, v) groups overflow it mid-fold, the runner doubles and
    re-folds — results stay bit-identical and stream_restarts moves."""
    _load(sess)
    orig = StreamRunner._ensure_step
    state = {"clamped": False}

    def clamped(self, source, params):
        orig(self, source, params)
        if not state["clamped"] and self.keys \
                and self.agg.strategy == "sorted" and self.acc_cap > 4:
            state["clamped"] = True
            self.acc_cap = 4
            self._jit_step = None
            orig(self, source, params)

    monkeypatch.setattr(StreamRunner, "_ensure_step", clamped)
    r0 = metrics.stream_restarts.value
    sess.db.query_log.clear()
    set_flag("tracing", True)       # the stager splits its chunks' waits
    try:
        streamed, resident, _ = _both(
            sess,
            "SELECT sv, v, COUNT(*) n, SUM(w) s FROM t "
            "GROUP BY sv, v ORDER BY sv, v, n")
    finally:
        set_flag("tracing", False)
        TRACER.clear()
    assert state["clamped"]             # the clamp actually bit
    assert metrics.stream_restarts.value - r0 >= 1
    assert streamed == resident
    assert len(streamed) == 500
    # each fold credits its own chunks' waits once: together they are part
    # of both folds' seams, not the first fold's counted twice
    phases = sess.db.query_log[0][5]
    seams = sum(phases[f"stream.stage.{k}"] for k in ("read", "decode", "h2d"))
    assert "stream.stage" not in phases
    assert 0 <= phases["stream.stage/wait"] <= seams * 1.1 + 1.0


# ---- parameterized re-runs share the runner -------------------------------

def test_param_rebind_same_plan(sess):
    """Two literals, one plan shape: the cached StreamRunner re-folds with
    new bound params, no re-trace needed for correctness."""
    _load(sess)
    for bound in (100, 300):
        streamed, resident, _ = _both(
            sess,
            f"SELECT g, SUM(v) s, COUNT(*) n FROM t WHERE id < {bound} "
            "GROUP BY g ORDER BY g")
        assert streamed == resident


# ---- observability surfaces -----------------------------------------------

def test_explain_analyze_stream_line(sess):
    _load(sess)
    out = sess.query("EXPLAIN ANALYZE SELECT g, SUM(v) s FROM t "
                     "WHERE id < 400 GROUP BY g")
    text = "\n".join(r[next(iter(r))] for r in out)
    m = re.search(r"-- stream: chunks=(\d+)/(\d+) skipped=(\d+) "
                  r"bytes_h2d=(\d+) prefetch_wait_ms=([\d.]+) "
                  r"stage_ms=([\d.]+) restarts=(\d+)", text)
    assert m, text
    chunks, total, skipped = int(m.group(1)), int(m.group(2)), int(m.group(3))
    assert total == 8 and chunks + skipped <= total and chunks >= 4
    assert int(m.group(4)) > 0          # real bytes moved host->device
    # the overlap measurement: prefetch wait is what the fold loop BLOCKED
    # on, staging is the serial copy cost.  Overlap keeps wait under the
    # serial cost; generous slack absorbs CI timer jitter.
    wait, stage = float(m.group(5)), float(m.group(6))
    assert wait <= stage * 1.5 + 50.0
    assert "stream(" in text            # scan access path names the chunks


def test_processlist_has_chunk_columns(sess):
    _load(sess, n=100)
    sess.query("SELECT SUM(v) s FROM t")
    rows = sess.query("SELECT * FROM information_schema.processlist")
    assert rows and "chunk_no" in rows[0] and "chunks_total" in rows[0]


def test_stream_metrics_move(sess):
    _load(sess)
    c0 = metrics.stream_chunks.value
    b0 = metrics.stream_bytes_h2d.value
    sess.query("SELECT g, SUM(v) s FROM t GROUP BY g")
    assert metrics.stream_chunks.value - c0 >= 4
    assert metrics.stream_bytes_h2d.value > b0


# ---- projected staging ----------------------------------------------------
# A streamed scan stages only its plan's ScanNode.columns (plus sel): what
# the plan never reads is neither decoded nor put.  Sizes below come from
# the dtypes: INT / DATE / string codes int32, BIGINT / DOUBLE 8 bytes,
# validity and sel one byte a row.

_LINEITEM = ("CREATE TABLE lineitem (l_orderkey INT, l_partkey INT, "
             "l_suppkey INT, l_linenumber INT, l_quantity DOUBLE, "
             "l_extendedprice DOUBLE, l_discount DOUBLE, l_tax DOUBLE, "
             "l_returnflag VARCHAR(1), l_linestatus VARCHAR(1), "
             "l_shipdate DATE, l_commitdate DATE, l_receiptdate DATE, "
             "l_shipinstruct VARCHAR(25), l_shipmode VARCHAR(10), "
             "l_comment VARCHAR(44))")

# TPC-H Q1 / Q6 over lineitem: 7 and 4 of its 16 columns
_Q1 = ("SELECT l_returnflag, l_linestatus, SUM(l_quantity) AS sum_qty, "
       "SUM(l_extendedprice) AS sum_base_price, "
       "SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
       "SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge, "
       "AVG(l_quantity) AS avg_qty, AVG(l_extendedprice) AS avg_price, "
       "AVG(l_discount) AS avg_disc, COUNT(*) AS count_order FROM lineitem "
       "WHERE l_shipdate <= '{cutoff}' GROUP BY l_returnflag, l_linestatus "
       "ORDER BY l_returnflag, l_linestatus")
_Q6 = ("SELECT SUM(l_extendedprice * l_discount) AS revenue FROM lineitem "
       "WHERE l_shipdate >= '1994-01-01' AND l_shipdate < '1995-01-01' "
       "AND l_discount BETWEEN 0.2 AND 0.3 AND l_quantity < {quantity}")
_Q1_COLS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_returnflag", "l_linestatus", "l_shipdate")
_Q6_COLS = ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate")


_DAY0 = datetime.date(1993, 1, 1)


def _load_lineitem(s, n=300, batch=100):
    """A lineitem-shaped table with dyadic prices, discounts and taxes, so
    every sum is exact in f64 whatever the fold order; ship dates spread
    over 1993-1998 out of id order, so no chunk is zone-map pruned."""
    s.execute(_LINEITEM)
    flags, status = ("'A'", "'N'", "'R'"), ("'F'", "'O'")
    for lo in range(0, n, batch):
        rows = []
        for i in range(lo, min(lo + batch, n)):
            ship = f"'{_DAY0 + datetime.timedelta(days=(i * 37) % 2000)}'"
            rows.append(
                f"({i // 4}, {i % 97}, {i % 13}, {i % 4 + 1}, "
                f"{float(i % 50 + 1)}, {float((i * 7) % 1000)}, "
                f"{(i % 3) * 0.25}, {(i % 2) * 0.5}, {flags[i % 3]}, "
                f"{status[i % 2]}, {ship}, {ship}, {ship}, 'NONE', 'MAIL', "
                f"'c{i}')")
        s.execute("INSERT INTO lineitem VALUES " + ", ".join(rows))


def _stream_line(s, sql):
    out = s.query("EXPLAIN ANALYZE " + sql)
    text = "\n".join(r[next(iter(r))] for r in out)
    m = re.search(r"-- stream: chunks=(\d+)/\d+ skipped=\d+ bytes_h2d=(\d+)",
                  text)
    assert m, text
    return int(m.group(1)), int(m.group(2))


@pytest.mark.parametrize("table,sql,ncols,row_bytes", [
    # TPC-H Q1: four DOUBLE + two string codes + one DATE, and sel
    ("lineitem", _Q1.format(cutoff="1998-09-02"), 7, 4 * 8 + 3 * 4 + 1),
    # TPC-H Q6: three DOUBLE + one DATE, and sel
    ("lineitem", _Q6.format(quantity=24), 4, 3 * 8 + 4 + 1),
    # COUNT(*) scans the one column the planner keeps (id BIGINT)
    ("t", "SELECT COUNT(*) n FROM t", 1, 8 + 1),
    # a filter's columns are the scan's: id and v
    ("t", "SELECT COUNT(*) n FROM t WHERE v > 10.0", 2, 8 + 8 + 1),
    # sv holds NULLs, so its validity rides with its codes
    ("t", "SELECT COUNT(sv) ns FROM t", 1, 4 + 1 + 1),
    # v holds none: no validity staged
    ("t", "SELECT SUM(v) s FROM t", 1, 8 + 1),
    # a string GROUP BY key and a DOUBLE
    ("t", "SELECT sv, SUM(w) s, COUNT(*) n FROM t GROUP BY sv "
          "ORDER BY n DESC, sv", 2, 4 + 1 + 8 + 1),
], ids=["q1", "q6", "count_star", "count_star_filtered", "with_validity",
        "without_validity", "string_key"])
def test_staged_bytes_are_the_plans_columns(sess, table, sql, ncols,
                                            row_bytes):
    (_load_lineitem if table == "lineitem" else _load)(sess)
    b0 = metrics.stream_bytes_h2d.value
    k0 = metrics.stream_columns_staged.value
    streamed, resident, folded = _both(sess, sql)
    assert streamed == resident
    assert folded >= 4
    assert metrics.stream_bytes_h2d.value - b0 == folded * CHUNK * row_bytes
    assert metrics.stream_columns_staged.value - k0 == folded * ncols
    chunks, nbytes = _stream_line(sess, sql)
    assert nbytes == chunks * CHUNK * row_bytes


def test_lineitem_full_width_is_sixteen_columns(sess):
    """The parent's per-chunk bytes, for scale: a plan that reads every
    lineitem column stages all 16 (12 four-byte, 4 eight-byte) and sel —
    81 bytes a row against Q1's 45 and Q6's 29."""
    _load_lineitem(sess)
    cols = ("l_orderkey, l_partkey, l_suppkey, l_linenumber, l_quantity, "
            "l_extendedprice, l_discount, l_tax, l_returnflag, l_linestatus, "
            "l_shipdate, l_commitdate, l_receiptdate, l_shipinstruct, "
            "l_shipmode, l_comment")
    sql = (f"SELECT {cols}, COUNT(*) n FROM lineitem GROUP BY {cols} "
           "ORDER BY l_orderkey, l_linenumber")
    k0 = metrics.stream_columns_staged.value
    streamed, resident, folded = _both(sess, sql)
    assert streamed == resident and len(streamed) == 300
    assert metrics.stream_columns_staged.value - k0 == folded * 16
    chunks, nbytes = _stream_line(sess, sql)
    assert nbytes == chunks * CHUNK * 81


def test_show_status_counts_staged_columns(sess):
    _load_lineitem(sess)
    sess.query(_Q6.format(quantity=24))
    rows = sess.query("SHOW STATUS LIKE 'stream_columns_staged.value'")
    assert int(rows[0]["Value"]) == metrics.stream_columns_staged.value > 0


def test_projections_share_one_chunk_set(sess):
    """Q1 and Q6 interleaved over one cached chunk set, then from two
    sessions on two threads at once: the set holds no per-query state, so
    each execution stages its own projection and answers right."""
    ROUNDS = 6
    _load_lineitem(sess)
    q1, q6 = _Q1.format(cutoff="1998-09-02"), _Q6.format(quantity=24)
    want = {}
    set_flag("streaming_scan", False)
    try:
        for sql in (q1, q6):
            want[sql] = sess.query(sql)
    finally:
        set_flag("streaming_scan", True)
    store = sess.db.stores["default.lineitem"]
    seen: list = []
    orig = StreamChunkSet.load_chunk

    def recording(self, i, dead, columns):
        seen.append(columns)
        return orig(self, i, dead, columns)

    StreamChunkSet.load_chunk = recording
    try:
        for sql in (q1, q6, q1, q6):
            assert sess.query(sql) == want[sql]
        cs = store._stream_chunks
        assert set(seen) == {_Q1_COLS, _Q6_COLS}

        other = Session(sess.db)
        got: dict = {q1: [], q6: []}
        errors: list = []
        start = threading.Barrier(2, timeout=60)

        def client(s, sql):
            try:
                start.wait()
                for _ in range(ROUNDS):
                    got[sql].append(s.query(sql))
            except Exception as e:      # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(sess, q1)),
                   threading.Thread(target=client, args=(other, q6))]
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)     # interleave the two folds finely
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
        finally:
            sys.setswitchinterval(switch)
        assert not any(th.is_alive() for th in threads)
    finally:
        StreamChunkSet.load_chunk = orig
    assert not errors, errors
    assert got[q1] == [want[q1]] * ROUNDS
    assert got[q6] == [want[q6]] * ROUNDS
    assert store._stream_chunks is cs       # one set served both
    assert set(seen) == {_Q1_COLS, _Q6_COLS}


def test_projected_rebinds_retrace_nothing(sess, monkeypatch):
    """Fresh Q1 / Q6 parameters rebind the cached plans: the fold step
    traces once a plan, not once an execution."""
    _load_lineitem(sess)
    traced = {"n": 0}
    orig = StreamRunner._step

    def counting(self, carry, chunk, params):
        traced["n"] += 1
        return orig(self, carry, chunk, params)

    monkeypatch.setattr(StreamRunner, "_step", counting)
    sess.query(_Q1.format(cutoff="1998-09-02"))
    sess.query(_Q6.format(quantity=24))
    settled = traced["n"]
    assert settled > 0
    for cutoff, quantity in (("1998-08-01", 25), ("1998-10-01", 24)):
        for sql in (_Q1.format(cutoff=cutoff), _Q6.format(quantity=quantity)):
            streamed, resident, _ = _both(sess, sql)
            assert streamed == resident
    assert traced["n"] == settled


def test_pushed_filter_outside_projection_is_refused():
    """The staged columns must cover what the scan's pushed filter reads;
    a plan that broke that would fail inside the fold's trace, so it is
    refused up front."""
    from baikaldb_tpu.exec.executor import ExecError
    from baikaldb_tpu.exec.streaming import staged_columns
    from baikaldb_tpu.expr.ast import call, col
    from baikaldb_tpu.plan.nodes import ScanNode

    names = ("id", "g", "v")
    scan = ScanNode(table_key="default.t", label="t", columns=["v", "id"],
                    pushed_filter=call("gt", col("t.v"), 1.0))
    assert staged_columns(scan, names) == ("id", "v")   # table order
    scan.pushed_filter = call("gt", col("t.g"), 1)
    with pytest.raises(ExecError, match="outside its staged columns"):
        staged_columns(scan, names)
