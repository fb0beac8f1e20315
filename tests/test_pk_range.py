"""The pk_range access path (index/selector.py, exec/session.py
``_access_path_batch``, storage/column_store.py ``pk_range_scan``): a range
on a single-column primary key reads its rows out of the resident device
image into one fixed capacity bucket.  Answers equal the full scan's, every
literal shares one compiled program a statement shape, and the arm follows
the table through writes, transactions and pins."""

import json
import random
import subprocess
import sys
from pathlib import Path

import pytest

from baikaldb_tpu.column import batch as batch_mod
from baikaldb_tpu.exec.session import Session
from baikaldb_tpu.index import selector
from baikaldb_tpu.utils import metrics

ROOT = Path(__file__).resolve().parents[1]
N = 6000
# sysbench's four range statements (oltp_read_only.lua)
SHAPES = [
    "SELECT c FROM sb WHERE {w}",
    "SELECT SUM(k) FROM sb WHERE {w}",
    "SELECT c FROM sb WHERE {w} ORDER BY c",
    "SELECT DISTINCT c FROM sb WHERE {w} ORDER BY c",
]


def _row(i: int) -> str:
    return f"({i},{(i * 7919) % 1000},'c{(i * 31) % 977:05d}','p{i % 5}')"


def _load(s: Session, ids) -> None:
    ids = list(ids)
    for at in range(0, len(ids), 2000):
        s.execute("INSERT INTO sb VALUES "
                  + ",".join(_row(i) for i in ids[at:at + 2000]))


@pytest.fixture
def sess():
    s = Session()
    s.execute("CREATE TABLE sb (id BIGINT PRIMARY KEY, k BIGINT, "
              "c VARCHAR(20), pad VARCHAR(10), KEY k_1 (k))")
    _load(s, range(1, N + 1))
    return s


def _grew(fn):
    s0, r0 = metrics.pk_range_scans.value, metrics.pk_range_rows.value
    out = fn()
    return (out, metrics.pk_range_scans.value - s0,
            metrics.pk_range_rows.value - r0)


def _without_arm(monkeypatch):
    """The selector with the arm taken out, from inside the test: what it
    would have sent through pk_range scans the full image."""
    real = selector.choose_access

    def no_pk_range(*a, **kw):
        access = real(*a, **kw)
        return ("full",) if access[0] == "pk_range" else access
    monkeypatch.setattr(selector, "choose_access", no_pk_range)


WHERES = (
    [f"id BETWEEN {lo} AND {lo + 99}"
     for lo in random.Random(30).sample(range(1, N), 6)]
    + ["id >= 5950", "id < 40", "id > 100 AND id <= 163",    # one-sided, open
       "id BETWEEN 7000 AND 7099",                           # empty
       "id BETWEEN 300 AND 200",                             # lo > hi
       f"id BETWEEN {N - 30} AND {N + 69}",                  # past the end
       "id = 77", "id = 999999",
       "id BETWEEN 10 AND 400 AND id > 350 AND k < 900"])


def test_the_four_statements_answer_as_the_full_scan_does(sess, monkeypatch):
    stmts = [q.format(w=w) for w in WHERES for q in SHAPES
             # `SELECT c ... WHERE id = v [ORDER BY c]` is the host tier's
             # point read, not this arm
             if not (w.startswith("id = ") and q in (SHAPES[0], SHAPES[2]))]
    got, scans, _ = _grew(lambda: [sess.query(q) for q in stmts])
    assert scans == len(stmts)
    with monkeypatch.context() as m:
        _without_arm(m)
        want, scans, _ = _grew(lambda: [sess.query(q) for q in stmts])
    assert scans == 0
    assert got == want
    assert len(sess.query(SHAPES[0].format(w=WHERES[0]))) == 100


def test_fifty_literals_compile_nothing_and_keep_the_dictionaries(
        sess, monkeypatch):
    gathered = []
    real = batch_mod.gather_padded

    def spy(*a):
        gathered.append(real(*a))
        return gathered[-1]
    monkeypatch.setattr(batch_mod, "gather_padded", spy)
    for q in SHAPES:                     # the first of each shape compiles
        sess.query(q.format(w="id BETWEEN 5 AND 104"))
    r0 = metrics.xla_retraces.value
    c0 = metrics.compile_ms.stats()["count"]
    takes = batch_mod._take_rows._cache_size()
    rng = random.Random(7)
    for _ in range(50):
        lo = rng.randrange(1, N + 50)    # some run past the table's end
        for q in SHAPES:
            sess.query(q.format(w=f"id BETWEEN {lo} AND {lo + 99}"))
    assert metrics.xla_retraces.value == r0
    assert metrics.compile_ms.stats()["count"] == c0
    assert batch_mod._take_rows._cache_size() == takes
    image = sess.db.stores["default.sb"].device_table_batch()
    assert len(gathered) == 4 * 51
    for b in gathered:
        assert len(b) == 1024
        for name in ("c", "pad"):
            assert b.column(name).dictionary is \
                image.column(name).dictionary


def test_explain_and_counters_show_the_arm(sess):
    plan = sess.execute("EXPLAIN SELECT SUM(k) FROM sb "
                        "WHERE id BETWEEN 5 AND 104").plan_text
    assert f"access=pk_range(id: 100 of {N} rows, capacity 1024)" in plan
    _, scans, rows = _grew(lambda: sess.query(
        "SELECT SUM(k) FROM sb WHERE id BETWEEN 5 AND 104"))
    assert (scans, rows) == (1, 100)
    status = {r["Variable_name"]: r["Value"]
              for r in sess.query("SHOW STATUS")}
    assert int(status["pk_range_scans.value"]) >= 1
    assert int(status["pk_range_rows.value"]) >= 100
    # a fifth of the table is the widest range the arm takes
    wide = "SELECT SUM(k) FROM sb WHERE id BETWEEN 1 AND {}"
    assert "access=pk_range(" in sess.execute(
        "EXPLAIN " + wide.format(N // 5)).plan_text
    assert "access=full" in sess.execute(
        "EXPLAIN " + wide.format(N // 5 + 1)).plan_text
    got, scans, _ = _grew(lambda: sess.query(wide.format(N // 5 + 1)))
    assert scans == 0
    assert got == [{"sum(k)": sum((i * 7919) % 1000
                                  for i in range(1, N // 5 + 2))}]


def test_a_table_no_larger_than_the_bucket_scans_whole():
    s = Session()
    s.execute("CREATE TABLE sb (id BIGINT PRIMARY KEY, k BIGINT, "
              "c VARCHAR(20), pad VARCHAR(10))")
    _load(s, range(1, 1025))
    q = "SELECT SUM(k) FROM sb WHERE id BETWEEN 5 AND 104"
    assert "access=pk_range" not in s.execute("EXPLAIN " + q).plan_text
    _, scans, _ = _grew(lambda: s.query(q))
    assert scans == 0


def _sum(s: Session, lo: int, hi: int):
    return s.query(f"SELECT SUM(k) AS s, COUNT(*) AS n FROM sb "
                   f"WHERE id BETWEEN {lo} AND {hi}")[0]


def _want(ks: dict, lo: int, hi: int) -> dict:
    hit = [k for i, k in ks.items() if lo <= i <= hi]
    return {"s": sum(hit) if hit else None, "n": len(hit)}


def test_answers_follow_the_table_through_writes(sess):
    ks = {i: (i * 7919) % 1000 for i in range(1, N + 1)}
    assert _sum(sess, 100, 199) == _want(ks, 100, 199)
    sess.execute("DELETE FROM sb WHERE id BETWEEN 120 AND 129")
    for i in range(120, 130):
        del ks[i]
    sess.execute("UPDATE sb SET k = k + 5000 WHERE id = 150")
    ks[150] += 5000
    sess.execute("INSERT INTO sb VALUES (125, 1, 'c-new', 'p')")
    ks[125] = 1
    got, scans, rows = _grew(lambda: _sum(sess, 100, 199))
    assert got == _want(ks, 100, 199) and (scans, rows) == (1, 91)
    assert sess.query("SELECT c FROM sb WHERE id BETWEEN 124 AND 131 "
                      "ORDER BY c") == [
        {"c": "c-new"}, {"c": f"c{130 * 31 % 977:05d}"},
        {"c": f"c{131 * 31 % 977:05d}"}]
    # inside a transaction, after the transaction's own writes
    sess.execute("BEGIN")
    sess.execute("UPDATE sb SET k = 7 WHERE id = 160")
    sess.execute("DELETE FROM sb WHERE id = 161")
    sess.execute(f"INSERT INTO sb VALUES ({N + 1}, 42, 'c-tail', 'p')")
    inside = dict(ks)
    inside[160] = 7
    del inside[161]
    inside[N + 1] = 42
    got, scans, _ = _grew(lambda: (_sum(sess, 100, 199),
                                   _sum(sess, N - 50, N + 49)))
    assert got == (_want(inside, 100, 199), _want(inside, N - 50, N + 49))
    assert scans == 2
    sess.execute("ROLLBACK")
    assert _sum(sess, 100, 199) == _want(ks, 100, 199)
    assert _sum(sess, N - 50, N + 49) == _want(ks, N - 50, N + 49)


def test_an_older_pin_takes_the_versioned_path(sess):
    q = "SELECT SUM(k) AS s FROM sb WHERE id BETWEEN 100 AND 199"
    base = sess.query(q)
    sess.execute("SET SNAPSHOT = 'now'")
    # a quiet table under a pin keeps the arm
    got, scans, _ = _grew(lambda: sess.query(q))
    assert got == base and scans == 1
    w = Session(sess.db)
    w.execute("UPDATE sb SET k = k + 1 WHERE id = 150")
    got, scans, _ = _grew(lambda: sess.query(q))
    assert got == base and scans == 0
    sess.execute("SET SNAPSHOT = 0")
    got, scans, _ = _grew(lambda: sess.query(q))
    assert got == [{"s": base[0]["s"] + 1}] and scans == 1


@pytest.mark.parametrize("ddl,where", [
    ("CREATE TABLE t (a BIGINT, b BIGINT, v BIGINT, PRIMARY KEY (a, b))",
     "a BETWEEN 5 AND 104"),
    ("CREATE TABLE t (a VARCHAR(8) PRIMARY KEY, b BIGINT, v BIGINT)",
     "a BETWEEN '00005' AND '00104'"),
    ("CREATE TABLE t (a BIGINT, b BIGINT, v BIGINT)",
     "a BETWEEN 5 AND 104"),
], ids=["composite", "string", "no_key"])
def test_other_keys_never_take_the_arm(ddl, where):
    s = Session()
    s.execute(ddl)
    for at in range(0, 3000, 1500):
        s.execute("INSERT INTO t VALUES " + ",".join(
            (f"('{i:05d}',{i},{i})" if "VARCHAR" in ddl else f"({i},{i},{i})")
            for i in range(at, at + 1500)))
    q = f"SELECT SUM(v) AS s FROM t WHERE {where}"
    assert "pk_range" not in s.execute("EXPLAIN " + q).plan_text
    got, scans, _ = _grew(lambda: s.query(q))
    assert got == [{"s": sum(range(5, 105))}] and scans == 0


def test_a_mesh_session_never_takes_the_arm(sess):
    from baikaldb_tpu.parallel.mesh import make_mesh

    dist = Session(db=sess.db, mesh=make_mesh(8))
    q = "SELECT SUM(k) AS s FROM sb WHERE id BETWEEN 5 AND 104"
    got, scans, _ = _grew(lambda: dist.query(q))
    assert scans == 0 and got == sess.query(q)


@pytest.mark.parametrize("ddl,lo,hi,want", [
    ("d DATE", "'1995-03-01'", "'1995-03-10'", 10),
    ("d DATETIME", "'1995-03-01 00:00:00'", "'1995-03-10 12:00:00'", 10),
    ("d DOUBLE", "59.5", "69.25", 10),
], ids=["date", "datetime", "double"])
def test_orderable_key_types(ddl, lo, hi, want):
    import datetime

    s = Session()
    s.execute(f"CREATE TABLE t ({ddl} PRIMARY KEY, v BIGINT)")
    day0 = datetime.date(1995, 1, 1)
    rows = []
    for i in range(3000):
        if "DOUBLE" in ddl:
            key = f"{i}.0"
        else:
            key = f"'{day0 + datetime.timedelta(days=i)}'"
        rows.append(f"({key},{i})")
    for at in range(0, 3000, 1500):
        s.execute("INSERT INTO t VALUES " + ",".join(rows[at:at + 1500]))
    q = f"SELECT COUNT(*) AS n, SUM(v) AS s FROM t WHERE d BETWEEN {lo} AND {hi}"
    assert "access=pk_range(d: 10 of 3000 rows" in \
        s.execute("EXPLAIN " + q).plan_text
    got, scans, rows = _grew(lambda: s.query(q))
    first = 59 if "DOUBLE" not in ddl else 60
    assert got == [{"n": want, "s": sum(range(first, first + 10))}]
    assert (scans, rows) == (1, 10)


def test_rehearsal_of_the_sysbench_cell():
    """`benchmark/run.py` at a rehearsal's size on the CPU: every range
    statement of every transaction takes the arm, and the window compiles
    nothing."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sysbench_1m.read_only", "--seed", str(2**31 + 30), "--seconds",
         "3", "--trace", "0", "--rehearse-scale", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    counters = line["counters"]
    assert counters["txn_commits"] > 0
    assert counters["pk_range_scans"] == 4 * counters["txn_commits"]
    assert "xla_retraces" not in counters
