"""A traffic file that says ``"prepared": true``: each statement's prepared
form derived from its template, the same draws as its text form,
COM_STMT_PREPARE once a connection and COM_STMT_EXECUTE a call (BEGIN and
COMMIT too), every accepted traffic file still sent as COM_QUERY, and the
wire's prepared path against the text path over a small ``sbtest1``, with
the answers of a prepared transaction held to the references by
``run.check_answers``."""

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import run, trafficgen
from benchmark.loaders import sbtest

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 4301
# the traffic files of the accepted cells: none prepares
ACCEPTED_TRAFFIC = ["groupby", "q1q3", "q1q6", "q3q18", "read_only",
                    "read_write"]
READ_ONLY = trafficgen.load_traffic("read_only")
PREPARED = dict(READ_ONLY, prepared=True)
LIMITS = json.loads((ROOT / "benchmark/workloads/sysbench_1m.read_only.json")
                    .read_text())["limits"]


@pytest.mark.parametrize("template,sql,names", [
    ("SELECT c FROM sbtest1 WHERE id = {id}",
     "SELECT c FROM sbtest1 WHERE id = ?", ("id",)),
    ("SELECT c FROM sbtest1 WHERE id BETWEEN {lo} AND {hi} ORDER BY c",
     "SELECT c FROM sbtest1 WHERE id BETWEEN ? AND ? ORDER BY c",
     ("lo", "hi")),
    ("UPDATE sbtest1 SET c='{c}' WHERE id={id}",
     "UPDATE sbtest1 SET c=? WHERE id=?", ("c", "id")),
    ("SELECT 1 FROM t WHERE a < '{d}' AND b > '{d}' AND s = 'it''s'",
     "SELECT 1 FROM t WHERE a < ? AND b > ? AND s = 'it''s'", ("d", "d")),
    ("BEGIN", "BEGIN", ()),
])
def test_the_prepared_form_is_the_template_with_a_mark_a_field(
        template, sql, names):
    assert trafficgen.prepared_form(template) == (sql, names)


@pytest.mark.parametrize("template", [
    "l_shipdate >= '{year}-01-01'",     # inside a longer literal
    "s = 'it''s {a}'",
    "x = {x:.2f}",                      # a format spec
    "x = {x!r}",                        # a conversion
])
def test_a_field_that_cannot_be_bound_is_refused(template):
    with pytest.raises(ValueError):
        trafficgen.prepared_form(template)


def _draws(traffic: dict, n: int, client: int = 0) -> list:
    c = trafficgen.Client(traffic, {"table_size": 1_000_000}, SEED, client)
    return [c.next() for _ in range(n)]


def test_a_prepared_statement_draws_what_its_text_form_draws():
    """Parameters come from the same stream in the same order: the flag
    changes how a statement is sent, never what is drawn; the text form is
    the statement the server binds."""
    from baikaldb_tpu.server.mysql_server import _bind_placeholders

    prepared, plain = _draws(PREPARED, 40), _draws(READ_ONLY, 40)
    assert [(t.name, t.begin, t.commit) for t in prepared] == \
        [(t.name, t.begin, t.commit) for t in plain]
    assert all(t.prepared for t in prepared)
    assert not any(t.prepared for t in plain)
    got = [s for t in prepared for s in t.statements]
    want = [s for t in plain for s in t.statements]
    assert [(s.name, s.sql, s.params) for s in got] == \
        [(s.name, s.sql, s.params) for s in want]
    assert all(s.prepare is None and s.args == () for s in want)
    for s in got:
        assert "?" in s.prepare and "{" not in s.prepare
        assert s.args == tuple(s.params[a] for a in ("id", "lo", "hi")
                               if a in s.params)
        assert _bind_placeholders(s.prepare, list(s.args)) == s.sql


class FakeConn:
    """Records what a ``run.Wire`` asks of its connection."""

    def __init__(self):
        self.calls = []
        self.next_id = 0

    def query(self, sql):
        self.calls.append(("query", sql))
        return NS(columns=[], rows=[])

    def prepare(self, sql):
        self.next_id += 1
        self.calls.append(("prepare", sql, self.next_id))
        return self.next_id

    def execute(self, sid, args):
        self.calls.append(("execute", sid, args))
        return NS(columns=[], rows=[])


def test_each_connection_prepares_once_and_executes_every_call():
    """Seven statements a connection, prepared on their first use, in its
    first transaction; every later call is an execution of its own
    statement, with the arguments in ``?`` order."""
    wires = [run.Wire(FakeConn()), run.Wire(FakeConn())]
    clients = [trafficgen.Client(PREPARED, {"table_size": 1000}, SEED, i)
               for i in range(2)]
    sent = [[], []]
    for _ in range(5):
        for i, (wire, client) in enumerate(zip(wires, clients)):
            txn = client.next()
            rec = run.run_txn(wire, txn, i)
            assert not rec.error and len(rec.statements) == 14
            sent[i].append(txn)
    for wire, txns in zip(wires, sent):
        calls = wire.conn.calls
        prepared = [c for c in calls if c[0] == "prepare"]
        assert [c[1] for c in prepared] == [
            "BEGIN", "SELECT c FROM sbtest1 WHERE id = ?"] + [
            trafficgen.prepared_form(READ_ONLY["statements"][n]["sql"])[0]
            for n in ("range", "sum", "order", "distinct")] + ["COMMIT"]
        ids = {c[1]: c[2] for c in prepared}
        want = []
        for t in txns:
            want.append(("execute", ids["BEGIN"], ()))
            want += [("execute", ids[s.prepare], s.args)
                     for s in t.statements]
            want.append(("execute", ids["COMMIT"], ()))
        assert [c for c in calls if c[0] != "prepare"] == want
        assert all(c[0] == "execute" for c in calls[16 + len(prepared):])
        assert wire.ids == ids


@pytest.mark.parametrize("name", ACCEPTED_TRAFFIC)
def test_every_accepted_traffic_file_goes_out_as_com_query(name):
    """None of them prepares: their statements leave byte for byte as
    before, BEGIN and COMMIT included."""
    traffic = trafficgen.load_traffic(name)
    assert not traffic.get("prepared")
    variables = {"table_size": 10_000, "lineitem_rows": 60_000}
    client = trafficgen.Client(traffic, variables, SEED, 0)
    wire = run.Wire(FakeConn())
    want = []
    for _ in range(2 * len(traffic["transactions"])):
        txn = client.next()
        run.run_txn(wire, txn, 0)
        want += [x for x in [txn.begin] if x]
        want += [s.sql for s in txn.statements]
        want += [x for x in [txn.commit] if x]
    assert wire.conn.calls == [("query", sql) for sql in want]
    assert wire.ids == {}


# -- the wire's prepared path, as a prepared traffic drives it ---------------

N = 2000


@pytest.fixture(scope="module")
def served():
    from baikaldb_tpu.client.mysql_client import Connection
    from baikaldb_tpu.exec.session import Database, Session
    from baikaldb_tpu.server.mysql_server import MySQLServer

    db = Database()
    srv = MySQLServer(db, port=0).start()
    config = run.load_json("configs", "sysbench_1m")
    tables = sbtest.load(config, SEED, N / 1_000_000, Session(db=db))["tables"]
    conn = Connection(port=srv.port)
    try:
        yield tables, conn
    finally:
        conn.close()
        srv.stop()


def _lookups() -> int:
    from baikaldb_tpu.utils import metrics

    return run.read_counters(metrics)["point_lookups"]


@pytest.mark.parametrize("id_", [1, N // 2, N, N + 1])
def test_execute_answers_what_com_query_answers(served, id_):
    """Ids at the start, in the middle and at the end of the table, and
    one past it (no rows): each execution is one host-tier lookup."""
    tables, conn = served
    sid = conn.prepare("SELECT c FROM sbtest1 WHERE id=?")
    text = conn.query(f"SELECT c FROM sbtest1 WHERE id={id_}")
    before = _lookups()
    got = conn.execute(sid, (id_,))
    assert _lookups() == before + 1
    assert got.columns == text.columns == ["c"]
    assert got.rows == text.rows
    c = tables["sbtest1"].column("c")
    assert got.rows == ([] if id_ > N else [(c[id_ - 1].as_py(),)])


def test_a_quoted_field_binds_as_a_string(served):
    tables, conn = served
    c = tables["sbtest1"].column("c")[6].as_py()
    template = "SELECT id FROM sbtest1 WHERE c='{c}'"
    sql, names = trafficgen.prepared_form(template)
    assert conn.execute(conn.prepare(sql), (c,)).rows == \
        conn.query(template.format(c=c)).rows == [("7",)]


def test_a_null_argument_goes_through_the_null_bitmap(served):
    _, conn = served
    sid = conn.prepare("SELECT c FROM sbtest1 WHERE id=?")
    assert conn.execute(sid, (None,)).rows == \
        conn.query("SELECT c FROM sbtest1 WHERE id=NULL").rows == []


def test_statement_ids_are_a_connections_own(served):
    """Two new connections each number their statements from 1 and answer
    their own; an id one connection prepared is unknown on the other."""
    from baikaldb_tpu.client.mysql_client import Connection, MySQLError

    _, conn = served
    a, b = Connection(port=conn.port), Connection(port=conn.port)
    try:
        sa = a.prepare("SELECT c FROM sbtest1 WHERE id=?")
        sb = b.prepare("SELECT id FROM sbtest1 WHERE id=?")
        assert sa == sb
        assert a.execute(sa, (3,)).columns == ["c"]
        assert b.execute(sb, (3,)).rows == [("3",)]
        later = a.prepare("SELECT k FROM sbtest1 WHERE id=?")
        assert a.execute(later, (3,)).columns == ["k"]
        with pytest.raises(MySQLError) as e:
            b.execute(later, (3,))
        assert e.value.code == 1243
    finally:
        a.close()
        b.close()


def test_execute_after_close_is_error_1243(served):
    from baikaldb_tpu.client.mysql_client import MySQLError, PreparedStatement

    _, conn = served
    ps = PreparedStatement(conn, "SELECT c FROM sbtest1 WHERE id=?")
    assert len(ps.execute((5,)).rows) == 1
    ps.close()
    with pytest.raises(MySQLError) as e:
        conn.execute(ps.sid, (5,))
    assert e.value.code == 1243
    assert conn.query("SELECT 1").rows == [("1",)]


def _run_prepared(served, traffic, n=6):
    """``n`` transactions of ``traffic`` on a fresh connection, and what
    ``check_answers`` makes of them."""
    from baikaldb_tpu.client.mysql_client import Connection

    tables, conn = served
    wire = run.Wire(Connection(port=conn.port))
    client = trafficgen.Client(traffic, {"table_size": N}, SEED, 0)
    try:
        txns = [run.run_txn(wire, client.next(), 0) for _ in range(n)]
    finally:
        wire.close()
    numbers, _ = run.check_answers(txns, traffic, dict(LIMITS, errors=0),
                                   tables, control=False)
    return txns, numbers


def test_a_prepared_transaction_answers_as_its_text_form(served):
    """The read-only transaction sent prepared, BEGIN and COMMIT too: every
    answer is its reference's, and the rows are those the same draws
    return over COM_QUERY."""
    txns, numbers = _run_prepared(served, PREPARED)
    assert not any(t.error or t.wrong for t in txns)
    assert set(numbers.values()) == {0}
    text, _ = _run_prepared(served, READ_ONLY)
    assert [[(s.sql, s.rows) for s in t.statements] for t in txns] == \
        [[(s.sql, s.rows) for s in t.statements] for t in text]


def test_a_server_that_keeps_its_first_binding_is_not_correct(
        served, monkeypatch):
    """The fault a statement prepared once tempts: the server binds the
    arguments of a statement's first execution into every later one."""
    from baikaldb_tpu.server import mysql_server

    plain = mysql_server._bind_placeholders
    first: dict = {}

    def sticky(sql, params):
        return plain(sql, first.setdefault(sql, params))
    monkeypatch.setattr(mysql_server, "_bind_placeholders", sticky)
    txns, numbers = _run_prepared(served, PREPARED)
    assert sum(t.wrong for t in txns) >= len(txns) - 1
    for name in ("point_mismatch", "range_mismatch", "sum_mismatch"):
        assert numbers[name] == 1
    assert not run.within(numbers, dict(LIMITS, errors=0))
