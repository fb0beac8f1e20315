"""Writes addressed by a primary key reach the resident device image as
patches (storage/column_store.py ``pk_update`` / ``pk_delete`` / the patched
``insert_rows``), and string dictionaries grow by append with their order
held as data (column/dictionary.py ``Lineage``).  sysbench's read-write
transaction is driven through ``Session`` and over the wire, every answer
held to the benchmark's numpy model (benchmark/refs/tpu_sysbench_rw.py)."""

import importlib
import threading

import pytest

from baikaldb_tpu.client.mysql_client import Connection
from baikaldb_tpu.column.dictionary import Dictionary
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.server.mysql_server import MySQLServer
from baikaldb_tpu.utils import metrics
from benchmark import trafficgen
from benchmark.loaders import sbtest
from benchmark.refs import tpu_sysbench_rw as ref

N = 10_000
SEED = 2**31 + 40
RAND = {"type": "special", "iter": 12, "pct": 1, "res": 75}


def _counts() -> dict:
    return {n: metrics.REGISTRY.expose()[n]["value"]
            for n in ("image_patches", "image_rebuilds", "dict_appends",
                      "pk_order_rebuilds", "xla_retraces")}


def _grew(before: dict) -> dict:
    after = _counts()
    return {k: after[k] - before[k] for k in after}


@pytest.fixture
def sb():
    """(a session over a seeded 10,000-row sbtest1, its tables)."""
    s = Session(db=Database())
    tables = sbtest.generate(N, 1, SEED, RAND)
    s.execute(sbtest.DDL.format(i=1))
    s.load_arrow("sbtest1", tables["sbtest1"])
    return s, tables


def _ctx(tables: dict) -> dict:
    import pyarrow as pa

    return {"tables": {**tables, "__replay__": pa.table(
        {"seed": [SEED], "table_size": [N]})}}


def _run(execute, tables, txns: int, stream=trafficgen.WINDOW_STREAM,
         ctx=None):
    """``txns`` read-write transactions through ``execute(sql) ->
    (columns, rows)``, each answer held to the model."""
    traffic = trafficgen.load_traffic("read_write")
    client = trafficgen.Client(traffic, {"table_size": N}, SEED, 0, stream)
    ctx = ctx if ctx is not None else _ctx(tables)
    ctx.setdefault("rw", ref.Model(tables["sbtest1"], N))
    refs = {n: _resolve(st["ref"])
            for n, st in traffic["statements"].items()}
    for _ in range(txns):
        t = client.next()
        execute(t.begin)
        for st in t.statements:
            cols, rows = execute(st.sql)
            want = refs[st.name].answer(ctx, st.params)
            gaps = refs[st.name].gaps(cols, rows, want)
            assert not any(gaps.values()), (st.sql, gaps, rows[:3],
                                            want[1][:3])
        execute(t.commit)
    return ctx


def _resolve(ref_name: str):
    module, attr = ref_name.split(":")
    return getattr(importlib.import_module(module), attr)


def _session_exec(s):
    def execute(sql):
        r = s.execute(sql)
        if r.arrow is None:
            return (), []
        return tuple(r.arrow.column_names), [tuple(x.values()) for x in
                                             r.arrow.to_pylist()]
    return execute


def test_read_write_transactions_answer_as_the_model(sb):
    s, tables = sb
    ctx = _run(_session_exec(s), tables, 30)
    rows, back = ref.read_back(ctx)
    assert rows > 0 and back > 0      # reads met rows written in the run


def test_over_the_wire(sb):
    s, tables = sb
    srv = MySQLServer(s.db, port=0).start()
    try:
        conn = Connection(port=srv.port)

        def execute(sql):
            r = conn.query(sql)
            return tuple(r.columns), [tuple(x) for x in r.rows]
        _run(execute, tables, 15)
        conn.close()
    finally:
        srv.stop()


def test_hundred_transactions_patch_and_never_retrace_or_rebuild(sb):
    s, tables = sb
    ctx = _run(_session_exec(s), tables, 5)        # warms every shape
    before = _counts()
    img = s.db.stores["default.sbtest1"]._img
    free = img.free
    _run(_session_exec(s), tables, 100, ctx=ctx,
         stream=trafficgen.WARMUP_STREAM + 7)
    grew = _grew(before)
    # each insert took the lane its id's delete freed: the bucket's tail
    # is where it was
    assert s.db.stores["default.sbtest1"]._img is img and img.free == free
    assert grew["image_patches"] == 100
    assert grew["image_rebuilds"] == 0
    assert grew["pk_order_rebuilds"] == 0
    assert grew["xla_retraces"] == 0
    assert 250 <= grew["dict_appends"] <= 300       # ~3 a transaction


def _load_small(s):
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, k BIGINT, c CHAR(20))")
    s.execute("INSERT INTO t VALUES " + ",".join(
        f"({i}, {i * 10}, 'm{i:03d}')" for i in range(1, 41)))
    s.query("SELECT c FROM t WHERE id BETWEEN 1 AND 5")  # the image


def test_appended_values_sort_into_the_middle():
    s = Session(db=Database())
    _load_small(s)
    s.execute("UPDATE t SET c = 'm005x' WHERE id = 30")
    s.execute("UPDATE t SET c = 'a' WHERE id = 31")
    s.execute("UPDATE t SET c = 'z' WHERE id = 32")
    d = s.db.stores["default.t"].device_table_batch().column("c").dictionary
    assert d.tailed and d.code_of("m005x") >= d.lineage.nb
    want = sorted([f"m{i:03d}" for i in range(1, 41) if i not in (30, 31, 32)]
                  + ["m005x", "a", "z"])
    got = [r["c"] for r in s.query("SELECT c FROM t WHERE id BETWEEN 1 AND 40"
                                   " ORDER BY c")]
    assert got == want
    got = [r["c"] for r in s.query("SELECT DISTINCT c FROM t WHERE id "
                                   "BETWEEN 1 AND 40 ORDER BY c DESC")]
    assert got == want[::-1]
    assert s.query("SELECT id FROM t WHERE c = 'm005x'") == [{"id": 30}]
    got = {r["id"] for r in s.query("SELECT id FROM t WHERE c LIKE 'm005%'")}
    assert got == {5, 30}
    got = {r["id"] for r in s.query("SELECT id FROM t WHERE c < 'm003'")}
    assert got == {1, 2, 31}
    got = {r["id"] for r in s.query("SELECT id FROM t WHERE c BETWEEN "
                                    "'m005' AND 'm006'")}
    assert got == {5, 6, 30}
    assert s.query("SELECT MIN(c) lo, MAX(c) hi FROM t") == \
        [{"lo": "a", "hi": "z"}]
    got = s.query("SELECT c, COUNT(*) n FROM t WHERE id IN (5, 30, 31) "
                  "GROUP BY c ORDER BY c")
    assert got == [{"c": "a", "n": 1}, {"c": "m005", "n": 1},
                   {"c": "m005x", "n": 1}]


def test_join_on_a_grown_dictionary():
    s = Session(db=Database())
    _load_small(s)
    s.execute("CREATE TABLE u (c CHAR(20), w BIGINT)")
    s.execute("INSERT INTO u VALUES ('m005x', 1), ('m007', 2)")
    s.execute("UPDATE t SET c = 'm005x' WHERE id = 30")
    got = s.query("SELECT t.id, u.w FROM t JOIN u ON t.c = u.c ORDER BY t.id")
    assert got == [{"id": 7, "w": 2}, {"id": 30, "w": 1}]
    s.execute("UPDATE t SET c = 'm007' WHERE id = 31")  # past the trace's n
    got = s.query("SELECT t.id, u.w FROM t JOIN u ON t.c = u.c ORDER BY t.id")
    assert got == [{"id": 7, "w": 2}, {"id": 30, "w": 1}, {"id": 31, "w": 2}]


def test_pk_range_after_delete_and_insert_of_one_id():
    s = Session(db=Database())
    _load_small(s)
    before = _counts()
    s.execute("BEGIN")
    s.execute("DELETE FROM t WHERE id = 10")
    s.execute("INSERT INTO t (id, k, c) VALUES (10, 7, 'new')")
    s.execute("COMMIT")
    got = s.query("SELECT id, k, c FROM t WHERE id BETWEEN 9 AND 11")
    assert sorted(got, key=lambda r: r["id"]) == [
        {"id": 9, "k": 90, "c": "m009"}, {"id": 10, "k": 7, "c": "new"},
        {"id": 11, "k": 110, "c": "m011"}]
    assert s.query("SELECT SUM(k) s FROM t WHERE id BETWEEN 9 AND 11") == \
        [{"s": 207}]
    s.execute("DELETE FROM t WHERE id = 11")
    assert s.query("SELECT COUNT(*) n FROM t WHERE id BETWEEN 9 AND 11") == \
        [{"n": 2}]
    grew = _grew(before)
    assert grew["pk_order_rebuilds"] == 0 and grew["image_rebuilds"] == 0


@pytest.mark.parametrize("write", [
    "UPDATE t SET k = k + 1 WHERE id = 3",
    "UPDATE t SET c = 'brand-new' WHERE id = 3",
    "DELETE FROM t WHERE id = 3",
    "INSERT INTO t (id, k, c) VALUES (99, 1, 'x99')",
])
def test_rollback_restores_the_lanes(write):
    s = Session(db=Database())
    _load_small(s)
    q = "SELECT id, k, c FROM t WHERE id BETWEEN 1 AND 100 ORDER BY id"
    want = s.query(q)
    store = s.db.stores["default.t"]
    pre = store.regions[0].data
    before = _counts()
    s.execute("BEGIN")
    s.execute(write)
    s.query(q)                                # reads its own write
    s.execute("ROLLBACK")
    assert s.query(q) == want
    assert store.regions[0].data is pre
    assert s.query("SELECT c FROM t WHERE id = 3") == [{"c": "m003"}]
    grew = _grew(before)
    assert grew["image_rebuilds"] == 0 and grew["pk_order_rebuilds"] == 0


def test_a_reader_keeps_its_snapshot_while_another_session_patches():
    db = Database()
    s = Session(db=db)
    _load_small(s)
    reader = Session(db=db)
    q = "SELECT SUM(k) s, MAX(c) c FROM t WHERE id BETWEEN 1 AND 40"
    reader.execute("BEGIN")
    first = reader.query(q)
    done = threading.Event()

    def write():
        w = Session(db=db)
        for i in range(1, 6):
            w.execute("BEGIN")
            w.execute(f"UPDATE t SET k = k + 100 WHERE id = {i}")
            w.execute(f"UPDATE t SET c = 'zz{i}' WHERE id = {i + 10}")
            w.execute("COMMIT")
        done.set()
    th = threading.Thread(target=write)
    th.start()
    th.join(60)
    assert done.is_set()
    assert reader.query(q) == first
    reader.execute("COMMIT")
    assert reader.query(q) == [{"s": sum(i * 10 for i in range(1, 41)) + 500,
                                "c": "zz5"}]


def test_a_transaction_keeps_its_snapshot_after_it_writes():
    """Reads after the transaction's first write still see its snapshot:
    a table it has not written stays pinned, a table it wrote shows its
    own rows, and a table that moved past the snapshot refuses its lease."""
    from baikaldb_tpu.storage.rowstore import ConflictError

    db = Database()
    s = Session(db=db)
    _load_small(s)
    s.execute("CREATE TABLE u (id BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO u VALUES (1, 1), (2, 2)")
    other = Session(db=db)
    qt = "SELECT SUM(k) s FROM t WHERE id BETWEEN 1 AND 40"
    qu = "SELECT SUM(v) s FROM u"
    s.execute("BEGIN")
    assert s.query(qt) == [{"s": 8200}] and s.query(qu) == [{"s": 3}]
    other.execute("UPDATE u SET v = v + 100 WHERE id = 1")
    s.execute("UPDATE t SET k = k + 1 WHERE id = 1")
    assert s.query(qt) == [{"s": 8201}]         # its own write shows
    assert s.query(qu) == [{"s": 3}]            # u: still the snapshot
    assert s.query("SELECT v FROM u WHERE id = 1") == [{"v": 1}]
    assert s.query("SELECT k FROM t WHERE id = 1") == [{"k": 11}]
    with pytest.raises(ConflictError):          # u moved past the snapshot
        s.execute("UPDATE u SET v = 0 WHERE id = 2")
    with pytest.raises(ConflictError):          # t is this transaction's
        other.execute("UPDATE t SET k = 0 WHERE id = 2")
    s.execute("COMMIT")
    assert s.query(qt) == [{"s": 8201}] and s.query(qu) == [{"s": 103}]
    s.execute("UPDATE u SET v = 0 WHERE id = 2")    # the lease was let go
    assert s.query(qu) == [{"s": 101}]


def test_dictionary_snapshots_share_codes_and_key_executables_by_lineage():
    d0, _ = Dictionary.encode(["b", "d", "f"])
    g = Dictionary.growable(d0)
    assert g == d0 and not g.tailed          # no tail: keyed by content
    lin = g.lineage
    assert lin.append("c") == 3 and lin.append("a") == 4
    assert lin.append("c") == 3               # present: the same code
    t1 = g.current()
    assert t1.tailed and t1 != d0
    lin.append("e")
    t2 = t1.current()
    assert t1 == t2 and hash(t1) == hash(t2)  # appends do not re-key
    keys = lin.keys[:len(t2)]
    order = [t2.decode(keys.argsort()[i:i + 1].astype("int32"))[0]
             for i in range(len(t2))]
    assert order == ["a", "b", "c", "d", "e", "f"]
    assert t2.code_of("e") == 5 and t1.code_of("e") is None
    # bounds in key space: value >= 'c' <=> key >= lower_bound('c')
    lo = t2.lower_bound("c")
    assert sorted(v for c, v in enumerate(t2.values) if keys[c] >= lo) == \
        ["c", "d", "e", "f"]


def test_dictionary_keys_respace_when_a_gap_runs_out():
    d0, _ = Dictionary.encode([f"v{i:02d}" for i in range(0, 100, 2)])
    g = Dictionary.growable(d0)
    lin = g.lineage
    vals = [f"v{i:02d}" for i in range(0, 100, 2)]
    for j in range(40):                       # halve one gap 40 times
        v = "v02" + "0" * j + "1"
        lin.append(v)
        vals.append(v)
    assert lin.respaces >= 1
    snap = g.current()
    keys = lin.keys[:lin.n]
    assert [snap.decode(keys.argsort()[i:i + 1].astype("int32"))[0]
            for i in range(lin.n)] == sorted(vals)


def test_a_load_and_a_compile_settle_the_gc():
    import gc

    import pyarrow as pa

    from baikaldb_tpu.utils import gcpause

    s = Session(db=Database())
    s.execute("CREATE TABLE big (id BIGINT PRIMARY KEY, v BIGINT)")
    n0 = gcpause.gc_settles.value
    try:
        s.load_arrow("big", pa.table({"id": pa.array(range(1000), pa.int64()),
                                      "v": pa.array([1] * 1000, pa.int64())}))
        assert gcpause.gc_settles.value == n0 + 1
        frozen = gc.get_freeze_count()
        assert frozen > 0
        # a compile settles again, walking only what was made since
        s.query("SELECT SUM(v) s FROM big WHERE id < 10")
        assert gcpause.gc_settles.value >= n0 + 2
        assert gc.get_freeze_count() >= frozen
        # once the frozen population has doubled, a settle walks it all
        gcpause._walked[0] = gc.get_freeze_count() // 2
        garbage = [[]]
        garbage[0].append(garbage)      # a cycle, frozen below
        gc.freeze()
        del garbage
        before = gc.get_freeze_count()
        gcpause.settle("test")
        assert gc.get_freeze_count() < before
    finally:
        gc.unfreeze()
