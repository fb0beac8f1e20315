"""SQL transactions + durability through the MVCC row tier.

Covers the VERDICT r1 #3 'done when' list: txn tests pass via the row tier
(no whole-table copies), a kill-9/restart test recovers committed SQL writes
from the WAL, and BEGIN/ROLLBACK restores state via zero-copy region
pre-images (reference: src/engine/transaction.cpp, region restart recovery
region.h:644)."""

import os
import signal
import subprocess
import sys
import textwrap

import pytest

from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.storage.rowstore import ConflictError


@pytest.fixture()
def s():
    sess = Session()
    sess.execute("CREATE TABLE acct (id BIGINT, bal DOUBLE, name VARCHAR, "
                 "PRIMARY KEY (id))")
    sess.execute("INSERT INTO acct VALUES (1, 100.0, 'a'), (2, 200.0, 'b'), "
                 "(3, 300.0, 'c')")
    return sess


def test_txn_commit(s):
    s.execute("BEGIN")
    s.execute("UPDATE acct SET bal = bal - 50 WHERE id = 1")
    s.execute("UPDATE acct SET bal = bal + 50 WHERE id = 2")
    # read-your-writes inside the txn
    assert s.query("SELECT bal FROM acct WHERE id = 1") == [{"bal": 50.0}]
    s.execute("COMMIT")
    assert s.query("SELECT SUM(bal) t FROM acct") == [{"t": 600.0}]
    assert s.query("SELECT bal FROM acct WHERE id = 2") == [{"bal": 250.0}]


def test_txn_rollback_restores_everything(s):
    store = s.db.stores["default.acct"]
    pre_data = store.regions[0].data      # pre-image ref (arrow is immutable)
    v0 = store.version
    s.execute("BEGIN")
    s.execute("INSERT INTO acct VALUES (4, 1.0, 'd')")
    s.execute("DELETE FROM acct WHERE id = 1")
    s.execute("UPDATE acct SET name = 'zz' WHERE id = 2")
    assert s.query("SELECT COUNT(*) c FROM acct") == [{"c": 3}]
    s.execute("ROLLBACK")
    rows = s.query("SELECT id, bal, name FROM acct ORDER BY id")
    assert rows == [{"id": 1, "bal": 100.0, "name": "a"},
                    {"id": 2, "bal": 200.0, "name": "b"},
                    {"id": 3, "bal": 300.0, "name": "c"}]
    # zero-copy undo: the restored region data IS the captured table object
    assert store.regions[0].data is pre_data
    # versions never go backwards (stale-cache aliasing guard)
    assert store.version > v0


def test_txn_rollback_discards_binlog(s):
    sub = s.db.binlog.subscribe()
    sub.poll()   # drain the setup events
    s.execute("BEGIN")
    s.execute("INSERT INTO acct VALUES (9, 9.0, 'x')")
    s.execute("ROLLBACK")
    assert sub.poll() == []
    s.execute("INSERT INTO acct VALUES (10, 10.0, 'y')")
    assert len(sub.poll()) == 1


def test_duplicate_pk_rejected(s):
    with pytest.raises(ConflictError, match="Duplicate entry"):
        s.execute("INSERT INTO acct VALUES (1, 5.0, 'dup')")
    # intra-statement duplicates too
    with pytest.raises(ConflictError, match="Duplicate entry"):
        s.execute("INSERT INTO acct VALUES (7, 1.0, 'x'), (7, 2.0, 'y')")
    # after rollback, the key is free again
    s.execute("BEGIN")
    s.execute("INSERT INTO acct VALUES (8, 1.0, 'x')")
    s.execute("ROLLBACK")
    s.execute("INSERT INTO acct VALUES (8, 2.0, 'z')")
    assert s.query("SELECT bal FROM acct WHERE id = 8") == [{"bal": 2.0}]


def test_concurrent_writer_conflict(s):
    other = Session(db=s.db)
    s.execute("BEGIN")
    s.execute("UPDATE acct SET bal = 0 WHERE id = 1")
    with pytest.raises(ConflictError):
        other.execute("UPDATE acct SET bal = 1 WHERE id = 2")
    s.execute("ROLLBACK")
    other.execute("UPDATE acct SET bal = 1 WHERE id = 2")   # lease released
    assert s.query("SELECT bal FROM acct WHERE id = 2") == [{"bal": 1.0}]


def test_durability_without_checkpoint(tmp_path):
    d = str(tmp_path / "data")
    db = Database(data_dir=d)
    s1 = Session(db=db)
    s1.execute("CREATE TABLE t (k BIGINT, v VARCHAR, PRIMARY KEY (k))")
    s1.execute("INSERT INTO t VALUES (1, 'one'), (2, 'two')")
    s1.execute("UPDATE t SET v = 'TWO' WHERE k = 2")
    s1.execute("INSERT INTO t VALUES (3, 'three')")
    s1.execute("DELETE FROM t WHERE k = 1")
    # no checkpoint, no clean shutdown: a fresh Database must recover the
    # committed hot writes from the WAL alone
    db2 = Database(data_dir=d)
    s2 = Session(db=db2)
    rows = s2.query("SELECT k, v FROM t ORDER BY k")
    assert rows == [{"k": 2, "v": "TWO"}, {"k": 3, "v": "three"}]
    # and rowid allocation continues without collision
    s2.execute("INSERT INTO t VALUES (4, 'four')")
    assert s2.query("SELECT COUNT(*) c FROM t") == [{"c": 3}]


def test_txn_rollback_leaves_wal_clean(tmp_path):
    d = str(tmp_path / "data")
    db = Database(data_dir=d)
    s1 = Session(db=db)
    s1.execute("CREATE TABLE t (k BIGINT, v BIGINT)")
    s1.execute("INSERT INTO t VALUES (1, 10)")
    s1.execute("BEGIN")
    s1.execute("INSERT INTO t VALUES (2, 20)")
    s1.execute("ROLLBACK")
    s1.execute("BEGIN")
    s1.execute("INSERT INTO t VALUES (3, 30)")
    s1.execute("COMMIT")
    db2 = Database(data_dir=d)
    rows = Session(db=db2).query("SELECT k FROM t ORDER BY k")
    assert rows == [{"k": 1}, {"k": 3}]


def test_checkpoint_then_more_dml(tmp_path):
    d = str(tmp_path / "data")
    db = Database(data_dir=d)
    s1 = Session(db=db)
    s1.execute("CREATE TABLE t (k BIGINT, v DOUBLE)")
    s1.execute("INSERT INTO t VALUES (1, 1.5), (2, 2.5)")
    db.checkpoint()
    s1.execute("UPDATE t SET v = 9.0 WHERE k = 1")   # hot delta over cold
    s1.execute("INSERT INTO t VALUES (3, 3.5)")
    db2 = Database(data_dir=d)
    rows = Session(db=db2).query("SELECT k, v FROM t ORDER BY k")
    assert rows == [{"k": 1, "v": 9.0}, {"k": 2, "v": 2.5},
                    {"k": 3, "v": 3.5}]


def test_kill9_recovery(tmp_path):
    """Hard-kill a writer mid-session; committed writes must survive."""
    d = str(tmp_path / "data")
    child = textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
        from baikaldb_tpu.exec.session import Database, Session
        s = Session(db=Database(data_dir={d!r}))
        s.execute("CREATE TABLE k9 (id BIGINT, v VARCHAR, PRIMARY KEY (id))")
        s.execute("INSERT INTO k9 VALUES (1, 'committed')")
        s.execute("BEGIN")
        s.execute("INSERT INTO k9 VALUES (2, 'uncommitted')")
        print("READY", flush=True)
        os.kill(os.getpid(), 9)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == -signal.SIGKILL and "READY" in p.stdout, p.stderr
    db = Database(data_dir=d)
    rows = Session(db=db).query("SELECT id, v FROM k9 ORDER BY id")
    assert rows == [{"id": 1, "v": "committed"}]


def test_ddl_recovery_and_drop(tmp_path):
    d = str(tmp_path / "data")
    db = Database(data_dir=d)
    s1 = Session(db=db)
    s1.execute("CREATE DATABASE appdb")
    s1.execute("CREATE TABLE appdb.u (id BIGINT, nm VARCHAR, PRIMARY KEY (id))")
    s1.execute("INSERT INTO appdb.u VALUES (1, 'x')")
    db2 = Database(data_dir=d)
    s2 = Session(db=db2, database="appdb")
    assert s2.query("SELECT nm FROM u") == [{"nm": "x"}]
    s2.execute("DROP TABLE u")
    assert not os.path.exists(os.path.join(d, "appdb.u.wal"))
    db3 = Database(data_dir=d)
    assert Session(db=db3).query(
        "SELECT COUNT(*) c FROM information_schema.tables "
        "WHERE table_schema = 'appdb'") == [{"c": 0}]


def test_truncate_durable(tmp_path):
    d = str(tmp_path / "data")
    db = Database(data_dir=d)
    s1 = Session(db=db)
    s1.execute("CREATE TABLE t (k BIGINT)")
    s1.execute("INSERT INTO t VALUES (1), (2)")
    db.checkpoint()
    s1.execute("TRUNCATE TABLE t")
    db2 = Database(data_dir=d)
    assert Session(db=db2).query("SELECT COUNT(*) c FROM t") == [{"c": 0}]


def test_alter_preserves_committed_writes(tmp_path):
    d = str(tmp_path / "data")
    db = Database(data_dir=d)
    s1 = Session(db=db)
    s1.execute("CREATE TABLE t (k BIGINT)")
    s1.execute("INSERT INTO t VALUES (1), (2)")    # WAL only, no checkpoint
    s1.execute("ALTER TABLE t ADD COLUMN v VARCHAR")
    s1.execute("UPDATE t SET v = 'x' WHERE k = 1")
    db2 = Database(data_dir=d)
    rows = Session(db=db2).query("SELECT k, v FROM t ORDER BY k")
    assert rows == [{"k": 1, "v": "x"}, {"k": 2, "v": None}]


def test_insert_select_hot_path(s):
    s.execute("CREATE TABLE acct2 (id BIGINT, bal DOUBLE, name VARCHAR, "
              "PRIMARY KEY (id))")
    s.execute("INSERT INTO acct2 SELECT id, bal, name FROM acct")
    with pytest.raises(ConflictError, match="Duplicate entry"):
        s.execute("INSERT INTO acct2 SELECT id, bal, name FROM acct")
    assert s.query("SELECT COUNT(*) c FROM acct2") == [{"c": 3}]


def test_bulk_load_then_checkpoint_durable(tmp_path):
    import pyarrow as pa

    d = str(tmp_path / "data")
    db = Database(data_dir=d)
    s1 = Session(db=db)
    s1.execute("CREATE TABLE big (k BIGINT, v DOUBLE)")
    s1.load_arrow("big", pa.table({"k": list(range(1000)),
                                   "v": [float(i) for i in range(1000)]}))
    db.checkpoint()
    db2 = Database(data_dir=d)
    assert Session(db=db2).query("SELECT COUNT(*) c, SUM(k) s FROM big") == \
        [{"c": 1000, "s": 499500}]
