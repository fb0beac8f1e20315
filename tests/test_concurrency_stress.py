"""Concurrency stress over the round-4 critical sections: concurrent
sessions running global-index DML, reads, and online DDL against one
Database must stay consistent (the store-lock serialization of coupling
decisions, unique checks, and backfill publishes)."""

import threading

import pytest

from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.storage.rowstore import ConflictError


def test_concurrent_global_unique_inserts_never_double_admit():
    """Many threads race to claim the same unique values; exactly one
    winner per value, and the backing index stays consistent."""
    db = Database()
    boot = Session(db)
    boot.execute("CREATE TABLE u (id BIGINT, email VARCHAR(32), "
                 "PRIMARY KEY (id), GLOBAL UNIQUE INDEX g (email))")
    n_threads, per = 6, 30
    wins: list[tuple[int, int]] = []
    errs: list[str] = []
    lock = threading.Lock()

    def worker(tid: int):
        s = Session(db)
        for i in range(per):
            rid = tid * per + i
            try:
                # every thread fights for the SAME value space e0..e<per-1>
                s.execute(f"INSERT INTO u VALUES ({rid}, 'e{i}')")
                with lock:
                    wins.append((i, rid))
            except ConflictError:
                pass
            except Exception as e:      # noqa: BLE001
                with lock:
                    errs.append(f"{type(e).__name__}: {e}")

    ts = [threading.Thread(target=worker, args=(t,))
          for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    # exactly one winner per contested value
    by_val: dict[int, int] = {}
    for v, _rid in wins:
        by_val[v] = by_val.get(v, 0) + 1
    assert all(c == 1 for c in by_val.values()), by_val
    s = Session(db)
    assert s.query("SELECT COUNT(*) n FROM u") == [{"n": len(wins)}]
    # the backing index matches the main table exactly
    bstore = db.stores["default.__gidx__u__g"]
    assert bstore.num_rows == len(wins)
    # and stays enforcing
    with pytest.raises(ConflictError):
        s.execute("INSERT INTO u VALUES (9999, 'e0')")


def test_readers_run_against_concurrent_writers():
    """Readers must never crash or see torn state while writers churn a
    partitioned table with a global index."""
    db = Database()
    boot = Session(db)
    boot.execute("CREATE TABLE t (id BIGINT, v BIGINT, tag VARCHAR(16), "
                 "PRIMARY KEY (id), GLOBAL INDEX g (tag)) ")
    stop = threading.Event()
    errs: list[str] = []

    def writer():
        s = Session(db)
        i = 0
        while not stop.is_set():
            try:
                s.execute(f"INSERT INTO t VALUES ({i}, {i % 50}, 'w{i % 7}')")
                if i % 5 == 0:
                    s.execute(f"UPDATE t SET v = v + 1 WHERE id = {i}")
                if i % 11 == 0:
                    s.execute(f"DELETE FROM t WHERE id = {i}")
            except Exception as e:      # noqa: BLE001
                errs.append(f"writer {type(e).__name__}: {e}")
                return
            i += 1

    def reader():
        s = Session(db)
        while not stop.is_set():
            try:
                rows = s.query("SELECT COUNT(*) n, SUM(v) sv FROM t")
                assert rows and rows[0]["n"] >= 0
                s.query("SELECT id FROM t WHERE tag = 'w3' ORDER BY id")
            except Exception as e:      # noqa: BLE001
                errs.append(f"reader {type(e).__name__}: {e}")
                return

    wt = threading.Thread(target=writer)
    rts = [threading.Thread(target=reader) for _ in range(2)]
    wt.start()
    for r in rts:
        r.start()
    import time

    time.sleep(6)
    stop.set()
    wt.join()
    for r in rts:
        r.join()
    assert not errs, errs[:3]
    # final consistency: index rows == live main rows
    s = Session(db)
    n = s.query("SELECT COUNT(*) n FROM t")[0]["n"]
    assert db.stores["default.__gidx__t__g"].num_rows == n


def test_concurrent_backfill_and_dml_lose_nothing():
    """DML racing an online global-index backfill: every row that commits
    is indexed once the work publishes."""
    db = Database()
    s = Session(db)
    s.execute("CREATE TABLE b (id BIGINT, k VARCHAR(16), PRIMARY KEY (id))")
    for i in range(50):
        s.execute(f"INSERT INTO b VALUES ({i}, 'k{i}')")
    stop = threading.Event()
    errs: list[str] = []
    next_id = [1000]

    def writer():
        w = Session(db)
        while not stop.is_set():
            i = next_id[0]
            next_id[0] += 1
            try:
                w.execute(f"INSERT INTO b VALUES ({i}, 'k{i}')")
            except Exception as e:      # noqa: BLE001
                errs.append(f"{type(e).__name__}: {e}")
                return

    wt = threading.Thread(target=writer)
    wt.start()
    r = s.execute("ALTER TABLE b ADD GLOBAL UNIQUE INDEX g (k)")
    work = db.ddl.wait(r.arrow.to_pylist()[0]["work_id"], timeout=60)
    stop.set()
    wt.join()
    assert not errs, errs
    assert work.state == "public", work.error
    n = s.query("SELECT COUNT(*) n FROM b")[0]["n"]
    assert db.stores["default.__gidx__b__g"].num_rows == n
    with pytest.raises(ConflictError):
        s.execute("INSERT INTO b VALUES (99999, 'k3')")


def test_sorted_index_read_of_no_rows_answers_no_rows():
    """What made the readers test above fail once in a while (PR 35 found
    it): a reader that met the table empty — before the writer's first
    INSERT, or after its DELETE of row 0 — ran ORDER BY over an index
    gather of zero lanes, and ``ops/compact.stable_partition`` indexed the
    last element of an empty prefix sum."""
    import jax.numpy as jnp

    from baikaldb_tpu.ops.compact import stable_partition

    assert stable_partition(jnp.zeros(0, bool)).shape == (0,)
    db = Database()
    s = Session(db)
    s.execute("CREATE TABLE t (id BIGINT, v BIGINT, tag VARCHAR(16), "
              "PRIMARY KEY (id), GLOBAL INDEX g (tag)) ")
    q = "SELECT id FROM t WHERE tag = 'w3' ORDER BY id"
    assert s.query(q) == []
    s.execute("INSERT INTO t VALUES (0, 0, 'w0')")
    s.execute("DELETE FROM t WHERE id = 0")
    assert s.query(q) == []
    s.execute("INSERT INTO t VALUES (3, 3, 'w3')")
    assert s.query(q) == [{"id": 3}]
    assert s.query("SELECT id FROM t WHERE tag = 'w0' ORDER BY id") == []
