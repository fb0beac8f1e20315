"""A bulk load stamps a range (PR 35): ``MvccState.runs`` beside ``live_cts``.

``insert_arrow`` records one ``(first_rowid, stop_rowid, commit_ts)`` run
where it made a dict entry a row.  Everything a reader could see has to be
what the per-row dict gave: the twin of every scenario here is the same
engine with the bulk path switched back to ``MvccState.stamp`` (one dict
entry a row, the code before this PR), and the two are compared answer for
answer and stamp for stamp.
"""

import numpy as np
import pyarrow as pa
import pytest

from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.storage import mvcc
from baikaldb_tpu.storage.column_store import TableStore
from baikaldb_tpu.storage.mvcc import PENDING, MvccState


def _rows(lo: int, n: int) -> pa.Table:
    ids = np.arange(lo, lo + n, dtype=np.int64)
    return pa.table({"id": ids, "g": ids % 3, "v": ids * 10})


def _session():
    db = Database()
    s = Session(db, "t")
    s.execute("CREATE DATABASE t")
    s.execute("CREATE TABLE r (id BIGINT, g BIGINT, v BIGINT, "
              "PRIMARY KEY (id))")
    return db, s


@pytest.fixture
def per_row(monkeypatch):
    """Switch the bulk path back to one dict entry a row (the twin)."""
    def on():
        orig = TableStore._mvcc_stamp_new
        monkeypatch.setattr(
            TableStore, "_mvcc_stamp_new",
            lambda self, rowids, tctx, bulk=False: orig(self, rowids, tctx))
    return on


AGG = "SELECT g, COUNT(*) AS n, SUM(v) AS sv FROM r GROUP BY g ORDER BY g"
ALL = "SELECT id, v FROM r ORDER BY id"


def _scenario(db, s) -> list:
    """Pins before and after two loads, UPDATE and DELETE of loaded rows,
    a load inside a transaction committed and one rolled back, a GC sweep:
    every answer a reader gets on the way."""
    out = []
    store = db.stores["t.r"]
    r = Session(db, "t")                        # the pinned reader
    for i in range(4):
        s.execute(f"INSERT INTO r VALUES ({i}, {i % 3}, {i * 10})")
    r.execute("SET SNAPSHOT = 'now'")           # before any load
    s.load_arrow("r", _rows(100, 500))
    out.append(("pin before load", r.query(AGG), r.query(ALL)))
    out.append(("live after load", s.query(AGG)))
    r.execute("SET SNAPSHOT = 'now'")           # after the first load
    s.load_arrow("r", _rows(1000, 300))
    s.execute("UPDATE r SET v = v + 7 WHERE id >= 120 AND id < 140")
    s.execute("DELETE FROM r WHERE id >= 200 AND id < 260")
    s.execute("DELETE FROM r WHERE id = 2")
    out.append(("pin between loads", r.query(AGG), r.query(ALL)))
    out.append(("live after dml", s.query(AGG), s.query(ALL)))
    r.execute("SET SNAPSHOT = 'now'")
    s.execute("BEGIN")
    s.load_arrow("r", _rows(5000, 200))
    s.execute("UPDATE r SET v = v + 1 WHERE id >= 5000 AND id < 5010")
    s.execute("DELETE FROM r WHERE id = 5100")
    out.append(("own writes in txn", s.query(AGG)))
    out.append(("pin under open txn", r.query(AGG)))
    s.execute("COMMIT")
    out.append(("pin after commit", r.query(AGG), r.query(ALL)))
    r.execute("SET SNAPSHOT = 'now'")
    out.append(("new pin after commit", r.query(AGG)))
    s.execute("BEGIN")
    s.load_arrow("r", _rows(9000, 100))
    s.execute("UPDATE r SET v = 0 WHERE id < 150 AND id >= 100")
    s.execute("ROLLBACK")
    out.append(("after rollback", s.query(AGG), s.query(ALL),
                r.query(AGG)))
    r.execute("SET SNAPSHOT = 0")
    db.mvcc.gc(db.stores.values())
    out.append(("after gc", s.query(AGG), s.query(ALL)))
    # every later pin sees the settled table; an older one cannot exist
    r.execute("SET SNAPSHOT = 'now'")
    s.execute("UPDATE r SET v = v + 3 WHERE id >= 1000 AND id < 1005")
    out.append(("pin after gc under update", r.query(AGG), s.query(AGG)))
    mv = store._mvcc
    assert not mv.pending and not mv.pending_runs
    return out


def test_bulk_load_answers_as_the_per_row_dict_did(per_row):
    got = _scenario(*_session())
    per_row()
    want = _scenario(*_session())
    assert [x[0] for x in got] == [x[0] for x in want]
    for g, w in zip(got, want):
        assert g == w, g[0]
    # and the answers are the right ones, not merely the same
    by = {x[0]: x for x in got}
    assert [r["n"] for r in by["pin before load"][1]] == [2, 1, 1]
    assert sum(r["n"] for r in by["live after load"][1]) == 504
    assert sum(r["n"] for r in by["pin between loads"][1]) == 504
    assert sum(r["n"] for r in by["live after dml"][1]) == 804 - 61
    assert sum(r["n"] for r in by["own writes in txn"][1]) == 743 + 199
    assert by["pin under open txn"][1] == by["live after dml"][1]
    assert by["pin after commit"][1] == by["live after dml"][1]
    assert sum(r["n"] for r in by["new pin after commit"][1]) == 942
    assert by["after rollback"][1] == by["new pin after commit"][1]
    assert by["after rollback"][3] == by["new pin after commit"][1]


def test_a_load_is_one_run_whatever_its_size():
    db, s = _session()
    store = db.stores["t.r"]
    mv = store._mvcc
    before = mvcc.mvcc_range_stamps.value
    n = 1_000_000
    s.load_arrow("r", _rows(0, n))
    assert mvcc.mvcc_range_stamps.value - before == 1
    assert len(mv.live_cts) == 0 and len(mv.runs) == 1
    first, stop, cts = mv.runs[0]
    assert stop - first == n and 0 < cts < PENDING
    assert mv.high_water == cts and mv.live_stamps() == n
    s.load_arrow("r", _rows(n, 10))
    s.execute(f"INSERT INTO r VALUES ({n + 10}, 0, 0)")
    assert len(mv.runs) == 2 and len(mv.live_cts) == 1
    # the state holds O(runs) objects: a capture copies two tuples
    pre = mv.capture()
    assert pre[4] == mv.runs and pre[4] is not mv.runs
    # a pin older than the load reads the versioned image: one stamp a
    # live row, from a search of the runs
    live, cts_arr, dts_arr, scanned = store.snapshot_versions(cts - 1)
    assert live.num_rows == n + 11 and scanned == 0
    assert (cts_arr[:n] == cts).all() and (cts_arr[n:] > cts).all()
    assert store.snapshot_versions(db.mvcc.now_ts()) is None
    assert s.query("SELECT COUNT(*) AS c FROM r")[0]["c"] == n + 11
    # SHOW STATUS carries the counter a loader may ask for
    names = {str(r[0]).partition(".")[0]
             for r in s.execute("SHOW STATUS").rows}
    assert "mvcc_range_stamps" in names


def test_dict_wins_over_a_run_and_gc_drops_a_run_whole():
    mv = MvccState()
    mv.stamp_range(10, 20, 5)
    mv.stamp_range(20, 30, 9)
    assert [mv.stamp_of(r) for r in (9, 10, 19, 20, 29, 30)] \
        == [0, 5, 5, 9, 9, 0]
    mv.record_dead([{"id": 12}], [12], 11)      # an UPDATE of a loaded row
    mv.stamp([12], 11)
    assert mv.history == [({"id": 12}, 5, 11)]
    assert mv.stamp_of(12) == 11 and mv.stamp_of(13) == 5
    ids = np.array([30, 12, 10, 25, 9, 19], np.int64)
    assert mv.stamps_for(ids).tolist() == [0, 11, 5, 9, 0, 5]
    assert mv.high_water == 11
    assert mv.gc(5) == 0                        # the first run settles
    assert mv.runs == [(20, 30, 9)]
    assert mv.stamps_for(ids).tolist() == [0, 11, 0, 9, 0, 0]
    assert mv.gc(11) == 1
    assert mv.runs == [] and mv.live_cts == {} and mv.high_water == 0
    assert mv.stamps_for(ids).tolist() == [0] * 6


def test_pending_run_restamps_at_commit_and_leaves_at_rollback():
    mv = MvccState()
    mv.stamp_range(0, 4, 3)
    pre = mv.capture()
    mv.stamp_range(4, 10, PENDING)
    assert mv.pending_runs == 1 and mv.live_newer_than(10**18)
    assert mv.high_water == 3 and mv.stamp_of(5) == PENDING
    mv.record_dead([{"id": 5}], [5], PENDING)   # deleted by its own txn
    mv.stamp([1], PENDING)                      # an update of a settled row
    assert mv.restamp_pending(40) == 6 + 1 + 1
    assert mv.runs == [(0, 4, 3), (4, 10, 40)] and mv.pending_runs == 0
    assert mv.history == [({"id": 5}, PENDING, 40)]
    assert mv.stamp_of(1) == 40 and mv.high_water == 40
    assert not mv.live_newer_than(40) and mv.live_newer_than(39)
    mv.restore(pre)
    assert mv.runs == [(0, 4, 3)] and mv.pending_runs == 0
    assert mv.live_cts == {} and mv.history == [] and mv.high_water == 3
    mv.stamp_range(4, 10, PENDING)
    mv.restore(pre)                             # the rollback
    assert mv.runs == [(0, 4, 3)] and not mv.live_newer_than(3)
    mv.reset()
    assert mv.runs == [] and mv.pending_runs == 0


@pytest.mark.parametrize("seed", [1, 7, 35, 2**31 + 35])
def test_runs_read_as_the_per_row_dict(seed):
    """Random bulk stamps, per-row stamps, deaths, commits, rollbacks and
    sweeps on a state with runs and on one stamped a row at a time."""
    rng = np.random.default_rng(seed)
    a, b = MvccState(), MvccState()             # runs / the per-row dict
    next_rid, ts, live = 0, 10, []
    pre = None
    for _ in range(200):
        op = rng.choice(["bulk", "row", "dead", "begin", "commit",
                         "rollback", "gc"],
                        p=[.25, .2, .2, .1, .1, .05, .1])
        ts += 1
        cts = PENDING if pre is not None else ts
        if op == "bulk":
            n = int(rng.integers(1, 50))
            a.stamp_range(next_rid, next_rid + n, cts)
            b.stamp(range(next_rid, next_rid + n), cts)
            live.extend(range(next_rid, next_rid + n))
            next_rid += n
        elif op == "row":
            a.stamp([next_rid], cts)
            b.stamp([next_rid], cts)
            live.append(next_rid)
            next_rid += 1
        elif op == "dead" and live:
            rid = int(rng.choice(live))
            for m in (a, b):
                m.record_dead([{"id": rid}], [rid], cts)
            if rng.random() < .5:
                for m in (a, b):
                    m.stamp([rid], cts)         # an update: restamped
            else:
                live.remove(rid)
        elif op == "begin" and pre is None:
            pre = (a.capture(), b.capture(), list(live), next_rid)
        elif op == "commit" and pre is not None:
            # a run counts its whole range, a row of it that died or was
            # restamped inside the transaction too
            assert a.restamp_pending(ts) >= b.restamp_pending(ts)
            pre = None
        elif op == "rollback" and pre is not None:
            a.restore(pre[0])
            b.restore(pre[1])
            live, pre = pre[2], None    # burned rowids are never reused
        elif op == "gc":
            wm = int(rng.integers(10, ts + 1))
            assert a.gc(wm) == b.gc(wm)
        ids = np.array(live + [next_rid, next_rid + 5], np.int64)
        rng.shuffle(ids)
        want = [b.live_cts.get(int(r), 0) for r in ids]
        assert a.stamps_for(ids).tolist() == want
        assert [a.stamp_of(int(r)) for r in ids[:8]] == want[:8]
        assert a.history == b.history
        assert a.high_water == b.high_water
        for snap in (1, ts - 3, ts, ts + 1):
            assert a.live_newer_than(snap) == b.live_newer_than(snap)
        assert len(a.live_cts) <= len(b.live_cts)
