"""MVCC snapshot reads: visibility, SET SNAPSHOT, automatic analytical
pins, GC watermarks, the off-switch, and the observability surface.

The tentpole contract (docs + ISSUE): a pinned analytical query sees
exactly the state committed at its snapshot timestamp while OLTP write
traffic keeps flowing — resident path, under a live region split, and
after GC sweeps — and ``mvcc=0`` reads bit-identically to the pre-MVCC
engine.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from baikaldb_tpu.chaos.failpoint import clear_all, set_failpoint
from baikaldb_tpu.exec.session import Database, Session
from baikaldb_tpu.raft.core import raft_available
from baikaldb_tpu.sql.lexer import SqlError
from baikaldb_tpu.storage.mvcc import (MAX_TS, PENDING, MvccState,
                                       SnapshotRegistry, visibility_mask)
from baikaldb_tpu.utils.flags import FLAGS, set_flag

needs_raft = pytest.mark.skipif(not raft_available(),
                                reason="native raft core unavailable")


@pytest.fixture(autouse=True)
def _clean():
    clear_all()
    set_flag("mvcc", True)
    yield
    clear_all()
    set_flag("mvcc", True)
    set_flag("snapshot_max_age_s", 300.0)


def _session():
    db = Database()
    s = Session(db, "t")
    s.execute("CREATE DATABASE t")
    s.execute("CREATE TABLE r (id BIGINT, g BIGINT, v BIGINT, "
              "PRIMARY KEY (id))")
    for i in range(8):
        s.execute(f"INSERT INTO r VALUES ({i}, {i % 2}, {i * 10})")
    return db, s


# ---- visibility primitive --------------------------------------------------

def test_visibility_mask_interval_semantics():
    cts = jnp.asarray(np.array([1, 5, 9, 0, 3], dtype=np.int64))
    dts = jnp.asarray(np.array([4, MAX_TS, MAX_TS, MAX_TS, PENDING],
                               dtype=np.int64))
    m = np.asarray(visibility_mask(cts, dts, jnp.int64(5)))
    # [cts <= 5 < dts]: closed at 4 -> dead; 5 visible; 9 future; 0 always
    assert m.tolist() == [False, True, False, True, True]
    # a PENDING delete_ts never hides the version from real snapshots
    m0 = np.asarray(visibility_mask(cts, dts, jnp.int64(0)))
    assert m0.tolist() == [False, False, False, True, False]


def test_mvcc_state_restamp_and_rollback_capture():
    st = MvccState()
    st.stamp([1, 2], PENDING)
    pre = st.capture()
    st.record_dead([{"id": 3}], [3], PENDING)
    assert st.restamp_pending(77) == 3
    assert st.live_cts == {1: 77, 2: 77}
    assert st.history == [({"id": 3}, 0, 77)]
    st.restore(pre)
    assert st.live_cts == {1: PENDING, 2: PENDING} and st.history == []


def test_rollback_after_mid_txn_gc_drops_only_its_own_history():
    """A sweep while a transaction is open shortens the history under the
    captured mark; the rollback must still cut exactly the transaction's
    own (PENDING) entries, or a dead-but-unclosed version stays behind
    and a pinned read returns the row twice."""
    st = MvccState()
    st.stamp([1, 2], 10)
    st.record_dead([{"id": 1}], [1], 20)        # committed, reclaimable
    pre = st.capture()
    st.record_dead([{"id": 2}], [2], PENDING)   # the open transaction's
    assert st.gc(25) == 1
    st.restore(pre)
    assert st.history == [] and st.live_cts == {2: 10}
    assert (st.high_water, st.pending) == (10, set())


# ---- pinned reads under writes --------------------------------------------

def test_set_snapshot_pins_under_update_delete_insert():
    db, s = _session()
    s.execute("SET SNAPSHOT = 'now'")
    base = s.query("SELECT id, v FROM r ORDER BY id")
    agg = s.query("SELECT g, SUM(v) FROM r GROUP BY g ORDER BY g")
    w = Session(db, "t")
    w.execute("UPDATE r SET v = v + 1000 WHERE id < 4")
    w.execute("DELETE FROM r WHERE id = 5")
    w.execute("INSERT INTO r VALUES (100, 0, 1)")
    assert s.query("SELECT id, v FROM r ORDER BY id") == base
    assert s.query("SELECT g, SUM(v) FROM r GROUP BY g ORDER BY g") == agg
    s.execute("SET SNAPSHOT = 0")
    now = s.query("SELECT id, v FROM r ORDER BY id")
    assert now != base
    assert {r["id"] for r in now} == {0, 1, 2, 3, 4, 6, 7, 100}


def test_set_snapshot_at_recorded_ts_replays():
    db, s = _session()
    s.execute("SET SNAPSHOT = 'now'")
    ts = s._snapshot[1]
    base = s.query("SELECT SUM(v), COUNT(*) FROM r")
    w = Session(db, "t")
    for i in range(8):
        w.execute(f"UPDATE r SET v = v + 5 WHERE id = {i}")
    s2 = Session(db, "t")
    s2.execute(f"SET SNAPSHOT = {ts}")
    assert s2.query("SELECT SUM(v), COUNT(*) FROM r") == base
    s2.execute("SET SNAPSHOT = 0")
    s.execute("SET SNAPSHOT = 0")


def test_set_snapshot_validation():
    db, s = _session()
    with pytest.raises(SqlError):
        s.execute("SET SNAPSHOT = 'tuesday'")
    set_flag("mvcc", False)
    with pytest.raises(SqlError):
        s.execute("SET SNAPSHOT = 'now'")


def test_auto_pin_analytical_consistency_point():
    """An aggregate without an explicit pin draws ONE fresh ts: its pin
    registers while it runs and releases after."""
    db, s = _session()
    reg = db.mvcc.snapshots
    seen = []
    orig = reg.pin

    def spy(ts, query="", holder=""):
        seen.append(query)
        return orig(ts, query=query, holder=holder)

    reg.pin = spy
    try:
        s.query("SELECT g, SUM(v) FROM r GROUP BY g ORDER BY g")
    finally:
        reg.pin = orig
    assert seen == ["auto"]
    assert reg.describe() == []         # released at query end
    # non-analytical statements never pin
    seen.clear()
    reg.pin = spy
    try:
        s.query("SELECT id FROM r WHERE id = 3")
    finally:
        reg.pin = orig
    assert seen == []


def test_auto_pin_refusal_degrades_unpinned():
    db, s = _session()
    set_flag("chaos_seed", 1)
    set_failpoint("snapshot.pin", "drop")
    # automatic pins degrade silently; results still correct
    assert s.query("SELECT SUM(v) AS sv FROM r")[0]["sv"] == sum(
        i * 10 for i in range(8))
    # explicit pins surface the refusal
    with pytest.raises(SqlError):
        s.execute("SET SNAPSHOT = 'now'")


def test_off_switch_bit_identical():
    db, s = _session()
    on = s.query("SELECT g, SUM(v) FROM r GROUP BY g ORDER BY g")
    rows_on = s.query("SELECT id, v FROM r ORDER BY id")
    set_flag("mvcc", False)
    assert s.query("SELECT g, SUM(v) FROM r GROUP BY g ORDER BY g") == on
    assert s.query("SELECT id, v FROM r ORDER BY id") == rows_on


# ---- transactions ----------------------------------------------------------

def test_txn_commit_stamps_one_ts_rollback_restores():
    db, s = _session()
    store = db.stores["t.r"]
    s.execute("SET SNAPSHOT = 'now'")
    base = s.query("SELECT SUM(v) FROM r")
    w = Session(db, "t")
    w.execute("BEGIN")
    w.execute("UPDATE r SET v = v + 100 WHERE id = 0")
    w.execute("INSERT INTO r VALUES (50, 0, 7)")
    # uncommitted rows carry PENDING: invisible to every real snapshot
    assert PENDING in store._mvcc.live_cts.values()
    w.execute("COMMIT")
    stamps = {c for c in store._mvcc.live_cts.values() if c != PENDING}
    assert PENDING not in store._mvcc.live_cts.values()
    # the txn's two DMLs share ONE decide-time commit_ts
    new_rows = [c for c in store._mvcc.live_cts.values()]
    assert len(set(new_rows)) >= 1
    assert s.query("SELECT SUM(v) FROM r") == base     # pin unaffected
    # rollback: the MVCC preimage restores with the row preimage
    w.execute("BEGIN")
    w.execute("DELETE FROM r WHERE id = 1")
    pre_hist = len(store._mvcc.history)
    w.execute("ROLLBACK")
    assert len(store._mvcc.history) < pre_hist or pre_hist == 0 or \
        len(store._mvcc.history) == pre_hist - 1
    s.execute("SET SNAPSHOT = 0")
    assert {r["id"] for r in s.query("SELECT id FROM r")} >= {0, 1, 50}


# ---- GC --------------------------------------------------------------------

def test_gc_never_reclaims_at_or_above_oldest_pin():
    db, s = _session()
    s.execute("SET SNAPSHOT = 'now'")
    ts = s._snapshot[1]
    base = s.query("SELECT SUM(v) FROM r")
    w = Session(db, "t")
    for i in range(8):
        w.execute(f"UPDATE r SET v = v + 3 WHERE id = {i}")
    store = db.stores["t.r"]
    assert store._mvcc.history          # versions exist
    wm = db.mvcc.snapshots.watermark(db.mvcc.tso.last_ts())
    assert wm <= ts
    db.mvcc.gc(db.stores.values())
    assert s.query("SELECT SUM(v) FROM r") == base
    # release the pin: the watermark advances and the sweep reclaims
    s.execute("SET SNAPSHOT = 0")
    reclaimed = db.mvcc.gc(db.stores.values())
    assert reclaimed >= 8
    assert store._mvcc.history == []


def test_expired_pin_stops_holding_watermark():
    reg = SnapshotRegistry()
    reg.pin(1000, query="q")
    assert reg.watermark(5000) == 1000
    set_flag("snapshot_max_age_s", 0.0)     # every pin is instantly stale
    assert reg.watermark(5000) == 5000


def test_wedged_gc_failpoint_skips_one_sweep():
    db, s = _session()
    w = Session(db, "t")
    for i in range(8):
        w.execute(f"UPDATE r SET v = v + 3 WHERE id = {i}")
    store = db.stores["t.r"]
    n = len(store._mvcc.history)
    assert n >= 8
    set_flag("chaos_seed", 1)
    set_failpoint("mvcc.gc", "drop")
    assert db.mvcc.gc(db.stores.values()) == 0      # wedged
    assert len(store._mvcc.history) == n
    clear_all()
    assert db.mvcc.gc(db.stores.values()) >= n


# ---- fleet: pinned snapshot survives a live split -------------------------

@needs_raft
def test_pinned_snapshot_survives_live_split():
    from baikaldb_tpu.meta.service import MetaService
    from baikaldb_tpu.raft.fleet import StoreFleet

    fleet = StoreFleet(MetaService(peer_count=3),
                       [f"c{i + 1}:1" for i in range(3)], seed=9)
    db = Database(fleet=fleet)
    s = Session(db, "t")
    s.execute("CREATE DATABASE t")
    s.execute("CREATE TABLE r (id BIGINT, v BIGINT, PRIMARY KEY (id))")
    for i in range(12):
        s.execute(f"INSERT INTO r VALUES ({i}, {i})")
    s.execute("SET SNAPSHOT = 'now'")
    base = s.query("SELECT SUM(v), COUNT(*) FROM r")
    tier = fleet.row_tiers["t.r"]
    parent = tier.metas[0].region_id
    mid = []

    def hook(phase):
        # the pinned aggregate re-runs DURING the split, writes flowing
        s.execute(f"INSERT INTO r VALUES ({100 + len(mid)}, 1)")
        mid.append(s.query("SELECT SUM(v), COUNT(*) FROM r") == base)

    tier.split_region_online(parent, chaos_hook=hook)
    assert mid and all(mid), "pinned agg diverged mid-split"
    assert s.query("SELECT SUM(v), COUNT(*) FROM r") == base
    s.execute("SET SNAPSHOT = 0")
    assert s.query("SELECT COUNT(*) AS c FROM r")[0]["c"] == \
        12 + len(mid)


@needs_raft
def test_snapshot_chaos_scenario_deterministic():
    from baikaldb_tpu.chaos.scenarios import run_scenario

    a = run_scenario("snapshot_chaos", 5, writes=24)
    assert a["ok"], a
    b = run_scenario("snapshot_chaos", 5, writes=24)
    assert b["ok"] and b["state_digest"] == a["state_digest"]
    assert b["fault_schedule"] == a["fault_schedule"]


# ---- observability ---------------------------------------------------------

def test_information_schema_snapshots_and_query_log():
    db, s = _session()
    s.execute("SET SNAPSHOT = 'now'")
    ts = s._snapshot[1]
    rows = s.query("SELECT * FROM information_schema.snapshots")
    assert len(rows) == 1
    assert rows[0]["snapshot_ts"] == ts
    assert rows[0]["query"] == "SET SNAPSHOT"
    assert rows[0]["holder"] == "root"
    assert rows[0]["age_ms"] >= 0
    s.query("SELECT SUM(v) FROM r")
    ql = s.query("SELECT query, snapshot_ts FROM "
                 "information_schema.query_log")
    pinned = [r for r in ql if r["query"] == "SELECT SUM(v) FROM r"]
    assert pinned and pinned[-1]["snapshot_ts"] == ts
    s.execute("SET SNAPSHOT = 0")
    assert s.query("SELECT * FROM information_schema.snapshots") == []


def test_show_status_tso_mvcc_rows():
    db, s = _session()
    rows = {r["Variable_name"]: r["Value"]
            for r in s.query("SHOW STATUS")}
    assert "tso.allocations.value" in rows
    assert "tso.batch_refills.value" in rows
    assert "mvcc.gc_reclaimed.value" in rows
    assert "mvcc.quiet_checks.value" in rows
    assert "mvcc.versioned_checks.value" in rows
    assert "mvcc.live_versions.value" in rows
    assert "mvcc.oldest_pin.value" in rows
    assert int(rows["tso.allocations.value"]) > 0   # the inserts stamped


def test_explain_analyze_snapshot_line():
    db, s = _session()
    s.execute("SET SNAPSHOT = 'now'")
    w = Session(db, "t")
    w.execute("UPDATE r SET v = v + 1 WHERE id = 0")    # creates a version
    plan = "\n".join(
        r["plan"] for r in s.query("EXPLAIN ANALYZE SELECT SUM(v) FROM r"))
    line = next(l for l in plan.splitlines() if l.startswith("-- snapshot:"))
    assert f"ts={s._snapshot[1]}" in line
    assert "versions_scanned=1" in line
    assert "gc_watermark=" in line
    s.execute("SET SNAPSHOT = 0")
    plan2 = "\n".join(
        r["plan"] for r in s.query("EXPLAIN ANALYZE SELECT id FROM r "
                                   "WHERE id = 3"))
    assert "-- snapshot:" not in plan2


# ---- the live-stamp summary (high_water / pending) vs the walk -------------
#
# The pinned read's "has this table moved past my snapshot?" used to walk
# every live stamp; MvccState now answers from a summary kept at the write
# hooks.  The walk stays HERE, as the plain reference the summary is held to.

def _walk_stamps(mv: MvccState) -> list:
    """Every live stamp: the dict's and, since a bulk load is one run
    (PR 35), each run's."""
    return list(mv.live_cts.values()) + [r[2] for r in mv.runs]


def _walk_diverged(mv: MvccState, snap: int) -> bool:
    """The pre-summary answer: history alive at snap, or any live stamp
    above it (PENDING is MAX_TS, so an open transaction counts)."""
    return bool(mv.versions_at(snap)) or \
        any(c > snap for c in _walk_stamps(mv))


def _walk_max(mv: MvccState) -> int:
    return max((c for c in _walk_stamps(mv) if c != PENDING), default=0)


def _check_summary(store, stamps, rng) -> None:
    """Both check sites against the walk, at pins older than, between and
    newer than every stamp handed out so far."""
    mv = store._mvcc
    assert mv.pending == {r for r, c in mv.live_cts.items() if c == PENDING}
    true_max = _walk_max(mv)
    assert mv.high_water >= true_max        # an upper bound, always
    pool = sorted(set(stamps))
    snaps = {1, pool[-1] + 7, pool[0] - 1 if pool[0] > 1 else 1,
             mv.high_water, max(mv.high_water - 1, 1), true_max or 1}
    snaps.update(int(x) for x in rng.choice(pool, size=min(4, len(pool))))
    snaps.update(int(x) + 1 for x in rng.choice(pool, size=2))
    for snap in sorted(snaps):
        ref = _walk_diverged(mv, snap)
        got = store.mvcc_needs_versioned(snap)
        built = store.snapshot_versions(snap) is not None
        assert got == built                 # the two sites agree
        assert got or not ref, \
            f"summary says quiet at {snap} where the walk says changed"
        if mv.high_water == true_max or mv.pending:
            assert got == ref, (snap, mv.high_water, true_max)


@pytest.mark.parametrize("seed", [3, 11, 27, 42, 1009, 2600000311])
def test_summary_matches_walk_reference(seed):
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    db = Database()
    s = Session(db, "t")
    s.execute("CREATE DATABASE t")
    s.execute("CREATE TABLE r (id BIGINT, g BIGINT, v BIGINT, "
              "PRIMARY KEY (id))")
    store = db.stores["t.r"]
    mv = store._mvcc
    tso = db.mvcc.tso
    stamps = [tso.next_ts()]
    next_id = [0]
    in_txn = False
    # no delete has popped the maximum since the summary was last exact
    # (reset, or a GC at or above the mark): high_water must EQUAL the max.
    # A rollback brings back the mark that went with the pre-image, so it
    # is as exact as it was when the transaction began.
    clean = clean_at_begin = True

    def fresh_ids(n):
        ids = list(range(next_id[0], next_id[0] + n))
        next_id[0] += n
        return ids

    def live_ids():
        return [r["id"] for r in store.snapshot().select(["id"]).to_pylist()]

    for _ in range(70):
        op = rng.choice(["load", "insert", "update", "delete", "begin",
                         "commit", "rollback", "gc", "truncate"],
                        p=[.08, .2, .2, .16, .1, .08, .06, .09, .03])
        ids = live_ids()
        if op == "load" and not in_txn:
            new = fresh_ids(int(rng.integers(1, 40)))
            store.insert_arrow(pa.table({
                "id": pa.array(new, pa.int64()),
                "g": pa.array([i % 3 for i in new], pa.int64()),
                "v": pa.array([i * 10 for i in new], pa.int64())}))
        elif op == "insert":
            i, = fresh_ids(1)
            s.execute(f"INSERT INTO r VALUES ({i}, {i % 3}, {i * 10})")
        elif op == "update" and ids:
            i = int(rng.choice(ids))
            s.execute(f"UPDATE r SET v = v + 1 WHERE id >= {i} "
                      f"AND id < {i + int(rng.integers(1, 4))}")
        elif op == "delete" and ids:
            # newest rows die as often as old ones: the popped maximum
            i = ids[-1] if rng.random() < .4 else int(rng.choice(ids))
            s.execute(f"DELETE FROM r WHERE id = {i}")
            clean = False
        elif op == "begin" and not in_txn:
            s.execute("BEGIN")
            in_txn, clean_at_begin = True, clean
        elif op == "commit" and in_txn:
            s.execute("COMMIT")
            in_txn = False
        elif op == "rollback" and in_txn:
            s.execute("ROLLBACK")
            in_txn, clean = False, clean_at_begin
        elif op == "gc":
            wm = int(rng.choice(stamps + [tso.last_ts() + 1]))
            mark = mv.high_water
            store.mvcc_gc(wm)
            if wm >= mark:
                assert mv.high_water == 0
                assert _walk_max(mv) == 0   # exact again
                clean = True
        elif op == "truncate" and not in_txn:
            s.execute("TRUNCATE TABLE r")
            assert mv.high_water == 0 and not mv.pending
            clean = True
        stamps.append(tso.last_ts())
        if clean and not mv.pending:
            assert mv.high_water == _walk_max(mv)
        _check_summary(store, stamps, rng)
    if in_txn:
        s.execute("ROLLBACK")
        _check_summary(store, stamps, rng)


@pytest.mark.parametrize("seed", [5, 19, 77, 2600000313])
def test_pinned_select_matches_history_model(seed):
    """The rows a pinned SELECT returns == a plain in-memory model of the
    same committed history, at pins before, between and after the writes
    — whichever of the live or the versioned image the summary chose."""
    rng = np.random.default_rng(seed)
    db = Database()
    w = Session(db, "t")
    w.execute("CREATE DATABASE t")
    w.execute("CREATE TABLE r (id BIGINT, g BIGINT, v BIGINT, "
              "PRIMARY KEY (id))")
    reader = Session(db, "t")
    tso = db.mvcc.tso
    cur: dict[int, int] = {}            # committed state: id -> v
    txn: dict[int, int] | None = None   # the open transaction's view
    states = [(tso.next_ts(), {})]      # (ts, committed state at ts)
    floor = 0                           # pins below it have lost history
    next_id = 0

    def check():
        pins = [t for t, _ in states if t >= floor]
        picks = {pins[-1], int(rng.choice(pins)), int(rng.choice(pins))}
        for ts in sorted(picks):
            want = next(st for t, st in reversed(states) if t <= ts)
            reader.execute(f"SET SNAPSHOT = {ts}")
            try:
                rows = reader.query("SELECT id, v FROM r ORDER BY id")
                agg = reader.query("SELECT SUM(v) AS sv, COUNT(*) AS c "
                                   "FROM r")
            finally:
                reader.execute("SET SNAPSHOT = 0")
            assert [(r["id"], r["v"]) for r in rows] == sorted(want.items())
            # (grouped by g the keys can come back wrong: the strict xfail
            # below pins that older fault of the versioned path)
            assert (agg[0]["sv"] or 0, agg[0]["c"]) == \
                (sum(want.values()), len(want))

    for step in range(45):
        view = cur if txn is None else txn
        op = rng.choice(["insert", "update", "delete", "begin", "commit",
                         "rollback", "gc", "truncate"],
                        p=[.3, .22, .16, .1, .08, .06, .06, .02])
        if op == "insert":
            i, next_id = next_id, next_id + 1
            w.execute(f"INSERT INTO r VALUES ({i}, {i % 3}, {i * 10})")
            view[i] = i * 10
        elif op == "update" and view:
            i = int(rng.choice(sorted(view)))
            w.execute(f"UPDATE r SET v = v + 1 WHERE id = {i}")
            view[i] += 1
        elif op == "delete" and view:
            ids = sorted(view)
            i = ids[-1] if rng.random() < .4 else int(rng.choice(ids))
            w.execute(f"DELETE FROM r WHERE id = {i}")
            del view[i]
        elif op == "begin" and txn is None:
            w.execute("BEGIN")
            txn = dict(cur)
        elif op == "commit" and txn is not None:
            w.execute("COMMIT")
            cur, txn = txn, None
        elif op == "rollback" and txn is not None:
            w.execute("ROLLBACK")
            txn = None
        elif op == "gc":
            floor = max(floor, db.mvcc.snapshots.watermark(tso.last_ts()))
            db.mvcc.gc(db.stores.values())
        elif op == "truncate" and txn is None:
            w.execute("TRUNCATE TABLE r")
            cur = {}
            floor = max(floor, tso.next_ts())
        states.append((tso.next_ts(), dict(cur)))
        if step % 4 == 3:
            check()
    if txn is not None:
        w.execute("ROLLBACK")
    check()


@pytest.mark.xfail(strict=True, reason=(
    "older than the summary (the walk gives the same answer): the planner "
    "sizes a dense GROUP BY domain from the LIVE image's min/max, and the "
    "versioned image holds history rows outside it — ROADMAP A3"))
def test_pinned_group_by_key_outside_live_domain():
    db = Database()
    s = Session(db, "t")
    s.execute("CREATE DATABASE t")
    s.execute("CREATE TABLE r (id BIGINT, g BIGINT, v BIGINT, "
              "PRIMARY KEY (id))")
    s.execute("INSERT INTO r VALUES (0, 0, 0)")
    s.execute("INSERT INTO r VALUES (1, 1, 10)")
    s.execute("SET SNAPSHOT = 'now'")
    Session(db, "t").execute("DELETE FROM r WHERE id = 1")
    agg = s.query("SELECT g, SUM(v) AS sv FROM r GROUP BY g ORDER BY g")
    assert [(r["g"], r["sv"]) for r in agg] == [(0, 0), (1, 10)]


# ---- the quiet path does not scale with rows -------------------------------

class _NoWalk(dict):
    """live_cts that refuses to be walked: the quiet-table answer may read
    the summary (and index single rowids), never iterate the stamps."""

    def _refuse(self, *a, **k):
        raise AssertionError("quiet path walked live_cts")

    values = items = keys = __iter__ = _refuse


def test_quiet_check_never_walks_live_stamps():
    import pyarrow as pa

    db, s = _session()
    store = db.stores["t.r"]
    ids = list(range(100, 5100))
    store.insert_arrow(pa.table({
        "id": pa.array(ids, pa.int64()),
        "g": pa.array([i % 2 for i in ids], pa.int64()),
        "v": pa.array(ids, pa.int64())}))
    mv = store._mvcc
    # a load stamps every row, as one run beside the eight INSERTs' entries
    assert len(mv.live_cts) == 8 and mv.live_stamps() == 8 + len(ids)
    snap = db.mvcc.now_ts()
    mv.live_cts = _NoWalk(mv.live_cts)
    with pytest.raises(AssertionError):
        any(c > snap for c in mv.live_cts.values())    # the old walk trips
    assert store.mvcc_needs_versioned(snap) is False
    assert store.snapshot_versions(snap) is None
    # a pin older than the load: "changed" is answered without a walk too
    assert store.mvcc_needs_versioned(mv.high_water - 1) is True
    # end to end: the auto-pinned aggregate runs on the unwalkable dict
    assert s.query("SELECT COUNT(*) AS c FROM r")[0]["c"] == 8 + len(ids)


def test_served_path_check_counters():
    """An auto-pinned aggregate asks twice (route gate, batch staging) and
    both answers are "quiet"; under an older explicit pin, after another
    session's committed UPDATE, both are "versioned"."""
    from baikaldb_tpu.storage.mvcc import (mvcc_quiet_checks,
                                           mvcc_versioned_checks)

    db, s = _session()

    def grew(fn):
        q0, v0 = mvcc_quiet_checks.value, mvcc_versioned_checks.value
        out = fn()
        return (out, mvcc_quiet_checks.value - q0,
                mvcc_versioned_checks.value - v0)

    q = "SELECT g, SUM(v) AS sv FROM r GROUP BY g ORDER BY g"
    base, quiet, versioned = grew(lambda: s.query(q))
    assert (quiet, versioned) == (2, 0)
    s.execute("SET SNAPSHOT = 'now'")
    w = Session(db, "t")
    w.execute("UPDATE r SET v = v + 1000 WHERE id = 2")
    pinned, quiet, versioned = grew(lambda: s.query(q))
    assert (quiet, versioned) == (0, 2)
    assert pinned == base
    s.execute("SET SNAPSHOT = 0")
    # inside BEGIN..COMMIT no pin is taken and neither site is reached
    s.execute("BEGIN")
    _, quiet, versioned = grew(lambda: s.query(q))
    s.execute("COMMIT")
    assert (quiet, versioned) == (0, 0)
