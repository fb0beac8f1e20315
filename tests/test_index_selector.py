"""IndexSelector + access paths (index/selector.py, reference:
src/physical_plan/index_selector.cpp): the host point-read fast path,
secondary-index row gathers, zone-map region pruning — choice visible in
EXPLAIN and flipping with predicates, results always identical to the full
scan."""

import numpy as np
import pytest

from baikaldb_tpu.exec.session import Session
from baikaldb_tpu.utils import metrics


@pytest.fixture
def sess():
    s = Session()
    s.execute("CREATE TABLE u (id BIGINT PRIMARY KEY, name VARCHAR(16), "
              "score DOUBLE, KEY kn (name))")
    s.execute("INSERT INTO u VALUES " +
              ",".join(f"({i},'u{i % 50}',{i * 1.0})" for i in range(1000)))
    return s


def test_point_lookup(sess):
    p0 = metrics.point_lookups.value
    assert sess.query("SELECT id, name FROM u WHERE id = 7") == \
        [{"id": 7, "name": "u7"}]
    assert sess.query("SELECT * FROM u WHERE id = 7") == \
        [{"id": 7, "name": "u7", "score": 7.0}]
    assert metrics.point_lookups.value == p0 + 2
    # miss -> empty, not an error
    assert sess.query("SELECT * FROM u WHERE id = 99999") == []
    # alias respected
    assert sess.query("SELECT name n FROM u WHERE id = 3") == [{"n": "u3"}]
    # expressions fall back to the device path but stay correct
    assert sess.query("SELECT score * 2 d FROM u WHERE id = 3") == \
        [{"d": 6.0}]
    # extra non-pk conjuncts are NOT a pure point read
    assert sess.query("SELECT id FROM u WHERE id = 7 AND score > 100") == []


def test_point_lookup_sees_txn_writes(sess):
    sess.execute("BEGIN")
    sess.execute("UPDATE u SET score = -1 WHERE id = 5")
    assert sess.query("SELECT score FROM u WHERE id = 5") == [{"score": -1.0}]
    sess.execute("ROLLBACK")
    assert sess.query("SELECT score FROM u WHERE id = 5") == [{"score": 5.0}]


def test_secondary_index_path(sess):
    plan = sess.execute("EXPLAIN SELECT score FROM u WHERE name = 'u3'") \
        .plan_text
    assert "index(kn:name)" in plan
    i0 = metrics.index_scans.value
    r = sess.query("SELECT COUNT(*) c, SUM(score) s FROM u "
                   "WHERE name = 'u3'")
    assert metrics.index_scans.value > i0
    want = [i * 1.0 for i in range(1000) if i % 50 == 3]
    assert r == [{"c": len(want), "s": sum(want)}]
    # stays correct after DML invalidates the index snapshot
    sess.execute("INSERT INTO u VALUES (5000, 'u3', 123.0)")
    r = sess.query("SELECT COUNT(*) c FROM u WHERE name = 'u3'")
    assert r == [{"c": len(want) + 1}]


def test_secondary_skipped_at_high_selectivity():
    s = Session()
    s.execute("CREATE TABLE h (id BIGINT PRIMARY KEY, g VARCHAR(4), "
              "KEY kg (g))")
    s.execute("INSERT INTO h VALUES " +
              ",".join(f"({i},'same')" for i in range(100)))
    plan = s.execute("EXPLAIN SELECT id FROM h WHERE g = 'same'").plan_text
    assert "index(" not in plan          # every row matches: full scan wins
    assert "full" in plan


def test_zone_map_pruning(sess):
    # on `score`, not the key: a selective range on `id` is the pk_range
    # arm's (tests/test_pk_range.py), tried before the zone maps
    st = sess.db.stores["default.u"]
    st.region_rows = 200
    sess.execute("INSERT INTO u VALUES " +
                 ",".join(f"({i},'z',{i * 1.0})" for i in range(2000, 3000)))
    assert len(st.regions) > 3
    plan = sess.execute("EXPLAIN SELECT SUM(score) FROM u "
                        "WHERE score >= 2900").plan_text
    assert "zonemap(" in plan and "regions pruned" in plan
    r0 = metrics.regions_pruned.value
    assert sess.query("SELECT COUNT(*) c FROM u WHERE score >= 2900") == \
        [{"c": 100}]
    assert metrics.regions_pruned.value > r0
    # range on both sides
    assert sess.query("SELECT COUNT(*) c FROM u WHERE score >= 2100 "
                      "AND score < 2300") == [{"c": 200}]
    # predicate outside every zone -> all regions pruned, empty result
    assert sess.query("SELECT COUNT(*) c FROM u WHERE score > 10000000") == \
        [{"c": 0}]
    # a range on the key too wide for the pk_range arm still prunes regions
    plan = sess.execute("EXPLAIN SELECT SUM(score) FROM u "
                        "WHERE id >= 2100").plan_text
    assert "zonemap(" in plan
    assert sess.query("SELECT COUNT(*) c FROM u WHERE id >= 2100") == \
        [{"c": 900}]


def test_zone_map_dates():
    s = Session()
    s.execute("CREATE TABLE ev (id BIGINT PRIMARY KEY, d DATE, v INT)")
    s.db.stores["default.ev"].region_rows = 100
    rows = []
    for i in range(300):
        month = 1 + (i // 100)
        rows.append(f"({i},'1994-{month:02d}-15',{i})")
    s.execute("INSERT INTO ev VALUES " + ",".join(rows))
    plan = s.execute("EXPLAIN SELECT SUM(v) FROM ev "
                     "WHERE d >= '1994-03-01'").plan_text
    assert "zonemap(" in plan
    assert s.query("SELECT COUNT(*) c FROM ev WHERE d >= '1994-03-01'") == \
        [{"c": 100}]


def test_access_paths_compose_with_joins(sess):
    """Multi-scan plans keep full scans (the conservative default)."""
    sess.execute("CREATE TABLE g (name VARCHAR(16) PRIMARY KEY, lab VARCHAR(8))")
    sess.execute("INSERT INTO g VALUES ('u3','three'),('u4','four')")
    r = sess.query("SELECT g.lab, COUNT(*) c FROM u JOIN g ON u.name=g.name "
                   "GROUP BY g.lab ORDER BY g.lab")
    assert r == [{"lab": "four", "c": 20}, {"lab": "three", "c": 20}]


def test_point_lookup_residual_predicates_respected(sess):
    # non-pk equality conjunct must NOT be dropped by the fast path
    assert sess.query("SELECT id, name FROM u WHERE id = 7 "
                      "AND name = 'WRONG'") == []
    # contradictory pk equalities
    assert sess.query("SELECT id FROM u WHERE id = 7 AND id = 8") == []
    # consistent duplicates are fine
    assert sess.query("SELECT id FROM u WHERE id = 7 AND id = 7") == \
        [{"id": 7}]
    # duplicate output names keep the device path's rename behavior
    r = sess.query("SELECT name, name FROM u WHERE id = 7")
    assert len(r[0]) == 2


def _ranges(where: str) -> dict:
    from baikaldb_tpu.index.selector import analyze_conjuncts
    from baikaldb_tpu.sql.parser import parse_sql

    stmt, = parse_sql(f"SELECT id FROM u WHERE {where}")
    return analyze_conjuncts(stmt.where).ranges


@pytest.mark.parametrize("where,want", [
    ("id BETWEEN 5 AND 104", {"id": [5, 104]}),
    ("id BETWEEN 1 AND 9 AND id > 3", {"id": [3, 9]}),
    ("id >= 2 AND id BETWEEN 1 AND 9 AND score < 7.5",
     {"id": [2, 9], "score": [None, 7.5]}),
    ("id BETWEEN NULL AND 9", {}),
    ("id BETWEEN 1 AND NULL", {}),
    ("id BETWEEN score AND 9", {}),
    ("id BETWEEN 1 AND score", {}),
    ("id NOT BETWEEN 1 AND 9", {}),
    ("id BETWEEN 1 AND 9 OR id = 20", {}),
])
def test_between_is_the_closed_range(where, want):
    assert _ranges(where) == want


def test_mixed_type_literals_dont_crash(sess):
    # a nonsense comparison must not break predicate analysis
    r = sess.query("SELECT id FROM u WHERE id = 7 AND id > 'x'")
    assert isinstance(r, list)


def test_access_cache_bounded(sess):
    for i in range(60):
        sess.query(f"SELECT COUNT(*) c FROM u WHERE name = 'u{i % 50}'")
    assert len(getattr(sess, "_access_batches", {})) <= \
        sess._ACCESS_CACHE_MAX


def test_point_write_fast_path_semantics():
    """Point UPDATE/DELETE (full-PK equality WHERE) take the host mask +
    narrow-assign path; semantics must match the compiled path exactly."""
    from baikaldb_tpu.exec.session import Database, Session

    s = Session(Database())
    s.execute("CREATE TABLE pw (id BIGINT, k BIGINT, c VARCHAR(20), "
              "PRIMARY KEY (id))")
    s.execute("INSERT INTO pw VALUES (1, 10, 'a'), (2, 20, 'b'), "
              "(3, 30, NULL)")
    # expression assignment referencing another column
    assert s.execute("UPDATE pw SET k = k + id WHERE id = 2").affected_rows == 1
    assert s.query("SELECT k FROM pw WHERE id = 2") == [{"k": 22}]
    # NULL assignment and NULL-input expression
    s.execute("UPDATE pw SET c = NULL WHERE id = 1")
    s.execute("UPDATE pw SET c = CONCAT(c, '!') WHERE id = 3")  # NULL stays
    assert s.query("SELECT c FROM pw WHERE id = 1") == [{"c": None}]
    assert s.query("SELECT c FROM pw WHERE id = 3") == [{"c": None}]
    # PK reassignment goes through (index refresh still correct)
    s.execute("UPDATE pw SET id = 9 WHERE id = 1")
    assert s.query("SELECT id FROM pw WHERE id = 9") == [{"id": 9}]
    assert s.query("SELECT id FROM pw WHERE id = 1") == []
    # no-match update and residual non-pk conjunct (must NOT fast-path)
    assert s.execute("UPDATE pw SET k = 0 WHERE id = 99").affected_rows == 0
    assert s.execute("UPDATE pw SET k = 0 WHERE id = 2 AND c = 'ZZZ'") \
        .affected_rows == 0
    # point delete
    assert s.execute("DELETE FROM pw WHERE id = 2").affected_rows == 1
    assert s.query("SELECT COUNT(*) n FROM pw") == [{"n": 2}]
    # type-mismatched pk literal: the compiled path evaluates id = 2.5
    # numerically (0 rows); the fast path must fall back, not abort
    assert s.execute("UPDATE pw SET k = 0 WHERE id = 2.5").affected_rows == 0
    assert s.execute("DELETE FROM pw WHERE id = 2.5").affected_rows == 0
    assert s.query("SELECT COUNT(*) n FROM pw") == [{"n": 2}]
