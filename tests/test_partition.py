"""Table partitioning (VERDICT r03 missing #2 / next #5).

Reference: range/hash partitions live in SchemaInfo
(include/common/schema_factory.h:427-533) with a dedicated PartitionAnalyze
pass (src/physical_plan/physical_planner.cpp:27-120) pruning partitions the
predicates cannot touch.  Here each partition's rows land in that
partition's own column-tier regions; the selector drops whole partitions
before zone maps look, and EXPLAIN shows the pruning.
"""

import pytest

from baikaldb_tpu.exec.session import Database, PlanError, Session


def mk():
    return Session(Database())


LINEITEM_DDL = """
CREATE TABLE lineitem (
  l_orderkey BIGINT, l_quantity DOUBLE, l_extendedprice DOUBLE,
  l_discount DOUBLE, l_shipdate DATE, PRIMARY KEY (l_orderkey)
) PARTITION BY RANGE (l_shipdate) (
  PARTITION p1992 VALUES LESS THAN ('1993-01-01'),
  PARTITION p1993 VALUES LESS THAN ('1994-01-01'),
  PARTITION p1994 VALUES LESS THAN ('1995-01-01'),
  PARTITION pmax VALUES LESS THAN MAXVALUE
)
"""


def fill_lineitem(s, n=120):
    rows = []
    for i in range(n):
        year = 1992 + (i % 4)
        day = 1 + (i % 27)
        rows.append(f"({i}, {i % 50}.0, {100.0 + i}, 0.0{i % 9}, "
                    f"'{year}-03-{day:02d}')")
    s.execute("INSERT INTO lineitem VALUES " + ", ".join(rows))


def test_range_partition_prunes_and_matches_unpartitioned():
    """The verdict's done-criterion: lineitem partitioned by date range,
    EXPLAIN shows pruned partitions, results golden-checked against the
    same data unpartitioned."""
    s = mk()
    s.execute(LINEITEM_DDL)
    fill_lineitem(s)
    s.execute("CREATE TABLE flat (l_orderkey BIGINT, l_quantity DOUBLE, "
              "l_extendedprice DOUBLE, l_discount DOUBLE, l_shipdate DATE, "
              "PRIMARY KEY (l_orderkey))")
    s.execute("INSERT INTO flat SELECT * FROM lineitem")
    q = ("SELECT COUNT(*) n, SUM(l_extendedprice * (1 - l_discount)) rev "
         "FROM {t} WHERE l_shipdate >= '1993-01-01' "
         "AND l_shipdate < '1994-01-01'")
    plan = "\n".join(r["plan"] for r in
                     s.query("EXPLAIN " + q.format(t="lineitem")))
    assert "partition(" in plan and "partitions pruned" in plan
    got = s.query(q.format(t="lineitem"))
    want = s.query(q.format(t="flat"))
    # two region layouts reduce in two orders: the count is exact, the
    # float sum agrees to rounding
    assert got[0]["n"] == want[0]["n"] and got[0]["n"] > 0
    assert got[0]["rev"] == pytest.approx(want[0]["rev"], rel=1e-12, abs=0)


def test_rows_land_in_per_partition_regions():
    s = mk()
    s.execute(LINEITEM_DDL)
    fill_lineitem(s, 40)
    store = s.db.stores[f"{s.current_db}.lineitem"]
    parts = {r.part for r in store.regions if r.num_rows}
    assert parts == {0, 1, 2, 3}
    for r in store.regions:
        if not r.num_rows:
            continue
        ids = store.partition_ids(r.data)
        assert set(ids.tolist()) == {r.part}       # no partition mixing


def test_no_partition_for_value_rejected():
    s = mk()
    s.execute("CREATE TABLE t (id BIGINT, v BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY RANGE (v) ("
              "PARTITION p0 VALUES LESS THAN (10), "
              "PARTITION p1 VALUES LESS THAN (20))")
    s.execute("INSERT INTO t VALUES (1, 5), (2, 15)")
    with pytest.raises(Exception, match="no partition for value"):
        s.execute("INSERT INTO t VALUES (3, 25)")
    assert s.query("SELECT COUNT(*) n FROM t") == [{"n": 2}]


def test_hash_partitioning_routes_and_prunes_equality():
    s = mk()
    s.execute("CREATE TABLE h (id BIGINT, k BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY HASH (k) PARTITIONS 4")
    s.execute("INSERT INTO h VALUES " +
              ", ".join(f"({i}, {i % 10})" for i in range(80)))
    store = s.db.stores[f"{s.current_db}.h"]
    assert {r.part for r in store.regions if r.num_rows} <= {0, 1, 2, 3}
    plan = "\n".join(r["plan"] for r in
                     s.query("EXPLAIN SELECT COUNT(*) n FROM h WHERE k = 3"))
    assert "partition(3/4 partitions pruned)" in plan
    assert s.query("SELECT COUNT(*) n FROM h WHERE k = 3") == [{"n": 8}]


def test_add_and_drop_partition():
    s = mk()
    s.execute("CREATE TABLE t (id BIGINT, v BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY RANGE (v) ("
              "PARTITION p0 VALUES LESS THAN (10), "
              "PARTITION p1 VALUES LESS THAN (20))")
    s.execute("INSERT INTO t VALUES (1, 5), (2, 15)")
    with pytest.raises(Exception):
        s.execute("INSERT INTO t VALUES (3, 25)")
    s.execute("ALTER TABLE t ADD PARTITION "
              "(PARTITION p2 VALUES LESS THAN (30))")
    s.execute("INSERT INTO t VALUES (3, 25)")       # now routable
    ddl = s.query("SHOW CREATE TABLE t")[0]["Create Table"]
    assert "PARTITION BY RANGE" in ddl and "p2" in ddl
    # DROP PARTITION removes the partition's rows
    r = s.execute("ALTER TABLE t DROP PARTITION p0")
    assert r.affected_rows == 1
    got = s.query("SELECT id FROM t ORDER BY id")
    assert [x["id"] for x in got] == [2, 3]
    # values below the old p0 bound now fall into the next range
    s.execute("INSERT INTO t VALUES (9, 5)")
    assert s.query("SELECT COUNT(*) n FROM t WHERE v < 10") == [{"n": 1}]
    with pytest.raises(PlanError):
        s.execute("ALTER TABLE t DROP PARTITION nope")


def test_closed_upper_bound_keeps_boundary_partition():
    """WHERE v <= bound: the partition holding the bound itself (v = bound
    lives in the NEXT range) must survive pruning."""
    s = mk()
    s.execute("CREATE TABLE t (id BIGINT, v BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY RANGE (v) ("
              "PARTITION p0 VALUES LESS THAN (10), "
              "PARTITION p1 VALUES LESS THAN (20))")
    s.execute("INSERT INTO t VALUES (1, 5), (2, 10), (3, 15)")
    got = s.query("SELECT id FROM t WHERE v <= 10 ORDER BY id")
    assert [r["id"] for r in got] == [1, 2]


def test_null_partition_key_routes_to_lowest():
    s = mk()
    s.execute("CREATE TABLE t (id BIGINT, v VARCHAR(8), PRIMARY KEY (id)) "
              "PARTITION BY RANGE (v) ("
              "PARTITION p0 VALUES LESS THAN ('m'), "
              "PARTITION p1 VALUES LESS THAN MAXVALUE)")
    s.execute("INSERT INTO t VALUES (1, NULL), (2, 'a'), (3, 'z')")
    assert s.query("SELECT COUNT(*) n FROM t") == [{"n": 3}]
    got = s.query("SELECT id FROM t WHERE v IS NULL")
    assert [r["id"] for r in got] == [1]
    # hash partitioning with a NULL key also routes (to partition 0)
    s.execute("CREATE TABLE h (id BIGINT, k BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY HASH (k) PARTITIONS 3")
    s.execute("INSERT INTO h VALUES (1, NULL), (2, 7)")
    assert s.query("SELECT COUNT(*) n FROM h") == [{"n": 2}]


def test_partition_clause_after_options():
    s = mk()
    s.execute("CREATE TABLE t (id BIGINT, k BIGINT, PRIMARY KEY (id)) "
              "ENGINE=olap PARTITION BY HASH (k) PARTITIONS 4")
    store = s.db.stores[f"{s.current_db}.t"]
    assert store.partition_spec() is not None
    assert (store.info.options or {}).get("engine") == "olap"


def test_partition_ddl_guards():
    s = mk()
    with pytest.raises(PlanError):
        s.execute("CREATE TABLE z (id BIGINT, k BIGINT) "
                  "PARTITION BY HASH (k) PARTITIONS 0")
    s.execute("CREATE TABLE t (id BIGINT, v BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY RANGE (v) "
              "(PARTITION p0 VALUES LESS THAN (10))")
    with pytest.raises(PlanError):
        s.execute("ALTER TABLE t DROP PARTITION p0")   # last partition
    # DDL implicit-commits an open transaction (MySQL semantics): ROLLBACK
    # after partition DDL must not resurrect rows across the remap
    s.execute("BEGIN")
    s.execute("INSERT INTO t VALUES (1, 5)")
    s.execute("ALTER TABLE t ADD PARTITION "
              "(PARTITION p1 VALUES LESS THAN (20))")
    s.execute("ROLLBACK")                              # nothing to undo
    assert s.query("SELECT COUNT(*) n FROM t") == [{"n": 1}]


def test_partition_bounds_validated():
    s = mk()
    with pytest.raises(PlanError):
        s.execute("CREATE TABLE bad (id BIGINT, v BIGINT) "
                  "PARTITION BY RANGE (v) ("
                  "PARTITION p0 VALUES LESS THAN (20), "
                  "PARTITION p1 VALUES LESS THAN (10))")
    with pytest.raises(PlanError):
        s.execute("CREATE TABLE bad2 (id BIGINT) "
                  "PARTITION BY RANGE (nope) ("
                  "PARTITION p0 VALUES LESS THAN (10))")


def test_update_moves_row_across_partitions():
    """UPDATE changing the partition-column value must MOVE the row to its
    new partition's regions — a stale region tag would make pruning drop
    it from results."""
    s = mk()
    s.execute("CREATE TABLE t (id BIGINT, v BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY RANGE (v) ("
              "PARTITION p0 VALUES LESS THAN (10), "
              "PARTITION p1 VALUES LESS THAN (20))")
    s.execute("INSERT INTO t VALUES (1, 5), (2, 15)")
    s.execute("UPDATE t SET v = 15 WHERE id = 1")
    got = s.query("SELECT id FROM t WHERE v = 15 ORDER BY id")
    assert [r["id"] for r in got] == [1, 2]
    store = s.db.stores[f"{s.current_db}.t"]
    for r in store.regions:
        if r.num_rows and r.part >= 0:
            assert set(store.partition_ids(r.data).tolist()) == {r.part}
    # moving OUT of every range fails the statement cleanly
    with pytest.raises(Exception, match="no partition for value"):
        s.execute("UPDATE t SET v = 99 WHERE id = 1")
    assert s.query("SELECT COUNT(*) n FROM t") == [{"n": 2}]


def test_unroutable_insert_does_not_strand_wal_row(tmp_path):
    """A rejected INSERT (no partition for value) must not leave a durable
    WAL row that bricks replay on reopen."""
    d = str(tmp_path / "db")
    s = Session(Database(data_dir=d))
    s.execute("CREATE TABLE t (id BIGINT, v BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY RANGE (v) "
              "(PARTITION p0 VALUES LESS THAN (10))")
    s.execute("INSERT INTO t VALUES (1, 5)")
    with pytest.raises(Exception, match="no partition for value"):
        s.execute("INSERT INTO t VALUES (2, 25)")
    # reopen: replay must succeed and hold exactly the committed row
    s2 = Session(Database(data_dir=d))
    assert s2.query("SELECT id FROM t") == [{"id": 1}]


def test_partitions_survive_checkpoint_reload(tmp_path):
    d = str(tmp_path / "db")
    s = Session(Database(data_dir=d))
    s.execute("CREATE TABLE t (id BIGINT, v BIGINT, PRIMARY KEY (id)) "
              "PARTITION BY RANGE (v) ("
              "PARTITION p0 VALUES LESS THAN (10), "
              "PARTITION p1 VALUES LESS THAN (20))")
    s.execute("INSERT INTO t VALUES (1, 5), (2, 15)")
    s.db.checkpoint()
    s2 = Session(Database(data_dir=d))
    store = s2.db.stores[f"{s2.current_db}.t"]
    assert store.partition_spec() is not None
    parts = {r.part for r in store.regions if r.num_rows}
    assert parts == {0, 1}                          # tags survived reload
    plan = "\n".join(r["plan"] for r in
                     s2.query("EXPLAIN SELECT id FROM t WHERE v = 5"))
    assert "partitions pruned" in plan
    assert s2.query("SELECT id FROM t WHERE v = 5") == [{"id": 1}]
