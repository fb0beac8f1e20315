"""The cell ``tpch_sf1_mesh4.q1q3``: Q3's plain reference against a
nested-loop one, its gaps, the traffic's parameters, the readers it brings,
and a rehearsal of the whole cell on the CPU's virtual devices."""

import datetime
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import trafficgen
from benchmark.loaders import tpch as loader
from benchmark.readers import mesh as mesh_readers
from benchmark.refs import tpch_joins

ROOT = Path(__file__).resolve().parents[2]
CELL = "tpch_sf1_mesh4.q1q3"
LIMITS = json.loads((ROOT / "benchmark/workloads" / f"{CELL}.json")
                    .read_text())["limits"]
REFS = {s: getattr(tpch_joins, f"q3_{s.lower()}") for s in loader.SEGMENTS}


def nested_loop_q3(tables: dict, segment: str, date: str) -> list:
    """Q3 as the text reads, row by row in Python floats."""
    day = datetime.date.fromisoformat(date)
    cust = {k for k, s in zip(tables["customer"]["c_custkey"].to_pylist(),
                              tables["customer"]["c_mktsegment"].to_pylist())
            if s == segment}
    o = tables["orders"]
    orders = {k: (d, p) for k, c, d, p in zip(
        o["o_orderkey"].to_pylist(), o["o_custkey"].to_pylist(),
        o["o_orderdate"].to_pylist(), o["o_shippriority"].to_pylist())
        if c in cust and d < day}
    li = tables["lineitem"]
    revenue: dict = {}
    for k, ship, price, disc in zip(
            li["l_orderkey"].to_pylist(), li["l_shipdate"].to_pylist(),
            li["l_extendedprice"].to_pylist(), li["l_discount"].to_pylist()):
        if k in orders and ship > day:
            revenue[k] = revenue.get(k, 0.0) + price * (1 - disc)
    rows = sorted(((k, r, *orders[k]) for k, r in revenue.items()),
                  key=lambda x: (-x[1], x[2], x[0]))[:tpch_joins.LIMIT]
    return [(k, r, d.isoformat(), p) for k, r, d, p in rows]


@pytest.fixture(scope="module")
def small():
    """200 orders: small enough for the nested loop, all dates of Q3's
    range on both sides."""
    return loader.generate(200 / 1_500_000, 2**31 + 28)


@pytest.mark.parametrize("segment", loader.SEGMENTS)
@pytest.mark.parametrize("date", ["1995-03-01", "1995-03-31", "1997-01-01"])
def test_q3_reference_is_the_nested_loop(small, segment, date):
    cols, rows = REFS[segment].answer({"tables": small}, {"date": date})
    want = nested_loop_q3(small, segment, date)
    assert cols == tpch_joins.Q3_COLUMNS
    assert [(r[0], r[2], r[3]) for r in rows] \
        == [(w[0], w[2], w[3]) for w in want]
    assert [r[1] for r in rows] == pytest.approx([w[1] for w in want],
                                                 rel=1e-15)
    got = REFS[segment].gaps(cols, [tuple(map(str, r)) for r in rows],
                             (cols, rows))
    assert got == {"q3_rel_gap": 0.0, "q3_mismatch": 0}


def test_q3_float32_control_fails_the_limit_and_keeps_the_keys():
    tables = loader.generate(0.01, 2**31 + 29)
    ctx = {"tables": tables}
    for i, (segment, ref) in enumerate(REFS.items()):
        p = {"date": f"1995-03-{1 + 7 * i:02d}"}
        want = ref.answer(ctx, p)
        assert len(want[1]) == tpch_joins.LIMIT
        low = ref.answer(ctx, p, lower=True)
        got = ref.gaps(low[0], low[1], want)
        assert got["q3_rel_gap"] > 100 * LIMITS["q3_rel_gap"]
        assert got["q3_rel_gap"] < 1e-5


def test_q3_gaps_count_what_differs():
    cols = tpch_joins.Q3_COLUMNS
    want = [(7, 300.0, "1995-03-01", 0), (3, 200.0, "1995-02-01", 0),
            (9, 200.0, "1995-02-01", 0), (4, 100.0, "1995-01-01", 0)]
    gaps = tpch_joins.q3_building.gaps

    def wire(rows):
        return [tuple(map(str, r)) for r in rows]
    assert gaps(cols, wire(want), (cols, want)) \
        == {"q3_rel_gap": 0.0, "q3_mismatch": 0}
    # rows that tie in revenue and date may swap; others may not
    swapped = [want[0], want[2], want[1], want[3]]
    assert gaps(cols, wire(swapped), (cols, want))["q3_mismatch"] == 0
    moved = [want[1], want[0], want[2], want[3]]
    assert gaps(cols, wire(moved), (cols, want))["q3_mismatch"] == 2
    # a key, a date, a priority, a missing row, a wrong header
    for i, v in ((0, 8), (2, "1995-03-02"), (3, 1)):
        bad = [tuple(v if j == i else x for j, x in enumerate(want[0]))] \
            + want[1:]
        assert gaps(cols, wire(bad), (cols, want))["q3_mismatch"] == 1
    assert gaps(cols, wire(want[:3]), (cols, want))["q3_mismatch"] == 1
    assert gaps(cols[::-1], wire(want), (cols, want))["q3_mismatch"] == 1
    off = [(7, 300.0 * (1 + 1e-9), "1995-03-01", 0)] + want[1:]
    got = gaps(cols, wire(off), (cols, want))
    assert got["q3_mismatch"] == 0
    assert got["q3_rel_gap"] == pytest.approx(1e-9, rel=1e-3)


def test_q1q3_traffic_is_the_specification():
    """Ten transactions a cycle: Q1 before each of the five segments' Q3;
    Q3's DATE a day of March 1995 (TPC-H 2.4.3.3), Q1's DELTA 60-120."""
    traffic = trafficgen.load_traffic("q1q3")
    assert traffic["clients"] == 1 and traffic["warmup_rounds"] == 1
    c = trafficgen.Client(traffic, {}, 2**31 + 5, 0)
    seen = []
    days = set()
    for _ in range(300):
        t = c.next()
        (s,) = t.statements
        seen.append(s.name)
        assert t.begin is None and t.commit is None
        assert t.annotation == f"client.{t.name}"
        if t.name == "q1":
            assert 60 <= s.params["delta"] <= 120
            assert f"l_shipdate <= '{s.params['cutoff']}'" in s.sql
            continue
        day = datetime.date.fromisoformat(s.params["date"])
        assert datetime.date(1995, 3, 1) <= day <= datetime.date(1995, 3, 31)
        days.add(day.day)
        segment = s.name[len("q3_"):].upper()
        assert segment in loader.SEGMENTS
        assert f"c_mktsegment = '{segment}'" in s.sql
        assert s.sql.count(f"'{s.params['date']}'") == 2
        assert s.sql.endswith("ORDER BY revenue DESC, o_orderdate LIMIT 10")
    assert seen[:10] == [x for seg in loader.SEGMENTS
                         for x in ("q1", f"q3_{seg.lower()}")]
    assert seen[10:20] == seen[:10] and days == set(range(1, 32))


def test_mesh_readers_read_nothing_where_there_is_nothing():
    w = NS(counters={"shuffle_rounds": 3, "exchange_bytes": 6e6},
           txns=[1, 2, 3], trace=None)
    assert mesh_readers.counter_per_txn(w, "mesh_programs") is None
    assert mesh_readers.counter_per_txn(w, "exchange_bytes", 1e6) == 2.0
    assert mesh_readers.counter_per_txn(NS(counters={"x": 1}, txns=[]),
                                        "x") is None
    assert mesh_readers.scan_roofline(w, chips=4) is None


def test_mesh_scan_roofline_counts_every_named_table_over_the_chips():
    import pyarrow as pa

    tables = {"a": pa.table({"k": np.zeros(1000, np.int32),
                             "v": np.zeros(1000, np.float64)}),
              "b": pa.table({"k2": np.zeros(500, np.int32),
                             "unused": np.zeros(500, np.float64)})}
    st = NS(name="j", sql="SELECT v FROM a, b WHERE k = k2", t0=1.0, t1=2.0)
    w = NS(trace={"busy_s": 1e-6, "window_s": 4.0}, trace_span=(0.0, 4.0),
           device_kind="TPU v5 lite", tables=tables,
           traffic={"statements": {"j": {"scans": ["a", "b"]},
                                   "n": {}}},
           txns=[NS(statements=[st]),
                 NS(statements=[NS(name="n", sql="SELECT 1", t0=2, t1=3)])])
    nbytes = 1000 * 12 + 500 * 4
    assert mesh_readers.scan_roofline(w, chips=4) == pytest.approx(
        100.0 * nbytes / (4 * 819e9) / 1e-6)
    w.traffic["statements"]["j"]["scans"] = "a"
    assert mesh_readers.scan_roofline(w, chips=1) == pytest.approx(
        100.0 * 12000 / 819e9 / 1e-6)
    w.device_kind = "TPU v9"
    with pytest.raises(KeyError):
        mesh_readers.scan_roofline(w, chips=4)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_virtual_devices(trace, tmp_path):
    """The whole cell at 1% on four of eight virtual CPU devices, as the
    driver starts it: correct, every statement one mesh program, one
    shuffle round a Q3, and the float32 control not correct."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 2801), "--seconds", "3", "--trace", str(trace),
         "--control", "1", "--rehearse-scale", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert line["control"]["correct"] is False
    for gap in ("q1_rel_gap", "q3_rel_gap"):
        assert line["control"]["numbers"][gap] > 100 * LIMITS[gap]
        assert line["compared"][gap]["value"] <= LIMITS[gap]
    c = line["counters"]
    assert c["mesh_programs"] == line["attempted"]
    assert "join_cap_retries" not in c and "xla_retraces" not in c
    cell = json.loads((ROOT / "benchmark/workloads" / f"{CELL}.json")
                      .read_text())
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(got) == set(cell["end_to_end"])
        return
    assert set(got) < set(cell["per_layer"])
    assert got["tpch_mesh4.mesh_programs"] == 1.0
    assert got["tpch_mesh4.retraces"] == 0
    assert got["tpch_mesh4.exchange_rounds"] == pytest.approx(0.5, abs=0.05)
    assert got["tpch_mesh4.exchange_mb"] > 0
    assert got["tpch_mesh4.exec_ms"] > 0


def test_a_program_without_the_setting_refuses_the_cell_at_once():
    """What the parent commit does with this cell: the loader's first
    statement is an unknown flag there, and the run ends before any load."""
    from benchmark.loaders import tpch_mesh

    class Refuses:
        def __init__(self):
            self.seen = []

        def execute(self, sql):
            self.seen.append(sql)
            raise RuntimeError("unknown flag 'mesh_devices'")

    s = Refuses()
    with pytest.raises(RuntimeError, match="mesh_devices"):
        tpch_mesh.load({"scale": {"scale_factor": 1.0, "mesh_devices": 4}},
                       1, 0.01, s)
    assert s.seen == ["SET GLOBAL mesh_devices = 4"]
