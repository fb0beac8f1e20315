"""The cell ``tpch_sf1_onechip_joins.q3q18``: Q18's plain reference against
a nested-loop one, its gaps, the traffic's parameters, the reader it brings,
and a rehearsal of the whole cell on the CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import trafficgen
from benchmark.loaders import tpch as loader
from benchmark.readers import joins as joins_readers
from benchmark.refs import tpch_q18

ROOT = Path(__file__).resolve().parents[2]
CELL = "tpch_sf1_onechip_joins.q3q18"
CELL_FILE = json.loads((ROOT / "benchmark/workloads" / f"{CELL}.json")
                       .read_text())
LIMITS = CELL_FILE["limits"]
COLS = tpch_q18.Q18_COLUMNS


def nested_loop_q18(tables: dict, quantity: int) -> list:
    """Q18 as the text reads, row by row in Python floats."""
    li = tables["lineitem"]
    qty: dict = {}
    for k, q in zip(li["l_orderkey"].to_pylist(),
                    li["l_quantity"].to_pylist()):
        qty[k] = qty.get(k, 0.0) + q
    names = dict(zip(tables["customer"]["c_custkey"].to_pylist(),
                     tables["customer"]["c_name"].to_pylist()))
    o = tables["orders"]
    rows = [(names[c], c, k, d.isoformat(), t, qty[k]) for k, c, d, t in zip(
        o["o_orderkey"].to_pylist(), o["o_custkey"].to_pylist(),
        o["o_orderdate"].to_pylist(), o["o_totalprice"].to_pylist())
        if qty.get(k, 0.0) > quantity and c in names]
    return sorted(rows, key=lambda r: (-r[4], r[3], r[2]))[:tpch_q18.LIMIT]


@pytest.fixture(scope="module")
def small():
    """200 orders: small enough for the nested loop."""
    return loader.generate(200 / 1_500_000, 2**31 + 33)


@pytest.mark.parametrize("quantity", [0, 60, 150, 200, 250, 400])
def test_q18_reference_is_the_nested_loop(small, quantity):
    cols, rows = tpch_q18.q18.answer({"tables": small},
                                     {"quantity": quantity})
    want = nested_loop_q18(small, quantity)
    assert cols == COLS
    assert rows == want                 # the sums are of whole numbers
    assert len(rows) == {0: 100, 400: 0}.get(quantity, len(rows))
    got = tpch_q18.q18.gaps(cols, [tuple(map(str, r)) for r in rows],
                            (cols, rows))
    assert got == {"q18_rel_gap": 0.0, "q18_mismatch": 0}


def test_q18_sums_are_kept_between_quantities():
    tables = loader.generate(0.002, 2**31 + 34)
    ctx = {"tables": tables}
    tpch_q18.q18.answer(ctx, {"quantity": 312})
    kept = ctx["q18.f64"]
    tpch_q18.q18.answer(ctx, {"quantity": 250})
    assert ctx["q18.f64"] is kept and "q18.f32" not in ctx


def test_q18_float32_control_fails_the_limit_and_keeps_the_keys():
    tables = loader.generate(0.01, 2**31 + 35)
    ctx = {"tables": tables}
    for quantity in (230, 250, 270):
        p = {"quantity": quantity}
        want = tpch_q18.q18.answer(ctx, p)
        assert 0 < len(want[1]) <= tpch_q18.LIMIT
        low = tpch_q18.q18.answer(ctx, p, lower=True)
        got = tpch_q18.q18.gaps(low[0], low[1], want)
        assert got["q18_rel_gap"] > 100 * LIMITS["q18_rel_gap"]
        assert got["q18_rel_gap"] < 1e-6
        assert [r[:4] for r in low[1]] == [r[:4] for r in want[1]]


def test_q18_gaps_count_what_differs():
    want = [("Customer#000000007", 7, 70, "1995-03-01", 900.5, 320.0),
            ("Customer#000000003", 3, 30, "1995-02-01", 800.25, 313.0),
            ("Customer#000000009", 9, 90, "1995-02-01", 800.25, 316.0),
            ("Customer#000000004", 4, 40, "1995-01-01", 700.0, 314.0)]
    gaps = tpch_q18.q18.gaps
    wire_cols = COLS[:5] + ("SUM(l_quantity)",)

    def wire(rows):
        return [tuple(map(str, r)) for r in rows]
    assert gaps(wire_cols, wire(want), (COLS, want)) \
        == {"q18_rel_gap": 0.0, "q18_mismatch": 0}
    # rows that tie in total price and date may swap; others may not
    swapped = [want[0], want[2], want[1], want[3]]
    assert gaps(wire_cols, wire(swapped), (COLS, want))["q18_mismatch"] == 0
    moved = [want[1], want[0], want[2], want[3]]
    assert gaps(wire_cols, wire(moved), (COLS, want))["q18_mismatch"] == 2
    # a name, a customer key, an order key, a date; a missing row; headers
    for i, v in ((0, "Customer#000000008"), (1, 8), (2, 71),
                 (3, "1995-03-02")):
        bad = [tuple(v if j == i else x for j, x in enumerate(want[0]))] \
            + want[1:]
        assert gaps(wire_cols, wire(bad), (COLS, want))["q18_mismatch"] == 1
    assert gaps(wire_cols, wire(want[:3]), (COLS, want))["q18_mismatch"] == 1
    assert gaps(wire_cols[::-1], wire(want), (COLS, want))["q18_mismatch"] \
        == 1
    assert gaps(wire_cols[:5], wire(want), (COLS, want))["q18_mismatch"] == 1
    # no rows against no rows is right; rows against none are not
    assert gaps(wire_cols, [], (COLS, [])) \
        == {"q18_rel_gap": 0.0, "q18_mismatch": 0}
    assert gaps(wire_cols, wire(want[:2]), (COLS, []))["q18_mismatch"] == 2
    assert gaps(wire_cols, [], (COLS, want))["q18_mismatch"] == 4
    # the two DOUBLEs
    for i in (4, 5):
        off = [tuple(x * (1 + 1e-9) if j == i else x
                     for j, x in enumerate(want[0]))] + want[1:]
        got = gaps(wire_cols, wire(off), (COLS, want))
        assert got["q18_mismatch"] == 0
        assert got["q18_rel_gap"] == pytest.approx(1e-9, rel=1e-3)
    nulled = [want[0][:4] + (None, 320.0)] + want[1:]
    assert gaps(wire_cols, nulled, (COLS, want))["q18_mismatch"] == 1


def test_q3q18_traffic_is_the_specification():
    """Ten transactions a cycle: each of the five segments' Q3 followed by
    a Q18; Q3's DATE a day of March 1995 (TPC-H 2.4.3.3), Q18's QUANTITY
    312-315 (2.4.18.3); Q3's statements are q1q3's, letter for letter."""
    traffic = trafficgen.load_traffic("q3q18")
    assert traffic["clients"] == 1 and traffic["warmup_rounds"] == 1
    assert len(traffic["transactions"]) == 10
    mesh = trafficgen.load_traffic("q1q3")["statements"]
    for name, st in traffic["statements"].items():
        if name != "q18":
            assert st == mesh[name]
        assert st["scans"] == ["customer", "orders", "lineitem"]
    c = trafficgen.Client(traffic, {}, 2**31 + 5, 0)
    seen, quantities = [], set()
    for _ in range(300):
        t = c.next()
        (s,) = t.statements
        seen.append(s.name)
        assert t.begin is None and t.commit is None
        assert t.annotation == f"client.{t.name}"
        if t.name == "q3":
            assert s.sql.endswith("ORDER BY revenue DESC, o_orderdate "
                                  "LIMIT 10")
            continue
        quantities.add(s.params["quantity"])
        assert f"HAVING SUM(l_quantity) > {s.params['quantity']})" in s.sql
        assert s.sql.startswith(
            "SELECT c_name, c_custkey, o_orderkey, o_orderdate, "
            "o_totalprice, SUM(l_quantity) FROM customer, orders, lineitem "
            "WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem GROUP BY "
            "l_orderkey HAVING")
        assert s.sql.endswith(
            "GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, "
            "o_totalprice ORDER BY o_totalprice DESC, o_orderdate LIMIT 100")
    assert seen[:10] == [x for seg in loader.SEGMENTS
                         for x in (f"q3_{seg.lower()}", "q18")]
    assert seen[10:20] == seen[:10]
    assert quantities == {312, 313, 314, 315}


def test_cap_fill_reads_nothing_where_there_is_nothing():
    assert joins_readers.cap_fill_pct(NS(counters={})) is None
    assert joins_readers.cap_fill_pct(
        NS(counters={"join_cap_retries": 1})) is None
    assert joins_readers.cap_fill_pct(
        NS(counters={"join_cap_slots": 0, "join_live_rows": 0})) is None
    assert joins_readers.cap_fill_pct(
        NS(counters={"join_cap_slots": 4096, "join_live_rows": 1024})) == 25.0


# what BENCHMARK.json held before this cell (PR 28): every new name has to
# sort after every one of these, whatever later cells add
ACCEPTED = {
    "configs": ["sysbench_1m", "tpch_sf1", "tpch_sf1_mesh4"],
    "workloads": ["sysbench_1m.read_only", "tpch_sf1.q1q6",
                  "tpch_sf1_mesh4.q1q3"],
    "analytic_qps": ["tpch_sf1.q1q6", "tpch_sf1_mesh4.q1q3"],
    "per_layer_last": "tpch_mesh4.scan_roofline",
}


def test_the_cells_files_sort_after_every_accepted_one():
    """``manifest.py`` lists files by name and the driver reads an entry in
    the middle of a list as an edit: this cell's configuration, cell and
    metrics come right after every entry the benchmark held before it."""
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    n = len(ACCEPTED["configs"])
    assert [c["name"] for c in m["configs"]][:n + 1] == \
        ACCEPTED["configs"] + [CELL_FILE["config"]]
    n = len(ACCEPTED["workloads"])
    assert [w["name"] for w in m["workloads"]][:n + 1] == \
        ACCEPTED["workloads"] + [CELL]
    assert m["workloads"][n]["chips"] == 1
    e2e = {x["name"]: x for x in m["end_to_end"]}
    n = len(ACCEPTED["analytic_qps"])
    assert e2e["analytic_qps"]["workloads"][:n + 1] == \
        ACCEPTED["analytic_qps"] + [CELL]
    names = [x["name"] for x in m["per_layer"]]
    mine = sorted(CELL_FILE["per_layer"])
    at = names.index(ACCEPTED["per_layer_last"])
    assert names[at + 1:at + 1 + len(mine)] == mine
    for x in m["per_layer"][at + 1:at + 1 + len(mine)]:
        assert x["workloads"] == [CELL] and x["moves"] == "analytic_qps"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace, tmp_path):
    """The whole cell at 1% as the driver starts it: correct, one Q18
    program for every QUANTITY, no retry and no trace inside the window,
    and the float32 control not correct."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 3301), "--seconds", "3", "--trace", str(trace),
         "--control", "1", "--rehearse-scale", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert line["control"]["correct"] is False
    assert line["control"]["numbers"]["q3_rel_gap"] \
        > 100 * LIMITS["q3_rel_gap"]
    for gap in ("q3_rel_gap", "q18_rel_gap"):
        assert line["compared"][gap]["value"] <= LIMITS[gap]
    c = line["counters"]
    assert "join_cap_retries" not in c and "xla_retraces" not in c
    assert "compile_ms" not in c and "plan_cache_misses" not in c
    assert c["join_cap_slots"] >= c["join_live_rows"] > 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(got) == set(CELL_FILE["end_to_end"])
        return
    assert set(got) < set(CELL_FILE["per_layer"])
    assert got["tpch_onechip_joins.retraces"] == 0
    assert got["tpch_onechip_joins.cap_retries"] == 0
    assert 0 < got["tpch_onechip_joins.cap_fill_pct"] <= 100
    assert got["tpch_onechip_joins.exec_ms"] > 0


def test_a_program_without_the_counters_refuses_the_cell_at_once():
    """What the parent commit does with this cell: its SHOW STATUS has no
    ``join_cap_slots``, and the run ends before any table is made (its
    first run would otherwise pass the driver's limit and be killed)."""
    from benchmark.loaders import tpch_joins

    config = json.loads((ROOT / "benchmark/configs"
                         / f"{CELL_FILE['config']}.json").read_text())
    assert config["loader"] == "benchmark.loaders.tpch_joins:load"

    class Parent:
        def __init__(self):
            self.seen = []

        def execute(self, sql):
            self.seen.append(sql)
            return NS(rows=[("join_cap_retries.value", "0"),
                            ("join_cap_retries.per_second", "0.0"),
                            ("Uptime", "3")])

    s = Parent()
    with pytest.raises(RuntimeError, match="join_cap_slots"):
        tpch_joins.load(config, 1, 0.01, s)
    assert s.seen == ["SHOW STATUS"]
