"""The cell ``tpu_northstar_100m.groupby``: its files against
``BENCHMARK.json``, the plain reference against a row-by-row one and
against the engine, the float32 control, the loader's refusal, the traffic,
the reader it brings, and a rehearsal of the whole cell on the CPU."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import trafficgen
from benchmark.loaders import northstar as loader
from benchmark.readers import northstar as readers
from benchmark.refs import northstar as refs

ROOT = Path(__file__).resolve().parents[2]
CELL = "tpu_northstar_100m.groupby"
CELL_FILE = json.loads((ROOT / "benchmark/workloads" / f"{CELL}.json")
                       .read_text())
CONFIG = json.loads((ROOT / "benchmark/configs"
                     / f"{CELL_FILE['config']}.json").read_text())
LIMITS = CELL_FILE["limits"]
TRAFFIC = trafficgen.load_traffic(CELL_FILE["traffic"])
STATEMENTS = {"g16": "g", "g1000": "g1000", "g4000": "g4000"}
XS = ("0.25", "0.50", "0.75", "1.00")
# what BENCHMARK.json held before this cell (PR 34): every new name has to
# sort after every one of these, whatever later cells add
ACCEPTED = {
    "configs": ["sysbench_1m", "tpch_sf1", "tpch_sf1_mesh4",
                "tpch_sf1_onechip_joins"],
    "workloads": ["sysbench_1m.read_only", "tpch_sf1.q1q6",
                  "tpch_sf1_mesh4.q1q3", "tpch_sf1_onechip_joins.q3q18"],
    "per_layer_last": "tpch_onechip_joins.scan_roofline",
}


def test_benchmark_json_holds_the_cell_after_every_accepted_entry():
    """``manifest.py`` lists files by name and the driver reads an entry in
    the middle of a list as an edit: the configuration, the cell and the
    metrics come after everything PR 34's ``BENCHMARK.json`` held, in the
    order it held it."""
    from benchmark import manifest

    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert manifest.build() == m
    configs = [c["name"] for c in m["configs"]]
    cells = [w["name"] for w in m["workloads"]]
    assert configs[:4] == ACCEPTED["configs"]
    assert cells[:4] == ACCEPTED["workloads"]
    assert configs[4] == CELL_FILE["config"] and cells[4] == CELL
    assert m["workloads"][4]["chips"] == 1
    assert m["configs"][4]["reduced"] == ["cluster_layout"]
    assert m["configs"][4]["file"] == \
        "benchmark/configs/tpu_northstar_100m.json"
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["analytic_qps"]["workloads"][:4] == ACCEPTED["workloads"][1:] \
        + [CELL]
    assert "workloads" not in e2e["setup_s"]
    names = [x["name"] for x in m["per_layer"]]
    mine = sorted(CELL_FILE["per_layer"])
    at = names.index(ACCEPTED["per_layer_last"])
    assert names[at + 1:at + 1 + len(mine)] == mine
    assert all(n.startswith("tpu_northstar.") for n in mine)
    for x in m["per_layer"][at + 1:at + 1 + len(mine)]:
        assert x["workloads"] == [CELL] and x["moves"] == "analytic_qps"
    assert set(CELL_FILE["end_to_end"]) == {"analytic_qps", "setup_s"}
    assert len(CELL_FILE["why"]) <= 200 and len(CONFIG["why"]) <= 200
    assert len(CONFIG["source"]) <= 200


def test_the_deployment_is_the_sources_size():
    assert CONFIG["scale"]["rows"] == 100_000_000
    assert CONFIG["requires"]["status"] == ["mvcc_range_stamps"]
    assert loader.DDL == \
        "CREATE TABLE t (g INT, g1000 INT, g4000 INT, v FLOAT)"
    t = loader.generate(50_000, 2**31 + 35)
    assert t.column_names == ["g", "g1000", "g4000", "v"]
    assert [str(f.type) for f in t.schema] == ["int32"] * 3 + ["float"]
    for name, n in loader.KEYS.items():
        col = t.column(name).to_numpy()
        assert col.min() == 0 and col.max() == n - 1
    assert t.equals(loader.generate(50_000, 2**31 + 35))
    assert not t.equals(loader.generate(50_000, 2**31 + 36))
    v = t.column("v").to_numpy()
    assert abs(v.mean()) < 0.02 and abs(v.std() - 1) < 0.02


# -- the traffic --------------------------------------------------------------

def test_traffic_is_the_baseline_statement_at_three_group_counts():
    assert TRAFFIC["clients"] == 1 and TRAFFIC["warmup_rounds"] == 2
    assert [t["name"] for t in TRAFFIC["transactions"]] == list(STATEMENTS)
    c = trafficgen.Client(TRAFFIC, {"table_size": 1000}, 2**31 + 7, 0)
    seen = set()
    for i in range(240):
        t = c.next()
        (s,) = t.statements
        assert t.begin is None and t.commit is None
        assert t.name == s.name == list(STATEMENTS)[i % 3]
        assert t.annotation == f"client.{t.name}"
        key = STATEMENTS[s.name]
        assert s.params["x"] in XS
        assert s.sql == (f"SELECT {key}, COUNT(*) n, SUM(v) s, AVG(v) a, "
                         f"MIN(v) mn FROM t WHERE v*2+1 > {s.params['x']} "
                         f"GROUP BY {key}")
        seen.add((s.name, s.params["x"]))
    assert len(seen) == 12          # every statement with every x


def test_no_alias_of_the_sql_is_a_column_name():
    """``scanned_bytes`` reads a statement's text for column names: an
    alias that is one would count that column's bytes."""
    from benchmark.readers.device import scanned_bytes

    columns = set(loader.KEYS) | {"v"}
    table = loader.generate(1000, 1)
    for name, st in TRAFFIC["statements"].items():
        assert st["scans"] == "t"
        aliases = re.findall(r"\)\s+(\w+)", st["sql"])
        assert aliases == ["n", "s", "a", "mn"]
        assert not columns & set(aliases)
        assert "t" not in columns
        named = {c for c in columns if re.search(rf"\b{c}\b", st["sql"])}
        assert named == {STATEMENTS[name], "v"}
        assert scanned_bytes(st["sql"].format(x="0.50"), table) == 2 * 4000


# -- the reference ------------------------------------------------------------

def _row_by_row(table, key: str, x: float) -> dict:
    """The statement as its text reads, a row at a time in Python floats."""
    out: dict = {}
    for g, v in zip(table.column(key).to_pylist(),
                    table.column("v").to_pylist()):
        if v * 2 + 1 > x:
            n, s, mn = out.get(g, (0, 0.0, float("inf")))
            out[g] = (n + 1, s + v, min(mn, v))
    return out


@pytest.mark.parametrize("name", list(STATEMENTS))
def test_reference_is_the_statement_row_by_row(name):
    table = loader.generate(30_000, 2**31 + 3501)
    ref = getattr(refs, name)
    for x in XS:
        cols, rows = ref.answer({"tables": {"t": table}}, {"x": x})
        want = _row_by_row(table, STATEMENTS[name], float(x))
        assert cols == (STATEMENTS[name], "n", "s", "a", "mn")
        assert [r[0] for r in rows] == sorted(want)
        for g, n, s, a, mn in rows:
            assert (n, mn) == (want[g][0], want[g][2])
            assert s == pytest.approx(want[g][1], rel=1e-13, abs=1e-13)
            assert a == pytest.approx(want[g][1] / n, rel=1e-13, abs=1e-13)


def test_reference_takes_the_filter_in_float64_at_the_boundary():
    """``v*2+1 > x`` over a FLOAT column is DOUBLE arithmetic: a float32
    evaluation rounds the value next above the boundary onto it."""
    import pyarrow as pa

    edge = np.float32(-0.25)
    v = np.array([edge, np.nextafter(edge, np.float32(0)),
                  np.nextafter(edge, np.float32(-1)), 2.0 ** -30, 0.0],
                 np.float32)
    table = pa.table({"g": np.zeros(5, np.int32), "g1000": np.zeros(5, np.int32),
                      "g4000": np.zeros(5, np.int32), "v": v})
    ctx = {"tables": {"t": table}}
    assert refs.g16.answer(ctx, {"x": "0.50"})[1][0][1] == 3
    assert refs.g16.answer(ctx, {"x": "1.00"})[1][0][1] == 1


def test_reference_computes_each_statement_and_x_once(monkeypatch):
    """The check after the window costs 12 passes whatever the rate
    (ROADMAP S1: a check whose cost grows with the rate lost PR 29)."""
    calls = []
    real = refs._compute

    def counted(table, key, x, lower):
        calls.append((key, x, lower))
        return real(table, key, x, lower)

    monkeypatch.setattr(refs, "_compute", counted)
    ctx = {"tables": {"t": loader.generate(5_000, 9)}}
    for _ in range(5):
        for name in STATEMENTS:
            for x in XS:
                getattr(refs, name).answer(ctx, {"x": x})
    assert len(calls) == 12 and len(set(calls)) == 12
    for name in STATEMENTS:
        getattr(refs, name).answer(ctx, {"x": "0.50"}, lower=True)
        getattr(refs, name).answer(ctx, {"x": "0.50"}, lower=True)
    assert len(calls) == 15


@pytest.mark.parametrize("name", list(STATEMENTS))
def test_float32_control_fails_its_limit_and_keeps_the_exact_part(name):
    table = loader.generate(400_000, 2**31 + 3502)
    ctx = {"tables": {"t": table}}
    ref = getattr(refs, name)
    for x in XS:
        want = ref.answer(ctx, {"x": x})
        low = ref.answer(ctx, {"x": x}, lower=True)
        got = ref.gaps(low[0], low[1], want)
        assert got["groupby_mismatch"] == 0
        assert got[f"{name}_rel_gap"] > 10 * LIMITS[f"{name}_rel_gap"]
        assert got[f"{name}_rel_gap"] < 1e-5
        assert set(got) == {f"{name}_rel_gap", "groupby_mismatch"}
    # set from the chip's readings (PERF.md section 2): select+reduce
    # accumulates in the chip's DOUBLE, the Pallas arm in f32 pairs
    assert LIMITS == {"g16_rel_gap": 1e-10, "g1000_rel_gap": 1e-9,
                      "g4000_rel_gap": 1e-9, "groupby_mismatch": 0}


def test_gaps_count_what_differs_whatever_the_order():
    want_cols = ("g", "n", "s", "a", "mn")
    want = [(0, 3, 1.5, 0.5, -0.25), (1, 2, -4.0, -2.0, -3.0),
            (5, 1, 2.0, 2.0, 2.0)]
    gaps = refs.g16.gaps

    def wire(rows):
        return [tuple(map(str, r)) for r in rows]
    ok = {"g16_rel_gap": 0.0, "groupby_mismatch": 0}
    assert gaps(want_cols, wire(want), (want_cols, want)) == ok
    assert gaps(want_cols, wire(want[::-1]), (want_cols, want)) == ok
    for i, v in ((0, 7), (1, 4), (4, -0.5)):       # key, count, MIN
        bad = [tuple(v if j == i else x for j, x in enumerate(want[0]))] \
            + want[1:]
        assert gaps(want_cols, wire(bad),
                    (want_cols, want))["groupby_mismatch"] == 1
    assert gaps(want_cols, wire(want[:2]),
                (want_cols, want))["groupby_mismatch"] == 1
    assert gaps(want_cols, wire(want + [want[0]]),
                (want_cols, want))["groupby_mismatch"] == 2
    assert gaps(("g", "n", "s", "a", "x"), wire(want),
                (want_cols, want))["groupby_mismatch"] == 4
    nulled = [(0, 3, None, 0.5, -0.25)] + want[1:]
    assert gaps(want_cols, nulled, (want_cols, want))["groupby_mismatch"] == 1
    for i in (2, 3):                                # SUM, AVG
        off = [tuple(x * (1 + 1e-9) if j == i else x
                     for j, x in enumerate(want[1]))] + [want[0], want[2]]
        got = gaps(want_cols, wire(off), (want_cols, want))
        assert got["groupby_mismatch"] == 0
        assert got["g16_rel_gap"] == pytest.approx(1e-9, rel=1e-3)
    # MIN is compared as the float32 it is, however the wire prints it
    third = np.float32(1) / np.float32(3)
    w = [(0, 1, float(third), float(third), float(third))]
    assert gaps(want_cols, [("0", "1", repr(float(third)),
                             repr(float(third)), str(third))],
                (want_cols, w)) == ok


@pytest.mark.parametrize("name", list(STATEMENTS))
def test_reference_agrees_with_the_engine(name):
    """Three group counts, four x, seeded data, over the wire's client."""
    from baikaldb_tpu.exec.session import Database, Session

    table = loader.generate(200_000, 2**31 + 3503)
    s = Session(db=Database())
    s.execute(loader.DDL)
    s.load_arrow("t", table)
    ref = getattr(refs, name)
    ctx = {"tables": {"t": table}}
    for x in XS:
        sql = TRAFFIC["statements"][name]["sql"].format(x=x)
        res = s.execute(sql)
        got = ref.gaps(res.columns, res.rows, ref.answer(ctx, {"x": x}))
        assert got == {f"{name}_rel_gap": pytest.approx(0, abs=1e-13),
                       "groupby_mismatch": 0}, (x, got)


# -- the loader ---------------------------------------------------------------

class _Recording:
    def __init__(self, status_rows):
        self.seen, self.loaded, self.rows = [], [], status_rows

    def execute(self, sql):
        self.seen.append(sql)
        return NS(rows=self.rows)

    def load_arrow(self, name, table):
        self.loaded.append((name, table.num_rows))


def test_a_program_without_range_stamps_is_refused_before_any_row():
    """What the parent commit does with this cell: its SHOW STATUS has no
    ``mvcc_range_stamps``, and the run ends before a row is made."""
    s = _Recording([("mvcc.quiet_checks.value", "0"), ("Uptime", "3"),
                    ("join_cap_slots.value", "0")])
    with pytest.raises(RuntimeError, match="mvcc_range_stamps"):
        loader.load(CONFIG, 1, 0.01, s)
    assert s.seen == ["SHOW STATUS"] and s.loaded == []


def test_the_loader_states_the_layout_after_the_load():
    s = _Recording([("mvcc_range_stamps.value", "0"),
                    ("mvcc_range_stamps.per_second", "0.0")])
    out = loader.load(CONFIG, 2**31 + 5, 1e-5, s)
    assert s.seen == ["SHOW STATUS", loader.DDL,
                      "SET GLOBAL streaming_scan = 0"]
    assert s.loaded == [("t", 1000)]
    assert out["vars"] == {"table_size": 1000}
    assert out["tables"]["t"].equals(loader.generate(1000, 2**31 + 5))


# -- the reader ---------------------------------------------------------------

def _window(trace, clients=1, kind="TPU v5 lite"):
    table = loader.generate(1000, 1)        # 4,000 B a column

    def txn(name, t0, t1):
        sql = TRAFFIC["statements"][name]["sql"].format(x="0.50")
        return NS(name=name, annotation=f"client.{name}", t0=t0, t1=t1,
                  statements=[NS(name=name, sql=sql, t0=t0, t1=t1)])
    return NS(trace=trace, trace_span=(10.0, 20.0), device_kind=kind,
              tables={"t": table},
              traffic={"clients": clients,
                       "statements": TRAFFIC["statements"]},
              txns=[txn("g16", 9.0, 11.0), txn("g1000", 11.0, 15.0),
                    txn("g16", 15.0, 17.0), txn("g4000", 17.0, 25.0)])


def test_statement_roofline_reads_a_statements_own_device_time():
    trace = {"busy_s": 8.0, "window_s": 10.0,
             "idle_gaps": [["client.g16", 1.0], ["client.g1000", 0.5],
                           ["outside_client_calls", 0.5]]}
    w = _window(trace)
    peak = 819e9
    # g16: 1 s + 2 s of calls inside the span, 1 s of them idle; one and a
    # half executions' worth of 8,000 bytes
    assert readers.statement_roofline(w, "g16") == pytest.approx(
        100 * (1.5 * 8000 / peak) / 2.0)
    assert readers.statement_roofline(w, "g1000") == pytest.approx(
        100 * (8000 / peak) / 3.5)
    # g4000: 3 of its 8 s inside, no idle named for it
    assert readers.statement_roofline(w, "g4000") == pytest.approx(
        100 * (3 / 8 * 8000 / peak) / 3.0)
    for name in STATEMENTS:
        assert 0 < readers.statement_roofline(w, name) <= 100


def test_statement_roofline_reads_nothing_where_there_is_nothing():
    assert readers.statement_roofline(_window(None), "g16") is None
    trace = {"busy_s": 8.0, "window_s": 10.0, "idle_gaps": []}
    assert readers.statement_roofline(_window(trace, clients=2),
                                      "g16") is None
    all_idle = {"busy_s": 0.0, "window_s": 10.0,
                "idle_gaps": [["client.g16", 3.0]]}
    assert readers.statement_roofline(_window(all_idle), "g16") is None
    w = _window(trace)
    w.txns = [t for t in w.txns if t.name != "g1000"]
    assert readers.statement_roofline(w, "g1000") is None
    with pytest.raises(KeyError, match="peaks.json"):
        readers.statement_roofline(_window(trace, kind="TPU v9"), "g16")


# -- the whole cell -----------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace, tmp_path):
    """The whole cell at 1% (1 M rows) as the driver starts it: correct,
    one program a statement for every x, no statistic and no trace inside
    the window, one run stamped by the load, and the float32 control not
    correct on any of the three gaps."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 3501), "--seconds", "3", "--trace", str(trace),
         "--control", "1", "--rehearse-scale", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 6
    assert line["control"]["correct"] is False
    for gap in ("g16_rel_gap", "g1000_rel_gap", "g4000_rel_gap"):
        assert line["compared"][gap]["value"] <= LIMITS[gap]
        assert line["control"]["numbers"][gap] > 10 * LIMITS[gap]
    assert line["compared"]["groupby_mismatch"]["value"] == 0
    c = line["counters"]
    assert "xla_retraces" not in c and "compile_ms" not in c
    assert "plan_cache_misses" not in c and "column_stats_ms" not in c
    assert "stream_chunks" not in c             # resident, not streamed
    assert c["agg_scatter_runs"] == line["attempted"]   # the CPU's lowering
    assert "agg_select_reduce_runs" not in c and "agg_pallas_runs" not in c
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(got) == set(CELL_FILE["end_to_end"])
        return
    assert set(got) < set(CELL_FILE["per_layer"])
    assert got["tpu_northstar.retraces"] == 0
    assert got["tpu_northstar.stats_ms"] == 0
    assert got["tpu_northstar.scatter_runs"] == 1.0
    assert got["tpu_northstar.select_reduce_runs"] == 0
    assert got["tpu_northstar.pallas_runs"] == 0
    assert got["tpu_northstar.exec_ms"] > 0
    # no device-trace metric from a rehearsal
    assert not [k for k in got if "roofline" in k or "device" in k]
