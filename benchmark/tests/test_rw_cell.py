"""The cell ``tpu_sysbench_1m_rw.read_write``: its files against
``BENCHMARK.json``, the reference's replay of the warm-up's writes, its
stale-read control failing the limits, the loader's refusal of a program
without the patch counters, and a rehearsal of the whole cell on the CPU."""

import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pyarrow as pa
import pytest

from benchmark import trafficgen
from benchmark.loaders import sbtest
from benchmark.loaders import tpu_sysbench_rw as loader
from benchmark.refs import tpu_sysbench_rw as ref

ROOT = Path(__file__).resolve().parents[2]
CELL = "tpu_sysbench_1m_rw.read_write"
CELL_FILE = json.loads((ROOT / "benchmark/workloads" / f"{CELL}.json")
                       .read_text())
CONFIG = json.loads((ROOT / "benchmark/configs"
                     / f"{CELL_FILE['config']}.json").read_text())
TRAFFIC = trafficgen.load_traffic(CELL_FILE["traffic"])
LIMITS = CELL_FILE["limits"]
N = 2000
SEED = 2**31 + 41
# what BENCHMARK.json and benchmark/ held before this cell: every new name
# has to sort after every one of these, whatever later cells add
ACCEPTED = {
    "configs": ["sysbench_1m", "tpch_sf1", "tpch_sf1_mesh4",
                "tpch_sf1_onechip_joins", "tpu_northstar_100m"],
    "workloads": ["sysbench_1m.read_only", "tpch_sf1.q1q6",
                  "tpch_sf1_mesh4.q1q3", "tpch_sf1_onechip_joins.q3q18",
                  "tpu_northstar_100m.groupby"],
    "per_layer_last": "tpu_northstar.stats_ms",
    "traffic": ["groupby", "q1q3", "q1q6", "q3q18", "read_only"],
    "loaders": ["northstar", "sbtest", "tpch", "tpch_joins", "tpch_mesh"],
    "refs": ["northstar", "sbtest", "tpch", "tpch_joins", "tpch_q18"],
}


def test_benchmark_json_holds_the_cell_after_every_accepted_entry():
    from benchmark import manifest

    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert manifest.build() == m
    n = len(ACCEPTED["configs"])
    assert [c["name"] for c in m["configs"]][:n + 1] == \
        ACCEPTED["configs"] + [CELL_FILE["config"]]
    n = len(ACCEPTED["workloads"])
    assert [w["name"] for w in m["workloads"]][:n + 1] == \
        ACCEPTED["workloads"] + [CELL]
    assert m["workloads"][n]["chips"] == 1
    per_layer = [p["name"] for p in m["per_layer"]]
    mine = [p for p in per_layer if p.startswith("tpu_sysbench_rw.")]
    assert sorted(mine) == sorted(p for p in CELL_FILE["per_layer"]
                                  if p.startswith("tpu_sysbench_rw."))
    assert per_layer.index(ACCEPTED["per_layer_last"]) \
        < min(per_layer.index(p) for p in mine)
    # the accepted sysbench metrics this cell reports too: the cell is
    # appended to their lists, and nothing else of them changes
    for p in m["per_layer"]:
        if p["name"] in CELL_FILE["per_layer"] and p["name"] not in mine:
            assert p["name"].endswith(".oltp")
            assert p["workloads"][:2] == ["sysbench_1m.read_only", CELL]
    for e in m["end_to_end"]:
        if e["name"] in ("oltp_tps", "oltp_p95_ms"):
            assert e["workloads"][:2] == ["sysbench_1m.read_only", CELL]
    assert len(m["workloads"][n]["why"]) <= 200


def test_new_file_names_sort_after_the_accepted_ones():
    for kind, mine in (("configs", CELL_FILE["config"]),
                       ("workloads", CELL),
                       ("traffic", CELL_FILE["traffic"]),
                       ("loaders", "tpu_sysbench_rw"),
                       ("refs", "tpu_sysbench_rw")):
        stems = {p.stem for p in (ROOT / "benchmark" / kind).iterdir()
                 if p.suffix in (".json", ".py") and p.name != "__init__.py"}
        assert mine in stems, (kind, mine)
        assert all(a < mine for a in ACCEPTED[kind]), (kind, mine)


def test_manifest_check_passes():
    p = subprocess.run([sys.executable, "benchmark/manifest.py", "--check"],
                       cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stdout + p.stderr


def _tables() -> dict:
    tables = sbtest.generate(N, 1, SEED, {"type": "special", "iter": 12,
                                          "pct": 1, "res": 75})
    tables["__replay__"] = pa.table({"seed": [SEED], "table_size": [N]})
    return tables


def test_the_reference_replays_the_warm_up_writes():
    """The model the reference starts the window from is the loaded table
    after the warm-up's transactions, drawn again from the seed."""
    tables = _tables()
    ctx = {"tables": tables}
    m = ref._model(ctx)
    warm = trafficgen.Client(TRAFFIC, {"table_size": N}, SEED, 0,
                             trafficgen.WARMUP_STREAM)
    k = tables["sbtest1"].column("k").to_numpy().copy()
    c = tables["sbtest1"].column("c").to_pylist()
    writes = 0
    for _ in range(TRAFFIC["warmup_rounds"]):
        for s in warm.next().statements:
            i = s.params.get("id", 0) - 1
            if s.name == "update_k":
                k[i] += 1
            elif s.name == "update_c":
                c[i] = ref._groups(s.params, "c", 10)
            elif s.name == "delete_insert":
                k[i] = s.params["k"]
                c[i] = ref._groups(s.params, "c", 10)
            else:
                continue
            writes += 1
    assert writes == 3 * TRAFFIC["warmup_rounds"]
    assert (m.k == k).all() and list(m.c) == c
    assert m.written.sum() > 0 and m.live.all()


def test_the_stale_read_control_fails_the_limits():
    """Answers of the model against the control's over a stretch of
    window transactions: the model holds every limit against itself, the
    control (reads from the table as loaded) fails them."""
    tables = _tables()
    ctx = {"tables": tables}
    client = trafficgen.Client(TRAFFIC, {"table_size": N}, SEED, 0)
    refs = {n: getattr(ref, s["ref"].split(":")[1])
            for n, s in TRAFFIC["statements"].items()}
    numbers = {k: 0 for k in LIMITS}
    lowered = {k: 0 for k in LIMITS}
    for _ in range(60):
        for s in client.next().statements:
            want = refs[s.name].answer(ctx, s.params)
            low = refs[s.name].answer(ctx, s.params, lower=True)
            for k, v in refs[s.name].gaps(want[0], want[1], want).items():
                numbers[k] = max(numbers[k], v)
            for k, v in refs[s.name].gaps(low[0], low[1], want).items():
                lowered[k] = max(lowered[k], v)
    assert all(numbers[k] <= LIMITS[k] for k in LIMITS)
    assert not all(lowered[k] <= LIMITS[k] for k in LIMITS)
    rows, back = ref.read_back(ctx)
    assert back > 0 and rows > back


def test_a_write_that_answers_rows_is_a_mismatch():
    assert ref.update_k.gaps((), [], ((), [])) == {"write_mismatch": 0}
    assert ref.update_k.gaps(("x",), [(1,)], ((), [])) == \
        {"write_mismatch": 1}


def test_the_loader_refuses_a_program_without_the_patch_counters():
    class Old:
        def execute(self, sql):
            assert sql == "SHOW STATUS", sql
            return NS(rows=[("queries_total", 1), ("mvcc.quiet_checks", 0)])
    with pytest.raises(RuntimeError, match="image_patches"):
        loader.load(CONFIG, SEED, 0.002, Old())


def test_rehearsal_of_the_cell_ends_correct():
    """``benchmark/run.py`` at a hundredth of the rows on the CPU, with the
    control: correct, nothing failed, nothing rebuilt or retraced in the
    window, ~3 dictionary appends and one image patch a transaction, and
    the stale-read control refused by the limits."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(SEED), "--seconds", "3", "--trace", "0",
         "--rehearse-scale", "0.01", "--control", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["control"]["correct"] is False
    c = line["counters"]
    txns = c["txn_commits"]
    assert txns > 0 and c["image_patches"] == txns
    assert abs(c["dict_appends"] - 3 * txns) <= 3
    for name in ("image_rebuilds", "pk_order_rebuilds", "xla_retraces",
                 "overlay_folds"):
        assert name not in c, name
