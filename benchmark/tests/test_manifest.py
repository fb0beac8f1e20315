"""BENCHMARK.json and the files under benchmark/ say the same thing, and
every name resolves."""

import json
from pathlib import Path

import pytest

from benchmark import manifest, run, trafficgen

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_is_what_the_files_build():
    """One place holds each list: a cell's file names its metrics, and
    BENCHMARK.json is generated (``python3 benchmark/manifest.py``)."""
    assert manifest.build() == MANIFEST
    assert manifest.main(["--check"]) == 0


def test_every_cell_and_config_has_its_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for cell in MANIFEST["workloads"]:
        w = run.load_json("workloads", cell["name"])
        assert {k: w[k] for k in ("name", "config", "traffic", "chips",
                                  "why")} == cell
        c = run.load_json("configs", w["config"])
        m = configs[w["config"]]
        assert m["file"] == f"benchmark/configs/{w['config']}.json"
        assert m["source"] == c["source"]
        assert m["reduced"] == list(c["reduced"]) and m["why"] == c["why"]
        assert callable(run.resolve(c["loader"]))
        traffic = trafficgen.load_traffic(w["traffic"])
        for s in traffic["statements"].values():
            ref = run.resolve(s["ref"])
            assert callable(ref.answer) and callable(ref.gaps)
        assert w["limits"] and "errors" not in w["limits"]


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics_match_their_files_and_cells(kind):
    cells = {c["name"]: run.load_json("workloads", c["name"])
             for c in MANIFEST["workloads"]}
    for m in MANIFEST[kind]:
        f = run.load_json("metrics", m["name"])
        assert f["kind"] == kind
        for k in ("unit", "better", "source", "layer", "moves", "bound"):
            assert m.get(k) == f.get(k), (m["name"], k)
        assert callable(run.resolve(f["reader"]))
        where = m.get("workloads", list(cells))
        assert where == [n for n, w in cells.items() if m["name"] in w[kind]]
        if kind == "per_layer":
            assert all(m["moves"] in cells[n]["end_to_end"] for n in where)
    listed = {n for w in cells.values() for n in w[kind]}
    assert listed == {m["name"] for m in MANIFEST[kind]}


def test_run_py_names_no_cell_config_or_metric():
    text = (ROOT / "benchmark/run.py").read_text()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in MANIFEST[k]]
    names += [w["traffic"] for w in MANIFEST["workloads"]]
    assert [n for n in names if n != "setup_s" and n in text] == []
