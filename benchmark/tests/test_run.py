"""A whole run at a rehearsal's size: the last line parses and says
``correct``; a corrupted reference, an answer altered where the server
writes it, and the lower-precision control each come out as not correct."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run
from benchmark.refs import Ref

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["tpch_sf1.q1q6", "sysbench_1m.read_only"]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(capsys, cell, trace=0, seconds=2.0, seed=2**31 + 7,
             scale=0.01, control=0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--control",
                   str(control), "--rehearse-scale", str(scale)])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell", CELLS)
def test_the_command_ends_in_a_parseable_correct_line(cell):
    """As the driver starts it: a process of its own, from the root."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2**31 + 99), "--seconds", "2", "--trace", "0",
         "--rehearse-scale", "0.01"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert KEYS <= set(line) and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    cellfile = json.loads(
        (ROOT / "benchmark/workloads" / f"{cell}.json").read_text())
    assert set(line["metrics"]) == set(cellfile["end_to_end"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "compared"
    # each number compared stands beside its limit, last on stderr too
    tail = p.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_no_device_trace_metric(capsys, cell):
    rc, line, _ = rehearse(capsys, cell, trace=1)
    assert rc == 0 and line["correct"] is True
    cellfile = json.loads(
        (ROOT / "benchmark/workloads" / f"{cell}.json").read_text())
    assert set(line["metrics"]) < set(cellfile["per_layer"])
    for name in line["metrics"]:
        m = json.loads((ROOT / "benchmark/metrics" / f"{name}.json")
                       .read_text())
        assert m["source"] != "device_trace"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["metrics"][[n for n in line["metrics"]
                            if n.startswith("retraces.")][0]]["value"] == 0


def test_without_a_chip_nothing_runs_and_nothing_prints(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc == run.EXIT_NO_DEVICE
    assert capsys.readouterr().out == ""


def scaled(ref: Ref, factor: float) -> Ref:
    def answer(ctx, params, lower=False):
        cols, rows = ref.answer(ctx, params, lower)
        return cols, [tuple(v * factor if isinstance(v, float) else v
                            for v in r) for r in rows]
    return Ref(answer, ref.gaps)


def test_a_corrupted_reference_is_not_correct(capsys, monkeypatch):
    from benchmark.refs import sbtest as sref
    from benchmark.refs import tpch as tref

    monkeypatch.setattr(tref, "q6", scaled(tref.q6, 1 + 1e-7))
    rc, line, _ = rehearse(capsys, CELLS[0])
    assert rc == 0 and line["correct"] is False and line["failed"] > 0
    assert line["compared"]["q6_rel_gap"]["value"] > 5e-8
    assert line["compared"]["q1_rel_gap"]["value"] < 1e-9

    plain = sref.sum_range

    def off_by_one(ctx, params, lower=False):
        cols, rows = plain.answer(ctx, params, lower)
        return cols, [(rows[0][0] + 1,)]
    monkeypatch.setattr(sref, "sum_range", Ref(off_by_one, plain.gaps))
    rc, line, _ = rehearse(capsys, CELLS[1])
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["sum_mismatch"]["value"] == 1
    assert line["compared"]["point_mismatch"]["value"] == 0


@pytest.mark.parametrize("cell,number", [(CELLS[0], "q1_rel_gap"),
                                         (CELLS[1], "range_mismatch"),
                                         (CELLS[1], "order_mismatch")])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, cell, number):
    """The timed path broken underneath: the server writes a float 1e-7
    off, or a string with its last digit changed."""
    from baikaldb_tpu.server import mysql_server

    plain = mysql_server._text_value

    def altered(v):
        if isinstance(v, float):
            return plain(v * (1 + 1e-7))
        if isinstance(v, str) and len(v) > 100:
            return plain(v[:-1] + ("0" if v[-1] != "0" else "1"))
        return plain(v)
    monkeypatch.setattr(mysql_server, "_text_value", altered)
    rc, line, _ = rehearse(capsys, cell)
    assert rc == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    c = line["compared"][number]
    assert c["value"] > c["limit"]


# the sysbench control is a float32 SUM(k): it is exact while the sums stay
# under 2**24, so it is read at the cell's own 1M rows (a minute on the CPU)
@pytest.mark.parametrize("cell,scale,number", [
    (CELLS[0], 0.01, "q1_rel_gap"), (CELLS[0], 0.01, "q6_rel_gap"),
    (CELLS[1], 1.0, "sum_mismatch")])
def test_the_lower_precision_control_comes_out_not_correct(
        capsys, cell, scale, number):
    """``--control 1`` puts the reference, computed in the precision below
    the configuration's, in the program's place, and holds it to the cell's
    limits by the expression that decides ``correct``."""
    rc, line, err = rehearse(capsys, cell, scale=scale, control=1)
    assert rc == 0 and line["correct"] is True
    control = line["control"]
    assert control["correct"] is False
    limit = line["compared"][number]["limit"]
    assert control["numbers"][number] > 3 * limit
    assert line["compared"][number]["value"] <= limit
    assert "correct=False" in err
    assert list(line)[-1] == "compared"


def test_a_run_without_the_control_says_nothing_of_it(capsys):
    rc, line, _ = rehearse(capsys, CELLS[0])
    assert rc == 0 and "control" not in line
    # what a window that reads far off is looked up by, in every run
    assert line["longest_quiet_s"] > 0 and line["window_s"] >= 2.0
    assert line["counters"]["queries_total"] == line["attempted"]
    slow = line["slowest_logged"]
    assert slow["sql"].startswith("SELECT") and slow["ms"] > 0
    assert {"parse", "plan", "exec", "egress"} <= set(slow["phases_ms"])
