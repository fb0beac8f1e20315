"""tools/span_profile.py: the per-layer metrics that the program's spans and
counters feed, read by the benchmark's own readers over a rehearsed window
of each cell, and the reduction that gives a thread's time to its innermost
span.  (The device's idle seconds by program span need a chip.)"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

OVERLAY = ROOT / "tools" / "span_metrics"
CELLS = json.loads((OVERLAY / "cells.json").read_text())


def test_innermost_span_owns_the_time():
    from tools.span_profile import innermost

    segs = innermost([(0, 10, "a"), (1, 3, "b"), (2, 2.5, "c"), (5, 6, "d"),
                      (12, 13, "e")])
    assert segs == [(0, 1, "a"), (1, 2, "b"), (2, 2.5, "c"), (2.5, 3, "b"),
                    (3, 5, "a"), (5, 6, "d"), (6, 10, "a"), (12, 13, "e")]
    # every moment of a span has one owner: the parts add up to the roots
    assert sum(e - s for s, e, _ in segs) == 10 + 1


def test_idle_and_busy_seconds_go_to_the_spans_open_meanwhile():
    """``reduce_spans`` on a made-up trace (the real one needs a chip): one
    chip busy for [1, 2] and [4, 4.5] of a 5 s span; the statement thread
    and the stager thread each have program spans open."""
    from types import SimpleNamespace as NS

    from benchmark import trace_reduce
    from tools.span_profile import NO_SPAN, reduce_spans

    def line(name, *events):
        return NS(name=name, events=[
            NS(name=n, start_ns=int(s * 1e9), duration_ns=int((e - s) * 1e9))
            for s, e, n in events])

    dev = trace_reduce.DEVICE_PREFIX + "0"
    raw = [
        NS(name=trace_reduce.HOST_PLANE, lines=[
            line("statement", (0.0, 3.0, "db.exec.batches"),
                 (0.5, 1.5, "db.mvcc.visibility"),
                 (0.0, 5.0, "db.client.query"),       # the client's own
                 (4.0, 5.0, "db.egress.count"), (0.0, 5.0, "client.q1")),
            line("stager", (2.0, 3.0, "db.stream.stage.decode"))]),
        NS(name=dev, lines=[
            line("XLA Modules", (1.0, 2.0, "jit_run_local(7)"),
                 (4.0, 4.5, "jit_cumsum(9)"))])]
    planes = {"devices": {dev: [(1.0, 2.0, "fusion.1"),
                                (4.0, 4.5, "fusion.2")]},
              "client_spans": [(0.0, 5.0, "client.q1")], "marks": [],
              "extent": (0.0, 5.0)}
    got = reduce_spans(planes, raw, 1, (0.0, 5.0))
    assert got["window_s"] == 5.0
    idle, busy = dict(got["idle_s"]), dict(got["busy_s"])
    # idle: [0,1] [2,4] [4.5,5].  [2,3] is shared by the two threads
    assert idle == pytest.approx({
        "db.exec.batches": 0.5 + 0.5, "db.mvcc.visibility": 0.5,
        "db.stream.stage.decode": 0.5, NO_SPAN: 1.0, "db.egress.count": 0.5})
    assert busy == pytest.approx({
        "db.mvcc.visibility": 0.5, "db.exec.batches": 0.5,
        "db.egress.count": 0.5})
    assert sum(idle.values()) + sum(busy.values()) == pytest.approx(5.0)
    assert got["modules"] == [["jit_run_local", 1.0, 1.0],
                              ["jit_cumsum", 1.0, 0.5]]
    assert got["op_classes"]["chips"][dev]["other"] == pytest.approx(1.5)
    assert got["op_classes"]["skew"] == pytest.approx(1.0)


def test_device_seconds_by_op_class_and_skew_between_chips():
    """Two chips of a made-up mesh trace: collectives by the head of their
    HLO name (async pairs under their collective), the rest as ``other``,
    cut to the span; skew = busiest chip / mean."""
    from benchmark import trace_reduce
    from tools.span_profile import op_class, op_class_seconds

    assert [op_class(n) for n in (
        "%all-to-all.3 = f32[4,8]{1,0} all-to-all(...)", "all-reduce-start.1",
        "all-gather-done.2", "collective-permute.7", "%fusion.12",
        "copy-done")] == ["all-to-all", "all-reduce", "all-gather",
                          "collective-permute", "other", "other"]
    d0, d1 = (trace_reduce.DEVICE_PREFIX + c for c in "01")
    got = op_class_seconds({
        d0: [(0.0, 1.0, "%fusion.1"), (1.0, 1.5, "%all-to-all.2"),
             (1.5, 1.75, "all-reduce.3"), (9.0, 11.0, "%fusion.9")],
        d1: [(0.0, 0.5, "%fusion.1"), (0.5, 1.0, "%all-to-all.2"),
             (0.75, 1.0, "all-gather-start.4")]}, 0.0, 10.0, 2)
    assert got["chips"][d0] == pytest.approx({
        "all-to-all": 0.5, "all-reduce": 0.25, "all-gather": 0.0,
        "collective-permute": 0.0, "other": 2.0, "busy_s": 2.75})
    assert got["chips"][d1] == pytest.approx({
        "all-to-all": 0.5, "all-reduce": 0.0, "all-gather": 0.25,
        "collective-permute": 0.0, "other": 0.5, "busy_s": 1.0})
    assert got["skew"] == pytest.approx(2.75 / ((2.75 + 1.0) / 2))


def test_metric_files_fit_the_benchmarks_schema():
    """What a ``benchmark`` PR moves to ``benchmark/metrics/`` as it is."""
    from benchmark import run

    ends = {p.stem for p in (ROOT / "benchmark/metrics").glob("*.json")}
    named = [n for names in CELLS.values() for n in names]
    assert sorted(named) == sorted(
        p.stem for p in (OVERLAY / "metrics").glob("*.json"))
    assert not set(named) & ends
    for cell, names in CELLS.items():
        assert (ROOT / "benchmark/workloads" / f"{cell}.json").exists()
        for name in names:
            m = json.loads((OVERLAY / "metrics" / f"{name}.json").read_text())
            assert m["name"] == name and m["kind"] == "per_layer"
            assert m["source"] in ("program_span", "program_counter")
            assert m["moves"] in ends and m["better"] == "lower"
            assert callable(run.resolve(m["reader"]))


# the waits a span keeps only while a trace tree is live (--tracing 1), and
# the stager's, which at 1% has nothing to read: no table passes
# streaming_min_rows, so no stager runs
UNDER_A_TREE = {"wait.point_lookup_ms.oltp", "wait.wire_encode_ms.oltp",
                "wait.statement_ms.oltp"}
NO_STAGER = {"wait.stage_ms.analytic"}


@pytest.mark.parametrize("cell,tracing",
                         [(c, 0) for c in sorted(CELLS)]
                         + [("sysbench_1m.read_only", 1)])
def test_rehearsal_prints_every_span_metric(cell, tracing, tmp_path):
    p = subprocess.run(
        [sys.executable, "tools/span_profile.py", "--workload", cell,
         "--seed", str(2**31 + 5), "--seconds", "2", "--trace", "1",
         "--rehearse-scale", "0.01", "--tracing", str(tracing),
         "--out", str(tmp_path / "out")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        # a compile cache of its own: tests/test_bring_up.py watches the
        # checkout's .jax_cache for writes while this may be running
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})
    assert p.returncode == 0, p.stderr[-2000:]
    line, spans = map(json.loads, p.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    silent = NO_STAGER | (set() if tracing else UNDER_A_TREE)
    assert set(CELLS[cell]) - silent <= set(got), set(CELLS[cell]) - set(got)
    assert not silent & set(got)
    # the cell's own per-layer metrics still stand beside them
    assert any("retraces" in n and got[n]["value"] == 0 for n in got)
    for n in got:
        if n.startswith("untraced_ms."):
            assert got[n]["value"] >= 0
    assert spans == {"by_program_span": None, "tracing": tracing}  # no chip
    assert len(list((tmp_path / "out").glob("*.jsonl"))) == 1
    if cell.startswith("tpu_northstar"):
        # on the CPU every dense aggregate is the scatter: rows once, and
        # v holds no NULL, so one pass a statement where five were
        assert got["count_passes.northstar"]["value"] == 1.0
        assert line["counters"]["agg_count_passes"] == line["attempted"]
        # and no Pallas kernel, so no dense MIN / MAX pass
        assert got["minmax_passes.northstar"]["value"] == 0.0
        assert "agg_minmax_passes" not in line["counters"]
    if cell.startswith("sysbench"):
        assert line["counters"]["txn_commits"] == line["attempted"]
        assert got["point_lookup_ms.oltp"]["value"] > 0
        assert got["wire_encode_ms.oltp"]["value"] > 0
    if cell.startswith("sysbench") and tracing:
        # a wait is part of its span's wall time
        for part, whole in (("wait.point_lookup_ms.oltp",
                             "point_lookup_ms.oltp"),
                            ("wait.wire_encode_ms.oltp",
                             "wire_encode_ms.oltp")):
            assert 0 < got[part]["value"] <= got[whole]["value"]
        assert got["wait.statement_ms.oltp"]["value"] >= 0
    if cell.startswith("sysbench") or cell == "tpch_sf1.q1q6":
        # every thread of the process shares the one interpreter lock, and
        # numpy, Arrow and XLA work off it: more than nothing, and not more
        # cores than the host has
        cores = [v["value"] for n, v in got.items()
                 if n.startswith("work.host_cores.")]
        assert len(cores) == 1 and 0 < cores[0] <= os.cpu_count()
