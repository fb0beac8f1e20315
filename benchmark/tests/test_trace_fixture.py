"""The trace reduction on a recorded trace: 0.8 s of the sysbench cell on
a TPU v5e (PR 25's chip run, seed 204), kept gzipped beside this file."""

import gzip
import shutil
from pathlib import Path

import pytest

from benchmark import trace_reduce

FIXTURE = Path(__file__).parent / "data" / "oltp_0p8s.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(FIXTURE) as src, open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(d.parents[2])


def test_planes_hold_one_chip_its_ops_the_mark_and_whole_client_spans(
        trace_dir):
    planes = trace_reduce.read_planes(trace_reduce.find_xplane(trace_dir))
    assert list(planes["devices"]) == ["/device:TPU:0"]
    assert len(planes["devices"]["/device:TPU:0"]) == 2956
    assert len(planes["marks"]) == 1
    assert len(planes["client_spans"]) == 12
    assert {n for _, _, n in planes["client_spans"]} == {"client.txn"}
    first, last = planes["extent"]
    assert first == planes["marks"][0] and last - first \
        == pytest.approx(0.7768409)


def test_busy_union_idle_share_and_ranking(trace_dir):
    planes = trace_reduce.read_planes(trace_reduce.find_xplane(trace_dir))
    r = trace_reduce.reduce_planes(planes, 1)
    ops = planes["devices"]["/device:TPU:0"]
    # ops on one chip's "XLA Ops" line run one after another: the union is
    # their sum here, and never more
    assert r["busy_s"] == pytest.approx(0.007483552, rel=1e-9)
    assert r["busy_s"] <= sum(e - s for s, e, _ in ops) * (1 + 1e-12)
    assert r["window_s"] == pytest.approx(0.7768409)
    assert 100 * (1 - r["busy_s"] / r["window_s"]) \
        == pytest.approx(99.0367, abs=1e-3)
    assert len(r["device_ops"]) == trace_reduce.TOP
    assert r["device_ops"][0][0].startswith("%reduce-window = s32[8192,128]")
    assert r["device_ops"][0][1] == pytest.approx(0.003635808)
    assert all(len(n) <= trace_reduce.NAME_CHARS for n, _ in r["device_ops"])
    seconds = [s for _, s in r["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    # every idle second has a name, and they add up to the idle time
    assert sum(s for _, s in r["idle_gaps"]) \
        == pytest.approx(r["window_s"] - r["busy_s"])


def test_span_and_host_calls_come_onto_the_trace_clock_through_the_mark(
        trace_dir):
    planes = trace_reduce.read_planes(trace_reduce.find_xplane(trace_dir))
    mark = planes["marks"][0]
    first, last = planes["extent"]
    # the harness's record: the same calls on a host clock 1,000 s ahead,
    # with the two calls that straddle the trace's edges, which the trace
    # itself cannot hold; the harness asked for the stop 0.9 s after the
    # mark, later than the last event the trace kept
    host = [(s + 1000, e + 1000, n) for s, e, n in planes["client_spans"]]
    host += [(first + 999.5, first + 1000.05, "client.txn"),
             (last + 999.9, last + 1001.0, "client.txn")]
    span = (mark + 1000, mark + 1000.9)
    with_host = trace_reduce.reduce_trace(trace_dir, 1, span, host)
    alone = trace_reduce.reduce_planes(planes, 1)
    # the window is the harness's span, not the extent of the events: the
    # idle edge counts as idle
    assert with_host["window_s"] == pytest.approx(0.9)
    assert alone["window_s"] == pytest.approx(last - first) and last - first < 0.9
    assert with_host["busy_s"] == alone["busy_s"]
    named = dict(with_host["idle_gaps"])
    assert named["client.txn"] > dict(alone["idle_gaps"])["client.txn"]
    assert sum(named.values()) \
        == pytest.approx(with_host["window_s"] - with_host["busy_s"])
    # a span shorter than the trace cuts the device's operations to it
    short = trace_reduce.reduce_trace(trace_dir, 1,
                                      (mark + 1000, mark + 1000.4), host)
    assert short["window_s"] == pytest.approx(0.4)
    assert 0 < short["busy_s"] < alone["busy_s"]
    assert sum(s for _, s in short["idle_gaps"]) \
        == pytest.approx(0.4 - short["busy_s"])
