"""Rate and percentile arithmetic, and the interval arithmetic of the trace
reduction, on made-up windows."""

import pytest

from benchmark import trace_reduce
from benchmark.readers import rates
from benchmark.run import (StatementRecord, TxnRecord, Window,
                           longest_quiet_s, within)


def window(txns, seconds):
    return Window(setup_s=1.0, t_open=100.0, t_close=100.0 + seconds,
                  txns=txns, counters={}, query_log=[], tables={},
                  traffic={}, device_kind="cpu")


def txn(t0, t1, error="", wrong=False, statements=()):
    return TxnRecord(0, "txn", "client.txn", t0, t1, error, wrong, list(statements))


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert rates.percentile(values, 95) == 95
    assert rates.percentile(values, 100) == 100
    assert rates.percentile([7.0], 95) == 7.0
    # 20 samples: the 95th percentile is the 19th, one sample lies beyond
    assert rates.percentile(list(range(20)), 95) == 18


def test_rate_counts_all_work_over_all_time_through_a_stall():
    # nine quick transactions, then one that stalls for five seconds: the
    # rate is over the whole window, the stall included
    txns = [txn(100 + i * 0.5, 100.5 + i * 0.5) for i in range(9)]
    txns.append(txn(104.5, 109.5))
    w = window(txns, 9.5)
    assert rates.completed_per_s(w) == pytest.approx(10 / 9.5)
    # the tail is the tail of all transactions: the stalled one is in it
    assert rates.txn_percentile_ms(w, 95) == pytest.approx(5000.0)
    assert rates.txn_percentile_ms(w, 50) == pytest.approx(500.0)


def test_a_stall_of_every_client_shows_as_the_longest_quiet_stretch():
    txns = [txn(100 + i * 0.5, 100.5 + i * 0.5) for i in range(9)]
    assert longest_quiet_s(txns, 100.0, 104.5) == pytest.approx(0.5)
    txns.append(txn(104.5, 109.5))
    assert longest_quiet_s(txns, 100.0, 109.5) == pytest.approx(5.0)
    assert longest_quiet_s([], 100.0, 103.0) == pytest.approx(3.0)


def test_one_expression_decides_correct_and_the_controls_verdict():
    limits = {"errors": 0, "gap": 1e-10, "mismatch": 0}
    assert within({"errors": 0, "gap": 3e-14, "mismatch": 0}, limits)
    assert not within({"errors": 0, "gap": 4e-7, "mismatch": 0}, limits)
    assert not within({"errors": 0, "gap": 0.0, "mismatch": 1}, limits)
    assert not within({"errors": 0, "gap": float("nan"), "mismatch": 0},
                      limits)


def test_failed_and_wrong_transactions_do_not_count_as_completed():
    txns = [txn(100, 101), txn(101, 102, error="MySQLError: boom"),
            txn(102, 103, wrong=True), txn(103, 104)]
    assert rates.completed_per_s(window(txns, 4.0)) == pytest.approx(0.5)


def test_statement_median_takes_the_named_statements():
    def stmt(name, ms):
        return StatementRecord(name, {}, "", 0.0, ms / 1e3)
    t = txn(100, 101, statements=[stmt("point", 2), stmt("point", 4),
                                  stmt("point", 9), stmt("range", 50)])
    w = window([t], 1.0)
    assert rates.statement_median_ms(w, ["point"]) == pytest.approx(4.0)
    assert rates.statement_median_ms(w, ["range", "sum"]) \
        == pytest.approx(50.0)
    assert rates.statement_median_ms(w, ["absent"]) is None


def test_union_merges_overlaps_and_gaps_are_what_is_left():
    busy = trace_reduce.union([(5, 6), (1, 2), (1.5, 3), (2.5, 2.8)])
    assert busy == [(1, 3), (5, 6)]
    assert trace_reduce.covered(busy) == pytest.approx(3.0)
    assert trace_reduce.gaps(busy, 0, 10) == [(0, 1), (3, 5), (6, 10)]
    assert trace_reduce.gaps([], 0, 2) == [(0, 2)]


def test_gaps_are_named_by_the_open_client_span():
    spans = [(0.0, 4.0, "client.q1"), (4.0, 6.0, "client.q6"),
             (5.0, 6.0, "client.txn")]
    named = trace_reduce.name_gaps([(1.0, 3.0), (3.5, 5.5), (7.0, 8.0)],
                                   spans)
    # (3.5, 5.5): 0.5 under q1, 1.0 under q6 alone, 0.5 shared by two
    assert named["client.q1"] == pytest.approx(2.5)
    assert named["client.q6"] == pytest.approx(1.25)
    assert named["client.txn"] == pytest.approx(0.25)
    assert named["outside_client_calls"] == pytest.approx(1.0)


def test_reduce_planes_averages_over_chips_and_ranks():
    planes = {"devices": {
        "/device:TPU:0": [(1.0, 2.0, "fusion.1"), (1.5, 3.0, "copy.2")],
        "/device:TPU:1": [(1.0, 2.0, "fusion.1")]},
        "client_spans": [(0.0, 4.0, "client.q1")], "marks": [],
        "extent": (0.0, 4.0)}
    one = trace_reduce.reduce_planes(planes, 1)
    assert one["busy_s"] == pytest.approx(2.0)
    assert one["window_s"] == pytest.approx(4.0)
    assert one["device_ops"][0] == ["copy.2", pytest.approx(1.5)]
    assert one["idle_gaps"] == [["client.q1", pytest.approx(2.0)]]
    two = trace_reduce.reduce_planes(planes, 2)
    assert two["busy_s"] == pytest.approx(1.5)
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes({**planes, "devices": {}}, 1)


def test_events_are_cut_to_the_traced_span():
    events = [(0.0, 1.0, "a"), (0.5, 2.5, "b"), (3.0, 9.0, "c"),
              (9.0, 9.5, "d")]
    assert trace_reduce.cut(events, 1.0, 4.0) \
        == [(1.0, 2.5, "b"), (3.0, 4.0, "c")]
