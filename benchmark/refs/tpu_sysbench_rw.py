"""sysbench's read-write transaction over the generated ``sbtest1``: a plain
numpy model of the table that applies every write in order and answers
every read from itself.

``check_answers`` hands the window's statements over in the order the one
client sent them, with one ``ctx``; the model is built on the first call
from the loaded table and the warm-up's writes, which the harness does not
compare but the program applied: they are drawn again from the seed the
loader left in ``ctx["tables"]["__replay__"]`` (``trafficgen.Client`` on the
warm-up stream, ``warmup_rounds`` transactions a kind).  The model keeps
which ids were written, and how many rows the reads returned of them
(``read_back``).

``lower`` (the control) answers every read from the table as loaded, the
writes ignored: stale reads, which the limits have to fail.  A write
answers no rows either way; ``write_mismatch`` is 1 where one did.
"""

import numpy as np

from benchmark import trafficgen
from benchmark.refs import Ref, sbtest

TRAFFIC = "read_write"


class Model:
    """``sbtest1`` as arrays indexed by ``id - 1``."""

    def __init__(self, table, size: int):
        self.k = table.column("k").to_numpy().copy()
        self.c = table.column("c").to_numpy(zero_copy_only=False).copy()
        self.pad = table.column("pad").to_numpy(
            zero_copy_only=False).copy()
        self.live = np.ones(size, bool)
        self.written = np.zeros(size, bool)
        self.rows_read = 0
        self.rows_read_back = 0

    def ids(self, lo: int, hi: int) -> np.ndarray:
        ids = np.arange(max(lo, 1), min(hi, len(self.live)) + 1)
        return ids[self.live[ids - 1]]

    def saw(self, ids) -> None:
        self.rows_read += len(ids)
        self.rows_read_back += int(self.written[np.asarray(ids, int) - 1]
                                   .sum()) if len(ids) else 0

    def apply(self, name: str, p: dict) -> None:
        i = p["id"] - 1
        if name == "update_k":
            if self.live[i]:
                self.k[i] += 1
        elif name == "update_c":
            if self.live[i]:
                self.c[i] = _groups(p, "c", 10)
        else:                               # delete_insert
            self.live[i] = True
            self.k[i] = p["k"]
            self.c[i] = _groups(p, "c", 10)
            self.pad[i] = _groups(p, "p", 5)
        self.written[i] = True


def _groups(p: dict, prefix: str, n: int) -> str:
    return "-".join(f"{p[f'{prefix}{i}']:011d}" for i in range(n))


def _model(ctx: dict) -> Model:
    if "rw" not in ctx:
        replay = ctx["tables"]["__replay__"]
        seed = int(replay.column("seed")[0].as_py())
        size = int(replay.column("table_size")[0].as_py())
        m = Model(ctx["tables"]["sbtest1"], size)
        traffic = trafficgen.load_traffic(TRAFFIC)
        warm = trafficgen.Client(traffic, {"table_size": size}, seed, 0,
                                 trafficgen.WARMUP_STREAM)
        for _ in range(traffic["warmup_rounds"]
                       * len(traffic["transactions"])):
            for s in warm.next().statements:
                if s.name in WRITES:
                    m.apply(s.name, s.params)
        m.rows_read = m.rows_read_back = 0
        ctx["rw"] = m
    return ctx["rw"]


def read_back(ctx: dict) -> tuple:
    """(rows the window's reads returned, of them rows of ids written
    earlier in the run, warm-up included)."""
    m = _model(ctx)
    return m.rows_read, m.rows_read_back


def _write(name: str):
    def answer(ctx, params, lower=False):
        if not lower:
            _model(ctx).apply(name, params)
        return (), []
    return answer


def _point(ctx, params, lower=False):
    if lower:
        return sbtest.point.answer(ctx, params)
    m = _model(ctx)
    ids = m.ids(params["id"], params["id"])
    m.saw(ids)
    return ("c",), [(m.c[i - 1],) for i in ids]


def _range(ctx, params, lower=False):
    if lower:
        return sbtest.simple_range.answer(ctx, params)
    m = _model(ctx)
    ids = m.ids(params["lo"], params["hi"])
    m.saw(ids)
    return ("c",), [(m.c[i - 1],) for i in ids]


def _sorted(distinct: bool):
    stale = sbtest.distinct_range if distinct else sbtest.order_range

    def answer(ctx, params, lower=False):
        if lower:
            return stale.answer(ctx, params)
        m = _model(ctx)
        ids = m.ids(params["lo"], params["hi"])
        m.saw(ids)
        c = [m.c[i - 1] for i in ids]
        return ("c",), [(v,) for v in sorted(set(c) if distinct else c)]
    return answer


def _sum(ctx, params, lower=False):
    if lower:
        return sbtest.sum_range.answer(ctx, params)
    m = _model(ctx)
    ids = m.ids(params["lo"], params["hi"])
    m.saw(ids)
    return ("SUM(k)",), [(int(m.k[ids - 1].sum()) if len(ids) else None,)]


def _no_rows(columns, rows, want) -> dict:
    return {"write_mismatch": int(bool(columns) or bool(rows))}


def _rows_differ(name: str, ordered: bool):
    return sbtest._rows_differ(name, ordered)


WRITES = ("update_k", "update_c", "delete_insert")

point = Ref(_point, _rows_differ("point_mismatch", True))
simple_range = Ref(_range, _rows_differ("range_mismatch", False))
sum_range = Ref(_sum, _rows_differ("sum_mismatch", True))
order_range = Ref(_sorted(False), _rows_differ("order_mismatch", True))
distinct_range = Ref(_sorted(True), _rows_differ("distinct_mismatch", True))
update_k = Ref(_write("update_k"), _no_rows)
update_c = Ref(_write("update_c"), _no_rows)
delete_insert = Ref(_write("delete_insert"), _no_rows)
