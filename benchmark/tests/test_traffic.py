"""The statements of a run are a pure function of --seed."""

import datetime

import pytest

import numpy as np

from benchmark import dists, trafficgen
from benchmark.loaders import sbtest, tpch

BIG_SEED = 2**31 + 12345


def draws(name, variables, seed, client, n, stream=trafficgen.WINDOW_STREAM):
    c = trafficgen.Client(trafficgen.load_traffic(name), variables, seed,
                          client, stream)
    out = []
    for _ in range(n):
        t = c.next()
        out.append((t.name, t.begin, t.commit,
                    [(s.name, s.sql, tuple(s.params.items()))
                     for s in t.statements]))
    return out


@pytest.mark.parametrize("name,variables", [
    ("q1q6", {"lineitem_rows": 60_000}),
    ("read_only", {"table_size": 10_000})])
def test_same_seed_same_statements_other_seed_others(name, variables):
    a = draws(name, variables, BIG_SEED, 0, 40)
    assert a == draws(name, variables, BIG_SEED, 0, 40)
    assert a != draws(name, variables, BIG_SEED + 1, 0, 40)
    assert a != draws(name, variables, BIG_SEED, 1, 40)
    assert a != draws(name, variables, BIG_SEED, 0, 40,
                      trafficgen.WARMUP_STREAM)


def test_tpch_parameters_are_the_specifications():
    for name, _, _, stmts in draws("q1q6", {}, BIG_SEED, 0, 200):
        (stmt, sql, params), = stmts
        p = dict(params)
        assert stmt == name
        if name == "q1":
            assert 60 <= p["delta"] <= 120
            assert datetime.date.fromisoformat(p["cutoff"]) \
                == datetime.date(1998, 12, 1) \
                - datetime.timedelta(days=p["delta"])
            assert f"l_shipdate <= '{p['cutoff']}'" in sql
        else:
            assert 1993 <= p["year"] <= 1997
            assert p["next_year"] == p["year"] + 1
            assert 2 <= p["discount"] <= 9 and p["quantity"] in (24, 25)
            assert (p["disc_lo"], p["disc_hi"]) == (
                f"{(p['discount'] - 1) / 100:.2f}",
                f"{(p['discount'] + 1) / 100:.2f}")
            assert f"BETWEEN {p['disc_lo']} AND {p['disc_hi']}" in sql
    names = [t[0] for t in draws("q1q6", {}, 5, 0, 6)]
    assert names == ["q1", "q6"] * 3


def test_sysbench_transaction_is_the_script_and_ids_stay_in_the_table():
    size = 5_000
    sqls = {"point": "SELECT c FROM sbtest1 WHERE id = {id}",
            "range": "SELECT c FROM sbtest1 WHERE id BETWEEN {lo} AND {hi}",
            "sum": "SELECT SUM(k) FROM sbtest1 WHERE id BETWEEN {lo} AND {hi}",
            "order": "SELECT c FROM sbtest1 WHERE id BETWEEN {lo} AND {hi} "
                     "ORDER BY c",
            "distinct": "SELECT DISTINCT c FROM sbtest1 WHERE id BETWEEN "
                        "{lo} AND {hi} ORDER BY c"}
    firsts = []
    for _, begin, commit, stmts in draws("read_only", {"table_size": size},
                                         BIG_SEED, 3, 50):
        assert (begin, commit) == ("BEGIN", "COMMIT")
        # oltp_read_only.lua's event(), every option at its default
        assert [s[0] for s in stmts] \
            == ["point"] * 10 + ["range", "sum", "order", "distinct"]
        for name, sql, params in stmts:
            p = dict(params)
            assert sql == sqls[name].format(**p)
            first = p["id"] if name == "point" else p["lo"]
            assert 1 <= first <= size
            assert name == "point" or p["hi"] == p["lo"] + 99
            firsts.append(first)
    # rand_type special: most ids lie in the 1% around the middle
    middle = [size // 2 - size // 200 <= i < size // 2 + size // 200 + 1
              for i in firsts]
    assert 0.65 < sum(middle) / len(middle) < 0.85


def test_special_is_sysbenchs_default_distribution():
    x = dists.special(np.random.default_rng(BIG_SEED), 1, 1_000_000, 200_000)
    assert x.min() >= 1 and x.max() <= 1_000_000
    # 3 of 4 draws fall evenly on the 10,000 ids from 495,001 ...
    few = (x > 495_000) & (x <= 505_000)
    assert 0.75 < few.mean() < 0.78
    assert len(np.unique(x[few])) > 9_900
    # ... the fourth is the mean of 12 uniform draws: a bell over the range
    bell = x[~few]
    assert abs(bell.mean() - 500_000) < 2_000
    assert abs(bell.std() - 1_000_000 / 12) < 4_000
    assert np.array_equal(
        x, dists.special(np.random.default_rng(BIG_SEED), 1, 1_000_000,
                         200_000))


def test_arith_takes_only_arithmetic():
    assert trafficgen.arith("table_size - 99", {"table_size": 1000}) == 901
    assert trafficgen.arith("(d + 1) / 100", {"d": 6}) == 0.07
    assert trafficgen.arith(7, {}) == 7
    with pytest.raises(ValueError):
        trafficgen.arith("__import__('os')", {})
    with pytest.raises(KeyError):
        trafficgen.arith("rows + 1", {"table_size": 1})


def test_generated_tables_are_a_function_of_the_seed():
    a, b = tpch.generate(0.002, BIG_SEED), tpch.generate(0.002, BIG_SEED)
    assert all(a[k].equals(b[k]) for k in a)
    assert not a["lineitem"].equals(tpch.generate(0.002, 1)["lineitem"])
    assert set(a) == set(tpch.DDL)
    rand = {"type": "special", "iter": 12, "pct": 1, "res": 75}
    s = sbtest.generate(2_000, 1, BIG_SEED, rand)["sbtest1"]
    assert s.equals(sbtest.generate(2_000, 1, BIG_SEED, rand)["sbtest1"])
    k = s.column("k").to_numpy()
    assert 0.65 < ((k > 990) & (k <= 1010)).mean() < 0.85
    row = s.slice(17, 1).to_pylist()[0]
    assert row["id"] == 18 and 1 <= row["k"] <= 2_000
    assert len(row["c"]) == 119 and len(row["pad"]) == 59
    groups = row["c"].split("-")
    assert len(groups) == 10 and all(len(g) == 11 and g.isdigit()
                                     for g in groups)
    assert len(row["pad"].split("-")) == 5


def test_lineitem_follows_dbgen_where_the_queries_look():
    t = tpch.generate(0.01, BIG_SEED)
    li = {c: t["lineitem"].column(c).to_numpy(zero_copy_only=False)
          for c in ("l_returnflag", "l_linestatus", "l_shipdate",
                    "l_receiptdate", "l_quantity", "l_extendedprice",
                    "l_partkey", "l_suppkey", "l_orderkey")}
    current = np.datetime64(tpch.CURRENTDATE)
    assert np.array_equal(li["l_linestatus"] == "O",
                          li["l_shipdate"] > current)
    assert np.array_equal(li["l_returnflag"] == "N",
                          li["l_receiptdate"] > current)
    groups = {(f, s) for f, s in zip(li["l_returnflag"].tolist(),
                                     li["l_linestatus"].tolist())}
    assert groups == {("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")}
    flags = li["l_returnflag"][li["l_returnflag"] != "N"]
    assert 0.48 < (flags == "R").mean() < 0.52
    small = ((li["l_returnflag"] == "N") & (li["l_linestatus"] == "F")).mean()
    assert 0.002 < small < 0.015
    retail = t["part"].column("p_retailprice").to_numpy()
    assert retail.min() >= 900.0 and retail.max() <= 2098.99
    assert np.allclose(li["l_extendedprice"],
                       li["l_quantity"] * retail[li["l_partkey"] - 1],
                       rtol=0, atol=0.006)
    # every (part, supplier) of a line is one partsupp offers
    ps = set(zip(t["partsupp"].column("ps_partkey").to_numpy().tolist(),
                 t["partsupp"].column("ps_suppkey").to_numpy().tolist()))
    assert set(zip(li["l_partkey"].tolist(), li["l_suppkey"].tolist())) <= ps
    # sparse order keys, and an order's status and price from its lines
    keys = t["orders"].column("o_orderkey").to_numpy()
    assert np.all(keys % 32 < 8) and np.all(np.diff(keys) > 0)
    status = dict(zip(keys.tolist(),
                      t["orders"].column("o_orderstatus").to_pylist()))
    is_open = {}
    for k, o in zip(li["l_orderkey"].tolist(),
                    (li["l_linestatus"] == "O").tolist()):
        is_open.setdefault(k, set()).add(o)
    assert all(status[k] == ("P" if len(v) == 2 else "O" if True in v
                             else "F") for k, v in is_open.items())
