"""TPC-H tables made from a seed, and their load into the served database.

The generator is the benchmark's own, after TPC-H v3 clause 4.2.3 (dbgen):
the eight tables with their columns and types, row counts that scale like
dbgen's (SF1 = 1.5 M orders, ~6.0 M lineitem), sparse order keys, retail
price from the part key, ``l_extendedprice = l_quantity * p_retailprice``,
ship, commit and receipt dates from the order date, ``l_returnflag`` and
``l_linestatus`` from those dates against 1995-06-17 (so Q1 has dbgen's four
groups: A/F, N/F, N/O, R/F, with N/F small), ``o_orderstatus`` and
``o_totalprice`` from the order's lines.  What it leaves out is text: names,
addresses, phones and comments are filler from a small vocabulary (an Arrow
``take``, not a Python string per row), not dbgen's grammar, and ``p_name``
has two colours, not five; the configuration's file lists these.  It stands
in for ``baikaldb_tpu/models/tpch.py:generate`` (the program's, 29 s per unit
of scale, uniform flags and prices).  Nothing here imports the program;
``load`` is handed the session.
"""

import datetime

import numpy as np
import pyarrow as pa

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1)]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
RETURNFLAGS = ["R", "A", "N"]
LINESTATUS = ["O", "F"]
ORDERSTATUS = ["F", "O", "P"]
CURRENTDATE = "1995-06-17"
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "chartreuse", "chocolate", "coral", "cornflower", "cream", "cyan",
          "dark", "deep", "dim", "dodger", "drab", "firebrick", "forest",
          "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey",
          "honeydew", "hot", "indian", "ivory", "khaki", "lace", "lavender",
          "lawn", "lemon", "light", "lime", "linen", "magenta", "maroon",
          "medium", "metallic", "midnight", "mint", "misty", "moccasin",
          "navajo", "navy", "olive", "orange", "orchid", "pale", "papaya",
          "peach", "peru", "pink", "plum", "powder", "puff", "purple", "red",
          "rose", "rosy", "royal", "saddle", "salmon", "sandy", "seashell",
          "sienna", "sky", "slate", "smoke", "snow", "spring", "steel", "tan",
          "thistle", "tomato", "turquoise", "violet", "wheat", "white",
          "yellow"]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_S1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_S2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
WORDS = ["fluffily", "carefully", "quickly", "ideas", "deposits", "packages",
         "accounts", "requests", "pending", "regular", "express", "bold",
         "silent"]

DDL = {
    "region": "CREATE TABLE region (r_regionkey INT PRIMARY KEY, "
              "r_name VARCHAR(25), r_comment VARCHAR(152))",
    "nation": "CREATE TABLE nation (n_nationkey INT PRIMARY KEY, "
              "n_name VARCHAR(25), n_regionkey INT, n_comment VARCHAR(152))",
    "part": "CREATE TABLE part (p_partkey INT PRIMARY KEY, "
            "p_name VARCHAR(55), p_mfgr VARCHAR(25), p_brand VARCHAR(10), "
            "p_type VARCHAR(25), p_size INT, p_container VARCHAR(10), "
            "p_retailprice DOUBLE, p_comment VARCHAR(23))",
    "supplier": "CREATE TABLE supplier (s_suppkey INT PRIMARY KEY, "
                "s_name VARCHAR(25), s_address VARCHAR(40), s_nationkey INT, "
                "s_phone VARCHAR(15), s_acctbal DOUBLE, "
                "s_comment VARCHAR(101))",
    "partsupp": "CREATE TABLE partsupp (ps_partkey INT, ps_suppkey INT, "
                "ps_availqty INT, ps_supplycost DOUBLE, "
                "ps_comment VARCHAR(199), "
                "PRIMARY KEY (ps_partkey, ps_suppkey))",
    "customer": "CREATE TABLE customer (c_custkey INT PRIMARY KEY, "
                "c_name VARCHAR(25), c_address VARCHAR(40), c_nationkey INT, "
                "c_phone VARCHAR(15), c_acctbal DOUBLE, "
                "c_mktsegment VARCHAR(10), c_comment VARCHAR(117))",
    "orders": "CREATE TABLE orders (o_orderkey INT PRIMARY KEY, "
              "o_custkey INT, o_orderstatus VARCHAR(1), o_totalprice DOUBLE, "
              "o_orderdate DATE, o_orderpriority VARCHAR(15), "
              "o_clerk VARCHAR(15), o_shippriority INT, o_comment VARCHAR(79))",
    "lineitem": "CREATE TABLE lineitem (l_orderkey INT, l_partkey INT, "
                "l_suppkey INT, l_linenumber INT, l_quantity DOUBLE, "
                "l_extendedprice DOUBLE, l_discount DOUBLE, l_tax DOUBLE, "
                "l_returnflag VARCHAR(1), l_linestatus VARCHAR(1), "
                "l_shipdate DATE, l_commitdate DATE, l_receiptdate DATE, "
                "l_shipinstruct VARCHAR(25), l_shipmode VARCHAR(10), "
                "l_comment VARCHAR(44))",
}

_EPOCH = datetime.date(1970, 1, 1)


def _d(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def _take(vocab: list, idx: np.ndarray) -> pa.Array:
    """vocab[idx] as a plain Arrow string array."""
    return pa.array(vocab, pa.string()).take(pa.array(idx))


def _pairs(a: list, b: list, sep: str = " ") -> list:
    return [x + sep + y for x in a for y in b]


_COMMENT_VOCAB = _pairs(_pairs(WORDS, WORDS), WORDS)


def _comments(rng, n: int, phrase: str = "", p: float = 0.0) -> pa.Array:
    """Three filler words; ``phrase`` is appended with probability ``p``."""
    idx = rng.integers(0, len(_COMMENT_VOCAB), n)
    if not phrase:
        return _take(_COMMENT_VOCAB, idx)
    idx = idx + len(_COMMENT_VOCAB) * (rng.random(n) < p)
    return _take(_COMMENT_VOCAB + [c + " " + phrase for c in _COMMENT_VOCAB],
                 idx)


def _choice(rng, vocab: list, n: int) -> pa.Array:
    return _take(vocab, rng.integers(0, len(vocab), n))


def _dates(days: np.ndarray) -> pa.Array:
    return pa.array(days.astype(np.int32), pa.int32()).cast(pa.date32())


def _phones(rng, nations: np.ndarray) -> pa.Array:
    n = len(nations)
    a, b = rng.integers(100, 999, n), rng.integers(100, 999, n)
    c = rng.integers(1000, 9999, n)
    return pa.array([f"{10 + k}-{x}-{y}-{z}"
                     for k, x, y, z in zip(nations.tolist(), a.tolist(),
                                           b.tolist(), c.tolist())])


def _tagged(prefix: str, nums: np.ndarray) -> pa.Array:
    return pa.array([f"{prefix}{k:09d}" for k in nums.tolist()])


def _retailprice(partkey: np.ndarray) -> np.ndarray:
    """dbgen's price of a part, a function of its key: 900.00 to 2098.99."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


def _supplier_of(partkey: np.ndarray, i: np.ndarray, n_supp: int):
    """dbgen's i-th (0..3) supplier of a part."""
    return ((partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp
            + 1).astype(np.int32)


def generate(scale: float, seed: int) -> dict:
    """-> table name -> pa.Table, a pure function of (scale, seed)."""
    rng = np.random.default_rng(seed)
    n_orders = max(100, int(1_500_000 * scale))
    n_cust = max(30, int(150_000 * scale))
    n_supp = max(10, int(10_000 * scale))
    n_part = max(40, int(200_000 * scale))

    region = pa.table({
        "r_regionkey": np.arange(5, dtype=np.int32),
        "r_name": REGIONS,
        "r_comment": _comments(rng, 5),
    })
    nation = pa.table({
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int32),
        "n_name": [n for n, _ in NATIONS],
        "n_regionkey": np.asarray([r for _, r in NATIONS], np.int32),
        "n_comment": _comments(rng, len(NATIONS)),
    })

    mfgr = rng.integers(1, 6, n_part)
    brand = rng.integers(1, 6, n_part)
    part = pa.table({
        "p_partkey": np.arange(1, n_part + 1, dtype=np.int32),
        "p_name": _choice(rng, _pairs(COLORS, COLORS), n_part),
        "p_mfgr": _take([f"Manufacturer#{i}" for i in range(6)], mfgr),
        "p_brand": _take([f"Brand#{i}{j}" for i in range(6)
                          for j in range(6)], mfgr * 6 + brand),
        "p_type": _choice(rng, _pairs(_pairs(TYPE_S1, TYPE_S2), TYPE_S3),
                          n_part),
        "p_size": rng.integers(1, 51, n_part).astype(np.int32),
        "p_container": _choice(rng, _pairs(CONTAINER_S1, CONTAINER_S2),
                               n_part),
        "p_retailprice": _retailprice(np.arange(1, n_part + 1)),
        "p_comment": _comments(rng, n_part),
    })

    s_nat = rng.integers(0, len(NATIONS), n_supp).astype(np.int32)
    supplier = pa.table({
        "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int32),
        "s_name": _tagged("Supplier#", np.arange(1, n_supp + 1)),
        "s_address": _comments(rng, n_supp),
        "s_nationkey": s_nat,
        "s_phone": _phones(rng, s_nat),
        "s_acctbal": np.round(rng.uniform(-999, 9999, n_supp), 2),
        "s_comment": _comments(rng, n_supp, "Customer Complaints", 0.03),
    })

    # each part is supplied by 4 suppliers; a small n_supp can collide, so
    # the first of each (part, supplier) pair is kept
    ps_part = np.repeat(np.arange(1, n_part + 1, dtype=np.int32), 4)
    ps_supp = _supplier_of(ps_part.astype(np.int64),
                           np.tile(np.arange(4), n_part), n_supp)
    _, first = np.unique(ps_part.astype(np.int64) * (n_supp + 1) + ps_supp,
                         return_index=True)
    first.sort()
    ps_part, ps_supp = ps_part[first], ps_supp[first]
    n_ps = len(ps_part)
    partsupp = pa.table({
        "ps_partkey": ps_part,
        "ps_suppkey": ps_supp,
        "ps_availqty": rng.integers(1, 10000, n_ps).astype(np.int32),
        "ps_supplycost": np.round(rng.uniform(1, 1000, n_ps), 2),
        "ps_comment": _comments(rng, n_ps),
    })

    c_nat = rng.integers(0, len(NATIONS), n_cust).astype(np.int32)
    customer = pa.table({
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int32),
        "c_name": _tagged("Customer#", np.arange(1, n_cust + 1)),
        "c_address": _comments(rng, n_cust),
        "c_nationkey": c_nat,
        "c_phone": _phones(rng, c_nat),
        "c_acctbal": np.round(rng.uniform(-999, 9999, n_cust), 2),
        "c_mktsegment": _choice(rng, SEGMENTS, n_cust),
        "c_comment": _comments(rng, n_cust, "special requests", 0.1),
    })

    o_dates = rng.integers(_d("1992-01-01"), _d("1998-08-02") + 1, n_orders)
    # like dbgen, a third of the customers never order: custkeys divisible
    # by 3 are skipped; and order keys are sparse, 8 used of every 32
    o_cust = rng.integers(1, n_cust + 1, n_orders).astype(np.int32)
    o_cust = np.where(o_cust % 3 == 0, np.maximum(o_cust - 1, 1), o_cust)
    seq = np.arange(1, n_orders + 1)
    o_key = ((seq >> 3 << 5) | (seq & 7)).astype(np.int32)

    per = rng.integers(1, 8, n_orders)
    first_line = np.cumsum(per) - per
    n_li = int(per.sum())
    linenum = (np.arange(n_li) - np.repeat(first_line, per) + 1) \
        .astype(np.int32)
    ship = np.repeat(o_dates, per) + rng.integers(1, 122, n_li)
    commit = np.repeat(o_dates, per) + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    l_part = rng.integers(1, n_part + 1, n_li)
    quantity = rng.integers(1, 51, n_li).astype(np.float64)
    price = np.round(quantity * _retailprice(l_part), 2)
    discount = np.round(rng.integers(0, 11, n_li) / 100.0, 2)
    tax = np.round(rng.integers(0, 9, n_li) / 100.0, 2)
    # returned (R) or accepted (A) once received by the current date, else
    # N; shipped after it: still open (O), else filled (F)
    received = receipt <= _d(CURRENTDATE)
    flag = np.where(received, rng.integers(0, 2, n_li), 2)
    open_line = ship > _d(CURRENTDATE)
    lineitem = pa.table({
        "l_orderkey": np.repeat(o_key, per),
        "l_partkey": l_part.astype(np.int32),
        "l_suppkey": _supplier_of(l_part, rng.integers(0, 4, n_li), n_supp),
        "l_linenumber": linenum,
        "l_quantity": quantity,
        "l_extendedprice": price,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": _take(RETURNFLAGS, flag),
        "l_linestatus": _take(LINESTATUS, np.where(open_line, 0, 1)),
        "l_shipdate": _dates(ship),
        "l_commitdate": _dates(commit),
        "l_receiptdate": _dates(receipt),
        "l_shipinstruct": _choice(rng, SHIPINSTRUCT, n_li),
        "l_shipmode": _choice(rng, SHIPMODES, n_li),
        "l_comment": _comments(rng, n_li),
    })

    open_lines = np.add.reduceat(open_line.astype(np.int64), first_line)
    status = np.where(open_lines == 0, 0, np.where(open_lines == per, 1, 2))
    total = np.add.reduceat(
        np.round(price * (1 + tax) * (1 - discount), 2), first_line)
    orders = pa.table({
        "o_orderkey": o_key,
        "o_custkey": o_cust,
        "o_orderstatus": _take(ORDERSTATUS, status),
        "o_totalprice": np.round(total, 2),
        "o_orderdate": _dates(o_dates),
        "o_orderpriority": _choice(rng, PRIORITIES, n_orders),
        "o_clerk": _take([f"Clerk#{i:09d}" for i in range(1000)],
                         rng.integers(1, 1000, n_orders)),
        "o_shippriority": np.zeros(n_orders, np.int32),
        "o_comment": _comments(rng, n_orders, "special requests", 0.08),
    })
    return {"region": region, "nation": nation, "part": part,
            "supplier": supplier, "partsupp": partsupp, "customer": customer,
            "orders": orders, "lineitem": lineitem}


def load(config: dict, seed: int, scale: float, session) -> dict:
    """Create and fill the eight tables.  -> {"tables", "vars"}: the Arrow
    tables the references read, and the sizes the traffic may name."""
    tables = generate(config["scale"]["scale_factor"] * scale, seed)
    for name, ddl in DDL.items():
        session.execute(ddl)
        session.load_arrow(name, tables[name])
    return {"tables": tables,
            "vars": {"lineitem_rows": tables["lineitem"].num_rows}}
