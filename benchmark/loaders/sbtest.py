"""sysbench's ``sbtest`` tables made from a seed, and their load.

``c`` is ten groups of eleven digits joined by ``-`` (119 characters) and
``pad`` five such groups (59), as sysbench's ``oltp_common.lua`` fills them;
``k`` is drawn from [1, table_size] by the configuration's ``rand_type`` as
sysbench's prepare draws it (``special`` by default; ``benchmark/dists.py``),
and ``k_1`` is the secondary index on it that sysbench creates.  The strings are
one ``uint8`` matrix of digits handed to Arrow as the value buffer of a
string array: seconds for a million rows, where a Python string per row
takes minutes.  Nothing here imports the program; ``load`` is handed the
session.
"""

import numpy as np
import pyarrow as pa

from benchmark import dists

DDL = ("CREATE TABLE sbtest{i} (id BIGINT PRIMARY KEY, k BIGINT, "
       "c CHAR(120), pad CHAR(60), KEY k_{i} (k))")
GROUP = 11


def _digit_groups(rng, n: int, groups: int) -> pa.Array:
    width = groups * GROUP + groups - 1
    m = rng.integers(ord("0"), ord("9") + 1, (n, width), dtype=np.uint8)
    m[:, GROUP::GROUP + 1] = ord("-")
    offsets = np.arange(n + 1, dtype=np.int32) * width
    return pa.StringArray.from_buffers(n, pa.py_buffer(offsets),
                                       pa.py_buffer(m))


def generate(table_size: int, tables: int, seed: int, rand: dict) -> dict:
    """-> table name -> pa.Table, a pure function of its arguments.  ``rand``
    is ``{"type": "special", "iter", "pct", "res"}`` or ``{"type":
    "uniform"}``."""
    out = {}
    for i in range(1, tables + 1):
        rng = np.random.default_rng([seed, i])
        if rand["type"] == "special":
            k = dists.special(rng, 1, table_size, table_size, rand["iter"],
                              rand["pct"], rand["res"])
        elif rand["type"] == "uniform":
            k = rng.integers(1, table_size + 1, table_size, dtype=np.int64)
        else:
            raise ValueError(f"sbtest: unknown rand type {rand['type']!r}")
        out[f"sbtest{i}"] = pa.table({
            "id": np.arange(1, table_size + 1, dtype=np.int64),
            "k": k,
            "c": _digit_groups(rng, table_size, 10),
            "pad": _digit_groups(rng, table_size, 5),
        })
    return out


def load(config: dict, seed: int, scale: float, session) -> dict:
    """Create and fill the tables.  -> {"tables", "vars"}."""
    size = max(1000, int(config["scale"]["table_size"] * scale))
    n = config["scale"]["tables"]
    opts = config["sysbench_options"]
    tables = generate(size, n, seed, {
        "type": opts["rand_type"], "iter": opts["rand_spec_iter"],
        "pct": opts["rand_spec_pct"], "res": opts["rand_spec_res"]})
    for i in range(1, n + 1):
        session.execute(DDL.format(i=i))
        session.load_arrow(f"sbtest{i}", tables[f"sbtest{i}"])
    return {"tables": tables, "vars": {"table_size": size}}
