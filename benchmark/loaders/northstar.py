"""The north-star table: 100 M rows of ``t (g INT, g1000 INT, g4000 INT, v
FLOAT)`` made from a seed, loaded whole and held on the chip.

``BASELINE.json`` states the metric this build exists for, "rows/sec/chip
on 100M-row filter+GROUP BY"; ``chip_smoke.py:phase_groupby`` (PR 21) made
it a table: three key columns uniform in 16, 1,000 and 4,000 values, one
for each lowering of the dense GROUP BY, and ``v`` standard normal float32.
The columns are drawn in that order from ``numpy.random.default_rng(seed)``.

Before a row is made this loader asks the served program, over the session
it is handed, for the counters the configuration lists under ``requires``
(``SHOW STATUS``).  A program without them cannot run this deployment
as it is stated (``PERF.md`` section 6, PR 35, step 0): it stamps a bulk
load one Python dict entry a row (100 M of them: 35 s and 13.8 GB of host
memory), and its answers are not the configuration's — its Pallas
aggregate rounds SUM to float32 and its filter over the FLOAT column is
float32 arithmetic, 25 of a window's 35 answers wrong — so it is refused
here, in seconds, in place of a run whose result could only be "not
correct".

After the load it states the deployment's layout as an operator does, in
SQL: ``SET GLOBAL streaming_scan = 0`` — the table stays on the chip
between statements.  Nothing here imports the program.
"""

import numpy as np
import pyarrow as pa

DDL = "CREATE TABLE t (g INT, g1000 INT, g4000 INT, v FLOAT)"
KEYS = {"g": 16, "g1000": 1000, "g4000": 4000}


def generate(rows: int, seed: int) -> pa.Table:
    """-> the table, a pure function of its arguments."""
    rng = np.random.default_rng(seed)
    cols = {name: rng.integers(0, n, rows, dtype=np.int32)
            for name, n in KEYS.items()}
    cols["v"] = rng.standard_normal(rows, dtype=np.float32)
    return pa.table(cols)


def load(config: dict, seed: int, scale: float, session) -> dict:
    """Create and fill ``t``.  -> {"tables", "vars"}."""
    have = {str(r[0]).partition(".")[0]
            for r in session.execute("SHOW STATUS").rows}
    missing = [n for n in config["requires"]["status"] if n not in have]
    if missing:
        raise RuntimeError(
            f"{config['name']}: the program's SHOW STATUS lacks {missing}: "
            f"its bulk load makes a Python dict entry a row and its sums "
            f"over a FLOAT column are not DOUBLE, so it cannot run this "
            f"deployment as stated")
    rows = max(1000, int(config["scale"]["rows"] * scale))
    table = generate(rows, seed)
    session.execute(DDL)
    session.load_arrow("t", table)
    session.execute("SET GLOBAL streaming_scan = 0")
    return {"tables": {"t": table}, "vars": {"table_size": rows}}
