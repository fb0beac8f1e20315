"""TPC-H for the join cells: the tables of ``benchmark/loaders/tpch.py``,
loaded into a program that can settle a join plan's capacities.

The generator, the DDL and the load are ``tpch.load``'s, unchanged.  Before
them this loader asks the served program, over the session it is handed,
for the counters the configuration lists under ``requires`` (``SHOW
STATUS``).  A program without them plans and compiles Q18 anew for every
value of QUANTITY, twice each, and its first run of the cell takes several
times the 360 s at which the driver stops a run (``PERF.md`` section 6, PR
33), so it is refused here, before any table is made, in place of a run
that could only be killed.  No flag is set.
"""

from benchmark.loaders import tpch


def load(config: dict, seed: int, scale: float, session) -> dict:
    have = {str(r[0]).partition(".")[0]
            for r in session.execute("SHOW STATUS").rows}
    missing = [n for n in config["requires"]["status"] if n not in have]
    if missing:
        raise RuntimeError(
            f"{config['name']}: the program's SHOW STATUS lacks {missing}: "
            f"it cannot settle a join plan's capacities in one recompile, "
            f"and a first run of this cell would pass the driver's limit")
    return tpch.load(config, seed, scale, session)
