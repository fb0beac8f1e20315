"""sysbench's ``sbtest1`` for the read-write transaction: the table of
``benchmark/loaders/sbtest.py``, loaded the same way.

Before a row is made this loader asks the served program, over the session
it is handed, for the counters the configuration lists under ``requires``
(``SHOW STATUS``).  A program without them takes every write by rebuilding
the whole 1M-row device image and retracing its programs on a changed
dictionary, so it cannot run this deployment inside a window; it is refused
here, in seconds.

Beside the table it hands the reference one more, ``__replay__``: the seed
and the table size, from which the reference draws the warm-up's writes
again (the warm-up writes too, and its statements are not compared).
Nothing here imports the program.
"""

import pyarrow as pa

from benchmark.loaders import sbtest


def load(config: dict, seed: int, scale: float, session) -> dict:
    """Create and fill ``sbtest1``.  -> {"tables", "vars"}."""
    have = {str(r[0]).partition(".")[0]
            for r in session.execute("SHOW STATUS").rows}
    missing = [n for n in config["requires"]["status"] if n not in have]
    if missing:
        raise RuntimeError(
            f"{config['name']}: the program's SHOW STATUS lacks {missing}: "
            f"it rebuilds the resident image on every write, so it cannot "
            f"run this deployment as stated")
    out = sbtest.load(config, seed, scale, session)
    out["tables"]["__replay__"] = pa.table({
        "seed": pa.array([seed], pa.int64()),
        "table_size": pa.array([out["vars"]["table_size"]], pa.int64())})
    return out
