"""Numbers of a cell whose tables are row-sharded over several chips.  Each
returns ``None`` where the run holds nothing to read: a program without the
counter, a run without a trace."""

import json
from pathlib import Path

from benchmark.readers.device import _share_inside, scanned_bytes


def counter_per_txn(w, counter: str, divide_by: float = 1.0):
    """Growth of one counter of the program over the window, per
    transaction, in units of ``divide_by``; ``None`` where the program's
    registry has no such counter."""
    if counter not in w.counters or not w.txns:
        return None
    return w.counters[counter] / divide_by / len(w.txns)


def scan_roofline(w, chips: int):
    """Least time ``chips`` chips could take over the mean device-busy time
    of the traced span.  Memory-bound, as ``device.scan_roofline``: the
    bytes are ``scanned_bytes`` of each statement over every table its
    traffic entry names under ``"scans"`` (one name or a list), weighted by
    the statement's share inside the span, spread evenly over the chips'
    HBM.  Reading every named column once is the least a join can do, so
    for a join this is a lower bound of the share, not the share of its
    own roofline."""
    if w.trace is None or not w.trace["busy_s"]:
        return None
    with open(Path(__file__).resolve().parent.parent / "peaks.json") as f:
        peaks = json.load(f)["peaks"]
    if w.device_kind not in peaks:
        raise KeyError(f"peaks.json has no device kind {w.device_kind!r}")
    total = 0.0
    for t in w.txns:
        for s in t.statements:
            scans = w.traffic["statements"][s.name].get("scans") or []
            if isinstance(scans, str):
                scans = [scans]
            total += _share_inside(w, s) * sum(
                scanned_bytes(s.sql, w.tables[name]) for name in scans)
    if not total:
        return None
    least_s = total / (chips * peaks[w.device_kind]["hbm_bytes_per_s"])
    return 100.0 * least_s / w.trace["busy_s"]
