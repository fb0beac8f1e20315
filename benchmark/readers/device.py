"""Numbers from the profiler's trace, reduced by ``benchmark/trace_reduce``.
Each returns ``None`` where the run took no trace."""

import json
import re
from pathlib import Path


def _share_inside(w, rec) -> float:
    """The share of a transaction's or statement's own time that lies inside
    the traced span."""
    t_on, t_off = w.trace_span
    if rec.t1 <= rec.t0:
        return 0.0
    return max(0.0, min(rec.t1, t_off) - max(rec.t0, t_on)) / (rec.t1 - rec.t0)


def _txns_in_span(w) -> float:
    """Transactions' worth of work inside the traced span: each counts by
    its share inside (about three analytical queries fit a 5 s span, so whole
    counts would swing by a third)."""
    return sum(_share_inside(w, t) for t in w.txns)


def busy_ms_per_txn(w):
    """Device-busy time (union of the device's operation intervals) per
    transaction of the traced span."""
    if w.trace is None:
        return None
    n = _txns_in_span(w)
    return w.trace["busy_s"] * 1e3 / n if n else None


def idle_pct(w):
    """1 - busy / traced span."""
    if w.trace is None:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])


def scanned_bytes(sql: str, table) -> int:
    """The bytes a statement has to read at the least: the table's rows
    times the value widths, as the loaded Arrow table stores them, of the
    columns its text names.  From the text and the table, never the plan:
    the same work whatever implements it."""
    return sum(table.column(c).nbytes for c in table.column_names
               if re.search(rf"\b{re.escape(c)}\b", sql))


def scan_roofline(w):
    """Least time the chip could take over the device-busy time of the
    traced span.  Memory-bound: a scan-filter-aggregate does a few flops a
    byte, so the least time is bytes / peak HBM bytes/s; the bytes are
    ``scanned_bytes`` of each statement that names a table to scan
    (``"scans"`` in the traffic file), weighted by its share inside the
    span."""
    if w.trace is None or not w.trace["busy_s"]:
        return None
    with open(Path(__file__).resolve().parent.parent / "peaks.json") as f:
        peaks = json.load(f)["peaks"]
    if w.device_kind not in peaks:
        raise KeyError(f"peaks.json has no device kind {w.device_kind!r}")
    total = 0.0
    for t in w.txns:
        for s in t.statements:
            table = w.traffic["statements"][s.name].get("scans")
            if table:
                total += _share_inside(w, s) \
                    * scanned_bytes(s.sql, w.tables[table])
    if not total:
        return None
    least_s = total / peaks[w.device_kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / w.trace["busy_s"]
