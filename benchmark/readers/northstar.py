"""Numbers of a cell whose statements each run one lowering of the dense
GROUP BY: a statement's share of its own roofline.  Each returns ``None``
where the run holds nothing to read (no trace, or several clients whose
device time the reduction cannot tell apart)."""

import json
from pathlib import Path

from benchmark.readers.device import _share_inside, scanned_bytes


def statement_roofline(w, statement: str):
    """Least time the chip could take over the named statement's work in
    the traced span, over the device time spent on it there.  Memory-bound
    by the statement's work, whatever implements it: the bytes are
    ``scanned_bytes`` of each of its executions (the columns its text
    names, over the table its traffic entry names under ``"scans"``),
    weighted by the share inside the span.  The device time is the
    statement's own: with one closed-loop client a transaction is one
    client call, so the chip's busy time while the call is open is the
    call's time inside the span less the idle seconds the reduction names
    by that call's annotation."""
    if w.trace is None or w.traffic["clients"] != 1:
        return None
    table = w.traffic["statements"][statement].get("scans")
    if not table:
        return None
    t_on, t_off = w.trace_span
    idle = dict(w.trace["idle_gaps"])
    total = open_s = idle_s = 0.0
    annotations = set()
    for t in w.txns:
        mine = [s for s in t.statements if s.name == statement]
        if not mine:
            continue
        if len(mine) != len(t.statements):
            return None     # the call's device time is not this statement's
        open_s += max(0.0, min(t.t1, t_off) - max(t.t0, t_on))
        annotations.add(t.annotation)
        total += sum(_share_inside(w, s) * scanned_bytes(s.sql,
                                                         w.tables[table])
                     for s in mine)
    busy_s = open_s - sum(idle.get(a, 0.0) for a in annotations)
    if not total or busy_s <= 0:
        return None
    with open(Path(__file__).resolve().parent.parent / "peaks.json") as f:
        peaks = json.load(f)["peaks"]
    if w.device_kind not in peaks:
        raise KeyError(f"peaks.json has no device kind {w.device_kind!r}")
    least_s = total / peaks[w.device_kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
