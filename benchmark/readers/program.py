"""Numbers the program keeps itself: ``db.query_log`` rows (text, dur_ms,
rows, cache outcome, buckets, {parse, plan, exec, egress} ms, snapshot ts)
and the counters of ``baikaldb_tpu.utils.metrics``.  Both are taken over the
window only: the log is emptied and the counters are read as it opens."""


def query_log_phase_ms(w, phases: list):
    """Mean over the window's query_log rows of the summed phases."""
    rows = [r[5] for r in w.query_log]
    if not rows:
        return None
    return sum(sum(r.get(p, 0.0) for p in phases) for r in rows) / len(rows)


def counter_growth(w, counters: list, per_transaction: bool = False):
    """Summed growth of the named counters (a recorder gives its count),
    whole or per transaction of the window."""
    missing = [c for c in counters if c not in w.counters]
    if missing:
        raise KeyError(f"the program's registry has no {missing}")
    total = sum(w.counters[c] for c in counters)
    if per_transaction:
        return total / len(w.txns) if w.txns else None
    return total
