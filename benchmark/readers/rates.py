"""Rates and latencies on the generator's own clock (``time.perf_counter``
around the client's calls)."""

import statistics


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` % of
    the sample at or below it."""
    ordered = sorted(values)
    rank = -(-len(ordered) * q // 100)          # ceil
    return ordered[max(0, int(rank) - 1)]


def setup_s(w):
    """Process start -> window open."""
    return w.setup_s


def completed_per_s(w):
    """Transactions completed (answered, and answered right) over the whole
    window, which closes when the last transaction begun inside ``--seconds``
    is acknowledged."""
    return sum(t.completed for t in w.txns) / w.seconds


def txn_percentile_ms(w, q: float):
    """The q-th percentile of the latency of all transactions of all
    clients, first statement sent -> last acknowledged.  A transaction that
    failed counts at its own (shorter) time: ``failed`` reports it."""
    if not w.txns:
        return None
    return percentile([(t.t1 - t.t0) * 1e3 for t in w.txns], q)


def statement_median_ms(w, statements: list):
    """Median client-side time of the named statements."""
    ms = [(s.t1 - s.t0) * 1e3 for t in w.txns for s in t.statements
          if s.name in statements]
    return statistics.median(ms) if ms else None
