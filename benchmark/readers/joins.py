"""Numbers of a cell whose plans join: what the overflow protocol of the
static-shape join path (``exec/caps.py``) leaves in the program's counters.
Each returns ``None`` where the program lacks the counters."""


def cap_fill_pct(w):
    """Rows the window's shrink and join nodes put out, as a share of the
    rows their settled capacities are sized for: ``join_live_rows`` over
    ``join_cap_slots``.  A program sized exactly for its data reads 100; the
    power-of-two rounding alone keeps it between 50 and 100 for one node."""
    slots = w.counters.get("join_cap_slots")
    live = w.counters.get("join_live_rows")
    if not slots or live is None:
        return None
    return 100.0 * live / slots
