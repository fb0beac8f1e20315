"""Long-lived objects out of the interpreter's full collections.

CPython's cyclic collector walks every tracked container of the oldest
generation on a full (generation-2) collection, and it starts one whenever
the objects that survived into that generation since the last one pass a
quarter of those already there.  A serving frontend holds hundreds of
thousands of such objects that never die: the runtime's compiled programs
and their caches, the plan cache, the tables' host-side indexes.  Walking
them takes tens of milliseconds, and the walk lands inside whatever
statement allocated last: one transaction in a few dozen is that much
slower, which is what a p95 feels (``PERF.md`` section 6).

``settle`` runs where the engine has just made long-lived state — after
every bulk load and after every program it compiles: one collection of
what is not frozen yet, then every survivor frozen (``gc.freeze``), so
later collections skip them and a full collection of serving walks only
what serving keeps alive since.  Its cost is the objects made since the
last settle.  A frozen object that becomes cyclic garbage waits for the
next settle that walks everything: the one after the frozen population has
doubled since the last such walk, so what is held that way stays under
what is alive, at a cost that stays proportional to what was made.
"""

import gc
import time

from . import metrics

gc_settles = metrics.Counter("gc_settles")
gc_settle_ms = metrics.Counter("gc_settle_ms")

# the frozen population after the last settle that walked everything
_walked = [0]


def settle(reason: str) -> None:
    """A collection, then its survivors frozen (module docstring)."""
    from ..obs import trace

    t0 = time.perf_counter()
    frozen = gc.get_freeze_count()
    whole = frozen >= 2 * _walked[0]
    with trace.span("gc.settle", reason=reason, whole=whole):
        if whole:
            gc.unfreeze()
        gc.collect()
        gc.freeze()
        if whole:
            _walked[0] = gc.get_freeze_count()
    gc_settles.add(1)
    gc_settle_ms.add((time.perf_counter() - t0) * 1e3)
