"""Readers: one function per kind of metric, ``(window, **params) -> number``
or ``None`` where the run holds nothing to read (a CPU rehearsal has no
device trace).  ``window`` is ``benchmark/run.py``'s ``Window``; a metric's
file under ``benchmark/metrics`` names its reader and the parameters."""
