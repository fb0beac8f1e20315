"""From a ``jax.profiler`` trace (``.xplane.pb``) to device busy time, idle
gaps named by what the client waited on, and the longest device operations.

What the trace holds, as looked at by hand on a TPU v5e (jax 0.9.0):
one plane per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` has one
event per device operation, named by its HLO text (start and duration in ns,
on the clock the host planes share), beside ``XLA Modules`` (one event per
program run, 5% longer in all than the ops' union) and ``Async XLA Ops``
(each copy from its start to its done, across the compute it overlaps: a
wait, not work; what of a copy is not overlapped shows as its ``copy-done``
on ``XLA Ops``);
``/host:CPU`` has one line per host thread, and ``TraceAnnotation`` spans are
events on the ``python`` line of the thread that opened them, but only those
that began and ended inside the trace.  A client call that straddles the
trace's edge is therefore missing, so the harness hands over its own record
of the client's calls on the host clock, with the host-clock time of a
``bench.mark`` annotation it opened inside the trace: that one event ties
the two clocks together.

Busy is the union of the ``XLA Ops`` intervals, so operations that overlap
count once.  The traced window is the harness's own span, from the mark to
the moment it asked the profiler to stop, laid onto the trace's clock at the
mark; device operations are cut to it, so idle edges count as idle.  (A
trace with no mark is taken from its first to its last event kept.)  The
reduction is kept here, under the benchmark's paths, so that every PR
computes these numbers the same way; ``benchmark/tests`` checks it against a
small recorded trace.
"""

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CLIENT_PREFIX = "client."
MARK = "bench.mark"
TOP = 10
NAME_CHARS = 160        # an op's HLO text is cut to this in the breakdown


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: list, start: float, end: float) -> list:
    """The idle intervals of ``[start, end]`` that the merged ``busy`` list
    leaves."""
    out, at = [], start
    for s, e in busy:
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
    if end > at:
        out.append((at, end))
    return [(s, e) for s, e in out if e > s]


def name_gaps(idle: list, spans: list) -> dict:
    """Idle seconds by the client span that was open meanwhile.  ``spans``
    are ``(start, end, name)``; where several clients wait at once the gap
    goes to each open span's name in equal parts, and time with no client
    call open is ``outside_client_calls``."""
    out: dict = defaultdict(float)
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    for gs, ge in idle:
        cuts = [gs] + [t for t in edges if gs < t < ge] + [ge]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_now = [n for s, e, n in spans if s <= mid < e]
            if not open_now:
                out["outside_client_calls"] += b - a
            for n in open_now:
                out[n] += (b - a) / len(open_now)
    return dict(out)


def read_planes(path: str) -> dict:
    """-> {"devices": {plane: [(start_s, end_s, name)]}, "client_spans":
    [(start_s, end_s, name)], "marks": [start_s], "extent": (first_s,
    last_s)}."""
    from jax.profiler import ProfileData

    devices: dict = {}
    spans: list = []
    marks: list = []
    first, last = float("inf"), float("-inf")
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(DEVICE_PREFIX) \
            and plane.name[len(DEVICE_PREFIX):].isdigit()
        is_host = plane.name == HOST_PLANE
        if not (is_device or is_host):
            continue
        for line in plane.lines:
            if is_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = ev.start_ns / 1e9
                e = s + ev.duration_ns / 1e9
                if is_device:
                    devices.setdefault(plane.name, []).append((s, e, ev.name))
                elif ev.name.startswith(CLIENT_PREFIX):
                    spans.append((s, e, ev.name))
                elif ev.name == MARK:
                    marks.append(s)
                else:
                    continue
                first, last = min(first, s), max(last, e)
    return {"devices": devices, "client_spans": spans, "marks": marks,
            "extent": (first, last)}


def reduce_planes(planes: dict, chips: int) -> dict:
    """-> busy_s (mean over the chips used), window_s, the ten longest
    device operations by total time and the ten largest idle shares by what
    the client was waiting on."""
    devices = planes["devices"]
    if not devices:
        raise ValueError("the trace holds no device operation")
    start, end = planes["extent"]
    names = sorted(devices)[:chips]
    busy_s, op_s, idle_s = 0.0, defaultdict(float), defaultdict(float)
    for name in names:
        busy = union([(s, e) for s, e, _ in devices[name]])
        busy_s += covered(busy) / len(names)
        for s, e, op in devices[name]:
            op_s[op[:NAME_CHARS]] += (e - s) / len(names)
        for who, secs in name_gaps(gaps(busy, start, end),
                                   planes["client_spans"]).items():
            idle_s[who] += secs / len(names)

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_s, "window_s": end - start,
            "device_ops": top(op_s), "idle_gaps": top(idle_s)}


def cut(events: list, first: float, last: float) -> list:
    """The ``(start, end, name)`` events that reach into ``[first, last]``,
    cut to it."""
    return [(max(s, first), min(e, last), n) for s, e, n in events
            if e > first and s < last]


def reduce_trace(trace_dir: str, chips: int, host_span: tuple,
                 host_spans: list) -> dict:
    """Reduce the trace under ``trace_dir``.  ``host_span`` is the traced
    span on the host clock, from the ``bench.mark`` annotation to the call
    that stopped the profiler, and ``host_spans`` are the client's calls
    ``(start, end, name)`` on that clock: span and calls are moved onto the
    trace's clock at the mark, and calls and device operations are cut to
    the span.  Without a mark in the trace, its own extent and the spans it
    holds itself are used."""
    planes = read_planes(find_xplane(trace_dir))
    if planes["marks"]:
        t_on, t_off = host_span
        shift = planes["marks"][0] - t_on
        first, last = t_on + shift, t_off + shift
        planes["extent"] = (first, last)
        planes["devices"] = {k: cut(v, first, last)
                             for k, v in planes["devices"].items()}
        planes["client_spans"] = cut(
            [(s + shift, e + shift, n) for s, e, n in host_spans],
            first, last)
    return reduce_planes(planes, chips)


def describe(path: str) -> None:
    """Print what a trace holds: planes, lines, event counts and the most
    frequent names of each line.  For the look by hand that comes before
    trusting the reduction on a new installation."""
    from collections import Counter

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            busy = covered(union([(e.start_ns, e.start_ns + e.duration_ns)
                                  for e in events])) / 1e9
            first = min(e.start_ns for e in events) / 1e9
            last = max(e.start_ns + e.duration_ns for e in events) / 1e9
            names = Counter(e.name for e in events).most_common(6)
            print(f"  line {line.name!r}: {len(events)} events, union "
                  f"{busy:.4f} s over [{first:.4f}, {last:.4f}] s; "
                  f"{names}")


if __name__ == "__main__":
    import json
    import sys

    describe(sys.argv[1])
    found = read_planes(sys.argv[1])
    print(f"marks at {found['marks']}, {len(found['client_spans'])} whole "
          f"client spans, extent {found['extent']}")
    print(json.dumps(reduce_planes(found, 1), indent=1))
