"""One benchmark run with the program's spans read out: the per-layer metrics
that the spans of ``obs/trace.py`` feed, and the device's idle and busy
seconds by the program span that was open.

    chiprun -- python tools/span_profile.py --workload tpch_sf1.q1q6 --seed 7 --trace 1

It runs ``benchmark/run.py``'s ``main`` in this process, unchanged, and lays
two things over it from outside (the benchmark's files are the yardstick and
this tool edits none):

- ``tools/span_metrics/metrics/<metric>.json`` are metric files in the
  benchmark's own schema, read by the benchmark's own readers
  (``query_log_phase_ms`` over the span keys of the window's ``query_log``
  rows, ``counter_growth`` over ``point_lookup_ms`` / ``wire_result_set_ms``)
  and ``tools/span_metrics/cells.json`` names the cell each belongs to.  The
  run's result line then carries them beside the cell's own per-layer
  metrics.  A ``benchmark`` PR adopts them by moving the files to
  ``benchmark/metrics/`` and the names into the cells' ``per_layer`` lists.
- with ``--trace 1`` on a TPU, the ``.xplane.pb`` is reduced once more before
  ``run.py`` deletes it: every ``db.*`` event of the host plane is a program
  span on the clock of ``XLA Ops``; per host thread the innermost open span
  owns the time, and an idle gap (or a busy interval) of the device goes to
  the spans open meanwhile, in equal parts where several threads have one
  open.  That is ``trace_reduce.name_gaps`` fed program spans where the
  benchmark feeds it client calls.  ``modules`` has the device's seconds by
  program (``[name, runs, seconds]`` from the ``XLA Modules`` line): which
  of the busy time is a plan's program and which an eager op's.
  ``op_classes`` has every chip's device seconds by class of operation
  (``all-to-all``, ``all-reduce``, ``all-gather``, ``collective-permute``
  against the rest) and the skew between the chips (busiest / mean): what a
  mesh cell's exchanges cost on the device, which the benchmark's own
  reduction (the ten longest ops, the mean busy time) cannot say.

``--tracing 1`` turns the ``tracing`` flag on for the run (span trees, the
store), to read what the tree costs end to end.  Both JSON lines (the
benchmark's and ``{"by_program_span": ...}``) also land in
``chiprun_out/span_profile/``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import run, trace_reduce  # noqa: E402

OVERLAY = Path(__file__).resolve().parent / "span_metrics"
SPAN_PREFIX = "db."
# the in-repo MySQL client's own span: the benchmark's client threads open
# it, and it is no part of the program
CLIENT_SPAN = "db.client.query"
NO_SPAN = "no_program_span"
MODULES_LINE = "XLA Modules"
MODULES_TOP = 16
# classes of device operation, by the head of the op's HLO name (async pairs
# ``-start`` / ``-done`` fall under their collective)
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather",
               "collective-permute")
OTHER_OPS = "other"


def innermost(events: list) -> list:
    """One thread's properly nested ``(start, end, name)`` events as
    non-overlapping segments, each owned by the innermost span open."""
    out: list = []
    stack: list = []            # (end, name) of the open spans
    at = 0.0                    # up to where the time has an owner

    def close_until(t: float) -> None:
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close_until(s)
        if stack and s > at:
            out.append((at, s, stack[-1][1]))
        at = s
        stack.append((e, name))
    close_until(float("inf"))
    return out


def program_spans(planes: list) -> list:
    """The ``db.*`` events of the host plane (of a ``ProfileData``'s
    ``planes``) as innermost segments of every host thread, ``(start_s,
    end_s, name)`` on the trace's clock."""
    segments: list = []
    for plane in planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            events = [(ev.start_ns / 1e9,
                       (ev.start_ns + ev.duration_ns) / 1e9, ev.name)
                      for ev in line.events
                      if ev.name.startswith(SPAN_PREFIX)
                      and ev.name != CLIENT_SPAN]
            segments.extend(innermost(events))
    return segments


def module_seconds(planes: list, first: float, last: float,
                   chips: int) -> list:
    """Seconds of ``[first, last]`` by device program (the ``XLA Modules``
    line: one event per run of a jitted or eager program, named by its
    module), mean over the chips used, longest first."""
    devices = sorted((p for p in planes
                      if p.name.startswith(trace_reduce.DEVICE_PREFIX)
                      and p.name[len(trace_reduce.DEVICE_PREFIX):].isdigit()),
                     key=lambda p: p.name)[:chips]
    out: dict = {}
    for plane in devices:
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            events = [(ev.start_ns / 1e9,
                       (ev.start_ns + ev.duration_ns) / 1e9,
                       re.sub(r"\(\d+\)$", "", ev.name))
                      for ev in line.events]
            for s, e, name in trace_reduce.cut(events, first, last):
                runs, secs = out.get(name, (0, 0.0))
                out[name] = (runs + 1 / len(devices),
                             secs + (e - s) / len(devices))
    return sorted(([k, n, v] for k, (n, v) in out.items()),
                  key=lambda row: -row[2])[:MODULES_TOP]


def op_class(name: str) -> str:
    """The class of a device operation named by its HLO text."""
    head = re.match(r"%?([a-z][a-z\-]*)", name)
    for c in COLLECTIVES:
        if head and head.group(1).startswith(c):
            return c
    return OTHER_OPS


def op_class_seconds(devices: dict, first: float, last: float,
                     chips: int) -> dict:
    """Per chip, the seconds of ``[first, last]`` its operations took by
    class (summed durations: an async collective overlapping compute counts
    under both) beside the chip's busy union; and ``skew``, the busiest
    chip's busy seconds over the mean.  ``devices`` is
    ``trace_reduce.read_planes``'s ``{plane: [(start, end, name)]}``."""
    out: dict = {}
    for plane in sorted(devices)[:chips]:
        events = trace_reduce.cut(devices[plane], first, last)
        secs = dict.fromkeys(COLLECTIVES + (OTHER_OPS,), 0.0)
        for s, e, name in events:
            secs[op_class(name)] += e - s
        secs["busy_s"] = trace_reduce.covered(
            trace_reduce.union([(s, e) for s, e, _ in events]))
        out[plane] = secs
    busy = [c["busy_s"] for c in out.values()]
    mean = sum(busy) / len(busy) if busy else 0.0
    return {"chips": out, "skew": max(busy) / mean if mean else None}


def by_program_span(trace_dir: str, chips: int, host_span: tuple) -> dict:
    """``reduce_spans`` of the ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = trace_reduce.find_xplane(trace_dir)
    return reduce_spans(trace_reduce.read_planes(path),
                        list(ProfileData.from_file(path).planes),
                        chips, host_span)


def reduce_spans(planes: dict, raw: list, chips: int,
                 host_span: tuple) -> dict:
    """Idle and busy seconds of the traced span by program span (mean over
    the chips used), cut to the span as ``trace_reduce.reduce_trace`` cuts.
    ``planes`` is ``trace_reduce.read_planes``'s dict and ``raw`` the same
    file's ``ProfileData`` planes."""
    first, last = planes["extent"]
    if planes["marks"]:
        shift = planes["marks"][0] - host_span[0]
        first, last = host_span[0] + shift, host_span[1] + shift
    spans = trace_reduce.cut(program_spans(raw), first, last)
    names = sorted(planes["devices"])[:chips]
    idle_s: dict = {}
    busy_s: dict = {}
    for name in names:
        busy = trace_reduce.union(
            [(s, e) for s, e, _ in
             trace_reduce.cut(planes["devices"][name], first, last)])
        for into, intervals in (
                (idle_s, trace_reduce.gaps(busy, first, last)),
                (busy_s, busy)):
            for who, secs in trace_reduce.name_gaps(intervals,
                                                    spans).items():
                who = NO_SPAN if who == "outside_client_calls" else who
                into[who] = into.get(who, 0.0) + secs / len(names)

    def ranked(d):
        return sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])

    return {"window_s": last - first, "program_span_events": len(spans),
            "idle_s": ranked(idle_s), "busy_s": ranked(busy_s),
            "modules": module_seconds(raw, first, last, chips),
            "op_classes": op_class_seconds(planes["devices"], first, last,
                                           chips)}


def main(argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: the benchmark's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse-scale", type=float, default=None)
    ap.add_argument("--tracing", type=int, choices=(0, 1), default=0,
                    help="run with the tracing flag on (span trees)")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "span_profile"))
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        seconds = json.loads(
            (run.BENCH / "manifest.json").read_text())["run_seconds"]
    extra = json.loads((OVERLAY / "cells.json").read_text())

    plain_load = run.load_json

    def load_json(kind: str, name: str) -> dict:
        if kind == "metrics" and (OVERLAY / kind / f"{name}.json").exists():
            return json.loads((OVERLAY / kind / f"{name}.json").read_text())
        d = plain_load(kind, name)
        if kind == "workloads":
            d["per_layer"] = d["per_layer"] + extra.get(name, [])
        return d

    by_span: dict = {}
    plain_reduce = trace_reduce.reduce_trace

    def reduce_trace(trace_dir, chips, host_span, host_spans):
        by_span.update(by_program_span(trace_dir, chips, host_span))
        return plain_reduce(trace_dir, chips, host_span, host_spans)

    if args.tracing:
        from baikaldb_tpu.obs import trace  # noqa: F401  (defines the flag)
        from baikaldb_tpu.utils.flags import set_flag
        set_flag("tracing", True)
    cmd = ["--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(seconds), "--trace", str(args.trace)]
    if args.rehearse_scale is not None:
        cmd += ["--rehearse-scale", str(args.rehearse_scale)]
    run.load_json, trace_reduce.reduce_trace = load_json, reduce_trace
    printed = io.StringIO()
    try:
        with contextlib.redirect_stdout(printed):
            rc = run.main(cmd)
    finally:
        run.load_json, trace_reduce.reduce_trace = plain_load, plain_reduce
    lines = printed.getvalue().strip().splitlines()
    if rc == 0 and lines:
        lines.append(json.dumps({"by_program_span": by_span or None,
                                 "tracing": args.tracing}))
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        tag = f"{args.workload}.seed{args.seed}.trace{args.trace}" \
              f".tracing{args.tracing}"
        (out / f"{tag}.jsonl").write_text("\n".join(lines[-2:]) + "\n")
    print("\n".join(lines), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
