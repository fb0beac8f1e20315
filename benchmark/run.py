"""One run of one benchmark cell over the served path.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children: a ``Database`` behind ``MySQLServer`` as
``python -m baikaldb_tpu.server`` builds them, with the cell's clients as
threads of this process speaking the MySQL wire through the in-repo client.
It loads the configuration's data from ``--seed``, warms every statement on
every connection, measures for ``--seconds``, compares every answer the
window returned with the plain reference, and prints one JSON object as its
last line.  The engine runs with default flags and places its own compile
cache (``<checkout>/.jax_cache`` and ``.aot_cache``).

Everything that belongs to one cell, configuration, traffic mix or metric is
a file found by its name (``benchmark/README.md``); this file names none.
On anything but a TPU it exits 3 and prints no result, unless
``--rehearse-scale`` (accepted on the CPU only) asks for a rehearsal at a
fraction of the rows, which reports no device-trace metric.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from benchmark import trace_reduce, trafficgen  # noqa: E402

EXIT_NO_DEVICE = 3
# how long past the close of the window an answer is waited for
ANSWER_WAIT_S = 60.0


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def resolve(ref: str):
    module, attr = ref.split(":")
    return getattr(importlib.import_module(module), attr)


def load_json(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


@dataclass
class StatementRecord:
    name: str
    params: dict
    sql: str
    t0: float
    t1: float = 0.0
    columns: list = field(default_factory=list)
    rows: list = field(default_factory=list)


@dataclass
class TxnRecord:
    client: int
    name: str
    annotation: str
    t0: float
    t1: float = 0.0
    error: str = ""
    wrong: bool = False
    statements: list = field(default_factory=list)

    @property
    def completed(self) -> bool:
        return not self.error and not self.wrong


@dataclass
class Window:
    """What one run measured; the readers under ``benchmark/readers`` take
    their metric from it."""
    setup_s: float
    t_open: float
    t_close: float
    txns: list
    counters: dict          # name -> growth over the window
    query_log: list         # the rows db.query_log gained in the window
    tables: dict
    traffic: dict
    device_kind: str
    trace: dict | None = None       # benchmark.trace_reduce's reduction
    trace_span: tuple | None = None     # host clock (start, stop)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


class Wire:
    """One client's connection and the statements it has prepared on it
    (SQL with ``?`` -> statement id)."""

    def __init__(self, conn):
        self.conn, self.ids = conn, {}

    def send(self, sql: str, prepare: str | None = None, args: tuple = ()):
        """``sql`` as COM_QUERY, or, given ``prepare``, COM_STMT_EXECUTE of
        it with ``args`` bound, prepared on its first use here (in the
        warm-up, as sysbench prepares in ``thread_init``)."""
        if prepare is None:
            return self.conn.query(sql)
        if prepare not in self.ids:
            self.ids[prepare] = self.conn.prepare(prepare)
        return self.conn.execute(self.ids[prepare], args)

    def close(self) -> None:
        self.conn.close()


def run_txn(wire: Wire, txn, client: int) -> TxnRecord:
    """One transaction over the wire, every statement's answer kept; the
    call is a span of its own in a profiler trace."""
    from jax.profiler import TraceAnnotation

    from baikaldb_tpu.client.mysql_client import MySQLError

    rec = TxnRecord(client, txn.name, txn.annotation, time.perf_counter())
    with TraceAnnotation(txn.annotation):
        try:
            if txn.begin:
                wire.send(txn.begin, txn.begin if txn.prepared else None)
            for s in txn.statements:
                sr = StatementRecord(s.name, s.params, s.sql,
                                     time.perf_counter())
                res = wire.send(s.sql, s.prepare, s.args)
                sr.t1 = time.perf_counter()
                sr.columns, sr.rows = res.columns, res.rows
                rec.statements.append(sr)
            if txn.commit:
                wire.send(txn.commit, txn.commit if txn.prepared else None)
        except MySQLError as e:
            rec.error = f"{type(e).__name__}: {e}"
        rec.t1 = time.perf_counter()
    return rec


class ClientThread(threading.Thread):
    """A closed-loop client: the next transaction goes out when the last
    one's COMMIT is acknowledged, until the deadline."""

    def __init__(self, wire, client):
        super().__init__(name=f"client-{client.index}")
        self.wire, self.client = wire, client
        self.deadline = 0.0
        self.go = threading.Event()
        self.txns: list = []
        self.crash: BaseException | None = None

    def run(self):
        self.go.wait()
        try:
            while time.perf_counter() < self.deadline:
                self.txns.append(run_txn(self.wire, self.client.next(),
                                         self.client.index))
        except BaseException as e:          # handed to main(), which raises
            self.crash = e


def drive(wires: list, clients: list, seconds: float,
          meanwhile=lambda t_open: None) -> tuple:
    """Every client at once, each on its own connection, for ``seconds``;
    ``meanwhile(t_open)`` runs on this thread while they work.  Waits for
    every answer.  -> (t_open, the transactions, what ``meanwhile`` gave)."""
    threads = [ClientThread(w, cl) for w, cl in zip(wires, clients)]
    for t in threads:
        t.start()
    t_open = time.perf_counter()
    for t in threads:
        t.deadline = t_open + seconds
        t.go.set()
    try:
        done = meanwhile(t_open)
    finally:
        for t in threads:
            t.join(seconds + ANSWER_WAIT_S)
    for t in threads:
        if t.crash is not None:
            raise t.crash
    late = [t.name for t in threads if t.is_alive()]
    if late:
        raise RuntimeError(f"no answer {ANSWER_WAIT_S:.0f} s past the "
                           f"deadline from {late}")
    return t_open, [x for t in threads for x in t.txns], done


def read_counters(metrics_mod) -> dict:
    """Every counter and recorder count of the program's registry."""
    out = {}
    for name, m in metrics_mod.REGISTRY.expose().items():
        if isinstance(m.get("value"), (int, float)):
            out[name] = m["value"]
        elif isinstance(m.get("count"), (int, float)):
            out[name] = m["count"]
    return out


def device_block(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        peak = max(peak, (d.memory_stats() or {}).get("peak_bytes_in_use", 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def check_answers(txns: list, traffic: dict, limits: dict, tables: dict,
                  control: bool) -> tuple:
    """Compare every answer of the window with its reference.  -> (the
    numbers compared, each the worst over the window; the same numbers of
    the control, the lower-precision reference put in the program's place,
    or None).  Marks wrong transactions."""
    ctx = {"tables": tables}
    refs = {name: resolve(s["ref"])
            for name, s in traffic["statements"].items()}
    numbers = {name: 0 for name in limits}
    lowered = {name: 0 for name in limits} if control else None
    for txn in txns:
        if txn.error:
            numbers["errors"] += 1
            continue
        for s in txn.statements:
            ref = refs[s.name]
            want = ref.answer(ctx, s.params)
            for name, v in ref.gaps(s.columns, s.rows, want).items():
                numbers[name] = max(numbers[name], v)
                if not v <= limits[name]:
                    txn.wrong = True
            if control:
                low = ref.answer(ctx, s.params, lower=True)
                for name, v in ref.gaps(low[0], low[1], want).items():
                    lowered[name] = max(lowered[name], v)
    return numbers, lowered


def within(numbers: dict, limits: dict) -> bool:
    """The comparison that decides ``correct``, and the control's verdict."""
    return all(numbers[k] <= limits[k] for k in limits)


def longest_quiet_s(txns: list, t_open: float, t_close: float) -> float:
    """The longest stretch of the window in which no transaction ended: a
    stall of every client at once shows here, whatever the rate says."""
    ends = sorted([t_open] + [x.t1 for x in txns] + [t_close])
    return max(b - a for a, b in zip(ends, ends[1:]))


def slowest_logged(query_log: list) -> dict | None:
    """The slowest statement the program's own log holds of the window (its
    last 1,000 rows), with the log's split into phases: where a stalled
    window lost its time."""
    if not query_log:
        return None
    r = max(query_log, key=lambda r: r[1])
    return {"sql": r[0][:80], "ms": r[1], "plan_cache": r[3],
            "phases_ms": r[5]}


def main(argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-scale", type=float, default=None,
                    help="CPU only: run at this fraction of the rows")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also hold the lower-precision control to the limits")
    args = ap.parse_args(argv)

    cell = load_json("workloads", args.workload)
    config = load_json("configs", cell["config"])
    traffic = trafficgen.load_traffic(cell["traffic"])
    limits = {"errors": 0, **cell["limits"]}

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    rehearsal = args.rehearse_scale is not None
    if rehearsal and platform != "cpu":
        say(f"run: --rehearse-scale is for the cpu, found {platform!r}")
        return 2
    if not rehearsal and (platform != "tpu" or len(devices) < cell["chips"]):
        say(f"run: {args.workload} needs {cell['chips']} tpu chip(s); jax "
            f"found {len(devices)} x {platform!r}; nothing was run")
        return EXIT_NO_DEVICE
    say(f"run: {args.workload} seed={args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}" + (" (rehearsal)" if rehearsal else ""))

    import baikaldb_tpu  # noqa: F401  (places the compile cache)
    from baikaldb_tpu.client.mysql_client import Connection
    from baikaldb_tpu.exec.session import Database, Session
    from baikaldb_tpu.server.mysql_server import MySQLServer
    from baikaldb_tpu.utils import compilecache, metrics

    db = Database()
    srv = MySQLServer(db, port=0).start()
    wires: list = []
    try:
        t0 = time.perf_counter()
        loaded = resolve(config["loader"])(
            config, args.seed, args.rehearse_scale if rehearsal else 1.0,
            Session(db=db))
        tables, variables = loaded["tables"], loaded["vars"]
        say(f"run: loaded {sum(t.nbytes for t in tables.values())} Arrow "
            f"bytes in {time.perf_counter() - t0:.1f} s")

        # warm-up: every kind of transaction on every connection the window
        # will use (a connection's first statement pays its own first
        # touch), then all connections at once (statements that meet in the
        # server are a shape of their own), then wait for the publisher's
        # background compiles
        n = traffic["clients"]
        kinds = len(traffic["transactions"])
        warm = [trafficgen.Client(traffic, variables, args.seed, i,
                                  trafficgen.WARMUP_STREAM) for i in range(n)]
        for i in range(n):
            wires.append(Wire(Connection(port=srv.port)))
            for r in range(traffic.get("warmup_rounds", 2) * kinds):
                rec = run_txn(wires[i], warm[i].next(), i)
                if rec.error:
                    raise RuntimeError(f"warm-up failed: {rec.error}")
                say(f"run: warm-up client {i} {rec.name} #{r // kinds}: "
                    f"{(rec.t1 - rec.t0) * 1e3:.1f} ms")
        together = traffic.get("warmup_together_s", 0)
        if together:
            _, recs, _ = drive(wires, warm, together)
            bad = [x.error for x in recs if x.error]
            if bad:
                raise RuntimeError(f"warm-up failed: {bad[0]}")
            say(f"run: warm-up, {n} clients at once for {together} s: "
                f"{len(recs)} transactions, slowest "
                f"{max(x.t1 - x.t0 for x in recs) * 1e3:.1f} ms")
        drained = compilecache.AOT.drain(600)
        say(f"run: aot publisher drained={drained}")

        # the window
        def trace_some(t_open: float):
            """A profiler trace of a few seconds inside the window."""
            if not (args.trace and platform == "tpu"):
                return None, None
            at = min(cell["trace"]["start_s"], args.seconds / 4)
            length = min(cell["trace"]["length_s"], args.seconds / 2)
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            time.sleep(max(0.0, t_open + at - time.perf_counter()))
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace_reduce.MARK):
                # ties the trace's clock to this one
                t_on = time.perf_counter()
            time.sleep(length)
            t_off = time.perf_counter()
            jax.profiler.stop_trace()
            return trace_dir, (t_on, t_off)

        clients = [trafficgen.Client(traffic, variables, args.seed, i)
                   for i in range(n)]
        db.query_log.clear()
        before = read_counters(metrics)
        t_open, txns, (trace_dir, trace_span) = drive(
            wires, clients, args.seconds, trace_some)
        t_close = max([x.t1 for x in txns] + [t_open + args.seconds])
        after = read_counters(metrics)
        grew = {k: v - before.get(k, 0) for k, v in after.items()}
        say("run: counters that grew in the window: "
            + json.dumps({k: v for k, v in grew.items() if v and v == v}))
        window = Window(
            setup_s=t_open - T_START, t_open=t_open, t_close=t_close,
            txns=txns, counters=grew, query_log=list(db.query_log),
            tables=tables, traffic=traffic,
            device_kind=devices[0].device_kind, trace_span=trace_span)
        device = device_block(devices, cell["chips"])
    finally:
        for w in wires:
            w.close()
        srv.stop()

    if trace_dir is not None:
        try:
            window.trace = trace_reduce.reduce_trace(
                trace_dir, cell["chips"], trace_span,
                [(x.t0, x.t1, x.annotation) for x in txns])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = window.trace["busy_s"]
        device["window_s"] = window.trace["window_s"]

    # the answers, once the window has closed and the peak has been read
    t0 = time.perf_counter()
    numbers, lowered = check_answers(txns, traffic, limits, tables,
                                     bool(args.control))
    say(f"run: compared {sum(len(x.statements) for x in txns)} answers in "
        f"{time.perf_counter() - t0:.1f} s")
    failed = sum(not x.completed for x in txns)
    for kind in sorted({x.name for x in txns}):
        ms = sorted((x.t1 - x.t0) * 1e3 for x in txns if x.name == kind)
        say(f"run: {len(ms)} x {kind}: min {ms[0]:.1f} median "
            f"{ms[len(ms) // 2]:.1f} max {ms[-1]:.1f} ms")
    for x in [x for x in txns if x.error][:5]:
        say(f"run: failed {x.name} on client {x.client}: {x.error}")
    correct = bool(txns) and failed == 0 and within(numbers, limits)

    out_metrics = {}
    for name in cell["per_layer" if args.trace else "end_to_end"]:
        m = load_json("metrics", name)
        value = resolve(m["reader"])(window, **m.get("params", {}))
        if value is not None:
            out_metrics[name] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(txns), "failed": failed,
              "metrics": out_metrics, "device": device}
    if window.trace is not None:
        result["breakdown"] = {"device_ops": window.trace["device_ops"],
                               "idle_gaps": window.trace["idle_gaps"]}
    # beside the contract's keys: what a window that reads far off is
    # looked up by (every run has them, traced or not)
    result["window_s"] = window.seconds
    result["longest_quiet_s"] = longest_quiet_s(txns, t_open, t_close)
    result["counters"] = {k: v for k, v in grew.items() if v and v == v}
    result["slowest_logged"] = slowest_logged(window.query_log)
    if lowered is not None:
        result["control"] = {"correct": within(lowered, limits),
                             "numbers": lowered}
        say(f"control (the reference in the precision below, held to the "
            f"same limits): correct={result['control']['correct']} "
            f"{json.dumps(lowered)}")
    result["compared"] = {k: {"value": numbers[k], "limit": limits[k]}
                          for k in limits}
    for k in limits:
        say(f"compared {k}: {numbers[k]!r} limit {limits[k]!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
