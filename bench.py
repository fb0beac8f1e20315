"""Headline benchmark: filter + GROUP BY rows/sec vs CPU Arrow execution.

BASELINE.md target: >=10x rows/sec vs CPU Arrow exec on a 100M-row
filter+GROUP BY (the reference's vectorized Acero path,
src/store/region.cpp select_vectorized -> GlobalArrowExecutor, is what
pyarrow's compute engine stands in for here).

Prints one JSON line per phase; the first is
  {"metric": ..., "value": rows/sec on device, "unit": "rows/sec",
   "vs_baseline": speedup_over_pyarrow, "platform": ...}

The run uses whatever backend this process has, labels every line with it
and never switches backend.  A phase that raises prints its traceback to
stderr and no JSON line, the remaining phases still run, and the exit code
is 1.  Two phases (multiway, coldstart) run their work in child processes
pinned to the CPU — a parent that touched JAX holds the chip — and are
labelled ``"platform": "cpu"`` whatever the parent runs on.

Env knobs: BENCH_ROWS (default 100M; 4M on the CPU), BENCH_REPEATS,
BENCH_KERNEL=pallas, BENCH_SKIP_<PHASE>=1.
"""

import json
import os
import platform as _platform_mod
import subprocess
import sys
import time
import traceback

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))


def _hardware_context() -> dict:
    """Hardware/host fields for every bench JSON: perf numbers are not
    comparable across unlike hosts without these."""
    return {
        "nproc": os.cpu_count(),
        "host_machine": _platform_mod.machine(),
        "python": _platform_mod.python_version(),
    }


def _git_head() -> str | None:
    try:
        r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           capture_output=True, text=True, cwd=_REPO,
                           timeout=10)
        return r.stdout.strip() or None
    except (OSError, subprocess.TimeoutExpired):
        return None


def run_bench() -> dict:
    import jax
    import jax.numpy as jnp

    import baikaldb_tpu  # noqa: F401
    from baikaldb_tpu import ColumnBatch, col, lit
    from baikaldb_tpu.column.batch import Column
    from baikaldb_tpu.expr.compile import eval_predicate
    from baikaldb_tpu.ops.hashagg import AggSpec, group_aggregate_dense
    from baikaldb_tpu.types import LType

    platform = jax.devices()[0].platform
    n_rows = int(os.environ.get("BENCH_ROWS",
                                100_000_000 if platform != "cpu" else 4_000_000))
    repeats = int(os.environ.get("BENCH_REPEATS", 5))
    n_groups = 16

    rng = np.random.default_rng(42)
    g_np = rng.integers(0, n_groups, n_rows).astype(np.int32)
    v_np = rng.normal(size=n_rows).astype(np.float32)

    # ---- device pipeline: WHERE v*2+1 > 0.5 GROUP BY g -> count/sum/avg/min
    batch = ColumnBatch(
        ("g", "v"),
        [Column(jnp.asarray(g_np), None, LType.INT32),
         Column(jnp.asarray(v_np), None, LType.FLOAT32)])
    specs = [AggSpec("count_star", None, "n"), AggSpec("sum", "v", "s"),
             AggSpec("avg", "v", "a"), AggSpec("min", "v", "mn")]
    pred = (col("v") * lit(2.0) + lit(1.0)) > lit(0.5)

    use_pallas = os.environ.get("BENCH_KERNEL", "") == "pallas"
    if use_pallas:
        # hand-written fused kernel (ops/pallas_kernels.py): COUNT+SUM only,
        # for comparing against the XLA segment_sum lowering on real TPU
        from baikaldb_tpu.ops.pallas_kernels import filtered_group_sum

        @jax.jit
        def step(b):
            m = eval_predicate(pred, b)
            counts, sums = filtered_group_sum(
                b.column("g").data, b.column("v").data, m, n_groups)
            return (b.column("g").data[:1], counts.astype(jnp.int64), sums,
                    sums / jnp.maximum(counts, 1), counts, m[:1])
    else:
        @jax.jit
        def step(b):
            out = group_aggregate_dense(b.and_sel(eval_predicate(pred, b)),
                                        ["g"], [n_groups], specs)
            return tuple(c.data for c in out.columns) + (out.sel,)

    # Timing discipline: the clock stops on a device->host fetch of the
    # (tiny) aggregate outputs, which cannot complete before the program
    # has, and ITERS kernel iterations are scanned inside one jit to
    # amortize the per-dispatch cost — each iteration re-reads the columns
    # with a per-iteration additive nudge so XLA cannot fold the loop into
    # one pass.
    iters = int(os.environ.get("BENCH_ITERS", 8))

    @jax.jit
    def step_n(b):
        vdata = b.column("v").data

        def body(carry, i):
            bi = ColumnBatch(
                b.names,
                [b.column("g"),
                 Column(vdata + i.astype(vdata.dtype) * 1e-30, None,
                        LType.FLOAT32)], b.sel, b.num_rows)
            out = step(bi)
            return jax.tree.map(lambda c, o: c + o.astype(c.dtype),
                                carry, out[:-1]), None

        shapes = jax.eval_shape(step, b)[:-1]     # no kernel execution
        init = jax.tree.map(lambda o: jnp.zeros(o.shape, jnp.float64)
                            if o.dtype.kind == "f" else
                            jnp.zeros(o.shape, o.dtype), shapes)
        acc, _ = jax.lax.scan(body, init, jnp.arange(iters))
        return acc

    def fetch(r):
        return [np.asarray(x) for x in jax.tree.leaves(r)]

    out = step(batch)
    fetch(out)                                    # compile + warm single step
    fetch(step_n(batch))                          # compile + warm scan
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fetch(step_n(batch))
        times.append(time.perf_counter() - t0)
    dev_time = float(np.median(times)) / iters
    dev_rps = n_rows / dev_time

    # ---- CPU Arrow baseline (pyarrow compute = the Acero stand-in)
    import pyarrow as pa
    import pyarrow.compute as pc

    t = pa.table({"g": g_np, "v": v_np})
    bas_times = []
    for _ in range(max(2, repeats // 2)):
        t0 = time.perf_counter()
        f = t.filter(pc.greater(pc.add(pc.multiply(t.column("v"),
                                                   pa.scalar(2.0, pa.float32())),
                                       pa.scalar(1.0, pa.float32())),
                                pa.scalar(0.5, pa.float32())))
        f.group_by("g").aggregate([("v", "count"), ("v", "sum"),
                                   ("v", "mean"), ("v", "min")])
        bas_times.append(time.perf_counter() - t0)
    bas_time = float(np.median(bas_times))
    bas_rps = n_rows / bas_time

    # cross-check correctness against numpy on a sample
    mask = (v_np.astype(np.float64) * 2 + 1) > 0.5  # expr compiler promotes to f64
    want_n = np.bincount(g_np[mask], minlength=n_groups)
    got_n = np.asarray(out[1])[:n_groups]   # slot n_groups is the NULL-key slot
    assert np.array_equal(want_n, got_n), "benchmark kernel wrong"

    result = {
        "metric": f"filter+GROUP BY rows/sec ({n_rows / 1e6:.0f}M rows, "
                  f"{platform})",
        "value": round(dev_rps, 1),
        "unit": "rows/sec",
        "vs_baseline": round(dev_rps / bas_rps, 3),
        "platform": platform,
        "rows": n_rows,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }
    return result


def run_mixed_bench() -> dict:
    """Mixed read/write steady state (the capacity-bucketing headline):
    one SELECT repeated across interleaved single-row INSERTs.

    Without capacity buckets every insert changes the scan's device shape,
    so the cached plan retraces+recompiles per statement and compile time
    dominates; with buckets (the default) the executable is reused until a
    power-of-two boundary.  Reports steady-state scanned rows/sec with
    bucketing on, the per-query speedup over bucketing off, and the retrace
    counts observed in each phase."""
    import pyarrow as pa

    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.utils import metrics as _m
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    n_rows = int(os.environ.get("BENCH_MIXED_ROWS", 60_000))
    iters = int(os.environ.get("BENCH_MIXED_ITERS", 24))
    off_iters = int(os.environ.get("BENCH_MIXED_OFF_ITERS", 6))
    rng = np.random.default_rng(11)
    base = pa.table({
        "id": np.arange(n_rows, dtype=np.int64),
        "g": rng.integers(0, 16, n_rows).astype(np.int64),
        "v": rng.normal(size=n_rows).astype(np.float64),
    })
    q = ("SELECT g, COUNT(*) AS n, SUM(v) AS sv FROM mx "
         "WHERE v > 0.25 GROUP BY g ORDER BY g")

    def phase(bucketing: bool, its: int):
        set_flag("batch_bucketing", bucketing)
        s = Session()
        s.execute("CREATE TABLE mx (id BIGINT, g BIGINT, v DOUBLE)")
        s.load_arrow("mx", base)
        s.execute(q)                      # plan + first compile
        s.execute(q)
        r0 = _m.xla_retraces.value
        t0 = time.perf_counter()
        for i in range(its):
            s.execute(f"INSERT INTO mx VALUES ({n_rows + i}, {i % 16}, 0.5)")
            s.execute(q)
        return (time.perf_counter() - t0, _m.xla_retraces.value - r0)

    prev = bool(FLAGS.batch_bucketing)
    try:
        on_dt, on_retraces = phase(True, iters)
        off_dt, off_retraces = phase(False, off_iters)
    finally:
        set_flag("batch_bucketing", prev)
    on_per_query = on_dt / iters
    off_per_query = off_dt / off_iters
    platform = None
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:                                   # noqa: BLE001
        pass
    return {
        "metric": f"mixed read/write steady-state rows/sec "
                  f"({n_rows / 1e3:.0f}k rows, {platform})",
        "value": round(n_rows * iters / on_dt, 1),
        "unit": "rows/sec",
        "vs_baseline": round(off_per_query / on_per_query, 3),
        "platform": platform,
        "rows": n_rows,
        "queries": iters,
        "per_query_ms": round(on_per_query * 1e3, 2),
        "per_query_ms_unbucketed": round(off_per_query * 1e3, 2),
        "xla_retraces_bucketed": on_retraces,
        "xla_retraces_unbucketed": off_retraces,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_point_bench() -> dict:
    """Point-query steady state (the auto-parameterization headline): ONE
    query shape, N distinct literals.

    With param_queries off every literal is a new SQL text — full parse ->
    plan -> trace -> XLA compile per query, the recompilation pathology of
    TCR-backed engines.  With the normalizer on (the default) the literals
    hoist into runtime params of one cached executable: compiles-per-query
    drops to ~0 and throughput is bounded by dispatch, not compilation.
    Reports steady-state queries/sec with parameterization on, the
    per-query speedup over parameterization off, and compiles-per-query
    observed in each phase."""
    import pyarrow as pa

    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.utils import metrics as _m
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    n_rows = int(os.environ.get("BENCH_POINT_ROWS", 100_000))
    n_q = int(os.environ.get("BENCH_POINT_QUERIES", 64))
    off_q = int(os.environ.get("BENCH_POINT_OFF_QUERIES", 8))
    rng = np.random.default_rng(7)
    base = pa.table({
        # deliberately NOT a primary key: the PK point read is served by
        # the host row tier without any device program — this measures the
        # compiled-plan path that every non-key predicate takes
        "id": np.arange(n_rows, dtype=np.int64),
        "v": rng.normal(size=n_rows).astype(np.float64),
    })

    def phase(flag_on: bool, its: int):
        set_flag("param_queries", flag_on)
        s = Session()
        s.execute("CREATE TABLE pt (id BIGINT, v DOUBLE)")
        s.load_arrow("pt", base)
        s.query("SELECT v FROM pt WHERE id = 0")      # plan + first compile
        r0 = _m.xla_retraces.value
        t0 = time.perf_counter()
        for i in range(its):
            s.query(f"SELECT v FROM pt WHERE id = {1 + (i * 9173) % n_rows}")
        return (time.perf_counter() - t0, _m.xla_retraces.value - r0)

    prev = bool(FLAGS.param_queries)
    try:
        on_dt, on_re = phase(True, n_q)
        off_dt, off_re = phase(False, off_q)
    finally:
        set_flag("param_queries", prev)
    on_per_query = on_dt / n_q
    off_per_query = off_dt / off_q
    platform = None
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:                                   # noqa: BLE001
        pass
    return {
        "metric": f"point-query steady-state queries/sec "
                  f"({n_rows / 1e3:.0f}k rows, {n_q} literals, {platform})",
        "value": round(n_q / on_dt, 1),
        "unit": "queries/sec",
        "vs_baseline": round(off_per_query / on_per_query, 3),
        "platform": platform,
        "rows": n_rows,
        "queries": n_q,
        "per_query_ms": round(on_per_query * 1e3, 2),
        "per_query_ms_unparameterized": round(off_per_query * 1e3, 2),
        "compiles_per_query": round(on_re / n_q, 3),
        "compiles_per_query_unparameterized": round(off_re / off_q, 3),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_trace_bench() -> dict:
    """Tracing overhead on the point-query steady state: the SAME cached
    one-shape workload as run_point_bench, measured with tracing=off then
    tracing=on (sampled default: every root kept).  The acceptance contract
    (docs/OBSERVABILITY.md): off <= 1% overhead (one flag check + the no-op
    span singleton), on <= 5% (a dozen host-side dict spans per query)."""
    import pyarrow as pa

    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.obs.trace import TRACER
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    n_rows = int(os.environ.get("BENCH_TRACE_ROWS", 100_000))
    n_q = int(os.environ.get("BENCH_TRACE_QUERIES", 64))
    rng = np.random.default_rng(13)
    base = pa.table({
        "id": np.arange(n_rows, dtype=np.int64),
        "v": rng.normal(size=n_rows).astype(np.float64),
    })

    def phase(tracing_on: bool, its: int) -> float:
        set_flag("tracing", tracing_on)
        s = Session()
        s.execute("CREATE TABLE tr (id BIGINT, v DOUBLE)")
        s.load_arrow("tr", base)
        s.query("SELECT v FROM tr WHERE id = 0")      # plan + first compile
        t0 = time.perf_counter()
        for i in range(its):
            s.query(f"SELECT v FROM tr WHERE id = {1 + (i * 9173) % n_rows}")
        return time.perf_counter() - t0

    prev = bool(FLAGS.tracing)
    try:
        off_dt = phase(False, n_q)
        on_dt = phase(True, n_q)
    finally:
        set_flag("tracing", prev)
        TRACER.clear()
    off_per, on_per = off_dt / n_q, on_dt / n_q
    platform = None
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:                                   # noqa: BLE001
        pass
    return {
        "metric": f"point-query steady state with tracing=on vs off "
                  f"({n_rows / 1e3:.0f}k rows, {n_q} queries, {platform})",
        "value": round(n_q / on_dt, 1),
        "unit": "queries/sec",
        # >1 means tracing made it slower; the CI-visible overhead guard
        "vs_baseline": round(on_per / off_per, 3),
        "overhead_pct": round((on_per / off_per - 1.0) * 100, 2),
        "platform": platform,
        "rows": n_rows,
        "queries": n_q,
        "per_query_ms_tracing_on": round(on_per * 1e3, 2),
        "per_query_ms_tracing_off": round(off_per * 1e3, 2),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_progress_bench() -> dict:
    """Introspection overhead on the point-query steady state: the SAME
    cached one-shape workload as run_point_bench, measured with progress
    tracking off (no-op singleton, one flag check per beat site) and then
    with progress tracking on PLUS a live query watchdog scanning the
    registry in the background.  The acceptance contract
    (docs/OBSERVABILITY.md): on <= 1% overhead — every beat is a few
    host-side attribute writes at span seams already paid for, and the
    watchdog runs off the query path."""
    import pyarrow as pa

    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    n_rows = int(os.environ.get("BENCH_PROGRESS_ROWS", 100_000))
    n_q = int(os.environ.get("BENCH_PROGRESS_QUERIES", 64))
    rng = np.random.default_rng(29)
    base = pa.table({
        "id": np.arange(n_rows, dtype=np.int64),
        "v": rng.normal(size=n_rows).astype(np.float64),
    })

    def phase(tracking_on: bool, its: int) -> float:
        set_flag("progress_tracking", tracking_on)
        s = Session()
        s.execute("CREATE TABLE pr (id BIGINT, v DOUBLE)")
        s.load_arrow("pr", base)
        if tracking_on:
            s.db.watchdog.start()
        s.query("SELECT v FROM pr WHERE id = 0")      # plan + first compile
        t0 = time.perf_counter()
        try:
            for i in range(its):
                s.query(f"SELECT v FROM pr "
                        f"WHERE id = {1 + (i * 9173) % n_rows}")
            return time.perf_counter() - t0
        finally:
            if tracking_on:
                s.db.watchdog.stop()

    prev = bool(FLAGS.progress_tracking)
    try:
        off_dt = phase(False, n_q)
        on_dt = phase(True, n_q)
    finally:
        set_flag("progress_tracking", prev)
    off_per, on_per = off_dt / n_q, on_dt / n_q
    platform = None
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:                                   # noqa: BLE001
        pass
    return {
        "metric": f"point-query steady state with progress tracking + "
                  f"watchdog on vs off ({n_rows / 1e3:.0f}k rows, "
                  f"{n_q} queries, {platform})",
        "value": round(n_q / on_dt, 1),
        "unit": "queries/sec",
        # >1 means introspection made it slower; contract: <= 1.01
        "vs_baseline": round(on_per / off_per, 3),
        "overhead_pct": round((on_per / off_per - 1.0) * 100, 2),
        "platform": platform,
        "rows": n_rows,
        "queries": n_q,
        "per_query_ms_progress_on": round(on_per * 1e3, 2),
        "per_query_ms_progress_off": round(off_per * 1e3, 2),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_guard_bench() -> dict:
    """Runtime-guard overhead on the point-query steady state: the SAME
    cached one-shape workload measured with ``debug_guards=off`` (plain C
    locks, plain attributes) and with ``debug_guards=disallow`` — which
    arms the GuardedLock rank bookkeeping AND the lockset-witness data
    descriptors over every enrolled class's owned attributes
    (analysis/runtime.py).  The contract (docs/LINT.md): the assertions
    are a diagnostic mode, but they must stay cheap enough to leave on in
    stress/chaos CI — single-digit-percent, not multiples."""
    import pyarrow as pa

    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    n_rows = int(os.environ.get("BENCH_GUARD_ROWS", 100_000))
    n_q = int(os.environ.get("BENCH_GUARD_QUERIES", 64))
    rng = np.random.default_rng(31)
    base = pa.table({
        "id": np.arange(n_rows, dtype=np.int64),
        "v": rng.normal(size=n_rows).astype(np.float64),
    })

    def phase(guards_on: bool, its: int) -> float:
        set_flag("debug_guards", "disallow" if guards_on else "off")
        s = Session()
        s.execute("CREATE TABLE gd (id BIGINT, v DOUBLE)")
        s.load_arrow("gd", base)
        s.query("SELECT v FROM gd WHERE id = 0")      # plan + first compile
        t0 = time.perf_counter()
        for i in range(its):
            s.query(f"SELECT v FROM gd "
                    f"WHERE id = {1 + (i * 9173) % n_rows}")
        return time.perf_counter() - t0

    prev = str(FLAGS.debug_guards)
    try:
        off_dt = phase(False, n_q)
        on_dt = phase(True, n_q)
    finally:
        set_flag("debug_guards", prev)
    off_per, on_per = off_dt / n_q, on_dt / n_q
    platform = None
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:                                   # noqa: BLE001
        pass
    return {
        "metric": f"point-query steady state with debug_guards=disallow "
                  f"(lockset witness + rank asserts) vs off "
                  f"({n_rows / 1e3:.0f}k rows, {n_q} queries, {platform})",
        "value": round(n_q / on_dt, 1),
        "unit": "queries/sec",
        # >1 means arming the guards made it slower
        "vs_baseline": round(on_per / off_per, 3),
        "overhead_pct": round((on_per / off_per - 1.0) * 100, 2),
        "platform": platform,
        "rows": n_rows,
        "queries": n_q,
        "per_query_ms_guards_on": round(on_per * 1e3, 2),
        "per_query_ms_guards_off": round(off_per * 1e3, 2),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_telemetry_bench() -> dict:
    """Telemetry-plane overhead guard (eighth JSON line): the point-query
    steady state with the fleet telemetry poller scraping two REAL
    in-process store daemons in the background, vs no poller.

    The poller runs off the query path (its own thread, RPC + merge work
    only), so the acceptance contract (docs/OBSERVABILITY.md) pins the
    steady-state overhead at <= 1%.  Also reports one full fleet scrape
    round-trip — poll both daemons, merge bucket-wise, render Prometheus
    text — the latency a dashboard refresh actually pays."""
    import pyarrow as pa

    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.obs.telemetry import Telemetry
    from baikaldb_tpu.server.store_server import StoreServer, schema_to_wire
    from baikaldb_tpu.types import Field, LType, Schema
    from baikaldb_tpu.utils.net import RpcClient

    n_rows = int(os.environ.get("BENCH_TELEMETRY_ROWS", 100_000))
    n_q = int(os.environ.get("BENCH_TELEMETRY_QUERIES", 64))
    poll_s = float(os.environ.get("BENCH_TELEMETRY_POLL_S", 0.05))
    rng = np.random.default_rng(23)
    base = pa.table({
        "id": np.arange(n_rows, dtype=np.int64),
        "v": rng.normal(size=n_rows).astype(np.float64),
    })
    sch = Schema((Field("id", LType.INT64, False),
                  Field("v", LType.FLOAT64, True)))
    stores = []
    for sid in (1, 2):
        st = StoreServer(sid, "127.0.0.1:0", tick_interval=0.02)
        st.address = f"127.0.0.1:{st.rpc.port}"
        st.start()
        stores.append(st)
    try:
        for i, st in enumerate(stores, 1):
            c = RpcClient(st.address)
            c.call("create_region", region_id=i,
                   peers=[[st.store_id, st.address]],
                   fields=schema_to_wire(sch), key_columns=["id"])
            c.close()

        def phase(poller_on: bool) -> float:
            s = Session()
            s.execute("CREATE TABLE tm (id BIGINT, v DOUBLE)")
            s.load_arrow("tm", base)
            tel = s.db.telemetry
            if poller_on:
                for st in stores:
                    tel.register(st.address)
                tel.start(interval_s=poll_s)
            s.query("SELECT v FROM tm WHERE id = 0")    # first compile
            t0 = time.perf_counter()
            try:
                for i in range(n_q):
                    s.query(f"SELECT v FROM tm "
                            f"WHERE id = {1 + (i * 9173) % n_rows}")
                return time.perf_counter() - t0
            finally:
                if poller_on:
                    tel.stop()

        off_dt = phase(False)
        on_dt = phase(True)
        # one cold fleet scrape round-trip: poll + merge + render
        tel = Telemetry(device_gauges=False)
        for st in stores:
            tel.register(st.address)
        t0 = time.perf_counter()
        text = tel.prometheus()
        scrape_ms = (time.perf_counter() - t0) * 1e3
    finally:
        for st in stores:
            st.stop()
    off_per, on_per = off_dt / n_q, on_dt / n_q
    platform = None
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:                                   # noqa: BLE001
        pass
    return {
        "metric": f"point-query steady state with telemetry poller on vs "
                  f"off ({n_rows / 1e3:.0f}k rows, {n_q} queries, 2 store "
                  f"daemons, {platform})",
        "value": round(n_q / on_dt, 1),
        "unit": "queries/sec",
        # >1 means the poller made queries slower; contract: <= 1.01
        "vs_baseline": round(on_per / off_per, 3),
        "overhead_pct": round((on_per / off_per - 1.0) * 100, 2),
        "platform": platform,
        "rows": n_rows,
        "queries": n_q,
        "poll_interval_s": poll_s,
        "per_query_ms_poller_on": round(on_per * 1e3, 2),
        "per_query_ms_poller_off": round(off_per * 1e3, 2),
        "scrape_roundtrip_ms": round(scrape_ms, 2),
        "scrape_bytes": len(text),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_chaos_bench() -> dict:
    """Chaos machinery overhead + a seeded latency-injection run.

    Part 1 (the headline): the SAME cached point-query steady state as
    run_point_bench, measured with the chaos machinery fully disabled and
    then with chaos_enable=1 but NO failpoint armed — i.e. every wired
    site evaluates its registry lookup and misses.  The acceptance
    contract (docs/CHAOS.md): disabled overhead <= 1% (one module-bool
    read per site; no distributed seam is even on this path), enabled-
    but-unarmed stays within a few percent.

    Part 2: one seeded rpc_chaos scenario (in-process store daemons,
    store.handler latency + rpc.recv response drops + a leader crash)
    reporting retry counts, dedupe hits, and write-latency p99."""
    import pyarrow as pa

    from baikaldb_tpu.chaos import failpoint
    from baikaldb_tpu.chaos.scenarios import run_scenario
    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.utils.flags import set_flag

    n_rows = int(os.environ.get("BENCH_CHAOS_ROWS", 100_000))
    n_q = int(os.environ.get("BENCH_CHAOS_QUERIES", 64))
    rng = np.random.default_rng(17)
    base = pa.table({
        "id": np.arange(n_rows, dtype=np.int64),
        "v": rng.normal(size=n_rows).astype(np.float64),
    })

    def phase(chaos_on: bool, its: int) -> float:
        failpoint.clear_all()
        set_flag("chaos_enable", chaos_on)
        s = Session()
        s.execute("CREATE TABLE ch (id BIGINT, v DOUBLE)")
        s.load_arrow("ch", base)
        s.query("SELECT v FROM ch WHERE id = 0")      # plan + first compile
        t0 = time.perf_counter()
        for i in range(its):
            s.query(f"SELECT v FROM ch WHERE id = {1 + (i * 9173) % n_rows}")
        return time.perf_counter() - t0

    try:
        off_dt = phase(False, n_q)
        on_dt = phase(True, n_q)
    finally:
        failpoint.clear_all()
        set_flag("chaos_enable", False)
    off_per, on_per = off_dt / n_q, on_dt / n_q
    chaos_run = run_scenario(
        "rpc_chaos", int(os.environ.get("BENCH_CHAOS_SEED", 7)),
        writes=int(os.environ.get("BENCH_CHAOS_WRITES", 12)))
    platform = None
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:                                   # noqa: BLE001
        pass
    return {
        "metric": f"point-query steady state with chaos machinery "
                  f"compiled in but disabled "
                  f"({n_rows / 1e3:.0f}k rows, {n_q} queries, {platform})",
        "value": round(n_q / off_dt, 1),
        "unit": "queries/sec",
        # >1 means the enabled-but-unarmed machinery made it slower
        "vs_baseline": round(on_per / off_per, 3),
        "overhead_pct": round((on_per / off_per - 1.0) * 100, 2),
        "platform": platform,
        "rows": n_rows,
        "queries": n_q,
        "per_query_ms_chaos_off": round(off_per * 1e3, 2),
        "per_query_ms_chaos_enabled_unarmed": round(on_per * 1e3, 2),
        "chaos_latency_run": {
            k: chaos_run.get(k)
            for k in ("seed", "ok", "writes", "faults", "rpc_retries",
                      "rpc_dedup_hits", "rpc_timeouts", "p50_ms", "p99_ms",
                      "state_digest")},
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_elastic_bench() -> dict:
    """Elastic-regions line: SQL write latency + throughput while the
    fleet executes a forced live split AND a forced learner-first
    migration on the serving region, against the identical workload at
    steady state (fresh fleet, no topology change).  Both runs go
    through the in-process raft fleet (LocalBus), so the numbers are
    deterministic apart from host timing.  The hard contract gated by
    tools/bench_regress.py: zero lost writes, the split and the
    migration both actually happened (counters), and the elastic-phase
    write p99 stays within a documented multiple of steady state."""
    from baikaldb_tpu.exec.session import Database, Session
    from baikaldb_tpu.meta.service import MetaService
    from baikaldb_tpu.raft.fleet import StoreFleet
    from baikaldb_tpu.utils import metrics as _m

    n_writes = int(os.environ.get("BENCH_ELASTIC_WRITES", 200))

    def mk():
        fleet = StoreFleet(MetaService(peer_count=3),
                           [f"eb{i + 1}:1" for i in range(4)], seed=29)
        s = Session(Database(fleet=fleet))
        s.execute("CREATE DATABASE eb")
        s.execute("USE eb")
        s.execute("CREATE TABLE t (k BIGINT, v BIGINT, PRIMARY KEY (k))")
        return fleet, s

    def pq(lat: list, q: float) -> float:
        srt = sorted(lat)
        return round(srt[min(len(srt) - 1, int(q * (len(srt) - 1) + 0.5))],
                     3)

    # steady state: same writes, nothing moving
    _fleet, s = mk()
    lat_steady: list[float] = []
    t0 = time.perf_counter()
    for i in range(n_writes):
        w0 = time.perf_counter()
        s.execute(f"INSERT INTO t VALUES ({i}, {i})")
        lat_steady.append((time.perf_counter() - w0) * 1e3)
    steady_dt = time.perf_counter() - t0

    # elastic phase: the same write stream keeps flowing while the
    # serving region live-splits and a replica live-migrates off the
    # leader's store (hooks land writes inside every phase of both)
    fleet, s = mk()
    tier = fleet.row_tiers["eb.t"]
    lat_el: list[float] = []
    issued = 0

    def put(n: int):
        nonlocal issued
        for _ in range(n):
            k = issued
            issued += 1
            w0 = time.perf_counter()
            s.execute(f"INSERT INTO t VALUES ({k}, {k})")
            lat_el.append((time.perf_counter() - w0) * 1e3)

    splits0 = _m.region_splits.value
    migr0 = _m.region_migrations.value
    hand0 = _m.region_handoff_ms.stats()["count"]
    t0 = time.perf_counter()
    put(n_writes // 2)
    rid = tier.metas[0].region_id
    tier.split_region_online(rid, chaos_hook=lambda ph: put(4))
    rm = fleet.meta.regions[rid]
    target = next(a for a in sorted(fleet.addresses)
                  if a not in rm.peers)
    fleet.migrate_replica(rid, rm.leader, target,
                          chaos_hook=lambda ph: put(2))
    put(max(0, n_writes - issued))
    el_dt = time.perf_counter() - t0
    rows = {r["k"] for r in s.query("SELECT k FROM t")}
    hstats = _m.region_handoff_ms.stats()
    return {
        "metric": f"elastic regions: write p99 + q/s during forced live "
                  f"split + migration vs steady state "
                  f"({n_writes} writes, 4 stores)",
        "value": round(issued / el_dt, 1),
        "unit": "writes/sec",
        # <1 means the elastic phase was slower than steady state
        "vs_baseline": round((issued / el_dt) / (n_writes / steady_dt), 3),
        "steady_writes_per_sec": round(n_writes / steady_dt, 1),
        "steady_p50_ms": pq(lat_steady, 0.50),
        "steady_p99_ms": pq(lat_steady, 0.99),
        "elastic_p50_ms": pq(lat_el, 0.50),
        "elastic_p99_ms": pq(lat_el, 0.99),
        "splits": _m.region_splits.value - splits0,
        "migrations": _m.region_migrations.value - migr0,
        "handoffs": hstats["count"] - hand0,
        "handoff_p99_ms": hstats["p99_ms"],
        "lost_writes": issued - len(rows),
        "regions": len(tier.metas),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_concurrency_bench() -> dict:
    """Concurrent point-query scaling (the batched-dispatch headline):
    q/s and p99 vs client count, dispatcher on vs off.

    Every client thread owns a Session on ONE shared Database and replays
    the same parameterized point-query shape with distinct literals — the
    workload PR 3 made compile-free and this PR makes dispatch-free: with
    ``batch_dispatch`` on, concurrent queries hitting the same plan-cache
    group coalesce into one vmapped device batch per combiner tick, so
    throughput scales with client count instead of thread count.  Off, each
    thread pays its own device dispatch + egress + GIL round-trip."""
    import threading

    import pyarrow as pa

    from baikaldb_tpu.exec.session import Database, Session
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    n_rows = int(os.environ.get("BENCH_CONC_ROWS", 20_000))
    counts = [int(x) for x in
              os.environ.get("BENCH_CONC_CLIENTS", "1,8,64,256").split(",")]
    per = int(os.environ.get("BENCH_CONC_QUERIES", 24))
    rng = np.random.default_rng(23)
    base = pa.table({
        # NOT a primary key: PK point reads are host-tier lookups; this
        # drives the compiled-plan path every non-key predicate takes
        "id": np.arange(n_rows, dtype=np.int64),
        "v": rng.normal(size=n_rows).astype(np.float64),
    })

    def phase(dispatch_on: bool, n_clients: int):
        set_flag("batch_dispatch", dispatch_on)
        db = Database()
        boot = Session(db)
        boot.execute("CREATE TABLE cq (id BIGINT, v DOUBLE)")
        boot.load_arrow("cq", base)
        boot.query("SELECT v FROM cq WHERE id = 0")
        sessions = [Session(db) for _ in range(n_clients)]
        # rebound per round below; the worker closure reads the latest
        start = threading.Barrier(n_clients)
        lats: list[list[float]] = [[] for _ in range(n_clients)]

        def worker(tid: int, s: Session, record: bool):
            start.wait()
            for q in range(per):
                i = 2 + ((tid * per + q) * 9173) % (n_rows - 2)
                q0 = time.perf_counter()
                s.query(f"SELECT v FROM cq WHERE id = {i}")
                if record:
                    lats[tid].append((time.perf_counter() - q0) * 1e3)

        # concurrent warmup: two full untimed rounds — the off path compiles
        # one executable per session, the on path compiles the dispatcher's
        # pow2-padded batched executables for the group sizes this client
        # count actually forms.  Steady state is the metric; first-compile
        # cost has its own telemetry (metrics.compile_ms)
        best = None
        for measured in (False, False, True, True):
            start = threading.Barrier(n_clients)
            lats = [[] for _ in range(n_clients)]
            ts = [threading.Thread(target=worker, args=(i, s, measured))
                  for i, s in enumerate(sessions)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.perf_counter() - t0
            if not measured:
                continue
            flat = sorted(x for ls in lats for x in ls)

            def q(p):
                return round(flat[min(len(flat) - 1,
                                      int(p * (len(flat) - 1) + 0.5))], 3)
            r = {"qps": round(n_clients * per / dt, 1),
                 "p50_ms": q(0.50), "p99_ms": q(0.99)}
            if best is None or r["qps"] > best["qps"]:
                best = r            # best-of-2: a stray GC/compile round
                #                     must not stand in for steady state
        return best

    prev = bool(FLAGS.batch_dispatch)
    curve: dict[str, dict] = {}
    try:
        for n in counts:
            off = phase(False, n)
            on = phase(True, n)
            curve[str(n)] = {
                "clients": n,
                "qps_on": on["qps"], "qps_off": off["qps"],
                "speedup": round(on["qps"] / max(off["qps"], 1e-9), 3),
                "p50_ms_on": on["p50_ms"], "p50_ms_off": off["p50_ms"],
                "p99_ms_on": on["p99_ms"], "p99_ms_off": off["p99_ms"],
            }
    finally:
        set_flag("batch_dispatch", prev)
    head = curve.get("64") or curve[str(counts[-1])]
    solo = curve.get("1")
    platform = None
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:                                   # noqa: BLE001
        pass
    return {
        "metric": f"concurrent point-query q/s at {head['clients']} clients"
                  f", dispatcher on vs off ({n_rows / 1e3:.0f}k rows, "
                  f"{platform})",
        "value": head["qps_on"],
        "unit": "queries/sec",
        "vs_baseline": head["speedup"],
        "platform": platform,
        "rows": n_rows,
        "queries_per_client": per,
        "curve": curve,
        # acceptance guard: the inline bypass must keep the idle-server
        # single-client p50 within noise of the dispatcher-off path
        "single_client_p50_regression_pct": None if solo is None else round(
            (solo["p50_ms_on"] / max(solo["p50_ms_off"], 1e-9) - 1.0) * 100,
            2),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_multiway_bench() -> dict:
    """3-table shared-key join at scale, chained-binary vs fused multiway
    exchange (MPP exchange v2): same SQL, same mesh, the only difference is
    FLAGS.multiway_join — off pays one build/probe + shuffle round per
    binary join (the intermediate result re-shuffles), on repartitions
    every input ONCE and probes all build sides in a single fused pass.
    Reports warm wall-clock both ways, shuffle rounds per execution
    (counted, not inferred), and compile counts.

    Runs on a mesh (the caller arranges >= 2 devices); the fact table has
    BENCH_MULTIWAY_ROWS rows (default 4M), each dim BENCH_MULTIWAY_ROWS/4
    unique keys, so the join output stays linear in the fact size."""
    import pyarrow as pa

    import baikaldb_tpu.plan.distribute  # noqa: F401 — defines the flag
    from baikaldb_tpu.exec.session import Session
    from baikaldb_tpu.parallel.mesh import make_mesh
    from baikaldb_tpu.utils import metrics
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    import jax

    n_rows = int(os.environ.get("BENCH_MULTIWAY_ROWS", 4_000_000))
    repeats = int(os.environ.get("BENCH_MULTIWAY_REPEATS", 2))
    n_dim = max(16, n_rows // 4)
    platform = jax.devices()[0].platform
    mesh = make_mesh()
    n_dev = int(mesh.devices.size)

    rng = np.random.default_rng(11)
    s = Session(mesh=mesh)
    s.execute("CREATE TABLE fact (id BIGINT, k BIGINT, val DOUBLE)")
    s.load_arrow("fact", pa.table({
        "id": np.arange(n_rows, dtype=np.int64),
        "k": rng.integers(0, n_dim, n_rows).astype(np.int64),
        "val": rng.normal(size=n_rows).astype(np.float64)}))
    s.execute("CREATE TABLE d1 (k BIGINT, w DOUBLE)")
    s.load_arrow("d1", pa.table({
        "k": np.arange(n_dim, dtype=np.int64),
        "w": rng.normal(size=n_dim).astype(np.float64)}))
    s.execute("CREATE TABLE d2 (k BIGINT, u DOUBLE)")
    s.load_arrow("d2", pa.table({
        "k": np.arange(n_dim, dtype=np.int64),
        "u": rng.normal(size=n_dim).astype(np.float64)}))

    sql = ("SELECT SUM(f.val * d1.w + d2.u) s3 FROM fact f "
           "JOIN d1 ON f.k = d1.k JOIN d2 ON f.k = d2.k")

    import baikaldb_tpu.plan.distribute as dist_mod

    prev = bool(FLAGS.multiway_join)
    prev_bcast = dist_mod.BROADCAST_ROWS
    # the exchange is what this line measures: force the repartition path
    # at every BENCH_MULTIWAY_ROWS scale (at the 4M default the dims exceed
    # the broadcast threshold anyway)
    dist_mod.BROADCAST_ROWS = 0
    out: dict = {}
    try:
        for label, on in (("chained", False), ("multiway", True)):
            set_flag("multiway_join", on)
            c0 = metrics.xla_retraces.value
            t0 = time.perf_counter()
            first_res = s.query(sql)
            first = time.perf_counter() - t0
            compiles = metrics.xla_retraces.value - c0
            warm, rounds = [], 0
            for _ in range(repeats):
                r0 = metrics.shuffle_rounds.value
                t0 = time.perf_counter()
                res = s.query(sql)
                warm.append(time.perf_counter() - t0)
                rounds = metrics.shuffle_rounds.value - r0
            out[label] = {
                "warm_ms": round(min(warm) * 1e3, 1),
                "first_ms": round(first * 1e3, 1),
                "shuffle_rounds": rounds,
                "compiles": compiles,
                "result": round(float(first_res[0]["s3"]), 3),
            }
            # a different SQL text per flag value is NOT what we measure:
            # drop the cached plans so each arm plans + compiles its own
            s._plan_cache.clear()
    finally:
        set_flag("multiway_join", prev)
        dist_mod.BROADCAST_ROWS = prev_bcast
    assert out["chained"]["result"] == out["multiway"]["result"], \
        "multiway result diverged from chained"
    speedup = out["chained"]["warm_ms"] / max(out["multiway"]["warm_ms"],
                                              1e-9)
    return {
        "metric": f"3-table shared-key join, multiway vs chained exchange "
                  f"({n_rows / 1e6:.1f}M rows, {platform}, mesh={n_dev})",
        "value": out["multiway"]["warm_ms"],
        "unit": "ms",
        "vs_baseline": round(speedup, 3),
        "platform": platform,
        "rows": n_rows,
        "mesh": n_dev,
        "chained": out["chained"],
        "multiway": out["multiway"],
        "shuffle_rounds_saved":
            out["chained"]["shuffle_rounds"] - out["multiway"]["shuffle_rounds"],
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def _cpu_child(code: str, env_extra: dict, timeout: float) -> dict:
    """Run ``python -c code`` from the repo root in a child pinned to the CPU
    — this process may hold the chip, and a chip belongs to one process —
    and return the JSON object on its last stdout line.  Whatever such a
    child measures is a CPU number."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO, env=env, timeout=timeout)
    lines = [ln for ln in r.stdout.strip().splitlines() if ln]
    if r.returncode != 0 or not lines:
        raise RuntimeError(f"cpu child failed ({code}): "
                           f"{(r.stderr or 'no output').strip()[-400:]}")
    return json.loads(lines[-1])


_COLD_QUERIES = [
    "SELECT g, COUNT(*) n, SUM(v) sv FROM ct WHERE v > 0.1 "
    "GROUP BY g ORDER BY g",
    "SELECT COUNT(*) c, AVG(v) a FROM ct WHERE id < 2000",
    "SELECT g, MIN(v) mn, MAX(v) mx FROM ct WHERE v < 0.5 "
    "GROUP BY g ORDER BY g",
    "SELECT d.w, COUNT(*) n, SUM(ct.v) s FROM ct JOIN dt d ON ct.g = d.k "
    "GROUP BY d.w ORDER BY d.w",
    "SELECT COUNT(*) c FROM ct WHERE v > 0.25 AND g = 3",
]


def _coldstart_worker() -> None:
    """One simulated node lifetime (subprocess of run_coldstart_bench):
    build the store, run the query workload once (restart-to-steady pass —
    every executable either compiles or AOT-loads here), then measure
    steady state.  Config rides env BENCH_COLD_CFG; prints one JSON line:
    first-pass wall clock, compiles paid, AOT hits, steady per-query ms
    and a result digest (phases must be bit-identical)."""
    import hashlib

    cfg = json.loads(os.environ["BENCH_COLD_CFG"])
    # the XLA persistent cache is placed by the parent through
    # JAX_COMPILATION_CACHE_DIR (see _coldstart_phase); nothing sets a
    # directory in code
    from baikaldb_tpu.utils import compilecache  # defines the aot_* flags
    from baikaldb_tpu.utils.flags import set_flag

    set_flag("aot_cache", bool(cfg.get("aot")))
    if cfg.get("aot_dir"):
        set_flag("aot_cache_dir", cfg["aot_dir"])
    import pyarrow as pa

    from baikaldb_tpu.exec.session import Database, Session
    from baikaldb_tpu.utils import metrics as _m

    if cfg.get("meta"):
        Database.attach_aot_peer(cfg["meta"])
    n = int(cfg.get("rows", 40_000))
    rng = np.random.default_rng(5)
    s = Session()
    s.execute("CREATE TABLE ct (id BIGINT, g BIGINT, v DOUBLE)")
    s.load_arrow("ct", pa.table({
        "id": np.arange(n, dtype=np.int64),
        "g": rng.integers(0, 8, n).astype(np.int64),
        "v": rng.normal(size=n)}))
    s.execute("CREATE TABLE dt (k BIGINT, w BIGINT)")
    s.load_arrow("dt", pa.table({
        "k": np.arange(8, dtype=np.int64),
        "w": (np.arange(8, dtype=np.int64) % 3)}))
    r0 = _m.xla_retraces.value
    h0 = _m.aot_cache_hits.value
    t0 = time.perf_counter()
    results = [s.query(q) for q in _COLD_QUERIES]
    first_pass_s = time.perf_counter() - t0
    warm_compiles = _m.xla_retraces.value - r0
    steady = []
    for _ in range(int(cfg.get("steady_iters", 3))):
        t0 = time.perf_counter()
        for q in _COLD_QUERIES:
            s.query(q)
        steady.append((time.perf_counter() - t0) / len(_COLD_QUERIES))
    if cfg.get("drain"):
        compilecache.AOT.drain(300)
    digest = hashlib.md5(json.dumps(results, sort_keys=True,
                                    default=str).encode()).hexdigest()
    print(json.dumps({
        "first_pass_s": round(first_pass_s, 3),
        "warm_compiles": int(warm_compiles),
        "aot_hits": int(_m.aot_cache_hits.value - h0),
        "steady_ms": round(min(steady) * 1e3, 2),
        "digest": digest,
    }))


def _coldstart_phase(cfg: dict, timeout: float,
                     xla_dir: str | None = None) -> dict:
    """Run one node lifetime in a subprocess (a REAL restart: plan cache,
    jit caches and process state all die between phases).  ``xla_dir``
    places the child's XLA persistent cache unless the caller's environment
    already placed one."""
    env = {"BENCH_COLD_CFG": json.dumps(cfg)}
    if xla_dir and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = xla_dir
    return _cpu_child("import bench; bench._coldstart_worker()", env, timeout)


def run_coldstart_bench() -> dict:
    """Restart-to-full-throughput, cold vs warm-started (the AOT
    persistent executable cache headline).

    Four node lifetimes, each a real subprocess restart over the same
    deterministic store and query workload:

    - **cold**: aot_cache off, emptied XLA cache — every executable pays
      plan + trace + compile (today's restart behavior).
    - **warm_disk**: a seed node compiled + published to a local artifact
      dir; the restarted node AOT-loads every executable from disk —
      ``warm_compiles`` must be 0.
    - **warm_peer**: a fresh node with an EMPTY local dir warm-starts from
      a peer: meta-manifest lookup -> store daemon fetch -> deserialize.
    - **chaos rejoin**: the artifact-holding store daemon is crashed
      (hard-stop, the kill-9 analog) and a replacement on the same address
      + artifact dir rejoins; another fresh node still warm-starts from it
      at steady-state latency with ``warm_compiles=0``.

    Results must be bit-identical across all phases (digest-checked).

    The phases' XLA caches sit at fixed paths under .bench_cache/ (XLA's
    cache keys incorporate the path) and are emptied here; with
    JAX_COMPILATION_CACHE_DIR set every phase uses that directory instead
    and "cold" is only as cold as it is."""
    import shutil

    timeout = float(os.environ.get("BENCH_COLD_TIMEOUT", 600))
    rows = int(os.environ.get("BENCH_COLD_ROWS", 40_000))
    root = os.path.join(_REPO, ".bench_cache", "coldstart")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    out: dict = {}
    meta_srv = store = None
    try:
        base = {"rows": rows}
        out["cold"] = _coldstart_phase(
            dict(base, aot=0), timeout, os.path.join(root, "xla_cold"))
        # same-node restart: artifact dir AND xla cache survive on disk
        disk_dir = os.path.join(root, "disk")
        xla_disk = os.path.join(root, "xla_disk")
        out["seed"] = _coldstart_phase(
            dict(base, aot=1, aot_dir=disk_dir, drain=1), timeout, xla_disk)
        out["warm_disk"] = _coldstart_phase(
            dict(base, aot=1, aot_dir=disk_dir), timeout, xla_disk)

        from baikaldb_tpu.server.meta_server import MetaServer
        from baikaldb_tpu.server.store_server import StoreServer

        meta_srv = MetaServer("127.0.0.1:0")
        meta_srv.rpc.host = "127.0.0.1"
        meta_srv.start()
        meta_addr = f"127.0.0.1:{meta_srv.rpc.port}"
        blob_dir = os.path.join(root, "store_blobs")
        store = StoreServer(1, "127.0.0.1:0", meta_addr, aot_dir=blob_dir)
        store.address = f"127.0.0.1:{store.rpc.port}"
        store.start()
        # fleet warm start: fresh "machines" share the fleet-constant xla
        # path (cleared between phases — a new node has the same CONFIG,
        # empty DISK; its cache entries arrive via the peer fetch)
        xla_fleet = os.path.join(root, "xla_fleet")
        out["seed_peer"] = _coldstart_phase(
            dict(base, aot=1, aot_dir=os.path.join(root, "peer_seed"),
                 meta=meta_addr, drain=1), timeout, xla_fleet)
        shutil.rmtree(xla_fleet, ignore_errors=True)
        out["warm_peer"] = _coldstart_phase(
            dict(base, aot=1, aot_dir=os.path.join(root, "peer_fresh"),
                 meta=meta_addr), timeout, xla_fleet)
        # chaos: kill the artifact holder, let a replacement rejoin on the
        # same address over the same durable blob dir
        addr = store.address
        store.crash()
        for _ in range(50):     # the crashed daemon's listen socket may
            try:                # take a beat to release the port
                store = StoreServer(1, addr, meta_addr, aot_dir=blob_dir)
                break
            except OSError:
                time.sleep(0.1)
        else:
            raise RuntimeError(
                f"rejoining store daemon could not rebind {addr}")
        store.start()
        shutil.rmtree(xla_fleet, ignore_errors=True)
        out["chaos_rejoin"] = _coldstart_phase(
            dict(base, aot=1, aot_dir=os.path.join(root, "rejoin_fresh"),
                 meta=meta_addr), timeout, xla_fleet)
    finally:
        if store is not None:
            store.stop()
        if meta_srv is not None:
            meta_srv.stop()
        shutil.rmtree(root, ignore_errors=True)
    digests = {k: v["digest"] for k, v in out.items()}
    assert len(set(digests.values())) == 1, \
        f"cold-start phases not bit-identical: {digests}"
    cold_s = out["cold"]["first_pass_s"]
    disk_s = out["warm_disk"]["first_pass_s"]
    platform = "cpu"                      # phases pin JAX_PLATFORMS=cpu
    return {
        "metric": "restart-to-steady wall clock, cold vs AOT warm-start "
                  f"({len(_COLD_QUERIES)} queries, {rows / 1e3:.0f}k rows, "
                  f"{platform})",
        "value": round(disk_s * 1e3, 1),
        "unit": "ms",
        "vs_baseline": round(cold_s / max(disk_s, 1e-9), 3),
        "platform": platform,
        "rows": rows,
        "queries": len(_COLD_QUERIES),
        "cold": out["cold"],
        "warm_disk": out["warm_disk"],
        "warm_peer": out["warm_peer"],
        "chaos_rejoin": out["chaos_rejoin"],
        "restart_to_steady_ms": round(disk_s * 1e3, 1),
        "cold_compiles": out["cold"]["warm_compiles"],
        "bit_identical": True,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_stream_bench() -> dict:
    """Data-scale line: the out-of-core streaming scan (exec/streaming.py)
    vs the resident path over the SAME filter+GROUP BY SQL.  The table is
    many multiples of the per-chunk device budget (steady-state residency
    is TWO chunks), so the streamed number is the throughput the engine
    keeps once a table no longer fits on device — the resident path's
    ceiling is device memory, the streamed path's is staging bandwidth.
    Correctness is asserted in-line: streamed rows == resident rows
    (integer-valued doubles, so the fold order cannot move bits).  The
    per-query fold telemetry (chunks, skipped, bytes H2D, prefetch wait
    vs serial stage time — the overlap measurement) is parsed from
    EXPLAIN ANALYZE's ``-- stream:`` line; tools/bench_regress.py gates
    on it."""
    import re
    import shutil
    import tempfile

    import jax
    import pyarrow as pa

    from baikaldb_tpu.exec.session import Database, Session
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    platform = jax.devices()[0].platform
    n_rows = int(os.environ.get(
        "BENCH_STREAM_ROWS", 2_000_000 if platform != "cpu" else 262_144))
    chunk = int(os.environ.get("BENCH_STREAM_CHUNK", 1 << 15))
    repeats = int(os.environ.get("BENCH_REPEATS", 5))
    n_groups = 64

    ids = np.arange(n_rows, dtype=np.int64)
    g_np = (ids % n_groups).astype(np.int64)
    v_np = (ids % 251).astype(np.float64)

    prev = {k: getattr(FLAGS, k) for k in
            ("streaming_scan", "streaming_min_rows", "streaming_chunk_rows")}
    cold = tempfile.mkdtemp(prefix="bench_stream_")
    sql = ("SELECT g, COUNT(*) n, SUM(v) s, AVG(v) a, MIN(v) mn, MAX(v) mx "
           "FROM st WHERE v >= 1.0 GROUP BY g")
    try:
        set_flag("streaming_scan", True)
        set_flag("streaming_min_rows", 1)
        set_flag("streaming_chunk_rows", chunk)
        s = Session(Database(cold_dir=cold))
        s.execute("CREATE TABLE st (id BIGINT, g BIGINT, v DOUBLE, "
                  "PRIMARY KEY (id))")
        s.db.stores["default.st"].insert_arrow(
            pa.table({"id": ids, "g": g_np, "v": v_np}))

        def timed():
            t0 = time.perf_counter()
            out = s.query(sql)
            return time.perf_counter() - t0, out

        timed()             # compile + build/persist the chunk segments
        streamed = None
        st_times = []
        for _ in range(repeats):
            dt, streamed = timed()
            st_times.append(dt)
        ea = "\n".join(str(r[next(iter(r))]) for r in
                       s.query("EXPLAIN ANALYZE " + sql))
        m = re.search(r"-- stream: chunks=(\d+)/(\d+) skipped=(\d+) "
                      r"bytes_h2d=(\d+) prefetch_wait_ms=([\d.]+) "
                      r"stage_ms=([\d.]+) restarts=(\d+)", ea)
        if m is None:
            raise RuntimeError("EXPLAIN ANALYZE carried no -- stream: line "
                               "(the scan did not stream)")
        set_flag("streaming_scan", False)
        timed()             # compile the resident program
        resident = None
        rs_times = []
        for _ in range(repeats):
            dt, resident = timed()
            rs_times.append(dt)
        if streamed != resident:
            raise RuntimeError("streamed result diverged from resident")
    finally:
        for k, vv in prev.items():
            set_flag(k, vv)
        shutil.rmtree(cold, ignore_errors=True)
    st_dt = float(np.median(st_times))
    rs_dt = float(np.median(rs_times))
    return {
        "metric": f"out-of-core stream: filter+GROUP BY rows/sec folding "
                  f"{m.group(1)} x {chunk}-row chunks vs resident "
                  f"({n_rows / 1e6:.1f}M rows, {platform})",
        "value": round(n_rows / st_dt, 1),
        "unit": "rows/sec",
        # <1: the fold pays staging; the streamed path's win is CAPACITY
        # (2-chunk residency), not speed at sizes the resident path fits
        "vs_baseline": round(rs_dt / st_dt, 3),
        "platform": platform,
        "rows": n_rows,
        "chunk_rows": chunk,
        "table_over_chunk_budget_x": round(n_rows / (2.0 * chunk), 1),
        "resident_rows_per_sec": round(n_rows / rs_dt, 1),
        "chunks": int(m.group(1)),
        "chunks_total": int(m.group(2)),
        "skipped": int(m.group(3)),
        "bytes_h2d": int(m.group(4)),
        "prefetch_wait_ms": float(m.group(5)),
        "stage_ms": float(m.group(6)),
        "restarts": int(m.group(7)),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_fragment_bench() -> dict:
    """Pushed-down fragment line: the SAME scan->filter->GROUP BY SQL
    executed (a) pushed — per-region fragments dispatched to 3 in-process
    store daemons that fold locally and return only aggregate partials —
    vs (b) frontend-pulled — a cold frontend pulls whole regions over the
    wire and aggregates on the image path.  The table is pre-split into 3
    regions so the pushed dispatch actually fans out.  Deterministic
    gates for tools/bench_regress.py: fragments were dispatched, daemon
    scans saved real frontend ingress bytes (``bytes_saved`` > 0), and
    the steady repeat loop paid ZERO fragment warm compiles anywhere
    (frontend inline resends AND daemon-side compiles) — the
    content-hash artifact ladder must serve every re-dispatch."""
    from baikaldb_tpu.exec.session import Database, Session
    from baikaldb_tpu.server.meta_server import MetaServer
    from baikaldb_tpu.server.store_server import StoreServer
    from baikaldb_tpu.utils import metrics as _m
    from baikaldb_tpu.utils.flags import FLAGS, set_flag
    from baikaldb_tpu.utils.net import WIRE_STATS

    n_rows = int(os.environ.get("BENCH_FRAGMENT_ROWS", 6000))
    repeats = int(os.environ.get("BENCH_REPEATS", 5))
    pad = "x" * 64
    ddl = ("CREATE TABLE fb (id BIGINT NOT NULL, g BIGINT, v BIGINT, "
           "pad VARCHAR(80), PRIMARY KEY (id))")
    sql = ("SELECT g, COUNT(*) n, SUM(v) s, MIN(v) lo, MAX(v) hi FROM fb "
           "WHERE v >= 0 GROUP BY g ORDER BY g")
    prev = {k: getattr(FLAGS, k) for k in ("pushdown_reads",
                                           "fragment_pushdown")}
    meta = MetaServer("127.0.0.1:0")
    meta.start()
    stores = []
    try:
        meta_addr = f"127.0.0.1:{meta.rpc.port}"
        for sid in (1, 2, 3):
            st = StoreServer(sid, "127.0.0.1:0", meta_addr,
                             tick_interval=0.02)
            st.address = f"127.0.0.1:{st.rpc.port}"
            st.start()
            stores.append(st)
        writer = Session(Database(cluster=meta_addr))
        writer.db.telemetry.stop()
        writer.execute(ddl)
        for lo in range(0, n_rows, 200):
            vals = ", ".join(
                f"({i}, {i % 16}, {(i * 13) % 997}, '{pad}')"
                for i in range(lo, min(lo + 200, n_rows)))
            writer.execute(f"INSERT INTO fb VALUES {vals}")
        tier = writer.db.stores["default.fb"].replicated
        tier.split_region(0)
        tier.split_region(0)            # 3 regions: the dispatch fans out

        def fresh():
            s = Session(Database(cluster=meta_addr))
            s.db.telemetry.stop()
            s.execute(ddl)
            return s

        # pushed: daemons fold, partials cross the wire
        set_flag("pushdown_reads", "always")
        set_flag("fragment_pushdown", True)
        push_s = fresh()
        push_s.query(sql)               # publish + daemon warm-up
        d0 = _m.fragments_dispatched.value
        bs0 = _m.fragment_bytes_saved.value
        wc0 = _m.fragment_warm_compiles.value + \
            sum(st.metrics.counter("fragment_warm_compiles").value
                for st in stores)
        in0 = WIRE_STATS["recv_bytes"]
        t0 = time.perf_counter()
        pushed = None
        for _ in range(repeats):
            pushed = push_s.query(sql)
        push_dt = time.perf_counter() - t0
        push_ingress = WIRE_STATS["recv_bytes"] - in0
        dispatched = _m.fragments_dispatched.value - d0
        bytes_saved = _m.fragment_bytes_saved.value - bs0
        warm_compiles = (_m.fragment_warm_compiles.value +
                         sum(st.metrics.counter(
                             "fragment_warm_compiles").value
                             for st in stores)) - wc0
        # pulled: a COLD frontend funnels whole regions, aggregates itself
        set_flag("pushdown_reads", "off")
        fresh().query(sql)              # compile the image program once
        in0 = WIRE_STATS["recv_bytes"]
        t0 = time.perf_counter()
        pulled = None
        for _ in range(repeats):
            pulled = fresh().query(sql)     # cold: every query re-pulls
        pull_dt = time.perf_counter() - t0
        pull_ingress = WIRE_STATS["recv_bytes"] - in0
        if pushed != pulled:
            raise RuntimeError("pushed result diverged from pulled")
    finally:
        for k, v in prev.items():
            set_flag(k, v)
        for st in stores:
            st.stop()
        meta.stop()
    push_rps = n_rows * repeats / push_dt
    pull_rps = n_rows * repeats / pull_dt
    return {
        "metric": f"pushed fragments: scan->filter->GROUP BY rows/sec, "
                  f"3-daemon store-side execution vs frontend-pulled "
                  f"({n_rows} rows, 3 regions)",
        "value": round(push_rps, 1),
        "unit": "rows/sec",
        # >1: daemons fold in place, the frontend stops being the funnel
        "vs_baseline": round(push_rps / pull_rps, 3),
        "pulled_rows_per_sec": round(pull_rps, 1),
        "rows": n_rows,
        "regions": len(tier.regions),
        "repeats": repeats,
        "fragments_dispatched": int(dispatched),
        "bytes_saved": int(bytes_saved),
        "fragment_warm_compiles": int(warm_compiles),
        "pushed_ingress_bytes_per_query": round(push_ingress / repeats),
        "pulled_ingress_bytes_per_query": round(pull_ingress / repeats),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_snapshot_bench() -> dict:
    """Snapshot-reads line: a pinned analytical GROUP BY repeated while an
    OLTP write stream mutates the same table, against the two isolations
    (writes alone, analytics alone with mvcc off).  The hard contract
    gated by tools/bench_regress.py: ZERO lost writes, the pinned
    aggregate stayed bit-identical across every repetition under live
    inserts+updates, mvcc=0 replays the unpinned plan bit-identically on
    quiesced data (the off-switch really is free), and the mixed-phase
    write p99 stays within a documented multiple of write-only
    isolation."""
    from baikaldb_tpu.exec.session import Database, Session
    import baikaldb_tpu.storage.mvcc  # noqa: F401 — registers the flags
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    n_writes = int(os.environ.get("BENCH_SNAPSHOT_WRITES", 240))
    n_aggs = int(os.environ.get("BENCH_SNAPSHOT_AGGS", 12))
    seed_rows = 256
    agg_sql = ("SELECT g, COUNT(*) AS c, SUM(v) AS sv FROM t "
               "GROUP BY g ORDER BY g")

    def mk():
        s = Session(Database())
        s.execute("CREATE DATABASE sb")
        s.execute("USE sb")
        s.execute("CREATE TABLE t (k BIGINT, g BIGINT, v BIGINT)")
        vals = ", ".join(f"({i}, {i % 8}, {i * 3})"
                         for i in range(seed_rows))
        s.execute(f"INSERT INTO t VALUES {vals}")
        return s

    def pq(lat: list, q: float) -> float:
        srt = sorted(lat)
        return round(srt[min(len(srt) - 1, int(q * (len(srt) - 1) + 0.5))],
                     3)

    mvcc0 = bool(FLAGS.mvcc)
    try:
        set_flag("mvcc", 1)

        # write-only isolation: the same stream, no analytics running
        s = mk()
        issued = seed_rows
        lat_iso: list[float] = []
        for i in range(n_writes):
            k = issued
            issued += 1
            w0 = time.perf_counter()
            if i % 4 == 3:      # churn versions, not just append
                s.execute(f"UPDATE t SET v = v + 1 WHERE k = {k % 64}")
                issued -= 1
            else:
                s.execute(f"INSERT INTO t VALUES ({k}, {k % 8}, {k * 3})")
            lat_iso.append((time.perf_counter() - w0) * 1e3)

        # mixed phase, snapshot ON: pin once, interleave write bursts with
        # the pinned aggregate; every repetition must be bit-identical
        s = mk()
        s.execute("SET SNAPSHOT = 'now'")
        base = s.query(agg_sql)
        issued = seed_rows
        lat_mix: list[float] = []
        agg_on_ms: list[float] = []
        identical = 0
        burst = max(1, n_writes // n_aggs)
        for r in range(n_aggs):
            for i in range(burst):
                k = issued
                issued += 1
                w0 = time.perf_counter()
                if i % 4 == 3:
                    s.execute(f"UPDATE t SET v = v + 1 WHERE k = {k % 64}")
                    issued -= 1
                else:
                    s.execute(
                        f"INSERT INTO t VALUES ({k}, {k % 8}, {k * 3})")
                lat_mix.append((time.perf_counter() - w0) * 1e3)
            a0 = time.perf_counter()
            got = s.query(agg_sql)
            agg_on_ms.append((time.perf_counter() - a0) * 1e3)
            identical += int(got == base)
        s.execute("SET SNAPSHOT = 0")   # unpin BEFORE counting live rows
        lost = issued - s.query("SELECT COUNT(*) AS c FROM t")[0]["c"]

        # mixed phase, snapshot OFF: identical interleave, unpinned live
        # reads (results drift by design — only the wall clock is kept)
        set_flag("mvcc", 0)
        s = mk()
        issued = seed_rows
        agg_off_ms: list[float] = []
        for r in range(n_aggs):
            for i in range(burst):
                k = issued
                issued += 1
                if i % 4 == 3:
                    s.execute(f"UPDATE t SET v = v + 1 WHERE k = {k % 64}")
                    issued -= 1
                else:
                    s.execute(
                        f"INSERT INTO t VALUES ({k}, {k % 8}, {k * 3})")
            a0 = time.perf_counter()
            s.query(agg_sql)
            agg_off_ms.append((time.perf_counter() - a0) * 1e3)

        # off-switch bit-identity on quiesced data: mvcc=0 and mvcc=1
        # (unpinned, auto-pin at now) must agree to the bit
        off_rows = s.query(agg_sql)
        set_flag("mvcc", 1)
        off_identical = s.query(agg_sql) == off_rows
    finally:
        set_flag("mvcc", int(mvcc0))

    qps_on = n_aggs / (sum(agg_on_ms) / 1e3)
    qps_off = n_aggs / (sum(agg_off_ms) / 1e3)
    return {
        "metric": f"snapshot reads: pinned GROUP BY under live "
                  f"inserts+updates vs mvcc off ({n_writes} writes, "
                  f"{n_aggs} repetitions)",
        "value": round(qps_on, 1),
        "unit": "queries/sec",
        # <1 means the snapshot (versioned staging + sel-mask) costs
        "vs_baseline": round(qps_on / qps_off, 3),
        "analytics_snap_on_p50_ms": pq(agg_on_ms, 0.50),
        "analytics_snap_off_p50_ms": pq(agg_off_ms, 0.50),
        "write_p99_iso_ms": pq(lat_iso, 0.99),
        "write_p99_mixed_ms": pq(lat_mix, 0.99),
        "snap_rounds": n_aggs,
        "snap_identical_rounds": identical,
        "off_bit_identical": bool(off_identical),
        "lost_writes": int(lost),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def run_cdc_bench() -> dict:
    """CDC/rollup-view line: a GROUP BY dashboard read answered from an
    incrementally maintained materialized view while an
    insert/update/delete stream mutates the base table, vs the same read
    recomputed from base rows.  The hard contract gated by
    tools/bench_regress.py: ZERO lost change events (an audit
    subscription replays the full stream and every write is accounted
    for), a nonzero number of deltas actually folded (the view was
    maintained incrementally, not rebuilt), and at quiesce the view
    answer is BIT-IDENTICAL to the recompute — if it is not, this
    function refuses to emit timings and reports the divergence
    instead."""
    from baikaldb_tpu.exec.session import Database, Session
    import baikaldb_tpu.cdc.views  # noqa: F401 — registers the flags
    from baikaldb_tpu.utils.flags import FLAGS, set_flag

    n_writes = int(os.environ.get("BENCH_CDC_WRITES", 240))
    n_reads = int(os.environ.get("BENCH_CDC_READS", 24))
    seed_rows = int(os.environ.get("BENCH_CDC_SEED_ROWS", 4096))
    agg_sql = ("SELECT g, COUNT(*) AS c, SUM(v) AS sv, MIN(v) AS mn, "
               "MAX(v) AS mx FROM t GROUP BY g ORDER BY g")

    def pq(lat: list, q: float) -> float:
        srt = sorted(lat)
        return round(srt[min(len(srt) - 1, int(q * (len(srt) - 1) + 0.5))],
                     3)

    def mk():
        s = Session(Database())
        s.execute("CREATE DATABASE cb")
        s.execute("USE cb")
        s.execute("CREATE TABLE t (k BIGINT, g BIGINT, v BIGINT, "
                  "PRIMARY KEY (k))")
        vals = ", ".join(f"({i}, {i % 8}, {i * 3})"
                         for i in range(seed_rows))
        s.execute(f"INSERT INTO t VALUES {vals}")
        s.execute("CREATE MATERIALIZED VIEW mv AS SELECT g, COUNT(*), "
                  "SUM(v), MIN(v), MAX(v) FROM t GROUP BY g")
        s.query(agg_sql)    # untimed warmup: compile the read path once
        return s

    burst = max(1, n_writes // n_reads)

    def drive(s) -> tuple[list[float], list[int], int]:
        """The shared load: write bursts interleaved with timed GROUP BY
        reads.  Returns (read latencies, staleness samples, rows
        touched) — identical statement sequence for both phases, so the
        read timings differ only by who answers them."""
        mv = s.db.matviews.get("cb", "mv")
        issued = seed_rows
        applied = 0
        lat: list[float] = []
        stale: list[int] = []
        for r in range(n_reads):
            for i in range(burst):
                k = issued
                if i % 5 == 4:
                    res = s.execute(
                        f"UPDATE t SET v = v + 1 WHERE k = {k % 64}")
                elif i % 5 == 3:
                    res = s.execute(f"DELETE FROM t WHERE k = {k % 96}")
                else:
                    res = s.execute(
                        f"INSERT INTO t VALUES ({k}, {k % 8}, {k * 3})")
                    issued += 1
                applied += int(res.affected_rows)
            a0 = time.perf_counter()
            s.query(agg_sql)
            lat.append((time.perf_counter() - a0) * 1e3)
            stale.append(int(mv.staleness_ms()))
        return lat, stale, applied

    answer0 = bool(FLAGS.matview_answer)
    try:
        # view phase: reads answered from the maintained rollup (each
        # read folds the burst's pending deltas first — maintenance cost
        # is IN the number, not hidden); an audit subscription replays
        # the whole change stream afterwards to prove nothing was lost
        set_flag("matview_answer", 1)
        s = mk()
        audit = s.db.cdc.create("bench_audit", table_key="cb.t")
        view_ms, stale_ms, applied = drive(s)

        # quiesce: the view answer must be bit-identical to the
        # recompute of the same table — the emit gate
        view_rows = s.query(agg_sql)
        set_flag("matview_answer", 0)
        base_rows = s.query(agg_sql)
        agree = view_rows == base_rows

        # audit replay: every row the write loop touched must appear in
        # the stream (the subscription started at the live tail, so the
        # seed INSERT is excluded; the view's backing-table traffic is
        # excluded by the cb.t table filter)
        seen = 0
        while True:
            got = audit.fetch(4096)
            if not got:
                break
            seen += sum(int(e.affected) for e in got)
            audit.ack(got[-1].commit_ts)
        s.db.cdc.drop("bench_audit")
        lost = applied - seen
        d = s.db.matviews.get("cb", "mv").describe()

        # recompute phase: the IDENTICAL interleave against a fresh
        # session with the view switched off — reads scan+aggregate base
        # rows under the same live write pressure
        s = mk()
        recompute_ms, _, _ = drive(s)
    finally:
        set_flag("matview_answer", int(answer0))

    if not agree:
        raise RuntimeError(
            "view answer diverged from recompute at quiesce: "
            f"view={view_rows[:4]!r}... base={base_rows[:4]!r}...")
    qps_view = n_reads / (sum(view_ms) / 1e3)
    qps_re = n_reads / (sum(recompute_ms) / 1e3)
    return {
        "metric": f"rollup views: GROUP BY answered from maintained view "
                  f"vs recompute under live writes ({n_writes} writes, "
                  f"{n_reads} reads)",
        "value": round(qps_view, 1),
        "unit": "queries/sec",
        # >1 means the view read beats recomputing the aggregate
        "vs_baseline": round(qps_view / qps_re, 3),
        "view_read_p50_ms": pq(view_ms, 0.50),
        "view_read_p99_ms": pq(view_ms, 0.99),
        "recompute_p50_ms": pq(recompute_ms, 0.50),
        "recompute_p99_ms": pq(recompute_ms, 0.99),
        "staleness_p50_ms": pq([float(x) for x in stale_ms], 0.50),
        "staleness_max_ms": int(max(stale_ms)),
        "deltas_folded": int(d["deltas_folded"]),
        "view_rescans": int(d["rescans"]),
        "events_streamed": int(seen),
        "lost_events": int(lost),
        "quiesced_agree": bool(agree),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_commit": _git_head(),
        **_hardware_context(),
    }


def _run_multiway_child() -> dict:
    """The multiway phase needs a mesh of 8, so it runs in a child with 8
    virtual CPU devices (the device count is fixed before jax initialises,
    and this process may already hold a one-device backend)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    return _cpu_child(
        "import json, bench; print(json.dumps(bench.run_multiway_bench()))",
        {"XLA_FLAGS": flags},
        float(os.environ.get("BENCH_MULTIWAY_TIMEOUT", 1800)))


# (BENCH_SKIP_<NAME> suffix, runner) in emission order — one JSON line each
_PHASES = [
    ("HEADLINE", run_bench),
    ("MIXED", run_mixed_bench),
    ("POINT", run_point_bench),
    ("TRACE", run_trace_bench),
    ("CHAOS", run_chaos_bench),
    ("CONC", run_concurrency_bench),
    ("MULTIWAY", _run_multiway_child),
    ("TELEMETRY", run_telemetry_bench),
    ("COLDSTART", run_coldstart_bench),
    ("PROGRESS", run_progress_bench),
    ("GUARD", run_guard_bench),
    ("ELASTIC", run_elastic_bench),
    ("STREAM", run_stream_bench),
    ("FRAGMENT", run_fragment_bench),
    ("SNAPSHOT", run_snapshot_bench),
    ("CDC", run_cdc_bench),
]


def main() -> int:
    """Run every phase not skipped by BENCH_SKIP_<NAME>=1 on the backend
    this process has.  -> 0 iff no phase raised."""
    failed = []
    for name, run in _PHASES:
        if os.environ.get(f"BENCH_SKIP_{name}") == "1":
            continue
        try:
            print(json.dumps(run()), flush=True)
        except Exception:                               # noqa: BLE001
            # phase boundary: record the failure, keep measuring the rest
            print(f"bench: phase {name} failed", file=sys.stderr)
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"bench: FAILED phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
