"""Metrics instruments — the bvar analog (SURVEY §5.5).

The reference instruments everything with brpc bvars (Adder /
LatencyRecorder / PerSecond, e.g. include/protocol/state_machine.h:149-152,
include/exec/fetcher_store.h:189-192) and dumps them per-process to files /
the brpc HTTP port.  Same shapes here, host-side and dependency-free:

- ``Counter``: monotonically growing adder (+ per-second rate derived from
  a sliding window).
- ``LatencyRecorder``: ring of recent observations -> count/avg/p50/p95/
  p99/max.  Process-local only: a ring of raw samples cannot merge across
  daemons (which recent N wins?) — use ``Histogram`` for anything the fleet
  aggregator must sum.
- ``Histogram``: fixed log-spaced bucket counts + sum.  The mergeable
  instrument: two snapshots with identical bounds sum bucket-wise, so the
  frontend's fleet aggregator (obs/telemetry.py) can combine per-daemon
  latency distributions exactly.
- ``Gauge``: callable or settable cell sampled at dump time (queue depths,
  cache sizes, HBM in use).
- ``*Family``: labeled variants — one logical metric keyed by a label
  tuple (``table``, ``method``, ``region``), children created on first
  ``labels(...)`` touch.

All instruments register in a ``Registry``.  The process-wide ``REGISTRY``
serves the engine; daemons (server/store_server.py, server/meta_server.py)
carry their OWN Registry so several in-process daemons never collide.
Surfaces: ``SHOW STATUS``, ``information_schema.metrics``,
``registry.dump()`` text lines (the bvar-dump-file analog), and
``registry.snapshot()`` — the plain-dict, JSON-safe form the telemetry
plane ships over RPC and renders as Prometheus exposition.
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Callable, Optional


class _NullRegistry:
    """Registration sink for family children: the family itself is the
    registered object; its labeled children must not collide in the
    by-name table."""

    def _register(self, inst) -> None:
        pass


NULL_REGISTRY = _NullRegistry()


class Counter:
    kind = "counter"

    def __init__(self, name: str, registry: Optional["Registry"] = None):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()
        # (ts, cumulative) sliding window; deque so the per-add trim is
        # O(1) popleft — list.pop(0) shifted the whole window on every
        # hot-path increment
        self._window: deque[tuple[float, int]] = deque()
        (registry or REGISTRY)._register(self)

    def add(self, n: int = 1) -> None:
        with self._lock:
            self._value += n
            now = time.monotonic()
            self._window.append((now, self._value))
            cutoff = now - 60.0
            while len(self._window) > 2 and self._window[0][0] < cutoff:
                self._window.popleft()

    @property
    def value(self) -> int:
        with self._lock:
            return self._value

    def per_second(self, window_s: float = 10.0) -> float:
        """Rate over (at most) the trailing ``window_s``: baseline is the
        NEWEST sample older than the window start, so the measured interval
        brackets the window; when every retained sample is inside the
        window the oldest retained sample is the baseline."""
        with self._lock:
            if len(self._window) < 2:
                return 0.0
            now = time.monotonic()
            cutoff = now - window_s
            # scan from the RIGHT: the baseline sits at the window boundary,
            # so this touches only the samples INSIDE the rate window
            # (~window_s worth) — the old forward scan walked everything
            # OLDER than it first (up to the full 60 s retention) on every
            # call, O(retention) per dump
            first = None
            for ts, v in reversed(self._window):
                if ts < cutoff:
                    first = (ts, v)
                    break
            if first is None:
                first = self._window[0]
            dt = now - first[0]
            return (self._value - first[1]) / dt if dt > 0 else 0.0

    def stats(self) -> dict:
        return {"value": self.value,
                "per_second": round(self.per_second(), 3)}


class LatencyRecorder:
    kind = "latency"

    def __init__(self, name: str, capacity: int = 4096,
                 registry: Optional["Registry"] = None):
        self.name = name
        self.capacity = capacity
        self._ring: list[float] = []
        self._idx = 0
        self._count = 0
        self._total = 0.0
        self._max = 0.0
        self._lock = threading.Lock()
        (registry or REGISTRY)._register(self)

    def observe(self, ms: float) -> None:
        with self._lock:
            self._count += 1
            self._total += ms
            self._max = max(self._max, ms)
            if len(self._ring) < self.capacity:
                self._ring.append(ms)
            else:
                self._ring[self._idx] = ms
                self._idx = (self._idx + 1) % self.capacity
    def time(self):
        """Context manager: records elapsed milliseconds."""
        rec = self

        class _T:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                rec.observe((time.perf_counter() - self.t0) * 1e3)
                return False
        return _T()

    def stats(self) -> dict:
        with self._lock:
            n = self._count
            if n == 0:
                return {"count": 0, "avg_ms": 0.0, "p50_ms": 0.0,
                        "p95_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}
            s = sorted(self._ring)

            def q(p):
                return s[min(len(s) - 1, int(p * (len(s) - 1) + 0.5))]
            return {"count": n, "avg_ms": round(self._total / n, 3),
                    "p50_ms": round(q(0.50), 3), "p95_ms": round(q(0.95), 3),
                    "p99_ms": round(q(0.99), 3), "max_ms": round(self._max, 3)}


# default latency-histogram bounds (milliseconds): 1-2.5-5 per decade from
# 0.1 ms to 50 s.  FIXED and log-spaced so every process bins identically —
# bucket-wise summation across daemons is exact only when bounds match.
DEFAULT_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                   100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
                   10000.0, 25000.0, 50000.0)


def histogram_quantile(q: float, le: list, buckets: list) -> float:
    """Quantile estimate from cumulative-able bucket counts (per-bin counts
    + the +Inf overflow bin): linear interpolation inside the owning bucket
    — the Prometheus histogram_quantile estimator, shared by live
    instruments and the fleet aggregator's merged rows."""
    total = sum(buckets)
    if total <= 0:
        return 0.0
    rank = q * total
    cum = 0.0
    lo = 0.0
    for i, c in enumerate(buckets):
        cum += c
        if cum >= rank:
            if i >= len(le):            # +Inf bin: no upper bound to
                return float(lo)        # interpolate toward — clamp
            hi = le[i]
            frac = (rank - (cum - c)) / c if c > 0 else 0.0
            return float(lo + (hi - lo) * frac)
        if i < len(le):
            lo = le[i]
    return float(lo)


def histogram_stats(le: list, buckets: list, count: float,
                    total: float) -> dict:
    """count/sum/avg + interpolated quantiles from bucket counts — works on
    a live instrument's state AND on merged snapshot rows."""
    n = float(count)
    return {"count": n, "sum": round(float(total), 3),
            "avg": round(float(total) / n, 3) if n > 0 else 0.0,
            "p50": round(histogram_quantile(0.50, le, buckets), 3),
            "p95": round(histogram_quantile(0.95, le, buckets), 3),
            "p99": round(histogram_quantile(0.99, le, buckets), 3)}


class Histogram:
    """Fixed-bucket histogram: the fleet-mergeable latency instrument.

    ``LatencyRecorder``'s ring of recent raw samples gives better local
    quantiles but cannot aggregate across processes; bucket counts sum
    bucket-wise (order-independent, exact) as long as every party uses the
    same bounds — which is why the bounds are fixed at construction and
    ride along in every snapshot."""

    kind = "histogram"

    def __init__(self, name: str, buckets=DEFAULT_BUCKETS,
                 registry: Optional["Registry"] = None):
        self.name = name
        self.le = tuple(float(b) for b in buckets)
        self._counts = [0] * (len(self.le) + 1)     # last = +Inf overflow
        self._count = 0
        self._sum = 0.0
        self._lock = threading.Lock()
        (registry or REGISTRY)._register(self)

    def observe(self, v: float) -> None:
        # bisect_left: a value exactly on a bound belongs to THAT bucket
        # (Prometheus ``le`` = less-than-or-equal semantics)
        i = bisect_left(self.le, v)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v

    def time(self):
        """Context manager: records elapsed milliseconds."""
        rec = self

        class _T:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                rec.observe((time.perf_counter() - self.t0) * 1e3)
                return False
        return _T()

    def stats(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            n, s = self._count, self._sum
        return histogram_stats(list(self.le), counts, n, s)

    def snapshot_fields(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            n, s = self._count, self._sum
        out = histogram_stats(list(self.le), counts, n, s)
        out["le"] = list(self.le)
        out["buckets"] = counts
        return out


class Gauge:
    """Sampled at dump time: construct with a callable, or call ``set()``
    on a plain instance (family cells are settable)."""

    kind = "gauge"

    def __init__(self, name: str, fn: Optional[Callable[[], float]] = None,
                 registry: Optional["Registry"] = None):
        self.name = name
        self.fn = fn
        self._value = float("nan")
        self._vlock = threading.Lock()
        (registry or REGISTRY)._register(self)

    def set(self, v: float) -> None:
        with self._vlock:
            self._value = float(v)

    def add(self, d: float) -> None:
        """Relative move (in-flight counts, pool sizes); an unset gauge
        starts from 0."""
        with self._vlock:
            v = self._value
            self._value = (0.0 if v != v else v) + float(d)

    def stats(self) -> dict:
        if self.fn is None:
            return {"value": self._value}
        try:
            return {"value": float(self.fn())}
        except Exception:
            # a raising gauge fn must not break SHOW STATUS / expose():
            # the row stays (NaN) and the failure is countable per-site
            count_swallowed("metrics.gauge")
            return {"value": float("nan")}


class _Family:
    """One logical metric keyed by a label tuple.  Children are real
    instruments created on first ``labels()`` touch, registered nowhere
    (the family is the registry entry); the hot path after creation is one
    dict lookup under the family lock."""

    def __init__(self, name: str, label_names: tuple, factory,
                 registry: Optional["Registry"] = None):
        self.name = name
        self.label_names = tuple(label_names)
        self._factory = factory
        self._children: dict[tuple, object] = {}
        self._lock = threading.Lock()
        (registry or REGISTRY)._register(self)

    def _key(self, kv: dict) -> tuple:
        if set(kv) != set(self.label_names):
            raise ValueError(
                f"metric {self.name}: labels {sorted(kv)} != declared "
                f"{sorted(self.label_names)}")
        return tuple(str(kv[n]) for n in self.label_names)

    def labels(self, **kv):
        key = self._key(kv)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    child = self._factory(
                        f"{self.name}{{{','.join(key)}}}")
                    self._children[key] = child
        return child

    def remove(self, **kv) -> None:
        """Drop one labeled row (a region moved away, a table dropped)."""
        with self._lock:
            self._children.pop(self._key(kv), None)

    def rows(self) -> list[tuple[tuple, object]]:
        with self._lock:
            return sorted(self._children.items())

    def stats(self) -> dict:
        """Flattened ``{label=value,...}.field`` rows — the SHOW STATUS /
        dump() rendering of a labeled family."""
        out: dict = {}
        for key, child in self.rows():
            tag = ",".join(f"{n}={v}"
                           for n, v in zip(self.label_names, key))
            for f, v in child.stats().items():
                out[f"{{{tag}}}.{f}"] = v
        return out

    def snapshot_rows(self) -> list[dict]:
        rows = []
        for key, child in self.rows():
            fields = child.snapshot_fields() \
                if isinstance(child, Histogram) else child.stats()
            rows.append({"labels": list(key), **fields})
        return rows


class CounterFamily(_Family):
    kind = "counter"

    def __init__(self, name: str, label_names: tuple,
                 registry: Optional["Registry"] = None):
        super().__init__(name, label_names,
                         lambda n: Counter(n, registry=NULL_REGISTRY),
                         registry)


class GaugeFamily(_Family):
    kind = "gauge"

    def __init__(self, name: str, label_names: tuple,
                 registry: Optional["Registry"] = None):
        super().__init__(name, label_names,
                         lambda n: Gauge(n, registry=NULL_REGISTRY),
                         registry)


class HistogramFamily(_Family):
    kind = "histogram"

    def __init__(self, name: str, label_names: tuple,
                 buckets=DEFAULT_BUCKETS,
                 registry: Optional["Registry"] = None):
        super().__init__(
            name, label_names,
            lambda n: Histogram(n, buckets=buckets,
                                registry=NULL_REGISTRY),
            registry)


class LatencyFamily(_Family):
    kind = "latency"

    def __init__(self, name: str, label_names: tuple,
                 registry: Optional["Registry"] = None):
        super().__init__(
            name, label_names,
            lambda n: LatencyRecorder(n, registry=NULL_REGISTRY),
            registry)


class Registry:
    def __init__(self):
        self._by_name: dict[str, object] = {}
        self._lock = threading.Lock()

    def _register(self, inst) -> None:
        with self._lock:
            self._by_name[inst.name] = inst

    def get(self, name: str):
        with self._lock:
            return self._by_name.get(name)

    def expose(self) -> dict[str, dict]:
        """{metric -> stats dict}; the SHOW STATUS / info_schema source.
        Labeled families flatten to ``{label=value,...}.field`` keys."""
        with self._lock:
            items = sorted(self._by_name.items())
        return {name: inst.stats() for name, inst in items}

    def snapshot(self) -> dict:
        """Structured, JSON-safe snapshot — the wire form of this registry
        (daemon ``rpc_metrics`` responses, the fleet aggregator's input,
        the Prometheus renderer's input)::

            {name: {"kind": "counter|latency|histogram|gauge",
                    "label_names": [...],        # [] for plain instruments
                    "rows": [{"labels": [...], <fields>}, ...]}}

        Histogram rows carry ``le`` + per-bin ``buckets`` so merging can
        sum bucket-wise; every other row is its ``stats()`` fields."""
        with self._lock:
            items = sorted(self._by_name.items())
        out: dict = {}
        for name, inst in items:
            if isinstance(inst, _Family):
                out[name] = {"kind": inst.kind,
                             "label_names": list(inst.label_names),
                             "rows": inst.snapshot_rows()}
            else:
                fields = inst.snapshot_fields() \
                    if isinstance(inst, Histogram) else inst.stats()
                out[name] = {"kind": inst.kind, "label_names": [],
                             "rows": [{"labels": [], **fields}]}
        return out

    def dump(self) -> str:
        """bvar-dump-style text: one ``name.field : value`` per line."""
        lines = []
        for name, stats in self.expose().items():
            for k, v in stats.items():
                lines.append(f"{name}.{k} : {v}")
        return "\n".join(lines)

    def _get_or_create(self, name: str, make):
        """Atomic first-touch: lookup-and-create under the registry lock.
        A bare get()-then-construct lets two racing threads mint two
        instruments for one name — the loser keeps mutating an orphan the
        snapshot never sees.  ``make`` constructs with NULL_REGISTRY so the
        instrument's self-registration no-ops while we hold the lock."""
        with self._lock:
            inst = self._by_name.get(name)
            if inst is None:
                inst = make()
                self._by_name[name] = inst
            return inst

    def counter(self, name: str) -> Counter:
        return self._get_or_create(
            name, lambda: Counter(name, registry=NULL_REGISTRY))

    def latency(self, name: str) -> LatencyRecorder:
        return self._get_or_create(
            name, lambda: LatencyRecorder(name, registry=NULL_REGISTRY))

    def histogram(self, name: str, buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(
            name, lambda: Histogram(name, buckets=buckets,
                                    registry=NULL_REGISTRY))

    def gauge(self, name: str,
              fn: Optional[Callable[[], float]] = None) -> Gauge:
        return self._get_or_create(
            name, lambda: Gauge(name, fn=fn, registry=NULL_REGISTRY))

    def counter_family(self, name: str, label_names: tuple) -> CounterFamily:
        return self._get_or_create(
            name, lambda: CounterFamily(name, label_names,
                                        registry=NULL_REGISTRY))

    def gauge_family(self, name: str, label_names: tuple) -> GaugeFamily:
        return self._get_or_create(
            name, lambda: GaugeFamily(name, label_names,
                                      registry=NULL_REGISTRY))

    def histogram_family(self, name: str, label_names: tuple,
                         buckets=DEFAULT_BUCKETS) -> HistogramFamily:
        return self._get_or_create(
            name, lambda: HistogramFamily(name, label_names, buckets=buckets,
                                          registry=NULL_REGISTRY))

    def latency_family(self, name: str, label_names: tuple) -> LatencyFamily:
        return self._get_or_create(
            name, lambda: LatencyFamily(name, label_names,
                                        registry=NULL_REGISTRY))


REGISTRY = Registry()

# -- engine-wide instruments (the reference's always-on bvars) -------------
queries_total = Counter("queries_total")
queries_failed = Counter("queries_failed")
slow_queries = Counter("slow_queries")
rows_returned = Counter("rows_returned")
dml_rows = Counter("dml_rows")
query_latency = LatencyRecorder("query_latency")
plan_cache_hits = Counter("plan_cache_hits")
plan_cache_misses = Counter("plan_cache_misses")
# normalized-key plan-cache hits whose SQL text differs from the text that
# built the entry: literal auto-parameterization (plan/paramize.py) serving
# a new literal variant from an existing executable.  Split from exact-text
# hits so dashboards show how much of the hit rate parameterization buys.
# Accounting invariant (tests/test_param_cache.py): every cached-path SELECT
# counts exactly one of {hits, param_hits, misses} — a hit that still
# re-traces (capacity-bucket crossing) is a HIT at the plan level, the
# retrace shows in xla_retraces/compile_ms only.
plan_cache_param_hits = Counter("plan_cache_param_hits")
# parameterized planning/binding that had to fall back to baked-literal
# execution (unresolvable schema, bind failure, trace error): correctness
# valve, should stay ~0
plan_cache_param_fallbacks = Counter("plan_cache_param_fallbacks")
# literals hoisted into runtime params across all statements
params_hoisted = Counter("params_hoisted")
prepared_executes = Counter("prepared_executes")
# SQL transactions ended by COMMIT (or the implicit commit of a new BEGIN)
# and by ROLLBACK (exec/session.py _txn_stmt)
txn_commits = Counter("txn_commits")
txn_rollbacks = Counter("txn_rollbacks")
connections_total = Counter("connections_total")
point_lookups = Counter("point_lookups")
# ms, not counts: the wall time of two obs/trace spans that close where no
# query_log row can carry them — point.lookup (the row tier answers and no
# row is written) and wire.result_set (encode + socket write, after the row)
point_lookup_ms = Counter("point_lookup_ms")
# ms, not a count: the wall time of stats.column, a TableStore.column_stats
# miss (min/max, histogram sample, HLL, the ordered pass: once a column a
# table version, on the thread of the statement that first plans over it)
column_stats_ms = Counter("column_stats_ms")
wire_result_set_ms = Counter("wire_result_set_ms")
# the MySQL framing (server/mysql_server.Packets, both ends of the wire in
# a process that holds server and clients): packets framed, sendall calls
# that carried them, recv calls.  packets / sends is the coalescing ratio
wire_packets = Counter("wire_packets")
wire_sends = Counter("wire_sends")
wire_recvs = Counter("wire_recvs")
index_scans = Counter("index_scans")
# statements whose scan input was the pk_range arm's fixed-capacity gather
# out of the resident image (exec/session._access_path_batch), and the rows
# their key ranges matched
pk_range_scans = Counter("pk_range_scans")
pk_range_rows = Counter("pk_range_rows")
regions_pruned = Counter("regions_pruned")
# XLA (re)traces of query programs: each count is one compile.  With capacity
# bucketing on, an identical SELECT repeated across DML that stays inside one
# bucket must not move this counter (tests/test_shape_buckets.py pins that).
xla_retraces = Counter("xla_retraces")
# wall time of executions that included a trace+compile (first run / bucket
# crossing) — compare its percentiles against query_latency for the
# steady-state-vs-first-run split
compile_ms = LatencyRecorder("compile_ms")
# distributed-binlog appends that failed and were queued for retry / dropped
# after the retry queue overflowed (counted in EVENTS, not batches)
binlog_retry_queued = Counter("binlog_retry_queued")
binlog_events_dropped = Counter("binlog_events_dropped")
# CDC change streams (cdc/streams.py) + incrementally maintained rollup
# views (cdc/views.py): events handed to subscribers, fetch calls, how far
# behind the table high-water a cursor's ack stands, ring-trim deferrals
# because an unacked cursor pinned events, cursors force-expired past
# cdc_cursor_max_lag_s (their next fetch raises CursorLagging), matview
# fold rounds / individual deltas folded / full-or-group rescans (MIN/MAX
# retract + statement-image events), and queries the planner answered
# from view state instead of recomputing
cdc_events_delivered = Counter("cdc_events_delivered")
cdc_fetches = Counter("cdc_fetches")
cdc_cursor_lag_ms = LatencyRecorder("cdc_cursor_lag_ms")
binlog_gc_held_by_cursor = Counter("binlog_gc_held_by_cursor")
cdc_cursors_expired = Counter("cdc_cursors_expired")
view_folds = Counter("view_folds")
view_deltas_folded = Counter("view_deltas_folded")
view_rescans = Counter("view_rescans")
view_answered_queries = Counter("view_answered_queries")
# intentionally-swallowed exceptions on best-effort paths (tpulint BAREEXC
# policy: a swallow must at least be countable) — total plus a per-site
# counter so SHOW METRICS points at the failing subsystem
swallowed_exceptions = Counter("swallowed_exceptions")
# query-lifecycle tracing (obs/trace.py): traces kept in the bounded store
# (head-sampled + slow-query always-keep), and spans dropped by the
# per-trace cap or store eviction — if this moves, raise trace_max_spans /
# trace_store_max or lower the sampling rate
traces_sampled = Counter("traces_sampled")
trace_spans_dropped = Counter("trace_spans_dropped")
# RPC plane (utils/net.py): calls that exhausted their per-call deadline
# budget (typed RpcTimeout), transport-failure resends under the
# backoff+jitter policy, and daemon-side idempotency-token dedupe hits
# (a retried write whose first copy executed with the response lost —
# the dedupe is what makes resending writes safe)
rpc_timeouts = Counter("rpc_timeouts")
rpc_retries = Counter("rpc_retries")
rpc_dedup_hits = Counter("rpc_dedup_hits")
# chaos (chaos/failpoint.py): total failpoint trips across all points
# (per-point counts live in failpoint.<name> counters)
failpoint_trips = Counter("failpoint_trips")
# leaderless regions served by the most advanced live replica (learner
# included) instead of failing the read — bounded-degradation valve
learner_fallback_reads = Counter("learner_fallback_reads")
# elastic regions (meta tick -> fleet): completed / aborted live splits and
# learner-first migrations, plus the fenced-handoff window each one paid
# (the only interval where the tier lock blocks writers).  Surfaced by
# SHOW STATUS as region.*
region_splits = Counter("region.splits")
region_split_aborts = Counter("region.split_aborts")
region_merges = Counter("region.merges")
region_migrations = Counter("region.migrations")
region_migrate_aborts = Counter("region.migrate_aborts")
region_handoff_ms = LatencyRecorder("region.handoff_ms")
# cross-query batched dispatch (exec/dispatch.py): combiner ticks that ran
# a batched executable, the group sizes they combined (percentiles over the
# occupancy distribution), per-member queue wait, and wall time of the
# batched device run itself
batched_groups = Counter("batched_groups")
group_occupancy = LatencyRecorder("group_occupancy")
queue_wait_ms = LatencyRecorder("queue_wait_ms")
dispatch_tick_ms = LatencyRecorder("dispatch_tick_ms")
# queries that bypassed the queue (idle group / solo tick) and members that
# degraded to inline execution after a combiner failure — the fallback
# valve, should stay ~0 outside chaos runs
dispatch_inline = Counter("dispatch_inline")
dispatch_fallbacks = Counter("dispatch_fallbacks")
# typed admission rejections: qos token buckets (per-sign/user/table) and
# the dispatcher's bounded per-group queue
qos_rejections = Counter("qos_rejections")
# MPP exchange v2 (plan/distribute.py + exec/executor.py): hash-repartition
# exchange rounds executed (a fused multiway join counts ONE round however
# many inputs it repartitions — the headline the fusion reduces), retries
# forced by a per-destination shuffle capacity overflow (skew), and join
# chains folded into a MultiJoinNode at plan time
shuffle_rounds = Counter("shuffle_rounds")
shuffle_overflow_retries = Counter("shuffle_overflow_retries")
multiway_joins_fused = Counter("multiway_joins_fused")
# keyed exchange scheduler: repartition collectives SKIPPED because the
# input was already hash-partitioned on the key class (transitive
# partition reuse) — each one is an avoided all_to_all + its trace
shuffle_rounds_saved = Counter("shuffle_rounds_saved")
# the mesh arm of the served path (exec/session._run_plan).  mesh_programs:
# SELECTs that ran as one shard_map program over the deployment's mesh.
# exchange_bytes: per execution, the bytes that program's repartition and
# gather collectives carry between chips, reckoned at trace time from static
# shapes (exec/executor.py): a repartition's all_to_all moves every column's
# [n, cap] send buffer (validity and the row mask too) less the 1/n that
# stays home, from each of n chips; a gather's all_gather brings each chip
# the other n-1 slices.  The buffers are fixed-size, so this is what the ICI
# carries whatever the live rows; the psum/pmax merges of partial aggregates
# and flags (a few KB) are not counted.  join_cap_retries: recompiles of the
# cap-retry loop (a join or shuffle capacity overflowed).  mesh_shard_ms: ms,
# not a count: the wall time of mesh.shard (parallel/mesh.shard_batch),
# which runs where a table is first read and writes no query_log row of a
# warmed window
mesh_programs = Counter("mesh_programs")
exchange_bytes = Counter("exchange_bytes")
join_cap_retries = Counter("join_cap_retries")
# exec/caps.settle, once an execution of a plan with capacity flags (shrink,
# join, multiway, exchange): join_cap_slots = the capacities its flags were
# held against, join_live_rows = the rows the flags reported (each at most
# its capacity); their ratio is how full the static shapes ran.  A shrink
# that cuts nothing (cap = its child's size) has no flag and counts in
# neither.  aot_publish_ms: ms, not a count: the publisher's wall time per
# settled executable (export + serialize + disk), off the query's thread
join_cap_slots = Counter("join_cap_slots")
# exec/caps.settle: stream_agg_runs +1 for each GROUP BY an execution ran as
# segmented scans over rows already in key order (the planner's ``stream``
# strategy) and whose own check of that order passed; stream_agg_fallbacks
# +1 when the check failed: that node was traced again as a scatter or a sort
stream_agg_runs = Counter("stream_agg_runs")
# +1 for each dense GROUP BY an execution of a compiled plan ran, by the
# lowering its program was traced with (ops/hashagg.dense_lowering: the
# fused select+reduce, the Pallas one-hot kernels, the segment scatter);
# the choice is recorded with the program at trace time (exec/executor.
# compile_plan, an AOT artifact's ``extra``) and counted where an execution
# settles.  A streamed fold's per-chunk aggregate counts in none
agg_select_reduce_runs = Counter("agg_select_reduce_runs")
agg_pallas_runs = Counter("agg_pallas_runs")
agg_scatter_runs = Counter("agg_scatter_runs")
# counted beside them: the passes over their input lanes those aggregates
# make only to count rows (``present``, COUNT(*), a column's non-NULL
# count), as traced.  ops/hashagg.group_aggregate_dense counts a group's
# rows once and reads the rest off it: 1 an aggregate on the segment arms
# (+1 for each nullable column aggregated), 0 on the Pallas arm where a
# fused kernel's own count serves, else 1
agg_count_passes = Counter("agg_count_passes")
stream_agg_fallbacks = Counter("stream_agg_fallbacks")
join_live_rows = Counter("join_live_rows")
aot_publish_ms = Counter("aot_publish_ms")
mesh_shard_ms = Counter("mesh_shard_ms")
# equality-class constant propagation (plan/planner.py): derived
# col = const conjuncts pushed to sibling scans at plan time
eqclass_consts_pushed = Counter("eqclass_consts_pushed")
# cardinality-adaptive partial aggregation decisions (plan time, from the
# index/stats ndv estimate): local = pre-reduce before the exchange,
# raw = shuffle raw rows and aggregate once
agg_strategy_local = Counter("agg_strategy_local")
agg_strategy_raw = Counter("agg_strategy_raw")
# AOT persistent executable cache (utils/compilecache.py): artifacts served
# from the disk/peer tiers instead of a fresh trace+compile (hits), compile
# seams that found no artifact (misses), artifacts fetched from a peer
# through the meta manifest, artifacts published (exported + verified +
# written), stale/corrupt artifacts evicted, and loads that had to degrade
# back to a fresh compile AFTER a hit (corruption, baked-cap overflow) —
# the correctness valve, should stay ~0 outside chaos runs
aot_cache_hits = Counter("aot_cache_hits")
aot_cache_misses = Counter("aot_cache_misses")
aot_cache_peer_fetches = Counter("aot_cache_peer_fetches")
aot_cache_publishes = Counter("aot_cache_publishes")
aot_cache_evictions = Counter("aot_cache_evictions")
aot_cache_fallbacks = Counter("aot_cache_fallbacks")
# wall time of deserialize + first executable build for an AOT hit — the
# cold-start cost that REPLACES compile_ms on warm-started nodes
aot_cache_deser_ms = LatencyRecorder("aot_cache_deser_ms")
# live query introspection (obs/progress.py): queries whose cancel token a
# KILL flipped (the victim raises ER_QUERY_INTERRUPTED at its next beat)
queries_killed = Counter("queries_killed")
# fleet watchdogs (obs/watchdog.py): stall detections — a live query with
# no progress beat for watchdog_stall_s, a raft apply-lag that stopped
# draining, a wedged daemon tick loop.  Each detection counts ONCE per
# stalled subject, not per scan
watchdog_stalls_detected = Counter("watchdog_stalls_detected")
# flight recorder (obs/flightrec.py): completed-query summaries recorded
# and the subset that carried a full forensic bundle (slow/killed/failed)
flightrec_records = Counter("flightrec_records")
flightrec_bundles = Counter("flightrec_bundles")
# out-of-core streaming scans (exec/streaming.py): chunks folded, chunks
# zone-map-skipped before any transfer, coldfs segment-read retries, fold
# restarts after a group-capacity overflow, bytes moved host->device, and
# how long the fold loop waited on the prefetcher (0-ish wait = the H2D
# copy fully overlapped the previous chunk's compute)
stream_chunks = Counter("stream_chunks")
stream_chunks_skipped = Counter("stream_chunks_skipped")
stream_retries = Counter("stream_retries")
stream_restarts = Counter("stream_restarts")
stream_bytes_h2d = Counter("stream_bytes_h2d")
stream_prefetch_wait_ms = LatencyRecorder("stream_prefetch_wait_ms")
# pushed-down fragment execution (exec/fragments.py): per-region fragment
# dispatches to store daemons, re-dispatches after a mid-flight split/
# migration re-target (StaleRoutingError -> refresh -> re-slice), whole
# queries that fell back to the frontend-pulled image path, raw region
# bytes that did NOT cross the wire because only partials came back
# (daemon-scanned bytes minus partial payload bytes), and dispatches
# where no daemon could warm-start the fragment from its artifact tier
# (disk -> peer both missed; the body had to ship inline) — pinned at 0
# on any re-dispatch of a published fragment
fragments_dispatched = Counter("fragments_dispatched")
fragment_retargets = Counter("fragment_retargets")
fragment_fallbacks = Counter("fragment_fallbacks")
fragment_bytes_saved = Counter("fragment_bytes_saved")
fragment_warm_compiles = Counter("fragment_warm_compiles")


def count_swallowed(site: str) -> None:
    """Record an intentionally-swallowed exception at ``site``."""
    swallowed_exceptions.add(1)
    REGISTRY.counter(f"swallowed.{site}").add(1)
