"""Session: the SQL entry point (parse -> plan -> jit -> result).

The analog of the reference's connection state machine driving a query
(src/protocol/state_machine.cpp:1775 _handle_client_query_common_query:
LogicalPlanner::analyze -> PhysicalPlanner::analyze -> execute -> PacketNode),
minus the wire protocol (server tier lands later).  Includes the plan cache
(reference: state_machine.cpp:1984) keyed by SQL text + table versions +
static shapes, so repeated queries skip parse/plan/trace and reuse the
compiled XLA executable.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np
import pyarrow as pa

from ..column.batch import ColumnBatch
from ..expr.compile import eval_expr, eval_output, eval_predicate
from ..meta.catalog import Catalog, IndexInfo, parse_type
from ..ops.compact import compact
from ..plan.nodes import (AggNode, ExchangeNode, JoinNode, MultiJoinNode,
                          PlanNode, ScalarSourceNode, plan_signature)
from ..plan.planner import PlanError, Planner
from ..sql.lexer import SqlError
from ..sql.parser import parse_sql
from ..sql.stmt import (AlterTableStmt, CreateDatabaseStmt, CreateTableStmt, DeleteStmt,
                        DescribeStmt, DropDatabaseStmt, DropTableStmt,
                        ExplainStmt, InsertStmt, SelectStmt, ShowStmt,
                        SetStmt, TruncateStmt, TxnStmt, UpdateStmt, UseStmt)
from ..meta.privileges import READ, WRITE, AccessError, PrivilegeManager
from ..sql.stmt import (CreateMatViewStmt, CreateSubscriptionStmt,
                        CreateUserStmt, CreateViewStmt, DeallocateStmt,
                        DropMatViewStmt, DropSubscriptionStmt, DropUserStmt,
                        DropViewStmt, ExecuteStmt, FetchStmt, GrantStmt,
                        HandleStmt,
                        KillStmt, LoadDataStmt, PrepareStmt, RevokeStmt)
from ..plan import paramize
from ..storage.column_store import ROWID as ROWID_COL
from ..storage.column_store import (TableStore, check_cold_readable,
                                    schema_to_arrow)
from ..storage.rowstore import ConflictError
from ..column.dictionary import KEYS_KEY
from ..types import Field, LType, Schema
from ..analysis.runtime import guard_stats, hot_path_guard
from ..obs import progress, trace
from ..obs.flightrec import (FlightRecorder, device_stats, metric_delta,
                             metric_marks)
from ..obs.progress import PROGRESS, QueryKilled
from ..obs.trace import TRACER
from ..obs.watchdog import QueryWatchdog
from ..utils import compilecache, gcpause, metrics
from ..utils.flags import FLAGS, define

define("cold_fs_dir", "",
       "external cold-storage root (posix AFS stand-in); empty = cold "
       "tier disabled")
define("mesh_devices", 0,
       "the deployment's device mesh: with N >= 2 the Database owns a mesh "
       "of the first N devices and every session without a mesh of its own "
       "(every wire connection) runs SELECTs as one shard_map program over "
       "it, tables row-sharded; 0 or 1 = one device, no mesh.  More than "
       "jax.devices() holds is an error, never a fallback")
define("param_queries", True,
       "auto-parameterize WHERE literals (plan/paramize.py): one plan-cache "
       "entry and one compiled executable serve every literal variant of a "
       "query shape; 0 restores SQL-text-keyed caching with baked literals")
from .dispatch import BatchDispatcher
from . import caps, executor, streaming
from . import fragments as _fragments  # noqa: F401 — registers the
# fragment_pushdown / fragment_retry_max flags at session load (SET and
# the CLI must see them before the first pushed dispatch)
from .executor import (_CapBox, compile_plan, count_shuffle_rounds,
                       exchange_summary)

# join overflow retry budget lives in FLAGS.join_retry_max: retries settle
# at most one operator per re-trace, so a chain of N joins can need N rounds
# in the worst case (each is a recompile)
# INSERT..SELECT at or below this lands in the hot (WAL-durable) row tier;
# above it, the bulk cold path (durable at the next checkpoint)
HOT_INSERT_ROWS = 100_000
# a result of at most this many lanes leaves the chip whole, mask and all,
# in one transfer (32 KiB an 8-byte column), and the host keeps its live
# rows; a larger one is counted and gathered on the device first
EGRESS_HOST_LANES = 1 << 13


# server-level system variable defaults (reference: the session_variables
# map MySQL clients read at connect; SHOW VARIABLES and SELECT @@x share it)
_SERVER_VARS = {
    "version": "8.0.0-baikaldb-tpu",
    "version_comment": "baikaldb_tpu (JAX/XLA)",
    "lower_case_table_names": "0",
    "max_allowed_packet": str(1 << 24),
    "character_set_server": "utf8mb4",
    "character_set_client": "utf8mb4",
    "character_set_results": "utf8mb4",
    "collation_server": "utf8mb4_bin",
    "collation_connection": "utf8mb4_bin",
    "autocommit": "ON",
    "sql_mode": "STRICT_TRANS_TABLES",
    "tx_isolation": "REPEATABLE-READ",
    "transaction_isolation": "REPEATABLE-READ",
    "wait_timeout": "28800",
    "interactive_timeout": "28800",
    "net_write_timeout": "60",
    "time_zone": "SYSTEM",
    "system_time_zone": "UTC",
    "init_connect": "",
    "license": "Apache-2.0",
    "performance_schema": "0",
}

_CONN_IDS = itertools.count(1)


def next_conn_id() -> int:
    """One connection-id space for embedded Sessions AND wire connections:
    KILL <id> and the processlist Id column resolve against the same
    counter no matter which door the client came through."""
    return next(_CONN_IDS)

_ENV_FNS = ("database", "schema", "user", "current_user", "session_user",
            "system_user", "connection_id", "version")


def _opt_on(v) -> bool:
    """Table-option truth: parser option values arrive as strings, so
    BINLOG=0 / BINLOG=false must read as OFF."""
    if v is None:
        return False
    return str(v).strip().lower() not in ("", "0", "false", "off", "no")


def _env_alias(e):
    """MySQL column captions for environment expressions: SELECT @@version
    titles the column '@@version', DATABASE() titles it 'DATABASE()'."""
    from ..expr.ast import Call
    if isinstance(e, Call):
        if e.op == "__sysvar__":
            return "@@" + e.args[0].value
        if e.op == "__uservar__":
            return "@" + e.args[0].value
        if e.op in _ENV_FNS and not e.args:
            return f"{e.op.upper()}()"
    return None


@functools.lru_cache(maxsize=64)
def _show_like_rx(pat: str):
    """Compiled SHOW ... LIKE matcher (MySQL semantics: case-insensitive,
    wildcard/escape translation shared with expression-level LIKE)."""
    import re

    from ..expr.compile import _like_to_regex
    return re.compile(_like_to_regex(pat), re.IGNORECASE)


def _empty_info(name: str):
    return schema_to_arrow(Catalog.INFORMATION_SCHEMA[name]).empty_table()


def _string_dictionaries(batches: dict):
    """The dictionary of every string column of the batches."""
    for b in batches.values():
        for c in getattr(b, "columns", ()):
            if c.dictionary is not None:
                yield c.dictionary


def _dictionary_keys(batches: dict) -> dict:
    """lineage token -> order keys on the device, for every growing
    dictionary with a tail among the batches' string columns."""
    out = {}
    for d in _string_dictionaries(batches):
        if d.tailed and d.lineage.token not in out:
            out[d.lineage.token] = d.lineage.keys_device()
    return out


def _bakes_outgrown(raw, batches: dict) -> bool:
    """Whether a program's trace read a growing dictionary's values (a
    string function's table, a join's merge) up to an ``n`` some input
    dictionary of that lineage now passes."""
    baked = getattr(raw, "baked", None)
    if not baked:
        return False
    return any(d.lineage is not None and d.lineage.id in baked
               and len(d) > baked[d.lineage.id]
               for d in _string_dictionaries(batches))


def _coerce_row(row: dict, schema: pa.Schema) -> dict:
    """A row dict with every value as the table's Arrow type stores it."""
    return pa.Table.from_pylist([row], schema=schema).to_pylist()[0]


def _stmt_image(kind: str, s) -> str:
    where = f" WHERE {s.where!r}" if getattr(s, "where", None) is not None else ""
    if kind == "update":
        sets = ", ".join(f"{n}={e!r}" for n, e in s.assignments)
        return f"UPDATE {s.table.name} SET {sets}{where}"
    if kind == "replace":
        return f"REPLACE INTO {s.table.name} ({len(s.rows)} rows)"
    if kind == "upsert":
        sets = ", ".join(f"{c}={v!r}" for c, v in s.on_dup)
        return (f"INSERT INTO {s.table.name} ({len(s.rows)} rows) "
                f"ON DUPLICATE KEY UPDATE {sets}")
    return f"DELETE FROM {s.table.name}{where}"


def _is_vector_component(name: str, vcols: dict) -> bool:
    if not name.startswith("__"):
        return False
    return _component_owner(name, vcols) is not None

def _component_owner(name: str, vcols: dict):
    for v in vcols:
        if name.startswith(f"__{v}_") and name[len(v) + 3:].isdigit():
            return v
    return None


def _parse_vector(v, dim: int):
    if v is None:
        return [None] * dim
    if isinstance(v, str):
        body = v.strip().lstrip("[").rstrip("]").replace(",", " ")
        vals = [float(x) for x in body.split()]
    else:
        vals = [float(x) for x in v]
    if len(vals) != dim:
        raise PlanError(f"vector literal has {len(vals)} components, "
                        f"expected {dim}")
    return vals


def _expand_vector_arrow(t: pa.Table, vcols: dict) -> pa.Table:
    """Split list-typed vector columns into float32 component columns
    (NULL vectors allowed, like the row path)."""
    for name, dim in vcols.items():
        if name not in t.column_names:
            continue
        rows = t.column(name).to_pylist()
        mat = np.zeros((len(rows), dim), np.float32)
        isnull = np.zeros(len(rows), bool)
        for i, v in enumerate(rows):
            if v is None:
                isnull[i] = True
                continue
            if len(v) != dim:
                raise PlanError(f"vector column {name!r} expects dim {dim}")
            mat[i] = v
        t = t.drop_columns([name])
        for i in range(dim):
            t = t.append_column(
                f"__{name}_{i}",
                pa.array(mat[:, i], pa.float32(),
                         mask=isnull if isnull.any() else None))
    return t


def _expand_vector_row(r: dict, vcols: dict) -> dict:
    out = dict(r)
    for name, dim in vcols.items():
        if name in out:
            vals = _parse_vector(out.pop(name), dim)
            for i, x in enumerate(vals):
                out[f"__{name}_{i}"] = x
    return out


def _qualify_free(e):
    """Strip table qualifiers: region batches carry plain column names."""
    from ..expr.ast import AggCall, Call, ColRef

    if isinstance(e, ColRef):
        return ColRef(e.name)
    if isinstance(e, AggCall):
        raise PlanError("aggregates not allowed in UPDATE/DELETE")
    if isinstance(e, Call):
        return Call(e.op, tuple(_qualify_free(a) for a in e.args))
    return e


@dataclass
class Result:
    """Query result (the PacketNode analog: result set or affected-rows OK)."""
    columns: list[str] = field(default_factory=list)
    arrow: Optional[pa.Table] = None
    affected_rows: int = 0
    plan_text: Optional[str] = None

    @property
    def rows(self) -> list[tuple]:
        if self.arrow is None:
            return []
        cols = [self.arrow.column(i).to_pylist() for i in range(self.arrow.num_columns)]
        return [tuple(c[i] for c in cols) for i in range(self.arrow.num_rows)]

    def to_pylist(self) -> list[dict]:
        return [] if self.arrow is None else self.arrow.to_pylist()

    def scalar(self):
        r = self.rows
        return r[0][0] if r else None


class _TableBinlogRetry:
    """One table's CDC retry state: a queue of failed distributed-binlog
    event batches plus the lock that serializes this table's drain/append
    rounds.  Rank 20: acquired INSIDE the store lock (10) by the autocommit
    CDC path and BEFORE the replicated tier's lock (30) when a queued append
    retries through the distributed binlog.  Every instance shares the
    runtime name ``db.binlog_retry_mu`` — one rank covers the per-table
    family, and two tables' locks (same rank) are never nested."""

    __slots__ = ("mu", "q")
    RANK = 20

    def __init__(self):
        from ..analysis.runtime import GuardedLock
        self.mu = GuardedLock("db.binlog_retry_mu", rank=self.RANK)
        self.q: deque = deque()


# instances are lazy (first binlogged table), but the declared rank must be
# visible to the static<->runtime consistency check from import time
from ..analysis.runtime import LOCK_RANKS as _LOCK_RANKS  # noqa: E402

_LOCK_RANKS.setdefault("db.binlog_retry_mu", _TableBinlogRetry.RANK)


class Database:
    """Shared engine state: catalog + table stores (one per server).

    With ``data_dir`` set the engine is durable: every table gets a WAL for
    hot DML (storage/column_store.py row tier), DDL persists the catalog as
    JSON, and ``checkpoint()`` flushes cold Parquet + resets WALs.  A new
    Database over the same directory recovers committed state — the analog
    of baikalStore restart recovery (SURVEY §3.4)."""

    def __init__(self, data_dir: Optional[str] = None, fleet=None,
                 cluster=None, cold_dir: Optional[str] = None,
                 read_replica: str = "leader", read_tag: str = "",
                 read_max_lag: int = 0):
        """``fleet``: a raft.fleet.StoreFleet — when set, every table's hot
        row tier is raft-replicated across the fleet's store nodes (DML
        quorum-commits through region raft groups; a new Database over the
        same fleet recovers committed state from the replicas).  The
        reference's always-on mode: every DML is a raft apply on a Region
        (src/store/region.cpp:1961,2301).

        ``cluster``: a storage.remote_tier.ClusterClient (or "host:port" of
        the meta daemon) — the multi-process variant of ``fleet``: the same
        replication discipline, but regions live in real store daemon
        processes reached over TCP (the three-binary deployment,
        src/protocol/main.cpp + store/main.cpp + meta_server/main.cpp)."""
        self.catalog = Catalog()
        self.fleet = fleet
        if isinstance(cluster, str):
            from ..storage.remote_tier import ClusterClient
            cluster = ClusterClient(cluster)
        self.cluster = cluster
        if data_dir and (fleet is not None or cluster is not None):
            # the replicated tier IS the durability story in fleet/cluster
            # mode; silently skipping the requested WAL would be worse than
            # refusing (the operator asked for local durability)
            raise ValueError("data_dir cannot combine with fleet/cluster "
                             "mode: durability lives in the replicated tier")
        self.stores: dict[str, TableStore] = {}
        # MVCC plane (storage/mvcc.py): one TSO client per Database — in
        # fleet mode it draws batched grants from the meta service's
        # oracle, so every frontend on the fleet shares one clock — plus
        # the snapshot pin registry feeding the GC watermark
        from ..storage.mvcc import MvccRuntime
        self.mvcc = MvccRuntime(
            fleet.meta.tso.gen if fleet is not None else None)
        # fleet telemetry plane (obs/telemetry.py): registered daemon
        # addresses polled into information_schema.cluster_metrics /
        # SHOW STATUS cluster.* rows; cheap until daemons register (no
        # thread, no RPC) — device HBM gauges install into REGISTRY here
        from ..obs.telemetry import Telemetry
        self.telemetry = Telemetry()
        if cluster is not None:
            # three-binary deployment: meta + its registered stores join
            # the scrape set automatically (instances refresh per poll)
            self.telemetry.attach_meta(
                f"{cluster.meta.host}:{cluster.meta.port}")
            # ... and the AOT executable tier replicates through the same
            # deployment: this node publishes its compilations to the
            # store daemons and warm-starts from its peers'
            compilecache.AOT.attach_peer(
                f"{cluster.meta.host}:{cluster.meta.port}")
            # real TCP daemons: scrape in the background (telemetry_poll_s)
            # so cluster_metrics / SHOW STATUS read a warm cache instead of
            # paying a serial fleet RPC round inline per query
            self.telemetry.start()
        # query statistics ring (reference: slow-SQL collection + print_agg_sql,
        # network_server.h:82-107) — feeds information_schema.query_log
        self.query_log = deque(maxlen=1000)
        # the deployment's mesh (flag mesh_devices) and the row-sharded
        # copies of its tables: one copy a table, whatever the number of
        # connections reading it
        self._mesh = None
        self._mesh_mu = threading.Lock()
        self._mesh_batches: dict = {}
        from ..storage.binlog import Binlog
        self.qos = None          # optional utils.qos.QosManager
        # cross-query batched dispatch (exec/dispatch.py): engine-wide so
        # concurrent SESSIONS coalesce onto one device batch per tick
        self.dispatcher = BatchDispatcher()
        self.privileges = PrivilegeManager()
        from ..meta.ddl import DdlManager
        self.ddl = DdlManager(self)   # online-DDL work queue + worker
        # live connections for SHOW PROCESSLIST (id -> dict), kept by the
        # wire server (reference: show processlist over NetworkServer conns)
        self.processlist: dict[int, dict] = {}
        # always-on flight recorder (obs/flightrec.py): bounded ring of
        # completed-query summaries; slow/killed/failed queries keep a full
        # forensic bundle — SELECT * FROM information_schema.flight_recorder
        self.flightrec = FlightRecorder()
        # wedged-query detector: scans this Database's live QueryProgress
        # records for silent beats (obs/watchdog.py); the thread only runs
        # in cluster mode — embedded single-process tests scan on demand
        self.watchdog = QueryWatchdog(db=self)
        if cluster is not None:
            self.watchdog.start()
        # committed-txn CDC batches whose distributed-binlog append failed:
        # PER-TABLE queues of event batches retried on later flushes instead
        # of silently dropped (bounded; overflow counts in
        # metrics.binlog_events_dropped).  CDC ordering is a per-table
        # contract, so each table gets its own queue+lock: one table's dead
        # binlog region no longer convoys every other table's commits (the
        # old engine-wide db.binlog_retry_mu), and holding the table's lock
        # across the drain-check AND the append closes the release-to-append
        # race the global design had in column_store._write_hot
        self._binlog_retry: dict[str, _TableBinlogRetry] = {}
        self._binlog_retry_reg_mu = threading.Lock()    # registry dict only
        self.data_dir = data_dir
        # external cold-storage FS (AFS stand-in, storage/coldfs): segment
        # bytes live here, manifests replicate through the region groups
        self.cold_dir = cold_dir
        self._cold_fs = None
        # read routing (reference: fetcher_store.cpp:351 choose_opt_instance
        # — leader for writes; follower/learner resource-isolated reads):
        # "follower" serves this frontend's table rebuilds from non-leader
        # replicas under a bounded applied-index staleness check, optionally
        # pinned to instances with a resource tag (the OLAP-isolated reader)
        self.read_replica = read_replica
        self.read_tag = read_tag
        self.read_max_lag = int(read_max_lag)
        from ..cdc import ChangeStreams, MatViews
        if data_dir:
            import os
            os.makedirs(data_dir, exist_ok=True)
            # WAL-backed binlog: CDC events + capturer checkpoints survive
            # kill-9 with the rest of the durable tier (region_binlog analog)
            self.binlog = Binlog(path=os.path.join(data_dir, "binlog.wal"))
            # change-stream + matview registries attach BEFORE recovery:
            # _recover re-arms persisted subscriptions and views against
            # the already-recovered binlog cursors
            self.cdc = ChangeStreams(self)
            self.matviews = MatViews(self)
            self._recover()
        else:
            self.binlog = Binlog()
            self.cdc = ChangeStreams(self)
            self.matviews = MatViews(self)

    def close(self) -> None:
        """Stop this Database's background machinery — today the fleet
        telemetry poller (auto-started in cluster mode), whose scrape RPCs
        would otherwise outlive a discarded Database, paying timeouts
        against dead daemon addresses forever.  Idempotent."""
        self.telemetry.stop()
        self.watchdog.stop()
        self.mvcc.stop_gc()

    def store(self, key: str) -> TableStore:
        return self.stores[key]

    @property
    def mesh(self):
        """The mesh ``mesh_devices`` asks for, or None below two devices."""
        n = int(FLAGS.mesh_devices)
        if n < 2:
            return None
        m = self._mesh
        if m is None or m.devices.size != n:
            from ..parallel.mesh import make_mesh
            with self._mesh_mu:
                m = self._mesh
                if m is None or m.devices.size != n:
                    try:
                        m = self._mesh = make_mesh(n)
                    except ValueError as e:
                        raise SqlError(f"mesh_devices: {e}") from None
        return m

    def sharded_batch(self, table_key: str, store: TableStore,
                      mesh) -> ColumnBatch:
        """Row-shard a table across ``mesh`` (cached per table version) —
        the region-to-store placement analog: each mesh device holds one
        horizontal slice, padded to SPMD-equal length.  Sharding runs under
        the lock: a second connection waits for the first one's copy
        instead of making its own."""
        from ..parallel.mesh import shard_batch

        # bucket config joins the key: flipping batch_bucketing (or the
        # bucket floor) must re-shard, not serve a cached batch of the
        # other shape discipline
        ck = (table_key, mesh, store.version, bool(FLAGS.batch_bucketing),
              int(FLAGS.batch_bucket_min))
        with self._mesh_mu:
            b = self._mesh_batches.get(ck)
            if b is None:
                # one copy a table: drop its stale versions (and a copy
                # made for another mesh) before caching the new one
                self._mesh_batches = {
                    k: v for k, v in self._mesh_batches.items()
                    if k[0] != table_key}
                b = shard_batch(store.device_table_batch(), mesh)
                self._mesh_batches[ck] = b
        return b

    @staticmethod
    def attach_aot_peer(meta_address: str) -> None:
        """Join the fleet AOT executable tier without full cluster mode:
        publish compiled artifacts to / warm-start from the store daemons
        behind this meta service (the cache tier is process-wide, so one
        attach serves every Database in the process)."""
        compilecache.AOT.attach_peer(meta_address)

    _BINLOG_RETRY_MAX = 1024    # queued batches PER TABLE; beyond, oldest drop

    def binlog_retry_queue(self, table_key: str) -> _TableBinlogRetry:
        """This table's retry state (created on first use)."""
        rq = self._binlog_retry.get(table_key)
        if rq is None:
            with self._binlog_retry_reg_mu:
                rq = self._binlog_retry.setdefault(table_key,
                                                   _TableBinlogRetry())
        return rq

    def binlog_retry_pending(self) -> list[str]:
        """Tables with queued retry batches (unlocked snapshot — callers
        take the per-table lock before acting)."""
        return [tk for tk, rq in list(self._binlog_retry.items()) if rq.q]

    def binlog_retry_depth(self, table_key: Optional[str] = None) -> int:
        """Queued batch count, per table or engine-wide (tests/metrics)."""
        if table_key is not None:
            rq = self._binlog_retry.get(table_key)
            return len(rq.q) if rq is not None else 0
        return sum(len(rq.q) for rq in list(self._binlog_retry.values()))

    def discard_binlog_retry(self, table_key: str) -> None:
        """Forget a DROPPED table's retry state: queued batches count as
        dropped (no table, no subscribers to replay to — retrying them
        forever against dist.append would be phantom CDC), and the registry
        entry goes away so the per-commit pending scan stays O(live tables)
        under create/drop churn."""
        with self._binlog_retry_reg_mu:
            rq = self._binlog_retry.pop(table_key, None)
        if rq is not None:
            with rq.mu:
                while rq.q:
                    metrics.binlog_events_dropped.add(len(rq.q.popleft()))

    def drain_binlog_retry(self, dist) -> None:
        """Re-attempt queued distributed-binlog appends, table by table.
        Thread-safe; tables are independent — one table's dead binlog
        region stops only ITS queue, never another table's."""
        for tk in self.binlog_retry_pending():
            rq = self.binlog_retry_queue(tk)
            with rq.mu:
                self._drain_rq_locked(rq, tk, dist)

    def _drain_rq_locked(self, rq: _TableBinlogRetry, table_key: str,
                         dist) -> None:
        """Arrival-order drain of ONE table's queue; the first failure stops
        it (the region is likely still down — later batches of this table
        must not jump the queue).  Caller holds rq.mu."""
        q = rq.q
        for _ in range(len(q)):
            events = q.popleft()
            try:
                dist.append(table_key, events)
            except Exception:   # noqa: BLE001
                q.appendleft(events)
                break

    def _queue_rq_locked(self, rq: _TableBinlogRetry, events: list) -> None:
        """Caller holds rq.mu."""
        rq.q.append(events)
        metrics.binlog_retry_queued.add(len(events))
        while len(rq.q) > self._BINLOG_RETRY_MAX:
            dropped = rq.q.popleft()
            metrics.binlog_events_dropped.add(len(dropped))

    def dist_binlog(self):
        """The cluster's distributed binlog writer (storage/binlog_regions)
        — None off the daemon plane or when binlog_regions is off."""
        if self.cluster is None:
            return None
        from ..storage.binlog_regions import DistributedBinlog

        if not FLAGS.binlog_regions:
            return None
        dl = getattr(self, "_dist_binlog", None)
        if dl is None:
            dl = self._dist_binlog = DistributedBinlog(self.cluster)
        return dl

    def cold_fs(self, required: bool = False):
        """The external cold-storage FS, or None when unconfigured."""
        if self._cold_fs is None:
            root = self.cold_dir or str(FLAGS.cold_fs_dir)
            if root:
                from ..storage.coldfs import ExternalFS

                self._cold_fs = ExternalFS(root)
        if required and self._cold_fs is None:
            raise PlanError("no cold storage configured (set cold_dir or "
                            "the cold_fs_dir flag)")
        return self._cold_fs

    def _new_store(self, info) -> TableStore:
        """A TableStore joined to this Database's MVCC plane (shared TSO
        clock + snapshot pin registry)."""
        st = TableStore(info)
        st.attach_mvcc(self.mvcc)
        return st

    def make_store(self, info) -> TableStore:
        """Create a table's store; durable (WAL-attached) under data_dir,
        raft-replicated when the Database is fleet-bound."""
        key = f"{info.database}.{info.name}"
        if self.fleet is not None:
            from ..storage.replicated import ReplicatedRowTier
            st = self._new_store(info)
            tier = ReplicatedRowTier.get_or_create(
                self.fleet, info.table_id, key, st._row_schema(),
                [ROWID_COL])
            fs = self.cold_fs()
            check_cold_readable(tier, fs, key)
            cold = tier.cold_rows(fs) if fs is not None else None
            hot = None
            if self.read_replica == "follower":
                hot = tier.follower_rows(max_lag=self.read_max_lag,
                                         resource_tag=self.read_tag)
            st.attach_replicated(tier, cold_rows=cold, hot_rows=hot)
            return st
        if self.cluster is not None:
            from ..storage.remote_tier import RemoteRowTier
            st = self._new_store(info)
            tier = RemoteRowTier.get_or_create(
                self.cluster, key, st._row_schema(), [ROWID_COL])
            fs = self.cold_fs()
            # checked eagerly even for a deferred attach: a frontend that
            # cannot read the cold tier must refuse the table at attach,
            # not at first query
            check_cold_readable(tier, fs, key)
            if not info.name.startswith("__") and \
                    _opt_on((info.options or {}).get("binlog")):
                # binlog is opt-in per table, like the reference's
                # link-to-binlog option (CREATE TABLE ... BINLOG=1):
                # unlinked tables keep 1PC write latency.  Hidden backing
                # tables (global-index, rollups) ride their main table's
                # events — a sink there would double-log
                st.binlog_sink = self.dist_binlog()
                # back-reference for the autocommit ordering guard: queued
                # retry batches must drain before a fresh autocommit CDC
                # event for the same table lands (column_store._write_hot)
                st.binlog_db = self
            if str(FLAGS.pushdown_reads) != "off":
                # defer the full-region pull: eligible SELECTs execute as
                # pushed fragments ON the store daemons (the reference's
                # read architecture); the image materializes only when a
                # query actually needs it
                st.attach_replicated_lazy(tier, fs)
                return st
            # one manifest fetch: cold_rows returns [] when no cold exists
            cold = tier.cold_rows(fs) if fs is not None else None
            st.attach_replicated(tier, cold_rows=cold)
            return st
        if not self.data_dir:
            return self._new_store(info)
        import os
        st = self._new_store(info)
        pq_dir = os.path.join(self.data_dir, key)
        if os.path.isdir(pq_dir):
            st.load_parquet(pq_dir)
        st.durable_dir = pq_dir
        st.attach_wal(os.path.join(self.data_dir, key + ".wal"))
        return st

    # -- durability -------------------------------------------------------
    def save_catalog(self):
        if not self.data_dir:
            return
        import json
        import os
        dbs = [d for d in self.catalog.databases()
               if d != "information_schema"]
        out = {"databases": dbs, "tables": []}
        for db in dbs:
            for t in self.catalog.tables(db):
                info = self.catalog.get_table(db, t)
                out["tables"].append({
                    "database": db, "name": t,
                    "fields": [[f.name, f.ltype.value, f.nullable]
                               for f in info.schema.fields],
                    "indexes": [[ix.name, ix.kind, list(ix.columns),
                                 {k: v for k, v in ix.params.items()
                                  if k != "fresh_at"}]   # refresh on restart
                                for ix in info.indexes],
                    "options": dict(info.options or {}),
                })
        vsnap = self.catalog._views      # ONE published dict: a concurrent
        #                                  DROP VIEW swaps the attr, never
        #                                  mutates this snapshot
        out["views"] = [
            {"database": k.split(".", 1)[0], "name": k.split(".", 1)[1], **v}
            for k, v in sorted(vsnap.items())
            if k.split(".", 1)[0] in dbs]
        out["subscriptions"] = self.cdc.to_meta()
        out["matviews"] = self.matviews.to_meta()
        tmp = os.path.join(self.data_dir, "catalog.json.tmp")
        with open(tmp, "w") as f:
            json.dump(out, f)
        os.replace(tmp, os.path.join(self.data_dir, "catalog.json"))

    def _recover(self):
        import json
        import os
        path = os.path.join(self.data_dir, "catalog.json")
        if not os.path.exists(path):
            return
        with open(path) as f:
            saved = json.load(f)
        for db in saved["databases"]:
            if db not in self.catalog.databases():
                self.catalog.create_database(db, if_not_exists=True)
        resume: list[tuple[str, IndexInfo]] = []
        for t in saved["tables"]:
            fields = tuple(Field(n, LType(v), nullable)
                           for n, v, nullable in t["fields"])
            indexes = [IndexInfo(ix[0], ix[1], ix[2],
                                 ix[3] if len(ix) > 3 else {})
                       for ix in t["indexes"]]
            info = self.catalog.create_table(
                t["database"], t["name"], Schema(fields), indexes,
                options=t["options"], if_not_exists=True)
            key = f"{t['database']}.{t['name']}"
            self.stores[key] = self.make_store(info)
            for ix in indexes:
                if ix.params.get("state") == "backfilling":
                    resume.append((key, ix))
        for v in saved.get("views", []):
            self.catalog.create_view(v["database"], v["name"], v["sql"],
                                     v.get("columns"), or_replace=True)
        # durable CDC cursors were recovered with the binlog; these entries
        # re-attach the subscription objects (and their GC holds) to them
        self.cdc.recover(saved.get("subscriptions"))
        self.matviews.recover(saved.get("matviews"))
        # resume interrupted backfills only AFTER every table is loaded:
        # the worker save_catalog()s at publish, and a snapshot taken
        # mid-recovery would persist a catalog missing later tables
        for key, ix in resume:
            self.ddl.submit(key, ix)

    def checkpoint(self):
        """Flush every table's live state to Parquet + reset WALs (the
        hot->cold flush boundary, region_olap.cpp:445)."""
        if not self.data_dir:
            raise RuntimeError("checkpoint requires a data_dir")
        import os
        for key, st in self.stores.items():
            st.checkpoint(os.path.join(self.data_dir, key))
        self.save_catalog()


class Session:
    def __init__(self, db: Optional[Database] = None, database: str = "default",
                 mesh=None, user: str = "root"):
        """``mesh``: a jax.sharding.Mesh with one axis — when set, every
        SELECT plans through plan/distribute.py and executes as a single
        shard_map program over the mesh (scans row-sharded across devices,
        exchanges as ICI collectives — the MPP mode, SURVEY §3.2).  Left
        out, the session runs on the deployment's mesh (``Database.mesh``,
        flag ``mesh_devices``), which is none by default.
        ``user``: the authenticated account; statements are checked against
        its grants (reference: privilege_manager + per-statement checks)."""
        self.db = db or Database()
        self.current_db = database
        self.user = user
        self._mesh = mesh
        # SQL-text-keyed compiled plans, LRU-bounded (FLAGS.plan_cache_size;
        # a long-lived server must not leak one executable per distinct
        # query text)
        self._plan_cache: OrderedDict = OrderedDict()
        # active SQL transaction: table_key -> storage TxnContext (row-tier
        # locks + buffered WAL writes + zero-copy region pre-images; the
        # reference's Transaction, src/engine/transaction.cpp:98-396)
        self._sql_txn: Optional[dict] = None
        # (pin id, ts) of the open transaction's snapshot (_txn_snapshot)
        self._txn_pin: Optional[tuple] = None
        # session variables (@vars + per-session system vars via SET)
        self.session_vars: dict = {}
        # binlog events buffered until COMMIT (discarded on ROLLBACK) so CDC
        # subscribers never see uncommitted changes
        self._txn_binlog: list = []
        # PREPARE name FROM '...' bodies (text, re-parsed per EXECUTE; the
        # auto-parameterized plan cache dedups the compiled executables)
        self._prepared: dict[str, str] = {}
        # explicit MVCC snapshot (SET SNAPSHOT): (pin_id, snap_ts) in the
        # Database's pin registry, or None.  Automatic analytical pins are
        # per-SELECT (scoped inside _select) and never land here.
        self._snapshot: Optional[tuple[int, int]] = None
        # the snapshot ts the CURRENT query runs at (0 = unpinned read) —
        # query_log / EXPLAIN ANALYZE read it; set per-SELECT
        self._snap_ts: int = 0

    @property
    def mesh(self):
        return self._mesh if self._mesh is not None else self.db.mesh

    def _log_binlog(self, event_type, db_name, table, rows=None, statement="",
                    affected=0):
        if rows and len(rows) > 1000:
            # bulk ingest: statement image only (avoid O(n) python row images)
            statement = statement or f"bulk insert {len(rows)} rows"
            rows = None
        if self._sql_txn is not None:
            self._txn_binlog.append((event_type, db_name, table, rows,
                                     statement, affected))
            return
        self.db.binlog.append(event_type, db_name, table, rows=rows,
                              statement=statement, affected=affected)

    # -- access control ---------------------------------------------------
    def _stmt_dbs(self, s) -> set[str]:
        """Databases a SELECT reads — FROM/joins/CTEs/unions AND expression
        subqueries (WHERE/items/HAVING), so a subquery can't read around the
        grants (coarse db-granular enforcement like the reference's)."""
        from ..expr.ast import Subquery

        out: set[str] = set()

        def walk_expr(e):
            if e is None:
                return
            if isinstance(e, Subquery):
                walk_sel(e.stmt)
                return
            for a in getattr(e, "args", ()):
                walk_expr(a)

        def walk_sel(st):
            refs = ([st.table] if st.table is not None else []) + \
                   [j.table for j in st.joins]
            for r in refs:
                if r.subquery is not None:
                    walk_sel(r.subquery)
                else:
                    out.add(r.database or self.current_db)
            for j in st.joins:
                walk_expr(j.on)
            for it in st.items:
                walk_expr(it.expr)
            walk_expr(st.where)
            walk_expr(st.having)
            for _, sub in st.ctes:
                walk_sel(sub)
            if st.union is not None:
                walk_sel(st.union[1])

        walk_sel(s)
        return out or {self.current_db}

    def _access_check(self, s):
        P = self.db.privileges
        if isinstance(s, (CreateUserStmt, DropUserStmt, GrantStmt,
                          RevokeStmt, HandleStmt)):
            u = P.users.get(self.user)
            if u is None or not u.is_super:
                raise AccessError(f"{type(s).__name__} requires SUPER")
            return
        if isinstance(s, SelectStmt):
            for db in self._stmt_dbs(s):
                P.check(self.user, db, READ)
            return
        if isinstance(s, (InsertStmt, UpdateStmt, DeleteStmt, TruncateStmt,
                          LoadDataStmt)):
            P.check(self.user, s.table.database or self.current_db, WRITE)
            # reads feeding the write are grants too (INSERT..SELECT,
            # subqueries in WHERE/assignments)
            if isinstance(s, InsertStmt) and s.select is not None:
                for db in self._stmt_dbs(s.select):
                    P.check(self.user, db, READ)
            from ..expr.ast import Subquery

            def sub_dbs(e):
                if e is None:
                    return
                if isinstance(e, Subquery):
                    for db in self._stmt_dbs(e.stmt):
                        P.check(self.user, db, READ)
                    return
                for a in getattr(e, "args", ()):
                    sub_dbs(a)

            sub_dbs(getattr(s, "where", None))
            for _, e in getattr(s, "assignments", []) or []:
                sub_dbs(e)
            return
        if isinstance(s, (CreateTableStmt, DropTableStmt, AlterTableStmt,
                          CreateViewStmt, DropViewStmt, CreateMatViewStmt,
                          DropMatViewStmt)):
            P.check(self.user, s.table.database or self.current_db, WRITE)
            return
        if isinstance(s, (CreateSubscriptionStmt, DropSubscriptionStmt)):
            db = (s.table.database if getattr(s, "table", None) is not None
                  else None) or self.current_db
            P.check(self.user, db, READ)
            return
        if isinstance(s, CreateDatabaseStmt):
            P.check(self.user, s.name, WRITE)
            return
        if isinstance(s, DropDatabaseStmt):
            P.check(self.user, s.name, WRITE)
            return
        if isinstance(s, UseStmt):
            P.check(self.user, s.database, READ)
            return
        if isinstance(s, ExplainStmt):
            for db in self._stmt_dbs(s.stmt):
                P.check(self.user, db, READ)
            return
        if isinstance(s, DescribeStmt):
            P.check(self.user, s.table.database or self.current_db, READ)
            return
        if isinstance(s, ShowStmt):
            # SHOW against another db needs a grant THERE, not on current
            db = s.database or (s.table.database if s.table is not None
                                else None) or self.current_db
            P.check(self.user, db, READ)

    # -- public API -------------------------------------------------------
    def connection_id(self) -> int:
        """This session's id in the shared processlist/KILL space, lazily
        assigned from the same counter the wire server draws from."""
        if not hasattr(self, "_conn_id"):
            self._conn_id = next_conn_id()
        return self._conn_id

    def execute(self, sql: str) -> Result:
        metrics.queries_total.add(1)
        t0 = time.perf_counter()
        marks = metric_marks()   # flight-recorder metric baseline
        err: Optional[BaseException] = None
        spans: list = []
        # the progress record opens here (or at the wire server's _query,
        # whichever ran first — nested opens share the outer record); live
        # for the statement's whole life so SHOW PROCESSLIST, the watchdog
        # and KILL from other threads can see it
        with progress.track(sql, conn_id=self.connection_id(),
                            user=self.user, db=self.db,
                            dbname=self.current_db) as qp:
            try:
                # the per-query trace roots here (or at the wire server's
                # _query, whichever ran first); stage spans nest under it and
                # the keep/drop decision (sampling + slow always-keep) lands
                # when this scope closes (obs/trace.py)
                tmark = trace.mark()
                with trace.root("query", sql):
                    try:
                        res = self._execute(sql)
                    finally:
                        # live-buffer snapshot must happen before the root
                        # closes (the ctx dies with it)
                        spans = trace.since(tmark)
            except Exception as e:
                metrics.queries_failed.add(1)
                err = e
                raise
            finally:
                dur_ms = (time.perf_counter() - t0) * 1e3
                metrics.query_latency.observe(dur_ms)
                if dur_ms > FLAGS.slow_query_ms:
                    metrics.slow_queries.add(1)
                self._flight_record(sql, qp, dur_ms, err, marks, spans)
        if res.arrow is not None:
            metrics.rows_returned.add(res.arrow.num_rows)
        if res.affected_rows:
            metrics.dml_rows.add(res.affected_rows)
        return res

    def _flight_record(self, sql: str, qp, dur_ms: float,
                       err: Optional[BaseException], marks: dict,
                       spans: list) -> None:
        """Flight-recorder entry for the statement that just finished: a
        summary always, plus the full forensic bundle (plan, trace spans,
        metric deltas, device stats, exchange summary) when the query was
        slow, killed, or failed — the three cases an operator digs into
        after the fact."""
        try:
            killed = isinstance(err, QueryKilled)
            slow = dur_ms > float(FLAGS.slow_query_ms)
            summary = {
                "query_id": getattr(qp, "query_id", 0),
                "conn_id": getattr(qp, "conn_id", 0),
                "user": self.user, "db": self.current_db,
                "text": sql, "dur_ms": round(dur_ms, 3),
                "status": ("killed" if killed else
                           "error" if err is not None else "ok"),
                "error": "" if err is None else
                         f"{type(err).__name__}: {err}",
                "phase_ms": {k: round(v, 3)
                             for k, v in qp.phase_ms().items()},
                "rows": getattr(qp, "rows_done", 0),
                "batches": getattr(qp, "batches_done", 0),
                "rounds": getattr(qp, "round_no", 0),
            }
            bundle = None
            if killed or err is not None or slow:
                plan = getattr(qp, "plan", None)
                bundle = {
                    "plan": (plan.tree_repr() if hasattr(plan, "tree_repr")
                             else str(plan)) if plan is not None else "",
                    "spans": spans,
                    "metric_delta": metric_delta(marks),
                    "device_stats": device_stats(),
                    "exchange": getattr(qp, "exchange", None),
                }
            self.db.flightrec.record(summary, bundle=bundle)
        except Exception:
            # forensics must never turn a working query into a failed one
            metrics.count_swallowed("session.flight_record")

    def _execute(self, sql: str) -> Result:
        progress.current().beat(phase="parse")
        with trace.span("parse.sql"):
            stmts = parse_sql(sql)
        if len(stmts) == 1 and isinstance(stmts[0], SelectStmt):
            # select.route is everything between the parser and the plan
            # cache, in four blocks that sum under the one key: admission
            # and the access check here, the snapshot scope's probes
            # (_snapshot_pinned), the fast paths that may answer instead
            # and the auto-parameterization (_select_impl)
            with trace.span("select.route"):
                self._qos_admit(sql, stmts)
                self._access_check(stmts[0])
                stmt, env = self._resolve_session_exprs(stmts[0])
            # env-substituted literals are session state: never cache those
            return self._select(stmt, cache_key=None if env
                                else (sql, self.current_db))
        self._qos_admit(sql, stmts)
        res = Result()
        for s in stmts:
            # check immediately before EACH statement: an earlier USE in the
            # same batch changes what an unqualified name resolves to
            self._access_check(s)
            res = self._execute_stmt(s)
        return res

    def _qos_admit(self, sql: str, stmts) -> None:
        if self.db.qos is None:
            return
        # COMMIT/ROLLBACK are exempt: shedding load must never pin open
        # transactions; batches are charged per statement
        billable = sum(1 for s in stmts if not isinstance(s, TxnStmt))
        if billable:
            self.db.qos.admit(sql, cost=float(billable), user=self.user,
                              tables=self._qos_tables(stmts))

    def query(self, sql: str) -> list[dict]:
        return self.execute(sql).to_pylist()

    def _qos_tables(self, stmts) -> tuple:
        """Base tables a statement batch touches directly (FROM/joins/DML
        target) — the per-table admission dimension.  Deliberately shallow:
        qos gating is a rate limiter, not an access-control wall, so
        subquery tables may ride free."""
        out: list[str] = []
        for s in stmts:
            for t in [getattr(s, "table", None)] + \
                    [j.table for j in getattr(s, "joins", ()) or ()]:
                if t is not None and getattr(t, "subquery", None) is None \
                        and getattr(t, "name", None):
                    out.append(f"{t.database or self.current_db}.{t.name}")
        return tuple(dict.fromkeys(out))

    def _sysvar(self, name: str):
        """@@name lookup: session SETs override server defaults; live flags
        are visible too (they appear in SHOW VARIABLES)."""
        if name in ("tx_isolation", "transaction_isolation"):
            # the two spellings are one variable in MySQL: a SET of either
            # must be visible through both
            for k in ("transaction_isolation", "tx_isolation"):
                if k in self.session_vars:
                    return self.session_vars[k]
            return _SERVER_VARS[name]
        if name in self.session_vars:
            return self.session_vars[name]
        if name in _SERVER_VARS:
            if name == "autocommit":
                return 1 if self.session_vars.get("autocommit",
                                                  "ON") in ("ON", 1) else 0
            return _SERVER_VARS[name]
        flags = FLAGS.snapshot()
        if name in flags:
            return flags[name]
        raise SqlError(f"Unknown system variable '{name}'")

    def _resolve_session_exprs(self, stmt):
        """Substitute connection-environment expressions — @@sysvars, @user
        vars, DATABASE()/USER()/VERSION()/CONNECTION_ID() — with literals
        before planning (reference: these never reach the executor in the
        reference either; the protocol layer answers them).  Returns
        (stmt, changed); changed disables the plan cache for the statement
        since the substituted values are session state."""
        from ..expr.ast import AggCall, Call, Lit, Subquery, WindowCall
        from ..sql.stmt import SelectStmt
        changed = [False]

        def lit(v):
            changed[0] = True
            return Lit(v)

        def walk_e(e):
            if isinstance(e, Call):
                if e.op == "__sysvar__":
                    return lit(self._sysvar(e.args[0].value))
                if e.op == "__uservar__":
                    return lit(self.session_vars.get("@" + e.args[0].value))
                if e.op in ("database", "schema") and not e.args:
                    return lit(self.current_db or None)
                if e.op in ("user", "current_user", "session_user",
                            "system_user") and not e.args:
                    return lit(f"{self.user}@localhost")
                if e.op == "connection_id" and not e.args:
                    return lit(self.connection_id())
                if e.op == "version" and not e.args:
                    return lit(_SERVER_VARS["version"])
                return Call(e.op, tuple(walk_e(a) for a in e.args))
            if isinstance(e, AggCall):
                return AggCall(e.op, tuple(walk_e(a) for a in e.args),
                               e.distinct)
            if isinstance(e, WindowCall):
                return WindowCall(
                    e.op, tuple(walk_e(a) for a in e.args),
                    tuple(walk_e(p) for p in e.partition_by),
                    tuple((walk_e(oe), asc) for oe, asc in e.order_by),
                    e.running, e.frame)
            if isinstance(e, Subquery):
                return Subquery(walk_s(e.stmt))
            return e

        def opt(e):
            return None if e is None else walk_e(e)

        def walk_s(st: SelectStmt) -> SelectStmt:
            from dataclasses import replace
            from ..sql.stmt import OrderItem, SelectItem
            def walk_t(t):
                if t is not None and t.subquery is not None:
                    return replace(t, subquery=walk_s(t.subquery))
                return t

            return replace(
                st,
                items=[SelectItem(opt(it.expr),
                                  it.alias or _env_alias(it.expr),
                                  it.star_table) for it in st.items],
                table=walk_t(st.table),
                where=opt(st.where),
                group_by=[walk_e(g) for g in st.group_by],
                having=opt(st.having),
                order_by=[OrderItem(walk_e(o.expr), o.asc)
                          for o in st.order_by],
                joins=[replace(j, table=walk_t(j.table), on=opt(j.on))
                       for j in st.joins],
                ctes=[(n, walk_s(c)) for n, c in st.ctes],
                union=None if st.union is None
                else (st.union[0], walk_s(st.union[1])))

        from dataclasses import replace as _rep
        from ..sql.stmt import DeleteStmt, InsertStmt, UpdateStmt
        if isinstance(stmt, SelectStmt):
            out = walk_s(stmt)
        elif isinstance(stmt, UpdateStmt):
            out = _rep(stmt, assignments=[(n, walk_e(e))
                                          for n, e in stmt.assignments],
                       where=opt(stmt.where))
        elif isinstance(stmt, DeleteStmt):
            out = _rep(stmt, where=opt(stmt.where))
        elif isinstance(stmt, InsertStmt) and stmt.select is not None:
            out = _rep(stmt, select=walk_s(stmt.select))
        else:
            return (stmt, False)
        return (out, True) if changed[0] else (stmt, False)

    def _set_stmt(self, s: SetStmt) -> Result:
        """SET (reference: setkv_planner.cpp): GLOBAL names update the flag
        registry (and fire its listeners); ``failpoint.<point>`` arms/clears
        the chaos registry (process-global regardless of scope — fault
        injection is a deployment property, not a session one); @vars and
        unknown session names (autocommit, sql_mode, ...) are stored
        per-session — MySQL clients set those on connect and expect silent
        success."""
        from ..utils.flags import FlagError
        for name, value in [(s.name, s.value)] + list(s.more):
            if name.lower() == "snapshot":
                self._set_snapshot(value)
                continue
            if name.lower().startswith("failpoint."):
                from ..chaos import failpoint as _fp
                spec = "" if value is None else str(value)
                if spec.strip().lower() not in ("", "off") and \
                        not bool(FLAGS.chaos_enable):
                    # chaos_enable is the real master switch at the SQL
                    # surface: any connected client can reach SET, and an
                    # armed panic/drop is destructive — clearing is always
                    # allowed, arming needs the deployment to opt in
                    raise SqlError("failpoints are disabled: "
                                   "SET GLOBAL chaos_enable = 1 first")
                try:
                    _fp.set_failpoint(name.lower()[len("failpoint."):],
                                      spec)
                except ValueError as e:
                    raise SqlError(str(e)) from None
                continue
            if s.scope == "global":
                try:
                    if name.lower() == "mesh_devices":
                        have = len(jax.devices())
                        if not 0 <= int(value) <= have:
                            raise SqlError(
                                f"mesh_devices = {value}: this process "
                                f"has {have} device(s)")
                    FLAGS.set_flag(name, value)
                except (FlagError, ValueError) as e:
                    raise SqlError(str(e)) from None
            else:
                self.session_vars[name] = value
        return Result()

    def _set_snapshot(self, value) -> None:
        """SET SNAPSHOT = 'now' | <ts> | 0/''/OFF — pin (or release) this
        session's MVCC read timestamp.  Every subsequent SELECT sees
        exactly the state committed at the pinned instant, regardless of
        concurrent writes; the pin holds the GC watermark until released
        (or it expires past ``snapshot_max_age_s``).  Refusals from the
        ``snapshot.pin`` failpoint surface to the client — an explicit pin
        must not silently degrade to an unpinned read."""
        from ..storage.mvcc import SnapshotRefused
        raw = "" if value is None else str(value).strip()
        if raw.lower() in ("", "0", "off", "none"):
            if self._snapshot is not None:
                self.db.mvcc.snapshots.unpin(self._snapshot[0])
                self._snapshot = None
            return
        if not bool(FLAGS.mvcc):
            raise SqlError("SET SNAPSHOT requires mvcc=1")
        if raw.lower() == "now":
            ts = self.db.mvcc.now_ts()
        else:
            try:
                ts = int(raw)
            except ValueError:
                raise SqlError(
                    f"SET SNAPSHOT expects 'now', a timestamp, or 0/OFF "
                    f"(got {raw!r})") from None
        try:
            with trace.span("snapshot.pin", ts=ts, explicit=True):
                pid = self.db.mvcc.snapshots.pin(
                    ts, query="SET SNAPSHOT", holder=self.user)
        except SnapshotRefused as e:
            raise SqlError(str(e)) from None
        if self._snapshot is not None:
            self.db.mvcc.snapshots.unpin(self._snapshot[0])
        self._snapshot = (pid, ts)

    # -- prepared statements (textual PREPARE/EXECUTE; the wire server's
    # COM_STMT_* path binds ?s into text and rides the same normalizer) ----
    def _prepare_stmt(self, s: PrepareStmt) -> Result:
        stmts = parse_sql(s.sql)
        if len(stmts) != 1:
            raise PlanError("PREPARE body must be a single statement")
        if not isinstance(stmts[0], (SelectStmt, InsertStmt, UpdateStmt,
                                     DeleteStmt)):
            raise PlanError("PREPARE supports SELECT/INSERT/UPDATE/DELETE")
        self._prepared[s.name] = s.sql
        return Result()

    def _execute_prepared(self, s: ExecuteStmt) -> Result:
        sql = self._prepared.get(s.name)
        if sql is None:
            raise PlanError(f"unknown prepared statement {s.name!r}")
        vals = [self.session_vars.get("@" + v) if kind == "var" else v
                for kind, v in s.params]
        stmt = parse_sql(sql)[0]
        need = paramize.count_placeholders(stmt)
        if need != len(vals):
            raise PlanError(f"prepared statement {s.name!r} needs {need} "
                            f"parameters, got {len(vals)}")
        bound = paramize.substitute_placeholders(stmt, vals)
        metrics.prepared_executes.add(1)
        self._access_check(bound)
        if isinstance(bound, SelectStmt):
            bound, env = self._resolve_session_exprs(bound)
            # the text key carries the bound values: distinct values that
            # land in PINNED positions (IN lists, LIMIT) must not collide;
            # hoistable values collapse onto one normalized entry anyway
            key = None if env else \
                (f"{sql} /*execute:{vals!r}*/", self.current_db)
            return self._select(bound, cache_key=key)
        return self._execute_stmt(bound)

    # -- dispatch -----------------------------------------------------------
    def _execute_stmt(self, s) -> Result:
        # DDL implicitly commits any open transaction (MySQL semantics);
        # rolling back across a schema change is not supported
        if isinstance(s, (CreateTableStmt, DropTableStmt, CreateDatabaseStmt,
                          DropDatabaseStmt, TruncateStmt, AlterTableStmt,
                          CreateViewStmt, DropViewStmt, CreateMatViewStmt,
                          DropMatViewStmt)):
            self._commit_txn()
        if isinstance(s, PrepareStmt):
            return self._prepare_stmt(s)
        if isinstance(s, ExecuteStmt):
            return self._execute_prepared(s)
        if isinstance(s, DeallocateStmt):
            if s.name not in self._prepared:
                raise PlanError(f"unknown prepared statement {s.name!r}")
            del self._prepared[s.name]
            return Result()
        if isinstance(s, (SelectStmt, UpdateStmt, DeleteStmt, InsertStmt)):
            # connection-env expressions are legal anywhere MySQL allows
            # an expression — DML included
            s = self._resolve_session_exprs(s)[0]
        if isinstance(s, SelectStmt):
            return self._select(s)
        if isinstance(s, ExplainStmt):
            if s.fmt == "analyze":
                return self._explain_analyze(s.stmt)
            stmt_x = s.stmt
            cand = self._pushdown_candidate(stmt_x)
            if cand is not None:
                txt = self._render_pushdown(*cand)
                return Result(columns=["plan"], plan_text=txt,
                              arrow=pa.table({"plan": txt.split("\n")}))
            rw = self._try_matview(stmt_x, refresh=False)
            if rw is None:
                rw = self._try_rollup(stmt_x, refresh=False)
            if rw is not None:
                stmt_x = rw
            plan = self._plan_select(stmt_x)
            self._annotate_access(plan)
            return Result(columns=["plan"], plan_text=plan.tree_repr(),
                          arrow=pa.table({"plan": plan.tree_repr().split("\n")}))
        if isinstance(s, InsertStmt):
            return self._insert(s)
        if isinstance(s, UpdateStmt):
            return self._update(s)
        if isinstance(s, DeleteStmt):
            return self._delete(s)
        if isinstance(s, CreateTableStmt):
            return self._create_table(s)
        if isinstance(s, CreateViewStmt):
            db = s.table.database or self.current_db
            prior = self.db.catalog.get_view(db, s.table.name)
            try:
                self.db.catalog.create_view(db, s.table.name, s.select_sql,
                                            s.columns, s.or_replace)
            except ValueError as e:
                raise PlanError(str(e)) from None
            # a view shadows nothing but must PLAN against current tables:
            # surface body errors at CREATE, like the reference's validator
            try:
                self._plan_select(parse_sql(
                    f"SELECT * FROM `{db}`.`{s.table.name}`")[0])
            except Exception:
                # a failed OR REPLACE keeps the previous definition (MySQL)
                if prior is not None:
                    self.db.catalog.create_view(db, s.table.name,
                                                prior["sql"],
                                                prior.get("columns"),
                                                or_replace=True)
                else:
                    self.db.catalog.drop_view(db, s.table.name,
                                              if_exists=True)
                raise
            self._plan_cache.clear()
            self.db.save_catalog()
            return Result()
        if isinstance(s, DropViewStmt):
            db = s.table.database or self.current_db
            try:
                self.db.catalog.drop_view(db, s.table.name, s.if_exists)
            except ValueError as e:
                raise PlanError(str(e)) from None
            self._plan_cache.clear()
            self.db.save_catalog()
            return Result()
        if isinstance(s, CreateMatViewStmt):
            db = s.table.database or self.current_db
            try:
                self.db.matviews.create(self, db, s.table.name,
                                        s.select_sql, s.if_not_exists)
            except ValueError as e:
                raise PlanError(str(e)) from None
            self._plan_cache.clear()
            return Result()
        if isinstance(s, DropMatViewStmt):
            db = s.table.database or self.current_db
            self.db.matviews.drop(self, db, s.table.name, s.if_exists)
            self._plan_cache.clear()
            return Result()
        if isinstance(s, CreateSubscriptionStmt):
            table_key = None
            if s.table is not None:
                tdb = s.table.database or self.current_db
                # surface unknown tables at CREATE, not at first FETCH
                self.db.catalog.get_table(tdb, s.table.name)
                table_key = f"{tdb}.{s.table.name}"
            try:
                self.db.cdc.create(s.name, table_key,
                                   if_not_exists=s.if_not_exists)
            except ValueError as e:
                raise PlanError(str(e)) from None
            self.db.save_catalog()
            return Result()
        if isinstance(s, DropSubscriptionStmt):
            try:
                sub = self.db.cdc.subs.get(s.name)
                if sub is not None and sub.internal:
                    raise PlanError(
                        f"subscription {s.name!r} maintains a materialized "
                        "view; drop the view instead")
                self.db.cdc.drop(s.name, s.if_exists)
            except KeyError as e:
                raise PlanError(str(e.args[0])) from None
            self.db.save_catalog()
            return Result()
        if isinstance(s, FetchStmt):
            return self._fetch_stmt(s)
        if isinstance(s, AlterTableStmt):
            return self._alter_table(s)
        if isinstance(s, DropTableStmt):
            from ..index.globalindex import backing_table_name
            from ..index.rollup import rollup_table_name
            db = s.table.database or self.current_db
            rollups, globals_ = [], []
            if self.db.catalog.has_table(db, s.table.name):
                info = self.db.catalog.get_table(db, s.table.name)
                rollups = [ix.name for ix in info.indexes
                           if ix.kind == "rollup"]
                globals_ = [ix.name for ix in info.indexes
                            if ix.kind in ("global", "global_unique")]
            self.db.catalog.drop_table(db, s.table.name, s.if_exists)
            st = self.db.stores.pop(f"{db}.{s.table.name}", None)
            self._drop_durable(f"{db}.{s.table.name}", st)
            self.db.discard_binlog_retry(f"{db}.{s.table.name}")
            # matviews over the dropped base go with it (cascade), like
            # rollups and global indexes below
            self.db.matviews.drop_for_base(self, f"{db}.{s.table.name}")
            for rn in rollups:
                rt = rollup_table_name(s.table.name, rn)
                self.db.catalog.drop_table(db, rt, if_exists=True)
                self._drop_durable(f"{db}.{rt}",
                                   self.db.stores.pop(f"{db}.{rt}", None))
            for gn in globals_:
                gt = backing_table_name(s.table.name, gn)
                self.db.catalog.drop_table(db, gt, if_exists=True)
                self._drop_durable(f"{db}.{gt}",
                                   self.db.stores.pop(f"{db}.{gt}", None))
            self.db.save_catalog()
            return Result()
        if isinstance(s, TruncateStmt):
            store = self._store(s.table)
            store.truncate()
            for _ix, bstore in self._coupled_global(store):
                bstore.truncate()   # global-index entries go with the rows
            self._log_binlog("truncate", s.table.database or self.current_db,
                             s.table.name, statement="truncate")
            return Result()
        if isinstance(s, CreateDatabaseStmt):
            self.db.catalog.create_database(s.name, if_not_exists=s.if_not_exists)
            self.db.save_catalog()
            return Result()
        if isinstance(s, DropDatabaseStmt):
            self.db.catalog.drop_database(s.name, s.if_exists)
            for k in [k for k in self.db.stores if k.startswith(s.name + ".")]:
                self._drop_durable(k, self.db.stores.pop(k))
                self.db.discard_binlog_retry(k)
            self.db.save_catalog()
            return Result()
        if isinstance(s, UseStmt):
            if s.database not in self.db.catalog.databases():
                raise PlanError(f"unknown database {s.database!r}")
            self.current_db = s.database
            return Result()
        if isinstance(s, SetStmt):
            return self._set_stmt(s)
        if isinstance(s, TxnStmt):
            return self._txn_stmt(s)
        if isinstance(s, ShowStmt):
            return self._show(s)
        if isinstance(s, KillStmt):
            return self._kill(s)
        if isinstance(s, CreateUserStmt):
            self.db.privileges.create_user(s.name, s.password, s.if_not_exists)
            return Result()
        if isinstance(s, DropUserStmt):
            self.db.privileges.drop_user(s.name, s.if_exists)
            return Result()
        if isinstance(s, GrantStmt):
            self.db.privileges.grant(s.user, s.level, s.db)
            return Result()
        if isinstance(s, RevokeStmt):
            self.db.privileges.revoke(s.user, s.db)
            return Result()
        if isinstance(s, LoadDataStmt):
            return self._load_data(s)
        if isinstance(s, HandleStmt):
            return self._handle(s)
        if isinstance(s, DescribeStmt):
            db = s.table.database or self.current_db
            if self.db.catalog.get_view(db, s.table.name) is not None:
                # DESCRIBE on a view: plan the view body (no execution) and
                # read the root node's output schema — logical type names
                # match what tables report (MySQL describes views alike)
                stmt = parse_sql(
                    f"SELECT * FROM `{db}`.`{s.table.name}`")[0]
                fields = self._plan_select(stmt).schema.fields
                return Result(
                    columns=["Field", "Type", "Null", "Key"],
                    arrow=pa.table({
                        "Field": [f.name for f in fields],
                        "Type": [f.ltype.value for f in fields],
                        "Null": ["YES" if f.nullable else "NO"
                                 for f in fields],
                        "Key": [""] * len(fields)}))
            info = self.db.catalog.get_table(db, s.table.name)
            pk = info.primary_key()
            pkcols = set(pk.columns) if pk else set()
            vcols = (info.options or {}).get("vector_cols") or {}
            names, types, nulls, keys = [], [], [], []
            for f in info.schema.fields:
                owner = _component_owner(f.name, vcols)
                if owner is not None:
                    if not names or names[-1] != owner:
                        names.append(owner)
                        types.append(f"vector({vcols[owner]})")
                        nulls.append("YES")
                        keys.append("")
                    continue
                names.append(f.name)
                types.append(f.ltype.value)
                nulls.append("YES" if f.nullable else "NO")
                keys.append("PRI" if f.name in pkcols else "")
            return Result(columns=["Field", "Type", "Null", "Key"],
                          arrow=pa.table({"Field": names, "Type": types,
                                          "Null": nulls, "Key": keys}))
        raise SqlError(f"unsupported statement {type(s).__name__}")

    # -- SHOW / admin surface ---------------------------------------------
    def _show_profile(self, s: ShowStmt) -> Result:
        """SHOW PROFILES / SHOW PROFILE [FOR QUERY n] over the kept trace
        store (obs/trace.py) — the per-stage answer to "where did this
        query's time go", reading the SAME span records EXPLAIN ANALYZE
        renders from."""
        # introspection must not pollute the store it reads: never keep
        # the trace of the SHOW statement itself
        trace.discard()
        if s.what == "profiles":
            recs = TRACER.list()
            return Result(
                columns=["Query_ID", "Duration_ms", "Kind", "Query"],
                arrow=pa.table({
                    "Query_ID": pa.array([r["query_id"] for r in recs],
                                         pa.int64()),
                    "Duration_ms": pa.array([r["duration_ms"] for r in recs],
                                            pa.float64()),
                    "Kind": [r["kind"] for r in recs],
                    "Query": [r["text"] for r in recs]}))
        rec = TRACER.get(s.query_id) if s.query_id is not None \
            else TRACER.last()
        if rec is None:
            where = f"query {s.query_id}" if s.query_id is not None \
                else "any query"
            raise PlanError(
                f"no kept trace for {where} (enable tracing: "
                "SET GLOBAL tracing = 1; see SHOW PROFILES)")
        rows = trace.span_tree(rec)
        return Result(
            columns=["Status", "Duration_ms", "Node", "Wait_ms"],
            arrow=pa.table({
                "Status": ["  " * d + sp["name"] for d, sp in rows],
                "Duration_ms": pa.array([sp["dur_ms"] for _, sp in rows],
                                        pa.float64()),
                "Node": [sp.get("node") or "frontend" for _, sp in rows],
                # the span's off-CPU ms (obs/trace.py); events have none
                "Wait_ms": pa.array([sp.get("wait_ms") for _, sp in rows],
                                    pa.float64())}))

    def _show(self, s: ShowStmt) -> Result:
        """SHOW command family (reference: show_helper.cpp's registry)."""
        def like(name: str, pat: str) -> bool:
            # MySQL LIKE for SHOW ... LIKE: case-insensitive; wildcard and
            # \-escape translation shared with expression-level LIKE
            return _show_like_rx(pat).match(name) is not None

        def visible(db):
            # user-facing tables + views: rollup and global-index backing
            # tables are internal
            from ..cdc.views import is_mv_table
            from ..index.globalindex import is_backing_table
            from ..index.rollup import is_rollup_table
            return ([n for n in cat.tables(db) if not is_rollup_table(n)
                     and not is_backing_table(n) and not is_mv_table(n)],
                    list(cat.views(db)))

        cat = self.db.catalog
        if s.what in ("profile", "profiles"):
            return self._show_profile(s)
        if s.what == "databases":
            names = cat.databases()
            return Result(columns=["Database"],
                          arrow=pa.table({"Database": names}))
        if s.what == "tables":
            db = s.database or self.current_db
            tbls, views = visible(db)
            names = sorted(tbls + views)   # MySQL lists views too
            if s.pattern is not None:
                names = [n for n in names if like(n, s.pattern)]
            return Result(columns=[f"Tables_in_{db}"],
                          arrow=pa.table({f"Tables_in_{db}": names}))
        if s.what == "full_tables":
            db = s.database or self.current_db
            tbls, views = visible(db)
            all_names = sorted(tbls + views)
            if s.pattern is not None:
                all_names = [n for n in all_names if like(n, s.pattern)]
            vset = set(views)
            return Result(
                columns=[f"Tables_in_{db}", "Table_type"],
                arrow=pa.table({
                    f"Tables_in_{db}": all_names,
                    "Table_type": ["VIEW" if n in vset else "BASE TABLE"
                                   for n in all_names]}))
        if s.what == "collation":
            # the collations the engine actually implements (reference:
            # show_helper.cpp _show_collation; comparisons support _bin
            # semantics by default and utf8mb4_general_ci via COLLATE)
            rows = [("utf8mb4_bin", "utf8mb4", 46, "Yes"),
                    ("utf8mb4_general_ci", "utf8mb4", 45, ""),
                    ("binary", "binary", 63, "Yes")]
            if s.pattern is not None:
                rows = [r for r in rows if like(r[0], s.pattern)]
            return Result(
                columns=["Collation", "Charset", "Id", "Default",
                         "Compiled", "Sortlen"],
                arrow=pa.table({
                    "Collation": [r[0] for r in rows],
                    "Charset": [r[1] for r in rows],
                    "Id": pa.array([r[2] for r in rows], pa.int64()),
                    "Default": [r[3] for r in rows],
                    "Compiled": ["Yes"] * len(rows),
                    "Sortlen": pa.array([1] * len(rows), pa.int64()),
                }))
        if s.what == "charset":
            rows = [("utf8mb4", "UTF-8 Unicode", "utf8mb4_bin", 4),
                    ("binary", "Binary pseudo charset", "binary", 1)]
            if s.pattern is not None:
                rows = [r for r in rows if like(r[0], s.pattern)]
            return Result(
                columns=["Charset", "Description", "Default collation",
                         "Maxlen"],
                arrow=pa.table({
                    "Charset": [r[0] for r in rows],
                    "Description": [r[1] for r in rows],
                    "Default collation": [r[2] for r in rows],
                    "Maxlen": pa.array([r[3] for r in rows], pa.int64()),
                }))
        if s.what == "engines":
            return Result(
                columns=["Engine", "Support", "Comment", "Transactions",
                         "XA", "Savepoints"],
                arrow=pa.table({
                    "Engine": ["BaikalTPU"],
                    "Support": ["DEFAULT"],
                    "Comment": ["TPU-native columnar HTAP engine (JAX/XLA)"],
                    "Transactions": ["YES"],
                    "XA": ["NO"],
                    "Savepoints": ["YES"]}))
        if s.what == "table_status":
            db = s.database or self.current_db
            tbls, views = visible(db)
            if s.pattern is not None:   # filter names before the per-table store scans
                tbls = [n for n in tbls if like(n, s.pattern)]
                views = [n for n in views if like(n, s.pattern)]
            rows = []
            for n in tbls:
                # don't force-materialize stores for a metadata listing
                # (fleet/cluster tiers, cold segments, WAL attach): a table
                # this frontend hasn't touched reports Rows=NULL (MySQL
                # treats Rows as an estimate; NULL = unknown)
                st = self.db.stores.get(f"{db}.{n}")
                nrows = st.num_rows if st is not None else None
                info = cat.get_table(db, n)
                pspec = (info.options or {}).get("partition")
                rows.append((n, "BaikalTPU", nrows,
                             "partitioned" if pspec else "", ""))
            for n in views:
                rows.append((n, None, None, "", "VIEW"))
            rows.sort(key=lambda r: r[0])
            return Result(
                columns=["Name", "Engine", "Rows", "Collation",
                         "Create_options", "Comment"],
                arrow=pa.table({
                    "Name": [r[0] for r in rows],
                    "Engine": pa.array([r[1] for r in rows], pa.string()),
                    "Rows": pa.array([r[2] for r in rows], pa.int64()),
                    "Collation": pa.array(
                        ["utf8mb4_bin" if r[1] else None for r in rows],
                        pa.string()),
                    "Create_options": [r[3] for r in rows],
                    "Comment": [r[4] for r in rows]}))
        if s.what == "create_table":
            db = s.table.database or self.current_db
            view = cat.get_view(db, s.table.name)
            if view is not None:
                cols = f" ({', '.join(view['columns'])})" \
                    if view["columns"] else ""
                ddl = (f"CREATE VIEW `{s.table.name}`{cols} AS "
                       f"{view['sql']}")
                return Result(columns=["View", "Create View"],
                              arrow=pa.table({"View": [s.table.name],
                                              "Create View": [ddl]}))
            info = cat.get_table(db, s.table.name)
            lines = []
            pk = info.primary_key()
            auto_col = (info.options or {}).get("auto_increment")
            for f in info.schema.fields:
                bits = [f"  `{f.name}` {f.ltype.value.upper()}"]
                if not f.nullable:
                    bits.append("NOT NULL")
                if f.name == auto_col:
                    bits.append("AUTO_INCREMENT")
                lines.append(" ".join(bits))
            if pk:
                lines.append("  PRIMARY KEY (" +
                             ", ".join(f"`{c}`" for c in pk.columns) + ")")
            for ix in info.indexes:
                if ix.kind == "primary":
                    continue
                kw = {"unique": "UNIQUE KEY", "fulltext": "FULLTEXT KEY",
                      "global": "GLOBAL KEY",
                      "global_unique": "GLOBAL UNIQUE KEY"} \
                    .get(ix.kind, "KEY")
                lines.append(f"  {kw} `{ix.name}` (" +
                             ", ".join(f"`{c}`" for c in ix.columns) + ")")
            ddl = f"CREATE TABLE `{s.table.name}` (\n" + ",\n".join(lines) + \
                "\n)"
            pspec = (info.options or {}).get("partition")
            if pspec and pspec["kind"] == "hash":
                ddl += (f"\nPARTITION BY HASH (`{pspec['column']}`) "
                        f"PARTITIONS {pspec['n']}")
            elif pspec and pspec["kind"] == "range":
                parts = ", ".join(
                    f"PARTITION {nm} VALUES LESS THAN "
                    + ("MAXVALUE" if u is None else f"({u!r})")
                    for nm, u in zip(pspec["names"], pspec["uppers"]))
                ddl += (f"\nPARTITION BY RANGE (`{pspec['column']}`) "
                        f"({parts})")
            return Result(columns=["Table", "Create Table"], arrow=pa.table(
                {"Table": [s.table.name], "Create Table": [ddl]}))
        if s.what in ("columns", "full_columns"):
            base = self._execute_stmt(DescribeStmt(s.table)).arrow
            if s.pattern is not None:
                base = base.take(
                    [i for i, f in
                     enumerate(base.column("Field").to_pylist())
                     if like(f, s.pattern)])
            if s.what == "columns":
                return Result(columns=list(base.column_names), arrow=base)
            # the FULL shape MySQL connectors index by name:
            # Field/Type/Collation/Null/Key/Default/Extra/Privileges/Comment
            fields = base.column("Field").to_pylist()
            types = base.column("Type").to_pylist()
            db = s.table.database or self.current_db
            auto_col = None
            if cat.get_view(db, s.table.name) is None:
                info = cat.get_table(db, s.table.name)
                auto_col = (info.options or {}).get("auto_increment")
            return Result(
                columns=["Field", "Type", "Collation", "Null", "Key",
                         "Default", "Extra", "Privileges", "Comment"],
                arrow=pa.table({
                    "Field": fields,
                    "Type": types,
                    "Collation": pa.array(
                        ["utf8mb4_bin" if t == "string" else None
                         for t in types], pa.string()),
                    "Null": base.column("Null"),
                    "Key": base.column("Key"),
                    "Default": pa.array([None] * len(fields), pa.string()),
                    "Extra": ["auto_increment" if f == auto_col else ""
                              for f in fields],
                    "Privileges": ["select,insert,update,references"]
                    * len(fields),
                    "Comment": [""] * len(fields)}))
        if s.what == "index":
            db = s.table.database or self.current_db
            info = cat.get_table(db, s.table.name)
            rows = []
            for ix in info.indexes:
                for seq, c in enumerate(ix.columns, 1):
                    rows.append((s.table.name, ix.name, ix.kind, seq, c))
            return Result(
                columns=["Table", "Key_name", "Index_type", "Seq_in_index",
                         "Column_name"],
                arrow=pa.table({
                    "Table": [r[0] for r in rows],
                    "Key_name": [r[1] for r in rows],
                    "Index_type": [r[2] for r in rows],
                    "Seq_in_index": pa.array([r[3] for r in rows], pa.int64()),
                    "Column_name": [r[4] for r in rows],
                }))
        if s.what in ("variables", "status"):
            if s.what == "variables":
                vals = dict(_SERVER_VARS)
                # per-session overrides (SET name = v)
                vals.update({k: str(v) for k, v in self.session_vars.items()
                             if not k.startswith("@")})
                # live flag table (gflags analog — SHOW VARIABLES is how
                # MySQL clients inspect server config)
                vals.update({k: str(v).lower() if isinstance(v, bool)
                             else str(v)
                             for k, v in FLAGS.snapshot().items()})
            else:
                vals = {
                    "Threads_connected": str(len(self.db.processlist)),
                    "Uptime": "0",
                }
                # flattened engine counters (bvar analog)
                for name, st in metrics.REGISTRY.expose().items():
                    for k, v in st.items():
                        vals[f"{name}.{k}"] = str(v)
                # fleet extension: merged cluster counters/histograms plus
                # per-daemon liveness as cluster.* rows (only when daemons
                # are registered — a standalone frontend adds nothing)
                if self.db.telemetry.has_daemons():
                    vals.update(self.db.telemetry.status_rows())
                # frontend watchdog verdict (obs/watchdog.py): ok/stalled
                # plus episode counters, same rows the health RPC serves
                vals.update(self.db.watchdog.status_rows())
            items = sorted(vals.items())
            if s.pattern is not None:
                items = [(k, v) for k, v in items if like(k, s.pattern)]
            return Result(columns=["Variable_name", "Value"], arrow=pa.table({
                "Variable_name": [k for k, _ in items],
                "Value": [v for _, v in items]}))
        if s.what == "processlist":
            # wire connections (db.processlist, kept by the MySQL server)
            # merged with live progress records (obs/progress.py) — an
            # embedded Session mid-query shows up even with no socket.
            # Snapshot first: connection threads insert/pop concurrently.
            now = time.time()
            merged: dict[int, dict] = {}
            for cid, ent in dict(self.db.processlist).items():
                merged[cid] = {
                    "user": ent.get("user", ""),
                    "host": ent.get("host", ""),
                    "db": ent.get("db", ""),
                    "command": ent.get("command", "Sleep"),
                    "time_s": int(now - ent.get("since", now)),
                    "state": "", "info": ent.get("info", "")}
            for qp in PROGRESS.live(self.db):
                row = merged.setdefault(qp.conn_id, {
                    "user": qp.user, "host": qp.host, "db": qp.dbname})
                row.update(command=qp.command,
                           time_s=int(qp.elapsed_s()),
                           state=qp.state(), info=qp.text)
            rows = sorted(merged.items())
            # MySQL semantics: Info truncates at 100 chars unless FULL
            infos = [r.get("info", "") for _, r in rows]
            if not s.full:
                infos = [i[:100] for i in infos]
            return Result(
                columns=["Id", "User", "Host", "db", "Command", "Time",
                         "State", "Info"],
                arrow=pa.table({
                    "Id": pa.array([i for i, _ in rows], pa.int64()),
                    "User": [r.get("user", "") for _, r in rows],
                    "Host": [r.get("host", "") for _, r in rows],
                    "db": [r.get("db", "") for _, r in rows],
                    "Command": [r.get("command", "Sleep") for _, r in rows],
                    "Time": pa.array([r.get("time_s", 0) for _, r in rows],
                                     pa.int64()),
                    "State": [r.get("state", "") for _, r in rows],
                    "Info": infos,
                }))
        if s.what == "grants":
            user = s.user or self.user
            gs = self.db.privileges.grants_of(user)
            lines = [f"GRANT {lv} ON {'*' if db == '*' else db}.* TO "
                     f"'{user}'" for db, lv in gs]
            return Result(columns=[f"Grants for {user}"],
                          arrow=pa.table({f"Grants for {user}": lines}))
        if s.what == "regions":
            rows = []
            for key, st in sorted(self.db.stores.items()):
                if s.table is not None:
                    db = s.table.database or self.current_db
                    if key != f"{db}.{s.table.name}":
                        continue
                for r in st.regions:
                    rows.append((key, r.region_id, r.num_rows, r.version))
            return Result(
                columns=["Table", "Region_id", "Rows", "Version"],
                arrow=pa.table({
                    "Table": [r[0] for r in rows],
                    "Region_id": pa.array([r[1] for r in rows], pa.int64()),
                    "Rows": pa.array([r[2] for r in rows], pa.int64()),
                    "Version": pa.array([r[3] for r in rows], pa.int64()),
                }))
        raise SqlError(f"unsupported SHOW {s.what!r}")

    def _kill(self, s: KillStmt) -> Result:
        """KILL [QUERY|CONNECTION] <id> (reference: the kill path through
        state_machine.cpp).  QUERY flips the cancel token of the target
        connection's live statements — the victim's own thread raises
        ER_QUERY_INTERRUPTED (1317) at its next progress beat, so no
        cross-thread exception injection and no torn side effects.
        CONNECTION additionally marks the wire connection for teardown
        and severs its socket so even an idle connection dies now."""
        import socket as _socket
        tid = int(s.target_id)
        n = PROGRESS.kill(conn_id=tid, db=self.db,
                          reason=f"kill {s.kind} {tid}")
        known = bool(n) or tid in self.db.processlist \
            or tid == getattr(self, "_conn_id", None)
        if s.kind == "connection":
            ent = self.db.processlist.get(tid)
            if ent is not None:
                ent["kill"] = True
                sock = ent.get("_sock")
                if sock is not None:
                    # wakes a connection blocked in read(); the serve loop
                    # sees the kill marker and tears down cleanly
                    try:
                        sock.shutdown(_socket.SHUT_RDWR)
                    except OSError:
                        pass
        if not known:
            raise SqlError(f"Unknown thread id: {tid}")
        return Result()

    def _load_data(self, s: LoadDataStmt) -> Result:
        """LOAD DATA INFILE: CSV -> bulk columnar ingest (reference:
        load_planner + the importer; here pyarrow's CSV reader feeds
        insert_arrow directly)."""
        from pyarrow import csv as pacsv

        store = self._store(s.table)
        names = store.info.schema.names()
        ropt = pacsv.ReadOptions(column_names=names,
                                 skip_rows=s.ignore_lines)
        popt = pacsv.ParseOptions(delimiter=s.sep)
        copt = pacsv.ConvertOptions(
            column_types={f.name: schema_to_arrow(store.info.schema).field(
                f.name).type for f in store.info.schema.fields},
            null_values=["", "\\N", "NULL"], strings_can_be_null=True)
        table = pacsv.read_csv(s.path, read_options=ropt,
                               parse_options=popt, convert_options=copt)
        self._ingest_arrow(store, table, check_dups=True)
        db_name = s.table.database or self.current_db
        self._log_binlog("insert", db_name, s.table.name,
                         statement=f"LOAD DATA INFILE {s.path!r}",
                         affected=table.num_rows)
        return Result(affected_rows=table.num_rows)

    def _handle(self, s: HandleStmt) -> Result:
        """Operator commands (reference: handle_helper.cpp's map; the subset
        that has a real in-process counterpart)."""
        if s.command == "checkpoint":
            self.db.checkpoint()
            return Result()
        if s.command == "flightrec" and s.args:
            # handle flightrec dump '/path.jsonl' [rec_id] | clear — the
            # JSON-lines export tools/flightrec.py renders offline
            op = s.args[0].lower()
            if op == "dump" and len(s.args) >= 2:
                rid = int(s.args[2]) if len(s.args) > 2 else None
                return Result(affected_rows=self.db.flightrec.dump(
                    s.args[1], rec_id=rid))
            if op == "clear":
                self.db.flightrec.clear()
                return Result()
        if s.command in ("ttl", "ttl_tick"):
            return Result(affected_rows=self.ttl_tick())
        if s.command == "gc":
            for st in self.db.stores.values():
                if st.row_table is not None:
                    st.row_table.gc(st.row_table.snapshot())
            return Result()
        if s.command == "split" and len(s.args) >= 2:
            # handle split <db.table> <region_rows>: force a smaller split
            # threshold and re-split oversized regions.  `db.t` lexes as
            # three tokens, so rejoin everything before the row count.
            key, rows = "".join(s.args[:-1]), int(s.args[-1])
            st = self.db.stores.get(key)
            if st is None:
                raise PlanError(f"unknown table {key!r}")
            st.region_rows = rows
            with st._lock:
                for r in list(st.regions):
                    st._maybe_split(r)
                st._mutations += 1
            return Result()
        if s.command == "ddl" and s.args:
            # handle ddl suspend|resume (reference: DDL suspend/restart
            # operator commands, handle_helper.cpp)
            op = s.args[0]
            if op == "suspend":
                self.db.ddl.suspend()
                return Result()
            if op in ("resume", "restart"):
                self.db.ddl.resume()
                return Result()
            raise SqlError(f"unsupported HANDLE ddl {op!r}")
        if s.command == "add_privilege" and len(s.args) >= 3:
            # handle add_privilege <user> <db|*> <read|write|all>
            self.db.privileges.grant(s.args[0], s.args[2], s.args[1])
            return Result()
        if s.command == "drop_privilege" and len(s.args) >= 2:
            self.db.privileges.revoke(s.args[0], s.args[1])
            return Result()
        if s.command == "set_flag" and len(s.args) >= 2:
            # handle set_flag <name> <value> (reference: modify gflags)
            FLAGS.set_flag(s.args[0], " ".join(s.args[1:]))
            return Result()
        if s.command in ("drop_instance", "migrate") and s.args:
            # mark a store MIGRATE: balancing drains its peers (reference:
            # handle migrate -> cluster_manager migrate handling)
            self._fleet_meta().drop_instance("".join(s.args))
            return Result()
        if s.command == "add_instance" and s.args:
            # handle add_instance <store_addr> [resource_tag]: register a
            # store (e.g. an OLAP-isolated learner host) with the meta.
            # The lexer splits "host:port" into tokens, so the tag is only
            # the trailing arg when it can't be part of an address (no
            # colon, not a bare port number)
            args = [str(a) for a in s.args]
            tag = ""
            if len(args) > 1 and ":" not in args[-1] and \
                    not args[-1].isdigit():
                tag = args[-1]
                args = args[:-1]
            self._fleet_meta().add_instance("".join(args), resource_tag=tag)
            return Result()
        if s.command in ("add_peer", "remove_peer", "trans_leader",
                         "add_learner", "remove_learner") and \
                len(s.args) >= 2:
            # handle add_peer|remove_peer|trans_leader <region_id> <store>:
            # validated, executed, and recorded in meta by the fleet (the
            # raft_control RPC surface); failures RAISE — an operator must
            # never see success for an op that didn't happen
            try:
                self._fleet_required().operator_order(
                    s.command, int(s.args[0]), "".join(s.args[1:]))
            except (ValueError, RuntimeError) as e:
                raise PlanError(str(e)) from None
            return Result(affected_rows=1)
        if s.command == "split_region" and s.args:
            tier, idx = self._find_region(int(s.args[0]))
            tier.split_region(idx)
            return Result()
        if s.command == "merge_region" and s.args:
            tier, idx = self._find_region(int(s.args[0]))
            tier.merge_region(idx)
            return Result()
        if s.command in ("store_heartbeat", "balance_tick"):
            # one control-loop turn: heartbeats in, balance orders executed
            return Result(affected_rows=self._fleet_required().control_tick())
        if s.command in ("cold_flush", "cold_gc", "cold_status") and s.args:
            # handle cold_flush <db.table> [upto_rowid]: hot rows -> one
            # immutable segment per region on the external FS, manifest +
            # eviction raft-committed (region_olap.cpp:445 flush_to_cold);
            # cold_gc merges segments (latest version per rowid, deletes
            # dropped); cold_status reports hot bytes + manifest size
            has_upto = s.command == "cold_flush" and len(s.args) > 1 and \
                str(s.args[-1]).isdigit()
            key = "".join(s.args[:-1] if has_upto else s.args)
            st = self.db.stores.get(key)
            if st is None or st.replicated is None or \
                    not hasattr(st.replicated, "flush_cold"):
                raise PlanError(f"no cold-capable replicated tier for "
                                f"{key!r}")
            fs = self.db.cold_fs(required=True)
            tier = st.replicated
            if s.command == "cold_flush":
                upto = int(s.args[-1]) if has_upto else None
                return Result(affected_rows=tier.flush_cold(fs, upto=upto))
            if s.command == "cold_gc":
                return Result(affected_rows=tier.cold_gc(fs))
            n_regions = len(tier.groups) if hasattr(tier, "groups") \
                else len(tier.regions)
            entries = sum(len(self._cold_manifest_of(tier, i))
                          for i in range(n_regions))
            return Result(columns=["hot_bytes", "cold_segments"], arrow=(
                pa.table({"hot_bytes": [tier.hot_bytes()],
                          "cold_segments": [entries]})))
        if s.command == "compact":
            # raft log compaction across every replicated tier (the
            # space-efficient snapshot scheme)
            fleet = self.db.fleet
            if fleet is not None:
                for tier in fleet.row_tiers.values():
                    tier.compact_all()
                if hasattr(fleet.meta, "compact_all"):
                    fleet.meta.compact_all()
            return Result()
        raise SqlError(f"unsupported HANDLE command {s.command!r}")

    @staticmethod
    def _cold_manifest_of(tier, i):
        if hasattr(tier, "groups"):     # in-process fleet plane
            g = tier.groups[i]
            return g.bus.nodes[g.leader()].cold_manifest
        return tier._region_manifest(tier.regions[i])   # daemon plane

    def _fleet_required(self):
        if self.db.fleet is None:
            raise PlanError("this HANDLE command needs a fleet-bound "
                            "Database (store fleet + meta)")
        return self.db.fleet

    def _fleet_meta(self):
        return self._fleet_required().meta

    def _find_region(self, region_id: int):
        """(tier, index) hosting a replicated region (fleet mode)."""
        fleet = self._fleet_required()
        for tier in fleet.row_tiers.values():
            for i, m in enumerate(tier.metas):
                if m.region_id == region_id:
                    return tier, i
        raise PlanError(f"unknown region {region_id}")

    def _drop_durable(self, key: str, store):
        """Remove a dropped table's WAL + Parquet from data_dir (and its
        replicated tier from a fleet-bound Database)."""
        if self.db.fleet is not None:
            tier = self.db.fleet.row_tiers.pop(key, None)
            if tier is not None:
                tier.release_regions()   # no ghost raft groups in the fleet
        if self.db.cluster is not None:
            tier = self.db.cluster.tiers.pop(key, None)
            if tier is not None:
                tier.release_regions()
        if not self.db.data_dir:
            return
        import os
        import shutil
        if store is not None:
            store.row_table = None      # release the WAL file handle
        wal = os.path.join(self.db.data_dir, key + ".wal")
        if os.path.exists(wal):
            os.remove(wal)
        pq_dir = os.path.join(self.db.data_dir, key)
        if os.path.isdir(pq_dir):
            shutil.rmtree(pq_dir)

    # -- helpers ------------------------------------------------------------
    def _stats_fn(self, table_key: str, col: str):
        """Collected column statistics, or None — the ONE stats-access
        closure behind the planner's selectivity estimates AND the
        distributor's adaptive-agg ndv lookups."""
        st = self.db.stores.get(table_key)
        if st is None:
            return None
        try:
            return st.column_stats(col)
        except Exception:   # noqa: BLE001 — stats are advisory
            return None

    def _planner(self) -> Planner:
        # a mesh session's scans read shards: a shard boundary splits a
        # run of equal keys, so no aggregate there is planned as a stream
        return Planner(self.db.catalog, self.db.stores, self.current_db,
                       self._stats_fn, lane_order=self.mesh is None)

    def _plan_select(self, stmt: SelectStmt) -> PlanNode:
        """Logical+physical planning, plus the distribution pass (the
        Separate/MppAnalyzer analog) when this session is mesh-bound."""
        with trace.span("plan.build"):
            return self._plan_select_inner(stmt)

    def _where_selectivity(self, stmt: SelectStmt):
        """Combined selectivity estimate of the WHERE conjuncts that have
        a stats basis (index/stats histograms + MCVs over THIS
        execution's literal values); None when no conjunct resolves.
        Feeds the adaptive-agg local-vs-raw decision and the mesh plan
        cache's selectivity class — a parameterized statement replans per
        CLASS, not per value, so the executable multiplier stays small."""
        if stmt.where is None:
            return None
        from ..expr.ast import Call as ECall, ColRef as EColRef, Lit as ELit
        from ..index.stats import conjunct_selectivity
        from ..plan.eqclasses import conjuncts

        _FLIP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}
        resolve = self._param_resolver(stmt)
        total, basis = 1.0, False
        for cj in conjuncts(stmt.where):
            if not (isinstance(cj, ECall)
                    and cj.op in ("eq", "ne", "lt", "le", "gt", "ge")
                    and len(cj.args) == 2):
                continue
            a, b = cj.args
            op = cj.op
            if isinstance(b, EColRef) and isinstance(a, ELit):
                a, b = b, a
                op = _FLIP.get(op, op)
            if not (isinstance(a, EColRef) and isinstance(b, ELit)):
                continue
            src = resolve(a.table, a.name)
            if src is None:
                continue
            st = self._stats_fn(src[0], a.name.split(".")[-1])
            s = conjunct_selectivity(st, op, b.value)
            if s is not None:
                total *= s
                basis = True
        return total if basis else None

    def _plan_select_inner(self, stmt: SelectStmt) -> PlanNode:
        plan = self._planner().plan_select(stmt)
        self._annotate_ann(stmt, plan)

        def rows_fn(table_key: str) -> int:
            st = self.db.stores.get(table_key)
            return st.num_rows if st is not None else 0

        if self.mesh is None:
            # a shrink's first capacity from the row counts (a mesh traces
            # per-shard sizes and keeps its cut)
            caps.annotate_rows(plan, rows_fn)
        else:
            from ..plan.distribute import distribute

            def ndv_fn(table_key: str, col: str):
                # index/stats distinct-count estimate feeding the
                # cardinality-adaptive aggregation choice
                return (self._stats_fn(table_key, col) or {}).get("ndv")

            from ..parallel import agg as _agg  # noqa: F401 — defines the
            #                                     adaptive_agg_* flags

            # the parameterized path stashes the ORIGINAL statement's
            # bound-value selectivity before planning (stmt here carries
            # Param markers, not values); EXPLAIN and unparameterized
            # plans compute it from their own baked literals
            wsel = getattr(self, "_where_sel_hint", None)
            if wsel is None and bool(FLAGS.adaptive_agg_selectivity):
                wsel = self._where_selectivity(stmt)
            with trace.span("plan.distribute"):
                plan = distribute(plan, int(self.mesh.devices.size), rows_fn,
                                  ndv_fn=ndv_fn, stats_fn=self._stats_fn,
                                  where_selectivity=wsel)
        return plan

    def _annotate_ann(self, stmt: SelectStmt, plan: PlanNode) -> None:
        """When the statement is the ANN shape over a table with an ANN
        index, mark its ScanNode: the batch builder reduces the scan to
        the IVF candidate set (index/annindex) and the unchanged plan
        re-ranks exactly."""
        from ..index import annindex
        from ..plan.nodes import ScanNode

        t = stmt.table
        if t is None or t.subquery is not None or self.mesh is not None:
            return
        dbname = t.database or self.current_db
        try:
            info = self.db.catalog.get_table(dbname, t.name)
        except Exception:       # noqa: BLE001 — planner already validated
            return
        m = annindex.match_ann_query(stmt, info, t.label)
        if m is None:
            return
        ix, col, metric, qvec, k = m
        key = f"{dbname}.{t.name}"
        scans = []

        def walk(n):
            if isinstance(n, ScanNode) and n.table_key == key:
                scans.append(n)
            for c in n.children:
                walk(c)
        walk(plan)
        if len(scans) == 1:
            # the WHERE flag rides along: filters re-apply AFTER candidate
            # reduction, so the batch builder must widen the pre-filter pool
            # (or fall back to brute force) to still fill LIMIT k
            scans[0].ann = (ix.name, col, metric, qvec, int(k),
                            stmt.where is not None)

    def _ann_batch(self, n, store) -> Optional[ColumnBatch]:
        """IVF candidate batch for an ANN-annotated scan: positions from
        the trained index, sliced out of the store snapshot (same row
        source the full scan would read)."""
        from ..index import annindex

        ix_name, col, metric, qvec, k, has_where = n.ann
        filtered = has_where or n.pushed_filter is not None
        dim = (store.info.options or {}).get("vector_cols", {}).get(col)
        if dim is None:
            return None
        cache = getattr(self, "_access_batches", None)
        if cache is None:
            cache = self._access_batches = {}
        ck = (n.table_key, store.version, "ann", col, qvec, k, filtered)
        hit = cache.get(ck)
        if hit is not None:
            b, desc = hit
            n.access_desc = desc
            return b
        res = annindex.manager(self.db).candidates(
            n.table_key, store, col, int(dim), qvec, metric, k,
            filtered=filtered)
        if res is None:
            n.access_desc = "full"
            return None
        positions, nprobe = res
        import pyarrow as _pa
        b = ColumnBatch.from_arrow(
            store.snapshot().take(_pa.array(positions)))
        n.access_desc = (f"ann({ix_name} nprobe={nprobe}, "
                         f"cand={len(positions)})")
        self._evict_access(n.table_key, store.version)
        cache[ck] = (b, n.access_desc)
        metrics.index_scans.add(1)
        return b

    def _store(self, tref) -> TableStore:
        db = tref.database or self.current_db
        if db == "information_schema":
            raise PlanError("information_schema tables are read-only")
        key = f"{db}.{tref.name}"
        if key not in self.db.stores:
            # registers lazily in case catalog was populated externally
            info = self.db.catalog.get_table(db, tref.name)
            self.db.stores[key] = self.db.make_store(info)
        return self.db.stores[key]

    # -- transactions ------------------------------------------------------
    def _txn_stmt(self, s: TxnStmt) -> Result:
        """BEGIN/COMMIT/ROLLBACK (reference: transaction_planner.cpp +
        TransactionNode fan-out).  Each touched table gets a storage
        TxnContext: pessimistic row locks + row-tier write buffer + zero-copy
        region pre-images; COMMIT is one atomic WAL batch per table."""
        self._release_txn_snapshot()
        if s.kind == "begin":
            # a new BEGIN implicitly commits any previous txn (MySQL behavior)
            open_txn = self._sql_txn is not None
            self._commit_txn()
            if open_txn:
                metrics.txn_commits.add(1)
            self._sql_txn = {}
            return Result()
        if self._sql_txn is None:
            return Result()      # COMMIT/ROLLBACK outside txn: no-op
        if s.kind == "commit":
            self._commit_txn()
            metrics.txn_commits.add(1)
            return Result()
        for tctx in self._sql_txn.values():
            tctx.rollback()
        self._sql_txn = None
        self._txn_binlog.clear()    # rolled back: subscribers never see these
        metrics.txn_rollbacks.add(1)
        return Result()

    def _commit_txn(self):
        if self._sql_txn is not None:
            from ..storage.column_store import commit_group
            try:
                # one atomic commit across every table the transaction
                # touched: replicated tables group into a single 2PC
                # spanning all their region groups (global-index writes and
                # cross-table transactions commit or abort together)
                commit_group(list(self._sql_txn.values()))
            except BaseException:
                # the txn did NOT commit: its buffered events must never
                # publish (a later successful commit would otherwise emit
                # them as phantom CDC rows)
                self._txn_binlog.clear()
                raise
            finally:
                # even a failed WAL write must not trap the session in the
                # transaction (the contexts released their leases already)
                self._sql_txn = None
        self._flush_txn_binlog()

    def _flush_txn_binlog(self):
        # an empty commit still flows through: pending retry batches (failed
        # appends of EARLIER commits) piggyback a drain on any commit
        if not self._txn_binlog and not self.db.binlog_retry_pending():
            return
        with trace.span("binlog.flush", events=len(self._txn_binlog)):
            self._flush_txn_binlog_inner()

    def _flush_txn_binlog_inner(self):
        from ..storage.binlog_regions import DistributedBinlog

        per_table: OrderedDict = OrderedDict()
        for ev in self._txn_binlog:
            event_type, db_name, table, rows, statement, affected = ev
            self.db.binlog.append(event_type, db_name, table, rows=rows,
                                  statement=statement, affected=affected)
            if self._table_binlogged(db_name, table):
                per_table.setdefault(f"{db_name}.{table}", []).extend(
                    DistributedBinlog.events_from_statement(
                        event_type, rows, statement, affected))
        # one prewrite/commit round per table, not per statement (the
        # autocommit path instead joins the data's own 2PC in _write_hot).
        # dist_binlog() resolves only when a binlogged event exists: it
        # creates the __binlog__ regions cluster-wide on first use
        dist = self.db.dist_binlog() \
            if per_table or self.db.binlog_retry_pending() else None
        if dist is not None:
            # CDC must not fail the txn the user already committed — but a
            # failed append is COMMITTED data subscribers would silently
            # lose.  Queue it durably in-process and retry on later flushes;
            # only a bounded-queue overflow drops events, and that shows in
            # metrics.binlog_events_dropped.  Per-table locks: each table's
            # drain-then-append is atomic vs concurrent commits/autocommits
            # of THAT table (the stream-order contract), while other tables
            # proceed in parallel — no engine-wide convoy.  Locks are taken
            # one table at a time, never nested.
            db = self.db
            # piggyback: retry other tables' queued batches on any commit
            for tk in db.binlog_retry_pending():
                if tk not in per_table:
                    rq = db.binlog_retry_queue(tk)
                    with rq.mu:
                        db._drain_rq_locked(rq, tk, dist)
            for table_key, events in per_table.items():
                rq = db.binlog_retry_queue(table_key)
                with rq.mu:
                    db._drain_rq_locked(rq, table_key, dist)
                    if rq.q:
                        # an older batch for this table is still queued:
                        # appending now would reorder the table's CDC stream
                        db._queue_rq_locked(rq, events)
                        continue
                    try:
                        dist.append(table_key, events)
                    except Exception:   # noqa: BLE001
                        db._queue_rq_locked(rq, events)
        self._txn_binlog.clear()

    def _table_binlogged(self, db_name: str, table: str) -> bool:
        try:
            info = self.db.catalog.get_table(db_name, table)
        except Exception:       # noqa: BLE001
            return False
        return _opt_on((info.options or {}).get("binlog"))

    def _tctx(self, store: TableStore):
        """The open transaction's per-table context (created on first touch),
        or None in autocommit."""
        if self._sql_txn is None:
            return None
        key = f"{store.info.database}.{store.info.name}"
        if key not in self._sql_txn:
            tctx = store.begin_txn()
            if self._txn_pin is not None and \
                    store.mvcc_needs_versioned(self._txn_pin[1]):
                # the transaction read this table at its snapshot and
                # someone committed to it since: its reads of the table
                # from here on (its own rows showing) could not be that
                # snapshot.  First committer wins, as snapshot isolation
                tctx.rollback()
                raise ConflictError(
                    f"table {store.info.name} changed since this "
                    "transaction's snapshot")
            self._sql_txn[key] = tctx
        return self._sql_txn[key]

    def load_arrow(self, table_name: str, table: pa.Table,
                   database: str | None = None) -> int:
        """Bulk ingest (the importer/fast_importer analog, src/tools/importer):
        appends an Arrow table straight into the column store, bypassing SQL
        row parsing (cold path — durable at the next Database.checkpoint)."""
        from ..sql.stmt import TableRef

        store = self._store(TableRef(database, table_name))
        vcols = (store.info.options or {}).get("vector_cols") or {}
        if vcols:
            table = _expand_vector_arrow(table, vcols)
        self._ingest_arrow(store, table)
        gcpause.settle("load")
        return table.num_rows

    # -- DDL --------------------------------------------------------------
    def _create_table(self, s: CreateTableStmt) -> Result:
        db = s.table.database or self.current_db
        fields = []
        vector_cols: dict[str, int] = {}
        for c in s.columns:
            tl = c.type_name.strip().lower()
            if tl.startswith("vector"):
                # VECTOR(d): stored as d hidden FLOAT32 component columns, so
                # distance expressions fuse into the one-jit query program
                # (the faiss sidecar re-designed as columns; reference:
                # vector_index.cpp stores blobs + a faiss index)
                try:
                    dim = int(tl.split("(")[1].rstrip(") "))
                except (IndexError, ValueError):
                    raise PlanError("VECTOR needs a dimension: VECTOR(d)")
                if not 1 <= dim <= 4096:
                    raise PlanError("VECTOR dimension out of range")
                vector_cols[c.name] = dim
                for i in range(dim):
                    fields.append(Field(f"__{c.name}_{i}", LType.FLOAT32,
                                        True))
                continue
            lt = parse_type(c.type_name)
            nullable = c.nullable and c.name not in s.primary_key
            fields.append(Field(c.name, lt, nullable))
        options = dict(s.options)
        if vector_cols:
            options["vector_cols"] = vector_cols
        pspec = options.get("partition")
        if pspec:
            names = {f.name for f in fields}
            if pspec["column"] not in names:
                raise PlanError(f"unknown partition column "
                                f"{pspec['column']!r}")
            if pspec["kind"] == "range":
                if len(set(pspec["names"])) != len(pspec["names"]):
                    raise PlanError("duplicate partition name")
                pf = next(f for f in fields if f.name == pspec["column"])
                try:
                    finite = [TableStore._norm_part_scalar(u, pf)
                              for u in pspec["uppers"] if u is not None]
                    if any(b <= a for a, b in zip(finite, finite[1:])):
                        raise PlanError("partition bounds must be strictly "
                                        "increasing")
                except (TypeError, ValueError) as e:
                    if isinstance(e, PlanError):
                        raise
                    raise PlanError(f"partition bounds do not match column "
                                    f"{pspec['column']!r}: {e}") from None
            elif pspec["kind"] == "hash" and int(pspec["n"]) < 1:
                raise PlanError("PARTITIONS must be at least 1")
        auto_cols = [c for c in s.columns if c.auto_increment]
        if auto_cols:
            if len(auto_cols) > 1:
                raise PlanError("only one AUTO_INCREMENT column allowed")
            if not parse_type(auto_cols[0].type_name).is_integer:
                raise PlanError("AUTO_INCREMENT requires an integer column")
            options["auto_increment"] = auto_cols[0].name
        schema = Schema(tuple(fields))
        indexes = []
        if s.primary_key:
            indexes.append(IndexInfo("PRIMARY", "primary", list(s.primary_key)))
        for kind, name, cols in s.indexes:
            if kind == "ann":
                if len(cols) != 1 or cols[0] not in vector_cols:
                    raise PlanError("ANN INDEX needs exactly one VECTOR "
                                    "column")
                indexes.append(IndexInfo(name or f"ann_{cols[0]}", kind,
                                         cols))
                continue
            indexes.append(IndexInfo(name or f"idx_{'_'.join(cols)}", kind, cols))
        info = self.db.catalog.create_table(db, s.table.name, schema, indexes,
                                            options=options,
                                            if_not_exists=s.if_not_exists)
        key = f"{db}.{s.table.name}"
        if key not in self.db.stores:
            self.db.stores[key] = self.db.make_store(info)
        for ix in info.indexes:
            if ix.kind in ("global", "global_unique"):
                self._create_global_backing(db, info, ix)
        self.db.save_catalog()
        return Result()

    def _create_global_backing(self, db: str, info, ix) -> TableStore:
        """Materialize a global index's hidden backing table: its own
        catalog entry, its own store — and in fleet/cluster mode its own
        replicated row tier with its OWN region groups (reference: index
        data in separate regions, separate.cpp:653)."""
        from ..index import globalindex as gi

        for c in ix.columns:
            if c not in info.schema:
                raise PlanError(f"unknown column {c!r} in global index "
                                f"{ix.name!r}")
        bname = gi.backing_table_name(info.name, ix.name)
        bkey = f"{db}.{bname}"
        if bkey in self.db.stores:
            return self.db.stores[bkey]
        binfo = self.db.catalog.create_table(
            db, bname, gi.backing_schema(info, ix),
            [IndexInfo("PRIMARY", "primary", gi.backing_pk(info, ix))],
            if_not_exists=True)
        store = self.db.stores[bkey] = self.db.make_store(binfo)
        return store

    # -- daemon-plane pushed-down execution (reference: store-side plan
    # fragments, region.cpp:2671 / store.interface.proto:418) --------------
    def _pushdown_candidate(self, stmt: SelectStmt):
        """(push, info, table_key) when this SELECT can execute as a pushed
        fragment on the store daemons, else None.  Shared by execution and
        EXPLAIN so the displayed plan is the plan that runs."""
        from ..plan.fragment import build_push_query

        db = self.db
        if db.cluster is None:
            return None
        mode = str(FLAGS.pushdown_reads)
        if mode == "off" or self._sql_txn is not None:
            return None
        if self._snap_dirty(stmt):
            # pinned snapshot with version churn: store daemons evaluate
            # the physically-latest region image; the versioned read needs
            # the frontend's MVCC state, so the pin routes this query to
            # the resident path (quiet tables keep the pushed path)
            return None
        t = stmt.table
        if t is None:
            return None
        dbname = t.database or self.current_db
        if dbname == "information_schema":
            return None
        if db.catalog.get_view(dbname, t.name) is not None:
            return None
        try:
            info = db.catalog.get_table(dbname, t.name)
        except Exception:       # noqa: BLE001 — unknown table: planner errs
            return None
        if (info.options or {}).get("partition"):
            return None          # partitioned layout: image path prunes
        if any(f.ltype is LType.DECIMAL for f in info.schema.fields):
            # the row tier's DECIMAL encoding is scaled; row-wise eval
            # would disagree with the image path — not pushable
            return None
        key = f"{dbname}.{t.name}"
        store = db.stores.get(key)
        if mode != "always" and store is not None \
                and not store.attach_pending:
            return None          # warm image: compiled JAX path is faster
        if mode != "always":
            from ..index.selector import is_point_statement

            if is_point_statement(stmt):
                # repeated PK point reads: one image pull then
                # microsecond-class local lookups beats a per-query
                # full-region fragment scan (the OLTP path)
                return None
        push = build_push_query(stmt, info)
        if push is None:
            return None
        return push, info, key

    def _render_pushdown(self, push, info, key) -> str:
        """EXPLAIN display of a pushed fragment: what the store daemons
        execute vs what the frontend finishes."""
        from ..expr.roweval import expr_from_wire

        f = push.frag
        lines = [f"PushDown({key} -> store daemons)"]
        if f.get("filter") is not None:
            lines.append(f"  store filter: {expr_from_wire(f['filter'])!r}")
        if push.mode == "rows":
            outs = ", ".join(f"{n}={expr_from_wire(w)!r}"
                             for n, w in f["outputs"])
            lines.append(f"  store project: {outs}")
            if f.get("limit") is not None:
                lines.append(f"  store limit: {f['limit']} per region")
        else:
            if f["keys"]:
                keys = ", ".join(f"{n}={expr_from_wire(w)!r}"
                                 for n, w in f["keys"])
                lines.append(f"  store group by: {keys}")
            aggs = ", ".join(
                "{}={}({})".format(
                    out, kind,
                    repr(expr_from_wire(w)) if w is not None else "*")
                for kind, w, out in f["aggs"])
            lines.append(f"  store partial aggs: {aggs}")
        finish = []
        if push.having is not None:
            finish.append(f"having {push.having!r}")
        if push.order:
            finish.append("order by " + ", ".join(
                f"{e!r} {'asc' if asc else 'desc'}"
                for e, asc in push.order))
        if push.limit is not None:
            finish.append(f"limit {push.limit}"
                          + (f" offset {push.offset}" if push.offset
                             else ""))
        lines.append("  frontend merge: "
                     + ("; ".join(finish) if finish else "concat/combine"))
        lines.append("  items: " + ", ".join(f"{n}={e!r}"
                                             for n, e in push.items))
        return "\n".join(lines)

    def _try_pushdown(self, stmt: SelectStmt) -> Optional[Result]:
        """Execute an eligible SELECT store-side: only qualifying rows /
        aggregate partials cross the wire, and a cold frontend never pulls
        whole regions for it (VERDICT r04 missing #1)."""
        cand = self._pushdown_candidate(stmt)
        if cand is None:
            return None
        push, info, key = cand
        from ..plan.fragment import merge_push_results
        from ..storage.remote_tier import (PushdownUnsupported,
                                           RemoteRowTier, ReplicationError,
                                           StaleRoutingError)

        store = self.db.stores.get(key)
        if store is None:
            store = self.db.stores[key] = self.db.make_store(info)
        tier = store.replicated
        if not isinstance(tier, RemoteRowTier):
            return None
        try:
            if bool(FLAGS.fragment_pushdown):
                # parallel dispatcher: hash-addressed specs, one thread per
                # region owner, split/migration re-targeting
                # (exec/fragments.py).  Same payloads in the same region
                # order as the serial loop -> bit-identical merge
                from .fragments import dispatch_fragments

                payloads, _fstats = dispatch_fragments(tier, push.frag)
            else:
                payloads = tier.exec_fragment(push.frag)
        except (PushdownUnsupported, ReplicationError,
                StaleRoutingError):
            metrics.fragment_fallbacks.add(1)
            return None          # image path retries / surfaces the error
        with trace.span("fragment.merge", table=key,
                        regions=len(payloads)):
            names, rows = merge_push_results(push, payloads)
        return self._host_rows_result(names, rows)

    @staticmethod
    def _host_rows_result(names: list, rows: list) -> Result:
        """Host-computed row tuples -> Result (pushdown merge, egress
        finish).  from_arrays permits duplicate output names (SELECT a, a
        FROM t) so the wire layer sends the names the client asked for."""
        arrays = []
        for i in range(len(names)):
            vals = [r[i] for r in rows]
            try:
                arrays.append(pa.array(vals))
            except (pa.ArrowInvalid, pa.ArrowTypeError):
                arrays.append(pa.array([None if v is None else str(v)
                                        for v in vals]))
        return Result(columns=list(names),
                      arrow=pa.Table.from_arrays(arrays, names=list(names)))

    def _select_egress(self, eg, cache_key) -> Result:
        """Run the egress-rewritten inner statement, then evaluate the
        string skeletons host-side over the final-sized result
        (exec/egress.py)."""
        from . import egress as egress_mod

        inner_stmt, spec = eg
        key = None if cache_key is None else \
            (cache_key[0] + " /*egress*/", cache_key[1])
        inner = self._select(inner_stmt, cache_key=key)
        names, rows = egress_mod.finish(spec, inner)
        return self._host_rows_result(names, rows)

    # -- OLTP point-read fast path (reference: primary-index point SELECT
    # through the row path, region.cpp select_normal) ----------------------
    def _try_point_lookup(self, stmt: SelectStmt) -> Optional[Result]:
        """WHERE fixes the whole primary key by equality and the statement
        is a plain row fetch: serve from the host tier — no device program,
        no compile, microsecond-class latency (the OLTP path)."""
        from ..expr.ast import ColRef
        from ..index.selector import is_point_statement, point_key

        if not is_point_statement(stmt):
            return None
        db = stmt.table.database or self.current_db
        key = f"{db}.{stmt.table.name}"
        store = self.db.stores.get(key)
        if store is None or store._pk_cols is None:
            return None
        pk = point_key(stmt, store._pk_cols)
        if pk is None:
            return None
        if stmt.offset or stmt.limit == 0:
            return None         # row-skipping edge cases: normal path
        # output must be plain columns (or *); expressions fall through to
        # the normal path rather than re-implementing eval host-side
        names = []
        for it in stmt.items:
            if it.expr is None:
                names.extend(f.name for f in store.info.schema.fields)
            elif isinstance(it.expr, ColRef):
                cname = it.expr.name.split(".")[-1]
                if cname not in store.info.schema:
                    return None
                names.append(it.alias or cname)
            else:
                return None
        if len(set(names)) != len(names):
            return None     # duplicate output names: the device path's
            #                 rename-dedup behavior must not change shape
        # the row tier answers: no query_log row is written on this path,
        # so the time also feeds a counter
        with trace.timed("point.lookup", table=key) as sp:
            try:
                row = store.point_lookup(pk)
            except Exception:
                return None     # any host-index hiccup: run the full path
            metrics.point_lookups.add(1)
            sch = schema_to_arrow(store.info.schema)
            cols: dict = {}
            for cname, out_name in zip(self._expand_items(stmt.items, store),
                                       names):
                cols[out_name] = pa.array(
                    [None if row is None else row.get(cname)],
                    sch.field(cname).type)
            t = pa.table(cols) if row is not None else \
                pa.table({n: c.slice(0, 0) for n, c in cols.items()})
        metrics.point_lookup_ms.add(sp.ms)
        if sp.wait_ms is not None:      # a tree is live: the span split
            metrics.point_lookup_wait_ms.add(sp.wait_ms)
        return Result(columns=names, arrow=t)

    def _expand_items(self, items, store):
        out = []
        for it in items:
            if it.expr is None:
                out.extend(f.name for f in store.info.schema.fields)
            else:
                out.append(it.expr.name.split(".")[-1])
        return out

    # -- CDC: FETCH + materialized views (cdc/) ----------------------------
    def _fetch_stmt(self, s: FetchStmt) -> Result:
        """FETCH [n] FROM sub: deliver the next ordered event batch, then
        durably ack past it — deliver-then-ack, so a frontend crash after
        the client read the batch never redelivers it, and a crash BEFORE
        the reply redelivers the whole batch (at-least-once across crash,
        exactly-once in steady state; consumers wanting strict
        exactly-once under crashes dedupe on commit_ts)."""
        import json as _json

        try:
            sub = self.db.cdc.get(s.name)
        except KeyError as e:
            raise PlanError(str(e.args[0])) from None
        events = sub.fetch(s.limit)     # may raise CursorLagging (typed)
        names = ["commit_ts", "event_type", "table_name", "rows",
                 "statement", "affected"]
        rows = [(e.commit_ts, e.event_type, f"{e.database}.{e.table}",
                 _json.dumps(e.rows, default=str), e.statement, e.affected)
                for e in events]
        if events:
            sub.ack(events[-1].commit_ts)
        return self._host_rows_result(names, rows)

    def _try_matview(self, stmt: SelectStmt, refresh: bool = True):
        """If a registered materialized view covers this GROUP BY SELECT,
        fold its pending change-stream deltas,
        flush state into the hidden __mv_* table, and return the
        rewritten statement.  ``refresh=False`` (EXPLAIN) only rewrites.
        The same gates as _try_rollup: never inside a pinned snapshot or
        an open transaction, never while a seed/rescan query runs."""
        from ..index.rollup import try_rewrite

        if not FLAGS.matview_answer:
            return None
        if getattr(self, "_in_mv_refresh", False) or \
                getattr(self, "_in_rollup_refresh", False):
            return None
        if self._snap_ts or self._sql_txn is not None:
            return None
        if stmt.table is None or stmt.joins or stmt.ctes or stmt.union:
            return None
        db = stmt.table.database or self.current_db
        for mv in self.db.matviews.for_base(f"{db}.{stmt.table.name}"):
            rw = try_rewrite(stmt, stmt.table.name, mv.name, mv.keys,
                             mv.measures, mv.database,
                             target_table=mv.hidden)
            if rw is None:
                continue
            if refresh:
                mv.maintain(self)
                mv.materialize(self)
                mv.answered += 1
                metrics.view_answered_queries.add(1)
                # zero-duration marker span: EXPLAIN ANALYZE renders it as
                # the `-- view:` line; info-schema reads the same numbers
                with trace.span("view", view=f"{mv.database}.{mv.name}",
                                applied_ts=mv.applied_ts,
                                staleness_ms=mv.staleness_ms(),
                                deltas_folded=mv.deltas_folded,
                                groups=len(mv.state or {})):
                    pass
            return rw
        return None

    # -- rollup index (reference: I_ROLLUP, region_olap.cpp:530-651) -------
    def _try_rollup(self, stmt: SelectStmt, refresh: bool = True):
        """If a rollup covers this SELECT, refresh it (lazily, on base
        version change) and return the rewritten statement.  ``refresh=False``
        (EXPLAIN) only rewrites — plan display must stay side-effect-free."""
        from ..index.rollup import try_rewrite
        if getattr(self, "_in_rollup_refresh", False):
            return None      # the refresh GROUP BY must hit the base table
        if self._snap_ts:
            # pinned snapshot (explicit SET SNAPSHOT / nested scope): the
            # rollup tracks commit-time freshness, not the pin — and its
            # refresh would write AFTER the pin, hiding its own rows from
            # the versioned read.  Scan the base table versioned instead.
            # (The automatic analytical pin defers to the rollup in
            # _snapshot_scope, so this gate only fires for explicit pins.)
            return None
        if self._sql_txn is not None:
            # inside a transaction the rollup can't see this txn's buffered
            # writes (and refresh would write under the user's locks): scan
            # the base table for read-your-writes semantics
            return None
        if stmt.table is None or stmt.joins or stmt.ctes or stmt.union:
            return None
        db = stmt.table.database or self.current_db
        try:
            info = self.db.catalog.get_table(db, stmt.table.name)
        except ValueError:
            return None
        for ix in info.indexes:
            if ix.kind != "rollup":
                continue
            keys = list(ix.columns)
            measures = list(ix.params.get("measures", ()))
            rw = try_rewrite(stmt, stmt.table.name, ix.name, keys, measures,
                             db)
            if rw is None:
                continue
            if refresh:
                self._refresh_rollup(db, info, ix)
            return rw
        return None

    def _refresh_rollup(self, db: str, info, ix) -> None:
        """Rematerialize iff the base version moved (one GROUP BY program)."""
        from ..index.rollup import refresh_sql, rollup_table_name
        base_key = f"{db}.{info.name}"
        base = self.db.stores[base_key]
        if ix.params.get("fresh_at") == base.version:
            return
        rt = rollup_table_name(info.name, ix.name)
        sql = refresh_sql(f"{db}.{info.name}", rt, list(ix.columns),
                          list(ix.params.get("measures", ())))
        self._in_rollup_refresh = True
        try:
            table = self._execute(sql).arrow
        finally:
            self._in_rollup_refresh = False
        store = self.db.stores[f"{db}.{rt}"]
        store.truncate()
        if table is not None and table.num_rows:
            rinfo = self.db.catalog.get_table(db, rt)
            cast = pa.table({f.name: table.column(f.name).cast(
                schema_to_arrow(rinfo.schema).field(f.name).type)
                for f in rinfo.schema.fields})
            store.insert_arrow(cast, self._tctx(store))
        ix.params["fresh_at"] = base.version

    def _alter_index(self, s: AlterTableStmt, db: str, info) -> Result:
        """Online ADD INDEX: the statement returns once the work is queued
        (reference: DDL accepted by meta's DDLManager, ddl_manager.cpp);
        a background worker backfills region by region and PUBLISHES the
        index, at which point the IndexSelector starts choosing it.  DROP
        INDEX is immediate (the artifact is derived state)."""
        if s.action == "drop_index":
            # only secondary-index kinds: rollups own a hidden backing
            # table and must go through DROP ROLLUP (vector columns are
            # schema-bound); dropping them here would orphan state
            kept = [ix for ix in info.indexes
                    if not (ix.name == s.index_name and
                            ix.kind in ("key", "unique", "fulltext", "ann",
                                        "global", "global_unique"))]
            if len(kept) == len(info.indexes):
                raise PlanError(f"unknown index {s.index_name!r}")
            dropped = [ix for ix in info.indexes if ix not in kept]
            info.indexes = kept
            info.version += 1
            # cached plans compiled WITH the index must re-plan
            self._store(s.table)._mutations += 1
            for ix in dropped:
                if ix.kind in ("global", "global_unique"):
                    self._drop_global_backing(db, info, ix)
            self.db.save_catalog()
            return Result()
        if s.index_kind == "ann":
            vcols = (info.options or {}).get("vector_cols") or {}
            if len(s.index_cols) != 1 or s.index_cols[0] not in vcols:
                raise PlanError("ANN INDEX needs exactly one VECTOR column")
        else:
            self._validate_index_cols(s, info)
        prefix = {"fulltext": "ft", "global": "gidx",
                  "global_unique": "gidx", "ann": "ann"}.get(s.index_kind,
                                                            "idx")
        name = s.index_name or f"{prefix}_{'_'.join(s.index_cols)}"
        if any(ix.name == name for ix in info.indexes):
            raise PlanError(f"index {name!r} exists")
        if s.index_kind in ("global", "global_unique"):
            # online ADD GLOBAL INDEX: register backfilling, materialize the
            # backing table (own regions), hand the fill to the DDL worker;
            # the index becomes choosable — and DML starts maintaining it —
            # only at publish
            ix = IndexInfo(name, s.index_kind, list(s.index_cols),
                           {"state": "backfilling"})
            info.indexes.append(ix)
            self._create_global_backing(db, info, ix)
            self.db.save_catalog()
            work = self.db.ddl.submit(f"{db}.{s.table.name}", ix)
            return Result(affected_rows=0, columns=["work_id"],
                          arrow=pa.table({"work_id": [work.work_id]}))
        if s.index_kind == "fulltext":
            # fulltext is dictionary-side (built lazily per dictionary
            # version, index/fulltext.py) — no backfill artifact: declare
            # it public immediately
            info.indexes.append(IndexInfo(name, "fulltext",
                                          list(s.index_cols)))
            info.version += 1
            self.db.save_catalog()
            return Result()
        if s.index_kind == "ann":
            # trained lazily from the current snapshot on first ANN query
            # (index/annindex drift policy) — no backfill artifact
            info.indexes.append(IndexInfo(name, "ann", list(s.index_cols)))
            info.version += 1
            self._store(s.table)._mutations += 1    # cached plans re-plan
            self.db.save_catalog()
            return Result()
        ix = IndexInfo(name, s.index_kind, list(s.index_cols),
                       {"state": "backfilling"})
        info.indexes.append(ix)
        self.db.save_catalog()
        work = self.db.ddl.submit(f"{db}.{s.table.name}", ix)
        return Result(affected_rows=0,
                      columns=["work_id"],
                      arrow=pa.table({"work_id": [work.work_id]}))

    def _alter_partition(self, s: AlterTableStmt, db: str, info) -> Result:
        """ADD PARTITION extends a range-partitioned table's bounds (the
        reference's dynamic-partition management, table_manager.cpp); DROP
        PARTITION removes a partition's ROWS AND its regions — the
        partition-grade bulk delete."""
        spec = (info.options or {}).get("partition")
        if spec is None:
            raise PlanError(f"table {info.name!r} is not partitioned")
        # NOTE: _execute_stmt already implicit-committed any open
        # transaction before dispatching DDL (MySQL semantics), so a later
        # ROLLBACK can never resurrect rows across the partition remap
        store = self._store(s.table)
        if s.action == "add_partition":
            if spec["kind"] != "range":
                raise PlanError("ADD PARTITION applies to RANGE "
                                "partitioning")
            if s.partition_name in spec["names"]:
                raise PlanError(f"partition {s.partition_name!r} exists")
            if spec["uppers"] and spec["uppers"][-1] is None:
                raise PlanError("cannot ADD PARTITION after MAXVALUE")
            f = info.schema.field(spec["column"])
            if s.partition_upper is not None and spec["uppers"]:
                new_u = store._norm_part_scalar(s.partition_upper, f)
                last_u = store._norm_part_scalar(spec["uppers"][-1], f)
                if new_u <= last_u:
                    raise PlanError("new partition bound must exceed the "
                                    "last bound")
            spec["names"].append(s.partition_name)
            spec["uppers"].append(s.partition_upper)
            info.version += 1
            store._mutations += 1
            self.db.save_catalog()
            return Result()
        # drop_partition
        if spec["kind"] != "range":
            raise PlanError("DROP PARTITION applies to RANGE partitioning")
        if s.partition_name not in spec["names"]:
            raise PlanError(f"unknown partition {s.partition_name!r}")
        if len(spec["names"]) == 1:
            raise PlanError("cannot remove all partitions; use DROP TABLE")
        pid = spec["names"].index(s.partition_name)
        with store._lock:
            coupled = self._coupled_global(store)
            import numpy as np

            def mask_fn(t, _store=store, _pid=pid, _spec=spec):
                ids = _store.partition_ids(t)
                return ids == _pid
            if coupled:
                n = self._delete_with_global(store, coupled, mask_fn)
            else:
                n = store.delete_where(mask_fn, self._tctx(store))
            # remap surviving regions' partition tags past the dropped slot
            spec["names"].pop(pid)
            spec["uppers"].pop(pid)
            for r in store.regions:
                if r.part == pid:
                    r.part = -1          # now empty; tag cleared
                elif r.part > pid:
                    r.part -= 1
            info.version += 1
            store._mutations += 1
        self.db.save_catalog()
        return Result(affected_rows=n)

    def _drop_global_backing(self, db: str, info, ix) -> None:
        from ..index import globalindex as gi

        bname = gi.backing_table_name(info.name, ix.name)
        bkey = f"{db}.{bname}"
        self.db.catalog.drop_table(db, bname, if_exists=True)
        self._drop_durable(bkey, self.db.stores.pop(bkey, None))

    def _validate_index_cols(self, s: AlterTableStmt, info) -> None:
        if not s.index_cols:
            raise PlanError("index needs at least one column")
        for c in s.index_cols:
            if c not in info.schema:
                raise PlanError(f"unknown column {c!r}")

    def _alter_rollup(self, s: AlterTableStmt, db: str, info) -> Result:
        from ..index.rollup import rollup_schema, rollup_table_name
        if s.action == "add_rollup":
            if any(ix.name == s.rollup_name for ix in info.indexes):
                raise PlanError(f"index {s.rollup_name!r} exists")
            for c in s.rollup_keys + s.rollup_aggs:
                if c not in info.schema:
                    raise PlanError(f"unknown column {c!r}")
            if not s.rollup_keys:
                raise PlanError("rollup needs at least one key column")
            sch = rollup_schema(info.schema, s.rollup_keys, s.rollup_aggs)
            rt = rollup_table_name(info.name, s.rollup_name)
            rinfo = self.db.catalog.create_table(db, rt, sch, [])
            self.db.stores[f"{db}.{rt}"] = self.db.make_store(rinfo)
            info.indexes.append(IndexInfo(
                s.rollup_name, "rollup", list(s.rollup_keys),
                {"measures": list(s.rollup_aggs), "fresh_at": -1}))
            self.db.save_catalog()
            return Result()
        # drop_rollup
        kept = [ix for ix in info.indexes
                if not (ix.kind == "rollup" and ix.name == s.rollup_name)]
        if len(kept) == len(info.indexes):
            raise PlanError(f"unknown rollup {s.rollup_name!r}")
        info.indexes = kept
        rt = rollup_table_name(info.name, s.rollup_name)
        self.db.catalog.drop_table(db, rt, if_exists=True)
        st = self.db.stores.pop(f"{db}.{rt}", None)
        self._drop_durable(f"{db}.{rt}", st)
        self.db.save_catalog()
        return Result()

    def _alter_table(self, s: AlterTableStmt) -> Result:
        """ALTER TABLE ADD/DROP COLUMN (reference: online column DDL via the
        meta DDLManager; single-node: immediate schema rewrite)."""
        db = s.table.database or self.current_db
        info = self.db.catalog.get_table(db, s.table.name)
        if s.action in ("add_rollup", "drop_rollup"):
            return self._alter_rollup(s, db, info)
        if s.action in ("add_index", "drop_index"):
            return self._alter_index(s, db, info)
        if s.action in ("add_partition", "drop_partition"):
            return self._alter_partition(s, db, info)
        fields = list(info.schema.fields)
        store = self._store(s.table)
        if s.action == "add_column":
            if s.column.name in info.schema:
                raise PlanError(f"column {s.column.name!r} exists")
            if not s.column.nullable and store.num_rows:
                raise PlanError("cannot ADD COLUMN ... NOT NULL to a non-empty "
                                "table (existing rows would hold NULL)")
            fields.append(Field(s.column.name, parse_type(s.column.type_name),
                                s.column.nullable))
        elif s.action == "drop_column":
            if s.column_name not in info.schema:
                raise PlanError(f"unknown column {s.column_name!r}")
            if len(fields) == 1:
                raise PlanError("cannot drop the last column")
            fields = [f for f in fields if f.name != s.column_name]
            # indexes referencing the dropped column go with it
            info.indexes = [ix for ix in info.indexes
                            if s.column_name not in ix.columns]
        else:
            raise PlanError(f"unsupported ALTER action {s.action!r}")
        new_schema = Schema(tuple(fields))
        store.alter_schema(new_schema)   # bumps info.version itself
        self.db.binlog.append("ddl", db, s.table.name,
                              statement=f"ALTER TABLE {s.table.name} {s.action}")
        self.db.save_catalog()
        return Result()

    def ttl_tick(self, now=None) -> int:
        """Purge expired rows of every TTL table (reference: store-side TTL
        timers).  TTL tables declare options TTL=<seconds> and
        TTL_COLUMN=<datetime col> (default create_time)."""
        import datetime

        now = now or datetime.datetime.now()
        purged = 0
        for key, store in list(self.db.stores.items()):
            opts = store.info.options or {}
            if "ttl" not in opts:
                continue
            try:
                col = opts.get("ttl_column", "create_time")
                f = store.info.schema.field(col) if col in store.info.schema else None
                if f is None or not f.ltype.is_temporal:
                    raise ValueError(f"TTL column {col!r} missing or not temporal")
                cutoff = now - datetime.timedelta(seconds=int(opts["ttl"]))
                if f.ltype is LType.DATE:
                    cutoff = cutoff.date()
                n = store.purge_expired(col, cutoff)
            except Exception as exc:
                # one misconfigured table must not block the sweep
                import logging
                logging.getLogger(__name__).warning("TTL skip %s: %s", key, exc)
                continue
            if n:
                db, name = key.split(".", 1)
                self.db.binlog.append("delete", db, name,
                                      statement=f"TTL purge {col} < {cutoff}",
                                      affected=n)
            purged += n
        return purged

    # -- DML --------------------------------------------------------------
    # -- global secondary indexes (reference: separate.cpp:653 lock nodes,
    # select_manager_node.cpp:1081 lookup join) --------------------------
    def _coupled_global(self, store: TableStore) -> list:
        """[(IndexInfo, backing TableStore)] for this table's PUBLIC global
        indexes: DML must maintain the backing tables in the same (2PC)
        transaction as the main table."""
        from ..index import globalindex as gi

        info = store.info
        if gi.is_backing_table(info.name):
            return []
        out = []
        for ix in info.indexes:
            if ix.kind not in ("global", "global_unique") or \
                    ix.params.get("state", "public") != "public":
                continue
            bname = gi.backing_table_name(info.name, ix.name)
            bkey = f"{info.database}.{bname}"
            bstore = self.db.stores.get(bkey)
            if bstore is None:
                binfo = self.db.catalog.get_table(info.database, bname)
                bstore = self.db.stores[bkey] = self.db.make_store(binfo)
            out.append((ix, bstore))
        return out

    def _run_coupled(self, store: TableStore, coupled: list, fn_main,
                     fns_backing: list):
        """Main-table DML + per-index backing maintenance in ONE atomic
        commit: inside an open transaction they ride the session's per-store
        contexts (COMMIT groups them); in autocommit they run under internal
        contexts committed by commit_group — a single primary-first 2PC
        across every touched region group of every table."""
        from ..storage.column_store import commit_group

        if self._sql_txn is not None:
            r = fn_main(self._tctx(store))
            for (ix, bstore), fb in zip(coupled, fns_backing):
                fb(self._tctx(bstore), r)
            return r
        tctxs = [store.begin_txn()]
        try:
            for ix, bstore in coupled:
                tctxs.append(bstore.begin_txn())
            r = fn_main(tctxs[0])
            for (ix, bstore), fb, t in zip(coupled, fns_backing, tctxs[1:]):
                fb(t, r)
        except BaseException:
            for t in tctxs:
                try:
                    t.rollback()
                except Exception:   # best-effort unwind; keep it countable
                    metrics.count_swallowed("session.coupled_rollback")
            raise
        commit_group(tctxs)
        return r

    def _ingest_arrow(self, store: TableStore, table: "pa.Table",
                      check_dups: bool = False) -> None:
        """Bulk ingest honoring global indexes: entry projections land in
        the backing tables in the same atomic commit (the reference's
        importer maintains global indexes through the same DML plane)."""
        with store._lock:   # one critical section vs backfill publish
            coupled = self._coupled_global(store)
            if not coupled:
                store.insert_arrow(table, self._tctx(store),
                                   check_dups=check_dups)
                return
            from ..index import globalindex as gi

            info = store.info
            if any(ix.kind == "global_unique" for ix, _ in coupled):
                # rows materialize only when a unique check will use them
                rows = table.to_pylist()
                for ix, bstore in coupled:
                    gi.check_unique(info, ix, bstore, rows)

            def main(t):
                store.insert_arrow(table, t, check_dups=check_dups)

            fbs = [(lambda t, _r, ix=ix, b=bstore:
                    b.insert_arrow(gi.entry_table(info, ix, table), t))
                   for ix, bstore in coupled]
            self._run_coupled(store, coupled, main, fbs)

    def _insert_with_global(self, store: TableStore, coupled: list,
                            rows: list[dict]) -> None:
        from ..index import globalindex as gi

        info = store.info
        for ix, bstore in coupled:
            gi.check_unique(info, ix, bstore, rows)

        def main(t):
            store.insert_rows(rows, t)

        fbs = [(lambda t, _r, ix=ix, b=bstore:
                b.insert_rows(gi.entry_rows(info, ix, rows), t))
               for ix, bstore in coupled]
        self._run_coupled(store, coupled, main, fbs)

    def _delete_with_global(self, store: TableStore, coupled: list,
                            mask_fn) -> int:
        from ..index import globalindex as gi

        info = store.info
        cols = sorted({f.name for ix, _ in coupled
                       for f in gi.backing_schema(info, ix).fields})

        def main(t):
            return store.delete_where(mask_fn, t, collect_cols=cols)

        def fb(t, r, ix=None, b=None):
            _, old = r
            entries = gi.entry_table(info, ix, old)
            if entries.num_rows:
                b.delete_where(self._entry_delete_mask(entries), t)

        fbs = [(lambda t, r, ix=ix, b=bstore: fb(t, r, ix, b))
               for ix, bstore in coupled]
        return self._run_coupled(store, coupled, main, fbs)[0]

    def _update_with_global(self, store: TableStore, coupled: list,
                            mask_fn, assign_fn,
                            changed_cols: list[str]) -> int:
        from ..index import globalindex as gi

        info = store.info
        pk = info.primary_key()
        pk_cols = list(pk.columns) if pk else []
        # only indexes whose entries can actually change need maintenance
        touched = [(ix, b) for ix, b in coupled
                   if set(changed_cols) & set(list(ix.columns) + pk_cols)]
        if not touched:
            return store.update_where(mask_fn, assign_fn, self._tctx(store),
                                      changed_cols=changed_cols)
        cols = sorted({f.name for ix, _ in touched
                       for f in gi.backing_schema(info, ix).fields})
        # unique check BEFORE any mutation (a failed check mid-statement
        # would leave main updated but index entries stale): a dry run
        # computes the would-be old/new rows; the caller holds store._lock,
        # so the real update below sees the same rows
        _, dry_old, dry_new = store.update_where(
            mask_fn, assign_fn, self._tctx(store),
            changed_cols=changed_cols, collect_cols=cols, dry_run=True)
        exclude = set(zip(*[dry_old.column(c).to_pylist()
                            for c in pk_cols])) \
            if pk_cols and dry_old.num_rows else set()
        for ix, bstore in touched:
            gi.check_unique(info, ix, bstore, dry_new.to_pylist(),
                            exclude_pks=exclude)

        def main(t):
            return store.update_where(mask_fn, assign_fn, t,
                                      changed_cols=changed_cols,
                                      collect_cols=cols)

        def fb(t, r, ix=None, b=None):
            _, old, new = r
            old_e = gi.entry_table(info, ix, old)
            new_e = gi.entry_table(info, ix, new)
            if old_e.num_rows:
                b.delete_where(self._entry_delete_mask(old_e), t)
            if new_e.num_rows:
                b.insert_rows(new_e.to_pylist(), t)

        fbs = [(lambda t, r, ix=ix, b=bstore: fb(t, r, ix, b))
               for ix, bstore in touched]
        return self._run_coupled(store, touched, main, fbs)[0]

    @staticmethod
    def _entry_delete_mask(entries):
        """Backing-table mask fn matching rows whose full entry tuple is in
        ``entries`` (the outgoing index entries of a DELETE/UPDATE)."""
        import numpy as np

        names = entries.column_names
        tuples = set(zip(*[entries.column(c).to_pylist() for c in names])) \
            if entries.num_rows else set()

        def bmask(bt):
            if not bt.num_rows or not tuples:
                return np.zeros(bt.num_rows, dtype=bool)
            vals = zip(*[bt.column(c).to_pylist() for c in names])
            return np.fromiter((v in tuples for v in vals), dtype=bool,
                               count=bt.num_rows)
        return bmask

    def _insert(self, s: InsertStmt) -> Result:
        store = self._store(s.table)
        schema = store.info.schema
        if s.select is not None:
            sub = self._select(s.select)
            t = sub.arrow
            if s.columns:
                t = t.rename_columns(s.columns)
            else:
                t = t.rename_columns(schema.names()[:t.num_columns])
            if s.replace or s.on_dup:
                # REPLACE INTO .. SELECT / INSERT .. SELECT .. ON DUP KEY:
                # same upsert semantics as the VALUES form
                return self._insert_upsert(
                    store, s, t.to_pylist(),
                    s.table.database or self.current_db)
            if t.num_rows <= HOT_INSERT_ROWS:
                # small INSERT..SELECT takes the hot path: PK-checked and
                # WAL-durable like INSERT..VALUES
                with store._lock:   # vs backfill publish
                    coupled = self._coupled_global(store)
                    if coupled:
                        self._insert_with_global(store, coupled,
                                                 t.to_pylist())
                    else:
                        store.insert_rows(t.to_pylist(), self._tctx(store))
            else:
                self._ingest_arrow(store, t, check_dups=True)
            db_name = s.table.database or self.current_db
            if t.num_rows > 1000:
                self._log_binlog("insert", db_name, s.table.name,
                                 statement=f"bulk insert {t.num_rows} rows",
                                 affected=t.num_rows)
            else:
                self._log_binlog("insert", db_name, s.table.name,
                                 rows=t.to_pylist(), affected=t.num_rows)
            return Result(affected_rows=t.num_rows)
        vcols = (store.info.options or {}).get("vector_cols") or {}
        # positional VALUES address user-visible columns (vector columns by
        # their own names, components hidden)
        cols = s.columns or self._user_columns(store)
        if any(len(r) != len(cols) for r in s.rows):
            raise SqlError("VALUES row length does not match column list")
        rows = [dict(zip(cols, r)) for r in s.rows]
        if vcols:
            rows = [_expand_vector_row(r, vcols) for r in rows]
        auto_col = (store.info.options or {}).get("auto_increment")
        if auto_col:
            missing = [i for i, r in enumerate(rows)
                       if r.get(auto_col) is None]
            if missing:
                ids = store.next_auto_incr(auto_col, len(missing))
                for i, v in zip(missing, ids):
                    rows[i][auto_col] = v
            # explicit ids advance the counter inside the store (all ingest
            # paths — VALUES, INSERT..SELECT, LOAD DATA — share that hook)
        db_name = s.table.database or self.current_db
        for r in rows:
            for f in schema.fields:
                if f.name in r and r[f.name] is not None and f.ltype.is_temporal \
                        and isinstance(r[f.name], str):
                    from ..expr.compile import parse_temporal
                    import datetime
                    v = parse_temporal(r[f.name], f.ltype)
                    if f.ltype is LType.DATE:
                        r[f.name] = datetime.date(1970, 1, 1) + datetime.timedelta(days=v)
                    else:
                        r[f.name] = datetime.datetime(1970, 1, 1) + \
                            datetime.timedelta(microseconds=v)
        if s.replace or s.on_dup:
            return self._insert_upsert(store, s, rows, db_name)
        # the coupling decision, unique check, and mutation must be ONE
        # critical section against the backfill worker's publish (which
        # snapshots + flips the index state under this same lock): deciding
        # "no maintenance" outside it could lose an entry forever
        with store._lock:
            coupled = self._coupled_global(store)
            if coupled:
                self._insert_with_global(store, coupled, rows)
            else:
                store.insert_rows(rows, self._tctx(store))
        self._log_binlog("insert", db_name, s.table.name, rows=rows,
                         affected=len(rows))
        return Result(affected_rows=len(rows))

    def _insert_upsert(self, store: TableStore, s, rows: list[dict],
                       db_name: str) -> Result:
        """REPLACE INTO (delete conflicting PKs, insert all — MySQL counts
        2 per replaced row) and INSERT ... ON DUPLICATE KEY UPDATE
        (insert the new, apply assignments to the conflicting — literals
        and VALUES(col) references).  Reference: insert_planner.cpp
        REPLACE / ON DUP KEY handling."""
        import numpy as np

        if store._pk_cols is None:
            raise PlanError("REPLACE / ON DUPLICATE KEY needs a PRIMARY "
                            "KEY")
        cols = {f.name: [r.get(f.name) for r in rows]
                for f in store.arrow_schema}
        incoming = pa.table(cols, schema=store.arrow_schema)
        with store._lock:
            keys = store._encode_pk_table(incoming)
            idx = store._ensure_pk_index()
            # MySQL processes VALUES rows in order: a key may conflict with
            # the TABLE or with an EARLIER row of the same statement — both
            # are "duplicates", and later occurrences win sequentially
            dupset: set = set()
            new_rows: list[dict] = []
            dup_rows: list[tuple] = []
            seen: set = set()
            for k, r in zip(keys, rows):
                if k in idx or k in seen:
                    dup_rows.append((k, r))
                    if k in idx:
                        dupset.add(k)
                else:
                    new_rows.append(r)
                seen.add(k)
            # rows beyond the first occurrence of their key, however the
            # first fared: each counts as a sequential within-batch replace
            batch_extras = len(rows) - len(seen)

            def mask_over(keyset):
                def pk_mask(t: pa.Table):
                    ks = store._encode_pk_table(t)
                    return np.asarray([k in keyset for k in ks], bool)
                return pk_mask

            coupled = self._coupled_global(store)
            affected = 0
            if s.replace:
                if dupset:
                    if coupled:
                        n = self._delete_with_global(store, coupled,
                                                     mask_over(dupset))
                    else:
                        n = store.delete_where(mask_over(dupset),
                                               self._tctx(store))
                    affected += n
                # last occurrence per key wins (sequential REPLACE result)
                effective: dict = {}
                order: list = []
                for k, r in zip(keys, rows):
                    if k not in effective:
                        order.append(k)
                    effective[k] = r
                ins = [effective[k] for k in order]
                if coupled:
                    self._insert_with_global(store, coupled, ins)
                else:
                    store.insert_rows(ins, self._tctx(store))
                affected += len(rows) + batch_extras
            else:
                if new_rows:
                    if coupled:
                        self._insert_with_global(store, coupled, new_rows)
                    else:
                        store.insert_rows(new_rows, self._tctx(store))
                    affected += len(new_rows)
                if dup_rows:
                    pk_mask = mask_over({k for k, _ in dup_rows})
                    mapping = {}
                    for k, r in dup_rows:
                        vals = {}
                        for col, (kind, v) in s.on_dup:
                            if col not in store.info.schema:
                                raise PlanError(f"unknown column {col!r}")
                            vals[col] = r.get(v) if kind == "values" else v
                        mapping[k] = vals
                    assigned = sorted({c for c, _ in s.on_dup})

                    def assign_fn(t: pa.Table, mask):
                        ks = store._encode_pk_table(t)
                        out = t
                        for col in assigned:
                            f = store.arrow_schema.field(col)
                            old = t.column(col).to_pylist()
                            newv = [mapping.get(k, {}).get(col, old[i])
                                    if m else old[i]
                                    for i, (k, m) in enumerate(
                                        zip(ks, np.asarray(mask)))]
                            out = out.set_column(
                                out.column_names.index(col), f,
                                pa.array(newv, f.type))
                        return out

                    if coupled:
                        n = self._update_with_global(store, coupled,
                                                     pk_mask, assign_fn,
                                                     assigned)
                    else:
                        n = store.update_where(pk_mask, assign_fn,
                                               self._tctx(store),
                                               changed_cols=assigned)
                    affected += 2 * n       # MySQL: 2 per updated row
        # statement image only: the applied row state differs from the
        # incoming VALUES for updated rows, so a row-image 'insert' event
        # would diverge CDC subscribers from the source
        self._log_binlog("insert", db_name, s.table.name,
                         affected=affected,
                         statement=_stmt_image(
                             "replace" if s.replace else "upsert", s))
        return Result(affected_rows=affected)

    def _user_columns(self, store: TableStore) -> list[str]:
        """Declared column order with vector components collapsed back to
        their user-visible vector column name."""
        vcols = (store.info.options or {}).get("vector_cols") or {}
        out: list[str] = []
        for n in store.info.schema.names():
            owner = _component_owner(n, vcols)
            if owner is None:
                out.append(n)
            elif not out or out[-1] != owner:
                out.append(owner)
        return out

    def _host_mask(self, store: TableStore, where):
        """Build host mask fn: predicate evaluated by the SAME device compiler
        over each region (one semantics for reads and writes)."""
        from ..expr.ast import ColRef as _CR

        def fn(region_table: pa.Table):
            if where is None:
                return np.ones(region_table.num_rows, dtype=bool)
            b = ColumnBatch.from_arrow(region_table)
            m = eval_predicate(_qualify_free(where), b)
            return np.asarray(m)

        return fn

    def _pk_mask_fn(self, store: TableStore, key: dict):
        """Host mask for a full-PK-equality WHERE: pyarrow compute only —
        no ColumnBatch encode, no device program (the OLTP write path's
        analog of the point-select fast path; reference: primary-index
        point DML through the row path, region.cpp dml_1pc)."""
        import pyarrow.compute as pc

        sch = store.arrow_schema
        # cast literals NOW, so a type-mismatched literal (id = 2.5 on a
        # BIGINT pk) rejects the fast path here — inside the caller's
        # try/except — instead of aborting the statement mid-region-scan
        # (the compiled predicate evaluates such comparisons numerically)
        scalars = {col: pa.scalar(v).cast(sch.field(col).type)
                   for col, v in key.items()}
        for col, v in key.items():
            if scalars[col].as_py() != v:
                raise ValueError("lossy literal cast")    # e.g. 2.5 -> 2

        def fn(region_table: pa.Table):
            m = None
            for col, sc in scalars.items():
                c = pc.equal(region_table.column(col), sc)
                m = c if m is None else pc.and_(m, c)
            return np.asarray(pc.fill_null(m, False))

        return fn

    def _point_write_mask(self, store: TableStore, where):
        """The cheap PK mask when WHERE fixes the whole primary key by
        equality; None otherwise (fall back to the compiled predicate)."""
        key = self._point_write_key(store, where)
        if key is None:
            return None
        try:
            return self._pk_mask_fn(store, key)
        except Exception:
            return None              # odd literal/type: compiled path

    @staticmethod
    def _point_write_key(store: TableStore, where) -> Optional[dict]:
        """The primary key WHERE fixes by equality, or None."""
        from ..index.selector import point_key

        if store._pk_cols is None or where is None:
            return None

        class _W:                    # point_key reads .where only
            pass

        w = _W()
        w.where = where
        try:
            return point_key(w, store._pk_cols)
        except Exception:
            return None

    def _patch_key(self, store: TableStore, where, db_name: str,
                   table: str) -> Optional[dict]:
        """The key of a write the store can take as a patch
        (``TableStore.pk_update`` / ``pk_delete``): WHERE fixes an integer
        primary key to a whole number, no global index rides the table, and
        no CDC consumer wants its rows; else None."""
        if not store.pk_patchable():
            return None
        key = self._point_write_key(store, where)
        if key is None:
            return None
        v = next(iter(key.values()))
        if isinstance(v, bool) or not isinstance(v, int) \
                or self._coupled_global(store) \
                or self.db.cdc.wants_rows(f"{db_name}.{table}"):
            return None
        return key

    @staticmethod
    def _host_assign(assigns, schema):
        """``row -> new row`` for assignments of literals, columns and
        ``+`` / ``-`` / ``*`` over them evaluated in Python; None when some
        assignment is anything else (the compiled path evaluates it)."""
        from ..expr.ast import Call, Lit
        from ..expr.ast import ColRef as _CRef

        ops = {"add": lambda a, b: a + b, "sub": lambda a, b: a - b,
               "mul": lambda a, b: a * b}

        def compile_(e):
            if isinstance(e, Lit):
                v = e.value
                return lambda row: v
            if isinstance(e, _CRef):
                name = e.name.split(".")[-1]
                if name not in schema:
                    return None
                return lambda row: row[name]
            if isinstance(e, Call) and e.op in ops and len(e.args) == 2:
                a, b = (compile_(x) for x in e.args)
                if a is None or b is None:
                    return None
                f = ops[e.op]

                def ev(row):
                    x, y = a(row), b(row)
                    if x is None or y is None:
                        return None
                    if not all(isinstance(t, int) and not isinstance(t, bool)
                               for t in (x, y)):
                        raise TypeError("not integer arithmetic")
                    return f(x, y)
                return ev
            return None

        fns = []
        for name, e in assigns:
            f = schema.field(name)
            fn = compile_(_qualify_free(e))
            if fn is None:
                return None
            if isinstance(e, Lit):
                v = e.value
                ok = (v is None or (f.ltype is LType.STRING
                                    and isinstance(v, str))
                      or (f.ltype.is_integer and isinstance(v, int)
                          and not isinstance(v, bool)))
                if not ok:
                    return None
            elif not f.ltype.is_integer:
                return None
            fns.append((name, fn))

        def assign(row):
            new = dict(row)
            for name, fn in fns:
                new[name] = fn(row)
            return new
        return assign

    def _update(self, s: UpdateStmt) -> Result:
        store = self._store(s.table)
        schema = store.info.schema
        arrow_schema = store.arrow_schema
        assigns = s.assignments
        for name, _ in assigns:
            if name not in schema:
                raise PlanError(f"unknown column {name!r}")

        def assign_fn(region_table: pa.Table, mask: np.ndarray) -> pa.Table:
            # columnar merge (if_else over the WHERE mask) — no per-row
            # Python; this is the write-path hot loop the reference keeps
            # in C++ (UpdateNode row mutation, src/exec/update_node.cpp)
            b = ColumnBatch.from_arrow(region_table)
            out = region_table
            n = region_table.num_rows
            cond = pa.array(np.asarray(mask, bool))
            for name, e in assigns:
                c = eval_output(_qualify_free(e), b)
                data, valid = c.to_numpy()
                f = arrow_schema.field(name)
                if np.ndim(data) == 0:
                    data = np.broadcast_to(data, (n,))
                if c.ltype is LType.STRING and c.dictionary is not None:
                    vals = c.dictionary.decode(np.asarray(data, np.int32))
                else:
                    vals = np.asarray(data)
                if valid is None:
                    nulls = None
                else:
                    v = np.asarray(valid, bool)
                    nulls = ~(np.broadcast_to(v, (n,)) if v.ndim == 0 else v)
                new_arr = pa.array(vals, mask=nulls)
                if new_arr.type != f.type:
                    new_arr = new_arr.cast(f.type)
                idx = out.column_names.index(name)
                merged = pa.compute.if_else(cond, new_arr, out.column(name))
                out = out.set_column(idx, f, merged)
            return out

        db_name = s.table.database or self.current_db
        changed = [name for name, _ in assigns]
        pkey = self._patch_key(store, s.where, db_name, s.table.name)
        if pkey is not None and not set(changed) & set(store._pk_cols):
            # the row by the pk index, the new version a patch of the
            # resident image (TableStore.pk_update)
            fast = self._host_assign(assigns, schema)

            def assign_row(row, _fast=fast):
                if _fast is not None:
                    try:
                        return _coerce_row(_fast(row), arrow_schema)
                    except (TypeError, OverflowError, ValueError):
                        pass
                one = pa.Table.from_pylist([row], schema=arrow_schema)
                return assign_fn(one, np.ones(1, bool)).to_pylist()[0]
            got = store.pk_update(pkey, assign_row, self._tctx(store))
            n = 0 if got is None else 1
            if n:
                self._log_binlog("update", db_name, s.table.name, rows=None,
                                 statement=_stmt_image("update", s),
                                 affected=n)
            return Result(affected_rows=n)
        mask_fn = self._point_write_mask(store, s.where)
        if mask_fn is not None:
            # point update: evaluate assignments on the ONE matched row,
            # restricted to the columns the assignments actually touch
            # (encoding untouched VARCHARs into device dictionaries is the
            # dominant cost otherwise), then scalar-merge into the region
            from ..expr.ast import ColRef as _CRef

            needed = {store._pk_cols[0]}
            for name, e in assigns:
                needed.add(name)
                stack = [_qualify_free(e)]
                while stack:
                    x = stack.pop()
                    if isinstance(x, _CRef):
                        needed.add(x.name.split(".")[-1])
                    stack.extend(getattr(x, "args", ()) or ())
            full_assign = assign_fn

            def assign_fn(region_table, mask, _full=full_assign):
                cond = pa.array(np.asarray(mask, bool))
                rows = region_table.filter(cond)
                if rows.num_rows != 1:      # PK dup (shouldn't happen):
                    return _full(region_table, np.asarray(mask, bool))
                rows = rows.select([c for c in region_table.column_names
                                    if c in needed])
                try:
                    small = _full(rows, np.ones(1, dtype=bool))
                except Exception:
                    # a 1-row slice can hit shapes the full path never sees
                    # (e.g. empty dictionaries); semantics win over speed
                    return _full(region_table, np.asarray(mask, bool))
                out = region_table
                for name, _ in assigns:
                    f = arrow_schema.field(name)
                    idx = out.column_names.index(name)
                    merged = pa.compute.if_else(cond, small.column(name)[0],
                                                out.column(name))
                    out = out.set_column(idx, f, merged)
                return out
        else:
            mask_fn = self._host_mask(store, s.where)
        # row-image capture for CDC/matviews: old/new pairs let consumers
        # fold the delta instead of rescanning; only on the non-coupled
        # path (the global-index path dry-runs assign_fn, which would
        # double-capture) and self-verified below against the affected
        # count — any mismatch falls back to the statement image, which
        # consumers treat as "rescan"
        captured: list = []
        with store._lock:   # one critical section vs backfill publish
            coupled = self._coupled_global(store)
            if coupled:
                n = self._update_with_global(store, coupled, mask_fn,
                                             assign_fn, changed)
            else:
                use_assign = assign_fn
                if self.db.cdc.wants_rows(f"{db_name}.{s.table.name}"):
                    def use_assign(t, mask, _inner=assign_fn):
                        cond = pa.array(np.asarray(mask, bool))
                        old = t.filter(cond).to_pylist()
                        out = _inner(t, mask)
                        new = out.filter(cond).to_pylist()
                        captured.extend({"old": o, "new": w}
                                        for o, w in zip(old, new))
                        return out
                n = store.update_where(mask_fn, use_assign,
                                       self._tctx(store),
                                       changed_cols=changed)
        if n:
            rows = captured if len(captured) == n else None
            self._log_binlog("update", db_name, s.table.name, rows=rows,
                             statement=_stmt_image("update", s), affected=n)
        return Result(affected_rows=n)

    def _delete(self, s: DeleteStmt) -> Result:
        store = self._store(s.table)
        db_name = s.table.database or self.current_db
        pkey = self._patch_key(store, s.where, db_name, s.table.name)
        if pkey is not None:
            # the row by the pk index, its lane a patch of the resident
            # image (TableStore.pk_delete)
            old = store.pk_delete(pkey, self._tctx(store))
            if old is not None:
                self._log_binlog("delete", db_name, s.table.name, rows=None,
                                 statement=_stmt_image("delete", s),
                                 affected=1)
            return Result(affected_rows=int(old is not None))
        mask_fn = self._point_write_mask(store, s.where) or \
            self._host_mask(store, s.where)
        # row-image capture (see _update): outgoing rows let CDC consumers
        # retract exactly; count-verified, statement-image fallback
        captured: list = []
        with store._lock:   # one critical section vs backfill publish
            coupled = self._coupled_global(store)
            if coupled:
                n = self._delete_with_global(store, coupled, mask_fn)
            else:
                use_mask = mask_fn
                if self.db.cdc.wants_rows(f"{db_name}.{s.table.name}"):
                    def use_mask(t, _inner=mask_fn):
                        m = np.asarray(_inner(t), bool)
                        if m.any():
                            captured.extend(
                                t.filter(pa.array(m)).to_pylist())
                        return m
                n = store.delete_where(use_mask, self._tctx(store))
        if n:
            rows = captured if len(captured) == n else None
            self._log_binlog("delete", db_name, s.table.name, rows=rows,
                             statement=_stmt_image("delete", s), affected=n)
        return Result(affected_rows=n)

    # -- SELECT ---------------------------------------------------------
    def _select_into_outfile(self, stmt: SelectStmt, cache_key) -> Result:
        """SELECT ... INTO OUTFILE: run the query, stream the rows to a
        file (reference: full_export_node streaming export,
        src/exec/full_export_node.cpp).  MySQL conventions: refuses to
        overwrite (O_EXCL claim, concurrency-safe), \\N for NULL,
        backslash escaping of separators, 1/0 booleans, the row count as
        the result."""
        import copy
        import os
        import tempfile

        path, fsep, lsep = stmt.into_outfile
        try:
            final_fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            raise PlanError(f"OUTFILE {path!r} already exists") from None
        inner = copy.copy(stmt)
        inner.into_outfile = None
        try:
            res = self._select(
                inner, cache_key=None if cache_key is None else
                (cache_key[0] + " /*outfile*/", cache_key[1]))

            def cell(v):
                if v is None:
                    return "\\N"
                if isinstance(v, bool):
                    return "1" if v else "0"
                s = str(v)
                return (s.replace("\\", "\\\\")
                        .replace(fsep, "\\" + fsep)
                        .replace(lsep, "\\" + lsep))

            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(
                os.path.abspath(path)) or ".", suffix=".outfile")
            try:
                with os.fdopen(fd, "w", encoding="utf-8", newline="") as f:
                    for r in res.rows:          # positional: duplicate
                        f.write(fsep.join(       # column names stay intact
                            cell(v) for v in r) + lsep)
                os.replace(tmp, path)
            except BaseException:
                os.unlink(tmp)
                raise
        except BaseException:
            os.close(final_fd)
            os.unlink(path)
            raise
        os.close(final_fd)
        n = res.arrow.num_rows if res.arrow is not None else 0
        return Result(affected_rows=n)

    def _select_group_concat(self, stmt: SelectStmt) -> Result:
        """GROUP_CONCAT is an egress aggregate: device strings are dictionary
        codes, so concatenation happens at the result layer (the reference
        also accumulates GROUP_CONCAT strings row-wise on CPU,
        src/expr/agg_fn_call.cpp — same tier, different engine).  Runs the
        grouped query without the GROUP_CONCAT items plus one detail query
        (keys + inputs), then assembles strings host-side."""
        import copy

        from ..expr.ast import AggCall
        from ..plan.planner import _display_name
        from ..sql.stmt import SelectItem

        from ..expr.ast import Call as _Call
        from ..expr.ast import ColRef as _ColRef
        from ..expr.ast import Lit as _Lit

        gc: dict[int, object] = {}
        for i, item in enumerate(stmt.items):
            e = item.expr
            if isinstance(e, AggCall) and e.op == "group_concat":
                extra = e.args[1:]
                if any(not (isinstance(x, _Call) and x.op == "__sep")
                       for x in extra):
                    raise PlanError("multi-argument GROUP_CONCAT is not "
                                    "supported (use CONCAT inside it)")
                gc[i] = item

        def mentions_gc(e):
            if isinstance(e, AggCall) and e.op == "group_concat":
                return True
            args = getattr(e, "args", ())
            return any(mentions_gc(a) for a in args)

        if stmt.having is not None and mentions_gc(stmt.having):
            raise PlanError("GROUP_CONCAT in HAVING is not supported")
        gc_aliases = {stmt.items[i].alias for i in gc if stmt.items[i].alias}
        for o in stmt.order_by:
            if mentions_gc(o.expr) or (isinstance(o.expr, _ColRef) and
                                       o.expr.table is None and
                                       o.expr.name in gc_aliases):
                raise PlanError("GROUP_CONCAT in ORDER BY is not supported")
        for i, item in enumerate(stmt.items):
            if i not in gc and mentions_gc(item.expr):
                raise PlanError("GROUP_CONCAT nested in an expression is "
                                "not supported")

        # resolve ordinal (GROUP BY 1) and select-alias keys BEFORE copying
        # them into the helper queries (the planner normally does this)
        keys = []
        alias_map = {it.alias: it.expr for it in stmt.items if it.alias}
        for k in stmt.group_by:
            if isinstance(k, _Lit) and isinstance(k.value, int):
                idx = k.value - 1
                if not 0 <= idx < len(stmt.items) or idx in gc:
                    raise PlanError(f"GROUP BY ordinal {k.value} is invalid "
                                    "here")
                keys.append(stmt.items[idx].expr)
            elif isinstance(k, _ColRef) and k.table is None and \
                    k.name in alias_map:
                if mentions_gc(alias_map[k.name]):
                    raise PlanError("GROUP BY a GROUP_CONCAT alias is invalid")
                keys.append(alias_map[k.name])
            else:
                keys.append(k)
        key_aliases = [f"__gck{j}" for j in range(len(keys))]
        base = copy.copy(stmt)
        base.group_by = [copy.copy(k) for k in keys]   # resolved form
        base.items = [it for i, it in enumerate(stmt.items) if i not in gc]
        n_vis = len(base.items)
        base.items = base.items + [SelectItem(copy.copy(k), a)
                                   for k, a in zip(keys, key_aliases)]
        if not base.items:
            base.items = [SelectItem(AggCall("count_star", ()), "__gcn")]
            n_vis = 0
        main = self._select(base)

        detail = copy.copy(stmt)
        detail.group_by = []
        detail.having = None
        detail.order_by = []
        detail.limit = None
        detail.offset = 0
        detail.distinct = False
        ins = [gc[i].expr.args[0] for i in gc]
        detail.items = [SelectItem(copy.copy(k), a)
                        for k, a in zip(keys, key_aliases)] + \
                       [SelectItem(copy.copy(e), f"__gcv{j}")
                        for j, e in enumerate(ins)]
        drows = self._select(detail).to_pylist()
        groups: dict[tuple, list[list]] = {}
        for r in drows:
            k = tuple(r[a] for a in key_aliases)
            slot = groups.setdefault(k, [[] for _ in ins])
            for j in range(len(ins)):
                v = r[f"__gcv{j}"]
                if v is not None:
                    slot[j].append(v)

        mrows = main.to_pylist()
        mcols = list(main.arrow.column_names)
        out_cols: dict[str, list] = {}
        order_names: list[str] = []
        vis_iter = iter(mcols[:n_vis])
        gclist = list(gc.items())
        for i, item in enumerate(stmt.items):
            if i in gc:
                j = next(jj for jj, (idx, _) in enumerate(gclist) if idx == i)
                call = gc[i].expr
                sep = ","
                if len(call.args) > 1:
                    sep = str(call.args[1].args[0].value)   # __sep wrapper
                vals = []
                for r in mrows:
                    k = tuple(r[a] for a in key_aliases)
                    lst = groups.get(k, [[] for _ in ins])[j]
                    if call.distinct:
                        lst = sorted(set(map(str, lst)))
                    else:
                        lst = list(map(str, lst))
                    # MySQL truncates at group_concat_max_len (default 1024)
                    vals.append(sep.join(lst)[:1024] if lst else None)
                name = gc[i].alias or _display_name(call)
                order_names.append(name)
                out_cols[name] = vals
            else:
                name = next(vis_iter)
                order_names.append(name)
                out_cols[name] = [r[name] for r in mrows]
        table = pa.table({n: out_cols[n] for n in order_names})
        return Result(columns=order_names, arrow=table)

    def _select(self, stmt: SelectStmt, cache_key=None) -> Result:
        """MVCC snapshot scope around the planner: resolve the read
        timestamp (explicit SET SNAPSHOT pin, else an automatic pin for
        eligible analytical statements), hold it in ``self._snap_ts`` for
        the whole execution — every batch-staging seam underneath reads
        it — and release an automatic pin when the query finishes."""
        with self._snapshot_pinned(stmt):
            return self._select_impl(stmt, cache_key)

    @contextmanager
    def _snapshot_pinned(self, stmt: SelectStmt):
        """Enter this SELECT's snapshot scope (see _snapshot_scope)."""
        with trace.span("select.route"):
            pin = self._snapshot_scope(stmt)
        if pin is None:
            yield
            return
        pid, ts = pin
        prev = self._snap_ts
        self._snap_ts = ts
        try:
            yield
        finally:
            self._snap_ts = prev
            if pid is not None:
                self.db.mvcc.snapshots.unpin(pid)

    def _snapshot_scope(self, stmt: SelectStmt):
        """(pin_id | None, snap_ts) for this SELECT, or None to read
        unpinned.  An explicit session pin (SET SNAPSHOT) always applies
        and is NOT released per-query (pin_id None here).  Otherwise an
        analytical statement (GROUP BY / aggregates) pins a fresh
        timestamp automatically for its own duration, so a long scan sees
        one consistent state under live writes — but only outside SQL
        transactions (the txn's own locks already isolate it) and off the
        mesh path (sharded device batches stage through their own seam;
        documented limitation).  A chaos-refused automatic pin degrades
        to the unpinned read it would have been before MVCC."""
        if not bool(FLAGS.mvcc):
            return None
        if self._snap_ts:
            return None     # nested SELECT (subquery): inherit the scope
        if self._snapshot is not None:
            return (None, self._snapshot[1])
        if self._sql_txn is not None and self.mesh is None:
            # a transaction reads one snapshot, taken by its first SELECT
            # and held to its end.  A table it has written reads its latest
            # state instead, so its own PENDING rows show: its writer lease
            # keeps every other writer off that table, and _tctx refused the
            # lease if the table had moved past the snapshot, so that state
            # is the snapshot's plus the transaction's own writes
            tables = self._qos_tables([stmt])
            if not any(t in self._sql_txn for t in tables):
                return self._txn_snapshot()
            if self._txn_pin is not None:
                for t in tables:
                    store = self.db.stores.get(t)
                    if t not in self._sql_txn and store is not None and \
                            store.mvcc_needs_versioned(self._txn_pin[1]):
                        raise ConflictError(
                            f"table {t} changed since this transaction's "
                            "snapshot, and the statement also reads a "
                            "table the transaction wrote")
            return None
        if self._sql_txn is not None or self.mesh is not None:
            return None
        from ..expr.ast import AggCall
        analytical = bool(stmt.group_by) or any(
            isinstance(it.expr, AggCall) for it in stmt.items)
        if not analytical:
            return None
        if self._try_matview(stmt, refresh=False) is not None:
            # a materialized view will answer this aggregate from folded
            # state; pinning first would hide the maintenance writes
            return None
        if self._try_rollup(stmt, refresh=False) is not None:
            # a rollup covers this aggregate: the version-gated refresh
            # already materializes ONE consistent cut of the base table,
            # and pinning first would hide the refresh's own writes
            return None
        if self._pushdown_candidate(stmt) is not None:
            # served by daemon-plane fragments over their own region
            # images; snapshot_ts does not travel with fragments yet
            # (ROADMAP), so a pin only adds TSO/registry round-trips
            return None
        from ..storage.mvcc import SnapshotRefused
        ts = self.db.mvcc.now_ts()
        try:
            with trace.span("snapshot.pin", ts=ts, explicit=False):
                pid = self.db.mvcc.snapshots.pin(
                    ts, query="auto", holder=self.user)
        except SnapshotRefused:
            metrics.count_swallowed("snapshot.autopin")
            return None
        return (pid, ts)

    def _txn_snapshot(self):
        """(None, ts) of the open transaction's snapshot, pinned on the
        first call (released by ``_release_txn_snapshot``)."""
        from ..storage.mvcc import SnapshotRefused
        if self._txn_pin is None:
            ts = self.db.mvcc.now_ts()
            try:
                pid = self.db.mvcc.snapshots.pin(ts, query="txn",
                                                 holder=self.user)
            except SnapshotRefused:
                metrics.count_swallowed("snapshot.txnpin")
                return None
            self._txn_pin = (pid, ts)
        return (None, self._txn_pin[1])

    def _release_txn_snapshot(self) -> None:
        if self._txn_pin is not None:
            self.db.mvcc.snapshots.unpin(self._txn_pin[0])
            self._txn_pin = None

    def _snap_dirty(self, stmt) -> bool:
        """Does the pinned snapshot actually diverge from the live image
        of this statement's table?  Quiet tables keep their fast paths
        (egress, point lookup, pushdown): those read the current image,
        which IS the snapshot state when nothing committed past the pin."""
        if not self._snap_ts:
            return False
        t = getattr(stmt, "table", None)
        if t is None or getattr(stmt, "joins", None):
            return True     # multi-table: stage per-table versioned batches
        dbname = t.database or self.current_db
        store = self.db.stores.get(f"{dbname}.{t.name}")
        if store is None:
            return False    # view / info-schema / unstaged: nothing to pin
        with trace.span("mvcc.dirty_check", table=f"{dbname}.{t.name}",
                        live_rows=store.num_rows):
            return store.mvcc_needs_versioned(self._snap_ts)

    def _select_impl(self, stmt: SelectStmt, cache_key=None) -> Result:
        """Plan cache (reference: state_machine.cpp:1984): one logical plan
        per SQL text, one compiled executable per (table versions, shapes)."""
        from ..expr.ast import AggCall

        if stmt.into_outfile is not None:
            return self._select_into_outfile(stmt, cache_key)
        with trace.span("select.route"):
            answer, stmt, cache_key = self._route_fast_paths(stmt, cache_key)
        if answer is not None:
            return answer

        def _has_gc(e):
            if e is None:
                return False
            if isinstance(e, AggCall) and e.op == "group_concat":
                return True
            return any(_has_gc(a) for a in getattr(e, "args", ()))

        if any(_has_gc(it.expr) for it in stmt.items) or _has_gc(stmt.having) \
                or any(_has_gc(o.expr) for o in stmt.order_by):
            return self._select_group_concat(stmt)
        with trace.span("select.route"):
            stmt_run, lookup_key, norm = self._route_paramize(stmt, cache_key)
        if norm is None:
            return self._select_cached(stmt, cache_key, cache_key, None)
        from ..expr.compile import ExprError
        from ..expr.params import ParamError
        self._param_counted = False
        try:
            return self._select_cached(stmt_run, cache_key, lookup_key, norm)
        except (paramize.BindError, ExprError, ParamError, PlanError):
            # conservative valve: anything the parameterized path cannot
            # express replans with baked literals (a genuine user error
            # re-raises identically from the baked run)
            self._plan_cache.pop(lookup_key, None)
            # hold the one-count-per-SELECT invariant: the baked re-run
            # only counts if the param attempt died before its counter
            self._qlog_outcome = "fallback"   # query_log: WHY it was slow
            try:
                res = self._select_cached(stmt, cache_key, cache_key, None,
                                          count=not self._param_counted)
            finally:
                self._qlog_outcome = None
            # counted only when the baked run SUCCEEDED: a genuine user
            # error (unknown column, bad subquery) re-raised above and is
            # not a param-machinery fallback — the metric stays an alarm
            # for the parameterized path itself
            metrics.plan_cache_param_fallbacks.add(1)
            return res
        finally:
            self._where_sel_hint = None

    def _route_fast_paths(self, stmt: SelectStmt, cache_key) -> tuple:
        """The paths that answer a SELECT without the plan cache (pushdown,
        egress, point lookup), then the matview / rollup rewrite.
        -> (Result | None, the statement to plan, its cache key)."""
        pushed = self._try_pushdown(stmt)
        if pushed is not None:
            return pushed, stmt, cache_key
        from . import egress as egress_mod
        # pinned snapshot over a table with version churn: egress streaming
        # and rowstore point lookups read the physically-latest image
        # directly — route them through the versioned batch staging.  A
        # quiet table's live image IS the snapshot state, so its fast
        # paths stay engaged (bit-identical by construction).
        snap_dirty = self._snap_dirty(stmt)
        eg = None if snap_dirty else egress_mod.extract(stmt, self)
        if eg is not None:
            return self._select_egress(eg, cache_key), stmt, cache_key
        point = None if snap_dirty else self._try_point_lookup(stmt)
        if point is not None:
            return point, stmt, cache_key
        rewritten = self._try_matview(stmt)
        if rewritten is not None:
            # answered from incrementally maintained view state: re-enter
            # with the hidden-table statement (cdc/views.py)
            stmt = rewritten
            cache_key = None if cache_key is None else \
                (cache_key[0] + " /*mv*/", cache_key[1])
        else:
            rewritten = self._try_rollup(stmt)
            if rewritten is not None:
                # re-enter with the rollup statement; versions in the cache
                # key come from the rollup store, which refresh just bumped
                stmt = rewritten
                cache_key = None if cache_key is None else \
                    (cache_key[0] + " /*rollup*/", cache_key[1])
        return None, stmt, cache_key

    def _route_paramize(self, stmt: SelectStmt, cache_key) -> tuple:
        """Auto-parameterization (plan/paramize.py): hoist WHERE literals
        into a runtime params vector and key the plan cache on the
        canonical statement structure — WHERE id = 42 and WHERE id = 43
        share one entry AND one compiled executable.  Mesh programs
        participate too: the executor's per-leaf in_specs replicate the
        params feed (P()) while batches shard P(AXIS), so one shard_map
        executable serves every literal variant — without this, the big
        MPP programs (fused multiway exchange) would fork per WHERE value.
        -> (the statement to run, the plan-cache key, norm | None)."""
        norm = None
        lookup_key = cache_key
        stmt_run = stmt
        if cache_key is not None and bool(FLAGS.param_queries):
            try:
                with trace.span("plan.paramize"):
                    n = paramize.normalize(stmt, self._param_resolver(stmt))
            except Exception:   # noqa: BLE001 — normalization is an
                #                 optimization; a bug must not fail the query
                metrics.count_swallowed("session.paramize")
                n = None
            if n is not None and n.slots:
                norm = n
                lookup_key = ("//params", self.current_db, n.key)
                stmt_run = n.stmt
                metrics.params_hoisted.add(len(n.slots))
                if self.mesh is not None and stmt.group_by:
                    # selectivity-aware parameterized plans (scoped to the
                    # adaptive-agg decision): the bound values' combined
                    # WHERE selectivity joins the cache key as a coarse
                    # CLASS, so a highly selective literal replans (and can
                    # flip local->raw) while same-regime literals share one
                    # plan + executable.  Class 0 / no-basis keep the
                    # unsuffixed key, and only GROUP BY statements key at
                    # all (the class exists to flip the keyed-agg
                    # local/raw decision; forking scalar-agg executables
                    # per class would repay nothing) — the common case
                    # pays nothing.
                    from ..index.stats import selectivity_class
                    from ..parallel import agg as _agg  # noqa: F401 —
                    #   defines the adaptive_agg_* flags

                    wsel = self._where_selectivity(stmt) \
                        if bool(FLAGS.adaptive_agg_selectivity) else None
                    self._where_sel_hint = wsel
                    cls = selectivity_class(wsel)
                    if cls > 0:
                        lookup_key = lookup_key + (f"selcls{cls}",)
        return stmt_run, lookup_key, norm

    def _select_cached(self, stmt: SelectStmt, text_key, lookup_key,
                       norm, count: bool = True) -> Result:
        qp = progress.current()
        qp.beat(phase="plan")
        with trace.span("plan.cache") as sp:
            entry, qlog_outcome = self._plan_entry(stmt, text_key,
                                                   lookup_key, norm, count)
            sp.set(outcome=qlog_outcome)
        return self._run_entry(entry, qlog_outcome, text_key, lookup_key,
                               norm)

    def _plan_entry(self, stmt: SelectStmt, text_key, lookup_key, norm,
                    count: bool) -> tuple:
        """The plan-cache lookup, its staleness check and, on a miss or a
        stale entry, the (re)plan.  -> (entry, the outcome query_log
        shows)."""
        entry = self._plan_cache.get(lookup_key) if lookup_key else None
        replanned = False
        mesh = self.mesh
        mesh_n = int(mesh.devices.size) if mesh is not None else 0
        if entry is not None and entry.get("mesh_n", 0) != mesh_n:
            # SET GLOBAL mesh_devices since this entry was planned: a plan
            # distributed for another mesh (or none) cannot run on this one
            entry = None
        if entry is not None:
            self._plan_cache.move_to_end(lookup_key)
            # stats-derived plan choices (dense group-by domains, key shifts)
            # go stale when data changes: replan on any version bump
            stale = any(self.db.stores.get(tk) is None or
                        self.db.stores[tk].plan_version != v
                        for tk, v in entry["versions"].items())
            # view redefinitions (possibly by ANOTHER session) change plans
            # without touching any table store version
            if entry.get("view_gen") != self.db.catalog.view_gen:
                entry = None
            elif stale:
                # version gates the PLAN only, the capacity bucket gates the
                # executable: replan (stats may have moved), and when the
                # fresh plan is structurally identical keep the old entry —
                # its settled join caps AND its compiled executables, which
                # stay valid because bucketed shapes survive the DML.  Only
                # a genuinely different plan drops the executables.
                plan = self._plan_select(stmt)
                sig = plan_signature(plan)
                if sig != entry.get("plan_sig"):
                    entry["plan"] = plan
                    entry["plan_sig"] = sig
                    entry["compiled"] = {}
                    entry.pop("exchange_summary", None)  # re-count: the
                    # fresh plan may shuffle differently
                    # the plan AND every executable were just rebuilt: in
                    # cost terms this is a miss, and the hit/miss split is
                    # how recompile churn shows on dashboards
                    replanned = True
        hit = entry is not None and not replanned
        hit_text = entry.get("text") if entry is not None else None
        if entry is None:
            plan = self._plan_select(stmt)
            entry = {"plan": plan, "plan_sig": plan_signature(plan),
                     "compiled": {}, "versions": {}, "mesh_n": mesh_n,
                     "view_gen": self.db.catalog.view_gen,
                     "text": text_key[0] if text_key else None}
            cap = int(FLAGS.plan_cache_size)
            if lookup_key and cap > 0:
                self._plan_cache[lookup_key] = entry
                while len(self._plan_cache) > cap:
                    self._plan_cache.popitem(last=False)
        # accounting invariant (tests/test_param_cache.py): each SELECT
        # counts exactly one of {hit, param_hit, miss} — counted AFTER the
        # fallible planning so a param-path fallback can re-count iff this
        # attempt never did.  A hit that still re-traces downstream
        # (capacity-bucket crossing) is a plan-level HIT — the trace shows
        # in xla_retraces/compile_ms, never as a plan-cache miss
        if hit and norm is not None and text_key is not None \
                and hit_text != text_key[0]:
            outcome = "param_hit"
        elif hit:
            outcome = "hit"
        else:
            outcome = "miss"
        if count:
            if outcome == "param_hit":
                metrics.plan_cache_param_hits.add(1)
            elif outcome == "hit":
                metrics.plan_cache_hits.add(1)
            else:
                metrics.plan_cache_misses.add(1)
            self._param_counted = True
        # the query_log row reports the param-machinery fallback, not the
        # baked re-run's own hit/miss — that's the "why was it slow" signal
        return entry, getattr(self, "_qlog_outcome", None) or outcome

    def _run_entry(self, entry: dict, qlog_outcome: str, text_key,
                   lookup_key, norm) -> Result:
        """Stage, run and egress a plan-cache entry; log the statement."""
        qp = progress.current()
        plan = entry["plan"]
        # forensic-dump reference + progress denominators (host plan walk,
        # cached on the entry): SHOW PROCESSLIST renders "batch m/n" /
        # "round m/n" against these before the first scan even stages
        totals = entry.get("progress_totals")
        if totals is None:
            totals = entry["progress_totals"] = \
                executor.progress_totals(plan)
        qp.beat(phase="exec.batches", plan=plan,
                batches_total=totals["scans"],
                rounds_total=totals["rounds"] if self.mesh is not None
                else 0)
        # host-side access paths (index gather, zonemap/partition pruning)
        # see this execution's literal values even though the compiled plan
        # does not: _access_path_batch substitutes them into pushed filters
        self._param_subst = {s.index: s for s in norm.slots} \
            if norm is not None else None
        try:
            with trace.span("exec.batches"):
                batches, shape_key, full_scan = self._collect_batches(plan)
        finally:
            self._param_subst = None
        # what the plan was built on: a pk-addressed write (a patch of the
        # image) leaves it standing unless it widened a statistic
        entry["versions"] = {
            p[0]: (self.db.stores[p[0]].plan_version
                   if p[1] >= 0 and p[0] in self.db.stores else p[1])
            for p in shape_key}
        if norm is not None:
            from ..expr.params import PARAMS_KEY
            with trace.span("plan.bind"):
                batches[PARAMS_KEY] = paramize.bind(norm.slots, batches)
        t0 = time.perf_counter()
        qp.beat(phase="exec.run")
        result = self._maybe_batched_run(entry, batches, shape_key, norm,
                                         lookup_key, full_scan)
        qp.beat(phase="egress.arrow")
        with trace.span("egress.arrow"):
            table = result.to_arrow()
        dur_ms = (time.perf_counter() - t0) * 1e3
        # close the egress wall-clock bucket so the query_log row carries
        # every phase (the beats ride the same seams as the trace spans —
        # SHOW PROFILE over the trace shows the same splits)
        qp.beat(phase="finish", rows_done=table.num_rows)
        if text_key is not None:
            # slow-query rows explain WHY: plan-cache outcome + the
            # capacity buckets the scan batches compiled against
            buckets = ";".join(f"{p[0]}={p[2]}"
                               for p in sorted(shape_key))
            # dur_ms (the duration_ms column) runs from after staging;
            # the dict's ``query`` is the statement's whole wall time
            self.db.query_log.append((text_key[0], dur_ms, table.num_rows,
                                      qlog_outcome, buckets, qp.logged_ms(),
                                      self._snap_ts))
        return Result(columns=list(table.column_names), arrow=table)

    def _param_resolver(self, stmt: SelectStmt):
        """(table_label, column) -> (table_key, LType) against the live
        catalog, for paramize's string-literal binder analysis.  Only plain
        base tables resolve; derived tables/views/ambiguous names return
        None, pinning their comparands."""
        tables: dict = {}
        for r in [stmt.table] + [j.table for j in stmt.joins]:
            if r is None or r.subquery is not None:
                continue
            db = r.database or self.current_db
            try:
                info = self.db.catalog.get_table(db, r.name)
            except (ValueError, KeyError):      # view/unknown name: pin
                continue
            tables[r.label] = (f"{db}.{r.name}", info.schema)

        def resolve(tlabel, col):
            cname = col.split(".")[-1]
            if tlabel is not None:
                ent = tables.get(tlabel)
                if ent is not None and cname in ent[1]:
                    return (ent[0], ent[1].field(cname).ltype)
                return None
            hits = [(tk, sch.field(cname).ltype)
                    for tk, sch in tables.values() if cname in sch]
            return hits[0] if len(hits) == 1 else None
        return resolve

    def _explain_analyze(self, stmt: SelectStmt) -> Result:
        """EXPLAIN ANALYZE: run the query once, report per-operator live-row
        counts + compile/run wall time (reference: EXPLAIN FORMAT='analyze'
        over the TraceNode tree, trace_state.h).

        One timing truth: every measurement records as spans/events in the
        query's trace (forced — EXPLAIN ANALYZE always traces, sampler or
        no), and the ``--`` telemetry lines below render FROM those span
        records.  SHOW PROFILE over the same trace shows the same numbers;
        there is no second timing path."""
        with trace.root("explain_analyze", force=True):
            m = trace.mark()
            with self._snapshot_pinned(stmt):
                self._explain_analyze_measure(stmt)
            spans = trace.since(m)
        lines = self._render_analyze(spans)
        txt = "\n".join(lines)
        return Result(columns=["plan"], plan_text=txt,
                      arrow=pa.table({"plan": lines}))

    def _explain_analyze_measure(self, stmt: SelectStmt) -> None:
        """Run + instrument; all output lands in the active trace."""
        # materialized-view answering applies here exactly as in _select
        # (the zero-duration `view` span renders the `-- view:` line)
        rw = self._try_matview(stmt)
        if rw is not None:
            stmt = rw
        cand = self._pushdown_candidate(stmt)
        if cand is not None:
            # pushed-fragment execution: the dispatcher's `fragments`
            # event (dispatched/local/retargeted/partial_rows/bytes_saved)
            # IS the measurement — render the store/frontend plan split
            # and skip the image-path instrumentation, which would measure
            # a plan that does not run
            pushed = self._try_pushdown(stmt)
            if pushed is not None:
                for line in self._render_pushdown(*cand).splitlines():
                    trace.event("op", label=line)
                return
            # dispatch fell back: measure the image path below
        plan = self._plan_select(stmt)
        batches, shape_key, full_scan = self._collect_batches(plan)
        # settle join caps first (the overflow-retry loop), so traced counts
        # describe the plan that actually runs, not a truncated first attempt
        entry = {"plan": plan, "compiled": {}, "versions": {}}
        self._run_plan(entry, batches, shape_key)
        streamed = streaming.stream_source(batches) is not None
        if streamed:
            # chunk-folded execution: there is no single jitted program to
            # re-run under the counting tracer (the scan input is a host
            # chunk iterator) — ops render uncounted; the measured fold
            # telemetry landed in the run's `stream` event instead
            by_node: dict = {}
        else:
            raw = compile_plan(plan, trace=True,
                               mesh=self.mesh if batches else None)
            fn = jax.jit(raw)
            with trace.span("exec.first"):
                with hot_path_guard():
                    out, flags, counts = fn(batches)
                jax.block_until_ready(jax.tree.leaves(counts))
            with trace.span("exec.steady"):
                with hot_path_guard():
                    out, flags, counts = fn(batches)
                jax.block_until_ready(jax.tree.leaves(counts))
            # materialize every per-node counter in one explicit transfer —
            # int(c) per operator is a device round-trip each
            # (tpulint HOSTSYNC)
            by_node = {id(n): int(c) for n, c in
                       zip(raw.trace_order, jax.device_get(counts))}

        def render(node: PlanNode, indent: int):
            rows = by_node.get(id(node))
            attrs = {} if rows is None else {"rows": rows}
            trace.event("op", label="  " * indent + node._label(), **attrs)
            for c in node.children:
                render(c, indent + 1)

        render(plan, 0)
        # capacity buckets + compile telemetry: which shapes this query
        # compiled against, and the engine-wide retrace/compile counters
        # (steady state = xla_retraces stops moving between identical runs)
        scans = [(p[0], p[2], batches[p[0]]) for p in sorted(shape_key)
                 if isinstance(batches.get(p[0]), ColumnBatch)]
        # one fused transfer for all live counts (not an int() per table)
        lives = jax.device_get([b.live_count() for _, _, b in scans])
        for (tk, cap, _b), live in zip(scans, lives):
            # only full-table scans carry pow2 capacity buckets; an index/
            # ANN access-path batch's shape is just its candidate count
            # (and DOES retrace per version) — label it honestly
            kind = "capacity" if tk in full_scan else "gathered"
            trace.event("batch", table=tk, kind=kind, capacity=int(cap),
                        live=int(live))
        cstats = metrics.compile_ms.stats()
        trace.event("xla", retraces_total=metrics.xla_retraces.value,
                    compiles=cstats["count"],
                    compile_avg_ms=cstats["avg_ms"])
        # AOT persistent executable cache: whether this node can serve the
        # plan without compiling after a restart, and the engine-wide
        # hit/miss/fallback state of the tier
        dstats = metrics.aot_cache_deser_ms.stats()
        trace.event("aot", enabled=int(compilecache.AOT.enabled()),
                    hits_total=metrics.aot_cache_hits.value,
                    misses_total=metrics.aot_cache_misses.value,
                    fallbacks_total=metrics.aot_cache_fallbacks.value,
                    publishes_total=metrics.aot_cache_publishes.value,
                    deser_avg_ms=dstats["avg_ms"])
        # device-resource accounting for THIS plan's executable (same rows
        # as information_schema.executables): what the program costs the
        # accelerator, not just how long the host waited.  A streamed
        # statement has no such executable (its fold step and finalize are
        # jitted outside the plan's entry; the `stream` event has what it
        # ran): asking would find the newest OTHER one
        if compilecache.EXECUTABLES.enabled() and not streamed:
            dev = compilecache.EXECUTABLES.find(
                plan_sig=entry.get("plan_sig"))
            if dev is not None:
                trace.event("device", compile_ms=dev["last_compile_ms"],
                            flops=dev["flops"],
                            bytes_accessed=dev["bytes_accessed"],
                            peak_hbm_bytes=dev["peak_hbm_bytes"],
                            source=dev["mem_source"])
        # literal auto-parameterization: how many literals the normalizer
        # hoists into runtime params vs pins into the cache key for this
        # statement (plan/paramize.py; pinned = shape/trace-time feeders)
        try:
            nz = paramize.normalize(stmt, self._param_resolver(stmt)) \
                if bool(FLAGS.param_queries) else None
        except Exception:   # noqa: BLE001 — display stays best-effort
            metrics.count_swallowed("session.explain_paramize")
            nz = None
        hoisted = nz.hoisted if nz is not None else 0
        pinned = nz.pinned if nz is not None else paramize._count_lits(stmt)
        trace.event("params", hoisted=hoisted, pinned=pinned,
                    param_hits_total=metrics.plan_cache_param_hits.value)
        gs = guard_stats()
        trace.event("guards", mode=gs["mode"],
                    transfer_trips=gs["transfer_trips"],
                    lock_trips=gs["lock_trips"],
                    owner_trips=gs["owner_trips"])
        # cross-query batched dispatch: whether this statement's shape is
        # served by the combiner under concurrency, plus engine-wide tick
        # telemetry (EXPLAIN ANALYZE itself always runs inline)
        from . import dispatch as _dispatch
        occ = metrics.group_occupancy.stats()
        trace.event("dispatch", enabled=_dispatch.enabled(),
                    groups_total=metrics.batched_groups.value,
                    avg_occupancy=occ["avg_ms"],
                    queue_p50_ms=metrics.queue_wait_ms.stats()["p50_ms"])
        # MPP exchange v2: shuffle rounds this plan pays, join chains fused
        # into a multiway exchange, and the adaptive-agg strategy decision
        # (local pre-reduce vs raw-row shuffle) per AggNode
        mj = [0]
        aggs: list[str] = []
        seen_x: set = set()

        def walk_x(n):
            if id(n) in seen_x:
                return
            seen_x.add(id(n))
            if isinstance(n, MultiJoinNode):
                mj[0] += 1
            if isinstance(n, AggNode) and getattr(n, "agg_dist", ""):
                aggs.append(n.agg_dist)
            for c in n.children:
                walk_x(c)

        walk_x(plan)
        xsum = (exchange_summary(plan) if self.mesh is not None
                else {"rounds": 0, "reused": 0, "collectives": 0,
                      "keys": []})
        trace.event("exchange",
                    rounds=xsum["rounds"], reused=xsum["reused"],
                    collectives=xsum["collectives"],
                    keys="[" + ",".join(xsum["keys"]) + "]",
                    multiway=mj[0], agg=",".join(aggs) or "-",
                    retries_total=metrics.shuffle_overflow_retries.value,
                    saved_total=metrics.shuffle_rounds_saved.value)

    @staticmethod
    def _render_analyze(spans: list[dict]) -> list[str]:
        """EXPLAIN ANALYZE display, rendered exclusively from the span
        records (the same ones SHOW PROFILE / trace_spans read)."""
        def find(name):
            return [s for s in spans if s["name"] == name]

        lines: list[str] = []
        for s in find("op"):
            a = s["attrs"]
            suffix = f"  rows={a['rows']}" if "rows" in a else ""
            lines.append(a["label"] + suffix)
        first = find("exec.first")
        steady = find("exec.steady")
        if first and steady:
            lines.append(f"-- run: {steady[-1]['dur_ms']:.2f} ms "
                         f"(first incl. compile: "
                         f"{first[-1]['dur_ms']:.2f} ms)")
        for s in find("batch"):
            a = s["attrs"]
            lines.append(f"-- batch: {a['table']} {a['kind']}="
                         f"{a['capacity']} live={a['live']}")
        for s in find("view"):
            a = s["attrs"]
            lines.append(f"-- view: {a['view']} "
                         f"applied_ts={a['applied_ts']} "
                         f"staleness_ms={a['staleness_ms']} "
                         f"deltas_folded={a['deltas_folded']} "
                         f"groups={a['groups']}")
        snaps = find("snapshot")
        if snaps:
            # one line per query: the pinned ts is shared; versions sum
            a0 = snaps[0]["attrs"]
            vs = sum(s["attrs"].get("versions_scanned", 0) for s in snaps)
            lines.append(f"-- snapshot: ts={a0['ts']} "
                         f"versions_scanned={vs} "
                         f"gc_watermark={a0['gc_watermark']}")
        for s in find("xla"):
            a = s["attrs"]
            lines.append(f"-- xla: retraces_total={a['retraces_total']} "
                         f"compiles={a['compiles']} "
                         f"compile_avg_ms={a['compile_avg_ms']}")
        for s in find("aot"):
            a = s["attrs"]
            lines.append(f"-- aot: enabled={a['enabled']} "
                         f"hits_total={a['hits_total']} "
                         f"misses_total={a['misses_total']} "
                         f"fallbacks_total={a['fallbacks_total']} "
                         f"publishes_total={a['publishes_total']} "
                         f"deser_avg_ms={a['deser_avg_ms']}")
        for s in find("device"):
            a = s["attrs"]
            lines.append(f"-- device: compile_ms={a['compile_ms']} "
                         f"flops={a['flops']:.0f} "
                         f"bytes={a['bytes_accessed']:.0f} "
                         f"peak_hbm={a['peak_hbm_bytes']:.0f} "
                         f"mem_source={a['source']}")
        for s in find("params"):
            a = s["attrs"]
            lines.append(f"-- params: hoisted={a['hoisted']} "
                         f"pinned={a['pinned']} "
                         f"param_hits_total={a['param_hits_total']}")
        for s in find("guards"):
            a = s["attrs"]
            lines.append(f"-- guards: mode={a['mode']} "
                         f"transfer_trips={a['transfer_trips']} "
                         f"lock_trips={a['lock_trips']} "
                         f"owner_trips={a.get('owner_trips', 0)}")
        for s in find("dispatch"):
            a = s["attrs"]
            lines.append(f"-- dispatch: enabled={int(a['enabled'])} "
                         f"groups_total={a['groups_total']} "
                         f"avg_occupancy={a['avg_occupancy']} "
                         f"queue_p50_ms={a['queue_p50_ms']}")
        for s in find("exchange"):
            a = s["attrs"]
            lines.append(f"-- exchange: rounds={a['rounds']} "
                         f"reused={a.get('reused', 0)} "
                         f"collectives={a.get('collectives', 0)} "
                         f"keys={a.get('keys', '[]')} "
                         f"multiway={a['multiway']} agg={a['agg']} "
                         f"shuffle_retries_total={a['retries_total']}")
        for s in find("stream"):
            a = s["attrs"]
            lines.append(f"-- stream: chunks={a['chunks']}/"
                         f"{a['chunks_total']} skipped={a['skipped']} "
                         f"bytes_h2d={a['bytes_h2d']} "
                         f"prefetch_wait_ms={a['prefetch_wait_ms']} "
                         f"stage_ms={a['stage_ms']} "
                         f"restarts={a['restarts']}")
        for s in find("fragments"):
            a = s["attrs"]
            lines.append(f"-- fragments: dispatched={a['dispatched']} "
                         f"local={a['local']} "
                         f"retargeted={a['retargeted']} "
                         f"partial_rows={a['partial_rows']} "
                         f"bytes_saved={a['bytes_saved']}")
        lines.append(f"-- trace: spans={len(spans)} "
                     "(SHOW PROFILE shows the same span records)")
        return lines

    def _snapshot_batch(self, table_key: str, store) -> \
            Optional[ColumnBatch]:
        """Versioned device batch at the pinned ``self._snap_ts``: the
        live image concatenated with the history versions alive at the
        snapshot, with the MVCC visibility predicate
        (storage/mvcc.visibility_mask) ANDed into the batch's sel mask —
        the versioned read stays INSIDE the jitted plan as a sel-mask, no
        host-side row filtering.  None when the resident image already
        equals the snapshot (quiet table): the caller reuses the cached
        unversioned batch, so the pin is free AND bit-identical there."""
        import jax.numpy as jnp

        from ..column.batch import bucket_capacity, pad_batch
        from ..storage.mvcc import visibility_mask

        snap = self._snap_ts
        with trace.span("mvcc.visibility", table=table_key, ts=snap) as sp:
            sv = store.snapshot_versions(snap)
            wm = self.db.mvcc.snapshots.watermark(
                self.db.mvcc.tso.last_ts())
            if sv is None:
                sp.set(versions_scanned=0)
                trace.event("snapshot", ts=snap, table=table_key,
                            versions_scanned=0, gc_watermark=wm)
                return None
            tbl, cts, dts, nver = sv
            sp.set(versions_scanned=nver)
            b = ColumnBatch.from_arrow(tbl)
            mask = visibility_mask(jnp.asarray(cts), jnp.asarray(dts),
                                   jnp.int64(snap))
            b = b.and_sel(mask)
            if bool(FLAGS.batch_bucketing):
                b = pad_batch(b, bucket_capacity(
                    len(b), int(FLAGS.batch_bucket_min)))
            trace.event("snapshot", ts=snap, table=table_key,
                        versions_scanned=nver, gc_watermark=wm)
            return b

    def _collect_batches(self, plan: PlanNode):
        from ..plan.nodes import ScanNode

        batches: dict[str, ColumnBatch] = {}
        key_parts = []
        scan_count: dict[str, int] = {}
        # tables whose batch IS the store's full device image (not an
        # index-gathered subset): the only inputs host presort permutations
        # may apply to.  Tracked explicitly — with capacity bucketing the
        # padded batch length no longer equals store.num_rows, so the old
        # length comparison can't identify a full scan
        full_scan: set = set()

        def count(n: PlanNode):
            if isinstance(n, ScanNode):
                scan_count[n.table_key] = scan_count.get(n.table_key, 0) + 1
            for c in n.children:
                count(c)
        count(plan)

        # progress beats per scan staged (host-side, batch boundary — also
        # a cancellation point, so KILL lands between table loads)
        qp = progress.current()
        nscanned = [0, 0]                       # batches staged, rows seen
        qp.beat(batches_total=len(scan_count))

        def scan_beat(table_key: str, b) -> None:
            nscanned[0] += 1
            nscanned[1] += len(b)
            qp.beat(operator=f"scan {table_key}", batches_done=nscanned[0],
                    rows_done=nscanned[1])

        def walk_plan(n: PlanNode):
            if isinstance(n, ScanNode) and n.table_key not in batches:
                db, name = n.table_key.split(".", 1)
                if db == "information_schema":
                    b = ColumnBatch.from_arrow(self._info_schema_table(name))
                    if self.mesh is not None:
                        from ..parallel.mesh import shard_batch
                        b = shard_batch(b, self.mesh)
                    batches[n.table_key] = b
                    key_parts.append((n.table_key, -1, len(b)))
                    scan_beat(n.table_key, b)
                    for c in n.children:
                        walk_plan(c)
                    return
                store = self.db.stores.get(n.table_key)
                if store is None:
                    info = self.db.catalog.get_table(db, name)
                    store = self.db.stores[n.table_key] = self.db.make_store(info)
                b = None
                snapped = False
                # pinned snapshot: a table with version churn past the pin
                # stages the versioned image (replacing index-gathered
                # subsets and streamed chunk sources, which read the
                # physically-latest image); a QUIET table declines here
                # (b stays None) and keeps every fast path below — its
                # live image is the snapshot state, bit-identical
                if self._snap_ts and self.mesh is None:
                    b = self._snapshot_batch(n.table_key, store)
                    snapped = b is not None
                if b is None and \
                        self.mesh is None and scan_count[n.table_key] == 1:
                    if n.ann is not None:
                        b = self._ann_batch(n, store)
                    if b is None:
                        with trace.span("access.path",
                                        table=n.table_key) as sp:
                            b = self._access_path_batch(n, db, name, store)
                            if b is not None:
                                sp.set(rows=len(b), access=n.access_desc)
                if b is None:
                    if self.mesh is not None:
                        b = self.db.sharded_batch(n.table_key, store,
                                                  self.mesh)
                    else:
                        # out-of-core: an eligible scan->filter->aggregate
                        # plan over a big-enough table stages a ChunkSource
                        # (chunk ids post zone-map pruning) instead of the
                        # whole table; _run_plan folds it chunk by chunk.
                        # NOT a full_scan member: presort permutations and
                        # the batched dispatcher need resident positions
                        with trace.span("stream.source",
                                        table=n.table_key) as sp:
                            b = self._maybe_stream_source(plan, n, store)
                            if b is not None:
                                sp.set(chunks_kept=len(b.keep),
                                       chunks=b.chunks.n_chunks)
                        if b is None:
                            with trace.span("stage.resident",
                                            table=n.table_key) as sp:
                                b = store.device_table_batch()
                                sp.set(rows=len(b))
                            full_scan.add(n.table_key)
                batches[n.table_key] = b
                # snapped batches append a constant marker, NOT the ts:
                # executables are shape-keyed, and two pins at different
                # timestamps with the same shapes must share one compile
                key_parts.append(
                    (n.table_key, store.version,
                     len(batches[n.table_key])) if not snapped else
                    (n.table_key, store.version,
                     len(batches[n.table_key]), "snap"))
                scan_beat(n.table_key, b)
            for c in n.children:
                walk_plan(c)

        walk_plan(plan)

        captured = {p[0]: p[1] for p in key_parts}

        def walk_presort(n: PlanNode):
            spec = getattr(n, "presort", None)
            if spec is not None and self.mesh is None:
                n.presort_input = None
                kind, table_key, cols = spec
                store = self.db.stores.get(table_key)
                base = batches.get(table_key)
                # only when the scan input IS the full base table (an
                # index-gathered or sharded batch has different positions)
                # AND the store still sits at the version the batch was
                # captured at — a permutation computed over newer data
                # applied to an older batch would be silently unsorted
                if store is not None and base is not None and \
                        table_key in full_scan and \
                        store.version == captured.get(table_key):
                    pkey = f"__presort__{kind}|{table_key}|{','.join(cols)}"
                    if pkey not in batches:
                        import jax.numpy as jnp
                        fn = store.sort_permutation if kind == "join" \
                            else store.agg_sort_permutation
                        perm = fn(tuple(cols))
                        if store.version != captured.get(table_key):
                            perm = None     # raced a write mid-build
                        if perm is not None:
                            batches[pkey] = jnp.asarray(perm)
                    if pkey in batches:
                        n.presort_input = pkey
            for c in n.children:
                walk_presort(c)
        walk_presort(plan)
        return batches, tuple(sorted(key_parts)), full_scan

    def _access_path_batch(self, n, db: str, name: str, store):
        """IndexSelector-driven scan input (index/selector.py): a secondary
        equality gathers just the matching rows; a primary-key range
        gathers its rows out of the resident image into one capacity
        bucket; zone maps drop whole regions.  Returns None for a full
        scan (the default batch).  The device program's own filter still
        runs — these are conservative row supersets, so correctness never
        depends on the index choice."""
        from ..index.selector import (analyze_conjuncts, choose_access,
                                      pk_range_capacity, pk_range_desc)

        if n.pushed_filter is None:
            return None
        pf = n.pushed_filter
        subst = getattr(self, "_param_subst", None)
        if subst:
            # parameterized plan: the filter carries Param markers; the
            # access-path analysis is host-side and per-execution, so it
            # gets this execution's literal values substituted back in
            pf = paramize.substitute_params(pf, subst)
        try:
            info = self.db.catalog.get_table(db, name)
            pred = analyze_conjuncts(pf)
            access = choose_access(info, store, pred, db=self.db)
        except Exception:
            return None
        cache = getattr(self, "_access_batches", None)
        if cache is None:
            cache = self._access_batches = {}
        if access[0] == "global":
            from ..index.globalindex import backing_table_name
            _, ix_name, col, value = access
            n.access_desc = f"global_index({ix_name}:{col})"
            ck = (n.table_key, store.version, "gidx", ix_name, col, value)
            b = cache.get(ck)
            if b is None:
                bkey = f"{db}.{backing_table_name(name, ix_name)}"
                bstore = self.db.stores[bkey]
                # index-region scan -> pk values -> main-table lookup join
                # (select_manager_node.cpp:1081)
                entries = bstore.secondary_scan(col, value)
                b = ColumnBatch.from_arrow(store.lookup_by_pks(entries))
                self._evict_access(n.table_key, store.version)
                cache[ck] = b
            metrics.index_scans.add(1)
            return b
        if access[0] == "secondary":
            _, ix_name, col, value = access
            n.access_desc = f"index({ix_name}:{col})"
            ck = (n.table_key, store.version, "sec", col, value)
            b = cache.get(ck)
            if b is None:
                b = ColumnBatch.from_arrow(store.secondary_scan(col, value))
                self._evict_access(n.table_key, store.version)
                cache[ck] = b
            metrics.index_scans.add(1)
            return b
        if access[0] == "pk_range":
            from ..column.batch import gather_padded
            _, col, lo, hi = access
            # positions and image of one version; the batch is NOT a
            # full_scan member (positions differ per literal) and has no
            # entry in the cache above: the gather is one small program,
            # cheaper than the cache's eviction walk and its pinned arrays
            pos, image = store.pk_range_scan(lo, hi)
            n.access_desc = pk_range_desc(col, len(pos), store.num_rows)
            metrics.pk_range_scans.add(1)
            metrics.pk_range_rows.add(len(pos))
            # every literal's rows land in one capacity bucket with the
            # image's own dictionaries, so the plan compiles once a bucket
            return gather_padded(image, pos, pk_range_capacity(len(pos)))
        if access[0] == "partition":
            _, parts, ptotal = access
            keep, rtotal = store.prune_parts(parts)
            if len(keep) == rtotal:
                n.access_desc = "full"
                return None         # tags unknown: nothing actually drops
            n.access_desc = (f"partition({ptotal - len(parts)}/{ptotal} "
                             f"partitions pruned)")
            ck = (n.table_key, store.version, "part", tuple(sorted(keep)))
            b = cache.get(ck)
            if b is None:
                b = ColumnBatch.from_arrow(store.regions_table(keep))
                self._evict_access(n.table_key, store.version)
                cache[ck] = b
            metrics.regions_pruned.add(rtotal - len(keep))
            return b
        if access[0] == "zonemap":
            keep, total = store.prune_regions(access[1])
            if len(keep) == total:
                n.access_desc = "full"
                return None
            n.access_desc = f"zonemap({total - len(keep)}/{total} " \
                            f"regions pruned)"
            ck = (n.table_key, store.version, "zone", tuple(keep))
            b = cache.get(ck)
            if b is None:
                b = ColumnBatch.from_arrow(store.regions_table(keep))
                self._evict_access(n.table_key, store.version)
                cache[ck] = b
            metrics.regions_pruned.add(total - len(keep))
            return b
        n.access_desc = "full"
        return None

    _ACCESS_CACHE_MAX = 16

    def _maybe_stream_source(self, plan, n, store):
        """A ChunkSource for this scan when the plan is chunk-foldable
        (exec/streaming.py) and the table clears the size gate; None keeps
        the resident path.  Host-side and per-execution, like the access
        paths — the chunk-level zone maps see this execution's literals."""
        from ..index.selector import analyze_conjuncts
        from ..storage.streamchunks import ChunkSource, chunk_set

        if not bool(FLAGS.streaming_scan) or self._sql_txn is not None:
            return None
        if store.num_rows < int(FLAGS.streaming_min_rows):
            return None
        hit = streaming.eligible(plan, n)
        if hit is None:
            return None
        try:
            cs = chunk_set(store, n.table_key, self.db.cold_fs())
        except Exception:       # noqa: BLE001 — staging is best-effort
            metrics.count_swallowed("session.stream_stage")
            return None
        ranges = {}
        if n.pushed_filter is not None:
            pf = n.pushed_filter
            subst = getattr(self, "_param_subst", None)
            if subst:
                pf = paramize.substitute_params(pf, subst)
            try:
                ranges = analyze_conjuncts(pf).ranges
            except Exception:   # noqa: BLE001 — prune is conservative
                metrics.count_swallowed("session.stream_prune")
                ranges = {}
        keep = cs.pruned(ranges)
        n.access_desc = (f"stream({len(keep)}/{cs.n_chunks} chunks, "
                         f"{cs.capacity} rows each)")
        if hit[1].strategy == "stream":
            # the chunk fold merges partials by group id: an aggregate
            # planned for resident rows in key order folds as the scatter
            # or the sort it would have been
            hit[1].unstream()
        return ChunkSource(cs, keep, streaming.staged_columns(n, cs.names))

    def _evict_access(self, table_key: str, version: int):
        """Drop access-path batches of older versions of this table, and
        cap the cache (distinct predicate literals each pin device arrays —
        unbounded growth would OOM a long-lived session)."""
        self._access_batches = {
            k: v for k, v in self._access_batches.items()
            if not (k[0] == table_key and k[1] != version)}
        while len(self._access_batches) >= self._ACCESS_CACHE_MAX:
            self._access_batches.pop(next(iter(self._access_batches)))

    def _annotate_access(self, plan: PlanNode):
        """EXPLAIN display: run IndexSelector per scan without building
        batches, so the shown choice flips with the predicates."""
        from ..index.annindex import ANN_NPROBE
        from ..index.selector import (analyze_conjuncts, choose_access,
                                      pk_range_desc)
        from ..plan.nodes import ScanNode

        def walk(n):
            if isinstance(n, ScanNode) and getattr(n, "ann", None):
                n.access_desc = f"ann({n.ann[0]} nprobe={ANN_NPROBE})"
                return
            if isinstance(n, ScanNode) and "." in n.table_key:
                db, name = n.table_key.split(".", 1)
                store = self.db.stores.get(n.table_key)
                if store is not None and db != "information_schema":
                    try:
                        info = self.db.catalog.get_table(db, name)
                        pred = analyze_conjuncts(n.pushed_filter)
                        access = choose_access(info, store, pred, db=self.db)
                        if access[0] == "secondary":
                            n.access_desc = f"index({access[1]}:{access[2]})"
                        elif access[0] == "global":
                            n.access_desc = \
                                f"global_index({access[1]}:{access[2]})"
                        elif access[0] == "pk_range":
                            n.access_desc = pk_range_desc(
                                access[1],
                                store.pk_range_count(access[2], access[3]),
                                store.num_rows)
                        elif access[0] == "partition":
                            n.access_desc = (
                                f"partition({access[2] - len(access[1])}"
                                f"/{access[2]} partitions pruned)")
                        elif access[0] == "zonemap":
                            keep, total = store.prune_regions(access[1])
                            n.access_desc = (
                                "full" if len(keep) == total else
                                f"zonemap({total - len(keep)}/{total} "
                                f"regions pruned)")
                        else:
                            n.access_desc = "full"
                    except Exception:
                        # EXPLAIN display stays best-effort; the real scan
                        # path reports its own errors
                        metrics.count_swallowed("session.annotate_access")
            for c in n.children:
                walk(c)
        walk(plan)

    def _info_schema_table(self, name: str) -> pa.Table:
        cat = self.db.catalog
        if name == "tables":
            rows = []
            for db in cat.databases():
                for t in cat.tables(db):
                    info = cat.get_table(db, t)
                    st = self.db.stores.get(f"{db}.{t}")
                    rows.append((db, t, st.num_rows if st else 0, info.version))
            return pa.table({
                "table_schema": [r[0] for r in rows],
                "table_name": [r[1] for r in rows],
                "table_rows": pa.array([r[2] for r in rows], pa.int64()),
                "version": pa.array([r[3] for r in rows], pa.int64()),
            }) if rows else _empty_info("tables")
        if name == "columns":
            rows = []
            for db in cat.databases():
                for t in cat.tables(db):
                    info = cat.get_table(db, t)
                    for f in info.schema.fields:
                        rows.append((db, t, f.name, f.ltype.value,
                                     "YES" if f.nullable else "NO"))
            return pa.table({
                "table_schema": [r[0] for r in rows],
                "table_name": [r[1] for r in rows],
                "column_name": [r[2] for r in rows],
                "data_type": [r[3] for r in rows],
                "is_nullable": [r[4] for r in rows],
            }) if rows else _empty_info("columns")
        if name == "views":
            vsnap = cat._views        # one atomic snapshot: a concurrent
            #                           DROP VIEW swaps the attr, never
            #                           mutates this dict
            rows = [(k.split(".", 1)[0], k.split(".", 1)[1], v["sql"])
                    for k, v in sorted(vsnap.items())]
            return pa.table({
                "table_schema": [r[0] for r in rows],
                "table_name": [r[1] for r in rows],
                "view_definition": [r[2] for r in rows],
            }) if rows else _empty_info("views")
        if name == "subscriptions":
            rows = self.db.cdc.describe()
            return pa.table({
                "name": [r["name"] for r in rows],
                "table_key": [r["table_key"] for r in rows],
                "internal": ["YES" if r["internal"] else "NO"
                             for r in rows],
                "acked_ts": pa.array([r["acked_ts"] for r in rows],
                                     pa.int64()),
                "cursor_lag_ms": pa.array(
                    [r["cursor_lag_ms"] for r in rows], pa.int64()),
                "events_delivered": pa.array(
                    [r["events_delivered"] for r in rows], pa.int64()),
            }) if rows else _empty_info("subscriptions")
        if name == "materialized_views":
            rows = self.db.matviews.describe()
            return pa.table({
                "table_schema": [r["database"] for r in rows],
                "view_name": [r["name"] for r in rows],
                "base_table": [r["base_table"] for r in rows],
                "definition": [r["definition"] for r in rows],
                "applied_ts": pa.array([r["applied_ts"] for r in rows],
                                       pa.int64()),
                "staleness_ms": pa.array(
                    [r["staleness_ms"] for r in rows], pa.int64()),
                "cursor_lag_ms": pa.array(
                    [r["cursor_lag_ms"] for r in rows], pa.int64()),
                "deltas_folded": pa.array(
                    [r["deltas_folded"] for r in rows], pa.int64()),
                "rescans": pa.array([r["rescans"] for r in rows],
                                    pa.int64()),
                "answered_queries": pa.array(
                    [r["answered_queries"] for r in rows], pa.int64()),
                "groups": pa.array([r["groups"] for r in rows],
                                   pa.int64()),
            }) if rows else _empty_info("materialized_views")
        if name == "partitions":
            rows = []
            for db in cat.databases():
                if db == "information_schema":
                    continue
                for t in cat.tables(db):
                    info = cat.get_table(db, t)
                    spec = (info.options or {}).get("partition")
                    if not spec:
                        continue
                    st = self.db.stores.get(f"{db}.{t}")
                    counts: dict[int, int] = {}
                    # snapshot names/uppers/counts under the store lock:
                    # ALTER ... PARTITION pops those lists in place under
                    # the same lock, and an unlocked read between the two
                    # pops would mispair bounds with names
                    import contextlib

                    with (st._lock if st is not None
                          else contextlib.nullcontext()):
                        names = list(spec.get("names", ()))
                        uppers = list(spec.get("uppers", ()))
                        if st is not None:
                            for r in st.regions:
                                counts[r.part] = counts.get(r.part, 0) \
                                    + r.num_rows
                    if spec["kind"] == "hash":
                        for i in range(int(spec["n"])):
                            rows.append((db, t, f"p{i}", "HASH",
                                         spec["column"], "",
                                         counts.get(i, 0)))
                    else:
                        for i, (nm, up) in enumerate(zip(names, uppers)):
                            rows.append((db, t, nm, "RANGE",
                                         spec["column"],
                                         "MAXVALUE" if up is None
                                         else str(up), counts.get(i, 0)))
            return pa.table({
                "table_schema": [r[0] for r in rows],
                "table_name": [r[1] for r in rows],
                "partition_name": [r[2] for r in rows],
                "partition_method": [r[3] for r in rows],
                "partition_expression": [r[4] for r in rows],
                "partition_description": [r[5] for r in rows],
                "table_rows": pa.array([r[6] for r in rows], pa.int64()),
            }) if rows else _empty_info("partitions")
        if name == "cold_segments":
            rows = []
            for key, st in list(self.db.stores.items()):  # DDL-safe snap
                tier = st.replicated
                if tier is None or not hasattr(tier, "cold_rows"):
                    continue
                db, _, tname = key.partition(".")
                if hasattr(tier, "groups"):
                    # aligned (meta, group) pairs under the tier lock: a
                    # concurrent split inserts into both lists
                    with tier._mu:
                        sources = [(m.region_id, g)
                                   for m, g in zip(tier.metas, tier.groups)]
                else:
                    sources = [(r.region_id, r)
                               for r in list(tier.regions)]
                for rid, src in sources:
                    try:       # a leaderless/unreachable region skips, it
                        #        must not fail the whole listing
                        if hasattr(tier, "groups"):
                            manifest = src.bus.nodes[
                                src.leader()].cold_manifest
                        else:
                            manifest = tier._region_manifest(src)
                    except Exception:
                        metrics.count_swallowed("session.cold_manifest")
                        continue
                    for seq, f, w in manifest:
                        rows.append((db, tname, rid, seq, f, w))
            return pa.table({
                "table_schema": [r[0] for r in rows],
                "table_name": [r[1] for r in rows],
                "region_id": pa.array([r[2] for r in rows], pa.int64()),
                "seq": pa.array([r[3] for r in rows], pa.int64()),
                "file": [r[4] for r in rows],
                "watermark": pa.array([r[5] for r in rows], pa.int64()),
            }) if rows else _empty_info("cold_segments")
        if name == "query_log":
            log = list(self.db.query_log)

            def ph(e, key):
                # per-phase wall-clock split (progress beats ride the same
                # seams as the trace spans — one timing truth with SHOW
                # PROFILE); pre-upgrade 5-tuples read as 0
                d = e[5] if len(e) > 5 else {}
                return round(float(d.get(key, 0.0)), 3)
            return pa.table({
                "query": [e[0] for e in log],
                "duration_ms": pa.array([e[1] for e in log], pa.float64()),
                "result_rows": pa.array([e[2] for e in log], pa.int64()),
                # why a slow row was slow: plan-cache outcome
                # (hit/param_hit/miss/fallback) + the capacity buckets the
                # scan batches compiled against
                "cache": [e[3] for e in log],
                "capacity_bucket": [e[4] for e in log],
                "parse_ms": pa.array([ph(e, "parse") for e in log],
                                     pa.float64()),
                "plan_ms": pa.array([ph(e, "plan") for e in log],
                                    pa.float64()),
                "exec_ms": pa.array([ph(e, "exec") for e in log],
                                    pa.float64()),
                "egress_ms": pa.array([ph(e, "egress") for e in log],
                                      pa.float64()),
                # MVCC read timestamp the query ran at (0 = unpinned);
                # pre-MVCC 6-tuples read as 0
                "snapshot_ts": pa.array(
                    [int(e[6]) if len(e) > 6 else 0 for e in log],
                    pa.int64()),
            }) if log else _empty_info("query_log")
        if name == "snapshots":
            rows = self.db.mvcc.snapshots.describe()
            return pa.table({
                "snapshot_ts": pa.array([r["snapshot_ts"] for r in rows],
                                        pa.int64()),
                "age_ms": pa.array([r["age_ms"] for r in rows],
                                   pa.int64()),
                "query": [r["query"] for r in rows],
                "holder": [r["holder"] for r in rows],
            }) if rows else _empty_info("snapshots")
        if name == "processlist":
            rows = [qp.row() for qp in PROGRESS.live(self.db)]
            rows.sort(key=lambda r: r["query_id"])
            return pa.table({
                "id": pa.array([r["id"] for r in rows], pa.int64()),
                "user": [r["user"] for r in rows],
                "host": [r["host"] for r in rows],
                "db": [r["db"] for r in rows],
                "command": [r["command"] for r in rows],
                "time_s": pa.array([r["time_s"] for r in rows], pa.int64()),
                "state": [r["state"] for r in rows],
                "info": [r["info"] for r in rows],
                "query_id": pa.array([r["query_id"] for r in rows],
                                     pa.int64()),
                "phase": [r["phase"] for r in rows],
                "operator": [r["operator"] for r in rows],
                "batches_done": pa.array([r["batches_done"] for r in rows],
                                         pa.int64()),
                "batches_total": pa.array([r["batches_total"] for r in rows],
                                          pa.int64()),
                "rows_done": pa.array([r["rows_done"] for r in rows],
                                      pa.int64()),
                "rows_est": pa.array([r["rows_est"] for r in rows],
                                     pa.int64()),
                "round": pa.array([r["round"] for r in rows], pa.int64()),
                "rounds_total": pa.array([r["rounds_total"] for r in rows],
                                         pa.int64()),
                "chunk_no": pa.array([r["chunk_no"] for r in rows],
                                     pa.int64()),
                "chunks_total": pa.array([r["chunks_total"] for r in rows],
                                         pa.int64()),
                "queue_wait_ms": pa.array([r["queue_wait_ms"] for r in rows],
                                          pa.float64()),
                "elapsed_ms": pa.array([r["elapsed_ms"] for r in rows],
                                       pa.float64()),
            }) if rows else _empty_info("processlist")
        if name == "flight_recorder":
            import json as _json
            rows = self.db.flightrec.rows()
            return pa.table({
                "rec_id": pa.array([r["rec_id"] for r in rows], pa.int64()),
                "ts": pa.array([r["ts"] for r in rows], pa.float64()),
                "query_id": pa.array([r.get("query_id", 0) for r in rows],
                                     pa.int64()),
                "conn_id": pa.array([r.get("conn_id", 0) for r in rows],
                                    pa.int64()),
                "user": [r.get("user", "") for r in rows],
                "db": [r.get("db", "") for r in rows],
                "query": [r.get("text", "") for r in rows],
                "duration_ms": pa.array([r.get("dur_ms", 0.0) for r in rows],
                                        pa.float64()),
                "status": [r.get("status", "") for r in rows],
                "error": [r.get("error", "") for r in rows],
                "phase_ms": [_json.dumps(r.get("phase_ms") or {},
                                         default=str) for r in rows],
                "rows": pa.array([r.get("rows", 0) for r in rows],
                                 pa.int64()),
                "has_bundle": pa.array([bool(r.get("bundle"))
                                        for r in rows], pa.bool_()),
            }) if rows else _empty_info("flight_recorder")
        if name == "trace_spans":
            import json as _json
            rows = []
            for rec in TRACER.list():
                for sp in rec["spans"]:
                    rows.append((rec["query_id"], rec["trace_id"],
                                 sp["span_id"], sp["parent_id"], sp["name"],
                                 sp.get("node") or "frontend",
                                 float(sp["ts_us"]), float(sp["dur_ms"]),
                                 _json.dumps(sp["attrs"], default=str)
                                 if sp["attrs"] else ""))
            return pa.table({
                "query_id": pa.array([r[0] for r in rows], pa.int64()),
                "trace_id": [r[1] for r in rows],
                "span_id": [r[2] for r in rows],
                "parent_id": [r[3] for r in rows],
                "name": [r[4] for r in rows],
                "node": [r[5] for r in rows],
                "start_us": pa.array([r[6] for r in rows], pa.float64()),
                "duration_ms": pa.array([r[7] for r in rows], pa.float64()),
                "attrs": [r[8] for r in rows],
            }) if rows else _empty_info("trace_spans")
        if name == "dispatcher":
            # live state of the cross-query batched dispatcher: queue
            # depth + in-flight, tick latency, the exact group-occupancy
            # histogram, and per-bucket qos token levels
            rows = []
            dp = getattr(self.db, "dispatcher", None)
            if dp is not None:
                snap = dp.snapshot()
                rows += [("queue", "depth", float(snap["queue_depth"]), ""),
                         ("queue", "live_groups",
                          float(snap["live_groups"]), ""),
                         ("queue", "inflight", float(snap["inflight"]), ""),
                         ("executables", "cached",
                          float(snap["compiled"]), "")]
                for size in sorted(snap["occupancy"]):
                    rows.append(("occupancy", str(size),
                                 float(snap["occupancy"][size]),
                                 "groups combined at this size"))
            tick = metrics.dispatch_tick_ms.stats()
            wait = metrics.queue_wait_ms.stats()
            rows += [("tick", k, float(tick[k]), "") for k in
                     ("count", "avg_ms", "p50_ms", "p99_ms", "max_ms")]
            rows += [("queue_wait", k, float(wait[k]), "") for k in
                     ("count", "avg_ms", "p50_ms", "p99_ms")]
            for c in ("batched_groups", "dispatch_inline",
                      "dispatch_fallbacks", "qos_rejections"):
                rows.append(("counter", c,
                             float(metrics.REGISTRY.counter(c).value), ""))
            if self.db.qos is not None:
                for kind, key, tokens, detail in self.db.qos.state():
                    rows.append((kind, key, float(tokens), detail))
            return pa.table({
                "kind": [r[0] for r in rows],
                "name": [r[1] for r in rows],
                "value": pa.array([r[2] for r in rows], pa.float64()),
                "detail": [r[3] for r in rows],
            }) if rows else _empty_info("dispatcher")
        if name == "column_stats":
            rows = []
            for db in cat.databases():
                if db == "information_schema":
                    continue
                for t in cat.tables(db):
                    st = self.db.stores.get(f"{db}.{t}")
                    if st is None:
                        continue
                    info = cat.get_table(db, t)
                    for f in info.schema.fields:
                        try:
                            s = st.column_stats(f.name) or {}
                        except Exception:   # noqa: BLE001 — stats advisory
                            metrics.count_swallowed("session.column_stats")
                            continue
                        rows.append((db, t, f.name, int(s.get("ndv") or 0),
                                     s.get("ndv_method") or "",
                                     int(s.get("nulls") or 0),
                                     int(s.get("n") or 0),
                                     len(s.get("mcv") or ()),
                                     max(0, len(s.get("hist") or ()) - 1)))
            return pa.table({
                "table_schema": [r[0] for r in rows],
                "table_name": [r[1] for r in rows],
                "column_name": [r[2] for r in rows],
                "ndv": pa.array([r[3] for r in rows], pa.int64()),
                "ndv_method": [r[4] for r in rows],
                "nulls": pa.array([r[5] for r in rows], pa.int64()),
                "row_count": pa.array([r[6] for r in rows], pa.int64()),
                "mcv_count": pa.array([r[7] for r in rows], pa.int64()),
                "hist_buckets": pa.array([r[8] for r in rows], pa.int64()),
            }) if rows else _empty_info("column_stats")
        if name == "fragments":
            from .fragments import recent_dispatches
            recs = recent_dispatches()
            return pa.table({
                "frag_key": [r["frag_key"] for r in recs],
                "table_name": [r["table"] for r in recs],
                "mode": [r["mode"] for r in recs],
                "dispatched": pa.array([r["dispatched"] for r in recs],
                                       pa.int64()),
                "local": pa.array([r["local"] for r in recs], pa.int64()),
                "retargeted": pa.array([r["retargeted"] for r in recs],
                                       pa.int64()),
                "partial_rows": pa.array([r["partial_rows"] for r in recs],
                                         pa.int64()),
                "scanned": pa.array([r["scanned"] for r in recs],
                                    pa.int64()),
                "bytes_saved": pa.array([r["bytes_saved"] for r in recs],
                                        pa.int64()),
                "status": [r["status"] for r in recs],
            }) if recs else _empty_info("fragments")
        if name == "failpoints":
            from ..chaos import failpoint as _fp
            rows = _fp.describe()
            return pa.table({
                "name": [r[0] for r in rows],
                "spec": [r[2] for r in rows],
                "hits": pa.array([r[3] for r in rows], pa.int64()),
                "trips": pa.array([r[4] for r in rows], pa.int64()),
                "site": [r[1] for r in rows],
            }) if rows else _empty_info("failpoints")
        if name == "metrics":
            rows = [(mname, k, float(v))
                    for mname, st in metrics.REGISTRY.expose().items()
                    for k, v in st.items() if v is not None]
            return pa.table({
                "name": [r[0] for r in rows],
                "field": [r[1] for r in rows],
                "value": pa.array([r[2] for r in rows], pa.float64()),
            }) if rows else _empty_info("metrics")
        if name == "cluster_metrics":
            # the fleet telemetry plane: this frontend's registry plus
            # every registered daemon's last rpc_metrics snapshot, merged
            # under daemon='fleet' (counters sum, histograms bucket-wise);
            # a daemon whose scrape failed keeps its last rows, stale=1
            rows = self.db.telemetry.cluster_rows()
            return pa.table({
                "daemon": [r[0] for r in rows],
                "metric": [r[1] for r in rows],
                "labels": [r[2] for r in rows],
                "field": [r[3] for r in rows],
                "value": pa.array([r[4] for r in rows], pa.float64()),
                "stale": pa.array([int(r[5]) for r in rows], pa.int64()),
                "age_ms": pa.array([round(float(r[6]), 3) for r in rows],
                                   pa.float64()),
            }) if rows else _empty_info("cluster_metrics")
        if name == "executables":
            # device-resource accounting: what each cached executable costs
            # the accelerator (cost/memory analysis fills lazily here)
            ex = compilecache.EXECUTABLES.rows()
            return pa.table({
                "statement": [r["statement"] for r in ex],
                "kind": [r["kind"] for r in ex],
                "plan_sig": [r["plan_sig"] for r in ex],
                "shape": [r["shape"] for r in ex],
                "compiles": pa.array([r["compiles"] for r in ex],
                                     pa.int64()),
                "compile_ms_total": pa.array(
                    [r["compile_ms_total"] for r in ex], pa.float64()),
                "last_compile_ms": pa.array(
                    [r["last_compile_ms"] for r in ex], pa.float64()),
                "flops": pa.array([r["flops"] for r in ex], pa.float64()),
                "bytes_accessed": pa.array(
                    [r["bytes_accessed"] for r in ex], pa.float64()),
                "peak_hbm_bytes": pa.array(
                    [r["peak_hbm_bytes"] for r in ex], pa.float64()),
                "argument_bytes": pa.array(
                    [r["argument_bytes"] for r in ex], pa.float64()),
                "output_bytes": pa.array(
                    [r["output_bytes"] for r in ex], pa.float64()),
                "mem_source": [r["mem_source"] for r in ex],
            }) if ex else _empty_info("executables")
        if name == "aot_cache":
            # the persistent executable tier: what survives a restart
            # (disk artifacts) and what this process did with it
            # (hits / sources / deserialization cost)
            rows = compilecache.AOT.rows()
            return pa.table({
                "key": [r["key"] for r in rows],
                "kind": [r["kind"] for r in rows],
                "statement": [r["statement"] for r in rows],
                "plan_sig": [r["plan_sig"] for r in rows],
                "size_bytes": pa.array([r["size_bytes"] for r in rows],
                                       pa.int64()),
                "jax_version": [r["jax_version"] for r in rows],
                "created_at": [r["created_at"] for r in rows],
                "source": [r["source"] for r in rows],
                "hits": pa.array([r["hits"] for r in rows], pa.int64()),
                "deser_ms": pa.array([r["deser_ms"] for r in rows],
                                     pa.float64()),
                "status": [r["status"] for r in rows],
            }) if rows else _empty_info("aot_cache")
        if name == "flags":
            rows = FLAGS.describe()
            return pa.table({
                "name": [r[0] for r in rows],
                "value": [str(r[1]) for r in rows],
                "default_value": [str(r[2]) for r in rows],
                "help": [r[3] for r in rows],
            }) if rows else _empty_info("flags")
        if name == "regions":
            fleet = self.db.fleet
            if fleet is None:
                return _empty_info("regions")
            # table_id -> table name via the registered row tiers; regions
            # whose tier is gone (or was never materialized through a tier)
            # fall back to the numeric id
            names = {t.table_id: t.table_key
                     for t in fleet.row_tiers.values()}
            rms = sorted(fleet.meta.regions.values(),
                         key=lambda r: r.region_id)
            return pa.table({
                "region_id": pa.array([r.region_id for r in rms],
                                      pa.int64()),
                "table_name": [names.get(r.table_id, str(r.table_id))
                               for r in rms],
                "start_key": [r.start_key for r in rms],
                "end_key": [r.end_key for r in rms],
                "peers": [",".join(r.peers) for r in rms],
                "learners": [",".join(r.learners) for r in rms],
                "leader": [r.leader for r in rms],
                "state": [r.state for r in rms],
                "version": pa.array([r.version for r in rms], pa.int64()),
                "num_rows": pa.array([r.num_rows for r in rms], pa.int64()),
                "apply_lag": pa.array([r.apply_lag for r in rms],
                                      pa.int64()),
                "proposal_queue": pa.array([r.proposal_queue for r in rms],
                                           pa.int64()),
                "write_rate": pa.array([r.write_rate for r in rms],
                                       pa.int64()),
            }) if rms else _empty_info("regions")
        if name == "ddl_work":
            ws = list(self.db.ddl.works.values())
            return pa.table({
                "work_id": [w.work_id for w in ws],
                "table_name": [w.table_key for w in ws],
                "index_name": [w.index_name for w in ws],
                "kind": [w.kind for w in ws],
                "state": [w.state for w in ws],
                "regions_done": [w.regions_done for w in ws],
                "regions_total": [w.regions_total for w in ws],
                "error": [w.error for w in ws],
            }) if ws else _empty_info("ddl_work")
        raise PlanError(f"unknown information_schema table {name!r}")

    def _maybe_batched_run(self, entry: dict, batches: dict, shape_key,
                           norm, lookup_key, full_scan) -> ColumnBatch:
        """Route through the cross-query batched dispatcher when this query
        is groupable; otherwise (and for every bypass/fallback) run the
        session's own inline ``_run_plan``."""
        from . import dispatch

        def inline():
            return self._run_plan(entry, batches, shape_key)

        if norm is None or self.mesh is not None \
                or self._sql_txn is not None or not dispatch.enabled():
            return inline()
        # groupability: every scan input must be the table's full device
        # image at a real version — index/ANN-gathered batches are
        # literal-dependent (two members' same-shaped inputs would hold
        # DIFFERENT rows), information_schema (version -1) renders fresh
        # per call, and host presort permutations are per-plan-object state
        for tk, v, *_rest in shape_key:
            if v < 0 or tk not in full_scan:
                return inline()
        if any(k.startswith("__presort__") for k in batches) \
                or _dictionary_keys(batches):
            return inline()
        # members coalesce on (statement structure + pinned values, scan
        # shapes at exact versions, plan signature): they differ only in
        # their bound param feeds.  The compile key drops versions so DML
        # inside one capacity bucket reuses the batched executable, but
        # keeps the plan signature — a stats-driven replan must compile
        # its own batched variant, never execute a structurally different
        # stored plan.
        group_key = (lookup_key, shape_key, entry["plan_sig"])
        ck_base = (lookup_key, entry["plan_sig"],
                   tuple((p[0],) + tuple(p[2:]) for p in shape_key),
                   int(FLAGS.radix_join_buckets),
                   int(FLAGS.radix_join_min_build))
        try:
            return self.db.dispatcher.run(inline, group_key, ck_base,
                                          entry, batches)
        except dispatch.CombineFallback:    # belt: never escapes normally
            metrics.dispatch_fallbacks.add(1)
            return inline()

    def _run_plan(self, entry: dict, batches: dict, shape_key) -> ColumnBatch:
        plan = entry["plan"]
        if streaming.stream_source(batches) is not None:
            # out-of-core path: the scan staged a ChunkSource, so this
            # execution is a chunk fold driven from the host
            # (exec/streaming.py), not one jitted program over resident
            # batches — none of the executable caching below applies
            out = streaming.run_streamed(self, entry, batches,
                                         progress.current())
            with trace.span("egress.compact"):
                return self._egress_compact(out)
        # the order keys of growing string dictionaries ride the batches
        # as data (column/dictionary.py); their capacities join the shape
        keys = _dictionary_keys(batches)
        if keys:
            batches = dict(batches)
            batches[KEYS_KEY] = keys
            shape_key = tuple(shape_key) + tuple(
                (token, -2, int(a.shape[0])) for token, a in keys.items())
        # a plan with no scans has no sharded state (distribute leaves it
        # fully replicated) — run it as a plain single-device program
        mesh = self.mesh if batches else None
        # executables key on per-table (table_key, capacity bucket) — NOT
        # the store version: a version bump whose row count stays inside the
        # capacity bucket reuses the executable outright (version gates plan
        # staleness in _select; shape gates compilation here).  Trace-time
        # execution flags join the key: flipping SET GLOBAL
        # radix_join_buckets must re-trace, not silently reuse an executable
        # compiled under the other strategy
        versions_key = tuple((p[0], p[1]) for p in shape_key)
        # snapped batches keep their "snap" marker in the compile key: the
        # versioned staging can change the batch's pytree structure vs the
        # cached resident image at the same capacity
        shape_key = (tuple((p[0],) + tuple(p[2:]) for p in shape_key),
                     int(FLAGS.radix_join_buckets),
                     int(FLAGS.radix_join_min_build))

        # AOT persistent tier (utils/compilecache.AOT): the artifact key
        # adds the input pytree skeleton (incl. dictionary content) + jax
        # version + topology to the shape key, so a hit is exactly "the
        # program this compile would produce".  Derived LAZILY — only on a
        # shape-cache miss or at publish time — so the steady-state hot
        # path never pays the fingerprint walk.
        aot_key = None

        # progress: planned shuffle rounds are the mesh query's round
        # denominator (cached on the entry — one plan walk per entry life);
        # the summary also feeds the flight-recorder bundle
        qp = progress.current()
        if mesh is not None:
            summary = entry.get("exchange_summary")
            if summary is None:
                summary = entry["exchange_summary"] = exchange_summary(plan)
            qp.beat(rounds_total=int(summary["rounds"]), round_no=0,
                    exchange=summary)

        def get_aot_key():
            nonlocal aot_key
            # a growing dictionary's key is this process's own lineage:
            # no artifact holds it
            if aot_key is None and compilecache.AOT.enabled() and not keys:
                sig = entry.get("plan_sig")
                if sig is None:
                    sig = entry["plan_sig"] = plan_signature(plan)
                aot_key = compilecache.aot_key(
                    "plan", sig, shape_key,
                    compilecache.input_fingerprint(batches), mesh)
            return aot_key

        compiled_here = False
        for attempt in range(int(FLAGS.join_retry_max) + 1):
            # overflow-retry boundary: between device programs, no side
            # effects yet — a KILL lands here instead of paying another
            # trace+compile+run of the whole plan
            qp.checkpoint()
            pair = entry["compiled"].get(shape_key)
            if pair is not None and _bakes_outgrown(pair[1], batches):
                # the trace read a growing dictionary's values up to an n
                # this input has passed: trace it again
                entry["compiled"].pop(shape_key, None)
                pair = None
            if pair is not None and len(pair) == 3 \
                    and pair[2] != versions_key:
                # an AOT pair is pinned to the EXACT store versions it
                # loaded under: unlike jit (which keys on pytree aux and
                # silently retraces when a dictionary's content changes),
                # a deserialized program cannot notice that its baked
                # string dictionaries went stale.  Any DML — even inside
                # the capacity bucket — re-derives the artifact key; an
                # unchanged input skeleton re-hits the same artifact, a
                # changed dictionary is a clean miss
                entry["compiled"].pop(shape_key, None)
                pair = None
            if pair is None and compilecache.AOT.enabled() \
                    and shape_key not in entry.get("aot_bad", ()) \
                    and get_aot_key() is not None:
                art = compilecache.AOT.load(aot_key, mesh=mesh)
                if art is not None:
                    # no trace, no compile: the deserialized program runs
                    # with its settled caps baked in; the shim feeds the
                    # overflow loop below from the artifact's flag meta
                    pair = (art.run,
                            executor.AotRawShim(art.flag_meta, art.extra),
                            versions_key)
                    entry["compiled"][shape_key] = pair
            if pair is None:
                raw = compile_plan(plan, mesh=mesh)
                # not a per-iteration wrapper: built only on a shape-cache
                # miss and cached in entry["compiled"] keyed by shape_key.
                # The final compact stays EAGER (outside the jit): its
                # partition scatter is expensive to compile, and the eager
                # op cache pays that once per capacity shape process-wide
                # instead of once per cached executable
                # traced through jax.export when the AOT tier is on: what
                # this thread compiles is then what the publisher
                # serialises and a loader compiles (compilecache.
                # ExportedProgram) — one compile a settled executable
                pair = (compilecache.ExportedProgram(raw)
                        if compilecache.AOT.enabled()
                        else jax.jit(raw), raw)  # tpulint: disable=RETRACE
                comp = entry["compiled"]
                # distinct shapes (bucket crossings, access-path batches)
                # each pin an executable; without a cap one hot query would
                # pin every executable it ever compiled
                while len(comp) >= max(1, int(FLAGS.plan_cache_shapes)):
                    comp.pop(next(iter(comp)))
                comp[shape_key] = pair
            fn, raw = pair[0], pair[1]
            traces_before = raw.trace_count[0]
            t0 = time.perf_counter()
            # debug_guards: no implicit device->host transfer may hide in
            # the compiled path; the explicit flag egress happens below,
            # OUTSIDE the guard scope.  The span wraps the dispatch from
            # the HOST side — spans inside the traced fn would bake into
            # the program (tpulint SPANINJIT)
            # a retry's recompile (a cap grew: trace + compile of the whole
            # plan again) shows under its own name beside the first run's
            with trace.span("exec.cap_retry", attempt=attempt) \
                    if attempt else nullcontext():
                with trace.span("exec.run") as sp:
                    with hot_path_guard():
                        out, flags = fn(batches)
                    if raw.trace_count[0] > traces_before:
                        # this execution paid a trace+compile (first run /
                        # bucket crossing / overflow retry): record it so
                        # first-run vs steady-state shows up in SHOW metrics
                        # and the trace vs execute split shows in the span
                        cms = (time.perf_counter() - t0) * 1e3
                        metrics.compile_ms.observe(cms)
                        sp.set(compiled=True)
                        # a new executable is long-lived state: out of
                        # the collections of serving with what it made
                        gcpause.settle("compile")
                        compiled_here = True
                        # device-resource accounting (compile seam): the cost/
                        # memory analysis itself is LAZY — only the identity,
                        # wall-ms, and the arg shape skeleton record here
                        if compilecache.EXECUTABLES.enabled():
                            sig = entry.get("plan_sig")
                            if sig is None:
                                sig = entry["plan_sig"] = plan_signature(plan)
                            compilecache.EXECUTABLES.record_compile(
                                "plan", entry.get("text") or "<unnamed>", sig,
                                ";".join(f"{p[0]}={p[1]}"
                                         for p in shape_key[0]),
                                cms, fn, (batches,))
            # ONE explicit transfer for every overflow flag: int(flag) per
            # join would block on a device round-trip once per node
            # (tpulint HOSTSYNC)
            with trace.span("exec.flags"):
                # where the plan has flags (joins, shuffles, scalar
                # subqueries) the host blocks here until its program has
                # run; a flag-less plan returns at once and first waits in
                # egress.count
                host_flags = jax.device_get(flags)
            if mesh is not None:
                # the one device program carried every planned collective:
                # all rounds are behind us once the flags landed on host
                qp.beat(round_no=int(qp.rounds_total)
                        if qp.query_id else 0)
            needs = []
            for node, flag in zip(raw.join_order, host_flags):
                needed = int(flag)
                if isinstance(node, ScalarSourceNode) \
                        or getattr(node, "aot_scalar", False):
                    if needed > 1:
                        raise PlanError("Subquery returns more than 1 row")
                    needed = None
                elif mesh is not None and needed > (node.cap or 0) and (
                        isinstance(node, ExchangeNode)
                        or (isinstance(node, _CapBox)
                            and node.kind == "shuffle")):
                    # a skewed key blew past the per-destination shuffle
                    # capacity — the exchange backpressure analog, worth
                    # its own counter
                    metrics.shuffle_overflow_retries.add(1)
                needs.append(needed)
            # the capacities against the needs (exec/caps.py): what
            # overflowed grows to its need and what is downstream of it to
            # its input's bound, so one recompile settles the plan.  Host
            # work on values already fetched
            grew = False
            if needs:       # a plan without flags has nothing to settle
                with trace.span("exec.cap_settle"):
                    grew, slots, live = caps.settle(
                        None if isinstance(raw, executor.AotRawShim)
                        else plan, raw.join_order, needs)
                metrics.join_cap_slots.add(slots)
                metrics.join_live_rows.add(live)
            if grew:
                metrics.join_cap_retries.add(1)
                # the artifact under this key has the outgrown capacities
                # baked in (the key does not hold them): this shape
                # compiles from scratch, never re-loads it in this entry's
                # lifetime, and publishes over it once settled
                entry.setdefault("aot_bad", set()).add(shape_key)
            if grew and isinstance(raw, executor.AotRawShim):
                # live data outgrew a loaded artifact: an exported program
                # cannot re-trace
                metrics.aot_cache_fallbacks.add(1)
                entry["compiled"].pop(shape_key, None)
                continue
            if not grew:
                if compiled_here and getattr(fn, "exported", None) is not None \
                        and get_aot_key() is not None:
                    # settled executable: hand the module this thread
                    # traced and compiled to the background publisher
                    # (serialise + verify + disk + peer); the query path
                    # never waits on it
                    compilecache.AOT.publish_async(
                        aot_key, "plan",
                        str(entry.get("text") or "<unnamed>"),
                        entry.get("plan_sig"), fn, (out, flags),
                        executor.flag_meta_of(raw.join_order),
                        extra=executor.traced_extra(raw, mesh is not None),
                        mesh=mesh)
                executor.count_lowerings(raw)
                if mesh is not None:
                    metrics.mesh_programs.add(1)
                    metrics.exchange_bytes.add(raw.exchange_bytes[0])
                    self._mpp_telemetry(plan, entry, raw.join_order,
                                        host_flags)
                with trace.span("egress.compact"):
                    return self._egress_compact(out)
            entry["compiled"].pop(shape_key, None)  # caps changed: re-trace
        raise RuntimeError("join output cap still overflowing after retries")

    def _mpp_telemetry(self, plan, entry: dict, join_order,
                       host_flags) -> None:
        """Per-execution exchange observability for mesh plans: the
        shuffle_rounds counter plus mpp.repartition / mpp.join / mpp.agg
        spans with occupancy/overflow/strategy attrs.  Pure host work on
        the already-fetched flag values — no extra device sync."""
        summary = entry.get("exchange_summary")
        if summary is None:
            summary = entry["exchange_summary"] = exchange_summary(plan)
        metrics.shuffle_rounds.add(summary["rounds"])
        if summary["reused"]:
            # keyed exchange scheduler: collectives this execution did NOT
            # pay because an input was already partitioned on the key class
            metrics.shuffle_rounds_saved.add(summary["reused"])
        if not trace.active():
            # no trace tree: the counter above is the whole cost — no plan
            # walk, no per-node event churn on the hot path
            return
        for node, flag in zip(join_order, host_flags):
            needed = int(flag)
            if isinstance(node, ExchangeNode) and node.kind == "repartition":
                trace.event("mpp.repartition",
                            keys=",".join(node.keys or ()),
                            cap=int(node.cap or 0), occupancy=needed)
            elif isinstance(node, _CapBox) and node.kind == "shuffle":
                trace.event("mpp.repartition", site=node.site,
                            cap=int(node.cap or 0), occupancy=needed)
            elif isinstance(node, MultiJoinNode):
                trace.event("mpp.join", strategy="multiway",
                            builds=len(node.children) - 1, rows=needed,
                            cap=int(node.cap or 0))
            elif isinstance(node, JoinNode) and any(
                    isinstance(c, ExchangeNode) and c.kind == "repartition"
                    for c in node.children):
                trace.event("mpp.join", strategy="chained", rows=needed,
                            cap=int(node.cap or 0))

        seen: set = set()

        def walk(n):
            if id(n) in seen:
                return
            seen.add(id(n))
            if isinstance(n, AggNode) and getattr(n, "agg_dist", ""):
                trace.event("mpp.agg", strategy=n.agg_dist,
                            agg_kind=n.strategy)
            for c in n.children:
                walk(c)

        walk(plan)

    def _egress_compact(self, batch: ColumnBatch) -> ColumnBatch:
        """Densify the finished result for egress, O(live) not O(capacity).

        The generic compact permutes every lane of the batch — for a
        selective point read that is a full-capacity scatter+gather to
        surface a handful of rows, and it dominated steady-state latency.
        Egress is the sanctioned sync point, so fetch the (scalar) live
        count first and gather just the live indices into a pow2-padded
        batch: the eager nonzero/gather kernels cache per (capacity, cap)
        pair, and num_rows trims the padding at to_arrow time."""
        import jax.numpy as jnp

        if batch.sel is None or batch.live_prefix or len(batch) == 0:
            return compact(batch)
        if len(batch) <= EGRESS_HOST_LANES:
            # a result this small crosses whole, with its mask, in the one
            # transfer of ``to_arrow``, which keeps the live rows on the
            # host: no count to wait for, no device gather
            return batch
        sel = batch.sel_mask()
        cs = jnp.cumsum(sel.astype(jnp.int32))
        with trace.span("egress.count"):
            # egress: one scalar fetch.  The host's first wait for the chip
            # in a statement whose plan has no overflow flags: the plan's
            # program and the cumsum run, behind every other session's
            n = int(jax.device_get(cs[-1]))
        cap = min(len(batch), max(16, 1 << max(0, n - 1).bit_length()))
        # index of the k-th live row = first i with cumsum[i] >= k; a
        # vectorized binary search, not jnp.nonzero (whose CPU lowering is
        # an order of magnitude slower at this capacity)
        idx = jnp.searchsorted(cs, jnp.arange(1, cap + 1, dtype=jnp.int32))
        out = batch.gather(jnp.clip(idx, 0, len(batch) - 1))
        out.num_rows = jnp.asarray(n, jnp.int32)
        out.sel = jnp.arange(cap) < n
        return out
