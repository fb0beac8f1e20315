"""Columnar table storage: host Arrow tier + device cache, backed by the
MVCC row tier for durability and transactions.

Two tiers, mirroring the reference's hot/cold split (hot rows in RocksDB,
cold Parquet flushed by region_olap.cpp:445):

- **Cold / columnar**: a pyarrow Table per Region (persistable to Parquet)
  plus a lazily-built device ColumnBatch cache — what every query scans
  (the ParquetCache analog, include/column/parquet_cache.h:168).
- **Hot / row delta**: every SQL DML statement also writes the C++ MVCC row
  tier (storage/rowstore.py -> native/engine.cpp) keyed by an implicit
  ``__rowid``; with a WAL attached this makes committed DML durable — on
  restart the WAL deltas replay over the last Parquet checkpoint (the
  reference's recovery from applied_index + raft log, region.h:644).

Transactions take region *pre-image references* (Arrow tables are immutable,
so capture is O(1) — no data copy, unlike the round-1 whole-table snapshot)
plus pessimistic row locks and buffered row-tier writes via rowstore.Txn;
rollback restores the references and discards the buffer (reference:
src/engine/transaction.cpp:98-396).

Regions partition the row axis (the reference's key-range Region shards,
include/store/region.h:445); round 1 splits by fixed row-count ranges and the
parallel layer shards regions across mesh devices.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from ..column.batch import ColumnBatch
from ..meta.catalog import TableInfo
from ..types import Field, LType, Schema
from ..obs import trace
from ..utils import metrics
from .rowstore import ConflictError, KeyCodec, RowTable, Txn

DEFAULT_REGION_ROWS = 1 << 20  # split threshold on the row axis
# an INSERT of at most this many rows into a pk_patchable table is patched
# into the resident image; a larger one appends to the region and rebuilds
PATCH_INSERT_ROWS = 64
ROWID = "__rowid"              # hidden parquet column carrying row identity


def check_cold_readable(tier, fs, label: str) -> None:
    """A frontend that cannot read the cold tier must refuse the table:
    rebuilding from the (evicted) hot tier alone would silently lose rows.
    Shared by eager attach (exec/session.make_store) and the deferred
    materialization path."""
    if fs is None and tier.has_cold():
        raise ValueError(
            f"table {label!r} has cold segments but no cold storage "
            f"is configured (set cold_dir or the cold_fs_dir flag)")


def _zone_scalar(x, ltype):
    """Normalize a zone-map bound or predicate literal to one comparable
    number in the COLUMN's unit (DATE: epoch days; DATETIME/TIMESTAMP: epoch
    seconds; numerics: as-is).  None = unbounded/incomparable — pruning
    treats it as 'keep the region'."""
    import datetime
    if x is None:
        return None
    if isinstance(x, str):
        try:
            if ltype is LType.DATE:
                d = datetime.date.fromisoformat(x[:10])
                return (d - datetime.date(1970, 1, 1)).days
            if ltype.is_temporal:
                dt = datetime.datetime.fromisoformat(x)
                return dt.replace(tzinfo=datetime.timezone.utc).timestamp()
        except ValueError:
            return None
        return None
    if isinstance(x, datetime.datetime):
        return x.replace(tzinfo=datetime.timezone.utc).timestamp()
    if isinstance(x, datetime.date):
        if ltype.is_temporal and ltype is not LType.DATE:
            return datetime.datetime(x.year, x.month, x.day,
                                     tzinfo=datetime.timezone.utc).timestamp()
        return (x - datetime.date(1970, 1, 1)).days
    if isinstance(x, bool) or isinstance(x, (int, float)):
        if ltype is LType.DATE and isinstance(x, int):
            return x                       # already epoch days
        return x
    return None


def schema_to_arrow(schema: Schema) -> pa.Schema:
    m = {
        LType.BOOL: pa.bool_(), LType.INT8: pa.int8(), LType.INT16: pa.int16(),
        LType.INT32: pa.int32(), LType.INT64: pa.int64(),
        LType.UINT32: pa.uint32(), LType.UINT64: pa.uint64(),
        LType.FLOAT32: pa.float32(), LType.FLOAT64: pa.float64(),
        LType.DECIMAL: pa.float64(), LType.DATE: pa.date32(),
        LType.DATETIME: pa.timestamp("us"), LType.TIMESTAMP: pa.timestamp("us"),
        LType.STRING: pa.string(),
    }
    return pa.schema([pa.field(f.name, m[f.ltype], nullable=f.nullable)
                      for f in schema.fields])


class _Overlay:
    """Rows a region's pk-addressed writes changed since its Arrow table
    was last rebuilt: ``rows`` the live new versions (updated or
    inserted) by rowid, ``dead`` the rowids of the Arrow table's rows that
    are no longer live as stored (deleted or updated)."""

    __slots__ = ("rows", "dead")

    def __init__(self):
        self.rows: dict = {}
        self.dead: set = set()


class Region:
    """One row-range shard of a table (reference Region minus Raft, which
    arrives with the distributed store tier).

    A pk-addressed write (``TableStore.pk_update`` / ``pk_delete`` /
    ``insert_rows``) changes a region through its overlay and leaves the
    Arrow table as it is; reading ``data`` or ``rowids`` folds the overlay
    into a new table first, so every other reader sees one plain table."""

    def __init__(self, region_id: int, data: pa.Table,
                 rowids: Optional[np.ndarray] = None, version: int = 1,
                 part: int = -1):
        self.region_id = region_id
        self._data = data
        self._rowids = np.zeros(data.num_rows, np.int64) \
            if rowids is None else rowids
        self.version = version
        # table-partition id this region belongs to (reference: partitioned
        # tables place each partition's data in its own regions,
        # schema_factory.h:427-533); -1 = unpartitioned/unknown
        self.part = part
        self._ov: Optional[_Overlay] = None
        # set while a transaction's pk writes are open on the region: a read
        # of ``data`` merges without folding, so a rollback finds the Arrow
        # table it started from
        self.hold = False
        self._merge_cache = (None, None)
        self._rising = (None, False)    # (rowids array, does it rise?)
        self._device: Optional[ColumnBatch] = None
        self._device_version = -1

    @property
    def data(self) -> pa.Table:
        if self._ov is not None:
            if self.hold:
                return self._merged()[0]
            self._fold()
        return self._data

    @data.setter
    def data(self, t: pa.Table) -> None:
        if self._ov is not None:
            self._fold()
        self._data = t

    @property
    def rowids(self) -> np.ndarray:
        if self._ov is not None:
            if self.hold:
                return self._merged()[1]
            self._fold()
        return self._rowids

    @rowids.setter
    def rowids(self, r: np.ndarray) -> None:
        if self._ov is not None:
            self._fold()
        self._rowids = r

    @property
    def num_rows(self) -> int:
        ov = self._ov
        n = self._data.num_rows
        return n if ov is None else n - len(ov.dead) + len(ov.rows)

    # -- the overlay ------------------------------------------------------
    def _overlay(self) -> _Overlay:
        if self._ov is None:
            self._ov = _Overlay()
        return self._ov

    def _base_pos(self, rid: int) -> int:
        """Position of ``rid`` in the Arrow table, or -1: a binary search
        when its rowids rise (a bulk load, appends), else a scan."""
        base = self._rowids
        if self._rising[0] is not base:
            self._rising = (base, bool(len(base) < 2
                                       or (base[1:] > base[:-1]).all()))
        if self._rising[1]:
            i = int(np.searchsorted(base, rid))
            return i if i < len(base) and int(base[i]) == rid else -1
        found = np.nonzero(base == rid)[0]
        return int(found[0]) if found.size else -1

    def row(self, rid: int) -> Optional[dict]:
        """The live row ``rid`` as a dict, without a fold (None: absent)."""
        ov = self._ov
        if ov is not None:
            hit = ov.rows.get(rid)
            if hit is not None:
                return dict(hit)
            if rid in ov.dead:
                return None
        return self._base_row(rid)

    def put_row(self, rid: int, row: Optional[dict]) -> None:
        """Row ``rid`` becomes ``row`` (None: deleted) in the overlay; a
        row put back as the Arrow table stores it leaves the overlay."""
        ov = self._overlay()
        in_base = self._base_pos(rid) >= 0
        if row is None:
            ov.rows.pop(rid, None)
        elif in_base and row == self._base_row(rid):
            ov.rows.pop(rid, None)
            ov.dead.discard(rid)
            in_base = False
        else:
            ov.rows[rid] = row
        if in_base:
            ov.dead.add(rid)
        if not ov.rows and not ov.dead:
            self._ov = None

    def _base_row(self, rid: int) -> Optional[dict]:
        at = self._base_pos(rid)
        return None if at < 0 else self._data.slice(at, 1).to_pylist()[0]

    def _fold(self) -> None:
        """The Arrow table rebuilt with the overlay folded in."""
        self._data, self._rowids = self._merged()
        self._ov = None

    def _merged(self) -> tuple:
        """(a new Arrow table from the old one and the overlay: updated
        rows in place, deleted rows gone, inserted rows at the end in
        rowid order, its rowids)."""
        ov = self._ov
        if not ov.rows and not ov.dead:
            return self._data, self._rowids
        key = (self.version, id(self._data), id(ov))
        if self._merge_cache[0] == key:
            return self._merge_cache[1]
        metrics.overlay_folds.add(1)
        base, rids = self._data, self._rowids
        n = base.num_rows
        extra = list(ov.rows)
        at = {rid: n + j for j, rid in enumerate(extra)}
        keep = np.ones(n, bool)
        order = np.arange(n, dtype=np.int64)
        if ov.dead:
            dead = np.fromiter(ov.dead, np.int64, count=len(ov.dead))
            for p in np.nonzero(np.isin(rids, dead))[0]:
                j = at.pop(int(rids[p]), None)
                if j is None:
                    keep[p] = False
                else:
                    order[p] = j
        tail = sorted(at)       # inserted rows in rowid (= arrival) order
        order = np.concatenate([order[keep],
                                np.asarray([at[r] for r in tail], np.int64)])
        new_ids = np.concatenate([rids, np.asarray(extra, np.int64)])[order]
        ext = pa.Table.from_pylist([ov.rows[r] for r in extra],
                                   schema=base.schema)
        out = (pa.concat_tables([base, ext]).take(order).combine_chunks(),
               new_ids)
        self._merge_cache = (key, out)
        return out

    def device_batch(self) -> ColumnBatch:
        """Device-resident batch, rebuilt only when the region mutates."""
        if self._device is None or self._device_version != self.version:
            self._device = ColumnBatch.from_arrow(self.data)
            self._device_version = self.version
        return self._device


class TxnContext:
    """One table's open-transaction state: buffered row-tier writes with
    pessimistic locks (rowstore.Txn) + column-tier undo as region pre-image
    REFERENCES (Arrow immutability makes capture copy-free)."""

    def __init__(self, store: "TableStore"):
        self.store = store
        self.row_txn: Txn = store.row_table.begin()
        self._snap = None
        self._mvcc_pre = None
        # pk-addressed writes (TableStore.pk_update / pk_delete / the
        # patched insert): (rowid, row before) in order, undone in reverse;
        # ``_snap_at`` is how many there were when a bulk write took the
        # region capture below
        self.undo: list = []
        self._snap_at = 0

    def _capture(self):
        """Called by the store (under its lock) before the first mutation
        that rebuilds a region (references only: nothing a row)."""
        if self._snap is None:
            st = self.store
            self._snap = (list(st.regions),
                          [(r, r._data, r._rowids, r._ov, r.version)
                           for r in st.regions])
            self._snap_at = len(self.undo)
            self._capture_mvcc()

    def _capture_mvcc(self):
        # MVCC preimage rides the first capture: rollback must also unwind
        # PENDING stamps and this txn's history entries
        if self._mvcc_pre is None:
            self._mvcc_pre = self.store._mvcc.capture()

    def commit(self, commit_ts: int | None = None):
        """``commit_ts``: the decide-time MVCC stamp — multi-table commits
        (commit_group) pass ONE timestamp for the whole transaction; None
        allocates a fresh one for this table alone."""
        try:
            if self.store.replicated is not None:
                # SQL COMMIT on a replicated table: the buffered write set
                # becomes raft proposals (1PC single-region, primary-first
                # 2PC across regions — fetcher_store.cpp:1848-1904); the
                # local buffer only ever held the row LOCKS
                ops = self.row_txn.pending_ops()
                self.row_txn.rollback()
                try:
                    self.store.replicated.write_ops(ops)
                except Exception:
                    # quorum lost at COMMIT: the columnar cache already
                    # applied this txn's statements — restore the pre-image
                    # or SELECTs would show rows that never replicated
                    self._restore_preimage()
                    raise
            elif self.store.wal_path is not None:
                self.row_txn.commit()   # one atomic WAL batch + flush
            else:
                # non-durable store: the buffered rows would never be read —
                # just release the row locks
                self.row_txn.rollback()
            self._stamp_commit(commit_ts)
        finally:
            # release the writer lease even on a failed WAL write, or every
            # later statement on this table would conflict forever
            self.store._end_txn(self)

    def _stamp_commit(self, commit_ts: int | None = None):
        """Replace this txn's PENDING version stamps with the decide-time
        commit_ts — after the write is durable, before the lease releases
        (single-writer, so every PENDING stamp is ours)."""
        from ..utils.flags import FLAGS
        if not FLAGS.mvcc:
            return
        st = self.store
        with st._lock:
            if commit_ts is None:
                commit_ts = st._mvcc_ts()
            st._mvcc.restamp_pending(int(commit_ts))
            st._mvcc_maybe_gc(int(commit_ts))

    def _restore_preimage(self):
        st = self.store
        with st._lock:
            undo = self.undo
            if self._snap is not None:
                # the region capture supersedes every pk write after it
                undo = undo[:self._snap_at]
                regions, states = self._snap
                st.regions = list(regions)
                for r, data, rowids, ov, version in states:
                    r._data, r._rowids, r._ov = data, rowids, ov
                    # versions stay monotonic so stale device/stats caches
                    # can never alias a rolled-back state
                    r.version = max(r.version, version) + 1
                st._mutations += 1
                st._pk_stale = True
            for rid, row in reversed(undo):
                st._pk_restore(rid, row)
            self.undo = []
            if self._mvcc_pre is not None:
                st._mvcc.restore(self._mvcc_pre)

    def rollback(self):
        self.row_txn.rollback()
        self._restore_preimage()
        self.store._end_txn(self)


def commit_group(tctxs: list["TxnContext"]) -> None:
    """Commit several tables' buffered writes as ONE transaction.

    Replicated stores commit through a single primary-first 2PC spanning
    every touched region group of every table (the reference's global-index
    DML: LockPrimaryNode/LockSecondaryNode span main + index regions,
    separate.cpp:653); either all tables' writes replicate or none do, and
    every column cache rolls back to its pre-image on failure.  Non-
    replicated stores fall back to their per-table commit (WAL flush)."""
    from ..utils.flags import FLAGS
    from .remote_tier import RemoteRowTier, write_ops_atomic_remote
    from .replicated import ReplicatedRowTier, write_ops_atomic

    # ONE decide-time commit timestamp for the whole transaction: every
    # table's versions become visible at the same instant, so a snapshot
    # either sees all of this transaction or none of it
    commit_ts = None
    if FLAGS.mvcc and tctxs:
        commit_ts = tctxs[0].store._mvcc_ts()
    fleet = [t for t in tctxs
             if isinstance(t.store.replicated, ReplicatedRowTier)]
    remote = [t for t in tctxs
              if isinstance(t.store.replicated, RemoteRowTier)]
    others = [t for t in tctxs if t not in fleet and t not in remote]
    groups = [(fleet, write_ops_atomic), (remote, write_ops_atomic_remote)]
    for g_i, (group, atomic) in enumerate(groups):
        if len(group) <= 1:
            others.extend(group)    # nothing to span: per-table commit
            continue
        try:
            pairs = []
            for t in group:
                pairs.append((t.store.replicated, t.row_txn.pending_ops()))
                t.row_txn.rollback()  # buffer only ever held the row locks
            try:
                if atomic is write_ops_atomic:
                    # the fleet 2PC persists the commit_ts in the decision
                    # record's log entry (raft/twopc.py)
                    atomic(pairs, commit_ts=commit_ts or 0)
                else:
                    atomic(pairs)
            except Exception:
                for t in group:
                    t._restore_preimage()
                raise
        except BaseException:
            # a failed group must not strand the REMAINING contexts with
            # their writer leases held and uncommitted column mutations
            # visible: roll everything not yet committed back
            for t in group:
                t.store._end_txn(t)
            for later_group, _ in groups[g_i + 1:]:
                if len(later_group) > 1:
                    for t in later_group:
                        t.rollback()
                else:
                    others.extend(later_group)
            for t in others:
                t.rollback()
            raise
        else:
            for t in group:
                t._stamp_commit(commit_ts)
                t.store._end_txn(t)
    for t in others:
        t.commit(commit_ts=commit_ts)


class _LaneImage:
    """The resident device image of a table and what maps it to rows.

    ``lane_rowid`` is the rowid each lane holds (-1: dead or free),
    ``free`` the first lane of the never-used tail, ``holes`` the dead
    lanes below it, which an insert takes before the tail, ``pending`` the lanes
    pk-addressed writes changed since the last patch (lane -> the row, or
    None: dead), and ``pk_vals`` / ``pk_lanes`` the primary key's values in
    key order with the lane of each (-1: the key's row is deleted)."""

    __slots__ = ("key", "batch", "lane_rowid", "free", "holes", "pending",
                 "pk_vals", "pk_lanes")

    def __init__(self, key, batch, lane_rowid, free):
        self.key, self.batch = key, batch
        self.lane_rowid, self.free = lane_rowid, free
        self.holes: list = []
        self.pending: dict = {}
        self.pk_vals = None
        self.pk_lanes = None


@jax.jit
def _image_patch(sel, idx, live, leaves):
    """Changed lanes scattered into every column of the image and its
    ``sel``; padded lanes point past the end and drop."""
    out = []
    for data, d, validity, v in leaves:
        nd = data.at[idx].set(d.astype(data.dtype), mode="drop")
        nv = None if validity is None else \
            validity.at[idx].set(v, mode="drop")
        out.append((nd, nv))
    return sel.at[idx].set(live, mode="drop"), out


class TableStore:
    """All regions of one table + DML on the host tier.

    Writes mutate the host Arrow data (the read-optimized copy every query
    scans) AND mirror into the row tier for WAL durability; the device cache
    refreshes lazily."""

    # rank 10 — acquired FIRST on the write path (see __init__ comment)
    RANK = 10

    def __init__(self, info: TableInfo, region_rows: int = DEFAULT_REGION_ROWS,
                 wal_path: str | None = None):
        self.info = info
        self.region_rows = region_rows
        self.arrow_schema = schema_to_arrow(info.schema)
        # guarded: rank 10 — acquired FIRST on the write path; _write_hot
        # (under this lock) takes the binlog retry lock (20) for the CDC
        # drain and the replicated tier's lock (30) via write_ops.  The
        # statically-derived order (tools/tpulint.py --lock-order),
        # asserted when debug_guards is on
        from ..analysis.runtime import GuardedLock
        self._lock = GuardedLock("store.table_lock", rank=self.RANK,
                                 reentrant=True)
        self._mutations = 0
        # pk-addressed writes (pk_update / pk_delete / the patched insert)
        # bump _patch_seq, not _mutations: the resident image and the pk key
        # order take them as patches; _plan_gen moves when one of them
        # widens a statistic a plan was built on
        self._patch_seq = 0
        self._plan_gen = 0
        self._img: Optional[_LaneImage] = None
        self._next_region = 1
        self._next_rowid = 1
        self._rowid_pool = 0          # meta-allocated range (replicated)
        self._rowid_pool_left = 0
        # deferred cluster attach (set by attach_replicated_lazy): the
        # remote tier's full-region pull happens on FIRST data touch, so a
        # frontend whose reads all push down never pays it
        self._attach_pending = None
        self._attaching = False
        self.regions: list[Region] = [Region(self._alloc_region_id(),
                                             self.arrow_schema.empty_table())]
        self.wal_path = None
        self.durable_dir: Optional[str] = None   # Parquet checkpoint home
        # raft-replicated hot tier (storage/replicated.py); when set, DML
        # replicates through region raft groups instead of the local WAL
        self.replicated = None
        # distributed binlog writer (storage/binlog_regions): autocommit
        # DML events join the data's cross-tier 2PC when set
        self.binlog_sink = None
        self._writer: Optional[TxnContext] = None
        # AUTO_INCREMENT high-water mark, lazily seeded from max(col)+1 (the
        # reference allocates ranges from meta's auto_incr_state_machine;
        # single-process: the store IS the allocator)
        self._auto_incr: Optional[int] = None
        # MVCC version bookkeeping (storage/mvcc.py): commit stamps +
        # dead-version history kept BESIDE the resident Arrow image, all
        # mutated under this table's lock.  The TSO client / snapshot
        # registry are engine-shared (attach_mvcc); a standalone store
        # lazily builds a process-local oracle on first stamp
        from .mvcc import MvccState
        self._mvcc = MvccState()
        self._tso = None
        self._snap_reg = None
        self._build_row_tier(None)
        # primary-key uniqueness index (lazy; bulk loads mark it stale)
        pk = info.primary_key() if hasattr(info, "primary_key") else None
        self._pk_cols = list(pk.columns) if pk else None
        self._pk_codec = KeyCodec(info.schema, self._pk_cols) if pk else None
        self._pk_index: Optional[dict] = None
        self._pk_stale = True
        if wal_path:
            self.attach_wal(wal_path)

    # every data access inside TableStore flows through ``self.regions``
    # (reads, writes, stats, the pk index), so the property is the ONE
    # chokepoint where a deferred cluster attach materializes
    @property
    def regions(self) -> list:
        if self._attach_pending is not None:
            # double-checked under the store lock: concurrent first readers
            # (thread-per-connection frontends) must either perform the
            # attach or WAIT for it — a bare read during materialization
            # would silently see the empty initial region.  _attach_pending
            # stays set until the pull SUCCEEDS (so the unlocked fast path
            # can never skip a half-built image); _attaching breaks the
            # same-thread re-entrancy of the replay, which reads .regions
            with self._lock:
                if self._attach_pending is not None and not self._attaching:
                    self._ensure_attached()
        return self._regions

    @regions.setter
    def regions(self, v: list) -> None:
        self._regions = v

    @property
    def attach_pending(self) -> bool:
        """True while the cluster image is deferred (nothing pulled yet)."""
        return self._attach_pending is not None

    def attach_replicated_lazy(self, tier, fs) -> None:
        """Bind to a daemon-plane tier WITHOUT pulling any rows.  Eligible
        SELECTs push fragments to the store daemons (exec/session
        _try_pushdown); the first access that needs the local columnar
        image (DML, complex plans, point lookups) triggers the pull.
        The reference's frontend works this way permanently — it never
        holds table images, every read executes on the stores."""
        self.replicated = tier
        self._attach_pending = (tier, fs)

    def _ensure_attached(self) -> None:
        tier, fs = self._attach_pending
        self._attaching = True
        try:
            # re-checked at materialization time (not just at make_store):
            # another frontend may have flushed cold segments since
            check_cold_readable(tier, fs, self.info.name)
            cold = tier.cold_rows(fs) if fs is not None else None
            self.attach_replicated(tier, cold_rows=cold)
            self._attach_pending = None      # only a COMPLETE pull clears it
        finally:
            self._attaching = False

    # -- row tier ---------------------------------------------------------
    def _row_schema(self) -> Schema:
        return Schema((Field(ROWID, LType.INT64, False),
                       Field("__del", LType.BOOL, True))
                      + self.info.schema.fields)

    def _build_row_tier(self, wal_path: str | None):
        self.row_table = RowTable(self._row_schema(), [ROWID],
                                  wal_path=wal_path)
        self.wal_path = wal_path

    def attach_wal(self, path: str):
        """Open (and replay) the WAL: committed hot deltas since the last
        checkpoint apply over the current cold state (reference: restart
        recovery from applied_index + log replay, include/store/region.h:644)."""
        self._build_row_tier(path)
        self._replay_hot(self.row_table.scan_rows())

    def attach_replicated(self, tier, cold_rows: Optional[list] = None,
                          hot_rows: Optional[list] = None):
        """Bind this table to its raft-replicated hot tier and recover: the
        replicas' committed row state replays over the cold state, exactly
        like a WAL replay — but the log here survives any single node (the
        on_snapshot_load_for_restart analog, include/store/region.h:644).

        ``cold_rows``: manifest-ordered rows from the external cold tier
        (storage/coldfs) — they replay FIRST, with the hot tier's (newer)
        versions winning per rowid, so a SELECT transparently spans
        hot + cold (region_olap.cpp's cold-SST + hot-Rocks merge)."""
        self.replicated = tier
        rows = hot_rows if hot_rows is not None else tier.scan_rows()
        if cold_rows:
            merged: dict[int, dict] = {}
            for r in cold_rows:
                merged[int(r[ROWID])] = r
            for r in rows:
                merged[int(r[ROWID])] = r
            rows = [merged[k] for k in sorted(merged)]
        self._replay_hot(rows)

    def _replay_hot(self, rows: list[dict]):
        """Apply recovered hot-tier rows over cold state, advancing the
        rowid watermark (shared by WAL and replicated recovery)."""
        if rows:
            self._apply_deltas(rows)
        with self._lock:        # reentrant; watermark races with inserts
            for r in rows:
                self._next_rowid = max(self._next_rowid, int(r[ROWID]) + 1)

    def _apply_deltas(self, rows: list[dict]):
        """Replay WAL rows (inserts / updates / __del markers) over cold."""
        with self._lock:
            loc = {}
            for reg in self.regions:
                for off, rid in enumerate(reg.rowids):
                    loc[int(rid)] = (reg, off)
            per_region: dict[int, dict[int, Optional[dict]]] = {}
            appends: list[dict] = []
            for row in rows:
                rid = int(row[ROWID])
                if rid in loc:
                    reg, off = loc[rid]
                    patch = per_region.setdefault(reg.region_id, {})
                    patch[off] = None if row.get("__del") else row
                elif not row.get("__del"):
                    appends.append(row)
            for reg in self.regions:
                patch = per_region.get(reg.region_id)
                if not patch:
                    continue
                py = reg.data.to_pylist()
                keep = np.ones(reg.num_rows, bool)
                for off, row in patch.items():
                    if row is None:
                        keep[off] = False
                    else:
                        py[off] = {f.name: row.get(f.name)
                                   for f in self.info.schema.fields}
                cols = {f.name: [r[f.name] for r in py]
                        for f in self.arrow_schema}
                reg.data = pa.table(cols, schema=self.arrow_schema) \
                    .filter(pa.array(keep))
                reg.rowids = reg.rowids[keep]
                reg.version += 1
            if appends:
                rowids = np.asarray([int(r[ROWID]) for r in appends], np.int64)
                cols = {f.name: [r.get(f.name) for r in appends]
                        for f in self.arrow_schema}
                self._append_table(pa.table(cols, schema=self.arrow_schema),
                                   rowids)
            self._mutations += 1
            self._pk_stale = True

    def checkpoint(self, directory: str):
        """Flush the full live state to Parquet and reset the WAL — the
        hot->cold flush (region_olap.cpp:445 flush_to_cold)."""
        with self._lock:
            self.save_parquet(directory)
            self._reset_wal()

    # -- transactions -----------------------------------------------------
    def begin_txn(self) -> TxnContext:
        with self._lock:
            if self._writer is not None:
                raise ConflictError(
                    f"table {self.info.name} locked by an open transaction")
            tctx = TxnContext(self)
            self._writer = tctx
            return tctx

    def _end_txn(self, tctx: TxnContext):
        with self._lock:
            if self._writer is tctx:
                self._writer = None
            for r in self._regions:
                r.hold = False

    def _writer_check(self, tctx: Optional[TxnContext]):
        """Statement-level write admission: an open transaction holds the
        table's writer lease; concurrent writers conflict (the coarse analog
        of the reference's per-row pessimistic locks + 2PC ordering)."""
        if self._writer is not None and self._writer is not tctx:
            raise ConflictError(
                f"table {self.info.name} locked by an open transaction")
        if tctx is not None:
            tctx._capture()

    # -- reads ----------------------------------------------------------
    def _alloc_region_id(self) -> int:
        rid = self._next_region
        self._next_region += 1
        return rid

    def _alloc_rowids(self, n: int) -> np.ndarray:
        """Rowid allocation.  Replicated tiers allocate CLUSTER-WIDE ranges
        from meta (chunked to amortize the round trip; burned remainders
        are never reused — the auto-incr range discipline), so concurrent
        frontends over the same fleet/cluster cannot mint colliding keys.
        Standalone stores use the local watermark counter."""
        if self.replicated is not None:
            # no duck-type fallback: a tier without alloc_rowids must fail
            # loudly, not quietly revert to colliding local counters
            if self._rowid_pool_left < n:
                grab = max(n, 512)
                self._rowid_pool = self.replicated.alloc_rowids(
                    grab, floor=self._next_rowid)
                self._rowid_pool_left = grab
            start = self._rowid_pool
            self._rowid_pool += n
            self._rowid_pool_left -= n
            self._next_rowid = max(self._next_rowid, start + n)
            return np.arange(start, start + n, dtype=np.int64)
        start = self._next_rowid
        self._next_rowid += n
        return np.arange(start, start + n, dtype=np.int64)

    @property
    def num_rows(self) -> int:
        with self._lock:
            return sum(r.num_rows for r in self.regions)

    def snapshot(self) -> pa.Table:
        with self._lock:
            return pa.concat_tables([r.data for r in self.regions]) \
                if self.regions else self.arrow_schema.empty_table()

    def device_batches(self) -> list[ColumnBatch]:
        with self._lock:
            return [r.device_batch() for r in self.regions if r.num_rows]

    @property
    def version(self) -> int:
        """Monotonic mutation counter: every write, patches included.  NOT
        derived from region versions: transaction rollback rebuilds
        regions, and a derived version could revisit an old value and
        alias stale device/stats caches."""
        with self._lock:
            return self._mutations + self._patch_seq

    @property
    def plan_version(self) -> int:
        """What a cached plan and the statistics it read are valid for: a
        pk-addressed write moves it only where it widened a statistic."""
        with self._lock:
            return self._mutations + self._plan_gen

    def device_table_batch(self) -> ColumnBatch:
        """Whole-table device batch with table-wide string dictionaries.

        Built from the concatenated snapshot so every string column has ONE
        dictionary (regions sharing dictionaries is what lets per-region
        partial aggregates merge by code), each the first snapshot of a
        growing one (column/dictionary.Lineage).  Kept until a write that
        is not pk-addressed; a pk-addressed write reaches it as a patch
        (``_flush_patches``), so lanes and row positions part ways: a
        deleted row's lane turns dead, an inserted row takes the next lane
        of the dead tail.

        With ``FLAGS.batch_bucketing`` the batch pads to a power-of-two
        capacity bucket (column/batch.bucket_capacity) with a dead-row tail
        (``sel=False``), so DML that moves the row count inside one bucket
        keeps the device shape — compiled executables scanning this table
        stay valid and only a bucket crossing retraces."""
        with self._lock:
            img = self._image()
            if img.pending:
                self._flush_patches(img)
                img = self._image()     # built again if the patch could not
            return img.batch

    def _image_key(self) -> tuple:
        from ..utils.flags import FLAGS

        bucketing = bool(FLAGS.batch_bucketing)
        return (self._mutations, bucketing,
                int(FLAGS.batch_bucket_min) if bucketing else 0)

    def _image(self) -> "_LaneImage":
        """The lane image of the current structural version, built when
        there is none (caller holds the lock)."""
        from ..column.batch import bucket_capacity, pad_batch
        from ..column.dictionary import Dictionary
        from dataclasses import replace

        key = self._image_key()
        img = self._img
        if img is not None and img.key == key:
            return img
        if img is not None:
            metrics.image_rebuilds.add(1)
        snap = self.snapshot()
        b = ColumnBatch.from_arrow(snap)
        b = ColumnBatch(b.names, [
            replace(c, dictionary=Dictionary.growable(c.dictionary))
            if c.dictionary is not None else c for c in b.columns])
        n = len(b)
        if key[1]:
            b = pad_batch(b, bucket_capacity(n, key[2]))
        lanes = np.full(len(b), -1, np.int64)
        if n:
            lanes[:n] = np.concatenate([r.rowids for r in self.regions])
        img = _LaneImage(key, b, lanes, n)
        pk = self.pk_range_column()
        if pk is not None:
            # the primary key in key order, by lane: sorted once an image,
            # then kept by the pk-addressed writes
            if getattr(self, "_pk_order_built", False):
                metrics.pk_order_rebuilds.add(1)
            self._pk_order_built = True
            svals, spos = self._secondary_order(pk)
            img.pk_vals, img.pk_lanes = svals, spos.astype(np.int64)
        self._img = img
        return img

    def _flush_patches(self, img: "_LaneImage") -> None:
        """The rows pk-addressed writes changed since the last flush, laid
        into the resident image by one program: every column of each
        changed lane, and its ``sel`` bit (caller holds the lock)."""
        from ..column.batch import _arrow_to_numpy
        from ..column.dictionary import NULL_CODE
        from dataclasses import replace

        pending, img.pending = img.pending, {}
        lanes = sorted(pending)
        live = [pending[ln] is not None for ln in lanes]
        rows = [pending[ln] for ln in lanes if pending[ln] is not None]
        with trace.timed("image.patch", lanes=len(lanes)) as sp:
            b = img.batch
            m = len(lanes)
            width = max(8, 1 << (m - 1).bit_length())
            idx = np.full(width, len(b), np.int32)
            idx[:m] = lanes
            sel = np.zeros(width, bool)
            sel[:m] = live
            upd = pa.Table.from_pylist(rows, schema=self.arrow_schema)
            at = np.cumsum(live) - 1
            leaves, cols = [], []
            for name, c in zip(b.names, b.columns):
                arr = upd.column(name)
                if c.ltype is LType.STRING:
                    lin = c.dictionary.lineage
                    vals = arr.to_pylist()
                    data = np.asarray(
                        [NULL_CODE if v is None else lin.append(v)
                         for v in vals], np.int32)
                    valid = None if not arr.null_count else \
                        np.asarray([v is not None for v in vals])
                    c = replace(c, dictionary=c.dictionary.current())
                else:
                    data, valid, _, _ = _arrow_to_numpy(arr, arr.type)
                if valid is not None and c.validity is None:
                    # a NULL where the image keeps no validity: the
                    # image's structure changes, so it is built again
                    self._drop_image()
                    return
                d = np.zeros(width, c.data.dtype)
                v = np.zeros(width, bool)
                if rows:
                    d[:m] = np.where(sel[:m], data[np.maximum(at, 0)], 0)
                    v[:m] = sel[:m] & (True if valid is None
                                      else valid[np.maximum(at, 0)])
                leaves.append((c.data, d, c.validity,
                               v if c.validity is not None else None))
                cols.append(c)
            out_sel, out = _image_patch(b.sel, idx, sel, leaves)
            img.batch = ColumnBatch(
                b.names, [replace(c, data=d, validity=vd)
                          for c, (d, vd) in zip(cols, out)],
                out_sel, None, live_prefix=b.live_prefix and not img.holes)
        metrics.image_patches.add(1)
        metrics.image_patch_ms.add(sp.ms)

    # -- MVCC (storage/mvcc.py) ------------------------------------------
    def attach_mvcc(self, runtime) -> None:
        """Share the engine's MVCC plane (Database.mvcc): one TSO client
        and one snapshot registry across every table, so commit order is
        a total order engine-wide."""
        self._tso = runtime.tso
        self._snap_reg = runtime.snapshots

    def _mvcc_ts(self) -> int:
        """A fresh commit timestamp (lazy local oracle when unattached)."""
        if self._tso is None:
            from .mvcc import TsoClient
            self._tso = TsoClient()
        return self._tso.next_ts()

    def _mvcc_stamp_new(self, rowids, tctx, bulk: bool = False) -> None:
        """Stamp freshly-appended rows: PENDING inside a transaction
        (restamped at decide time), a fresh ts for autocommit.  A ``bulk``
        append's rowids (``_alloc_rowids``: contiguous) are one run."""
        from ..utils.flags import FLAGS
        from .mvcc import PENDING
        if not FLAGS.mvcc:
            return
        cts = PENDING if tctx is not None else self._mvcc_ts()
        if bulk:
            if len(rowids):
                self._mvcc.stamp_range(int(rowids[0]), int(rowids[-1]) + 1,
                                       cts)
        else:
            self._mvcc.stamp(rowids, cts)
        if tctx is None:
            self._mvcc_maybe_gc(cts)

    def _mvcc_record_dead(self, rows: list[dict], rowids, tctx,
                          ts: int | None = None) -> int:
        """Old versions of deleted/updated rows enter history; returns the
        delete_ts used (PENDING in-txn) so updates can stamp the new
        versions with the same instant."""
        from ..utils.flags import FLAGS
        from .mvcc import PENDING
        if not FLAGS.mvcc:
            return 0
        dts = PENDING if tctx is not None else (ts or self._mvcc_ts())
        self._mvcc.record_dead(rows, rowids, dts)
        return dts

    def _mvcc_maybe_gc(self, now_ts: int, threshold: int = 512) -> None:
        """Opportunistic commit-seam sweep: keeps version debt bounded
        without a background thread.  Caller holds the table lock; the
        registry lock (rank 12) nests INSIDE it (rank 10) — ascending."""
        if len(self._mvcc.history) < threshold:
            return
        wm = self._snap_reg.watermark(now_ts) if self._snap_reg is not None \
            else now_ts
        with trace.span("mvcc.gc", versions=len(self._mvcc.history)):
            self._mvcc.gc(wm)
        metrics.mvcc_gc_sweeps.add(1)

    def mvcc_gc(self, watermark: int) -> int:
        """One watermark-driven sweep (MvccRuntime.gc / the GC thread)."""
        with self._lock:
            return self._mvcc.gc(int(watermark))

    def _mvcc_diverged(self, snap_ts: int) -> tuple[bool, list]:
        """(does the live image differ from the one at ``snap_ts``?, the
        history versions alive at it) — the one question both pinned-read
        check sites ask, each at its own instant.  Caller holds the table
        lock.  The live half is MvccState's O(1) summary, never a walk of
        ``live_cts``; its error is one-sided (a popped maximum says
        "diverged" where the walk would say "quiet")."""
        from .mvcc import mvcc_quiet_checks, mvcc_versioned_checks
        mv = self._mvcc
        hist = mv.versions_at(snap_ts)
        diverged = bool(hist) or mv.live_newer_than(snap_ts)
        (mvcc_versioned_checks if diverged else mvcc_quiet_checks).add(1)
        return diverged, hist

    def mvcc_needs_versioned(self, snap_ts: int) -> bool:
        """True when a read pinned at ``snap_ts`` cannot be served by the
        CURRENT resident image: some commit landed after the snapshot, or
        a version alive at it has since died.  O(history), whatever the
        table's size (no walk of the live stamps, no image build) —
        the session uses it to keep the fast paths (egress, point lookup,
        access-path gathers, streaming, pushdown) engaged on quiet tables
        under a pin, where live and snapshot images are identical."""
        with self._lock:
            return self._mvcc_diverged(int(snap_ts))[0]

    def snapshot_versions(self, snap_ts: int):
        """The versioned read image at ``snap_ts``, or None when the
        CURRENT resident image already equals it (no commit after the
        snapshot, no relevant dead version) — the fast path that makes an
        automatic pin free on quiet tables and keeps it bit-identical to
        the unpinned read.

        Returns ``(table, cts, dts, versions_scanned)``: the live image
        concatenated with history versions alive at snap_ts, plus aligned
        int64 commit/delete timestamp arrays for the device-side
        visibility mask.  Built atomically under the table lock, so the
        caller gets ONE instant even while writes flow — and because the
        history rides the table (frontend-level), a region split or
        migration mid-query never moves it."""
        from .mvcc import MAX_TS
        snap_ts = int(snap_ts)
        with self._lock:
            diverged, hist = self._mvcc_diverged(snap_ts)
            if not diverged:
                return None
            live = self.snapshot()
            regions = self.regions
            rowids = (np.concatenate([r.rowids for r in regions])
                      if regions else np.empty(0, dtype=np.int64))
            cts = self._mvcc.stamps_for(rowids)
            dts = np.full(len(rowids), MAX_TS, dtype=np.int64)
            if hist:
                htbl = pa.Table.from_pylist([h[0] for h in hist],
                                            schema=live.schema)
                live = pa.concat_tables([live, htbl])
                cts = np.concatenate(
                    [cts, np.fromiter((h[1] for h in hist), dtype=np.int64,
                                      count=len(hist))])
                dts = np.concatenate(
                    [dts, np.fromiter((h[2] for h in hist), dtype=np.int64,
                                      count=len(hist))])
            return live, cts, dts, len(hist)

    def column_stats(self, column: str) -> dict:
        """Host-side column statistics for planner decisions (the analog of
        the reference's statistics.proto CM-sketch/histogram feed)."""
        with self._lock:
            v = self.plan_version
            cache = getattr(self, "_stats_cache", None)
            if cache is None or cache[0] != v:
                cache = (v, {})
                self._stats_cache = cache
            if column in cache[1]:
                return cache[1][column]
            with trace.timed("stats.column", column=column,
                             rows=self.num_rows) as sp:
                st = cache[1][column] = self._collect_stats(column)
            metrics.column_stats_ms.add(sp.ms)
            return st

    def _collect_stats(self, column: str) -> dict:
        """A ``column_stats`` miss: one column's statistics from the
        snapshot (caller holds the table lock)."""
        import pyarrow.compute as pc

        snap = self.snapshot()
        col = snap.column(column)
        st: dict = {}
        f = self.info.schema.field(column)
        if f.ltype is LType.STRING:
            batch = self.device_table_batch()
            st["dict_size"] = _dict_domain(batch.column(column).dictionary)
        elif snap.num_rows:
            try:
                mm = pc.min_max(col).as_py()
                mn, mx = mm["min"], mm["max"]
                if hasattr(mn, "toordinal") and not hasattr(mn, "hour"):
                    import datetime
                    epoch = datetime.date(1970, 1, 1)
                    mn = (mn - epoch).days
                    mx = (mx - epoch).days
                if isinstance(mn, (int,)) or f.ltype.is_integer or f.ltype is LType.DATE:
                    st["min"], st["max"] = mn, mx
            except Exception:
                # stats stay partial; planner falls back to defaults
                metrics.count_swallowed("column_store.zone_stats")
        st.update(self._histogram_stats(col, f) or {})
        if f.ltype.is_integer or f.ltype is LType.DATE:
            st["ordered"] = _non_decreasing(col)
        return st

    def _histogram_stats(self, col, f) -> Optional[dict]:
        """Equi-depth histogram + MCVs per column version (index/stats —
        the reference's ANALYZE-time CM-sketch/histogram collection done
        lazily, like every other derived artifact here)."""
        from ..index.stats import collect
        from ..utils.flags import FLAGS

        try:
            if not FLAGS.histogram_stats:
                return None
            n_total = len(col)
            if n_total == 0:
                return None
            import pyarrow.compute as pc
            n_nulls = col.null_count
            vals = pc.drop_null(col).combine_chunks() \
                .to_numpy(zero_copy_only=False)
            kind = None
            if f.ltype is LType.STRING:
                vals = np.asarray(vals, dtype=object)
                numeric = False
            else:
                if vals.dtype.kind == "M":        # date/datetime
                    if f.ltype is LType.DATE:
                        vals = vals.astype("datetime64[D]")
                        kind = "date"
                    else:
                        vals = vals.astype("datetime64[us]")
                        kind = "datetime"
                    vals = vals.astype(np.int64)
                elif vals.dtype.kind == "O":
                    return None                   # decimals etc.
                numeric = True
            st = collect(vals, n_total, n_nulls, numeric)
            if kind:
                st["kind"] = kind
            return st
        except Exception:       # noqa: BLE001 — stats are advisory
            return None

    def next_auto_incr(self, col: str, n: int) -> list[int]:
        """Allocate n consecutive AUTO_INCREMENT ids (monotonic; rollback
        never reuses a burned range, like MySQL/the reference)."""
        import pyarrow.compute as pc

        with self._lock:
            if self._auto_incr is None:
                mx = 0
                for r in self.regions:
                    if r.num_rows:
                        m = pc.max(r.data.column(col)).as_py()
                        if m is not None:
                            mx = max(mx, int(m))
                self._auto_incr = mx
            start = self._auto_incr + 1
            self._auto_incr += n
            return list(range(start, start + n))

    # -- access paths (reference: index_selector.cpp feeding scan ranges) --

    _ZONE_TYPES = "int/float/date/ts"   # doc anchor; see zone_map_column

    def zone_map_column(self, column: str):
        """Per-region (min, max, has_null) for numeric/temporal columns, or
        None when the type can't range-prune.  Cached per table version —
        the column tier's statistics-pruning analog."""
        import pyarrow.compute as pc

        f = self.info.schema.field(column)
        if not (f.ltype.is_integer or f.ltype.is_float
                or f.ltype is LType.DATE or f.ltype.is_temporal):
            return None
        with self._lock:
            v = self.plan_version
            cache = getattr(self, "_zone_cache", None)
            if cache is None or cache[0] != v:
                cache = (v, {})
                self._zone_cache = cache
            if column in cache[1]:
                return cache[1][column]
            zones = []
            for r in self.regions:
                if not r.num_rows:
                    zones.append(None)        # empty region: always prunable
                    continue
                col = r.data.column(column)
                if col.null_count == col.length():
                    zones.append((None, None, True))
                    continue
                mm = pc.min_max(col).as_py()
                zones.append((_zone_scalar(mm["min"], f.ltype),
                              _zone_scalar(mm["max"], f.ltype),
                              col.null_count > 0))
            cache[1][column] = zones
            return zones

    def prune_regions(self, ranges: dict):
        """Regions whose zone maps can satisfy every [lo, hi] constraint.
        -> (list of region indexes kept, total regions).  Conservative: any
        uncertainty keeps the region."""
        with self._lock:
            keep = []
            for i, r in enumerate(self.regions):
                if not r.num_rows:
                    continue
                alive = True
                for col, (lo, hi) in ranges.items():
                    zones = self.zone_map_column(col)
                    if zones is None or zones[i] is None:
                        continue
                    zmin, zmax, _ = zones[i]
                    if zmin is None:              # all-NULL region: no row
                        alive = False             # can match a comparison
                        break
                    lt = self.info.schema.field(col).ltype
                    lo_c = _zone_scalar(lo, lt)
                    hi_c = _zone_scalar(hi, lt)
                    if lo_c is not None and zmax < lo_c:
                        alive = False
                        break
                    if hi_c is not None and zmin > hi_c:
                        alive = False
                        break
                if alive:
                    keep.append(i)
            return keep, sum(1 for r in self.regions if r.num_rows)

    def regions_table(self, keep: list[int]) -> pa.Table:
        with self._lock:
            tabs = [self.regions[i].data for i in keep]
            return pa.concat_tables(tabs) if tabs \
                else self.arrow_schema.empty_table()

    def _secondary_order(self, column: str):
        """(sorted values ndarray, row positions ndarray) over the snapshot,
        NULLs excluded; cached per version."""
        with self._lock:
            v = self.version
            cache = getattr(self, "_sec_cache", None)
            if cache is None or cache[0] != v:
                cache = (v, {})
                self._sec_cache = cache
            if column in cache[1]:
                return cache[1][column]
            snap = self.snapshot()
            col = snap.column(column)
            f = self.info.schema.field(column)
            if f.ltype is LType.STRING:
                vals = np.asarray(col.to_pylist(), dtype=object)
            else:
                vals = col.to_numpy(zero_copy_only=False)
            if col.null_count:
                mask = ~np.asarray(col.is_null())
                pos = np.nonzero(mask)[0]
                vals = vals[mask]
            else:
                pos = np.arange(len(vals))
            order = np.argsort(vals, kind="stable")
            entry = (vals[order], pos[order])
            cache[1][column] = entry
            return entry

    def _perm_cache_key(self) -> tuple:
        """Permutations are computed over the (flag-dependent) padded device
        batch, so the bucket config joins the version in the cache key —
        flipping batch_bucketing must not serve a wrong-length permutation
        for the same version."""
        from ..utils.flags import FLAGS

        return (self.version, bool(FLAGS.batch_bucketing),
                int(FLAGS.batch_bucket_min))

    def sort_permutation(self, cols: tuple) -> "np.ndarray":
        """Host-side permutation sorting the DEVICE-VISIBLE arrays of
        ``cols`` (last = secondary key), packed the way the join kernels
        pack them: primary key int64<<32 | secondary&0xFFFFFFFF.  Cached
        per table version — the 'index build' that lets a static table's
        joins skip the on-device bitonic sort entirely (the reference
        reads pre-sorted secondary indexes from RocksDB the same way)."""
        import jax

        with self._lock:
            v = self._perm_cache_key()
            cache = getattr(self, "_perm_cache", None)
            if cache is None or cache[0] != v:
                cache = (v, {})
                self._perm_cache = cache
            ck = ("join",) + tuple(cols)
            if ck in cache[1]:
                return cache[1][ck]
            batch = self.device_table_batch()
        # device->host materialization + argsort OUTSIDE the lock: a
        # blocking transfer under self._lock stalls every writer queued on
        # it (tpulint LOCKORDER); the batch is an immutable snapshot, and
        # one fused device_get replaces per-column implicit transfers
        arrs = [np.asarray(a).astype(np.int64) for a in
                jax.device_get([batch.column(c).data for c in cols])]
        if len(arrs) == 1:
            order = np.argsort(arrs[0], kind="stable")
        else:
            packed = (arrs[0] << 32) | (arrs[1] & 0xFFFFFFFF)
            order = np.argsort(packed, kind="stable")
        order = order.astype(np.int32)
        with self._lock:
            # install only while the table still sits at the captured
            # version — a permutation over an older snapshot must never
            # serve a newer table
            cache = getattr(self, "_perm_cache", None)
            if cache is not None and cache[0] == v:
                cache[1][ck] = order
        return order

    def agg_sort_permutation(self, cols: tuple) -> "np.ndarray":
        """Host-side permutation replicating group_aggregate_sorted's key
        ordering chain EXACTLY (canonical 0 under NULL lanes, stable sort
        per key, NULLs-first per key): the device kernel then needs only
        an O(n) liveness partition instead of a multi-key bitonic sort.
        Cached per table version."""
        import jax

        with self._lock:
            v = self._perm_cache_key()
            cache = getattr(self, "_perm_cache", None)
            if cache is None or cache[0] != v:
                cache = (v, {})
                self._perm_cache = cache
            ck = ("agg",) + tuple(cols)
            if ck in cache[1]:
                return cache[1][ck]
            batch = self.device_table_batch()
        # materialize every key column (+validity) in ONE fused device_get,
        # outside the lock — same LOCKORDER discipline as sort_permutation
        host = jax.device_get(
            [(batch.column(c).data, batch.column(c).validity)
             for c in cols])
        perm = np.arange(len(batch))
        for d, vmask in reversed(host):
            d = np.asarray(d)
            if d.dtype == np.bool_:
                d = d.astype(np.int32)
            if vmask is not None:
                vmask = np.asarray(vmask)
                d = np.where(vmask, d, np.zeros((), d.dtype))
            perm = perm[np.argsort(d[perm], kind="stable")]
            if vmask is not None:
                perm = perm[np.argsort(vmask[perm], kind="stable")]
        perm = perm.astype(np.int32)
        with self._lock:
            cache = getattr(self, "_perm_cache", None)
            if cache is not None and cache[0] == v:
                cache[1][ck] = perm
        return perm

    def secondary_count(self, column: str, value):
        """How many rows match column = value (None if unindexable)."""
        try:
            svals, _ = self._secondary_order(column)
        except Exception:
            return None
        lo = np.searchsorted(svals, value, "left")
        hi = np.searchsorted(svals, value, "right")
        return int(hi - lo)

    def secondary_positions(self, column: str, value) -> np.ndarray:
        """Snapshot row positions with column = value (sorted ascending)."""
        svals, spos = self._secondary_order(column)
        lo = np.searchsorted(svals, value, "left")
        hi = np.searchsorted(svals, value, "right")
        return np.sort(spos[lo:hi])

    def secondary_scan(self, column: str, value) -> pa.Table:
        """Rows with column = value, positions and snapshot taken under ONE
        lock acquisition (a concurrent write between them would make the
        gather index a different table)."""
        with self._lock:
            pos = self.secondary_positions(column, value)
            return self.snapshot().take(pos)

    def pk_range_column(self) -> Optional[str]:
        """The primary key's column when a range over it can name row
        positions: one column of an orderable fixed-width type (the types
        zone maps prune on).  None for a composite, string or absent key."""
        if not self._pk_cols or len(self._pk_cols) != 1:
            return None
        lt = self.info.schema.field(self._pk_cols[0]).ltype
        if lt.is_integer or lt.is_float or lt is LType.DATE \
                or lt.is_temporal:
            return self._pk_cols[0]
        return None

    def _pk_range_slice(self, lo, hi):
        """(image lanes in key order, i, j): the rows with pk in the closed
        range [lo, hi] are lanes[i:j], less the -1 of deleted keys.
        Either bound may be None; a literal the key's type cannot be
        compared with leaves its side open (callers only need a
        superset)."""
        col = self.pk_range_column()
        lt = self.info.schema.field(col).ltype
        img = self._image()
        svals, spos = img.pk_vals, img.pk_lanes
        lo, hi = _zone_scalar(lo, lt), _zone_scalar(hi, lt)
        if svals.dtype.kind == "M":
            # DATE -> epoch days, DATETIME/TIMESTAMP -> epoch seconds: the
            # unit _zone_scalar speaks; back to the column's datetime64
            unit, scale = ("D", 1) if lt is LType.DATE else ("us", 10**6)
            lo, hi = (None if b is None else
                      np.datetime64(int(round(b * scale)), unit)
                      for b in (lo, hi))
        i = 0 if lo is None else int(np.searchsorted(svals, lo, "left"))
        j = len(svals) if hi is None else \
            int(np.searchsorted(svals, hi, "right"))
        return spos, i, max(i, j)

    def pk_range_count(self, lo, hi) -> Optional[int]:
        """How many rows hold pk in [lo, hi], for a table that has a
        pk_range_column; None when a bound does not compare with the key."""
        try:
            with self._lock:
                lanes, i, j = self._pk_range_slice(lo, hi)
                return int(np.count_nonzero(lanes[i:j] >= 0))
        except (TypeError, ValueError, OverflowError):
            return None

    def pk_range_scan(self, lo, hi):
        """(ascending snapshot positions of the rows with pk in [lo, hi],
        the resident device image they index), both of ONE version: taken
        under one lock acquisition, as secondary_scan takes its pair."""
        with self._lock:
            lanes, i, j = self._pk_range_slice(lo, hi)
            pos = lanes[i:j]
            return np.sort(pos[pos >= 0]), self.device_table_batch()

    def point_lookup(self, values: dict):
        """Primary-key point read from the host tier (no device program).
        -> row dict or None.  ``values``: pk column -> python literal."""
        if self._pk_codec is None:
            return None
        one = {}
        for name in self._pk_cols:
            f = self.arrow_schema.field(name)
            one[name] = pa.array([values[name]]).cast(f.type)
        key = self._encode_pk_table(pa.table(one))[0]
        idx = self._ensure_pk_index()
        rid = idx.get(key)
        if rid is None:
            return None
        with self._lock:
            for r in self.regions:
                row = r.row(rid)
                if row is not None:
                    return row
        return None

    # -- table partitioning (reference: range/hash partitions in
    # SchemaInfo, schema_factory.h:427-533; PartitionAnalyze prunes) ------
    def partition_spec(self) -> Optional[dict]:
        """{"kind": "range", "column": c, "names": [...], "uppers": [...]}
        (last upper None = MAXVALUE) or {"kind": "hash", "column": c,
        "n": N} or None."""
        return (self.info.options or {}).get("partition")

    @staticmethod
    def _norm_part_scalar(v, f):
        """One partition-column literal -> comparable numpy-friendly value
        (temporal to epoch int, everything else as-is)."""
        if v is None:
            return None
        if f.ltype.is_temporal and isinstance(v, str):
            from ..expr.compile import parse_temporal

            return parse_temporal(v, f.ltype)
        if f.ltype.is_temporal:
            import datetime

            if isinstance(v, datetime.datetime):
                return int((v - datetime.datetime(1970, 1, 1))
                           .total_seconds() * 1e6)
            if isinstance(v, datetime.date):
                return (v - datetime.date(1970, 1, 1)).days
        return v

    def _norm_part_array(self, arr, f) -> np.ndarray:
        if f.ltype.is_temporal:
            if f.ltype is LType.DATE:
                return np.asarray(arr.cast(pa.int32()).to_numpy(
                    zero_copy_only=False), np.int64)
            return np.asarray(arr.cast(pa.timestamp("us"))
                              .cast(pa.int64()).to_numpy(
                                  zero_copy_only=False), np.int64)
        if f.ltype is LType.STRING:
            return np.asarray(arr.to_pylist(), dtype=object)
        return arr.to_numpy(zero_copy_only=False)

    def partition_ids(self, table: pa.Table) -> np.ndarray:
        """Partition id per row (raises when a value falls past the last
        range bound and there is no MAXVALUE partition — MySQL's 'no
        partition for value').  NULL keys route to partition 0 (MySQL
        places NULL in the lowest partition); comparisons never match NULL,
        so pruning stays correct regardless."""
        spec = self.partition_spec()
        f = self.info.schema.field(spec["column"])
        arr = table.column(spec["column"])
        null_mask = np.asarray(arr.is_null()) if arr.null_count else None
        if null_mask is not None:
            import datetime

            if f.ltype is LType.STRING:
                fill = ""
            elif f.ltype is LType.DATE:
                fill = datetime.date(1970, 1, 1)
            elif f.ltype.is_temporal:
                fill = datetime.datetime(1970, 1, 1)
            else:
                fill = 0
            import pyarrow.compute as pc

            arr = pc.fill_null(arr, fill)
        vals = self._norm_part_array(arr, f)
        if spec["kind"] == "hash":
            n = int(spec["n"])
            if vals.dtype == object:
                from .replicated import _fnv64

                pids = np.fromiter(
                    (_fnv64(str(v).encode()) % n for v in vals),
                    dtype=np.int64, count=len(vals))
            else:
                pids = (vals.astype(np.int64) % n + n) % n
            if null_mask is not None:
                pids[null_mask] = 0
            return pids
        uppers = [self._norm_part_scalar(u, f) for u in spec["uppers"]]
        has_max = uppers and uppers[-1] is None
        finite = np.array([u for u in uppers if u is not None],
                          dtype=object if vals.dtype == object else None)
        pids = np.searchsorted(finite, vals, side="right")
        if null_mask is not None:
            pids[null_mask] = 0
        if not has_max and len(finite):
            over = pids >= len(finite)
            if null_mask is not None:
                over = over & ~null_mask
            if over.any():
                bad = vals[over][0]
                raise ValueError(
                    f"table {self.info.name!r} has no partition for value "
                    f"{bad!r} in column {spec['column']!r}")
        return pids

    def partitions_for(self, eq_value=None, range_=None) -> Optional[set]:
        """Partition ids a predicate on the partition column can touch, or
        None when the predicate cannot prune (e.g. range on hash)."""
        spec = self.partition_spec()
        if spec is None:
            return None
        f = self.info.schema.field(spec["column"])
        if eq_value is not None:
            t = pa.table({spec["column"]:
                          pa.array([eq_value]).cast(
                              schema_to_arrow(self.info.schema)
                              .field(spec["column"]).type)})
            try:
                return {int(self.partition_ids(t)[0])}
            except ValueError:
                return set()          # value past all bounds: matches none
        if spec["kind"] != "range" or range_ is None:
            return None
        lo, hi = range_
        uppers = [self._norm_part_scalar(u, f) for u in spec["uppers"]]
        finite = [u for u in uppers if u is not None]
        nparts = len(spec["uppers"])
        lo_n = self._norm_part_scalar(lo, f) if lo is not None else None
        hi_n = self._norm_part_scalar(hi, f) if hi is not None else None
        import bisect

        # ScanPredicates ranges are CLOSED ([lo, hi]) — the partition
        # holding hi itself must stay (side='right' matches partition_ids'
        # searchsorted routing)
        first = bisect.bisect_right(finite, lo_n) if lo_n is not None else 0
        last = bisect.bisect_right(finite, hi_n) if hi_n is not None \
            else nparts - 1
        return set(range(first, min(last, nparts - 1) + 1))

    def _rehome_partition_rows(self, only_ids: Optional[set] = None) -> None:
        """Move rows whose partition-column value no longer matches their
        region's tag into the right partition's regions (post-UPDATE; the
        caller holds self._lock and has already validated routability).
        ``only_ids``: id()s of the regions the update actually staged —
        the only ones that can hold misrouted rows."""
        moved_tabs, moved_ids = [], []
        for r in self.regions:
            if r.part < 0 or not r.num_rows:
                continue
            if only_ids is not None and id(r) not in only_ids:
                continue
            ids = self.partition_ids(r.data)
            wrong = ids != r.part
            if not wrong.any():
                continue
            m = pa.array(wrong)
            moved_tabs.append(r.data.filter(m))
            moved_ids.append(r.rowids[wrong])
            r.data = r.data.filter(pa.array(~wrong))
            r.rowids = r.rowids[~wrong]
            r.version += 1
        if moved_tabs:
            self._pk_stale = True
            self._append_table(pa.concat_tables(moved_tabs).combine_chunks(),
                               np.concatenate(moved_ids))

    def prune_parts(self, parts: set) -> tuple[list[int], int]:
        """(kept region INDEXES — regions_table's addressing — and total
        regions): regions tagged with a pruned partition drop; untagged
        (part=-1, e.g. reloaded from an old checkpoint) regions always
        stay — pruning must be conservative."""
        with self._lock:
            keep = [i for i, r in enumerate(self.regions)
                    if r.num_rows and (r.part == -1 or r.part in parts)]
            total = sum(1 for r in self.regions if r.num_rows)
            return keep, total

    def lookup_by_pks(self, pk_table: pa.Table) -> pa.Table:
        """Gather full rows matching the given primary-key values — the
        global-index LOOKUP JOIN (reference: select_manager_node.cpp:1081,
        the frontend joins index-region results back to main-table rows by
        pk).  Missing keys are silently absent (a concurrent delete)."""
        with self._lock:
            if self._pk_codec is None or not pk_table.num_rows:
                return self.snapshot().slice(0, 0)
            keys = self._encode_pk_table(pk_table)
            idx = self._ensure_pk_index()
            rids = {idx[k] for k in keys if k in idx}
            if not rids:
                return self.snapshot().slice(0, 0)
            wanted = np.fromiter(rids, dtype=np.int64)
            parts = []
            for r in self.regions:
                if not r.num_rows:
                    continue
                mask = np.isin(r.rowids, wanted)
                if mask.any():
                    parts.append(r.data.filter(pa.array(mask)))
            if not parts:
                return self.snapshot().slice(0, 0)
            return pa.concat_tables(parts).combine_chunks()

    # -- primary-key index -----------------------------------------------
    def _ensure_pk_index(self):
        if self._pk_codec is None:
            return None
        # staleness check + rebuild + publish under one critical section:
        # two lookups racing a write could otherwise both see stale, and
        # the later (older) rebuild would overwrite the fresher index
        with self._lock:
            if self._pk_index is None or self._pk_stale:
                idx: dict = {}
                for reg in self.regions:
                    if not reg.num_rows:
                        continue
                    keys = self._encode_pk_table(reg.data)
                    for k, rid in zip(keys, reg.rowids):
                        idx[k] = int(rid)
                self._pk_index = idx
                self._pk_stale = False
            return self._pk_index

    def _encode_pk_table(self, table: pa.Table) -> list[bytes]:
        cols, valids = [], []
        for name in self._pk_cols:
            arr = table.column(name)
            f = self.info.schema.field(name)
            if f.ltype is LType.STRING:
                cols.append(np.asarray(arr.to_pylist(), dtype=object))
            elif f.ltype is LType.DATE:
                cols.append(np.asarray(arr.cast(pa.int32()).to_numpy(
                    zero_copy_only=False), np.int64))
            elif f.ltype.is_temporal:
                cols.append(np.asarray(
                    arr.cast(pa.timestamp("us")).cast(pa.int64()).to_numpy(
                        zero_copy_only=False), np.int64))
            elif f.ltype.is_float:
                cols.append(arr.to_numpy(zero_copy_only=False))
            else:
                nulls = arr.null_count
                work = arr.fill_null(0) if nulls else arr
                cols.append(np.asarray(work.to_numpy(zero_copy_only=False),
                                       np.int64))
            valids.append(~np.asarray(arr.is_null()) if arr.null_count
                          else None)
        n = table.num_rows
        return self._pk_codec.encode_rows(cols, valids) if n else []

    def _check_duplicates(self, table: pa.Table):
        """INSERT-time primary-key uniqueness (reference: rocksdb key
        collision -> ER_DUP_ENTRY)."""
        if self._pk_codec is None or not table.num_rows:
            return
        idx = self._ensure_pk_index()
        keys = self._encode_pk_table(table)
        seen = set()
        for k in keys:
            if k in idx or k in seen:
                raise ConflictError(
                    f"Duplicate entry for key 'PRIMARY' in table "
                    f"{self.info.name!r}")
            seen.add(k)
        return keys

    # -- writes ---------------------------------------------------------
    def _append_table(self, table: pa.Table, rowids: np.ndarray,
                      split: bool = True):
        # every ingest path advances the AUTO_INCREMENT watermark past
        # explicitly-supplied ids (MySQL semantics; later auto ids must not
        # collide with bulk-loaded ones)
        auto_col = (self.info.options or {}).get("auto_increment")
        if auto_col and auto_col in table.column_names and table.num_rows:
            import pyarrow.compute as pc

            mx = pc.max(table.column(auto_col)).as_py()
            if mx is not None:
                if self._auto_incr is None:
                    self._auto_incr = int(mx)
                else:
                    self._auto_incr = max(self._auto_incr, int(mx))
        spec = self.partition_spec()
        if spec is None:
            last = self.regions[-1]
            if last.num_rows:
                last.data = pa.concat_tables([last.data, table]) \
                    .combine_chunks()
                last.rowids = np.concatenate([last.rowids, rowids])
            else:
                # the first rows of an empty region: the table's own
                # buffers, not a copy of them (a 100M-row load is 1.6 GB)
                last.data = table.combine_chunks()
                last.rowids = rowids
            last.version += 1
            if split:
                self._maybe_split(last)
            return
        # partitioned table: each partition's rows land in that partition's
        # OWN regions (reference: per-partition regions,
        # schema_factory.h:427-533, PartitionAnalyze routing)
        pids = self.partition_ids(table)
        for pid in np.unique(pids):
            m = pids == pid
            sub = table.filter(pa.array(m))
            subids = rowids[m]
            reg = None
            for r in reversed(self.regions):
                if r.part == int(pid):
                    reg = r
                    break
            if reg is None:
                reg = Region(self._alloc_region_id(),
                             self.arrow_schema.empty_table(),
                             part=int(pid))
                self.regions.append(reg)
            reg.data = pa.concat_tables([reg.data, sub]).combine_chunks()
            reg.rowids = np.concatenate([reg.rowids, subids])
            reg.version += 1
            if split:
                self._maybe_split(reg)

    def insert_arrow(self, table: pa.Table, tctx: Optional[TxnContext] = None,
                     check_dups: bool = False):
        """Bulk/cold append (the importer/fast_importer path): rows land in
        the column tier only — durable at the next checkpoint, not per-row
        WAL'd (exactly the reference's SST-building fast importer, which
        also trusts its input unless ``check_dups`` is requested)."""
        table = _coerce(table, self.arrow_schema)
        with self._lock:
            self._writer_check(tctx)
            if check_dups:
                self._check_duplicates(table)
            if self.partition_spec() is not None:
                self.partition_ids(table)   # reject before durable writes
            rowids = self._alloc_rowids(table.num_rows)
            if self.replicated is not None:
                # replicated tables have no "cold only" ingest: a rebuild
                # from the raft tier is THE recovery path, so the bulk batch
                # replicates as one write (the reference's fast importer
                # likewise lands SSTs in regions through raft ingest)
                recs = [dict(row, **{ROWID: int(rid)})
                        for row, rid in zip(table.to_pylist(), rowids)]
                self._write_hot(recs, tctx)
            self._mutations += 1
            self._pk_stale = True
            self._append_table(table, rowids)
            self._mvcc_stamp_new(rowids, tctx, bulk=True)

    def insert_rows(self, rows: list[dict], tctx: Optional[TxnContext] = None):
        """Hot insert (SQL INSERT ... VALUES): duplicate-PK checked, written
        to the row tier (WAL-durable / lock-buffered) AND the column tier."""
        cols = {f.name: [r.get(f.name) for r in rows] for f in self.arrow_schema}
        table = pa.table(cols, schema=self.arrow_schema)
        with self._lock:
            if len(rows) <= PATCH_INSERT_ROWS and self.pk_patchable() \
                    and table.column(self._pk_cols[0]).null_count == 0:
                self._patch_writer(tctx)
                new_keys = self._check_duplicates(table)
                rowids = self._pk_insert(table, tctx)
                if new_keys and self._pk_index is not None \
                        and not self._pk_stale:
                    for k, rid in zip(new_keys, rowids):
                        self._pk_index[k] = int(rid)
                return
            self._writer_check(tctx)
            new_keys = self._check_duplicates(table)
            if self.partition_spec() is not None:
                self.partition_ids(table)   # reject BEFORE the durable
                #                             write: WAL/raft replay must
                #                             never hold an unroutable row
            self._mutations += 1
            rowids = self._alloc_rowids(len(rows))
            recs = [dict(r, **{ROWID: int(rid)})
                    for r, rid in zip(rows, rowids)]
            self._write_hot(recs, tctx)
            self._append_table(table, rowids)
            self._mvcc_stamp_new(rowids, tctx)
            if new_keys and self._pk_index is not None and not self._pk_stale:
                for k, rid in zip(new_keys, rowids):
                    self._pk_index[k] = int(rid)

    def delete_where(self, host_mask_fn, tctx: Optional[TxnContext] = None,
                     collect_cols: Optional[list[str]] = None):
        """Delete rows where host_mask_fn(pa.Table) -> bool np.ndarray.
        Column tier filters; row tier records __del markers per rowid.
        With ``collect_cols``, returns (count, deleted-rows projection) —
        the global-index maintenance path needs the outgoing rows' indexed
        values to delete the matching index entries."""
        deleted = 0
        markers: list[dict] = []
        collected: list[pa.Table] = []
        with self._lock:
            self._writer_check(tctx)
            # phase 1: evaluate masks only (no mutation) so the hot-tier
            # write — a raft quorum commit on replicated tables — can fail
            # without leaving the columnar cache ahead of the durable state
            masks: list[tuple[Region, np.ndarray]] = []
            # a fresh PK index maintains itself incrementally: we know the
            # exact keys leaving the table (no O(n) rebuild on next insert)
            fresh = (self._pk_codec is not None and
                     self._pk_index is not None and not self._pk_stale)
            dead_keys: list[bytes] = []
            from ..utils.flags import FLAGS as _FLAGS
            mvcc_on = bool(_FLAGS.mvcc)
            dead_rows: list[dict] = []
            dead_rids: list[int] = []
            for r in self.regions:
                if not r.num_rows:
                    continue
                mask = np.asarray(host_mask_fn(r.data), dtype=bool)
                if mask.any():
                    if fresh:
                        dead_keys.extend(
                            self._encode_pk_table(r.data.filter(pa.array(mask))))
                    if collect_cols is not None:
                        collected.append(
                            r.data.filter(pa.array(mask)).select(collect_cols))
                    if mvcc_on:
                        # the outgoing versions: tombstoned into history at
                        # phase 2 so a pinned snapshot still sees them
                        dead_rows.extend(
                            r.data.filter(pa.array(mask)).to_pylist())
                        dead_rids.extend(int(x) for x in r.rowids[mask])
                    markers.extend({ROWID: int(rid), "__del": True}
                                   for rid in r.rowids[mask])
                    masks.append((r, mask))
                    deleted += int(mask.sum())
            if not markers:
                if collect_cols is not None:
                    return 0, self.snapshot().slice(0, 0).select(collect_cols)
                return 0
            self._write_hot(markers, tctx)
            # phase 2: the delete is durable/replicated — apply to columns
            self._mutations += 1
            if mvcc_on:
                self._mvcc_record_dead(dead_rows, dead_rids, tctx)
            for r, mask in masks:
                r.data = r.data.filter(pa.array(~mask))
                r.rowids = r.rowids[~mask]
                r.version += 1
            if fresh:
                for k in dead_keys:
                    self._pk_index.pop(k, None)
            else:
                self._pk_stale = True
        if collect_cols is not None:
            return deleted, pa.concat_tables(collected).combine_chunks()
        return deleted

    def update_where(self, host_mask_fn, assign_fn,
                     tctx: Optional[TxnContext] = None,
                     changed_cols: Optional[list[str]] = None,
                     collect_cols: Optional[list[str]] = None,
                     dry_run: bool = False):
        """Update rows in place: assign_fn(pa.Table, mask) -> pa.Table.
        Row tier records the full new row versions under the same rowids.
        ``changed_cols`` (the assignment targets) lets the PK index survive
        updates that don't touch key columns.  With ``collect_cols``,
        returns (count, old-rows projection, new-rows projection) — the
        global-index maintenance path deletes entries for the old values
        and inserts entries for the new ones."""
        updated = 0
        hot: list[dict] = []
        old_rows: list[pa.Table] = []
        new_rows_t: list[pa.Table] = []
        with self._lock:
            self._writer_check(tctx)
            # phase 1: compute the new region tables without installing them,
            # so a failed hot-tier write (raft no-quorum on replicated
            # tables) leaves the columnar cache consistent
            staged: list[tuple[Region, pa.Table]] = []
            from ..utils.flags import FLAGS as _FLAGS
            mvcc_on = bool(_FLAGS.mvcc)
            old_vers: list[dict] = []
            old_rids: list[int] = []
            for r in self.regions:
                if not r.num_rows:
                    continue
                mask = np.asarray(host_mask_fn(r.data), dtype=bool)
                if mask.any():
                    new_data = _coerce(assign_fn(r.data, mask),
                                       self.arrow_schema)
                    staged.append((r, new_data))
                    updated += int(mask.sum())
                    if collect_cols is not None:
                        old_rows.append(r.data.filter(pa.array(mask))
                                        .select(collect_cols))
                        new_rows_t.append(new_data.filter(pa.array(mask))
                                          .select(collect_cols))
                    if mvcc_on:
                        # pre-update versions close at the commit instant;
                        # the new versions open at the same instant
                        old_vers.extend(
                            r.data.filter(pa.array(mask)).to_pylist())
                        old_rids.extend(int(x) for x in r.rowids[mask])
                    new_rows = new_data.filter(pa.array(mask)).to_pylist()
                    hot.extend(dict(row, **{ROWID: int(rid)})
                               for row, rid in zip(new_rows, r.rowids[mask]))
            spec = self.partition_spec()
            part_moved = spec is not None and staged and (
                changed_cols is None or spec["column"] in changed_cols)
            if part_moved and not dry_run:
                # validate BEFORE any durable write: a new value past the
                # last range bound must fail the statement, not strand a
                # WAL/raft row that later replay cannot route
                for r, new_data in staged:
                    self.partition_ids(new_data)
            if not staged or dry_run:
                # dry_run: phase 1 only — the would-be old/new rows for a
                # pre-mutation constraint check (global UNIQUE), nothing
                # installed or written
                if collect_cols is not None:
                    if staged:
                        return (updated,
                                pa.concat_tables(old_rows).combine_chunks(),
                                pa.concat_tables(new_rows_t)
                                .combine_chunks())
                    empty = self.snapshot().slice(0, 0).select(collect_cols)
                    return 0, empty, empty
                return updated if dry_run else 0
            self._write_hot(hot, tctx)
            # phase 2: durable/replicated — install the new region tables
            self._mutations += 1
            if mvcc_on and old_vers:
                dts = self._mvcc_record_dead(old_vers, old_rids, tctx)
                # newest-wins is structural: the dying version's interval
                # closes exactly where the new version's opens
                self._mvcc.stamp(old_rids, dts)
            if self._pk_cols is not None and (
                    changed_cols is None or
                    any(c in self._pk_cols for c in changed_cols)):
                self._pk_stale = True
            for r, new_data in staged:
                r.data = new_data
                r.version += 1
            if part_moved:
                # rows whose partition-column value changed must MOVE to
                # their new partition's regions, or the stale region tag
                # makes pruning silently drop them from results
                self._rehome_partition_rows({id(r) for r, _ in staged})
        if collect_cols is not None:
            return (updated,
                    pa.concat_tables(old_rows).combine_chunks(),
                    pa.concat_tables(new_rows_t).combine_chunks())
        return updated

    # -- pk-addressed writes: patches, not rebuilds -----------------------
    def pk_patchable(self) -> bool:
        """Whether a write addressed by this table's primary key can take
        the patch path: one region, one integer key column, local
        (no raft tier, no deferred attach), unpartitioned, and a resident
        image with a dead tail to insert into (``batch_bucketing``)."""
        from ..utils.flags import FLAGS

        col = self.pk_range_column()
        with self._lock:
            return (col is not None and bool(FLAGS.batch_bucketing)
                    and self.info.schema.field(col).ltype.is_integer
                    and self.replicated is None
                    and self._attach_pending is None
                    and self.partition_spec() is None
                    and len(self.regions) == 1)

    def _pk_slot(self, img: "_LaneImage", v: int) -> int:
        """Index of key ``v`` in the image's key order, or -1."""
        i = int(np.searchsorted(img.pk_vals, v))
        return i if i < len(img.pk_vals) and img.pk_vals[i] == v else -1

    def _patch_writer(self, tctx: Optional[TxnContext]) -> None:
        """Write admission for a pk-addressed write: the writer lease, and
        the MVCC preimage (no region capture: the undo log restores)."""
        if self._writer is not None and self._writer is not tctx:
            raise ConflictError(
                f"table {self.info.name} locked by an open transaction")
        if tctx is not None:
            tctx._capture_mvcc()
            self.regions[0].hold = True

    def _pk_key_bytes(self, key: dict) -> bytes:
        one = {c: pa.array([key[c]]).cast(self.arrow_schema.field(c).type)
               for c in self._pk_cols}
        return self._encode_pk_table(pa.table(one))[0]

    def _pk_put(self, rid: int, key: int, row: Optional[dict],
                insert: bool = False) -> None:
        """Row ``rid`` (primary key ``key``) becomes ``row`` (None:
        deleted; ``insert``: a new rowid) in the region's overlay, the pk
        key order and the image's pending lanes (caller holds the lock)."""
        reg = self.regions[0]
        reg.put_row(rid, row)
        reg.version += 1
        self._patch_seq += 1
        img = self._img
        if img is None or img.key != self._image_key():
            if row is not None:
                self._stats_patch(row, insert)
            return
        i = self._pk_slot(img, key)
        lane = int(img.pk_lanes[i]) if i >= 0 else -1
        if row is None:
            if lane >= 0:
                img.pk_lanes[i] = -1
                img.lane_rowid[lane] = -1
                img.pending[lane] = None
                img.holes.append(lane)
            return
        if lane < 0:
            if img.holes:
                # a deleted row's lane first: a table that deletes as much
                # as it inserts never reaches the end of its bucket
                lane = img.holes.pop()
                insert = False          # not past the last lane
            elif img.free >= len(img.lane_rowid):
                self._drop_image()      # no dead lane left: a bucket crossing
                self._stats_patch(row, insert)
                return
            else:
                lane, img.free = img.free, img.free + 1
            if i >= 0:
                img.pk_lanes[i] = lane
            else:
                j = int(np.searchsorted(img.pk_vals, key))
                img.pk_vals = np.insert(img.pk_vals, j, key)
                img.pk_lanes = np.insert(img.pk_lanes, j, lane)
            img.lane_rowid[lane] = rid
        # a new string gets its code now, so the statistics (and the
        # plan that reads them) see the dictionary the patch will carry
        for c, name in zip(img.batch.columns, img.batch.names):
            if c.dictionary is not None and row.get(name) is not None:
                lin = c.dictionary.lineage
                n = lin.n
                lin.append(row[name])
                if lin.n > n:
                    metrics.dict_appends.add(1)
        img.pending[lane] = row
        self._stats_patch(row, insert)

    def _drop_image(self) -> None:
        """The resident image goes: the next read builds it, with new
        dictionaries, so no statistic a plan read of the old one stands."""
        self._img = None
        self._plan_gen += 1
        self._stats_cache = None
        self._zone_cache = None

    def _stats_patch(self, row: dict, insert: bool = False) -> None:
        """Keep the cached statistics true for a written row: a zone map
        or a min / max widens, an ``ordered`` column stops being so, a
        string's domain grows with its dictionary; each such change of a
        column statistic moves plan_version."""
        zones = getattr(self, "_zone_cache", None)
        if zones is not None and zones[0] == self.plan_version:
            for col, zs in zones[1].items():
                if zs is None:
                    continue
                v = _zone_scalar(row.get(col),
                                 self.info.schema.field(col).ltype)
                z = zs[0]
                if row.get(col) is None:
                    zs[0] = (None, None, True) if z is None else \
                        (z[0], z[1], True)
                elif v is None:
                    zones[1][col] = None    # incomparable: prune nothing
                elif z is None or z[0] is None:
                    zs[0] = (v, v, bool(z and z[2]))
                else:
                    zs[0] = (min(z[0], v), max(z[1], v), z[2])
        cache = getattr(self, "_stats_cache", None)
        if cache is None or cache[0] != self.plan_version:
            return
        moved = False
        for col, st in cache[1].items():
            v = row.get(col)
            if "dict_size" in st:
                if self._img is None:
                    continue
                size = _dict_domain(
                    self._img.batch.column(col).dictionary.current())
                if size != st["dict_size"]:
                    st["dict_size"], moved = size, True
                continue
            if v is None:
                if st.get("ordered"):
                    st["ordered"], moved = False, True
                continue
            v = _zone_scalar(v, self.info.schema.field(col).ltype)
            # a row past the last lane in value order keeps the column
            # ordered; any other write may not
            tail = insert and v is not None and st.get("max") is not None \
                and v >= st["max"]
            if st.get("ordered") and not tail:
                st["ordered"], moved = False, True
            if st.get("min") is not None and v is not None:
                if v < st["min"]:
                    st["min"], moved = v, True
                if v > st["max"]:
                    st["max"], moved = v, True
        if moved:
            self._plan_gen += 1
            self._stats_cache = (self.plan_version, cache[1])
            if zones is not None and zones[0] == self.plan_version - 1:
                self._zone_cache = (self.plan_version, zones[1])

    def _pk_restore(self, rid: int, row: Optional[dict]) -> None:
        """Undo of one pk-addressed write (a rolled-back transaction): row
        ``rid`` back to ``row`` (None: it did not exist)."""
        pkc = self._pk_cols[0]
        cur = self.regions[0].row(rid)
        key = (row or cur or {}).get(pkc)
        if key is None:
            return
        self._pk_put(rid, key, row)
        if self._pk_index is not None and not self._pk_stale:
            kb = self._pk_key_bytes({pkc: key})
            if row is None:
                self._pk_index.pop(kb, None)
            else:
                self._pk_index[kb] = rid

    def pk_update(self, key: dict, assign,
                  tctx: Optional[TxnContext] = None):
        """``UPDATE ... WHERE <pk> = v`` on a pk_patchable table: the row
        found by the pk index, ``assign(old_row) -> new_row`` (the key
        columns unchanged), the new version written as a patch.  -> (old
        row, new row), or None when no row holds the key."""
        with self._lock:
            self._patch_writer(tctx)
            rid = self._ensure_pk_index().get(self._pk_key_bytes(key))
            if rid is None:
                return None
            with trace.span("dml.pk_write", op="update"):
                old = self.regions[0].row(rid)
                new = assign(dict(old))
                self._write_hot([dict(new, **{ROWID: rid})], tctx)
                from ..utils.flags import FLAGS
                if FLAGS.mvcc:
                    # the dying version closes where the new one opens
                    dts = self._mvcc_record_dead([old], [rid], tctx)
                    self._mvcc.stamp([rid], dts)
                self._pk_put(rid, old[self._pk_cols[0]], new)
                if tctx is not None:
                    tctx.undo.append((rid, old))
            return old, new

    def pk_delete(self, key: dict, tctx: Optional[TxnContext] = None):
        """``DELETE ... WHERE <pk> = v`` on a pk_patchable table, as a
        patch.  -> the deleted row, or None when no row holds the key."""
        with self._lock:
            self._patch_writer(tctx)
            kb = self._pk_key_bytes(key)
            idx = self._ensure_pk_index()
            rid = idx.get(kb)
            if rid is None:
                return None
            with trace.span("dml.pk_write", op="delete"):
                old = self.regions[0].row(rid)
                self._write_hot([{ROWID: rid, "__del": True}], tctx)
                self._mvcc_record_dead([old], [rid], tctx)
                self._pk_put(rid, old[self._pk_cols[0]], None)
                idx.pop(kb, None)
                if tctx is not None:
                    tctx.undo.append((rid, old))
            return old

    def _pk_insert(self, table: pa.Table, tctx: Optional[TxnContext]):
        """``insert_rows`` as patches: each row takes a fresh rowid, the
        overlay, the key order and the next lane of the image's dead tail
        (caller holds the lock and has checked the keys are new)."""
        with trace.span("dml.pk_write", op="insert", rows=table.num_rows):
            rows = table.to_pylist()
            rowids = self._alloc_rowids(len(rows))
            self._write_hot([dict(r, **{ROWID: int(rid)})
                             for r, rid in zip(rows, rowids)], tctx)
            auto_col = (self.info.options or {}).get("auto_increment")
            pkc = self._pk_cols[0]
            for row, rid in zip(rows, rowids):
                if auto_col and row.get(auto_col) is not None:
                    self._auto_incr = max(self._auto_incr or 0,
                                          int(row[auto_col]))
                self._pk_put(int(rid), row[pkc], row, insert=True)
                if tctx is not None:
                    tctx.undo.append((int(rid), None))
            self._mvcc_stamp_new(rowids, tctx)
            return rowids

    def _write_hot(self, recs: list[dict], tctx: Optional[TxnContext]):
        if not recs:
            return
        if tctx is not None:
            # in-txn rows always buffer (that's where the row LOCKS live);
            # TxnContext.commit drops the buffer for non-durable stores
            for rec in recs:
                tctx.row_txn.put_row(rec)
            return
        if self.replicated is not None:
            # autocommit DML on a replicated table: quorum-commit the batch
            # through raft BEFORE the column tier reflects it (the dml_1pc
            # path, region.cpp:2301); no quorum -> the statement fails
            kc, rc = self.row_table.key_codec, self.row_table.row_codec
            ops = [(0, kc.encode_one(rec), rc.encode(rec)) for rec in recs]
            sink = getattr(self, "binlog_sink", None)
            if sink is not None:
                guard = getattr(self, "binlog_db", None)
                from .binlog_regions import DistributedBinlog

                table_key = f"{self.info.database}.{self.info.name}"
                if guard is not None:
                    # THIS table's retry lock held across the drain-check
                    # AND the append: a concurrent txn flush can no longer
                    # queue a batch for this table between our check and our
                    # write (the release-to-append race of the old global
                    # queue).  Per-table lock, so only same-table CDC
                    # serializes — which the stream-order contract requires
                    # anyway — and other tables' commits proceed in parallel
                    rq = guard.binlog_retry_queue(table_key)
                    with rq.mu:
                        if rq.q:
                            # queued CDC batches of earlier (txn-path)
                            # commits must land before this autocommit
                            # event or the table's stream reorders
                            guard._drain_rq_locked(rq, table_key, sink)
                        if rq.q:
                            # this table's binlog region is still down:
                            # appending now would jump the queue.  Commit
                            # the data and queue the event BEHIND the older
                            # batch — the txn path's discipline
                            # (session._flush_txn_binlog)
                            self.replicated.write_ops(ops)
                            guard._queue_rq_locked(
                                rq, DistributedBinlog.events_of(recs))
                            return
                        # distributed binlog: the CDC event rides the
                        # data's own cross-tier 2PC — present iff the data
                        # committed (storage/binlog_regions)
                        sink.write_with_data(
                            self.replicated, ops, table_key,
                            DistributedBinlog.events_of(recs))
                        return
                sink.write_with_data(
                    self.replicated, ops, table_key,
                    DistributedBinlog.events_of(recs))
            else:
                self.replicated.write_ops(ops)
            return
        if self.wal_path is None:
            return      # non-durable autocommit: nothing would ever read it
        kc, rc = self.row_table.key_codec, self.row_table.row_codec
        self.row_table.write_batch(
            [(0, kc.encode_one(rec), rc.encode(rec)) for rec in recs])

    def truncate(self):
        """DDL-grade wipe: resets regions AND the row tier/WAL (TRUNCATE is
        an implicit commit; it is never part of a transaction).  Durable
        stores rewrite the Parquet checkpoint too, or the truncated rows
        would resurrect on restart."""
        with self._lock:
            if self._writer is not None:
                raise ConflictError("TRUNCATE while a transaction is open")
            if self.replicated is not None:
                # the wipe must replicate, or a rebuild from the raft tier
                # would resurrect the rows; region retirement keeps it
                # O(regions) instead of per-row tombstones living forever
                self.replicated.truncate()
            self._mutations += 1
            self._pk_stale = True
            self.regions = [Region(self._alloc_region_id(),
                                   self.arrow_schema.empty_table())]
            # TRUNCATE is a version horizon: prior stamps and history
            # describe an image that no longer exists
            self._mvcc.reset()
            self._reset_wal()
            if self.durable_dir:
                self.save_parquet(self.durable_dir)

    def _reset_wal(self):
        path = self.wal_path
        if path and os.path.exists(path):
            self.row_table = None
            os.remove(path)
        self._build_row_tier(path)

    def _maybe_split(self, region: Region):
        """Row-count split (the reference splits oversized regions,
        region.cpp:4472; here a plain row-range cut, no raft catch-up)."""
        while region.num_rows > self.region_rows:
            keep = region.data.slice(0, self.region_rows)
            rest = region.data.slice(self.region_rows)
            keep_ids = region.rowids[:self.region_rows]
            rest_ids = region.rowids[self.region_rows:]
            region.data = keep.combine_chunks()
            region.rowids = keep_ids
            region.version += 1
            new = Region(self._alloc_region_id(), rest.combine_chunks(),
                         rest_ids, part=region.part)
            self.regions.append(new)
            region = new

    def alter_schema(self, new_schema: Schema):
        """Online schema change (reference: column DDL via the DDLManager;
        here: rewrite region tables to the new arrow schema — added columns
        fill NULL, dropped columns vanish).  The row tier resets (its value
        encoding is schema-bound): ALTER implies a checkpoint boundary."""
        with self._lock:
            if self._writer is not None:
                raise ConflictError("ALTER while a transaction is open")
            self._mutations += 1
            self._pk_stale = True
            # history rows carry the OLD schema's columns; rewriting them
            # is not worth it (ALTER is a checkpoint boundary like the WAL
            # reset below) — snapshots pinned before the ALTER re-read the
            # post-ALTER image, exactly like the pre-MVCC engine
            self._mvcc.reset()
            self.info.schema = new_schema
            self.info.version += 1
            self.arrow_schema = schema_to_arrow(new_schema)
            for r in self.regions:
                r.data = _coerce(r.data, self.arrow_schema)
                r.version += 1
            # the WAL's value encoding is schema-bound, so ALTER is a
            # checkpoint boundary: flush the rewritten cold state FIRST or
            # committed hot deltas since the last checkpoint would vanish
            if self.durable_dir:
                self.save_parquet(self.durable_dir)
            self._reset_wal()
            if self.replicated is not None:
                # the replicated row encoding is schema-bound too: retire
                # the old-encoding regions and re-replicate the rewritten
                # rows, or recovery would decode bytes with the wrong codec
                kc, rc = self.row_table.key_codec, self.row_table.row_codec
                ops = [(0, kc.encode_one({ROWID: int(rid)}),
                        rc.encode(dict(row, **{ROWID: int(rid)})))
                       for r in self.regions
                       for row, rid in zip(r.data.to_pylist(), r.rowids)]
                self.replicated.reset_schema(self._row_schema(), ops)
            if self._pk_cols:
                missing = [c for c in self._pk_cols if c not in new_schema]
                if missing:
                    self._pk_cols = None
                    self._pk_codec = None
                    self._pk_index = None
                else:
                    self._pk_codec = KeyCodec(new_schema, self._pk_cols)
                    self._pk_index = None

    def purge_expired(self, ttl_column: str, expire_before) -> int:
        """TTL purge (reference: TTL delete loops, store.cpp:46-48 timers +
        ttl_delete_node): delete rows whose ttl_column < expire_before."""
        import pyarrow.compute as pc

        def mask_fn(t: pa.Table):
            col = t.column(ttl_column)
            return np.asarray(pc.less(col, pa.scalar(expire_before)).fill_null(False))

        return self.delete_where(mask_fn)

    # -- persistence ----------------------------------------------------
    def save_parquet(self, directory: str):
        os.makedirs(directory, exist_ok=True)
        with self._lock:
            for f in os.listdir(directory):
                if f.endswith(".parquet"):
                    os.remove(os.path.join(directory, f))
            for r in self.regions:
                t = r.data.append_column(ROWID, pa.array(r.rowids, pa.int64()))
                suffix = f"_p{r.part}" if r.part >= 0 else ""
                pq.write_table(t, os.path.join(
                    directory, f"region_{r.region_id}{suffix}.parquet"))

    def load_parquet(self, directory: str):
        files = sorted(f for f in os.listdir(directory) if f.endswith(".parquet"))
        with self._lock:
            self._mutations += 1
            self._pk_stale = True
            self._mvcc.reset()      # the image is replaced wholesale
            self.regions = []
            for f in files:
                t = pq.read_table(os.path.join(directory, f))
                if ROWID in t.column_names:
                    rowids = np.asarray(t.column(ROWID).to_numpy(
                        zero_copy_only=False), np.int64)
                    t = t.drop_columns([ROWID])
                else:
                    rowids = self._alloc_rowids(t.num_rows)
                if len(rowids):
                    self._next_rowid = max(self._next_rowid,
                                           int(rowids.max()) + 1)
                part = -1
                stem = f[:-len(".parquet")]
                if "_p" in stem:
                    try:
                        part = int(stem.rsplit("_p", 1)[1])
                    except ValueError:
                        part = -1
                self.regions.append(Region(self._alloc_region_id(),
                                           _coerce(t, self.arrow_schema),
                                           rowids, part=part))
            if not self.regions:
                self.regions = [Region(self._alloc_region_id(),
                                       self.arrow_schema.empty_table())]


def _dict_domain(d) -> int:
    """The ``dict_size`` statistic: the codes a string column can hold — its
    dictionary's size, or, once it grows, the capacity of its order keys,
    so a dense GROUP BY domain outlives the appends inside it."""
    from ..column.dictionary import _pow2

    if d is None:
        return 0
    if not d.tailed:
        return len(d)
    return _pow2(len(d) + 1)


# rows compared at a time by ``_non_decreasing``: a column out of order
# says so within its first piece, whatever its length
_ORDER_PIECE = 1 << 20


def _non_decreasing(col) -> bool:
    """The ``ordered`` statistic: the column holds no NULL and never steps
    down in image order (the snapshot's: ``device_table_batch`` is the
    snapshot plus a dead tail), so a GROUP BY on it finds equal keys in
    adjacent rows (plan/planner._streams).  One comparison pass, in pieces,
    left at the first step down."""
    if col.null_count:
        return False
    last = None
    for chunk in col.chunks:
        whole = chunk.to_numpy(zero_copy_only=False)
        for at in range(0, len(whole), _ORDER_PIECE):
            a = whole[at:at + _ORDER_PIECE]
            if (last is not None and a[0] < last) \
                    or bool((a[1:] < a[:-1]).any()):
                return False
            last = a[-1]
    return True


def _coerce(table: pa.Table, schema: pa.Schema) -> pa.Table:
    if table.schema == schema:
        return table
    cols = []
    for f in schema:
        if f.name not in table.column_names:
            cols.append(pa.nulls(table.num_rows, f.type))
        else:
            cols.append(table.column(f.name).cast(f.type))
    return pa.table(cols, schema=schema)


# rank visible at import: docs/LINT.md's rank table is pinned against the
# runtime registry by test_lint.py without building a store
from ..analysis.runtime import LOCK_RANKS as _LOCK_RANKS  # noqa: E402

_LOCK_RANKS.setdefault("store.table_lock", TableStore.RANK)
