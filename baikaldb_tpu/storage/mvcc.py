"""MVCC + snapshot-read primitives: TSO client, version state, pins, GC.

The reference's HTAP core is a timestamp oracle on the meta raft group
(tso_state_machine — the TiDB-PD hybrid physical+logical design) feeding
MVCC snapshot reads: every committed row version carries a ``commit_ts``,
a delete stamps a tombstone ts, and a long analytical query pins one
snapshot timestamp so it sees exactly the state committed at that instant
while OLTP writes keep flowing.  This module is the engine-side half:

- ``TsoClient`` — the cached-range allocator over any grant source.  A
  hybrid timestamp is ``physical_ms << 18 | logical`` (meta/service.Tso),
  so a grant of N *contiguous* timestamps is the plain integer interval
  ``[first, first+N)``: logical overflow carries into the physical bits by
  ordinary integer arithmetic, exactly the carry ``Tso.gen_at`` performs.
  One raft propose therefore persists a whole batch
  (``tso_batch_size``); allocation is an in-memory bump until the range
  exhausts, and monotonicity across meta leader failover is the raft
  group's save-ahead lease (``Tso._save_ahead_ms`` riding the meta
  snapshot), not anything this client must remember.
- ``MvccState`` — per-table version bookkeeping kept BESIDE the resident
  Arrow image, never inside it: the store's ``Region.data`` stays
  physically latest (the ``mvcc=0`` off-switch and the no-concurrent-write
  fast path are bit-identical for free).  ``live_cts`` maps rowid ->
  commit_ts for every row written one at a time since the table's last
  reset whose stamp GC has not settled; a bulk load (``insert_arrow``:
  contiguous fresh rowids) is one entry of ``runs``, ``(first_rowid,
  stop_rowid, commit_ts)``, whatever its size — 100 M loaded rows are one
  tuple, not 100 M dict entries.  A row's stamp is the dict's, else its
  run's, else 0 = visible to every snapshot.  ``history`` holds dead
  versions as
  ``(row_values, commit_ts, delete_ts)``.  Uncommitted rows carry the
  ``PENDING`` sentinel (MAX_TS — invisible to every real snapshot) and are
  restamped with ONE decide-time commit_ts at transaction commit.  Beside
  the dict the state keeps a summary — ``high_water``, an upper bound on
  the largest committed live stamp, ``pending``, the rowids stamped
  PENDING, and ``pending_runs``, how many runs are — kept at every write
  hook, so a pinned read learns "nothing live is newer than my snapshot"
  in O(1) instead of walking the stamps.
- ``SnapshotRegistry`` — live pins (explicit ``SET SNAPSHOT`` and
  automatic analytical pins) feeding the GC watermark: nothing at or
  above the oldest unexpired pin is ever reclaimed.
- ``visibility_mask`` — the device-side visibility predicate, evaluated
  as a vectorized sel-mask INSIDE the jitted plan (*Query Processing on
  Tensor Computation Runtimes*: keep the versioned read path in the same
  kernels, not a host-side row filter).
- ``MvccGcThread`` — optional background sweeper; the engine also sweeps
  opportunistically at commit seams, so tests and embedded use need no
  thread.
"""

from __future__ import annotations

import threading
import time
import weakref
from bisect import bisect_right

import jax.numpy as jnp
import numpy as np

from ..analysis.runtime import LOCK_RANKS, GuardedLock
from ..chaos import failpoint
from ..utils import metrics
from ..utils.flags import FLAGS, define

define("mvcc", True,
       "stamp commit timestamps on DML and serve pinned snapshot reads "
       "(SET SNAPSHOT / automatic analytical pins); 0 = versionless "
       "stores, bit-identical to the pre-MVCC engine")
define("tso_batch_size", 64,
       "timestamps granted per TSO range propose: the client bumps "
       "in-memory inside the granted range and pays one meta raft "
       "round-trip per refill")
define("mvcc_gc_interval_s", 30.0,
       "background MVCC GC sweep period (MvccGcThread; the engine also "
       "sweeps opportunistically at commit seams)")
define("snapshot_max_age_s", 300.0,
       "pins older than this stop holding the GC watermark: a forgotten "
       "SET SNAPSHOT session bounds version retention instead of "
       "pinning history forever")

# TSO + MVCC observability (SHOW STATUS tso.* / mvcc.* rows ride
# REGISTRY.expose() automatically)
tso_allocations = metrics.Counter("tso.allocations")
tso_batch_refills = metrics.Counter("tso.batch_refills")
mvcc_gc_reclaimed = metrics.Counter("mvcc.gc_reclaimed")
# how a pinned read's two "has this table moved past my snapshot?" checks
# (TableStore.mvcc_needs_versioned / snapshot_versions) were answered
mvcc_quiet_checks = metrics.Counter("mvcc.quiet_checks")
mvcc_versioned_checks = metrics.Counter("mvcc.versioned_checks")
# bulk appends recorded as one run of rowids (MvccState.stamp_range); no
# dot in the name: a loader's SHOW STATUS check reads names up to the first
mvcc_range_stamps = metrics.Counter("mvcc_range_stamps")

#: commit_ts sentinel for uncommitted (in-transaction) rows: above every
#: real timestamp, so no snapshot ever admits a pending version.  Rollback
#: restores the captured MVCC preimage, so a PENDING stamp never leaks.
MAX_TS = (1 << 63) - 1
PENDING = MAX_TS


def visibility_mask(cts, dts, snap_ts):
    """The MVCC visibility predicate as a vectorized device mask.

    A version is visible at ``snap_ts`` iff it committed at or before the
    snapshot and was not yet superseded/deleted: ``commit_ts <= snap_ts <
    delete_ts``.  Newest-wins is structural, not computed: each rowid has
    exactly one version alive in any ``[cts, dts)`` interval because an
    update closes the old version's interval at the new version's cts.

    Pure jnp on int64 inputs (x64 is enabled engine-wide) — this runs
    INSIDE jitted plans as a sel-mask, so it must stay free of host
    syncs and metric writes (pinned jit-clean in tests/test_lint.py).
    """
    return jnp.logical_and(cts <= snap_ts, dts > snap_ts)


class TsoError(RuntimeError):
    """A timestamp could not be allocated (grant source unavailable)."""


class TsoClient:
    """Monotonic timestamp allocator over batched raft-persisted grants.

    ``gen``: a callable ``(count) -> first_ts`` granting ``count``
    contiguous timestamps — ``ReplicatedMeta.tso.gen`` (raft-persisted),
    ``MetaService.tso.gen`` (fleet mode), or None for a process-local
    ``Tso`` (embedded single-node engine).  The client caches the granted
    interval ``[next, limit)`` and serves allocations with one lock-bump;
    a refill proposes ``tso_batch_size`` at once.

    The ``tso.allocate`` failpoint models a grant response lost in flight:
    the granted range is burned (never handed out) and the client
    re-proposes — monotonicity holds because the source never re-issues a
    granted range.
    """

    RANK = 15   # above store.table_lock (10): commit stamping allocates
                # under the table lock; nothing locks tables under us

    def __init__(self, gen=None):
        if gen is None:
            from ..meta.service import Tso
            gen = Tso().gen
        self._gen = gen
        self._mu = GuardedLock("mvcc.tso_mu", rank=self.RANK)
        self._next = 0      # next ts to hand out
        self._limit = 0     # one past the granted range
        self._last = 0      # newest ts ever returned (monotonicity check)

    def next_ts(self, count: int = 1) -> int:
        """First of ``count`` contiguous timestamps (count=1: the ts)."""
        count = max(1, int(count))
        with self._mu:
            if self._next + count > self._limit:
                self._refill(count)
            ts = self._next
            self._next += count
            tso_allocations.add(count)
            self._last = self._next - 1
            return ts

    def last_ts(self) -> int:
        """Newest timestamp this client has handed out (0 = none yet)."""
        with self._mu:
            return self._last

    def _refill(self, count: int) -> None:
        batch = max(count, int(FLAGS.tso_batch_size))
        first = self._gen(batch)
        if failpoint.ENABLED and failpoint.hit("tso.allocate", batch=batch):
            # drop: the grant response never arrived — that range is
            # burned; propose again (the source's persisted max makes the
            # second grant strictly higher, never a reissue)
            first = self._gen(batch)
        if first is None:
            raise TsoError("TSO grant source returned no range")
        first = int(first)
        if first < self._limit:
            # a grant below an already-consumed range would fork time —
            # refuse loudly rather than hand out a duplicate timestamp
            raise TsoError(
                f"TSO range regressed: granted {first} below consumed "
                f"limit {self._limit}")
        self._next = first
        self._limit = first + batch
        tso_batch_refills.add(1)


class MvccState:
    """Per-table version bookkeeping beside the resident Arrow image.

    Mutated only under the owning TableStore's table lock (the store
    passes itself in for every call) — no lock of its own, so it adds
    nothing to the lock order.  A live row's stamp is kept in one of two
    places, read through :meth:`stamp_of` / :meth:`stamps_for`:
    ``live_cts``, rowid -> commit_ts for every row stamped one at a time
    since the last reset (autocommit DML, committed transactions), and
    ``runs``, ``(first_rowid, stop_rowid, commit_ts)`` in rowid order,
    one for each bulk append (``stamp_range``) whatever its size.  The
    dict wins over a run (an UPDATE of a loaded row restamps that row);
    a rowid in neither reads 0: visible to every snapshot —
    truncate-reset state and stamps GC already settled.
    ``history``: dead versions as ``(row_values, commit_ts, delete_ts)``
    dicts in arrival order; a GC sweep drops entries whose delete_ts is at
    or below the watermark.

    The summary of the live stamps, kept by every write hook so the pinned
    read's "has the live image moved past my snapshot?" never walks them:
    ``high_water`` is an upper bound on the largest non-PENDING stamp,
    ``pending`` the rowids of the dict stamped PENDING and
    ``pending_runs`` the number of runs that are.  The last two are
    exact.  ``high_water`` errs to one side only: ``record_dead`` may pop
    the row that held the maximum and the mark stays (finding the next
    one would be the walk again), so it can send a read to the versioned
    image that the walk would have served live — same rows either way —
    and never the reverse.  A GC sweep at or above the mark zeroes it,
    which makes it exact again.
    """

    __slots__ = ("live_cts", "runs", "history", "high_water", "pending",
                 "pending_runs", "reclaimed", "dead_high", "__weakref__")

    def __init__(self):
        self.live_cts: dict[int, int] = {}
        self.runs: list[tuple[int, int, int]] = []
        self.history: list[tuple[dict, int, int]] = []
        self.high_water = 0
        self.pending: set[int] = set()
        self.pending_runs = 0
        self.reclaimed = 0      # history versions GC has dropped, ever
        # the largest delete_ts in history (PENDING while an open
        # transaction's is there): no version is alive past it
        self.dead_high = 0
        _STATES.add(self)

    # -- write-path hooks (caller holds the table lock) -----------------
    def stamp(self, rowids, cts: int) -> None:
        lc = self.live_cts
        if cts == PENDING:
            pend = self.pending
            for rid in rowids:
                rid = int(rid)
                lc[rid] = cts
                pend.add(rid)
            return
        rid = None
        for rid in rowids:
            lc[int(rid)] = cts
        if self.pending:    # a committed stamp over a PENDING row settles it
            self.pending.difference_update(int(r) for r in rowids)
        if rid is not None and cts > self.high_water:
            self.high_water = cts

    def stamp_range(self, first: int, stop: int, cts: int) -> None:
        """Stamp the fresh contiguous rowids ``[first, stop)`` of one bulk
        append: one run, no per-row object."""
        if stop <= first:
            return
        run = (int(first), int(stop), cts)
        # rowids are handed out in rising order: the end, as a rule
        self.runs.insert(bisect_right(self.runs, run[0], key=_first), run)
        if cts == PENDING:
            self.pending_runs += 1
        elif cts > self.high_water:
            self.high_water = cts
        mvcc_range_stamps.add(1)

    def _run_stamp(self, rid: int) -> int:
        """The stamp of the run that holds ``rid``, else 0."""
        i = bisect_right(self.runs, rid, key=_first) - 1
        return self.runs[i][2] if i >= 0 and rid < self.runs[i][1] else 0

    def stamp_of(self, rid: int) -> int:
        """A live row's commit stamp: the dict's, else its run's, else 0."""
        cts = self.live_cts.get(rid)
        return self._run_stamp(rid) if cts is None else cts

    def stamps_for(self, rowids: np.ndarray) -> np.ndarray:
        """:meth:`stamp_of` over an int64 array of live rowids, without a
        Python step a row: a search of the run starts, then the dict's
        entries laid over by a search of its sorted keys."""
        cts = np.zeros(len(rowids), np.int64)
        if self.runs:
            first, stop, stamp = (np.array(c, np.int64)
                                  for c in zip(*self.runs))
            i = np.searchsorted(first, rowids, side="right") - 1
            hit = (i >= 0) & (rowids < stop[np.maximum(i, 0)])
            cts[hit] = stamp[i[hit]]
        lc = self.live_cts
        if lc:
            keys = np.fromiter(lc.keys(), np.int64, count=len(lc))
            vals = np.fromiter(lc.values(), np.int64, count=len(lc))
            order = np.argsort(keys, kind="stable")
            keys, vals = keys[order], vals[order]
            i = np.minimum(np.searchsorted(keys, rowids), len(keys) - 1)
            hit = keys[i] == rowids
            cts[hit] = vals[i[hit]]
        return cts

    def live_stamps(self) -> int:
        """Rows that carry a stamp GC has not settled (a row restamped
        over its run counts twice)."""
        return len(self.live_cts) + sum(r[1] - r[0] for r in self.runs)

    def record_dead(self, rows: list[dict], rowids, dts: int) -> None:
        """Old versions of deleted/updated rows enter history."""
        lc = self.live_cts
        hist = self.history
        pend = self.pending
        for row, rid in zip(rows, rowids):
            rid = int(rid)
            cts = lc.pop(rid, None)
            if cts is None:
                cts = self._run_stamp(rid)
            elif cts == PENDING:
                pend.discard(rid)
            hist.append((row, cts, dts))
        if dts > self.dead_high:
            self.dead_high = dts

    def restamp_pending(self, commit_ts: int) -> int:
        """Replace every PENDING stamp with the decide-time commit_ts —
        the one-timestamp-per-transaction contract.  Single-writer (the
        store's writer lease) means every pending stamp belongs to the
        committing transaction.  Returns the number restamped."""
        n = len(self.pending)
        if n:
            lc = self.live_cts
            for rid in self.pending:
                lc[rid] = commit_ts
            self.pending.clear()
        if self.pending_runs:
            for i, (first, stop, c) in enumerate(self.runs):
                if c == PENDING:
                    self.runs[i] = (first, stop, commit_ts)
                    n += stop - first
            self.pending_runs = 0
        if n and commit_ts > self.high_water:
            self.high_water = commit_ts
        if self.dead_high == PENDING:
            for i, (row, c, d) in enumerate(self.history):
                if d == PENDING:
                    self.history[i] = (row, c, commit_ts)
                    n += 1
            self._settle_dead_high()
        return n

    def _settle_dead_high(self) -> None:
        self.dead_high = max((h[2] for h in self.history), default=0)

    # -- preimage (transaction rollback) --------------------------------
    def capture(self) -> tuple:
        # the history mark counts from the table's first version, not from
        # the list's current head: a GC sweep while the transaction is
        # open drops older entries (never the transaction's own, whose
        # delete_ts is PENDING) and would shift a plain length
        return (dict(self.live_cts), self.reclaimed + len(self.history),
                self.high_water, set(self.pending), list(self.runs),
                self.pending_runs)

    def restore(self, pre: tuple) -> None:
        live, hist_mark, high_water, pending, runs, pending_runs = pre
        self.live_cts = dict(live)
        del self.history[max(hist_mark - self.reclaimed, 0):]
        self.high_water = high_water
        self.pending = set(pending)
        self.runs = list(runs)
        self.pending_runs = pending_runs
        self._settle_dead_high()

    def reset(self) -> None:
        """Table image replaced wholesale (truncate / load / DDL rebuild):
        all prior stamps and versions are meaningless."""
        self.live_cts.clear()
        self.runs.clear()
        self.reclaimed += len(self.history)
        self.history.clear()
        self.high_water = 0
        self.pending.clear()
        self.pending_runs = 0
        self.dead_high = 0

    # -- read-path helpers ----------------------------------------------
    def versions_at(self, snap_ts: int) -> list[tuple[dict, int, int]]:
        """History versions alive at ``snap_ts`` (cts <= snap < dts)."""
        if self.dead_high <= snap_ts:
            return []
        return [h for h in self.history if h[1] <= snap_ts < h[2]]

    def live_newer_than(self, snap_ts: int) -> bool:
        """May some live row carry a stamp above ``snap_ts`` (a commit
        after the snapshot, or an open transaction's PENDING row)?  O(1),
        from the summary: False is exact, True may be the popped-maximum
        upper bound (class docstring)."""
        return bool(self.pending) or bool(self.pending_runs) \
            or self.high_water > snap_ts

    def gc(self, watermark: int) -> int:
        """Drop history below the watermark and settle old live stamps.

        A history version is reclaimable iff its delete_ts is at or below
        the watermark: visibility needs ``dts > snap``, and the watermark
        lower-bounds every current and future pin, so nothing pinned can
        still see it.  A live stamp at or below the watermark degrades to
        the implicit 0 (visible to everything that can still pin) and
        leaves the dict; a run at or below it is dropped whole.  Returns
        reclaimed version count.
        """
        if failpoint.ENABLED and failpoint.hit("mvcc.gc",
                                               watermark=watermark):
            return 0    # drop: this sweep is skipped (a wedged GC)
        before = len(self.history)
        if before:
            self.history = [h for h in self.history if h[2] > watermark]
        settled = [rid for rid, c in self.live_cts.items()
                   if c <= watermark]
        for rid in settled:
            del self.live_cts[rid]
        if self.runs:
            self.runs = [r for r in self.runs if r[2] > watermark]
        if self.high_water <= watermark:
            self.high_water = 0     # every committed stamp just settled
        reclaimed = before - len(self.history)
        self._settle_dead_high()
        self.reclaimed += reclaimed
        if reclaimed:
            mvcc_gc_reclaimed.add(reclaimed)
        return reclaimed


def _first(run: tuple) -> int:
    return run[0]


class SnapshotRegistry:
    """Live snapshot pins: the GC watermark source + the introspection
    surface behind information_schema.snapshots."""

    RANK = 12   # between store.table_lock (10) and mvcc.tso_mu (15):
                # pin() allocates a ts (takes the tso lock) under us; GC
                # computes the watermark here, RELEASES, then sweeps
                # per-table under each store's lock — never nested

    def __init__(self):
        self._mu = GuardedLock("mvcc.registry_mu", rank=self.RANK)
        self._pins: dict[int, dict] = {}
        self._seq = 0
        _REGISTRIES.add(self)

    def pin(self, ts: int, query: str = "", holder: str = "") -> int:
        """Register a pin at ``ts``; returns the pin id for unpin().

        The ``snapshot.pin`` failpoint refuses the pin (drop) — an
        automatic analytical pin degrades to an unpinned read; an
        explicit SET SNAPSHOT surfaces the refusal to the client.
        """
        if failpoint.ENABLED and failpoint.hit("snapshot.pin", ts=ts):
            raise SnapshotRefused("snapshot.pin dropped by failpoint")
        with self._mu:
            self._seq += 1
            pid = self._seq
            self._pins[pid] = {"ts": int(ts), "pinned_at": time.time(),
                               "query": query, "holder": holder}
            return pid

    def unpin(self, pin_id: int) -> None:
        with self._mu:
            self._pins.pop(pin_id, None)

    def _unexpired(self) -> list[dict]:
        horizon = time.time() - float(FLAGS.snapshot_max_age_s)
        return [p for p in self._pins.values() if p["pinned_at"] >= horizon]

    def oldest(self) -> int:
        """Oldest unexpired pinned ts (0 = no live pins)."""
        with self._mu:
            return min((p["ts"] for p in self._unexpired()), default=0)

    def watermark(self, now_ts: int) -> int:
        """Reclaim bound: everything strictly below it is dead to every
        current AND future pin (future pins get ts > now_ts)."""
        with self._mu:
            return min((p["ts"] for p in self._unexpired()),
                       default=int(now_ts))

    def describe(self) -> list[dict]:
        """Rows for information_schema.snapshots (oldest pin first)."""
        now = time.time()
        with self._mu:
            return sorted(
                ({"snapshot_ts": p["ts"],
                  "age_ms": int((now - p["pinned_at"]) * 1e3),
                  "query": p["query"], "holder": p["holder"]}
                 for p in self._pins.values()),
                key=lambda r: r["snapshot_ts"])


class SnapshotRefused(RuntimeError):
    """A snapshot pin was refused (chaos injection or shutdown)."""


class MvccRuntime:
    """Per-Database MVCC plane: one shared TSO client + the pin registry.

    ``gen``: the TSO grant source (fleet mode passes the meta service's
    oracle so every frontend on the fleet draws from one clock; embedded
    mode defaults to a process-local Tso).
    """

    def __init__(self, gen=None):
        self.tso = TsoClient(gen)
        self.snapshots = SnapshotRegistry()
        self._gc_thread: MvccGcThread | None = None

    def now_ts(self) -> int:
        """A fresh timestamp: everything committed so far is below it."""
        return self.tso.next_ts()

    def gc(self, stores) -> int:
        """One watermark-driven sweep over ``stores`` (TableStore iter).

        The watermark is computed first, under the registry lock alone;
        each table then sweeps under its own lock — the registry lock is
        never held across a table lock (rank 12 vs 10 would trip the
        lockset witness, by design).
        """
        wm = self.snapshots.watermark(self.tso.last_ts())
        reclaimed = 0
        for st in list(stores):
            reclaimed += st.mvcc_gc(wm)
        return reclaimed

    def start_gc(self, db) -> "MvccGcThread":
        """Start (once) the background sweeper over ``db``'s stores."""
        if self._gc_thread is None:
            self._gc_thread = MvccGcThread(self, db)
            self._gc_thread.start()
        return self._gc_thread

    def stop_gc(self) -> None:
        if self._gc_thread is not None:
            self._gc_thread.stop()
            self._gc_thread = None


class MvccGcThread(threading.Thread):
    """Periodic watermark-driven GC (``mvcc_gc_interval_s``).

    Explicitly started (``MvccRuntime.start_gc``) — never implicitly, so
    the hundreds of short-lived embedded Databases tests build don't each
    leak a thread.  Commit-seam opportunistic sweeps keep version debt
    bounded without it; the thread exists for long-lived serving
    processes where commits may go quiet while pins expire.
    """

    def __init__(self, runtime: MvccRuntime, db):
        super().__init__(name="mvcc-gc", daemon=True)
        self._runtime = runtime
        self._db = weakref.ref(db)
        self._stop = threading.Event()

    def run(self) -> None:
        while not self._stop.wait(float(FLAGS.mvcc_gc_interval_s)):
            db = self._db()
            if db is None:
                return
            try:
                self._runtime.gc(db.stores.values())
            except Exception:   # noqa: BLE001 — sweep must never die
                metrics.count_swallowed("mvcc.gc")

    def stop(self) -> None:
        self._stop.set()
        self.join(timeout=2.0)


# engine-wide introspection: every state / registry alive in the process
# (weak — a dropped Database releases its tables' version debt)
_STATES: "weakref.WeakSet[MvccState]" = weakref.WeakSet()
_REGISTRIES: "weakref.WeakSet[SnapshotRegistry]" = weakref.WeakSet()


def _live_versions() -> int:
    return sum(len(s.history) + s.live_stamps() for s in list(_STATES))


def _oldest_pin() -> int:
    return min((ts for ts in (r.oldest() for r in list(_REGISTRIES))
                if ts), default=0)


metrics.Gauge("mvcc.live_versions", fn=_live_versions)
metrics.Gauge("mvcc.oldest_pin", fn=_oldest_pin)

# module-level rank registration (docs/LINT.md rank table is pinned
# against this registry by tests/test_lint.py)
LOCK_RANKS.setdefault("mvcc.registry_mu", SnapshotRegistry.RANK)
LOCK_RANKS.setdefault("mvcc.tso_mu", TsoClient.RANK)
