"""Cross-query batched dispatch: coalesce concurrent point queries into one
device batch per tick.

PR 3's auto-parameterized plan cache means thousands of concurrent point
queries of the same statement shape share ONE compiled executable — but each
still paid its own device dispatch, egress densify, and GIL round-trip.
*Tailwind* (PAPERS.md) frames the fix: admit concurrent queries into a
combiner that batches them onto the accelerator; *Query Processing on Tensor
Computation Runtimes* motivates keeping the hot path a handful of LARGE
tensor-runtime launches instead of per-client small ones.

The dispatcher sits between the session layer and the jitted plan executor:

- **Group key**: queries coalesce when they hit the same plan-cache entry
  (the paramize lookup key: canonical statement structure + pinned values),
  the same scan shapes (table, version, capacity bucket — PR 1's buckets),
  and the same plan signature.  Members differ ONLY in their bound param
  feeds, so one program serves the whole group.
- **Inline bypass**: a query whose group is idle (nothing queued, nothing
  in flight) executes inline on its own thread — single-in-flight queries
  pay zero added latency.  Only genuine concurrency queues.
- **Combiner tick**: the first queued waiter becomes the group's leader and
  sleeps for ``batch_dispatch_tick_ms`` (or until the group fills to
  ``batch_dispatch_max_group``), then stacks the pending param feeds along
  a new leading client axis, pads the group to a power-of-two size (so
  group-size variation forks O(log max_group) executables, not O(sizes)),
  and runs ONE ``jax.vmap``-batched executable: every lane evaluates the
  same plan against the same table batches with its own params.
- **Scatter-back**: the per-lane egress compact is FUSED into the batched
  program (``exec.egress.gather_live``), so a tick costs one jit call plus
  ONE fused device->host transfer; ``exec.egress.rebuild_clients`` then
  slices per-client host batches out of it with plain numpy — bit-identical
  to what a serial run's ``_egress_compact`` would produce.
- **Admission**: the per-group queue is bounded (``batch_dispatch_queue_max``;
  overflow raises the typed :class:`DispatchOverload`), and the session
  layer's qos gate (utils/qos.py, now per-user/per-table token buckets)
  sheds load BEFORE anything enqueues — overload degrades to bounded
  queueing + typed rejection, never collapse.
- **Fallback valve**: any combiner failure (a plan the vmap lowering cannot
  express, an injected ``dispatch.combine`` fault) lands every member —
  leader included — back on its own inline execution path, preserving
  exactly-once results per client.

Trace seams: ``batch.enqueue`` (waiter-side, duration = queue wait),
``batch.combine`` (leader, group/padded/compiled attrs), ``batch.scatter``.
All ride obs/trace.py's no-op singleton when tracing is off.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict

import numpy as np

from ..obs import progress, trace
from ..utils import metrics
from ..utils.flags import FLAGS, define
from ..utils.qos import RejectedError

define("batch_dispatch", True,
       "cross-query batched dispatch: concurrent point queries hitting the "
       "same plan-cache group run as ONE vmapped device batch per combiner "
       "tick (param feeds stacked along a leading client axis); single-in-"
       "flight queries bypass the queue entirely.  0 restores per-query "
       "dispatch")
define("batch_dispatch_tick_ms", 1.5,
       "combiner latency budget: how long a group leader waits for more "
       "members before running the batch (the admission tick)")
define("batch_dispatch_max_group", 256,
       "combine at most this many queries per tick; a full group fires "
       "immediately without waiting out the tick")
define("batch_dispatch_queue_max", 1024,
       "bounded per-group queue: arrivals beyond this many waiting queries "
       "get a typed DispatchOverload rejection instead of queueing "
       "unboundedly")

# waiter safety net: a member falls back to inline execution if its combine
# result does not arrive within this window (covers a leader paying a
# multi-second first compile)
WAIT_S = 120.0
# batched executables kept by the dispatcher (distinct (statement group,
# shapes, padded group size) triples)
CACHE_ENTRIES = 64
# static per-lane scatter budget: the batched executable returns up to this
# many live rows per client (the egress compact fused into the program); a
# lane returning more re-runs inline
SCATTER_ROWS = 128


class DispatchOverload(RejectedError):
    """The group's queue is full: typed admission rejection (the reference's
    reject strategy under overload — the client sees a MySQL error, the
    server never queues unboundedly)."""


class CombineFallback(Exception):
    """Internal control flow: this member must execute inline (combiner
    failed / timed out / an injected fault abandoned the tick).  The session
    catches it and runs its own ``_run_plan``."""


# cached master switch (the per-SELECT eligibility check must not take the
# flag-registry lock; the ``tracing`` off-switch discipline)
_ON = bool(FLAGS.batch_dispatch)


def _refresh(value=None) -> None:
    global _ON
    _ON = bool(FLAGS.batch_dispatch if value is None else value)


FLAGS.on_change("batch_dispatch", _refresh)


def enabled() -> bool:
    return _ON


class _Waiter:
    """One queued query: its bound param feed + the rendezvous."""

    __slots__ = ("params", "done", "out", "err", "t0", "group")

    def __init__(self, params):
        self.params = params
        self.done = threading.Event()
        self.out = None             # compacted ColumnBatch on success
        self.err = None             # exception to re-raise on this thread
        self.t0 = time.perf_counter()
        self.group = 0              # occupancy, filled by the leader


class _Group:
    """Transient queue of waiters for one (statement, shapes) group; lives
    only while members wait — the leader pops it when the tick fires."""

    __slots__ = ("pending", "filled")

    def __init__(self):
        self.pending: list[_Waiter] = []
        self.filled = threading.Event()


class BatchDispatcher:
    """One per Database: engine-wide, so queries from DIFFERENT sessions
    (connections) coalesce — that is the whole point."""

    # ranked below store.table_lock(10): the combiner only holds its lock
    # for map bookkeeping — never across device work or store calls
    RANK = 4

    def __init__(self):
        # the lockset witness (debug_guards) asserts the dispatcher maps
        # are only touched under this lock
        from ..analysis.runtime import GuardedLock
        self._mu = GuardedLock("dispatch.combine_mu", rank=self.RANK)
        self._groups: dict = {}          # group_key -> _Group (queued only)
        self._inflight: dict = {}        # group_key -> runs in flight
        # ck_base -> the plan object every batched compile of this statement
        # group traces from (the first leader's; join-cap growth mutates it)
        self._plans: OrderedDict = OrderedDict()
        # (ck_base, padded_group) -> (jitted fn, raw, meta, publishable)
        # — LRU-bounded; ``publishable`` is the unjitted batched callable
        # the AOT publisher exports, None for AOT-loaded pairs
        self._compiled: OrderedDict = OrderedDict()
        # batched-executable keys whose AOT artifact's baked caps
        # overflowed on live data: never re-load them this process
        self._aot_bad: set = set()
        # exact group-size histogram for information_schema.dispatcher
        self.occupancy: dict[int, int] = {}

    # -- introspection (information_schema.dispatcher) ---------------------
    def queue_depth(self) -> int:
        with self._mu:
            return sum(len(g.pending) for g in self._groups.values())

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "queue_depth": sum(len(g.pending)
                                   for g in self._groups.values()),
                "live_groups": len(self._groups),
                "inflight": sum(self._inflight.values()),
                "occupancy": dict(self.occupancy),
                "compiled": len(self._compiled),
            }

    # -- admission ---------------------------------------------------------
    def run(self, run_inline, group_key, ck_base, entry, batches):
        """Execute one query through the dispatcher.

        ``run_inline``: zero-arg closure running the session's own
        ``_run_plan`` (the bypass and fallback path).  Returns the compacted
        result ColumnBatch.  Raises :class:`DispatchOverload` when the
        group's queue is full; :class:`CombineFallback` never escapes
        (handled internally by re-running inline)."""
        from ..expr.params import PARAMS_KEY
        with self._mu:
            g = self._groups.get(group_key)
            if g is None and not self._inflight.get(group_key):
                # idle group: run inline on this thread, zero added latency
                self._inflight[group_key] = 1
                w = None
                leader = False
            else:
                if g is None:
                    g = self._groups[group_key] = _Group()
                if len(g.pending) >= max(1, int(
                        FLAGS.batch_dispatch_queue_max)):
                    metrics.qos_rejections.add(1)
                    raise DispatchOverload(
                        "dispatcher queue full for this statement group "
                        f"({len(g.pending)} waiting)")
                w = _Waiter(batches[PARAMS_KEY])
                g.pending.append(w)
                leader = len(g.pending) == 1
                if len(g.pending) >= max(2, int(
                        FLAGS.batch_dispatch_max_group)):
                    # full group fires now AND rotates out of the registry,
                    # so later arrivals form a fresh group under a new
                    # leader — max_group is a per-tick cap, not a hint
                    g.filled.set()
                    if self._groups.get(group_key) is g:
                        del self._groups[group_key]
        if w is None:
            metrics.dispatch_inline.add(1)
            try:
                return run_inline()
            finally:
                self._release(group_key)
        if leader:
            return self._lead(g, group_key, ck_base, entry, batches,
                              run_inline)
        return self._wait(w, run_inline)

    def _release(self, group_key) -> None:
        with self._mu:
            n = self._inflight.get(group_key, 0) - 1
            if n > 0:
                self._inflight[group_key] = n
            else:
                self._inflight.pop(group_key, None)

    # -- member side -------------------------------------------------------
    def _wait(self, w: _Waiter, run_inline):
        qp = progress.current()
        with trace.span("batch.enqueue") as sp:
            # sliced wait: each slice is a progress beat and a KILL
            # cancellation point (the dispatch queue is a pure read path —
            # abandoning the rendezvous has no side effects; the leader's
            # combined run just carries one unread lane)
            deadline = time.perf_counter() + WAIT_S
            while True:
                remaining = deadline - time.perf_counter()
                ok = w.done.wait(timeout=min(0.05, max(0.0, remaining)))
                wait_ms = (time.perf_counter() - w.t0) * 1e3
                qp.beat(phase="exec.queued", queue_wait_ms=wait_ms)
                if ok or remaining <= 0:
                    break
            sp.set(queue_wait_ms=round(
                (time.perf_counter() - w.t0) * 1e3, 3), group=w.group)
        if not ok or isinstance(w.err, CombineFallback):
            metrics.dispatch_fallbacks.add(1)
            return run_inline()
        if w.err is not None:
            raise w.err
        return w.out

    # -- leader side -------------------------------------------------------
    def _lead(self, g_mine: _Group, group_key, ck_base, entry, batches,
              run_inline):
        # the tick: wait out the latency budget (or a full group) so
        # followers can pile on, then pop the group and combine
        g_mine.filled.wait(timeout=max(0.0, float(
            FLAGS.batch_dispatch_tick_ms)) / 1e3)
        with self._mu:
            if self._groups.get(group_key) is g_mine:
                del self._groups[group_key]
            ws = g_mine.pending
            self._inflight[group_key] = \
                self._inflight.get(group_key, 0) + 1
        try:
            now = time.perf_counter()
            G = len(ws)
            for m in ws:
                m.group = G
                metrics.queue_wait_ms.observe((now - m.t0) * 1e3)
            if G == 1:
                # nobody joined during the tick: plain inline run
                metrics.dispatch_inline.add(1)
                return run_inline()
            from ..chaos.failpoint import FailpointPanic
            try:
                outs = self._combine(ws, ck_base, entry, batches)
            except (Exception, FailpointPanic) as e:  # noqa: BLE001 — the
                #   valve: ANY combiner failure (incl. an injected
                #   FailpointPanic, which has no daemon to crash at the
                #   frontend seam) degrades every member to inline
                #   execution; exactly-once is preserved because no result
                #   was delivered yet.  KeyboardInterrupt/SystemExit flow.
                metrics.count_swallowed("dispatch.combine")
                fb = CombineFallback(f"{type(e).__name__}: {e}")
                for m in ws[1:]:
                    m.err = fb
                    m.done.set()
                metrics.dispatch_fallbacks.add(1)
                return run_inline()
            for m, out in zip(ws[1:], outs[1:]):
                m.out = out
                m.done.set()
            if isinstance(ws[0].err, CombineFallback):
                metrics.dispatch_fallbacks.add(1)   # own-lane overflow
                return run_inline()
            if ws[0].err is not None:
                raise ws[0].err     # this lane's own per-client error
            return outs[0]
        finally:
            self._release(group_key)

    def _combine(self, ws, ck_base, entry, batches):
        """Stack the group's param feeds, run ONE batched executable —
        plan evaluation AND the per-lane egress compact fused into a single
        jitted program (exec/egress.gather_live) — then rebuild per-client
        host batches from one fused transfer.  The leader's thread does all
        of it; under the GIL the combiner IS the serialization point, so
        its critical path must be a fixed handful of Python steps, not a
        per-client chain of eager device ops."""
        import jax

        from ..chaos import failpoint
        from ..expr.params import PARAMS_KEY
        from ..plan.nodes import ScalarSourceNode
        from ..plan.planner import PlanError
        from . import egress as egress_mod
        from .executor import compile_plan

        G = len(ws)
        gpad = max(2, 1 << (G - 1).bit_length())
        feeds = [m.params for m in ws] + [ws[0].params] * (gpad - G)
        # host-side stack: bind() leaves are numpy, so the whole group's
        # feed ships to the device in ONE transfer at the jit call below
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *feeds)
        table_batches = {k: v for k, v in batches.items()
                        if k != PARAMS_KEY}
        with self._mu:
            plan = self._plans.get(ck_base)
            if plan is None:
                self._plans[ck_base] = plan = entry["plan"]
                while len(self._plans) > CACHE_ENTRIES:
                    self._plans.popitem(last=False)
            self.occupancy[G] = self.occupancy.get(G, 0) + 1
        metrics.batched_groups.add(1)
        metrics.group_occupancy.observe(float(G))
        from ..utils import compilecache
        from . import caps
        from .executor import (AotRawShim, count_lowerings, flag_meta_of,
                               traced_extra)

        # AOT artifact identity for this batched program: the statement
        # group + plan signature (ck_base), the padded group size and
        # scatter budget, the input skeleton (incl. dictionary content)
        # and the topology.  Derived lazily — a warm tick that hits the
        # in-memory pair never pays the fingerprint walk.
        aot_key = None

        def get_aot_key():
            nonlocal aot_key
            if aot_key is None and compilecache.AOT.enabled():
                aot_key = compilecache.aot_key(
                    "batched", entry.get("plan_sig"),
                    (str(ck_base), gpad, SCATTER_ROWS),
                    compilecache.input_fingerprint((table_batches,
                                                    stacked)))
            return aot_key

        # AOT pairs pin the EXACT store versions they loaded under: jit
        # retraces when a dictionary's content changes (pytree aux), a
        # deserialized program cannot — in-bucket DML must re-derive the
        # artifact key instead of reusing a stale-dictionary executable
        vk = tuple(sorted(entry.get("versions", {}).items()))

        t0 = time.perf_counter()
        with trace.span("batch.combine", group=G, padded=gpad) as sp:
            if failpoint.ENABLED:
                if failpoint.hit("dispatch.combine", group=G):
                    # drop: abandon this tick — members fall back inline
                    raise CombineFallback("dispatch.combine dropped")
            for _ in range(int(FLAGS.join_retry_max) + 1):
                ck = (ck_base, gpad)
                with self._mu:
                    pair = self._compiled.get(ck)
                    if pair is not None and pair[3] is not None \
                            and pair[3] != vk:
                        del self._compiled[ck]      # stale AOT pair
                        pair = None
                    elif pair is not None:
                        self._compiled.move_to_end(ck)
                    # membership read under the same lock as the .add in
                    # the fallback path — combiner ticks race session
                    # threads here
                    aot_ok = aot_key not in self._aot_bad
                if pair is None and compilecache.AOT.enabled() \
                        and get_aot_key() is not None and aot_ok:
                    art = compilecache.AOT.load(aot_key)
                    if art is not None and isinstance(
                            (art.extra or {}).get("egress_meta"), tuple):
                        # the vmapped program + its egress column meta
                        # round-trip from the artifact: zero traces
                        pair = (lambda tb, sp_, _art=art: _art.run((tb, sp_)),
                                AotRawShim(art.flag_meta, art.extra),
                                [art.extra["egress_meta"]], vk)
                        with self._mu:
                            self._compiled[ck] = pair
                if pair is None:
                    raw = compile_plan(plan)
                    meta: list = []          # filled at trace time

                    def batched(tb, sp_, _raw=raw, _meta=meta, _cap=SCATTER_ROWS):
                        def one(p):
                            b = dict(tb)
                            b[PARAMS_KEY] = p
                            out, flags = _raw(b)
                            _meta.clear()
                            _meta.append(egress_mod.column_meta(out))
                            return egress_mod.gather_live(out, _cap), flags
                        return jax.vmap(one)(sp_)

                    # traced through jax.export where the AOT tier is on:
                    # what this tick compiles is what the publisher
                    # serialises (compilecache.ExportedProgram)
                    pair = (compilecache.ExportedProgram(batched)
                            if compilecache.AOT.enabled()
                            else jax.jit(batched),  # tpulint: disable=RETRACE
                            raw, meta, None)
                    with self._mu:
                        self._compiled[ck] = pair
                        while len(self._compiled) > CACHE_ENTRIES:
                            self._compiled.popitem(last=False)
                fn, raw, meta, _aot_vk = pair
                traces_before = raw.trace_count[0]
                (gdatas, gvalids, ns_dev), flags = fn(table_batches, stacked)
                compiled_now = raw.trace_count[0] > traces_before
                if compiled_now:
                    cms = (time.perf_counter() - t0) * 1e3
                    metrics.compile_ms.observe(cms)
                    sp.set(compiled=True)
                    # device accounting: a batched executable is its own
                    # compile (vmapped over the padded group) — record
                    # under kind="batched" with the group size in the
                    # shape so fleet dashboards see the fork-out
                    if compilecache.EXECUTABLES.enabled():
                        compilecache.EXECUTABLES.record_compile(
                            "batched",
                            str(entry.get("text") or "<unnamed>"),
                            entry.get("plan_sig"), f"group={gpad}", cms,
                            fn, (table_batches, stacked))
                # ONE fused transfer for every lane of every overflow flag
                host_flags = jax.device_get(flags)
                needs = []
                for node, flag in zip(raw.join_order, host_flags):
                    fl = np.asarray(flag)
                    if isinstance(node, ScalarSourceNode) \
                            or getattr(node, "aot_scalar", False):
                        for i in np.nonzero(fl[:G] > 1)[0]:
                            ws[int(i)].err = PlanError(
                                "Subquery returns more than 1 row")
                        needs.append(None)
                    else:
                        needs.append(int(fl.max()))
                grew = caps.settle(
                    None if isinstance(raw, AotRawShim) else plan,
                    raw.join_order, needs).grew
                if not grew:
                    if compiled_now \
                            and getattr(fn, "exported", None) is not None \
                            and get_aot_key() is not None:
                        compilecache.AOT.publish_async(
                            aot_key, "batched",
                            str(entry.get("text") or "<unnamed>"),
                            entry.get("plan_sig"), fn,
                            ((gdatas, gvalids, ns_dev), flags),
                            flag_meta_of(raw.join_order),
                            extra={"egress_meta": meta[0],
                                   **traced_extra(raw, False)})
                    count_lowerings(raw)
                    break
                if isinstance(raw, AotRawShim):
                    # live data outgrew the artifact's baked caps: drop it
                    # for this process and compile fresh
                    with self._mu:
                        self._aot_bad.add(aot_key)
                    metrics.aot_cache_fallbacks.add(1)
                with self._mu:
                    self._compiled.pop(ck, None)   # caps changed: re-trace
            else:
                raise RuntimeError(
                    "join output cap still overflowing after retries")
            metrics.dispatch_tick_ms.observe(
                (time.perf_counter() - t0) * 1e3)
        with trace.span("batch.scatter", group=G):
            # the one egress transfer for the whole group
            hdatas, hvalids, ns = jax.device_get((gdatas, gvalids, ns_dev))
            outs = egress_mod.rebuild_clients(meta[0], hdatas, hvalids,
                                              ns, G)
        # a lane that overflowed the static scatter budget re-runs inline
        # (rare: a groupable point query returning > scatter_rows rows)
        fb = None
        for m, o in zip(ws, outs):
            if o is None and m.err is None:
                if fb is None:
                    fb = CombineFallback("scatter budget overflow")
                    metrics.count_swallowed("dispatch.scatter_overflow")
                m.err = fb
        # scalar-subquery / overflow errors claim their lanes; the rest
        # carry their compacted host batch
        return [None if m.err is not None else o
                for m, o in zip(ws, outs)]


# lockset witness enrollment: debug_guards=log|disallow installs
# per-attribute assertions from the static ownership map (the dispatcher
# is the canonical witnessed class — its maps are mutated by every
# session thread plus the combiner)
from ..analysis.runtime import LOCK_RANKS as _LOCK_RANKS  # noqa: E402
from ..analysis.runtime import register_witness  # noqa: E402

register_witness(BatchDispatcher,
                 "baikaldb_tpu/exec/dispatch.py:BatchDispatcher")
# rank visible at import (docs/LINT.md table is pinned against the
# registry by test_lint.py without constructing a dispatcher)
_LOCK_RANKS.setdefault("dispatch.combine_mu", BatchDispatcher.RANK)
