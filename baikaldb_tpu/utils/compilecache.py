"""The persistent XLA compilation-cache location + the per-executable
device-resource accounting registry.

One cache directory per process, placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` is set jax itself maps it onto
``jax_compilation_cache_dir`` and nothing here touches the option; unset,
the cache lives at ``<checkout>/.jax_cache``.  XLA's cache keys incorporate
the directory path, so a directory that moves never hits — no path is ever
derived from a temp dir, a pid or the clock.  ``enable()`` runs once, at
package import (``baikaldb_tpu/__init__.py``), i.e. before the first
compile of any Session, server, daemon or test process.

Device-resource accounting (the telemetry plane's "what does an executable
COST" half): every compile seam (exec/session.py ``_run_plan``,
exec/dispatch.py ``_combine``) records its executable here — statement,
plan signature, data shape, compile wall-ms — and the expensive XLA
``cost_analysis()`` / ``memory_analysis()`` numbers (FLOPs, bytes accessed,
argument/output/temp HBM) are filled LAZILY, only when
``information_schema.executables`` or EXPLAIN ANALYZE's ``-- device:`` line
asks, then memoized.  Lazy because the AOT re-lower that produces them is
not free; it must never tax the hot path that merely executes.

The re-lower traces the plan function once more, which would corrupt the
retrace telemetry the bucketing tests pin (``metrics.xla_retraces``, the
per-plan ``trace_count``) — so the analysis pass flags itself thread-locally
(``executor.ACCOUNTING_TRACE``) and ``run_local`` skips both counters for
that trace.  Executables are referenced through weakrefs:
an entry whose executable the plan cache evicted reports its recorded
compile stats but no fresh analysis (``analyzed='evicted'``).
"""

from __future__ import annotations

import hashlib
import os
import pickle
import queue
import threading
import time
import weakref
from collections import OrderedDict
from typing import Optional

from .flags import FLAGS, define

REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(REPO_DIR, ".jax_cache")

# bump when the artifact container / aux pickle layout changes: old
# artifacts become clean misses instead of deserialization landmines
AOT_FORMAT = 1


def enable() -> None:
    """Place the persistent compile cache (see the module docstring) and
    make every compile eligible for it.  Initialises no backend."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


define("device_accounting", True,
       "per-executable device-resource accounting: compile seams record "
       "(statement, plan signature, shape, compile ms) and "
       "information_schema.executables / EXPLAIN ANALYZE's '-- device:' "
       "line add lazy XLA cost/memory analysis (FLOPs, bytes accessed, "
       "peak HBM).  0 disables recording entirely")
# executable-accounting LRU entries (distinct (kind, statement, plan
# signature, shape) tuples)
DEVICE_ACCOUNTING_MAX = 256


class _ExecRecord:
    __slots__ = ("kind", "statement", "plan_sig", "shape", "compiles",
                 "compile_ms_total", "last_compile_ms", "fn_ref",
                 "arg_structs", "analysis", "analyzed")

    def __init__(self, kind: str, statement: str, plan_sig, shape: str):
        self.kind = kind
        self.statement = statement
        self.plan_sig = plan_sig
        self.shape = shape
        self.compiles = 0
        self.compile_ms_total = 0.0
        self.last_compile_ms = 0.0
        self.fn_ref = None
        self.arg_structs = None
        self.analysis: Optional[dict] = None
        self.analyzed = ""          # "" | "xla" | "estimate" | "evicted"
                                    # | "error"


def _tree_bytes(structs) -> float:
    import jax
    total = 0
    # structs holds ShapeDtypeStructs (host metadata), never live device
    # arrays — iterating them is plain host work
    leaves = jax.tree.leaves(structs)
    for leaf in leaves:  # tpulint: disable=RETRACE

        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * getattr(dtype, "itemsize", 1)
    return float(total)


class ExecutableAccounting:
    """Bounded LRU of executable cost records, snapshot-able as rows for
    ``information_schema.executables``."""

    def __init__(self):
        self._mu = threading.Lock()
        # serializes lazy analysis OUTSIDE _mu: a lower+compile is slow and
        # must not block record() on the compile hot path, but two view
        # readers analyzing one record concurrently would double-pay the
        # AOT trace; held per record, not across a whole view read
        self._an_mu = threading.Lock()
        self._entries: "OrderedDict[tuple, _ExecRecord]" = OrderedDict()

    def enabled(self) -> bool:
        return bool(FLAGS.device_accounting)

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()

    def record_compile(self, kind: str, statement: str, plan_sig,
                       shape: str, compile_ms: float, fn,
                       args: tuple) -> None:
        """One compile at a seam.  ``fn`` is the jitted callable (weakref'd
        — the plan cache owns its lifetime), ``args`` the positional
        example args whose shape/dtype skeleton the lazy analysis lowers
        against."""
        if not self.enabled():
            return
        import jax
        key = (kind, statement, plan_sig, shape)
        structs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape") and hasattr(x, "dtype") else x, args)
        with self._mu:
            rec = self._entries.get(key)
            if rec is None:
                rec = self._entries[key] = _ExecRecord(
                    kind, statement, plan_sig, shape)
                while len(self._entries) > DEVICE_ACCOUNTING_MAX:
                    self._entries.popitem(last=False)
            else:
                self._entries.move_to_end(key)
            rec.compiles += 1
            rec.compile_ms_total += float(compile_ms)
            rec.last_compile_ms = float(compile_ms)
            try:
                rec.fn_ref = weakref.ref(fn)
            except TypeError:       # non-weakref-able callable: pin it —
                rec.fn_ref = (lambda f=fn: f)   # bounded by the LRU cap
            rec.arg_structs = structs
            rec.analysis = None     # recompiled: stale numbers must refresh
            rec.analyzed = ""

    def _analyze(self, rec: _ExecRecord) -> None:
        """Fill FLOPs / bytes / HBM via one AOT re-lower + compile (served
        from XLA's in-memory/persistent compile cache when possible).  The
        re-trace this costs is flagged via ``executor.ACCOUNTING_TRACE`` so
        it never enters the retrace telemetry — accounting must not look
        like plan-cache churn."""
        import jax

        from . import metrics
        from ..exec import executor
        fn = rec.fn_ref() if rec.fn_ref is not None else None
        if fn is None or rec.arg_structs is None:
            rec.analysis = {}
            rec.analyzed = "evicted"
            return
        # jax traces on THIS thread: flag the re-lower as accounting so
        # run_local skips trace_count / metrics.xla_retraces entirely —
        # suppression at the source beats decrementing afterwards (no race
        # with a concurrent legitimate compile, and the exported counter
        # stays monotonic for Prometheus rate())
        executor.ACCOUNTING_TRACE.active = True
        try:
            compiled = fn.lower(*rec.arg_structs).compile()
            out = {}
            try:
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                out["flops"] = float(ca.get("flops", float("nan")))
                out["bytes_accessed"] = float(
                    ca.get("bytes accessed", float("nan")))
            except Exception:
                metrics.count_swallowed("device.cost_analysis")
            arg_est = _tree_bytes(rec.arg_structs)
            out.setdefault("flops", float("nan"))
            out.setdefault("bytes_accessed", float("nan"))
            try:
                ma = compiled.memory_analysis()
            except Exception:
                ma = None
            if ma is not None and getattr(ma, "argument_size_in_bytes",
                                          None) is not None:
                arg_b = float(ma.argument_size_in_bytes)
                out_b = float(ma.output_size_in_bytes)
                tmp_b = float(ma.temp_size_in_bytes)
                out.update(argument_bytes=arg_b, output_bytes=out_b,
                           temp_bytes=tmp_b,
                           # the standard XLA live-set peak: args + outputs
                           # + transient workspace
                           peak_hbm_bytes=arg_b + out_b + tmp_b,
                           code_bytes=float(
                               ma.generated_code_size_in_bytes))
                rec.analyzed = "xla"
            else:
                # backend without memory stats: shape-derived lower bound
                out_est = _tree_bytes(jax.eval_shape(fn, *rec.arg_structs))
                out.update(argument_bytes=arg_est, output_bytes=out_est,
                           temp_bytes=float("nan"),
                           peak_hbm_bytes=arg_est + out_est,
                           code_bytes=float("nan"))
                rec.analyzed = "estimate"
            rec.analysis = out
        except Exception:   # noqa: BLE001 — accounting is advisory; the
            #   view must answer even when a lowering path can't re-run
            metrics.count_swallowed("device.analyze")
            rec.analysis = {}
            rec.analyzed = "error"
        finally:
            executor.ACCOUNTING_TRACE.active = False

    def _row(self, rec: _ExecRecord, analyze: bool) -> dict:
        if analyze and rec.analysis is None:
            with self._an_mu:
                if rec.analysis is None:       # lost the race: memoized
                    self._analyze(rec)
        a = rec.analysis or {}
        nan = float("nan")
        return {
            "statement": rec.statement, "kind": rec.kind,
            "plan_sig": str(rec.plan_sig), "shape": rec.shape,
            "compiles": rec.compiles,
            "compile_ms_total": round(rec.compile_ms_total, 3),
            "last_compile_ms": round(rec.last_compile_ms, 3),
            "flops": a.get("flops", nan),
            "bytes_accessed": a.get("bytes_accessed", nan),
            "peak_hbm_bytes": a.get("peak_hbm_bytes", nan),
            "argument_bytes": a.get("argument_bytes", nan),
            "output_bytes": a.get("output_bytes", nan),
            "mem_source": rec.analyzed,
        }

    def find(self, plan_sig=None) -> Optional[dict]:
        """Newest row matching ``plan_sig``, analyzed on demand (EXPLAIN
        ANALYZE's ``-- device:`` feed) — only the match is analyzed, not
        every pending record."""
        with self._mu:
            recs = [r for r in self._entries.values()
                    if plan_sig is None or str(r.plan_sig) == str(plan_sig)]
        if not recs:
            return None
        return self._row(recs[-1], analyze=True)

    def rows(self, analyze: bool = True) -> list[dict]:
        with self._mu:
            recs = list(self._entries.values())
        return [self._row(rec, analyze) for rec in recs]


EXECUTABLES = ExecutableAccounting()


# -- AOT persistent executable cache ----------------------------------------
#
# The other half of zero-compile cold start: the in-memory plan cache dies
# with the process, so a restarted node used to re-pay every (plan
# signature, capacity bucket) trace+lower+compile from scratch.  Here every
# settled executable is serialized via JAX AOT export (StableHLO + the
# in/out calling convention) into a self-verifying artifact
# (storage/aot_tier.py), spilled to a local disk tier, and replicated
# through the store daemons + meta manifest so a fresh node warm-starts
# from its peers' compilations.
#
# Two costs die separately:
# - the Python trace + jax lowering (and every join-cap overflow retrace,
#   since settled caps are baked into the exported program) die at
#   ``export.deserialize`` — no plan function ever runs;
# - the backend StableHLO->executable compile dies at the XLA persistent
#   compilation cache: the query's thread runs its program as
#   ``jit(exported.call)`` (``ExportedProgram``), the module a loader
#   compiles, so the one compile a settled executable gets in its process
#   is the entry a loader hits (a plain ``jit`` of the plan function has
#   another cache key: compiling that would leave the loader a compile).
#
# Trust boundary: artifacts are advisory.  Corrupt bytes, foreign jax
# versions, and alien device topologies are detected before anything
# executes (digest + version/fingerprint checks); a loaded executable whose
# baked capacities overflow on live data falls back to compile-from-scratch
# (metrics.aot_cache_fallbacks).  The off-switch restores the exact
# pre-cache behavior: every path below is gated on FLAGS.aot_cache.

define("aot_cache", True,
       "persist settled executables via JAX AOT export to a local disk "
       "tier (and the peer tier when a meta service is attached) so a "
       "restarted node warm-starts with zero compiles.  0 restores "
       "compile-from-scratch cold starts")
define("aot_cache_dir", "",
       "AOT artifact directory (empty = <repo>/.aot_cache)")
# programs the in-process tier keeps (least recently used go first)
LIVE_PROGRAMS_MAX = 256

define("aot_cache_disk_max", 256,
       "local disk tier bound (artifacts); least-recently-touched evict")

def backend_fingerprint(mesh=None) -> str:
    """Platform/topology identity an artifact is only valid under: a CPU
    export must never feed a TPU process, an 8-device shard_map program
    never a 1-device mesh."""
    import jax

    devs = jax.devices()
    fp = (f"{devs[0].platform}:{getattr(devs[0], 'device_kind', '?')}"
          f":{len(devs)}")
    if mesh is not None:
        fp += ":mesh=" + "x".join(str(int(s)) for s in mesh.devices.shape)
    return fp


def _dict_digest(d) -> str:
    if d is None:
        return "-"
    try:
        return d._fingerprint().hex()
    except Exception:   # noqa: BLE001 — an unhashable dictionary only
        #                 costs cache reuse, never correctness
        from . import metrics
        metrics.count_swallowed("aot.dict_digest")
        return f"?{id(d)}"


def _fp_walk(h, obj) -> None:
    """Structural fingerprint of a program input pytree: leaf shapes and
    dtypes plus the STATIC aux data jit keys executables on (column ltypes,
    dictionary contents, names, live-prefix promises).  Two batches with
    equal fingerprints flatten to the same leaf order and trace to the
    same program."""
    from ..column.batch import Column, ColumnBatch

    if isinstance(obj, ColumnBatch):
        h.update(b"B")
        h.update(repr(obj.names).encode())
        h.update(b"1" if obj.live_prefix else b"0")
        _fp_walk(h, obj.sel)
        _fp_walk(h, obj.num_rows)
        for c in obj.columns:
            _fp_walk(h, c)
        return
    if isinstance(obj, Column):
        h.update(b"C")
        h.update(str(obj.ltype.value).encode())
        h.update(_dict_digest(obj.dictionary).encode())
        _fp_walk(h, obj.data)
        _fp_walk(h, obj.validity)
        return
    if isinstance(obj, dict):
        h.update(b"D")
        for k in sorted(obj):
            h.update(str(k).encode())
            _fp_walk(h, obj[k])
        return
    if isinstance(obj, (tuple, list)):
        h.update(b"T" if isinstance(obj, tuple) else b"L")
        h.update(str(len(obj)).encode())
        for x in obj:
            _fp_walk(h, x)
        return
    if hasattr(obj, "shape") and hasattr(obj, "dtype"):
        h.update(f"A{tuple(obj.shape)}{obj.dtype}".encode())
        return
    h.update(f"V{obj!r}".encode())


def input_fingerprint(args) -> str:
    h = hashlib.sha256()
    _fp_walk(h, args)
    return h.hexdigest()


def aot_key(kind: str, plan_sig, shape_sig, input_fp: str,
            mesh=None) -> str:
    """Artifact identity: program structure (plan signature), data shape
    (capacity buckets + trace-time flags in ``shape_sig``), the input
    pytree skeleton, jax/jaxlib versions and the backend topology.  Any
    component moving is a clean miss — never a wrong-program hit."""
    import jax
    import jaxlib

    h = hashlib.sha256()
    for part in (f"fmt={AOT_FORMAT}", f"kind={kind}",
                 f"sig={plan_sig}", f"shape={shape_sig!r}",
                 f"in={input_fp}", f"jax={jax.__version__}",
                 f"jaxlib={jaxlib.__version__}",
                 f"dev={backend_fingerprint(mesh)}"):
        h.update(part.encode())
        h.update(b"\x00")
    return h.hexdigest()


class LoadedArtifact:
    """A deserialized AOT executable plus the host-side metadata a run
    needs: the output pytree template, the flag-order capacity metadata
    (exec/executor.AotRawShim consumes it), and any kind-specific extra
    (the batched dispatcher's egress column meta)."""

    __slots__ = ("key", "meta", "source", "flag_meta", "extra",
                 "_call", "_out_struct")

    def __init__(self, key, meta, source, call, template, extra):
        import jax

        self.key = key
        self.meta = meta
        self.source = source                    # "disk" | "peer"
        self.flag_meta = meta.get("flag_meta") or []
        self.extra = extra
        self._call = call
        self._out_struct = jax.tree_util.tree_structure(template)

    def run(self, args):
        """Execute on an input pytree structurally identical to the one
        the artifact was exported against (the key guarantees it)."""
        import jax

        leaves = jax.tree_util.tree_leaves(args)
        out_leaves = self._call(*leaves)
        return jax.tree_util.tree_unflatten(self._out_struct,
                                            list(out_leaves))


class ExportedProgram:
    """A traceable program run the way an artifact runs it: traced once
    through ``jax.export`` on its first call, and executed as
    ``jax.jit(exported.call)``.  The module this process compiles is then
    the module a published artifact carries, so the publisher serialises
    ``exported`` without tracing or compiling anything again, and a process
    that loads the artifact compiles the same module (a hit in an XLA
    persistent cache the two share).  Where the program cannot be exported
    it runs as a plain ``jax.jit`` and ``exported`` stays ``None``: nothing
    is published for it."""

    def __init__(self, raw_call):
        self._raw = raw_call
        self.call = None            # jitted (*leaves) -> tuple of leaves
        self._in_tree = None
        self._out_tree = None
        self.exported = None

    def _trace(self, leaves, treedef):
        import jax
        from jax import export as jax_export

        from . import metrics

        self._in_tree = treedef

        def _flat(*xs):
            out = self._raw(*jax.tree_util.tree_unflatten(treedef, list(xs)))
            self._out_tree = jax.tree_util.tree_structure(out)
            return tuple(jax.tree_util.tree_leaves(out))

        try:
            # leaves is a host list; per-leaf work reads metadata only
            structs = [_leaf_struct(x)
                       for x in leaves]  # tpulint: disable=RETRACE
            self.exported = jax_export.export(jax.jit(_flat))(*structs)
            self.call = jax.jit(self.exported.call)
        except Exception:   # noqa: BLE001 — an op export cannot carry:
            #   the program still runs, it only is not published
            metrics.count_swallowed("aot.export")
            self.exported = None
            self.call = jax.jit(_flat)

    def _stale(self, treedef) -> bool:
        return self._in_tree is None or treedef != self._in_tree

    def _leaves(self, args: tuple) -> list:
        """The input's leaves, the program traced for them.  Like a
        ``jax.jit`` the program is traced again when the input's static
        half moves (a dictionary's content, a column's type): an exported
        module has those baked in and cannot notice by itself."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        # a treedef is a host object: nothing is concretized here
        if self._stale(treedef):  # tpulint: disable=RETRACE
            self._trace(leaves, treedef)
        return leaves

    def __call__(self, *args):
        import jax

        leaves = self._leaves(args)     # first: it may trace
        out = self.call(*leaves)
        return jax.tree_util.tree_unflatten(self._out_tree, list(out))

    def lower(self, *args):
        """The accounting's re-lower (``ExecutableAccounting._analyze``)."""
        leaves = self._leaves(args)
        return self.call.lower(*leaves)


def _leaf_struct(x):
    """Shape and dtype of one input leaf — host attributes on jax arrays
    and numpy feeds alike: the value is never materialized."""
    import jax

    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        import numpy as np

        arr = np.asarray(x)     # plain host scalar leaf
        shape, dtype = arr.shape, arr.dtype
    return jax.ShapeDtypeStruct(shape, dtype)


class _PublishTask:
    __slots__ = ("key", "kind", "statement", "plan_sig", "exported",
                 "template", "flag_meta", "extra", "mesh")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw[k])


class AotExecutableCache:
    """Process-wide orchestrator of the artifact tiers (one instance,
    ``AOT``): load = disk -> peer -> miss; publish = background
    serialise + verify + disk put + peer push.  Every operation is gated on
    FLAGS.aot_cache and degrades to a miss on any failure."""

    def __init__(self):
        self._mu = threading.Lock()
        self._disk = None
        self._disk_root = None
        self._replicator = None
        self._records: "OrderedDict[str, dict]" = OrderedDict()
        self._q: "queue.Queue[_PublishTask]" = queue.Queue()
        self._worker = None
        # XLA persistent-cache files already pushed to the peer tier: each
        # publish ships every not-yet-pushed local entry (the query
        # executables AND the eager op kernels around them — egress
        # compact, dictionary remaps), so a peer-warmed node compiles
        # nothing at all, not just no plan programs
        self._xla_pushed: set = set()
        # keys with a publish already queued/in flight: concurrent first
        # touches of one executable (two sessions racing the same compile)
        # export exactly once — the second enqueue is a no-op
        self._pending: set = set()
        # the programs this process compiled or loaded, by artifact key:
        # another session of the process (a new connection's first
        # statements) runs the compiled program as it is, instead of
        # reading an artifact back from disk — which, for a program with a
        # large dictionary baked in, is hundreds of MB to deserialize
        self._live: "OrderedDict[str, LoadedArtifact]" = OrderedDict()

    # -- config -----------------------------------------------------------
    def enabled(self) -> bool:
        return bool(FLAGS.aot_cache)

    def root(self) -> str:
        d = str(FLAGS.aot_cache_dir).strip()
        return d or os.path.join(REPO_DIR, ".aot_cache")

    def disk(self):
        from ..storage.aot_tier import ArtifactDisk

        root = self.root()
        with self._mu:
            if self._disk is None or self._disk_root != root:
                self._disk = ArtifactDisk(
                    root, max_entries=int(FLAGS.aot_cache_disk_max))
                self._disk_root = root
            self._disk.max_entries = max(1, int(FLAGS.aot_cache_disk_max))
            return self._disk

    def attach_peer(self, meta_address: str) -> None:
        """Join the fleet tier: publish to / fetch from the store daemons
        behind this meta service's manifest."""
        from ..storage.aot_tier import AotReplicator

        with self._mu:
            self._replicator = AotReplicator(meta_address)

    def detach_peer(self) -> None:
        with self._mu:
            self._replicator = None

    def xla_cache_dir(self) -> Optional[str]:
        """The process's persistent compile cache (placed by
        :func:`enable`).  Peer-replicated cache entries only hit when the
        fleet agrees on one absolute path — XLA's cache keys incorporate
        it — so a fleet sets ``JAX_COMPILATION_CACHE_DIR`` alike on every
        node, like any shared-cache mount point."""
        import jax

        return jax.config.jax_compilation_cache_dir or None

    # -- load -------------------------------------------------------------
    def _version_ok(self, meta: dict, mesh) -> bool:
        import jax
        import jaxlib

        return (meta.get("jax") == jax.__version__
                and meta.get("jaxlib") == jaxlib.__version__
                and meta.get("fingerprint") == backend_fingerprint(mesh)
                and meta.get("format") == AOT_FORMAT)

    def load(self, key: str, mesh=None) -> Optional[LoadedArtifact]:
        """disk -> peer -> None.  Counts exactly one of hits/misses; a
        corrupt artifact additionally counts an eviction + fallback."""
        from . import metrics

        if not self.enabled():
            return None
        with self._mu:
            art = self._live.get(key)
            if art is not None:
                self._live.move_to_end(key)
        if art is not None:
            metrics.aot_cache_hits.add(1)
            self._record(key, art.meta, "memory", 0.0)
            return art
        disk = self.disk()
        data = disk.get(key)
        source = "disk"
        if data is None:
            # local miss: resolve the artifact through the meta manifest
            # and fetch it from the holding store daemon
            with self._mu:
                rep = self._replicator
            if rep is not None:
                fetched = rep.fetch(key)
                if fetched is not None:
                    data, xla_files = fetched
                    source = "peer"
                    metrics.aot_cache_peer_fetches.add(1)
                    disk.put(key, data)
                    self._plant_xla_files(xla_files)
        if data is None:
            metrics.aot_cache_misses.add(1)
            return None
        t0 = time.perf_counter()
        try:
            from ..storage.aot_tier import unpack_artifact

            meta, blob, aux = unpack_artifact(data)
            if not self._version_ok(meta, mesh):
                # clean miss: a stale-version/foreign-topology artifact is
                # not corruption, but keeping it on disk would re-run this
                # check on every cold start forever
                disk.delete(key)
                metrics.aot_cache_evictions.add(1)
                metrics.aot_cache_misses.add(1)
                self._record(key, meta, "stale", 0.0)
                return None
            import jax
            from jax import export as jax_export

            exported = jax_export.deserialize(bytearray(blob))
            call = jax.jit(exported.call)
            auxd = pickle.loads(aux)
            art = LoadedArtifact(key, meta, source, call,
                                 auxd["template"], auxd.get("extra"))
        except Exception:   # noqa: BLE001 — poisoned artifact: evict,
            #   count, and let the caller compile; a cache must never turn
            #   a query into a crash
            metrics.count_swallowed("aot.load")
            disk.delete(key)
            metrics.aot_cache_evictions.add(1)
            metrics.aot_cache_fallbacks.add(1)
            self._record(key, {}, "corrupt", 0.0)
            return None
        deser_ms = (time.perf_counter() - t0) * 1e3
        metrics.aot_cache_hits.add(1)
        metrics.aot_cache_deser_ms.observe(deser_ms)
        self._record(key, meta, source, deser_ms)
        self._keep_live(art)
        return art

    def _keep_live(self, art: LoadedArtifact) -> None:
        with self._mu:
            self._live[art.key] = art
            self._live.move_to_end(art.key)
            while len(self._live) > LIVE_PROGRAMS_MAX:
                self._live.popitem(last=False)

    def forget_live(self) -> None:
        """Drop the in-process tier: what a restarted process starts with
        (tests of the disk and peer tiers)."""
        with self._mu:
            self._live.clear()

    def _plant_xla_files(self, xla_files) -> None:
        """Write peer-fetched XLA persistent-cache entries into the local
        cache dir so the artifact's backend compile is a cache hit."""
        xdir = self.xla_cache_dir()
        if not xdir or not xla_files:
            return
        try:
            os.makedirs(xdir, exist_ok=True)
            for name, data in xla_files:
                safe = os.path.basename(str(name))
                p = os.path.join(xdir, safe)
                if os.path.exists(p):
                    continue
                tmp = p + f".tmp.{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, p)
        except OSError:
            from . import metrics
            metrics.count_swallowed("aot.plant_xla")

    def _record(self, key: str, meta: dict, source: str,
                deser_ms: float) -> None:
        with self._mu:
            rec = self._records.get(key)
            if rec is None:
                rec = self._records[key] = {
                    "key": key, "hits": 0, "deser_ms": 0.0}
                while len(self._records) > 512:
                    self._records.popitem(last=False)
            rec.update(kind=meta.get("kind", rec.get("kind", "?")),
                       statement=meta.get("statement",
                                          rec.get("statement", "")),
                       plan_sig=str(meta.get("plan_sig",
                                             rec.get("plan_sig", ""))),
                       source=source, deser_ms=round(deser_ms, 3))
            if source in ("memory", "disk", "peer"):
                rec["hits"] += 1

    # -- publish ----------------------------------------------------------
    def publish_async(self, key: str, kind: str, statement: str, plan_sig,
                      program: "ExportedProgram", out, flag_meta, extra=None,
                      mesh=None) -> None:
        """Keep one settled executable: in this process at once (another
        session's first run of it is a hit of the in-process tier), and on
        disk and at the peers through the background publisher, which
        serialises ``program.exported`` — the module the query's thread
        traced and compiled — and neither traces nor compiles.  ``out`` is
        the full output pytree of a successful run (only its structure
        template is kept).  A program that could not be exported is not
        kept."""
        import jax

        exported = getattr(program, "exported", None)
        if not self.enabled() or exported is None:
            return
        template = jax.tree_util.tree_map(lambda _x: 0, out)
        self._keep_live(LoadedArtifact(
            key, {"kind": kind, "statement": statement,
                  "plan_sig": str(plan_sig), "flag_meta": flag_meta},
            "memory", program.call, template, extra))
        task = _PublishTask(key=key, kind=kind, statement=statement,
                            plan_sig=plan_sig, exported=exported,
                            template=template, flag_meta=flag_meta,
                            extra=extra, mesh=mesh)
        with self._mu:
            if key in self._pending:
                return          # a concurrent first touch already queued it
            self._pending.add(key)
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(target=self._work,
                                                daemon=True,
                                                name="aot-publish")
                self._worker.start()
                # a daemon thread killed mid-XLA-compile aborts the
                # interpreter teardown; give in-flight publishes a bounded
                # window to finish before exit
                import atexit
                atexit.register(self.drain, 10.0)
        self._q.put(task)

    def drain(self, timeout_s: float = 60.0) -> bool:
        """Block until every queued publish finished (tests/CLI)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._q.all_tasks_done:
                if self._q.unfinished_tasks == 0:
                    return True
            time.sleep(0.01)
        return False

    def _work(self) -> None:
        while True:
            task = self._q.get()
            try:
                self._publish_one(task)
            except Exception:   # noqa: BLE001 — publishing is strictly
                #   best-effort: a failed export costs one future recompile
                from . import metrics
                metrics.count_swallowed("aot.publish")
            finally:
                with self._mu:
                    self._pending.discard(task.key)
                self._q.task_done()

    def _xla_listing(self) -> set:
        xdir = self.xla_cache_dir()
        if not xdir:
            return set()
        try:
            return set(os.listdir(xdir))
        except OSError:
            return set()

    def _publish_one(self, task: _PublishTask) -> None:
        import jax
        import jaxlib
        from jax import export as jax_export

        from ..storage.aot_tier import pack_artifact
        from . import metrics

        t_publish = time.perf_counter()
        try:
            if task.statement == "<unnamed>" \
                    and os.path.exists(self.disk().path(task.key)):
                # an EXPLAIN ANALYZE re-run of an already-published
                # executable: same key, same program — re-exporting would
                # only overwrite the artifact's real statement label
                return
            blob = bytes(task.exported.serialize())
            # verify: deserializing our own bytes is the integrity check —
            # a corrupt export dies here, not on a serving node.  Nothing
            # is compiled: the query's thread compiled jit(exported.call),
            # the module a loader compiles, so it is in the XLA cache
            jax_export.deserialize(bytearray(blob))
            meta = {"format": AOT_FORMAT, "key": task.key,
                    "kind": task.kind, "statement": task.statement,
                    "plan_sig": str(task.plan_sig),
                    "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                    "fingerprint": backend_fingerprint(task.mesh),
                    "created_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                time.gmtime()),
                    "flag_meta": task.flag_meta}
            aux = pickle.dumps({"template": task.template,
                                "extra": task.extra})
            data = pack_artifact(meta, blob, aux)
            self.disk().put(task.key, data)
            metrics.aot_cache_publishes.add(1)
            self._record(task.key, meta, "published", 0.0)
            with self._mu:
                rep = self._replicator
            if rep is not None:
                xdir = self.xla_cache_dir()
                xla_files = []
                to_push = self._xla_listing() - self._xla_pushed
                for name in sorted(to_push):
                    try:
                        with open(os.path.join(xdir, name), "rb") as f:
                            xla_files.append((name, f.read()))
                    except OSError:
                        continue
                if rep.publish(task.key, data,
                               {"kind": task.kind,
                                "plan_sig": str(task.plan_sig),
                                "jax": jax.__version__}, xla_files):
                    self._xla_pushed |= {n for n, _ in xla_files}
        finally:
            metrics.aot_publish_ms.add(
                (time.perf_counter() - t_publish) * 1e3)

    # -- introspection (information_schema.aot_cache, tools/aotcache) -----
    def rows(self) -> list[dict]:
        disk_rows = {r["key"]: r for r in self.disk().entries()} \
            if self.enabled() else {}
        with self._mu:
            recs = dict(self._records)
        out = []
        for key in sorted(set(disk_rows) | set(recs)):
            d = disk_rows.get(key, {})
            m = d.get("meta", {})
            r = recs.get(key, {})
            out.append({
                "key": key,
                "kind": r.get("kind") or m.get("kind", "?"),
                "statement": r.get("statement") or m.get("statement", ""),
                "plan_sig": r.get("plan_sig") or str(m.get("plan_sig", "")),
                "size_bytes": int(d.get("size", 0)),
                "jax_version": m.get("jax", ""),
                "created_at": m.get("created_at", ""),
                "source": r.get("source", "disk" if d else "memory"),
                "hits": int(r.get("hits", 0)),
                "deser_ms": float(r.get("deser_ms", 0.0)),
                "status": "corrupt" if d.get("error") else "ok",
            })
        return out

    def reset_records(self) -> None:
        with self._mu:
            self._records.clear()


AOT = AotExecutableCache()
