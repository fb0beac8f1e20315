"""Plan executor: lower the plan IR to one jit-compiled kernel pipeline.

This replaces BOTH of the reference's execution modes: the volcano
open/get_next interpreter (include/exec/exec_node.h:140-145) and the Acero
declaration path (GlobalArrowExecutor::execute,
src/runtime/arrow_io_excutor.cpp:265).  The whole query — scan filters,
projections, group-by, joins, sort — traces into a single XLA program, so
operator boundaries cost nothing: XLA fuses scan+filter+aggregate into a few
HBM passes (the fusion the reference hopes Acero's pipelining approximates).

Static-shape discipline: join/limit caps are compile-time constants; join
overflow is detected via returned flags and retried with doubled caps
(recompile), the analog of the reference re-fetching on region-version change
(fetcher_store.cpp handle_version_old).
"""

from __future__ import annotations

from dataclasses import replace as dreplace
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..column.batch import Column, ColumnBatch
from ..expr.ast import ColRef, Lit
from ..expr.compile import eval_expr, eval_output, eval_predicate, infer_type
from ..column.dictionary import KEYS_KEY, bind_keys, recording_bakes
from ..expr.params import PARAMS_KEY, bind_params
from ..ops import join as join_ops
from ..ops.compact import compact, head
from ..ops.hashagg import (AggSpec, MERGE_OP, finalize_partials,
                           group_aggregate_dense, group_aggregate_sorted,
                           group_aggregate_stream, noting_lowerings,
                           partial_specs,
                           scalar_aggregate)
from ..ops.sort import SortKey, sort_batch, top_k
from ..ops.compact import shrink
from ..plan.nodes import (AggNode, DistinctNode, ExchangeNode, FilterNode,
                          JoinNode, LimitNode, MembershipNode, MultiJoinNode,
                          PlanNode, ProjectNode, ScalarSourceNode, ScanNode,
                          ShrinkNode, SortNode, StreamResultNode, UnionNode,
                          ValuesNode, WindowNode)
from ..column.batch import concat_batches
from ..parallel.mesh import AXIS
from ..types import LType


class ExecError(RuntimeError):
    pass


from ..utils import metrics  # noqa: E402
from ..utils.flags import FLAGS, define  # noqa: E402
from . import caps  # noqa: E402

# Pushed-down fragments (exec/fragments.py) merge daemon partials HOST-side
# under parallel.agg.WIRE_MERGE while this executor merges mesh partials
# under ops.hashagg.MERGE_OP — the same semantic in two planes.  Pin them
# at import: a kind whose wire merge drifted from its device merge would
# make pushed results silently diverge from the image path (the
# off-switch's bit-identity guarantee), so fail loudly instead.
from ..parallel.agg import WIRE_MERGE as _WIRE_MERGE  # noqa: E402
from ..parallel.agg import merge_collective  # noqa: E402

_drift = {k for k, op in _WIRE_MERGE.items() if MERGE_OP.get(k) != op}
if _drift:
    raise ExecError(
        f"wire/device partial-merge drift for agg kinds {sorted(_drift)}: "
        "parallel.agg.WIRE_MERGE must match ops.hashagg.MERGE_OP")
del _drift

import threading  # noqa: E402

_I32_MAX = (1 << 31) - 1

# set (thread-locally) by utils/compilecache._analyze while it AOT
# re-lowers a cached executable for cost accounting: jax traces on the
# calling thread, and that bookkeeping trace must not count as plan-cache
# churn in trace_count / metrics.xla_retraces
ACCOUNTING_TRACE = threading.local()

define("radix_join_buckets", 0,
       "hash-partition sort-join builds into this many buckets (power of "
       "two; 0 = off): batched per-bucket sorts replace the one global "
       "bitonic sort — the TPU-shaped hash join (ops/radix.py)")
define("radix_join_min_build", 65536,
       "radix-partition joins only engage for builds at least this large")


class AotFlagShim:
    """Stands in for one plan node in the flag order of an AOT-loaded
    executable: the artifact records each overflow flag's settled capacity
    (and whether it is a scalar-subquery count) at publish time, and the
    session's retry loop checks live flags against these.  A shim whose
    cap is exceeded cannot grow (the capacity is baked into the exported
    program) — the session falls back to compile-from-scratch instead."""

    __slots__ = ("cap", "aot_scalar", "kind")

    def __init__(self, cap, scalar: bool, kind: str):
        self.cap = cap
        self.aot_scalar = bool(scalar)
        self.kind = kind


def flag_meta_of(join_order) -> list:
    """The publish-time snapshot of a settled executable's flag order:
    [(cap, is_scalar, node-kind), ...] — everything an AOT run needs to
    interpret the returned overflow flags without the plan objects."""
    out = []
    for node in join_order:
        cap = getattr(node, "cap", None)
        out.append({"cap": None if cap is None else int(cap),
                    "scalar": isinstance(node, ScalarSourceNode),
                    "kind": type(node).__name__})
    return out


class AotRawShim:
    """Quacks like :func:`compile_plan`'s raw closure for the session /
    dispatcher retry loops: ``trace_count`` never moves (an AOT run never
    compiles — warm_compiles stays 0 by construction) and ``join_order``
    carries :class:`AotFlagShim` entries in the artifact's flag order."""

    def __init__(self, flag_meta: list, extra: Optional[dict] = None):
        self.join_order = [AotFlagShim(m.get("cap"), m.get("scalar", False),
                                       m.get("kind", "?"))
                           for m in (flag_meta or [])]
        self.trace_order: list = []
        self.trace_count = [0]
        extra = extra or {}
        self.exchange_bytes = [int(extra.get("exchange_bytes", 0))]
        self.agg_lowerings = list(extra.get("agg_lowerings", ()))
        self.agg_count_passes = [int(extra.get("agg_count_passes", 0))]
        self.agg_minmax_passes = [int(extra.get("agg_minmax_passes", 0))]


def traced_extra(raw, mesh: bool) -> dict:
    """What a program's trace recorded beside its flags, as an AOT artifact
    carries it (``extra``) and :class:`AotRawShim` reads it back."""
    extra: dict = {"agg_lowerings": tuple(raw.agg_lowerings),
                   "agg_count_passes": raw.agg_count_passes[0],
                   "agg_minmax_passes": raw.agg_minmax_passes[0]}
    if mesh:
        extra["exchange_bytes"] = raw.exchange_bytes[0]
    return extra


def count_lowerings(raw) -> None:
    """One execution of ``raw``'s program: +1 on ``agg_<lowering>_runs`` for
    each dense aggregate in it, on ``agg_count_passes`` the passes they were
    traced with only to count rows, and on ``agg_minmax_passes`` the dense
    MIN / MAX passes of their Pallas kernels."""
    for lowering in raw.agg_lowerings:
        _AGG_COUNTERS[lowering].add(1)
    _AGG_COUNTERS["count_passes"].add(raw.agg_count_passes[0])
    _AGG_COUNTERS["minmax_passes"].add(raw.agg_minmax_passes[0])


_AGG_COUNTERS = {"select_reduce": metrics.agg_select_reduce_runs,
                 "pallas": metrics.agg_pallas_runs,
                 "scatter": metrics.agg_scatter_runs,
                 "count_passes": metrics.agg_count_passes,
                 "minmax_passes": metrics.agg_minmax_passes}


class _CapBox:
    """A retryable capacity knob that rides the join-overflow protocol
    (exec/caps.py): a retry loop grows ``.cap`` to the reported need and
    re-traces (used for the radix join's per-bucket width and the fused
    exchange's per-input shuffle capacities).  ``kind``/``site`` label the
    knob for shuffle-retry accounting and the mpp.* trace spans."""

    def __init__(self, cap=None, kind: str = "width", site: str = ""):
        self.cap = cap
        self.kind = kind
        self.site = site


def compile_plan(plan: PlanNode, trace: bool = False, mesh=None) -> Callable:
    """-> fn(table_batches: dict) -> (ColumnBatch, overflow_flags[, counts]).

    The returned fn is pure/traceable; wrap in jax.jit by the session.  Join
    caps live on the plan nodes (mutated by the retry loop, forcing re-trace).
    With trace=True the result also carries per-node live-row counts — the
    EXPLAIN ANALYZE feed (reference: TraceNode tree, include/runtime/
    trace_state.h, surfaced via EXPLAIN FORMAT=analyze).

    With ``mesh`` set, the plan must have been through plan/distribute.py:
    the WHOLE query runs inside one shard_map over the mesh's row axis —
    table batches arrive shard-partitioned, ExchangeNodes lower to
    all_gather/all_to_all over ICI, partial aggregates merge via
    psum/pmin/pmax, and the final (replicated) result leaves the program.
    This is the MPP fragment DAG (SURVEY §3.2) as a single XLA program."""

    join_order: list = []
    trace_order: list = []
    n_shards = int(mesh.devices.size) if mesh is not None else 0
    # Python-side-effect trace counter: run_local's body only executes when
    # jax (re)traces — a steady-state cached execution never enters it.  The
    # session's compile telemetry (metrics.xla_retraces / compile_ms) and the
    # bucketing regression tests key off this.
    trace_count = [0]
    # bytes the program's repartition and gather collectives move between
    # chips per execution (metrics.exchange_bytes has how it is reckoned):
    # static shapes, so tallied once per trace like join_order
    exchange_bytes = [0]
    # the lowering each dense aggregate of the program was traced with
    # (ops/hashagg.dense_lowering), in trace order, the passes over their
    # input they make only to count rows, and their Pallas kernels' dense
    # MIN / MAX passes: tallied like the above
    agg_lowerings: list = []
    agg_count_passes = [0]
    agg_minmax_passes = [0]
    # lineage id -> the n of the growing dictionary whose values the last
    # trace read (column/dictionary.BAKED)
    baked: dict = {}

    def run_local(batches: dict):
        if not getattr(ACCOUNTING_TRACE, "active", False):
            trace_count[0] += 1
            metrics.xla_retraces.add(1)
        overflows: list = []
        counts: list = []
        trace_order.clear()
        moved: list = []
        ctx = (overflows, counts if trace else None, trace_order, n_shards,
               moved)
        # hoisted-literal params (plan/paramize.py) ride the batches pytree;
        # Param expr nodes read their slots from this trace-scoped binding,
        # as the order keys of growing string dictionaries from theirs
        # (column/dictionary.py); the values of those the trace reads are
        # noted, and the program is good up to them
        with bind_params(batches.get(PARAMS_KEY, ())), \
                bind_keys(batches.get(KEYS_KEY, {})), \
                recording_bakes() as read, \
                noting_lowerings() as lowered:
            out = _sub(plan, batches, overflows, ctx)
        baked.clear()
        baked.update(read)
        agg_lowerings[:] = [lowering for lowering, _, _ in lowered]
        agg_count_passes[0] = sum(passes for _, passes, _ in lowered)
        agg_minmax_passes[0] = sum(passes for _, _, passes in lowered)
        # nodes are host objects: expose them on the closure (filled at trace
        # time), return only the traced flags
        join_order.clear()
        join_order.extend(n for n, _ in overflows)
        exchange_bytes[0] = sum(moved)
        flags = tuple(f for _, f in overflows)
        if n_shards:
            # flags carry NEEDED capacities: the retry must satisfy the
            # hungriest shard, so reduce with pmax — as int32 (a capacity is
            # a row count): the TPU lowers no 64-bit max all-reduce
            flags = tuple(
                jax.lax.pmax(jnp.minimum(jnp.asarray(f), _I32_MAX)
                             .astype(jnp.int32), AXIS) for f in flags)
        if trace:
            return out, flags, tuple(counts)
        return out, flags

    if mesh is None:
        run = run_local
    else:
        from jax.sharding import PartitionSpec as P

        def run(batches: dict):
            # per-leaf in_specs (the pjit per-leaf in_axis_resources shape):
            # table batches shard over the row axis, the hoisted-literal
            # params feed replicates P() — scalar params ride the
            # partitioned batches pytree, so ONE mesh executable serves
            # every literal variant instead of baking each literal into
            # its own shard_map program.  Built per call from the batch
            # keys; jit caches on the pytree structure, so steady state
            # never reconstructs a trace.
            specs = {k: (P() if k in (PARAMS_KEY, KEYS_KEY) else P(AXIS))
                     for k in batches}
            smapped = jax.shard_map(run_local, mesh=mesh, in_specs=(specs,),
                                    out_specs=P(), check_vma=False)
            return smapped(batches)

    run.join_order = join_order
    run.trace_order = trace_order
    run.trace_count = trace_count
    run.exchange_bytes = exchange_bytes
    run.agg_lowerings = agg_lowerings
    run.agg_count_passes = agg_count_passes
    run.agg_minmax_passes = agg_minmax_passes
    run.baked = baked
    return run


def _presort_order(node, batches: dict, expected_len: int):
    """The host-precomputed sort permutation fed by the session's
    walk_presort, or None when absent / the input's positions are not the
    base table's (access-path gather, shard slice)."""
    pkey = getattr(node, "presort_input", None)
    order = batches.get(pkey) if pkey else None
    if order is not None and len(order) != expected_len:
        return None
    return order


def _eval_traced(node: PlanNode, batches: dict, ctx):
    overflows, counts, trace_order, n_shards, _moved = ctx
    out = _eval(node, batches, overflows, ctx)
    trace_order.append(node)
    c = out.live_count()
    if n_shards and getattr(node, "dist", None) == "shard":
        c = jax.lax.psum(c, AXIS)
    counts.append(c)
    return out


def _eval(node: PlanNode, batches: dict, overflows: list, ctx=None) -> ColumnBatch:
    if isinstance(node, ScanNode):
        b = batches[node.table_key]
        names = tuple(f"{node.label}.{c}" for c in node.columns)
        cols = [b.column(c) for c in node.columns]
        # bucket-padded store batches arrive with a live-prefix sel mask;
        # the static promise survives the scan (and dies at the first
        # and_sel), letting compact skip its gather on unfiltered scans
        out = ColumnBatch(names, cols, b.sel, b.num_rows,
                          live_prefix=b.live_prefix)
        if node.pushed_filter is not None:
            out = out.and_sel(eval_predicate(node.pushed_filter, out))
        return out

    if isinstance(node, FilterNode):
        child = _sub(node.child(), batches, overflows, ctx)
        return child.and_sel(eval_predicate(node.pred, child))

    if isinstance(node, ShrinkNode):
        child = _sub(node.child(), batches, overflows, ctx)
        if node.cap is None:
            node.cap = caps.first_cap(node, len(child),
                                      caps.shrink_guess(node, len(child)))
        if node.cap >= len(child):
            return child    # no cut possible, so nothing can overflow
        out, needed = shrink(child, node.cap)
        overflows.append((node, needed))
        return out

    if isinstance(node, ProjectNode):
        child = _sub(node.child(), batches, overflows, ctx)
        n = len(child)
        cols = []
        for e in node.exprs:
            c = eval_output(e, child)
            cols.append(_broadcast(c, n))
        return ColumnBatch(tuple(node.names), cols, child.sel, child.num_rows)

    if isinstance(node, JoinNode):
        left = _sub(node.children[0], batches, overflows, ctx)
        right = _sub(node.children[1], batches, overflows, ctx)
        if node.how == "cross":
            if node.cap is None:
                node.cap = caps.first_cap(node, len(left) * len(right),
                                          max(1, len(left) * len(right)))
            out, ovf = join_ops.cross_join(left, right, cap=node.cap)
        elif node.neq is not None and node.how in ("semi", "anti"):
            # EXISTS + one <> residual: range counts, no expansion; with a
            # host-precomputed build permutation, no on-device sort either
            out, ovf = join_ops.semi_join_neq(left, node.left_keys, right,
                                              node.right_keys, node.neq[0],
                                              node.neq[1], how=node.how,
                                              order=_presort_order(
                                                  node, batches, len(right)))
        elif node.strategy == "dense":
            # unique-build PK-FK join: scatter/gather over the dense key
            # domain(s), output keeps the probe's shape (no overflow
            # protocol)
            out, ovf = join_ops.dense_join(left, node.left_keys, right,
                                           node.right_keys,
                                           list(node.dense_lo),
                                           list(node.dense_span),
                                           how=node.how)
        else:
            if node.cap is None:
                # key-FK joins emit at most max(sides) rows; true many-to-many
                # expansion beyond that reports its exact need via the flag
                sides = max(1, len(left), len(right))
                node.cap = caps.first_cap(node, sides, sides)
            nb = int(FLAGS.radix_join_buckets)
            presort = _presort_order(node, batches, len(right))
            float_keys = any(right.column(k).ltype.is_float
                             for k in node.right_keys
                             if k in right.names)
            use_radix = (nb >= 2 and (nb & (nb - 1)) == 0 and
                         presort is None and not float_keys and
                         not getattr(node, "build_sorted", False) and
                         len(right) >= int(FLAGS.radix_join_min_build))
            if use_radix:
                box = getattr(node, "radix_width", None)
                if box is None:
                    box = node.radix_width = _CapBox()
                if box.cap is None:
                    # 4x average occupancy as the first guess; skew reports
                    # the exact need through the flag channel
                    box.cap = caps.first_cap(
                        box, len(right),
                        max(64, 1 << (4 * len(right) // nb - 1).bit_length()))
                out, ovf, wneed = join_ops.radix_join(
                    left, node.left_keys, right, node.right_keys,
                    how=node.how, cap=node.cap,
                    wide_keys_ok=getattr(node, "pack32_verified", False),
                    n_buckets=nb, width=box.cap)
                overflows.append((node, ovf))
                overflows.append((box, wneed))
                return out
            out, ovf = join_ops.join(
                left, node.left_keys, right, node.right_keys, how=node.how,
                cap=node.cap,
                wide_keys_ok=getattr(node, "pack32_verified", False),
                build_sorted=getattr(node, "build_sorted", False),
                order=presort)
        overflows.append((node, ovf))
        # label-qualified names are globally unique, no suffixing occurs
        return out

    if isinstance(node, MultiJoinNode):
        probe = _sub(node.children[0], batches, overflows, ctx)
        builds = [_sub(c, batches, overflows, ctx)
                  for c in node.children[1:]]
        n = ctx[3]
        reuse = node.reuse or [False] * len(node.children)
        exch = node.exch_keys or ([list(node.probe_keys)]
                                  + [list(bk) for bk in node.build_keys])
        if n:
            # the fused exchange: every input hash-repartitions ONCE on
            # the segment's key class (one shuffle round for the whole
            # segment); intermediate join results never exist, so never
            # re-shuffle.  Inputs the scheduler proved already partitioned
            # on the class — and replicated rider builds (exch None) —
            # flow through without a collective.
            if node.exch_caps is None:
                node.exch_caps = [
                    None if (reuse[i] or exch[i] is None) else
                    _CapBox(kind="shuffle", site=f"multiway[{i}]")
                    for i in range(len(node.children))]
            inputs = list(zip([probe] + builds, exch))
            shuffled = []
            for (b, keys), box in zip(inputs, node.exch_caps):
                if box is None:         # reused partition / replicated rider
                    shuffled.append(b)
                    continue
                if box.cap is None:
                    box.cap = caps.first_cap(box, len(b),
                                             max(1, 2 * len(b) // n))
                out_b, needed = _repartition_exec(b, list(keys), n, box.cap,
                                                  ctx)
                overflows.append((box, needed))
                shuffled.append(out_b)
            probe, builds = shuffled[0], shuffled[1:]
        if node.cap is None:
            sides = max(1, len(probe), *(len(b) for b in builds))
            node.cap = caps.first_cap(node, sides, sides)
        out, ovf = join_ops.multiway_join(
            probe, node.probe_keys, list(zip(builds, node.build_keys)),
            list(node.hows), cap=node.cap, level_keys=node.level_keys,
            packs=node.packs)
        overflows.append((node, ovf))
        return out

    if isinstance(node, ExchangeNode):
        child = _sub(node.child(), batches, overflows, ctx)
        if node.kind == "gather":
            return _all_gather_batch(child, ctx)
        if node.reused:
            # keyed exchange scheduler: the child is already hash-
            # partitioned on this key class — rows flow through, no
            # collective, no overflow flag
            return child
        n = ctx[3]
        keys = node.keys if node.keys is not None else list(child.names)
        if node.cap is None:
            node.cap = caps.first_cap(node, len(child),
                                      max(1, 2 * len(child) // max(1, n)))
        out, ovf = _repartition_exec(child, keys, n, node.cap, ctx)
        overflows.append((node, ovf))
        return out

    if isinstance(node, AggNode):
        child = _sub(node.child(), batches, overflows, ctx)
        merge = node.merge
        if not node.key_names:
            if merge:
                return _scalar_agg_merged(child, node.specs)
            return scalar_aggregate(child, node.specs)
        if node.strategy == "stream":
            # rows already in key order: segmented scans over the lanes as
            # they come; the kernel's own check of that order rides the
            # flag channel (a flag that is no capacity: exec/caps.settle)
            out, unordered = group_aggregate_stream(
                child, node.key_names[0], node.specs)
            overflows.append((node, unordered))
            return out
        shift = getattr(node, "key_shift", {}) or {}
        if node.strategy == "dense":
            work = child
            if shift:
                cols = list(work.columns)
                for kn, mn in shift.items():
                    i = work.names.index(kn)
                    c = cols[i]
                    cols[i] = dreplace(c, data=c.data - jnp.asarray(mn, c.data.dtype))
                work = ColumnBatch(work.names, cols, work.sel, work.num_rows)
            if merge:
                out = _dense_agg_merged(work, node.key_names, node.domains,
                                        node.specs)
            else:
                out = group_aggregate_dense(work, node.key_names, node.domains,
                                            node.specs)
            if shift:
                cols = list(out.columns)
                for kn, mn in shift.items():
                    i = out.names.index(kn)
                    c = cols[i]
                    cols[i] = dreplace(c, data=c.data + jnp.asarray(mn, c.data.dtype))
                out = ColumnBatch(out.names, cols, out.sel, out.num_rows)
            return out
        if node.key_names and getattr(node, "agg_dist", "") == "local" \
                and ctx is not None and ctx[3]:
            # cardinality-adaptive "local" arm (sorted strategy): pre-reduce
            # this shard's rows into partial-aggregate rows, shuffle only
            # the PARTIALS on the key hash, merge co-located partials once
            # (Partial Partial Aggregates; parallel/agg.py has the policy)
            from ..parallel.agg import merge_partial_agg_specs

            n = ctx[3]
            parts, fin = partial_specs(node.specs)
            mg_part = max(1, min(node.max_groups, len(child))
                          if node.max_groups else len(child))
            part = group_aggregate_sorted(child, node.key_names, parts,
                                          mg_part)
            part = ColumnBatch(part.names, part.columns, part.sel, None)
            box = getattr(node, "agg_exch_cap", None)
            if box is None:
                box = node.agg_exch_cap = _CapBox(kind="shuffle", site="agg")
            if box.cap is None:
                box.cap = caps.first_cap(box, len(part),
                                         max(1, 2 * len(part) // n))
            shuf, needed = _repartition_exec(part, node.key_names, n,
                                             box.cap, ctx)
            overflows.append((box, needed))
            final = group_aggregate_sorted(shuf, node.key_names,
                                           merge_partial_agg_specs(parts),
                                           max(1, len(shuf)))
            return finalize_partials(final, fin, node.key_names)
        mg = node.max_groups or max(1, len(child))
        return group_aggregate_sorted(child, node.key_names, node.specs, mg,
                                      order=_presort_order(node, batches,
                                                           len(child)))

    if isinstance(node, DistinctNode):
        child = _sub(node.child(), batches, overflows, ctx)
        mg = max(1, len(child))
        return group_aggregate_sorted(child, list(child.names), [], mg)

    if isinstance(node, SortNode):
        child = _sub(node.child(), batches, overflows, ctx)
        keys = [SortKey(k, asc) for k, asc in node.keys]
        if node.limit is not None:
            k = node.limit + node.offset
            if node.dist_topk:
                # per-shard top-k, all_gather the candidates, final top-k —
                # the TopNSorter merge of per-region streams (src/runtime/
                # topn_sorter.cpp) as two kernels + one collective
                local = top_k(child, keys, min(k, len(child)))
                child = _all_gather_batch(local, ctx)
            out = top_k(child, keys, k)
            if node.offset:
                out = head(out, node.limit, node.offset)
            return out
        return sort_batch(child, keys)

    if isinstance(node, LimitNode):
        child = _sub(node.child(), batches, overflows, ctx)
        return head(child, node.limit, node.offset)

    if isinstance(node, UnionNode):
        parts = [compact(_sub(c, batches, overflows, ctx)) for c in node.children]
        names = [f.name for f in node.schema.fields]
        parts = [p.rename(names) for p in parts]
        parts = [_harmonize(p, node.schema) for p in parts]
        parts = _align_string_dicts(parts)
        return concat_batches(parts)

    if isinstance(node, MembershipNode):
        child = _sub(node.children[0], batches, overflows, ctx)
        sub = _sub(node.children[1], batches, overflows, ctx)
        sub_name = sub.names[0]
        if len(sub) == 0:
            # empty list: IN -> FALSE, NOT IN -> TRUE even for NULL keys —
            # no comparison ever happens, so the result is non-NULL
            n = len(child)
            data = jnp.broadcast_to(jnp.asarray(node.negate), (n,))
            names = list(child.names) + [node.out_name]
            cols = list(child.columns) + [Column(data, None, LType.BOOL)]
            return ColumnBatch(tuple(names), cols, child.sel, child.num_rows)
        probe = ColumnBatch((node.key_col,), [child.column(node.key_col)],
                            child.sel, None)
        probe2, build2 = join_ops._align_string_keys(
            probe, [node.key_col], sub, [sub_name])
        xc = probe2.column(node.key_col)
        bc = build2.column(sub_name)
        bsel = sub.sel_mask()
        bvalid = bc.valid_mask() & bsel
        sentinel = (jnp.iinfo if bc.data.dtype.kind in "iu"
                    else jnp.finfo)(bc.data.dtype).max
        bkey = jnp.where(bvalid, bc.data, sentinel)
        bsorted = jnp.sort(bkey)
        nlive = jnp.sum(bvalid)
        pos = jnp.searchsorted(bsorted, xc.data)
        hit = (pos < nlive) & \
            (jnp.take(bsorted, jnp.clip(pos, 0, len(sub) - 1), mode="clip")
             == xc.data)
        has_null_in_list = jnp.any(bsel & ~bc.valid_mask())
        found = hit
        if node.negate:
            data = ~found
        else:
            data = found
        # SQL three-valued IN: NULL key -> NULL; a miss with NULLs
        # in the list -> NULL.  A live-empty list (all rows filtered out,
        # nonzero capacity) behaves like the empty fast path above: no
        # comparison happens, so even NULL keys yield a non-NULL result
        live_empty = nlive == 0
        validity = (xc.valid_mask() | live_empty) & (found | ~has_null_in_list)
        names = list(child.names) + [node.out_name]
        cols = list(child.columns) + [Column(data, validity, LType.BOOL)]
        return ColumnBatch(tuple(names), cols, child.sel, child.num_rows)

    if isinstance(node, ScalarSourceNode):
        child = _sub(node.children[0], batches, overflows, ctx)
        sub = compact(_sub(node.children[1], batches, overflows, ctx))
        n = len(child)
        names = list(child.names)
        cols = list(child.columns)
        live = sub.live_count()
        has_row = live > 0
        # MySQL ER_SUBQUERY_NO_1_ROW (1242): the live count rides back with
        # the needed-capacity flags; the session raises when it exceeds 1
        overflows.append((node, jnp.asarray(live, jnp.int32)))
        for i, name in enumerate(node.col_names):
            c = sub.columns[i]
            if len(sub) == 0:
                # zero-capacity source: constant NULL
                v0 = jnp.zeros((), c.data.dtype)
                val0 = jnp.asarray(False)
            else:
                v0 = c.data[0]
                val0 = c.valid_mask()[0] & has_row   # empty subquery -> NULL
            cols.append(Column(jnp.broadcast_to(v0, (n,)),
                               jnp.broadcast_to(val0, (n,)), c.ltype,
                               c.dictionary))
            names.append(name)
        return ColumnBatch(tuple(names), cols, child.sel, child.num_rows)

    if isinstance(node, WindowNode):
        from ..ops.window import window_compute

        child = _sub(node.child(), batches, overflows, ctx)
        keys = [SortKey(k, asc) for k, asc in node.order_keys]
        return window_compute(child, node.partition_names, keys, node.specs)

    if isinstance(node, ValuesNode):
        cols = []
        empty = ColumnBatch((), [], None, None)
        for i, e in enumerate(node.exprs[0]):
            c = eval_output(e, empty)
            cols.append(_broadcast(c, 1))
        return ColumnBatch(tuple(node.names), cols)

    if isinstance(node, StreamResultNode):
        # the chunk-folded aggregate's finalized batch (exec/streaming.py)
        return batches[node.key]

    raise ExecError(f"unknown plan node {type(node).__name__}")


def _sub(node, batches, overflows, ctx):
    if ctx is not None and ctx[1] is not None:
        return _eval_traced(node, batches, ctx)
    return _eval(node, batches, overflows, ctx)


# -- mesh collectives (dist mode; plan/distribute.py inserts the markers) ----

def exchange_summary(plan: PlanNode) -> dict:
    """Exchange accounting for a distributed plan — the numbers the keyed
    exchange scheduler exists to move.  One round = one EXECUTED
    synchronized repartition step: a binary shuffle join's two input
    exchanges are ONE round, a fused MultiJoin's N+1 input exchanges are
    ONE round, a lone repartition (group-by / distinct co-location) or a
    "local" adaptive agg's internal partial shuffle is one each.  Reused
    partitions (scheduler-proved, collective skipped at runtime) count in
    ``reused``, never in ``rounds`` or ``collectives`` — the EXPLAIN
    ANALYZE line and the bench JSON must report what the device actually
    paid, not what the plan tree syntactically contains.  ``collectives``
    counts individual executed repartition all_to_alls (a fused segment's
    probe + each shuffled build; replicated rider builds cost none);
    ``keys`` lists the chosen partition key (short names) per counted
    round, outermost-last."""
    rounds = 0
    reused = 0
    collectives = 0
    keys: list = []
    skip: set = set()
    seen: set = set()

    def short(cols) -> str:
        return "+".join(c.split(".")[-1] for c in (cols or ()))

    def walk(n: PlanNode) -> None:
        nonlocal rounds, reused, collectives
        if id(n) in seen:           # DAG-shared subtrees execute per parent
            return                  # trace, but count once for the metric
        seen.add(id(n))
        if isinstance(n, MultiJoinNode):
            r = n.reuse or [False] * len(n.children)
            exch = n.exch_keys or ([n.probe_keys] + list(n.build_keys))
            execd = sum(1 for i in range(len(n.children))
                        if exch[i] is not None and not r[i])
            reused += sum(1 for i in range(len(n.children))
                          if exch[i] is not None and r[i])
            collectives += execd
            if execd:
                rounds += 1
                keys.append(short(n.probe_keys))
        elif isinstance(n, JoinNode):
            reps = [c for c in n.children
                    if isinstance(c, ExchangeNode) and c.kind == "repartition"]
            if reps:
                reused += sum(c.reused for c in reps)
                execd = sum(1 for c in reps if not c.reused)
                collectives += execd
                if execd:
                    rounds += 1
                    keys.append(short(n.left_keys))
                skip.update(id(c) for c in reps)
        elif isinstance(n, ExchangeNode) and n.kind == "repartition" \
                and id(n) not in skip:
            if n.reused:
                reused += 1
            else:
                rounds += 1
                collectives += 1
                keys.append(short(n.keys) or "*")
        elif isinstance(n, AggNode) and \
                getattr(n, "agg_dist", "") == "local" \
                and n.strategy != "dense":
            rounds += 1
            collectives += 1
            keys.append(short(n.key_names))
        for c in n.children:
            walk(c)

    walk(plan)
    # outermost-last reads as execution order (keys collected top-down)
    keys.reverse()
    return {"rounds": rounds, "reused": reused, "collectives": collectives,
            "keys": keys}


def count_shuffle_rounds(plan: PlanNode) -> int:
    """Executed hash-repartition rounds (see :func:`exchange_summary`)."""
    return exchange_summary(plan)["rounds"]


def progress_totals(plan: PlanNode) -> dict:
    """HOST-side work estimates for the live progress record (obs/
    progress.py): operator count, scan count, and the executed exchange
    rounds a multi-round MPP query will pay — the denominators SHOW
    PROCESSLIST renders "m/n" against.  A plan-tree walk over host
    objects; nothing here touches device state or traced scope."""
    operators = 0
    scans = 0
    seen: set = set()

    def walk(n: PlanNode) -> None:
        nonlocal operators, scans
        if id(n) in seen:
            return
        seen.add(id(n))
        operators += 1
        if isinstance(n, ScanNode):
            scans += 1
        for c in n.children:
            walk(c)

    walk(plan)
    return {"operators": operators, "scans": scans,
            "rounds": exchange_summary(plan)["rounds"]}


def _row_bytes(b: ColumnBatch) -> int:
    """Bytes one row of ``b`` takes in a collective's buffers: every
    column's data and validity, and the row mask."""
    return 1 + sum(c.data.dtype.itemsize
                   + (0 if c.validity is None else c.validity.dtype.itemsize)
                   for c in b.columns)


def _all_gather_batch(b: ColumnBatch, ctx) -> ColumnBatch:
    """Shard-partitioned rows -> replicated full batch (one all_gather)."""
    n = ctx[3]
    # each of n chips receives the other n-1 local slices
    ctx[4].append(n * (n - 1) * len(b) * _row_bytes(b))

    def ag(x):
        return jax.lax.all_gather(x, AXIS, axis=0, tiled=True)

    cols = [dreplace(c, data=ag(c.data),
                     validity=None if c.validity is None else ag(c.validity))
            for c in b.columns]
    return ColumnBatch(b.names, cols, ag(b.sel_mask()), None)


def _repartition_exec(b: ColumnBatch, keys: list[str], n: int, cap: int,
                      ctx):
    """Hash-partition local rows on ``keys`` + all_to_all: equal keys land on
    one shard (the ExchangeSender/Receiver pair as one collective)."""
    from ..parallel.shuffle import repartition_collective

    # each of n chips sends its [n, cap] buffer less the slot it keeps
    ctx[4].append(n * (n - 1) * cap * _row_bytes(b))
    return repartition_collective(b, keys, n, cap)


def _merge_partial_cols(part: ColumnBatch, parts: list[AggSpec],
                        key_names: list[str]):
    """psum/pmin/pmax-merge the aggregate columns of a local partial table."""
    cols = []
    for name, c in zip(part.names, part.columns):
        if name in key_names:
            cols.append(c)
            continue
        spec = next(s for s in parts if s.out_name == name)
        merged = merge_collective(MERGE_OP[spec.op], c.data)
        validity = c.validity
        if validity is not None:
            validity = jax.lax.psum(validity.astype(jnp.int32), AXIS) > 0
        cols.append(dreplace(c, data=merged, validity=validity))
    return cols


def _dense_agg_merged(batch: ColumnBatch, key_names: list[str],
                      domains: list[int], specs: list[AggSpec]) -> ColumnBatch:
    """Per-shard dense partial group-by + in-network merge (the partial
    AggNode on every region + MERGE_AGG_NODE on the coordinator,
    src/exec/agg_node.cpp, as psum/pmin/pmax over ICI)."""
    parts, fin = partial_specs(specs)
    part = group_aggregate_dense(batch, key_names, domains, parts)
    cols = _merge_partial_cols(part, parts, key_names)
    present = jax.lax.psum(part.sel_mask().astype(jnp.int32), AXIS) > 0
    merged = ColumnBatch(part.names, cols, present, None)
    return finalize_partials(merged, fin, key_names)


def _scalar_agg_merged(batch: ColumnBatch, specs: list[AggSpec]) -> ColumnBatch:
    parts, fin = partial_specs(specs)
    part = scalar_aggregate(batch, parts)
    cols = _merge_partial_cols(part, parts, [])
    merged = ColumnBatch(part.names, cols, None, None)
    return finalize_partials(merged, fin, [])


def _broadcast(c: Column, n: int) -> Column:
    data = jnp.asarray(c.data)
    if data.ndim == 0:
        data = jnp.broadcast_to(data, (n,))
    v = c.validity
    if v is not None and jnp.ndim(v) == 0:
        v = jnp.broadcast_to(v, (n,))
    return dreplace(c, data=data, validity=v)


def _align_string_dicts(parts: list[ColumnBatch]) -> list[ColumnBatch]:
    """Remap string columns of UNION arms onto shared dictionaries."""
    from ..column.dictionary import NULL_CODE, Dictionary
    import numpy as np

    if len(parts) < 2:
        return parts
    out = [list(p.columns) for p in parts]
    for i, c0 in enumerate(parts[0].columns):
        if c0.ltype is not LType.STRING:
            continue
        dicts = [p.columns[i].dictionary for p in parts]
        if any(d is None for d in dicts):
            raise ExecError("UNION string column lacks a dictionary")
        if all(d._id == dicts[0]._id for d in dicts):
            continue
        values = dicts[0].values
        for d in dicts[1:]:
            values = np.union1d(values, d.values)
        merged = Dictionary(values)
        for pi, p in enumerate(parts):
            c = p.columns[i]
            remap = jnp.asarray(np.searchsorted(values, c.dictionary.values)
                                .astype(np.int32))
            data = jnp.where(c.data >= 0,
                             jnp.take(remap, jnp.clip(c.data, 0, None), mode="clip"),
                             NULL_CODE)
            out[pi][i] = dreplace(c, data=data, dictionary=merged)
    return [ColumnBatch(p.names, cols, p.sel, p.num_rows)
            for p, cols in zip(parts, out)]


def _harmonize(p: ColumnBatch, schema) -> ColumnBatch:
    """Cast union arms to the unified schema's types."""
    from ..expr.compile import cast_column

    cols = []
    for c, f in zip(p.columns, schema.fields):
        if c.ltype != f.ltype:
            if c.ltype is LType.STRING or f.ltype is LType.STRING:
                raise ExecError("UNION of string and non-string columns")
            c = cast_column(c, f.ltype)
        cols.append(c)
    return ColumnBatch(p.names, cols, p.sel, p.num_rows)
