"""Out-of-core streaming scans: double-buffered prefetch + chunk folding.

``device_table_batch`` bounds a scan by what fits in device memory at once.
This module removes that bound for the plan shape the bound hurts most —
scan -> filter -> aggregate — by running it as a FOLD over the table's
chunked segments (storage/streamchunks.py):

- eligibility (``eligible``): the whole plan must be one Project/Filter
  chain under the root down to a single AggNode, then a Project/Filter
  chain down to exactly one ScanNode.  The aggregate must be expressible
  as mergeable partials (ops/hashagg.partial_specs): no DISTINCT, no
  row-set aggregates, and no scalar (keyless) stddev/variance — the
  keyless kernel uses a mean-centered formula the sumsq partial form is
  not bit-identical to;
- the fold step is ONE jitted program: evaluate the below-agg chain over
  a chunk, partial-aggregate it, merge into the accumulator under the
  MERGE_OP protocol.  Carry and chunk are passed with
  ``donate_argnums=(0, 1)`` so the device recycles the accumulator
  in place and frees each chunk the moment it folds — steady-state
  device residency is two chunks (the one folding + the one prefetched);
- a daemon thread stages chunk i+1 (coldfs read -> decode -> device put)
  through a Queue(maxsize=1) while chunk i folds, so host I/O overlaps
  device compute.  ``stream_prefetch_wait_ms`` vs per-chunk stage time is
  the overlap measurement;
- sorted-strategy accumulators carry an overflow bit folded into the
  carry (read ONCE on host, after the loop); overflow restarts the whole
  fold with a doubled accumulator, bounded by the table's row count;
- the finalize step (partials -> user aggregates, then the remainder of
  the plan above the aggregate) runs as one more jitted program over a
  plan copy whose agg subtree is replaced by a StreamResultNode leaf.

With ``streaming_scan`` off (or any gate failing) the session takes the
resident path unchanged — the off-switch is bit-identical by construction
for everything streaming accepts.
"""

from __future__ import annotations

import copy
import warnings

import jax
import jax.numpy as jnp

from ..column.batch import ColumnBatch, bucket_capacity, concat_batches
from ..expr.params import PARAMS_KEY, bind_params
from ..obs import trace
from ..ops.hashagg import (ROW_AGGS, group_aggregate_dense,
                           group_aggregate_sorted, partial_specs,
                           scalar_aggregate)
from ..parallel.agg import merge_partial_agg_specs, rewrap_partial
from ..plan.nodes import (AggNode, FilterNode, LimitNode, PlanNode,
                          ProjectNode, ScanNode, SortNode, StreamResultNode)
from ..storage.streamchunks import ChunkSource
from ..utils import metrics
from ..utils.flags import FLAGS, define
from ..utils.prefetch import staged
from . import executor

define("streaming_scan", True,
       "stream eligible scan->filter->aggregate plans over chunked "
       "segments instead of materializing the whole table on device "
       "(off-switch: the resident path, bit-identical)")
define("streaming_min_rows", 1 << 18,
       "tables below this row count always take the resident path — "
       "chunking a table that fits comfortably only adds staging cost")

# the batches-dict slot the remainder plan's StreamResultNode leaf reads
STREAM_KEY = "__stream__"

# keyless stddev/variance use hashagg's mean-centered formula; the sumsq
# partial finalize is a different float expression — not bit-identical
_SCALAR_NO_PARTIAL = ("stddev", "stddev_samp", "variance", "var_samp")

# the chain nodes a fold can leave for the finalize program (above the
# agg) / evaluate per chunk (below it) — anything else (joins, windows,
# distinct, unions, subquery sources) needs cross-chunk row visibility
_ABOVE_OK = (ProjectNode, FilterNode, SortNode, LimitNode)
_BELOW_OK = (ProjectNode, FilterNode)


def eligible(plan: PlanNode, scan_node=None):
    """-> (above_chain, agg, below_root, scan) when ``plan`` is a
    chunk-foldable single-scan aggregate, else None.  ``scan_node`` (when
    given) must be the one ScanNode the walk lands on — the session calls
    this per scan it is about to stage."""
    above: list = []
    node = plan
    while not isinstance(node, AggNode):
        if isinstance(node, _ABOVE_OK) and len(node.children) == 1:
            above.append(node)
            node = node.children[0]
        else:
            return None
    agg = node
    if agg.merge or getattr(agg, "agg_dist", ""):
        return None
    if len(agg.children) != 1:
        return None
    try:
        parts, _fin = partial_specs(agg.specs)
    except ValueError:          # ROW_AGGS have no scalar partial form
        return None
    if any(p.distinct for p in parts) or any(s.distinct for s in agg.specs):
        return None
    if not agg.key_names and any(s.op in _SCALAR_NO_PARTIAL
                                 for s in agg.specs):
        return None
    below = agg.children[0]
    node = below
    while not isinstance(node, ScanNode):
        if isinstance(node, _BELOW_OK) and len(node.children) == 1:
            node = node.children[0]
        else:
            return None
    scan = node
    if scan.children or getattr(scan, "ann", None) is not None:
        return None
    if scan_node is not None and scan is not scan_node:
        return None
    return above, agg, below, scan


def stream_source(batches: dict):
    """The (table_key, ChunkSource) riding this execution's batches, or
    None — how _run_plan recognizes a streamed execution."""
    for k, v in batches.items():
        if isinstance(v, ChunkSource):
            return k, v
    return None


def _dead_zeros(struct):
    """A concrete carry matching ``struct`` with every leaf zeroed — the
    fold identity: sel all-False (no live groups), validity all-False,
    data all-identity-zero (harmless: dead lanes never merge)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def _resize_rows(struct, cap: int):
    """Rewrite leading dimension of every leaf to ``cap`` (partial tables
    are [chunk_capacity]; the accumulator is [acc_cap])."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cap,) + tuple(s.shape[1:]), s.dtype),
        struct)


def _same_struct(a, b) -> bool:
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    # ShapeDtypeStruct metadata, never tracers
    # tpulint: disable-next-line=RETRACE
    return ta == tb and len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype for x, y in zip(la, lb))


def _shift_keys(batch: ColumnBatch, shift: dict, sign: int) -> ColumnBatch:
    """The dense-strategy key rebasing the resident executor applies
    around group_aggregate_dense (-1 going in, +1 coming out)."""
    if not shift:
        return batch
    cols = list(batch.columns)
    for kn, mn in shift.items():
        i = batch.names.index(kn)
        c = cols[i]
        off = jnp.asarray(mn, c.data.dtype)
        cols[i] = c.with_data(c.data - off if sign < 0 else c.data + off)
    return ColumnBatch(batch.names, cols, batch.sel, batch.num_rows)


class StreamOverflow(RuntimeError):
    """Sorted accumulator hit capacity mid-fold; restart with more."""


class StreamRunner:
    """One plan entry's streaming executor: the jitted fold step, the
    settled accumulator shape, and the finalize/remainder program —
    cached on the entry so steady-state re-runs never re-trace."""

    def __init__(self, plan: PlanNode, table_key: str):
        parsed = eligible(plan)
        if parsed is None:          # the session gated on this already
            raise executor.ExecError("plan is not streaming-eligible")
        self.plan = plan
        self.table_key = table_key
        self.above, self.agg, self.below, self.scan = parsed
        self.parts, self.finalize = partial_specs(self.agg.specs)
        self.merge_specs = merge_partial_agg_specs(self.parts)
        self.keys = list(self.agg.key_names)
        self.shift = dict(getattr(self.agg, "key_shift", {}) or {})
        self.acc_cap = 0            # sorted strategy only; set per chunk set
        self.cap_limit = 0
        self._skey = None
        self._jit_step = None
        self._acc_struct = None
        self._fin_jit = None
        # the finalize program runs the plan ABOVE the aggregate against
        # the folded result: shallow node copies, so join caps / presort
        # state on the live plan never alias the remainder's
        rem: PlanNode = StreamResultNode(key=STREAM_KEY)
        rem.schema = getattr(self.agg, "schema", None)
        for anc in reversed(self.above):
            c = copy.copy(anc)
            c.children = [rem]
            rem = c
        self.remainder = rem

    # -- the fold step (pure/traceable) ---------------------------------
    def _partial(self, chunk: ColumnBatch, params) -> ColumnBatch:
        with bind_params(params):
            child = executor._eval(self.below, {self.table_key: chunk}, [])
        if not self.keys:
            return rewrap_partial(scalar_aggregate(child, self.parts))
        if self.agg.strategy == "dense":
            work = _shift_keys(child, self.shift, -1)
            return rewrap_partial(group_aggregate_dense(
                work, self.keys, self.agg.domains, self.parts))
        # per-chunk cap = chunk capacity: a chunk cannot carry more groups
        # than rows, so the PARTIAL can never overflow — only the merge
        # into the accumulator needs the overflow bit
        return rewrap_partial(group_aggregate_sorted(
            child, self.keys, self.parts, len(chunk)))

    def _merge(self, acc: ColumnBatch, part: ColumnBatch):
        both = concat_batches([acc, part])
        if not self.keys:
            return (rewrap_partial(scalar_aggregate(both, self.merge_specs)),
                    jnp.asarray(False))
        if self.agg.strategy == "dense":
            return (rewrap_partial(group_aggregate_dense(
                both, self.keys, self.agg.domains, self.merge_specs)),
                jnp.asarray(False))
        out, ovf = group_aggregate_sorted(both, self.keys, self.merge_specs,
                                          self.acc_cap, with_overflow=True)
        return rewrap_partial(out), ovf

    def _step(self, carry, chunk, params):
        acc, ovf = carry
        acc2, movf = self._merge(acc, self._partial(chunk, params))
        return acc2, ovf | movf

    def _finalize_batch(self, acc: ColumnBatch) -> ColumnBatch:
        from ..ops.hashagg import finalize_partials
        out = acc
        if self.keys and self.agg.strategy == "dense":
            out = _shift_keys(out, self.shift, +1)
        return finalize_partials(out, self.finalize, self.keys)

    # -- compilation bootstrap ------------------------------------------
    def _ensure_step(self, source: ChunkSource, params) -> None:
        cs = source.chunks
        if not self.cap_limit:
            self.cap_limit = bucket_capacity(max(1, cs.total_rows))
            if self.agg.strategy == "sorted" and self.keys:
                want = self.agg.max_groups or 1024
                self.acc_cap = min(bucket_capacity(want), self.cap_limit)
        skey = (cs.capacity, cs.names,
                tuple(str(cs._dtypes[n]) for n in cs.names),
                tuple(bool(cs._has_validity[n]) for n in cs.names),
                self.acc_cap)
        if self._jit_step is not None and self._skey == skey:
            return
        chunk_struct = cs.device_struct()
        # the accumulator's pytree is the FIXPOINT of the step: partial
        # columns can gain validity after one merge (count: None -> ct>0)
        # — iterate abstractly (eval_shape; nothing runs on device) until
        # the carry structure maps to itself, so the jitted fold compiles
        # exactly once
        acc_struct = jax.eval_shape(self._partial, chunk_struct, params)
        if self.keys and self.agg.strategy == "sorted":
            acc_struct = _resize_rows(acc_struct, self.acc_cap)
        ovf_struct = jax.ShapeDtypeStruct((), jnp.bool_)
        for _ in range(4):
            nxt, _o = jax.eval_shape(self._step, (acc_struct, ovf_struct),
                                     chunk_struct, params)
            if _same_struct(nxt, acc_struct):
                break
            acc_struct = nxt
        else:
            raise executor.ExecError(
                "streaming accumulator structure did not settle")
        self._acc_struct = acc_struct
        self._jit_step = jax.jit(self._step, donate_argnums=(0, 1))
        self._fin_jit = None        # acc structure moved: re-trace finalize
        self._skey = skey

    # -- the drive loop --------------------------------------------------
    def run(self, source: ChunkSource, batches: dict, qp) -> ColumnBatch:
        params = batches.get(PARAMS_KEY, ())
        cs = source.chunks
        nlive = sum(1 for l in cs.live if l)
        skipped = nlive - len(source.keep)
        if skipped:
            metrics.stream_chunks_skipped.add(skipped)
        stats = {"chunks": 0, "chunks_total": cs.n_chunks,
                 "skipped": skipped, "bytes_h2d": 0,
                 "prefetch_wait_ms": 0.0, "stage_ms": 0.0, "restarts": 0}
        with warnings.catch_warnings():
            # CPU backends decline buffer donation with a warning per
            # compile; the fold is donation-correct either way
            warnings.filterwarnings("ignore",
                                    message=".*donated buffers.*")
            while True:
                self._ensure_step(source, params)
                acc, ovf = self._fold(source, params, qp, stats)
                with trace.span("stream.sync"):
                    # the host blocks here until the chip has folded the
                    # last chunk
                    overflowed = bool(jax.device_get(ovf))
                if not overflowed:
                    break
                # sorted accumulator overflowed: the only carry-dependent
                # capacity.  Grow (bounded by the table's row count — the
                # true group count can never exceed it) and re-fold
                if self.acc_cap >= self.cap_limit:
                    raise executor.ExecError(
                        "stream aggregate overflow at table row capacity")
                self.acc_cap = min(self.acc_cap * 2, self.cap_limit)
                self._jit_step = None
                metrics.stream_restarts.add(1)
                stats["restarts"] += 1
            out = self._run_finalize(acc, params)
        trace.event("stream", **{k: (round(v, 3)
                                     if isinstance(v, float) else v)
                                 for k, v in stats.items()})
        return out

    def _fold(self, source: ChunkSource, params, qp, stats):
        cs = source.chunks
        # zero chunks survived pruning: fold chunk 0 with an all-False sel
        # so the aggregate still sees its (empty) input shape — COUNT
        # renders 0, not a missing row
        dead = not source.keep
        ids = source.keep or [0]

        # the shared double-buffer discipline (utils/prefetch.staged):
        # chunk i+1 stages on a daemon thread while chunk i folds — the
        # same staging the store daemons use for cold-segment fragment
        # folds, so both planes keep one prefetch truth
        it = staged(ids, lambda i: cs.load_chunk(i, dead=dead),
                    name="stream-prefetch")
        carry = (_dead_zeros(self._acc_struct), jnp.asarray(False))
        staged_ms: dict = {}
        try:
            for m, i in enumerate(ids):
                if qp is not None:
                    qp.beat(operator=f"StreamScan({self.table_key})",
                            chunk_no=m, chunks_total=len(ids))
                with trace.timed("stream.prefetch", chunk=i) as sp:
                    _i, (dev, nbytes, seams) = next(it)
                wait = sp.ms
                metrics.stream_prefetch_wait_ms.observe(wait)
                metrics.stream_bytes_h2d.add(nbytes)
                stats["prefetch_wait_ms"] += wait
                stats["bytes_h2d"] += nbytes
                for seam, ms in seams.items():
                    staged_ms[seam] = staged_ms.get(seam, 0.0) + ms
                with trace.span("stream.fold", chunk=i):
                    carry = self._jit_step(carry, dev, params)
                if not dead:
                    metrics.stream_chunks.add(1)
                    stats["chunks"] += 1
            if qp is not None:
                qp.beat(chunk_no=len(ids), chunks_total=len(ids))
        finally:
            it.close()      # stops the stager and drains on early exit
            # the stager thread's seams (storage/streamchunks.load_chunk),
            # credited to the statement once per fold: they overlap the
            # loop above, and stage_ms is their sum
            for seam, ms in staged_ms.items():
                trace.add(seam, ms)
                stats["stage_ms"] += ms
        return carry

    def _run_finalize(self, acc: ColumnBatch, params) -> ColumnBatch:
        if self._fin_jit is None:
            raw = executor.compile_plan(self.remainder)

            def fin(a, ps):
                out, _flags = raw({STREAM_KEY: self._finalize_batch(a),
                                   PARAMS_KEY: ps})
                return out

            self._fin_jit = jax.jit(fin)
        with trace.span("stream.finalize"):
            return self._fin_jit(acc, params)


def run_streamed(session, entry: dict, batches: dict, qp) -> ColumnBatch:
    """Entry point from the session's _run_plan: fold the ChunkSource in
    ``batches`` and return the (padded) result batch for egress."""
    src = stream_source(batches)
    if src is None:
        raise executor.ExecError("no chunk source in batches")
    table_key, source = src
    plan = entry["plan"]
    runner = entry.get("stream_runner")
    if runner is None or runner.plan is not plan:
        runner = entry["stream_runner"] = StreamRunner(plan, table_key)
    return runner.run(source, batches, qp)
