"""Static capacities of a plan's shrink, join and exchange nodes.

A traced program has static shapes, so every operator that packs or expands
rows (``ShrinkNode``, a sort-strategy ``JoinNode``, ``MultiJoinNode``, a
repartition ``ExchangeNode`` and the ``_CapBox`` knobs of the radix join,
the fused exchange and the local aggregate) is compiled for a capacity and
returns, as a flag, the rows it needed.  This module owns the two decisions
around that: what a node is first traced with, and what changes when a flag
reports more than its capacity.  The session's and the dispatcher's retry
loops call :func:`settle`; the executor calls :func:`first_cap`.

The rule of :func:`settle`: a plan compiles at most twice for its caps.  A
holder whose flag is over its capacity grows to the need (rounded up to a
power of two, so runs over slightly different data reuse the executable).
Every holder *downstream* of it saw only the rows that fit, so its own flag
says nothing: it grows to the bound the new cap implies — the overflowed
holder's exact need, carried up through every operator that puts out at most
its input's rows (a filter, a group-by, a shrink, a semi or unique-build
join) — or, where no row bound reaches it (an exchange, a knob), is traced
again at its input's static size (``cap_full``: every row to one
destination).  Neither can overflow, whatever the chain's length.  Only a
many-to-many join above an overflow, whose need has no bound but its flag,
can ask for a third compile.

One flag is no capacity and settles here all the same: a ``stream``
aggregate's check of the order its planner claimed (0, or 1 when some live
row's key lay below the row before it).  Raised, the node takes the strategy
it would have had (``AggNode.unstream``) and the plan is traced again: one
more compile, like a cap's, and never an answer from rows out of order.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

from ..utils import metrics
from ..plan.nodes import (AggNode, DistinctNode, FilterNode, JoinNode,
                          LimitNode, MembershipNode, MultiJoinNode, PlanNode,
                          ProjectNode, ScalarSourceNode, ScanNode, ShrinkNode,
                          SortNode, WindowNode)

# a shrink's first guess where nothing says better: a 16x cut
SHRINK_CUT = 16
_BOX_ATTRS = ("radix_width", "agg_exch_cap")
# holders whose flag counts the live rows of their output (an exchange's or
# a knob's counts its fullest bucket)
_COUNTS_OUTPUT = (ShrinkNode, JoinNode, MultiJoinNode)
# operators that put out at most their first child's live rows
_ROW_BOUNDED = (FilterNode, ProjectNode, ShrinkNode, AggNode, SortNode,
                LimitNode, DistinctNode, WindowNode, MembershipNode,
                ScalarSourceNode)


def pow2(n: int) -> int:
    """``n`` rounded up to a power of two, at least 16."""
    return max(16, 1 << (max(1, int(n)) - 1).bit_length())


def first_cap(holder, bound: int, guess: int) -> int:
    """The capacity a holder without one is traced with: ``guess``, or,
    where :func:`settle` marked it downstream of an overflow, ``bound`` —
    the static size of its input, which no run can exceed — and never less
    than it had."""
    full = getattr(holder, "cap_full", None)
    return guess if full is None else max(bound, full)


def shrink_guess(node: ShrinkNode, child_len: int) -> int:
    """A shrink's first capacity.  Where the subtree under it applies no
    predicate, the rows that reach it are the probe table's rows
    (``live_rows``, from the store's row count at plan time): the cut is
    sized for them, which for a table that fills its bucket is no cut at
    all and no retry.  Else a 16x cut; the flag reports the true count, so
    one retry lands exactly when the guess is short."""
    rows = getattr(node, "live_rows", None)
    if rows is not None:
        return min(child_len, pow2(rows))
    return max(1024, pow2(child_len // SHRINK_CUT))


def _unfiltered_rows(n: PlanNode, rows_fn) -> Optional[int]:
    """Rows the subtree puts out if it applies no predicate: a scan of a
    whole table, carried through projections, shrinks and unique-build
    (dense) inner / left joins whose build side is unfiltered too (a foreign
    key finds its row).  ``None`` once anything filters."""
    if isinstance(n, ScanNode):
        if n.pushed_filter is not None or n.ann is not None:
            return None
        return int(rows_fn(n.table_key))
    if isinstance(n, (ProjectNode, ShrinkNode)):
        return _unfiltered_rows(n.children[0], rows_fn)
    if isinstance(n, JoinNode) and n.strategy == "dense" \
            and n.how in ("inner", "left") and n.residual is None:
        if _unfiltered_rows(n.children[1], rows_fn) is None:
            return None
        return _unfiltered_rows(n.children[0], rows_fn)
    return None


def annotate_rows(plan: PlanNode, rows_fn) -> None:
    """Give every shrink over an unfiltered subtree that subtree's row
    count (``live_rows``); ``rows_fn(table_key)`` is the store's."""
    seen: set = set()

    def walk(n: PlanNode) -> None:
        if id(n) in seen:
            return
        seen.add(id(n))
        if isinstance(n, ShrinkNode):
            n.live_rows = _unfiltered_rows(n.children[0], rows_fn)
        for c in n.children:
            walk(c)

    walk(plan)


class Settled(NamedTuple):
    grew: bool      # a cap changed: the executable has to be traced again
    slots: int      # sum of the capacities the flags were checked against
    live: int       # sum of the needs the flags reported


def _boxes(n: PlanNode) -> list:
    out = [getattr(n, a, None) for a in _BOX_ATTRS]
    out.extend(getattr(n, "exch_caps", None) or ())
    return [b for b in out if b is not None]


def _carries_probe(n: PlanNode) -> bool:
    """Whether ``n`` puts out at most the live rows of its first child: the
    operators that filter, project, group, order or cut rows, and a join
    whose build side adds none (semi, anti, or a unique build)."""
    if isinstance(n, JoinNode):
        return n.how in ("semi", "anti") or \
            (n.strategy == "dense" and n.how in ("inner", "left"))
    return isinstance(n, _ROW_BOUNDED)


def _mark_downstream(plan: PlanNode, grown: set, rows: dict) -> None:
    """Every cap holder above a grown one (``grown``: ids) gets the bound
    the new cap implies.  ``rows`` maps the id of a holder whose flag
    counts its output's live rows to that count: exact where nothing under
    the holder overflowed, and for the grown holders themselves.  From
    there a live-row bound is carried up through every operator that puts
    out at most its first child's rows; a holder above an overflow grows to
    it, or, where no such bound reaches it (a many-to-many join, an
    exchange, a knob), is traced again at its input's static size
    (``cap_full``).  A node's own knobs sit between its children and its
    output: downstream of the children, upstream of the node's cap."""
    def full(h) -> None:
        if id(h) not in grown and getattr(h, "cap", None) is not None:
            h.cap_full, h.cap = h.cap, None

    def visit(n: PlanNode) -> tuple:
        kids = [visit(c) for c in n.children]
        below = any(k[0] for k in kids)
        boxes = _boxes(n)
        if below:
            for b in boxes:
                full(b)
        inside = below or any(id(b) in grown for b in boxes)
        if id(n) in rows and (id(n) in grown or not inside):
            bound = rows[id(n)]
        else:
            bound = kids[0][1] if kids and _carries_probe(n) else None
        if inside and id(n) not in grown \
                and getattr(n, "cap", None) is not None:
            if bound is None:
                full(n)
            else:
                n.cap = max(n.cap, pow2(bound))
        return inside or id(n) in grown, bound

    visit(plan)


def settle(plan: Optional[PlanNode], join_order, needs) -> Settled:
    """Hold one execution's flags against the capacities it ran with.
    ``needs[i]`` is the rows ``join_order[i]`` reported (``None`` for a flag
    that is no capacity: a scalar subquery's count; a stream aggregate's
    order check reports 0 or 1, and 1 takes the node off that strategy).
    Grows what overflowed and, given the ``plan`` the holders belong to, what is
    downstream of it; an AOT executable's shims have no plan (the caller
    falls back to a fresh compile)."""
    grown: set = set()
    rows: dict = {}
    slots = live = 0
    for holder, need in zip(join_order, needs):
        if need is None:
            continue
        is_agg = isinstance(holder, AggNode)
        if is_agg or getattr(holder, "kind", "") == "AggNode":
            # a stream aggregate's order check.  An AOT executable's shim
            # has no node to change: its caller compiles the plan afresh,
            # and that program's own check lands on the node
            if not need:
                metrics.stream_agg_runs.add(1)
            else:
                grown.add(id(holder))
                if is_agg:
                    holder.unstream()
                    metrics.stream_agg_fallbacks.add(1)
            continue
        cap = holder.cap or 0
        slots += cap
        live += min(need, cap)
        if isinstance(holder, _COUNTS_OUTPUT):
            rows[id(holder)] = need
        if need > cap:
            # flags carry the exact need (join output cardinality, largest
            # shuffle bucket, live rows under a shrink): jump straight there
            holder.cap = pow2(need)
            grown.add(id(holder))
    if grown and plan is not None:
        _mark_downstream(plan, grown, rows)
    return Settled(bool(grown), slots, live)
