"""IndexSelector: choose each scan's access path from its predicates.

Reference: src/physical_plan/index_selector.cpp (1549 LoC of cost/heuristic
index choice across primary/secondary/fulltext/vector paths) feeding
RocksdbScanNode ranges.

TPU re-design: a full-region columnar scan is the BASELINE here (brute-force
device scans are what the hardware is good at), so index selection is about
what NOT to ship to the device:

- **point**: WHERE fixes every primary-key column by equality and the
  statement is a plain row fetch -> answer from the host row tier, no XLA
  program at all (the OLTP path; reference: primary-index point SELECT).
- **secondary**: an equality on a declared KEY column -> host index gathers
  the matching row positions; the device program runs over just those rows
  (reference: secondary-index range read).  Only chosen when the estimated
  match fraction is small — at high selectivity the full scan wins.
- **pk_range**: a range (or a non-point equality) on a single-column
  orderable primary key -> the host's sorted key order names the matching
  row positions; the device program runs over a fixed-capacity gather of
  them out of the resident image (reference: primary-index range read).
  Same selectivity gate as the secondary arm.
- **zonemap**: range/equality predicates on numeric/temporal columns prune
  whole regions by their min/max before upload (reference: the column
  tier's statistics pruning).
- **full**: everything else.

The same analysis annotates EXPLAIN so the chosen path is visible and flips
with predicates, and drives the batch builders in exec/session.py.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..expr.ast import AggCall, Call, ColRef, Expr, Lit, Subquery

# predicates usable for zone-map pruning: op -> (lo, hi) interval builder
_RANGE_OPS = {"eq", "lt", "le", "gt", "ge"}


@dataclass
class ScanPredicates:
    """Per-column conjunctive constraints extracted from a pushed filter."""
    eq: dict = field(default_factory=dict)        # col -> literal value
    ranges: dict = field(default_factory=dict)    # col -> [lo, hi] (closed,
    #                                               None = unbounded)


def _strip(name: str) -> str:
    return name.split(".", 1)[1] if "." in name else name


def analyze_conjuncts(e: Optional[Expr]) -> ScanPredicates:
    """Walk the AND-tree collecting col-vs-literal comparisons; anything
    else is ignored (the device filter still applies it — index choices
    must only be conservative supersets)."""
    sp = ScanPredicates()
    if e is None:
        return sp

    def bound(col, op, v):
        lo, hi = sp.ranges.get(col, [None, None])
        try:
            if op in ("eq", "gt", "ge"):
                lo = v if lo is None else max(lo, v)
            if op in ("eq", "lt", "le"):
                hi = v if hi is None else min(hi, v)
        except TypeError:
            return          # mixed-type literals on one column: no constraint
        sp.ranges[col] = [lo, hi]

    def visit(x):
        if isinstance(x, Call) and x.op == "and":
            for a in x.args:
                visit(a)
            return
        if isinstance(x, Call) and x.op == "between" and len(x.args) == 3:
            c, lo, hi = x.args
            # col BETWEEN lit AND lit is col >= lo AND col <= hi; a NULL
            # bound matches no row, which "no constraint" safely covers
            if isinstance(c, ColRef) and isinstance(lo, Lit) \
                    and isinstance(hi, Lit) and lo.value is not None \
                    and hi.value is not None:
                bound(_strip(c.name), "ge", lo.value)
                bound(_strip(c.name), "le", hi.value)
            return
        if not isinstance(x, Call) or x.op not in _RANGE_OPS:
            return
        if len(x.args) != 2:
            return
        a, b = x.args
        op = x.op
        if isinstance(a, Lit) and isinstance(b, ColRef):
            a, b = b, a
            op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                  "eq": "eq"}[op]
        if not (isinstance(a, ColRef) and isinstance(b, Lit)):
            return
        col = _strip(a.name)
        v = b.value
        if v is None:
            return
        if op == "eq":
            sp.eq[col] = v
        bound(col, op, v)

    visit(e)
    return sp


def pk_range_capacity(matches: int) -> int:
    """Rows of the pk_range arm's gathered scan input: the capacity bucket
    ``matches`` pad into, by the rule full tables follow — every literal's
    range of a statement lands in one bucket, so its plan compiles once."""
    from ..column.batch import bucket_capacity
    from ..utils.flags import FLAGS

    return bucket_capacity(matches, int(FLAGS.batch_bucket_min))


def pk_range_desc(col: str, matches: int, rows: int) -> str:
    """EXPLAIN's text for the pk_range arm."""
    return (f"pk_range({col}: {matches} of {rows} rows, "
            f"capacity {pk_range_capacity(matches)})")


def is_point_statement(stmt) -> bool:
    """A statement shape the host row tier can answer directly."""
    if (stmt.joins or stmt.ctes or stmt.union or stmt.distinct
            or stmt.group_by or stmt.having or stmt.table is None):
        return False
    for it in stmt.items:
        if it.expr is None:                        # SELECT * is fine
            continue
        if _has_special(it.expr):
            return False
    return not (stmt.where is None) and not _has_special(stmt.where)


def _has_special(e) -> bool:
    """Aggregates / window calls / subqueries block the host fast path."""
    if isinstance(e, (AggCall, Subquery)):
        return True
    if type(e).__name__ == "WindowCall":
        return True
    return any(_has_special(a) for a in getattr(e, "args", ()))


def point_key(stmt, pk_cols: list[str]) -> Optional[dict]:
    """If WHERE is EXACTLY a pk-equality conjunction — every conjunct a
    ``pk_col = literal``, every pk column fixed, duplicates consistent —
    the key values.  Any residual term (non-pk column, conflicting
    duplicate, non-eq op) disqualifies the fast path: the device filter
    would have dropped rows the host fetch cannot."""
    terms: list = []
    if not _collect_eq_terms(stmt.where, terms):
        return None
    key: dict = {}
    for col, v in terms:
        if col not in pk_cols:
            return None
        if col in key and key[col] != v:
            return None          # id = 7 AND id = 8: contradiction
        key[col] = v
    if set(key) != set(pk_cols):
        return None
    return key


def _collect_eq_terms(e, out: list) -> bool:
    """Flatten an AND-tree of col = literal terms; False if any other
    shape appears."""
    if isinstance(e, Call) and e.op == "and":
        return all(_collect_eq_terms(a, out) for a in e.args)
    if isinstance(e, Call) and e.op == "eq" and len(e.args) == 2:
        a, b = e.args
        if isinstance(b, ColRef) and isinstance(a, Lit):
            a, b = b, a
        if isinstance(a, ColRef) and isinstance(b, Lit):
            out.append((_strip(a.name), b.value))
            return True
    return False


def choose_access(info, store, pred: ScanPredicates,
                  secondary_max_fraction: float = 0.2, db=None):
    """-> ("secondary", index_name, col, value) |
    ("global", index_name, col, value) | ("pk_range", col, lo, hi) |
    ("partition", parts, total) | ("zonemap", ranges) | ("full",).
    Point lookups are decided at the statement level, not here.  ``db``
    (the Database) resolves global indexes' backing stores; without it the
    global route is not considered."""
    # secondary equality beats everything when selective enough
    for ix in info.indexes:
        if ix.kind not in ("key", "unique"):
            continue
        if ix.params.get("state", "public") != "public":
            continue    # backfilling/failed: not yet (or never) choosable
        col = ix.columns[0]
        if col in pred.eq:
            n = max(store.num_rows, 1)
            matches = store.secondary_count(col, pred.eq[col])
            if matches is not None and matches / n <= secondary_max_fraction:
                return ("secondary", ix.name, col, pred.eq[col])
    # global index: equality on the index prefix routes through the backing
    # table (its own regions) then joins back by pk (the reference's
    # global-index lookup join, select_manager_node.cpp:1081)
    if db is not None:
        from .globalindex import backing_table_name

        for ix in info.indexes:
            if ix.kind not in ("global", "global_unique"):
                continue
            if ix.params.get("state", "public") != "public":
                continue
            col = ix.columns[0]
            if col not in pred.eq:
                continue
            bkey = f"{info.database}." \
                   f"{backing_table_name(info.name, ix.name)}"
            bstore = db.stores.get(bkey)
            if bstore is None:
                continue
            n = max(store.num_rows, 1)
            matches = bstore.secondary_count(col, pred.eq[col])
            if matches is not None and matches / n <= secondary_max_fraction:
                return ("global", ix.name, col, pred.eq[col])
    # a range on the primary key (an equality in a statement that is not a
    # point read is the range [v, v]): the key's sorted order names the row
    # positions, so the scan reads those rows and not the table.  Decided on
    # what the code can observe — the key's shape, the filter's bounds, and
    # the match count: under the secondary arm's selectivity gate, and only
    # when the capacity bucket the matches pad into is smaller than the table
    pkc = store.pk_range_column()
    if pkc is not None and pkc in pred.ranges:
        lo, hi = pred.ranges[pkc]
        n = store.num_rows
        matches = store.pk_range_count(lo, hi)
        if matches is not None and matches <= secondary_max_fraction * n \
                and pk_range_capacity(matches) < n:
            return ("pk_range", pkc, lo, hi)
    # table-partition pruning (reference: PartitionAnalyze,
    # physical_planner.cpp:27-120): a predicate on the partition column
    # drops whole partitions' regions before zone maps even look
    spec = store.partition_spec() if hasattr(store, "partition_spec") \
        else None
    if spec is not None:
        pc = spec["column"]
        parts = None
        if pc in pred.eq:
            parts = store.partitions_for(eq_value=pred.eq[pc])
        elif pc in pred.ranges:
            parts = store.partitions_for(range_=tuple(pred.ranges[pc]))
        if parts is not None:
            total = len(spec.get("names") or []) or int(spec.get("n", 0))
            if len(parts) < total:
                return ("partition", parts, total)
    prunable = {c: r for c, r in pred.ranges.items()
                if store.zone_map_column(c) is not None}
    if prunable:
        return ("zonemap", prunable)
    return ("full",)
