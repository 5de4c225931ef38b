"""Scan pushdown: column pruning + predicate extraction.

The reference pushes the plan's required-column set and filter predicates
into its scans (GpuParquetScan.scala:655-661 row-group clipping;
GpuFileSourceScanExec requiredSchema).  Round 1 measured the cost of not
doing this: TPC-H Q6 uploaded all 10 lineitem columns — 5.7 s of scan for a
0.7 s query.  This pass walks the logical plan once, narrowing every
pushdown-capable :class:`LogicalScan` to the columns the plan actually
references and handing it simple comparison conjuncts for row-group pruning.

Filters are *advisory* at the scan (they still execute in the plan); pruning
is exact.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Set, Tuple

from .. import exprs as E
from . import logical as L

__all__ = ["optimize_scans", "extract_predicates"]


# ---------------------------------------------------------------------------------
# Predicate extraction (Expression -> simple (col, op, value) conjuncts)
# ---------------------------------------------------------------------------------

_OPS = {
    E.LessThan: "<", E.LessThanOrEqual: "<=",
    E.GreaterThan: ">", E.GreaterThanOrEqual: ">=", E.EqualTo: "==",
}
_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "=="}


def _conjuncts(e: E.Expression) -> List[E.Expression]:
    if isinstance(e, E.And):
        return _conjuncts(e.children[0]) + _conjuncts(e.children[1])
    return [e]


def _as_predicate(e: E.Expression):
    op = _OPS.get(type(e))
    if op is not None:
        l, r = e.children
        if isinstance(l, E.UnresolvedColumn) and isinstance(r, E.Literal) \
                and r.value is not None:
            return (l.name, op, r.value)
        if isinstance(r, E.UnresolvedColumn) and isinstance(l, E.Literal) \
                and l.value is not None:
            return (r.name, _FLIP[op], l.value)
        return None
    if isinstance(e, E.In) and isinstance(e.children[0], E.UnresolvedColumn):
        # an IN over a subquery (plan/subquery.InSubqueryValues, in a
        # plan explained before it is resolved) has no literal list
        if not isinstance(e.values, (list, tuple)):
            return None
        return (e.children[0].name, "in", list(e.values))
    if isinstance(e, E.IsNotNull) and isinstance(e.children[0],
                                                 E.UnresolvedColumn):
        return (e.children[0].name, "isnotnull", None)
    return None


def extract_predicates(condition: E.Expression) -> List[Tuple[str, str, object]]:
    """Simple pushable conjuncts of a filter condition (others are ignored)."""
    out = []
    for c in _conjuncts(condition):
        p = _as_predicate(c)
        if p is not None:
            out.append(p)
    return out


# ---------------------------------------------------------------------------------
# The pass
# ---------------------------------------------------------------------------------

def optimize_scans(plan: L.LogicalPlan) -> L.LogicalPlan:
    return _walk(plan, required=None, preds=[])


def _refs(exprs) -> Set[str]:
    out: Set[str] = set()
    for e in exprs:
        out |= e.references()
    return out


def _prune_to(node: L.LogicalPlan,
              required: Optional[Set[str]]) -> L.LogicalPlan:
    """Insert a column-pruning Project when a join input's schema carries
    columns the join doesn't need.  Operators pass their whole schema
    through, so without this a filtered dimension table drags its filter
    column (often a host string) into the join build side — blocking the
    device fast paths and widening every shuffle."""
    if required is None or isinstance(node, (L.LogicalScan, L.Cache)):
        return node
    names = node.schema().names()
    keep = [n for n in names if n in required]
    if not keep or len(keep) == len(names):
        return node
    out = L.Project(node, [(n, E.UnresolvedColumn(n)) for n in keep])
    if getattr(node, "broadcast_hint", False):
        out.broadcast_hint = True
    return out


def _walk(node: L.LogicalPlan, required: Optional[Set[str]],
          preds: List[Tuple[str, str, object]]) -> L.LogicalPlan:
    out = _walk_impl(node, required, preds)
    # rebuilt nodes must keep planner hints riding on the original
    # (a dropped broadcast_hint silently turns a broadcast join into a
    # shuffle)
    if out is not node and getattr(node, "broadcast_hint", False):
        out.broadcast_hint = True
    return out


def _walk_impl(node: L.LogicalPlan, required: Optional[Set[str]],
               preds: List[Tuple[str, str, object]]) -> L.LogicalPlan:
    if isinstance(node, L.LogicalScan):
        src = getattr(node, "source", None)
        if src is None or not hasattr(src, "with_pushdown"):
            return node
        names = node.schema().names()
        cols = None
        if required is not None and set(names) - required:
            cols = [n for n in names if n in required]
            if not cols:
                # count(*)-style plans reference no columns; keep one (prefer
                # a device-typed column) for row accounting
                fields = node.schema().fields
                pick = next((f.name for f in fields if not f.dtype.is_string),
                            names[0])
                cols = [pick]
        scan_preds = [p for p in preds if p[0] in names]
        if cols is None and not scan_preds:
            return node
        new_src = src.with_pushdown(cols, scan_preds)
        out = L.LogicalScan(new_src.schema(), new_src, new_src.describe(),
                            fmt=node.fmt)
        out.source = new_src
        return out

    if isinstance(node, L.Filter):
        child_req = None if required is None else \
            (required | node.condition.references())
        child_preds = preds + extract_predicates(node.condition)
        child = _walk(node.children[0], child_req, child_preds)
        return L.Filter(child, node.condition)

    if isinstance(node, L.Project):
        kept = node.exprs
        if required is not None:
            kept = [(n, e) for n, e in node.exprs if n in required]
            if not kept:  # keep at least one column for row accounting
                kept = node.exprs[:1]
        child_req = _refs(e for _, e in kept)
        # translate predicates through pure column pass-throughs
        mapping = {n: e.name for n, e in kept
                   if isinstance(e, E.UnresolvedColumn)}
        child_preds = [(mapping[c], op, v) for c, op, v in preds
                       if c in mapping]
        child = _walk(node.children[0], child_req, child_preds)
        return L.Project(child, kept)

    if isinstance(node, L.Aggregate):
        child_req = _refs(e for _, e in node.group_exprs) | \
            _refs(e for _, e in node.agg_exprs)
        child = _walk(node.children[0], child_req, [])
        return L.Aggregate(child, node.group_exprs, node.agg_exprs,
                           node.distinct_one_pass, node.rollup)

    if isinstance(node, L.Sort):
        child_req = None if required is None else \
            (required | _refs(o.expr for o in node.orders))
        child = _walk(node.children[0], child_req, preds)
        return L.Sort(child, node.orders, node.global_sort)

    if isinstance(node, L.Limit):
        # predicates must not cross a limit (they would change which rows
        # the limit sees); column pruning flows through
        child = _walk(node.children[0], required, [])
        return L.Limit(child, node.n, node.offset)

    if isinstance(node, L.Join):
        lnames = set(node.children[0].schema().names())
        rnames = set(node.children[1].schema().names())
        if required is None:
            lreq = rreq = None
        else:
            lreq = ({c for c in required if c in lnames}
                    | _refs(node.left_keys))
            rreq = ({c for c in required if c in rnames}
                    | _refs(node.right_keys))
            if node.condition is not None:
                crefs = node.condition.references()
                lreq |= {c for c in crefs if c in lnames}
                rreq |= {c for c in crefs if c in rnames}
        left = _prune_to(_walk(node.children[0], lreq, []), lreq)
        right = _prune_to(_walk(node.children[1], rreq, []), rreq)
        out = L.Join(left, right, node.left_keys, node.right_keys,
                     how=node.how, condition=node.condition)
        if hasattr(node, "using"):
            out.using = node.using
        if hasattr(node, "exists_col"):
            out.exists_col = node.exists_col
        return out

    if isinstance(node, L.Union):
        # children must stay schema-aligned; don't prune through unions
        return L.Union([_walk(c, None, []) for c in node.children])

    if isinstance(node, L.Distinct):
        return L.Distinct(_walk(node.children[0], None, []))

    if isinstance(node, L.Expand):
        child_req = set()
        for proj in node.projections:
            child_req |= _refs(e for _, e in proj)
        return L.Expand(_walk(node.children[0], child_req, []),
                        node.projections)

    if isinstance(node, L.Window):
        # predicates must not cross: a filter above a window would change
        # partition contents if pushed below it
        child_req = None
        if required is not None:
            wnames = {n for n, _ in node.window_exprs}
            child_req = {c for c in required if c not in wnames}
            child_req |= _refs(e for _, e in node.window_exprs)
        return L.Window(_walk(node.children[0], child_req, []),
                        node.window_exprs)

    if isinstance(node, L.Sample):
        return L.Sample(_walk(node.children[0], required, []),
                        node.fraction, node.seed)

    if isinstance(node, L.Cache):
        # barrier: the node is shared mutable state across queries (it owns
        # the materialized handles), and its batches must keep the full
        # schema — never rebuild or prune through it
        return node

    if not node.children:
        return node
    # unknown operator: conservatively require everything below it
    new_children = tuple(_walk(c, None, []) for c in node.children)
    import copy
    out = copy.copy(node)
    out.children = new_children
    return out
