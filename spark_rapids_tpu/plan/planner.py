"""Expression binding helpers shared by the planner (overrides.py).

The actual logical→physical conversion is overrides._convert
(GpuOverrides.doConvertPlan analog, GpuOverrides.scala:4192); this module
holds the pieces both binding-time and conversion-time code need.
"""

from __future__ import annotations

from typing import List, Tuple

from ..batch import Field, Schema
from ..exprs import BoundReference, Expression, bind

__all__ = ["strip_alias", "plan_query_regions", "explain_regions"]


def plan_query_regions(root, conf):
    """Public entry to the region-fusion planner (plan/fusion.py): group
    fusible operator chains of an already-converted physical tree into
    fused regions.  ``apply_overrides`` calls this implicitly at the end
    of planning; tests and tooling that build physical trees by hand
    (bench harnesses, mini-plan fixtures) call it directly to get the
    same region formation the SQL path gets."""
    from .fusion import plan_regions
    return plan_regions(root, conf)


def explain_regions(root) -> List[str]:
    """One line per fused region of a planned physical tree — operator
    kinds and member count, in plan order.  Empty when fusion formed no
    regions (or is disabled)."""
    from .fusion import FusedRegionExec
    lines: List[str] = []

    def walk(n):
        if isinstance(n, FusedRegionExec):
            lines.append(f"region[{len(n.members)}]: " + " -> ".join(
                type(m).__name__ for m in n.members))
        for c in n.children:
            walk(c)

    walk(root)
    return lines


def strip_alias(e: Expression) -> Expression:
    from ..sql.column import _AliasMarker
    from ..exprs import Alias
    while isinstance(e, (_AliasMarker, Alias)):
        e = e.children[0]
    return e


def _bind_project(exprs, schema: Schema):
    """Bind projection exprs; detect host-column pass-through references.

    Returns (payload triples [(name, bound_expr_or_None, host_src)], schema).
    """
    triples = []
    fields = []
    for name, e in exprs:
        b = bind(e, schema)
        core = strip_alias(b)
        if isinstance(core, BoundReference) and core.dtype.is_host_carried:
            triples.append((name, None, core.ordinal))
            fields.append(Field(name, core.dtype, core.nullable))
        else:
            triples.append((name, b, None))
            fields.append(Field(name, b.dtype, b.nullable))
    return triples, Schema(fields)


def _case_parts(e: Expression):
    """(conditions, values) of an IF or a CASE, or None for anything else."""
    from ..exprs import CaseWhen, If
    if isinstance(e, If):
        return [e.children[0]], list(e.children[1:])
    if isinstance(e, CaseWhen):
        values = [v for _, v in e.branches]
        if e.otherwise is not None:
            values.append(e.otherwise)
        return [c for c, _ in e.branches], values
    return None


def string_code_source(e: Expression):
    """Where a string-valued key expression takes its strings from.

    A sort or a window runs string keys as int32 dictionary codes
    (``ops/strings.key_view``).  That works for a bare string column and
    for a CASE / IF that picks between ONE string column and NULL (TPC-DS
    Q36's ``case when grouping(i_class) = 0 then i_category end``): the
    branches select codes as they would select strings.  Returns the
    column's ordinal, -1 where every branch is a NULL literal, or None for
    any other string expression (device string kernels pending).
    """
    from ..exprs import Literal
    e = strip_alias(e)
    if isinstance(e, BoundReference):
        return e.ordinal if e.dtype.is_string else None
    if isinstance(e, Literal):
        return -1 if e.value is None else None
    parts = _case_parts(e)
    if parts is None:
        return None
    src = -1
    for v in parts[1]:
        s = string_code_source(v)
        if s is None or (s >= 0 and src >= 0 and s != src):
            return None
        src = max(src, s)
    return src


def string_code_predicates(e: Expression) -> List[Expression]:
    """The conditions of a :func:`string_code_source` expression: they run
    on the device as they stand, so the planner tags them like any other
    computed expression."""
    parts = _case_parts(strip_alias(e))
    if parts is None:
        return []
    return parts[0] + [p for v in parts[1]
                       for p in string_code_predicates(v)]


def string_key_ordinals(exprs) -> List[int]:
    """Ordinals of the string columns that ``exprs`` (bound key
    expressions) read as dictionary codes."""
    out = []
    for e in exprs:
        if e.dtype is not None and e.dtype.is_string:
            s = string_code_source(e)
            if s is not None and s >= 0 and s not in out:
                out.append(s)
    return out
