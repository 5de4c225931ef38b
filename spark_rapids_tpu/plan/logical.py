"""Logical plan nodes built by the DataFrame API.

The reference plugs into Spark Catalyst and never owns a logical plan; this
framework is standalone, so it carries a small Catalyst-equivalent logical
algebra that the planner (overrides.py) tags and converts to TpuExec physical
operators — the same wrap→tag→convert flow as GpuOverrides.scala:4513.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

from ..batch import Field, Schema
from ..exprs import (AggregateExpression, Alias, Expression, UnresolvedColumn,
                     bind)

__all__ = ["LogicalPlan", "LogicalScan", "Project", "Filter", "Aggregate",
           "Sort", "SortOrder", "Join", "Limit", "Union", "LogicalRange",
           "Sample", "Expand", "Distinct", "Window"]


class LogicalPlan:
    children: Tuple["LogicalPlan", ...] = ()

    def schema(self) -> Schema:
        raise NotImplementedError

    def node_desc(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        lines = [("  " * indent) + ("+- " if indent else "") + self.node_desc()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)


class LogicalScan(LogicalPlan):
    """Leaf: a file/table source. ``source_factory`` yields pyarrow tables."""

    def __init__(self, schema: Schema, source_factory: Callable, desc: str,
                 fmt: str = "parquet"):
        self._schema = schema
        self.source_factory = source_factory
        self.desc = desc
        self.fmt = fmt

    def schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        return f"Scan {self.fmt} [{self.desc}]"


class Project(LogicalPlan):
    def __init__(self, child: LogicalPlan, exprs: List[Tuple[str, Expression]]):
        self.children = (child,)
        self.exprs = exprs  # unbound; names are output names

    def schema(self) -> Schema:
        in_schema = self.children[0].schema()
        fields = []
        for name, e in self.exprs:
            b = bind(e, in_schema)
            fields.append(Field(name, b.dtype, b.nullable))
        return Schema(fields)

    def node_desc(self):
        return f"Project [{', '.join(n for n, _ in self.exprs)}]"


class Filter(LogicalPlan):
    def __init__(self, child: LogicalPlan, condition: Expression):
        self.children = (child,)
        self.condition = condition

    def schema(self) -> Schema:
        return self.children[0].schema()

    def node_desc(self):
        return f"Filter [{self.condition.fingerprint()}]"


class Aggregate(LogicalPlan):
    def __init__(self, child: LogicalPlan,
                 group_exprs: List[Tuple[str, Expression]],
                 agg_exprs: List[Tuple[str, Expression]],
                 distinct_one_pass: bool = False):
        self.children = (child,)
        self.group_exprs = group_exprs
        self.agg_exprs = agg_exprs  # each contains an AggregateExpression tree
        # the level-2 aggregate of a count(DISTINCT) lowered to two stacked
        # aggregates over one copy of the child (sql/dataframe.py
        # _plan_distinct_one_pass); counted where the plan is converted
        self.distinct_one_pass = distinct_one_pass

    def schema(self) -> Schema:
        in_schema = self.children[0].schema()
        fields = []
        for name, e in self.group_exprs:
            b = bind(e, in_schema)
            fields.append(Field(name, b.dtype, b.nullable))
        for name, e in self.agg_exprs:
            b = bind(e, in_schema)
            fields.append(Field(name, b.dtype, b.nullable))
        return Schema(fields)

    def node_desc(self):
        return (f"Aggregate keys=[{', '.join(n for n, _ in self.group_exprs)}] "
                f"aggs=[{', '.join(n for n, _ in self.agg_exprs)}]"
                + (" distinct_one_pass" if self.distinct_one_pass else ""))


class SortOrder:
    def __init__(self, expr: Expression, ascending: bool = True,
                 nulls_first: Optional[bool] = None):
        self.expr = expr
        self.ascending = ascending
        # Spark default: nulls first for ASC, nulls last for DESC
        self.nulls_first = nulls_first if nulls_first is not None else ascending


class Sort(LogicalPlan):
    def __init__(self, child: LogicalPlan, orders: List[SortOrder],
                 global_sort: bool = True):
        self.children = (child,)
        self.orders = orders
        self.global_sort = global_sort

    def schema(self) -> Schema:
        return self.children[0].schema()

    def node_desc(self):
        return f"Sort [{len(self.orders)} keys, global={self.global_sort}]"


class Join(LogicalPlan):
    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 left_keys: List[Expression], right_keys: List[Expression],
                 how: str = "inner", condition: Optional[Expression] = None):
        self.children = (left, right)
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.how = how
        self.condition = condition

    def schema(self) -> Schema:
        l, r = self.children[0].schema(), self.children[1].schema()
        if self.how in ("semi", "anti", "left_semi", "left_anti"):
            return l
        if self.how == "existence":
            # ExistenceJoin (Spark-internal, from IN/EXISTS inside
            # disjunctions): left rows + a boolean match column
            from .. import types as T
            return Schema(list(l.fields)
                          + [Field(getattr(self, "exists_col", "exists"),
                                   T.BOOLEAN, False)])
        using = set(getattr(self, "using", []) or [])
        fields = list(l.fields)
        rf = [f for f in r.fields if f.name not in using]
        if self.how in ("left", "left_outer", "full", "full_outer"):
            rf = [Field(f.name, f.dtype, True) for f in rf]
        if self.how in ("right", "right_outer", "full", "full_outer"):
            fields = [Field(f.name, f.dtype, True) for f in fields]
        return Schema(fields + rf)

    def node_desc(self):
        return f"Join {self.how}"


class Limit(LogicalPlan):
    def __init__(self, child: LogicalPlan, n: int, offset: int = 0):
        self.children = (child,)
        self.n = n
        self.offset = offset

    def schema(self) -> Schema:
        return self.children[0].schema()

    def node_desc(self):
        return f"Limit {self.n}"


class Union(LogicalPlan):
    def __init__(self, plans: Sequence[LogicalPlan]):
        self.children = tuple(plans)

    def schema(self) -> Schema:
        return self.children[0].schema()


class Distinct(LogicalPlan):
    def __init__(self, child: LogicalPlan):
        self.children = (child,)

    def schema(self) -> Schema:
        return self.children[0].schema()


class LogicalRange(LogicalPlan):
    """spark.range() analog (GpuRangeExec, basicPhysicalOperators.scala:1096)."""

    def __init__(self, start: int, end: int, step: int = 1):
        from .. import types as T
        self.start, self.end, self.step = start, end, step
        self._schema = Schema([Field("id", T.INT64, False)])

    def schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        return f"Range ({self.start}, {self.end}, step={self.step})"


class Generate(LogicalPlan):
    """Explode an ARRAY column into one row per element
    (GpuGenerateExec analog; ``outer`` keeps empty/null arrays as a null
    row like OUTER EXPLODE)."""

    def __init__(self, child: LogicalPlan, column: str, out_name: str,
                 outer: bool = False):
        self.children = (child,)
        self.column = column
        self.out_name = out_name
        self.outer = outer

    def schema(self) -> Schema:
        fields = []
        for f in self.children[0].schema():
            if f.name == self.column:
                fields.append(Field(self.out_name, f.dtype.element, True))
            else:
                fields.append(f)
        return Schema(fields)

    def node_desc(self):
        kind = "explode_outer" if self.outer else "explode"
        return f"Generate {kind}({self.column}) as {self.out_name}"


class Cache(LogicalPlan):
    """df.cache() — materialized batches live in the spill catalog as
    spillable handles (ParquetCachedBatchSerializer.scala:264 analog: the
    reference serializes cached batches as in-memory parquet; here they
    stay device-resident and spill to host/disk under memory pressure)."""

    def __init__(self, child: LogicalPlan):
        import threading
        import weakref
        self.children = (child,)
        self._cell = {"handles": None}  # shared with the GC finalizer
        self.lock = threading.Lock()
        # a cache dropped without unpersist() must still release its
        # spillable handles (disk-tier files would orphan otherwise)
        weakref.finalize(self, Cache._close_handles, self._cell)

    @property
    def materialized(self):
        return self._cell["handles"]

    @materialized.setter
    def materialized(self, v):
        self._cell["handles"] = v

    @staticmethod
    def _close_handles(cell) -> None:
        handles = cell.get("handles")
        cell["handles"] = None
        for h in handles or ():
            h.close()

    def schema(self) -> Schema:
        return self.children[0].schema()

    def unpersist(self) -> None:
        with self.lock:
            Cache._close_handles(self._cell)

    def node_desc(self):
        state = "materialized" if self.materialized else "lazy"
        return f"InMemoryCache [{state}]"


class Sample(LogicalPlan):
    def __init__(self, child: LogicalPlan, fraction: float, seed: int = 0):
        self.children = (child,)
        self.fraction = fraction
        self.seed = seed

    def schema(self) -> Schema:
        return self.children[0].schema()


class Window(LogicalPlan):
    """Append window-function columns (GpuWindowExec analog).

    All ``window_exprs`` share one (partition_by, order_by) sort spec — the
    DataFrame layer splits mixed-spec selections into a chain of Window nodes,
    like Spark's ExtractWindowExpressions analysis rule.  Output schema =
    child columns ++ window columns.
    """

    def __init__(self, child: LogicalPlan,
                 window_exprs: List[Tuple[str, Expression]]):
        self.children = (child,)
        self.window_exprs = window_exprs

    def schema(self) -> Schema:
        in_schema = self.children[0].schema()
        fields = list(in_schema.fields)
        for name, e in self.window_exprs:
            b = bind(e, in_schema)
            fields.append(Field(name, b.dtype, b.nullable))
        return Schema(fields)

    def node_desc(self):
        return f"Window [{', '.join(n for n, _ in self.window_exprs)}]"


class Expand(LogicalPlan):
    """Grouping-sets expansion (GpuExpandExec analog)."""

    def __init__(self, child: LogicalPlan,
                 projections: List[List[Tuple[str, Expression]]]):
        self.children = (child,)
        self.projections = projections

    def schema(self) -> Schema:
        in_schema = self.children[0].schema()
        fields = []
        for name, e in self.projections[0]:
            b = bind(e, in_schema)
            fields.append(Field(name, b.dtype, True))
        return Schema(fields)
