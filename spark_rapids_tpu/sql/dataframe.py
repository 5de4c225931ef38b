"""DataFrame: the user-facing lazy query surface (PySpark DataFrame analog).

The reference accelerates Spark's own DataFrame transparently; this framework
is standalone, so it ships the equivalent surface.  Everything is lazy — an
action (collect/count/to_pandas) triggers planning (overrides → physical) and
batch execution.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

from .. import exprs as E
from ..plan import logical as L
from .column import Column, to_expr

__all__ = ["DataFrame", "GroupedData"]


def _named(c: Union[str, Column]) -> tuple:
    if isinstance(c, str):
        if c == "*":
            raise ValueError("use df.select('*') via df.select(*df.columns)")
        return (c, E.UnresolvedColumn(c))
    return (c.name, c.expr)


def _decompose_agg_exprs(child: L.LogicalPlan, group_exprs, agg_exprs
                         ) -> L.LogicalPlan:
    """Build the Aggregate node, decomposing COMPOUND aggregate expressions
    (Spark's physical-aggregate resultExpressions split):
    ``agg((sum(v) * 0.2).alias("lim"))`` becomes
    ``Aggregate(__agg0=sum(v))`` + ``Project(lim=__agg0 * 0.2)``."""
    import copy

    from ..exprs import AggregateExpression
    from ..plan.planner import strip_alias

    agg_exprs = [(n, strip_alias(e)) for n, e in agg_exprs]
    if all(isinstance(e, AggregateExpression) for _, e in agg_exprs):
        return L.Aggregate(child, group_exprs, agg_exprs)

    internal: List[tuple] = []
    by_fp: dict = {}  # dedupe structurally identical aggregates

    def rewrite(e):
        e = strip_alias(e)
        if isinstance(e, AggregateExpression):
            fp = e.fingerprint()
            name = by_fp.get(fp)
            if name is None:
                name = f"__agg{len(internal)}"
                by_fp[fp] = name
                internal.append((name, e))
            return E.UnresolvedColumn(name)
        if not e.children:
            return e
        node = copy.copy(e)
        node.children = tuple(rewrite(c) for c in e.children)
        return node

    finals = [(name, rewrite(e)) for name, e in agg_exprs]
    if not internal:
        raise ValueError(
            "agg() expressions must contain at least one aggregate "
            "function (use select() for row-wise expressions)")
    # every remaining column reference must resolve in the aggregate's
    # output (a grouping column or an internal agg) — catching a stray
    # row column HERE gives an analysis error, not a bind-time KeyError
    group_names = {n for n, _ in group_exprs}
    valid = group_names | {n for n, _ in internal}
    for name, e in finals:
        stray = {r for r in e.references() if r not in valid}
        if stray:
            raise ValueError(
                f"agg() expression {name!r} references non-grouping "
                f"column(s) {sorted(stray)}: every column must be inside "
                f"an aggregate function or be a grouping column")
    agg_node = L.Aggregate(child, group_exprs, internal)
    # group columns pass through by their output names
    proj = [(n, E.UnresolvedColumn(n)) for n, _ in group_exprs] + finals
    return L.Project(agg_node, proj)


def _rewrite_windows(plan: L.LogicalPlan, exprs: List[tuple]):
    """Pull WindowExpressions out of a projection into Window nodes
    (Spark's ExtractWindowExpressions analysis rule analog).

    Returns (new_child_plan, rewritten_exprs): each window subtree is
    replaced by a reference to a generated ``__w{i}`` column computed by a
    chain of L.Window nodes (one per distinct partition+order spec).
    """
    from ..windowfns import WindowExpression

    found: List[tuple] = []  # (gen_name, wexpr)
    by_fp = {}

    def walk_replace(e: E.Expression) -> E.Expression:
        if isinstance(e, WindowExpression):
            fp = e.fingerprint()
            if fp in by_fp:
                return E.UnresolvedColumn(by_fp[fp])
            gen = f"__w{len(found)}"
            by_fp[fp] = gen
            found.append((gen, e))
            return E.UnresolvedColumn(gen)
        if not e.children:
            return e
        import copy
        new_children = tuple(walk_replace(c) for c in e.children)
        if all(a is b for a, b in zip(new_children, e.children)):
            return e
        node = copy.copy(e)
        node.children = new_children
        return node

    new_exprs = [(n, walk_replace(e)) for n, e in exprs]
    if not found:
        return plan, exprs
    # group by sort spec: one Window node per distinct (partition, order)
    groups: Dict[str, List[tuple]] = {}
    order: List[str] = []
    for gen, w in found:
        key = w.spec.spec_fingerprint()
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append((gen, w))
    child = plan
    for key in order:
        child = L.Window(child, groups[key])
    return child, new_exprs


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session):
        self._plan = plan
        self.session = session

    # -- metadata -----------------------------------------------------------------
    @property
    def schema(self):
        return self._plan.schema()

    @property
    def columns(self) -> List[str]:
        return self._plan.schema().names()

    def __getitem__(self, name: str) -> Column:
        assert name in self._plan.schema(), f"no column {name!r}"
        return Column(E.UnresolvedColumn(name))

    # -- transformations ----------------------------------------------------------
    def select(self, *cols: Union[str, Column]) -> "DataFrame":
        exprs = [_named(c) for c in cols]
        child, exprs = _rewrite_windows(self._plan, exprs)
        return DataFrame(L.Project(child, exprs), self.session)

    def where(self, condition: Union[Column, str]) -> "DataFrame":
        assert not isinstance(condition, str), "SQL string filters: use sql()"
        return DataFrame(L.Filter(self._plan, condition.expr), self.session)

    filter = where

    def with_column(self, name: str, c: Column) -> "DataFrame":
        exprs = []
        replaced = False
        for f in self._plan.schema():
            if f.name == name:
                exprs.append((name, c.expr))
                replaced = True
            else:
                exprs.append((f.name, E.UnresolvedColumn(f.name)))
        if not replaced:
            exprs.append((name, c.expr))
        child, exprs = _rewrite_windows(self._plan, exprs)
        return DataFrame(L.Project(child, exprs), self.session)

    withColumn = with_column

    def with_column_renamed(self, old: str, new: str) -> "DataFrame":
        exprs = [((new if f.name == old else f.name),
                  E.UnresolvedColumn(f.name)) for f in self._plan.schema()]
        return DataFrame(L.Project(self._plan, exprs), self.session)

    def drop(self, *names: str) -> "DataFrame":
        keep = [f.name for f in self._plan.schema() if f.name not in names]
        return self.select(*keep)

    def group_by(self, *cols: Union[str, Column]) -> "GroupedData":
        return GroupedData(self, [_named(c) for c in cols])

    groupBy = group_by

    def rollup(self, *cols: Union[str, Column]) -> "GroupedData":
        """GROUP BY ROLLUP(cols): the n+1 grouping sets cols[:n], ...,
        cols[:1], () in one pass.  ``agg`` reduces the child's rows at the
        finest set and each coarser set from the one before it (an Expand
        under the ordinary aggregate where an aggregate does not
        re-aggregate from its partials); ``functions.grouping`` /
        ``grouping_id`` tell a NULL the set put there from one in the
        data."""
        n = len(cols)
        return GroupedData(self, [_named(c) for c in cols],
                           [tuple(range(k)) for k in range(n, -1, -1)])

    def cube(self, *cols: Union[str, Column]) -> "GroupedData":
        """GROUP BY CUBE(cols): every subset of cols, the full set first."""
        n = len(cols)
        sets = [tuple(i for i in range(n) if not (m >> (n - 1 - i)) & 1)
                for m in range(1 << n)]
        return GroupedData(self, [_named(c) for c in cols], sets)

    def agg(self, *cols: Column) -> "DataFrame":
        return GroupedData(self, []).agg(*cols)

    def sort(self, *cols, ascending: Optional[Union[bool, list]] = None
             ) -> "DataFrame":
        orders = []
        for c in cols:
            if isinstance(c, L.SortOrder):
                orders.append(c)
            elif isinstance(c, str):
                orders.append(L.SortOrder(E.UnresolvedColumn(c)))
            else:
                orders.append(L.SortOrder(c.expr))
        if ascending is not None:
            flags = ([ascending] * len(orders)
                     if isinstance(ascending, bool) else list(ascending))
            orders = [L.SortOrder(o.expr, asc, None if asc else None)
                      for o, asc in zip(orders, flags)]
        return DataFrame(L.Sort(self._plan, orders), self.session)

    orderBy = order_by = sort

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(L.Limit(self._plan, n), self.session)

    def offset(self, n: int) -> "DataFrame":
        return DataFrame(L.Limit(self._plan, 1 << 62, offset=n), self.session)

    def explode(self, column: str, out_name: Optional[str] = None,
                outer: bool = False) -> "DataFrame":
        """One row per array element of ``column`` (GenerateExec/explode);
        ``outer`` keeps empty/null arrays as a null row."""
        return DataFrame(L.Generate(self._plan, column,
                                    out_name or column, outer=outer),
                         self.session)

    def cache(self) -> "DataFrame":
        """Materialize this result in the spill catalog on first use;
        later actions replay the cached batches (InMemoryTableScan)."""
        return DataFrame(L.Cache(self._plan), self.session)

    persist = cache

    def unpersist(self) -> "DataFrame":
        if isinstance(self._plan, L.Cache):
            self._plan.unpersist()
        return self

    def sample(self, fraction: float, seed: Optional[int] = None
               ) -> "DataFrame":
        """Bernoulli row sample without replacement (SampleExec)."""
        if seed is None:
            import random
            seed = random.randint(0, 2 ** 31 - 1)
        return DataFrame(L.Sample(self._plan, fraction, seed), self.session)

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(L.Union([self._plan, other._plan]), self.session)

    unionAll = union

    def distinct(self) -> "DataFrame":
        return DataFrame(L.Distinct(self._plan), self.session)

    def join(self, other: "DataFrame", on=None, how: str = "inner"
             ) -> "DataFrame":
        """``on``: a column name or a list of them (USING), a list of
        ``(left, right)`` name pairs, or a boolean ``Column`` over both
        sides' columns as pyspark takes it: the conjuncts that equate one
        column of each side become the join's keys, what is left its
        condition, which takes part in MATCHING (an EXISTS with a
        non-equi predicate is ``how="semi"`` with one)."""
        if on is None:
            raise NotImplementedError("cross join: use crossJoin")
        if isinstance(on, Column):
            return self._join_on_condition(other, on.expr, how)
        if isinstance(on, str):
            on = [on]
        if isinstance(on, (list, tuple)) and all(isinstance(x, str) for x in on):
            lk = [E.UnresolvedColumn(k) for k in on]
            rk = [E.UnresolvedColumn(k) for k in on]
            node = L.Join(self._plan, other._plan, lk, rk, how=how)
            node.using = list(on)
            return DataFrame(node, self.session)
        if isinstance(on, (list, tuple)) and all(
                isinstance(x, (list, tuple)) and len(x) == 2 for x in on):
            # [(left_col, right_col), ...] equi-join with distinct key names
            lk = [E.UnresolvedColumn(a) for a, _ in on]
            rk = [E.UnresolvedColumn(b) for _, b in on]
            node = L.Join(self._plan, other._plan, lk, rk, how=how)
            return DataFrame(node, self.session)
        raise NotImplementedError(
            "join on: column names, (left, right) name pairs or a Column")

    def _join_on_condition(self, other: "DataFrame", on: E.Expression,
                           how: str) -> "DataFrame":
        lnames = set(self._plan.schema().names())
        rnames = set(other._plan.schema().names())
        # the condition binds over both sides' columns by name: a name
        # both sides carry could mean either
        for name in sorted(on.references()):
            if name in lnames and name in rnames:
                raise ValueError(
                    f"join condition: column {name!r} is on both sides; "
                    f"rename one side's (select(col.alias(...))) first")
            if name not in lnames and name not in rnames:
                raise ValueError(
                    f"join condition: no column {name!r} on either side")
        from ..plan.optimizer import _and_all, _conjuncts
        lk, rk, rest = [], [], []
        for c in _conjuncts(on):
            a, b = c.children if isinstance(c, E.EqualTo) else (None, None)
            if isinstance(a, E.UnresolvedColumn) \
                    and isinstance(b, E.UnresolvedColumn) \
                    and (a.name in lnames) != (b.name in lnames):
                left_first = a.name in lnames
                lk.append(a if left_first else b)
                rk.append(b if left_first else a)
            else:
                rest.append(c)
        if not lk:
            if how not in ("inner", "cross"):
                raise NotImplementedError(
                    f"{how} join on a condition with no equality between "
                    f"one column of each side")
            return self.cross_join(other).filter(Column(on))
        return DataFrame(L.Join(self._plan, other._plan, lk, rk, how=how,
                                condition=_and_all(rest)), self.session)

    def hint(self, name: str, *args) -> "DataFrame":
        """Planner hint. Supported: "broadcast" — prefer broadcasting this
        side in joins (ResolvedHint analog; consumed by
        plan/join_exec.plan_broadcast_join)."""
        if name.lower() not in ("broadcast", "broadcastjoin", "mapjoin"):
            return self  # unknown hints are ignored, as in Spark
        import copy
        plan = copy.copy(self._plan)
        plan.broadcast_hint = True
        return DataFrame(plan, self.session)

    def cross_join(self, other: "DataFrame") -> "DataFrame":
        node = L.Join(self._plan, other._plan, [], [], how="cross")
        return DataFrame(node, self.session)

    crossJoin = cross_join

    def to_device_arrays(self) -> dict:
        """Execute and return the result as DEVICE-resident jax arrays —
        no host round trip (ColumnarRdd.scala:42-51 zero-copy ML-handoff
        analog; the XGBoost-style consumer keeps working in HBM).

        Returns ``{column: (data, valid)}`` with ``data`` a jax array of
        the column's physical dtype (decimals as scaled ints, dates as
        epoch days) and ``valid`` a bool mask or None.  Host-carried
        columns (strings/nested) have no device representation and raise.
        """
        from ..batch import DeviceColumn
        from ..ops import batch_utils
        whole = self.session._execute_device(self._plan)
        if whole is None:
            return {f.name: None for f in self.schema}
        out = {}
        for f, c in zip(whole.schema, whole.columns):
            if not isinstance(c, DeviceColumn):
                raise TypeError(
                    f"column {f.name!r} ({f.dtype}) is host-carried and "
                    f"has no device representation; drop or encode it "
                    f"before to_device_arrays()")
            out[f.name] = (c.data[:whole.num_rows],
                           None if c.valid is None
                           else c.valid[:whole.num_rows])
        return out

    def to_dlpack(self) -> dict:
        """Execute and export each device column as a DLPack capsule for
        zero-copy handoff to other frameworks (torch/cupy-style
        consumers; the ColumnarRdd interop surface).  jax arrays speak
        the DLPack protocol natively (``__dlpack__``); this materializes
        one capsule per column data/validity array."""
        return {name: (d.__dlpack__(),
                       None if v is None else v.__dlpack__())
                for name, (d, v) in self.to_device_arrays().items()}

    # -- actions ------------------------------------------------------------------
    @property
    def write(self):
        """Write builder: ``df.write.mode("overwrite").parquet(path)``
        (ColumnarOutputWriter.scala:69 analog; io/writers.py)."""
        from ..io.writers import DataFrameWriter
        return DataFrameWriter(self)

    def _executed(self):
        return self.session._execute(self._plan)

    def to_arrow(self):
        return self._executed()

    def to_pandas(self):
        t = self._executed()
        return t.to_pandas() if t is not None else None

    toPandas = to_pandas

    def collect(self, timeout: Optional[float] = None) -> List[tuple]:
        """Execute and fetch all rows.  ``timeout`` (seconds) installs a
        per-query deadline: execution aborts cooperatively at the next
        batch boundary with
        :class:`..service.cancel.QueryDeadlineExceeded`, releasing its
        semaphore permits, pipeline slots, and spill handles."""
        if timeout is not None:
            from ..service import cancel
            with cancel.scope(cancel.QueryControl(label="collect",
                                                  deadline_s=timeout)):
                return self._collected()
        return self._collected()

    def _collected(self) -> List[tuple]:
        # the query's scope opens HERE, so its wall is the caller's:
        # planning, execution and the rows below
        with self.session._query_scope():
            return self.session._rows(self._executed())

    def submit(self, **kw):
        """Async execution through the session's query scheduler:
        ``df.submit(priority=, deadline_s=, tenant=)`` returns a
        :class:`..service.scheduler.QueryHandle` whose ``result()`` is
        this DataFrame's ``collect()`` output."""
        return self.session.submit(self, **kw)

    def count(self) -> int:
        from . import functions as F
        t = self.agg(F.count_star().alias("count"))._executed()
        return t.column(0).to_pylist()[0]

    def show(self, n: int = 20) -> None:
        print(self.limit(n).to_pandas())

    def explain(self, mode: str = "formatted") -> None:
        """Print the plan.  ``mode="profiled"`` EXECUTES the query and
        re-renders the physical tree annotated with every operator's
        accumulated metrics (rows/batches/bytes/time), the SQL-UI
        per-operator metrics view analog."""
        if mode == "profiled":
            print(self.explain_profiled())
        else:
            print(self.explain_string())

    def explain_string(self) -> str:
        return self.session._explain(self._plan)

    def explain_profiled(self) -> str:
        """Execute this query and return the physical plan tree annotated
        with each operator's accumulated metrics."""
        return self.session._explain_profiled(self._plan)


def _split_count_distinct(agg_exprs):
    """Partition (name, expr) aggregates into (count-distinct items,
    plain items), or None when no count_distinct is present."""
    from .functions import _CountDistinctMarker
    from ..plan.planner import strip_alias
    cds, plain = [], []
    for n, e in agg_exprs:
        core = strip_alias(e)
        if isinstance(core, _CountDistinctMarker):
            cds.append((n, list(core.children)))
        else:
            plain.append((n, e))
    if not cds:
        return None
    return cds, plain


def _distinct_columns(idx, cols):
    """The distinct set's expressions under the names the dedup aggregate
    groups them by (marker children are already expressions)."""
    return [(f"__cd{idx}_{i}", c) for i, c in enumerate(cols)]


def _count_of_deduped(dcols, groupless):
    """The count over a dedup aggregate's groups: those whose EVERY
    distinct column is non-null (Spark count(distinct) semantics; a NULL
    is a group of its own down there).  A groupless count over no groups
    is 0, not the NULL a sum over nothing is."""
    from . import functions as F
    cond = None
    for n_, _ in dcols:
        c_ = F.col(n_).is_not_null()
        cond = c_ if cond is None else (cond & c_)
    cnt = F.sum(F.when(cond, F.lit(1)).otherwise(F.lit(0)))
    return F.coalesce(cnt, F.lit(0)) if groupless else cnt


def _plan_count_distinct(df, group_exprs, cds, plain, order):
    """count(DISTINCT ...) lowering.  ONE distinct set beside plain
    aggregates that re-aggregate exactly runs the child once
    (:func:`_plan_distinct_one_pass`); anything else keeps the join form
    (:func:`_plan_count_distinct_join`).  The choice reads the aggregate
    list alone."""
    if len(cds) == 1:
        node = _plan_distinct_one_pass(df._plan, group_exprs, cds[0], plain,
                                       order)
        if node is not None:
            return DataFrame(node, df.session)
    return _plan_count_distinct_join(df, group_exprs, cds, plain, order)


def _plan_distinct_one_pass(child, group_exprs, cd, plain, order):
    """Two stacked aggregates over ONE copy of the child (Spark's
    AggUtils.planAggregateWithOneDistinct): level 1 groups by the keys +
    the distinct columns and carries a partial for every plain aggregate
    leaf; level 2 groups by the keys, counts level 1's groups whose
    distinct columns are all non-null and merges the partials (a sum of
    sums, a sum of counts, a min of mins, a max of maxes, sum over count
    for an average).  A row whose distinct column is NULL is a level-1
    group of its own: not counted, its partials merged like any other.
    Float sums become sums of per-group sums, so their additions come in
    another order than the join form's.

    Returns None where a plain leaf does not re-aggregate by expression
    (first / last, the central moments, covariance, percentiles,
    collect_*, and the sum or average of a decimal, whose merge the
    device cannot keep in level 1's type): the caller falls back to the
    join form."""
    import copy

    from .. import aggfns as A
    from .. import types as T
    from ..exprs import AggregateExpression, bind
    from ..plan.planner import strip_alias

    schema = child.schema()
    name, cols = cd
    keys = [n for n, _ in group_exprs]
    partials: List[tuple] = []
    by_fp: dict = {}  # identical leaves are computed once

    def partial(agg):
        fp = agg.fingerprint()
        pname = by_fp.get(fp)
        if pname is None:
            pname = by_fp[fp] = f"__p{len(partials)}"
            partials.append((pname, agg))
        return E.UnresolvedColumn(pname)

    def merge(e):
        """``e`` with every aggregate leaf replaced by the merge of its
        partial(s); None where a leaf has no exact merge."""
        e = strip_alias(e)
        if isinstance(e, AggregateExpression):
            kind = type(e)
            if kind is A.Sum:
                # Sum's type is ten digits wider than a decimal input's:
                # the sum of sums is a decimal past 18 digits, which is
                # finalized on the host where no device cast back to
                # level 1's type can read it.  Integers and floats keep
                # their type
                if bind(e, schema).dtype.is_decimal:
                    return None
                return A.Sum(partial(e))
            if kind in (A.Count, A.CountStar):
                # a count is never NULL; a sum over no level-1 groups is
                return E.Coalesce(A.Sum(partial(e)), E.Literal(0, T.INT64))
            if kind in (A.Min, A.Max):
                return kind(partial(e))
            if kind is A.Average:
                # Average's own accumulator: a float64 sum and a count.
                # A decimal's average is a decimal made on the host from
                # its sum's lanes: no merge expression keeps it exact
                x = e.children[0]
                if bind(x, schema).dtype.is_decimal:
                    return None
                return E.Divide(
                    A.Sum(partial(A.Sum(E.Cast(x, T.FLOAT64)))),
                    A.Sum(partial(A.Count(x))))
            return None
        if not e.children:
            return e
        kids = [merge(c) for c in e.children]
        if any(k is None for k in kids):
            return None
        node = copy.copy(e)
        node.children = tuple(kids)
        return node

    finals = {}
    for n_, e_ in plain:
        finals[n_] = merge(e_)
        if finals[n_] is None:
            return None
    dcols = _distinct_columns(0, cols)
    finals[name] = _count_of_deduped(dcols, groupless=not keys).expr
    level1 = L.Aggregate(child, group_exprs + dcols, partials)
    # the aggregates AS WRITTEN, after the keys
    node = _decompose_agg_exprs(
        level1, [(k, E.UnresolvedColumn(k)) for k in keys],
        [(n_, finals[n_]) for n_ in order])
    level2 = node if isinstance(node, L.Aggregate) else node.children[0]
    level2.distinct_one_pass = True
    return node


def _plan_count_distinct_join(df, group_exprs, cds, plain, order):
    """The join form (Spark's RewriteDistinctAggregates, single-join
    shape): the plain aggregates over the child, one dedup aggregation +
    count per distinct set over the child AGAIN, joined back on the group
    keys.  Reached with several distinct sets (each needs its own dedup;
    one pass over them takes an Expand, which no plan here has yet) and
    with a plain aggregate that cannot be merged from per-group partials
    by an expression (first / last, the central moments, covariance,
    percentiles, collect_*; the sum or average of a decimal).  Groupless aggregates
    are each ONE row whatever the input holds (a count over no rows is 0,
    a sum NULL) and join via a constant key put on after they are
    computed."""
    from . import functions as F

    sess = df.session
    keys = [n for n, _ in group_exprs]
    groupless = not keys

    def one_row(part):
        return part.with_column("__cd_k", F.lit(1)) if groupless else part

    parts = []
    if plain:
        node = _decompose_agg_exprs(df._plan, group_exprs, plain)
        parts.append(one_row(DataFrame(node, sess)))
    for idx, (name, cols) in enumerate(cds):
        dcols = _distinct_columns(idx, cols)
        dedup = DataFrame(
            _decompose_agg_exprs(df._plan, group_exprs + dcols, []), sess)
        cnt = _count_of_deduped(dcols, groupless)
        parts.append(one_row(dedup.group_by(*keys).agg(cnt.alias(name))))
    if groupless:
        keys = ["__cd_k"]
    out = parts[0]
    for p_ in parts[1:]:
        renamed = p_
        for k in keys:
            renamed = renamed.with_column_renamed(k, f"__r_{k}")
        out = out.join(renamed, on=[(k, f"__r_{k}") for k in keys])
    # restore output column order: keys then aggregates AS WRITTEN
    names = ([] if groupless else list(keys)) + list(order)
    return out.select(*names)


class PivotedData:
    """group_by(...).pivot(col, values): rewrites aggregates as
    conditional aggregations, one output column per (value, agg)."""

    def __init__(self, grouped: "GroupedData", column: str, values):
        self._g = grouped
        self._column = column
        self._values = values

    def agg(self, *cols: "Column") -> DataFrame:
        from .. import exprs as E
        from ..plan.planner import strip_alias
        from .column import Column as C, _AliasMarker

        def conditional(agg_expr, pv):
            import copy

            from .. import aggfns as A
            core = strip_alias(agg_expr)
            cond = E.EqualTo(E.UnresolvedColumn(self._column),
                             E.Literal(pv))
            if not core.children:
                # count(*) has nothing to wrap: count the pivot matches
                return A.Count(E.If(cond, E.Literal(1),
                                    E.Literal(None, None)))
            node = copy.copy(core)
            node.children = tuple(
                E.If(cond, ch, E.Literal(None, None))
                for ch in core.children)
            if hasattr(node, "ignore_nulls"):
                # non-matching rows became NULLs: first/last must skip
                # them or they would return the injected NULLs
                node.ignore_nulls = True
            return node

        def default_name(c):
            """sum(x)-style label for an unaliased aggregate (Spark
            naming), instead of an expression fingerprint."""
            core = strip_alias(c.expr)
            fn = getattr(core, "func", type(core).__name__.lower())
            if core.children:
                ch = core.children[0]
                arg = getattr(ch, "name", "") or "expr"
            else:
                arg = ""
            return f"{fn}({arg})"

        out = []
        for pv in self._values:  # Spark orders pivot values outermost
            for c in cols:
                base_name = (c.name if isinstance(c.expr, _AliasMarker)
                             else None)
                core = conditional(c.expr, pv)
                name = (f"{pv}" if len(cols) == 1 and base_name is None
                        else f"{pv}_{base_name or default_name(c)}")
                out.append(C(core).alias(name))
        return self._g.agg(*out)

    def sum(self, name: str) -> DataFrame:
        from . import functions as F
        return self.agg(F.sum(F.col(name)))

    def count(self) -> DataFrame:
        from . import functions as F
        return self.agg(F.count_star())

    def first(self, name: str) -> DataFrame:
        from . import functions as F
        return self.agg(F.first(F.col(name)))


GROUPING_ID = "spark_grouping_id"


def _is_chain(sets, n: int) -> bool:
    """The first set holds every key and each set after it is the one
    before it less some keys, as ROLLUP's sets are."""
    return (set(sets[0]) == set(range(n))
            and all(set(b) < set(a) for a, b in zip(sets, sets[1:])))


def _reaggregates(agg_exprs) -> bool:
    """Every aggregate's result over a group is the same aggregate over
    the partial results of any split of the group's rows: sum, count,
    min, max, and avg as a sum and a count."""
    from .. import aggfns as A
    from ..exprs import AggregateExpression

    def ok(e) -> bool:
        if isinstance(e, AggregateExpression):
            return type(e) in (A.Sum, A.Count, A.CountStar, A.Min, A.Max,
                               A.Average)
        return all(ok(c) for c in e.children)

    return all(ok(e) for _, e in agg_exprs)


def _plan_grouping_sets(child: L.LogicalPlan, group_exprs, sets, agg_exprs
                        ) -> L.LogicalPlan:
    """ROLLUP / CUBE as Spark plans them: an Expand with one projection a
    grouping set (the columns the aggregates read as they are, a copy of
    every grouping key with NULL where the key is outside the set, and the
    set's number as ``spark_grouping_id``), the ordinary Aggregate over
    keys + grouping id (so a NULL key in the data and a key the set
    null-ed stay different groups), and a Project that drops the id.
    ``grouping(col)`` / ``grouping_id()`` become bits of the id.

    Sets that form a chain (ROLLUP's) under aggregates that re-aggregate
    from their partials take no Expand: the Aggregate reduces the child's
    rows once, at the finest set with grouping id 0, and carries the
    coarser sets as ``rollup``, each reduced from the groups of the set
    before it (``plan/physical.AggregateExec._rollup_levels``)."""
    from .. import bitwisefns as B
    from .. import types as T
    from ..exprs import bind
    from .functions import _GroupingMarker

    n = len(group_exprs)
    schema = child.schema()
    gid = E.UnresolvedColumn(GROUPING_ID)
    key_fps = [e.fingerprint() for _, e in group_exprs]

    def rewrite(e):
        if isinstance(e, _GroupingMarker):
            if not e.children:
                return gid
            fp = e.children[0].fingerprint()
            if fp not in key_fps:
                raise ValueError(
                    f"grouping() of {e.children[0]!r}: not one of the "
                    f"grouping columns {[k for k, _ in group_exprs]}")
            shift = n - 1 - key_fps.index(fp)
            return E.Cast(B.BitwiseAnd(
                B.ShiftRight(gid, E.Literal(shift, T.INT32)),
                E.Literal(1, T.INT64)), T.INT8)
        if not e.children:
            return e
        import copy
        node = copy.copy(e)
        node.children = tuple(rewrite(c) for c in e.children)
        return node

    def number(members) -> int:
        return sum(1 << (n - 1 - i) for i in range(n) if i not in members)

    agg_exprs = [(name, rewrite(e)) for name, e in agg_exprs]
    if _is_chain(sets, n) and _reaggregates(agg_exprs):
        keys = list(group_exprs) + [(GROUPING_ID, E.Literal(0, T.INT64))]
        node = _decompose_agg_exprs(child, keys, agg_exprs)
        agg = node if isinstance(node, L.Aggregate) else node.children[0]
        agg.rollup = tuple((tuple(m), number(m)) for m in sets[1:])
    else:
        reads = sorted(set().union(*[e.references() for _, e in agg_exprs])
                       - {GROUPING_ID})
        null_of = [E.Literal(None, bind(e, schema).dtype)
                   for _, e in group_exprs]
        projections = [
            [(r, E.UnresolvedColumn(r)) for r in reads]
            + [(f"__gk{i}", e if i in members else null_of[i])
               for i, (_, e) in enumerate(group_exprs)]
            + [(GROUPING_ID, E.Literal(number(members), T.INT64))]
            for members in sets]
        keys = [(name, E.UnresolvedColumn(f"__gk{i}"))
                for i, (name, _) in enumerate(group_exprs)]
        node = _decompose_agg_exprs(L.Expand(child, projections),
                                    keys + [(GROUPING_ID, gid)], agg_exprs)
    return L.Project(node, [(name, E.UnresolvedColumn(name))
                            for name, _ in group_exprs + agg_exprs])


class GroupedData:
    def __init__(self, df: DataFrame, group_exprs, grouping_sets=None):
        self._df = df
        self._group_exprs = group_exprs
        # rollup / cube: tuples of indices into group_exprs, the full set
        # first; None for a plain GROUP BY
        self._grouping_sets = grouping_sets

    def agg(self, *cols: Column) -> DataFrame:
        agg_exprs = [_named(c) for c in cols]
        cd = _split_count_distinct(agg_exprs)
        if self._grouping_sets is not None:
            if cd is not None:
                raise NotImplementedError(
                    "count(DISTINCT) under rollup / cube")
            return DataFrame(_plan_grouping_sets(
                self._df._plan, self._group_exprs, self._grouping_sets,
                agg_exprs), self._df.session)
        if cd is not None:
            return _plan_count_distinct(self._df, self._group_exprs,
                                        *cd,
                                        order=[n for n, _ in agg_exprs])
        node = _decompose_agg_exprs(self._df._plan, self._group_exprs, agg_exprs)
        return DataFrame(node, self._df.session)

    def pivot(self, column: str, values) -> "PivotedData":
        """Pivot on explicit values (Spark requires the explicit list for
        GPU PivotFirst; AggregateFunctions.scala PivotFirst analog).  Each
        (pivot value, aggregate) pair lowers to a conditional aggregate —
        agg(when(pivot == v, child)) — so the whole pivot stays on the
        device aggregation path."""
        return PivotedData(self, column, list(values))

    def count(self) -> DataFrame:
        from . import functions as F
        return self.agg(F.count_star().alias("count"))

    def sum(self, *names: str) -> DataFrame:
        from . import functions as F
        return self.agg(*[F.sum(F.col(n)).alias(f"sum({n})") for n in names])

    def avg(self, *names: str) -> DataFrame:
        from . import functions as F
        return self.agg(*[F.avg(F.col(n)).alias(f"avg({n})") for n in names])

    def min(self, *names: str) -> DataFrame:
        from . import functions as F
        return self.agg(*[F.min(F.col(n)).alias(f"min({n})") for n in names])

    def max(self, *names: str) -> DataFrame:
        from . import functions as F
        return self.agg(*[F.max(F.col(n)).alias(f"max({n})") for n in names])
