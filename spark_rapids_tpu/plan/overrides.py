"""TpuOverrides: the wrap→tag→convert planner.

Direct analog of the reference's planning layer:
  * wrap: build a meta tree over the logical plan (RapidsMeta.scala —
    SparkPlanMeta:575 / ExprMeta).
  * tag: per-node TypeSig + capability checks accumulate human-readable
    ``will_not_work_on_tpu`` reasons (RapidsMeta.scala:184,293).
  * convert: supported nodes become TpuExec operators (fusing project/filter
    chains into whole-stage XLA programs); tagged nodes fall back to the CPU
    operators in cpu/exec.py (GpuOverrides.applyOverrides flow,
    GpuOverrides.scala:4513-4541).
  * explain: render per-node placement + reasons, like
    ``spark.rapids.sql.explain=NOT_ON_GPU`` (GpuOverrides.scala:4530-4537).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from .. import exprs as E
from ..aggfns import AGG_CLASSES, AggregateExpression
from ..config import TpuConf
from ..batch import Schema
from ..exprs import BoundReference, Expression, bind
from . import logical as L
from .physical import AggregateExec, ScanExec, StageExec, TpuExec
from .planner import (_bind_project, string_code_predicates,
                      string_code_source, strip_alias)

__all__ = ["apply_overrides", "explain_plan", "NodeMeta"]


# ---------------------------------------------------------------------------------
# Expression tagging
# ---------------------------------------------------------------------------------

def expr_reasons(e: Expression, allow_string_passthrough: bool = True,
                 allow_string_preds: bool = False) -> List[str]:
    """Reasons this bound expression tree cannot lower to the device.

    ``allow_string_preds``: inside fused stages, boolean subtrees over a
    single string column lower to host-precomputed bool columns
    (plan/stringpred.py), so they don't disqualify the node.
    """
    reasons: List[str] = []
    core = strip_alias(e)
    if isinstance(core, BoundReference):
        if core.dtype.is_host_carried:
            # rides as a host arrow column: fine to pass through a device
            # plan untouched, unusable as a compute/key input
            if not allow_string_passthrough:
                reasons.append(
                    f"host-carried column {core.name or core.ordinal} "
                    f"({core.dtype}) used in computation")
        else:
            # a bare column is device data too: its sig (nested types,
            # decimal precision, ...) gates the node exactly like a
            # computed expression's would
            r = core.output_sig.check(core.dtype)
            if r is not None:
                reasons.append(
                    f"column {core.name or core.ordinal}: {r}")
        return reasons

    def walk(node: Expression):
        from ..udf import UserDefinedFunction
        if allow_string_preds:
            from .stringpred import lowerable_kind
            if lowerable_kind(node) is not None:
                return  # lowers to a dictionary-evaluated host column
        if isinstance(node, UserDefinedFunction) and not node.device:
            reasons.append(
                f"python UDF {node.name} is opaque to the planner "
                f"(runs on CPU; use tpu_udf for a device implementation)")
            return
        dt = node.dtype
        if dt is not None:
            if dt.is_string:
                reasons.append(
                    f"expression {type(node).__name__} produces/consumes "
                    f"string (device string kernels pending)")
                return
            # declared support signature drives tagging (TypeChecks.scala
            # ExprChecks model: the same sigs generate supported_ops.md)
            r = node.output_sig.check(dt)
            if r is not None:
                label = (f"column {node.name or node.ordinal}"
                         if isinstance(node, BoundReference)
                         else type(node).__name__)
                reasons.append(f"{label}: {r}")
                return
        in_sig = node.input_sig
        for c in node.children:
            cdt = getattr(c, "dtype", None)
            if cdt is not None and not cdt.is_string:
                r = in_sig.check(cdt)
                if r is not None:
                    reasons.append(
                        f"{type(node).__name__} input "
                        f"{getattr(c, 'name', '') or type(c).__name__}: {r}")
                    continue  # the child's own sig reason would be redundant
            walk(c)

    walk(core)
    return reasons


def key_reasons(e: Expression) -> List[str]:
    """Reasons a sort key or a window's partition / order key cannot run
    on the device.  A string key rides as int32 dictionary codes
    (ops/strings.key_view: equal strings equal codes, and for an ORDER BY
    the rank of the string among the column's distinct values), so a bare
    string column is fine, and so is a CASE that picks between one string
    column and NULL; any other string expression still needs device
    string kernels."""
    core = strip_alias(e)
    if core.dtype is not None and core.dtype.is_string:
        src = string_code_source(core)
        if src is None or src < 0:
            return ["computed string expression (device string kernels "
                    "pending)"]
        return [r for p in string_code_predicates(core)
                for r in expr_reasons(p, allow_string_passthrough=False)]
    return expr_reasons(e, allow_string_passthrough=False)


# ---------------------------------------------------------------------------------
# Meta tree
# ---------------------------------------------------------------------------------

class NodeMeta:
    def __init__(self, plan: L.LogicalPlan, conf: TpuConf):
        self.plan = plan
        self.conf = conf
        self.children = [NodeMeta(c, conf) for c in plan.children]
        self.reasons: List[str] = []
        self._tagged = False

    def will_not_work(self, reason: str):
        self.reasons.append(reason)

    @property
    def on_tpu(self) -> bool:
        return not self.reasons

    # -- tagging ------------------------------------------------------------------
    def tag(self):
        if self._tagged:
            return
        self._tagged = True
        for c in self.children:
            c.tag()
        if not self.conf["spark.rapids.tpu.sql.enabled"]:
            self.will_not_work("spark.rapids.tpu.sql.enabled is false")
            return
        try:
            self._tag_self()
        except Exception as ex:  # tagging must never fail the query
            self.will_not_work(f"tagging error: {ex}")

    def _tag_self(self):
        p = self.plan
        if isinstance(p, L.LogicalScan):
            return  # scans upload whatever arrow gives us
        if isinstance(p, L.Project):
            schema = p.children[0].schema()
            for name, e in p.exprs:
                b = bind(e, schema)
                for r in expr_reasons(b, allow_string_preds=True):
                    self.will_not_work(f"{name}: {r}")
            return
        if isinstance(p, L.Filter):
            b = bind(p.condition, p.children[0].schema())
            for r in expr_reasons(b, allow_string_passthrough=False,
                                  allow_string_preds=True):
                self.will_not_work(f"condition: {r}")
            return
        if isinstance(p, L.Aggregate):
            schema = p.children[0].schema()
            for name, e in p.group_exprs:
                b = bind(e, schema)
                core = strip_alias(b)
                if core.dtype is not None and core.dtype.is_string:
                    # bare string COLUMNS group on device via dictionary
                    # codes (ops/strings.py); computed string keys still
                    # need device string kernels
                    if not isinstance(core, BoundReference):
                        self.will_not_work(
                            f"group key {name} is a computed string "
                            f"expression (device string kernels pending)")
                elif core.dtype is not None and getattr(
                        core.dtype, "is_wide_decimal", False):
                    # two-limb columns sort/compare on device but the
                    # hash-grouping kernels are one-word; CPU fallback
                    self.will_not_work(
                        f"group key {name}: decimal128 grouping keys "
                        "run on CPU")
                else:
                    for r in expr_reasons(b, allow_string_passthrough=False):
                        self.will_not_work(f"group key {name}: {r}")
            for name, e in p.agg_exprs:
                b = strip_alias(bind(e, schema))
                if not isinstance(b, AggregateExpression):
                    self.will_not_work(
                        f"aggregate {name} is not a plain aggregate call")
                    continue
                if not getattr(b, "device_supported", True):
                    self.will_not_work(
                        f"aggregate {name}: {b.func} requires materialized "
                        f"groups (CPU only)")
                    continue
                for c in b.children:
                    for r in expr_reasons(c, allow_string_passthrough=False):
                        self.will_not_work(f"aggregate {name}: {r}")
            return
        if isinstance(p, L.Sort):
            schema = p.children[0].schema()
            for o in p.orders:
                for r in key_reasons(bind(o.expr, schema)):
                    self.will_not_work(f"sort key: {r}")
            return
        if isinstance(p, L.Generate):
            f = next((f for f in p.children[0].schema()
                      if f.name == p.column), None)
            if f is None or f.dtype.element is None:
                self.will_not_work(
                    f"explode column {p.column!r} is not an ARRAY")
            else:
                elem = f.dtype.element
                if elem.is_string or elem.is_nested or elem.is_decimal:
                    self.will_not_work(
                        f"explode of array<{elem}> runs on CPU (elements "
                        f"have no device representation)")
            return
        if isinstance(p, (L.Limit, L.Union, L.LogicalRange, L.Distinct,
                          L.Sample, L.Cache)):
            # Distinct groups by bare column references — string columns
            # go through dictionary codes like any group key
            return
        if isinstance(p, L.Join):
            schema_l = p.children[0].schema()
            schema_r = p.children[1].schema()
            def _tag_keys(keys, schema, side):
                for k in keys:
                    b = bind(k, schema)
                    core = strip_alias(b)
                    if core.dtype is not None and core.dtype.is_string:
                        # bare string columns join via dictionary codes
                        if not isinstance(core, BoundReference):
                            self.will_not_work(
                                f"{side} join key is a computed string "
                                f"expression (device string kernels pending)")
                        continue
                    if core.dtype is not None and getattr(
                            core.dtype, "is_wide_decimal", False):
                        self.will_not_work(
                            f"{side} join key: decimal128 join keys run "
                            "on CPU (one-word hash kernels)")
                        continue
                    for r in expr_reasons(b, allow_string_passthrough=False):
                        self.will_not_work(f"{side} join key: {r}")
            _tag_keys(p.left_keys, schema_l, "left")
            _tag_keys(p.right_keys, schema_r, "right")
            if p.how not in ("inner", "left", "left_outer", "right",
                             "right_outer", "full", "full_outer", "semi",
                             "anti", "left_semi", "left_anti", "cross",
                             "existence"):
                self.will_not_work(f"join type {p.how} not supported")
            cond_ok = ("inner", "left", "left_outer", "semi", "anti",
                       "existence", "left_semi", "left_anti",
                       "right", "right_outer", "full", "full_outer",
                       "outer")
            if p.condition is not None and p.how not in cond_ok:
                self.will_not_work(
                    f"non-equi residual condition on {p.how} join "
                    "runs on CPU")
            if p.condition is not None and p.how in (
                    "left", "left_outer", "right", "right_outer",
                    "full", "full_outer", "outer") \
                    and getattr(p, "using", None):
                self.will_not_work(
                    "conditioned outer USING join (coalesced key columns) "
                    "runs on CPU")
            if p.condition is not None and p.how in cond_ok:
                schema_all = Schema(list(schema_l.fields)
                                    + list(schema_r.fields))
                for r in expr_reasons(bind(p.condition, schema_all),
                                      allow_string_passthrough=False):
                    self.will_not_work(f"join condition: {r}")
            return
        if isinstance(p, L.Expand):
            schema = p.children[0].schema()
            for proj in p.projections:
                for name, e in proj:
                    b = strip_alias(bind(e, schema))
                    if isinstance(b, E.Literal) and b.value is None:
                        continue  # a key outside the grouping set
                    for r in expr_reasons(b):
                        self.will_not_work(f"{name}: {r}")
            return
        if isinstance(p, L.Window):
            from ..windowfns import WindowExpression, device_support_reason
            schema = p.children[0].schema()
            for name, e in p.window_exprs:
                b = strip_alias(bind(e, schema))
                if not isinstance(b, WindowExpression):
                    self.will_not_work(f"{name} is not a window expression")
                    continue
                r = device_support_reason(b)
                if r:
                    self.will_not_work(f"{name}: {r}")
                for pe in b.spec.partition_by:
                    for rr in key_reasons(pe):
                        self.will_not_work(f"{name} partition key: {rr}")
                for o in b.spec.order_by:
                    for rr in key_reasons(o.expr):
                        self.will_not_work(f"{name} order key: {rr}")
                for c in b.func.children:
                    for rr in expr_reasons(c, allow_string_passthrough=False):
                        self.will_not_work(f"{name}: {rr}")
            return
        self.will_not_work(f"operator {type(p).__name__} has no TPU version")

    # -- explain ------------------------------------------------------------------
    def explain_lines(self, indent: int = 0, verbosity: str = "NOT_ON_TPU"
                      ) -> List[str]:
        mark = "*" if self.on_tpu else "!"
        show = verbosity == "ALL" or not self.on_tpu
        lines = []
        if show or True:
            lines.append("  " * indent + f"{mark} {self.plan.node_desc()}")
        for r in self.reasons:
            lines.append("  " * indent + f"    @{r}")
        for c in self.children:
            lines += c.explain_lines(indent + 1, verbosity)
        return lines


# ---------------------------------------------------------------------------------
# Conversion with fusion + fallback
# ---------------------------------------------------------------------------------

def _plan_aggregate(child_phys: TpuExec, group_bound, agg_bound,
                    conf: TpuConf) -> TpuExec:
    """Grouped aggregation as partial → shuffle exchange → final, the
    reference's two-phase shape (GpuHashAggregateExec partial/final around
    GpuShuffleExchangeExec); ungrouped aggregates reduce to one scalar and
    need no exchange."""
    if not group_bound or not conf["spark.rapids.tpu.sql.exchange.enabled"]:
        return AggregateExec(child_phys, group_bound, agg_bound,
                             mode="complete")
    if conf["spark.rapids.tpu.shuffle.mode"] == "CACHE_ONLY" \
            and conf["spark.rapids.tpu.sql.agg.singleProcessComplete"]:
        # single-process: the partial -> exchange -> final shape exists to
        # colocate groups across workers; with one process it is pure
        # overhead (the round-4 sync profile measured ~0.5 s/query of
        # partial-agg sampling + exchange staging).  ICI/HOST modes keep
        # the two-phase shape — their exchanges do real distribution.
        return AggregateExec(child_phys, group_bound, agg_bound,
                             mode="complete")
    from .exchange_exec import ShuffleExchangeExec
    # string keys: partial and final share one dictionary registry so codes
    # stay comparable across the exchange (ops/strings.py)
    shared_dicts: dict = {}
    partial = AggregateExec(child_phys, group_bound, agg_bound, mode="partial",
                            string_dicts=shared_dicts)
    n_parts = conf["spark.rapids.tpu.sql.shuffle.partitions"]
    buf_schema = partial.output_schema
    exch_keys = [BoundReference(i, f.dtype, f.nullable, f.name)
                 for i, f in enumerate(buf_schema.fields[:len(group_bound)])]
    # the final agg only needs groups confined to one batch, not partition
    # alignment — let the exchange coalesce small partitions on read (AQE
    # coalesced-shuffle-read analog, GpuCustomShuffleReaderExec)
    exchange = ShuffleExchangeExec(partial, exch_keys, n_parts,
                                   coalesce_output=True)
    final_keys = [(n, BoundReference(i, e.dtype, e.nullable, n))
                  for i, (n, e) in enumerate(group_bound)]
    return AggregateExec(exchange, final_keys, agg_bound, mode="final",
                         string_dicts=shared_dicts)


def _cpu_node(plan: L.LogicalPlan, children: List[TpuExec]) -> TpuExec:
    """A node placed on the CPU, counted in the query that planned it
    (``QueryStats.cpu_fallback_nodes``)."""
    from ..cpu.exec import CpuOpExec
    from ..utils.metrics import QueryStats
    QueryStats.get().cpu_fallback_nodes += 1
    return CpuOpExec(plan, children)


def _convert(meta: NodeMeta, conf: TpuConf) -> TpuExec:
    p = meta.plan

    if not meta.on_tpu:
        if not conf["spark.rapids.tpu.sql.fallback.enabled"]:
            raise NotImplementedError(
                f"{type(p).__name__} cannot run on TPU and CPU fallback is "
                f"disabled: {'; '.join(meta.reasons)}")
        if conf["spark.rapids.tpu.test.validateExecsOnTpu"]:
            raise AssertionError(
                f"validateExecsOnTpu: {type(p).__name__} fell back to CPU: "
                f"{'; '.join(meta.reasons)}")
        return _cpu_node(p, [_convert(c, conf) for c in meta.children])

    # fuse supported project/filter chains into one StageExec
    if isinstance(p, (L.Project, L.Filter)):
        chain: List[NodeMeta] = []
        node = meta
        while isinstance(node.plan, (L.Project, L.Filter)) and node.on_tpu:
            chain.append(node)
            node = node.children[0]
        child_phys = _convert(node, conf)
        schema = child_phys.output_schema
        steps: List[Tuple[str, object]] = []
        for nm in reversed(chain):
            ln = nm.plan
            if isinstance(ln, L.Filter):
                steps.append(("filter", bind(ln.condition, schema)))
            else:
                triples, schema = _bind_project(ln.exprs, schema)
                steps.append(("project", triples))
        return StageExec(child_phys, steps, schema)

    if isinstance(p, L.LogicalScan):
        return ScanExec(p.schema(), p.source_factory, p.desc)

    if isinstance(p, L.Aggregate):
        child_phys = _convert(meta.children[0], conf)
        schema = child_phys.output_schema
        group_bound = [(n, bind(e, schema)) for n, e in p.group_exprs]
        agg_bound = [(n, strip_alias(bind(e, schema))) for n, e in p.agg_exprs]
        if p.distinct_one_pass:
            from ..utils.metrics import QueryStats
            QueryStats.get().distinct_one_pass_aggs += 1
        return _plan_aggregate(child_phys, group_bound, agg_bound, conf)

    if isinstance(p, L.Distinct):
        child_phys = _convert(meta.children[0], conf)
        schema = child_phys.output_schema
        group_bound = [(f.name, BoundReference(i, f.dtype, f.nullable, f.name))
                       for i, f in enumerate(schema)]
        return _plan_aggregate(child_phys, group_bound, [], conf)

    if isinstance(p, L.Sort):
        from .exec_nodes import SortExec
        child_phys = _convert(meta.children[0], conf)
        schema = child_phys.output_schema
        orders = [(bind(o.expr, schema), o.ascending, o.nulls_first)
                  for o in p.orders]
        return SortExec(child_phys, orders)

    if isinstance(p, L.Limit):
        from .exec_nodes import LimitExec, TopKExec
        child_meta = meta.children[0]
        if isinstance(child_meta.plan, L.Sort) and child_meta.on_tpu:
            # Limit(Sort) ⇒ running top-k (TakeOrderedAndProject / GpuTopN)
            sort_plan = child_meta.plan
            grandchild = _convert(child_meta.children[0], conf)
            schema = grandchild.output_schema
            orders = [(bind(o.expr, schema), o.ascending, o.nulls_first)
                      for o in sort_plan.orders]
            return TopKExec(grandchild, orders, p.n, p.offset)
        return LimitExec(_convert(child_meta, conf), p.n, p.offset)

    if isinstance(p, L.Sample):
        from .exec_nodes import SampleExec
        return SampleExec(_convert(meta.children[0], conf),
                          p.fraction, p.seed)

    if isinstance(p, L.Cache):
        from .exec_nodes import CacheExec
        return CacheExec(_convert(meta.children[0], conf), p)

    if isinstance(p, L.Union):
        from .exec_nodes import UnionExec
        return UnionExec([_convert(c, conf) for c in meta.children])

    if isinstance(p, L.LogicalRange):
        from .exec_nodes import RangeExec
        return RangeExec(p.start, p.end, p.step,
                         conf["spark.rapids.tpu.sql.batchSizeRows"])

    if isinstance(p, L.Join):
        from .exec_nodes import plan_join
        left = _convert(meta.children[0], conf)
        right = _convert(meta.children[1], conf)
        return plan_join(p, left, right, conf)

    if isinstance(p, L.Window):
        from .window_exec import WindowExec
        child_phys = _convert(meta.children[0], conf)
        schema = child_phys.output_schema
        bound = [(n, strip_alias(bind(e, schema)))
                 for n, e in p.window_exprs]
        return WindowExec(child_phys, bound)

    if isinstance(p, L.Generate):
        from .exec_nodes import GenerateExec
        return GenerateExec(_convert(meta.children[0], conf), p.column,
                            p.out_name, p.outer, p.schema())

    if isinstance(p, L.Expand):
        from .exec_nodes import ExpandExec
        child_phys = _convert(meta.children[0], conf)
        schema = child_phys.output_schema
        projections = [
            _bind_project(proj, schema)[0] for proj in p.projections]
        return ExpandExec(child_phys, projections, p.schema())

    raise NotImplementedError(f"no conversion for {type(p).__name__}")


def apply_overrides(plan: L.LogicalPlan, conf: Optional[TpuConf] = None
                    ) -> TpuExec:
    conf = conf or TpuConf()
    from ..utils import tracing
    with tracing.span(None, "plan:overrides", "plan"):
        phys, on_tpu = _place(plan, conf)
    if not on_tpu:
        return phys
    from .coalesce import insert_coalesce
    from .fusion import plan_regions
    # region fusion runs LAST: it groups the final operator chains (incl.
    # the coalesce nodes insert_coalesce just placed) into fused regions.
    # Identity under sql.fusion.enabled=false — the per-op escape hatch.
    with tracing.span(None, "plan:fusion", "plan"):
        return plan_regions(insert_coalesce(phys, conf), conf)


def _place(plan: L.LogicalPlan, conf: TpuConf):
    """Pushdown, tagging, CBO and conversion to exec nodes: (tree,
    whether it was placed on the TPU; False for the all-CPU tree of
    explainonly / sql.enabled=false)."""
    from .optimizer import push_filters
    from .pushdown import optimize_scans
    plan = push_filters(plan)
    plan = optimize_scans(plan)
    meta = NodeMeta(plan, conf)
    meta.tag()
    from .cbo import apply_cbo
    apply_cbo(meta, conf)
    mode = conf["spark.rapids.tpu.sql.mode"]
    explain = conf["spark.rapids.tpu.sql.explain"]
    if explain != "NONE":
        lines = meta.explain_lines(verbosity=explain)
        not_on = [ln for ln in lines if "@" in ln or ln.lstrip().startswith("!")]
        if explain == "ALL" or (not_on and explain == "NOT_ON_TPU"):
            import logging
            logging.getLogger("spark_rapids_tpu.overrides").info(
                "plan placement:\n%s", "\n".join(lines))
    if mode == "explainonly" or not conf["spark.rapids.tpu.sql.enabled"]:
        # force everything to CPU, preserving the tagging report
        def all_cpu(m: NodeMeta) -> TpuExec:
            p = m.plan
            if isinstance(p, L.LogicalScan):
                return ScanExec(p.schema(), p.source_factory, p.desc)
            if isinstance(p, L.LogicalRange):
                from .exec_nodes import RangeExec
                return RangeExec(p.start, p.end, p.step,
                                 conf["spark.rapids.tpu.sql.batchSizeRows"])
            return _cpu_node(p, [all_cpu(c) for c in m.children])
        return all_cpu(meta), False
    return _convert(meta, conf), True


def explain_plan(plan: L.LogicalPlan, conf: Optional[TpuConf] = None) -> str:
    """Explain-only API (ExplainPlan.scala analog)."""
    conf = conf or TpuConf()
    from .optimizer import push_filters
    from .pushdown import optimize_scans
    plan = push_filters(plan)
    plan = optimize_scans(plan)
    meta = NodeMeta(plan, conf)
    meta.tag()
    from .cbo import apply_cbo
    apply_cbo(meta, conf)
    header = ("*  = runs on TPU\n!  = falls back to CPU (reasons follow "
              "on @-lines)\n")
    return header + "\n".join(meta.explain_lines(verbosity="ALL"))
