"""Physical operators (TpuExec nodes).

TPU-native analog of the reference's ``GpuExec`` operator layer
(GpuExec.scala:348-360): every operator consumes/produces an iterator of
:class:`ColumnBatch`.  The defining difference from the reference: a chain of
project/filter operators does not issue per-expression kernels
(basicPhysicalOperators.scala GpuProjectExec/GpuFilterExec) — it is *fused*
into one jitted XLA computation per capacity bucket (``StageExec``), the
whole-stage-codegen idea applied at the XLA level.

Execution is lazy: ``execute(ctx)`` returns a generator; the driver pulls
batches, which keeps peak HBM bounded the same way the reference's iterator
chains do.
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..batch import ColumnBatch, DeviceColumn, Field, HostStringColumn, Schema
from ..config import TpuConf
from ..exprs import (AggregateExpression, Alias, BoundReference, EvalContext,
                     Expression)
from ..ops import batch_utils, dense_agg, groupby
from ..utils import tracing
from ..utils.metrics import MetricSet, QueryStats, fetch, fetch_scalars, \
    prestage, region_fetch, region_scalars, upload

__all__ = ["ExecContext", "TpuExec", "ScanExec", "StageExec", "AggregateExec",
           "CollectExec"]


class ExecContext:
    """Per-query execution context: conf + metrics + device placement."""

    def __init__(self, conf: Optional[TpuConf] = None, device=None):
        self.conf = conf or TpuConf()
        self.device = device
        self.metrics: Dict[str, MetricSet] = {}
        # query-scoped dedupe of identical stats programs across operator
        # INSTANCES (join_exec._dense_prefetch): maps (program identity,
        # build identity) -> the shared pending list, so the same dim
        # table joined N times pays its stats dispatch + sync once
        self.stats_memo: Dict[tuple, list] = {}
        # arm the OOM injector from the test configs (inject_oom marker /
        # spark.rapids.sql.test.injectRetryOOM analog)
        n_retry = self.conf["spark.rapids.tpu.test.injectRetryOOM"]
        n_split = self.conf["spark.rapids.tpu.test.injectSplitAndRetryOOM"]
        # arm unconditionally: a conf with no injection must CLEAR any
        # injections a previous query armed on the process-global injector
        from ..memory.retry import INJECTOR
        INJECTOR.arm(n_retry, n_split)
        # same contract for the unified fault injector (faults/): the
        # spark.rapids.tpu.faults.inject.* confs arm per query, and an
        # unarmed conf clears the previous query's schedule/rate
        from ..faults.injector import INJECTOR as FAULT_INJECTOR
        FAULT_INJECTOR.arm_from_conf(self.conf)
        # the network link-fault fabric arms from conf on the same
        # contract (identical re-arms preserve its RNG + engage state)
        from ..faults.netfabric import FABRIC as NET_FABRIC
        NET_FABRIC.arm_from_conf(self.conf)
        # the live metrics registry arms/disarms on the same per-query
        # contract (telemetry.enabled + the server.slo.* objectives)
        from ..utils import telemetry
        telemetry.configure(self.conf)
        # the capacity-bucket ladder arms on the same contract (the
        # warmstore.bucket.* confs; identical re-arms are free)
        from . import bucketing
        bucketing.configure(self.conf)

    def metric_set(self, op_id: str) -> MetricSet:
        if op_id not in self.metrics:
            self.metrics[op_id] = MetricSet(
                op_id, level=self.conf["spark.rapids.tpu.sql.metrics.level"])
        return self.metrics[op_id]


def _instrument_execute(fn):
    """Wrap a subclass's ``execute`` with the span layer: every batch pull
    is timed on the thread it runs on (utils/tracing.instrument_batches),
    recording uniform rows/batches/bytes/time per operator — the profiled
    EXPLAIN and trace-export surface.  Applied at class-definition time by
    ``TpuExec.__init_subclass__`` so no operator can opt out."""
    import functools

    from ..utils import tracing

    @functools.wraps(fn)
    def execute(self, ctx, *args, **kwargs):
        it = fn(self, ctx, *args, **kwargs)
        m = ctx.metric_set(self.op_id) if isinstance(ctx, ExecContext) \
            else None
        return tracing.instrument_batches(self.op_id, type(self).__name__,
                                          m, it)

    execute._span_instrumented = True
    return execute


class TpuExec:
    """Base physical operator."""

    # True when execute() yields one batch per shuffle partition, in
    # partition-id order (set by ShuffleExchangeExec; consumed by final
    # aggregates and shuffled joins)
    outputs_partitions = False

    # True for operators the region planner (plan/fusion.py) may group
    # into a fused region: streaming device operators whose host syncs
    # route through the region's batched prologue.  Pipeline breakers
    # (exchanges, sorts, windows, CPU fallbacks) stay False — they are
    # the region boundaries.
    region_fusible = False

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        fn = cls.__dict__.get("execute")
        if fn is not None and not getattr(fn, "_span_instrumented", False):
            cls.execute = _instrument_execute(fn)

    def __init__(self, children: Sequence["TpuExec"] = ()):
        self.children = list(children)
        self.op_id = f"{type(self).__name__}@{id(self):x}"

    @property
    def output_schema(self) -> Schema:
        raise NotImplementedError

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        raise NotImplementedError

    def child_coalesce_goal(self, i: int, conf):
        """Desired input-batch granularity for child ``i`` (CoalesceGoal),
        or None.  The transition pass (plan/coalesce.insert_coalesce)
        materializes non-None goals as CoalesceBatchesExec nodes."""
        return None

    # -- plan display -------------------------------------------------------------
    def node_desc(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        lines = [("  " * indent) + ("+- " if indent else "") + self.node_desc()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)


# ---------------------------------------------------------------------------------
# Scan: pulls pyarrow record batches from a source and uploads them.
# ---------------------------------------------------------------------------------

class ScanExec(TpuExec):
    """Leaf scan over a host Arrow batch source (parquet/csv/... readers in
    io/ produce the source).  Mirrors GpuFileSourceScanExec: host-side parse,
    then upload at the device boundary (GpuParquetScan.scala readToTable)."""

    region_fusible = True

    def __init__(self, schema: Schema, source_factory: Callable[[], Iterator],
                 desc: str = "source"):
        super().__init__()
        self._schema = schema
        self._source_factory = source_factory
        self.desc = desc
        # runtime predicates injected by dynamic partition pruning
        # (plan/join_exec._inject_dpp): applied through with_pushdown at
        # execute time so file/row-group pruning sees them
        self.runtime_predicates = None

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        return f"TpuScan [{self.desc}] {self._schema.names()}"

    def _effective_source(self):
        src = self._source_factory
        preds = self.runtime_predicates
        if callable(preds):
            # DPP hands over a THUNK: predicate materialization (which
            # blocks on the join's build stats) defers to the first scan
            # read.  Inside a fused region that ordering is the whole
            # point — every join in the chain has STAGED its stats by
            # the time the scan reads, so one prologue fetch covers all
            # of them instead of one eager sync per join at build time.
            preds = self.runtime_predicates = preds()
        if preds and hasattr(src, "with_pushdown"):
            src = src.with_pushdown(None, preds)
        return src

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        from ..batch import ColumnBatch as _CB, from_arrow
        m = ctx.metric_set(self.op_id)
        min_cap = ctx.conf["spark.rapids.tpu.sql.minBatchCapacity"]
        source = self._effective_source()

        # cross-query device cache (spark_rapids_tpu/cache/): a hit skips
        # decode AND upload across QUERIES, not just reruns of this plan;
        # a cached superset projection serves narrower scans by slicing
        # (the host decoded-file cache still composes on misses).
        from ..cache import cache_enabled
        qcache = None
        qkey = None
        if cache_enabled(ctx.conf, "scan"):
            from ..cache import get_query_cache, scan_key
            qkey = scan_key(source, min_cap, ctx.device)
            if qkey is not None:
                qcache = get_query_cache(ctx.conf)
                hit = qcache.lookup_scan(qkey, self._schema,
                                         op_id=self.op_id)
                if hit is not None:
                    entry, batches = hit
                    origin = str(getattr(source, "path", "") or "")
                    m.add("cacheHitBatches", len(batches))
                    try:
                        for b in batches:
                            from ..service import cancel as _cancel
                            _cancel.check()
                            b.origin_file = origin
                            m.add("numOutputRows", b.num_rows)
                            m.add("numOutputBatches", 1)
                            yield b
                    finally:
                        # released even when the consumer abandons the
                        # stream (LIMIT) — the entry stays evictable
                        qcache.release(entry)
                    return

        # the accumulator pins batches in HBM until the scan completes, so
        # abandon it the moment the running size exceeds the cache budget —
        # an over-budget scan must keep streaming/spilling, not OOM
        from ..cache import batch_bytes as _cb_bytes
        acc = [] if qcache is not None else None
        acc_bytes = 0
        origin = str(getattr(source, "path", "") or "")

        from ..runtime.pipeline import effective_depth, pipeline_map
        depth = effective_depth(ctx)

        def _upload(table):
            # staged on the pipeline worker: batch N+1's Arrow→numpy
            # conversion and device_put run while batch N's XLA program
            # is in flight (depth 0 = the old serial loop)
            with m.time("scanTime"):
                return from_arrow(table, min_capacity=min_cap,
                                  device=ctx.device)

        try:
            # size the decode-prefetch queue to keep the upload stage fed
            tables = source(prefetch_depth=max(4, 2 * depth))
        except TypeError:  # plain-callable sources (tests, exchanges)
            tables = source()
        from ..service import cancel
        for b in pipeline_map(tables, _upload, depth, label=self.op_id):
            cancel.check()  # a cancelled query stops decoding/uploading
            b.origin_file = origin
            m.add("numOutputRows", b.num_rows)
            m.add("numOutputBatches", 1)
            if acc is not None:
                acc_bytes += _cb_bytes(b)
                if acc_bytes > qcache.max_bytes:
                    acc = None
                else:
                    acc.append(b)
                    # re-wrap on the populate path too: consumers must never
                    # hold the object that sits in the cache
                    b = _CB(b.schema, b.columns, b.num_rows, b.sel)
            yield b
        if acc is not None:
            qcache.insert_scan(qkey, acc, op_id=self.op_id, conf=ctx.conf)


# ---------------------------------------------------------------------------------
# Fused project/filter stage.
# ---------------------------------------------------------------------------------

from collections import OrderedDict

_STAGE_CACHE: "OrderedDict[str, Callable]" = OrderedDict()
_STAGE_CACHE_LOCK = threading.Lock()
_STAGE_CACHE_MAX = 512


def _cached_program(fp: str, build: Callable[[], Callable]) -> Callable:
    """Process-wide jitted-program cache keyed by structural fingerprint.

    jax.jit memoizes per function *object*; operators build fresh closures
    per execution, so without this every query run would recompile (the
    executable-cache idea from SURVEY §7.2: cache keyed by (HLO, shapes) —
    here (fingerprint, shapes), jit handling the shapes part).  Bounded LRU:
    fingerprints embed literal values, so parameterized query streams would
    otherwise grow it without limit.
    """
    with _STAGE_CACHE_LOCK:
        fn = _STAGE_CACHE.get(fp)
        if fn is None:
            fn = build()
            _STAGE_CACHE[fp] = fn
            while len(_STAGE_CACHE) > _STAGE_CACHE_MAX:
                _STAGE_CACHE.popitem(last=False)
        else:
            _STAGE_CACHE.move_to_end(fp)
        return fn


# ---------------------------------------------------------------------------------
# Program names.  Every function jitted under plan/, ops/batch_utils.py,
# runtime/warmstore.py and parallel/ goes through :func:`program`, under
# a name from this fixed vocabulary (the kind that heads its cache key,
# where it has one).  The name is the jitted function's ``__name__``, so
# it is the ``fun_name`` of jax's compile events (QueryStats' listener,
# recorder.compile_note, the benchmark's ``slowest_compiles``), the
# ``jit_<name>`` module on a device trace's ``XLA Modules`` line, and the
# ``program:<name>`` span around every call.  Never a fingerprint or a
# literal: those belong to the cache key.
# ---------------------------------------------------------------------------------

PROGRAM_NAMES = frozenset((
    "stage", "window",
    "agg_ungrouped", "agg_ungrouped_fused", "agg_ungrouped_merge",
    "agg_ungrouped_finalize", "agg_dense_stats", "agg_dense_update",
    "agg_mdense_stats", "agg_mdense_update", "agg_mdense_violation",
    "agg_grouped", "agg_grid", "agg_passthrough", "agg_sample",
    "agg_bucket_pid", "agg_finalize", "agg_merge_grouped",
    "sort", "sort_range_key", "generate_gather", "expand_project",
    "exchange_pid", "batch_concat", "batch_concat_packed", "batch_compact",
    "batch_compact_scatter", "batch_slice",
    "smj_filter_stats", "smj_filter_vals", "join_subpid",
    "join_cond_expand", "join_cond", "join_residual", "join_match",
    "join_expand", "join_unmatched", "join_take64", "bjoin_sort",
    "bjoin_probe",
    "bjoin_csr", "bjoin_csr_probe", "bjoin_dense_stats",
    "bjoin_dense_table", "bjoin_dense_probe",
    "ici_fragment_step", "ici_fragment_gather", "ici_agg_step",
))


class _Program:
    """A named program: every call is a ``program:<name>`` span (the
    account's ``dispatch``: the driving thread inside the JAX runtime).
    Everything else (``lower``, ...) is the wrapped callable's."""

    __slots__ = ("name", "call", "_span_name")

    def __init__(self, name: str, call: Callable):
        if name not in PROGRAM_NAMES:
            raise ValueError(f"program name {name!r} is not in "
                             f"plan/physical.PROGRAM_NAMES")
        self.name = name
        self.call = call
        self._span_name = "program:" + name

    def __call__(self, *args, **kwargs):
        with tracing.span(None, self._span_name, "program"):
            return self.call(*args, **kwargs)

    def __getattr__(self, attr):
        return getattr(self.call, attr)


def program(name: str, fn: Optional[Callable] = None, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under ``name`` (one of
    :data:`PROGRAM_NAMES`); without ``fn``, the decorator form."""
    if fn is None:
        return functools.partial(program, name, **jit_kwargs)
    fn.__name__ = fn.__qualname__ = name
    return _Program(name, jax.jit(fn, **jit_kwargs))


def install_program(fp: str, name: str, fn: Callable) -> None:
    """Pre-install a program under a cache key (the warm-start prewarm
    lane's entry point: an AOT-compiled executable takes the slot the
    live path would otherwise fill with a cold jit), wrapped as the
    live one is: ``name`` is its :data:`PROGRAM_NAMES` entry.
    First-writer wins — a live query that already compiled keeps its
    program."""
    fn = _Program(name, fn)
    with _STAGE_CACHE_LOCK:
        if fp in _STAGE_CACHE:
            return
        _STAGE_CACHE[fp] = fn
        while len(_STAGE_CACHE) > _STAGE_CACHE_MAX:
            _STAGE_CACHE.popitem(last=False)


def has_program(fp: str) -> bool:
    with _STAGE_CACHE_LOCK:
        return fp in _STAGE_CACHE


def program_cache_size() -> int:
    """Distinct compiled stage programs resident right now — the
    program-count metric bench.py reports per query (bucketing's win
    is fewer programs, not just fewer compile seconds)."""
    with _STAGE_CACHE_LOCK:
        return len(_STAGE_CACHE)


def clear_program_cache() -> List[str]:
    """Drop every resident program and return the evicted cache keys —
    the restart simulation used by the warm-start differential (loadgen
    --restart-probe, tests): a process restart loses exactly this state,
    and the returned keys are what the old life would have persisted."""
    with _STAGE_CACHE_LOCK:
        keys = list(_STAGE_CACHE)
        _STAGE_CACHE.clear()
    return keys


class StageExec(TpuExec):
    """A fused pipeline of project and filter steps over one input.

    ``steps`` is a list of ("project", [(name, expr, host_src), ...]) or
    ("filter", pred_expr); expressions are bound against the running
    intermediate schema.  ``host_src`` (set when expr is None) marks a host
    string column passed through by reference.  The whole list compiles to
    ONE XLA computation.
    """

    region_fusible = True

    def __init__(self, child: TpuExec, steps: List[Tuple[str, object]],
                 output_schema: Schema):
        super().__init__([child])
        from .stringpred import lower_string_predicate_steps
        self.steps, self.host_exprs = lower_string_predicate_steps(
            steps, child.output_schema)
        self._schema = output_schema

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        kinds = "+".join(k for k, _ in self.steps)
        return f"TpuStage [{kinds}] -> {self._schema.names()}"

    # fingerprint identifies the traced program (cache key)
    def fingerprint(self) -> str:
        def host_fp(src):
            if isinstance(src, tuple) and src[0] == "hc":
                return f"hc#{self.host_exprs[src[1]][0].fingerprint()}"
            return f"host#{src}"

        parts = []
        for kind, payload in self.steps:
            if kind == "project":
                parts.append("P(" + ";".join(
                    f"{n}={e.fingerprint() if e is not None else host_fp(src)}"
                    for n, e, src in payload) + ")")
            else:
                parts.append(f"F({payload.fingerprint()})")
        return "|".join(parts)

    def _build_fn(self, in_schema: Schema, ansi: bool = False):
        steps = self.steps

        def stage_fn(arrays, extras, sel, num_rows):
            capacity = None
            for a in arrays:
                if a is not None:
                    capacity = a[0].shape[0]
                    break
            if capacity is None:
                capacity = next(e[0].shape[0] for e in extras
                                if e is not None)
            active = jnp.arange(capacity, dtype=jnp.int32) < num_rows
            if sel is not None:
                active = active & sel
            cur = list(arrays)
            errors = []
            for kind, payload in steps:
                ctx = EvalContext(cur, capacity, active=active,
                                  extras=extras, ansi=ansi)
                if kind == "filter":
                    d, v = payload.eval(ctx)
                    keep = d if v is None else (d & v)
                    active = active & keep
                else:
                    nxt = []
                    for name, e, host_src in payload:
                        if e is None:  # host-column pass-through marker
                            nxt.append(None)
                        else:
                            nxt.append(e.eval(ctx))
                    cur = nxt
                errors += ctx.errors
            if not ansi:
                return tuple(cur), active
            err = jnp.zeros((), dtype=bool)
            for e in errors:
                err = err | jnp.any(e)
            return tuple(cur), active, err

        return stage_fn

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        child = self.children[0]
        in_schema = child.output_schema
        m = ctx.metric_set(self.op_id)
        ansi = ctx.conf["spark.rapids.tpu.sql.ansi.enabled"]
        fp = self.fingerprint() + ("|ansi" if ansi else "")
        fn = _cached_program(
            "stage|" + fp,
            lambda: program("stage", self._build_fn(in_schema, ansi=ansi)))
        from ..runtime.pipeline import effective_depth, pipeline_batches

        # figure out host pass-through columns for the final projection
        final_proj = None
        for kind, payload in reversed(self.steps):
            if kind == "project":
                final_proj = payload
                break

        from ..cpu.eval import set_ansi
        from ..faults.recovery import device_guard
        from ..memory.retry import with_retry

        # batch-context state for mid()/spark_partition_id()/
        # input_file_name() (miscfns.py): per-partition row offsets when
        # the child yields partitions, else one running stream.  The
        # base advances inside run_one (per INVOCATION, not per input
        # batch) so OOM split-retry halves draw disjoint id ranges —
        # unique and increasing with gaps, which is all Spark promises.
        partitioned = child.outputs_partitions
        pid0 = getattr(ctx, "partition_id_base", 0)
        bstate = {"row_base": 0, "pid": pid0}

        def run_one(b: ColumnBatch) -> ColumnBatch:
            arrays = []
            for i, (f_, c) in enumerate(zip(b.schema, b.columns)):
                arrays.append(None if isinstance(c, HostStringColumn)
                              else (c.data, c.valid))
            extras = []
            host_computed = {}
            if self.host_exprs:
                from ..miscfns import set_batch_context
                from .stringpred import evaluate_host_expr
                base = bstate["row_base"]
                bstate["row_base"] += b.num_rows
                set_batch_context(
                    row_base=base,
                    partition_id=bstate["pid"],
                    file_name=getattr(b, "origin_file", "") or "")
                cap = b.capacity
                set_ansi(ansi)
                try:
                    for k, (expr, ords, kind) in enumerate(self.host_exprs):
                        data, valid = evaluate_host_expr(
                            expr, ords, b.columns, b.num_rows)
                        if kind == "host":
                            # computed host-carried output (string / ARRAY
                            # / STRUCT): arrow column of the expr type
                            import pyarrow as pa

                            from ..batch import logical_to_arrow
                            vals = [v if ok else None
                                    for v, ok in zip(data.tolist(),
                                                     valid.tolist())]
                            host_computed[k] = HostStringColumn(
                                pa.array(vals,
                                         type=logical_to_arrow(expr.dtype)),
                                capacity=cap)
                            extras.append(None)
                            continue
                        pad = cap - len(data)
                        if pad > 0:
                            data = np.concatenate(
                                [data, np.zeros(pad, dtype=data.dtype)])
                            valid = np.concatenate(
                                [valid, np.zeros(pad, dtype=bool)])
                        extras.append((jnp.asarray(data),
                                       jnp.asarray(valid)))
                finally:
                    # the thread-local must never leak past this batch —
                    # ANSI errors raise out of evaluate_host_expr
                    set_ansi(False)
            def _assemble(out_arrays, new_sel):
                cols: List = []
                for oi, f_ in enumerate(self._schema):
                    val = out_arrays[oi] if oi < len(out_arrays) else None
                    if val is None:
                        # host column: pass-through ref or host-computed
                        # string
                        src = self._host_source_ordinal(oi)
                        if isinstance(src, tuple) and src[0] == "hc":
                            cols.append(host_computed[src[1]])
                        else:
                            cols.append(b.columns[src])
                    else:
                        data, valid = val
                        cols.append(DeviceColumn(f_.dtype, data, valid))
                return ColumnBatch(self._schema, cols, b.num_rows, new_sel)

            if all(a is None for a in arrays) and \
                    all(e is None for e in extras):
                # pure host-column stage (string-only projection): no XLA
                # program to run
                return _assemble((None,) * len(self._schema), b.sel)
            from ..runtime import warmstore
            if warmstore.is_active():
                # record this program call's pytree signature under the
                # statement's warm-start entry (deduped after batch 1)
                warmstore.note_program("stage|" + fp, arrays, extras,
                                       b.sel, ansi)

            def _device_result():
                outs = fn(tuple(arrays), tuple(extras),
                          b.sel, np.int32(b.num_rows))
                if ansi:
                    out_arrays, new_sel, err = outs
                    if bool(err):
                        raise ArithmeticError(
                            "ANSI mode: overflow, invalid cast, or "
                            "division by zero (spark.rapids.tpu.sql."
                            "ansi.enabled=true raises instead of "
                            "nulling/wrapping)")
                else:
                    out_arrays, new_sel = outs
                return _assemble(out_arrays, new_sel)

            # device.op guard: transient (non-OOM) runtime failures
            # re-dispatch with backoff, then this batch degrades to the
            # host expression evaluator (cpu/eval) when the stage has no
            # host-lowered exprs and ANSI error masking is off (the CPU
            # path cannot scope ANSI errors to active rows)
            cpu_fb = None if (self.host_exprs or ansi) \
                else (lambda: self._cpu_batch(b, ctx))
            return device_guard(ctx, self.op_id, _device_result,
                                cpu_fallback=cpu_fb)

        # pull the child up to `depth` batches ahead: its host decode +
        # upload (and any upstream dispatch) overlaps this stage's XLA
        # programs (depth 0 = the old lockstep pull loop)
        for batch in pipeline_batches(child.execute(ctx),
                                      effective_depth(ctx),
                                      label=self.op_id):
            with m.time("opTime"):
                outs = list(with_retry(ctx, batch, run_one))
            if partitioned:
                bstate["pid"] += 1
                bstate["row_base"] = 0
            for out in outs:
                m.add("numOutputRows", out.num_rows)
                m.add("numOutputBatches", 1)
                yield out

    def _host_source_ordinal(self, out_ordinal: int):
        """Chase a host output back to its input ordinal, or to an
        ("hc", k) host-computed marker."""
        ord_ = out_ordinal
        for kind, payload in reversed(self.steps):
            if kind != "project":
                continue
            name, e, src = payload[ord_]
            assert e is None and src is not None, (
                "host column used in computed expression; planner "
                "should have routed this stage to CPU")
            if isinstance(src, tuple) and src[0] == "hc":
                return src
            ord_ = src
        return ord_

    def _cpu_batch(self, b: ColumnBatch, ctx: ExecContext) -> ColumnBatch:
        """Graceful-degradation path (faults/recovery.device_guard): run
        THIS batch through the host expression evaluator when the
        device op keeps failing transiently — same project/filter
        semantics as the XLA program, evaluated by cpu/eval over the
        fetched rows.  Only engaged for stages without host-lowered
        exprs and with ANSI off (see execute); the result re-uploads so
        downstream operators are unaffected."""
        import pyarrow as pa

        from ..batch import from_arrow, to_arrow
        from ..cpu.eval import eval_cpu
        from ..cpu.exec import arrow_to_values, values_to_arrow
        from ..ops import batch_utils
        t = to_arrow(batch_utils.compact(b))
        n = t.num_rows
        cur = arrow_to_values(t, self.children[0].output_schema)
        active = np.ones(n, dtype=bool)
        for kind, payload in self.steps:
            if kind == "filter":
                d, v = eval_cpu(payload, cur, n)
                keep = np.asarray(d, dtype=bool)
                if v is not None:
                    keep = keep & np.asarray(v, dtype=bool)
                active &= keep
            else:
                nxt = []
                for _name, e, src in payload:
                    nxt.append(cur[src] if e is None
                               else eval_cpu(e, cur, n))
                cur = nxt
        out_t = values_to_arrow(self._schema, cur, n)
        if not active.all():
            out_t = out_t.filter(pa.array(active))
        out = from_arrow(
            out_t,
            min_capacity=ctx.conf["spark.rapids.tpu.sql.minBatchCapacity"],
            device=ctx.device)
        origin = getattr(b, "origin_file", None)
        if origin is not None:
            out.origin_file = origin
        return out


# ---------------------------------------------------------------------------------
# Hash aggregate (sort-based on device; concat-merge across batches, like the
# reference's GpuMergeAggregateIterator concat-merge loop aggregate.scala:711).
# ---------------------------------------------------------------------------------

class AggregateExec(TpuExec):
    """Group-by aggregation over all input batches.

    mode: "complete" (single pass), or "partial"/"final" around an exchange.
    Buffer layout (partial output schema): [key0..kN, buf0..bufM] where each
    aggregate contributes len(buffers()) buffer columns.
    """

    region_fusible = True

    def __init__(self, child: TpuExec, group_exprs: List[Tuple[str, Expression]],
                 agg_exprs: List[Tuple[str, AggregateExpression]],
                 mode: str = "complete", string_dicts: Optional[dict] = None):
        super().__init__([child])
        self.group_exprs = group_exprs
        self.agg_exprs = agg_exprs
        self.mode = mode
        # group-index → StringDictionary for string-typed keys (shared with
        # the partner partial/final exec so codes stay comparable across the
        # exchange; see ops/strings.py)
        self.string_dicts = string_dicts if string_dicts is not None else {}
        out_fields = [Field(n, e.dtype, e.nullable) for n, e in group_exprs]
        if mode == "partial":
            for name, agg in agg_exprs:
                for bi, (dt, op) in enumerate(agg.buffers()):
                    out_fields.append(Field(f"{name}#buf{bi}", dt, True))
        else:
            out_fields += [Field(n, a.dtype, a.nullable) for n, a in agg_exprs]
        self._schema = Schema(out_fields)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def node_desc(self):
        keys = [n for n, _ in self.group_exprs]
        aggs = [f"{a.func}({n})" for n, a in self.agg_exprs]
        return f"TpuHashAggregate [{self.mode}] keys={keys} aggs={aggs}"

    def child_coalesce_goal(self, i, conf):
        # grouped modes: bigger input batches -> fewer reduce/merge passes.
        # Scalar (ungrouped) aggregates reduce each batch in one cheap pass
        # and handle selection masks in the reduction itself — coalescing
        # ahead of them is pure overhead (measured: Q6 warm +70%).  The
        # final mode's exchange child is partition-aligned (skipped by the
        # transition pass anyway).
        from .coalesce import TargetSize
        if self.group_exprs and self.mode in ("complete", "partial"):
            # host string columns make coalescing a net loss twice over:
            # the concat itself is an O(rows) host copy per run, and the
            # fresh column objects defeat the per-column dictionary-encode
            # cache (_encode_string_keys) — per-batch grid/group passes
            # cost the same total device time anyway
            if any(f.dtype.is_string
                   for f in self.children[0].output_schema):
                return None
            # dense-eligible single-int-key aggregates scatter per batch
            # into one domain-sized accumulator: coalescing ahead of them
            # buys nothing on-device and costs a live-count round trip +
            # concat pass (if the dense path rejects at runtime, the sort
            # path still merges per-batch partials correctly)
            ops = self._buffer_ops()
            if self._dense_agg_static_ok(ops, conf) \
                    or self._dense_residual_static_ok(ops, conf):
                return None
            return TargetSize(conf["spark.rapids.tpu.sql.batchSizeRows"])
        return None

    def _fingerprint(self) -> str:
        """Structural key for the jitted-program cache: a new AggregateExec
        for the same query shape must reuse the compiled executable."""
        parts = [self.mode]
        parts += [f"k:{e.fingerprint()}" for _, e in self.group_exprs]
        parts += [f"a:{a.fingerprint()}" for _, a in self.agg_exprs]
        return "|".join(parts)

    # -- helpers ------------------------------------------------------------------
    def _buffer_ops(self) -> List[str]:
        ops = []
        for _, agg in self.agg_exprs:
            ops += [op for _, op in agg.buffers()]
        return ops

    def _merge_input_layout(self):
        """When mode == 'final', inputs are already buffer columns."""
        n_keys = len(self.group_exprs)
        return n_keys

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        self._ansi = ctx.conf["spark.rapids.tpu.sql.ansi.enabled"]
        if self.group_exprs:
            yield from self._execute_grouped(ctx)
        else:
            yield from self._execute_ungrouped(ctx)

    # -- ungrouped ----------------------------------------------------------------
    def _detached(self) -> "AggregateExec":
        """Shallow copy with no children, for closures that outlive the
        query in the program cache — a cached program must pin only the
        expressions it traces, never the plan tree (operators reference
        cache nodes, spillable handles, sources)."""
        import copy
        d = copy.copy(self)
        d.children = ()
        return d

    def _execute_ungrouped(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        child = self.children[0]
        m = ctx.metric_set(self.op_id)
        ops = self._buffer_ops()
        slf = self._detached()

        if self.mode == "final":
            update = slf._final_mode_update
        else:
            update = slf._update_contributions

        # whole-stage scalar aggregation: fold the child filter/project
        # stage INTO the per-batch reduction program — one dispatch less
        # per batch, and a scalar aggregate needs nothing from the stage
        # but its (tiny) reduced outputs
        fused_stage = None
        if isinstance(child, StageExec) and not child.host_exprs \
                and not ctx.conf["spark.rapids.tpu.sql.ansi.enabled"]:
            # (under ANSI the stage runs unfused so its error channel is
            # checked at the stage boundary)
            fused_stage = child
            child = fused_stage.children[0]
            stage_fn = fused_stage._build_fn(child.output_schema)

            def build():
                @program("agg_ungrouped_fused")
                def batch_partials(arrays, sel, num_rows):
                    out_arrays, active = stage_fn(arrays, (), sel, num_rows)
                    cap = next(a[0].shape[0] for a in arrays
                               if a is not None)
                    ectx = EvalContext(list(out_arrays), cap, active=active)
                    contribs = update(ectx)
                    return groupby.ungrouped_reduce(
                        [(cv, op) for cv, op in zip(contribs, ops)], active)
                return batch_partials

            fp = ("agg-ungrouped-fused|" + fused_stage.fingerprint()
                  + "|" + self._fingerprint())
        else:
            def build():
                @program("agg_ungrouped")
                def batch_partials(arrays, sel, num_rows):
                    cap = next(a[0].shape[0] for a in arrays
                               if a is not None)
                    active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                    if sel is not None:
                        active = active & sel
                    ectx = EvalContext(arrays, cap, active=active)
                    contribs = update(ectx)
                    return groupby.ungrouped_reduce(
                        [(cv, op) for cv, op in zip(contribs, ops)], active)
                return batch_partials

            fp = "agg-ungrouped|" + self._fingerprint()

        batch_partials = _cached_program(fp, build)

        from ..memory.retry import with_retry

        def run_one(b: ColumnBatch):
            arrays = tuple((c.data, c.valid) if isinstance(c, DeviceColumn)
                           else None for c in b.columns)
            return batch_partials(arrays, b.sel, np.int32(b.num_rows))

        # merge runs as ONE jitted program per pair — never eager ops:
        # each eager primitive is its own dispatch, dwarfing the actual
        # compute
        merge_fn = _cached_program(
            "agg-merge|" + self._fingerprint(),
            lambda: program("agg_ungrouped_merge",
                            lambda a, b: slf._merge_scalars(a, b, ops)))

        from ..runtime.pipeline import effective_depth, pipeline_batches
        acc: Optional[List] = None
        # scan decode/upload of batch N+1 overlaps this reduction's
        # dispatch (the fused path consumes the scan directly, so this
        # is its only pipelining point)
        for batch in pipeline_batches(child.execute(ctx),
                                      effective_depth(ctx),
                                      label=self.op_id):
            with m.time("opTime"):
                for partials in with_retry(ctx, batch, run_one):
                    acc = partials if acc is None else merge_fn(acc, partials)
        if acc is None:
            acc = self._empty_scalars()
        out = self._finalize_scalars(acc)
        m.add("numOutputRows", 1)
        yield out

    def _update_contributions(self, ectx: EvalContext):
        contribs = []
        for _, agg in self.agg_exprs:
            contribs += agg.update(ectx)
        return contribs

    def _final_mode_update(self, ectx: EvalContext):
        """In final mode the child columns ARE the buffers: pass them through."""
        n_keys = len(self.group_exprs)
        return [ectx.arrays[i] for i in range(n_keys, len(ectx.arrays))]

    @staticmethod
    def _merge_scalars(a, b, ops):
        out = []
        for (ad, av), (bd, bv), op in zip(a, b, ops):
            if op == "sum":
                out.append((ad + bd, None))
            elif op == "min":
                out.append((jnp.minimum(ad, bd), None))
            elif op == "max":
                out.append((jnp.maximum(ad, bd), None))
            elif op in ("first", "first_valid"):
                # validity channel = "partial had a qualifying row"; keep the
                # earlier partial only when it actually saw one
                ha = jnp.asarray(True) if av is None else av
                hb = jnp.asarray(True) if bv is None else bv
                out.append((jnp.where(ha, ad, bd), ha | hb))
            elif op in ("last", "last_valid"):
                ha = jnp.asarray(True) if av is None else av
                hb = jnp.asarray(True) if bv is None else bv
                out.append((jnp.where(hb, bd, ad), ha | hb))
            else:
                raise ValueError(op)
        return out

    def _empty_scalars(self):
        outs = []
        for _, agg in self.agg_exprs:
            for dt, op in agg.buffers():
                np_dt = dt.numpy_dtype
                if op == "sum":
                    outs.append((jnp.zeros((), dtype=np_dt), None))
                elif op == "min":
                    outs.append((jnp.array(
                        groupby._SENTINELS["min"]["f" if dt.is_floating else "i"](
                            np_dt), dtype=np_dt), None))
                elif op == "max":
                    outs.append((jnp.array(
                        groupby._SENTINELS["max"]["f" if dt.is_floating else "i"](
                            np_dt), dtype=np_dt), None))
                else:
                    outs.append((jnp.zeros((), dtype=np_dt),
                                 jnp.array(False)))
        return outs

    def _finalize_scalars(self, acc) -> ColumnBatch:
        from ..batch import bucket_capacity
        cap = bucket_capacity(1)
        mode = self.mode
        agg_exprs = self.agg_exprs

        def _fin(acc_):
            """Whole finalize as one traced program (no eager primitives)."""
            outs = []
            i = 0
            for (_name, agg) in agg_exprs:
                nb = len(agg.buffers())
                buf_vals = []
                for (d, v) in acc_[i: i + nb]:
                    bd = jnp.broadcast_to(d, (cap,))
                    bv = None if v is None else jnp.broadcast_to(v, (cap,))
                    buf_vals.append((bd, bv))
                i += nb
                if mode == "partial":
                    outs.extend(buf_vals)
                elif getattr(agg, "host_finalize", False):
                    outs.extend(buf_vals)  # raw limbs: host reconstructs
                else:
                    data, valid = agg.finalize(buf_vals)
                    data = jnp.broadcast_to(data, (cap,))
                    if valid is not None:
                        valid = jnp.broadcast_to(valid, (cap,))
                    outs.append((data.astype(agg.dtype.numpy_dtype), valid))
            return tuple(outs)

        fin = _cached_program(
            f"agg-fin|{self.mode}|" + self._fingerprint(),
            lambda: program("agg_ungrouped_finalize", _fin))
        res = fin(tuple(acc))

        cols: List = []
        fields = []
        oi = 0
        for (name, agg) in self.agg_exprs:
            if self.mode == "partial":
                for bi, (dt, _) in enumerate(agg.buffers()):
                    bd, bv = res[oi]
                    oi += 1
                    fields.append(Field(f"{name}#buf{bi}", dt, True))
                    cols.append(DeviceColumn(dt, bd, bv))
            elif getattr(agg, "host_finalize", False):
                import pyarrow as pa
                nb = len(agg.buffers())
                bufs = res[oi: oi + nb]
                oi += nb
                arr = agg.finalize_host(list(bufs), 1,
                                        getattr(self, "_ansi", False))
                if len(arr) < cap:
                    arr = pa.concat_arrays(
                        [arr, pa.nulls(cap - len(arr), type=arr.type)])
                fields.append(Field(name, agg.dtype, agg.nullable))
                cols.append(HostStringColumn(arr))
            else:
                data, valid = res[oi]
                oi += 1
                fields.append(Field(name, agg.dtype, agg.nullable))
                cols.append(DeviceColumn(agg.dtype, data, valid))
        return ColumnBatch(Schema(fields), cols, 1)

    # -- dense direct-address grouping --------------------------------------------
    #
    # The group-by sibling of the dense join kernel: a single int/date
    # group key with a bounded domain aggregates by SCATTER into
    # domain-sized accumulators (acc.at[key - kmin].add/min/max), with no
    # sort at all.  TPC-H q10/q17/q18/q21's high-cardinality
    # aggregations are the measured victims of the sort path.  What the
    # scatter costs on one v5e (PERF.md section 6, PR 27's grid): 70.3 ns
    # a SOURCE row for int64/float64 and 5.2-6.4 for 32-bit and 8-bit
    # arrays, live or not, against 16.0 / 8.2 ns an element for a gather
    # and 0.32 ns a row for a cumulative sum.  So the update programs
    # count their in-domain rows and, where few of a batch's padded rows
    # are bound for the tables (q3: 10,000 of 2,097,152 behind its
    # joins), compact them on the device and scatter those alone
    # (ops/dense_agg.update_tables, by the rule
    # batch_utils.scatter_rung).  Out-of-domain and NULL-key rows divert
    # to the generic sort path and merge at the end (usually empty).

    @staticmethod
    def _count_dense_batches(m, batches: int, compacted: int) -> None:
        """How many batches the dense update programs took, and how many
        of them scattered a compacted rung: read in the tail fetch."""
        m.add("aggDenseBatches", batches)
        m.add("aggDenseCompactedBatches", compacted)
        stats = QueryStats.get()
        stats.agg_dense_batches += batches
        stats.agg_dense_compacted_batches += compacted

    def _dense_agg_static_ok(self, ops, conf) -> bool:
        if self.mode != "complete" or len(self.group_exprs) != 1:
            return False
        if not conf["spark.rapids.tpu.sql.agg.dense.enabled"]:
            return False
        if not conf["spark.rapids.tpu.join.denseDomainCap"]:
            return False
        if any(op not in ("sum", "min", "max") for op in ops):
            return False
        if any(getattr(agg, "host_finalize", False)
               for _, agg in self.agg_exprs):
            return False
        from .planner import strip_alias
        key = strip_alias(self.group_exprs[0][1])
        if not isinstance(key, BoundReference) or key.dtype is None:
            return False
        if key.dtype.is_string or key.dtype.is_host_carried:
            return False  # dictionary codes are per-batch, not a domain
        try:
            return np.dtype(key.dtype.numpy_dtype).kind in "iu"
        except TypeError:
            return False

    def _try_dense_grouped(self, ctx, m, first: ColumnBatch, rest,
                           ops, update, buffer_schema, sort_part_fn):
        """Return an output iterator, or None when the first batch's key
        stats reject the dense path (caller falls back, re-chaining
        ``first``)."""
        from .planner import strip_alias
        key = strip_alias(self.group_exprs[0][1])
        fp = "agg-dense|" + self._fingerprint()

        def build_stats():
            @program("agg_dense_stats")
            def f(arrays, sel, num_rows):
                cap = next(a[0].shape[0] for a in arrays
                           if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                d, v = key.eval(EvalContext(arrays, cap, active=active))
                ok = active if v is None else (active & v)
                d64 = d.astype(jnp.int64)
                big = jnp.int64(np.iinfo(np.int64).max)
                kmin = jnp.min(jnp.where(ok, d64, big))
                kmax = jnp.max(jnp.where(ok, d64, -big))
                return jnp.stack([kmin, kmax,
                                  jnp.sum(ok.astype(jnp.int64))])
            return f

        def arrays_of(b):
            return tuple((c.data, c.valid) if isinstance(c, DeviceColumn)
                         else None for c in b.columns)

        sfn = _cached_program(fp + "|stats", build_stats)
        # region-batched when fused: rides the region's prologue fetch
        # alongside any join build stats staged during this same pull
        kmin, kmax, n_valid = region_scalars(
            sfn(arrays_of(first), first.sel, np.int32(first.num_rows)))
        if n_valid == 0:
            return None
        domain = kmax - kmin + 1
        from ..batch import bucket_capacity
        cap_conf = ctx.conf["spark.rapids.tpu.join.denseDomainCap"]
        if domain <= 0 or domain > cap_conf:
            return None
        D = bucket_capacity(domain)
        n_bufs = len(ops)

        def _init_acc():
            return [dense_agg.empty_table(op, D, f.dtype.numpy_dtype)
                    for f, op in zip(buffer_schema.fields[1:], ops)]

        def build_update():
            @program("agg_dense_update")
            def f(arrays, sel, num_rows, accs, present, kmin_s,
                  n_compacted):
                cap = next(a[0].shape[0] for a in arrays
                           if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                ectx = EvalContext(arrays, cap, active=active)
                kd, kv = key.eval(ectx)
                ok = active if kv is None else (active & kv)
                idx = kd.astype(jnp.int64) - kmin_s
                in_dom = ok & (idx >= 0) & (idx < D)
                sidx = jnp.where(in_dom, idx, jnp.int64(D))
                new_accs, _, present, took = dense_agg.update_tables(
                    sidx, in_dom, update(ectx), (), accs, ops, (),
                    present)
                # rows the dense table cannot hold (null key / outside
                # the first batch's domain) divert to the generic path
                leftover = active & ~in_dom
                return new_accs, present, leftover, n_compacted + took
            return f

        ufn = _cached_program(fp + f"|update|{D}", build_update)

        # the domain [kmin, kmax] comes FROM the first batch, so its
        # valid keys are in-domain by construction: when the first
        # batch's key column carries no validity mask it PROVABLY
        # leaves no leftovers, and (in the common single-batch stream)
        # the leftover flush costs zero round trips
        kcol = first.columns[key.ordinal]
        key_nonnull = (isinstance(kcol, DeviceColumn)
                       and kcol.valid is None)

        def run():
            import itertools
            accs = _init_acc()
            present = jnp.zeros((D,), dtype=jnp.int8)
            kmin_s = jnp.int64(kmin)
            # [(sel-masked view, count scalar)]: the count's D2H copy is
            # prestaged at append time, so the flush/tail fetch finds the
            # bytes already en route instead of stalling the loop
            leftovers = []
            left_parts = []

            def flush_leftovers():
                if not leftovers:
                    return
                # ONE batched fetch resolves which batches diverted rows
                counts = fetch([c for _, c in leftovers])  # fusion-ok (bounded-pin drain: data-dependent mid-stream, already batched across all leftovers)
                for (b, _), cnt in zip(leftovers, counts):
                    if int(cnt):
                        left_parts.append(sort_part_fn(
                            batch_utils.compact(b)))
                leftovers.clear()

            first_batch = True
            n_batches = 0
            # batches that scattered a compacted rung: counted on the
            # device, read in the tail fetch
            n_compacted = np.int32(0)
            for batch in itertools.chain([first], rest):
                if batch.num_rows == 0:
                    continue
                with m.time("opTime"):
                    accs_t, present, leftover, n_compacted = ufn(
                        arrays_of(batch), batch.sel,
                        np.int32(batch.num_rows), tuple(accs), present,
                        kmin_s, n_compacted)
                    accs = list(accs_t)
                n_batches += 1
                if not (first_batch and key_nonnull):
                    leftovers.append((
                        ColumnBatch(batch.schema, batch.columns,
                                    batch.num_rows, leftover),
                        prestage(jnp.sum(leftover.astype(jnp.int32)))))
                first_batch = False
                if len(leftovers) >= 8:  # bound pinned input batches
                    flush_leftovers()
            m.add("aggDensePath", 1)
            key_f = buffer_schema.fields[0]
            key_col = (kmin + jnp.arange(D, dtype=jnp.int64)).astype(
                key_f.dtype.numpy_dtype)
            pending = self._to_buffer_batch(
                buffer_schema, [(key_col, None)],
                [(a, None) for a in accs], present > 0)
            # one tail fetch: leftover counts + group count + compacted
            # batches together — n_groups then sizes a sync-free output
            # compaction, so a sparse domain (D >> groups) doesn't
            # inflate every downstream operator to D capacity
            n_groups_dev = jnp.sum((present > 0).astype(jnp.int64))
            left_counts, n_groups, n_compacted = fetch(  # fusion-ok (end-of-stream tail: one batched fetch by construction)
                ([c for _, c in leftovers], n_groups_dev, n_compacted))
            self._count_dense_batches(m, n_batches, int(n_compacted))
            for (b, _), cnt in zip(leftovers, left_counts):
                if int(cnt):
                    left_parts.append(sort_part_fn(
                        batch_utils.compact(b)))
            leftovers.clear()
            n_groups = int(n_groups)
            from ..batch import bucket_capacity as _bcap
            if _bcap(max(n_groups, 1)) < D:
                pending = batch_utils.compact(pending, n_live=n_groups)
            for part in left_parts:
                pending = self._merge_partials(
                    [pending, batch_utils.compact_packed(part)], ops, 1)
            out = self._finalize_grouped(pending)
            if left_parts:
                m.add("numOutputRows", out.row_count())
            else:
                m.add("numOutputRows", n_groups)
            yield out

        return run()

    # -- dense multi-key grouping (primary key + residual keys) -------------------
    #
    # TPC-H/DS aggregates routinely group by (bounded int key, attributes
    # functionally dependent on it): q3 (l_orderkey, o_orderdate,
    # o_shippriority), q10 (c_custkey, c_name, ...), q18 (o_orderkey,
    # c_name, ...).  The sort path pays a multi-operand device sort per
    # batch plus concat-merge passes; here the PRIMARY key scatters into
    # a domain-sized table exactly like the single-key dense path, and
    # every RESIDUAL key keeps scatter-min/scatter-max channels whose
    # equality PROVES per-slot functional dependence.  Any violated slot
    # flips one device flag, checked once at stream end — on violation
    # (or domain rejection) the buffered input replays through the sort
    # path, so the rewrite is sound without planner-level constraints.

    def _dense_residual_static_ok(self, ops, conf) -> bool:
        if self.mode != "complete" or len(self.group_exprs) < 2:
            return False
        if not conf["spark.rapids.tpu.sql.agg.dense.enabled"]:
            return False
        if not conf["spark.rapids.tpu.join.denseDomainCap"]:
            return False
        if any(op not in ("sum", "min", "max") for op in ops):
            return False
        if any(getattr(agg, "host_finalize", False)
               for _, agg in self.agg_exprs):
            return False
        from .planner import strip_alias
        has_int = False
        for _n, e in self.group_exprs:
            core = strip_alias(e)
            if not isinstance(core, BoundReference) or core.dtype is None:
                return False
            dt = core.dtype
            if dt.is_string:
                continue  # encoded to int32 codes before the kernel
            if getattr(dt, "is_host_carried", False) or dt.is_nested:
                return False
            try:
                kind = np.dtype(dt.numpy_dtype).kind
            except TypeError:
                return False
            if kind not in "iufb":
                return False
            if kind in "iu":
                has_int = True
        return has_int

    def _try_dense_grouped_multi(self, ctx, m, first, rest, ops,
                                 update, buffer_schema, sort_part_fn):
        """Multi-key dense aggregation; None rejects to the sort path."""
        import itertools

        from .planner import strip_alias
        keys = [strip_alias(e) for _n, e in self.group_exprs]
        n_keys = len(keys)
        fp = "agg-mdense|" + self._fingerprint()
        first = self._encode_string_keys(first, ctx)
        # candidate primaries: int-typed keys (stats for all in ONE fetch)
        cand = [i for i, k in enumerate(keys)
                if not k.dtype.is_string
                and np.dtype(k.dtype.numpy_dtype).kind in "iu"]

        def build_stats():
            from ..ops.hashing import xxhash64_columns

            @program("agg_mdense_stats")
            def f(arrays, sel, num_rows):
                cap = next(a[0].shape[0] for a in arrays
                           if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                ectx = EvalContext(arrays, cap, active=active)
                outs = []
                big = jnp.int64(np.iinfo(np.int64).max)
                kvs = []
                for i in cand:
                    d, v = keys[i].eval(ectx)
                    kvs.append((i, d, v))
                    ok = active if v is None else (active & v)
                    d64 = d.astype(jnp.int64)
                    outs.append(jnp.stack([
                        jnp.min(jnp.where(ok, d64, big)),
                        jnp.max(jnp.where(ok, d64, -big)),
                        jnp.sum(ok.astype(jnp.int64))]))
                # sampled functional-dependence probe: distinct(all keys)
                # vs distinct(each primary candidate) over a prefix — if
                # the full key is strictly finer than the candidate, the
                # residuals are NOT dependent and the dense path would
                # only violate + replay (q21's DISTINCT was the victim)
                scap = min(cap, 1 << 18)
                s_active = active[:scap]

                def _nd(h):
                    sh = jnp.sort(jnp.where(s_active, h.astype(jnp.int64),
                                            big))
                    first = jnp.concatenate(
                        [jnp.ones((1,), bool), sh[1:] != sh[:-1]])
                    return jnp.sum((first & (sh != big)).astype(jnp.int64))

                # 64-bit hashes: at 2^18-row samples a 32-bit hash
                # loses a coin-flip's worth of distincts to collisions,
                # which would spuriously reject dependent keys
                all_kv = [e.eval(ectx) for e in keys]
                h_all = xxhash64_columns(
                    [(d[:scap], None if v is None else v[:scap])
                     for d, v in all_kv])
                nd = [_nd(h_all)]
                for i, d, v in kvs:
                    h_c = xxhash64_columns(
                        [(d[:scap], None if v is None else v[:scap])])
                    nd.append(_nd(h_c))
                return jnp.stack(outs), jnp.stack(nd)
            return f

        def arrays_of(b):
            return tuple((c.data, c.valid) if isinstance(c, DeviceColumn)
                         else None for c in b.columns)

        if any(not isinstance(first.columns[k.ordinal], DeviceColumn)
               for k in keys):
            # un-encodable key column (string keys became device codes
            # above, so this is a host-carried nested/decimal): sort path
            return None
        sfn = _cached_program(fp + "|stats", build_stats)
        stats, nd = region_fetch(sfn(arrays_of(first), first.sel,
                                     np.int32(first.num_rows)))
        nd_all = int(nd[0])
        nd_by_cand = {i: int(nd[1 + k]) for k, i in enumerate(cand)}
        cap_conf = ctx.conf["spark.rapids.tpu.join.denseDomainCap"]
        best = None  # (domain, cand_idx, kmin)
        for row, i in zip(np.asarray(stats), cand):
            kmin, kmax, n_valid = [int(x) for x in row]
            if n_valid == 0:
                continue
            domain = kmax - kmin + 1
            if domain <= 0 or domain > cap_conf:
                continue
            if nd_all > nd_by_cand[i]:
                # sampled full-key cardinality strictly exceeds this
                # candidate's: residuals not functionally dependent
                continue
            if best is None or domain < best[0]:
                best = (domain, i, kmin)
        if best is None:
            return None
        domain, pidx, kmin = best
        primary = keys[pidx]
        residual_idx = [i for i in range(n_keys) if i != pidx]
        from ..batch import bucket_capacity
        D = bucket_capacity(domain)
        n_bufs = len(ops)
        # HBM guardrail: accumulators are D * (residual channels + bufs)
        est = D * (len(residual_idx) * (16 + 2) + 2 + 8 * n_bufs)
        if est > ctx.conf["spark.rapids.tpu.sql.agg.dense.maxAccumBytes"]:
            return None

        def _res_np_dtype(k):
            if k.dtype.is_string:
                return np.dtype(np.int32)  # dictionary codes
            return np.dtype(k.dtype.numpy_dtype)

        def _init_acc():
            return [dense_agg.empty_table(op, D, f.dtype.numpy_dtype)
                    for f, op in zip(buffer_schema.fields[n_keys:], ops)]

        def _init_res():
            res = []
            for i in residual_idx:
                np_dt = _res_np_dtype(keys[i])
                res.append((
                    dense_agg.empty_table("min", D, np_dt),  # vmin
                    dense_agg.empty_table("max", D, np_dt),  # vmax
                    jnp.ones((D,), dtype=jnp.int8),          # validmin
                    jnp.zeros((D,), dtype=jnp.int8)))        # validmax
            return res

        def build_update():
            @program("agg_mdense_update")
            def f(arrays, sel, num_rows, accs, res, present, kmin_s,
                  n_compacted):
                cap = next(a[0].shape[0] for a in arrays
                           if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                ectx = EvalContext(arrays, cap, active=active)
                kd, kv = primary.eval(ectx)
                ok = active if kv is None else (active & kv)
                idx = kd.astype(jnp.int64) - kmin_s
                in_dom = ok & (idx >= 0) & (idx < D)
                sidx = jnp.where(in_dom, idx, jnp.int64(D))
                new_accs, new_res, present, took = dense_agg.update_tables(
                    sidx, in_dom, update(ectx),
                    [keys[ri].eval(ectx) for ri in residual_idx],
                    accs, ops, res, present)
                leftover = active & ~in_dom
                return (new_accs, new_res, present, leftover,
                        n_compacted + took)
            return f

        ufn = _cached_program(fp + f"|update|{pidx}|{D}", build_update)

        def build_violation():
            @program("agg_mdense_violation")
            def f(res, present):
                viol = jnp.zeros((), dtype=bool)
                for (vmin, vmax, dmn, dmx) in res:
                    has_val = dmx == 1
                    mixed = has_val & (dmn == 0)
                    # NaN residuals: vmin/vmax comparisons are unreliable
                    # -> treat any NaN as a violation (sort fallback)
                    if np.dtype(vmin.dtype).kind == "f":
                        bad = has_val & (~(vmin == vmax) | jnp.isnan(vmin)
                                         | jnp.isnan(vmax))
                    else:
                        bad = has_val & (vmin != vmax)
                    viol = viol | jnp.any(present.astype(bool)
                                          & (bad | mixed))
                return viol
            return f

        vfn = _cached_program(fp + f"|viol|{pidx}|{D}", build_violation)

        kcol = first.columns[primary.ordinal]
        key_nonnull = (isinstance(kcol, DeviceColumn)
                       and kcol.valid is None)

        def run():
            from ..memory.spill import get_catalog
            catalog = get_catalog(ctx.conf)
            accs = _init_acc()
            res = _init_res()
            present = jnp.zeros((D,), dtype=jnp.int8)
            kmin_s = jnp.int64(kmin)
            leftovers = []
            left_parts = []
            # replay buffer for the violation fallback: SPILLABLE handles
            # (priority 1) so a long stream doesn't pin its whole input
            # in HBM next to the D-sized accumulators
            buffered = []
            first_batch = True
            n_batches = 0
            # batches that scattered a compacted rung: counted on the
            # device, read in the tail fetch
            n_compacted = np.int32(0)

            def flush_leftovers():
                if not leftovers:
                    return
                counts = fetch([c for _, c in leftovers])  # fusion-ok (bounded-pin drain: data-dependent mid-stream, already batched across all leftovers)
                for (b, _), cnt in zip(leftovers, counts):
                    if int(cnt):
                        left_parts.append(sort_part_fn(
                            batch_utils.compact(b)))
                leftovers.clear()

            for batch in itertools.chain([first], rest):
                if batch.num_rows == 0:
                    continue
                if not first_batch:
                    batch = self._encode_string_keys(batch, ctx)
                if any(not isinstance(batch.columns[k.ordinal],
                                      DeviceColumn) for k in keys):
                    # un-encodable key in a later batch: replay all
                    yield from self._sort_path_replay(
                        ctx, m,
                        [h.get() for h in buffered] + [batch], rest, ops,
                        sort_part_fn)
                    for h in buffered:
                        h.close()
                    return
                buffered.append(catalog.register(batch, priority=1))
                with m.time("opTime"):
                    accs_t, res_t, present, leftover, n_compacted = ufn(
                        arrays_of(batch), batch.sel,
                        np.int32(batch.num_rows), tuple(accs),
                        tuple(res), present, kmin_s, n_compacted)
                    accs = list(accs_t)
                    res = list(res_t)
                n_batches += 1
                if not (first_batch and key_nonnull):
                    # count prestaged: its D2H copy overlaps the next
                    # batch's dispatch instead of stalling the tail fetch
                    leftovers.append((
                        ColumnBatch(batch.schema, batch.columns,
                                    batch.num_rows, leftover),
                        prestage(jnp.sum(leftover.astype(jnp.int32)))))
                first_batch = False
                if len(leftovers) >= 8:
                    flush_leftovers()
            # ONE end-of-stream fetch: violation flag + per-batch
            # leftover counts + group count + compacted batches together
            n_groups_dev = jnp.sum((present > 0).astype(jnp.int64))
            tail = fetch((vfn(tuple(res), present),  # fusion-ok (end-of-stream tail: one batched fetch by construction)
                          [c for _, c in leftovers], n_groups_dev,
                          n_compacted))
            violated, left_counts, n_groups, n_compacted = tail
            self._count_dense_batches(m, n_batches, int(n_compacted))
            if bool(violated):
                m.add("aggDenseResidualFallback", 1)
                try:
                    yield from self._sort_path_replay(
                        ctx, m, (h.get() for h in buffered), None, ops,
                        sort_part_fn)
                finally:
                    for h in buffered:
                        h.close()
                return
            for h in buffered:
                h.close()
            buffered.clear()
            for (b, _), cnt in zip(leftovers, left_counts):
                if int(cnt):
                    left_parts.append(sort_part_fn(
                        batch_utils.compact(b)))
            leftovers.clear()
            m.add("aggDensePath", 1)
            # assemble the buffer batch: keys in original order
            key_cols = []
            for i in range(n_keys):
                f = buffer_schema.fields[i]
                if i == pidx:
                    prim = (kmin + jnp.arange(D, dtype=jnp.int64))
                    if f.dtype.is_string:
                        key_cols.append((prim.astype(jnp.int32), None))
                    else:
                        key_cols.append((
                            prim.astype(f.dtype.numpy_dtype), None))
                else:
                    ri = residual_idx.index(i)
                    vmin, vmax, dmn, dmx = res[ri]
                    key_cols.append((vmin, dmx == 1))
            pending = self._to_buffer_batch(
                buffer_schema, key_cols,
                [(a, None) for a in accs], present > 0)
            n_groups = int(n_groups)
            from ..batch import bucket_capacity as _bcap
            if _bcap(max(n_groups, 1)) < D:
                # sync-free (count already fetched): don't let a sparse
                # domain inflate downstream operators to D capacity
                pending = batch_utils.compact(pending, n_live=n_groups)
            for part in left_parts:
                pending = self._merge_partials(
                    [pending, batch_utils.compact_packed(part)], ops,
                    n_keys)
            out = self._finalize_grouped(pending)
            if left_parts:
                m.add("numOutputRows", out.row_count())
            else:
                m.add("numOutputRows", int(n_groups))
            yield out

        return run()

    def _sort_path_replay(self, ctx, m, buffered, rest, ops, sort_part_fn):
        """Violation/ineligibility fallback: run the buffered (and any
        remaining) batches through the generic sort path."""
        import itertools
        held = _HeldPartials(self, ops, len(self.group_exprs))
        stream = buffered if rest is None else itertools.chain(
            buffered, rest)
        for batch in stream:
            if batch.num_rows == 0:
                continue
            batch = self._encode_string_keys(batch, ctx)
            with m.time("opTime"):
                held.add(sort_part_fn(batch))
        with m.time("opTime"):
            pending = held.take()
        if pending is None:
            yield ColumnBatch(self._schema, self._empty_cols(), 0)
            return
        out = self._finalize_grouped(pending)
        m.add("numOutputRows", out.num_rows)
        yield out

    # -- grouped ------------------------------------------------------------------
    def _execute_grouped(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        child = self.children[0]
        m = ctx.metric_set(self.op_id)
        ops = self._buffer_ops()
        n_keys = len(self.group_exprs)

        slf = self._detached()
        if self.mode == "final":
            update = slf._final_mode_update
            key_eval = slf._final_mode_keys
        else:
            update = slf._update_contributions
            key_eval = slf._key_contributions

        def build():
            @program("agg_grouped")
            def batch_group(arrays, sel, num_rows):
                cap = next(a[0].shape[0] for a in arrays
                           if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                ectx = EvalContext(arrays, cap, active=active)
                keys = key_eval(ectx)
                contribs = update(ectx)
                out_keys, out_vals, n_groups, gmask = groupby.group_reduce(
                    keys, [(cv, op) for cv, op in zip(contribs, ops)], active)
                return out_keys, out_vals, gmask
            return batch_group

        sort_batch_group = _cached_program(
            "agg-grouped|" + self._fingerprint(), build)

        grid_ok = (
            len(self._string_key_refs()) == len(self.group_exprs)
            and len(self.group_exprs) > 0
            and all(op in ("sum", "first", "last") for op in ops))
        grid_max = ctx.conf["spark.rapids.tpu.sql.agg.gridMaxGroups"]

        def _grid_bound():
            """Static live-row bound of a grid-path output (None = sort
            path, unbounded): enables sync-free bounded compaction."""
            dims = _grid_dims()
            if dims is None:
                return None
            g = 1
            for d in dims:
                g *= (d + 1)
            return g

        def _grid_dims():
            """Bucketed dictionary sizes, or None when the grid would be
            too large / dictionaries unavailable."""
            if not grid_ok:
                return None
            dims = []
            G = 1
            for gi, _ in self._string_key_refs():
                d = self.string_dicts.get(gi) if self.string_dicts \
                    else None
                if d is None or len(d) == 0:
                    return None
                b = 1
                while b < len(d):
                    b <<= 1
                dims.append(b)
                G *= (b + 1)
            if G > grid_max:
                return None
            return tuple(dims)

        def _grid_program(dims):
            def build_grid():
                @program("agg_grid")
                def f(arrays, sel, num_rows):
                    cap = next(a[0].shape[0] for a in arrays
                               if a is not None)
                    active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                    if sel is not None:
                        active = active & sel
                    ectx = EvalContext(arrays, cap, active=active)
                    keys = key_eval(ectx)
                    contribs = update(ectx)
                    ok, ov, n_g, gmask = groupby.grid_group_reduce(
                        keys, list(dims),
                        [(cv, op) for cv, op in zip(contribs, ops)],
                        active)
                    return ok, ov, gmask
                return f
            return _cached_program(
                f"agg-grid|{dims}|" + self._fingerprint(), build_grid)

        def batch_group(arrays, sel, num_rows):
            # dense-grid fast path for dictionary-coded keys (no sort, no
            # permutation gathers — see grid_group_reduce); dims re-read
            # per batch because dictionaries grow incrementally
            dims = _grid_dims()
            if dims is not None:
                return _grid_program(dims)(arrays, sel, num_rows)
            return sort_batch_group(arrays, sel, num_rows)

        buffer_schema = self._buffer_schema()
        if self.mode == "final" and child.outputs_partitions:
            # a shuffle guarantees each group is confined to one partition
            # batch: finalize per batch, no cross-batch merge (streaming)
            from ..runtime.pipeline import (effective_depth,
                                            pipeline_batches)
            any_out = False
            for batch in pipeline_batches(child.execute(ctx),
                                          effective_depth(ctx),
                                          label=self.op_id):
                with m.time("opTime"):
                    batch = self._encode_string_keys(batch, ctx)
                    arrays = tuple(
                        (c.data, c.valid) if isinstance(c, DeviceColumn)
                        else None for c in batch.columns)
                    ok, ov, gmask = batch_group(arrays, batch.sel,
                                                np.int32(batch.num_rows))
                    # group_reduce packs live groups at the front: a
                    # slice-compact avoids a full sort+gather pass, and a
                    # grid bound makes it sync-free entirely
                    part = batch_utils.compact_packed(
                        self._to_buffer_batch(buffer_schema, ok, ov, gmask),
                        bound=_grid_bound())
                if part.num_rows == 0:
                    continue
                out = self._finalize_grouped(part)
                any_out = True
                m.add("numOutputRows", out.num_rows)
                yield out
            if not any_out:
                yield ColumnBatch(self._schema, self._empty_cols(), 0)
            return
        from ..memory.retry import with_retry
        from ..runtime.pipeline import effective_depth, pipeline_batches

        def run_one(b: ColumnBatch) -> ColumnBatch:
            arrays = tuple((c.data, c.valid) if isinstance(c, DeviceColumn)
                           else None for c in b.columns)
            ok, ov, gmask = batch_group(arrays, b.sel, np.int32(b.num_rows))
            return self._to_buffer_batch(buffer_schema, ok, ov, gmask)

        # pull the child ahead: upstream host work overlaps the per-batch
        # group/scatter programs (the dense paths' `rest` stream included)
        child_batches = pipeline_batches(child.execute(ctx),
                                         effective_depth(ctx),
                                         label=self.op_id)
        if self._dense_agg_static_ok(ops, ctx.conf):
            peek = next(child_batches, None)
            if peek is None:
                yield ColumnBatch(self._schema, self._empty_cols(), 0)
                return
            dense = self._try_dense_grouped(ctx, m, peek, child_batches,
                                            ops, update, buffer_schema,
                                            run_one)
            if dense is not None:
                yield from dense
                return
            import itertools
            child_batches = itertools.chain([peek], child_batches)
        elif self._dense_residual_static_ok(ops, ctx.conf):
            peek = next(child_batches, None)
            if peek is None:
                yield ColumnBatch(self._schema, self._empty_cols(), 0)
                return
            dense = self._try_dense_grouped_multi(
                ctx, m, peek, child_batches, ops, update, buffer_schema,
                run_one)
            if dense is not None:
                yield from dense
                return
            import itertools
            child_batches = itertools.chain([peek], child_batches)

        # Adaptive skip of partial aggregation for high-cardinality keys
        # (GpuHashAggregateExec skipAggPassReductionRatio analog): a hash
        # sample of the first batch estimates the reduction ratio with a
        # cheap-to-compile elementwise program; when grouping barely
        # shrinks the data, every batch streams keys + per-row buffer
        # contributions to the exchange unreduced — the expensive sort
        # program never even compiles.
        skip_ratio = ctx.conf["spark.rapids.tpu.sql.agg.skipPartialAggRatio"]
        decide = self.mode == "partial" and skip_ratio < 1.0
        pass_through = False
        first = True

        def build_pt():
            @program("agg_passthrough")
            def f(arrays, sel, num_rows):
                cap = next(a[0].shape[0] for a in arrays if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                ectx = EvalContext(arrays, cap, active=active)
                keys = key_eval(ectx)
                contribs = update(ectx)
                return tuple(keys), tuple(contribs), active
            return f

        # Re-partition fallback (GpuMergeAggregateIterator,
        # aggregate.scala:711): when the merged pending output outgrows
        # the batch budget, a partial agg simply EMITS it (the exchange +
        # final agg combine duplicates), while a final/complete agg
        # hash-splits every merged/merging batch into disjoint key
        # buckets and finalizes per bucket — bounded peak batch size
        # with correctness preserved (a key lives in exactly one bucket).
        # The trigger is BYTE-denominated over the BUFFER's physical
        # layout (string keys ride as int32 dictionary codes): a narrow
        # distinct can pend 10x more rows than a wide aggregation in the
        # same memory, and tripping the fallback needlessly costs
        # per-bucket merge passes (TPC-H Q21's 5.8M-group dedups were
        # the measured victim); a wide buffer conversely trips EARLIER
        # than the row cap would.
        width = 0
        for f_ in buffer_schema:
            if f_.dtype.is_string:
                width += 4  # int32 dictionary codes in buffer batches
            elif getattr(f_.dtype, "is_host_carried", False):
                width += 64
            else:
                width += np.dtype(f_.dtype.numpy_dtype).itemsize
        limit = max(1, ctx.conf["spark.rapids.tpu.sql.batchSizeBytes"]
                    // max(width, 1))
        buckets = None
        bucket_over = None  # single OR-accumulated device overflow flag
        held = _HeldPartials(self, ops, n_keys, limit)
        for batch in child_batches:
            out_now: List[ColumnBatch] = []
            with m.time("opTime"):
                batch = self._encode_string_keys(batch, ctx)
                if decide and first:
                    first = False
                    ratio = self._sample_group_ratio(batch, key_eval)
                    pass_through = ratio > skip_ratio
                    if pass_through:
                        m.add("skippedPartialAgg", 1)
                if pass_through:
                    pt = _cached_program(
                        "agg-pt|" + self._fingerprint(), build_pt)
                    arrays = tuple(
                        (c.data, c.valid) if isinstance(c, DeviceColumn)
                        else None for c in batch.columns)
                    ks, cs, active = pt(arrays, batch.sel,
                                        np.int32(batch.num_rows))
                    out_now.append(self._to_buffer_batch(
                        buffer_schema, list(ks), list(cs), active))
                else:
                    for part in with_retry(ctx, batch, run_one):
                        gb = _grid_bound()
                        if buckets is not None:
                            pieces = self._split_by_key_hash(
                                part, n_keys, len(buckets))
                            for bi, piece in enumerate(pieces):
                                buckets[bi], flag = self._merge_bucket(
                                    buckets[bi], piece, ops, n_keys, limit)
                                bucket_over = flag if bucket_over is None \
                                    else (bucket_over | flag)
                            continue
                        held.add(part, bound=gb)
                        if gb is None and held.rows > limit:
                            # add() merged before its rows could pass
                            # the limit: the count is the merged one
                            pending = held.take()
                            if self.mode == "partial":
                                out_now.append(pending)
                            else:
                                nb = ctx.conf[
                                    "spark.rapids.tpu.sql.agg"
                                    ".repartitionBuckets"]
                                buckets = self._split_by_key_hash(
                                    pending, n_keys, nb)
                                m.add("aggRepartitions", 1)
            for ob in out_now:
                m.add("numOutputRows", ob.num_rows)
                yield ob
        if pass_through:
            return
        if buckets is not None:
            if bucket_over is not None and bool(bucket_over):
                raise RuntimeError(
                    "aggregate re-partition bucket overflowed "
                    "spark.rapids.tpu.sql.batchSizeRows: raise the "
                    "conf (extreme key skew across hash buckets)")
            any_rows = False
            for bp in buckets:
                # full compact: a bucket that never merged is a pid-masked
                # view whose live rows are NOT front-packed
                bp = batch_utils.compact(bp)
                if bp.num_rows == 0:
                    continue
                any_rows = True
                out = self._finalize_grouped(bp) \
                    if self.mode != "partial" else bp
                m.add("numOutputRows", out.num_rows)
                yield out
            if not any_rows:
                yield ColumnBatch(self._schema, self._empty_cols(), 0)
            return
        with m.time("opTime"):
            pending = held.take()
        if pending is None:
            yield ColumnBatch(self._schema, self._empty_cols(), 0)
            return
        out = self._finalize_grouped(pending) if self.mode != "partial" else pending
        m.add("numOutputRows", out.num_rows)
        yield out

    def _sample_group_ratio(self, batch: ColumnBatch, key_eval) -> float:
        """distinct/live ratio of the group keys over a prefix sample, via
        one murmur3 hash pass + DEVICE-side sort/adjacent-distinct count
        (collisions negligible for a heuristic).  Fetches TWO scalars
        instead of shipping the 256k-element sample to the host."""
        from ..batch import bucket_capacity
        from ..ops.hashing import hash_columns
        srows = min(batch.num_rows, 1 << 18)
        scap = min(bucket_capacity(srows), batch.capacity)

        def build():
            @program("agg_sample")
            def f(arrays, sel, num_rows):
                cap = next(a[0].shape[0] for a in arrays if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                ectx = EvalContext(arrays, cap, active=active)
                keys = key_eval(ectx)
                h = hash_columns(keys).astype(jnp.int64)
                big = jnp.int64(np.iinfo(np.int64).max)
                s = jnp.sort(jnp.where(active, h, big))
                n_live = jnp.sum(active.astype(jnp.int64))
                first = jnp.concatenate(
                    [jnp.ones((1,), bool), s[1:] != s[:-1]])
                n_distinct = jnp.sum((first & (s != big)).astype(jnp.int64))
                return jnp.stack([n_distinct, n_live])
            return f

        fn = _cached_program("agg-sample|" + self._fingerprint(), build)
        arrays = tuple(
            (c.data[:scap],
             c.valid[:scap] if c.valid is not None else None)
            if isinstance(c, DeviceColumn) else None
            for c in batch.columns)
        sel = batch.sel[:scap] if batch.sel is not None else None
        n_distinct, n_live = region_scalars(
            fn(arrays, sel, np.int32(min(srows, scap))))
        if n_live == 0:
            return 0.0
        return float(n_distinct) / float(n_live)

    # -- string keys via dictionary codes (ops/strings.py) ------------------------
    def _string_key_refs(self):
        """[(group_index, child_ordinal)] of string-typed bare-column keys."""
        from .planner import strip_alias
        out = []
        for gi, (_n, e) in enumerate(self.group_exprs):
            core = strip_alias(e)
            if isinstance(core, BoundReference) and core.dtype is not None \
                    and core.dtype.is_string:
                out.append((gi, core.ordinal))
        return out

    def _encode_string_keys(self, batch: ColumnBatch, ctx) -> ColumnBatch:
        """Replace host string key columns with device int32 dictionary
        codes (incremental dictionary shared with the partner partial/final
        exec so codes stay comparable across the exchange; ops/strings.py).

        Encodings are cached ON the column object (immutable, and stable
        across query runs when the scan's decoded-file cache serves the
        same batch), and the query ADOPTS the first cached dictionary it
        sees — repeat queries over cached scans skip the O(rows) host
        encode and the device upload entirely (measured: Q1 @ SF1 warm
        partial-agg 4.5s -> sub-second)."""
        refs = self._string_key_refs()
        if not refs:
            return batch
        from ..batch import DictStringColumn, PageCodedStringColumn
        from ..ops.strings import StringDictionary
        cols = list(batch.columns)
        changed = False
        for gi, ordn in refs:
            col = cols[ordn]
            if not isinstance(col, HostStringColumn):
                continue  # already encoded (or device data)
            if isinstance(col, DictStringColumn):
                # join outputs carry device dictionary codes already: adopt
                # the dictionary (codes valid verbatim) — no host encode,
                # no decode, no upload
                d = self.string_dicts.get(gi)
                if d is None or getattr(d, "_arrow_src", None) \
                        is col.dictionary:
                    if d is None:
                        self.string_dicts[gi] = StringDictionary.from_arrow(
                            col.dictionary)
                    cols[ordn] = DeviceColumn(T.STRING, col.codes, col.valid)
                    changed = True
                    continue
                # incompatible existing dictionary: decode (lazy .array)
                # and fall through to the host re-encode below
            d = self.string_dicts.get(gi)
            cached = getattr(col, "_enc_cache", None)
            if d is None and cached is not None:
                # adopt the column's existing dictionary for this query
                d, jcodes, jvalid = cached
                self.string_dicts[gi] = d
            elif d is not None and cached is not None and cached[0] is d:
                _, jcodes, jvalid = cached
            else:
                if d is None:
                    d = StringDictionary()
                    self.string_dicts[gi] = d
                if isinstance(col, PageCodedStringColumn):
                    # the file's page codes: its dictionaries remapped,
                    # no row hashed, no string made
                    codes, valid = d.encode_page_codes(col)
                    QueryStats.get().page_coded_keys += 1
                else:
                    codes, valid = d.encode(col.array)
                jcodes, jvalid = upload((codes, valid), ctx.device)
                col._enc_cache = (d, jcodes, jvalid)
            cols[ordn] = DeviceColumn(T.STRING, jcodes, jvalid)
            changed = True
        if not changed:
            return batch
        return ColumnBatch(batch.schema, cols, batch.num_rows, batch.sel)

    def _decode_string_keys(self, out: ColumnBatch) -> ColumnBatch:
        """Re-type coded key columns as DictStringColumn at the output
        boundary: codes STAY on device, the dictionary snapshot rides
        along, and the decode fetch happens only if/when a downstream
        consumer touches .array (collect decodes inside its one batched
        fetch) — the r4 version paid a blocking fetch per agg here."""
        if not self.string_dicts or self.mode == "partial":
            return out
        from ..batch import DictStringColumn
        cols = list(out.columns)
        changed = False
        for gi, d in self.string_dicts.items():
            col = cols[gi]
            if not isinstance(col, DeviceColumn):
                continue
            cols[gi] = DictStringColumn(
                col.data.astype(jnp.int32), col.valid, d.to_arrow())
            changed = True
        if not changed:
            return out
        return ColumnBatch(out.schema, cols, out.num_rows, out.sel)

    def _key_contributions(self, ectx: EvalContext):
        return [e.eval(ectx) for _, e in self.group_exprs]

    def _final_mode_keys(self, ectx: EvalContext):
        return [ectx.arrays[i] for i in range(len(self.group_exprs))]

    def _buffer_schema(self) -> Schema:
        fields = [Field(n, e.dtype, e.nullable) for n, e in self.group_exprs]
        for name, agg in self.agg_exprs:
            for bi, (dt, op) in enumerate(agg.buffers()):
                fields.append(Field(f"{name}#buf{bi}", dt, True))
        return Schema(fields)

    def _to_buffer_batch(self, schema: Schema, out_keys, out_vals,
                         gmask) -> ColumnBatch:
        cols: List[DeviceColumn] = []
        for (d, v), f in zip(out_keys + out_vals, schema):
            if f.dtype.is_string:
                # dictionary codes: physical type is int32, logical STRING
                cols.append(DeviceColumn(f.dtype, d.astype(jnp.int32), v))
            else:
                cols.append(DeviceColumn(f.dtype, d.astype(f.dtype.numpy_dtype),
                                         v))
        cap = cols[0].capacity
        return ColumnBatch(schema, cols, cap, gmask)

    def _split_by_key_hash(self, batch: ColumnBatch, n_keys: int,
                           n_buckets: int):
        """Partition a buffer batch into disjoint key-hash buckets as
        sel-masked views (zero copies; the merges compact)."""
        fp = f"agg-bucket-pid|{n_keys}|{n_buckets}|" + self._fingerprint()

        def build():
            @program("agg_bucket_pid")
            def f(arrays, sel, num_rows):
                from ..ops.hashing import xxhash64_columns
                cap = next(a[0].shape[0] for a in arrays
                           if a is not None)
                active = jnp.arange(cap, dtype=jnp.int32) < num_rows
                if sel is not None:
                    active = active & sel
                h = xxhash64_columns(list(arrays[:n_keys]))
                return (h % jnp.uint64(n_buckets)).astype(jnp.int32), active
            return f

        fn = _cached_program(fp, build)
        arrays = tuple((c.data, c.valid) for c in batch.columns)
        pid, active = fn(arrays, batch.sel, np.int32(batch.num_rows))
        return [ColumnBatch(batch.schema, batch.columns, batch.num_rows,
                            active & (pid == b)) for b in range(n_buckets)]

    def _merge_bucket(self, a: ColumnBatch, piece: ColumnBatch, ops,
                      n_keys, limit: int):
        """Merge one hash bucket's pending with a piece; stays bounded at
        ``limit`` live rows (sync-free slice) and returns a device
        overflow flag, all flags checked ONCE at stream end."""
        merged = self._concat_merge([a, piece], ops, n_keys)
        gmask = merged.sel
        from ..batch import bucket_capacity
        cap = bucket_capacity(min(limit, merged.capacity))
        over = jnp.any(gmask[cap:]) if cap < merged.capacity \
            else jnp.zeros((), dtype=bool)
        return batch_utils.compact_packed(merged, bound=limit), over

    def _concat_merge(self, parts: List[ColumnBatch], ops,
                      n_keys) -> ColumnBatch:
        """ONE run of ``agg_merge_grouped`` over partial results laid end
        to end in the order given (the group sort is stable: first/last
        see rows in that order).  The result is at the concatenation's
        capacity, groups packed at the front under its mask."""
        both = batch_utils.concat_packed(parts)
        arrays = tuple((c.data, c.valid) for c in both.columns)
        merge = _merge_fn(tuple(ops), n_keys)
        ok, ov, gmask = merge(arrays, both.sel, np.int32(both.num_rows))
        stats = QueryStats.get()
        stats.agg_merges += 1
        stats.agg_merge_parts += len(parts)
        return self._to_buffer_batch(both.schema, list(ok), list(ov), gmask)

    def _merge_partials(self, parts: List[ColumnBatch], ops, n_keys,
                        bound=None) -> ColumnBatch:
        """Concat partial results and re-reduce (concat-merge loop).

        A part comes out of ``group_reduce`` at the INPUT batch's full
        capacity with live groups packed at the front: the caller has
        compacted it (``compact_packed``), or the concat+re-reduce runs
        over millions of dead rows per merge (measured: Q1 @ SF1 spent
        ~3s here)."""
        return batch_utils.compact_packed(
            self._concat_merge(parts, ops, n_keys), bound=bound)

    def _finalize_grouped(self, pending: ColumnBatch) -> ColumnBatch:
        n_keys = len(self.group_exprs)
        arrays = tuple((c.data, c.valid) for c in pending.columns)
        agg_exprs = self.agg_exprs  # don't capture self in the cached fn

        def build():
            @program("agg_finalize")
            def fin(arrays):
                outs = []
                i = n_keys
                for name, agg in agg_exprs:
                    nb = len(agg.buffers())
                    if getattr(agg, "host_finalize", False):
                        i += nb
                        continue  # finalized exactly on the host below
                    data, valid = agg.finalize(
                        [arrays[i + k] for k in range(nb)])
                    outs.append((data.astype(agg.dtype.numpy_dtype), valid))
                    i += nb
                return tuple(outs)
            return fin

        fin = _cached_program("agg-fin|" + self._fingerprint(), build)
        fin_vals = list(fin(arrays))
        cols: List = list(pending.columns[:n_keys])
        oi = 0
        bi = n_keys
        for name, agg in self.agg_exprs:
            nb = len(agg.buffers())
            if getattr(agg, "host_finalize", False):
                # wide-decimal (etc.) results: exact host reconstruction
                # from the device buffer limbs into an arrow column
                import pyarrow as pa
                n = pending.num_rows
                arr = agg.finalize_host(
                    [arrays[bi + k] for k in range(nb)], n,
                    getattr(self, "_ansi", False))
                if len(arr) < pending.capacity:
                    arr = pa.concat_arrays(
                        [arr, pa.nulls(pending.capacity - len(arr),
                                       type=arr.type)])
                cols.append(HostStringColumn(arr))
            else:
                d, v = fin_vals[oi]
                oi += 1
                cols.append(DeviceColumn(agg.dtype, d, v))
            bi += nb
        out = ColumnBatch(self._schema, cols, pending.num_rows, pending.sel)
        return self._decode_string_keys(out)

    def _empty_cols(self):
        cols = []
        from ..batch import bucket_capacity
        cap = bucket_capacity(0)
        for f in self._schema:
            if f.dtype.is_string:
                import pyarrow as pa
                cols.append(HostStringColumn(pa.nulls(cap, type=pa.string())))
            else:
                cols.append(DeviceColumn(
                    f.dtype, jnp.zeros((cap,), dtype=f.dtype.numpy_dtype),
                    jnp.zeros((cap,), dtype=bool)))
        return cols


@functools.lru_cache(maxsize=256)
def _merge_fn(ops: tuple, n_keys: int):
    """Cached jitted merge for the concat-merge aggregation loop."""

    @program("agg_merge_grouped")
    def merge(arrays, sel, num_rows):
        cap = next(a[0].shape[0] for a in arrays
                   if a is not None)
        active = jnp.arange(cap, dtype=jnp.int32) < num_rows
        if sel is not None:
            active = active & sel
        keys = [arrays[i] for i in range(n_keys)]
        vals = [(arrays[n_keys + i], op) for i, op in enumerate(ops)]
        ok, ov, n_groups, gmask = groupby.group_reduce(keys, vals, active)
        return tuple(ok), tuple(ov), gmask

    return merge


# A concat is one program per tuple of capacities: however long a stream
# and however few its groups, no merge takes more partials than this.
_MERGE_FAN_IN = 16


class _HeldPartials:
    """The partial results a sort-path aggregate holds between merges.

    Every concat-merge loop of the sort path keeps its partials here,
    each compacted to the rung over its own groups, and merges them all
    in ONE ``agg_merge_grouped`` (the last merge's result first, then the
    partials in arrival order) only when

    * the stream has ended (:meth:`take`);
    * the rows held are twice the largest piece held (the last merge's
      result, or a partial), so a merge at most doubles work already
      paid and the merges' total is linear in the rows that arrive (one
      merge a part re-reduces the first part's rows once for every part
      after it), AND the pieces fill an input batch's capacity in slots:
      under that a merge costs a program and two blocking fetches to
      free less memory than the batch in flight holds;
    * the rows held could pass ``limit``: the caller consults the limit
      on merged counts only, so the re-partition fallback and a partial
      aggregate's early emit fire where one merge a part fires them;
    * ``_MERGE_FAN_IN`` pieces are held.

    The rule reads what the host has anyway: each piece's ``num_rows``
    after its compact (under a grid ``bound`` the static slice's, capped
    by the bound: the compacts stay sync-free), capacities, ``limit``."""

    def __init__(self, agg: "AggregateExec", ops, n_keys: int,
                 limit: Optional[int] = None):
        self._agg = agg
        self._ops = ops
        self._n_keys = n_keys
        self._limit = limit
        self._bound = None
        self._parts: List[ColumnBatch] = []

    def _piece_rows(self) -> List[int]:
        """The most rows each piece can hold (a grid bound only grows)."""
        return [p.num_rows if self._bound is None
                else min(p.num_rows, self._bound) for p in self._parts]

    @property
    def rows(self) -> int:
        """Rows held: exact after a merge, else the most there can be."""
        return sum(self._piece_rows())

    def add(self, part: ColumnBatch, bound: Optional[int] = None) -> None:
        """Hold one ``group_reduce`` output; merge if the rule says so."""
        batch_slots = part.capacity
        part = batch_utils.compact_packed(part, bound=bound)
        if bound is None and part.num_rows == 0:
            return      # no group: nothing to hold, nothing to merge
        self._bound = bound
        self._parts.append(part)
        rows = self._piece_rows()
        if (bound is None and self._limit is not None
                and sum(rows) > self._limit) \
                or len(self._parts) >= _MERGE_FAN_IN \
                or (sum(rows) >= 2 * max(rows)
                    and sum(p.capacity for p in self._parts)
                    >= batch_slots):
            self._merge()

    def _merge(self) -> None:
        if len(self._parts) > 1:
            self._parts = [self._agg._merge_partials(
                self._parts, self._ops, self._n_keys, bound=self._bound)]

    def take(self) -> Optional[ColumnBatch]:
        """Everything held, merged into one batch (None if nothing is
        held); nothing is held afterwards."""
        self._merge()
        out = self._parts[0] if self._parts else None
        self._parts = []
        return out


# ---------------------------------------------------------------------------------
# Collect: device → host Arrow (GpuBringBackToHost + GpuColumnarToRowExec analog)
# ---------------------------------------------------------------------------------

class CollectExec(TpuExec):
    def __init__(self, child: TpuExec):
        super().__init__([child])

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def node_desc(self):
        return "TpuBringBackToHost"

    def collect_arrow(self, ctx: ExecContext):
        import pyarrow as pa
        from ..batch import to_arrow, to_arrow_async
        from ..runtime.pipeline import effective_depth
        from ..service import cancel
        depth = effective_depth(ctx)
        if depth <= 0:
            tables = []
            for b in self.children[0].execute(ctx):
                cancel.check()
                tables.append(to_arrow(b))
        else:
            # async D2H: batch N's fetch rides behind batch N+1's
            # dispatch; at most `depth` fetches (each pinning its device
            # batch) are outstanding, so peak HBM stays bounded
            from collections import deque
            pending: "deque" = deque()
            tables = []
            for b in self.children[0].execute(ctx):
                cancel.check()
                pending.append(to_arrow_async(b))
                while len(pending) > depth:
                    tables.append(pending.popleft()())
            tables.extend(f() for f in pending)
        if not tables:
            return None
        with tracing.span(None, "result:concat", "result"):
            return pa.concat_tables(tables)

    def execute(self, ctx: ExecContext) -> Iterator[ColumnBatch]:
        yield from self.children[0].execute(ctx)
