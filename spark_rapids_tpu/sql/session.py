"""Session: entry point, config holder, executor (SparkSession analog).

Plays the role of the reference's plugin bootstrap (Plugin.scala:276-388):
device discovery, config fixup, and the planning hook.  The `explain`
machinery mirrors the plugin's "could not run on TPU because ..." output
(GpuOverrides.scala:4530-4537).
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
from typing import Any, Dict, Iterable, Optional

from ..config import TpuConf
from ..plan import logical as L
from ..plan.physical import CollectExec, ExecContext
from .dataframe import DataFrame

__all__ = ["Session"]


class _QueryScope:
    """What one query's scope holds for whoever runs inside it."""

    __slots__ = ("conf", "stats", "trace", "ctxs", "tid")

    def __init__(self, conf, stats, trace):
        self.conf = conf
        self.stats = stats
        self.trace = trace
        self.ctxs = []  # ExecContexts of the executions the scope ran
        self.tid = threading.get_ident()


_SCOPE: "contextvars.ContextVar[Optional[_QueryScope]]" = \
    contextvars.ContextVar("srt_query_scope", default=None)


class _RuntimeConf:
    def __init__(self, session: "Session"):
        self._session = session

    def set(self, key: str, value) -> None:
        self._session._settings[key] = value

    def get(self, key: str):
        if key in self._session._settings:
            return self._session._settings[key]
        from ..config import ALL_ENTRIES
        return ALL_ENTRIES[key].default

    def unset(self, key: str) -> None:
        self._session._settings.pop(key, None)


class Session:
    """A query session bound to one device set."""

    _lock = threading.Lock()
    _active: Optional["Session"] = None

    def __init__(self, settings: Optional[Dict[str, Any]] = None, device=None):
        self._settings: Dict[str, Any] = dict(settings or {})
        self.conf = _RuntimeConf(self)
        if device is None:
            from ..runtime.device import DeviceManager
            device = DeviceManager.initialize(self._tpu_conf()).device
        self.device = device

    @classmethod
    def get_or_create(cls, settings: Optional[Dict[str, Any]] = None,
                      device=None) -> "Session":
        with cls._lock:
            if cls._active is None:
                cls._active = Session(settings, device)
            elif settings:
                cls._active._settings.update(settings)
            return cls._active

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            if cls._active is not None:
                sched = getattr(cls._active, "_scheduler", None)
                if sched is not None:
                    sched.close()
                    # a later submit() on a still-held reference lazily
                    # rebuilds instead of hitting a closed scheduler
                    cls._active._scheduler = None
            cls._active = None
        # the cross-query cache outlives queries, not sessions: a reset
        # closes its spill-registered handles so the next session's
        # leak/budget accounting starts clean
        from ..cache import clear_query_cache
        clear_query_cache()

    def query_cache(self):
        """The process-wide cross-query device cache (scan batches +
        broadcast builds), sized from this session's conf —
        ``sess.query_cache().snapshot()`` is the operator surface."""
        from ..cache import get_query_cache
        return get_query_cache(self._tpu_conf())

    def _tpu_conf(self) -> TpuConf:
        # a circuit-breaker canary worker (service/breaker.py) carries
        # sandbox overrides in its copied context: serial pipeline, cpu
        # degradation allowed — every conf read inside the probe sees
        # them, no other query does
        from ..service.breaker import sandbox_overrides
        sandbox = sandbox_overrides()
        if sandbox:
            merged = dict(self._settings)
            merged.update(sandbox)
            return TpuConf(merged)
        return TpuConf(self._settings)

    def _clamp_reader_rows(self, src):
        """spark.rapids.tpu.sql.reader.batchSizeBytes: soft byte cap on one
        scan batch, applied as a row clamp via the schema's estimated row
        width (the source's with_pushdown rebuilds inherit it)."""
        byte_cap = self._tpu_conf()[
            "spark.rapids.tpu.sql.reader.batchSizeBytes"]
        if byte_cap > 0:
            from ..batch import estimated_row_bytes
            width = estimated_row_bytes(src.schema())
            src.batch_rows = max(1, min(src.batch_rows, byte_cap // width))
        return src

    # -- data sources -------------------------------------------------------------
    def _replace_path(self, path):
        """Remote-storage path redirection (AlluxioUtils.scala:37-74
        analog): `spark.rapids.tpu.io.pathReplacementRules` is a comma
        list of `prefix=>replacement` pairs applied to every reader
        path — the reference rewrites s3://bucket/... to an
        alluxio://mount/... cache mount the same way."""
        rules = self._tpu_conf()[
            "spark.rapids.tpu.io.pathReplacementRules"]
        if not rules or not isinstance(path, str):
            return path
        for rule in rules.split(","):
            rule = rule.strip()
            if "=>" not in rule:
                continue
            pre, repl = rule.split("=>", 1)
            if path.startswith(pre):
                return repl + path[len(pre):]
        return path

    def read_parquet(self, path, columns=None) -> DataFrame:
        from ..io.parquet import ParquetSource
        path = self._replace_path(path)
        conf = self._tpu_conf()
        cache_bytes = (
            conf["spark.rapids.tpu.sql.fileCache.maxBytes"]
            if conf["spark.rapids.tpu.sql.fileCache.enabled"] else 0)
        src = ParquetSource(
            path, columns=columns,
            batch_rows=conf["spark.rapids.tpu.sql.batchSizeRows"],
            num_threads=conf[
                "spark.rapids.tpu.sql.multiThreadedRead.numThreads"],
            cache_bytes=cache_bytes,
            exact_filter=conf["spark.rapids.tpu.sql.scan.exactFilterPushdown"])
        src = self._clamp_reader_rows(src)
        node = L.LogicalScan(src.schema(), src, src.describe(), fmt="parquet")
        node.source = src
        return DataFrame(node, self)

    def _file_source_df(self, cls, path, columns=None, **options) -> DataFrame:
        path = self._replace_path(path)
        conf = self._tpu_conf()
        src = cls(path, columns=columns,
                  batch_rows=conf["spark.rapids.tpu.sql.batchSizeRows"],
                  num_threads=conf[
                      "spark.rapids.tpu.sql.multiThreadedRead.numThreads"],
                  **options)
        src = self._clamp_reader_rows(src)
        node = L.LogicalScan(src.schema(), src, src.describe(), fmt=src.fmt)
        node.source = src
        return DataFrame(node, self)

    def read_csv(self, path, schema=None, header: bool = True, sep: str = ","
                 ) -> DataFrame:
        from ..io.sources import CsvSource
        return self._file_source_df(CsvSource, path, schema=schema,
                                    header=header, sep=sep)

    def read_orc(self, path, columns=None) -> DataFrame:
        from ..io.sources import OrcSource
        return self._file_source_df(OrcSource, path, columns=columns)

    def read_json(self, path, schema=None) -> DataFrame:
        """Line-delimited JSON (Spark's default JSON source)."""
        from ..io.sources import JsonSource
        return self._file_source_df(JsonSource, path, schema=schema)

    def read_avro(self, path, columns=None) -> DataFrame:
        from ..io.avro import AvroSource
        return self._file_source_df(AvroSource, path, columns=columns)

    def read_hive_text(self, path, schema=None, sep: str = "\x01"
                       ) -> DataFrame:
        """Hive LazySimpleSerDe-style delimited text
        (GpuHiveTableScanExec / GpuHiveTextFileFormat analog)."""
        from ..io.sources import CsvSource

        class HiveTextSource(CsvSource):
            fmt = "hivetext"
            ext = ""

        return self._file_source_df(HiveTextSource, path, schema=schema,
                                    header=False, sep=sep)

    def read_iceberg(self, path, snapshot_id: Optional[int] = None
                     ) -> DataFrame:
        """Apache Iceberg table (metadata/manifest replay; pure-python
        Avro manifests — io/iceberg.py)."""
        from ..io.iceberg import read_iceberg
        conf = self._tpu_conf()
        src = read_iceberg(
            path, snapshot_id=snapshot_id,
            batch_rows=conf["spark.rapids.tpu.sql.batchSizeRows"],
            num_threads=conf[
                "spark.rapids.tpu.sql.multiThreadedRead.numThreads"])
        src = self._clamp_reader_rows(src)
        node = L.LogicalScan(src.schema(), src, src.describe(),
                             fmt="iceberg")
        node.source = src
        return DataFrame(node, self)

    def read_delta(self, path, version: Optional[int] = None) -> DataFrame:
        """Delta Lake table (log replay; ``version`` = time travel)."""
        from ..io.delta import read_delta
        conf = self._tpu_conf()
        cache_bytes = (
            conf["spark.rapids.tpu.sql.fileCache.maxBytes"]
            if conf["spark.rapids.tpu.sql.fileCache.enabled"] else 0)
        src = read_delta(
            path, version=version,
            batch_rows=conf["spark.rapids.tpu.sql.batchSizeRows"],
            num_threads=conf[
                "spark.rapids.tpu.sql.multiThreadedRead.numThreads"],
            cache_bytes=cache_bytes,
            exact_filter=conf["spark.rapids.tpu.sql.scan.exactFilterPushdown"])
        src = self._clamp_reader_rows(src)
        node = L.LogicalScan(src.schema(), src, src.describe(), fmt="delta")
        node.source = src
        return DataFrame(node, self)

    def create_dataframe(self, data, schema=None) -> DataFrame:
        """From a pandas DataFrame, pyarrow Table, or dict of arrays."""
        import pyarrow as pa
        if isinstance(data, dict):
            table = pa.table(data)
        elif isinstance(data, pa.Table):
            table = data
        else:  # pandas
            table = pa.Table.from_pandas(data, preserve_index=False)
        from ..batch import _arrow_to_logical, Field, Schema
        fields = [Field(n, _arrow_to_logical(t), True)
                  for n, t in zip(table.column_names, table.schema.types)]
        out_schema = Schema(fields)
        batch_rows = self._tpu_conf()["spark.rapids.tpu.sql.batchSizeRows"]

        def factory(t=table, rows=batch_rows):
            if t.num_rows <= rows:
                yield t
                return
            for off in range(0, t.num_rows, rows):
                yield t.slice(off, min(rows, t.num_rows - off))

        factory.estimated_rows = table.num_rows  # CBO/auto-broadcast stat
        node = L.LogicalScan(out_schema, factory, "local", fmt="memory")
        return DataFrame(node, self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1
              ) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(L.LogicalRange(start, end, step), self)

    # -- ICI mesh -----------------------------------------------------------------
    def set_mesh(self, mesh) -> None:
        """Install the jax.sharding.Mesh used by shuffle.mode=ICI."""
        self._mesh = mesh

    def ici_mesh(self):
        """The session's ICI mesh; built over the visible devices when not
        set explicitly (shuffle.ici.devices bounds the count)."""
        mesh = getattr(self, "_mesh", None)
        if mesh is not None:
            return mesh
        import jax
        import numpy as _np
        from jax.sharding import Mesh
        n = self._tpu_conf()["spark.rapids.tpu.shuffle.ici.devices"]
        # cache keyed by the conf value so changing shuffle.ici.devices
        # rebuilds (an explicit set_mesh always wins above)
        auto = getattr(self, "_mesh_auto", None)
        if auto is not None and auto[0] == n:
            return auto[1]
        devices = jax.devices()
        if n:
            if len(devices) < n:
                raise RuntimeError(
                    f"shuffle.ici.devices={n} but only {len(devices)} "
                    f"devices are visible")
            devices = devices[:n]
        mesh = Mesh(_np.array(devices), ("data",))
        self._mesh_auto = (n, mesh)
        return mesh

    # -- execution ----------------------------------------------------------------
    def _plan_physical(self, plan: L.LogicalPlan):
        from ..plan.overrides import apply_overrides
        conf = self._tpu_conf()
        return apply_overrides(plan, conf)

    def _distribute_if_ici(self, phys, ctx):
        """shuffle.mode=ICI: run exchange-bearing fragments on the mesh,
        return the residual plan (parallel/spmd.py)."""
        if ctx.conf["spark.rapids.tpu.shuffle.mode"] != "ICI":
            return phys
        from ..parallel.spmd import distribute_plan
        from ..utils import tracing
        with tracing.span(None, "plan:distribute", "plan"):
            return distribute_plan(phys, ctx, self.ici_mesh())

    def _resolve_subqueries(self, plan: L.LogicalPlan):
        """Subqueries rewritten to their values.  Each one EXECUTES
        inside the ``plan:subqueries`` span and inside this query's
        scope: its spans are charged to their own terms of this query's
        account, and what is left is the rewrite's own work."""
        from ..plan.subquery import resolve_subqueries
        from ..utils import tracing
        with tracing.span(None, "plan:subqueries", "plan"):
            return resolve_subqueries(plan, self._collect_rows)

    @staticmethod
    def _rows(t) -> list:
        """An arrow table as python rows (``result:rows``)."""
        if t is None:
            return []
        from ..utils import tracing
        with tracing.span(None, "result:rows", "result"):
            cols = [t.column(i).to_pylist() for i in range(t.num_columns)]
            return [tuple(c[i] for c in cols) for i in range(t.num_rows)]

    def _collect_rows(self, plan: L.LogicalPlan):
        """Execute a (sub)plan to host rows — the subquery resolver's
        executor (plans passed here are already subquery-free)."""
        return self._rows(self._execute_resolved(plan))

    def _execute(self, plan: L.LogicalPlan):
        with self._query_scope():
            plan = self._resolve_subqueries(plan)
            return self._execute_resolved(plan)

    # -- query service ------------------------------------------------------------
    def scheduler(self):
        """The session's lazily-created :class:`..service.scheduler.
        QueryScheduler` (admission-controlled concurrent execution)."""
        sched = getattr(self, "_scheduler", None)
        if sched is None:
            with Session._lock:
                sched = getattr(self, "_scheduler", None)
                if sched is None:
                    from ..service.scheduler import QueryScheduler
                    sched = self._scheduler = QueryScheduler(self)
        return sched

    def submit(self, df, *, priority: Optional[int] = None,
               deadline_s: Optional[float] = None, tenant: str = "default",
               weight: float = 1.0, label: Optional[str] = None,
               fingerprint: Optional[str] = None):
        """Submit a query for ASYNC execution through the session's
        scheduler; returns a :class:`..service.scheduler.QueryHandle`
        (future + cancel + per-query stats).  ``fingerprint`` (a
        ``cache/keys.statement_fingerprint``; the front door supplies
        it for wire queries) keys the predictive-admission cost model.
        Sheds with a typed :class:`..service.scheduler.QueryRejected`
        (reason + retry_after_ms) under overload."""
        return self.scheduler().submit(
            df, priority=priority, deadline_s=deadline_s, tenant=tenant,
            weight=weight, label=label, fingerprint=fingerprint)

    @contextlib.contextmanager
    def _control_scope(self, conf):
        """Install a per-query cancellation/deadline control unless the
        caller (scheduler worker, ``collect(timeout=)``) already did.
        ``scheduler.deadlineMs`` > 0 gives synchronous queries a default
        deadline; otherwise the scope is a pass-through (the engine's
        batch-boundary checks cost one ContextVar read)."""
        from ..service import cancel
        existing = cancel.current()
        if existing is not None:
            yield existing
            return
        dl_ms = conf["spark.rapids.tpu.sql.scheduler.deadlineMs"]
        if dl_ms <= 0:
            yield None
            return
        ctl = cancel.QueryControl(label="session-query",
                                  deadline_s=dl_ms / 1000.0)
        with cancel.scope(ctl) as c:
            yield c

    def _fault_scope(self, conf):
        """Per-query transient-fault scope: the retry budget
        (``spark.rapids.tpu.faults.retryBudget``) plus the conf the
        recovery layer's conf-less call sites (io sources, shuffle
        readers) resolve backoff parameters from.  Worker threads run
        copied contexts, so the whole query draws one budget."""
        from ..faults.recovery import budget_scope
        return budget_scope(conf)

    # -- query tracing ------------------------------------------------------------
    _query_seq = 0

    def _trace_scope(self, conf):
        """The per-query observability scope: query-scoped QueryStats
        (contextvars — concurrent queries never cross-account) plus an
        active QueryTrace for the span tree when ``sql.trace.enabled``
        OR the flight recorder is armed (``recorder.enabled``, default
        on — the recorder decides at COMPLETION whether the trace is
        worth retaining; see utils/recorder.py)."""
        from ..service import cancel
        from ..utils import tracing
        with Session._lock:
            Session._query_seq += 1
            label = f"query-{Session._query_seq:04d}"
        ctl = cancel.current()
        if ctl is not None and ctl.label:
            label = f"{label}[{ctl.label}]"
        return tracing.query_trace(
            label,
            enabled=(conf["spark.rapids.tpu.sql.trace.enabled"]
                     or conf["spark.rapids.tpu.recorder.enabled"]),
            max_events=conf["spark.rapids.tpu.sql.trace.maxEvents"])

    @contextlib.contextmanager
    def _query_scope(self):
        """The per-query scope stack: query-scoped QueryStats, the
        fault budget, the cancellation control, the QueryTrace and the
        host-time account (utils/tracing.account), whose driving thread
        is this one.  Opened ONCE per query, at the entry point
        (``DataFrame.collect`` / ``_executed``, or an ``_execute*`` path
        called directly), so planning and result materialisation are
        inside the query's wall; whatever opens it again on the same
        thread (the ``_execute*`` path under the entry point, a scalar
        subquery's ``_collect_rows``) joins the scope that is open."""
        from ..utils import tracing
        from ..utils.metrics import QueryStats
        outer = _SCOPE.get()
        if outer is not None and outer.tid == threading.get_ident():
            yield outer
            return
        conf = self._tpu_conf()
        with QueryStats.scoped() as stats, self._fault_scope(conf), \
                self._control_scope(conf), self._trace_scope(conf) as tr:
            qs = _QueryScope(conf, stats, tr)
            tok = _SCOPE.set(qs)
            self._note_scheduler(tr)
            try:
                with tracing.account(stats):
                    yield qs
            except BaseException as e:
                self._trace_status(tr, e)
                raise
            finally:
                try:
                    _SCOPE.reset(tok)
                except ValueError:  # generator-held scope, closed out of order
                    _SCOPE.set(None)
                # the trace finishes (and auto-dumps) even for an
                # aborted query, carrying its cancelled/deadline status;
                # the account has closed into ``stats`` by now
                self._finish_trace(qs)

    def _note_scheduler(self, tr) -> None:
        """Fold the scheduler's per-query accounting into the trace:
        a ``scheduler:queue_wait`` span (rendered at the head of the
        timeline) plus scheduler attrs on the query's root event — the
        Perfetto export shows where a query waited before running."""
        from ..service import cancel
        ctl = cancel.current()
        if ctl is None:
            return
        if tr is not None:
            ctl.trace = tr  # QueryHandle.trace() surfaces it post-hoc
        if ctl.enqueued_t is None or tr is None:
            return
        from ..utils import tracing
        tracing.record(None, "scheduler:queue_wait", "scheduler",
                       ctl.enqueued_t, ctl.queue_wait_s,
                       priority=ctl.priority, tenant=ctl.tenant)
        tr.attrs.update({
            "scheduler_label": ctl.label,
            "priority": ctl.priority,
            "tenant": ctl.tenant,
            "queue_wait_s": round(ctl.queue_wait_s, 6)})
        server_attrs = getattr(ctl, "server_attrs", None)
        if server_attrs:
            # a wire query's root span carries its connection identity
            # (server/endpoint.py sets these at submit): the trace is
            # attributable to a tenant AND a connection end to end
            tr.attrs.update(server_attrs)
        resubmit_of = getattr(ctl, "resubmit_of", None)
        if resubmit_of:
            # a scheduler-resubmitted attempt links BACK to the faulted
            # attempt it retries (whose trace links forward via
            # resubmitted_to) — the faulted→resubmitted→done lineage is
            # walkable from either end
            tr.attrs["resubmit_of"] = resubmit_of

    @staticmethod
    def _trace_status(tr, exc: BaseException) -> None:
        """Map the exception that ended execution onto the trace's span
        status, so an aborted query's trace ends 'cancelled' (and a
        query whose transient-fault recovery exhausted ends 'faulted')."""
        if tr is None or isinstance(exc, GeneratorExit):
            return  # an abandoned stream (LIMIT) is not a failure
        from ..faults.recovery import QueryFaulted
        from ..service import cancel
        if isinstance(exc, QueryFaulted):
            tr.set_status("faulted")
        elif isinstance(exc, cancel.QueryStalled):
            # the watchdog's cooperative cancel: a hang is a gray
            # FAILURE (the scheduler finishes it faulted/resubmittable),
            # so the trace says faulted, not cancelled
            tr.set_status("faulted")
        elif isinstance(exc, cancel.QueryDrained):
            # graceful drain: the query was healthy, the service is
            # leaving — the trace says so, and the scheduler surfaces a
            # typed resubmittable failure the caller re-routes
            tr.set_status("drained")
        elif isinstance(exc, cancel.QueryDeadlineExceeded):
            tr.set_status("deadline")
        elif isinstance(exc, cancel.QueryCancelled):
            tr.set_status("cancelled")
        else:
            tr.set_status("error")

    def _finish_trace(self, qs: _QueryScope) -> None:
        tr, stats, conf = qs.trace, qs.stats, qs.conf
        if tr is None:
            return
        if tr.status == "ok" and stats.degraded_batches:
            # the query finished, but some batches ran the CPU
            # degradation path after device-op retries exhausted — an
            # accurate trace says so (the degraded:cpu marks carry the
            # per-operator detail)
            tr.set_status("degraded")
        metrics = {}
        for ctx in qs.ctxs:
            metrics.update(ctx.metrics)
        tr.finish(metrics=metrics, stats=stats.snapshot())
        self._last_trace = tr
        trace_dir = conf["spark.rapids.tpu.sql.trace.dir"]
        if trace_dir and conf["spark.rapids.tpu.sql.trace.enabled"]:
            # the every-query dump stays opt-in via sql.trace.enabled;
            # the recorder (below) dumps only what retention keeps
            import os
            os.makedirs(trace_dir, exist_ok=True)
            tr.write(os.path.join(trace_dir, f"{tr.label}.trace.json"))
        from ..utils import recorder
        recorder.offer(tr, conf)

    def last_trace(self):
        """The QueryTrace of the most recent traced execution (None
        until a query runs with sql.trace.enabled=true or the flight
        recorder armed — recorder.enabled defaults true, so ordinarily
        every query's trace lands here)."""
        return getattr(self, "_last_trace", None)

    def profiled_explain(self) -> str:
        """The most recent query's physical plan re-rendered with each
        operator's accumulated metrics (rows/batches/bytes/time + the
        operator's own counters) — the SQL-UI metrics view analog."""
        from ..utils import tracing
        phys = getattr(self, "_last_phys", None)
        ctx = getattr(self, "_last_ctx", None)
        if phys is None or ctx is None:
            return "<no query has executed in this session>"
        return tracing.render_profiled(phys, ctx.metrics)

    def _explain_profiled(self, plan: L.LogicalPlan) -> str:
        """Execute the plan, then render the profiled physical tree
        (df.explain('profiled'))."""
        self._execute(plan)
        return self.profiled_explain()

    # -- execution entry points ---------------------------------------------------
    def _execute_device(self, plan: L.LogicalPlan):
        """Execute to ONE compacted device-resident batch (no host round
        trip) — the zero-copy export pipeline (DataFrame.to_device_arrays).
        Shares the same resolve/plan/distribute sequence as collect().
        Concatenates sel-masked batches BEFORE compacting: one host sync
        total instead of one per batch."""
        from ..ops import batch_utils
        from ..runtime.semaphore import get_semaphore
        with self._query_scope() as qs:
            plan = self._resolve_subqueries(plan)
            phys = self._plan_physical(plan)
            ctx = ExecContext(qs.conf, device=self.device)
            qs.ctxs.append(ctx)
            with get_semaphore(qs.conf).acquire():
                phys = self._distribute_if_ici(phys, ctx)
                if qs.trace is not None:
                    qs.trace.register_plan(phys)
                batches = [b for b in phys.execute(ctx)
                           if b.num_rows > 0]
                if not batches:
                    return None
                whole = batches[0] if len(batches) == 1 else \
                    batch_utils.concat_batches(batches)
                return batch_utils.compact(whole)

    def _execute_resolved(self, plan: L.LogicalPlan):
        from ..runtime.semaphore import get_semaphore
        with self._query_scope() as qs:
            phys = self._plan_physical(plan)
            ctx = ExecContext(qs.conf, device=self.device)
            qs.ctxs.append(ctx)
            # expose the last query's per-operator metrics + plan for
            # debugging/profiling (sess.last_exec_context().metrics,
            # sess.profiled_explain())
            self._last_ctx = ctx
            self._last_phys = phys
            with get_semaphore(qs.conf).acquire():
                phys = self._distribute_if_ici(phys, ctx)
                self._last_phys = phys
                if qs.trace is not None:
                    qs.trace.register_plan(phys)
                return CollectExec(phys).collect_arrow(ctx)

    def last_exec_context(self):
        """ExecContext of the most recent collect (per-operator MetricSet
        map keyed by op id) — the EXPLAIN-with-metrics debugging surface."""
        return getattr(self, "_last_ctx", None)

    def _execute_batches(self, plan: L.LogicalPlan):
        """Stream the result as pyarrow Tables, one per output batch —
        the write path's entry so results never materialize wholesale."""
        return self._stream(lambda: self._plan_physical(plan))

    def _stream_plan(self, plan: L.LogicalPlan):
        """Plan + stream a logical plan (subqueries resolved) — the
        network front door's FRESH-submit path (server/endpoint.py):
        result batches reach the consumer as their D2H fetches complete
        instead of after a wholesale collect."""
        return self._stream(lambda: self._plan_physical(
            self._resolve_subqueries(plan)))

    def _execute_planned_stream(self, phys, conf=None):
        """Stream pyarrow tables from an ALREADY-PLANNED physical tree.
        Logical planning and overrides are SKIPPED — this is the
        prepared-statement fast path (server/prepared.py plans once,
        clones the tree per execution, and re-runs it here with freshly
        bound parameters)."""
        return self._stream(lambda: phys, conf)

    def _stream(self, planned, conf=None):
        """Stream pyarrow tables from the physical tree ``planned()``
        returns, under the full per-query scope stack (stats / fault /
        control / trace / account + semaphore); ``planned`` runs inside
        the scope, so planning is in the query's wall.  D2H fetches ride
        the async pipeline depth (runtime/pipeline.stream_arrow), so
        incremental consumers — the wire, the write path — see batch N
        while batch N+1 dispatches.  While the consumer holds a table
        the account's clock stands still: the query's wall is the
        engine's share of the stream."""
        from ..runtime.pipeline import stream_arrow
        from ..runtime.semaphore import get_semaphore
        from ..utils import tracing
        with self._query_scope() as qs:
            if conf is None:
                conf = qs.conf
            phys = planned()
            ctx = ExecContext(conf, device=self.device)
            qs.ctxs.append(ctx)
            with get_semaphore(conf).acquire():
                phys = self._distribute_if_ici(phys, ctx)
                if qs.trace is not None:
                    qs.trace.register_plan(phys)
                for t in stream_arrow(ctx, phys.execute(ctx)):
                    with tracing.suspended():
                        yield t

    def _explain(self, plan: L.LogicalPlan) -> str:
        """The plan as it would run.  ``IN (subquery)`` as a filter
        conjunct is a rewrite to a semi join and runs nothing, so it is
        shown rewritten; a scalar or NOT IN subquery would have to
        execute: the plan is then explained as written."""
        from ..plan.overrides import explain_plan
        from ..plan.subquery import resolve_subqueries

        class NeedsARun(Exception):
            pass

        def refuse(_subplan):
            raise NeedsARun

        try:
            plan = resolve_subqueries(plan, refuse)
        except (NeedsARun, NotImplementedError):
            pass
        return explain_plan(plan, self._tpu_conf())
