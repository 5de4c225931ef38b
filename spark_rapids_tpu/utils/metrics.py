"""Operator metrics + trace annotations.

Two-tier design copied from the reference (SURVEY.md §5.1): per-operator SQL
metrics (GpuExec.scala:49-141 ``GpuMetric`` with ESSENTIAL/MODERATE/DEBUG
levels) and task-level counters (GpuTaskMetrics.scala).  NVTX ranges
(NvtxWithMetrics.scala:34) become ``jax.profiler.TraceAnnotation`` so the
ranges land in XLA/TPU profiler timelines.
"""

from __future__ import annotations

import contextlib
import contextvars
import time
from collections import defaultdict
from typing import Dict

import jax

from . import tracing

__all__ = ["MetricSet", "TaskMetrics", "QueryStats", "trace_range",
           "fetch", "fetch_async", "fetch_scalars", "prestage",
           "sync_budget", "FetchFuture", "RegionPrologue", "region_scope",
           "region_enter", "region_exit", "current_region",
           "stage_scalars", "region_scalars", "region_fetch"]


# the stack of query-scoped QueryStats instances for this context;
# contextvars (not a process global) so two concurrent queries — or a
# bench run alongside a test — never cross-account fetches/compiles.
# Worker threads (runtime/pipeline, io prefetch) run in a copied context
# and therefore write into their query's scope.
_STATS_STACK: "contextvars.ContextVar[tuple]" = \
    contextvars.ContextVar("srt_query_stats", default=())


class QueryStats:
    """Sync/compile profile (VERDICT r4 item 2), query-scoped.

    The reference's per-query NVTX + SQL-metric story answers "where did
    the time go"; here the two questions that matter are *how many
    blocking device→host fetches did this query issue* (each stalls the
    dispatch front until the device drains) and *how many XLA programs
    did it compile* (each is seconds).  Every blocking fetch in
    the engine routes through :func:`fetch`/:func:`fetch_scalars`;
    compiles are counted by a ``jax.monitoring`` listener on
    ``/jax/core/compile/backend_compile_duration``.

    ``bench.py`` snapshots this around each timed run and emits the
    deltas in the per-query JSON.

    Scoping: :meth:`get` resolves the innermost active :meth:`scoped`
    instance (the running query's), falling back to the process-level
    aggregate.  When a scope exits, its counts fold into the enclosing
    scope — ultimately the process aggregate, which therefore keeps the
    cumulative totals existing callers (bench deltas, sync-budget tests)
    rely on.
    """

    _process: "QueryStats" = None
    _listener_installed = False

    def __init__(self):
        self.blocking_fetches = 0
        # device→host fetches resolved through a FetchFuture: the copy
        # runs behind the dispatch front, so these do NOT count against
        # the blocking-fetch budget (they are still traced and byte- and
        # wait-accounted)
        self.async_fetches = 0
        self.fetch_bytes = 0
        # wall-clock the engine spent BLOCKED inside jax.device_get
        # (sync + async-resolve combined): the attributable D2H stall
        self.fetch_wait_s = 0.0
        self.compiles = 0
        self.compile_s = 0.0
        self.uploads = 0
        self.upload_bytes = 0
        # bytes entering shuffle exchanges (device batch sizes at the
        # staging barrier) — BASELINE.json's shuffle-GB/s metric input
        self.shuffle_bytes = 0
        # execution-pipeline accounting (runtime/pipeline.py): time the
        # consumer blocked waiting on a staged batch vs time the worker
        # spent staging — bench derives overlap_s = stage - wait
        self.h2d_wait_s = 0.0
        self.pipeline_stage_s = 0.0
        # input batches whose device buffers were donated to a fused
        # stage program (HBM reuse; plan/physical.StageExec)
        self.donated_batches = 0
        # wall-clock this query waited in the service admission queue
        # before starting (service/scheduler.py writes it; 0 for
        # synchronous queries) — the bench concurrency mode derives
        # service latency = queue wait + execution
        self.queue_wait_s = 0.0
        # cross-query device cache (spark_rapids_tpu/cache/): lookups
        # against the scan + broadcast tiers, bytes served from cache
        # instead of decode+upload, and entries dropped (budget/TTL/
        # invalidation) — bench's cache_hits_warm / cache_mb_saved
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_hit_bytes = 0
        self.cache_evictions = 0
        self.cache_evict_bytes = 0
        # transient-fault framework (spark_rapids_tpu/faults/): faults
        # the injector fired, retries the recovery layer issued (and the
        # wall-clock spent backing off), shuffle fragments re-pulled
        # from their producing stage after a fault, and batches that
        # degraded to the cpu/ path after device-op retries exhausted —
        # bench's SRT_BENCH_FAULT_RATE columns and the trace_report
        # fault-summary line read these
        self.faults_injected = 0
        self.transient_retries = 0
        self.retry_backoff_s = 0.0
        self.fragments_recomputed = 0
        self.degraded_batches = 0
        # distributed failure survival (parallel/dcn.py + service/
        # scheduler.py): peers the coordinator declared dead while this
        # query ran, shuffle fragments re-pulled from a DEAD peer's
        # durable map output (the cross-peer generalization of
        # fragments_recomputed), reduce partitions re-owned across the
        # shrunk group, and whole-query scheduler resubmissions after a
        # permanent-at-this-placement failure — the trace_report peer
        # summary and bench's SRT_BENCH_KILL_PEER columns read these
        self.peers_lost = 0
        self.fragments_recomputed_remote = 0
        self.partitions_reowned = 0
        self.queries_resubmitted = 0
        # network partition survival (parallel/dcn.py + faults/
        # netfabric.py): duplicated/reordered frames whose recorded
        # reply replayed from a dedup journal instead of re-applying,
        # ranks that parked typed (QuorumLostError) on the minority
        # side of a partition instead of promoting a second
        # coordinator, and parked ranks that healed + re-registered
        # (under flap damping) after the partition healed — the
        # partition chaos differential and loadgen's partition drill
        # read these
        self.frames_deduped = 0
        self.quorum_losses = 0
        self.rank_rejoins = 0
        # coordinator failovers this rank performed (re-dialed the
        # deterministic successor after coordinator loss; the successor
        # itself also counts its self-promotion) — epoch continuity plus
        # this counter make a survived coordinator death attributable
        self.coordinator_failovers = 0
        # gray-failure survival (faults/integrity.py, service/watchdog
        # .py, parallel/dcn.py hedging): checksum verifications that
        # FAILED (each one a silent-corruption event caught and routed
        # into recovery), slow-peer fragment fetches hedged against the
        # durable map output (first result wins), and queries the
        # watchdog declared stalled — the trace_report integrity:/
        # stalls: lines and bench's SRT_BENCH_GRAY_RATE columns read
        # these
        self.integrity_failures = 0
        self.fragments_hedged = 0
        self.stalls_detected = 0
        # network front door (spark_rapids_tpu/server/): Arrow IPC bytes
        # a wire query produced for its result stream, bytes of those
        # that overflowed to the disk spool (slow client / large
        # collect), and prepared-statement plan-cache hits/misses
        # (PREPARE-time; hits skip the full planning stack at EXECUTE) —
        # the trace_report server: line and the loadgen report read
        # these
        self.server_stream_bytes = 0
        self.server_spooled_bytes = 0
        self.prepared_hits = 0
        self.prepared_misses = 0
        # whole-query data-path fusion (plan/fusion.py): regions the
        # planner formed and executed, and the blocking fetches those
        # regions paid through their batched prologue (a subset of
        # blocking_fetches) — bench's fused_regions columns and the
        # trace_report fusion: line read these
        self.fused_regions = 0
        self.region_fetches = 0
        # overload survival (service/admission.py): device spill events
        # attributed to THIS query's scope (the spill catalog stamps
        # the active scope at each device->host demotion) — the
        # spill-degrade signal the admission cost model and the AIMD
        # concurrency controller both consume
        self.spill_events = 0

    # -- accessors ----------------------------------------------------------
    @classmethod
    def get(cls) -> "QueryStats":
        """The stats of the innermost active query scope, or the process
        aggregate when no scope is active."""
        stack = _STATS_STACK.get()
        if stack:
            return stack[-1]
        return cls.process()

    @classmethod
    def process(cls) -> "QueryStats":
        """The process-level aggregate (backward-compatible totals)."""
        if cls._process is None:
            cls._process = QueryStats()
            cls._install_listener()
        return cls._process

    @classmethod
    @contextlib.contextmanager
    def scoped(cls):
        """Open a query-scoped stats instance for this context.  Yields
        the fresh instance; on exit its counts fold into the enclosing
        scope (ultimately the process aggregate)."""
        cls.process()  # aggregate + compile listener exist first
        s = QueryStats()
        tok = _STATS_STACK.set(_STATS_STACK.get() + (s,))
        try:
            yield s
        finally:
            try:
                _STATS_STACK.reset(tok)
            except ValueError:
                # interleaved streaming executions can violate token
                # LIFO (generator-held scopes): drop just this entry
                _STATS_STACK.set(tuple(
                    x for x in _STATS_STACK.get() if x is not s))
            cls.get()._absorb(s)
            if not _STATS_STACK.get():
                # the scope exited to the PROCESS aggregate: mirror the
                # query's counts into the live metrics registry — THE
                # fold-in choke point (nested scopes fold outward and
                # reach here exactly once, so nothing double-counts)
                from . import telemetry
                telemetry.fold_query_stats(s)

    def _absorb(self, other: "QueryStats") -> None:
        for k, v in other.__dict__.items():
            setattr(self, k, getattr(self, k, 0) + v)

    @classmethod
    def total_blocking_fetches(cls) -> int:
        """Cumulative blocking fetches across the process aggregate AND
        every open scope — the sync-budget denominator (a budget spanning
        multiple queries must see fetches already folded out of their
        scopes plus the in-flight scope's)."""
        n = cls.process().blocking_fetches
        for s in _STATS_STACK.get():
            n += s.blocking_fetches
        return n

    @classmethod
    def _install_listener(cls):
        if cls._listener_installed:
            return
        cls._listener_installed = True

        def on_duration(event: str, duration: float, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                s = cls.get()
                s.compiles += 1
                s.compile_s += duration
                tracing.record(None, "compile", "compile",
                               time.perf_counter() - duration, duration)
                # a finished compile is PROGRESS: the watchdog must not
                # mistake a query grinding through a compile sequence
                # for a hung one
                from ..service import cancel as _cancel
                ctl = _cancel.current()
                if ctl is not None:
                    ctl.note_progress()
                # feed the compile ledger: per-statement-fingerprint
                # count/duration with trigger classification (first-seen
                # vs shape-change vs post-restart vs cache-evict) — the
                # traffic×compile profile behind precompile priority
                from . import recorder as _recorder
                _recorder.compile_note(
                    duration,
                    getattr(ctl, "fingerprint", None)
                    if ctl is not None else None)

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> Dict[str, float]:
        return {k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in self.__dict__.items()}

    @classmethod
    def reset(cls) -> "QueryStats":
        s = cls.get()
        s.__init__()
        return s

    @classmethod
    def delta_since(cls, before: Dict[str, float]) -> Dict[str, float]:
        now = cls.get().snapshot()
        return {k: (round(now[k] - before.get(k, 0), 4)
                    if isinstance(now[k], float)
                    else now[k] - before.get(k, 0)) for k in now}


import os as _os

_TRACE_SYNCS = bool(_os.environ.get("SRT_SYNC_TRACE"))
SYNC_TRACE: list = []  # [(call-site, seconds)] when SRT_SYNC_TRACE is set
# hard cap on the debug list: a long bench/serve run under SRT_SYNC_TRACE
# must not grow host memory without bound — entries beyond the cap are
# counted, not stored (sync_trace_dropped()).
SYNC_TRACE_MAX = int(_os.environ.get("SRT_SYNC_TRACE_MAX", "10000"))
_SYNC_TRACE_DROPPED = [0]


def sync_trace_dropped() -> int:
    """Entries dropped from SYNC_TRACE after it hit SYNC_TRACE_MAX."""
    return _SYNC_TRACE_DROPPED[0]


def _export_sync_trace_drops() -> None:
    """Scrape-time provider: the SYNC_TRACE debug list's drop count is
    visible on the ops surface instead of silently lost."""
    from . import telemetry
    telemetry.gauge_set("sync_trace_dropped", float(sync_trace_dropped()))


from . import telemetry as _telemetry  # noqa: E402 (after the state it exports)

_telemetry.register_provider(_export_sync_trace_drops)


def _sync_trace_append(entry) -> None:
    if len(SYNC_TRACE) < SYNC_TRACE_MAX:
        SYNC_TRACE.append(entry)
    else:
        _SYNC_TRACE_DROPPED[0] += 1


def _tree_nbytes(host) -> int:
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(host):
        if isinstance(leaf, np.ndarray):
            total += leaf.nbytes
        elif isinstance(leaf, np.generic):
            total += leaf.nbytes
    return total


def _call_site(extra_frames: int = 0) -> str:
    import traceback
    drop = 2 + extra_frames  # _call_site + the helper that asked for it
    return "|".join(
        f"{f.filename.rsplit('/', 1)[-1]}:{f.lineno}"
        for f in traceback.extract_stack(limit=6 + drop)[:-drop])


def _resolve_tree(tree, site=None, tag: str = ""):
    """The ONE ``jax.device_get`` call site for sync AND async fetches:
    times the wait (``fetch_wait_s``), accounts bytes, and — under
    SRT_SYNC_TRACE — appends the attributed call site to SYNC_TRACE."""
    s = QueryStats.get()
    t0 = time.perf_counter()
    host = jax.device_get(tree)
    dt = time.perf_counter() - t0
    nbytes = _tree_nbytes(host)
    s.fetch_wait_s += dt
    s.fetch_bytes += nbytes
    tracing.record(None, "fetch", "fetch", t0, dt,
                   bytes=nbytes, blocking=not tag)
    if _TRACE_SYNCS:
        if site is None:
            site = _call_site(extra_frames=1)
        _sync_trace_append(((tag + site) if tag else site, round(dt, 4)))
    return host


def fetch(tree):
    """The engine's ONE blocking device→host transfer choke point.

    Counts a single blocking round-trip regardless of how many arrays
    ride in the tree (jax.device_get batches them into one transfer),
    plus the bytes moved.  All hot-path syncs route through here so the
    per-query sync profile in bench output is trustworthy.
    """
    s = QueryStats.get()
    s.blocking_fetches += 1
    host = _resolve_tree(tree, site=_call_site() if _TRACE_SYNCS else None)
    _check_budget()
    return host


def _start_copies(tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        start = getattr(leaf, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # fault-ok (async-copy hint only; the blocking get still works)
                pass


class FetchFuture:
    """A device→host fetch whose copy is already in flight.

    ``result()`` blocks only for whatever part of the transfer has not
    finished yet — the copy overlaps the next batch's dispatch instead
    of stalling the pull loop.  Resolution
    routes through the same accounting as :func:`fetch` (bytes, wait
    time, SRT_SYNC_TRACE site) but counts as an *async* fetch, excluded
    from the blocking-fetch budget.
    """

    __slots__ = ("_tree", "_site", "_host", "_done")

    def __init__(self, tree, site=None):
        self._tree = tree
        self._site = site
        self._host = None
        self._done = False

    def result(self):
        if not self._done:
            self._host = _resolve_tree(self._tree, site=self._site,
                                       tag="async|")
            self._tree = None  # drop device refs once resolved
            self._done = True
        return self._host


def fetch_async(tree) -> FetchFuture:
    """Start a device→host transfer WITHOUT blocking: kicks off
    ``copy_to_host_async`` on every device leaf and returns a
    :class:`FetchFuture`.  Deferred metrics and collect's tail fetches
    ride this so the copy overlaps the next batch's dispatch."""
    s = QueryStats.get()
    s.async_fetches += 1
    site = _call_site() if _TRACE_SYNCS else None
    _start_copies(tree)
    return FetchFuture(tree, site)


def prestage(tree):
    """Fire-and-forget ``copy_to_host_async``: no counters, no future —
    a later :func:`fetch` of the same arrays finds the data already en
    route, shrinking its blocking wait.  Returns ``tree`` unchanged."""
    _start_copies(tree)
    return tree


def fetch_scalars(x) -> list:
    """Fetch a small device array of scalars as a list of Python ints."""
    import numpy as np
    return [int(v) for v in np.ravel(fetch(x))]


# ---------------------------------------------------------------------------------
# Region prologue: the batched stats-fetch contract of fused plan regions
# (plan/fusion.py).  Every member operator STAGES its small device stat
# vectors (join build stats, dense-agg key stats) as soon as they are
# dispatched; the first member that needs a VALUE resolves every staged
# vector in ONE blocking fetch — the region's prologue fetch.  Later
# demands hit the host copy with zero syncs.  With no region active the
# helpers degrade to plain prestage/fetch_scalars, byte-identically —
# that is the sql.fusion.enabled=false escape hatch.
# ---------------------------------------------------------------------------------

_REGION_STACK: "contextvars.ContextVar[tuple]" = \
    contextvars.ContextVar("srt_fusion_region", default=())


class RegionPrologue:
    """Per-region batching of blocking scalar fetches.

    Keys identify a staged vector for later lookup (a join instance's
    build-stats key); anonymous resolves ride the same batched fetch but
    are not retained.  Thread-safe: member operators may stage from
    pipeline workers running in a copied context.
    """

    __slots__ = ("label", "_lock", "_pending", "_host", "_trees", "_seq",
                 "fetches", "staged", "batched")

    def __init__(self, label: str = ""):
        import threading
        self.label = label
        self._lock = threading.Lock()
        self._pending: dict = {}   # key -> device tree (copy in flight)
        self._host: dict = {}      # key -> host tree
        self._trees: list = []     # pins staged device trees (id-stable keys)
        self._seq = 0              # anonymous-resolve key counter
        self.fetches = 0           # blocking prologue fetches this region paid
        self.staged = 0            # vectors staged into the prologue
        self.batched = 0           # values that rode a batch they didn't pay for

    def stage(self, key, tree) -> None:
        """Start the async D2H copy of ``tree`` and remember it under
        ``key``.  Idempotent per key — re-staging an already staged or
        resolved key is a no-op (the first dispatch wins)."""
        with self._lock:
            if key in self._host or key in self._pending:
                return
            self._pending[key] = tree
            self._trees.append(tree)
            self.staged += 1
        _start_copies(tree)

    def resolve(self, key, tree=None):
        """Host value for ``key``.  A staged-and-resolved key costs zero
        fetches; otherwise ALL currently pending vectors (plus ``tree``,
        when given) resolve in one blocking fetch."""
        with self._lock:
            hit = self._host.get(key)
            if hit is None and key not in self._pending:
                if tree is None:
                    raise KeyError(
                        f"region prologue: {key!r} was never staged")
                self._pending[key] = tree
                self._trees.append(tree)
                self.staged += 1
        if hit is not None:
            return hit
        with self._lock:
            pending, self._pending = self._pending, {}
        if pending:
            self.fetches += 1
            QueryStats.get().region_fetches += 1
            # fetch over a key-ordered LIST, not the dict: jax pytrees
            # sort dict keys, and prologue keys mix strings with tuples
            # (join-stats (program, build-id) pairs, anonymous counters)
            # which Python cannot order
            ks = list(pending)
            vals = fetch([pending[k] for k in ks])  # fusion-ok (THE region prologue fetch: one batched sync for every staged vector)
            with self._lock:
                self._host.update(zip(ks, vals))
                self.batched += max(0, len(ks) - 1)
        with self._lock:
            return self._host[key]

    def scalars(self, key, tree=None) -> list:
        import numpy as np
        return [int(v) for v in np.ravel(self.resolve(key, tree))]


def current_region():
    """The innermost active region prologue, or None outside any fused
    region (the per-op fallback path)."""
    stack = _REGION_STACK.get()
    return stack[-1] if stack else None


def region_enter(r: RegionPrologue):
    """Push a region prologue onto the scope stack (low-level form of
    :func:`region_scope`, for callers that must open/close the scope
    around individual pulls of a generator rather than a ``with``
    block — a scope held across a yield would leak to the consumer)."""
    return _REGION_STACK.set(_REGION_STACK.get() + (r,))


def region_exit(tok, r: RegionPrologue) -> None:
    """Pop the region pushed by :func:`region_enter`."""
    try:
        _REGION_STACK.reset(tok)
    except ValueError:
        # generator-held scopes can violate token LIFO (interleaved
        # streaming executions): drop just this entry
        _REGION_STACK.set(tuple(
            x for x in _REGION_STACK.get() if x is not r))


@contextlib.contextmanager
def region_scope(label: str = ""):
    """Open a region prologue for the scope (contextvar-carried, so
    pipeline workers spawned inside join it)."""
    r = RegionPrologue(label)
    tok = region_enter(r)
    try:
        yield r
    finally:
        region_exit(tok, r)


def stage_scalars(key, tree) -> None:
    """Stage a small device stat vector for the enclosing region's
    batched prologue fetch; outside a region this is :func:`prestage`
    (async copy hint only), byte-identically."""
    r = current_region()
    if r is None:
        prestage(tree)
        return
    r.stage(key, tree)


def region_fetch(tree, key=None):
    """:func:`fetch` that routes through the enclosing region's batched
    prologue (structure-preserving: returns the host tree); outside a
    region it IS fetch — the escape-hatch path."""
    r = current_region()
    if r is None:
        return fetch(tree)
    if key is None:
        with r._lock:
            r._seq += 1
            key = ("anon", r._seq)
    return r.resolve(key, tree)


def region_scalars(tree, key=None) -> list:
    """:func:`fetch_scalars` that routes through the enclosing region's
    prologue: inside a region the value resolves via the batched
    prologue fetch (one blocking sync covers every staged vector);
    outside a region it IS fetch_scalars — the escape-hatch path."""
    r = current_region()
    if r is None:
        return fetch_scalars(tree)
    if key is None:
        # anonymous one-shot: ride the batched fetch without retention
        with r._lock:
            r._seq += 1
            key = ("anon", r._seq)
    return r.scalars(key, tree)


class _SyncBudget:
    """Test-only enforcement: raise when a scope exceeds its fetch budget."""
    limit = None
    label = ""


def _check_budget():
    if _SyncBudget.limit is not None:
        # cumulative across the process aggregate + open query scopes: a
        # budget wrapping several queries keeps counting across them
        n = QueryStats.total_blocking_fetches()
        if n > _SyncBudget.limit:
            raise AssertionError(
                f"sync budget exceeded in {_SyncBudget.label}: "
                f"{n} blocking fetches > limit {_SyncBudget.limit}")


@contextlib.contextmanager
def sync_budget(limit: int, label: str = "scope"):
    """Enforce a blocking-fetch budget over a scope (regression tests)."""
    QueryStats.reset()
    _SyncBudget.limit = limit
    _SyncBudget.label = label
    try:
        yield QueryStats.get()
    finally:
        _SyncBudget.limit = None


class MetricSet:
    """Named counters/timers for one operator instance.

    ``level`` mirrors spark.rapids.tpu.sql.metrics.level (GpuMetric's
    ESSENTIAL/MODERATE/DEBUG): ESSENTIAL records counters only (timers are
    no-ops), MODERATE (default) adds wall-clock timers, DEBUG additionally
    emits jax profiler trace ranges so operator spans land in TPU profiles.
    """

    def __init__(self, op_id: str, level: str = "MODERATE"):
        self.op_id = op_id
        self.level = level
        self.values: Dict[str, float] = defaultdict(float)
        self._deferred: list = []  # [(name, device scalar)]

    def add(self, name: str, amount: float) -> None:
        self.values[name] += amount

    def add_deferred(self, name: str, device_scalar) -> None:
        """Count a device scalar WITHOUT a blocking fetch: the D2H copy
        starts immediately (async, behind the dispatch front) and the
        value is resolved only when the metric is actually read.
        A query must never pay a blocking round trip for a counter
        nobody looks at, and a counter somebody does look at should
        already be on the host by then."""
        self._deferred.append((name, fetch_async(device_scalar)))

    def _resolve(self) -> None:
        if not self._deferred:
            return
        pending, self._deferred = self._deferred, []
        for name, fut in pending:
            self.values[name] += int(fut.result())  # wait-ok (deferred metric; the copy is already behind the dispatch front)

    @contextlib.contextmanager
    def time(self, name: str):
        """Time a named phase of this operator.  This is the span API for
        exec-node timing (the srtlint span-timing pass rejects raw clock
        reads in the operator layer): the measurement lands in the metric
        value AND — when a query trace is active — as a phase span under
        the operator (decode/H2D/dispatch/fetch attribution)."""
        if self.level == "ESSENTIAL":
            yield
            return
        t0 = time.perf_counter()
        if self.level == "DEBUG":
            with trace_range(f"{self.op_id}:{name}"):
                yield
        else:
            yield
        dt = time.perf_counter() - t0
        self.values[name] += dt
        tracing.record(self.op_id, name, "phase", t0, dt)

    def __getitem__(self, name: str) -> float:
        self._resolve()
        return self.values.get(name, 0.0)

    def __repr__(self):
        self._resolve()
        inner = ", ".join(f"{k}={v:.4g}" for k, v in sorted(self.values.items()))
        return f"MetricSet({self.op_id}: {inner})"


class TaskMetrics:
    """Task-scope counters: semaphore wait, retries, spill bytes
    (GpuTaskMetrics.scala:81-142 analog).  Written by memory/retry.py and
    memory/spill.py; read by tests and session reporting."""

    _current = None

    def __init__(self):
        self.semaphore_wait_s = 0.0
        self.retry_count = 0
        self.split_retry_count = 0
        self.retry_block_s = 0.0
        self.spill_to_host_bytes = 0
        self.spill_to_disk_bytes = 0
        self.spill_count = 0

    def snapshot(self) -> Dict[str, float]:
        return dict(self.__dict__)

    def reset_counts(self) -> None:
        self.__init__()

    @classmethod
    def get(cls) -> "TaskMetrics":
        if cls._current is None:
            cls._current = TaskMetrics()
        return cls._current

    @classmethod
    def reset(cls) -> "TaskMetrics":
        # reset IN PLACE: writers hold no stale references to an orphaned
        # instance (there is exactly one task-metrics object per process)
        cls.get().reset_counts()
        return cls._current


@contextlib.contextmanager
def trace_range(name: str):
    """Profiler trace annotation (NVTX range analog)."""
    with jax.profiler.TraceAnnotation(name):
        yield
