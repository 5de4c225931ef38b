"""Operator metrics + the counted device boundaries.

Two-tier design copied from the reference (SURVEY.md §5.1): per-operator SQL
metrics (GpuExec.scala:49-141 ``GpuMetric`` with ESSENTIAL/MODERATE/DEBUG
levels) and task-level counters (GpuTaskMetrics.scala).  NVTX ranges
(NvtxWithMetrics.scala:34) are the spans of ``utils/tracing.py``: every
timer here is one, so it lands in XLA/TPU profiler timelines at every
metrics level.
"""

from __future__ import annotations

import contextlib
import contextvars
import sys
from collections import defaultdict
from typing import Dict

import jax

from . import tracing

__all__ = ["MetricSet", "TaskMetrics", "QueryStats", "upload",
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
        # host→device uploads through :func:`upload` (one per column of
        # a batch a scan stages): calls, device bytes as padded, seconds
        # inside jax.device_put, summed over the threads that upload
        self.uploads = 0
        self.upload_bytes = 0
        self.upload_s = 0.0
        # seconds inside ``scan:decode`` spans (io/), summed over the
        # threads that decode: can pass the query's wall
        self.decode_s = 0.0
        # mesh fragments (parallel/spmd.py, shuffle.mode=ICI): fragments
        # run, the seconds of their four phases (``ici:materialize`` /
        # ``ici:feed`` / ``ici:step`` / ``ici:gather`` spans, on the
        # driving thread; like decode_s and upload_s they overlap the
        # account's terms and add to nothing), the bytes placed on the
        # mesh as padded (every device's copy of a replicated leaf), the
        # bytes the all_to_alls move (from the static bucket shapes:
        # n_dev * n_dev * bucket rows * row width an exchange), the
        # re-runs at 4x capacities after an overflow, and the exchanges
        # that bucketed fewer rows than their input's capacity (the
        # ladder rung over the fullest sender's counted rows was below it)
        self.ici_fragments = 0
        self.ici_materialize_s = 0.0
        self.ici_feed_s = 0.0
        self.ici_step_s = 0.0
        self.ici_gather_s = 0.0
        self.ici_feed_bytes = 0
        self.ici_exchange_bytes = 0
        self.ici_overflow_retries = 0
        self.ici_compacted_exchanges = 0
        # dense aggregation (plan/physical.py, ops/dense_agg.py): batches
        # its update programs took, and those of them whose in-domain
        # rows fit a rung and were compacted on the device before the
        # scatters (decided in the program, read in the tail fetch)
        self.agg_dense_batches = 0
        self.agg_dense_compacted_batches = 0
        # the sort path's merges (plan/physical.py): runs of the
        # ``agg_merge_grouped`` program, counted where it is called, and
        # the partial results that entered them.  parts / merges is 2.0
        # where every part is merged into the result as it arrives, and
        # the parts a merge took where they were held (_HeldPartials)
        self.agg_merges = 0
        self.agg_merge_parts = 0
        # count(DISTINCT) beside plain aggregates lowered to two stacked
        # aggregates over ONE copy of the child (sql/dataframe.py
        # _plan_distinct_one_pass): the level-2 aggregates of that form
        # in plans that were converted (plan/overrides.py).  0 where the
        # join form ran the child once a distinct set and once more
        self.distinct_one_pass_aggs = 0
        # string group keys an aggregate coded from the parquet file's
        # page codes (PageCodedStringColumn; plan/physical.py
        # _encode_string_keys): one a key column a batch, each coded by
        # remapping the page dictionaries, no row hashed
        self.page_coded_keys = 0
        # the reporting operators (plan/window_exec.py, plan/exec_nodes.py
        # ExpandExec): seconds inside ``window:exec`` spans (concat,
        # compact, the program, the gather) and the live rows that entered
        # the window (the host knows them after the compact); seconds
        # inside ``expand:project`` spans and the slots the expansion
        # handed on (capacity x projections x batches, live or not: what
        # the aggregate above it pays for); plan nodes placed on the CPU
        # (plan/overrides.py: ``CpuOpExec`` in a plan that was run).
        # Whole-span seconds, like decode_s: they overlap the account's
        # terms and add to nothing
        self.window_exec_s = 0.0
        self.window_rows = 0
        self.expand_exec_s = 0.0
        self.expand_slot_rows = 0
        self.cpu_fallback_nodes = 0
        # the joins (plan/join_exec.py): seconds inside ``join:pair``
        # spans (one probe batch against its build side; whole-span
        # seconds of every thread, like window_exec_s), the candidate
        # pairs the expansions were sized for (the ``total`` the host
        # reads before it picks a capacity: every key match, before a
        # condition drops any), the output slots they ran at (the
        # capacity rung over that total: what the gathers above pay
        # for), and the semi, anti and existence joins run (a batch
        # each), which expand only where a condition takes part
        self.join_exec_s = 0.0
        self.join_pairs = 0
        self.join_out_slots = 0
        self.join_semi_anti = 0
        # the query's host-time account (utils/tracing.account): nine
        # disjoint terms of the DRIVING thread's time, by span self
        # time, that sum to ``query_wall_s``.  Unlike fetch_wait_s /
        # h2d_wait_s above and below, which sum the waits of every
        # thread, these are shares of one wall
        self.acct_plan_s = 0.0
        self.acct_admit_s = 0.0
        self.acct_compile_s = 0.0
        self.acct_h2d_wait_s = 0.0
        self.acct_fetch_wait_s = 0.0
        self.acct_dispatch_s = 0.0
        self.acct_result_s = 0.0
        self.acct_host_exec_s = 0.0
        self.acct_unattributed_s = 0.0
        self.query_wall_s = 0.0
        # ``acct_h2d_wait_s`` resolved (tracing.RESOLVED_TERMS): what the
        # producer thread the driving thread waited on was doing
        # meanwhile, through the producers upstream of it; the seven
        # sum to acct_h2d_wait_s
        self.acct_h2d_decode_s = 0.0
        self.acct_h2d_convert_s = 0.0
        self.acct_h2d_upload_s = 0.0
        self.acct_h2d_dispatch_s = 0.0
        self.acct_h2d_fetch_wait_s = 0.0
        self.acct_h2d_host_exec_s = 0.0
        self.acct_h2d_handoff_s = 0.0
        # bytes entering shuffle exchanges (device batch sizes at the
        # staging barrier) — BASELINE.json's shuffle-GB/s metric input
        self.shuffle_bytes = 0
        # execution-pipeline accounting (runtime/pipeline.py): time the
        # consumer blocked waiting on a staged batch, every thread's
        self.h2d_wait_s = 0.0
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
                # measured by jax, on the thread that compiled: recorded
                # after the fact, under the name the program was given
                tracing.record(None, "compile", "compile",
                               tracing._pc() - duration, duration,
                               fun_name=kw.get("fun_name"))
                tracing.charge("compile", duration)
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


def _tree_nbytes(host) -> int:
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(host):
        if isinstance(leaf, np.ndarray):
            total += leaf.nbytes
        elif isinstance(leaf, np.generic):
            total += leaf.nbytes
    return total


def _call_site() -> str:
    """``file:line|file:line|...`` of the frames that asked for a fetch,
    outermost first (no source lookup: cheap enough for every fetch of a
    traced query)."""
    f = sys._getframe(2)  # past _call_site and the fetch helper
    parts = []
    while f is not None and len(parts) < 6:
        parts.append(f"{f.f_code.co_filename.rsplit('/', 1)[-1]}:"
                     f"{f.f_lineno}")
        f = f.f_back
    return "|".join(reversed(parts))


def _resolve_tree(tree, site=None, blocking: bool = True):
    """The ONE ``jax.device_get`` call site for sync AND async fetches:
    a ``fetch:blocking`` / ``fetch:async`` span whose seconds add to
    ``fetch_wait_s`` (every thread's) and whose event carries the bytes
    and, when a QueryTrace is active, the call site that asked."""
    s = QueryStats.get()
    with tracing.span(None, "fetch:blocking" if blocking
                      else "fetch:async", "fetch") as sp:
        host = jax.device_get(tree)
        nbytes = _tree_nbytes(host)
        if site is not None:
            sp.set(bytes=nbytes, blocking=blocking, site=site)
    s.fetch_wait_s += sp.dur
    s.fetch_bytes += nbytes
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
    host = _resolve_tree(
        tree, site=_call_site() if tracing.active() is not None else None)
    _check_budget()
    return host


def upload(tree, device=None):
    """The engine's ONE counted host→device transfer: ``jax.device_put``
    of a pytree of host arrays under a ``scan:upload`` span, counted in
    ``QueryStats.uploads`` / ``upload_bytes`` (device bytes, as padded)
    / ``upload_s``."""
    s = QueryStats.get()
    with tracing.span(None, "scan:upload", "io") as sp:
        out = jax.device_put(tree, device)
    s.uploads += 1
    s.upload_bytes += sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(out))
    s.upload_s += sp.dur
    return out


@contextlib.contextmanager
def counted_span(field: str, op_id, name: str, cat: str):
    """A tracing span whose whole seconds also add to the running query's
    ``QueryStats.<field>`` (``window_exec_s``, ``expand_exec_s``,
    ``join_exec_s``): like
    ``decode_s`` they overlap the account's terms and add to nothing."""
    stats = QueryStats.get()
    sp = tracing.span(op_id, name, cat)
    try:
        with sp:
            yield stats
    finally:
        setattr(stats, field, getattr(stats, field) + sp.dur)


def _start_copies(tree) -> None:
    with tracing.span(None, "fetch:start_copies", "fetch"):
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
    time, call site) but counts as an *async* fetch, excluded from the
    blocking-fetch budget.
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
                                       blocking=False)
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
    site = _call_site() if tracing.active() is not None else None
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
    ESSENTIAL/MODERATE/DEBUG): ESSENTIAL records counters only (timers
    keep their span and add no value), MODERATE (default) adds wall-clock
    timers, DEBUG additionally makes operators count what costs a fetch
    (plan/join_exec.py).
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

    def time(self, name: str) -> "_TimedSpan":
        """Time a named phase of this operator.  This is the span API for
        exec-node timing (the srtlint span-timing pass rejects raw clock
        reads in the operator layer): an ``op:<name>`` span at every
        level (profiler annotation, phase event under the operator, the
        account's ``host_exec``), whose seconds also land in the metric
        value unless the level is ESSENTIAL."""
        return _TimedSpan(self, name)

    def __getitem__(self, name: str) -> float:
        self._resolve()
        return self.values.get(name, 0.0)

    def __repr__(self):
        self._resolve()
        inner = ", ".join(f"{k}={v:.4g}" for k, v in sorted(self.values.items()))
        return f"MetricSet({self.op_id}: {inner})"


class _TimedSpan(tracing._Span):
    """``MetricSet.time``'s span: closes into the operator's metric."""

    __slots__ = ("_mset",)

    def __init__(self, mset: MetricSet, name: str):
        super().__init__(mset.op_id, name, "phase", "op:" + name)
        self._mset = mset

    def __exit__(self, et=None, ev=None, tb=None):
        super().__exit__(et, ev, tb)
        if self._mset.level != "ESSENTIAL":
            self._mset.values[self._name] += self.dur
        return False


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
