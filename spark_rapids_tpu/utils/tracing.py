"""Query-scoped structured tracing: per-operator span trees.

The reference answers "where did the time go" with per-operator
``GpuMetric``s rendered in the Spark SQL UI plus NVTX ranges on the GPU
profiler timeline (SURVEY.md §5.1).  This module is the port's version of
that two-tier story, rebuilt for an engine whose wall time is a weave of
overlapped decode / H2D staging / dispatch / D2H phases (runtime/pipeline):

  * one **operator span** per physical plan node (keyed by the node's
    ``op_id``), forming a tree that mirrors the plan — every batch pull
    through an operator is timed and recorded on the thread it ran on;
  * **phase spans** under each operator for the engine's data-movement
    phases: decode (io layer), H2D staging (``scanTime``), dispatch
    (``opTime``), pipeline stage/wait (runtime/pipeline), and D2H fetch
    (utils/metrics ``fetch``/``fetch_async``), under the fixed
    ``<layer>:<name>`` vocabulary of :data:`SPANS`;
  * a **Chrome-trace-event JSON exporter** (loads in Perfetto /
    ``chrome://tracing``) so a query's overlap structure is visually
    inspectable, plus a ``spanTree`` extension key carrying the
    plan-shaped tree with per-operator accumulated metrics.

Everything is contextvar-scoped: two concurrent queries trace
independently, and the pipeline/io worker threads join their query's
trace by running in a copied context.

ONE span primitive serves three readers.  Every :func:`span` enters a
``jax.profiler.TraceAnnotation`` under its vocabulary name, so whenever
any profiler session is live the program's spans sit in the same
``.xplane.pb`` as ``XLA Ops``, on its clock; it adds an event to the
active :class:`QueryTrace`, if any; and on the query's DRIVING thread
(the one that opened the scope) it charges its self time to one term of
the query's host-time account (:func:`account`), which closes into
``QueryStats.acct_*`` once per query.  On a thread that stages for the
query (:func:`start_producer`) it moves that thread's running account,
which resolves a consumer's wait on the thread into what the thread
was doing meanwhile (:data:`RESOLVED_TERMS`).

This module is the ONE place exec-node timing may read the clock;
srtlint's ``span-timing`` pass rejects raw ``time.perf_counter()`` in the
plan/parallel layers so attribution cannot silently rot.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

from ..service import cancel as _cancel

__all__ = ["QueryTrace", "active", "query_trace", "span", "record", "mark",
           "instrument_batches", "render_profiled", "SPANS",
           "ACCOUNT_TERMS", "RESOLVED_TERMS", "account", "charge",
           "suspended", "start_producer",
           "merge_chrome", "write_merged", "trace_context",
           "shard_record", "shard_paths"]

_pc = time.perf_counter

_ACTIVE: "contextvars.ContextVar[Optional[QueryTrace]]" = \
    contextvars.ContextVar("srt_active_trace", default=None)

DEFAULT_MAX_EVENTS = 100_000

# ---------------------------------------------------------------------------------
# Governed mark vocabulary.  Marks in the ``perf:`` / ``compile:``
# namespaces are DISPATCH TARGETS: tools/explain_slow.py, trace_report
# --why, and srtop key behavior off these exact names, so they get the
# telemetry.METRICS treatment — declared once in a pure literal, held
# two-way by srtlint's metrics-registry pass (an unregistered governed
# name at an emit site and a registered name nobody emits are both
# findings).  Other mark namespaces (breaker:, query:, trace:, ...)
# stay free-form; only the prefixes below are governed.
# ---------------------------------------------------------------------------------

MARK_PREFIXES = ("perf:", "compile:")

MARKS = (
    ("compile:storm",
     "Recompile-storm detector tripped: non-first-seen compiles in the "
     "trailing window crossed the storm threshold (utils/recorder.py "
     "CompileLedger; compile_storm_active gauge mirrors it)."),
    ("perf:anomaly",
     "Root-cause verdict sealed onto a captured query: the named wait "
     "term ran anomalously over its fingerprint's EWMA baseline "
     "(utils/recorder.py; perf_anomalies_total{term} mirrors it)."),
)


# ---------------------------------------------------------------------------------
# Span vocabulary and the host-time account.  A span's name is
# ``<layer>:<name>``: exactly one colon, and never an object id, a
# literal or a fingerprint (an ``op_id`` is ``ClassName@<id(self)>`` and
# differs in every process: it rides in the QueryTrace event, not in the
# name).  The second column is the account term the span's SELF time is
# charged to on the driving thread; ``op:`` and ``program:`` are
# families (``op:<exec class or MetricSet timer>``, ``program:<name
# given to plan/physical.program>``).  docs/observability.md renders
# this table; tests hold emitted names to it.
# ---------------------------------------------------------------------------------

ACCOUNT_TERMS = ("plan", "admit", "compile", "h2d_wait", "fetch_wait",
                 "dispatch", "result", "host_exec", "unattributed")

SPANS = (
    ("plan:subqueries", "plan",
     "sql/session.py: resolve_subqueries' own work (a subquery's "
     "execution nests inside and is charged to its own terms)."),
    ("plan:overrides", "plan",
     "plan/overrides.py apply_overrides: pushdown, tagging, CBO, "
     "conversion to exec nodes."),
    ("plan:fusion", "plan",
     "plan/overrides.py apply_overrides: insert_coalesce + "
     "plan/fusion.py plan_regions."),
    ("plan:distribute", "plan",
     "sql/session.py _distribute_if_ici (shuffle.mode=ICI)."),
    ("admit:semaphore", "admit",
     "runtime/semaphore.py acquire: waiting for a device permit."),
    ("scan:decode", "host_exec",
     "io/parquet.py, io/sources.py: one decoded table (QueryStats."
     "decode_s sums it over threads)."),
    ("scan:decimal", "host_exec",
     "batch.py decimal_scaled: one decimal column's scaled values read "
     "from its arrow buffer, inside op:scanTime: the convert term on a "
     "producer (QueryStats.decimal_decode_s sums it over threads)."),
    ("scan:wait", "h2d_wait",
     "io/parquet.py, io/sources.py: the scan blocked on its prefetch "
     "thread's next decoded table."),
    ("scan:upload", "dispatch",
     "utils/metrics.py upload: jax.device_put of host arrays "
     "(QueryStats.uploads / upload_bytes / upload_s)."),
    ("pipeline:wait", "h2d_wait",
     "runtime/pipeline.py: the consumer blocked on a staged batch "
     "(QueryStats.h2d_wait_s sums it over threads)."),
    ("pipeline:stage", "host_exec",
     "runtime/pipeline.py: the worker producing one staged batch (its "
     "self time is the worker's host_exec in a resolved wait)."),
    ("fetch:blocking", "fetch_wait",
     "utils/metrics.py fetch: jax.device_get behind the dispatch front "
     "(QueryStats.fetch_wait_s sums it over threads)."),
    ("fetch:async", "fetch_wait",
     "utils/metrics.py FetchFuture.result: what is left of a copy "
     "started earlier."),
    ("fetch:start_copies", "dispatch",
     "utils/metrics.py _start_copies: copy_to_host_async on every leaf."),
    ("eager:gather", "dispatch",
     "ops/batch_utils.py gather: one eager x[indices] per array of a "
     "batch, each a jitted dispatch of its own (sort, top-k, window)."),
    ("result:arrow", "result",
     "batch.py _to_arrow_finish: host arrays to a pyarrow table."),
    ("result:concat", "result",
     "plan/physical.py CollectExec: pa.concat_tables."),
    ("result:rows", "result",
     "sql/dataframe.py collect, sql/session.py _collect_rows: arrow "
     "table to python rows."),
    ("shuffle:write", "host_exec", "parallel/host_shuffle.py."),
    ("shuffle:read", "host_exec", "parallel/host_shuffle.py."),
    ("dcn:fetch", "host_exec", "parallel/dcn.py fragment fetch."),
    ("ici:fragment", "host_exec",
     "parallel/spmd.py: one run of a mesh fragment, the parent of the "
     "four below: what is left is lowering and retry bookkeeping."),
    ("ici:materialize", "host_exec",
     "parallel/spmd.py: the fragment's leaves run single-process and "
     "brought to host Arrow (the executor's spans nest inside and keep "
     "their own terms; QueryStats.ici_materialize_s)."),
    ("ici:feed", "dispatch",
     "parallel/spmd.py: leaves padded to the mesh's static capacity and "
     "placed on it (scan:upload nests inside; QueryStats.ici_feed_s, "
     "ici_feed_bytes)."),
    ("ici:step", "dispatch",
     "parallel/spmd.py: the fragment's mesh programs, one per level of "
     "exchanges (program:ici_fragment_step and the fetch:blocking of "
     "each step's row counts nest inside; QueryStats.ici_step_s, "
     "ici_exchange_bytes)."),
    ("ici:gather", "host_exec",
     "parallel/spmd.py: the fragment's outputs cut to their live rows "
     "on the mesh and brought to a host table (program:"
     "ici_fragment_gather, fetch:blocking and result:arrow nest inside; "
     "QueryStats.ici_gather_s)."),
    ("window:exec", "host_exec",
     "plan/window_exec.py WindowExec: its input concatenated and "
     "compacted, string keys to dictionary codes, the window program, "
     "the gather into sorted order (program:window, eager:gather and "
     "the compact's fetch nest inside and keep their own terms; "
     "QueryStats.window_exec_s, window_rows)."),
    ("expand:project", "dispatch",
     "plan/exec_nodes.py ExpandExec: one projection of one batch "
     "(program:expand_project nests inside; QueryStats.expand_exec_s, "
     "expand_slot_rows)."),
    ("join:pair", "host_exec",
     "plan/join_exec.py: one probe batch joined with its build side, "
     "SortMergeJoinExec._join_pair and the broadcast join's probe of a "
     "streamed batch, its compaction included (program:join_* / bjoin_*, "
     "eager:gather and the fetch of the candidate-pair count nest "
     "inside and keep their own terms; the children's work is outside "
     "it; QueryStats.join_exec_s, join_pairs, join_out_slots, "
     "join_semi_anti)."),
    ("op:", "host_exec",
     "instrument_batches (one pull through an exec node) and "
     "MetricSet.time (op:opTime, op:scanTime, op:buildTime): the "
     "program's own Python inside an operator."),
    ("program:", "dispatch",
     "plan/physical.py program(): one call of a jitted program."),
)

_TERM_OF = {name: term for name, term, _ in SPANS
            if not name.endswith(":")}
_FAMILY_TERM = {name: term for name, term, _ in SPANS
                if name.endswith(":")}


def _term_of(name: str) -> Optional[str]:
    """The account term of a span name (None: transparent, its self time
    stays its parent's).  Family members are memoised: the vocabulary is
    fixed, so the table stays small."""
    try:
        return _TERM_OF[name]
    except KeyError:
        term = _FAMILY_TERM.get(name[:name.find(":") + 1])
        _TERM_OF[name] = term
        return term


# The driving thread's ``h2d_wait`` resolved: what the producer it waited
# on (and, through that producer's own waits, the producers upstream of
# it) was doing meanwhile.  ``handoff`` is producer time under no span
# (queue hand-off, wake-up, the GIL, thread start) and whatever of the
# wait the producers' totals did not cover.  QueryStats.acct_h2d_<t>_s.
RESOLVED_TERMS = ("decode", "convert", "upload", "dispatch", "fetch_wait",
                  "host_exec", "handoff")
(_DECODE, _CONVERT, _UPLOAD, _DISPATCH, _FETCH_WAIT, _HOST_EXEC,
 _HANDOFF) = range(len(RESOLVED_TERMS))
_WAIT = len(RESOLVED_TERMS)  # a producer's own wait: resolved upstream

_SLOT_OF = {"scan:decode": _DECODE, "op:scanTime": _CONVERT,
            "scan:decimal": _CONVERT, "scan:upload": _UPLOAD}
_SLOT_OF_TERM = {"dispatch": _DISPATCH, "fetch_wait": _FETCH_WAIT}


def _slot_of(name: str) -> Optional[int]:
    """The resolved term a producer's span charges its self time to
    (None: transparent).  Memoised like :func:`_term_of`."""
    try:
        return _SLOT_OF[name]
    except KeyError:
        term = _term_of(name)
        slot = None if term is None else _SLOT_OF_TERM.get(term, _HOST_EXEC)
        _SLOT_OF[name] = slot
        return slot


class _Account:
    """One query's host-time account, kept on the driving thread.

    Self time without a stack: ``child`` is the time that spans closed
    so far cover under the span now open.  A span saves it on entry and
    zeroes it; on exit its self time is its duration less ``child``, and
    ``child`` becomes the saved value plus its whole duration.  ``h2d``
    holds the ``h2d_wait`` term resolved into :data:`RESOLVED_TERMS`."""

    __slots__ = ("tid", "t0", "child", "excluded", "terms", "h2d")

    def __init__(self):
        self.tid = threading.get_ident()
        self.child = 0.0
        self.excluded = 0.0
        self.terms = dict.fromkeys(ACCOUNT_TERMS[:-1], 0.0)
        self.h2d = [0.0] * len(RESOLVED_TERMS)
        self.t0 = _pc()


class _Producer:
    """The running account of a thread that stages for a query.

    A state machine under the same self-time rule: at every span
    boundary on the thread the segment since the last one (``last``) is
    added to ``totals[slot]``, the slot of the innermost open span, or
    ``_HANDOFF`` under none.  While the thread itself waits on its
    upstream (``slot == _WAIT``) the segment is resolved through that
    upstream's totals (``on``, read as ``snap`` at the wait's entry).
    Only the thread writes; :meth:`read` may be called from any thread
    without a lock, and a torn read misplaces at most one segment."""

    __slots__ = ("tid", "name", "last", "slot", "totals", "on", "snap")

    def __init__(self, name: str):
        self.tid = None  # the thread's, once it runs
        self.name = name
        self.totals = [0.0] * len(RESOLVED_TERMS)
        self.slot = _HANDOFF
        self.on = self.snap = None
        self.last = _pc()

    def read(self, now: float) -> List[float]:
        """The totals as they are at ``now``, the open segment included."""
        tot = self.totals[:]
        slot, last = self.slot, self.last
        if slot == _WAIT:
            for i, v in enumerate(_resolve(self.on, self.snap, now,
                                           now - last)):
                tot[i] += v
        else:
            tot[slot] += now - last
        return tot

    def open(self, slot: Optional[int], t0: float, on, snap) -> int:
        prev = self.slot
        self.totals[prev] += t0 - self.last
        self.last = t0
        if on is not None:
            self.on, self.snap = on, snap
            self.slot = _WAIT
        elif slot is not None:
            self.slot = slot
        return prev

    def close(self, t1: float, prev: int, on, snap) -> Optional[List[float]]:
        own = t1 - self.last
        parts = None
        if on is None:
            self.totals[self.slot] += own
        else:
            parts = _resolve(on, snap, t1, own)
            for i, v in enumerate(parts):
                self.totals[i] += v
        self.last = t1
        self.slot = prev
        return parts


def _resolve(on: _Producer, snap: List[float], now: float,
             dur: float) -> List[float]:
    """``dur`` seconds of a wait on ``on`` since its totals read ``snap``,
    split by what ``on`` did meanwhile; the parts sum to ``dur``."""
    if dur <= 0.0:
        return [0.0] * len(RESOLVED_TERMS)
    parts = [c - s if c > s else 0.0 for c, s in zip(on.read(now), snap)]
    covered = sum(parts)
    if covered > dur:  # a torn read, or the reads' clocks apart
        k = dur / covered
        parts = [p * k for p in parts]
        covered = dur
    parts[_HANDOFF] += dur - covered
    return parts


_ACCT: "contextvars.ContextVar[Optional[_Account | _Producer]]" = \
    contextvars.ContextVar("srt_query_account", default=None)


def _my_account():
    """The account this thread keeps: the query's (driving thread), a
    producer's (a staging thread), or None."""
    acct = _ACCT.get()
    if acct is not None and acct.tid == threading.get_ident():
        return acct
    return None


def start_producer(target: Callable[[], None], name: str) \
        -> Optional[_Producer]:
    """Run ``target`` on a new daemon thread named ``name``, in a COPY of
    the caller's context (its spans join the caller's trace, its counters
    the caller's QueryStats), as the producer of a hand-off: the thread
    keeps a running account, returned here, that a consumer's wait on
    the hand-off reads (``span(..., on=producer)``).  None, and no
    account, where no query account is open in the caller's context."""
    cctx = contextvars.copy_context()
    prod = _Producer(name) if _ACCT.get() is not None else None

    def run():
        if prod is not None:
            prod.tid = threading.get_ident()
            _ACCT.set(prod)
        target()

    threading.Thread(target=lambda: cctx.run(run), daemon=True,
                     name=name).start()
    return prod


@contextlib.contextmanager
def account(stats):
    """Open the query's host-time account on THIS thread, the driving
    thread; on exit write the nine terms and the wall into ``stats``
    (``QueryStats.acct_*_s``, ``query_wall_s``).  One per query: a
    nested sub-execution (a scalar subquery) runs inside its parent's
    scope (``Session._query_scope``), so its spans are charged here and
    its wall is not counted twice."""
    acct = _Account()
    tok = _ACCT.set(acct)
    try:
        yield
    finally:
        wall = max(0.0, _pc() - acct.t0 - acct.excluded)
        try:
            _ACCT.reset(tok)
        except ValueError:  # generator-held scope closed out of order
            _ACCT.set(None)
        rest = wall
        for term, v in acct.terms.items():
            setattr(stats, f"acct_{term}_s",
                    getattr(stats, f"acct_{term}_s") + v)
            rest -= v
        stats.acct_unattributed_s += max(0.0, rest)
        stats.query_wall_s += wall
        for term, v in zip(RESOLVED_TERMS, acct.h2d):
            setattr(stats, f"acct_h2d_{term}_s",
                    getattr(stats, f"acct_h2d_{term}_s") + v)


def charge(term: str, dur: float) -> None:
    """Charge an interval that someone else measured on this thread (a
    backend compile, reported by jax.monitoring when it ends) to
    ``term``, and take it out of the open span's self time.  On a
    producer's thread it is ``host_exec``."""
    acct = _my_account()
    if acct is None:
        return
    if acct.__class__ is _Account:
        acct.terms[term] += dur
        acct.child += dur
    else:
        acct.totals[_HOST_EXEC] += dur
        acct.last += dur


@contextlib.contextmanager
def suspended():
    """Stop the account's clock while a streaming execution's consumer
    holds the thread (the ``yield`` of a generator-shaped entry point):
    that time is neither the query's wall nor any span's."""
    acct = _my_account()
    if acct is None or acct.__class__ is not _Account:
        yield
        return
    t0 = _pc()
    try:
        yield
    finally:
        dt = _pc() - t0
        acct.excluded += dt
        acct.child += dt


class _Span:
    """A live timed span: a profiler annotation, one QueryTrace event on
    exit, and a charge to the account its thread keeps: the query's on
    the driving thread, a producer's on a staging thread.  A wait on a
    hand-off names its producer (``on``) and is resolved through it.
    ``dur`` holds its seconds once it has closed."""

    __slots__ = ("_op", "_name", "_cat", "_ann_name", "_args", "_t0",
                 "_ann", "_acct", "_saved", "_on", "_snap", "dur")

    def __init__(self, op_id, name, cat, ann=None, on=None):
        self._op = op_id
        self._name = name
        self._cat = cat
        self._ann_name = ann or name
        self._args = None
        self._on = on

    def set(self, **attrs):
        if self._args is None:
            self._args = {}
        self._args.update(attrs)
        return self

    def __enter__(self):
        acct = self._acct = _my_account()
        t0 = self._t0 = _pc()
        if acct is not None:
            on = self._on
            snap = self._snap = None if on is None else on.read(t0)
            if acct.__class__ is _Account:
                self._saved = acct.child
                acct.child = 0.0
            else:
                self._saved = acct.open(_slot_of(self._ann_name), t0, on,
                                        snap)
        self._ann = _Annotation(self._ann_name)
        self._ann.__enter__()
        return self

    def __exit__(self, et=None, ev=None, tb=None):
        self._ann.__exit__(et, ev, tb)
        t1 = _pc()
        dur = self.dur = t1 - self._t0
        acct = self._acct
        parts = None
        if acct is not None:
            if acct.__class__ is _Account:
                term = _term_of(self._ann_name)
                if term is None:
                    acct.child += self._saved
                else:
                    own = dur - acct.child
                    acct.terms[term] += own
                    acct.child = self._saved + dur
                    if self._on is not None:
                        parts = _resolve(self._on, self._snap, t1, own)
                        h2d = acct.h2d
                        for i, v in enumerate(parts):
                            h2d[i] += v
            else:
                parts = acct.close(t1, self._saved, self._on, self._snap)
        # a pull that only found its stream ended leaves no event
        if et is not StopIteration:
            tr = _ACTIVE.get()
            if tr is not None:
                args = self._args
                if parts is not None:
                    args = dict(args or (), on=self._on.name,
                                **dict(zip(RESOLVED_TERMS, parts)))
                tr.add_event(self._op, self._name, self._cat, self._t0,
                             dur, args)
        return False


class QueryTrace:
    """The span tree + flat event log of one query execution.

    Operator structure comes from :meth:`register_plan` (one span node per
    physical plan node, children mirroring the plan); timed events arrive
    through :meth:`add_event` from any thread.  ``finish`` folds the
    query's accumulated per-operator :class:`..utils.metrics.MetricSet`
    values and the query-scoped ``QueryStats`` snapshot into the tree.
    """

    def __init__(self, label: str, max_events: int = DEFAULT_MAX_EVENTS):
        self.label = label
        # cross-rank identity: DCN request frames carry it so remote
        # serve-side work (fetches, re-pulls) lands in per-rank trace
        # SHARDS beside this trace, stitched back into one Perfetto
        # tree by ``tools/trace_report.py --stitch``
        import uuid as _uuid
        self.trace_id = _uuid.uuid4().hex[:16]
        self.t0 = _pc()
        self.wall_start = time.time()
        self.t_end: Optional[float] = None
        # span status of the whole query: 'ok' | 'degraded' |
        # 'cancelled' | 'deadline' | 'faulted' | 'resubmitted' | 'error'
        # — the session sets it from the exception that ended execution
        # (and the scheduler promotes 'faulted' to 'resubmitted' when it
        # requeues the query), so an aborted query's trace says so
        self.status = "ok"
        self.max_events = max_events
        self.dropped = 0
        # flat event log: (op_id, name, cat, rel_t0_s, dur_s, tid, args)
        self.events: List[tuple] = []
        self.ops: Dict[str, dict] = {}
        self.roots: List[dict] = []
        self.attrs: Dict[str, object] = {}
        self._lock = threading.Lock()
        self._tids: Dict[int, tuple] = {}  # thread ident -> (tid, name)

    # -- structure ----------------------------------------------------------------
    def register_plan(self, root) -> None:
        """Build the span tree from a physical plan: one node per operator,
        children mirroring the plan tree."""
        def walk(node, parent):
            entry = {"op_id": node.op_id, "name": type(node).__name__,
                     "desc": node.node_desc(), "children": [],
                     "metrics": {}}
            self.ops[node.op_id] = entry
            (self.roots if parent is None
             else parent["children"]).append(entry)
            for c in getattr(node, "children", ()):
                walk(c, entry)
        walk(root, None)

    def _ensure_op(self, op_id: str, name: str) -> dict:
        """Late registration for operators created at runtime (AQE
        re-plans, staged join inputs): they attach at the root, flagged."""
        entry = self.ops.get(op_id)
        if entry is None:
            entry = {"op_id": op_id, "name": name, "desc": name,
                     "children": [], "metrics": {}, "runtime": True}
            with self._lock:
                self.ops[op_id] = entry
                self.roots.append(entry)
        return entry

    # -- events -------------------------------------------------------------------
    def _tid(self) -> int:
        ident = threading.get_ident()
        e = self._tids.get(ident)
        if e is None:
            with self._lock:
                e = self._tids.get(ident)
                if e is None:
                    e = (len(self._tids) + 1,
                         threading.current_thread().name)
                    self._tids[ident] = e
        return e[0]

    def add_event(self, op_id, name, cat, t0, dur, args=None) -> None:
        if len(self.events) >= self.max_events:
            if self.dropped == 0:
                # a truncated trace must be VISIBLY truncated on the
                # timeline, not just in otherData: the first overflow
                # appends a single forced trace:events_dropped mark
                # (the only event allowed past the cap)
                with self._lock:
                    if self.dropped == 0:
                        self.dropped = 1
                        self.events.append((
                            None, "trace:events_dropped", "mark",
                            max(0.0, t0 - self.t0), 0.0, self._tid(),
                            {"max_events": self.max_events}))
                        return
            self.dropped += 1
            return
        self.events.append((op_id, name, cat, max(0.0, t0 - self.t0),
                            max(0.0, dur), self._tid(), args))

    # -- lifecycle ----------------------------------------------------------------
    @property
    def duration_s(self) -> float:
        return (self.t_end if self.t_end is not None else _pc()) - self.t0

    def set_status(self, status: str) -> None:
        self.status = status

    def finish(self, metrics: Optional[dict] = None,
               stats: Optional[dict] = None) -> None:
        """Close the clock and absorb the query's accumulated accounting:
        per-operator MetricSet values become span attributes; the
        query-scoped QueryStats snapshot becomes root attributes."""
        if self.t_end is None:
            self.t_end = _pc()
        if self.dropped:
            # drop accounting reaches the live metrics registry too, so
            # a scraper sees truncation without opening the trace file
            from . import telemetry
            telemetry.count("trace_events_dropped_total", self.dropped)
        if stats:
            self.attrs.update(stats)
        for op_id, mset in (metrics or {}).items():
            entry = self._ensure_op(op_id, op_id.split("@", 1)[0])
            try:
                mset._resolve()  # deferred device counters land on host
            except Exception:  # fault-ok (best-effort metrics on a dead backend)
                pass
            entry["metrics"].update(
                {k: (round(v, 6) if isinstance(v, float) else v)
                 for k, v in mset.values.items()})

    # -- export -------------------------------------------------------------------
    def to_chrome(self) -> dict:
        """Chrome trace event format (Perfetto / chrome://tracing), with a
        ``spanTree`` extension key carrying the plan-shaped span tree."""
        pid = 1
        evs: List[dict] = [
            {"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
             "args": {"name": f"spark_rapids_tpu {self.label}"}},
            {"ph": "M", "pid": pid, "tid": 0, "name": "thread_name",
             "args": {"name": "query"}},
        ]
        for tid, tname in sorted(self._tids.values()):
            evs.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_name", "args": {"name": tname}})
        qargs = dict(sorted(self.attrs.items()))
        qargs["status"] = self.status
        evs.append({"ph": "X", "pid": pid, "tid": 0, "name": self.label,
                    "cat": "query", "ts": 0.0,
                    "dur": round(self.duration_s * 1e6, 1),
                    "args": qargs})
        for op_id, name, cat, ts, dur, tid, args in self.events:
            a = {"op": op_id} if op_id else {}
            if args:
                a.update(args)
            evs.append({"ph": "X", "pid": pid, "tid": tid, "name": name,
                        "cat": cat, "ts": round(ts * 1e6, 1),
                        "dur": round(dur * 1e6, 1), "args": a})
        return {
            "traceEvents": evs,
            "displayTimeUnit": "ms",
            "otherData": {"label": self.label,
                          "status": self.status,
                          "trace_id": self.trace_id,
                          "dropped_events": self.dropped,
                          "wall_s": round(self.duration_s, 6),
                          "wall_start_epoch_s": round(self.wall_start, 6)},
            "spanTree": self.roots,
        }

    def write(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


def merge_chrome(traces) -> dict:
    """Merge several queries' traces into ONE Chrome-trace dict: each
    query becomes its own pid, with event timestamps offset to a common
    epoch so concurrent queries genuinely overlap on the Perfetto
    timeline.  The per-query plan-shaped trees ride in a ``spanTrees``
    list (``tools/trace_report.py`` renders per-query sections plus a
    contention summary from this form)."""
    traces = [t for t in traces if t is not None]
    if not traces:
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "otherData": {"label": "merged", "queries": 0},
                "spanTrees": []}
    epoch = min(t.wall_start for t in traces)
    evs: List[dict] = []
    span_trees: List[dict] = []
    for i, tr in enumerate(sorted(traces, key=lambda t: t.wall_start), 1):
        sub = tr.to_chrome()
        off = round((tr.wall_start - epoch) * 1e6, 1)
        for e in sub["traceEvents"]:
            e = dict(e)
            e["pid"] = i
            if e.get("ph") == "X":
                e["ts"] = round(e["ts"] + off, 1)
            evs.append(e)
        span_trees.append({"label": tr.label, "pid": i,
                           "status": tr.status,
                           "start_offset_s": round(tr.wall_start - epoch, 6),
                           "wall_s": round(tr.duration_s, 6),
                           "dropped_events": tr.dropped,
                           "roots": sub["spanTree"]})
    return {"traceEvents": evs, "displayTimeUnit": "ms",
            "otherData": {"label": "merged", "queries": len(span_trees),
                          "wall_start_epoch_s": round(epoch, 3)},
            "spanTrees": span_trees}


def write_merged(traces, path: str) -> str:
    with open(path, "w") as f:
        json.dump(merge_chrome(traces), f)
    return path


# ---------------------------------------------------------------------------------
# Module-level API: the engine's one tracing entry surface.
# ---------------------------------------------------------------------------------

def active() -> Optional[QueryTrace]:
    return _ACTIVE.get()


@contextlib.contextmanager
def query_trace(label: str, enabled: bool = True,
                max_events: int = DEFAULT_MAX_EVENTS):
    """Activate a query trace for the scope (contextvar-carried, so worker
    threads running a copied context join it).  ``enabled=False`` — or an
    already-active trace (a nested sub-execution) — yields None and the
    scope is a pure pass-through."""
    if not enabled or _ACTIVE.get() is not None:
        yield None
        return
    tr = QueryTrace(label, max_events=max_events)
    tok = _ACTIVE.set(tr)
    try:
        yield tr
    finally:
        try:
            _ACTIVE.reset(tok)
        except ValueError:
            # interleaved streaming executions can violate token LIFO
            # (generator-held scopes); clearing is the safe fallback
            _ACTIVE.set(None)
        if tr.t_end is None:
            tr.t_end = _pc()


def span(op_id: Optional[str], name: str, cat: str = "phase",
         ann: Optional[str] = None, on: Optional[_Producer] = None) -> _Span:
    """THE timed span: a context manager attributed to ``op_id`` (None
    for query-level work), live at every metrics level and with or
    without a QueryTrace.  ``name`` is the QueryTrace event's name and,
    unless ``ann`` gives another, the profiler annotation's: one of
    :data:`SPANS`.  ``on`` is the producer a wait on a hand-off waits
    for (:func:`start_producer`): the wait is resolved through it, and
    its QueryTrace event carries the producer's name and the parts."""
    return _Span(op_id, name, cat, ann, on)


def record(op_id: Optional[str], name: str, cat: str, t0: float,
           dur: float, **args) -> None:
    """Record an interval that someone else measured (perf_counter
    timebase): the compile listener's, the scheduler's queue wait.
    Everything the program times itself is a live :func:`span`."""
    tr = _ACTIVE.get()
    if tr is not None:
        tr.add_event(op_id, name, cat, t0, dur, args or None)


def mark(op_id: Optional[str], name: str, cat: str = "mark",
         **args) -> None:
    """Record an instant event (zero duration) with attributes."""
    tr = _ACTIVE.get()
    if tr is not None:
        tr.add_event(op_id, name, cat, _pc(), 0.0, args or None)


@contextlib.contextmanager
def region_span(op_id: Optional[str], args_out: Optional[dict] = None):
    """A ``fusion:region`` span wrapping a fused region's whole
    execution (plan/fusion.FusedRegionExec).  Member-op spans recorded
    inside keep their own attribution — profiled EXPLAIN and
    trace_report still see per-op time — while this span carries the
    region's summary attributes.  ``args_out`` is filled IN by the
    caller before the scope closes (member count, prologue syncs,
    compiles); it lands as the span's args.  The clock lives here so
    the exec-node layer stays inside the span API."""
    t0 = _pc()
    try:
        yield
    finally:
        record(op_id, "fusion:region", "fusion", t0, _pc() - t0,
               **(args_out or {}))


# ---------------------------------------------------------------------------------
# Cross-rank trace shards: remote work done ON BEHALF of another rank's
# traced query (a peer server streaming shuffle fragments to it) lands
# in a per-rank shard file beside the query trace, keyed by the
# requester's trace id — ``tools/trace_report.py --stitch`` merges the
# shards into ONE Perfetto tree parented under the query root.
# ---------------------------------------------------------------------------------

_SHARD_LOCK = threading.Lock()


def trace_context() -> Optional[list]:
    """The active trace's cross-rank context — ``[trace_id, label]`` —
    for stamping onto DCN request frames; None when untraced (remote
    sides then record nothing)."""
    tr = _ACTIVE.get()
    if tr is None:
        return None
    return [tr.trace_id, tr.label]


def _shard_dir() -> str:
    from ..config import TpuConf
    return TpuConf()["spark.rapids.tpu.sql.trace.dir"]


def shard_path(trace_id: str, rank: int, directory: str) -> str:
    import os
    return os.path.join(directory, f"{trace_id}.rank{rank}.shard.jsonl")


def shard_record(trace_id: str, rank: int, name: str, cat: str,
                 t_wall: float, dur_s: float, **args) -> None:
    """Append one serve-side span to this rank's shard for the remote
    query ``trace_id``.  Timestamps are WALL epoch seconds (the only
    clock two hosts share well enough for a merged timeline); no-op
    when ``sql.trace.dir`` is unset — shards only exist where traces
    are being dumped."""
    directory = _shard_dir()
    if not directory or not trace_id:
        return
    import os
    rec = {"trace_id": trace_id, "rank": int(rank), "name": name,
           "cat": cat, "t_wall": round(t_wall, 6),
           "dur_s": round(max(0.0, dur_s), 6)}
    if args:
        rec["args"] = args
    line = json.dumps(rec, sort_keys=True)
    path = shard_path(trace_id, rank, directory)
    with _SHARD_LOCK:
        os.makedirs(directory, exist_ok=True)
        with open(path, "a") as f:
            f.write(line + "\n")


def shard_paths(trace_id: str, directory: str) -> List[str]:
    """Every rank shard written for ``trace_id`` under ``directory``
    (the stitch tool's discovery step)."""
    import glob
    import os
    return sorted(glob.glob(os.path.join(
        directory, f"{trace_id}.rank*.shard.jsonl")))


# ---------------------------------------------------------------------------------
# Operator instrumentation: every TpuExec.execute is routed through here
# (plan/physical.py wraps subclasses at class-definition time).
# ---------------------------------------------------------------------------------

def instrument_batches(op_id: str, op_name: str, metrics,
                       it: Iterator) -> Iterator:
    """Wrap an operator's batch iterator: each pull is timed on the thread
    it runs on (operator span when a trace is active) and uniform
    ``outputRows`` / ``outputBatches`` / ``outputBytes`` / ``produceTimeS``
    counters accumulate into the operator's MetricSet — the profiled
    EXPLAIN surface, populated for EVERY operator with no opt-out."""
    ann = "op:" + op_name
    try:
        while True:
            # the engine's universal cancellation checkpoint: every
            # batch pull through every operator passes here, so a
            # cancelled/expired query aborts at the next batch boundary
            # on whatever thread is driving it (one ContextVar read when
            # no control is installed)
            _cancel.check()
            sp = _Span(op_id, op_name, "operator", ann)
            try:
                with sp:
                    b = next(it)
                    rows = getattr(b, "num_rows", 0)
                    if _ACTIVE.get() is not None:
                        sp.set(rows=rows)
            except StopIteration:
                return
            if metrics is not None:
                v = metrics.values
                v["outputRows"] += rows
                v["outputBatches"] += 1
                size_fn = getattr(b, "device_size_bytes", None)
                if size_fn is not None:
                    v["outputBytes"] += size_fn()
                v["produceTimeS"] += sp.dur
            yield b
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


# ---------------------------------------------------------------------------------
# Profiled EXPLAIN: the plan tree re-rendered with accumulated metrics
# (the reference's SQL-UI per-operator metrics view analog).
# ---------------------------------------------------------------------------------

def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"


def _fmt_metric(name: str, v) -> str:
    if isinstance(v, float):
        if name.lower().endswith(("time", "times", "_s", "wait_s")) \
                or "Time" in name:
            return f"{v * 1e3:.1f}ms"
        return f"{v:.4g}"
    return str(v)


def render_profiled(root, metrics: Dict[str, object]) -> str:
    """Render the physical plan tree annotated with each operator's
    accumulated metrics.  Every node gets a metrics line — rows, bytes,
    batches and wall time come from the span instrumentation, followed by
    the operator's own counters/timers."""
    lines: List[str] = []
    seen = set()

    def node_metrics_line(op_id: str) -> str:
        mset = metrics.get(op_id)
        if mset is None:
            return "rows=0 batches=0 bytes=0B time=0.0ms (not executed)"
        try:
            mset._resolve()
        except Exception:  # fault-ok (best-effort metrics on a dead backend)
            pass
        v = dict(mset.values)
        rows = int(v.pop("outputRows", 0))
        batches = int(v.pop("outputBatches", 0))
        nbytes = v.pop("outputBytes", 0.0)
        t = v.pop("produceTimeS", 0.0)
        head = (f"rows={rows} batches={batches} "
                f"bytes={_fmt_bytes(nbytes)} time={t * 1e3:.1f}ms")
        rest = " ".join(f"{k}={_fmt_metric(k, val)}"
                        for k, val in sorted(v.items()))
        return head + ((" | " + rest) if rest else "")

    def walk(node, indent):
        seen.add(node.op_id)
        pad = "  " * indent
        lines.append(pad + ("+- " if indent else "") + node.node_desc())
        lines.append(pad + ("|    " if indent else "  ")
                     + node_metrics_line(node.op_id))
        for c in node.children:
            walk(c, indent + 1)

    walk(root, 0)
    extras = [op for op in metrics if op not in seen]
    if extras:
        lines.append("runtime operators (created during execution):")
        for op in sorted(extras):
            lines.append(f"  {op}: {node_metrics_line(op)}")
    return "\n".join(lines)
