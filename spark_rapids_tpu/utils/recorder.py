"""Performance flight recorder: always-on tail-sampled trace capture.

Tracing (utils/tracing.py) answers "where did the time go" for a query
someone REMEMBERED to trace.  Production outliers don't announce
themselves in advance: the p99 straggler, the recompile storm after a
rolling restart, the one tenant whose queries suddenly wait in the
admission queue — by the time anyone flips ``sql.trace.enabled`` the
evidence is gone.  This module keeps tracing armed for every query and
makes retention, not capture, the decision:

  * **tail-sampled ring** — every completed query's span tree is
    OFFERED to a bounded per-process ring
    (``spark.rapids.tpu.recorder.{enabled,maxQueries,maxBytes}``).  A
    retention policy keeps the interesting tail: SLO violations, any
    non-ok outcome (faulted / stalled / degraded / drained / ...), the
    top-k slowest per statement fingerprint over a trailing window,
    and first-seen fingerprints.  The boring median is dropped
    (counted, never silently);
  * **seal handshake** — a scheduler query's verdict (SLO latency, ok)
    lives on the scheduler side while its trace finishes on the
    session side, and result STREAMING can hold the trace open past
    the scheduler's completion.  Whichever side arrives second seals
    the capture; un-sealed controls are a leak the drain audit counts
    (``pending_seals``);
  * **compile ledger** — the ``jax.monitoring`` compile listener
    (utils/metrics.py) feeds a per-statement-fingerprint ledger
    (count, seconds, trigger: first_seen / shape_change /
    post_restart / cache_evict / store_hit / prewarm) with a
    recompile-storm detector
    (``compile_storm_active`` gauge + ``compile:storm`` mark).  This
    is the traffic×compile profile the ROADMAP's persistent compile
    cache needs to prioritize precompilation;
  * **root-cause attribution** — at seal time the query decomposes
    into canonical wait terms (:data:`TERMS`), each compared against
    the fingerprint's EWMA baseline; a dominant anomalous term gets a
    typed verdict stamped into the trace (``perf_verdict`` attr +
    ``perf:anomaly`` event + ``perf_anomalies_total{term}``), so
    ``tools/explain_slow.py`` can answer "why was THIS query slow"
    offline from the dump alone.

Everything here is bounded and lock-cheap: one process lock held for
dict/deque updates only; trace file dumps happen outside it.
"""

from __future__ import annotations

import hashlib
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

__all__ = ["TERMS", "FlightRecorder", "CompileLedger", "offer",
           "outcome", "configure", "snapshot", "pending_seals",
           "compile_note", "compile_evicted", "compile_prime",
           "compile_store_known", "compile_prewarm_scope",
           "decompose", "decompose_chrome", "judge",
           "recorder", "compile_ledger", "reset_for_tests"]

_pc = time.perf_counter

# ---------------------------------------------------------------------------------
# Canonical wait-term vocabulary: the decomposition explain_slow,
# trace_report --why, and the perf_anomalies_total counter all share.
# ---------------------------------------------------------------------------------

# ``queue_wait`` is the scheduler's, before the query's wall starts.  The
# nine from ``plan`` to ``unattributed`` are the query's host-time account
# (utils/tracing.account, ``QueryStats.acct_*_s``): disjoint shares of the
# driving thread's time that sum to the wall.  ``shuffle`` / ``spill`` /
# ``stream_spool`` are span seconds of any thread, kept beside the account
# for the verdicts that name them (they overlap ``host_exec``).
TERMS = ("queue_wait", "plan", "admit", "compile", "h2d", "dispatch",
         "fetch_wait", "host_exec", "result", "unattributed",
         "shuffle", "spill", "stream_spool")

# account term -> (QueryStats field, the all-thread stat an older dump
# without the account carries for it)
_ACCOUNT_FIELDS = (
    ("plan", "acct_plan_s", None),
    ("admit", "acct_admit_s", None),
    ("compile", "acct_compile_s", "compile_s"),
    ("h2d", "acct_h2d_wait_s", "h2d_wait_s"),
    ("dispatch", "acct_dispatch_s", None),
    ("fetch_wait", "acct_fetch_wait_s", "fetch_wait_s"),
    ("host_exec", "acct_host_exec_s", None),
    ("result", "acct_result_s", None),
    ("unattributed", "acct_unattributed_s", None),
)

# a term is anomalous when it exceeds BOTH a ratio over the fingerprint's
# EWMA baseline and an absolute floor (sub-50ms jitter is not a verdict)
ANOMALY_RATIO = 2.0
ANOMALY_FLOOR_S = 0.05
EWMA_ALPHA = 0.3
MIN_BASELINE_SAMPLES = 2

# retention: top-k slowest per fingerprint over a trailing sample window
TOP_K = 3
FP_WINDOW = 32

# recompile-storm detector: this many non-first-seen compiles inside the
# trailing window trips the gauge; half that clears it
STORM_WINDOW_S = 30.0
STORM_THRESHOLD = 8

_CONF_ENABLED = "spark.rapids.tpu.recorder.enabled"
_CONF_MAX_QUERIES = "spark.rapids.tpu.recorder.maxQueries"
_CONF_MAX_BYTES = "spark.rapids.tpu.recorder.maxBytes"
_CONF_TRACE_DIR = "spark.rapids.tpu.sql.trace.dir"


# ---------------------------------------------------------------------------------
# Term decomposition (shared with tools/explain_slow.py)
# ---------------------------------------------------------------------------------

def decompose(attrs: Dict[str, object],
              events) -> Dict[str, float]:
    """Decompose one query into the canonical wait terms (seconds).

    ``attrs`` is the trace's root attribute dict: the QueryStats
    snapshot absorbed at finish carries the query's host-time account,
    whose nine terms sum to ``query_wall_s``; ``events`` is an iterable
    of ``(name, cat, ts_s, dur_s, tid)`` tuples for what the account
    does not break out (shuffle / spill / server span seconds)."""
    def att(key):
        try:
            return max(0.0, float(attrs.get(key, 0.0) or 0.0))
        except (TypeError, ValueError):
            return 0.0

    shuffle = spill = stream = 0.0
    for name, cat, ts, dur, tid in events:
        if dur <= 0.0:
            continue
        if cat == "shuffle":
            shuffle += dur
        elif cat == "server":
            stream += dur
        if "spill" in name:
            spill += dur
    closed = "query_wall_s" in attrs
    terms = {"queue_wait": att("queue_wait_s")}
    for term, field, older in _ACCOUNT_FIELDS:
        terms[term] = att(field if closed or older is None else older)
    terms["shuffle"] = round(shuffle, 6)
    terms["spill"] = round(spill, 6)
    terms["stream_spool"] = round(stream, 6)
    return terms


def _trace_events(tr):
    """QueryTrace flat events -> the decompose() event shape."""
    for _op, name, cat, ts, dur, tid, _args in tr.events:
        yield name, cat, ts, dur, tid


def decompose_chrome(doc: dict) -> Dict[str, float]:
    """Same decomposition from a dumped Chrome-trace JSON document
    (``tools/explain_slow.py`` runs this offline)."""
    attrs: Dict[str, object] = {}
    events = []
    for e in doc.get("traceEvents", ()):
        if e.get("ph") != "X":
            continue
        if e.get("cat") == "query":
            attrs = dict(e.get("args") or {})
            continue
        events.append((e.get("name", ""), e.get("cat", ""),
                       float(e.get("ts", 0.0)) / 1e6,
                       float(e.get("dur", 0.0)) / 1e6,
                       int(e.get("tid", 0))))
    return decompose(attrs, events)


def judge(terms: Dict[str, float], baseline: Dict[str, float],
          samples: int) -> Tuple[Optional[str], Dict[str, float]]:
    """Compare each term against its EWMA baseline; return the dominant
    anomalous term (None when everything is in line, or the baseline
    is too young to judge) plus the per-term excess seconds."""
    excess: Dict[str, float] = {}
    if samples < MIN_BASELINE_SAMPLES:
        return None, excess
    for term in TERMS:
        v = terms.get(term, 0.0)
        base = baseline.get(term, 0.0)
        if v > max(base * ANOMALY_RATIO, base + ANOMALY_FLOOR_S):
            excess[term] = round(v - base, 6)
    if not excess:
        return None, excess
    return max(excess, key=excess.get), excess


# ---------------------------------------------------------------------------------
# Capture ring
# ---------------------------------------------------------------------------------

class _Capture:
    """One retained query: the full trace plus its seal verdict."""

    __slots__ = ("trace", "capture_id", "fingerprint", "reason",
                 "status", "wall_s", "latency_s", "terms", "verdict",
                 "approx_bytes", "path", "sealed_wall")

    def __init__(self, trace, fingerprint, reason, status, wall_s,
                 latency_s, terms, verdict):
        self.trace = trace
        self.capture_id = trace.trace_id
        self.fingerprint = fingerprint
        self.reason = reason
        self.status = status
        self.wall_s = wall_s
        self.latency_s = latency_s
        self.terms = terms
        self.verdict = verdict
        # conservative per-event estimate: an event tuple plus its JSON
        # rendering; the ring bound is on this estimate, not a deep
        # sizeof walk (which would cost more than the capture)
        self.approx_bytes = 200 * (len(trace.events) + 8)
        self.path = ""
        self.sealed_wall = time.time()

    def summary(self) -> Dict[str, object]:
        return {
            "capture_id": self.capture_id,
            "label": self.trace.label,
            "fingerprint": self.fingerprint[:16],
            "reason": self.reason,
            "status": self.status,
            "wall_ms": round(self.wall_s * 1e3, 1),
            "latency_ms": (round(self.latency_s * 1e3, 1)
                           if self.latency_s is not None else None),
            "verdict": self.verdict or "",
            "terms_ms": {k: round(v * 1e3, 1)
                         for k, v in self.terms.items() if v > 0},
            "path": self.path,
        }


class _FpProfile:
    """Per-fingerprint trailing state: recent walls (top-k retention)
    and per-term EWMA baselines (anomaly judging)."""

    __slots__ = ("walls", "baseline", "samples")

    def __init__(self):
        self.walls: deque = deque(maxlen=FP_WINDOW)
        self.baseline: Dict[str, float] = {}
        self.samples = 0

    def is_top_k(self, wall_s: float) -> bool:
        if len(self.walls) < TOP_K:
            return True
        return wall_s > sorted(self.walls, reverse=True)[TOP_K - 1]

    def update(self, wall_s: float, terms: Dict[str, float]) -> None:
        self.walls.append(wall_s)
        for term, v in terms.items():
            old = self.baseline.get(term)
            self.baseline[term] = (v if old is None
                                   else old + EWMA_ALPHA * (v - old))
        self.samples += 1


class FlightRecorder:
    """The bounded ring of retained query traces + retention policy."""

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = True
        self.max_queries = 48
        self.max_bytes = 32 << 20
        self.trace_dir = ""
        self._ring: deque = deque()  # _Capture, oldest first
        self._bytes = 0
        self._profiles: Dict[str, _FpProfile] = {}
        # controls whose offer/outcome handshake is half-done; weakly
        # held so an abandoned query can't pin its trace forever
        self._pending: "weakref.WeakSet" = weakref.WeakSet()
        self.sealed = 0
        self.dropped_boring = 0
        self.evicted = 0
        self.missed = 0
        self.captured_by_reason: Dict[str, int] = {}

    # -- config -------------------------------------------------------------------
    def configure(self, conf) -> None:
        try:
            enabled = bool(conf[_CONF_ENABLED])
            max_q = int(conf[_CONF_MAX_QUERIES])
            max_b = int(conf[_CONF_MAX_BYTES])
            tdir = str(conf[_CONF_TRACE_DIR] or "")
        except KeyError:
            return
        with self._lock:
            self.enabled = enabled
            self.max_queries = max(1, max_q)
            self.max_bytes = max(1, max_b)
            self.trace_dir = tdir
            evicted = self._evict_locked()
        if evicted:
            from . import telemetry
            telemetry.count("recorder_dropped_total", evicted,
                            reason="evicted")

    # -- the seal -----------------------------------------------------------------
    def _fingerprint(self, tr, ctl) -> str:
        fp = getattr(ctl, "fingerprint", None) if ctl is not None \
            else None
        if fp:
            return str(fp)
        names = sorted({str(e.get("name", ""))
                        for e in tr.ops.values()})
        if names:
            return "plan:" + hashlib.sha1(
                "|".join(names).encode()).hexdigest()[:12]
        return "anon:" + tr.label.split("[", 1)[-1].rstrip("]")

    def _slo_bad(self, latency_s: Optional[float], ok: bool) -> bool:
        if not ok:
            return True
        if latency_s is None:
            return False
        from . import telemetry
        return latency_s > telemetry.slo_latency_s()

    def seal(self, tr, ctl, latency_s: Optional[float], ok: bool,
             slo_eligible: bool) -> Optional[str]:
        """Judge one finished trace and decide retention.  Returns the
        retention reason (None = dropped).  Thread-safe; the dump (if
        retained and a trace dir is set) happens outside the lock."""
        from . import telemetry
        fp = self._fingerprint(tr, ctl)
        wall = tr.duration_s
        terms = decompose(tr.attrs, _trace_events(tr))
        slo_violated = slo_eligible and self._slo_bad(latency_s, ok)
        with self._lock:
            prof = self._profiles.get(fp)
            if prof is None:
                prof = self._profiles[fp] = _FpProfile()
                first_seen = True
            else:
                first_seen = prof.samples == 0
            verdict, excess = judge(terms, prof.baseline, prof.samples)
            baseline = dict(prof.baseline)
            if slo_violated:
                reason: Optional[str] = "slo"
            elif tr.status != "ok":
                reason = "outcome"
            elif first_seen:
                reason = "first_seen"
            elif prof.is_top_k(wall):
                reason = "top_k"
            else:
                reason = None
            prof.update(wall, terms)
            self.sealed += 1
            cap = None
            evicted = 0
            if reason is not None:
                cap = _Capture(tr, fp, reason, tr.status, wall,
                               latency_s, terms, verdict)
                self._ring.append(cap)
                self._bytes += cap.approx_bytes
                evicted = self._evict_locked()
                self.captured_by_reason[reason] = \
                    self.captured_by_reason.get(reason, 0) + 1
            else:
                self.dropped_boring += 1
            trace_dir = self.trace_dir
        if evicted:
            telemetry.count("recorder_dropped_total", evicted,
                            reason="evicted")
        # attribution stamp: the dump is self-describing so
        # explain_slow needs nothing but the file
        tr.attrs["fingerprint"] = fp
        tr.attrs["perf_terms"] = {k: round(v, 6)
                                  for k, v in terms.items()}
        tr.attrs["perf_baseline"] = {k: round(v, 6)
                                     for k, v in baseline.items()}
        tr.attrs["perf_verdict"] = verdict or ""
        if reason is not None:
            tr.attrs["capture_reason"] = reason
        if verdict is not None:
            # the typed verdict is visible on the timeline itself and
            # in the live registry, not only in the report tool
            tr.add_event(None, "perf:anomaly", "mark", tr.t0 + wall,
                         0.0, {"term": verdict,
                               "excess_s": excess.get(verdict, 0.0)})
            telemetry.count("perf_anomalies_total", term=verdict)
        if reason is not None:
            telemetry.count("recorder_captures_total", reason=reason)
            if cap is not None and trace_dir:
                self._dump(cap, trace_dir)
        else:
            telemetry.count("recorder_dropped_total", reason="boring")
        return reason

    def _evict_locked(self) -> int:
        """Ring-bound enforcement (caller holds the lock; the caller
        emits the eviction counter AFTER releasing it — telemetry has
        its own lock and the two must never nest).  The newest capture
        always survives, even when it alone exceeds maxBytes."""
        n = 0
        while self._ring and (
                len(self._ring) > self.max_queries
                or (self._bytes > self.max_bytes
                    and len(self._ring) > 1)):
            old = self._ring.popleft()
            self._bytes -= old.approx_bytes
            self.evicted += 1
            n += 1
        return n

    def _dump(self, cap: _Capture, trace_dir: str) -> None:
        import os
        try:
            os.makedirs(trace_dir, exist_ok=True)
            path = os.path.join(
                trace_dir, f"capture-{cap.capture_id}.trace.json")
            cap.trace.write(path)
            cap.path = path
        except OSError:
            cap.path = ""

    def note_missed(self) -> None:
        from . import telemetry
        with self._lock:
            self.missed += 1
        telemetry.count("recorder_missed_total")

    # -- read side ----------------------------------------------------------------
    def captures(self) -> List[_Capture]:
        with self._lock:
            return list(self._ring)

    def find(self, capture_id: str) -> Optional[_Capture]:
        with self._lock:
            for cap in self._ring:
                if cap.capture_id == capture_id \
                        or cap.capture_id.startswith(capture_id):
                    return cap
        return None

    def pending_seals(self) -> int:
        with self._lock:
            return len(self._pending)

    def export_gauges(self) -> None:
        """Scrape-time provider: ring occupancy as live gauges."""
        from . import telemetry
        with self._lock:
            q, b = len(self._ring), self._bytes
        telemetry.gauge_set("recorder_queries", float(q))
        telemetry.gauge_set("recorder_bytes", float(b))

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            caps = list(self._ring)
            out: Dict[str, object] = {
                "enabled": self.enabled,
                "queries": len(caps),
                "bytes": self._bytes,
                "max_queries": self.max_queries,
                "max_bytes": self.max_bytes,
                "sealed": self.sealed,
                "dropped_boring": self.dropped_boring,
                "evicted": self.evicted,
                "missed": self.missed,
                "pending_seals": len(self._pending),
                "captures_by_reason": dict(self.captured_by_reason),
            }
        out["captures"] = [c.summary() for c in reversed(caps)]
        return out


# ---------------------------------------------------------------------------------
# Compile ledger
# ---------------------------------------------------------------------------------

class CompileLedger:
    """Per-statement-fingerprint compile accounting with trigger
    classification and a recompile-storm detector."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, dict] = {}
        self._evicted: set = set()
        self._primed: set = set()
        self._store: set = set()
        self._recent: deque = deque()  # monotonic t of recompiles
        self.storming = False
        self.total_compiles = 0
        self.total_s = 0.0

    def note(self, duration_s: float,
             fingerprint: Optional[str]) -> str:
        """Classify and record one backend compile; returns the
        trigger.  Called from the jax.monitoring listener — must stay
        allocation-light and never raise."""
        from . import telemetry
        from . import tracing
        attributed = bool(fingerprint)
        fp = str(fingerprint) if fingerprint else "<anon>"
        prewarming = getattr(_PREWARM_TLS, "depth", 0) > 0
        if prewarming and not attributed:
            # the prewarm lane compiles outside any live query control,
            # so the listener has no fingerprint — the scope carries it
            scope_fp = getattr(_PREWARM_TLS, "fp", None)
            if scope_fp:
                fp, attributed = scope_fp, True
        now = time.monotonic()  # span-api-ok (storm window bookkeeping)
        storm_args = None
        with self._lock:
            ent = self._entries.get(fp)
            if prewarming:
                # a deliberate background compile, never recompile
                # pressure; consume the warm-start markers so the LIVE
                # path's later compiles (if any) classify honestly
                trigger = "prewarm"
                self._store.discard(fp)
                self._primed.discard(fp)
            elif not attributed:
                # a session-direct query compiles MANY distinct
                # programs under no statement identity; calling those
                # "shape changes" of one phantom statement would trip
                # the storm detector on any warm-up, so they get their
                # own honest bucket and stay out of the storm window
                trigger = "unattributed"
            elif fp in self._evicted:
                self._evicted.discard(fp)
                trigger = "cache_evict"
            elif fp in self._store:
                # known to the persistent warm store: this "compile" is
                # a disk deserialization of a prior program, not the
                # post-restart storm the primed set would call it
                # (checked before _primed — a store-backed restart is
                # the warm path working)
                self._store.discard(fp)
                self._primed.discard(fp)
                trigger = "store_hit"
            elif fp in self._primed:
                self._primed.discard(fp)
                trigger = "post_restart"
            elif ent is None:
                trigger = "first_seen"
            else:
                trigger = "shape_change"
            if ent is None:
                ent = self._entries[fp] = {
                    "count": 0, "total_s": 0.0, "last_s": 0.0,
                    "triggers": {}, "first_wall": time.time(),
                    "last_wall": 0.0}
            ent["count"] += 1
            ent["total_s"] += duration_s
            ent["last_s"] = duration_s
            ent["last_wall"] = time.time()
            ent["triggers"][trigger] = ent["triggers"].get(trigger,
                                                           0) + 1
            self.total_compiles += 1
            self.total_s += duration_s
            if trigger not in ("first_seen", "unattributed",
                               "prewarm", "store_hit"):
                # a storm is RE-compilation pressure on identified
                # statements: steady first-seen warmup, anonymous
                # session compiles, deliberate prewarm bursts, and
                # store-served deserializations are expected and must
                # not trip it
                self._recent.append(now)
            while self._recent and now - self._recent[0] \
                    > STORM_WINDOW_S:
                self._recent.popleft()
            n = len(self._recent)
            if not self.storming and n >= STORM_THRESHOLD:
                self.storming = True
                storm_args = {"recompiles": n,
                              "window_s": STORM_WINDOW_S}
            elif self.storming and n <= STORM_THRESHOLD // 2:
                self.storming = False
        telemetry.count("compiles_by_trigger_total", trigger=trigger)
        telemetry.gauge_set("compile_storm_active",
                            1.0 if self.storming else 0.0)
        if storm_args is not None:
            tracing.mark(None, "compile:storm", "compile",
                         **storm_args)
        return trigger

    def note_evicted(self, fingerprint) -> None:
        """A prepared/compile cache entry was evicted: this
        fingerprint's NEXT compile is attributable to the eviction."""
        if fingerprint:
            with self._lock:
                self._evicted.add(str(fingerprint))

    def prime(self, fingerprints) -> None:
        """Mark fingerprints expected to recompile after a process
        restart (a restored prepared catalog, a warmup manifest): their
        next compile classifies post_restart, not shape_change."""
        with self._lock:
            for fp in fingerprints:
                if fp:
                    self._primed.add(str(fp))

    def note_store_known(self, fingerprints) -> None:
        """Mark fingerprints the persistent warm store holds programs
        for (a loaded manifest, a shipped payload): their next compile
        classifies store_hit — a disk deserialization, not a storm."""
        with self._lock:
            for fp in fingerprints:
                if fp:
                    self._store.add(str(fp))

    def export_gauges(self) -> None:
        from . import telemetry
        telemetry.gauge_set("compile_storm_active",
                            1.0 if self.storming else 0.0)

    def snapshot(self, top: int = 20) -> Dict[str, object]:
        with self._lock:
            entries = sorted(self._entries.items(),
                             key=lambda kv: kv[1]["total_s"],
                             reverse=True)
            return {
                "fingerprints": len(self._entries),
                "compiles": self.total_compiles,
                "compile_s": round(self.total_s, 4),
                "storming": self.storming,
                "recent_recompiles": len(self._recent),
                "top": [{
                    "fingerprint": fp[:16],
                    "count": e["count"],
                    "total_s": round(e["total_s"], 4),
                    "last_s": round(e["last_s"], 4),
                    "triggers": dict(e["triggers"]),
                } for fp, e in entries[:top]],
            }


# ---------------------------------------------------------------------------------
# Module singletons + the offer/outcome seal handshake
# ---------------------------------------------------------------------------------

_REC = FlightRecorder()
_LEDGER = CompileLedger()

from . import telemetry as _telemetry  # noqa: E402 (after the state it exports)

_telemetry.register_provider(_REC.export_gauges)
_telemetry.register_provider(_LEDGER.export_gauges)


def recorder() -> FlightRecorder:
    return _REC


def compile_ledger() -> CompileLedger:
    return _LEDGER


def configure(conf) -> None:
    _REC.configure(conf)


def offer(tr, conf) -> None:
    """Session side of the seal: called from ``_finish_trace`` with the
    finished trace, on EVERY execution path (exceptions and abandoned
    streams included).  Scheduler-managed queries wait for the
    scheduler's outcome; direct session queries seal immediately."""
    _REC.configure(conf)
    if tr is None or not _REC.enabled:
        return
    from ..service import cancel
    ctl = cancel.current()
    if ctl is not None and getattr(ctl, "enqueued_t", None) is not None:
        with _REC._lock:
            if getattr(ctl, "_rec_sealed", False):
                return
            out = getattr(ctl, "_rec_outcome", None)
            if out is None:
                ctl._rec_trace = tr
                _REC._pending.add(ctl)
                return
            ctl._rec_sealed = True
            _REC._pending.discard(ctl)
        _REC.seal(tr, ctl, *out)
    else:
        _REC.seal(tr, ctl, None, tr.status == "ok",
                  slo_eligible=False)


def outcome(ctl, latency_s: Optional[float], ok: bool,
            slo_eligible: bool = True) -> None:
    """Scheduler side of the seal: called exactly once per terminal
    scheduler resolution (``_finish``, a successful resubmit requeue,
    or the watchdog's ``_force_finish``) with the SAME latency/ok the
    SLO burn tracker was fed — the capture ledger and ``slo_bad_total``
    reconcile exactly because they share this verdict."""
    if ctl is None:
        return
    if not _REC.enabled:
        # the burn tracker still counted this query: an SLO-bad
        # resolution with no possible capture is an explicit miss, so
        # slo_bad_total == captures{slo} + missed stays exact even with
        # the recorder switched off
        if slo_eligible and _REC._slo_bad(latency_s, ok):
            _REC.note_missed()
        return
    with _REC._lock:
        if getattr(ctl, "_rec_sealed", False):
            return
        tr = getattr(ctl, "_rec_trace", None)
        if tr is None:
            # trace not offered yet (streaming still open, or a wedged
            # worker): park the verdict for the late offer
            ctl._rec_outcome = (latency_s, ok, slo_eligible)
            _REC._pending.add(ctl)
            return
        ctl._rec_sealed = True
        _REC._pending.discard(ctl)
    _REC.seal(tr, ctl, latency_s, ok, slo_eligible)


def snapshot() -> Dict[str, object]:
    """The ops-surface section (``/snapshot`` → ``recorder``)."""
    out = _REC.snapshot()
    out["compile_ledger"] = _LEDGER.snapshot()
    return out


def pending_seals() -> int:
    """Half-sealed queries right now (the drain leak audit: 0 after a
    clean drain)."""
    return _REC.pending_seals()


def compile_note(duration_s: float, fingerprint) -> None:
    """utils/metrics.py's compile listener feed (never raises)."""
    try:
        _LEDGER.note(duration_s, fingerprint)
    except Exception:  # fault-ok (ledger accounting must never fail a compile)
        pass


def compile_evicted(fingerprint) -> None:
    _LEDGER.note_evicted(fingerprint)


def compile_prime(fingerprints) -> None:
    _LEDGER.prime(fingerprints)


def compile_store_known(fingerprints) -> None:
    _LEDGER.note_store_known(fingerprints)


# thread-local prewarm scope: compiles issued on a thread inside the
# scope classify as trigger=prewarm (and inherit the scope's statement
# fingerprint when the listener has none)
_PREWARM_TLS = threading.local()


class compile_prewarm_scope:
    """``with compile_prewarm_scope(fp):`` — every backend compile this
    thread issues inside the block is the prewarm lane's doing."""

    def __init__(self, fingerprint=None):
        self._fp = str(fingerprint) if fingerprint else None

    def __enter__(self):
        _PREWARM_TLS.depth = getattr(_PREWARM_TLS, "depth", 0) + 1
        self._prev_fp = getattr(_PREWARM_TLS, "fp", None)
        if self._fp:
            _PREWARM_TLS.fp = self._fp
        return self

    def __exit__(self, *exc):
        _PREWARM_TLS.depth -= 1
        _PREWARM_TLS.fp = self._prev_fp
        return False


def reset_for_tests() -> None:
    global _REC, _LEDGER
    old_rec, old_led = _REC, _LEDGER
    _REC = FlightRecorder()
    _LEDGER = CompileLedger()
    # swap the registered providers in place (register_provider dedups
    # by identity; the old singletons' providers must not linger)
    provs = _telemetry._REG._providers
    for i, p in enumerate(list(provs)):
        if p == old_rec.export_gauges:
            provs[i] = _REC.export_gauges
        elif p == old_led.export_gauges:
            provs[i] = _LEDGER.export_gauges
