"""Per-query progress watchdog: hung queries cannot strand permits.

The gray failure the fault framework (PR 5/6) cannot see is the one
that never raises: a D2H fetch wedged inside native code, a DCN wait
whose peer is neither dead nor answering, an XLA dispatch that simply
never returns.  Cooperative cancellation only helps a query that
reaches its next batch boundary — a truly hung query holds its
scheduler slot and semaphore permit forever, and under bounded
admission a handful of hangs brown out the whole service.

The watchdog closes that hole with the progress signal the engine
already emits for free: every operator batch pull passes the
``service.cancel.check()`` checkpoint (``tracing.instrument_batches``
owns it), which stamps ``QueryControl.progress_t``.  A scan thread owned
by the :class:`..service.scheduler.QueryScheduler` compares each
RUNNING query's last stamp against ``faults.watchdog.stallMs`` and
escalates in three steps:

  1. **diagnose** — a ``watchdog:stall`` mark with the worker thread's
     live stack lands in the query's trace (the post-mortem a hung
     query otherwise never produces), ``QueryStats.stalls_detected``
     counts it;
  2. **cooperative cancel** — ``control.cancel(stalled=True)`` wakes
     every registered waker; the unwind raises
     :class:`..service.cancel.QueryStalled` at the next boundary and
     the scheduler finishes the query ``faulted(resubmittable=True)``
     (a hang is a gray failure a fresh attempt may outrun, not a user
     cancel) with permits/slots/handles released by the ordinary
     unwind;
  3. **forcible reclaim** — if the worker is wedged in native code and
     the cancel never takes (one more stall window passes), the entry's
     future is resolved ``QueryFaulted(resubmittable=True)``, its
     running slot is freed, and one semaphore permit is forfeited
     (``TpuSemaphore.forfeit`` — clamped, so the zombie's eventual
     release cannot double-count).  The zombie thread is abandoned
     (daemon); the SERVICE lives on.

The watchdog is conf-driven per cycle (``faults.watchdog.{enabled,
stallMs}``), so a runtime ``conf.set`` applies to queries already in
flight.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Dict

__all__ = ["QueryWatchdog"]

_pc = time.perf_counter

# cap on the stack snapshot folded into the trace mark (frames, not
# bytes: deep plans produce deep pull stacks; the top is what matters)
_STACK_FRAMES = 25

# cold-start grace: until a query passes its FIRST batch-pull
# checkpoint, planning + XLA compilation legitimately run long
# (minutes for a large plan), so the stall window stretches by this
# factor.  Compile completions also stamp progress (utils/metrics
# compile listener), so a sequence of compiles each under stallMs never
# trips; a query wedged before its first batch is still reclaimed —
# within coldGrace x stallMs instead of stallMs.
_COLD_GRACE = 4.0


class QueryWatchdog:
    """Scans the owning scheduler's running entries for stalled queries.

    One daemon thread per scheduler; poll cadence adapts to the
    configured stall window (stallMs/4, clamped to [50 ms, 1 s]) so
    detection lands within ``stallMs + one poll`` without burning a hot
    loop.
    """

    def __init__(self, scheduler):
        self._sched = scheduler
        self._stop = threading.Event()
        # entry -> perf_counter at which the cooperative cancel was
        # issued; stage-3 reclaim triggers one stall window later
        self._cancelled_at: Dict[object, float] = {}
        self.stalls = 0
        self.reclaims = 0
        self._thread = threading.Thread(  # ctx-ok (service-lifetime monitor; touches queries only through their controls)
            target=self._loop, daemon=True, name="srt-query-watchdog")
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)

    # -- the scan -----------------------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                conf = self._sched._conf()
                enabled = conf["spark.rapids.tpu.faults.watchdog.enabled"]
                stall_s = conf[
                    "spark.rapids.tpu.faults.watchdog.stallMs"] / 1000.0
            except Exception:  # fault-ok (conf resolution during teardown; idle until next cycle)
                enabled, stall_s = False, 30.0
            if enabled:
                try:
                    self._scan(stall_s)
                except Exception:  # fault-ok (a watchdog crash must never take the scheduler down)
                    pass
            self._stop.wait(min(1.0, max(0.05, stall_s / 4.0)))

    def _scan(self, stall_s: float) -> None:
        with self._sched._cv:
            running = list(self._sched._running)
        now = _pc()
        for e in running:
            ctl = e.control
            if e.future.done():
                self._cancelled_at.pop(e, None)
                continue
            if ctl.cancelled.is_set():
                # someone (us, the user, a deadline) already asked the
                # query to stop; our stage 3 applies only to OUR cancels
                t0 = self._cancelled_at.get(e)
                if t0 is not None and now - t0 > stall_s:
                    self._reclaim(e)
                continue
            # the stall clock starts at DISPATCH (QueryControl.
            # note_dispatch stamps progress_t when the worker starts),
            # never at submit: a query that waited past stallMs in a
            # deep admission queue is the scheduler's business, not a
            # hang.  An entry whose worker has not stamped yet is not
            # yet running — skip it.
            if ctl.dispatched_t is None:
                continue
            idle = now - max(ctl.progress_t, ctl.dispatched_t)
            window = stall_s if ctl.progress_seen \
                else stall_s * _COLD_GRACE
            if idle <= window:
                continue
            self._escalate(e, idle, window)

    # -- stage 1 + 2: diagnose, then cooperative cancel ---------------------------
    def _escalate(self, e, idle: float, stall_s: float) -> None:
        from ..utils.metrics import QueryStats
        ctl = e.control
        stack = self._worker_stack(e)
        # keep the newest stall stack on the control too: a quarantine
        # diagnosis bundle (service/breaker.py) includes it even when
        # tracing is off for the query
        ctl.last_stall_stack = stack
        tr = ctl.trace
        if tr is not None:
            # the stack-dump mark is the hung query's only post-mortem:
            # land it BEFORE the cancel, while the stack is still hung
            tr.add_event(None, "watchdog:stall", "fault", _pc(), 0.0,
                         {"idle_ms": round(idle * 1e3, 1),
                          "stall_ms": round(stall_s * 1e3, 1),
                          "label": ctl.label, "stack": stack})
        # the query's stats scope lives on its worker thread; the
        # watchdog accounts on the process aggregate (the per-query
        # evidence is the trace mark + the faulted handle)
        QueryStats.process().stalls_detected += 1
        self.stalls += 1
        self._cancelled_at[e] = _pc()
        ctl.cancel(
            f"watchdog: no progress for {idle * 1e3:.0f}ms "
            f"(stallMs={stall_s * 1e3:.0f})", stalled=True)

    def _worker_stack(self, e) -> str:
        ident = getattr(e, "worker_ident", None)
        if ident is None:
            return "<worker thread unknown>"
        frame = sys._current_frames().get(ident)
        if frame is None:
            return "<worker thread gone>"
        return "".join(
            traceback.format_stack(frame, limit=_STACK_FRAMES))

    # -- stage 3: forcible reclaim ------------------------------------------------
    def _reclaim(self, e) -> None:
        """The cooperative cancel never took (worker wedged in native
        code): resolve the caller's future typed, free the running slot,
        forfeit the permit the zombie holds.  The service stays live;
        the zombie thread is abandoned."""
        from ..faults.recovery import QueryFaulted
        from ..utils import tracing
        self._cancelled_at.pop(e, None)
        self.reclaims += 1
        err = QueryFaulted(
            "watchdog",
            f"query {e.control.label} hung past cooperative cancel; "
            f"worker abandoned and permit reclaimed by the watchdog",
            resubmittable=True)
        tracing.mark(None, "watchdog:reclaim", "fault",
                     label=e.control.label)
        tr = e.control.trace
        if tr is not None and tr.t_end is None:
            tr.set_status("faulted")
            tr.finish()
        self._sched._force_finish(e, err)
        # a force-reclaim is a CHARGEABLE containment strike the wedged
        # worker can never report itself (its completion hook will never
        # run): feed the breaker here so the fingerprint's quarantine
        # counts the worker this query just killed
        try:
            self._sched.breaker.on_outcome(e, "faulted", err,
                                           self._sched._conf())
        except Exception:  # fault-ok (containment accounting must never fail the reclaim)
            pass
        try:
            from ..runtime.semaphore import get_semaphore
            get_semaphore(self._sched._conf()).forfeit()
        except Exception:  # fault-ok (no backend in pure-callable schedulers; slot release already happened)
            pass
