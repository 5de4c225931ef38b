"""Task semaphore limiting concurrent queries on the device.

Reference: GpuSemaphore.scala:68-160 — ``spark.rapids.sql.concurrentGpuTasks``
bounds how many tasks hold the device at once (1000 permits split by the
concurrency level), with wait time surfaced in task metrics.  The TPU
analog: there are no CUDA streams to oversubscribe, but concurrent Python
threads submitting XLA programs still contend for HBM; the semaphore bounds
them and records the wait in :class:`..utils.metrics.TaskMetrics` and —
when a query trace is active — as an ``admit:semaphore`` span.

Service-era requirements (service/scheduler.py):

  * permits are **reconfigurable at runtime** (:meth:`resize`): a
    ``conf.set`` of ``concurrentTpuTasks`` widens/narrows the SAME
    instance, so in-flight holders and blocked waiters keep their state
    instead of being orphaned on a recreated semaphore;
  * waits are **cancellable**: a blocked ``acquire`` registers a waker
    with the query's :class:`..service.cancel.QueryControl` and raises
    ``QueryCancelled`` as soon as the query is cancelled or its deadline
    timer fires — no polling loop, no 100 ms of held thread;
  * the scheduler can observe ``available()`` and subscribe to permit
    releases (``add_release_listener``) to wake its dispatcher.
"""

from __future__ import annotations

import contextlib
import threading

__all__ = ["TpuSemaphore", "get_semaphore"]


class TpuSemaphore:
    def __init__(self, permits: int):
        self._cv = threading.Condition()
        self._permits = max(1, permits)
        self._in_use = 0
        self._release_listeners = []

    @property
    def permits(self) -> int:
        with self._cv:  # resize() runs concurrently with probes
            return self._permits

    def available(self) -> int:
        """Free permits right now (scheduler admission probe)."""
        with self._cv:
            return self._permits - self._in_use

    def in_use(self) -> int:
        with self._cv:
            return self._in_use

    def resize(self, permits: int) -> None:
        """Reconfigure the permit count at runtime.  Blocked waiters
        re-evaluate immediately; holders are unaffected (shrinking below
        the in-use count simply admits nobody until enough release)."""
        with self._cv:
            self._permits = max(1, permits)
            self._cv.notify_all()

    def add_release_listener(self, fn) -> None:
        """``fn()`` fires after every permit release — the scheduler's
        event-driven dispatch signal."""
        with self._cv:
            if fn not in self._release_listeners:
                self._release_listeners.append(fn)

    def forfeit(self) -> None:
        """Reclaim a permit held by an abandoned (wedged) worker — the
        watchdog's stage-3 escape hatch.  Counted as a release so
        waiters and the dispatcher wake; if the zombie thread later
        unwinds and releases for real, the release path clamps at zero
        so the permit cannot double-count."""
        with self._cv:
            self._in_use = max(0, self._in_use - 1)
            self._cv.notify_all()
            listeners = list(self._release_listeners)
        for fn in listeners:
            try:
                fn()
            except Exception:  # fault-ok (listener callback; reclaim must proceed)
                pass

    def _notify(self) -> None:
        with self._cv:
            self._cv.notify_all()

    @contextlib.contextmanager
    def acquire(self):
        from ..service import cancel
        from ..utils import tracing
        from ..utils.metrics import TaskMetrics
        ctl = cancel.current()
        tok = None
        if ctl is not None:
            # wake this wait the instant the query is cancelled (or its
            # deadline timer fires) — event-driven, not polled
            tok = ctl.add_waker(self._notify)
        sp = tracing.span(None, "admit:semaphore", "scheduler")
        try:
            with sp, self._cv:
                while self._in_use >= self._permits:
                    if ctl is not None:
                        ctl.check()
                    self._cv.wait()  # wait-ok (cancellation waker + resize/release notify wake this)
                if ctl is not None:
                    ctl.check()
                self._in_use += 1
        finally:
            if tok is not None:
                ctl.remove_waker(tok)
            TaskMetrics.get().semaphore_wait_s += sp.dur
        try:
            yield
        finally:
            with self._cv:
                # clamp: a watchdog forfeit may have reclaimed this
                # permit already (the holder was declared wedged)
                self._in_use = max(0, self._in_use - 1)
                self._cv.notify_all()
                listeners = list(self._release_listeners)
            for fn in listeners:
                try:
                    fn()
                except Exception:  # fault-ok (listener callback; release must proceed)
                    pass


_lock = threading.Lock()
_instance: TpuSemaphore = None


def get_semaphore(conf) -> TpuSemaphore:
    """Process-wide semaphore sized by concurrentTpuTasks on first use
    (resized IN PLACE if the configured concurrency changes — waiters
    and holders survive the reconfiguration)."""
    global _instance
    n = max(1, int(conf["spark.rapids.tpu.sql.concurrentTpuTasks"]))
    with _lock:
        if _instance is None:
            _instance = TpuSemaphore(n)
        elif _instance.permits != n:
            _instance.resize(n)
        return _instance
