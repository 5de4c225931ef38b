"""Typed transient-failure recovery: retry with backoff, budgets,
degradation, and the terminal :class:`QueryFaulted`.

The recovery contract, per injection point (docs/robustness.md):

  * ``io.read`` / ``shuffle.fragment`` / ``dcn.heartbeat`` —
    :func:`transient_retry`: exponential backoff + seeded jitter
    (``spark.rapids.tpu.faults.backoff.{baseMs,maxMs,multiplier}``),
    at most ``faults.maxRetries`` attempts per call site, all attempts
    drawing down one per-query ``faults.retryBudget``;
  * ``io.write`` — only *injected* faults retry (re-running a failed
    filesystem write in place could duplicate rows); real write errors
    propagate, and the atomic temp-path+rename writers guarantee no
    partial file becomes visible either way;
  * ``device.op`` — :func:`device_guard`: up to ``faults.device.retries``
    re-dispatches, then graceful degradation to the operator's ``cpu/``
    fallback for that batch (``degraded:cpu`` trace mark,
    ``QueryStats.degraded_batches``);
  * ``cache.lookup`` — handled inside the cache: a faulted lookup
    degrades to a miss (recompute), a faulted fill is abandoned without
    leaving a poisoned entry.

Exhausting retries (or the per-query budget, or running with
``faults.recovery.enabled=false``) raises :class:`QueryFaulted`
carrying the accumulated :class:`FaultRecord` history — the scheduler
maps it to a ``faulted`` query status, and the ordinary exception
unwind releases permits, pipeline slots, and spill handles
(``assert_no_leaks`` clean after a faulted query).

Backoff sleeps are cancellation-aware: a cancelled/deadline-expired
query wakes immediately instead of serving out its backoff.
"""

from __future__ import annotations

import contextlib
import contextvars
import errno
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

__all__ = ["TransientFault", "PermanentFault", "QueryFaulted",
           "FaultRecord", "transient_retry", "device_guard",
           "budget_scope", "backoff_delays", "recovery_enabled",
           "check_disk_full", "RETRYABLE"]


class TransientFault(RuntimeError):
    """A recoverable data-movement failure (base of injected faults;
    ``parallel.dcn.PeerFailedError`` subclasses it too)."""

    def __init__(self, message: str, point: Optional[str] = None):
        super().__init__(message)
        self.point = point


class PermanentFault(RuntimeError):
    """A failure that will not heal at this placement: a peer the
    coordinator has *declared dead*, or a coordinator whose socket
    closed.  :func:`transient_retry` fast-fails on these — raising
    :class:`QueryFaulted` with ``resubmittable=True`` immediately
    instead of riding the exponential-backoff budget against a rank
    that will never come back.  The scheduler may then RESUBMIT the
    whole query against the surviving membership
    (``spark.rapids.tpu.faults.resubmit.max``).

    May be mixed into a :class:`TransientFault` subclass (see
    ``parallel.dcn.PeerLostError``): the permanent classification wins.
    """

    def __init__(self, message: str, point: Optional[str] = None):
        super().__init__(message)
        self.point = point


@dataclass
class FaultRecord:
    """One observed fault: what failed, which attempt, how long we
    backed off before the next try (0 when the fault was terminal)."""

    point: str
    attempt: int
    error: str
    backoff_s: float = 0.0


class QueryFaulted(RuntimeError):
    """Transient-fault recovery exhausted (or disabled): the query fails
    typed, carrying the full per-query fault history for diagnosis.

    ``resubmittable=True`` marks a *permanent-at-this-placement*
    failure (:class:`PermanentFault` — e.g. a declared-dead DCN peer):
    re-running the SAME query against the surviving membership can
    succeed, so the scheduler may resubmit it
    (``spark.rapids.tpu.faults.resubmit.max``)."""

    def __init__(self, point: str, message: str,
                 history: Optional[List[FaultRecord]] = None,
                 resubmittable: bool = False):
        super().__init__(message)
        self.point = point
        self.history = list(history or [])
        self.resubmittable = resubmittable


# Per-point transient classification.  FileNotFoundError is deliberately
# NOT transient for reads (a missing file is a dataset problem, not a
# network blip); io.write retries only injected faults (see module doc).
def _read_retryable() -> tuple:
    return (TransientFault, ConnectionError, TimeoutError,
            InterruptedError, OSError)


RETRYABLE = {
    "io.read": _read_retryable(),
    "io.write": (TransientFault,),
    "shuffle.fragment": _read_retryable(),
    "dcn.heartbeat": _read_retryable(),
    "device.op": (TransientFault,),
    "cache.lookup": (TransientFault,),
}

_NON_RETRYABLE = (FileNotFoundError,)

# disk-full errnos: a FULL disk does not heal on the retry-backoff
# curve — the spill/write paths type it PermanentFault so the query
# fast-fails resubmittable (a different placement may have room)
# instead of burning the per-query retry budget against ENOSPC
_DISK_FULL_ERRNOS = (errno.ENOSPC, getattr(errno, "EDQUOT", errno.ENOSPC))


def check_disk_full(ex: BaseException, point: str) -> None:
    """Re-raise an ENOSPC/EDQUOT ``OSError`` as a typed
    :class:`PermanentFault` (the spill and atomic-writer paths call
    this from their except blocks).  Any other exception passes
    through untouched for the caller's own handling."""
    if isinstance(ex, OSError) and ex.errno in _DISK_FULL_ERRNOS:
        raise PermanentFault(
            f"disk full at {point}: {ex} — fast-failing resubmittable "
            f"instead of retrying against a full disk", point=point) from ex


# ---------------------------------------------------------------------------------
# Per-query retry budget (contextvar-scoped; worker threads run copied
# contexts and therefore share their query's budget object by reference).
# ---------------------------------------------------------------------------------

class _Budget:
    __slots__ = ("remaining", "history", "conf")

    def __init__(self, remaining: int, conf=None):
        self.remaining = remaining
        self.history: List[FaultRecord] = []
        self.conf = conf


_BUDGET: "contextvars.ContextVar[Optional[_Budget]]" = \
    contextvars.ContextVar("srt_fault_budget", default=None)


@contextlib.contextmanager
def budget_scope(conf):
    """Install the per-query retry budget (+ the query's conf, so call
    sites without a ctx — io sources, shuffle readers — resolve backoff
    parameters from the RUNNING query's settings).  The session's
    execution entry points open this alongside ``QueryStats.scoped``."""
    b = _Budget(conf["spark.rapids.tpu.faults.retryBudget"], conf)
    tok = _BUDGET.set(b)
    try:
        yield b
    finally:
        try:
            _BUDGET.reset(tok)
        except ValueError:
            # generator-held scopes can violate token LIFO (mirrors
            # tracing.query_trace); clearing is the safe fallback
            _BUDGET.set(None)


def fault_history() -> List[FaultRecord]:
    """The running query's accumulated fault records (empty outside a
    budget scope)."""
    b = _BUDGET.get()
    return list(b.history) if b is not None else []


def _resolve_conf(ctx):
    """ctx may be an ExecContext (has .conf), a TpuConf, or None (fall
    back to the installed budget scope's conf, then process defaults)."""
    conf = getattr(ctx, "conf", ctx)
    if conf is not None:
        return conf
    b = _BUDGET.get()
    if b is not None and b.conf is not None:
        return b.conf
    from ..config import TpuConf
    return TpuConf()


def recovery_enabled(ctx=None) -> bool:
    return _resolve_conf(ctx)["spark.rapids.tpu.faults.recovery.enabled"]


# ---------------------------------------------------------------------------------
# Backoff.
# ---------------------------------------------------------------------------------

def _backoff_s(conf, attempt: int) -> float:
    """Capped exponential backoff with seeded jitter for ``attempt``
    (1-based).  The exponent is clamped: a long-lived wait loop riding
    this curve (the coordinator's barrier re-check cadence) can reach
    attempt counts where ``mult ** attempt`` overflows float range —
    past ~64 doublings the result is beyond any cap regardless."""
    from .injector import INJECTOR
    base = conf["spark.rapids.tpu.faults.backoff.baseMs"]
    cap = conf["spark.rapids.tpu.faults.backoff.maxMs"]
    mult = conf["spark.rapids.tpu.faults.backoff.multiplier"]
    raw = min(cap, base * (mult ** min(64, max(0, attempt - 1))))
    return (raw / 1000.0) * INJECTOR.jitter()


def backoff_delays(conf=None, max_attempts: Optional[int] = None):
    """Yield the backoff schedule (seconds) the framework would sleep —
    for wait loops that need the curve without the retry driver (the DCN
    coordinator's barrier re-check cadence)."""
    conf = _resolve_conf(conf)
    attempt = 1
    while max_attempts is None or attempt <= max_attempts:
        yield _backoff_s(conf, attempt)
        attempt += 1


def _sleep(delay: float) -> None:
    """Cancellation-aware backoff sleep: a cancelled query wakes
    immediately and raises instead of serving out the backoff."""
    from ..service import cancel
    ctl = cancel.current()
    if ctl is not None:
        if ctl.cancelled.wait(timeout=delay):
            ctl.raise_()
    else:
        time.sleep(delay)


# ---------------------------------------------------------------------------------
# The retry driver.
# ---------------------------------------------------------------------------------

def _note_fault(point: str, attempt: int, ex: BaseException,
                backoff_s: float = 0.0) -> FaultRecord:
    rec = FaultRecord(point, attempt, f"{type(ex).__name__}: {ex}",
                      backoff_s)
    b = _BUDGET.get()
    if b is not None:
        b.history.append(rec)
    return rec


def _faulted(point: str, ex: BaseException, attempt: int,
             resubmittable: bool = False) -> QueryFaulted:
    history = fault_history()
    what = ("permanent at this placement"
            if resubmittable else "transient-fault recovery exhausted")
    return QueryFaulted(
        point,
        f"{what} at {point} after "
        f"{attempt} attempt(s): {type(ex).__name__}: {ex} "
        f"({len(history)} fault(s) this query)",
        history=history, resubmittable=resubmittable)


def transient_retry(ctx, point: str, fn: Callable, *args,
                    desc: str = "", retryable: Optional[tuple] = None,
                    deadline_s: Optional[float] = None,
                    recover_counter: Optional[str] = None):
    """Run ``fn(*args)`` under the transient-fault protocol for ``point``.

    Consults the injector before every attempt (so every guarded call
    site is automatically an injection point), classifies failures by
    the per-point ``RETRYABLE`` tuple, and retries with exponential
    backoff + jitter while the per-call attempt cap
    (``faults.maxRetries``, or ``deadline_s`` when given) and the
    per-query retry budget both hold.  Exhaustion — or
    ``faults.recovery.enabled=false`` — raises :class:`QueryFaulted`.

    ``recover_counter`` names a ``QueryStats`` counter bumped when the
    call ultimately SUCCEEDS after at least one fault (the
    ``fragments_recomputed`` accounting for shuffle re-pulls).
    """
    from .injector import INJECTOR
    from ..utils import tracing
    from ..utils.metrics import QueryStats
    conf = _resolve_conf(ctx)
    classes = retryable if retryable is not None else RETRYABLE[point]
    max_retries = conf["spark.rapids.tpu.faults.maxRetries"]
    t_deadline = None if deadline_s is None \
        else time.monotonic() + deadline_s
    attempt = 0
    while True:
        try:
            INJECTOR.maybe_raise(point, desc=desc)
            out = fn(*args)
            if attempt and recover_counter is not None:
                s = QueryStats.get()
                setattr(s, recover_counter,
                        getattr(s, recover_counter, 0) + 1)
                tracing.mark(None, "recovered", "fault", point=point,
                             attempts=attempt + 1, counter=recover_counter,
                             desc=desc)
            return out
        except (PermanentFault,) + tuple(classes) as ex:
            if isinstance(ex, PermanentFault):
                # permanent at this placement (declared-dead peer, lost
                # coordinator): backing off cannot help — fail typed NOW
                # without drawing down the retry budget, flagged so the
                # scheduler may resubmit against surviving membership
                attempt += 1
                _note_fault(point, attempt, ex)
                raise _faulted(point, ex, attempt,
                               resubmittable=True) from ex
            if isinstance(ex, _NON_RETRYABLE) \
                    and not isinstance(ex, TransientFault):
                raise
            attempt += 1
            budget = _BUDGET.get()
            exhausted = (
                not conf["spark.rapids.tpu.faults.recovery.enabled"]
                or (t_deadline is None and attempt > max_retries)
                or (t_deadline is not None
                    and time.monotonic() > t_deadline)
                or (budget is not None and budget.remaining <= 0))
            if exhausted:
                _note_fault(point, attempt, ex)
                raise _faulted(point, ex, attempt) from ex
            if budget is not None:
                budget.remaining -= 1
            delay = _backoff_s(conf, attempt)
            _note_fault(point, attempt, ex, delay)
            s = QueryStats.get()
            s.transient_retries += 1
            s.retry_backoff_s += delay
            tracing.mark(None, "retry:attempt", "fault", point=point,
                         attempt=attempt, backoff_ms=round(delay * 1e3, 2),
                         error=type(ex).__name__, desc=desc)
            _sleep(delay)


def _simulate_hang(conf, op_id: str) -> None:
    """The ``device.hang`` gray injection: wedge this dispatch the way a
    hung D2H fetch or a stuck XLA program would — no exception, no batch
    progress.  Under a query control the hang holds until the watchdog's
    cooperative cancel (or the caller's own) wakes it and raises; with
    no control installed it self-bounds at 2× the watchdog stall window
    so an unscheduled chaos run cannot wedge forever.
    """
    from ..service import cancel
    from ..utils import tracing
    tracing.mark(op_id, "device:hang", "fault", point="device.hang")
    limit_s = max(0.05,
                  conf["spark.rapids.tpu.faults.watchdog.stallMs"] / 500.0)
    ctl = cancel.current()
    if ctl is not None:
        if ctl.cancelled.wait(timeout=limit_s * 20):
            ctl.raise_()  # the watchdog (or caller) reclaimed the query
        return  # pathological: no cancel ever arrived — un-wedge
    time.sleep(limit_s)


# ---------------------------------------------------------------------------------
# Device-op guard: bounded retries, then degrade to the CPU path.
# ---------------------------------------------------------------------------------

def _is_transient_device(ex: BaseException) -> bool:
    """A non-OOM device/runtime error worth re-dispatching: transport or
    runtime blips, never RESOURCE_EXHAUSTED (that is the OOM protocol's,
    memory/retry.py) and never ordinary Python errors."""
    if isinstance(ex, TransientFault):
        return True
    import jax
    if not isinstance(ex, jax.errors.JaxRuntimeError):
        return False
    msg = str(ex)
    if "RESOURCE_EXHAUSTED" in msg:
        return False
    return any(tag in msg for tag in
               ("UNAVAILABLE", "ABORTED", "DATA_LOSS", "connection"))


def device_guard(ctx, op_id: str, fn: Callable,
                 cpu_fallback: Optional[Callable] = None):
    """Run one device computation (``device.op`` point) with bounded
    re-dispatch and graceful degradation.

    Transient failures re-dispatch up to ``faults.device.retries`` times
    (budget-checked, backoff between attempts); if the op STILL fails
    and the operator supplied a ``cpu_fallback``, the batch degrades to
    the CPU path — marked ``degraded:cpu`` in the trace and counted in
    ``QueryStats.degraded_batches`` — instead of failing the query.
    OOM (RetryOOM / RESOURCE_EXHAUSTED) is not handled here: that is
    the spill-and-retry protocol in memory/retry.py.
    """
    from .injector import INJECTOR
    from ..utils import tracing
    from ..utils.metrics import QueryStats
    conf = _resolve_conf(ctx)
    retries = conf["spark.rapids.tpu.faults.device.retries"]
    attempt = 0
    while True:
        try:
            if INJECTOR.maybe_fire("device.hang", desc=op_id):
                # gray failure: the dispatch WEDGES instead of raising —
                # the per-query watchdog (service/watchdog.py) is the
                # layer that must notice the stalled batch cadence
                _simulate_hang(conf, op_id)
            INJECTOR.maybe_raise("device.op", desc=op_id)
            return fn()
        except BaseException as ex:
            if not _is_transient_device(ex):
                raise
            attempt += 1
            budget = _BUDGET.get()
            enabled = conf["spark.rapids.tpu.faults.recovery.enabled"]
            can_retry = (enabled and attempt <= retries
                         and (budget is None or budget.remaining > 0))
            if can_retry:
                if budget is not None:
                    budget.remaining -= 1
                delay = _backoff_s(conf, attempt)
                _note_fault("device.op", attempt, ex, delay)
                s = QueryStats.get()
                s.transient_retries += 1
                s.retry_backoff_s += delay
                tracing.mark(op_id, "retry:attempt", "fault",
                             point="device.op", attempt=attempt,
                             backoff_ms=round(delay * 1e3, 2),
                             error=type(ex).__name__)
                _sleep(delay)
                continue
            _note_fault("device.op", attempt, ex)
            if enabled and cpu_fallback is not None \
                    and conf["spark.rapids.tpu.faults.degrade.enabled"]:
                QueryStats.get().degraded_batches += 1
                tracing.mark(op_id, "degraded:cpu", "fault",
                             point="device.op", attempts=attempt,
                             error=type(ex).__name__)
                return cpu_fallback()
            raise _faulted("device.op", ex, attempt) from ex
