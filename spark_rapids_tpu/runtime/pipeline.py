"""Bounded-depth execution pipeline: overlap host work with XLA dispatch.

The engine's pull loop was strictly serial: ``ScanExec`` decodes a pyarrow
batch, blocks in ``jax.device_put``, runs the stage program, and only then
starts decoding the next batch — so the chip idles during every decode and
H2D transfer.  This module is the latency-hiding primitive the
operator layer threads through (the Theseus overlap-data-movement-with-
compute idea, PAPERS.md, realized inside one process):

  * a single worker thread drives the upstream iterator AHEAD of the
    consumer, staging up to ``depth`` batches (decode + ``device_put``
    for a scan; the whole child pull for a stage), so batch N+1's host
    work overlaps batch N's XLA program;
  * depth is a hard bound: a slot is reserved BEFORE the next item is
    produced, so at most ``depth`` staged batches are ever live — HBM
    stays bounded exactly like the serial iterator chain;
  * ``depth == 0`` reproduces today's serial pull loop byte-for-byte
    (the debugging escape hatch; ``spark.rapids.tpu.sql.pipeline.depth``).

The consumer's time blocked on a staged batch lands in
:class:`..utils.metrics.QueryStats` ``h2d_wait_s``; the worker is the
hand-off's producer (``tracing.start_producer``), so on the driving
thread that wait is resolved into what the worker was doing meanwhile
(``acct_h2d_<term>_s``).
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, TypeVar

__all__ = ["pipeline_map", "pipeline_batches", "effective_depth"]

T = TypeVar("T")
U = TypeVar("U")

_END = object()
_CANCELLED = object()


class _Slots:
    """Event-driven bounded-slot gate for the staging worker.

    Replaces the old 0.1 s ``Semaphore.acquire(timeout=)`` poll loop:
    the worker blocks on a condition that the consumer's release, the
    consumer's teardown (``stop``), or the query's cancellation waker
    notifies — an aborted query frees its staging thread immediately
    instead of holding it for up to 100 ms per slot.
    """

    def __init__(self, depth: int):
        self._cv = threading.Condition()
        self._free = depth
        self._stopped = False

    def acquire(self, ctl) -> bool:
        """Block until a slot frees; False when the pipeline stopped or
        the query was cancelled (the worker exits either way)."""
        with self._cv:
            while True:
                if self._stopped:
                    return False
                if ctl is not None and ctl.cancelled.is_set():
                    return False
                if self._free > 0:
                    self._free -= 1
                    return True
                self._cv.wait()  # wait-ok (release/stop/cancel-waker notify wake this slot gate)

    def release(self) -> None:
        with self._cv:
            self._free += 1
            self._cv.notify_all()

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()

    def notify(self) -> None:
        with self._cv:
            self._cv.notify_all()


_DEPTH_KEY = "spark.rapids.tpu.sql.pipeline.depth"


def effective_depth(ctx) -> int:
    """The pipeline depth this execution should use.

    OOM-injection tests force ``0``: the injector arms "the next N device
    ops" process-globally, and two threads racing for those ops would make
    the injection point nondeterministic.

    On the CPU backend the DEFAULT also resolves to ``0``: staging and
    "device" programs run on the same cores there, so overlap is pure
    contention (measured: q13 warm 61→157 ms on the 8-virtual-device
    mesh) — the depth only hides latency when host and device are
    different silicon.  An explicitly-set depth always wins (tests and
    ``SRT_BENCH_PIPELINE_DEPTH`` A/Bs set it on purpose).
    """
    conf = ctx.conf
    if conf["spark.rapids.tpu.test.injectRetryOOM"] \
            or conf["spark.rapids.tpu.test.injectSplitAndRetryOOM"]:
        return 0
    # deterministic fault schedules ("fail the Nth op at P") need the
    # same serial-execution guarantee: staged workers racing for the
    # per-point invocation counters would make the injection point
    # nondeterministic.  Probabilistic chaos rates keep the pipeline.
    from ..faults.injector import INJECTOR as FAULT_INJECTOR
    if FAULT_INJECTOR.deterministic_armed():
        return 0
    # inside a fused region (plan/fusion.py) the REGION is the pipeline
    # stage: member operators pull serially so the whole chain runs as
    # one staged unit; the region's consumer stages region output at the
    # configured depth.  Without this, every member would spawn its own
    # stage workers and the "one dispatch per region" property dissolves.
    from ..utils.metrics import current_region
    if current_region() is not None:
        return 0
    if not conf.is_set(_DEPTH_KEY):
        import jax
        if jax.default_backend() == "cpu":
            return 0
    return conf[_DEPTH_KEY]


def pipeline_map(src: Iterable[T], fn: Callable[[T], U],
                 depth: int, label: str = None) -> Iterator[U]:
    """Yield ``fn(item)`` for each upstream item, staging up to ``depth``
    results ahead of the consumer on a worker thread.

    ``depth <= 0`` degrades to the plain serial loop.  Upstream exceptions
    surface at the consumer's next pull; abandoning the iterator (LIMIT,
    errors) stops the worker and closes the upstream generator without
    leaking the thread or its staged batches.

    ``label`` names the consuming operator (its ``op_id``) so the stage/
    wait intervals land in the query trace as that operator's pipeline
    phases.  The worker runs in a COPY of the caller's context, as the
    producer of the hand-off (``tracing.start_producer``): it writes into
    the caller's query-scoped QueryStats, its spans join the caller's
    active trace, and the consumer's wait is resolved through it.
    """
    from ..service import cancel
    if depth <= 0:
        for item in src:
            cancel.check()
            yield fn(item)
        return

    from ..utils import tracing
    from ..utils.metrics import QueryStats

    slots = _Slots(depth)
    q: "queue.Queue" = queue.Queue()
    it = iter(src)
    ctl = cancel.current()
    # cancellation wakes BOTH sides event-driven: the worker blocked on
    # a slot (slots re-checks the flag) and the consumer blocked on the
    # staged-batch queue (the sentinel makes q.get return immediately)
    waker_tok = ctl.add_waker(
        lambda: (slots.notify(), q.put(_CANCELLED))) if ctl is not None \
        else None

    def worker():
        try:
            while True:
                # reserve a slot BEFORE producing: at most `depth` staged
                # items are ever live (queue + the one being produced)
                if not slots.acquire(ctl):  # srtlint: ignore[release-paths] (cross-thread gate: the consumer loop releases per item and its finally stop()s the gate, freeing any held slot)
                    return  # stopped or cancelled
                try:
                    with tracing.span(label, "pipeline:stage", "pipeline"):
                        item = next(it)
                        out = fn(item)
                except StopIteration:
                    q.put(_END)
                    return
                q.put(out)
        except BaseException as e:  # surfaced on the consumer side
            q.put(e)
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except BaseException:  # fault-ok (teardown of an already-failed upstream)
                    pass

    prod = tracing.start_producer(worker, "srt-pipeline-stage")
    try:
        pending_release = False
        while True:
            if pending_release:
                # the previous item's slot frees only once the consumer
                # comes back for more: staged batches + the one in the
                # consumer's hands never exceed `depth` (strict HBM bound)
                slots.release()
            with tracing.span(label, "pipeline:wait", "pipeline",
                              on=prod) as sp:
                item = q.get()
            QueryStats.get().h2d_wait_s += sp.dur
            if item is _END:
                return
            if item is _CANCELLED:
                cancel.check()  # raises QueryCancelled/DeadlineExceeded
                continue        # spurious (already-handled) wake
            if isinstance(item, BaseException):
                raise item
            pending_release = True
            yield item
    finally:
        slots.stop()
        if waker_tok is not None:
            ctl.remove_waker(waker_tok)


def pipeline_batches(batches: Iterable[T], depth: int,
                     label: str = None) -> Iterator[T]:
    """Pull an operator's child iterator up to ``depth`` batches ahead:
    the child's host decode/upload/dispatch runs on the worker thread
    while the consumer's XLA program is in flight."""
    return pipeline_map(batches, lambda b: b, depth, label=label)


def stream_arrow(ctx, batches) -> "Iterator":
    """Yield pyarrow tables from a stream of device batches with up to
    ``pipeline.depth`` D2H fetches resolving BEHIND the dispatch front —
    the fetch→wire handoff: batch N's device→host copy overlaps batch
    N+1's dispatch, so a network consumer (server/endpoint.py result
    streaming) puts Arrow IPC frames on the wire as fetches complete
    instead of collect-then-ship.  Depth 0 degrades to the serial
    fetch-per-batch loop (the CollectExec.collect_arrow discipline,
    applied to incremental consumers).  Cancellation is checked at every
    batch boundary; abandoning the generator drains nothing (pending
    fetch futures resolve on close)."""
    from collections import deque

    from ..batch import to_arrow, to_arrow_async
    from ..service import cancel
    depth = effective_depth(ctx)
    if depth <= 0:
        for b in batches:
            cancel.check()
            yield to_arrow(b)
        return
    pending: "deque" = deque()
    for b in batches:
        cancel.check()
        pending.append(to_arrow_async(b))
        while len(pending) > depth:
            yield pending.popleft()()
    while pending:
        yield pending.popleft()()
