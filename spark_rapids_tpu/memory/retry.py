"""OOM retry: catch device OOM, spill, retry — splitting inputs in half
when a plain retry cannot fit.

Reference: RmmRapidsRetryIterator.scala:61-181 (withRetry/withRetryNoSplit),
:622 (splitSpillableInHalfByRows), DeviceMemoryEventHandler.scala:111.  The
reference's native RMM state machine throws RetryOOM/SplitAndRetryOOM into
task threads; PJRT exposes no such hook, so here the boundary is the Python
device-op call: an XLA RESOURCE_EXHAUSTED is translated to :class:`RetryOOM`,
the catalog spills, and the op re-runs — escalating to
:class:`SplitAndRetryOOM` (halve the input batch, process the halves) after
``MAX_PLAIN_RETRIES``.  ``spark.rapids.tpu.test.injectRetryOOM`` forces
synthetic OOMs so suites can prove every operator survives and splits
(the reference's HashAggregateRetrySuite et al; inject_oom marker).
"""

from __future__ import annotations

import threading
from typing import Callable, Iterator, List, Optional

import jax

from ..batch import ColumnBatch

__all__ = ["RetryOOM", "SplitAndRetryOOM", "OOMInjector", "device_op",
           "with_retry", "split_in_half"]

MAX_PLAIN_RETRIES = 2


class RetryOOM(RuntimeError):
    """Device allocation failed; inputs were spillable — spill and re-run."""


class SplitAndRetryOOM(RuntimeError):
    """Retry alone cannot fit: split the input batch and run per half."""


class OOMInjector:
    """Test hook: force the next N device ops to raise a retry OOM
    (RmmSpark.forceRetryOOM / spark.rapids.sql.test.injectRetryOOM)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.remaining = 0
        self.split_remaining = 0

    def arm(self, n_retry: int, n_split: int = 0) -> None:
        with self._lock:
            self.remaining = n_retry
            self.split_remaining = n_split

    def maybe_raise(self) -> None:
        with self._lock:
            if self.remaining > 0:
                self.remaining -= 1
                raise RetryOOM("injected retry OOM")
            if self.split_remaining > 0:
                self.split_remaining -= 1
                raise SplitAndRetryOOM("injected split-and-retry OOM")


INJECTOR = OOMInjector()


def _is_xla_oom(ex: BaseException) -> bool:
    msg = str(ex)
    if isinstance(ex, jax.errors.JaxRuntimeError):
        return ("RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
                or "out of memory" in msg)
    # seen on the v5e (jaxlib 0.9.0, libtpu 0.0.34): a buffer the runtime
    # cannot allocate at dispatch is raised as a plain ValueError whose
    # text opens with the status code ("RESOURCE_EXHAUSTED: Error
    # allocating device buffer: Attempting to allocate 4.00G ...")
    return isinstance(ex, ValueError) and msg.startswith("RESOURCE_EXHAUSTED")


def device_op(ctx, fn: Callable, *args):
    """Run one device computation under the OOM protocol.

    Consults the injector (test hook), translates XLA OOM into RetryOOM,
    and on OOM spills the catalog before re-raising for the caller's retry
    loop (the DeviceMemoryEventHandler.onAllocFailure flow).
    """
    from .spill import get_catalog
    if ctx is None or ctx.conf["spark.rapids.tpu.memory.retry.enabled"]:
        INJECTOR.maybe_raise()
    try:
        return fn(*args)
    except BaseException as ex:
        if _is_xla_oom(ex):
            catalog = get_catalog(ctx.conf if ctx is not None else None)
            catalog.spill_all_device()
            # the cross-query cache IS catalog-registered (its device
            # bytes just spilled to host above); dropping unpinned
            # entries additionally frees the host copies before retry
            from ..cache import get_query_cache
            get_query_cache().drop_unpinned()
            raise RetryOOM(f"device OOM: {ex}") from ex
        raise


def split_in_half(batch: ColumnBatch) -> List[ColumnBatch]:
    """splitSpillableInHalfByRows analog: one batch → two half-row batches."""
    from ..ops import batch_utils
    b = batch_utils.compact(batch)
    if b.num_rows <= 1:
        raise SplitAndRetryOOM(
            f"cannot split a {b.num_rows}-row batch further")
    mid = b.num_rows // 2
    halves = [batch_utils.slice_batch(b, 0, mid),
              batch_utils.slice_batch(b, mid, b.num_rows - mid)]
    # batch-context metadata (input_file_name) survives the split
    origin = getattr(batch, "origin_file", None)
    if origin is not None:
        for h in halves:
            h.origin_file = origin
    return halves


def with_retry(ctx, batch: ColumnBatch, fn: Callable[[ColumnBatch], object],
               split: Optional[Callable] = split_in_half) -> Iterator:
    """Run ``fn(batch)`` with retry/split-retry semantics; yields results
    (one per final sub-batch).  The input is registered spillable for the
    duration so an OOM elsewhere can evict it (withRetry contract)."""
    from ..utils.metrics import TaskMetrics
    from .spill import get_catalog
    enabled = ctx is None or ctx.conf["spark.rapids.tpu.memory.retry.enabled"]
    if not enabled:
        yield fn(batch)
        return
    catalog = get_catalog(ctx.conf if ctx is not None else None)
    # pending holds spillable HANDLES, not raw batches: a batch waiting its
    # turn (or being retried) must be evictable, and no strong device ref may
    # outlive the attempt or spilling it cannot actually free HBM.
    pending = [catalog.register(batch, priority=10)]
    del batch
    try:
        while pending:
            handle = pending.pop(0)
            try:
                attempts = 0
                while True:
                    try:
                        yield device_op(ctx, fn, handle.get())
                        break
                    except (RetryOOM, SplitAndRetryOOM) as ex:
                        escalate = isinstance(ex, SplitAndRetryOOM)
                        if not escalate:
                            attempts += 1
                            TaskMetrics.get().retry_count += 1
                            catalog.spill_all_device()
                            if attempts <= MAX_PLAIN_RETRIES:
                                continue  # plain retry (restored on get)
                            escalate = True  # retries exhausted: split
                        if split is None:
                            raise
                        TaskMetrics.get().split_retry_count += 1
                        halves = split(handle.get())
                        pending = [catalog.register(h, priority=10)
                                   for h in halves] + pending
                        del halves
                        break
            finally:
                handle.close()
    finally:
        # consumer may abandon the generator mid-stream (LIMIT → GeneratorExit)
        # or fn may raise a non-OOM error: queued handles must not stay
        # registered or they pin memory in the catalog forever
        for h in pending:
            h.close()
