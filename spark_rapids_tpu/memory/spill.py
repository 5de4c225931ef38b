"""Spillable batches + the spill catalog (tiered device→host→disk).

Reference: RapidsBufferCatalog.scala:551 (synchronousSpill walking a
priority-ordered store), SpillableColumnarBatch.scala (handle-based
re-materialization), RapidsHostMemoryStore/RapidsDiskStore.  The TPU
redesign: device columns are JAX arrays; spilling is ``jax.device_get`` to
pinned host numpy (XLA frees the HBM once the last reference drops), and the
host tier overflows to a pickle file under ``memory.spill.dir``.  PJRT has no
alloc-failure callback (SURVEY §7.3), so instead of reacting to a native
callback the catalog is consulted *before* device work
(:meth:`SpillCatalog.ensure_budget`) and *after* an XLA RESOURCE_EXHAUSTED
(memory/retry.py turns that into a spill-then-retry).
"""

from __future__ import annotations

import os
import pickle
import threading
import uuid
from typing import Dict, List, Optional

import numpy as np

from ..batch import ColumnBatch, DeviceColumn, HostStringColumn

__all__ = ["SpillableBatch", "SpillCatalog", "get_catalog",
           "PRIORITY_CACHE", "PRIORITY_LIVE", "PRIORITY_RUNS",
           "PRIORITY_RETRY"]

# Spill priority classes (LOWER spills first — SpillPriorities analog).
# The cross-query cache registers at PRIORITY_CACHE, strictly below every
# live-query registration, so ensure_budget always demotes cold cache
# entries to host before touching a running query's state.
PRIORITY_CACHE = 0   # spark_rapids_tpu/cache/ entries (cold, rebuildable)
PRIORITY_LIVE = 1    # materialized join sides, broadcasts, df.cache()
PRIORITY_RUNS = 2    # out-of-core sort runs
PRIORITY_RETRY = 10  # batches inside a with_retry attempt (hottest)


class SpillableBatch:
    """A handle to a batch that may live on device, host, or disk.

    States: DEVICE (ColumnBatch with live JAX arrays), HOST (numpy copies),
    DISK (pickle file).  ``get()`` re-materializes to device on demand.
    """

    DEVICE, HOST, DISK = "device", "host", "disk"

    def __init__(self, batch: ColumnBatch, catalog: "SpillCatalog",
                 priority: int = 0):
        self._batch: Optional[ColumnBatch] = batch
        self._host: Optional[dict] = None
        self._disk_path: Optional[str] = None
        self._catalog = catalog
        self.priority = priority  # lower spills first (SpillPriorities)
        self.state = self.DEVICE
        self.device_bytes = batch.device_size_bytes()
        # stable metadata: readable without re-materializing a spilled batch
        self.num_rows = batch.num_rows
        self._lock = threading.Lock()
        self._closed = False
        # leak canary (cudf MemoryCleaner analog): warn at GC time if the
        # handle was dropped without close() — disk files would orphan
        import weakref
        self._leak_cell = {"closed": False}
        weakref.finalize(self, _warn_leaked_handle, self._leak_cell,
                         self.device_bytes)

    # -- state moves --------------------------------------------------------------
    def spill_to_host(self) -> int:
        """DEVICE → HOST; returns bytes freed on device."""
        with self._lock:
            if self.state != self.DEVICE or self._closed:
                return 0
            b = self._batch
            cols = []
            for c in b.columns:
                if isinstance(c, DeviceColumn):
                    cols.append(("d", c.dtype, np.asarray(c.data),
                                 None if c.valid is None else
                                 np.asarray(c.valid)))
                else:
                    cols.append(("s", c.array))
            self._host = {
                "schema": b.schema, "cols": cols, "num_rows": b.num_rows,
                "sel": None if b.sel is None else np.asarray(b.sel),
            }
            self._batch = None  # drop device refs → XLA frees HBM
            self.state = self.HOST
            return self.device_bytes

    def spill_to_disk(self) -> int:
        """HOST → DISK; returns host bytes freed.

        The stored bytes are crc-stamped (``faults/integrity.py``) so a
        corrupted spill file is CAUGHT at re-materialization instead of
        silently feeding wrong data back into the query; a full disk
        types ``PermanentFault`` (fast-fail resubmittable) rather than
        burning the retry-backoff budget against ENOSPC."""
        with self._lock:
            if self.state != self.HOST or self._closed:
                return 0
            os.makedirs(self._catalog.spill_dir, exist_ok=True)
            path = os.path.join(self._catalog.spill_dir,
                                f"srt-spill-{uuid.uuid4().hex}.bin")
            payload = pickle.dumps(self._host, protocol=4)
            # nvcomp-LZ4 analog: compress the disk tier via the native codec
            from .. import native
            from ..faults import integrity
            from ..faults.recovery import check_disk_full
            comp = native.compress(payload) if self._catalog.compress_spill \
                else None
            try:
                with open(path, "wb") as f:
                    if comp is not None and len(comp) < len(payload):
                        stored = comp
                        f.write(b"SRTC")
                        f.write(len(payload).to_bytes(8, "little"))
                    else:
                        stored = payload
                        f.write(b"SRTR")
                    f.write(integrity.checksum(stored)
                            .to_bytes(4, "little"))
                    f.write(stored)
            except OSError as ex:
                try:
                    os.unlink(path)  # never leave a torn spill file
                except OSError:
                    pass
                check_disk_full(ex, "spill")
                raise
            freed = self.host_bytes()
            self._host = None
            self._disk_path = path
            self.state = self.DISK
            return freed

    def host_bytes(self) -> int:
        if self._host is None:
            return 0
        total = 0
        for c in self._host["cols"]:
            if c[0] == "d":
                total += c[2].nbytes
                if c[3] is not None:
                    total += c[3].nbytes
        return total

    def get(self) -> ColumnBatch:
        """Materialize on device (re-uploading if spilled).

        A disk-tier read verifies the crc stamped at spill time.  A
        mismatch on a CACHE-owned handle raises
        :class:`..faults.integrity.IntegrityFault` — the cache drops
        the entry and serves a MISS (recompute, never poison).  For a
        handle backing LIVE query state there is no durable copy to
        re-pull, so it fails typed ``QueryFaulted(resubmittable=True)``
        (permanent at this placement: a resubmission recomputes from
        source)."""
        import jax
        with self._lock:
            if self._closed:
                raise RuntimeError("spillable batch already closed")
            if self.state == self.DISK:
                from ..faults import integrity
                from ..faults.injector import INJECTOR
                with open(self._disk_path, "rb") as f:
                    magic = f.read(4)
                    raw_len = int.from_bytes(f.read(8), "little") \
                        if magic == b"SRTC" else 0
                    crc = int.from_bytes(f.read(4), "little")
                    stored = f.read()
                if INJECTOR.maybe_fire("spill.corrupt",
                                       desc=self._disk_path):
                    stored = integrity.flip(stored)
                try:
                    integrity.verify(stored, crc,
                                     what=f"spill file {self._disk_path}",
                                     point="spill")
                except integrity.IntegrityFault as ex:
                    # cache-owned handles (mark_long_lived — set ONLY by
                    # the cross-query cache) propagate IntegrityFault:
                    # the cache drops the entry and serves a MISS.
                    # (Priority can't discriminate: PRIORITY_CACHE == 0
                    # is also the default live registration.)
                    if not self._leak_cell.get("long_lived"):
                        from ..faults.recovery import QueryFaulted
                        raise QueryFaulted(
                            "spill",
                            f"spill file backing live query state is "
                            f"corrupt ({ex}); no durable copy exists at "
                            f"this placement", resubmittable=True) from ex
                    raise  # cache-owned: the cache drops + misses
                if magic == b"SRTC":
                    from .. import native
                    payload = native.decompress(stored, raw_len)
                else:
                    payload = stored
                self._host = pickle.loads(payload)
                os.unlink(self._disk_path)
                self._disk_path = None
                self.state = self.HOST
            if self.state == self.HOST:
                h = self._host
                cols = []
                for c in h["cols"]:
                    if c[0] == "d":
                        _, dtype, data, valid = c
                        cols.append(DeviceColumn(
                            dtype, jax.numpy.asarray(data),
                            None if valid is None else
                            jax.numpy.asarray(valid)))
                    else:
                        cols.append(HostStringColumn(c[1]))
                sel = h["sel"]
                self._batch = ColumnBatch(
                    h["schema"], cols, h["num_rows"],
                    None if sel is None else jax.numpy.asarray(sel))
                self._host = None
                self.state = self.DEVICE
                self._catalog._note_unspill(self)
            return self._batch

    def mark_long_lived(self) -> None:
        """Quiet the GC leak canary for handles owned by a process-
        lifetime structure (the cross-query cache): they legitimately
        outlive queries and whole sessions, and their owner closes them
        on eviction/invalidation/clear — a finalizer-time warning for
        a still-cached entry at interpreter exit is noise, not a leak.
        ``SpillCatalog.assert_no_leaks`` still counts them (tests drop
        the cache before asserting)."""
        self._leak_cell["long_lived"] = True

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._leak_cell["closed"] = True
            self._batch = None
            self._host = None
            if self._disk_path:
                try:
                    os.unlink(self._disk_path)
                except OSError:
                    pass
                self._disk_path = None
        self._catalog.unregister(self)


_SHUTTING_DOWN: List[bool] = []

import atexit as _atexit

_atexit.register(_SHUTTING_DOWN.append, True)


def _warn_leaked_handle(cell: dict, device_bytes: int) -> None:
    if _SHUTTING_DOWN:
        return  # interpreter exit: cached frames may legitimately be live
    if cell.get("long_lived"):
        return  # cache-owned handle: closed by eviction/clear, not GC
    if not cell.get("closed"):
        import logging
        logging.getLogger("spark_rapids_tpu").warning(
            "spillable batch handle leaked (never closed; ~%d device "
            "bytes) — a with_retry/operator is missing a close()",
            device_bytes)


class SpillCatalog:
    """Tracks spillable batches; spills lowest-priority first to stay under
    the device budget (RapidsBufferCatalog.synchronousSpill analog)."""

    def __init__(self, device_budget: int, host_budget: int,
                 spill_dir: str = "/tmp/srt_spill",
                 compress_spill: bool = True):
        self.device_budget = device_budget
        self.host_budget = host_budget
        self.spill_dir = spill_dir
        self.compress_spill = compress_spill
        self._lock = threading.Lock()
        self._entries: List[SpillableBatch] = []
        self.spilled_device_bytes = 0
        self.spilled_host_bytes = 0
        self.spill_count = 0

    # -- registration -------------------------------------------------------------
    def register(self, batch: ColumnBatch, priority: int = 0) -> SpillableBatch:
        sb = SpillableBatch(batch, self, priority)
        with self._lock:
            self._entries.append(sb)
        self.ensure_budget()
        return sb

    def unregister(self, sb: SpillableBatch) -> None:
        with self._lock:
            try:
                self._entries.remove(sb)
            except ValueError:
                pass

    # -- leak detection (MemoryCleaner / dev/host_memory_leaks analog) ------------
    def open_handles(self) -> int:
        """Registered handles never closed — each pins device/host/disk
        resources; a nonzero count at query end is a leak."""
        with self._lock:
            return len(self._entries)

    def assert_no_leaks(self) -> None:
        with self._lock:
            leaked = list(self._entries)
        if leaked:
            states = [(e.state, e.device_bytes) for e in leaked]
            raise AssertionError(
                f"{len(leaked)} spillable batch handle(s) leaked: {states}")

    def _note_unspill(self, sb: SpillableBatch) -> None:
        # re-materialized batch counts against the device budget again
        pass

    # -- accounting ---------------------------------------------------------------
    def device_bytes_in_use(self) -> int:
        with self._lock:
            return sum(e.device_bytes for e in self._entries
                       if e.state == SpillableBatch.DEVICE)

    def host_bytes_in_use(self) -> int:
        with self._lock:
            return sum(e.host_bytes() for e in self._entries
                       if e.state == SpillableBatch.HOST)

    # -- spilling -----------------------------------------------------------------
    def ensure_budget(self, extra_bytes: int = 0) -> int:
        """Spill until (tracked device bytes + extra) fits the budget."""
        freed = 0
        while (self.device_bytes_in_use() + extra_bytes > self.device_budget):
            if not self.spill_one_device():
                break
            freed += 1
        while self.host_bytes_in_use() > self.host_budget:
            if not self._spill_one_host():
                break
        return freed

    def spill_one_device(self) -> bool:
        """Spill the lowest-priority device-resident batch; False if none."""
        with self._lock:
            cands = [e for e in self._entries
                     if e.state == SpillableBatch.DEVICE]
            if not cands:
                return False
            victim = min(cands, key=lambda e: e.priority)
        freed = victim.spill_to_host()
        if freed:
            self.spilled_device_bytes += freed
            self.spill_count += 1
            from ..utils.metrics import QueryStats, TaskMetrics
            TaskMetrics.get().spill_to_host_bytes += freed
            TaskMetrics.get().spill_count += 1
            # query-scoped: the running query whose pressure forced the
            # demotion carries the spill-degrade signal the admission
            # layer's AIMD controller and cost model consume
            QueryStats.get().spill_events += 1
        return freed > 0

    def _spill_one_host(self) -> bool:
        with self._lock:
            cands = [e for e in self._entries
                     if e.state == SpillableBatch.HOST]
            if not cands:
                return False
            victim = min(cands, key=lambda e: e.priority)
        freed = victim.spill_to_disk()
        if freed:
            self.spilled_host_bytes += freed
            from ..utils.metrics import TaskMetrics
            TaskMetrics.get().spill_to_disk_bytes += freed
        return freed > 0

    def spill_all_device(self) -> int:
        """Emergency: spill everything device-resident (OOM reaction)."""
        n = 0
        while self.spill_one_device():
            n += 1
        return n


_catalog: Optional[SpillCatalog] = None
_catalog_lock = threading.Lock()


def get_catalog(conf=None) -> SpillCatalog:
    """Session-level catalog; budgets come from the conf on first use."""
    global _catalog
    with _catalog_lock:
        if _catalog is None:
            if conf is None:
                from ..config import TpuConf
                conf = TpuConf()
            device_budget = _device_budget(conf)
            _catalog = SpillCatalog(
                device_budget,
                conf["spark.rapids.tpu.memory.host.spillStorageSize"],
                conf["spark.rapids.tpu.memory.spill.dir"],
                compress_spill=conf["spark.rapids.tpu.shuffle.compress"])
        return _catalog


def reset_catalog() -> None:
    global _catalog
    with _catalog_lock:
        _catalog = None


def _device_budget(conf) -> int:
    """poolFraction × the session device's memory."""
    from ..runtime.device import DeviceManager, device_memory_bytes
    info = DeviceManager.info()
    if info is not None:
        total = info.memory_bytes
    else:
        import jax
        total = device_memory_bytes(jax.devices()[0])
    return int(total * conf["spark.rapids.tpu.memory.tpu.poolFraction"])
