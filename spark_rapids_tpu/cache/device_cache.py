"""Query-spanning device cache: scan batches + broadcast builds, one
budget, eviction into spill.

The concurrent scheduler (PR 3) made N queries share the chip, but every
admitted query still paid full price: parquet decode, Arrow→numpy, H2D
upload, and broadcast hash-build redone from scratch even when four
tenants replay the same tables back-to-back.  This module keeps that
work's RESULTS resident across queries:

  * **scan tier** — device-resident ``ColumnBatch`` lists keyed by
    (source fingerprint, projection, pushed filters): a hit skips decode
    AND upload; a *partial* hit (a cached superset projection) slices
    columns instead of re-uploading;
  * **broadcast tier** — materialized build sides keyed by the build
    subtree's structural fingerprint, shared across concurrent queries
    via refcounted handles; entries also carry the dense-join probed
    stats so a reuse hit skips the build's blocking stats fetches;
  * **eviction into spill, not OOM** — every cached batch is registered
    with the ``SpillCatalog`` at :data:`CACHE_PRIORITY` (below every
    live-query priority), so ``ensure_budget`` demotes cold cache
    entries to host/disk BEFORE touching live query state; the cache's
    own byte budget (``sql.cache.maxBytes``) drops LRU entries outright,
    but never one a query currently holds (refcounts).

Entries are held through :class:`..memory.spill.SpillableBatch` handles,
which re-materialize transparently after a spill demotion.  All
lookups/insertions key through
:mod:`.keys` (the srtlint ``cache-keys`` pass enforces it).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from ..utils import tracing
from ..utils.metrics import QueryStats
from .keys import CacheKey, path_covers

__all__ = ["QueryCache", "CacheEntry", "CachedBuildHandle",
           "get_query_cache", "clear_query_cache", "invalidate_path",
           "batch_bytes", "set_serve_only", "serve_only"]

# Brownout serve-only mode (service/admission.BrownoutController):
# while set, the cache SERVES hits but adopts no new fills — during a
# degraded-capacity episode recovery traffic must not evict the
# survivors' hot working set from HBM.  A one-way-per-episode flag
# toggled on brownout enter/exit; fills skipped while set are counted
# (``fills_paused`` in the snapshot).
_SERVE_ONLY = threading.Event()


def set_serve_only(flag: bool) -> None:
    if flag:
        _SERVE_ONLY.set()
    else:
        _SERVE_ONLY.clear()


def serve_only() -> bool:
    return _SERVE_ONLY.is_set()

# spill priority of cached batches: BELOW every live-query registration
# (memory/spill.py priority classes), so SpillCatalog.ensure_budget
# always demotes the cache before live state
from ..memory.spill import PRIORITY_CACHE as CACHE_PRIORITY


def _reraise(ex: BaseException):
    raise ex


def batch_bytes(b) -> int:
    """Device + host-arrow footprint of one batch (budget accounting)."""
    total = b.device_size_bytes()
    for c in b.columns:
        arr = getattr(c, "array", None)  # HostStringColumn payloads
        if arr is not None:
            total += arr.nbytes
    return total


class CacheEntry:
    """One cached value: spill-registered batch handles + metadata.

    ``refs`` counts live consumers; an entry with refs > 0 is never
    dropped (budget eviction and invalidation defer the close to the
    last ``release``).  ``stats`` carries per-join probed build stats
    (host arrays) for the broadcast tier's dense fast path.
    """

    def __init__(self, key: CacheKey, handles: list, nbytes: int):
        self.key = key
        self.cols = key.cols  # projection this entry holds (None = all)
        self.handles = handles  # List[SpillableBatch]
        self.nbytes = nbytes
        self.refs = 0
        self.dead = False  # invalidated/evicted while referenced
        self.created_t = time.monotonic()
        self.hits = 0
        self.stats: Dict[tuple, object] = {}
        self._lock = threading.Lock()

    def cols_superset(self, want: set) -> bool:
        """Can this entry serve a scan projecting ``want`` by slicing?"""
        if self.dead:
            return False
        if self.cols is None:
            return True  # all columns cached
        return want <= set(self.cols)

    # -- probed-stats side channel (broadcast tier) -------------------------------
    def get_stat(self, skey: tuple):
        with self._lock:
            return self.stats.get(skey)

    def put_stat(self, skey: tuple, value) -> None:
        with self._lock:
            self.stats[skey] = value

    def _close(self) -> None:
        for h in self.handles:
            h.close()
        self.handles = []
        self.stats.clear()


class CachedBuildHandle:
    """Refcounted view of a broadcast-tier entry with the
    ``SpillableBatch``-handle surface the join execs expect: ``get()``
    materializes the cached build on device; ``close()`` releases the
    reference (the entry itself outlives the query)."""

    def __init__(self, cache: "QueryCache", entry: CacheEntry):
        self._cache = cache
        self.cache_entry = entry
        self._closed = False

    def get(self):
        from ..faults.integrity import IntegrityFault
        from ..faults.recovery import QueryFaulted
        try:
            return self.cache_entry.handles[0].get()
        except IntegrityFault as ex:
            # a spilled build entry whose crc failed at
            # re-materialization: drop it so no FUTURE lookup hits it,
            # then fail this query typed + resubmittable — the retry
            # misses and rebuilds from source.  (A lazy hit cannot
            # degrade to a miss: the join already holds this handle.)
            with self._cache._lock:
                if not self.cache_entry.dead:
                    self._cache._drop(self.cache_entry, "integrity")
            raise QueryFaulted(
                "cache", f"cached broadcast build is corrupt ({ex}); "
                f"entry dropped — a resubmission rebuilds from source",
                resubmittable=True) from ex

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._cache.release(self.cache_entry)


class QueryCache:
    """The process-wide cross-query cache (both tiers, one byte budget)."""

    def __init__(self, max_bytes: int, ttl_ms: int = 0):
        self.max_bytes = max_bytes
        self.ttl_ms = ttl_ms
        self._lock = threading.RLock()
        self._entries: "OrderedDict[CacheKey, CacheEntry]" = OrderedDict()
        self._groups: Dict[tuple, List[CacheEntry]] = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.fills_paused = 0  # fills skipped while brownout serve-only

    # -- configuration ------------------------------------------------------------
    def configure(self, max_bytes: int, ttl_ms: int) -> None:
        with self._lock:
            self.max_bytes = max_bytes
            self.ttl_ms = ttl_ms
            self._evict_to_budget()

    # -- internal bookkeeping (caller holds the lock) ------------------------------
    def _index(self, entry: CacheEntry) -> None:
        self._entries[entry.key] = entry
        self._groups.setdefault(entry.key.group(), []).append(entry)
        self._bytes += entry.nbytes

    def _unindex(self, entry: CacheEntry) -> None:
        self._entries.pop(entry.key, None)
        grp = self._groups.get(entry.key.group())
        if grp is not None:
            try:
                grp.remove(entry)
            except ValueError:
                pass
            if not grp:
                self._groups.pop(entry.key.group(), None)
        self._bytes -= entry.nbytes

    def _drop(self, entry: CacheEntry, reason: str) -> None:
        """Remove from the index; close now or defer to the last ref."""
        self._unindex(entry)
        entry.dead = True
        self.evictions += 1
        s = QueryStats.get()
        s.cache_evictions += 1
        s.cache_evict_bytes += entry.nbytes
        tracing.mark(None, "cache:evict", "cache", tier=entry.key.tier,
                     bytes=entry.nbytes, reason=reason)
        if entry.refs == 0:
            entry._close()

    def _evict_to_budget(self, extra: int = 0) -> None:
        while self._bytes + extra > self.max_bytes:
            victim = None
            for e in self._entries.values():  # LRU order
                if e.refs == 0:
                    victim = e
                    break
            if victim is None:
                break  # everything pinned: over-budget until releases
            self._drop(victim, "budget")

    def _expired(self, entry: CacheEntry) -> bool:
        return self.ttl_ms > 0 and \
            (time.monotonic() - entry.created_t) * 1000.0 > self.ttl_ms

    def _hit(self, entry: CacheEntry, op_id, nbytes: int, tier: str,
             partial: bool = False, unspilled: bool = False) -> None:
        self._entries.move_to_end(entry.key)
        entry.refs += 1
        entry.hits += 1
        self.hits += 1
        s = QueryStats.get()
        s.cache_hits += 1
        s.cache_hit_bytes += nbytes
        tracing.mark(op_id, "cache:hit", "cache", tier=tier, bytes=nbytes,
                     partial=partial, unspilled=unspilled)

    def _miss(self, op_id, tier: str) -> None:
        self.misses += 1
        QueryStats.get().cache_misses += 1
        tracing.mark(op_id, "cache:miss", "cache", tier=tier)

    def _note_fill_paused(self, op_id, tier: str) -> None:
        with self._lock:
            self.fills_paused += 1
        tracing.mark(op_id, "cache:fill-paused", "cache", tier=tier,
                     reason="brownout")

    def _check_faults(self, op_id, tier: str) -> bool:
        """``cache.lookup`` injection point.  A transient fault in the
        cache tier must never fail the query: with recovery enabled the
        lookup degrades to a MISS (the caller recomputes; the entry is
        untouched and serves the next lookup).  With recovery disabled
        (fail-fast debugging) the typed QueryFaulted propagates.
        Returns False when the lookup should report a miss."""
        from ..faults.injector import INJECTOR
        from ..faults.recovery import (TransientFault, recovery_enabled,
                                       transient_retry)
        try:
            INJECTOR.maybe_raise("cache.lookup", desc=tier)
        except TransientFault as ex:
            if not recovery_enabled():
                # route through the retry driver with retries exhausted
                # so the failure carries the standard typed history
                transient_retry(None, "cache.lookup",
                                _reraise, ex, desc=tier)
            self._miss(op_id, tier)
            return False
        return True

    # -- scan tier ----------------------------------------------------------------
    def lookup_scan(self, key: CacheKey, schema,
                    op_id: Optional[str] = None
                    ) -> Optional[Tuple[CacheEntry, list]]:
        """Serve a scan from cache: exact projection match, else a cached
        SUPERSET projection sliced down to ``schema``'s columns.  Returns
        (entry, fresh ColumnBatch wrappers) with one reference taken —
        the caller MUST :meth:`release` the entry (use try/finally; the
        consumer may abandon the batch stream mid-way)."""
        from ..batch import ColumnBatch
        if not self._check_faults(op_id, "scan"):
            return None
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and self._expired(entry):
                self._drop(entry, "ttl")
                entry = None
            partial = False
            if entry is None and key.cols is not None:
                want = set(key.cols)
                for cand in self._groups.get(key.group(), ()):
                    if self._expired(cand):
                        continue
                    if cand.cols_superset(want):
                        entry = cand
                        partial = True
                        break
            if entry is None:
                self._miss(op_id, "scan")
                return None
            entry.refs += 1  # pin across the (unlocked) materialization
        try:
            from ..faults import integrity
            from ..faults.injector import INJECTOR
            if INJECTOR.maybe_fire("cache.corrupt", desc="scan"):
                integrity.fail(f"cache scan entry {key.group()}",
                               point="cache")
            spilled = any(h.state != h.DEVICE for h in entry.handles)
            names = list(key.cols) if key.cols is not None else None
            out: list = []
            served = 0
            for h in entry.handles:
                b = h.get()
                if partial:
                    idxs = [b.schema.index_of(n) for n in names]
                    cols = [b.columns[i] for i in idxs]
                else:
                    cols = b.columns
                # fresh wrapper: consumers can't perturb cached row
                # accounting
                out.append(ColumnBatch(schema, cols, b.num_rows, b.sel))
                served += batch_bytes(out[-1])
        except integrity.IntegrityFault:
            # corrupt cache entry (injected, or a spilled copy whose crc
            # failed at re-materialization): DROP it and serve a MISS —
            # the caller recomputes from source; a poisoned hit is the
            # one outcome a cache must never produce
            with self._lock:
                entry.refs -= 1
                if not entry.dead:
                    self._drop(entry, "integrity")
            self._miss(op_id, "scan")
            return None
        except BaseException:
            self.release(entry)
            raise
        with self._lock:
            entry.refs -= 1  # swap the pin for the recorded hit ref
            self._hit(entry, op_id, served, "scan", partial=partial,
                      unspilled=spilled)
        return entry, out

    def insert_scan(self, key: CacheKey, batches: list,
                    op_id: Optional[str] = None,
                    conf=None) -> Optional[CacheEntry]:
        """Adopt a completed scan's uploaded batches.  Batches are
        registered spillable at :data:`CACHE_PRIORITY`; over-budget
        inserts evict LRU unpinned entries first and give up (returning
        None) when the value alone exceeds the budget."""
        from ..faults.recovery import TransientFault
        from ..memory.spill import get_catalog
        if _SERVE_ONLY.is_set():
            self._note_fill_paused(op_id, "scan")
            return None
        nbytes = sum(batch_bytes(b) for b in batches)
        if nbytes > self.max_bytes or not batches:
            return None
        catalog = get_catalog(conf)
        handles: list = []
        try:
            from ..faults.injector import INJECTOR
            for b in batches:
                INJECTOR.maybe_raise("cache.lookup", desc="scan-fill")
                h = catalog.register(b, priority=CACHE_PRIORITY)
                handles.append(h)
                h.mark_long_lived()
        except BaseException as ex:
            # a faulted fill NEVER leaves a poisoned (half-registered)
            # entry: close what was registered and either skip caching
            # (transient — the query proceeds uncached) or re-raise
            for h in handles:
                h.close()
            if isinstance(ex, TransientFault):
                tracing.mark(op_id, "cache:fill-abandoned", "cache",
                             tier="scan")
                return None
            raise
        entry = CacheEntry(key, handles, nbytes)
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None and not self._expired(existing):
                # lost a populate race: keep the warm entry
                entry._close()
                return existing
            if existing is not None:
                self._drop(existing, "ttl")
            self._evict_to_budget(extra=nbytes)
            self._index(entry)
        return entry

    # -- broadcast tier -----------------------------------------------------------
    def lookup_broadcast(self, key: CacheKey,
                         op_id: Optional[str] = None
                         ) -> Optional[CachedBuildHandle]:
        if not self._check_faults(op_id, "broadcast"):
            return None
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and self._expired(entry):
                self._drop(entry, "ttl")
                entry = None
            if entry is not None:
                from ..faults.injector import INJECTOR
                if INJECTOR.maybe_fire("cache.corrupt", desc="broadcast"):
                    # injected corrupt build entry: drop-and-miss (the
                    # query materializes its own build — never a
                    # poisoned join side)
                    self._drop(entry, "integrity")
                    entry = None
                    from ..faults import integrity
                    try:
                        integrity.fail(f"cache broadcast entry "
                                       f"{key.group()}", point="cache")
                    except integrity.IntegrityFault:
                        pass  # accounted; serve the miss below
            if entry is None:
                self._miss(op_id, "broadcast")
                return None
            spilled = any(h.state != h.DEVICE for h in entry.handles)
            self._hit(entry, op_id, entry.nbytes, "broadcast",
                      unspilled=spilled)
            return CachedBuildHandle(self, entry)

    def insert_broadcast(self, key: CacheKey, handle,
                         op_id: Optional[str] = None) -> object:
        """Adopt a freshly materialized build side (a ``SpillableBatch``
        handle).  The handle's spill priority drops to
        :data:`CACHE_PRIORITY` (it is cache state now) and the caller
        gets a refcounted :class:`CachedBuildHandle` in exchange.  When
        the build exceeds the budget the handle is returned unwrapped —
        the query owns it exactly as before the cache existed."""
        from ..faults.injector import INJECTOR
        from ..faults.recovery import TransientFault
        if _SERVE_ONLY.is_set():
            self._note_fill_paused(op_id, "broadcast")
            return handle
        nbytes = getattr(handle, "device_bytes", 0)
        if nbytes > self.max_bytes:
            return handle
        try:
            INJECTOR.maybe_raise("cache.lookup", desc="broadcast-fill")
        except TransientFault:
            # faulted fill: the query keeps sole ownership of its build
            # handle exactly as before the cache existed — no entry is
            # indexed, nothing is poisoned
            tracing.mark(op_id, "cache:fill-abandoned", "cache",
                         tier="broadcast")
            return handle
        handle.priority = CACHE_PRIORITY
        handle.mark_long_lived()
        entry = CacheEntry(key, [handle], nbytes)
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None and not self._expired(existing):
                # lost a populate race: adopt the warm entry, drop the
                # duplicate build (never leak a registered handle)
                handle.close()
                existing.refs += 1
                return CachedBuildHandle(self, existing)
            if existing is not None:
                self._drop(existing, "ttl")
            self._evict_to_budget(extra=nbytes)
            self._index(entry)
            entry.refs += 1
        return CachedBuildHandle(self, entry)

    # -- reference counting -------------------------------------------------------
    def release(self, entry: CacheEntry) -> None:
        with self._lock:
            entry.refs -= 1
            if entry.refs <= 0 and entry.dead:
                entry._close()

    # -- invalidation + pressure ----------------------------------------------------
    def invalidate_path(self, prefix: str) -> int:
        """Drop every entry whose source files live under ``prefix``
        (write hooks: io/writers, Delta commits).  Pinned entries finish
        their in-flight reads and close on the last release; no NEW
        lookup can hit them once this returns."""
        with self._lock:
            victims = [e for e in self._entries.values()
                       if path_covers(e.key, prefix)]
            for e in victims:
                self._drop(e, "invalidate")
            return len(victims)

    def drop_unpinned(self) -> int:
        """Memory-pressure valve (OOM retry, scheduler admission): drop
        every entry no query currently holds.  Device bytes already
        demote to host via the spill catalog first; this frees the host
        copies too."""
        with self._lock:
            victims = [e for e in self._entries.values() if e.refs == 0]
            for e in victims:
                self._drop(e, "pressure")
            return len(victims)

    def clear(self) -> None:
        with self._lock:
            for e in list(self._entries.values()):
                self._drop(e, "clear")

    # -- introspection ------------------------------------------------------------
    def entry_count(self) -> int:
        with self._lock:
            return len(self._entries)

    def bytes_cached(self) -> int:
        with self._lock:
            return self._bytes

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "fills_paused": self.fills_paused,
                    "serve_only": _SERVE_ONLY.is_set(),
                    "max_bytes": self.max_bytes}


_cache: Optional[QueryCache] = None
_cache_lock = threading.Lock()


def get_query_cache(conf=None) -> QueryCache:
    """The process singleton; budgets/TTL track the conf on every call
    (resize-in-place, never a wholesale drop of a warmed cache)."""
    global _cache
    max_bytes = ttl = None
    if conf is not None:
        max_bytes = conf["spark.rapids.tpu.sql.cache.maxBytes"]
        ttl = conf["spark.rapids.tpu.sql.cache.ttlMs"]
    with _cache_lock:
        if _cache is None:
            _cache = QueryCache(max_bytes if max_bytes is not None
                                else 2 << 30,
                                ttl if ttl is not None else 0)
        elif max_bytes is not None and (
                _cache.max_bytes != max_bytes or _cache.ttl_ms != ttl):
            _cache.configure(max_bytes, ttl)
        return _cache


def clear_query_cache() -> None:
    with _cache_lock:
        if _cache is not None:
            _cache.clear()


def invalidate_path(path) -> int:
    """Module-level invalidation hook for the write paths: a no-op until
    the cache has been instantiated."""
    with _cache_lock:
        cache = _cache
    if cache is None or not isinstance(path, str):
        return 0
    return cache.invalidate_path(path)
