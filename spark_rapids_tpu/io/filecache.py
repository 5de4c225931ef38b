"""In-memory decoded-file cache (the reference's FileCache analog).

The reference ships a local-disk cache of remote input files (hook points in
Plugin.scala:379 ``FileCache.init``; docs/additional-functionality/filecache.md)
so repeated scans skip the slow fetch.  On TPU the expensive step is not the
fetch but the host-side parquet *decode*; this cache keeps decoded Arrow
tables keyed by (path, mtime, size, columns, row-groups) with LRU eviction
under a byte budget, so repeated scans skip decode and go straight to the
host→HBM upload.

The device tier above it (uploaded batches kept in HBM across queries) is
the cross-query cache in ``spark_rapids_tpu/cache/``, which the spill
catalog sees.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import List, Optional, Tuple

__all__ = ["FileCache", "get_file_cache", "clear_file_cache"]


class FileCache:
    """Byte-budgeted LRU of decoded Arrow tables keyed by file identity."""

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, Tuple[int, list]]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key_for(path: str, columns, row_groups) -> Optional[tuple]:
        try:
            st = os.stat(path)
        except OSError:
            return None
        cols = tuple(columns) if columns is not None else None
        rgs = tuple(row_groups) if row_groups is not None else None
        return (os.path.abspath(path), st.st_mtime_ns, st.st_size, cols, rgs)

    def _entry_bytes(self, values: list) -> int:
        return sum(t.nbytes for t in values)

    def get(self, key: tuple) -> Optional[list]:
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return hit[1]

    def put(self, key: tuple, values: list) -> None:
        nbytes = self._entry_bytes(values)
        if nbytes > self.max_bytes:
            return
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[0]
            self._entries[key] = (nbytes, values)
            self._bytes += nbytes
            self._evict_to_budget()

    def _evict_to_budget(self) -> None:
        # caller holds self._lock
        while self._bytes > self.max_bytes and self._entries:
            _, (sz, _v) = self._entries.popitem(last=False)
            self._bytes -= sz

    def set_max_bytes(self, max_bytes: int) -> None:
        """Resize in place (evict down if shrinking) instead of dropping the
        warmed cache wholesale."""
        with self._lock:
            self.max_bytes = max_bytes
            self._evict_to_budget()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0


_cache: Optional[FileCache] = None
_cache_lock = threading.Lock()


def get_file_cache(max_bytes: int) -> FileCache:
    global _cache
    with _cache_lock:
        if _cache is None:
            _cache = FileCache(max_bytes)
        elif _cache.max_bytes != max_bytes:
            _cache.set_max_bytes(max_bytes)
        return _cache


def clear_file_cache() -> None:
    with _cache_lock:
        if _cache is not None:
            _cache.clear()
    # the cross-query cache (spark_rapids_tpu/cache/) composes ABOVE this
    # host tier — "drop every cached scan" should mean both layers
    from ..cache import clear_query_cache
    clear_query_cache()
