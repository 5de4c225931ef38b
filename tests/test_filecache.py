"""The two scan caches: the host decoded-file tier (``sql.fileCache.enabled``,
io/filecache.py) and the device scan tier of the cross-query cache
(``sql.cache.enabled`` + ``sql.cache.scan.enabled``, cache/): hit path
correctness, isolation of the entries from their consumers, and the
device tier's behaviour on the OOM path.

Reference model: filecache.md (decoded-file cache) + the keep-batches-
resident idea of RapidsShuffleInternalManagerBase.scala:897; the OOM
interplay mirrors DeviceMemoryEventHandler.onAllocFailure: cached device
bytes are catalog-registered, so the spill that precedes a retry reaches
them.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.cache import clear_query_cache, get_query_cache
from spark_rapids_tpu.io.filecache import clear_file_cache, get_file_cache
from spark_rapids_tpu.sql import functions as F

_FILE_TIER = "spark.rapids.tpu.sql.fileCache.enabled"
_SCAN_TIER = "spark.rapids.tpu.sql.cache.enabled"


@pytest.fixture()
def pq_file(tmp_path):
    pdf = pd.DataFrame({
        "a": np.arange(1000, dtype=np.int64),
        "b": np.linspace(0.0, 1.0, 1000),
    })
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.Table.from_pandas(pdf, preserve_index=False), path)
    return path, pdf


@pytest.fixture(params=["file", "scan"])
def tier(request):
    """A session with one tier on, and the cache object whose ``hits``
    that tier counts."""
    s = srt.Session.get_or_create()
    if request.param == "file":
        key = _FILE_TIER
        max_bytes = s._tpu_conf()["spark.rapids.tpu.sql.fileCache.maxBytes"]

        def cache():
            return get_file_cache(max_bytes)
    else:
        key, cache = _SCAN_TIER, get_query_cache
    clear_file_cache()  # the query cache too
    s.conf.set(key, True)
    try:
        yield s, cache
    finally:
        s.conf.unset(key)
        clear_file_cache()


def _cached_session():
    s = srt.Session.get_or_create()
    s.conf.set(_FILE_TIER, True)
    return s


def test_cache_hit_same_results(pq_file, tier):
    path, pdf = pq_file
    s, cache = tier
    df = s.read_parquet(path)
    q = lambda: df.select((F.col("a") * 2).alias("x")).collect()
    first = q()
    assert cache().hits + cache().misses > 0, "tier never consulted"
    hits = cache().hits
    second = q()
    assert cache().hits > hits, "second scan should hit the tier"
    assert [tuple(r) for r in first] == [tuple(r) for r in second]
    expected = [(int(a) * 2,) for a in pdf["a"]]
    assert [tuple(r) for r in second] == expected


def test_cache_entries_isolated_from_consumers(pq_file, tier):
    """A filter narrowing one query's selection must not leak into the
    cached entry another query will receive."""
    path, pdf = pq_file
    s, cache = tier
    df = s.read_parquet(path)
    # not a comparison the scan can take as a pushed predicate: both
    # queries read one entry
    filtered = df.filter(F.col("a") * 2 < 20).select("a").collect()
    assert len(filtered) == 10
    hits = cache().hits
    full = df.select("a").collect()
    assert cache().hits > hits, "the second query never read the entry"
    assert len(full) == len(pdf)


def test_scan_tier_demoted_on_oom_path(pq_file):
    """device_op's OOM handler needs no special case for cached scan
    batches: they are catalog-registered, so the spill that precedes the
    retry demotes an entry a query still holds, unheld ones are dropped,
    and the next scan of a demoted entry re-materializes it."""
    import jax

    from spark_rapids_tpu.memory.retry import RetryOOM, device_op
    from spark_rapids_tpu.memory.spill import SpillableBatch
    path, _ = pq_file
    s = srt.Session.get_or_create()
    clear_query_cache()
    s.conf.set(_SCAN_TIER, True)
    try:
        df = s.read_parquet(path)
        first = df.select("a").collect()  # populate
        qc = get_query_cache()
        entry = next(iter(qc._entries.values()))
        assert entry.handles and all(
            h.state == SpillableBatch.DEVICE for h in entry.handles)
        entry.refs += 1  # a query is reading it

        def boom():
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: out of memory")

        try:
            with pytest.raises(RetryOOM):
                device_op(None, boom)
            assert qc.entry_count() == 1, "a held entry must survive"
            assert all(h.state != SpillableBatch.DEVICE
                       for h in entry.handles), "OOM path must demote it"
        finally:
            qc.release(entry)
        hits = qc.hits
        assert df.select("a").collect() == first
        assert qc.hits > hits
        with pytest.raises(RetryOOM):
            device_op(None, boom)
        assert qc.entry_count() == 0, "an unheld entry is dropped"
    finally:
        s.conf.unset(_SCAN_TIER)
        clear_query_cache()


def test_stale_file_invalidates(pq_file, tmp_path):
    """Rewriting the file (new mtime/size) must miss the old entry."""
    path, pdf = pq_file
    clear_file_cache()
    s = _cached_session()
    try:
        df = s.read_parquet(path)
        r1 = df.agg(F.sum(F.col("a"))).collect()[0][0]
        assert r1 == int(pdf["a"].sum())
        pdf2 = pd.DataFrame({"a": np.arange(10, dtype=np.int64),
                             "b": np.zeros(10)})
        import os
        import time
        time.sleep(0.01)
        pq.write_table(pa.Table.from_pandas(pdf2, preserve_index=False), path)
        os.utime(path)
        df2 = s.read_parquet(path)
        r2 = df2.agg(F.sum(F.col("a"))).collect()[0][0]
        assert r2 == int(pdf2["a"].sum())
    finally:
        s.conf.unset(_FILE_TIER)
        clear_file_cache()
