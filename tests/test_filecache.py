"""Device-tier file cache: hit path correctness, isolation, OOM clearing.

Reference model: filecache.md (decoded-file cache) + the keep-batches-
resident idea of RapidsShuffleInternalManagerBase.scala:897; the OOM
interplay mirrors DeviceMemoryEventHandler.onAllocFailure freeing every
non-catalog reference it can reach.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.io.filecache import (clear_file_cache,
                                           get_device_cache, get_file_cache)
from spark_rapids_tpu.sql import functions as F


@pytest.fixture()
def pq_file(tmp_path):
    pdf = pd.DataFrame({
        "a": np.arange(1000, dtype=np.int64),
        "b": np.linspace(0.0, 1.0, 1000),
    })
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.Table.from_pandas(pdf, preserve_index=False), path)
    return path, pdf


def _cached_session():
    s = srt.Session.get_or_create()
    s.conf.set("spark.rapids.tpu.sql.fileCache.enabled", True)
    s.conf.set("spark.rapids.tpu.sql.fileCache.deviceTier", True)
    return s


def test_device_cache_hit_same_results(pq_file):
    path, pdf = pq_file
    clear_file_cache()
    s = _cached_session()
    try:
        df = s.read_parquet(path)
        q = lambda: df.select((F.col("a") * 2).alias("x")).collect()
        first = q()
        cache = get_device_cache(1 << 30)
        assert cache.hits + cache.misses > 0, "device tier never consulted"
        second = q()
        assert cache.hits > 0, "second scan should hit the device tier"
        assert [tuple(r) for r in first] == [tuple(r) for r in second]
        expected = [(int(a) * 2,) for a in pdf["a"]]
        assert [tuple(r) for r in second] == expected
    finally:
        s.conf.set("spark.rapids.tpu.sql.fileCache.enabled", False)
        clear_file_cache()


def test_device_cache_entries_isolated_from_consumers(pq_file):
    """A filter narrowing one query's selection must not leak into the
    cached batches another query will receive."""
    path, pdf = pq_file
    clear_file_cache()
    s = _cached_session()
    try:
        df = s.read_parquet(path)
        filtered = df.filter(F.col("a") < 10).select("a").collect()
        assert len(filtered) == 10
        full = df.select("a").collect()
        assert len(full) == len(pdf)
    finally:
        s.conf.set("spark.rapids.tpu.sql.fileCache.enabled", False)
        clear_file_cache()


def test_device_cache_cleared_on_oom_path(pq_file):
    """device_op's OOM handler must drop HBM-cached scan batches — they are
    invisible to the spill catalog, so spilling alone cannot free them."""
    path, _ = pq_file
    clear_file_cache()
    s = _cached_session()
    try:
        df = s.read_parquet(path)
        df.select("a").collect()  # populate
        cache = get_device_cache(1 << 30)
        assert cache._bytes > 0

        import jax

        from spark_rapids_tpu.memory.retry import RetryOOM, device_op

        def boom():
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: out of memory")

        with pytest.raises(RetryOOM):
            device_op(None, boom)
        assert cache._bytes == 0, "OOM path must clear the device tier"
    finally:
        s.conf.set("spark.rapids.tpu.sql.fileCache.enabled", False)
        clear_file_cache()


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
        s.conf.set("spark.rapids.tpu.sql.fileCache.enabled", False)
        clear_file_cache()
