"""Sync-budget regression tests (VERDICT r4 item 2).

Every blocking device→host fetch in the engine routes through
``utils.metrics.fetch`` (each stalls the dispatch front until the
device drains), so the per-operator budgets below are the engine's
latency contract: a change that adds a fetch to the join/agg/collect hot path
fails here before it ships as a 2x suite regression.

Async fetches (``utils.metrics.fetch_async``: the D2H copy rides behind
the dispatch front) are EXCLUDED from the blocking budget but still
traced and byte/wait-accounted through the same choke point — the
budget measures stalls, not transfers.

Reference analog: the sync discipline that GpuExec operators get from
cuDF's stream-ordered batching (SURVEY.md §3.2); here the budget is
explicit because remote-TPU round trips are ~1000x costlier than a
local cudaMemcpy.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.utils import metrics as M
from spark_rapids_tpu.utils.metrics import QueryStats, sync_budget


@pytest.fixture()
def sess():
    return srt.Session.get_or_create()


def _frame(sess, n, seed, **cols):
    rng = np.random.default_rng(seed)
    data = {}
    for name, spec in cols.items():
        kind, hi = spec
        if kind == "int":
            data[name] = rng.integers(0, hi, n).astype(np.int64)
        else:
            data[name] = rng.random(n)
    return sess.create_dataframe(data)


def test_scan_filter_agg_collect_budget(sess):
    """Q6-shape (scan→filter→scalar agg→collect): <= 2 *blocking*
    fetches; the collect tail may additionally ride async."""
    df = _frame(sess, 4096, 1, a=("int", 100), b=("f", None))
    q = df.filter(srt.functions.col("a") < 50).agg(
        srt.functions.sum(srt.functions.col("b")).alias("s"))
    with sync_budget(2, "scan-filter-agg") as s:
        q.collect()
    assert s.blocking_fetches <= 2
    # every transfer — blocking or async — is still byte-accounted
    assert s.fetch_bytes > 0


def test_scan_agg_budget_holds_under_pipeline(sess):
    """The async pipeline must not ADD blocking fetches: the same plan
    holds the same budget at depth 0 (serial) and depth 2."""
    f = srt.functions
    df = _frame(sess, 4096, 7, a=("int", 100), b=("f", None))
    q = df.filter(f.col("a") < 50).agg(f.sum(f.col("b")).alias("s"))
    for depth in (0, 2):
        sess.conf.set("spark.rapids.tpu.sql.pipeline.depth", depth)
        try:
            with sync_budget(2, f"scan-filter-agg@depth{depth}"):
                q.collect()
        finally:
            sess.conf.unset("spark.rapids.tpu.sql.pipeline.depth")


def test_join_agg_sort_budget(sess):
    """Q3-shape (join→grouped agg→sort→collect): the full pipeline must
    hold under 12 blocking fetches (measured 2026-07: 8-10 on this plan
    shape; the slack covers planner variation, not new per-batch syncs)."""
    f = srt.functions
    left = _frame(sess, 8192, 2, k=("int", 512), v=("f", None))
    right = _frame(sess, 512, 3, k2=("int", 512), w=("f", None))
    q = (left.join(right, on=[("k", "k2")])
         .group_by("k").agg(f.sum(f.col("v")).alias("sv"))
         .sort(f.col("sv").desc())
         .limit(10))
    with sync_budget(12, "join-agg-sort"):
        q.collect()


def test_counters_track_fetches(sess):
    """QueryStats counts transfers and bytes for a collect — the tail
    fetch may be blocking (depth 0) or async (pipelined), but it is
    never unaccounted."""
    df = _frame(sess, 1024, 4, a=("int", 10))
    QueryStats.reset()
    df.collect()
    s = QueryStats.get()
    assert s.blocking_fetches + s.async_fetches >= 1
    assert s.fetch_bytes > 0


def test_async_fetch_excluded_from_budget_but_traced():
    """fetch_async resolves outside the blocking budget yet through the
    same accounting: bytes, wait time, and the fetch span's call-site
    attribute when a QueryTrace is active."""
    from spark_rapids_tpu.utils import tracing
    with sync_budget(0, "async-only"), \
            tracing.query_trace("async-only") as tr:
        # zero BLOCKING fetches allowed
        fut = M.fetch_async(jnp.arange(1024, dtype=jnp.int64))
        vals = fut.result()
        assert vals.shape == (1024,)
        assert vals[-1] == 1023
    s = QueryStats.get()
    assert s.blocking_fetches == 0
    assert s.async_fetches == 1
    assert s.fetch_bytes >= 1024 * 8
    assert s.fetch_wait_s >= 0.0
    # one fetch span, async, carrying the fetch_async call site
    fetches = [e for e in tr.events if e[2] == "fetch"
               and e[1] != "fetch:start_copies"]
    assert [e[1] for e in fetches] == ["fetch:async"]
    args = fetches[0][6]
    assert args["blocking"] is False and args["bytes"] >= 1024 * 8
    assert "test_sync_budget" in args["site"]
    # resolving twice must not double-count
    fut.result()
    assert QueryStats.get().async_fetches == 1


def test_warm_cache_scan_agg_budget(sess, tmp_path):
    """Warm cross-query cache, Q6 shape (parquet scan→filter→scalar
    agg→collect): the hit path serves device-resident batches, so the
    ONLY blocking fetch is the collect tail — 0 before it."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.cache import clear_query_cache, get_query_cache
    f = srt.functions
    rng = np.random.default_rng(13)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.Table.from_pandas(pd.DataFrame({
        "a": rng.integers(0, 100, 4096).astype(np.int64),
        "b": rng.random(4096)}), preserve_index=False), path)
    sess.conf.set("spark.rapids.tpu.sql.cache.enabled", True)
    clear_query_cache()
    try:
        df = sess.read_parquet(path)
        q = df.filter(f.col("a") < 50).agg(f.sum(f.col("b")).alias("s"))
        warm = q.collect()  # populate pass
        with sync_budget(1, "warm-cache-scan-agg") as s:
            got = q.collect()
        assert got == warm
        assert s.blocking_fetches <= 1  # the collect tail, nothing else
        assert get_query_cache().hits >= 1
    finally:
        sess.conf.unset("spark.rapids.tpu.sql.cache.enabled")
        clear_query_cache()


def _dense_join_query(sess, n=8192, seed=1):
    """Scan→filter→join→join→agg chain whose join build stats ride the
    dense path (unique arange build keys; denseMinProbeRows lowered by
    the caller) — the shape the region prologue batches."""
    f = srt.functions
    rng = np.random.default_rng(seed)
    fact = sess.create_dataframe({
        "k": rng.integers(0, 512, n).astype(np.int64),
        "j": rng.integers(0, 128, n).astype(np.int64),
        "v": rng.random(n)})
    d1 = sess.create_dataframe({"k": np.arange(512, dtype=np.int64),
                                "w": rng.random(512)})
    d2 = sess.create_dataframe({"j": np.arange(128, dtype=np.int64),
                                "u": rng.random(128)})
    return (fact.filter(f.col("k") < 400)
                .join(d1, "k", "inner").join(d2, "j", "inner")
                .group_by(f.col("k")).agg(f.sum(f.col("v")).alias("s")))


def _norm(rows):
    return sorted(tuple(r.values()) if isinstance(r, dict) else tuple(r)
                  for r in rows)


def _collect_with_stats(sess, q, **conf):
    for k, v in conf.items():
        sess.conf.set(k, v)
    st = QueryStats()
    tok = M._STATS_STACK.set(M._STATS_STACK.get() + (st,))
    try:
        return q.collect(), st
    finally:
        M._STATS_STACK.reset(tok)
        for k in conf:
            sess.conf.unset(k)


def test_fused_region_prologue_budget(sess):
    """The tentpole contract: a fused scan→filter→join→join→agg region
    batches its member stats syncs into the region prologue, so the
    two joins' build-stats fetches cost ONE prologue fetch — fusion-on
    pays strictly fewer blocking fetches than the per-operator path,
    and the fusion-off oracle stays exact."""
    from spark_rapids_tpu.memory.spill import get_catalog
    q = _dense_join_query(sess)
    sess.conf.set("spark.rapids.tpu.join.denseMinProbeRows", 1024)
    try:
        on, s_on = _collect_with_stats(
            sess, q, **{"spark.rapids.tpu.sql.fusion.enabled": True})
        off, s_off = _collect_with_stats(
            sess, q, **{"spark.rapids.tpu.sql.fusion.enabled": False})
    finally:
        sess.conf.unset("spark.rapids.tpu.join.denseMinProbeRows")
    assert s_on.fused_regions >= 1
    assert s_off.fused_regions == 0
    # both join-stat syncs collapsed into one batched prologue fetch:
    # at least one blocking round trip saved outright
    assert s_on.blocking_fetches <= s_off.blocking_fetches - 1
    # each region pays at most 2 batched resolves on this shape (the
    # join-stats prologue + the agg candidate-stats pull), never the
    # per-operator fetch count
    assert s_on.region_fetches <= 2 * s_on.fused_regions
    assert _norm(on) == _norm(off)
    get_catalog().assert_no_leaks()


def test_fusion_on_off_share_cache_entries(sess, tmp_path):
    """plan_fingerprint sees THROUGH FusedRegionExec: data cached by a
    fusion-on run must hit for the same query with fusion off (and vice
    versa) — the region is an execution grouping, not a different query."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.cache import clear_query_cache, get_query_cache
    f = srt.functions
    rng = np.random.default_rng(23)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.Table.from_pandas(pd.DataFrame({
        "a": rng.integers(0, 100, 4096).astype(np.int64),
        "b": rng.random(4096)}), preserve_index=False), path)
    sess.conf.set("spark.rapids.tpu.sql.cache.enabled", True)
    clear_query_cache()
    try:
        df = sess.read_parquet(path)
        q = df.filter(f.col("a") < 50).agg(f.sum(f.col("b")).alias("s"))
        on, _ = _collect_with_stats(
            sess, q, **{"spark.rapids.tpu.sql.fusion.enabled": True})
        hits0 = get_query_cache().hits
        off, _ = _collect_with_stats(
            sess, q, **{"spark.rapids.tpu.sql.fusion.enabled": False})
        assert get_query_cache().hits > hits0
        assert _norm(on) == _norm(off)
    finally:
        sess.conf.unset("spark.rapids.tpu.sql.cache.enabled")
        clear_query_cache()


def test_fusion_concurrent_queries_stay_scoped(sess):
    """Two queries running fused regions concurrently (the scheduler
    path): the contextvar-carried region scope must not leak across
    threads — each query batches only its own stats, results exact."""
    import threading

    from spark_rapids_tpu.memory.spill import get_catalog
    qs = [_dense_join_query(sess, seed=s) for s in (11, 12)]
    oracle = []
    for q in qs:
        out, _ = _collect_with_stats(
            sess, q, **{"spark.rapids.tpu.sql.fusion.enabled": False})
        oracle.append(_norm(out))
    sess.conf.set("spark.rapids.tpu.sql.fusion.enabled", True)
    results = [None, None]
    errors = []

    def run(i):
        try:
            results[i] = _norm(qs[i].collect())
        except Exception as e:  # pragma: no cover - surfaced by assert
            errors.append(e)

    try:
        ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        sess.conf.unset("spark.rapids.tpu.sql.fusion.enabled")
    assert not errors
    assert results[0] == oracle[0]
    assert results[1] == oracle[1]
    get_catalog().assert_no_leaks()


def test_deferred_metrics_do_not_block(sess):
    """Deferred operator metrics resolve via the async path: reading
    them after a query adds no blocking fetch."""
    from spark_rapids_tpu.utils.metrics import MetricSet
    QueryStats.reset()
    m = MetricSet("op@test")
    m.add_deferred("numOutputRows", jnp.sum(jnp.arange(10)))
    before = QueryStats.get().blocking_fetches
    assert m["numOutputRows"] == 45
    assert QueryStats.get().blocking_fetches == before
    assert QueryStats.get().async_fetches >= 1
