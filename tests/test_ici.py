"""shuffle.mode=ICI: plans execute their exchanges on the device mesh.

Differential contract: every query must produce exactly what the
single-process CACHE_ONLY engine produces (which is itself differentially
tested against pandas/duckdb elsewhere).  The suite runs on the 8-device
virtual CPU mesh the conftest forces.

Reference parity: RapidsShuffleInternalManagerBase.scala:1046 serves every
exchange in every plan; parallel/spmd.py is the TPU-native equivalent
(fragments lowered onto the mesh, SURVEY §5.8).
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql import functions as F


def _both_modes(df, sess):
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    want = df.collect()
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    got = df.collect()
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    return got, want


def _assert_rows_equal(got, want):
    def key(r):
        return tuple((x is None, x) for x in r)
    got = sorted(got, key=key)
    want = sorted(want, key=key)
    assert len(got) == len(want), f"{len(got)} vs {len(want)} rows"
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for gi, wi in zip(g, w):
            if gi is None or wi is None:
                assert gi is None and wi is None, (g, w)
            elif isinstance(wi, float):
                assert abs(gi - wi) <= 1e-9 * max(1.0, abs(wi)), (g, w)
            else:
                assert gi == wi, (g, w)


@pytest.fixture()
def sess(fresh_session):
    return fresh_session


@pytest.fixture()
def shuffle_only(sess):
    """Pin the shuffled-join path: the tiny test dims would otherwise
    auto-broadcast and bypass the all_to_all join under test."""
    sess.conf.set("spark.rapids.tpu.sql.autoBroadcastJoinThreshold", -1)
    yield sess
    sess.conf.set("spark.rapids.tpu.sql.autoBroadcastJoinThreshold",
                  10 * 1024 * 1024)


def _tables(rng, no=400, nl=2500, null_keys=False):
    ok = np.arange(no)
    lk = rng.integers(0, no + 60, nl)  # some keys match nothing
    orders = {
        "o_orderkey": pa.array(ok),
        "o_custkey": pa.array(rng.integers(0, 37, no)),
        "o_flag": pa.array(rng.integers(0, 2, no)),
    }
    items = {
        "l_orderkey": pa.array(
            [None if null_keys and i % 17 == 0 else int(v)
             for i, v in enumerate(lk)], type=pa.int64()),
        "l_price": pa.array(rng.uniform(1.0, 1000.0, nl)),
        "l_qty": pa.array(rng.integers(1, 50, nl)),
    }
    return pa.table(orders), pa.table(items)


def test_ici_grouped_agg(sess, rng):
    n = 6000
    t = pa.table({"k": pa.array(rng.integers(0, 61, n)),
                  "v": pa.array(rng.uniform(0, 100, n)),
                  "w": pa.array(rng.integers(-5, 5, n))})
    df = (sess.create_dataframe(t).group_by("k")
          .agg(F.sum(F.col("v")).alias("s"),
               F.count(F.col("v")).alias("c"),
               F.avg(F.col("v")).alias("a"),
               F.min(F.col("w")).alias("mn"),
               F.max(F.col("w")).alias("mx")))
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


def test_ici_string_group_keys(sess, rng):
    n = 3000
    cats = ["alpha", "beta", "gamma", "delta", None]
    t = pa.table({
        "k": pa.array([cats[i % len(cats)] for i in range(n)]),
        "v": pa.array(rng.uniform(0, 10, n))})
    df = (sess.create_dataframe(t).group_by("k")
          .agg(F.sum(F.col("v")).alias("s")))
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_ici_join_types(shuffle_only, rng, how):
    sess = shuffle_only
    orders, items = _tables(rng, null_keys=True)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    df = do.join(dl, [("o_orderkey", "l_orderkey")], how)
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


def test_ici_q3_shape(shuffle_only, rng):
    """join + filter + group-by + order-by: the round-2 verdict's done
    criterion for ICI (fragment = join..final-agg; sort runs above)."""
    sess = shuffle_only
    orders, items = _tables(rng)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    df = (do.join(dl, [("o_orderkey", "l_orderkey")], "inner")
          .filter(F.col("o_flag") == 1)
          .group_by("o_custkey")
          .agg(F.sum(F.col("l_price")).alias("rev"),
               F.count(F.col("l_qty")).alias("cnt"))
          .order_by(F.col("rev").desc()))
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    want = df.collect()
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    got = df.collect()
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    # order-by runs in the fringe: exact ordered comparison
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[2] == w[2], (g, w)
        assert abs(g[1] - w[1]) <= 1e-9 * max(1.0, abs(w[1]))


def test_ici_residual_condition_inner(shuffle_only, rng):
    sess = shuffle_only
    orders, items = _tables(rng)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    joined = do.join(dl, [("o_orderkey", "l_orderkey")], "inner")
    df = joined.filter(F.col("l_price") > F.col("o_custkey") * 10.0)
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


def test_ici_two_fragments_union(sess, rng):
    """A union of two aggregations: union is not lowerable, so each agg
    subtree runs as its own mesh fragment (multi-fragment loop)."""
    n = 2000
    t = pa.table({"k": pa.array(rng.integers(0, 11, n)),
                  "v": pa.array(rng.uniform(0, 5, n))})
    d1 = (sess.create_dataframe(t).group_by("k")
          .agg(F.sum(F.col("v")).alias("s")))
    d2 = (sess.create_dataframe(t).group_by("k")
          .agg(F.min(F.col("v")).alias("s")))
    df = d1.union(d2)
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


def test_ici_string_predicate_leaf(sess, rng):
    """A host-lowered string predicate below the aggregate: the stage runs
    single-process as a fragment leaf, the exchange still rides ICI."""
    n = 2000
    cats = ["BUILDING", "MACHINERY", "AUTOMOBILE"]
    t = pa.table({
        "seg": pa.array([cats[i % 3] for i in range(n)]),
        "k": pa.array(rng.integers(0, 9, n)),
        "v": pa.array(rng.uniform(0, 10, n))})
    df = (sess.create_dataframe(t)
          .filter(F.col("seg") == "BUILDING")
          .group_by("k").agg(F.sum(F.col("v")).alias("s")))
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


def test_ici_bucket_overflow_detected(sess, rng):
    n = 4000
    t = pa.table({"k": pa.array(rng.integers(0, 500, n)),
                  "v": pa.array(rng.uniform(0, 1, n))})
    df = (sess.create_dataframe(t).group_by("k")
          .agg(F.sum(F.col("v")).alias("s")))
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    sess.conf.set("spark.rapids.tpu.shuffle.ici.bucketRows", 2)
    try:
        with pytest.raises(RuntimeError, match="bucketRows"):
            df.collect()
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.ici.bucketRows", 0)
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")


def test_ici_bucket_overflow_transparent_recovery(sess, rng):
    """Sibling of test_ici_bucket_overflow_detected (VERDICT r4 item 8):
    a bucket one notch too small must NOT surface — distribute_plan
    re-lowers the fragment at 4x capacities and the query completes with
    answers identical to CACHE_ONLY mode."""
    n = 4000
    t = pa.table({"k": pa.array(rng.integers(0, 500, n)),
                  "v": pa.array(rng.uniform(0, 1, n))})
    df = (sess.create_dataframe(t).group_by("k")
          .agg(F.sum(F.col("v")).alias("s")))
    want = sorted(df.collect())
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    # ~4000/8 devices = 500 rows/device; 500 distinct keys spread over
    # 8 targets ~ 62/bucket: 32 overflows once, 128 (one 4x retry) fits
    sess.conf.set("spark.rapids.tpu.shuffle.ici.bucketRows", 32)
    try:
        got = sorted(df.collect())
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.ici.bucketRows", 0)
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    assert len(got) == len(want)
    for (gk, gs), (wk, ws) in zip(got, want):
        assert gk == wk and abs(gs - ws) < 1e-9


def test_ici_exchange_never_silently_degrades(sess):
    """An exchange reached by the single-process executor under mode=ICI
    must raise unless shuffle.ici.fallback is set (round-2 weak #2)."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.batch import Field, Schema
    from spark_rapids_tpu.exprs import BoundReference
    from spark_rapids_tpu.plan.exchange_exec import ShuffleExchangeExec
    from spark_rapids_tpu.plan.physical import ExecContext, ScanExec

    schema = Schema([Field("x", T.INT64, False)])
    scan = ScanExec(schema, lambda: iter([pa.table({"x": [1, 2, 3]})]))
    exch = ShuffleExchangeExec(
        scan, [BoundReference(0, T.INT64, False, "x")], 4)
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    ctx = ExecContext(sess._tpu_conf(), device=sess.device)
    try:
        with pytest.raises(RuntimeError, match="ICI"):
            list(exch.execute(ctx))
        sess.conf.set("spark.rapids.tpu.shuffle.ici.fallback", True)
        ctx2 = ExecContext(sess._tpu_conf(), device=sess.device)
        outs = list(exch.execute(ctx2))
        assert sum(b.row_count() for b in outs) == 3
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.ici.fallback", False)
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")


def test_ici_host_predicate_above_join(shuffle_only, rng):
    """A host-lowered string predicate ABOVE a shuffled join: the inner
    join fragment distributes first, then the predicate runs single-process
    and the outer aggregation distributes as a second fragment — a leaf
    must never swallow an exchange-bearing subtree."""
    sess = shuffle_only
    orders, items = _tables(rng, no=200, nl=1200)
    orders = orders.append_column(
        "o_seg", pa.array([["BUILDING", "MACHINERY"][i % 2]
                           for i in range(orders.num_rows)]))
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    df = (do.join(dl, [("o_orderkey", "l_orderkey")], "inner")
          .filter(F.col("o_seg") == "BUILDING")
          .group_by("o_custkey")
          .agg(F.sum(F.col("l_price")).alias("rev")))
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


def test_ici_avg_and_compound_aggs(sess, rng):
    n = 3000
    t = pa.table({"k": pa.array(rng.integers(0, 23, n)),
                  "v": pa.array(rng.uniform(0, 100, n))})
    df = (sess.create_dataframe(t).group_by("k")
          .agg((F.sum(F.col("v")) * 0.2).alias("fifth"),
               (F.max(F.col("v")) - F.min(F.col("v"))).alias("spread")))
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti"])
def test_ici_broadcast_join_types(sess, rng, how):
    """Broadcast joins under SPMD: the build side feeds the mesh
    replicated (P() in_spec) — no all_to_all for the join at all; the
    aggregate above still exchanges over ICI."""
    orders, items = _tables(rng, null_keys=True)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    joined = dl.join(F.broadcast(do), [("l_orderkey", "o_orderkey")], how)
    if how in ("left_semi", "left_anti"):
        df = (joined.group_by("l_qty")
              .agg(F.sum(F.col("l_price")).alias("rev")))
    else:
        df = (joined.group_by("o_custkey")
              .agg(F.sum(F.col("l_price")).alias("rev")))
    # the plan must actually contain a broadcast join
    phys = sess._plan_physical(df._plan)
    assert "TpuBroadcast" in phys.tree_string()
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


def test_ici_broadcast_right_outer(sess, rng):
    """how=right broadcasts the LEFT side (the kernel's build)."""
    orders, items = _tables(rng)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    df = (do.hint("broadcast").join(dl, [("o_orderkey", "l_orderkey")],
                                    "right")
          .group_by("l_qty")
          .agg(F.count(F.col("l_price")).alias("c")))
    phys = sess._plan_physical(df._plan)
    assert "build=left" in phys.tree_string()
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


@pytest.mark.parametrize("how", ["left", "left_semi", "left_anti", "full"])
def test_ici_conditioned_noninner_join(shuffle_only, rng, how):
    """ADVICE r3 high: non-inner joins with a residual condition must NOT
    lower onto the mesh (the post-expansion filter is inner-only
    semantics); they run single-process via _conditioned_probe_join while
    the child exchanges still distribute."""
    sess = shuffle_only
    orders, items = _tables(rng, null_keys=True)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    joined = do.join(dl, [("o_orderkey", "l_orderkey")], how)
    joined._plan.condition = (F.col("o_custkey") * 30.0
                              < F.col("l_price")).expr
    got, want = _both_modes(joined, sess)
    _assert_rows_equal(got, want)


@pytest.mark.parametrize("how", ["left", "left_semi", "left_anti"])
def test_ici_conditioned_broadcast_noninner(sess, rng, how):
    """Same contract for broadcast joins with residual conditions."""
    orders, items = _tables(rng, null_keys=True)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    joined = dl.join(F.broadcast(do), [("l_orderkey", "o_orderkey")], how)
    joined._plan.condition = (F.col("o_custkey") * 30.0
                              < F.col("l_price")).expr
    got, want = _both_modes(joined, sess)
    _assert_rows_equal(got, want)


def test_ici_existence_join_runs_single_process(shuffle_only, rng):
    """Existence joins (IN-subquery inside OR) have no SPMD lowering —
    they must run single-process under shuffle.mode=ICI with correct
    results."""
    sess = shuffle_only
    orders, items = _tables(rng)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    sub = do.filter(F.col("o_flag") == 1).select("o_orderkey")
    df = dl.filter(F.col("l_orderkey").isin_subquery(sub)
                   | (F.col("l_price") > 900.0))
    got, want = _both_modes(df, sess)
    _assert_rows_equal(got, want)


# ---------------------------------------------------------------------------------
# the mesh layer in the tracing: the account, the counters, the program cache
# ---------------------------------------------------------------------------------

def _mesh_query(sess, rng):
    orders, items = _tables(rng)
    do = sess.create_dataframe(orders)
    dl = sess.create_dataframe(items)
    return (do.join(dl, [("o_orderkey", "l_orderkey")], "inner")
            .group_by("o_custkey")
            .agg(F.sum(F.col("l_price")).alias("rev")))


def _run_recording_fragments(df, monkeypatch):
    """Collect ``df`` under ICI; returns (rows, the query's QueryStats,
    [(lowered root, rows of the gathered table)] per fragment run)."""
    from spark_rapids_tpu.parallel import spmd
    from spark_rapids_tpu.utils.metrics import QueryStats
    ran = []
    inner = spmd._execute_fragment

    def recording(lowered, *args, **kw):
        table = inner(lowered, *args, **kw)
        ran.append((lowered, table.num_rows))
        return table
    monkeypatch.setattr(spmd, "_execute_fragment", recording)
    with QueryStats.scoped() as st:
        rows = df.collect()
    return rows, st, ran


def _exchanges(lowered):
    from spark_rapids_tpu.parallel import spmd
    return [n for n in spmd._emit_order(lowered, False)
            if isinstance(n, spmd._Exchange)]


def _row_bytes(schema):
    # every column rides as its data and a validity byte
    return sum((4 if f.dtype.is_string else f.dtype.numpy_dtype.itemsize)
               + 1 for f in schema)


def test_ici_account_closes_over_a_mesh_query(shuffle_only, rng,
                                              monkeypatch):
    from spark_rapids_tpu.utils import tracing
    sess = shuffle_only
    df = _mesh_query(sess, rng)
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    try:
        df.collect()  # compiles land in the first run's account
        rows, st, ran = _run_recording_fragments(df, monkeypatch)
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    assert rows and len(ran) == 1 and st.ici_fragments == 1
    terms = [getattr(st, f"acct_{t}_s") for t in tracing.ACCOUNT_TERMS]
    assert all(v >= 0.0 for v in terms)
    assert sum(terms) == pytest.approx(st.query_wall_s, rel=0.01)
    phases = [st.ici_materialize_s, st.ici_feed_s, st.ici_step_s,
              st.ici_gather_s]
    assert all(v > 0.0 for v in phases)
    # the four are inside the wall, and the account holds their waits and
    # dispatches under the terms it has
    assert sum(phases) <= st.query_wall_s
    assert st.acct_fetch_wait_s > 0 and st.acct_dispatch_s > 0
    assert st.compiles == 0 and st.ici_overflow_retries == 0
    # what the all_to_alls move, from the static shapes: every device
    # holds n_dev buckets of bucket_cap rows for each of its n_dev peers
    import jax
    n_dev = len(jax.devices())
    exchanges = _exchanges(ran[0][0])
    assert len(exchanges) == 3  # the join's two sides, the aggregate's
    assert st.ici_exchange_bytes == sum(
        n_dev * n_dev * e.bucket_cap * _row_bytes(e.schema)
        for e in exchanges)
    # a bucket holds the rows counted, not the sender's whole capacity
    assert all(e.bucket_cap < e.child.cap for e in exchanges)
    names = {e[1] for e in sess.last_trace().events}
    assert {"ici:fragment", "ici:materialize", "ici:feed", "ici:step",
            "ici:gather", "program:ici_fragment_step",
            "program:ici_fragment_gather"} <= names


def test_ici_upload_and_fetch_counters_cover_the_feed_and_the_gather(
        shuffle_only, rng, monkeypatch):
    sess = shuffle_only
    df = _mesh_query(sess, rng)
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    try:
        rows, st, ran = _run_recording_fragments(df, monkeypatch)
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    lowered, gathered_rows = ran[0]
    per_device = {}
    for mset in sess.last_exec_context().metrics.values():
        for name, v in mset.values.items():
            if name.startswith("iciInputBytes."):
                per_device[name] = v
    import jax
    assert len(per_device) == len(jax.devices())
    assert st.ici_feed_bytes == sum(per_device.values()) > 0
    # the leaves' own scans upload too: the feed is part of the count
    assert st.upload_bytes >= st.ici_feed_bytes
    assert st.uploads >= 2  # one placement per leaf, at least
    # a step a level of exchanges and the last one each read the mesh
    # once, the gather once; the leaves' collects come on top
    assert st.blocking_fetches >= 2 + 1 + 1
    assert gathered_rows == len(rows) > 0
    assert st.fetch_bytes >= gathered_rows * _row_bytes(lowered.schema)


def test_ici_cached_step_programs_hold_no_plan(shuffle_only, rng):
    """A step's program outlives its query in the process-wide program
    cache: it must keep the expressions it traces, not the plan, whose
    scans hold the host tables of in-memory DataFrames."""
    import gc
    import weakref
    from spark_rapids_tpu.plan import physical
    sess = shuffle_only
    orders, items = _tables(rng)
    held = [weakref.ref(orders), weakref.ref(items)]
    df = (sess.create_dataframe(orders)
          .join(sess.create_dataframe(items),
                [("o_orderkey", "l_orderkey")], "inner")
          .group_by("o_custkey").agg(F.sum(F.col("l_price")).alias("rev")))
    del orders, items
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    try:
        assert df.collect()
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    with physical._STAGE_CACHE_LOCK:
        steps = [k for k in physical._STAGE_CACHE if k.startswith("ici-step|")]
    assert steps
    # what else may hold the query: the session's last trace and context
    df = None
    sess.create_dataframe(pa.table({"x": [1]})).collect()
    gc.collect()
    assert [r() for r in held] == [None, None]


# ---------------------------------------------------------------------------------
# the exchange buckets its live rows only (live_cap, from the counts the
# host read at the end of the step below)
# ---------------------------------------------------------------------------------

def _grouped(sess, table):
    return (sess.create_dataframe(pa.table(table)).group_by("k")
            .agg(F.sum(F.col("v")).alias("s"), F.count(F.col("v")).alias("c")))


def _live_all(sess, rng, n):
    # every row its own group: the partial aggregate's output is full
    return _grouped(sess, {"k": pa.array(rng.permutation(n)),
                           "v": pa.array(rng.uniform(0, 10, n))})


def _live_few(sess, rng, n):
    return _grouped(sess, {"k": pa.array(rng.integers(0, 5, n)),
                           "v": pa.array(rng.uniform(0, 10, n))})


def _live_none_on_one_device(sess, rng, n):
    # device 0 holds the first n/8 rows: the filter leaves it none
    df = sess.create_dataframe(pa.table({
        "pos": pa.array(np.arange(n)), "k": pa.array(rng.integers(0, 5, n)),
        "v": pa.array(rng.uniform(0, 10, n))}))
    return (df.filter(F.col("pos") >= n // 8).group_by("k")
            .agg(F.sum(F.col("v")).alias("s")))


def _live_exactly_a_rung(sess, rng, n):
    # all 64 keys in every device's share: 64 groups a sender, a whole rung
    return _grouped(sess, {"k": pa.array(np.arange(n) % 64),
                           "v": pa.array(rng.uniform(0, 10, n))})


def _live_nullable_and_strings(sess, rng, n):
    cats = ["alpha", "beta", "gamma", None]
    return _grouped(sess, {
        "k": pa.array([cats[i % 4] for i in range(n)]),
        "v": pa.array([None if i % 7 == 0 else float(i % 13)
                       for i in range(n)], type=pa.float64())})


LIVE_CASES = {
    # name: (query, rows, the exchange's (child.cap, live_cap), counter)
    "all_live": (_live_all, 8 * 512, (512, 512), 0),
    "few_live": (_live_few, 8 * 2048, (2048, 8), 1),
    "none_on_one_device": (_live_none_on_one_device, 8 * 2048, (2048, 8), 1),
    "exactly_a_rung": (_live_exactly_a_rung, 8 * 2048, (2048, 64), 1),
    "nullable_and_strings": (_live_nullable_and_strings, 8 * 1024,
                             (1024, 8), 1),
}


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_ici_exchange_buckets_its_live_rows(sess, rng, monkeypatch, case):
    """The receive half buckets ``live_cap`` rows, the ladder rung over
    the fullest sender's counted rows, never more than the step below
    staged; the answers are the single-process plan's either way and
    ``ici_compacted_exchanges`` counts the exchanges that were cut."""
    make, n, (child_cap, live_cap), compacted = LIVE_CASES[case]
    df = make(sess, rng, n)
    want = df.collect()
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    try:
        got, st, ran = _run_recording_fragments(df, monkeypatch)
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    _assert_rows_equal(got, want)
    (exch,) = _exchanges(ran[0][0])
    assert (exch.child.cap, exch.live_cap) == (child_cap, live_cap)
    assert exch.compacted == (live_cap < child_cap)
    assert st.ici_compacted_exchanges == compacted
    assert st.ici_overflow_retries == 0
    # the step that opens with the exchange is cached under all three
    # numbers: the rows staged, the rows bucketed, the bucket
    assert exch.fingerprint().startswith(
        f"recv0[{child_cap}->{live_cap}->8x{exch.bucket_cap}](")


def _scatter_gather_rows(jaxpr, out):
    """(primitive, source rows) of every scatter and gather in ``jaxpr``
    and under it: a scatter is paid by update row, a gather by element
    fetched; plus ("sort", rows) and ("cumsum", rows)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith("scatter"):
            out.append((name, eqn.invars[2].aval.shape[:1]))
        elif name == "gather":
            out.append((name, eqn.outvars[0].aval.shape[:1]))
        elif name in ("sort", "cumsum"):
            out.append((name, eqn.invars[0].aval.shape[:1]))
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (list, tuple)) else (sub,):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _scatter_gather_rows(inner, out)
    return out


@pytest.mark.parametrize("case", ["few_live", "all_live"])
def test_ici_compacted_step_walks_the_staged_rows_once(sess, rng,
                                                       monkeypatch, case):
    """In the step above a compacted exchange nothing but the one sort
    is as long as the rows staged: no column is gathered or scattered
    at ``child.cap`` rows.  The all-live step is the control: the same
    walk finds its ``child.cap``-long gathers and scatters."""
    import jax
    from spark_rapids_tpu.parallel import spmd
    make, n, (child_cap, live_cap), _ = LIVE_CASES[case]
    traced = []
    inner = spmd._step_program

    def recording(roots, final, mesh, axis):
        fn, *rest = inner(roots, final, mesh, axis)

        def call(*args):
            traced.append((final, jax.make_jaxpr(fn.call)(*args)))
            return fn(*args)
        return (call, *rest)
    monkeypatch.setattr(spmd, "_step_program", recording)
    sess.conf.set("spark.rapids.tpu.shuffle.mode", "ICI")
    try:
        assert make(sess, rng, n).collect()
    finally:
        sess.conf.set("spark.rapids.tpu.shuffle.mode", "CACHE_ONLY")
    (send, _), (final, above) = traced
    assert not send and final
    ops = _scatter_gather_rows(above.jaxpr, [])
    long_ops = [(name, rows) for name, rows in ops if rows == (child_cap,)]
    assert any(name == "gather" for name, _ in ops)
    assert any(name.startswith("scatter") for name, _ in ops)
    if live_cap < child_cap:
        assert long_ops == [("sort", (child_cap,))]
    else:
        assert {name for name, _ in long_ops} >= {"sort", "gather",
                                                  "scatter"}


@pytest.mark.parametrize("n_keys,nulls", [(1, False), (3, False), (2, True)])
def test_group_sort_puts_equal_keys_in_one_stable_run(rng, n_keys, nulls):
    """The group sort is four stable passes of a two-operand sort over
    the digits of a 128-bit key hash (cheap to compile for the TPU):
    every distinct key, null or not, is one run of adjacent rows in
    their first order, and the inactive rows come last."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.groupby import group_sort_indices
    n = 5000
    keys, cols = [], []
    for k in range(n_keys):
        data = rng.integers(-40, 40, n) * (k + 1)
        valid = rng.random(n) > 0.1 if nulls else np.ones(n, dtype=bool)
        keys.append((jnp.asarray(data), jnp.asarray(valid) if nulls else None))
        cols.append([(int(d), bool(v)) if v else None
                     for d, v in zip(data, valid)])
    active = rng.random(n) > 0.2
    perm = np.asarray(group_sort_indices(keys, jnp.asarray(active)))
    assert sorted(perm.tolist()) == list(range(n))
    n_active = int(active.sum())
    assert active[perm[:n_active]].all() and not active[perm[n_active:]].any()
    runs = {}
    rows = [tuple(c[i] for c in cols) for i in perm[:n_active]]
    for pos, (row, src) in enumerate(zip(rows, perm[:n_active])):
        first, last_pos, last_src = runs.get(row, (pos, pos - 1, -1))
        assert pos == last_pos + 1 and src > last_src, row
        runs[row] = (first, pos, src)
    assert len(runs) > 1
