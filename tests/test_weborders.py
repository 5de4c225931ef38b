"""``DataFrame.join(on=<Column>)``: joins whose condition takes part in
matching; many-to-many joins; ``count_distinct`` beside sums over IN-subquery
selections; and the two TPC-DS web-order fulfilment queries the benchmark
runs (Q94, Q95: ``benchmark/queries/tpcds``) against their pandas references
at SF0.02, with the joins' span and counters.
"""

import os
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from .support import assert_rows_equal

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def F():
    from spark_rapids_tpu.sql import functions
    return functions


def _cell(v):
    if v is None or v is pd.NA or (isinstance(v, float) and v != v):
        return None
    return v.item() if hasattr(v, "item") else v


def _rows(pdf):
    return [tuple(_cell(v) for v in r) for r in pdf.itertuples(index=False)]


def _nodes(plan):
    yield plan
    for child in plan.children:
        yield from _nodes(child)


def _on_device(df):
    """The plan below the legend has no ``!``."""
    plan = df.explain_string()
    assert "!" not in plan.split("\n", 2)[2], plan
    return plan


# -- join(on=Column) ----------------------------------------------------------

@pytest.fixture(scope="module")
def sides():
    """NULLs in both keys and in both columns of the residual; keys that
    match many to many."""
    rng = np.random.default_rng(35)

    def side(n, key, val):
        return pa.table({
            key: pa.array(rng.integers(0, 12, n), type=pa.int64(),
                          mask=rng.random(n) < 0.1),
            val: pa.array(rng.integers(0, 50, n), type=pa.int64(),
                          mask=rng.random(n) < 0.15)})
    return side(90, "k", "a"), side(70, "k2", "b")


def _conditioned_pandas(left, right, how):
    """``on (k = k2) and (a < b)``: a NULL in a key or in a residual
    column makes the pair no match."""
    lp = left.to_pandas(types_mapper=pd.ArrowDtype).reset_index()
    rp = right.to_pandas(types_mapper=pd.ArrowDtype).reset_index()
    pairs = lp.dropna(subset=["k"]).merge(
        rp.dropna(subset=["k2"]), left_on="k", right_on="k2",
        suffixes=("", "2"))
    pairs = pairs[(pairs.a < pairs.b).fillna(False)]
    hit = lp["index"].isin(pairs["index"])
    if how == "semi":
        return lp[hit][["k", "a"]]
    if how == "anti":
        return lp[~hit][["k", "a"]]
    parts = [pairs]
    if how in ("left", "full"):
        parts.append(lp[~hit].assign(k2=None, b=None))
    if how in ("right", "full"):
        parts.append(rp[~rp["index"].isin(pairs["index2"])]
                     .assign(k=None, a=None))
    return pd.concat(parts)[["k", "a", "k2", "b"]]


@pytest.fixture(scope="module")
def merge_session():
    """No side is ever broadcast: the sort-merge operator runs the join."""
    import spark_rapids_tpu as srt
    srt.Session.reset()
    yield srt.Session.get_or_create(settings={
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    srt.Session.reset()


def _check_conditioned(sess, sides, how, operator):
    from spark_rapids_tpu.utils.metrics import QueryStats
    f = F()
    left, right = sides
    out = sess.create_dataframe(left).join(
        sess.create_dataframe(right),
        on=(f.col("k2") == f.col("k")) & (f.col("a") < f.col("b")), how=how)
    _on_device(out)
    assert operator in sess._plan_physical(out._plan).tree_string()
    with QueryStats.scoped() as qs:
        got = out.collect()
    assert qs.cpu_fallback_nodes == 0 and qs.join_exec_s > 0
    # a batch a count: a shuffled join runs one a partition
    assert (qs.join_semi_anti > 0) == (how in ("semi", "anti"))
    assert_rows_equal(got, _rows(_conditioned_pandas(left, right, how)),
                      ignore_order=True)


HOWS = ["inner", "left", "right", "full", "semi", "anti"]


@pytest.mark.parametrize("how", HOWS)
def test_broadcast_join_on_a_column(session, sides, how):
    # a full outer join preserves both sides: neither can be broadcast
    _check_conditioned(session, sides, how, "TpuSortMergeJoin"
                       if how == "full" else "TpuBroadcastHashJoin")


@pytest.mark.parametrize("how", HOWS)
def test_sort_merge_join_on_a_column(merge_session, sides, how):
    _check_conditioned(merge_session, sides, how, "TpuSortMergeJoin")


def test_join_on_a_column_splits_keys_from_the_condition(session, sides):
    import spark_rapids_tpu.plan.logical as L
    f = F()
    left, right = (session.create_dataframe(t) for t in sides)
    node = left.join(right, on=(f.col("k") == f.col("k2"))
                     & (f.col("a") != f.col("b")) & (f.col("a") > 3),
                     how="semi")._plan
    assert isinstance(node, L.Join) and node.how == "semi"
    assert [k.name for k in node.left_keys] == ["k"]
    assert [k.name for k in node.right_keys] == ["k2"]
    assert node.condition.references() == {"a", "b"}
    # every conjunct an equality of one column a side: keys, no condition
    node = left.join(right, on=(f.col("k") == f.col("k2"))
                     & (f.col("b") == f.col("a")))._plan
    assert [k.name for k in node.left_keys] == ["k", "a"]
    assert [k.name for k in node.right_keys] == ["k2", "b"]
    assert node.condition is None


def test_join_on_a_column_refuses_what_it_cannot_mean(session, sides):
    f = F()
    left, right = (session.create_dataframe(t) for t in sides)
    with pytest.raises(ValueError, match="'k' is on both sides"):
        left.join(left, on=f.col("k") == f.col("k"))
    with pytest.raises(ValueError, match="no column 'zz'"):
        left.join(right, on=f.col("k") == f.col("zz"))
    with pytest.raises(NotImplementedError, match="no equality"):
        left.join(right, on=f.col("a") < f.col("b"), how="semi")
    # an inner join with no equality is the cross join, filtered
    got = left.join(right, on=f.col("a") + 45 < f.col("b")).collect()
    lp, rp = (t.to_pandas(types_mapper=pd.ArrowDtype) for t in sides)
    want = lp.merge(rp, how="cross")
    want = want[(want.a + 45 < want.b).fillna(False)]
    assert len(want) and len(got) == len(want)


def test_explain_takes_an_in_subquery(session, sides):
    """``plan/pushdown._as_predicate`` met ``In`` over a subquery's marker
    with ``list()``; the plan is explained with the IN as its semi join."""
    from spark_rapids_tpu.plan.pushdown import extract_predicates
    f = F()
    left, right = (session.create_dataframe(t) for t in sides)
    cond = f.col("k").isin_subquery(right.select("k2")) & (f.col("a") > 1)
    assert extract_predicates(cond.expr) == [("a", ">", 1)]
    plan = _on_device(left.filter(cond))
    assert "Join semi" in plan and "InSubquery" not in plan


# -- many to many, count(distinct) over IN subqueries -------------------------

@pytest.fixture(scope="module")
def lines():
    """Orders of 8..16 lines, a warehouse a line, NULLs among them."""
    rng = np.random.default_rng(94)
    per = rng.integers(8, 17, 60)
    n = int(per.sum())
    return pa.table({
        "o": np.repeat(np.arange(1, 61), per).astype(np.int64),
        "wh": pa.array(rng.integers(1, 4, n), type=pa.int64(),
                       mask=rng.random(n) < 0.1),
        "cost": rng.uniform(1, 100, n).round(2)})


def test_a_self_join_grows_past_both_inputs(fresh_session, lines):
    """sum(n^2) candidate pairs out of two 1,024-slot inputs: the
    expansion runs at the capacity rung over the pairs, and the counters
    say both numbers."""
    from spark_rapids_tpu.batch import bucket_capacity
    from spark_rapids_tpu.utils.metrics import QueryStats
    f = F()
    df = fresh_session.create_dataframe(lines)
    one = df.select("o", f.col("wh").alias("wh1"))
    two = df.select(f.col("o").alias("o2"), f.col("wh").alias("wh2"))
    out = one.join(two, on=(f.col("o") == f.col("o2"))
                   & (f.col("wh1") != f.col("wh2")))
    _on_device(out)
    with QueryStats.scoped() as qs:
        got = out.collect()
    per = np.bincount(lines["o"].to_numpy())
    pairs = int((per.astype(np.int64) ** 2).sum())
    assert pairs > 4 * lines.num_rows
    assert qs.join_pairs == pairs
    assert qs.join_out_slots == bucket_capacity(pairs) > 2 * 1024
    assert qs.cpu_fallback_nodes == 0
    assert "join:pair" in {e[1] for e in fresh_session.last_trace().events}
    p = lines.to_pandas(types_mapper=pd.ArrowDtype)
    want = p[["o", "wh"]].merge(p[["o", "wh"]], on="o", suffixes=("1", "2"))
    want = want[(want.wh1 != want.wh2).fillna(False)]
    assert 0 < len(want) < pairs
    assert_rows_equal(got, _rows(want.assign(o2=want.o)[
        ["o", "wh1", "o2", "wh2"]]), ignore_order=True)


@pytest.mark.parametrize("floor", [30.0, 1000.0])
def test_count_distinct_beside_sums_over_in_subqueries(session, lines,
                                                       floor):
    """``floor`` 1000 selects nothing: a global aggregate still answers
    one row, (0, NULL, NULL)."""
    f = F()
    df = session.create_dataframe(lines)
    many = (df.group_by("o").agg(f.count(f.col("wh")).alias("n"))
            .filter(f.col("n") > 11).select("o"))
    third = df.filter(f.col("wh") == 3).select(f.col("o").alias("o3"))
    out = (df.filter((f.col("cost") > floor)
                     & f.col("o").isin_subquery(many)
                     & f.col("o").isin_subquery(third))
           .agg(f.count_distinct(f.col("o")).alias("orders"),
                f.sum(f.col("cost")).alias("cost"),
                f.sum(f.col("wh")).alias("whs")))
    _on_device(out)
    p = lines.to_pandas(types_mapper=pd.ArrowDtype)
    n = p.groupby("o").wh.count()
    sel = p[(p.cost > floor) & p.o.isin(n[n > 11].index)
            & p.o.isin(p[(p.wh == 3).fillna(False)].o)]
    want = [(sel.o.nunique(),
             float(sel.cost.sum()) if len(sel) else None,
             int(sel.wh.sum()) if len(sel) else None)]
    assert (want[0][0] == 0) == (floor == 1000.0)
    assert_rows_equal(out.collect(), want, approx_float=True)


def test_the_joins_gather_of_a_64_bit_column(session):
    """``join_exec._take``: int64 / uint64 columns as rows of two 32-bit
    words, every other type the plain gather; the same values."""
    import jax.numpy as jnp

    from spark_rapids_tpu.plan.join_exec import _take
    rng = np.random.default_rng(64)
    idx = jnp.asarray(rng.integers(0, 1000, 5000).astype(np.int32))
    wide = rng.integers(-2**62, 2**62, 1000)
    for src in (wide, wide.astype(np.uint64), rng.uniform(-1, 1, 1000),
                wide.astype(np.int32), wide > 0):
        got = _take(jnp.asarray(src), idx)
        assert got.dtype == src.dtype
        assert (np.asarray(got) == src[np.asarray(idx)]).all()


# -- the benchmark's two queries against their references ---------------------

QUERIES = ("q94", "q95")


@pytest.fixture(scope="module")
def bench_modules():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import compare, sources
    gen = sources.load_module([BENCH], "datagen", "tpcds_weborders.py")
    return gen, compare, {q: sources.load_module(
        [BENCH], "queries", "tpcds", q + ".py") for q in QUERIES}


@pytest.fixture(scope="module", params=[2**31 + 35, 94])
def world(request, session, bench_modules, tmp_path_factory):
    import pyarrow.parquet as pq
    gen = bench_modules[0]
    paths = gen.gen(0.02, request.param,
                    str(tmp_path_factory.mktemp("weborders")))
    return (request.param,
            {t: session.read_parquet(p) for t, p in paths.items()},
            {t: pq.read_table(p).to_pandas() for t, p in paths.items()})


@pytest.mark.parametrize("name", QUERIES)
def test_fulfilment_query_equals_its_reference(name, world, bench_modules,
                                               session):
    """Every parameter set of a pool of four, as the cell draws them; the
    plan all on the device, no ``L.Cache`` in it."""
    import zlib

    import spark_rapids_tpu.plan.logical as L
    from spark_rapids_tpu.utils.metrics import QueryStats
    _, compare, queries = bench_modules
    seed, dfs, pds = world
    q = queries[name]
    rng = np.random.default_rng([seed, zlib.crc32(name.encode()), 7])
    pool = [q.params(rng) for _ in range(4)]
    # 1,200 orders at this scale: a set selects about half an order, and
    # the empty answer (0, NULL, NULL) is compared like any other; one
    # more set, the first drawn that selects an order, so that one is not
    from queries.tpcds import _weborders
    pool.append(next(p for p in (q.params(rng) for _ in range(200))
                     if len(_weborders.selected_pandas(pds, p))
                     and q.reference(pds, p)[0][0] > 0))
    for p in pool:
        plan = q.plan(dfs, p)
        _on_device(plan)
        assert not any(isinstance(n, L.Cache) for n in _nodes(plan._plan))
        with QueryStats.scoped() as qs:
            got = q.run(dfs, p)
        want = q.reference(pds, p)
        assert compare.rows_rel_err(got, want) <= 1e-10, (p, got, want)
        assert qs.cpu_fallback_nodes == 0
    assert want[0][0] > 0 and want[0][1] is not None


def _order(lines):
    """A world of one order: ``lines`` of (warehouse, ship cost, profit),
    every one inside the parameters' selection."""
    import datetime
    n = len(lines)
    ws = pa.table({
        "ws_ship_date_sk": pa.array([10] * n, type=pa.int64()),
        "ws_ship_addr_sk": pa.array([7] * n, type=pa.int64()),
        "ws_web_site_sk": pa.array([3] * n, type=pa.int64()),
        "ws_order_number": pa.array([42] * n, type=pa.int64()),
        "ws_warehouse_sk": pa.array([w for w, _, _ in lines],
                                    type=pa.int64()),
        "ws_ext_ship_cost": pa.array([c for _, c, _ in lines]),
        "ws_net_profit": pa.array([g for _, _, g in lines])})
    return {
        "web_sales": ws,
        "web_returns": pa.table({
            "wr_order_number": pa.array([41], type=pa.int64())}),
        "date_dim": pa.table({
            "d_date_sk": pa.array([10], type=pa.int64()),
            "d_date": pa.array([datetime.date(2000, 3, 15)])}),
        "customer_address": pa.table({
            "ca_address_sk": pa.array([7], type=pa.int64()),
            "ca_state": pa.array(["TN"])}),
        "web_site": pa.table({
            "web_site_sk": pa.array([3], type=pa.int64()),
            "web_company_name": pa.array(["pri"])})}


def test_q94_is_the_specifications_not_the_rewrite(session, bench_modules):
    """An order in warehouses 1, 2 and NULL.  The text's EXISTS asks, a
    line at a time, for another line ``ws1.ws_warehouse_sk <>
    ws2.ws_warehouse_sk``: the NULL line satisfies no ``<>`` and is not
    counted.  ``models/tpcds_q2.py``'s rewrite (orders whose min and max
    warehouse differ) keeps all three lines."""
    q = bench_modules[2]["q94"]
    tables = _order([(1, 10.0, 1.0), (2, 20.0, 2.0), (None, 40.0, 4.0)])
    p = {"year": 2000, "month": 3, "state": "TN"}
    dfs = {t: session.create_dataframe(v) for t, v in tables.items()}
    pds = {t: v.to_pandas() for t, v in tables.items()}
    got = q.run(dfs, p)
    assert got == q.reference(pds, p) == [(1, 30.0, 3.0)]
    # one warehouse and a NULL: no line has a partner, the order is out
    tables = _order([(1, 10.0, 1.0), (None, 40.0, 4.0)])
    dfs = {t: session.create_dataframe(v) for t, v in tables.items()}
    assert q.run(dfs, p) == [(0, None, None)]


def test_q94_runs_a_conditioned_semi_and_an_anti_join(fresh_session,
                                                     bench_modules):
    """One of each a selection, and the selection runs ONCE: the
    ``count_distinct`` beside the sums is two stacked aggregates over one
    copy of the child (``sql/dataframe._plan_distinct_one_pass``), not two
    aggregates over two copies joined back, so two a Q94 and not four."""
    from spark_rapids_tpu.utils.metrics import QueryStats
    q = bench_modules[2]["q94"]
    tables = _order([(1, 10.0, 1.0), (2, 20.0, 2.0), (None, 40.0, 4.0)])
    p = {"year": 2000, "month": 3, "state": "TN"}
    dfs = {t: fresh_session.create_dataframe(v) for t, v in tables.items()}
    tree = fresh_session._plan_physical(q.plan(dfs, p)._plan).tree_string()
    assert tree.count("[semi]") == 1 and tree.count("[anti]") == 1
    with QueryStats.scoped() as qs:
        q.run(dfs, p)
    assert qs.join_semi_anti == 2 and qs.cpu_fallback_nodes == 0
    assert qs.distinct_one_pass_aggs == 1
    # the conditioned semi join expands its candidates: three lines meet
    # the order's three lines
    assert qs.join_pairs >= 9
    names = {e[1] for e in fresh_session.last_trace().events}
    assert "join:pair" in names


def test_q95_reads_its_selection_once(fresh_session, bench_modules,
                                      monkeypatch):
    """Q95's tree holds half the scans the join form's did: the selection
    and both IN subqueries stand once.  ``ws_wh`` is still there twice,
    alone under one IN and joined to ``web_returns`` under the other (a
    subtree shared inside one execution is ROADMAP S12's, not this)."""
    from spark_rapids_tpu.sql import dataframe as D
    from spark_rapids_tpu.utils.metrics import QueryStats
    q = bench_modules[2]["q95"]
    tables = _order([(1, 10.0, 1.0), (2, 20.0, 2.0), (None, 40.0, 4.0)])
    tables["web_returns"] = pa.table({
        "wr_order_number": pa.array([42], type=pa.int64())})
    p = {"year": 2000, "month": 3, "state": "TN"}
    dfs = {t: fresh_session.create_dataframe(v) for t, v in tables.items()}
    pds = {t: v.to_pandas() for t, v in tables.items()}

    def trees():
        plan = q.plan(dfs, p)
        return (plan.explain_string(),
                fresh_session._plan_physical(plan._plan).tree_string())
    logical, physical = trees()
    with QueryStats.scoped() as qs:
        got = q.run(dfs, p)
    # IN takes the order whole, its NULL-warehouse line too
    assert got == q.reference(pds, p) == [(1, 70.0, 7.0)]
    assert qs.distinct_one_pass_aggs == 1 and qs.cpu_fallback_nodes == 0
    # the outer selection's four tables, ws_wh's two web_sales under one
    # IN, web_returns with ws_wh's two more under the other
    assert logical.count("Scan memory") == 4 + 2 + 3
    assert logical.count("Project [ws_order_number, wh1]") == 2
    assert logical.count("Join semi") == 2
    # (a world this small answers its INs while it plans: the physical
    # tree keeps the selection's four scans alone)
    monkeypatch.setattr(D, "_plan_distinct_one_pass", lambda *a, **k: None)
    logical2, physical2 = trees()
    assert logical2.count("Scan memory") == 2 * logical.count("Scan memory")
    assert physical2.count("TpuScan") == 2 * physical.count("TpuScan") > 0
