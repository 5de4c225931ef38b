"""ROLLUP / CUBE through Expand, windows and sorts over string keys on the
device, and the three TPC-DS reporting queries the benchmark runs (Q67, Q36,
Q89: ``benchmark/queries/tpcds``) against their pandas twins at SF0.02.
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


def W():
    from spark_rapids_tpu.sql.window import Window
    return Window


def _none(v):
    return None if v is None or (isinstance(v, float) and v != v) else v


def _rows(pdf):
    return [tuple(_none(v.item() if hasattr(v, "item") else v) for v in r)
            for r in pdf.itertuples(index=False)]


def _on_device(session, df):
    """The plan below the legend has no ``!``; returns the physical tree."""
    plan = df.explain_string()
    assert "!" not in plan.split("\n", 2)[2], plan
    return session._plan_physical(df._plan).tree_string()


@pytest.fixture(scope="module")
def sales():
    # NULLs in the DATA of both keys, next to the NULLs the sets will add
    rng = np.random.default_rng(33)
    n = 400
    a = rng.choice(["north", "south", "east", None], n, p=[.3, .3, .3, .1])
    b = rng.choice([1, 2, 3, None], n, p=[.3, .3, .3, .1])
    c = rng.choice(["x", "y"], n)
    return pa.table({
        "a": pa.array(a.tolist(), type=pa.string()),
        "b": pa.array([None if v is None else int(v) for v in b],
                      type=pa.int64()),
        "c": pa.array(c.tolist()),
        "v": pa.array(np.round(rng.uniform(0, 100, n), 2)),
    })


def _grouping_sets_pandas(pdf, keys, sets):
    """The union of one plain group-by a grouping set, with the keys
    outside the set NULL and Spark's grouping id beside them."""
    n, parts = len(keys), []
    for members in sets:
        ks = [keys[i] for i in members]
        if ks:
            g = pdf.groupby(ks, dropna=False)["v"].sum().reset_index()
        else:
            g = pd.DataFrame({"v": [pdf.v.sum()]})
        for i, k in enumerate(keys):
            if i not in members:
                g[k] = None
        g["gid"] = sum(1 << (n - 1 - i) for i in range(n)
                       if i not in members)
        parts.append(g[keys + ["v", "gid"]].astype(object))
    return pd.concat(parts, ignore_index=True)


@pytest.mark.parametrize("how,sets", [
    ("rollup", [(0, 1, 2), (0, 1), (0,), ()]),
    ("cube", [(0, 1, 2), (0, 1), (0, 2), (0,), (1, 2), (1,), (2,), ()]),
])
def test_grouping_sets_equal_the_union_of_group_bys(session, sales, how, sets):
    f = F()
    df = session.create_dataframe(sales)
    keys = ["a", "b", "c"]
    out = getattr(df, how)(*keys).agg(
        f.sum(f.col("v")).alias("v"), f.grouping_id().alias("gid"))
    tree = _on_device(session, out)
    assert f"TpuExpand [{len(sets)} projections]" in tree
    assert "Expand" in out.explain_string()
    want = _grouping_sets_pandas(sales.to_pandas(), keys, sets)
    # a NULL from the data and a NULL from the set are different groups:
    # (None, None, None) occurs once a grouping id that nulls all three
    assert_rows_equal(out.collect(), _rows(want), ignore_order=True,
                      approx_float=True)


def test_grouping_bits_are_sparks(session, sales):
    f = F()
    df = session.create_dataframe(sales)
    out = df.rollup("a", "b", "c").agg(
        f.count_star().alias("n"), f.grouping("a").alias("ga"),
        f.grouping("b").alias("gb"), f.grouping("c").alias("gc"),
        f.grouping_id().alias("gid"),
        (f.grouping("a") + f.grouping("c")).alias("gac"))
    seen = set()
    for a, b, c, n, ga, gb, gc, gid, gac in out.collect():
        # bit n-1-i of the id is key i's grouping(): a is the high bit
        assert gid == (ga << 2) | (gb << 1) | gc and gac == ga + gc
        assert (ga, gb, gc) in ((0, 0, 0), (0, 0, 1), (0, 1, 1), (1, 1, 1))
        if gc:
            assert c is None
        seen.add(gid)
    assert seen == {0, 1, 3, 7}
    with pytest.raises(ValueError, match="not one of the grouping columns"):
        df.rollup("a").agg(f.grouping("b"))


def test_rollup_keeps_the_aggregated_column_whole(session, sales):
    """An aggregate over a grouping column reads the column, not the copy
    the grouping set null-ed (Spark's Expand carries both)."""
    f = F()
    df = session.create_dataframe(sales)
    got = df.rollup("b").agg(f.sum(f.col("b")).alias("s"),
                             f.count(f.col("a")).alias("n")).collect()
    pdf = sales.to_pandas()
    total = [r for r in got if r[0] is None and r[1] == pdf.b.sum()]
    assert total and total[0][2] == pdf.a.notna().sum()


def _people(n):
    rng = np.random.default_rng(7)
    city = rng.choice(["oslo", "rome", "lima", "pune", None], n,
                      p=[.4, .3, .2, .05, .05]).tolist()
    city[17] = "solo"          # a partition of one row
    name = rng.choice(["ann", "bob", "cy", "di", None], n).tolist()
    return pa.table({
        "city": pa.array(city, type=pa.string()),
        "name": pa.array(name, type=pa.string()),
        "u": pa.array(rng.permutation(n).astype(np.int64)),
        "x": pa.array(np.round(rng.uniform(0, 10, n), 1)),
    })


@pytest.fixture(scope="module")
def people():
    return _people(300)


def test_window_over_string_partitions(session, people):
    f, w = F(), W()
    # two batches under the window: every partition spans both
    half = people.num_rows // 2
    df = session.create_dataframe(people.slice(0, half)).union(
        session.create_dataframe(people.slice(half)))
    by_city = w.partition_by("city")
    out = df.select(
        "city", "u",
        f.rank().over(by_city.order_by(f.col("x").desc())).alias("rk"),
        f.avg(f.col("x")).over(by_city).alias("mean"))
    assert "TpuWindow" in _on_device(session, out)
    pdf = people.to_pandas()
    g = pdf.groupby("city", dropna=False)["x"]
    pdf["rk"] = g.rank(method="min", ascending=False).astype(int)
    pdf["mean"] = g.transform("mean")
    assert_rows_equal(out.collect(), _rows(pdf[["city", "u", "rk", "mean"]]),
                      ignore_order=True, approx_float=True)


def test_window_orders_by_a_string(session, people):
    """An ORDER BY key gets order-preserving codes: the rank of each string
    among the column's distinct values (NULLs first, as Spark sorts)."""
    f, w = F(), W()
    df = session.create_dataframe(people)
    out = df.select("city", "name", "u", f.dense_rank().over(
        w.partition_by("city").order_by("name")).alias("dr"))
    assert "TpuWindow" in _on_device(session, out)
    pdf = people.to_pandas()
    order = {v: i for i, v in enumerate(
        [None] + sorted(pdf.name.dropna().unique()))}
    pdf["k"] = [order[_none(v)] for v in pdf.name]
    pdf["dr"] = (pdf.groupby("city", dropna=False)["k"]
                 .rank(method="dense").astype(int))
    assert_rows_equal(out.collect(), _rows(pdf[["city", "name", "u", "dr"]]),
                      ignore_order=True)


def test_case_over_one_string_column_is_a_key(session, people):
    """Q36's shape: ``case when <numbers> then <string column> end`` as a
    partition key and as a sort key selects codes as it would strings."""
    f, w = F(), W()
    df = session.create_dataframe(people)
    key = f.when(f.col("u") % 2 == 0, f.col("city"))
    out = (df.select("city", "u", f.count_star().over(
        w.partition_by(key)).alias("n"))
        .sort(f.when(f.col("u") % 3 == 0, f.col("city")), "u").limit(40))
    tree = _on_device(session, out)
    assert "TpuWindow" in tree and "TpuTopK" in tree
    pdf = people.to_pandas()
    pdf["key"] = pdf.city.where(pdf.u % 2 == 0, None)
    pdf["n"] = pdf.groupby("key", dropna=False)["u"].transform("size")
    pdf["s"] = pdf.city.where(pdf.u % 3 == 0, None)
    want = pdf.sort_values(["s", "u"], na_position="first").head(40)
    assert out.collect() == _rows(want[["city", "u", "n"]])


@pytest.mark.parametrize("rows,limit", [(300, None), (300, 25),
                                        (6000, None), (6000, 25)])
def test_sort_by_strings_on_the_device(session, rows, limit):
    """Planned on the device either way; at run time 300 rows are ordered
    on the host (exec_nodes._HOST_SORT_ROWS), 6,000 by order-preserving
    codes on the device: the same rows in the same order."""
    f = F()
    people = _people(rows)
    df = session.create_dataframe(people)
    out = df.sort(f.col("city").desc(), "name", "u")
    out = out.limit(limit) if limit else out
    assert ("TpuTopK" if limit else "TpuSort") in _on_device(session, out)
    # Spark: NULLs first under ASC, last under DESC; stable sorts, the
    # minor key first
    want = sorted(_rows(people.to_pandas()), key=lambda r: r[2])
    want.sort(key=lambda r: (r[1] is not None, r[1] or ""))
    want.sort(key=lambda r: (r[0] is not None, r[0] or ""), reverse=True)
    assert out.collect() == (want[:limit] if limit else want)


def test_a_computed_string_key_still_falls_back(session, people):
    f, w = F(), W()
    df = session.create_dataframe(people)
    out = df.select("u", f.row_number().over(
        w.partition_by(f.upper(f.col("city"))).order_by("u")).alias("rn"))
    plan = out.explain_string()
    assert "!" in plan.split("\n", 2)[2]
    assert "computed string expression" in plan
    pdf = people.to_pandas()
    pdf["rn"] = pdf.sort_values("u").groupby("city", dropna=False) \
        .cumcount() + 1
    assert_rows_equal(out.collect(), _rows(pdf[["u", "rn"]]),
                      ignore_order=True)


def test_the_reporting_operators_are_counted_and_traced(fresh_session, sales):
    from spark_rapids_tpu.utils.metrics import QueryStats
    f, w = F(), W()
    df = fresh_session.create_dataframe(sales)
    out = (df.rollup("a", "c").agg(f.sum(f.col("v")).alias("v"))
           .select("a", "c", "v", f.rank().over(
               w.partition_by("a").order_by(f.col("v").desc())).alias("rk")))
    with QueryStats.scoped() as qs:
        rows = out.collect()
    assert qs.window_rows == len(rows) and qs.window_exec_s > 0
    # three projections of one 1,024-slot batch
    assert qs.expand_slot_rows == 3 * 1024 and qs.expand_exec_s > 0
    assert qs.cpu_fallback_nodes == 0
    names = {e[1] for e in fresh_session.last_trace().events}
    assert {"window:exec", "expand:project", "program:window",
            "program:expand_project"} <= names
    with QueryStats.scoped() as qs:
        df.select("a", f.row_number().over(
            w.partition_by(f.upper(f.col("a"))).order_by("v"))
            .alias("rn")).collect()
    assert qs.cpu_fallback_nodes >= 1 and qs.window_rows == 0


# -- the benchmark's three queries against their twins -----------------------

QUERIES = ("q67", "q36", "q89")


@pytest.fixture(scope="module")
def bench_modules():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import compare, sources
    gen = sources.load_module([BENCH], "datagen", "tpcds_reports.py")
    return gen, compare, {q: sources.load_module(
        [BENCH], "queries", "tpcds", q + ".py") for q in QUERIES}


@pytest.fixture(scope="module", params=[2**31 + 33, 67])
def world(request, session, bench_modules, tmp_path_factory):
    import pyarrow.parquet as pq
    gen = bench_modules[0]
    paths = gen.gen(0.02, request.param,
                    str(tmp_path_factory.mktemp("reports")))
    return (request.param,
            {t: session.read_parquet(p) for t, p in paths.items()},
            {t: pq.read_table(p).to_pandas() for t, p in paths.items()})


@pytest.mark.parametrize("k", [0, 1])
@pytest.mark.parametrize("name", QUERIES)
def test_reporting_query_equals_its_twin(name, k, world, bench_modules,
                                         session):
    _, compare, queries = bench_modules
    seed, dfs, pds = world
    q = queries[name]
    # the k-th drawn parameter set with an answer (two stores at this
    # scale: Q36's eight states can refuse both)
    rng = np.random.default_rng([seed, len(name), 7])
    draws = (q.params(rng) for _ in range(16))
    p, want = [(p, w) for p, w in ((p, q.reference(pds, p)) for p in draws)
               if w][k]
    got = q.run(dfs, p)
    # the configuration's limits: no wrong answer, 1e-10 on the floats
    assert compare.rows_rel_err(got, want) <= 1e-10, (p, got[:3], want[:3])
    assert len(want) <= 100 and len(want[0]) == len(got[0])


def test_every_grouping_set_runs_one_aggregate_program(fresh_session):
    """ExpandExec hands every projection on as the same tree of arrays
    (validity masks included, NULL-ed dictionary columns like passed ones),
    so the aggregate above compiles once, not once a grouping set."""
    from spark_rapids_tpu.plan import physical
    f = F()
    rng = np.random.default_rng(5)
    fact = fresh_session.create_dataframe(pa.table({
        "k": pa.array(rng.integers(0, 40, 3000), type=pa.int64()),
        # a float key keeps the aggregate off its dense path: the sort
        # path's agg_grouped is the program Q67 runs nine times
        "w": pa.array(rng.integers(0, 2, 3000) / 2.0),
        "v": pa.array(rng.uniform(0, 1, 3000))}))
    dim = fresh_session.create_dataframe(pa.table({
        "dk": pa.array(np.arange(40), type=pa.int64()),
        "region": pa.array([f"r{i % 4}" for i in range(40)]),
        "name": pa.array([f"n{i}" for i in range(40)])}))
    out = (fact.join(dim.hint("broadcast"), on=[("k", "dk")])
           .rollup("region", "name", "w").agg(f.sum(f.col("v")).alias("s")))
    before = set(physical._STAGE_CACHE)
    rows = out.collect()
    assert len(rows) == 1 + 4 + 40 + 80
    new = [physical._STAGE_CACHE[k] for k in set(physical._STAGE_CACHE)
           - before if k.startswith("agg-grouped|")]
    assert new and all(p.call._cache_size() == 1 for p in new)
