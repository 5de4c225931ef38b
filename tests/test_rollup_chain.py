"""ROLLUP without Expand: the aggregate reduces the child's rows once, at
the finest grouping set, and each coarser set from the groups of the set
one key finer (``sql/dataframe._plan_grouping_sets``,
``plan/physical.AggregateExec._rollup_levels``).  Every case against the
Expand lowering of the same sets and against pandas; CUBE and aggregates
that do not re-aggregate from their partials keep Expand."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql import dataframe
from spark_rapids_tpu.utils.metrics import QueryStats

from .support import assert_rows_equal


def F():
    from spark_rapids_tpu.sql import functions
    return functions


def _table(n_keys, n, seed):
    """Keys of strings and ints, about a tenth of each NULL in the data,
    few enough values that the coarser sets hold several groups each; a
    float and an int to aggregate, both with NULLs."""
    rng = np.random.default_rng(seed)
    cols = {}
    for i in range(n_keys):
        vals = rng.integers(0, 3 if i < 3 else 2, n)
        null = rng.random(n) < 0.1
        if i % 2 == 0:
            cols[f"k{i}"] = pa.array(
                [None if z else f"s{v}" for v, z in zip(vals, null)],
                type=pa.string())
        else:
            cols[f"k{i}"] = pa.array(
                [None if z else int(v) for v, z in zip(vals, null)],
                type=pa.int64())
    v = np.round(rng.uniform(-50, 100, n), 2)
    cols["v"] = pa.array([None if z else float(x) for x, z in
                          zip(v, rng.random(n) < 0.1)], type=pa.float64())
    cols["q"] = pa.array([None if z else int(x) for x, z in
                          zip(rng.integers(-9, 9, n), rng.random(n) < 0.1)],
                         type=pa.int64())
    return pa.table(cols)


def _query(df, keys):
    f = F()
    return df.rollup(*keys).agg(
        f.sum(f.col("v")).alias("s"), f.count(f.col("q")).alias("n"),
        f.count_star().alias("rows"), f.min(f.col("v")).alias("lo"),
        f.max(f.col("q")).alias("hi"), f.avg(f.col("v")).alias("mean"),
        f.grouping_id().alias("gid"), f.grouping(keys[0]).alias("g0"))


def _cell(v):
    if v is None or (isinstance(v, float) and v != v) or v is pd.NA:
        return None
    if isinstance(v, float) and v.is_integer() and abs(v) < 2 ** 53:
        return v
    return v.item() if hasattr(v, "item") else v


def _pandas(t, keys):
    """One group-by a level of the rollup, the keys past it NULL."""
    pdf = t.to_pandas()
    m, parts = len(keys), []
    for level in range(m, -1, -1):
        ks = keys[:level]
        g = (pdf.groupby(ks, dropna=False, sort=False) if ks
             else pdf.groupby(np.zeros(len(pdf)), sort=False))
        a = pd.DataFrame({
            "s": g["v"].sum(min_count=1), "n": g["q"].count(),
            "rows": g.size(), "lo": g["v"].min(), "hi": g["q"].max(),
            "mean": g["v"].mean()})
        a = a.reset_index(drop=not ks)
        for k in keys[level:]:
            a[k] = None
        a["gid"] = (1 << (m - level)) - 1
        a["g0"] = int(level == 0)
        parts.append(a[keys + ["s", "n", "rows", "lo", "hi", "mean", "gid",
                               "g0"]].astype(object))
    rows = []
    for r in pd.concat(parts, ignore_index=True).itertuples(index=False):
        r = [_cell(x) for x in r]
        # an int key with NULLs comes back from pandas as a float
        r[:m] = [int(x) if isinstance(x, float) else x for x in r[:m]]
        rows.append(tuple(r))
    return rows


def _collect(df):
    with QueryStats.scoped() as st:
        rows = df.collect()
    return rows, st


CASES = [
    # (keys, rows, batch rows, conf)
    pytest.param(1, 300, None, {}, id="one_key"),
    pytest.param(2, 300, None, {}, id="two_keys"),
    pytest.param(8, 600, None, {}, id="eight_keys"),
    pytest.param(8, 5000, 1024, {}, id="eight_keys_several_batches"),
    pytest.param(3, 3000, 1024,
                 {"spark.rapids.tpu.sql.agg.singleProcessComplete": False},
                 id="three_keys_partial_exchange_final"),
    # the mesh takes the exchange and the partial aggregate below a chain
    pytest.param(3, 3000, 1024, {"spark.rapids.tpu.shuffle.mode": "ICI"},
                 id="three_keys_ici"),
]


@pytest.mark.parametrize("n_keys,n,batch_rows,confs", CASES)
def test_a_chain_equals_expand_and_pandas(fresh_session, monkeypatch,
                                          n_keys, n, batch_rows, confs):
    sess = fresh_session
    if batch_rows:
        sess.conf.set("spark.rapids.tpu.sql.batchSizeRows", batch_rows)
    for k, v in confs.items():
        sess.conf.set(k, v)
    t = _table(n_keys, n, seed=n_keys * 7 + n)
    keys = [f"k{i}" for i in range(n_keys)]
    df = sess.create_dataframe(t)

    chain = _query(df, keys)
    assert "rollup_levels=" in chain.explain_string()
    assert "Expand" not in chain.explain_string()
    got, st = _collect(chain)
    assert st.rollup_levels_merged == n_keys
    assert st.expand_slot_rows == 0 and st.cpu_fallback_nodes == 0
    if batch_rows and not confs:
        # the finest set's partials of the several batches are merged as
        # any aggregate's are; no coarser set is counted there
        assert st.agg_merges >= 1
    if confs.get("spark.rapids.tpu.shuffle.mode") == "ICI":
        assert st.ici_fragments >= 1

    monkeypatch.setattr(dataframe, "_is_chain", lambda sets, n_: False)
    expand = _query(df, keys)
    assert "Expand" in expand.explain_string()
    want_expand, st_x = _collect(expand)
    assert st_x.rollup_levels_merged == 0 and st_x.expand_slot_rows > 0

    # float sums of sums: rel 1e-12 (assert_rows_equal's exact form)
    assert_rows_equal(got, want_expand)
    assert_rows_equal(got, _pandas(t, keys))


def test_a_computed_key_rolls_up_on_the_cpu(fresh_session):
    """A computed string key puts the aggregate on the CPU: the fallback
    groups the rows again for each coarser set, with its keys NULL."""
    f = F()
    t = _table(2, 300, seed=11)
    df = fresh_session.create_dataframe(t)
    out = df.rollup(f.upper(f.col("k0")), "k1").agg(
        f.sum(f.col("v")).alias("s"), f.count(f.col("q")).alias("n"),
        f.count_star().alias("rows"), f.min(f.col("v")).alias("lo"),
        f.max(f.col("q")).alias("hi"), f.avg(f.col("v")).alias("mean"),
        f.grouping_id().alias("gid"),
        f.grouping(f.upper(f.col("k0"))).alias("g0"))
    got, st = _collect(out)
    assert st.cpu_fallback_nodes >= 1 and st.expand_slot_rows == 0
    t = t.set_column(0, "k0", pa.array(
        [None if s is None else s.upper() for s in t["k0"].to_pylist()]))
    assert_rows_equal(got, _pandas(t, ["k0", "k1"]))


def test_a_brand_with_one_product_ties_its_product(fresh_session):
    """TPC-DS Q67's tie: a brand with one product covers the same rows as
    that product, so its sum is the same float, bit for bit, and rank()
    gives both the same rank."""
    f = F()
    from spark_rapids_tpu.sql.window import Window
    rng = np.random.default_rng(67)
    n = 4000
    # products 0..29; brand = product // 3 except products 27.. whose
    # brands 9, 10, 11 hold one product each
    product = rng.integers(0, 30, n)
    brand = np.where(product < 27, product // 3, product - 18)
    t = pa.table({
        "category": pa.array([f"c{b % 2}" for b in brand]),
        "brand": pa.array([f"b{b}" for b in brand]),
        "product": pa.array([f"p{p}" for p in product]),
        # magnitudes far apart: a different order of the additions
        # rounds differently
        "v": pa.array(rng.uniform(0, 1, n) * 10.0 ** rng.integers(-3, 6, n)),
    })
    df = fresh_session.create_dataframe(t)
    by_category = Window.partition_by("category").order_by(
        f.col("s").desc())
    out = (df.rollup("category", "brand", "product")
           .agg(f.sum(f.col("v")).alias("s"))
           .select("category", "brand", "product", "s",
                   f.rank().over(by_category).alias("rk")))
    got, st = _collect(out)
    assert st.rollup_levels_merged == 3
    rows = {(c, b, p): (s, rk) for c, b, p, s, rk in got}
    for one in ("b9", "b10", "b11"):
        (c, _b, p), = [k for k in rows if k[1] == one and k[2] is not None]
        assert rows[(c, one, p)] == rows[(c, one, None)]
    pdf = t.to_pandas()
    want = pdf.groupby(["category", "brand", "product"])["v"].sum()
    for (c, b, p), x in want.items():
        assert rows[(c, b, p)][0] == pytest.approx(x, rel=1e-12)


@pytest.mark.parametrize("how", ["cube", "first", "last"])
def test_cube_and_order_dependent_aggregates_keep_expand(fresh_session, how):
    f = F()
    t = _table(3, 300, seed=3)
    df = fresh_session.create_dataframe(t)
    keys = ["k0", "k1", "k2"]
    if how == "cube":
        out = df.cube(*keys).agg(f.sum(f.col("v")).alias("s"))
        want = 8 * 1024
    else:
        out = df.rollup(*keys).agg(f.sum(f.col("v")).alias("s"),
                                   getattr(f, how)(f.col("q")).alias("x"))
        want = 4 * 1024
    assert "Expand" in out.explain_string()
    assert "rollup_levels" not in out.explain_string()
    _rows, st = _collect(out)
    assert st.rollup_levels_merged == 0 and st.expand_slot_rows == want
