"""TPC-DS Q36 (query36.tpl): gross margin by ROLLUP(i_category, i_class) in
one year over the stores of eight states, ranked within each parent.  The
specification's text: three grouping sets, ``grouping(i_category) +
grouping(i_class)`` as ``lochierarchy``, ``rank() over (partition by
lochierarchy, case when grouping(i_class) = 0 then i_category end order by
gross_margin asc)`` as a window, ordered by ``lochierarchy desc, case when
lochierarchy = 0 then i_category end, rank_within_parent``, ``limit 100``.
``YEAR`` is drawn over the generator's sales (the template: 1998..2002);
the eight states from the generator's pool (the template draws them from
the states ``dsdgen`` has stores in).

The order is total on the twin's rows unless two classes of one category
(or two categories) have the same margin, a float64 quotient of sums over
different rows: they differ.
"""

from datagen import tpcds_reports
from harness.bytes import table_bytes
from queries.tpcds import _reports

TABLES = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                    "ss_ext_sales_price", "ss_net_profit"],
    "date_dim": ["d_date_sk", "d_year"],
    "item": ["i_item_sk", "i_category", "i_class"],
    "store": ["s_store_sk", "s_state"],
}
PARAMS = {"year": _reports.YEARS, "states": [8, tpcds_reports.STATES]}
RESULT_ROW_BYTES = 8 + 4 + 4 + 1 + 4


def params(rng):
    states = rng.choice(len(tpcds_reports.STATES), size=8, replace=False)
    return {"year": int(rng.integers(_reports.YEARS[0],
                                     _reports.YEARS[1] + 1)),
            "states": sorted(tpcds_reports.STATES[int(i)] for i in states)}


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    from spark_rapids_tpu.sql.window import Window
    g = (_reports.star(dfs, f.col("d_year") == p["year"],
                       store_pred=f.col("s_state").isin(*p["states"]))
         .rollup("i_category", "i_class")
         .agg((f.sum(f.col("ss_net_profit"))
               / f.sum(f.col("ss_ext_sales_price"))).alias("gross_margin"),
              (f.grouping("i_category") + f.grouping("i_class"))
              .alias("lochierarchy"),
              f.grouping("i_class").alias("g_class")))
    within_parent = Window.partition_by(
        "lochierarchy",
        f.when(f.col("g_class") == 0, f.col("i_category"))).order_by(
        f.col("gross_margin").asc())
    return (g.select("gross_margin", "i_category", "i_class", "lochierarchy",
                     f.rank().over(within_parent)
                     .alias("rank_within_parent"))
            .sort(f.col("lochierarchy").desc(),
                  f.when(f.col("lochierarchy") == 0, f.col("i_category")),
                  "rank_within_parent")
            .limit(100)).collect()


def reference(pds, p):
    m = _reports.star_pandas(
        pds, lambda d: d.d_year == p["year"],
        store_mask=lambda s: s.s_state.isin(p["states"]))
    g = _reports.rollup_pandas(
        m, ["i_category", "i_class"],
        {"profit": "ss_net_profit", "sales": "ss_ext_sales_price"})
    g["gross_margin"] = g.profit / g.sales
    # level = grouping(i_category) + grouping(i_class) under a rollup
    g["parent"] = g.i_category.where(g.level == 0, None)
    g["rk"] = (g.groupby(["level", "parent"], dropna=False)["gross_margin"]
               .rank(method="min", ascending=True))
    g = (g.sort_values(["level", "parent", "rk"],
                       ascending=[False, True, True], na_position="first")
         .head(100))
    return [(float(r.gross_margin), _reports.cell(r.i_category),
             _reports.cell(r.i_class), int(r.level), int(r.rk))
            for r in g.itertuples()]


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
