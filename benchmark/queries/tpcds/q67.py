"""TPC-DS Q67 (query67.tpl): the top 100 sales sums of each category over
every level of ROLLUP(i_category, i_class, i_brand, i_product_name, d_year,
d_qoy, d_moy, s_store_id), twelve months from ``DMS``.  The specification's
text: nine grouping sets, ``rank() over (partition by i_category order by
sumsales desc)`` as a window, ``rk <= 100``, ordered by all ten columns,
``limit 100``.  ``DMS`` is drawn over the generator's sales (1176..1224;
the template draws 1176..1224 too: ``d_month_seq`` of 1998-01 .. 2002-01).

The order is total on the twin's rows: two output rows never share all
eight keys (no key is NULL in the data, so a NULL key names its grouping
set).  Ranks compare float64 sums; the twin sums in float64 in the fact's
row order, as the engine's stable group sort leaves them, so the sums of
two grouping sets over the same rows (a brand with one product) are equal
on both sides and tie.
"""

from harness.bytes import table_bytes
from queries.tpcds import _reports

KEYS = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id"]
TABLES = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                    "ss_quantity", "ss_sales_price"],
    "date_dim": ["d_date_sk", "d_month_seq", "d_year", "d_qoy", "d_moy"],
    "item": ["i_item_sk", "i_category", "i_class", "i_brand",
             "i_product_name"],
    "store": ["s_store_sk", "s_store_id"],
}
PARAMS = {"dms": [1176, 1224]}
TOP = 100      # fixed by the query's text: rk <= 100, limit 100
RESULT_ROW_BYTES = 5 * 4 + 3 * 8 + 8 + 4


def params(rng):
    return {"dms": int(rng.integers(PARAMS["dms"][0], PARAMS["dms"][1] + 1))}


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    from spark_rapids_tpu.sql.window import Window
    dw1 = (_reports.star(dfs, (f.col("d_month_seq") >= p["dms"])
                         & (f.col("d_month_seq") <= p["dms"] + 11))
           .rollup(*KEYS)
           .agg(f.sum(f.coalesce(f.col("ss_sales_price")
                                 * f.col("ss_quantity"), f.lit(0.0)))
                .alias("sumsales")))
    by_category = Window.partition_by("i_category").order_by(
        f.col("sumsales").desc())
    dw2 = dw1.select(*KEYS, "sumsales",
                     f.rank().over(by_category).alias("rk"))
    return (dw2.filter(f.col("rk") <= TOP)
            .sort(*KEYS, "sumsales", "rk").limit(TOP)).collect()


def reference(pds, p):
    m = _reports.star_pandas(
        pds, lambda d: d.d_month_seq.between(p["dms"], p["dms"] + 11))
    m = m.assign(v=(m.ss_sales_price * m.ss_quantity).fillna(0.0))
    g = _reports.rollup_pandas(m, KEYS, {"sumsales": "v"})
    g["rk"] = (g.groupby("i_category", dropna=False)["sumsales"]
               .rank(method="min", ascending=False))
    g = (g[g.rk <= TOP]
         .sort_values(KEYS + ["sumsales", "rk"], na_position="first")
         .head(TOP))
    return [tuple(_reports.cell(v) for v in r[:8])
            + (float(r[8]), int(r[9]))
            for r in g[KEYS + ["sumsales", "rk"]].itertuples(index=False)]


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
