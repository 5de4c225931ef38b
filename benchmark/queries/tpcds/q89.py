"""TPC-DS Q89 (query89.tpl): the months in which a brand's sales in a store
stray more than 10% from that brand's and store's monthly average, in one
year, over two sets of three categories and three classes.  The
specification's text: ``sum(ss_sales_price)`` by i_category, i_class,
i_brand, s_store_name, s_company_name, d_moy; ``avg(sum_sales) over
(partition by i_category, i_brand, s_store_name, s_company_name)`` as a
window (not a join with a second aggregate, as the repo's Q98 is written:
``models/tpcds_q2.py``); ``case when avg <> 0 then abs(sum_sales - avg) / avg
end > 0.1``; ordered by ``sum_sales - avg_monthly_sales, s_store_name``;
``limit 100``.  ``YEAR`` is drawn over the generator's sales; the two sets
are disjoint draws of three of the generator's categories and three of its
classes each (the template draws from its category / class lists).

The order is not total by its text (two rows of one store name could share
a difference); a difference is a float64 sum less a mean of such sums over
other rows, so two of the first hundred do not share one.
"""

from datagen import tpcds_reports
from harness.bytes import table_bytes
from queries.tpcds import _reports

KEYS = ["i_category", "i_class", "i_brand", "s_store_name",
        "s_company_name", "d_moy"]
PARTITION = ["i_category", "i_brand", "s_store_name", "s_company_name"]
TABLES = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                    "ss_sales_price"],
    "date_dim": ["d_date_sk", "d_year", "d_moy"],
    "item": ["i_item_sk", "i_category", "i_class", "i_brand"],
    "store": ["s_store_sk", "s_store_name", "s_company_name"],
}
PARAMS = {"year": _reports.YEARS,
          "sets": [2, 3, tpcds_reports.CATEGORIES, tpcds_reports.CLASSES]}
RESULT_ROW_BYTES = 5 * 4 + 8 + 8 + 8


def params(rng):
    cats = rng.permutation(len(tpcds_reports.CATEGORIES))[:6]
    classes = rng.permutation(len(tpcds_reports.CLASSES))[:6]
    return {"year": int(rng.integers(_reports.YEARS[0],
                                     _reports.YEARS[1] + 1)),
            "sets": [{"categories": sorted(tpcds_reports.CATEGORIES[int(i)]
                                           for i in cats[3 * k:3 * k + 3]),
                      "classes": sorted(tpcds_reports.CLASSES[int(i)]
                                        for i in classes[3 * k:3 * k + 3])}
                     for k in range(2)]}


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    from spark_rapids_tpu.sql.window import Window
    a, b = p["sets"]
    items = ((f.col("i_category").isin(*a["categories"])
              & f.col("i_class").isin(*a["classes"]))
             | (f.col("i_category").isin(*b["categories"])
                & f.col("i_class").isin(*b["classes"])))
    tmp1 = (_reports.star(dfs, f.col("d_year") == p["year"], item_pred=items)
            .group_by(*KEYS)
            .agg(f.sum(f.col("ss_sales_price")).alias("sum_sales"))
            .select(*KEYS, "sum_sales",
                    f.avg(f.col("sum_sales"))
                    .over(Window.partition_by(*PARTITION))
                    .alias("avg_monthly_sales")))
    avg, diff = (f.col("avg_monthly_sales"),
                 f.col("sum_sales") - f.col("avg_monthly_sales"))
    return (tmp1.filter(f.when(avg != 0.0, f.expr_abs(diff) / avg) > 0.1)
            .sort(diff, "s_store_name").limit(100)).collect()


def reference(pds, p):
    a, b = p["sets"]
    m = _reports.star_pandas(
        pds, lambda d: d.d_year == p["year"],
        item_mask=lambda i: (
            (i.i_category.isin(a["categories"]) & i.i_class.isin(a["classes"]))
            | (i.i_category.isin(b["categories"])
               & i.i_class.isin(b["classes"]))))
    g = (m.groupby(KEYS, sort=False)["ss_sales_price"].sum()
         .reset_index().rename(columns={"ss_sales_price": "sum_sales"}))
    g["avg_monthly_sales"] = (g.groupby(PARTITION)["sum_sales"]
                              .transform("mean"))
    g["diff"] = g.sum_sales - g.avg_monthly_sales
    g = g[(g.avg_monthly_sales != 0)
          & ((g["diff"].abs() / g.avg_monthly_sales) > 0.1)]
    g = g.sort_values(["diff", "s_store_name"]).head(100)
    return [(r.i_category, r.i_class, r.i_brand, r.s_store_name,
             r.s_company_name, int(r.d_moy), float(r.sum_sales),
             float(r.avg_monthly_sales)) for r in g.itertuples()]


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
