"""TPC-DS Q52: brand revenue of manager 1's items in one month.  Copied
from ``spark_rapids_tpu/models/tpcds.py``."""

from harness.bytes import table_bytes
from queries.tpcds import _star

TABLES = {
    "store_sales": _star.FACT,
    "date_dim": ["d_date_sk", "d_year", "d_moy"],
    "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manager_id"],
}
PARAMS = {"year": _star.YEARS, "month": _star.MONTHS}
MANAGER = 1  # fixed by the query's text
RESULT_ROW_BYTES = 8 + 8 + 4 + 8

params = _star.draw_year_month


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    return (_star.star(dfs, (f.col("d_moy") == p["month"])
                       & (f.col("d_year") == p["year"]),
                       f.col("i_manager_id") == MANAGER)
            .group_by("d_year", "i_brand_id", "i_brand")
            .agg(f.sum(f.col("ss_ext_sales_price")).alias("ext_price"))
            .sort("d_year", f.col("ext_price").desc(), "i_brand_id")
            .limit(100)).collect()


def reference(pds, p):
    m = _star.star_pandas(
        pds, lambda d: (d.d_moy == p["month"]) & (d.d_year == p["year"]),
        lambda i: i.i_manager_id == MANAGER)
    g = (m.groupby(["d_year", "i_brand_id", "i_brand"])
         ["ss_ext_sales_price"].sum().reset_index()
         .sort_values(["d_year", "ss_ext_sales_price", "i_brand_id"],
                      ascending=[True, False, True]).head(100))
    return [(int(r.d_year), int(r.i_brand_id), r.i_brand,
             float(r.ss_ext_sales_price)) for r in g.itertuples()]


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
