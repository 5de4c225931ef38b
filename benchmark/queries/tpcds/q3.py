"""TPC-DS Q3: brand revenue of one manufacturer in one month of every
year.  Copied from ``spark_rapids_tpu/models/tpcds.py``."""

from harness.bytes import table_bytes
from queries.tpcds import _star

TABLES = {
    "store_sales": _star.FACT,
    "date_dim": ["d_date_sk", "d_year", "d_moy"],
    "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manufact_id"],
}
PARAMS = {"manufact": [1, 1000], "month": _star.MONTHS}
RESULT_ROW_BYTES = 8 + 8 + 4 + 8


def params(rng):
    return {"manufact": int(rng.integers(1, 1001)),
            "month": int(rng.integers(11, 13))}


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    return (_star.star(dfs, f.col("d_moy") == p["month"],
                       f.col("i_manufact_id") == p["manufact"])
            .group_by("d_year", "i_brand_id", "i_brand")
            .agg(f.sum(f.col("ss_ext_sales_price")).alias("sum_agg"))
            .sort("d_year", f.col("sum_agg").desc(), "i_brand_id")
            .limit(100)).collect()


def reference(pds, p):
    m = _star.star_pandas(pds, lambda d: d.d_moy == p["month"],
                          lambda i: i.i_manufact_id == p["manufact"])
    g = (m.groupby(["d_year", "i_brand_id", "i_brand"])
         ["ss_ext_sales_price"].sum().reset_index()
         .sort_values(["d_year", "ss_ext_sales_price", "i_brand_id"],
                      ascending=[True, False, True]).head(100))
    return [(int(r.d_year), int(r.i_brand_id), r.i_brand,
             float(r.ss_ext_sales_price)) for r in g.itertuples()]


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
