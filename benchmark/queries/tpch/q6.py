"""TPC-H Q6, forecasting revenue change: scan, filter, one sum (clause
2.4.6).  Copied from ``spark_rapids_tpu/models/tpch.py``."""

import datetime

from harness.bytes import table_bytes

TABLES = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                       "l_extendedprice"]}
# clause 2.4.6.3: DATE 1 January of 1993..1997, DISCOUNT 0.02..0.09,
# QUANTITY 24..25
PARAMS = {"year": [1993, 1997], "discount": [0.02, 0.09],
          "quantity": [24, 25]}
RESULT_ROW_BYTES = 8


def params(rng):
    return {"year": int(rng.integers(1993, 1998)),
            "discount": int(rng.integers(2, 10)) / 100.0,
            "quantity": int(rng.integers(24, 26))}


def _bounds(p):
    lo = datetime.date(p["year"], 1, 1)
    hi = datetime.date(p["year"] + 1, 1, 1)
    # the generator's discounts are whole hundredths: take the band between
    # the half-hundredths so that neither side rounds a boundary value away
    return lo, hi, p["discount"] - 0.015, p["discount"] + 0.015


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as F
    lo, hi, dlo, dhi = _bounds(p)
    df = dfs["lineitem"]
    return (df.where((F.col("l_shipdate") >= lo) & (F.col("l_shipdate") < hi)
                     & (F.col("l_discount") >= dlo)
                     & (F.col("l_discount") <= dhi)
                     & (F.col("l_quantity") < p["quantity"]))
              .agg(F.sum(F.col("l_extendedprice") * F.col("l_discount"))
                   .alias("revenue"))).collect()


def reference(pds, p):
    lo, hi, dlo, dhi = _bounds(p)
    pdf = pds["lineitem"]
    m = ((pdf.l_shipdate >= lo) & (pdf.l_shipdate < hi)
         & (pdf.l_discount >= dlo) & (pdf.l_discount <= dhi)
         & (pdf.l_quantity < p["quantity"]))
    return [(float((pdf.l_extendedprice[m] * pdf.l_discount[m]).sum()),)]


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
