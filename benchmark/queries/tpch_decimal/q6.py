"""TPC-H Q6, forecasting revenue change (clause 2.4.6), over ``lineitem``
with its money DECIMAL(15,2) (dbgen's ``dss.ddl``)::

    SELECT sum(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= date '[DATE]'
      AND l_shipdate < date '[DATE]' + interval '1' year
      AND l_discount BETWEEN [DISCOUNT] - 0.01 AND [DISCOUNT] + 0.01
      AND l_quantity < [QUANTITY]

``revenue`` is decimal(38,4): the product is decimal(31,4) (p1 + p2 + 1,
s1 + s2) and its sum decimal(min(38, 31 + 10), 4); NULL where no row
qualifies.  DISCOUNT is a decimal and the band is compared exactly.

``reference`` computes in cents as exact integers; it never calls the
engine.
"""

import datetime
import decimal

import numpy as np

from harness.bytes import table_bytes
from queries.tpch_decimal import _money

TABLES = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                       "l_extendedprice"]}
# clause 2.4.6.3: DATE 1 January of 1993..1997, DISCOUNT 0.02..0.09,
# QUANTITY 24..25
PARAMS = {"year": [1993, 1997], "discount": ["0.02", "0.09"],
          "quantity": [24, 25]}
RESULT_ROW_BYTES = 16
HUNDREDTH = decimal.Decimal("0.01")


def params(rng):
    return {"year": int(rng.integers(1993, 1998)),
            "discount": int(rng.integers(2, 10)) * HUNDREDTH,
            "quantity": int(rng.integers(24, 26))}


def _bounds(p):
    return (datetime.date(p["year"], 1, 1),
            datetime.date(p["year"] + 1, 1, 1),
            p["discount"] - HUNDREDTH, p["discount"] + HUNDREDTH)


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
    c = _money.columns(pds["lineitem"], TABLES["lineitem"])
    ship, ship_ok = c["l_shipdate"]
    disc, d_ok = c["l_discount"]
    qty, q_ok = c["l_quantity"]
    price, p_ok = c["l_extendedprice"]
    cent = 10 ** _money.SCALE
    m = (ship_ok & d_ok & q_ok & p_ok
         & (ship >= np.datetime64(lo)) & (ship < np.datetime64(hi))
         & (disc >= int(dlo * cent)) & (disc <= int(dhi * cent))
         & (qty < p["quantity"] * cent))
    if not m.any():
        return [(None,)]
    price, disc = price[m], disc[m]
    bound = (int(np.abs(price).max()), int(np.abs(disc).max()))
    if not _money.int64_safe(*bound, rows=len(price)):
        price, disc = price.astype(object), disc.astype(object)
    total = int((price * disc).sum())
    return [(None if abs(total) >= 10 ** 38
             else _money.at_scale(total, 4),)]


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
