"""TPC-H Q1, pricing summary report (clause 2.4.1), over ``lineitem`` with
its money DECIMAL(15,2) (dbgen's ``dss.ddl``)::

    SELECT l_returnflag, l_linestatus,
           sum(l_quantity)                                  AS sum_qty,
           sum(l_extendedprice)                             AS sum_base_price,
           sum(l_extendedprice * (1 - l_discount))          AS sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax))
                                                            AS sum_charge,
           avg(l_quantity)                                  AS avg_qty,
           avg(l_extendedprice)                             AS avg_price,
           avg(l_discount)                                  AS avg_disc,
           count(*)                                         AS count_order
    FROM lineitem
    WHERE l_shipdate <= date '1998-12-01' - interval '[DELTA]' day
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus

The Spark 3.x type of each output column (``1`` is decimal(1,0), so
``1 - l_discount`` and ``1 + l_tax`` are decimal(16,2)):

    l_returnflag, l_linestatus  string
    sum_qty, sum_base_price     decimal(25,2)   sum of decimal(15,2)
    sum_disc_price              decimal(38,4)   sum of decimal(32,4)
    sum_charge                  decimal(38,6)   sum of decimal(38,6): the
                                                product asks for (49,6)
    avg_qty, avg_price,         decimal(19,6)   the decimal(25,2) sum over
    avg_disc                                    the count as decimal(20,0)
                                                at Divide's decimal(38,15),
                                                then cast: HALF_UP twice
    count_order                 bigint

``reference`` computes in cents (scale 2), base prices at scale 4 and
charges at scale 6 as exact integers; it never calls the engine.
"""

import datetime

import numpy as np

from harness.bytes import table_bytes
from queries.tpch_decimal import _money

TABLES = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                       "l_quantity", "l_extendedprice", "l_discount",
                       "l_tax"]}
PARAMS = {"delta_days": [60, 120]}  # clause 2.4.1.3
RESULT_ROW_BYTES = 4 + 4 + 8 * 8
SUM_PRECISION = 25                   # sum of decimal(15,2)
AVG_SCALE, AVG_DIVIDE_SCALE = 6, 15  # decimal(19,6); Divide's (38,15)


def params(rng):
    return {"delta_days": int(rng.integers(60, 121))}


def _cutoff(p):
    return datetime.date(1998, 12, 1) - datetime.timedelta(
        days=p["delta_days"])


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as F
    disc_price = F.col("l_extendedprice") * (1 - F.col("l_discount"))
    charge = disc_price * (1 + F.col("l_tax"))
    return (dfs["lineitem"].where(F.col("l_shipdate") <= _cutoff(p))
            .group_by("l_returnflag", "l_linestatus")
            .agg(F.sum(F.col("l_quantity")).alias("sum_qty"),
                 F.sum(F.col("l_extendedprice")).alias("sum_base_price"),
                 F.sum(disc_price).alias("sum_disc_price"),
                 F.sum(charge).alias("sum_charge"),
                 F.avg(F.col("l_quantity")).alias("avg_qty"),
                 F.avg(F.col("l_extendedprice")).alias("avg_price"),
                 F.avg(F.col("l_discount")).alias("avg_disc"),
                 F.count_star().alias("count_order"))
            .sort("l_returnflag", "l_linestatus")).collect()


def _product(*factors):
    """The rows' exact product: int64 where the bound on the factors
    proves that neither it nor a sum of it over the rows passes 2^63."""
    bounds = [int(np.abs(f).max()) if len(f) else 0 for f in factors]
    if not _money.int64_safe(*bounds, rows=len(factors[0])):
        factors = [f.astype(object) for f in factors]
    out = factors[0]
    for f in factors[1:]:
        out = out * f
    return out


def _sum(values, scale: int, precision: int = 38):
    """Spark's sum: None over no value or past the precision."""
    if not len(values):
        return None
    total = int(values.sum())
    return None if abs(total) >= 10 ** precision \
        else _money.at_scale(total, scale)


def reference(pds, p):
    c = _money.columns(pds["lineitem"], TABLES["lineitem"])
    ship, ship_ok = c["l_shipdate"]
    keep = ship_ok & (ship <= np.datetime64(_cutoff(p)))
    (rf, rf_names), _ = c["l_returnflag"]
    (ls, ls_names), _ = c["l_linestatus"]
    qty, q_ok = c["l_quantity"]
    price, p_ok = c["l_extendedprice"]
    disc, d_ok = c["l_discount"]
    tax, t_ok = c["l_tax"]
    one = 10 ** _money.SCALE
    base = _product(price, one - disc)               # scale 4
    charge = _product(price, one - disc, one + tax)  # scale 6
    base_ok, charge_ok = p_ok & d_ok, p_ok & d_ok & t_ok
    out = []
    for f, s in sorted({(int(a), int(b)) for a, b in zip(rf[keep],
                                                        ls[keep])}):
        g = keep & (rf == f) & (ls == s)

        def avg(v, ok):
            return _money.spark_avg(int(v[g & ok].sum()), int((g & ok).sum()),
                                    _money.SCALE, AVG_SCALE, AVG_DIVIDE_SCALE)
        out.append((
            rf_names[f] if f >= 0 else None, ls_names[s] if s >= 0 else None,
            _sum(qty[g & q_ok], 2, SUM_PRECISION),
            _sum(price[g & p_ok], 2, SUM_PRECISION),
            _sum(base[g & base_ok], 4), _sum(charge[g & charge_ok], 6),
            avg(qty, q_ok), avg(price, p_ok), avg(disc, d_ok),
            int(g.sum())))
    return out


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
