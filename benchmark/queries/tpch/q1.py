"""TPC-H Q1, pricing summary report: scan, filter, eight aggregates over
four groups (clause 2.4.1).  Copied from
``spark_rapids_tpu/models/tpch.py``."""

import datetime

from harness.bytes import table_bytes

TABLES = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                       "l_quantity", "l_extendedprice", "l_discount",
                       "l_tax"]}
PARAMS = {"delta_days": [60, 120]}  # clause 2.4.1.3
RESULT_ROW_BYTES = 4 + 4 + 8 * 8


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


def reference(pds, p):
    pdf = pds["lineitem"]
    sub = pdf[pdf.l_shipdate <= _cutoff(p)].copy()
    sub["disc_price"] = sub.l_extendedprice * (1 - sub.l_discount)
    sub["charge"] = sub.disc_price * (1 + sub.l_tax)
    g = sub.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    ).reset_index().sort_values(["l_returnflag", "l_linestatus"])
    return [(r[0], r[1], *(float(x) for x in r[2:9]), int(r[9]))
            for r in g.itertuples(index=False)]


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
