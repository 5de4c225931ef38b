"""TPC-H Q3, shipping priority: customer x orders x lineitem, grouped
revenue, top 10 (clause 2.4.3).  Runner and pandas twin copied from
``spark_rapids_tpu/models/tpch.py`` with the substitution parameters made
arguments."""

import datetime

from harness.bytes import table_bytes

TABLES = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                 "l_shipdate"],
}
# clause 2.4.3.3: SEGMENT one of the five, DATE a day of 1995-03-01..31
PARAMS = {"segment": ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
                      "HOUSEHOLD"],
          "date": ["1995-03-01", "1995-03-31"]}
RESULT_ROW_BYTES = 8 + 4 + 8 + 8  # l_orderkey, o_orderdate, prio, revenue


def params(rng):
    return {"segment": PARAMS["segment"][int(rng.integers(0, 5))],
            "date": f"1995-03-{int(rng.integers(1, 32)):02d}"}


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as F
    cutoff = datetime.date.fromisoformat(p["date"])
    revenue = F.col("l_extendedprice") * (1 - F.col("l_discount"))
    return (dfs["customer"].where(F.col("c_mktsegment") == p["segment"])
            .join(dfs["orders"], [("c_custkey", "o_custkey")])
            .join(dfs["lineitem"], [("o_orderkey", "l_orderkey")])
            .where((F.col("o_orderdate") < cutoff)
                   & (F.col("l_shipdate") > cutoff))
            .group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(F.sum(revenue).alias("revenue"))
            .sort(F.col("revenue").desc(), F.col("o_orderdate"))
            .limit(10)).collect()


def reference(pds, p):
    cutoff = datetime.date.fromisoformat(p["date"])
    cdf, odf, ldf = pds["customer"], pds["orders"], pds["lineitem"]
    c = cdf[cdf.c_mktsegment == p["segment"]]
    o = odf[odf.o_orderdate < cutoff]
    li = ldf[ldf.l_shipdate > cutoff]
    m = c.merge(o, left_on="c_custkey", right_on="o_custkey")
    m = m.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    m = m.assign(revenue=m.l_extendedprice * (1 - m.l_discount))
    g = (m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                   as_index=False)["revenue"].sum()
         .sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(10))
    return [(int(r.l_orderkey), r.o_orderdate, int(r.o_shippriority),
             float(r.revenue)) for r in g.itertuples(index=False)]


def min_bytes(nrows, schema, result_rows):
    """Bytes the query has to read and write whatever implements it."""
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
