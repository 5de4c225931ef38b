"""TPC-H Q13, customer distribution: customer left-join orders, count per
customer, count per count (clause 2.4.13).  Copied from
``spark_rapids_tpu/models/tpch_suite.py``.  The repo's Q13 filters
``o_orderpriority`` and not the words of ``o_comment`` (the generator writes
no comment column), so the parameter drawn is the priority left out."""

from harness.bytes import table_bytes

TABLES = {
    "customer": ["c_custkey"],
    "orders": ["o_orderkey", "o_custkey", "o_orderpriority"],
}
PARAMS = {"priority": ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                       "5-LOW"]}
RESULT_ROW_BYTES = 8 + 8


def params(rng):
    return {"priority": PARAMS["priority"][int(rng.integers(0, 5))]}


def run(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    kept = dfs["orders"].filter(f.col("o_orderpriority") != p["priority"])
    per_cust = (dfs["customer"]
                .join(kept, on=[("c_custkey", "o_custkey")], how="left")
                .group_by("c_custkey")
                .agg(f.count(f.col("o_orderkey")).alias("c_count")))
    return (per_cust.group_by("c_count")
            .agg(f.count_star().alias("custdist"))
            .sort(f.col("custdist").desc(), f.col("c_count").desc())
            ).collect()


def reference(pds, p):
    c, o = pds["customer"], pds["orders"]
    ko = o[o.o_orderpriority != p["priority"]]
    m = c.merge(ko, left_on="c_custkey", right_on="o_custkey", how="left")
    cc = m.groupby("c_custkey")["o_orderkey"].count().reset_index(
        name="c_count")
    exp = (cc.groupby("c_count").size().reset_index(name="custdist")
           .sort_values(["custdist", "c_count"], ascending=[False, False]))
    return list(zip(exp.c_count.astype(int), exp.custdist.astype(int)))


def min_bytes(nrows, schema, result_rows):
    return table_bytes(TABLES, nrows, schema) + result_rows * RESULT_ROW_BYTES
