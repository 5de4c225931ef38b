"""What the two web-order fulfilment queries (Q94, Q95) share: the outer
query's selection (``web_sales ws1`` with one 61-day window of ship dates in
``date_dim``, one state of ``customer_address``, the sites of company
``'pri'``), the ``web_sales`` pair both read under two names, the
aggregate, and the same in pandas.  Inner joins: a line whose ship date,
ship address or web site is NULL (about 4% a key) matches nothing, in both.

Parameters, as ``query94.tpl`` / ``query95.tpl`` draw them: YEAR
1999..2002, MONTH 2..5, STATE one state of the address pool (the templates
draw it from ``dsdgen``'s county list)."""

import datetime

from datagen import tpcds_weborders

YEARS = [1999, 2002]
MONTHS = [2, 5]
STATES = tpcds_weborders.STATES
PARAMS = {"year": YEARS, "month": MONTHS, "state": STATES}
COMPANY = "pri"
SUMS = ["ws_ext_ship_cost", "ws_net_profit"]
# the outer query's columns of web_sales, and the dimensions'
WS1 = ["ws_ship_date_sk", "ws_ship_addr_sk", "ws_web_site_sk",
       "ws_order_number", "ws_warehouse_sk"] + SUMS
PAIR = ["ws_order_number", "ws_warehouse_sk"]
DIMS = {"date_dim": ["d_date_sk", "d_date"],
        "customer_address": ["ca_address_sk", "ca_state"],
        "web_site": ["web_site_sk", "web_company_name"]}
RESULT_ROW_BYTES = 8 + 8 + 8
# both queries read the same base-table columns
TABLES = {"web_sales": WS1, "web_returns": ["wr_order_number"], **DIMS}


def params(rng):
    return {"year": int(rng.integers(YEARS[0], YEARS[1] + 1)),
            "month": int(rng.integers(MONTHS[0], MONTHS[1] + 1)),
            "state": STATES[int(rng.integers(0, len(STATES)))]}


def window(p):
    """``d_date between 'Y-M-01' and (cast('Y-M-01' as date) + 60 days)``:
    both ends are in."""
    first = datetime.date(p["year"], p["month"], 1)
    return first, first + datetime.timedelta(days=60)


def selected(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    first, last = window(p)
    return (dfs["web_sales"].select(*WS1)
            .join(dfs["date_dim"].filter((f.col("d_date") >= first)
                                         & (f.col("d_date") <= last)),
                  on=[("ws_ship_date_sk", "d_date_sk")])
            .join(dfs["customer_address"]
                  .filter(f.col("ca_state") == p["state"]),
                  on=[("ws_ship_addr_sk", "ca_address_sk")])
            .join(dfs["web_site"]
                  .filter(f.col("web_company_name") == COMPANY),
                  on=[("ws_web_site_sk", "web_site_sk")]))


def second_sales(dfs, order="o2", warehouse="wh2"):
    """``web_sales ws2``: the pair's columns under names of their own (a
    join condition binds over both sides' columns by name)."""
    from spark_rapids_tpu.sql import functions as f
    return dfs["web_sales"].select(
        f.col("ws_order_number").alias(order),
        f.col("ws_warehouse_sk").alias(warehouse))


def aggregate(df):
    """``count(distinct ws_order_number), sum(ws_ext_ship_cost),
    sum(ws_net_profit) ... order by 1 limit 100``."""
    from spark_rapids_tpu.sql import functions as f
    return (df.agg(f.count_distinct(f.col("ws_order_number"))
                   .alias("order_count"),
                   f.sum(f.col("ws_ext_ship_cost"))
                   .alias("total_shipping_cost"),
                   f.sum(f.col("ws_net_profit")).alias("total_net_profit"))
            .sort("order_count").limit(100))


def selected_pandas(pds, p):
    ws, d, ca, web = (pds[t] for t in ("web_sales", "date_dim",
                                       "customer_address", "web_site"))
    first, last = window(p)
    return (ws.merge(d[(d.d_date >= first) & (d.d_date <= last)],
                     left_on="ws_ship_date_sk", right_on="d_date_sk")
            .merge(ca[ca.ca_state == p["state"]],
                   left_on="ws_ship_addr_sk", right_on="ca_address_sk")
            .merge(web[web.web_company_name == COMPANY],
                   left_on="ws_web_site_sk", right_on="web_site_sk"))


def aggregate_pandas(m):
    """One row; SQL's sum over no rows (or over NULLs alone) is NULL."""
    def total(col):
        return float(m[col].sum()) if m[col].notna().any() else None
    return [(int(m.ws_order_number.nunique()),
             total("ws_ext_ship_cost"), total("ws_net_profit"))]


def min_bytes(nrows, schema, result_rows):
    """The outer query's columns, the returns' key, and the pair of
    ``web_sales``' columns a second time (``ws2`` of Q94's EXISTS, of
    Q95's ``ws_wh``), as the text reads ``web_sales`` twice."""
    from harness.bytes import table_bytes
    return (table_bytes(TABLES, nrows, schema)
            + table_bytes({"web_sales": PAIR}, nrows, schema)
            + result_rows * RESULT_ROW_BYTES)
