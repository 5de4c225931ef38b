"""TPC-DS Q95 (query95.tpl): the web orders of one state and 61 days of
ship dates, sold through the sites of company 'pri', that shipped from more
than one warehouse AND had a line returned: how many, their shipping cost
and their net profit.  The specification's text::

    with ws_wh as
    (select ws1.ws_order_number, ws1.ws_warehouse_sk wh1,
            ws2.ws_warehouse_sk wh2
     from web_sales ws1, web_sales ws2
     where ws1.ws_order_number = ws2.ws_order_number
       and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
    select count(distinct ws_order_number) as "order count",
           sum(ws_ext_ship_cost) as "total shipping cost",
           sum(ws_net_profit) as "total net profit"
    from web_sales ws1, date_dim, customer_address, web_site
    where d_date between '[YEAR]-[MONTH]-01'
                     and (cast('[YEAR]-[MONTH]-01' as date) + 60 days)
      and ws1.ws_ship_date_sk = d_date_sk
      and ws1.ws_ship_addr_sk = ca_address_sk and ca_state = '[STATE]'
      and ws1.ws_web_site_sk = web_site_sk and web_company_name = 'pri'
      and ws1.ws_order_number in (select ws_order_number from ws_wh)
      and ws1.ws_order_number in (select wr_order_number
                                  from web_returns, ws_wh
                                  where wr_order_number =
                                        ws_wh.ws_order_number)
    order by count(distinct ws_order_number) limit 100

``ws_wh`` is the self-join the text writes, every pair of an order's lines
in different warehouses (sum of n^2 candidate pairs over the orders, most of
which pass ``<>``), used twice; no aggregate stands in for it, nothing is
cached, the plan is collected once.  (The text, written from memory of the
templates: ``assumed``.)
"""

from queries.tpcds import _weborders

TABLES = _weborders.TABLES
PARAMS = _weborders.PARAMS
RESULT_ROW_BYTES = _weborders.RESULT_ROW_BYTES
params = _weborders.params
min_bytes = _weborders.min_bytes


def ws_wh(dfs):
    from spark_rapids_tpu.sql import functions as f
    ws1 = dfs["web_sales"].select(
        f.col("ws_order_number"), f.col("ws_warehouse_sk").alias("wh1"))
    return ws1.join(_weborders.second_sales(dfs),
                    on=((f.col("ws_order_number") == f.col("o2"))
                        & (f.col("wh1") != f.col("wh2")))
                    ).select("ws_order_number", "wh1", "wh2")


def plan(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    pairs = ws_wh(dfs)
    returned = (dfs["web_returns"]
                .join(pairs, on=[("wr_order_number", "ws_order_number")])
                .select("wr_order_number"))
    order = f.col("ws_order_number")
    return _weborders.aggregate(
        _weborders.selected(dfs, p)
        .filter(order.isin_subquery(pairs.select("ws_order_number"))
                & order.isin_subquery(returned)))


def run(dfs, p):
    return plan(dfs, p).collect()


def reference(pds, p):
    ws, wr = pds["web_sales"], pds["web_returns"]
    pair = ws[_weborders.PAIR]
    both = pair.merge(pair, on="ws_order_number", suffixes=("1", "2"))
    # NULL <> x is not true: a NULL warehouse pairs with nothing
    both = both[both.ws_warehouse_sk1.notna() & both.ws_warehouse_sk2.notna()
                & (both.ws_warehouse_sk1 != both.ws_warehouse_sk2)]
    returned = wr.merge(both, left_on="wr_order_number",
                        right_on="ws_order_number").wr_order_number
    m = _weborders.selected_pandas(pds, p)
    m = m[m.ws_order_number.isin(both.ws_order_number)
          & m.ws_order_number.isin(returned)]
    return _weborders.aggregate_pandas(m)

