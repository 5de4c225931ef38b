"""TPC-DS Q94 (query94.tpl): the web orders of one state and 61 days of
ship dates, sold through the sites of company 'pri', that shipped from more
than one warehouse and had NO line returned.  The specification's text::

    select count(distinct ws_order_number) as "order count",
           sum(ws_ext_ship_cost) as "total shipping cost",
           sum(ws_net_profit) as "total net profit"
    from web_sales ws1, date_dim, customer_address, web_site
    where d_date between '[YEAR]-[MONTH]-01'
                     and (cast('[YEAR]-[MONTH]-01' as date) + 60 days)
      and ws1.ws_ship_date_sk = d_date_sk
      and ws1.ws_ship_addr_sk = ca_address_sk and ca_state = '[STATE]'
      and ws1.ws_web_site_sk = web_site_sk and web_company_name = 'pri'
      and exists (select * from web_sales ws2
                  where ws1.ws_order_number = ws2.ws_order_number
                    and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
      and not exists (select * from web_returns wr1
                      where ws1.ws_order_number = wr1.wr_order_number)
    order by count(distinct ws_order_number) limit 100

EXISTS is a semi join whose ``<>`` takes part in matching, NOT EXISTS an
anti join.  A line whose own ``ws_warehouse_sk`` is NULL satisfies no
``<>``: the text drops it, where an aggregate over the order's warehouses
(``models/tpcds_q2.py``) keeps it.  (The text, written from memory of the
templates: ``assumed``.)
"""

from queries.tpcds import _weborders

TABLES = _weborders.TABLES
PARAMS = _weborders.PARAMS
RESULT_ROW_BYTES = _weborders.RESULT_ROW_BYTES
params = _weborders.params
min_bytes = _weborders.min_bytes


def plan(dfs, p):
    from spark_rapids_tpu.sql import functions as f
    return _weborders.aggregate(
        _weborders.selected(dfs, p)
        .join(_weborders.second_sales(dfs),
              on=((f.col("ws_order_number") == f.col("o2"))
                  & (f.col("ws_warehouse_sk") != f.col("wh2"))),
              how="semi")
        .join(dfs["web_returns"].select("wr_order_number"),
              on=[("ws_order_number", "wr_order_number")], how="anti"))


def run(dfs, p):
    return plan(dfs, p).collect()


def reference(pds, p):
    ws, wr = pds["web_sales"], pds["web_returns"]
    m = _weborders.selected_pandas(pds, p)
    # exists: another line of the order in a warehouse that differs; NULL
    # on either side satisfies no <>
    m = m.reset_index(drop=True)
    cand = m[["ws_order_number", "ws_warehouse_sk"]].reset_index().merge(
        ws[_weborders.PAIR], on="ws_order_number", suffixes=("", "2"))
    cand = cand[cand.ws_warehouse_sk.notna() & cand.ws_warehouse_sk2.notna()
                & (cand.ws_warehouse_sk != cand.ws_warehouse_sk2)]
    m = m[m.index.isin(cand["index"])]
    m = m[~m.ws_order_number.isin(wr.wr_order_number)]
    return _weborders.aggregate_pandas(m)

