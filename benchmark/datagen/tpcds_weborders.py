"""The TPC-DS web channel's order tables, for the web-order fulfilment
queries (Q94, Q95): ``web_sales`` (34 columns), ``web_returns`` (24),
``customer_address`` (13) and ``web_site`` (26) after the specification's
(v3) table definitions, with ``date_dim`` as ``datagen/tpcds.py`` writes it
(its function, the same bytes).  At SF1: ``web_sales`` 719,384 rows in
60,000 orders, ``web_returns`` 71,763, ``customer_address`` 50,000,
``web_site`` 30.

An order is 8..16 lines (the last is cut where the row count ends) of
different items that share sold date and time, ship date (1..120
days after the sale), the billing and shipping customer, demographics and
addresses, web page, web site and ship mode; ``ws_warehouse_sk`` (1..5 at
SF1), ``ws_item_sk`` and ``ws_promo_sk`` are drawn a LINE, so most orders
ship from several warehouses: the predicate both queries turn on.  About 4%
of each foreign key is NULL, a line at a time (``datagen/tpcds.py``'s
convention), ``ws_warehouse_sk`` included: the case in which the
specification's EXISTS and an aggregate over warehouses disagree.  A return
is one sold line in ten, drawn without replacement, and carries that line's
``(ws_order_number, ws_item_sk)``; its amounts derive from the line's.

It stands in for ``dsdgen`` and is not it (``assumed`` in the configuration
file): the random streams are numpy's; sales are spread evenly over
1998-01-02..2003-01-02; ``ca_state`` comes from ``tpcds_reports``' pool of 14
states (``dsdgen`` weights some fifty by population); a site's company goes
round ``dsdgen``'s six syllables by ``web_site_sk`` so that every scale
factor has a ``'pri'``; text comes out of the word pool; money is float64.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Iterable, Optional

import numpy as np

from datagen import tpcds
from datagen._columns import money, pick, text
from datagen.tpcds_reports import STATES

_WEB_SALES_PER_SF = 719_384
_WEB_RETURNS_PER_SF = 71_763
_ADDRESS_PER_SF = 50_000
TABLES = ["customer_address", "date_dim", "web_returns", "web_sales",
          "web_site"]

_I, _F, _D, _S = tpcds._I, tpcds._F, tpcds._D, tpcds._S
SCHEMA = {
    "date_dim": tpcds.SCHEMA["date_dim"],
    "web_sales": {
        "ws_sold_date_sk": _I, "ws_sold_time_sk": _I, "ws_ship_date_sk": _I,
        "ws_item_sk": _I, "ws_bill_customer_sk": _I, "ws_bill_cdemo_sk": _I,
        "ws_bill_hdemo_sk": _I, "ws_bill_addr_sk": _I,
        "ws_ship_customer_sk": _I, "ws_ship_cdemo_sk": _I,
        "ws_ship_hdemo_sk": _I, "ws_ship_addr_sk": _I, "ws_web_page_sk": _I,
        "ws_web_site_sk": _I, "ws_ship_mode_sk": _I, "ws_warehouse_sk": _I,
        "ws_promo_sk": _I, "ws_order_number": _I, "ws_quantity": _I,
        "ws_wholesale_cost": _F, "ws_list_price": _F, "ws_sales_price": _F,
        "ws_ext_discount_amt": _F, "ws_ext_sales_price": _F,
        "ws_ext_wholesale_cost": _F, "ws_ext_list_price": _F,
        "ws_ext_tax": _F, "ws_coupon_amt": _F, "ws_ext_ship_cost": _F,
        "ws_net_paid": _F, "ws_net_paid_inc_tax": _F,
        "ws_net_paid_inc_ship": _F, "ws_net_paid_inc_ship_tax": _F,
        "ws_net_profit": _F},
    "web_returns": {
        "wr_returned_date_sk": _I, "wr_returned_time_sk": _I,
        "wr_item_sk": _I, "wr_refunded_customer_sk": _I,
        "wr_refunded_cdemo_sk": _I, "wr_refunded_hdemo_sk": _I,
        "wr_refunded_addr_sk": _I, "wr_returning_customer_sk": _I,
        "wr_returning_cdemo_sk": _I, "wr_returning_hdemo_sk": _I,
        "wr_returning_addr_sk": _I, "wr_web_page_sk": _I,
        "wr_reason_sk": _I, "wr_order_number": _I,
        "wr_return_quantity": _I, "wr_return_amt": _F, "wr_return_tax": _F,
        "wr_return_amt_inc_tax": _F, "wr_fee": _F,
        "wr_return_ship_cost": _F, "wr_refunded_cash": _F,
        "wr_reversed_charge": _F, "wr_account_credit": _F,
        "wr_net_loss": _F},
    "customer_address": {
        "ca_address_sk": _I, "ca_address_id": _S, "ca_street_number": _S,
        "ca_street_name": _S, "ca_street_type": _S, "ca_suite_number": _S,
        "ca_city": _S, "ca_county": _S, "ca_state": _S, "ca_zip": _S,
        "ca_country": _S, "ca_gmt_offset": _F, "ca_location_type": _S},
    "web_site": {
        "web_site_sk": _I, "web_site_id": _S, "web_rec_start_date": _D,
        "web_rec_end_date": _D, "web_name": _S, "web_open_date_sk": _I,
        "web_close_date_sk": _I, "web_class": _S, "web_manager": _S,
        "web_mkt_id": _I, "web_mkt_class": _S, "web_mkt_desc": _S,
        "web_market_manager": _S, "web_company_id": _I,
        "web_company_name": _S, "web_street_number": _S,
        "web_street_name": _S, "web_street_type": _S,
        "web_suite_number": _S, "web_city": _S, "web_county": _S,
        "web_state": _S, "web_zip": _S, "web_country": _S,
        "web_gmt_offset": _F, "web_tax_percentage": _F},
}

# dsdgen's syllables for a company id 1..6
COMPANIES = ["ought", "able", "pri", "ese", "anti", "cally"]
_STREET_TYPES = ["Ave", "Blvd", "Boulevard", "Circle", "Court", "Ct.",
                 "Dr.", "Drive", "Lane", "Ln", "Parkway", "Pkwy", "RD",
                 "Road", "ST", "Street", "Way", "Wy"]
_CITIES = ["Midway", "Fairview", "Oak Grove", "Five Points", "Pleasant Hill",
           "Riverside", "Centerville", "Mount Pleasant", "Salem", "Union",
           "Greenville", "Franklin", "Lebanon", "Springfield", "Clinton"]
_COUNTIES = ["Williamson County", "Ziebach County", "Walker County",
             "Fairfield County", "Richland County", "Bronx County",
             "Franklin Parish", "Daviess County", "Barrow County",
             "Luce County"]
_LOCATIONS = ["apartment", "condo", "single family"]


def n_sites(sf: float) -> int:
    return max(6, int(30 * sf))


def n_warehouses(sf: float) -> int:
    return max(5, int(5 * sf))


def rows(sf: float) -> Dict[str, int]:
    return {"date_dim": tpcds._N_DATES,
            "web_sales": max(64, int(_WEB_SALES_PER_SF * sf)),
            "web_returns": max(6, int(_WEB_RETURNS_PER_SF * sf)),
            "customer_address": max(32, int(_ADDRESS_PER_SF * sf)),
            "web_site": n_sites(sf)}


def _nullable(rng, values, frac=0.04):
    """A foreign key column: some rows carry a NULL, as dsdgen writes them
    (``datagen/tpcds.py``'s convention and share)."""
    import pyarrow as pa
    return pa.array(values, type=pa.int64(),
                    mask=rng.random(len(values)) < frac)


def _numbered(rng, lo, hi, n, fmt):
    import pyarrow as pa
    return pa.array([fmt(v) for v in rng.integers(lo, hi, n).tolist()])


def _street(rng, n, prefix):
    """The ten address columns the two dimensions share, street number
    to GMT offset, under ``prefix``."""
    return {
        f"{prefix}_street_number": _numbered(rng, 1, 1000, n, str),
        f"{prefix}_street_name": text(rng, n, 5, 20),
        f"{prefix}_street_type": pick(
            _STREET_TYPES, rng.integers(0, len(_STREET_TYPES), n)),
        f"{prefix}_suite_number": _numbered(rng, 0, 500, n,
                                            lambda v: f"Suite {v}"),
        f"{prefix}_city": pick(_CITIES, rng.integers(0, len(_CITIES), n)),
        f"{prefix}_county": pick(_COUNTIES,
                                 rng.integers(0, len(_COUNTIES), n)),
        f"{prefix}_state": pick(STATES, rng.integers(0, len(STATES), n)),
        f"{prefix}_zip": _numbered(rng, 10_000, 99_999, n,
                                   lambda v: f"{v:05d}"),
        f"{prefix}_country": pick(["United States"], np.zeros(n, np.int64)),
        f"{prefix}_gmt_offset": rng.integers(-8, -4, n).astype(np.float64),
    }


def _customer_address(rng, n: int):
    import pyarrow as pa
    key = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "ca_address_sk": key,
        "ca_address_id": tpcds._business_id(key),
        **_street(rng, n, "ca"),
        "ca_location_type": pick(_LOCATIONS,
                                 rng.integers(0, len(_LOCATIONS), n)),
    })


def _web_site(rng, n: int):
    import pyarrow as pa
    key = np.arange(1, n + 1, dtype=np.int64)
    start = rng.integers(0, 3, n)
    starts = np.array(["1997-08-16", "1999-08-16", "2001-08-16"],
                      dtype="datetime64[D]")[start]
    ended = rng.random(n) < 0.5
    closed = rng.random(n) < 0.3
    company = (key - 1) % len(COMPANIES) + 1
    street = _street(rng, n, "web")
    return pa.table({
        "web_site_sk": key,
        "web_site_id": tpcds._business_id((key + 1) // 2),
        "web_rec_start_date": pa.array(starts, type=pa.date32()),
        "web_rec_end_date": pa.array(starts + np.timedelta64(730, "D"),
                                     type=pa.date32(), mask=~ended),
        "web_name": pa.array([f"site_{(k - 1) // 6}" for k in key.tolist()]),
        "web_open_date_sk": rng.integers(tpcds._SOLD[0] - 720,
                                         tpcds._SOLD[0], n),
        "web_close_date_sk": pa.array(
            rng.integers(tpcds._SOLD[0], tpcds._SOLD[1] + 1, n),
            type=pa.int64(), mask=~closed),
        "web_class": pick(["Unknown"], np.zeros(n, np.int64)),
        "web_manager": text(rng, n, 10, 40),
        "web_mkt_id": rng.integers(1, 7, n),
        "web_mkt_class": text(rng, n, 20, 50),
        "web_mkt_desc": text(rng, n, 30, 100),
        "web_market_manager": text(rng, n, 10, 40),
        "web_company_id": company,
        "web_company_name": pick(COMPANIES, company - 1),
        **street,
        "web_tax_percentage": np.round(rng.integers(0, 13, n) / 100.0, 2),
    })


def _items(rng, of, n_item: int):
    """An item a line, no item twice in an order: ``(ws_item_sk,
    ws_order_number)`` is the table's primary key.  Lines that repeat an
    item of their order draw again."""
    item = rng.integers(1, n_item + 1, len(of))
    while True:
        _, first = np.unique(of * (n_item + 1) + item, return_index=True)
        again = np.ones(len(of), dtype=bool)
        again[first] = False
        if not again.any():
            return item
        item[again] = rng.integers(1, n_item + 1, int(again.sum()))


def _web_sales(rng, n: int, sf: float):
    """``web_sales`` and what ``web_returns`` derives from it."""
    import pyarrow as pa
    n_item = max(16, tpcds.rows(sf)["item"])   # an order's lines differ
    n_cust = max(64, int(100_000 * sf))
    n_cd, n_hd = 1_920_800, 7_200
    n_ca = rows(sf)["customer_address"]
    n_page = max(2, int(60 * max(sf, 0.05)))
    n_promo = max(4, int(300 * max(sf, 0.05)))

    nullable = functools.partial(_nullable, rng)

    lines = rng.integers(8, 17, n // 8 + 1)
    n_order = int(np.searchsorted(np.cumsum(lines), n)) + 1
    of = np.repeat(np.arange(n_order), lines[:n_order])[:n]

    def per_order(lo, hi):
        return rng.integers(lo, hi + 1, n_order)[of]

    sold = per_order(*tpcds._SOLD)
    ship = sold + per_order(1, 120)
    item = _items(rng, of, n_item)
    bill_cust = per_order(1, n_cust)
    qty = rng.integers(1, 101, n)
    wholesale = money(rng, 1.0, 100.0, n)
    list_price = np.round(wholesale * rng.uniform(1.0, 3.0, n), 2)
    sales_price = np.round(list_price * rng.uniform(0.0, 1.0, n), 2)
    ext_sales = np.round(sales_price * qty, 2)
    ext_wholesale = np.round(wholesale * qty, 2)
    ext_list = np.round(list_price * qty, 2)
    coupon = np.round(ext_sales * rng.uniform(0.0, 1.0, n)
                      * (rng.random(n) < 0.2), 2)
    ship_cost = np.round(list_price * rng.uniform(0.0, 0.5, n) * qty, 2)
    net_paid = np.round(ext_sales - coupon, 2)
    ext_tax = np.round(net_paid * rng.integers(0, 10, n) / 100.0, 2)
    table = pa.table({
        "ws_sold_date_sk": nullable(sold),
        "ws_sold_time_sk": nullable(per_order(0, 86_399)),
        "ws_ship_date_sk": nullable(ship),
        "ws_item_sk": item,
        "ws_bill_customer_sk": nullable(bill_cust),
        "ws_bill_cdemo_sk": nullable(per_order(1, n_cd)),
        "ws_bill_hdemo_sk": nullable(per_order(1, n_hd)),
        "ws_bill_addr_sk": nullable(per_order(1, n_ca)),
        "ws_ship_customer_sk": nullable(per_order(1, n_cust)),
        "ws_ship_cdemo_sk": nullable(per_order(1, n_cd)),
        "ws_ship_hdemo_sk": nullable(per_order(1, n_hd)),
        "ws_ship_addr_sk": nullable(per_order(1, n_ca)),
        "ws_web_page_sk": nullable(per_order(1, n_page)),
        "ws_web_site_sk": nullable(per_order(1, n_sites(sf))),
        "ws_ship_mode_sk": nullable(per_order(1, 20)),
        "ws_warehouse_sk": nullable(
            rng.integers(1, n_warehouses(sf) + 1, n)),
        "ws_promo_sk": nullable(rng.integers(1, n_promo + 1, n)),
        "ws_order_number": of.astype(np.int64) + 1,
        "ws_quantity": qty,
        "ws_wholesale_cost": wholesale,
        "ws_list_price": list_price,
        "ws_sales_price": sales_price,
        "ws_ext_discount_amt": np.round(ext_list - ext_sales, 2),
        "ws_ext_sales_price": ext_sales,
        "ws_ext_wholesale_cost": ext_wholesale,
        "ws_ext_list_price": ext_list,
        "ws_ext_tax": ext_tax,
        "ws_coupon_amt": coupon,
        "ws_ext_ship_cost": ship_cost,
        "ws_net_paid": net_paid,
        "ws_net_paid_inc_tax": np.round(net_paid + ext_tax, 2),
        "ws_net_paid_inc_ship": np.round(net_paid + ship_cost, 2),
        "ws_net_paid_inc_ship_tax": np.round(net_paid + ship_cost + ext_tax,
                                            2),
        "ws_net_profit": np.round(net_paid - ext_wholesale, 2),
    })
    sold_lines = {"order": of.astype(np.int64) + 1, "item": item,
                  "ship": ship, "customer": bill_cust, "qty": qty,
                  "sales_price": sales_price, "ship_cost": ship_cost,
                  "n_page": n_page, "n_cust": n_cust, "n_ca": n_ca}
    return table, sold_lines


def _web_returns(rng, n: int, ws: dict):
    """``n`` of the sold lines come back, none twice."""
    import pyarrow as pa
    line = np.sort(rng.choice(len(ws["order"]), size=n, replace=False))
    n_cd, n_hd = 1_920_800, 7_200

    nullable = functools.partial(_nullable, rng)

    qty = np.minimum(rng.integers(1, 101, n), ws["qty"][line])
    amt = np.round(ws["sales_price"][line] * qty, 2)
    tax = np.round(amt * rng.integers(0, 10, n) / 100.0, 2)
    fee = money(rng, 0.5, 100.0, n)
    ship_cost = np.round(ws["ship_cost"][line] * qty / ws["qty"][line], 2)
    cash = np.round(amt * rng.uniform(0.0, 1.0, n), 2)
    reversed_ = np.round((amt - cash) * rng.uniform(0.0, 1.0, n), 2)
    returning = np.where(rng.random(n) < 0.8, ws["customer"][line],
                         rng.integers(1, ws["n_cust"] + 1, n))
    return pa.table({
        "wr_returned_date_sk": nullable(ws["ship"][line]
                                        + rng.integers(1, 91, n)),
        "wr_returned_time_sk": nullable(rng.integers(0, 86_400, n)),
        "wr_item_sk": ws["item"][line],
        "wr_refunded_customer_sk": nullable(ws["customer"][line]),
        "wr_refunded_cdemo_sk": nullable(rng.integers(1, n_cd + 1, n)),
        "wr_refunded_hdemo_sk": nullable(rng.integers(1, n_hd + 1, n)),
        "wr_refunded_addr_sk": nullable(
            rng.integers(1, ws["n_ca"] + 1, n)),
        "wr_returning_customer_sk": nullable(returning),
        "wr_returning_cdemo_sk": nullable(rng.integers(1, n_cd + 1, n)),
        "wr_returning_hdemo_sk": nullable(rng.integers(1, n_hd + 1, n)),
        "wr_returning_addr_sk": nullable(
            rng.integers(1, ws["n_ca"] + 1, n)),
        "wr_web_page_sk": nullable(rng.integers(1, ws["n_page"] + 1, n)),
        "wr_reason_sk": nullable(rng.integers(1, 36, n)),
        "wr_order_number": ws["order"][line],
        "wr_return_quantity": qty,
        "wr_return_amt": amt,
        "wr_return_tax": tax,
        "wr_return_amt_inc_tax": np.round(amt + tax, 2),
        "wr_fee": fee,
        "wr_return_ship_cost": ship_cost,
        "wr_refunded_cash": cash,
        "wr_reversed_charge": reversed_,
        "wr_account_credit": np.round(amt - cash - reversed_, 2),
        "wr_net_loss": np.round(tax + fee + ship_cost, 2),
    })


def gen(sf: float, seed: int, out_dir: str,
        tables: Optional[Iterable[str]] = None,
        chunk: int = 1_000_000) -> Dict[str, str]:
    """Write ``tables`` (all five when None) under ``out_dir``, anew every
    time; returns {table: parquet path}.  ``date_dim`` comes from
    ``tpcds.gen``.  ``web_returns`` draws its lines from ``web_sales``'
    own stream, so it is the same whether or not ``web_sales`` is written."""
    import pyarrow.parquet as pq

    tables = list(tables) if tables is not None else list(TABLES)
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise ValueError(f"tpcds_weborders datagen has no table {unknown}")
    paths = tpcds.gen(sf, seed, out_dir,
                      [t for t in tables if t == "date_dim"], chunk)
    os.makedirs(out_dir, exist_ok=True)
    n = rows(sf)
    made = {}
    if "web_sales" in tables or "web_returns" in tables:
        made["web_sales"], sold = _web_sales(
            tpcds._rng(seed, "web_sales"), n["web_sales"], sf)
        if "web_returns" in tables:
            made["web_returns"] = _web_returns(
                tpcds._rng(seed, "web_returns"),
                min(n["web_returns"], n["web_sales"]), sold)
    if "customer_address" in tables:
        made["customer_address"] = _customer_address(
            tpcds._rng(seed, "customer_address"), n["customer_address"])
    if "web_site" in tables:
        made["web_site"] = _web_site(tpcds._rng(seed, "web_site"),
                                     n["web_site"])
    for table in tables:
        if table == "date_dim":
            continue
        t = made[table]
        assert t.column_names == list(SCHEMA[table]), table
        paths[table] = os.path.join(out_dir, f"{table}.parquet")
        pq.write_table(t, paths[table], row_group_size=chunk)
    return paths
