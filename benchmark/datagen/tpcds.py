"""The TPC-DS ``store_sales`` star (store_sales, date_dim, item) as parquet,
made from a seed, after the specification's (v3) table definitions: every
column of the three tables (``store_sales`` 23, ``date_dim`` 28, ``item``
22), ``date_dim``'s 73,049 days (1900-01-02 .. 2100-01-01, ``d_date_sk`` the
Julian day number, at every scale factor), ``item``'s 18,000 rows x SF,
``store_sales``' 2,880,404 rows x SF sold 1998-01-02 .. 2003-01-02 in tickets
of 8..16 lines that share date, time, customer and store, about 4% of each
foreign key null, and the price columns derived from one another as the
specification's column definitions say (``ss_ext_sales_price =
ss_sales_price * ss_quantity`` and so on).

It stands in for ``dsdgen`` and is not it (``assumed`` in the configuration
file): the random streams are numpy's, so no value equals ``dsdgen``'s;
sales are spread evenly over the days and the items, where ``dsdgen``
weights them by season; text columns come out of the word pool of
``datagen/_columns.py``; money is float64.  The other 21 tables are not written
(``reduced``): the fact's foreign keys into them keep their SF-scaled key
ranges.  ``models/tpcds.py::gen_db``, which this replaces as the yardstick's
generator, is listed in PERF.md for a later PR to delete.

The seed is an argument and every table draws from its own stream of it;
``date_dim`` draws nothing.  A cell over the other channels adds a datagen
file of its own.
"""

from __future__ import annotations

import os
import zlib
from typing import Dict, Iterable, Optional

import numpy as np

from datagen._columns import money, pick, text

_STORE_SALES_PER_SF = 2_880_404
_ITEM_PER_SF = 18_000
_D_FIRST = np.datetime64("1900-01-02")
_N_DATES = 73_049        # .. 2100-01-01
_SK0 = 2_415_022         # d_date_sk of 1900-01-02: its Julian day number
_SOLD = (2_450_816, 2_452_642)   # 1998-01-02 .. 2003-01-02
_TODAY = np.datetime64("2003-01-08")   # dsdgen's CURRENT_DAY
TABLES = ["date_dim", "item", "store_sales"]

_I, _F, _D, _S = 8, 8, 4, 4  # device bytes: see datagen/tpch.py
SCHEMA = {
    "date_dim": {
        "d_date_sk": _I, "d_date_id": _S, "d_date": _D, "d_month_seq": _I,
        "d_week_seq": _I, "d_quarter_seq": _I, "d_year": _I, "d_dow": _I,
        "d_moy": _I, "d_dom": _I, "d_qoy": _I, "d_fy_year": _I,
        "d_fy_quarter_seq": _I, "d_fy_week_seq": _I, "d_day_name": _S,
        "d_quarter_name": _S, "d_holiday": _S, "d_weekend": _S,
        "d_following_holiday": _S, "d_first_dom": _I, "d_last_dom": _I,
        "d_same_day_ly": _I, "d_same_day_lq": _I, "d_current_day": _S,
        "d_current_week": _S, "d_current_month": _S, "d_current_quarter": _S,
        "d_current_year": _S},
    "item": {
        "i_item_sk": _I, "i_item_id": _S, "i_rec_start_date": _D,
        "i_rec_end_date": _D, "i_item_desc": _S, "i_current_price": _F,
        "i_wholesale_cost": _F, "i_brand_id": _I, "i_brand": _S,
        "i_class_id": _I, "i_class": _S, "i_category_id": _I,
        "i_category": _S, "i_manufact_id": _I, "i_manufact": _S,
        "i_size": _S, "i_formulation": _S, "i_color": _S, "i_units": _S,
        "i_container": _S, "i_manager_id": _I, "i_product_name": _S},
    "store_sales": {
        "ss_sold_date_sk": _I, "ss_sold_time_sk": _I, "ss_item_sk": _I,
        "ss_customer_sk": _I, "ss_cdemo_sk": _I, "ss_hdemo_sk": _I,
        "ss_addr_sk": _I, "ss_store_sk": _I, "ss_promo_sk": _I,
        "ss_ticket_number": _I, "ss_quantity": _I, "ss_wholesale_cost": _F,
        "ss_list_price": _F, "ss_sales_price": _F,
        "ss_ext_discount_amt": _F, "ss_ext_sales_price": _F,
        "ss_ext_wholesale_cost": _F, "ss_ext_list_price": _F,
        "ss_ext_tax": _F, "ss_coupon_amt": _F, "ss_net_paid": _F,
        "ss_net_paid_inc_tax": _F, "ss_net_profit": _F},
}

_CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry", "Men",
               "Music", "Shoes", "Sports", "Women"]
_CLASSES = ["accessories", "athletic", "bedding", "classical", "computers",
            "country", "dresses", "earings", "fiction", "fishing", "golf",
            "infants", "kids", "mens", "pop", "reference"]
_COLORS = ["papaya", "peach", "firebrick", "sienna", "slate", "chartreuse",
           "orchid", "salmon", "plum", "maroon", "azure", "gainsboro",
           "powder", "metallic"]
_SIZES = ["petite", "small", "medium", "large", "extra large", "economy",
          "N/A"]
_UNITS = ["Bunch", "Bundle", "Box", "Carton", "Case", "Cup", "Dozen",
          "Dram", "Each", "Gram", "Gross", "Lb", "N/A", "Ounce", "Oz",
          "Pallet", "Pound", "Tbl", "Ton", "Tsp", "Unknown"]
_DAYS = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
         "Saturday"]


def rows(sf: float) -> Dict[str, int]:
    return {"date_dim": _N_DATES,
            "item": max(8, int(_ITEM_PER_SF * sf)),
            "store_sales": max(64, int(_STORE_SALES_PER_SF * sf))}


def _rng(seed: int, table: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(table.encode())])


def _business_id(keys):
    """``AAAAAAAABAAAAAAA``: dsdgen's 16 letters for a surrogate key."""
    import pyarrow as pa
    out = []
    for k in keys.tolist():
        s = ""
        for _ in range(8):
            s += chr(65 + k % 16)
            k //= 16
        out.append(s + "AAAAAAAA")
    return pa.array(out)


def _flags(mask):
    import pyarrow as pa
    return pa.array(["N", "Y"]).take(pa.array(mask.astype(np.int64)))


def _date_dim():
    import pyarrow as pa
    day = np.arange(_N_DATES, dtype=np.int64)
    date = _D_FIRST + day.astype("timedelta64[D]")
    sk = _SK0 + day
    month0 = date.astype("datetime64[M]")
    year = date.astype("datetime64[Y]").astype(np.int64) + 1970
    moy = month0.astype(np.int64) % 12 + 1
    qoy = (moy - 1) // 3 + 1
    dow = (date.astype(np.int64) + 4) % 7          # 0 = Sunday
    dom0 = (date - month0).astype(np.int64)
    first_dom = sk - dom0
    last_dom = first_dom + ((month0 + 1).astype("datetime64[D]")
                            - month0).astype(np.int64) - 1
    month_seq = (year - 1900) * 12 + moy - 1
    quarter_seq = (year - 1900) * 4 + qoy
    week_seq = (day + 1) // 7 + 1                  # weeks start on Sunday
    holiday = ((moy == 1) & (dom0 == 0)) | ((moy == 7) & (dom0 == 3)) | \
        ((moy == 12) & (dom0 == 24))
    today = int((_TODAY - _D_FIRST).astype(np.int64))
    return pa.table({
        "d_date_sk": sk,
        "d_date_id": _business_id(sk),
        "d_date": pa.array(date, type=pa.date32()),
        "d_month_seq": month_seq,
        "d_week_seq": week_seq,
        "d_quarter_seq": quarter_seq,
        "d_year": year,
        "d_dow": dow,
        "d_moy": moy,
        "d_dom": dom0 + 1,
        "d_qoy": qoy,
        "d_fy_year": year,
        "d_fy_quarter_seq": quarter_seq,
        "d_fy_week_seq": week_seq,
        "d_day_name": pa.array(_DAYS).take(pa.array(dow)),
        "d_quarter_name": pa.array([f"{y}Q{q}" for y, q in zip(
            year.tolist(), qoy.tolist())]),
        "d_holiday": _flags(holiday),
        "d_weekend": _flags((dow == 0) | (dow == 6)),
        "d_following_holiday": _flags(np.roll(holiday, 1)),
        "d_first_dom": first_dom,
        "d_last_dom": last_dom,
        "d_same_day_ly": sk - 365,
        "d_same_day_lq": sk - 91,
        "d_current_day": _flags(day == today),
        "d_current_week": _flags(week_seq == week_seq[today]),
        "d_current_month": _flags(month_seq == month_seq[today]),
        "d_current_quarter": _flags(quarter_seq == quarter_seq[today]),
        "d_current_year": _flags(year == year[today]),
    })


def _item(rng, n_item: int):
    import pyarrow as pa
    key = np.arange(1, n_item + 1, dtype=np.int64)
    cat_id = rng.integers(1, len(_CATEGORIES) + 1, n_item)
    class_id = rng.integers(1, len(_CLASSES) + 1, n_item)
    manufact_id = rng.integers(1, 1001, n_item)
    brand_id = rng.integers(1001001, 10016017, n_item)
    wholesale = money(rng, 0.05, 90.0, n_item)
    start = rng.integers(0, 3, n_item)   # one of three revisions' start days
    starts = np.array(["1997-10-27", "2000-10-27", "2001-10-27"],
                      dtype="datetime64[D]")[start]
    ended = rng.random(n_item) < 0.5
    return pa.table({
        "i_item_sk": key,
        "i_item_id": _business_id((key + 1) // 2),  # two revisions an id
        "i_rec_start_date": pa.array(starts, type=pa.date32()),
        "i_rec_end_date": pa.array(starts + np.timedelta64(730, "D"),
                                   type=pa.date32(), mask=~ended),
        "i_item_desc": text(rng, n_item, 1, 200),
        "i_current_price": np.round(wholesale
                                    * rng.uniform(1.1, 3.3, n_item), 2),
        "i_wholesale_cost": wholesale,
        "i_brand_id": brand_id,
        "i_brand": pa.array([f"brand#{b % 997}" for b in brand_id.tolist()]),
        "i_class_id": class_id,
        "i_class": pick(_CLASSES, class_id - 1),
        "i_category_id": cat_id,
        "i_category": pick(_CATEGORIES, cat_id - 1),
        "i_manufact_id": manufact_id,
        "i_manufact": pa.array([f"manufact#{m}"
                                for m in manufact_id.tolist()]),
        "i_size": pick(_SIZES, rng.integers(0, len(_SIZES), n_item)),
        "i_formulation": text(rng, n_item, 20, 20),
        "i_color": pick(_COLORS, rng.integers(0, len(_COLORS), n_item)),
        "i_units": pick(_UNITS, rng.integers(0, len(_UNITS), n_item)),
        "i_container": pick(["Unknown"], np.zeros(n_item, np.int64)),
        "i_manager_id": rng.integers(1, 101, n_item),
        "i_product_name": text(rng, n_item, 10, 50),
    })


def _store_sales(rng, n: int, n_item: int, sf: float):
    import pyarrow as pa
    # key ranges of the dimensions that are not written here
    n_cust = max(64, int(100_000 * sf))
    n_cd = 1_920_800
    n_hd = 7_200
    n_ca = max(32, int(50_000 * sf))
    n_store = max(2, int(12 * max(sf, 0.1)))
    n_promo = max(4, int(300 * max(sf, 0.05)))

    def nullable(values, frac=0.04):
        # some fact rows carry a null foreign key, as dsdgen writes them
        return pa.array(values, type=pa.int64(),
                        mask=rng.random(len(values)) < frac)

    # a ticket is 8..16 lines sold at once: one date, time, customer, store
    lines = rng.integers(8, 17, n // 8 + 1)
    n_tick = int(np.searchsorted(np.cumsum(lines), n)) + 1
    of = np.repeat(np.arange(n_tick), lines[:n_tick])[:n]

    def per_ticket(lo, hi):
        return rng.integers(lo, hi + 1, n_tick)[of]

    qty = rng.integers(1, 101, n)
    wholesale = money(rng, 1.0, 100.0, n)
    list_price = np.round(wholesale * rng.uniform(1.0, 3.0, n), 2)
    sales_price = np.round(list_price * rng.uniform(0.0, 1.0, n), 2)
    ext_sales = np.round(sales_price * qty, 2)
    ext_wholesale = np.round(wholesale * qty, 2)
    ext_list = np.round(list_price * qty, 2)
    coupon = np.round(ext_sales * rng.uniform(0.0, 1.0, n)
                      * (rng.random(n) < 0.2), 2)
    net_paid = np.round(ext_sales - coupon, 2)
    ext_tax = np.round(net_paid * rng.integers(0, 10, n) / 100.0, 2)
    return pa.table({
        "ss_sold_date_sk": nullable(per_ticket(*_SOLD)),
        "ss_sold_time_sk": nullable(per_ticket(28_800, 75_599)),
        "ss_item_sk": rng.integers(1, n_item + 1, n),
        "ss_customer_sk": nullable(per_ticket(1, n_cust)),
        "ss_cdemo_sk": nullable(per_ticket(1, n_cd)),
        "ss_hdemo_sk": nullable(per_ticket(1, n_hd)),
        "ss_addr_sk": nullable(per_ticket(1, n_ca)),
        "ss_store_sk": nullable(per_ticket(1, n_store)),
        "ss_promo_sk": nullable(rng.integers(1, n_promo + 1, n)),
        "ss_ticket_number": of.astype(np.int64) + 1,
        "ss_quantity": qty,
        "ss_wholesale_cost": wholesale,
        "ss_list_price": list_price,
        "ss_sales_price": sales_price,
        "ss_ext_discount_amt": np.round(ext_list - ext_sales, 2),
        "ss_ext_sales_price": ext_sales,
        "ss_ext_wholesale_cost": ext_wholesale,
        "ss_ext_list_price": ext_list,
        "ss_ext_tax": ext_tax,
        "ss_coupon_amt": coupon,
        "ss_net_paid": net_paid,
        "ss_net_paid_inc_tax": np.round(net_paid + ext_tax, 2),
        "ss_net_profit": np.round(net_paid - ext_wholesale, 2),
    })


def gen(sf: float, seed: int, out_dir: str,
        tables: Optional[Iterable[str]] = None,
        chunk: int = 1_000_000) -> Dict[str, str]:
    """Write ``tables`` (all three when None) under ``out_dir``, anew every
    time, in row groups of ``chunk`` rows; returns {table: parquet path}."""
    import pyarrow.parquet as pq

    tables = list(tables) if tables is not None else list(TABLES)
    unknown = sorted(set(tables) - set(TABLES))
    if unknown:
        raise ValueError(f"tpcds datagen has no table {unknown}")
    os.makedirs(out_dir, exist_ok=True)
    paths = {t: os.path.join(out_dir, f"{t}.parquet") for t in tables}
    n = rows(sf)
    make = {
        "date_dim": _date_dim,
        "item": lambda: _item(_rng(seed, "item"), n["item"]),
        "store_sales": lambda: _store_sales(
            _rng(seed, "store_sales"), n["store_sales"], n["item"], sf),
    }
    for table in tables:
        t = make[table]()
        assert t.column_names == list(SCHEMA[table]), table
        pq.write_table(t, paths[table], row_group_size=chunk)
    return paths
