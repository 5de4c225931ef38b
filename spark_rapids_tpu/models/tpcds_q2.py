"""TPC-DS acceptance queries, wave 2 (VERDICT r4 item 4).

Seventeen more queries over the v2 star schema (store/catalog/web
channels, returns, customer/address/household dims), including the
BASELINE.json shuffle-stress pair q64 and q95.  Same
(runner(dfs) -> rows, oracle(pds) -> rows) contract as models/tpcds.py;
each runner/oracle pair ends in a deterministic total order so the
differential harness compares exactly.

Queries follow the official TPC-DS SQL shapes (v2.4,
tools/query_templates) restricted to the columns the generator
produces; reference checklist:
integration_tests/src/main/python (SURVEY.md Appendix B).
"""

from __future__ import annotations

from typing import Dict, List


def _F():
    from ..sql import functions
    return functions


# ---------------------------------------------------------------------------------
# q12 / q20 / q98 — revenue-ratio within class, one per channel
# ---------------------------------------------------------------------------------

_Q12_CATS = ["Sports", "Books", "Home"]


def _revratio_runner(dfs, fact, item_col, price_col, date_lo, date_hi):
    # Q12 / Q20 / Q98 are written AROUND their window: the specification's
    # ``sum(sum(price)) over (partition by i_class)`` is the join with a
    # second aggregate below, from when a window over a string partition
    # fell back to the CPU.  It no longer does (plan/window_exec.py: string
    # keys as dictionary codes), but these stay as they are: the
    # differential tests pin this plan shape (tests/test_tpcds.py and the
    # fusion / sync-budget counts over it).  The window form of the same
    # idea is benchmark/queries/tpcds/q89.py (``avg(sum(..)) over``).
    pre = {"web_sales": "ws", "catalog_sales": "cs",
           "store_sales": "ss"}[fact]
    f = _F()
    import datetime
    lo = datetime.date(*date_lo)
    hi = datetime.date(*date_hi)
    sales = (dfs[fact]
             .join(dfs["item"].filter(f.col("i_category").isin(_Q12_CATS)),
                   on=[(item_col, "i_item_sk")])
             .join(dfs["date_dim"].filter(
                 (f.col("d_date") >= lo) & (f.col("d_date") <= hi)),
                 on=[(pre + "_sold_date_sk", "d_date_sk")]))
    per_item = (sales.group_by("i_item_id", "i_class", "i_category",
                               "i_current_price")
                .agg(f.sum(f.col(price_col)).alias("itemrevenue")))
    per_class = (per_item.group_by(f.col("i_class").alias("cls"))
                 .agg(f.sum(f.col("itemrevenue")).alias("classrevenue")))
    q = (per_item.join(per_class, on=[("i_class", "cls")])
         .select("i_item_id", "i_category", "i_class", "i_current_price",
                 "itemrevenue",
                 (f.col("itemrevenue") * 100.0
                  / f.col("classrevenue")).alias("revenueratio"))
         .sort("i_category", "i_class", "i_item_id", "revenueratio")
         .limit(100))
    return q.collect()


def _revratio_oracle(pds, fact, item_col, price_col, date_lo, date_hi):
    pre = {"web_sales": "ws", "catalog_sales": "cs",
           "store_sales": "ss"}[fact]
    import datetime
    lo = datetime.date(*date_lo)
    hi = datetime.date(*date_hi)
    i, d, s = pds["item"], pds["date_dim"], pds[fact]
    m = (s.merge(i[i.i_category.isin(_Q12_CATS)], left_on=item_col,
                 right_on="i_item_sk")
         .merge(d[(d.d_date >= lo) & (d.d_date <= hi)],
                left_on=pre + "_sold_date_sk", right_on="d_date_sk"))
    g = (m.groupby(["i_item_id", "i_class", "i_category",
                    "i_current_price"])[price_col]
         .sum().reset_index(name="itemrevenue"))
    cls = g.groupby("i_class")["itemrevenue"].sum().rename("classrevenue")
    g = g.join(cls, on="i_class")
    g["revenueratio"] = g.itemrevenue * 100.0 / g.classrevenue
    g = g.sort_values(["i_category", "i_class", "i_item_id",
                       "revenueratio"]).head(100)
    return [(r.i_item_id, r.i_category, r.i_class, r.i_current_price,
             r.itemrevenue, r.revenueratio) for r in g.itertuples()]


def run_q12(dfs):
    return _revratio_runner(dfs, "web_sales", "ws_item_sk",
                            "ws_ext_sales_price", (1999, 2, 22),
                            (1999, 3, 24))


def pandas_q12(pds):
    return _revratio_oracle(pds, "web_sales", "ws_item_sk",
                            "ws_ext_sales_price", (1999, 2, 22),
                            (1999, 3, 24))


def run_q20(dfs):
    return _revratio_runner(dfs, "catalog_sales", "cs_item_sk",
                            "cs_ext_sales_price", (1999, 2, 22),
                            (1999, 3, 24))


def pandas_q20(pds):
    return _revratio_oracle(pds, "catalog_sales", "cs_item_sk",
                            "cs_ext_sales_price", (1999, 2, 22),
                            (1999, 3, 24))


def run_q98(dfs):
    return _revratio_runner(dfs, "store_sales", "ss_item_sk",
                            "ss_ext_sales_price", (1999, 2, 22),
                            (1999, 3, 24))


def pandas_q98(pds):
    return _revratio_oracle(pds, "store_sales", "ss_item_sk",
                            "ss_ext_sales_price", (1999, 2, 22),
                            (1999, 3, 24))


# ---------------------------------------------------------------------------------
# q13 — single-row averages under OR'd demographic/address conditions
# ---------------------------------------------------------------------------------

def run_q13(dfs):
    f = _F()
    cd_ok = (
        ((f.col("cd_marital_status") == "M")
         & (f.col("cd_education_status") == "Advanced Degree")
         & (f.col("ss_sales_price").between(100.0, 150.0)))
        | ((f.col("cd_marital_status") == "S")
           & (f.col("cd_education_status") == "College")
           & (f.col("ss_sales_price").between(50.0, 100.0)))
        | ((f.col("cd_marital_status") == "W")
           & (f.col("cd_education_status") == "2 yr Degree")
           & (f.col("ss_sales_price").between(150.0, 200.0))))
    ca_ok = (
        (f.col("ca_state").isin(["TX", "OH", "TX"])
         & f.col("ss_net_profit").between(100.0, 200.0))
        | (f.col("ca_state").isin(["OR", "NM", "KY"])
           & f.col("ss_net_profit").between(150.0, 300.0))
        | (f.col("ca_state").isin(["VA", "TX", "MS"])
           & f.col("ss_net_profit").between(50.0, 250.0)))
    q = (dfs["store_sales"]
         .join(dfs["store"], on=[("ss_store_sk", "s_store_sk")])
         .join(dfs["date_dim"].filter(f.col("d_year") == 2001),
               on=[("ss_sold_date_sk", "d_date_sk")])
         .join(dfs["customer_demographics"],
               on=[("ss_cdemo_sk", "cd_demo_sk")])
         .join(dfs["customer_address"].filter(
             f.col("ca_country") == "United States"),
             on=[("ss_addr_sk", "ca_address_sk")])
         .filter(cd_ok & ca_ok)
         .agg(f.avg(f.col("ss_quantity")).alias("a1"),
              f.avg(f.col("ss_ext_sales_price")).alias("a2"),
              f.avg(f.col("ss_ext_wholesale_cost")).alias("a3"),
              f.sum(f.col("ss_ext_wholesale_cost")).alias("a4")))
    return q.collect()


def pandas_q13(pds):
    ss, st, d, cd, ca = (pds[k] for k in
                         ["store_sales", "store", "date_dim",
                          "customer_demographics", "customer_address"])
    m = (ss.merge(st, left_on="ss_store_sk", right_on="s_store_sk")
         .merge(d[d.d_year == 2001], left_on="ss_sold_date_sk",
                right_on="d_date_sk")
         .merge(cd, left_on="ss_cdemo_sk", right_on="cd_demo_sk")
         .merge(ca[ca.ca_country == "United States"],
                left_on="ss_addr_sk", right_on="ca_address_sk"))
    cd_ok = (((m.cd_marital_status == "M")
              & (m.cd_education_status == "Advanced Degree")
              & m.ss_sales_price.between(100.0, 150.0))
             | ((m.cd_marital_status == "S")
                & (m.cd_education_status == "College")
                & m.ss_sales_price.between(50.0, 100.0))
             | ((m.cd_marital_status == "W")
                & (m.cd_education_status == "2 yr Degree")
                & m.ss_sales_price.between(150.0, 200.0)))
    ca_ok = ((m.ca_state.isin(["TX", "OH"])
              & m.ss_net_profit.between(100.0, 200.0))
             | (m.ca_state.isin(["OR", "NM", "KY"])
                & m.ss_net_profit.between(150.0, 300.0))
             | (m.ca_state.isin(["VA", "TX", "MS"])
                & m.ss_net_profit.between(50.0, 250.0)))
    m = m[cd_ok & ca_ok]
    import numpy as np
    return [(m.ss_quantity.mean() if len(m) else None,
             m.ss_ext_sales_price.mean() if len(m) else None,
             m.ss_ext_wholesale_cost.mean() if len(m) else None,
             m.ss_ext_wholesale_cost.sum() if len(m) else None)]


# ---------------------------------------------------------------------------------
# q19 — brand revenue where customer zip prefix differs from store zip
# ---------------------------------------------------------------------------------

def run_q19(dfs):
    f = _F()
    q = (dfs["store_sales"]
         .join(dfs["date_dim"].filter(
             (f.col("d_moy") == 11) & (f.col("d_year") == 1998)),
             on=[("ss_sold_date_sk", "d_date_sk")])
         .join(dfs["item"].filter(f.col("i_manager_id") == 8),
               on=[("ss_item_sk", "i_item_sk")])
         .join(dfs["customer"], on=[("ss_customer_sk", "c_customer_sk")])
         .join(dfs["customer_address"],
               on=[("c_current_addr_sk", "ca_address_sk")])
         .join(dfs["store"], on=[("ss_store_sk", "s_store_sk")])
         .filter(f.col("ca_zip").substr(1, 5)
                 != f.col("s_zip").substr(1, 5))
         .group_by("i_brand_id", "i_brand", "i_manufact_id")
         .agg(f.sum(f.col("ss_ext_sales_price")).alias("ext_price"))
         .sort(f.col("ext_price").desc(), f.col("i_brand_id").asc(),
               f.col("i_brand").asc(), f.col("i_manufact_id").asc())
         .limit(100))
    return q.collect()


def pandas_q19(pds):
    ss, d, i, c, ca, st = (pds[k] for k in
                           ["store_sales", "date_dim", "item", "customer",
                            "customer_address", "store"])
    m = (ss.merge(d[(d.d_moy == 11) & (d.d_year == 1998)],
                  left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(i[i.i_manager_id == 8], left_on="ss_item_sk",
                right_on="i_item_sk")
         .merge(c, left_on="ss_customer_sk", right_on="c_customer_sk")
         .merge(ca, left_on="c_current_addr_sk", right_on="ca_address_sk")
         .merge(st, left_on="ss_store_sk", right_on="s_store_sk"))
    m = m[m.ca_zip.str[:5] != m.s_zip.str[:5]]
    g = (m.groupby(["i_brand_id", "i_brand", "i_manufact_id"])
         ["ss_ext_sales_price"].sum().reset_index(name="ext_price")
         .sort_values(["ext_price", "i_brand_id", "i_brand",
                       "i_manufact_id"],
                      ascending=[False, True, True, True]).head(100))
    return [(r.i_brand_id, r.i_brand, r.i_manufact_id, r.ext_price)
            for r in g.itertuples()]


# ---------------------------------------------------------------------------------
# q25 — store sale -> store return -> catalog re-purchase, profit sums
# ---------------------------------------------------------------------------------

def run_q25(dfs):
    f = _F()
    d1 = dfs["date_dim"].filter((f.col("d_moy") == 4)
                                & (f.col("d_year") == 2001))
    d2 = (dfs["date_dim"]
          .filter(f.col("d_moy").between(4, 10)
                  & (f.col("d_year") == 2001))
          .select(f.col("d_date_sk").alias("d2_sk")))
    d3 = (dfs["date_dim"]
          .filter(f.col("d_moy").between(4, 10)
                  & (f.col("d_year") == 2001))
          .select(f.col("d_date_sk").alias("d3_sk")))
    q = (dfs["store_sales"]
         .join(dfs["store_returns"],
               on=[("ss_customer_sk", "sr_customer_sk"),
                   ("ss_item_sk", "sr_item_sk"),
                   ("ss_ticket_number", "sr_ticket_number")])
         .join(dfs["catalog_sales"],
               on=[("sr_customer_sk", "cs_bill_customer_sk"),
                   ("sr_item_sk", "cs_item_sk")])
         .join(d1, on=[("ss_sold_date_sk", "d_date_sk")])
         .join(d2, on=[("sr_returned_date_sk", "d2_sk")])
         .join(d3, on=[("cs_sold_date_sk", "d3_sk")])
         .join(dfs["store"], on=[("ss_store_sk", "s_store_sk")])
         .join(dfs["item"], on=[("ss_item_sk", "i_item_sk")])
         .group_by("i_item_id", "s_store_id", "s_store_name")
         .agg(f.sum(f.col("ss_net_profit")).alias("store_sales_profit"),
              f.sum(f.col("sr_net_loss")).alias("store_returns_loss"),
              f.sum(f.col("cs_net_profit")).alias("catalog_sales_profit"))
         .sort("i_item_id", "s_store_id", "s_store_name")
         .limit(100))
    return q.collect()


def pandas_q25(pds):
    ss, sr, cs, d, st, i = (pds[k] for k in
                            ["store_sales", "store_returns",
                             "catalog_sales", "date_dim", "store", "item"])
    d1 = d[(d.d_moy == 4) & (d.d_year == 2001)]
    d23 = d[d.d_moy.between(4, 10) & (d.d_year == 2001)]
    m = (ss.merge(sr, left_on=["ss_customer_sk", "ss_item_sk",
                               "ss_ticket_number"],
                  right_on=["sr_customer_sk", "sr_item_sk",
                            "sr_ticket_number"])
         .merge(cs, left_on=["sr_customer_sk", "sr_item_sk"],
                right_on=["cs_bill_customer_sk", "cs_item_sk"])
         .merge(d1[["d_date_sk"]], left_on="ss_sold_date_sk",
                right_on="d_date_sk")
         .merge(d23[["d_date_sk"]].rename(columns={"d_date_sk": "d2"}),
                left_on="sr_returned_date_sk", right_on="d2")
         .merge(d23[["d_date_sk"]].rename(columns={"d_date_sk": "d3"}),
                left_on="cs_sold_date_sk", right_on="d3")
         .merge(st, left_on="ss_store_sk", right_on="s_store_sk")
         .merge(i, left_on="ss_item_sk", right_on="i_item_sk"))
    g = (m.groupby(["i_item_id", "s_store_id", "s_store_name"])
         .agg(p1=("ss_net_profit", "sum"), p2=("sr_net_loss", "sum"),
              p3=("cs_net_profit", "sum"))
         .reset_index()
         .sort_values(["i_item_id", "s_store_id", "s_store_name"])
         .head(100))
    return [(r.i_item_id, r.s_store_id, r.s_store_name, r.p1, r.p2, r.p3)
            for r in g.itertuples()]


# ---------------------------------------------------------------------------------
# q26 — catalog twin of q7
# ---------------------------------------------------------------------------------

def run_q26(dfs):
    f = _F()
    cd = dfs["customer_demographics"].filter(
        (f.col("cd_gender") == "M") & (f.col("cd_marital_status") == "S")
        & (f.col("cd_education_status") == "College"))
    promo = dfs["promotion"].filter(
        (f.col("p_channel_email") == "N")
        | (f.col("p_channel_event") == "N"))
    q = (dfs["catalog_sales"]
         .join(cd, on=[("cs_bill_cdemo_sk", "cd_demo_sk")])
         .join(dfs["date_dim"].filter(f.col("d_year") == 2000),
               on=[("cs_sold_date_sk", "d_date_sk")])
         .join(dfs["item"], on=[("cs_item_sk", "i_item_sk")])
         .join(promo, on=[("cs_promo_sk", "p_promo_sk")])
         .group_by("i_item_id")
         .agg(f.avg(f.col("cs_quantity")).alias("agg1"),
              f.avg(f.col("cs_list_price")).alias("agg2"),
              f.avg(f.col("cs_coupon_amt")).alias("agg3"),
              f.avg(f.col("cs_sales_price")).alias("agg4"))
         .sort("i_item_id").limit(100))
    return q.collect()


def pandas_q26(pds):
    cs, cd, d, i, p = (pds[k] for k in
                       ["catalog_sales", "customer_demographics",
                        "date_dim", "item", "promotion"])
    cdf = cd[(cd.cd_gender == "M") & (cd.cd_marital_status == "S")
             & (cd.cd_education_status == "College")]
    pf = p[(p.p_channel_email == "N") | (p.p_channel_event == "N")]
    m = (cs.merge(cdf, left_on="cs_bill_cdemo_sk", right_on="cd_demo_sk")
         .merge(d[d.d_year == 2000], left_on="cs_sold_date_sk",
                right_on="d_date_sk")
         .merge(i, left_on="cs_item_sk", right_on="i_item_sk")
         .merge(pf, left_on="cs_promo_sk", right_on="p_promo_sk"))
    g = (m.groupby("i_item_id")
         .agg(a1=("cs_quantity", "mean"), a2=("cs_list_price", "mean"),
              a3=("cs_coupon_amt", "mean"), a4=("cs_sales_price", "mean"))
         .reset_index().sort_values("i_item_id").head(100))
    return [(r.i_item_id, r.a1, r.a2, r.a3, r.a4) for r in g.itertuples()]


# ---------------------------------------------------------------------------------
# q34 / q73 — ticket-size buckets per customer
# ---------------------------------------------------------------------------------

def _ticket_counts_runner(dfs, counties, pot_list, lo, hi, dom_cond):
    f = _F()
    hd = dfs["household_demographics"].filter(
        f.col("hd_buy_potential").isin(pot_list)
        & (f.col("hd_vehicle_count") > 0)
        & ((f.col("hd_dep_count") * 1.0
            / f.col("hd_vehicle_count")) > 1.2))
    q = (dfs["store_sales"]
         .join(dfs["date_dim"].filter(
             dom_cond(f) & f.col("d_year").isin([1999, 2000, 2001])),
             on=[("ss_sold_date_sk", "d_date_sk")])
         .join(dfs["store"].filter(f.col("s_county").isin(counties)),
               on=[("ss_store_sk", "s_store_sk")])
         .join(hd, on=[("ss_hdemo_sk", "hd_demo_sk")])
         .group_by("ss_ticket_number", "ss_customer_sk")
         .agg(f.count_star().alias("cnt")))
    q = (q.filter(f.col("cnt").between(lo, hi))
         .join(dfs["customer"], on=[("ss_customer_sk", "c_customer_sk")])
         .select("c_last_name", "c_first_name", "c_salutation"
                 if "c_salutation" in dfs["customer"].columns
                 else "c_preferred_cust_flag", "ss_ticket_number", "cnt")
         .sort("c_last_name", "c_first_name", "ss_ticket_number")
         .limit(200))
    return q.collect()


def _ticket_counts_oracle(pds, counties, pot_list, lo, hi, dom_mask):
    ss, d, st, hd, c = (pds[k] for k in
                        ["store_sales", "date_dim", "store",
                         "household_demographics", "customer"])
    hdf = hd[hd.hd_buy_potential.isin(pot_list) & (hd.hd_vehicle_count > 0)
             & ((hd.hd_dep_count * 1.0 / hd.hd_vehicle_count) > 1.2)]
    df = d[dom_mask(d) & d.d_year.isin([1999, 2000, 2001])]
    m = (ss.merge(df, left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(st[st.s_county.isin(counties)], left_on="ss_store_sk",
                right_on="s_store_sk")
         .merge(hdf, left_on="ss_hdemo_sk", right_on="hd_demo_sk"))
    g = (m.groupby(["ss_ticket_number", "ss_customer_sk"])
         .size().reset_index(name="cnt"))
    g = g[g.cnt.between(lo, hi)]
    g = g.merge(c, left_on="ss_customer_sk", right_on="c_customer_sk")
    g = (g[["c_last_name", "c_first_name", "c_preferred_cust_flag",
            "ss_ticket_number", "cnt"]]
         .sort_values(["c_last_name", "c_first_name", "ss_ticket_number"])
         .head(200))
    return [tuple(r) for r in g.itertuples(index=False)]


_Q34_COUNTIES = ["Williamson County", "Walker County", "Daviess County",
                 "Barrow County"]


def run_q34(dfs):
    return _ticket_counts_runner(
        dfs, _Q34_COUNTIES, [">10000", "Unknown"], 15, 20,
        lambda f: (f.col("d_dom").between(1, 3)
                   | f.col("d_dom").between(25, 28)))


def pandas_q34(pds):
    return _ticket_counts_oracle(
        pds, _Q34_COUNTIES, [">10000", "Unknown"], 15, 20,
        lambda d: (d.d_dom.between(1, 3) | d.d_dom.between(25, 28)))


def run_q73(dfs):
    return _ticket_counts_runner(
        dfs, _Q34_COUNTIES, [">10000", "5001-10000"], 1, 5,
        lambda f: f.col("d_dom").between(1, 2))


def pandas_q73(pds):
    return _ticket_counts_oracle(
        pds, _Q34_COUNTIES, [">10000", "5001-10000"], 1, 5,
        lambda d: d.d_dom.between(1, 2))


# ---------------------------------------------------------------------------------
# q46 / q68 / q79 — per-ticket city sums joined back to customers
# ---------------------------------------------------------------------------------

def _city_sums_runner(dfs, hd_cond, date_cond, store_filter, sums,
                      out_extra):
    f = _F()
    q = (dfs["store_sales"]
         .join(dfs["date_dim"].filter(date_cond(f)),
               on=[("ss_sold_date_sk", "d_date_sk")])
         .join(store_filter(f, dfs["store"]),
               on=[("ss_store_sk", "s_store_sk")])
         .join(dfs["household_demographics"].filter(hd_cond(f)),
               on=[("ss_hdemo_sk", "hd_demo_sk")])
         .join(dfs["customer_address"],
               on=[("ss_addr_sk", "ca_address_sk")])
         .group_by("ss_ticket_number", "ss_customer_sk",
                   f.col("ca_city").alias("bought_city"))
         .agg(*[f.sum(f.col(c)).alias(a) for c, a in sums]))
    cur = (dfs["customer"]
           .join(dfs["customer_address"],
                 on=[("c_current_addr_sk", "ca_address_sk")]))
    q = (q.join(cur, on=[("ss_customer_sk", "c_customer_sk")])
         .filter(f.col("ca_city") != f.col("bought_city"))
         .select("c_last_name", "c_first_name", "ca_city", "bought_city",
                 "ss_ticket_number", *[a for _, a in sums])
         .sort("c_last_name", "c_first_name", "ca_city", "bought_city",
               "ss_ticket_number")
         .limit(100))
    return q.collect()


def _city_sums_oracle(pds, hd_mask, date_mask, store_mask, sums):
    ss, d, st, hd, ca, c = (pds[k] for k in
                            ["store_sales", "date_dim", "store",
                             "household_demographics", "customer_address",
                             "customer"])
    m = (ss.merge(d[date_mask(d)], left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
         .merge(st[store_mask(st)], left_on="ss_store_sk",
                right_on="s_store_sk")
         .merge(hd[hd_mask(hd)], left_on="ss_hdemo_sk",
                right_on="hd_demo_sk")
         .merge(ca, left_on="ss_addr_sk", right_on="ca_address_sk"))
    g = (m.groupby(["ss_ticket_number", "ss_customer_sk", "ca_city"])
         .agg(**{a: (col, "sum") for col, a in sums}).reset_index()
         .rename(columns={"ca_city": "bought_city"}))
    cur = c.merge(ca, left_on="c_current_addr_sk",
                  right_on="ca_address_sk")
    g = g.merge(cur, left_on="ss_customer_sk", right_on="c_customer_sk")
    g = g[g.ca_city != g.bought_city]
    cols = ["c_last_name", "c_first_name", "ca_city", "bought_city",
            "ss_ticket_number"] + [a for _, a in sums]
    g = (g[cols].sort_values(cols[:5]).head(100))
    return [tuple(r) for r in g.itertuples(index=False)]


_Q46_CITIES = ["Fairview", "Midway", "Cedar Grove", "Five Points",
               "Oak Grove"]


def run_q46(dfs):
    return _city_sums_runner(
        dfs,
        lambda f: ((f.col("hd_dep_count") == 4)
                   | (f.col("hd_vehicle_count") == 3)),
        lambda f: (f.col("d_dow").isin([6, 0])
                   & f.col("d_year").isin([1999, 2000, 2001])),
        lambda f, store: store.filter(f.col("s_city").isin(_Q46_CITIES)),
        [("ss_coupon_amt", "amt"), ("ss_net_profit", "profit")],
        None)


def pandas_q46(pds):
    return _city_sums_oracle(
        pds,
        lambda hd: (hd.hd_dep_count == 4) | (hd.hd_vehicle_count == 3),
        lambda d: d.d_dow.isin([6, 0]) & d.d_year.isin([1999, 2000, 2001]),
        lambda st: st.s_city.isin(_Q46_CITIES),
        [("ss_coupon_amt", "amt"), ("ss_net_profit", "profit")])


def run_q68(dfs):
    return _city_sums_runner(
        dfs,
        lambda f: ((f.col("hd_dep_count") == 4)
                   | (f.col("hd_vehicle_count") == 3)),
        lambda f: (f.col("d_dom").between(1, 2)
                   & f.col("d_year").isin([1998, 1999, 2000])),
        lambda f, store: store.filter(
            f.col("s_city").isin(["Midway", "Fairview"])),
        [("ss_ext_sales_price", "extended_price"),
         ("ss_ext_list_price", "list_price"),
         ("ss_ext_wholesale_cost", "extended_tax")],
        None)


def pandas_q68(pds):
    return _city_sums_oracle(
        pds,
        lambda hd: (hd.hd_dep_count == 4) | (hd.hd_vehicle_count == 3),
        lambda d: d.d_dom.between(1, 2) & d.d_year.isin([1998, 1999,
                                                         2000]),
        lambda st: st.s_city.isin(["Midway", "Fairview"]),
        [("ss_ext_sales_price", "extended_price"),
         ("ss_ext_list_price", "list_price"),
         ("ss_ext_wholesale_cost", "extended_tax")])


def run_q79(dfs):
    return _city_sums_runner(
        dfs,
        lambda f: ((f.col("hd_dep_count") == 6)
                   | (f.col("hd_vehicle_count") > 2)),
        lambda f: ((f.col("d_dow") == 1)
                   & f.col("d_year").isin([1998, 1999, 2000])),
        lambda f, store: store.filter(
            f.col("s_number_employees").between(200, 295)),
        [("ss_coupon_amt", "amt"), ("ss_net_profit", "profit")],
        None)


def pandas_q79(pds):
    return _city_sums_oracle(
        pds,
        lambda hd: (hd.hd_dep_count == 6) | (hd.hd_vehicle_count > 2),
        lambda d: (d.d_dow == 1) & d.d_year.isin([1998, 1999, 2000]),
        lambda st: st.s_number_employees.between(200, 295),
        [("ss_coupon_amt", "amt"), ("ss_net_profit", "profit")])


# ---------------------------------------------------------------------------------
# q48 — sum(quantity) under OR'd demographic/address conditions
# ---------------------------------------------------------------------------------

def run_q48(dfs):
    f = _F()
    cd_ok = (
        ((f.col("cd_marital_status") == "M")
         & (f.col("cd_education_status") == "4 yr Degree")
         & f.col("ss_sales_price").between(100.0, 150.0))
        | ((f.col("cd_marital_status") == "D")
           & (f.col("cd_education_status") == "2 yr Degree")
           & f.col("ss_sales_price").between(50.0, 100.0))
        | ((f.col("cd_marital_status") == "S")
           & (f.col("cd_education_status") == "College")
           & f.col("ss_sales_price").between(150.0, 200.0)))
    ca_ok = (
        (f.col("ca_state").isin(["CO", "OH", "TX"])
         & f.col("ss_net_profit").between(0.0, 2000.0))
        | (f.col("ca_state").isin(["OR", "MN", "KY"])
           & f.col("ss_net_profit").between(150.0, 3000.0))
        | (f.col("ca_state").isin(["VA", "CA", "MS"])
           & f.col("ss_net_profit").between(50.0, 25000.0)))
    q = (dfs["store_sales"]
         .join(dfs["store"], on=[("ss_store_sk", "s_store_sk")])
         .join(dfs["date_dim"].filter(f.col("d_year") == 2000),
               on=[("ss_sold_date_sk", "d_date_sk")])
         .join(dfs["customer_demographics"],
               on=[("ss_cdemo_sk", "cd_demo_sk")])
         .join(dfs["customer_address"].filter(
             f.col("ca_country") == "United States"),
             on=[("ss_addr_sk", "ca_address_sk")])
         .filter(cd_ok & ca_ok)
         .agg(f.sum(f.col("ss_quantity")).alias("q")))
    return q.collect()


def pandas_q48(pds):
    ss, st, d, cd, ca = (pds[k] for k in
                         ["store_sales", "store", "date_dim",
                          "customer_demographics", "customer_address"])
    m = (ss.merge(st, left_on="ss_store_sk", right_on="s_store_sk")
         .merge(d[d.d_year == 2000], left_on="ss_sold_date_sk",
                right_on="d_date_sk")
         .merge(cd, left_on="ss_cdemo_sk", right_on="cd_demo_sk")
         .merge(ca[ca.ca_country == "United States"],
                left_on="ss_addr_sk", right_on="ca_address_sk"))
    cd_ok = (((m.cd_marital_status == "M")
              & (m.cd_education_status == "4 yr Degree")
              & m.ss_sales_price.between(100.0, 150.0))
             | ((m.cd_marital_status == "D")
                & (m.cd_education_status == "2 yr Degree")
                & m.ss_sales_price.between(50.0, 100.0))
             | ((m.cd_marital_status == "S")
                & (m.cd_education_status == "College")
                & m.ss_sales_price.between(150.0, 200.0)))
    ca_ok = ((m.ca_state.isin(["CO", "OH", "TX"])
              & m.ss_net_profit.between(0.0, 2000.0))
             | (m.ca_state.isin(["OR", "MN", "KY"])
                & m.ss_net_profit.between(150.0, 3000.0))
             | (m.ca_state.isin(["VA", "CA", "MS"])
                & m.ss_net_profit.between(50.0, 25000.0)))
    m = m[cd_ok & ca_ok]
    return [(int(m.ss_quantity.sum()) if len(m) else None,)]


# ---------------------------------------------------------------------------------
# q65 — under-performing (store, item) pairs vs 10% of store average
# ---------------------------------------------------------------------------------

def run_q65(dfs):
    f = _F()
    dd = dfs["date_dim"].filter(f.col("d_month_seq").between(1176, 1187))
    sc = (dfs["store_sales"]
          .join(dd, on=[("ss_sold_date_sk", "d_date_sk")])
          .group_by("ss_store_sk", "ss_item_sk")
          .agg(f.sum(f.col("ss_sales_price")).alias("revenue")))
    sb = (sc.group_by(f.col("ss_store_sk").alias("sb_store_sk"))
          .agg(f.avg(f.col("revenue")).alias("ave")))
    q = (sc.join(sb, on=[("ss_store_sk", "sb_store_sk")])
         .filter(f.col("revenue") <= f.col("ave") * 0.1)
         .join(dfs["store"], on=[("ss_store_sk", "s_store_sk")])
         .join(dfs["item"], on=[("ss_item_sk", "i_item_sk")])
         .select("s_store_name", "i_item_id", "revenue")
         .sort("s_store_name", "i_item_id")
         .limit(100))
    return q.collect()


def pandas_q65(pds):
    ss, d, st, i = (pds[k] for k in
                    ["store_sales", "date_dim", "store", "item"])
    dd = d[d.d_month_seq.between(1176, 1187)]
    m = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    sc = (m.groupby(["ss_store_sk", "ss_item_sk"])["ss_sales_price"]
          .sum().reset_index(name="revenue"))
    sb = sc.groupby("ss_store_sk")["revenue"].mean().rename("ave")
    sc = sc.join(sb, on="ss_store_sk")
    sc = sc[sc.revenue <= 0.1 * sc.ave]
    sc = (sc.merge(st, left_on="ss_store_sk", right_on="s_store_sk")
          .merge(i, left_on="ss_item_sk", right_on="i_item_sk"))
    g = (sc[["s_store_name", "i_item_id", "revenue"]]
         .sort_values(["s_store_name", "i_item_id"]).head(100))
    return [tuple(r) for r in g.itertuples(index=False)]


# ---------------------------------------------------------------------------------
# q94 / q95 — web order fulfillment (multi-warehouse / returned)
# ---------------------------------------------------------------------------------

def _web_ship_base(dfs, f):
    import datetime
    lo, hi = datetime.date(1999, 2, 1), datetime.date(1999, 4, 2)
    return (dfs["web_sales"]
            .join(dfs["date_dim"].filter(
                (f.col("d_date") >= lo) & (f.col("d_date") <= hi)),
                on=[("ws_ship_date_sk", "d_date_sk")])
            .join(dfs["customer_address"].filter(
                f.col("ca_state") == "IL"),
                on=[("ws_ship_addr_sk", "ca_address_sk")])
            .join(dfs["web_site"].filter(
                f.col("web_company_name") == "pri"),
                on=[("ws_web_site_sk", "web_site_sk")]))


def _pd_web_ship_base(pds):
    import datetime
    lo, hi = datetime.date(1999, 2, 1), datetime.date(1999, 4, 2)
    ws, d, ca, web = (pds[k] for k in
                      ["web_sales", "date_dim", "customer_address",
                       "web_site"])
    return (ws.merge(d[(d.d_date >= lo) & (d.d_date <= hi)],
                     left_on="ws_ship_date_sk", right_on="d_date_sk")
            .merge(ca[ca.ca_state == "IL"], left_on="ws_ship_addr_sk",
                   right_on="ca_address_sk")
            .merge(web[web.web_company_name == "pri"],
                   left_on="ws_web_site_sk", right_on="web_site_sk"))


def _multi_wh_orders(dfs, f):
    """Orders shipping from more than one warehouse (ws1/ws2 self-join
    shape of the official q94/q95 EXISTS).  Cached: both consumers
    (the EXISTS semi and the wr semi) reuse one materialization, the
    WITH-clause semantics of the official query."""
    per = (dfs["web_sales"]
           .group_by(f.col("ws_order_number").alias("mw_order"))
           .agg(f.min(f.col("ws_warehouse_sk")).alias("wh_min"),
                f.max(f.col("ws_warehouse_sk")).alias("wh_max")))
    return per.filter(f.col("wh_min") != f.col("wh_max")) \
        .select("mw_order").cache()


def run_q94(dfs):
    f = _F()
    base = _web_ship_base(dfs, f)
    # EXISTS multi-warehouse, NOT EXISTS returned
    wr = dfs["web_returns"].select(
        f.col("wr_order_number").alias("wr_on")).distinct()
    kept = (base
            .join(_multi_wh_orders(dfs, f),
                  on=[("ws_order_number", "mw_order")], how="semi")
            .join(wr, on=[("ws_order_number", "wr_on")], how="anti"))
    orders = kept.select("ws_order_number").distinct().count()
    sums = kept.agg(f.sum(f.col("ws_ext_ship_cost")).alias("s1"),
                    f.sum(f.col("ws_net_profit")).alias("s2")).collect()
    return [(orders, sums[0][0], sums[0][1])]


def pandas_q94(pds):
    m = _pd_web_ship_base(pds)
    ws = pds["web_sales"]
    per = ws.groupby("ws_order_number")["ws_warehouse_sk"].nunique()
    multi = set(per[per > 1].index)
    returned = set(pds["web_returns"].wr_order_number.unique())
    kept = m[m.ws_order_number.isin(multi)
             & ~m.ws_order_number.isin(returned)]
    return [(kept.ws_order_number.nunique(),
             kept.ws_ext_ship_cost.sum() if len(kept) else None,
             kept.ws_net_profit.sum() if len(kept) else None)]


def run_q95(dfs):
    f = _F()
    base = _web_ship_base(dfs, f)
    multi = _multi_wh_orders(dfs, f)
    wr = (dfs["web_returns"]
          .join(multi.select(f.col("mw_order").alias("mw2")),
                on=[("wr_order_number", "mw2")], how="semi")
          .select(f.col("wr_order_number").alias("wr_on")).distinct())
    kept = (base
            .join(multi, on=[("ws_order_number", "mw_order")], how="semi")
            .join(wr, on=[("ws_order_number", "wr_on")], how="semi"))
    orders = kept.select("ws_order_number").distinct().count()
    sums = kept.agg(f.sum(f.col("ws_ext_ship_cost")).alias("s1"),
                    f.sum(f.col("ws_net_profit")).alias("s2")).collect()
    return [(orders, sums[0][0], sums[0][1])]


def pandas_q95(pds):
    m = _pd_web_ship_base(pds)
    ws = pds["web_sales"]
    per = ws.groupby("ws_order_number")["ws_warehouse_sk"].nunique()
    multi = set(per[per > 1].index)
    wr = pds["web_returns"]
    ret_multi = set(wr[wr.wr_order_number.isin(multi)]
                    .wr_order_number.unique())
    kept = m[m.ws_order_number.isin(multi)
             & m.ws_order_number.isin(ret_multi)]
    return [(kept.ws_order_number.nunique(),
             kept.ws_ext_ship_cost.sum() if len(kept) else None,
             kept.ws_net_profit.sum() if len(kept) else None)]


# ---------------------------------------------------------------------------------
# q64 — cross-channel item repurchase, year-over-year self-join
# ---------------------------------------------------------------------------------

_Q64_COLORS = ["papaya", "firebrick", "azure", "salmon", "plum",
               "chartreuse"]


def _q64_cs_ui(dfs, f):
    """cs_ui CTE: catalog items whose sales beat 2x their refunds —
    computed ONCE and cached; both year slices of cross_sales reuse it
    (the official query's WITH clause)."""
    cs_r = (dfs["catalog_sales"]
            .join(dfs["catalog_returns"],
                  on=[("cs_item_sk", "cr_item_sk"),
                      ("cs_order_number", "cr_order_number")])
            .group_by(f.col("cs_item_sk").alias("ui_item_sk"))
            .agg(f.sum(f.col("cs_ext_list_price")).alias("sale"),
                 f.sum(f.col("cr_refunded_cash")
                       + f.col("cr_reversed_charge")
                       + f.col("cr_store_credit")).alias("refund")))
    return cs_r.filter(f.col("sale") > f.col("refund") * 2.0) \
        .select("ui_item_sk").cache()


def _q64_cross_sales(dfs, f, year, cs_ui):
    item = dfs["item"].filter(
        f.col("i_color").isin(_Q64_COLORS)
        & f.col("i_current_price").between(35.0, 45.0))
    d1 = (dfs["date_dim"].filter(f.col("d_year") == year)
          .select(f.col("d_date_sk").alias("d1_sk"),
                  f.col("d_year").alias("syear")))
    q = (dfs["store_sales"]
         .join(dfs["store_returns"],
               on=[("ss_item_sk", "sr_item_sk"),
                   ("ss_ticket_number", "sr_ticket_number")])
         .join(cs_ui, on=[("ss_item_sk", "ui_item_sk")], how="semi")
         .join(d1, on=[("ss_sold_date_sk", "d1_sk")])
         .join(dfs["store"], on=[("ss_store_sk", "s_store_sk")])
         .join(dfs["customer"], on=[("ss_customer_sk", "c_customer_sk")])
         .join(dfs["customer_address"],
               on=[("c_current_addr_sk", "ca_address_sk")])
         .join(item, on=[("ss_item_sk", "i_item_sk")])
         .group_by("i_product_name", "ss_item_sk", "s_store_name",
                   "s_zip", "syear")
         .agg(f.count_star().alias("cnt"),
              f.sum(f.col("ss_wholesale_cost")).alias("s1"),
              f.sum(f.col("ss_list_price")).alias("s2"),
              f.sum(f.col("ss_coupon_amt")).alias("s3")))
    return q


def run_q64(dfs):
    f = _F()
    cs_ui = _q64_cs_ui(dfs, f)
    cs1 = _q64_cross_sales(dfs, f, 1999, cs_ui)
    cs2 = _q64_cross_sales(dfs, f, 2000, cs_ui)
    cs2 = cs2.select(
        f.col("ss_item_sk").alias("item2"),
        f.col("s_store_name").alias("store2"),
        f.col("s_zip").alias("zip2"),
        f.col("syear").alias("syear2"), f.col("cnt").alias("cnt2"),
        f.col("s1").alias("s1_2"), f.col("s2").alias("s2_2"),
        f.col("s3").alias("s3_2"))
    q = (cs1.join(cs2, on=[("ss_item_sk", "item2"),
                           ("s_store_name", "store2"),
                           ("s_zip", "zip2")])
         .filter(f.col("cnt2") <= f.col("cnt"))
         .select("i_product_name", "s_store_name", "s_zip", "syear",
                 "cnt", "s1", "s2", "s3", "syear2", "cnt2", "s1_2",
                 "s2_2", "s3_2")
         .sort("i_product_name", "s_store_name", "s_zip", "cnt2",
               "syear", "s1"))
    return q.collect()


def _pd_q64_cross(pds, year):
    cs, cr = pds["catalog_sales"], pds["catalog_returns"]
    m = cs.merge(cr, left_on=["cs_item_sk", "cs_order_number"],
                 right_on=["cr_item_sk", "cr_order_number"])
    m["refund"] = (m.cr_refunded_cash + m.cr_reversed_charge
                   + m.cr_store_credit)
    g = m.groupby("cs_item_sk").agg(sale=("cs_ext_list_price", "sum"),
                                    refund=("refund", "sum"))
    ui = set(g[g.sale > 2.0 * g.refund].index)
    ss, sr, d, st, c, ca, i = (pds[k] for k in
                               ["store_sales", "store_returns",
                                "date_dim", "store", "customer",
                                "customer_address", "item"])
    itf = i[i.i_color.isin(_Q64_COLORS)
            & i.i_current_price.between(35.0, 45.0)]
    m = (ss.merge(sr, left_on=["ss_item_sk", "ss_ticket_number"],
                  right_on=["sr_item_sk", "sr_ticket_number"])
         .merge(d[d.d_year == year][["d_date_sk", "d_year"]],
                left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(st, left_on="ss_store_sk", right_on="s_store_sk")
         .merge(c, left_on="ss_customer_sk", right_on="c_customer_sk")
         .merge(ca, left_on="c_current_addr_sk",
                right_on="ca_address_sk")
         .merge(itf, left_on="ss_item_sk", right_on="i_item_sk"))
    m = m[m.ss_item_sk.isin(ui)]
    g = (m.groupby(["i_product_name", "ss_item_sk", "s_store_name",
                    "s_zip", "d_year"])
         .agg(cnt=("ss_item_sk", "size"),
              s1=("ss_wholesale_cost", "sum"),
              s2=("ss_list_price", "sum"), s3=("ss_coupon_amt", "sum"))
         .reset_index().rename(columns={"d_year": "syear"}))
    return g


def pandas_q64(pds):
    cs1 = _pd_q64_cross(pds, 1999)
    cs2 = _pd_q64_cross(pds, 2000)
    m = cs1.merge(cs2, on=["ss_item_sk", "s_store_name", "s_zip"],
                  suffixes=("", "_2"))
    m = m[m.cnt_2 <= m.cnt]
    m = m.sort_values(["i_product_name", "s_store_name", "s_zip",
                       "cnt_2", "syear", "s1"])
    return [(r.i_product_name, r.s_store_name, r.s_zip, r.syear, r.cnt,
             r.s1, r.s2, r.s3, r.syear_2, r.cnt_2, r.s1_2, r.s2_2,
             r.s3_2) for r in m.itertuples()]


QUERIES2 = {
    "ds_q12": (run_q12, pandas_q12),
    "ds_q13": (run_q13, pandas_q13),
    "ds_q19": (run_q19, pandas_q19),
    "ds_q20": (run_q20, pandas_q20),
    "ds_q25": (run_q25, pandas_q25),
    "ds_q26": (run_q26, pandas_q26),
    "ds_q34": (run_q34, pandas_q34),
    "ds_q46": (run_q46, pandas_q46),
    "ds_q48": (run_q48, pandas_q48),
    "ds_q64": (run_q64, pandas_q64),
    "ds_q65": (run_q65, pandas_q65),
    "ds_q68": (run_q68, pandas_q68),
    "ds_q73": (run_q73, pandas_q73),
    "ds_q79": (run_q79, pandas_q79),
    "ds_q94": (run_q94, pandas_q94),
    "ds_q95": (run_q95, pandas_q95),
    "ds_q98": (run_q98, pandas_q98),
}

TABLES2: Dict[str, List[str]] = {
    "ds_q12": ["web_sales", "item", "date_dim"],
    "ds_q13": ["store_sales", "store", "date_dim",
               "customer_demographics", "customer_address"],
    "ds_q19": ["store_sales", "date_dim", "item", "customer",
               "customer_address", "store"],
    "ds_q20": ["catalog_sales", "item", "date_dim"],
    "ds_q25": ["store_sales", "store_returns", "catalog_sales",
               "date_dim", "store", "item"],
    "ds_q26": ["catalog_sales", "customer_demographics", "date_dim",
               "item", "promotion"],
    "ds_q34": ["store_sales", "date_dim", "store",
               "household_demographics", "customer"],
    "ds_q46": ["store_sales", "date_dim", "store",
               "household_demographics", "customer_address", "customer"],
    "ds_q48": ["store_sales", "store", "date_dim",
               "customer_demographics", "customer_address"],
    "ds_q64": ["catalog_sales", "catalog_returns", "store_sales",
               "store_returns", "date_dim", "store", "customer",
               "customer_address", "item"],
    "ds_q65": ["store_sales", "date_dim", "store", "item"],
    "ds_q68": ["store_sales", "date_dim", "store",
               "household_demographics", "customer_address", "customer"],
    "ds_q73": ["store_sales", "date_dim", "store",
               "household_demographics", "customer"],
    "ds_q79": ["store_sales", "date_dim", "store",
               "household_demographics", "customer_address", "customer"],
    "ds_q94": ["web_sales", "web_returns", "web_site",
               "customer_address", "date_dim"],
    "ds_q95": ["web_sales", "web_returns", "web_site",
               "customer_address", "date_dim"],
    "ds_q98": ["store_sales", "item", "date_dim"],
}
