"""What the four store_sales star queries share: the join of the fact table
with one month of ``date_dim`` and the items of one manager or manufacturer,
on the engine and in pandas.  Copied from
``spark_rapids_tpu/models/tpcds.py`` (``_brand_month_year`` and the twins'
merges) with the substitution parameters made arguments."""

FACT = ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"]
YEARS = [1998, 2002]   # the generator's date_dim runs 1998-01-01..2003-12-31
MONTHS = [11, 12]


def draw_year_month(rng):
    return {"year": int(rng.integers(YEARS[0], YEARS[1] + 1)),
            "month": int(rng.integers(MONTHS[0], MONTHS[1] + 1))}


def star(dfs, date_pred, item_pred):
    return (dfs["store_sales"]
            .join(dfs["date_dim"].filter(date_pred),
                  on=[("ss_sold_date_sk", "d_date_sk")])
            .join(dfs["item"].filter(item_pred),
                  on=[("ss_item_sk", "i_item_sk")]))


def star_pandas(pds, date_mask, item_mask):
    ss, d, i = pds["store_sales"], pds["date_dim"], pds["item"]
    return (ss.merge(d[date_mask(d)], left_on="ss_sold_date_sk",
                     right_on="d_date_sk")
            .merge(i[item_mask(i)], left_on="ss_item_sk",
                   right_on="i_item_sk"))
