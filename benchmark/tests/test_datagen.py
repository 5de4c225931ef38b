"""The generated tables against the specifications' table definitions: every
column, the row counts, and the rules that tie one column to another."""

import numpy as np
import pyarrow.parquet as pq
import pytest

import run as bench_run
from harness import sources

SF = 0.01
# columns per table: TPC-H v3 clause 1.4, TPC-DS v3 clause 2.3/2.4
TPCH_COLUMNS = {"region": 3, "nation": 4, "customer": 8, "supplier": 7,
                "part": 9, "partsupp": 5, "orders": 9, "lineitem": 16}
TPCDS_COLUMNS = {"store_sales": 23, "date_dim": 28, "item": 22}


def _tables(suite, tmp_path_factory, seed):
    gen = sources.load_module([bench_run.HERE], "datagen", suite + ".py")
    paths = gen.gen(SF, seed, str(tmp_path_factory.mktemp(suite)))
    return gen, {t: pq.read_table(p).to_pandas() for t, p in paths.items()}


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    return _tables("tpch", tmp_path_factory, 2**31 + 21)


@pytest.fixture(scope="module")
def tpcds(tmp_path_factory):
    return _tables("tpcds", tmp_path_factory, 2**31 + 22)


def days(col):
    """A date column (python dates, as parquet's date32 reads) as days."""
    return np.array(col.to_numpy(), dtype="datetime64[D]").astype(int)


def test_tpch_has_every_column_and_the_specifications_rows(tpch):
    gen, t = tpch
    assert {k: len(v.columns) for k, v in t.items()} == TPCH_COLUMNS
    assert {k: len(v) for k, v in t.items()} == gen.rows(SF)
    assert gen.rows(1.0)["lineitem"] == 6_001_215
    assert gen.rows(1.0)["orders"] == 1_500_000


def test_orderkeys_are_sparse_and_a_third_of_customers_have_no_order(tpch):
    o, c = tpch[1]["orders"], tpch[1]["customer"]
    i = np.arange(len(o))
    assert (o.o_orderkey.to_numpy() == (i // 8) * 32 + i % 8 + 1).all()
    assert (o.o_custkey % 3 != 0).all()
    assert o.o_custkey.between(1, len(c)).all()
    with_orders = o.o_custkey.nunique() / len(c)
    assert 0.6 < with_orders <= 2 / 3


def test_a_lines_dates_hang_on_its_orders(tpch):
    o, li = tpch[1]["orders"], tpch[1]["lineitem"]
    m = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    assert len(m) == len(li)                       # every line has its order
    od, ship = days(m.o_orderdate), days(m.l_shipdate)
    assert ((ship - od >= 1) & (ship - od <= 121)).all()
    commit = days(m.l_commitdate) - od
    assert ((commit >= 30) & (commit <= 90)).all()
    receipt = days(m.l_receiptdate) - ship
    assert ((receipt >= 1) & (receipt <= 30)).all()
    first = np.datetime64("1992-01-01").astype(int)
    assert od.min() >= first and od.max() <= first + 2405
    # 1..7 lines an order, numbered from 1, the key ascending
    n = li.groupby("l_orderkey").l_linenumber.agg(["count", "max", "min"])
    assert n["count"].between(1, 7).all()
    assert (n["count"] == n["max"]).all() and (n["min"] == 1).all()
    assert li.l_orderkey.is_monotonic_increasing


def test_flags_status_and_prices_follow_their_rules(tpch):
    gen, t = tpch
    o, li = t["orders"], t["lineitem"]
    today = np.datetime64("1995-06-17").astype(int)
    late = days(li.l_receiptdate) > today
    assert (li.l_returnflag[late] == "N").all()
    assert li.l_returnflag[~late].isin(["R", "A"]).all()
    assert ((li.l_linestatus == "O") == (days(li.l_shipdate) > today)).all()
    retail = gen.retail_price(li.l_partkey.to_numpy())
    assert np.allclose(li.l_extendedprice, li.l_quantity * retail, atol=0.006)
    assert li.l_discount.between(0, 0.10).all() and \
        li.l_tax.between(0, 0.08).all()
    g = li.assign(
        charge=li.l_extendedprice * (1 + li.l_tax) * (1 - li.l_discount),
        open_=li.l_linestatus == "O").groupby("l_orderkey").agg(
        total=("charge", "sum"), n_open=("open_", "sum"),
        n=("open_", "size"))
    m = o.merge(g, left_on="o_orderkey", right_index=True)
    assert np.allclose(m.o_totalprice, m.total, atol=0.006)
    want = np.where(m.n_open == 0, "F", np.where(m.n_open == m.n, "O", "P"))
    assert (m.o_orderstatus == want).all()
    # the supplier is one of the part's four
    ps = t["partsupp"]
    pairs = set(zip(ps.ps_partkey, ps.ps_suppkey))
    assert len(pairs) == len(ps)
    assert all(p in pairs for p in zip(li.l_partkey[:5000],
                                       li.l_suppkey[:5000]))


@pytest.mark.parametrize("table,column,lo,hi", [
    ("lineitem", "l_comment", 10, 43), ("orders", "o_comment", 19, 78),
    ("customer", "c_comment", 29, 116), ("customer", "c_address", 10, 40),
    ("partsupp", "ps_comment", 49, 198), ("part", "p_comment", 5, 22),
    ("supplier", "s_comment", 25, 100)])
def test_text_columns_keep_their_range_of_lengths(tpch, table, column, lo,
                                                  hi):
    n = tpch[1][table][column].str.len()
    assert n.min() >= lo and n.max() <= hi and n.nunique() > 5


def test_tpcds_has_every_column_and_the_specifications_rows(tpcds):
    gen, t = tpcds
    assert {k: len(v.columns) for k, v in t.items()} == TPCDS_COLUMNS
    assert len(t["date_dim"]) == 73_049             # at every scale factor
    assert gen.rows(1.0) == {"date_dim": 73_049, "item": 18_000,
                             "store_sales": 2_880_404}


def test_date_dim_is_the_calendar_with_julian_keys(tpcds):
    import datetime
    d = tpcds[1]["date_dim"]
    assert d.d_date.iloc[0] == datetime.date(1900, 1, 2)
    assert d.d_date.iloc[-1] == datetime.date(2100, 1, 1)
    assert (d.d_date_sk.diff().dropna() == 1).all()
    row = d[d.d_date == datetime.date(1998, 1, 1)].iloc[0]
    assert (row.d_date_sk, row.d_year, row.d_moy, row.d_dom, row.d_qoy,
            row.d_month_seq, row.d_day_name) == (2450815, 1998, 1, 1, 1,
                                                 1176, "Thursday")
    for r in d.sample(200, random_state=1).itertuples():
        assert (r.d_year, r.d_moy, r.d_dom) == (
            r.d_date.year, r.d_date.month, r.d_date.day)
        assert r.d_dow == (r.d_date.weekday() + 1) % 7


def test_store_sales_keys_and_prices(tpcds):
    ss, item = tpcds[1]["store_sales"], tpcds[1]["item"]
    sold = ss.ss_sold_date_sk.dropna()
    assert sold.between(2450816, 2452642).all()
    assert 0.02 < ss.ss_sold_date_sk.isna().mean() < 0.06
    assert ss.ss_item_sk.between(1, len(item)).all()
    per_ticket = ss.groupby("ss_ticket_number").size()
    assert per_ticket.iloc[:-1].between(8, 16).all()
    assert (ss.groupby("ss_ticket_number").ss_store_sk.nunique() <= 1).all()
    assert np.allclose(ss.ss_ext_sales_price,
                       ss.ss_sales_price * ss.ss_quantity, atol=0.006)
    assert np.allclose(ss.ss_net_paid_inc_tax, ss.ss_net_paid + ss.ss_ext_tax,
                       atol=0.006)
    assert np.allclose(ss.ss_net_profit,
                       ss.ss_net_paid - ss.ss_ext_wholesale_cost, atol=0.006)
