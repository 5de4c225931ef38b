"""The cell PR 35 adds, as files and entries only:
``tpcds_sf1_weborders.exists_distinct`` (a configuration, a generator of four
tables, two queries, a traffic mix, four per-layer metrics read in every
cell)."""

import filecmp
import json
import os

import numpy as np
import pyarrow.parquet as pq
import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import sources

CELL = "tpcds_sf1_weborders.exists_distinct"
QUERIES = ("q94", "q95")
METRICS = {  # name: (QueryStats field, per, scale)
    "join_pct": ("join_exec_s", "window_s", 100),
    "join_pairs_mrows_per_query": ("join_pairs", "queries", 1e-6),
    "join_slot_mrows_per_query": ("join_out_slots", "queries", 1e-6),
    "semi_anti_joins_per_query": ("join_semi_anti", "queries", 1),
}
# TPC-DS v3, the tables' columns in the specification's order
WIDTHS = {"web_sales": 34, "web_returns": 24, "customer_address": 13,
          "web_site": 26, "date_dim": 28}
PER_ORDER = ["ws_sold_date_sk", "ws_sold_time_sk", "ws_ship_date_sk",
             "ws_bill_customer_sk", "ws_bill_cdemo_sk", "ws_bill_hdemo_sk",
             "ws_bill_addr_sk", "ws_ship_customer_sk", "ws_ship_cdemo_sk",
             "ws_ship_hdemo_sk", "ws_ship_addr_sk", "ws_web_page_sk",
             "ws_web_site_sk", "ws_ship_mode_sk"]
PER_LINE = ["ws_item_sk", "ws_warehouse_sk", "ws_promo_sk"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def gen():
    return sources.load_module([BENCH], "datagen", "tpcds_weborders.py")


@pytest.fixture(scope="module")
def tables(gen, tmp_path_factory):
    paths = gen.gen(0.02, 2**31 + 35, str(tmp_path_factory.mktemp("wo")))
    return {t: pq.read_table(p) for t, p in paths.items()}


def test_the_new_cell_is_an_entry_as_the_issue_names_it(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": "tpcds_sf1_weborders",
                           "traffic": "exists_distinct", "chips": 1}
    assert bench["workloads"][-1]["name"] == CELL
    traffic = sources.load_json([BENCH], "traffic", "exists_distinct.json")
    assert (traffic["mix"], traffic["pool"], traffic["clients"],
            traffic["loop"], traffic["suite"]) == (
                list(QUERIES), 4, 1, "closed", "tpcds")
    entry = bench["configs"][-1]
    assert entry["name"] == "tpcds_sf1_weborders"
    assert entry["reduced"] == ["scale_factor", "query_set", "tables"]
    # the four read in every cell: no ``workloads`` list
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(METRICS)
    for m in bench["per_layer"][-4:]:
        assert "workloads" not in m and m["layer"] == "operators"
        assert m["moves"] == "queries_per_s"


def test_the_configuration_is_the_stars_key_for_key():
    with open(os.path.join(BENCH, "configs", "tpcds_sf1.json")) as f:
        stars = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           "tpcds_sf1_weborders.json")) as f:
        orders = json.load(f)
    assert sorted(orders) == sorted(stars)
    differ = {k for k in stars if stars[k] != orders[k]}
    assert differ == {"name", "source", "datagen", "query_set", "tables",
                      "reduced", "assumed"}
    assert sorted(orders["reduced"]) == sorted(stars["reduced"])
    assert orders["query_set"] == list(QUERIES) and orders["confs"] == {}
    assert orders["tables"] == sorted(WIDTHS) and len(orders["source"]) <= 200
    assert orders["limits"] == {"answers_wrong": 0, "max_rel_err": 1e-10}


def test_row_counts_and_columns_are_the_specifications(gen, tables):
    assert gen.rows(1.0) == {
        "web_sales": 719_384, "web_returns": 71_763,
        "customer_address": 50_000, "web_site": 30, "date_dim": 73_049}
    small = gen.rows(0.02)
    assert small == {"web_sales": 14_387, "web_returns": 1_435,
                     "customer_address": 1_000, "web_site": 6,
                     "date_dim": 73_049}
    for t, width in WIDTHS.items():
        assert tables[t].num_rows == small[t], t
        assert tables[t].column_names == list(gen.SCHEMA[t]), t
        assert tables[t].num_columns == width, t
    assert sorted(gen.TABLES) == sorted(WIDTHS)
    with pytest.raises(ValueError, match="no table"):
        gen.gen(0.02, 1, "/nonexistent", ["store_sales"])


def test_an_order_is_8_to_16_lines_that_share_what_an_order_shares(tables):
    ws = tables["web_sales"].to_pandas()
    by = ws.groupby("ws_order_number")
    lines = by.size()
    # the row count cuts the last order
    assert lines.iloc[:-1].between(8, 16).all() and lines.iloc[-1] <= 16
    assert set(lines.iloc[:-1]) == set(range(8, 17))
    assert ws.ws_order_number.is_monotonic_increasing
    # one value an order, where it is not NULL; NULLs fall a line at a time
    assert (by[PER_ORDER].nunique() <= 1).all().all()
    assert (by[PER_ORDER].count().lt(lines, axis=0)).any().all()
    # a line's own: most orders ship from several warehouses
    for c in PER_LINE:
        assert (by[c].nunique() > 1).mean() > 0.9, c
    assert ws.ws_warehouse_sk.dropna().between(1, 5).all()
    ship = (ws.ws_ship_date_sk - ws.ws_sold_date_sk).dropna()
    assert ship.between(1, 120).all()
    fks = PER_ORDER + ["ws_warehouse_sk", "ws_promo_sk"]
    share = ws[fks].isna().mean()
    assert share.between(0.025, 0.055).all(), share
    assert ws.drop(columns=fks).notna().all().all()
    assert np.allclose(ws.ws_net_paid_inc_ship,
                       ws.ws_net_paid + ws.ws_ext_ship_cost)
    assert np.allclose(ws.ws_net_profit,
                       ws.ws_net_paid - ws.ws_ext_wholesale_cost)


def test_every_return_is_a_sold_line_and_no_line_returns_twice(tables):
    ws = tables["web_sales"].to_pandas()
    wr = tables["web_returns"].to_pandas()
    assert 0.09 < len(wr) / len(ws) < 0.11
    sold = ws.merge(wr, left_on=["ws_order_number", "ws_item_sk"],
                    right_on=["wr_order_number", "wr_item_sk"])
    # (item, order) is web_sales' key and web_returns': one line each
    assert not ws.duplicated(["ws_order_number", "ws_item_sk"]).any()
    assert len(sold) == len(wr)
    assert wr.wr_order_number.notna().all() and wr.wr_item_sk.notna().all()
    assert (sold.wr_return_quantity <= sold.ws_quantity).all()
    late = (sold.wr_returned_date_sk - sold.ws_ship_date_sk).dropna()
    assert late.between(1, 90).all()


def test_keys_point_into_the_tables_they_name(gen, tables):
    ws = tables["web_sales"].to_pandas()
    wr = tables["web_returns"].to_pandas()
    ca = tables["customer_address"].to_pandas()
    web = tables["web_site"].to_pandas()
    dates = set(tables["date_dim"].column("d_date_sk").to_pylist())
    assert ca.ca_address_sk.tolist() == list(range(1, len(ca) + 1))
    assert web.web_site_sk.tolist() == list(range(1, len(web) + 1))
    for col in ("ws_bill_addr_sk", "ws_ship_addr_sk"):
        assert ws[col].dropna().between(1, len(ca)).all()
    assert wr.wr_refunded_addr_sk.dropna().between(1, len(ca)).all()
    assert ws.ws_web_site_sk.dropna().between(1, len(web)).all()
    for col in ("ws_sold_date_sk", "ws_ship_date_sk"):
        assert set(ws[col].dropna().astype(int)) <= dates
    assert set(ca.ca_state) <= set(gen.STATES) and len(gen.STATES) == 14
    assert set(web.web_company_name) == set(gen.COMPANIES)
    assert "pri" in gen.COMPANIES and web.web_site_id.nunique() == 3
    full = gen._web_site(np.random.default_rng(1), 30).to_pandas()
    assert (full.web_company_name == "pri").sum() == 5


def test_the_same_seed_the_same_bytes(gen, tmp_path):
    stars = sources.load_module([BENCH], "datagen", "tpcds.py")
    seed = 2**31 + 35
    a = gen.gen(0.01, seed, str(tmp_path / "a"))
    b = gen.gen(0.01, seed, str(tmp_path / "b"))
    other = gen.gen(0.01, seed + 1, str(tmp_path / "c"))
    for t in gen.TABLES:
        assert filecmp.cmp(a[t], b[t], shallow=False), t
    assert not filecmp.cmp(a["web_sales"], other["web_sales"],
                           shallow=False)
    # web_returns alone draws the same lines as beside web_sales
    alone = gen.gen(0.01, seed, str(tmp_path / "d"), ["web_returns"])
    assert sorted(alone) == ["web_returns"]
    assert filecmp.cmp(a["web_returns"], alone["web_returns"],
                       shallow=False)
    d = stars.gen(0.01, seed, str(tmp_path / "e"), ["date_dim"])
    assert filecmp.cmp(a["date_dim"], d["date_dim"], shallow=False)
    assert gen.SCHEMA["date_dim"] == stars.SCHEMA["date_dim"]


@pytest.mark.parametrize("name", QUERIES)
def test_a_query_names_its_tables_parameters_and_bytes(name, gen):
    q = sources.load_module([BENCH], "queries", "tpcds", name + ".py")
    rng = np.random.default_rng(35)
    draws = [q.params(rng) for _ in range(200)]
    assert {p["year"] for p in draws} == {1999, 2000, 2001, 2002}
    assert {p["month"] for p in draws} == {2, 3, 4, 5}
    assert {p["state"] for p in draws} == set(gen.STATES)
    assert set(q.PARAMS) == set(draws[0])
    for t, cols in q.TABLES.items():
        assert set(cols) <= set(gen.SCHEMA[t]), t
    # the pair of web_sales' columns is read a second time, as the text
    # reads web_sales twice
    nrows = gen.rows(1.0)
    once = sum(nrows[t] * sum(gen.SCHEMA[t][c] for c in cols)
               for t, cols in q.TABLES.items())
    assert q.min_bytes(nrows, gen.SCHEMA, 1) \
        == once + nrows["web_sales"] * 16 + q.RESULT_ROW_BYTES


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_reader_returns_none_without_its_field(name):
    field, per, scale = METRICS[name]
    spec = sources.load_json([BENCH], "metrics", name + ".json")
    read = sources.reader(name, spec)
    seen = sources.Observed(
        setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0],
        qs_delta={field: 3}, memory={}, device_kind="cpu", platform="cpu")
    assert read(spec, seen) == pytest.approx(
        3 * scale / (4.0 if per == "window_s" else 2))
    seen.qs_delta = {}          # the parent commit: no such field
    assert read(spec, seen) is None


def test_a_traced_rehearsal_of_the_weborders_cell(tmp_path):
    from conftest import make_root
    root = make_root(tmp_path, sf=0.05)
    code, line = bench_run.run_cell(CELL, 2**31 + 36, 1.0, True,
                                    root=root, require_chip=False)
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert set(METRICS) <= set(m)
    assert m["compiles_in_window"]["value"] == 0
    # ws_wh's pairs four times a Q95, the returns' twice, at rungs over them
    assert m["join_pairs_mrows_per_query"]["value"] > 0.5
    assert m["join_slot_mrows_per_query"]["value"] \
        >= m["join_pairs_mrows_per_query"]["value"]
    assert 0 < m["semi_anti_joins_per_query"]["value"] <= 4
    assert 0 < m["join_pct"]["value"]
    assert line["compared"]["max_rel_err"]["value"] <= 1e-10
    assert line["compared"]["answers_wrong"]["value"] == 0


def test_the_joins_cell_reports_the_four_too(tiny_root):
    code, line = bench_run.run_cell("tpch_sf1.joins", 2**31 + 37, 1.0, True,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True
    m = line["metrics"]
    assert set(METRICS) <= set(m)
    # Q13's left join expands; nothing there is a semi or an anti join
    assert m["join_pairs_mrows_per_query"]["value"] > 0
    assert m["semi_anti_joins_per_query"]["value"] == 0
