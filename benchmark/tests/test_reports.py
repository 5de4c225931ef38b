"""The cells PR 33 adds, as files and entries only:
``tpcds_sf1_reports.rollup_rank`` (a configuration, a generator, three
queries, a traffic mix, five per-layer metrics) and ``tpch_sf1.scans`` (an
entry over files that were there)."""

import filecmp
import json
import os

import numpy as np
import pyarrow.parquet as pq
import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import compare, sources

CELL = "tpcds_sf1_reports.rollup_rank"
QUERIES = ("q67", "q36", "q89")
METRICS = {  # name: (QueryStats field, per, scale)
    "window_pct": ("window_exec_s", "window_s", 100),
    "expand_pct": ("expand_exec_s", "window_s", 100),
    "window_rows_per_query": ("window_rows", "queries", 1),
    "expand_slot_mrows_per_query": ("expand_slot_rows", "queries", 1e-6),
    "cpu_fallback_nodes_per_query": ("cpu_fallback_nodes", "queries", 1),
}
# TPC-DS v3 clause 2.4.? "store": the 29 columns in the specification's order
STORE = ["s_store_sk", "s_store_id", "s_rec_start_date", "s_rec_end_date",
         "s_closed_date_sk", "s_store_name", "s_number_employees",
         "s_floor_space", "s_hours", "s_manager", "s_market_id",
         "s_geography_class", "s_market_desc", "s_market_manager",
         "s_division_id", "s_division_name", "s_company_id",
         "s_company_name", "s_street_number", "s_street_name",
         "s_street_type", "s_suite_number", "s_city", "s_county", "s_state",
         "s_zip", "s_country", "s_gmt_offset", "s_tax_precentage"]


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def gen():
    return sources.load_module([BENCH], "datagen", "tpcds_reports.py")


def test_the_new_cells_are_entries_as_the_issue_names_them(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": "tpcds_sf1_reports",
                           "traffic": "rollup_rank", "chips": 1}
    assert cells["tpch_sf1.scans"] == {
        **cells["tpch_sf1.scans"], "config": "tpch_sf1", "traffic": "scans",
        "chips": 1}
    traffic = sources.load_json([BENCH], "traffic", "rollup_rank.json")
    assert (traffic["mix"], traffic["pool"], traffic["clients"],
            traffic["loop"]) == (list(QUERIES), 4, 1, "closed")
    for name in METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["better"] == "lower"
        assert entry["moves"] == "queries_per_s"


def test_the_configuration_is_the_stars_key_for_key(bench):
    with open(os.path.join(BENCH, "configs", "tpcds_sf1.json")) as f:
        stars = json.load(f)
    with open(os.path.join(BENCH, "configs", "tpcds_sf1_reports.json")) as f:
        reports = json.load(f)
    assert sorted(reports) == sorted(stars)
    differ = {k for k in stars if stars[k] != reports[k]}
    assert differ == {"name", "source", "datagen", "query_set", "tables",
                      "reduced", "assumed"}
    assert sorted(reports["reduced"]) == sorted(stars["reduced"])
    assert reports["query_set"] == list(QUERIES) and reports["confs"] == {}
    assert reports["tables"] == ["date_dim", "item", "store", "store_sales"]


def test_store_has_the_specifications_columns_and_rows(gen, tmp_path):
    paths = gen.gen(0.01, 2**31 + 5, str(tmp_path), ["store"])
    store = pq.read_table(paths["store"])
    assert store.column_names == STORE == list(gen.SCHEMA["store"])
    assert gen.rows(1.0)["store"] == 12 and store.num_rows == 2
    big = gen._store(np.random.default_rng(1), 12).to_pandas()
    # a business key over two revisions; keys are the range the fact draws
    assert big.s_store_id.nunique() == 6
    assert big.s_store_sk.tolist() == list(range(1, 13))
    assert set(big.s_state) <= set(gen.STATES)
    assert set(big.s_store_name) <= set(gen.STORE_NAMES)
    assert big.drop(columns=["s_rec_end_date", "s_closed_date_sk"]) \
        .notna().all().all()


def test_the_stars_three_tables_are_byte_equal_to_the_stars(gen, tmp_path):
    stars = sources.load_module([BENCH], "datagen", "tpcds.py")
    seed = 2**31 + 33
    a = gen.gen(0.01, seed, str(tmp_path / "reports"))
    b = stars.gen(0.01, seed, str(tmp_path / "stars"))
    assert sorted(a) == ["date_dim", "item", "store", "store_sales"]
    for t in stars.TABLES:
        assert filecmp.cmp(a[t], b[t], shallow=False), t
    assert {t: gen.SCHEMA[t] for t in stars.TABLES} == stars.SCHEMA


@pytest.fixture(scope="module")
def world(gen, tmp_path_factory):
    import spark_rapids_tpu as srt
    sess = srt.Session.get_or_create()
    paths = gen.gen(0.01, 2**31 + 9, str(tmp_path_factory.mktemp("reports")))
    return (sess, {t: sess.read_parquet(p) for t, p in paths.items()},
            {t: pq.read_table(p).to_pandas() for t, p in paths.items()})


@pytest.mark.parametrize("name", QUERIES)
def test_run_equals_reference_and_nothing_falls_back(name, world, gen):
    q = sources.load_module([BENCH], "queries", "tpcds", name + ".py")
    sess, dfs, pds = world
    rng = np.random.default_rng([33, len(name)])
    nonempty = 0
    for _ in range(3):
        p = q.params(rng)
        got, want = q.run(dfs, p), q.reference(pds, p)
        assert compare.rows_rel_err(got, want) <= 1e-10, (p, got[:3],
                                                          want[:3])
        assert "CpuOp" not in sess.profiled_explain()
        nonempty += bool(want)
    assert nonempty, "every parameter set drawn gave an empty answer"
    # the templates' parameter ranges, and bytes from the schema's widths
    assert set(q.PARAMS) == set(p)
    nrows = gen.rows(1.0)
    assert q.min_bytes(nrows, gen.SCHEMA, 10) == sum(
        nrows[t] * sum(gen.SCHEMA[t][c] for c in cols)
        for t, cols in q.TABLES.items()) + 10 * q.RESULT_ROW_BYTES


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


def test_a_traced_rehearsal_of_the_reports_cell(tiny_root):
    code, line = bench_run.run_cell(CELL, 2**31 + 34, 1.0, True,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert set(METRICS) <= set(m)
    assert m["cpu_fallback_nodes_per_query"]["value"] == 0
    assert m["compiles_in_window"]["value"] == 0
    assert m["window_rows_per_query"]["value"] > 0
    # Q67 expands to nine sets and Q36 to three, one batch each here
    assert m["expand_slot_mrows_per_query"]["value"] > 0
    assert line["compared"]["max_rel_err"]["value"] <= 1e-10


def test_a_rehearsal_of_the_scans_cell(tiny_root):
    code, line = bench_run.run_cell("tpch_sf1.scans", 2**31 + 35, 1.0, False,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True
    assert sorted(line["metrics"]) == ["queries_per_s", "setup_s"]
