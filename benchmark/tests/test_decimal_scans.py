"""The decimal deployment (``tpch_sf1_decimal``, cell ``decimal_scans``):
its entries and files, the configuration against ``tpch_sf1``'s, the
generator against ``tpch.py``'s values, the precision control, and the two
metrics it adds, read by the ``querystats_delta`` reader that was there."""

import json
import os

import numpy as np
import pyarrow.parquet as pq
import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import compare, sources

CELL = "tpch_sf1_decimal.decimal_scans"
METRICS = {"decimal_decode_pct": ("decimal_decode_s", "scan and pipeline",
                                  "program_span", "%"),
           "wide_decimal_mslots_per_query": ("wide_decimal_slots",
                                             "operators", "program_counter",
                                             "Mslots")}
# keys of tpch_sf1.json the decimal configuration may differ in
DIFFER = {"name", "source", "datagen", "suite", "query_set", "reduced",
          "assumed", "precision", "limits"}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_the_entries_are_appended_as_named():
    b = _bench()
    configs = [c["name"] for c in b["configs"]]
    cells = [w["name"] for w in b["workloads"]]
    layers = [m["name"] for m in b["per_layer"]]
    # appended after every entry that was there before them
    assert configs.index("tpch_sf1_decimal") > configs.index(
        "tpcds_sf1_weborders")
    assert cells.index(CELL) > cells.index(
        "tpcds_sf1_weborders.exists_distinct")
    for name in METRICS:
        assert layers.index(name) > layers.index("page_coded_keys_per_query")
    cfg = next(c for c in b["configs"] if c["name"] == "tpch_sf1_decimal")
    assert cfg["file"] == "benchmark/configs/tpch_sf1_decimal.json"
    assert cfg["reduced"] == ["scale_factor", "query_set"]
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf1_decimal", "decimal_scans", 1)
    traffic = sources.load_json([BENCH], "traffic", "decimal_scans.json")
    assert traffic["suite"] == "tpch_decimal"
    assert (traffic["mix"], traffic["pool"], traffic["clients"],
            traffic["loop"]) == (["q6", "q1"], 4, 1, "closed")
    for name, (field, layer, source, unit) in METRICS.items():
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert (m["layer"], m["source"], m["unit"], m["better"],
                m["moves"], m["workloads"]) == (
            layer, source, unit, "lower", "queries_per_s", [CELL])
        assert sources.load_json([BENCH], "metrics",
                                 name + ".json")["field"] == field


def test_the_configuration_is_tpch_sf1_but_its_types():
    twin, cfg = _config("tpch_sf1"), _config("tpch_sf1_decimal")
    assert list(cfg) == list(twin)
    assert {k for k in cfg if cfg[k] != twin[k]} <= DIFFER
    assert (cfg["suite"], cfg["datagen"], cfg["query_set"]) == (
        "tpch_decimal", "tpch_decimal", ["q1", "q6"])
    assert cfg["precision"] == "decimal(15,2), exact"
    assert cfg["limits"] == {"answers_wrong": 0, "max_rel_err": 0}
    assert set(cfg["reduced"]) == {"scale_factor", "query_set"}
    assert not any("float64" in a or "half-hundredths" in a
                   for a in cfg["assumed"])
    assert cfg["assumed"][0] == twin["assumed"][0]  # the generator's line


@pytest.mark.parametrize("seed", [2**31 + 3, 77])
def test_the_generator_is_tpch_times_a_hundred(tmp_path, seed):
    dec = sources.load_module([BENCH], "datagen", "tpch_decimal.py")
    flt = sources.load_module([BENCH], "datagen", "tpch.py")
    a = pq.read_table(dec.gen(0.01, seed, str(tmp_path / "d"))["lineitem"])
    b = pq.read_table(flt.gen(0.01, seed, str(tmp_path / "f"),
                              ["lineitem"])["lineitem"])
    assert a.column_names == b.column_names == list(dec.SCHEMA["lineitem"])
    for name in a.column_names:
        if name not in dec.MONEY:
            assert a.column(name).equals(b.column(name)), name
            continue
        assert str(a.schema.field(name).type) == "decimal128(15, 2)"
        cents = np.array([int(v.scaleb(2))
                          for v in a.column(name).to_pylist()])
        floats = b.column(name).to_numpy()
        assert (cents == np.rint(floats * 100)).all()
        assert np.abs(cents - floats * 100).max() < 1e-6
    md = pq.ParquetFile(dec.gen(0.01, seed, str(tmp_path / "d"))[
        "lineitem"]).metadata
    kinds = {md.schema.column(i).name: md.schema.column(i).physical_type
             for i in range(md.num_columns)}
    assert {kinds[m] for m in dec.MONEY} == {"INT64"}
    assert all(dec.SCHEMA["lineitem"][m] == 8 for m in dec.MONEY)


def test_the_precision_control_fails(tmp_path):
    """The twin's float64 reference over the same data, and a Decimal at
    the wrong scale, fail ``compare.judge`` under the configuration's
    limits; the reference itself passes."""
    dec = sources.load_module([BENCH], "datagen", "tpch_decimal.py")
    flt = sources.load_module([BENCH], "datagen", "tpch.py")
    limits = _config("tpch_sf1_decimal")["limits"]
    seed = 2**31 + 5
    pdec = pq.read_table(dec.gen(0.01, seed, str(tmp_path / "d"))[
        "lineitem"]).to_pandas()
    pflt = pq.read_table(flt.gen(0.01, seed, str(tmp_path / "f"),
                                 ["lineitem"])["lineitem"]).to_pandas()
    for q in ("q1", "q6"):
        exact = sources.load_module([BENCH], "queries", "tpch_decimal",
                                    q + ".py")
        twin = sources.load_module([BENCH], "queries", "tpch", q + ".py")
        rng = np.random.default_rng(9)
        p = exact.params(rng)
        want = exact.reference({"lineitem": pdec}, p)
        fp = dict(p, discount=float(p["discount"])) if q == "q6" else p
        floats = twin.reference({"lineitem": pflt}, fp)
        coarse = [tuple(x.quantize(exact._money.at_scale(1, 1))
                        if hasattr(x, "quantize") else x for x in r)
                  for r in want]

        def ok(rows):
            return compare.judge([(q, 0, rows)], {(q, 0): want}, limits)[0]
        assert ok(want)
        assert not ok(floats) and not ok(coarse), q


def test_the_readers_leave_out_what_a_parent_lacks():
    for name, (field, *_rest) in METRICS.items():
        spec = sources.load_json([BENCH], "metrics", name + ".json")
        read = sources.reader(name, spec)
        seen = sources.Observed(
            setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0],
            qs_delta={field: 2.0}, memory={}, device_kind="cpu",
            platform="cpu")
        want = 2.0 / 4.0 * 100 if name == "decimal_decode_pct" \
            else 2.0 / 2 * 1e-6
        assert read(spec, seen) == pytest.approx(want)
        seen.qs_delta = {}
        assert read(spec, seen) is None


def test_a_traced_rehearsal_prints_both_metrics(tiny_root):
    code, line = bench_run.run_cell(CELL, 2**31 + 41, 1.0, True,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True
    assert line["compared"]["answers_wrong"]["value"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(METRICS) <= set(got)
    assert got["decimal_decode_pct"] > 0
    # Q1's two wide outputs a slot and Q6's one
    assert got["wide_decimal_mslots_per_query"] > 0
