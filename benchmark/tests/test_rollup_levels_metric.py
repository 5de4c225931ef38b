"""The rollup chain's counter as a per-layer metric: a file and an entry
only, read by the ``querystats_delta`` reader that was there."""

import json
import os

import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import sources

NAME = "rollup_levels_merged_per_query"
CELL = "tpcds_sf1_reports.rollup_rank"


def test_the_metric_is_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = [e for e in json.load(f)["per_layer"]
                   if e["name"] == NAME]
    assert entries == [{"name": NAME, "unit": "count", "better": "higher",
                        "source": "program_counter", "layer": "operators",
                        "moves": "queries_per_s", "workloads": [CELL]}]
    spec = sources.load_json([BENCH], "metrics", NAME + ".json")
    assert spec == {"kind": "querystats_delta",
                    "field": "rollup_levels_merged", "per": "queries"}
    read = sources.reader(NAME, spec)
    seen = sources.Observed(
        setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0, 1.0],
        qs_delta={"rollup_levels_merged": 10, "expand_slot_rows": 0},
        memory={}, device_kind="cpu", platform="cpu")
    assert read(spec, seen) == pytest.approx(10 / 3)
    # a program without the counter (the parent commit) gives nothing
    seen.qs_delta = {}
    assert read(spec, seen) is None


def test_a_traced_rehearsal_prints_it(tiny_root):
    """Q67's eight coarser sets and Q36's two are each reduced from the
    set one key finer, Q89 rolls nothing up, and no Expand copies the
    child: (8 + 2 + 0) / 3 a query over whole passes."""
    code, line = bench_run.run_cell(CELL, 2**31 + 43, 1.0, True,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True
    assert line["metrics"][NAME]["value"] == pytest.approx(10 / 3)
    assert line["metrics"]["expand_slot_mrows_per_query"]["value"] == 0
