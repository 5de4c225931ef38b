"""The sort-path aggregate's merge counter as a per-layer metric (PR 34):
a file and an entry only, read by the ``querystats_delta`` reader that
was there."""

import json
import os

import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import sources

NAME = "agg_merges_per_query"


def test_the_metric_is_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = [e for e in json.load(f)["per_layer"]
                   if e["name"] == NAME]
    assert entries == [{"name": NAME, "unit": "count", "better": "lower",
                        "source": "program_counter", "layer": "operators",
                        "moves": "queries_per_s"}]   # every cell reports it
    spec = sources.load_json([BENCH], "metrics", NAME + ".json")
    assert spec == {"kind": "querystats_delta", "field": "agg_merges",
                    "per": "queries"}
    read = sources.reader(NAME, spec)
    seen = sources.Observed(
        setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0, 1.0],
        qs_delta={"agg_merges": 2, "agg_merge_parts": 12}, memory={},
        device_kind="cpu", platform="cpu")
    assert read(spec, seen) == pytest.approx(2 / 3)
    # a program without the counter (the parent commit) gives nothing
    seen.qs_delta = {}
    assert read(spec, seen) is None


def test_a_traced_rehearsal_prints_it(tiny_root):
    """At the rehearsal's size Q67's nine grouping sets of one batch are
    merged twice (the first six fill the batch's 8,192 slots), Q36's
    three once, and Q89 has nothing to merge: one merge a part read 3.3."""
    code, line = bench_run.run_cell("tpcds_sf1_reports.rollup_rank",
                                    2**31 + 34, 1.0, True, root=tiny_root,
                                    require_chip=False)
    assert code == 0 and line["correct"] is True
    assert 0 < line["metrics"][NAME]["value"] <= 1.2
