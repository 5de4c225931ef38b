"""The dense aggregation's engagement counter as a per-layer metric
(PR 30): a file and an entry only, read by the ``querystats_delta``
reader that was there."""

import json
import os

import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import sources

NAME = "agg_compacted_batches_per_query"


def test_the_metric_is_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["per_layer"]
                     if e["name"] == NAME)
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "operators",
                     "moves": "queries_per_s"}   # every cell reports it
    spec = sources.load_json([BENCH], "metrics", NAME + ".json")
    read = sources.reader(NAME, spec)
    seen = sources.Observed(
        setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0],
        qs_delta={"agg_dense_compacted_batches": 3}, memory={},
        device_kind="cpu", platform="cpu")
    assert read(spec, seen) == pytest.approx(1.5)
    # a program without the counter (the parent commit) gives nothing
    seen.qs_delta = {}
    assert read(spec, seen) is None


def test_a_traced_rehearsal_prints_it(tiny_root):
    """At the rehearsal's size no batch is large enough for a rung: the
    counter is there and reads 0."""
    code, line = bench_run.run_cell("tpch_sf1.joins", 2**31 + 30, 1.0, True,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True
    assert line["metrics"][NAME]["value"] == 0
