"""The one-pass distinct aggregate's counter as a per-layer metric (PR 36):
a file and an entry only, read by the ``querystats_delta`` reader that
was there."""

import json
import os

import pytest

import run as bench_run
from conftest import BENCH, REPO, make_root
from harness import sources

NAME = "distinct_one_pass_aggs_per_query"


def test_the_metric_is_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "planner",
        "moves": "queries_per_s"}   # every cell reports it, 0 or not
    spec = sources.load_json([BENCH], "metrics", NAME + ".json")
    assert spec == {"kind": "querystats_delta",
                    "field": "distinct_one_pass_aggs", "per": "queries"}
    read = sources.reader(NAME, spec)
    seen = sources.Observed(
        setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0, 1.0, 1.0],
        qs_delta={"distinct_one_pass_aggs": 4, "join_semi_anti": 8},
        memory={}, device_kind="cpu", platform="cpu")
    assert read(spec, seen) == pytest.approx(1.0)
    seen.qs_delta = {"distinct_one_pass_aggs": 0}
    assert read(spec, seen) == 0
    # a program without the counter (the parent commit) gives nothing
    seen.qs_delta = {}
    assert read(spec, seen) is None


@pytest.mark.parametrize("cell,sf,want", [
    # both of the cell's queries end in count(distinct) beside two sums
    ("tpcds_sf1_weborders.exists_distinct", 0.05, 1.0),
    # no plan of the joins holds a count_distinct: the lowering is not
    # entered
    ("tpch_sf1.joins", 0.01, 0.0)])
def test_a_traced_rehearsal_prints_it(cell, sf, want, tmp_path):
    code, line = bench_run.run_cell(cell, 2**31 + 36, 1.0, True,
                                    root=make_root(tmp_path, sf=sf),
                                    require_chip=False)
    assert code == 0 and line["correct"] is True
    assert line["metrics"][NAME]["value"] == want
    if want:
        # the selection runs once: one semi and one anti join a Q94, two
        # semi joins a Q95
        assert line["metrics"]["semi_anti_joins_per_query"]["value"] == 2.0
