"""The mesh exchange's engagement counter as a per-layer metric (PR 32):
a file and an entry only, read by the ``querystats_delta`` reader that was
there, reported in ``tpch_sf1_ici4.joins`` alone; and the counts a second
run of a query repeats."""

import json
import os

import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import sources
from test_ici4 import CELL, SEEDS, _stats, cell, sess, worlds  # noqa: F401

NAME = "ici_compacted_exchanges_per_query"


def test_the_metric_is_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["per_layer"]
                     if e["name"] == NAME)
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "mesh",
                     "moves": "queries_per_s", "workloads": [CELL]}
    spec = sources.load_json([BENCH], "metrics", NAME + ".json")
    read = sources.reader(NAME, spec)
    seen = sources.Observed(
        setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0],
        qs_delta={"ici_compacted_exchanges": 4}, memory={},
        device_kind="cpu", platform="cpu")
    assert read(spec, seen) == pytest.approx(2.0)
    # a program without the counter (the parent commit) gives nothing
    seen.qs_delta = {}
    assert read(spec, seen) is None


@pytest.mark.parametrize("query", ["q3", "q13"])
def test_a_second_run_repeats_the_bytes_and_the_cut(cell, sess, worlds,
                                                    query):
    """The bucket and the rows bucketed come off the same counts: the
    all_to_alls move what they moved, and as many exchanges are cut."""
    dfs, _pds, pools = worlds[SEEDS[0]]
    q = cell.queries[query]
    with _stats() as first:
        q.run(dfs, pools[query][0])
    with _stats() as second:
        q.run(dfs, pools[query][0])
    assert second.compiles == 0
    assert second.ici_exchange_bytes == first.ici_exchange_bytes > 0
    # the exchange above each partial aggregate, at least: a few groups
    # in the capacity of the join under it
    assert second.ici_compacted_exchanges \
        == first.ici_compacted_exchanges >= 1


def test_a_traced_rehearsal_prints_it(tiny_root):
    code, line = bench_run.run_cell(CELL, 2**31 + 32, 1.0, True,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True
    assert line["metrics"][NAME]["value"] >= 1.0
