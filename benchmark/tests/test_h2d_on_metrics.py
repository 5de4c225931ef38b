"""The seven per-layer metrics that read the driving thread's h2d wait
resolved through the threads it waited on (PR 37): files and entries
only, read by the ``querystats_delta`` reader that was there."""

import json
import os

import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import sources

TERMS = ["decode", "convert", "upload", "dispatch", "fetch_wait",
         "host_exec", "handoff"]
NEW = [f"h2d_on_{t}_pct" for t in TERMS]


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_a_file_and_an_entry(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert entries[name]["moves"] == "queries_per_s"
    assert entries[name]["unit"] == "%" and entries[name]["better"] == "lower"
    assert "workloads" not in entries[name]       # every cell reports it
    spec = sources.load_json([BENCH], "metrics", name + ".json")
    assert spec["field"] == "acct_" + name[:-len("_pct")].replace(
        "h2d_on_", "h2d_") + "_s"
    read = sources.reader(name, spec)
    seen = sources.Observed(
        setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0],
        qs_delta={spec["field"]: 2.0}, memory={}, device_kind="cpu",
        platform="cpu")
    assert read(spec, seen) == pytest.approx(2.0 / 4.0 * 100)
    # a program without the field (the parent commit) gives nothing
    seen.qs_delta = {}
    assert read(spec, seen) is None


def test_a_traced_rehearsal_prints_all_seven_and_they_sum_to_the_wait(
        tiny_root, capsys):
    code, line = bench_run.run_cell("tpch_sf1.joins", 2**31 + 37, 1.0, True,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got)
    assert all(got[k] >= 0 for k in NEW)
    assert sum(got[k] for k in NEW) == pytest.approx(
        got["driver_h2d_wait_pct"], abs=0.1)
    window = next(json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                  if ln.startswith('{"phase": "window"'))
    qs = window["querystats"]               # each rounded to 1e-4 s
    assert qs["acct_h2d_wait_s"] > 0       # the scans' prefetch threads
    assert sum(qs.get(f"acct_h2d_{t}_s", 0.0) for t in TERMS) == \
        pytest.approx(qs["acct_h2d_wait_s"], abs=4e-4)
