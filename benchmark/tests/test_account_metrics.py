"""The eight per-layer metrics that read the program's host-time account
(PR 26): files and entries only, read by the ``querystats_delta`` reader
that was there."""

import json
import os

import pytest

import run as bench_run
from conftest import BENCH, REPO
from harness import sources

ACCOUNT = ["plan_pct", "host_exec_pct", "dispatch_pct",
           "driver_fetch_wait_pct", "driver_h2d_wait_pct",
           "unattributed_pct"]
NEW = ACCOUNT + ["decode_busy_pct", "upload_mb_per_query"]


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_a_file_and_an_entry(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert entries[name]["moves"] == "queries_per_s"
    assert "workloads" not in entries[name]       # every cell reports it
    spec = sources.load_json([BENCH], "metrics", name + ".json")
    read = sources.reader(name, spec)
    seen = sources.Observed(
        setup_s=1.0, window_s=4.0, latencies=[1.0, 1.0],
        qs_delta={spec["field"]: 2.0}, memory={}, device_kind="cpu",
        platform="cpu")
    per = {"window_s": 4.0, "queries": 2}[spec["per"]]
    assert read(spec, seen) == pytest.approx(2.0 / per * spec["scale"])
    # a program without the field (the parent commit) gives nothing
    seen.qs_delta = {}
    assert read(spec, seen) is None


def test_a_traced_rehearsal_prints_all_eight_and_the_account_closes(
        tiny_root, capsys):
    code, line = bench_run.run_cell("tpch_sf1.joins", 2**31 + 26, 1.0, True,
                                    root=tiny_root, require_chip=False)
    assert code == 0 and line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got)
    assert all(got[k] >= 0 for k in NEW)
    assert got["upload_mb_per_query"] > 0 and got["decode_busy_pct"] > 0
    # with the window's compile / admit / result shares the six add to the
    # window, less the harness's own work between queries
    window = next(json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                  if ln.startswith('{"phase": "window"'))
    qs, seconds = window["querystats"], window["seconds"]
    rest = sum(qs.get(f"acct_{t}_s", 0.0)
               for t in ("compile", "admit", "result"))
    total = sum(got[k] for k in ACCOUNT) + 100.0 * rest / seconds
    assert 90.0 < total <= 100.5
    assert qs["query_wall_s"] <= seconds
