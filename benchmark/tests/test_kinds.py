"""What has no code yet fails by name, before anything runs."""

import json
import os

import pytest

import run as bench_run
from conftest import make_root
from harness import loop, sources


def test_unknown_loop_kind_fails_by_name():
    with pytest.raises(SystemExit, match="loop kind 'open' has no code"):
        loop.get("open")


def test_unknown_source_kind_fails_by_name():
    with pytest.raises(SystemExit,
                       match="metric 'plan_s' has source kind 'plan_span'"):
        sources.reader("plan_s", {"kind": "plan_span"})


def test_an_open_loop_cell_is_expressible_and_refused(tmp_path):
    def served(bench, tmp):
        os.makedirs(os.path.join(tmp, "traffic"))
        with open(os.path.join(tmp, "traffic", "served.json"), "w") as f:
            json.dump({"suite": "tpch", "mix": ["q6"], "pool": 4,
                       "loop": "open", "rate_per_s": 2.0}, f)
        bench["workloads"].append({
            "name": "tpch_sf1.served", "config": "tpch_sf1",
            "traffic": "served", "chips": 4, "why": "open loop"})
    root = make_root(tmp_path, extra=served)
    with pytest.raises(SystemExit, match="loop kind 'open'"):
        bench_run.Cell("tpch_sf1.served", root)


def test_a_metric_of_an_unknown_kind_stops_the_run_before_it_starts(
        tmp_path):
    def planner(bench, tmp):
        os.makedirs(os.path.join(tmp, "metrics"))
        with open(os.path.join(tmp, "metrics", "plan_s.json"), "w") as f:
            json.dump({"kind": "plan_span"}, f)
        bench["per_layer"].append({
            "name": "plan_s", "unit": "s", "better": "lower",
            "source": "program_span", "layer": "planner",
            "moves": "queries_per_s"})
    root = make_root(tmp_path, extra=planner)
    with pytest.raises(SystemExit, match="'plan_s' has source kind"):
        bench_run.run_cell("tpch_sf1.joins", 1, 1.0, True, root=root,
                           require_chip=False)


def test_missing_names_say_what_is_there(tiny_root):
    with pytest.raises(SystemExit, match="no workload 'nope'"):
        bench_run.Cell("nope", tiny_root)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    ob = sources.Observed(setup_s=1.0, window_s=2.0, latencies=[],
                          qs_delta={}, memory={}, device_kind="cpu",
                          platform="cpu", trace={"device_idle_pct": 5.0,
                                                 "busy_s": 1.0})
    specs = {
        "queries_per_s": {"kind": "harness_clock", "stat": "rate"},
        "h2d_wait_pct": {"kind": "querystats_delta", "field": "h2d_wait_s",
                         "per": "window_s"},
        "peak_hbm_pct": {"kind": "memory_stats", "num": "peak_bytes_in_use",
                         "den": "bytes_limit"},
        # a trace taken on a CPU backend is no device metric
        "device_idle_pct": {"kind": "trace", "field": "device_idle_pct"},
        "setup_s": {"kind": "harness_clock", "stat": "setup"},
    }
    units = {k: "x" for k in specs}
    assert sources.read_all(specs, units, ob) == {
        "setup_s": {"value": 1.0, "unit": "x"}}


def test_roofline_share_from_least_bytes_and_busy_time():
    ob = sources.Observed(
        setup_s=1.0, window_s=2.0, latencies=[0.5], qs_delta={}, memory={},
        device_kind="TPU v5 lite", platform="tpu",
        trace={"busy_s": 0.5, "device_idle_pct": 75.0},
        traced_min_bytes=819e9 * 0.01)
    spec = {"kind": "trace", "field": "bytes_roofline_pct"}
    assert sources.reader("r", spec)(spec, ob) == pytest.approx(2.0)
    ob.device_kind = "TPU v9"
    with pytest.raises(KeyError, match="no peak"):
        sources.reader("r", spec)(spec, ob)
