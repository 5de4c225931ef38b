"""A whole run on the named CPU at SF0.01: the result line's keys, a cell
added as files only, the timed path broken underneath, and the control."""

import json
import os

import pytest

import control as bench_control
import run as bench_run
from conftest import make_root

KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def run(workload, root, trace=False, seed=2**31 + 99):
    code, line = bench_run.run_cell(workload, seed, 1.0, trace, root=root,
                                    require_chip=False)
    assert code == 0
    return line


def test_last_line_has_exactly_the_contracts_keys(tiny_root, capsys):
    line = run("tpch_sf1.joins", tiny_root)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == line
    assert list(line) == KEYS                      # compared comes last
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert sorted(line["metrics"]) == ["queries_per_s", "setup_s"]
    assert all(sorted(m) == ["unit", "value"]
               for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"     # named, never hidden
    assert sorted(line["device"]) == ["count", "kind", "memory_peak_bytes",
                                      "platform"]
    # each number compared beside its limit ends stderr
    tail = err.strip().splitlines()[-2:]
    assert tail[0].startswith("compared answers_wrong value 0 limit 0")
    assert tail[1].startswith("compared max_rel_err value ")


def test_traced_line_has_the_layer_metrics_and_no_device_metric_on_a_cpu(
        tiny_root):
    line = run("tpcds_sf1.stars", tiny_root, trace=True)
    assert line["correct"] is True
    assert list(line) == KEYS                      # no breakdown on a CPU
    got = set(line["metrics"])
    assert {"query_median_s", "fetch_wait_pct", "h2d_wait_pct",
            "blocking_fetches_per_query", "compiles_in_window"} <= got
    # read from a device trace or the device's memory: absent, not filled in
    assert not got & {"device_idle_pct", "bytes_roofline_pct",
                      "peak_hbm_pct"}
    assert "busy_s" not in line["device"]
    assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_without_a_chip_there_is_no_result(tiny_root, capsys):
    code, line = bench_run.run_cell("tpch_sf1.joins", 1, 1.0, False,
                                    root=tiny_root)
    assert (code, line) == (2, None)
    assert "no result" in capsys.readouterr().err


def test_a_cell_a_configuration_and_a_metric_are_files_only(tmp_path):
    """A new configuration (a file), a new traffic mix (a file), a new
    per-layer metric over an existing kind (a file), and their entries."""
    def add(bench, tmp):
        for d in ("traffic", "metrics"):
            os.makedirs(os.path.join(tmp, d))
        with open(os.path.join(tmp, "configs", "tpch_tiny.json"), "w") as f:
            json.dump({"suite": "tpch", "datagen": "tpch", "sf": 0.02,
                       "confs": {}, "limits": {"answers_wrong": 0,
                                               "max_rel_err": 1e-9}}, f)
        with open(os.path.join(tmp, "traffic", "q6_only.json"), "w") as f:
            json.dump({"suite": "tpch", "mix": ["q6"], "pool": 2,
                       "clients": 1, "loop": "closed"}, f)
        with open(os.path.join(tmp, "metrics", "fetch_mb_per_query.json"),
                  "w") as f:
            json.dump({"kind": "querystats_delta", "field": "fetch_bytes",
                       "per": "queries", "scale": 1e-6}, f)
        bench["configs"].append({"name": "tpch_tiny", "source": "test",
                                 "file": "configs/tpch_tiny.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": "tpch_tiny.q6_only",
                                   "config": "tpch_tiny",
                                   "traffic": "q6_only", "chips": 1,
                                   "why": "test"})
        bench["per_layer"].append({
            "name": "fetch_mb_per_query", "unit": "MB", "better": "lower",
            "source": "program_counter", "layer": "operators",
            "moves": "queries_per_s", "workloads": ["tpch_tiny.q6_only"]})
    root = make_root(tmp_path, extra=add)
    line = run("tpch_tiny.q6_only", root, trace=True)
    assert line["correct"] is True
    assert line["metrics"]["fetch_mb_per_query"]["value"] > 0
    # the new metric lists its cell: another cell's line does not carry it
    assert "fetch_mb_per_query" not in [
        m["name"] for m in bench_run.Cell("tpch_sf1.joins",
                                          root).metrics("per_layer")]


# -- the timed path broken underneath: correct has to come out false ---------

def _break_collect(monkeypatch, alter):
    from spark_rapids_tpu.sql.dataframe import DataFrame
    sound = DataFrame.collect

    def collect(self, *a, **kw):
        return alter(sound(self, *a, **kw))
    monkeypatch.setattr(DataFrame, "collect", collect)


def _nudge_a_float(rows, by):
    rows = [tuple(r) for r in rows]
    for i, r in enumerate(rows):
        for j, x in enumerate(r):
            if isinstance(x, float):
                rows[i] = r[:j] + (x * (1 + by),) + r[j + 1:]
                return rows
    return rows


@pytest.mark.parametrize("fault", ["float_nudged", "row_dropped",
                                   "count_altered", "query_raises"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        fault, tiny_root, monkeypatch):
    alter = {
        # two orders above float64 rounding, far under float32's
        "float_nudged": lambda rows: _nudge_a_float(rows, 1e-8),
        "row_dropped": lambda rows: list(rows)[:-1],
        "count_altered": lambda rows: [
            tuple(x + 1 if isinstance(x, int) and not isinstance(x, bool)
                  else x for x in r) for r in rows],
    }.get(fault)
    if fault == "query_raises":
        def alter(rows):
            raise RuntimeError("device lost")
    # warm-up has to pass, as it would for a fault that only the window
    # meets: break the path after the cell's 8 warm-up queries
    calls = {"n": 0}

    def late(rows):
        calls["n"] += 1
        return alter(rows) if calls["n"] > 8 else rows
    _break_collect(monkeypatch, late)
    line = run("tpch_sf1.joins", tiny_root)
    assert line["correct"] is False
    assert line["failed"] > 0
    c = line["compared"]
    if fault == "float_nudged":
        assert c["answers_wrong"]["value"] == 0
        assert c["max_rel_err"]["value"] > c["max_rel_err"]["limit"]
    else:
        assert c["answers_wrong"]["value"] > 0


def test_half_of_the_input_left_out_is_not_correct(tiny_root, monkeypatch):
    """The scan drops every other row group's worth: half of lineitem."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.sql.session import Session
    sound = Session.read_parquet

    def half(self, path, columns=None):
        if path.endswith("lineitem.parquet"):
            t = pq.read_table(path)
            path = path.replace("lineitem.parquet", "lineitem_half.parquet")
            pq.write_table(t.slice(0, t.num_rows // 2), path)
        return sound(self, path, columns)
    monkeypatch.setattr(Session, "read_parquet", half)
    line = run("tpch_sf1.joins", tiny_root)
    assert line["correct"] is False


# -- the control: the reference itself, one precision down -------------------

@pytest.mark.parametrize("workload", ["tpch_sf1.joins", "tpcds_sf1.stars"])
@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_the_float32_control_is_not_correct(workload, seed, tmp_path):
    root = make_root(tmp_path, sf=0.05)
    rec = bench_control.control(workload, seed, root)
    assert rec["control_correct"] is False
    c = rec["compared"]["max_rel_err"]
    assert c["value"] > 3 * c["limit"]
