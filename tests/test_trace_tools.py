"""tools/trace_report.py + tools/bench_compare.py + span-timing lint."""

import json

import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F

from tools import bench_compare, trace_report
from tools.srtlint.engine import run as srtlint_run


@pytest.fixture()
def sess():
    s = srt.Session.get_or_create()
    yield s
    s.conf.unset("spark.rapids.tpu.sql.trace.enabled")


def _trace_file(sess, tmp_path):
    rng = np.random.default_rng(3)
    df = sess.create_dataframe({"k": rng.integers(0, 50, 30000),
                                "v": rng.random(30000)})
    q = (df.where(F.col("v") > 0.2)
         .group_by((F.col("k") % 7).cast("int").alias("g"))
         .agg(F.sum(F.col("v")).alias("s")))
    sess.conf.set("spark.rapids.tpu.sql.trace.enabled", True)
    try:
        q.collect()
    finally:
        sess.conf.unset("spark.rapids.tpu.sql.trace.enabled")
    path = str(tmp_path / "q.trace.json")
    sess.last_trace().write(path)
    return path


# ---------------------------------------------------------------------------------
# trace_report
# ---------------------------------------------------------------------------------

def test_trace_report_hot_operators_and_overlap(sess, tmp_path):
    path = _trace_file(sess, tmp_path)
    a = trace_report.analyze(trace_report.load(path))
    assert a["wall_s"] > 0
    assert a["operators"], "no per-operator rows"
    # per-operator self time is positive and sums to <= ~wall (nesting
    # subtracts children; on the serial CPU path nothing double-counts)
    assert a["self_total_s"] > 0
    assert a["self_total_s"] <= a["wall_s"] * 1.1
    # self-time accounts for the bulk of the query wall time
    assert a["self_coverage"] > 0.5
    assert a["blocking_fetches"] >= 1
    assert 0 < a["overlap_ratio"] <= 4.0
    out = trace_report.format_report(a)
    assert "hot operators" in out
    assert "blocking fetches:" in out
    assert "overlap:" in out
    assert "TpuScan" in out or "ScanExec" in out


def test_trace_report_main(sess, tmp_path, capsys):
    path = _trace_file(sess, tmp_path)
    assert trace_report.main([path]) == 0
    assert "hot operators" in capsys.readouterr().out
    assert trace_report.main([]) == 2


def test_trace_report_peer_fault_summary(sess, tmp_path):
    """A query that survived distributed failures gets a peers: line
    (QueryStats snapshot on the root event is authoritative); clean
    queries don't."""
    path = _trace_file(sess, tmp_path)
    data = trace_report.load(path)
    assert "peers:" not in trace_report.format_report(
        trace_report.analyze(data))
    for e in data["traceEvents"]:
        if e.get("cat") == "query":
            e.setdefault("args", {}).update({
                "peers_lost": 1, "fragments_recomputed_remote": 8,
                "partitions_reowned": 4, "queries_resubmitted": 1})
    a = trace_report.analyze(data)
    assert a["peers_lost"] == 1
    assert a["fragments_recomputed_remote"] == 8
    out = trace_report.format_report(a)
    assert ("peers: lost=1 remote_recomputed=8 reowned=4 "
            "resubmissions=1") in out


def test_trace_report_merged_concurrent(sess, tmp_path, capsys):
    """A merged multi-query trace renders per-query sections plus a
    contention summary instead of assuming one serial query."""
    from spark_rapids_tpu.utils import tracing
    sess.conf.set("spark.rapids.tpu.sql.trace.enabled", True)
    try:
        rng = np.random.default_rng(7)
        df = sess.create_dataframe({"k": rng.integers(0, 20, 10000),
                                    "v": rng.random(10000)})
        q = df.group_by("k").agg(F.sum(F.col("v")).alias("s"))
        handles = [sess.submit(q, label=f"conc-{i}") for i in range(3)]
        for h in handles:
            h.result(timeout=60)
    finally:
        sess.conf.unset("spark.rapids.tpu.sql.trace.enabled")
    traces = [h.trace() for h in handles]
    assert all(t is not None for t in traces)
    path = str(tmp_path / "merged.trace.json")
    tracing.write_merged(traces, path)
    data = trace_report.load(path)
    # one pid + spanTrees entry per query
    assert len(data["spanTrees"]) == 3
    assert {st["pid"] for st in data["spanTrees"]} == {1, 2, 3}
    subs, span_trees = trace_report.split_queries(data)
    assert len(subs) == 3 and span_trees is not None
    for sub in subs:
        a = trace_report.analyze(sub)
        assert a["wall_s"] > 0
        assert a["operators"], "per-query section lost its operators"
    c = trace_report.contention(span_trees)
    assert c["queries"] == 3
    assert c["span_s"] > 0
    assert c["sum_walls_s"] >= c["span_s"] * 0.99
    assert 1 <= c["peak_concurrency"] <= 3
    assert c["statuses"] == {"ok": 3}
    assert trace_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "contention summary (3 concurrent queries)" in out
    assert "aggregate throughput" in out
    # a single-query trace still renders the old way
    single = _trace_file(sess, tmp_path)
    subs1, st1 = trace_report.split_queries(trace_report.load(single))
    assert len(subs1) == 1 and st1 is None


# ---------------------------------------------------------------------------------
# bench_compare
# ---------------------------------------------------------------------------------

def _bench(value, **queries):
    agg = {"metric": "tpch22_tpcds22_geomean_speedup_vs_cpu",
           "value": value, "unit": "x"}
    agg.update(queries)
    return agg


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def test_bench_compare_ok(tmp_path, capsys):
    old = _write(tmp_path, "old.json", _bench(
        4.0, q1={"engine_s": 1.0}, q6={"engine_s": 0.5}))
    new = _write(tmp_path, "new.json", _bench(
        4.1, q1={"engine_s": 1.05}, q6={"engine_s": 0.45}))
    assert bench_compare.main([old, new]) == 0
    assert "OK" in capsys.readouterr().out


def test_bench_compare_query_regression(tmp_path, capsys):
    old = _write(tmp_path, "old.json", _bench(4.0, q1={"engine_s": 1.0}))
    new = _write(tmp_path, "new.json", _bench(4.0, q1={"engine_s": 1.5}))
    assert bench_compare.main([old, new]) == 1
    assert "REGRESSION" in capsys.readouterr().err


def test_bench_compare_aggregate_regression(tmp_path, capsys):
    old = _write(tmp_path, "old.json", _bench(4.0, q1={"engine_s": 1.0}))
    new = _write(tmp_path, "new.json", _bench(3.0, q1={"engine_s": 1.0}))
    assert bench_compare.main([old, new]) == 1
    err = capsys.readouterr().err
    assert "aggregate geomean" in err


def test_bench_compare_errored_query_is_regression(tmp_path):
    old = _write(tmp_path, "old.json", _bench(4.0, q1={"engine_s": 1.0}))
    new = _write(tmp_path, "new.json", _bench(
        4.0, q1={"error": "timeout after 300s"}))
    assert bench_compare.main([old, new]) == 1


def test_bench_compare_thresholds_and_driver_wrapper(tmp_path):
    # 30% slower passes with a 50% threshold
    old = _write(tmp_path, "old.json", _bench(4.0, q1={"engine_s": 1.0}))
    new_obj = _bench(4.0, q1={"engine_s": 1.3})
    new = _write(tmp_path, "new.json", new_obj)
    assert bench_compare.main(
        [old, new, "--max-query-regress-pct", "50"]) == 0
    # the BENCH_r0N driver capture shape: {"parsed": {...}} and
    # {"tail": "...\n<json line>"}
    wrapped = _write(tmp_path, "wrapped.json",
                     {"rc": 0, "parsed": new_obj})
    tail = _write(tmp_path, "tail.json",
                  {"rc": 124, "parsed": None,
                   "tail": "noise\n" + json.dumps(new_obj)})
    assert bench_compare.main(
        [old, wrapped, "--max-query-regress-pct", "50"]) == 0
    assert bench_compare.main(
        [old, tail, "--max-query-regress-pct", "50"]) == 0


def test_bench_compare_bad_file(tmp_path):
    bad = _write(tmp_path, "bad.json", {"nothing": True})
    ok = _write(tmp_path, "ok.json", _bench(4.0))
    assert bench_compare.main([bad, ok]) == 2


# ---------------------------------------------------------------------------------
# span-timing lint
# ---------------------------------------------------------------------------------

def test_span_timing_lint_clean_and_detects(tmp_path):
    from tools.srtlint import run_for_pytest
    assert [f for f in run_for_pytest().failing
            if f.rule == "span-timing"] == []
    # a synthetic violation is caught; a REASONED marker suppresses,
    # a bare marker does not (every suppression must say why)
    pkg = tmp_path / "spark_rapids_tpu"
    (pkg / "plan").mkdir(parents=True)
    (pkg / "parallel").mkdir()
    (pkg / "plan" / "bad.py").write_text(
        "import time\n"
        "t0 = time.perf_counter()\n"
        "ok = time.monotonic()  # span-api-ok (a seed, not timing)\n"
        "t1 = time.time()  # span-api-ok\n")
    report = srtlint_run(str(tmp_path), roots=("spark_rapids_tpu",),
                         rules=["span-timing"])
    assert sorted(f.line for f in report.failing) == [2, 4]
    assert "no reason" in [f for f in report.failing
                           if f.line == 4][0].message
    assert [f.line for f in report.suppressed] == [3]


# ---------------------------------------------------------------------------------
# explain_slow + trace_report --why
# ---------------------------------------------------------------------------------

from spark_rapids_tpu.utils import recorder, telemetry  # noqa: E402
from tools import explain_slow, perfwatch  # noqa: E402


@pytest.fixture()
def fresh_recorder():
    recorder.reset_for_tests()
    telemetry.reset_for_tests()
    yield recorder.recorder()
    recorder.reset_for_tests()
    telemetry.reset_for_tests()


def _sealed_capture(rec, tmp_path, term="compile", excess=1.5):
    """A recorder-retained capture whose verdict names ``term``."""
    from spark_rapids_tpu.utils.tracing import QueryTrace
    rec.configure({
        "spark.rapids.tpu.recorder.enabled": True,
        "spark.rapids.tpu.recorder.maxQueries": 48,
        "spark.rapids.tpu.recorder.maxBytes": 32 << 20,
        "spark.rapids.tpu.sql.trace.dir": str(tmp_path),
    })

    def seal(wall, attrs):
        tr = QueryTrace(f"q[{term}]")
        tr.attrs.update(attrs)
        tr.t_end = tr.t0 + wall
        tr.status = "ok"
        rec.seal(tr, None, 0.01, True, False)

    for _ in range(3):
        seal(0.05, {f"{term}_s" if term != "h2d"
                    else "h2d_wait_s": 0.005})
    seal(2.0, {f"{term}_s" if term != "h2d"
               else "h2d_wait_s": excess})
    cap = rec.captures()[-1]
    assert cap.verdict == term
    return cap


class TestExplainSlow:
    def test_sealed_capture_is_authoritative(self, fresh_recorder,
                                             tmp_path):
        cap = _sealed_capture(fresh_recorder, tmp_path)
        res = explain_slow.analyze_path(cap.path)
        assert res["sealed"] is True
        assert res["verdict"] == "compile"
        assert res["capture_reason"] == "top_k"
        assert res["excess_s"] == pytest.approx(1.5, abs=0.1)
        out = explain_slow.format_why(res)
        assert "<-- dominant" in out
        assert "verdict: compile" in out
        assert "EWMA baseline" in out

    def test_unsealed_trace_recomputes_without_verdict(self, sess,
                                                       tmp_path):
        # a trace dumped with the recorder off predates the seal:
        # terms are recomputed offline, no baseline verdict is invented
        sess.conf.set("spark.rapids.tpu.recorder.enabled", False)
        try:
            path = _trace_file(sess, tmp_path)
        finally:
            sess.conf.unset("spark.rapids.tpu.recorder.enabled")
        res = explain_slow.analyze_path(path)
        assert res["sealed"] is False
        assert res["verdict"] is None
        assert res["terms"]["dispatch"] > 0
        out = explain_slow.format_why(res)
        assert "n/a" in out and "recomputed" in out

    def test_main_json_and_exit_codes(self, fresh_recorder, tmp_path,
                                      capsys):
        cap = _sealed_capture(fresh_recorder, tmp_path,
                              term="fetch_wait")
        assert explain_slow.main([cap.path, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out.strip())
        assert doc["verdict"] == "fetch_wait"
        bad = tmp_path / "nope.json"
        bad.write_text("{")
        assert explain_slow.main([str(bad)]) == 2

    def test_trace_report_why_section(self, fresh_recorder, tmp_path,
                                      capsys):
        cap = _sealed_capture(fresh_recorder, tmp_path,
                              term="queue_wait")
        assert trace_report.main([cap.path, "--why"]) == 0
        out = capsys.readouterr().out
        assert "why (root-cause attribution):" in out
        assert "verdict: queue_wait" in out

    def test_trace_report_why_on_plain_trace(self, sess, tmp_path,
                                             capsys):
        path = _trace_file(sess, tmp_path)
        assert trace_report.main([path, "--why"]) == 0
        out = capsys.readouterr().out
        assert "hot operators" in out  # the timing report still leads
        assert "why (root-cause attribution):" in out


# ---------------------------------------------------------------------------------
# bench_compare compile gate
# ---------------------------------------------------------------------------------

class TestCompileGate:
    def test_warm_recompile_is_a_regression(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", _bench(
            4.0, q1={"engine_s": 1.0, "compiles_warm": 0}))
        new = _write(tmp_path, "new.json", _bench(
            4.0, q1={"engine_s": 1.0, "compiles_warm": 2}))
        assert bench_compare.main([old, new]) == 1
        err = capsys.readouterr().err
        assert "compiles_warm 0 -> 2" in err
        # an explicit allowance admits it
        assert bench_compare.main(
            [old, new, "--max-compile-increase", "2"]) == 0

    def test_compile_improvement_is_a_note(self, tmp_path, capsys):
        old = _write(tmp_path, "old.json", _bench(
            4.0, q1={"engine_s": 1.0, "compiles_warm": 3}))
        new = _write(tmp_path, "new.json", _bench(
            4.0, q1={"engine_s": 1.0, "compiles_warm": 0}))
        assert bench_compare.main([old, new]) == 0
        assert "improved" in capsys.readouterr().out


# ---------------------------------------------------------------------------------
# perfwatch: the append-only regression sentinel
# ---------------------------------------------------------------------------------

class TestPerfwatch:
    def _ledger(self, tmp_path):
        return str(tmp_path / "perf.jsonl")

    def test_bench_record_then_clean_check(self, tmp_path, capsys):
        led = self._ledger(tmp_path)
        base = _write(tmp_path, "b0.json", _bench(
            4.0, q1={"engine_s": 1.0, "syncs_warm": 2,
                     "compiles_warm": 0}))
        assert perfwatch.main(["record", led, base]) == 0
        run = _write(tmp_path, "b1.json", _bench(
            4.05, q1={"engine_s": 1.02, "syncs_warm": 2,
                      "compiles_warm": 0}))
        assert perfwatch.main(["check", led, run]) == 0
        assert "OK" in capsys.readouterr().out

    def test_bench_compile_and_sync_regressions_gate(self, tmp_path,
                                                     capsys):
        led = self._ledger(tmp_path)
        base = _write(tmp_path, "b0.json", _bench(
            4.0, q1={"engine_s": 1.0, "syncs_warm": 2,
                     "compiles_warm": 0}))
        assert perfwatch.main(["record", led, base]) == 0
        run = _write(tmp_path, "b1.json", _bench(
            4.0, q1={"engine_s": 1.0, "syncs_warm": 3,
                     "compiles_warm": 1}))
        assert perfwatch.main(["check", led, run]) == 1
        err = capsys.readouterr().err
        assert "compiles_warm 0 -> 1" in err
        assert "syncs_warm 2 -> 3" in err
        # the tolerances admit the same run
        assert perfwatch.main(
            ["check", led, run, "--max-sync-increase", "1",
             "--max-compile-increase", "1"]) == 0

    def _loadgen_report(self, tmp_path, name, p95, slo=0):
        return _write(tmp_path, name, {
            "loadgen": 1, "p50_ms": 10.0, "p95_ms": p95,
            "p99_ms": p95 * 1.4, "throughput_qps": 50.0,
            "typed_errors": 0, "mismatches": 0,
            "slo_violations": slo, "queries_completed": 100})

    def test_loadgen_latency_and_slo_gates(self, tmp_path, capsys):
        led = self._ledger(tmp_path)
        base = self._loadgen_report(tmp_path, "l0.json", p95=20.0)
        assert perfwatch.main(["record", led, base]) == 0
        ok = self._loadgen_report(tmp_path, "l1.json", p95=22.0)
        assert perfwatch.main(["check", led, ok]) == 0
        slow = self._loadgen_report(tmp_path, "l2.json", p95=40.0)
        assert perfwatch.main(["check", led, slow]) == 1
        assert "p95_ms" in capsys.readouterr().err
        burned = self._loadgen_report(tmp_path, "l3.json", p95=20.0,
                                      slo=3)
        assert perfwatch.main(["check", led, burned]) == 1
        assert "slo_violations 0 -> 3" in capsys.readouterr().err

    def test_check_record_appends_and_baseline_modes(self, tmp_path,
                                                     capsys):
        led = self._ledger(tmp_path)
        run = _write(tmp_path, "b.json", _bench(
            4.0, q1={"engine_s": 1.0}))
        # first check of a stream: no baseline, still exit 0
        assert perfwatch.main(["check", led, run, "--record"]) == 0
        assert "no baseline" in capsys.readouterr().out
        assert len(perfwatch.read_ledger(led)) == 1
        for mode in ("last", "best", "median"):
            assert perfwatch.main(
                ["check", led, run, "--baseline", mode]) == 0
        assert perfwatch.main(["show", led]) == 0
        assert "1 run(s)" in capsys.readouterr().out

    def test_labels_partition_streams(self, tmp_path, capsys):
        led = self._ledger(tmp_path)
        a = _write(tmp_path, "a.json", _bench(4.0, q1={"engine_s": 1.0}))
        assert perfwatch.main(["record", led, a, "--label", "tpch"]) == 0
        slow = _write(tmp_path, "s.json", _bench(
            4.0, q1={"engine_s": 9.0}))
        # a different label never gates against the tpch stream
        assert perfwatch.main(
            ["check", led, slow, "--label", "tpcds"]) == 0
        assert perfwatch.main(
            ["check", led, slow, "--label", "tpch"]) == 1
        capsys.readouterr()

    def test_usage_and_parse_errors(self, tmp_path, capsys):
        led = self._ledger(tmp_path)
        assert perfwatch.main(["check", led]) == 2
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        assert perfwatch.main(["record", led, str(bad)]) == 2
        capsys.readouterr()
        # a torn ledger line is skipped, not fatal
        run = _write(tmp_path, "ok.json", _bench(
            4.0, q1={"engine_s": 1.0}))
        assert perfwatch.main(["record", led, run]) == 0
        with open(led, "a") as f:
            f.write("{torn json\n")
        assert perfwatch.main(["check", led, run]) == 0
        capsys.readouterr()


# ---------------------------------------------------------------------------------
# /debug/slow + srtop slow-queries panel
# ---------------------------------------------------------------------------------

class TestDebugSlowSurfaces:
    def test_render_debug_slow_lists_captures_and_ledger(
            self, fresh_recorder, tmp_path):
        from spark_rapids_tpu.server.ops import render_debug_slow
        cap = _sealed_capture(fresh_recorder, tmp_path)
        recorder.compile_note(0.2, "stmt:hot")
        page = render_debug_slow()
        assert "flight recorder:" in page
        assert cap.capture_id in page
        assert "compile" in page  # the verdict column
        assert "compile ledger:" in page
        assert "stmt:hot" in page
        assert "first_seen=1" in page

    def test_http_route_and_snapshot_section(self, sess,
                                             fresh_recorder, tmp_path):
        import urllib.request

        from spark_rapids_tpu.server import SqlFrontDoor
        cap = _sealed_capture(fresh_recorder, tmp_path)
        door = SqlFrontDoor(sess).start()
        try:
            base = f"http://127.0.0.1:{door.ops_port}"
            with urllib.request.urlopen(base + "/debug/slow",
                                        timeout=5) as r:
                assert r.status == 200
                body = r.read().decode()
            assert cap.capture_id in body
            with urllib.request.urlopen(base + "/snapshot",
                                        timeout=5) as r:
                snap = json.loads(r.read().decode())
            rec = snap["recorder"]
            assert rec["queries"] >= 1
            assert rec["captures"][0]["capture_id"] == cap.capture_id
            assert "compile_ledger" in rec
        finally:
            door.close()

    def test_srtop_slow_queries_panel(self, sess, fresh_recorder,
                                      tmp_path, capsys):
        from spark_rapids_tpu.server import SqlFrontDoor

        import tools.srtop as srtop
        cap = _sealed_capture(fresh_recorder, tmp_path)
        door = SqlFrontDoor(sess).start()
        try:
            rc = srtop.main(["--url",
                             f"http://127.0.0.1:{door.ops_port}",
                             "--once"])
        finally:
            door.close()
        assert rc == 0
        out = capsys.readouterr().out
        assert "recorder:" in out
        assert "slow queries (fingerprint / wall / why / capture):" \
            in out
        assert cap.capture_id in out
        assert "compile" in out


# ---------------------------------------------------------------------------------
# trace_report --xplane: the reduction, on a hand-made event list
# ---------------------------------------------------------------------------------

class TestXplaneReduce:
    DEV, HOST = "/device:TPU:0", "/host:CPU"
    MS = 1_000_000

    def _rows(self):
        ms, dev, host = self.MS, self.DEV, self.HOST
        seg = "jit(agg_grouped)/jit(main)/segmented_reduce/scatter-add"
        srt = "jit(agg_grouped)/jit(main)/groupby_sort/sort"
        body = "jit(join_expand)/jit(main)/while/body/add"
        return [
            # two programs; the id in brackets differs per launch
            (dev, "XLA Modules", "jit_agg_grouped(7)", 0, 40 * ms, ""),
            (dev, "XLA Modules", "jit_agg_grouped(9)", 100 * ms, 20 * ms, ""),
            (dev, "XLA Modules", "jit_join_expand(3)", 60 * ms, 10 * ms, ""),
            # ops: a while holding a nested fusion counts its own time only
            (dev, "XLA Ops", "fusion.1", 0, 30 * ms, seg),
            (dev, "XLA Ops", "sort.2", 30 * ms, 10 * ms, srt),
            (dev, "XLA Ops", "while.3", 60 * ms, 10 * ms,
             "jit(join_expand)/jit(main)/while"),
            (dev, "XLA Ops", "fusion.4", 62 * ms, 4 * ms, body),
            (dev, "XLA Ops", "fusion.5", 100 * ms, 20 * ms, seg),
            # host spans: the 40..60 gap sits under a fetch inside an op
            # pull; the 70..100 gap under planning only
            (host, "t1", "op:SortExec", 35 * ms, 30 * ms, ""),
            (host, "t1", "fetch:blocking", 41 * ms, 18 * ms, ""),
            (host, "t1", "plan:overrides", 72 * ms, 26 * ms, ""),
            (host, "t1", "bench:q3:run", 0, 120 * ms, ""),   # two colons
            (host, "t1", "$profiler.py:91 trace", 0, 120 * ms, ""),
        ]

    def test_programs_scopes_and_gaps(self):
        r = trace_report.reduce_xplane(self._rows(), top=5)
        assert dict(r["device_s_by_program"]) == pytest.approx(
            {"jit_agg_grouped": 0.060, "jit_join_expand": 0.010})
        scopes = dict(r["device_s_by_scope"])
        assert scopes["segmented_reduce"] == pytest.approx(0.050)
        assert scopes["groupby_sort"] == pytest.approx(0.010)
        assert scopes["(none)"] == pytest.approx(0.006)   # the while's own
        assert scopes["body"] == pytest.approx(0.004)
        assert r["busy_s"] == pytest.approx(0.070)
        assert r["span_s"] == pytest.approx(0.120)
        assert [(round(g["seconds"], 3), g["span"])
                for g in r["idle_gaps"]] == [
            (0.030, "plan:overrides"), (0.020, "fetch:blocking")]
        assert r["program_spans"] == 3
        out = trace_report.format_xplane(r)
        assert "jit_agg_grouped" in out and "segmented_reduce" in out

    def test_a_host_only_trace_reads_as_no_device(self):
        rows = [r for r in self._rows() if r[0] == self.HOST]
        r = trace_report.reduce_xplane(rows)
        assert r["busy_s"] == 0 and not r["device_s_by_program"]
        assert "not a device's trace" in trace_report.format_xplane(r)

    def test_scope_of(self):
        assert trace_report.scope_of(
            "jit(f)/jit(main)/segmented_reduce/scatter-add") \
            == "segmented_reduce"
        assert trace_report.scope_of("jit(f)/jit(main)/add") == "(none)"
        assert trace_report.scope_of("") == "(none)"

    def test_main_reads_a_profiler_directory(self, sess, tmp_path, capsys):
        import jax
        df = sess.create_dataframe({"k": np.arange(2000) % 5,
                                    "v": np.arange(2000) * 0.5})
        q = df.group_by("k").agg(F.sum(F.col("v")).alias("s"))
        q.collect()
        with jax.profiler.trace(str(tmp_path)):
            q.collect()
        assert trace_report.main(["--xplane", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        # on the CPU backend there is no device plane, but the engine's
        # spans are in the trace under their vocabulary names
        assert "program span(s) on the host planes" in out
        names = {ev.name for _, _, ev in
                 trace_report.xplane_events(str(tmp_path))}
        assert "plan:overrides" in names and "result:rows" in names
