"""Fleet telemetry (ISSUE 15): live metrics registry, ops endpoint,
cross-rank trace stitching, fleet aggregation, SLO burn tracking.

Covers the acceptance surface: registry semantics + the disabled fast
path, Prometheus/JSON scrape shapes, the QueryStats fold-in, the ops
HTTP endpoints (drain-aware healthz, scrape storm under concurrency),
the typed OPS wire op, exact client<->server counter reconciliation,
heartbeat-piggybacked fleet aggregation surviving a journal-fed
restore, the world=3 stitched Perfetto trace, trace-drop visibility,
SLO burn-rate math, the docs catalog two-way sync, and srtop.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _mini_df(sess, n=4000, seed=3):
    import numpy as np
    rng = np.random.default_rng(seed)
    return sess.create_dataframe({
        "k": rng.integers(0, 16, n),
        "v": rng.random(n).round(4)})


def _mini_query(sess, seed=3):
    return (_mini_df(sess, seed=seed)
            .group_by("k").agg(F.sum(F.col("v")).alias("sv"),
                               F.count_star().alias("c")))


# ---------------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------------

class TestRegistry:
    def setup_method(self):
        telemetry.reset_for_tests()

    def test_counter_gauge_histogram(self):
        telemetry.count("queries_shed_total", reason="queue_full")
        telemetry.count("queries_shed_total", 2, reason="queue_full")
        telemetry.count("queries_shed_total", reason="doomed")
        telemetry.gauge_set("queue_depth", 7)
        telemetry.observe("query_latency_seconds", 0.01, tenant="a")
        telemetry.observe("query_latency_seconds", 100.0, tenant="a")
        snap = telemetry.snapshot()
        assert snap["queries_shed_total"]["reason=queue_full"] == 3
        assert snap["queries_shed_total"]["reason=doomed"] == 1
        assert snap["queue_depth"][""] == 7
        h = snap["query_latency_seconds"]["tenant=a"]
        assert h["count"] == 2
        # 0.01s lands in a low bucket; 100s overflows past every bound
        assert h["buckets"][-1] == 1
        assert abs(h["sum"] - 100.01) < 1e-6

    def test_unregistered_name_raises(self):
        with pytest.raises(KeyError):
            telemetry.count("no_such_metric_total")
        with pytest.raises(KeyError):
            telemetry.gauge_set("queries_shed_total", 1)  # wrong kind

    def test_disabled_is_a_noop(self):
        conf = TpuConf({"spark.rapids.tpu.telemetry.enabled": False})
        telemetry.configure(conf)
        try:
            telemetry.count("queries_shed_total", reason="quota")
            telemetry.observe("query_latency_seconds", 1.0, tenant="x")
            telemetry.slo_observe("x", 1.0, ok=True)
            # even an unregistered name is a silent no-op when off
            telemetry.count("no_such_metric_total")
            assert telemetry.snapshot() == {}
        finally:
            telemetry.configure(TpuConf())
        assert telemetry.enabled()

    def test_prometheus_exposition_shape(self):
        telemetry.count("server_queries_total", 5)
        telemetry.observe("query_latency_seconds", 0.05, tenant="t1")
        text = telemetry.render_prometheus()
        assert "# TYPE srt_server_queries_total counter" in text
        assert "srt_server_queries_total 5" in text
        assert '# TYPE srt_query_latency_seconds histogram' in text
        assert 'le="+Inf"}' in text
        assert 'srt_query_latency_seconds_count{tenant="t1"} 1' in text

    def test_fold_query_stats(self):
        import jax.numpy as jnp

        from spark_rapids_tpu.utils.metrics import QueryStats, fetch
        with QueryStats.scoped():
            fetch(jnp.arange(8))
        snap = telemetry.snapshot()
        assert snap["query_blocking_fetches_total"][""] >= 1
        assert snap["query_fetch_bytes_total"][""] > 0

    def test_nested_scopes_fold_once(self):
        import jax.numpy as jnp

        from spark_rapids_tpu.utils.metrics import QueryStats, fetch
        with QueryStats.scoped():
            with QueryStats.scoped():
                fetch(jnp.arange(4))
        snap = telemetry.snapshot()
        # the inner scope folded outward, the OUTER scope folded to the
        # process aggregate exactly once — no double count
        assert snap["query_blocking_fetches_total"][""] == 1

    def test_catalog_two_way_sync_with_docs(self):
        """docs/observability.md's metrics table is generated from
        telemetry.METRICS (the configs.md contract): drift fails."""
        with open(os.path.join(REPO, "docs", "observability.md")) as f:
            doc = f.read()
        begin = doc.index("<!-- METRICS:BEGIN")
        begin = doc.index("\n", begin) + 1
        end = doc.index("<!-- METRICS:END -->")
        assert doc[begin:end].strip() == telemetry.catalog_md().strip(), \
            "docs/observability.md metrics catalog is stale — " \
            "regenerate it from telemetry.catalog_md()"

    def test_every_metric_declared_once(self):
        names = [m[0] for m in telemetry.METRICS]
        assert len(names) == len(set(names))
        kinds = {m[1] for m in telemetry.METRICS}
        assert kinds <= {"counter", "gauge", "histogram"}


class TestWireMerge:
    def setup_method(self):
        telemetry.reset_for_tests()

    def test_delta_and_replacement_merge(self):
        telemetry.count("server_queries_total", 3)
        d1 = telemetry.wire_delta({})
        assert d1["server_queries_total|"] == 3
        # nothing changed -> empty delta
        assert telemetry.wire_delta(d1) == {}
        telemetry.count("server_queries_total", 2)
        d2 = telemetry.wire_delta(d1)
        assert d2 == {"server_queries_total|": 5}  # CUMULATIVE value
        ranks = {}
        telemetry.merge_rank(ranks, 1, d1)
        telemetry.merge_rank(ranks, 1, d1)  # duplicated delivery
        telemetry.merge_rank(ranks, 1, d2)
        telemetry.merge_rank(ranks, 2, {"server_queries_total|": 7})
        roll = telemetry.rollup(ranks)
        # replacement per (rank, series): dup delivery cannot double
        assert roll["server_queries_total|"] == 12

    def test_gauges_stay_local(self):
        telemetry.gauge_set("queue_depth", 9)
        assert "queue_depth|" not in telemetry.wire_delta({})

    def test_fleet_view_roundtrip(self):
        view = {"version": 4, "ranks": {"0": {"x|": 1}}, "rollup": {}}
        telemetry.set_fleet(view)
        assert telemetry.fleet()["version"] == 4
        telemetry.set_fleet({})
        assert telemetry.fleet() == {}


class TestSlo:
    def setup_method(self):
        telemetry.reset_for_tests()

    def test_burn_rate_math(self):
        conf = TpuConf({
            "spark.rapids.tpu.server.slo.latencyMs": 100.0,
            "spark.rapids.tpu.server.slo.target": 0.9,
            "spark.rapids.tpu.server.slo.windows": "60"})
        telemetry.configure(conf)
        try:
            for _ in range(8):
                telemetry.slo_observe("t1", 0.01, ok=True)   # good
            telemetry.slo_observe("t1", 0.5, ok=True)        # late
            telemetry.slo_observe("t1", 0.01, ok=False)      # failed
            snap = telemetry.slo_snapshot()
            w = snap["tenants"]["t1"]["60s"]
            assert w["total"] == 10 and w["bad"] == 2
            # 20% error rate / 10% budget = burn 2.0
            assert abs(w["burn_rate"] - 2.0) < 1e-6
            # the gauge exports at scrape time
            reg = telemetry.snapshot()
            assert reg["slo_burn_rate"]["tenant=t1,window=60s"] == 2.0
            assert reg["slo_good_total"]["tenant=t1"] == 8
            assert reg["slo_bad_total"]["tenant=t1"] == 2
        finally:
            telemetry.configure(TpuConf())


# ---------------------------------------------------------------------------------
# ops endpoint + OPS wire op
# ---------------------------------------------------------------------------------

@pytest.fixture()
def door(session):
    from spark_rapids_tpu.server import SqlFrontDoor
    telemetry.reset_for_tests()
    d = SqlFrontDoor(session).start()
    d.register_table("mini", lambda: _mini_df(session))
    yield d
    d.close()


SPEC_SCAN = {"table": "mini",
             "ops": [{"op": "filter",
                      "expr": [">=", ["col", "v"],
                               ["param", 0, "double"]]}]}


class TestOpsEndpoint:
    def test_http_surfaces(self, door):
        base = f"http://127.0.0.1:{door.ops_port}"
        code, text = _get(base + "/metrics")
        assert code == 200
        assert "# TYPE srt_ops_scrapes_total counter" in text
        code, text = _get(base + "/healthz")
        assert code == 200
        h = json.loads(text)
        assert h["status"] == "ok" and h["serving"]
        code, text = _get(base + "/snapshot")
        snap = json.loads(text)
        for key in ("health", "server", "scheduler", "prepared",
                    "quotas", "cache", "telemetry", "slo", "fleet"):
            assert key in snap, key
        assert "admission" in snap["scheduler"]
        assert "breaker" in snap["scheduler"]
        # 404 for anything else
        with pytest.raises(urllib.error.HTTPError):
            _get(base + "/nope")

    def test_ops_wire_op_and_drain_awareness(self, door, session):
        from spark_rapids_tpu.server import WireClient
        c = WireClient("127.0.0.1", door.port, tenant="ops")
        try:
            snap = c.ops()
            assert snap["health"]["serving"]
            door.begin_drain(siblings=[])
            # healthz turns 503 the moment the door drains...
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://127.0.0.1:{door.ops_port}/healthz")
            assert ei.value.code == 503
            assert json.loads(ei.value.read().decode())["draining"]
            # ...but the scrape surfaces keep answering: /metrics over
            # HTTP and the OPS op on the established connection
            code, _text = _get(
                f"http://127.0.0.1:{door.ops_port}/metrics")
            assert code == 200
            snap = c.ops()
            assert snap["health"]["draining"]
            assert not snap["health"]["serving"]
        finally:
            with door._lock:
                door._draining = False
            c.close()

    def test_scrape_storm_never_blocks_queries(self, door, session):
        """Satellite: parallel /metrics + /snapshot readers during a
        scheduler burst — zero scrape failures, every query completes,
        nothing leaks."""
        base = f"http://127.0.0.1:{door.ops_port}"
        stop = threading.Event()
        failures = []
        scrapes = [0]

        def scraper():
            while not stop.is_set():
                try:
                    _get(base + "/metrics")
                    _get(base + "/snapshot")
                    scrapes[0] += 1
                except OSError as e:  # pragma: no cover
                    failures.append(repr(e))

        ts = [threading.Thread(target=scraper, daemon=True)
              for _ in range(4)]
        for t in ts:
            t.start()
        handles = [session.submit(_mini_query(session, seed=i),
                                  label=f"storm-{i}")
                   for i in range(8)]
        for h in handles:
            h.result(timeout=120)
        time.sleep(0.2)
        stop.set()
        for t in ts:
            t.join(timeout=5)
        assert not failures, failures
        assert scrapes[0] > 0
        from spark_rapids_tpu.memory.spill import get_catalog
        get_catalog().assert_no_leaks()

    def test_counters_reconcile_exactly(self, door, session):
        """The in-test observability differential: scrape deltas over a
        known wire workload equal client-observed truth exactly —
        successes, stream bytes, and typed error frames by code."""
        from spark_rapids_tpu.server import WireClient, WireError
        base = f"http://127.0.0.1:{door.ops_port}"
        tm0 = json.loads(_get(base + "/snapshot")[1])["telemetry"]
        c = WireClient("127.0.0.1", door.port, tenant="recon")
        wire_bytes = 0
        n_ok = 6
        for i in range(n_ok):
            rs = c.query(SPEC_SCAN, params=[i / 10.0])
            assert rs.rows()
            wire_bytes += rs.wire_bytes
        for _ in range(2):  # typed client mistakes, counted both sides
            with pytest.raises(WireError) as ei:
                c.query({"table": "mini", "ops": [{"op": "bogus"}]})
            assert ei.value.code == "BAD_REQUEST"
        c.close()
        tm1 = json.loads(_get(base + "/snapshot")[1])["telemetry"]

        def delta(metric, label=""):
            a = (tm0.get(metric) or {}).get(label, 0)
            b = (tm1.get(metric) or {}).get(label, 0)
            return b - a

        assert delta("server_queries_streamed_total") == n_ok
        assert delta("server_queries_total") == n_ok
        assert delta("server_stream_bytes_total") == wire_bytes
        assert delta("server_wire_errors_total", "code=BAD_REQUEST") == 2
        assert c.error_frames == {"BAD_REQUEST": 2}

    def test_scheduler_feed_and_shed_taxonomy(self, door, session):
        from spark_rapids_tpu.service.scheduler import QueryRejected
        telemetry.reset_for_tests()
        sched = session.scheduler()
        h = session.submit(_mini_query(session), tenant="feed",
                           label="feed-1")
        h.result(timeout=120)
        snap = telemetry.snapshot()
        assert snap["queries_submitted_total"]["tenant=feed"] == 1
        assert snap["queries_completed_total"][
            "status=done,tenant=feed"] == 1
        assert snap["query_latency_seconds"]["tenant=feed"]["count"] == 1
        # a typed shed lands in the taxonomy counter
        sched.drain(deadline_s=0.5)
        try:
            with pytest.raises(QueryRejected):
                session.submit(_mini_query(session), label="feed-2")
        finally:
            sched.resume()
        snap = telemetry.snapshot()
        assert snap["queries_shed_total"]["reason=draining"] == 1

    def test_srtop_once(self, door, session, capsys):
        session.submit(_mini_query(session), tenant="topt",
                       label="top-1").result(timeout=120)
        import tools.srtop as srtop
        rc = srtop.main(["--url",
                         f"http://127.0.0.1:{door.ops_port}",
                         "--once"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "srtop — status=ok" in out
        assert "server:" in out and "containment:" in out


# ---------------------------------------------------------------------------------
# fleet aggregation over DCN heartbeats (+ journal survival)
# ---------------------------------------------------------------------------------

def _make_group(world, **kw):
    from spark_rapids_tpu.parallel.dcn import Coordinator, ProcessGroup
    coord = Coordinator(world, **kw.pop("coordinator_kw", {}))
    pgs = [None] * world
    errs = []

    def mk(r):
        try:
            pgs[r] = ProcessGroup(r, world, ("127.0.0.1", coord.port),
                                  coordinator=coord if r == 0 else None,
                                  **kw)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=mk, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errs, errs
    return coord, pgs


class TestFleetAggregation:
    def test_heartbeat_piggyback_and_rollup(self):
        telemetry.reset_for_tests()
        coord, pgs = _make_group(3, heartbeat_interval=0.05)
        try:
            telemetry.count("dcn_frames_deduped_total", 5)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with coord._cv:
                    ranks = dict(coord._tm_ranks)
                if len(ranks) == 3 and all(
                        s.get("dcn_frames_deduped_total|")
                        for s in ranks.values()):
                    break
                time.sleep(0.05)
            assert len(ranks) == 3, ranks.keys()
            roll = telemetry.rollup(ranks)
            # thread ranks share one process registry, so each rank
            # ships the same cumulative value — the rollup proves the
            # per-rank merge + summation plumbing
            assert roll["dcn_frames_deduped_total|"] == 15
            # the fleet view lands back on ranks via heartbeat replies;
            # wait for a version that has absorbed all three ranks
            deadline = time.monotonic() + 10
            fleet = {}
            while time.monotonic() < deadline:
                fleet = telemetry.fleet()
                if len(fleet.get("ranks") or {}) == 3 and fleet.get(
                        "rollup", {}).get(
                        "dcn_frames_deduped_total|") == 15:
                    break
                time.sleep(0.05)
            assert fleet and fleet["version"] >= 1
            assert set(fleet["ranks"]) == {"0", "1", "2"}
            assert fleet["rollup"]["dcn_frames_deduped_total|"] == 15
        finally:
            for pg in pgs:
                pg.close()
            telemetry.reset_for_tests()

    def test_rollup_survives_journal_restore(self):
        """The journal-fed standby restores the per-rank metric views:
        fleet aggregates survive a coordinator failover instead of
        resetting to zero."""
        from spark_rapids_tpu.parallel.dcn import Coordinator
        telemetry.reset_for_tests()
        coord, pgs = _make_group(2, heartbeat_interval=0.05)
        try:
            telemetry.count("server_queries_total", 9)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with coord._cv:
                    ok = len(coord._tm_ranks) == 2 and all(
                        s.get("server_queries_total|")
                        for s in coord._tm_ranks.values())
                if ok:
                    break
                time.sleep(0.05)
            with coord._cv:
                journal = coord._journal_locked()
            assert journal["tm_ranks"], "journal carries no tm view"
            successor = Coordinator(2, listen=False,
                                    heartbeat_timeout=1.0)
            try:
                successor.restore(journal)
                with successor._cv:
                    restored = dict(successor._tm_ranks)
                    version = successor._tm_version
                assert set(restored) == {0, 1}
                assert version == journal["tm_version"]
                assert telemetry.rollup(restored)[
                    "server_queries_total|"] == 18
            finally:
                successor.close()
        finally:
            for pg in pgs:
                pg.close()
            telemetry.reset_for_tests()


# ---------------------------------------------------------------------------------
# cross-rank trace stitching (THE world=3 acceptance test)
# ---------------------------------------------------------------------------------

class TestStitchedTrace:
    def test_world3_distributed_query_stitches_to_one_tree(
            self, tmp_path, session):
        """A world=3 distributed query produces ONE stitched Perfetto
        trace with spans from all 3 ranks parented under the query
        root, fetch spans attributable to their owning rank."""
        import pyarrow as pa

        from spark_rapids_tpu.parallel.dcn import DcnShuffle
        from spark_rapids_tpu.utils import tracing
        import tools.trace_report as trace_report
        trace_dir = str(tmp_path)
        TpuConf.set_session("spark.rapids.tpu.sql.trace.dir", trace_dir)
        coord, pgs = _make_group(3, heartbeat_interval=0.2)
        world, n_parts = 3, 3
        try:
            shuffles = [DcnShuffle(pg, n_parts,
                                   str(tmp_path / f"r{pg.rank}"))
                        for pg in pgs]
            for rank, sh in enumerate(shuffles):
                for p in range(n_parts):
                    sh.write_partition(p, pa.table(
                        {"r": [rank] * 4, "p": [p] * 4}))
            ts = [threading.Thread(target=sh.commit) for sh in shuffles]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            # rank 0 runs the TRACED query: its fetches to ranks 1 and
            # 2 carry the trace id, so their serve-side work lands in
            # per-rank shards beside the trace
            with tracing.query_trace("stitch-q") as tr:
                for p in range(n_parts):
                    for peer in (1, 2):
                        payload = pgs[0].fetch(peer, shuffles[peer].id,
                                               p)
                        assert payload
            path = os.path.join(trace_dir, "stitch-q.trace.json")
            tr.write(path)
            # the requester's own trace carries its fetch spans
            fetch_spans = [e for e in tr.events if e[1] == "dcn:fetch"]
            assert len(fetch_spans) == n_parts * 2
            # shards exist for BOTH serving ranks
            shard_files = tracing.shard_paths(tr.trace_id, trace_dir)
            assert len(shard_files) == 2, shard_files
            out = trace_report.stitch_file(path)
            merged = trace_report.load(out)
            roots = merged["spanTree"]
            assert len(roots) == 1, "ONE tree, parented at the query root"
            root = roots[0]
            by_name = {c["name"]: c for c in root["children"]}
            assert "rank-1" in by_name and "rank-2" in by_name
            for rank in (1, 2):
                node = by_name[f"rank-{rank}"]
                assert node["metrics"]["spans"] == n_parts
                for child in node["children"]:
                    assert child["name"] == "dcn:serve_fetch"
            # timeline events: pid 1 (query) + pids 101/102 (ranks)
            pids = {e.get("pid") for e in merged["traceEvents"]
                    if e.get("ph") == "X"}
            assert {1, 101, 102} <= pids
            serve_evs = [e for e in merged["traceEvents"]
                         if e.get("name") == "dcn:serve_fetch"]
            assert {e["args"]["rank"] for e in serve_evs} == {1, 2}
            # the report renders per-rank attribution
            rendered = trace_report.format_stitched(merged)
            assert "rank 1: 3 remote span(s)" in rendered
            for sh in shuffles:
                sh.local.close()
        finally:
            TpuConf.unset_session("spark.rapids.tpu.sql.trace.dir")
            for pg in pgs:
                pg.close()

    def test_untraced_fetch_writes_no_shard(self, tmp_path):
        import pyarrow as pa

        from spark_rapids_tpu.parallel.dcn import DcnShuffle
        from spark_rapids_tpu.utils import tracing
        TpuConf.set_session("spark.rapids.tpu.sql.trace.dir",
                            str(tmp_path))
        coord, pgs = _make_group(2, heartbeat_interval=0.2)
        try:
            shuffles = [DcnShuffle(pg, 2, str(tmp_path / f"r{pg.rank}"))
                        for pg in pgs]
            for sh in shuffles:
                for p in range(2):
                    sh.write_partition(p, pa.table({"x": [1, 2]}))
            ts = [threading.Thread(target=sh.commit) for sh in shuffles]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            assert tracing.trace_context() is None
            assert pgs[0].fetch(1, shuffles[1].id, 0)
            import glob
            assert not glob.glob(str(tmp_path / "*.shard.jsonl"))
            for sh in shuffles:
                sh.local.close()
        finally:
            TpuConf.unset_session("spark.rapids.tpu.sql.trace.dir")
            for pg in pgs:
                pg.close()


# ---------------------------------------------------------------------------------
# trace drop accounting + overhead guard
# ---------------------------------------------------------------------------------

class TestDropAccountingAndOverhead:
    def test_trace_truncation_is_counted_and_visible(self, session):
        telemetry.reset_for_tests()
        session.conf.set("spark.rapids.tpu.sql.trace.enabled", True)
        session.conf.set("spark.rapids.tpu.sql.trace.maxEvents", 5)
        try:
            _mini_query(session).collect()
        finally:
            session.conf.unset("spark.rapids.tpu.sql.trace.enabled")
            session.conf.unset("spark.rapids.tpu.sql.trace.maxEvents")
        tr = session.last_trace()
        assert tr.dropped > 0
        snap = telemetry.snapshot()
        assert snap["trace_events_dropped_total"][""] == tr.dropped
        # the report header shouts it
        import tools.trace_report as trace_report
        a = trace_report.analyze(tr.to_chrome())
        assert "TRUNCATED" in trace_report.format_report(a)

    @pytest.mark.parametrize("iters", [4])
    def test_disabled_telemetry_costs_nothing_measurable(self, session,
                                                         iters):
        """Guarded like the tracing <2.5% bound from PR 2: the serial
        mini workload with telemetry DISABLED must not be measurably
        slower than enabled is allowed to be — the formal <=2% bound is
        bench-measured (SRT_BENCH_TELEMETRY=1); this guards the fast
        path structurally with generous CI headroom."""
        q = _mini_query(session)
        q.collect()  # compile warmup

        def timed(enabled: bool) -> float:
            session.conf.set("spark.rapids.tpu.telemetry.enabled",
                             enabled)
            try:
                best = float("inf")
                for _ in range(iters):
                    t0 = time.perf_counter()
                    q.collect()
                    best = min(best, time.perf_counter() - t0)
                return best
            finally:
                session.conf.unset(
                    "spark.rapids.tpu.telemetry.enabled")

        on = timed(True)
        off = timed(False)
        assert off < on * 1.5 + 0.05, (on, off)
        q.collect()  # the next ExecContext re-arms from the default
        assert telemetry.enabled()


class TestProtocolSurface:
    def test_ops_frame_types_registered(self):
        from spark_rapids_tpu.server import protocol as P
        assert P.REQ_OPS in P._REQUEST_TYPES
        assert P.RSP_OPS in P._RESPONSE_TYPES
