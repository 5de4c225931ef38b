"""Performance flight recorder (utils/recorder.py): tail-sampled
retention policy, ring bounds under capture storms, the offer/outcome
seal handshake, the compile ledger's trigger taxonomy + storm
detector, and root-cause attribution differentials (forced cold
compile / fetch stall / saturated queue each name the right term).
"""

import json
import os
import time

import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.service.cancel import QueryControl, scope
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils import recorder, telemetry
from spark_rapids_tpu.utils.tracing import QueryTrace

REC_KEY = "spark.rapids.tpu.recorder.enabled"


@pytest.fixture(autouse=True)
def _fresh():
    recorder.reset_for_tests()
    telemetry.reset_for_tests()
    yield
    recorder.reset_for_tests()
    telemetry.reset_for_tests()


def _conf(**over):
    """A minimal mapping standing in for TpuConf at the recorder's
    four keys."""
    c = {
        "spark.rapids.tpu.recorder.enabled": True,
        "spark.rapids.tpu.recorder.maxQueries": 48,
        "spark.rapids.tpu.recorder.maxBytes": 32 << 20,
        "spark.rapids.tpu.sql.trace.dir": "",
    }
    c.update(over)
    return c


def _trace(label="q[unit]", status="ok", wall=0.1, attrs=None,
           events=()):
    """A synthetic finished QueryTrace (events appended raw so the
    fixture controls timestamps exactly)."""
    tr = QueryTrace(label)
    for name, cat, ts, dur, tid in events:
        tr.events.append((None, name, cat, ts, dur, tid, None))
    tr.attrs.update(attrs or {})
    tr.t_end = tr.t0 + wall
    tr.status = status
    return tr


def _ctr(name, label=None):
    series = telemetry.snapshot().get(name) or {}
    if label is None:
        return sum(v for v in series.values()
                   if isinstance(v, (int, float)))
    return series.get(label, 0)


# ---------------------------------------------------------------------------------
# term decomposition + judging
# ---------------------------------------------------------------------------------

class TestDecompose:
    def test_account_terms_come_from_the_stats_and_close(self):
        # the snapshot's host-time account is authoritative: operator
        # events are not re-added (they nest the waits), and the nine
        # account terms sum to the wall
        acct = {"acct_plan_s": 0.01, "acct_admit_s": 0.02,
                "acct_compile_s": 0.3, "acct_h2d_wait_s": 0.1,
                "acct_fetch_wait_s": 0.05, "acct_dispatch_s": 0.2,
                "acct_result_s": 0.03, "acct_host_exec_s": 0.25,
                "acct_unattributed_s": 0.04}
        attrs = dict(acct, query_wall_s=1.0, queue_wait_s=0.2,
                     # the all-thread sums are NOT the account's terms
                     h2d_wait_s=1.9, fetch_wait_s=0.8, compile_s=0.3)
        events = [
            ("op:filter", "operator", 0.0, 1.0, 1),
            ("op:agg", "operator", 0.5, 1.0, 1),
            ("dcn:fetch", "shuffle", 0.0, 0.4, 3),
            ("spill:restore", "memory", 0.0, 0.25, 3),
            ("server:stream", "server", 0.0, 0.15, 4),
        ]
        t = recorder.decompose(attrs, events)
        assert t["queue_wait"] == pytest.approx(0.2)
        assert t["h2d"] == pytest.approx(0.1)
        assert t["fetch_wait"] == pytest.approx(0.05)
        assert t["dispatch"] == pytest.approx(0.2)
        assert t["shuffle"] == pytest.approx(0.4)
        assert t["spill"] == pytest.approx(0.25)
        assert t["stream_spool"] == pytest.approx(0.15)
        assert set(t) == set(recorder.TERMS)
        account = [term for term, _, _ in recorder._ACCOUNT_FIELDS]
        assert sum(t[k] for k in account) == pytest.approx(1.0)

    def test_a_dump_without_the_account_falls_back_to_the_stats(self):
        attrs = {"queue_wait_s": 0.2, "compile_s": 0.3,
                 "h2d_wait_s": 0.1, "fetch_wait_s": 0.05}
        t = recorder.decompose(attrs, [("op:agg", "operator", 0.0, 1.0, 1)])
        assert t["compile"] == pytest.approx(0.3)
        assert t["h2d"] == pytest.approx(0.1)
        assert t["fetch_wait"] == pytest.approx(0.05)
        assert t["dispatch"] == 0.0  # no union of nested operator spans

    def test_garbage_attrs_are_zero(self):
        t = recorder.decompose({"compile_s": "not-a-number",
                                "queue_wait_s": -3.0}, [])
        assert t["compile"] == 0.0
        assert t["queue_wait"] == 0.0

    def test_chrome_round_trip_matches(self):
        """decompose_chrome on the dumped doc equals decompose on the
        live trace — explain_slow recomputes identically offline."""
        attrs = {"queue_wait_s": 0.2, "compile_s": 0.3}
        events = [("op:agg", "operator", 0.0, 1.0, 1),
                  ("dcn:fetch", "shuffle", 0.1, 0.4, 2)]
        tr = _trace(attrs=attrs, events=events)
        live = recorder.decompose(attrs, events)
        off = recorder.decompose_chrome(tr.to_chrome())
        for term in recorder.TERMS:
            assert off[term] == pytest.approx(live[term], abs=1e-5)


class TestJudge:
    def test_young_baseline_never_judges(self):
        verdict, excess = recorder.judge(
            {"compile": 10.0}, {"compile": 0.01},
            recorder.MIN_BASELINE_SAMPLES - 1)
        assert verdict is None and excess == {}

    def test_dominant_term_is_largest_excess(self):
        terms = {"compile": 1.0, "fetch_wait": 0.4}
        base = {"compile": 0.1, "fetch_wait": 0.1}
        verdict, excess = recorder.judge(terms, base, 5)
        assert verdict == "compile"
        assert excess["compile"] == pytest.approx(0.9)
        assert excess["fetch_wait"] == pytest.approx(0.3)

    def test_absolute_floor_filters_jitter(self):
        # 40ms over a zero baseline is under the 50ms floor
        verdict, _ = recorder.judge({"compile": 0.04}, {}, 5)
        assert verdict is None

    def test_ratio_guard_filters_small_multiples(self):
        # 1.5x a 1s baseline is under the 2x ratio
        verdict, _ = recorder.judge({"compile": 1.5}, {"compile": 1.0},
                                    5)
        assert verdict is None


# ---------------------------------------------------------------------------------
# retention policy
# ---------------------------------------------------------------------------------

class TestRetention:
    def test_first_seen_is_kept(self):
        rec = recorder.recorder()
        assert rec.seal(_trace(), None, 0.01, True, False) \
            == "first_seen"
        assert _ctr("recorder_captures_total",
                    "reason=first_seen") == 1

    def test_slo_violation_is_kept(self):
        rec = recorder.recorder()
        rec.seal(_trace(), None, 0.01, True, False)  # baseline entry
        assert rec.seal(_trace(), None, 0.01, False, True) == "slo"
        # latency over the SLO with ok=True is the other slo leg
        slow = telemetry.slo_latency_s() * 10
        assert rec.seal(_trace(), None, slow, True, True) == "slo"
        assert _ctr("recorder_captures_total", "reason=slo") == 2

    def test_non_ok_outcome_is_kept(self):
        rec = recorder.recorder()
        rec.seal(_trace(), None, 0.01, True, False)
        for status in ("faulted", "degraded", "cancelled", "deadline",
                       "resubmitted", "error"):
            assert rec.seal(_trace(status=status), None, None, False,
                            False) in ("outcome", "slo")
        # slo outranks outcome when both hold; with slo_eligible=False
        # the non-ok status still retains as 'outcome'
        assert rec.seal(_trace(status="faulted"), None, None, True,
                        False) == "outcome"

    def test_top_k_kept_boring_median_dropped(self):
        rec = recorder.recorder()
        walls = [1.0, 0.9, 0.8]  # first_seen, then top-k fills
        reasons = [rec.seal(_trace(wall=w), None, 0.01, True, False)
                   for w in walls]
        assert reasons == ["first_seen", "top_k", "top_k"]
        # the boring median: not slower than the k-th slowest
        assert rec.seal(_trace(wall=0.01), None, 0.01, True,
                        False) is None
        assert _ctr("recorder_dropped_total", "reason=boring") == 1
        # a new tail entry re-qualifies
        assert rec.seal(_trace(wall=2.0), None, 0.01, True,
                        False) == "top_k"
        snap = rec.snapshot()
        assert snap["dropped_boring"] == 1
        assert snap["captures_by_reason"]["top_k"] == 3

    def test_snapshot_shape(self):
        rec = recorder.recorder()
        rec.seal(_trace(), None, 0.01, True, False)
        snap = recorder.snapshot()
        for key in ("enabled", "queries", "bytes", "max_queries",
                    "max_bytes", "sealed", "dropped_boring", "evicted",
                    "missed", "pending_seals", "captures_by_reason",
                    "captures", "compile_ledger"):
            assert key in snap, key
        cap = snap["captures"][0]
        for key in ("capture_id", "label", "fingerprint", "reason",
                    "status", "wall_ms", "verdict", "terms_ms",
                    "path"):
            assert key in cap, key


# ---------------------------------------------------------------------------------
# ring bounds (capture storms stay bounded)
# ---------------------------------------------------------------------------------

class TestRingBounds:
    def test_max_queries_evicts_oldest(self):
        rec = recorder.recorder()
        rec.configure(_conf(**{
            "spark.rapids.tpu.recorder.maxQueries": 2}))
        for i in range(5):
            # distinct labels -> distinct fingerprints -> first_seen
            rec.seal(_trace(label=f"q[l{i}]"), None, 0.01, True, False)
        snap = rec.snapshot()
        assert snap["queries"] == 2
        assert snap["evicted"] == 3
        assert _ctr("recorder_dropped_total", "reason=evicted") == 3
        # oldest-first: the survivors are the two newest
        labels = [c["label"] for c in snap["captures"]]
        assert labels == ["q[l4]", "q[l3]"]

    def test_max_bytes_bounds_a_capture_storm(self):
        rec = recorder.recorder()
        max_b = 4000
        rec.configure(_conf(**{
            "spark.rapids.tpu.recorder.maxBytes": max_b}))
        for i in range(20):
            rec.seal(_trace(label=f"q[s{i}]"), None, 0.01, True, False)
            assert rec.snapshot()["bytes"] <= max_b
        snap = rec.snapshot()
        assert snap["queries"] >= 1
        assert snap["evicted"] > 0

    def test_newest_capture_survives_even_alone_over_budget(self):
        rec = recorder.recorder()
        rec.configure(_conf(**{
            "spark.rapids.tpu.recorder.maxBytes": 1}))
        events = [(f"op:{i}", "operator", 0.0, 0.1, 1)
                  for i in range(50)]
        rec.seal(_trace(events=events), None, 0.01, True, False)
        snap = rec.snapshot()
        assert snap["queries"] == 1  # never evict down to empty
        assert snap["bytes"] > 1

    def test_reconfigure_shrink_evicts_immediately(self):
        rec = recorder.recorder()
        for i in range(6):
            rec.seal(_trace(label=f"q[r{i}]"), None, 0.01, True, False)
        assert rec.snapshot()["queries"] == 6
        rec.configure(_conf(**{
            "spark.rapids.tpu.recorder.maxQueries": 2}))
        assert rec.snapshot()["queries"] == 2


# ---------------------------------------------------------------------------------
# the offer/outcome seal handshake
# ---------------------------------------------------------------------------------

def _ctl(label="hs", fingerprint="stmt:abc"):
    ctl = QueryControl(label=label)
    ctl.enqueued_t = 1.0  # marks it scheduler-managed
    ctl.fingerprint = fingerprint
    return ctl


class TestSealHandshake:
    def test_outcome_then_offer(self):
        ctl = _ctl()
        recorder.outcome(ctl, 0.02, ok=True)
        assert recorder.pending_seals() == 1
        with scope(ctl):
            recorder.offer(_trace(), _conf())
        assert recorder.pending_seals() == 0
        snap = recorder.recorder().snapshot()
        assert snap["sealed"] == 1
        assert snap["captures"][0]["fingerprint"] == "stmt:abc"

    def test_offer_then_outcome(self):
        ctl = _ctl()
        with scope(ctl):
            recorder.offer(_trace(), _conf())
        # streaming may hold the trace open past scheduler completion:
        # nothing sealed yet
        assert recorder.pending_seals() == 1
        assert recorder.recorder().snapshot()["sealed"] == 0
        recorder.outcome(ctl, 0.02, ok=True)
        assert recorder.pending_seals() == 0
        assert recorder.recorder().snapshot()["sealed"] == 1

    def test_double_outcome_is_a_guarded_noop(self):
        ctl = _ctl()
        with scope(ctl):
            recorder.offer(_trace(), _conf())
        recorder.outcome(ctl, 0.02, ok=True)
        recorder.outcome(ctl, 0.02, ok=False)  # late zombie unwind
        snap = recorder.recorder().snapshot()
        assert snap["sealed"] == 1
        assert snap["captures_by_reason"].get("outcome") is None

    def test_direct_session_query_seals_immediately(self):
        # no control scope: seals at offer, never SLO-eligible (an
        # over-SLO wall stays first_seen, not a phantom slo capture)
        recorder.offer(_trace(wall=telemetry.slo_latency_s() * 10),
                       _conf())
        snap = recorder.recorder().snapshot()
        assert snap["sealed"] == 1
        assert snap["captures"][0]["reason"] == "first_seen"
        assert snap["captures"][0]["fingerprint"].startswith("anon:")

    def test_disabled_recorder_counts_slo_misses(self):
        recorder.configure(_conf(**{REC_KEY: False}))
        recorder.outcome(_ctl(), None, ok=False)  # slo-bad, no capture
        recorder.outcome(_ctl(), 0.001, ok=True)  # slo-good: no miss
        assert _ctr("recorder_missed_total") == 1
        assert recorder.recorder().snapshot()["missed"] == 1

    def test_slo_reconciliation_equation(self):
        """delta(slo_bad) == delta(captures{slo}) + delta(missed) —
        the loadgen drain audit's exact reconciliation, across
        enabled and disabled recorder states."""
        rec = recorder.recorder()
        for i, (lat, ok) in enumerate([(0.01, True), (None, False),
                                       (99.0, True), (0.02, True)]):
            ctl = _ctl(label=f"sr{i}", fingerprint=f"stmt:{i}")
            telemetry.slo_observe("t", lat if lat is not None else 0.0,
                                  ok=ok)
            recorder.outcome(ctl, lat, ok=ok)
            with scope(ctl):
                recorder.offer(_trace(label=f"q[sr{i}]"), _conf())
        recorder.configure(_conf(**{REC_KEY: False}))
        telemetry.slo_observe("t", 99.0, ok=False)
        recorder.outcome(_ctl(label="srx"), 99.0, ok=False)
        bad = _ctr("slo_bad_total")
        caps = _ctr("recorder_captures_total", "reason=slo")
        missed = _ctr("recorder_missed_total")
        assert bad == 3  # (None, not-ok), (99s), (disabled not-ok)
        assert bad == caps + missed
        assert missed == 1
        assert recorder.pending_seals() == 0


# ---------------------------------------------------------------------------------
# compile ledger
# ---------------------------------------------------------------------------------

class TestCompileLedger:
    def test_trigger_taxonomy(self):
        led = recorder.compile_ledger()
        assert led.note(0.1, None) == "unattributed"
        assert led.note(0.1, "fp1") == "first_seen"
        assert led.note(0.1, "fp1") == "shape_change"
        led.note_evicted("fp1")
        assert led.note(0.1, "fp1") == "cache_evict"
        assert led.note(0.1, "fp1") == "shape_change"  # marker consumed
        led.prime(["fp2"])
        assert led.note(0.1, "fp2") == "post_restart"
        for trig in ("unattributed", "first_seen", "shape_change",
                     "cache_evict", "post_restart"):
            assert _ctr("compiles_by_trigger_total",
                        f"trigger={trig}") >= 1, trig
        snap = led.snapshot()
        assert snap["compiles"] == 6
        assert snap["fingerprints"] == 3  # <anon>, fp1, fp2
        top = {e["fingerprint"]: e for e in snap["top"]}
        assert top["fp1"]["triggers"] == {"first_seen": 1,
                                          "shape_change": 2,
                                          "cache_evict": 1}

    def test_storm_trips_and_clears(self):
        led = recorder.compile_ledger()
        led.note(0.01, "fpS")  # first_seen: outside the storm window
        for _ in range(recorder.STORM_THRESHOLD - 1):
            led.note(0.01, "fpS")
        assert not led.storming
        led.note(0.01, "fpS")  # the threshold-th recompile
        assert led.storming
        assert _ctr("compile_storm_active", "") == 1.0
        assert led.snapshot()["recent_recompiles"] \
            == recorder.STORM_THRESHOLD
        # age the window out (rewrite the bookkeeping timestamps
        # rather than sleeping STORM_WINDOW_S in a unit test)
        now = time.monotonic()
        with led._lock:
            old = [now - recorder.STORM_WINDOW_S - 1.0
                   for _ in led._recent]
            led._recent.clear()
            led._recent.extend(old)
        led.note(0.01, "fpS")
        assert not led.storming
        assert _ctr("compile_storm_active", "") == 0.0

    def test_unattributed_compiles_never_storm(self):
        """A session warm-up compiles many distinct programs under no
        statement identity — that must not read as a recompile storm
        (the bug the 'unattributed' bucket exists for)."""
        led = recorder.compile_ledger()
        for _ in range(recorder.STORM_THRESHOLD * 3):
            led.note(0.01, None)
        assert not led.storming
        assert led.snapshot()["recent_recompiles"] == 0

    def test_first_seen_warmup_never_storms(self):
        led = recorder.compile_ledger()
        for i in range(recorder.STORM_THRESHOLD * 3):
            led.note(0.01, f"fp{i}")
        assert not led.storming

    def test_compile_note_never_raises(self):
        recorder.compile_note(object(), object())  # garbage in
        recorder.compile_note(0.1, "fpN")  # still alive


# ---------------------------------------------------------------------------------
# root-cause attribution differentials
# ---------------------------------------------------------------------------------

def _baseline(rec, label, n=3):
    """Warm a fingerprint's EWMA baseline with n healthy seals."""
    for _ in range(n):
        rec.seal(_trace(label=label, wall=0.05, attrs={
            "queue_wait_s": 0.005, "compile_s": 0.005,
            "fetch_wait_s": 0.005}), None, 0.01, True, False)


class TestAttribution:
    """The acceptance differentials: a forced cold compile, an
    injected fetch stall, and a saturated-queue wait each produce a
    retained trace whose verdict names the correct dominant term."""

    @pytest.mark.parametrize("attr,term", [
        ("compile_s", "compile"),          # forced cold compile
        ("fetch_wait_s", "fetch_wait"),    # dcn.slow_peer fetch stall
        ("queue_wait_s", "queue_wait"),    # saturated admission queue
        ("h2d_wait_s", "h2d"),             # staging stall
    ])
    def test_differential_names_the_dominant_term(self, attr, term,
                                                  tmp_path):
        rec = recorder.recorder()
        rec.configure(_conf(**{
            "spark.rapids.tpu.sql.trace.dir": str(tmp_path)}))
        label = f"q[{term}]"
        _baseline(rec, label)
        tr = _trace(label=label, wall=2.0, attrs={
            "queue_wait_s": 0.005, "compile_s": 0.005,
            "fetch_wait_s": 0.005, attr: 1.5})
        reason = rec.seal(tr, None, 0.01, True, False)
        assert reason == "top_k"  # 2s wall beats the 50ms window
        # the verdict is stamped into the trace for offline tools
        assert tr.attrs["perf_verdict"] == term
        assert tr.attrs["capture_reason"] == "top_k"
        assert tr.attrs["perf_terms"][term] == pytest.approx(1.5)
        assert tr.attrs["perf_baseline"][term] < 0.1
        # ... visible on the timeline itself ...
        marks = [e for e in tr.events if e[1] == "perf:anomaly"]
        assert len(marks) == 1 and marks[0][6]["term"] == term
        # ... and in the live registry
        assert _ctr("perf_anomalies_total", f"term={term}") == 1
        # the retained dump is self-describing: explain_slow reports
        # the sealed verdict from the file alone
        cap = rec.captures()[-1]
        assert cap.verdict == term and os.path.exists(cap.path)
        from tools import explain_slow
        res = explain_slow.analyze_path(cap.path)
        assert res["sealed"] is True
        assert res["verdict"] == term
        assert res["excess_s"] > 1.0
        assert term in explain_slow.format_why(res)
        assert "dominant" in explain_slow.format_why(res)

    def test_healthy_run_gets_no_verdict(self):
        rec = recorder.recorder()
        _baseline(rec, "q[ok]", n=4)
        tr = _trace(label="q[ok]", wall=0.05, attrs={
            "queue_wait_s": 0.005, "compile_s": 0.005})
        rec.seal(tr, None, 0.01, True, False)
        assert tr.attrs["perf_verdict"] == ""
        assert not [e for e in tr.events if e[1] == "perf:anomaly"]
        assert _ctr("perf_anomalies_total") == 0


# ---------------------------------------------------------------------------------
# end-to-end: a real session query lands in the ring
# ---------------------------------------------------------------------------------

class TestEndToEnd:
    def _q(self, sess, seed=7, n=4000):
        rng = np.random.default_rng(seed)
        df = sess.create_dataframe({
            "qty": rng.integers(1, 51, n).astype(np.float64),
            "price": (rng.random(n) * 1000).round(2),
        })
        return (df.where(F.col("qty") < 24)
                .group_by((F.col("qty") % 4).cast("int").alias("b"))
                .agg(F.sum(F.col("price")).alias("rev")))

    def test_default_on_capture_and_ledger(self, session, tmp_path):
        session.conf.set("spark.rapids.tpu.sql.trace.dir",
                         str(tmp_path))
        try:
            self._q(session).collect()
        finally:
            session.conf.unset("spark.rapids.tpu.sql.trace.dir")
        snap = recorder.snapshot()
        assert snap["enabled"] and snap["queries"] >= 1
        cap = snap["captures"][0]
        assert cap["reason"] == "first_seen"
        assert cap["fingerprint"].startswith("plan:")
        assert recorder.pending_seals() == 0
        # retention dumped the capture into the trace dir (without
        # sql.trace.enabled — the recorder's own dump path)
        assert cap["path"] and os.path.basename(
            cap["path"]).startswith("capture-")
        doc = json.loads(open(cap["path"]).read())
        assert doc["otherData"]["trace_id"] == cap["capture_id"]
        # the session's compiles landed in the ledger (unattributed:
        # a direct session query has no statement fingerprint)
        led = snap["compile_ledger"]
        assert led["compiles"] >= 1
        assert not led["storming"]

    def test_repeat_queries_drop_the_boring_median(self, session):
        for seed in range(10):
            self._q(session, seed=5).collect()
        snap = recorder.snapshot()
        assert snap["dropped_boring"] >= 1
        assert snap["pending_seals"] == 0

    def test_disabled_recorder_captures_nothing(self, session):
        session.conf.set(REC_KEY, False)
        try:
            self._q(session).collect()
        finally:
            session.conf.unset(REC_KEY)
        assert recorder.snapshot()["sealed"] == 0
