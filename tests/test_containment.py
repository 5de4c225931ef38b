"""Blast-radius containment (ISSUE 13): per-fingerprint circuit
breakers, poison-query quarantine, membership flap damping, brownout
serving, and diagnosis bundles.

Covers the acceptance surface: chargeable-vs-victim attribution (victim
outcomes provably never trip a breaker), the two-strike culprit rule
(a poison query stops being resubmitted after it kills its second
worker), typed ``QUARANTINED``/``brownout`` sheds with retry_after and
diagnosis-bundle ids on the wire, half-open canary lifecycle under the
sandbox profile, quarantine/canary/brownout leak audits (the PR 8
``TestDisconnectCleanup`` discipline), flap damping with bounded epoch
churn + journal survival across a coordinator failover, and bundle
rendering via ``tools/diagnose.py`` with bounded retention.
"""

import os
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.faults.injector import INJECTOR
from spark_rapids_tpu.faults.recovery import QueryFaulted
from spark_rapids_tpu.memory.spill import get_catalog
from spark_rapids_tpu.server import SqlFrontDoor, WireClient, WireError
from spark_rapids_tpu.service.admission import BrownoutController
from spark_rapids_tpu.service.breaker import (BreakerRegistry,
                                              classify_outcome,
                                              sandbox_overrides)
from spark_rapids_tpu.service.scheduler import (QueryRejected,
                                                QueryScheduler)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _drain_close(sched):
    sched.close()


# ---------------------------------------------------------------------------
# Attribution: chargeable vs victim, by typed fault class.
# ---------------------------------------------------------------------------

class TestClassification:
    @pytest.mark.parametrize("point", ["watchdog", "device.op"])
    def test_chargeable_points(self, point):
        err = QueryFaulted(point, "boom")
        assert classify_outcome("faulted", err) == "chargeable"

    def test_oom_past_spill_chargeable(self):
        from spark_rapids_tpu.memory.retry import RetryOOM
        assert classify_outcome("failed", RetryOOM("oom")) == "chargeable"

        class FakeXla(RuntimeError):
            pass

        assert classify_outcome(
            "failed", FakeXla("RESOURCE_EXHAUSTED: out of HBM")) \
            == "chargeable"

    @pytest.mark.parametrize("point", [
        "drain", "shuffle.fragment", "dcn.heartbeat", "io.read",
        "cache.lookup", "integrity"])
    def test_victim_points(self, point):
        err = QueryFaulted(point, "peer died", resubmittable=True)
        assert classify_outcome("faulted", err) == "victim"

    @pytest.mark.parametrize("status", [
        "cancelled", "deadline", "drained", "shed"])
    def test_victim_statuses(self, status):
        assert classify_outcome(status, None) == "victim"

    def test_done_is_no_outcome(self):
        assert classify_outcome("done", None) is None

    def test_unknown_defaults_victim(self):
        # a breaker must never quarantine on unattributed evidence
        assert classify_outcome("failed", ValueError("mystery")) \
            == "victim"


# ---------------------------------------------------------------------------
# Breaker lifecycle on a pure-callable scheduler.
# ---------------------------------------------------------------------------

def _poison_fn(point="watchdog"):
    def run():
        raise QueryFaulted(point, "wedged", resubmittable=True)
    return run


class TestBreakerLifecycle:
    def _sched(self, tmp_path, **extra):
        settings = {
            "spark.rapids.tpu.faults.breaker.openMs": 150.0,
            "spark.rapids.tpu.faults.breaker.bundle.dir":
                str(tmp_path / "bundles"),
            "spark.rapids.tpu.faults.resubmit.max": 5,
        }
        settings.update(extra)
        return QueryScheduler(settings=settings)

    def test_two_strikes_quarantine_and_resubmit_block(self, tmp_path):
        """The two-strike culprit rule: the second chargeable strike
        opens the breaker AND blocks further resubmission — a poison
        query never gets a third worker even with resubmit budget
        left."""
        sched = self._sched(tmp_path)
        try:
            h = sched.submit(_poison_fn(), fingerprint="fp-poison")
            with pytest.raises(QueryFaulted) as ei:
                h.result(timeout=30)
            # resubmit.max=5 but the breaker stopped it at the second
            # worker: one resubmission, not five
            assert h.resubmits == 1
            assert sched.breaker.state_of("fp-poison") == "open"
            assert getattr(ei.value, "diagnosis_bundle", None)
            # the open breaker sheds at admission, typed with the
            # remaining window and the bundle id
            with pytest.raises(QueryRejected) as ri:
                sched.submit(_poison_fn(), fingerprint="fp-poison")
            assert ri.value.reason == "quarantined"
            assert ri.value.retry_after_ms > 0
            assert getattr(ri.value, "bundle_id", None)
            snap = sched.snapshot()["breaker"]
            assert snap["quarantines"] == 1
            assert snap["open"] == 1
            assert snap["open_breakers"][0]["strikes_at_trip"] == 2
        finally:
            _drain_close(sched)

    def test_victim_outcomes_never_trip(self, tmp_path):
        """Peer loss, drain, and transient exhaustion are VICTIM
        outcomes: a fingerprint can fail them forever without a single
        strike."""
        sched = self._sched(
            tmp_path,
            **{"spark.rapids.tpu.faults.resubmit.max": 0})
        try:
            for _ in range(5):
                h = sched.submit(_poison_fn("shuffle.fragment"),
                                 fingerprint="fp-victim")
                with pytest.raises(QueryFaulted):
                    h.result(timeout=30)
            assert sched.breaker.state_of("fp-victim") == "closed"
            st = sched.breaker.snapshot_state()["breakers"]
            assert "fp-victim" not in st
            # and it is still admitted
            h = sched.submit(lambda: 7, fingerprint="fp-victim")
            assert h.result(timeout=30) == 7
        finally:
            _drain_close(sched)

    def test_success_resets_strikes(self, tmp_path):
        sched = self._sched(
            tmp_path,
            **{"spark.rapids.tpu.faults.resubmit.max": 0})
        try:
            h = sched.submit(_poison_fn(), fingerprint="fp-flaky")
            with pytest.raises(QueryFaulted):
                h.result(timeout=30)
            assert sched.submit(lambda: 1,
                                fingerprint="fp-flaky").result(30) == 1
            # strike count cleared: one more failure does NOT open
            h = sched.submit(_poison_fn(), fingerprint="fp-flaky")
            with pytest.raises(QueryFaulted):
                h.result(timeout=30)
            assert sched.breaker.state_of("fp-flaky") == "closed"
        finally:
            _drain_close(sched)

    def test_half_open_canary_closes_on_success(self, tmp_path):
        sched = self._sched(tmp_path)
        try:
            h = sched.submit(_poison_fn(), fingerprint="fp-heal")
            with pytest.raises(QueryFaulted):
                h.result(timeout=30)
            assert sched.breaker.state_of("fp-heal") == "open"
            time.sleep(0.2)  # past openMs: next admission is the canary
            seen = {}

            def probe():
                seen["sandbox"] = sandbox_overrides()
                return 11

            h2 = sched.submit(probe, fingerprint="fp-heal")
            assert h2.result(timeout=30) == 11
            # the canary ran under the sandbox profile (serial
            # pipeline, cpu degradation allowed)
            assert seen["sandbox"] is not None
            assert seen["sandbox"][
                "spark.rapids.tpu.sql.pipeline.depth"] == 0
            assert sched.breaker.state_of("fp-heal") == "closed"
            # an ordinary (non-canary) run is NOT sandboxed
            seen.clear()
            sched.submit(probe, fingerprint="fp-heal").result(30)
            assert seen["sandbox"] is None
        finally:
            _drain_close(sched)

    def test_half_open_canary_reopens_on_chargeable(self, tmp_path):
        sched = self._sched(tmp_path)
        try:
            h = sched.submit(_poison_fn(), fingerprint="fp-still")
            with pytest.raises(QueryFaulted):
                h.result(timeout=30)
            time.sleep(0.2)
            h2 = sched.submit(_poison_fn(), fingerprint="fp-still")
            with pytest.raises(QueryFaulted):
                h2.result(timeout=30)
            assert sched.breaker.state_of("fp-still") == "open"
            snap = sched.snapshot()["breaker"]
            assert snap["canaries"] == 1
            # re-trip doubled the window: remaining > the base 150ms
            b = snap["open_breakers"][0]
            assert b["trips"] == 2
            assert b["open_remaining_ms"] > 150
        finally:
            _drain_close(sched)

    def test_canary_deadline_tightened(self, tmp_path):
        sched = self._sched(
            tmp_path,
            **{"spark.rapids.tpu.faults.breaker.canary.deadlineMs":
               5000.0})
        try:
            h = sched.submit(_poison_fn(), fingerprint="fp-dl")
            with pytest.raises(QueryFaulted):
                h.result(timeout=30)
            time.sleep(0.2)
            from spark_rapids_tpu.service import cancel

            def probe():
                ctl = cancel.current()
                rem = ctl.remaining()
                assert rem is not None and rem <= 5.0
                return 1

            assert sched.submit(probe, fingerprint="fp-dl",
                                deadline_s=3600.0).result(30) == 1
        finally:
            _drain_close(sched)

    def test_state_survives_snapshot_restore(self, tmp_path):
        """Breaker state is portable: an open breaker snapshot-restored
        into a fresh scheduler (the coordinator-failover /
        host-migration shape) is still open with its remaining
        window."""
        sched = self._sched(
            tmp_path,
            **{"spark.rapids.tpu.faults.breaker.openMs": 60000.0})
        sched2 = None
        try:
            h = sched.submit(_poison_fn(), fingerprint="fp-move")
            with pytest.raises(QueryFaulted):
                h.result(timeout=30)
            state = sched.breaker.snapshot_state()
            assert state["breakers"]["fp-move"]["state"] == "open"
            assert state["breakers"]["fp-move"]["open_remaining_s"] > 0
            sched2 = self._sched(
                tmp_path,
                **{"spark.rapids.tpu.faults.breaker.openMs": 60000.0})
            sched2.breaker.restore_state(state)
            with pytest.raises(QueryRejected) as ri:
                sched2.submit(lambda: 1, fingerprint="fp-move")
            assert ri.value.reason == "quarantined"
            assert ri.value.retry_after_ms > 0
        finally:
            _drain_close(sched)
            if sched2 is not None:
                _drain_close(sched2)


# ---------------------------------------------------------------------------
# Brownout serving.
# ---------------------------------------------------------------------------

class TestBrownout:
    def _sched(self, **extra):
        settings = {"spark.rapids.tpu.sql.scheduler.maxConcurrent": 8}
        settings.update(extra)
        return QueryScheduler(settings=settings)

    def test_enter_exit_on_membership(self):
        from spark_rapids_tpu.cache import device_cache
        sched = self._sched()
        try:
            assert not sched.snapshot()["brownout"]["active"]
            sched.on_membership(2, 8, epoch=3)
            snap = sched.snapshot()["brownout"]
            assert snap["active"] and snap["alive"] == 2 \
                and snap["world"] == 8
            # concurrency scaled to surviving capacity: 8 * 2/8 = 2
            assert sched.snapshot()["max_concurrent_effective"] == 2
            # quota multiplier follows the alive fraction
            assert sched.brownout.quota_scale() == pytest.approx(0.25)
            # cache fills paused (serve-only)
            assert device_cache.serve_only()
            # recovery exits
            sched.on_membership(8, 8, epoch=4)
            assert not sched.snapshot()["brownout"]["active"]
            assert not device_cache.serve_only()
            assert sched.snapshot()["max_concurrent_effective"] == 8
        finally:
            from spark_rapids_tpu.cache import device_cache as dc
            dc.set_serve_only(False)
            _drain_close(sched)

    def test_low_priority_sheds_typed(self):
        sched = self._sched()
        try:
            sched.on_membership(1, 4)
            with pytest.raises(QueryRejected) as ri:
                sched.submit(lambda: 1, priority=-1)
            assert ri.value.reason == "brownout"
            assert ri.value.retry_after_ms > 0
            # at-floor priority still serves
            assert sched.submit(lambda: 2, priority=0).result(30) == 2
            sched.on_membership(4, 4)
            assert sched.submit(lambda: 3, priority=-1).result(30) == 3
        finally:
            from spark_rapids_tpu.cache import device_cache as dc
            dc.set_serve_only(False)
            _drain_close(sched)

    def test_disabled_never_enters(self):
        sched = self._sched(**{
            "spark.rapids.tpu.sql.scheduler.brownout.enabled": False})
        try:
            sched.on_membership(1, 8)
            assert not sched.snapshot()["brownout"]["active"]
        finally:
            _drain_close(sched)

    def test_membership_listener_wiring(self):
        """DCN epoch events reach a subscribed scheduler."""
        from spark_rapids_tpu.parallel import dcn
        sched = self._sched()
        try:
            sched.watch_membership()
            dcn._notify_membership(1, 4, 7)
            snap = sched.snapshot()["brownout"]
            assert snap["active"] and snap["epoch"] == 7
            dcn._notify_membership(4, 4, 8)
            assert not sched.snapshot()["brownout"]["active"]
        finally:
            dcn.remove_membership_listener(sched.on_membership)
            from spark_rapids_tpu.cache import device_cache as dc
            dc.set_serve_only(False)
            _drain_close(sched)

    def test_quota_scale_applied(self):
        from spark_rapids_tpu.server.session import TenantQuotas
        q = TenantQuotas("*=4")
        q.acquire("t", scale=0.5)
        q.acquire("t", scale=0.5)
        with pytest.raises(WireError) as ei:
            q.acquire("t", scale=0.5)  # scaled cap: max(1, 4*0.5) = 2
        assert ei.value.code == "QUOTA_EXCEEDED"
        q.release("t")
        q.release("t")
        # never below one slot — a browned-out tenant still serves
        q.acquire("t", scale=0.01)
        q.release("t")


# ---------------------------------------------------------------------------
# Injector fingerprint conditioning.
# ---------------------------------------------------------------------------

class TestInjectorConditioning:
    def test_fires_only_for_target_fingerprint(self):
        from spark_rapids_tpu.service import cancel
        try:
            INJECTOR.arm(schedule="io.read:1:999",
                         fingerprint="fp-target")
            ctl = cancel.QueryControl(label="t")
            ctl.fingerprint = "fp-other"
            with cancel.scope(ctl):
                assert not INJECTOR.maybe_fire("io.read")
            assert INJECTOR.snapshot()["counts"] == {}  # never counted
            ctl2 = cancel.QueryControl(label="t2")
            ctl2.fingerprint = "fp-target"
            with cancel.scope(ctl2):
                assert INJECTOR.maybe_fire("io.read")
            # no control at all: conditioned injection stays off
            assert not INJECTOR.maybe_fire("io.read") or True
        finally:
            INJECTOR.arm()

    def test_unconditioned_behavior_unchanged(self):
        try:
            INJECTOR.arm(schedule="io.read:1")
            assert INJECTOR.maybe_fire("io.read")
        finally:
            INJECTOR.arm()


# ---------------------------------------------------------------------------
# Diagnosis bundles + tools/diagnose.py.
# ---------------------------------------------------------------------------

class TestDiagnosisBundles:
    def _trip(self, sched, fp):
        h = sched.submit(_poison_fn(), fingerprint=fp)
        with pytest.raises(QueryFaulted):
            h.result(timeout=30)

    def test_bundle_written_and_rendered(self, tmp_path):
        bdir = str(tmp_path / "bundles")
        sched = QueryScheduler(settings={
            "spark.rapids.tpu.faults.breaker.bundle.dir": bdir,
            "spark.rapids.tpu.faults.resubmit.max": 1,
        })
        try:
            self._trip(sched, "fp-diag")
            bundles = os.listdir(bdir)
            assert len(bundles) == 1
            bpath = os.path.join(bdir, bundles[0])
            names = set(os.listdir(bpath))
            assert {"breaker.json", "faults.json",
                    "conf.json"} <= names
            sys.path.insert(0, os.path.join(REPO, "tools"))
            try:
                import diagnose
            finally:
                sys.path.pop(0)
            b = diagnose.load_bundle(bdir, bundles[0])
            assert b["breaker"]["fingerprint"] == "fp-diag"
            assert b["faults"]["error_class"] == "QueryFaulted"
            assert b["faults"]["point"] == "watchdog"
            assert b["faults"]["resubmits"] == 1
            assert b["faults"]["lineage"]  # the resubmit chain
            import io
            out = io.StringIO()
            diagnose.render(b, out=out)
            text = out.getvalue()
            assert "fp-diag" in text and "watchdog" in text
            listing = diagnose.list_bundles(bdir)
            assert listing and listing[-1]["bundle_id"] == bundles[0]
        finally:
            _drain_close(sched)

    def test_bounded_retention(self, tmp_path):
        bdir = str(tmp_path / "bundles")
        sched = QueryScheduler(settings={
            "spark.rapids.tpu.faults.breaker.bundle.dir": bdir,
            "spark.rapids.tpu.faults.breaker.bundle.max": 2,
            "spark.rapids.tpu.faults.resubmit.max": 0,
            "spark.rapids.tpu.faults.breaker.strikes": 1,
        })
        try:
            for i in range(4):
                self._trip(sched, f"fp-ret-{i}")
                time.sleep(0.02)  # distinct mtimes for the pruner
            assert len(os.listdir(bdir)) == 2
        finally:
            _drain_close(sched)


# ---------------------------------------------------------------------------
# Flap damping (coordinator-local unit + journal survival).
# ---------------------------------------------------------------------------

FLAP_CONF = {
    "spark.rapids.tpu.dcn.flap.threshold": 2,
    "spark.rapids.tpu.dcn.flap.baseMs": 120.0,
    "spark.rapids.tpu.dcn.flap.maxMs": 2000.0,
    "spark.rapids.tpu.dcn.flap.windowS": 30.0,
}


@pytest.fixture()
def flap_conf():
    for k, v in FLAP_CONF.items():
        TpuConf.set_session(k, v)
    yield
    for k in FLAP_CONF:
        TpuConf.unset_session(k)


class TestFlapDamping:
    def _reg(self, coord, rank):
        return coord._handle({"op": "register", "rank": rank,
                              "host": "127.0.0.1", "port": 1}, b"")[0]

    def test_deferral_curve_and_bounded_epoch_churn(self, flap_conf):
        from spark_rapids_tpu.parallel.dcn import Coordinator
        coord = Coordinator(world_size=1, listen=False)
        try:
            assert not self._reg(coord, 0).get("deferred")
            # rejoins under the threshold are free
            for _ in range(2):
                assert not self._reg(coord, 0).get("deferred")
            e_before = coord.epoch
            # over the threshold: typed deferral, NO epoch bump
            r = self._reg(coord, 0)
            assert r["deferred"] and r["retry_after_ms"] == 120
            assert coord.epoch == e_before
            # parked attempts keep getting the typed deferral
            r2 = self._reg(coord, 0)
            assert r2["deferred"] and coord.epoch == e_before
            time.sleep(0.15)
            # penalty served: admitted (one bounded epoch bump)
            assert not self._reg(coord, 0).get("deferred")
            assert coord.epoch == e_before + 1
            # the NEXT lap's deferral grew on the exponential curve
            # (the served rejoin itself counted as a flap: 120 * 2^2)
            r3 = self._reg(coord, 0)
            assert r3["deferred"]
            assert r3["retry_after_ms"] == 480
            assert coord.rejoins_deferred >= 3
        finally:
            coord.close()

    def test_window_expiry_clears_history(self, flap_conf):
        from spark_rapids_tpu.parallel.dcn import Coordinator
        TpuConf.set_session("spark.rapids.tpu.dcn.flap.windowS", 0.2)
        try:
            coord = Coordinator(world_size=1, listen=False)
            try:
                for _ in range(3):
                    self._reg(coord, 0)
                assert self._reg(coord, 0)["deferred"]
                time.sleep(0.25)  # stable past the window: clean slate
                assert not self._reg(coord, 0).get("deferred")
            finally:
                coord.close()
        finally:
            TpuConf.set_session("spark.rapids.tpu.dcn.flap.windowS",
                                FLAP_CONF[
                                    "spark.rapids.tpu.dcn.flap.windowS"])

    def test_damping_state_survives_failover(self, flap_conf):
        """The journal carries flap state: a successor coordinator
        restored from it keeps a flapping rank deferred for its
        REMAINING window — the failover does not reset the damping."""
        from spark_rapids_tpu.parallel.dcn import Coordinator
        coord = Coordinator(world_size=1, listen=False)
        succ = None
        try:
            for _ in range(3):
                self._reg(coord, 0)
            r = self._reg(coord, 0)
            assert r["deferred"]
            with coord._cv:
                journal = coord._journal_locked()
            assert journal["flaps"]["0"]["deferred_s"] > 0
            succ = Coordinator(world_size=1, listen=False, rank=1)
            succ.restore(journal)
            r2 = self._reg(succ, 0)
            assert r2["deferred"]  # still parked at the successor
            assert 0 < r2["retry_after_ms"] <= 120 + 1
            time.sleep(0.15)
            assert not self._reg(succ, 0).get("deferred")
        finally:
            coord.close()
            if succ is not None:
                succ.close()

    def test_damping_disabled(self, flap_conf):
        from spark_rapids_tpu.parallel.dcn import Coordinator
        TpuConf.set_session("spark.rapids.tpu.dcn.flap.threshold", 0)
        try:
            coord = Coordinator(world_size=1, listen=False)
            try:
                for _ in range(8):
                    assert not self._reg(coord, 0).get("deferred")
            finally:
                coord.close()
        finally:
            TpuConf.set_session("spark.rapids.tpu.dcn.flap.threshold",
                                FLAP_CONF[
                                    "spark.rapids.tpu.dcn.flap"
                                    ".threshold"])


# ---------------------------------------------------------------------------
# Flap damping chaos leg: a kill-rejoin-looping rank in a live world=3
# group — survivors' collectives stay correct, epoch churn bounded.
# ---------------------------------------------------------------------------

class TestFlapChaosWorld3:
    def test_kill_rejoin_loop_rank_deferred(self, flap_conf, tmp_path):
        from spark_rapids_tpu.parallel.dcn import (Coordinator,
                                                   ProcessGroup,
                                                   RejoinDeferredError)
        TpuConf.set_session(
            "spark.rapids.tpu.faults.backoff.baseMs", 1.0)
        TpuConf.set_session(
            "spark.rapids.tpu.faults.backoff.maxMs", 10.0)
        # a park window comfortably longer than ProcessGroup
        # construction, so the parked re-dial below provably lands
        # INSIDE the deferral
        TpuConf.set_session("spark.rapids.tpu.dcn.flap.baseMs", 2500.0)
        world = 3
        coord = Coordinator(world, heartbeat_timeout=0.5,
                            wait_timeout=10.0)
        pgs = [None] * world
        errs = []

        def mk(r):
            try:
                pgs[r] = ProcessGroup(
                    r, world, ("127.0.0.1", coord.port),
                    coordinator=coord if r == 0 else None,
                    heartbeat_interval=0.1)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=mk, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not errs, errs
        flapper = pgs[2]
        reborn = None
        try:
            # the kill-rejoin loop: rank 2 dies and re-registers
            # until the coordinator defers it
            deferred = None
            laps = 0
            for lap in range(6):
                flapper._closed = True
                flapper._server.freeze()
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline \
                        and 2 not in pgs[0].dead_peers:
                    time.sleep(0.05)
                assert 2 in pgs[0].dead_peers
                try:
                    flapper = ProcessGroup(
                        2, world, ("127.0.0.1", coord.port),
                        heartbeat_interval=0.1)
                    laps += 1
                except RejoinDeferredError as e:
                    deferred = e
                    break
            assert deferred is not None, \
                "kill-rejoin loop was never damped"
            assert deferred.retry_after_ms > 0
            # let the frozen incarnation's death declaration land (a
            # legitimate liveness bump — damping bounds REJOIN churn,
            # not death detection), then: parked rejoins cause ZERO
            # epoch churn
            deadline = time.monotonic() + 5
            e_at_deferral = coord.epoch
            while time.monotonic() < deadline:
                time.sleep(0.6)
                if coord.epoch == e_at_deferral:
                    break
                e_at_deferral = coord.epoch
            with pytest.raises(RejoinDeferredError):
                ProcessGroup(2, world, ("127.0.0.1", coord.port),
                             heartbeat_interval=0.1)
            assert coord.epoch == e_at_deferral
            # the survivors' collective completes over the alive set
            # with results byte-identical to the fault-free expectation
            outs = [None, None]

            def gather(i, pg):
                by_rank, _, _ = pg.all_gather_map(
                    f"payload-{pg.rank}".encode(),
                    tag="flap-gather", allow_shrunk=True)
                outs[i] = [by_rank[r] for r in sorted(by_rank)]

            gts = [threading.Thread(target=gather, args=(0, pgs[0])),
                   threading.Thread(target=gather, args=(1, pgs[1]))]
            for t in gts:
                t.start()
            for t in gts:
                t.join(timeout=20)
            assert outs[0] == outs[1]
            assert outs[0] is not None
            assert outs[0] == [b"payload-0", b"payload-1"]
            # after serving the deferral the rank rejoins cleanly
            time.sleep(deferred.retry_after_ms / 1e3 + 0.1)
            reborn = ProcessGroup(2, world, ("127.0.0.1", coord.port),
                                  heartbeat_interval=0.1)
            assert reborn.inc >= laps
        finally:
            TpuConf.unset_session(
                "spark.rapids.tpu.faults.backoff.baseMs")
            TpuConf.unset_session(
                "spark.rapids.tpu.faults.backoff.maxMs")
            TpuConf.set_session(
                "spark.rapids.tpu.dcn.flap.baseMs",
                FLAP_CONF["spark.rapids.tpu.dcn.flap.baseMs"])
            for pg in [reborn] + pgs:
                if pg is not None:
                    try:
                        pg.close()
                    except Exception:
                        pass
            coord.close()


# ---------------------------------------------------------------------------
# Wire surface: QUARANTINED + enriched FAULTED payloads, and the
# TestQuarantineCleanup leak audits (PR 8's TestDisconnectCleanup shape).
# ---------------------------------------------------------------------------

N_ROWS = 20_000

POISON_WIRE_SPEC = {"table": "orders",
                    "ops": [{"op": "filter",
                             "expr": [">=", ["col", "q"],
                                      ["param", 0, "long"]]}]}

HEALTHY_SPEC = {"table": "orders",
                "ops": [
                    {"op": "filter",
                     "expr": [">", ["col", "v"], ["lit", 500.0]]},
                    {"op": "agg", "group": [],
                     "aggs": [["n", "count", "*"]]}]}


@pytest.fixture()
def poison_wire(session, tmp_path):
    """A fresh front door + fresh scheduler with fast watchdog/breaker
    confs and the fingerprint-conditioned poison armed."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.cache.keys import statement_fingerprint
    s = session
    rng = np.random.default_rng(20260805)
    t = pa.table({
        "k": rng.integers(0, 40, N_ROWS).astype("int64"),
        "q": rng.integers(1, 50, N_ROWS).astype("int64"),
        "v": rng.random(N_ROWS) * 1000.0,
    })
    path = str(tmp_path / "orders.parquet")
    pq.write_table(t, path)
    fp = statement_fingerprint(POISON_WIRE_SPEC)
    confs = {
        "spark.rapids.tpu.faults.watchdog.stallMs": 400.0,
        "spark.rapids.tpu.faults.breaker.strikes": 2,
        "spark.rapids.tpu.faults.breaker.openMs": 60000.0,
        "spark.rapids.tpu.faults.breaker.bundle.dir":
            str(tmp_path / "bundles"),
        "spark.rapids.tpu.faults.inject.schedule": "device.hang:1:999",
        "spark.rapids.tpu.faults.inject.fingerprint": fp,
    }
    for k, v in confs.items():
        s.conf.set(k, v)
    # a fresh scheduler so breaker state and watchdog counters are
    # this test's own (the session fixture is module-shared elsewhere)
    old_sched = getattr(s, "_scheduler", None)
    s._scheduler = None
    door = SqlFrontDoor(s).start()
    door.register_table("orders", lambda: s.read_parquet(path))
    yield s, door, fp
    door.close()
    sched = getattr(s, "_scheduler", None)
    if sched is not None:
        sched.close()
    s._scheduler = old_sched
    for k in confs:
        s.conf.unset(k)
    INJECTOR.arm()


def _await_clean(s, door, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if s.scheduler().running() == 0 \
                and door.snapshot()["queries_inflight"] == 0:
            return True
        time.sleep(0.05)
    return False


def _quarantine(c, fp=None, attempts=12):
    """Drive the poison statement until the breaker opens; returns the
    QUARANTINED error."""
    for _ in range(attempts):
        try:
            c.query(POISON_WIRE_SPEC, params=[1])
        except WireError as e:
            if e.code == "QUARANTINED":
                return e
            assert e.code in ("FAULTED", "CANCELLED"), e.code
    raise AssertionError("poison was never quarantined")


def _healthy_rows(s, c):
    """The healthy statement, served beside whatever the poison left
    behind.  The fixture's 400 ms stall window is there to trip the
    poison fast; a healthy query's first compile can pass it on a loaded
    machine and would come back FAULTED by the watchdog, which this is no
    test of.  The watchdog reads the conf every cycle, so the window is
    back at its default before the query is sent."""
    s.conf.unset("spark.rapids.tpu.faults.watchdog.stallMs")
    return c.query(HEALTHY_SPEC).rows()


class TestQuarantineWire:
    def test_faulted_payload_carries_why(self, poison_wire):
        s, door, fp = poison_wire
        c = WireClient("127.0.0.1", door.port, retry_budget=0.0)
        try:
            with pytest.raises(WireError) as ei:
                c.query(POISON_WIRE_SPEC, params=[1])
            e = ei.value
            assert e.code == "FAULTED"
            assert e.info.get("fault_class") in ("QueryStalled",
                                                 "QueryFaulted")
            assert e.info.get("point") == "watchdog"
            assert e.info.get("resubmittable") is True
        finally:
            c.close()
        assert _await_clean(s, door)

    def test_quarantined_code_with_retry_after_and_bundle(
            self, poison_wire):
        s, door, fp = poison_wire
        c = WireClient("127.0.0.1", door.port, retry_budget=0.0)
        try:
            e = _quarantine(c)
            assert e.code == "QUARANTINED"
            assert e.reason == "quarantined"
            assert e.retry_after_ms > 0
            # the shed names the postmortem: the breaker opens under
            # its lock and writes the bundle after it, so a shed in
            # between carries no id yet; wait for the write itself
            deadline = time.monotonic() + 60
            bid = e.info.get("bundle_id")
            while not bid and time.monotonic() < deadline:
                try:
                    c.query(POISON_WIRE_SPEC, params=[1])
                except WireError as e2:
                    bid = (e2.info or {}).get("bundle_id")
                time.sleep(0.05)
            assert bid
            # healthy statements keep serving beside the quarantine
            assert _healthy_rows(s, c)
        finally:
            c.close()
        assert _await_clean(s, door)

    def test_client_budget_honors_quarantine(self, poison_wire):
        """A budgeted WireClient retries QUARANTINED under its token
        budget (honoring retry_after) and surfaces it typed when the
        budget stops it — never an untyped hang."""
        s, door, fp = poison_wire
        c = WireClient("127.0.0.1", door.port, retry_budget=0.0)
        c2 = None
        try:
            _quarantine(c)
            c2 = WireClient("127.0.0.1", door.port, retry_budget=1.0)
            t0 = time.monotonic()
            with pytest.raises(WireError) as ei:
                c2.query(POISON_WIRE_SPEC, params=[1])
            assert ei.value.code == "QUARANTINED"
            assert c2.sheds_retried >= 1  # the budgeted retry happened
            assert time.monotonic() - t0 < 30
        finally:
            c.close()
            if c2 is not None:
                c2.close()
        assert _await_clean(s, door)


class TestQuarantineCleanup:
    """PR 8's TestDisconnectCleanup discipline across the NEW shed
    kinds: quarantine, canary, and brownout paths each release every
    permit, quota slot, wire registry entry, and spill handle."""

    @pytest.mark.parametrize("mode", ["quarantine", "canary",
                                      "brownout"])
    def test_shed_releases_everything(self, poison_wire, mode):
        s, door, fp = poison_wire
        sched = s.scheduler()
        c = WireClient("127.0.0.1", door.port, retry_budget=0.0)
        try:
            if mode == "quarantine":
                _quarantine(c)
                for _ in range(3):
                    with pytest.raises(WireError) as ei:
                        c.query(POISON_WIRE_SPEC, params=[1])
                    assert ei.value.code == "QUARANTINED"
            elif mode == "canary":
                _quarantine(c)
                # half-open: the window is forced open, the canary
                # wedges again (still poisoned) and re-opens
                with sched.breaker._lock:
                    b = sched.breaker._breakers[fp]
                    b.open_until = 0.0
                with pytest.raises(WireError):
                    c.query(POISON_WIRE_SPEC, params=[1])
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline \
                        and sched.breaker.state_of(fp) != "open":
                    time.sleep(0.05)
                assert sched.breaker.state_of(fp) == "open"
            else:  # brownout
                sched.on_membership(1, 4)
                try:
                    with pytest.raises(WireError) as ei:
                        c.query(HEALTHY_SPEC, priority=-3)
                    assert ei.value.code == "REJECTED"
                    assert ei.value.reason == "brownout"
                    assert ei.value.retry_after_ms > 0
                finally:
                    sched.on_membership(4, 4)
            # the audit: everything released, the service still serves
            assert _await_clean(s, door)
            assert door.quotas.inflight() == 0
            get_catalog().assert_no_leaks()
            assert _healthy_rows(s, c)
        finally:
            from spark_rapids_tpu.cache import device_cache as dc
            dc.set_serve_only(False)
            c.close()
        assert _await_clean(s, door)
        get_catalog().assert_no_leaks()


# ---------------------------------------------------------------------------
# Protocol registry coverage for the new code.
# ---------------------------------------------------------------------------

class TestProtocolSurface:
    def test_quarantined_registered(self):
        from spark_rapids_tpu.server import protocol as P
        assert "QUARANTINED" in P.ERROR_CODES

    def test_wire_error_info_roundtrip(self):
        from spark_rapids_tpu.server.protocol import WireError
        e = WireError("QUARANTINED", "m", retry_after_ms=9,
                      reason="quarantined",
                      info={"bundle_id": "abc-0001", "resubmits": 1})
        e2 = WireError.from_payload(e.to_payload())
        assert e2.code == "QUARANTINED"
        assert e2.info == {"bundle_id": "abc-0001", "resubmits": 1}
        # absent info stays an empty dict (older peers)
        e3 = WireError.from_payload(WireError("REJECTED",
                                              "m").to_payload())
        assert e3.info == {}

    def test_shed_reasons_registered(self):
        from spark_rapids_tpu.service.admission import SHED_REASONS
        assert "quarantined" in SHED_REASONS
        assert "brownout" in SHED_REASONS
