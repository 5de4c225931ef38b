"""Network partition survival (ISSUE 14): the seeded link-fault fabric,
quorum-fenced coordinator failover, delivery dedup, suspicion strikes,
and heal-and-rejoin.

Tier-1 runs the thread-rank simulations every collection: partition the
minority of a world=3/world=5 group mid-run — the majority completes
byte-identically to fault-free (durable re-pull + adoption), the
minority PARKS with a typed :class:`QuorumLostError` instead of
electing a second coordinator, and after ``FABRIC.heal()`` the parked
rank re-registers under flap damping with zero epoch churn beyond the
single rejoin bump.  The @slow leg reruns the same differential over
real processes (tests/dcn_worker.py ``--net-partition``).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pyarrow as pa
import pytest

from spark_rapids_tpu.config import ALL_ENTRIES, TpuConf
from spark_rapids_tpu.faults import INJECTOR
from spark_rapids_tpu.faults.netfabric import (FABRIC, LinkPartitionedError,
                                               NetFabric)
from spark_rapids_tpu.parallel.dcn import (Coordinator, DcnShuffle,
                                           ProcessGroup, QuorumLostError)
from spark_rapids_tpu.utils.metrics import QueryStats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAST = {
    "spark.rapids.tpu.faults.backoff.baseMs": 1.0,
    "spark.rapids.tpu.faults.backoff.maxMs": 10.0,
    # the PG-side liveness horizon (vote aging, heartbeat-reply recv
    # timeout) rides this conf; the recv timeout floors at 1 s, so
    # votes age "unreachable" ~2 s after a cut
    "spark.rapids.tpu.dcn.heartbeatTimeout": 0.8,
    # ...and the vote-poll window must cover that aging
    "spark.rapids.tpu.dcn.quorum.windowMs": 3500.0,
}


@pytest.fixture()
def net_conf():
    for k, v in FAST.items():
        TpuConf.set_session(k, v)
    yield
    for k in FAST:
        TpuConf.unset_session(k)
    INJECTOR.arm()
    FABRIC.reset()  # clear any standing program, runtime cuts included


def _make_group(world, hb_timeout=0.4, wait_timeout=10.0, interval=0.1):
    coord = Coordinator(world, heartbeat_timeout=hb_timeout,
                        wait_timeout=wait_timeout)
    pgs = [None] * world
    errs = []

    def mk(r):
        try:
            pgs[r] = ProcessGroup(r, world, ("127.0.0.1", coord.port),
                                  coordinator=coord if r == 0 else None,
                                  heartbeat_interval=interval)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=mk, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errs, errs
    return coord, pgs


def _close_all(pgs):
    for pg in pgs:
        if pg is not None:
            try:
                pg.close()
            except Exception:  # fault-ok (chaos teardown of parked/partitioned ranks)
                pass


def _wait(pred, timeout=15.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(
        f"timed out waiting for {what() if callable(what) else what}")


def _active_coordinators(coord, pgs):
    coords = [coord] + [pg.coordinator for pg in pgs
                        if pg is not None and pg.coordinator is not None
                        and pg.coordinator is not coord]
    return [c for c in coords if c.is_active()]


# ---------------------------------------------------------------------------
# The fabric itself.
# ---------------------------------------------------------------------------

class TestNetFabric:
    def test_partition_grammar(self):
        f = NetFabric()
        f.arm(partition="0>2")
        with pytest.raises(LinkPartitionedError):
            f.check_send(0, 2)
        f.check_send(2, 0)  # asymmetric: reverse direction flows
        f.arm(partition="1-2")
        with pytest.raises(LinkPartitionedError):
            f.check_send(1, 2)
        with pytest.raises(LinkPartitionedError):
            f.check_send(2, 1)
        f.arm(partition="0+1|2")
        for s, d in ((0, 2), (2, 0), (1, 2), (2, 1)):
            with pytest.raises(LinkPartitionedError):
                f.check_send(s, d)
        f.check_send(0, 1)
        f.arm(partition="2|*")
        with pytest.raises(LinkPartitionedError):
            f.check_send(2, 4)
        with pytest.raises(LinkPartitionedError):
            f.check_send(3, 2)
        f.check_send(0, 1)
        with pytest.raises(ValueError):
            f.arm(partition="bogus")

    def test_loopback_never_faulted(self):
        f = NetFabric()
        f.arm(partition="*|*", delay="*:5000")
        f.check_send(1, 1)  # a rank's own link is exempt

    def test_delay_applies(self):
        f = NetFabric()
        f.arm(delay="0>1:80")
        t0 = time.monotonic()
        f.check_send(0, 1)
        assert time.monotonic() - t0 >= 0.07
        t0 = time.monotonic()
        f.check_send(1, 0)  # one-way: reverse is instant
        assert time.monotonic() - t0 < 0.05

    def test_after_ops_gates_engagement(self):
        f = NetFabric()
        f.arm(partition="0-1", after_ops=2)
        f.check_send(0, 1)  # not engaged yet
        f.note_op()
        f.check_send(0, 1)
        f.note_op()
        with pytest.raises(LinkPartitionedError):
            f.check_send(0, 1)

    def test_heal_is_sticky_across_identical_rearm(self):
        f = NetFabric()
        f.arm(partition="0-1")
        with pytest.raises(LinkPartitionedError):
            f.check_send(0, 1)
        f.heal()
        f.check_send(0, 1)
        f.arm(partition="0-1")  # identical re-arm (next ExecContext)
        f.check_send(0, 1)  # still healed
        f.arm(partition="0-2")  # CHANGED program re-engages
        with pytest.raises(LinkPartitionedError):
            f.check_send(0, 2)

    def test_seeded_dup_reorder_deterministic(self):
        msgs = [({"op": "x", "n": i}, b"") for i in range(40)]

        def run():
            f = NetFabric()
            f.arm(dup_rate=0.3, reorder_rate=0.3, seed=7)
            out = []
            prev = None
            for m, b in msgs:
                ds = f.deliveries(0, 1, m, b, prev=prev)
                out.append(tuple(d[0]["n"] for d in ds))
                prev = (m, b)
            return out, f.frames_duplicated, f.frames_reordered

        a, b = run(), run()
        assert a == b
        assert a[1] > 0 and a[2] > 0
        # exactly one reply per received frame, always the current one
        f = NetFabric()
        f.arm(dup_rate=1.0)
        ds = f.deliveries(0, 1, {"op": "y"}, b"")
        assert [d[2] for d in ds] == [False, True]

    def test_confs_registered(self):
        for key in ("spark.rapids.tpu.faults.net.partition",
                    "spark.rapids.tpu.faults.net.delayMs",
                    "spark.rapids.tpu.faults.net.dup.rate",
                    "spark.rapids.tpu.faults.net.reorder.rate",
                    "spark.rapids.tpu.faults.net.seed",
                    "spark.rapids.tpu.faults.net.afterOps",
                    "spark.rapids.tpu.dcn.suspect.strikes",
                    "spark.rapids.tpu.dcn.quorum.enabled",
                    "spark.rapids.tpu.dcn.quorum.windowMs"):
            assert key in ALL_ENTRIES
        from spark_rapids_tpu.faults.injector import POINTS
        for p in ("dcn.partition", "dcn.net.dup", "dcn.net.reorder"):
            assert p in POINTS
        from spark_rapids_tpu.parallel.dcn import DCN_OPS
        assert "vote" in DCN_OPS


# ---------------------------------------------------------------------------
# Suspicion strikes: delay is not death.
# ---------------------------------------------------------------------------

class TestSuspicionStrikes:
    def test_suspected_before_declared(self, net_conf):
        TpuConf.set_session("spark.rapids.tpu.dcn.suspect.strikes", 4)
        try:
            coord, pgs = _make_group(2, hb_timeout=0.3)
            try:
                pgs[1]._closed = True
                pgs[1]._server.freeze()
                _wait(lambda: 1 in coord.suspected(), timeout=5,
                      what="suspicion")
                # suspected is NOT declared: no epoch bump yet
                assert coord.declared_dead() == []
                assert coord.epoch == 0
                _wait(lambda: coord.declared_dead() == [1], timeout=10,
                      what="declaration after strikes")
                assert coord.epoch >= 1
            finally:
                _close_all(pgs)
        finally:
            TpuConf.unset_session("spark.rapids.tpu.dcn.suspect.strikes")

    def test_delay_under_strike_horizon_not_declared(self, net_conf):
        """Injected link delay below strikes x hb_timeout must cause
        suspicion at most — never a death declaration (the satellite's
        whole point: congestion is not death)."""
        coord, pgs = _make_group(2, hb_timeout=0.4, interval=0.1)
        try:
            FABRIC.arm(delay="1>0:250")
            time.sleep(2.5)  # many delayed heartbeat cycles
            assert coord.declared_dead() == []
            assert coord.epoch == 0
        finally:
            FABRIC.reset()
            _close_all(pgs)

    def test_contact_clears_suspicion(self, net_conf):
        """Heartbeat gaps of ~1.4 windows: each gap SUSPECTS the rank,
        each arrival clears it — with the default 2 strikes nobody is
        ever declared."""
        coord, pgs = _make_group(2, hb_timeout=0.5, interval=0.7)
        try:
            time.sleep(2.5)
            assert coord.declared_dead() == []
            assert coord.epoch == 0
        finally:
            _close_all(pgs)

    def test_strikes_one_restores_declare_on_first_timeout(self,
                                                           net_conf):
        """The escape hatch: strikes=1 declares on the first missed
        window — the same 1.4-window heartbeat gaps that survive the
        default now get a rank declared."""
        TpuConf.set_session("spark.rapids.tpu.dcn.suspect.strikes", 1)
        try:
            coord, pgs = _make_group(2, hb_timeout=0.5, interval=0.7)
            try:
                _wait(lambda: len(coord.declared_dead()) > 0, timeout=8,
                      what="strikes=1 declaration")
            finally:
                _close_all(pgs)
        finally:
            TpuConf.unset_session("spark.rapids.tpu.dcn.suspect.strikes")


# ---------------------------------------------------------------------------
# Delivery hardening: duplicated/reordered frames are idempotent.
# ---------------------------------------------------------------------------

class TestDeliveryDedup:
    def test_dup_rate_full_group_still_correct(self, net_conf, tmp_path):
        """Every frame delivered twice: collectives, registers and
        fetches all succeed with byte-identical results, replays
        counted in frames_deduped."""
        coord, pgs = _make_group(2, hb_timeout=30.0, interval=60.0)
        try:
            before = QueryStats.process().frames_deduped
            FABRIC.arm(dup_rate=1.0, seed=3)
            outs = [None, None]

            def gather(i):
                outs[i] = pgs[i].all_gather_bytes(
                    f"payload-{i}".encode(), tag="dup-gather")

            ts = [threading.Thread(target=gather, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            assert outs[0] == outs[1] == [b"payload-0", b"payload-1"]
            # data plane: a duplicated fetch replays its payload
            sh = DcnShuffle(pgs[0], 1, str(tmp_path / "dup"))
            sh.write_partition(0, pa.table({"x": [1, 2, 3]}))
            sh.local.finish_writes()
            payload = pgs[1].fetch(0, sh.id, 0)
            assert payload
            pgs[0].unregister_shuffle(sh.id)
            sh.local.close()
            assert QueryStats.process().frames_deduped > before
        finally:
            FABRIC.reset()
            _close_all(pgs)

    def test_duplicated_register_single_incarnation(self, net_conf):
        """The non-idempotent op: a duplicated re-register must bump
        the incarnation exactly ONCE (and count one flap, not two) —
        the dedup journal replays the second delivery."""
        coord, pgs = _make_group(2, hb_timeout=0.4)
        reborn = None
        try:
            pgs[1]._closed = True
            pgs[1]._server.freeze()
            _wait(lambda: coord.declared_dead() == [1], timeout=10,
                  what="declaration")
            FABRIC.arm(dup_rate=1.0, seed=5)
            reborn = ProcessGroup(1, 2, ("127.0.0.1", coord.port),
                                  heartbeat_interval=60.0)
            assert reborn.inc == 1  # exactly one bump despite the dup
            assert coord._inc[1] == 1
            assert coord.flap_snapshot()["counts"].get(1, 0) <= 1
        finally:
            FABRIC.reset()
            if reborn is not None:
                reborn.close()
            _close_all(pgs)

    def test_reorder_rate_full_group_still_correct(self, net_conf):
        coord, pgs = _make_group(2, hb_timeout=30.0, interval=60.0)
        try:
            FABRIC.arm(reorder_rate=1.0, seed=9)
            for tag in ("ro-1", "ro-2", "ro-3"):
                outs = [None, None]

                def gather(i, tag=tag):
                    outs[i] = pgs[i].all_gather_bytes(
                        f"{tag}-{i}".encode(), tag=tag)

                ts = [threading.Thread(target=gather, args=(i,))
                      for i in range(2)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=30)
                assert outs[0] == outs[1]
                assert outs[0] == [f"{tag}-0".encode(),
                                   f"{tag}-1".encode()]
        finally:
            FABRIC.reset()
            _close_all(pgs)


# ---------------------------------------------------------------------------
# Quorum-fenced failover + heal-and-rejoin (the tentpole's control plane).
# ---------------------------------------------------------------------------

class TestQuorumFencedFailover:
    def test_majority_side_promotes_minority_coordinator_parks(
            self, net_conf):
        """Partition {0(coord)} | {1, 2}: the majority votes the
        coordinator unreachable and promotes rank 1 at generation 2;
        the OLD coordinator loses its quorum and parks (zero epoch
        bumps — no divergent declarations), so its host rank parks
        typed too.  At most one coordinator generation stays active.
        After heal, rank 0 discovers generation 2, its stale
        coordinator ABDICATES, and it rejoins under flap damping."""
        coord, pgs = _make_group(3, hb_timeout=0.6)
        try:
            s0 = QueryStats.process().snapshot()
            FABRIC.cut("0|1+2")
            # majority side: collectives complete after quorum-fenced
            # failover to rank 1
            outs = [None, None, None]

            def gather(i, tag="post-cut"):
                outs[i] = pgs[i].all_gather_map(
                    f"p{i}".encode(), tag=tag, allow_shrunk=True)

            ts = [threading.Thread(target=gather, args=(i,))
                  for i in (1, 2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert outs[1] is not None and outs[2] is not None
            assert outs[1] == outs[2]
            assert sorted(outs[1][0]) == [1, 2]
            assert pgs[1].coord_rank == 1 and pgs[2].coord_rank == 1
            assert pgs[1].coordinator is not None
            assert pgs[1].coordinator.generation == 2
            # the minority coordinator parked: no declarations of 1/2,
            # and its host rank fails typed
            _wait(lambda: coord.quorum_lost, timeout=10,
                  what="old coordinator quorum park")
            assert coord.declared_dead() == []
            with pytest.raises(QuorumLostError):
                pgs[0].barrier(tag="minority-barrier")
            assert pgs[0].quorum_lost
            # THE invariant: at most one ACTIVE coordinator generation
            assert len(_active_coordinators(coord, pgs)) == 1
            assert not coord.is_active()
            epoch_mid = pgs[1].epoch
            d = QueryStats.delta_since(s0)
            assert d["quorum_losses"] >= 1
            assert d["coordinator_failovers"] >= 2

            # HEAL: rank 0 probes, finds gen 2, abdicates its stale
            # coordinator, re-registers (fresh incarnation)
            FABRIC.heal()
            # the rejoin clears quorum_lost first, then absorbs the new
            # membership (generation, epoch), then counts itself: wait
            # for the last of these, not the first
            _wait(lambda: not pgs[0].quorum_lost
                  and QueryStats.delta_since(s0)["rank_rejoins"] >= 1,
                  timeout=60,
                  what=lambda: (
                      f"rank 0 heal + rejoin (pg0: ql="
                      f"{pgs[0].quorum_lost} coord_rank="
                      f"{pgs[0].coord_rank} gen={pgs[0].coord_gen} "
                      f"inc={pgs[0].inc} defer_in="
                      f"{pgs[0]._heal_defer_until - time.monotonic():.1f}"
                      f" fenced={pgs[0].fenced} "
                      f"lost={pgs[0].coordinator_lost}; old coord: "
                      f"abdicated={coord._abdicated} "
                      f"ql={coord.quorum_lost}; new coord flaps="
                      f"{pgs[1].coordinator.flap_snapshot()})"))
            assert pgs[0].coord_rank == 1
            assert pgs[0].coord_gen == 2
            assert coord._abdicated
            assert len(_active_coordinators(coord, pgs)) == 1
            d = QueryStats.delta_since(s0)
            assert d["rank_rejoins"] >= 1
            # zero churn beyond the single rejoin bump
            epoch_after = pgs[0].epoch
            assert epoch_after <= epoch_mid + 1
            time.sleep(1.0)
            assert pgs[1].coordinator.epoch == epoch_after
            # the healed world=3 group completes a collective again
            # (a FRESH tag: the parked-era tag replays from the journal
            # by design)
            outs = [None, None, None]
            ts = [threading.Thread(target=gather, args=(i, "post-heal"))
                  for i in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert outs[0] == outs[1] == outs[2]
            assert sorted(outs[0][0]) == [0, 1, 2]
        finally:
            FABRIC.reset()
            _close_all(pgs)

    def test_minority_rank_parks_instead_of_promoting(self, net_conf):
        """Partition {0(coord), 1} | {2}: rank 2 cannot gather a
        connectivity quorum (it reaches nobody) — it PARKS typed
        instead of promoting, while the majority simply declares it
        dead and keeps serving under the ORIGINAL coordinator
        generation.  Heal: rank 2 re-registers (one epoch bump, the
        flap-damping contract)."""
        coord, pgs = _make_group(3, hb_timeout=0.5)
        try:
            FABRIC.cut("2|0+1")
            with pytest.raises(QuorumLostError):
                pgs[2].barrier(tag="cut-barrier")
            assert pgs[2].quorum_lost
            assert pgs[2].coordinator is None  # never promoted
            # majority unaffected: same coordinator, generation 1
            _wait(lambda: coord.declared_dead() == [2], timeout=10,
                  what="majority declares rank 2")
            assert not coord.quorum_lost
            assert coord.generation == 1
            assert pgs[0].coord_rank == 0 and pgs[1].coord_rank == 0
            outs = [None, None]

            def gather(i):
                outs[i] = pgs[i].all_gather_map(
                    f"p{i}".encode(), tag="majority-gather",
                    allow_shrunk=True)

            ts = [threading.Thread(target=gather, args=(i,))
                  for i in (0, 1)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            assert outs[0] == outs[1]
            assert sorted(outs[0][0]) == [0, 1]
            death_epoch = coord.epoch

            FABRIC.heal()
            _wait(lambda: not pgs[2].quorum_lost, timeout=30,
                  what="rank 2 rejoin")
            assert pgs[2].inc == 1  # fresh incarnation
            assert coord.declared_dead() == []
            assert coord.epoch == death_epoch + 1  # exactly one bump
            time.sleep(1.0)
            assert coord.epoch == death_epoch + 1  # ...and it stays
        finally:
            FABRIC.reset()
            _close_all(pgs)

    def test_asymmetric_link_parks_not_promotes(self, net_conf):
        """One-way loss 2->0 only: rank 2's frames to the coordinator
        vanish while every other link flows.  The voters still reach
        the coordinator, so rank 2 gets no quorum — it parks typed;
        the majority declares it (its heartbeats stopped arriving) and
        keeps the original coordinator."""
        coord, pgs = _make_group(3, hb_timeout=0.5)
        try:
            FABRIC.cut("2>0")
            with pytest.raises(QuorumLostError):
                pgs[2].barrier(tag="asym-barrier")
            assert pgs[2].quorum_lost
            assert pgs[2].coordinator is None
            _wait(lambda: coord.declared_dead() == [2], timeout=10,
                  what="declaration of the one-way-cut rank")
            assert coord.generation == 1 and not coord.quorum_lost
            assert pgs[1].coord_rank == 0  # no failover on the majority
            FABRIC.heal()
            _wait(lambda: not pgs[2].quorum_lost, timeout=30,
                  what="asymmetric heal + rejoin")
            assert coord.declared_dead() == []
        finally:
            FABRIC.reset()
            _close_all(pgs)

    def test_quorum_disabled_escape_hatch(self, net_conf):
        """dcn.quorum.enabled=false restores the fail-stop-biased
        behavior: the cut-off rank presumes coordinator death, burns
        its promote window against the (deterministic but unreachable)
        successor, and fails PERMANENT — never the typed quorum park."""
        TpuConf.set_session("spark.rapids.tpu.dcn.quorum.enabled", False)
        try:
            coord, pgs = _make_group(3, hb_timeout=0.5)
            try:
                FABRIC.cut("2|0+1")
                from spark_rapids_tpu.parallel.dcn import \
                    CoordinatorLostError
                with pytest.raises(CoordinatorLostError) as ei:
                    pgs[2].barrier(tag="unfenced-barrier")
                assert not isinstance(ei.value, QuorumLostError)
                assert not pgs[2].quorum_lost
            finally:
                FABRIC.arm()
                _close_all(pgs)
        finally:
            TpuConf.unset_session("spark.rapids.tpu.dcn.quorum.enabled")


# ---------------------------------------------------------------------------
# The tier-1 partition chaos differential (thread ranks, world=3 and 5).
# ---------------------------------------------------------------------------

def _shuffle_rows(world, n_parts, rows_per, pgs, tmp, cut):
    """Write+commit a DcnShuffle on every rank, cut the fabric, reduce
    on the majority; returns (rows_by_rank, parked_errors_by_rank)."""
    shuffles = [DcnShuffle(pg, n_parts, os.path.join(tmp, f"r{pg.rank}"))
                for pg in pgs]
    for rank, sh in enumerate(shuffles):
        for p in range(n_parts):
            sh.write_partition(p, pa.table(
                {"r": [rank] * rows_per, "p": [p] * rows_per,
                 "v": list(range(rows_per))}))
    ts = [threading.Thread(target=sh.commit) for sh in shuffles]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert all(sh.committed == list(range(world)) for sh in shuffles)
    if cut:
        FABRIC.cut(cut)
    rows = {}
    parked = {}

    def reduce_rank(r):
        try:
            n = 0
            for p in shuffles[r].my_parts():
                n += sum(t_.num_rows
                         for t_ in shuffles[r].read_partition(p))
            for p in shuffles[r].adopt_orphans():
                n += sum(t_.num_rows
                         for t_ in shuffles[r].read_partition(p))
            rows[r] = n
            shuffles[r].close()
        except Exception as e:
            parked[r] = e
            shuffles[r].close()

    ts = [threading.Thread(target=reduce_rank, args=(r,))
          for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    return rows, parked


class TestPartitionChaosDifferentialTier1:
    @pytest.mark.parametrize("world,minority,cut", [
        (3, [2], "2|0+1"),
        (5, [3, 4], "3+4|0+1+2"),
    ])
    def test_majority_completes_minority_parks_then_heals(
            self, net_conf, tmp_path, world, minority, cut):
        n_parts, rows_per = 2 * world, 16
        coord, pgs = _make_group(world, hb_timeout=0.5,
                                 wait_timeout=30.0)
        try:
            s0 = QueryStats.process().snapshot()
            rows, parked = _shuffle_rows(world, n_parts, rows_per, pgs,
                                         str(tmp_path), cut)
            majority = [r for r in range(world) if r not in minority]
            # the majority's union covers EVERY rank's committed map
            # output — byte count identical to the fault-free total
            assert sum(rows.get(r, 0) for r in majority) \
                == world * n_parts * rows_per
            # every minority rank parked TYPED (QuorumLostError direct,
            # or wrapped typed by the retry layer) — never wrong rows
            from spark_rapids_tpu.faults.recovery import QueryFaulted
            for r in minority:
                assert r in parked, f"rank {r} did not park: {rows}"
                e = parked[r]
                assert isinstance(e, (QuorumLostError, QueryFaulted)), e
                assert pgs[r].quorum_lost
            assert not coord.quorum_lost
            assert coord.generation == 1  # no election happened
            assert len(_active_coordinators(coord, pgs)) == 1
            d = QueryStats.delta_since(s0)
            assert d["quorum_losses"] >= len(minority)
            death_epoch = coord.epoch

            # HEAL: every parked rank rejoins; zero churn beyond one
            # rejoin bump per rank (the flap-damping contract)
            FABRIC.heal()
            for r in minority:
                _wait(lambda r=r: not pgs[r].quorum_lost, timeout=40,
                      what=f"rank {r} rejoin")
            assert coord.declared_dead() == []
            assert coord.epoch == death_epoch + len(minority)
            time.sleep(1.0)
            assert coord.epoch == death_epoch + len(minority)
            d = QueryStats.delta_since(s0)
            assert d["rank_rejoins"] >= len(minority)
        finally:
            FABRIC.reset()
            _close_all(pgs)


# ---------------------------------------------------------------------------
# Wire satellites: the sibling-sweep demotion and the result-stream
# delivery check at the protocol decoder.
# ---------------------------------------------------------------------------

def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TestWireClientSweepDemotion:
    def test_failover_demotes_dark_endpoints(self, session):
        """Under a half-partitioned fleet the GOAWAY sweep must not
        burn its dials on the dark side in fixed order: an endpoint
        that refused a dial is demoted behind a backoff window and
        sorts LAST on subsequent sweeps."""
        from spark_rapids_tpu.server import SqlFrontDoor, WireClient
        from spark_rapids_tpu.server.protocol import ServerDraining
        door = SqlFrontDoor(session).start()
        try:
            dead_addr = ("127.0.0.1", _free_port())  # nobody listening
            live_addr = ("127.0.0.1", door.port)
            c = WireClient(*live_addr)
            try:
                # GOAWAY advertising the dark sibling FIRST: the sweep
                # dials it once, demotes it, then lands on the door
                c._failover(ServerDraining(
                    "drain", siblings=[dead_addr], retry_after_ms=1))
                assert c.goaways_survived == 1
                assert c._down[dead_addr][0] >= 1
                assert c.endpoints_demoted >= 1
                # while the demotion window holds, healthy endpoints
                # sort first and the dark one last
                c._down[dead_addr][1] = time.monotonic() + 30
                order = c._sweep_order([dead_addr, live_addr])
                assert order == [live_addr, dead_addr]
                # a second failover never re-dials the demoted side
                fails_before = c._down[dead_addr][0]
                c._failover(ServerDraining(
                    "again", siblings=[dead_addr], retry_after_ms=1))
                assert c.goaways_survived == 2
                assert c._down[dead_addr][0] == fails_before
                # ...and a successful dial restores full standing
                c._down[live_addr] = [3, time.monotonic() + 30]
                c._connect(live_addr)
                assert live_addr not in c._down
            finally:
                c.close()
        finally:
            door.close()


class TestResultStreamDeliveryCheck:
    def _run_stream(self, frames):
        """Feed a crafted frame sequence to WireClient._collect_result
        over a socketpair."""
        import socket as _socket

        from spark_rapids_tpu.server import WireClient
        from spark_rapids_tpu.server import protocol as P
        a, b = _socket.socketpair()
        try:
            def serve():
                for ftype, payload in frames:
                    P.send_frame(b, ftype, payload)

            t = threading.Thread(target=serve)
            t.start()
            c = object.__new__(WireClient)
            c._sock = a
            try:
                return c._collect_result()
            finally:
                t.join(timeout=10)
        finally:
            a.close()
            b.close()

    def _ipc(self):
        t = pa.table({"x": [1, 2, 3]})
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, t.schema) as w:
            w.write_table(t)
        return sink.getvalue().to_pybytes()

    def test_correct_count_passes(self):
        from spark_rapids_tpu.server import protocol as P
        meta = P.pack_json({"query_id": "q", "schema": []})
        rs = self._run_stream([
            (P.RSP_META, meta),
            (P.RSP_BATCH, self._ipc()),
            (P.RSP_END, P.pack_json({"batches": 1, "rows": 3})),
        ])
        assert rs.rows() == [(1,), (2,), (3,)]

    def test_duplicated_batch_frame_detected_typed(self):
        """A batch frame delivered twice (broken middlebox): the END
        count exposes it as a typed ProtocolError — rows are never
        silently double-counted."""
        from spark_rapids_tpu.server import protocol as P
        meta = P.pack_json({"query_id": "q", "schema": []})
        ipc = self._ipc()
        with pytest.raises(P.ProtocolError, match="duplicated or lost"):
            self._run_stream([
                (P.RSP_META, meta),
                (P.RSP_BATCH, ipc),
                (P.RSP_BATCH, ipc),  # the duplicate
                (P.RSP_END, P.pack_json({"batches": 1})),
            ])

    def test_lost_batch_frame_detected_typed(self):
        from spark_rapids_tpu.server import protocol as P
        meta = P.pack_json({"query_id": "q", "schema": []})
        with pytest.raises(P.ProtocolError, match="duplicated or lost"):
            self._run_stream([
                (P.RSP_META, meta),
                (P.RSP_END, P.pack_json({"batches": 2})),
            ])

    def test_reordered_end_before_batch_detected(self):
        """END arriving ahead of its batch (reordered delivery): the
        count mismatch surfaces typed at the decoder."""
        from spark_rapids_tpu.server import protocol as P
        meta = P.pack_json({"query_id": "q", "schema": []})
        with pytest.raises(P.ProtocolError, match="duplicated or lost"):
            self._run_stream([
                (P.RSP_META, meta),
                (P.RSP_END, P.pack_json({"batches": 1})),
            ])


# ---------------------------------------------------------------------------
# The @slow multi-process partition chaos differential.
# ---------------------------------------------------------------------------

def _write_shards(tmp, world, rows=600):
    import numpy as np
    import pyarrow.parquet as pq
    rng = np.random.default_rng(17)
    for r in range(world):
        n = rows
        t = pa.table({
            "k": rng.integers(0, 23, n),
            "s": rng.choice(["ab", "cd", "ef"], n),
            "v": rng.integers(0, 1000, n),
            "w": rng.random(n),
        })
        pq.write_table(t, os.path.join(tmp, f"part-{r}.parquet"))


def _run_world(tmp, out, world, port, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    procs = []
    for r in range(world):
        cmd = [sys.executable, os.path.join(REPO, "tests",
                                            "dcn_worker.py"),
               "--rank", str(r), "--world", str(world),
               "--port", str(port), "--data", tmp, "--out", out,
               "--hb-interval", "0.2", "--hb-timeout", "1.0",
               "--wait-timeout", "60", "--quorum-window-ms", "4000",
               *extra]
        procs.append(subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT))
    return procs


@pytest.mark.slow
class TestDupReorderMiniSuiteDifferential:
    def test_seeded_dup_reorder_rate_across_query_suite(self, tmp_path):
        """The distributed query mini-suite (grouped agg, top-k,
        shuffled join, broadcast join — every DCN collective and
        data-plane shape) under a seeded dup+reorder rate: results
        byte-identical to the clean distributed run, replays
        attributable (frames_deduped), zero leaked spill handles
        (asserted in-worker)."""
        import socket as _socket
        import numpy as np
        import pyarrow.parquet as pq
        data = str(tmp_path / "data")
        os.makedirs(data)
        _write_shards(data, 3)
        rng = np.random.default_rng(5)
        for r in range(3):
            pq.write_table(pa.table({
                "dk": np.arange(r * 8, r * 8 + 8),
                "dname": [f"d{r}-{i}" for i in range(8)],
            }), os.path.join(data, f"dim-{r}.parquet"))

        def free_port():
            with _socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        def norm(rows):
            return sorted((tuple(r) for r in rows),
                          key=lambda r: tuple(str(x) for x in r))

        for query in ("simple", "topk", "join", "bjoin"):
            outs = {}
            for tag, extra in (
                    ("clean", ()),
                    ("faulted", ("--net-dup-rate", "0.15",
                                 "--net-reorder-rate", "0.1",
                                 "--net-seed", "11"))):
                out = str(tmp_path / f"{query}-{tag}")
                procs = _run_world(data, out, 3, free_port(),
                                   extra=("--query", query, *extra))
                for p in procs:
                    log = p.communicate(timeout=300)[0].decode()
                    assert p.returncode == 0, \
                        f"{query}/{tag}:\n{log[-4000:]}"
                outs[tag] = [json.load(open(f"{out}.{r}"))
                             for r in range(3)]
                if tag == "faulted":
                    deduped = sum(
                        json.load(open(f"{out}.stats.{r}"))
                        ["frames_deduped"] for r in range(3))
                    assert deduped > 0, \
                        f"{query}: no dup/reorder ever replayed"
            for r in range(3):
                assert norm(outs["faulted"][r]) == norm(outs["clean"][r]), \
                    f"{query}: rank {r} diverged under dup/reorder"


@pytest.mark.slow
class TestPartitionChaosDifferentialMultiProcess:
    @pytest.mark.parametrize("world,cut,minority", [
        (3, "2|0+1", [2]),
        (5, "3+4|0+1+2", [3, 4]),
    ])
    def test_partition_mid_query_differential(self, tmp_path, world,
                                              cut, minority):
        import socket as _socket
        data = str(tmp_path / "data")
        os.makedirs(data)
        _write_shards(data, world)

        def free_port():
            with _socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        # fault-free oracle
        out0 = str(tmp_path / "clean")
        procs = _run_world(data, out0, world, free_port())
        for p in procs:
            log = p.communicate(timeout=300)[0].decode()
            assert p.returncode == 0, log[-4000:]
        clean = json.load(open(f"{out0}.0"))
        assert clean

        # partition the minority after 1 shuffle op on each rank, heal
        # at t+12s; majority must match the oracle byte-identically,
        # minority must park typed then rejoin after the heal
        out1 = str(tmp_path / "cut")
        procs = _run_world(
            data, out1, world, free_port(),
            extra=("--net-partition", cut, "--net-after", "1",
                   "--net-heal-s", "12", "--await-parked",
                   ",".join(str(r) for r in minority)))
        logs = []
        for p in procs:
            log = p.communicate(timeout=300)[0].decode()
            logs.append(log)
            assert p.returncode == 0, log[-4000:]
        def norm(rows):
            return sorted((tuple(r) for r in rows),
                          key=lambda r: tuple(str(x) for x in r))

        majority = [r for r in range(world) if r not in minority]
        for r in majority:
            # adoption appends the minority's partitions after a
            # survivor's own, so the row ORDER shifts — the values must
            # be identical, unrounded (same combine order per fragment)
            assert norm(json.load(open(f"{out1}.{r}"))) == norm(clean), \
                f"rank {r} diverged\n{logs[r]}"
        epochs = set()
        for r in majority:
            stats = json.load(open(f"{out1}.stats.{r}"))
            epochs.add(stats["final_epoch"])
        for r in minority:
            marker = json.load(open(f"{out1}.parked.{r}"))
            assert marker["parked"]
            assert marker["error"] in ("QuorumLostError", "QueryFaulted")
            assert marker["rejoined"], marker
        assert len(epochs) == 1  # survivors agree on the epoch
