"""Seeded, conf-driven fault injector with named injection points.

Generalizes the OOM-only ``memory/retry.OOMInjector`` (which stays, for
the RetryOOM/SplitAndRetryOOM protocol) into one injector for every
transient fault class the engine recovers from.  Each registered point
is a place a real deployment loses work: a flaky object-store read, a
mid-write disk error, a lost shuffle fragment, a dropped DCN heartbeat,
a device op failing with a non-OOM XLA error, a cache tier timing out.

Two modes, composable:

  * **deterministic schedule** — ``"io.read:2"`` fails the 2nd
    invocation at ``io.read``; ``"device.op:1:3"`` fails invocations
    1..3 (the repeated-failure shape that drives CPU degradation).
    Re-arming (every :class:`..plan.physical.ExecContext`, mirroring the
    OOM injector) resets the per-point invocation counters, so a
    schedule means "the Nth op of each query".
  * **probabilistic rate** — every invocation at the selected points
    fails with probability ``rate``, drawn from a ``random.Random``
    seeded by ``faults.inject.seed`` so chaos runs replay exactly.

Injection raises :class:`InjectedFault` (a
:class:`..faults.recovery.TransientFault`), which the recovery layer
retries/degrades exactly like the real fault it stands in for.  Every
injection lands a ``fault:injected`` trace mark and a
``QueryStats.faults_injected`` count; per-point cumulative totals
survive re-arming so multi-query chaos suites can assert coverage.
"""

from __future__ import annotations

import random
import threading
from typing import Dict, List, Tuple

from .recovery import TransientFault

__all__ = ["POINTS", "InjectedFault", "FaultInjector", "INJECTOR"]

# The registry of injection points.  Adding a point means adding the
# matching recovery path and a docs/robustness.md row — the leak suite
# parametrizes over this tuple, so an unrecovered point fails tests.
# ``dcn.peer_kill`` is special: it does not stand in for a recoverable
# fault but for PEER DEATH — the DCN layer catches the injected fault
# and kills the rank (silent heartbeat stop, or a hard process kill
# under spark.rapids.tpu.dcn.kill.mode=hard), driving the killed-peer
# chaos differential deterministically ("kill rank R after N ops").
#
# The GRAY points (ISSUE 7) do not raise at all — call sites consult
# :meth:`FaultInjector.maybe_fire` and ACT the gray failure out:
#   * ``shuffle.corrupt`` / ``spill.corrupt`` — flip one bit in the
#     payload so the integrity layer (faults/integrity.py) must catch
#     it and route recovery;
#   * ``cache.corrupt`` — treat the found cache entry as corrupt
#     (drop-and-miss, never a poisoned hit);
#   * ``device.hang`` — wedge the dispatch until cancelled (the
#     watchdog's prey: no batch progress, no exception);
#   * ``dcn.slow_peer`` — the peer server answers, but late (the
#     straggler-hedging prey: slow is not dead);
#   * ``server.conn`` — the network front door's client drops
#     mid-result-stream (server/endpoint.py consults maybe_fire at each
#     BATCH send and ACTS the drop out: closes the connection and
#     unwinds through the real disconnect path — cooperative cancel,
#     permit + quota + spool release; the leak-hygiene and loadgen
#     suites assert zero residue).
#   * ``server.malformed`` — hostile input at the front door's recv
#     path (server/endpoint.py consults maybe_fire after each request
#     frame decodes and ACTS the corruption out: the frame is treated
#     as a resyncable decode failure, driving the strike-budget
#     machinery — typed BAD_REQUEST, strike counted, connection
#     disconnected when the budget burns — so hostile input composes
#     with peer kills and partitions in the chaos differential);
#   * ``dcn.coordinator_kill`` — like ``dcn.peer_kill`` but the rank
#     that dies is HOSTING the coordinator: silent mode freezes the
#     coordinator too (control requests are received and never
#     answered), driving the coordinator-failover chaos differential;
#     hard mode exits the hosting process.
#
# The NETWORK points (ISSUE 14) ride the link-fault fabric
# (faults/netfabric.py) — the fault is a property of a LINK between two
# healthy ranks, not of a host:
#   * ``dcn.partition`` — drop the Nth fabric-checked DCN send (a
#     one-message link blip: the sender sees a typed
#     LinkPartitionedError and recovers by re-dial/retry; standing
#     partitions come from the faults.net.partition program instead);
#   * ``dcn.net.dup`` / ``dcn.net.reorder`` — gray delivery faults at
#     the RECEIVING serve loop (maybe_fire): a frame is delivered
#     twice, or the connection's previous frame is re-delivered late —
#     the per-request dedup journal must make both idempotent.
POINTS = ("io.read", "io.write", "shuffle.fragment", "dcn.heartbeat",
          "device.op", "cache.lookup", "dcn.peer_kill",
          "shuffle.corrupt", "spill.corrupt", "cache.corrupt",
          "device.hang", "dcn.slow_peer", "server.conn",
          "server.malformed", "dcn.coordinator_kill",
          "dcn.partition", "dcn.net.dup", "dcn.net.reorder")


class InjectedFault(TransientFault):
    """A synthetic transient fault raised at an injection point."""


def _parse_schedule(spec: str) -> Dict[str, List[Tuple[int, int]]]:
    """``"point:N[:K]"`` comma list → {point: [(first_n, count)]}: fail
    invocations ``first_n .. first_n+count-1`` (1-based) at ``point``."""
    out: Dict[str, List[Tuple[int, int]]] = {}
    for item in (spec or "").split(","):
        item = item.strip()
        if not item:
            continue
        parts = item.split(":")
        if len(parts) not in (2, 3):
            raise ValueError(
                f"bad fault schedule entry {item!r} (want point:N[:K])")
        point = parts[0].strip()
        if point not in POINTS:
            raise ValueError(
                f"unknown injection point {point!r}; registered: {POINTS}")
        n = int(parts[1])
        k = int(parts[2]) if len(parts) == 3 else 1
        if n < 1 or k < 1:
            raise ValueError(f"bad fault schedule entry {item!r}: "
                             f"N and K must be >= 1")
        out.setdefault(point, []).append((n, k))
    return out


class FaultInjector:
    """Process-global injector consulted by every registered point.

    Armed from the faults confs at each :class:`ExecContext` creation
    (like the OOM injector, an unarmed conf CLEARS previous arming —
    and, being process-global, deterministic schedules are only
    meaningful for one query at a time; chaos rate mode is the
    concurrent-safe mode).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._sched: Dict[str, List[Tuple[int, int]]] = {}
        self._rate = 0.0
        self._rate_points: Tuple[str, ...] = POINTS
        self._rng = random.Random(0)
        self._armed_args = None  # last arm() arguments (see arm())
        self._counts: Dict[str, int] = {}
        # fingerprint conditioning (faults.inject.fingerprint): when
        # set, injection fires — and deterministic counters advance —
        # only inside queries whose control carries this statement
        # fingerprint, so a poison scenario targets ONE statement in a
        # mixed workload without touching healthy queries
        self._fingerprint = ""
        # cumulative per-point injections: survives re-arming (chaos
        # suites assert coverage across several queries), reset only by
        # reset_totals()
        self.injected_total: Dict[str, int] = {p: 0 for p in POINTS}

    # -- arming -------------------------------------------------------------------
    def arm(self, schedule: str = "", rate: float = 0.0,
            points: str = "", seed: int = 0,
            fingerprint: str = "") -> None:
        sched = _parse_schedule(schedule)
        sel = tuple(p.strip() for p in points.split(",") if p.strip()) \
            if points else POINTS
        for p in sel:
            if p not in POINTS:
                raise ValueError(
                    f"unknown injection point {p!r}; registered: {POINTS}")
        args = (schedule, float(rate), sel, seed, fingerprint)
        with self._lock:
            self._sched = sched
            self._rate = max(0.0, float(rate))
            self._rate_points = sel
            self._fingerprint = fingerprint or ""
            # Re-arming with IDENTICAL arguments (every ExecContext of a
            # chaos run re-arms from the same confs) preserves the RNG
            # stream: rate mode stays a true seeded rate across queries.
            # Re-seeding on every query would collapse "rate" into a
            # fixed threshold over the first few draws of one sequence —
            # all-or-nothing per send position instead of probabilistic.
            # Any changed argument reseeds, so runs still replay exactly.
            if args != self._armed_args:
                self._rng = random.Random(seed or 0)
                self._armed_args = args
            self._counts = {}

    def arm_from_conf(self, conf) -> None:
        self.arm(
            schedule=conf["spark.rapids.tpu.faults.inject.schedule"],
            rate=conf["spark.rapids.tpu.faults.inject.rate"],
            points=conf["spark.rapids.tpu.faults.inject.points"],
            seed=conf["spark.rapids.tpu.faults.inject.seed"],
            fingerprint=conf[
                "spark.rapids.tpu.faults.inject.fingerprint"])

    # -- state --------------------------------------------------------------------
    def armed(self) -> bool:
        """True while any injection (schedule or rate) can fire."""
        with self._lock:
            return bool(self._sched) or self._rate > 0.0

    def deterministic_armed(self) -> bool:
        """True while a deterministic schedule is armed: the pipeline
        runs serially (depth 0) so "the Nth op at P" is well-defined —
        the same determinism contract as the OOM injector."""
        with self._lock:
            return bool(self._sched)

    def jitter(self) -> float:
        """A seeded jitter factor in [0.5, 1.0] for the backoff sleeps
        (deterministic under a seeded chaos run)."""
        with self._lock:
            return 0.5 + 0.5 * self._rng.random()

    # -- the injection check --------------------------------------------------------
    @staticmethod
    def _current_fingerprint() -> str:
        """The RUNNING query's statement fingerprint (set by the
        scheduler on its control), '' when none/unknown."""
        from ..service import cancel
        ctl = cancel.current()
        return getattr(ctl, "fingerprint", None) or "" \
            if ctl is not None else ""

    def _select(self, point: str) -> int:
        """Count one invocation at ``point``; return the (1-based)
        invocation number when the schedule or chaos rate selects it,
        else 0.  Accounting (stats + trace mark) is the caller's —
        through :meth:`maybe_raise` or :meth:`maybe_fire`.

        With fingerprint conditioning armed, invocations from OTHER
        queries neither count nor fire: "the Nth op at P" means the
        Nth op of the targeted statement."""
        with self._lock:
            if not self._sched and self._rate <= 0.0:
                return 0
            fp = self._fingerprint
        if fp and self._current_fingerprint() != fp:
            return 0
        with self._lock:
            n = self._counts.get(point, 0) + 1
            self._counts[point] = n
            fire = any(first <= n < first + count
                       for first, count in self._sched.get(point, ()))
            if not fire and self._rate > 0.0 and point in self._rate_points:
                fire = self._rng.random() < self._rate
            if not fire:
                return 0
            self.injected_total[point] += 1
            return n

    def _account(self, point: str, n: int, desc: str) -> None:
        from ..utils import tracing
        from ..utils.metrics import QueryStats
        QueryStats.get().faults_injected += 1
        tracing.mark(None, "fault:injected", "fault", point=point, n=n,
                     desc=desc)

    def maybe_raise(self, point: str, desc: str = "") -> None:
        """Count one invocation at ``point``; raise :class:`InjectedFault`
        when the schedule or the chaos rate selects it."""
        n = self._select(point)
        if not n:
            return
        self._account(point, n, desc)
        raise InjectedFault(
            f"injected fault at {point} (invocation {n}"
            + (f", {desc}" if desc else "") + ")", point=point)

    def maybe_fire(self, point: str, desc: str = "") -> bool:
        """The GRAY-point check: count one invocation and return True
        when selected — the call site then ACTS the failure out
        (corrupt the payload, wedge the dispatch, delay the reply)
        instead of raising, because gray failures don't raise."""
        n = self._select(point)
        if not n:
            return False
        self._account(point, n, desc)
        return True

    # -- introspection --------------------------------------------------------------
    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {"schedule": {p: list(v) for p, v in self._sched.items()},
                    "rate": self._rate,
                    "fingerprint": self._fingerprint,
                    "counts": dict(self._counts),
                    "injected_total": dict(self.injected_total)}

    def reset_totals(self) -> None:
        with self._lock:
            self.injected_total = {p: 0 for p in POINTS}


INJECTOR = FaultInjector()
