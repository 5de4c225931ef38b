#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, the entry points a user calls: ``Session.get_or_create()``,
``read_parquet`` over TPC-H SF1 written by the repo's own seeded generator,
five queries that between them walk the code the platform selects only on a
TPU (scans and stage pulls pipelined at depth 2) and the kernels tier-1 has
only seen on the CPU (dense and sort-merge joins, string-grid and dense
aggregation, region fusion), each run once cold
and three times warm with every run's rows compared to the query's pandas
oracle; then one prepared statement served twice through the wire door; and,
when more than one device is visible, a shuffled join + grouped aggregate
over the ICI mesh compared with the single-chip answer.

Prints one JSON line per phase as it goes, so a killed run still says where
it was, then one ``{"summary": ...}`` line with everything observed, and as
the last line of stdout the verdict and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Exits 0 only when the platform is ``tpu`` and every phase passed; without an
accelerator it exits 2 and prints no result.  The numbers it prints are observations of one
run, not a benchmark: nothing is claimed from them.

    python chip_smoke.py                 # the whole smoke, SF1
    python chip_smoke.py --queries q6    # one query + served (+ ICI)
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import glob
import hashlib
import importlib.metadata
import json
import os
import re
import statistics
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

DATA_DIR = os.path.join(REPO, ".bench_data")
# q6, q3 and q13 stay whatever is cut; a cut comes from the end
QUERIES = ["q6", "q1", "q3", "q13", "ds_q3"]
WARM_RUNS = 3
BUDGET_S = 1140.0  # the whole run; the driver allows 1200

_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_STAT_KEYS = ["blocking_fetches", "async_fetches", "fetch_wait_s",
              "h2d_wait_s", "compiles", "compile_s", "degraded_batches",
              "transient_retries", "fused_regions"]
# operators the planner places on the CPU today, by query: accounted for
# here by name and reason instead of dropping the placement check
CPU_PLACED = {
    "q1": {"Sort": "the sort keys l_returnflag and l_linestatus are "
                   "host-carried string columns; the sort is over the "
                   "aggregate's handful of output rows"},
}
_VALIDATE = "spark.rapids.tpu.test.validateExecsOnTpu"


class Watchdog(threading.Thread):
    """Ends the run with the phase's name when a phase passes its
    deadline.  A compile that hangs cannot be interrupted from Python, so
    the process exits from this thread."""

    def __init__(self, budget_s: float, device: dict):
        super().__init__(daemon=True, name="chip-smoke-watchdog")
        self._device = device
        self._lock = threading.Lock()
        self._end = time.monotonic() + budget_s
        self._phase = None  # (name, limit_s, deadline)

    def enter(self, name: str, limit_s: float) -> None:
        with self._lock:
            self._phase = (name, limit_s,
                           min(self._end, time.monotonic() + limit_s))

    def leave(self) -> None:
        with self._lock:
            self._phase = None

    def run(self) -> None:
        while True:
            time.sleep(0.5)
            with self._lock:
                ph = self._phase
            if ph is not None and time.monotonic() > ph[2]:
                msg = (f"phase {ph[0]} passed its deadline "
                       f"({ph[1]:.0f} s, run budget {BUDGET_S:.0f} s)")
                print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
                faulthandler.dump_traceback(file=sys.stderr)
                print(json.dumps({"summary": {"failures": [msg]}}))
                print_verdict(False, self._device)
                os._exit(4)


class Smoke:
    def __init__(self, sf: float, queries, require_tpu: bool):
        self.sf = sf
        self.queries = queries
        self.require_tpu = require_tpu
        self.failures = []
        self.cache_counts = {"requests": 0, "hits": 0, "writes": 0}
        self.compile_log = []  # (seconds, program name), every compile
        self.out = {}

    # -- plumbing -------------------------------------------------------------
    @contextlib.contextmanager
    def phase(self, name: str, limit_s: float, fatal: bool = False):
        """Run one phase under the watchdog.  A failure is recorded and
        fails the run; the phases that do not depend on it still run, so
        one chip call says everything that is broken."""
        rec = {}
        self.dog.enter(name, limit_s)
        t0 = time.perf_counter()
        try:
            yield rec
        except Exception as e:
            if fatal:
                raise
            traceback.print_exc()
            self.fail(name, f"{type(e).__name__}: {e}")
        finally:
            self.dog.leave()
            rec["seconds"] = round(time.perf_counter() - t0, 3)
            print(json.dumps({"phase": name, **rec}), flush=True)

    def fail(self, where: str, what: str) -> None:
        self.failures.append(f"{where}: {what}"[:600])

    def _stats(self):
        from spark_rapids_tpu.utils.metrics import QueryStats, TaskMetrics
        s = QueryStats.get().snapshot()
        t = TaskMetrics.get().snapshot()
        s["oom_retries"] = t["retry_count"]
        s["oom_splits"] = t["split_retry_count"]
        s.update({f"cache_{k}": v for k, v in self.cache_counts.items()})
        return s

    def _delta(self, before):
        now = self._stats()
        keys = _STAT_KEYS + ["oom_retries", "oom_splits", "cache_requests",
                             "cache_hits", "cache_writes"]
        return {k: round(now[k] - before[k], 4) for k in keys}

    # -- phases ---------------------------------------------------------------
    def start(self):
        import spark_rapids_tpu as srt  # enables x64 before jax is used
        import jax
        devs = jax.devices()
        self.device = device = {"platform": devs[0].platform,
                                "kind": devs[0].device_kind,
                                "count": len(devs)}
        if self.require_tpu and device["platform"] != "tpu":
            print(f"chip_smoke: platform is {device['platform']}, not tpu "
                  f"({device['count']} x {device['kind']}): no accelerator, "
                  f"no result", file=sys.stderr)
            sys.exit(2)

        def on_event(event, **kw):
            key = _CACHE_EVENTS.get(event)
            if key:
                self.cache_counts[key] += 1
        jax.monitoring.register_event_listener(on_event)

        def on_duration(event, duration, fun_name=None, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_log.append((round(duration, 2), fun_name))
        jax.monitoring.register_event_duration_secs_listener(on_duration)

        self.dog = Watchdog(BUDGET_S, device)
        self.dog.start()

    def session(self):
        import jax
        import spark_rapids_tpu as srt
        with self.phase("session", 180, fatal=True) as rec:
            # default confs, plus the planner check that no operator of a
            # smoke query is placed on the CPU
            self.sess = srt.Session.get_or_create(
                settings={_VALIDATE: True})
            from spark_rapids_tpu import native
            from spark_rapids_tpu.runtime import warmstore
            conf = self.sess._tpu_conf()
            self.cache_dir = warmstore.xla_cache_dir(conf)
            rec.update({
                "device": self.device,
                "versions": {
                    "python": sys.version.split()[0],
                    "jax": jax.__version__,
                    "jaxlib": _version("jaxlib"),
                    "libtpu": _version("libtpu")},
                "compile_cache": {
                    "dir": self.cache_dir,
                    "from_env": bool(
                        os.environ.get("JAX_COMPILATION_CACHE_DIR")),
                    "jax_config_dir": jax.config.jax_compilation_cache_dir,
                    "entries_at_start": self._cache_entries()},
                "native": native.available(),
                "bytes_limit": (self.sess.device.memory_stats()
                                or {}).get("bytes_limit"),
            })
            self.out.update(rec)

    def _cache_entries(self):
        if not self.cache_dir:
            return None
        return len(glob.glob(os.path.join(self.cache_dir, "*-cache")))

    def load(self):
        """Set-up, timed as such: generate the data, load the oracle's
        copy into pandas, open the engine's DataFrames on the parquet."""
        import pyarrow.parquet as pq
        from spark_rapids_tpu.models import tpcds, tpch_suite
        self.mods = {q: (tpcds if q.startswith("ds_") else tpch_suite)
                     for q in self.queries}
        with self.phase("datagen", 300, fatal=True) as rec:
            # TPC-H always: the served and ICI phases read orders, lineitem
            paths = {mod: mod.gen_db(self.sf, DATA_DIR)
                     for mod in {tpch_suite, *self.mods.values()}}
            rec["sf"] = self.sf
        with self.phase("pandas_load", 300, fatal=True) as rec:
            self.dfs, self.pds = {}, {}
            for q, mod in self.mods.items():
                for t in mod.TABLES[q]:
                    key = (mod, t)
                    if key not in self.dfs:
                        self.dfs[key] = self.sess.read_parquet(paths[mod][t])
                        self.pds[key] = pq.read_table(
                            paths[mod][t]).to_pandas()
            for t in ("orders", "lineitem"):
                self.dfs.setdefault(
                    (tpch_suite, t),
                    self.sess.read_parquet(paths[tpch_suite][t]))
            rec["tables"] = sorted({t for _, t in self.pds})
        self.tpch = tpch_suite

    def query(self, name: str):
        mod = self.mods[name]
        runner, oracle = mod.QUERIES[name]
        dfs = {t: self.dfs[(mod, t)] for t in mod.TABLES[name]}
        pds = {t: self.pds[(mod, t)] for t in mod.TABLES[name]}
        rel_err = self.tpch.rows_rel_err
        expected_cpu = CPU_PLACED.get(name, {})
        with self.phase(name, 420) as rec, self._placement(expected_cpu):
            want = oracle(pds)
            s0 = self._stats()
            n0 = len(self.compile_log)
            t0 = time.perf_counter()
            rows = runner(dfs)  # collect(): host rows, so the device is done
            rec["cold_s"] = round(time.perf_counter() - t0, 4)
            rec["cold"] = self._delta(s0)
            rec["slowest_compiles"] = sorted(self.compile_log[n0:],
                                             reverse=True)[:3]
            rec["rows"] = len(rows)
            rec["rows_sha"] = hashlib.sha256(
                repr(rows).encode()).hexdigest()[:16]
            errs = [rel_err(rows, want)]
            s1 = self._stats()
            warm = []
            for _ in range(WARM_RUNS):
                t0 = time.perf_counter()
                rows = runner(dfs)
                warm.append(round(time.perf_counter() - t0, 4))
                errs.append(rel_err(rows, want))
            rec["warm_s"] = warm
            rec["warm_median_s"] = statistics.median(warm)
            rec["warm"] = self._delta(s1)  # over the three warm runs
            rec["rel_err"] = max(errs)
            placed = sorted(set(re.findall(r"CpuFallback\[(\w+)",
                                           self.sess.profiled_explain())))
            rec["cpu_placed"] = {op: expected_cpu.get(op, "unaccounted")
                                 for op in placed}
            if placed != sorted(expected_cpu):
                self.fail(name, f"operators on the CPU {placed}, "
                                f"accounted for {sorted(expected_cpu)}")
            if "pipeline_depth" not in self.out:
                from spark_rapids_tpu.runtime.pipeline import effective_depth
                self.out["pipeline_depth"] = effective_depth(
                    self.sess.last_exec_context())
            self.out.setdefault("queries", {})[name] = rec
            if max(errs) >= 1e-6:
                self.fail(name, f"rows differ from the pandas oracle "
                                f"(rel_err per run {errs}, "
                                f"{len(rows)} rows vs {len(want)})")
            if rec["warm"]["compiles"]:
                self.fail(name, f"{rec['warm']['compiles']} compiles "
                                f"inside the warm runs")

    @contextlib.contextmanager
    def _placement(self, expected_cpu):
        """validateExecsOnTpu raises at planning on any CPU-placed
        operator; a query with an accounted-for one runs without it and
        has its executed plan checked against the account instead."""
        if expected_cpu:
            self.sess.conf.set(_VALIDATE, False)
        try:
            yield
        finally:
            self.sess.conf.set(_VALIDATE, True)

    def served(self):
        """One prepared aggregate over the wire door, executed twice, rows
        equal to the same DataFrame collected in-process."""
        from spark_rapids_tpu.server import SqlFrontDoor, WireClient
        from spark_rapids_tpu.sql import functions as F
        orders = self.dfs[(self.tpch, "orders")]
        threshold = 250_000.0
        spec = {"table": "orders", "ops": [
            {"op": "filter", "expr": [">", ["col", "o_totalprice"],
                                      ["param", 0, "double"]]},
            {"op": "agg", "group": ["o_orderpriority"],
             "aggs": [["n", "count", "*"],
                      ["total", "sum", ["col", "o_totalprice"]]]}]}
        with self.phase("served", 300) as rec:
            want = (orders.where(F.col("o_totalprice") > F.lit(threshold))
                    .group_by("o_orderpriority")
                    .agg(F.count_star().alias("n"),
                         F.sum(F.col("o_totalprice")).alias("total"))
                    .collect())
            door = SqlFrontDoor(self.sess).start()
            try:
                door.register_table("orders", orders)
                with WireClient("127.0.0.1", door.port,
                                tenant="smoke") as c:
                    t0 = time.perf_counter()
                    stmt = c.prepare(spec)
                    rec["prepare_s"] = round(time.perf_counter() - t0, 4)
                    errs, times = [], []
                    for _ in range(2):
                        t0 = time.perf_counter()
                        got = c.execute(stmt["statement_id"],
                                        [threshold]).rows()
                        times.append(round(time.perf_counter() - t0, 4))
                        errs.append(self.tpch.rows_rel_err(got, want))
            finally:
                door.close()
            rec.update({"rows": len(want), "execute_s": times,
                        "rel_err": max(errs)})
            self.out["served"] = rec
            if max(errs) >= 1e-6 or not want:
                self.fail("served", f"wire rows differ from the in-process "
                                    f"answer (rel_err {errs}, {len(want)} "
                                    f"rows)")

    def ici(self):
        """The shuffled join + grouped aggregate of
        ``__graft_entry__.dryrun_multichip`` at SF1 over the ICI mesh; rows
        equal to the single-chip (CACHE_ONLY) answer, and the bytes each
        device received printed, so "everything landed on device 0" is
        visible."""
        from spark_rapids_tpu.sql import functions as F
        orders = self.dfs[(self.tpch, "orders")]
        lineitem = self.dfs[(self.tpch, "lineitem")]
        df = (orders.join(lineitem, [("o_orderkey", "l_orderkey")], "inner")
              .group_by("o_custkey")
              .agg(F.sum(F.col("l_extendedprice")).alias("sv"),
                   F.count(F.col("l_quantity")).alias("cnt"),
                   F.avg(F.col("l_extendedprice")).alias("av"))
              .order_by(F.col("o_custkey")))
        conf = self.sess.conf
        keys = ["spark.rapids.tpu.sql.autoBroadcastJoinThreshold",
                "spark.rapids.tpu.shuffle.mode"]
        prev = {k: conf.get(k) for k in keys}
        with self.phase("ici", 900) as rec:
            try:
                conf.set(keys[0], -1)  # pin the SHUFFLED join
                conf.set(keys[1], "CACHE_ONLY")
                t0 = time.perf_counter()
                want = df.collect()
                rec["single_chip_s"] = round(time.perf_counter() - t0, 4)
                conf.set(keys[1], "ICI")
                t0 = time.perf_counter()
                got = df.collect()
                rec["ici_cold_s"] = round(time.perf_counter() - t0, 4)
            finally:
                for k, v in prev.items():
                    conf.set(k, v)
            per_dev = {}
            for ms in self.sess.last_exec_context().metrics.values():
                for k, v in ms.values.items():
                    if k.startswith("iciInputBytes."):
                        d = k.split(".", 1)[1]
                        per_dev[d] = per_dev.get(d, 0) + int(v)
            err = self.tpch.rows_rel_err(got, want)
            rec.update({"devices": self.device["count"],
                        "rows": len(got), "rel_err": err,
                        "input_bytes_per_device": per_dev})
            self.out["ici"] = rec
            if err >= 1e-6 or not got:
                self.fail("ici", f"ICI rows differ from the single-chip "
                                 f"answer (rel_err {err}, {len(got)} rows "
                                 f"vs {len(want)})")
            n = self.device["count"]
            if len(per_dev) != n or min(per_dev.values()) == 0:
                self.fail("ici", f"inputs did not reach all {n} devices: "
                                 f"{per_dev}")

    def finish(self, t_start: float) -> int:
        totals = self._delta(self.start_stats)
        mem = self.sess.device.memory_stats() or {}
        self.out.update({
            "totals": totals,
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            "wall_s": round(time.perf_counter() - t_start, 2),
        })
        self.out["compile_cache"]["entries_at_end"] = self._cache_entries()
        for k in ("degraded_batches", "transient_retries"):
            if totals[k]:
                self.fail("run", f"{k} = {totals[k]}: a device error was "
                                 f"retried or a batch re-ran on the CPU")
        if self.device["platform"] == "tpu" \
                and self.out.get("pipeline_depth") != 2:
            # the path the platform selects only here must have engaged
            self.fail("run", f"pipeline depth "
                             f"{self.out.get('pipeline_depth')}, not 2")
        print(json.dumps({"summary": {"failures": self.failures, **self.out,
                                      "claim": None}}), flush=True)
        return 1 if self.failures else 0


def print_verdict(ok: bool, device: dict) -> None:
    """The last line of stdout: these keys and no others."""
    print(json.dumps({"ok": ok, "device": device}), flush=True)


def _version(dist: str):
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def run(sf: float = 1.0, queries=tuple(QUERIES),
        require_tpu: bool = True) -> int:
    """The smoke.  ``require_tpu=False`` walks the same phases on whatever
    platform JAX has (debugging on the CPU at a tiny ``sf``); the command
    line never passes it."""
    t_start = time.perf_counter()
    smoke = Smoke(sf, list(queries), require_tpu)
    smoke.start()  # no accelerator: exits here, no result
    code = 1
    try:
        smoke.session()
        smoke.start_stats = smoke._stats()
        smoke.load()
        for q in smoke.queries:
            smoke.query(q)
        smoke.served()
        if smoke.device["count"] > 1:
            smoke.ici()
        code = smoke.finish(t_start)
    finally:
        # a phase that raised still ends the output with a verdict; the
        # exception goes on to end the process with its traceback
        print_verdict(code == 0, smoke.device)
    return code


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H/TPC-DS scale factor (default 1.0, the "
                         "size the smoke is specified at)")
    ap.add_argument("--queries", default=",".join(QUERIES),
                    help="comma list, default %(default)s")
    args = ap.parse_args()
    return run(args.sf, [q for q in args.queries.split(",") if q])


if __name__ == "__main__":
    sys.exit(main())
