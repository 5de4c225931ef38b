"""Benchmark: the full TPC-H suite (q1..q22) + 22 TPC-DS queries
(incl. the q64/q95 shuffle-stress pair) vs pandas on CPU, at SF1.

Prints ONE JSON line:
  {"metric": "tpch22_tpcds22_geomean_speedup_vs_cpu", "value": <x>,
   "unit": "x", "vs_baseline": <x>, "q1": {...}, ..., "ds_q7": {...}}

The reference's headline claim is 3-7x (4x typical) end-to-end speedup
over CPU Spark (BASELINE.md, docs/FAQ.md:107-109); ``vs_baseline`` is
geomean-speedup / 4.0, so 1.0 means "matches the reference's typical
multiplier".  Every query is verified against its pandas oracle
(rel_err < 1e-6) before its timing counts.

Environment knobs: SRT_BENCH_SF (default 1.0), SRT_BENCH_ITERS (timed
iterations, default 3), SRT_BENCH_QUERIES (comma list; default = all 44),
SRT_BENCH_QUERY_TIMEOUT (per-query subprocess budget, default 300 s),
SRT_BENCH_WALL_BUDGET (whole-run wall-clock budget, default 820 s —
queries that don't fit are reported as skipped, never killed mid-print),
SRT_BENCH_PIPELINE_DEPTH (sets spark.rapids.tpu.sql.pipeline.depth for
the engine run; 0 = serial baseline for overlap A/B),
SRT_BENCH_TRACE_DIR (enables spark.rapids.tpu.sql.trace.enabled and
writes one Chrome-trace JSON per query — <query>.trace.json, the last
warm iteration's span tree — for Perfetto / tools/trace_report.py),
SRT_BENCH_CACHE=0|1 (default 1: the cross-query device cache — scan
batches + broadcast builds resident across queries; per-query output
gains cache_hits_warm / cache_mb_saved columns, and the concurrency
mode replays the suite cache-off THEN cache-on so the win is a printed
number: throughput_qps vs throughput_qps_cache_off / cache_speedup),
SRT_BENCH_CONCURRENCY=N (N>1: replay the suite with N queries in flight
through the query service and report p50/p95 service latency + aggregate
throughput next to the serial numbers from the same warm state; results
are verified equal to the serial run and per-query QueryStats must
reconcile with the process aggregate.  Defaults to the TPC-H 22; with
SRT_BENCH_TRACE_DIR also writes a merged concurrent.trace.json whose
per-query sections + contention summary tools/trace_report.py renders),
SRT_BENCH_GRAY_RATE=R (gray-chaos knob: replay the timed pass with
seeded SILENT CORRUPTION at the shuffle/spill/cache byte paths —
integrity detection + recovery columns (integrity_failures,
fragments_hedged, re-pulls) land next to the clean numbers, results
still oracle-verified).

SRT_BENCH_FAULT_RATE=R (chaos knob: after the clean numbers, replay the
timed pass with spark.rapids.tpu.faults.inject.rate=R — every injection
point fails with probability R, seeded so runs replay — and report the
under-fault throughput/latency NEXT TO the clean numbers plus the
transient_retries / fragments_recomputed / degraded_batches /
retry_backoff_s recovery columns; results are still verified against
the oracle, so the line also proves recovery preserves answers),
SRT_BENCH_LOADGEN=1 (serving-traffic proxy: run the sustained-load
harness — tools/loadgen.py — ahead of the suite and emit its JSON line:
wire queries over TCP through the network SQL front door with a
zipf-skewed tenant mix, prepared-statement plan-cache A/B, seeded
server.conn connection drops, disk spooling, oracle verification, and
p50/p95/p99 + SLO-violation reporting; SRT_LOADGEN_QUERIES /
SRT_LOADGEN_CONNECTIONS / SRT_LOADGEN_FAULT_RATE / SRT_LOADGEN_SEED
parameterize it, and SRT_BENCH_QUERIES="" makes the run loadgen-only),
SRT_BENCH_FUZZ=1 (hostile-input survival drill: the seeded wire/spec
fuzzer — tools/fuzzwire.py — against a live door with an oracle-verified
healthy-traffic sidecar, emitted as a fuzz_survival JSON line gated
absolutely by tools/perfwatch.py: zero crashes/hangs/untyped
rejections/leaks and sidecar goodput >= 0.9x the fuzz-free baseline;
SRT_FUZZ_CASES / SRT_FUZZ_SEED parameterize it, and
SRT_BENCH_QUERIES="" makes the run fuzz-only),
SRT_BENCH_SOAK=1 (zero-downtime drill: a short scripted rolling-restart
soak via tools/loadgen.py --soak — a 2-door front-door fleet under
sustained zipf load, each door gracefully drained (GOAWAY naming its
sibling) and restarted in place, ONE coordinator kill + failover
mid-run (thread-rank world=3, silent freeze), and quota churn — every
result oracle-verified, drain leak audits between phases, emitted as a
soak_rolling_restart JSON line ahead of the suite numbers;
SRT_SOAK_DURATION_S caps the duration at <=120 s, SRT_BENCH_QUERIES=""
makes the run soak-only),
SRT_BENCH_OVERLOAD=1 (overload-survival drill via tools/loadgen.py
--overload: closed-loop capacity probe, then an open-loop offered-load
ramp to ~5x capacity with per-query deadlines — the admission layer's
cost-model packing, doomed/overload shedding, and AIMD concurrency
control must hold goodput >= 0.85x capacity with every shed typed
(reason + retry_after_ms); emitted as an overload_survival JSON line
next to the soak line; SRT_OVERLOAD_DURATION_S caps the ramp,
SRT_OVERLOAD_ADMISSION_OFF=1 runs the static-permit A/B,
SRT_BENCH_QUERIES="" makes the run overload-only),
SRT_BENCH_POISON=1 (blast-radius containment drill via
tools/loadgen.py --poison: a seeded fingerprint-conditioned poison
statement inside a healthy zipf mix must be QUARANTINED within two
chargeable strikes with healthy goodput >= 0.9x the no-poison
baseline, every shed typed, zero worker deaths after quarantine, zero
leaks; emitted as a poison_containment JSON line beside the
overload/soak lines; SRT_POISON_PHASE_S sets the per-phase duration,
SRT_BENCH_QUERIES="" makes the run poison-only),
SRT_BENCH_PARTITION=1 (network-partition survival drill: a world=3
thread-rank DcnShuffle whose minority rank is cut off by the link-fault
fabric mid-reduce — the majority must complete the exact row count
under the original coordinator generation, the minority must park
TYPED (QuorumLostError) with zero epoch bumps while parked, and after
the fabric heals the parked rank must rejoin through flap damping with
exactly one epoch bump; emitted as a partition_survival JSON line
beside the other drills, SRT_BENCH_QUERIES="" makes the run
partition-only),
SRT_BENCH_TELEMETRY=1 (telemetry-tax drill: the live metrics registry
on vs off over a serial in-memory mini-suite — alternating passes, min
wall per side, overhead_pct against the <=2% bound — plus scrape
latency p95 while 4 threads hammer /metrics + /snapshot during a
concurrent burst; emitted as a telemetry_overhead JSON line ahead of
the suite numbers, SRT_BENCH_QUERIES="" makes the run telemetry-only),
SRT_BENCH_RECORDER=1 (flight-recorder-tax drill: the always-on
tail-sampled capture path on vs off over the same alternating
mini-suite — overhead_pct against the <=2% bound, plus the retained
capture / boring-drop counts that prove tail sampling actually
dropped the repeats; emitted as a recorder_overhead JSON line,
SRT_BENCH_QUERIES="" makes the run recorder-only),
SRT_BENCH_KILL_PEER=1 (killed-peer drill: a world=2 DcnShuffle over
thread ranks commits on both sides, then rank 1 dies SILENTLY
mid-reduce — the drill prints a dcn_killed_peer_recovery JSON line with
kill_recovery_s (heartbeat detection + durable remote re-pulls + orphan
adoption, end to end), peers_lost / fragments_recomputed_remote /
partitions_reowned, and rows_recovered_complete, ahead of the suite
numbers; SRT_BENCH_KILL_PEER_HB tunes the detection horizon).

The aggregate JSON line is re-printed after EVERY query (flush=True), so
a driver that kills the run on a timeout still finds the latest complete
snapshot on the last stdout line.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

DATA_DIR = os.path.join(REPO, ".bench_data")
REFERENCE_TYPICAL_SPEEDUP = 4.0  # docs/FAQ.md:107-109 "4x typical"

TPCH_QUERIES = [f"q{i}" for i in range(1, 23)]
TPCDS_QUERIES = [
    "ds_q3", "ds_q7", "ds_q12", "ds_q13", "ds_q19", "ds_q20", "ds_q25",
    "ds_q26", "ds_q34", "ds_q42", "ds_q46", "ds_q48", "ds_q52", "ds_q55",
    "ds_q64", "ds_q65", "ds_q68", "ds_q73", "ds_q79", "ds_q94", "ds_q95",
    "ds_q98",
]
ALL_QUERIES = TPCH_QUERIES + TPCDS_QUERIES


def _time(fn, iters):
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _run_one(name: str, sf: float, iters: int) -> dict:
    """Time one query in this process (the subprocess side)."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.models import tpcds, tpch_suite

    mod = tpcds if name.startswith("ds_") else tpch_suite
    runner, oracle = mod.QUERIES[name]
    tables = mod.TABLES[name]
    paths = mod.gen_db(sf, DATA_DIR)

    settings = {
        "spark.rapids.tpu.sql.fileCache.enabled": True,
        # cross-query device cache: on by default (the pandas baseline is
        # fully in-memory; cached warm iterations give the engine the
        # same footing on-device).  SRT_BENCH_CACHE=0 is the A/B knob.
        "spark.rapids.tpu.sql.cache.enabled":
            os.environ.get("SRT_BENCH_CACHE", "1") != "0",
    }
    depth_env = os.environ.get("SRT_BENCH_PIPELINE_DEPTH")
    if depth_env is not None:
        settings["spark.rapids.tpu.sql.pipeline.depth"] = int(depth_env)
    # SRT_BENCH_TRACE_DIR: record a structured query trace and dump one
    # Chrome-trace JSON per query (tools/trace_report.py reads them)
    trace_dir = os.environ.get("SRT_BENCH_TRACE_DIR")
    if trace_dir:
        settings["spark.rapids.tpu.sql.trace.enabled"] = True
    sess = srt.Session.get_or_create(settings=settings)
    dfs = {t: sess.read_parquet(paths[t]) for t in tables}
    # pandas baseline runs fully in-memory; the engine's decoded-file
    # cache gives it the same footing (parquet decode out of the loop)
    import pyarrow.parquet as pq
    pds = {t: pq.read_table(paths[t]).to_pandas() for t in tables}

    from spark_rapids_tpu.plan import physical
    from spark_rapids_tpu.utils.metrics import QueryStats
    stats0 = QueryStats.get().snapshot()
    progs0 = physical.program_cache_size()
    t0 = time.perf_counter()
    engine_rows = runner(dfs)
    cold_s = time.perf_counter() - t0
    cold_stats = QueryStats.delta_since(stats0)
    progs_cold = physical.program_cache_size() - progs0
    warm0 = QueryStats.get().snapshot()
    engine_s = _time(lambda: runner(dfs), iters)
    warm_stats = QueryStats.delta_since(warm0)
    # bucketed-execution evidence: warm iterations (whatever their
    # cardinalities) must land in ALREADY-COMPILED bucket programs —
    # programs_warm > 0 means a shape escaped its bucket
    progs_warm = physical.program_cache_size() - progs0 - progs_cold
    if trace_dir:
        # one trace per query: the last warm iteration's span tree
        os.makedirs(trace_dir, exist_ok=True)
        tr = sess.last_trace()
        if tr is not None:
            tr.write(os.path.join(trace_dir, f"{name}.trace.json"))
    # per warm iteration: the sync profile of ONE steady-state run
    for k in warm_stats:
        warm_stats[k] = round(warm_stats[k] / iters, 4)
    # cpu baseline: warm the OS/page cache with one untimed run, then
    # best-of-N — the same statistic as engine_s, so the ratio compares
    # like with like (PERF.md r4: cache-state swings of 2-3x made
    # cross-round ratios noise)
    cpu_rows = oracle(pds)
    cpu_s = _time(lambda: oracle(pds), max(3, iters))
    rel_err = tpch_suite.rows_rel_err(engine_rows, cpu_rows)
    assert rel_err < 1e-6, \
        f"{name} result mismatch (rel_err={rel_err}, rows={len(engine_rows)})"
    # chaos pass: same query under probabilistic fault injection — the
    # recovery framework (faults/) must keep the answer identical while
    # the recovery columns show what it cost
    fault_rate = float(os.environ.get("SRT_BENCH_FAULT_RATE", "0") or 0)
    faulted = {}
    if fault_rate > 0:
        sess.conf.set("spark.rapids.tpu.faults.inject.rate", fault_rate)
        sess.conf.set("spark.rapids.tpu.faults.inject.seed", 20260804)
        try:
            f0 = QueryStats.get().snapshot()
            faulted_rows = runner(dfs)
            faulted_s = _time(lambda: runner(dfs), iters)
            f_stats = QueryStats.delta_since(f0)
            per_iter = 1 + iters  # verify run + timed iterations
            faulted = {
                "fault_rate": fault_rate,
                "engine_s_faulted": round(faulted_s, 5),
                "faulted_slowdown": round(faulted_s / engine_s, 4),
                "faulted_rel_err": tpch_suite.rows_rel_err(
                    faulted_rows, cpu_rows),
                "faults_injected": f_stats["faults_injected"],
                "transient_retries": f_stats["transient_retries"],
                "fragments_recomputed": f_stats["fragments_recomputed"],
                "degraded_batches": f_stats["degraded_batches"],
                "retry_backoff_s": round(
                    f_stats["retry_backoff_s"] / per_iter, 4),
            }
            assert faulted["faulted_rel_err"] < 1e-6, \
                f"{name} result mismatch UNDER FAULTS " \
                f"(rel_err={faulted['faulted_rel_err']})"
        finally:
            sess.conf.unset("spark.rapids.tpu.faults.inject.rate")
            sess.conf.unset("spark.rapids.tpu.faults.inject.seed")
    # gray-chaos pass: the same query under seeded GRAY injection
    # (silent corruption at the shuffle/spill/cache byte paths) — the
    # integrity layer must catch every flipped bit and route it into
    # recovery with the answer still oracle-identical; the recovery
    # columns show what the detection + re-pull cost
    gray_rate = float(os.environ.get("SRT_BENCH_GRAY_RATE", "0") or 0)
    gray = {}
    if gray_rate > 0:
        sess.conf.set("spark.rapids.tpu.faults.inject.rate", gray_rate)
        sess.conf.set("spark.rapids.tpu.faults.inject.points",
                      "shuffle.corrupt,spill.corrupt,cache.corrupt")
        sess.conf.set("spark.rapids.tpu.faults.inject.seed", 20260804)
        try:
            g0 = QueryStats.get().snapshot()
            gray_rows = runner(dfs)
            gray_s = _time(lambda: runner(dfs), iters)
            g_stats = QueryStats.delta_since(g0)
            gray = {
                "gray_rate": gray_rate,
                "engine_s_gray": round(gray_s, 5),
                "gray_slowdown": round(gray_s / engine_s, 4),
                "gray_rel_err": tpch_suite.rows_rel_err(
                    gray_rows, cpu_rows),
                "integrity_failures": g_stats["integrity_failures"],
                "fragments_hedged": g_stats["fragments_hedged"],
                "gray_fragments_recomputed":
                    g_stats["fragments_recomputed"],
                "gray_cache_misses": g_stats["cache_misses"],
            }
            assert gray["gray_rel_err"] < 1e-6, \
                f"{name} result mismatch UNDER GRAY FAULTS " \
                f"(rel_err={gray['gray_rel_err']})"
        finally:
            sess.conf.unset("spark.rapids.tpu.faults.inject.rate")
            sess.conf.unset("spark.rapids.tpu.faults.inject.points")
            sess.conf.unset("spark.rapids.tpu.faults.inject.seed")
    return {
        **faulted,
        **gray,
        "speedup": round(cpu_s / engine_s, 4),
        "engine_s": round(engine_s, 5),
        "engine_cold_s": round(cold_s, 5),
        "cpu_s": round(cpu_s, 5),
        "result_rel_err": rel_err,
        "rows": len(engine_rows),
        # sync/compile profile (VERDICT r4 item 2): warm = per-iteration
        "syncs_warm": warm_stats["blocking_fetches"],
        "syncs_cold": cold_stats["blocking_fetches"],
        "asyncs_warm": warm_stats["async_fetches"],
        # region-fusion profile: regions formed + the prologue fetches
        # they paid (region_fetches ⊆ syncs; 0s under sql.fusion.enabled
        # =false — the printed A/B evidence for the fused data path)
        "fused_regions_warm": warm_stats["fused_regions"],
        "fused_regions_cold": cold_stats["fused_regions"],
        "region_fetches_warm": warm_stats["region_fetches"],
        "region_fetches_cold": cold_stats["region_fetches"],
        "fetch_mb_warm": round(warm_stats["fetch_bytes"] / 1e6, 3),
        # pipeline profile (round 6): time the pull loop blocked on a
        # staged batch, plus the attributable D2H stall
        "h2d_wait_s": warm_stats["h2d_wait_s"],
        "fetch_wait_s": warm_stats["fetch_wait_s"],
        # cross-query cache profile: hits per warm iteration and the MB
        # served from HBM instead of decode+upload (0s when
        # SRT_BENCH_CACHE=0 — the printed A/B evidence)
        "cache_hits_warm": warm_stats["cache_hits"],
        "cache_mb_saved": round(warm_stats["cache_hit_bytes"] / 1e6, 3),
        "compiles_cold": cold_stats["compiles"],
        "compile_s_cold": cold_stats["compile_s"],
        "compiles_warm": warm_stats["compiles"],
        # stage-program cache growth: cold = programs this query
        # compiled, warm = programs the warm iterations ADDED (0 when
        # shape bucketing holds every cardinality in a compiled bucket)
        "programs_cold": progs_cold,
        "programs_warm": progs_warm,
        "shuffle_mb_warm": round(warm_stats["shuffle_bytes"] / 1e6, 3),
        "shuffle_gbps_warm": round(
            warm_stats["shuffle_bytes"] / 1e9 / engine_s, 4),
    }


def _run_concurrent(sf: float, conc: int, which) -> None:
    """SRT_BENCH_CONCURRENCY=N: replay the suite with N queries in
    flight through the query service (service/scheduler.py) and print
    ONE JSON line with p50/p95 service latency + aggregate throughput
    NEXT TO the serial numbers from the same process/warm state.

    Verifies the concurrent results match the serial run exactly and
    that per-query QueryStats sums reconcile with the process aggregate
    (zero cross-query accounting bleed).
    """
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.models import tpcds, tpch_suite
    from spark_rapids_tpu.utils.metrics import QueryStats

    settings = {
        # host decoded-file cache stays on in BOTH A/B passes, so the
        # A/B isolates the cross-query cache
        "spark.rapids.tpu.sql.fileCache.enabled": True,
        "spark.rapids.tpu.sql.scheduler.maxConcurrent": conc,
        "spark.rapids.tpu.sql.concurrentTpuTasks": conc,
    }
    trace_dir = os.environ.get("SRT_BENCH_TRACE_DIR")
    if trace_dir:
        settings["spark.rapids.tpu.sql.trace.enabled"] = True
    sess = srt.Session.get_or_create(settings=settings)

    runners = {}
    for name in which:
        mod = tpcds if name.startswith("ds_") else tpch_suite
        runner, _oracle = mod.QUERIES[name]
        tables = mod.TABLES[name]
        paths = mod.gen_db(sf, DATA_DIR)
        dfs = {t: sess.read_parquet(paths[t]) for t in tables}
        runners[name] = (runner, dfs)

    # warm pass: compiles + decoded-file cache out of both timed passes
    for name, (runner, dfs) in runners.items():
        runner(dfs)

    # serial pass: the reference numbers the concurrent pass must beat
    serial_rows, serial_s = {}, {}
    t0 = time.perf_counter()
    for name, (runner, dfs) in runners.items():
        q0 = time.perf_counter()
        serial_rows[name] = runner(dfs)
        serial_s[name] = round(time.perf_counter() - q0, 5)
    serial_wall = time.perf_counter() - t0

    # concurrent passes: once with the cross-query cache OFF, once ON
    # (same build, same warm decoded-file state) — the cache win is a
    # printed number, not a claim.  The ON pass starts cold and
    # populates DURING the replay: hits come from concurrent queries
    # sharing tables, the exact service shape the cache targets.
    from spark_rapids_tpu.cache import clear_query_cache

    def _concurrent_pass():
        handles = {}
        t0 = time.perf_counter()
        for name, (runner, dfs) in runners.items():
            handles[name] = sess.submit(
                (lambda r=runner, d=dfs: r(d)), label=name)
        rows, errs = {}, {}
        for name, h in handles.items():
            try:
                rows[name] = h.result(timeout=600)
            except BaseException as e:
                errs[name] = f"{type(e).__name__}: {e}"[:200]
        return rows, errs, time.perf_counter() - t0, handles

    # OFF pass: the PR-3 service as it was (decoded-file cache + legacy
    # per-scan device tier, both warm from the passes above)
    sess.conf.set("spark.rapids.tpu.sql.cache.enabled", False)
    clear_query_cache()
    off_rows, off_errors, off_wall, _off_handles = _concurrent_pass()

    # ON pass: one untimed replay populates the cross-query cache and a
    # second one settles the grown allocator arena (a CPU-backend
    # artifact: the populate pass's first-touch of ~100s of MB of fresh
    # pages costs ~1s ONCE; real-TPU pools pre-reserve HBM), then the
    # timed replay measures the steady-state service — apples to apples
    # with the off pass, whose tiers warmed during the passes above
    sess.conf.set("spark.rapids.tpu.sql.cache.enabled",
                  os.environ.get("SRT_BENCH_CACHE", "1") != "0")
    clear_query_cache()
    _concurrent_pass()  # populate
    _concurrent_pass()  # settle
    stats0 = QueryStats.get().snapshot()
    conc_rows, errors, conc_wall, handles = _concurrent_pass()
    delta = QueryStats.delta_since(stats0)
    errors.update({f"off:{k}": v for k, v in off_errors.items()})

    results_match = not errors and all(
        tpch_suite.rows_rel_err(conc_rows[n], serial_rows[n]) < 1e-6
        and tpch_suite.rows_rel_err(off_rows[n], serial_rows[n]) < 1e-6
        for n in which)
    # per-query scopes fold into the process aggregate: the sums must
    # reconcile exactly or accounting bled across queries
    sums = {k: sum((h.stats or {}).get(k, 0) for h in handles.values())
            for k in ("blocking_fetches", "async_fetches", "fetch_bytes")}
    reconciled = all(abs(sums[k] - delta.get(k, 0)) < 1e-6 for k in sums)

    lat = sorted(h.latency_s or 0.0 for h in handles.values())

    def pct(p, ls=None):
        ls = lat if ls is None else ls
        return round(ls[min(len(ls) - 1, int(p * len(ls)))], 5)

    # chaos replay: the same concurrent batch under probabilistic fault
    # injection — service throughput/p95 under faults lands NEXT TO the
    # clean numbers, with the recovery columns showing what it cost
    fault_rate = float(os.environ.get("SRT_BENCH_FAULT_RATE", "0") or 0)
    faulted = {}
    if fault_rate > 0:
        sess.conf.set("spark.rapids.tpu.faults.inject.rate", fault_rate)
        sess.conf.set("spark.rapids.tpu.faults.inject.seed", 20260804)
        try:
            f0 = QueryStats.get().snapshot()
            f_rows, f_errs, f_wall, f_handles = _concurrent_pass()
            f_delta = QueryStats.delta_since(f0)
            f_lat = sorted(h.latency_s or 0.0
                           for h in f_handles.values())
            faulted = {
                "fault_rate": fault_rate,
                "concurrent_wall_s_faulted": round(f_wall, 5),
                "throughput_qps_faulted": round(len(which) / f_wall, 4),
                "latency_p95_s_faulted": pct(0.95, f_lat),
                "results_match_faulted": not f_errs and all(
                    tpch_suite.rows_rel_err(f_rows[n], serial_rows[n])
                    < 1e-6 for n in which),
                "faulted_errors": f_errs,
                "faults_injected": f_delta.get("faults_injected", 0),
                "transient_retries": f_delta.get("transient_retries", 0),
                "fragments_recomputed": f_delta.get(
                    "fragments_recomputed", 0),
                "degraded_batches": f_delta.get("degraded_batches", 0),
                "retry_backoff_s": f_delta.get("retry_backoff_s", 0.0),
            }
        finally:
            sess.conf.unset("spark.rapids.tpu.faults.inject.rate")
            sess.conf.unset("spark.rapids.tpu.faults.inject.seed")

    if trace_dir:
        from spark_rapids_tpu.utils import tracing
        os.makedirs(trace_dir, exist_ok=True)
        tracing.write_merged(
            [h.trace() for h in handles.values()],
            os.path.join(trace_dir, "concurrent.trace.json"))
    print(json.dumps({
        "metric": "tpch_concurrent_throughput",
        "concurrency": conc,
        "sf": sf,
        "n_queries": len(which),
        "device": _device(),
        "serial_wall_s": round(serial_wall, 5),
        "concurrent_wall_s": round(conc_wall, 5),
        "serial_qps": round(len(which) / serial_wall, 4),
        "throughput_qps": round(len(which) / conc_wall, 4),
        "speedup_vs_serial": round(serial_wall / conc_wall, 4),
        # cache A/B on the same build: the OFF pass ran first on the
        # same warm decoded-file state, the ON pass started cold and
        # populated during the replay
        "concurrent_wall_s_cache_off": round(off_wall, 5),
        "throughput_qps_cache_off": round(len(which) / off_wall, 4),
        "cache_speedup": round(off_wall / conc_wall, 4),
        "cache_hits": delta.get("cache_hits", 0),
        "cache_mb_saved": round(delta.get("cache_hit_bytes", 0) / 1e6, 3),
        "latency_p50_s": pct(0.50),
        "latency_p95_s": pct(0.95),
        "queue_wait_max_s": round(max(
            h.queue_wait_s for h in handles.values()), 5),
        "results_match": results_match,
        "stats_reconciled": reconciled,
        "errors": errors,
        **faulted,
        "per_query": {n: {
            "serial_s": serial_s[n],
            "latency_s": round(handles[n].latency_s or 0.0, 5),
            "queue_wait_s": round(handles[n].queue_wait_s, 5),
            "status": handles[n].status,
        } for n in which},
    }), flush=True)


def _killed_peer_drill() -> dict:
    """SRT_BENCH_KILL_PEER=1: a compact killed-peer recovery drill over
    thread ranks (world=2 DcnShuffle, both sides commit, rank 1 dies
    SILENTLY mid-reduce).  Reports the wall clock from kill to a fully
    recovered read — detection (heartbeat timeout) + durable remote
    re-pulls + orphan adoption — next to the recovery counters, so the
    bench line makes 'bounded recovery time' a printed number."""
    import tempfile
    import threading

    import pyarrow as pa

    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.parallel.dcn import (Coordinator, DcnShuffle,
                                               ProcessGroup)
    from spark_rapids_tpu.utils.metrics import QueryStats
    hb_timeout = float(os.environ.get("SRT_BENCH_KILL_PEER_HB", "1.0"))
    TpuConf.set_session("spark.rapids.tpu.dcn.heartbeatTimeout",
                        hb_timeout)
    world, n_parts = 2, 8
    tmp = tempfile.mkdtemp(prefix="srt_kill_drill_")
    coord = Coordinator(world, heartbeat_timeout=hb_timeout,
                        wait_timeout=60.0)
    pgs = [None] * world
    try:
        def mk(r):
            pgs[r] = ProcessGroup(
                r, world, ("127.0.0.1", coord.port),
                coordinator=coord if r == 0 else None,
                heartbeat_interval=0.1)

        ts = [threading.Thread(target=mk, args=(r,)) for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        shuffles = [DcnShuffle(pg, n_parts, os.path.join(tmp, f"r{pg.rank}"))
                    for pg in pgs]
        for rank, sh in enumerate(shuffles):
            for p in range(n_parts):
                sh.write_partition(p, pa.table(
                    {"r": [rank] * 64, "p": [p] * 64,
                     "v": list(range(64))}))
        ts = [threading.Thread(target=sh.commit) for sh in shuffles]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        before = QueryStats.get().snapshot()
        t0 = time.monotonic()
        # rank 1 dies silently mid-shuffle: detection is heartbeat-only
        pgs[1]._closed = True
        pgs[1]._server.freeze()
        rows = 0
        for p in shuffles[0].my_parts():
            rows += sum(t_.num_rows for t_ in shuffles[0].read_partition(p))
        for p in shuffles[0].adopt_orphans():
            rows += sum(t_.num_rows for t_ in shuffles[0].read_partition(p))
        recovery_s = time.monotonic() - t0
        d = QueryStats.delta_since(before)
        complete = rows == world * n_parts * 64
        shuffles[0].close()
        return {
            "metric": "dcn_killed_peer_recovery",
            "kill_mode": "silent",
            "heartbeat_timeout_s": hb_timeout,
            "kill_recovery_s": round(recovery_s, 4),
            "rows_recovered_complete": complete,
            "peers_lost": d.get("peers_lost", 0),
            "fragments_recomputed_remote":
                d.get("fragments_recomputed_remote", 0),
            "partitions_reowned": d.get("partitions_reowned", 0),
            "transient_retries": d.get("transient_retries", 0),
        }
    finally:
        for pg in pgs:
            if pg is not None:
                pg.close()
        TpuConf.unset_session("spark.rapids.tpu.dcn.heartbeatTimeout")


def _telemetry_overhead_drill() -> dict:
    """SRT_BENCH_TELEMETRY=1: pin the telemetry tax with numbers.

    (1) on-vs-off wall delta over a serial in-memory mini-suite
    (scan->filter->agg / join / sort shapes, alternating passes so
    drift cancels) — the <=2% acceptance bound; (2) scrape latency p95
    while 4 scraper threads hammer /metrics + /snapshot during a
    concurrent burst — the scrape-storm-never-blocks-queries check."""
    import threading
    import urllib.request

    import numpy as np

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql import functions as F

    sess = srt.Session.get_or_create()
    rng = np.random.default_rng(7)
    n = 60_000
    df = sess.create_dataframe({
        "k": rng.integers(0, 64, n),
        "v": rng.random(n).round(4),
        "w": (rng.random(n) * 1e4).round(2)})
    dim = sess.create_dataframe({
        "dk": list(range(64)), "name": [f"g{i:02d}" for i in range(64)]})

    def queries():
        return [
            (df.where(F.col("v") >= 0.25)
             .group_by("k").agg(F.sum(F.col("w")).alias("sw"),
                                F.count_star().alias("c"))),
            (df.join(dim, on=[("k", "dk")]).group_by("name")
             .agg(F.avg(F.col("v")).alias("av"))),
            df.sort(F.col("w").desc()).limit(50),
        ]

    def one_pass() -> float:
        t0 = time.perf_counter()
        for q in queries():
            q.collect()
        return time.perf_counter() - t0

    key = "spark.rapids.tpu.telemetry.enabled"
    for _ in range(2):  # warm compiles out of the measurement
        one_pass()
    on_s, off_s = [], []
    for i in range(6):  # alternate so drift lands on both sides
        sess.conf.set(key, i % 2 == 0)
        (on_s if i % 2 == 0 else off_s).append(one_pass())
    sess.conf.unset(key)
    on_w, off_w = min(on_s), min(off_s)
    overhead_pct = (on_w - off_w) / off_w * 100.0 if off_w else 0.0

    # scrape storm beside a concurrent burst through the scheduler
    from spark_rapids_tpu.server import SqlFrontDoor
    door = SqlFrontDoor(sess).start()
    lat_ms, lat_lock = [], threading.Lock()
    stop = threading.Event()

    def scraper():
        base = f"http://127.0.0.1:{door.ops_port}"
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                for path in ("/metrics", "/snapshot"):
                    with urllib.request.urlopen(base + path,
                                                timeout=5) as r:
                        r.read()
                with lat_lock:
                    lat_ms.append((time.perf_counter() - t0) * 1e3)
            except OSError:
                pass
    ts = [threading.Thread(target=scraper, daemon=True)
          for _ in range(4)]
    for t in ts:
        t.start()
    handles = [sess.submit(q, label=f"tmb-{i}")
               for i in range(3) for q in queries()]
    for h in handles:
        h.result(timeout=120)
    time.sleep(0.3)
    stop.set()
    for t in ts:
        t.join(timeout=5)
    door.close()
    lat_ms.sort()
    p95 = lat_ms[int(0.95 * (len(lat_ms) - 1))] if lat_ms else 0.0
    return {
        "metric": "telemetry_overhead",
        "mini_suite_queries": 3,
        "wall_on_s": round(on_w, 4),
        "wall_off_s": round(off_w, 4),
        "overhead_pct": round(overhead_pct, 2),
        "scrapes": len(lat_ms),
        "scrape_p95_ms": round(p95, 2),
        "bound_pct": 2.0,
    }


def _recorder_overhead_drill() -> dict:
    """SRT_BENCH_RECORDER=1: pin the flight-recorder tax with numbers.

    Same alternating mini-suite as the telemetry drill, toggling
    ``spark.rapids.tpu.recorder.enabled`` instead (telemetry stays on
    both sides, so the delta isolates the recorder's own cost: trace
    capture, term decomposition, and the seal handshake) — the <=2%
    acceptance bound.  The retained-capture counters ride along: a
    repeated identical workload must tail-sample (boring repeats
    dropped), not archive every run."""
    import numpy as np

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.utils import recorder

    sess = srt.Session.get_or_create()
    rng = np.random.default_rng(11)
    n = 400_000
    df = sess.create_dataframe({
        "k": rng.integers(0, 64, n),
        "v": rng.random(n).round(4),
        "w": (rng.random(n) * 1e4).round(2)})
    dim = sess.create_dataframe({
        "dk": list(range(64)), "name": [f"g{i:02d}" for i in range(64)]})

    def queries():
        return [
            (df.where(F.col("v") >= 0.25)
             .group_by("k").agg(F.sum(F.col("w")).alias("sw"),
                                F.count_star().alias("c"))),
            (df.join(dim, on=[("k", "dk")]).group_by("name")
             .agg(F.avg(F.col("v")).alias("av"))),
            df.sort(F.col("w").desc()).limit(50),
        ]

    def one_pass() -> float:
        t0 = time.perf_counter()
        for q in queries():
            q.collect()
        return time.perf_counter() - t0

    key = "spark.rapids.tpu.recorder.enabled"
    recorder.reset_for_tests()  # count captures from a known zero
    sess.conf.set(key, True)
    for _ in range(4):
        # warm compiles out of the measurement AND fill each
        # fingerprint's top-k window, so the measured on-passes hit
        # the steady-state path (boring repeats dropped, not archived)
        one_pass()
    on_s, off_s = [], []
    # 15 pairs: the CPU test mesh jitters ~10% pass to pass, so a
    # sub-2% bound needs enough samples for min-of-side to stabilize
    for i in range(30):  # alternate so drift lands on both sides
        sess.conf.set(key, i % 2 == 0)
        (on_s if i % 2 == 0 else off_s).append(one_pass())
    sess.conf.unset(key)
    on_w, off_w = min(on_s), min(off_s)
    overhead_pct = (on_w - off_w) / off_w * 100.0 if off_w else 0.0
    snap = recorder.snapshot()
    return {
        "metric": "recorder_overhead",
        "mini_suite_queries": 3,
        "wall_on_s": round(on_w, 4),
        "wall_off_s": round(off_w, 4),
        "overhead_pct": round(overhead_pct, 2),
        "captures": snap["queries"],
        "dropped_boring": snap["dropped_boring"],
        "pending_seals": snap["pending_seals"],
        "bound_pct": 2.0,
    }


def main() -> None:
    sf = float(os.environ.get("SRT_BENCH_SF", "1.0"))
    iters = int(os.environ.get("SRT_BENCH_ITERS", "3"))
    conc = int(os.environ.get("SRT_BENCH_CONCURRENCY", "0") or 0)
    if os.environ.get("SRT_BENCH_RECORDER", "0") == "1":
        # flight-recorder tax drill: capture path on vs off over the
        # same mini-suite — the <=2% bound, plus tail-sampling proof
        print(json.dumps(_recorder_overhead_drill()), flush=True)
        if os.environ.get("SRT_BENCH_QUERIES", None) == "":
            return  # recorder-only invocation
    if os.environ.get("SRT_BENCH_TELEMETRY", "0") == "1":
        # telemetry tax drill: on-vs-off mini-suite wall delta (the
        # <=2% bound) + scrape latency p95 under a scrape storm —
        # emitted as a telemetry_overhead JSON line beside the others
        print(json.dumps(_telemetry_overhead_drill()), flush=True)
        if os.environ.get("SRT_BENCH_QUERIES", None) == "":
            return  # telemetry-only invocation
    if os.environ.get("SRT_BENCH_KILL_PEER", "0") == "1":
        # killed-peer recovery columns ride their own JSON line ahead of
        # the suite numbers (and are NOT re-run by per-query subprocesses)
        print(json.dumps(_killed_peer_drill()), flush=True)
    if os.environ.get("SRT_BENCH_SOAK", "0") == "1":
        # zero-downtime drill: rolling front-door restarts + one
        # coordinator failover under sustained load, oracle-verified,
        # ahead of the suite numbers (<=120 s, SRT_SOAK_DURATION_S)
        print(json.dumps(_soak_drill()), flush=True)
        if os.environ.get("SRT_BENCH_QUERIES", None) == "":
            return  # soak-only invocation
    if os.environ.get("SRT_BENCH_OVERLOAD", "0") == "1":
        # overload-survival drill: offered-load ramp to ~5x measured
        # capacity through the front door — goodput plateau ratio,
        # typed shed taxonomy, admitted p99 (tools/loadgen.py
        # --overload) — emitted as an overload_survival JSON line
        # next to the soak line
        print(json.dumps(_overload_drill()), flush=True)
        if os.environ.get("SRT_BENCH_QUERIES", None) == "":
            return  # overload-only invocation
    if os.environ.get("SRT_BENCH_PARTITION", "0") == "1":
        # partition-survival drill: cut a minority off mid-shuffle —
        # majority rows complete, minority parks typed, zero epoch
        # churn while parked, heal-and-rejoin — emitted as a
        # partition_survival JSON line beside the other drills
        print(json.dumps(_partition_survival_drill()), flush=True)
        if os.environ.get("SRT_BENCH_QUERIES", None) == "":
            return  # partition-only invocation
    if os.environ.get("SRT_BENCH_POISON", "0") == "1":
        # blast-radius containment drill: a seeded poison statement in
        # a healthy zipf mix must be quarantined within two strikes
        # with healthy goodput held (tools/loadgen.py --poison) —
        # emitted as a poison_containment JSON line beside the
        # overload/soak lines
        print(json.dumps(_poison_drill()), flush=True)
        if os.environ.get("SRT_BENCH_QUERIES", None) == "":
            return  # poison-only invocation
    if os.environ.get("SRT_BENCH_LOADGEN", "0") == "1":
        # serving-traffic proxy: drive the sustained-load harness
        # (tools/loadgen.py — wire queries over TCP through the network
        # front door: admission + quotas + prepared plan cache + spool +
        # seeded server.conn faults, oracle-verified) and emit its JSON
        # line ahead of the suite numbers.  SRT_LOADGEN_* env knobs
        # (QUERIES / CONNECTIONS / FAULT_RATE / SEED) parameterize it.
        print(json.dumps(_loadgen_drill()), flush=True)
        if os.environ.get("SRT_BENCH_QUERIES", None) == "":
            return  # loadgen-only invocation
    if os.environ.get("SRT_BENCH_FUZZ", "0") == "1":
        # hostile-input survival drill: the seeded wire/spec fuzzer
        # (tools/fuzzwire.py) against a live door with a healthy-
        # traffic sidecar — emitted as a fuzz_survival JSON line whose
        # absolute perfwatch gate needs no baseline (zero crashes /
        # hangs / untyped rejections / leaks, goodput >= 0.9x).
        # SRT_FUZZ_CASES / SRT_FUZZ_SEED parameterize it.
        print(json.dumps(_fuzz_drill()), flush=True)
        if os.environ.get("SRT_BENCH_QUERIES", None) == "":
            return  # fuzz-only invocation
    if conc > 1:
        # concurrency mode defaults to the TPC-H suite (the service
        # replay the scheduler was built for); SRT_BENCH_QUERIES narrows
        which = [q for q in os.environ.get(
            "SRT_BENCH_QUERIES", ",".join(TPCH_QUERIES)).split(",") if q]
        _run_concurrent(sf, conc, which)
        return
    which = [q for q in os.environ.get(
        "SRT_BENCH_QUERIES", ",".join(ALL_QUERIES)).split(",") if q]
    if len(which) > 1:
        # isolate each query in a subprocess with its own time budget: a
        # pathological compile or regression in one query must not take
        # down the whole benchmark signal
        _run_isolated(sf, iters, which)
        return
    name = which[0]
    print(json.dumps({name: _run_one(name, sf, iters),
                      "device": _device()}))


def _assemble(sf: float, results: dict, detail: dict, device) -> dict:
    """``device`` is the identity the first answering child printed (None
    until one has)."""
    speedups = list(results.values())
    geomean = (math.exp(sum(math.log(s) for s in speedups) / len(speedups))
               if speedups else 0.0)
    return {
        "metric": "tpch22_tpcds22_geomean_speedup_vs_cpu",
        "value": round(geomean, 4),
        "unit": "x",
        "vs_baseline": round(geomean / REFERENCE_TYPICAL_SPEEDUP, 4),
        "sf": sf,
        "queries_completed": sorted(results),
        "n_queries": len(results),
        "device": device,
        **detail,
    }


def _run_isolated(sf: float, iters: int, which) -> None:
    import subprocess
    budget = int(os.environ.get("SRT_BENCH_QUERY_TIMEOUT", "300"))
    # whole-run wall budget (BENCH_r05 was rc=124 with an empty tail: the
    # DRIVER's timeout killed us before a single line printed): stop
    # launching new queries in time to always emit the aggregate line
    wall = float(os.environ.get("SRT_BENCH_WALL_BUDGET", "820"))
    t_start = time.monotonic()
    results = {}
    detail = {}
    # this parent never imports jax: a process that has touched JAX holds
    # the chip and the next child could not get it.  Device identity is
    # copied from the first child that answered.
    device = None
    for q in which:
        remaining = wall - (time.monotonic() - t_start)
        if remaining < 15:
            detail[q] = {"error": "skipped: wall budget exhausted"}
            continue
        q_budget = max(15, min(budget, int(remaining)))
        env = dict(os.environ)
        env["SRT_BENCH_QUERIES"] = q
        env.pop("SRT_BENCH_KILL_PEER", None)  # drill ran once, up top
        env.pop("SRT_BENCH_LOADGEN", None)    # ditto the loadgen drill
        env.pop("SRT_BENCH_SOAK", None)       # ditto the soak drill
        env.pop("SRT_BENCH_OVERLOAD", None)   # ditto the overload drill
        env.pop("SRT_BENCH_POISON", None)     # ditto the poison drill
        env.pop("SRT_BENCH_PARTITION", None)  # ditto the partition drill
        env.pop("SRT_BENCH_FUZZ", None)       # ditto the fuzz drill
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env,
                capture_output=True, text=True, timeout=q_budget)
            out_lines = proc.stdout.strip().splitlines() \
                if proc.stdout else []
            line = out_lines[-1] if out_lines else ""
            sub = json.loads(line) if line.startswith("{") else None
            if proc.returncode == 0 and sub is not None and q in sub:
                detail[q] = sub[q]
                results[q] = sub[q]["speedup"]
                device = device or sub["device"]
            else:
                detail[q] = {"error":
                             proc.stderr.strip().splitlines()[-1][:200]
                             if proc.stderr.strip() else "no output"}
        except subprocess.TimeoutExpired:
            detail[q] = {"error": f"timeout after {q_budget}s"}
        # flush the aggregate after EVERY query: a killed run still
        # leaves the latest complete snapshot as the last stdout line
        print(json.dumps(_assemble(sf, results, detail, device)),
              flush=True)
    print(json.dumps(_assemble(sf, results, detail, device)), flush=True)
    failed = [q for q in which if q not in results]
    if failed:
        sys.exit(f"bench: no result for {','.join(failed)}")


def _soak_drill() -> dict:
    """SRT_BENCH_SOAK=1: a short (<=120 s) scripted rolling-restart
    soak via tools/loadgen.py --soak — a fleet of front doors under
    sustained zipf load, each door drain+GOAWAY+restarted in place, one
    coordinator kill + failover mid-run, quota churn — emitted as a
    ``soak_rolling_restart`` JSON line so the trajectory file tracks
    zero-downtime operations (queries completed, restarts survived,
    coordinator failovers, mismatches, leaks, per-tenant p99s)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import argparse

    import loadgen as _lg
    duration = min(120.0, float(os.environ.get("SRT_SOAK_DURATION_S",
                                               "45")))
    args = argparse.Namespace(
        queries=0, connections=6, tenants=8, rows=60_000,
        prepared_frac=0.5, fault_rate=0.0, slow_frac=0.15,
        slo_ms=2000.0,
        seed=int(os.environ.get("SRT_LOADGEN_SEED", "42")),
        tenant_quotas="*=16", serial_ab=0, timeout=600.0,
        no_verify=False, soak=True, soak_duration_s=duration, doors=2,
        drain_deadline_s=10.0)
    try:
        rep = _lg.run_soak(args)
        rep["metric"] = "soak_rolling_restart"
        return rep
    finally:
        import spark_rapids_tpu as _srt
        _srt.Session.reset()


def _partition_survival_drill() -> dict:
    """SRT_BENCH_PARTITION=1: the network-partition survival drill via
    tools/loadgen.py's ``_partition_drill`` — a world=3 thread-rank
    shuffle whose minority rank is cut off by the link-fault fabric
    mid-reduce; emitted as a ``partition_survival`` JSON line (rows
    complete on the majority, typed minority park, epoch bumps while
    parked — must be zero — rejoin after heal, quorum losses) so the
    trajectory file tracks partition behavior."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import loadgen as _lg
    leaks: list = []
    rep = _lg._partition_drill(leaks)
    rep["metric"] = "partition_survival"
    rep["leaks"] = leaks
    return rep


def _poison_drill() -> dict:
    """SRT_BENCH_POISON=1: the blast-radius containment drill via
    tools/loadgen.py --poison — a seeded poison statement inside a
    healthy zipf mix; emitted as a ``poison_containment`` JSON line
    (strikes-to-quarantine, healthy goodput ratio, post-quarantine
    worker deaths, typed QUARANTINED shed counts, diagnosis-bundle id,
    leaks) beside the overload/soak lines so the trajectory file
    tracks containment behavior."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import argparse

    import loadgen as _lg
    args = argparse.Namespace(
        connections=6, tenants=8, rows=60_000, prepared_frac=0.5,
        seed=int(os.environ.get("SRT_LOADGEN_SEED", "42")),
        tenant_quotas="*=16", timeout=600.0, no_verify=False,
        poison=True,
        poison_phase_s=min(60.0, float(
            os.environ.get("SRT_POISON_PHASE_S", "10"))),
        poison_goodput_min=0.9)
    try:
        rep = _lg.run_poison(args)
        rep["metric"] = "poison_containment"
        return rep
    finally:
        import spark_rapids_tpu as _srt
        _srt.Session.reset()


def _overload_drill() -> dict:
    """SRT_BENCH_OVERLOAD=1: the overload-survival drill via
    tools/loadgen.py --overload — capacity probe, then an open-loop
    offered-load ramp to ~5x capacity with per-query deadlines;
    emitted as an ``overload_survival`` JSON line (goodput plateau
    ratio, shed counts by typed reason, admitted p99, spill events,
    AIMD target) so the trajectory file tracks overload behavior."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import argparse

    import loadgen as _lg
    args = argparse.Namespace(
        connections=8, tenants=8, rows=60_000,
        seed=int(os.environ.get("SRT_LOADGEN_SEED", "42")),
        timeout=600.0,
        overload=True,
        overload_duration_s=min(60.0, float(
            os.environ.get("SRT_OVERLOAD_DURATION_S", "24"))),
        capacity_probe_s=6.0, overload_steps="1,2,3.5,5",
        overload_deadline_ms=800, plateau_min=0.85,
        admission_off=os.environ.get("SRT_OVERLOAD_ADMISSION_OFF",
                                     "0") == "1")
    try:
        rep = _lg.run_overload(args)
        rep["metric"] = "overload_survival"
        return rep
    finally:
        import spark_rapids_tpu as _srt
        _srt.Session.reset()


def _loadgen_drill() -> dict:
    """Run the sustained-load harness in-process and return its report
    (a fresh Session is NOT required — loadgen drives the current one's
    scheduler through a real TCP front door)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import argparse

    import loadgen as _lg
    args = argparse.Namespace(
        queries=int(os.environ.get("SRT_LOADGEN_QUERIES", "1000")),
        connections=int(os.environ.get("SRT_LOADGEN_CONNECTIONS", "8")),
        tenants=8, rows=200_000, prepared_frac=0.5,
        fault_rate=float(os.environ.get("SRT_LOADGEN_FAULT_RATE",
                                        "0.02")),
        slow_frac=0.05, slo_ms=2000.0,
        seed=int(os.environ.get("SRT_LOADGEN_SEED", "42")),
        tenant_quotas="*=16", serial_ab=20, timeout=600.0,
        no_verify=False)
    try:
        return _lg.run(args)
    finally:
        # loadgen tuned session confs (batch size, cache) for the wire
        # workload: a fresh session keeps the suite numbers untainted
        import spark_rapids_tpu as _srt
        _srt.Session.reset()


def _fuzz_drill() -> dict:
    """Run the hostile-input fuzzer in-process and return its
    ``fuzz_survival`` report (frames + specs against a live door, with
    the oracle-verified healthy-traffic sidecar measuring goodput)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import argparse

    import fuzzwire as _fw
    args = argparse.Namespace(
        cases=int(os.environ.get("SRT_FUZZ_CASES", "600")),
        seed=int(os.environ.get("SRT_FUZZ_SEED", "20260807")),
        rows=20_000, attackers=4, case_timeout=6.0,
        sidecar_connections=2, baseline_s=3.0,
        corpus_dir=None, replay=None, out=None)
    try:
        rep = _fw.run_fuzz(args)
        rep["metric"] = "fuzz_survival"
        return rep
    finally:
        # the fuzz door tuned session confs for the wire workload: a
        # fresh session keeps the suite numbers untainted
        import spark_rapids_tpu as _srt
        _srt.Session.reset()


def _device() -> dict:
    """Device identity as JAX reports it (child side only)."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


if __name__ == "__main__":
    main()
