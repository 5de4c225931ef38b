#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From ``--seed`` it makes the cell's data (parquet, anew every run) and a
pool of substitution-parameter sets per query, opens a ``Session`` under the
configuration's confs, runs every (query, set) once to warm every shape up
(all of that is ``setup_s``), then drives the mix in a closed loop for
``--seconds`` through ``DataFrame.collect()``.  Once the window has closed
and the device's peak memory has been read it frees the session, computes
the plain pandas reference for every (query, set) and compares every answer
the window produced with it.  With ``--trace 1`` two whole cycles of the mix
run under the JAX profiler before the window, and the per-layer metrics are
printed instead of the end-to-end ones.

The last line of stdout is the result; earlier lines are one JSON object per
phase.  Without an accelerator, or with fewer chips than the cell asks for,
the run exits 2 and prints no result.  ``--rehearse`` (never passed by the
driver) skips that look, for a walk through the phases on a named CPU: the
line then says ``cpu`` in ``device`` and carries no device metric.

Everything a cell is made of is a file found by name (see
``harness/sources.py``); ``--root`` names a directory with a
``BENCHMARK.json`` and files of its own that is searched before this one.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from the process's first line

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import zlib  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for _p in (REPO, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import (compare, lastline, loop, sources, stats,  # noqa: E402
                     trace_reduce)
from harness.watchdog import Watchdog  # noqa: E402

BUDGET_S = 1180.0       # a first run compiles; the driver allows it 1200 s
TRACE_CYCLES = 2
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}


def say(phase: str, **rec) -> None:
    print(json.dumps({"phase": phase, **rec}, default=str), flush=True)


def entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: BENCHMARK.json has no {what} {name!r} "
                     f"(it has {[e['name'] for e in entries]})")


def pools(mix, queries, pool, seed):
    """Per query a pool of parameter sets, drawn from the seed and the
    query's name alone: the same whatever else is in the mix."""
    import numpy as np
    out = {}
    for q in mix:
        rng = np.random.default_rng([int(seed), zlib.crc32(q.encode()), 7])
        out[q] = [queries[q].params(rng) for _ in range(pool)]
    return out


class Cell:
    """A cell's files, found by the names ``BENCHMARK.json`` gives."""

    def __init__(self, workload: str, root: str = None):
        self.name = workload
        self.bench_dir = root or REPO
        self.roots = [root, HERE] if root else [HERE]
        with open(os.path.join(self.bench_dir, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self.entry = entry(self.bench["workloads"], workload, "workload")
        self.chips = self.entry["chips"]
        cfg = entry(self.bench["configs"], self.entry["config"], "config")
        with open(os.path.join(self.bench_dir, cfg["file"])) as f:
            self.config = json.load(f)
        self.traffic = sources.load_json(
            self.roots, "traffic", self.entry["traffic"] + ".json")
        self.loop = loop.get(self.traffic["loop"])
        if self.traffic.get("clients", 1) != 1:
            raise SystemExit(
                f"benchmark: {self.traffic['clients']} clients have no code "
                f"yet; a cell that needs them brings it")
        self.mix, self.pool = self.traffic["mix"], self.traffic["pool"]
        suite = self.config["suite"]
        if self.traffic["suite"] != suite:
            raise SystemExit(
                f"benchmark: traffic {self.entry['traffic']!r} is over "
                f"suite {self.traffic['suite']!r}, config "
                f"{self.entry['config']!r} over {suite!r}")
        self.datagen = sources.load_module(
            self.roots, "datagen", self.config["datagen"] + ".py")
        self.queries = {
            q: sources.load_module(self.roots, "queries", suite, q + ".py")
            for q in self.mix}
        self.columns = {}  # table -> the columns the mix reads
        for q in self.mix:
            for t, cols in self.queries[q].TABLES.items():
                self.columns.setdefault(t, set()).update(cols)
        state = os.path.join(self.bench_dir, ".cache", "benchmark")
        self.data_dir = os.path.join(state, "data", self.entry["config"])
        self.trace_dir = os.path.join(state, "trace", workload)

    def metrics(self, key: str):
        """The cell's metrics of one list (``end_to_end``, ``per_layer``):
        those with no ``workloads`` key, or that list the cell."""
        return [m for m in self.bench[key]
                if self.name in m.get("workloads", [self.name])]

    def make_data(self, seed: int):
        shutil.rmtree(self.data_dir, ignore_errors=True)
        return self.datagen.gen(self.config["sf"], seed, self.data_dir,
                                sorted(self.columns))

    def pools(self, seed: int):
        return pools(self.mix, self.queries, self.pool, seed)

    def reference_tables(self, paths):
        import pyarrow.parquet as pq
        return {t: pq.read_table(paths[t], columns=sorted(cols)).to_pandas()
                for t, cols in self.columns.items()}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = None, require_chip: bool = True):
    """Returns (exit code, result line or None)."""
    cell = Cell(workload, root)
    config, mix, pool = cell.config, cell.mix, cell.pool
    queries, datagen, run_loop = cell.queries, cell.datagen, cell.loop
    wanted = cell.metrics("per_layer" if trace else "end_to_end")
    units = {m["name"]: m["unit"] for m in wanted}
    specs = {m["name"]: sources.load_json(cell.roots, "metrics",
                                          m["name"] + ".json")
             for m in wanted}
    for name, spec in specs.items():
        sources.reader(name, spec)  # an unknown kind fails here, by name

    # -- the system under test: fails here where the program is absent ------
    import spark_rapids_tpu as srt  # turns on 64-bit before jax is used
    import jax
    from spark_rapids_tpu.utils.metrics import QueryStats
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if require_chip and (device["platform"] == "cpu"
                         or device["count"] < cell.chips):
        print(f"benchmark: {workload} asks for {cell.chips} chip(s), JAX "
              f"has {device['count']} x {device['platform']} "
              f"({device['kind']}): no result", file=sys.stderr)
        return 2, None

    compile_log, cache_counts = [], {"requests": 0, "hits": 0, "writes": 0}

    def on_event(event, **kw):
        key = _CACHE_EVENTS.get(event)
        if key:
            cache_counts[key] += 1

    def on_duration(event, duration, fun_name=None, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_log.append((round(duration, 2), fun_name))
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    dog = Watchdog(BUDGET_S)
    dog.start()
    columns, trace_dir = cell.columns, cell.trace_dir
    with dog.phase("datagen", 240):
        t0 = time.perf_counter()
        paths = cell.make_data(seed)
        nrows = datagen.rows(config["sf"])
        say("datagen", seconds=time.perf_counter() - t0, sf=config["sf"],
            tables=sorted(columns),
            bytes=sum(os.path.getsize(p) for p in paths.values()))
    with dog.phase("session", 180):
        t0 = time.perf_counter()
        sess = srt.Session.get_or_create(settings=dict(config["confs"]))
        # every program goes to the persistent cache, the quick ones too
        # (the program's device init sets the threshold to 0.5 s, so this
        # comes after it): a second run in a checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        dfs = {t: sess.read_parquet(paths[t]) for t in columns}
        say("session", seconds=time.perf_counter() - t0, device=device,
            jax=jax.__version__,
            compile_cache_dir=jax.config.jax_compilation_cache_dir)
    param_pools = cell.pools(seed)
    say("params", pools=param_pools)

    def run_one(query, k):
        with jax.profiler.TraceAnnotation(
                f"{trace_reduce.SPAN_PREFIX}{query}:run"):
            # collect() returns host rows: the device has finished
            return queries[query].run(dfs, param_pools[query][k])

    def qs_now():
        return {k: v for k, v in QueryStats.get().snapshot().items()
                if isinstance(v, (int, float))}

    with dog.phase("warmup", 1000):
        t0 = time.perf_counter()
        warm = run_loop(run_one, mix, pool, cycles=pool)[0]
        for a in warm:
            if a.error:  # a shape that cannot warm up cannot be timed
                raise SystemExit(f"benchmark: warm-up of {a.query} set "
                                 f"{a.set_index} failed: {a.error}")
        say("warmup", seconds=time.perf_counter() - t0,
            per_query_s={q: [round(a.seconds, 3) for a in warm
                             if a.query == q] for q in mix},
            compiles=len(compile_log), persistent_cache=dict(cache_counts),
            slowest_compiles=sorted(compile_log, reverse=True)[:5])
    setup_s = time.perf_counter() - T_START

    answers, reduced, traced_bytes, position = [], None, 0.0, 0
    if trace:
        with dog.phase("trace", 300):
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                traced, _, position = run_loop(run_one, mix, pool,
                                               cycles=TRACE_CYCLES)
            finally:
                jax.profiler.stop_trace()
            others = {}
            events = trace_reduce.events_of(trace_dir, others)
            reduced = trace_reduce.reduce(events)
            say("trace", holds=trace_reduce.describe(events),
                other_host_events=sorted(others.items(),
                                         key=lambda kv: -kv[1])[:12],
                # the program's own trace_range() names are <op id>:<name>
                program_annotations=sorted(
                    ((n, c) for n, c in others.items()
                     if re.fullmatch(r"[\w.#-]+:[\w.#-]+", n)),
                    key=lambda kv: -kv[1])[:12],
                reduced={k: v for k, v in (reduced or {}).items()
                         if k not in ("device_ops", "idle_gaps")})
            shutil.rmtree(trace_dir, ignore_errors=True)
            answers += traced
            traced_bytes = float(sum(
                queries[a.query].min_bytes(nrows, datagen.SCHEMA,
                                           len(a.rows))
                for a in traced if a.rows is not None))

    with dog.phase("window", seconds + 240):
        n0 = len(compile_log)
        qs0 = qs_now()
        window, window_s, _ = run_loop(run_one, mix, pool, seconds=seconds,
                                       start_at=position)
        qs1 = qs_now()
        answers += window
    memory = [d.memory_stats() or {} for d in devs[:cell.chips]]
    memory = max(memory, key=lambda m: m.get("peak_bytes_in_use", 0))
    device["memory_peak_bytes"] = memory.get("peak_bytes_in_use", 0)
    done = [a for a in window if a.error is None]
    say("window", seconds=window_s, queries=len(window),
        failed=[(a.query, a.set_index, a.error) for a in answers if a.error],
        median_s={q: stats.median(
            [a.seconds for a in done if a.query == q] or [0.0])
            for q in mix},
        latencies_s={q: [round(a.seconds, 3) for a in done if a.query == q]
                     for q in mix},
        compiles_logged=compile_log[n0:],
        # what the program counted over the window: a run that reads far
        # off is looked into from here
        querystats={k: qs1[k] - qs0.get(k, 0) for k in qs1
                    if qs1[k] != qs0.get(k, 0)})

    # -- the reference, once the program's state is freed -------------------
    with dog.phase("reference", 300):
        t0 = time.perf_counter()
        del dfs
        srt.Session.reset()
        pds = cell.reference_tables(paths)
        used = sorted({(a.query, a.set_index) for a in answers})
        want = {(q, k): queries[q].reference(pds, param_pools[q][k])
                for q, k in used}
        correct, failed, compared, errs = compare.judge(
            [(a.query, a.set_index, a.rows) for a in answers], want,
            config["limits"])
        say("reference", seconds=time.perf_counter() - t0,
            pairs=len(used), answers=len(answers),
            rows={f"{q}/{k}": len(r) for (q, k), r in want.items()})
    shutil.rmtree(cell.data_dir, ignore_errors=True)

    ob = sources.Observed(
        setup_s=setup_s, window_s=window_s,
        latencies=[a.seconds for a in done],
        qs_delta={k: qs1[k] - qs0.get(k, 0) for k in qs1},
        memory=memory, device_kind=device["kind"],
        platform=device["platform"], trace=reduced,
        traced_min_bytes=traced_bytes)
    breakdown = None
    if reduced is not None and device["platform"] != "cpu":
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    line = lastline.build(correct, len(answers), failed,
                          sources.read_all(specs, units, ob), device,
                          compared, breakdown)
    lastline.emit(line)
    return 0, line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=None,
                    help="a directory with a BENCHMARK.json and files of "
                         "its own, searched before this one")
    ap.add_argument("--rehearse", action="store_true",
                    help="skip the look for a chip (never a result the "
                         "driver takes: the line names the device)")
    args = ap.parse_args(argv)
    code, _ = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), root=args.root,
                       require_chip=not args.rehearse)
    return code


if __name__ == "__main__":
    sys.exit(main())
