"""Where the harness finds a cell's files, and the readers of metrics.

A cell, a configuration, a traffic mix, a query and a metric are files found
by the names ``BENCHMARK.json`` gives: a later PR adds files and entries and
edits none.  ``roots`` is the list of directories searched, a throw-away
``--root`` before the benchmark's own.

A metric's file names its ``kind``; each kind has one reader here, which
takes the metric from what the run observed (``Observed``) and returns None
where there is nothing to read: the metric then stays out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from harness import stats
from harness.peaks import peak


def find(roots: List[str], *parts: str) -> str:
    for root in roots:
        path = os.path.join(root, *parts)
        if os.path.exists(path):
            return path
    raise SystemExit(f"benchmark: no file {os.path.join(*parts)} under "
                     f"{' or '.join(roots)}")


def load_json(roots: List[str], *parts: str) -> Dict:
    with open(find(roots, *parts)) as f:
        return json.load(f)


def load_module(roots: List[str], *parts: str):
    path = find(roots, *parts)
    name = "bench_" + "_".join(parts).replace(".py", "").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Observed:
    """What one run saw, for the metric readers."""
    setup_s: float
    window_s: float
    latencies: List[float]            # completed queries of the window
    qs_delta: Dict[str, float]        # QueryStats after - before the window
    memory: Dict[str, float]          # device.memory_stats() after it
    device_kind: str
    platform: str
    trace: Optional[Dict] = None      # trace_reduce.reduce() of the span
    traced_min_bytes: float = 0.0     # sum of min_bytes over the span


def _harness_clock(spec, ob: Observed):
    stat = spec["stat"]
    if stat == "setup":
        return ob.setup_s
    if not ob.latencies:
        return None
    if stat == "rate":
        return len(ob.latencies) / ob.window_s
    if stat == "median":
        return stats.median(ob.latencies)
    if stat.startswith("p"):
        return stats.percentile(ob.latencies, float(stat[1:]))
    raise SystemExit(f"benchmark: harness_clock has no stat {stat!r}")


def _querystats_delta(spec, ob: Observed):
    if spec["field"] not in ob.qs_delta:
        return None
    v = ob.qs_delta[spec["field"]]
    per = spec.get("per")
    if per == "window_s":
        v /= ob.window_s
    elif per == "queries":
        if not ob.latencies:
            return None
        v /= len(ob.latencies)
    elif per is not None:
        raise SystemExit(f"benchmark: querystats_delta has no per {per!r}")
    return v * spec.get("scale", 1)


def _memory_stats(spec, ob: Observed):
    num, den = ob.memory.get(spec["num"]), ob.memory.get(spec.get("den"))
    if num is None or (spec.get("den") and not den):
        return None
    return num / den * spec.get("scale", 1) if spec.get("den") else num


def _trace(spec, ob: Observed):
    # never from a CPU backend: a host's trace is no device metric
    if ob.trace is None or ob.platform == "cpu":
        return None
    what = spec["field"]
    if what == "bytes_roofline_pct":
        if not ob.traced_min_bytes:
            return None
        least_s = ob.traced_min_bytes / peak(ob.device_kind,
                                             "hbm_bytes_per_s")
        return 100.0 * least_s / ob.trace["busy_s"]
    return ob.trace.get(what)


READERS: Dict[str, Callable] = {
    "harness_clock": _harness_clock,
    "querystats_delta": _querystats_delta,
    "memory_stats": _memory_stats,
    "trace": _trace,
}


def reader(metric: str, spec: Dict) -> Callable:
    kind = spec.get("kind")
    if kind not in READERS:
        raise SystemExit(
            f"benchmark: metric {metric!r} has source kind {kind!r}, which "
            f"has no reader yet (known: {sorted(READERS)}); a metric that "
            f"needs it brings it")
    return READERS[kind]


def read_all(specs: Dict[str, Dict], units: Dict[str, str],
             ob: Observed) -> Dict[str, Dict]:
    out = {}
    for name, spec in specs.items():
        v = reader(name, spec)(spec, ob)
        if v is not None:
            out[name] = {"value": v, "unit": units[name]}
    return out
