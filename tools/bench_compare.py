"""Diff two bench.py aggregate JSON files; exit non-zero on regression.

The trajectory guard for ``BENCH_r0N`` snapshots: compares the per-query
engine times and the aggregate geomean speedup of a NEW run against an
OLD one, with percentage thresholds for what counts as a regression.

Accepted file shapes (auto-detected):
  * the raw aggregate object ``bench.py`` prints (its last stdout line);
  * a driver wrapper ``{"parsed": {...}}`` or ``{"tail": "...json..."}``
    (the ``BENCH_r0N.json`` capture format) — the aggregate is pulled
    from ``parsed``, falling back to the last JSON line of ``tail``.

Usage:
  python tools/bench_compare.py OLD.json NEW.json \
      [--max-query-regress-pct 20] [--max-agg-regress-pct 5] \
      [--max-sync-increase 0] [--max-compile-increase 0] \
      [--max-cold-seconds 0]

Exit codes: 0 = no regression, 1 = regression found, 2 = usage/parse
error.  A query that completed in OLD but errored/vanished in NEW is a
regression; queries new to NEW are reported as additions only.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Optional, Tuple


def load_aggregate(path: str) -> dict:
    """Load a bench aggregate from either accepted file shape."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and "metric" in data:
        return data
    if isinstance(data, dict):
        parsed = data.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            return parsed
        tail = data.get("tail") or ""
        for line in reversed(tail.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    obj = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "metric" in obj:
                    return obj
    raise ValueError(f"{path}: no bench aggregate found "
                     "(expected bench.py output or a driver capture)")


def query_times(agg: dict) -> Dict[str, Optional[float]]:
    """{query: engine_s or None-if-errored} from an aggregate object."""
    out: Dict[str, Optional[float]] = {}
    for k, v in agg.items():
        if not isinstance(v, dict):
            continue
        if "engine_s" in v:
            out[k] = float(v["engine_s"])
        elif "error" in v:
            out[k] = None
    return out


def query_syncs(agg: dict) -> Dict[str, Optional[float]]:
    """{query: warm blocking-sync count} where the aggregate has one."""
    out: Dict[str, Optional[float]] = {}
    for k, v in agg.items():
        if isinstance(v, dict) and "syncs_warm" in v:
            out[k] = float(v["syncs_warm"])
    return out


def query_compiles(agg: dict) -> Dict[str, Optional[float]]:
    """{query: warm compile count} where the aggregate has one."""
    out: Dict[str, Optional[float]] = {}
    for k, v in agg.items():
        if isinstance(v, dict) and "compiles_warm" in v:
            out[k] = float(v["compiles_warm"])
    return out


def query_cold_compile_s(agg: dict) -> Dict[str, Optional[float]]:
    """{query: cold compile seconds} where the aggregate has one."""
    out: Dict[str, Optional[float]] = {}
    for k, v in agg.items():
        if isinstance(v, dict) and "compile_s_cold" in v:
            out[k] = float(v["compile_s_cold"])
    return out


def compare(old: dict, new: dict, max_query_pct: float,
            max_agg_pct: float, max_sync_increase: float = 0.0,
            max_compile_increase: float = 0.0,
            max_cold_seconds: float = 0.0) -> Tuple[list, list]:
    """Return (regressions, notes) as printable strings."""
    regressions, notes = [], []
    old_q, new_q = query_times(old), query_times(new)

    # cold-vs-warm compile seconds (the warm-start subsystem's CI
    # teeth): the cold pass is where a restart pays — a per-query
    # cold-compile budget turns "the fleet restarts cold" from a pager
    # into a failed gate.  First-class column either way; a gate only
    # when --max-cold-seconds is set
    old_k, new_k = query_cold_compile_s(old), query_cold_compile_s(new)
    for q in sorted(set(old_k) | set(new_k)):
        o, n = old_k.get(q), new_k.get(q)
        if o is not None and n is not None:
            notes.append(
                f"{q}: compile_s_cold {o:.3f} -> {n:.3f}"
                + (f"  (warm compiles {query_compiles(new).get(q, 0):g})"
                   if q in query_compiles(new) else ""))
        if max_cold_seconds > 0 and n is not None \
                and n > max_cold_seconds:
            regressions.append(
                f"{q}: compile_s_cold {n:.3f}s  "
                f"[> --max-cold-seconds {max_cold_seconds:g}]")

    # sync-count guard (region fusion's latency contract): each blocking
    # device→host fetch stalls the dispatch front, so a warm sync-count
    # increase beyond the tolerance is a regression even when wall-clock
    # noise hides it
    old_s, new_s = query_syncs(old), query_syncs(new)
    for q in sorted(set(old_s) & set(new_s)):
        o, n = old_s[q], new_s[q]
        if n > o + max_sync_increase:
            regressions.append(
                f"{q}: syncs_warm {o:g} -> {n:g}  "
                f"[> +{max_sync_increase:g} blocking fetches]")
        elif n < o:
            notes.append(f"{q}: syncs_warm {o:g} -> {n:g}  [improved]")

    # compile-count guard (the compile ledger's CI teeth): a warm-path
    # recompile costs whole seconds on a real TPU even when the CPU
    # test mesh hides it in wall-clock noise, so a warm compile-count
    # increase beyond the tolerance is a regression in its own right
    old_c, new_c = query_compiles(old), query_compiles(new)
    for q in sorted(set(old_c) & set(new_c)):
        o, n = old_c[q], new_c[q]
        if n > o + max_compile_increase:
            regressions.append(
                f"{q}: compiles_warm {o:g} -> {n:g}  "
                f"[> +{max_compile_increase:g} warm compiles]")
        elif n < o:
            notes.append(
                f"{q}: compiles_warm {o:g} -> {n:g}  [improved]")

    old_v = float(old.get("value") or 0.0)
    new_v = float(new.get("value") or 0.0)
    if old_v > 0:
        delta_pct = (new_v - old_v) / old_v * 100
        line = (f"aggregate geomean: {old_v:.3f}x -> {new_v:.3f}x "
                f"({delta_pct:+.1f}%)")
        if delta_pct < -max_agg_pct:
            regressions.append(line + f"  [> {max_agg_pct}% drop]")
        else:
            notes.append(line)

    for q in sorted(set(old_q) | set(new_q)):
        o, n = old_q.get(q), new_q.get(q)
        if o is None and n is None:
            continue
        if q not in old_q:
            notes.append(f"{q}: new in NEW (engine_s={n})")
            continue
        if o is None:
            if n is not None:
                notes.append(f"{q}: fixed (errored in OLD, now {n:.3f}s)")
            continue
        if n is None or q not in new_q:
            regressions.append(
                f"{q}: completed in OLD ({o:.3f}s) but "
                f"{'errored' if q in new_q else 'missing'} in NEW")
            continue
        delta_pct = (n - o) / o * 100
        line = f"{q}: engine_s {o:.4f} -> {n:.4f} ({delta_pct:+.1f}%)"
        if delta_pct > max_query_pct:
            regressions.append(line + f"  [> {max_query_pct}% slower]")
        elif delta_pct < -max_query_pct:
            notes.append(line + "  [improved]")
    return regressions, notes


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="diff two bench.py aggregate JSON files")
    p.add_argument("old")
    p.add_argument("new")
    p.add_argument("--max-query-regress-pct", type=float, default=20.0,
                   help="per-query engine_s slowdown tolerated (%%)")
    p.add_argument("--max-agg-regress-pct", type=float, default=5.0,
                   help="aggregate geomean drop tolerated (%%)")
    p.add_argument("--max-sync-increase", type=float, default=0.0,
                   help="per-query warm blocking-sync count increase "
                        "tolerated (absolute fetches; default 0)")
    p.add_argument("--max-compile-increase", type=float, default=0.0,
                   help="per-query warm compile count increase "
                        "tolerated (absolute compiles; default 0)")
    p.add_argument("--max-cold-seconds", type=float, default=0.0,
                   help="per-query COLD compile-seconds budget in NEW "
                        "(0 = report only, no gate)")
    p.add_argument("-q", "--quiet", action="store_true",
                   help="print regressions only")
    args = p.parse_args(argv)
    try:
        old = load_aggregate(args.old)
        new = load_aggregate(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2
    regressions, notes = compare(old, new, args.max_query_regress_pct,
                                 args.max_agg_regress_pct,
                                 args.max_sync_increase,
                                 args.max_compile_increase,
                                 args.max_cold_seconds)
    if not args.quiet:
        for line in notes:
            print("  " + line)
    if regressions:
        print(f"bench_compare: {len(regressions)} regression(s):",
              file=sys.stderr)
        for line in regressions:
            print("  REGRESSION " + line, file=sys.stderr)
        return 1
    print(f"bench_compare: OK ({len(query_times(new))} queries compared, "
          f"no regression beyond thresholds)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
