"""Summarize a query trace file (Chrome trace events + spanTree).

Reads a trace written by the engine (``spark.rapids.tpu.sql.trace.dir``,
``SRT_BENCH_TRACE_DIR``, ``Session.last_trace().write(...)``, or a
MERGED multi-query trace from ``utils.tracing.write_merged`` — the
bench concurrency mode and the query service emit those) and prints:

  * the hot-operator table: per-operator SELF time (operator interval
    minus nested child-operator intervals on the same thread), total
    time, rows, and batches — self time sums to ~query wall time on a
    serial (depth-0) run;
  * the blocking-fetch count and attributable D2H wait;
  * the overlap ratio: thread-busy time over wall time (1.0 = fully
    serial; >1 means the pipeline actually overlapped host and device
    work).

A trace containing several overlapping query span trees (the merged
``spanTrees`` form, one pid per query) renders one section per query
plus a **contention summary**: the span of the whole batch, per-query
concurrency overlap, peak concurrency, and aggregate throughput.

Cross-rank stitching (``--stitch``): a distributed query's DCN request
frames carry its trace id, so remote serve-side work (peer fetches,
durable re-pulls) lands in per-rank SHARD files
(``<trace_id>.rank<k>.shard.jsonl``) beside the trace.  ``--stitch``
discovers every shard for the trace's id, merges them into ONE
Perfetto-loadable tree — each rank its own pid, every remote span
parented under the query root in the ``spanTree``, attributable to its
owning rank — writes ``<trace>.stitched.json``, and reports per-rank
span counts.

Root-cause attribution (``--why``): append the flight recorder's wait
decomposition for each query — canonical terms (queue wait, compile,
H2D, dispatch, fetch wait, shuffle, spill, stream/spool) against the
statement fingerprint's EWMA baseline, the dominant anomalous term
named — the same analysis ``tools/explain_slow.py`` runs standalone
(traces sealed by ``utils/recorder.py`` carry it pre-stamped).

A profiler trace (``--xplane <dir>``): the engine's spans enter a
``jax.profiler.TraceAnnotation`` under their vocabulary names
(``utils/tracing.SPANS``), so ``with jax.profiler.trace(d):
df.collect()`` puts them in the same ``.xplane.pb`` as the device's
operations, on one clock.  ``--xplane d`` reads the newest trace under
``d`` with ``jax.profiler.ProfileData`` and prints device seconds by
jitted program (the ``XLA Modules`` line: ``jit_<program name>``), by
named scope (``segmented_reduce``, ``groupby_sort``, ...), and the
longest device-idle gaps, each labelled with the innermost program span
whose interval covers its middle.

Usage: ``python tools/trace_report.py [--stitch] [--why] TRACE.json [...]``
       ``python tools/trace_report.py --xplane TRACE_DIR [--top N]``
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Dict, List


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _op_meta(span_tree: List[dict]) -> Dict[str, dict]:
    """Flatten the spanTree into op_id -> {name, desc, metrics, depth}."""
    out: Dict[str, dict] = {}

    def walk(node, depth):
        out[node["op_id"]] = {"name": node.get("name", node["op_id"]),
                              "desc": node.get("desc", ""),
                              "metrics": node.get("metrics", {}),
                              "depth": depth}
        for c in node.get("children", ()):
            walk(c, depth + 1)

    for root in span_tree or ():
        walk(root, 0)
    return out


def split_queries(data: dict):
    """Decompose a trace into per-query sub-traces.

    A single-query trace (the ``spanTree`` form) passes through as-is.
    A merged multi-query trace (``spanTrees``: one entry and one pid per
    query, overlapping timestamps) splits by pid; the second return
    value carries the merged metadata for the contention summary.
    """
    span_trees = data.get("spanTrees")
    if not span_trees:
        return [data], None
    by_pid: Dict[int, list] = {}
    for e in data.get("traceEvents", []):
        by_pid.setdefault(e.get("pid", 1), []).append(e)
    subs = []
    for st in span_trees:
        pid = st.get("pid", 1)
        subs.append({
            "traceEvents": by_pid.get(pid, []),
            "spanTree": st.get("roots", []),
            "otherData": {"label": st.get("label", f"pid-{pid}"),
                          "status": st.get("status", "ok"),
                          "dropped_events": st.get("dropped_events", 0)},
        })
    return subs, span_trees


def contention(span_trees: List[dict]) -> dict:
    """Cross-query contention numbers for a merged trace: where queries
    overlapped, how deep the concurrency went, and the batch throughput."""
    ivs = sorted((st.get("start_offset_s", 0.0),
                  st.get("start_offset_s", 0.0) + st.get("wall_s", 0.0))
                 for st in span_trees)
    marks = sorted({t for iv in ivs for t in iv})
    overlap_s = 0.0
    busy_s = 0.0
    peak = 0
    for lo, hi in zip(marks, marks[1:]):
        n = sum(1 for s, t in ivs if s <= lo and t >= hi)
        peak = max(peak, n)
        if n >= 1:
            busy_s += hi - lo
        if n >= 2:
            overlap_s += hi - lo
    span_s = (max(t for _, t in ivs) - min(s for s, _ in ivs)) \
        if ivs else 0.0
    sum_walls = sum(t - s for s, t in ivs)
    statuses: Dict[str, int] = {}
    for st in span_trees:
        s = st.get("status", "ok")
        statuses[s] = statuses.get(s, 0) + 1
    return {
        "queries": len(span_trees),
        "span_s": span_s,
        "sum_walls_s": sum_walls,
        "overlap_s": overlap_s,
        "busy_s": busy_s,
        "peak_concurrency": peak,
        # >1 means the service genuinely ran queries side by side
        "concurrency_ratio": (sum_walls / span_s) if span_s else 0.0,
        "throughput_qps": (len(span_trees) / span_s) if span_s else 0.0,
        "statuses": statuses,
    }


def analyze(data: dict) -> dict:
    """Compute the report's numbers from a loaded (single-query) trace
    dict."""
    events = data.get("traceEvents", [])
    xs = [e for e in events if e.get("ph") == "X"]
    query = next((e for e in xs if e.get("cat") == "query"), None)
    wall_us = (query or {}).get("dur", 0.0) or max(
        (e["ts"] + e["dur"] for e in xs), default=0.0)

    ops = _op_meta(data.get("spanTree", []))
    per_op: Dict[str, dict] = {}

    def op_entry(op_id):
        e = per_op.get(op_id)
        if e is None:
            meta = ops.get(op_id, {})
            e = per_op[op_id] = {
                "op": op_id, "name": meta.get("name", op_id),
                "desc": meta.get("desc", ""),
                "metrics": meta.get("metrics", {}),
                "self_us": 0.0, "total_us": 0.0}
        return e

    # self time: per thread, nest the operator intervals by containment;
    # an interval's self time is its duration minus its immediate
    # children's durations (the classic flame-graph subtraction)
    op_events = [e for e in xs if e.get("cat") == "operator"]
    by_tid: Dict[int, list] = {}
    for e in op_events:
        by_tid.setdefault(e.get("tid", 0), []).append(e)
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list = []  # (end_us, event, child_us accumulator ref)
        for e in evs:
            end = e["ts"] + e["dur"]
            while stack and e["ts"] >= stack[-1][0] - 1e-9:
                stack.pop()
            if stack:
                stack[-1][1]["_child_us"] = \
                    stack[-1][1].get("_child_us", 0.0) + e["dur"]
            stack.append((end, e))
        for e in evs:
            op = e.get("args", {}).get("op")
            if not op:
                continue
            ent = op_entry(op)
            ent["total_us"] += e["dur"]
            ent["self_us"] += max(0.0, e["dur"] - e.pop("_child_us", 0.0))

    # busy time per thread (union of operator+io+shuffle intervals) for
    # the overlap ratio
    busy_us = 0.0
    work = [e for e in xs
            if e.get("cat") in ("operator", "io", "shuffle", "ici")]
    by_tid_work: Dict[int, list] = {}
    for e in work:
        by_tid_work.setdefault(e.get("tid", 0), []).append(e)
    for evs in by_tid_work.values():
        ivs = sorted((e["ts"], e["ts"] + e["dur"]) for e in evs)
        cur_s, cur_e = None, None
        for s, t in ivs:
            if cur_s is None:
                cur_s, cur_e = s, t
            elif s <= cur_e:
                cur_e = max(cur_e, t)
            else:
                busy_us += cur_e - cur_s
                cur_s, cur_e = s, t
        if cur_s is not None:
            busy_us += cur_e - cur_s

    # cross-query cache events (cat "cache": cache:hit / cache:miss /
    # cache:evict marks with tier+bytes attrs); the QueryStats snapshot
    # on the query's root event is authoritative when present
    cache_events = [e for e in xs if e.get("cat") == "cache"]

    def _cname(n):
        return sum(1 for e in cache_events if e.get("name") == n)

    # fault-framework events (cat "fault": fault:injected /
    # retry:attempt / degraded:cpu marks); the QueryStats snapshot on
    # the root event is authoritative when present
    fault_events = [e for e in xs if e.get("cat") == "fault"]

    def _fname(n):
        return sum(1 for e in fault_events if e.get("name") == n)

    # network-front-door events (cat "server")
    server_events = [e for e in xs if e.get("cat") == "server"]

    # scheduler/admission events (cat "scheduler": queue-wait spans,
    # admission:shed / admission:aimd marks)
    sched_events = [e for e in xs if e.get("cat") == "scheduler"]

    def _fname_cat(evs, n):
        return sum(1 for e in evs if e.get("name") == n)

    # region-fusion spans (cat "fusion": one fusion:region span per
    # executed region, args = member count / prologue syncs / compiles)
    fusion_events = [e for e in xs if e.get("cat") == "fusion"
                     and e.get("name") == "fusion:region"]

    fetch_events = [e for e in xs if e.get("cat") == "fetch"]
    blocking = [e for e in fetch_events
                if e.get("args", {}).get("blocking")]
    fetch_wait_us = sum(e["dur"] for e in fetch_events)
    compiles = [e for e in xs if e.get("cat") == "compile"]
    qargs = (query or {}).get("args", {})

    self_total_us = sum(e["self_us"] for e in per_op.values())
    return {
        "label": data.get("otherData", {}).get("label", "?"),
        "status": data.get("otherData", {}).get(
            "status", qargs.get("status", "ok")),
        "wall_s": wall_us / 1e6,
        "n_events": len(xs),
        "dropped": data.get("otherData", {}).get("dropped_events", 0),
        "operators": sorted(per_op.values(),
                            key=lambda e: -e["self_us"]),
        "op_depth": {op: m.get("depth", 0) for op, m in ops.items()},
        "self_total_s": self_total_us / 1e6,
        "busy_s": busy_us / 1e6,
        "overlap_ratio": (busy_us / wall_us) if wall_us else 0.0,
        "self_coverage": (self_total_us / wall_us) if wall_us else 0.0,
        "blocking_fetches": int(qargs.get("blocking_fetches",
                                          len(blocking))),
        "async_fetches": int(qargs.get("async_fetches",
                                       len(fetch_events) - len(blocking))),
        "fetch_wait_s": fetch_wait_us / 1e6,
        "compiles": int(qargs.get("compiles", len(compiles))),
        "compile_s": float(qargs.get("compile_s",
                                     sum(e["dur"] for e in compiles) / 1e6)),
        "threads": len(by_tid_work),
        "cache_hits": int(qargs.get("cache_hits", _cname("cache:hit"))),
        "cache_misses": int(qargs.get("cache_misses",
                                      _cname("cache:miss"))),
        "cache_evictions": int(qargs.get("cache_evictions",
                                         _cname("cache:evict"))),
        "cache_bytes_saved": int(qargs.get("cache_hit_bytes", sum(
            e.get("args", {}).get("bytes", 0) for e in cache_events
            if e.get("name") == "cache:hit"))),
        "fused_regions": len(fusion_events),
        "fusion_members": [int(e.get("args", {}).get("members", 0))
                           for e in fusion_events],
        "fusion_syncs": [int(e.get("args", {}).get("syncs", 0))
                         for e in fusion_events],
        "fusion_compiles": sum(int(e.get("args", {}).get("compiles", 0))
                               for e in fusion_events),
        "faults_injected": int(qargs.get("faults_injected",
                                         _fname("fault:injected"))),
        "transient_retries": int(qargs.get("transient_retries",
                                           _fname("retry:attempt"))),
        "fragments_recomputed": int(qargs.get("fragments_recomputed", 0)),
        "degraded_batches": int(qargs.get("degraded_batches",
                                          _fname("degraded:cpu"))),
        "retry_backoff_s": float(qargs.get("retry_backoff_s", 0.0)),
        # distributed failure survival (peer:lost /
        # fragment:remote_repull / query:resubmitted marks; QueryStats
        # snapshot on the root event authoritative when present)
        "peers_lost": int(qargs.get("peers_lost", _fname("peer:lost"))),
        "fragments_recomputed_remote": int(qargs.get(
            "fragments_recomputed_remote",
            _fname("fragment:remote_repull"))),
        "partitions_reowned": int(qargs.get("partitions_reowned", sum(
            e.get("args", {}).get("adopted", 0) for e in fault_events
            if e.get("name") == "peer:lost"))),
        "queries_resubmitted": int(qargs.get(
            "queries_resubmitted", _fname("query:resubmitted"))),
        # gray-failure survival (integrity:fault / fragment:hedged /
        # peer:slow / watchdog:stall marks; QueryStats snapshot on the
        # root event authoritative when present)
        "integrity_failures": int(qargs.get("integrity_failures",
                                            _fname("integrity:fault"))),
        "fragments_hedged": int(qargs.get("fragments_hedged",
                                          _fname("fragment:hedged"))),
        "peers_slow": _fname("peer:slow"),
        "stalls_detected": int(qargs.get("stalls_detected",
                                         _fname("watchdog:stall"))),
        "watchdog_reclaims": _fname("watchdog:reclaim"),
        # network front door (cat "server": server:stream_write spans
        # from the connection thread, server:spool_start /
        # server:prepared_hit marks; QueryStats snapshot on the root
        # event authoritative when present)
        "server_stream_bytes": int(qargs.get(
            "server_stream_bytes",
            sum(e.get("args", {}).get("bytes", 0) for e in server_events
                if e.get("name") == "server:stream_write"))),
        "server_spooled_bytes": int(qargs.get("server_spooled_bytes", 0)),
        "server_writes": sum(1 for e in server_events
                             if e.get("name") == "server:stream_write"),
        "server_write_s": sum(
            e.get("dur", 0.0) for e in server_events
            if e.get("name") == "server:stream_write") / 1e6,
        "server_connection": qargs.get("connection", ""),
        "server_prepared": bool(qargs.get("prepared", False)),
        "prepared_hits": int(qargs.get("prepared_hits",
                                       _fname_cat(server_events,
                                                  "server:prepared_hit"))),
        "prepared_misses": int(qargs.get("prepared_misses", 0)),
        # overload survival (cat "scheduler": admission:shed /
        # admission:aimd marks land in whatever trace was active at the
        # shed/adjustment; spill_events from the QueryStats snapshot is
        # the per-query spill-degrade signal the AIMD controller eats)
        "spill_events": int(qargs.get("spill_events", 0)),
        "admission_sheds": _fname_cat(sched_events, "admission:shed"),
        "aimd_changes": _fname_cat(sched_events, "admission:aimd"),
    }


def format_report(a: dict) -> str:
    status = f"  status={a['status']}" if a.get("status", "ok") != "ok" \
        else ""
    # a truncated trace is VISIBLY truncated: the one-time
    # trace:events_dropped mark rides the timeline, and the header
    # says so in capitals
    trunc = "  TRUNCATED" if a.get("dropped", 0) else ""
    lines = [
        f"query {a['label']}: wall={a['wall_s'] * 1e3:.1f}ms  "
        f"events={a['n_events']} (dropped={a['dropped']}){trunc}{status}",
        "",
        "hot operators (self time):",
        f"  {'self_ms':>9} {'total_ms':>9} {'rows':>10} "
        f"{'batches':>8}  operator",
    ]
    for ent in a["operators"]:
        m = ent["metrics"]
        lines.append(
            f"  {ent['self_us'] / 1e3:>9.1f} {ent['total_us'] / 1e3:>9.1f} "
            f"{int(m.get('outputRows', 0)):>10} "
            f"{int(m.get('outputBatches', 0)):>8}  {ent['desc'] or ent['name']}")
    lines += [
        "",
        f"blocking fetches: {a['blocking_fetches']}  "
        f"async: {a['async_fetches']}  "
        f"fetch wait: {a['fetch_wait_s'] * 1e3:.1f}ms",
        f"compiles: {a['compiles']}  "
        f"compile time: {a['compile_s'] * 1e3:.1f}ms",
        f"overlap: busy={a['busy_s'] * 1e3:.1f}ms over {a['threads']} "
        f"thread(s), wall={a['wall_s'] * 1e3:.1f}ms, "
        f"ratio={a['overlap_ratio']:.2f}",
        f"self-time coverage: {a['self_total_s'] * 1e3:.1f}ms = "
        f"{a['self_coverage'] * 100:.0f}% of wall",
    ]
    # cache summary only when the query touched the cross-query cache
    looked = a.get("cache_hits", 0) + a.get("cache_misses", 0)
    if looked or a.get("cache_evictions", 0):
        ratio = (a["cache_hits"] / looked) if looked else 0.0
        lines.append(
            f"cache: hits={a['cache_hits']} misses={a['cache_misses']} "
            f"evictions={a['cache_evictions']} hit_ratio={ratio:.2f} "
            f"saved={a['cache_bytes_saved'] / 1e6:.1f}MB")
    # fusion summary only when the region planner formed fused regions
    if a.get("fused_regions"):
        members = ",".join(str(m) for m in a.get("fusion_members", []))
        syncs = ",".join(str(s) for s in a.get("fusion_syncs", []))
        lines.append(
            f"fusion: regions={a['fused_regions']} "
            f"members/region=[{members}] syncs/region=[{syncs}] "
            f"fused_compiles={a['fusion_compiles']}")
    # fault summary only when the query saw the fault framework act
    touched = (a.get("faults_injected", 0) + a.get("transient_retries", 0)
               + a.get("fragments_recomputed", 0)
               + a.get("degraded_batches", 0))
    if touched:
        lines.append(
            f"faults: injected={a['faults_injected']} "
            f"retries={a['transient_retries']} "
            f"recomputed={a['fragments_recomputed']} "
            f"degraded={a['degraded_batches']} "
            f"backoff={a['retry_backoff_s'] * 1e3:.1f}ms")
    # peer-fault summary only when the query survived distributed
    # failures (a killed peer, remote fragment recovery, resubmission)
    peer = (a.get("peers_lost", 0)
            + a.get("fragments_recomputed_remote", 0)
            + a.get("partitions_reowned", 0)
            + a.get("queries_resubmitted", 0))
    if peer:
        lines.append(
            f"peers: lost={a['peers_lost']} "
            f"remote_recomputed={a['fragments_recomputed_remote']} "
            f"reowned={a['partitions_reowned']} "
            f"resubmissions={a['queries_resubmitted']}")
    # gray-failure summary only when corruption was caught or a
    # straggler was hedged
    gray = (a.get("integrity_failures", 0) + a.get("fragments_hedged", 0)
            + a.get("peers_slow", 0))
    if gray:
        lines.append(
            f"integrity: failures={a['integrity_failures']} "
            f"hedged={a['fragments_hedged']} "
            f"slow_peers={a['peers_slow']}")
    # stall summary only when the watchdog acted on this query
    if a.get("stalls_detected", 0) or a.get("watchdog_reclaims", 0):
        lines.append(
            f"stalls: detected={a['stalls_detected']} "
            f"reclaims={a['watchdog_reclaims']} (watchdog)")
    # admission summary only when the overload machinery acted (spill
    # demotions charged to this query, typed sheds, AIMD adjustments)
    adm = (a.get("spill_events", 0) + a.get("admission_sheds", 0)
           + a.get("aimd_changes", 0))
    if adm:
        lines.append(
            f"admission: spill_events={a['spill_events']} "
            f"sheds={a['admission_sheds']} "
            f"aimd_changes={a['aimd_changes']}")
    # server summary only when the query arrived over the wire (stream
    # writes / spool / prepared-cache traffic)
    srv = (a.get("server_stream_bytes", 0) + a.get("server_writes", 0)
           + a.get("prepared_hits", 0) + a.get("prepared_misses", 0))
    if srv or a.get("server_prepared"):
        looked = a.get("prepared_hits", 0) + a.get("prepared_misses", 0)
        rate_part = (f" prepared_hit_rate="
                     f"{a['prepared_hits'] / looked:.2f}") if looked else ""
        conn = a.get("server_connection", "")
        lines.append(
            f"server: streamed={a['server_stream_bytes'] / 1e6:.1f}MB "
            f"in {a['server_writes']} writes "
            f"({a['server_write_s'] * 1e3:.1f}ms on the wire) "
            f"spooled={a['server_spooled_bytes'] / 1e6:.1f}MB "
            f"prepared={'yes' if a.get('server_prepared') else 'no'}"
            + rate_part
            + (f" connection={conn}" if conn else ""))
    return "\n".join(lines)


def format_contention(c: dict) -> str:
    stat = " ".join(f"{k}={v}" for k, v in sorted(c["statuses"].items()))
    return "\n".join([
        f"contention summary ({c['queries']} concurrent queries):",
        f"  batch span: {c['span_s'] * 1e3:.1f}ms  "
        f"sum of walls: {c['sum_walls_s'] * 1e3:.1f}ms  "
        f"(concurrency ratio {c['concurrency_ratio']:.2f})",
        f"  >=2 queries in flight for {c['overlap_s'] * 1e3:.1f}ms  "
        f"peak concurrency: {c['peak_concurrency']}",
        f"  aggregate throughput: {c['throughput_qps']:.2f} queries/s",
        f"  statuses: {stat}",
    ])


# ---------------------------------------------------------------------------------
# Cross-rank trace stitching
# ---------------------------------------------------------------------------------

def discover_shards(trace_path: str, data: dict) -> Dict[int, List[dict]]:
    """Find and load every per-rank shard written for this trace's id
    in the trace file's directory: {rank: [shard events]}."""
    import re
    tid = data.get("otherData", {}).get("trace_id", "")
    if not tid:
        return {}
    directory = os.path.dirname(os.path.abspath(trace_path))
    out: Dict[int, List[dict]] = {}
    import glob
    for path in sorted(glob.glob(os.path.join(
            directory, f"{tid}.rank*.shard.jsonl"))):
        m = re.search(r"\.rank(\d+)\.shard\.jsonl$", path)
        if not m:
            continue
        rank = int(m.group(1))
        events = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue  # a torn tail write is not fatal
        if events:
            out[rank] = events
    return out


def stitch(data: dict, shards: Dict[int, List[dict]]) -> dict:
    """Merge per-rank shards into ONE Perfetto tree.

    The query trace stays pid 1; each remote rank becomes its own pid
    (``100 + rank``) with its serve-side spans placed on the shared
    wall-clock timeline; the ``spanTree`` gains one ``rank-<k>`` node
    PER RANK, parented under the query root, whose children are that
    rank's remote spans — every fetch/re-pull is attributable to its
    owning rank."""
    other = dict(data.get("otherData", {}))
    epoch = float(other.get("wall_start_epoch_s", 0.0))
    evs = [dict(e) for e in data.get("traceEvents", [])]
    roots = [dict(r) for r in data.get("spanTree", [])]
    root_node = {
        "op_id": "query-root",
        "name": other.get("label", "query"),
        "desc": f"query root ({other.get('label', '?')})",
        "children": roots,
        "metrics": {},
    }
    rank_counts: Dict[int, int] = {}
    for rank in sorted(shards):
        pid = 100 + rank
        evs.append({"ph": "M", "pid": pid, "tid": 0,
                    "name": "process_name",
                    "args": {"name": f"rank {rank} (remote)"}})
        rank_node = {"op_id": f"rank-{rank}",
                     "name": f"rank-{rank}",
                     "desc": f"remote spans served by rank {rank}",
                     "children": [], "metrics": {}}
        for i, ev in enumerate(shards[rank]):
            ts = max(0.0, (float(ev.get("t_wall", epoch)) - epoch)) * 1e6
            dur = float(ev.get("dur_s", 0.0)) * 1e6
            args = dict(ev.get("args") or {})
            args["rank"] = rank
            evs.append({"ph": "X", "pid": pid, "tid": 1,
                        "name": ev.get("name", "remote"),
                        "cat": ev.get("cat", "shuffle"),
                        "ts": round(ts, 1), "dur": round(dur, 1),
                        "args": args})
            child = {"op_id": f"rank-{rank}/{i}",
                     "name": ev.get("name", "remote"),
                     "desc": " ".join(f"{k}={v}" for k, v
                                      in sorted(args.items())),
                     "children": [],
                     "metrics": {"durS": round(float(
                         ev.get("dur_s", 0.0)), 6)}}
            rank_node["children"].append(child)
        rank_node["metrics"]["spans"] = len(rank_node["children"])
        rank_counts[rank] = len(rank_node["children"])
        root_node["children"].append(rank_node)
    other["stitched_ranks"] = sorted(rank_counts)
    other["stitched_spans"] = rank_counts and {
        str(r): n for r, n in sorted(rank_counts.items())} or {}
    return {"traceEvents": evs, "displayTimeUnit": "ms",
            "otherData": other, "spanTree": [root_node]}


def stitch_file(path: str, out: str = "") -> str:
    """Stitch one trace file with its shards; writes (and returns the
    path of) ``<trace>.stitched.json``."""
    data = load(path)
    shards = discover_shards(path, data)
    merged = stitch(data, shards)
    out = out or (path[:-5] if path.endswith(".json") else path) \
        + ".stitched.json"
    with open(out, "w") as f:
        json.dump(merged, f)
    return out


def format_stitched(merged: dict) -> str:
    other = merged.get("otherData", {})
    spans = other.get("stitched_spans") or {}
    lines = [f"stitched trace {other.get('label', '?')} "
             f"(trace_id={other.get('trace_id', '?')}): "
             f"{len(spans)} remote rank shard(s)"]
    for rank, n in sorted(spans.items(), key=lambda kv: int(kv[0])):
        lines.append(f"  rank {rank}: {n} remote span(s) parented "
                     f"under the query root")
    if not spans:
        lines.append("  (no shards found beside the trace — was "
                     "sql.trace.dir set on the serving ranks?)")
    return "\n".join(lines)


def report_file(data: dict) -> str:
    """Render one trace file: a single-query report, or per-query
    sections + a contention summary for a merged multi-query trace."""
    subs, span_trees = split_queries(data)
    parts = [format_report(analyze(s)) for s in subs]
    if span_trees:
        parts.append(format_contention(contention(span_trees)))
    return ("\n" + "- " * 36 + "\n").join(parts)


def why_file(data: dict) -> str:
    """Root-cause attribution section (``--why``): each query in the
    trace decomposed into canonical wait terms vs its fingerprint's
    EWMA baseline, dominant anomalous term named — shared verbatim
    with tools/explain_slow.py."""
    try:
        from tools import explain_slow
    except ImportError:  # run as a script from tools/
        import explain_slow
    subs, _ = split_queries(data)
    return "\n\n".join(
        explain_slow.format_why(explain_slow.analyze_doc(sub))
        for sub in subs)


# ---------------------------------------------------------------------------------
# --xplane: a jax.profiler trace directory (device operations AND the
# engine's spans, on the profiler's clock)
# ---------------------------------------------------------------------------------

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_SPAN_NAME = re.compile(r"[A-Za-z_][\w.#-]*:[\w.#-]+")
# where a device operation's trace event says which jax op it came from
# (``jit(agg_grouped)/jit(main)/segmented_reduce/scatter-add``): a stat
# of the event on some backends, else the ``op_name`` of the HLO line
# the TPU's trace names the event by
_SCOPE_STATS = ("tf_op", "long_name")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.upper()


def xplane_events(trace_dir: str):
    """``(plane name, line name, event)`` of the newest ``.xplane.pb``
    under ``trace_dir``, with nothing but JAX."""
    import glob

    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    for plane in ProfileData.from_file(found[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                yield plane.name, line.name, ev


def _scope_path(ev) -> str:
    try:
        stats = dict(ev.stats)
    except Exception:  # an event without readable stats has no scope
        return ""
    for key in _SCOPE_STATS:
        v = stats.get(key)
        if isinstance(v, str) and v:
            return v
    found = _OP_NAME.search(ev.name)
    return found.group(1) if found else ""


def xplane_rows(trace_dir: str) -> List[tuple]:
    """The trace as plain rows ``(plane, line, name, start_ns, dur_ns,
    scope path)``: what :func:`reduce_xplane` works on, so that a
    hand-made list tests it."""
    rows = []
    for plane, line, ev in xplane_events(trace_dir):
        device = _is_device_plane(plane)
        if not device and not _SPAN_NAME.fullmatch(ev.name):
            continue
        rows.append((plane, line, ev.name, int(ev.start_ns),
                     int(ev.duration_ns),
                     _scope_path(ev) if device and line == OPS_LINE
                     else ""))
    return rows


def scope_of(path: str) -> str:
    """The innermost named scope of a jax op path: its components less
    the ``jit(...)`` wrappers and the primitive's own name."""
    parts = [c for c in path.split("/")[:-1]
             if c and not c.startswith(("jit(", "pjit("))]
    return parts[-1] if parts else "(none)"


def _merged(intervals) -> List[List[int]]:
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def reduce_xplane(rows, top: int = 10) -> dict:
    """Device seconds by program and by named scope, and the ``top``
    longest device-idle gaps by the program span that covers them.

    - a program's seconds are its events' on the ``XLA Modules`` line of
      a device plane, under the module's name less its ``(<id>)``;
    - a scope's seconds are those of the ``XLA Ops`` events whose op
      path names it innermost, each event's OWN time (a ``while`` less
      the operations nested in it);
    - busy is the union of the ``XLA Ops`` intervals of one device, an
      idle gap the space between two of them; a gap's label is the
      shortest host span of the ``<layer>:<name>`` form that covers its
      middle, else ``(no span)``."""
    by_program: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    ops: Dict[str, list] = {}
    spans = []
    for plane, line, name, start, dur, path in rows:
        if not _is_device_plane(plane):
            if _SPAN_NAME.fullmatch(name):
                spans.append((start, start + dur, name))
            continue
        if line == MODULES_LINE:
            prog = re.sub(r"\(\d+\)$", "", name)
            by_program[prog] = by_program.get(prog, 0.0) + dur / 1e9
        elif line == OPS_LINE:
            ops.setdefault(plane, []).append((start, start + dur, path))
    busy_ns = span_ns = 0
    gaps = []
    for plane, evs in sorted(ops.items()):
        # own time: an enclosing op (a while) less what nests in it
        open_: List[list] = []  # [end, path, own ns]
        for lo, hi, path in sorted(evs, key=lambda e: (e[0], -e[1])):
            while open_ and open_[-1][0] <= lo:
                end, p, own = open_.pop()
                by_scope[scope_of(p)] = by_scope.get(scope_of(p), 0.0) \
                    + own / 1e9
            if open_:
                open_[-1][2] -= hi - lo
            open_.append([hi, path, hi - lo])
        for end, p, own in open_:
            by_scope[scope_of(p)] = by_scope.get(scope_of(p), 0.0) \
                + own / 1e9
        merged = _merged((lo, hi) for lo, hi, _ in evs)
        busy_ns += sum(hi - lo for lo, hi in merged)
        span_ns += merged[-1][1] - merged[0][0]
        for (_, a), (b, _) in zip(merged, merged[1:]):
            gaps.append((b - a, a, b, plane))
    gaps.sort(reverse=True)
    idle = []
    for length, a, b, plane in gaps[:top]:
        mid = (a + b) // 2
        cover = [(hi - lo, name) for lo, hi, name in spans
                 if lo <= mid < hi]
        idle.append({"seconds": length / 1e9, "plane": plane,
                     "span": min(cover)[1] if cover else "(no span)"})
    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns / 1e9, "span_s": span_ns / 1e9,
            "idle_s": (span_ns - busy_ns) / 1e9,
            "device_s_by_program": order(by_program),
            "device_s_by_scope": order(by_scope),
            "idle_gaps": idle,
            "program_spans": len(spans)}


def format_xplane(r: dict) -> str:
    lines = [f"device busy {r['busy_s']:.3f} s of {r['span_s']:.3f} s "
             f"(idle {r['idle_s']:.3f} s); {r['program_spans']} program "
             f"span(s) on the host planes",
             "device seconds by program (XLA Modules):"]
    lines += [f"  {v:9.4f}  {k}" for k, v in r["device_s_by_program"]] \
        or ["  (no XLA Modules line: not a device's trace)"]
    lines.append("device seconds by named scope (XLA Ops, own time):")
    lines += [f"  {v:9.4f}  {k}" for k, v in r["device_s_by_scope"]] \
        or ["  (no XLA Ops line)"]
    lines.append("longest device-idle gaps, by the program span over "
                 "their middle:")
    lines += [f"  {g['seconds']:9.4f}  {g['span']}"
              for g in r["idle_gaps"]] or ["  (none)"]
    return "\n".join(lines)


def main(argv: List[str]) -> int:
    if "--xplane" in argv:
        i = argv.index("--xplane")
        top = int(argv[argv.index("--top") + 1]) if "--top" in argv \
            else 10
        if i + 1 >= len(argv):
            print(__doc__, file=sys.stderr)
            return 2
        print(format_xplane(reduce_xplane(xplane_rows(argv[i + 1]),
                                          top=top)))
        return 0
    do_stitch = False
    do_why = False
    paths: List[str] = []
    for a in argv:
        if a == "--stitch":
            do_stitch = True
        elif a == "--why":
            do_why = True
        else:
            paths.append(a)
    if not paths:
        print(__doc__, file=sys.stderr)
        return 2
    for path in paths:
        if do_stitch:
            out = stitch_file(path)
            merged = load(out)
            print(format_stitched(merged))
            print(f"wrote {out}")
            print(report_file(merged))
        else:
            print(report_file(load(path)))
        if do_why:
            print()
            print("why (root-cause attribution):")
            print(why_file(load(path)))
        if len(paths) > 1:
            print("-" * 72)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
