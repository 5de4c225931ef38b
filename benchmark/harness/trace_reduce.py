"""From a profiler trace to the device's busy time, its idle gaps and the
operations that took it.

The reduction works on plain events, ``(plane, line, name, start_ns,
duration_ns)``, so that a hand-made list tests it; ``events_of`` reads them
out of the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX.

What counts as what:

- a device plane is one whose name starts with ``/device:`` and that is not
  a host CPU's; of its lines the one named ``XLA Ops`` holds the device's
  operations.  A device plane without that line has nothing to read;
- the traced span runs from the start of the first to the end of the last
  host event the harness itself annotated (``bench:<query>:run``): what is
  traced before and after, starting and stopping the profiler, is not the
  workload.  A trace with no such event has no span, and nothing to read;
- busy is the union of the device operations' intervals inside the span,
  averaged over the devices; idle is the rest of the span; an operation's
  time in ``device_ops`` is its own, less the operations nested in it;
- an idle gap is labelled by the annotation that covers its middle
  (``<query>:run``), else ``between_queries``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, str, str, int, int]
SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.upper()


def events_of(trace_dir: str, others: Optional[Dict[str, int]] = None
              ) -> List[Event]:
    """Every device event and every harness annotation of the newest trace
    under ``trace_dir``.  ``others``, where given, is filled with the names
    of the host events left out and how often each came: what else the
    program wrote into the trace."""
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Event] = []
    for plane in ProfileData.from_file(found[-1]).planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if device or name.startswith(SPAN_PREFIX):
                    out.append((plane.name, line.name, name,
                                int(ev.start_ns), int(ev.duration_ns)))
                elif others is not None:
                    others[name] = others.get(name, 0) + 1
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short(name: str, limit: int = 160) -> str:
    """A device operation's name as the trace gives it is its whole HLO
    line; keep its head: result, shapes and the first operands, without
    layouts."""
    name = _LAYOUT.sub("", name).replace("%", "")
    return name if len(name) <= limit else name[:limit - 3] + "..."


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def self_times(ops: Iterable[Tuple[str, int, int]], t_lo: int, t_hi: int
               ) -> List[List]:
    """Each operation's own nanoseconds inside the span: its interval less
    the operations nested in it.  The ``XLA Ops`` line nests: a ``while``
    holds its body's fusions, and would otherwise count their time again."""
    out: List[List] = []        # [name, own ns]
    open_: List[Tuple[int, int]] = []  # (end, index in out) of enclosing ops
    for name, lo, hi in sorted(ops, key=lambda o: (o[1], -o[2])):
        lo, hi = max(lo, t_lo), min(hi, t_hi)
        if hi <= lo:
            continue
        while open_ and (open_[-1][0] <= lo or open_[-1][0] < hi):
            open_.pop()
        if open_:
            out[open_[-1][1]][1] -= hi - lo
        open_.append((hi, len(out)))
        out.append([name, hi - lo])
    return out


def reduce(events: Iterable[Event], top: int = 10,
           gaps: int = 5) -> Optional[Dict]:
    """None where the trace holds no ``XLA Ops`` line of a device or no
    span of the harness's: there is nothing to read, and the metrics that
    would read it stay out of the line."""
    spans = []                              # (lo, hi, label)
    by_plane = defaultdict(lambda: defaultdict(list))
    for plane, line, name, start, dur in events:
        if name.startswith(SPAN_PREFIX):
            label = name[len(SPAN_PREFIX):].split("#")[0]
            spans.append((start, start + dur, label))
        elif is_device_plane(plane):
            by_plane[plane][line].append((short(name), start, start + dur))
    devices = {plane: lines[OPS_LINE] for plane, lines in by_plane.items()
               if lines.get(OPS_LINE)}
    if not devices or not spans:
        return None
    t_lo = min(s[0] for s in spans)
    t_hi = max(s[1] for s in spans)
    window_ns = t_hi - t_lo
    if window_ns <= 0:
        return None

    busy_ns, totals = [], defaultdict(int)
    idle: List[Tuple[int, int]] = []
    for i, plane in enumerate(sorted(devices)):
        ops = devices[plane]
        inside = [(max(lo, t_lo), min(hi, t_hi)) for _, lo, hi in ops
                  if hi > t_lo and lo < t_hi]
        merged = _union(inside)
        busy_ns.append(sum(hi - lo for lo, hi in merged))
        for name, ns in self_times(ops, t_lo, t_hi):
            totals[name] += ns
        if i == 0:  # gaps of the first device: on one chip, the device
            edge = t_lo
            for lo, hi in merged:
                if lo > edge:
                    idle.append((edge, lo))
                edge = max(edge, hi)
            if t_hi > edge:
                idle.append((edge, t_hi))
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    if busy_s <= 0:
        return None

    def label(lo, hi):
        mid = (lo + hi) / 2
        return next((s[2] for s in spans if s[0] <= mid < s[1]),
                    "between_queries")
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:gaps]
    n_dev = len(devices)
    return {
        "busy_s": busy_s,
        "window_s": window_ns / 1e9,
        "device_idle_pct": 100.0 * (1.0 - busy_s / (window_ns / 1e9)),
        "device_ops": [[name, ns / n_dev / 1e9] for name, ns in sorted(
            totals.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(lo, hi), (hi - lo) / 1e9] for lo, hi in longest],
        "devices": n_dev,
        "annotated_spans": len(spans),
        "device_events": sum(len(ops) for ops in devices.values()),
    }


def describe(events: Iterable[Event]) -> Dict[str, Dict[str, int]]:
    """Planes and lines with their event counts: what a trace holds, for a
    look by hand."""
    out: Dict[str, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for plane, line, _, _, _ in events:
        out[plane][line] += 1
    return {p: dict(v) for p, v in out.items()}
