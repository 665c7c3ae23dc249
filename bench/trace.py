"""Reduction of a profiler trace to the device's busy time and idle gaps.

`load` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and keeps
only what the readers use, as plain lists that a test can write down by
hand (``tests/bench/data``):

* per device, the operations of its "XLA Ops" line and the programs of its
  "XLA Modules" line, each as ``[name, start_ns, duration_ns]``;
* the benchmark's own host spans (annotations named ``bench.*``), which
  include ``bench.window``, the measured window.

Busy time is the union of a device's operation intervals inside the
window; the idle share is 1 minus busy over the window.  Each stretch of
an idle gap is put down to the innermost benchmark span over it: what the
host was doing while the device waited.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]     # name, start_ns, duration_ns

WINDOW = "bench.window"


def load(trace_dir: str) -> dict:
    """The trace under ``trace_dir`` as {"devices": {id: {"ops": [...],
    "modules": [...]}}, "spans": [...]}."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, dict] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            dev = devices.setdefault(plane.name[12:], {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is not None:
                    # an op's event name is its whole HLO line: keep the name
                    dev[key].extend([e.name.split(" = ", 1)[0], e.start_ns,
                                     e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events if e.name.startswith("bench."))
    return {"devices": devices, "spans": spans}


def window(trace: dict) -> Optional[Tuple[float, float]]:
    """(start_ns, end_ns) of the measured window, or None."""
    ws = [(s, s + d) for name, s, d in trace["spans"] if name == WINDOW]
    return max(ws, key=lambda w: w[1] - w[0]) if ws else None


def union(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Sorted disjoint union of ``intervals``, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: dict) -> Optional[float]:
    """Device busy time inside the window, averaged over the devices that
    ran an operation there."""
    w = window(trace)
    if w is None:
        return None
    per_dev = []
    for dev in trace["devices"].values():
        u = union([(s, s + d) for _, s, d in dev["ops"]], *w)
        if u:
            per_dev.append(sum(e - s for s, e in u))
    return sum(per_dev) / len(per_dev) if per_dev else None


def idle_share(trace: dict) -> Optional[float]:
    w = window(trace)
    busy = busy_ns(trace)
    if w is None or busy is None or w[1] <= w[0]:
        return None
    return 1.0 - busy / (w[1] - w[0])


def module_time(trace: dict, prefix: str) -> Tuple[float, int]:
    """(device ns, count) of the window's programs whose name starts with
    ``prefix``, summed over devices."""
    w = window(trace)
    if w is None:
        return 0.0, 0
    total, count = 0.0, 0
    for dev in trace["devices"].values():
        for name, s, d in dev["modules"]:
            if name.startswith(prefix) and s >= w[0] and s + d <= w[1]:
                total += d
                count += 1
    return total, count


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Device ns by operation name, net of the operations nested inside it
    (a loop's body runs as operations of its own on the same line)."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []     # [name, end, child_ns]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out[top[0]] -= top[2]
        if stack:
            stack[-1][2] += d
        out[name] += d
        stack.append([name, s + d, 0.0])
    while stack:
        top = stack.pop()
        out[top[0]] -= top[2]
    return dict(out)


def top_ops(trace: dict, n: int = 10) -> List[List]:
    """The ``n`` operations that held the devices longest in the window,
    as [name, seconds averaged over devices]."""
    w = window(trace)
    if w is None or not trace["devices"]:
        return []
    total: Dict[str, float] = collections.defaultdict(float)
    for dev in trace["devices"].values():
        inside = [e for e in dev["ops"] if e[1] >= w[0] and e[1] + e[2] <= w[1]]
        for name, t in self_times(inside).items():
            total[name] += t
    n_dev = len(trace["devices"])
    return [[name, t / n_dev / 1e9] for name, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> List[List]:
    """Idle seconds of the window by what the host was doing: each stretch
    of a gap is put down to the innermost benchmark span over it.  Longest
    first."""
    w = window(trace)
    if w is None or not trace["devices"]:
        return []
    spans = [(name, s, s + d) for name, s, d in trace["spans"]]
    # the window cut at every span edge, each piece named by its innermost span
    cuts = sorted({w[0], w[1]} | {t for _, s, e in spans for t in (s, e)
                                  if w[0] < t < w[1]})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        inner = [sp for sp in spans if sp[1] <= mid < sp[2]]
        pieces.append((a, b, min(inner, key=lambda sp: sp[2] - sp[1])[0]
                       if inner else "outside any span"))
    total: Dict[str, float] = collections.defaultdict(float)
    for dev in trace["devices"].values():
        gaps, edge = [], w[0]
        for s, e in union([(s, s + d) for _, s, d in dev["ops"]], *w) + [(w[1], w[1])]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        i = 0
        for a, b in gaps:
            while i < len(pieces) and pieces[i][1] <= a:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < b:
                total[pieces[j][2]] += min(b, pieces[j][1]) - max(a, pieces[j][0])
                j += 1
    n_dev = len(trace["devices"])
    return [[name, t / n_dev / 1e9] for name, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
