"""The program's own spans and counters, placed on the device trace.

Each ``mine()`` call returns its record as ``MiningResult.trace``: spans
``(name, start_ns, end_ns, parent)`` on the host's realtime clock, with
``mine`` at the root, and a dict of counters.  A loaded trace
(`bench.trace.load`) counts time from the profiler session's start
instead.  A query's ``mine`` span and the benchmark's ``bench.query`` span
around the same call mark the same stretch, so the k-th of each fix the
offset between the two clocks: `aligned` takes it as the mean over both
ends of every query, and gives None when any end then misses its twin by
more than 1 ms (another clock, or queries paired wrongly).  Every reader
gives None where the program keeps no record.

Idle time is that of `bench.trace`: the window less the union of a
device's operations, averaged over the devices.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from bench import trace as trace_lib

TOLERANCE_NS = 1e6
QUERY = "bench.query"
ROOT = "mine"

Interval = Tuple[float, float]
# name, start_ns, end_ns, parent index
Placed = Tuple[str, float, float, int]


def records(run) -> Optional[list]:
    """The window's query records, or None if any query lacks one."""
    recs = [getattr(q.result, "trace", None) for q in run.queries]
    if not recs or any(r is None or not r.spans for r in recs):
        return None
    return recs


def counter(run, name: str) -> Optional[List[float]]:
    """``name``'s count in each query of the window (0 where absent)."""
    recs = records(run)
    return None if recs is None else [r.counters.get(name, 0) for r in recs]


def aligned(run) -> Optional[List[List[Placed]]]:
    """Each query's spans on the trace's clock, or None (see module)."""
    recs = records(run)
    if recs is None or run.trace is None:
        return None
    queries = sorted((s, s + d) for name, s, d in run.trace["spans"]
                     if name == QUERY)
    roots = [r.spans[0] for r in recs]
    if len(queries) != len(recs) or any(r.name != ROOT for r in roots):
        return None
    # in whole ns: the realtime clock's readings are too large for floats
    diffs = ([round(q[0]) - r.start_ns for q, r in zip(queries, roots)]
             + [round(q[1]) - r.end_ns for q, r in zip(queries, roots)])
    off = sum(diffs) // len(diffs)
    if any(abs(d - off) > TOLERANCE_NS for d in diffs):
        return None
    return [[(s.name, s.start_ns + off, s.end_ns + off, s.parent)
             for s in r.spans] for r in recs]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def complement(intervals: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The stretches of [lo, hi] that none of ``intervals`` covers."""
    out, edge = [], lo
    for s, e in trace_lib.union(intervals, lo, hi) + [(hi, hi)]:
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    return out


def idle_gaps(tr: dict) -> Optional[List[List[Interval]]]:
    """Per device, the stretches of the window in which it ran nothing."""
    w = trace_lib.window(tr)
    if w is None or not tr["devices"]:
        return None
    return [complement([(s, s + d) for _, s, d in dev["ops"]], *w)
            for dev in tr["devices"].values()]


def idle_ns(tr: dict, where: Sequence[Interval]) -> Optional[float]:
    """Idle ns of the window inside the intervals ``where``, averaged over
    the devices."""
    gaps = idle_gaps(tr)
    if gaps is None:
        return None
    inside = trace_lib.union(where, *trace_lib.window(tr))
    return sum(overlap(g, inside) for g in gaps) / len(gaps)


def block_idle_ms(run) -> Optional[float]:
    """Device-idle ms inside the program's ``block`` spans, per block."""
    spans = aligned(run)
    if spans is None:
        return None
    blocks = [(s, e) for q in spans for name, s, e, _ in q if name == "block"]
    idle = idle_ns(run.trace, blocks) if blocks else None
    return None if idle is None else idle / len(blocks) / 1e6


def idle_unspanned(run) -> Optional[float]:
    """Share of the window's idle time that falls inside a query's ``mine``
    span but under none of its other spans, %."""
    spans = aligned(run)
    if spans is None:
        return None
    bare = [piece for q in spans
            for piece in complement([(s, e) for _, s, e, _ in q[1:]],
                                    q[0][1], q[0][2])]
    total = idle_ns(run.trace, [trace_lib.window(run.trace)])
    part = idle_ns(run.trace, bare)
    if not total or part is None:
        return None
    return 100.0 * part / total
