"""Spans and counters of one mining query.

`mine()` opens a `QueryTrace` for each call (`query`) and returns it as
``MiningResult.trace``.  Inside it, the layers of the program mark their
boundaries with two calls:

* ``span(name, **meta)``: a context manager.  It opens a
  ``jax.profiler.TraceAnnotation("flexis.<name>", **meta)``, so a profiler
  trace shows the span on the host beside the device's operations, and it
  appends a `Span` to the active query's record.  Spans nest: each one's
  parent is the innermost span open when it started, so a query's spans
  form one tree rooted at ``flexis.mine``.
* ``count(name, n=1)``: adds ``n`` to one of the active query's counters.

Both stamp time with `clock_ns`, the clock the profiler stamps host events
with (the realtime clock in ns), so a span in the record and its twin in a
trace differ only by the trace's own origin (its ``profile_start_time``).
The record is always on; off the profiler a span costs about 2 µs.
Outside a query (a level executor called on its own) a span only
annotates and a counter is dropped.

`watch_compiles` registers one listener per process on JAX's monitoring
events: every backend compile counts as ``compiles`` and ``compile_s`` in
the query that triggered it, and into process totals (`compile_totals`),
with the persistent-cache hits beside them.
"""
from __future__ import annotations

import contextvars
import dataclasses
import time
from typing import Dict, List, Optional, Union

import jax

__all__ = ["Span", "QueryTrace", "CompileTotals", "query", "span", "count",
           "clock_ns", "watch_compiles", "compile_totals", "PREFIX"]

PREFIX = "flexis."
clock_ns = time.time_ns

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

Number = Union[int, float]


@dataclasses.dataclass(slots=True)
class Span:
    """One timed stretch of a query: ``[start_ns, end_ns)`` on `clock_ns`;
    ``parent`` is the index of the enclosing span in the record (-1 for
    the root)."""

    name: str
    start_ns: int
    end_ns: int
    parent: int
    meta: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@dataclasses.dataclass
class QueryTrace:
    """What one ``mine()`` call recorded: its spans, parents before
    children, and its counters."""

    spans: List[Span] = dataclasses.field(default_factory=list)
    counters: Dict[str, Number] = dataclasses.field(default_factory=dict)
    _open: List[int] = dataclasses.field(default_factory=list, repr=False,
                                         compare=False)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def self_seconds(self) -> Dict[str, float]:
        """Seconds by span name, each span's duration less what its child
        spans cover."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.seconds
            if s.parent >= 0:
                parent = self.spans[s.parent].name
                out[parent] = out.get(parent, 0.0) - s.seconds
        return out


_active: contextvars.ContextVar[Optional[QueryTrace]] = \
    contextvars.ContextVar("flexis_query_trace", default=None)


class query:
    """``with query() as trace:`` makes ``trace`` the record that spans
    and counters go to until the block ends."""

    def __enter__(self) -> QueryTrace:
        watch_compiles()
        self._trace = QueryTrace()
        self._token = _active.set(self._trace)
        return self._trace

    def __exit__(self, *exc) -> None:
        _active.reset(self._token)


class span:
    """``with span("block", block=b) as s:`` times the block as
    ``flexis.block``; ``s.seconds`` holds its duration once it ends."""

    __slots__ = ("_span", "_ann", "_trace")

    def __init__(self, name: str, **meta):
        self._span = Span(name, 0, 0, -1, meta)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **meta)

    def __enter__(self) -> Span:
        self._ann.__enter__()
        s = self._span
        trace = self._trace = _active.get()
        if trace is not None:
            s.parent = trace._open[-1] if trace._open else -1
            trace._open.append(len(trace.spans))
            trace.spans.append(s)
        s.start_ns = clock_ns()
        return s

    def __exit__(self, *exc) -> None:
        self._span.end_ns = clock_ns()
        if self._trace is not None:
            self._trace._open.pop()
        self._ann.__exit__(*exc)


def count(name: str, n: Number = 1) -> None:
    trace = _active.get()
    if trace is not None:
        trace.counters[name] = trace.counters.get(name, 0) + n


# -- compiles ---------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompileTotals:
    """Process totals: backend compiles, their seconds, and programs loaded
    from the persistent cache (a cache hit still reports a short backend
    compile)."""

    compiles: int = 0
    seconds: float = 0.0
    cache_hits: int = 0

    def __sub__(self, other: "CompileTotals") -> "CompileTotals":
        return CompileTotals(self.compiles - other.compiles,
                             self.seconds - other.seconds,
                             self.cache_hits - other.cache_hits)

    def __str__(self) -> str:
        return (f"backend compiles {self.compiles} ({self.seconds:.1f} s, "
                f"{self.cache_hits} from the persistent cache)")


_totals = CompileTotals()
_watching = False


def _on_duration(event: str, duration: float, **_) -> None:
    global _totals
    if event == _BACKEND_COMPILE:
        _totals = dataclasses.replace(_totals, compiles=_totals.compiles + 1,
                                      seconds=_totals.seconds + duration)
        count("compiles")
        count("compile_s", duration)


def _on_event(event: str, **_) -> None:
    global _totals
    if event == _CACHE_HIT:
        _totals = dataclasses.replace(_totals,
                                      cache_hits=_totals.cache_hits + 1)


def watch_compiles() -> None:
    """Register the compile listeners (once per process; later calls do
    nothing).  Compiles before the first call go uncounted."""
    global _watching
    if not _watching:
        _watching = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)


def compile_totals() -> CompileTotals:
    return _totals

