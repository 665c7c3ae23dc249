"""How a window drives ``mine()``, for every traffic mix.

A traffic file (``bench/traffic/<mix>.json``) holds the query and its
loop.  The driver reads ``OWN`` itself: the ``metric`` (whose rule file,
``bench/references/<metric>.py``, decides ``correct``), ``sigma``, ``lam``,
the frontier ``cap``, the ``loop`` and ``warm_up_s``.  The keys of
``PASSED`` choose how the program computes an answer, not what the answer
is, and reach ``MiningConfig`` as they stand where the file sets them.  Any
other key is refused, as is a metric with no rule file (`check` raises
`BadTraffic` before anything is set up): ``complete``, ``escalate``,
``root_order`` and ``generation`` would change the answer or the block
order the reference follows.  The loops:

* ``"cut"``: one query called at the window's start and cut by its own time
  limit at the window's end; should it finish first, the next one starts.
  Its end-to-end number is the (candidate, root vertex) pairs the queries
  settled, over the window's seconds.  The warm-up runs the same query for
  ``warm_up_s``.
* ``"closed"``: one client repeats the whole query, each a fresh
  ``mine()`` call; the window ends with the first query that completes
  after ``--seconds``.

The configuration file (``bench/configs/<config>.json``) gives the graph
and the largest pattern size.  The program gets the generated arrays
through its own ``build_graph`` and is driven only through ``mine()``.
"""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

from bench import reference
from bench.recorder import Recorder, Spans
from bench.rmat import Graph

LOOPS = ("cut", "closed")
OWN = ("loop", "metric", "sigma", "lam", "cap", "warm_up_s")
PASSED = ("execution", "batch_patterns", "blocks_per_super", "sample_fraction",
          "confidence", "sample_seed", "sample_rounds")

# Benchmark-side spans: (span, program module, attribute), found by name.
SPANS = (
    ("cand_build", "repro.core.flexis", "initial_candidates"),
    ("generation", "repro.core.flexis", "generate_new_patterns"),
    ("plan", "repro.core.flexis", "ExecutionPlanner.plan_level"),
)


@dataclasses.dataclass
class Query:
    latency_s: float
    result: object              # the program's MiningResult
    recorder: Recorder
    pairs: float                # (candidate, root vertex) pairs settled


class BadTraffic(ValueError):
    """A traffic file the harness cannot drive or check."""


def check(traffic: dict, rules: Optional[Path] = None) -> ModuleType:
    """The rule of the traffic's metric (`reference.rule`, from ``rules``
    or bench/references/); raises `BadTraffic` for a key the driver neither
    owns nor passes on, a loop it does not know, or a metric with no rule
    file."""
    unknown = sorted(set(traffic) - set(OWN) - set(PASSED))
    if unknown:
        raise BadTraffic(f"traffic key(s) {unknown} neither read by the driver "
                         f"{list(OWN)} nor passed to MiningConfig {list(PASSED)}")
    if traffic["loop"] not in LOOPS:
        raise BadTraffic(f"loop {traffic['loop']!r} is not one of {LOOPS}")
    try:
        return reference.rule(traffic["metric"], rules)
    except LookupError as e:
        raise BadTraffic(str(e)) from None


class Cell:
    """One configuration under one traffic mix, on the program; the
    metric's rule comes from ``rules`` (default bench/references/)."""

    def __init__(self, config: dict, traffic: dict, graph: Graph,
                 rules: Optional[Path] = None):
        import jax

        from repro.core import MatchConfig, MiningConfig, build_graph

        self.rule = check(traffic, rules)
        self.config, self.traffic, self.graph = config, traffic, graph
        self.g = build_graph(graph.n, graph.edges, graph.labels,
                             n_labels=graph.n_labels,
                             undirected=config["undirected"])
        self.mining = MiningConfig(
            sigma=traffic["sigma"], lam=traffic["lam"],
            metric=traffic["metric"],
            max_pattern_size=config["max_pattern_size"],
            match=MatchConfig.for_graph(self.g, cap=traffic["cap"]),
            **{k: traffic[k] for k in PASSED if k in traffic})
        self.spans = Spans()
        self._annotate = jax.profiler.TraceAnnotation

    # -- the loops ----------------------------------------------------------
    def _query(self, time_limit: Optional[float]) -> Query:
        from repro.core import mine

        rec = Recorder()
        cfg = dataclasses.replace(self.mining, time_limit_s=time_limit)
        t = time.perf_counter()
        with self._annotate("bench.query"):
            res = mine(self.g, cfg, hooks=rec)
        return Query(time.perf_counter() - t, res, rec, self._pairs(res, rec))

    def run(self, seconds: float) -> List[Query]:
        """Drive the loop for ``seconds``; returns every query it made."""
        queries: List[Query] = []
        with self.spans.wrap(SPANS), self._annotate("bench.window"):
            t0 = time.perf_counter()
            if self.traffic["loop"] == "closed":
                while not queries or time.perf_counter() - t0 < seconds:
                    queries.append(self._query(None))
            else:
                while not queries or time.perf_counter() - t0 < seconds:
                    left = seconds - (time.perf_counter() - t0)
                    queries.append(self._query(max(left, 0.0)))
            self.window_s = time.perf_counter() - t0
        return queries

    def warm_up(self) -> None:
        """Compile or load what the window runs: one whole query, or the
        cut query for the traffic's ``warm_up_s``."""
        self.run(self.traffic.get("warm_up_s", 0.0))
        self.spans = Spans()

    # -- counts -------------------------------------------------------------
    def _pairs(self, res, rec: Recorder) -> float:
        """(candidate, root vertex) pairs settled: every decided or pruned
        candidate settles all its roots; one still in flight, the roots of
        the blocks it walked, in the program's schedule."""
        from repro.core import root_block_order

        n = self.g.n
        decided = len(res.stats) + sum(int(v.get("pruned", 0))
                                       for v in res.per_level.values())
        pairs = float(decided) * n
        for level, lvl in rec.levels.items():
            walked = rec.in_flight_blocks(level)
            if not walked:
                continue
            R = int((lvl.plan or {}).get("root_block",
                                         self.mining.match.root_block))
            width = np.minimum(R, n - root_block_order(self.g, R) * R)
            prefix = np.concatenate([[0], np.cumsum(width)])
            pairs += float(prefix[np.asarray(walked)].sum())
        return pairs

    # -- correctness --------------------------------------------------------
    def answers(self, queries: List[Query]) -> List[reference.Answer]:
        return [reference.Answer(
            [(st.pattern.adj, st.pattern.labels, st.support)
             for st in q.result.stats],
            [(p.adj, p.labels, s) for p, s in q.result.frequent])
            for q in queries]

    def checker(self, **control) -> reference.Checker:
        """The reference under this cell's rule; ``control`` goes to the
        rule's ``support`` (for mIS, ``width``)."""
        if not self.config["undirected"]:
            raise ValueError("the reference covers undirected graphs only")
        g = self.graph
        ref_g = reference.RefGraph(g.n, g.edges, g.labels, g.n_labels)
        return reference.Checker(ref_g, self.rule, self.traffic["sigma"],
                                 self.traffic["lam"],
                                 self.config["max_pattern_size"], **control)


def compare(checker: reference.Checker, answers: List[reference.Answer],
            whole: List[bool]) -> Dict[str, int]:
    """Wrong answers summed over the queries, with the queries that had
    any ("failed_queries")."""
    total = {"wrong_supports": 0, "wrong_frequent": 0, "wrong_candidates": 0}
    failed = 0
    for ans, w in zip(answers, whole):
        got = checker.compare(ans, whole=w)
        failed += any(got.values())
        for key, v in got.items():
            total[key] += v
    total["failed_queries"] = failed
    return total


def control_answers(control: reference.Checker,
                    answers: List[reference.Answer]) -> List[reference.Answer]:
    """The control in the program's place: each searched pattern with the
    support the control gives it, and the patterns that makes frequent."""
    out = []
    for ans in answers:
        searched = [(a, lab, control.support(a, lab)) for a, lab, _ in ans.searched]
        out.append(reference.Answer(
            searched, [(a, lab, s) for a, lab, s in searched
                       if s >= control.tau(len(lab))]))
    return out
