"""What the benchmark observes of a running query, without changing it.

* `Recorder` is handed to ``mine(hooks=...)``.  It answers the resume
  questions of that surface with "nothing to resume" and keeps, per level,
  the recorded plan and the last per-block state of every candidate group:
  which candidates are still in flight and how many root blocks each has
  walked.
* `spans` times calls into the program's layers from outside: it wraps a
  named function of a program module for the length of a ``with`` block,
  calls straight through, and records each call's host time, also as a
  profiler annotation so that a trace shows it, and keeps what each call
  returned.  A name that is not found is left alone, and its span stays
  empty.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class GroupRecord:
    blocks_run: np.ndarray      # (P0,) root blocks walked per candidate
    in_flight: np.ndarray       # (P0,) bool: not yet decided


class LevelRecord:
    """Level hooks of the batched plane (see `repro.core.batched`)."""

    def __init__(self):
        self.plan: Optional[dict] = None
        self.groups: Dict[Tuple[int, int], GroupRecord] = {}

    # the resume half of the surface: nothing to resume
    def resume_outcomes(self):
        return None

    def resume_dispatches(self) -> int:
        return 0

    def resume_plan(self):
        return None

    def group_resume(self, k, lo):
        return None

    # the observing half
    def record_plan(self, d: dict) -> None:
        self.plan = dict(d)

    def on_group_state(self, k, lo, gs) -> None:
        bucket = np.asarray(gs.bucket_map)
        live = np.zeros(len(gs.blocks_run), bool)
        live[bucket[bucket >= 0]] = True
        self.groups[(k, lo)] = GroupRecord(np.array(gs.blocks_run, np.int64), live)

    def on_group_done(self, k, lo, idxs, outcomes, dispatches, **_) -> None:
        self.groups[(k, lo)] = GroupRecord(
            np.array([o.blocks_run for o in outcomes], np.int64),
            np.zeros(len(outcomes), bool))


class Recorder:
    """The hooks object for one ``mine()`` call."""

    def __init__(self):
        self.levels: Dict[int, LevelRecord] = {}

    def loop_resume(self):
        return None

    def level_hooks(self, level: int) -> LevelRecord:
        rec = self.levels[level] = LevelRecord()
        return rec

    def on_level_end(self, state) -> None:
        pass

    def in_flight_blocks(self, level: int) -> List[int]:
        """Root blocks walked by each candidate of ``level`` still in flight."""
        rec = self.levels.get(level)
        if rec is None:
            return []
        return [int(b) for g in rec.groups.values()
                for b in g.blocks_run[g.in_flight]]


class Spans:
    """Host time of named calls into the program, per span name."""

    def __init__(self):
        self.seconds: Dict[str, List[float]] = {}
        self.results: Dict[str, list] = {}
        self.found: Dict[str, bool] = {}

    @contextlib.contextmanager
    def wrap(self, targets: Sequence[Tuple[str, str, str]]) -> Iterator[None]:
        """``targets``: (span name, module, dotted attribute) triples."""
        import jax

        undo = []
        try:
            for span, module, attr in targets:
                try:
                    owner = importlib.import_module(module)
                except ImportError:
                    owner = None
                *path, leaf = attr.split(".")
                for part in path:
                    owner = getattr(owner, part, None)
                fn = getattr(owner, leaf, None) if owner is not None else None
                self.found[span] = callable(fn)
                if not callable(fn):
                    continue
                times = self.seconds.setdefault(span, [])
                results = self.results.setdefault(span, [])

                def timed(*a, _fn=fn, _span=span, _times=times, _res=results,
                          **kw):
                    t = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench." + _span):
                        out = _fn(*a, **kw)
                    _times.append(time.perf_counter() - t)
                    _res.append(out)
                    return out

                functools.update_wrapper(timed, fn)
                setattr(owner, leaf, timed)
                undo.append((owner, leaf, fn))
            yield
        finally:
            for owner, leaf, fn in reversed(undo):
                setattr(owner, leaf, fn)
