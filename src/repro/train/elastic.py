"""Elastic scaling + failure handling for the host training loop.

Design (1000+-node posture, DESIGN.md §4):

  * Checkpoints are mesh-shape-agnostic (full logical arrays), so recovery
    after losing nodes is: build the largest feasible mesh from surviving
    devices (`best_mesh`), `restore(..., shardings=new)` — no format change.
  * The step loop runs under `StepGuard`: a wall-clock budget per step; a
    straggling/hung step raises `StragglerTimeout` so the runner can
    checkpoint-restart (in a real deployment, after excluding the slow
    host).  Inside a step, work is fixed-shape (frontier caps, padded
    blocks), which bounds skew structurally.
  * `HeartbeatFile` is the cross-host liveness primitive a cluster agent
    watches (mtime stale ⇒ kill + reschedule).
"""
from __future__ import annotations

import dataclasses
import os
import signal
import time
from pathlib import Path
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["best_mesh", "StragglerTimeout", "StepGuard", "HeartbeatFile",
           "resume_or_init"]


class StragglerTimeout(RuntimeError):
    pass


def best_mesh(n_devices: Optional[int] = None, *,
              prefer_model: int = 16) -> Mesh:
    """Largest (data, model) mesh over surviving devices: model axis is the
    largest power-of-two divisor ≤ prefer_model, data gets the rest."""
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    model = 1
    while model * 2 <= prefer_model and n % (model * 2) == 0:
        model *= 2
    data = n // model
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2, devices=np.array(devs[:n]))


@dataclasses.dataclass
class StepGuard:
    """Raise StragglerTimeout if a step exceeds `budget_s` (SIGALRM-based;
    main thread only — exactly where the host loop lives)."""

    budget_s: float

    def __enter__(self):
        if self.budget_s and hasattr(signal, "SIGALRM"):
            self._old = signal.signal(signal.SIGALRM, self._fire)
            signal.setitimer(signal.ITIMER_REAL, self.budget_s)
        return self

    @staticmethod
    def _fire(signum, frame):
        raise StragglerTimeout("step exceeded wall-clock budget")

    def __exit__(self, *exc):
        if self.budget_s and hasattr(signal, "SIGALRM"):
            signal.setitimer(signal.ITIMER_REAL, 0.0)
            signal.signal(signal.SIGALRM, self._old)
        return False


class HeartbeatFile:
    def __init__(self, path: str | os.PathLike):
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)

    def beat(self, step: int):
        self.path.write_text(f"{step} {time.time()}\n")

    def age_s(self) -> Optional[float]:
        if not self.path.exists():
            return None
        return time.time() - self.path.stat().st_mtime


def resume_or_init(ckpt_dir, init_fn, abstract_tree, shardings=None):
    """Restore the latest committed checkpoint onto the (possibly new) mesh,
    or initialize fresh. Returns (state, extra, start_step)."""
    from . import checkpoint as ckpt

    step = ckpt.latest_step(ckpt_dir)
    if step is None:
        return init_fn(), {}, 0
    state, extra, step = ckpt.restore(ckpt_dir, abstract_tree, step=step,
                                      shardings=shardings)
    return state, extra, step + 1
