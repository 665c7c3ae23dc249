"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (the dry-run must set XLA_FLAGS before
any jax initialization)."""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for_devices(n: Optional[int] = None,
                          model_parallel: int = 1) -> Mesh:
    """Small-scale mesh for local runs/tests: (n/model, model)."""
    n = n if n is not None else len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh(
        (n // model_parallel, model_parallel), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2)


def mesh_device_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
