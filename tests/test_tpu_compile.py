"""Compile rehearsal for TPU v5e: the mining path's programs, compiled for a
described ``v5e:2x2`` topology without a chip attached.

Nothing runs: each case lowers and compiles one program with the TPU
compiler, which refuses what the chip would refuse (unsupported lowerings,
programs past device memory).  The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only the
worker given this file loads the TPU library.  Geometry is small (cap 1024,
k = 3) to keep each compile to seconds.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import MatchConfig, build_graph
from repro.core import batched as batched_lib
from repro.core.distributed import sharded_batched_mis_step
from repro.core.flexis import initial_candidates
from repro.core.generation import generate_new_patterns
from repro.core.graph import DeviceGraph
from repro.core.plan import make_plan, stack_plans

K = 3
N_PATTERNS = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def problem():
    """A small labeled graph, its matcher geometry and K-vertex plans."""
    rng = np.random.default_rng(0)
    n = 512
    src = rng.integers(0, n, 4 * n)
    dst = rng.integers(0, n, 4 * n)
    g = build_graph(n, np.stack([src, dst], 1), rng.integers(0, 5, n),
                    undirected=True)
    cfg = MatchConfig.for_graph(g, cap=1024, root_block=256)
    pats = generate_new_patterns(initial_candidates(g)[:8])[:N_PATTERNS]
    assert len(pats) == N_PATTERNS and all(p.k == K for p in pats)
    plans = stack_plans([make_plan(p, g) for p in pats])
    return g, cfg, plans


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _device_graph(g, sharding):
    dg = DeviceGraph.from_host(g)
    return dataclasses.replace(dg, **{
        f.name: jax.ShapeDtypeStruct(getattr(dg, f.name).shape,
                                     getattr(dg, f.name).dtype,
                                     sharding=sharding)
        for f in dataclasses.fields(dg) if f.name != "n"})


@pytest.mark.parametrize("metric", ["mis", "mni"])
def test_batched_step_compiles_for_one_chip(topo, problem, metric):
    g, cfg, plans = problem
    one_chip = SingleDeviceSharding(topo.devices[0])
    state = batched_lib._state_init(metric, N_PATTERNS, K, g.n)
    step = batched_lib._step_fn(metric, K, cfg)
    compiled = step.lower(
        _device_graph(g, one_chip), _shapes(plans, one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        _shapes(state, one_chip),
        jax.ShapeDtypeStruct((N_PATTERNS,), jnp.int32, sharding=one_chip),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_sharded_batched_mis_step_compiles_for_four_chips(topo, problem):
    g, cfg, plans = problem
    mesh = Mesh(np.array(topo.devices[:4]), ("workers",),
                axis_types=(AxisType.Auto,))
    rep = NamedSharding(mesh, P())
    bitmaps, counts = batched_lib._state_init("mis", N_PATTERNS, K, g.n)
    compiled = sharded_batched_mis_step.lower(
        _device_graph(g, rep), _shapes(plans, rep),
        jax.ShapeDtypeStruct((4,), jnp.int32,
                             sharding=NamedSharding(mesh, P("workers"))),
        _shapes(bitmaps, rep), _shapes(counts, rep),
        jax.ShapeDtypeStruct((N_PATTERNS,), jnp.int32, sharding=rep),
        cfg=cfg, k=K, n=g.n, axis="workers", mesh=mesh,
    ).compile()
    hlo = compiled.as_text()
    # Luby conflict resolution: the per-round all-reduce(min) across chips
    assert "all-reduce" in hlo


def test_frontier_expand_refused_by_mosaic(topo, problem):
    """The fused kernel does not lower for v5e; `ops.interpret_mode` quotes
    this refusal.  If this starts to compile, the kernel can run on the
    chip and the platform rule in `ops.interpret_mode` should change."""
    from repro.kernels.frontier_expand.kernel import frontier_expand
    from repro.kernels.frontier_expand.ops import MOSAIC_REFUSAL

    g, cfg, plans = problem
    one_chip = SingleDeviceSharding(topo.devices[0])
    dg = _device_graph(g, one_chip)
    plan = jax.tree_util.tree_map(lambda a: a[0], plans)
    lvl = 1

    def scalar(a):
        return jax.ShapeDtypeStruct(np.shape(a[lvl]), a.dtype,
                                    sharding=one_chip)

    with pytest.raises(NotImplementedError, match=MOSAIC_REFUSAL):
        frontier_expand.lower(
            dg.labels, dg.out_indptr, dg.out_indices, dg.in_indptr,
            dg.in_indices,
            jax.ShapeDtypeStruct((cfg.cap, K), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
            scalar(plan.anchor_pos), scalar(plan.anchor_out),
            scalar(plan.cand_label), scalar(plan.min_out),
            scalar(plan.min_in), scalar(plan.check_out),
            scalar(plan.check_in),
            level=lvl, k=K, cap=cfg.cap, chunk=cfg.chunk,
            max_chunks=cfg.max_chunks, bisect_iters=cfg.bisect_iters, n=g.n,
            interpret=False).compile()
