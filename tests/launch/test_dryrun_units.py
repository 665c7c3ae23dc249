"""Dry-run machinery units: HLO collective parser + divisibility fixup.

(The full 512-device dry-run grid is executed by launch/dryrun.py and
recorded in EXPERIMENTS.md — too heavy for CI; these tests cover its parts
on small meshes.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.launch.dryrun import (
    _fix_divisibility, collective_bytes_from_hlo,
)


def _mesh(shape, names):
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def test_collective_parser_counts_psum():
    mesh = _mesh((1,), ("data",))

    def f(x):
        return jax.lax.psum(x, "data")

    x = jnp.ones((128, 64), jnp.float32)
    hlo = (
        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False))
        .lower(x).compile().as_text())
    stats = collective_bytes_from_hlo(hlo)
    assert stats["count"] >= 1
    assert stats["all-reduce"] >= 128 * 64 * 4


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %ar = f32[256,1024]{1,0} all-reduce(f32[256,1024]{1,0} %x), replica_groups={}
  %ag.1 = bf16[8,128]{1,0} all-gather(bf16[4,128]{1,0} %y), dimensions={0}
  %done = f32[4]{0} all-reduce-done(f32[4]{0} %start)
  %nothing = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b)
"""
    stats = collective_bytes_from_hlo(hlo)
    # the `-done` line is skipped (its shape is carried by the start op)
    assert stats["all-reduce"] == 256 * 1024 * 4
    assert stats["all-gather"] == 8 * 128 * 2


def _shape_only_mesh(shape, names):
    """_fix_divisibility/_axis_size read only axis_names + devices.shape, so
    tests can use a stub and stay independent of the process device count
    (a real (2, 4) mesh would need 8 devices — and whether that works would
    depend on whether another test initialized jax first)."""
    import types

    return types.SimpleNamespace(axis_names=tuple(names),
                                 devices=np.empty(shape))


def test_fix_divisibility_relocates_axis():
    mesh = _shape_only_mesh((2, 4), ("data", "model"))
    # 8 experts on a 4-way axis is fine; 6 is not → move to last dividing dim
    spec = _fix_divisibility(P("model", None, None), (6, 12, 16), mesh)
    assert spec == P(None, None, "model")
    # nothing to fix
    spec = _fix_divisibility(P("model", None), (8, 5), mesh)
    assert spec == P("model", None)
    # nowhere to go → dropped
    spec = _fix_divisibility(P("model",), (6,), mesh)
    assert spec == P(None)


def test_constrain_divisibility_guard():
    # needs a real 2-way model axis; forced host devices must be set before
    # jax initializes, so run isolated (same pattern as test_distributed)
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp
        from repro.models.sharding import constrain, use_rules

        mesh = jax.make_mesh((1, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with use_rules(mesh):
            @jax.jit
            def f(x):
                return constrain(x, "batch", None, "heads", None)

            # 3 heads on a 2-way model axis -> guard must drop the constraint
            out = f(jnp.ones((2, 4, 3, 8)))
            assert out.shape == (2, 4, 3, 8)
        print("CONSTRAIN_GUARD_OK", flush=True)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    assert "CONSTRAIN_GUARD_OK" in proc.stdout, proc.stderr[-3000:]
