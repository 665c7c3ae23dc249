"""AdamW + schedule + ZeRO-1 spec tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.train.optimizer import (
    AdamWConfig, adamw_init, adamw_update, global_norm, schedule, zero1_specs,
)


def test_adamw_minimizes_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=200, grad_clip=10.0)
    params = {"w": jnp.asarray([3.0, -2.0, 1.5])}
    state = adamw_init(params)
    loss_fn = lambda p: jnp.sum(jnp.square(p["w"]))
    for _ in range(150):
        grads = jax.grad(loss_fn)(params)
        params, state = adamw_update(cfg, grads, state, params)
    assert float(loss_fn(params)) < 1e-3
    assert int(state.step) == 150


def test_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    assert float(schedule(cfg, jnp.int32(0))) == 0.0
    assert abs(float(schedule(cfg, jnp.int32(10))) - 1.0) < 1e-6
    assert float(schedule(cfg, jnp.int32(5))) == 0.5
    end = float(schedule(cfg, jnp.int32(100)))
    assert abs(end - 0.1) < 1e-5


def test_grad_clip_applies():
    cfg = AdamWConfig(lr=1e-3, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = adamw_init(params)
    big = {"w": jnp.full(4, 1e6)}
    p2, s2 = adamw_update(cfg, big, state, params)
    # first-step Adam update magnitude ≈ lr regardless of grad scale
    assert float(jnp.abs(p2["w"]).max()) < 2 * cfg.lr


def test_global_norm():
    t = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    assert abs(float(global_norm(t)) - 5.0) < 1e-6


def test_zero1_specs_moves_to_data_axis():
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # data axis size 1 → no change
    specs = {"w": P(None, "model")}
    abst = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
    out = zero1_specs(specs, abst, mesh=mesh)
    assert out["w"] == P(None, "model")
