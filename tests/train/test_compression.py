"""Gradient compression: error bounds + error feedback + psum path."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.train.compression import (
    compress_tree, compression_init, compressed_psum, decompress_tree,
)


def test_quantization_error_bound():
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(256,)), jnp.float32)}
    q, s, _ = compress_tree(g)
    back = decompress_tree(q, s)
    max_abs = float(jnp.abs(g["w"]).max())
    # int8 symmetric quantization: error ≤ scale/2 = max/254
    assert float(jnp.abs(back["w"] - g["w"]).max()) <= max_abs / 254 + 1e-7


def test_error_feedback_reduces_bias():
    rng = np.random.default_rng(1)
    g = {"w": jnp.asarray(rng.normal(size=(64,)) * 0.01 + 5.0, jnp.float32)}
    state = compression_init(g)
    acc_fb = jnp.zeros(64)
    for _ in range(50):
        q, s, state = compress_tree(g, state)
        acc_fb += decompress_tree(q, s)["w"]
    # with error feedback, the running mean converges to the true gradient
    np.testing.assert_allclose(np.asarray(acc_fb / 50), np.asarray(g["w"]),
                               rtol=1e-3, atol=1e-3)


def test_compressed_psum_single_device():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.asarray([1.0, -2.0, 3.0])}

    def f(g):
        out, _ = compressed_psum(g, "data")
        return out

    got = jax.shard_map(
        f, mesh=mesh, in_specs=({"w": jax.sharding.PartitionSpec()},),
        out_specs={"w": jax.sharding.PartitionSpec()}, check_vma=False)(g)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(g["w"]),
                               atol=0.02)
