"""Fused frontier-expansion kernel: parity vs ref vs the XLA pipeline.

Three implementations must agree bit-for-bit (single-phase semantics):
  * the Pallas kernel (interpret mode: it runs only off-TPU),
  * `ref.frontier_expand_ref` (the single-phase XLA pipeline),
  * `match_block` with expansion="xla", two_phase=False.
Coverage includes edgeless graphs, cap-overflow truncation, the two-phase
no-overflow equivalence, and the batched pattern axis (vmap ⇒ kernel grid).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import MatchConfig, Pattern, build_graph
from repro.core.flexis import initial_candidates
from repro.core.generation import generate_new_patterns
from repro.core.graph import DeviceGraph
from repro.core.matcher import match_block
from repro.core.plan import make_plan, stack_plans
from repro.kernels.frontier_expand.ops import frontier_expand_level
from repro.kernels.frontier_expand.ref import frontier_expand_ref


def _random_graph(n, deg, n_labels, seed, undirected=True):
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, n * deg)
    labels = rng.integers(0, n_labels, n)
    return build_graph(n, np.stack([src, dst], 1), labels,
                       undirected=undirected)


def _xla_cfg(g, cap=256, root_block=128):
    cfg = MatchConfig.for_graph(g, cap=cap, root_block=root_block)
    return dataclasses.replace(cfg, two_phase=False)


def _pallas_cfg(cfg):
    return dataclasses.replace(cfg, expansion="pallas")


def _some_plans(g, want=6):
    pats = initial_candidates(g)
    plans = [make_plan(p, g) for p in pats[:want]]
    for p in generate_new_patterns(pats[: min(len(pats), 6)])[:want]:
        plans.append(make_plan(p, g))
    return plans


def _assert_block_equal(a, b):
    # frontier_expand_level returns 4 fields; match_block appends peak
    ea, ca, fa, oa, *rest_a = a
    eb, cb, fb, ob, *rest_b = b
    assert int(ca) == int(cb)
    assert int(fa) == int(fb)
    assert bool(oa) == bool(ob)
    np.testing.assert_array_equal(np.asarray(ea), np.asarray(eb))
    for ra, rb in zip(rest_a, rest_b):
        assert int(ra) == int(rb)


# ---------------------------------------------------------------------------
# whole-block parity: pallas == xla(single-phase) on directed + undirected
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("undirected", [True, False])
def test_match_block_parity(undirected):
    g = _random_graph(200, 3, 4, seed=1, undirected=undirected)
    dev = DeviceGraph.from_host(g)
    cfg = _xla_cfg(g)
    for plan in _some_plans(g):
        for bs in (0, cfg.root_block):
            _assert_block_equal(
                match_block(dev, plan, jnp.int32(bs), cfg),
                match_block(dev, plan, jnp.int32(bs), _pallas_cfg(cfg)))


# ---------------------------------------------------------------------------
# per-level parity: kernel vs ref on hand-built frontier states
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(16, 80), st.integers(1, 4),
       st.integers(2, 4))
def test_level_parity_property(seed, n, deg, n_labels):
    g = _random_graph(n, deg, n_labels, seed=seed)
    dev = DeviceGraph.from_host(g)
    cfg = _xla_cfg(g, cap=64, root_block=64)
    for plan in _some_plans(g, want=3):
        emb, count, *_ = match_block(dev, plan, jnp.int32(0), cfg)
        if plan.k < 3:
            continue
        # re-run the last level in isolation through both planes
        base = jnp.concatenate(
            [emb[:, : plan.k - 1],
             jnp.full((cfg.cap, 1), -1, jnp.int32)], axis=1)
        got = frontier_expand_level(dev, plan, base, count, plan.k - 1, cfg)
        ref = frontier_expand_ref(dev, plan, base, count, plan.k - 1, cfg)
        _assert_block_equal(got, ref)


# ---------------------------------------------------------------------------
# edgeless graphs: sentinel index arrays must stay well-formed in-kernel
# ---------------------------------------------------------------------------

def test_edgeless_graph():
    n = 32
    g = build_graph(n, np.zeros((0, 2), np.int64), np.zeros(n, np.int32))
    dev = DeviceGraph.from_host(g)
    cfg = _xla_cfg(g, cap=64, root_block=32)
    pat = Pattern(np.array([[False, True], [False, False]]),
                  np.zeros(2, np.int32))
    plan = make_plan(pat, g)
    got = match_block(dev, plan, jnp.int32(0), _pallas_cfg(cfg))
    ref = match_block(dev, plan, jnp.int32(0), cfg)
    _assert_block_equal(got, ref)
    assert int(got[1]) == 0 and int(got[2]) == 0


# ---------------------------------------------------------------------------
# cap overflow: identical truncation (content, count, found, flag)
# ---------------------------------------------------------------------------

def test_cap_overflow_truncation():
    # dense same-label graph + tiny cap forces every level past capacity
    g = _random_graph(64, 8, 1, seed=3)
    cfg = dataclasses.replace(
        MatchConfig.for_graph(g, cap=8192, root_block=64),
        cap=16, two_phase=False)
    dev = DeviceGraph.from_host(g)
    plans = _some_plans(g, want=4)
    overflowed_any = False
    for plan in plans:
        got = match_block(dev, plan, jnp.int32(0), _pallas_cfg(cfg))
        ref = match_block(dev, plan, jnp.int32(0), cfg)
        _assert_block_equal(got, ref)
        overflowed_any |= bool(got[3])
    assert overflowed_any, "geometry was meant to overflow"


# ---------------------------------------------------------------------------
# two-phase xla path: same results when nothing overflows
# ---------------------------------------------------------------------------

def test_two_phase_equivalence_no_overflow():
    g = _random_graph(150, 2, 5, seed=4)
    dev = DeviceGraph.from_host(g)
    cfg1 = _xla_cfg(g)                                        # single-phase
    cfg2 = dataclasses.replace(cfg1, two_phase=True)
    cfgp = _pallas_cfg(cfg1)
    for plan in _some_plans(g):
        ref = match_block(dev, plan, jnp.int32(0), cfg2)
        if bool(ref[3]):
            continue  # phase-1 overflow may reorder truncation; skip
        _assert_block_equal(match_block(dev, plan, jnp.int32(0), cfgp), ref)


# ---------------------------------------------------------------------------
# batched pattern axis: vmap turns into one kernel launch per level
# ---------------------------------------------------------------------------

def test_batched_pattern_axis_parity():
    g = _random_graph(200, 3, 3, seed=5)
    dev = DeviceGraph.from_host(g)
    cfg = _xla_cfg(g)
    pats = initial_candidates(g)
    k3 = generate_new_patterns(pats[: min(len(pats), 8)])[:4]
    assert len(k3) >= 2
    plans = stack_plans([make_plan(p, g) for p in k3])

    def run(c):
        return jax.vmap(
            lambda p: match_block(dev, p, jnp.int32(0), c))(plans)

    for a, b in zip(run(cfg), run(_pallas_cfg(cfg))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_plane_end_to_end():
    """evaluate_level_batched with the pallas plane == sequential oracle."""
    from repro.core.batched import evaluate_level_batched
    from repro.core.flexis import MiningConfig, evaluate_pattern

    g = _random_graph(120, 2, 3, seed=6)
    dev = DeviceGraph.from_host(g)
    cfg = _pallas_cfg(_xla_cfg(g, cap=64, root_block=64))
    cands = initial_candidates(g)[:6]
    taus = [2] * len(cands)
    out, timed_out, _ = evaluate_level_batched(
        g, dev, cands, taus, "mis", cfg, complete=True)
    assert not timed_out
    seq_cfg = MiningConfig(sigma=2, lam=1.0, metric="mis", complete=True,
                           match=cfg, execution="sequential")
    for pat, tau, o in zip(cands, taus, out):
        st_ = evaluate_pattern(g, dev, pat, tau, seq_cfg)
        assert (o.support, o.embeddings_found, o.overflowed) == \
            (st_.support, st_.embeddings_found, st_.overflowed)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_expansion_validation():
    with pytest.raises(ValueError):
        MatchConfig(expansion="fused")
    assert MatchConfig(expansion="pallas").expansion == "pallas"


def test_pallas_config_refused_on_tpu(monkeypatch):
    """Off-TPU the kernel is interpreted; on a TPU, where Mosaic cannot
    lower it, building the config raises instead of interpreting there."""
    from repro.kernels.frontier_expand import ops

    assert ops.interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        MatchConfig(expansion="pallas")
    assert MatchConfig(expansion="xla").expansion == "xla"


def test_two_phase_normalized_off_on_pallas_plane():
    """two_phase is an xla-plane knob; a pallas config must not claim it."""
    cfg = MatchConfig(expansion="pallas", two_phase=True)
    assert cfg.two_phase is False
    assert MatchConfig(expansion="xla", two_phase=True).two_phase is True


def test_vmem_guard_rejects_oversized_hardware_geometry():
    from repro.kernels.frontier_expand.kernel import (
        frontier_expand, frontier_expand_vmem_bytes)

    g = _random_graph(64, 2, 2, seed=7)
    dev = DeviceGraph.from_host(g)
    plan = make_plan(initial_candidates(g)[0], g)
    cap = 1 << 20  # ~8 GiB of candidate rows: must be refused pre-Mosaic
    assert frontier_expand_vmem_bytes(g.n, 2 * g.n_edges, cap, 64,
                                      plan.k) > 16 * 2**20
    emb = jnp.full((cap, plan.k), -1, jnp.int32)
    with pytest.raises(ValueError, match="VMEM"):
        frontier_expand(
            dev.labels, dev.out_indptr, dev.out_indices, dev.in_indptr,
            dev.in_indices, emb, jnp.int32(0), plan.anchor_pos[1],
            plan.anchor_out[1], plan.cand_label[1], plan.min_out[1],
            plan.min_in[1], plan.check_out[1], plan.check_in[1],
            level=1, k=plan.k, cap=cap, chunk=64, max_chunks=1,
            bisect_iters=4, n=g.n, interpret=False)
