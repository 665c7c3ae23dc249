"""Batched-pattern matching ≡ per-pattern loop (same supports)."""
import numpy as np

from repro.core import (
    MatchConfig, MiningConfig, initial_candidates, tau_threshold,
)
from repro.core.batched import batched_mis_supports, stack_plans
from repro.core.flexis import evaluate_pattern
from repro.core.graph import DeviceGraph
from repro.core.plan import make_plan
from repro.data.synthetic import rmat_graph


def test_batched_supports_equal_per_pattern():
    g = rmat_graph(300, 2000, n_labels=3, seed=4, undirected=True)
    dg = DeviceGraph.from_host(g)
    cfg = MatchConfig.for_graph(g, cap=2048, root_block=128)
    cands = initial_candidates(g)[:12]
    taus = [tau_threshold(5, 1.0, p.k) for p in cands]
    mcfg = MiningConfig(sigma=5, lam=1.0, metric="mis", complete=True,
                        match=cfg)
    base = [evaluate_pattern(g, dg, p, t, mcfg).support
            for p, t in zip(cands, taus)]
    res = batched_mis_supports(g, cands, taus, cfg, complete=True)
    assert list(res.supports) == base
    assert not res.overflowed.any()


def test_batched_early_exit_reaches_tau():
    g = rmat_graph(200, 1500, n_labels=2, seed=1, undirected=True)
    cfg = MatchConfig.for_graph(g, cap=2048, root_block=64)
    cands = initial_candidates(g)[:4]
    res_full = batched_mis_supports(g, cands, [10**6] * len(cands), cfg,
                                    complete=True)
    taus = [max(1, int(s) // 2) for s in res_full.supports]
    res = batched_mis_supports(g, cands, taus, cfg)
    # early exit guarantees at least tau for patterns that can reach it
    for s, t, full in zip(res.supports, taus, res_full.supports):
        assert s >= min(t, full)


def test_stack_plans_shapes():
    g = rmat_graph(100, 600, n_labels=2, seed=2)
    cands = initial_candidates(g)[:3]
    plans = [make_plan(p, g) for p in cands]
    stacked = stack_plans(plans)
    assert stacked.anchor_pos.shape == (3, 2)
    assert stacked.check_out.shape == (3, 2, 2)


# Each field's dtype and shape for a size-k plan, in pytree order.
_PLAN_FIELDS = (
    ("root_label", np.int32, lambda k: ()),
    ("root_min_out", np.int32, lambda k: ()),
    ("root_min_in", np.int32, lambda k: ()),
    ("anchor_pos", np.int32, lambda k: (k,)),
    ("anchor_out", np.bool_, lambda k: (k,)),
    ("cand_label", np.int32, lambda k: (k,)),
    ("min_out", np.int32, lambda k: (k,)),
    ("min_in", np.int32, lambda k: (k,)),
    ("check_out", np.bool_, lambda k: (k, k)),
    ("check_in", np.bool_, lambda k: (k, k)),
)

# The plans of `test_stack_plans_shapes`' graph (three edges, then two
# 3-vertex patterns) as the matcher saw them when each field was built on
# the device: the fields in `_PLAN_FIELDS` order, then ``order``.
_DEVICE_BUILT = (
    (0, 1, 0, [0, 0], [0, 1], [0, 0], [1, 0], [0, 1],
     [[0, 0], [0, 0]], [[0, 0], [0, 0]], (0, 1)),
    (0, 1, 0, [0, 0], [0, 1], [0, 1], [1, 0], [0, 1],
     [[0, 0], [0, 0]], [[0, 0], [0, 0]], (0, 1)),
    (0, 0, 1, [0, 0], [0, 0], [0, 1], [0, 1], [1, 0],
     [[0, 0], [0, 0]], [[0, 0], [0, 0]], (1, 0)),
    (0, 0, 2, [0, 0, 0], [0, 0, 0], [0, 0, 0], [0, 1, 1], [2, 0, 0],
     [[0, 0, 0], [0, 0, 0], [0, 0, 0]], [[0, 0, 0], [0, 0, 0], [0, 0, 0]],
     (0, 1, 2)),
    (0, 0, 2, [0, 0, 1], [0, 0, 1], [0, 0, 0], [0, 2, 1], [2, 0, 1],
     [[0, 0, 0], [0, 0, 0], [1, 0, 0]], [[0, 0, 0], [0, 0, 0], [0, 0, 0]],
     (0, 1, 2)),
)


def _plan_patterns():
    from repro.core.generation import generate_new_patterns

    g = rmat_graph(100, 600, n_labels=2, seed=2)
    edges = initial_candidates(g)
    k3 = [p for p in generate_new_patterns(edges) if p.k == 3]
    return g, edges[:3] + k3[:2]


def test_make_plan_builds_host_arrays_with_the_device_plans_values():
    import jax

    from repro.core.plan import put_plan

    g, pats = _plan_patterns()
    for pat, want in zip(pats, _DEVICE_BUILT, strict=True):
        plan = make_plan(pat, g)
        placed = put_plan(plan)
        assert plan.order == want[-1] and placed.order == plan.order
        for (name, dtype, shape), value in zip(_PLAN_FIELDS, want):
            host, dev = getattr(plan, name), getattr(placed, name)
            assert not isinstance(host, jax.Array), name
            assert host.dtype == dtype and host.shape == shape(pat.k), name
            np.testing.assert_array_equal(host, np.asarray(value, dtype))
            assert isinstance(dev, jax.Array), name
            # one abstract signature on both sides: a jitted step that
            # takes either traces once
            assert jax.typeof(dev) == jax.typeof(host), name
            np.testing.assert_array_equal(np.asarray(dev), host)


def test_stack_plans_stacks_on_the_host():
    import jax

    g, pats = _plan_patterns()
    for group in (pats[:3], pats[3:]):
        stacked = stack_plans([make_plan(p, g) for p in group])
        k = group[0].k
        assert stacked.k == k and stacked.order == ()
        for name, dtype, shape in _PLAN_FIELDS:
            leaf = getattr(stacked, name)
            assert isinstance(leaf, np.ndarray), name
            assert not isinstance(leaf, jax.Array), name
            assert leaf.dtype == dtype, name
            assert leaf.shape == (len(group),) + shape(k), name


def test_a_host_plan_and_its_put_share_one_trace():
    import jax
    import jax.numpy as jnp

    from repro.core.matcher import match_block_lanes
    from repro.core.plan import put_plan

    g, pats = _plan_patterns()
    dg = DeviceGraph.from_host(g)
    cfg = MatchConfig.for_graph(g, cap=256, root_block=32)
    plan = make_plan(pats[3], g)
    traced = []

    @jax.jit
    def step(pl, start):
        traced.append(1)
        return match_block_lanes.__wrapped__(dg, pl, start, cfg)

    want = step(plan, jnp.int32(0))
    got = step(put_plan(plan), jnp.int32(0))
    assert len(traced) == 1
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unbatched_step_bit_identical_to_vmapped():
    """The P=1 no-vmap fast path must return exactly what the vmapped
    size-1 bucket returns (it replaces it transparently in _mine_group)."""
    import jax
    import jax.numpy as jnp

    from repro.core.batched import _state_init, _step_fn

    g = rmat_graph(200, 1200, n_labels=2, seed=5, undirected=True)
    dg = DeviceGraph.from_host(g)
    cfg = MatchConfig.for_graph(g, cap=512, root_block=64)
    pat = initial_candidates(g)[0]
    plans = stack_plans([make_plan(pat, g)])
    for metric in ("mis", "mis_luby", "mni", "frac"):
        state = _state_init(metric, 1, pat.k, g.n)
        taus = jnp.full((1,), 10**6, jnp.int32)
        outs = {}
        for unbatched in (False, True):
            step = _step_fn(metric, pat.k, cfg, unbatched=unbatched)
            outs[unbatched] = step(dg, plans, jnp.int32(0), state, taus)
        for a, b in zip(jax.tree_util.tree_leaves(outs[False]),
                        jax.tree_util.tree_leaves(outs[True])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_collect_pattern_embeddings_matches_per_block_loop():
    """mis_exact's device half: block-batched collection == the one-block-
    per-dispatch loop, field for field, for any dispatch width."""
    import jax.numpy as jnp

    from repro.core.batched import collect_pattern_embeddings
    from repro.core.matcher import match_block

    g = rmat_graph(150, 900, n_labels=3, seed=8, undirected=True)
    dg = DeviceGraph.from_host(g)
    cfg = MatchConfig.for_graph(g, cap=1024, root_block=32)
    n_blocks = -(-g.n // cfg.root_block)
    rng = np.random.default_rng(0)
    order = rng.permutation(n_blocks).astype(np.int64)

    for pat in initial_candidates(g)[:3]:
        plan = make_plan(pat, g)
        ref_rows, ref_found, ref_ovf, ref_peak = [], 0, False, 0
        for b in order:
            emb, count, found, ovf, peak = match_block(
                dg, plan, jnp.int32(int(b) * cfg.root_block), cfg)
            c = int(count)
            if c:
                ref_rows.append(np.asarray(emb[:c]))
            ref_found += int(found)
            ref_ovf |= bool(ovf)
            ref_peak = max(ref_peak, int(peak))
        ref = (np.concatenate(ref_rows, 0) if ref_rows
               else np.zeros((0, pat.k), np.int32))
        for width in (1, 3, 8, 64):
            embs, found, ovf, blocks, peak, dispatches = \
                collect_pattern_embeddings(
                    dg, plan, cfg, g.n, block_order=order,
                    blocks_per_dispatch=width)
            np.testing.assert_array_equal(embs, ref)
            assert (found, ovf, blocks, peak) == \
                (ref_found, ref_ovf, n_blocks, ref_peak)
            assert dispatches == -(-n_blocks // width)
