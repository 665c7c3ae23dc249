"""JAX frontier matcher vs the brute-force host oracle (property tests)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, assume, HealthCheck

from repro.core import MatchConfig, make_plan, match_block
from repro.core.graph import DeviceGraph
from repro.core.matcher import edge_exists
from repro.core.metrics import enumerate_embeddings_host
from tests.conftest import patterns, data_graphs


def _all_embeddings(g, pat, cfg):
    """Run every root block; return embeddings in pattern-vertex order."""
    dg = DeviceGraph.from_host(g)
    plan = make_plan(pat, g)
    rows = []
    total_found = 0
    overflow = False
    for b in range(0, g.n, cfg.root_block):
        emb, count, found, ovf, _peak = match_block(dg, plan, jnp.int32(b), cfg)
        c = int(count)
        total_found += int(found)
        overflow |= bool(ovf)
        if c:
            rows.append(np.asarray(emb[:c]))
    got = np.concatenate(rows, 0) if rows else np.zeros((0, pat.k), np.int32)
    inv = np.argsort(np.array(plan.order))
    return got[:, inv], total_found, overflow


@settings(max_examples=60, deadline=None, suppress_health_check=[HealthCheck.too_slow])
@given(data_graphs(max_n=16), patterns(min_k=2, max_k=4))
def test_matcher_matches_oracle(g, pat):
    cfg = MatchConfig.for_graph(g, cap=4096, root_block=8, chunk=4)
    got, found, overflow = _all_embeddings(g, pat, cfg)
    assume(not overflow)
    oracle = enumerate_embeddings_host(g, pat)
    got_set = set(map(tuple, got.tolist()))
    oracle_set = set(map(tuple, oracle.tolist()))
    assert got_set == oracle_set
    assert found == len(oracle_set)


@settings(max_examples=25, deadline=None, suppress_health_check=[HealthCheck.too_slow])
@given(data_graphs(max_n=14), patterns(min_k=2, max_k=3))
def test_matcher_chunk_size_invariant(g, pat):
    """Chunked gathers must not change results across chunk geometries."""
    base = None
    for chunk in (1, 3, 8):
        cfg = MatchConfig.for_graph(g, cap=4096, root_block=16, chunk=chunk)
        got, _, overflow = _all_embeddings(g, pat, cfg)
        assume(not overflow)
        s = set(map(tuple, got.tolist()))
        if base is None:
            base = s
        else:
            assert s == base


def test_overflow_flag_and_clipping():
    """Tiny cap: matcher must flag overflow and never exceed capacity."""
    # star graph: hub label 0, many leaves label 1 — k=2 pattern explodes
    n = 40
    labels = [0] + [1] * (n - 1)
    edges = [(0, i) for i in range(1, n)]
    from repro.core import build_graph, pattern_from_edges

    g = build_graph(n, edges, labels)
    pat = pattern_from_edges([0, 1], [(0, 1)])
    cfg = MatchConfig.for_graph(g, cap=8, root_block=64, chunk=4)
    dg = DeviceGraph.from_host(g)
    plan = make_plan(pat, g)
    emb, count, found, ovf, peak = match_block(dg, plan, jnp.int32(0), cfg)
    assert bool(ovf)
    assert int(count) == 8
    assert int(found) == n - 1
    assert int(peak) == 8  # post-clip peak never exceeds cap


def test_edge_exists_bisect():
    rng = np.random.default_rng(3)
    n = 50
    m = rng.random((n, n)) < 0.15
    np.fill_diagonal(m, False)
    src, dst = np.nonzero(m)
    from repro.core import build_graph

    g = build_graph(n, np.stack([src, dst], 1), np.zeros(n, np.int32))
    dg = DeviceGraph.from_host(g)
    u = jnp.asarray(rng.integers(0, n, size=500), jnp.int32)
    v = jnp.asarray(rng.integers(0, n, size=500), jnp.int32)
    iters = MatchConfig.for_graph(g).bisect_iters
    got = np.asarray(edge_exists(dg.out_indptr, dg.out_indices, u, v, iters))
    want = m[np.asarray(u), np.asarray(v)]
    np.testing.assert_array_equal(got, want)


def test_directed_vs_bidirectional_patterns():
    """A→B must not match where only B→A exists."""
    from repro.core import build_graph, pattern_from_edges

    g = build_graph(2, [(1, 0)], [0, 1])
    cfg = MatchConfig.for_graph(g, cap=16, root_block=4, chunk=2)
    pat_fwd = pattern_from_edges([0, 1], [(0, 1)])  # A→B
    pat_bwd = pattern_from_edges([0, 1], [], bidir=False).with_edge(1, 0)  # B→A
    got_f, _, _ = _all_embeddings(g, pat_fwd, cfg)
    got_b, _, _ = _all_embeddings(g, pat_bwd, cfg)
    assert got_f.shape[0] == 0
    assert got_b.shape[0] == 1


@pytest.mark.parametrize("two_phase,cap,n_labels", [
    (False, 512, 3), (True, 512, 3), (False, 24, 1), (True, 24, 1)])
def test_row_tiles_do_not_change_results(monkeypatch, two_phase, cap,
                                         n_labels):
    """The expansion walks the frontier in row tiles; any tile height gives
    the untiled result — rows, order, count, found and overflow (cap 24 on
    one label overflows, in phase 1 of the two-phase path too)."""
    import dataclasses

    import jax

    from repro.core import build_graph, matcher
    from repro.core.flexis import initial_candidates
    from repro.core.generation import generate_new_patterns

    rng = np.random.default_rng(11)
    n = 300
    src = rng.integers(0, n, 5 * n)
    dst = rng.integers(0, n, 5 * n)
    g = build_graph(n, np.stack([src, dst], 1),
                    rng.integers(0, n_labels, n), undirected=True)
    dev = DeviceGraph.from_host(g)
    cfg = dataclasses.replace(
        MatchConfig.for_graph(g, cap=4096, root_block=128, chunk=4),
        cap=cap, two_phase=two_phase)
    pats = generate_new_patterns(initial_candidates(g)[:6])[:3]
    assert pats and matcher._row_tile(cfg) >= cfg.cap  # one tile by default

    def run(plan):
        # a fresh jit: the tile height is read at trace time
        return jax.jit(lambda s: match_block.__wrapped__(dev, plan, s, cfg))(
            jnp.int32(0))

    ref = [run(make_plan(p, g)) for p in pats]
    monkeypatch.setattr(matcher, "TILE_LANES", 5 * cfg.chunk)
    assert matcher._row_tile(cfg) == 5
    for p, want in zip(pats, ref):
        got = run(make_plan(p, g))
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if cap == 24:
        assert any(bool(r[3]) for r in ref), "cap 24 was meant to overflow"



@pytest.mark.parametrize("two_phase", [False, True])
def test_batch_checks_skip_only_unneeded_edge_checks(two_phase):
    """A vmapped batch skips the edge checks none of its patterns needs:
    per-pattern results equal the unbatched plan-driven match and the host
    oracle, for a batch that needs none (single edges), some, and all."""
    import dataclasses

    import jax

    from repro.core import build_graph
    from repro.core.flexis import initial_candidates
    from repro.core.generation import generate_new_patterns
    from repro.core.matcher import batch_checks
    from repro.core.plan import stack_plans

    rng = np.random.default_rng(5)
    n = 200
    g = build_graph(n, rng.integers(0, n, (6 * n, 2)),
                    rng.integers(0, 2, n), undirected=False)
    dev = DeviceGraph.from_host(g)
    cfg = dataclasses.replace(
        MatchConfig.for_graph(g, cap=8192, root_block=256, chunk=4),
        two_phase=two_phase)
    edges = initial_candidates(g)
    k3 = [p for p in generate_new_patterns(edges) if p.k == 3]
    need = lambda p: bool(np.any(np.asarray(make_plan(p, g).check_out)  # noqa: E731
                                 | np.asarray(make_plan(p, g).check_in)))
    batches = [[p for p in edges if not need(p)][:3],
               [p for p in k3 if not need(p)][:2] + [p for p in k3 if need(p)][:2],
               [p for p in edges if need(p)][:2]]
    for pats in batches:
        assert pats
        plans = stack_plans([make_plan(p, g) for p in pats])
        got = jax.vmap(lambda pl: match_block(
            dev, pl, jnp.int32(0), cfg, batch_checks(plans)))(plans)
        for i, p in enumerate(pats):
            plan = make_plan(p, g)
            want = match_block(dev, plan, jnp.int32(0), cfg)
            for a, b in zip(got, want):
                np.testing.assert_array_equal(np.asarray(a)[i], np.asarray(b))
            # one root block holds every root: all embeddings, none dropped
            assert not bool(got[3][i])
            rows = np.asarray(got[0][i])[:int(got[1][i])]
            rows = rows[:, np.argsort(np.array(plan.order))]
            assert set(map(tuple, rows.tolist())) == set(
                map(tuple, enumerate_embeddings_host(g, p).tolist()))
