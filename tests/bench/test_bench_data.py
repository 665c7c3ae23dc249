"""The benchmark's copied generator and the yardstick's byte counts."""
import numpy as np
import pytest

from bench import rmat, roofline


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name,scale", [("gnutella", 0.25), ("mico", 0.02)])
def test_the_copied_generator_draws_the_programs_graph(name, scale, seed):
    from repro.core import build_graph
    from repro.data.synthetic import paper_dataset

    want = paper_dataset(name, scale=scale, seed=seed)
    s = rmat.scaled(name, scale)
    got = rmat.rmat(s["n"], s["m"], n_labels=s["n_labels"], seed=seed)
    g = build_graph(got.n, got.edges, got.labels, n_labels=got.n_labels,
                    undirected=True)
    assert g.n == want.n and g.n_labels == want.n_labels
    np.testing.assert_array_equal(g.labels, want.labels)
    np.testing.assert_array_equal(g.out_indptr, want.out_indptr)
    np.testing.assert_array_equal(g.out_indices, want.out_indices)


def test_renumbering_keeps_the_work():
    base = rmat.rmat(300, 900, n_labels=5, seed=3)
    a, b = rmat.renumbered(base, 11, 128), rmat.renumbered(base, 11, 128)
    np.testing.assert_array_equal(a.edges, b.edges)
    c = rmat.renumbered(base, 12, 128)
    assert not np.array_equal(a.edges, c.edges)
    for g in (a, c):
        # each run of 128 ids keeps its vertices, degrees and labels
        for lo in range(0, 300, 128):
            ids = np.arange(lo, min(lo + 128, 300))
            for x, y in ((g, base),):
                deg_x = np.bincount(x.edges.ravel(), minlength=300)[ids]
                deg_y = np.bincount(y.edges.ravel(), minlength=300)[ids]
                assert sorted(deg_x) == sorted(deg_y)
                assert sorted(x.labels[ids]) == sorted(y.labels[ids])


def test_config_graph_follows_the_file():
    cfg = {"vertices": 200, "edges": 600, "labels": 4,
           "generator": {"kind": "rmat", "a": 0.57, "b": 0.19, "c": 0.19},
           "structure_seed": 0, "renumber_window": 4096}
    g1, g2 = rmat.config_graph(cfg, 2**31 + 5), rmat.config_graph(cfg, 2**31 + 5)
    np.testing.assert_array_equal(g1.edges, g2.edges)
    g3 = rmat.config_graph(cfg, 2**31 + 6)
    assert g3.edges.shape == g1.edges.shape
    assert not np.array_equal(g3.edges, g1.edges)


def _brute_bytes(n, edges, labels, R, lab, reciprocal, blocks):
    nbrs = [set() for _ in range(n)]
    for u, v in edges:
        if u != v:
            nbrs[u].add(v)
            nbrs[v].add(u)
    best = None
    for r, o in ((lab[0], lab[1]), (lab[1], lab[0])):
        total = 0
        for b in blocks:
            for u in range(b * R, min(n, (b + 1) * R)):
                if labels[u] != r:
                    continue
                total += 8 + 8 * len(nbrs[u])
                hits = sum(labels[v] == o for v in nbrs[u])
                total += (12 if reciprocal else 8) * hits
        best = total if best is None else min(best, total)
    return best


@pytest.mark.parametrize("reciprocal", [False, True])
def test_roofline_bytes_match_a_brute_force_count(reciprocal):
    g = rmat.rmat(700, 2500, n_labels=3, seed=4)
    R = 128
    sums = roofline.BlockSums(g.n, g.edges, g.labels, g.n_labels, R)
    for lab in ((0, 1), (2, 2), (1, 0)):
        for blocks in ([0], [5, 1, 3], list(range(6))):
            want = _brute_bytes(g.n, g.edges.tolist(), g.labels.tolist(), R,
                                lab, reciprocal, blocks)
            assert sums.bytes(lab, reciprocal, blocks) == want
