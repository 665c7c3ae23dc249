"""The benchmark's own data generator: R-MAT graphs with random labels.

A copy of the program's `repro.data.synthetic.rmat_graph` and its Table-1
sizes, kept here so that a change to the program cannot move the data it
is measured on.  It returns plain arrays; the harness hands them to the
program's `build_graph`, and the reference builds its own adjacency from
the same arrays.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import numpy as np

# FLEXIS (arXiv:2404.01585) Table 1: vertices, undirected edges, labels.
TABLE1: Dict[str, Dict[str, int]] = {
    "gnutella": dict(n=6301, m=20777, n_labels=5),
    "epinions": dict(n=75879, m=508837, n_labels=5),
    "slashdot": dict(n=82168, m=948464, n_labels=5),
    "wiki-vote": dict(n=7115, m=103689, n_labels=5),
    "mico": dict(n=100000, m=1080298, n_labels=29),
}


class Graph(NamedTuple):
    n: int
    edges: np.ndarray      # (m, 2) int64, directed as drawn; no self-loops
    labels: np.ndarray     # (n,) int32
    n_labels: int


def rmat(n: int, m: int, *, n_labels: int, seed: int, a: float = 0.57,
         b: float = 0.19, c: float = 0.19) -> Graph:
    """R-MAT (Chakrabarti et al.) edges with Graph500's Kronecker
    parameters and uniform labels; draws exactly as the program's
    generator does, so the same seed gives the same graph."""
    rng = np.random.default_rng(seed)
    scale = max(1, int(np.ceil(np.log2(max(n, 2)))))
    # oversample to survive self-loop/duplicate removal
    m_gen = int(m * 1.3) + 16
    src = np.zeros(m_gen, dtype=np.int64)
    dst = np.zeros(m_gen, dtype=np.int64)
    for level in range(scale):
        r = rng.random(m_gen)
        quad_b = (r >= a) & (r < a + b)
        quad_c = (r >= a + b) & (r < a + b + c)
        quad_d = r >= a + b + c
        bit = 1 << level
        src += bit * (quad_c | quad_d)
        dst += bit * (quad_b | quad_d)
    keep = (src < n) & (dst < n) & (src != dst)
    src, dst = src[keep], dst[keep]
    keys = np.unique(src * n + dst)[:m]
    src, dst = keys // n, keys % n
    labels = rng.integers(0, n_labels, n).astype(np.int32)
    return Graph(n, np.stack([src, dst], axis=1), labels, n_labels)


def scaled(name: str, scale: float) -> Dict[str, int]:
    """A Table-1 row at ``scale`` of its published size, as the program's
    ``paper_dataset`` sizes it."""
    sizes = TABLE1[name]
    return dict(n=max(16, int(sizes["n"] * scale)),
                m=max(32, int(sizes["m"] * scale)), n_labels=sizes["n_labels"])


def renumbered(g: Graph, seed: int, window: int) -> Graph:
    """``g`` with its vertex ids shuffled by ``seed`` inside each run of
    ``window`` consecutive ids; labels move with their vertices.

    Every run of ids keeps its vertices, so R-MAT's locality (hubs at low
    ids) and the work of each run stay as drawn; only the order changes.
    """
    rng = np.random.default_rng(seed)
    new_id = np.concatenate([lo + rng.permutation(min(window, g.n - lo))
                             for lo in range(0, g.n, window)])
    labels = np.empty(g.n, np.int32)
    labels[new_id] = g.labels
    return Graph(g.n, new_id[g.edges], labels, g.n_labels)


def config_graph(config: dict, seed: int) -> Graph:
    """The graph of a configuration file for one ``--seed``.

    The R-MAT draw comes from the file's ``structure_seed``, so every seed
    gives the same shapes and the same work (and the program's compiled
    steps fit every seed); ``seed`` renumbers the vertices inside runs of
    ``renumber_window`` ids.
    """
    gen = config["generator"]
    g = rmat(config["vertices"], config["edges"], n_labels=config["labels"],
             seed=config["structure_seed"], a=gen["a"], b=gen["b"], c=gen["c"])
    return renumbered(g, seed, config["renumber_window"])
