"""Shared fixtures/strategies. NOTE: no XLA_FLAGS here — tests see 1 device."""
import gc

import jax
import numpy as np
import pytest

try:  # real hypothesis when installed (CI); frozen containers use the shim
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    from repro.testing import hypothesis_fallback

    hypothesis_fallback.install()
from hypothesis import strategies as st

from repro.core import Pattern, build_graph


# Each XLA:CPU executable holds a few memory mappings for as long as JAX
# caches it.  A test worker that compiles thousands of programs reaches the
# kernel's vm.max_map_count (65530 by default) and dies inside the compiler
# with a segfault or an abort; one hypothesis test can add ~11,000.
_MAPPINGS_BEFORE_RELEASE = 30000


def _mapping_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:  # no procfs: nothing to watch
        return 0


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """Drop every compiled program once the process holds too many
    mappings (tests that follow recompile what they need)."""
    yield
    if _mapping_count() > _MAPPINGS_BEFORE_RELEASE:
        jax.clear_caches()
        gc.collect()


# ---------------------------------------------------------------------------
# hypothesis strategies
# ---------------------------------------------------------------------------

@st.composite
def patterns(draw, min_k=2, max_k=5, n_labels=3, connected=True):
    """Random connected directed labeled pattern."""
    k = draw(st.integers(min_k, max_k))
    labels = draw(st.lists(st.integers(0, n_labels - 1), min_size=k, max_size=k))
    adj = np.zeros((k, k), dtype=bool)
    # spanning structure first (guarantees connectivity)
    for v in range(1, k):
        u = draw(st.integers(0, v - 1))
        if draw(st.booleans()):
            adj[u, v] = True
        else:
            adj[v, u] = True
    # extra edges
    for i in range(k):
        for j in range(k):
            if i != j and not adj[i, j] and draw(st.integers(0, 3)) == 0:
                adj[i, j] = True
    return Pattern(adj, np.array(labels, np.int32))


@st.composite
def data_graphs(draw, min_n=4, max_n=24, n_labels=3, p_edge_denom=4):
    """Random directed labeled data graph."""
    n = draw(st.integers(min_n, max_n))
    labels = draw(st.lists(st.integers(0, n_labels - 1), min_size=n, max_size=n))
    edges = []
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    m = rng.random((n, n)) < (1.0 / p_edge_denom)
    np.fill_diagonal(m, False)
    src, dst = np.nonzero(m)
    edges = np.stack([src, dst], axis=1)
    return build_graph(n, edges, labels, n_labels=n_labels)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
