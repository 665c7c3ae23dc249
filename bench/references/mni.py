"""The reference's rules for the MNI metric (GraMi, T-FSM).

Its four functions are those every rule file has (see
``bench/references/mis.py``).

MNI, the minimum-image-based support, is the least number of distinct data
vertices that one pattern vertex is mapped to, over the pattern's vertices.
The threshold is σ itself at every size, and no candidate is pruned by the
vertex bound: MNI is not an independent-set count, so k·τ distinct vertices
are not needed.  The images grow one root block at a time, in the block
order `bench.reference` documents, and the count stops after the first
block where it reaches τ: it may pass τ inside that block.  A count over a
whole block does not depend on the order of its rows.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def tau(sigma: int, lam: float, k: int) -> int:
    """σ, for every k; λ has no part in MNI."""
    return sigma


def searchable(k: int, tau: int, n: int) -> bool:
    """Every candidate is searched."""
    return True


def support(g, pat, tau: int, *, width: Optional[int] = None) -> Tuple[int, int]:
    """(least image count over the pattern's vertices, root blocks walked).

    Column ``i`` of a block's rows holds the images of one pattern vertex
    (the plan's ``i``-th), so the least count over the columns is the least
    over the vertices.  ``width`` folds vertex ids into an image table of
    that many slots: the control, which merges images on collisions.
    """
    plan = g.plan(*pat)
    width = g.n if width is None else width
    k = len(pat[1])
    images = np.zeros((k, width), bool)
    value = 0
    walked = 0
    for b in g.schedule:
        walked += 1
        rows = g.block_rows(pat, plan, int(b))
        if rows.size:
            images[np.arange(k), rows % width] = True
            value = int(images.sum(axis=1).min())
        if value >= tau:
            break
    return value, walked


def same(got, want) -> bool:
    """Supports are counts: exact."""
    return got == want
