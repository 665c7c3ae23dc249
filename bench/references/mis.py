"""The reference's rules for the mIS metric (FLEXIS).

A rule file ``bench/references/<metric>.py`` gives what the answers of one
metric are held to (`bench.reference.rule` finds it by the metric's name):

* ``tau(sigma, lam, k)``: the threshold a k-vertex pattern must reach;
* ``searchable(k, tau, n)``: whether a query searches a k-vertex candidate
  at all, on a graph of ``n`` vertices;
* ``support(g, pat, tau, **control)``: ``(support, root blocks walked)`` of
  one pattern on a `bench.reference.RefGraph`;
* ``same(got, want)``: whether a reported support is the reference's.

For mIS the support is the size of a greedy maximal set of vertex-disjoint
embeddings, taken in the priority order `bench.reference` documents and
stopped once it reaches τ.  A different order gives a different maximal
set, and can change the frequent set, so the order is part of the answer.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple


def tau(sigma: int, lam: float, k: int) -> int:
    """FLEXIS Eq. (1): τ = ⌊σ(1 − 1/k)λ + σ/k⌋, at least 1."""
    k = max(k, 1)
    return max(1, math.floor(sigma * (1.0 - 1.0 / k) * lam + sigma / k))


def searchable(k: int, tau: int, n: int) -> bool:
    """FLEXIS §3.1.2's vertex bound: a frequent k-vertex pattern needs k·τ
    distinct data vertices."""
    return k * tau <= n


def support(g, pat, tau: int, *, width: Optional[int] = None) -> Tuple[int, int]:
    """(greedy mIS support stopped at τ, root blocks walked).

    ``width`` folds vertex ids into a used-vertex set of that many
    slots: the control, which breaks disjointness on collisions.
    """
    plan = g.plan(*pat)
    width = g.n if width is None else width
    used = bytearray(width)
    count = 0
    walked = 0
    for b in g.schedule:
        walked += 1
        rows = g.block_rows(pat, plan, int(b))
        if rows.size:
            for row in (rows % width).tolist():
                if count >= tau:
                    break
                if not any(used[x] for x in row):
                    for x in row:
                        used[x] = 1
                    count += 1
        if count >= tau:
            break
    return count, walked


def same(got, want) -> bool:
    """Supports are counts: exact."""
    return got == want
