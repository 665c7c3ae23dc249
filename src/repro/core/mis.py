"""mIS — the paper's Maximal-Independent-Set support metric, on device.

Two interchangeable implementations compute the *identical* set — the
lexicographically-first maximal independent set in embedding-row order —
when run to completion (τ = ∞).  Under early exit (τ reached mid-selection)
each returns *some* valid independent set of size τ, which may differ
between the two — exactly the paper's contract, where mIS is any maximal
set (Fig. 3c vs 3d) and early termination returns any τ-subset (§3.1.1):

  * ``mis_greedy_update`` — a sequential loop over the valid embedding rows
    carrying a packed uint32 used-vertex bitmap (mirrors the paper's shared
    bitmap across VF3 states).  A Pallas kernel version keeps the bitmap
    VMEM-resident (see ``repro.kernels.mis_bitmap``).

  * ``mis_luby_update`` — parallel rounds: an embedding is accepted in a
    round iff its priority (row index) is the minimum over every data vertex
    it touches.  With unique priorities this is exactly the greedy result
    (lexicographically-first MIS), in O(log) expected rounds, and each round
    reduces to one dense per-vertex ``min`` — which becomes a single
    ``all-reduce(min)`` when embeddings are sharded across devices
    (``core/distributed.py``).  This equivalence is property-tested.

The bitmap/count state persists across root blocks so the host loop can
early-terminate as soon as count ≥ τ (the paper's key speed lever).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "bitmap_init",
    "mis_greedy_update",
    "mis_luby_update",
    "touches_used",
]


def bitmap_words(n: int) -> int:
    return (n + 31) // 32


def bitmap_init(n: int) -> jnp.ndarray:
    """Packed used-vertex bitmap for a data graph of n vertices."""
    return jnp.zeros(bitmap_words(n), dtype=jnp.uint32)


def touches_used(bitmap: jnp.ndarray, verts: jnp.ndarray) -> jnp.ndarray:
    """For (rows, k) vertex ids: does any vertex have its bit set?"""
    words = (verts >> 5).astype(jnp.int32)
    bits = (jnp.uint32(1) << (verts & 31).astype(jnp.uint32))
    return jnp.any((bitmap[words] & bits) != 0, axis=-1)


@functools.partial(jax.jit, static_argnames=("k",))
def mis_greedy_update(
    bitmap: jnp.ndarray,
    count: jnp.ndarray,
    emb: jnp.ndarray,
    n_valid: jnp.ndarray,
    tau: jnp.ndarray,
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy maximal-independent-set selection, row order = priority.

    emb: (cap, K) int32 with the first `k` columns valid; vertices within a
    row must be distinct (guaranteed by the matcher's injectivity check —
    the scatter-add-as-OR trick relies on it). Returns updated
    (bitmap, count).
    """
    # the scan stops at the last valid row, or once τ is reached: no later
    # row can be taken, so the rows it skips would change nothing
    n_rows = jnp.minimum(n_valid, emb.shape[0]).astype(jnp.int32)

    def cond(carry):
        r, _, cnt = carry
        return (r < n_rows) & (cnt < tau)

    def body(carry):
        r, bm, cnt = carry
        vs = jnp.clip(emb[r, :k], 0, None)
        words = (vs >> 5).astype(jnp.int32)
        bits = jnp.uint32(1) << (vs & 31).astype(jnp.uint32)
        take = jnp.all((bm[words] & bits) == 0)
        # distinct vertices ⇒ distinct (word, bit) pairs; under `take` none of
        # the bits are set, so scatter-add of the bit values is exactly OR.
        bm = bm.at[words].add(jnp.where(take, bits, jnp.uint32(0)))
        return r + 1, bm, cnt + take.astype(jnp.int32)

    _, bitmap, count = jax.lax.while_loop(
        cond, body, (jnp.int32(0), bitmap, count))
    return bitmap, count


@functools.partial(jax.jit, static_argnames=("k", "n"))
def mis_luby_update(
    bitmap: jnp.ndarray,
    count: jnp.ndarray,
    emb: jnp.ndarray,
    n_valid: jnp.ndarray,
    tau: jnp.ndarray,
    k: int,
    n: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Parallel-rounds mIS (priority = row index). Same result as greedy.

    Each round: per-data-vertex min of alive embedding priorities
    (`segment`-style scatter-min into a dense (n,) array), then an embedding
    wins iff it holds the min on all k of its vertices.  Winners' vertices
    are retired into the bitmap.  The τ cut keeps the lowest-priority winners
    of the final round, so exactly τ embeddings are counted; under early exit
    the *set* may differ from the greedy scan's (see module docstring) but
    both are valid independent τ-sets.
    """
    cap = emb.shape[0]
    rowid = jnp.arange(cap, dtype=jnp.int32)
    vs = jnp.clip(emb[:, :k], 0, None)
    valid = rowid < n_valid

    def touches(bm):
        return touches_used(bm, vs)

    state0 = (bitmap, count, valid & ~touches(bitmap))

    def cond(state):
        bm, cnt, alive = state
        return jnp.any(alive) & (cnt < tau)

    def body(state):
        bm, cnt, alive = state
        INF = jnp.int32(cap)
        prio = jnp.where(alive, rowid, INF)
        vmin = jnp.full((n,), INF, dtype=jnp.int32)
        vmin = vmin.at[vs].min(prio[:, None])
        win = alive & jnp.all(vmin[vs] == prio[:, None], axis=1)
        # enforce τ in priority order: only the lowest (τ − cnt) winners count
        win_rank = jnp.cumsum(win.astype(jnp.int32)) - 1
        win &= win_rank < (tau - cnt)
        words = (vs >> 5).astype(jnp.int32)
        bits = jnp.uint32(1) << (vs & 31).astype(jnp.uint32)
        bm = bm.at[words].add(jnp.where(win[:, None], bits, jnp.uint32(0)))
        cnt = cnt + win.sum().astype(jnp.int32)
        alive = alive & ~win & ~touches_used(bm, vs)
        return bm, cnt, alive

    bitmap, count, _ = jax.lax.while_loop(cond, body, state0)
    return bitmap, count
