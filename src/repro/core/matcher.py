"""Data-parallel subgraph matcher — the TPU-native replacement for VF3-Light.

VF3-Light enumerates embeddings with DFS backtracking; here a *frontier
table* of partial embeddings (a dense ``(cap, k)`` int32 array) advances one
pattern vertex per level, in lockstep:

  level i:  anchors  = emb[:, anchor_pos[i]]
            cands    = chunked gather of the anchors' CSR adjacency rows
            mask     = label ∧ degree ∧ injectivity ∧ edge-checks
            emb'     = cumsum-compaction of the masked (rows × chunk) grid,
                       walked in row tiles over the valid rows only

Edge-existence checks run a fixed-depth branchless binary search over each
CSR row (no hash tables, no int64 keys — int32 only, TPU-friendly).

Everything is static-shaped; overflow beyond ``cap`` is *counted* and
surfaced, never silently dropped.  The host drives root *blocks* through
``match_block`` and owns early termination (τ reached) — device code is one
jit-compiled function per pattern size k, reused across all patterns of that
size (plans are data, not static arguments).  Because plans are data,
``match_block`` is also ``vmap``-able over a leading pattern axis — the
batched data plane (``core/batched.py``) runs a whole same-k candidate
level as one program, and ``core/distributed.py`` composes that axis with
root sharding under ``shard_map``.

Two expansion planes implement the level step (``MatchConfig.expansion``):

  * ``"xla"`` — the reference pipeline below (`_expand_level`): one XLA op
    chain per chunk, with the candidate grid and frontier tables spilling
    to HBM between stages.  Optionally two-phase (cheap filters → compact
    → bisect survivors only).
  * ``"pallas"`` — the fused kernel (``repro.kernels.frontier_expand``):
    the whole level runs as one Pallas program with the frontier tile and
    CSR arrays VMEM-resident across chunks.  Bit-identical to the
    single-phase XLA pipeline (survivor order included); under ``vmap``
    the pattern axis becomes a kernel-grid dimension, so a batched level
    is still one launch.  It runs interpreted off-TPU and is refused on a
    TPU, where Mosaic cannot lower it yet (``docs/kernels.md``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import DataGraph, DeviceGraph
from .plan import PatternPlan

__all__ = ["MatchConfig", "match_block", "batch_checks", "edge_exists",
           "device_graph_tuple", "transient_match_bytes"]


# Register the graph/plan dataclasses as pytrees so they pass through jit
# without recompilation per pattern.
def _dg_flatten(g: DeviceGraph):
    return (
        (g.labels, g.out_indptr, g.out_indices, g.in_indptr, g.in_indices),
        g.n,
    )


def _dg_unflatten(n, children):
    return DeviceGraph(n, *children)


jax.tree_util.register_pytree_node(DeviceGraph, _dg_flatten, _dg_unflatten)


def _plan_flatten(p: PatternPlan):
    arrays = (
        p.root_label,
        p.root_min_out,
        p.root_min_in,
        p.anchor_pos,
        p.anchor_out,
        p.cand_label,
        p.min_out,
        p.min_in,
        p.check_out,
        p.check_in,
    )
    return arrays, (p.k, p.order)


def _plan_unflatten(aux, children):
    k, order = aux
    return PatternPlan(k, *children, order=order)


jax.tree_util.register_pytree_node(PatternPlan, _plan_flatten, _plan_unflatten)


@dataclasses.dataclass(frozen=True)
class MatchConfig:
    """Static matcher geometry (one jit cache entry per distinct config + k).

    Hashable & frozen — it is a ``static_argnames`` entry of ``match_block``,
    so every distinct config value is a separate compiled program.
    """

    cap: int = 8192          # frontier capacity (embeddings per level)
    root_block: int = 4096   # roots processed per host iteration
    chunk: int = 64          # neighbors gathered per expansion chunk
    max_chunks: int = 8      # ceil(max_degree / chunk)
    bisect_iters: int = 12   # ceil(log2(max_degree + 1))
    # two-phase expansion (EXPERIMENTS.md §Perf, flexis-mining cell): run the
    # cheap filters (label/degree/injectivity) on the full (cap × chunk)
    # grid, compact survivors, and run the edge-existence bisection only on
    # the compacted lanes — label selectivity pays for the extra compaction.
    # Only meaningful on the "xla" plane; the fused kernel keeps the grid
    # VMEM-resident, which is what two-phase's HBM-traffic cut approximates.
    two_phase: bool = False
    # expansion plane: "xla" = per-chunk op pipeline (reference), "pallas" =
    # fused per-level kernel (repro.kernels.frontier_expand), bit-identical
    # to the single-phase xla pipeline.  Its mode follows the platform
    # (`frontier_expand.ops.interpret_mode`): building a "pallas" config on
    # a TPU raises, since the kernel does not lower there yet.
    expansion: str = "xla"

    def __post_init__(self):
        if self.expansion not in ("xla", "pallas"):
            raise ValueError('expansion must be "xla" or "pallas"')
        # two_phase is an xla-plane knob; the fused kernel is single-phase by
        # construction.  Normalize so a pallas config never *claims* two-phase
        # semantics (truncation content under overflow differs between the
        # two-phase pipeline and the single-phase planes — always flagged via
        # `overflowed`, but configs should say what they run).
        if self.expansion == "pallas" and self.two_phase:
            object.__setattr__(self, "two_phase", False)
        if self.expansion == "pallas":
            from repro.kernels.frontier_expand.ops import interpret_mode

            interpret_mode()  # raises on a TPU

    @classmethod
    def for_graph(cls, g: DataGraph, *, cap: int = 8192, root_block: int = 4096,
                  chunk: int = 64, expansion: str = "xla") -> "MatchConfig":
        """Right-size the geometry to the graph: the frontier capacity and
        root blocks never usefully exceed the graph scale, and the chunk
        width never usefully exceeds the max degree."""
        max_deg = max(g.max_out_degree, g.max_in_degree, 1)
        chunk = int(min(chunk, 1 << int(np.ceil(np.log2(max_deg + 1)))))
        root_block = int(min(root_block, max(128, 1 << int(np.ceil(np.log2(g.n))))))
        cap = int(min(cap, max(1024, 1 << int(np.ceil(np.log2(g.n_edges + 1))))))
        return cls(
            cap=cap,
            root_block=root_block,
            chunk=chunk,
            max_chunks=max(1, -(-max_deg // chunk)),
            bisect_iters=max(2, int(np.ceil(np.log2(max_deg + 1))) + 1),
            # measured 8–9× matcher speedup at identical results on both
            # label-rich and label-poor graphs (EXPERIMENTS.md §Perf cell 3)
            two_phase=True,
            expansion=expansion,
        )


def transient_match_bytes(cfg: MatchConfig, k: int) -> int:
    """Transient device footprint of one match step for ONE pattern (bytes).

    Counts the two (cap, k) int32 frontier tables plus one
    candidate-expansion grid with its per-lane intermediates (≈ k + 8
    int32 each: candidate rows, mask/cumsum/dest lanes): a (row tile ×
    chunk) tile on the "xla" plane, the whole (cap × chunk) grid on the
    "pallas" plane.

    This is a *per-pattern* number: the batched plane runs P patterns per
    program (leading pattern axis), so its peak transient footprint is
    ``bucket_size(P) · transient_match_bytes(cfg, k)`` — exactly how
    ``core/batched.py`` accounts it, keeping sequential and batched
    ``peak_device_bytes`` telemetry consistent.  On the "pallas" expansion
    plane these buffers live in VMEM scratch for the duration of a level
    instead of spilling to HBM between pipeline stages.
    """
    emb = cfg.cap * k * 4
    rows = cfg.cap if cfg.expansion == "pallas" else _row_tile(cfg)
    return emb * 2 + rows * cfg.chunk * (k + 8) * 4


def edge_exists(indptr, indices, u, v, n_iters: int):
    """Branchless bounded binary search: is v in sorted indices[indptr[u]:indptr[u+1]]?

    indptr: (n+1,) int32 CSR row pointers; indices: (E,) int32 sorted within
    each row.  u, v: int32 arrays (broadcast-compatible); entries must be
    pre-clipped to [0, n).  n_iters must be ≥ ceil(log2(max_degree + 1)).
    Returns a bool array of the broadcast shape.  Pure dataflow (no host
    control), so it runs unchanged inside jit, vmap, shard_map, and the
    Pallas kernel body.
    """
    lo = indptr[u].astype(jnp.int32)
    hi = (indptr[u + 1]).astype(jnp.int32)
    # invariant: answer position (if any) in [lo, hi)
    for _ in range(n_iters):
        mid = (lo + hi) >> 1
        mid_safe = jnp.clip(mid, 0, indices.shape[0] - 1)
        go_right = (indices[mid_safe] < v) & (lo < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right | (lo >= hi), hi, mid)
    lo_safe = jnp.clip(lo, 0, indices.shape[0] - 1)
    found = (lo < indptr[u + 1].astype(jnp.int32)) & (indices[lo_safe] == v)
    return found


def device_graph_tuple(g: DataGraph) -> DeviceGraph:
    """Upload a host `DataGraph` as the int32 jnp mirror the matcher reads.

    Returns a `DeviceGraph` pytree: labels (n,), out/in_indptr (n+1,),
    out/in_indices (E,) — all int32; edgeless graphs get 1-element sentinel
    index arrays so gathers stay well-formed (see `DeviceGraph.from_host`).
    """
    return DeviceGraph.from_host(g)


def _degrees(indptr, verts):
    return (indptr[verts + 1] - indptr[verts]).astype(jnp.int32)


def _init_roots(g: DeviceGraph, plan: PatternPlan, block_start, cfg: MatchConfig):
    """Root frontier for one block: vertices in [block_start, block_start+R)
    matching the root's label + degree filters, compacted into (cap, k)."""
    R, cap, k = cfg.root_block, cfg.cap, plan.k
    verts = block_start + jnp.arange(R, dtype=jnp.int32)
    in_range = verts < g.n
    safe = jnp.clip(verts, 0, g.n - 1)
    ok = (
        in_range
        & (g.labels[safe] == plan.root_label)
        & (_degrees(g.out_indptr, safe) >= plan.root_min_out)
        & (_degrees(g.in_indptr, safe) >= plan.root_min_in)
    )
    pos = jnp.cumsum(ok) - 1
    dest = jnp.where(ok & (pos < cap), pos, cap)
    emb = jnp.full((cap + 1, k), -1, dtype=jnp.int32)
    emb = emb.at[dest, 0].set(safe, mode="drop")
    count = jnp.minimum(ok.sum(), cap).astype(jnp.int32)
    return emb[:cap], count


# Lanes of one expansion tile (rows × chunk): small enough that the chunks
# only a few hub rows reach stay cheap, large enough that a full frontier
# takes few tiles.
TILE_LANES = 2048


# Scatter index of the lanes that keep nothing: past the end of every
# target, so the scatter drops them instead of writing them all to one
# trash row.
_DROP = np.iinfo(np.int32).max


def _row_tile(cfg: MatchConfig) -> int:
    """Frontier rows per expansion tile (static per geometry)."""
    return max(1, min(cfg.cap, TILE_LANES // cfg.chunk))


def _expand_level(g: DeviceGraph, plan: PatternPlan, emb, count, level: int,
                  cfg: MatchConfig, checks=None):
    """Extend every partial embedding by pattern-order vertex `level`.

    emb: (cap, k) int32 frontier (columns ≥ level are -1); count: () int32
    valid rows.  Returns (out_emb (cap, k) int32, out_count () int32,
    found () int32, overflowed () bool); survivors are packed in
    (chunk, row, position) order — the order the greedy-mIS metric consumes.
    Dispatches to the fused Pallas kernel when cfg.expansion == "pallas"
    (bit-identical to the single-phase pipeline below).

    The work follows the frontier, not its capacity: chunk c walks, in
    tiles of ``_row_tile(cfg)`` rows, only the rows whose degree reaches into
    it (compacted in row order), and the chunk loop stops at the widest
    valid row's degree.  Rows past ``count`` and chunks past a row's degree
    gather nothing, so skipping them changes no result.

    ``checks`` = (out, in) (k, k) bool: the edge checks to compute at all
    (default: the plan's own).  A check nobody asks for passes every lane,
    so skipping its bisect changes no result; a vmapped caller passes the
    OR over its batch, unbatched, and the skip stays a real branch.
    """
    if cfg.expansion == "pallas":
        from repro.kernels.frontier_expand.ops import frontier_expand_level

        return frontier_expand_level(g, plan, emb, count, level, cfg)
    cap, C, k = cfg.cap, cfg.chunk, plan.k
    T = _row_tile(cfg)
    i = level  # python int (static): column being filled
    # rows that can be valid: the root frontier holds at most one block
    n_rows = min(cap, cfg.root_block) if i == 1 else cap
    n_slots = -(-n_rows // T) * T    # active-row list padded to whole tiles
    n_idx = g.out_indices.shape[0]
    # concatenated adjacency so out/in selection is an offset, not two gathers
    indices_cat = jnp.concatenate([g.out_indices, g.in_indices])

    anchor_pos = plan.anchor_pos[i]
    use_out = plan.anchor_out[i]
    anchors = jnp.take_along_axis(emb, jnp.full((cap, 1), anchor_pos, jnp.int32), axis=1)[:, 0]
    anchors_safe = jnp.clip(anchors, 0, g.n - 1)
    out_start = g.out_indptr[anchors_safe].astype(jnp.int32)
    in_start = g.in_indptr[anchors_safe].astype(jnp.int32)
    start = jnp.where(use_out, out_start, in_start + n_idx)
    row_valid = jnp.arange(cap, dtype=jnp.int32) < count
    deg = jnp.where(
        row_valid,
        jnp.where(use_out, _degrees(g.out_indptr, anchors_safe),
                  _degrees(g.in_indptr, anchors_safe)),
        0)
    row_ids = jnp.arange(n_rows, dtype=jnp.int32)
    # one gather per lane and filter, not two
    out_deg = jnp.diff(g.out_indptr).astype(jnp.int32)
    in_deg = jnp.diff(g.in_indptr).astype(jnp.int32)

    def _cheap_mask(emb_t, cand, cand_safe, in_deg_range):
        mask = in_deg_range
        mask &= g.labels[cand_safe] == plan.cand_label[i]
        mask &= out_deg[cand_safe] >= plan.min_out[i]
        mask &= in_deg[cand_safe] >= plan.min_in[i]
        for j in range(i):
            mask &= cand != emb_t[:, j][:, None]  # injectivity
        return mask

    need_out, need_in = ((plan.check_out, plan.check_in) if checks is None
                         else checks)

    def _edge_checks(cand_safe, prev_rows):
        """prev_rows: (..., k) prefix columns aligned with cand_safe."""
        ok = jnp.ones(cand_safe.shape, bool)
        for j in range(i):  # static unroll over prefix
            prev_safe = jnp.clip(prev_rows[..., j], 0, g.n - 1)
            for need, check, u, v in (
                    (need_out[i, j], plan.check_out[i, j], cand_safe,
                     prev_safe),
                    (need_in[i, j], plan.check_in[i, j], prev_safe,
                     cand_safe)):
                hit = jax.lax.cond(
                    need,
                    lambda u, v: edge_exists(g.out_indptr, g.out_indices,
                                             u, v, cfg.bisect_iters),
                    lambda u, v: jnp.ones(
                        jnp.broadcast_shapes(u.shape, v.shape), bool), u, v)
                ok &= jnp.where(check, hit, True)
        return ok

    src_row_tile = jnp.arange(T * C, dtype=jnp.int32) // C
    # two-phase survivor buffer: a tile never yields more than T·C cheap
    # survivors, and the chunk never keeps more than cap of them
    n_buf = min(T * C, cap)

    def tile_body(t, c, act, n_active, carry):
        out_emb, out_count, found, n_phase1 = carry
        rows_t = jax.lax.dynamic_slice(act, (t * T,), (T,))
        emb_t = emb[rows_t]
        start_t = start[rows_t]
        deg_t = jnp.where(t * T + jnp.arange(T, dtype=jnp.int32) < n_active,
                          deg[rows_t], 0)
        off = c * C + jnp.arange(C, dtype=jnp.int32)[None, :]          # (1, C)
        idx = start_t[:, None] + off                                   # (T, C)
        in_deg_range = off < deg_t[:, None]
        cand = indices_cat[jnp.clip(idx, 0, indices_cat.shape[0] - 1)]  # (T, C)
        cand_safe = jnp.clip(cand, 0, g.n - 1)
        mask = _cheap_mask(emb_t, cand, cand_safe, in_deg_range)

        if cfg.two_phase and i > 0:
            # compact cheap-filter survivors, bisect only those lanes; the
            # chunk keeps its first cap survivors in (row, position) order
            flat = mask.reshape(-1)
            pos1 = jnp.cumsum(flat).astype(jnp.int32) - 1
            keep = flat & (n_phase1 + pos1 < cap)
            dest1 = jnp.where(keep, pos1, _DROP)
            cand_buf = jnp.zeros((n_buf + 1,), jnp.int32).at[dest1].set(
                cand_safe.reshape(-1), mode="drop")[:n_buf]
            row_buf = jnp.zeros((n_buf + 1,), jnp.int32).at[dest1].set(
                src_row_tile, mode="drop")[:n_buf]
            n_mid = keep.sum().astype(jnp.int32)
            mid_valid = jnp.arange(n_buf, dtype=jnp.int32) < n_mid
            prev_rows = emb_t[row_buf]                                  # (n_buf, k)
            ok = mid_valid & _edge_checks(cand_buf, prev_rows)
            n_new = ok.sum().astype(jnp.int32)
            pos = jnp.cumsum(ok).astype(jnp.int32) - 1 + out_count
            dest = jnp.where(ok & (pos < cap), pos, _DROP)
            rows = prev_rows.at[:, i].set(cand_buf)
            out_emb = out_emb.at[dest].set(rows, mode="drop")
            return (out_emb, jnp.minimum(out_count + n_new, cap),
                    found + n_new, n_phase1 + flat.sum().astype(jnp.int32))

        mask &= _edge_checks(cand_safe, emb_t[:, None, :])
        flat_mask = mask.reshape(-1)
        n_new = flat_mask.sum().astype(jnp.int32)
        pos = jnp.cumsum(flat_mask).astype(jnp.int32) - 1 + out_count
        dest = jnp.where(flat_mask & (pos < cap), pos, _DROP)
        rows = emb_t[src_row_tile].at[:, i].set(cand.reshape(-1))
        out_emb = out_emb.at[dest].set(rows, mode="drop")
        return (out_emb, jnp.minimum(out_count + n_new, cap),
                found + n_new, n_phase1)

    n_chunks = jnp.minimum((jnp.max(deg) + C - 1) // C, cfg.max_chunks)

    def chunk_body(c, carry):
        out_emb, out_count, found, ovf = carry
        # the rows whose degree reaches into chunk c, in row order
        active = deg[:n_rows] > c * C
        n_active = active.sum().astype(jnp.int32)
        slot = jnp.cumsum(active).astype(jnp.int32) - 1
        act = jnp.zeros((n_slots + 1,), jnp.int32).at[
            jnp.where(active, slot, _DROP)].set(row_ids, mode="drop")
        out_emb, out_count, found, n_phase1 = jax.lax.fori_loop(
            0, (n_active + T - 1) // T,
            lambda t, cr: tile_body(t, c, act, n_active, cr),
            (out_emb, out_count, found, jnp.int32(0)))
        # phase-1 drop: results may be incomplete
        return out_emb, out_count, found, ovf | (n_phase1 > cap)

    out_emb0 = jnp.full((cap + 1, k), -1, dtype=jnp.int32)
    out_emb, out_count, found, ovf = jax.lax.fori_loop(
        0, n_chunks, chunk_body,
        (out_emb0, jnp.int32(0), jnp.int32(0), jnp.bool_(False)),
    )
    return out_emb[:cap], out_count, found, ovf


def batch_checks(plans: PatternPlan):
    """The edge checks any pattern of a stacked plan batch needs:
    (out, in) (k, k) bool, for `match_block`'s ``checks``."""
    return (jnp.any(plans.check_out, axis=0), jnp.any(plans.check_in, axis=0))


@functools.partial(jax.jit, static_argnames=("cfg",))
def match_block(g: DeviceGraph, plan: PatternPlan, block_start, cfg: MatchConfig,
                checks=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
                           jnp.ndarray]:
    """Enumerate embeddings rooted in one vertex block.

    Args:
      g:    DeviceGraph pytree (int32 arrays; see `device_graph_tuple`).
      plan: PatternPlan pytree — *data*, so one compiled program serves all
            patterns of size k.  A leading pattern axis on every plan field
            (from `plan.stack_plans`) makes this function `vmap`-able; with
            cfg.expansion == "pallas" that axis becomes a kernel-grid
            dimension rather than a per-pattern kernel re-entry.
      block_start: () int32 — first root vertex of this block.
      cfg:  static MatchConfig (hashable; keys the jit cache with k).
      checks: optional (out, in) (k, k) bool — the edge checks any pattern
            of a vmapped batch needs (`batch_checks`), passed unbatched so
            that a check no pattern needs is skipped; None = the plan's own.

    Returns (emb, count, found, overflowed, peak):
      emb:    (cap, k) int32 — embeddings in pattern-order columns, row-major
              in (root, discovery) order (so row index = greedy priority);
              invalid rows are -1-filled.
      count:  () int32 — rows of `emb` that are valid (≤ cap).
      found:  () int32 — embeddings enumerated in the last level before
              capacity clipping.
      overflowed: () bool — some level produced more than `cap` rows (results
              are truncated, never silently wrong).
      peak:   () int32 — max frontier occupancy over all levels (root level
              included, post-clip, so ≤ cap).  This is the observed-occupancy
              signal the execution planner's per-level ``cap`` right-sizing
              consumes (`core/planner.py`); when `overflowed` is set the true
              need exceeded `cap` and `peak` is only a lower bound.
    """
    emb, count = _init_roots(g, plan, block_start, cfg)
    found = count
    peak = count
    overflowed = jnp.bool_(False)
    for level in range(1, plan.k):
        emb, count, lvl_found, lvl_ovf = _expand_level(
            g, plan, emb, count, level, cfg, checks)
        overflowed |= lvl_ovf | (lvl_found > cfg.cap)
        found = lvl_found
        peak = jnp.maximum(peak, count)
    return emb, count, found, overflowed, peak
