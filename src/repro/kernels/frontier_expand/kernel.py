"""Fused frontier expansion — one Pallas program per match level.

The XLA expansion pipeline (``core/matcher._expand_level``) lowers to a
chain of separate HLOs per chunk — adjacency gather, cheap-filter mask,
edge-existence bisection, cumsum compaction — and the (cap × chunk)
candidate grid plus both frontier tables round-trip through HBM between
every stage.  This kernel runs the *whole level* as a single Pallas
program: the input frontier, the data-graph CSR arrays, and the output
frontier are pinned in VMEM for the duration of the call, the chunk loop
is a ``fori_loop`` inside the kernel, and compaction appends survivors
into a VMEM-resident output tile — zero HBM traffic between stages.

Semantics are the *single-phase* pipeline (``MatchConfig.two_phase=False``)
and are bit-identical to it, including the candidate ordering that the
greedy-mIS metric depends on: survivors are appended in (chunk, row,
position) order, exactly the order the XLA cumsum compaction produces.

Batched plane: the kernel is ``vmap``-able — JAX's Pallas batching rule
prepends the mapped pattern axis as a leading *grid* dimension, so a whole
same-k candidate level (``core/batched.py``) runs as one kernel launch
whose grid carries the pattern axis, instead of re-entering the kernel per
pattern.  The kernel body is grid-index-free, which keeps that transform
sound.

Lowering note: the body uses 1-D vector gathers (CSR rows, labels) and a
scatter-compaction.  Mosaic (jaxlib 0.9.0) refuses them for TPU v5e
("Only 2D gather is supported"), so the kernel runs only in interpret mode,
off-TPU, where it is property-tested against the XLA pipeline;
``ops.interpret_mode`` refuses it on a TPU and ``docs/kernels.md`` states
the rule.

VMEM budget: the graph CSR arrays plus two (cap, k) frontier tiles plus
the transient (cap·chunk, k) candidate rows must fit in VMEM (~16 MB/core)
— `frontier_expand_vmem_bytes` estimates the footprint, and
`frontier_expand` enforces it at trace time when lowering for hardware
(interpret=False), so oversized geometries fail with a right-sizing hint
instead of a Mosaic compile error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.matcher import edge_exists


# conservative per-core VMEM budget the hardware guard checks against
_VMEM_BUDGET_BYTES = 16 * 2**20


def frontier_expand_vmem_bytes(n: int, n_index_entries: int, cap: int,
                               chunk: int, k: int) -> int:
    """Rough VMEM-resident footprint of one fused-level call, in bytes.

    n_index_entries = len(out_indices) + len(in_indices) (2·E for a fully
    mirrored graph; 2 for the edgeless sentinels).  Counts the graph arrays
    (labels + two indptr + the concatenated ``indices_cat`` operand plus
    the out-prefix slice the bisection reads, ≈1.5× the stored index
    entries), the in/out frontier tiles, and the (cap·chunk) candidate grid
    with its (cap·chunk, k) expanded rows.  `frontier_expand` refuses
    geometries past ~16 MiB when lowering for hardware (interpret=False).
    """
    graph = (n + 2 * (n + 1) + 3 * max(n_index_entries, 2) // 2) * 4
    frontier = 2 * cap * k * 4
    grid = cap * chunk * (k + 4) * 4
    return graph + frontier + grid


def _frontier_kernel(emb_ref, count_ref, labels_ref, out_indptr_ref,
                     in_indptr_ref, indices_cat_ref,
                     anchor_pos_ref, use_out_ref, cand_label_ref,
                     min_out_ref, min_in_ref, check_out_ref, check_in_ref,
                     out_emb_ref, out_count_ref, found_ref, ovf_ref,
                     *, level: int, k: int, cap: int, chunk: int,
                     max_chunks: int, bisect_iters: int, n: int, n_out: int):
    i = level  # static: the pattern-order column being filled
    C = chunk

    # ---- load VMEM-resident operands once --------------------------------
    emb = emb_ref[...]                       # (cap, k) int32
    count = count_ref[0, 0]
    labels = labels_ref[...][:, 0]           # (n,)
    out_indptr = out_indptr_ref[...][:, 0]   # (n+1,)
    in_indptr = in_indptr_ref[...][:, 0]
    # one concatenated [out_indices ‖ in_indices] operand; the bisection's
    # out-CSR view is its static-length prefix (no duplicate VMEM copies)
    indices_cat = indices_cat_ref[...][:, 0]
    out_indices = indices_cat[:n_out]

    anchor_pos = anchor_pos_ref[0, 0]
    use_out = use_out_ref[0, 0] != 0
    cand_label = cand_label_ref[0, 0]
    min_out = min_out_ref[0, 0]
    min_in = min_in_ref[0, 0]

    # ---- per-row anchor state (computed once, reused by every chunk) -----
    # anchor_pos < i always (anchors live in the ordered prefix), so an
    # unrolled select over the prefix columns replaces a dynamic gather.
    anchors = emb[:, 0]
    for j in range(1, i):
        anchors = jnp.where(anchor_pos == j, emb[:, j], anchors)
    anchors_safe = jnp.clip(anchors, 0, n - 1)
    out_start = out_indptr[anchors_safe]
    in_start = in_indptr[anchors_safe]
    start = jnp.where(use_out, out_start, in_start + n_out)
    deg = jnp.where(
        use_out,
        out_indptr[anchors_safe + 1] - out_start,
        in_indptr[anchors_safe + 1] - in_start,
    )
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (cap, 1), 0)[:, 0]
    row_valid = row_ids < count

    def cheap_mask(cand, cand_safe, in_deg_range):
        m = row_valid[:, None] & in_deg_range
        m &= labels[cand_safe] == cand_label
        m &= (out_indptr[cand_safe + 1] - out_indptr[cand_safe]) >= min_out
        m &= (in_indptr[cand_safe + 1] - in_indptr[cand_safe]) >= min_in
        for j in range(i):  # injectivity against the prefix (static unroll)
            m &= cand != emb[:, j][:, None]
        return m

    def edge_checks(cand_safe):
        ok = jnp.ones(cand_safe.shape, bool)
        for j in range(i):
            prev_safe = jnp.clip(emb[:, j], 0, n - 1)[:, None]   # (cap, 1)
            ok_out = edge_exists(out_indptr, out_indices, cand_safe,
                                 prev_safe, bisect_iters)
            ok_in = edge_exists(out_indptr, out_indices, prev_safe,
                                cand_safe, bisect_iters)
            ok &= jnp.where(check_out_ref[0, j] != 0, ok_out, True)
            ok &= jnp.where(check_in_ref[0, j] != 0, ok_in, True)
        return ok

    def chunk_body(c, carry):
        out_emb, out_count, found = carry
        off = c * C + jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        idx = start[:, None] + off                              # (cap, C)
        in_deg_range = off < deg[:, None]
        cand = indices_cat[jnp.clip(idx, 0, indices_cat.shape[0] - 1)]
        cand_safe = jnp.clip(cand, 0, n - 1)
        mask = cheap_mask(cand, cand_safe, in_deg_range)
        mask &= edge_checks(cand_safe)
        flat = mask.reshape(-1)                                 # (cap·C,)
        n_new = flat.sum().astype(jnp.int32)
        pos = jnp.cumsum(flat).astype(jnp.int32) - 1 + out_count
        dest = jnp.where(flat & (pos < cap), pos, cap)          # cap ⇒ drop
        rows = jnp.broadcast_to(emb[:, None, :], (cap, C, k)).reshape(-1, k)
        rows = rows.at[:, i].set(cand.reshape(-1))
        out_emb = out_emb.at[dest].set(rows, mode="drop")
        return out_emb, jnp.minimum(out_count + n_new, cap), found + n_new

    out_emb0 = jnp.full((cap, k), -1, jnp.int32)
    out_emb, out_count, found = jax.lax.fori_loop(
        0, max_chunks, chunk_body, (out_emb0, jnp.int32(0), jnp.int32(0)))

    out_emb_ref[...] = out_emb
    out_count_ref[0, 0] = out_count
    found_ref[0, 0] = found
    ovf_ref[0, 0] = (found > cap).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("level", "k", "cap", "chunk", "max_chunks",
                     "bisect_iters", "n", "interpret"))
def frontier_expand(labels, out_indptr, out_indices, in_indptr, in_indices,
                    emb, count, anchor_pos, use_out, cand_label, min_out,
                    min_in, check_out_row, check_in_row, *, level: int,
                    k: int, cap: int, chunk: int, max_chunks: int,
                    bisect_iters: int, n: int, interpret: bool = False):
    """Run one fused expansion level.

    Args (all jnp, int32 unless noted):
      labels (n,); out_indptr/in_indptr (n+1,); out_indices/in_indices (E,)
      — edgeless graphs pass the 1-element sentinel arrays that
      ``DeviceGraph.from_host`` builds.
      emb (cap, k) frontier, columns ≥ `level` are -1; count () valid rows.
      anchor_pos/use_out/cand_label/min_out/min_in: () plan scalars for this
      level (use_out bool-ish).
      check_out_row/check_in_row: (k,) bool-ish — plan.check_out[level].
    Returns (out_emb (cap, k) int32, out_count (), found (), overflowed ()
    bool) — bit-identical to the single-phase XLA pipeline.
    """
    n_out = out_indices.shape[0]
    if not interpret:
        need = frontier_expand_vmem_bytes(
            n, n_out + in_indices.shape[0], cap, chunk, k)
        if need > _VMEM_BUDGET_BYTES:
            raise ValueError(
                f"frontier_expand geometry needs ~{need / 2**20:.1f} MiB of "
                f"VMEM (> {_VMEM_BUDGET_BYTES / 2**20:.0f} MiB); shrink "
                f"cap/chunk (cap={cap}, chunk={chunk}, k={k}, n={n}) or use "
                f'expansion="xla"')

    kern = functools.partial(
        _frontier_kernel, level=level, k=k, cap=cap, chunk=chunk,
        max_chunks=max_chunks, bisect_iters=bisect_iters, n=n, n_out=n_out)

    def smem_i32(x):
        return jnp.asarray(x, jnp.int32).reshape(1, 1)

    out_emb, out_count, found, ovf = pl.pallas_call(
        kern,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),   # emb (cap, k)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # count (1, 1)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # labels (n, 1)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # out_indptr (n+1, 1)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # in_indptr (n+1, 1)
            pl.BlockSpec(memory_space=pltpu.VMEM),   # indices_cat (2E, 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # anchor_pos (1, 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # use_out (1, 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # cand_label (1, 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # min_out (1, 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # min_in (1, 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # check_out_row (1, k)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # check_in_row (1, k)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap, k), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(
        emb,
        smem_i32(count),
        labels[:, None],
        out_indptr[:, None],
        in_indptr[:, None],
        jnp.concatenate([out_indices, in_indices])[:, None],
        smem_i32(anchor_pos),
        smem_i32(use_out),
        smem_i32(cand_label),
        smem_i32(min_out),
        smem_i32(min_in),
        jnp.asarray(check_out_row, jnp.int32).reshape(1, k),
        jnp.asarray(check_in_row, jnp.int32).reshape(1, k),
    )
    return out_emb, out_count[0, 0], found[0, 0], ovf[0, 0] != 0
