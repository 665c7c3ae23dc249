"""Public wrapper: drop-in replacement for the XLA expansion pipeline.

``core/matcher._expand_level`` dispatches here when
``MatchConfig.expansion == "pallas"``; the whole expansion level then runs
as one fused Pallas program (see ``kernel.py``) instead of the per-chunk
XLA op chain.  Under ``vmap`` (the batched data plane) the pattern axis
becomes a leading kernel-grid dimension — one launch per level, not one
per pattern.
"""
from __future__ import annotations

import jax

from .kernel import frontier_expand

# What Mosaic (jaxlib 0.9.0) says when asked to lower the kernel for a TPU:
# its CSR-row and label lookups are 1-D vector gathers.
MOSAIC_REFUSAL = "Only 2D gather is supported"


def interpret_mode() -> bool:
    """The one place the fused kernel's execution mode is decided.

    Off-TPU the kernel runs in Pallas interpret mode (Mosaic lowers for TPU
    only).  On a TPU it would run compiled and never interpreted, but Mosaic
    refuses to lower it, so this raises instead; ``MatchConfig`` calls it
    when a ``"pallas"`` config is built.
    """
    if jax.default_backend() != "tpu":
        return True
    raise NotImplementedError(
        f'expansion="pallas" does not run on a TPU: Mosaic refuses to lower '
        f'frontier_expand ("{MOSAIC_REFUSAL}"). Use expansion="xla".')


def frontier_expand_level(g, plan, emb, count, level: int, cfg):
    """Same signature/result as the single-phase ``_expand_level`` pipeline.

    g: DeviceGraph; plan: PatternPlan; emb (cap, k) int32; count () int32.
    Returns (out_emb (cap, k) int32, out_count (), found (), ovf () bool).
    """
    i = level
    return frontier_expand(
        g.labels, g.out_indptr, g.out_indices, g.in_indptr, g.in_indices,
        emb, count,
        plan.anchor_pos[i], plan.anchor_out[i], plan.cand_label[i],
        plan.min_out[i], plan.min_in[i],
        plan.check_out[i], plan.check_in[i],
        level=i, k=plan.k, cap=cfg.cap, chunk=cfg.chunk,
        max_chunks=cfg.max_chunks, bisect_iters=cfg.bisect_iters, n=g.n,
        interpret=interpret_mode(),
    )
