"""Oracle for the fused frontier-expansion kernel = the XLA pipeline.

The reference is the production single-phase expansion in
``core/matcher._expand_level`` (``two_phase=False``, ``expansion="xla"``):
gather → cheap mask → edge bisection → cumsum compaction, one XLA op
chain per chunk.  The kernel is bit-identical to this path, including the
(chunk, row, position) survivor ordering the greedy-mIS metric depends on.
"""
from __future__ import annotations

import dataclasses

from repro.core.matcher import MatchConfig, _expand_level


def frontier_expand_ref(g, plan, emb, count, level: int, cfg: MatchConfig):
    """Single-phase XLA expansion of one level; same returns as the kernel:
    (out_emb (cap, k) int32, out_count (), found (), overflowed () bool).

    The XLA pipeline defers the found > cap overflow check to
    ``match_block``; the kernel flags it per level.  The ref normalizes to
    the kernel's contract so the two are comparable level-by-level —
    ``match_block`` results are identical either way (it ORs the same
    check back in).
    """
    cfg = dataclasses.replace(cfg, expansion="xla", two_phase=False)
    out_emb, out_count, found, ovf, _ = _expand_level(g, plan, emb, count,
                                                      level, cfg)
    return out_emb, out_count, found, ovf | (found > cfg.cap)
