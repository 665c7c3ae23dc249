"""Matching plans — compile a Pattern into static arrays for the JAX matcher.

VF3-Light picks its matching order dynamically during DFS.  On a TPU the
matcher is a fixed dataflow program, so the order is planned here, once per
pattern, on the host:

  * root   = the pattern vertex with the rarest label in the data graph
             (tie-break: max degree) — smallest initial frontier;
  * order  = greedy connected extension, at each step choosing the vertex
             with the most edges into the ordered prefix (max constraints ⇒
             max pruning), tie-break rare label then high degree;
  * anchor = for each non-root vertex, one already-ordered neighbor whose
             adjacency list is gathered to enumerate candidates.

All plan fields are *data* (jnp arrays), not static attributes, so the jitted
matcher compiles once per pattern size k and is reused across every pattern
of that size — crucial when a mining level evaluates hundreds of candidates.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import tracing
from .graph import DataGraph
from .pattern import Pattern

__all__ = ["PatternPlan", "make_plan", "stack_plans"]


@dataclasses.dataclass(frozen=True)
class PatternPlan:
    """Device-side matching plan for one pattern.

    k:            pattern size (the only static field).
    root_label:   int32 scalar.
    root_min_out / root_min_in: degree filters for the root.
    anchor_pos:   (k,) int32 — position (into `order`) of the anchor for step
                  i (entry 0 unused).
    anchor_out:   (k,) bool — gather anchor's out-neighbors (else in-).
    cand_label:   (k,) int32 — required label of step-i candidate.
    min_out/min_in: (k,) int32 — degree filters per step.
    check_out:    (k, k) bool — step i must verify edge cand → emb[j].
    check_in:     (k, k) bool — step i must verify edge emb[j] → cand.
    """

    k: int
    root_label: jnp.ndarray
    root_min_out: jnp.ndarray
    root_min_in: jnp.ndarray
    anchor_pos: jnp.ndarray
    anchor_out: jnp.ndarray
    cand_label: jnp.ndarray
    min_out: jnp.ndarray
    min_in: jnp.ndarray
    check_out: jnp.ndarray
    check_in: jnp.ndarray
    order: tuple  # host-side: order[i] = original pattern vertex at step i


def stack_plans(plans: Sequence[PatternPlan]) -> PatternPlan:
    """Stack same-k plans into one plan pytree with a leading pattern axis.

    The per-plan host-side ``order`` metadata is dropped (set to ``()``) so
    every stacked plan of a given k shares one treedef — jit programs keyed on
    the plan pytree then cache-hit across levels instead of retracing per
    stack.
    """
    assert len(plans) > 0, "cannot stack zero plans"
    k = plans[0].k
    assert all(p.k == k for p in plans), "plans must share pattern size"
    leaves = [jax.tree_util.tree_flatten(p)[0] for p in plans]
    stacked = [jnp.stack([ln[i] for ln in leaves]) for i in range(len(leaves[0]))]
    return PatternPlan(k, *stacked, order=())


def make_plan(pat: Pattern, graph: Optional[DataGraph] = None) -> PatternPlan:
    if not pat.is_connected():
        raise ValueError("can only plan connected patterns")
    tracing.count("plans_built")
    k = pat.k
    und = pat.undirected_adj()
    out_deg = pat.adj.sum(axis=1).astype(np.int32)
    in_deg = pat.adj.sum(axis=0).astype(np.int32)

    if graph is not None:
        label_freq = graph.label_counts()
        rarity = label_freq[np.clip(pat.labels, 0, label_freq.shape[0] - 1)]
    else:
        rarity = np.zeros(k, dtype=np.int64)

    # --- choose order -------------------------------------------------------
    total_deg = und.sum(axis=0)
    root = int(np.lexsort((-total_deg, rarity))[0])
    order = [root]
    remaining = set(range(k)) - {root}
    while remaining:
        best, best_key = None, None
        for v in remaining:
            conn = int(sum(und[v, u] for u in order))
            if conn == 0:
                continue
            key = (-conn, int(rarity[v]), -int(total_deg[v]))
            if best_key is None or key < best_key:
                best, best_key = v, key
        assert best is not None, "pattern connected but no extension found"
        order.append(best)
        remaining.remove(best)

    pos_of = {v: i for i, v in enumerate(order)}

    # --- anchors + checks ---------------------------------------------------
    anchor_pos = np.zeros(k, dtype=np.int32)
    anchor_out = np.zeros(k, dtype=bool)
    check_out = np.zeros((k, k), dtype=bool)
    check_in = np.zeros((k, k), dtype=bool)
    for i in range(1, k):
        v = order[i]
        # candidate anchors = ordered neighbors; prefer one with a pattern
        # edge anchor→v (out-gather), tie-break earliest (smallest frontier
        # growth history)
        anchors = [j for j in range(i) if und[order[j], v]]
        outs = [j for j in anchors if pat.adj[order[j], v]]
        if outs:
            a = outs[0]
            anchor_pos[i], anchor_out[i] = a, True
        else:
            a = anchors[0]
            anchor_pos[i], anchor_out[i] = a, False
        for j in range(i):
            u = order[j]
            need_in = bool(pat.adj[u, v])   # emb[j] → cand
            need_out = bool(pat.adj[v, u])  # cand → emb[j]
            # the gather itself certifies the anchor edge in gather direction
            if j == a:
                if anchor_out[i]:
                    need_in = False  # anchor→cand guaranteed by out-gather
                else:
                    need_out = False  # cand→anchor guaranteed by in-gather
            check_in[i, j] = need_in
            check_out[i, j] = need_out

    labels_o = pat.labels[order]
    out_o = out_deg[order]
    in_o = in_deg[order]
    return PatternPlan(
        k=k,
        root_label=jnp.int32(labels_o[0]),
        root_min_out=jnp.int32(out_o[0]),
        root_min_in=jnp.int32(in_o[0]),
        anchor_pos=jnp.asarray(anchor_pos),
        anchor_out=jnp.asarray(anchor_out),
        cand_label=jnp.asarray(labels_o, jnp.int32),
        min_out=jnp.asarray(out_o, jnp.int32),
        min_in=jnp.asarray(in_o, jnp.int32),
        check_out=jnp.asarray(check_out),
        check_in=jnp.asarray(check_in),
        order=tuple(order),
    )
