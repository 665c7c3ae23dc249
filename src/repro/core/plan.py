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

All plan fields are *data* (arrays), not static attributes, so the jitted
matcher compiles once per pattern size k and is reused across every pattern
of that size — crucial when a mining level evaluates hundreds of candidates.

A plan is built, and a group of plans stacked, as host (numpy) arrays; the
consumer then puts what it runs on the device in one `put_plan` call (one
per candidate group on the batched plane), instead of one transfer per
field per plan.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np
import jax

from . import tracing
from .graph import DataGraph
from .pattern import Pattern

__all__ = ["PatternPlan", "make_plan", "stack_plans", "put_plan"]


@dataclasses.dataclass(frozen=True)
class PatternPlan:
    """Matching plan for one pattern: numpy fields as `make_plan` and
    `stack_plans` build it, device arrays once `put_plan` has placed it.

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
    root_label: np.ndarray
    root_min_out: np.ndarray
    root_min_in: np.ndarray
    anchor_pos: np.ndarray
    anchor_out: np.ndarray
    cand_label: np.ndarray
    min_out: np.ndarray
    min_in: np.ndarray
    check_out: np.ndarray
    check_in: np.ndarray
    order: tuple  # host-side: order[i] = original pattern vertex at step i


def stack_plans(plans: Sequence[PatternPlan]) -> PatternPlan:
    """Stack same-k plans into one plan pytree with a leading pattern axis.

    The per-plan host-side ``order`` metadata is dropped (set to ``()``) so
    every stacked plan of a given k shares one treedef — jit programs keyed on
    the plan pytree then cache-hit across levels instead of retracing per
    stack.  Stacks on the host: the result is numpy, for one `put_plan`.
    """
    assert len(plans) > 0, "cannot stack zero plans"
    k = plans[0].k
    assert all(p.k == k for p in plans), "plans must share pattern size"
    leaves = [jax.tree_util.tree_flatten(p)[0] for p in plans]
    stacked = [np.stack([ln[i] for ln in leaves]) for i in range(len(leaves[0]))]
    return PatternPlan(k, *stacked, order=())


def put_plan(plan: PatternPlan, device=None) -> PatternPlan:
    """``plan`` (one plan or a stacked group) on the device in one
    `jax.device_put` of the whole pytree; ``device`` as `jax.device_put`
    takes it (a sharding places it on a mesh).  Counted as ``plan_puts``
    in the query's record."""
    tracing.count("plan_puts")
    return jax.device_put(plan, device)


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
        root_label=np.int32(labels_o[0]),
        root_min_out=np.int32(out_o[0]),
        root_min_in=np.int32(in_o[0]),
        anchor_pos=anchor_pos,
        anchor_out=anchor_out,
        cand_label=labels_o.astype(np.int32),
        min_out=out_o.astype(np.int32),
        min_in=in_o.astype(np.int32),
        check_out=check_out,
        check_in=check_in,
        order=tuple(order),
    )
