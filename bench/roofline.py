"""Bytes and peaks for the match step's roofline share.

The count is of the work, not of how a program does it: for each
(candidate, root block) pair that a window dispatched, the fewest bytes
that any implementation must move between memory and the chip to match a
2-vertex candidate over the block's roots, in int32 ids and labels:

* a root ``u`` whose label is the root end's label reads its two CSR
  pointers (8 B), its adjacency list (4 B a neighbour) and each
  neighbour's label (4 B a neighbour);
* each neighbour whose label is the other end's writes one 2-vertex row
  (8 B);
* a reciprocal candidate (a⇄b) also reads one word of the neighbour's own
  adjacency for each such neighbour (4 B): the least that proves the
  reverse edge.

Either end can serve as the root, so the count takes the cheaper one.
Nothing is counted for pad rows of a batch, for the root scan or for the
metric update, so the count stays a lower bound.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


class BlockSums:
    """Per (root block, label) sums over an undirected graph's vertices."""

    def __init__(self, n: int, edges: np.ndarray, labels: np.ndarray,
                 n_labels: int, root_block: int):
        edges = np.asarray(edges, np.int64).reshape(-1, 2)
        src, dst = edges[:, 0], edges[:, 1]
        keep = src != dst
        keys = np.unique(np.concatenate([src[keep] * n + dst[keep],
                                         dst[keep] * n + src[keep]]))
        src, dst = keys // n, keys % n
        labels = np.asarray(labels, np.int64)
        L = n_labels
        n_blocks = -(-n // root_block)
        block = np.arange(n) // root_block
        cell = block * L + labels                       # (n,) block·label
        self.roots = np.bincount(cell, minlength=n_blocks * L).reshape(n_blocks, L)
        self.degree = np.bincount(cell[src], minlength=n_blocks * L
                                  ).reshape(n_blocks, L)
        self.to_label = np.bincount(cell[src] * L + labels[dst],
                                    minlength=n_blocks * L * L
                                    ).reshape(n_blocks, L, L)

    def bytes(self, labels: Tuple[int, int], reciprocal: bool,
              blocks: Iterable[int]) -> float:
        blocks = np.asarray(list(blocks), np.int64)
        if blocks.size == 0:
            return 0.0
        best = None
        for r, o in ((labels[0], labels[1]), (labels[1], labels[0])):
            hits = self.to_label[blocks, r, o].sum()
            b = (8 * self.roots[blocks, r].sum() + 8 * self.degree[blocks, r].sum()
                 + (12 if reciprocal else 8) * hits)
            best = b if best is None else min(best, b)
        return float(best)
