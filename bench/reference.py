"""Plain reference for the answers the benchmark checks.

It imports nothing of the program.  From the same edge and label arrays it
builds its own adjacency and recomputes, in straightforward numpy and
Python, what a FLEXIS query answers: the candidate patterns of each level,
and each candidate's support.

What depends on the metric (the threshold τ, which candidates are searched,
the support and how two supports compare) is one rule file per metric,
``bench/references/<metric>.py``, found by the metric's name (`rule`).
What every metric shares is here: the graph, the matching order, the block
schedule and the candidates of each level.

Supports that stop early depend on the order in which embeddings are met,
so the reference takes that order as the program documents it:

* root blocks of ``R`` vertex ids, walked in descending order of their
  largest out-degree (ties by block id);
* inside a block, the pattern is matched vertex by vertex in the plan's
  order.  The root is the pattern vertex of rarest label (then most
  pattern edges, then lowest index).  Each next vertex has the most edges
  into those already placed (then rarest label, most edges, lowest index).
  It is reached from one placed neighbour, its anchor: the first one with a
  pattern edge towards it, through out-edges, or else the first neighbour,
  through in-edges;
* the rows of each step come in (chunk, row, position) order, where
  ``position`` indexes the anchor's sorted adjacency and ``chunk`` is
  ``position // C``.

``R`` and ``C`` follow from the graph alone (`geometry`).  The block
schedule and the candidate rule are likewise written out here, not taken
from the program.
"""
from __future__ import annotations

import importlib.util
import itertools
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# A pattern as the reference sees it: (adjacency (k, k) bool, labels (k,)).
Pat = Tuple[np.ndarray, np.ndarray]


RULES = Path(__file__).resolve().parent / "references"


def rule(metric: str, directory: Optional[Path] = None) -> ModuleType:
    """The rule file ``<directory>/<metric>.py`` (default:
    bench/references/), loaded: its ``tau``, ``searchable``, ``support`` and
    ``same`` (see ``bench/references/mis.py``).  LookupError where the
    metric has none."""
    path = (directory or RULES) / f"{metric}.py"
    if not re.fullmatch(r"[A-Za-z0-9_]+", metric) or not path.is_file():
        raise LookupError(f"metric {metric!r} has no rule file "
                          f"{path.parent.name}/{metric}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_rule_" + metric, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def canonical_key(adj: np.ndarray, labels: np.ndarray) -> Tuple:
    """Smallest (labels, adjacency) over all vertex orders: equal keys ⇔
    isomorphic labeled directed patterns."""
    k = len(labels)
    best = None
    for p in itertools.permutations(range(k)):
        p = list(p)
        key = (tuple(int(x) for x in labels[p]),
               tuple(bool(x) for x in adj[np.ix_(p, p)].ravel()))
        if best is None or key < best:
            best = key
    return best


def connected(adj: np.ndarray) -> bool:
    und = adj | adj.T
    k = und.shape[0]
    seen, todo = {0}, [0]
    while todo:
        u = todo.pop()
        for v in range(k):
            if und[u, v] and v not in seen:
                seen.add(v)
                todo.append(v)
    return len(seen) == k


def geometry(n: int, max_degree: int) -> Tuple[int, int]:
    """(R, C): root-block width and expansion chunk for a graph of ``n``
    vertices whose largest in- or out-degree is ``max_degree``."""
    R = int(min(4096, max(128, 1 << int(np.ceil(np.log2(n))))))
    C = int(min(64, 1 << int(np.ceil(np.log2(max(max_degree, 1) + 1)))))
    return R, C


class RefGraph:
    """Undirected labeled graph, stored with both edge directions."""

    def __init__(self, n: int, edges: np.ndarray, labels: np.ndarray,
                 n_labels: int):
        edges = np.asarray(edges, np.int64).reshape(-1, 2)
        src, dst = edges[:, 0], edges[:, 1]
        keep = src != dst
        src, dst = src[keep], dst[keep]
        keys = np.unique(np.concatenate([src * n + dst, dst * n + src]))
        self.n = n
        self.keys = keys
        self.labels = np.asarray(labels, np.int64)
        self.n_labels = n_labels
        src, dst = keys // n, keys % n
        self.out_ptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
        self.out_idx = dst
        t = np.argsort(dst * n + src, kind="stable")
        self.in_ptr = np.concatenate([[0], np.cumsum(np.bincount(dst, minlength=n))])
        self.in_idx = src[t]
        self.out_deg = np.diff(self.out_ptr)
        self.in_deg = np.diff(self.in_ptr)
        self.label_counts = np.bincount(self.labels, minlength=n_labels)
        self.R, self.C = geometry(
            n, int(max(self.out_deg.max(initial=0), self.in_deg.max(initial=0))))
        n_blocks = -(-n // self.R)
        padded = np.full(n_blocks * self.R, -1, np.int64)
        padded[:n] = self.out_deg
        self.schedule = np.argsort(-padded.reshape(n_blocks, self.R).max(axis=1),
                                   kind="stable")

    def has_edge(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        q = u * self.n + v
        if not self.keys.size:
            return np.zeros(np.shape(q), bool)
        i = np.minimum(np.searchsorted(self.keys, q), self.keys.size - 1)
        return self.keys[i] == q

    # -- matching -----------------------------------------------------------
    def plan(self, adj: np.ndarray, labels: np.ndarray):
        k = len(labels)
        und = adj | adj.T
        rar = self.label_counts[labels]
        tot = und.sum(axis=0)
        order = [min(range(k), key=lambda v: (rar[v], -tot[v], v))]
        rest = [v for v in range(k) if v != order[0]]
        while rest:
            reach = [v for v in rest if any(und[v, u] for u in order)]
            best = min(reach, key=lambda v: (
                -sum(int(und[v, u]) for u in order), rar[v], -tot[v], v))
            order.append(best)
            rest.remove(best)
        steps = []
        for i in range(1, k):
            v = order[i]
            placed = [j for j in range(i) if und[order[j], v]]
            outs = [j for j in placed if adj[order[j], v]]
            a, use_out = (outs[0], True) if outs else (placed[0], False)
            checks = []
            for j in range(i):
                u = order[j]
                to_cand, from_cand = bool(adj[u, v]), bool(adj[v, u])
                if j == a:
                    # the anchor's own edge is the one walked
                    to_cand, from_cand = ((False, from_cand) if use_out
                                          else (to_cand, False))
                if to_cand:
                    checks.append((j, True))
                if from_cand:
                    checks.append((j, False))
            steps.append((v, a, use_out, checks))
        out_p, in_p = adj.sum(axis=1), adj.sum(axis=0)
        return order, steps, out_p, in_p

    def block_rows(self, pat: Pat, plan, block: int) -> np.ndarray:
        """Embedding rows of one root block, in priority order."""
        adj, labels = pat
        order, steps, out_p, in_p = plan
        lo = block * self.R
        verts = np.arange(lo, min(lo + self.R, self.n))
        r0 = order[0]
        ok = ((self.labels[verts] == labels[r0])
              & (self.out_deg[verts] >= out_p[r0])
              & (self.in_deg[verts] >= in_p[r0]))
        rows = verts[ok][:, None]
        for i, (v, a, use_out, checks) in enumerate(steps, start=1):
            ptr, idx = ((self.out_ptr, self.out_idx) if use_out
                        else (self.in_ptr, self.in_idx))
            x = rows[:, a]
            deg = ptr[x + 1] - ptr[x]
            r = np.repeat(np.arange(rows.shape[0]), deg)
            p = np.arange(int(deg.sum())) - np.repeat(np.cumsum(deg) - deg, deg)
            cand = idx[ptr[x][r] + p]
            m = ((self.labels[cand] == labels[v])
                 & (self.out_deg[cand] >= out_p[v])
                 & (self.in_deg[cand] >= in_p[v]))
            for j in range(i):
                m &= cand != rows[r, j]
            r, p, cand = r[m], p[m], cand[m]
            m = np.ones(cand.shape, bool)
            for j, to_cand in checks:
                m &= (self.has_edge(rows[r, j], cand) if to_cand
                      else self.has_edge(cand, rows[r, j]))
            r, p, cand = r[m], p[m], cand[m]
            o = np.lexsort((p, r, p // self.C))
            rows = np.concatenate([rows[r[o]], cand[o][:, None]], axis=1)
        return rows

    # -- candidates ---------------------------------------------------------
    def level1(self) -> List[Pat]:
        """Every 2-vertex pattern with an embedding: a→b, and a⇄b where an
        edge and its reverse both exist (always, in an undirected graph)."""
        src, dst = self.keys // self.n, self.keys % self.n
        pairs = np.unique(np.stack([self.labels[src], self.labels[dst]], 1), axis=0)
        one = np.array([[False, True], [False, False]])
        both = np.array([[False, True], [True, False]])
        out: Dict[Tuple, Pat] = {}
        for adj in (one, both):
            for a, b in pairs.tolist():
                lab = np.array([a, b])
                out.setdefault(canonical_key(adj, lab), (adj, lab))
        return list(out.values())

    def next_level(self, frequent_keys: set, k: int,
                   label_universe: Sequence[int]) -> List[Pat]:
        """Every connected k-vertex pattern whose connected (k−1)-vertex
        induced subpatterns are all frequent: the candidates FLEXIS's merge
        generation yields under its downward-closure rule."""
        pairs = [(i, j) for i in range(k) for j in range(k) if i != j]
        out: Dict[Tuple, Pat] = {}
        for lab in itertools.combinations_with_replacement(label_universe, k):
            lab = np.array(lab)
            for bits in range(1, 1 << len(pairs)):
                adj = np.zeros((k, k), bool)
                for e, (i, j) in enumerate(pairs):
                    adj[i, j] = bool(bits >> e & 1)
                if not connected(adj):
                    continue
                key = canonical_key(adj, lab)
                if key in out:
                    continue
                subs_ok = True
                for v in range(k):
                    keep = [u for u in range(k) if u != v]
                    sub = adj[np.ix_(keep, keep)]
                    if connected(sub) and canonical_key(sub, lab[keep]) \
                            not in frequent_keys:
                        subs_ok = False
                        break
                if subs_ok:
                    out[key] = (adj, lab)
        return list(out.values())


def _plain(value):
    """A support as a Python number of its own type: an int stays an int,
    a float a float."""
    return value.item() if hasattr(value, "item") else value


class Answer:
    """What one query reported: every searched pattern with its support."""

    def __init__(self, searched: Iterable[Tuple[np.ndarray, np.ndarray, float]],
                 frequent: Iterable[Tuple[np.ndarray, np.ndarray, float]]):
        self.searched = [(np.asarray(a, bool), np.asarray(lab, np.int64), _plain(s))
                         for a, lab, s in searched]
        self.frequent = [(np.asarray(a, bool), np.asarray(lab, np.int64), _plain(s))
                         for a, lab, s in frequent]


class Checker:
    """Compares answers with the reference under one metric's ``rule``;
    caches per pattern.  ``control`` goes to the rule's ``support``."""

    def __init__(self, g: RefGraph, rule: ModuleType, sigma: int, lam: float,
                 max_k: int, **control):
        self.g, self.rule = g, rule
        self.sigma, self.lam, self.max_k = sigma, lam, max_k
        self.control = control
        self._support: Dict[Tuple, float] = {}

    def tau(self, k: int):
        return self.rule.tau(self.sigma, self.lam, k)

    def support(self, adj: np.ndarray, labels: np.ndarray):
        memo = (labels.tobytes(), adj.tobytes())
        if memo not in self._support:
            self._support[memo] = self.rule.support(
                self.g, (adj, labels), self.tau(len(labels)), **self.control)[0]
        return self._support[memo]

    def compare(self, ans: Answer, *, whole: bool) -> Dict[str, int]:
        """Counts of wrong answers: supports, frequent set, candidate set.

        ``whole``: the query ran to its end, so its candidates must equal the
        reference's level by level; otherwise (a query cut by its time limit)
        every pattern it decided must be a level-1 candidate.
        """
        g = self.g
        got: Dict[Tuple, Tuple[np.ndarray, np.ndarray, int]] = {}
        wrong_candidates = 0
        for adj, lab, s in ans.searched:
            key = canonical_key(adj, lab)
            wrong_candidates += key in got
            got[key] = (adj, lab, s)
        wrong_supports = 0
        ref_frequent = set()
        for key, (adj, lab, s) in got.items():
            ref = self.support(adj, lab)
            wrong_supports += not self.rule.same(s, ref)
            if ref >= self.tau(len(lab)):
                ref_frequent.add(key)
        got_frequent = {canonical_key(a, lab) for a, lab, _ in ans.frequent}
        wrong_frequent = len(got_frequent ^ ref_frequent)

        want = {canonical_key(a, lab): (a, lab) for a, lab in g.level1()}
        if not whole:
            wrong_candidates += len(set(got) - set(want))
        else:
            labels_present = sorted(set(g.labels.tolist()))
            k, expected = 2, set()
            while want:
                want = {key: p for key, p in want.items()
                        if self.rule.searchable(k, self.tau(k), g.n)}
                expected |= set(want)
                # the program's own drawing of a pattern where it has one
                frequent_k = {key for key, (a, lab) in want.items()
                              if (key in ref_frequent if key in got
                                  else self.support(a, lab) >= self.tau(k))}
                if not frequent_k or k + 1 > self.max_k:
                    break
                k += 1
                want = {canonical_key(a, lab): (a, lab) for a, lab in
                        g.next_level(frequent_k, k, labels_present)}
            wrong_candidates += len(set(got) ^ expected)
        return {"wrong_supports": int(wrong_supports),
                "wrong_frequent": int(wrong_frequent),
                "wrong_candidates": int(wrong_candidates)}
