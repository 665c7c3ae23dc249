"""Share of the HBM roofline that the match step reached, %.

The least bytes the dispatched (candidate, root block) pairs need
(`bench.roofline.BlockSums`) over the step programs' device time times the
chip's peak HBM bandwidth (`bench/peaks.json`).  The step is bound by
bytes: a 2-vertex match does no arithmetic to speak of."""
from bench import roofline, trace

STEP = "jit_step"


def read(run):
    if run.trace is None:
        return None
    ns, count = trace.module_time(run.trace, STEP)
    cands = run.spans.results.get("cand_build", [])
    if not count or len(cands) != len(run.queries):
        return None
    from repro.core import root_block_order

    g, cell = run.graph, run.cell
    total = 0.0
    for q, cp in zip(run.queries, cands):
        lvl = q.recorder.levels.get(1)
        if lvl is None or lvl.plan is None:
            continue
        R = int(lvl.plan["root_block"])
        sums = roofline.BlockSums(g.n, g.edges, g.labels, g.n_labels, R)
        order = root_block_order(cell.g, R)
        tau = cell.rule.tau(cell.traffic["sigma"], cell.traffic["lam"], 2)
        cands_eval = [p for p in cp if cell.rule.searchable(p.k, tau, g.n)]
        for (k, lo), grp in lvl.groups.items():
            for i, walked in enumerate(grp.blocks_run):
                p = cands_eval[lo + i]
                if p.k != 2:
                    return None
                total += sums.bytes((int(p.labels[0]), int(p.labels[1])),
                                    bool(p.adj[1, 0]), order[:int(walked)])
    if total <= 0:
        return None
    peak = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * total / (ns / 1e9 * peak)
