"""FLEXIS × GNN: frequent motifs as node features for GraphSAGE.

    PYTHONPATH=src python examples/mine_motifs_gnn.py

Where the paper's technique meets the assigned GNN architectures
(DESIGN.md §5): mine frequent patterns from a graph, build per-node
motif-participation counts from the matcher's embeddings, concatenate them
to the node features, and train GraphSAGE — mining and message passing
share the same CSR + segment-op substrate.
"""
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (MatchConfig, MiningConfig, make_plan, match_block, mine,
                        put_plan)
from repro.core.graph import DeviceGraph
from repro.data.synthetic import rmat_graph
from repro.models.gnn.common import GraphBatch
from repro.models.gnn.graphsage import SAGEConfig, sage_init, sage_loss
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update


def motif_features(g, patterns, cfg):
    """(n, |patterns|) counts of pattern embeddings through each vertex."""
    dev_g = DeviceGraph.from_host(g)
    feats = np.zeros((g.n, len(patterns)), np.float32)
    for j, pat in enumerate(patterns):
        plan = put_plan(make_plan(pat, g))
        for b in range(0, g.n, cfg.root_block):
            emb, count, _, _, _ = match_block(dev_g, plan, jnp.int32(b), cfg)
            rows = np.asarray(emb[: int(count)]).reshape(-1)
            np.add.at(feats[:, j], rows[rows >= 0], 1.0)
    return feats


def main():
    g = rmat_graph(400, 2400, n_labels=3, seed=1, undirected=True)
    print(f"graph: |V|={g.n} |E|={g.n_edges}")

    mcfg = MatchConfig.for_graph(g, cap=4096)
    res = mine(g, MiningConfig(sigma=6, lam=0.5, metric="mis",
                               max_pattern_size=3, match=mcfg))
    motifs = [p for p, _ in res.frequent if p.k == 3][:8]
    print(f"mined {len(res.frequent)} frequent patterns; "
          f"using {len(motifs)} 3-vertex motifs as features")

    mf = motif_features(g, motifs, mcfg)
    base = np.eye(g.n_labels, dtype=np.float32)[g.labels]
    x = np.concatenate([base, mf / (1 + mf.max(0, keepdims=True))], axis=1)

    # node classification: predict the label from structure+motifs
    gb = GraphBatch(
        x=jnp.asarray(x),
        edge_src=jnp.asarray(np.repeat(np.arange(g.n), np.diff(g.out_indptr)),
                             jnp.int32),
        edge_dst=jnp.asarray(g.out_indices, jnp.int32),
        edge_mask=jnp.ones((g.n_edges,), bool),
        node_mask=jnp.ones((g.n,), bool),
        graph_ids=jnp.zeros((g.n,), jnp.int32), n_graphs=1,
        targets=jnp.asarray(g.labels, jnp.int32))

    cfg = SAGEConfig(d_in=x.shape[1], d_hidden=32, n_classes=g.n_labels)
    params = sage_init(jax.random.key(0), cfg)
    opt_cfg = AdamWConfig(lr=5e-3, total_steps=60, warmup_steps=5)
    opt = adamw_init(params)

    @jax.jit
    def step(params, opt):
        loss, grads = jax.value_and_grad(lambda p: sage_loss(p, cfg, gb))(params)
        params, opt = adamw_update(opt_cfg, grads, opt, params)
        return loss, params, opt

    for i in range(60):
        loss, params, opt = step(params, opt)
        if i % 15 == 0:
            print(f"  step {i:3d} loss {float(loss):.4f}")
    print(f"final loss {float(loss):.4f} (motif features wired end to end)")


if __name__ == "__main__":
    main()
