"""Kernel micro-benchmarks (interpret-mode correctness + jnp-path timing).

Wall-clock on this CPU container times the *jnp oracle paths* (the
production CPU fallbacks); the Pallas kernels themselves are TPU-targeted
and validated for correctness in interpret mode (see tests/kernels)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import SMOKE, bench_iters, emit


def _time(fn, *args, iters=20):
    iters = bench_iters(iters)
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        out = out[0] if isinstance(out, tuple) else out
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def main() -> None:
    rng = np.random.default_rng(0)
    rows = []

    # chunked attention (flash oracle)
    from repro.models.attention import AttentionConfig, _attn_chunked

    B, S, H, KV, hd = 1, 1024, 8, 4, 64
    cfg = AttentionConfig(d_model=H * hd, n_heads=H, n_kv_heads=KV, head_dim=hd)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: _attn_chunked(q, k, v, cfg, 256, 256))
    us = _time(f, q, k, v)
    flops = 4 * B * S * S * H * hd / 2
    rows.append({"name": f"kernel/attn_chunked/B{B}S{S}H{H}",
                 "us_per_call": round(us, 1),
                 "derived": round(flops / (us * 1e-6) / 1e9, 2)})  # GFLOP/s

    # mIS greedy scan (production path)
    from repro.core.mis import bitmap_init, mis_greedy_update

    n, cap, kk = 100_000, 8192, 4
    emb = np.stack([rng.choice(n, kk, replace=False) for _ in range(cap)]).astype(np.int32)
    bm = bitmap_init(n)
    g = jax.jit(lambda bm, e: mis_greedy_update(bm, jnp.int32(0), e,
                                                jnp.int32(cap),
                                                jnp.int32(10**9), kk))
    us = _time(g, bm, jnp.asarray(emb))
    rows.append({"name": f"kernel/mis_greedy/cap{cap}k{kk}",
                 "us_per_call": round(us, 1),
                 "derived": round(cap / (us * 1e-6) / 1e6, 3)})  # M emb/s

    # Luby parallel rounds
    from repro.core.mis import mis_luby_update

    h = jax.jit(lambda bm, e: mis_luby_update(bm, jnp.int32(0), e,
                                              jnp.int32(cap),
                                              jnp.int32(10**9), kk, n))
    us = _time(h, bm, jnp.asarray(emb))
    rows.append({"name": f"kernel/mis_luby/cap{cap}k{kk}",
                 "us_per_call": round(us, 1),
                 "derived": round(cap / (us * 1e-6) / 1e6, 3)})

    # batched level step (PR 1 data plane): one vmapped program for a
    # 16-pattern candidate batch vs 16 single-pattern dispatches
    from repro.core import MatchConfig, build_graph
    from repro.core.batched import _state_init, _step_fn
    from repro.core.flexis import initial_candidates
    from repro.core.graph import DeviceGraph
    from repro.core.matcher import match_block
    from repro.core.mis import bitmap_init, mis_greedy_update as mgu
    from repro.core.plan import make_plan, stack_plans

    bn = 1000 if SMOKE else 4000
    src = np.repeat(np.arange(bn), 2)
    dst = rng.integers(0, bn, bn * 2)
    bg = build_graph(bn, np.stack([src, dst], 1),
                     rng.integers(0, 8, bn), undirected=True)
    dev_bg = DeviceGraph.from_host(bg)
    mcfg = MatchConfig.for_graph(bg, cap=64, root_block=64)
    pats = initial_candidates(bg)[:16]
    plans = [make_plan(p, bg) for p in pats]
    stacked = stack_plans(plans)
    state = _state_init("mis", 16, 2, bn)
    taus16 = jnp.full((16,), 10**9, jnp.int32)
    step = _step_fn("mis", 2, mcfg)
    us_b = _time(lambda: step(dev_bg, stacked, jnp.int32(0), state, taus16)[1])

    def _sixteen_singles():
        c = jnp.int32(0)
        for plan in plans:
            emb, n_valid, _, _, _ = match_block(dev_bg, plan, jnp.int32(0), mcfg)
            _, c = mgu(bitmap_init(bn), jnp.int32(0), emb, n_valid,
                       jnp.int32(10**9), 2)
        return c

    us_s = _time(_sixteen_singles)
    rows.append({"name": "kernel/batched_step/P16",
                 "us_per_call": round(us_b, 1),
                 "derived": round(us_s / us_b, 2)})  # speedup vs 16 singles

    # fused frontier expansion (PR 2): whole match_block through the XLA
    # pipeline vs the fused Pallas kernel.  The kernel runs only in
    # interpret mode, off-TPU, so its wall-clock is not a hardware number —
    # the row documents *bit-exact parity* (derived=1.0).
    import dataclasses as _dc

    from repro.core import MatchConfig, build_graph
    from repro.core.flexis import initial_candidates
    from repro.core.generation import generate_new_patterns
    from repro.core.graph import DeviceGraph
    from repro.core.matcher import match_block
    from repro.core.plan import make_plan as _make_plan

    fn_n = 500 if SMOKE else 4000
    fdeg = 4
    fsrc = np.repeat(np.arange(fn_n), fdeg)
    fdst = rng.integers(0, fn_n, fn_n * fdeg)
    fg = build_graph(fn_n, np.stack([fsrc, fdst], 1),
                     rng.integers(0, 4, fn_n), undirected=True)
    fdev = DeviceGraph.from_host(fg)
    fcfg = _dc.replace(
        MatchConfig.for_graph(fg, cap=256 if SMOKE else 2048,
                              root_block=256),
        two_phase=False)
    fpats = initial_candidates(fg)
    fk3 = generate_new_patterns(fpats[:6])
    assert fk3, "graph yields no size-3 candidates"
    fplan = _make_plan(fk3[0], fg)
    assert fplan.k == 3

    geo = f"cap{fcfg.cap}C{fcfg.chunk}k{fplan.k}"
    cands_per_call = fcfg.cap * fcfg.chunk * fcfg.max_chunks * (fplan.k - 1)
    us = _time(lambda: match_block(fdev, fplan, jnp.int32(0), fcfg), iters=10)
    rows.append({"name": f"kernel/frontier_expand_xla/{geo}",
                 "us_per_call": round(us, 1),
                 "derived": round(cands_per_call / (us * 1e-6) / 1e6, 2)})  # M cand/s
    if jax.default_backend() != "tpu":
        fcfg_p = _dc.replace(fcfg, expansion="pallas")
        xla_out = match_block(fdev, fplan, jnp.int32(0), fcfg)
        pal_out = match_block(fdev, fplan, jnp.int32(0), fcfg_p)
        parity = float(
            int(xla_out[1]) == int(pal_out[1])
            and int(xla_out[2]) == int(pal_out[2])
            and bool(xla_out[3]) == bool(pal_out[3])
            and bool(np.array_equal(np.asarray(xla_out[0]),
                                    np.asarray(pal_out[0]))))
        us_p = _time(lambda: match_block(fdev, fplan, jnp.int32(0), fcfg_p),
                     iters=2 if SMOKE else 5)
        rows.append({"name": f"kernel/frontier_expand_pallas_interp/{geo}",
                     "us_per_call": round(us_p, 1),
                     "derived": parity})  # 1.0 = bit-exact parity with xla

    # embedding bag (jnp path)
    from repro.models.embedding import embedding_bag_apply, embedding_bag_init

    tbl = embedding_bag_init(jax.random.key(0), 1_000_00, 64)
    idx = jnp.asarray(rng.integers(0, 1_000_00, (8192, 4)), jnp.int32)
    eb = jax.jit(lambda t, i: embedding_bag_apply(t, i))
    us = _time(eb, tbl, idx)
    rows.append({"name": "kernel/embedding_bag/B8192H4D64",
                 "us_per_call": round(us, 1),
                 "derived": round(8192 * 4 / (us * 1e-6) / 1e6, 2)})  # M lookups/s

    # segment-sum GNN aggregation (jnp path)
    from repro.models.gnn.common import scatter_sum

    E, N, F = 100_000, 10_000, 128
    msgs = jnp.asarray(rng.normal(size=(E, F)), jnp.float32)
    dst = jnp.asarray(rng.integers(0, N, E), jnp.int32)
    mask = jnp.ones((E,), bool)
    ss = jax.jit(lambda m, d: scatter_sum(m, d, mask, N))
    us = _time(ss, msgs, dst)
    rows.append({"name": f"kernel/scatter_sum/E{E}F{F}",
                 "us_per_call": round(us, 1),
                 "derived": round(E * F * 4 / (us * 1e-6) / 2**30, 2)})  # GiB/s

    emit(rows, ["name", "us_per_call", "derived"])


if __name__ == "__main__":
    main()
