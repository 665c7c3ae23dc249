"""Micro-calibration of the execution planner's cost model.

    PYTHONPATH=src python -m benchmarks.calibrate [--out planner_calibration.json]

Fits the four `repro.core.planner.CostModel` constants on the current
backend by timing the *actual* ``mis`` step program at controlled
geometries.  The model has two separate work terms —

    t = dispatch_overhead_s + lanes·lane_time_s + cap·row_time_s

(expansion-grid lanes vs the per-frontier-row metric scan) — and the
probes are chosen so each constant is isolated:

  * ``lane_time_s`` — same cap, chunk 4 vs 64 (``max_chunks`` pinned to
    1): only the lane count moves, the scan term cancels;
  * ``row_time_s`` — same chunk, cap 512 vs 4096: the lane term is
    subtracted with the fitted ``lane_time_s``, what remains scales with
    cap (on CPU the greedy-mIS ``lax.scan`` dominates here);
  * ``dispatch_overhead_s`` — the small-geometry timing minus both fitted
    work terms (includes host↔device sync, i.e. what the sequential
    loop pays per block);
  * ``vmap_factor`` — per-pattern work of a bucket-4 vmapped step over 4×
    the unbatched work: XLA loses cross-op fusion on batched grids, and
    this tax is what tips compute-bound levels back to sequential.

Schema 2 adds per-metric scan constants: the cap-scaling probe pair is
re-timed under ``mni``, ``frac`` and ``mis_luby`` (the expansion-grid
lane term is metric-independent, so the fitted ``lane_time_s`` is
subtracted as-is) and the residuals land in ``row_time_{mni,frac,luby}_s``
— `CostModel.row_time(metric)` falls back to the ``mis`` constant for
anything unprobed, so schema-1 files keep loading.

Schema 3 adds ``escalation_fraction`` — the measured fraction of sampled
patterns that escalated to the exact pass, folded in after each launch
run by `repro.core.planner.persist_escalation_fraction` (this fit writes
``None`` on a fresh file and preserves any existing measurement);
`CostModel.esc_prior()` uses it to warm-start the auto planner's
sampled-plane pricing when a level has no telemetry of its own.

The result is a tiny JSON (`planner_calibration.json` by default — the
file `repro.core.planner.load_calibration` picks up from the working
directory or ``$REPRO_PLANNER_CALIBRATION``).  ``benchmarks/run.py``
runs this pass automatically in ``--smoke`` mode so a fresh checkout's
first bench sweep also refreshes the planner constants.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional

import numpy as np


def _time_calls(fn, iters: int) -> float:
    fn()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def fit_cost_model(iters: int = 20) -> dict:
    """Measure the step program and return a CostModel dict (schema 2)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core import MatchConfig, build_graph, initial_candidates
    from repro.core.batched import _state_init, _step_fn
    from repro.core.graph import DeviceGraph
    from repro.core.plan import make_plan, put_plan, stack_plans
    from repro.core.planner import CALIBRATION_SCHEMA, CostModel

    rng = np.random.default_rng(0)
    n, deg = 4096, 3
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, n * deg)
    g = build_graph(n, np.stack([src, dst], 1), rng.integers(0, 4, n),
                    undirected=True)
    dev_g = DeviceGraph.from_host(g)
    pats = initial_candidates(g)[:4]
    plans = [make_plan(p, g) for p in pats]
    k = pats[0].k

    def step_time(cap: int, chunk: int, bucket: int,
                  metric: str = "mis") -> float:
        # max_chunks pinned to 1 so lanes == cap·chunk exactly (timing
        # probe only — truncated candidate enumeration is fine here)
        cfg = dataclasses.replace(
            MatchConfig.for_graph(g, cap=cap, root_block=1024),
            chunk=chunk, max_chunks=1, two_phase=False)
        step = _step_fn(metric, k, cfg, unbatched=bucket == 1)
        sel = [plans[i % len(plans)] for i in range(bucket)]
        stacked = put_plan(stack_plans(sel))
        state = _state_init(metric, bucket, k, n)
        taus = jnp.full((bucket,), 10**9, jnp.int32)

        def call():
            out = step(dev_g, stacked, jnp.int32(0), state, taus)
            jax.block_until_ready(out[1])

        return _time_calls(call, iters)

    CAP_S, CAP_B, CH_S, CH_B = 512, 4096, 4, 64
    t_ss = step_time(CAP_S, CH_S, 1)      # small cap, small chunk
    t_sb = step_time(CAP_S, CH_B, 1)      # small cap, big chunk
    t_bs = step_time(CAP_B, CH_S, 1)      # big cap, small chunk

    # lanes = (k-1)·cap·chunk with max_chunks == 1
    lane_time = max((t_sb - t_ss) / ((k - 1) * CAP_S * (CH_B - CH_S)),
                    1e-12)
    row_time = max(
        (t_bs - t_ss - (k - 1) * (CAP_B - CAP_S) * CH_S * lane_time)
        / (CAP_B - CAP_S), 1e-12)
    overhead = max(
        t_ss - (k - 1) * CAP_S * CH_S * lane_time - CAP_S * row_time, 1e-6)

    # the fusion tax shows on WIDE grids (the scan term vmaps fine): fit
    # it where the lane term dominates
    work_bb = (k - 1) * CAP_B * CH_B * lane_time + CAP_B * row_time
    t_vmap4 = step_time(CAP_B, CH_B, 4)
    vmap_factor = max(1.0, (t_vmap4 - overhead) / (4 * work_bb))

    # per-metric scan constants: same cap pair, lane term cancelled with
    # the mis-fitted lane_time (the expansion grid is metric-independent)
    lane_delta = (k - 1) * (CAP_B - CAP_S) * CH_S * lane_time
    metric_rows, metric_probe = {}, {}
    for metric, key in (("mni", "row_time_mni_s"),
                        ("frac", "row_time_frac_s"),
                        ("mis_luby", "row_time_luby_s")):
        t_s_m = step_time(CAP_S, CH_S, 1, metric)
        t_b_m = step_time(CAP_B, CH_S, 1, metric)
        metric_rows[key] = float(
            max((t_b_m - t_s_m - lane_delta) / (CAP_B - CAP_S), 1e-12))
        metric_probe[f"t_cap4096_ch4_{metric}"] = round(t_b_m, 6)

    return {
        "schema": CALIBRATION_SCHEMA,
        "dispatch_overhead_s": float(overhead),
        "lane_time_s": float(lane_time),
        "row_time_s": float(row_time),
        **metric_rows,
        # schema 3: measured per-run escalation fraction — not a timing
        # probe; `repro.launch.mine` folds the observed value in after
        # each sampled run (`planner.persist_escalation_fraction`) and
        # `write_calibration` carries any existing measurement forward
        "escalation_fraction": None,
        "vmap_factor": float(round(vmap_factor, 3)),
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "source": "benchmarks/calibrate.py",
        "probe": {
            "n": n, "k": k,
            "t_cap512_ch4": round(t_ss, 6),
            "t_cap512_ch64": round(t_sb, 6),
            "t_cap4096_ch4": round(t_bs, 6),
            "t_cap4096_ch64_vmap4": round(t_vmap4, 6),
            **metric_probe,
        },
        # keep the defaults' semantics documented next to the numbers
        "_model": "t_step = dispatch_overhead_s + bucket * ((k-1)*cap*chunk"
                  "*max_chunks*lane_time_s + cap*row_time_s)"
                  " * (vmap_factor if bucket>1)",
    }


def write_calibration(out: Optional[str] = None, iters: int = 20) -> str:
    from repro.core.planner import DEFAULT_CALIBRATION_FILE

    out = out or DEFAULT_CALIBRATION_FILE
    model = fit_cost_model(iters=iters)
    try:
        # a re-fit refreshes the timing constants but must not discard the
        # mining-measured escalation fraction accumulated by launch runs
        with open(out) as f:
            prev = json.load(f).get("escalation_fraction")
        if isinstance(prev, (int, float)):
            model["escalation_fraction"] = float(prev)
    except (OSError, ValueError):
        pass
    with open(out, "w") as f:
        json.dump(model, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# planner calibration → {out}: "
          f"overhead={model['dispatch_overhead_s'] * 1e6:.0f}us "
          f"lane={model['lane_time_s'] * 1e9:.3f}ns "
          f"row={model['row_time_s'] * 1e6:.3f}us "
          f"(mni {model['row_time_mni_s'] * 1e6:.3f} / "
          f"frac {model['row_time_frac_s'] * 1e6:.3f} / "
          f"luby {model['row_time_luby_s'] * 1e6:.3f}) "
          f"vmap_factor={model['vmap_factor']:.2f}")
    return out


def main() -> None:
    write_calibration()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    write_calibration(args.out, iters=args.iters)
    sys.exit(0)
