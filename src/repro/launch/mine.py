"""FLEXIS mining launcher — the paper's end-to-end driver.

    PYTHONPATH=src python -m repro.launch.mine --dataset gnutella \
        --scale 0.05 --sigma 30 --lam 0.4 --metric mis

Loads (synthesizes) a dataset, mines frequent subgraphs with the configured
metric/generation strategy, prints the paper's telemetry (per-level counts,
searched patterns, memory, time).  ``--execution distributed`` shards match
roots over every local device; ``--checkpoint-dir`` makes the run a
resumable *session* (`repro.runtime`) that snapshots the full mining state
at level-boundary and block/super-block granularity, and ``--resume``
continues one after a kill — on the same or a different device count —
with a bit-identical result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.core import MatchConfig, MiningConfig, mine
from repro.core.flexis import tau_threshold
from repro.data.synthetic import PAPER_DATASETS, paper_dataset
from repro.launch.compile_cache import enable_compile_cache

# distinct "preempted, resumable" status: the run was stopped on request
# (SIGTERM/SIGINT) after committing a final snapshot — rerunning the same
# command line resumes it.  75 = BSD EX_TEMPFAIL ("temporary failure,
# retry"), which is exactly the contract.
EXIT_PREEMPTED = 75


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="gnutella",
                    choices=sorted(PAPER_DATASETS))
    ap.add_argument("--scale", type=float, default=0.05,
                    help="dataset size multiplier (1.0 = paper size)")
    ap.add_argument("--sigma", type=int, default=20)
    ap.add_argument("--lam", type=float, default=0.4)
    ap.add_argument("--metric", default="mis",
                    choices=["mis", "mis_luby", "mni", "frac"])
    ap.add_argument("--generation", default="merge",
                    choices=["merge", "edge_ext"])
    ap.add_argument("--execution", default="auto",
                    choices=["auto", "batched", "sequential", "distributed",
                             "sampled"],
                    help="data plane: cost-model planner picks per level "
                         "(auto, default; decisions recorded in per_level "
                         "and --json), one vmapped program per same-k "
                         "candidate group (batched), the paper's "
                         "per-pattern loop (sequential oracle), match "
                         "roots sharded over every local device "
                         "(distributed; forces metric=mis_luby), or a "
                         "weighted root-block sample with exact escalation "
                         "(sampled; same frequent set as batched — see "
                         "--sample-fraction/--confidence)")
    ap.add_argument("--sample-fraction", type=float, default=0.25,
                    help="sampled plane: target fraction of root blocks "
                         "drawn per level (1.0 degenerates to the exact "
                         "batched plane)")
    ap.add_argument("--confidence", type=float, default=0.95,
                    help="sampled plane: nominal CI level of the support "
                         "estimator — patterns whose interval reaches tau "
                         "escalate to the exact plane")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="sampled plane: RNG key root of the per-level "
                         "block draws (part of the session fingerprint)")
    ap.add_argument("--sample-rounds", type=int, default=3,
                    help="sampled plane: max adaptive draw rounds per "
                         "level — each round doubles block coverage for "
                         "the still-undecided patterns until the "
                         "undecided set stops shrinking (1 = the single "
                         "--sample-fraction draw)")
    ap.add_argument("--root-order", default="degree",
                    choices=["degree", "vertex"],
                    help="root-block schedule: highest max-out-degree "
                         "blocks first (degree, default — τ early exit "
                         "fires sooner) or legacy vertex-id order")
    ap.add_argument("--calibration", default=None,
                    help="planner calibration JSON (benchmarks/calibrate.py"
                         "); default: $REPRO_PLANNER_CALIBRATION, then "
                         "./planner_calibration.json, then built-in "
                         "defaults")
    ap.add_argument("--expansion", default="xla",
                    choices=["xla", "pallas"],
                    help="expansion plane inside match_block: per-chunk XLA "
                         "op pipeline (reference) or the fused Pallas "
                         "frontier kernel — bit-identical to the "
                         "single-phase xla pipeline (when a level overflows "
                         "cap, truncation content may differ from the "
                         "two-phase xla pipeline; overflow is always "
                         "flagged); interpreted off-TPU, refused on a TPU "
                         "(Mosaic cannot lower it yet)")
    ap.add_argument("--root-block", type=int, default=None,
                    help="root-block width override (default: sized by "
                         "MatchConfig.for_graph).  The sampled plane draws "
                         "at root-block granularity — a graph the default "
                         "geometry covers in one block has nothing to "
                         "sample, so shrink this to turn estimation on")
    ap.add_argument("--max-size", type=int, default=4)
    ap.add_argument("--time-limit", type=float, default=1800.0,
                    help="paper uses a 30-minute timeout")
    ap.add_argument("--cap", type=int, default=16384)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, help="write result JSON here")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="run as a resumable session: snapshot the full "
                         "mining state into this directory (atomic "
                         "manifest/COMMIT protocol, see repro.runtime)")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="snapshot cadence in carried-state updates (root "
                         "blocks on the batched plane, super-blocks on the "
                         "distributed plane); 0 = level boundaries only")
    ap.add_argument("--resume", action="store_true",
                    help="require a committed snapshot in --checkpoint-dir "
                         "and continue it (without this flag a snapshot is "
                         "still picked up when present; --resume makes a "
                         "missing one an error instead of a fresh start)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    if args.execution == "distributed" and args.metric != "mis_luby":
        print(f"[mine] execution=distributed forces metric=mis_luby "
              f"(was {args.metric})")
        args.metric = "mis_luby"
    if args.calibration:
        import os

        from repro.core.planner import CALIBRATION_ENV

        os.environ[CALIBRATION_ENV] = args.calibration

    t0 = time.monotonic()
    g = paper_dataset(args.dataset, scale=args.scale, seed=args.seed)
    print(f"[mine] {args.dataset}×{args.scale}: |V|={g.n} |E|={g.n_edges} "
          f"labels={g.n_labels} (load {time.monotonic() - t0:.1f}s)")

    import dataclasses as _dc

    cfg = MiningConfig(
        sigma=args.sigma, lam=args.lam, metric=args.metric,
        generation=args.generation, max_pattern_size=args.max_size,
        time_limit_s=args.time_limit, execution=args.execution,
        root_order=args.root_order,
        sample_fraction=args.sample_fraction, confidence=args.confidence,
        sample_seed=args.sample_seed, sample_rounds=args.sample_rounds,
        match=_dc.replace(
            MatchConfig.for_graph(g, cap=args.cap, expansion=args.expansion),
            **({"root_block": args.root_block}
               if args.root_block is not None else {})),
    )
    if args.checkpoint_dir:
        import signal

        from repro.runtime import MiningSession, PreemptedError
        from repro.train import checkpoint as ckpt

        session = MiningSession(
            g, cfg, args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            resume="must" if args.resume else "auto",
            meta={"dataset": args.dataset, "scale": args.scale,
                  "seed": args.seed})

        # graceful shutdown: SIGTERM/SIGINT ask the session to stop at the
        # next snapshot point instead of dying mid-write; the session cuts
        # one final COMMIT'd snapshot and raises PreemptedError
        def _on_signal(signum, frame):
            print(f"[mine] caught signal {signum}: finishing the current "
                  f"snapshot, then exiting resumable", flush=True)
            session.request_preempt()

        prev_handlers = {s: signal.signal(s, _on_signal)
                         for s in (signal.SIGTERM, signal.SIGINT)}
        try:
            res = session.run()
        except PreemptedError as e:
            ckpt.wait_pending(raise_errors=False)  # flush async writes
            print(f"[mine] preempted: {e}")
            print(f"[mine] session: {session.snapshots_written} snapshots "
                  f"written under {args.checkpoint_dir}")
            return EXIT_PREEMPTED
        finally:
            for s, h in prev_handlers.items():
                signal.signal(s, h)
        print(f"[mine] session: {session.snapshots_written} snapshots "
              f"written under {args.checkpoint_dir}")
    else:
        res = mine(g, cfg)

    print(f"[mine] done in {res.elapsed_s:.2f}s on {res.device}"
          f"{' (TIMED OUT)' if res.timed_out else ''}")
    print(f"[mine] frequent patterns: {len(res.frequent)}  "
          f"searched: {res.searched}  peak device bytes: "
          f"{res.peak_device_bytes / 2**20:.1f} MiB")
    if res.health.degraded:
        print(f"[mine] health: {res.health.to_dict()['counts']} — results "
              f"are exact; see --json health.events for detail")
    for lvl, st in res.per_level.items():
        pretty = {k: (round(v, 3) if isinstance(v, float) else v)
                  for k, v in st.items()
                  if k != "block_peaks"}  # long per-block list; JSON only
        print(f"[mine]   level {lvl}: {pretty}")
    for pat, sup in res.frequent[:10]:
        tau = tau_threshold(args.sigma, args.lam, pat.k)
        print(f"[mine]   k={pat.k} sup={sup} (tau={tau}) "
              f"labels={pat.labels.tolist()} edges={pat.edges()}")
    if len(res.frequent) > 10:
        print(f"[mine]   … and {len(res.frequent) - 10} more")

    # warm-start future pricing: fold the measured escalation fraction of
    # this run's sampled levels into the calibration file named by
    # --calibration or $REPRO_PLANNER_CALIBRATION (schema 3) — the
    # planner's `esc_prior()` reads it back instead of the built-in
    # ESCALATION_PRIOR constant.  The tracked default file is never
    # rewritten, so two runs of one query plan alike.
    samp = [v["sampled"] for v in res.per_level.values()
            if isinstance(v.get("sampled"), dict)
            and not v["sampled"].get("exact", False)]
    decided = sum(int(d.get("escalated", 0)) + int(d.get("pruned", 0))
                  for d in samp)
    if decided > 0 and not res.timed_out:
        from repro.core.planner import persist_escalation_fraction

        measured = sum(int(d.get("escalated", 0)) for d in samp) / decided
        where = persist_escalation_fraction(measured, path=args.calibration)
        if where:
            print(f"[mine] calibration: measured escalation fraction "
                  f"{measured:.3f} folded into {where}")

    if args.json:
        out = {
            "dataset": args.dataset, "scale": args.scale,
            "sigma": args.sigma, "lam": args.lam, "metric": args.metric,
            "generation": args.generation, "execution": args.execution,
            "elapsed_s": res.elapsed_s, "timed_out": res.timed_out,
            "n_frequent": len(res.frequent), "searched": res.searched,
            "peak_device_bytes": res.peak_device_bytes,
            "device": res.device,
            "dispatches": sum(int(v.get("dispatches", 0))
                              for v in res.per_level.values()),
            # sampled plane: escalations across levels (per-level detail —
            # sample fraction, CI width, pruned count — sits in each
            # per_level[...]["sampled"] dict)
            "escalated": sum(int(v.get("sampled", {}).get("escalated", 0))
                             for v in res.per_level.values()),
            "estimated_patterns": sum(1 for st in res.stats if st.estimated),
            # every recovery/fallback/retry the run performed (see
            # core/health.py and README "Run health"); deliberately NOT
            # part of the resume bit-identity contract — a resumed run
            # records the recoveries the uninterrupted oracle never needed
            "health": res.health.to_dict(),
            "per_level": {str(k): v for k, v in res.per_level.items()},
            # deterministic digest of the mined set: (k, support) pairs in
            # result order — what the CI resume-smoke diffs against an
            # uninterrupted run
            "frequent": [[p.k, int(s)] for p, s in res.frequent],
        }
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
