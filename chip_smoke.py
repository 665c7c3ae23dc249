#!/usr/bin/env python3
"""Bring-up check: the mining path runs on a TPU and gives exact results.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the distributed plane on four chips

One chip, in one process:

  1. device check — exits non-zero, printing no result, unless JAX's first
     device is a TPU;
  2. reference — a small exact case (gnutella ×0.05, metrics mis and mni)
     mined on the TPU by the auto planner and by the sequential oracle, and
     on the host CPU: frequent sets and supports must be identical;
  3. real size — the paper's largest Table-1 graph (mico at scale 1.0:
     100,000 vertices, 1,080,298 edges, 29 labels) mined through the
     command-line entry point `repro.launch.mine` with mis, auto planning
     and 2-vertex patterns.  It must finish in time, find frequent
     patterns and never fall back to another plane.

``--chips 4`` runs only the multi-chip path: the distributed plane
(mis_luby, roots sharded over a 4-device mesh) on the mico graph at the
same σ and pattern size, against the batched plane on one device;
supports must be identical.

Lines before the last are set-up and sanity figures (wall time, compiled
programs and compile seconds, peak device bytes), not benchmark numbers.
The last line is one JSON object: ``{"ok": true, "device": {...}}``.
Artifacts go to ``chiprun_out/``.  JAX's persistent compile cache is on
(`repro.launch.compile_cache`): a second run compiles far less.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# The script must end within 1200 s, compilation included; the real-size
# phase gets what the earlier phases leave of this budget as its
# --time-limit, so an overrun shows as a timed-out (failed) run.
BUDGET_S = 1080.0
# Patterns up to 3 vertices: every further size adds step programs to
# compile, and compiling is most of this phase's time on the chip.
REF = dict(dataset="gnutella", scale=0.05, seed=0, max_size=3,
           sigma=25)
# mico at the paper's size, level 1 only: all 1,276 two-vertex candidates
# over 25 root blocks.  A P=64 step there takes up to 1.9 s on a v5e, and
# one 3-vertex step at the hub block did not finish in minutes, so level 2
# cannot fit the budget (CHANGES.md, PR 11).  σ = 790 with λ = 0 keeps 48
# frequent patterns.
MICO = dict(dataset="mico", scale=1.0, seed=0, max_size=2, sigma=790,
            lam=0.0, cap=16384)


class Failed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def compiles_since(snap) -> str:
    """Backend compiles and persistent-cache hits since ``snap``, from the
    program's own compile counter (`repro.core.tracing`)."""
    from repro.core import tracing

    return str(tracing.compile_totals() - snap)


def compile_snapshot():
    from repro.core import tracing

    return tracing.compile_totals()


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def supports(res) -> dict:
    from repro.core.canonical import canonical_key

    return {canonical_key(p): int(s) for p, s in res.frequent}


def device_check(jax):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[smoke] no TPU: JAX's first device is {devices[0]!r}",
              file=sys.stderr)
        raise SystemExit(2)

    def version(dist):
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            return "absent"

    say(f"device {devices[0].device_kind!r} x{len(devices)} "
        f"(platform {devices[0].platform}); jax {jax.__version__}, "
        f"jaxlib {version('jaxlib')}, libtpu {version('libtpu')}")
    return devices


def calibration_line(jax) -> None:
    from repro.core.planner import load_calibration

    cm = load_calibration()
    dev = jax.devices()[0]
    priced = cm.backend in (None, dev.platform)
    say(f"planner calibration: {cm.source}, fitted for {cm.backend} "
        f"(running on {dev.platform}, {dev.device_kind!r}): auto "
        + ("prices planes with it" if priced else
           "runs the batched plane, with no constants for this platform"))


def programs() -> int:
    from repro.core import batched

    return batched.program_cache_stats().misses


def run_twice(mine, g, cfg, label):
    """Mine once cold (compiles included) and once warm; returns the warm
    result after checking both agree."""
    snap = compile_snapshot()
    t0 = time.monotonic()
    first = mine(g, cfg)
    t1 = time.monotonic()
    warm = mine(g, cfg)
    t2 = time.monotonic()
    check(supports(first) == supports(warm), f"{label}: warm run differs")
    say(f"  {label}: first {t1 - t0:.2f} s, warm {t2 - t1:.2f} s, "
        f"{compiles_since(snap)}; {len(warm.frequent)} frequent of "
        f"{warm.searched} searched on {warm.device}")
    return warm


def reference_phase(jax) -> None:
    """Small exact case: TPU auto ≡ TPU sequential ≡ host CPU."""
    from repro.core import MatchConfig, MiningConfig, mine
    from repro.data.synthetic import paper_dataset

    g = paper_dataset(REF["dataset"], scale=REF["scale"], seed=REF["seed"])
    say(f"reference: {REF['dataset']} x{REF['scale']}: |V|={g.n} "
        f"|E|={g.n_edges} labels={g.n_labels}")
    cpu = jax.devices("cpu")[0]
    for metric in ("mis", "mni"):
        base = MiningConfig(sigma=REF["sigma"], metric=metric,
                            max_pattern_size=REF["max_size"],
                            match=MatchConfig.for_graph(g))
        got = {}
        for label, execution, device in (
                ("tpu auto", "auto", None),
                ("tpu sequential", "sequential", None),
                ("cpu auto", "auto", cpu)):
            cfg = dataclasses.replace(base, execution=execution)
            ctx = (jax.default_device(device) if device is not None
                   else contextlib.nullcontext())
            with ctx:
                res = run_twice(mine, g, cfg,
                                f"{metric} sigma={cfg.sigma} {label}")
            want = "cpu" if device is not None else "tpu"
            check(res.device.startswith(want + ":"),
                  f"{metric} {label} ran on {res.device}")
            check(not res.timed_out, f"{metric} {label} timed out")
            got[label] = supports(res)
        check(len(got["tpu auto"]) > 0, f"{metric}: nothing frequent")
        same = all(v == got["tpu auto"] for v in got.values())
        check(same, f"{metric}: frequent sets differ across planes/devices: "
              + ", ".join(f"{k}={len(v)}" for k, v in got.items()))
        say(f"  {metric}: {len(got['tpu auto'])} frequent patterns, "
            f"supports identical on tpu auto, tpu sequential and cpu")
        if metric == "mis":
            # the sequential plane compiles a handful of programs: enough
            # to show the disk cache without re-tracing auto's hundred
            cache_check(jax, mine, g,
                        dataclasses.replace(base, execution="sequential"),
                        got["tpu auto"])


def cache_check(jax, mine, g, cfg, want) -> None:
    """The persistent compile cache: with every compiled program dropped
    from memory, mining again must load its programs from the disk cache."""
    from repro.core import batched

    jax.clear_caches()
    batched.clear_program_cache()
    snap = compile_snapshot()
    t0 = time.monotonic()
    res = mine(g, cfg)
    check(supports(res) == want, "rerun after clearing caches differs")
    say(f"  mis tpu sequential again, in-memory caches cleared: "
        f"{time.monotonic() - t0:.2f} s, {compiles_since(snap)}")
    check(compile_snapshot().cache_hits > snap.cache_hits,
          "no program came from the persistent compile cache")


def mico_phase(jax, deadline: float) -> None:
    """The paper's largest graph through the command-line entry point."""
    from repro.launch import mine as mine_cli

    out = OUT / "mine_mico.json"
    limit = deadline - time.monotonic()
    check(limit > 60, f"only {limit:.0f} s left for the real-size phase")
    argv = ["--dataset", MICO["dataset"], "--scale", str(MICO["scale"]),
            "--seed", str(MICO["seed"]), "--metric", "mis",
            "--execution", "auto", "--max-size", str(MICO["max_size"]),
            "--sigma", str(MICO["sigma"]), "--lam", str(MICO["lam"]),
            "--cap", str(MICO["cap"]),
            "--time-limit", f"{limit:.0f}", "--json", str(out)]
    say("real size: python -m repro.launch.mine " + " ".join(argv))
    snap, n_prog = compile_snapshot(), programs()
    t0 = time.monotonic()
    rc = mine_cli.main(argv)
    wall = time.monotonic() - t0
    check(rc == 0, f"repro.launch.mine exited {rc}")
    d = json.loads(out.read_text())
    say(f"  mico: wall {wall:.2f} s (mining {d['elapsed_s']:.2f} s, graph "
        f"build and candidate set-up the rest), {compiles_since(snap)}, "
        f"{programs() - n_prog} batched step programs")
    say(f"  mico: {d['n_frequent']} frequent of {d['searched']} searched, "
        f"{d['dispatches']} dispatches, per level (candidates, frequent, "
        f"wall s, plane, cap): " + ", ".join(
            f"L{k}=({v['candidates']}, {v['frequent']}, {v['wall_s']:.1f}, "
            f"{v.get('plan', {}).get('plane')}, {v.get('plan', {}).get('cap')})"
            for k, v in sorted(d["per_level"].items(), key=lambda kv:
                               int(kv[0]))))
    fallbacks = [e for e in d["health"].get("events", [])
                 if e.get("kind") == "plane_fallback"]
    check(not d["timed_out"], "mico run timed out")
    check(d["n_frequent"] > 0, "mico run found no frequent pattern")
    check(not fallbacks, f"plane fallback: {fallbacks}")
    check(d["device"].startswith("tpu:"), f"mico ran on {d['device']}")
    say(f"  mico: device peak {peak_bytes(jax.devices()[0])} bytes in use "
        f"(process so far), analytic peak_device_bytes "
        f"{d['peak_device_bytes']}")


def distributed_phase(jax) -> None:
    """mis_luby on a 4-chip mesh against the batched plane on one chip."""
    from repro.core import MatchConfig, MiningConfig, mine
    from repro.core.distributed import mining_mesh
    from repro.data.synthetic import paper_dataset

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    mesh = mining_mesh()
    mesh_devs = list(mesh.devices.flat)
    check(len({d.id for d in mesh_devs}) == 4
          and all(d.platform == "tpu" for d in mesh_devs),
          f"mesh is not 4 distinct TPUs: {mesh_devs}")
    say(f"mesh {dict(mesh.shape)} over {[d.id for d in mesh_devs]}")
    g = paper_dataset(MICO["dataset"], scale=MICO["scale"], seed=MICO["seed"])
    base = MiningConfig(sigma=MICO["sigma"], lam=MICO["lam"],
                        metric="mis_luby", max_pattern_size=MICO["max_size"],
                        match=MatchConfig.for_graph(g, cap=MICO["cap"]))
    got = {}
    for execution in ("distributed", "batched"):
        cfg = dataclasses.replace(base, execution=execution)
        snap = compile_snapshot()
        t0 = time.monotonic()
        res = mine(g, cfg)
        wall = time.monotonic() - t0
        kinds = [e.kind for e in res.health.events]
        check("plane_fallback" not in kinds,
              f"{execution}: plane fallback: {res.health.to_dict()}")
        check(not res.timed_out, f"{execution} timed out")
        dispatches = sum(int(v.get("dispatches", 0))
                         for v in res.per_level.values())
        say(f"  mis_luby {execution}: wall {wall:.2f} s, "
            f"{compiles_since(snap)}; {len(res.frequent)} frequent of "
            f"{res.searched} searched, {dispatches} dispatches, "
            f"{programs()} batched step programs so far")
        got[execution] = supports(res)
        if execution == "distributed":
            # every device held the graph (replicated on the mesh), and each
            # was given root blocks: a dispatch hands one block of the
            # schedule to each mesh position, and the graph has at least
            # one block per device
            peaks = [peak_bytes(d) for d in mesh_devs]
            n_blocks = -(-g.n // base.match.root_block)
            say(f"  per-device peak bytes in use: {peaks}; {n_blocks} root "
                f"blocks over {len(mesh_devs)} devices")
            check(dispatches > 0 and n_blocks >= len(mesh_devs)
                  and min(peaks) > 0, "a mesh device did no work")
    check(len(got["batched"]) > 0, "nothing frequent")
    check(got["distributed"] == got["batched"],
          "distributed supports differ from batched")
    say(f"  {len(got['batched'])} frequent patterns, supports identical on "
        f"the 4-chip distributed plane and the 1-chip batched plane")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    cache_dir = enable_compile_cache()

    import jax

    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        # the reference phase also mines on the host CPU, in this process
        jax.config.update("jax_platforms", platforms + ",cpu")

    devices = device_check(jax)
    OUT.mkdir(exist_ok=True)
    say(f"compile cache: {cache_dir}")
    from repro.core import tracing

    tracing.watch_compiles()
    calibration_line(jax)
    try:
        if args.chips == 4:
            distributed_phase(jax)
        else:
            t0 = time.monotonic()
            reference_phase(jax)
            say(f"reference phase: {time.monotonic() - t0:.2f} s; device "
                f"peak {peak_bytes(devices[0])} bytes in use")
            mico_phase(jax, t_start + BUDGET_S)
    except Failed as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(f"total {time.monotonic() - t_start:.2f} s, "
        f"{compiles_since(tracing.CompileTotals())}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
