import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ first lines, before any jax import (device count locks on first init)

"""Dry-run for the paper's own workload: one distributed mining step
(match_block per device + global Luby mIS rounds) lowered + compiled on the
production meshes.  Proves the technique's collective pattern (per-round
all-reduce(min) over the |V| priority array + bitmap psum) partitions.

    PYTHONPATH=src python -m repro.launch.dryrun_flexis [--multi-pod]
"""
import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs import flexis_paper as FP
from repro.core.graph import DeviceGraph
from repro.core.matcher import MatchConfig
from repro.core import mis as mis_lib
from repro.core.distributed import sharded_mis_step
from repro.core.plan import PatternPlan
from repro.launch.dryrun import collective_bytes_from_hlo
from repro.launch.mesh import make_production_mesh, mesh_device_count


def abstract_graph(n: int, m: int) -> DeviceGraph:
    sds = jax.ShapeDtypeStruct
    return DeviceGraph(
        n=n,
        labels=sds((n,), jnp.int32),
        out_indptr=sds((n + 1,), jnp.int32),
        out_indices=sds((m,), jnp.int32),
        in_indptr=sds((n + 1,), jnp.int32),
        in_indices=sds((m,), jnp.int32),
    )


def abstract_plan(k: int) -> PatternPlan:
    sds = jax.ShapeDtypeStruct
    return PatternPlan(
        k=k,
        root_label=sds((), jnp.int32),
        root_min_out=sds((), jnp.int32),
        root_min_in=sds((), jnp.int32),
        anchor_pos=sds((k,), jnp.int32),
        anchor_out=sds((k,), jnp.bool_),
        cand_label=sds((k,), jnp.int32),
        min_out=sds((k,), jnp.int32),
        min_in=sds((k,), jnp.int32),
        check_out=sds((k, k), jnp.bool_),
        check_in=sds((k, k), jnp.bool_),
        order=tuple(range(k)),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    rc = 0
    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        ndev = mesh_device_count(mesh)
        axis = "roots"
        flat = Mesh(mesh.devices.reshape(-1), (axis,),
                    axis_types=(AxisType.Auto,))
        cfg = MatchConfig(cap=FP.MATCH_CAP, root_block=FP.ROOT_BLOCK,
                          chunk=FP.CHUNK, max_chunks=FP.MAX_CHUNKS,
                          bisect_iters=FP.BISECT_ITERS)
        n, m, k = FP.N_VERTICES, FP.N_EDGES, FP.PATTERN_K
        g = abstract_graph(n, m)
        plan = abstract_plan(k)
        starts = jax.ShapeDtypeStruct((ndev,), jnp.int32)
        bitmap = jax.ShapeDtypeStruct(((n + 31) // 32,), jnp.uint32)
        count = jax.ShapeDtypeStruct((), jnp.int32)
        tau = jax.ShapeDtypeStruct((), jnp.int32)

        def step(g_, plan_, starts_, bitmap_, count_, tau_):
            return sharded_mis_step(g_, plan_, starts_, bitmap_, count_,
                                    tau_, cfg=cfg, k=k, n=n, axis=axis,
                                    mesh=flat)

        t0 = time.monotonic()
        with flat:
            lowered = jax.jit(
                step,
                in_shardings=(
                    jax.tree_util.tree_map(lambda _: NamedSharding(flat, P()), g),
                    jax.tree_util.tree_map(lambda _: NamedSharding(flat, P()), plan),
                    NamedSharding(flat, P(axis)),
                    NamedSharding(flat, P()),
                    NamedSharding(flat, P()),
                    NamedSharding(flat, P()),
                ),
            ).lower(g, plan, starts, bitmap, count, tau)
            compiled = lowered.compile()
        dt = time.monotonic() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        colls = collective_bytes_from_hlo(compiled.as_text())
        rec = {
            "arch": "flexis-mining", "shape": f"mico_k{k}",
            "mesh": "2x16x16" if mp else "16x16", "multi_pod": mp,
            "kind": "mine", "status": "ok", "devices": ndev,
            "compile_seconds": round(dt, 1),
            "memory": {
                "argument_size_in_bytes": int(mem.argument_size_in_bytes),
                "temp_size_in_bytes": int(mem.temp_size_in_bytes),
                "per_device_total_bytes": int(mem.argument_size_in_bytes
                                              + mem.temp_size_in_bytes),
            },
            "cost": {"flops": float(cost.get("flops", -1)),
                     "bytes_accessed": float(cost.get("bytes accessed", -1))},
            "collectives": colls,
            # one step ≈ cap·chunks·k gathers + bisect work; report matcher
            # work as "model flops" proxy: candidate checks × ops
            "model_flops": float(ndev * cfg.cap * cfg.chunk * cfg.max_chunks
                                 * k * (2 * cfg.bisect_iters + 8)),
        }
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        tag = f"flexis-mining__mico_k{k}__{'mp' if mp else 'sp'}"
        (out / f"{tag}.json").write_text(json.dumps(rec, indent=2))
        print(f"[dryrun-flexis] {tag}: ok "
              f"mem/dev={rec['memory']['per_device_total_bytes']/2**30:.2f}GiB "
              f"coll/dev={colls['total']/2**20:.1f}MiB "
              f"(compile {dt:.0f}s)", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
