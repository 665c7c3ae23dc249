#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file, its traffic file ``bench/traffic/<mix>.json``, the
rule file ``bench/references/<metric>.py`` of the traffic's metric and one
reader ``bench/metrics/<metric>.py`` per reported metric.  The run makes
the graph from ``--seed``, warms up (``setup_s``), drives the program for
``--seconds``, checks every answer the window produced against the plain
reference (``bench/reference.py`` under the metric's rule), and prints one
JSON line last.  With ``--trace 1`` the window runs under the profiler and
the line carries the per-layer metrics instead of the end-to-end ones.

It refuses to report from anything but a TPU: without one, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.  So
it does, before set-up, for a traffic file the harness cannot check: a
metric with no rule file, or a key the driver neither reads nor passes on.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import drive  # noqa: E402  (after the paths above)

# Each number compared with the reference, and its limit: the answers are
# exact, so any wrong one fails.
LIMITS = {"wrong_supports": 0, "wrong_frequent": 0, "wrong_candidates": 0,
          "failed_queries": 0}


class NoChip(Exception):
    pass


def spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_files(bench: dict, workload: str):
    """(cell, configuration, traffic) dicts of ``workload``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(ROOT / config["file"]) as f:
        config_data = json.load(f)
    with open(ROOT / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, config_data, traffic


def cell_metrics(bench: dict, workload: str, traced: bool) -> List[dict]:
    """The metrics ``workload`` reports: its end-to-end ones untraced, its
    per-layer ones traced."""
    def listed(m: dict) -> bool:
        return workload in m.get("workloads", [workload])

    ends = [m for m in bench["end_to_end"] if listed(m)]
    if not traced:
        return ends
    moved = {m["name"] for m in ends}
    return [m for m in bench["per_layer"]
            if listed(m) and ("workloads" in m or m["moves"] in moved)]


def reader(name: str, directory: Optional[Path] = None):
    """``read(run)`` of ``<directory>/metrics/<name>.py`` (default: bench/)."""
    path = (directory or ROOT / "bench") / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def devices_for(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} x {devices[0].platform} "
                     f"({devices[0].device_kind})")
    return devices[:chips]


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        check_device: bool = True, bench: Optional[dict] = None,
        files=None, rules: Optional[Path] = None,
        t_start: Optional[float] = None) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    ``bench``, ``files`` and ``rules`` stand in for BENCHMARK.json, the
    cell's files and bench/references/ (tests use them to run a cell at a
    size a CPU can hold, or under another rule).
    """
    t_start = time.perf_counter() if t_start is None else t_start
    bench = spec() if bench is None else bench
    cell, config, traffic = cell_files(bench, workload) if files is None else files
    drive.check(traffic, rules)
    metrics = cell_metrics(bench, workload, traced)
    readers = {m["name"]: reader(m["name"]) for m in metrics}

    import jax

    from bench import jaxmon, rmat, trace

    devices = (devices_for(jax, cell["chips"]) if check_device
               else jax.devices()[:cell["chips"]])
    kind = devices[0].device_kind
    counter = jaxmon.CompileCounter(jax)

    graph = rmat.config_graph(config, seed)
    c = drive.Cell(config, traffic, graph, rules)
    c.warm_up()
    setup_s = time.perf_counter() - t_start
    snap = counter.snapshot()
    say(f"setup: {counter.since((0, 0.0, 0))}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            # no Python function tracing: it would slow the host it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                queries = c.run(seconds)
            t_read = time.perf_counter()
            tr = trace.load(trace_dir)
            say(f"trace: {sum(len(d['ops']) for d in tr['devices'].values())} "
                f"device ops, read in {time.perf_counter() - t_read:.1f} s")
        else:
            queries, tr = c.run(seconds), None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    in_window = counter.since(snap)
    peak = max(jaxmon.peak_bytes(d) for d in devices)

    whole = [not q.result.timed_out for q in queries]
    answers = c.answers(queries)
    checks = drive.compare(c.checker(), answers, whole)
    if traffic["loop"] == "cut":
        attempted = sum(len(q.result.stats) for q in queries)
        failed = min(attempted, checks["wrong_supports"] + checks["wrong_candidates"])
    else:
        attempted, failed = len(queries), checks["failed_queries"]
    correct = attempted > 0 and all(checks[k] <= LIMITS[k] for k in LIMITS)

    # what a metric reader sees of the run
    r = SimpleNamespace(workload=workload, queries=queries, window_s=c.window_s,
            setup_s=setup_s, spans=c.spans, trace=tr, cell=c, graph=graph,
            device_kind=kind)
    values: Dict[str, dict] = {}
    for m in metrics:
        v = readers[m["name"]](r)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": values, "device": device}
    if tr is not None:
        busy, w = trace.busy_ns(tr), trace.window(tr)
        if busy is not None and w is not None:
            device["busy_s"] = busy / 1e9
            device["window_s"] = (w[1] - w[0]) / 1e9
        line["breakdown"] = {"device_ops": trace.top_ops(tr),
                             "idle_gaps": trace.idle_gaps(tr)}
    lat = [q.latency_s for q in queries]
    say(f"window {c.window_s:.3f} s, {len(queries)} queries "
        f"(latency min {min(lat):.3f} s, max {max(lat):.3f} s), "
        f"{in_window}")
    say(f"setup {setup_s:.3f} s; device peak {peak} bytes; "
        f"{attempted} answers checked, {failed} wrong")
    line["checks"] = {k: {"value": int(checks[k]), "limit": LIMITS[k]}
                      for k in LIMITS}
    for k in LIMITS:
        say(f"check {k} {checks[k]} limit {LIMITS[k]}")
    return line


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # JAX's persistent cache, in the checkout or where
    # $JAX_COMPILATION_CACHE_DIR says: only a cell's first run compiles
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start)
    except (NoChip, drive.BadTraffic) as e:
        say(f"no result: {e}")
        return 3
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
