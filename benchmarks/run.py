"""Benchmark harness driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--smoke] [--out CSV]

Emits ``name,us_per_call,derived[,...]`` CSV blocks per benchmark.  Exits
nonzero if any benchmark module fails (or ``--only`` matches nothing).
``--smoke`` collapses dataset scales/iteration counts to CI-budget sizes
and additionally writes ``BENCH_smoke.json`` at the repo root — a stable
machine-readable trajectory point (per-row name/us_per_call/parity plus
per-module wall time) successive PRs can diff; ``--out`` tees all output
to a CSV file (the CI smoke job uploads it as an artifact).
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
from pathlib import Path


BENCHES = [
    ("execution_time (Fig 9/10)", "benchmarks.bench_execution_time"),
    ("memory (Fig 11)", "benchmarks.bench_memory"),
    ("patterns (Table 2 / Fig 12)", "benchmarks.bench_patterns"),
    ("slider (Fig 13)", "benchmarks.bench_slider"),
    ("similarity (Table 3)", "benchmarks.bench_similarity"),
    ("kernels", "benchmarks.bench_kernels"),
]


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self._streams = streams

    def write(self, s):
        for st in self._streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self._streams:
            st.flush()


class _RowCollector(io.TextIOBase):
    """Parse the CSV convention out of the printed stream.

    ``emit`` prints a header line (first cell ``name``) then rows; comment
    lines start with ``#``.  Collected rows become the stable
    ``BENCH_smoke.json`` entries: name, us_per_call, derived, and parity —
    ``derived`` is each row family's own figure of merit (speedup,
    GFLOP/s, counts …); for the plane-equivalence families
    (``exec_time/expansion_plane/*``, ``kernel/frontier_expand_pallas*``)
    it is the bit-exactness indicator and is surfaced as ``parity``
    (1.0 = bit-exact), null elsewhere.  ``exec_time/sampled/*`` and
    ``exec_time/auto_sampled/*`` rows additionally carry their own
    ``accuracy`` column (1.0 = frequent set identical to the
    forced-batched oracle) — persisted so the regression gate can fail
    on exactness loss, not just latency.
    """

    _PARITY_FAMILIES = ("exec_time/expansion_plane/",
                        "kernel/frontier_expand_pallas")

    def __init__(self):
        self.rows = []
        self._cols = None
        self._buf = ""

    def write(self, s):
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            self._line(line.strip())
        return len(s)

    def _line(self, line):
        if not line or line.startswith("#") or "," not in line:
            return
        cells = [c.strip() for c in line.split(",")]
        if cells[0] == "name":
            self._cols = cells
            return
        if self._cols is None or len(cells) != len(self._cols):
            return
        row = dict(zip(self._cols, cells))
        try:
            us = float(row.get("us_per_call", "nan"))
        except ValueError:
            return
        try:
            derived = float(row.get("derived", "nan"))
        except ValueError:
            derived = float("nan")
        derived_ok = derived == derived  # not NaN
        is_parity = row["name"].startswith(self._PARITY_FAMILIES)
        entry = {
            "name": row["name"],
            "us_per_call": us,
            "derived": derived if derived_ok else None,
            "parity": derived if (derived_ok and is_parity) else None,
        }
        try:
            entry["accuracy"] = float(row["accuracy"])
        except (KeyError, ValueError):
            pass  # rows without an accuracy column stay schema-compatible
        self.rows.append(entry)


def _env_stamp() -> dict:
    """Host/runtime provenance stamped into the trajectory file.

    Two smoke points only diff meaningfully when they ran on comparable
    hardware; the stamp lets the regression gate's reader (and a human
    reading the JSON) tell a real regression from a host change.
    """
    import platform
    import socket

    env = {
        "host": socket.gethostname(),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    try:
        import jax

        env["jax_version"] = jax.__version__
        env["device_count"] = jax.device_count()
        env["device_platforms"] = sorted({d.platform for d in jax.devices()})
    except Exception:  # trajectory must still be written on a broken jax
        env["jax_version"] = None
        env["device_count"] = 0
        env["device_platforms"] = []
    return env


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module names")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes / few iters (CI smoke job)")
    ap.add_argument("--out", default=None,
                    help="also write all output to this CSV file")
    ap.add_argument("--calibrate", action="store_true",
                    help="run the planner micro-calibration pass "
                         "(benchmarks/calibrate.py) before the benchmarks "
                         "and write planner_calibration.json; --smoke "
                         "always runs it")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.smoke:
        # must be set before benchmark modules import benchmarks.common
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    # the trajectory file is only meaningful for a *full* smoke sweep — a
    # partial --only run must not overwrite it with a subset of the rows
    write_trajectory = args.smoke and not args.only

    import importlib

    out_file = open(args.out, "w") if args.out else None
    collector = _RowCollector() if write_trajectory else None
    streams = [sys.stdout]
    if out_file:
        streams.append(out_file)
    if collector:
        streams.append(collector)
    stdout = _Tee(*streams) if len(streams) > 1 else sys.stdout

    failures = 0
    matched = 0
    modules = []
    with contextlib.redirect_stdout(stdout):
        if args.calibrate or (args.smoke and not args.only):
            # planner cost-model fit: constants the execution planner loads
            # (repro.core.planner.load_calibration); smoke keeps it cheap
            print("# === planner calibration [benchmarks.calibrate] ===",
                  flush=True)
            t0 = time.monotonic()
            try:
                from benchmarks.calibrate import write_calibration

                write_calibration(iters=3 if args.smoke else 20)
                ok = True
            except Exception as e:
                failures += 1
                ok = False
                print(f"# FAILED: {e!r}", flush=True)
            wall = time.monotonic() - t0
            modules.append({"module": "benchmarks.calibrate",
                            "wall_s": round(wall, 3), "ok": ok})
            print(f"# ({wall:.1f}s)", flush=True)
        for label, modname in BENCHES:
            if args.only and args.only not in modname:
                continue
            matched += 1
            print(f"# === {label} [{modname}] ===", flush=True)
            t0 = time.monotonic()
            ok = True
            try:
                importlib.import_module(modname).main()
            except Exception as e:  # surface but keep going
                failures += 1
                ok = False
                print(f"# FAILED: {e!r}", flush=True)
            wall = time.monotonic() - t0
            modules.append({"module": modname, "wall_s": round(wall, 3),
                            "ok": ok})
            print(f"# ({wall:.1f}s)", flush=True)
        if args.only and matched == 0:
            print(f"# ERROR: --only {args.only!r} matched no benchmark",
                  flush=True)
            failures += 1
    if out_file:
        out_file.close()
    if collector is not None:
        # the perf-trajectory point successive PRs diff (stable schema)
        trajectory = {
            "schema": 1,
            "smoke": True,
            "env": _env_stamp(),
            "failures": failures,
            "modules": modules,
            "rows": collector.rows,
        }
        path = Path(__file__).resolve().parent.parent / "BENCH_smoke.json"
        path.write_text(json.dumps(trajectory, indent=2, sort_keys=True) + "\n")
        print(f"# wrote {path}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
