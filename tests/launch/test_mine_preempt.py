"""Graceful-shutdown CLI contract: SIGTERM → final snapshot → exit 75 →
rerun resumes to the bit-identical result.

Subprocess-based on purpose: the signal handler installation, the
PreemptedError → EXIT_PREEMPTED translation, and the async-save flush all
live in `repro.launch.mine` and only compose for real across an actual
process boundary.
"""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
EXIT_PREEMPTED = 75  # keep in sync with repro.launch.mine


def _cmd(json_path, ckpt_dir=None):
    cmd = [sys.executable, "-m", "repro.launch.mine",
           "--dataset", "gnutella", "--scale", "0.02", "--sigma", "10",
           "--lam", "0.6", "--max-size", "3", "--cap", "4096",
           "--execution", "batched", "--json", str(json_path)]
    if ckpt_dir is not None:
        cmd += ["--checkpoint-dir", str(ckpt_dir), "--checkpoint-every", "1"]
    return cmd


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def _norm(json_path):
    d = json.loads(Path(json_path).read_text())
    d.pop("elapsed_s", None)
    d.pop("health", None)  # a resumed run records recoveries; oracle never
    # counters and span times describe this process's share of the run
    d.pop("counters", None)
    d.pop("span_self_s", None)
    for lvl in d.get("per_level", {}).values():
        lvl.pop("wall_s", None)
    return d


def test_sigterm_preempts_resumably(tmp_path):
    env = _env()
    oracle_json = tmp_path / "oracle.json"
    subprocess.run(_cmd(oracle_json), env=env, check=True,
                   capture_output=True, text=True, timeout=600, cwd=ROOT)

    ckpt_dir = tmp_path / "ckpt"
    out_json = tmp_path / "out.json"
    proc = subprocess.Popen(_cmd(out_json, ckpt_dir), env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, cwd=ROOT)
    # wait until at least one snapshot committed, then ask it to stop
    deadline = time.time() + 300
    while (time.time() < deadline and proc.poll() is None
           and not list(ckpt_dir.glob("step_*/COMMIT"))):
        time.sleep(0.1)
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    output, _ = proc.communicate(timeout=600)

    # either we caught it mid-run (preempted, resumable) or the run was
    # simply faster than the first COMMIT poll (finished clean) — both are
    # valid terminal states of the contract
    assert proc.returncode in (0, EXIT_PREEMPTED), output
    if proc.returncode == EXIT_PREEMPTED:
        assert "preempted" in output, output
        assert list(ckpt_dir.glob("step_*/COMMIT")), \
            "preempted exit without a committed snapshot"
        assert not out_json.exists()  # no result JSON for a partial run

    # rerunning the same command line resumes (or re-verifies) to the
    # bit-identical result — same diff the CI resume-smoke performs
    r2 = subprocess.run(_cmd(out_json, ckpt_dir), env=env,
                        capture_output=True, text=True, timeout=600,
                        cwd=ROOT)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert _norm(out_json) == _norm(oracle_json)
