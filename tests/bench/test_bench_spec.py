"""BENCHMARK.json against its schema, and the registry that
finds each configuration, traffic mix and metric by its name."""
import json
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import run as bench_run
from bench import roofline

ROOT = bench_run.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert set(c["reduced"]) == set(data["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    _, config, traffic = bench_run.cell_files(SPEC, cell)
    assert traffic["loop"] in ("cut", "closed")
    ends = bench_run.cell_metrics(SPEC, cell, traced=False)
    layers = bench_run.cell_metrics(SPEC, cell, traced=True)
    names = {m["name"] for m in ends}
    assert "setup_s" in names and len(names) >= 2
    assert layers and all(m["moves"] in names for m in layers)
    for m in ends + layers:
        assert callable(bench_run.reader(m["name"]))


def test_a_new_metric_is_found_by_its_file_alone(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "queries.query.py").write_text(
        "def read(run):\n    return float(len(run.queries))\n")
    spec = dict(SPEC, per_layer=SPEC["per_layer"] + [
        {"name": "queries.query", "unit": "queries", "better": "higher",
         "source": "program_counter", "layer": "block loop",
         "moves": "query_s", "workloads": ["gnutella.q3-mis"]}])
    names = [m["name"] for m in bench_run.cell_metrics(
        spec, "gnutella.q3-mis", traced=True)]
    assert "queries.query" in names
    read = bench_run.reader("queries.query", tmp_path)
    assert read(SimpleNamespace(queries=[1, 2, 3])) == 3.0


def test_peaks_refuse_an_unknown_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def _command(cwd, env_extra=None):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, str(cwd / SPEC["command"][1]),
                               "--workload", "mico.l1-mis", "--seed",
                               str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_a_cpu_and_prints_no_result():
    out = _command(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no result" in out.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
