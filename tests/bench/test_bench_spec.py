"""BENCHMARK.json against its schema, and the registry that
finds each configuration, traffic mix and metric by its name."""
import json
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import drive, roofline
from bench import run as bench_run

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
    assert callable(drive.check(traffic).support)
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


def _command(cwd, env_extra=None, workload="mico.l1-mis"):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, str(cwd / SPEC["command"][1]),
                               "--workload", workload, "--seed",
                               str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _copy_benchmark(to):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", to)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, to / p,
                        ignore=shutil.ignore_patterns("__pycache__"))


def test_the_command_refuses_a_cpu_and_prints_no_result():
    out = _command(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no result" in out.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    _copy_benchmark(tmp_path)
    out = _command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("change,name", [
    ({"metric": "nmi"}, "nmi"),
    ({"complete": True}, "complete"),
    ({"escalate": False}, "escalate"),
    ({"root_order": "vertex"}, "root_order"),
    ({"generation": "extend"}, "generation"),
])
def test_the_command_refuses_a_traffic_it_cannot_check(change, name, tmp_path):
    """Before set-up, with the name in the message: a metric with no rule
    file, or a key the driver neither reads nor passes to the program."""
    _copy_benchmark(tmp_path)
    _, _, traffic = bench_run.cell_files(SPEC, "gnutella.q3-mis")
    (tmp_path / "bench" / "traffic" / "refused.json").write_text(
        json.dumps(dict(traffic, **change)))
    spec = dict(SPEC, workloads=SPEC["workloads"] + [
        {"name": "gnutella.refused", "config": "gnutella", "traffic": "refused",
         "chips": 1, "why": "a traffic the harness cannot check"}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _command(tmp_path, {"PYTHONPATH": str(ROOT / "src")}, "gnutella.refused")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no result" in out.stderr and name in out.stderr
    assert "setup:" not in out.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_the_command_refuses_a_cell_whose_rule_file_is_gone(cell, tmp_path):
    """The same refusal, for each cell as it stands, with its metric's rule
    file taken out of the copy."""
    _copy_benchmark(tmp_path)
    _, _, traffic = bench_run.cell_files(SPEC, cell)
    (tmp_path / "bench" / "references" / f"{traffic['metric']}.py").unlink()
    out = _command(tmp_path, {"PYTHONPATH": str(ROOT / "src")}, cell)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no result" in out.stderr and repr(traffic["metric"]) in out.stderr
    assert "setup:" not in out.stderr
