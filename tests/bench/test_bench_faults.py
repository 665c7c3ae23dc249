"""A run with its timed path broken underneath must come out not correct.

These drive the whole of a run on the CPU (only the look for a chip is
skipped), at a size a test run can hold, with the program's batched block
step broken in each of the ways a cell can show: a step that returns its
state unchanged, half of the batch left out, and a support altered where
it is produced.  One chip has no exchange between chips to leave out.
A sound step under a rule file whose support is off by one is not correct
either: the metric's rule file decides."""
import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run

from test_bench_reference import GNUTELLA, MICO, QUERY, RULES

CELLS = {
    "gnutella.q3-mis": (GNUTELLA, dict(QUERY, execution="auto")),
    # few labels, so few candidates: the cut query decides them within the
    # window even on a loaded CPU, and the warm-up compiles the step first
    "mico.l1-mis": (dict(MICO, labels=3),
                    dict(QUERY, loop="cut", sigma=40, lam=0.0, execution="auto",
                         warm_up_s=3.0)),
}


def _broken(orig, fault):
    def step_fn(metric, k, cfg, unbatched=False, capture=False):
        step = orig(metric, k, cfg, unbatched, capture)

        def broken(g, plans, block_start, state, taus):
            out = step(g, plans, block_start, state, taus)
            new = out[0]
            if fault == "unchanged":
                new = state
            elif fault == "half":
                P = taus.shape[0]
                keep = jnp.arange(P) < (P + 1) // 2
                new = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(
                        keep.reshape((P,) + (1,) * (a.ndim - 1)), a, b),
                    new, state)
            elif fault == "altered":
                new = (new[0], new[1].at[0].add(1))
            return (new, new[1]) + tuple(out[2:])

        return broken

    return step_fn


def _run(cell_name, monkeypatch, fault=None, rules=None):
    import repro.core.batched as batched
    import repro.core.planner as planner

    monkeypatch.setattr(planner, "load_calibration",
                        lambda *a, **k: planner.CostModel(backend="tpu"))
    if fault is not None:
        monkeypatch.setattr(batched, "_step_fn", _broken(batched._step_fn, fault))
    spec = bench_run.spec()
    cell = next(w for w in spec["workloads"] if w["name"] == cell_name)
    config, traffic = CELLS[cell_name]
    return bench_run.run(cell_name, 2**31 + 11, 4.0, False, check_device=False,
                         bench=spec, files=(cell, config, traffic), rules=rules)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", [None, "unchanged", "half", "altered"])
def test_a_broken_step_is_not_correct(cell, fault, monkeypatch):
    line = _run(cell, monkeypatch, fault)
    assert line["correct"] is (fault is None), line["checks"]
    assert line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in bench_run.cell_metrics(
        bench_run.spec(), cell, traced=False)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_metrics_rule_file_decides_correct(cell, monkeypatch, tmp_path):
    (tmp_path / "mis.py").write_text(RULES["off_by_one"])
    line = _run(cell, monkeypatch, rules=tmp_path)
    assert line["correct"] is False
    assert line["checks"]["wrong_supports"]["value"] > 0
