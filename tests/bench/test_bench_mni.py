"""The program under MNI held to its rule file, bench/references/mni.py.

``mine()`` with ``metric="mni"``, on the batched and the sequential planes,
must read 0 wrong against the reference: on the gnutella configuration as
the benchmark sizes it (one root block, queries to 3 vertices), and on an
R-MAT graph of three root blocks, where candidates stop at different
blocks.  The control (image tables folded to half width), a rule that never
stops, a rule off by one, and an image update that drops a row must each
come out not correct.

The tests need the rule file; without it there is nothing to hold the
program to, and a cell of the metric is refused (test_bench_reference).
"""
import json

import jax.numpy as jnp
import pytest

from bench import drive, reference, rmat
from bench import run as bench_run

pytestmark = pytest.mark.skipif(not (reference.RULES / "mni.py").is_file(),
                                reason="no rule file bench/references/mni.py")

BENCH = bench_run.ROOT / "bench"
GNUTELLA = json.loads((BENCH / "configs" / "gnutella.json").read_text())
QUERY = dict(json.loads((BENCH / "traffic" / "q3-mis.json").read_text()),
             metric="mni")
# 10,000 vertices: three root blocks of 4,096 ids, walked in the order
# 0, 2, 1.  At σ 540 two candidates stop after the second block and the
# others walk all three.
BLOCKS = dict(GNUTELLA, name="blocks", vertices=10000, edges=24000, labels=4,
              max_pattern_size=2)
CASES = {
    "gnutella-batched": (GNUTELLA, dict(QUERY, execution="batched"), 2**31 + 41),
    "gnutella-sequential": (GNUTELLA, dict(QUERY, execution="sequential"),
                            2**31 + 43),
    "blocks-batched": (BLOCKS, dict(QUERY, sigma=540, execution="batched"),
                       2**31 + 47),
    "blocks-sequential": (BLOCKS, dict(QUERY, sigma=540, execution="sequential"),
                          2**31 + 53),
}
ZERO = {"wrong_supports": 0, "wrong_frequent": 0, "wrong_candidates": 0,
        "failed_queries": 0}


def _mine(case, rules=None):
    from repro.core import mine

    config, traffic, seed = CASES[case]
    cell = drive.Cell(config, traffic, rmat.config_graph(config, seed), rules)
    res = mine(cell.g, cell.mining)
    return cell, cell.answers([drive.Query(0.0, res, None, 0.0)])


def _correct(checks):
    """As the command decides it: every check within its limit."""
    return all(checks[k] <= bench_run.LIMITS[k] for k in bench_run.LIMITS)


@pytest.fixture(scope="module")
def mined():
    """Each case mined once for the module."""
    done = {}

    def get(case):
        if case not in done:
            done[case] = _mine(case)
        return done[case]

    return get


def test_check_gives_the_mni_rule_whose_tau_is_sigma():
    rule = drive.check(QUERY)
    assert rule.__file__ == str(reference.RULES / "mni.py")
    for lam in (0.0, 0.4, 1.0):
        for k in range(1, 6):
            assert rule.tau(QUERY["sigma"], lam, k) == QUERY["sigma"]
            assert rule.searchable(k, QUERY["sigma"], 10)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_program_agrees_with_the_mni_rule(case, mined):
    cell, answers = mined(case)
    checker = cell.checker()
    assert drive.compare(checker, answers, [True]) == ZERO
    sizes = {len(lab) for _, lab, _ in answers[0].searched}
    assert sizes == set(range(2, cell.config["max_pattern_size"] + 1))
    if case.startswith("blocks"):
        g = checker.g
        walked = [checker.rule.support(g, (a, lab), checker.tau(len(lab)))[1]
                  for a, lab, _ in answers[0].searched]
        assert len(g.schedule) == 3 and min(walked) < len(g.schedule)
        assert max(walked) == len(g.schedule)


@pytest.mark.parametrize("case", ["gnutella-batched", "blocks-batched"])
def test_the_control_is_not_correct(case, mined):
    cell, answers = mined(case)
    control = drive.control_answers(cell.checker(width=-(-cell.graph.n // 2)),
                                    answers)
    got = drive.compare(cell.checker(), control, [True])
    assert not _correct(got) and got["wrong_supports"] > 0


# rule files that differ from bench/references/mni.py in one function
_MNI = """from bench import reference
_mni = reference.rule("mni")
tau, searchable, support, same = _mni.tau, _mni.searchable, _mni.support, _mni.same
"""
RULES = {
    # the whole schedule, as a τ no count reaches
    "never_stops": _MNI + """
def support(g, pat, tau, **control):
    return _mni.support(g, pat, float("inf"), **control)
""",
    "off_by_one": _MNI + """
def support(g, pat, tau, **control):
    value, walked = _mni.support(g, pat, tau, **control)
    return value + 1, walked
""",
}


@pytest.mark.parametrize("rule,case", [("never_stops", "blocks-batched"),
                                       ("off_by_one", "gnutella-batched")])
def test_a_changed_rule_file_is_not_correct(rule, case, mined, tmp_path):
    (tmp_path / "mni.py").write_text(RULES[rule])
    cell, answers = mined(case)
    config, traffic, _ = CASES[case]
    ruled = drive.Cell(config, traffic, cell.graph, rules=tmp_path)
    got = drive.compare(ruled.checker(), answers, [True])
    assert not _correct(got) and got["wrong_supports"] > 0


@pytest.fixture
def drop_a_row(monkeypatch):
    """The image update leaves out each block's first row; the batched
    plane's compiled steps are dropped before and after, so that the fault
    reaches them and goes with them."""
    import repro.core.batched as batched
    import repro.core.metrics as metrics

    update = metrics.mni_update

    def dropped(images, emb, n_valid, k):
        return update(images, emb[1:], jnp.maximum(n_valid - 1, 0), k)

    batched.clear_program_cache()
    monkeypatch.setattr(metrics, "mni_update", dropped)
    yield
    monkeypatch.undo()
    batched.clear_program_cache()


@pytest.mark.parametrize("case", ["blocks-batched", "blocks-sequential"])
def test_a_dropped_row_is_not_correct(case, drop_a_row):
    cell, answers = _mine(case)
    got = drive.compare(cell.checker(), answers, [True])
    assert not _correct(got) and got["wrong_supports"] > 0
