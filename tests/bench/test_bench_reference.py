"""The plain reference against the program, and the control against both.

The reference decides `correct` on the chip; here it is held to the
program's answers on the CPU, at sizes a test run can hold, and the control
(the reference with a half-width used-vertex set) must come out wrong.
What depends on the metric comes from its rule file alone, and the traffic
file reaches the program only through the keys the driver passes on."""
import re

import pytest

from bench import drive, reference, rmat

GNUTELLA = {"name": "gnutella", "vertices": 315, "edges": 1038, "labels": 5,
            "undirected": True, "max_pattern_size": 3,
            "generator": {"kind": "rmat", "a": 0.57, "b": 0.19, "c": 0.19},
            "structure_seed": 0, "renumber_window": 4096}
# more vertices than one 4096-wide root block: two blocks, degree-ordered
MICO = dict(GNUTELLA, name="mico", vertices=5000, edges=54014, labels=29,
            max_pattern_size=2)
QUERY = {"loop": "closed", "metric": "mis", "sigma": 25, "lam": 0.4,
         "cap": 16384, "execution": "batched"}


def _mine(config, traffic, seed, rules=None):
    from repro.core import mine

    cell = drive.Cell(config, traffic, rmat.config_graph(config, seed), rules)
    res = mine(cell.g, cell.mining)
    return cell, cell.answers([drive.Query(0.0, res, None, 0.0)])


@pytest.mark.parametrize("config,traffic,seed,control_fails", [
    (GNUTELLA, QUERY, 1, True),
    # every pattern reaches a low τ despite the control's collisions
    (GNUTELLA, dict(QUERY, sigma=15, lam=0.0), 2**31 + 3, False),
    (dict(GNUTELLA, structure_seed=3, renumber_window=100), QUERY, 7, True),
    (MICO, dict(QUERY, sigma=40, lam=0.0), 5, True),
])
def test_reference_agrees_with_the_program(config, traffic, seed, control_fails):
    cell, answers = _mine(config, traffic, seed)
    assert answers[0].searched
    got = drive.compare(cell.checker(), answers, [True])
    assert got == {"wrong_supports": 0, "wrong_frequent": 0,
                   "wrong_candidates": 0, "failed_queries": 0}
    # the control: the reference with its used-vertex set at half width
    control = drive.control_answers(cell.checker(width=-(-cell.graph.n // 2)),
                                    answers)
    bad = drive.compare(cell.checker(), control, [True])
    assert (bad["wrong_supports"] > 0) == control_fails
    assert bad["failed_queries"] == int(control_fails)


def test_a_changed_support_or_a_missing_candidate_is_caught():
    cell, answers = _mine(GNUTELLA, QUERY, 1)
    ans = answers[0]
    adj, lab, s = ans.searched[0]
    altered = type(ans)([(adj, lab, s + 1)] + ans.searched[1:], ans.frequent)
    assert drive.compare(cell.checker(), [altered], [True])["wrong_supports"] == 1
    missing = type(ans)(ans.searched[1:], ans.frequent)
    assert drive.compare(cell.checker(), [missing], [True])["wrong_candidates"] == 1
    # a cut query may stop anywhere, but only at level-1 candidates
    two = [(a, lb, x) for a, lb, x in ans.searched if len(lb) == 2]
    assert drive.compare(cell.checker(), [type(ans)(two[1:], [])],
                         [False])["wrong_candidates"] == 0
    three = [(a, lb, x) for a, lb, x in ans.searched if len(lb) == 3]
    assert three and drive.compare(cell.checker(), [type(ans)(three, [])],
                                   [False])["wrong_candidates"] == len(three)


# rule files that differ from bench/references/mis.py in one function
_MIS = """from bench import reference
_mis = reference.rule("mis")
tau, searchable, support, same = _mis.tau, _mis.searchable, _mis.support, _mis.same
"""
RULES = {
    "copy": _MIS,
    "off_by_one": _MIS + """
def support(g, pat, tau, **control):
    value, walked = _mis.support(g, pat, tau, **control)
    return value + 1, walked
""",
    "search_all": _MIS + """
def searchable(k, tau, n):
    return True
""",
}
# τ(2) = 175 and 2·175 > 315 vertices: the program searches no candidate
PRUNED = dict(QUERY, sigma=250)


@pytest.mark.parametrize("rule,traffic", [
    ("copy", QUERY), ("off_by_one", QUERY), ("copy", PRUNED),
    ("search_all", PRUNED),
])
def test_a_rule_file_alone_decides_correct(rule, traffic, tmp_path):
    (tmp_path / "mis.py").write_text(RULES[rule])
    cell, answers = _mine(GNUTELLA, traffic, 2**31 + 19, rules=tmp_path)
    got = drive.compare(cell.checker(), answers, [True])
    searched = len(answers[0].searched)
    if rule == "copy":
        assert got == {"wrong_supports": 0, "wrong_frequent": 0,
                       "wrong_candidates": 0, "failed_queries": 0}
    elif rule == "off_by_one":
        assert searched and got["wrong_supports"] == searched
    else:
        # the program searched nothing; the rule wants every level-1 candidate
        assert searched == 0
        assert got["wrong_candidates"] == len(cell.checker().g.level1())
    assert got["failed_queries"] == int(rule != "copy")


@pytest.mark.parametrize("key,value", [
    ("execution", "sequential"), ("batch_patterns", 8),
    ("blocks_per_super", 2), ("sample_fraction", 0.5), ("confidence", 0.9),
    ("sample_seed", 7), ("sample_rounds", 2),
])
def test_an_execution_option_reaches_the_program(key, value):
    from repro.core import MiningConfig

    assert getattr(MiningConfig(sigma=1), key) != value
    traffic = {k: v for k, v in QUERY.items() if k != "execution"}
    cell = drive.Cell(GNUTELLA, dict(traffic, **{key: value}),
                      rmat.config_graph(GNUTELLA, 3))
    assert getattr(cell.mining, key) == value
    assert cell.mining.sigma == QUERY["sigma"]


@pytest.mark.parametrize("traffic,name", [
    (dict(QUERY, metric="mni"), "mni"),
    (dict(QUERY, metric="../references/mis"), "../references/mis"),
    (dict(QUERY, complete=True), "complete"),
    (dict(QUERY, sigam=25), "sigam"),
    (dict(QUERY, loop="open"), "open"),
])
def test_a_traffic_the_reference_cannot_check_is_refused(traffic, name):
    with pytest.raises(drive.BadTraffic, match=re.escape(name)):
        drive.check(traffic)


def test_answers_keep_the_type_of_each_support():
    import numpy as np

    adj, lab = np.array([[0, 1], [0, 0]], bool), np.array([0, 1])
    ans = reference.Answer([(adj, lab, np.int32(3)), (adj, lab, 2.5),
                            (adj, lab, np.float32(0.25))], [(adj, lab, 7)])
    assert [type(s) for _, _, s in ans.searched] == [int, float, float]
    assert [s for _, _, s in ans.searched] == [3, 2.5, 0.25]
    assert type(ans.frequent[0][2]) is int
