"""The plain reference against the program, and the control against both.

The reference decides `correct` on the chip; here it is held to the
program's answers on the CPU, at sizes a test run can hold, and the control
(the reference with a half-width used-vertex set) must come out wrong."""
import pytest

from bench import drive, rmat

GNUTELLA = {"name": "gnutella", "vertices": 315, "edges": 1038, "labels": 5,
            "undirected": True, "max_pattern_size": 3,
            "generator": {"kind": "rmat", "a": 0.57, "b": 0.19, "c": 0.19},
            "structure_seed": 0, "renumber_window": 4096}
# more vertices than one 4096-wide root block: two blocks, degree-ordered
MICO = dict(GNUTELLA, name="mico", vertices=5000, edges=54014, labels=29,
            max_pattern_size=2)
QUERY = {"loop": "closed", "metric": "mis", "sigma": 25, "lam": 0.4,
         "cap": 16384, "execution": "batched"}


def _mine(config, traffic, seed):
    from repro.core import mine

    cell = drive.Cell(config, traffic, rmat.config_graph(config, seed))
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
