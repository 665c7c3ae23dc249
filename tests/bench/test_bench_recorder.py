"""The recorder only observes, and the pairs it lets the harness count."""
import numpy as np
import pytest

from bench import drive, rmat
from bench.recorder import GroupRecord, LevelRecord, Recorder

from test_bench_reference import GNUTELLA, MICO, QUERY


@pytest.fixture
def unpriced(monkeypatch):
    """Plan as on a TPU, where no fitted cost model prices the planes:
    ``auto`` runs the batched plane, whose per-block hooks the recorder
    reads."""
    import repro.core.planner as planner

    monkeypatch.setattr(planner, "load_calibration",
                        lambda *a, **k: planner.CostModel(backend="tpu"))


def _answer(res):
    return (sorted((tuple(st.pattern.labels), st.pattern.adj.tobytes(), st.support)
                   for st in res.stats),
            {k: {kk: vv for kk, vv in v.items() if kk != "wall_s"}
             for k, v in res.per_level.items()})


@pytest.mark.parametrize("execution", ["auto", "batched"])
def test_mine_answers_alike_with_and_without_the_recorder(unpriced, execution):
    from repro.core import mine

    traffic = dict(QUERY, execution=execution)
    cell = drive.Cell(GNUTELLA, traffic, rmat.config_graph(GNUTELLA, 4))
    rec = Recorder()
    with_rec = mine(cell.g, cell.mining, hooks=rec)
    without = mine(cell.g, cell.mining)
    assert _answer(with_rec) == _answer(without)
    assert rec.levels[1].groups and rec.levels[2].groups
    if execution == "auto":
        assert rec.levels[1].plan["plane"] == "batched"


def test_early_exit_settles_every_root(unpriced):
    from repro.core import mine

    traffic = dict(QUERY, sigma=40, lam=0.0, loop="cut", execution="auto")
    cell = drive.Cell(MICO, traffic, rmat.config_graph(MICO, 5))
    rec = Recorder()
    res = mine(cell.g, cell.mining, hooks=rec)
    n_blocks = -(-cell.g.n // cell.mining.match.root_block)
    assert n_blocks == 2
    assert any(st.blocks_run < n_blocks for st in res.stats)    # early exits
    assert cell._pairs(res, rec) == len(res.stats) * cell.g.n


class _Result:
    def __init__(self, decided, pruned):
        self.stats = [None] * decided
        self.per_level = {1: {"pruned": pruned}}


@pytest.mark.parametrize("root_block", [128, 256])
def test_pairs_in_flight_count_the_roots_of_the_blocks_walked(root_block):
    import dataclasses

    from repro.core import root_block_order

    config = dict(GNUTELLA, vertices=1000, edges=4000)
    cell = drive.Cell(config, dict(QUERY, loop="cut"),
                      rmat.config_graph(config, 9))
    cell.mining = dataclasses.replace(
        cell.mining, match=dataclasses.replace(cell.mining.match,
                                               root_block=root_block))
    n = cell.g.n
    n_blocks = -(-n // root_block)
    order = root_block_order(cell.g, root_block)
    walked = np.array([0, 1, 3, n_blocks, 2])
    level = LevelRecord()
    level.plan = {"root_block": root_block}
    level.groups[(2, 0)] = GroupRecord(walked, np.array([1, 1, 1, 1, 0], bool))
    rec = Recorder()
    rec.levels[1] = level
    block_of = np.arange(n) // root_block
    want = 2 * n + 1 * n + sum(
        int(np.isin(block_of, order[:m]).sum()) for m in walked[:4])
    assert cell._pairs(_Result(2, 1), rec) == want
    assert cell._pairs(_Result(0, 0), Recorder()) == 0
