"""Execution planner — auto ≡ forced planes, cost-model properties,
degree-ordered root schedule, geometry derivation, calibration loading."""
import dataclasses
import json

import numpy as np
import pytest
from hypothesis import given, settings, HealthCheck

from repro.core import (
    CostModel,
    ExecutionPlanner,
    LevelPlan,
    MatchConfig,
    MiningConfig,
    build_graph,
    initial_candidates,
    load_calibration,
    mine,
    root_block_order,
)
from repro.core.planner import CAP_FLOOR, CALIBRATION_ENV
from repro.data.synthetic import rmat_graph
from tests.conftest import data_graphs

METRICS = ("mis", "mis_luby", "mni")


def _cfg(g, execution, metric="mis", **kw):
    # cap ≤ CAP_FLOOR and two_phase=False pin the geometry, so this config
    # isolates the *plane* decision (geometry derivation is tested
    # separately on graphs where occupancy is known)
    kw.setdefault("match", dataclasses.replace(
        MatchConfig.for_graph(g, cap=1024, root_block=32, chunk=4),
        two_phase=False))
    kw.setdefault("sigma", 2)
    kw.setdefault("lam", 1.0)
    kw.setdefault("max_pattern_size", 3)
    return MiningConfig(metric=metric, execution=execution, **kw)


def _norm(res):
    """Everything plane-invariant: stats, frequent set, per-level counts
    minus wall clock, dispatch counts (amortized differently per plane)
    and the auto-only records (plan/pricing, sampled telemetry, occupancy
    weights and within-level replan counts — diagnostics of *how* a plane
    ran, not *what* it found)."""
    return dict(
        stats=[(s.pattern.key(), s.support, s.tau, s.frequent,
                s.embeddings_found, s.overflowed, s.blocks_run, s.max_count)
               for s in res.stats],
        frequent=[(p.key(), s) for p, s in res.frequent],
        searched=res.searched,
        per_level={
            lvl: {k: v for k, v in st.items()
                  if k not in ("wall_s", "dispatches", "plan", "sampled",
                               "block_peaks", "replans")}
            for lvl, st in res.per_level.items()},
        timed_out=res.timed_out,
    )


# ---------------------------------------------------------------------------
# auto ≡ forced planes (the acceptance property)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", METRICS)
@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(data_graphs(min_n=6, max_n=16, n_labels=2))
def test_auto_bit_identical_to_forced_planes(metric, g):
    auto = mine(g, _cfg(g, "auto", metric))
    seq = mine(g, _cfg(g, "sequential", metric))
    bat = mine(g, _cfg(g, "batched", metric))
    assert _norm(auto) == _norm(seq)
    assert _norm(auto) == _norm(bat)
    # and the decision trail exists for every mined level
    for st in auto.per_level.values():
        assert st["plan"]["plane"] in ("sequential", "batched")


def test_auto_geometry_derivation_preserves_results():
    """On a bounded-degree graph the planner shrinks cap below the
    oversized graph-global guess; results must not move vs forced planes."""
    rng = np.random.default_rng(0)
    n = 600
    src = np.repeat(np.arange(n), 2)
    dst = rng.integers(0, n, 2 * n)
    g = build_graph(n, np.stack([src, dst], 1), rng.integers(0, 4, n),
                    undirected=True)
    big = dataclasses.replace(
        MatchConfig.for_graph(g, cap=16384, root_block=64), two_phase=True)
    kw = dict(sigma=3, lam=1.0, max_pattern_size=3, complete=True, match=big)
    auto = mine(g, MiningConfig(execution="auto", **kw))
    bat = mine(g, MiningConfig(execution="batched", **kw))
    seq = mine(g, MiningConfig(execution="sequential", **kw))
    assert _norm(auto) == _norm(bat) == _norm(seq)
    # the planner actually derived a smaller frontier for level ≥ 2
    derived = [st["plan"]["cap"] for lvl, st in auto.per_level.items()
               if lvl >= 2]
    assert derived and all(c < big.cap for c in derived)
    assert not any(st["overflowed"] for st in auto.per_level.values())


def test_mis_exact_auto_equals_forced():
    g = rmat_graph(24, 60, n_labels=4, seed=9, undirected=True)
    cfg = MatchConfig.for_graph(g, cap=1024, root_block=32)
    res = {}
    for ex in ("auto", "sequential", "batched"):
        res[ex] = mine(g, MiningConfig(
            sigma=2, lam=1.0, metric="mis_exact", max_pattern_size=3,
            match=cfg, execution=ex))
    assert _norm(res["auto"]) == _norm(res["sequential"]) \
        == _norm(res["batched"])


# ---------------------------------------------------------------------------
# cost-model properties
# ---------------------------------------------------------------------------

def _planner(g=None, execution="auto", cost=None, ndev=1, **cfg_kw):
    g = g if g is not None else rmat_graph(128, 700, n_labels=2, seed=1,
                                           undirected=True)
    cfg_kw.setdefault("sigma", 3)
    cfg_kw.setdefault("match", MatchConfig.for_graph(g, cap=1024,
                                                     root_block=32))
    cfg = MiningConfig(execution=execution, **cfg_kw)
    return ExecutionPlanner(g, cfg, cost_model=cost or CostModel(),
                            n_devices=ndev), g, cfg


def test_bucket_choice_monotone_in_pattern_count():
    """More patterns ⇒ never a smaller bucket (the acceptance unit test)."""
    for cost in (CostModel(),
                 CostModel(dispatch_overhead_s=1e-2, lane_time_s=1e-10),
                 CostModel(dispatch_overhead_s=1e-6, lane_time_s=1e-6,
                           vmap_factor=2.0)):
        planner, g, _ = _planner(cost=cost)
        prev = None
        for p_count in range(1, 200):
            bucket = planner.choose_bucket(p_count)
            assert bucket >= 1
            if prev is not None:
                assert bucket >= prev, (p_count, bucket, prev)
            prev = bucket


def test_plane_decision_regimes():
    planner, g, _ = _planner()
    cands = initial_candidates(g)
    assert len(cands) >= 4
    # single pattern: nothing to amortize — sequential (no vmap tax)
    assert planner.plan_level(1, cands[:1], [2]).plane == "sequential"
    # dispatch-bound: many patterns on a small grid — batched
    assert planner.plan_level(1, cands * 8, [2] * len(cands) * 8
                              ).plane == "batched"
    # forced modes pass through verbatim
    for forced in ("sequential", "batched"):
        pl, _, cfg = _planner(execution=forced)
        plan = pl.plan_level(1, cands[:4], [2] * 4)
        assert plan.plane == forced
        assert plan.match == cfg.match
        assert plan.max_batch == cfg.batch_patterns


@pytest.mark.parametrize("backend,platform,priced", [
    ("cpu", "cpu", True),
    (None, "tpu", True),
    ("cpu", "tpu", False),
    ("tpu", "tpu", True),
])
def test_unpriced_platform_plans_batched(backend, platform, priced):
    """Constants fitted on another platform compare no planes: auto keeps
    the batched plane (still deriving geometry) even where the model would
    pick sequential."""
    g = rmat_graph(128, 700, n_labels=2, seed=1, undirected=True)
    cfg = MiningConfig(sigma=3, execution="auto",
                       match=MatchConfig.for_graph(g, cap=1024,
                                                   root_block=32))
    planner = ExecutionPlanner(
        g, cfg, cost_model=CostModel(backend=backend), platform=platform)
    assert planner.priced is priced
    cands = initial_candidates(g)
    plan = planner.plan_level(1, cands[:1], [2])
    assert plan.plane == ("sequential" if priced else "batched")
    prev = {"max_count": 10, "overflowed": False}
    assert planner.plan_level(2, cands[:1], [2], prev=prev).match.cap \
        == CAP_FLOOR


def test_distributed_gating():
    """Auto may pick distributed only with metric=mis_luby, >1 device AND a
    pinned mesh-invariant super-block schedule."""
    g = rmat_graph(128, 700, n_labels=2, seed=1, undirected=True)
    match = MatchConfig.for_graph(g, cap=1024, root_block=16)  # 8 blocks
    make = lambda **kw: _planner(  # noqa: E731
        g=g, metric="mis_luby", ndev=4, match=match,
        cost=CostModel(dispatch_overhead_s=5e-3, lane_time_s=1e-10), **kw)
    planner, _, _ = make(blocks_per_super=4)
    cands = initial_candidates(g)[:4]
    assert planner.plan_level(1, cands, [2] * 4).plane == "distributed"
    # no pinned schedule → never distributed
    planner, _, _ = make()
    assert planner.plan_level(1, cands, [2] * 4).plane != "distributed"
    # wrong metric → never distributed (greedy scan isn't mesh-collective)
    planner, _, _ = _planner(
        g=g, metric="mis", ndev=4, blocks_per_super=4, match=match,
        cost=CostModel(dispatch_overhead_s=5e-3, lane_time_s=1e-10))
    assert planner.plan_level(1, cands, [2] * 4).plane != "distributed"


def test_derive_match_rules():
    planner, g, cfg = _planner()
    base = cfg.match
    # no telemetry → base geometry (two_phase passthrough is k-dependent)
    assert planner.derive_match(3, None).cap == base.cap
    # small occupancy → pow2(4×peak) clamped to the floor, never above base
    m = planner.derive_match(3, {"max_count": 10, "overflowed": False})
    assert m.cap == min(base.cap, CAP_FLOOR)
    # previous overflow → never shrink
    m = planner.derive_match(3, {"max_count": 10, "overflowed": True})
    assert m.cap == base.cap
    # ordering-sensitive knobs never move
    for prev in (None, {"max_count": 3, "overflowed": False}):
        m = planner.derive_match(3, prev)
        assert (m.chunk, m.max_chunks, m.root_block, m.bisect_iters) == \
            (base.chunk, base.max_chunks, base.root_block, base.bisect_iters)
    # two_phase derivation: k=2 has no non-anchor edge checks
    pl2, _, cfg2 = _planner(match=dataclasses.replace(
        MatchConfig.for_graph(g, cap=1024, root_block=32), two_phase=True))
    assert pl2.derive_match(2, None).two_phase is False
    assert pl2.derive_match(3, None).two_phase is True


def test_level_plan_dict_roundtrip():
    planner, g, cfg = _planner()
    cands = initial_candidates(g)
    plan = planner.plan_level(2, cands, [2] * len(cands),
                              prev={"max_count": 7, "overflowed": False})
    d = json.loads(json.dumps(plan.to_dict()))  # what the snapshot does
    back = LevelPlan.from_dict(d, cfg.match)
    assert back == plan
    assert back.to_dict() == plan.to_dict()


# ---------------------------------------------------------------------------
# degree-ordered root schedule
# ---------------------------------------------------------------------------

def test_root_block_order_is_degree_descending_permutation():
    g = rmat_graph(300, 2000, n_labels=2, seed=4, undirected=True)
    order = root_block_order(g, 32, "degree")
    n_blocks = -(-g.n // 32)
    assert sorted(order.tolist()) == list(range(n_blocks))
    deg = np.diff(g.out_indptr)
    pad = np.full(n_blocks * 32, -1, np.int64)
    pad[: deg.shape[0]] = deg
    block_max = pad.reshape(n_blocks, 32).max(axis=1)
    assert list(block_max[order]) == sorted(block_max, reverse=True)
    # ties stay in ascending block-id order (stable ⇒ deterministic)
    for a, b in zip(order, order[1:]):
        if block_max[a] == block_max[b]:
            assert a < b
    # vertex mode = identity
    assert root_block_order(g, 32, "vertex").tolist() == list(range(n_blocks))


@pytest.mark.parametrize("root_order", ["degree", "vertex"])
def test_root_order_plane_equivalence(root_order):
    """Both schedules keep every plane bit-identical to each other (the
    schedule is shared; only the cross-schedule values may differ)."""
    g = rmat_graph(200, 1200, n_labels=2, seed=3, undirected=True)
    cfg_kw = dict(sigma=4, lam=1.0, metric="mis", max_pattern_size=3,
                  root_order=root_order,
                  match=MatchConfig.for_graph(g, cap=1024, root_block=32))
    res = {ex: mine(g, MiningConfig(execution=ex, **cfg_kw))
           for ex in ("auto", "sequential", "batched")}
    assert _norm(res["auto"]) == _norm(res["sequential"]) \
        == _norm(res["batched"])


def test_degree_order_terminates_levels_in_fewer_blocks():
    """The point of the schedule: with all match roots (high out-degree
    vertices) at the END of the id range, vertex order scans every empty
    block before τ fires; degree order runs the root block first."""
    rng = np.random.default_rng(7)
    n = 512
    hubs = np.arange(n - 32, n)          # the only vertices with out-edges
    src = np.repeat(hubs, 24)
    dst = rng.integers(0, n - 32, src.shape[0])
    g = build_graph(n, np.stack([src, dst], 1), np.zeros(n, np.int32))
    cfg_kw = dict(sigma=8, lam=1.0, metric="mis", max_pattern_size=2,
                  match=MatchConfig.for_graph(g, cap=1024, root_block=32))
    by_order = {}
    for ro in ("degree", "vertex"):
        res = mine(g, MiningConfig(execution="sequential", root_order=ro,
                                   **cfg_kw))
        assert [p.key() for p, _ in res.frequent]  # something was mined
        by_order[ro] = sum(s.blocks_run for s in res.stats if s.frequent)
    assert by_order["degree"] < by_order["vertex"]


# ---------------------------------------------------------------------------
# calibration loading
# ---------------------------------------------------------------------------

def test_load_calibration(tmp_path, monkeypatch):
    monkeypatch.delenv(CALIBRATION_ENV, raising=False)
    # explicit path
    p = tmp_path / "cal.json"
    p.write_text(json.dumps({"schema": 1, "dispatch_overhead_s": 1e-3,
                             "lane_time_s": 2e-9, "vmap_factor": 1.5}))
    cm = load_calibration(str(p))
    assert (cm.dispatch_overhead_s, cm.lane_time_s, cm.vmap_factor) == \
        (1e-3, 2e-9, 1.5)
    # env var
    monkeypatch.setenv(CALIBRATION_ENV, str(p))
    assert load_calibration().lane_time_s == 2e-9
    monkeypatch.delenv(CALIBRATION_ENV)
    # malformed / wrong schema / missing → defaults, never an error
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert load_calibration(str(bad)) == CostModel()
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"schema": 99, "lane_time_s": 1.0}))
    assert load_calibration(str(wrong)) == CostModel()
    assert load_calibration(str(tmp_path / "nope.json")) == CostModel()
    # dict round-trip (what the session pins in snapshots)
    assert CostModel.from_dict(cm.to_dict()) == dataclasses.replace(
        cm, source=cm.source)
    assert cm.backend is None   # the file names no backend
    assert CostModel.from_dict(CostModel().to_dict()).backend == "cpu"


@pytest.mark.parametrize("fitted,loads", [
    ({"backend": "cpu", "device_kind": "cpu"}, True),
    ({"backend": "cpu"}, True),
    ({"backend": "tpu"}, False),
    ({"backend": "cpu", "device_kind": "TPU v5 lite"}, False),
])
def test_load_calibration_only_for_its_device(tmp_path, monkeypatch, capsys,
                                              fitted, loads):
    """A calibration fitted on another backend or device kind prices
    nothing: the planner falls back to the defaults and says so."""
    monkeypatch.delenv(CALIBRATION_ENV, raising=False)
    p = tmp_path / "cal.json"
    p.write_text(json.dumps({"schema": 3, "lane_time_s": 7e-9, **fitted}))
    cm = load_calibration(str(p))
    if loads:
        assert cm.lane_time_s == 7e-9
        assert "ignoring" not in capsys.readouterr().err
    else:
        assert cm == CostModel()
        assert "fitted for" in capsys.readouterr().err
    # the cwd default is held to the same rule
    monkeypatch.chdir(tmp_path)
    p.rename(tmp_path / "planner_calibration.json")
    assert (load_calibration().lane_time_s == 7e-9) is loads


def test_config_validation():
    with pytest.raises(ValueError):
        MiningConfig(sigma=2, execution="planner")
    with pytest.raises(ValueError):
        MiningConfig(sigma=2, root_order="random")
    assert MiningConfig(sigma=2).execution == "auto"
    assert MiningConfig(sigma=2).root_order == "degree"
