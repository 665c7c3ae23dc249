"""Sampled execution plane — exactness, statistics, and planner gating.

Three layers of property tests (ISSUE 7):

  * **exactness invariant** — `execution="sampled"` with escalation
    returns the *identical* frequent-pattern set and supports as the
    forced-batched oracle, across metrics {mis, mis_luby, mni, frac} and
    sample fractions {0.25, 0.5, 1.0}; fraction 1.0 must degenerate to
    the exact plane with zero escalations;
  * **statistical machinery** — over ≥200 seeded draws from a per-block
    mass population measured on a real mining level, the nominal 95% CI
    covers the true support at ≥90% empirical rate and its mean width
    shrinks monotonically as the sample fraction grows;
  * **planner gating + calibration back-compat** — the sampled plan
    records a replayable draw, degenerates to batched when a sample
    cannot help, and schema-1 calibration files still load with the
    per-metric `row_time` accessor falling back to the shared constant.

Graphs are tiny on purpose: every claim here is structural/statistical,
not scale-dependent, and the full metric × fraction sweep must fit CI.
"""
import dataclasses
import json
import math

import numpy as np
import pytest

from repro.core import (
    CostModel, MatchConfig, MiningConfig, build_graph, load_calibration,
    mine,
)
from repro.core.planner import (
    CALIBRATION_ENV, ExecutionPlanner, LevelPlan, MIN_SAMPLED_BLOCKS,
    block_degree_stat,
)
from repro.core.sampled import (
    ht_estimate, ht_interval, normal_quantile, sample_key, sample_uniform,
    systematic_sample,
)

METRICS = ("mis", "mis_luby", "mni", "frac")
FRACTIONS = (0.25, 0.5, 1.0)


def _graph(n=64, deg=4, n_labels=3, seed=0):
    """Bounded-out-degree random digraph — several root blocks' worth."""
    rng = np.random.default_rng(seed)
    edges = set()
    for u in range(n):
        for v in rng.integers(0, n, deg):
            if u != int(v):
                edges.add((u, int(v)))
    labels = rng.integers(0, n_labels, n).astype(np.int32)
    return build_graph(n, sorted(edges), labels, n_labels=n_labels)


def _match_cfg():
    # root_block=8 → 8 blocks on the 64-vertex graph: enough schedule for
    # a 0.25 draw to be a real subset
    return MatchConfig(cap=256, root_block=8, chunk=8, max_chunks=2,
                       two_phase=False)


def _cfg(metric, execution, **kw):
    kw.setdefault("sigma", 6)
    kw.setdefault("max_pattern_size", 3)
    kw.setdefault("match", _match_cfg())
    return MiningConfig(metric=metric, execution=execution, **kw)


def _frequent(res):
    return [(p.key(), int(s)) for p, s in res.frequent]


def _freq_stats(res):
    """Full PatternStats of the frequent set (escalated ⇒ exact fields)."""
    return sorted(
        (st.pattern.key(), st.support, st.tau, st.embeddings_found,
         st.overflowed, st.blocks_run, st.max_count, st.estimated)
        for st in res.stats if st.frequent)


# ---------------------------------------------------------------------------
# the headline exactness invariant
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def oracle():
    """Forced-batched oracle result per metric (computed once)."""
    g = _graph()
    return g, {m: mine(g, _cfg(m, "batched")) for m in METRICS}


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("fraction", FRACTIONS)
def test_sampled_matches_batched_oracle(oracle, metric, fraction):
    g, refs = oracle
    ref = refs[metric]
    res = mine(g, _cfg(metric, "sampled", sample_fraction=fraction))
    assert _frequent(res) == _frequent(ref)
    # escalated patterns are exact — the frequent set's stats match the
    # oracle field-for-field (and are never flagged estimated)
    assert _freq_stats(res) == _freq_stats(ref)
    sampled_tel = [lvl["sampled"] for lvl in res.per_level.values()
                   if "sampled" in lvl]
    assert sampled_tel, "sampled plane never engaged"
    for tel in sampled_tel:
        assert 0 < tel["n_sample"] <= tel["n_blocks"]
        assert tel["escalated"] + tel["pruned"] >= 0
        if fraction == 1.0:
            assert tel["exact"] and tel["escalated"] == 0
        else:
            assert not tel["exact"]
    # infrequent prunes are flagged, and their supports sit below τ
    for st in res.stats:
        if st.estimated:
            assert not st.frequent and st.support < st.tau


def test_fraction_one_equals_batched_everywhere(oracle):
    """Fraction 1.0 is the exact plane: whole per_level trajectory matches
    (modulo the sampled plane's own bookkeeping keys)."""
    g, refs = oracle
    ref = refs["mis"]
    res = mine(g, _cfg("mis", "sampled", sample_fraction=1.0))
    drop = {"wall_s", "plan", "sampled", "block_peaks"}
    for lvl, st in ref.per_level.items():
        got = {k: v for k, v in res.per_level[lvl].items() if k not in drop}
        want = {k: v for k, v in st.items() if k not in drop}
        assert got == want, f"level {lvl}"
    assert all(not st.estimated for st in res.stats)


def test_sampled_deterministic(oracle):
    g, _ = oracle
    cfg = _cfg("mis", "sampled", sample_fraction=0.5)
    a, b = mine(g, cfg), mine(g, cfg)
    assert _frequent(a) == _frequent(b)
    assert [lvl.get("sampled") for lvl in a.per_level.values()] == \
           [lvl.get("sampled") for lvl in b.per_level.values()]


def test_escalation_disabled_is_flagged(oracle):
    """escalate=False trades exactness for speed — every sampled-level
    verdict is an estimate and says so."""
    g, _ = oracle
    res = mine(g, _cfg("mis", "sampled", sample_fraction=0.5,
                       escalate=False))
    est = [st for st in res.stats if st.estimated]
    assert est, "no estimated outcomes despite escalate=False"
    for lvl in res.per_level.values():
        if "sampled" in lvl and not lvl["sampled"]["exact"]:
            assert lvl["sampled"]["escalated"] == 0


# ---------------------------------------------------------------------------
# statistical machinery
# ---------------------------------------------------------------------------

def test_normal_quantile():
    assert normal_quantile(0.975) == pytest.approx(1.959964, abs=1e-5)
    assert normal_quantile(0.5) == pytest.approx(0.0, abs=1e-12)
    assert normal_quantile(0.025) == pytest.approx(-1.959964, abs=1e-5)
    with pytest.raises(ValueError):
        normal_quantile(0.0)
    with pytest.raises(ValueError):
        normal_quantile(1.0)


def test_sample_uniform_deterministic_and_keyed():
    u = sample_uniform(sample_key(0, 1))
    assert u == sample_uniform(sample_key(0, 1))
    assert 0.0 <= u < 1.0
    assert u != sample_uniform(sample_key(0, 2))
    assert u != sample_uniform(sample_key(1, 1))


def test_systematic_sample_inclusion_probabilities():
    w = np.array([5.0, 1.0, 1.0, 1.0, 1.0, 1.0], np.float64)
    positions, pis = systematic_sample(w, 3, u=0.37)
    assert positions.shape[0] == 3
    assert np.all(np.diff(positions) > 0)
    # the heavy unit is a certainty unit: 3·(5/10) ≥ 1
    assert 0 in positions and pis[list(positions).index(0)] == 1.0
    # π sums to the sample size over the whole population
    _, all_pis = systematic_sample(w, 3, u=0.0)
    full = np.zeros(6)
    # recompute π for every unit via the definition: certainty unit 0,
    # remaining 2 slots spread evenly over 5 unit-weight units
    assert pis[0] == 1.0
    np.testing.assert_allclose(pis[1:], 2.0 / 5.0)
    del all_pis, full


def test_systematic_sample_degenerate():
    w = np.ones(4)
    p, pi = systematic_sample(w, 10, u=0.5)      # n ≥ m → everything
    assert list(p) == [0, 1, 2, 3] and np.all(pi == 1.0)
    p, pi = systematic_sample(w, 0, u=0.5)
    assert p.size == 0 and pi.size == 0
    with pytest.raises(ValueError):
        systematic_sample(np.array([1.0, -1.0]), 1, 0.5)


def test_ht_estimate_unbiased_over_u():
    """Averaging the HT total over a fine grid of the single uniform u
    reproduces the population total (systematic PPS is u-unbiased)."""
    rng = np.random.default_rng(7)
    y = rng.integers(0, 5, 12).astype(float)
    w = rng.random(12) + 0.1
    ests = []
    for u in np.linspace(0.0, 0.999, 200):
        pos, pis = systematic_sample(w, 4, float(u))
        ests.append(ht_estimate(y[pos], pis))
    assert np.mean(ests) == pytest.approx(y.sum(), rel=0.02)


def _population(metric="mis"):
    """Per-block support increments of a real level, complete coverage —
    the fixed population the coverage trials resample from."""
    g = _graph()
    from repro.core.flexis import initial_candidates, tau_threshold
    from repro.core.graph import DeviceGraph
    from repro.core.plan import make_plan
    from repro.core.sampled import sample_group

    cfg = _match_cfg()
    pats = initial_candidates(g)[:6]
    dev_g = DeviceGraph.from_host(g)
    taus = [tau_threshold(6, 0.4, p.k) for p in pats]
    n_blocks = -(-g.n // cfg.root_block)
    ys, outs, _, _, timed, _ = sample_group(
        dev_g, [make_plan(p, g) for p in pats], taus, metric, cfg, n=g.n,
        sampled_ids=np.arange(n_blocks, dtype=np.int64))
    assert not timed
    return np.asarray(ys, np.float64)


def test_ci_coverage_and_width_shrinks():
    """≥200 seeded trials: nominal 95% CI covers the true support at ≥90%,
    and the mean width is monotone non-increasing in the sample fraction."""
    pop = _population()                    # (P, m) per-block increments
    m = pop.shape[1]
    rng = np.random.default_rng(11)
    weights = rng.random(m) + 0.5          # a fixed, uneven draw weight
    trials = 220
    mean_widths = []
    for fraction in (0.25, 0.5, 0.75):
        n_sample = max(1, math.ceil(fraction * m))
        covered = total = 0
        widths = []
        for seed in range(trials):
            u = sample_uniform(sample_key(seed, 0))
            pos, pis = systematic_sample(weights, n_sample, u)
            for row in pop:
                truth = row.sum()
                est, lo, hi = ht_interval(row[pos], pis, m, 0.95)
                total += 1
                covered += bool(lo <= truth <= hi)
                if math.isfinite(hi - lo):
                    widths.append(hi - lo)
        assert covered / total >= 0.90, \
            f"coverage {covered / total:.3f} at fraction {fraction}"
        mean_widths.append(np.mean(widths))
    assert mean_widths[0] >= mean_widths[1] >= mean_widths[2], mean_widths


def test_ht_interval_edge_cases():
    # full coverage → zero-width exact interval
    est, lo, hi = ht_interval(np.array([2.0, 3.0]), np.array([1.0, 1.0]),
                              2, 0.95)
    assert est == lo == hi == 5.0
    # a single non-certainty draw → no variance estimate → infinite CI
    est, lo, hi = ht_interval(np.array([2.0, 1.0]), np.array([1.0, 0.4]),
                              5, 0.95)
    assert lo == -math.inf and hi == math.inf
    # all-zero sample → hidden-block bound, shrinking with coverage
    z = np.zeros(4)
    pis = np.full(4, 0.5)
    _, lo8, hi8 = ht_interval(z, pis, 8, 0.95)       # f = 0.5
    _, lo16, hi16 = ht_interval(z, pis, 16, 0.95)    # f = 0.25
    assert lo8 == lo16 == 0.0
    assert hi8 == pytest.approx(math.log(0.05) / math.log(0.5))
    assert hi16 > hi8


# ---------------------------------------------------------------------------
# planner gating + plan codec
# ---------------------------------------------------------------------------

def _planner(g, cfg):
    return ExecutionPlanner(g, cfg, cost_model=CostModel())


def test_plan_sampled_records_replayable_draw():
    g = _graph()
    cfg = _cfg("mis", "sampled", sample_fraction=0.5)
    from repro.core.flexis import initial_candidates
    pats = initial_candidates(g)
    plan = _planner(g, cfg).plan_level(1, pats, [3] * len(pats))
    assert plan.plane == "sampled"
    s = plan.sample
    assert s is not None and s["weights"] == "degree"
    assert s["key"] == sample_key(0, 1)
    assert len(s["positions"]) == s["n_sample"] == len(s["pis"])
    assert s["n_sample"] < -(-g.n // cfg.match.root_block)
    # JSON round-trip preserves the draw exactly (resume replays it)
    d = json.loads(json.dumps(plan.to_dict()))
    back = LevelPlan.from_dict(d, cfg.match)
    assert back.sample == s and back.plane == "sampled"
    # occupancy telemetry beats the degree fallback when present
    peaks = list(range(-(-g.n // cfg.match.root_block)))
    plan2 = _planner(g, cfg).plan_level(
        2, pats, [3] * len(pats), prev={"block_peaks": peaks})
    assert plan2.sample["weights"] == "occupancy"
    assert plan2.sample["positions"] != s["positions"] or \
        plan2.sample["key"] != s["key"]


def test_plan_sampled_degenerates_to_batched():
    g = _graph()
    from repro.core.flexis import initial_candidates
    pats = initial_candidates(g)
    # complete=True: every block must run → no sample can help
    cfg = _cfg("mis", "sampled", complete=True)
    assert _planner(g, cfg).plan_level(1, pats, [3] * len(pats)).plane \
        == "batched"
    # empty level
    cfg = _cfg("mis", "sampled")
    assert _planner(g, cfg).plan_level(1, [], []).plane == "batched"
    # too few blocks to both sample and leave something out
    big_block = dataclasses.replace(_match_cfg(), root_block=64)
    cfg = _cfg("mis", "sampled", match=big_block)
    p = _planner(g, cfg)
    assert p.n_blocks < MIN_SAMPLED_BLOCKS
    assert p.plan_level(1, pats, [3] * len(pats)).plane == "batched"
    # a fraction that rounds to full coverage stays sampled but unit-π
    cfg = _cfg("mis", "sampled", sample_fraction=1.0)
    plan = _planner(g, cfg).plan_level(1, pats, [3] * len(pats))
    assert plan.plane == "sampled" and plan.sample["fraction"] == 1.0
    assert all(p == 1.0 for p in plan.sample["pis"])


def test_auto_prices_sampled_by_tau_and_escalation():
    """The auto planner prices the sampled plane per level (ISSUE 10):
    below the hidden-mass bound it must stay exact (a zero-support pattern
    cannot be pruned there), above it the predicted escalation mass decides
    — and the whole decision, inputs included, rides in the plan."""
    g = _graph()
    from repro.core.flexis import initial_candidates
    from repro.core.planner import hidden_mass_bound
    pats = initial_candidates(g)

    # τ = 3 sits below the hidden-mass bound at f = 0.25 → batched, with
    # the pricing record explaining why
    pl = _planner(g, _cfg("mis", "auto"))
    plan = pl.plan_level(1, pats, [3] * len(pats))
    assert plan.plane in ("sequential", "batched", "distributed")
    assert plan.sample is None
    if plan.pricing is not None:
        assert plan.pricing["chosen"] == "batched"
        assert plan.pricing["tau_min"] <= plan.pricing["hidden_bound"]

    # τ far above the bound + telemetry showing everything pruned →
    # sampled wins, decision + draw recorded and JSON-replayable
    hidden = hidden_mass_bound(0.95, 0.25)
    tau = int(hidden) + 5
    prev = {"sampled": {"exact": False, "escalated": 0, "pruned": 20},
            "searched": 20, "frequent": 0}
    plan2 = _planner(g, _cfg("mis", "auto")).plan_level(
        2, pats, [tau] * len(pats), prev=prev)
    assert plan2.plane == "sampled" and plan2.sample is not None
    assert plan2.pricing["chosen"] == "sampled"
    assert plan2.pricing["esc_source"] == "telemetry"
    assert plan2.pricing["esc"] == 0.0
    assert plan2.pricing["sampled_s"] < plan2.pricing["batched_s"]
    d = json.loads(json.dumps(plan2.to_dict()))
    back = LevelPlan.from_dict(d, _match_cfg())
    assert back.pricing == plan2.pricing and back.sample == plan2.sample

    # ... but a prior of certain escalation makes sampling pointless even
    # at a huge τ (f·b + 1.0·((1−f)·b + replay) ≥ margin·b)
    prev_bad = {"sampled": {"exact": False, "escalated": 20, "pruned": 0},
                "searched": 20, "frequent": 20}
    plan3 = _planner(g, _cfg("mis", "auto")).plan_level(
        2, pats, [tau] * len(pats), prev=prev_bad)
    assert plan3.plane != "sampled"
    assert plan3.pricing is None or plan3.pricing["chosen"] == "batched"


def test_predict_escalation_chain():
    """telemetry → frontier → prior, most-informed first."""
    g = _graph()
    pl = _planner(g, _cfg("mis", "auto"))
    # no prev at all → the calibration prior
    from repro.core.planner import ESCALATION_PRIOR
    esc, src = pl._predict_escalation(None)
    assert (esc, src) == (ESCALATION_PRIOR, "prior")
    # sampled telemetry wins
    esc, src = pl._predict_escalation(
        {"sampled": {"exact": False, "escalated": 3, "pruned": 9},
         "searched": 12, "frequent": 12})
    assert src == "telemetry" and esc == pytest.approx(0.25)
    # exact (degenerate) sampled telemetry is no telemetry
    esc, src = pl._predict_escalation(
        {"sampled": {"exact": True, "escalated": 0, "pruned": 0},
         "searched": 10, "frequent": 5})
    assert src == "frontier"
    assert esc == pytest.approx(0.5 + ESCALATION_PRIOR * 0.5)
    # calibrated prior replaces the constant
    pl2 = ExecutionPlanner(g, _cfg("mis", "auto"),
                           cost_model=CostModel(escalation_fraction=0.1))
    esc, src = pl2._predict_escalation(None)
    assert (esc, src) == (0.1, "prior")


def test_block_degree_stat_indexes_block_ids():
    g = _graph()
    stat = block_degree_stat(g, 8)
    deg = np.diff(g.out_indptr)
    assert stat.shape[0] == -(-g.n // 8)
    assert int(stat[0]) == int(deg[:8].max())


def test_sampled_config_validation():
    with pytest.raises(ValueError):
        _cfg("mis_exact", "sampled")
    with pytest.raises(ValueError):
        _cfg("mis", "sampled", sample_fraction=0.0)
    with pytest.raises(ValueError):
        _cfg("mis", "sampled", sample_fraction=1.5)
    with pytest.raises(ValueError):
        _cfg("mis", "sampled", confidence=1.0)


# ---------------------------------------------------------------------------
# calibration schema 2 (per-metric row times) + schema-1 back-compat
# ---------------------------------------------------------------------------

def test_row_time_per_metric_with_fallback():
    cm = CostModel(row_time_s=4e-6, row_time_mni_s=1e-6)
    assert cm.row_time("mni") == 1e-6
    assert cm.row_time("mis") == 4e-6
    assert cm.row_time("frac") == 4e-6        # no override → shared constant
    assert cm.row_time("mis_luby") == 4e-6
    # the metric reaches the block-step estimate
    cfg = MatchConfig(cap=64, root_block=16, chunk=4, max_chunks=1)
    assert cm.block_step_s(cfg, 3, 1, batched=False, metric="mni") \
        < cm.block_step_s(cfg, 3, 1, batched=False, metric="mis")


def test_schema1_calibration_still_loads(tmp_path, monkeypatch):
    old = tmp_path / "old.json"
    old.write_text(json.dumps({
        "schema": 1, "dispatch_overhead_s": 1e-3, "lane_time_s": 1e-9,
        "row_time_s": 2e-6, "vmap_factor": 1.1}))
    monkeypatch.setenv(CALIBRATION_ENV, str(old))
    cm = load_calibration()
    assert cm.row_time_s == 2e-6
    # schema-1 files carry no per-metric overrides → shared constant
    for metric in METRICS:
        assert cm.row_time(metric) == 2e-6


def test_schema2_roundtrip(tmp_path, monkeypatch):
    cm = CostModel(row_time_s=4e-6, row_time_mni_s=1e-6,
                   row_time_frac_s=2e-6, row_time_luby_s=8e-6,
                   source="fit")
    f = tmp_path / "new.json"
    f.write_text(json.dumps(cm.to_dict()))
    monkeypatch.setenv(CALIBRATION_ENV, str(f))
    back = load_calibration()
    assert back == dataclasses.replace(cm, source=str(f))
    assert back.row_time("mis_luby") == 8e-6


# ---------------------------------------------------------------------------
# calibration schema 3 (measured escalation fraction) — ISSUE 10
# ---------------------------------------------------------------------------

def test_persist_escalation_fraction_ema_and_schema_upgrade(tmp_path):
    from repro.core.planner import (
        CALIBRATION_SCHEMA, persist_escalation_fraction,
    )
    # fresh file: the raw measurement lands as-is, schema stamped 3
    p = tmp_path / "cal.json"
    assert persist_escalation_fraction(0.4, path=str(p)) == str(p)
    d = json.loads(p.read_text())
    assert d["schema"] == CALIBRATION_SCHEMA
    assert d["escalation_fraction"] == pytest.approx(0.4)
    # second run folds in with EMA weight 0.5
    persist_escalation_fraction(0.0, path=str(p))
    assert json.loads(p.read_text())["escalation_fraction"] \
        == pytest.approx(0.2)
    # out-of-range measurements clamp before the EMA
    persist_escalation_fraction(7.5, path=str(p))
    assert json.loads(p.read_text())["escalation_fraction"] \
        == pytest.approx(0.6)
    # schema-1 files upgrade in place, preserving their fitted constants
    old = tmp_path / "old.json"
    old.write_text(json.dumps({
        "schema": 1, "dispatch_overhead_s": 1e-3, "lane_time_s": 1e-9,
        "row_time_s": 2e-6, "vmap_factor": 1.1}))
    persist_escalation_fraction(0.3, path=str(old))
    up = json.loads(old.read_text())
    assert up["schema"] == CALIBRATION_SCHEMA
    assert up["row_time_s"] == 2e-6
    assert up["escalation_fraction"] == pytest.approx(0.3)
    # and the loaded model's prior is the measured fraction
    cm = load_calibration(str(old))
    assert cm.escalation_fraction == pytest.approx(0.3)
    assert cm.esc_prior() == pytest.approx(0.3)


def test_persist_escalation_fraction_only_into_named_file(tmp_path,
                                                        monkeypatch):
    """The tracked cwd default is never rewritten; the env-named file is."""
    from repro.core.planner import persist_escalation_fraction

    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv(CALIBRATION_ENV, raising=False)
    default = tmp_path / "planner_calibration.json"
    default.write_text(json.dumps({"schema": 3, "lane_time_s": 1e-9}))
    before = default.read_text()
    assert persist_escalation_fraction(0.5) is None
    assert default.read_text() == before
    named = tmp_path / "named.json"
    monkeypatch.setenv(CALIBRATION_ENV, str(named))
    assert persist_escalation_fraction(0.5) == str(named)
    assert json.loads(named.read_text())["escalation_fraction"] == 0.5
    assert default.read_text() == before


def test_schema12_load_leaves_prior_at_constant(tmp_path, monkeypatch):
    from repro.core.planner import ESCALATION_PRIOR
    f = tmp_path / "s2.json"
    f.write_text(json.dumps({
        "schema": 2, "dispatch_overhead_s": 1e-3, "lane_time_s": 1e-9,
        "row_time_s": 2e-6, "vmap_factor": 1.1, "row_time_mni_s": 1e-6}))
    monkeypatch.setenv(CALIBRATION_ENV, str(f))
    cm = load_calibration()
    assert cm.escalation_fraction is None
    assert cm.esc_prior() == ESCALATION_PRIOR


# ---------------------------------------------------------------------------
# RNG golden values — the draws below are part of the resume format: a
# numpy upgrade that shifts any of them would silently break replay of
# recorded sample rounds, so they are pinned to exact floats (ISSUE 10)
# ---------------------------------------------------------------------------

def test_rng_golden_values():
    assert sample_key(0, 1) == [0, 1]
    assert sample_key(3, 2) == [3, 2]
    k = sample_key(0, 1)
    assert sample_uniform(k) == 0.70962399485867
    # count=1 must be bit-identical to the historical single-draw form
    assert sample_uniform(k, count=1) == sample_uniform(k)
    # count=r+1 is the round-r uniform: a later round never disturbs an
    # earlier round's draw (same generator, last of r+1 variates)
    assert sample_uniform(k, count=2) == 0.9795624859036957
    assert sample_uniform(sample_key(3, 2), count=3) == 0.6850707717552736

    w = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0])
    pos, pis = systematic_sample(w, 3, 0.5)
    assert pos.tolist() == [3, 5, 7]
    assert pis.tolist() == [
        0.3333333333333333, 0.5, 0.6666666666666666]
    from repro.core.sampled import inclusion_probs
    assert inclusion_probs(w, 3).tolist() == [
        0.08333333333333333, 0.16666666666666666, 0.25,
        0.3333333333333333, 0.4166666666666667, 0.5,
        0.5833333333333334, 0.6666666666666666]
    # the full-schedule vector agrees with the draw's own π at every
    # sampled position — the identity conditional PPS composes on
    assert inclusion_probs(w, 3)[pos].tolist() == pis.tolist()


# ---------------------------------------------------------------------------
# adaptive rounds + escalation reuse (direct level evaluation) — ISSUE 10
# ---------------------------------------------------------------------------

def _level_fixture(metric="mis", fraction=0.5):
    """One real level: graph, device graph, candidate patterns, the
    planner's recorded draw, and the complete-coverage exact outcomes."""
    from repro.core.batched import evaluate_level_batched
    from repro.core.flexis import initial_candidates
    from repro.core.graph import DeviceGraph

    g = _graph()
    cfg = _cfg(metric, "sampled", sample_fraction=fraction)
    pats = initial_candidates(g)
    plan = _planner(g, cfg).plan_level(1, pats, [3] * len(pats))
    assert plan.plane == "sampled" and plan.sample is not None
    dev_g = DeviceGraph.from_host(g)
    exact, timed, _ = evaluate_level_batched(
        g, dev_g, pats, [1] * len(pats), metric, cfg.match, complete=True)
    assert not timed
    return g, dev_g, cfg, pats, plan, exact


def test_escalation_reuse_never_rematches_sampled_blocks():
    """Acceptance: with τ one above every true support nothing early-exits
    and nothing prunes, so the escalation walks the full schedule for
    every pattern — and the counters prove each sampled block is replayed,
    never re-matched.  All-escalate also means the settled-set CI width
    has no samples: `ci_width_mean` must be None (JSON null), not NaN."""
    from repro.core.sampled import evaluate_level_sampled

    g, dev_g, cfg, pats, plan, exact = _level_fixture("mis", 0.5)
    taus = [o.support + 1 for o in exact]
    m = -(-g.n // cfg.match.root_block)
    counters = {}
    outs, timed, tel = evaluate_level_sampled(
        g, dev_g, pats, taus, "mis", cfg.match, sample=plan.sample,
        confidence=cfg.confidence, escalate=True, max_batch=64,
        sample_rounds=1, counters=counters)
    assert not timed
    s = tel.sampled
    assert s["escalated"] == len(pats) and s["pruned"] == 0
    assert s["ci_width_mean"] is None
    assert "NaN" not in json.dumps(s, allow_nan=False)
    # every pattern escalated ⇒ exact outcomes, bit-identical to complete
    for o, e in zip(outs, exact):
        assert not o.estimated
        assert (o.support, o.embeddings_found, o.overflowed) \
            == (e.support, e.embeddings_found, e.overflowed)
    # one k=2 group (max_batch ≥ P): the full walk visits every block
    # exactly once per group — sampled positions via the update-only
    # replay step, the rest via real match steps
    n_groups = -(-len(pats) // 64)
    assert counters["replay_blocks"] == n_groups * s["n_sample"]
    assert counters["match_blocks"] == n_groups * (m - s["n_sample"])


def test_adaptive_rounds_grow_coverage_until_undecided_stops_shrinking():
    """Mixed τ: half the patterns sit far below an astronomic τ (the CI
    prunes them round 1), the rest straddle τ (stay undecided) — so the
    sampler must draw a second geometric round before handing the rest to
    escalation.  Escalated outcomes stay bit-identical to complete."""
    from repro.core.sampled import evaluate_level_sampled

    g, dev_g, cfg, pats, plan, exact = _level_fixture("mis", 0.5)
    taus = [10 ** 6 if i % 2 == 0 else exact[i].support + 1
            for i in range(len(pats))]
    outs, timed, tel = evaluate_level_sampled(
        g, dev_g, pats, taus, "mis", cfg.match, sample=plan.sample,
        confidence=cfg.confidence, escalate=True, max_batch=64,
        sample_rounds=3)
    assert not timed
    s = tel.sampled
    assert s["pruned"] >= 1 and s["escalated"] >= 1
    # round 1 pruned the easy half and left undecided mass → a further
    # round ran, and coverage grew beyond the plan's round-0 draw
    assert s["rounds"] >= 2
    assert s["n_sample"] > plan.sample["n_sample"]
    assert s["ci_width_mean"] is not None and s["ci_width_mean"] >= 0.0
    for i, (o, e) in enumerate(zip(outs, exact)):
        if taus[i] == 10 ** 6:
            assert o.estimated and not o.frequent
        else:
            assert not o.estimated
            assert (o.support, o.embeddings_found) \
                == (e.support, e.embeddings_found)
