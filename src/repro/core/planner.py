"""Adaptive execution planner — cost-model-driven level scheduling.

Every mining level asks the same three questions:

  1. **Which data plane?**  The batched plane (`core/batched.py`) amortizes
     dispatch + host-sync overhead across a level's candidates and wins
     ≥2–4× when levels are dispatch-bound; but when a single pattern's
     block already saturates the device (one pattern, a big ``cap·chunk``
     grid) it is parity-or-slower than the sequential oracle.  The
     distributed plane adds the mesh, at collective-latency cost.
  2. **How wide a pattern bucket?**  Bigger buckets amortize more dispatch
     overhead but multiply transient device memory.
  3. **What matcher geometry?**  `MatchConfig.for_graph` is one
     graph-global guess; actual frontier occupancy is a per-level quantity
     the previous level already measured (``max_count`` telemetry), so
     ``cap`` can be right-sized level by level — on compute-bound levels
     that is directly proportional compute saved.

`ExecutionPlanner` answers all three from a small calibrated cost model
(`CostModel`: dispatch overhead + per-lane throughput + vmap fusion-loss
factor — fitted by ``benchmarks/calibrate.py``, loaded from a JSON file
with safe built-in defaults) plus the level's observable inputs: candidate
count, per-pattern frontier occupancy of the previous level, and graph
degree stats.  With ``MiningConfig.execution == "auto"`` (the default)
`mine()` consults the planner at every level boundary and records the
decision in ``MiningResult.per_level[level]["plan"]`` and in the session
snapshot, so a ``--resume`` replays the in-flight level's plan
bit-identically even if the calibration file changed between processes.

Result-preservation contract (what "auto is bit-identical to every forced
plane" rests on):

  * plane choice never changes per-pattern results — that is the batched ≡
    sequential equivalence contract, property-tested since PR 1;
  * ``cap`` right-sizing preserves results whenever no level overflows the
    derived cap (truncation is the *only* cap-dependent behaviour, and it
    is always flagged via ``overflowed``); the planner therefore only
    shrinks with ≥``CAP_HEADROOM``× headroom over the observed peak, never
    below ``CAP_FLOOR``, and not at all when the previous level overflowed;
  * ``chunk``/``max_chunks`` are **never** changed when ``max_chunks > 1``:
    survivors are packed in (chunk, row, position) order, so re-chunking a
    multi-chunk gather would permute embedding priority and with it the
    greedy-mIS selection.  When one chunk already covers the max degree the
    order is plain row-major and shrinking ``chunk`` is order-preserving;
  * ``two_phase`` toggling preserves results absent overflow (same
    survivors, same packing order — `tests/kernels` pin this).

**Degree-ordered root blocks** (`root_block_order`): root blocks are
dispatched in descending max-out-degree order instead of vertex-id order.
High-yield roots are matched first, so the τ early-exit in ``mis`` /
``mis_luby`` fires after fewer blocks.  The permutation is a static
function of (graph, root_block, ``MiningConfig.root_order``): it is the
*schedule*, shared verbatim by all three planes (which keeps them
bit-identical to each other) and part of the session config fingerprint
(which keeps resumes bit-identical).  Completed metric values remain
deterministic because mIS priority is embedding-row order *within* the
chosen schedule.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .batched import _bucket_size as _pow2_ceil
from .graph import DataGraph
from .matcher import MatchConfig

__all__ = [
    "CostModel", "LevelPlan", "ExecutionPlanner", "block_degree_stat",
    "root_block_order", "DEFAULT_CALIBRATION_FILE", "load_calibration",
    "persist_escalation_fraction",
]

# calibration file the planner looks for (cwd-relative; override with the
# REPRO_PLANNER_CALIBRATION env var).  Written by `benchmarks/calibrate.py`.
DEFAULT_CALIBRATION_FILE = "planner_calibration.json"
CALIBRATION_ENV = "REPRO_PLANNER_CALIBRATION"
# schema 2 added per-metric row times (row_time_{mni,frac,luby}_s); schema 3
# added the measured escalation fraction (escalation_fraction — the sampled
# plane's pricing warm-start).  Schema-1/2 files still load — the missing
# constants fall back to the shared one / the ESCALATION_PRIOR constant.
CALIBRATION_SCHEMA = 3
CALIBRATION_SCHEMAS = (1, 2, 3)

# cap right-sizing safety rails (see module docstring / docs/architecture.md)
CAP_HEADROOM = 4        # derived cap ≥ headroom × observed peak occupancy
CAP_FLOOR = 1024        # never shrink below this many frontier rows

# sampled plane (execution="sampled"): prior on the fraction of a level's
# batched cost the exact escalation pass re-spends, scaled by the unsampled
# fraction — the cost-model row for the sample pass prices
#   f·batched + ESCALATION_PRIOR·(1−f)·batched
# so fraction 1.0 prices exactly like (and degenerates to) forced batched
ESCALATION_PRIOR = 0.25
# below this many root blocks a sample cannot both draw ≥1 block and leave
# ≥1 out — the plan falls back to the exact batched plane
MIN_SAMPLED_BLOCKS = 2
# auto only picks the sampled plane when its priced cost undercuts the
# batched row by this factor — a win margin that absorbs the model's own
# error (escalation prediction, replay pricing) before auto gambles on a
# statistical plane whose worst case is "everything escalates"
SAMPLED_MARGIN = 0.9


def hidden_mass_bound(confidence: float, f_cov: float) -> float:
    """Max support the unsampled blocks can hide at the CI confidence.

    Mirrors `sampled.ht_interval`'s zero-mass hidden-block bound: with
    covered probability mass ``f_cov``, a pattern whose sample saw nothing
    can still hold up to ``ln(1−confidence)/ln(1−f_cov)`` embeddings before
    the miss probability drops below ``1−confidence``.  The planner uses it
    as an eligibility gate: when a level's smallest τ is below this bound,
    even zero-mass (i.e. hopeless) patterns escalate and the sample pass is
    pure overhead.
    """
    if f_cov >= 1.0:
        return 0.0
    alpha = max(1e-12, 1.0 - confidence)
    return math.log(alpha) / math.log(max(1e-300, 1.0 - f_cov))


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CostModel:
    """Three-term linear device-step model, plus the vmap fusion tax.

    One batched step over a bucket of P same-k patterns costs

        dispatch_overhead_s
          + P · (lanes(cfg, k) · lane_time_s + cap · row_time_s)
              · (vmap_factor if P > 1 else 1)

    with two distinct work terms — they scale differently and conflating
    them is exactly the miscalibration that flips plane decisions:

      * ``lanes · lane_time_s`` — the expansion grid:
        ``(k−1) · cap · chunk · max_chunks`` candidate lanes, each paying
        the gather/mask/compact pipeline;
      * ``cap · row_time_s`` — the per-frontier-row metric update (the
        greedy-mIS loop walks the frontier's rows; dominant on CPU where
        loop iteration overhead is large).

    Both work terms are upper bounds: the matcher walks only the valid
    frontier rows and the chunks their degrees need.

    ``dispatch_overhead_s`` is everything a step pays regardless of
    geometry: program dispatch, host↔device sync, the host loop's python
    bookkeeping.  ``vmap_factor ≥ 1`` is the measured per-lane slowdown
    of the vmapped program vs the unbatched one (XLA loses cross-op
    fusion on wide batched grids; see docs/architecture.md "Why the
    vmapped matcher loses fusion").  The sequential plane pays the
    overhead once per pattern per block with no vmap tax.

    ``row_time_s`` is fitted on the ``mis`` step; the metric scan term is
    the one constant that genuinely differs across metrics (greedy mIS's
    sequential loop vs MNI's scatter-OR vs frac's scatter-add), so schema-2
    calibrations carry optional per-metric overrides
    (``row_time_{mni,frac,luby}_s`` — ``row_time(metric)`` resolves them,
    falling back to the shared constant for schema-1 files and defaults).
    Everything else is metric-independent: the model prices *relative*
    plane/bucket choices, not absolute runtimes.  Defaults are
    conservative CPU numbers, and ``backend`` says so: a model prices
    planes only on the platform it describes (None: a file that does not
    say — taken to describe the running one).
    """

    dispatch_overhead_s: float = 2.0e-3
    lane_time_s: float = 2.0e-9
    row_time_s: float = 4.0e-6
    vmap_factor: float = 1.15
    row_time_mni_s: Optional[float] = None
    row_time_frac_s: Optional[float] = None
    row_time_luby_s: Optional[float] = None
    # schema 3: measured per-run escalation fraction of the sampled plane
    # (escalated / classified, persisted by `launch/mine.py` after a
    # sampled run) — warm-starts the auto pricing's escalation predictor
    # when a level has no telemetry of its own yet.  None (schema-1/2
    # files, fresh fits) falls back to the ESCALATION_PRIOR constant.
    escalation_fraction: Optional[float] = None
    source: str = "defaults"
    backend: Optional[str] = "cpu"

    def to_dict(self) -> Dict[str, Any]:
        return {
            "schema": CALIBRATION_SCHEMA,
            "dispatch_overhead_s": self.dispatch_overhead_s,
            "lane_time_s": self.lane_time_s,
            "row_time_s": self.row_time_s,
            "vmap_factor": self.vmap_factor,
            "row_time_mni_s": self.row_time_mni_s,
            "row_time_frac_s": self.row_time_frac_s,
            "row_time_luby_s": self.row_time_luby_s,
            "escalation_fraction": self.escalation_fraction,
            "source": self.source,
            "backend": self.backend,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "CostModel":
        base = cls()

        def opt(key: str) -> Optional[float]:
            v = d.get(key)
            return None if v is None else float(v)

        try:
            return cls(
                dispatch_overhead_s=float(
                    d.get("dispatch_overhead_s", base.dispatch_overhead_s)),
                lane_time_s=float(d.get("lane_time_s", base.lane_time_s)),
                row_time_s=float(d.get("row_time_s", base.row_time_s)),
                vmap_factor=max(1.0, float(d.get("vmap_factor",
                                                 base.vmap_factor))),
                row_time_mni_s=opt("row_time_mni_s"),
                row_time_frac_s=opt("row_time_frac_s"),
                row_time_luby_s=opt("row_time_luby_s"),
                escalation_fraction=opt("escalation_fraction"),
                source=str(d.get("source", "file")),
                backend=(None if d.get("backend") is None
                         else str(d["backend"])),
            )
        except (TypeError, ValueError):
            return base

    def lanes(self, cfg: MatchConfig, k: int) -> int:
        return max(1, (k - 1)) * cfg.cap * cfg.chunk * cfg.max_chunks

    def row_time(self, metric: str = "mis") -> float:
        """The metric-scan constant for ``metric`` (schema-2 override or
        the shared mis-fitted ``row_time_s``)."""
        override = {"mni": self.row_time_mni_s,
                    "frac": self.row_time_frac_s,
                    "mis_luby": self.row_time_luby_s}.get(metric)
        return self.row_time_s if override is None else override

    def pattern_work_s(self, cfg: MatchConfig, k: int,
                       metric: str = "mis") -> float:
        """Device work of ONE pattern's block step (no overhead/tax)."""
        return (self.lanes(cfg, k) * self.lane_time_s
                + cfg.cap * self.row_time(metric))

    def block_step_s(self, cfg: MatchConfig, k: int, bucket: int,
                     *, batched: bool, metric: str = "mis") -> float:
        """Predicted wall time of ONE device step over one root block."""
        factor = self.vmap_factor if (batched and bucket > 1) else 1.0
        return (self.dispatch_overhead_s
                + bucket * self.pattern_work_s(cfg, k, metric) * factor)

    def esc_prior(self) -> float:
        """Escalation-mass prior: the measured fraction when calibrated
        (schema 3), the ESCALATION_PRIOR constant otherwise — clamped to
        [0, 1] so a corrupt calibration can't price a negative pass."""
        if self.escalation_fraction is None:
            return ESCALATION_PRIOR
        return min(1.0, max(0.0, float(self.escalation_fraction)))

    def replay_step_s(self, cfg: MatchConfig, k: int, bucket: int,
                      *, metric: str = "mis") -> float:
        """Predicted wall time of ONE update-only replay step.

        Escalation reuse replays a sampled block's recorded embeddings
        through the metric update without re-running the expansion grid —
        so the step pays dispatch plus the per-row metric scan, but no
        ``lanes · lane_time`` term.
        """
        factor = self.vmap_factor if bucket > 1 else 1.0
        return (self.dispatch_overhead_s
                + bucket * cfg.cap * self.row_time(metric) * factor)


def load_calibration(path: Optional[str] = None,
                     device=None) -> CostModel:
    """Load the fitted `CostModel`, falling back to safe defaults.

    Search order: explicit ``path`` (exclusively, when given) →
    ``$REPRO_PLANNER_CALIBRATION`` → ``./planner_calibration.json``.  A
    missing or malformed file is never an error — the planner must work
    out of the box.  A file fitted on another device — its ``backend`` or,
    where recorded, ``device_kind`` differs from ``device`` (default:
    ``jax.devices()[0]``) — is ignored for the built-in defaults, with a
    note on stderr (once per file and reason): CPU timings must not price
    planes on a TPU.
    """
    env = os.environ.get(CALIBRATION_ENV)
    candidates = [path] if path is not None else [env,
                                                  DEFAULT_CALIBRATION_FILE]
    # the cwd default may legitimately be absent; an *explicitly requested*
    # file (argument or env var) that can't be used deserves a warning —
    # silently planning with different constants than asked for is worse
    # than noise on stderr
    explicit = {c for c in (path, env) if c}
    for cand in candidates:
        if not cand:
            continue
        problem = None
        p = Path(cand)
        if not p.is_file():
            problem = "not found"
        else:
            try:
                d = json.loads(p.read_text())
            except (OSError, ValueError) as e:
                problem, d = f"unreadable ({e})", None
            if d is not None and d.get("schema") not in CALIBRATION_SCHEMAS:
                problem = (f"schema {d.get('schema')!r} not in "
                           f"{CALIBRATION_SCHEMAS}")
        mismatch = _device_mismatch(d, device) if problem is None else None
        # an explicitly requested file that can't be used does NOT fall
        # through to whatever file happens to sit in cwd
        if mismatch is not None or (problem is not None and cand in explicit):
            note = (f"[planner] ignoring calibration {cand}: "
                    f"{mismatch or problem}; using built-in defaults")
            if note not in _NOTED:
                _NOTED.add(note)
                print(note, file=sys.stderr)
            return CostModel()
        if problem is not None:
            continue
        # `source` records provenance-as-loaded: the path wins over any
        # source the file itself carries (calibrate.py writes "fit")
        d["source"] = str(p)
        return CostModel.from_dict(d)
    return CostModel()


_NOTED: set = set()   # stderr notes already given by load_calibration


def _device_mismatch(d: Dict[str, Any], dev=None) -> Optional[str]:
    """Why calibration ``d`` does not describe device ``dev`` (default:
    ``jax.devices()[0]``), or None."""
    if dev is None:
        import jax

        dev = jax.devices()[0]
    for key, running in (("backend", dev.platform),
                         ("device_kind", dev.device_kind)):
        fitted = d.get(key)
        if fitted is not None and fitted != running:
            return f"fitted for {key} {fitted!r}, running on {running!r}"
    return None


def persist_escalation_fraction(fraction: float,
                                path: Optional[str] = None) -> Optional[str]:
    """Fold a run's measured escalation fraction into the calibration file.

    The sampled-plane pricing (`ExecutionPlanner._price_sampled`) falls
    back to ``ESCALATION_PRIOR`` when a level has no telemetry; persisting
    the measured fraction (schema 3) warm-starts the next run's prior from
    real data.  EMA with weight 0.5 against any existing value smooths
    run-to-run noise.  Only a file named explicitly (``path``, else
    ``$REPRO_PLANNER_CALIBRATION``) is written — never the cwd default,
    whose drift would make two runs of one query plan differently.
    Schema-1/2 files are upgraded in place, other existing constants are
    preserved, and any I/O or parse problem is swallowed (calibration is an
    optimization, never a correctness input).  Returns the path written,
    or None.
    """
    frac = min(1.0, max(0.0, float(fraction)))
    target = path or os.environ.get(CALIBRATION_ENV)
    if not target:
        return None
    p = Path(target)
    d: Dict[str, Any] = {}
    if p.is_file():
        try:
            loaded = json.loads(p.read_text())
            if (isinstance(loaded, dict)
                    and loaded.get("schema") in CALIBRATION_SCHEMAS):
                d = loaded
        except (OSError, ValueError):
            pass
    prev = d.get("escalation_fraction")
    if isinstance(prev, (int, float)):
        frac = 0.5 * float(prev) + 0.5 * frac
    d["schema"] = CALIBRATION_SCHEMA
    d["escalation_fraction"] = frac
    try:
        p.write_text(json.dumps(d, indent=2, sort_keys=True) + "\n")
    except OSError:
        return None
    return str(p)


# ---------------------------------------------------------------------------
# root-block schedule
# ---------------------------------------------------------------------------

def block_degree_stat(g: DataGraph, root_block: int) -> np.ndarray:
    """Per-root-block max out-degree (block-id indexed, int64 ≥ −1).

    The yield proxy shared by the degree schedule (`root_block_order`) and
    the sampled plane's fallback draw weights (no occupancy telemetry yet
    at k = 2).
    """
    n_blocks = max(1, -(-g.n // root_block))
    deg = np.diff(g.out_indptr).astype(np.int64)
    padded = np.full(n_blocks * root_block, -1, np.int64)
    padded[: deg.shape[0]] = deg
    return padded.reshape(n_blocks, root_block).max(axis=1)


def root_block_order(g: DataGraph, root_block: int,
                     mode: str = "degree") -> np.ndarray:
    """Static permutation of root-block ids — the level's block schedule.

    ``"degree"``: blocks sorted by descending max out-degree of their
    vertices (stable, so ties keep vertex-id order) — high-yield roots run
    first and τ early-exit terminates levels sooner.  ``"vertex"``: the
    legacy identity order.  The permutation depends only on
    (graph, root_block, mode), so every plane — and every resume — walks
    the identical schedule.
    """
    n_blocks = max(1, -(-g.n // root_block))
    if mode == "vertex" or n_blocks == 1:
        return np.arange(n_blocks, dtype=np.int64)
    if mode != "degree":
        raise ValueError('root_order must be "degree" or "vertex"')
    block_max = block_degree_stat(g, root_block)
    # stable descending sort: ties stay in ascending block-id order
    return np.argsort(-block_max, kind="stable").astype(np.int64)


# ---------------------------------------------------------------------------
# per-level plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LevelPlan:
    """One level's execution decision (JSON-stable via to/from_dict)."""

    plane: str                 # "sequential" | "batched" | "distributed"
                               # | "sampled"
    match: MatchConfig         # per-level matcher geometry
    max_batch: int             # pattern-bucket ceiling for level_groups
    # sampled plane only: the level's recorded block draw —
    # {"fraction", "n_sample", "positions" (schedule indices), "pis"
    # (inclusion probabilities), "key" (RNG key words), "weights"
    # ("occupancy" | "degree"), "w" (full schedule-ordered weight vector —
    # what the adaptive rounds redraw from)}.  Part of to_dict/from_dict,
    # so a resumed level replays the *identical* sample instead of
    # re-drawing.
    sample: Optional[Dict[str, Any]] = None
    # auto pricing record: every input of the sampled-vs-batched decision
    # ({"batched_s", "sampled_s", "replay_s", "fraction", "esc",
    # "esc_source", "margin", "tau_min", "hidden_bound", "chosen"}) —
    # recorded whenever auto evaluated the sampled plane, chosen or not,
    # and replayed verbatim on resume (part of to_dict/from_dict).
    pricing: Optional[Dict[str, Any]] = None

    def to_dict(self) -> Dict[str, Any]:
        """The decision as recorded in per_level / session snapshots.

        JSON-native values only (the sample dict holds ints/floats/
        strings), so the dict survives a JSON round-trip unchanged — which
        is what makes a replayed decision compare equal to the original in
        the resume bit-identity tests.
        """
        m = self.match
        d = {
            "plane": self.plane,
            "cap": int(m.cap),
            "root_block": int(m.root_block),
            "chunk": int(m.chunk),
            "max_chunks": int(m.max_chunks),
            "two_phase": bool(m.two_phase),
            "max_batch": int(self.max_batch),
        }
        if self.sample is not None:
            d["sample"] = self.sample
        if self.pricing is not None:
            d["pricing"] = self.pricing
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any], base: MatchConfig) -> "LevelPlan":
        """Rebuild a recorded decision on top of the run's base geometry."""
        match = dataclasses.replace(
            base,
            cap=int(d["cap"]),
            root_block=int(d["root_block"]),
            chunk=int(d["chunk"]),
            max_chunks=int(d["max_chunks"]),
            two_phase=bool(d["two_phase"]),
        )
        return cls(plane=str(d["plane"]), match=match,
                   max_batch=int(d["max_batch"]), sample=d.get("sample"),
                   pricing=d.get("pricing"))


class ExecutionPlanner:
    """Chooses (plane, bucket, geometry) per level for ``mine()``.

    Forced execution modes pass through unchanged (the three planes stay
    available as oracles); ``"auto"`` applies the cost model.  The planner
    is pure host arithmetic — it never touches the device — and fully
    deterministic given (graph, config, cost model), which the session
    runtime leans on for resume bit-identity (it additionally pins the
    cost model and the in-flight level's decision in every snapshot).
    """

    def __init__(self, g: DataGraph, cfg, *,
                 cost_model: Optional[CostModel] = None,
                 n_devices: int = 1, platform: Optional[str] = None):
        self.g = g
        self.cfg = cfg
        self.cost = cost_model or load_calibration()
        self.n_devices = max(1, int(n_devices))
        if platform is None:
            import jax

            platform = jax.devices()[0].platform
        # constants fitted on another platform (the built-in ones are CPU
        # guesses) cannot compare planes here: auto then keeps the default
        # batched plane and only derives result-preserving geometry
        self.priced = self.cost.backend in (None, platform)
        self.block_order = root_block_order(
            g, cfg.match.root_block, getattr(cfg, "root_order", "degree"))
        self.n_blocks = int(self.block_order.shape[0])

    # -- geometry -----------------------------------------------------------
    def derive_match(self, k: int,
                     prev: Optional[Dict[str, Any]]) -> MatchConfig:
        """Per-level `MatchConfig` from observed occupancy.

        ``prev`` is the previous level's per_level telemetry dict
        (``max_count`` / ``overflowed``).  Only result-preserving knobs
        move (see module docstring): ``cap`` shrinks to
        pow2(max(CAP_HEADROOM · max_count, CAP_FLOOR)) when the previous
        level measured small occupancy without overflow, and ``two_phase``
        is dropped for k == 2 (the only prefix edge is certified by the
        anchor gather itself, so phase 2 has nothing to prune — the extra
        compaction is pure overhead).
        """
        base = self.cfg.match
        cap = base.cap
        if prev is not None and not prev.get("overflowed", False):
            peak = int(prev.get("max_count", 0))
            if peak > 0:
                cap = min(base.cap,
                          max(_pow2_ceil(CAP_HEADROOM * peak), CAP_FLOOR))
        two_phase = bool(base.two_phase and k >= 3)
        if cap == base.cap and two_phase == base.two_phase:
            return base
        return dataclasses.replace(base, cap=cap, two_phase=two_phase)

    # -- bucketing ----------------------------------------------------------
    def choose_bucket(self, n_patterns: int) -> int:
        """Pattern-bucket ceiling for one level.

        Monotone in ``n_patterns`` (more candidates never picks a smaller
        bucket — unit-tested) and capped by ``cfg.batch_patterns``, the
        transient-memory ceiling the config already owns.
        """
        if n_patterns <= 1:
            return 1
        return int(min(_pow2_ceil(n_patterns), self.cfg.batch_patterns))

    # -- level costs --------------------------------------------------------
    def _level_costs(self, sizes: List[Tuple[int, int]], match: MatchConfig,
                     max_batch: int) -> Dict[str, float]:
        """Predicted per-block cost of one level under each plane.

        ``sizes`` = (group size, k) pairs of the level (mixed-k levels
        under edge-extension generation contribute one term per group).
        Costs are per root block — the block count multiplies every plane
        equally, so it cancels out of the comparison.
        """
        metric = self.cfg.metric
        seq = bat = 0.0
        for sz, k in sizes:
            seq += sz * self.cost.block_step_s(match, k, 1, batched=False,
                                               metric=metric)
            full, rem = divmod(sz, max_batch)
            for bucket_n in [max_batch] * full + ([rem] if rem else []):
                # _pow2_ceil IS batched._bucket_size — the estimate prices
                # the real padded bucket _mine_group will run
                bat += self.cost.block_step_s(match, k,
                                              _pow2_ceil(bucket_n),
                                              batched=True, metric=metric)
        costs = {"sequential": seq, "batched": bat}
        if self.n_devices > 1:
            # roots shard over the mesh: ndev blocks advance per step, at
            # one extra dispatch-overhead's worth of collective latency
            costs["distributed"] = (bat + self.cost.dispatch_overhead_s
                                    ) / self.n_devices
        return costs

    # -- the decision -------------------------------------------------------
    def plan_level(self, level: int, patterns: Sequence, taus: Sequence[int],
                   prev: Optional[Dict[str, Any]] = None) -> LevelPlan:
        """Plan one level given its candidate set and last level's telemetry.

        Forced execution modes return the config's plane/geometry verbatim.
        ``"auto"`` derives geometry from ``prev`` (see `derive_match`),
        sizes the bucket, and picks the cheapest plane under the cost
        model; ``mis_exact`` always plans sequential (its MIS solve is
        host-side — though its embedding *collection* is batched over
        blocks, see `batched.collect_pattern_embeddings`).  The
        distributed plane is only eligible when the caller pinned a
        mesh-invariant super-block schedule (``cfg.blocks_per_super``) and
        the metric is ``mis_luby`` — without those, auto silently changing
        accounting granularity would break the forced-plane equivalence.
        A cost model not fitted for the running platform prices nothing:
        auto then runs the batched plane.
        """
        cfg = self.cfg
        if cfg.execution == "sampled":
            return self._plan_sampled(level, patterns, taus, prev)
        if cfg.execution != "auto":
            return LevelPlan(plane=cfg.execution, match=cfg.match,
                             max_batch=cfg.batch_patterns)
        if not patterns or cfg.metric == "mis_exact":
            return LevelPlan(plane="sequential",
                             match=self.derive_match(
                                 max((p.k for p in patterns), default=2),
                                 prev),
                             max_batch=cfg.batch_patterns)

        match = self.derive_match(max(p.k for p in patterns), prev)
        # same-k group sizes, mirroring batched.level_groups' slicing
        by_k: Dict[int, int] = {}
        for p in patterns:
            by_k[p.k] = by_k.get(p.k, 0) + 1
        max_batch = self.choose_bucket(max(by_k.values()))
        if not self.priced:
            return LevelPlan(plane="batched", match=match,
                             max_batch=max_batch)
        sizes = sorted(by_k.items())
        costs = self._level_costs([(sz, k) for k, sz in sizes], match,
                                  max_batch)

        plane = "sequential" if costs["sequential"] <= costs["batched"] \
            else "batched"
        if ("distributed" in costs
                and cfg.metric == "mis_luby"
                and cfg.blocks_per_super is not None
                and self.n_blocks >= 2 * self.n_devices
                and costs["distributed"] < costs[plane]):
            plane = "distributed"
        if plane == "batched":
            sample, pricing = self._price_sampled(
                level, taus, prev, match,
                [(sz, k) for k, sz in sizes], max_batch, costs["batched"])
            if pricing is not None and pricing["chosen"] == "sampled":
                return LevelPlan(plane="sampled", match=match,
                                 max_batch=max_batch, sample=sample,
                                 pricing=pricing)
            if pricing is not None:
                return LevelPlan(plane="batched", match=match,
                                 max_batch=max_batch, pricing=pricing)
        return LevelPlan(plane=plane, match=match, max_batch=max_batch)

    # -- auto sampled pricing -----------------------------------------------
    def _predict_escalation(self, prev: Optional[Dict[str, Any]]
                            ) -> Tuple[float, str]:
        """Predicted escalation mass E[esc] for the next level's sample.

        Predictor chain, most-informed first:

          * ``"telemetry"`` — the previous level ran sampled: its measured
            escalated/(escalated+pruned) classification split is the best
            available estimate of how separable supports are from τ;
          * ``"frontier"`` — the previous level's frequent/searched ratio:
            frequent parents spawn candidates whose supports sit near τ
            (they escalate); the infrequent rest prune at the prior's rate;
          * ``"prior"`` — `CostModel.esc_prior()` (the measured per-run
            fraction when calibrated, ESCALATION_PRIOR otherwise).
        """
        prior = self.cost.esc_prior()
        if prev is not None:
            s = prev.get("sampled")
            if s is not None and not s.get("exact", False):
                classified = int(s.get("escalated", 0)) + int(
                    s.get("pruned", 0))
                if classified > 0:
                    return (int(s.get("escalated", 0)) / classified,
                            "telemetry")
            searched = int(prev.get("searched", 0))
            if searched > 0:
                freq = min(1.0, int(prev.get("frequent", 0)) / searched)
                return min(1.0, freq + prior * (1.0 - freq)), "frontier"
        return prior, "prior"

    def _price_sampled(self, level: int, taus: Sequence[int],
                       prev: Optional[Dict[str, Any]], match: MatchConfig,
                       sizes: List[Tuple[int, int]], max_batch: int,
                       batched_s: float
                       ) -> Tuple[Optional[Dict[str, Any]],
                                  Optional[Dict[str, Any]]]:
        """Price a sampled pass for one auto level; returns (sample, pricing).

        (None, None) when the level is ineligible (non-batchable metric,
        escalation disabled, complete run, too few blocks, or τ below the
        hidden-mass bound — where even zero-support patterns escalate).
        Otherwise the pricing dict records every decision input plus
        ``"chosen"``; the sample dict is the recorded draw when sampled won.

        The sampled row prices three phases against the batched row:
        ``f·batched`` (the sample pass), ``E[esc]·(1−f)·batched`` (match
        steps over the unsampled schedule) and ``E[esc]·f·replay``
        (update-only replay of the recorded sample blocks, keeping the
        schedule permutation intact) — sampled wins only under
        `SAMPLED_MARGIN`.
        """
        cfg = self.cfg
        m = self.n_blocks
        from .batched import _BATCHABLE_METRICS
        if (cfg.metric not in _BATCHABLE_METRICS or cfg.complete
                or not getattr(cfg, "escalate", True)
                or m < MIN_SAMPLED_BLOCKS or not taus):
            return None, None
        f = min(1.0, max(1, math.ceil(cfg.sample_fraction * m)) / m)
        if f >= 1.0:
            return None, None
        hidden = hidden_mass_bound(cfg.confidence, f)
        tau_min = int(min(taus))
        esc, esc_source = self._predict_escalation(prev)
        rep = 0.0
        for sz, k in sizes:
            full, r = divmod(sz, max_batch)
            for bucket_n in [max_batch] * full + ([r] if r else []):
                rep += self.cost.replay_step_s(match, k,
                                               _pow2_ceil(bucket_n),
                                               metric=cfg.metric)
        # all terms are per root block (`_level_costs` normalizes — the
        # block count multiplies every row equally): the sample pass runs
        # f of the blocks, escalation matches the unsampled (1−f) and
        # replays the sampled f with the cheap update-only step
        sampled_s = batched_s * f \
            + esc * (batched_s * (1.0 - f) + rep * f)
        pricing = {
            "batched_s": float(batched_s), "sampled_s": float(sampled_s),
            "replay_s": float(rep), "fraction": float(f),
            "esc": float(esc), "esc_source": esc_source,
            "margin": SAMPLED_MARGIN, "tau_min": tau_min,
            "hidden_bound": float(hidden),
        }
        if tau_min <= hidden or sampled_s >= SAMPLED_MARGIN * batched_s:
            pricing["chosen"] = "batched"
            return None, pricing
        sample = self._draw_block_sample(level, prev, match,
                                         cfg.sample_fraction)
        pricing["chosen"] = "sampled"
        return sample, pricing

    # -- sampled plane ------------------------------------------------------
    def _plan_sampled(self, level: int, patterns: Sequence,
                      taus: Sequence[int],
                      prev: Optional[Dict[str, Any]]) -> LevelPlan:
        """Draw (and record) one level's root-block sample.

        Forced geometry — ``execution="sampled"`` is an accuracy/latency
        dial over the *batched* plane, so it keeps the config's match/
        bucket verbatim (like every forced mode) and only decides the
        block draw.  The draw is systematic PPS (Madow) over the level's
        block *schedule*: weights come from the previous level's per-block
        peak-occupancy telemetry (``prev["block_peaks"]``, block-id
        indexed, re-ordered by the schedule) with the degree stat as the
        k = 2 fallback; the single uniform comes from a counter-based
        generator keyed on (``sample_seed``, level), so the draw is a pure
        function of (graph, config, level, telemetry) — which is what lets
        a resume replay it bit-identically from the recorded plan.

        Degenerate cases plan the exact batched plane outright: empty
        levels, ``complete=True`` (every block must run anyway), and
        levels with fewer than `MIN_SAMPLED_BLOCKS` blocks.  A fraction
        that rounds up to full coverage keeps the sampled plane but with a
        unit-probability sample — `evaluate_level_sampled` recognises it
        and degenerates to the exact pass with zero escalations.
        """
        from . import sampled as sampled_lib

        cfg = self.cfg
        match, max_batch = cfg.match, cfg.batch_patterns
        m = self.n_blocks
        if not patterns or cfg.complete or m < MIN_SAMPLED_BLOCKS:
            return LevelPlan(plane="batched", match=match,
                             max_batch=max_batch)

        key = sampled_lib.sample_key(cfg.sample_seed, level)
        n_sample = max(1, math.ceil(cfg.sample_fraction * m))
        # cost-model row for the sample pass: f·batched plus the expected
        # exact re-spend esc_prior·(1−f)·batched.  With the prior < 1 this
        # never exceeds the batched row, but the guard keeps the plane
        # honest should the prior ever be calibrated past 1.
        by_k: Dict[int, int] = {}
        for p in patterns:
            by_k[p.k] = by_k.get(p.k, 0) + 1
        costs = self._level_costs([(sz, k) for k, sz in sorted(by_k.items())],
                                  match, self.choose_bucket(max(by_k.values())))
        f = n_sample / m
        sampled_cost = costs["batched"] * (f + self.cost.esc_prior()
                                           * (1.0 - f))
        if sampled_cost > costs["batched"]:
            return LevelPlan(plane="batched", match=match,
                             max_batch=max_batch)
        if n_sample >= m:
            sample = {"fraction": 1.0, "n_sample": int(m),
                      "n_requested": int(m),
                      "positions": list(range(m)), "pis": [1.0] * m,
                      "key": key, "weights": "full", "w": [1.0] * m}
            return LevelPlan(plane="sampled", match=match,
                             max_batch=max_batch, sample=sample)
        sample = self._draw_block_sample(level, prev, match,
                                         cfg.sample_fraction)
        return LevelPlan(plane="sampled", match=match, max_batch=max_batch,
                         sample=sample)

    def _draw_block_sample(self, level: int, prev: Optional[Dict[str, Any]],
                           match: MatchConfig,
                           fraction: float) -> Dict[str, Any]:
        """One level's recorded systematic-PPS block draw (round 0).

        Weights come from the previous level's per-block peak-occupancy
        telemetry (``prev["block_peaks"]``, block-id indexed, re-ordered by
        the schedule) with the degree stat as the k = 2 fallback, floored
        at 1 so zero-yield blocks keep nonzero inclusion probability (the
        HT estimator needs π > 0 everywhere it might observe mass).  The
        full schedule-ordered weight vector is recorded as ``"w"`` — the
        adaptive rounds (`sampled.evaluate_level_sampled`) redraw from it
        via conditional PPS, so a recorded plan is self-contained.
        """
        from . import sampled as sampled_lib

        cfg = self.cfg
        m = self.n_blocks
        key = sampled_lib.sample_key(cfg.sample_seed, level)
        n_sample = min(m, max(1, math.ceil(fraction * m)))
        peaks = None if prev is None else prev.get("block_peaks")
        if peaks is not None and len(peaks) == m:
            # block-id indexed telemetry → schedule order
            w = np.asarray(peaks, np.float64)[self.block_order]
            weights_src = "occupancy"
        else:
            w = block_degree_stat(
                self.g, match.root_block).astype(np.float64)[self.block_order]
            weights_src = "degree"
        w = np.maximum(w, 1.0)
        u = sampled_lib.sample_uniform(key)
        positions, pis = sampled_lib.systematic_sample(w, n_sample, u)
        return {
            "fraction": float(fraction),
            "n_sample": int(positions.shape[0]),
            "n_requested": int(n_sample),
            "positions": [int(x) for x in positions],
            "pis": [float(x) for x in pis],
            "key": key,
            "weights": weights_src,
            "w": [float(x) for x in w],
        }
