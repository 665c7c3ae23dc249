"""Batched level-wise mining — the default data plane of ``mine()``.

The paper's loop (and our ``execution="sequential"`` oracle) evaluates
candidate patterns one device program at a time; but a mining level holds
tens-to-hundreds of same-size candidates, and ``match_block`` is pure
dataflow over *plan arrays* — so an entire level runs as ONE device program:
plans stack into a leading pattern axis (``plan.stack_plans``), the data
graph broadcasts, ``match_block`` runs under ``vmap``, and the metric state
(mIS bitmaps/counters, MNI image tables, fractional count tables) batches
along the same axis.

Wins: (CPU) dispatch amortization across candidates; (TPU) one big program
with pattern-level parallelism instead of many small ones — and under
shard_map the pattern axis is a free extra parallelism dimension
(``core/distributed.py``).

τ early exit stays *per pattern*: after every root block the host reads the
batched support values, snapshots finished patterns out of the active set,
and — once the active set has halved — re-stacks the survivors into a
smaller power-of-two bucket.  A finished pattern therefore wastes at most
one extra block of masked work (its ``count < τ`` guard freezes all state
updates), repaid many times over by batching; and bucketing bounds
recompilation at log2(P) shapes per (k, geometry).

Per-pattern results are bit-identical to the sequential oracle for the
``mis``, ``mis_luby``, ``mni`` and ``frac`` metrics because each pattern
sees the exact same (block, update) history; ``mis_exact`` (host-side
branch & bound) falls back to the sequential path.  This equivalence is
property-tested in ``tests/core/test_batched_equivalence.py``.

Compiled programs are cached: one executable per (metric, k, match
geometry) python callable (``_step_fn`` below), with XLA's jit cache keying
the remaining shape axes (pattern-bucket size P, graph size).  Levels and
whole mining runs reuse executables instead of re-tracing.

Expansion planes compose transparently: with
``MatchConfig.expansion == "pallas"`` the vmapped ``match_block`` lowers
its fused level kernel with the pattern axis as a leading *grid*
dimension (JAX's Pallas batching rule), so a batched level is still one
kernel launch per expansion level — not P re-entries.  Results stay
bit-identical across (execution plane × expansion plane); see
``docs/architecture.md``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import DataGraph, DeviceGraph
from .pattern import Pattern
from .plan import PatternPlan, make_plan, put_plan, stack_plans
from .matcher import (MatchConfig, batch_checks, match_block,
                      match_block_lanes, transient_match_bytes)
from . import tracing
from . import mis as mis_lib
from . import metrics as metrics_lib

__all__ = [
    "BatchedResult", "GroupState", "LevelTelemetry", "PatternOutcome",
    "batched_mis_supports", "collect_pattern_embeddings",
    "evaluate_level_batched", "level_groups",
    "program_cache_stats", "clear_program_cache", "stack_plans",
]

_BATCHABLE_METRICS = ("mis", "mis_luby", "mni", "frac")
# metrics whose sequential loop early-exits on support >= tau
_EARLY_EXIT_METRICS = ("mis", "mis_luby", "mni")

_INT32_MAX = np.iinfo(np.int32).max

# default ceiling on the pattern axis: transient match memory is
# O(P · cap · chunk), so an unbounded level (hundreds of candidates) would
# multiply device footprint by hundreds; 64 keeps the dispatch win while
# bounding memory and the set of compiled bucket shapes.
DEFAULT_MAX_BATCH = 64

# blocks stacked per dispatch by the mis_exact embedding collector — also
# the transient-memory multiplier `flexis._device_bytes` accounts for it
MIS_EXACT_BLOCKS_PER_DISPATCH = 8


# ---------------------------------------------------------------------------
# compiled-program cache: one traced step per (metric, k, match geometry)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _step_fn(metric: str, k: int, cfg: MatchConfig, unbatched: bool = False,
             capture: bool = False):
    """Jitted batched block step for one (metric, k, match geometry).

    Signature of the returned callable:
        step(dev_g, plans, block_start, state, taus)
            -> (state', values, found, overflowed, peaks, lanes)
    With ``capture=True`` two more outputs are appended — ``emb`` (P, cap,
    k) int32 and ``n_valid`` (P,) int32, `match_block`'s raw embedding
    table — which the sampled plane records per (pattern, block) so exact
    escalation can *replay* the block instead of re-matching it.

    Shapes/dtypes (P = padded pattern-bucket size, n = graph vertices):
      dev_g:   DeviceGraph pytree (unbatched; broadcasts over P).
      plans:   PatternPlan pytree with a leading P axis on every array
               field (`stack_plans`).
      block_start: () int32 — shared root-block offset.
      state:   metric state, leading P axis —
               mis/mis_luby: ((P, ⌈n/32⌉) uint32 bitmaps, (P,) int32 counts)
               mni: (P, k, n) bool image tables
               frac: (P, k, n) float32 count tables.
      taus:    (P,) int32 device-side freeze guard (mis/mis_luby only).
      values:  (P,) running support — int32 counts/minima, float32 mass.
      found:   (P,) int32 embeddings enumerated this block;
      overflowed: (P,) bool frontier-capacity flags.
      peaks:   (P,) int32 max frontier occupancy inside the block
               (`match_block`'s peak — the planner's cap-sizing signal).
      lanes:   (P, 2) int32 expansion lanes walked and lanes that passed
               every filter (`match_block_lanes`; zeros on the Pallas
               plane) — the ``lanes_*`` counters of `core/tracing.py`.

    ``unbatched=True`` compiles the P == 1 bucket *without* the vmap: the
    math is identical (size-1 batch), but XLA fuses the unbatched op chain
    where the degenerate batch dimensions of the vmapped program block
    cross-op fusion on wide ``cap·chunk`` grids — measured ~1.1–1.3×
    on single-pattern compute-bound levels (docs/architecture.md "Why the
    vmapped matcher loses fusion").  Results are bit-identical.
    """

    if metric in ("mis", "mis_luby"):

        def step_one(g, plan, block_start, bm, cnt, tau, checks=None):
            emb, n_valid, found, ovf, peak, lanes = match_block_lanes(
                g, plan, block_start, cfg, checks)
            if metric == "mis":
                bm, cnt = mis_lib.mis_greedy_update(
                    bm, cnt, emb, n_valid, tau, k)
            else:
                bm, cnt = mis_lib.mis_luby_update(
                    bm, cnt, emb, n_valid, tau, k, g.n)
            if capture:
                return bm, cnt, found, ovf, peak, lanes, emb, n_valid
            return bm, cnt, found, ovf, peak, lanes

        def step(g, plans, block_start, state, taus):
            bitmaps, counts = state
            if unbatched:
                squeeze = jax.tree_util.tree_map(lambda a: a[0], plans)
                out = step_one(
                    g, squeeze, block_start, bitmaps[0], counts[0], taus[0])
                bm, cnt = out[0], out[1]
                rest = tuple(x[None] for x in out[2:])
                return ((bm[None], cnt[None]), cnt[None]) + rest
            checks = batch_checks(plans)
            out = jax.vmap(
                lambda plan, bm, cnt, tau: step_one(
                    g, plan, block_start, bm, cnt, tau, checks))(
                plans, bitmaps, counts, taus)
            bitmaps, counts = out[0], out[1]
            return ((bitmaps, counts), counts) + tuple(out[2:])

    elif metric in ("mni", "frac"):

        def step_one(g, plan, block_start, table, checks=None):
            emb, n_valid, found, ovf, peak, lanes = match_block_lanes(
                g, plan, block_start, cfg, checks)
            if metric == "mni":
                table = metrics_lib.mni_update(table, emb, n_valid, k)
                value = metrics_lib.mni_value(table)
            else:
                table = metrics_lib.frac_update(table, emb, n_valid, k)
                value = metrics_lib.frac_value(table)
            if capture:
                return table, value, found, ovf, peak, lanes, emb, n_valid
            return table, value, found, ovf, peak, lanes

        def step(g, plans, block_start, state, taus):
            del taus  # MNI/frac need no device-side τ; the host owns early exit
            if unbatched:
                squeeze = jax.tree_util.tree_map(lambda a: a[0], plans)
                out = step_one(g, squeeze, block_start, state[0])
                return tuple(x[None] for x in out)
            checks = batch_checks(plans)
            out = jax.vmap(
                lambda plan, table: step_one(g, plan, block_start, table,
                                             checks))(
                plans, state)
            return out

    else:
        raise ValueError(f"metric {metric!r} has no batched step")

    return jax.jit(step)


@functools.lru_cache(maxsize=None)
def _replay_step_fn(metric: str, k: int, n: int):
    """Jitted update-only block step — escalation's replay of a recorded
    sample block.

    Signature: ``step(state, emb, n_valid, taus) -> (state', values)`` with
    ``emb`` (P, cap, k) int32 / ``n_valid`` (P,) int32 being a recorded
    `match_block` output.  Applies exactly the metric update the full step
    would have applied — same embedding rows, same order, same device-side
    τ guard — without re-running the expansion grid, so a replayed block's
    metric state transition is bit-identical to the matched one.
    """

    if metric in ("mis", "mis_luby"):

        def step_one(emb, n_valid, bm, cnt, tau):
            if metric == "mis":
                return mis_lib.mis_greedy_update(bm, cnt, emb, n_valid,
                                                 tau, k)
            return mis_lib.mis_luby_update(bm, cnt, emb, n_valid, tau, k, n)

        def step(state, emb, n_valid, taus):
            bitmaps, counts = jax.vmap(step_one)(emb, n_valid, *state, taus)
            return (bitmaps, counts), counts

    elif metric in ("mni", "frac"):

        def step_one(emb, n_valid, table):
            if metric == "mni":
                table = metrics_lib.mni_update(table, emb, n_valid, k)
                return table, metrics_lib.mni_value(table)
            table = metrics_lib.frac_update(table, emb, n_valid, k)
            return table, metrics_lib.frac_value(table)

        def step(state, emb, n_valid, taus):
            del taus
            return jax.vmap(step_one)(emb, n_valid, state)

    else:
        raise ValueError(f"metric {metric!r} has no replay step")

    return jax.jit(step)


def _replay_arrays(replay, bucket_map: np.ndarray, b: int, cap: int, k: int):
    """Assemble one replayed block's device inputs + host accounting.

    ``replay`` is the group's per-pattern replay table (group index →
    {schedule position → {"emb", "found", "ovf", "peak"}}).  Pad rows
    (bucket_map == −1) get empty embeddings — their τ guard is 0 and their
    accounting rows are dead, exactly like pad rows of a matched step.
    """
    P = int(bucket_map.size)
    emb = np.full((P, cap, k), -1, np.int32)
    nv = np.zeros(P, np.int32)
    found = np.zeros(P, np.int32)
    ovf = np.zeros(P, bool)
    peak = np.zeros(P, np.int32)
    for row in range(P):
        gi = int(bucket_map[row])
        if gi < 0:
            continue
        rec = replay[gi][b]
        rows = np.asarray(rec["emb"], np.int32).reshape(-1, k)
        c = int(rows.shape[0])
        if c:
            emb[row, :c] = rows
        nv[row] = c
        found[row] = int(rec["found"])
        ovf[row] = bool(rec["ovf"])
        peak[row] = int(rec["peak"])
    return emb, nv, found, ovf, peak


def program_cache_stats():
    """lru_cache stats of the batched step-program cache (hits = executable
    reuse across levels/runs; misses = distinct (metric, k, geometry))."""
    return _step_fn.cache_info()


def clear_program_cache() -> None:
    _step_fn.cache_clear()


# ---------------------------------------------------------------------------
# batched metric state
# ---------------------------------------------------------------------------

def _state_init(metric: str, P: int, k: int, n: int):
    """Zeroed metric state with a leading P pattern axis (see `_step_fn`)."""
    if metric in ("mis", "mis_luby"):
        return (jnp.zeros((P, mis_lib.bitmap_words(n)), jnp.uint32),
                jnp.zeros((P,), jnp.int32))
    if metric == "mni":
        return jnp.zeros((P, k, n), jnp.bool_)
    if metric == "frac":
        return jnp.zeros((P, k, n), jnp.float32)
    raise ValueError(metric)


def _state_bytes(metric: str, k: int, n: int) -> int:
    """Per-pattern metric-state footprint (telemetry)."""
    if metric in ("mis", "mis_luby"):
        return mis_lib.bitmap_words(n) * 4 + 4 + (n * 4 if metric == "mis_luby" else 0)
    if metric == "mni":
        return k * n
    if metric == "frac":
        return k * n * 4
    return 0


def _gather_rows(tree, sel: np.ndarray):
    idx = jnp.asarray(sel, jnp.int32)
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


def _bucket_size(n_active: int) -> int:
    return max(1, 1 << max(0, math.ceil(math.log2(max(n_active, 1)))))


# ---------------------------------------------------------------------------
# level executor
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PatternOutcome:
    """Per-pattern result of a batched level — mirrors the sequential
    ``evaluate_pattern`` outputs field-for-field."""
    support: int
    frequent: bool
    embeddings_found: int
    overflowed: bool
    blocks_run: int
    # max frontier occupancy observed over the blocks this pattern ran
    # (post-clip, ≤ cap) — the planner's per-level cap-sizing input
    max_count: int = 0
    # sampled plane only: True when `support` is a Horvitz–Thompson
    # estimate (clamped below τ) rather than an exact count — every exact
    # plane, and every escalated pattern, reports False
    estimated: bool = False


@dataclasses.dataclass
class BatchedResult:
    """Level result arrays aligned with the input pattern list (length P₀ =
    number of requested patterns, NOT the padded device bucket size)."""

    supports: np.ndarray          # (P₀,) int64 metric supports (≥ tau ⇒ frequent)
    found: np.ndarray             # (P₀,) int64 embeddings enumerated
    overflowed: np.ndarray        # (P₀,) bool


@dataclasses.dataclass
class LevelTelemetry:
    """Aggregate accounting of one level-executor call."""

    state_bytes: int = 0          # peak transient device state (pattern axis)
    dispatches: int = 0           # device program invocations
    max_count: int = 0            # peak frontier occupancy across patterns
    overflowed: bool = False      # any pattern hit the frontier cap
    # per-root-block peak frontier occupancy, indexed by block id (int64,
    # length ⌈n/root_block⌉) — the sampled plane's occupancy weights for
    # the next level's block draw (`core/sampled.py`)
    block_peaks: Optional[np.ndarray] = None
    # within-level replans: how many times `_mine_group` re-derived its cap
    # geometry at a shrink boundary (auto plane only; see ``replan``)
    replans: int = 0
    # sampled-plane summary (fraction, escalations, CI widths); None on
    # the other planes — `mine()` records it as per_level["sampled"]
    sampled: Optional[dict] = None


@dataclasses.dataclass
class GroupState:
    """Carried state of one in-flight same-k group, snapshotted per block.

    This is the batched plane's resume unit: everything `_mine_group` needs
    to continue from root block ``next_block`` — the (possibly re-stacked)
    active-set ``bucket_map``, the device metric state for the current
    bucket (kept as device arrays here; the session serializes them to
    logical host arrays only when it actually persists a snapshot), and the
    per-pattern host accumulators for the whole group (P₀-aligned).
    """

    next_block: int               # next schedule position (block-order index)
    bucket_map: np.ndarray        # (P_pad,) int — group index per row, -1 pad
    state: object                 # device metric state, leading P_pad axis
    supports: np.ndarray          # (P₀,) int64
    found: np.ndarray             # (P₀,) int64
    overflowed: np.ndarray        # (P₀,) bool
    blocks_run: np.ndarray        # (P₀,) int64
    dispatches: int = 0
    max_count: Optional[np.ndarray] = None   # (P₀,) int64 peak occupancy
    # per-block peak occupancy by block id (see LevelTelemetry.block_peaks);
    # carried so a resumed group reports identical occupancy telemetry
    block_peaks: Optional[np.ndarray] = None
    # within-level replanning (auto plane): the group's *current* frontier
    # cap and how many times it was re-derived — carried so a resumed
    # group continues with the identical (possibly shrunk) geometry
    cap: Optional[int] = None
    replans: int = 0


def level_groups(patterns: Sequence[Pattern], max_batch: int):
    """Deterministic (k, slice-offset, indices) schedule of a level.

    Shared by the batched and distributed level executors — and by the
    session runtime, whose mid-level cursor is the (k, lo) pair — so a
    resumed level re-derives the exact same grouping.
    """
    groups: dict = {}
    for i, p in enumerate(patterns):
        groups.setdefault(p.k, []).append(i)
    for k in sorted(groups):
        for lo in range(0, len(groups[k]), max_batch):
            yield k, lo, groups[k][lo:lo + max_batch]


def _mine_group(
    dev_g: DeviceGraph,
    plans: List[PatternPlan],
    taus: Sequence[int],
    metric: str,
    cfg: MatchConfig,
    *,
    complete: bool,
    n: int,
    deadline: Optional[float] = None,
    resume: Optional[GroupState] = None,
    on_block=None,
    block_order: Optional[np.ndarray] = None,
    replay: Optional[List[dict]] = None,
    emb_sink=None,
    replan: bool = False,
) -> Tuple[List[Optional[PatternOutcome]], bool, int, np.ndarray, int]:
    """Run one same-k candidate group level-wise; returns
    (outcomes, timed_out, dispatches, block_peaks, replans).

    ``replay`` (escalation reuse): per-pattern tables {schedule position →
    {"emb", "found", "ovf", "peak"}} recorded by the sample pass.  At a
    schedule position every live pattern has a record for, the loop applies
    the recorded embeddings through `_replay_step_fn` — the identical
    metric update, minus the expansion grid — instead of re-matching the
    block.  ``emb_sink(b, emb, n_valid, found, ovf, peak, bucket_map)`` is
    the recording side: when set, steps run in capture mode and the raw
    `match_block` outputs stream to the callback per block.

    ``replan=True`` (auto plane only) re-derives the frontier cap at
    shrink-re-stack boundaries: when the live survivors' observed peak
    occupancy fits a smaller cap with `planner.CAP_HEADROOM`× headroom
    (never below `planner.CAP_FLOOR`, and never once any live pattern has
    overflowed), the remaining blocks run at the shrunk geometry.  The
    current cap and replan count ride in `GroupState` so resumes continue
    bit-identically; `flexis.mine` re-checks overflow against the full
    config cap, so a replan that shrinks too far only costs an escalation.

    Each block is a ``flexis.block`` span (`core/tracing.py`) holding its
    ``dispatch``, ``pull``, ``account``, ``restack`` and ``hooks`` spans;
    the group's set-up is a ``plan_build`` span, in which the group's
    host-stacked plan goes to the device in one put.  The query's counters
    gain ``plan_puts``, ``match_blocks``/``replay_blocks``, ``host_pulls``,
    ``restacks`` and, on the XLA expansion,
    ``lanes_processed``/``lanes_useful`` of the live rows.

    ``block_order`` is the static root-block schedule — a permutation of
    block ids from `planner.root_block_order` (None = vertex-id order), or
    a *subset* of one: the sampled plane (`core/sampled.py`) passes only
    its drawn blocks, and the loop runs exactly the schedule it is given.
    The loop cursor — including `GroupState.next_block` — indexes into
    the *schedule*, so a resumed run walks the identical permutation.
    ``block_peaks`` maps block id → peak frontier occupancy over the
    group's still-live patterns at that block (0 for blocks not run).

    Per-pattern histories reproduce the sequential loop exactly: a pattern
    accumulates (found, overflowed, blocks) for precisely the block prefix the
    sequential loop would have run, and its support is snapshotted at the
    block where it crosses τ (or at the end, for complete runs).

    On a timeout, only patterns that *finished* (reached τ, or ran every
    block) get an outcome; still-in-flight patterns return ``None`` — the
    sequential loop's all-or-nothing timeout contract, where a pattern is
    either fully evaluated or not reported at all.

    ``resume`` continues a previously snapshotted `GroupState` (its plans
    bucket is re-derived from ``plans`` + the saved active-set map — pad
    rows may rebind to a different plan, which is unobservable: their τ
    guard is 0 and their accounting rows are dead); ``on_block`` is called
    with the carried `GroupState` after every block that leaves the group
    still in flight.  Continuation is bit-identical: the per-pattern
    (block, update) history of a resumed run equals the uninterrupted one.
    """
    P0 = len(plans)
    k = plans[0].k
    early_exit = (not complete) and metric in _EARLY_EXIT_METRICS

    taus_np = np.asarray(taus, np.int64)
    # device-side τ guard: freeze mis counters at τ unless complete
    dev_tau_full = np.full(P0, _INT32_MAX if complete else 0, np.int32)
    if not complete:
        dev_tau_full[:] = np.minimum(taus_np, _INT32_MAX)

    def bucket_taus(bucket_map: np.ndarray) -> jnp.ndarray:
        safe = np.where(bucket_map >= 0, bucket_map, 0)
        return jnp.asarray(
            np.where(bucket_map >= 0, dev_tau_full[safe], 0), jnp.int32)

    total_blocks = -(-n // cfg.root_block)
    if resume is None:
        supports = np.zeros(P0, np.int64)
        found = np.zeros(P0, np.int64)
        ovf = np.zeros(P0, bool)
        blocks_run = np.zeros(P0, np.int64)
        max_count = np.zeros(P0, np.int64)
        block_peaks = np.zeros(total_blocks, np.int64)
        # current bucket: stacked plans + state + map to group idx (-1 = pad)
        P_pad = _bucket_size(P0)
        bucket_map = np.concatenate([np.arange(P0), np.full(P_pad - P0, -1)])
        start_block = 0
        dispatches = 0
    else:
        supports = resume.supports.astype(np.int64).copy()
        found = resume.found.astype(np.int64).copy()
        ovf = resume.overflowed.astype(bool).copy()
        blocks_run = resume.blocks_run.astype(np.int64).copy()
        max_count = (np.zeros(P0, np.int64) if resume.max_count is None
                     else resume.max_count.astype(np.int64).copy())
        block_peaks = (np.zeros(total_blocks, np.int64)
                       if resume.block_peaks is None
                       else resume.block_peaks.astype(np.int64).copy())
        bucket_map = np.asarray(resume.bucket_map, np.int64).copy()
        start_block = int(resume.next_block)
        dispatches = int(resume.dispatches)
    replans = 0 if resume is None else int(getattr(resume, "replans", 0))
    if resume is not None and resume.cap is not None \
            and int(resume.cap) != cfg.cap:
        # continue at the geometry the killed process had replanned to
        cfg = dataclasses.replace(cfg, cap=int(resume.cap))
    with tracing.span("plan_build", k=k, patterns=P0):
        state = (_state_init(metric, bucket_map.size, k, n) if resume is None
                 else jax.tree_util.tree_map(jnp.asarray, resume.state))
        # the bucket's rows are picked on the host; the group's plan then
        # goes to the device in one put
        rows = np.where(bucket_map >= 0, bucket_map, 0)
        plans_cur = put_plan(jax.tree_util.tree_map(lambda a: a[rows],
                                                    stack_plans(plans)))
        taus_dev = bucket_taus(bucket_map)

    timed_out = False
    unfinished: set = set()
    if block_order is None:
        block_order = np.arange(total_blocks, dtype=np.int64)
    # the schedule may be a subset (sampled plane): the loop length is the
    # schedule's, not the graph's
    n_blocks = int(block_order.shape[0])
    # positions every live pattern can replay (escalation reuse) — the
    # sample pass drew level-wide, so escalated patterns share one set
    replay_at = (set(replay[0].keys()) if replay else set())
    rstep = _replay_step_fn(metric, k, n) if replay_at else None
    # the P=1 bucket compiles without the vmap (fusion win, bit-identical);
    # re-resolved only when a shrink re-stack changes the bucket width
    capture = emb_sink is not None
    # the Pallas kernel does not count its lanes
    count_lanes = cfg.expansion == "xla"
    step = _step_fn(metric, k, cfg, unbatched=bucket_map.size == 1,
                    capture=capture)
    for b in range(start_block, n_blocks):
        if deadline is not None and time.monotonic() > deadline:
            timed_out = True
            unfinished = {int(i) for i in bucket_map[bucket_map >= 0]}
            break
        with tracing.span("block", block=b, bucket=int(bucket_map.size)):
            lanes_np = None
            if b in replay_at:
                emb_np, nv_np, found_np, ovf_np, peak_np = _replay_arrays(
                    replay, bucket_map, b, cfg.cap, k)
                with tracing.span("dispatch"):
                    state, values = rstep(
                        state, jnp.asarray(emb_np), jnp.asarray(nv_np),
                        taus_dev)
                with tracing.span("pull"):
                    values_np = np.asarray(values)
                tracing.count("host_pulls")
                tracing.count("replay_blocks")
            else:
                with tracing.span("dispatch"):
                    out = step(
                        dev_g, plans_cur,
                        jnp.int32(int(block_order[b]) * cfg.root_block),
                        state, taus_dev)
                state, values, blk_found, blk_ovf, blk_peak = out[:5]
                with tracing.span("pull"):
                    values_np = np.asarray(values)
                    found_np = np.asarray(blk_found)
                    ovf_np = np.asarray(blk_ovf)
                    peak_np = np.asarray(blk_peak)
                    if count_lanes:
                        lanes_np = np.asarray(out[5])
                tracing.count("host_pulls", 5 if count_lanes else 4)
                tracing.count("match_blocks")
                if capture:
                    with tracing.span("hooks"):
                        emb_sink(b, np.asarray(out[6]), np.asarray(out[7]),
                                 found_np, ovf_np, peak_np, bucket_map)
                    tracing.count("host_pulls", 2)
            dispatches += 1

            with tracing.span("account"):
                live = bucket_map >= 0
                gi = bucket_map[live]
                if lanes_np is not None:
                    walked, useful = lanes_np[live].sum(axis=0).tolist()
                    tracing.count("lanes_processed", walked)
                    tracing.count("lanes_useful", useful)
                found[gi] += found_np[live].astype(np.int64)
                ovf[gi] |= ovf_np[live]
                blocks_run[gi] += 1
                max_count[gi] = np.maximum(max_count[gi],
                                           peak_np[live].astype(np.int64))
                bid = int(block_order[b])
                block_peaks[bid] = max(block_peaks[bid],
                                       int(peak_np[live].max(initial=0)))
                if metric == "frac":
                    supports[gi] = np.floor(values_np[live].astype(np.float64)).astype(np.int64)
                else:
                    supports[gi] = values_np[live].astype(np.int64)
                if early_exit:
                    still = gi[supports[gi] < taus_np[gi]]

            if early_exit:
                if still.size == 0:
                    break
                if still.size <= bucket_map.size // 2 and b + 1 < n_blocks:
                    with tracing.span("restack", survivors=int(still.size)):
                        # shrink: re-stack survivors into the next power-of-two bucket
                        pos_of = {g_idx: i for i, g_idx in enumerate(bucket_map)}
                        pos = np.array([pos_of[g_idx] for g_idx in still])
                        pad = _bucket_size(still.size) - still.size
                        sel = np.concatenate([pos, np.full(pad, pos[0])]).astype(np.int64)
                        plans_cur = _gather_rows(plans_cur, sel)
                        state = _gather_rows(state, sel)
                        bucket_map = np.concatenate([still, np.full(pad, -1)])
                        taus_dev = bucket_taus(bucket_map)
                        if replan and not ovf[still].any():
                            # within-level replanning: the survivors' measured peak
                            # may fit a much smaller frontier cap — re-derive it
                            # with the planner's headroom/floor rails (never once a
                            # live pattern has overflowed: truncation is the only
                            # cap-dependent behaviour and it must stay flagged)
                            from .planner import CAP_FLOOR, CAP_HEADROOM
                            live_peak = int(max_count[still].max())
                            if live_peak > 0:
                                new_cap = min(cfg.cap,
                                              max(_bucket_size(CAP_HEADROOM
                                                               * live_peak),
                                                  CAP_FLOOR))
                                if new_cap < cfg.cap:
                                    cfg = dataclasses.replace(cfg, cap=new_cap)
                                    replans += 1
                        step = _step_fn(metric, k, cfg,
                                        unbatched=bucket_map.size == 1,
                                        capture=capture)
                    tracing.count("restacks")
                elif still.size < gi.size:
                    # same bucket; just stop accounting for the finished patterns
                    bucket_map = np.where(np.isin(bucket_map, still), bucket_map, -1)

            if on_block is not None and b + 1 < n_blocks:
                with tracing.span("hooks"):
                    on_block(GroupState(
                        next_block=b + 1, bucket_map=bucket_map.copy(), state=state,
                        supports=supports.copy(), found=found.copy(),
                        overflowed=ovf.copy(), blocks_run=blocks_run.copy(),
                        dispatches=dispatches, max_count=max_count.copy(),
                        block_peaks=block_peaks.copy(), cap=int(cfg.cap),
                        replans=replans))

    outcomes: List[Optional[PatternOutcome]] = [
        None if i in unfinished else PatternOutcome(
            support=int(supports[i]),
            frequent=bool(supports[i] >= taus_np[i]),
            embeddings_found=int(found[i]),
            overflowed=bool(ovf[i]),
            blocks_run=int(blocks_run[i]),
            max_count=int(max_count[i]),
        )
        for i in range(P0)
    ]
    return outcomes, timed_out, dispatches, block_peaks, replans


def evaluate_level_batched(
    host_g: DataGraph,
    dev_g: DeviceGraph,
    patterns: Sequence[Pattern],
    taus: Sequence[int],
    metric: str,
    cfg: MatchConfig,
    *,
    complete: bool = False,
    deadline: Optional[float] = None,
    max_batch: int = DEFAULT_MAX_BATCH,
    hooks=None,
    block_order: Optional[np.ndarray] = None,
    replay: Optional[List[dict]] = None,
    replan: bool = False,
) -> Tuple[List[Optional[PatternOutcome]], bool, LevelTelemetry]:
    """Evaluate a whole candidate level with the batched data plane.

    ``replay``/``replan`` thread through to `_mine_group` (escalation
    reuse, within-level replanning — see its docstring); ``replay`` aligns
    with ``patterns`` and is sliced per group.

    Args:
      host_g/dev_g: the data graph and its device mirror.
      patterns: sequence of `Pattern` (sizes may mix — edge-extension
        generation); taus: same-length int thresholds.
      metric: one of ``("mis", "mis_luby", "mni", "frac")``.
      cfg: `MatchConfig` — both its execution geometry and its
        ``expansion`` plane apply to every pattern of the level.
      complete: disable τ early exit (exact metric values).
      deadline: ``time.monotonic()`` cutoff; max_batch: pattern-axis cap.
      hooks: optional level-hooks object (the session runtime's resume
        surface; see `repro.runtime.session`).  Duck-typed methods —
        ``resume_outcomes()``: {pattern index → `PatternOutcome`} already
        computed by a previous process (a group is skipped iff every one of
        its indices is present); ``resume_dispatches()``: device dispatches
        already spent on the skipped groups (keeps level telemetry
        identical across a resume); ``resume_block_peaks()`` (optional):
        the per-block occupancy peaks those groups recorded, or None;
        ``group_resume(k, lo)``: the in-flight `GroupState` for one group,
        or None; ``on_group_state(k, lo, group_state)``: called after every
        block of an unfinished group; ``on_group_done(k, lo, idxs,
        outcomes, dispatches, block_peaks=None)``: called when a group
        completes.

    Candidates are grouped by k — and each group split into ≤ ``max_batch``
    slices to bound transient device memory (peak transient is
    ``bucket_size(P) · (state + transient_match_bytes)``) — with each slice
    running as one vmapped program.  Returns (outcomes aligned with the
    input — ``None`` for candidates not reached before a timeout —,
    timed_out, `LevelTelemetry`).
    """
    assert len(patterns) == len(taus)
    assert metric in _BATCHABLE_METRICS, metric
    assert max_batch >= 1
    outcomes: List[Optional[PatternOutcome]] = [None] * len(patterns)
    prefilled = hooks.resume_outcomes() if hooks is not None else None

    timed_out = False
    telemetry = LevelTelemetry()
    peaks = np.zeros(-(-host_g.n // cfg.root_block), np.int64)
    if hooks is not None:
        telemetry.dispatches = int(hooks.resume_dispatches())
        rbp = getattr(hooks, "resume_block_peaks", None)
        done_peaks = rbp() if rbp is not None else None
        if done_peaks is not None:
            peaks = np.maximum(peaks, np.asarray(done_peaks, np.int64))
        rr = getattr(hooks, "resume_replans", None)
        if rr is not None:
            telemetry.replans = int(rr())
    for k, lo, idxs in level_groups(patterns, max_batch):
        # state_bytes is pure arithmetic — account skipped groups too, so a
        # resumed level reports the same peak as the uninterrupted one
        telemetry.state_bytes = max(
            telemetry.state_bytes,
            _bucket_size(len(idxs))
            * (_state_bytes(metric, k, host_g.n)
               + transient_match_bytes(cfg, k)))
        if prefilled is not None and all(i in prefilled for i in idxs):
            for i in idxs:
                outcomes[i] = prefilled[i]
            continue
        with tracing.span("plan_build", k=k, patterns=len(idxs)):
            plans = [make_plan(patterns[i], host_g) for i in idxs]
        group_taus = [taus[i] for i in idxs]
        resume = hooks.group_resume(k, lo) if hooks is not None else None
        on_block = (functools.partial(hooks.on_group_state, k, lo)
                    if hooks is not None else None)
        group_replay = None if replay is None else [replay[i] for i in idxs]
        got, group_timed_out, dispatches, group_peaks, group_replans = \
            _mine_group(
                dev_g, plans, group_taus, metric, cfg,
                complete=complete, n=host_g.n, deadline=deadline,
                resume=resume, on_block=on_block, block_order=block_order,
                replay=group_replay, replan=replan)
        telemetry.dispatches += dispatches
        telemetry.replans += group_replans
        peaks = np.maximum(peaks, group_peaks)
        for i, out in zip(idxs, got):
            outcomes[i] = out
        if hooks is not None and not group_timed_out:
            with tracing.span("hooks"):
                hooks.on_group_done(k, lo, idxs, got, dispatches,
                                    block_peaks=[int(x) for x in group_peaks],
                                    replans=group_replans)
        if group_timed_out:
            timed_out = True
            break
    assert timed_out or all(o is not None for o in outcomes)
    telemetry.block_peaks = peaks
    for o in outcomes:
        if o is not None:
            telemetry.max_count = max(telemetry.max_count, o.max_count)
            telemetry.overflowed |= o.overflowed
    return outcomes, timed_out, telemetry


# ---------------------------------------------------------------------------
# batched embedding collection (mis_exact's device half)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _collect_fn(k: int, cfg: MatchConfig):
    """Jitted embedding collector: `match_block` vmapped over a *blocks*
    axis — (B,) block starts in, ((B, cap, k) emb, (B,) count/found/ovf/peak)
    out.  One program per (k, geometry, B); B is bucketed by the caller."""

    def collect(g, plan, starts):
        return jax.vmap(lambda s: match_block(g, plan, s, cfg))(starts)

    return jax.jit(collect)


def collect_pattern_embeddings(
    dev_g: DeviceGraph,
    plan: PatternPlan,
    cfg: MatchConfig,
    n: int,
    *,
    block_order: Optional[np.ndarray] = None,
    blocks_per_dispatch: int = MIS_EXACT_BLOCKS_PER_DISPATCH,
) -> Tuple[np.ndarray, int, bool, int, int, int]:
    """Enumerate EVERY block's embeddings for one pattern, batched on device.

    The device half of ``mis_exact``: instead of one dispatch per root
    block (the pre-planner sequential loop), blocks stack on a vmapped
    leading axis — ``blocks_per_dispatch`` per program — and only the
    branch-and-bound MIS solve stays on host.  Tail dispatches pad with
    ``block_start = n`` (matches no roots), so results are independent of
    the dispatch width.

    Returns (embeddings (m, k) int32 in schedule order, found, overflowed,
    blocks_run, max_count, dispatches) — field-for-field what the
    per-block sequential loop accumulated, because each block's
    (emb, count) is unchanged and exact MIS is invariant to embedding
    order anyway.
    """
    assert blocks_per_dispatch >= 1
    n_blocks = -(-n // cfg.root_block)
    if block_order is None:
        block_order = np.arange(n_blocks, dtype=np.int64)
    assert block_order.shape[0] == n_blocks
    collect = _collect_fn(plan.k, cfg)

    chunks: List[np.ndarray] = []
    found_total = 0
    overflowed = False
    max_count = 0
    dispatches = 0
    for lo in range(0, n_blocks, blocks_per_dispatch):
        ids = block_order[lo: lo + blocks_per_dispatch]
        pad = blocks_per_dispatch - ids.shape[0]
        starts = np.concatenate(
            [ids * cfg.root_block, np.full(pad, n, np.int64)])
        emb, count, found, ovf, peak = collect(
            dev_g, plan, jnp.asarray(starts, jnp.int32))
        dispatches += 1
        counts = np.asarray(count)
        valid = ids.shape[0]
        found_total += int(np.asarray(found)[:valid].sum())
        overflowed |= bool(np.asarray(ovf)[:valid].any())
        max_count = max(max_count, int(np.asarray(peak)[:valid].max()))
        emb_np = None
        for j in range(valid):
            c = int(counts[j])
            if c:
                if emb_np is None:
                    emb_np = np.asarray(emb)
                chunks.append(emb_np[j, :c])
    embs = (np.concatenate(chunks, axis=0) if chunks
            else np.zeros((0, plan.k), np.int32))
    return embs, found_total, overflowed, n_blocks, max_count, dispatches


# ---------------------------------------------------------------------------
# legacy convenience API (kept for callers/tests of the original sketch)
# ---------------------------------------------------------------------------

def batched_mis_supports(
    host_g: DataGraph,
    patterns: Sequence[Pattern],
    taus: Sequence[int],
    cfg: MatchConfig,
    *,
    complete: bool = False,
) -> BatchedResult:
    """mIS supports for a whole same-k candidate level in batched steps.

    patterns/taus: same-length sequences; returns a `BatchedResult` whose
    arrays align with the input order (see the class docstring).  Runs the
    full level to completion unless per-pattern τ early exit applies.
    """
    assert len(patterns) == len(taus) and len(patterns) > 0
    dev_g = DeviceGraph.from_host(host_g)
    outcomes, _, _ = evaluate_level_batched(
        host_g, dev_g, patterns, taus, "mis", cfg, complete=complete)
    return BatchedResult(
        supports=np.asarray([o.support for o in outcomes], np.int64),
        found=np.asarray([o.embeddings_found for o in outcomes], np.int64),
        overflowed=np.asarray([o.overflowed for o in outcomes], bool),
    )
