"""Distributed FLEXIS mining — shard_map over match roots.

Scaling story (DESIGN.md §4): the data graph is replicated (FSM graphs are
MBs; the *work* is the search), match roots are sharded across every device
in the mesh, and the mIS metric's conflict resolution becomes the collective
signature of the technique:

  per Luby round:   all-reduce(min)  over the (n,) per-vertex priority array
                    all-reduce(sum)  over the packed bitmap word-addends
                    all-reduce(sum)  of the accepted count

Priorities are globally unique (device_index · cap + local row), so winners
are globally vertex-disjoint and the bitwise-OR of retired vertices is an
exact scatter-add — no second pass needed.

Straggler note: blocks are fixed-size and uniform; root-block work variance
(hub vertices) is bounded by the frontier cap, so a step is O(cap · chunks)
on every device regardless of local degree skew — the mitigation is
structural rather than reactive.  The host round-robins super-blocks, which
also gives elastic re-entry: a rescheduled mesh just resumes from the
current super-block with the carried (bitmap, count) state.

Super-blocks are *logical*: a super-block is a fixed run of
``blocks_per_super`` root blocks, dispatched over the mesh ``ndev`` blocks
at a time (tail dispatches padded with empty blocks).  Because the logical
schedule — and therefore the embedding priority order, the per-super-block
early-exit checks, and the (found, overflowed, blocks_run) accounting — is
independent of the mesh shape, the carried ``SuperBlockState`` snapshotted
between super-blocks (`iter_batched_supports`) restores bit-identically on
any device count: greedy mIS selection over a fixed priority order is
invariant to how the order is cut into dispatch batches.  The session
runtime (`repro.runtime`) persists exactly this state for mid-pattern
resume.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .graph import DataGraph, DeviceGraph
from .pattern import Pattern
from .plan import PatternPlan, make_plan, put_plan, stack_plans
from .matcher import (MatchConfig, batch_checks, match_block,
                      transient_match_bytes)
from . import mis as mis_lib
from . import batched as batched_lib
from . import tracing

__all__ = ["mining_mesh", "sharded_mis_step", "distributed_support",
           "sharded_batched_mis_step", "distributed_batched_supports",
           "SuperBlockState", "iter_batched_supports",
           "evaluate_level_distributed"]


def mining_mesh(axis: str = "workers", devices=None) -> Mesh:
    """A 1-D mesh over all available devices (mining shards roots, period)."""
    devices = np.array(jax.devices() if devices is None else devices)
    return jax.make_mesh((devices.size,), (axis,),
                         axis_types=(AxisType.Auto,), devices=devices)


def _replicated(tree, mesh: Mesh):
    """``tree`` placed whole on every device of ``mesh``, once — the graph
    every step reads, instead of one device's copy moved to the mesh at
    each dispatch (the plans go the same way through `put_plan`)."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def _luby_rounds_global(bitmap, count, emb, n_valid, tau, k: int, n: int,
                        cap: int, axis: str):
    """Globally-synchronized Luby rounds inside shard_map.

    bitmap/count are replicated; emb/n_valid are per-device locals.
    """
    ndev = jax.lax.axis_size(axis)
    didx = jax.lax.axis_index(axis).astype(jnp.int32)
    rowid = jnp.arange(cap, dtype=jnp.int32)
    gprio_base = didx * cap
    INF = jnp.int32(ndev * cap)
    vs = jnp.clip(emb[:, :k], 0, None)
    valid = rowid < n_valid

    def touches(bm):
        return mis_lib.touches_used(bm, vs)

    state0 = (bitmap, count, valid & ~touches(bitmap))

    def cond(state):
        bm, cnt, alive = state
        any_alive = jax.lax.pmax(jnp.any(alive).astype(jnp.int32), axis) > 0
        return any_alive & (cnt < tau)

    def body(state):
        bm, cnt, alive = state
        prio = jnp.where(alive, gprio_base + rowid, INF)
        vmin = jnp.full((n,), INF, dtype=jnp.int32)
        vmin = vmin.at[vs].min(prio[:, None])
        vmin = jax.lax.pmin(vmin, axis)                       # ← collective 1
        win = alive & jnp.all(vmin[vs] == prio[:, None], axis=1)
        # global τ cut in priority order: exclusive prefix of win-counts
        local_wins = win.sum().astype(jnp.int32)
        all_wins = jax.lax.all_gather(local_wins, axis)       # ← collective 2
        prefix = jnp.sum(jnp.where(jnp.arange(ndev) < didx, all_wins, 0))
        win_rank = prefix + jnp.cumsum(win.astype(jnp.int32)) - 1
        win &= win_rank < (tau - cnt)
        words = (vs >> 5).astype(jnp.int32)
        bits = jnp.uint32(1) << (vs & 31).astype(jnp.uint32)
        addend = jnp.zeros_like(bm).at[words].add(
            jnp.where(win[:, None], bits, jnp.uint32(0)))
        addend = jax.lax.psum(addend, axis)                   # ← collective 3
        bm = bm + addend                                      # add ≡ OR here
        cnt = cnt + jax.lax.psum(win.sum().astype(jnp.int32), axis)
        alive = alive & ~win & ~touches(bm)
        return bm, cnt, alive

    bitmap, count, _ = jax.lax.while_loop(cond, body, state0)
    return bitmap, count


@functools.partial(
    jax.jit, static_argnames=("cfg", "k", "n", "axis", "mesh"))
def sharded_mis_step(g: DeviceGraph, plan: PatternPlan, block_starts,
                     bitmap, count, tau, *, cfg: MatchConfig, k: int, n: int,
                     axis: str, mesh: Mesh):
    """One distributed mining step: every device matches its own root block,
    then the mesh resolves mIS conflicts globally.

    block_starts: (ndev,) int32 — one root-block origin per device.
    bitmap/count: replicated metric state. Returns (bitmap, count, found).
    """

    def step(block_start, bm, cnt):
        emb, n_valid, found, _, _ = match_block(g, plan, block_start[0], cfg)
        bm, cnt = _luby_rounds_global(bm, cnt, emb, n_valid, tau, k, n,
                                      cfg.cap, axis)
        return bm, cnt, jax.lax.psum(found, axis)

    return jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )(block_starts, bitmap, count)


@functools.partial(
    jax.jit, static_argnames=("cfg", "k", "n", "axis", "mesh"))
def sharded_batched_mis_step(g: DeviceGraph, plans: PatternPlan, block_starts,
                             bitmaps, counts, taus, *, cfg: MatchConfig,
                             k: int, n: int, axis: str, mesh: Mesh):
    """One distributed step for a whole same-k candidate batch.

    The batched data plane's pattern axis composes with root sharding: roots
    are split across the mesh (``block_starts``: one origin per device) while
    the stacked plans and the (P, …) metric state are replicated and vmapped
    on every device — the pattern axis is pure extra parallelism, the root
    axis is where the collectives run.  Per-pattern results are identical to
    `sharded_mis_step` run pattern-by-pattern (globally-unique priorities are
    per pattern; patterns never interact).

    plans/bitmaps/counts/taus: leading (P,) pattern axis, replicated.
    block_starts: (ndev,) int32 — one root-block origin per device.
    Returns (bitmaps, counts, found, overflowed, peak) with found summed,
    overflow OR-ed and peak frontier occupancy max-ed over the mesh,
    each (P,).
    """

    checks = batch_checks(plans)

    def step(block_start, bms, cnts):
        def one(plan, bm, cnt, tau):
            emb, n_valid, found, ovf, peak = match_block(
                g, plan, block_start[0], cfg, checks)
            bm, cnt = _luby_rounds_global(bm, cnt, emb, n_valid, tau, k, n,
                                          cfg.cap, axis)
            return bm, cnt, found, ovf, peak

        bms, cnts, found, ovf, peak = jax.vmap(one)(plans, bms, cnts, taus)
        return (bms, cnts, jax.lax.psum(found, axis),
                jax.lax.psum(ovf.astype(jnp.int32), axis) > 0,
                jax.lax.pmax(peak, axis))

    return jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )(block_starts, bitmaps, counts)


# ---------------------------------------------------------------------------
# resumable super-block schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SuperBlockState:
    """Carried state of a batched distributed run between super-blocks.

    This is the unit the session runtime checkpoints (mid-pattern resume):
    ``bitmaps``/``counts`` are the device-side mIS metric state saved as
    *full logical arrays* — the sharded step replicates them (out_specs
    ``P()``), so `np.asarray` yields the logical value and a restore on any
    mesh shape is just handing the host array back to ``shard_map``.  The
    remaining fields are host-side telemetry accumulators plus the
    ``next_block`` cursor (in root-block units).
    """

    next_block: int               # next schedule position (block-order index)
    bitmaps: Any                  # (P, ⌈n/32⌉) uint32 — logical/replicated
    counts: Any                   # (P,) int32
    found: np.ndarray             # (P,) int64, frozen per pattern at τ
    overflowed: np.ndarray        # (P,) bool
    blocks_run: np.ndarray        # (P,) int64, frozen per pattern at τ
    super_blocks_run: int = 0
    dispatches: int = 0           # sharded step invocations (telemetry)
    max_count: Optional[np.ndarray] = None  # (P,) int64 peak occupancy

    def supports(self) -> np.ndarray:
        return np.asarray(self.counts, np.int64)


def _init_super_block_state(P_: int, n: int) -> SuperBlockState:
    return SuperBlockState(
        next_block=0,
        bitmaps=jnp.zeros((P_, mis_lib.bitmap_words(n)), jnp.uint32),
        counts=jnp.zeros((P_,), jnp.int32),
        found=np.zeros(P_, np.int64),
        overflowed=np.zeros(P_, bool),
        blocks_run=np.zeros(P_, np.int64),
        max_count=np.zeros(P_, np.int64),
    )


def iter_batched_supports(
    host_g: DataGraph,
    patterns: Sequence[Pattern],
    taus: Sequence[int],
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "workers",
    match_cfg: Optional[MatchConfig] = None,
    complete: bool = False,
    blocks_per_super: Optional[int] = None,
    state: Optional[SuperBlockState] = None,
    block_order: Optional[np.ndarray] = None,
) -> Iterator[SuperBlockState]:
    """Mine a same-k batch one *logical* super-block at a time.

    Yields the carried `SuperBlockState` after every super-block; the caller
    may stop consuming at any yield, snapshot the state, and later rebuild
    the iterator with ``state=`` to continue — on the same or a different
    mesh shape — with bit-identical ``counts``/``bitmaps``/accounting.

    ``blocks_per_super`` fixes the logical super-block width in root blocks
    (default: the current device count, the legacy schedule).  τ early exit
    and the per-pattern (found, overflowed, blocks_run) freeze happen at
    super-block boundaries, so any two runs with the same width agree
    exactly regardless of ``ndev``; runs with different widths agree on
    supports but may differ in the telemetry fields (they see different
    early-exit granularity).

    ``block_order`` is the static root-block schedule (a permutation of
    block ids, `planner.root_block_order`; None = vertex-id order).  The
    super-block cursor — including `SuperBlockState.next_block` — indexes
    into the schedule, which stays mesh-shape-invariant: the permutation
    is a pure function of (graph, root_block, root_order).
    """
    assert len(patterns) == len(taus) and len(patterns) > 0
    k = patterns[0].k
    assert all(p.k == k for p in patterns), "batch must share pattern size"
    mesh = mesh or mining_mesh(axis)
    ndev = int(np.prod(list(mesh.shape.values())))
    cfg = match_cfg or MatchConfig.for_graph(host_g)
    dev_g = _replicated(DeviceGraph.from_host(host_g), mesh)
    with tracing.span("plan_build", k=k, patterns=len(patterns)):
        plans = put_plan(
            stack_plans([make_plan(p, host_g) for p in patterns]),
            NamedSharding(mesh, P()))
    n = host_g.n
    P_ = len(patterns)
    taus_np = np.asarray(taus, np.int64)
    bps = ndev if blocks_per_super is None else int(blocks_per_super)
    assert bps >= 1

    int32_max = np.iinfo(np.int32).max
    tau_full = np.full(P_, int32_max, np.int64) if complete else taus_np
    tau_dev = jnp.asarray(np.minimum(tau_full, int32_max), jnp.int32)

    if state is None:
        state = _init_super_block_state(P_, n)
    # re-shard on entry: a restored state carries host (logical) arrays
    bitmaps = jnp.asarray(state.bitmaps, jnp.uint32)
    counts = jnp.asarray(state.counts, jnp.int32)
    assert bitmaps.shape == (P_, mis_lib.bitmap_words(n)), bitmaps.shape
    found = state.found.copy()
    ovf = state.overflowed.copy()
    blocks_run = state.blocks_run.copy()
    max_count = (np.zeros(P_, np.int64) if state.max_count is None
                 else state.max_count.copy())
    next_block = int(state.next_block)
    super_blocks = int(state.super_blocks_run)
    dispatches = int(state.dispatches)

    n_blocks = -(-n // cfg.root_block)
    if block_order is None:
        block_order = np.arange(n_blocks, dtype=np.int64)
    assert block_order.shape[0] == n_blocks
    while next_block < n_blocks:
        # a super-block is one flexis.block span, closed before the yield
        # so that no span stays open while the consumer runs
        with tracing.span("block", block=next_block, bucket=P_):
            with tracing.span("pull"):
                counts_np = np.asarray(counts, np.int64)
            tracing.count("host_pulls")
            if not complete and bool((counts_np >= taus_np).all()):
                return
            # per-pattern freeze at super-block granularity: a pattern that
            # already reached τ stops accumulating telemetry (its device state
            # is frozen anyway by the cnt < τ guard in the Luby rounds)
            active = np.ones(P_, bool) if complete else counts_np < taus_np
            stop = min(next_block + bps, n_blocks)
            sb_found = np.zeros(P_, np.int64)
            sb_ovf = np.zeros(P_, bool)
            sb_peak = np.zeros(P_, np.int64)
            for lo in range(next_block, stop, ndev):
                # pad tail dispatches with empty blocks (start ≥ n matches no
                # roots) so a super-block never leaks into the next one
                pos = lo + np.arange(ndev)
                ids = block_order[np.minimum(pos, n_blocks - 1)]
                starts = jnp.asarray(
                    np.where(pos < stop, ids * cfg.root_block, n), jnp.int32)
                with tracing.span("dispatch"):
                    bitmaps, counts, d_found, d_ovf, d_peak = sharded_batched_mis_step(
                        dev_g, plans, starts, bitmaps, counts, tau_dev,
                        cfg=cfg, k=k, n=n, axis=axis, mesh=mesh)
                with tracing.span("pull"):
                    sb_found += np.asarray(d_found, np.int64)
                    sb_ovf |= np.asarray(d_ovf, bool)
                    sb_peak = np.maximum(sb_peak, np.asarray(d_peak, np.int64))
                tracing.count("host_pulls", 3)
                tracing.count("match_blocks")
                dispatches += 1
            with tracing.span("account"):
                found[active] += sb_found[active]
                ovf[active] |= sb_ovf[active]
                blocks_run[active] += stop - next_block
                max_count[active] = np.maximum(max_count[active], sb_peak[active])
                next_block = stop
                super_blocks += 1
                state = SuperBlockState(
                    next_block=next_block, bitmaps=bitmaps, counts=counts,
                    found=found.copy(), overflowed=ovf.copy(),
                    blocks_run=blocks_run.copy(), super_blocks_run=super_blocks,
                    dispatches=dispatches, max_count=max_count.copy())
        yield state


def distributed_batched_supports(
    host_g: DataGraph,
    patterns: Sequence[Pattern],
    taus: Sequence[int],
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "workers",
    match_cfg: Optional[MatchConfig] = None,
    complete: bool = False,
    blocks_per_super: Optional[int] = None,
    state: Optional[SuperBlockState] = None,
    return_state: bool = False,
):
    """mIS supports of a same-k candidate batch, mined across the whole mesh.

    Returns (supports, found), each (P,) — or (supports, found, state) with
    ``return_state=True``.  Per-pattern semantics match
    `distributed_support`; the host early-exits the super-block loop once
    every pattern has reached its τ (each pattern's ``count < τ`` guard
    freezes its own state as soon as it individually finishes).  Drives
    `iter_batched_supports` to completion; pass ``state=`` to continue a
    snapshotted run.
    """
    last = state if state is not None else _init_super_block_state(
        len(patterns), host_g.n)
    for last in iter_batched_supports(
            host_g, patterns, taus, mesh=mesh, axis=axis, match_cfg=match_cfg,
            complete=complete, blocks_per_super=blocks_per_super, state=state):
        pass
    if return_state:
        return last.supports(), last.found, last
    return last.supports(), last.found


def evaluate_level_distributed(
    host_g: DataGraph,
    patterns: Sequence[Pattern],
    taus: Sequence[int],
    cfg: MatchConfig,
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "workers",
    complete: bool = False,
    deadline: Optional[float] = None,
    max_batch: int = batched_lib.DEFAULT_MAX_BATCH,
    blocks_per_super: Optional[int] = None,
    hooks=None,
    block_order: Optional[np.ndarray] = None,
) -> Tuple[List[Optional["batched_lib.PatternOutcome"]], bool,
           "batched_lib.LevelTelemetry"]:
    """Evaluate a whole candidate level on the mesh (mIS/Luby semantics).

    The distributed counterpart of `batched.evaluate_level_batched`: the
    level is cut into the same deterministic (k, lo) groups, each group is
    mined by `iter_batched_supports` (roots sharded × patterns batched), and
    the same duck-typed ``hooks`` surface drives mid-level resume — here at
    *super-block* granularity, with `SuperBlockState` as the carried unit.
    Supports are bit-identical to the single-device ``mis_luby`` oracle;
    found/overflowed/blocks_run are accounted at super-block granularity
    (see `iter_batched_supports`).

    Timeouts follow the all-or-nothing contract: the deadline is checked
    between super-blocks, and an interrupted group reports ``None`` for
    every pattern still in flight.
    """
    assert len(patterns) == len(taus)
    # fault-injection point for the mesh-failure class: an `error` fault
    # here exercises `mine()`'s distributed→batched fallback exactly the
    # way a real collective/mesh failure would (lazy import — core/ must
    # not require runtime/ at import time)
    try:
        from repro.runtime import faults as _faults
    except ImportError:  # pragma: no cover
        _faults = None
    if _faults is not None:
        _faults.fire("level.distributed")
    mesh = mesh or mining_mesh(axis)
    n = host_g.n
    outcomes: List[Optional[batched_lib.PatternOutcome]] = [None] * len(patterns)
    prefilled = hooks.resume_outcomes() if hooks is not None else None

    timed_out = False
    telemetry = batched_lib.LevelTelemetry()
    if hooks is not None:
        telemetry.dispatches = int(hooks.resume_dispatches())
    for k, lo, idxs in batched_lib.level_groups(patterns, max_batch):
        telemetry.state_bytes = max(
            telemetry.state_bytes,
            len(idxs) * (batched_lib._state_bytes("mis_luby", k, n)
                         + transient_match_bytes(cfg, k)))
        if prefilled is not None and all(i in prefilled for i in idxs):
            for i in idxs:
                outcomes[i] = prefilled[i]
            continue
        group_pats = [patterns[i] for i in idxs]
        group_taus = [taus[i] for i in idxs]
        state = hooks.group_resume(k, lo) if hooks is not None else None
        group_timed_out = False
        it = iter_batched_supports(
            host_g, group_pats, group_taus, mesh=mesh, axis=axis,
            match_cfg=cfg, complete=complete,
            blocks_per_super=blocks_per_super, state=state,
            block_order=block_order)
        last = state if state is not None else _init_super_block_state(
            len(idxs), n)
        while True:
            if deadline is not None and time.monotonic() > deadline:
                group_timed_out = True
                break
            try:
                last = next(it)
            except StopIteration:
                break
            if hooks is not None:
                with tracing.span("hooks"):
                    hooks.on_group_state(k, lo, last)
        telemetry.dispatches += int(last.dispatches)
        if group_timed_out:
            timed_out = True
            break
        sups = last.supports()
        last_max = (last.max_count if last.max_count is not None
                    else np.zeros(len(idxs), np.int64))
        got = [
            batched_lib.PatternOutcome(
                support=int(sups[j]),
                frequent=bool(sups[j] >= group_taus[j]),
                embeddings_found=int(last.found[j]),
                overflowed=bool(last.overflowed[j]),
                blocks_run=int(last.blocks_run[j]),
                max_count=int(last_max[j]),
            )
            for j in range(len(idxs))
        ]
        for i, out in zip(idxs, got):
            outcomes[i] = out
        if hooks is not None:
            with tracing.span("hooks"):
                hooks.on_group_done(k, lo, idxs, got, int(last.dispatches))
    assert timed_out or all(o is not None for o in outcomes)
    for o in outcomes:
        if o is not None:
            telemetry.max_count = max(telemetry.max_count, o.max_count)
            telemetry.overflowed |= o.overflowed
    return outcomes, timed_out, telemetry


def distributed_support(
    host_g: DataGraph,
    pat: Pattern,
    tau: int,
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "workers",
    match_cfg: Optional[MatchConfig] = None,
    complete: bool = False,
) -> Tuple[int, int]:
    """mIS support of one pattern, mined across the whole mesh.

    Returns (support, embeddings_found).  Semantics match the single-device
    `evaluate_pattern(metric="mis_luby")`: the complete run yields the
    lexicographically-first maximal independent set in global priority order.
    """
    mesh = mesh or mining_mesh(axis)
    ndev = int(np.prod(list(mesh.shape.values())))
    cfg = match_cfg or MatchConfig.for_graph(host_g)
    dev_g = _replicated(DeviceGraph.from_host(host_g), mesh)
    plan = put_plan(make_plan(pat, host_g), NamedSharding(mesh, P()))
    n = host_g.n
    bitmap = mis_lib.bitmap_init(n)
    count = jnp.int32(0)
    tau_dev = jnp.int32(np.iinfo(np.int32).max if complete else tau)
    found_total = 0

    stride = ndev * cfg.root_block
    n_super = -(-n // stride)
    for s in range(n_super):
        starts = jnp.asarray(
            s * stride + np.arange(ndev) * cfg.root_block, jnp.int32)
        bitmap, count, found = sharded_mis_step(
            dev_g, plan, starts, bitmap, count, tau_dev,
            cfg=cfg, k=pat.k, n=n, axis=axis, mesh=mesh)
        found_total += int(found)
        if not complete and int(count) >= tau:
            break
    return int(count), found_total
