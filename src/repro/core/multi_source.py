"""Multi-source BFS as batched BVSS bit-SpMM (paper §2 + §7, DESIGN §2.5).

Stacking S frontiers column-wise turns the SpMSpV pull into an SpMM; on TPU
this is where the MXU path pays off (DESIGN.md §2.2): the slices of every
queued VSS are contracted against the S stacked σ-bit frontier bytes of its
slice set as small bit-SpMM tiles (``kernels.bvss_spmm``).  Unlike the seed
implementation, the hot path never materialises the O(n²/32) dense
``to_dense_bits`` adjacency — peak device memory scales with BVSS words.

The level loop rides the same :class:`~repro.core.level_pipeline.LevelPipeline`
skeleton as the single-source engines, and pulls its queue through their
chunk loop (:func:`~repro.core.bfs.pull_chunks`): one compacted *union*
queue of VSSs (a slice set is live if ANY source column's frontier touches
it), the per-column frontier kept as packed words.

:func:`make_ms_engine` exposes the jit-able building blocks (init / insert /
requeue / one lock-step level) so GraphSession (``repro.serve``) can drive
the same step with host control between levels — the wave-serving loop with
mid-flight slot refills — while :func:`make_multi_source_bfs` fuses the whole
loop on device for the fixed-cohort case (closeness centrality).
:func:`drive_wave` is the generic host loop both ride: callers supply only a
*refill hook* (``next_source``) and a harvest callback, so every wave client
— level serving, connected-components flood-fill re-seeding
(``repro.analytics.components``), centrality cohorts — shares one slot-pool
discipline instead of re-implementing it.

``make_ms_engine(..., track_sigma=True)`` widens the wave state with a σ
path-count channel (DESIGN §2.6): alongside the Boolean bit-SpMM pull, each
level runs the *weighted* tile product ``kernels.bvss_spmm_w`` over the same
queued BVSS masks, propagating ``paths[u] = Σ paths[pred]`` for the Brandes
forward phase (``repro.analytics.betweenness``); the Boolean counts still
gate discovery, so the float channel can never invent a vertex.

Everything here is MESH-NATIVE (DESIGN §2.4), the float channel included:
a row-sharded :class:`~repro.core.bfs.BlestProblem` runs the same
step/finalize under ``shard_map`` — each shard pulls/scatters its local
``(rows_per_shard, S)`` level block, every shard carries a replica of the
stacked global frontier words (that replica IS each device's pull
operand), and one frontier-word all-gather per level refreshes it.  The
σ channel shards exactly like the frontier bits: ``paths`` is a local
``(rows_per_shard, S)`` block, and each level's weighted pull consumes a
per-level all-gather of the σ-frontier float values — the float twin of
the frontier-word gather, hoisted OUT of the chunk loop (each shard runs
its own trip count, and a collective inside it would wedge the mesh).
The host-visible wave state then has a leading shard axis on every
field.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bfs import (DIRECTIONS, BlestProblem, _frontier_bytes,
                            _round_width, chunk_ceil, chunk_count,
                            expand_push_queue, make_compactor,
                            make_vertex_compactor, pull_chunks,
                            queue_capacity, resolve_push_cap)
from repro.core.bvss import ShardedBVSSDevice
from repro.core.level_pipeline import LevelPipeline, global_any, run_levels
from repro.distributed.bfs_dist import frontier_all_gather
from repro.distributed.collectives import (butterfly_frontier_exchange,
                                           butterfly_or_allreduce)
from repro.errors import ConfigError
from repro.graphs import Graph
from repro.kernels import bvss_spmm, bvss_spmm_w, bvss_spmm_w_local
from repro.kernels.ref import bvss_spmm_ref, bvss_spmm_w_ref

INF = np.int32(np.iinfo(np.int32).max)


def _union_words(F: jnp.ndarray) -> jnp.ndarray:
    """OR the per-column packed frontiers ``(n_fwords, S)`` into one union
    word array — the push phase compacts its vertex queue from this (a
    vertex is queued iff ANY column's frontier holds it)."""
    return jax.lax.reduce(F, jnp.uint32(0), jax.lax.bitwise_or, (1,))


def _push_fbytes(F: jnp.ndarray, vrep: jnp.ndarray, sigma: int
                 ) -> jnp.ndarray:
    """Per-(queue entry, column) one-hot frontier bytes for the batched
    push phase: entry b pushing vertex v contributes ``1 << (v % σ)`` to
    exactly the columns whose frontier actually holds v, 0 elsewhere — so
    a vertex live in SOME columns never leaks discoveries into the others.
    Dummy entries need no special case: whatever byte they produce meets
    the all-zero dummy masks row of their dummy VSS id."""
    member = ((F[vrep // 32] >> (vrep % 32).astype(jnp.uint32)[:, None])
              & jnp.uint32(1))                               # (B, S) {0,1}
    return (jnp.uint32(1)
            << (vrep % sigma).astype(jnp.uint32))[:, None] * member


def _pack_cols(bits: jnp.ndarray, lwords: int) -> jnp.ndarray:
    """Per-column frontier pack: bool (lwords*32, S) -> uint32 (lwords, S)."""
    S = bits.shape[1]
    w = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits.reshape(lwords, 32, S).astype(jnp.uint32)
                   * w[None, :, None], axis=1, dtype=jnp.uint32)


def _unpack_cols(words: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`_pack_cols`: uint32 (lwords, S) -> bool (lwords*32, S)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return ((words[:, None, :] >> shifts[None, :, None]) & 1
            ).reshape(-1, words.shape[1]) != 0


class MSState(NamedTuple):
    levels: jnp.ndarray   # (n+1, S) int32; row n is the dummy-row sink
                          #   sharded: (D, rps+1, S), LOCAL rows per shard
    F: jnp.ndarray        # (n_fwords, S) uint32 per-column packed frontier
                          #   sharded: (D, n_fwords, S), one global replica
                          #   per shard (each device's pull operand)
    Q: jnp.ndarray        # (qcap,) int32 union VSS queue, dummy-padded
                          #   sharded: (D, qcap), one queue per shard
    count: jnp.ndarray    # int32 live VSS count (chunks to pull; sharded (D,))
    col_lvl: jnp.ndarray  # (S,) int32 per-column BFS depth reached so far
                          #   sharded: (D, S) identical replicas
    cont: jnp.ndarray     # bool: any live VSS anywhere (mesh-global)
    paths: jnp.ndarray | None = None
                          # (n, S) float32 σ shortest-path counts (Brandes
                          # forward channel), present iff the engine was
                          # built with ``track_sigma=True``; None otherwise
                          # (a None pytree leaf costs the default engines
                          # nothing).  Sharded: (D, rps, S), LOCAL rows per
                          # shard — the float channel shards like levels,
                          # not like the replicated frontier words


@dataclasses.dataclass(frozen=True)
class MSEngine:
    """Jit-able building blocks of the batched BVSS SpMM level step.

    ``step``/``finalize`` plug into :class:`LevelPipeline` for the fused
    on-device loop; ``insert``/``requeue``/``level_step``/``col_live`` are
    the wave-serving surface (jitted, host-driven between levels).
    ``levels_of(state, slot)`` extracts one column's ``(n,)`` levels in
    global row ids so the serving layer never needs to know the shard
    layout."""

    problem: BlestProblem
    n_slots: int
    init: Callable        # (sources (S,) i32) -> MSState, queue rebuilt
    idle: Callable        # () -> MSState with no live columns
    insert: Callable      # (state, slot, src) -> MSState (requeue after!)
    insert_batch: Callable  # (state, srcs (S,), mask (S,)) -> MSState with
                          # every masked slot reset + queue rebuilt: ONE
                          # dispatch per refill round (the drive_wave path)
    requeue: Callable     # (state) -> state with Q/count rebuilt from F
    step: Callable | None        # (state) -> state after gather+pull+update
    finalize: Callable | None    # (state) -> state after pack+requeue
                          # (None on the sharded surface: the fused loop is
                          # built by make_multi_source_bfs instead)
    level_step: Callable  # jitted (state) -> (state, live (S,) bool) after
                          # one full level — liveness piggybacks on the
                          # step so serving pays ONE dispatch per level
    col_live: Callable    # jitted (state) -> (S,) bool frontier non-empty
    levels_of: Callable   # (state, slot) -> (n,) levels in global row ids
    paths_of: Callable | None = None
                          # (state, slot) -> (n,) σ path counts in global
                          # row ids; None unless built with track_sigma


def make_ms_engine(problem: BlestProblem, n_slots: int, *,
                   use_kernel: bool = True,
                   track_sigma: bool = False,
                   direction: str = "auto", push_cap: int | None = None,
                   alpha: float = 4.0,
                   spmm_impl: Callable | None = None,
                   spmm_w_impl: Callable | None = None,
                   gather_impl: Callable | None = None,
                   push_impl: Callable | None = None) -> MSEngine:
    """Build the S-column lock-step BVSS level machinery (mesh-native when
    ``problem`` is sharded).  ``track_sigma`` widens the wave state with
    the Brandes σ path-count channel — on a sharded problem the channel
    rides the generic sharded float path (per-level all-gather of the
    σ-frontier values, DESIGN §2.6).

    ``direction`` / ``push_cap`` / ``alpha`` are the
    direction-optimizing knobs of DESIGN §2.8, batched: the push branch
    compacts the UNION frontier (any column) into a vertex queue, expands
    each vertex into the ≤ R VSSs of its own slice set, and pushes
    per-column one-hot frontier bytes through the SAME bit-SpMM tile
    product the pull uses — so both directions share one kernel and one
    fault seam (``spmm_impl``).  ``track_sigma`` pins ``direction="pull"``
    (the σ channel's weighted twin has no push formulation; asking for
    forced push with σ tracking is a :class:`~repro.errors.ConfigError`).
    The pull runs :func:`~repro.core.bfs.pull_chunks` over the union
    queue; auto mode prices it at ``chunk_ceil(count)`` slots.

    ``spmm_impl`` / ``spmm_w_impl`` / ``gather_impl`` are the documented
    FAULT SEAMS (DESIGN §2.7): engines capture their kernels in jitted
    closures at build time, so fault injection (``serve/faults.py``) — and
    any future kernel substitution — happens here, as explicit build-time
    overrides of the bit-SpMM, weighted-SpMM and frontier-word all-gather
    call sites, not by monkeypatching module globals after tracing.
    ``gather_impl`` must match :func:`repro.distributed.bfs_dist.
    frontier_all_gather`'s ``(fw_local, axis)`` signature and is only
    consulted on a sharded problem.  ``push_impl`` is accepted so fault
    plans can splat ONE override dict into every engine build; the wave
    engine's push branch rides the bit-SpMM seam (see above), so the
    single-source push-kernel override has nothing to attach to here and
    is ignored."""
    del push_impl  # wave push rides the spmm seam (docstring above)
    p = problem
    if direction not in DIRECTIONS:
        raise ConfigError(
            f"direction must be one of {DIRECTIONS}, got {direction!r}")
    if track_sigma:
        if direction == "push":
            raise ConfigError(
                "track_sigma is pull-only (the Brandes σ channel has no "
                "weighted push twin); direction='push' is contradictory")
        direction = "pull"
    spmm = spmm_impl if spmm_impl is not None else \
        (bvss_spmm if use_kernel else bvss_spmm_ref)
    spmm_w = spmm_w_impl if spmm_w_impl is not None else \
        (bvss_spmm_w if use_kernel else bvss_spmm_w_ref)
    if p.mesh is not None:
        if p.is_2d:
            return _make_ms_engine_sharded_2d(p, n_slots, spmm=spmm,
                                              spmm_w=spmm_w,
                                              track_sigma=track_sigma,
                                              gather=gather_impl,
                                              direction=direction)
        return _make_ms_engine_sharded(p, n_slots, spmm=spmm,
                                       spmm_w=spmm_w,
                                       track_sigma=track_sigma,
                                       gather=gather_impl,
                                       direction=direction,
                                       push_cap=push_cap, alpha=alpha)
    dev = p.dev
    sigma = p.sigma
    S = n_slots
    n, n_fwords = p.n, p.n_fwords
    qcap = queue_capacity(p.num_vss)
    compact = make_compactor(dev, p.num_vss, qcap)
    all_sets = jnp.arange(p.n_sets, dtype=jnp.int32)
    n_pad = n_fwords * 32
    n_cols = p.n_sets * sigma  # padded column space (≥ n) for value gathers
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

    def pull_step(state: MSState) -> MSState:
        cand = (state.col_lvl + 1)[None, :]
        acc = None
        if track_sigma:
            # σ channel (DESIGN §2.6): the weighted twin of the pull — the
            # SAME queued tiles, contracted against the frontier's float
            # path counts; rows discovered this level take the accumulated
            # sum (Boolean counts gate discovery, so a converged column —
            # whose frontier bits are gone but whose levels still match
            # col_lvl — contributes nothing).  No chunk changes a row at
            # col_lvl, so the operand is built once, before the loop.
            xv = jnp.where(state.levels[:n] == state.col_lvl[None, :],
                           state.paths, 0.0)
            xv = jnp.concatenate(
                [xv, jnp.zeros((n_cols - n, S), jnp.float32)])
            acc = jnp.zeros((n + 1, S), jnp.float32)

        def chunk(i, ids, carry):
            levels, acc = carry
            fb = _frontier_bytes(state.F, dev.virtual_to_real[ids], sigma)
            counts = spmm(dev.masks[ids], fb, sigma=sigma)  # (C, spw, 32, S)
            rows = dev.row_ids[ids].reshape(-1)
            upd = jnp.where(counts.reshape(-1, S) > 0, cand, INF
                            ).astype(jnp.int32)
            # eager scatter-min: an already-visited row keeps its smaller
            # level; dummy rows land in the level sink (row n)
            levels = levels.at[rows].min(upd)
            if track_sigma:
                wv = bvss_spmm_w_local(dev.masks[ids],
                                       dev.virtual_to_real[ids], xv,
                                       sigma=sigma, impl=spmm_w)
                acc = acc.at[rows].add(wv.reshape(-1, S))
            return levels, acc

        levels, acc = pull_chunks(state.Q, chunk_count(state.count), chunk,
                                  (state.levels, acc))
        if not track_sigma:
            return state._replace(levels=levels)
        newly = levels[:n] == cand
        return state._replace(
            levels=levels, paths=jnp.where(newly, acc[:n], state.paths))

    pcap = resolve_push_cap(direction, push_cap, n)
    pqcap = _round_width(pcap)
    R = p.max_vss_per_set
    push_cost = pqcap * R
    if direction == "pull" or (direction == "auto" and push_cost >= qcap):
        # push can never undercut even the full queue: compile the pure
        # pull step (same static bail as the single-source engines)
        step = pull_step
    else:
        compact_vertices = make_vertex_compactor(n_fwords, n, pqcap)

        def push_update(state: MSState) -> MSState:
            """Batched push level (DESIGN §2.8): union-frontier vertex
            queue → per-vertex VSS expansion → per-column one-hot bytes
            through the same bit-SpMM tiles → the same scatter-min."""
            VQ, _ = compact_vertices(_union_words(state.F))
            ids = expand_push_queue(dev, VQ, R, p.num_vss)
            vrep = jnp.broadcast_to(VQ[:, None], (pqcap, R)).reshape(-1)
            fb = _push_fbytes(state.F, vrep, sigma)
            counts = spmm(dev.masks[ids], fb, sigma=sigma)
            rows = dev.row_ids[ids].reshape(-1)
            cand = (state.col_lvl + 1)[None, :]
            upd = jnp.where(counts.reshape(-1, S) > 0, cand, INF
                            ).astype(jnp.int32)
            return state._replace(levels=state.levels.at[rows].min(upd))

        if direction == "push":
            step = push_update
        else:
            def step(state: MSState) -> MSState:
                ucount = jnp.sum(jax.lax.population_count(
                    _union_words(state.F))).astype(jnp.int32)
                tbits = jnp.sum(jax.lax.population_count(state.F)
                                ).astype(jnp.float32)
                unvisited = jnp.sum(state.levels[:n] == INF
                                    ).astype(jnp.float32)
                use_push = ((ucount <= pcap)
                            & (jnp.int32(push_cost)
                               < chunk_ceil(state.count))
                            & (tbits * jnp.float32(alpha) <= unvisited))
                return jax.lax.cond(use_push, push_update, pull_step,
                                    state)

    def requeue(state: MSState) -> MSState:
        """Rebuild the union queue from the per-column frontiers: a slice
        set is live iff any column's σ-bit frontier byte is non-zero."""
        set_active = (_frontier_bytes(state.F, all_sets, sigma) != 0
                      ).any(axis=1)
        Q, count = compact(set_active)
        return state._replace(Q=Q, count=count, cont=count > 0)

    def finalize(state: MSState) -> MSState:
        nxt = (state.col_lvl + 1)[None, :]
        new = state.levels[:n] == nxt                     # (n, S)
        bits = jnp.zeros((n_pad, S), dtype=bool).at[:n].set(new)
        F = jnp.sum(bits.reshape(n_fwords, 32, S).astype(jnp.uint32)
                    * weights[None, :, None], axis=1, dtype=jnp.uint32)
        state = state._replace(F=F, col_lvl=state.col_lvl + new.any(axis=0))
        return requeue(state)

    def _paths0() -> jnp.ndarray | None:
        return jnp.zeros((n, S), jnp.float32) if track_sigma else None

    def init(sources: jnp.ndarray) -> MSState:
        sources = jnp.asarray(sources, dtype=jnp.int32)
        cols = jnp.arange(S)
        levels = jnp.full((n + 1, S), INF, dtype=jnp.int32)
        levels = levels.at[sources, cols].set(0)
        F = jnp.zeros((n_fwords, S), dtype=jnp.uint32)
        F = F.at[sources // 32, cols].set(
            jnp.uint32(1) << (sources % 32).astype(jnp.uint32))
        paths = _paths0()
        if track_sigma:
            paths = paths.at[sources, cols].set(1.0)
        st = MSState(levels=levels, F=F,
                     Q=jnp.full((qcap,), p.num_vss, dtype=jnp.int32),
                     count=jnp.int32(0),
                     col_lvl=jnp.zeros((S,), dtype=jnp.int32),
                     cont=jnp.bool_(False), paths=paths)
        return requeue(st)

    def idle() -> MSState:
        return MSState(levels=jnp.full((n + 1, S), INF, dtype=jnp.int32),
                       F=jnp.zeros((n_fwords, S), dtype=jnp.uint32),
                       Q=jnp.full((qcap,), p.num_vss, dtype=jnp.int32),
                       count=jnp.int32(0),
                       col_lvl=jnp.zeros((S,), dtype=jnp.int32),
                       cont=jnp.bool_(False), paths=_paths0())

    def insert(state: MSState, slot: jnp.ndarray, src: jnp.ndarray
               ) -> MSState:
        """Reset column ``slot`` to a fresh query from ``src`` (internal
        ids).  Call ``requeue`` once after a refill round."""
        slot = jnp.asarray(slot, dtype=jnp.int32)
        src = jnp.asarray(src, dtype=jnp.int32)
        levels = state.levels.at[:, slot].set(INF).at[src, slot].set(0)
        F = state.F.at[:, slot].set(jnp.uint32(0))
        F = F.at[src // 32, slot].set(
            jnp.uint32(1) << (src % 32).astype(jnp.uint32))
        paths = state.paths
        if track_sigma:
            paths = paths.at[:, slot].set(0.0).at[src, slot].set(1.0)
        return state._replace(levels=levels, F=F, paths=paths,
                              col_lvl=state.col_lvl.at[slot].set(0))

    def insert_batch(state: MSState, srcs: jnp.ndarray, mask: jnp.ndarray
                     ) -> MSState:
        """Reset every slot with ``mask[j]`` to a fresh query from
        ``srcs[j]`` and rebuild the queue — one fused dispatch per refill
        round (``srcs[j]`` is ignored where the mask is False)."""
        cols = jnp.arange(S)
        levels = jnp.where(mask[None, :], INF, state.levels)
        levels = levels.at[srcs, cols].set(
            jnp.where(mask, 0, levels[srcs, cols]))
        F = jnp.where(mask[None, :], jnp.uint32(0), state.F)
        bit = jnp.uint32(1) << (srcs % 32).astype(jnp.uint32)
        F = F.at[srcs // 32, cols].set(
            jnp.where(mask, bit, F[srcs // 32, cols]))
        paths = state.paths
        if track_sigma:
            paths = jnp.where(mask[None, :], 0.0, paths)
            paths = paths.at[srcs, cols].set(
                jnp.where(mask, 1.0, paths[srcs, cols]))
        st = state._replace(levels=levels, F=F, paths=paths,
                            col_lvl=jnp.where(mask, 0, state.col_lvl))
        return requeue(st)

    def level_step(state: MSState) -> tuple[MSState, jnp.ndarray]:
        state = finalize(step(state))
        return state, (state.F != 0).any(axis=0)

    return MSEngine(
        problem=p, n_slots=S, init=jax.jit(init), idle=jax.jit(idle),
        insert=jax.jit(insert), insert_batch=jax.jit(insert_batch),
        requeue=jax.jit(requeue),
        step=step, finalize=finalize,
        level_step=jax.jit(level_step),
        col_live=jax.jit(lambda st: (st.F != 0).any(axis=0)),
        levels_of=lambda st, slot: st.levels[:n, slot],
        paths_of=(lambda st, slot: st.paths[:, slot]) if track_sigma
        else None)


# ---------------------------------------------------------------------------
# generic wave driver: the ONE slot-pool serving loop (DESIGN §2.5/§2.6)
# ---------------------------------------------------------------------------
def drive_wave(eng: MSEngine,
               next_source: Callable[[int], int | None],
               on_converged: Callable[[int, np.ndarray], None], *,
               max_steps: int | None = None,
               should_harvest: Callable[[int], bool] | None = None,
               on_harvested: Callable[[int, np.ndarray], None] | None = None
               ) -> int:
    """Drive batched waves with mid-flight slot refills until the refill
    hook runs dry — the host loop shared by level serving
    (``GraphSession.levels_batch``) and flood-fill re-seeding
    (``repro.analytics.components``).

    ``next_source(slot)`` returns the next source (internal row id) to
    launch in a freed slot, or None when the caller has nothing to queue
    *right now* (it is asked again after every harvest, so dynamic seeding
    off previous results is fine).  ``on_converged(slot, levels)`` receives
    each converged column's ``(n,)`` level array (global internal ids; the
    engine's ``levels_of`` hides any shard layout).  Returns the number of
    lock-step levels run.

    ``should_harvest(slot)`` / ``on_harvested(slot, levels)`` are the
    CANCELLATION hooks (DESIGN §2.7): after every lock-step level, each
    still-live slot is offered to ``should_harvest``; answering True
    harvests the column mid-flight — ``on_harvested`` receives the PARTIAL
    levels computed so far (vertices not yet reached are ``INF``) and the
    slot is freed for refill on the next round, so one over-deadline query
    cannot block the wave.  Cancellation granularity is one level step:
    that is the natural preemption point of the lock-step loop (the device
    dispatch itself is never interrupted).
    """
    S = eng.n_slots
    busy = [False] * S
    st = eng.idle()
    steps = 0
    srcs = np.zeros(S, dtype=np.int32)
    mask = np.zeros(S, dtype=bool)
    while True:
        mask[:] = False
        for slot in range(S):
            if not busy[slot]:
                src = next_source(slot)
                if src is None:
                    continue
                srcs[slot] = int(src)
                mask[slot] = True
                busy[slot] = True
        if not any(busy):
            return steps
        if mask.any():  # ONE fused insert+requeue dispatch per refill round
            st = eng.insert_batch(st, jnp.asarray(srcs), jnp.asarray(mask))
        st, live_dev = eng.level_step(st)
        live = np.asarray(live_dev)
        for slot in range(S):
            if not busy[slot]:
                continue
            if not live[slot]:
                on_converged(slot, np.asarray(eng.levels_of(st, slot)))
                busy[slot] = False
            elif should_harvest is not None and should_harvest(slot):
                # harvest mid-flight: hand back the partial levels and free
                # the slot; its stale column is overwritten by the next
                # insert_batch (until then its frontier bits cost only the
                # union queue a few extra live sets — never correctness)
                if on_harvested is not None:
                    on_harvested(slot, np.asarray(eng.levels_of(st, slot)))
                busy[slot] = False
        steps += 1
        if max_steps is not None and steps > max_steps:
            raise RuntimeError(
                f"wave serving did not converge in {max_steps} level steps")


# ---------------------------------------------------------------------------
# mesh-native wave machinery (DESIGN §2.4): shard_map'd step/finalize
# ---------------------------------------------------------------------------
class _MSLocals(NamedTuple):
    """The per-shard (unstacked-state) wave ops, shared by the host-driven
    serving surface and the fused on-device loop."""
    init: Callable
    insert: Callable
    insert_batch: Callable
    requeue: Callable
    step: Callable
    finalize: Callable


def _make_ms_locals(p: BlestProblem, S: int, spmm, qcap: int, *,
                    spmm_w=None,
                    track_sigma: bool = False,
                    gather: Callable | None = None,
                    direction: str = "pull",
                    push_cap: int | None = None,
                    alpha: float = 4.0) -> Callable:
    """Build ``locals_for(dev) -> _MSLocals`` closing over one shard's BVSS
    views.  State fields here are LOCAL: levels (rps+1, S), F (n_fwords, S)
    global replica, Q (qcap,), count/cont scalars, col_lvl (S,).

    ``track_sigma`` threads the generic sharded float channel (DESIGN
    §2.6): ``paths`` is a LOCAL (rps, S) block, and each level's weighted
    pull contracts the shard's queued tiles against a per-level
    ``all_gather`` of every shard's σ-frontier float values — the float
    twin of the frontier-word gather in ``finalize``.  The gather is
    hoisted OUT of the chunk loop (each shard loops over its own live
    count, and a collective inside a device-varying loop wedges the
    mesh).

    ``direction`` / ``push_cap`` / ``alpha`` thread the direction
    heuristic (DESIGN §2.8): the frontier words are GLOBAL replicas, so
    every shard compacts the SAME union vertex queue and expands it
    through its OWN vertex→local-VSS maps — both cond branches stay
    collective-free (the heuristic's unvisited psum runs unconditionally
    before the cond), so the per-shard pull-cost term may diverge
    safely.
    ``track_sigma`` callers must pass (or default to) ``direction="pull"``
    — the σ channel has no push twin."""
    if direction not in DIRECTIONS:
        raise ConfigError(
            f"direction must be one of {DIRECTIONS}, got {direction!r}")
    if track_sigma and direction != "pull":
        raise ConfigError(
            "track_sigma locals are pull-only (no weighted push twin); "
            f"got direction={direction!r}")
    axis = p.axis
    sigma = p.sigma
    rps = p.rows_per_shard
    lwords = rps // 32
    all_sets = jnp.arange(p.n_sets, dtype=jnp.int32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    if gather is None:
        gather = frontier_all_gather
    pcap = resolve_push_cap(direction, push_cap, p.n)
    pqcap = _round_width(pcap)
    R = p.max_vss_per_set
    push_cost = pqcap * R
    pull_only = direction == "pull" or (direction == "auto"
                                        and push_cost >= qcap)

    def locals_for(dev: ShardedBVSSDevice) -> _MSLocals:
        compact = make_compactor(dev, p.num_vss, qcap)
        compact_vertices = make_vertex_compactor(p.n_fwords, p.n, pqcap)

        def pull_update(st: MSState, xg: jnp.ndarray | None) -> MSState:
            cand = (st.col_lvl + 1)[None, :]

            def chunk(i, ids, carry):
                levels, acc = carry
                fb = _frontier_bytes(st.F, dev.virtual_to_real[ids], sigma)
                counts = spmm(dev.masks[ids], fb, sigma=sigma)
                rows = dev.row_ids[ids].reshape(-1)  # LOCAL, dummy = rps
                upd = jnp.where(counts.reshape(-1, S) > 0, cand, INF
                                ).astype(jnp.int32)
                levels = levels.at[rows].min(upd)
                if track_sigma:
                    # σ channel: the weighted twin over the SAME queued
                    # tiles, pulling from the gathered global frontier
                    # values
                    wv = bvss_spmm_w_local(dev.masks[ids],
                                           dev.virtual_to_real[ids], xg,
                                           sigma=sigma, impl=spmm_w)
                    acc = acc.at[rows].add(wv.reshape(-1, S))
                return levels, acc

            acc = jnp.zeros((rps + 1, S), jnp.float32) if track_sigma \
                else None
            levels, acc = pull_chunks(st.Q, chunk_count(st.count), chunk,
                                      (st.levels, acc))
            if not track_sigma:
                return st._replace(levels=levels)
            # only rows the Boolean counts discovered THIS level take the
            # sum, so a converged column's stale values contribute nothing
            newly = levels[:rps] == cand
            return st._replace(
                levels=levels,
                paths=jnp.where(newly, acc[:rps], st.paths))

        def push_update(st: MSState) -> MSState:
            """Batched push level (DESIGN §2.8): the union vertex queue is
            compacted from the GLOBAL frontier replica (identical on every
            shard), expanded through this shard's vertex→local-VSS maps,
            and resolved by the same bit-SpMM tiles + local scatter-min."""
            VQ, _ = compact_vertices(_union_words(st.F))
            ids = expand_push_queue(dev, VQ, R, p.num_vss)
            vrep = jnp.broadcast_to(VQ[:, None], (pqcap, R)).reshape(-1)
            fb = _push_fbytes(st.F, vrep, sigma)
            counts = spmm(dev.masks[ids], fb, sigma=sigma)
            rows = dev.row_ids[ids].reshape(-1)   # LOCAL rows, dummy = rps
            cand = (st.col_lvl + 1)[None, :]
            upd = jnp.where(counts.reshape(-1, S) > 0, cand, INF
                            ).astype(jnp.int32)
            return st._replace(levels=st.levels.at[rows].min(upd))

        def step(st: MSState) -> MSState:
            if track_sigma:
                # the one extra cross-device term of the float channel:
                # all-gather the σ-frontier values (rows at depth col_lvl),
                # mirroring finalize's frontier-word gather — BEFORE the
                # direction cond and the chunk loop (no collectives in
                # either)
                xv = jnp.where(st.levels[:rps] == st.col_lvl[None, :],
                               st.paths, 0.0)
                xg = jax.lax.all_gather(xv, axis, tiled=True)  # (n_pad, S)
            else:
                xg = None

            if pull_only:
                return pull_update(st, xg)
            if direction == "push":
                return push_update(st)
            ucount = jnp.sum(jax.lax.population_count(
                _union_words(st.F))).astype(jnp.int32)
            tbits = jnp.sum(jax.lax.population_count(st.F)
                            ).astype(jnp.float32)
            # unvisited is mesh-global (levels are local row blocks); the
            # psum runs on every shard BEFORE the branch, so the cond
            # bodies stay collective-free even if the pull-cost term
            # diverges
            unvisited = jax.lax.psum(
                jnp.sum(st.levels[:rps] == INF), axis).astype(jnp.float32)
            use_push = ((ucount <= pcap)
                        & (jnp.int32(push_cost) < chunk_ceil(st.count))
                        & (tbits * jnp.float32(alpha) <= unvisited))
            return jax.lax.cond(use_push, push_update,
                                lambda s: pull_update(s, xg), st)

        def requeue(st: MSState) -> MSState:
            # F is already the global replica: no gather needed here
            set_active = (_frontier_bytes(st.F, all_sets, sigma) != 0
                          ).any(axis=1)
            Q, count = compact(set_active)
            return st._replace(Q=Q, count=count,
                               cont=global_any(count > 0, axis))

        def finalize(st: MSState) -> MSState:
            nxt = (st.col_lvl + 1)[None, :]
            new = st.levels[:rps] == nxt                     # (rps, S)
            fw = jnp.sum(new.reshape(lwords, 32, S).astype(jnp.uint32)
                         * weights[None, :, None], axis=1, dtype=jnp.uint32)
            advanced = global_any(new.any(axis=0), axis)     # (S,)
            # the one cross-device term per level: refresh every shard's
            # global frontier replica from the per-shard new words
            F = gather(fw, axis)                             # (n_fwords, S)
            st = st._replace(F=F, col_lvl=st.col_lvl + advanced)
            return requeue(st)

        def _seed_paths(paths: jnp.ndarray, lsrc: jnp.ndarray,
                        cols: jnp.ndarray, own: jnp.ndarray) -> jnp.ndarray:
            """Set σ(source) = 1 on the owning shard: ``paths`` has no
            dummy row, so non-owned writes clamp to a real row and write
            back the old value (a no-op)."""
            row = jnp.clip(lsrc, 0, rps - 1)
            return paths.at[row, cols].set(
                jnp.where(own, 1.0, paths[row, cols]))

        def init(sources: jnp.ndarray) -> MSState:
            d = jax.lax.axis_index(axis)
            cols = jnp.arange(S)
            lsrc = sources - d * rps
            own = (lsrc >= 0) & (lsrc < rps)
            levels = jnp.full((rps + 1, S), INF, dtype=jnp.int32)
            levels = levels.at[jnp.where(own, lsrc, rps), cols].set(
                jnp.where(own, 0, INF))
            F = jnp.zeros((p.n_fwords, S), dtype=jnp.uint32)
            F = F.at[sources // 32, cols].set(
                jnp.uint32(1) << (sources % 32).astype(jnp.uint32))
            paths = None
            if track_sigma:
                paths = _seed_paths(jnp.zeros((rps, S), jnp.float32),
                                    lsrc, cols, own)
            st = MSState(levels=levels, F=F,
                         Q=jnp.full((qcap,), p.num_vss, dtype=jnp.int32),
                         count=jnp.int32(0),
                         col_lvl=jnp.zeros((S,), dtype=jnp.int32),
                         cont=jnp.bool_(False), paths=paths)
            return requeue(st)

        def insert(st: MSState, slot, src) -> MSState:
            d = jax.lax.axis_index(axis)
            slot = jnp.asarray(slot, dtype=jnp.int32)
            src = jnp.asarray(src, dtype=jnp.int32)
            lsrc = src - d * rps
            own = (lsrc >= 0) & (lsrc < rps)
            levels = st.levels.at[:, slot].set(INF)
            levels = levels.at[jnp.where(own, lsrc, rps), slot].set(
                jnp.where(own, 0, INF))
            # F is the global replica: every shard sets the same bit
            F = st.F.at[:, slot].set(jnp.uint32(0))
            F = F.at[src // 32, slot].set(
                jnp.uint32(1) << (src % 32).astype(jnp.uint32))
            paths = st.paths
            if track_sigma:
                paths = _seed_paths(paths.at[:, slot].set(0.0),
                                    lsrc, slot, own)
            return st._replace(levels=levels, F=F, paths=paths,
                               col_lvl=st.col_lvl.at[slot].set(0))

        def insert_batch(st: MSState, srcs, mask) -> MSState:
            d = jax.lax.axis_index(axis)
            cols = jnp.arange(S)
            lsrc = srcs - d * rps
            own = mask & (lsrc >= 0) & (lsrc < rps)
            rows = jnp.where(own, lsrc, rps)    # non-owned -> dummy row
            levels = jnp.where(mask[None, :], INF, st.levels)
            levels = levels.at[rows, cols].set(
                jnp.where(own, 0, levels[rows, cols]))
            F = jnp.where(mask[None, :], jnp.uint32(0), st.F)
            bit = jnp.uint32(1) << (srcs % 32).astype(jnp.uint32)
            F = F.at[srcs // 32, cols].set(
                jnp.where(mask, bit, F[srcs // 32, cols]))
            paths = st.paths
            if track_sigma:
                paths = _seed_paths(jnp.where(mask[None, :], 0.0, paths),
                                    lsrc, cols, own)
            st = st._replace(levels=levels, F=F, paths=paths,
                             col_lvl=jnp.where(mask, 0, st.col_lvl))
            return requeue(st)

        return _MSLocals(init=init, insert=insert,
                         insert_batch=insert_batch, requeue=requeue,
                         step=step, finalize=finalize)

    return locals_for


def _make_ms_locals_2d(p: BlestProblem, S: int, spmm, qcap: int, *,
                       spmm_w=None,
                       track_sigma: bool = False,
                       gather: Callable | None = None,
                       direction: str = "pull") -> Callable:
    """2-D (row × column) per-device wave ops (DESIGN §2.4): device (i, j)
    holds LOCAL levels (rps+1, S) for row block i, a COLUMN-BLOCK frontier
    ``F`` of (n_fwords, S) words (colblock j's offsets of EVERY row block,
    interleaved layout — this is the only frontier the device ever pulls,
    1/cols of the global words), one union queue over its (i, j) BVSS
    block, and — with ``track_sigma`` — a LOCAL (rps, S) σ block.

    The level step swaps the 1-D eager scatter-min for mark-accumulate:
    each device's pull covers only colblock j of the frontier, so its
    partial hits must be OR-combined ACROSS the column axis (butterfly
    OR-allreduce of the per-column packed hit words) before any level may
    commit — an eager local scatter-min would assign levels off partial
    evidence.  ``finalize`` then packs the newly array, keeps only this
    device's column segment of it, and butterfly-exchanges the segments
    over the ROW axis to rebuild next level's column-block frontier.  The
    σ partial sums ride a float ``psum`` over the column axis, after the
    chunk loop (each device loops over its own live count, and a
    collective inside a device-varying loop wedges the mesh), and the
    σ-frontier values are butterfly-gathered over the row axis exactly
    like the frontier words, before it.

    The 2-D partition is PULL-ONLY: the push formulation writes to remote
    row blocks, which the column-partitioned frontier cannot express
    without a second scatter collective.  ``direction="auto"`` silently
    resolves to pull; a forced ``"push"`` raises
    :class:`~repro.errors.ConfigError`.  ``gather`` is the same fault seam
    as the 1-D engines — it wraps the ROW-axis frontier-segment exchange
    (default :func:`~repro.distributed.collectives.
    butterfly_frontier_exchange`)."""
    if direction == "push":
        raise ConfigError(
            "the 2-D row × column partition is pull-only: push writes to "
            "remote row blocks, which the column-partitioned frontier "
            "cannot express; use direction='pull' or 'auto', or a 1-D mesh")
    rax, cax = p.axis, p.col_axis
    sigma = p.sigma
    rps = p.rows_per_shard
    cpb = p.cols_per_block
    C = p.n_col_shards
    lwords = rps // 32          # packed words covering one row block
    wpc = lwords // C           # words per column segment of a row block
    ncw = p.n_fwords            # column-block frontier words = R·cpb/32
    n_loc = ncw * 32            # local column space = R·cpb
    n_cols = p.n_sets * sigma   # padded pull-operand columns (≥ n_loc)
    all_sets = jnp.arange(p.n_sets, dtype=jnp.int32)
    if gather is None:
        gather = butterfly_frontier_exchange

    def locals_for(dev: ShardedBVSSDevice) -> _MSLocals:
        compact = make_compactor(dev, p.num_vss, qcap)

        def pull_partial(st: MSState, xg: jnp.ndarray | None):
            """The chunked pull over block (i, j): returns the PARTIAL
            per-column hit marks (rps+1, S) — row rps is the dummy sink —
            plus the partial σ accumulator; nothing is committed until
            the cross-column reduce."""
            def chunk(i, ids, carry):
                hit, acc = carry
                fb = _frontier_bytes(st.F, dev.virtual_to_real[ids], sigma)
                counts = spmm(dev.masks[ids], fb, sigma=sigma)
                rows = dev.row_ids[ids].reshape(-1)  # LOCAL, dummy = rps
                hit = hit.at[rows].max(counts.reshape(-1, S) > 0)
                if track_sigma:
                    wv = bvss_spmm_w_local(dev.masks[ids],
                                           dev.virtual_to_real[ids], xg,
                                           sigma=sigma, impl=spmm_w)
                    acc = acc.at[rows].add(wv.reshape(-1, S))
                return hit, acc

            acc = jnp.zeros((rps + 1, S), jnp.float32) if track_sigma \
                else None
            return pull_chunks(st.Q, chunk_count(st.count), chunk,
                               (jnp.zeros((rps + 1, S), dtype=bool), acc))

        def step(st: MSState) -> MSState:
            j = jax.lax.axis_index(cax)
            if track_sigma:
                # σ-frontier values: this device contributes its column
                # segment of its row block's values, butterfly-gathered
                # over the ROW axis into the (n_loc, S) pull operand —
                # the float twin of finalize's frontier-word exchange,
                # hoisted BEFORE the chunk loop
                xv = jnp.where(st.levels[:rps] == st.col_lvl[None, :],
                               st.paths, 0.0)
                seg = jax.lax.dynamic_slice_in_dim(xv, j * cpb, cpb, axis=0)
                xg = butterfly_frontier_exchange(seg, rax)    # (n_loc, S)
                if n_cols > n_loc:
                    xg = jnp.concatenate(
                        [xg, jnp.zeros((n_cols - n_loc, S), jnp.float32)])
            else:
                xg = None
            hit, acc = pull_partial(st, xg)
            # cross-column combine: pack the partial hits per column, OR
            # them across the column axis, and only then commit levels —
            # every device in mesh row i sees the SAME full-row-block hits
            hw = butterfly_or_allreduce(_pack_cols(hit[:rps], lwords), cax)
            hit_full = _unpack_cols(hw)                       # (rps, S)
            cand = (st.col_lvl + 1)[None, :]
            newly = hit_full & (st.levels[:rps] == INF)
            levels = st.levels.at[:rps].set(
                jnp.where(newly, cand, st.levels[:rps]))
            if not track_sigma:
                return st._replace(levels=levels)
            accf = jax.lax.psum(acc[:rps], cax)
            return st._replace(
                levels=levels,
                paths=jnp.where(newly, accf, st.paths))

        def requeue(st: MSState) -> MSState:
            set_active = (_frontier_bytes(st.F, all_sets, sigma) != 0
                          ).any(axis=1)
            Q, count = compact(set_active)
            return st._replace(Q=Q, count=count,
                               cont=global_any(count > 0, (rax, cax)))

        def finalize(st: MSState) -> MSState:
            j = jax.lax.axis_index(cax)
            nxt = (st.col_lvl + 1)[None, :]
            new = st.levels[:rps] == nxt                      # (rps, S)
            fw = _pack_cols(new, lwords)                      # (lwords, S)
            advanced = global_any(new.any(axis=0), (rax, cax))
            # next level's column-block frontier: keep this device's
            # column segment of its row block's new words and butterfly-
            # exchange the segments over the ROW axis (the fault seam)
            seg = jax.lax.dynamic_slice_in_dim(fw, j * wpc, wpc, axis=0)
            F = gather(seg, rax)                              # (ncw, S)
            st = st._replace(F=F, col_lvl=st.col_lvl + advanced)
            return requeue(st)

        def _fseed(F: jnp.ndarray, srcs, cols, mask):
            """Seed frontier bits for masked slots in the COLUMN-BLOCK
            layout: only the mesh column owning each source's offset sets
            its bit (clamped no-op writes elsewhere)."""
            j = jax.lax.axis_index(cax)
            off = srcs % rps
            ownc = mask & ((off // cpb) == j)
            c = jnp.clip((srcs // rps) * cpb + (off - j * cpb),
                         0, n_loc - 1)
            bit = jnp.uint32(1) << (c % 32).astype(jnp.uint32)
            return F.at[c // 32, cols].set(
                jnp.where(ownc, bit, F[c // 32, cols]))

        def _seed_paths(paths: jnp.ndarray, lsrc, cols, own):
            row = jnp.clip(lsrc, 0, rps - 1)
            return paths.at[row, cols].set(
                jnp.where(own, 1.0, paths[row, cols]))

        def init(sources: jnp.ndarray) -> MSState:
            i = jax.lax.axis_index(rax)
            cols = jnp.arange(S)
            lsrc = sources - i * rps
            own = (lsrc >= 0) & (lsrc < rps)
            levels = jnp.full((rps + 1, S), INF, dtype=jnp.int32)
            levels = levels.at[jnp.where(own, lsrc, rps), cols].set(
                jnp.where(own, 0, INF))
            F = _fseed(jnp.zeros((ncw, S), dtype=jnp.uint32), sources,
                       cols, jnp.ones((S,), dtype=bool))
            paths = None
            if track_sigma:
                paths = _seed_paths(jnp.zeros((rps, S), jnp.float32),
                                    lsrc, cols, own)
            st = MSState(levels=levels, F=F,
                         Q=jnp.full((qcap,), p.num_vss, dtype=jnp.int32),
                         count=jnp.int32(0),
                         col_lvl=jnp.zeros((S,), dtype=jnp.int32),
                         cont=jnp.bool_(False), paths=paths)
            return requeue(st)

        def insert(st: MSState, slot, src) -> MSState:
            i = jax.lax.axis_index(rax)
            slot = jnp.asarray(slot, dtype=jnp.int32)
            src = jnp.asarray(src, dtype=jnp.int32)
            lsrc = src - i * rps
            own = (lsrc >= 0) & (lsrc < rps)
            levels = st.levels.at[:, slot].set(INF)
            levels = levels.at[jnp.where(own, lsrc, rps), slot].set(
                jnp.where(own, 0, INF))
            F = _fseed(st.F.at[:, slot].set(jnp.uint32(0)), src, slot,
                       jnp.bool_(True))
            paths = st.paths
            if track_sigma:
                paths = _seed_paths(paths.at[:, slot].set(0.0),
                                    lsrc, slot, own)
            return st._replace(levels=levels, F=F, paths=paths,
                               col_lvl=st.col_lvl.at[slot].set(0))

        def insert_batch(st: MSState, srcs, mask) -> MSState:
            i = jax.lax.axis_index(rax)
            cols = jnp.arange(S)
            lsrc = srcs - i * rps
            own = mask & (lsrc >= 0) & (lsrc < rps)
            rows = jnp.where(own, lsrc, rps)
            levels = jnp.where(mask[None, :], INF, st.levels)
            levels = levels.at[rows, cols].set(
                jnp.where(own, 0, levels[rows, cols]))
            F = _fseed(jnp.where(mask[None, :], jnp.uint32(0), st.F),
                       srcs, cols, mask)
            paths = st.paths
            if track_sigma:
                paths = _seed_paths(jnp.where(mask[None, :], 0.0, paths),
                                    lsrc, cols, own)
            st = st._replace(levels=levels, F=F, paths=paths,
                             col_lvl=jnp.where(mask, 0, st.col_lvl))
            return requeue(st)

        return _MSLocals(init=init, insert=insert,
                         insert_batch=insert_batch, requeue=requeue,
                         step=step, finalize=finalize)

    return locals_for


def _make_ms_engine_sharded(p: BlestProblem, n_slots: int, *, spmm,
                            spmm_w=None,
                            track_sigma: bool = False,
                            gather: Callable | None = None,
                            direction: str = "auto",
                            push_cap: int | None = None,
                            alpha: float = 4.0) -> MSEngine:
    """Host-driven wave surface over the shard_map'd local ops: every state
    field gains a leading shard axis; each public fn is one jitted
    shard_map dispatch."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs, state_specs

    mesh, axis = p.mesh, p.axis
    D, rps = p.n_shards, p.rows_per_shard
    S = n_slots
    qcap = queue_capacity(p.num_vss)
    locals_for = _make_ms_locals(p, S, spmm, qcap, spmm_w=spmm_w,
                                 track_sigma=track_sigma, gather=gather,
                                 direction=direction, push_cap=push_cap,
                                 alpha=alpha)

    state_spec = state_specs(axis, track_sigma=track_sigma)
    dev_specs = problem_specs(axis)
    dev_args = (p.dev.masks, p.dev.row_ids, p.dev.virtual_to_real,
                p.dev.vss_of_vertex_start, p.dev.vss_of_vertex_end)

    def _dev(masks, row_ids, v2r, vstart, vend) -> ShardedBVSSDevice:
        return ShardedBVSSDevice(masks[0], row_ids[0], v2r[0],
                                 vstart[0], vend[0])

    def _unstack(st: MSState) -> MSState:
        return jax.tree_util.tree_map(lambda x: x[0], st)

    def _stack(st: MSState) -> MSState:
        return jax.tree_util.tree_map(lambda x: x[None], st)

    def sm(f, in_specs, out_specs):
        fn = jax.shard_map(f, mesh=mesh, in_specs=dev_specs + in_specs,
                           out_specs=out_specs, check_vma=False)
        return lambda *args: fn(*dev_args, *args)

    def _init(masks, row_ids, v2r, vstart, vend, sources):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        return _stack(loc.init(sources))

    def _insert(masks, row_ids, v2r, vstart, vend, st, slot, src):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        return _stack(loc.insert(_unstack(st), slot, src))

    def _insert_batch(masks, row_ids, v2r, vstart, vend, st, srcs, mask):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        return _stack(loc.insert_batch(_unstack(st), srcs, mask))

    def _requeue(masks, row_ids, v2r, vstart, vend, st):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        return _stack(loc.requeue(_unstack(st)))

    def _level_step(masks, row_ids, v2r, vstart, vend, st):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        st = loc.finalize(loc.step(_unstack(st)))
        return _stack(st), (st.F != 0).any(axis=0)[None]

    init_sm = sm(_init, (P(),), state_spec)
    insert_sm = sm(_insert, (state_spec, P(), P()), state_spec)
    insert_batch_sm = sm(_insert_batch, (state_spec, P(), P()), state_spec)
    requeue_sm = sm(_requeue, (state_spec,), state_spec)
    level_sm = sm(_level_step, (state_spec,), (state_spec, P(axis)))

    def idle() -> MSState:
        def sh(a):
            return jax.device_put(a, NamedSharding(mesh, P(axis)))
        return MSState(
            levels=sh(np.full((D, rps + 1, S), INF, np.int32)),
            F=sh(np.zeros((D, p.n_fwords, S), np.uint32)),
            Q=sh(np.full((D, qcap), p.num_vss, np.int32)),
            count=sh(np.zeros((D,), np.int32)),
            col_lvl=sh(np.zeros((D, S), np.int32)),
            cont=sh(np.zeros((D,), bool)),
            paths=sh(np.zeros((D, rps, S), np.float32))
            if track_sigma else None)

    def level_step(st: MSState) -> tuple[MSState, jnp.ndarray]:
        st, live = level_sm(st)
        return st, live[0]

    def levels_of(st: MSState, slot) -> jnp.ndarray:
        # slice the column first: moves one (n,) column, not (n, S)
        return st.levels[:, :rps, slot].reshape(-1)[:p.n]

    def paths_of(st: MSState, slot) -> jnp.ndarray:
        return st.paths[:, :, slot].reshape(-1)[:p.n]

    return MSEngine(
        problem=p, n_slots=S,
        init=jax.jit(lambda sources: init_sm(
            jnp.asarray(sources, dtype=jnp.int32))),
        idle=idle,
        insert=jax.jit(lambda st, slot, src: insert_sm(st, slot, src)),
        insert_batch=jax.jit(
            lambda st, srcs, mask: insert_batch_sm(st, srcs, mask)),
        requeue=jax.jit(requeue_sm),
        step=None, finalize=None,   # fused via make_multi_source_bfs
        level_step=jax.jit(level_step),
        col_live=jax.jit(lambda st: (st.F[0] != 0).any(axis=0)),
        levels_of=levels_of,
        paths_of=paths_of if track_sigma else None)


def _make_ms_engine_sharded_2d(p: BlestProblem, n_slots: int, *, spmm,
                               spmm_w=None,
                               track_sigma: bool = False,
                               gather: Callable | None = None,
                               direction: str = "auto") -> MSEngine:
    """The 2-D twin of :func:`_make_ms_engine_sharded`: same host surface,
    R·C device blocks stacked row-major on every leading dim (block
    d = i·C + j), specs from ``state_specs2d``/``problem_specs2d``.  The
    host-visible extraction helpers read mesh column 0's replicas
    (``[::C]``) — levels and σ are column-replicated per row block — and
    ``col_live`` ORs the frontier words over ALL blocks (each holds only
    its column segment)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs2d, state_specs2d

    mesh = p.mesh
    rax, cax = p.axis, p.col_axis
    R, C, rps = p.n_shards, p.n_col_shards, p.rows_per_shard
    D = R * C
    S = n_slots
    qcap = queue_capacity(p.num_vss)
    locals_for = _make_ms_locals_2d(p, S, spmm, qcap, spmm_w=spmm_w,
                                    track_sigma=track_sigma, gather=gather,
                                    direction=direction)

    state_spec = state_specs2d(rax, cax, track_sigma=track_sigma)
    dev_specs = problem_specs2d(rax, cax)
    dev_args = (p.dev.masks, p.dev.row_ids, p.dev.virtual_to_real,
                p.dev.vss_of_vertex_start, p.dev.vss_of_vertex_end)

    def _dev(masks, row_ids, v2r, vstart, vend) -> ShardedBVSSDevice:
        return ShardedBVSSDevice(masks[0], row_ids[0], v2r[0],
                                 vstart[0], vend[0])

    def _unstack(st: MSState) -> MSState:
        return jax.tree_util.tree_map(lambda x: x[0], st)

    def _stack(st: MSState) -> MSState:
        return jax.tree_util.tree_map(lambda x: x[None], st)

    def sm(f, in_specs, out_specs):
        fn = jax.shard_map(f, mesh=mesh, in_specs=dev_specs + in_specs,
                           out_specs=out_specs, check_vma=False)
        return lambda *args: fn(*dev_args, *args)

    def _init(masks, row_ids, v2r, vstart, vend, sources):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        return _stack(loc.init(sources))

    def _insert(masks, row_ids, v2r, vstart, vend, st, slot, src):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        return _stack(loc.insert(_unstack(st), slot, src))

    def _insert_batch(masks, row_ids, v2r, vstart, vend, st, srcs, mask):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        return _stack(loc.insert_batch(_unstack(st), srcs, mask))

    def _requeue(masks, row_ids, v2r, vstart, vend, st):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        return _stack(loc.requeue(_unstack(st)))

    def _level_step(masks, row_ids, v2r, vstart, vend, st):
        loc = locals_for(_dev(masks, row_ids, v2r, vstart, vend))
        st = loc.finalize(loc.step(_unstack(st)))
        # each block sees only its column segment: make per-slot liveness
        # globally consistent before it reaches the host serving loop
        live = global_any((st.F != 0).any(axis=0), (rax, cax))
        return _stack(st), live[None]

    init_sm = sm(_init, (P(),), state_spec)
    insert_sm = sm(_insert, (state_spec, P(), P()), state_spec)
    insert_batch_sm = sm(_insert_batch, (state_spec, P(), P()), state_spec)
    requeue_sm = sm(_requeue, (state_spec,), state_spec)
    level_sm = sm(_level_step, (state_spec,), (state_spec, P((rax, cax))))

    def idle() -> MSState:
        def sh(a):
            return jax.device_put(a, NamedSharding(mesh, P((rax, cax))))
        return MSState(
            levels=sh(np.full((D, rps + 1, S), INF, np.int32)),
            F=sh(np.zeros((D, p.n_fwords, S), np.uint32)),
            Q=sh(np.full((D, qcap), p.num_vss, np.int32)),
            count=sh(np.zeros((D,), np.int32)),
            col_lvl=sh(np.zeros((D, S), np.int32)),
            cont=sh(np.zeros((D,), bool)),
            paths=sh(np.zeros((D, rps, S), np.float32))
            if track_sigma else None)

    def level_step(st: MSState) -> tuple[MSState, jnp.ndarray]:
        st, live = level_sm(st)
        return st, live[0]

    def levels_of(st: MSState, slot) -> jnp.ndarray:
        # mesh column 0's replicas of every row block, one (n,) column
        return st.levels[::C, :rps, slot].reshape(-1)[:p.n]

    def paths_of(st: MSState, slot) -> jnp.ndarray:
        return st.paths[::C, :, slot].reshape(-1)[:p.n]

    return MSEngine(
        problem=p, n_slots=S,
        init=jax.jit(lambda sources: init_sm(
            jnp.asarray(sources, dtype=jnp.int32))),
        idle=idle,
        insert=jax.jit(lambda st, slot, src: insert_sm(st, slot, src)),
        insert_batch=jax.jit(
            lambda st, srcs, mask: insert_batch_sm(st, srcs, mask)),
        requeue=jax.jit(requeue_sm),
        step=None, finalize=None,   # fused via make_multi_source_bfs
        level_step=jax.jit(level_step),
        col_live=jax.jit(lambda st: (st.F != 0).any(axis=(0, 1))),
        levels_of=levels_of,
        paths_of=paths_of if track_sigma else None)


def make_multi_source_bfs(g: Graph | None, n_sources: int, *,
                          use_kernel: bool = True,
                          max_levels: int | None = None,
                          bvss=None, problem: BlestProblem | None = None,
                          direction: str = "auto",
                          push_cap: int | None = None,
                          alpha: float = 4.0) -> Callable:
    """Build jitted ``f(sources (S,) i32) -> levels (n, S) i32`` with the
    whole level loop fused on device (fixed source cohort).  A sharded
    ``problem`` runs the loop as one ``shard_map``'d ``while_loop``.
    ``direction`` / ``push_cap`` / ``alpha`` are the direction-optimizing
    knobs (DESIGN §2.8; see :func:`make_ms_engine`)."""
    if problem is None:
        if bvss is None:
            from repro.core.bvss import build_bvss
            bvss = build_bvss(g)
        problem = BlestProblem.build(bvss)
    max_lv = max_levels if max_levels is not None else problem.n + 1
    if problem.mesh is not None:
        if problem.is_2d:
            return _make_multi_source_bfs_sharded_2d(
                problem, n_sources, use_kernel=use_kernel, max_lv=max_lv,
                direction=direction)
        return _make_multi_source_bfs_sharded(
            problem, n_sources, use_kernel=use_kernel, max_lv=max_lv,
            direction=direction, push_cap=push_cap, alpha=alpha)
    eng = make_ms_engine(problem, n_sources, use_kernel=use_kernel,
                         direction=direction, push_cap=push_cap,
                         alpha=alpha)
    step, finalize = eng.step, eng.finalize
    assert step is not None and finalize is not None
    pipe = LevelPipeline(step=lambda s, lvl: step(s),
                         finalize=lambda s, lvl: finalize(s),
                         active=lambda s: s.cont)

    def bfs(sources: jnp.ndarray) -> jnp.ndarray:
        state, _ = run_levels(pipe, eng.init(sources), max_levels=max_lv)
        return state.levels[:problem.n]

    return jax.jit(bfs)


def _make_multi_source_bfs_sharded(p: BlestProblem, n_sources: int, *,
                                   use_kernel: bool, max_lv: int,
                                   direction: str = "auto",
                                   push_cap: int | None = None,
                                   alpha: float = 4.0) -> Callable:
    """Fixed-cohort multi-source over the mesh: the SAME local step/finalize
    as the serving surface, with the whole level loop inside one
    ``shard_map``'d ``while_loop`` (no host sync, paper §4.3)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs

    mesh, axis = p.mesh, p.axis
    rps = p.rows_per_shard
    S = n_sources
    spmm = bvss_spmm if use_kernel else bvss_spmm_ref
    locals_for = _make_ms_locals(p, S, spmm, queue_capacity(p.num_vss),
                                 direction=direction, push_cap=push_cap,
                                 alpha=alpha)

    def local_loop(masks, row_ids, v2r, vstart, vend, sources):
        loc = locals_for(ShardedBVSSDevice(masks[0], row_ids[0], v2r[0],
                                           vstart[0], vend[0]))
        pipe = LevelPipeline(step=lambda s, lvl: loc.step(s),
                             finalize=lambda s, lvl: loc.finalize(s),
                             active=lambda s: s.cont)
        state, _ = run_levels(pipe, loc.init(sources), max_levels=max_lv)
        return state.levels[None, :rps]

    fn = jax.shard_map(local_loop, mesh=mesh,
                       in_specs=problem_specs(axis) + (P(),),
                       out_specs=P(axis), check_vma=False)

    def bfs(sources: jnp.ndarray) -> jnp.ndarray:
        out = fn(p.dev.masks, p.dev.row_ids, p.dev.virtual_to_real,
                 p.dev.vss_of_vertex_start, p.dev.vss_of_vertex_end,
                 jnp.asarray(sources, dtype=jnp.int32))
        return out.reshape(-1, S)[:p.n]

    return jax.jit(bfs)


def _make_multi_source_bfs_sharded_2d(p: BlestProblem, n_sources: int, *,
                                      use_kernel: bool, max_lv: int,
                                      direction: str = "auto") -> Callable:
    """Fixed-cohort multi-source on the 2-D mesh: the same 2-D local
    step/finalize as the serving surface, fused into one ``shard_map``'d
    ``while_loop`` (butterfly exchanges INSIDE the loop body — no host
    sync across levels, paper §4.3)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs2d

    mesh = p.mesh
    rax, cax = p.axis, p.col_axis
    R, C, rps = p.n_shards, p.n_col_shards, p.rows_per_shard
    S = n_sources
    spmm = bvss_spmm if use_kernel else bvss_spmm_ref
    locals_for = _make_ms_locals_2d(p, S, spmm, queue_capacity(p.num_vss),
                                    direction=direction)

    def local_loop(masks, row_ids, v2r, vstart, vend, sources):
        loc = locals_for(ShardedBVSSDevice(masks[0], row_ids[0], v2r[0],
                                           vstart[0], vend[0]))
        pipe = LevelPipeline(step=lambda s, lvl: loc.step(s),
                             finalize=lambda s, lvl: loc.finalize(s),
                             active=lambda s: s.cont)
        state, _ = run_levels(pipe, loc.init(sources), max_levels=max_lv)
        return state.levels[None, :rps]

    fn = jax.shard_map(local_loop, mesh=mesh,
                       in_specs=problem_specs2d(rax, cax) + (P(),),
                       out_specs=P((rax, cax)), check_vma=False)

    def bfs(sources: jnp.ndarray) -> jnp.ndarray:
        out = fn(p.dev.masks, p.dev.row_ids, p.dev.virtual_to_real,
                 p.dev.vss_of_vertex_start, p.dev.vss_of_vertex_end,
                 jnp.asarray(sources, dtype=jnp.int32))
        # (R·C, rps, S) blocks row-major: mesh column 0 holds the replicas
        return out.reshape(R, C, rps, S)[:, 0].reshape(-1, S)[:p.n]

    return jax.jit(bfs)

# closeness centrality (paper §7's target application for multi-source
# BFS) lives in ``repro.analytics.closeness`` since PR 5: it is a wave
# CLIENT — a reduction over the level channels this module produces —
# not part of the wave machinery itself.
