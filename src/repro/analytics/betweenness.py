"""Brandes betweenness centrality on the BVSS wave engine (DESIGN §2.6).

Brandes' algorithm per source s:

    forward   BFS from s recording levels d(v) and σ(v) shortest-path
              counts: σ(v) = Σ_{u ∈ pred(v)} σ(u);
    backward  dependency accumulation in decreasing level order:
              δ(v) = σ(v) · Σ_{w ∈ succ(v), d(w)=d(v)+1} (1 + δ(w)) / σ(w);
    bc(v)    += δ(v)  for v ≠ s.

Both phases are wave clients here, batched over S stacked sources:

* The FORWARD phase is the fused multi-source BFS with the σ channel
  threaded through the widened wave state
  (``make_ms_engine(track_sigma=True)``): each level runs the Boolean
  bit-SpMM pull (discovery) plus its weighted twin ``bvss_spmm_w`` over
  the SAME queued tiles (σ propagation), and records the per-level VSS
  queue into a :class:`~repro.core.bfs.QueueHistory`
  (``run_levels_recorded``) — one on-device ``while_loop``, no host sync.

* The BACKWARD phase replays that history in reverse: at level t the
  per-row values h(w) = [d(w)=t] · (1+δ(w))/σ(w) are gathered through
  ``row_ids`` and contracted by the *transposed* tile product
  ``bvss_spmm_t`` — the same BVSS tiles, contracted along the row axis
  instead of the column axis — then scattered into the slice-set columns
  and folded into δ at level t-1.  The recorded level-t queue is exactly
  the tile set whose columns meet the level-(t-1) frontier, so the
  reverse sweep is frontier-aware, not a full-BVSS sweep.

σ and δ are float32 (the MXU-native analytics dtype): path counts on the
benchmark families stay far below float32's 2^24 exact-integer range, and
the acceptance contract is oracle agreement within fp tolerance
(``kernels.ref.betweenness_ref``).

MESH-NATIVE (DESIGN §2.4/§2.6): on a row-sharded problem both phases run
under ``shard_map`` with ZERO replicated weighted sweeps.  Forward: the σ
channel rides the generic sharded float path of ``core.multi_source`` —
``paths`` and δ live as local ``(rps, S)`` row blocks, each level's
weighted pull consumes the per-level all-gather of the σ-frontier values,
and each shard records its OWN per-level queue history (the shard axis of
``QueueHistory``).  Backward: every shard replays its local history —
``h`` is built from local levels/σ/δ, contracted by ``bvss_spmm_t_local``
over the shard's tiles — and the column scatter is reduced across shards
with one ``lax.psum_scatter`` per level (a shard only sees the dependency
flowing through its own rows; the reduce-scatter hands each shard
exactly its row block of the global coefficient).
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analytics.common import pad_cohort
from repro.core.bfs import (BlestProblem, QueueHistory, make_queue_history,
                            queue_capacity)
from repro.core.bvss import ShardedBVSSDevice
from repro.core.level_pipeline import LevelPipeline, run_levels_recorded
from repro.core.multi_source import (INF, _make_ms_locals,
                                     _make_ms_locals_2d, make_ms_engine)
from repro.graphs import Graph
from repro.kernels import bvss_spmm, bvss_spmm_t, bvss_spmm_t_local, bvss_spmm_w
from repro.kernels.ref import bvss_spmm_ref, bvss_spmm_t_ref, bvss_spmm_w_ref


def make_betweenness(problem: BlestProblem, n_sources: int, *,
                     use_kernel: bool = True,
                     max_levels: int | None = None,
                     spmm_w_impl: Callable | None = None) -> Callable:
    """Build jitted ``f(sources (S,) i32) -> (levels (n,S), sigma (n,S),
    delta (n,S))`` running both Brandes phases on device — under
    ``shard_map`` when ``problem`` is row-sharded (outputs stay global).

    ``delta[:, j]`` is the dependency of every vertex on source ``j``
    (endpoints excluded: the source row is zeroed), so a caller sums
    columns over its source set to get partial betweenness.  ``max_levels``
    bounds the recorded history buffer ((max_levels+1) × qcap int32 —
    default n+1 is fine at lab scale, pass the graph's diameter bound to
    shrink it).  ``spmm_w_impl`` overrides the weighted tile product —
    the σ-channel fault seam (DESIGN §2.7).
    """
    p = problem
    if p.mesh is not None:
        if p.is_2d:
            return _make_betweenness_sharded_2d(p, n_sources,
                                                use_kernel=use_kernel,
                                                max_levels=max_levels,
                                                spmm_w_impl=spmm_w_impl)
        return _make_betweenness_sharded(p, n_sources,
                                         use_kernel=use_kernel,
                                         max_levels=max_levels,
                                         spmm_w_impl=spmm_w_impl)
    S = n_sources
    n, sigma = p.n, p.sigma
    dev = p.dev
    eng = make_ms_engine(p, S, use_kernel=use_kernel, track_sigma=True,
                         spmm_w_impl=spmm_w_impl)
    spmm_t = bvss_spmm_t if use_kernel else bvss_spmm_t_ref
    qcap = queue_capacity(p.num_vss)
    max_lv = max_levels if max_levels is not None else n + 1
    n_cols = p.n_sets * sigma
    hist0, record = make_queue_history(qcap, max_lv, p.num_vss)
    fwd_step, fwd_finalize = eng.step, eng.finalize
    assert fwd_step is not None and fwd_finalize is not None
    pipe = LevelPipeline(step=lambda s, lvl: fwd_step(s),
                         finalize=lambda s, lvl: fwd_finalize(s),
                         active=lambda s: s.cont)

    def backward(levels: jnp.ndarray, sig: jnp.ndarray,
                 hist: QueueHistory) -> jnp.ndarray:
        """Reverse per-level sweep over the recorded forward queues."""
        col_ids = (jnp.arange(sigma, dtype=jnp.int32)[None, :]
                   + jnp.zeros((qcap, 1), jnp.int32))

        def body(carry: tuple[jnp.ndarray, jnp.ndarray]
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
            delta, t = carry
            Q = jax.lax.dynamic_index_in_dim(hist.Q, t, keepdims=False)
            safe = jnp.maximum(sig, 1.0)
            h = jnp.where(levels == t, (1.0 + delta) / safe, 0.0)
            h = jnp.concatenate([h, jnp.zeros((1, S), jnp.float32)])
            part = bvss_spmm_t_local(dev.masks[Q], dev.row_ids[Q], h,
                                     sigma=sigma, impl=spmm_t)  # (qcap,σ,S)
            cols = dev.virtual_to_real[Q][:, None] * sigma + col_ids
            coeff = jnp.zeros((n_cols, S), jnp.float32).at[
                cols.reshape(-1)].add(part.reshape(-1, S))[:n]
            delta = delta + jnp.where(levels == t - 1, sig * coeff, 0.0)
            return delta, t - 1

        def cond(carry: tuple[jnp.ndarray, jnp.ndarray]) -> jnp.ndarray:
            return carry[1] >= 1

        delta0 = jnp.zeros((n, S), jnp.float32)
        tmax = jnp.where(levels == INF, 0, levels).max().astype(jnp.int32)
        delta, _ = jax.lax.while_loop(cond, body, (delta0, tmax))
        return delta

    def bc(sources: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
        sources = jnp.asarray(sources, dtype=jnp.int32)
        st, _, hist = run_levels_recorded(
            pipe, eng.init(sources), max_levels=max_lv, history=hist0,
            record=record)
        levels = st.levels[:n]
        delta = backward(levels, st.paths, hist)
        # endpoints excluded: a source contributes no dependency to itself
        delta = delta.at[sources, jnp.arange(S)].set(0.0)
        return levels, st.paths, delta

    return jax.jit(bc)


def _make_betweenness_sharded(p: BlestProblem, n_sources: int, *,
                              use_kernel: bool,
                              max_levels: int | None,
                              spmm_w_impl: Callable | None = None
                              ) -> Callable:
    """Mesh-native Brandes: forward σ wave AND backward dependency sweep
    inside ONE ``shard_map`` dispatch over the row partition — no
    replicated weighted sweeps anywhere.

    Per shard: the forward phase is the shared sharded σ-channel locals
    (Boolean pull + weighted twin + per-level σ-frontier all-gather)
    recording the shard's OWN per-level queue; the backward phase replays
    that local history in reverse, and the per-level column scatter —
    which only covers dependency flowing through this shard's rows — is
    reduced across the mesh by ``lax.psum_scatter`` (each shard receives
    exactly its row block of the global coefficient, so δ stays a local
    ``(rps, S)`` block throughout).  ``lax.pmax`` aligns the backward
    level countdown so the collectives stay in lock-step.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs

    mesh, axis = p.mesh, p.axis
    S = n_sources
    sigma = p.sigma
    rps = p.rows_per_shard
    n_pad = p.n_fwords * 32           # D·rps ≥ n_sets·σ: global column pad
    spmm = bvss_spmm if use_kernel else bvss_spmm_ref
    spmm_w = spmm_w_impl if spmm_w_impl is not None else \
        (bvss_spmm_w if use_kernel else bvss_spmm_w_ref)
    spmm_t = bvss_spmm_t if use_kernel else bvss_spmm_t_ref
    qcap = queue_capacity(p.num_vss)
    max_lv = max_levels if max_levels is not None else p.n + 1
    locals_for = _make_ms_locals(p, S, spmm, qcap, spmm_w=spmm_w,
                                 track_sigma=True)
    hist0, record = make_queue_history(qcap, max_lv, p.num_vss)

    def local_fn(masks: jnp.ndarray, row_ids: jnp.ndarray,
                 v2r: jnp.ndarray, vstart: jnp.ndarray, vend: jnp.ndarray,
                 sources: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        dev = ShardedBVSSDevice(masks[0], row_ids[0], v2r[0],
                                vstart[0], vend[0])
        loc = locals_for(dev)
        pipe = LevelPipeline(step=lambda s, lvl: loc.step(s),
                             finalize=lambda s, lvl: loc.finalize(s),
                             active=lambda s: s.cont)
        st, _, hist = run_levels_recorded(
            pipe, loc.init(sources), max_levels=max_lv, history=hist0,
            record=record)
        levels = st.levels[:rps]                     # (rps, S) local rows
        sig = st.paths                               # (rps, S)
        d = jax.lax.axis_index(axis)
        col_ids = (jnp.arange(sigma, dtype=jnp.int32)[None, :]
                   + jnp.zeros((qcap, 1), jnp.int32))

        def body(carry: tuple[jnp.ndarray, jnp.ndarray]
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
            delta, t = carry
            Q = jax.lax.dynamic_index_in_dim(hist.Q, t, keepdims=False)
            safe = jnp.maximum(sig, 1.0)
            h = jnp.where(levels == t, (1.0 + delta) / safe, 0.0)
            h = jnp.concatenate([h, jnp.zeros((1, S), jnp.float32)])
            part = bvss_spmm_t_local(dev.masks[Q], dev.row_ids[Q], h,
                                     sigma=sigma, impl=spmm_t)
            cols = dev.virtual_to_real[Q][:, None] * sigma + col_ids
            coeff = jnp.zeros((n_pad, S), jnp.float32).at[
                cols.reshape(-1)].add(part.reshape(-1, S))
            # the one backward collective per level: sum the per-shard
            # column partials and hand each shard its own row block
            coeff = jax.lax.psum_scatter(coeff, axis, scatter_dimension=0,
                                         tiled=True)           # (rps, S)
            delta = delta + jnp.where(levels == t - 1, sig * coeff, 0.0)
            return delta, t - 1

        def cond(carry: tuple[jnp.ndarray, jnp.ndarray]) -> jnp.ndarray:
            return carry[1] >= 1

        # the countdown start must be mesh-uniform: the while_loop body
        # carries collectives, so every shard walks the same levels
        tloc = jnp.where(levels == INF, 0, levels).max().astype(jnp.int32)
        tmax = jax.lax.pmax(tloc, axis)
        delta0 = jnp.zeros((rps, S), jnp.float32)
        delta, _ = jax.lax.while_loop(cond, body, (delta0, tmax))
        # endpoints excluded, on the owning shard only (clamped no-op
        # writes elsewhere — delta has no dummy row)
        lsrc = sources - d * rps
        own = (lsrc >= 0) & (lsrc < rps)
        row = jnp.clip(lsrc, 0, rps - 1)
        cols_s = jnp.arange(S)
        delta = delta.at[row, cols_s].set(
            jnp.where(own, 0.0, delta[row, cols_s]))
        return st.levels[None, :rps], sig[None], delta[None]

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=problem_specs(axis) + (P(),),
                       out_specs=(P(axis), P(axis), P(axis)), check_vma=False)

    def bc(sources: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
        sources = jnp.asarray(sources, dtype=jnp.int32)
        lv, sig, delta = fn(p.dev.masks, p.dev.row_ids,
                            p.dev.virtual_to_real,
                            p.dev.vss_of_vertex_start,
                            p.dev.vss_of_vertex_end, sources)
        return (lv.reshape(-1, S)[:p.n], sig.reshape(-1, S)[:p.n],
                delta.reshape(-1, S)[:p.n])

    return jax.jit(bc)


def _make_betweenness_sharded_2d(p: BlestProblem, n_sources: int, *,
                                 use_kernel: bool,
                                 max_levels: int | None,
                                 spmm_w_impl: Callable | None = None
                                 ) -> Callable:
    """Brandes on the 2-D row × column partition, one ``shard_map``
    dispatch.  Forward: the 2-D σ-channel locals (mark-accumulate pull,
    butterfly OR-allreduce of the hits over the column axis, butterfly
    σ-value gather over the row axis), each device recording its OWN
    (i, j)-block per-level queue.

    Backward per level: device (i, j)'s transposed tile product pushes
    dependency from its row block into its COLUMN block's columns only, so
    the global coefficient at a colblock-j column is the row-axis ``psum``
    of the (·, j) devices' partials.  ``psum_scatter`` over the row axis
    does that sum AND hands device (i, j) exactly the colblock-j segment
    of ITS OWN row block (local column ids [i·cpb, (i+1)·cpb) map to row
    block i by the interleaved layout); one butterfly exchange over the
    COLUMN axis then concatenates the C segments, index-ordered, into the
    full (rps, S) row-block coefficient.  Two log-stage collectives per
    backward level, no full-column replica anywhere.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs2d
    from repro.distributed.collectives import butterfly_frontier_exchange

    mesh = p.mesh
    rax, cax = p.axis, p.col_axis
    S = n_sources
    sigma = p.sigma
    R, C, rps = p.n_shards, p.n_col_shards, p.rows_per_shard
    cpb = p.cols_per_block
    n_loc = R * cpb                   # local column space of one device
    n_cols = p.n_sets * sigma         # padded scatter space (≥ n_loc)
    spmm = bvss_spmm if use_kernel else bvss_spmm_ref
    spmm_w = spmm_w_impl if spmm_w_impl is not None else \
        (bvss_spmm_w if use_kernel else bvss_spmm_w_ref)
    spmm_t = bvss_spmm_t if use_kernel else bvss_spmm_t_ref
    qcap = queue_capacity(p.num_vss)
    max_lv = max_levels if max_levels is not None else p.n + 1
    locals_for = _make_ms_locals_2d(p, S, spmm, qcap, spmm_w=spmm_w,
                                    track_sigma=True)
    hist0, record = make_queue_history(qcap, max_lv, p.num_vss)

    def local_fn(masks: jnp.ndarray, row_ids: jnp.ndarray,
                 v2r: jnp.ndarray, vstart: jnp.ndarray, vend: jnp.ndarray,
                 sources: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        dev = ShardedBVSSDevice(masks[0], row_ids[0], v2r[0],
                                vstart[0], vend[0])
        loc = locals_for(dev)
        pipe = LevelPipeline(step=lambda s, lvl: loc.step(s),
                             finalize=lambda s, lvl: loc.finalize(s),
                             active=lambda s: s.cont)
        st, _, hist = run_levels_recorded(
            pipe, loc.init(sources), max_levels=max_lv, history=hist0,
            record=record)
        levels = st.levels[:rps]                     # (rps, S) local rows
        sig = st.paths                               # (rps, S)
        i = jax.lax.axis_index(rax)
        col_ids = (jnp.arange(sigma, dtype=jnp.int32)[None, :]
                   + jnp.zeros((qcap, 1), jnp.int32))

        def body(carry: tuple[jnp.ndarray, jnp.ndarray]
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
            delta, t = carry
            Q = jax.lax.dynamic_index_in_dim(hist.Q, t, keepdims=False)
            safe = jnp.maximum(sig, 1.0)
            h = jnp.where(levels == t, (1.0 + delta) / safe, 0.0)
            h = jnp.concatenate([h, jnp.zeros((1, S), jnp.float32)])
            part = bvss_spmm_t_local(dev.masks[Q], dev.row_ids[Q], h,
                                     sigma=sigma, impl=spmm_t)
            cols = dev.virtual_to_real[Q][:, None] * sigma + col_ids
            coeff = jnp.zeros((n_cols, S), jnp.float32).at[
                cols.reshape(-1)].add(part.reshape(-1, S))[:n_loc]
            # sum the row-axis partials of this COLUMN block and keep this
            # device's own-row-block segment of the result ...
            coeff = jax.lax.psum_scatter(coeff, rax, scatter_dimension=0,
                                         tiled=True)           # (cpb, S)
            # ... then stitch the C per-colblock segments (index-ordered
            # by mesh column = offset order) into the full row block
            coeff = butterfly_frontier_exchange(coeff, cax)    # (rps, S)
            delta = delta + jnp.where(levels == t - 1, sig * coeff, 0.0)
            return delta, t - 1

        def cond(carry: tuple[jnp.ndarray, jnp.ndarray]) -> jnp.ndarray:
            return carry[1] >= 1

        # mesh-uniform countdown start over BOTH axes: the body carries
        # collectives, so every device walks the same levels
        tloc = jnp.where(levels == INF, 0, levels).max().astype(jnp.int32)
        tmax = jax.lax.pmax(tloc, (rax, cax))
        delta0 = jnp.zeros((rps, S), jnp.float32)
        delta, _ = jax.lax.while_loop(cond, body, (delta0, tmax))
        lsrc = sources - i * rps
        own = (lsrc >= 0) & (lsrc < rps)
        row = jnp.clip(lsrc, 0, rps - 1)
        cols_s = jnp.arange(S)
        delta = delta.at[row, cols_s].set(
            jnp.where(own, 0.0, delta[row, cols_s]))
        return st.levels[None, :rps], sig[None], delta[None]

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=problem_specs2d(rax, cax) + (P(),),
                       out_specs=(P((rax, cax)),) * 3, check_vma=False)

    def bc(sources: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
        sources = jnp.asarray(sources, dtype=jnp.int32)
        lv, sig, delta = fn(p.dev.masks, p.dev.row_ids,
                            p.dev.virtual_to_real,
                            p.dev.vss_of_vertex_start,
                            p.dev.vss_of_vertex_end, sources)

        def col0(a):  # (R·C, rps, S) blocks row-major -> mesh column 0
            return a.reshape(R, C, rps, S)[:, 0].reshape(-1, S)[:p.n]
        return col0(lv), col0(sig), col0(delta)

    return jax.jit(bc)


def betweenness_centrality(g: Graph | None,
                           sources: Sequence[int] | np.ndarray, *,
                           problem: BlestProblem | None = None,
                           use_kernel: bool = True,
                           batch: int | None = None,
                           max_levels: int | None = None,
                           bc_fn: Callable | None = None) -> np.ndarray:
    """Partial Brandes betweenness Σ_{s∈sources} δ_s(v), unnormalised —
    the quantity ``kernels.ref.betweenness_ref`` computes (equal to
    NetworkX ``betweenness_centrality(normalized=False)`` on a DiGraph
    when ``sources`` is every vertex).

    ``sources`` are ids of ``g`` (or of the prepared graph when
    ``problem`` is passed); duplicates contribute once each.  Sources are
    processed in fixed cohorts of ``batch`` stacked wave columns (default
    min(8, len(sources))).  ``bc_fn`` is an optional prebuilt
    :func:`make_betweenness` callable of width ``batch`` (sessions pass
    their cached one).  A sharded ``problem`` runs both phases mesh-native.
    """
    if problem is None:
        from repro.core.bvss import build_bvss
        problem = BlestProblem.build(build_bvss(g))
    sources = np.asarray(sources, dtype=np.int32)
    if len(sources) == 0:
        return np.zeros(problem.n, dtype=np.float64)
    S = batch if batch is not None else min(8, len(sources))
    f = bc_fn if bc_fn is not None else make_betweenness(
        problem, S, use_kernel=use_kernel, max_levels=max_levels)
    bc = np.zeros(problem.n, dtype=np.float64)
    for lo in range(0, len(sources), S):
        chunk = sources[lo:lo + S]
        valid = len(chunk)  # tail cohorts are padded, padded cols dropped
        _, _, delta = f(jnp.asarray(pad_cohort(chunk, S)))
        bc += np.asarray(delta[:, :valid], dtype=np.float64).sum(axis=1)
    return bc
