"""Delta-stepping single-source shortest paths on the wave surface
(DESIGN §2.9).

SSSP is the tropical-semiring instance of the wave machinery: swap the
Boolean pull tile for the min-plus product ``bvss_spmm_minplus`` (SlimSell's
algebraic formulation) and BFS levels become weighted distances.  The
driver is a bucketed label-correcting loop — batched delta-stepping:

* the OUTER loop owns a per-column bucket top ``btop``; every vertex with
  a settled distance below ``btop`` is final (positive weights: any
  shorter path runs entirely through already-settled vertices);
* the INNER loop relaxes the current bucket to a fixpoint: the frontier
  (vertices whose distance improved and sits below ``btop``) is compacted
  set-wise through the SAME ``make_compactor`` queue the BFS engines use,
  then pulled in chunks of ``PULL_CHUNK`` queue slots, as many as the
  live VSS count needs (every engine's pull loop, DESIGN §2.3):
  each chunk gathers its masks, weight-plane rows and σ-column distances,
  runs the min-plus tile product and scatter-``min``s into the distance
  rows (the Pallas ``scatter_min``, which walks the chunk's live pairs
  against distances held in VMEM, where they fit it).  The pull
  reads only the frontier's distances, which no chunk changes, and the
  scatter-min is idempotent, so the chunks give the one-shot relaxation
  exactly;
* the bucket advance jumps ``btop`` to the bucket holding the smallest
  unsettled distance — empty buckets cost nothing, so the classic Δ
  trade-off (bucket width vs relaxation rounds) only shapes performance,
  never correctness.

Both loops fuse into ONE jitted ``while_loop`` nest per cohort of S
sources (S stacked distance rows, each lane-dense over the vertices,
through one tile stream).  The engine runs under three named scopes
(``repro.tracing.SSSP_STAGES``): ``sssp.expand`` (queue chunks, gathers
and the min-plus kernel), ``sssp.update`` (the scatter-min) and
``sssp.bucket`` (the compaction, the settle sweep, the bucket advance,
the next frontier and the loops' own control).  The device arrays it
reads (the BVSS and the weight plane) are arguments of the compiled
program, not constants inside it.

A row-sharded problem runs the identical loop under ``shard_map``: local
rows scatter locally, each shard pulls over its own live count, the
frontier's distance values all-gather once per relaxation (the float
twin of the frontier-word gather, outside the chunk loop), and
continuation / bucket minima reduce with ``psum`` / ``pmin`` so every
shard stays in lock-step.  A 2-D problem is a typed
:class:`~repro.errors.ConfigError` (the weighted verbs ship 1-D;
DESIGN §2.9).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analytics.common import pad_cohort
from repro.core.bfs import (PULL_CHUNK, BlestProblem, chunk_count,
                            make_compactor, pull_chunks, queue_capacity)
from repro.errors import ConfigError
from repro.kernels import bvss_spmm_minplus_local, scatter_min
from repro.kernels.scatter_min import TILE
from repro.kernels.ref import bvss_spmm_minplus_ref, scatter_min_ref

#: the counted engine's device counters, in the order it returns them:
#: inner relaxation ``rounds``, outer ``buckets``, and over the rounds
#: ``pull_slots`` the queue slots pulled (``PULL_CHUNK`` × chunks),
#: ``live_vss`` the sum of the live VSS count, ``pull_chunks``, and
#: ``finite_rows`` the pulled slice rows with a finite candidate for some
#: source (the pairs of the update that can lower a distance)
SSSP_COUNTERS = ("rounds", "buckets", "pull_slots", "live_vss",
                 "pull_chunks", "finite_rows")


def default_delta(weights: np.ndarray) -> float:
    """Bucket width heuristic: the mean edge weight (classic delta-stepping
    uses Δ ≈ mean weight for random weights; correctness never depends on
    the choice — see the module docstring)."""
    w = np.asarray(weights, dtype=np.float64)
    return float(w.mean()) if w.size else 1.0


def _next_btop(rem: jnp.ndarray, btop: jnp.ndarray,
               delta: jnp.ndarray) -> jnp.ndarray:
    """Advance each column's bucket top past its smallest unsettled
    distance ``rem`` (jumping empty buckets); columns with no unsettled
    vertex (rem = +inf) keep their top.  ``nextafter`` guards the
    floating-point edge where the bucket formula lands exactly ON ``rem``
    (Δ much smaller than the distance scale) — the top must STRICTLY
    exceed ``rem`` or the frontier goes empty without progress."""
    nbt = (jnp.floor(rem / delta) + 1.0) * delta
    nbt = jnp.maximum(nbt, jnp.nextafter(rem, jnp.inf))
    return jnp.where(jnp.isfinite(rem), nbt, btop)


class SSSPEngine(NamedTuple):
    """A built delta-stepping engine: ``fn(arrays, sources, delta)``,
    jitted (or compiled), and the device arrays it reads — the BVSS and
    the weight plane, passed as arguments so that no compiled program
    carries a copy of the graph.  Call it as ``engine(sources (S,) i32,
    delta () f32)``: the distances ``(S, n)`` f32, with the counters
    (:data:`SSSP_COUNTERS`) as well when it was built with them."""

    fn: Callable
    arrays: tuple
    n_sources: int

    def __call__(self, sources, delta):
        return self.fn(self.arrays, sources, delta)

    def compile(self) -> "SSSPEngine":
        """The engine compiled ahead of its first call."""
        specs = (jax.ShapeDtypeStruct((self.n_sources,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.float32))
        return self._replace(fn=self.fn.lower(self.arrays, *specs).compile())


def make_sssp(problem: BlestProblem, wplane: jnp.ndarray, n_sources: int, *,
              use_kernel: bool = True, max_rounds: int | None = None,
              counters: bool = False) -> SSSPEngine:
    """Build the delta-stepping engine ``f(sources (S,) i32, delta () f32)
    -> dist (S, n) f32`` over ``problem`` (ids are the problem's own).
    ``wplane`` is the device weight plane ``prepare(..., weights=...)``
    committed (``PreparedBFS.wplane``); its dummy row makes padded queue
    entries relax nothing.  ``counters``: also return the int32 counters
    of :data:`SSSP_COUNTERS` (single-device only).  Single-device and 1-D
    row-sharded; 2-D raises :class:`~repro.errors.ConfigError`."""
    if wplane is None:
        raise ConfigError(
            "sssp needs a weight plane: prepare(..., weights=...) or let "
            "GraphSession default to unit weights")
    impl, scatter = ((None, scatter_min) if use_kernel
                     else (bvss_spmm_minplus_ref, _xla_scatter))
    cap = max_rounds if max_rounds is not None else problem.n + 2
    if problem.mesh is not None:
        if problem.is_2d:
            raise ConfigError(
                "sssp is not supported on a 2-D (row × column) mesh yet — "
                "the weighted verbs ship 1-D row-sharded (DESIGN §2.9)")
        if counters:
            raise ConfigError("sssp counters exist only on the "
                              "single-device engine")
        return _make_sssp_sharded(problem, wplane, n_sources, impl=impl,
                                  scatter=scatter, cap=cap)
    return _make_sssp_single(problem, wplane, n_sources, impl=impl,
                             scatter=scatter, cap=cap, counters=counters)


def _xla_scatter(dist, rows, vals, n_pairs):
    """The jnp scatter-min of every pair (``use_kernel=False``): the pairs
    past ``n_pairs`` are the dummy VSS's, all +inf, so it needs no
    count."""
    return scatter_min_ref(dist, rows, vals)


def _whole_tiles(rows: int) -> int:
    """``rows`` rounded up to whole (8, 128) float32 tiles: the engines'
    distance width, so that the update kernel takes the distances as a
    bitcast and never pads or slices a copy of them."""
    return -(-rows // TILE) * TILE


def _relaxation(dev, wplane: jnp.ndarray, p: BlestProblem, n_sources: int,
                impl, scatter, count_finite: bool = False) -> Callable:
    """``relax(dist, xg) -> (dist, (slots, live, chunks, finite))``: one
    frontier relaxation, the ONE body the single-device and the sharded
    engines run.  ``xg`` (S, ≥ n_sets·σ) holds the frontier's distances
    over the GLOBAL columns, +inf off the frontier; ``dist`` (S, rows)
    the rows this device owns, scattered through ``row_ids`` (dummy rows
    past the end are dropped or masked by the caller) by ``scatter(dist,
    rows, vals, live pairs)``; ``finite`` is counted only with
    ``count_finite`` (else 0)."""
    sigma, n_sets, S = p.sigma, p.n_sets, n_sources
    tau = (32 // sigma) * 32
    compact = make_compactor(dev, p.num_vss, queue_capacity(p.num_vss))

    def relax(dist: jnp.ndarray, xg: jnp.ndarray):
        with jax.named_scope("sssp.bucket"):
            set_active = (xg[:, :n_sets * sigma].reshape(S, n_sets, sigma)
                          < jnp.inf).any(axis=(0, 2))
            Q, count = compact(set_active)

        def chunk(i, ids, c):
            d, finite = c
            y = bvss_spmm_minplus_local(
                dev.masks[ids], wplane[ids], dev.virtual_to_real[ids], xg,
                sigma=sigma, impl=impl)                  # (S, C, τ)
            with jax.named_scope("sssp.update"):
                rows = dev.row_ids[ids].reshape(-1)
                y = y.reshape(S, -1)
                live = jnp.clip(count - i * PULL_CHUNK, 0, PULL_CHUNK) * tau
                # a finite candidate always has a real row: the dummy VSS
                # and a VSS's padding slices have no edge
                if count_finite:
                    finite += jnp.sum((y < jnp.inf).any(axis=0),
                                      dtype=jnp.int32)
                return scatter(d, rows, y, live), finite

        n_chunks = chunk_count(count)
        with jax.named_scope("sssp.expand"):
            dist, finite = pull_chunks(Q, n_chunks, chunk,
                                       (dist, jnp.int32(0)))
        return dist, (n_chunks * PULL_CHUNK, count, n_chunks, finite)

    return relax


def _make_sssp_single(p: BlestProblem, wplane: jnp.ndarray, n_sources: int,
                      *, impl, scatter, cap: int,
                      counters: bool) -> SSSPEngine:
    n, S = p.n, n_sources
    ncols = _whole_tiles(p.n_sets * p.sigma)

    @jax.named_scope("sssp.bucket")
    def sssp(arrays, sources, delta):
        masks, row_ids, v2r, wpl = arrays
        dev = p.dev._replace(masks=masks, row_ids=row_ids,
                             virtual_to_real=v2r)
        relax = _relaxation(dev, wpl, p, S, impl, scatter, counters)
        valid = jnp.arange(ncols) < n                    # padding columns
        delta = delta.astype(jnp.float32)
        each = jnp.arange(S)                             # one row a source
        dist = jnp.full((S, ncols), jnp.inf, jnp.float32)
        dist = dist.at[each, sources].set(0.0)
        fr = jnp.zeros((S, ncols), bool).at[each, sources].set(True)
        btop = jnp.broadcast_to(delta, (S,))
        tally = jnp.zeros((len(SSSP_COUNTERS),), jnp.int32)

        def outer_body(carry):
            dist, fr, btop, tally = carry

            def inner(c):
                dist, fr, it, tally = c
                d2, (slots, live, chunks, finite) = relax(
                    dist, jnp.where(fr, dist, jnp.inf))
                # dummy-row scatters may land in padding columns (row n <
                # ncols): wipe them so the padding never re-enters a
                # gather as a distance
                d2 = jnp.where(valid, d2, jnp.inf)
                fr2 = (d2 < dist) & (d2 < btop[:, None])
                return d2, fr2, it + 1, tally + jnp.stack(
                    [1, 0, slots, live, chunks, finite])

            dist, fr, _, tally = jax.lax.while_loop(
                lambda c: c[1].any() & (c[2] < cap),
                inner, (dist, fr, jnp.int32(0), tally))
            unsettled = jnp.where(valid & (dist >= btop[:, None]),
                                  dist, jnp.inf)
            rem = jnp.min(unsettled, axis=1)             # (S,)
            nbt = _next_btop(rem, btop, delta)
            fr = valid & (dist >= btop[:, None]) & (dist < nbt[:, None])
            return dist, fr, nbt, tally.at[1].add(1)

        dist, _, _, tally = jax.lax.while_loop(
            lambda c: c[1].any() & (c[3][1] < cap),
            outer_body, (dist, fr, btop, tally))
        if counters:
            return dist[:, :n], tally
        return dist[:, :n]

    return SSSPEngine(jax.jit(sssp), (p.dev.masks, p.dev.row_ids,
                                      p.dev.virtual_to_real, wplane), S)


def _make_sssp_sharded(p: BlestProblem, wplane: jnp.ndarray, n_sources: int,
                       *, impl, scatter, cap: int) -> SSSPEngine:
    from jax.sharding import PartitionSpec as P

    from repro.core.bvss import ShardedBVSSDevice
    from repro.core.level_pipeline import global_any

    mesh, axis = p.mesh, p.axis
    n = p.n
    rps = p.rows_per_shard
    width = _whole_tiles(rps + 1)                        # + the dummy row
    S = n_sources

    def local_loop(masks, row_ids, v2r, wpl, sources, delta):
        dev = ShardedBVSSDevice(masks[0], row_ids[0], v2r[0], None, None)
        relax = _relaxation(dev, wpl[0], p, S, impl, scatter)
        d = jax.lax.axis_index(axis)
        lvalid = (d * rps + jnp.arange(rps)) < n         # real local rows
        rowmask = jnp.concatenate([lvalid, jnp.zeros((width - rps,), bool)])
        delta32 = delta.astype(jnp.float32)

        def relax_local(dist: jnp.ndarray, fr: jnp.ndarray):
            # the float twin of the frontier-word gather: every shard
            # needs the frontier's distance VALUES for its global columns
            # — all-gathered BEFORE the chunk loop (no collectives in a
            # loop whose trip count differs by device)
            xv = jnp.where(fr, dist[:, :rps], jnp.inf)   # (S, rps)
            xg = jax.lax.all_gather(xv, axis, axis=1, tiled=True)
            d2, _ = relax(dist, xg)
            d2 = jnp.where(rowmask[None, :], d2, jnp.inf)
            return d2, d2 < dist

        each = jnp.arange(S)                             # one row a source
        lsrc = sources - d * rps
        own = (lsrc >= 0) & (lsrc < rps)
        dist = jnp.full((S, width), jnp.inf, jnp.float32)
        dist = dist.at[each, jnp.where(own, lsrc, rps)].set(
            jnp.where(own, 0.0, jnp.inf))
        fr = jnp.zeros((S, rps), bool).at[
            each, jnp.where(own, lsrc, 0)].set(own)
        btop = jnp.broadcast_to(delta32, (S,))

        # the repo's lock-step idiom: while_loop conds read a CARRIED
        # replicated cont flag; the global_any reduction runs in the
        # body (never in a cond)
        def outer_body(carry):
            dist, fr, btop, cont, rounds = carry

            def inner(c):
                dist, fr, cont, it = c
                d2, improved = relax_local(dist, fr)
                fr2 = improved[:, :rps] & (d2[:, :rps] < btop[:, None])
                return (d2, fr2, global_any(fr2.any(), axis), it + 1)

            dist, fr, _, _ = jax.lax.while_loop(
                lambda c: c[2] & (c[3] < cap),
                inner, (dist, fr, global_any(fr.any(), axis),
                        jnp.int32(0)))
            unsettled = jnp.where(
                lvalid[None, :] & (dist[:, :rps] >= btop[:, None]),
                dist[:, :rps], jnp.inf)
            rem = jax.lax.pmin(jnp.min(unsettled, axis=1), axis)
            nbt = _next_btop(rem, btop, delta32)
            fr = (lvalid[None, :] & (dist[:, :rps] >= btop[:, None])
                  & (dist[:, :rps] < nbt[:, None]))
            return (dist, fr, nbt, global_any(fr.any(), axis), rounds + 1)

        dist, _, _, _, _ = jax.lax.while_loop(
            lambda c: c[3] & (c[4] < cap),
            outer_body, (dist, fr, btop, global_any(fr.any(), axis),
                         jnp.int32(0)))
        return dist[:, :rps]

    fn = jax.shard_map(local_loop, mesh=mesh,
                       in_specs=(P(axis),) * 4 + (P(), P()),
                       out_specs=P(None, axis), check_vma=False)

    @jax.named_scope("sssp.bucket")
    def sssp(arrays, sources, delta):
        out = fn(*arrays, jnp.asarray(sources, jnp.int32),
                 jnp.asarray(delta, jnp.float32))
        return out[:, :n]

    return SSSPEngine(jax.jit(sssp), (p.dev.masks, p.dev.row_ids,
                                      p.dev.virtual_to_real, wplane), S)


def sssp_distances(sources: Sequence[int] | np.ndarray, *,
                   problem: BlestProblem, wplane: jnp.ndarray,
                   weights: np.ndarray, batch: int | None = None,
                   use_kernel: bool = True,
                   delta: float | None = None,
                   sssp_fn: Callable | None = None) -> np.ndarray:
    """Distances from each source (rows) to every vertex (cols): (S, n)
    float64, +inf where unreachable.  Ids are the problem's own.
    ``sssp_fn`` is an optional prebuilt engine of width ``batch``
    (sessions pass their cached one)."""
    sources = np.asarray(sources, dtype=np.int64)
    if len(sources) == 0:
        return np.zeros((0, problem.n), dtype=np.float64)
    S = batch if batch is not None else min(8, len(sources))
    if delta is None:
        delta = default_delta(weights)
    if sssp_fn is None:
        sssp_fn = make_sssp(problem, wplane, S, use_kernel=use_kernel)
    out = np.empty((len(sources), problem.n), dtype=np.float64)
    for lo in range(0, len(sources), S):
        chunk = sources[lo:lo + S]
        dist = np.asarray(sssp_fn(
            jnp.asarray(pad_cohort(chunk, S), dtype=jnp.int32),
            jnp.float32(delta)))
        out[lo:lo + len(chunk)] = dist[:len(chunk)]
    return out
