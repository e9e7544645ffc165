"""BFS engines (paper §4, Algorithms 2 & 3) plus the baselines of Table 2.

Every device engine runs its *entire* level loop inside one ``jit`` via the
shared :mod:`repro.core.level_pipeline` driver — the TPU analogue of the
paper's fused persistent kernel (§4.3): control never returns to the host
between levels and the convergence test is on-device.

Engines
-------
reference      host NumPy queue BFS (test oracle)
dense_pull     bitmap SpMSpV full sweep (frontier-oblivious lower bound)
csr_push       edge-parallel push (Gunrock-style edge map)
csr_pull       edge-parallel pull over the transposed CSR (GAP-style)
direction_opt  Beamer push/pull switching (GSWITCH's key pattern)
brs            BerryBees-like BRS: slice-set sweep, frontier-OBLIVIOUS
blest          Alg. 2: BVSS queue, frontier-aware blocks, eager scatter-min
blest_lazy     Alg. 3: lazy marks (fire-and-forget) + dense finalise sweep

TPU adaptation notes (DESIGN.md §2): the paper's atomic queue-append becomes
cumsum stream-compaction; `atomicOr`/`REDG` becomes scatter-max of byte
marks; the Alg. 3 stage-2 word sweep is a dense vectorised pass, which is
exactly what the VPU wants.

The ``blest``/``blest_lazy`` level step is FUSED (DESIGN.md §2.3): a
BVSS pull over the compacted queue (Pallas ``bvss_pull`` by default), its
scatter, and one fused finalise/pack/set-flag sweep
(``finalize_pack_sweep``).  The pull runs in fixed chunks of
``PULL_CHUNK`` queue slots, as many as the live VSS count needs — the
XLA-compatible stand-in for a dynamically-sized kernel launch, so a level
pays for its live VSSs (plus under one chunk), not for a static width.

The BVSS engines are MESH-NATIVE (DESIGN.md §2.4): a
:class:`BlestProblem` built from a row-sharded BVSS
(``BlestProblem.build_sharded``) runs the SAME step/finalize skeleton
under ``shard_map`` — the pull, scatter and finalise sweep stay local to
each shard's row block, the per-shard frontier words are all-gathered once
per level, and the convergence test is a ``psum`` inside the single fused
``while_loop`` (no host sync across devices, paper §4.3 preserved).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.bvss import (BVSS, BVSSDevice, ShardedBVSS, ShardedBVSS2D,
                             ShardedBVSSDevice, shard_to_device, to_device)
from repro.core.level_pipeline import (LevelPipeline, compose_step,
                                       global_any, run_levels)
from repro.distributed.bfs_dist import frontier_all_gather
from repro.errors import ConfigError, GraphValidationError
from repro.graphs import Graph, src_of_edges, to_dense_bits
from repro.kernels import (finalize_pack_sweep, pull_vss_kernel,
                           push_vss_kernel)
from repro.kernels.ref import bvss_push_ref, finalize_pack_ref

INF = np.int32(np.iinfo(np.int32).max)

PULL_TILE = 128  # push vertex queues are padded to this (bvss_pull tile)
#: queue slots one chunk of every engine's pull gathers, pulls and
#: scatters; a pull level runs ceil(live VSSs / PULL_CHUNK) chunks
PULL_CHUNK = 4 * PULL_TILE


# ---------------------------------------------------------------------------
# host oracle
# ---------------------------------------------------------------------------
def reference_bfs(g: Graph, src: int) -> np.ndarray:
    """NumPy frontier BFS over out-CSR; returns level array (INF = unreached)."""
    levels = np.full(g.n, INF, dtype=np.int32)
    levels[src] = 0
    frontier = np.array([src], dtype=np.int64)
    lvl = 0
    while len(frontier):
        lvl += 1
        nbrs = np.unique(np.concatenate(
            [g.indices[g.indptr[u]:g.indptr[u + 1]] for u in frontier]))
        new = nbrs[levels[nbrs] == INF].astype(np.int64)
        levels[new] = lvl
        frontier = new
    return levels


# ---------------------------------------------------------------------------
# shared device helpers
# ---------------------------------------------------------------------------
def _pack_bits(bits: jnp.ndarray, n_words: int) -> jnp.ndarray:
    """bool (n_words*32,) -> uint32 (n_words,), bit i of word w = bits[32w+i]."""
    b = bits.reshape(n_words, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(b * weights[None, :], axis=1, dtype=jnp.uint32)


def _unpack_bits(words: jnp.ndarray) -> jnp.ndarray:
    """uint32 (n_words,) -> bool (n_words*32,): inverse of :func:`_pack_bits`."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return ((words[:, None] >> shifts[None, :]) & 1).reshape(-1) != 0


def pull_vss_jnp(masks: jnp.ndarray, fbytes: jnp.ndarray, sigma: int
                 ) -> jnp.ndarray:
    """Pure-jnp pull over one batch of VSSs (oracle / non-Pallas fallback).

    masks:  (B, 32) uint32 — slot j of word l = mask of slice (j, l)
    fbytes: (B,)    uint32 — the σ-bit frontier word of each VSS's slice set
    returns hits (B, spw, 32) bool: slice/frontier dot product ≠ 0.
    """
    spw = 32 // sigma
    smask = jnp.uint32((1 << sigma) - 1)
    fb = fbytes & smask
    fword = jnp.zeros_like(fb)
    for j in range(spw):
        fword = fword | (fb << jnp.uint32(sigma * j))
    anded = masks & fword[:, None]
    hits = []
    for j in range(spw):
        sub = (anded >> jnp.uint32(sigma * j)) & smask
        hits.append(sub != 0)
    return jnp.stack(hits, axis=1)


def _frontier_bytes(F: jnp.ndarray, sets: jnp.ndarray, sigma: int) -> jnp.ndarray:
    """Gather the σ-bit frontier word of slice set ids ``sets`` from packed
    F: (n_fwords,) single frontier -> (B,), or (n_fwords, S) stacked
    per-source columns -> (B, S)."""
    bitpos = sets.astype(jnp.uint32) * jnp.uint32(sigma)
    idx = (bitpos >> jnp.uint32(5)).astype(jnp.int32)
    shift = bitpos & jnp.uint32(31)
    mask = jnp.uint32((1 << sigma) - 1)
    if F.ndim == 2:
        return (F[idx, :] >> shift[:, None]) & mask
    return (F[idx] >> shift) & mask


# ---------------------------------------------------------------------------
# BLEST problem bundle
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlestProblem:
    n: int
    sigma: int
    n_sets: int       # GLOBAL slice sets (columns) in either mode
                      #   (2-D: LOCAL column-block slice sets per device)
    num_vss: int      # per-shard padded VSS count when sharded
    n_fwords: int     # gathered (global) frontier words when sharded
                      #   (2-D: per-device COLUMN-BLOCK frontier words)
    dev: BVSSDevice | ShardedBVSSDevice
    # mesh-native row partition (DESIGN §2.4); mesh=None = single-device
    mesh: Mesh | None = None
    axis: str = "data"
    n_shards: int = 1
    rows_per_shard: int = 0
    # 2-D row × column partition (DESIGN §2.4): col_axis=None = 1-D.
    # Column block j owns, inside every row block, the cols_per_block
    # sources [i·rps + j·cpb, i·rps + (j+1)·cpb) — the butterfly exchange
    # moves one rps/cols/32-word segment per device per level.
    col_axis: str | None = None
    n_col_shards: int = 1
    cols_per_block: int = 0
    # static push expansion factor: every pushing vertex enqueues at most
    # this many VSSs of its own slice set (DESIGN §2.8)
    max_vss_per_set: int = 1

    @property
    def is_2d(self) -> bool:
        return self.col_axis is not None

    @staticmethod
    def build(bvss: BVSS) -> "BlestProblem":
        return BlestProblem(n=bvss.n, sigma=bvss.sigma, n_sets=bvss.n_sets,
                            num_vss=bvss.num_vss,
                            n_fwords=bvss.n_frontier_words,
                            dev=to_device(bvss),
                            max_vss_per_set=bvss.max_vss_per_set)

    @staticmethod
    def build_sharded(sb: ShardedBVSS, mesh: Mesh, axis: str = "data"
                      ) -> "BlestProblem":
        """Row-sharded problem: ``dev`` holds the shard-stacked arrays
        committed with their ``P(axis)`` placement; the engines run the
        level loop under ``shard_map`` over ``axis``."""
        if mesh.shape[axis] != sb.n_shards:
            raise ValueError(
                f"mesh axis {axis!r} has {mesh.shape[axis]} devices but the "
                f"BVSS is built for {sb.n_shards} shards")
        return BlestProblem(n=sb.n, sigma=sb.sigma, n_sets=sb.n_sets,
                            num_vss=sb.num_vss_pad,
                            n_fwords=sb.n_frontier_words,
                            dev=shard_to_device(sb, mesh, axis),
                            mesh=mesh, axis=axis, n_shards=sb.n_shards,
                            rows_per_shard=sb.rows_per_shard,
                            max_vss_per_set=sb.max_vss_per_set)

    @staticmethod
    def build_sharded_2d(sb: "ShardedBVSS2D", mesh: Mesh) -> "BlestProblem":
        """2-D row × column problem: device (i, j) of the mesh owns BVSS
        block i·cols + j (row-major stack, both mesh axes on dim 0).
        ``n_sets``/``n_fwords`` become the per-device LOCAL column-block
        quantities — the engines never materialise a global frontier."""
        from repro.core.bvss import shard_to_device_2d

        row_axis, col_axis = mesh.axis_names
        shape = (mesh.shape[row_axis], mesh.shape[col_axis])
        if shape != (sb.rows, sb.cols):
            raise ConfigError(
                f"mesh shape {shape} does not match the 2-D BVSS built "
                f"for ({sb.rows}, {sb.cols}) blocks")
        return BlestProblem(n=sb.n, sigma=sb.sigma,
                            n_sets=sb.n_sets_local,
                            num_vss=sb.num_vss_pad,
                            n_fwords=sb.n_frontier_words_local,
                            dev=shard_to_device_2d(sb, mesh),
                            mesh=mesh, axis=row_axis, n_shards=sb.rows,
                            rows_per_shard=sb.rows_per_shard,
                            col_axis=col_axis, n_col_shards=sb.cols,
                            cols_per_block=sb.cols_per_block,
                            max_vss_per_set=sb.max_vss_per_set)


PullFn = Callable[[jnp.ndarray, jnp.ndarray, int], jnp.ndarray]
PushFn = Callable[[jnp.ndarray, jnp.ndarray, int], jnp.ndarray]

#: direction modes of the hybrid BVSS engines (DESIGN §2.8)
DIRECTIONS = ("auto", "pull", "push")

#: default auto-mode push cap: the largest frontier popcount a push level
#: will take on (also the push vertex-queue width, so it is the static
#: scatter-side working-set knob — autotunable, DESIGN §2.8)
DEFAULT_PUSH_CAP = PULL_TILE


class _BlestState(NamedTuple):
    levels: jnp.ndarray  # (n + 1,) int32, slot n = dummy row sink
                         #   (sharded: (rps + 1,) LOCAL rows, dummy = rps)
    F: jnp.ndarray       # (n_fwords,) uint32 packed frontier (global; under
                         #   shard_map each shard carries the gathered copy)
    Q: jnp.ndarray       # (qcap,) int32 compacted VSS queue, dummy-padded
    count: jnp.ndarray   # int32 live VSS count (LOCAL: chunks to pull)
    marks: jnp.ndarray   # (n + 1,) uint8 lazy scratch ((1,) dummy when eager)
    unvisited: jnp.ndarray  # int32 GLOBAL unvisited-vertex count (the
                            #   Beamer-style saturation guard of the
                            #   direction heuristic, DESIGN §2.8)
    cont: jnp.ndarray    # bool continue flag (mesh-global via psum)
    tally: jnp.ndarray | None = None  # level counters after ``levels``
                                      #   (LEVEL_COUNTERS); None = uncounted


#: the counted engine's device counters, in the order it returns them:
#: ``levels`` loop iterations, ``push_levels``, and over pull levels
#: ``pull_slots`` the queue slots pulled, ``live_vss`` the sum of the
#: live VSS count and ``pull_chunks`` the chunks run
LEVEL_COUNTERS = ("levels", "push_levels", "pull_slots", "live_vss",
                  "pull_chunks")


def level_counter_names(n: int) -> tuple[str, ...]:
    """Names of a counted engine's ``n`` counters."""
    if n != len(LEVEL_COUNTERS):
        raise ValueError(f"a counted engine returns {len(LEVEL_COUNTERS)} "
                         f"counters, got {n}")
    return LEVEL_COUNTERS


def chunk_ceil(x):
    """``x`` rounded up to whole ``PULL_CHUNK``s: the queue slots a pull
    level over ``x`` live VSSs processes (a Python or a traced int)."""
    return (x + PULL_CHUNK - 1) // PULL_CHUNK * PULL_CHUNK


def queue_capacity(num_vss: int) -> int:
    """The compacted VSS queue's static length: every VSS, rounded up to
    whole chunks (dummy-padded), so no chunk's start is clamped."""
    return chunk_ceil(max(num_vss, 1))


def chunk_count(count):
    """The chunks a pull over ``count`` live VSSs runs (a traced int)."""
    return chunk_ceil(count) // PULL_CHUNK


def _tally(tally: jnp.ndarray | None, use_push, count
           ) -> jnp.ndarray | None:
    """Add one level to the counters after ``levels`` (the loop counts its
    own iterations): push or pull, and a pull level's slots and chunks."""
    if tally is None:
        return None
    pull = 1 - jnp.asarray(use_push, dtype=jnp.int32)
    chunks = pull * chunk_count(count)
    return tally + jnp.stack([1 - pull, chunks * PULL_CHUNK, pull * count,
                              chunks])


def _round_width(x: int) -> int:
    return max(PULL_TILE, ((x + PULL_TILE - 1) // PULL_TILE) * PULL_TILE)


def pull_chunks(Q: jnp.ndarray, n_chunks, body: Callable, init):
    """The one pull loop of every engine (DESIGN §2.3): ``body(i, ids,
    carry)`` over chunks ``i < n_chunks`` of the compacted queue ``Q``,
    chunk ``i`` holding the ``PULL_CHUNK`` VSS ids from slot ``i ·
    PULL_CHUNK`` (dummy VSSs past the live count).  ``n_chunks`` is
    ``chunk_count(count)``; the caller makes it, under its own named
    scope, and may count it.
    A pull reads only the frontier, which no chunk changes, and each
    engine's write splits exactly over chunks (the min of a level, the
    max of a mark, a sum of σ partials), so the chunks give the one-shot
    answer.  ``len(Q)`` must be a multiple of ``PULL_CHUNK``
    (:func:`queue_capacity`).  Under ``shard_map`` each shard runs its
    own trip count: ``body`` must hold no collective."""
    def chunk(i, carry):
        ids = jax.lax.dynamic_slice_in_dim(Q, i * PULL_CHUNK, PULL_CHUNK)
        return body(i, ids, carry)

    return jax.lax.fori_loop(0, n_chunks, chunk, init)


def make_vertex_compactor(n_fwords: int, dummy_vertex: int, pqcap: int
                          ) -> Callable:
    """Build ``compact(F (n_fwords,) uint32) -> (VQ, fcount)``: cumsum
    stream-compaction of the set frontier BITS into a static-width vertex
    queue (the push twin of :func:`make_compactor`; dummy-padded with
    ``dummy_vertex``, overflow beyond ``pqcap`` dropped — which is why
    auto mode only takes push when ``popcount(F) <= push_cap``)."""
    verts = jnp.arange(n_fwords * 32, dtype=jnp.int32)
    bitpos = jnp.arange(32, dtype=jnp.uint32)

    def compact(F: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        bits = ((F[:, None] >> bitpos[None, :]) & jnp.uint32(1)
                ).reshape(-1).astype(bool)
        pos = jnp.cumsum(bits.astype(jnp.int32)) - 1
        idx = jnp.where(bits, pos, pqcap)  # OOB -> dropped
        VQ = jnp.full((pqcap,), dummy_vertex, dtype=jnp.int32)
        VQ = VQ.at[idx].set(verts, mode="drop")
        return VQ, bits.sum().astype(jnp.int32)

    return compact


def expand_push_queue(dev, VQ: jnp.ndarray, R: int, num_vss: int
                      ) -> jnp.ndarray:
    """Expand a compacted frontier-vertex queue into the (|VQ|·R,) VSS ids
    the push phase processes: every VSS of each vertex's own slice set
    (``vss_of_vertex_start/end``), dummy-padded to the static factor R =
    ``max_vss_per_set``.  Dummy vertices map to the empty range, so the
    whole row degenerates to the all-zero dummy VSS ``num_vss``."""
    starts = dev.vss_of_vertex_start[VQ]
    ends = dev.vss_of_vertex_end[VQ]
    r = jnp.arange(R, dtype=jnp.int32)
    vss = starts[:, None] + r[None, :]
    valid = r[None, :] < (ends - starts)[:, None]
    return jnp.where(valid, vss, num_vss).reshape(-1)


def make_compactor(dev: BVSSDevice, num_vss: int, qcap: int) -> Callable:
    """Build ``compact(set_active (n_sets,) bool) -> (Q, count)``: cumsum
    stream-compaction of active slice sets into the static-width VSS queue
    (the TPU idiom for the paper's atomic queue append).  Shared by the
    single-source engines and the multi-source / serving path."""
    vss_ids = jnp.arange(num_vss, dtype=jnp.int32)
    dummy_vss = num_vss

    def compact(set_active: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        vss_active = set_active[dev.virtual_to_real[:num_vss]]
        pos = jnp.cumsum(vss_active.astype(jnp.int32)) - 1
        idx = jnp.where(vss_active, pos, qcap)  # OOB -> dropped
        Q = jnp.full((qcap,), dummy_vss, dtype=jnp.int32)
        Q = Q.at[idx].set(vss_ids, mode="drop")
        return Q, vss_active.sum().astype(jnp.int32)

    return compact


class QueueHistory(NamedTuple):
    """Per-level frontier history of one fused traversal: row ``lvl`` of
    ``Q`` is the compacted VSS queue the level-``lvl`` pull consumed (the
    tiles whose slice sets intersect the level-``lvl - 1`` frontier), with
    its live count.  Recorded via
    :func:`repro.core.level_pipeline.run_levels_recorded` and replayed in
    reverse by the Brandes backward sweep (``repro.analytics.betweenness``)
    — level-``t`` dependency flow lives in exactly the tiles whose columns
    meet the level-``t - 1`` frontier, which is this queue."""

    Q: jnp.ndarray      # (max_levels + 1, qcap) int32, dummy-padded
    count: jnp.ndarray  # (max_levels + 1,) int32


def make_queue_history(qcap: int, max_levels: int, dummy_vss: int
                       ) -> tuple[QueueHistory, Callable]:
    """Preallocate a :class:`QueueHistory` buffer and build the ``record``
    hook that snapshots a wave state's ``(Q, count)`` into row ``lvl``."""
    hist0 = QueueHistory(
        Q=jnp.full((max_levels + 1, qcap), dummy_vss, dtype=jnp.int32),
        count=jnp.zeros((max_levels + 1,), dtype=jnp.int32))

    def record(hist: QueueHistory, state, lvl) -> QueueHistory:
        return QueueHistory(Q=hist.Q.at[lvl].set(state.Q),
                            count=hist.count.at[lvl].set(state.count))

    return hist0, record


def _make_hybrid_step(dev, pull: PullFn, push: PushFn | None, sigma: int,
                      n_rows: int, qcap: int, *, lazy: bool,
                      direction: str, num_vss: int, n_fwords: int,
                      dummy_vertex: int, R: int, push_cap: int,
                      alpha: float) -> Callable:
    """The direction-optimizing level step (DESIGN §2.3/§2.8) — the ONE
    step body both the single-device and the shard_map'd engines run.

    Pull side: :func:`pull_chunks` over the compacted VSS queue: each
    chunk gathers its VSSs' masks and frontier bytes, pulls, and scatters
    (min of the level, max of the marks).  ``qcap`` is the queue length
    (:func:`queue_capacity`).
    Push side: compact the frontier BITS into a vertex queue, expand each
    vertex into the ≤ R VSSs of its own slice set, and resolve each
    (vertex, VSS) pair with the one-hot push kernel — processing width
    ``pqcap·R`` regardless of ``num_vss``.

    ``direction``: "pull"/"push" force a branch (forced push sizes the
    vertex queue to the full rounded vertex count so nothing is dropped);
    "auto" picks per level on device: push iff the frontier fits the cap,
    the frontier is small against the unvisited remainder (the Beamer-α
    guard), and push's static cost undercuts the slots the chunked pull
    would process.  When push's static cost cannot beat even the full
    queue, auto compiles to pure pull (no dead branch).

    ``n_rows`` is the scatter extent: the global ``n`` single-device, the
    shard's ``rows_per_shard`` under a mesh (row ids are local there, and
    each shard loops over its own live count — while the frontier words,
    and hence the push vertex queue, are GLOBAL replicas, so every shard
    expands the same vertices into its own local VSS ids and both cond
    branches stay collective-free)."""
    if direction not in DIRECTIONS:
        raise ConfigError(
            f"direction must be one of {DIRECTIONS}, got {direction!r}")

    def start(state: _BlestState) -> jnp.ndarray:
        """What a level scatters into: the levels (eager), or the lazy
        marks, zeroed once per level."""
        if not lazy:
            return state.levels
        with jax.named_scope("bfs.update"):
            return jnp.zeros((n_rows + 1,), dtype=jnp.uint8)

    @jax.named_scope("bfs.update")
    def scatter(out: jnp.ndarray, rows, h, lvl) -> jnp.ndarray:
        if lazy:
            # Alg. 3 stage 1: fire-and-forget mark (REDG analogue)
            return out.at[rows].max(h.astype(jnp.uint8))
        # Alg. 2: eager visited-check-and-set (ATOMG analogue):
        # scatter-min leaves already-visited levels untouched
        upd = jnp.where(h, lvl, INF).astype(jnp.int32)
        return out.at[rows].min(upd)

    def finish(state: _BlestState, out: jnp.ndarray, use_push: bool
               ) -> _BlestState:
        state = state._replace(**{"marks" if lazy else "levels": out})
        return state._replace(tally=_tally(state.tally, use_push,
                                           state.count))

    def pull_step(state: _BlestState, lvl) -> _BlestState:
        def chunk(i, ids, out):
            fbytes = _frontier_bytes(state.F, dev.virtual_to_real[ids],
                                     sigma)
            hits = pull(dev.masks[ids], fbytes, sigma)  # (C, spw, 32)
            return scatter(out, dev.row_ids[ids].reshape(-1),
                           hits.reshape(-1), lvl)

        with jax.named_scope("bfs.expand"):
            out = pull_chunks(state.Q, chunk_count(state.count), chunk,
                              start(state))
        return finish(state, out, False)

    if direction == "pull":
        return pull_step

    pqcap = _round_width(push_cap)
    push_cost = pqcap * R
    if direction == "auto" and push_cost >= qcap:
        # push can never undercut even the full queue (e.g. a hub set
        # blew up max_vss_per_set): compile the pure pull step
        return pull_step
    compact_vertices = make_vertex_compactor(n_fwords, dummy_vertex, pqcap)

    def push_step(state: _BlestState, lvl) -> _BlestState:
        with jax.named_scope("bfs.expand"):
            VQ, _ = compact_vertices(state.F)
            ids = expand_push_queue(dev, VQ, R, num_vss)
            bits = jnp.broadcast_to((VQ % sigma).astype(jnp.int32)[:, None],
                                    (pqcap, R)).reshape(-1)
            hits = push(dev.masks[ids], bits, sigma)     # (pqcap*R, spw, 32)
            out = scatter(start(state), dev.row_ids[ids].reshape(-1),
                          hits.reshape(-1), lvl)
        return finish(state, out, True)

    if direction == "push":
        return push_step

    def step(state: _BlestState, lvl) -> _BlestState:
        with jax.named_scope("bfs.expand"):
            fcount = jnp.sum(jax.lax.population_count(state.F)
                             ).astype(jnp.int32)
            use_push = ((fcount <= push_cap)
                        & (jnp.int32(push_cost) < chunk_ceil(state.count))
                        & (fcount * jnp.float32(alpha)
                           <= state.unvisited.astype(jnp.float32)))
        return jax.lax.cond(use_push, push_step, pull_step, state, lvl)

    return step


def resolve_push_cap(direction: str, push_cap: int | None, n: int) -> int:
    """The frontier cap a push level tolerates: forced push must hold EVERY
    vertex (a dropped overflow entry is a wrong answer), auto defaults to
    the tunable small-frontier cap."""
    if direction == "push":
        return n
    return push_cap if push_cap is not None else DEFAULT_PUSH_CAP


def make_blest_bfs(problem: BlestProblem, *, lazy: bool,
                   pull_impl: PullFn | None = None,
                   push_impl: PushFn | None = None,
                   use_kernels: bool = True,
                   direction: str = "auto", push_cap: int | None = None,
                   alpha: float = 4.0, max_levels: int | None = None,
                   counters: bool = False) -> Callable:
    """Build the jitted fused BLEST BFS (Alg. 2 eager / Alg. 3 lazy).

    The level step is direction-optimizing (DESIGN §2.8): the pull side
    loops over the compacted queue in chunks of ``PULL_CHUNK`` slots, as
    many as the live VSS count needs (:func:`pull_chunks`); the push side
    compacts the frontier bits into a vertex queue and expands each
    vertex's own slice-set VSSs through the one-hot push kernel.  Either
    way the scatter (min for eager levels, max for lazy marks) and one
    fused finalise + frontier-pack + set-flag sweep feed cumsum
    compaction.  A mesh-sharded ``problem`` runs the same pipeline under
    ``shard_map`` (local pull/push/scatter/finalise, frontier all-gather,
    psum convergence).

    pull_impl:   custom pull (masks, fbytes, sigma) -> hits; overrides the
                 kernel/jnp switch.
    push_impl:   custom push (masks, bits, sigma) -> hits — the push
                 fault seam (DESIGN §2.7/§2.8).
    use_kernels: route pull/push through the Pallas kernels and the tail
                 through Pallas ``finalize_pack_sweep`` (interpret-mode on
                 CPU); False = pure-jnp fallback for all three.
    direction:   "auto" (per-level on-device switch), "pull", "push".
    push_cap:    auto-mode frontier cap (None = DEFAULT_PUSH_CAP; forced
                 push always uses the full vertex count).
    alpha:       Beamer-style saturation guard: auto only pushes while
                 ``alpha * popcount(F) <= unvisited``.
    counters:    return ``(levels, counts)``: the level loop's int32
                 counters (:func:`level_counter_names`); single-device
                 only.

    The level step runs under three named scopes, which reach the
    compiled HLO's ``op_name``: ``bfs.expand`` (frontier gather and pull,
    or push compaction and expansion, kernels included), ``bfs.update``
    (scatter-min or lazy marks) and ``bfs.finalize`` (the finalise /
    pack / flag sweep and the queue compaction).
    """
    p = problem
    sigma = p.sigma
    if direction not in DIRECTIONS:
        raise ConfigError(
            f"direction must be one of {DIRECTIONS}, got {direction!r}")
    qcap = queue_capacity(p.num_vss)
    max_lv = max_levels if max_levels is not None else p.n + 1
    cap = resolve_push_cap(direction, push_cap, p.n)

    if pull_impl is not None:
        pull = pull_impl
    elif use_kernels:
        pull = pull_vss_kernel
    else:
        pull = pull_vss_jnp
    if push_impl is not None:
        push = push_impl
    elif use_kernels:
        push = push_vss_kernel
    else:
        push = bvss_push_ref
    fin_impl = finalize_pack_sweep if use_kernels else finalize_pack_ref

    if p.mesh is not None:
        if counters:
            raise ConfigError("level counters exist only on the "
                              "single-device engine")
        if p.is_2d:
            return _make_blest_bfs_sharded_2d(p, pull=pull, qcap=qcap,
                                              max_lv=max_lv,
                                              direction=direction)
        return _make_blest_bfs_sharded(p, lazy=lazy, pull=pull, push=push,
                                       fin_impl=fin_impl, qcap=qcap,
                                       max_lv=max_lv,
                                       direction=direction, push_cap=cap,
                                       alpha=alpha)

    dev = p.dev
    fin = functools.partial(fin_impl, sigma=sigma, n_fwords=p.n_fwords,
                            n_sets=p.n_sets)
    compact = make_compactor(dev, p.num_vss, qcap)
    step = _make_hybrid_step(dev, pull, push, sigma, p.n, qcap, lazy=lazy,
                             direction=direction, num_vss=p.num_vss,
                             n_fwords=p.n_fwords, dummy_vertex=p.n,
                             R=p.max_vss_per_set, push_cap=cap, alpha=alpha)

    @jax.named_scope("bfs.finalize")
    def finalize(state: _BlestState, lvl) -> _BlestState:
        if lazy:
            # Alg. 3 stage 2 fused: finalise + pack + set flags in one sweep
            lv_n, fwords, set_active = fin(state.levels[:p.n], lvl,
                                           marks=state.marks[:p.n])
            levels = jnp.concatenate([lv_n, state.levels[p.n:]])
        else:
            # eager: levels already final; the sweep just packs + flags
            _, fwords, set_active = fin(state.levels[:p.n], lvl)
            levels = state.levels
        Q, count = compact(set_active)
        unvisited = state.unvisited - jnp.sum(
            jax.lax.population_count(fwords)).astype(jnp.int32)
        return state._replace(levels=levels, F=fwords, Q=Q, count=count,
                              unvisited=unvisited, cont=count > 0)

    pipe = LevelPipeline(step=step, finalize=finalize,
                         active=lambda s: s.cont)

    def bfs(src: jnp.ndarray) -> jnp.ndarray:
        src = jnp.asarray(src, dtype=jnp.int32)
        levels = jnp.full((p.n + 1,), INF, dtype=jnp.int32).at[src].set(0)
        F = jnp.zeros((p.n_fwords,), dtype=jnp.uint32)
        F = F.at[src // 32].set(jnp.uint32(1) << (src % 32).astype(jnp.uint32))
        set0 = jnp.zeros((p.n_sets,), dtype=bool).at[src // sigma].set(True)
        Q, count = compact(set0)
        marks0 = jnp.zeros((p.n + 1 if lazy else 1,), dtype=jnp.uint8)
        tally = jnp.zeros((len(LEVEL_COUNTERS) - 1,),
                          dtype=jnp.int32) if counters else None
        state = _BlestState(levels, F, Q, count, marks0,
                            jnp.int32(p.n - 1), count > 0, tally)
        # the loop's own control (level count, continuation test, the
        # counters) is charged to finalize; the step's scopes nest inside
        with jax.named_scope("bfs.finalize"):
            state, n_levels = run_levels(pipe, state, max_levels=max_lv)
        if counters:
            return state.levels[:p.n], jnp.concatenate(
                [n_levels[None], state.tally])
        return state.levels[:p.n]

    return jax.jit(bfs)


def _make_blest_bfs_sharded(p: BlestProblem, *, lazy: bool, pull: PullFn,
                            push: PushFn, fin_impl, qcap: int,
                            max_lv: int, direction: str,
                            push_cap: int, alpha: float) -> Callable:
    """The mesh-native BLEST engine (DESIGN §2.4): the whole level loop is
    ONE ``shard_map``'d ``while_loop`` over the row partition.  Per level,
    each shard runs the same fused hybrid step as the single-device engine
    on its local rows (``bvss_pull``/``bvss_push`` + scatter +
    ``finalize_pack_sweep``), the per-shard frontier words are all-gathered
    into the global frontier, and the compacted per-shard queues feed a
    psum'd convergence test — no host sync anywhere inside the loop.

    Push levels need NO extra collective (DESIGN §2.8): the vertex queue is
    compacted from the gathered global frontier replica every shard already
    holds, and each shard expands it through its own vertex → local-VSS map
    — the direction cond may even resolve differently across shards
    (per-shard VSS counts differ) because both branches are collective-free;
    the all-gather stays hoisted in finalize."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs

    mesh, axis = p.mesh, p.axis
    sigma = p.sigma
    rps = p.rows_per_shard
    lwords = rps // 32
    all_sets = jnp.arange(p.n_sets, dtype=jnp.int32)
    fin = functools.partial(fin_impl, sigma=sigma, n_fwords=lwords,
                            n_sets=rps // sigma)

    def local_loop(masks, row_ids, v2r, vstart, vend, src):
        """One shard's slice of the fused BFS (runs under shard_map)."""
        dev = ShardedBVSSDevice(masks[0], row_ids[0], v2r[0], vstart[0],
                                vend[0])
        compact = make_compactor(dev, p.num_vss, qcap)
        step = _make_hybrid_step(dev, pull, push, sigma, rps, qcap,
                                 lazy=lazy, direction=direction,
                                 num_vss=p.num_vss, n_fwords=p.n_fwords,
                                 dummy_vertex=p.n, R=p.max_vss_per_set,
                                 push_cap=push_cap, alpha=alpha)
        d = jax.lax.axis_index(axis)

        @jax.named_scope("bfs.finalize")
        def finalize(state: _BlestState, lvl) -> _BlestState:
            # local fused sweep over THIS shard's rows; its local set flags
            # are meaningless (sets are global) and discarded
            if lazy:
                lv_loc, fw_loc, _ = fin(state.levels[:rps], lvl,
                                        marks=state.marks[:rps])
                levels = jnp.concatenate([lv_loc, state.levels[rps:]])
            else:
                _, fw_loc, _ = fin(state.levels[:rps], lvl)
                levels = state.levels
            # the one cross-device term: σ-bit frontier words, all-gathered
            F = frontier_all_gather(fw_loc, axis)  # (n_fwords,)
            set_active = _frontier_bytes(F, all_sets, sigma) != 0
            Q, count = compact(set_active)
            unvisited = state.unvisited - jnp.sum(
                jax.lax.population_count(F)).astype(jnp.int32)
            return state._replace(levels=levels, F=F, Q=Q, count=count,
                                  unvisited=unvisited,
                                  cont=global_any(count > 0, axis))

        pipe = LevelPipeline(step=step, finalize=finalize,
                             active=lambda s: s.cont)

        # init: local levels/marks, global frontier + per-shard queue
        lsrc = src - d * rps
        own = (lsrc >= 0) & (lsrc < rps)
        levels = jnp.full((rps + 1,), INF, dtype=jnp.int32)
        levels = levels.at[jnp.where(own, lsrc, rps)].set(
            jnp.where(own, 0, INF))
        F = jnp.zeros((p.n_fwords,), dtype=jnp.uint32)
        F = F.at[src // 32].set(jnp.uint32(1) << (src % 32).astype(jnp.uint32))
        set0 = jnp.zeros((p.n_sets,), dtype=bool).at[src // sigma].set(True)
        Q, count = compact(set0)
        marks0 = jnp.zeros((rps + 1 if lazy else 1,), dtype=jnp.uint8)
        state = _BlestState(levels, F, Q, count, marks0,
                            jnp.int32(p.n - 1), global_any(count > 0, axis))
        state, _ = run_levels(pipe, state, max_levels=max_lv)
        return state.levels[None, :rps]

    fn = jax.shard_map(local_loop, mesh=mesh,
                       in_specs=problem_specs(axis) + (P(),),
                       out_specs=P(axis), check_vma=False)

    def bfs(src: jnp.ndarray) -> jnp.ndarray:
        out = fn(p.dev.masks, p.dev.row_ids, p.dev.virtual_to_real,
                 p.dev.vss_of_vertex_start, p.dev.vss_of_vertex_end,
                 jnp.asarray(src, dtype=jnp.int32))
        return out.reshape(-1)[:p.n]

    return jax.jit(bfs)


def _make_blest_bfs_sharded_2d(p: BlestProblem, *, pull: PullFn,
                               qcap: int, max_lv: int,
                               direction: str) -> Callable:
    """The 2-D (row × column) mesh-native BLEST engine (DESIGN §2.4).

    Device (i, j) pulls its ROW block of vertices from its COLUMN block of
    frontier words, so per level it runs the same chunked pull as the 1-D
    engine over ``1/cols`` of the frontier (each device over its own live
    count, no collective inside the chunk loop), then two butterfly collectives
    replace the flat all-gather: an OR-allreduce of the packed partial hit
    words over the COLUMN axis (every column block saw a different frontier
    slice, so hits are partial), and a segment all-gather of the fresh
    frontier words over the ROW axis (device (i, j) contributes row block
    i's j-th word segment, receiving its full column block).  Per-device
    volume shrinks by ``cols`` vs the flat gather — the point of the
    partition.  Convergence is one psum over BOTH axes.

    The 2-D partition is pull-only: hits are accumulated as marks and
    reduced BEFORE levels update (a partial eager scatter-min would commit
    local hits that another column block already discovered at an earlier
    level — wrong), which makes the eager and lazy variants compile to the
    same mark-based body; forced ``direction="push"`` is a ConfigError
    (the frontier-bit vertex queue of the push phase indexes GLOBAL
    frontier replicas that no 2-D device holds).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs2d
    from repro.distributed.collectives import (butterfly_frontier_exchange,
                                               butterfly_or_allreduce)

    if direction == "push":
        raise ConfigError(
            "the 2-D row × column partition is pull-only (DESIGN §2.4); "
            "direction='push' needs the global frontier replica only the "
            "1-D partition holds — use a 1-D mesh or direction='pull'")
    mesh, rax, cax = p.mesh, p.axis, p.col_axis
    sigma = p.sigma
    rps = p.rows_per_shard
    lwords = rps // 32
    cpb = p.cols_per_block
    wpc = lwords // p.n_col_shards       # words per column segment
    ncw = p.n_fwords                     # per-device column-block words
    all_sets = jnp.arange(p.n_sets, dtype=jnp.int32)

    def local_loop(masks, row_ids, v2r, vstart, vend, src):
        """One device block's slice of the fused BFS (under shard_map)."""
        dev = ShardedBVSSDevice(masks[0], row_ids[0], v2r[0], vstart[0],
                                vend[0])
        compact = make_compactor(dev, p.num_vss, qcap)
        i = jax.lax.axis_index(rax)
        j = jax.lax.axis_index(cax)

        def step(state: _BlestState, lvl) -> _BlestState:
            def chunk(i, ids, marks):
                fbytes = _frontier_bytes(state.F,
                                         dev.virtual_to_real[ids], sigma)
                hits = pull(dev.masks[ids], fbytes, sigma)
                return marks.at[dev.row_ids[ids].reshape(-1)].max(
                    hits.reshape(-1).astype(jnp.uint8))
            marks = pull_chunks(state.Q, chunk_count(state.count), chunk,
                                jnp.zeros((rps + 1,), dtype=jnp.uint8))
            return state._replace(marks=marks)

        def finalize(state: _BlestState, lvl) -> _BlestState:
            # partial hits -> full row-block hits (butterfly OR over cols)
            hw = butterfly_or_allreduce(
                _pack_bits(state.marks[:rps] > 0, lwords), cax)
            newly = _unpack_bits(hw) & (state.levels[:rps] == INF)
            levels = jnp.concatenate(
                [jnp.where(newly, lvl, state.levels[:rps]),
                 state.levels[rps:]])
            # fresh frontier: this row block's j-th word segment, exchanged
            # along the row axis into the full column block
            seg = jax.lax.dynamic_slice_in_dim(_pack_bits(newly, lwords),
                                               j * wpc, wpc)
            F = butterfly_frontier_exchange(seg, rax)       # (ncw,)
            set_active = _frontier_bytes(F, all_sets, sigma) != 0
            Q, count = compact(set_active)
            return state._replace(levels=levels, F=F, Q=Q, count=count,
                                  cont=global_any(count > 0, (rax, cax)))

        pipe = LevelPipeline(step=step, finalize=finalize,
                             active=lambda s: s.cont)

        # init: local levels; frontier bit only on the owning column block
        lsrc = src - i * rps
        own = (lsrc >= 0) & (lsrc < rps)
        levels = jnp.full((rps + 1,), INF, dtype=jnp.int32)
        levels = levels.at[jnp.where(own, lsrc, rps)].set(
            jnp.where(own, 0, INF))
        off = src % rps
        ownc = (off // cpb) == j
        c = jnp.clip((src // rps) * cpb + (off - j * cpb), 0, ncw * 32 - 1)
        F = jnp.zeros((ncw,), dtype=jnp.uint32)
        F = F.at[c // 32].set(jnp.where(
            ownc, jnp.uint32(1) << (c % 32).astype(jnp.uint32),
            jnp.uint32(0)))
        set_active = _frontier_bytes(F, all_sets, sigma) != 0
        Q, count = compact(set_active)
        marks0 = jnp.zeros((rps + 1,), dtype=jnp.uint8)
        state = _BlestState(levels, F, Q, count, marks0, jnp.int32(p.n - 1),
                            global_any(count > 0, (rax, cax)))
        state, _ = run_levels(pipe, state, max_levels=max_lv)
        return state.levels[None, :rps]

    fn = jax.shard_map(local_loop, mesh=mesh,
                       in_specs=problem_specs2d(rax, cax) + (P(),),
                       out_specs=P((rax, cax)), check_vma=False)

    def bfs(src: jnp.ndarray) -> jnp.ndarray:
        out = fn(p.dev.masks, p.dev.row_ids, p.dev.virtual_to_real,
                 p.dev.vss_of_vertex_start, p.dev.vss_of_vertex_end,
                 jnp.asarray(src, dtype=jnp.int32))
        # (R·C, rps) row-major blocks, column-replicated: take column 0
        return out.reshape(p.n_shards, p.n_col_shards,
                           rps)[:, 0].reshape(-1)[:p.n]

    return jax.jit(bfs)


# ---------------------------------------------------------------------------
# BRS baseline (BerryBees-like): frontier-oblivious slice-set sweep
# ---------------------------------------------------------------------------
class _BrsState(NamedTuple):
    levels: jnp.ndarray
    F: jnp.ndarray
    cont: jnp.ndarray


def make_brs_bfs(problem: BlestProblem, *, max_levels: int | None = None
                 ) -> Callable:
    p = problem
    if p.mesh is not None:
        if p.is_2d:
            raise ConfigError(
                "the BRS baseline has no 2-D partition path — prepare with "
                "a 1-D mesh or the blest/blest_lazy engines")
        return _make_brs_bfs_sharded(p, max_levels=max_levels)
    dev = p.dev
    sigma = p.sigma
    n_pad = p.n_fwords * 32
    max_lv = max_levels if max_levels is not None else p.n + 1
    all_ids = jnp.arange(p.num_vss, dtype=jnp.int32)

    # every slice set visited, every level (paper drawback #2)
    def gather(s: _BrsState):
        return (dev.masks[all_ids],
                _frontier_bytes(s.F, dev.virtual_to_real[all_ids], sigma))

    def update(s: _BrsState, hits, lvl) -> _BrsState:
        rows = dev.row_ids[all_ids].reshape(-1)
        upd = jnp.where(hits.reshape(-1), lvl, INF).astype(jnp.int32)
        return s._replace(levels=s.levels.at[rows].min(upd))

    def finalize(s: _BrsState, lvl) -> _BrsState:
        new = s.levels[:p.n] == lvl
        new_pad = jnp.zeros((n_pad,), dtype=bool).at[:p.n].set(new)
        return s._replace(F=_pack_bits(new_pad, p.n_fwords), cont=new.any())

    pipe = LevelPipeline(
        step=compose_step(gather, lambda m, fb: pull_vss_jnp(m, fb, sigma),
                          update),
        finalize=finalize, active=lambda s: s.cont)

    def bfs(src: jnp.ndarray) -> jnp.ndarray:
        src = jnp.asarray(src, dtype=jnp.int32)
        levels = jnp.full((p.n + 1,), INF, dtype=jnp.int32).at[src].set(0)
        F = jnp.zeros((p.n_fwords,), dtype=jnp.uint32)
        F = F.at[src // 32].set(jnp.uint32(1) << (src % 32).astype(jnp.uint32))
        state = _BrsState(levels, F, jnp.bool_(True))
        state, _ = run_levels(pipe, state, max_levels=max_lv)
        return state.levels[:p.n]

    return jax.jit(bfs)


def _make_brs_bfs_sharded(p: BlestProblem, *, max_levels: int | None
                          ) -> Callable:
    """Mesh-native BRS: the frontier-oblivious sweep visits every VSS of
    every SHARD each level (paper drawback #2 doesn't shrink under a mesh —
    that is the point of the baseline); only the frontier words cross
    devices."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.bfs_dist import problem_specs

    mesh, axis = p.mesh, p.axis
    sigma = p.sigma
    rps = p.rows_per_shard
    lwords = rps // 32
    max_lv = max_levels if max_levels is not None else p.n + 1
    all_ids = jnp.arange(p.num_vss, dtype=jnp.int32)

    def local_loop(masks, row_ids, v2r, vstart, vend, src):
        dev = ShardedBVSSDevice(masks[0], row_ids[0], v2r[0], vstart[0],
                                vend[0])
        d = jax.lax.axis_index(axis)

        def gather(s: _BrsState):
            return (dev.masks[all_ids],
                    _frontier_bytes(s.F, dev.virtual_to_real[all_ids], sigma))

        def update(s: _BrsState, hits, lvl) -> _BrsState:
            rows = dev.row_ids[all_ids].reshape(-1)
            upd = jnp.where(hits.reshape(-1), lvl, INF).astype(jnp.int32)
            return s._replace(levels=s.levels.at[rows].min(upd))

        def finalize(s: _BrsState, lvl) -> _BrsState:
            new = s.levels[:rps] == lvl
            fw_loc = _pack_bits(new, lwords)
            F = frontier_all_gather(fw_loc, axis)
            return s._replace(F=F, cont=global_any(new.any(), axis))

        pipe = LevelPipeline(
            step=compose_step(gather,
                              lambda m, fb: pull_vss_jnp(m, fb, sigma),
                              update),
            finalize=finalize, active=lambda s: s.cont)

        lsrc = src - d * rps
        own = (lsrc >= 0) & (lsrc < rps)
        levels = jnp.full((rps + 1,), INF, dtype=jnp.int32)
        levels = levels.at[jnp.where(own, lsrc, rps)].set(
            jnp.where(own, 0, INF))
        F = jnp.zeros((p.n_fwords,), dtype=jnp.uint32)
        F = F.at[src // 32].set(jnp.uint32(1) << (src % 32).astype(jnp.uint32))
        state = _BrsState(levels, F, jnp.bool_(True))
        state, _ = run_levels(pipe, state, max_levels=max_lv)
        return state.levels[None, :rps]

    fn = jax.shard_map(local_loop, mesh=mesh,
                       in_specs=problem_specs(axis) + (P(),),
                       out_specs=P(axis), check_vma=False)

    def bfs(src: jnp.ndarray) -> jnp.ndarray:
        out = fn(p.dev.masks, p.dev.row_ids, p.dev.virtual_to_real,
                 p.dev.vss_of_vertex_start, p.dev.vss_of_vertex_end,
                 jnp.asarray(src, dtype=jnp.int32))
        return out.reshape(-1)[:p.n]

    return jax.jit(bfs)


# ---------------------------------------------------------------------------
# dense bitmap pull (naive SpMSpV lower bound)
# ---------------------------------------------------------------------------
def make_dense_pull_bfs(g: Graph, *, max_levels: int | None = None) -> Callable:
    n = g.n
    n_words = (n + 31) // 32
    adj = jnp.asarray(to_dense_bits(g))  # (n, n_words) of A^T
    max_lv = max_levels if max_levels is not None else n + 1

    def bfs(src: jnp.ndarray) -> jnp.ndarray:
        src = jnp.asarray(src, dtype=jnp.int32)
        levels = jnp.full((n,), INF, dtype=jnp.int32).at[src].set(0)
        F = jnp.zeros((n_words,), dtype=jnp.uint32)
        F = F.at[src // 32].set(jnp.uint32(1) << (src % 32).astype(jnp.uint32))

        def cond(state):
            return state[2] & (state[3] < max_lv)

        def body(state):
            levels, F, _, lvl = state
            lvl = lvl + 1
            y = jnp.any(adj & F[None, :], axis=1)
            new = y & (levels == INF)
            levels = jnp.where(new, lvl, levels)
            new_pad = jnp.zeros((n_words * 32,), dtype=bool).at[:n].set(new)
            return levels, _pack_bits(new_pad, n_words), new.any(), lvl

        state = (levels, F, jnp.bool_(True), jnp.int32(0))
        levels, *_ = jax.lax.while_loop(cond, body, state)
        return levels

    return jax.jit(bfs)


# ---------------------------------------------------------------------------
# CSR edge-parallel baselines (push / pull / direction-optimised)
# ---------------------------------------------------------------------------
def make_csr_bfs(g: Graph, mode: str = "push", *, alpha: float = 15.0,
                 max_levels: int | None = None) -> Callable:
    """Edge-parallel BFS baselines.

    push: next[dst] |= frontier[src] over all out-edges.
    pull: next[u] |= frontier[v] over all in-edges (v -> u), unvisited u only.
    dirop: Beamer switching between the two on scout-count heuristic.
    """
    if mode not in ("push", "pull", "dirop"):
        raise GraphValidationError(
            f"CSR BFS mode must be one of ('push', 'pull', 'dirop'), "
            f"got {mode!r}")
    n = g.n
    e_src = jnp.asarray(src_of_edges(g).astype(np.int32))
    e_dst = jnp.asarray(g.indices.astype(np.int32))
    out_deg = jnp.asarray(g.out_degree.astype(np.int32))
    m = g.m
    max_lv = max_levels if max_levels is not None else n + 1

    def push_step(frontier, levels):
        nxt = jnp.zeros((n,), dtype=jnp.uint8)
        nxt = nxt.at[e_dst].max(frontier[e_src].astype(jnp.uint8))
        return (nxt > 0) & (levels == INF)

    def pull_step(frontier, levels):
        # pull for u over its in-edges (v -> u): mask by unvisited dst FIRST
        # (the work-saving property of pull), then scatter.
        unvis = levels == INF
        vals = frontier[e_src] & unvis[e_dst]
        nxt = jnp.zeros((n,), dtype=jnp.uint8)
        nxt = nxt.at[e_dst].max(vals.astype(jnp.uint8))
        return nxt > 0

    def bfs(src: jnp.ndarray) -> jnp.ndarray:
        src = jnp.asarray(src, dtype=jnp.int32)
        levels = jnp.full((n,), INF, dtype=jnp.int32).at[src].set(0)
        frontier = jnp.zeros((n,), dtype=bool).at[src].set(True)

        def cond(state):
            return state[2] & (state[3] < max_lv)

        def body(state):
            levels, frontier, _, lvl = state
            lvl = lvl + 1
            if mode == "push":
                new = push_step(frontier, levels)
            elif mode == "pull":
                new = pull_step(frontier, levels)
            else:
                scout = jnp.sum(jnp.where(frontier, out_deg, 0))
                use_pull = scout * alpha > m
                new = jax.lax.cond(use_pull, pull_step, push_step,
                                   frontier, levels)
            levels = jnp.where(new, lvl, levels)
            return levels, new, new.any(), lvl

        state = (levels, frontier, jnp.bool_(True), jnp.int32(0))
        levels, *_ = jax.lax.while_loop(cond, body, state)
        return levels

    return jax.jit(bfs)


# ---------------------------------------------------------------------------
# engine registry
# ---------------------------------------------------------------------------
def make_engine(g: Graph, engine: str, *, sigma: int = 8,
                bvss: BVSS | None = None,
                problem: BlestProblem | None = None,
                pull_impl: PullFn | None = None,
                push_impl: PushFn | None = None,
                use_kernels: bool = True,
                direction: str = "auto", push_cap: int | None = None,
                alpha: float = 4.0,
                n_sources: int | None = None,
                counters: bool = False) -> Callable:
    """Build a jitted BFS callable ``f(src) -> levels`` for the named engine.

    ``problem`` lets callers that already hold a :class:`BlestProblem`
    (core.policy.prepare, GraphSession) skip rebuilding the device BVSS;
    a mesh-sharded problem routes the BVSS engines through the
    ``shard_map``'d pipeline (DESIGN §2.4).
    ``direction``/``push_cap``/``alpha`` are the hybrid knobs
    (DESIGN §2.8) of the blest/blest_lazy/multi_source engines;
    ``push_impl`` is the push-kernel fault seam.
    ``counters=True`` makes the single-device blest/blest_lazy engines
    return ``(levels, counts)`` (:func:`make_blest_bfs`).
    ``engine="multi_source"`` builds the batched BVSS bit-SpMM engine
    ``f(sources (S,)) -> levels (n, S)`` and requires ``n_sources``.
    """
    if counters and engine not in ("blest", "blest_lazy"):
        raise ConfigError(f"engine {engine!r} keeps no level counters")
    if engine == "dense_pull":
        return make_dense_pull_bfs(g)
    if engine in ("csr_push", "csr_pull", "dirop"):
        mode = {"csr_push": "push", "csr_pull": "pull", "dirop": "dirop"}[engine]
        return make_csr_bfs(g, mode)
    if engine in ("brs", "blest", "blest_lazy", "multi_source"):
        if problem is None:
            from repro.core.bvss import build_bvss
            b = bvss if bvss is not None else build_bvss(g, sigma=sigma)
            problem = BlestProblem.build(b)
        if engine == "multi_source":
            from repro.core.multi_source import make_multi_source_bfs
            if n_sources is None:
                raise ValueError("multi_source engine needs n_sources")
            return make_multi_source_bfs(g, n_sources, problem=problem,
                                         use_kernel=use_kernels,
                                         direction=direction,
                                         push_cap=push_cap)
        if engine == "brs":
            return make_brs_bfs(problem)
        return make_blest_bfs(problem, lazy=(engine == "blest_lazy"),
                              pull_impl=pull_impl, push_impl=push_impl,
                              use_kernels=use_kernels, direction=direction,
                              push_cap=push_cap, alpha=alpha,
                              counters=counters)
    raise ValueError(f"unknown engine {engine!r}")


ENGINES = ("dense_pull", "csr_push", "csr_pull", "dirop", "brs", "blest",
           "blest_lazy")
# engines with a (sources (S,)) -> (n, S) signature, built via n_sources=
MULTI_ENGINES = ("multi_source",)
