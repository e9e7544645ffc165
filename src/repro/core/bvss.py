"""Binarised Virtual Slice Sets (BVSS) — the paper's core data structure (§3).

Host-side construction is vectorised NumPy; ``to_device`` hands the arrays to
JAX.  Layout (σ = slice width in bits, LANES = 32 words per VSS row-group,
slices_per_word = 32 // σ, τ = LANES * slices_per_word):

* column *intervals* of σ consecutive columns of A^T form *slice sets*;
* a (row u, interval i) pair with ≥1 edge is a *slice*, its σ-bit mask holds
  A^T[u, σi : σ(i+1)];
* each slice set is split into *virtual* slice sets of ≤ τ slices (the unit
  of work), the last VSS of a set is padded to τ with zero masks / dummy rows;
* within a VSS, slices sorted by row id are laid out column-major over
  (slot, lane): slice k lives in lane ``k % 32``, sub-word slot ``k // 32``
  — the paper's Fig. 2(c) layout, which maximises update coalescing.

On TPU there are no warps: a "lane" here is one 32-bit vector lane, and one
(8,128) vreg holds 8 VSS mask rows; every 32-bit AND+popcount resolves
``slices_per_word`` slice/frontier dot products — the adaptation of the
paper's all-outputs-useful TC layout (Fig. 2(c), §4.1).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

from repro.errors import ConfigError, GraphValidationError
from repro.graphs import Graph

LANES = 32  # 32-bit words per VSS row-group (paper: WARP_SIZE)


@dataclasses.dataclass(frozen=True)
class BVSS:
    """Host-side BVSS arrays + structural metadata."""

    n: int                      # number of vertices
    m: int                      # number of edges
    sigma: int                  # slice width (bits)
    tau: int                    # slices per VSS = LANES * (32 // sigma)
    n_sets: int                 # ceil(n / sigma) real slice sets
    num_vss: int
    num_slices: int             # unpadded slices
    # static arrays (paper §3.1)
    masks: np.ndarray           # (num_vss, LANES) uint32; slot j of word l
                                #   = mask of slice k = j*LANES + l
    row_ids: np.ndarray         # (num_vss, 32//sigma, LANES) int32; dummy = n
    real_ptrs: np.ndarray       # (n_sets + 1,) int32: slice set -> VSS range
    virtual_to_real: np.ndarray  # (num_vss,) int32

    @property
    def slices_per_word(self) -> int:
        return 32 // self.sigma

    @property
    def n_frontier_words(self) -> int:
        """Frontier bit-array length in uint32 words (σ-bit set granularity)."""
        return (self.n_sets * self.sigma + 31) // 32

    @property
    def max_vss_per_set(self) -> int:
        """Largest VSS count of any slice set — the static expansion factor
        of the push phase (each pushing vertex enqueues every VSS of its own
        set, DESIGN §2.8)."""
        if self.n_sets == 0:
            return 1
        return max(int(np.diff(self.real_ptrs).max()), 1)

    # ---------------- analytics (paper Tables 1 & 4) ----------------
    def compression_ratio(self) -> float:
        """m / (num_slices * σ): fraction of set bits in unpadded masks."""
        if self.num_slices == 0:
            return 1.0
        return self.m / (self.num_slices * self.sigma)

    def connectivity_bits(self) -> int:
        return self.num_slices * self.sigma

    def update_divergence(self) -> float:
        """Paper §3.2.1: mean over VSSs of the mean per-slot std of live row ids."""
        spw = self.slices_per_word
        sig = self.sigma
        sub_mask = np.uint32((1 << sig) - 1)
        # sub[v, j, l] = mask of slice (slot j, lane l)
        shifts = (np.arange(spw, dtype=np.uint32) * sig)[None, :, None]
        sub = (self.masks[:, None, :] >> shifts) & sub_mask
        live = sub != 0
        rows = self.row_ids.astype(np.float64)
        cnt = live.sum(axis=2)                                   # (v, j)
        s1 = np.where(live, rows, 0.0).sum(axis=2)
        s2 = np.where(live, rows * rows, 0.0).sum(axis=2)
        with np.errstate(invalid="ignore", divide="ignore"):
            mean = s1 / cnt
            var = np.maximum(s2 / cnt - mean * mean, 0.0)
            col_div = np.sqrt(var)                               # (v, j)
        nonempty = cnt > 0
        set_cnt = nonempty.sum(axis=1)
        set_div = np.where(set_cnt > 0,
                           np.where(nonempty, col_div, 0.0).sum(axis=1)
                           / np.maximum(set_cnt, 1), 0.0)
        alive = set_cnt > 0
        return float(set_div[alive].mean()) if alive.any() else 0.0

    def memory_bytes(self) -> dict[str, int]:
        """Table-4 style footprint breakdown (bytes).

        ``push`` is the hybrid engine's scatter-side working set at the
        DEFAULT auto-mode cap (DESIGN §2.8): the compacted frontier-vertex
        queue plus the (cap × max_vss_per_set) expanded (VSS id, bit) pairs
        each push level materialises.  It is a sub-term of ``dynamic`` —
        ``total`` stays ``bvss + dynamic + level``."""
        static = (self.masks.nbytes + self.row_ids.nbytes
                  + self.real_ptrs.nbytes + self.virtual_to_real.nbytes)
        pq = max(128, self.n // 8)  # default auto-mode push cap
        push = 4 * (pq + 1) + 2 * 4 * pq * self.max_vss_per_set
        dynamic = (2 * 4 * (self.num_vss + 1)
                   + 2 * 4 * self.n_frontier_words + push)
        level = 4 * (self.n + 1)
        return {"bvss": static, "dynamic": dynamic, "push": push,
                "level": level, "total": static + dynamic + level}

    # ---------------- validation helpers ----------------
    def reconstruct_edges(self) -> tuple[np.ndarray, np.ndarray]:
        """Recover (src, dst) edge lists: bit b of slice (u, i) ⇒ edge (σi+b) → u."""
        spw, sig = self.slices_per_word, self.sigma
        shifts = (np.arange(spw, dtype=np.uint32) * sig)[None, :, None]
        sub = (self.masks[:, None, :] >> shifts) & np.uint32((1 << sig) - 1)
        vss_idx, slot, lane = np.nonzero(sub)
        sub_v = sub[vss_idx, slot, lane]
        rows = self.row_ids[vss_idx, slot, lane].astype(np.int64)
        sets = self.virtual_to_real[vss_idx].astype(np.int64)
        src_l, dst_l = [], []
        for b in range(sig):
            has = (sub_v >> np.uint32(b)) & 1 != 0
            src_l.append(sets[has] * sig + b)
            dst_l.append(rows[has])
        return np.concatenate(src_l), np.concatenate(dst_l)


def build_bvss(g: Graph, sigma: int = 8) -> BVSS:
    if not (1 <= sigma <= 32 and 32 % sigma == 0):
        raise GraphValidationError(
            f"sigma must be a divisor of 32 in [1, 32], got {sigma!r}")
    spw = 32 // sigma
    tau = LANES * spw
    n, m = g.n, g.m
    n_sets = (n + sigma - 1) // sigma

    t_indptr, t_indices = g.t_csr
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(t_indptr))
    cols = t_indices.astype(np.int64)
    interval = cols // sigma
    bit = (cols % sigma).astype(np.uint32)

    # unique (interval, row) pairs, interval-major / row-ascending
    keys = interval * n + rows
    ukeys, inverse = np.unique(keys, return_inverse=True)
    num_slices = len(ukeys)
    slice_mask = np.zeros(num_slices, dtype=np.uint32)
    np.bitwise_or.at(slice_mask, inverse, np.uint32(1) << bit)
    slice_interval = (ukeys // n).astype(np.int64)
    slice_row = (ukeys % n).astype(np.int32)

    # slices per set -> VSS counts -> realPtrs
    set_counts = np.bincount(slice_interval, minlength=n_sets)
    vss_counts = (set_counts + tau - 1) // tau
    real_ptrs = np.zeros(n_sets + 1, dtype=np.int32)
    real_ptrs[1:] = np.cumsum(vss_counts)
    num_vss = int(real_ptrs[-1])
    virtual_to_real = np.repeat(np.arange(n_sets, dtype=np.int32), vss_counts)

    # placement of each slice
    set_starts = np.zeros(n_sets + 1, dtype=np.int64)
    np.cumsum(set_counts, out=set_starts[1:])
    local = np.arange(num_slices, dtype=np.int64) - set_starts[slice_interval]
    vss = real_ptrs[slice_interval].astype(np.int64) + local // tau
    k = local % tau
    lane = (k % LANES).astype(np.int64)
    slot = (k // LANES).astype(np.int64)

    masks = np.zeros((num_vss, LANES), dtype=np.uint32)
    np.bitwise_or.at(masks.reshape(-1), vss * LANES + lane,
                     slice_mask << (slot * sigma).astype(np.uint32))
    row_ids = np.full((num_vss, spw, LANES), n, dtype=np.int32)  # dummy = n
    row_ids[vss, slot, lane] = slice_row

    return BVSS(n=n, m=m, sigma=sigma, tau=tau, n_sets=n_sets,
                num_vss=num_vss, num_slices=num_slices, masks=masks,
                row_ids=row_ids, real_ptrs=real_ptrs,
                virtual_to_real=virtual_to_real)


# ---------------------------------------------------------------------------
# Weight plane: per-edge float weights aligned with the bit slices
# (the min-plus / weighted-verb operand, DESIGN §2.9)
# ---------------------------------------------------------------------------
def build_weight_plane(g: Graph, weights: np.ndarray,
                       sigma: int = 8) -> np.ndarray:
    """Lay per-edge weights out exactly like the BVSS mask bits.

    ``weights`` is one float per CSR edge of ``g`` (``g.indices`` order).
    Returns a (num_vss, σ, τ) float32 plane, τ = 32//σ · LANES, where
    entry ``[v, i, slot·LANES + lane]`` is the weight of the edge encoded
    by bit σ·slot+i of ``masks[v, lane]`` — i.e. the same (slot, lane)
    slice placement :func:`build_bvss` computes — and +inf wherever that
    bit is unset (the tropical-semiring annihilator, so masked and missing
    edges agree).  The slices are the last, lane dimension: at σ = 8 a
    VSS's rows are one (8, 128) float32 tile, 4 KiB, with no padding.
    Parallel edges (if any survive ingress) keep the minimum weight.
    """
    if not (1 <= sigma <= 32 and 32 % sigma == 0):
        raise GraphValidationError(
            f"sigma must be a divisor of 32 in [1, 32], got {sigma!r}")
    spw = 32 // sigma
    tau = LANES * spw
    n = g.n
    n_sets = (n + sigma - 1) // sigma

    t_indptr, t_indices = g.t_csr
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(t_indptr))
    cols = t_indices.astype(np.int64)
    # t_csr edge j is original CSR edge argsort(indices)[j] (stable sort by
    # destination) — permute the weights into the same transposed order
    w_t = np.asarray(weights, dtype=np.float32)[
        np.argsort(g.indices, kind="stable")]
    interval = cols // sigma
    bit = (cols % sigma).astype(np.int64)

    # identical slice placement to build_bvss
    keys = interval * n + rows
    ukeys, inverse = np.unique(keys, return_inverse=True)
    num_slices = len(ukeys)
    slice_interval = (ukeys // n).astype(np.int64)
    set_counts = np.bincount(slice_interval, minlength=n_sets)
    vss_counts = (set_counts + tau - 1) // tau
    real_ptrs = np.zeros(n_sets + 1, dtype=np.int64)
    real_ptrs[1:] = np.cumsum(vss_counts)
    num_vss = int(real_ptrs[-1])
    set_starts = np.zeros(n_sets + 1, dtype=np.int64)
    np.cumsum(set_counts, out=set_starts[1:])
    local = np.arange(num_slices, dtype=np.int64) - set_starts[slice_interval]
    vss = real_ptrs[slice_interval] + local // tau
    k = local % tau                          # = slot·LANES + lane

    plane = np.full((num_vss, sigma, tau), np.inf, dtype=np.float32)
    np.minimum.at(plane, (vss[inverse], bit, k[inverse]), w_t)
    return plane


def build_sharded_weight_plane(g: Graph, weights: np.ndarray,
                               sb: ShardedBVSS) -> np.ndarray:
    """Row-sharded twin of :func:`build_weight_plane`: one weight plane per
    shard of ``sb``, built over the same destination-range subgraphs
    :func:`build_sharded_bvss` committed (so slice placement matches the
    sharded masks bit for bit), padded to the common VSS count with +inf.
    Returns (D, num_vss_pad, σ, τ) float32."""
    from repro.graphs import from_edges, src_of_edges

    n = g.n
    sigma = sb.sigma
    spw = sb.slices_per_word
    src = src_of_edges(g).astype(np.int64)
    dst = g.indices.astype(np.int64)
    w = np.asarray(weights, dtype=np.float32)
    D, rps = sb.n_shards, sb.rows_per_shard
    plane = np.full((D, sb.num_vss_pad, sigma, spw * LANES), np.inf,
                    dtype=np.float32)
    for d in range(D):
        lo, hi = d * rps, min((d + 1) * rps, n)
        keep = (dst >= lo) & (dst < hi)
        if not keep.any():
            continue
        # from_edges(dedup=True) emits edges in ascending (src·n + dst)
        # key order — reduce the kept weights into that order (min merges
        # parallel edges exactly like the mask OR does)
        key = src[keep] * n + (dst[keep] - lo)
        uk, inv = np.unique(key, return_inverse=True)
        wsub = np.full(len(uk), np.inf, dtype=np.float32)
        np.minimum.at(wsub, inv, w[keep])
        sub = from_edges(n, src[keep], dst[keep] - lo,
                         dedup=True, drop_loops=False)
        pd = build_weight_plane(sub, wsub, sigma=sigma)
        plane[d, :pd.shape[0]] = pd
    return plane


def weight_plane_to_device(plane: np.ndarray, mesh=None, axis: str = "data"):
    """Commit a weight plane to device, appending the +inf dummy-VSS row
    that mirrors the all-zero dummy mask row ``to_device`` /
    ``shard_to_device`` append (padded queue entries relax nothing)."""
    import jax
    import jax.numpy as jnp

    if plane.ndim == 3:                       # single device: (V, σ, τ)
        full = np.concatenate(
            [plane, np.full((1,) + plane.shape[1:], np.inf, np.float32)],
            axis=0)
        return jnp.asarray(full)
    D = plane.shape[0]                        # sharded: (D, V, σ, τ)
    full = np.concatenate(
        [plane, np.full((D, 1) + plane.shape[2:], np.inf, np.float32)],
        axis=1)
    if mesh is not None:
        from repro.distributed.bfs_dist import problem_sharding
        return jax.device_put(full, problem_sharding(mesh, axis))
    return jnp.asarray(full)


# ---------------------------------------------------------------------------
# Row-sharded BVSS (mesh-native build path, DESIGN §2.4)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardedBVSS:
    """Row-partitioned BVSS: shard d owns destination rows
    [d·rows_per_shard, (d+1)·rows_per_shard), i.e. the slices that pull INTO
    its vertex range.  Row ids are LOCAL (dummy = rows_per_shard); slice-set
    ids stay GLOBAL, because columns (frontier bits) are global — the σ-bit
    frontier words are the one all-gathered array.  All shards are padded to
    a common VSS count so one SPMD program serves every shard."""

    n: int                       # global vertex count
    m: int                       # global edge count
    sigma: int
    n_shards: int
    rows_per_shard: int          # 32-aligned so row blocks = frontier words
    num_vss_pad: int             # per-shard VSS count (padded to common max)
    n_sets: int                  # GLOBAL slice sets (columns)
    masks: np.ndarray            # (D, num_vss_pad, LANES) uint32
    row_ids: np.ndarray          # (D, num_vss_pad, spw, LANES) int32 LOCAL
    virtual_to_real: np.ndarray  # (D, num_vss_pad) int32 GLOBAL set ids
    vss_start: np.ndarray        # (D, n + 1) int32 GLOBAL vertex -> LOCAL
    vss_end: np.ndarray          #   VSS range [start, end) of the shard's
                                 #   slice sets for the vertex's own set;
                                 #   dummy vertex n maps to the empty range
    max_vss_per_set: int         # static push expansion factor (max shard)

    @property
    def slices_per_word(self) -> int:
        return 32 // self.sigma

    @property
    def n_frontier_words(self) -> int:
        """Gathered (global) frontier length in uint32 words: the all-gather
        of every shard's rows_per_shard//32 local words.  Covers n_sets·σ
        bits because rows_per_shard·D ≥ n rounded up to 32."""
        return self.n_shards * (self.rows_per_shard // 32)


def build_sharded_bvss(g: Graph, n_shards: "int | tuple[int, int]",
                       sigma: int = 8) -> "ShardedBVSS | ShardedBVSS2D":
    """Row-partition ``g`` into ``n_shards`` rectangular (local rows ×
    global columns) BVSS blocks (absorbs the old distributed ``shard_bvss``).

    Each shard's block is built by :func:`build_bvss` over the subgraph of
    edges whose DESTINATION lands in the shard's row range, destinations
    relabelled locally and sources (columns / frontier ids) kept global.

    A ``(rows, cols)`` tuple selects the 2-D row × column partition
    instead (:func:`build_sharded_bvss_2d`): device (i, j) owns the slices
    pulling its row block from its column block of frontier words."""
    if isinstance(n_shards, tuple):
        rows, cols = n_shards
        return build_sharded_bvss_2d(g, rows, cols, sigma=sigma)
    from repro.graphs import from_edges, src_of_edges

    n = g.n
    rows_per_shard = -(-n // n_shards)
    rows_per_shard = ((rows_per_shard + 31) // 32) * 32  # align frontier words
    spw = 32 // sigma
    src = src_of_edges(g)
    dst = g.indices.astype(np.int64)
    per_shard = []
    for d in range(n_shards):
        lo, hi = d * rows_per_shard, min((d + 1) * rows_per_shard, n)
        keep = (dst >= lo) & (dst < hi)
        # drop_loops=False: local dst ids numerically colliding with global
        # src ids are NOT self loops
        sub = from_edges(n, src[keep], dst[keep] - lo,
                         dedup=True, drop_loops=False)
        per_shard.append(build_bvss(sub, sigma=sigma))
    num_vss_pad = max(max(b.num_vss for b in per_shard), 1)
    D = n_shards
    masks = np.zeros((D, num_vss_pad, LANES), np.uint32)
    row_ids = np.full((D, num_vss_pad, spw, LANES), rows_per_shard, np.int32)
    # pad VSS entries keep set id 0: their masks are all-zero, so a level
    # whose frontier touches set 0 enqueues them as exact no-op pulls
    v2r = np.zeros((D, num_vss_pad), np.int32)
    # per-shard GLOBAL vertex -> LOCAL VSS range: columns are global in
    # every shard block, so each per-shard real_ptrs spans all n_sets and
    # the map mirrors to_device's vss_of_vertex_start/end per shard
    vss_start = np.zeros((D, n + 1), np.int32)
    vss_end = np.zeros((D, n + 1), np.int32)
    verts = np.arange(n, dtype=np.int64)
    sets = verts // sigma
    for d, b in enumerate(per_shard):
        vss_start[d, :n] = b.real_ptrs[sets]
        vss_end[d, :n] = b.real_ptrs[sets + 1]
        if b.num_vss == 0:
            continue
        masks[d, :b.num_vss] = b.masks
        rid = b.row_ids.copy()
        rid[rid == b.n] = rows_per_shard           # dummy -> local dummy
        row_ids[d, :b.num_vss] = np.minimum(rid, rows_per_shard)
        v2r[d, :b.num_vss] = b.virtual_to_real
    return ShardedBVSS(n=n, m=g.m, sigma=sigma, n_shards=D,
                       rows_per_shard=rows_per_shard,
                       num_vss_pad=num_vss_pad,
                       n_sets=(n + sigma - 1) // sigma,
                       masks=masks, row_ids=row_ids, virtual_to_real=v2r,
                       vss_start=vss_start, vss_end=vss_end,
                       max_vss_per_set=max(
                           max(b.max_vss_per_set for b in per_shard), 1))


# ---------------------------------------------------------------------------
# 2-D (row × column) sharded BVSS (butterfly partition, DESIGN §2.4)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardedBVSS2D:
    """2-D partitioned BVSS: device (i, j) of a ``rows × cols`` mesh owns
    the slices pulling its ROW block of destinations from its COLUMN block
    of frontier words.

    The column partition INTERLEAVES inside row blocks: column block j
    owns, within every row block i, the sources
    ``[i·rps + j·cpb, i·rps + (j+1)·cpb)`` where ``cpb = rps / cols``.
    That makes a row block's fresh frontier words split into ``cols``
    contiguous word segments (``rps`` is aligned to ``32·cols``), so the
    per-level exchange along the row axis moves exactly one segment per
    device — per-device volume shrinks by ``cols`` vs the flat 1-D gather.
    Source ids are relabelled to the column block's LOCAL space
    ``local(v) = (v // rps)·cpb + (v mod rps) − j·cpb`` of size
    ``rows · cpb``; destination rows are LOCAL to the row block (dummy =
    ``rps``).  Blocks stack row-major (block d = i·cols + j) and are
    padded to a common VSS count so one SPMD program serves all of them.
    ``rows >= cols`` is required so the local column space covers a row
    block (``rows·cpb >= rps``) — checked at build."""

    n: int
    m: int
    sigma: int
    rows: int
    cols: int
    rows_per_shard: int          # aligned to 32·cols
    cols_per_block: int          # cpb = rows_per_shard // cols
    num_vss_pad: int             # per-block VSS count (padded to common max)
    n_sets_local: int            # LOCAL slice sets = rows·cpb / sigma
    masks: np.ndarray            # (rows·cols, num_vss_pad, LANES) uint32
    row_ids: np.ndarray          # (rows·cols, num_vss_pad, spw, LANES) LOCAL
    virtual_to_real: np.ndarray  # (rows·cols, num_vss_pad) LOCAL set ids
    max_vss_per_set: int

    @property
    def n_blocks(self) -> int:
        return self.rows * self.cols

    @property
    def slices_per_word(self) -> int:
        return 32 // self.sigma

    @property
    def n_frontier_words_local(self) -> int:
        """Per-device frontier words: the device's full COLUMN block,
        ``rows`` segments of ``words_per_colseg`` words each."""
        return self.rows * self.cols_per_block // 32

    @property
    def words_per_colseg(self) -> int:
        """Words one row block contributes to one column block per level —
        the unit the butterfly exchange moves."""
        return self.cols_per_block // 32


def build_sharded_bvss_2d(g: Graph, rows: int, cols: int, sigma: int = 8
                          ) -> ShardedBVSS2D:
    """Partition ``g`` into ``rows × cols`` BVSS blocks (see
    :class:`ShardedBVSS2D` for the ownership contract)."""
    from repro.graphs import from_edges, src_of_edges

    if rows < 1 or cols < 1:
        raise ConfigError(f"2-D shard shape ({rows}, {cols}) must be "
                          f"positive")
    if rows < cols:
        raise ConfigError(
            f"2-D BVSS partition needs rows >= cols, got ({rows}, {cols})"
            f" — the interleaved column blocks must cover a row block")
    n = g.n
    rps = -(-n // rows)
    align = 32 * cols  # column segments land on frontier-word boundaries
    rps = ((rps + align - 1) // align) * align
    cpb = rps // cols
    n_loc = rows * cpb              # local column (source) space per block
    spw = 32 // sigma
    src = src_of_edges(g).astype(np.int64)
    dst = g.indices.astype(np.int64)
    soff = src % rps                # offset of each source in its row block
    sblk = src // rps               # row block each source lives in
    per_block: list[BVSS] = []
    for i in range(rows):
        lo, hi = i * rps, min((i + 1) * rps, n)
        in_row = (dst >= lo) & (dst < hi)
        for j in range(cols):
            keep = in_row & (soff // cpb == j)
            lsrc = sblk[keep] * cpb + (soff[keep] - j * cpb)
            # drop_loops=False: relabelled ids colliding are not self loops
            sub = from_edges(n_loc, lsrc, dst[keep] - lo,
                             dedup=True, drop_loops=False)
            per_block.append(build_bvss(sub, sigma=sigma))
    num_vss_pad = max(max(b.num_vss for b in per_block), 1)
    D = rows * cols
    masks = np.zeros((D, num_vss_pad, LANES), np.uint32)
    row_ids = np.full((D, num_vss_pad, spw, LANES), rps, np.int32)
    # pad VSS entries keep set id 0: all-zero masks -> exact no-op pulls
    v2r = np.zeros((D, num_vss_pad), np.int32)
    for d, b in enumerate(per_block):
        if b.num_vss == 0:
            continue
        masks[d, :b.num_vss] = b.masks
        rid = b.row_ids.copy()
        rid[rid == b.n] = rps                      # dummy -> local dummy
        row_ids[d, :b.num_vss] = np.minimum(rid, rps)
        v2r[d, :b.num_vss] = b.virtual_to_real
    return ShardedBVSS2D(n=n, m=g.m, sigma=sigma, rows=rows, cols=cols,
                         rows_per_shard=rps, cols_per_block=cpb,
                         num_vss_pad=num_vss_pad,
                         n_sets_local=n_loc // sigma,
                         masks=masks, row_ids=row_ids, virtual_to_real=v2r,
                         max_vss_per_set=max(
                             max(b.max_vss_per_set for b in per_block), 1))


class ShardedBVSSDevice(NamedTuple):
    """Per-shard device views of a :class:`ShardedBVSS` (a pytree).  The
    leading axis is the shard axis; inside ``shard_map`` each device sees
    its (1, ...) block and strips it to the same (masks, row_ids,
    virtual_to_real) surface the single-device engines consume.  One
    all-zero dummy VSS (index ``num_vss_pad``) is appended per shard, its
    rows mapped to the local dummy level slot ``rows_per_shard``."""

    masks: "jnp.ndarray"            # (D, num_vss_pad + 1, LANES) uint32
    row_ids: "jnp.ndarray"          # (D, num_vss_pad + 1, spw, LANES) int32
    virtual_to_real: "jnp.ndarray"  # (D, num_vss_pad + 1) int32
    # GLOBAL vertex -> LOCAL VSS range (push expansion); named like the
    # BVSSDevice fields so the hybrid step reads one surface in both modes
    vss_of_vertex_start: "jnp.ndarray"  # (D, n + 1) int32
    vss_of_vertex_end: "jnp.ndarray"    # (D, n + 1) int32


def shard_to_device(sb: ShardedBVSS, mesh=None, axis: str = "data"
                    ) -> ShardedBVSSDevice:
    """Append the per-shard dummy VSS and (when ``mesh`` is given) commit
    the stacked arrays with their row-partition sharding so every engine
    build and serving call starts from already-placed shards."""
    import jax
    import jax.numpy as jnp

    D = sb.n_shards
    spw = sb.slices_per_word
    masks = np.concatenate(
        [sb.masks, np.zeros((D, 1, LANES), np.uint32)], axis=1)
    row_ids = np.concatenate(
        [sb.row_ids,
         np.full((D, 1, spw, LANES), sb.rows_per_shard, np.int32)], axis=1)
    v2r = np.concatenate([sb.virtual_to_real, np.zeros((D, 1), np.int32)],
                         axis=1)
    if mesh is not None:
        from repro.distributed.bfs_dist import problem_sharding
        sharding = problem_sharding(mesh, axis)

        def put(x):
            return jax.device_put(x, sharding)
    else:
        put = jnp.asarray
    return ShardedBVSSDevice(masks=put(masks), row_ids=put(row_ids),
                             virtual_to_real=put(v2r),
                             vss_of_vertex_start=put(sb.vss_start),
                             vss_of_vertex_end=put(sb.vss_end))


def shard_to_device_2d(sb: ShardedBVSS2D, mesh=None) -> ShardedBVSSDevice:
    """2-D twin of :func:`shard_to_device`: append the per-block dummy VSS
    and commit the row-major block stack with both mesh axes on dim 0.
    The 2-D engines are pull-only (DESIGN §2.4), so the push-phase
    vertex -> VSS maps are empty placeholders that keep the
    :class:`ShardedBVSSDevice` surface uniform."""
    import jax
    import jax.numpy as jnp

    D = sb.n_blocks
    spw = sb.slices_per_word
    masks = np.concatenate(
        [sb.masks, np.zeros((D, 1, LANES), np.uint32)], axis=1)
    row_ids = np.concatenate(
        [sb.row_ids,
         np.full((D, 1, spw, LANES), sb.rows_per_shard, np.int32)], axis=1)
    v2r = np.concatenate([sb.virtual_to_real, np.zeros((D, 1), np.int32)],
                         axis=1)
    vss_start = np.zeros((D, 1), np.int32)
    vss_end = np.zeros((D, 1), np.int32)
    if mesh is not None:
        from repro.distributed.bfs_dist import problem_sharding
        sharding = problem_sharding(mesh)

        def put(x):
            return jax.device_put(x, sharding)
    else:
        put = jnp.asarray
    return ShardedBVSSDevice(masks=put(masks), row_ids=put(row_ids),
                             virtual_to_real=put(v2r),
                             vss_of_vertex_start=put(vss_start),
                             vss_of_vertex_end=put(vss_end))


class BVSSDevice(NamedTuple):
    """Device-resident BVSS (a pytree). One extra all-zero dummy VSS is
    appended so padded queue entries are harmless, and the level array gets
    an extra slot for dummy row id ``n``."""

    masks: "jnp.ndarray"            # (num_vss + 1, LANES) uint32
    row_ids: "jnp.ndarray"          # (num_vss + 1, spw, LANES) int32
    virtual_to_real: "jnp.ndarray"  # (num_vss + 1,) int32
    real_ptrs: "jnp.ndarray"        # (n_sets + 1,) int32
    vss_of_vertex_start: "jnp.ndarray"  # (n + 1,) int32 = real_ptrs[v // σ]
    vss_of_vertex_end: "jnp.ndarray"


def to_device(b: BVSS) -> BVSSDevice:
    import jax.numpy as jnp

    masks = np.concatenate([b.masks, np.zeros((1, LANES), np.uint32)], axis=0)
    row_ids = np.concatenate(
        [b.row_ids, np.full((1, b.slices_per_word, LANES), b.n, np.int32)],
        axis=0)
    v2r = np.concatenate([b.virtual_to_real, np.zeros(1, np.int32)])
    verts = np.arange(b.n, dtype=np.int64)
    sets = verts // b.sigma
    start = b.real_ptrs[sets].astype(np.int32)
    end = b.real_ptrs[sets + 1].astype(np.int32)
    # dummy vertex n: empty VSS range so a spurious mark enqueues nothing
    start = np.concatenate([start, np.zeros(1, np.int32)])
    end = np.concatenate([end, np.zeros(1, np.int32)])
    return BVSSDevice(
        masks=jnp.asarray(masks),
        row_ids=jnp.asarray(row_ids),
        virtual_to_real=jnp.asarray(v2r),
        real_ptrs=jnp.asarray(b.real_ptrs),
        vss_of_vertex_start=jnp.asarray(start),
        vss_of_vertex_end=jnp.asarray(end),
    )
