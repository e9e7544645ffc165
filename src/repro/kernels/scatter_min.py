"""Pallas TPU kernel: the SSSP relaxation's scatter-min (DESIGN §2.9).

Each chunk of the delta-stepping pull yields one candidate distance per
(source, slice row) pair, and the update is

    dist[s, r] = min(dist[s, r], y[s, p])    for every pair p with row r

XLA lowers ``dist.at[:, rows].min(y)`` to an element scatter that pays
for every index.  This kernel keeps the distance rows in VMEM for the
whole call and walks the pairs with scalar control: a pair reads the
(8, 128) tile that holds its row, takes the min at the row's place and
writes the tile back.  A ``+inf`` value changes nothing, so every pair
takes the same path: a scalar compare and branch to skip it costs more
(the branch's delay slots and the predicate chain, ~11 bundles) than
the read-min-write itself.

The read-min-writes of one buffer form a chain (a tile load waits for
the store before it), so the pairs are dealt round robin to
:data:`BANKS` copies of the distances: the first holds them, the others
start at ``+inf``, and at the end the first takes the min of all.  The
chains of different banks overlap, and ``min`` is exact and order-free,
so the result is bitwise that of the XLA scatter.

Layout.  The ``(S, R)`` distances are viewed as ``(S, R/128, 128)``
lane rows; a pair's row id arrives packed as the sublane offset of its
tile (``(r // 1024) * 8``) over its place in the tile (``r % 1024``),
so a load's dynamic offset is a multiple of 8 and no load has a dynamic
lane offset.  They enter VMEM by one DMA at the first grid step and
leave by one at the last, into the same HBM buffer (the input is
aliased to the output).  The pairs' row ids and values stream through
SMEM in blocks of :data:`BLOCK` pairs, one block a grid step; blocks
past the live count keep the last live block's index, so they copy
nothing, and the pair loop runs only over the live pairs (``n_pairs``,
scalar-prefetched), :data:`UNROLL` pairs a trip.

Shape rule.  The banks have to fit the VMEM the kernel asks for
(:data:`VMEM_DIST_BYTES`); :func:`fits` decides it from the static shape
and :func:`scatter_min` falls back to the XLA scatter where they do not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SHIFT = 10
TILE = 1 << SHIFT          # rows per (8, 128) float32 tile
BLOCK = 8192               # pairs a grid step streams through SMEM
UNROLL = 32                # pairs a trip of the pair loop
BANKS = 4                  # copies of the distances the pairs are dealt to
VMEM_DIST_BYTES = 32 << 20  # largest set of banks held in VMEM
VMEM_LIMIT_BYTES = 40 << 20


def fits(n_sources: int, n_rows: int) -> bool:
    """Whether :data:`BANKS` copies of ``(n_sources, n_rows)`` float32
    distances (padded to whole (8, 128) tiles) fit the VMEM the kernel
    asks for."""
    padded = -(-n_rows // TILE) * TILE
    return BANKS * n_sources * padded * 4 <= VMEM_DIST_BYTES


def _kernel(n_ref, rows_ref, vals_ref, d_hbm, out_hbm, *scratch,
            n_sources: int, block: int, unroll: int, banks: int):
    bank_refs, sem = scratch[:banks], scratch[banks]
    dist = bank_refs[0]
    n_tiles = dist.shape[1] // 8
    b = pl.program_id(0)

    def tile(i):
        return pl.ds(pl.multiple_of(i * 8, 8), 8)

    def each_tile(body):
        g = next(k for k in (8, 4, 2, 1) if n_tiles % k == 0)

        def step(i, carry):
            for k in range(g):
                for s in range(n_sources):
                    body(s, tile(i * g + k))
            return carry
        jax.lax.fori_loop(0, n_tiles // g, step, 0)

    @pl.when(b == 0)
    def _():
        cp = pltpu.make_async_copy(d_hbm, dist, sem)
        cp.start()
        inf = jnp.full((8, LANES), jnp.inf, jnp.float32)

        def fill(s, at):
            for bank in bank_refs[1:]:
                bank[s, at, :] = inf
        if banks > 1:
            each_tile(fill)
        cp.wait()

    flat = (jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1))

    def update(bank, w, vs):
        # the place in the tile is taken on the vector unit: the scalar
        # unit, one load slot and two ALUs, bounds the loop
        hit = flat == (jnp.full((8, LANES), w, jnp.int32) & (TILE - 1))
        at = pl.ds(pl.multiple_of(w >> SHIFT, 8), 8)
        for s, v in enumerate(vs):
            t = bank[s, at, :]
            bank[s, at, :] = jnp.minimum(t, jnp.where(hit, v, jnp.inf))

    def pairs(first, k):
        for u in range(k):
            update(bank_refs[u % banks], rows_ref[first + u],
                   [vals_ref[s * block + first + u]
                    for s in range(n_sources)])

    def trip(g, carry):
        pairs(g * unroll, unroll)
        return carry

    def tail(p, carry):
        pairs(p, 1)
        return carry

    live = jnp.clip(n_ref[0] - b * block, 0, block)
    jax.lax.fori_loop(0, live // unroll, trip, 0)
    jax.lax.fori_loop(live // unroll * unroll, live, tail, 0)

    @pl.when(b == pl.num_programs(0) - 1)
    def _():
        def merge(s, at):
            m = dist[s, at, :]
            for bank in bank_refs[1:]:
                m = jnp.minimum(m, bank[s, at, :])
            dist[s, at, :] = m
        if banks > 1:
            each_tile(merge)
        cp = pltpu.make_async_copy(dist, out_hbm, sem)
        cp.start()
        cp.wait()


def scatter_min_kernel(dist: jnp.ndarray, rows: jnp.ndarray,
                       vals: jnp.ndarray, n_pairs=None, *,
                       interpret: bool | None = None) -> jnp.ndarray:
    """``dist.at[:, rows].min(vals)`` over the first ``n_pairs`` pairs.

    dist:    (S, R) float32 distances; the result is written into
             their buffer (the input is aliased to the output), in place
             where the caller no longer reads them, as in a loop carry.
    rows:    (P,) int32 row ids, ≥ 0; a row at or past R is dropped, as
             the XLA scatter drops it.
    vals:    (S, P) float32 candidate distances, ``+inf`` for none.
    n_pairs: () int32 live pairs (a prefix of the P); all of them when
             omitted.  Pairs past it are not read.
    Returns (S, R) float32, bitwise ``min`` of each row over its pairs.
    Where R is not a whole number of (8, 128) tiles the distances are
    padded into a copy, and the result sliced out of it.
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    n = rows.shape[0] if n_pairs is None else n_pairs
    # interpreted, one pair a bank a trip: unrolling only makes the
    # traced program larger
    return _scatter_min(dist, rows, vals, n, block=BLOCK,
                        unroll=BANKS if interpret else UNROLL, banks=BANKS,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "unroll", "banks",
                                             "interpret"))
def _scatter_min(dist, rows, vals, n_pairs, *, block: int, unroll: int,
                 banks: int, interpret: bool):
    S, R = dist.shape
    P = rows.shape[0]
    # a row past the end is dropped: its value becomes +inf at row 0
    keep = (rows >= 0) & (rows < R)
    vals = jnp.where(keep, vals, jnp.inf)
    rows = jnp.where(keep, rows, 0)
    rows = ((rows >> SHIFT) << (SHIFT + 3)) | (rows & (TILE - 1))
    block = min(block, -(-P // LANES) * LANES)
    nb = -(-P // block)
    Pp, Rp = nb * block, -(-R // TILE) * TILE
    if Pp != P:
        rows = jnp.pad(rows, (0, Pp - P))
        vals = jnp.pad(vals, ((0, 0), (0, Pp - P)),
                       constant_values=jnp.inf)
    d = dist if Rp == R else jnp.pad(dist, ((0, 0), (0, Rp - R)),
                                     constant_values=jnp.inf)
    n = jnp.clip(jnp.asarray(n_pairs, jnp.int32), 0, P).reshape(1)
    # the S values of one block's pairs lie together: block b holds
    # vals[:, b·block:(b+1)·block] source by source
    vals = vals.reshape(S, nb, block).transpose(1, 0, 2).reshape(-1)

    def blk(i, n_ref):
        return (jnp.minimum(i, jnp.maximum((n_ref[0] + block - 1) // block
                                           - 1, 0)),)

    any_ = pl.BlockSpec(memory_space=pl.ANY)
    lanes = jax.ShapeDtypeStruct((S, Rp // LANES, LANES), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, n_sources=S, block=block, unroll=unroll,
                          banks=banks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((block,), blk, memory_space=pltpu.SMEM),
                pl.BlockSpec((S * block,), blk, memory_space=pltpu.SMEM),
                any_,
            ],
            out_specs=any_,
            scratch_shapes=[pltpu.VMEM(lanes.shape, jnp.float32)] * banks
            + [pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=lanes,
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="sssp_scatter_min",
    )(n, rows, vals, d.reshape(lanes.shape))
    out = out.reshape(S, Rp)
    return out if Rp == R else out[:, :R]


def scatter_min(dist: jnp.ndarray, rows: jnp.ndarray, vals: jnp.ndarray,
                n_pairs=None) -> jnp.ndarray:
    """The scatter-min by the kernel where its banks fit VMEM
    (:func:`fits`, from the static shape), else by XLA's scatter; the
    same result either way."""
    if fits(*dist.shape):
        return scatter_min_kernel(dist, rows, vals, n_pairs)
    from repro.kernels.ref import scatter_min_ref
    return scatter_min_ref(dist, rows, vals, n_pairs)
