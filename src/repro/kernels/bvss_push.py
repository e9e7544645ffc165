"""Pallas TPU kernel for the BVSS push phase (direction-optimizing hybrid,
DESIGN §2.8).

The pull kernel answers "which slices of these VSSs see ANY frontier
vertex" — its frontier operand is the full σ-bit byte of each VSS's slice
set.  The push phase asks the converse question from a SMALL frontier:
each queued entry is one (frontier-vertex, VSS) pair, where the VSS is one
of the slice sets covering the vertex's own set ``v // σ``
(``BVSSDevice.vss_of_vertex_start/end``), and the frontier operand is the
SINGLE bit the vertex occupies inside its set, ``v % σ``.

That makes push the same lane computation as pull with a one-hot frontier
byte — so the kernel reuses the lane-major bit-tile layout verbatim
(masks transposed ``(32, TILE)``, all 8 sublanes carrying distinct mask
words) and simply builds the frontier word in-kernel from the bit index:
``fword = replicate(1 << b)``.  Keeping the one-hot construction inside
the kernel means the engine ships a (B,) int32 bit-index vector instead of
a materialised byte per queue entry, and the AND/extract tail is shared
idiom with ``bvss_pull``.

The payoff is queue SHAPE, not per-entry work: a push queue is sized by
``popcount(frontier) * max_vss_per_set`` instead of the pull ladder's
static fraction of ``num_vss``, so small-frontier levels touch a few
hundred lanes instead of the full bucketed pull width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bvss_pull import DEFAULT_TILE, _fword


def _push_kernel_lanes(masks_ref, bits_ref, hits_ref, *, sigma: int):
    """masks_ref (32, T) u32; bits_ref (1, T) u32 one bit index per VSS
    (the frontier vertex's ``v % σ``); hits_ref (spw*32, T) i8."""
    spw = 32 // sigma
    smask = jnp.uint32((1 << sigma) - 1)
    masks = masks_ref[...]                               # (32, T)
    fb = jnp.uint32(1) << bits_ref[...]                  # one-hot σ-bit byte
    fword = _fword(fb, sigma)                            # (1, T)
    anded = masks & fword
    for j in range(spw):
        sub = (anded >> jnp.uint32(sigma * j)) & smask
        hits_ref[j * 32:(j + 1) * 32, :] = (sub != 0).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("sigma", "tile", "interpret"))
def bvss_push(masks: jnp.ndarray, bits: jnp.ndarray, *, sigma: int = 8,
              tile: int = DEFAULT_TILE,
              interpret: bool | None = None) -> jnp.ndarray:
    """Pallas BVSS push: expand queued (frontier-vertex, VSS) pairs.

    masks: (B, 32) uint32 mask rows of the queued VSSs (row-major BVSS
           layout; transposed internally for the lane-major kernel).
    bits:  (B,) int32/uint32 — the in-set bit index ``v % σ`` of the
           frontier vertex that queued each VSS.
    returns hits (B, spw, 32) bool; hits[b, j, l] set iff slice k = j*32+l
           of VSS b is adjacent to the pushing vertex (scatter its row).
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B = masks.shape[0]
    spw = 32 // sigma
    pad = (-B) % tile
    bits = bits.astype(jnp.uint32)
    if pad:
        masks = jnp.pad(masks, ((0, pad), (0, 0)))
        bits = jnp.pad(bits, (0, pad))
    Bp = B + pad
    grid = (Bp // tile,)

    out = pl.pallas_call(
        functools.partial(_push_kernel_lanes, sigma=sigma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((32, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((spw * 32, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((spw * 32, Bp), jnp.int8),
        interpret=interpret,
        name="bvss_push",
    )(masks.T, bits[None, :])
    hits = out.T[:B].reshape(B, spw, 32)
    return hits.astype(bool)
