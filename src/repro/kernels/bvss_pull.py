"""Pallas TPU kernel for the BVSS pull (paper §4.1, adapted per DESIGN §2.2).

The paper batches 128 Boolean dot products into two m8n8k128 bit-MMA calls
with zero wasted outputs.  The TPU has no bit-MMA; the VPU's (8,128) 32-bit
lanes with native AND + ``population_count`` are the right unit: each 32-bit
lane op resolves ``32/σ`` slice/frontier dot products.  The kernel below
processes TILE VSSs per grid step in the *lane-major* layout — masks stored
transposed ``(32, TILE)`` so the VSS axis occupies the full 128-lane dimension
and all 8 sublanes carry distinct mask words (zero idle lanes: the TPU
restatement of the paper's "all 64 fragC entries useful" rule).

Two layouts are selectable (the row-major one is the naive port and is kept
as the §Perf baseline):

* ``lanes`` (default): masks_t (32, B) u32, hits_t (spw*32, B) int8.
* ``rows``  (baseline): masks (B, 32) u32, hits (B, spw*32) int8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 128


def _fword(fb: jnp.ndarray, sigma: int) -> jnp.ndarray:
    """Replicate the σ-bit frontier byte across all 32/σ sub-words."""
    spw = 32 // sigma
    smask = jnp.uint32((1 << sigma) - 1)
    fb = fb & smask
    out = jnp.zeros_like(fb)
    for j in range(spw):
        out = out | (fb << jnp.uint32(sigma * j))
    return out


def _pull_kernel_lanes(masks_ref, fbytes_ref, hits_ref, *, sigma: int):
    """masks_ref (32, T) u32; fbytes_ref (1, T) u32; hits_ref (spw*32, T) i8."""
    spw = 32 // sigma
    smask = jnp.uint32((1 << sigma) - 1)
    masks = masks_ref[...]                       # (32, T)
    fword = _fword(fbytes_ref[...], sigma)       # (1, T)
    anded = masks & fword                        # broadcast over sublanes
    for j in range(spw):
        sub = (anded >> jnp.uint32(sigma * j)) & smask
        hits_ref[j * 32:(j + 1) * 32, :] = (sub != 0).astype(jnp.int8)


def _pull_kernel_rows(masks_ref, fbytes_ref, hits_ref, *, sigma: int):
    """masks_ref (T, 32) u32; fbytes_ref (T, 1) u32; hits_ref (T, spw*32) i8."""
    spw = 32 // sigma
    smask = jnp.uint32((1 << sigma) - 1)
    masks = masks_ref[...]
    fword = _fword(fbytes_ref[...], sigma)       # (T, 1)
    anded = masks & fword
    for j in range(spw):
        sub = (anded >> jnp.uint32(sigma * j)) & smask
        hits_ref[:, j * 32:(j + 1) * 32] = (sub != 0).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("sigma", "tile", "layout",
                                             "interpret"))
def bvss_pull(masks: jnp.ndarray, fbytes: jnp.ndarray, *, sigma: int = 8,
              tile: int = DEFAULT_TILE, layout: str = "lanes",
              interpret: bool | None = None) -> jnp.ndarray:
    """Pallas BVSS pull.

    masks:  (B, 32) uint32 (row-major BVSS layout; transposed internally for
            the ``lanes`` kernel).
    fbytes: (B,) uint32 frontier bytes (pre-gathered via virtualToReal).
    returns hits (B, spw, 32) bool, hits[b, j, l] for slice k = j*32+l.
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B = masks.shape[0]
    spw = 32 // sigma
    pad = (-B) % tile
    if pad:
        masks = jnp.pad(masks, ((0, pad), (0, 0)))
        fbytes = jnp.pad(fbytes, (0, pad))
    Bp = B + pad
    grid = (Bp // tile,)

    if layout == "lanes":
        masks_t = masks.T                        # (32, Bp)
        fb = fbytes[None, :]                     # (1, Bp)
        out = pl.pallas_call(
            functools.partial(_pull_kernel_lanes, sigma=sigma),
            grid=grid,
            in_specs=[
                pl.BlockSpec((32, tile), lambda i: (0, i)),
                pl.BlockSpec((1, tile), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((spw * 32, tile), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((spw * 32, Bp), jnp.int8),
            interpret=interpret,
            name="bvss_pull_lanes",
        )(masks_t, fb)
        hits = out.T                             # (Bp, spw*32), k = j*32+l
    elif layout == "rows":
        fb = fbytes[:, None]
        out = pl.pallas_call(
            functools.partial(_pull_kernel_rows, sigma=sigma),
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile, 32), lambda i: (i, 0)),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((tile, spw * 32), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((Bp, spw * 32), jnp.int8),
            interpret=interpret,
            name="bvss_pull_rows",
        )(masks, fb)
        hits = out
    else:
        raise ValueError(f"unknown layout {layout!r}")

    hits = hits[:B].reshape(B, spw, 32)
    return hits.astype(bool)
