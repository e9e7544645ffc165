"""Pallas TPU kernels for the end-of-level sweep (paper Alg. 3 stage 2).

The paper replaces scattered atomic updates with one dense, fully-coalesced
pass over the visited bitmap.  On TPU this is the *native* idiom — a pure
elementwise VPU sweep over vertex tiles.

Two entry points:

``finalize_sweep``
    The original Alg.-3 stage-2 kernel: ``levels' , new`` from ``marks``.
    Kept as the minimal unit (and as the §Perf baseline for the fused one).

``finalize_pack_sweep``
    The fused level-step tail (DESIGN.md §2.3).  One sweep over the vertex
    tiles emits all three per-level dense products at once:

        levels'     = finalised level array
        fwords      = packed uint32 frontier words (bit v = vertex v new)
        set_active  = per-slice-set "has a new vertex" flags (the input to
                      cumsum queue compaction)

    which replaces the seed's three separate dense passes (finalise,
    ``_pack_bits``, the set-reduction half of ``rebuild_queue``) — three HBM
    round-trips over the vertex arrays collapse into one, mirroring the
    paper's cache-locality argument for the stage-2 sweep.  Eager (Alg. 2)
    mode derives newness from ``levels == lvl`` (the scatter-min already
    wrote the levels); lazy (Alg. 3) mode finalises from byte marks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF32 = (1 << 31) - 1  # python literal so the kernel captures no tracers
LANES = 128
TILE_ROWS = 256  # vertex rows per grid step: (256, 128) = 32768 vertices

# Layout (DESIGN §2.3).  Vertex arrays are viewed as lane-dense
# ``(rows, 128)`` blocks (vertex 128r + c at row r, lane c), so every
# block is (sublane, lane)-tiled for int32 and, at a multiple of 32 rows,
# for int8.  The level scalar rides in SMEM.  Packing transposes each
# block in VMEM so the 32 bits of a frontier word run down sublanes:
#
#     fw  (4, rows)        [q, r]    = frontier word 4r + q
#     act (spw, 4, rows)   [j, q, r] = set flag (4r + q)·spw + j
#
# and the wrapper puts those small outputs (1/32 and spw/32 of the vertex
# array) back in vertex order with one XLA transpose each.


def _tiling(need: int) -> tuple[int, int]:
    """(rows per block, padded rows) covering ``need`` vertices."""
    rows = -(-need // LANES)
    tr = min(TILE_ROWS, -(-rows // 32) * 32)
    return tr, -(-rows // tr) * tr


def _rows(x: jnp.ndarray, n_rows: int, fill) -> jnp.ndarray:
    """Pad a vertex vector to ``n_rows * 128`` and view it as (rows, 128)."""
    x = jnp.pad(x, (0, n_rows * LANES - x.shape[0]), constant_values=fill)
    return x.reshape(n_rows, LANES)


def _smem_scalar(lvl) -> jnp.ndarray:
    return jnp.asarray(lvl, dtype=jnp.int32).reshape(1)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# original minimal finalise (kept: unit kernel + baseline)
# ---------------------------------------------------------------------------
def _finalize_kernel(marks_ref, levels_ref, lvl_ref, levels_out_ref,
                     new_ref):
    levels = levels_ref[...]
    new = (marks_ref[...].astype(jnp.int32) > 0) & (levels == INF32)
    levels_out_ref[...] = jnp.where(new, lvl_ref[0], levels)
    new_ref[...] = new.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def finalize_sweep(marks: jnp.ndarray, levels: jnp.ndarray, lvl: jnp.ndarray,
                   *, interpret: bool | None = None
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """marks (N,) uint8, levels (N,) int32, lvl scalar int32 ->
    (levels' (N,) int32, new (N,) bool)."""
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    N = marks.shape[0]
    tr, rows = _tiling(N)
    block = pl.BlockSpec((tr, LANES), lambda i: (i, 0))
    levels_out, new = pl.pallas_call(
        _finalize_kernel,
        grid=(rows // tr,),
        in_specs=[block, block, _SMEM],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        ],
        interpret=interpret,
        name="finalize_sweep",
    )(_rows(marks, rows, 0), _rows(levels, rows, 0), _smem_scalar(lvl))
    return (levels_out.reshape(-1)[:N],
            new.reshape(-1)[:N].astype(bool))


# ---------------------------------------------------------------------------
# fused finalise + frontier-pack + set-active sweep
# ---------------------------------------------------------------------------
def _emit_packed(new, fw_out_ref, act_out_ref, sigma: int):
    """Shared tail: pack the (TR, 128) new-vertex mask of one block into
    frontier words and set flags (layout in the module comment)."""
    t = new.astype(jnp.int32).T                          # (128, TR): [c, r]
    tr = t.shape[1]
    bit = jax.lax.broadcasted_iota(jnp.int32, (4, 32, tr), 1)
    # distinct bits: the int32 sum is the word's bit pattern (no carries)
    words = jnp.sum(t.reshape(4, 32, tr) << bit, axis=1).astype(jnp.uint32)
    fw_out_ref[...] = words
    smask = jnp.uint32((1 << sigma) - 1)
    for j in range(32 // sigma):
        sub = (words >> jnp.uint32(sigma * j)) & smask
        act_out_ref[j] = (sub != 0).astype(jnp.int32)


def _finalize_pack_lazy(marks_ref, levels_ref, lvl_ref, lv_out_ref,
                        fw_out_ref, act_out_ref, *, sigma: int):
    levels = levels_ref[...]
    new = (marks_ref[...].astype(jnp.int32) > 0) & (levels == INF32)
    lv_out_ref[...] = jnp.where(new, lvl_ref[0], levels)
    _emit_packed(new, fw_out_ref, act_out_ref, sigma)


def _finalize_pack_eager(levels_ref, lvl_ref, fw_out_ref, act_out_ref, *,
                         sigma: int):
    # eager scatter-min already wrote the levels: no levels output stream,
    # so the hot path pays two dense writes (words + flags), not three
    new = levels_ref[...] == lvl_ref[0]
    _emit_packed(new, fw_out_ref, act_out_ref, sigma)


@functools.partial(jax.jit, static_argnames=("sigma", "n_fwords", "n_sets",
                                             "interpret"))
def finalize_pack_sweep(levels: jnp.ndarray, lvl: jnp.ndarray, *,
                        sigma: int, n_fwords: int, n_sets: int,
                        marks: jnp.ndarray | None = None,
                        interpret: bool | None = None
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One fused sweep: finalise + frontier-pack + set-active flags.

    levels: (N,) int32 over real vertices (N = n).
    lvl:    scalar int32 current level (>= 1).
    marks:  (N,) uint8 lazy marks, or None for eager mode
            (newness = ``levels == lvl``; the returned levels ARE the input
            array — eager mode emits no levels stream at all).
    Returns ``(levels' (N,) int32, fwords (n_fwords,) uint32,
    set_active (n_sets,) bool)``; frontier bit ``v`` of fwords is vertex v,
    set_active[s] covers vertices ``σs .. σ(s+1)-1``.
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    N = levels.shape[0]
    spw = 32 // sigma
    tr, rows = _tiling(max(N, n_fwords * 32, n_sets * sigma))
    # pad with levels=0: for lvl >= 1 padded vertices are never "new" in
    # either mode (0 != lvl and 0 != INF)
    levels_p = _rows(levels, rows, 0)
    block = pl.BlockSpec((tr, LANES), lambda i: (i, 0))
    pack_specs = [
        pl.BlockSpec((4, tr), lambda i: (0, i)),
        pl.BlockSpec((spw, 4, tr), lambda i: (0, 0, i)),
    ]
    pack_shape = [
        jax.ShapeDtypeStruct((4, rows), jnp.uint32),
        jax.ShapeDtypeStruct((spw, 4, rows), jnp.int32),
    ]
    if marks is None:
        fwords, act = pl.pallas_call(
            functools.partial(_finalize_pack_eager, sigma=sigma),
            grid=(rows // tr,),
            in_specs=[block, _SMEM],
            out_specs=pack_specs,
            out_shape=pack_shape,
            interpret=interpret,
            name="finalize_pack_eager",
        )(levels_p, _smem_scalar(lvl))
        lv_out = levels  # untouched by eager finalise: no dense write
    else:
        lv_full, fwords, act = pl.pallas_call(
            functools.partial(_finalize_pack_lazy, sigma=sigma),
            grid=(rows // tr,),
            in_specs=[block, block, _SMEM],
            out_specs=[block] + pack_specs,
            out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int32)]
            + pack_shape,
            interpret=interpret,
            name="finalize_pack_lazy",
        )(_rows(marks, rows, 0), levels_p, _smem_scalar(lvl))
        lv_out = lv_full.reshape(-1)[:N]
    fwords = fwords.T.reshape(-1)[:n_fwords]
    act = act.transpose(2, 1, 0).reshape(-1)[:n_sets]
    return lv_out, fwords, act.astype(bool)
