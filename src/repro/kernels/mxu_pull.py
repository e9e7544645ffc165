"""Pallas TPU kernel: bit-SpMM pull on the MXU (multi-source BFS, DESIGN §2.2).

Paper §2: stacking S frontiers column-wise turns SpMSpV into SpMM.  On TPU
the MXU's native tile is 128×128 int8 — 16× wider than the paper's
m8n8k128 — so the bit-unpack cost (8× read amplification) only amortises
when many sources share one adjacency read.  This kernel computes

    Y[r, s] = Σ_c bits(A_packed)[r, c] * X[c, s]        (popcount semiring)

over K stripes of 128 packed words (4096 columns, one lane-dense u32
block): each of the 32 bit planes of the word block is an int8 {0,1} tile
fed to ``dot_general`` (int8 → int32) against the frontier rows of that
plane's columns, the exact analogue of the paper's (AND, +) popcount
accumulation, with every MXU output entry useful (128·S dot products per
call vs the paper's 64).

Grid = (row_tiles, s_tiles, k_stripes); the K dimension accumulates into the
output block (revisiting pattern), so K is the innermost grid axis.

Every entry point compiles for TPU v5e (``tests/test_tpu_compile.py``).
The slice tiles of the ``bvss_spmm*`` kernels are built from shifts and
masks on an iota (:func:`_slice_bits`), never by reshaping or transposing
unpacked bits inside the kernel, and go through int32 on their way to
int8 or float32.  The float32 products ask for ``Precision.HIGHEST``: at
the default precision the v5e MXU rounds the values to bfloat16 (errors
of ~1e-2 against the oracle on the chip).

``bvss_spmm`` is the *compressed* counterpart (DESIGN §2.5): instead of the
dense bit-adjacency it takes one batch of queued BVSS mask rows plus the S
stacked σ-bit frontier bytes of each VSS's slice set, and resolves every
(slice, source) Boolean dot product as a block of small bit-SpMM tiles —
per VSS an (τ, σ) slice-bit tile contracted against its (σ, S) frontier-bit
tile on the MXU.  This is the serving hot path: multi-source BFS touches
only BVSS words, never the O(n²/32) dense adjacency.

``bvss_spmm_w``/``bvss_spmm_t`` are the *weighted* analytics companions
(DESIGN §2.6): the same (τ, σ) adjacency bit tile, contracted against
float32 operands instead of frontier bits.  ``bvss_spmm_w`` contracts over
the σ column axis (a weighted pull — Brandes σ path-count propagation
feeds per-column predecessor values); ``bvss_spmm_t`` contracts over the τ
row axis (the transposed product — the Brandes backward dependency sweep
pushes per-row values back onto the columns).  One bit-unpack serves both
traversal and analytics, so every algorithm in ``repro.analytics`` rides
the tiles the BFS engines already own.

``bvss_spmm_w_local``/``bvss_spmm_t_local`` are their local-rows ×
global-columns forms (DESIGN §2.4/§2.6): the gather half of the weighted
products, phrased so one call site serves the single-device engines AND
every shard of a row-sharded BVSS under ``shard_map``.  The `_w` form
gathers each queued VSS's (σ, S) slice-set column block out of a GLOBAL
per-column value array (single-device: the padded σ-frontier values;
sharded: the per-level all-gather of every shard's local frontier values);
the `_t` form gathers per-row values through the caller's ``row_ids``
(LOCAL rows under a mesh) and returns the per-column partials the caller
scatter-adds into the global column space — and, when row-sharded,
reduces across shards (``lax.psum_scatter``), because each shard only
sees the dependency flowing through its own rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_R = 128   # rows per grid step
TILE_S = 128   # sources per grid step
TILE_W = 128   # packed u32 words per K stripe (= 4096 columns, lane-dense)


def _mxu_kernel(a_ref, x_ref, y_ref):
    """a_ref (TILE_R, TILE_W) u32; x_ref (32, TILE_W, TILE_S) i8 with
    x_ref[i, w] = frontier row 32w + i; y_ref (TILE_R, TILE_S) i32
    accumulated over the K grid axis.

    Bit plane i of the word block is the (TILE_R, TILE_W) {0,1} matrix of
    columns 32w + i, so the stripe's product is 32 full-width int8 MXU
    passes — no lane-splitting unpack inside the kernel."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    a = a_ref[...]
    acc = jnp.zeros(y_ref.shape, jnp.int32)
    for i in range(32):
        plane = ((a >> jnp.uint32(i)) & jnp.uint32(1)).astype(jnp.int32)
        acc += jax.lax.dot_general(
            plane.astype(jnp.int8), x_ref[i],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    y_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def bit_spmm(a_packed: jnp.ndarray, x: jnp.ndarray, *,
             interpret: bool | None = None) -> jnp.ndarray:
    """Popcount-semiring SpMM: Y = bits(A) @ X.

    a_packed: (R, ceil(C/32)) uint32 packed bit rows.
    x:        (C, S) int8 (0/1 frontier columns).
    returns   (R, S) int32 popcounts (threshold >0 outside for Boolean BFS).
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    R, W = a_packed.shape
    C, S = x.shape
    assert W * 32 >= C, (W, C)
    # pad everything to tile multiples
    pr, pw, ps = (-R) % TILE_R, (-W) % TILE_W, (-S) % TILE_S
    a_packed = jnp.pad(a_packed, ((0, pr), (0, pw)))
    Rp, Wp = a_packed.shape
    x = jnp.pad(x, ((0, Wp * 32 - C), (0, ps)))
    Sp = x.shape[1]
    xp = x.reshape(Wp, 32, Sp).transpose(1, 0, 2)    # (32, Wp, Sp) planes
    grid = (Rp // TILE_R, Sp // TILE_S, Wp // TILE_W)

    y = pl.pallas_call(
        _mxu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_R, TILE_W), lambda r, s, k: (r, k)),
            pl.BlockSpec((32, TILE_W, TILE_S), lambda r, s, k: (0, k, s)),
        ],
        out_specs=pl.BlockSpec((TILE_R, TILE_S), lambda r, s, k: (r, s)),
        out_shape=jax.ShapeDtypeStruct((Rp, Sp), jnp.int32),
        interpret=interpret,
        name="bit_spmm",
    )(a_packed, xp)
    return y[:R, :S]


# ---------------------------------------------------------------------------
# batched BVSS bit-SpMM: the compressed multi-source pull (DESIGN §2.5)
# ---------------------------------------------------------------------------
def _slice_bits(masks: jnp.ndarray, sigma: int) -> jnp.ndarray:
    """(TB, 32) u32 mask rows -> (TB, τ, σ) int32 {0,1} slice bits.

    Column i of row k = j*32 + l is mask bit σj + i of word l, i.e. slice
    k's adjacency to the i-th column of the VSS's slice set.  Built from
    shifts and masks on a row-replicated word column: the mask words move
    from lanes to sublanes once, with no reshape or transpose of the
    unpacked bits."""
    spw = 32 // sigma
    tb = masks.shape[0]
    col = masks[:, :, None]                                  # (TB, 32, 1)
    if spw > 1:
        col = jnp.concatenate([col] * spw, axis=1)           # (TB, τ, 1)
    shape = (tb, spw * 32, sigma)
    k = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    i = jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
    bits = (col >> ((k // 32) * jnp.uint32(sigma) + i)) & jnp.uint32(1)
    return bits.astype(jnp.int32)


def _bvss_spmm_kernel(masks_ref, fb_ref, y_ref, *, sigma: int):
    """masks_ref (TB, 32) u32; fb_ref (TB, TS) u32;
    y_ref (TB, spw*32, TS) i32: per-VSS (τ, σ) @ (σ, TS) bit-SpMM tiles.

    Slice k = j*32 + l of VSS b carries mask bits σj+i of word masks[b, l];
    unpacking those σ bits against the σ unpacked frontier bits of each of
    the TS stacked sources turns every (slice, source) Boolean dot product
    into one entry of a batched int8 matmul — the BVSS restatement of the
    ``bit_spmm`` tile, with the contraction length σ instead of 128.
    """
    a = _slice_bits(masks_ref[...], sigma).astype(jnp.int8)  # (TB, τ, σ)
    fb = fb_ref[...]
    i = jax.lax.broadcasted_iota(jnp.uint32,
                                 (fb.shape[0], sigma, fb.shape[1]), 1)
    x = (fb[:, None, :] >> i) & jnp.uint32(1)                # (TB, σ, TS)
    x = x.astype(jnp.int32).astype(jnp.int8)
    y_ref[...] = jax.lax.dot_general(
        a, x, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("sigma", "tile_b", "tile_s",
                                             "interpret"))
def bvss_spmm(masks: jnp.ndarray, fbytes: jnp.ndarray, *, sigma: int = 8,
              tile_b: int | None = None, tile_s: int | None = None,
              interpret: bool | None = None) -> jnp.ndarray:
    """Batched multi-source BVSS pull as bit-SpMM tiles.

    masks:  (B, 32) uint32 queued VSS mask rows (row-major BVSS layout).
    fbytes: (B, S) uint32 — the σ-bit frontier byte of each VSS's slice set,
            one column per stacked source.
    returns (B, spw, 32, S) int32 popcounts of slice∧frontier per source
            (threshold >0 for Boolean BFS); [b, j, l, s] is slice k=j*32+l.

    Tile defaults: compiled, the batch tile is 8 VSSs (a multiple of the
    8-row sublane tile of the (TB, 32) mask block) so the (TB, τ, TS)
    int32 accumulator stays small in VMEM; in interpret mode (CPU) a
    128-wide batch tile amortises the interpreter's per-grid-cell cost.
    The source tile is S rounded up to a multiple of 8 while S ≤ 128
    (a block as wide as the padded array), else 128 lanes; pass
    ``tile_s=128`` for full MXU lanes.
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, S = masks.shape[0], fbytes.shape[1]
    spw = 32 // sigma
    if tile_b is None:
        tile_b = 128 if interpret else 8
    if tile_s is None:
        tile_s = min(128, ((S + 7) // 8) * 8)
    pb, ps = (-B) % tile_b, (-S) % tile_s
    if pb:
        masks = jnp.pad(masks, ((0, pb), (0, 0)))
        fbytes = jnp.pad(fbytes, ((0, pb), (0, 0)))
    if ps:
        fbytes = jnp.pad(fbytes, ((0, 0), (0, ps)))
    Bp, Sp = B + pb, S + ps
    grid = (Bp // tile_b, Sp // tile_s)

    y = pl.pallas_call(
        functools.partial(_bvss_spmm_kernel, sigma=sigma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, 32), lambda b, s: (b, 0)),
            pl.BlockSpec((tile_b, tile_s), lambda b, s: (b, s)),
        ],
        out_specs=pl.BlockSpec((tile_b, spw * 32, tile_s),
                               lambda b, s: (b, 0, s)),
        out_shape=jax.ShapeDtypeStruct((Bp, spw * 32, Sp), jnp.int32),
        interpret=interpret,
        name="bvss_spmm",
    )(masks, fbytes)
    return y[:B, :, :S].reshape(B, spw, 32, S)


# ---------------------------------------------------------------------------
# weighted BVSS tiles: the analytics semiring (DESIGN §2.6)
# ---------------------------------------------------------------------------
def _bvss_spmm_w_kernel(masks_ref, xv_ref, y_ref, *, sigma: int):
    """masks_ref (TB, 32) u32; xv_ref (TB, σ, TS) f32 per-column values;
    y_ref (TB, τ, TS) f32 = per-VSS (τ, σ) bit tile @ (σ, TS) values."""
    a = _slice_bits(masks_ref[...], sigma).astype(jnp.float32)
    y_ref[...] = jax.lax.dot_general(
        a, xv_ref[...], dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _bvss_spmm_t_kernel(masks_ref, hv_ref, y_ref, *, sigma: int):
    """masks_ref (TB, 32) u32; hv_ref (TB, τ, TS) f32 per-row values;
    y_ref (TB, σ, TS) f32 = per-VSS (σ, τ) transposed tile @ (τ, TS)."""
    a = _slice_bits(masks_ref[...], sigma).astype(jnp.float32)
    y_ref[...] = jax.lax.dot_general(
        a, hv_ref[...], dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _spmm_float_call(kernel, masks, vals, mid: int, out_mid: int, *,
                     name: str, sigma: int, tile_b: int | None,
                     tile_s: int | None, interpret: bool | None):
    """Shared pallas_call plumbing for the two weighted tile products:
    vals is (B, mid, S) float32, the result (B, out_mid, S) float32."""
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, S = masks.shape[0], vals.shape[2]
    if tile_b is None:
        tile_b = 128 if interpret else 8
    if tile_s is None:
        tile_s = min(128, ((S + 7) // 8) * 8)
    pb, ps = (-B) % tile_b, (-S) % tile_s
    if pb:
        masks = jnp.pad(masks, ((0, pb), (0, 0)))
        vals = jnp.pad(vals, ((0, pb), (0, 0), (0, 0)))
    if ps:
        vals = jnp.pad(vals, ((0, 0), (0, 0), (0, ps)))
    Bp, Sp = B + pb, S + ps
    grid = (Bp // tile_b, Sp // tile_s)
    y = pl.pallas_call(
        functools.partial(kernel, sigma=sigma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, 32), lambda b, s: (b, 0)),
            pl.BlockSpec((tile_b, mid, tile_s), lambda b, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((tile_b, out_mid, tile_s),
                               lambda b, s: (b, 0, s)),
        out_shape=jax.ShapeDtypeStruct((Bp, out_mid, Sp), jnp.float32),
        interpret=interpret,
        name=name,
    )(masks, vals)
    return y[:B, :, :S]


@functools.partial(jax.jit, static_argnames=("sigma", "tile_b", "tile_s",
                                             "interpret"))
def bvss_spmm_w(masks: jnp.ndarray, xvals: jnp.ndarray, *, sigma: int = 8,
                tile_b: int | None = None, tile_s: int | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """Weighted BVSS pull: per-VSS (τ, σ) bit tile @ (σ, S) float values.

    masks: (B, 32) uint32 queued VSS mask rows.
    xvals: (B, σ, S) float32 — the values of each VSS's σ slice-set columns,
           one stacked column per source (zero where a column is not in the
           active contribution set, e.g. not on the current BFS frontier).
    returns (B, spw, 32, S) float32; [b, j, l, s] is the weighted sum over
           the in-neighbour columns of slice k = j*32 + l — scatter-add it
           into rows via ``row_ids`` (the σ path-count recurrence).
    """
    spw = 32 // sigma
    B = masks.shape[0]
    y = _spmm_float_call(_bvss_spmm_w_kernel, masks, xvals, sigma, spw * 32,
                         name="bvss_spmm_w", sigma=sigma, tile_b=tile_b,
                         tile_s=tile_s,
                         interpret=interpret)
    return y.reshape(B, spw, 32, y.shape[2])


@functools.partial(jax.jit, static_argnames=("sigma", "tile_b", "tile_s",
                                             "interpret"))
def bvss_spmm_t(masks: jnp.ndarray, hvals: jnp.ndarray, *, sigma: int = 8,
                tile_b: int | None = None, tile_s: int | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """Transposed weighted BVSS product: (σ, τ) tile @ (τ, S) float values.

    masks: (B, 32) uint32 queued VSS mask rows.
    hvals: (B, spw, 32, S) float32 — per-row values gathered through
           ``row_ids`` (zero where a row is not in the contributing level).
    returns (B, σ, S) float32; [b, i, s] is the weighted sum over the rows
           adjacent to the i-th column of the VSS's slice set — scatter-add
           it into columns (the Brandes backward dependency sweep).
    """
    B, spw = hvals.shape[0], hvals.shape[1]
    hv = hvals.reshape(B, spw * 32, hvals.shape[3])
    return _spmm_float_call(_bvss_spmm_t_kernel, masks, hv, spw * 32, sigma,
                            name="bvss_spmm_t", sigma=sigma, tile_b=tile_b,
                            tile_s=tile_s,
                            interpret=interpret)


# ---------------------------------------------------------------------------
# local-rows × global-columns weighted forms (DESIGN §2.4/§2.6)
# ---------------------------------------------------------------------------
def bvss_spmm_w_local(masks: jnp.ndarray, sets: jnp.ndarray,
                      xglobal: jnp.ndarray, *, sigma: int = 8,
                      impl=None) -> jnp.ndarray:
    """Weighted pull of a queued VSS batch against a GLOBAL column-value
    array: gathers each VSS's (σ, S) slice-set column block from
    ``xglobal`` and contracts it with the (τ, σ) bit tile.

    masks:   (B, 32) uint32 queued VSS mask rows (a shard's LOCAL rows
             under a mesh — the masks only name rows the caller owns).
    sets:    (B,) int32 GLOBAL slice-set id of each queued VSS
             (``virtual_to_real[ids]``); set j owns columns [σj, σ(j+1)).
    xglobal: (C, S) float32 per-column values with C ≥ n_sets·σ — the
             padded frontier values single-device, the per-level
             all-gather of every shard's local frontier values when
             row-sharded (the float twin of the frontier-word gather).
    returns  (B, spw, 32, S) float32 weighted sums per slice — scatter-add
             into (local) rows via ``row_ids``.

    ``impl`` overrides the tile product (``kernels.ref.bvss_spmm_w_ref``
    for the oracle path); columns stay global in either mode, so this is
    the ONE gather both the single-device σ channel and every shard of
    the mesh-native channel execute.
    """
    cols = (sets[:, None] * sigma
            + jnp.arange(sigma, dtype=jnp.int32)[None, :])      # (B, σ)
    f = bvss_spmm_w if impl is None else impl
    return f(masks, xglobal[cols], sigma=sigma)


# ---------------------------------------------------------------------------
# min-plus BVSS tiles: the tropical semiring (SSSP relaxation, DESIGN §2.9)
# ---------------------------------------------------------------------------
def _bvss_spmm_minplus_kernel(masks_ref, wv_ref, xv_ref, y_ref, *,
                              sigma: int, tile_b: int):
    """masks_ref (TB, 32) u32; wv_ref (TB·σ, τ) f32 weight-plane rows, row
    b·σ + i holding the weights of column i into the τ slices of VSS b
    (+inf on non-edges, also enforced here via the mask bits); xv_ref
    (1, σ, TB) f32 the distances of each VSS's σ columns for one source;
    y_ref (1, TB, τ) f32 the tropical product

        y[b, k] = min_i ( w[b, i, k] + x[i, b] )   over set bits i.

    Every block is lane-dense: τ or the batch fills the lanes.  Row i of
    each VSS is a strided read of the plane block, and the distances move
    from lanes to sublanes in one transpose.  σ is tiny (≤ 32), so the
    contraction is an unrolled elementwise min — no dot_general exists
    for (min, +) — and with +inf as the annihilator the expression never
    forms inf − inf, so no NaNs leak out."""
    spw = 32 // sigma
    m = masks_ref[...]
    if spw > 1:
        m = jnp.concatenate([m] * spw, axis=1)      # (TB, τ): word k % 32
    k = jax.lax.broadcasted_iota(jnp.uint32, (1, spw * 32), 1)
    shift = (k // 32) * jnp.uint32(sigma)           # bit σ·slot of slice k
    xt = xv_ref[0].T                                # (TB, σ)
    inf = jnp.float32(jnp.inf)
    acc = jnp.full((tile_b, spw * 32), inf, dtype=jnp.float32)
    for i in range(sigma):
        w = wv_ref[pl.ds(i, tile_b, stride=sigma), :]           # (TB, τ)
        bit = (m >> (shift + jnp.uint32(i))) & jnp.uint32(1)
        acc = jnp.minimum(acc, jnp.where(bit > 0, w + xt[:, i:i + 1], inf))
    y_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("sigma", "tile_b",
                                             "interpret"))
def bvss_spmm_minplus(masks: jnp.ndarray, wvals: jnp.ndarray,
                      xvals: jnp.ndarray, *, sigma: int = 8,
                      tile_b: int | None = None,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Tropical (min, +) BVSS pull: the SSSP relaxation tile (DESIGN §2.9).

    masks: (B, 32) uint32 queued VSS mask rows.
    wvals: (B, σ, τ) float32 — the weight plane rows of the queued VSSs
           (``build_weight_plane`` layout: +inf where no edge), slice
           k = j*32 + l last, in the lanes.
    xvals: (S, σ, B) float32 per-column tentative distances, one row per
           source and the batch last (+inf for unreached columns — the
           tropical zero).
    returns (S, B, τ) float32; [s, b, k] is the min over the in-neighbour
           columns i of (w[v→row] + dist[v]) for slice k of VSS b —
           scatter-``min`` it into rows via ``row_ids`` (the
           edge-relaxation recurrence).

    No operand or result has σ or S as its minor dimension, so nothing
    is padded to the 128 lanes: at σ = 8 a VSS's plane rows are one
    (8, 128) float32 tile, 4 KiB.  The batch tile is 128 VSSs (the lanes
    of the distance block); the grid runs the sources innermost, so each
    plane block is read once for all of them.
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, S = masks.shape[0], xvals.shape[0]
    tau = (32 // sigma) * 32
    if tile_b is None:
        tile_b = 128
    pb = (-B) % tile_b
    if pb:
        masks = jnp.pad(masks, ((0, pb), (0, 0)))
        wvals = jnp.pad(wvals, ((0, pb), (0, 0), (0, 0)))
        xvals = jnp.pad(xvals, ((0, 0), (0, 0), (0, pb)))
    Bp = B + pb
    y = pl.pallas_call(
        functools.partial(_bvss_spmm_minplus_kernel, sigma=sigma,
                          tile_b=tile_b),
        grid=(Bp // tile_b, S),
        in_specs=[
            pl.BlockSpec((tile_b, 32), lambda b, s: (b, 0)),
            pl.BlockSpec((tile_b * sigma, tau), lambda b, s: (b, 0)),
            pl.BlockSpec((1, sigma, tile_b), lambda b, s: (s, 0, b)),
        ],
        out_specs=pl.BlockSpec((1, tile_b, tau), lambda b, s: (s, b, 0)),
        out_shape=jax.ShapeDtypeStruct((S, Bp, tau), jnp.float32),
        interpret=interpret,
        name="bvss_spmm_minplus",
    )(masks, wvals.reshape(Bp * sigma, tau), xvals)
    return y[:, :B]


def bvss_spmm_minplus_local(masks: jnp.ndarray, wvals: jnp.ndarray,
                            sets: jnp.ndarray, xglobal: jnp.ndarray, *,
                            sigma: int = 8, impl=None) -> jnp.ndarray:
    """Min-plus pull of a queued VSS batch against a GLOBAL column-distance
    array — the tropical twin of ``bvss_spmm_w_local``: gathers each VSS's
    σ slice-set distances per source out of ``xglobal`` (single-device:
    the padded distance rows; row-sharded: the per-round all-gather of
    every shard's local distances) and relaxes them through the (σ, τ)
    weight tile.

    masks: (B, 32) u32 queued mask rows; wvals: (B, σ, τ) f32 queued
    weight-plane rows (``wplane[Q]``); sets: (B,) int32 GLOBAL slice-set
    ids; xglobal: (S, C) f32, C ≥ n_sets·σ.  Returns (S, B, τ) f32 —
    scatter-``min`` into (local) rows via ``row_ids``.
    """
    cols = (sets[None, :] * sigma
            + jnp.arange(sigma, dtype=jnp.int32)[:, None])      # (σ, B)
    f = bvss_spmm_minplus if impl is None else impl
    return f(masks, wvals, xglobal[:, cols], sigma=sigma)


def bvss_spmm_t_local(masks: jnp.ndarray, row_ids: jnp.ndarray,
                      hrows: jnp.ndarray, *, sigma: int = 8,
                      impl=None) -> jnp.ndarray:
    """Transposed weighted product against per-row values gathered through
    the caller's ``row_ids`` (LOCAL rows under a mesh, dummy row last).

    masks:   (B, 32) uint32 queued VSS mask rows.
    row_ids: (B, spw, 32) int32 destination rows of each slice (local ids
             when row-sharded; the dummy row indexes ``hrows``'s zero tail).
    hrows:   (R + 1, S) float32 per-row values, row R the zeroed dummy.
    returns  (B, σ, S) float32 per-column partial sums — scatter-add into
             the GLOBAL column space; on a row-sharded BVSS the partials
             only cover dependency flowing through this shard's rows, so
             the scatter must be psum'd (``lax.psum_scatter``) across the
             mesh axis before it folds into δ (DESIGN §2.6).
    """
    f = bvss_spmm_t if impl is None else impl
    return f(masks, hrows[row_ids], sigma=sigma)
