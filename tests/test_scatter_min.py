"""The SSSP update's scatter-min kernel (``kernels/scatter_min.py``, run
here in interpret mode) against its jnp reference ``.at[:, rows].min``:
bitwise the same distances for random pairs with repeated rows, ``+inf``
values, the dummy row and rows past the end, for 1, 3 and 8 sources,
targets that are not whole lane rows, and every live count of a chunk's
edges; pairs past the live count are never read.  The shape rule keeps
the XLA scatter where the distances do not fit the kernel's VMEM."""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bfs import PULL_CHUNK
from repro.kernels import scatter_min, scatter_min_kernel
from repro.kernels.ref import scatter_min_ref
from repro.kernels.scatter_min import BANKS, UNROLL, VMEM_DIST_BYTES, fits

# the module, not the function ``repro.kernels.scatter_min`` that shadows it
scatter_min_module = importlib.import_module("repro.kernels.scatter_min")
TAU = 128
P = PULL_CHUNK * TAU                     # one chunk's (row, value) pairs


def _case(rng, S: int, R: int, pairs: int):
    """Distances with unreached (+inf) rows; pairs whose rows repeat
    (drawn from a few hundred), hit the dummy row R - 1 and fall past
    the end; about two thirds of the values +inf."""
    d = np.where(rng.random((S, R)) < 0.3, np.inf,
                 rng.random((S, R), dtype=np.float32))
    hot = rng.integers(0, R, 300)
    rows = np.where(rng.random(pairs) < 0.5, rng.choice(hot, pairs),
                    rng.integers(0, R + 40, pairs)).astype(np.int32)
    rows[::97] = R - 1
    vals = np.where(rng.random((S, pairs)) < 0.64, np.inf,
                    rng.random((S, pairs), dtype=np.float32))
    return (jnp.asarray(d.astype(np.float32)), jnp.asarray(rows),
            jnp.asarray(vals.astype(np.float32)))


def _bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("live", [0, 1, 127, 128, 511, 512])
@pytest.mark.parametrize("S", [1, 3, 8])
def test_kernel_matches_the_jnp_scatter(S, live):
    rng = np.random.default_rng(1000 * S + live)
    R = 3000 + 129 * S                   # never whole (8, 128) tiles
    d, rows, vals = _case(rng, S, R, P)
    n = live * TAU
    got = scatter_min_kernel(d, rows, vals, jnp.int32(n))
    np.testing.assert_array_equal(
        _bits(got), _bits(scatter_min_ref(d, rows, vals, n)))
    # the pairs past the live count are never read, finite or not
    np.testing.assert_array_equal(
        _bits(got), _bits(scatter_min_ref(d, rows[:n], vals[:, :n])))


@pytest.mark.parametrize("pairs,block,live", [
    (1000, 256, None), (P, 4096, P - 3)])
def test_kernel_at_any_block_and_unroll(monkeypatch, pairs, block, live):
    """Every pair (no live count) of a pair count that is no whole block
    (padded with dropped pairs); and several SMEM blocks a call, with the
    pair loop unrolled as compiled, over a live count that leaves a tail
    of pairs past the last whole trip."""
    monkeypatch.setattr(scatter_min_module, "BLOCK", block)
    assert live is None or live % UNROLL
    rng = np.random.default_rng(pairs)
    d, rows, vals = _case(rng, 2, 5000, pairs)
    n = pairs if live is None else live
    if live is None:
        got = scatter_min_kernel(d, rows, vals)
    else:
        got = scatter_min_module._scatter_min(
            d, rows, vals, jnp.int32(live), block=block, unroll=UNROLL,
            banks=BANKS, interpret=True)
    np.testing.assert_array_equal(
        _bits(got), _bits(d.at[:, rows[:n]].min(vals[:, :n])))


def test_shape_rule_keeps_the_xla_scatter_where_vmem_is_short():
    rows = jax.ShapeDtypeStruct((P,), jnp.int32)
    fit = VMEM_DIST_BYTES // (4 * BANKS)
    assert fits(1, 1 << 19) and fits(1, fit) and not fits(1, fit + 1)
    assert fits(8, fit // 8) and not fits(8, 1 << 19)
    for S, R, kernel in ((1, 1 << 19, True), (8, 1 << 19, False)):
        text = str(jax.make_jaxpr(scatter_min)(
            jax.ShapeDtypeStruct((S, R), jnp.float32), rows,
            jax.ShapeDtypeStruct((S, P), jnp.float32), jnp.int32(P)))
        assert ("pallas_call" in text) == kernel
        assert ("scatter-min" in text) != kernel


@pytest.mark.parametrize("R,copies", [(2048, False), (2000, True)])
def test_whole_tiles_reach_the_kernel_without_a_copy(R, copies):
    """Distances of whole (8, 128) tiles, as the SSSP engines allocate
    them, enter and leave the kernel as a reshape; others are padded
    into a copy and sliced back out."""
    text = str(jax.make_jaxpr(scatter_min_kernel)(
        jax.ShapeDtypeStruct((1, R), jnp.float32),
        jax.ShapeDtypeStruct((1024,), jnp.int32),
        jax.ShapeDtypeStruct((1, 1024), jnp.float32)))
    prims = set(re.findall(r"= (\w+)\[", text))
    assert ("pad" in prims, "slice" in prims) == (copies, copies)
