"""Every Pallas kernel entry point compiles for a TPU v5e chip.

Interpret mode (the rest of the suite) never checks Mosaic's tiling rules
or VMEM limits, so each entry point is compiled here, ahead of time, for
one chip of a described ``v5e:2x2`` topology at deployment widths: 4096
queued VSSs (and the single-source pull's chunk), 64 stacked sources,
σ = 8, 2^20 vertices (the SSSP update's scatter-min at the Kernel 3
cell's (1, 2^19) distances and 512 × 128 pairs).  Nothing runs;
each test asserts the compiled program holds the Mosaic kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture (never at
import), and the persistent compilation cache is off around these
compiles: an entry written for a chip that is not attached cannot be read
back here.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels as K
from repro.core.bfs import PULL_CHUNK

B, S, SIGMA, N = 4096, 64, 8, 1 << 20
SPW = 32 // SIGMA
U32, I32, F32 = jnp.uint32, jnp.int32, jnp.float32

# name -> (entry point with its static arguments, argument (shape, dtype)s)
CASES = {
    "bvss_pull[lanes]": (
        lambda m, f: K.bvss_pull(m, f, sigma=SIGMA, layout="lanes",
                                 interpret=False),
        [((B, 32), U32), ((B,), U32)]),
    "bvss_pull[lanes,chunk]": (
        lambda m, f: K.bvss_pull(m, f, sigma=SIGMA, layout="lanes",
                                 interpret=False),
        [((PULL_CHUNK, 32), U32), ((PULL_CHUNK,), U32)]),
    "bvss_pull[rows]": (
        lambda m, f: K.bvss_pull(m, f, sigma=SIGMA, layout="rows",
                                 interpret=False),
        [((B, 32), U32), ((B,), U32)]),
    "bvss_push": (
        lambda m, b: K.bvss_push(m, b, sigma=SIGMA, interpret=False),
        [((B, 32), U32), ((B,), I32)]),
    "finalize_sweep": (
        lambda mk, lv, lvl: K.finalize_sweep(mk, lv, lvl, interpret=False),
        [((N,), jnp.uint8), ((N,), I32), ((), I32)]),
    "finalize_pack_sweep[eager]": (
        lambda lv, lvl: K.finalize_pack_sweep(
            lv, lvl, sigma=SIGMA, n_fwords=N // 32, n_sets=N // SIGMA,
            interpret=False),
        [((N,), I32), ((), I32)]),
    "finalize_pack_sweep[lazy]": (
        lambda lv, lvl, mk: K.finalize_pack_sweep(
            lv, lvl, sigma=SIGMA, n_fwords=N // 32, n_sets=N // SIGMA,
            marks=mk, interpret=False),
        [((N,), I32), ((), I32), ((N,), jnp.uint8)]),
    "bit_spmm": (
        lambda a, x: K.bit_spmm(a, x, interpret=False),
        [((B, N // 32), U32), ((N, S), jnp.int8)]),
    "bvss_spmm": (
        lambda m, f: K.bvss_spmm(m, f, sigma=SIGMA, interpret=False),
        [((B, 32), U32), ((B, S), U32)]),
    "bvss_spmm_w": (
        lambda m, x: K.bvss_spmm_w(m, x, sigma=SIGMA, interpret=False),
        [((B, 32), U32), ((B, SIGMA, S), F32)]),
    "bvss_spmm_t": (
        lambda m, h: K.bvss_spmm_t(m, h, sigma=SIGMA, interpret=False),
        [((B, 32), U32), ((B, SPW, 32, S), F32)]),
    "bvss_spmm_minplus": (
        lambda m, w, x: K.bvss_spmm_minplus(m, w, x, sigma=SIGMA,
                                            interpret=False),
        [((B, 32), U32), ((B, SIGMA, SPW * 32), F32), ((S, SIGMA, B), F32)]),
    "bvss_spmm_minplus[chunk,S=1]": (
        lambda m, w, x: K.bvss_spmm_minplus(m, w, x, sigma=SIGMA,
                                            interpret=False),
        [((PULL_CHUNK, 32), U32), ((PULL_CHUNK, SIGMA, SPW * 32), F32),
         ((1, SIGMA, PULL_CHUNK), F32)]),
    "bvss_spmm_w_local": (
        lambda m, sets, xg: K.bvss_spmm_w_local(
            m, sets, xg, sigma=SIGMA,
            impl=lambda *a, **k: K.bvss_spmm_w(*a, **k, interpret=False)),
        [((B, 32), U32), ((B,), I32), ((N, S), F32)]),
    "bvss_spmm_t_local": (
        lambda m, rows, h: K.bvss_spmm_t_local(
            m, rows, h, sigma=SIGMA,
            impl=lambda *a, **k: K.bvss_spmm_t(*a, **k, interpret=False)),
        [((B, 32), U32), ((B, SPW, 32), I32), ((N + 1, S), F32)]),
    "scatter_min[cell]": (
        lambda d, rows, y, n: K.scatter_min_kernel(d, rows, y, n,
                                                   interpret=False),
        [((1, 1 << 19), F32), ((PULL_CHUNK * 128,), I32),
         ((1, PULL_CHUNK * 128), F32), ((), I32)]),
    "scatter_min[S=8]": (
        lambda d, rows, y, n: K.scatter_min_kernel(d, rows, y, n,
                                                   interpret=False),
        [((8, 3 * (1 << 16) + 1), F32), ((PULL_CHUNK * 128,), I32),
         ((8, PULL_CHUNK * 128), F32), ((), I32)]),
    "bvss_spmm_minplus_local": (
        lambda m, w, sets, xg: K.bvss_spmm_minplus_local(
            m, w, sets, xg, sigma=SIGMA,
            impl=lambda *a, **k: K.bvss_spmm_minplus(*a, **k,
                                                     interpret=False)),
        [((B, 32), U32), ((B, SIGMA, SPW * 32), F32), ((B,), I32),
         ((S, N), F32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
        # the engine test traces the kernels with BLEST_INTERPRET=0; the
        # kernels' jit caches key on shapes alone, so those compiled-mode
        # traces would serve later CPU tests of the same process
        jax.clear_caches()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lazy", [False, True], ids=["eager", "lazy"])
def test_engine_stages_compile_for_v5e(one_chip, monkeypatch, lazy):
    """The counted single-source engine compiles for the chip with its
    Mosaic kernels, and every fusion and kernel of its level loop maps to
    one of the three named stages."""
    from repro import tracing
    from repro.core.bfs import BlestProblem, make_blest_bfs
    from repro.core.bvss import build_bvss
    from repro.graphs import generators as gen

    monkeypatch.setenv("BLEST_INTERPRET", "0")
    p = BlestProblem.build(build_bvss(gen.rmat(10, 16, seed=1)))
    fn = make_blest_bfs(p, lazy=lazy, counters=True)
    text = fn.lower(jax.ShapeDtypeStruct((), I32, sharding=one_chip)
                    ).compile().as_text()
    stages = tracing.stage_map(text)
    comps, entry = tracing.parse_hlo(text)
    loop = [n for i in comps[entry] if i.opcode == "while"
            for c in i.control for n in tracing.scheduled(comps, c)]
    ops = [i for n in loop for i in comps[n]
           if i.opcode in ("fusion", "custom-call")]
    kernels = [i for i in ops if i.opcode == "custom-call"]
    assert kernels and "tpu_custom_call" in text
    assert all(stages.get(i.name) in tracing.BFS_STAGES for i in ops)


def test_sssp_engine_compiles_lane_dense_for_v5e(one_chip, monkeypatch):
    """The counted delta-stepping engine compiles for the chip with its
    min-plus kernel; every fusion and kernel of its loops maps to one of
    its three named stages, and no float array it holds (plane rows,
    gathers, kernel operands and results, distances) of more than one
    element has σ or 1 as its minor dimension."""
    import re

    import numpy as np

    from repro import tracing
    from repro.analytics.sssp import make_sssp
    from repro.core.bfs import BlestProblem
    from repro.core.bvss import build_bvss, build_weight_plane
    from repro.graphs import generators as gen

    monkeypatch.setenv("BLEST_INTERPRET", "0")
    # the kernel's jit cache keys on shapes alone: a CPU test of the
    # engine at the chunk width may have traced it in interpret mode
    jax.clear_caches()
    g = gen.rmat(10, 16, seed=1)
    p = BlestProblem.build(build_bvss(g))
    plane = build_weight_plane(g, np.ones(g.m, np.float32))
    wplane = jax.ShapeDtypeStruct((plane.shape[0] + 1,) + plane.shape[1:],
                                  F32, sharding=one_chip)
    eng = make_sssp(p, wplane, 1, counters=True)
    arrays = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                   for a in eng.arrays)
    text = eng._replace(arrays=arrays).compile().fn.as_text()
    assert "tpu_custom_call" in text
    stages = tracing.stage_map(text)
    comps, entry = tracing.parse_hlo(text)
    loop = [n for i in comps[entry] if i.opcode == "while"
            for c in i.control for n in tracing.scheduled(comps, c)]
    ops = [i for n in loop for i in comps[n]
           if i.opcode in ("fusion", "custom-call")]
    assert ops and all(stages.get(i.name) in tracing.SSSP_STAGES
                       for i in ops)
    kernels = {(i.name.split(".")[0], stages[i.name]) for i in ops
               if i.opcode == "custom-call"
               and i.name.startswith(("bvss_", "sssp_"))}
    assert kernels == {("bvss_spmm_minplus", "sssp.expand"),
                       ("sssp_scatter_min", "sssp.update")}
    floats = re.findall(r"f32\[([\d,]+)\]\{([\d,]+)", text)
    assert any(d.split(",")[-1] == "128" for d, _ in floats)
    for dims, layout in floats:
        d = [int(x) for x in dims.split(",")]
        minor = d[int(layout.split(",")[0])]
        assert len(d) < 2 or np.prod(d) == 1 or minor not in (SIGMA, 1), \
            f"f32[{dims}]{{{layout}}}"
