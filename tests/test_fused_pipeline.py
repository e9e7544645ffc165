"""Fused level-step pipeline: Pallas routing, bucketing, and edge cases.

The ``blest``/``blest_lazy`` default path must (a) reproduce the host
oracle exactly, (b) actually route through the Pallas kernels
(``bvss_pull`` + ``finalize_pack_sweep``), and (c) agree with the
pure-jnp fallback and with a single-bucket (no ``lax.cond``) build.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.bfs as bfs_mod
from repro.core import make_engine, reference_bfs
from repro.graphs import from_edges, generators as gen

EDGE_CASES = {
    # directed: a one-way path — BFS from the tail reaches nothing
    "directed_path": from_edges(40, np.arange(39), np.arange(1, 40)),
    "disconnected": from_edges(50, np.array([1, 2, 10]),
                               np.array([2, 3, 11])),
    "single_vertex": from_edges(1, np.array([], dtype=np.int64),
                                np.array([], dtype=np.int64)),
    "two_isolated": from_edges(2, np.array([], dtype=np.int64),
                               np.array([], dtype=np.int64)),
    "high_diameter": gen.grid2d(23, 29),
}


@pytest.mark.parametrize("engine", ["blest", "blest_lazy"])
@pytest.mark.parametrize("gname", sorted(EDGE_CASES))
def test_fused_engine_edge_cases(engine, gname):
    g = EDGE_CASES[gname]
    fn = make_engine(g, engine)
    for src in {0, g.n // 2, g.n - 1}:
        np.testing.assert_array_equal(np.asarray(fn(src)),
                                      reference_bfs(g, src))


@pytest.mark.parametrize("engine", ["blest", "blest_lazy"])
def test_default_path_calls_pallas_kernels(engine, monkeypatch):
    """The default device path must route through the Pallas pull AND the
    fused finalise/pack kernel (not the jnp fallbacks)."""
    calls = {"pull": 0, "finalize": 0}
    real_pull = bfs_mod.pull_vss_kernel
    real_fin = bfs_mod.finalize_pack_sweep

    def spy_pull(*a, **k):
        calls["pull"] += 1
        return real_pull(*a, **k)

    def spy_fin(*a, **k):
        calls["finalize"] += 1
        return real_fin(*a, **k)

    monkeypatch.setattr(bfs_mod, "pull_vss_kernel", spy_pull)
    monkeypatch.setattr(bfs_mod, "finalize_pack_sweep", spy_fin)
    g = gen.rmat(7, 8, seed=3)
    fn = make_engine(g, engine)
    np.testing.assert_array_equal(np.asarray(fn(1)), reference_bfs(g, 1))
    assert calls["pull"] > 0, "Pallas bvss_pull not on the default path"
    assert calls["finalize"] > 0, \
        "Pallas finalize_pack_sweep not on the default path"


@pytest.mark.parametrize("engine", ["blest", "blest_lazy"])
def test_kernel_and_jnp_paths_agree(engine):
    g = gen.rmat(8, 6, seed=4)
    f_kernel = make_engine(g, engine, use_kernels=True)
    f_jnp = make_engine(g, engine, use_kernels=False)
    for src in (0, 7, g.n - 1):
        np.testing.assert_array_equal(np.asarray(f_kernel(src)),
                                      np.asarray(f_jnp(src)))
        np.testing.assert_array_equal(np.asarray(f_jnp(src)),
                                      reference_bfs(g, src))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 120), m=st.integers(0, 500),
       seed=st.integers(0, 10_000),
       engine=st.sampled_from(["blest", "blest_lazy"]))
def test_fused_pallas_path_random_graphs(n, m, seed, engine):
    """Hypothesis parity of the fused Pallas (interpret) path vs oracle on
    directed random multigraph edge lists."""
    rng = np.random.default_rng(seed)
    g = from_edges(n, rng.integers(0, n, m), rng.integers(0, n, m))
    fn = make_engine(g, engine)
    src = int(rng.integers(0, n))
    np.testing.assert_array_equal(np.asarray(fn(src)),
                                  reference_bfs(g, src))
