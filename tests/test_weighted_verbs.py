"""Weighted workload verbs (PR 9, DESIGN §2.9): the min-plus tile
product, the edge-weight plane, delta-stepping SSSP and PageRank — all
against independent SciPy/NetworkX oracles, single-device and sharded —
plus the typed weight-validation ingress (satellite: negative/zero/NaN
weights must surface as GraphValidationError, never as a wrong answer).

SSSP tests use dyadic-rational weights (k/32): float32 path sums are
then EXACT, so the wave distances must match the float64 Dijkstra
oracle bit-for-bit, not approximately.
"""
import numpy as np
import pytest

from conftest import require_devices
from repro.core.policy import prepare
from repro.errors import ConfigError, GraphValidationError, check_weights
from repro.graphs import from_edges
from repro.graphs import generators as gen
from repro.kernels.ref import pagerank_ref, sssp_ref
from repro.serve import GraphSession


def dyadic(rng, m):
    return (rng.integers(1, 128, m) / 32.0).astype(np.float32)


def assert_dist_equal(dist, ref):
    np.testing.assert_array_equal(np.isinf(dist), np.isinf(ref))
    np.testing.assert_allclose(np.where(np.isinf(dist), 0.0, dist),
                               np.where(np.isinf(ref), 0.0, ref),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# min-plus tile kernel vs reference
# ---------------------------------------------------------------------------
def _minplus_brute(masks, wv, xv, sigma):
    """(S, B, τ): per slice k of VSS b, the min over the set mask bits i
    of its word k % 32 of w[b, i, k] + x[s, i, b], loop by loop."""
    S, B, tau = xv.shape[0], masks.shape[0], wv.shape[2]
    out = np.full((S, B, tau), np.inf, dtype=np.float32)
    for b in range(B):
        for k in range(tau):
            word = int(masks[b, k % 32])
            for i in range(sigma):
                if (word >> ((k // 32) * sigma + i)) & 1:
                    out[:, b, k] = np.minimum(out[:, b, k],
                                              wv[b, i, k] + xv[:, i, b])
    return out


@pytest.mark.parametrize("sigma", [8, 4, 1, 2, 16, 32])
def test_minplus_kernel_matches_ref(sigma):
    from repro.kernels import bvss_spmm_minplus
    from repro.kernels.ref import bvss_spmm_minplus_ref
    rng = np.random.default_rng(0)
    B, S = 6, 5
    tau = (32 // sigma) * 32
    masks = rng.integers(0, 2**32, (B, 32), dtype=np.uint64) \
               .astype(np.uint32)
    wv = rng.uniform(0.5, 4.0, (B, sigma, tau)).astype(np.float32)
    xv = rng.uniform(0.0, 9.0, (S, sigma, B)).astype(np.float32)
    xv[rng.random((S, sigma, B)) < 0.3] = np.inf   # inactive columns
    got = np.asarray(bvss_spmm_minplus(masks, wv, xv, sigma=sigma))
    want = np.asarray(bvss_spmm_minplus_ref(masks, wv, xv, sigma=sigma))
    assert got.shape == (S, B, tau)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(want, _minplus_brute(masks, wv, xv, sigma))
    assert not np.isnan(got).any()


def test_minplus_all_inf_columns_yield_inf():
    from repro.kernels import bvss_spmm_minplus
    rng = np.random.default_rng(1)
    masks = rng.integers(0, 2**32, (3, 32), dtype=np.uint64) \
               .astype(np.uint32)
    wv = rng.uniform(0.5, 4.0, (3, 8, 128)).astype(np.float32)
    xv = np.full((2, 8, 3), np.inf, dtype=np.float32)
    out = np.asarray(bvss_spmm_minplus(masks, wv, xv, sigma=8))
    assert out.shape == (2, 3, 128)
    assert np.isinf(out).all() and not np.isnan(out).any()


# ---------------------------------------------------------------------------
# weight-validation ingress (typed errors, satellite)
# ---------------------------------------------------------------------------
def _bad_weight_cases(m):
    w = np.ones(m, dtype=np.float32)
    wrong_shape = np.ones(m + 1, dtype=np.float32)
    nan = w.copy(); nan[m // 2] = np.nan
    neg = w.copy(); neg[0] = -1.0
    zero = w.copy(); zero[-1] = 0.0
    inf = w.copy(); inf[0] = np.inf
    return {"shape": wrong_shape, "nan": nan, "negative": neg,
            "zero": zero, "inf": inf}


@pytest.mark.parametrize("case", ["shape", "nan", "negative", "zero", "inf"])
def test_check_weights_rejects(case):
    g = gen.rmat(6, 4, seed=3)
    bad = _bad_weight_cases(g.m)[case]
    with pytest.raises(GraphValidationError):
        check_weights(bad, g.m)


@pytest.mark.parametrize("case", ["shape", "nan", "negative", "zero"])
def test_prepare_and_session_reject_bad_weights(case):
    """The ingress is at prepare()/GraphSession() — a bad weight vector
    must be a typed error BEFORE any device work."""
    g = gen.rmat(6, 4, seed=3)
    bad = _bad_weight_cases(g.m)[case]
    with pytest.raises(GraphValidationError):
        prepare(g, weights=bad)
    with pytest.raises(GraphValidationError):
        GraphSession(g, weights=bad)


def test_check_weights_accepts_and_casts():
    w64 = np.arange(1, 11, dtype=np.float64) / 4.0
    out = check_weights(w64, 10)
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, w64)


# ---------------------------------------------------------------------------
# weight plane: prepare() threading
# ---------------------------------------------------------------------------
def test_prepare_threads_weights_through_ordering():
    """The ordered weight vector must track the permuted edges exactly:
    every (src, dst, w) triple of the original graph appears with the
    same weight in the ordered graph's CSR."""
    from repro.graphs import src_of_edges
    g = gen.rmat(7, 6, seed=4)
    rng = np.random.default_rng(5)
    w = dyadic(rng, g.m)
    pb = prepare(g, weights=w)
    assert pb.weights is not None and pb.wplane is not None
    go = pb.graph
    orig = {(int(pb.perm[s]), int(pb.perm[d])): float(wt)
            for s, d, wt in zip(src_of_edges(g), g.indices, w)}
    for s, d, wt in zip(src_of_edges(go), go.indices, pb.weights):
        assert orig[(int(s), int(d))] == float(wt)


def test_prepare_unweighted_has_no_plane():
    pb = prepare(gen.rmat(6, 4, seed=3))
    assert pb.weights is None and pb.wplane is None


# ---------------------------------------------------------------------------
# SSSP vs the SciPy Dijkstra oracle (single device)
# ---------------------------------------------------------------------------
def _sssp_case(g, srcs, seed=7, batch=None):
    from repro.analytics import sssp_distances
    rng = np.random.default_rng(seed)
    w = dyadic(rng, g.m)
    pb = prepare(g, weights=w)
    dist = sssp_distances(pb.perm[np.asarray(srcs)], problem=pb.problem,
                          wplane=pb.wplane, weights=pb.weights,
                          batch=batch)
    ref = sssp_ref(g, srcs, w)          # caller ids
    assert_dist_equal(dist[:, pb.perm], ref)


def test_sssp_directed_scale_free():
    g = gen.rmat(7, 8, seed=8)
    _sssp_case(g, [0, 3, g.n // 2, g.n - 1])


def test_sssp_high_diameter_grid():
    g = gen.grid2d(11, 11, shuffle=True, seed=9)
    _sssp_case(g, [0, 60])


def test_sssp_disconnected_unreachable_is_inf():
    src = np.array([0, 1, 2, 5, 6], dtype=np.int64)
    dst = np.array([1, 2, 0, 6, 5], dtype=np.int64)
    g = from_edges(48, src, dst)
    _sssp_case(g, [0, 5, 40])


def test_sssp_single_vertex():
    g = from_edges(1, np.zeros(0, np.int64), np.zeros(0, np.int64))
    _sssp_case(g, [0])


def test_sssp_batch_chunking_matches_oracle():
    """More sources than the cohort width: the host loop chunks through
    the same engine; padding columns never leak."""
    g = gen.rmat(6, 6, seed=10)
    _sssp_case(g, list(range(7)), batch=3)


def test_sssp_delta_choice_never_changes_answers():
    """Δ shapes performance only: wildly different bucket widths must
    produce identical distances (module contract)."""
    from repro.analytics import sssp_distances
    g = gen.grid2d(8, 8, shuffle=True, seed=11)
    rng = np.random.default_rng(12)
    w = dyadic(rng, g.m)
    pb = prepare(g, weights=w)
    outs = []
    for delta in (0.05, 1.0, 1e6):
        d = sssp_distances(pb.perm[[0, 17]], problem=pb.problem,
                           wplane=pb.wplane, weights=pb.weights,
                           delta=delta)
        outs.append(d)
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


# ---------------------------------------------------------------------------
# PageRank vs the NetworkX oracle (single device)
# ---------------------------------------------------------------------------
def _pagerank_case(g):
    from repro.analytics import pagerank_scores
    pb = prepare(g)
    r = pagerank_scores(pb.graph, problem=pb.problem, tol=1e-10,
                        max_iter=500)
    ref = pagerank_ref(pb.graph)
    rel = np.max(np.abs(r - ref) / np.maximum(np.abs(ref), 1e-30))
    assert rel <= 1e-6, rel
    assert abs(r.sum() - 1.0) < 1e-5


def test_pagerank_directed_scale_free():
    _pagerank_case(gen.rmat(7, 8, seed=13))


def test_pagerank_dangling_star():
    # out_hub=False: every spoke points at the hub, all spokes dangle
    _pagerank_case(gen.star(64, out_hub=False))


def test_pagerank_disconnected():
    src = np.array([0, 1, 2, 5, 6], dtype=np.int64)
    dst = np.array([1, 2, 0, 6, 5], dtype=np.int64)
    _pagerank_case(from_edges(48, src, dst))


def test_pagerank_single_vertex():
    g = from_edges(1, np.zeros(0, np.int64), np.zeros(0, np.int64))
    _pagerank_case(g)


# ---------------------------------------------------------------------------
# GraphSession verbs: caller-id contract + unit-weight default
# ---------------------------------------------------------------------------
def test_session_sssp_caller_ids():
    g = gen.rmat(7, 8, seed=14)
    rng = np.random.default_rng(15)
    w = dyadic(rng, g.m)
    sess = GraphSession(g, weights=w)
    ref = sssp_ref(g, [5], w)[0]
    assert_dist_equal(sess.sssp(5), ref)


def test_session_unweighted_sssp_equals_levels():
    """An unweighted session defaults the weighted verbs to unit
    weights: sssp is then exactly BFS hop counts."""
    g = gen.rmat(7, 8, seed=16)
    sess = GraphSession(g)
    d = sess.sssp(2)
    lv0 = sess.levels(2)
    lv = np.where(lv0 == np.iinfo(np.int32).max, np.inf,
                  lv0.astype(np.float64))     # INF sentinel -> +inf
    np.testing.assert_array_equal(d, lv)


def test_session_pagerank_caller_ids():
    g = gen.rmat(7, 8, seed=17)
    sess = GraphSession(g)
    pr = sess.pagerank(tol=1e-10, max_iter=500)
    ref = pagerank_ref(g)               # caller ids
    rel = np.max(np.abs(pr - ref) / np.maximum(np.abs(ref), 1e-30))
    assert rel <= 1e-6, rel


def test_session_source_validation():
    g = gen.rmat(6, 4, seed=18)
    sess = GraphSession(g)
    with pytest.raises(GraphValidationError):
        sess.sssp(g.n)
    with pytest.raises(GraphValidationError):
        sess.sssp_batch([0, -1])


# ---------------------------------------------------------------------------
# sharded parity (1-D mesh) + 2-D typed rejection
# ---------------------------------------------------------------------------
def test_sssp_sharded_matches_oracle():
    require_devices(2)
    from repro.distributed.bfs_dist import bfs_mesh
    g = gen.rmat(7, 8, seed=19)
    rng = np.random.default_rng(20)
    w = dyadic(rng, g.m)
    sess = GraphSession(g, weights=w, mesh=bfs_mesh(2))
    ref = sssp_ref(g, [0, 9], w)
    assert_dist_equal(sess.sssp_batch([0, 9]), ref)


def test_pagerank_sharded_matches_oracle():
    require_devices(2)
    from repro.distributed.bfs_dist import bfs_mesh
    g = gen.rmat(7, 8, seed=21)
    sess = GraphSession(g, mesh=bfs_mesh(2))
    pr = sess.pagerank(tol=1e-10, max_iter=500)
    ref = pagerank_ref(g)
    rel = np.max(np.abs(pr - ref) / np.maximum(np.abs(ref), 1e-30))
    assert rel <= 1e-6, rel


def test_sharded_session_rejects_bad_weights():
    require_devices(2)
    from repro.distributed.bfs_dist import bfs_mesh
    g = gen.rmat(6, 4, seed=22)
    for case, bad in _bad_weight_cases(g.m).items():
        with pytest.raises(GraphValidationError):
            GraphSession(g, weights=bad, mesh=bfs_mesh(2))


def test_2d_mesh_weighted_prepare_is_typed_config_error():
    require_devices(4)
    import jax
    from jax.sharding import Mesh
    g = gen.rmat(6, 4, seed=23)
    rng = np.random.default_rng(24)
    w = dyadic(rng, g.m)
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("rows", "cols"))
    with pytest.raises(ConfigError):
        prepare(g, weights=w, mesh=mesh, mesh_axis="rows")


def test_2d_mesh_verbs_are_typed_config_errors():
    require_devices(4)
    import jax
    from jax.sharding import Mesh
    g = gen.rmat(6, 4, seed=25)
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    sess = GraphSession(g, mesh=Mesh(devs, ("rows", "cols")),
                        mesh_axis="rows")
    with pytest.raises(ConfigError):
        sess.sssp(0)
    with pytest.raises(ConfigError):
        sess.pagerank()
