"""Oracles for the kernel layer (the ``ref.py`` contract).

Two kinds live here:

* pure-jnp twins of every Pallas kernel (drop-in, same signature) — the
  ``use_kernel(s)=False`` fallback path and the per-kernel test oracle;
* host-side *analytics* oracles (NetworkX / SciPy / NumPy) for the
  ``repro.analytics`` subsystem — connected components, eccentricity and
  Brandes betweenness computed by an independent implementation, so every
  wave-engine analytic is verified end-to-end, not just per tile.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

INF32 = jnp.int32(jnp.iinfo(jnp.int32).max)


def bvss_pull_ref(masks: jnp.ndarray, fbytes: jnp.ndarray, sigma: int = 8
                  ) -> jnp.ndarray:
    """Oracle for kernels.bvss_pull: hits (B, 32/σ, 32) bool."""
    spw = 32 // sigma
    smask = jnp.uint32((1 << sigma) - 1)
    fb = fbytes & smask
    fword = jnp.zeros_like(fb)
    for j in range(spw):
        fword = fword | (fb << jnp.uint32(sigma * j))
    anded = masks & fword[:, None]
    hits = []
    for j in range(spw):
        hits.append(((anded >> jnp.uint32(sigma * j)) & smask) != 0)
    return jnp.stack(hits, axis=1)


def bvss_push_ref(masks: jnp.ndarray, bits: jnp.ndarray, sigma: int = 8
                  ) -> jnp.ndarray:
    """Oracle for kernels.bvss_push: hits (B, 32/σ, 32) bool — the pull
    oracle evaluated against the one-hot frontier byte ``1 << (v % σ)`` of
    the vertex pushing each queued VSS."""
    fb = jnp.uint32(1) << bits.astype(jnp.uint32)
    return bvss_pull_ref(masks, fb, sigma)


def bvss_spmm_ref(masks: jnp.ndarray, fbytes: jnp.ndarray, sigma: int = 8
                  ) -> jnp.ndarray:
    """Oracle for kernels.bvss_spmm: (B, 32/σ, 32, S) int32 popcounts of
    slice∧frontier per stacked source column."""
    spw = 32 // sigma
    p = (jnp.arange(spw, dtype=jnp.uint32)[:, None] * jnp.uint32(sigma)
         + jnp.arange(sigma, dtype=jnp.uint32)[None, :])     # (spw, σ)
    abits = ((masks[:, None, :, None] >> p[None, :, None, :])
             & jnp.uint32(1)).astype(jnp.int32)              # (B, spw, 32, σ)
    ib = jnp.arange(sigma, dtype=jnp.uint32)
    xbits = ((fbytes[:, None, :] >> ib[None, :, None])
             & jnp.uint32(1)).astype(jnp.int32)              # (B, σ, S)
    return jnp.einsum("bjli,bis->bjls", abits, xbits)


def _abits(masks: jnp.ndarray, sigma: int) -> jnp.ndarray:
    """Decode (B, 32) mask words to (B, spw, 32, σ) {0,1} adjacency bits."""
    spw = 32 // sigma
    p = (jnp.arange(spw, dtype=jnp.uint32)[:, None] * jnp.uint32(sigma)
         + jnp.arange(sigma, dtype=jnp.uint32)[None, :])     # (spw, σ)
    return ((masks[:, None, :, None] >> p[None, :, None, :])
            & jnp.uint32(1)).astype(jnp.float32)             # (B, spw, 32, σ)


def bvss_spmm_w_ref(masks: jnp.ndarray, xvals: jnp.ndarray, sigma: int = 8
                    ) -> jnp.ndarray:
    """Oracle for kernels.bvss_spmm_w: (B, 32/σ, 32, S) float32 weighted
    pulls — per slice, the sum of its σ column values under the mask."""
    return jnp.einsum("bjli,bis->bjls", _abits(masks, sigma), xvals)


def bvss_spmm_minplus_ref(masks: jnp.ndarray, wvals: jnp.ndarray,
                          xvals: jnp.ndarray, sigma: int = 8) -> jnp.ndarray:
    """Oracle for kernels.bvss_spmm_minplus: (S, B, τ) float32 tropical
    pulls of (B, σ, τ) plane rows against (S, σ, B) column distances —
    per slice, the min over its masked σ columns of (edge weight + column
    distance), +inf where the slice has no edge."""
    B = masks.shape[0]
    a = _abits(masks, sigma).reshape(B, -1, sigma)       # (B, τ, σ)
    w = jnp.where(jnp.swapaxes(a, 1, 2) > 0, wvals, jnp.inf)   # (B, σ, τ)
    x = jnp.swapaxes(xvals, 1, 2)[..., None]             # (S, B, σ, 1)
    return jnp.min(w[None] + x, axis=2)


def scatter_min_ref(dist: jnp.ndarray, rows: jnp.ndarray, vals: jnp.ndarray,
                    n_pairs=None) -> jnp.ndarray:
    """Oracle for kernels.scatter_min: ``dist.at[:, rows].min(vals)``,
    rows at or past the end dropped; with ``n_pairs``, the pairs past it
    carry +inf (they change nothing)."""
    if n_pairs is not None:
        vals = jnp.where(jnp.arange(rows.shape[0]) < n_pairs, vals, jnp.inf)
    return dist.at[:, rows].min(vals)


def bvss_spmm_t_ref(masks: jnp.ndarray, hvals: jnp.ndarray, sigma: int = 8
                    ) -> jnp.ndarray:
    """Oracle for kernels.bvss_spmm_t: (B, σ, S) float32 transposed
    products — per slice-set column, the sum of adjacent row values."""
    return jnp.einsum("bjli,bjls->bis", _abits(masks, sigma), hvals)


def bit_spmm_ref(a_packed: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels.bit_spmm: Y (R, S) int32 popcounts."""
    R, W = a_packed.shape
    C, S = x.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((a_packed[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1))
    dense = bits.reshape(R, W * 32)[:, :C].astype(jnp.int32)
    return dense @ x.astype(jnp.int32)


def finalize_sweep_ref(marks: jnp.ndarray, levels: jnp.ndarray,
                       lvl) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for kernels.finalize_sweep."""
    new = (marks > 0) & (levels == INF32)
    return jnp.where(new, jnp.int32(lvl), levels), new


def finalize_pack_ref(levels: jnp.ndarray, lvl, *, sigma: int,
                      n_fwords: int, n_sets: int,
                      marks: jnp.ndarray | None = None
                      ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Oracle for kernels.finalize_pack_sweep — the inline jnp finalise +
    ``_pack_bits`` + set-reduction passes the fused kernel replaces."""
    if marks is None:                       # eager: scatter-min already ran
        new = levels == jnp.int32(lvl)
        lv_out = levels
    else:                                   # lazy: finalise from byte marks
        new = (marks > 0) & (levels == INF32)
        lv_out = jnp.where(new, jnp.int32(lvl), levels)
    n_pad = n_fwords * 32
    bits = jnp.zeros((n_pad,), dtype=bool).at[:new.shape[0]].set(new)
    b = bits.reshape(n_fwords, 32).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    fwords = jnp.sum(b * weights[None, :], axis=1, dtype=jnp.uint32)
    sbits = jnp.zeros((n_sets * sigma,), dtype=bool).at[:new.shape[0]].set(new)
    set_active = sbits.reshape(n_sets, sigma).any(axis=1)
    return lv_out, fwords, set_active


# ---------------------------------------------------------------------------
# analytics oracles (NetworkX / SciPy / NumPy) — repro.analytics contract
# ---------------------------------------------------------------------------
def _csr_matrix(g):
    import scipy.sparse as sp
    return sp.csr_matrix(
        (np.ones(g.m, dtype=np.int8), g.indices, g.indptr), shape=(g.n, g.n))


def connected_components_ref(g) -> np.ndarray:
    """Weakly-connected component labels via SciPy, normalised so that
    component ids are assigned in order of each component's smallest
    vertex id (the canonical form ``repro.analytics.components`` emits)."""
    from scipy.sparse.csgraph import connected_components
    _, labels = connected_components(_csr_matrix(g), directed=True,
                                     connection="weak")
    return normalize_labels(labels)


def normalize_labels(labels: np.ndarray) -> np.ndarray:
    """Relabel components to 0..k-1 in order of first appearance (labels
    may be arbitrary ints, e.g. union-find roots)."""
    labels = np.asarray(labels)
    _, first, inverse = np.unique(labels, return_index=True,
                                  return_inverse=True)
    order = np.argsort(first)
    remap = np.empty(len(order), dtype=np.int64)
    remap[order] = np.arange(len(order))
    return remap[inverse]


def eccentricity_ref(g, sources) -> np.ndarray:
    """Per-source eccentricity on ``g`` as given (symmetrise first for the
    classical undirected definition): the max *finite* BFS distance, so a
    vertex isolated from the rest of its graph has eccentricity 0."""
    from scipy.sparse.csgraph import dijkstra
    sources = np.asarray(sources, dtype=np.int64)
    dist = dijkstra(_csr_matrix(g), directed=True, unweighted=True,
                    indices=sources)
    dist = np.where(np.isfinite(dist), dist, 0.0)
    return dist.max(axis=1).astype(np.int64)


def closeness_ref(g, sources=None, *, wf_improved: bool = False
                  ) -> np.ndarray:
    """Closeness centrality oracle via SciPy BFS distances: outward
    distances over ``g`` as given (symmetrise first for the classical
    undirected definition) — c(s) = (reach-1)/Σ d(s, ·), 0 for a source
    reaching nothing.  ``sources=None`` evaluates every vertex (the exact
    variant); ``wf_improved`` applies the Wasserman–Faust
    ``(reach-1)/(n-1)`` scaling (NetworkX's default).  Matches NetworkX
    ``closeness_centrality(G.reverse(), wf_improved=...)`` on a DiGraph
    (NetworkX measures INWARD distance) — the analytics test suite
    cross-checks that equivalence."""
    from scipy.sparse.csgraph import dijkstra
    if sources is None:
        sources = np.arange(g.n)
    sources = np.asarray(sources, dtype=np.int64)
    if len(sources) == 0:
        return np.zeros(0, dtype=np.float64)
    dist = dijkstra(_csr_matrix(g), directed=True, unweighted=True,
                    indices=sources)                       # (S, n)
    finite = np.isfinite(dist)
    dist_sum = np.where(finite, dist, 0.0).sum(axis=1)
    reach = finite.sum(axis=1).astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        cc = np.where(dist_sum > 0, (reach - 1) / dist_sum, 0.0)
    if wf_improved and g.n > 1:
        cc = cc * (reach - 1) / (g.n - 1)
    return cc


def betweenness_ref(g, sources) -> np.ndarray:
    """Brandes partial betweenness: Σ_{s∈sources} δ_s(v), unnormalised,
    endpoints excluded — the exact quantity ``repro.analytics.betweenness``
    accumulates (NetworkX's ``betweenness_centrality`` equals this with
    ``sources=range(n)``, ``normalized=False`` on a DiGraph; the analytics
    test suite cross-checks that equivalence)."""
    n = g.n
    indptr, indices = g.indptr, g.indices
    bc = np.zeros(n, dtype=np.float64)
    for s in sources:
        dist = np.full(n, -1, dtype=np.int64)
        sigma = np.zeros(n, dtype=np.float64)
        dist[int(s)] = 0
        sigma[int(s)] = 1.0
        order = [int(s)]
        head = 0
        while head < len(order):
            v = order[head]
            head += 1
            for w in indices[indptr[v]:indptr[v + 1]]:
                w = int(w)
                if dist[w] < 0:
                    dist[w] = dist[v] + 1
                    order.append(w)
                if dist[w] == dist[v] + 1:
                    sigma[w] += sigma[v]
        delta = np.zeros(n, dtype=np.float64)
        for v in reversed(order):
            for w in indices[indptr[v]:indptr[v + 1]]:
                w = int(w)
                if dist[w] == dist[v] + 1:
                    delta[v] += sigma[v] / sigma[w] * (1.0 + delta[w])
        delta[int(s)] = 0.0
        bc += delta
    return bc


def _csr_matrix_w(g, weights: np.ndarray):
    import scipy.sparse as sp
    return sp.csr_matrix(
        (np.asarray(weights, dtype=np.float64), g.indices, g.indptr),
        shape=(g.n, g.n))


def sssp_ref(g, sources, weights: np.ndarray) -> np.ndarray:
    """Single-source shortest-path oracle via SciPy Dijkstra on the
    weighted CSR (directed, ``weights`` in ``g``'s edge order): (S, n)
    float64 distances, +inf for unreachable vertices — the exact quantity
    ``repro.analytics.sssp`` converges to (delta-stepping and Dijkstra
    agree on positive weights)."""
    from scipy.sparse.csgraph import dijkstra
    sources = np.asarray(sources, dtype=np.int64)
    return dijkstra(_csr_matrix_w(g, weights), directed=True,
                    indices=sources)


def pagerank_ref(g, *, damping: float = 0.85, tol: float = 1e-10,
                 weights: np.ndarray | None = None) -> np.ndarray:
    """PageRank oracle via NetworkX on the DiGraph of ``g`` (uniform
    out-edge split unless ``weights`` is given), matching the dangling-
    mass redistribution ``repro.analytics.pagerank`` implements."""
    import networkx as nx
    G = nx.DiGraph()
    G.add_nodes_from(range(g.n))
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    w = np.ones(g.m) if weights is None else np.asarray(weights, np.float64)
    G.add_weighted_edges_from(zip(src.tolist(), g.indices.tolist(),
                                  w.tolist()))
    pr = nx.pagerank(G, alpha=damping, tol=tol, max_iter=1000,
                     weight="weight")
    return np.array([pr[v] for v in range(g.n)], dtype=np.float64)
