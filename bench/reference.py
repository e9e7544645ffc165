"""The benchmark's plain references: a level-synchronous, top-down BFS in
NumPy over CSR, and SciPy's float64 Dijkstra for weighted distances.

Independent of the program under test: they read only the generated CSR
arrays and weights.  Unreached vertices hold ``INF`` (the int32 maximum)
in levels, as in the program's answers, and ``+inf`` in distances.
"""
from __future__ import annotations

import numpy as np

INF = np.int32(np.iinfo(np.int32).max)


def bfs_levels(indptr: np.ndarray, indices: np.ndarray, src: int,
               max_depth: int | None = None) -> np.ndarray:
    """Hop distance of every vertex from ``src``.  ``max_depth`` stops the
    search after that many levels (vertices beyond stay ``INF``)."""
    n = len(indptr) - 1
    levels = np.full(n, INF, dtype=np.int32)
    levels[src] = 0
    frontier = np.array([src], dtype=np.int64)
    depth = 0
    while len(frontier) and (max_depth is None or depth < max_depth):
        depth += 1
        lo = indptr[frontier]
        cnt = indptr[frontier + 1] - lo
        total = int(cnt.sum())
        pos = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(total)
        nbrs = indices[pos]
        nbrs = np.unique(nbrs[levels[nbrs] == INF])
        levels[nbrs] = depth
        frontier = nbrs.astype(np.int64)
    return levels


def edges_reached(indptr: np.ndarray, levels: np.ndarray) -> int:
    """Undirected edges with both endpoints reached (Graph500 TEPS count):
    half the degree sum over reached vertices of a symmetric CSR."""
    deg = np.diff(indptr)
    return int(deg[levels != INF].sum()) // 2


def sssp_distances(indptr: np.ndarray, indices: np.ndarray,
                   weights: np.ndarray | None, src: int, *,
                   predecessors: bool = False):
    """Float64 shortest-path length of every vertex from ``src`` over the
    arcs of the CSR with their ``weights`` (1 each when ``None``),
    ``+inf`` where unreached.  With ``predecessors``, also each vertex's
    parent in the shortest-path tree (-9999 at the root and unreached)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra
    n = len(indptr) - 1
    w = (np.ones(len(indices)) if weights is None
         else np.asarray(weights, dtype=np.float64))
    arcs = csr_matrix((w, indices, indptr), shape=(n, n))
    return dijkstra(arcs, directed=True, indices=int(src),
                    return_predecessors=predecessors)
