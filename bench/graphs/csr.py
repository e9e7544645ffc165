"""Undirected edge list -> symmetric CSR, and per-edge weights, shared by
the graph generators.

Self-loops and duplicate edges are dropped; every remaining edge {u, v}
is stored as the two arcs u->v and v->u, each row sorted.
"""
from __future__ import annotations

import numpy as np


def undirected_csr(n: int, u: np.ndarray, v: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
    """(indptr int64 (n+1,), indices int32 (2m,)) of the simple undirected
    graph on ``n`` vertices with edges ``{u[i], v[i]}``."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    keep = u != v
    lo = np.minimum(u[keep], v[keep])
    hi = np.maximum(u[keep], v[keep])
    key = np.unique(lo * n + hi)
    lo, hi = key // n, key % n
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    arc = np.sort(src * n + dst)
    counts = np.bincount(arc // n, minlength=n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr, (arc % n).astype(np.int32)


def edge_weights(n: int, indptr: np.ndarray, indices: np.ndarray,
                 rng: np.random.Generator) -> np.ndarray:
    """float32 (2m,): one weight per undirected edge, the same on both of
    its arcs, drawn in order of the edge's (lower, higher) endpoint,
    uniform on float32's grid of [0, 1) as Graph500 Kernel 3 has it, but
    for 0: an exact 0 (2^-24 per edge) is drawn again, since the program
    takes positive weights only, as delta-stepping needs."""
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    dst = indices.astype(np.int64)
    key = np.minimum(src, dst) * n + np.maximum(src, dst)
    lower = key[src < dst]          # each edge once, in CSR (= key) order
    w = rng.random(len(lower), dtype=np.float32)
    while (zero := np.flatnonzero(w == 0)).size:
        w[zero] = rng.random(zero.size, dtype=np.float32)
    return w[np.searchsorted(lower, key)]
