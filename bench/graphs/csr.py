"""Undirected edge list -> symmetric CSR, shared by the graph generators.

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
