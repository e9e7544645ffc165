"""Random geometric graph of the DIMACS10 ``rgg_n_2_k_s0`` family.

``n = 2^k`` points uniform in the unit square, an undirected edge between
every two points closer than ``radius_factor * sqrt(ln n / n)`` (0.55 in
the DIMACS10 generator).  Vectorised with a cell grid of side >= radius:
only points in the same or adjacent cells are compared.
"""
from __future__ import annotations

import numpy as np

from bench.graphs.csr import undirected_csr

# half of the 3x3 neighbourhood: each unordered pair of cells once
_OFFSETS = ((0, 0), (1, 0), (-1, 1), (0, 1), (1, 1))


def generate(cfg: dict, rng: np.random.Generator
             ) -> tuple[int, np.ndarray, np.ndarray]:
    n = 1 << int(cfg["k"])
    r = float(cfg["radius_factor"]) * np.sqrt(np.log(n) / n)
    xy = rng.random((n, 2))
    side = max(1, int(1.0 / r))
    cell = np.minimum((xy / (1.0 / side)).astype(np.int64), side - 1)
    cid = cell[:, 0] * side + cell[:, 1]
    order = np.argsort(cid, kind="stable")
    xy, cell, cid = xy[order], cell[order], cid[order]
    start = np.searchsorted(cid, np.arange(side * side + 1))
    us, vs = [], []
    for dx, dy in _OFFSETS:
        cx, cy = cell[:, 0] + dx, cell[:, 1] + dy
        ok = (cx >= 0) & (cx < side) & (cy >= 0) & (cy < side)
        i = np.flatnonzero(ok)
        other = cx[i] * side + cy[i]
        lo, cnt = start[other], start[other + 1] - start[other]
        ii = np.repeat(i, cnt)
        jj = (np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
              + np.arange(int(cnt.sum())))
        if (dx, dy) == (0, 0):
            keep = jj > ii
            ii, jj = ii[keep], jj[keep]
        d = xy[ii] - xy[jj]
        near = np.einsum("ij,ij->i", d, d) < r * r
        us.append(ii[near])
        vs.append(jj[near])
    # a vertex's id is its point's draw index: order maps sorted -> drawn
    indptr, indices = undirected_csr(n, order[np.concatenate(us)],
                                     order[np.concatenate(vs)])
    return n, indptr, indices
