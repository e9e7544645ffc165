"""Graph500 Kronecker generator (specification v3.0, Kernel 1 input).

Follows the specification's reference generator: ``edgefactor * 2^scale``
edge tuples, each built bit by bit from the initiator probabilities
A, B, C (D = 1 - A - B - C), then every vertex label scrambled by one
seeded permutation.  Kernel 1 then keeps the graph undirected, without
self-loops or duplicate edges (:func:`csr.undirected_csr`).
"""
from __future__ import annotations

import numpy as np

from bench.graphs.csr import undirected_csr


def generate(cfg: dict, rng: np.random.Generator
             ) -> tuple[int, np.ndarray, np.ndarray]:
    scale = int(cfg["scale"])
    a, b, c = float(cfg["A"]), float(cfg["B"]), float(cfg["C"])
    n = 1 << scale
    m = int(cfg["edgefactor"]) * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ii = np.zeros(m, dtype=np.int64)
    jj = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ii |= ii_bit.astype(np.int64) << bit
        jj |= jj_bit.astype(np.int64) << bit
    label = rng.permutation(n)
    indptr, indices = undirected_csr(n, label[ii], label[jj])
    return n, indptr, indices
