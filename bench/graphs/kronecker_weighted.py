from bench.graphs import kronecker
from bench.graphs.csr import edge_weights


def generate(cfg, rng):
    n, indptr, indices = kronecker.generate(cfg, rng)
    return n, indptr, indices, edge_weights(n, indptr, indices, rng)
