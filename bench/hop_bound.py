#!/usr/bin/env python3
"""Measure the hop bound behind the distances check's tolerance.

    python bench/hop_bound.py [--scale 19] [--roots 64] [--seed <n>]

Generates ``kron_g500``'s Kronecker graph at ``--scale`` with Graph500
Kernel 3 weights (``bench/graphs/csr.edge_weights``), drawn from the seed
as a run draws them, runs the reference Dijkstra from ``--roots`` seeded
roots of degree >= 1 and prints one JSON line per root: the host seconds
Dijkstra took and the most arcs on any vertex's shortest path, read from
its predecessor tree.  The last line holds the maximum over the roots.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.graphs import kronecker  # noqa: E402
from bench.graphs.csr import edge_weights  # noqa: E402
from bench.loadgen import roster  # noqa: E402
from bench.reference import sssp_distances  # noqa: E402
import scipy.sparse.csgraph  # noqa: E402,F401  (its import is not timed)


def tree_depth(pred: np.ndarray) -> np.ndarray:
    """Arcs from each vertex up to its tree's root (0 at the root and at
    unreached vertices), by pointer doubling over ``pred`` (< 0: none)."""
    n = len(pred)
    up = np.where(pred >= 0, pred, np.arange(n))
    hops = (pred >= 0).astype(np.int64)
    for _ in range(max(1, n).bit_length() + 1):
        hops = hops + hops[up]
        up = up[up]
    return hops


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=int, default=19)
    ap.add_argument("--roots", type=int, default=64)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "bench", "configs", "kron_g500.json")) as f:
        cfg = dict(json.load(f), scale=args.scale)
    rng = np.random.default_rng([args.seed, 1])
    n, indptr, indices = kronecker.generate(cfg, rng)
    weights = edge_weights(n, indptr, indices, rng)
    roots = roster(np.random.default_rng([args.seed, 2]), np.diff(indptr),
                   args.roots, 1)
    worst = 0
    for src in roots.tolist():
        t = time.perf_counter()
        dist, pred = sssp_distances(indptr, indices, weights, src,
                                    predecessors=True)
        took = time.perf_counter() - t
        hops = int(tree_depth(pred).max())
        worst = max(worst, hops)
        print(json.dumps({"root": src, "dijkstra_s": took, "hops": hops,
                          "reached": int(np.isfinite(dist).sum())}),
              flush=True)
    print(json.dumps({"scale": args.scale, "n": n, "arcs": int(indptr[-1]),
                      "roots": len(roots), "seed": args.seed,
                      "hop_bound": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
