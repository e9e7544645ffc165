"""Weighted shortest-path distances, compared with float64 Dijkstra.

The reference is SciPy's Dijkstra over the generated CSR and its float32
weights, computed in float64 (``bench.reference.sssp_distances``).

* Unreached vertices are ``+inf`` on both sides, exactly.
* Reached vertices agree within the relative tolerance ``TOL``.  The
  weights are float32 on both sides, so the program's only error is its
  float32 accumulation: each addition along a path rounds by at most
  2^-24 of the partial sum, which is at most the final distance, so a
  path of h arcs is off by at most h * 2^-24 relative.  ``HOP_BOUND`` is
  the most arcs any vertex's shortest path took in the reference's
  predecessor trees on the scale-19 Kronecker graph with [0, 1) weights
  (``bench/hop_bound.py``); ``TOL`` is 128 * 2^-24, several times that
  bound, and 256 times tighter than bfloat16's rounding of one weight
  (2^-9).
* ``edges`` counts the undirected edges with both ends reached, Graph500
  Kernel 3's count per root; ``levels_run`` stays 0.

The control is the reference with every weight rounded to bfloat16, the
precision below the float32 the configuration states.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

from bench.check import CsrGraph, Verdict
from bench.reference import sssp_distances

CONTROL = ("the reference over the weights rounded to bfloat16, in the "
           "program's place")

HOP_BOUND = 29      # measured: see the module docstring
TOL = 2.0 ** -17    # 7.6e-6 = 128 * 2^-24


def _reference(graph: CsrGraph, src: int, refs: dict) -> np.ndarray:
    if src not in refs:
        refs[src] = sssp_distances(graph.indptr, graph.indices,
                                   graph.weights, src)
    return refs[src]


def check(graph: CsrGraph, answers: list, refs: dict | None = None
          ) -> Verdict:
    """Compare every answer with the reference from its root."""
    refs = {} if refs is None else refs
    deg = np.diff(graph.indptr)
    v = Verdict()
    worst = 0.0
    for a in answers:
        want = _reference(graph, a.src, refs)
        reached = np.isfinite(want)
        v.edges += int(deg[reached].sum()) // 2
        v.checked += 1
        if a.value is None:
            v.missing_answers += 1
            continue
        got = np.asarray(a.value, dtype=np.float64)
        if got.shape != want.shape or not (
                np.isfinite(got[reached]).all()
                and (got[~reached] == np.inf).all()):
            v.wrong_answers += 1
            continue
        gap, ref = np.abs(got[reached] - want[reached]), want[reached]
        # a gap at a distance of 0 (the root) counts as 1
        rel = np.divide(gap, ref, out=(gap > 0).astype(np.float64),
                        where=ref > 0)
        top = float(rel.max())
        worst = max(worst, top)
        v.wrong_answers += int(top > TOL)
    v.extra["max_rel_error"] = (worst, TOL)
    return v


def control_answers(graph: CsrGraph, answers: list,
                    refs: dict | None = None) -> list:
    if graph.weights is None:
        raise ValueError("the distances control rounds the graph's "
                         "weights; this graph has none")
    low = dataclasses.replace(graph, weights=graph.weights.astype(
        ml_dtypes.bfloat16).astype(np.float32))
    cache: dict = {}
    return [dataclasses.replace(a, value=_reference(low, a.src, cache))
            for a in answers]
