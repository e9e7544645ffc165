"""BFS levels, compared exactly with the benchmark's own BFS.

Levels are integers, so every limit is 0: an answer is wrong if any
vertex's level differs, missing if it never came.  The configuration
states no precision, so the control breaks a stated guarantee: the
reference stops one level short and the vertices of the last level stay
unreached (an early exit from the level loop).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.check import CsrGraph, Verdict
from bench.reference import INF, bfs_levels, edges_reached

CONTROL = "the reference stopped one level short, in the program's place"


def depth_of(levels: np.ndarray) -> int:
    reached = levels[levels != INF]
    return int(reached.max()) if len(reached) else 0


def _reference(graph: CsrGraph, src: int, refs: dict) -> np.ndarray:
    if src not in refs:
        refs[src] = bfs_levels(graph.indptr, graph.indices, src)
    return refs[src]


def check(graph: CsrGraph, answers: list, refs: dict | None = None
          ) -> Verdict:
    """Compare every answer with the reference from its root."""
    refs = {} if refs is None else refs
    v = Verdict()
    for a in answers:
        want = _reference(graph, a.src, refs)
        v.levels_run += depth_of(want) + 1
        v.edges += edges_reached(graph.indptr, want)
        v.checked += 1
        if a.value is None:
            v.missing_answers += 1
            continue
        got = np.asarray(a.value)
        if got.shape != want.shape or not np.array_equal(got, want):
            v.wrong_answers += 1
    return v


def stopped_short(levels: np.ndarray) -> np.ndarray:
    """The reference's answer had its level loop exited one level early:
    the same as ``bfs_levels(..., max_depth=depth - 1)``."""
    out = levels.copy()
    depth = depth_of(levels)
    if depth:
        out[levels == depth] = INF
    return out


def control_answers(graph: CsrGraph, answers: list,
                    refs: dict | None = None) -> list:
    refs = {} if refs is None else refs
    return [dataclasses.replace(
        a, value=stopped_short(_reference(graph, a.src, refs)))
        for a in answers]
