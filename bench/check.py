"""Whether the timed path answered right: every answer against the plain
reference, exactly.

BFS levels are integers, so the comparison is exact and every limit is 0:
an answer is wrong if any vertex's level differs, missing if it never
came.  The control is the reference put in the program's place with one
stated guarantee broken: it stops one level short, so the vertices of
the last level stay unreached (an early exit from the level loop).
:func:`control_answers` puts its levels into the recorded answers, and
the same :func:`check` has to read them as not correct.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import INF, bfs_levels, edges_reached

LIMITS = {"wrong_answers": 0, "missing_answers": 0}


@dataclasses.dataclass
class Verdict:
    checked: int = 0
    wrong_answers: int = 0
    missing_answers: int = 0
    levels_run: int = 0        # sum over answers of (depth + 1)
    edges: int = 0             # undirected edges reached, summed

    @property
    def correct(self) -> bool:
        return (self.checked > 0 and self.wrong_answers <= LIMITS[
            "wrong_answers"] and self.missing_answers <= LIMITS[
            "missing_answers"])

    def readings(self) -> dict[str, dict]:
        return {k: {"value": getattr(self, k), "limit": v}
                for k, v in LIMITS.items()}


def depth_of(levels: np.ndarray) -> int:
    reached = levels[levels != INF]
    return int(reached.max()) if len(reached) else 0


def _reference(indptr, indices, src: int, refs: dict) -> np.ndarray:
    if src not in refs:
        refs[src] = bfs_levels(indptr, indices, src)
    return refs[src]


def check(indptr: np.ndarray, indices: np.ndarray, answers: list,
          refs: dict | None = None) -> Verdict:
    """Compare every answer with the reference from its root.  ``refs``
    (root -> levels) keeps the reference's answers between calls."""
    refs = {} if refs is None else refs
    v = Verdict()
    for a in answers:
        want = _reference(indptr, indices, a.src, refs)
        v.levels_run += depth_of(want) + 1
        v.edges += edges_reached(indptr, want)
        v.checked += 1
        if a.levels is None:
            v.missing_answers += 1
            continue
        got = np.asarray(a.levels)
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


def control_answers(indptr: np.ndarray, indices: np.ndarray, answers: list,
                    refs: dict | None = None) -> list:
    """The recorded answers with the control's levels in place of the
    program's: the same roots, due and done times."""
    refs = {} if refs is None else refs
    return [dataclasses.replace(
        a, levels=stopped_short(_reference(indptr, indices, a.src, refs)))
        for a in answers]
