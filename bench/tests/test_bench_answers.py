"""The answer checks on a small weighted graph (CPU): the distances check
reads what it should as wrong or missing, passes a float32 recomputation
and fails its bfloat16 control."""
from __future__ import annotations

import numpy as np
import pytest

from bench.answers import distances
from bench.check import CsrGraph, Verdict
from bench.graphs import kronecker
from bench.graphs.csr import edge_weights
from bench.harness import Answer
from bench.hop_bound import tree_depth
from bench.reference import INF, bfs_levels, sssp_distances

KRON = {"scale": 8, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng([2**31 + 15, 1])
    n, indptr, indices = kronecker.generate(KRON, rng)
    return CsrGraph(n, indptr, indices,
                    edge_weights(n, indptr, indices, rng))


@pytest.fixture(scope="module")
def roots(graph):
    deg = np.diff(graph.indptr)
    return [int(r) for r in np.argsort(-deg, kind="stable")[:4]]


def _answers(graph, roots, weights="same"):
    w = graph.weights if weights == "same" else weights
    return [Answer(r, sssp_distances(graph.indptr, graph.indices, w, r),
                   0.0, 0.0) for r in roots]


def _float32_distances(graph, src):
    """Bellman-Ford with every sum rounded to float32, as the program
    accumulates."""
    src_of = np.repeat(np.arange(graph.n), np.diff(graph.indptr))
    d = np.full(graph.n, np.inf, np.float32)
    d[src] = 0
    while True:
        new = d.copy()
        np.minimum.at(new, graph.indices, d[src_of] + graph.weights)
        if np.array_equal(new, d):
            return d.astype(np.float64)
        d = new


def test_float32_recomputation_passes(graph, roots):
    got = [Answer(r, _float32_distances(graph, r), 0.0, 0.0) for r in roots]
    v = distances.check(graph, got)
    assert v.correct and v.checked == len(roots)
    gap = v.extra["max_rel_error"]
    assert 0 < gap[0] <= gap[1] == distances.TOL


def test_reference_answers_pass_exactly(graph, roots):
    v = distances.check(graph, _answers(graph, roots))
    assert v.correct and v.extra["max_rel_error"][0] == 0
    reached = np.isfinite(sssp_distances(graph.indptr, graph.indices,
                                         graph.weights, roots[0]))
    assert v.edges > 0 and v.levels_run == 0
    assert v.edges == len(roots) * (
        int(np.diff(graph.indptr)[reached].sum()) // 2)


def _break(graph, roots, how):
    got = _answers(graph, roots)
    d = got[1].value
    reached = np.flatnonzero(np.isfinite(d) & (d > 0))
    if how == "moved":
        d[reached[0]] *= 1 + 1e-3
    elif how == "unreached":
        d[reached[-1]] = np.inf
    elif how == "reached":
        d[np.flatnonzero(~np.isfinite(d))[0]] = 5.0
    elif how == "nan":
        d[np.flatnonzero(~np.isfinite(d))[0]] = np.nan
    elif how == "missing":
        got[1].value = None
    return got


@pytest.mark.parametrize("how", ["moved", "unreached", "reached", "nan"])
def test_one_vertex_off_reads_wrong(graph, roots, how):
    v = distances.check(graph, _break(graph, roots, how))
    assert v.wrong_answers == 1 and v.missing_answers == 0
    assert not v.correct


def test_missing_answer_counts_as_missing(graph, roots):
    v = distances.check(graph, _break(graph, roots, "missing"))
    assert v.missing_answers == 1 and v.wrong_answers == 0
    assert v.checked == len(roots) and not v.correct


def test_bfloat16_control_fails(graph, roots):
    refs: dict = {}
    v = distances.check(graph, _answers(graph, roots), refs)
    ctl = distances.control_answers(graph, _answers(graph, roots), refs)
    c = distances.check(graph, ctl, refs)
    assert v.correct and not c.correct
    assert c.wrong_answers == c.checked == len(roots)
    assert c.extra["max_rel_error"][0] > 10 * distances.TOL


def test_control_needs_weights(graph, roots):
    bare = CsrGraph(graph.n, graph.indptr, graph.indices)
    with pytest.raises(ValueError):
        distances.control_answers(bare, _answers(graph, roots))


def test_tolerance_follows_the_hop_bound():
    assert distances.HOP_BOUND * 2.0 ** -24 <= distances.TOL
    assert distances.TOL * 100 <= 2.0 ** -9


def test_tree_depth_is_the_hop_count(graph, roots):
    for r in roots:
        _, pred = sssp_distances(graph.indptr, graph.indices, None, r,
                                 predecessors=True)
        lv = bfs_levels(graph.indptr, graph.indices, r)
        assert np.array_equal(tree_depth(pred), np.where(lv == INF, 0, lv))


def test_a_reading_over_its_limit_is_not_correct():
    v = Verdict(checked=3)
    assert v.correct
    v.extra["gap"] = (0.5, 0.25)
    assert not v.correct
    assert v.readings()["gap"] == {"value": 0.5, "limit": 0.25}
