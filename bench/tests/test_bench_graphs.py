"""The benchmark's generators and plain reference (CPU)."""
from __future__ import annotations

import ast
import os

import numpy as np
import pytest

from bench.graphs import kronecker, rgg
from bench.graphs.csr import edge_weights
from bench.reference import INF, bfs_levels, edges_reached

KRON = {"scale": 10, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}
RGG = {"k": 10, "radius_factor": 0.55}


def _gen(mod, cfg, seed):
    return mod.generate(cfg, np.random.default_rng([seed, 1]))


def _simple_undirected(n, indptr, indices):
    src = np.repeat(np.arange(n), np.diff(indptr))
    assert indptr[0] == 0 and indptr[-1] == len(indices)
    assert not (src == indices).any(), "self-loop"
    key = src * n + indices
    assert (np.diff(key) > 0).all(), "rows unsorted or duplicate arcs"
    assert np.array_equal(np.sort(indices * n + src), key), "not symmetric"


@pytest.mark.parametrize("mod,cfg", [(kronecker, KRON), (rgg, RGG)],
                         ids=["kronecker", "rgg"])
def test_same_seed_same_graph(mod, cfg):
    a, b, c = (_gen(mod, cfg, s) for s in (2**31 + 5, 2**31 + 5, 6))
    assert a[0] == b[0] and np.array_equal(a[1], b[1]) \
        and np.array_equal(a[2], b[2])
    assert not np.array_equal(a[2], c[2])


@pytest.mark.parametrize("scale", [10, 11, 12])
def test_kronecker_statistics(scale):
    n, indptr, indices = _gen(kronecker, dict(KRON, scale=scale), scale)
    assert n == 1 << scale
    _simple_undirected(n, indptr, indices)
    deg = np.diff(indptr)
    # 16 * 2^scale tuples, most distinct once loops and duplicates go
    edges = len(indices) // 2
    assert 0.55 * 16 * n < edges < 16 * n
    # skew: the top 1% of vertices hold far more than 1% of the arcs,
    # and many vertices are isolated, as in Graph500 graphs
    top = np.sort(deg)[::-1][: n // 100].sum() / deg.sum()
    assert top > 0.10
    assert (deg == 0).mean() > 0.05


@pytest.mark.parametrize("k", [10, 11, 12])
def test_rgg_statistics(k):
    n, indptr, indices = _gen(rgg, dict(RGG, k=k), k)
    assert n == 1 << k
    _simple_undirected(n, indptr, indices)
    mean = len(indices) / n
    expect = np.pi * 0.55 ** 2 * np.log(n)
    # the square's border trims a few percent off the interior degree
    assert 0.85 * expect < mean < 1.02 * expect


def test_rgg_edges_are_the_close_pairs():
    cfg = dict(RGG, k=8)
    n, indptr, indices = _gen(rgg, cfg, 3)
    xy = np.random.default_rng([3, 1]).random((n, 2))
    d2 = ((xy[:, None, :] - xy[None, :, :]) ** 2).sum(-1)
    r = 0.55 * np.sqrt(np.log(n) / n)
    want = (d2 < r * r) & ~np.eye(n, dtype=bool)
    got = np.zeros((n, n), dtype=bool)
    got[np.repeat(np.arange(n), np.diff(indptr)), indices] = True
    assert np.array_equal(got, want)


@pytest.mark.parametrize("mod,cfg", [(kronecker, KRON), (rgg, RGG)],
                         ids=["kronecker", "rgg"])
def test_reference_agrees_with_program_oracle(mod, cfg):
    from repro import Graph
    from repro.core import reference_bfs
    n, indptr, indices = _gen(mod, cfg, 11)
    g = Graph(n, indptr, indices)
    rng = np.random.default_rng(0)
    live = np.flatnonzero(np.diff(indptr) > 0)
    for src in rng.choice(live, 8, replace=False):
        assert np.array_equal(bfs_levels(indptr, indices, int(src)),
                              reference_bfs(g, int(src)))


def test_reference_depth_cap_and_edge_count():
    # path 0-1-2-3 plus the isolated vertex 4
    indptr = np.array([0, 1, 3, 5, 6, 6])
    indices = np.array([1, 0, 2, 1, 3, 2], dtype=np.int32)
    lv = bfs_levels(indptr, indices, 0)
    assert lv.tolist() == [0, 1, 2, 3, INF]
    assert bfs_levels(indptr, indices, 0, max_depth=2).tolist() == \
        [0, 1, 2, INF, INF]
    assert edges_reached(indptr, lv) == 3
    assert edges_reached(indptr, bfs_levels(indptr, indices, 4)) == 0


def test_reference_imports_nothing_of_the_program():
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("reference.py", "check.py", "loadgen.py", "xplane.py",
                "graphs/kronecker.py", "graphs/rgg.py", "graphs/csr.py",
                "answers/levels.py", "answers/distances.py",
                "hop_bound.py"):
        with open(os.path.join(bench, rel)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            assert not any(n.split(".")[0] == "repro" for n in names), rel


@pytest.mark.parametrize("mod,cfg", [(kronecker, KRON), (rgg, RGG)],
                         ids=["kronecker", "rgg"])
def test_edge_weights_are_seeded_and_symmetric(mod, cfg):
    n, indptr, indices = _gen(mod, cfg, 21)
    a, b, c = (edge_weights(n, indptr, indices, np.random.default_rng(s))
               for s in (2**31 + 9, 2**31 + 9, 10))
    assert a.dtype == np.float32 and a.shape == indices.shape
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert (a > 0).all() and (a < 1).all()
    src = np.repeat(np.arange(n), np.diff(indptr))
    back = np.searchsorted(src * n + indices, indices * n + src)
    assert np.array_equal(a[back], a)
    assert len(np.unique(a)) > 0.99 * len(a) / 2


class _ZerosFirst:
    """A generator whose first draw is all zeros."""

    def __init__(self):
        self.rng, self.calls = np.random.default_rng(0), 0

    def random(self, size, dtype):
        self.calls += 1
        if self.calls == 1:
            return np.zeros(size, dtype)
        return self.rng.random(size, dtype=dtype)


def test_edge_weights_draw_a_zero_again():
    n, indptr, indices = _gen(kronecker, KRON, 22)
    w = edge_weights(n, indptr, indices, _ZerosFirst())
    assert (w > 0).all()


def test_roster_is_seeded_and_keeps_to_the_degree_rule():
    from bench.loadgen import roster
    deg = np.array([0, 3, 1, 0, 2, 5, 0, 4, 1, 2])
    pick = [roster(np.random.default_rng([s, 2]), deg, 5, 1)
            for s in (2**33 + 1, 2**33 + 1, 7)]
    assert np.array_equal(pick[0], pick[1])
    assert not np.array_equal(pick[0], pick[2])
    for p in pick:
        assert len(set(p.tolist())) == 5 and (deg[p] >= 1).all()
    assert sorted(roster(np.random.default_rng(1), deg, 7,
                         1).tolist()) == [1, 2, 4, 5, 7, 8, 9]
    with pytest.raises(ValueError):
        roster(np.random.default_rng(1), deg, 8, 1)
