"""The single-source pull runs in chunks of ``PULL_CHUNK`` queue slots
(DESIGN §2.3): levels stay oracle-exact at every live VSS count around
the chunk edges, eager and lazy, in each direction mode, on one device
and on a 1-D mesh; the level counters account the chunks run."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import reference_bfs
from repro.core.bfs import (INF, PULL_CHUNK, BlestProblem, make_blest_bfs,
                            queue_capacity)
from repro.core.bvss import build_bvss
from repro.graphs import from_edges

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = PULL_CHUNK
#: the live VSS count one level of each case's graph pulls; ``full``
#: pulls these 600 sets and set 0's VSSs: the whole queue
LIVE = {"0": 0, "1": 1, "C-1": C - 1, "C": C, "C+1": C + 1, "2C": 2 * C,
        "full": 600}
DIRECTIONS = ("pull", "auto", "push")


def fan(case: str):
    """Source 0 reaches vertex 8·i (i = 1..k), one per slice set, and
    each of those one sink of its own (no out-edges): the level-2 pull
    queue holds exactly the k single-VSS sets 1..k.  ``full`` also puts
    vertex 1 on level 1, so that queue holds set 0's VSSs too — every
    VSS of the graph.  With k = 0 the source has no edge at all."""
    full = case == "full"
    k = LIVE[case]
    base = 8 * (k + 1)
    mids = 8 * np.arange(1, k + 1)
    src = [np.zeros(k, np.int64), mids]
    dst = [mids, base + np.arange(k)]
    if full:
        src += [np.array([0, 1])]
        dst += [np.array([1, base + k])]
    n = base + k + 2
    if k == 0:                       # one unreachable edge: a BVSS to build
        src, dst = [np.array([n - 2])], [np.array([n - 1])]
    return from_edges(n, np.concatenate(src), np.concatenate(dst))


def live_counts(b, lv: np.ndarray) -> list[int]:
    """The live VSS count of each level the loop runs: the VSSs of the
    slice sets the level's frontier touches, until one is 0."""
    v2r = np.asarray(b.virtual_to_real[:b.num_vss])
    out, L = [], 0
    while True:
        sets = np.unique(np.flatnonzero(lv == L) // b.sigma)
        c = int(np.isin(v2r, sets).sum())
        if c == 0:
            return out
        out.append(c)
        L += 1


@pytest.fixture(scope="module", params=sorted(LIVE))
def case(request):
    g = fan(request.param)
    b = build_bvss(g)
    want = reference_bfs(g, 0)
    counts = live_counts(b, want)
    k = LIVE[request.param]
    if request.param == "full":
        assert max(counts) == b.num_vss
    else:
        assert k in counts or (k == 0 and counts == [])
    assert queue_capacity(b.num_vss) > b.num_vss, "no dummy-padded tail"
    return request.param, g, b, want, counts


@pytest.fixture(scope="module")
def mesh_results():
    """Every case × lazy × direction on a 2-device 1-D mesh, in one child
    process (the simulated device count binds at backend start)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
               + REPO, XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = """
import json
import numpy as np
from repro.core import reference_bfs
from repro.core.bfs import BlestProblem, make_blest_bfs
from repro.core.bvss import build_sharded_bvss
from repro.distributed.bfs_dist import bfs_mesh
from tests.test_pull_chunks import DIRECTIONS, LIVE, fan
mesh = bfs_mesh(2)
out = {}
for case in LIVE:
    g = fan(case)
    p = BlestProblem.build_sharded(build_sharded_bvss(g, 2), mesh, "data")
    want = reference_bfs(g, 0)
    for lazy in (False, True):
        for direction in DIRECTIONS:
            fn = make_blest_bfs(p, lazy=lazy, direction=direction,
                                use_kernels=False)
            got = np.asarray(fn(0))
            out[f"{case}/{lazy}/{direction}"] = int((got != want).sum())
print(json.dumps(out))
"""
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("placement", ["device", "mesh"])
@pytest.mark.parametrize("direction", DIRECTIONS)
@pytest.mark.parametrize("lazy", [False, True], ids=["eager", "lazy"])
def test_chunked_pull_matches_the_reference(case, lazy, direction,
                                            placement, request):
    name, g, b, want, counts = case
    if placement == "mesh":
        wrong = request.getfixturevalue("mesh_results")[
            f"{name}/{lazy}/{direction}"]
        assert wrong == 0
        return
    fn = make_blest_bfs(BlestProblem.build(b), lazy=lazy,
                        direction=direction, use_kernels=False,
                        counters=True)
    lv, c = fn(0)
    np.testing.assert_array_equal(np.asarray(lv), want)
    levels, push_levels, slots, live, chunks = (int(x) for x in c)
    assert levels == len(counts)
    assert slots == C * chunks
    assert 0 <= slots - live <= (C - 1) * (levels - push_levels)
    if direction == "pull":
        assert push_levels == 0
        assert live == sum(counts)
        assert chunks == sum(-(-x // C) for x in counts)
    if direction == "push":
        assert push_levels == levels and slots == live == 0
    reached = 2 * LIVE[name] + 1 + 2 * (name == "full")
    assert want[want != INF].size == reached
