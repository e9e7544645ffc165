"""Every engine pulls its queue through the one chunk loop
(``repro.core.bfs.pull_chunks``, DESIGN §2.3): the wave engines on one
device in each direction mode and on a 1-D mesh, the 2-D BFS and wave
engines, and betweenness stay oracle-exact at every live VSS count around
the chunk edges (the graphs of ``tests.test_pull_chunks``); and the 2-D
butterfly's per-device collective bytes shrink as the mesh grows from
2×2 to 4×2 while the 1-D all-gather's do not."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.analytics.betweenness import make_betweenness
from repro.core import reference_bfs
from repro.core.bfs import INF, BlestProblem
from repro.core.bvss import build_bvss
from repro.core.multi_source import make_multi_source_bfs
from repro.kernels.ref import betweenness_ref
from tests.test_pull_chunks import DIRECTIONS, LIVE, REPO, fan

CASES = sorted(LIVE)


def wave_sources(g) -> np.ndarray:
    """Two wave columns: source 0, whose level-2 queue holds the case's
    live VSS count, and the last vertex."""
    return np.array([0, g.n - 1], dtype=np.int32)


def wrong_columns(lv: np.ndarray, g, srcs) -> int:
    """Vertices whose wave level differs from the oracle, over the
    columns."""
    return int(sum((lv[:g.n, j] != reference_bfs(g, int(s))).sum()
                   for j, s in enumerate(srcs)))


@pytest.fixture(scope="module")
def mesh_results():
    """Every case on a 1-D mesh of 2 (the wave engine, each direction)
    and a 2×2 mesh (the 2-D BFS and wave engines, pull), and the
    collective bytes of one 2-D and 1-D level as the mesh grows, in one
    child process (the simulated device count binds at backend start)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
               + REPO, XLA_FLAGS="--xla_force_host_platform_device_count=8")
    code = """
import json
import numpy as np
from repro.core.bfs import BlestProblem, make_blest_bfs
from repro.core.bvss import build_sharded_bvss
from repro.core.multi_source import make_multi_source_bfs
from repro.core.policy import prepare
from repro.distributed.bfs_dist import bfs_mesh, bfs_mesh2d
from repro.distributed.collectives import comm_ledger
from repro.graphs import generators as gen
from tests.test_pull_chunks import DIRECTIONS, LIVE, fan
from tests.test_pull_chunks_waves import wave_sources, wrong_columns
mesh1, mesh2 = bfs_mesh(2), bfs_mesh2d(2, 2)
out = {}
for case in LIVE:
    g = fan(case)
    srcs = wave_sources(g)
    p = BlestProblem.build_sharded(build_sharded_bvss(g, 2), mesh1, "data")
    for direction in DIRECTIONS:
        fn = make_multi_source_bfs(g, len(srcs), problem=p,
                                   use_kernel=False, direction=direction)
        out[f"1d/{case}/{direction}"] = wrong_columns(
            np.asarray(fn(srcs)), g, srcs)
    p2 = BlestProblem.build_sharded_2d(build_sharded_bvss(g, (2, 2)), mesh2)
    bfs = make_blest_bfs(p2, lazy=False, direction="pull",
                         use_kernels=False)
    out[f"2d/{case}/bfs"] = wrong_columns(
        np.asarray(bfs(0))[:, None], g, srcs[:1])
    wave = make_multi_source_bfs(g, len(srcs), problem=p2,
                                 use_kernel=False, direction="pull")
    out[f"2d/{case}/wave"] = wrong_columns(np.asarray(wave(srcs)), g, srcs)

def level_bytes(mesh):
    p = prepare(gen.rmat(8, 16, seed=1), w=512, mesh=mesh).problem
    with comm_ledger() as events:
        make_blest_bfs(p, lazy=False).lower(0)
    return int(sum(nb for _, nb in events))
out["bytes"] = {"2x2": level_bytes(bfs_mesh2d(2, 2)),
                "4x2": level_bytes(bfs_mesh2d(4, 2)),
                "flat4": level_bytes(bfs_mesh(4)),
                "flat8": level_bytes(bfs_mesh(8))}
print(json.dumps(out))
"""
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("direction", DIRECTIONS)
@pytest.mark.parametrize("case", CASES)
def test_wave_pull_matches_the_reference(case, direction):
    g = fan(case)
    srcs = wave_sources(g)
    fn = make_multi_source_bfs(g, len(srcs), use_kernel=False,
                               direction=direction)
    assert wrong_columns(np.asarray(fn(srcs)), g, srcs) == 0


@pytest.mark.parametrize("direction", DIRECTIONS)
@pytest.mark.parametrize("case", CASES)
def test_wave_pull_on_a_mesh_matches_the_reference(mesh_results, case,
                                                    direction):
    assert mesh_results[f"1d/{case}/{direction}"] == 0


@pytest.mark.parametrize("engine", ["bfs", "wave"])
@pytest.mark.parametrize("case", CASES)
def test_2d_pull_matches_the_reference(mesh_results, case, engine):
    assert mesh_results[f"2d/{case}/{engine}"] == 0


@pytest.mark.parametrize("case", CASES)
def test_betweenness_matches_the_oracle(case):
    """Levels and σ path counts exactly (every fan graph is a tree from
    each source, so each reached vertex has one shortest path), and the
    dependencies within the oracle tests' tolerance."""
    g = fan(case)
    srcs = wave_sources(g)
    fn = make_betweenness(BlestProblem.build(build_bvss(g)), len(srcs),
                          use_kernel=False)
    lv, sig, delta = (np.asarray(a) for a in fn(srcs))
    assert wrong_columns(lv, g, srcs) == 0
    reached = lv != INF
    np.testing.assert_array_equal(sig[reached], 1.0)
    np.testing.assert_allclose(delta.sum(axis=1), betweenness_ref(g, srcs),
                               rtol=1e-4, atol=1e-4)


def test_2d_collective_bytes_shrink_as_the_mesh_grows(mesh_results):
    """One level's per-device collective bytes, traced: the 2-D
    butterfly's fall from 2×2 to 4×2 devices, the 1-D all-gather's do not
    fall from 4 to 8."""
    b = mesh_results["bytes"]
    assert b["2x2"] > b["4x2"] > 0, b
    assert b["flat4"] <= b["flat8"], b
