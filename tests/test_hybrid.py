"""Direction-optimizing push/pull hybrid (DESIGN §2.8): push-kernel
parity, oracle parity of levels AND parents in all three direction modes
across the single-source / lazy / multi-source engines, and sharded parity
on {1, 2, 8} devices."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import reference_bfs
from repro.core.bfs import (DEFAULT_PUSH_CAP, BlestProblem, _round_width,
                            chunk_ceil, make_engine, queue_capacity)
from repro.core.bvss import build_bvss
from repro.core.multi_source import make_multi_source_bfs
from repro.core.policy import parents_from_levels
from repro.errors import ConfigError
from repro.graphs import generators as gen
from repro.kernels import push_vss_kernel
from repro.kernels.ref import bvss_push_ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(0)
INF = np.int32(np.iinfo(np.int32).max)
DIRECTIONS = ("pull", "push", "auto")

FAMILIES = {
    "rmat": gen.rmat(8, 8, seed=1),
    "star": gen.star(97),
    "path": gen.path(64),
    "grid": gen.grid2d(17, 19),
}
#: planted-partition graph whose frontier trace makes auto mode take BOTH
#: branches (probed host-side in test_auto_mode_genuinely_flips)
FLIP_GRAPH = gen.clustered(40, 60, p_in=0.4, seed=1)


def run_py(code: str, n_devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def check_parents(g, levels: np.ndarray, src: int) -> None:
    """A valid BFS tree: the source and unreached vertices are rootless,
    every other reached vertex has an in-neighbour one level shallower."""
    parents = parents_from_levels(g, levels)
    assert parents[src] == -1
    reached = np.flatnonzero((levels != INF) & (np.arange(g.n) != src))
    assert (parents[reached] >= 0).all()
    assert (levels[parents[reached]] == levels[reached] - 1).all()
    assert (parents[levels == INF] == -1).all()


# ---------------------------------------------------------------------------
# push kernel vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sigma", [4, 8, 16, 32])
@pytest.mark.parametrize("B", [1, 5, 127, 128, 129, 513])
def test_push_kernel_sweep(sigma, B):
    masks = RNG.integers(0, 2 ** 32, (B, 32), dtype=np.uint64
                         ).astype(np.uint32)
    bits = RNG.integers(0, sigma, (B,)).astype(np.int32)
    got = np.asarray(push_vss_kernel(masks, bits, sigma))
    want = np.asarray(bvss_push_ref(masks, bits, sigma))
    np.testing.assert_array_equal(got, want)


def test_push_is_pull_with_one_hot_frontier():
    """The defining identity: push(masks, b) == pull(masks, 1 << b)."""
    from repro.kernels import pull_vss_kernel
    masks = RNG.integers(0, 2 ** 32, (200, 32), dtype=np.uint64
                         ).astype(np.uint32)
    bits = RNG.integers(0, 8, (200,)).astype(np.int32)
    onehot = (np.uint32(1) << bits.astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(push_vss_kernel(masks, bits, 8)) > 0,
        np.asarray(pull_vss_kernel(masks, onehot, 8)) > 0)


# ---------------------------------------------------------------------------
# oracle parity: every engine x every direction, levels AND parents
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("direction", DIRECTIONS)
@pytest.mark.parametrize("engine", ["blest", "blest_lazy"])
@pytest.mark.parametrize("gname", sorted(FAMILIES))
def test_hybrid_engine_matches_oracle(engine, direction, gname):
    g = FAMILIES[gname]
    fn = make_engine(g, engine, direction=direction, use_kernels=False)
    for src in (0, g.n // 2, g.n - 1):
        ref = reference_bfs(g, src)
        lv = np.asarray(fn(src))
        np.testing.assert_array_equal(lv, ref)
        check_parents(g, lv, src)


@pytest.mark.parametrize("direction", DIRECTIONS)
def test_hybrid_engine_matches_oracle_kernels(direction):
    """One kernel-backed (interpret-mode Pallas) pass per direction."""
    g = FAMILIES["rmat"]
    fn = make_engine(g, "blest", direction=direction, use_kernels=True)
    for src in (0, g.n - 1):
        np.testing.assert_array_equal(np.asarray(fn(src)),
                                      reference_bfs(g, src))


@pytest.mark.parametrize("direction", DIRECTIONS)
@pytest.mark.parametrize("gname", ["rmat", "star", "path"])
def test_hybrid_multi_source_matches_oracle(direction, gname):
    g = FAMILIES[gname]
    srcs = np.array([0, g.n // 3, g.n // 2, g.n - 1], dtype=np.int32)
    fn = make_multi_source_bfs(g, len(srcs), use_kernel=False,
                               direction=direction)
    lv = np.asarray(fn(srcs))
    for j, s in enumerate(srcs):
        np.testing.assert_array_equal(lv[: g.n, j],
                                      reference_bfs(g, int(s)),
                                      err_msg=f"source {s}")


def test_auto_mode_genuinely_flips():
    """On FLIP_GRAPH the auto heuristic must take BOTH branches: replay
    the on-device predicate host-side from the oracle levels and assert a
    mixed trace, then check auto parity on exactly that graph — so the
    parity run exercises push levels AND pull levels, not one of them."""
    g = FLIP_GRAPH
    b = build_bvss(g)
    p = BlestProblem.build(b)
    qcap = queue_capacity(p.num_vss)
    pqcap = _round_width(DEFAULT_PUSH_CAP)
    push_cost = pqcap * p.max_vss_per_set
    assert push_cost < qcap, "static bail: graph cannot flip"
    vstart = np.asarray(p.dev.vss_of_vertex_start)
    vend = np.asarray(p.dev.vss_of_vertex_end)
    lv = reference_bfs(g, 0)
    n_push = n_pull = 0
    for L in range(int(lv[lv != INF].max())):
        fverts = np.flatnonzero(lv == L)
        rep = np.minimum(np.unique(fverts // b.sigma) * b.sigma, g.n - 1)
        count = int((vend[rep] - vstart[rep]).sum())
        use_push = (len(fverts) <= DEFAULT_PUSH_CAP
                    and push_cost < chunk_ceil(count)
                    and len(fverts) * 4.0 <= int(np.sum(lv > L)))
        n_push += use_push
        n_pull += not use_push
    assert n_push > 0 and n_pull > 0, (n_push, n_pull)
    fn = make_engine(g, "blest", problem=p, direction="auto",
                     use_kernels=False)
    got = np.asarray(fn(0))
    np.testing.assert_array_equal(got, lv)
    check_parents(g, got, 0)


def test_bad_direction_is_config_error():
    g = FAMILIES["path"]
    with pytest.raises(ConfigError):
        make_engine(g, "blest", direction="sideways")
    with pytest.raises(ConfigError):
        make_multi_source_bfs(g, 2, direction="sideways")


def test_track_sigma_rejects_forced_push():
    """The Brandes σ channel has no push twin: forcing push under
    track_sigma must be a typed ConfigError, never silent pull."""
    from repro.core.multi_source import make_ms_engine
    p = BlestProblem.build(build_bvss(FAMILIES["rmat"]))
    with pytest.raises(ConfigError):
        make_ms_engine(p, 2, use_kernel=False, track_sigma=True,
                       direction="push")


# ---------------------------------------------------------------------------
# sharded parity: the same hybrid on {1, 2, 8} devices
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_devices", [1, 2, 8])
def test_sharded_hybrid_matches_oracle(n_devices):
    run_py(f"""
import numpy as np
from repro.graphs import generators as gen
from repro.core import reference_bfs
from repro.core.policy import prepare, parents_from_levels
from repro.distributed.bfs_dist import bfs_mesh
INF = np.int32(np.iinfo(np.int32).max)
mesh = bfs_mesh({n_devices})
for g in (gen.rmat(8, 8, seed=3), gen.clustered(40, 60, p_in=0.4, seed=1)):
    for direction in ("pull", "push", "auto"):
        pb = prepare(g, w=256, mesh=mesh, direction=direction,
                     use_kernels=False)
        for src in (0, g.n - 1):
            lv = pb.levels(src)
            assert (lv == reference_bfs(g, src)).all(), (direction, src)
            par = parents_from_levels(g, lv)
            reached = np.flatnonzero((lv != INF) & (np.arange(g.n) != src))
            assert (par[reached] >= 0).all(), (direction, src)
            assert (lv[par[reached]] == lv[reached] - 1).all(), \\
                (direction, src)
print("ok")
""", n_devices=max(n_devices, 1))


@pytest.mark.parametrize("n_devices", [2, 8])
def test_sharded_multi_source_hybrid_matches_oracle(n_devices):
    run_py(f"""
import numpy as np
from repro.graphs import generators as gen
from repro.core import reference_bfs
from repro.core.bvss import build_sharded_bvss
from repro.core.bfs import BlestProblem
from repro.core.multi_source import make_multi_source_bfs
from repro.distributed.bfs_dist import bfs_mesh
mesh = bfs_mesh({n_devices})
g = gen.rmat(8, 8, seed=3)
sb = build_sharded_bvss(g, {n_devices})
p = BlestProblem.build_sharded(sb, mesh, "data")
srcs = np.array([0, g.n // 3, g.n - 1], dtype=np.int32)
for direction in ("pull", "push", "auto"):
    fn = make_multi_source_bfs(g, len(srcs), problem=p, use_kernel=False,
                               direction=direction)
    lv = np.asarray(fn(srcs))
    for j, s in enumerate(srcs):
        assert (lv[: g.n, j] == reference_bfs(g, int(s))).all(), \\
            (direction, int(s))
print("ok")
""", n_devices=n_devices)
