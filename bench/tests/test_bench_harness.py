"""The harness on the CPU at a tiny size: driven by data, refusing any
device but the chip, and reading ``correct`` as false under each fault the
cells can have and under the control."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from bench.answers.levels import depth_of, stopped_short
from bench.chip import check_devices, peaks
from bench.harness import metrics, run_cell
from bench.reference import bfs_levels
from bench.run import report
from bench.spec import load_cell
from bench.tests.tiny import ROOT, tiny_root

SEED = 2**31 + 77


def _digests(root: str) -> dict[str, str]:
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            if "__pycache__" in base:
                continue
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _write(path: str, text: str) -> None:
    with open(path, "w") as f:
        f.write(text)


def test_new_config_mix_and_metric_need_no_edit(tmp_path):
    root = tiny_root(str(tmp_path))
    before = _digests(root)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "kron_g500.json")) as f:
        conf = json.load(f)
    conf.update(name="kron_small", scale=7)
    _write(os.path.join(bench, "configs", "kron_small.json"),
           json.dumps(conf))
    _write(os.path.join(bench, "traffic", "bfs_short.json"), json.dumps(
        {"kind": "closed_loop", "roster": 8, "root_min_degree": 1}))
    _write(os.path.join(bench, "metrics", "answers_per_s.py"),
           "def read(run):\n"
           "    t0, t1 = run.window\n"
           "    return len(run.answers) / (t1 - t0)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "kron_small", "source": "test",
                            "file": "bench/configs/kron_small.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "kron_small.bfs_short",
                              "config": "kron_small", "traffic": "bfs_short",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "answers_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["kron_small.bfs_short"]})
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec))

    after = _digests(root)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {"BENCHMARK.json"}

    cell = load_cell(root, "kron_small.bfs_short")
    assert cell.config["scale"] == 7
    assert cell.traffic["roster"] == 8
    assert sorted(m["name"] for m in cell.end_to_end) == ["answers_per_s",
                                                   "setup_s"]
    run = run_cell(root, "kron_small.bfs_short", SEED, 0.3, False,
                   require_tpu=False)
    got = metrics(run)
    assert run.verdict.correct
    assert set(got) == {"answers_per_s", "setup_s"}
    assert got["answers_per_s"]["value"] > 0


# A weighted configuration whose mix names the distances check, and a
# test-only driver that asks the session for ``sssp``: new files only.
WEIGHTED_GENERATOR = """\
from bench.graphs import kronecker
from bench.graphs.csr import edge_weights


def generate(cfg, rng):
    n, indptr, indices = kronecker.generate(cfg, rng)
    return n, indptr, indices, edge_weights(n, indptr, indices, rng)
"""

SSSP_DRIVER = """\
import time

from bench.loadgen import roster


def _session(run):
    return run.mgr._get(run.name, "default").session


def setup(run):
    mix = run.cell.traffic
    run.plan = roster(run.rng, run.degrees, int(mix["roster"]),
                      int(mix["root_min_degree"]))
    _session(run).sssp(int(run.plan[0]))


def window(run, seconds):
    sess, plan = _session(run), run.plan
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() < t0 + seconds:
        src = int(plan[i % len(plan)])
        due = time.perf_counter()
        run.answer(src, sess.sssp(src), due, time.perf_counter())
        i += 1
    run.window = (t0, run.answers[-1].done)


def close(run):
    pass
"""

SSSP_CELL = "kron_sssp.sssp"


@pytest.fixture(scope="module")
def weighted(tmp_path_factory):
    """A tiny benchmark with the weighted cell added, and the files that
    adding it changed."""
    root = tiny_root(str(tmp_path_factory.mktemp("weighted")))
    before = _digests(root)
    bench = os.path.join(root, "bench")
    _write(os.path.join(bench, "graphs", "kronecker_weighted.py"),
           WEIGHTED_GENERATOR)
    _write(os.path.join(bench, "drivers", "sssp_loop.py"), SSSP_DRIVER)
    _write(os.path.join(bench, "traffic", "sssp.json"), json.dumps(
        {"kind": "sssp_loop", "answers": "distances", "roster": 8,
         "root_min_degree": 1}))
    with open(os.path.join(bench, "configs", "kron_g500.json")) as f:
        conf = json.load(f)
    conf.update(name="kron_sssp", generator="kronecker_weighted", scale=7)
    _write(os.path.join(bench, "configs", "kron_sssp.json"),
           json.dumps(conf))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "kron_sssp", "source": "test",
                            "file": "bench/configs/kron_sssp.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": SSSP_CELL, "config": "kron_sssp",
                              "traffic": "sssp", "chips": 1, "why": "test"})
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec))
    after = _digests(root)
    return root, {p for p in before if after.get(p) != before[p]}


def test_weighted_config_and_answer_check_need_no_edit(weighted):
    root, changed = weighted
    assert changed == {"BENCHMARK.json"}
    run = run_cell(root, SSSP_CELL, SEED + 2, 0.3, False, require_tpu=False,
                   control=True)
    assert run.weights is not None and len(run.graph) == 3
    v, c = run.verdict, run.control
    assert v.correct and v.checked > 0 and v.levels_run == 0
    assert 0 <= v.extra["max_rel_error"][0] <= v.extra["max_rel_error"][1]
    assert c.checked == v.checked and not c.correct
    line = report(run, c)
    assert line["correct"] is False
    assert set(line["checks"]) == {"wrong_answers", "missing_answers",
                                   "max_rel_error"}
    assert report(run)["correct"] is True


def _distances_unchanged(d: np.ndarray) -> np.ndarray:
    out = np.full_like(d, np.inf)
    out[d == 0] = 0             # the state the bucket loop started from
    return out


def _distance_altered(d: np.ndarray) -> np.ndarray:
    out = d.copy()
    far = np.flatnonzero(np.isfinite(d))
    out[far[np.argmax(d[far])]] *= 1 + 1e-4
    return out


def _distances_half(d: np.ndarray) -> np.ndarray:
    out = d.copy()
    out[: len(d) // 2] = np.inf
    return out


DISTANCE_FAULTS = {"state_unchanged": _distances_unchanged,
                   "answer_altered": _distance_altered,
                   "half_left_out": _distances_half}


def _plant_weighted(monkeypatch, fault: str) -> None:
    from repro import GraphSessionManager
    from repro.serve.graph_session import GraphSession
    if fault == "weights_left_out":
        open_session = GraphSessionManager.open_session
        monkeypatch.setattr(
            GraphSessionManager, "open_session",
            lambda self, name, g, weights=None, **kw:
            open_session(self, name, g, **kw))
        return
    sssp = GraphSession.sssp
    monkeypatch.setattr(GraphSession, "sssp", lambda self, src, **kw:
                        DISTANCE_FAULTS[fault](sssp(self, src, **kw)))


@pytest.mark.parametrize("fault",
                         sorted(DISTANCE_FAULTS) + ["weights_left_out"])
def test_weighted_fault_reads_not_correct(weighted, monkeypatch, fault):
    _plant_weighted(monkeypatch, fault)
    run = run_cell(weighted[0], SSSP_CELL, SEED + 3, 0.3, False,
                   require_tpu=False)
    assert run.verdict.checked > 0
    assert not run.verdict.correct


def _fake(platform: str, kind: str):
    return SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("devices,chips", [
    ([_fake("cpu", "cpu")], 1),
    ([_fake("tpu", "TPU v9 imaginary")], 1),
    ([_fake("tpu", "TPU v5 lite")], 4),
], ids=["cpu", "unknown-kind", "too-few-chips"])
def test_only_a_known_chip_is_measured(devices, chips):
    with pytest.raises(SystemExit):
        check_devices(devices, chips, peaks())
    check_devices([_fake("tpu", "TPU v5 lite")] * 4, chips, peaks())


def _bench_cmd(root: str) -> list[str]:
    return [sys.executable, os.path.join(root, "bench", "run.py"),
            "--workload", "kron_g500.bfs", "--seed", str(SEED),
            "--seconds", "1", "--trace", "0"]


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu-backend", "benchmark-files-alone"])
def test_run_exits_nonzero_without_chip_or_program(tmp_path, alone):
    root = ROOT
    if alone:
        root = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        shutil.copytree(os.path.join(ROOT, "bench"),
                        os.path.join(root, "bench"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(_bench_cmd(root), cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout


# ---------------------------------------------------------------------------
# faults planted under the timed path: each has to read as not correct
# ---------------------------------------------------------------------------
INF = np.iinfo(np.int32).max


def _unchanged(lv: np.ndarray) -> np.ndarray:
    out = np.full_like(lv, INF)
    out[lv == 0] = 0            # the state the level loop started from
    return out


def _altered(lv: np.ndarray) -> np.ndarray:
    out = lv.copy()
    far = np.flatnonzero((lv != INF) & (lv > 0))
    out[far[-1]] += 1
    return out


def _half(lv: np.ndarray) -> np.ndarray:
    out = lv.copy()
    out[: len(lv) // 2] = INF
    return out


ANSWER_FAULTS = {"state_unchanged": _unchanged, "answer_altered": _altered,
                 "half_left_out": _half}


def _plant_single_source(monkeypatch, fault) -> None:
    from repro.core.policy import PreparedBFS
    levels = PreparedBFS.levels
    monkeypatch.setattr(PreparedBFS, "levels",
                        lambda self, src: fault(levels(self, src)))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("fault", sorted(ANSWER_FAULTS))
def test_single_source_fault_reads_not_correct(root, monkeypatch, fault):
    _plant_single_source(monkeypatch, ANSWER_FAULTS[fault])
    run = run_cell(root, "kron_g500.bfs", SEED, 0.3, False,
                   require_tpu=False)
    assert run.verdict.checked > 0
    assert not run.verdict.correct


@pytest.mark.parametrize("cell", ["kron_g500.bfs", "rgg_dimacs10.bfs"])
def test_program_passes_and_control_fails(root, cell):
    run = run_cell(root, cell, SEED + 1, 0.5, False, require_tpu=False,
                   control=True)
    v, c = run.verdict, run.control
    assert v.correct and v.wrong_answers == 0 and v.missing_answers == 0
    assert c.checked == v.checked > 0
    assert not c.correct and c.wrong_answers == c.checked
    assert report(run)["correct"] is True
    line = report(run, c)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"] == {"value": c.checked,
                                               "limit": 0}
    assert set(line["checks"]) == {"wrong_answers", "missing_answers"}
    got = metrics(run)
    want = {m["name"] for m in run.cell.end_to_end}
    assert set(got) == want


@pytest.mark.parametrize("config", ["kron_g500", "rgg_dimacs10"])
def test_control_is_the_reference_stopped_one_level_short(root, config):
    cell = load_cell(root, config + ".bfs")
    gen = cell.plugin("graphs", cell.config["generator"])
    n, indptr, indices = gen.generate(cell.config,
                                      np.random.default_rng([SEED, 1]))
    src = int(np.argmax(np.diff(indptr)))
    want = bfs_levels(indptr, indices, src)
    depth = depth_of(want)
    assert depth >= 2
    assert np.array_equal(stopped_short(want),
                          bfs_levels(indptr, indices, src,
                                     max_depth=depth - 1))
