"""The Graph500 Kernel 3 cell on the CPU at a tiny size: its own
configuration, generator, mix and driver run through the harness and
read correct, the bfloat16 control reads every answer wrong, and each of
its per-layer readers reads what the program records (on a hand-made
run for the device stages) and leaves its metric out of a program
without the tracer."""
from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

from bench.harness import metrics, run_cell
from bench.run import report
from bench.spec import load_cell, load_plugin
from bench.tests.tiny import ROOT, tiny_root

CELL = "kron_g500_sssp.sssp"
SEED = 2**31 + 1_000_003
NEW = ("expand_ms_per_round.sssp", "update_ms_per_round.sssp",
       "bucket_ms_per_round.sssp", "queue_fill.sssp",
       "host_ms_per_query.sssp", "weight_plane_s", "minplus_roofline.sssp")


def _read(name: str, run):
    return load_plugin(os.path.join(ROOT, "bench", "metrics",
                                    name + ".py")).read(run)


@pytest.fixture(scope="module")
def cell_run(tmp_path_factory):
    """The real cell at scale 7, with the control read after it."""
    root = tiny_root(str(tmp_path_factory.mktemp("sssp")))
    path = os.path.join(root, "bench", "configs", "kron_g500_sssp.json")
    with open(path) as f:
        conf = json.load(f)
    conf["scale"] = 7
    with open(path, "w") as f:
        json.dump(conf, f)
    return run_cell(root, CELL, SEED, 0.5, False, require_tpu=False,
                    control=True)


def test_cell_reads_correct_and_its_control_not(cell_run):
    run = cell_run
    assert run.cell.traffic["kind"] == "closed_loop_sssp"
    assert run.cell.traffic["answers"] == "distances"
    assert run.weights is not None
    v, c = run.verdict, run.control
    assert v.correct and v.checked > 0 and v.levels_run == 0
    assert v.wrong_answers == 0 and v.missing_answers == 0
    worst, limit = v.extra["max_rel_error"]
    assert 0 <= worst <= limit == 2.0 ** -17
    assert c.checked == v.checked and c.wrong_answers == c.checked
    line = report(run)
    assert line["correct"] is True
    assert set(line["checks"]) == {"wrong_answers", "missing_answers",
                                   "max_rel_error"}
    assert report(run, c)["correct"] is False
    assert set(metrics(run)) == {"teps", "setup_s"}
    assert metrics(run)["teps"]["value"] > 0


def test_program_records_what_the_cell_reads(cell_run):
    """The readers of spans and counters read the CPU run itself; the
    stage and kernel readers need a device trace, which it has not."""
    run = cell_run
    assert _read("weight_plane_s", run) > 0
    assert 0 < _read("queue_fill.sssp", run) <= 100
    assert _read("host_ms_per_query.sssp", run) > 0
    for name in NEW:
        if "_round" in name or "roofline" in name:
            assert _read(name, run) is None


def _spans():
    from repro.tracing import Span
    spans = [Span("repro.prepare.weights", 3.5, 4.25, 4, 1, None)]
    # (request, start, end, wait start, wait end, counts): the first is
    # the warm-up, before the window
    queries = [(1, 5.0, 6.0, 5.1, 5.9, {"sssp.rounds": 100}),
               (2, 11.0, 12.0, 11.1, 11.9,
                {"sssp.rounds": 30, "sssp.pull_slots": 2048,
                 "sssp.live_vss": 1024, "sssp.pull_chunks": 4}),
               (3, 13.0, 13.5, 13.05, 13.45,
                {"sssp.rounds": 10, "sssp.pull_slots": 2048,
                 "sssp.live_vss": 1536, "sssp.pull_chunks": 4})]
    for r, t0, t1, w0, w1, counts in queries:
        spans += [Span("repro.query.wait", w0, w1, 10 * r + 2, 10 * r, r),
                  Span("repro.query", t0, t1, 10 * r, None, r, counts)]
    return spans


STAGES = {"fusion.1": "sssp.update", "bvss_spmm_minplus.2": "sssp.expand",
          "fusion.3": "sssp.expand", "fusion.4": "sssp.bucket",
          "fusion.5": "bfs.update"}
SLOT_BYTES = 4 * (32 + 8 * 128 + 8 + 128)        # mask, plane, x in, y out
EXPAND_S = 4096 * SLOT_BYTES / 819e9 / 0.8        # 80% of the roofline


def _run(trace=True):
    ops = {"fusion.1 f32[1,524288]": 2.0,
           "bvss_spmm_minplus.2 f32[1,512,128]": EXPAND_S / 10,
           "fusion.3 f32[512,8,128]": EXPAND_S * 0.9,
           "fusion.4 s32[129536]": 0.4,
           "fusion.5 s32[8]": 9.0}            # another engine's stage
    return SimpleNamespace(
        window=(10.0, 20.0),
        devices=[SimpleNamespace(device_kind="TPU v5 lite")],
        trace=SimpleNamespace(n_ops=len(ops), op_seconds=ops)
        if trace else None)


WANT = {"update_ms_per_round.sssp": 2000.0 / 40,
        "expand_ms_per_round.sssp": 1e3 * EXPAND_S / 40,
        "bucket_ms_per_round.sssp": 400.0 / 40,
        "queue_fill.sssp": 100.0 * 2560 / 4096,
        "host_ms_per_query.sssp": 150.0,
        "weight_plane_s": 0.75,
        "minplus_roofline.sssp": 80.0}


@pytest.fixture
def program(monkeypatch):
    from repro import tracing
    monkeypatch.setattr(tracing, "snapshot", _spans)
    monkeypatch.setattr(tracing, "stages", lambda: dict(STAGES))


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_hand_made_run(program, name):
    assert _read(name, _run()) == pytest.approx(WANT[name])


def test_minplus_bytes_per_slot():
    mod = load_plugin(os.path.join(ROOT, "bench", "metrics",
                                   "minplus_roofline.sssp.py"))
    assert mod.minplus_bytes(1) == 128 + 4096 + 32 + 512
    assert mod.minplus_bytes(512, sources=2) == 512 * (128 + 4096
                                                       + 64 + 1024)


@pytest.mark.parametrize("name", NEW)
def test_reader_leaves_out_a_program_without_tracer(monkeypatch, name):
    import repro
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read(name, _run()) is None


def test_each_new_metric_is_declared_for_the_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        assert declared[name]["workloads"] == [CELL]
        want = "setup_s" if name == "weight_plane_s" else "teps"
        assert declared[name]["moves"] == want
    teps = next(m for m in spec["end_to_end"] if m["name"] == "teps")
    assert teps["workloads"] == ["kron_g500.bfs", "rgg_dimacs10.bfs", CELL]
    cell = load_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.config["scale"] == 19
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s", "teps"]
