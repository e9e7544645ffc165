"""The readers of the program's tracer on a hand-made run: its spans and
counters, the stage map, and a trace reduced to device seconds per
operation; and each reader leaves its metric out of a run of a program
without the tracer."""
from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

from bench.spec import load_plugin
from bench.tests.tiny import ROOT

NEW = ("ordering_s", "bvss_build_s", "expand_ms_per_level.bfs",
       "update_ms_per_level.bfs", "finalize_ms_per_level.bfs",
       "queue_fill.bfs", "host_ms_per_query.bfs")
WANT = {"ordering_s": 2.0, "bvss_build_s": 0.5,
        "expand_ms_per_level.bfs": 10.0, "update_ms_per_level.bfs": 50.0,
        "finalize_ms_per_level.bfs": 2.5, "queue_fill.bfs": 25.0,
        "host_ms_per_query.bfs": 150.0}


def _read(name: str, run):
    return load_plugin(os.path.join(ROOT, "bench", "metrics",
                                    name + ".py")).read(run)


def _spans():
    from repro.tracing import Span
    spans = [Span("repro.prepare.ordering", 1.0, 3.0, 2, 1, None),
             Span("repro.prepare.bvss", 3.0, 3.5, 3, 1, None),
             Span("repro.prepare", 1.0, 4.0, 1, None, None)]
    # (request, start, end, wait start, wait end, counts); the first is
    # the warm-up, before the window
    queries = [(1, 5.0, 6.0, 5.1, 5.9, {"bfs.levels": 100}),
               (2, 11.0, 12.0, 11.1, 11.9,
                {"bfs.levels": 5, "bfs.pull_slots": 1000,
                 "bfs.live_vss": 250}),
               (3, 13.0, 13.5, 13.05, 13.45,
                {"bfs.levels": 3, "bfs.pull_slots": 200,
                 "bfs.live_vss": 50})]
    for r, t0, t1, w0, w1, counts in queries:
        spans += [Span("repro.query.dispatch", t0, w0, 10 * r + 1, 10 * r, r),
                  Span("repro.query.wait", w0, w1, 10 * r + 2, 10 * r, r),
                  Span("repro.query", t0, t1, 10 * r, None, r, counts)]
    return spans


STAGES = {"fusion.1": "bfs.update", "bvss_pull.2": "bfs.expand",
          "fusion.3": "bfs.finalize", "copy.4": "bfs.finalize"}


def _run(trace=True):
    ops = {"fusion.1 s32[8]": 0.4, "bvss_pull.2 s8[4]": 0.08,
           "fusion.3 s32[9]": 0.016, "copy.4 u32[2]": 0.004,
           "fusion.9 s32[1]": 0.5}            # outside every stage
    return SimpleNamespace(
        window=(10.0, 20.0),
        trace=SimpleNamespace(n_ops=len(ops), op_seconds=ops)
        if trace else None)


@pytest.fixture
def program(monkeypatch):
    from repro import tracing
    monkeypatch.setattr(tracing, "snapshot", _spans)
    monkeypatch.setattr(tracing, "stages", lambda: dict(STAGES))


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_hand_made_run(program, name):
    assert _read(name, _run()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", [n for n in NEW if n.endswith("_level.bfs")])
def test_stage_reader_needs_a_trace(program, name):
    assert _read(name, _run(trace=False)) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_leaves_out_a_program_without_tracer(monkeypatch, name):
    import repro
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read(name, _run()) is None


def test_each_new_metric_is_declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        m = declared[name]
        if name.endswith(".bfs"):
            assert m["workloads"] == ["kron_g500.bfs", "rgg_dimacs10.bfs"]
        else:
            assert "workloads" not in m and m["moves"] == "setup_s"
