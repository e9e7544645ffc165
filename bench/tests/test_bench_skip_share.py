"""The reader of ``update_skip_share.sssp``, the share of the SSSP
update's pairs that carry no candidate: on the Kernel 3 cell's CPU run,
on a hand-made run, and on programs without the ``finite_rows`` counter
or without the tracer, which it leaves out."""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import pytest

from bench.tests.test_bench_sssp import (CELL, _read, _run, _spans,
                                         cell_run)  # noqa: F401 - fixture

NAME = "update_skip_share.sssp"
# (query, finite_rows): the first is the warm-up, before the window
FINITE = {1: 999, 2: 100000, 3: 31072}


def _counted_spans():
    spans = []
    for s in _spans():
        if s.counts and s.request in FINITE:
            s = dataclasses.replace(s, counts={
                **s.counts, "sssp.finite_rows": FINITE[s.request]})
        spans.append(s)
    return spans


@pytest.fixture
def counted(monkeypatch):
    from repro import tracing
    monkeypatch.setattr(tracing, "snapshot", _counted_spans)


def test_cell_run_counts_finite_rows(cell_run):  # noqa: F811 - fixture
    """The engine's sixth counter reaches the reader through the
    session's query spans: a share of the pulled rows, not all of them."""
    assert 0 <= _read(NAME, cell_run) < 100


def test_reader_on_a_hand_made_run(counted):
    # 4096 slots of 128 rows in the window, 131072 of them finite
    assert _read(NAME, _run()) == pytest.approx(75.0)


def test_reader_leaves_out_a_program_without_the_counter(monkeypatch):
    """A program that counts the pulls but not ``finite_rows`` (one from
    before the update kernel) reads nothing, not a share of 100%."""
    from repro import tracing
    monkeypatch.setattr(tracing, "snapshot", _spans)
    assert _read("queue_fill.sssp", _run()) == pytest.approx(62.5)
    assert _read(NAME, _run()) is None


def test_reader_leaves_out_a_program_without_tracer(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read(NAME, _run()) is None


def test_metric_is_declared_for_the_cell_alone():
    from bench.tests.tiny import ROOT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    m = next(m for m in spec["per_layer"] if m["name"] == NAME)
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "SSSP engine",
                 "moves": "teps", "workloads": [CELL]}
