"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

Everything that belongs to one configuration, traffic mix, graph
generator, traffic driver or metric lives in a file of its own, found by
name, so that a new cell is added with new files and entries only:

* configuration   ``<file>`` named by its ``configs`` entry (JSON);
* traffic mix     ``bench/traffic/<traffic>.json``;
* generator       ``bench/graphs/<config["generator"]>.py``;
* traffic driver  ``bench/drivers/<mix["kind"]>.py``;
* answer check    ``bench/answers/<mix["answers"]>.py``, ``levels`` when
                  the mix names none (``bench/check.py``);
* metric reader   ``bench/metrics/<metric name>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: str

    @property
    def answers(self) -> str:
        return self.traffic.get("answers", "levels")

    def plugin(self, kind: str, name: str) -> ModuleType:
        return load_plugin(os.path.join(self.bench_dir, kind, name + ".py"))


def load_plugin(path: str) -> ModuleType:
    """Import one plug-in file by its path (names may hold dots)."""
    key = "bench_plugin:" + os.path.abspath(path)
    if key in sys.modules:
        return sys.modules[key]
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    work = _by_name(spec["workloads"], name, "workload")
    conf = _by_name(spec["configs"], work["config"], "config")
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, "bench")
    with open(os.path.join(bench_dir, "traffic",
                           work["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(work["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                bench_dir=bench_dir)
