"""Reduce a profiler trace to the benchmark's device numbers.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
three plain lists (kept as JSON for the tests):

* ``ops``     device operations ``[device, name, start_ns, dur_ns, pallas]``
  from each TPU plane's ``XLA Ops`` line; ``pallas`` marks Mosaic
  custom calls (the Pallas kernels);
* ``modules`` executions of compiled programs ``[device, name, start_ns,
  dur_ns]`` from the ``XLA Modules`` line;
* ``spans``   the benchmark's own host spans ``[name, start_ns, dur_ns]``.

:func:`reduce` works on those lists inside the benchmark's ``window``
span: device busy time is the union of op intervals (averaged over the
devices), Pallas and other time are sums over leaf ops (an op that
encloses others on its line, such as a loop, counts only through them),
and every idle gap is named by the innermost benchmark span open at its
middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_PALLAS_MARK = "tpu_custom_call"


def _short(name: str) -> str:
    """An HLO op event's instruction name and result type, without layout:
    ``%fusion.3 = s32[4097]{0:T(1024)} fusion(...)`` -> ``fusion.3 s32[4097]``
    (a tuple result is left out)."""
    op, _, rest = name.partition(" = ")
    op = op.lstrip("%")
    result = rest.split(" ", 1)[0]
    if not result or result.startswith("("):
        return op
    return op + " " + re.sub(r"\{[^}]*\}", "", result)


def _is_pallas(name: str) -> bool:
    """A Mosaic custom call: its HLO text names the TPU custom-call target."""
    return _PALLAS_MARK in name


def _device_id(plane_name: str) -> int | None:
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str, span_names: set[str]) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        dev = _device_id(plane.name)
        for line in plane.lines:
            if dev is None:
                if plane.name.startswith("/host:"):
                    spans.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                 for e in line.events
                                 if e.name in span_names)
                continue
            if line.name == OPS_LINE:
                for e in line.events:
                    ops.append([dev, _short(e.name), int(e.start_ns),
                                int(e.duration_ns),
                                _is_pallas(e.name)])
            elif line.name == MODULES_LINE:
                modules.extend([dev, e.name, int(e.start_ns),
                                int(e.duration_ns)] for e in line.events)
    return {"ops": ops, "modules": modules, "spans": spans}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _leaves(ops: list) -> list:
    """Ops of one device that enclose no other op of that device."""
    ops = sorted(ops, key=lambda o: (o[2], -o[3]))
    leaves = []
    for i, o in enumerate(ops):
        end = o[2] + o[3]
        if i + 1 < len(ops) and ops[i + 1][2] < end and o[3] > 0 \
                and ops[i + 1][2] + ops[i + 1][3] <= end:
            continue
        leaves.append(o)
    return leaves


def _innermost(points: list[float], spans: list) -> list[str]:
    """For each point (ascending), the name of the shortest span open at
    it, or ``WINDOW`` where none is."""
    spans = sorted(spans, key=lambda s: s[1])
    active: list = []
    out, i = [], 0
    for p in points:
        while i < len(spans) and spans[i][1] <= p:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] + s[2] >= p]
        out.append(min(active, key=lambda s: s[2])[0] if active else WINDOW)
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float               # per device, averaged over devices
    pallas_s: float             # per device
    other_s: float              # per device
    modules: dict[str, int]     # executions per device
    op_seconds: dict[str, float]
    gaps: list[tuple[str, float]]           # longest first
    idle_by_span: dict[str, float]
    n_ops: int                  # device ops inside the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def reduce(events: dict) -> Summary:
    windows = [s for s in events["spans"] if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} '{WINDOW}' spans")
    w0 = windows[0][1]
    w1 = w0 + windows[0][2]

    def clip(a: int, b: int) -> tuple[int, int] | None:
        a, b = max(a, w0), min(b, w1)
        return (a, b) if b > a else None

    by_dev: dict[int, list] = collections.defaultdict(list)
    for o in events["ops"]:
        by_dev[o[0]].append(o)
    n_dev = max(len(by_dev), 1)
    busy_ns = 0
    pallas_ns = other_ns = 0
    op_ns: dict[str, float] = collections.defaultdict(float)
    all_busy: list[tuple[int, int]] = []
    for dev_ops in by_dev.values():
        iv = [c for o in dev_ops if (c := clip(o[2], o[2] + o[3]))]
        merged = _union(iv)
        busy_ns += sum(b - a for a, b in merged)
        all_busy.extend(merged)
        for o in _leaves(dev_ops):
            c = clip(o[2], o[2] + o[3])
            if c is None:
                continue
            d = c[1] - c[0]
            op_ns[o[1]] += d
            if o[4]:
                pallas_ns += d
            else:
                other_ns += d
    modules: dict[str, int] = collections.Counter()
    for m in events["modules"]:
        if w0 <= m[2] < w1:
            modules[m[1]] += 1
    holes: list[tuple[float, float]] = []     # (middle ns, seconds)
    edge = w0
    for a, b in _union(all_busy) + [(w1, w1)]:
        if a > edge:
            holes.append(((edge + a) / 2, (a - edge) / 1e9))
        edge = max(edge, b)
    names = _innermost([h[0] for h in holes],
                       [s for s in events["spans"] if s[0] != WINDOW])
    idle: dict[str, float] = collections.defaultdict(float)
    for name, (_, sec) in zip(names, holes):
        idle[name] += sec
    gaps = sorted(zip(names, (h[1] for h in holes)), key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy_ns / n_dev / 1e9,
                   pallas_s=pallas_ns / n_dev / 1e9,
                   other_s=other_ns / n_dev / 1e9,
                   modules={k: v / n_dev for k, v in modules.items()},
                   op_seconds={k: v / n_dev / 1e9 for k, v in op_ns.items()},
                   gaps=gaps, idle_by_span=dict(idle),
                   n_ops=sum(1 for o in events["ops"]
                             if clip(o[2], o[2] + o[3])))


def summarize(trace_dir: str, span_names: set[str]) -> Summary:
    return reduce(extract(find_xplane(trace_dir), span_names))
