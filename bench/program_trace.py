"""What the program's own tracer (``repro.tracing``) recorded in a run:
its spans, the device counters each query carries, and the stage of each
device operation of the compiled engine.

Each reader returns None where the program has no tracer (a commit from
before it), so the metrics that read it are left out of such a run.
"""
from __future__ import annotations

import collections

QUERY = "repro.query"


def tracer():
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def last_span_s(name: str) -> float | None:
    """Seconds of the program's last span ``name``."""
    t = tracer()
    if t is None:
        return None
    found = [s for s in t.snapshot() if s.name == name]
    return found[-1].seconds if found else None


def window_queries(run) -> list:
    """``(query span, {child name: child span})`` for every query whose
    ``repro.query`` span lies inside the run's window."""
    t = tracer()
    if t is None or run.window is None:
        return []
    t0, t1 = run.window
    spans = t.snapshot()
    queries = {s.request: s for s in spans if s.name == QUERY
               and t0 <= s.start and s.end <= t1}
    children: dict = collections.defaultdict(dict)
    for s in spans:
        if s.request in queries and s.name != QUERY:
            children[s.request][s.name] = s
    return [(q, children[r]) for r, q in queries.items()]


def count(run, name: str) -> int:
    """Sum of the device counter ``name`` over the window's queries."""
    return sum((q.counts or {}).get(name, 0) for q, _ in window_queries(run))


def stage_ms_per_level(run, stage: str) -> float | None:
    """Device milliseconds of the operations the program maps to
    ``stage``, per level the engine counted in the window."""
    t = tracer()
    if t is None or run.trace is None or not run.trace.n_ops:
        return None
    levels = count(run, "bfs.levels")
    if not levels:
        return None
    stages = t.stages()
    seconds = sum(sec for op, sec in run.trace.op_seconds.items()
                  if stages.get(op.split(" ", 1)[0]) == stage)
    return seconds * 1e3 / levels
