"""Closed loop, one query at a time: ``GraphSessionManager.sssp`` from a
seeded roster of roots, cycled, back to back (Graph500 Kernel 3).

Mix keys: ``roster`` (roots), ``root_min_degree``.
"""
from __future__ import annotations

import time

from bench.loadgen import roster


def setup(run) -> None:
    mix = run.cell.traffic
    run.plan = roster(run.rng, run.degrees, int(mix["roster"]),
                      int(mix["root_min_degree"]))
    run.mgr.sssp(run.name, int(run.plan[0]))


def window(run, seconds: float) -> None:
    plan = run.plan
    t0 = time.perf_counter()
    end = t0 + seconds
    i = 0
    with run.spans("window"):
        while time.perf_counter() < end:
            src = int(plan[i % len(plan)])
            due = time.perf_counter()
            with run.spans("sssp_call"):
                dist = run.mgr.sssp(run.name, src)
            run.answer(src, dist, due, time.perf_counter())
            i += 1
    run.window = (t0, run.answers[-1].done)


def close(run) -> None:
    pass
