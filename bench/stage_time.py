"""Device time of the program's named stages in the window of a traced
run, read against the device counters its queries carry.

Each reader returns None where the program has no tracer (a commit from
before it), the run has no trace, or the counter read 0, so the metrics
that read it are left out of such a run.
"""
from __future__ import annotations

from bench.program_trace import count, tracer


def stage_ms_per(run, stage: str, counter: str) -> float | None:
    """Device milliseconds of the operations the program maps to
    ``stage``, per unit of the device counter ``counter`` summed over the
    window's queries."""
    t = tracer()
    if t is None or run.trace is None or not run.trace.n_ops:
        return None
    n = count(run, counter)
    if not n:
        return None
    stages = t.stages()
    seconds = sum(sec for op, sec in run.trace.op_seconds.items()
                  if stages.get(op.split(" ", 1)[0]) == stage)
    return seconds * 1e3 / n

