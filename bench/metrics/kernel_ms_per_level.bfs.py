"""Device milliseconds in Pallas kernels per BFS level run (levels counted
from the reference answers: depth + 1 per BFS)."""


def read(run):
    if run.trace is None or not run.trace.n_ops or not run.verdict.levels_run:
        return None
    return run.trace.pallas_s * 1e3 / run.verdict.levels_run
