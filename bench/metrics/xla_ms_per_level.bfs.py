"""Device milliseconds in every other device operation (XLA's scatter-min,
compaction, ladder) per BFS level run."""


def read(run):
    if run.trace is None or not run.trace.n_ops or not run.verdict.levels_run:
        return None
    return run.trace.other_s * 1e3 / run.verdict.levels_run
