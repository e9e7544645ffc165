"""Set-up of the run: process start, graph generation, ``prepare`` and the
warm-up of the cell's engine, ending with its first blocking result."""


def read(run):
    return run.setup["setup_s"]
