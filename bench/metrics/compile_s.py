"""Host seconds of the warm-up: the cell's engine compiled (or loaded from
the persistent cache) and run once, ended by a blocking result."""


def read(run):
    return run.setup["compile_s"]
