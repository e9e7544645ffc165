"""Device milliseconds of the SSSP engine's ``sssp.update`` stage (the
scatter-min of each chunk's relaxed rows into the distances) per inner
relaxation round the engine counted (``sssp.rounds``) in the window."""
from bench.stage_time import stage_ms_per


def read(run):
    return stage_ms_per(run, "sssp.update", "sssp.rounds")
