"""Device milliseconds of the SSSP engine's ``sssp.expand`` stage (queue
chunks, the gathers of masks, weight-plane rows and column distances,
and the min-plus kernel) per inner relaxation round the engine counted
(``sssp.rounds``) in the window."""
from bench.stage_time import stage_ms_per


def read(run):
    return stage_ms_per(run, "sssp.expand", "sssp.rounds")
