"""Device milliseconds of the SSSP engine's ``sssp.bucket`` stage (the
frontier's compaction into the VSS queue, the settle sweep, the bucket
advance, the next frontier and the loops' own control) per inner
relaxation round the engine counted (``sssp.rounds``) in the window."""
from bench.stage_time import stage_ms_per


def read(run):
    return stage_ms_per(run, "sssp.bucket", "sssp.rounds")
