"""Device milliseconds of the engine's ``bfs.expand`` stage (frontier gather and pull, or push compaction and expansion, Pallas kernels included) per
level the engine counted (``bfs.levels``) in the window."""
from bench.program_trace import stage_ms_per_level


def read(run):
    return stage_ms_per_level(run, "bfs.expand")
