"""Device milliseconds of the engine's ``bfs.finalize`` stage (the finalise / pack / flag sweep, the queue compaction and the loop's own control) per
level the engine counted (``bfs.levels``) in the window."""
from bench.program_trace import stage_ms_per_level


def read(run):
    return stage_ms_per_level(run, "bfs.finalize")
