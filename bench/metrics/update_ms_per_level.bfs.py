"""Device milliseconds of the engine's ``bfs.update`` stage (the scatter-min, or the lazy marks) per
level the engine counted (``bfs.levels``) in the window."""
from bench.program_trace import stage_ms_per_level


def read(run):
    return stage_ms_per_level(run, "bfs.update")
