"""Share of the pulled queue slots that held a live VSS: 100 x the sum of
the engine's ``bfs.live_vss`` counter over the sum of ``bfs.pull_slots``
(the ladder width chosen), over the window's queries."""
from bench.program_trace import count


def read(run):
    slots = count(run, "bfs.pull_slots")
    if not slots:
        return None
    return 100.0 * count(run, "bfs.live_vss") / slots
