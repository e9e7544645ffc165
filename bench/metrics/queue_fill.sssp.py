"""Share of the pulled queue slots that held a live VSS in the SSSP
relaxation: 100 x the sum of the engine's ``sssp.live_vss`` counter over
the sum of ``sssp.pull_slots`` (``PULL_CHUNK`` x chunks), over the
window's queries."""
from bench.program_trace import count


def read(run):
    slots = count(run, "sssp.pull_slots")
    if not slots:
        return None
    return 100.0 * count(run, "sssp.live_vss") / slots
