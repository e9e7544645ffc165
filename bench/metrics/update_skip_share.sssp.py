"""Share of the pulled slice rows whose SSSP update is a no-op, as they
carry no candidate: 100 x (1 - the sum of the engine's
``sssp.finite_rows`` counter, the rows with a finite candidate, over 128
x the sum of ``sssp.pull_slots``, the rows of the queue slots pulled),
over the window's queries.  A program that does
not count ``finite_rows`` reads nothing."""
from bench.program_trace import count, window_queries

ROWS_PER_SLOT = 128


def read(run):
    slots = count(run, "sssp.pull_slots")
    if not slots or not any("sssp.finite_rows" in (q.counts or {})
                            for q, _ in window_queries(run)):
        return None
    return 100.0 * (1 - count(run, "sssp.finite_rows")
                    / (ROWS_PER_SLOT * slots))
