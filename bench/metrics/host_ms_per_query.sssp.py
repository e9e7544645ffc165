"""Host milliseconds of an SSSP query outside the device wait: the mean
over the window's queries of the program's ``repro.query`` span less its
``repro.query.wait`` child (admission, dispatch, the copy to the host,
the permutation back to the caller's ids)."""
from bench.program_trace import window_queries


def read(run):
    host = [q.seconds - kids["repro.query.wait"].seconds
            for q, kids in window_queries(run) if "repro.query.wait" in kids]
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
