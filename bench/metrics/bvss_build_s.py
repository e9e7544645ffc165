"""Seconds of the BVSS stage of ``prepare``: the program's span
``repro.prepare.bvss`` (BVSS build, update scheme, device upload)."""
from bench.program_trace import last_span_s


def read(run):
    return last_span_s("repro.prepare.bvss")
