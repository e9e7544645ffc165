"""Seconds of the ordering stage of ``prepare``: the program's span
``repro.prepare.ordering`` (the ordering pre-pass, the permuted graph and
its inverse)."""
from bench.program_trace import last_span_s


def read(run):
    return last_span_s("repro.prepare.ordering")
