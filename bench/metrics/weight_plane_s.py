"""Seconds of the weight-plane stage of ``prepare``: the program's span
``repro.prepare.weights`` (the plane's host build in the BVSS slice
layout and its commit to the device), opened on a weighted graph only."""
from bench.program_trace import last_span_s


def read(run):
    return last_span_s("repro.prepare.weights")
