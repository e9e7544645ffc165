"""The benchmark's own host spans around its calls into the program.

Each span is kept in memory with its host-clock bounds and, in a traced
run, also written into the profiler's trace (``TraceAnnotation``) so the
trace reduction can name the device's idle gaps by what the host was
doing.
"""
from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))
