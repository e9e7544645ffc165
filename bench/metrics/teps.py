"""Traversed edges per second: the undirected edges with both endpoints
reached, summed over every BFS answered in the window, over the time from
the window's start to the end of the last answer (Graph500 TEPS, without
per-root averaging)."""


def read(run):
    t0, t1 = run.window
    return run.verdict.edges / (t1 - t0)
