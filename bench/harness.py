"""One run of one cell: set-up, the measured window, the check, the metrics.

Order of a run (one process, one chip):

1. generate the graph, and its edge weights where the configuration's
   generator draws them, from the seed;
2. ``GraphSessionManager.open_session`` -> ``repro.prepare`` (ordering,
   BVSS build, the weight plane where there are weights, engine), timed
   as ``prepare_s``;
3. the traffic driver warms up the cell's own engine (``compile_s``);
4. the window: the driver offers its traffic for ``seconds``; with
   ``trace`` the profiler records it, with the benchmark's host spans;
5. peak device memory is read, the program's state is freed;
6. every answer is compared with the plain reference by the mix's answer
   check (``bench/check.py``; with ``control``, the control's answers are
   then checked in their place, apart);
7. each metric's reader turns what was recorded into its number.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time

import numpy as np

from bench.check import CsrGraph, Verdict
from bench.spans import Spans
from bench.spec import Cell, load_cell

# The persistent compile cache keeps programs up to this size.  The
# program's engines carry their graph's BVSS as compiled-in constants, so
# each is tens of MB and fits one graph, i.e. one seed: kept, it would
# serve only a later run of the same seed, and a run's set-up would read
# the seeds that ran before it.  Such executables are compiled in every
# run; programs that do not carry the graph stay cached.
CACHE_MAX_BYTES = 8 << 20
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}


@dataclasses.dataclass
class Answer:
    src: int
    value: np.ndarray | None    # levels, distances: what the verb returns
    due: float                  # host clock, seconds
    done: float | None          # when the client had the result


class _Compiles:
    """Backend compilations and persistent-cache lookups, as JAX reports
    them through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.count = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def _event(self, event: str, **_) -> None:
        if event in CACHE_EVENTS:
            self.cache[CACHE_EVENTS[event]] += 1


class Run:
    """Everything one run records; the drivers and readers share it."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.traced = trace
        self.spans = Spans(annotate=trace)
        self.rng = np.random.default_rng([seed, 2])
        self.answers: list[Answer] = []
        self.window: tuple[float, float] | None = None
        self.setup: dict[str, float] = {}
        self.compiles_in_window = 0
        self.compile_cache: dict[str, int] = {}
        self.verdict: Verdict | None = None
        self.control: Verdict | None = None     # the control's, if asked
        self.trace = None
        self.memory_peak_bytes = 0
        # filled as the run goes: devices, graph (n, indptr, indices),
        # weights (per arc, or None), degrees, answer_check, mgr and name
        # of the session, plan, ordering, engine

    def answer(self, src: int, value, due: float, done: float | None
               ) -> None:
        self.answers.append(Answer(src, value, due, done))


def _peak_bytes(devices: list) -> int:
    stats = [d.memory_stats() for d in devices]
    return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float | None = None, require_tpu: bool = True,
             control: bool = False) -> Run:
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, name)
    if require_tpu:
        from bench.chip import require_chip
        devices = require_chip(cell.chips)
    import jax
    if not require_tpu:
        devices = jax.devices()[:cell.chips]
    from repro import Graph, GraphSessionManager
    from repro.compile_cache import use_compile_cache

    run = Run(cell, seed, seconds, trace)
    run.devices = devices
    run.cache_dir = use_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    compiles = _Compiles()
    driver = cell.plugin("drivers", cell.traffic["kind"])
    gen = cell.plugin("graphs", cell.config["generator"])
    run.answer_check = cell.plugin("answers", cell.answers)

    t = time.perf_counter()
    n, indptr, indices, *weights = gen.generate(
        cell.config, np.random.default_rng([seed, 1]))
    run.weights = weights[0] if weights else None
    run.graph = (n, indptr, indices)
    run.degrees = np.diff(indptr)
    run.setup["generate_s"] = time.perf_counter() - t

    t = time.perf_counter()
    run.mgr = GraphSessionManager()
    run.name = cell.name
    weighted = {} if run.weights is None else {"weights": run.weights}
    sess = run.mgr.open_session(run.name, Graph(n, indptr, indices),
                                **weighted)
    run.setup["prepare_s"] = time.perf_counter() - t
    run.ordering, run.engine = sess.ordering, sess.engine_name
    run.num_vss = int(sess.bvss.num_vss)
    run.bvss_bytes = int(sess.bvss.memory_bytes()["bvss"])
    del sess

    t = time.perf_counter()
    driver.setup(run)
    run.setup["compile_s"] = time.perf_counter() - t
    run.setup["setup_s"] = time.perf_counter() - t_start
    run.compile_cache = dict(compiles.cache)

    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = compiles.count
    try:
        driver.window(run, seconds)
    finally:
        run.compiles_in_window = compiles.count - before
        if trace:
            jax.profiler.stop_trace()
    run.memory_peak_bytes = _peak_bytes(devices)

    driver.close(run)
    run.mgr.close_session(run.name)
    run.mgr = None
    gc.collect()

    graph = CsrGraph(n, indptr, indices, run.weights)
    ac, refs = run.answer_check, {}
    run.verdict = ac.check(graph, run.answers, refs)
    if control:
        run.control = ac.check(
            graph, ac.control_answers(graph, run.answers, refs), refs)
    if trace:
        from bench import xplane
        try:
            run.trace = xplane.summarize(
                trace_dir, {n for n, _, _ in run.spans.records})
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return run


def metrics(run: Run) -> dict[str, dict]:
    """Each applicable metric's reader; a reader that finds nothing to
    read returns None and the metric is left out."""
    entries = run.cell.per_layer if run.traced else run.cell.end_to_end
    out = {}
    for m in entries:
        value = run.cell.plugin("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
