#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run
generates its graph and traffic from ``--seed``, prepares the graph
through the program's normal path, warms up the cell's own engine,
measures for ``--seconds``, checks every answer against the plain
reference and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit, also the last lines on standard error.

It exits non-zero and prints no result without a TPU, with fewer chips
than the cell needs, on a device kind missing from ``peaks.json``, or
without the program (``src/repro``) beside it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def report(run, verdict=None) -> dict:
    """The result line for ``verdict``, by default the program's."""
    import jax
    from bench.harness import metrics
    v = run.verdict if verdict is None else verdict
    n, indptr, _ = run.graph
    s = run.setup
    log(f"graph {run.cell.config['name']}: n={n} arcs={int(indptr[-1])} "
        f"generated in {s['generate_s']:.3f}s")
    log(f"prepare {s['prepare_s']:.3f}s ordering={run.ordering} "
        f"engine={run.engine} num_vss={run.num_vss} "
        f"bvss_bytes={run.bvss_bytes}")
    log(f"warm-up {s['compile_s']:.3f}s; persistent compile cache "
        f"{run.cache_dir}: hits={run.compile_cache['hits']} "
        f"misses={run.compile_cache['misses']}")
    log(f"setup {s['setup_s']:.3f}s")
    t0, t1 = run.window
    log(f"window {t1 - t0:.3f}s: {v.checked} answers, {v.levels_run} "
        f"levels, {v.edges} edges reached; compilations in window "
        f"{run.compiles_in_window}")
    took = sorted(a.done - a.due for a in run.answers if a.done is not None)
    if took:
        log(f"answer times: median {took[len(took) // 2] * 1e3:.3f} ms, "
            f"max {took[-1] * 1e3:.3f} ms over {len(took)}")
    d = run.devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": v.correct, "attempted": v.checked,
           "failed": v.wrong_answers + v.missing_answers,
           "metrics": metrics(run), "device": device}
    if run.trace is not None:
        tr = run.trace
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        out["breakdown"] = tr.breakdown()
        for span, sec in tr.idle_by_span.items():
            log(f"device idle during {span}: {sec:.6f}s")
    out["checks"] = v.readings()
    for k, r in out["checks"].items():
        log(f"check {k} {r['value']} limit {r['limit']}")
    return out


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        sys.exit(f"bench: the program is not in this checkout ({e})")
    log(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import run_cell
    run = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=T_START)
    line = report(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
