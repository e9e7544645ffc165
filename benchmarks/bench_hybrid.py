"""PR-7 compiled-dispatch hybrid benchmark: direction-optimizing hybrid
(autotuned ladder + push, DESIGN §2.8) vs the pull-only 2-bucket engine.

This is the COMPILED-DISPATCH lane: both engines run the pure-jnp kernel
twins (``use_kernels=False``) so the whole level loop is ONE XLA-compiled
computation end to end — no Pallas interpreter anywhere in the timed
region.  Interpret-mode wall clocks (the PR-1..PR-5 lanes) are dominated
by the Python kernel-body interpreter and bury exactly the dispatch- and
width-shaped effects the hybrid targets; this lane is the one whose
ratios track real accelerator-shaped behaviour.

Per graph of the small-frontier-heavy suite (high-diameter families whose
traversals spend most levels far below the full queue width):

* ``pull``   — the pre-PR-7 static engine: ``direction="pull"``, the
  original 2-bucket ladder;
* ``hybrid`` — ``direction="auto"`` with the knobs
  ``core.autotune.tune()`` picked for this backend.

Every graph is also oracle-verified (levels AND a valid parents tree via
``parents_from_levels``) in ALL THREE direction modes before timing —
a speedup over wrong answers is worthless, so verification failures zero
the speedup rather than report it.

``--json`` writes the ``BENCH_pr7`` artifact; CI gates
``hybrid.summary.geomean_hybrid_vs_pull`` against
``benchmarks/perf_floors.json`` (floor 1.15 — the PR-7 acceptance
threshold, stricter than the generic 25%-regression rule).
"""
from __future__ import annotations

import json

import numpy as np

import time

from benchmarks.common import bench_envelope, fmt_row, geomean
from repro.core import build_bvss, reference_bfs
from repro import tracing
from repro.core.autotune import tune
from repro.core.bfs import INF, BlestProblem, make_blest_bfs, queue_widths
from repro.core.policy import parents_from_levels
from repro.graphs import Graph, generators as gen


def hybrid_suite(scale: int = 14) -> dict[str, Graph]:
    """Small-frontier-heavy families: high diameter, trickling frontiers,
    ``num_vss`` large enough that the static 2-bucket ladder's small rung
    (``num_vss / 8``) sits far above the real per-level live counts."""
    side = int((1 << scale) ** 0.5)
    return {
        "road": gen.grid2d(side, side, shuffle=True, seed=3),
        "web": gen.clustered((1 << scale) // 60, 60, p_in=0.4, seed=4),
        "rgg": gen.rgg2d(1 << scale, seed=5),
        # planted-partition graph whose frontier trace makes auto mode
        # genuinely alternate pull and push levels (tests/test_hybrid.py
        # replays the predicate host-side to prove it)
        "flip": gen.clustered(40, 60, p_in=0.4, seed=1),
    }


#: graphs in the suite for oracle VERIFICATION only, excluded from the
#: gated geomean: flip is n=2400 — its ~40ms traversals sit at the
#: dispatch-noise floor, so its ratio is a coin toss that would make the
#: CI floor flake at par; its job (proving a genuine pull/push multi-flip
#: stays oracle-exact in all three modes) doesn't need a stopwatch
TIMING_EXCLUDED = frozenset({"flip"})


def _best_sec(f, reps: int) -> float:
    """Min-of-``reps`` wall time.  The lane gates a RATIO of two timed
    loops, and scheduler/co-tenant noise is one-sided (it only ever adds
    time), so the minimum is the low-variance estimator of the true
    dispatch cost — medians of this workload were observed swinging
    ~30% between idle runs, which would make the CI floor flake."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t0)
    return best


def _valid_parents(g: Graph, levels: np.ndarray, src: int) -> bool:
    parents = parents_from_levels(g, levels)
    if parents[src] != -1:
        return False
    reached = np.flatnonzero((levels != INF) & (np.arange(g.n) != src))
    return bool((parents[reached] >= 0).all()
                and (levels[parents[reached]] == levels[reached] - 1).all()
                and (parents[levels == INF] == -1).all())


def _verify(g: Graph, problem, cfg, src: int) -> dict[str, bool]:
    """Oracle parity (levels + parents) in all three direction modes."""
    want = reference_bfs(g, src)
    out = {}
    for direction in ("pull", "push", "auto"):
        fn = make_blest_bfs(problem, lazy=False, use_kernels=False,
                            direction=direction,
                            **(cfg.engine_kwargs()
                               if direction == "auto" else {}))
        lv = np.asarray(fn(src))
        out[direction] = bool(np.array_equal(lv, want)
                              and _valid_parents(g, lv, src))
    return out


def run(scale: int = 14, n_sources: int = 2, reps: int = 5,
        json_path: str | None = None, verbose: bool = True):
    suite = hybrid_suite(scale)
    graphs_out = {}
    for gname, g in suite.items():
        rng = np.random.default_rng(0)
        cand = np.flatnonzero(g.out_degree > 0)
        srcs = [int(s) for s in rng.choice(
            cand, size=min(n_sources, len(cand)), replace=False)]
        b = build_bvss(g)
        problem = BlestProblem.build(b)
        cfg = tune(problem, use_kernels=False)
        verified = _verify(g, problem, cfg, srcs[0])

        pull_fn = make_blest_bfs(problem, lazy=False, use_kernels=False,
                                 buckets=2, direction="pull")
        hybrid_fn = make_blest_bfs(problem, lazy=False, use_kernels=False,
                                   direction="auto", **cfg.engine_kwargs())

        def sweep(fn):
            for s in srcs:
                np.asarray(fn(s))

        sweep(pull_fn)      # compile + warm
        sweep(hybrid_fn)
        pull_sec = _best_sec(lambda: sweep(pull_fn), reps) / len(srcs)
        hybrid_sec = _best_sec(lambda: sweep(hybrid_fn), reps) / len(srcs)
        ref = reference_bfs(g, srcs[0])
        n_levels = (int(ref[ref != INF].max()) if (ref != INF).any() else 0)
        speedup = (pull_sec / max(hybrid_sec, 1e-12)
                   if all(verified.values()) else 0.0)
        graphs_out[gname] = {
            "timed": gname not in TIMING_EXCLUDED,
            "n": int(g.n), "m": int(g.m), "num_vss": int(b.num_vss),
            "max_vss_per_set": int(problem.max_vss_per_set),
            "levels": n_levels,
            "base_widths": queue_widths(b.num_vss, 2),
            "tuned": {"widths": list(cfg.pull_widths),
                      "push_cap": cfg.push_cap, "alpha": cfg.alpha,
                      "source": cfg.source},
            "pull_sec": pull_sec, "hybrid_sec": hybrid_sec,
            "speedup_hybrid_vs_pull": speedup,
            "verified": verified,
        }
        if verbose:
            print(fmt_row(f"bench_hybrid/{gname}/pull", pull_sec * 1e6,
                          f"levels={n_levels}"))
            print(fmt_row(f"bench_hybrid/{gname}/hybrid", hybrid_sec * 1e6,
                          f"speedup={speedup:.2f};verified="
                          f"{all(verified.values())}"))
    summary = {
        "geomean_hybrid_vs_pull": geomean(
            [go["speedup_hybrid_vs_pull"] for go in graphs_out.values()
             if go["timed"]]),
        "all_verified": all(all(go["verified"].values())
                            for go in graphs_out.values()),
        "autotune": {k: tracing.counter("autotune." + k)
                     for k in ("tune_runs", "cache_hits")},
    }
    out = {
        **bench_envelope("pr7_hybrid_compiled_dispatch", scale),
        "lane": "compiled-dispatch",
        "use_kernels": False,
        "n_sources": int(n_sources),
        "note": ("pure-jnp kernel twins, whole level loop XLA-compiled "
                 "end to end (no Pallas interpreter in the timed region); "
                 "speedups are zeroed unless the hybrid is oracle-exact "
                 "in all three direction modes, parents included; graphs "
                 "with timed=false (the multi-flip demonstration graph) "
                 "are verification-only and excluded from the gated "
                 "geomean — too small to time above dispatch noise"),
        "graphs": graphs_out,
        "summary": summary,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1, sort_keys=False)
        if verbose:
            print(f"# wrote {json_path}")
    if verbose:
        print(f"# geomean_hybrid_vs_pull="
              f"{summary['geomean_hybrid_vs_pull']:.2f}x "
              f"all_verified={summary['all_verified']}")
    return out


if __name__ == "__main__":
    run(json_path="BENCH_pr7_hybrid.json")
