"""Per-backend tile autotuning for the hybrid BFS engines (DESIGN §2.8).

The direction-optimizing step has three static knobs the compiler cannot
pick: the graduated pull-queue ladder (``widths``), the push-phase vertex
cap (``push_cap``) and the Beamer-α saturation guard.  Their best values
depend on the BACKEND (MXU tile shapes vs CPU vector widths vs interpret
overhead) and only coarsely on the graph, so this module measures them
once per *(backend, σ, size-class)* and memoises the winner:

* ``tune(problem)`` times candidate ladders and push caps on SYNTHETIC
  operands of the problem's true tile shapes — a handful of jitted kernel
  dispatches with a small rep budget, no graph traversal — and returns a
  frozen :class:`TileConfig`;
* the module-level cache keys on ``(backend, σ, pow2-bucketized num_vss,
  use_kernels)``: a second ``prepare(..., autotune=True)`` for the same
  backend and graph class performs ZERO additional timing dispatches (the
  tracer's ``autotune.tune_runs`` and ``autotune.cache_hits`` counters
  make that contract testable);
* ``BLEST_AUTOTUNE=0`` in the environment disables measurement entirely
  (the default config is returned, marked ``source="disabled"``) — the CI
  escape hatch for timing-hostile runners.

``core.policy.prepare(..., autotune=True)`` is the consumer: the winning
config is cached on the returned :class:`~repro.core.policy.PreparedBFS`
and its widths/cap are injected into the engine build.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.bfs import (DEFAULT_PUSH_CAP, _round_width, queue_widths)
from repro.errors import ConfigError

#: the dispatch-model's far anchor: candidate widths are never timed
#: directly, only the 128-row floor and this row count are (the affine
#: model interpolates/extrapolates the rest — graph-independent budget)
MAX_TIMED_ROWS = 2048
#: timing repetitions per candidate (after one untimed warmup/compile call)
DEFAULT_REPS = 2


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """The tuned static knobs of one hybrid engine build.

    ``source`` records provenance: ``"tuned"`` (measured this process),
    ``"cached"`` (measured earlier for the same class), ``"disabled"``
    (``BLEST_AUTOTUNE=0``: defaults, no measurement)."""

    pull_widths: tuple[int, ...]
    push_cap: int
    alpha: float
    source: str

    def engine_kwargs(self) -> dict:
        """The ``make_engine`` override dict this config injects."""
        return {"widths": list(self.pull_widths), "push_cap": self.push_cap,
                "alpha": self.alpha}


#: (backend, sigma, pow2 size class, use_kernels) -> winning TileConfig
_TUNE_CACHE: dict[tuple, TileConfig] = {}


def clear_cache() -> None:
    """Drop all memoised configs (test isolation helper)."""
    _TUNE_CACHE.clear()


def _size_class(num_vss: int) -> int:
    """Bucketize the VSS count to the next power of two: graphs in the
    same class share tile shapes closely enough to share a config."""
    b = 1
    while b < max(num_vss, 1):
        b <<= 1
    return b


def class_key(problem, use_kernels: bool) -> tuple:
    """The memoisation key of one tuning run."""
    return (jax.default_backend(), problem.sigma,
            _size_class(problem.num_vss), bool(use_kernels))


def default_config(problem, *, buckets: int = 2,
                   source: str = "disabled") -> TileConfig:
    """The untuned knobs every engine uses when autotuning is off."""
    return TileConfig(
        pull_widths=tuple(queue_widths(problem.num_vss, buckets)),
        push_cap=DEFAULT_PUSH_CAP, alpha=4.0, source=source)


def _time_call(fn: Callable, args: tuple, reps: int) -> float:
    """Best-of-``reps`` wall time of one jitted dispatch (one untimed
    warmup call absorbs compilation)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _pull_operands(width: int, sigma: int, seed: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    rng = np.random.default_rng(seed)
    masks = jnp.asarray(rng.integers(0, 2 ** 32, size=(width, 32),
                                     dtype=np.uint32))
    fbytes = jnp.asarray(rng.integers(0, 2 ** sigma, size=(width,),
                                      dtype=np.uint32))
    return masks, fbytes


def _push_operands(width: int, sigma: int, seed: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    rng = np.random.default_rng(seed)
    masks = jnp.asarray(rng.integers(0, 2 ** 32, size=(width, 32),
                                     dtype=np.uint32))
    bits = jnp.asarray(rng.integers(0, sigma, size=(width,),
                                    dtype=np.int32))
    return masks, bits


def _fit_dispatch_model(timed: Callable, reps: int) -> tuple[float, float]:
    """Fit the affine dispatch-cost model ``t(w) = a + b*w`` from two
    measured anchors (the PULL_TILE floor and ``MAX_TIMED_ROWS``).

    Scoring candidate widths through the fitted model instead of raw
    per-width timings is what makes tuning DETERMINISTIC on dispatch-
    dominated backends: at CPU scale ``t(128)`` and ``t(256)`` differ by
    less than timer noise, so comparing them directly picks a random
    ladder — while the model's slope, anchored ``MAX_TIMED_ROWS`` apart,
    resolves far above the noise floor."""
    lo, hi = 128, MAX_TIMED_ROWS
    t_lo, t_hi = timed(lo, reps), timed(hi, reps)
    b = max((t_hi - t_lo) / (hi - lo), 0.0)
    a = max(t_lo - b * lo, 0.0)
    return a, b


def tune(problem, *, use_kernels: bool = True,
         buckets_candidates: Iterable[int] = (2, 3, 4),
         push_cap_candidates: Iterable[int] = (128, 256),
         reps: int = DEFAULT_REPS) -> TileConfig:
    """Fit dispatch-cost models for the pull and push kernels on the
    current backend and pick ``problem``'s ladder and push cap through
    them; memoised per :func:`class_key`.

    Four timed dispatches total (two anchors per kernel, see
    :func:`_fit_dispatch_model`); every candidate is then scored
    analytically.  The ladder score is the modeled pull time at the
    ladder's SMALLEST width plus its FULL width — the two regimes a
    traversal alternates between (trickle levels ride the small rung,
    bulk levels the full queue); rungs between never cost more than
    either endpoint.  The push cap maximises the ENGAGEMENT RANGE: the
    auto heuristic only takes push when its static cost
    ``round(cap) * max_vss_per_set`` undercuts the rung the ladder would
    select, so the winning cap is the one with the most ladder rungs
    strictly above its cost (modeled push time breaks ties) — a larger
    cap that pushes its own cost past every rung would never fire.
    """
    if reps < 1:
        raise ConfigError(f"autotune needs reps >= 1, got {reps!r}")
    key = class_key(problem, use_kernels)
    cached = _TUNE_CACHE.get(key)
    if cached is not None:
        tracing.count("autotune.cache_hits")
        return dataclasses.replace(cached, source="cached")
    if os.environ.get("BLEST_AUTOTUNE", "") == "0":
        return default_config(problem)
    tracing.count("autotune.tune_runs")
    sigma = problem.sigma
    if use_kernels:
        from repro.kernels import pull_vss_kernel, push_vss_kernel
        pull, push = pull_vss_kernel, push_vss_kernel
    else:
        from repro.kernels.ref import bvss_pull_ref, bvss_push_ref
        pull, push = bvss_pull_ref, bvss_push_ref
    pull_j = jax.jit(lambda m, f: pull(m, f, sigma))
    push_j = jax.jit(lambda m, b: push(m, b, sigma))

    pa, pb = _fit_dispatch_model(
        lambda w, r: _time_call(pull_j, _pull_operands(w, sigma, seed=w), r),
        reps)
    qa, qb = _fit_dispatch_model(
        lambda w, r: _time_call(push_j, _push_operands(w, sigma, seed=w), r),
        reps)

    buckets = sorted(set(int(x) for x in buckets_candidates))
    caps = sorted(set(int(x) for x in push_cap_candidates))
    if not buckets or not caps:
        raise ConfigError("autotune needs at least one buckets and one "
                          f"push-cap candidate, got {buckets_candidates!r} "
                          f"/ {push_cap_candidates!r}")
    best_widths: tuple[int, ...] = ()
    best_score = float("inf")
    for b in buckets:
        widths = tuple(queue_widths(problem.num_vss, b))
        score = (pa + pb * widths[0]) + (pa + pb * widths[-1])
        if score < best_score:
            best_widths, best_score = widths, score

    R = max(problem.max_vss_per_set, 1)
    best_cap, best_key = DEFAULT_PUSH_CAP, None
    for cap in caps:
        pqcap = _round_width(cap)
        cost = pqcap * R
        engagement = sum(1 for w in best_widths if cost < w)
        cand = (-engagement, qa + qb * cost)
        if best_key is None or cand < best_key:
            best_cap, best_key = cap, cand

    cfg = TileConfig(pull_widths=best_widths, push_cap=best_cap,
                     alpha=4.0, source="tuned")
    _TUNE_CACHE[key] = cfg
    return cfg
