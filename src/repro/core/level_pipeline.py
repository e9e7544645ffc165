"""Shared BFS level-step skeleton (DESIGN.md §2.3).

Every device engine — eager BLEST (Alg. 2), lazy BLEST (Alg. 3), the BRS
baseline sweep, and the multi-source bit-SpMM path — performs the same
four-stage level step:

    gather   frontier operands for the pull (frontier bytes / bit columns)
    pull     the wide slice×frontier product (Pallas VPU / MXU kernel)
    update   scatter the hits into levels or marks
    finalize finalise levels + rebuild the frontier representation
             (pack words, flag sets, compact the queue)

``LevelPipeline`` captures that shape; ``run_levels`` is the single
on-device ``while_loop`` driver all engines share, so control never
returns to the host between levels (the TPU analogue of the paper's
persistent kernel, §4.3) and the convergence test is on-device.

``step`` is one fused gather→pull→update pass.  Engines whose pull is a
plain composition use :func:`compose_step`; the BLEST engines build a
chunked step instead (``repro.core.bfs.pull_chunks``: as many fixed
chunks of the queue as the live VSS count needs — the XLA-compatible
stand-in for the paper's dynamically-sized kernel launches).

The same skeleton is mesh-native (DESIGN §2.4): under ``shard_map`` the
``step`` stays purely local (each shard pulls/scatters its row block), the
``finalize`` all-gathers the per-shard frontier words, and the ``active``
predicate is made globally consistent with :func:`global_any` — a ``psum``
convergence test INSIDE the fused ``while_loop``, so the paper's
no-host-sync discipline (§4.3) holds across devices too.  ``run_levels``
is unchanged in either mode: one driver, any mesh shape.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

State = Any  # engine-specific pytree carried through the level loop


@dataclasses.dataclass(frozen=True)
class LevelPipeline:
    """One BFS level = ``step`` (gather → pull → update) then ``finalize``
    (finalise / pack / compact); ``active`` is the on-device continuation
    predicate."""

    step: Callable[[State, jnp.ndarray], State]
    finalize: Callable[[State, jnp.ndarray], State]
    active: Callable[[State], jnp.ndarray]


def compose_step(gather: Callable[[State], tuple],
                 pull: Callable[..., jnp.ndarray],
                 update: Callable[[State, jnp.ndarray, jnp.ndarray], State]
                 ) -> Callable[[State, jnp.ndarray], State]:
    """Fuse the three leading stages into one ``step`` callable."""
    def step(state: State, lvl: jnp.ndarray) -> State:
        return update(state, pull(*gather(state)), lvl)
    return step


def global_any(pred: jnp.ndarray,
               axis: "str | tuple[str, ...] | None") -> jnp.ndarray:
    """Continuation predicate across the mesh: ``pred`` is this shard's
    local "still work to do" bool; the result is True iff ANY shard says so
    (identical on every device, so the shared ``while_loop`` stays in
    lock-step).  ``axis=None`` is the single-device identity; a tuple of
    axis names reduces over all of them (the 2-D row × column mesh)."""
    if axis is None:
        return pred
    return jax.lax.psum(pred.astype(jnp.int32), axis) > 0


def run_levels(pipe: LevelPipeline, state: State, *, max_levels: int
               ) -> tuple[State, jnp.ndarray]:
    """Run the whole level loop on device; returns (final state, n_levels)."""
    def cond(carry):
        st, lvl = carry
        return pipe.active(st) & (lvl < max_levels)

    def body(carry):
        st, lvl = carry
        lvl = lvl + 1
        st = pipe.step(st, lvl)
        st = pipe.finalize(st, lvl)
        return st, lvl

    state, lvl = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    return state, lvl


def run_levels_recorded(pipe: LevelPipeline, state: State, *,
                        max_levels: int, history: State,
                        record: Callable[[State, State, jnp.ndarray], State]
                        ) -> tuple[State, jnp.ndarray, State]:
    """:func:`run_levels` with a per-level *history* channel: before each
    level's ``step``, ``record(hist, state, lvl)`` folds the pre-step state
    into a caller-preallocated buffer pytree (e.g. ``hist.Q.at[lvl].set``).

    This is how a traversal exposes its per-level frontier history to a
    consumer that must replay it — the Brandes backward dependency sweep
    (``repro.analytics.betweenness``) re-walks the recorded per-level VSS
    queues in reverse, so the backward phase touches exactly the tiles the
    forward phase pulled.  Still ONE fused on-device ``while_loop``; the
    history buffer is just extra carry.
    """
    def cond(carry):
        st, lvl, _ = carry
        return pipe.active(st) & (lvl < max_levels)

    def body(carry):
        st, lvl, hist = carry
        lvl = lvl + 1
        hist = record(hist, st, lvl)
        st = pipe.step(st, lvl)
        st = pipe.finalize(st, lvl)
        return st, lvl, hist

    state, lvl, history = jax.lax.while_loop(
        cond, body, (state, jnp.int32(0), history))
    return state, lvl, history
