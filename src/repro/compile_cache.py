"""JAX's persistent compilation cache for the repo's entry points.

Every program entry point (``chip_smoke.py``, the ``repro.launch.bfs`` and
``repro.launch.analytics`` launchers, the chip benchmark ``bench/run.py``)
calls :func:`use_compile_cache` before its first compile, so repeat runs
on the same machine skip recompiling the engines.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself; the
  directory is left to it and no other is set in code.
* Otherwise the cache lives at :data:`DEFAULT_DIR`, a fixed path inside
  the checkout (listed in ``.gitignore``).  It is never derived from a
  temp name, a process id or the time: the next run must find the same
  directory to hit.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
