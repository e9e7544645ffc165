"""Public jit'd entry points for the kernel layer.

Downstream code (BFS engines, multi-source BFS) imports from here so the
kernel/oracle switch is one flag.  On CPU the Pallas bodies execute in
``interpret=True``; on TPU they compile to Mosaic.  Every entry point
exported here compiles for TPU v5e at deployment widths
(``tests/test_tpu_compile.py`` compiles each against a described chip):

* ``bvss_pull`` / ``bvss_push``: lane-major ``(32, T)`` mask blocks (the
  row-major ``rows`` baseline: ``(T, 32)``), 128 VSSs per grid step;
* ``finalize_sweep`` / ``finalize_pack_sweep``: vertex arrays as
  lane-dense ``(rows, 128)`` blocks, the level scalar in SMEM, frontier
  words packed down sublanes after an in-VMEM transpose;
* ``bit_spmm``: K stripes of 128 packed words, one int8 MXU pass per bit
  plane;
* ``bvss_spmm*``: ``(8, 32)`` mask blocks (compiled), slice tiles built
  from shifts and masks, batched MXU contractions (int8, or float32 at
  ``Precision.HIGHEST``), an unrolled elementwise min for min-plus;
* ``scatter_min``: the distance rows held in VMEM as (8, 128) tiles in
  four banks, the (row, value) pairs streamed through SMEM and walked
  with scalar control, one branch-free read-min-write a pair.

:func:`resolve_interpret` is the ONE place that decides interpret-vs-
compiled for every Pallas entry point (DESIGN §2.8) — the per-kernel
``jax.default_backend() == "cpu"`` sniffing that used to be copy-pasted
across ``bvss_pull`` and the four ``mxu_pull`` entry points lives here,
plus a ``BLEST_INTERPRET`` env override so the compiled bench lane can
force either mode uniformly.
"""
from __future__ import annotations

import os

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` flag to a concrete bool.

    Precedence (first match wins):

    1. an explicit ``interpret=True/False`` argument;
    2. the ``BLEST_INTERPRET`` env var — ``"1"`` forces interpret mode,
       ``"0"`` forces compiled Mosaic (read at TRACE time: flip it before
       the first jitted call, not between calls to an already-compiled
       function);
    3. backend sniff: interpret on CPU (no Mosaic backend there),
       compiled elsewhere.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("BLEST_INTERPRET")
    if env is not None and env != "":
        return env != "0"
    return jax.default_backend() == "cpu"


from .bvss_pull import bvss_pull                              # noqa: E402
from .bvss_push import bvss_push                              # noqa: E402
from .mxu_pull import (bit_spmm, bvss_spmm, bvss_spmm_minplus,  # noqa: E402
                       bvss_spmm_minplus_local, bvss_spmm_t,
                       bvss_spmm_t_local, bvss_spmm_w, bvss_spmm_w_local)
from .frontier_finalize import (finalize_pack_sweep,          # noqa: E402
                                finalize_sweep)
from .scatter_min import scatter_min, scatter_min_kernel      # noqa: E402
from . import ref                                             # noqa: E402


def pull_vss_kernel(masks, fbytes, sigma: int = 8):
    """Drop-in replacement for core.bfs.pull_vss_jnp backed by the Pallas
    VPU kernel (lane-major layout)."""
    return bvss_pull(masks, fbytes, sigma=sigma)


def push_vss_kernel(masks, bits, sigma: int = 8):
    """Drop-in replacement for kernels.ref.bvss_push_ref backed by the
    Pallas VPU push kernel (lane-major layout)."""
    return bvss_push(masks, bits, sigma=sigma)


__all__ = ["resolve_interpret", "bvss_pull", "bvss_push", "bit_spmm",
           "bvss_spmm", "bvss_spmm_minplus", "bvss_spmm_minplus_local",
           "bvss_spmm_t", "bvss_spmm_t_local", "bvss_spmm_w",
           "bvss_spmm_w_local", "finalize_sweep", "finalize_pack_sweep",
           "pull_vss_kernel", "push_vss_kernel", "scatter_min",
           "scatter_min_kernel", "ref"]
