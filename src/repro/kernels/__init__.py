from .ops import (bvss_pull, bvss_push, bit_spmm, bvss_spmm,
                  bvss_spmm_minplus, bvss_spmm_minplus_local, bvss_spmm_t,
                  bvss_spmm_t_local, bvss_spmm_w, bvss_spmm_w_local,
                  finalize_pack_sweep, finalize_sweep, pull_vss_kernel,
                  push_vss_kernel, resolve_interpret, scatter_min,
                  scatter_min_kernel)
from . import ref

__all__ = ["bvss_pull", "bvss_push", "bit_spmm", "bvss_spmm",
           "bvss_spmm_minplus", "bvss_spmm_minplus_local", "bvss_spmm_t",
           "bvss_spmm_t_local", "bvss_spmm_w", "bvss_spmm_w_local",
           "finalize_sweep", "finalize_pack_sweep", "pull_vss_kernel",
           "push_vss_kernel", "resolve_interpret", "scatter_min",
           "scatter_min_kernel", "ref"]
