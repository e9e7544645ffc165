"""Time the SSSP scatter-min on the chip at the Kernel 3 cell's shapes.

One chunk of the relaxation scatters 512 VSSs × 128 rows (65536 pairs)
into a (1, 524288) float32 distance row.  This times, per chunk, inside
one jitted loop of ``--chunks`` chunks:

* ``xla``: ``d.at[:, rows].min(y)``, the engine's scatter before the
  kernel;
* ``xla_drop``: the same with the ``+inf`` pairs' rows sent past the end
  (``mode="drop"``);
* ``kernel[<block>,<unroll>,<banks>]``: the ``scatter_min`` kernel at
  the module's ``BLOCK``, ``UNROLL`` and ``BANKS``, and with each one
  varied alone (the sweep that sets the three constants);
* the kernel with no finite pair and with every pair finite: it takes
  one path for every pair, so the two should cost the same.

Each variant's distances are compared bitwise with the ``xla`` one; a
variant that fails to compile or run records its error instead.  Prints
one JSON line, also written to ``chiprun_out/scatter_min_chip.json``.
Exits non-zero without a TPU (the kernel is never timed in interpret
mode).
Run on a TPU: ``python benchmarks/scatter_min_chip.py [--finite 0.36]``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.kernels.scatter_min import (BANKS, BLOCK,  # noqa: E402
                                       UNROLL, _scatter_min)


def _loop(update, chunks: int):
    @jax.jit
    def run(d, rows, vals):
        k = rows.shape[0]

        def body(i, d):
            return update(d, rows[i % k], vals[i % k])
        return jax.lax.fori_loop(0, chunks, body, d)
    return run


def _time(fn, *args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 19)
    ap.add_argument("--pairs", type=int, default=512 * 128)
    ap.add_argument("--finite", type=float, default=0.36)
    ap.add_argument("--chunks", type=int, default=64)
    ap.add_argument("--sets", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--blocks", default="2048,8192,32768")
    ap.add_argument("--unrolls", default="8,16,64")
    ap.add_argument("--banks", default="1,2,6")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"scatter_min_chip: needs a TPU, found "
                 f"{jax.default_backend()!r}")
    R, P = a.rows, a.pairs
    rng = np.random.default_rng(7)
    d0 = jnp.asarray(rng.random((1, R), dtype=np.float32))
    rows = jnp.asarray(rng.integers(0, R, (a.sets, P), dtype=np.int32))
    fin = rng.random((a.sets, 1, P)) < a.finite
    vals = np.where(fin, 2 * rng.random((a.sets, 1, P), dtype=np.float32),
                    np.float32(np.inf)).astype(np.float32)

    def xla(d, r, v):
        return d.at[:, r].min(v)

    def xla_drop(d, r, v):
        return d.at[:, jnp.where(v[0] < jnp.inf, r, R)].min(v, mode="drop")

    def kernel(block, unroll, banks):
        return lambda d, r, v: _scatter_min(d, r, v, P, block=block,
                                            unroll=unroll, banks=banks,
                                            interpret=False)

    blocks = [int(b) for b in a.blocks.split(",")]
    unrolls = [int(u) for u in a.unrolls.split(",")]
    banks = [int(k) for k in a.banks.split(",")]
    inf = np.full_like(vals, np.inf)
    allfin = np.where(fin, vals, 2 * rng.random(vals.shape,
                                                dtype=np.float32))
    # name -> (update, values, which values)
    variants = {"xla": (xla, vals, "mix"), "xla_drop": (xla_drop, vals, "mix")}
    shipped = (BLOCK, UNROLL, BANKS)
    sweep = ([shipped] + [(b, UNROLL, BANKS) for b in blocks]
             + [(BLOCK, u, BANKS) for u in unrolls]
             + [(BLOCK, UNROLL, k) for k in banks])
    for c in dict.fromkeys(sweep):
        variants["kernel[{},{},{}]".format(*c)] = (kernel(*c), vals, "mix")
    variants["kernel_no_finite"] = (kernel(*shipped), inf, "inf")
    variants["kernel_all_finite"] = (kernel(*shipped), allfin, "finite")
    out = {"device": str(jax.devices()[0].device_kind), "rows": R,
           "pairs": P, "finite": a.finite, "chunks": a.chunks, "us": {},
           "equal": {}, "error": {}}
    ref = {}
    for name, (upd, v, kind) in variants.items():
        fn = _loop(upd, a.chunks)
        v = jnp.asarray(v)
        try:
            us = 1e6 * _time(fn, d0, rows, v, reps=a.reps) / a.chunks
            if kind not in ref:
                ref[kind] = np.asarray(_loop(xla, a.chunks)(d0, rows, v))
            same = np.array_equal(np.asarray(fn(d0, rows, v)), ref[kind])
        except Exception as e:  # noqa: BLE001 - one variant, recorded
            out["error"][name] = f"{type(e).__name__}: {e}"[:300]
            continue
        out["us"][name] = us
        out["equal"][name] = bool(same)
        print(name, us, bool(same), flush=True)
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "scatter_min_chip.json"), "w") as f:
        f.write(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
