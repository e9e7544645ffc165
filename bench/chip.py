"""The chip a run measures: found, or the run exits non-zero.

No fallback: a run that would measure the CPU, the Pallas interpreter or
a device missing from the peaks table stops here and prints no result.
"""
from __future__ import annotations

import json
import os
import sys

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks() -> dict:
    with open(PEAKS_FILE) as f:
        return json.load(f)


def check_devices(devices: list, n_chips: int, table: dict) -> None:
    """Raise SystemExit unless ``devices`` are at least ``n_chips`` TPUs of
    a kind the peaks table knows."""
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {platform!r}); every "
                 f"number of this benchmark comes from the chip")
    if len(devices) < n_chips:
        sys.exit(f"bench: the cell needs {n_chips} chips, JAX found "
                 f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in table:
        sys.exit(f"bench: device kind {kind!r} is not in the peaks table "
                 f"({PEAKS_FILE})")


def require_chip(n_chips: int) -> list:
    """The first ``n_chips`` TPU devices, with kernels compiled for them."""
    if os.environ.get("BLEST_INTERPRET"):
        sys.exit("bench: BLEST_INTERPRET is set; the kernels must run "
                 "compiled on the chip")
    import jax
    devices = jax.devices()
    check_devices(devices, n_chips, peaks())
    from repro.kernels.ops import resolve_interpret
    if resolve_interpret(None):
        sys.exit("bench: the Pallas kernels resolve to interpret mode")
    return devices[:n_chips]
