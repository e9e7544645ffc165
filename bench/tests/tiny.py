"""A copy of the benchmark at a size a test run holds."""
from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {"kron_g500": {"scale": 8}, "rgg_dimacs10": {"k": 9}}


def tiny_root(dest: str) -> str:
    """``BENCHMARK.json`` and ``bench/`` copied under ``dest``, with each
    configuration cut to a few hundred vertices."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(ROOT, "bench"),
                    os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, sizes in TINY.items():
        path = os.path.join(dest, "bench", "configs", name + ".json")
        with open(path) as f:
            data = json.load(f)
        data.update(sizes)
        with open(path, "w") as f:
            json.dump(data, f, indent=1)
    return dest
