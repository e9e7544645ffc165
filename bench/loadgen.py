"""The one traffic generator: the roots a run queries, from a seed."""
from __future__ import annotations

import numpy as np


def roster(rng: np.random.Generator, degrees: np.ndarray, size: int,
           min_degree: int) -> np.ndarray:
    """``size`` distinct vertices of degree >= ``min_degree``, seeded."""
    live = np.flatnonzero(degrees >= min_degree)
    if len(live) < size:
        raise ValueError(f"only {len(live)} vertices have degree >= "
                         f"{min_degree}; the roster needs {size}")
    return rng.choice(live, size=size, replace=False).astype(np.int64)
