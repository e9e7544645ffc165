"""Whether the timed path answered right: the seam between the harness and
the answer checks.

Every answer due in the window is compared with a plain reference once
the window has closed.  How depends on what the answers are, so the
comparison is a plug-in named by the traffic mix's ``"answers"`` key,
``bench/answers/<name>.py`` (``"levels"`` when the key is absent).  Each
plug-in exposes:

* ``check(graph, answers, refs) -> Verdict``: every answer against the
  reference from its root; ``refs`` keeps the reference's answers between
  calls, so the control's check reuses them;
* ``control_answers(graph, answers, refs)``: the recorded answers with the
  control's in place of the program's (same roots, due and done times);
  the same ``check`` has to read them as not correct;
* ``CONTROL``: one line that says what the control is, logged by
  ``bench/control.py``.

``graph`` is a :class:`CsrGraph`: the generated CSR and its per-arc
weights, ``None`` for a generator that draws none.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# what every verdict counts, each with its limit
LIMITS = {"wrong_answers": 0, "missing_answers": 0}


@dataclasses.dataclass(frozen=True)
class CsrGraph:
    n: int
    indptr: np.ndarray
    indices: np.ndarray
    weights: np.ndarray | None = None     # float32, one per arc


@dataclasses.dataclass
class Verdict:
    checked: int = 0
    wrong_answers: int = 0
    missing_answers: int = 0
    levels_run: int = 0        # sum over answers of (depth + 1), or 0
    edges: int = 0             # undirected edges reached, summed
    # a plug-in's own readings: name -> (value, limit)
    extra: dict[str, tuple[float, float]] = dataclasses.field(
        default_factory=dict)

    @property
    def correct(self) -> bool:
        return self.checked > 0 and all(
            r["value"] <= r["limit"] for r in self.readings().values())

    def readings(self) -> dict[str, dict]:
        out = {k: {"value": getattr(self, k), "limit": v}
               for k, v in LIMITS.items()}
        out.update({k: {"value": v, "limit": lim}
                    for k, (v, lim) in self.extra.items()})
        return out
