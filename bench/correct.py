"""The comparison that decides ``correct``.

The timed path's own logits, for a sample of the images it served in
the window (drawn from the seed), against the plain reference's logits
for the same images and weights. The number compared:

  logit_rel_err  the worst image's ||logits - reference|| / ||reference||
                 (2-norms over its classes)

Its limit is the configuration's ``limits.logit_rel_err``, set from the
program's readings over many seeds and the control's (the reference one
precision step down), as PERF.md records.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def logit_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    num = np.linalg.norm(got - want, axis=-1)
    den = np.maximum(np.linalg.norm(want, axis=-1), np.finfo(np.float32).tiny)
    return float((num / den).max())


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and, per number, its value beside its limit. A number
    must be at most its limit; one with no limit fails."""
    out = {k: {"value": v, "limit": limits.get(k, float("nan"))}
           for k, v in numbers.items()}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out


def lines(compared: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"[bench] compared {k} = {v['value']!r} (limit {v['limit']!r})"
            for k, v in compared.items()]
