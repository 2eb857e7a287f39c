"""The comparison that decides `correct`: fits against the plain reference.

Each cell holds the numbers that ``bench/limits/<cell>.json`` names, each
under its own limit; a number is the widest over the checked fits of

* ``obj_gap``: the gap between the program's and the reference's objective
  over the recorded ticks, as a share of the reference's value;
* ``w_gap``: the norm of the difference of the final iterates, as a share
  of the norm of the reference's;
* ``obj_gap_first``: the objective's gap at the first recorded tick after
  w = 0. Where that tick is iteration 1, every margin of the issue half is
  exactly 0, so no hinge subgradient can be taken differently by rounding:
  the gap is the program's rounding alone, on every fit. Later ticks and
  the final iterate read, on some fits, a row whose margin lay within
  rounding of the hinge's kink, decided one way by the program and the
  other by the reference, and grown over the remaining iterations.

A history or iterate that is not finite, or of the wrong shape, fails
whatever its gap.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

NAMES = ("obj_gap", "w_gap", "obj_gap_first")


def readings(hist: Sequence[float], w: np.ndarray, ref_hist: Sequence[float],
             ref_w: np.ndarray) -> Dict[str, float]:
    """Every number of one fit."""
    hist, ref_hist = np.asarray(hist, np.float64), np.asarray(ref_hist,
                                                             np.float64)
    w, ref_w = np.asarray(w, np.float64), np.asarray(ref_w, np.float64)
    if (hist.shape != ref_hist.shape or w.shape != ref_w.shape
            or hist.size < 2 or not np.all(np.isfinite(hist))
            or not np.all(np.isfinite(w))):
        return dict.fromkeys(NAMES, float("inf"))
    gaps = np.abs(hist - ref_hist) / np.abs(ref_hist)
    return {"obj_gap": float(np.max(gaps)),
            "w_gap": float(np.linalg.norm(w - ref_w) / np.linalg.norm(ref_w)),
            "obj_gap_first": float(gaps[1])}


def fit_ok(r: Dict[str, float], limits: Dict[str, float]) -> bool:
    """One fit's readings within the cell's limits."""
    return all(r[k] <= limits[k] for k in limits)


def summarize(per_fit: List[Dict[str, float]]) -> Dict[str, float]:
    """The run's numbers: each the widest over the checked fits."""
    return {k: max(r[k] for r in per_fit) for k in NAMES}


def judge(numbers: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """(every number the limits name within its limit,
    {name: {value, limit}})."""
    table = {k: {"value": numbers[k], "limit": float(limits[k])}
             for k in NAMES if k in limits}
    return all(v["value"] <= v["limit"] for v in table.values()), table
