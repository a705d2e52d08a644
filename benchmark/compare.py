"""The comparison that decides ``correct``: program against plain reference.

Both sides follow the same first three training calls from the same first
model. A *record* is what one side produced::

    {"quality": array of every epoch's quality, in order,
     "after_1": {leaf: array}, "after_3": {leaf: array}}

with the leaves in original ids (the program's come through its own way
out). Five numbers are read; which of them a configuration holds, and to
what limit, is in its file under ``limits`` (``PERF.md`` gives the readings
each limit was set from). A number with no limit is printed and not held.

All five are gaps measured against the reference's own change of that leaf
(or the median leaf's, whichever is larger: some leaves barely move):

* ``quality_gap``     widest relative gap between the two quality curves;
* ``step1_norm_gap``  gap between the norms of the first call's change
  (what the update rule made of the first gradients), worst leaf;
* ``step3_norm_gap``  the same over all three calls;
* ``step1_diff``      norm of the difference of the two models after the
  first call, worst leaf: the only one that sees a row in the wrong place;
* ``step3_diff``      the same after the third call.
"""

from __future__ import annotations

import statistics
from typing import Dict

import numpy as np

NUMBERS = ("quality_gap", "step1_norm_gap", "step3_norm_gap", "step1_diff",
           "step3_diff")


def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64)))


def _leaf_gaps(first: dict, prog: dict, ref: dict) -> tuple:
    """(worst norm gap, worst difference) over the leaves."""
    leaves = sorted(k for k in ref if not k.startswith("_"))
    missing = [k for k in leaves if k not in prog]
    if missing:
        raise KeyError(f"the program's state lacks the leaves {missing}")
    ref_change = {k: _norm(ref[k] - first[k]) for k in leaves}
    floor = statistics.median(ref_change.values())
    norm_gap = diff = 0.0
    for k in leaves:
        if np.shape(prog[k]) != np.shape(ref[k]):
            return float("inf"), float("inf")
        scale = max(ref_change[k], floor, np.finfo(np.float64).tiny)
        gaps = (abs(_norm(prog[k] - first[k]) - ref_change[k]) / scale,
                _norm(prog[k] - ref[k]) / scale)
        if not all(np.isfinite(gaps)):      # max() would drop a NaN
            return float("inf"), float("inf")
        norm_gap, diff = max(norm_gap, gaps[0]), max(diff, gaps[1])
    return norm_gap, diff


def numbers(first: dict, program: dict, reference: dict) -> Dict[str, float]:
    """The five gaps. A curve of another length, a leaf of another shape or a
    value that is not finite reads ``inf``: it can meet no limit."""
    q_p = np.asarray(program["quality"], np.float64)
    q_r = np.asarray(reference["quality"], np.float64)
    if q_p.shape != q_r.shape or not np.all(np.isfinite(q_p / q_r)):
        quality_gap = float("inf")
    else:
        quality_gap = float(np.max(np.abs(q_p - q_r) / np.abs(q_r)))
    n1, d1 = _leaf_gaps(first, program["after_1"], reference["after_1"])
    n3, d3 = _leaf_gaps(first, program["after_3"], reference["after_3"])
    return {"quality_gap": quality_gap, "step1_norm_gap": n1,
            "step3_norm_gap": n3, "step1_diff": d1, "step3_diff": d3}


def verdict(read: Dict[str, float], limits: Dict[str, float]) -> tuple:
    """``(correct, compared)``: ``compared`` maps each number to
    ``{"value", "limit"}`` (limit ``None`` where it is not held)."""
    compared = {k: {"value": read[k], "limit": limits.get(k)}
                for k in NUMBERS}
    unknown = sorted(set(limits) - set(NUMBERS))
    if unknown:
        raise KeyError(f"limits name numbers that are not read: {unknown}")
    if not limits:
        raise ValueError("the configuration holds no number to a limit")
    ok = all(read[k] <= lim for k, lim in limits.items())
    return ok, compared
