"""The comparison that decides ``correct``.

Two layers are compared with the plain reference under
``bench/reference``:

* the served batch: sampled rows of batches the window served, and every
  row of the three set-up batches, against the reference's decode and
  augmentation of the same sample ids (``row_gap``, the largest absolute
  difference of any element; ``label_errors``, labels that differ);
* the train step: the reference follows the same three set-up steps
  from the same seed.  ``loss_gap`` is the largest relative gap of a
  step's loss; ``grad_leaf_gap`` and ``change_leaf_gap`` take, leaf by
  leaf, the gap between the program's and the reference's norm of the
  first (clipped) gradient and of the change over the three steps,
  against the larger of the reference leaf's norm and the median leaf's.
  Leaves whose first reference gradient is under a thousandth of the
  median leaf's are left out of both.

Every number has its limit in the configuration or traffic file; a
number over its limit, or one that cannot be read, makes the run
incorrect.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             grad_raw: Dict[str, float]) -> Tuple[float, str]:
    """(worst gap, its leaf) over the leaves the gradient rule keeps."""
    med_g = float(np.median(list(grad_raw.values())))
    kept = [k for k in ref if grad_raw[k] >= 1e-3 * med_g]
    med = float(np.median([ref[k] for k in kept]))
    worst, where = 0.0, ""
    for k in kept:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not gap <= worst:            # NaN counts as the worst
            worst, where = gap, k
    return worst, where


def loss_gap(prog: List[float], ref: List[float]) -> float:
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog, ref)]
    if len(prog) != len(ref) or not all(np.isfinite(gaps)):
        return float("inf")
    return max(gaps)


def step_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    grad, _ = leaf_gap(prog["grad"], ref["grad"], ref["grad_raw"])
    change, _ = leaf_gap(prog["change"], ref["change"], ref["grad_raw"])
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_leaf_gap": grad, "change_leaf_gap": change}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    table = {k: {"value": numbers.get(k, float("nan")), "limit": lim}
             for k, lim in limits.items()}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    return ok, table
