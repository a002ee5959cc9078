"""The comparison that decides `correct` for a training cell.

Both sides give each of the first three steps' loss, every leaf's norm
of the first gradient, and every leaf's norm of the parameters' change
after the three steps. A number compared is a gap:

- `loss_gap`: the largest |program - reference| / |reference| over the
  three losses;
- `grad_norm_gap`, `change_norm_gap`: by the worst leaf, the gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf.

Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of both leaf gaps.
"""

from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "grad_norm_gap", "change_norm_gap")
NEGLIGIBLE = 1e-3


def gaps(program: dict, reference: dict) -> dict:
    ref_g = np.asarray(reference["grad_norms"], np.float64)
    keep = ref_g >= NEGLIGIBLE * np.median(ref_g)

    def worst_leaf(key):
        p = np.asarray(program[key], np.float64)[keep]
        r = np.asarray(reference[key], np.float64)[keep]
        denom = np.maximum(r, np.median(r))
        return float(np.max(np.abs(p - r) / denom))

    lp = np.asarray(program["losses"], np.float64)
    lr = np.asarray(reference["losses"], np.float64)
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_norm_gap": worst_leaf("grad_norms"),
            "change_norm_gap": worst_leaf("change_norms"),
            "leaves_left_out": int(np.sum(~keep))}


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value": gap, "limit": limit}}). A gap that is not
    finite fails."""
    checks = {n: {"value": found[n], "limit": limits[n]} for n in NAMES}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
