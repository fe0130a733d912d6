"""The comparison that decides ``correct`` for a training cell.

Numbers compared, each against a limit of its own from the cell's file:

- ``loss_r<k>``: |program's mean loss of round k - reference's| as a
  share of the reference's;
- ``first_update_gap``: the server's first pseudo-gradient (global weights
  after round 1 minus the initial ones), by the worst leaf: the gap between
  the program's norm of that leaf and the reference's, over the larger of
  the reference's norm of the leaf and of the median leaf;
- ``change_gap``: the same measure on the weights' change after the last
  checked round. Leaves whose first pseudo-gradient is under a thousandth
  of the median leaf's in the reference are left out of it.

A state that never moved reads 1 on both gaps; a value that is not finite
reads as failed.
"""

from __future__ import annotations

import math

import numpy as np


def worst_gap(prog, ref, skip=()):
    """(gap, leaf) of the worst leaf: |prog - ref| over max(ref of the
    leaf, ref of the median leaf)."""
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for name, r in ref.items():
        if name in skip:
            continue
        p = prog.get(name, float("nan"))
        gap = abs(p - r) / max(r, med, 1e-300)
        if not math.isfinite(gap):
            return float("inf"), name
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def training_checks(prog_losses, prog_norms, ref_losses, ref_norms, limits):
    """``[{"name", "value", "limit", ...}]`` in a fixed order.

    ``prog_norms[k]`` / ``ref_norms[k]``: leaf -> norm of the change of
    the global weights after round k+1."""
    checks = []
    for k, (p, r) in enumerate(zip(prog_losses, ref_losses), start=1):
        checks.append({"name": f"loss_r{k}",
                       "value": abs(p - r) / max(abs(r), 1e-300),
                       "limit": limits[f"loss_r{k}"]})
    med = float(np.median(list(ref_norms[0].values())))
    still = {k for k, v in ref_norms[0].items() if v < 1e-3 * med}
    gap, leaf = worst_gap(prog_norms[0], ref_norms[0])
    checks.append({"name": "first_update_gap", "value": gap, "leaf": leaf,
                   "limit": limits["first_update_gap"]})
    gap, leaf = worst_gap(prog_norms[-1], ref_norms[-1], skip=still)
    checks.append({"name": "change_gap", "value": gap, "leaf": leaf,
                   "limit": limits["change_gap"]})
    for c in checks:
        c["value"] = float(c["value"])
        c["ok"] = bool(math.isfinite(c["value"])
                       and c["value"] <= c["limit"])
    return checks
