"""The comparison that decides ``correct`` for a training cell.

Both sides give, for the first steps from the seed: each step's loss, the
norm of the first gradient per leaf and the norm of the parameters' change
per leaf. Three numbers are compared, each against a limit of its own that
the configuration's file states:

- ``loss_gap``: the widest |program - reference| / |reference| over the steps;
- ``grad_gap``, ``change_gap``: by the worst leaf, the gap between the
  program's norm and the reference's (not the norm of a difference), measured
  against the reference's norm of that leaf or of the median leaf, whichever
  is larger. Leaves whose reference gradient is under a thousandth of the
  median leaf's move by round-off alone and are left out of ``change_gap``.

``grad_gap_median`` and ``change_gap_median`` are the same gaps by the median
leaf: steadier from seed to seed where a trajectory amplifies round-off. A
number decides ``correct`` only where the configuration gives it a limit.
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "change_gap",
           "grad_gap_median", "change_gap_median")
DEAD_GRADIENT = 1e-3


def leaf_gaps(prog: dict, ref: dict, leaves=None):
    """((worst gap, its leaf), (median gap, "median leaf")). A leaf missing
    on either side, or a non-finite norm, reads infinity."""
    if set(prog) != set(ref):
        bad = (math.inf, "leaf sets differ")
        return bad, bad
    leaves = sorted(ref) if leaves is None else leaves
    median = statistics.median(ref[k] for k in leaves)
    per = {k: abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
           for k in leaves}
    per = {k: g if math.isfinite(g) else math.inf for k, g in per.items()}
    where = max(per, key=per.get)
    return ((per[where], where),
            (statistics.median(per.values()), "median leaf"))


def gaps(prog: dict, ref: dict) -> dict:
    """name -> (value, detail) for each of NUMBERS."""
    if len(prog["loss"]) != len(ref["loss"]):
        loss = (math.inf, "step counts differ")
    else:
        per = [abs(p - r) / abs(r) if math.isfinite(p) else math.inf
               for p, r in zip(prog["loss"], ref["loss"])]
        loss = (max(per), f"step {per.index(max(per)) + 1}")
    median_grad = statistics.median(ref["grad1"].values())
    moved = [k for k in sorted(ref["change"])
             if ref["grad1"][k] >= DEAD_GRADIENT * median_grad]
    grad, grad_median = leaf_gaps(prog["grad1"], ref["grad1"])
    change, change_median = leaf_gaps(prog["change"], ref["change"], moved)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "grad_gap_median": grad_median, "change_gap_median": change_median}


def decide(prog: dict, ref: dict, limits: dict):
    """(correct, compared) where compared is name -> {value, limit, at}."""
    found = gaps(prog, ref)
    compared = {name: {"value": found[name][0], "limit": limit,
                       "at": found[name][1]}
                for name, limit in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return correct, compared
