"""The comparison that decides ``correct`` for a training cell.

What the timed path produced over its first steps (each step's loss, the
norm by leaf of the first gradient as the optimizer got it, the norm by
leaf of the parameters' change) against what the plain reference gives
from the same weights and rows.  Norms are compared by the worst leaf: the
gap between the two norms, not the norm of a difference, measured against
the reference's norm of that leaf or of the median leaf, whichever is
larger, since some gradients are all but zero.
"""

from __future__ import annotations

import statistics

#: a leaf whose reference gradient is under this share of the median
#: leaf's moves by round-off alone under Adam; it is left out of the
#: parameters' change (by this rule, not by name)
DEAD_GRADIENT = 1e-3


def worst_leaf_gap(got, ref, skip=()):
    """(gap, leaf) of the leaf with the widest gap between norms."""
    floor = statistics.median(ref.values())
    worst, at = 0.0, None
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        gap = abs(got[leaf] - r) / max(r, floor, 1e-30)
        if gap > worst or at is None:
            worst, at = gap, leaf
    return worst, at


def dead_leaves(ref_grad_norms):
    floor = DEAD_GRADIENT * statistics.median(ref_grad_norms.values())
    return {leaf for leaf, n in ref_grad_norms.items() if n < floor}


def compare_training(got, ref):
    """{name: (value, detail)} of every number compared.  ``got`` and
    ``ref`` hold ``losses``, ``grad_norms`` and ``change_norms``."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"]), start=1):
        out[f"loss_gap_step{i}"] = (abs(a - b) / abs(b),
                                    f"program {a!r} reference {b!r}")
    missing = set(ref["grad_norms"]) ^ set(got["grad_norms"])
    if missing:
        raise ValueError(f"leaves differ between program and reference: "
                         f"{sorted(missing)}")
    gap, leaf = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    out["grad_norm_gap"] = (gap, f"worst leaf {leaf}")
    dead = dead_leaves(ref["grad_norms"])
    gap, leaf = worst_leaf_gap(got["change_norms"], ref["change_norms"],
                               skip=dead)
    out["change_norm_gap"] = (
        gap, f"worst leaf {leaf}; left out for a dead gradient: "
             f"{sorted(dead) or 'none'}")
    return out


def verdict(numbers, limits):
    """({name: {"value", "limit"}}, correct).  Only a number that has a
    limit is judged; the others are printed with a null limit."""
    compared, correct = {}, True
    for name, value in numbers.items():
        value = value[0] if isinstance(value, tuple) else value
        limit = limits.get(name)
        compared[name] = {"value": value, "limit": limit}
        if limit is not None and not value <= limit:
            correct = False
    for name in limits:
        if name not in numbers:
            compared[name] = {"value": None, "limit": limits[name]}
            correct = False
    return compared, correct
