"""The comparison that decides ``correct`` for training cells.

Program and reference each give, for the first steps: the loss of each step,
the norm per leaf of the first gradient as the optimizer gets it, and the
norm per leaf of the parameters' change after the last step.  Gaps are taken
by the worst leaf: the distance between the two norms (not the norm of a
difference), against the reference's norm of that leaf or of the median
leaf, whichever is larger.  Leaves whose reference gradient is under a
thousandth of the median leaf's are left out of the change: they move under
Adam by round-off alone.
"""

from __future__ import annotations

import statistics

import jax


def flat(tree) -> dict:
    """``{"a/b/c": leaf}`` of a pytree of dicts (or of flax/optax nodes)."""
    return {"/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> tuple[float, str]:
    names = sorted(ref if leaves is None else leaves)
    med = statistics.median(ref[n] for n in names)
    worst, at = 0.0, ""
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if gap > worst:
            worst, at = gap, n
    return worst, at


def training_gaps(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses": [...], "grad_norms": {leaf: norm},
    "change_norms": {leaf: norm}} -> the numbers compared, with the leaf each
    worst gap sits at."""
    n = min(len(prog["losses"]), len(ref["losses"]))
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"][:n], ref["losses"][:n]))
    med_g = statistics.median(ref["grad_norms"].values())
    moving = [k for k, v in ref["grad_norms"].items() if v >= 1e-3 * med_g]
    grad_gap, grad_at = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    change_gap, change_at = worst_leaf_gap(prog["change_norms"], ref["change_norms"], moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "change_gap": change_gap,
            "_at": {"grad_gap": grad_at, "change_gap": change_at,
                    "left_out_of_change": sorted(set(ref["grad_norms"]) - set(moving))}}


def judge(gaps: dict, limits: dict) -> tuple[bool, dict]:
    """Every number that has a limit is held to it; ``compared`` lists each
    beside its limit."""
    compared = {k: {"value": gaps[k], "limit": limits[k]} for k in limits}
    ok = all(v["value"] <= v["limit"] and v["value"] == v["value"] for v in compared.values())
    return ok, compared
