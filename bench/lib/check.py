"""The numbers that decide ``correct``, from the program's output and the
reference's.

Served models: the widest gap by which a served token's logit lies below
the reference's best at that position.  Only greedy tokens can be judged
so.  The control reads, at the same positions, the gap of the token that
its own logits put first.

Training: each step's loss, and per leaf the norms of the first gradient
(as the optimizer gets it) and of the parameters' change, each taken as
the gap between the program's norm and the reference's over the larger
of the reference's norm of that leaf and the median leaf's.
"""

from __future__ import annotations

import numpy as np

#: a leaf whose reference gradient is under this share of the median
#: leaf's moves under Adam by round-off alone; its change is not compared
STILL_LEAF = 1e-3


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """ref_logits [n, V], tokens [n]: best logit minus the token's."""
    ref_logits = np.asarray(ref_logits, np.float64)
    picked = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return ref_logits.max(axis=-1) - picked


def control_gaps(ref_logits: np.ndarray, ctl_logits: np.ndarray
                 ) -> np.ndarray:
    return token_gaps(ref_logits, np.argmax(ctl_logits, axis=-1))


def _floor(ref: dict, names) -> float:
    return float(np.median([ref[k] for k in names]))


def leaf_gap(prog: dict, ref: dict, names=None) -> tuple[float, str]:
    """Worst leaf of |prog - ref| / max(ref, median ref) over ``names``
    (all leaves when None); returns the gap and the leaf."""
    names = sorted(ref) if names is None else sorted(names)
    floor = _floor(ref, names)
    worst, where = -1.0, ""
    for k in names:
        g = abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
        if g > worst:
            worst, where = g, k
    return worst, where


def moving_leaves(ref_grad: dict) -> list[str]:
    """Leaves whose reference gradient is at least ``STILL_LEAF`` of the
    median leaf's."""
    floor = _floor(ref_grad, ref_grad)
    return sorted(k for k, v in ref_grad.items() if v >= STILL_LEAF * floor)


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold ``losses``, ``grad_norms`` and
    ``change_norms``.  ``loss1_gap`` is the first step's loss gap (before
    any update), ``loss_gap`` the widest over the steps."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise KeyError("program and reference leaves differ: "
                       f"{sorted(set(prog['grad_norms']) ^ set(ref['grad_norms']))}")
    gaps = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    if not gaps or len(prog["losses"]) != len(ref["losses"]):
        gaps = [float("inf")]
    grad_gap, grad_leaf = leaf_gap(prog["grad_norms"], ref["grad_norms"])
    moving = moving_leaves(ref["grad_norms"])
    change_gap, change_leaf = leaf_gap(prog["change_norms"],
                                       ref["change_norms"], moving)
    return {"loss1_gap": gaps[0], "loss_gap": max(gaps), "grad_gap": grad_gap,
            "change_gap": change_gap, "grad_leaf": grad_leaf,
            "change_leaf": change_leaf,
            "still_leaves": sorted(set(ref["grad_norms"]) - set(moving))}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number that has a limit must be finite and at most its
    limit.  Returns (correct, {name: {"value", "limit"}})."""
    out, ok = {}, True
    for name, limit in limits.items():
        if name not in numbers:
            continue
        v = float(numbers[name])
        out[name] = {"value": v, "limit": limit}
        ok = ok and bool(np.isfinite(v)) and v <= limit
    if not out:
        ok = False
    return ok, out
