"""The numbers that decide ``correct`` for a training cell.

Both sides give, for the first steps of one run from the same weights
and rows: each step's loss, each trainable leaf's norm of the first
gradient as the optimizer gets it, and each leaf's norm of the change
over those steps.  Three numbers are compared, each against its limit:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: over the trainable leaves, the largest gap between the
  two norms of the first gradient, over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
* ``change_gap``: the same for the norms of the change, over every leaf,
  frozen ones included (the reference leaves them where they are).
  A trainable leaf whose reference gradient is under a thousandth of
  the median leaf's moves by round-off alone and is left out.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

NOUGHT = 1e-3   # a gradient under this share of the median leaf's


def _gap(a: float, b: float, den: float) -> float:
    g = abs(a - b) / den
    return g if math.isfinite(g) else math.inf


def _worst(prog: Dict[str, float], ref: Dict[str, float], keys,
           med: float) -> Tuple[float, str]:
    gaps = {k: _gap(prog[k], ref[k], max(ref[k], med)) for k in keys}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def training(prog: Dict, ref: Dict, limits: Dict) -> List[Dict]:
    """``[{"name", "value", "limit", "where"}]`` for the three numbers."""
    if sorted(prog["grad"]) != sorted(ref["grad"]):
        raise ValueError("the two sides train different leaves: "
                         f"{sorted(set(prog['grad']) ^ set(ref['grad']))}")
    gaps = [_gap(a, b, abs(b)) for a, b in zip(prog["loss"], ref["loss"])]
    step = max(range(len(gaps)), key=gaps.__getitem__)
    loss = gaps[step]
    trainable = sorted(ref["grad"])
    g_med = statistics.median(ref["grad"][k] for k in trainable)
    grad, g_leaf = _worst(prog["grad"], ref["grad"], trainable, g_med)
    moving = [k for k in trainable if ref["grad"][k] >= NOUGHT * g_med]
    frozen = sorted(set(ref["change"]) - set(trainable))
    c_med = statistics.median(ref["change"][k] for k in moving)
    change, c_leaf = _worst(prog["change"], ref["change"], moving + frozen,
                            c_med)
    return [
        {"name": "loss_gap", "value": loss, "limit": limits["loss_gap"],
         "where": f"step {step + 1}"},
        {"name": "grad_gap", "value": grad, "limit": limits["grad_gap"],
         "where": g_leaf},
        {"name": "change_gap", "value": change,
         "limit": limits["change_gap"], "where": c_leaf},
    ]


def passed(checks: List[Dict]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)
