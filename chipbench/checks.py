"""The numbers that decide ``correct`` for a training cell.

Both sides give the same readings (see ``reference/train.py``): each
step's loss, the per-weight norm of the first clipped gradient, and the
per-weight norm of the change after the last checked step.

- ``loss_gap``: the largest |loss - reference loss| / |reference loss|
  over the checked steps.
- ``grad_gap``: over weights, the largest |norm - reference norm|,
  divided by the reference's norm of that weight or of the median weight,
  whichever is larger (some gradients are all but zero).
- ``change_gap``: the same for the change. Weights whose reference
  gradient is under a thousandth of the median weight's are left out:
  they have no gradient but round-off (a key's bias under softmax), and
  Adam moves them by its sign alone.

Each number is held to the limit the cell's workload file states.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Mapping

NOUGHT = 1e-3  # share of the median gradient norm below which a weight has none


def _gap(a: float, b: float, base: float) -> float:
    """|a - b| / base, infinite where either side is not a finite number."""
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / base


def _worst(prog: Mapping[str, float], ref: Mapping[str, float],
           keys: List[str]) -> float:
    med = statistics.median(ref[k] for k in keys)
    return max(_gap(prog[k], ref[k], max(ref[k], med, 1e-30)) for k in keys)


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    loss = max(_gap(a, b, abs(b)) for a, b in zip(prog["loss"], ref["loss"]))
    keys = sorted(ref["grad"])
    med = statistics.median(ref["grad"][k] for k in keys)
    moving = [k for k in keys if ref["grad"][k] >= NOUGHT * med]
    return {"loss_gap": loss,
            "grad_gap": _worst(prog["grad"], ref["grad"], keys),
            "change_gap": _worst(prog["change"], ref["change"], moving)}


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True where every number is finite and within its limit."""
    return all(k in nums and nums[k] <= lim for k, lim in limits.items())
