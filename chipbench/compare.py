"""
The comparison that decides ``correct`` for a training cell: the timed
trainer's first ``fit`` call against the plain reference that follows the
same call from the same seed (``chipbench/reference/training.py``).

Numbers compared, each against a limit of its own
(``chipbench/limits/<cell>.json``):

- ``loss_e<k>``: epoch k's loss, the worst machine's relative gap
  |program - reference| / reference.
- ``change``: the norm of each leaf's change over the call, by the worst
  leaf of the worst machine: the gap between the program's norm and the
  reference's, over the reference's norm of that leaf or of the machine's
  median leaf, whichever is larger. Leaves whose first gradient in the
  reference is under a thousandth of the machine's median leaf's are left
  out: under Adam they move by round-off alone.

"""

import numpy as np

DEAD_GRADIENT = 1e-3


def training_numbers(program, reference):
    """``program``: losses (M, E), change (M, L). ``reference``: the same
    plus grad1 (M, L). Returns {name: number}."""
    numbers = {}
    ref_losses = reference["losses"]
    for k in range(ref_losses.shape[1]):
        gap = np.abs(program["losses"][:, k] - ref_losses[:, k]) / np.abs(ref_losses[:, k])
        numbers[f"loss_e{k + 1}"] = _worst(gap)
    grad1 = reference["grad1"]
    live = grad1 >= DEAD_GRADIENT * np.median(grad1, axis=1, keepdims=True)
    ref_change = reference["change"]
    scale = np.maximum(ref_change, np.median(ref_change, axis=1, keepdims=True))
    gap = np.abs(program["change"] - ref_change) / scale
    numbers["change"] = _worst(np.where(live, gap, 0.0))
    return numbers


def _worst(gaps):
    """The largest gap; a gap that is not a number is the worst there is."""
    gaps = np.asarray(gaps, dtype=np.float64)
    return float("inf") if not np.isfinite(gaps).all() else float(gaps.max())


def verdict(numbers, limits):
    """(correct, [(name, number, limit)]): every number that the cell's
    limits name has to lie at or under its limit. A number that was read and
    has no limit in the cell is not compared (PERF.md says which, and why)."""
    rows = [(name, numbers[name], float(limit)) for name, limit in limits.items()]
    return all(value <= limit for _, value, limit in rows), rows
