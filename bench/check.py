"""The comparison that decides ``correct``: the program's readings of
the first three steps against the reference's.

Three numbers; those that the cell's workload file gives a limit are
compared, and all three are printed:

* ``loss_gap``: the largest ``|loss - ref| / |ref|`` over the three
  steps;
* ``first_loss_gap``: the same for the first step alone, which no
  update has yet touched: where the later steps amplify the update's
  rounding, as in a configuration whose losses are in the hundreds, it
  is the steady one of the two;
* ``delta_gap``: over the leaves, the largest gap between the norm of
  the program's change of the parameters over the three steps and the
  reference's, over the larger of the reference's norm of that leaf and
  of the median leaf, leaving out leaves whose first reference gradient
  is under a thousandth of the median leaf's (they move by round-off
  alone).
"""

from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "first_loss_gap", "delta_gap")
#: a leaf whose reference gradient norm is under this share of the median
#: leaf's moves by round-off alone and is left out of ``delta_gap``
STILL_LEAF = 1e-3


def _worst_leaf(got: dict, want: dict, keep) -> float:
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))[:4]}")
    scale = float(np.median(list(want.values())))
    gaps = [abs(got[k] - want[k]) / max(want[k], scale) for k in keep]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def gaps(got: dict, want: dict) -> dict:
    """The three numbers, from the program's readings and the reference's
    (:meth:`reference.Reference.readings`)."""
    loss = [abs(a - b) / abs(b) for a, b in zip(got["losses"], want["losses"])]
    g_med = float(np.median(list(want["grad"].values())))
    moving = [k for k, v in want["grad"].items() if v >= STILL_LEAF * g_med]
    return {
        "loss_gap": max(loss) if all(map(math.isfinite, loss)) else math.inf,
        "first_loss_gap": loss[0] if math.isfinite(loss[0]) else math.inf,
        "delta_gap": _worst_leaf(got["delta"], want["delta"], moving),
    }


def judge(numbers: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}``, one entry per limited number."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
