"""The comparison that decides ``correct``: the program's readings of its
first rounds against the plain reference's.

Three numbers, each against its limit from ``bench/limits/<cell>.json``:

* ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the rounds;
* ``update_gap``: over the leaves, the largest gap between the norms of the
  clients' mean update in round 1, as a share of the reference's norm of
  that leaf or of the median leaf, whichever is larger;
* ``change_gap``: the relative gap between the norms of the change of the
  params after the last round, over the whole tree of the leaves whose
  reference update is at least a thousandth of the median leaf's (a leaf
  whose gradient is nought to rounding, such as a key bias under softmax,
  moves by round-off alone).  Not by the worst leaf: under STC the change is
  sparse, most leaves get no or a few kept values in three rounds (the
  median leaf's change is 0), and one value at the threshold that bfloat16
  and float32 rounding put on either side moves a leaf from 0 to one step
  mu; the whole tree's norm is steady from seed to seed.

A reading that is not finite fails.
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["NUMBERS", "gaps", "judge", "worst_leaves"]

NUMBERS = ("loss_gap", "update_gap", "change_gap")
MOVED = 1e-3        # share of the median leaf's update below which a leaf
                    # counts as not moved by the reference


def _leaf_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(want, np.median(want))
    with np.errstate(divide="ignore", invalid="ignore"):
        share = np.where(got == want, 0.0, np.abs(got - want) / scale)
    return float(np.max(share)) if share.size else 0.0


def gaps(prog: dict, ref: dict) -> dict:
    """The three numbers from two sets of readings."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    upd = np.asarray(ref["update_norms"])
    moved = upd >= MOVED * np.median(upd)
    got = np.asarray(prog["change_norms"], np.float64)[moved]
    want = np.asarray(ref["change_norms"], np.float64)[moved]
    total = np.sqrt(np.sum(want ** 2))
    return {"loss_gap": loss_gap,
            "update_gap": _leaf_gap(prog["update_norms"], upd),
            "change_gap": float(abs(np.sqrt(np.sum(got ** 2)) - total)
                                / total)}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``."""
    out, ok = {}, True
    for name in NUMBERS:
        v, lim = numbers[name], limits[name]
        out[name] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, out


def worst_leaves(prog: dict, ref: dict, names, n: int = 4) -> dict:
    """The ``n`` leaves with the largest gaps, ``[name, program, reference]``
    per reading, for the log."""
    out = {}
    for key in ("update_norms", "change_norms"):
        got, want = np.asarray(prog[key]), np.asarray(ref[key])
        order = np.argsort(-np.abs(got - want))[:n]
        out[key] = [[names[i], float(got[i]), float(want[i])] for i in order]
    return out
