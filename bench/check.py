"""The comparison that decides ``correct``.

Three numbers, each against its cell's limit (``bench/limits/<cell>.json``,
which also records the readings the limit was set from):

- ``loss_gap``: the largest relative gap between the program's loss and
  the reference's, over the checked steps;
- ``grad_gap``: the first step's gradient as the optimizer gets it, per
  entry (one per layer and tensor): the gap between the program's norm
  and the reference's, over the larger of the reference's norm and the
  median entry's; the worst entry;
- ``change_gap``: the same for the norm of the weights' change over the
  checked steps, leaving out the entries whose reference gradient is
  under a thousandth of the median entry's (they move by round-off
  alone, as a key bias does under softmax).
"""
from __future__ import annotations

import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap")
FLAT_GRAD = 1e-3


def _worst(got: dict, want: dict, names) -> tuple[float, str]:
    med = float(np.median([want[k] for k in names]))
    worst, at = 0.0, ""
    for k in names:
        g = abs(got.get(k, 0.0) - want[k]) / max(want[k], med, 1e-30)
        if not np.isfinite(g):
            g = float("inf")
        if g >= worst:
            worst, at = g, k
    return worst, at


def gaps(prog: dict, ref: dict) -> dict:
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    loss = np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-30)
    loss_gap = float(np.max(loss)) if np.all(np.isfinite(loss)) \
        else float("inf")
    names = sorted(ref["grad"])
    grad_gap, g_at = _worst(prog["grad"], ref["grad"], names)
    med = float(np.median([ref["grad"][k] for k in names]))
    moved = [k for k in names if ref["grad"][k] >= FLAT_GRAD * med]
    change_gap, c_at = _worst(prog["change"], ref["change"], moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap, "grad_worst": g_at,
            "change_worst": c_at, "left_out": sorted(set(names) - set(moved))}


def judge(g: dict, limits: dict | None) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); no limits → not correct."""
    checks = {}
    ok = limits is not None
    for n in NAMES:
        lim = None if limits is None else limits[n]["limit"]
        checks[n] = {"value": g[n], "limit": lim}
        ok = ok and lim is not None and g[n] <= lim
    return ok, checks
