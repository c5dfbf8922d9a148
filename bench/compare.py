"""The comparison that decides ``correct`` for a training cell.

Four numbers, from the program's first steps and the reference's:

- ``loss_gap``: the relative gap between the program's and the
  reference's loss at the first step, where both start from the same
  weights (``loss_gap_all_steps``, the largest gap over all compared
  steps, is reported beside it but not compared: after the first update
  the two differ in every parameter whose gradient is near nought, which
  Adam moves by the full step either way, and that noise grows with the
  steps);
- ``grad_norm_gap``: for the first step's gradient as the optimizer got
  it, the worst leaf's gap between the program's norm and the
  reference's, over the larger of the reference's norm of that leaf and
  of the median leaf;
- ``update_norm_gap``: the same for the parameters' change over the
  compared steps;
- ``ema_norm_gap``: the same for the change of the parameters' moving
  average (EMA) over the compared steps, where the traffic keeps one.

Leaves whose reference gradient is under ``NEGLIGIBLE`` of the median
leaf's move under Adam by round-off alone; they are left out of the leaf
numbers. The EMA moves by a thousandth of the parameters' change, which
in the first steps is a few units in the last place of a float32 leaf
near 1 (LayerNorm scales, open gate biases): float32 rounding, on either
side, is as large as that change. A leaf whose reference EMA change is
under ``EMA_RESOLVABLE`` float32 epsilons of the leaf's own norm is left
out of ``ema_norm_gap``. A non-finite reading fails.
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3
EMA_RESOLVABLE = 8.0
NAMES = ("loss_gap", "grad_norm_gap", "update_norm_gap", "ema_norm_gap")


def _worst_leaf(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        return math.inf
    if not np.all(np.isfinite(prog)):
        return math.inf
    med = float(np.median(ref[keep]))
    gap = np.abs(prog - ref) / np.maximum(ref, med)
    return float(np.max(gap[keep]))


def readings(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``loss`` (per step), ``grad``, ``update``
    and, where there is an EMA, ``ema`` (per leaf, in the same leaf
    order); ``ref`` also holds ``weights``, the norm of each leaf."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    gaps = (np.abs(lp - lr) / np.abs(lr)
            if lp.shape == lr.shape and np.all(np.isfinite(lp))
            else np.full(lr.shape, math.inf))
    g_ref = np.asarray(ref["grad"], np.float64)
    keep = g_ref >= NEGLIGIBLE * np.median(g_ref)
    out = {"loss_gap": float(gaps[0]),
           "loss_gap_all_steps": float(np.max(gaps)),
           "grad_norm_gap": _worst_leaf(prog["grad"], ref["grad"], keep),
           "update_norm_gap": _worst_leaf(prog["update"], ref["update"],
                                          keep),
           "leaves_compared": int(keep.sum()),
           "left_out": [int(i) for i in np.flatnonzero(~keep)]}
    if "ema" in ref:
        e_ref = np.asarray(ref["ema"], np.float64)
        keep_e = keep & (e_ref >= EMA_RESOLVABLE * np.finfo(np.float32).eps
                         * np.asarray(ref["weights"], np.float64))
        out["ema_norm_gap"] = (_worst_leaf(prog["ema"], e_ref, keep_e)
                               if "ema" in prog else math.inf)
        out["ema_leaves_compared"] = int(keep_e.sum())
    return out


def checks(read: dict, limits: dict) -> list:
    """``[{name, value, limit}]`` in a fixed order, for the numbers read."""
    return [{"name": n, "value": read[n], "limit": limits[n]}
            for n in NAMES if n in read]


def passed(chk: list) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in chk)
