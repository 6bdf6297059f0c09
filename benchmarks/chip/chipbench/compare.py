"""The comparison that decides ``correct``: one horizon of the program
against the plain reference's horizon from the same instance seed.

Numbers (each is worst over the compared horizons):

  plan_rounds_differ  rounds whose plan differs: the devices (as an ordered
                      tuple), the bits each device sent, its rate, or the
                      cumulative simulated time.  Exact: limit 0.  Where
                      the reference follows the program's groups (online
                      policies) the devices agree by construction, and
                      selection is held by ``selection_regret``.
  acc_gap             largest |accuracy difference| over the rounds.
  selection_regret    online policies: the reference trains on the groups
                      the program chose and scores them with its own
                      float64 policy; the largest shortfall of a chosen
                      device below the K-th best score, relative to it.
  param_gap           worst leaf of the final parameters:
                      ||program - reference|| / max(||reference leaf||,
                      ||median leaf||).

A cell's file lists the numbers it holds and their limits.
"""
from __future__ import annotations

import numpy as np


def _round_plan(rec, t):
    rows = zip(tuple(rec["devices"][t]), np.asarray(rec["bits"][t]).tolist(),
               np.asarray(rec["rates"][t], np.float64).tolist())
    return list(rows), float(rec["times"][t])


def plan_rounds_differ(prog, ref):
    rounds = len(ref["devices"])
    if len(prog["devices"]) != rounds:
        return rounds
    return sum(_round_plan(prog, t) != _round_plan(ref, t)
               for t in range(rounds))


def acc_gap(prog, ref):
    a = np.asarray(prog["accs"], np.float64)
    b = np.asarray(ref["accs"], np.float64)
    if a.shape != b.shape or not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b)))


def param_gap(prog, ref):
    if set(prog["params"]) != set(ref["params"]):
        return float("inf")
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
             for k, v in ref["params"].items()}
    floor = float(np.median(list(norms.values())))
    worst = 0.0
    for k, r in ref["params"].items():
        p = np.asarray(prog["params"][k], np.float64)
        if p.shape != r.shape or not np.all(np.isfinite(p)):
            return float("inf")
        gap = np.linalg.norm(p - np.asarray(r, np.float64))
        worst = max(worst, float(gap) / max(norms[k], floor, 1e-30))
    return worst


def numbers(prog, ref):
    return {
        "plan_rounds_differ": float(plan_rounds_differ(prog, ref)),
        "selection_regret": float(ref["regret"]),
        "acc_gap": acc_gap(prog, ref),
        "param_gap": param_gap(prog, ref),
    }


def worst(readings):
    """Largest reading of each number over several comparisons."""
    out = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, v), v)
    return out


def verdict(readings, limits):
    """``(correct, checks)``: each held number with its limit, in the
    cell's order; correct when every one is within its limit."""
    checks = {k: {"value": readings.get(k, float("inf")), "limit": lim}
              for k, lim in limits.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
