"""The comparison that decides ``correct`` for a training cell.

Both sides report, for the job's first steps from the seed's weights, the
loss of each step up to ``r + 2``, where ``r`` is the first step after a
plan refresh (step 10: the first that runs a sampled backward SpMM under a
plan drawn from the gradients), the gradients of steps 0 and ``r``, and the
weights before step 0 and after ``UPDATE_STEPS``. Six numbers compare
them with the reference (``ref``):

* ``first_loss_gap``: step 0's ``|loss - loss_ref| / |loss_ref|``, the
  forward pass from the seed's weights alone. Adam's first steps move
  every weight by about the learning rate whatever its gradient's size,
  so gaps of rounding grow over steps 1-2 and read wider from seed to seed
  than step 0's;
* ``loss_gap``: the worst of steps 0-2 by ``|loss - loss_ref| / |loss_ref|``;
* ``refresh_loss_gap``: the same over steps ``r`` to ``r + 2``;
* ``grad_gap``: the worst leaf's gap between the norms of the step-0
  gradient, ``| |g| - |g_ref| |``, over the larger of that leaf's reference
  norm and the median leaf's;
* ``refresh_grad_gap``: the same for the step-``r`` gradient;
* ``update_gap``: the same for the weights' change over the first
  ``UPDATE_STEPS`` steps, over the leaves that the reference's step-0
  gradient moves. A leaf whose reference gradient is under a thousandth of
  the median leaf's (a bias in front of a batch norm) moves under Adam by
  round-off alone and is left out.

A cell's limits live in ``limits/<workload>.json``; a number that file
does not name is read and printed but not compared (a number for which
neither the control nor a fault gave an upper reading).
"""
from __future__ import annotations

import math

import jax
import numpy as np

NUMBERS = ("first_loss_gap", "loss_gap", "refresh_loss_gap", "grad_gap",
           "refresh_grad_gap", "update_gap")
UPDATE_STEPS = 3
STILL = 1e-3


def leaves(tree) -> dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in flat}


def _norms(tree) -> dict[str, float]:
    return {k: float(np.linalg.norm(v)) for k, v in leaves(tree).items()}


def _change_norms(run: dict) -> dict[str, float]:
    before = leaves(run["params"][0])
    return {k: float(np.linalg.norm(a - before[k]))
            for k, a in leaves(run["params"][UPDATE_STEPS]).items()}


def _gap(a: float, b: float, den: float) -> float:
    g = abs(a - b) / max(den, 1e-30)
    return g if math.isfinite(g) else math.inf


def _worst_gap(side: dict, ref: dict, keys) -> float:
    floor = float(np.median([ref[k] for k in keys]))
    return max(_gap(side[k], ref[k], max(ref[k], floor)) for k in keys)


def _loss_gap(side: dict, ref: dict, steps) -> float:
    return max(_gap(side["losses"][t], ref["losses"][t],
                    abs(ref["losses"][t])) for t in steps)


def gaps(side: dict, ref: dict, r: int) -> dict[str, float]:
    """The six numbers for one side against the reference."""
    g0, g0_ref = _norms(side["grads"][0]), _norms(ref["grads"][0])
    gr, gr_ref = _norms(side["grads"][r]), _norms(ref["grads"][r])
    if (len(side["losses"]) != len(ref["losses"])
            or g0.keys() != g0_ref.keys() or gr.keys() != gr_ref.keys()):
        return {k: math.inf for k in NUMBERS}
    median = float(np.median(list(g0_ref.values())))
    moved = [k for k, v in g0_ref.items() if v >= STILL * median]
    return {"first_loss_gap": _loss_gap(side, ref, range(1)),
            "loss_gap": _loss_gap(side, ref, range(3)),
            "refresh_loss_gap": _loss_gap(side, ref, range(r, r + 3)),
            "grad_gap": _worst_gap(g0, g0_ref, g0_ref),
            "refresh_grad_gap": _worst_gap(gr, gr_ref, gr_ref),
            "update_gap": _worst_gap(_change_norms(side),
                                     _change_norms(ref), moved)}


def verdict(numbers: dict[str, float], limits: dict[str, float]):
    """(correct, {name: {"value": v, "limit": l}}) for the numbers that
    have a limit, in a fixed order."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS
              if k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
