"""The numbers that decide ``correct``: the program against the reference
over the first rounds of training.

* ``loss``: the largest relative gap of the server's validation loss
  after each checked round.
* ``update1``: the global model's change over round 0 (the update FedAvg
  hands the server), per leaf: the gap between the program's norm and
  the reference's, over the larger of the reference's norm of that leaf
  and of the median leaf; the worst leaf.
* ``change3``: the same for the change over all checked rounds.

Leaves the reference leaves all but unmoved (norm under a thousandth of
the median leaf's, as the last layer's RZ angles, which commute with the
Z readout) are not compared by their gap: round-off alone moves them.
They count only where the program moves one by more than that.
"""
from __future__ import annotations

import numpy as np


def _leaves(tree) -> list:
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(tree)]


def norm_gap(prog_delta, ref_delta) -> float:
    p = [np.linalg.norm(x) for x in _leaves(prog_delta)]
    r = [np.linalg.norm(x) for x in _leaves(ref_delta)]
    med = float(np.median(r))
    if not med > 0:
        return float("inf")
    # a leaf the reference leaves unmoved must stay (all but) unmoved
    gaps = [abs(a - b) / max(b, med) if b >= 1e-3 * med
            else max(a - 1e-3 * med, 0.0) / med for a, b in zip(p, r)]
    return float(max(gaps))


def delta(a, b):
    import jax
    return jax.tree_util.tree_map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
        a, b)


def numbers(params0, prog_params: list, prog_losses: list,
            ref_params: list, ref_losses: list) -> dict:
    """prog_params / ref_params: global params after each checked round."""
    loss = max(abs(p - q) / abs(q) for p, q in zip(prog_losses, ref_losses))
    if not np.isfinite(loss):
        loss = float("inf")
    return {
        "loss": float(loss),
        "update1": norm_gap(delta(prog_params[0], params0),
                            delta(ref_params[0], params0)),
        "change3": norm_gap(delta(prog_params[-1], params0),
                            delta(ref_params[-1], params0)),
    }


def verdict(nums: dict, limits: dict) -> bool:
    return all(np.isfinite(nums[k]) and nums[k] <= limits[k]
               for k in limits)
