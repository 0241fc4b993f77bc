"""The comparison that decides ``correct``.

A run drives the program's own training step through its first three
epochs and keeps, on the host, the loss of each, the first gradient as
the optimizer received it, and the parameters after the third update.
The reference follows the same three steps from the same weights. Three
numbers compare them:

- ``loss_gap``: the largest relative gap between the two losses, over
  the three steps;
- ``grad_norm_gap``: over the leaves, the largest gap between the norm of
  the program's first gradient and the reference's, relative to the
  reference's norm of that leaf or of the median leaf, whichever is larger;
- ``update_norm_gap``: the same for the change of the parameters over the
  three steps.

Norms are compared leaf by leaf, not the norm of the difference: the
program quantizes the inter-group halo with stochastic Int2 rounding and
reads it one epoch stale, so single elements may part while each leaf's
size and the loss agree. A leaf whose reference gradient is under a
thousandth of the median leaf's moves under Adam by rounding alone, so
it is left out of both norm gaps.
"""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np

GRAD_FLOOR = 1e-3


def _norms(tree):
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)))
                     for a in jax.tree.leaves(tree)])


def _gap(got: np.ndarray, want: np.ndarray, keep: np.ndarray) -> float:
    base = np.maximum(want, np.median(want))
    return float(np.max(np.abs(got - want)[keep] / base[keep]))


def readings(prog: Dict, ref: Dict, params0) -> Dict[str, float]:
    """The three numbers for one run. ``prog`` and ``ref`` each hold
    ``losses`` (3), ``grad1`` and ``params`` (after step 3) as host trees."""
    g_ref = _norms(ref["grad1"])
    keep = g_ref >= GRAD_FLOOR * np.median(g_ref)
    d = lambda tree: jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64), tree, params0)
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_gap": _gap(_norms(prog["grad1"]), g_ref, keep),
        "update_norm_gap": _gap(_norms(d(prog["params"])),
                                _norms(d(ref["params"])), keep),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number finite and at or under its limit."""
    return all(np.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)
