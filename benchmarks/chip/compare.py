"""The comparison that decides ``correct`` for a training cell.

The program and the reference each report, over the first window of
steps: the mean loss, the mean norm of the aggregated gradient, and per
parameter leaf the norm of AdamW's first moment (the gradients as the
optimizer received them) and the norm of the parameters' change.  Four
numbers follow:

- ``loss_gap``: |loss - loss_ref| / loss_ref;
- ``gnorm_gap``: |gnorm - gnorm_ref| / gnorm_ref;
- ``grad_gap``: over the leaves, the worst |n - n_ref| of the first-moment
  norms, each over the larger of that leaf's reference norm and the
  median leaf's;
- ``update_gap``: the same for the change of the parameters, leaving out
  leaves whose reference first moment is under a thousandth of the median
  leaf's (round-off alone moves those under Adam);
- ``grad_error``: |m - m_ref| / |m_ref| of the first moments over all
  leaves together.  The norm gaps above are blind to a drop in precision:
  rounding noise that does not correlate with the gradient moves a norm
  only in the second order.  This one moves in the first.

Each is held to the cell's limit (``workloads/<cell>.json``); a number
that is not finite fails.
"""
from __future__ import annotations

import math
from typing import Dict

NUMBERS = ("loss_gap", "gnorm_gap", "grad_gap", "update_gap", "grad_error")
SMALL_LEAF = 1e-3


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _worst_leaf(got: Dict[str, float], ref: Dict[str, float], keep) -> float:
    if set(got) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(ref))}")
    med = _median([ref[k] for k in keep])
    return max(abs(got[k] - ref[k]) / max(ref[k], med) for k in keep)


def gaps(got: Dict, ref: Dict, moment_error: Dict[str, float]) -> Dict[str, float]:
    """``got`` and ``ref``: each side's loss, grad_norm, moment_norms and
    change_norms; ``moment_error``: per leaf |m - m_ref|."""
    moments = ref["moment_norms"]
    med = _median(list(moments.values()))
    moved = [k for k, v in moments.items() if v >= SMALL_LEAF * med]
    return {
        "loss_gap": abs(got["loss"] - ref["loss"]) / abs(ref["loss"]),
        "gnorm_gap": abs(got["grad_norm"] - ref["grad_norm"]) / ref["grad_norm"],
        "grad_gap": _worst_leaf(got["moment_norms"], moments, list(moments)),
        "update_gap": _worst_leaf(got["change_norms"], ref["change_norms"], moved),
        "grad_error": math.sqrt(sum(v * v for v in moment_error.values())
                                / sum(v * v for v in moments.values())),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
