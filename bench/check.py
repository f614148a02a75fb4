"""The comparison that decides ``correct``.

The program's first steps (driven through the window's own compiled step and
feed) give three readings, and the plain reference gives the same three:

* ``loss_gap``: the largest relative gap between the program's and the
  reference's mean loss, over the checked steps;
* ``grad_norm_gap``: the first gradient as the optimizer got it, read from
  the optimizer's first moment after one step; per leaf and node, the gap
  between the two L2 norms, over the reference's norm of that leaf or of the
  median leaf of that node, whichever is larger; the worst leaf counts;
* ``change_norm_gap``: the same measure of the change of every node's model
  over the checked steps.

The reference also checks the rows the feed gave against a plain
regeneration of the stream (``reference/data.py``): ``feed_mismatch`` is the
share of their ids that the stream does not give.

Leaves whose reference gradient lies under a thousandth of the median
leaf's are left out of the change: Adam moves them by round-off alone.

Where the nodes sit on separate chips, ``permute_dense_bytes`` counts the
bytes of the compiled step's collective-permutes that are not wire
containers (packed ``u32`` words, or ``f32`` per-block scales, whose last
dimension is 1): the model itself must never ride the wire.  Its limit is 0.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# leaves whose first gradient is this small next to the median leaf's move by
# round-off under Adam, and sit out the change comparison
NEGLIGIBLE_GRAD = 1e-3


def _norm_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              keep=None) -> float:
    if set(prog) != set(ref):
        missing = sorted(set(prog) ^ set(ref))
        raise ValueError(f"program and reference leaves differ: {missing[:4]}")
    leaves = sorted(ref) if keep is None else sorted(keep)
    ref_mat = np.stack([ref[k] for k in sorted(ref)])          # (leaves, nodes)
    floor = np.median(ref_mat, axis=0)                         # per node
    worst = 0.0
    for k in leaves:
        denom = np.maximum(ref[k], floor)
        gap = np.abs(prog[k] - ref[k]) / np.where(denom > 0, denom, 1.0)
        worst = max(worst, float(np.max(gap)))
    return worst


def moved_leaves(ref_grad: Dict[str, np.ndarray]):
    """Leaves whose reference first gradient is not negligible on any node."""
    mat = np.stack([ref_grad[k] for k in sorted(ref_grad)])
    med = np.median(mat, axis=0)
    return [k for k in sorted(ref_grad) if np.all(ref_grad[k] >= NEGLIGIBLE_GRAD * med)]


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": _norm_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_norm_gap": _norm_gap(prog["change_norms"], ref["change_norms"],
                                     keep=moved_leaves(ref["grad_norms"])),
        "feed_mismatch": float(ref["feed_mismatch"]),
    }


def permute_dense_bytes(operands) -> int:
    """Bytes of permute operands that are not wire containers."""
    from bench.hlo import nbytes

    return sum(nbytes(d, s) for d, s in operands
               if not (d == "u32" or (d == "f32" and s and s[-1] == 1)))


def judge(values: Dict[str, float], limits: Dict[str, float]):
    """(correct, checks): every number at or under its limit."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return bool(ok), checks
