"""The numbers that decide ``correct``, each against its limit.

Training: the gap of each step's loss, relative to the reference's; and
by the worst leaf (per layer for stacked leaves) the gap between the
program's and the reference's norms of the first gradient as AdamW gets
it, and of the weights' change after the first steps, each measured
against the larger of the reference's norm of that leaf and the median
leaf's. Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone and are left out of the change.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

#: A leaf whose reference gradient norm is under this share of the
#: median leaf's is moved by round-off alone under Adam.
STILL_LEAF = 1e-3


def _flat(d: Dict[str, np.ndarray]) -> Dict[str, float]:
    out = {}
    for k, v in d.items():
        v = np.atleast_1d(np.asarray(v, np.float64))
        for i, x in enumerate(v):
            out[f"{k}[{i}]" if len(v) > 1 else k] = float(x)
    return out


def worst_leaf(prog, ref, keep: Optional[set] = None) -> float:
    p, r = _flat(prog), _flat(ref)
    med = float(np.median(list(r.values())))
    gaps = [abs(p[k] - r[k]) / max(r[k], med) for k in r
            if keep is None or k in keep]
    return max(gaps)


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    rg = _flat(ref["grad"])
    med = float(np.median(list(rg.values())))
    keep = {k for k, v in rg.items() if v >= STILL_LEAF * med}
    loss = np.asarray(prog["loss"], np.float64)
    rloss = np.asarray(ref["loss"], np.float64)
    return {"loss_gap": float(np.max(np.abs(loss - rloss) / np.abs(rloss))),
            "grad_gap": worst_leaf(prog["grad"], ref["grad"]),
            "change_gap": worst_leaf(prog["change"], ref["change"], keep)}


def judge(numbers: Dict[str, float], limits: Dict[str, Optional[float]]):
    """(correct, checks): every number beside its limit; a number above
    its limit, not a finite number, or without a limit is not correct."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    ok = all(c["limit"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
