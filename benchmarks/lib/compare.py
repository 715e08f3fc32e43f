"""The arithmetic of `correct`: gaps between the program's readings and the
plain reference's, each held to a limit of its own."""

from __future__ import annotations

import numpy as np


def rel_gap(a: float, b: float) -> float:
    """|a - b| measured against the reference's b."""
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def worst_leaf_norm_gap(prog_leaves, ref_leaves, skip=None) -> float:
    """The worst leaf's gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of that
    leaf or of the median leaf, whichever is larger. `skip` marks leaves
    left out (a gradient that is nought to rounding in the reference)."""
    pn = np.array([float(np.linalg.norm(np.asarray(p, np.float64).ravel()))
                   for p in prog_leaves])
    rn = np.array([float(np.linalg.norm(np.asarray(r, np.float64).ravel()))
                   for r in ref_leaves])
    keep = np.ones(len(rn), bool) if skip is None else ~np.asarray(skip)
    if not keep.any():
        return float("nan")
    med = float(np.median(rn[keep]))
    gaps = np.abs(pn - rn) / np.maximum(np.maximum(rn, med), 1e-30)
    return float(gaps[keep].max())


def p95(values) -> float | None:
    """The value at rank int(0.95 n) of the sorted readings; nothing of
    none."""
    s = sorted(values)
    return s[min(len(s) - 1, int(0.95 * len(s)))] if s else None


def verdict(compared: dict) -> bool:
    """Every number at or under its limit; a number that is not finite
    fails."""
    ok = True
    for item in compared.values():
        v, lim = item["value"], item["limit"]
        if lim is None:
            continue
        if not (np.isfinite(v) and v <= lim):
            ok = False
    return ok
