"""The comparison that decides ``correct``: the program's first
``advance(n)``, the window's own call at the window's sizes, against the
plain reference over the same n periods from the same inputs.

Numbers compared (each against its cell's limit in ``limits/<cell>.json``):

- ``uploads_mismatch``: periods whose uploader count differs (exact);
- ``varsigma_gap``: the largest relative gap of the eq.-8 normaliser
  sum_k b_k p_k over the n periods;
- ``change_gap``: per leaf of the global model, the gap between the norms
  of the program's and the reference's change ``w^n - w^0``, over the
  larger of the reference leaf's change norm and the median leaf's; the
  worst leaf;
- ``global_diff``: per leaf, the norm of the difference of the two final
  globals on the same scale; the worst leaf.

Leaves that the reference leaves unmoved, a change norm under a thousandth
of the median leaf's, are left out of both leaf numbers.
"""
from __future__ import annotations

import numpy as np

NAMES = ("uploads_mismatch", "varsigma_gap", "change_gap", "global_diff")
STILL = 1e-3


def numbers(prog_rows, ref_rows, prog_w, ref_w, w0):
    """``*_w`` and ``w0``: lists of float64 leaves in one order."""
    n_prog = np.array([r["n_participants"] for r in prog_rows])
    n_ref = np.array([r["n_participants"] for r in ref_rows])
    vs_prog = np.array([r["varsigma"] for r in prog_rows], np.float64)
    vs_ref = np.array([r["varsigma"] for r in ref_rows], np.float64)
    vs_gap = np.abs(vs_prog - vs_ref) / np.maximum(vs_ref, 1e-30)
    ch_ref = np.array([np.linalg.norm(r - a) for r, a in zip(ref_w, w0)])
    ch_prog = np.array([np.linalg.norm(p - a) for p, a in zip(prog_w, w0)])
    diff = np.array([np.linalg.norm(p - r) for p, r in zip(prog_w, ref_w)])
    median = float(np.median(ch_ref))
    moved = ch_ref >= STILL * median
    scale = np.maximum(ch_ref, median)
    nonfinite = not all(np.all(np.isfinite(p)) for p in prog_w)
    worst = lambda v: float("inf") if nonfinite else float(
        np.max(v[moved] / scale[moved]))
    return {
        "uploads_mismatch": int(np.sum(n_prog != n_ref)),
        "varsigma_gap": float(np.max(vs_gap)) if len(vs_gap) else 0.0,
        "change_gap": worst(np.abs(ch_prog - ch_ref)),
        "global_diff": worst(diff),
    }, {"leaves": len(ch_ref), "leaves_left_out": int(np.sum(~moved)),
        "uploads": int(n_ref.sum())}


def verdict(nums: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): a number passes at or under
    its limit."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in NAMES}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
