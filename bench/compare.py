"""The numbers that decide ``correct``, each against its limit.

Training (per cell, over the outer steps that set-up drives and the
reference follows):

``loss_gap``    the median, over every inner step of every worker in
                those outer steps, of the relative gap of the program's
                loss from the reference's (the same weights and rows at
                the first step); the largest, ``loss_gap_max``, is
                printed but not compared: it falls in the steep first
                steps, where Adam moves every weight by the full step
                and bfloat16 carries the trajectory as far off as a
                lower precision does (PERF.md, section 6);
``grad_gap``    the first averaged pseudo-gradient, as the outer
                optimizer gets it (its momentum after step 1): the
                largest gap, over leaves, between the program's and the
                reference's norm of that leaf, over the larger of the
                reference's norm of the leaf and of the median leaf;
``change_gap``  the same for the anchor's change after the checked
                steps.

Both leaf measures count only the leaves whose reference gradient is at
least ``GRAD_FLOOR`` of the median leaf's: a leaf that the reference
does not move (the ones-initialised norm gains, pinned by bfloat16
storage) moves in the program by the int8 codec's rounding alone.

Serving: ``logit_gap``, the widest gap, over the served tokens of a
sample of finished greedy requests, between the reference's best logit
at that position and its logit of the token served.
"""
from __future__ import annotations

import math

import numpy as np

GRAD_FLOOR = 1e-3


def _leaf_gap(prog: list[float], ref: list[float],
              keep: list[bool] | None = None) -> float:
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = [True] * len(r)
    keep = np.asarray(keep, bool)
    med = float(np.median(r[keep]))
    den = np.maximum(r, med)
    gaps = np.abs(p - r) / np.where(den > 0, den, 1.0)
    return float(np.max(np.where(keep, gaps, 0.0)))


def _rel(prog, ref) -> np.ndarray:
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if p.shape != r.shape:
        return np.full(1, math.inf)
    return np.abs(p - r) / np.abs(r)


def train_numbers(prog: dict, ref: dict) -> dict:
    g = np.asarray(ref["grad_norms"], np.float64)
    keep = list(g >= GRAD_FLOOR * np.median(g))
    loss = _rel(prog["step_losses"], ref["step_losses"])
    return {"loss_gap": float(np.median(loss)),
            "loss_gap_max": float(np.max(loss)),
            "grad_gap": _leaf_gap(prog["grad_norms"], ref["grad_norms"],
                                  keep),
            "change_gap": _leaf_gap(prog["change_norms"],
                                    ref["change_norms"], keep)}


def logit_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap of ``tokens`` below the best of ``ref_logits`` (T, V)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, np.asarray(tokens)[:, None],
                             -1)[:, 0]
    return float(np.max(best - got))


def checks(numbers: dict, limits: dict) -> dict:
    """``{name: {value, limit, ok}}``; a number that is not finite fails."""
    out = {}
    for name, lim in limits.items():
        v = numbers.get(name, float("nan"))
        ok = v is not None and math.isfinite(v) and v <= lim
        out[name] = {"value": v, "limit": lim, "ok": bool(ok)}
    return out
