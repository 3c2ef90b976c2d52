"""The comparison that decides ``correct`` for last-position logits.

Each sampled sequence's served logits are compared with the plain float32
reference's over the true vocabulary: the error is the L2 norm of the
difference over the L2 norm of the reference.  Where the reference's
router puts its k-th and (k+1)-th choice for the last position closer
than ``tie_margin`` (in logits, in any MoE layer), rounding the router's
inputs to bfloat16 may pick the other expert, and the sequence's logits
then follow a different expert by design: such sequences are left out,
by the reference's own margin, never by the program's output.  The number
compared is the largest error over the sequences that remain.
"""
from __future__ import annotations

import numpy as np


def logit_errors(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    num = np.linalg.norm(served - ref, axis=-1)
    return num / np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)


def judge(served: np.ndarray, ref: np.ndarray, margins: np.ndarray,
          tie_margin: float, limit: float = np.inf) -> dict:
    """``logit_err`` (the widest error over the compared sequences; inf
    when served logits are not finite or no sequence is left), how many
    were compared, how many left out as router ties, and how many of the
    compared are over ``limit``."""
    errs = logit_errors(served, ref)
    compared = np.asarray(margins) >= tie_margin
    finite = np.isfinite(np.asarray(served, np.float64)).all(axis=-1)
    errs = np.where(finite, errs, np.inf)
    worst = float(errs[compared].max()) if compared.any() else float("inf")
    return {"logit_err": worst, "compared": int(compared.sum()),
            "ties": int((~compared).sum()),
            "failed": int((errs[compared] > limit).sum()),
            "errors": errs.tolist(),
            "margins": np.asarray(margins, np.float64).tolist()}


def judge_gaps(gaps, margins, tie_margin: float,
               limit: float = np.inf) -> dict:
    """Served tokens against the reference, one array per request:
    ``gaps`` (how far below the reference's best logit each served token's
    logit lies) and the reference's router ``margins`` at those positions.
    Positions whose margin is under ``tie_margin`` are left out, as for
    last-position logits.  ``token_gap`` is the widest gap compared (inf
    when one is not finite or none is left); ``failed`` counts the requests
    with a compared gap over ``limit``."""
    worst, compared, ties, failed = -np.inf, 0, 0, 0
    for g, m in zip(gaps, margins):
        g = np.where(np.isfinite(g), np.asarray(g, np.float64), np.inf)
        keep = np.asarray(m) >= tie_margin
        compared += int(keep.sum())
        ties += int((~keep).sum())
        if keep.any():
            worst = max(worst, float(g[keep].max()))
            failed += int((g[keep] > limit).any())
    return {"token_gap": worst if compared else float("inf"),
            "compared": compared, "ties": ties, "failed": failed,
            "gaps": [np.asarray(g, np.float64).tolist() for g in gaps],
            "margins": [np.asarray(m, np.float64).tolist() for m in margins]}


def router_shift(program, reference, k: int) -> dict:
    """How far the program's router logits lie from the reference's, at
    the same positions (arrays [..., n_experts] apiece, one a MoE layer,
    in order).  ``shift`` is the largest change of the gap between the
    reference's k-th and (k+1)-th expert: a position whose reference
    margin is wider keeps its top-k choice.  Past the first layer a
    position's input also carries the other positions' routing (a choice
    that flipped upstream, a packet dropped at capacity), so the first
    layer's largest shift (``shift_first``), which is rounding alone, and
    the 99.9th percentile over all layers are given beside it.  ``abs``
    is the largest change of one logit; ``flips`` counts the positions
    whose top-k set differs, and ``flip_margin`` is the widest reference
    margin among them."""
    shifts, firsts, dev, flip_margin, flips = [], 0.0, 0.0, 0.0, 0
    for layer, (p, r) in enumerate(zip(program, reference)):
        p = np.asarray(p, np.float64).reshape(-1, p.shape[-1])
        r = np.asarray(r, np.float64).reshape(-1, r.shape[-1])
        order = np.argsort(-r, axis=-1)
        ek, ek1 = order[:, k - 1:k], order[:, k:k + 1]
        take = lambda a, e: np.take_along_axis(a, e, -1)[:, 0]
        gap_r = take(r, ek) - take(r, ek1)
        shift = np.abs(take(p, ek) - take(p, ek1) - gap_r)
        shifts.append(shift)
        if layer == 0:
            firsts = float(shift.max())
        dev = max(dev, float(np.abs(p - r).max()))
        top_p = np.sort(np.argsort(-p, axis=-1)[:, :k], -1)
        flipped = (top_p != np.sort(order[:, :k], -1)).any(-1)
        flips += int(flipped.sum())
        if flipped.any():
            flip_margin = max(flip_margin, float(gap_r[flipped].max()))
    shifts = np.concatenate(shifts)
    return {"shift": float(shifts.max()), "shift_first": firsts,
            "shift_q999": float(np.quantile(shifts, 0.999)),
            "abs": dev, "flips": flips, "flip_margin": flip_margin,
            "positions": len(shifts)}
