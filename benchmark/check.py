"""The comparisons that decide ``correct``: the program's numbers against the
plain reference's, each to be held to a limit of its own."""

from __future__ import annotations

from typing import Any, Sequence

import numpy as np

IDLE_LEAF = 1e-3  # of the median leaf's raw gradient norm


def worst_leaf_gap(program: np.ndarray, reference: np.ndarray, keep: np.ndarray | None = None) -> tuple[float, int]:
    """The widest gap between the program's norm of a leaf and the
    reference's -- the gap of the norms, not the norm of a difference --
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    program, reference = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    gap = np.abs(program - reference) / np.maximum(reference, np.median(reference))
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    worst = int(np.argmax(gap))
    return float(gap[worst]), worst


def training(program: dict[str, Any], reference: dict[str, Any]) -> tuple[dict[str, float], str]:
    """``loss_step<k>_gap`` for every followed step, ``first_grad_norm_gap``
    and ``param_change_norm_gap`` by the worst leaf. Leaves whose raw gradient
    in the reference is under a thousandth of the median leaf's move under Adam
    by round-off alone and are left out of the change."""
    out = {}
    for k, (lp, lr) in enumerate(zip(program["loss"], reference["loss"]), start=1):
        out[f"loss_step{k}_gap"] = abs(lp - lr) / abs(lr)
    raw = np.asarray(reference["first_grad_raw"], np.float64)
    moving = raw >= IDLE_LEAF * np.median(raw)
    names = reference["names"]
    out["first_grad_norm_gap"], g = worst_leaf_gap(program["first_grad"], reference["first_grad"])
    out["param_change_norm_gap"], c = worst_leaf_gap(program["change"], reference["change"], moving)
    note = (
        f"worst leaf: first gradient {names[g]} ({program['first_grad'][g]:.6g} vs {reference['first_grad'][g]:.6g}), "
        f"change {names[c]} ({program['change'][c]:.6g} vs {reference['change'][c]:.6g}); "
        f"{int((~moving).sum())} idle leaves left out"
    )
    return out, note


def served_gap(reference_logits: np.ndarray, tokens: Sequence[int]) -> float:
    """The widest gap by which a token's reference logit lies below the
    reference's best at its position (0 where every token is the best)."""
    logits = np.asarray(reference_logits, np.float64)
    picked = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(logits.max(axis=-1) - picked))
