"""The numbers that decide ``correct``, and their judgement.

Training: the program's checked steps against the plain reference's
(``reftrain``): each step's loss, each leaf's norm of the first gradient
as the optimizer received it, and each leaf's norm of the change over
the checked steps. Norms are compared as the gap between the two norms,
over the larger of the reference's norm of that leaf and of the median
leaf (some gradients are all but zero). Leaves whose reference gradient
is under a thousandth of the median leaf's move by round-off alone under
Adam and are left out of the change.

Serving: the widest gap by which a served token's logit lies below the
reference's best logit at that position.

Each number has its own limit in the cell's file; a run is correct when
every number is at or under its limit.
"""
from __future__ import annotations

import numpy as np

__all__ = ["train_numbers", "judge", "format_checks"]

FLAT = 1e-3


def _gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> tuple[float, int]:
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    idx = np.arange(len(ref)) if keep is None else np.flatnonzero(keep)
    med = float(np.median(ref[idx]))
    gaps = np.abs(prog[idx] - ref[idx]) / np.maximum(ref[idx], med)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), int(idx[worst])


def train_numbers(prog: dict, ref: dict) -> tuple[dict, dict]:
    """(numbers, where): the three compared numbers and, for each, the
    step or leaf that set it."""
    losses = np.abs(np.asarray(prog["losses"]) - np.asarray(ref["losses"]))
    grad_gap, grad_leaf = _gap(prog["grad"], ref["grad"])
    moved = np.asarray(ref["grad"]) >= FLAT * np.median(ref["grad"])
    change_gap, change_leaf = _gap(prog["change"], ref["change"], moved)
    names = ref["names"]
    return ({"loss_gap": float(losses.max()), "grad_gap": grad_gap,
             "change_gap": change_gap},
            {"loss_gap": f"step {int(losses.argmax()) + 1}",
             "grad_gap": names[grad_leaf],
             "change_gap": names[change_leaf],
             "left_out": [n for n, m in zip(names, moved) if not m]})


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Correct when every number is finite and within its limit."""
    checks = {k: {"value": float(v), "limit": float(limits[k])}
              for k, v in numbers.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks


def format_checks(checks: dict) -> list[str]:
    return [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
            for k, c in checks.items()]
