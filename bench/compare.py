"""The numbers that decide ``correct``, each held to its limit.

Training (first steps of the run, against the reference's same steps):

* ``loss_gap`` — the largest ``|loss - ref| / |ref|`` over the steps;
* ``grad_gap`` — for the first step's gradient as the optimizer got it,
  the worst leaf's ``| ||g|| - ||g_ref|| |``, over the larger of that
  leaf's reference norm and the median leaf's;
* ``change_gap`` — the same, for each leaf's change from the starting
  weights after the last checked step.

Leaves whose reference gradient norm is under a thousandth of the median
leaf's are left out of both leaf numbers (their Adam step is round-off).

Serving (every request of the sampled micro-batches):

* ``logit_gap`` — the largest ``max |logit - ref| / max |ref|`` over the
  targets' logit rows.

Limits live in ``bench/limits/<cell>.json``; a number without a limit is
not correct.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

SMALL_GRAD = 1e-3


def _norms(tree_leaves: Sequence[np.ndarray]) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in tree_leaves])


def leaf_gap(prog: Sequence[np.ndarray], ref: Sequence[np.ndarray],
             keep: np.ndarray) -> float:
    p, r = _norms(prog), _norms(ref)
    scale = np.maximum(r, np.median(r[keep]))
    gaps = np.abs(p - r) / scale
    return float(gaps[keep].max())


def moving_leaves(ref_grad: Sequence[np.ndarray]) -> np.ndarray:
    g = _norms(ref_grad)
    return g >= SMALL_GRAD * np.median(g)


def training_numbers(prog_losses: Sequence[float],
                     ref_losses: Sequence[float],
                     prog_grad1: Sequence[np.ndarray],
                     ref_grad1: Sequence[np.ndarray],
                     params0: Sequence[np.ndarray],
                     prog_last: Sequence[np.ndarray],
                     ref_last: Sequence[np.ndarray]) -> Dict[str, float]:
    keep = moving_leaves(ref_grad1)
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog_losses, ref_losses))
    change = lambda last: [np.asarray(a, np.float64) - np.asarray(b,
                                                                   np.float64)
                           for a, b in zip(last, params0)]
    return {"loss_gap": float(loss_gap),
            "grad_gap": leaf_gap(prog_grad1, ref_grad1, keep),
            "change_gap": leaf_gap(change(prog_last), change(ref_last),
                                   keep)}


def logit_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = np.maximum(np.abs(ref).max(axis=1), 1e-30)
    return float((np.abs(prog - ref).max(axis=1) / scale).max())


def load_limits(bench_dir: Path, cell: str) -> Dict[str, float]:
    path = bench_dir / "limits" / f"{cell}.json"
    if not path.exists():
        return {}
    return {k: float(v) for k, v in
            json.loads(path.read_text())["limits"].items()}


def judge(numbers: Dict[str, float], limits: Dict[str, float],
          problems: Optional[List[str]] = None) -> tuple:
    """(correct, checks): every number within its limit and no problem.
    ``checks`` maps each number to ``{"value", "limit"}``."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    ok = not problems and bool(numbers) and all(
        c["limit"] is not None and np.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
