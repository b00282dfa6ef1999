"""The numbers that decide `correct`, and their comparison with limits.

For a training cell the program's first steps are held to the reference's
(`bench.reference`), on the same weights, batches and dropout keys:
  loss_gap.<t>  |loss_t - ref_t| / |ref_t| for each checked step t;
  grad_gap      the first gradient as the optimiser got it (read back from
                Adam's first moment after one step), by its worst leaf:
                | ||g|| - ||g_ref|| | over max(||g_ref leaf||, median leaf);
  update_gap    the parameters' change over the checked steps, read from
                the state the next step is given, by its worst leaf in the
                same measure. Leaves whose reference gradient is below a
                thousandth of the median leaf's are left out: Adam moves
                them by round-off alone.
A cell's limits file names the numbers it compares; the others are
worked out and left out of the comparison (PERF.md says why for each).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import numpy as np

ROUNDOFF_SHARE = 1e-3


def _norms(tree) -> List[float]:
    return [float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for x in jax.tree_util.tree_leaves(tree)]


def worst_leaf_gap(got, want, keep: Sequence[bool] | None = None) -> float:
    g, w = _norms(got), _norms(want)
    if len(g) != len(w):
        raise ValueError(f"{len(g)} leaves against {len(w)}")
    floor = float(np.median(w))
    gaps = [abs(a - b) / max(b, floor, 1e-30)
            for i, (a, b) in enumerate(zip(g, w))
            if keep is None or keep[i]]
    return max(gaps)


def training_numbers(losses: Sequence[float], grad, update,
                     ref_losses: Sequence[float], ref_grad,
                     ref_update) -> Dict[str, float]:
    out = {}
    for t, (a, b) in enumerate(zip(losses, ref_losses), start=1):
        out[f"loss_gap.{t}"] = (abs(a - b) / abs(b) if np.isfinite(a)
                                else float("inf"))
    out["grad_gap"] = worst_leaf_gap(grad, ref_grad)
    ref_g = _norms(ref_grad)
    moved = [n >= ROUNDOFF_SHARE * float(np.median(ref_g)) for n in ref_g]
    out["update_gap"] = worst_leaf_gap(update, ref_update, moved)
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) over the numbers that
    `limits` names; a limit with no number is a failure."""
    table = {n: {"value": numbers.get(n, float("nan")), "limit": limits[n]}
             for n in sorted(limits)}
    ok = all(n in numbers and bool(numbers[n] <= limits[n]) for n in limits)
    return ok, table
