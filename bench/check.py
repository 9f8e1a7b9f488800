"""The comparison that decides a run's ``correct``.

The program's first ``check_blocks`` scan blocks (set-up runs them through
the same ``FedSAEServer.run`` and compiled segment the window then drives)
are compared with the plain reference (``reference.fedsae``) from the same
seed.  A step here is one scan block of ``block_size`` rounds.

Numbers compared, each against its own limit (``limits/<cell>.json``):

cohort_mismatch    cohort slots whose client id differs, over every round
                   checked (selection; exact, limit 0)
dropped_mismatch   rounds whose count of dropped clients differs
                   (prediction outcomes; exact, limit 0)
workload_gap       largest relative gap of a round's mean assigned or mean
                   uploaded epochs (prediction budgets)
loss_gap           largest relative gap of a round's mean client training
                   loss (local SGD)
first_update_gap   the params' change over the first block, leaf by leaf:
                   | ||prog - p0|| - ||ref - p0|| | over the larger of the
                   reference leaf's norm and the median leaf's; the worst
                   leaf (local SGD, upload transform, aggregation)
change_gap         the same for the change over all checked blocks

Leaves whose first-block change in the reference is under a thousandth of
the median leaf's are left out of the two norm gaps: they move by
round-off alone.  A number that is not finite fails.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

NAMES = ("cohort_mismatch", "dropped_mismatch", "workload_gap", "loss_gap",
         "first_update_gap", "change_gap")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    den = np.maximum(np.abs(b), 1e-12)
    gap = np.abs(a - b) / den
    both_nan = np.isnan(a) & np.isnan(b)
    gap = np.where(both_nan, 0.0, gap)
    return float(np.max(gap)) if gap.size else 0.0


def leaf_norms(params: Dict, base: Dict) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(params[k], np.float64)
                                    - np.asarray(base[k], np.float64)))
            for k in base}


def norm_gap(prog: Dict, ref: Dict, base: Dict, moved: List[str]) -> float:
    """Worst-leaf gap between the program's and the reference's change
    norms, over the leaves in ``moved``."""
    np_, nr = leaf_norms(prog, base), leaf_norms(ref, base)
    median = float(np.median([nr[k] for k in moved]))
    return max(abs(np_[k] - nr[k]) / max(nr[k], median, 1e-30)
               for k in moved)


def moved_leaves(ref_first: Dict, base: Dict) -> List[str]:
    """Leaves the reference moves by more than round-off in the first
    block (more than a thousandth of the median leaf's change)."""
    nr = leaf_norms(ref_first, base)
    median = float(np.median(list(nr.values())))
    return sorted(k for k, v in nr.items() if v > 1e-3 * median)


def compare(prog: Dict, ref: Dict, block: int, rounds: int) -> Dict[str, float]:
    """``prog`` and ``ref`` hold, for rounds 0..rounds-1, ``ids`` [R, K],
    ``dropped``, ``assigned``, ``uploaded``, ``train_loss`` [R], and
    ``params`` {0: p0, block: p_block, rounds: p_rounds} (dicts of leaf
    arrays).  Returns the numbers compared."""
    ids_p = np.asarray(prog["ids"])[:rounds]
    ids_r = np.asarray(ref["ids"])[:rounds]
    out = {
        "cohort_mismatch": float(np.sum(ids_p != ids_r))
        if ids_p.shape == ids_r.shape else math.inf,
        "dropped_mismatch": float(np.sum(
            np.asarray(prog["dropped"])[:rounds]
            != np.asarray(ref["dropped"])[:rounds])),
        "workload_gap": max(
            _rel(np.asarray(prog[k])[:rounds], np.asarray(ref[k])[:rounds])
            for k in ("assigned", "uploaded")),
        "loss_gap": _rel(np.asarray(prog["train_loss"])[:rounds],
                         np.asarray(ref["train_loss"])[:rounds]),
    }
    p0 = ref["params"][0]
    moved = moved_leaves(ref["params"][block], p0)
    out["first_update_gap"] = norm_gap(prog["params"][block],
                                       ref["params"][block], p0, moved)
    out["change_gap"] = norm_gap(prog["params"][rounds],
                                 ref["params"][rounds], p0, moved)
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def verdict(numbers: Dict[str, float],
            limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict]]:
    """(correct, {name: {"value", "limit"}}) — every number at or under its
    limit."""
    table = {k: {"value": numbers[k], "limit": float(limits[k])}
             for k in NAMES}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
