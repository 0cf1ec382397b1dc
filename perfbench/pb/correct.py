"""The comparison that decides ``correct``.

Every number compared has a limit of its own, kept in the cell's file
(``perfbench/workloads/<cell>.json``, ``limits``); PERF.md gives the
readings each was set from.  A limit of ``null`` means the number is
printed and not judged.
"""
from __future__ import annotations

import statistics


def leaf_gaps(prog: dict, ref: dict, skip=()) -> dict:
    """Per leaf, the gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of
    that leaf or of the median leaf, whichever is larger: some
    gradients are all but zero."""
    med = statistics.median(ref.values())
    return {k: abs(prog[k] - r) / max(r, med)
            for k, r in ref.items() if k not in skip}


def _worst_and_median(out: dict, name: str, gaps: dict) -> None:
    where = max(gaps, key=gaps.get)
    out[f"{name}_gap"], out[f"_{name}_leaf"] = gaps[where], where
    out[f"{name}_median_gap"] = statistics.median(gaps.values())
    out[f"_{name}_leaf_gaps"] = gaps


def still_leaves(grad_norms: dict, share=1e-3) -> set:
    """Leaves whose reference gradient is nought to rounding (under
    ``share`` of the median leaf's): under Adam they move by round-off
    alone and are left out of the parameters' change."""
    med = statistics.median(grad_norms.values())
    return {k for k, g in grad_norms.items() if g < share * med}


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` / ``ref``: ``losses`` (per step), ``grad1_norms`` and
    ``delta_norms`` (per leaf)."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = abs(p - r) / abs(r)
    _worst_and_median(out, "grad1", leaf_gaps(prog["grad1_norms"],
                                              ref["grad1_norms"]))
    skip = still_leaves(ref["grad1_norms"])
    _worst_and_median(out, "delta3", leaf_gaps(prog["delta_norms"],
                                               ref["delta_norms"], skip))
    out["_still_leaves"] = sorted(skip)
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, compared)`` where ``compared`` maps each judged name
    to ``[value, limit]``; a number without a limit is shown with
    ``null`` and not judged."""
    compared, ok = {}, True
    for name, value in numbers.items():
        if name.startswith("_"):
            continue
        limit = limits.get(name)
        compared[name] = [value, limit]
        if limit is not None and not value <= limit:   # NaN fails
            ok = False
    return ok, compared
