"""The comparison that decides ``correct``: what the timed path produced
in its first steps against the plain reference, number by number, each
number that ``bench/limits/<cell>.json`` names held to its limit (a
number with no upper reading at the cell's size has no limit there and
is not compared).

A record (the program's, the reference's or the control's) holds each
step's ``loss``; ``grad``, the norm of each leaf's first gradient as
the optimizer got it; ``stats`` (K-FAC), the norm of each factor's
first stats panel X; ``change`` and ``last``, the norm of each leaf's
change over all the checked steps and over the last one.

Norms are compared, not differences: a gap is |program − reference|
over the reference's norm of that leaf or the median leaf's, whichever
is larger, and a number is the worst leaf's gap.  Leaves whose
reference gradient is under a thousandth of the median leaf's (a bias
ahead of batch norm: nought but rounding) move by round-off alone under
AdamW, and are left out of ``change`` and ``last``.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, Optional, Tuple

NULL_GRAD = 1e-3
NUMBERS = ("loss", "grad", "stats", "change", "last")


def gap(prog: Dict[str, float], ref: Dict[str, float],
        keys: Iterable[str]) -> Tuple[float, Optional[str]]:
    """(worst gap, its leaf) over ``keys``."""
    keys = [k for k in keys if k in prog and k in ref]
    if not keys:
        return float("nan"), None
    med = statistics.median(ref[k] for k in keys)
    worst, at = -1.0, None
    for k in keys:
        den = max(ref[k], med)
        g = abs(prog[k] - ref[k]) / den if den > 0 else float("inf")
        if not math.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, at = g, k
    return worst, at


def null_leaves(ref: dict) -> set:
    med = statistics.median(ref["grad"].values())
    return {k for k, g in ref["grad"].items() if g < NULL_GRAD * med}


def numbers(prog: dict, ref: dict) -> Dict[str, Tuple[float, Optional[str]]]:
    """Each number of the check with the leaf or step it comes from."""
    out = {}
    losses = list(zip(prog["loss"], ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]) or not losses:
        out["loss"] = (float("inf"), "steps")
    else:
        gaps = [abs(p - r) / abs(r) if r else float("inf")
                for p, r in losses]
        gaps = [g if math.isfinite(g) else float("inf") for g in gaps]
        i = max(range(len(gaps)), key=gaps.__getitem__)
        out["loss"] = (gaps[i], f"step {i}")
    out["grad"] = gap(prog["grad"], ref["grad"], prog["grad"])
    if "stats" in ref:
        out["stats"] = gap(prog.get("stats", {}), ref["stats"], ref["stats"])
    moving = [k for k in ref["change"] if k not in null_leaves(ref)]
    out["change"] = gap(prog["change"], ref["change"], moving)
    out["last"] = gap(prog["last"], ref["last"], moving)
    return out


def check(prog: dict, ref: dict, limits: dict) -> Tuple[bool, dict]:
    """(correct, {name: {"value", "limit", "at"}}) — every number that
    ``limits`` names at or under its limit, and finite."""
    res, ok = {}, True
    nums = numbers(prog, ref)
    for name, limit in limits.items():
        value, at = nums[name]
        good = math.isfinite(value) and value <= limit
        ok = ok and good
        res[name] = {"value": value, "limit": limit, "at": at}
    return ok, res
