"""The comparison that decides ``correct``: each number beside its limit.

A number is ``{"value": v, "limit": l}``; a run is correct when every number
is at or under its limit (and finite). Limits come from the cell's file
``perfbench/limits/<workload>.json``, which ``PERF.md`` explains reading by
reading; a number the file does not name is reported with a null limit and
is not held.
"""

from __future__ import annotations

import math
import statistics

GRAD_FLOOR = 1e-3  # leaves whose reference gradient is under this share of
# the median leaf's move under Adam by round-off alone (a key's bias under
# softmax): left out of the parameters' change, by this rule and not by name.


def worst_leaf(gaps: dict, want: dict, leaves=None):
    """max over leaves of gaps[leaf] / max(want[leaf], median want), and
    the leaf: a leaf the gaps lack reads infinite, a NaN wins."""
    leaves = list(want) if leaves is None else list(leaves)
    med = statistics.median(want[k] for k in leaves)
    worst, at = -1.0, None
    for k in leaves:
        gap = gaps.get(k, math.inf) / max(want[k], med, 1e-30)
        if not gap <= worst:
            worst, at = gap, k
    return worst, at


def worst_leaf_gap(got: dict, want: dict, leaves=None):
    """The gap between the two NORMS of each leaf, not the norm of a
    difference, by ``worst_leaf``."""
    return worst_leaf(
        {k: abs(v - want[k]) for k, v in got.items() if k in want},
        want, leaves)


def training(got: dict, want: dict, limits: dict) -> dict:
    """``got``/``want``: {"losses": [..], "grad_norms": {leaf: n},
    "delta_norms": {leaf: n}} of the program and of the reference; either
    may hold "grad_diff_norms", the per-leaf norm of the difference of the
    two sides' first gradients (whichever side worked it out)."""
    numbers = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        numbers[f"loss{i}_gap"] = abs(a - b) / abs(b)
    if len(got["losses"]) != len(want["losses"]):
        numbers["loss1_gap"] = math.inf
    g, g_at = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    numbers["grad1_norm_gap"] = g
    med = statistics.median(want["grad_norms"].values())
    moved = [k for k, v in want["grad_norms"].items() if v >= GRAD_FLOOR * med]
    d, d_at = worst_leaf_gap(got["delta_norms"], want["delta_norms"], moved)
    numbers["delta3_norm_gap"] = d
    diff = got.get("grad_diff_norms") or want.get("grad_diff_norms")
    x_at = None
    if diff is not None:
        # the difference's norm against the reference's norm of that leaf or
        # of the median leaf: how far rounding turned the gradient
        numbers["grad1_diff_gap"], x_at = worst_leaf(diff, want["grad_norms"])
    out = {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()}
    out["grad1_norm_gap"]["leaf"] = g_at
    out["delta3_norm_gap"]["leaf"] = d_at
    if x_at is not None:
        out["grad1_diff_gap"]["leaf"] = x_at
    return out


def serving(gaps: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits.get(k)} for k, v in gaps.items()}


def finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def verdict(numbers: dict) -> bool:
    held = [n for n in numbers.values() if n.get("limit") is not None]
    if not held:
        return False
    return all(finite(n["value"]) and n["value"] <= n["limit"] for n in held)
