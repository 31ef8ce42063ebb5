"""The arithmetic the readers of the PROGRAM's counters share.

A serving run hands its readers ``res["health"]`` = ``{"open", "close"}``:
the program's ``/healthz`` bodies as read at the two ends of the nominal
window. Here: a counter's, and a timer's count's, difference between the
two, summed over the replicas. A program without the counter (the parent of
the PR that added it), like a run without the bodies, gives None, and a
reader then returns None.
"""

from __future__ import annotations


def _replica_pairs(res):
    health = res.get("health") or {}
    opened = (health.get("open") or {}).get("replicas", {})
    for rep, close in (health.get("close") or {}).get("replicas", {}).items():
        yield opened.get(rep), close


def window_difference(res, name: str):
    """The engine counter's difference over the nominal window; None where
    a body lacks it."""
    total = 0
    for opened, close in _replica_pairs(res):
        if opened is None or name not in close.get("counters", {}) \
                or name not in opened.get("counters", {}):
            return None
        total += close["counters"][name] - opened["counters"][name]
    return total


def timer_count_difference(res, name: str) -> int:
    """How often the timer (a span's) was entered in the nominal window."""
    total = 0
    for opened, close in _replica_pairs(res):
        total += (close.get("timers", {}).get(name, {}).get("count", 0)
                  - ((opened or {}).get("timers", {}).get(name, {})
                     .get("count", 0)))
    return total
