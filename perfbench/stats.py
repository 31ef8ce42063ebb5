"""The arithmetic of the end-to-end metrics: all work over the whole
window, tails over all samples. No medians of chunks."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default, 'linear'), over ALL values given."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(total_work: float, t_start: float, t_end: float) -> float:
    """Work per second over the whole window."""
    if t_end <= t_start:
        raise ValueError(f"empty window: {t_start}..{t_end}")
    return total_work / (t_end - t_start)


def iqr_share(values) -> float:
    """The spread the contract names: (Q3 - Q1) / median, quartiles as
    ``statistics.quantiles(values, n=4)`` gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
