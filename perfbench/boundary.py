"""The arithmetic the readers of the dispatch boundary share.

Inside each ``pb.engine.dispatch.<kind>`` span the program (PR 38) writes
``pb.engine.launch.<kind>`` around the program call and
``pb.engine.sync.<kind>`` around the read-back of its outputs; the device
ops between them are the program. Here, per counted tick (a
``pb.engine.tick`` wholly inside the traced window), the sum over such
spans of an interval between a host span and the device ops of the first
device (as in ``trace.idle_gaps``) that lie in the same dispatch, and the
mean of those sums over the ticks. A dispatch with no device op inside its
span gives no interval and is left out, not counted as 0; a tick none of
whose dispatches gives one is left out of the mean. Nothing is clamped: a
negative interval says the host spans and the device ops disagree on the
clock, and has to stay visible. No trace, no device op, or a program
without the spans (the parent of PR 38): None.
"""

from __future__ import annotations

import bisect

from perfbench import spans


def _first_device(form: dict) -> tuple[list[int], list[int]] | None:
    """(sorted op starts, sorted op ends) of the first device."""
    if not form["devices"]:
        return None
    evs = next(iter(form["devices"].values()))
    if not evs:
        return None
    return sorted(s for _, s, _ in evs), sorted(s + d for _, s, d in evs)


def launch_ns(span, call, starts, ends) -> int | None:
    """From the span's start to the start of the first device op at or
    after it that starts inside the dispatch ``call``."""
    i = bisect.bisect_left(starts, span[0])
    if i == len(starts) or starts[i] > call[1]:
        return None
    return starts[i] - span[0]


def readback_ns(span, call, starts, ends) -> int | None:
    """From the end of the last device op that ends inside the dispatch
    ``call`` to the span's end."""
    i = bisect.bisect_right(ends, call[1]) - 1
    if i < 0 or ends[i] < call[0]:
        return None
    return span[1] - ends[i]


def per_tick_ms(res, stem: str, interval) -> float | None:
    """Mean over the counted ticks of the summed ``interval`` of their
    ``pb.engine.<stem>.*`` spans, in milliseconds."""
    form = res.get("trace")
    if form is None:
        return None
    ops = _first_device(form)
    ticks = spans.spans_of(form, "pb.engine.tick")
    if ops is None or not ticks:
        return None
    calls = spans.spans_of(form, "pb.engine.dispatch.")
    children = spans.spans_of(form, f"pb.engine.{stem}.")
    sums = []
    for tick in ticks:
        got = [interval(child, call, *ops)
               for child in spans.within(children, [tick])
               for call in calls
               if call[0] <= child[0] and child[1] <= call[1]]
        got = [ns for ns in got if ns is not None]
        if got:
            sums.append(sum(got))
    return spans.mean_ms(sums)
