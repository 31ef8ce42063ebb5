"""From a profiler trace to numbers: the one reduction every PR is read by.

``capture`` wraps ``jax.profiler`` around a slice of the measured window;
``load_xplane`` turns the ``.xplane.pb`` it wrote into a small neutral form

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "host":    [[name, start_ns, dur_ns], ...]}          # harness spans only

and the functions below reduce that form. ``perfbench/fixtures`` holds one
such form cut from a chip trace, which ``selfcheck.py`` reduces again.

Device events are those of the per-op line ("XLA Ops") of each device plane:
what ran on the chip, one event per executed HLO instruction or kernel.
Host spans are the harness's own ``jax.profiler.TraceAnnotation``s, whose
names start with ``pb.``; they say what the host was doing in a device gap.
"""

from __future__ import annotations

import glob
import os
import shutil
from collections import defaultdict

SPAN_PREFIX = "pb."
OP_LINES = ("XLA Ops",)
# Ops that only contain other ops (a scan over layers is one ``while``):
# they count for busy time, which is a union, and never as an op of their own.
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """The profiler names a device event by the whole HLO instruction,
    ``%fusion.12 = bf16[8,1024]{...} fusion(...)``: keep ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_base(name: str) -> str:
    """``fusion.12`` -> ``fusion``: the 36 copies of one op add up."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def span(name: str):
    """A host span in the profiler's own trace (no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class capture:
    """``capture(dir).start()`` ... ``stop()`` traces what runs between and
    returns the xplane file's path. The directory is emptied first and
    removed by ``discard()`` once the numbers are out: traces are not kept."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.path = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the harness spans say what it needs
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no xplane under {self.dir}")
        self.path = max(found, key=os.path.getmtime)
        return self.path

    def discard(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "TPU" in plane.name
        for line in plane.lines:
            if is_device and line.name in OP_LINES:
                evs = devices.setdefault(plane.name, [])
                for ev in line.events:
                    evs.append(
                        [op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)])
            elif not is_device:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append(
                            [ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return {"devices": devices, "host": host}


# -- reduction -----------------------------------------------------------------


def merge(intervals):
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_and_window(form: dict, window_ns: tuple[int, int] | None = None):
    """(busy seconds averaged over the devices, window seconds). The window
    is the harness's ``pb.window`` span when the trace holds one, else the
    span from the first device op to the last."""
    if window_ns is None:
        window_ns = window_of(form)
    w0, w1 = window_ns
    if w1 <= w0 or not form["devices"]:
        return 0.0, max(0.0, (w1 - w0) / 1e9)
    busy = []
    for evs in form["devices"].values():
        iv = merge(
            (max(s, w0), min(s + d, w1)) for _, s, d in evs
            if s + d > w0 and s < w1)
        busy.append(sum(e - s for s, e in iv))
    return sum(busy) / len(busy) / 1e9, (w1 - w0) / 1e9


def window_of(form: dict) -> tuple[int, int]:
    for name, s, d in form["host"]:
        if name == SPAN_PREFIX + "window":
            return s, s + d
    starts = [s for evs in form["devices"].values() for _, s, _ in evs]
    ends = [s + d for evs in form["devices"].values() for _, s, d in evs]
    if not starts:
        return 0, 0
    return min(starts), max(ends)


def op_totals(form: dict) -> dict[str, list]:
    """{op name: [count, total_ns]} summed over devices."""
    out: dict[str, list] = defaultdict(lambda: [0, 0])
    for evs in form["devices"].values():
        for name, _, d in evs:
            rec = out[name]
            rec[0] += 1
            rec[1] += d
    return dict(out)


def kernel_events(form: dict, prefixes) -> dict[str, list]:
    """{prefix: [count, total_ns]} of device events whose name starts with
    the prefix, averaged over devices (each device runs its own copy)."""
    n_dev = max(1, len(form["devices"]))
    out = {p: [0, 0] for p in prefixes}
    for evs in form["devices"].values():
        for name, _, d in evs:
            for p in prefixes:
                if name.startswith(p):
                    out[p][0] += 1
                    out[p][1] += d
                    break
    return {p: [c / n_dev, t / n_dev] for p, (c, t) in out.items()}


def top_device_ops(form: dict, k: int = 10) -> list[list]:
    """[[name, seconds], ...] of the ops that took most device time, copies
    of one op summed, containers left out, mean over devices."""
    n_dev = max(1, len(form["devices"]))
    agg: dict[str, int] = defaultdict(int)
    for name, (_, total) in op_totals(form).items():
        base = op_base(name)
        if base not in CONTAINERS:
            agg[base] += total
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / n_dev / 1e9] for n, t in top]


def idle_gaps(form: dict, k: int = 10) -> list[list]:
    """[[what the host was doing, seconds], ...]: every gap between device
    ops on the first device, inside the window, charged to the innermost
    harness span that covers the gap's middle (``unannotated`` when none
    does), summed by span name, longest first."""
    if not form["devices"]:
        return []
    w0, w1 = window_of(form)
    evs = next(iter(form["devices"].values()))
    iv = merge((s, s + d) for _, s, d in evs if s + d > w0 and s < w1)
    gaps = []
    cur = w0
    for s, e in iv:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    spans = sorted(
        (s, s + d, name) for name, s, d in form["host"]
        if name != SPAN_PREFIX + "window")
    agg: dict[str, int] = defaultdict(int)
    for g0, g1 in gaps:
        mid = (g0 + g1) // 2
        best = None
        for s, e, name in spans:
            if s > mid:
                break
            if e >= mid and (best is None or e - s < best[0]):
                best = (e - s, name)
        agg[best[1] if best else "unannotated"] += g1 - g0
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in top]
