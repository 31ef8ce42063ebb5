"""paged_decode_roofline.code-backlog: the paged decode-attention kernel's
share of its roofline, in percent: the least time the chip's memory could
take over the K and V the traced decode dispatches' rows attend
(``counts/<family>.py`` ``paged_attention_bytes``: 2,048 B a position a
layer; every position in a full layer, the window's in a sliding one), over
the summed device time of the events named ``paged_decode_attention``. The
dispatches are counted INSIDE the trace: one kernel event a layer a
dispatch, so events / layers; what a dispatch's rows attend is the window
mean of the engine's counters (``kv_positions_read.full`` and
``kv_positions_read.window`` per ``engine.dispatch.decode_step``,
differences between the two ``/healthz`` bodies). The kernel copies whole
blocks of pages and scores every key of a block it starts, so the share
stays under 100. Single-query attention is memory-bound (2 FLOPs a byte).
No trace, no such event, or a program without the counters: nothing to
read, never 0."""

from perfbench import flops, trace
from perfbench.counters import timer_count_difference, window_difference

KERNEL = "paged_decode_attention"


def read(res):
    if res.get("trace") is None or not res.get("peak"):
        return None
    events, spent_ns = trace.kernel_events(res["trace"], (KERNEL,))[KERNEL]
    full = window_difference(res, "kv_positions_read.full")
    window = window_difference(res, "kv_positions_read.window")
    n = timer_count_difference(res, "engine.dispatch.decode_step")
    count = flops.of(res["config"])
    layers = len(res["model"].get("layer_types", ()))
    if events <= 0 or spent_ns <= 0 or None in (full, window) or n <= 0 \
            or not layers or not hasattr(count, "paged_attention_bytes"):
        return None
    needed = (events / layers) * count.paged_attention_bytes(
        res["model"], full / n, window / n)
    return 100.0 * needed / res["peak"]["hbm_bytes_per_s"] / (spent_ns / 1e9)
