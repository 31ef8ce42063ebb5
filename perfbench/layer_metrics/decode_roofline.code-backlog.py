"""decode_roofline.code-backlog: the least time the chip's memory could take
over the bytes a decode dispatch NEEDS, over the time a decode dispatch took,
in percent, for a family with two groups of pages (the stem's reader asks
for kimi_k2's counters). Needed (``counts/<family>.py``
``decode_bytes_needed``): the weights outside the experts once, each expert
that received a token, K and V of every position the rows attend in each
layer group, from the window means of the engine's counters
(``moe_experts_hit.decode_step``, ``kv_positions_read.full`` and
``kv_positions_read.window`` per ``engine.dispatch.decode_step``,
differences between the two ``/healthz`` bodies). Took: the mean
``pb.engine.dispatch.decode_step`` span of the traced window, which holds the
call, the device's time and the host sync, so the share cannot pass 100. A
decode dispatch of 32 tokens is memory-bound. No trace, or a program without
the counters: nothing to read."""

from perfbench import flops, spans
from perfbench.counters import timer_count_difference, window_difference


def read(res):
    step_ms = spans.mean_duration_ms(res, "pb.engine.dispatch.decode_step")
    hit = window_difference(res, "moe_experts_hit.decode_step")
    full = window_difference(res, "kv_positions_read.full")
    window = window_difference(res, "kv_positions_read.window")
    n = timer_count_difference(res, "engine.dispatch.decode_step")
    count = flops.of(res["config"])
    if not step_ms or None in (hit, full, window) or n <= 0 \
            or not res.get("peak") or not hasattr(count, "decode_bytes_needed"):
        return None
    needed = count.decode_bytes_needed(
        res["model"], hit / n, full / n, window / n)
    return 100.0 * needed / res["peak"]["hbm_bytes_per_s"] / (step_ms / 1e3)
