"""decode_roofline.<serving mix>: the least time the chip's memory could
take over the bytes a decode dispatch NEEDS, over the time a decode dispatch
took, in percent. Needed (``counts/<family>.py`` ``decode_bytes_needed``):
the weights outside the routed experts, each held expert that received a
token, the latent of every position the rows attend, from the window means
of the engine's counters (``moe_experts_hit.decode_step`` and
``latent_positions_read`` per ``engine.dispatch.decode_step``, differences
between the two ``/healthz`` bodies). Took: the mean
``pb.engine.dispatch.decode_step`` span of the traced window, which holds the
call, the device's time and the host sync, so the share cannot pass 100. A
decode dispatch of 64 tokens is memory-bound: its FLOPs over the peak are a
tenth of its bytes over the bandwidth. No trace, or a program without the
counters: nothing to read."""

from perfbench import flops, spans
from perfbench.counters import timer_count_difference, window_difference


def read(res):
    step_ms = spans.mean_duration_ms(res, "pb.engine.dispatch.decode_step")
    hit = window_difference(res, "moe_experts_hit.decode_step")
    positions = window_difference(res, "latent_positions_read")
    n = timer_count_difference(res, "engine.dispatch.decode_step")
    count = flops.of(res["config"])
    if not step_ms or hit is None or positions is None or n <= 0 \
            or not res.get("peak") or not hasattr(count, "decode_bytes_needed"):
        return None
    needed = count.decode_bytes_needed(res["model"], hit / n, positions / n)
    return 100.0 * needed / res["peak"]["hbm_bytes_per_s"] / (step_ms / 1e3)
