"""The flash attention kernels' share of their roofline: the least time the
chip could take for the causal attention the traced steps NEED (forward and
backward of every layer once per step, from the cell's shapes, by the
family's count module) over the summed device time of the events named
flash_mha_fwd* and flash_mha_bwd*. A remat that ran the forward kernel twice
shows as a lower share, not as more work. No matching event: nothing to
read (the harness leaves the metric out), never 0."""

from perfbench import flops, trace

KERNELS = ("flash_mha_fwd", "flash_mha_bwd")


def read(res):
    if res.get("trace") is None or not res.get("peak"):
        return None
    found = trace.kernel_events(res["trace"], KERNELS)
    spent_s = sum(t for _, t in found.values()) / 1e9
    n_bwd = found["flash_mha_bwd"][0]
    if spent_s <= 0 or n_bwd <= 0:
        return None
    f = res["facts"]
    work = flops.of(res["config"]).flash_attention_work(
        res["model"], f["batch"] // res.get("chips", 1), f["seq_len"])
    least_fwd, _ = flops.roofline_seconds(work["fwd"], res["peak"])
    least_bwd, _ = flops.roofline_seconds(work["bwd"], res["peak"])
    # one backward event per layer per step; each needs one forward too
    return 100.0 * n_bwd * (least_fwd + least_bwd) / spent_s
