"""Whole-step model FLOP/s utilisation: the family's
``train_flops_per_token`` (GPT-2: 6N + 12 L E T, perfbench/counts/gpt2.py) x
all tokens of the window / (window x chips x peak bf16). Recomputation is
not counted. Source: program_counter (steps the harness dispatched) over
the host clock of the whole window."""


def read(res):
    peak, f = res["peak"], res["facts"]
    if not peak or not f.get("tokens"):
        return None
    chips = res.get("chips", 1)
    return 100.0 * f["train_flops_per_token"] * f["tokens"] / (
        f["window_s"] * chips * peak["bf16_flops_per_s"])
