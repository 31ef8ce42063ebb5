"""Whole-step share of the chip's peak while SERVING (step_mfu.<serving
mix>; training has step_mfu.train.py): forward FLOPs the
tokens processed inside the window needed (prompt tokens of requests whose
first token came inside it, and every generated token received inside it;
the family's ``serve_flops_span``; for GPT-2, 2 x matmul parameters + 4 L E
(position+1) each, perfbench/counts/gpt2.py) over
window x peak bf16, in percent."""


def read(res):
    peak, f = res["peak"], res["facts"]
    if not peak or not f.get("serve_flops_in_window"):
        return None
    return 100.0 * f["serve_flops_in_window"] / (
        f["window_s"] * res.get("chips", 1) * peak["bf16_flops_per_s"])
