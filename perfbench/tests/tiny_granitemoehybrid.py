"""Tiny stand-ins for the granitemoehybrid cell's files, for tests on the
CPU: the same keys as ``configs/granite-4.0-h-micro.json`` and
``traffic/chat-backlog.json``, at a size a test run holds (two periods of
the published pattern, heads and state cut, a block of 8 positions).
Nothing here is ever measured.
"""

_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

TINY_MODEL = {
    "model_type": "granitemoehybrid", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 20, "layer_types": _PERIOD * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 96, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_chunk_size": 8, "rms_norm_eps": 1e-5,
    "position_embedding_type": "nope", "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8,
    # the four multipliers as published; the matrices' 0.02 widened by
    # (2048 / 64)^1/2, the ratio of the widths: a projection's output then
    # has the published model's scale (0.02 x 2048^1/2 = 0.9), on which the
    # weight of the state's term against the skip depends
    "initializer_range": 0.113,
}

_SIZES = dict(
    vocab_size=512, n_ctx=128, n_embd=64, n_layer=20, n_head=4, n_kv_head=2,
    layer_types=tuple(_PERIOD * 2), mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=8, shared_intermediate_size=96,
    embedding_multiplier=12.0)

TINY_CONFIG = {
    "name": "tiny-granitemoehybrid",
    "reference": "granitemoehybrid",
    "model": TINY_MODEL,
    "program": {
        "preset": "granite-4.0-h-micro",
        "serve_overrides": dict(
            _SIZES, dtype="float32", param_dtype="bfloat16"),
        "serve_holds": {
            "n_embd": "hidden_size", "n_layer": "num_hidden_layers",
            "n_head": "num_attention_heads",
            "n_kv_head": "num_key_value_heads", "vocab_size": "vocab_size",
            "layer_types_list": "layer_types",
            "mamba_n_heads": "mamba_n_heads", "mamba_d_head": "mamba_d_head",
            "mamba_d_state": "mamba_d_state",
            "mamba_chunk_size": "mamba_chunk_size",
            "shared_intermediate_size": "shared_intermediate_size",
            "embedding_multiplier": "embedding_multiplier",
            "attention_multiplier": "attention_multiplier",
            "residual_multiplier": "residual_multiplier",
            "logits_scaling": "logits_scaling"},
    },
}

# prompts of one to four chunks of 16 with ragged final chunks, more
# requests than rows (every slot is reused), replies of several steps
TINY_SERVE = {
    "driver": "serve", "loop": "closed", "clients": 5,
    "requests_per_s_ceiling": 200, "cycle_requests": 12, "order_seed": 1,
    "prompt_tokens": {"median": 24, "sigma": 0.6, "min": 4, "max": 64},
    "new_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
    "sampled_share": 0.5, "temperature": 0.8, "top_k": 50,
    "engine": {"slots": 4, "max_len": 128, "page_size": 8,
               "prefill_chunk": 16, "pool_pages": 2048},
    "warm_requests": 2, "warm_new_tokens": 2, "compare_requests": 4,
    "compare_sampled_requests": 4, "trace_seconds": 1, "ramp_s": 0.5,
}

TINY_BENCH = {
    "end_to_end": [
        {"name": "serve_tok_s", "unit": "tokens/s",
         "workloads": ["tiny-granitemoehybrid.backlog"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}

# Limits for the TINY stand-in only, read on the CPU the way PERF.md section
# 2 reads the cell's own on the chip. The stand-in computes in float32 over
# bfloat16 weights, so the program reads 0 on both; the float8 control reads
# served_logit_gap 0.011-0.030, sampled_topk_gap 0.010-0.020 on the three
# seeds; the planted faults read served_logit_gap 0.0052 (the attention's
# scale: two layers of twenty, scores already small), 0.073-0.092 (the state
# not reset, the tail dropped, the padded tail advancing; seeds 3 and 4).
# They say nothing about a cell.
TINY_LIMITS = {"served_logit_gap": 0.002, "sampled_topk_gap": 0.002}
