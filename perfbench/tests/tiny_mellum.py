"""Tiny stand-ins for the mellum cell's files, for tests on the CPU: the same
keys as ``configs/mellum2-12b-a2.5b-l12.json`` and
``traffic/code-backlog.json``, at a size a test run holds (two periods of the
published pattern, 8 experts 2 a token, a window of 16 positions over pages
of 8, so that most prompts run past the window and past several page
releases). Nothing here is ever measured.
"""

_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

TINY_MODEL = {
    "model_type": "mellum", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 8, "layer_types": _PERIOD * 2,
    "mlp_layer_types": ["sparse"] * 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 48,
    "norm_topk_prob": True, "sliding_window": 16, "rms_norm_eps": 1e-6,
    "hidden_act": "silu",
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
    },
    # the matrices' 0.02 widened by (2304 / 64)^1/2, the ratio of the
    # widths: a projection's output then has the published model's scale
    "initializer_range": 0.12,
}

_SIZES = dict(
    vocab_size=512, n_ctx=128, n_embd=64, n_layer=8, n_head=4, n_kv_head=2,
    attn_head_dim=32, n_inner=128, layer_types=tuple(_PERIOD * 2),
    sliding_window=16, n_routed_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=48, rope_theta=10000.0, rope_factor=4.0,
    rope_original_max_position=32, rope_attention_factor=1.1386294361119891)

TINY_CONFIG = {
    "name": "tiny-mellum",
    "reference": "mellum",
    "model": TINY_MODEL,
    "program": {
        "preset": "mellum2-12b-a2.5b-l12",
        "serve_overrides": dict(
            _SIZES, dtype="float32", param_dtype="bfloat16"),
        "serve_holds": {
            "n_embd": "hidden_size", "n_layer": "num_hidden_layers",
            "n_head": "num_attention_heads",
            "n_kv_head": "num_key_value_heads", "head_dim": "head_dim",
            "vocab_size": "vocab_size", "n_inner": "intermediate_size",
            "layer_norm_epsilon": "rms_norm_eps",
            "layer_types_list": "layer_types",
            "sliding_window": "sliding_window",
            "n_routed_experts": "num_experts",
            "num_experts_per_tok": "num_experts_per_tok",
            "moe_intermediate_size": "moe_intermediate_size",
            "norm_topk_prob": "norm_topk_prob",
            "activation_function": "hidden_act",
            "rope_parameters": "rope_parameters"},
    },
}

# prompts of one to four chunks of 16 with ragged final chunks (a window of
# 16: every prompt past the first chunk has released pages behind it), more
# requests than rows (every slot is reused), replies of several steps
TINY_SERVE = {
    "driver": "serve", "loop": "closed", "clients": 5,
    "requests_per_s_ceiling": 200, "cycle_requests": 12, "order_seed": 1,
    "prompt_tokens": {"median": 32, "sigma": 0.6, "min": 8, "max": 64},
    "new_tokens": {"median": 10, "sigma": 0.5, "min": 4, "max": 24},
    "sampled_share": 0.5, "temperature": 0.8, "top_k": 50,
    "engine": {"slots": 4, "max_len": 128, "page_size": 8,
               "prefill_chunk": 16},
    "warm_requests": 2, "warm_new_tokens": 2, "compare_requests": 4,
    "compare_sampled_requests": 4, "trace_seconds": 1, "ramp_s": 0.5,
}

TINY_BENCH = {
    "end_to_end": [
        {"name": "serve_tok_s", "unit": "tokens/s",
         "workloads": ["tiny-mellum.backlog"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}

# Limits for the TINY stand-in only, read on the CPU the way PERF.md section
# 2 reads the cell's own on the chip (the readings are in test_mellum.py's
# docstring). They say nothing about a cell.
TINY_LIMITS = {"served_logit_gap": 0.02, "sampled_topk_gap": 0.02}
