"""Tiny stand-ins for a cell's files, for tests on the CPU: the same keys as
the files under configs/, traffic/ and limits/, at a size a test run holds.
Nothing here is ever measured."""

TINY_MODEL = {
    "model_type": "gpt2", "vocab_size": 512, "n_positions": 128,
    "n_embd": 64, "n_layer": 2, "n_head": 2, "n_inner": None,
    "activation_function": "gelu_new", "layer_norm_epsilon": 1e-5,
    "initializer_range": 0.02,
}

TINY_CONFIG = {
    "name": "tiny",
    "reference": "gpt2",
    "model": TINY_MODEL,
    "program": {
        "preset": "gpt2",
        "train_overrides": {
            "dtype": "bfloat16", "attention_impl": "flash", "remat": "names",
            "logits_dtype": "bfloat16", "attn_pdrop": 0.0, "resid_pdrop": 0.0,
            "embd_pdrop": 0.0, "vocab_size": 512, "n_ctx": 128, "n_embd": 64,
            "n_layer": 2, "n_head": 2,
        },
        "train_holds": {"n_embd": "n_embd", "n_layer": "n_layer",
                        "n_head": "n_head", "vocab_size": "vocab_size",
                        "n_ctx": "n_positions"},
        "serve_overrides": {
            "dtype": "bfloat16", "param_dtype": "bfloat16", "attn_pdrop": 0.0,
            "resid_pdrop": 0.0, "embd_pdrop": 0.0, "vocab_size": 512,
            "n_ctx": 128, "n_embd": 64, "n_layer": 2, "n_head": 2,
        },
        "serve_holds": {"n_embd": "n_embd", "n_layer": "n_layer",
                        "n_head": "n_head", "vocab_size": "vocab_size"},
    },
}

# The serving stand-in draws its weights ten times wider: at 0.02 a model this
# small puts the last prompt token first by a wide margin at every position,
# and no precision could flip it.
TINY_SERVE_CONFIG = dict(
    TINY_CONFIG, model=dict(TINY_MODEL, initializer_range=0.2))

TINY_TRAIN = {
    "driver": "train", "batch": 8, "seq_len": 64,
    "data": {"vocab": 128, "p_follow": 0.7, "setup_steps": 8,
             "tokens_per_s_ceiling": 200000},
    "optimizer": {"learning_rate": 3e-4, "weight_decay": 0.1, "beta1": 0.9,
                  "beta2": 0.999, "eps": 1e-8, "lr_schedule": "cosine",
                  "min_lr_ratio": 0.1, "schedule_steps": 100000},
    "warm_steps": 1, "trace_seconds": 1, "reference_rows_per_block": 4,
}

TINY_BENCH = {
    "end_to_end": [
        {"name": "train_tok_s", "unit": "tokens/s/chip",
         "workloads": ["tiny.train"]},
        {"name": "serve_tok_s", "unit": "tokens/s",
         "workloads": ["tiny.serve", "tiny.backlog"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}

TINY_SERVE = {
    "driver": "serve", "loop": "open", "cycle_requests": 12, "cycle_s": 2.0,
    "order_seed": 1,
    "prompt_tokens": {"median": 24, "sigma": 0.6, "min": 4, "max": 64},
    "new_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
    "sampled_share": 0.5, "temperature": 0.8, "top_k": 50,
    "engine": {"slots": 4, "max_len": 128, "page_size": 16},
    "warm_requests": 2, "warm_new_tokens": 2, "compare_requests": 4,
    "compare_sampled_requests": 4, "trace_seconds": 1, "ramp_s": 1.0,
}

# The closed loop, as the backlog cell runs it; the pool is wide enough that
# the router's page gate (PERF.md section 7 row 0) stays out of a test.
TINY_BACKLOG = dict(
    {k: v for k, v in TINY_SERVE.items() if k != "cycle_s"}, loop="closed",
    clients=5, requests_per_s_ceiling=200, ramp_s=0.5,
    engine=dict(TINY_SERVE["engine"], pool_pages=2048))

# Limits for the TINY stand-ins only, read on the CPU over three seeds the way
# PERF.md section 2 reads the cells' own on the chip (training, on
# delta3_norm_gap: program <= 5.2e-3, float8 control >= 1.05e-2, and on
# grad1_diff_gap: program <= 3.1e-2, control >= 6.2e-2; serving, over five
# seeds of either loop: served_logit_gap program <= 5.9e-2, control >= 0.30;
# sampled_topk_gap program <= 3.5e-2, control >= 0.42). They say nothing
# about a cell.
TINY_TRAIN_LIMITS = {
    "loss1_gap": 1e-3, "loss2_gap": 1e-3, "loss3_gap": 1e-3,
    "grad1_norm_gap": 5e-2, "delta3_norm_gap": 8e-3, "grad1_diff_gap": 4.5e-2,
    "compiles_in_window": 0,
}
TINY_SERVE_LIMITS = {"served_logit_gap": 1.2e-1, "sampled_topk_gap": 1e-1}
