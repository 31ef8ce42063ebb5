"""Tiny stand-ins for the kimi_k2 cell's files, for tests on the CPU: the
same keys as ``configs/kimi-k2.5-ep32.json`` and ``traffic/agent-backlog
.json``, at a size a test run holds (unequal latent and head sizes, 1 dense
+ 2 expert layers, 16 experts top-4, 4 held). Nothing here is ever measured.
"""

TINY_MODEL = {
    "model_type": "kimi_k2", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 16,
    "v_head_dim": 20, "intermediate_size": 96, "moe_intermediate_size": 40,
    "n_shared_experts": 1, "n_routed_experts": 16, "num_experts_per_tok": 4,
    "n_routed_experts_held": 4, "expert_offset": 4,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-5, "rope_theta": 50000,
    "routed_scaling_factor": 2.827,
    "rope_scaling": {"factor": 64, "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1, "type": "yarn"},
    # weights ten times wider than the cell's 0.02, as tiny.py's serving
    # stand-in: at 0.02 a model this small barely moves its logits
    "initializer_range": 0.2,
}

_SIZES = dict(
    vocab_size=512, n_ctx=128, n_embd=64, n_layer=3, n_head=4, n_inner=96,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
    qk_rope_head_dim=16, v_head_dim=20, first_k_dense_replace=1,
    n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
    moe_intermediate_size=40, rope_original_max_position=32,
    experts_held=4, expert_offset=4)

TINY_CONFIG = {
    "name": "tiny-kimi-k2",
    "reference": "kimi_k2",
    "model": TINY_MODEL,
    "program": {
        "preset": "kimi-k2.5-ep32",
        "serve_overrides": dict(
            _SIZES, dtype="float32", param_dtype="bfloat16"),
        "serve_holds": {
            "n_embd": "hidden_size", "n_layer": "num_hidden_layers",
            "n_head": "num_attention_heads", "vocab_size": "vocab_size",
            "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
            "experts_held": "n_routed_experts_held",
            "expert_offset": "expert_offset",
            "routed_scaling_factor": "routed_scaling_factor"},
    },
}

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
         "workloads": ["tiny-kimi-k2.backlog"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}

# Limits for the TINY stand-in only, read on the CPU over five seeds the way
# PERF.md section 2 reads the cell's own on the chip. The stand-in computes
# in float32 over bfloat16 weights (in bfloat16 a model this small, with a
# quarter of its 16 experts held, reads 0.5-1.9 by picking another expert
# than the float32 reference where two scores nearly tie: the control's
# 2.3-3.5 is then not 2x away), so the program reads 0 on both; the float8
# control reads served_logit_gap >= 1.89, sampled_topk_gap >= 1.20; the
# planted faults read served_logit_gap >= 0.46. They say nothing about a cell.
TINY_LIMITS = {"served_logit_gap": 0.15, "sampled_topk_gap": 0.15}
