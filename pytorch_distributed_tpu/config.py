"""Typed configuration for model / data / training / mesh.

The reference has no config system — hyperparameters are hardcoded constants in
each entry script (reference train_baseline.py:24-31, train_ddp.py:59-64,
train_fsdp.py:98-103) and model shape comes from HF AutoConfig
(train_baseline.py:24). We replace that with small frozen dataclasses
(SURVEY.md §5.6): enough structure to be testable, no Hydra-scale machinery.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class ModelConfig:
    """Transformer architecture config.

    Field names follow GPT-2 conventions (reference model/my_gpt2.py uses the
    HF GPT2Config fields n_embd/n_head/n_layer/n_ctx, vocab_size,
    activation_function, layer_norm_epsilon, *_pdrop).
    """

    # Family: "gpt2" (learned positions, LayerNorm, gelu MLP, tied head),
    # "llama" (RoPE, RMSNorm, SwiGLU, untied head) — SURVEY.md §7 stage 8 /
    # BASELINE.md configs 4-5 — or "kimi_k2" (the DeepSeek-V3 block as
    # Kimi-K2.5 publishes it: latent attention, leading dense layers, then
    # sigmoid-routed dropless experts beside a shared one, YaRN; served
    # only — models/kimi_k2.py; its fields are at the end of this class) —
    # or "granitemoehybrid" (Granite 4.0-H: Mamba-2 layers and a few
    # attention layers in a repeating pattern, no position encoding, the
    # four Granite multipliers; served only —
    # models/granitemoehybrid.py; fields after kimi_k2's) — or "mellum"
    # (Mellum 2: grouped-query attention whose layers are sliding-window
    # or full by ``layer_types``, each kind with its own rotary table, then
    # softmax-routed dropless experts with no shared one; served only —
    # models/mellum.py; fields last).
    family: str = "gpt2"

    vocab_size: int = 50257
    n_ctx: int = 1024  # max sequence length (positional table size for gpt2)
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    # Defaults to n_head (no GQA); llama-family configs may set fewer KV heads.
    n_kv_head: int | None = None
    # MLP hidden size; None → 4*n_embd (gpt2) or the llama 8/3 rule rounded.
    n_inner: int | None = None

    activation_function: str = "gelu_new"
    layer_norm_epsilon: float = 1e-5
    # RoPE base frequency (llama family only).
    rope_theta: float = 10000.0

    # Dropout probabilities (reference my_gpt2.py:25-26,152 — attn, resid, embd).
    embd_pdrop: float = 0.1
    attn_pdrop: float = 0.1
    resid_pdrop: float = 0.1
    # Attention dropout under explicit tensor parallelism: "reject" (default
    # — attn_pdrop > 0 with a tensor axis fails at build time, preserving
    # the bitwise single-device parity contract) or "folded" (opt-in: each
    # tensor shard folds its axis index into the attention-dropout key, so
    # its local heads draw INDEPENDENT masks — statistically equivalent to
    # the single-device draw, NOT bitwise-identical; embd/resid dropout
    # keys stay replicated so non-attention activations remain
    # bitwise-replicated across shards).
    tensor_dropout: str = "reject"

    # Numerics: params kept in param_dtype, activations computed in dtype.
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # Dtype the LM head emits. float32 matches the reference's fp32 logits;
    # "bfloat16" halves the [B, T, V] HBM traffic through the head + loss
    # (the MXU still accumulates in f32; cross-entropy upcasts to f32).
    logits_dtype: str = "float32"

    # Fuse the LM-head matmul into the cross-entropy loss
    # (ops/losses.linear_cross_entropy): logits are produced and consumed in
    # vocab blocks, so the [B, T, V] logits tensor never exists — the
    # largest activation in the step (823 MB bf16 at GPT-2 bench shapes,
    # 2.1 GB at llama-3 vocabulary). Honored by EVERY training path:
    # trainer/pjit, explicit (shard_map), and pipeline (the fusion lands on
    # the last stage, which owns the head). apply() itself still returns
    # logits unless called with return_hidden=True.
    fused_head_ce: bool = False

    # Selective activation checkpointing per block (reference my_gpt2.py:145,
    # 175-183 + pytorch_utils.py:5-17): save compute-intensive matmul outputs,
    # recompute the rest. One of: "none", "full", "dots", "dots_no_batch",
    # "names" (recommended: saves the tagged projection outputs and the
    # flash kernel's o/l/m, but never the quadratic score matrix), or
    # "flash" (ONLY the flash o/l/m — the long-context policy for
    # regimes where per-layer projection saves OOM HBM; see ops/remat.py).
    remat: str = "dots"
    # Unroll factor for the scan-over-layers (1 = no unroll). Unrolling
    # lets XLA fuse/pipeline across layer boundaries (e.g. merge adjacent
    # activation-save dynamic-update-slices) at the cost of HLO size.
    scan_unroll: int = 1

    # Attention implementation: "naive" (materialises the T×T score matrix like
    # reference my_gpt2.py:60-77) or "flash" (blockwise online-softmax /
    # Pallas). Whether the sequence IS sharded is a parallelism-layer
    # concern (parallel/); seq_impl picks the context-parallel technique
    # when it is: "ring" (ppermute KV ring, works for any head count) or
    # "ulysses" (head/sequence all-to-all, needs seq | n_head and
    # seq | kv_heads).
    attention_impl: str = "naive"
    seq_impl: str = "ring"

    # Mixture-of-Experts (ops/moe.py): 0 = dense MLP (reference behavior);
    # >0 replaces each block's MLP with n_experts expert MLPs and a top-1
    # router — dense-style experts for gpt2, SwiGLU experts for llama.
    # Aux-loss coefficient weights the Switch load-balancing term added to
    # the training objective.
    n_experts: int = 0
    expert_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    # Router top-k: 1 = Switch (argmax expert, raw-prob gate); k>1 =
    # GShard-style (k best experts, renormalised gates).
    moe_top_k: int = 1
    # Token dispatch: "einsum" (one-hot [A,X,C] tensor — exact-parity
    # path), "sort" (sort/segment path, O(A·D) memory — the at-scale
    # form), "auto" picks by dispatch-tensor size (ops/moe.py).
    moe_dispatch: str = "auto"

    # -- family "kimi_k2": the published config.json keys ------------------
    # (hidden_size, num_hidden_layers, num_attention_heads,
    # intermediate_size, rms_norm_eps, rope_theta and vocab_size are
    # n_embd, n_layer, n_head, n_inner, layer_norm_epsilon, rope_theta and
    # vocab_size above.) Latent attention: queries through a rank-
    # q_lora_rank bottleneck to n_head heads of qk_nope_head_dim +
    # qk_rope_head_dim; keys and values from ONE cached latent of
    # kv_lora_rank numbers plus one shared rotated key of qk_rope_head_dim.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Layers 0..first_k_dense_replace-1 carry one SwiGLU of n_inner; each
    # later layer n_routed_experts experts of moe_intermediate_size (the
    # router scores all of them with a sigmoid and picks
    # num_experts_per_tok by score + selection bias; gates are the picked
    # scores renormalised and scaled by routed_scaling_factor) beside
    # n_shared_experts shared ones every token passes through.
    first_k_dense_replace: int = 0
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    # rope_scaling (type "yarn"), flat because the config is hashed.
    rope_factor: float = 1.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # The share of an expert-parallel deployment this process holds:
    # experts expert_offset .. expert_offset + experts_held - 1 of every
    # expert layer (0 held = all of them). The router still scores all
    # n_routed_experts; what the absent ones would add is left out.
    experts_held: int = 0
    expert_offset: int = 0

    # -- family "granitemoehybrid": the published config.json keys ---------
    # (hidden_size, num_hidden_layers, num_attention_heads,
    # num_key_value_heads, rms_norm_eps and vocab_size are n_embd, n_layer,
    # n_head, n_kv_head, layer_norm_epsilon and vocab_size above.) Layer i
    # mixes tokens by layer_types[i], "mamba" or "attention"; the pattern is
    # one period repeated (models/granitemoehybrid.layer_period). A Mamba-2
    # mixer has mamba_n_heads heads of mamba_d_head with a state of
    # mamba_d_state per head entry, B and C shared by the heads of one of
    # mamba_n_groups groups, a depthwise causal convolution of mamba_d_conv
    # taps over [x | B | C], and is computed mamba_chunk_size tokens at a
    # time. Every layer's feed-forward is one SwiGLU of
    # shared_intermediate_size (num_local_experts is 0 where this is
    # served). position_embedding_type "nope": no position encoding at all.
    layer_types: tuple[str, ...] = ()
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    shared_intermediate_size: int = 0
    position_embedding_type: str = "nope"
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # -- family "mellum": the published config.json keys --------------------
    # (hidden_size, num_hidden_layers, num_attention_heads,
    # num_key_value_heads, rms_norm_eps, vocab_size, num_experts,
    # num_experts_per_tok and moe_intermediate_size are n_embd, n_layer,
    # n_head, n_kv_head, layer_norm_epsilon, vocab_size, n_routed_experts,
    # num_experts_per_tok and moe_intermediate_size above; layer_types is
    # granitemoehybrid's field with the kinds "sliding_attention" /
    # "full_attention".) A head is attn_head_dim wide whatever n_embd /
    # n_head says (0: that quotient). A sliding layer's query at position i
    # attends keys i - sliding_window + 1 .. i and rotates by plain
    # rope_theta; a full layer attends all keys <= i and rotates by YaRN
    # (rope_factor, rope_original_max_position, rope_beta_fast / _slow
    # above) with cos and sin times rope_attention_factor. The router is a
    # softmax over all n_routed_experts; the num_experts_per_tok largest are
    # chosen and, with norm_topk_prob, renormalised to sum to 1.
    attn_head_dim: int = 0
    sliding_window: int = 0
    rope_attention_factor: float = 1.0
    norm_topk_prob: bool = True

    def __post_init__(self) -> None:
        if self.n_embd % self.n_head != 0:
            raise ValueError(
                f"n_embd={self.n_embd} not divisible by n_head={self.n_head}"
            )
        if self.family not in ("gpt2", "llama", "kimi_k2",
                               "granitemoehybrid", "mellum"):
            raise ValueError(f"unknown model family: {self.family!r}")
        if self.family == "mellum":
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if not (
                len(self.layer_types) == self.n_layer
                and set(self.layer_types)
                <= {"sliding_attention", "full_attention"}
                and ("sliding_attention" not in self.layer_types
                     or self.sliding_window > 0)
                and 0 < self.num_experts_per_tok <= self.n_routed_experts
                and self.n_head % self.kv_heads == 0
                and not self.n_experts
            ):
                raise ValueError(
                    "mellum: need one of 'sliding_attention' / "
                    "'full_attention' for each of the n_layer layers, a "
                    "sliding_window where a layer slides, 0 < "
                    "num_experts_per_tok <= n_routed_experts, whole groups "
                    "of query heads a kv head, and n_experts 0 (that "
                    "selects the capacity-routed layer)"
                )
        if self.family == "granitemoehybrid":
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if not (
                len(self.layer_types) == self.n_layer
                and set(self.layer_types) <= {"mamba", "attention"}
                and self.mamba_expand * self.n_embd
                == self.mamba_n_heads * self.mamba_d_head
                and self.mamba_n_heads % self.mamba_n_groups == 0
                and self.position_embedding_type == "nope"
            ):
                raise ValueError(
                    "granitemoehybrid: need one of 'mamba' / 'attention' "
                    "for each of the n_layer layers, mamba_expand * n_embd "
                    "== mamba_n_heads * mamba_d_head, whole groups of "
                    "heads, and position_embedding_type 'nope' (rotary "
                    "attention layers are not built)"
                )
        if self.family == "kimi_k2":
            held = self.experts_held or self.n_routed_experts
            if not (
                0 < self.num_experts_per_tok <= self.n_routed_experts
                and 0 <= self.expert_offset
                and self.expert_offset + held <= self.n_routed_experts
                and 0 <= self.first_k_dense_replace <= self.n_layer
            ):
                raise ValueError(
                    "kimi_k2: need 0 < num_experts_per_tok <= "
                    "n_routed_experts, the held experts "
                    f"[{self.expert_offset}, {self.expert_offset + held}) "
                    f"inside the {self.n_routed_experts} routed ones, and "
                    "first_k_dense_replace <= n_layer"
                )
            if self.n_experts:
                raise ValueError(
                    "kimi_k2 routes through its own dropless layer "
                    "(n_routed_experts); n_experts selects the capacity-"
                    "routed one and must stay 0"
                )
        # Ring attention is selected by the parallelism layer (seq_axis in
        # ops/attention.py), not by this per-model switch.
        if self.attention_impl not in ("naive", "flash"):
            raise ValueError(
                f"unknown attention_impl: {self.attention_impl!r} "
                "(implemented: naive, flash)"
            )
        if self.seq_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown seq_impl: {self.seq_impl!r} "
                "(implemented: ring, ulysses)"
            )
        if self.n_experts and self.family not in ("gpt2", "llama"):
            raise ValueError(
                "MoE (n_experts > 0) requires the gpt2 or llama family"
            )
        if self.n_experts and not (1 <= self.moe_top_k <= self.n_experts):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} out of range for "
                f"n_experts={self.n_experts}"
            )
        if self.moe_dispatch not in ("auto", "einsum", "sort"):
            raise ValueError(
                f"unknown moe_dispatch: {self.moe_dispatch!r} "
                "(implemented: auto, einsum, sort)"
            )
        if self.scan_unroll < 1:
            raise ValueError(
                f"scan_unroll must be >= 1, got {self.scan_unroll}"
            )
        if self.tensor_dropout not in ("reject", "folded"):
            raise ValueError(
                f"unknown tensor_dropout: {self.tensor_dropout!r} "
                "(implemented: reject, folded)"
            )

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.n_embd // self.n_head

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head if self.n_kv_head is not None else self.n_head

    @property
    def inner_dim(self) -> int:
        if self.n_inner is not None:
            return self.n_inner
        if self.family == "llama":
            # Llama FFN rule: 2/3 * 4d, rounded up to a multiple of 256.
            return ((8 * self.n_embd // 3) + 255) // 256 * 256
        return 4 * self.n_embd

    @property
    def layer_types_list(self) -> list[str]:
        """``layer_types`` as the list a config.json holds (the field is a
        tuple because the config is hashed)."""
        return list(self.layer_types)

    @property
    def rope_parameters(self) -> dict[str, dict[str, Any]]:
        """The rotary fields as a mellum config.json nests them under
        ``rope_parameters``, a section a layer kind (for ``serve_holds``)."""
        return {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": self.rope_theta,
                "factor": self.rope_factor,
                "original_max_position_embeddings":
                    self.rope_original_max_position,
                "beta_fast": self.rope_beta_fast,
                "beta_slow": self.rope_beta_slow,
                "attention_factor": self.rope_attention_factor,
            },
            "sliding_attention": {
                "rope_type": "default", "rope_theta": self.rope_theta,
            },
        }

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# Preset shapes. gpt2/gpt2-medium/large/xl match HF AutoConfig presets the
# reference pulls (train_baseline.py:24 uses "gpt2-large", memory_analysis.py
# uses "gpt2"). gpt2-1p3b is the BASELINE.md config-3 size (GPT-3 XL shape).
_GPT2_PRESETS: dict[str, dict[str, int]] = {
    "gpt2": dict(n_embd=768, n_layer=12, n_head=12),  # 124M
    "gpt2-medium": dict(n_embd=1024, n_layer=24, n_head=16),  # 355M
    "gpt2-large": dict(n_embd=1280, n_layer=36, n_head=20),  # 774M
    "gpt2-xl": dict(n_embd=1600, n_layer=48, n_head=25),  # 1.56B
    "gpt2-1p3b": dict(n_embd=2048, n_layer=24, n_head=16),  # 1.31B
    # Smoke-test shape for CPU runs and CLI examples.
    "tiny": dict(
        vocab_size=256, n_ctx=128, n_embd=64, n_layer=2, n_head=4,
        dtype="float32",
    ),
}

_LLAMA_PRESETS: dict[str, dict[str, Any]] = {
    # Llama-3.2-1B / Llama-3.1-8B shapes (BASELINE.md configs 4-5).
    "llama3-1b": dict(
        vocab_size=128256, n_ctx=8192, n_embd=2048, n_layer=16, n_head=32,
        n_kv_head=8, n_inner=8192, rope_theta=500000.0,
    ),
    "llama3-8b": dict(
        vocab_size=128256, n_ctx=8192, n_embd=4096, n_layer=32, n_head=32,
        n_kv_head=8, n_inner=14336, rope_theta=500000.0,
    ),
}


_KIMI_K2_PRESETS: dict[str, dict[str, Any]] = {
    # One chip's share of Kimi-K2.5 in a 32-chip decode deployment
    # (https://huggingface.co/moonshotai/Kimi-K2.5/blob/main/config.json):
    # every width as published; 1 dense + 4 expert layers of the 61, 12 of
    # the 384 routed experts, 20480 of the 163840 vocabulary rows
    # (perfbench/configs/kimi-k2.5-ep32.json states the cut).
    "kimi-k2.5-ep32": dict(
        vocab_size=20480, n_ctx=4096, n_embd=7168, n_layer=5, n_head=64,
        n_inner=18432, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense_replace=1, n_routed_experts=384,
        num_experts_per_tok=8, n_shared_experts=1,
        moe_intermediate_size=2048, routed_scaling_factor=2.827,
        rope_theta=50000.0, rope_factor=64.0,
        rope_original_max_position=4096, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        experts_held=12, expert_offset=0,
    ),
}


_GRANITEMOEHYBRID_PRESETS: dict[str, dict[str, Any]] = {
    # granite-4.0-h-micro whole (https://huggingface.co/ibm-granite/
    # granite-4.0-h-micro/blob/main/config.json): 40 layers, the period
    # [mamba x5, attention, mamba x4] four times; nothing is cut
    # (perfbench/configs/granite-4.0-h-micro.json).
    "granite-4.0-h-micro": dict(
        vocab_size=100352, n_ctx=131072, n_embd=2048, n_layer=40,
        n_head=32, n_kv_head=8,
        layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4,
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
        mamba_chunk_size=256, shared_intermediate_size=8192,
        position_embedding_type="nope", embedding_multiplier=12.0,
        attention_multiplier=0.015625, residual_multiplier=0.22,
        logits_scaling=8.0,
    ),
}


_MELLUM_PRESETS: dict[str, dict[str, Any]] = {
    # Stage 1 of Mellum2-12B-A2.5B-Instruct cut over depth
    # (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/
    # main/config.json): the first 12 of 28 layers, three whole periods
    # [sliding x3, full]; every expert, head, width and vocabulary row as
    # published (perfbench/configs/mellum2-12b-a2.5b-l12.json states the cut).
    "mellum2-12b-a2.5b-l12": dict(
        vocab_size=98304, n_ctx=131072, n_embd=2304, n_layer=12, n_head=32,
        n_kv_head=4, attn_head_dim=128, n_inner=7168,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 3,
        sliding_window=1024, n_routed_experts=64, num_experts_per_tok=8,
        moe_intermediate_size=896, norm_topk_prob=True,
        rope_theta=500000.0, rope_factor=16.0,
        rope_original_max_position=8192, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_attention_factor=1.2772588722239782,
    ),
}


def model_config(name: str, **overrides: Any) -> ModelConfig:
    """Look up a preset by name (the TPU-native analogue of
    ``AutoConfig.from_pretrained`` in reference train_baseline.py:24)."""
    if name in _GPT2_PRESETS:
        base: dict[str, Any] = dict(family="gpt2", **_GPT2_PRESETS[name])
    elif name in _LLAMA_PRESETS:
        base = dict(
            family="llama",
            activation_function="silu",
            layer_norm_epsilon=1e-5,
            embd_pdrop=0.0,
            attn_pdrop=0.0,
            resid_pdrop=0.0,
            **_LLAMA_PRESETS[name],
        )
    elif name in _KIMI_K2_PRESETS:
        base = dict(
            family="kimi_k2",
            activation_function="silu",
            layer_norm_epsilon=1e-5,
            embd_pdrop=0.0,
            attn_pdrop=0.0,
            resid_pdrop=0.0,
            **_KIMI_K2_PRESETS[name],
        )
    elif name in _GRANITEMOEHYBRID_PRESETS:
        base = dict(
            family="granitemoehybrid",
            activation_function="silu",
            layer_norm_epsilon=1e-5,
            embd_pdrop=0.0,
            attn_pdrop=0.0,
            resid_pdrop=0.0,
            **_GRANITEMOEHYBRID_PRESETS[name],
        )
    elif name in _MELLUM_PRESETS:
        base = dict(
            family="mellum",
            activation_function="silu",
            layer_norm_epsilon=1e-6,
            embd_pdrop=0.0,
            attn_pdrop=0.0,
            resid_pdrop=0.0,
            **_MELLUM_PRESETS[name],
        )
    else:
        known = [
            *sorted(_GPT2_PRESETS), *sorted(_LLAMA_PRESETS),
            *sorted(_KIMI_K2_PRESETS), *sorted(_GRANITEMOEHYBRID_PRESETS),
            *sorted(_MELLUM_PRESETS),
        ]
        raise KeyError(f"unknown model preset {name!r}; known: {known}")
    base.update(overrides)
    return ModelConfig(**base)


@dataclass(frozen=True)
class DataConfig:
    """Data pipeline config (reference data/data_loader.py defaults)."""

    data_dir: str = ".cache/data/fineweb10B"
    batch_size: int = 8  # per-process micro-batch B (reference :83)
    seq_len: int = 1024  # T (reference :84)
    num_train_files: int = 10  # reference train_baseline.py:50
    source: str = "fineweb10B"  # or "synthetic" for tests / zero-egress runs
    synthetic_tokens: int = 2_000_000
    seed: int = 42


@dataclass(frozen=True)
class TrainConfig:
    """Training-loop config (reference train_baseline.py:26-31,61-64 and
    train/trainer.py:9-47)."""

    global_batch_size: int = 32
    micro_batch_size: int = 8
    num_steps: int = 20
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    grad_clip_norm: float | None = None
    # Exclude rank<2 params (norm scales, biases) from weight decay — the
    # modern pretraining convention. Default OFF: the reference decays
    # every param (torch AdamW default, train_baseline.py:61).
    decay_exclude_1d: bool = False
    # Gradient-accumulation buffer dtype (A > 1 only; honoured by the
    # single-device, pjit and explicit paths — the pipeline path's
    # accumulation dtype follows AD). "float32" (default) is the safe
    # convention; "bfloat16" halves the accumulator HBM — the buffer that
    # decides whether a 774M model accumulates on one 16 GB chip at all
    # (see scripts/_common.py --param-dtype help). bf16 accumulation
    # loses ~8 mantissa bits across the A partial sums; acceptable at
    # small A, measure before using at large A.
    accum_dtype: str = "float32"
    # Cosine anneal to min_lr_ratio * learning_rate over num_steps
    # (reference train_baseline.py:62-64: CosineAnnealingLR eta_min=0.1*lr).
    lr_schedule: str = "cosine"
    min_lr_ratio: float = 0.1
    warmup_steps: int = 0

    seed: int = 42
    log_every_n_steps: int = 10
    save_every_n_steps: int | None = None
    checkpoint_dir: str = "checkpoints"
    # Retain only the newest N checkpoints (None = keep all, the
    # reference's behavior). Pruning runs on process 0 after each
    # successful save. Validated at construction (grad_accum_steps-style
    # late failures would kill a run at its first save).
    keep_checkpoints: int | None = None
    # Overlap checkpoint writes with training (orbax AsyncCheckpointer):
    # the device arrays are snapshotted at the save step, serialization
    # runs in background threads, and the checkpoint becomes visible at
    # the next save / end of training (train/checkpoint.py
    # save_checkpoint_async). Off = the reference's blocking-save model.
    async_checkpoint: bool = False

    def __post_init__(self) -> None:
        if self.keep_checkpoints is not None and self.keep_checkpoints < 1:
            raise ValueError(
                f"keep_checkpoints must be >= 1 or None, got "
                f"{self.keep_checkpoints}"
            )
        if self.accum_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown accum_dtype: {self.accum_dtype!r} "
                "(implemented: float32, bfloat16)"
            )
        if self.anomaly_guard:
            # Late guard failures would kill a run at its first anomaly;
            # validate the policy here (GuardConfig re-validates the
            # traced parameters).
            from pytorch_distributed_tpu.train.guard import GuardConfig

            GuardConfig(
                spike_factor=self.guard_spike_factor,
                ema_decay=self.guard_ema_decay,
                warmup_steps=self.guard_warmup_steps,
                rollback_after=self.guard_rollback_after,
            )
            if self.guard_max_rollbacks < 1:
                raise ValueError(
                    f"guard_max_rollbacks must be >= 1, got "
                    f"{self.guard_max_rollbacks}"
                )
    # Traced anomaly guard (train/guard.py): a non-finite loss/grad
    # sentinel + EMA loss-spike check + corrupt-token-id check computed
    # INSIDE the compiled step. On anomaly the update is a traced no-op
    # (params/opt_state carried unchanged) and counters ride
    # TrainState.guard — zero host syncs per step, zero recompiles. The
    # host reads the counters at the existing log-window sync; after
    # guard_rollback_after CONSECUTIVE anomalies it rolls back to the
    # last good checkpoint (see docs/ROBUSTNESS.md §9).
    anomaly_guard: bool = False
    guard_spike_factor: float = 3.0
    guard_ema_decay: float = 0.98
    guard_warmup_steps: int = 10
    # Consecutive anomalies before the host rolls back (None: skip-only —
    # anomalous updates are dropped but training never rewinds).
    guard_rollback_after: int | None = 3
    # Hard bound on rollbacks per train() call: a persistently anomalous
    # run fails loudly instead of thrashing forever.
    guard_max_rollbacks: int = 8
    # On rollback, do NOT rewind the data stream: the window between the
    # last checkpoint and the rollback is dropped (the policy for
    # PERSISTENT data corruption — deterministic replay would hit the
    # same bad batches again). Off (default): replay the window, the
    # right call for transient faults (bit-identical recovery).
    guard_skip_window: bool = False
    # Optional JSONL metrics sink: every logged window (step/loss/lr/
    # elapsed) is appended as one JSON object — machine-readable run
    # history beyond the reference's stdout prints (process 0 only under
    # the distributed trainer).
    metrics_path: str | None = None
    # Graceful preemption (TPU pods get reclaimed): on SIGTERM/SIGINT the
    # train loop finishes the in-flight step, writes a checkpoint (with
    # the data-stream position), and returns — so --resume continues the
    # run exactly. Opt-in; recovery story beyond the reference's plain
    # checkpoint cadence (SURVEY.md §5.3).
    save_on_preemption: bool = False
    # Multi-host: how often (in optimizer steps) processes agree on a stop
    # decision. Each sync is a host-blocking process_allgather; 1 = every
    # step (tightest preemption response), N amortises the sync cost at
    # the price of up to N-1 extra steps after the signal. Signals landing
    # between syncs are deferred to the next sync so every process reaches
    # the same decision at the same step. Multi-host preemption requires
    # lockstep loaders (DistributedTokenShardLoader): all processes must
    # exhaust data at the same iteration or ANY collective — including the
    # train step itself — deadlocks.
    preemption_sync_every_n_steps: int = 1

    def grad_accum_steps(self, data_parallel_size: int = 1) -> int:
        """Micro-batches per optimizer step. Single-device rule
        (reference train/trainer.py:31-34) and the distributed rule
        global // (micro * world) (reference train/distributed_trainer.py:84-88)."""
        denom = self.micro_batch_size * data_parallel_size
        if self.global_batch_size % denom != 0:
            raise ValueError(
                f"global_batch_size={self.global_batch_size} must be divisible "
                f"by micro_batch_size*dp={denom}"
            )
        return self.global_batch_size // denom


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh / parallelism config (SURVEY.md §2.2, §5.8).

    Axes follow the scaling-book convention: data (DP replicas), fsdp
    (parameter/grad/opt-state sharding), tensor (TP), seq (sequence/context
    parallelism for ring attention), pipe (pipeline stages — GPipe-style
    layer partitioning, parallel/pipeline.py). Sizes of 1 collapse the axis.
    """

    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    pipe: int = 1
    # Expert parallelism (MoE): expert weights shard over this axis and the
    # batch shards over it too (it is a data axis for non-expert params);
    # all_to_all moves token slots to their expert's owner (ops/moe.py).
    expert: int = 1

    # FSDP sharding strategy, mirroring reference train_fsdp.py:49-59
    # (plus the ZeRO-1 level torch FSDP lacks):
    #   "full_shard"     — params+grads+opt sharded (ZeRO-3)
    #   "shard_grad_op"  — grads+opt sharded, params replicated (ZeRO-2)
    #   "shard_opt"      — opt sharded only; grads all-reduced replicated,
    #                      each shard updates its slice, updated params
    #                      re-gathered (ZeRO-1)
    #   "no_shard"       — DDP-equivalent
    strategy: str = "full_shard"

    # Pipeline schedule (pipe > 1): "gpipe" (backward by AD transposition)
    # or "1f1b" (hand-scheduled PipeDream-flush — activation stash bounded
    # at pipe slots instead of the microbatch count; parallel/pipeline.py).
    pipe_schedule: str = "gpipe"

    # Latency-hiding schedule knobs for the explicit (shard_map) path
    # (parallel/explicit.py; ops/layer_scan.py):
    #
    # prefetch_buffers (ZeRO-3/full_shard only): how many EXTRA layers'
    # params may be in flight beyond the one being computed. 0 = the
    # just-in-time schedule (gather layer l inside layer l's scan body —
    # compute stalls on every gather). N > 0 restructures the layer scan
    # into windows of N+1 layers whose all_gathers are all issued before
    # the window's first block runs, so layer l+1's gather overlaps layer
    # l's compute (and the rematted backward re-gathers a whole window up
    # front the same way, letting the AD-transposed reduce-scatters
    # interleave with the remaining backward compute). SOFT hint: the
    # effective window is the largest divisor of n_layer <= N+1. Costs
    # N extra layers' worth of live gathered params in HBM.
    prefetch_buffers: int = 0
    # rs_buckets (ZeRO-2/shard_grad_op only): when > 0, the boundary
    # per-leaf gradient psum_scatters are coalesced into ~rs_buckets
    # bucketed collectives (flattened + concatenated per dtype/vma group,
    # parallel/zero.scatter_grads_bucketed) — fewer, larger transfers
    # that amortise per-collective latency and let XLA pipeline buckets
    # against each other. 0 = per-leaf scatters (the teaching layout).
    rs_buckets: int = 0

    axis_order: tuple[str, ...] = (
        "pipe", "data", "fsdp", "expert", "seq", "tensor"
    )

    # Device subset for this mesh: the process-local ``jax.devices()``
    # ids this mesh builds over, in mesh order. None keeps the historic
    # behaviour (first ``num_devices`` of ``jax.devices()``). This is
    # how a serving fleet pins each replica to its OWN slice of the
    # machine (e.g. 4 replicas x TP=2 over 8 devices) instead of every
    # replica time-slicing device 0 — the mesh is otherwise identical,
    # so programs, shardings, and pinned collective budgets are
    # untouched by placement.
    device_ids: tuple[int, ...] | None = None

    def __post_init__(self) -> None:
        if self.device_ids is not None:
            ids = tuple(int(d) for d in self.device_ids)
            object.__setattr__(self, "device_ids", ids)
            if len(set(ids)) != len(ids):
                raise ValueError(
                    f"device_ids must be unique, got {ids}"
                )
            if len(ids) != self.num_devices:
                raise ValueError(
                    f"device_ids has {len(ids)} entries but the mesh "
                    f"needs {self.num_devices} devices"
                )
        if self.strategy not in (
            "full_shard", "shard_grad_op", "shard_opt", "no_shard"
        ):
            raise ValueError(f"unknown FSDP strategy: {self.strategy!r}")
        if self.pipe_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"unknown pipe_schedule: {self.pipe_schedule!r} "
                "(implemented: gpipe, 1f1b)"
            )
        if self.prefetch_buffers < 0:
            raise ValueError(
                f"prefetch_buffers must be >= 0, got {self.prefetch_buffers}"
            )
        if self.rs_buckets < 0:
            raise ValueError(
                f"rs_buckets must be >= 0, got {self.rs_buckets}"
            )

    @property
    def num_devices(self) -> int:
        return (
            self.data * self.fsdp * self.tensor * self.seq * self.pipe
            * self.expert
        )

    @property
    def shape(self) -> dict[str, int]:
        return {ax: getattr(self, ax) for ax in self.axis_order}


@dataclass(frozen=True)
class ExperimentConfig:
    """Bundle of everything an entry point needs."""

    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
