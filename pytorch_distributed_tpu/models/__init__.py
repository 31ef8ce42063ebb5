"""Model families: pure ``init(key, cfg) -> params`` / ``apply(params, ids, cfg)``."""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax

from pytorch_distributed_tpu.config import ModelConfig


class ModelApi(NamedTuple):
    init: Callable[[jax.Array, ModelConfig], dict]
    apply: Callable[..., jax.Array]
    # Phase functions — the same forward split at pipeline-stage boundaries
    # (embed | blocks | head), used by parallel/pipeline.py.
    embed: Callable[..., jax.Array]
    run_blocks: Callable[..., jax.Array]
    head: Callable[..., jax.Array]
    # (params) -> (head weight array, ops.losses layout tag): the LM-head
    # matrix the fused head+CE loss multiplies against — tied wte [V, E]
    # ("ve") for gpt2, untied lm_head [E, V] ("ev") for llama.
    head_weight: Callable[[dict], tuple[jax.Array, str]]
    # ln_f alone — head() minus the vocab matmul; what the fused head+CE
    # loss consumes on the pipeline path's last stage.
    final_norm: Callable[..., jax.Array]


def get_model(cfg: ModelConfig) -> ModelApi:
    if cfg.family == "gpt2":
        from pytorch_distributed_tpu.models import gpt2

        return ModelApi(
            gpt2.init, gpt2.apply, gpt2.embed, gpt2.run_blocks, gpt2.head,
            lambda params: (params["wte"], "ve"),
            gpt2.final_norm,
        )
    if cfg.family == "llama":
        from pytorch_distributed_tpu.models import llama

        return ModelApi(
            llama.init, llama.apply, llama.embed, llama.run_blocks,
            llama.head,
            lambda params: (params["lm_head"], "ev"),
            llama.final_norm,
        )
    if cfg.family == "kimi_k2":
        from pytorch_distributed_tpu.models import kimi_k2

        return ModelApi(
            kimi_k2.init, kimi_k2.apply, kimi_k2.embed, kimi_k2.run_blocks,
            kimi_k2.head,
            lambda params: (params["lm_head"], "ev"),
            kimi_k2.final_norm,
        )
    if cfg.family == "granitemoehybrid":
        from pytorch_distributed_tpu.models import granitemoehybrid as gmh

        return ModelApi(
            gmh.init, gmh.apply, gmh.embed, gmh.run_blocks, gmh.head,
            lambda params: (params["wte"], "ve"),
            gmh.final_norm,
        )
    if cfg.family == "mellum":
        from pytorch_distributed_tpu.models import mellum

        return ModelApi(
            mellum.init, mellum.apply, mellum.embed, mellum.run_blocks,
            mellum.head,
            lambda params: (params["lm_head"], "ev"),
            mellum.final_norm,
        )
    raise KeyError(f"unknown model family {cfg.family!r}")
