"""Memory accounting: analytic breakdown + measured device stats + snapshot.

Capability twin of reference assignment0/memory_analysis.py:
- analytic fp32 breakdown params/grads/Adam-moments (P*4 + P*4 + 2*P*4 bytes,
  reference :12-52), extended with an activation estimate that understands
  our remat modes;
- empirical measurement (reference :105-110 memory_allocated/reserved) via
  ``device.memory_stats()`` (TPU: bytes_in_use / peak_bytes_in_use);
- allocation snapshot for offline viewing (reference :112-117 dumps a pickle
  for pytorch.org/memory_viz) via
  ``jax.profiler.save_device_memory_profile`` (pprof format).
"""

from __future__ import annotations

from pathlib import Path

import jax
import numpy as np

from pytorch_distributed_tpu.config import ModelConfig


def _model_param_count(cfg: ModelConfig) -> int:
    from pytorch_distributed_tpu.models import get_model

    shapes = jax.eval_shape(
        lambda k: get_model(cfg).init(k, cfg), jax.random.key(0)
    )
    return int(
        sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    )


def activation_bytes_estimate(
    cfg: ModelConfig, batch_size: int, seq_len: int
) -> int:
    """Rough per-step live-activation bytes under our remat policy.

    With per-block remat saving dot outputs ("dots"), the dominant saved
    tensors per layer are the block I/O plus matmul outputs
    (qkv 3E, attn-out E, c_fc F, c_proj E per token); without remat, add the
    attention score matrices (H*T^2) and softmax outputs.
    """
    act_itemsize = 2 if cfg.dtype == "bfloat16" else 4
    b, t, e, f, h, l = (
        batch_size, seq_len, cfg.n_embd, cfg.inner_dim, cfg.n_head,
        cfg.n_layer,
    )
    per_layer_tokens = b * t * (e + 3 * e + e + f + e)  # x, qkv, attn, fc, proj
    if cfg.remat == "none":
        per_layer_tokens += b * t * (2 * e)  # ln outputs
        score_bytes = l * b * h * t * t * 4 * 2  # scores+softmax in f32
    elif cfg.remat == "full":
        per_layer_tokens = b * t * e  # only block inputs saved
        score_bytes = 0
    elif cfg.remat == "flash":
        # Only the flash kernel's (o, l, m) per layer — the long-context
        # policy; the o save is E per token, l/m are f32 [B, H, T].
        per_layer_tokens = b * t * (e + e)  # block input + o
        score_bytes = l * b * h * t * 4 * 2  # l and m, f32
    else:  # dots / dots_no_batch / names
        score_bytes = 0
    logits_bytes = (
        0 if cfg.fused_head_ce else b * t * cfg.vocab_size * 4
    )
    return l * per_layer_tokens * act_itemsize + score_bytes + logits_bytes


def analytic_memory_breakdown(
    cfg: ModelConfig,
    *,
    batch_size: int = 8,
    seq_len: int = 1024,
    optimizer: str = "adamw",
) -> dict:
    """Estimated training-memory breakdown in bytes
    (reference memory_analysis.py:12-52, defaults :136-138: gpt2-small,
    B=8, T=1024)."""
    n = _model_param_count(cfg)
    param_itemsize = 2 if cfg.param_dtype == "bfloat16" else 4
    params_b = n * param_itemsize
    grads_b = n * 4  # grads accumulate in f32
    opt_mult = {"adamw": 2, "adam": 2, "sgd": 0, "momentum": 1}[optimizer]
    opt_b = opt_mult * n * 4
    act_b = activation_bytes_estimate(cfg, batch_size, seq_len)
    total = params_b + grads_b + opt_b + act_b
    return {
        "param_count": n,
        "params_bytes": params_b,
        "grads_bytes": grads_b,
        "optimizer_bytes": opt_b,
        "activations_bytes_estimate": act_b,
        "total_bytes_estimate": total,
        "total_gib_estimate": total / 2**30,
        "config": {
            "batch_size": batch_size,
            "seq_len": seq_len,
            "remat": cfg.remat,
            "dtype": cfg.dtype,
            "param_dtype": cfg.param_dtype,
        },
    }


def measured_memory(device=None) -> dict:
    """Live/peak device memory (reference :105-110's
    memory_allocated/memory_reserved analogue). Returns zeros when the
    backend exposes no stats (CPU)."""
    device = device or jax.local_devices()[0]
    stats = device.memory_stats() or {}
    return {
        "bytes_in_use": stats.get("bytes_in_use", 0),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
        "bytes_limit": stats.get("bytes_limit", 0),
        "raw": dict(stats),
    }


def save_memory_snapshot(path: str | Path) -> str:
    """Dump the current device-memory profile (pprof .prof — open with
    ``pprof`` or pprof-web; the memory_viz-pickle analogue of
    reference :112-117)."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    jax.profiler.save_device_memory_profile(str(path))
    return str(path)


def compiled_memory_analysis(fn, *example_args) -> dict | None:
    """Exact compile-time HBM accounting from XLA's buffer assignment.

    Lowers + compiles ``fn`` on the example arguments and returns the
    compiler's memory numbers — the same figures an HBM OOM error reports
    ("Program hbm requirement ..."), available BEFORE running anything.
    Unlike ``measured_memory`` this works on backends with no runtime
    memory stats (CPU), and is the idiomatic TPU answer to the
    reference's allocator-history accounting (SURVEY.md §2.3: HLO
    buffer-assignment dump). Returns None if the backend or jax version
    does not expose the analysis.
    """
    try:
        # Already-jitted callables lower directly (preserving donation /
        # aliasing); plain functions get wrapped.
        # repolint: allow(jit-donation-decision) — wraps the USER's fn
        # purely to lower it; adding donation would skew the
        # alias/argument byte accounting this function reports.
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = jitted.lower(*example_args).compile()
        ma = compiled.memory_analysis()
    except (AttributeError, NotImplementedError, jax.errors.JaxRuntimeError):
        return None
    if ma is None:
        return None
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
        "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0)),
        # What must fit in HBM simultaneously: live args (minus donated
        # aliases) + outputs + program temporaries.
        "total_bytes": int(
            ma.argument_size_in_bytes
            - ma.alias_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
        ),
    }


def compare_estimate_vs_measured(
    cfg: ModelConfig, *, batch_size: int = 8, seq_len: int = 1024
) -> dict:
    """Side-by-side analytic estimate vs measured peak
    (reference :152-163)."""
    est = analytic_memory_breakdown(
        cfg, batch_size=batch_size, seq_len=seq_len
    )
    meas = measured_memory()
    est_total = est["total_bytes_estimate"]
    peak = meas["peak_bytes_in_use"]
    return {
        "estimated": est,
        "measured": meas,
        "ratio_measured_over_estimated": (peak / est_total) if est_total else None,
    }
