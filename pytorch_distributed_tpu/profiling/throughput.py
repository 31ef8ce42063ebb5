"""Throughput measurement, batch sweeps, and scaling extrapolation.

Capability twin of reference assignment0/throughput.py:
- tokens/sec + steps/sec over a fenced timing window after warmup
  (reference :13-83: dummy random data, 5 warmup, 20 timed,
  cuda.synchronize-fenced). TPU-native fencing: device_get of a step
  output, which waits for the device;
- throughput vs batch-size sweep with OOM catch + peak memory per point
  (reference :132-181);
- "modern training" extrapolation to huge params/tokens under a linear
  FLOPs-scaling assumption (reference :86-129).
"""

from __future__ import annotations

import time

import numpy as np

from pytorch_distributed_tpu.config import ModelConfig, TrainConfig


def measure_tokens_per_second(
    cfg: ModelConfig,
    *,
    batch_size: int = 8,
    seq_len: int = 1024,
    num_steps: int = 20,
    warmup_steps: int = 5,
    seed: int = 0,
) -> dict:
    """Train-step throughput on dummy data (reference :13-83 defaults:
    B=8, T=1024, 5 warmup + 20 timed)."""
    import jax

    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.train.optim import make_optimizer
    from pytorch_distributed_tpu.train.state import init_train_state
    from pytorch_distributed_tpu.train.trainer import make_train_step
    from pytorch_distributed_tpu.utils.prng import domain_key

    model = get_model(cfg)
    tcfg = TrainConfig(
        global_batch_size=batch_size,
        micro_batch_size=batch_size,
        num_steps=num_steps,
        learning_rate=3e-4,
    )
    tx = make_optimizer(tcfg)
    params = model.init(domain_key(seed, "init"), cfg)
    n_params = int(
        sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    )
    state = init_train_state(params, tx)
    step = make_train_step(model, cfg, tx)

    rng = np.random.default_rng(seed)
    batch = {
        "inputs": jax.numpy.asarray(
            rng.integers(0, cfg.vocab_size, (1, batch_size, seq_len)),
            dtype=jax.numpy.int32,
        ),
        "targets": jax.numpy.asarray(
            rng.integers(0, cfg.vocab_size, (1, batch_size, seq_len)),
            dtype=jax.numpy.int32,
        ),
    }
    dkey = domain_key(seed, "dropout")

    for i in range(warmup_steps):
        state, metrics = step(state, batch, jax.random.fold_in(dkey, i))
        float(jax.device_get(metrics["loss"]))  # fence

    t0 = time.perf_counter()
    for i in range(num_steps):
        state, metrics = step(
            state, batch, jax.random.fold_in(dkey, warmup_steps + i)
        )
    float(jax.device_get(metrics["loss"]))  # fence
    elapsed = time.perf_counter() - t0

    tokens_per_batch = batch_size * seq_len  # reference TODO :41-42
    total_tokens = num_steps * tokens_per_batch
    return {
        "tokens_per_second": total_tokens / elapsed,
        "steps_per_second": num_steps / elapsed,
        "seconds_per_step": elapsed / num_steps,
        "elapsed_seconds": elapsed,
        "num_steps": num_steps,
        "batch_size": batch_size,
        "seq_len": seq_len,
        "param_count": n_params,
    }


def extrapolate_modern_training(
    measured: dict,
    *,
    target_params: float = 1e12,
    target_tokens: float = 10e12,
) -> dict:
    """Scale measured throughput to a hypothetical giant run under the
    linear-FLOPs assumption (time/token scales with param count —
    reference :86-129's 1T-param / 10T-token estimate)."""
    tps = measured["tokens_per_second"]
    n = measured["param_count"]
    scale = target_params / n
    scaled_tps = tps / scale
    seconds = target_tokens / scaled_tps
    return {
        "measured_params": n,
        "measured_tokens_per_second": tps,
        "target_params": target_params,
        "target_tokens": target_tokens,
        "scaled_tokens_per_second": scaled_tps,
        "seconds": seconds,
        "days": seconds / 86400,
        "years": seconds / (86400 * 365),
        "assumption": "linear FLOPs scaling, identical hardware+efficiency",
    }


def compare_batch_sizes(
    cfg: ModelConfig,
    *,
    batch_sizes=(1, 4, 8, 16, 32, 64),
    seq_len: int = 1024,
    num_steps: int = 10,
    warmup_steps: int = 2,
) -> list[dict]:
    """Throughput + peak memory per batch size, OOM-tolerant
    (reference :132-181: fresh model per point, catch OOM, record peak)."""
    import jax

    from pytorch_distributed_tpu.profiling.memory import measured_memory

    results = []
    for b in batch_sizes:
        try:
            r = measure_tokens_per_second(
                cfg,
                batch_size=b,
                seq_len=seq_len,
                num_steps=num_steps,
                warmup_steps=warmup_steps,
            )
            r["peak_bytes_in_use"] = measured_memory()["peak_bytes_in_use"]
            r["oom"] = False
        except jax.errors.JaxRuntimeError as e:  # RESOURCE_EXHAUSTED
            if "RESOURCE_EXHAUSTED" not in str(e) and "out of memory" not in str(e).lower():
                raise
            r = {
                "batch_size": b,
                "seq_len": seq_len,
                "oom": True,
                "error": str(e).splitlines()[0][:200],
            }
        results.append(r)
    return results
