"""Benchmark entry point (driver contract: prints ONE JSON line).

Measures training throughput of GPT-2 124M on the available accelerator with
the reference harness's methodology (reference assignment0/throughput.py:13-83:
dummy data, warmup steps, fenced timing loop, tokens/sec), hardened:

- several independently-timed windows; the MEDIAN window is reported and the
  run fails loudly (stderr warning + "unreliable" flag) if windows disagree
  by more than 2x — defense against cold/contended captures.
- runs on an accelerator or not at all: the peak it divides by comes from
  ``PEAK_BF16_FLOPS``, keyed by ``device_kind``; a device that is not in
  the table (the CPU included) is an error, never a nominal default.
- benches the framework's best training path: Pallas flash attention,
  named-saves remat policy, bf16 logits, no dropout (the modern pretraining
  configuration; the reference's 0.1 attention dropout costs ~40% throughput
  and no current config trains with it).

vs_baseline is MFU / 0.40 — the BASELINE.md north-star target (>=40% MFU).
"""

from __future__ import annotations

import json
import statistics
import sys
import time

import numpy as np

# Peak dense bf16 FLOP/s of ONE chip, keyed by jax's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # what jax calls a v5e chip
}


def main() -> None:
    import jax

    device = jax.devices()[0]
    if device.device_kind not in PEAK_BF16_FLOPS:
        raise SystemExit(
            f"bench.py: no peak FLOP/s on record for device_kind "
            f"{device.device_kind!r} (platform {device.platform!r}); known: "
            f"{sorted(PEAK_BF16_FLOPS)}. A throughput measured here would "
            "not be a chip number — run on the TPU, or add the device's "
            "published peak with its source to PEAK_BF16_FLOPS."
        )

    from pytorch_distributed_tpu.utils.compile_cache import (
        place_compile_cache,
    )

    place_compile_cache()

    from pytorch_distributed_tpu.config import TrainConfig, model_config
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.train.optim import make_optimizer
    from pytorch_distributed_tpu.train.state import init_train_state
    from pytorch_distributed_tpu.train.trainer import make_train_step
    from pytorch_distributed_tpu.utils.prng import domain_key

    batch_size, seq_len = 8, 1024
    warmup_steps, window_steps, num_windows = 3, 48, 3
    seed = 0

    cfg = model_config("gpt2", dtype="bfloat16").replace(
        attention_impl="flash",
        remat="names",
        logits_dtype="bfloat16",
        attn_pdrop=0.0,
        resid_pdrop=0.0,
        embd_pdrop=0.0,
    )
    model = get_model(cfg)
    tcfg = TrainConfig(
        global_batch_size=batch_size,
        micro_batch_size=batch_size,
        num_steps=warmup_steps + window_steps * num_windows,
        learning_rate=3e-4,
    )
    tx = make_optimizer(tcfg)
    params = model.init(domain_key(seed, "init"), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
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
    step_idx = 0

    # Each timed window runs dispatch-to-fetch: the device_get of the last
    # step's loss waits for every step before it.
    for _ in range(warmup_steps):
        state, metrics = step(state, batch, jax.random.fold_in(dkey, step_idx))
        step_idx += 1
    float(jax.device_get(metrics["loss"]))

    window_tps: list[float] = []
    for _ in range(num_windows):
        t0 = time.perf_counter()
        for _ in range(window_steps):
            state, metrics = step(
                state, batch, jax.random.fold_in(dkey, step_idx)
            )
            step_idx += 1
        final_loss = float(jax.device_get(metrics["loss"]))
        elapsed = time.perf_counter() - t0
        window_tps.append(window_steps * batch_size * seq_len / elapsed)

    tokens_per_sec = statistics.median(window_tps)
    spread = max(window_tps) / min(window_tps)
    unreliable = spread > 2.0
    ms_per_step = batch_size * seq_len / tokens_per_sec * 1e3

    # PaLM-style MFU: fwd+bwd FLOPs/token ~= 6N + 12*L*E*T.
    flops_per_token = 6 * n_params + 12 * cfg.n_layer * cfg.n_embd * seq_len
    achieved_flops = tokens_per_sec * flops_per_token
    mfu = achieved_flops / PEAK_BF16_FLOPS[device.device_kind]

    result = {
        "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
    }
    if unreliable:
        result["unreliable"] = True
    print(json.dumps(result))
    print(
        f"# {device.device_kind} x{len(jax.devices())}: median "
        f"{tokens_per_sec:,.0f} tok/s over "
        f"{num_windows} windows "
        f"({', '.join(f'{t:,.0f}' for t in window_tps)}; spread "
        f"{spread:.2f}x), {ms_per_step:.1f} ms/step, MFU {mfu * 100:.1f}%, "
        f"loss {final_loss:.3f}",
        file=sys.stderr,
    )
    if unreliable:
        print(
            "# WARNING: windows disagree by >2x — cold or contended run; "
            "re-run before trusting this number",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
