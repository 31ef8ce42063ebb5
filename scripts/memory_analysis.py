#!/usr/bin/env python
"""Memory analysis: analytic breakdown vs measured device memory.

Capability twin of reference assignments/assignment0/memory_analysis.py:
analytic params/grads/Adam breakdown (reference :12-52), a few profiled
training steps (reference :91-103), live/peak measurement (reference
:105-110), and a memory snapshot for offline viewing — here a pprof profile
from jax.profiler.save_device_memory_profile instead of the CUDA allocator
pickle (reference :112-117). Defaults: gpt2 (small), B=8, T=1024
(reference :136-138).

Example:
  python scripts/memory_analysis.py --preset tiny --seq-len 64 \\
      --global-batch-size 4 --micro-batch-size 4 --cpu-devices 1
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _common import (  # noqa: E402
    add_common_args,
    build_model_cfg,
    build_train_cfg,
    setup_platform,
)


def _fmt(n: int) -> str:
    return f"{n / 2**30:.3f} GiB" if n >= 2**28 else f"{n / 2**20:.1f} MiB"


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    add_common_args(p, preset="gpt2")
    p.add_argument("--profile-steps", type=int, default=3)
    p.add_argument(
        "--snapshot", default="outputs/task1_memory_snapshot.prof"
    )
    args = p.parse_args()
    args.global_batch_size = args.micro_batch_size  # no accumulation here
    setup_platform(args)

    import jax
    import numpy as np

    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.profiling.memory import (
        analytic_memory_breakdown,
        compiled_memory_analysis,
        measured_memory,
        save_memory_snapshot,
    )
    from pytorch_distributed_tpu.train.optim import make_optimizer
    from pytorch_distributed_tpu.train.state import init_train_state
    from pytorch_distributed_tpu.train.trainer import make_train_step
    from pytorch_distributed_tpu.utils.prng import domain_key

    model_cfg = build_model_cfg(args)
    b, t = args.micro_batch_size, args.seq_len

    est = analytic_memory_breakdown(model_cfg, batch_size=b, seq_len=t)
    print("=== analytic breakdown (reference memory_analysis.py:12-52) ===")
    print(f"params:      {est['param_count']:,}  ({_fmt(est['params_bytes'])})")
    print(f"gradients:   {_fmt(est['grads_bytes'])}")
    print(f"adam states: {_fmt(est['optimizer_bytes'])}")
    print(f"activations: {_fmt(est['activations_bytes_estimate'])} (remat={model_cfg.remat})")
    print(f"TOTAL est:   {_fmt(est['total_bytes_estimate'])}")

    print(f"\n=== profiling {args.profile_steps} training steps ===")
    model = get_model(model_cfg)
    train_cfg = build_train_cfg(args)
    tx = make_optimizer(train_cfg)
    state = init_train_state(
        model.init(domain_key(args.seed, "init"), model_cfg), tx
    )
    step = make_train_step(model, model_cfg, tx)
    rng = np.random.default_rng(args.seed)
    batch = {
        "inputs": jax.numpy.asarray(
            rng.integers(0, model_cfg.vocab_size, (1, b, t)), dtype=jax.numpy.int32
        ),
        "targets": jax.numpy.asarray(
            rng.integers(0, model_cfg.vocab_size, (1, b, t)), dtype=jax.numpy.int32
        ),
    }
    dkey = domain_key(args.seed, "dropout")

    xla = compiled_memory_analysis(step, state, batch, dkey)
    if xla is not None:
        print("\n=== compiled program (XLA buffer assignment) ===")
        print(f"arguments:  {_fmt(xla['argument_bytes'])} "
              f"(donated/aliased: {_fmt(xla['alias_bytes'])})")
        print(f"outputs:    {_fmt(xla['output_bytes'])}")
        print(f"HLO temps:  {_fmt(xla['temp_bytes'])}")
        print(f"TOTAL live: {_fmt(xla['total_bytes'])} "
              f"-- exact pre-flight HBM requirement for one train step")
        ratio = xla["total_bytes"] / est["total_bytes_estimate"]
        print(f"xla/estimated: {ratio:.2f}x")

    for i in range(args.profile_steps):
        state, metrics = step(state, batch, jax.random.fold_in(dkey, i))
        loss = float(jax.device_get(metrics["loss"]))
        print(f"step {i}: loss {loss:.4f}")

    meas = measured_memory()
    print("\n=== measured (device.memory_stats) ===")
    print(f"bytes_in_use:      {_fmt(meas['bytes_in_use'])}")
    print(f"peak_bytes_in_use: {_fmt(meas['peak_bytes_in_use'])}")
    if meas["peak_bytes_in_use"]:
        ratio = meas["peak_bytes_in_use"] / est["total_bytes_estimate"]
        print(f"measured/estimated: {ratio:.2f}x")
    else:
        print(
            "(backend exposes no memory stats — CPU run; "
            "the analytic estimate above is the HBM budget)"
        )

    snap = save_memory_snapshot(args.snapshot)
    print(f"\nmemory snapshot written to {snap} (pprof format)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
