"""Shared CLI plumbing for entry scripts.

The reference hardcodes hyperparameters per script
(reference train_baseline.py:24-31: GPT-2 Large, global 32, micro 8, T=1024,
20 steps, AdamW lr 3e-4 wd 0.1, cosine->0.1lr) with one argparse flag.
These scripts keep those defaults but expose them as flags, plus:

--data synthetic|fineweb   zero-egress default is synthetic shards in kjj0
                           format; fineweb downloads like reference
                           data_loader.py:9-65.
--preset / model flags     AutoConfig replacement (config.model_config).
--cpu-devices N            run on N virtual CPU devices — the cluster-free
                           way to exercise multi-device paths
                           (SURVEY.md §4; must be set before jax imports,
                           which is why scripts parse args first and import
                           jax after).
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

# Make the scripts self-contained: importing _common puts the repo root on
# sys.path, so `pytorch_distributed_tpu` resolves even when the editable
# pip install is absent (fresh containers).
_REPO = str(Path(__file__).resolve().parent.parent)
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def add_common_args(p: argparse.ArgumentParser, *, preset: str) -> None:
    p.add_argument("--preset", default=preset,
                   help="model preset (gpt2, gpt2-large, gpt2-1p3b, "
                        "llama3-1b, ... or 'tiny')")
    p.add_argument("--data", default="synthetic",
                   choices=["synthetic", "fineweb", "local"],
                   help="synthetic (zero-egress generated shards), fineweb "
                        "(downloads like the reference), or local (train "
                        "on every *.bin already in --data-dir — e.g. from "
                        "scripts/tokenize_text.py)")
    p.add_argument("--data-dir", default=".cache/data")
    p.add_argument("--num-train-files", type=int, default=10)
    p.add_argument("--global-batch-size", type=int, default=32)
    p.add_argument("--micro-batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--save-every", type=int, default=None)
    p.add_argument("--checkpoint-dir", default="checkpoints")
    p.add_argument("--keep-checkpoints", type=int, default=None,
                   help="retain only the newest N checkpoints "
                        "(default: keep all)")
    p.add_argument("--async-checkpoint", action="store_true",
                   help="overlap checkpoint writes with training (orbax "
                        "async save; commits at the next save / end of "
                        "run)")
    p.add_argument("--accum-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="gradient-accumulation buffer dtype (A>1): bf16 "
                        "halves the accumulator HBM — what lets gpt2-large "
                        "accumulate on one 16 GB chip — at ~8 mantissa "
                        "bits of accumulation precision")
    p.add_argument("--metrics-out", default=None,
                   help="append logged metrics as JSON lines to this file")
    p.add_argument("--save-on-preemption", action="store_true",
                   help="on SIGTERM/SIGINT, finish the in-flight step, "
                        "write a resumable checkpoint (incl. data-stream "
                        "position), and exit cleanly")
    p.add_argument("--anomaly-guard", action="store_true",
                   help="traced anomaly guard (train/guard.py): non-finite "
                        "loss/grad + EMA loss-spike + corrupt-token "
                        "detection INSIDE the compiled step; anomalous "
                        "updates become traced no-ops (zero host syncs, "
                        "zero recompiles) and the host rolls back to the "
                        "last good checkpoint after --guard-rollback-after "
                        "consecutive anomalies")
    p.add_argument("--guard-rollback-after", type=int, default=3,
                   help="consecutive anomalies before rollback "
                        "(0 = skip-only, never roll back)")
    p.add_argument("--guard-skip-window", action="store_true",
                   help="on rollback, drop the offending data window "
                        "instead of replaying it (for persistent data "
                        "corruption)")
    p.add_argument("--resume", action="store_true",
                   help="resume from latest checkpoint (capability the "
                        "reference has at trainer level but never wires up)")
    p.add_argument("--dtype", default=None,
                   help="activation dtype override (bfloat16/float32)")
    p.add_argument("--param-dtype", default=None,
                   help="parameter/optimizer-state dtype override. A 774M+ "
                        "model with f32 master state cannot fit one 16 GB "
                        "v5e chip; the verified single-v5e gpt2-large recipe "
                        "is --dtype bfloat16 --param-dtype bfloat16 "
                        "--global-batch-size 4 --micro-batch-size 4 (no "
                        "accumulation — the f32 accumulator buffers are what "
                        "overflow). The reference's global-batch-32 config "
                        "belongs on a multi-chip fsdp mesh (train_fsdp.py / "
                        "train_parallel.py)")
    p.add_argument("--attention-impl", default="flash",
                   choices=["flash", "naive"],
                   help="flash (Pallas/blockwise, O(T) memory — default) or "
                        "naive (reference-parity [T,T] scores; with --remat "
                        "dots the saved f32 scores OOM any >12-layer model "
                        "at T=1024 on a 16 GB chip)")
    p.add_argument("--remat", default="names",
                   choices=["none", "full", "dots", "dots_no_batch",
                            "names", "flash"],
                   help="activation-checkpoint policy (default names = save "
                        "tagged projection outputs; the measured optimum is "
                        "length-dependent — dots at T=1024 for llama, names "
                        "at T=4096, flash (only the kernel's o/lse) at "
                        "T=8192 — August chip runs on older code)")
    p.add_argument("--no-profiler", action="store_true")
    p.add_argument("--trace-dir", default=None)
    p.add_argument("--cpu-devices", type=int, default=0,
                   help="force CPU platform with this many virtual devices")
    p.add_argument("--debug-nans", action="store_true",
                   help="jax_debug_nans: error at the op that first "
                        "produces a NaN (the functional-JAX analogue of "
                        "torch.autograd.detect_anomaly — SURVEY.md §5.2)")


def setup_platform(args) -> None:
    """MUST run before any jax import. Also places the persistent compile
    cache (utils/compile_cache.py), so a script's second run on a machine
    does not compile again."""
    if args.cpu_devices:
        # Strip any stale device-count flag first: re-entrant calls (or a
        # flag inherited from the environment) must not leave two counts
        # for XLA to pick between.
        flags = [
            f
            for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append(
            f"--xla_force_host_platform_device_count={args.cpu_devices}"
        )
        os.environ["XLA_FLAGS"] = " ".join(flags)
        import jax

        jax.config.update("jax_platforms", "cpu")
    if getattr(args, "debug_nans", False):
        import jax

        jax.config.update("jax_debug_nans", True)
    from pytorch_distributed_tpu.utils.compile_cache import (
        place_compile_cache,
    )

    place_compile_cache()


def build_model_cfg(args):
    from pytorch_distributed_tpu.config import model_config

    cfg = model_config(args.preset)
    if args.preset == "tiny":
        cfg = cfg.replace(n_ctx=max(args.seq_len, 32))
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)
    if getattr(args, "param_dtype", None):
        cfg = cfg.replace(param_dtype=args.param_dtype)
    # Unconditional: entry scripts default to the TPU-sane flash/names
    # combination (the ModelConfig defaults are the reference-parity
    # naive/dots, which OOM any >12-layer model at T=1024 on 16 GB);
    # argparse always supplies a value, so there is no "unset" case.
    cfg = cfg.replace(
        attention_impl=args.attention_impl, remat=args.remat
    )
    if args.seq_len > cfg.n_ctx:
        raise SystemExit(
            f"--seq-len {args.seq_len} exceeds model n_ctx {cfg.n_ctx}"
        )
    return cfg


def build_train_cfg(args, *, data_parallel_size: int = 1):
    from pytorch_distributed_tpu.config import TrainConfig

    cfg = TrainConfig(
        global_batch_size=args.global_batch_size,
        micro_batch_size=args.micro_batch_size,
        num_steps=args.steps,
        learning_rate=args.lr,
        weight_decay=args.weight_decay,
        seed=args.seed,
        log_every_n_steps=args.log_every,
        save_every_n_steps=args.save_every,
        checkpoint_dir=args.checkpoint_dir,
        keep_checkpoints=args.keep_checkpoints,
        accum_dtype=args.accum_dtype,
        async_checkpoint=args.async_checkpoint,
        metrics_path=args.metrics_out,
        save_on_preemption=args.save_on_preemption,
        anomaly_guard=args.anomaly_guard,
        guard_rollback_after=(
            args.guard_rollback_after if args.guard_rollback_after > 0
            else None
        ),
        guard_skip_window=args.guard_skip_window,
    )
    cfg.grad_accum_steps(data_parallel_size)  # validate divisibility early
    return cfg


def _local_shards(args) -> list[str]:
    import glob

    paths = sorted(glob.glob(os.path.join(args.data_dir, "*.bin")))
    if not paths:
        raise SystemExit(
            f"--data local: no *.bin shards in {args.data_dir!r} "
            "(produce some with scripts/tokenize_text.py)"
        )
    return paths


def _holds_out_val_shard(args, paths) -> bool:
    """Whether shard_paths excludes the last local shard for validation.
    The SINGLE predicate both shard_paths and val_shard_paths consult, so
    the train list and the overlap warning cannot drift. Note it depends
    on eval_batches: resuming a checkpointed run with eval toggled
    CHANGES the training shard list (and therefore the data stream) —
    val_shard_paths warns when the shard it returns was not held out."""
    return len(paths) > 1 and getattr(args, "eval_batches", 0) > 0


def shard_paths(args, vocab_size: int) -> list[str]:
    if args.data == "local":
        paths = _local_shards(args)
        # Hold the last shard out for validation ONLY when this run
        # actually evaluates — a train-only run keeps its whole corpus.
        if _holds_out_val_shard(args, paths):
            print(
                f"--data local: holding out {paths[-1]!r} as the "
                f"validation shard (training on {len(paths) - 1} shard(s))"
            )
            return paths[:-1]
        return paths
    if args.data == "fineweb":
        from pytorch_distributed_tpu.data.download import (
            download_fineweb10B_files,
        )

        return download_fineweb10B_files(
            os.path.join(args.data_dir, "fineweb10B"),
            num_train_files=args.num_train_files,
        )
    from pytorch_distributed_tpu.data.synthetic import make_synthetic_shards

    return make_synthetic_shards(
        os.path.join(args.data_dir, "synthetic"),
        num_shards=max(2, args.num_train_files),
        tokens_per_shard=2_000_000,
        vocab_size=min(vocab_size, 2**16),
        seed=args.seed,
    )


def val_shard_paths(args, vocab_size: int) -> list[str]:
    """Validation data: the fineweb val shard (reference
    data_loader.py:28-41 downloads it; nothing there ever reads it), a
    held-out synthetic shard from a disjoint seed, or — for --data local —
    the LAST local shard (held out of training by shard_paths when there
    is more than one shard)."""
    if args.data == "local":
        paths = _local_shards(args)
        if len(paths) == 1:
            print(
                "WARNING: --data local has a single shard; validation "
                "overlaps training data, so val loss is optimistic"
            )
        elif not _holds_out_val_shard(args, paths):
            # Multi-shard but the holdout didn't engage (eval was off or
            # the caller never sets eval_batches): the shard returned here
            # was part of training.
            print(
                f"WARNING: --data local: validation shard {paths[-1]!r} "
                "was NOT held out of training (holdout engages only when "
                "eval_batches > 0), so val loss is optimistic"
            )
        return [paths[-1]]
    if args.data == "fineweb":
        from pathlib import Path

        from pytorch_distributed_tpu.data.download import (
            download_fineweb10B_files,
        )

        d = os.path.join(args.data_dir, "fineweb10B")
        download_fineweb10B_files(d, num_train_files=0)
        return [str(Path(d) / "fineweb_val_000000.bin")]
    from pytorch_distributed_tpu.data.synthetic import make_synthetic_shards

    return make_synthetic_shards(
        os.path.join(args.data_dir, "synthetic_val"),
        num_shards=1,
        tokens_per_shard=500_000,
        vocab_size=min(vocab_size, 2**16),
        seed=args.seed + 10_000,
    )


def make_profiler(args, default_trace_dir: str):
    if args.no_profiler:
        return None
    from pytorch_distributed_tpu.profiling.profiler import ScheduledProfiler

    # Reference schedule: wait=2, warmup=2, active=6, repeat=1
    # (train_baseline.py:83-86).
    return ScheduledProfiler(
        args.trace_dir or default_trace_dir,
        wait=2, warmup=2, active=6, repeat=1,
    )
