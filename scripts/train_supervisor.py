#!/usr/bin/env python
"""Crash-recovery supervisor + seeded fault storm for the training loop.

PR 6 proved the serving tier survives failure by storming it and
asserting bit-equal outputs; this is the training twin. The supervisor
restarts a real training PROCESS across injected faults and proves the
whole recovery stack — traced anomaly guard (train/guard.py), checkpoint
integrity with crash-safe resume (train/checkpoint.py), preemption
saves, loader-position resume, step-keyed dropout — by one acceptance
bar: after a storm of

- process crashes at seeded steps (``os._exit`` — no cleanup runs),
- crashes landing INSIDE a checkpoint save (pre-commit: the
  half-written-checkpoint hazard),
- SIGTERM mid-window (the preemption path),
- corrupt-token batches (the traced guard must skip + roll back),
- bit-flipped checkpoint payloads (resume must fall back to an older
  retained checkpoint via the checksum manifest),
- slow steps (straggler stalls, charged to goodput),

the final params/opt_state must be **bit-equal** to an uninterrupted
fault-free leg of the same seed, with zero steady-state recompiles in
every process incarnation (compile-count pinned). Everything is a pure
function of --seed: the storm replays exactly.

Usage:
  python scripts/train_supervisor.py --seed 0                # the storm
  python scripts/train_supervisor.py --soak --json OUT.json  # bench leg
  python scripts/train_supervisor.py --soak --dryrun         # CI smoke
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from _common import setup_platform  # noqa: F401  (sys.path side effect)

DONE_NAME = "DONE.json"


def _worker_config(args) -> dict:
    """Everything a worker attempt needs, written once by the supervisor
    so every attempt (and the fault-free leg) runs the same run."""
    return {
        "seed": args.seed,
        "steps": args.steps,
        "save_every": args.save_every,
        "keep_checkpoints": args.keep_checkpoints,
        "async_checkpoint": bool(args.async_checkpoint),
        "p_crash": args.p_crash,
        "p_save_crash": args.p_save_crash,
        "p_sigterm": args.p_sigterm,
        "p_bad_batch": args.p_bad_batch,
        "p_ckpt_corrupt": args.p_ckpt_corrupt,
        "p_ckpt_corrupt_attempt": args.p_ckpt_corrupt_attempt,
        "p_slow_step": args.p_slow_step,
        "slow_step_s": args.slow_step_s,
    }


def _build_trainer(workdir: Path, cfg: dict, leg: str):
    from pytorch_distributed_tpu.config import ModelConfig, TrainConfig
    from pytorch_distributed_tpu.data import (
        TokenShardLoader,
        make_synthetic_shards,
    )
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.train.trainer import Trainer

    # Dropout stays ON: resume must reproduce the step-keyed dropout
    # draws bit-exactly or the storm's final-params comparison fails.
    mcfg = ModelConfig(
        vocab_size=101, n_ctx=16, n_embd=32, n_layer=2, n_head=4,
        dtype="float32", remat="dots",
    )
    shards = make_synthetic_shards(
        workdir / "data", num_shards=2, tokens_per_shard=20_000,
        vocab_size=101, seed=cfg["seed"],
    )
    loader = TokenShardLoader(shards, 4, 16)
    tcfg = TrainConfig(
        global_batch_size=8, micro_batch_size=4,  # grad accum A=2
        num_steps=cfg["steps"], learning_rate=1e-3,
        log_every_n_steps=4, seed=cfg["seed"],
        save_every_n_steps=cfg["save_every"],
        checkpoint_dir=str(workdir / f"ckpt_{leg}"),
        keep_checkpoints=cfg["keep_checkpoints"],
        async_checkpoint=cfg["async_checkpoint"],
        save_on_preemption=True,
        anomaly_guard=True,
        guard_rollback_after=1,  # any anomaly -> rollback+replay, so the
        # chaos leg must converge bit-exactly to the fault-free leg
        guard_warmup_steps=4,
        guard_max_rollbacks=1000,  # the storm, not the guard, bounds it
    )
    return Trainer(get_model(mcfg), mcfg, tcfg), loader


def _make_injector(workdir: Path, cfg: dict, attempt: int):
    import numpy as np

    from pytorch_distributed_tpu.train.chaos import (
        TrainFault,
        TrainFaultInjector,
    )

    # The schedule is a pure function of (seed, attempt): each restart
    # sees a fresh — but reproducible — storm.
    fold = cfg["seed"] * 1000 + attempt
    scripted = []
    rng = np.random.default_rng(fold + 7)
    # Save-coupled faults are scheduled on EARLY save boundaries: under
    # the storm an attempt rarely survives far past its first kill draw,
    # so a tick uniform over the whole run would mostly never be reached.
    early_saves = min(4, max(1, cfg["steps"] // cfg["save_every"]))
    if rng.random() < cfg["p_save_crash"]:
        # A crash INSIDE a checkpoint save (pre-commit): schedule it on
        # a save-boundary step so it actually lands mid-save.
        tick = cfg["save_every"] * int(rng.integers(1, early_saves + 1))
        scripted.append(TrainFault(tick=tick, kind="crash", program="save"))
    if rng.random() < cfg["p_ckpt_corrupt_attempt"]:
        # Bit rot only lands when a save actually happens that tick, so
        # (like the mid-save crash) it is scheduled on a save boundary —
        # the per-step seeded probability alone fires only 1/save_every
        # of its draws.
        tick = cfg["save_every"] * int(rng.integers(1, early_saves + 1))
        scripted.append(TrainFault(tick=tick, kind="ckpt_corrupt"))
    return TrainFaultInjector(
        scripted,
        seed=fold,
        p_crash=cfg["p_crash"],
        p_sigterm=cfg["p_sigterm"],
        p_bad_batch=cfg["p_bad_batch"],
        p_ckpt_corrupt=cfg["p_ckpt_corrupt"],
        p_slow_step=cfg["p_slow_step"],
        slow_step_s=cfg["slow_step_s"],
        crash_mode="exit",
        counts_path=workdir / f"counts_{attempt}.json",
    )


def run_worker(args) -> int:
    """One training attempt: resume from the newest loadable checkpoint,
    train (under injected faults on the chaos leg), record the outcome.
    Exit 0 with a DONE marker only when all steps completed."""
    import jax

    from pytorch_distributed_tpu.train import checkpoint as ckpt_lib

    workdir = Path(args.workdir)
    cfg = json.loads((workdir / "config.json").read_text())
    leg_dir = workdir / args.leg
    leg_dir.mkdir(parents=True, exist_ok=True)
    trainer, loader = _build_trainer(workdir, cfg, args.leg)

    state = trainer.init_state()
    t0 = time.perf_counter()
    if ckpt_lib.latest_checkpoint(trainer.train_cfg.checkpoint_dir) is None:
        # Anchor: rollback/resume always has a target, even for a fault
        # in the first save window.
        trainer.save_checkpoint(state, loader=loader)
    state = trainer.resume_latest(state, loader=loader)
    start_step = int(jax.device_get(state.step))

    if args.leg == "chaos":
        _make_injector(workdir, cfg, args.attempt).install(trainer)

    state, history = trainer.train(loader, state=state)
    end_step = int(jax.device_get(state.step))
    compile_count = trainer.train_step._cache_size()
    record = {
        "attempt": args.attempt,
        "leg": args.leg,
        "start_step": start_step,
        "end_step": end_step,
        "wallclock_s": round(time.perf_counter() - t0, 3),
        "rollbacks": getattr(trainer, "_rollbacks", 0),
        "anomalies": history[-1].get("anomalies", 0) if history else 0,
        # Zero steady-state recompiles: ONE executable per process
        # incarnation, storm or no storm.
        "compile_count": compile_count,
    }
    (workdir / f"attempt_{args.leg}_{args.attempt}.json").write_text(
        json.dumps(record)
    )
    if end_step >= cfg["steps"]:
        final_dir = workdir / f"final_{args.leg}"
        ckpt_lib.save_checkpoint(final_dir, state, format="npz")
        (leg_dir / DONE_NAME).write_text(json.dumps(record))
    return 0


def _spawn_worker(args, leg: str, attempt: int, log_dir: Path) -> int:
    # The supervisor never initialises jax, so it holds no chip: workers
    # run one at a time and may take whatever platform the environment
    # names (the CPU when it names none).
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    log = log_dir / f"worker_{leg}_{attempt}.log"
    with log.open("w") as f:
        return subprocess.call(
            [
                sys.executable, os.path.abspath(__file__), "--worker",
                "--workdir", str(args.workdir), "--leg", leg,
                "--attempt", str(attempt),
            ],
            stdout=f, stderr=subprocess.STDOUT, env=env,
        )


def _run_leg(args, leg: str) -> dict:
    """Drive one leg to completion across restarts. Returns the leg
    summary (attempts, wallclock, exit codes)."""
    workdir = Path(args.workdir)
    log_dir = workdir / "logs"
    log_dir.mkdir(parents=True, exist_ok=True)
    done_path = workdir / leg / DONE_NAME
    rcs = []
    t0 = time.perf_counter()
    max_attempts = 1 if leg == "clean" else args.max_restarts + 1
    for attempt in range(max_attempts):
        rc = _spawn_worker(args, leg, attempt, log_dir)
        rcs.append(rc)
        if done_path.exists():
            break
    wallclock = time.perf_counter() - t0
    attempts = []
    for p in sorted(workdir.glob(f"attempt_{leg}_*.json")):
        attempts.append(json.loads(p.read_text()))
    return {
        "leg": leg,
        "completed": done_path.exists(),
        "spawned": len(rcs),
        "restarts": len(rcs) - 1,
        "exit_codes": rcs,
        "wallclock_s": round(wallclock, 3),
        "attempts": attempts,
    }


def _bit_equal_finals(workdir: Path) -> tuple[bool, list[str]]:
    import numpy as np

    diffs = []
    paths = [workdir / "final_chaos", workdir / "final_clean"]
    loaded = []
    for p in paths:
        if not (p / "arrays.npz").exists():
            return False, [f"missing final checkpoint {p}"]
        with np.load(p / "arrays.npz") as data:
            loaded.append({k: data[k] for k in data.files})
    chaos, clean = loaded
    if set(chaos) != set(clean):
        return False, ["final checkpoints have different leaf sets"]
    for k in sorted(chaos):
        a, b = chaos[k], clean[k]
        if a.shape != b.shape or a.dtype != b.dtype or (
            a.tobytes() != b.tobytes()
        ):
            diffs.append(k)
    return not diffs, diffs


def run_supervisor(args) -> dict:
    workdir = Path(args.workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    (workdir / "config.json").write_text(json.dumps(_worker_config(args)))

    chaos = _run_leg(args, "chaos")
    clean = _run_leg(args, "clean")

    failures: list[str] = []
    if not chaos["completed"]:
        failures.append(
            f"chaos leg did not complete within {args.max_restarts} restarts"
        )
    if not clean["completed"]:
        failures.append("fault-free leg did not complete (harness bug)")

    bit_equal, diffs = (False, ["legs incomplete"])
    if chaos["completed"] and clean["completed"]:
        bit_equal, diffs = _bit_equal_finals(workdir)
        if not bit_equal:
            failures.append(
                f"final state NOT bit-equal to the fault-free run: "
                f"{diffs[:5]}"
            )

    # Fault coverage: aggregated across every attempt, including the ones
    # that died mid-write (the injector records each firing BEFORE a
    # crash fault kills the process).
    counts: dict[str, int] = {}
    for p in sorted(workdir.glob("counts_*.json")):
        for k, v in json.loads(p.read_text()).items():
            counts[k] = counts.get(k, 0) + v
    for kind in ("crash", "sigterm", "bad_batch", "ckpt_corrupt",
                 "slow_step"):
        if not counts.get(kind):
            failures.append(
                f"fault kind {kind!r} never fired — this seed's storm did "
                "not exercise it (raise its probability)"
            )

    for leg in (chaos, clean):
        for a in leg["attempts"]:
            if a["compile_count"] != 1:
                failures.append(
                    f"{a['leg']} attempt {a['attempt']}: compile_count "
                    f"{a['compile_count']} != 1 (steady-state recompile)"
                )

    # Goodput: useful steps per wallclock second, faulted vs fault-free.
    goodput_chaos = args.steps / max(chaos["wallclock_s"], 1e-9)
    goodput_clean = args.steps / max(clean["wallclock_s"], 1e-9)
    report = {
        "seed": args.seed,
        "steps": args.steps,
        "save_every": args.save_every,
        "async_checkpoint": bool(args.async_checkpoint),
        "chaos": chaos,
        "clean": clean,
        "fault_counts": counts,
        "bit_equal": bit_equal,
        "goodput_steps_per_s": {
            "chaos": round(goodput_chaos, 3),
            "clean": round(goodput_clean, 3),
        },
        "goodput_retention": round(goodput_chaos / goodput_clean, 4),
        "recovery_overhead_s": round(
            chaos["wallclock_s"] - clean["wallclock_s"], 3
        ),
        "failures": failures,
        "ok": not failures,
    }
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one training attempt")
    ap.add_argument("--leg", default="chaos", choices=["chaos", "clean"])
    ap.add_argument("--attempt", type=int, default=0)
    ap.add_argument("--workdir", default=None,
                    help="storm state dir (default: a fresh temp dir)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--save-every", type=int, default=4)
    ap.add_argument("--keep-checkpoints", type=int, default=3)
    ap.add_argument("--async-checkpoint", action="store_true",
                    help="storm the orbax async-save path instead of the "
                         "sync npz one")
    ap.add_argument("--max-restarts", type=int, default=40)
    ap.add_argument("--p-crash", type=float, default=0.03)
    ap.add_argument("--p-save-crash", type=float, default=0.5,
                    help="per-ATTEMPT probability of scheduling one crash "
                         "inside a checkpoint save (pre-commit)")
    ap.add_argument("--p-sigterm", type=float, default=0.02)
    ap.add_argument("--p-bad-batch", type=float, default=0.05)
    ap.add_argument("--p-ckpt-corrupt", type=float, default=0.03)
    ap.add_argument("--p-ckpt-corrupt-attempt", type=float, default=0.5,
                    help="per-ATTEMPT probability of scheduling one "
                         "checkpoint bit-flip on a save boundary")
    ap.add_argument("--p-slow-step", type=float, default=0.08)
    ap.add_argument("--slow-step-s", type=float, default=0.05)
    ap.add_argument("--soak", action="store_true",
                    help="the full storm at soak scale (more steps)")
    ap.add_argument("--dryrun", action="store_true",
                    help="small CI smoke (fewer steps, hotter faults)")
    ap.add_argument("--json", default=None, help="write the report here")
    ap.add_argument("--cpu-devices", type=int, default=0)
    args = ap.parse_args()
    setup_platform(args)

    if args.worker:
        if args.workdir is None:
            raise SystemExit("--worker requires --workdir")
        return run_worker(args)

    if args.soak:
        args.steps = max(args.steps, 64)
    if args.dryrun:
        # Fewer steps means fewer ticks, so the per-step fault
        # probabilities scale UP to keep every injection kind firing —
        # the smoke must exercise the same paths as the full storm.
        args.steps = min(args.steps, 20)
        args.save_every = min(args.save_every, 2)
        args.p_crash = max(args.p_crash, 0.06)
        args.p_sigterm = max(args.p_sigterm, 0.05)
        args.p_bad_batch = max(args.p_bad_batch, 0.12)
        args.p_ckpt_corrupt = max(args.p_ckpt_corrupt, 0.10)
        args.p_slow_step = max(args.p_slow_step, 0.20)
    if args.workdir is None:
        import tempfile

        args.workdir = tempfile.mkdtemp(prefix="train_storm_")

    report = run_supervisor(args)
    print(json.dumps(report, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    if not report["ok"]:
        print("TRAIN STORM FAILED", file=sys.stderr)
        return 1
    print(
        f"train storm ok: {args.steps} steps, "
        f"{report['chaos']['restarts']} restarts, faults "
        f"{report['fault_counts']}, goodput retention "
        f"{report['goodput_retention']}", file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
