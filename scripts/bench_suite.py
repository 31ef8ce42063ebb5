"""BASELINE.md benchmark suite: configs 1-5, DDP vs FSDP, tokens/s/chip + MFU.

Produces ``benchmarks/results.json`` and ``benchmarks/RESULTS.md`` (the
results table the reference's run matrix implies but never commits —
reference assignments/assignment1/README.md:33-49, BASELINE.md configs 1-5).

Two kinds of rows:

- measured: run on the real accelerator with the hardened bench.py
  methodology (median of several windows, fresh seed). Configs that fit one
  chip: GPT-2 124M (f32 master weights) and GPT-2 1.3B / Llama-3 1B with
  bf16 optimizer state (f32 state for a 1B-param model exceeds one v5e's
  16 GB HBM; noted in the row).
- correctness-only: multi-chip parallelism configs executed on an 8-virtual-
  device CPU mesh at reduced dimensions (the cluster-free contract,
  SURVEY.md §4). These validate the parallelism wiring (DDP/FSDP/TP loss
  finiteness + step completion) and are clearly marked — tokens/s on a CPU
  mesh is meaningless.

Usage:
  python scripts/bench_suite.py                 # all rows
  python scripts/bench_suite.py --rows 1,3      # subset
  python scripts/bench_suite.py --no-virtual    # measured rows only
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# Row definitions (BASELINE.md "Configs to benchmark").
ROWS = {
    1: dict(
        name="gpt2-124M single-chip",
        preset="gpt2",
        parallelism="none",
        measured=True,
        batch=8,
        param_dtype="float32",
    ),
    2: dict(
        name="gpt2-124M DP x8 (DDP equivalent)",
        preset="gpt2",
        parallelism="dp8",
        measured=False,
        mesh=dict(data=8, strategy="no_shard"),
    ),
    3: dict(
        name="gpt2-1.3B FSDP full-shard x8 (ZeRO-3)",
        preset="gpt2-1p3b",
        parallelism="fsdp8",
        measured=True,  # single-chip proxy with bf16 state + virtual-mesh correctness
        batch=4,
        param_dtype="bfloat16",
        mesh=dict(fsdp=8, strategy="full_shard"),
    ),
    4: dict(
        name="llama3-1B FSDP + bf16",
        preset="llama3-1b",
        parallelism="fsdp8",
        measured=True,
        batch=4,
        param_dtype="bfloat16",
        # A/B'd round 4 (scripts/perf_ab.py): dots beats names by ~1.3%
        # on the SwiGLU family (13.7k vs 13.5k tok/s); gpt2 rows keep
        # names (names beats dots by ~4% at 1.3B).
        remat="dots",
        mesh=dict(fsdp=8, strategy="full_shard"),
    ),
    5: dict(
        name="llama3-8B FSDP + activation ckpt",
        preset="llama3-8b",
        parallelism="fsdp8",
        measured=False,  # 8B does not fit one chip in any dtype
        mesh=dict(fsdp=8, strategy="full_shard"),
    ),
    # Long context (beyond the BASELINE table, benchmarks/PERF_NOTES.md
    # "Long-context datapoint"): T=4096 trains on ONE chip thanks to the
    # flash kernel's O(T) memory + fused head/CE; T=8192 exceeds one
    # chip's HBM and is what the ring-attention seq-parallel path shards
    # -- projected as row 6p from the ring comm model.
    6: dict(
        name="llama3-1B long-context T=4096",
        preset="llama3-1b",
        parallelism="none",
        measured=True,
        batch=1,
        seq_len=4096,
        param_dtype="bfloat16",
        # A/B'd round 4: at T=4096 "names" WINS (11.2k tok/s / 60.4% MFU
        # vs dots 10.3k / 55.7%) even though dots wins at T=1024 (row 4)
        # — at long context the quadratic-in-T attention recompute that
        # names avoids dominates the policy tradeoff.
        remat="names",
        fused_head_ce=True,
        ring_projection=dict(n_chips=2),  # T_global=8192 over seq=2
    ),
    # Round 5: T=8192 MEASURED on one chip (the regime round 4 projected
    # as infeasible). Three things unlock it: the fused flash backward
    # kernel's per-kernel vmem budget now scales past Mosaic's 16 MB
    # default (ops/flash_kernel.py), the fused head+CE keeps the logits
    # out of HBM, and the "flash" remat policy saves ONLY the kernel's
    # (o, l, m) — the remat ladder at this length: names/dots OOM HBM
    # (17.5G/17.5G vs 15.75G), full fits at 46.9% MFU, flash fits and
    # wins at 53.4%. B=2 OOMs by 140 MB — B=1 is the single-chip
    # ceiling. The ring projection extends to T_global=16384 over seq=2.
    7: dict(
        name="llama3-1B long-context T=8192",
        preset="llama3-1b",
        parallelism="none",
        measured=True,
        batch=1,
        seq_len=8192,
        param_dtype="bfloat16",
        remat="flash",
        fused_head_ce=True,
        ring_projection=dict(n_chips=2),  # T_global=16384 over seq=2
    ),
}

V5E_PEAK_BF16 = 197e12


def measure_row(row: dict, *, windows: int, window_steps: int) -> dict:
    """Single-chip measured throughput, bench.py methodology."""
    import jax
    import numpy as np

    from pytorch_distributed_tpu.config import TrainConfig, model_config
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.train.optim import make_optimizer
    from pytorch_distributed_tpu.train.state import init_train_state
    from pytorch_distributed_tpu.train.trainer import make_train_step
    from pytorch_distributed_tpu.utils.prng import domain_key

    seed = 0
    B, T = row["batch"], row.get("seq_len", 1024)
    # cfg_overrides (perf_ab variants) may override ANY key below —
    # merge into one kwargs dict so e.g. {"remat": "dots"} replaces the
    # row default instead of colliding with it.
    cfg_kwargs = dict(
        attention_impl="flash",
        remat=row.get("remat", "names"),
        logits_dtype="bfloat16",
        embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0,
        n_ctx=T,  # benchmark sequence length (llama presets default 8192)
        fused_head_ce=row.get("fused_head_ce", False),
    )
    cfg_kwargs.update(row.get("cfg_overrides", {}))
    cfg = model_config(
        row["preset"], dtype="bfloat16", param_dtype=row["param_dtype"]
    ).replace(**cfg_kwargs)
    model = get_model(cfg)
    tcfg = TrainConfig(
        global_batch_size=B, micro_batch_size=B,
        num_steps=3 + windows * window_steps, learning_rate=3e-4,
    )
    tx = make_optimizer(tcfg)
    params = model.init(domain_key(seed, "init"), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    state = init_train_state(params, tx)
    step = make_train_step(model, cfg, tx)
    rng = np.random.default_rng(seed)
    batch = {
        "inputs": jax.numpy.asarray(
            rng.integers(0, cfg.vocab_size, (1, B, T)), dtype=jax.numpy.int32
        ),
        "targets": jax.numpy.asarray(
            rng.integers(0, cfg.vocab_size, (1, B, T)), dtype=jax.numpy.int32
        ),
    }
    dkey = domain_key(seed, "dropout")
    idx = 0
    for _ in range(3):
        state, m = step(state, batch, jax.random.fold_in(dkey, idx))
        idx += 1
    float(jax.device_get(m["loss"]))

    tps = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(window_steps):
            state, m = step(state, batch, jax.random.fold_in(dkey, idx))
            idx += 1
        loss = float(jax.device_get(m["loss"]))
        tps.append(window_steps * B * T / (time.perf_counter() - t0))

    tok_s = statistics.median(tps)
    flops_per_token = 6 * n_params + 12 * cfg.n_layer * cfg.n_embd * T
    mfu = tok_s * flops_per_token / V5E_PEAK_BF16
    notes = []
    if row.get("mesh"):
        # The FSDP-labeled configs are MEASURED on one chip with no mesh
        # and no collectives — an upper bound on the multi-chip number,
        # never the config's number (VERDICT r2 weak #1). Said in the row.
        notes.append("single-chip proxy — NO FSDP communication")
    if row["param_dtype"] == "bfloat16":
        notes.append(
            "bf16 optimizer state (f32 state for ~1B params exceeds one "
            "chip's HBM)"
        )
    return dict(
        kind="measured",
        platform=jax.devices()[0].platform,
        n_params=n_params,
        n_layer=cfg.n_layer, n_embd=cfg.n_embd,
        kv_dim=cfg.kv_heads * cfg.head_dim,
        batch=B, seq_len=T,
        tokens_per_sec_per_chip=round(tok_s, 1),
        ms_per_step=round(B * T / tok_s * 1e3, 1),
        mfu_pct=round(mfu * 100, 1),
        window_spread=round(max(tps) / min(tps), 3),
        final_loss=round(loss, 3),
        note="; ".join(notes),
    )


def virtual_row_main(row_id: int) -> None:
    """Child-process entry: correctness-only run on an 8-virtual-device CPU
    mesh at reduced dimensions. Prints one JSON line."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from pytorch_distributed_tpu.config import (
        MeshConfig, TrainConfig, model_config,
    )
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.parallel import (
        make_mesh, make_parallel_train_step, shard_train_state,
    )
    from pytorch_distributed_tpu.train.optim import make_optimizer
    from pytorch_distributed_tpu.train.state import init_train_state

    row = ROWS[row_id]
    scaled = dict(n_layer=2, n_ctx=256, vocab_size=1024)
    cfg = model_config(row["preset"], dtype="float32").replace(
        embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0, remat="names",
        **scaled,
    )
    model = get_model(cfg)
    mesh_cfg = MeshConfig(**row["mesh"])
    mesh = make_mesh(mesh_cfg)
    B, T = 8, 64
    tcfg = TrainConfig(
        global_batch_size=2 * B, micro_batch_size=1,
        num_steps=2, learning_rate=1e-3,
    )
    tx = make_optimizer(tcfg)
    state = init_train_state(model.init(jax.random.key(0), cfg), tx)
    state, _ = shard_train_state(state, mesh, mesh_cfg)
    step, put = make_parallel_train_step(model, cfg, tx, mesh, mesh_cfg, state)
    rng = np.random.default_rng(0)
    batch = put({
        "inputs": rng.integers(0, cfg.vocab_size, (2, B, T)).astype(np.int32),
        "targets": rng.integers(0, cfg.vocab_size, (2, B, T)).astype(np.int32),
    })
    losses = []
    for i in range(2):
        state, m = step(state, batch, jax.random.key(i))
        losses.append(float(jax.device_get(m["loss"])))
    assert all(np.isfinite(losses)), losses
    assert int(jax.device_get(state.step)) == 2
    print(json.dumps(dict(
        kind="correctness_only",
        platform="cpu-virtual-8dev",
        mesh=row["mesh"],
        scaled_dims=dict(**scaled, batch=2 * B, seq_len=T),
        losses=[round(x, 4) for x in losses],
        note=(
            "parallelism wiring validated on a virtual CPU mesh at reduced "
            "dimensions; throughput not meaningful without real chips"
        ),
    )))


def run_virtual_subprocess(row_id: int) -> dict:
    env = dict(os.environ)
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    # This parent has measured rows and holds the chip; a chip belongs to
    # one process, so the child is pinned to the CPU platform.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, __file__, "--virtual-row", str(row_id)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1200,
    )
    if proc.returncode != 0:
        return dict(kind="correctness_only", ok=False,
                    error=proc.stderr.strip().splitlines()[-5:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _projection_for(rid: str, res: dict) -> dict | None:
    """Analytic v5e-16 FSDP projection for a measured single-chip proxy row
    (profiling/comm_model.py; unit-tested in tests/test_comm_model.py)."""
    row = ROWS[int(rid)]
    if res.get("kind") != "measured" or not row.get("mesh"):
        return None
    sys.path.insert(0, str(REPO))
    from pytorch_distributed_tpu.profiling.comm_model import project_fsdp_mfu

    param_bytes = 2 if row["param_dtype"] == "bfloat16" else 4
    return project_fsdp_mfu(
        n_params=res["n_params"],
        n_chips=16,
        measured_ms_per_step=res["ms_per_step"],
        measured_mfu_pct=res["mfu_pct"],
        param_bytes=param_bytes,
    )


def _ring_projection_for(rid: str, res: dict) -> dict | None:
    """Ring-attention sequence-parallel projection for a measured
    long-context row: T_global = n_chips * T_local over a seq mesh
    (profiling/comm_model.py project_ring_mfu, unit-tested)."""
    row = ROWS[int(rid)]
    rp = row.get("ring_projection")
    if rp is None or res.get("kind") != "measured":
        return None
    if "n_layer" not in res:
        return None  # row measured by an older suite version; re-measure
    sys.path.insert(0, str(REPO))
    from pytorch_distributed_tpu.profiling.comm_model import project_ring_mfu

    return project_ring_mfu(
        measured_ms_per_step=res["ms_per_step"],
        n_params=res["n_params"],
        n_layer=res["n_layer"],
        n_embd=res["n_embd"],
        kv_dim=res["kv_dim"],
        batch=res["batch"],
        t_local=res["seq_len"],
        n_chips=rp["n_chips"],
    )


def _llama8b_memory_note() -> str:
    """Row-5 feasibility (llama3-8B never fits one chip): analytic ZeRO-3
    per-chip state memory (unit-tested, profiling/comm_model.py)."""
    sys.path.insert(0, str(REPO))
    from pytorch_distributed_tpu.profiling.comm_model import (
        zero_memory_per_chip,
    )

    z16 = zero_memory_per_chip(
        8_030_000_000, 16, strategy="full_shard", param_bytes=2,
        grad_bytes=2, opt_bytes=8,
    )
    z64 = zero_memory_per_chip(
        8_030_000_000, 64, strategy="full_shard", param_bytes=2,
        grad_bytes=2, opt_bytes=8,
    )
    return (
        f"- Row 5 feasibility (analytic, `zero_memory_per_chip`): "
        f"llama3-8B under ZeRO-3 with bf16 params/grads + f32 moments "
        f"needs {z16['total'] / 1e9:.1f} GB of state per chip on v5e-16 "
        f"and {z64['total'] / 1e9:.1f} GB on v5e-64 (16 GB HBM each) — "
        f"state fits from 16 chips up; per-layer gathered working set "
        f"and activations set the usable batch."
    )


def write_artifacts(results: dict) -> None:
    outdir = REPO / "benchmarks"
    outdir.mkdir(exist_ok=True)
    for rid, res in list(results["rows"].items()):
        if res.get("kind") == "measured" and ROWS[int(rid)].get("mesh"):
            # Normalise rows produced by older suite versions too (--regen).
            if "single-chip proxy" not in (res.get("note") or ""):
                res["note"] = "; ".join(
                    x for x in
                    ["single-chip proxy — NO FSDP communication",
                     res.get("note") or ""]
                    if x
                )
        proj = _projection_for(rid, res)
        if proj is not None:
            res["v5e16_projection"] = proj
        rproj = _ring_projection_for(rid, res)
        if rproj is not None:
            res["ring_projection"] = rproj
    (outdir / "results.json").write_text(json.dumps(results, indent=1))

    lines = [
        "# Benchmark results (BASELINE.md configs 1-5)",
        "",
        "Generated by `scripts/bench_suite.py`. Three kinds of rows:",
        "",
        "- **measured** — real accelerator, median of timed windows "
        "(bench.py methodology). The rig has ONE chip: rows whose config "
        "names a multi-chip mesh are **single-chip proxies with NO "
        "communication** — an upper bound, not the config's number.",
        "- **projected** — the single-chip measurement plus the analytic "
        "collective-traffic model (`profiling/comm_model.py`, unit-tested): "
        "an MFU *band* bracketing bandwidth and overlap assumptions.",
        "- **correctness-only** — 8-virtual-device CPU mesh at reduced "
        "dims; validates the parallelism wiring, no throughput claim.",
        "",
        "| # | Config | Parallelism | tok/s/chip | ms/step | MFU | Status |",
        "|---|--------|-------------|-----------:|--------:|----:|--------|",
    ]
    for rid, res in sorted(results["rows"].items(), key=lambda kv: int(kv[0])):
        row = ROWS[int(rid)]
        if res.get("kind") == "measured":
            par = (
                "none (single chip)" if row.get("mesh") else row["parallelism"]
            )
            lines.append(
                f"| {rid} | {row['name']} | {par} | "
                f"{res['tokens_per_sec_per_chip']:,.0f} | "
                f"{res['ms_per_step']} | {res['mfu_pct']}% | measured "
                f"({res.get('note') or 'real chip'}) |"
            )
            proj = res.get("v5e16_projection")
            if proj is not None:
                lo, hi = proj["mfu_pct_band"]
                s_lo, s_hi = proj["step_ms_band"]
                lines.append(
                    f"| {rid}p | {row['name']} -> v5e-16 fsdp16 | fsdp16 | "
                    f"n/a | {s_lo:.0f}-{s_hi:.0f} | "
                    f"{lo:.1f}-{hi:.1f}% | PROJECTED (analytic comm model; "
                    f"not a measurement) |"
                )
            rproj = res.get("ring_projection")
            if rproj is not None:
                lo, hi = rproj["mfu_pct_band"]
                s_lo, s_hi = rproj["step_ms_band"]
                n = rproj["n_chips"]
                lines.append(
                    f"| {rid}p | {row['name']} -> ring seq{n} "
                    f"T={rproj['t_global']} | seq{n} (ring attention) | "
                    f"{rproj['tokps_per_chip_band'][0]:,.0f}-"
                    f"{rproj['tokps_per_chip_band'][1]:,.0f} | "
                    f"{s_lo:.0f}-{s_hi:.0f} | {lo:.1f}-{hi:.1f}% | "
                    f"PROJECTED (ring comm model; not a measurement) |"
                )
        else:
            status = (
                "correctness-only (virtual CPU mesh)"
                if res.get("losses") or res.get("ok", True)
                else f"FAILED: {res.get('error')}"
            )
            lines.append(
                f"| {rid} | {row['name']} | {row['parallelism']} | "
                f"n/a | n/a | n/a | {status} |"
            )
        extra = results.get("virtual", {}).get(str(rid))
        if extra and res.get("kind") == "measured":
            lines.append(
                f"| {rid}v | {row['name']} (mesh wiring) | "
                f"{extra.get('mesh')} | n/a | n/a | n/a | "
                f"correctness-only (virtual CPU mesh) |"
            )
    lines += [
        "",
        "Notes:",
        "- MFU = tok/s x (6N + 12·L·E·T) / 197e12 (v5e bf16 peak).",
        "- All measured rows: T=1024 unless the row names a longer "
        "context, bf16 activations, Pallas flash attention, bf16 logits, "
        "no dropout; remat policy is per-row (ROWS[n]['remat'], "
        "A/B-measured optimum — 'names' unless stated).",
        "- ~1B-param rows use bf16 optimizer state to fit one chip's HBM; "
        "multi-chip f32-state runs are what the mesh configs are for.",
        "- The BASELINE.md north star (>=40% MFU for 1B FSDP on v5e-16) is "
        "**projected**, not achieved: the projected bands above come from "
        "the comm model's assumptions (per-chip ICI 45-90 GB/s effective, "
        "overlap bracketed none..full, weak scaling), and no multi-chip "
        "measurement exists on this rig.",
        _llama8b_memory_note(),
    ]
    (outdir / "RESULTS.md").write_text("\n".join(lines) + "\n")
    print(f"wrote {outdir / 'results.json'} and {outdir / 'RESULTS.md'}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="1,2,3,4,5,6")
    ap.add_argument("--windows", type=int, default=3)
    # 48-step windows match bench.py.
    ap.add_argument("--window-steps", type=int, default=48)
    ap.add_argument("--no-virtual", action="store_true")
    ap.add_argument(
        "--regen", action="store_true",
        help="rewrite RESULTS.md (+ projections) from the committed "
        "results.json without re-measuring — no accelerator needed",
    )
    ap.add_argument("--virtual-row", type=int, default=None,
                    help=argparse.SUPPRESS)  # child-process entry
    args = ap.parse_args()

    if args.virtual_row is not None:
        virtual_row_main(args.virtual_row)
        return

    if args.regen:
        prior = REPO / "benchmarks" / "results.json"
        write_artifacts(json.loads(prior.read_text()))
        return

    row_ids = [int(r) for r in args.rows.split(",")]
    # Merge into any existing artifact so subset runs (--rows, --no-virtual)
    # refresh their rows without clobbering the rest of the table.
    results: dict = {"rows": {}, "virtual": {}}
    prior = REPO / "benchmarks" / "results.json"
    if prior.exists():
        try:
            loaded = json.loads(prior.read_text())
            results["rows"].update(loaded.get("rows", {}))
            results["virtual"].update(loaded.get("virtual", {}))
        except (json.JSONDecodeError, OSError):
            pass
    for rid in row_ids:
        row = ROWS[rid]
        if row["measured"]:
            print(f"[row {rid}] measuring {row['name']} ...", file=sys.stderr)
            results["rows"][str(rid)] = measure_row(
                row, windows=args.windows, window_steps=args.window_steps
            )
            if row.get("mesh") and not args.no_virtual:
                print(f"[row {rid}] virtual-mesh wiring check ...",
                      file=sys.stderr)
                results["virtual"][str(rid)] = run_virtual_subprocess(rid)
        elif not args.no_virtual:
            print(f"[row {rid}] correctness-only {row['name']} ...",
                  file=sys.stderr)
            results["rows"][str(rid)] = run_virtual_subprocess(rid)
    write_artifacts(results)


if __name__ == "__main__":
    sys.path.insert(0, str(REPO))
    main()
