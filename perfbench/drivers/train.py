"""The training driver: one compiled step with its state, driven from the
seed through its first steps in set-up and then through the window.

What it takes from the program: ``Trainer`` (its jitted ``train_step``, its
grouping and placing of loader batches, its dropout keys — the calls
``Trainer.train`` makes, without its logging and checkpoint branches),
``TokenShardLoader`` and the shard format. Weights, data, the window, the
rate and the comparison are the benchmark's.
"""

from __future__ import annotations

import collections
import gc
import itertools
import time
from pathlib import Path

import numpy as np

from perfbench import compare, flops, preset, reference, stats, trace, traffic

CHECK_STEPS = 3  # the reference follows the first three
LAG = 2  # the host dispatches at most this many steps ahead of the device


def _adam_mu(opt_state):
    """The first-moment tree inside the program's optax state."""
    import jax

    found = [s.mu for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


def build(ctx):
    """Program objects for this cell: (trainer, loader, model cfg)."""
    from pytorch_distributed_tpu.config import TrainConfig
    from pytorch_distributed_tpu.data import TokenShardLoader, bin_format
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.train import Trainer

    tr = ctx.traffic
    opt = tr["optimizer"]
    cfg = preset.of(ctx.config, "train")
    tcfg = TrainConfig(
        global_batch_size=tr["batch"], micro_batch_size=tr["batch"],
        num_steps=opt["schedule_steps"], learning_rate=opt["learning_rate"],
        weight_decay=opt["weight_decay"], beta1=opt["beta1"],
        beta2=opt["beta2"], eps=opt["eps"], lr_schedule=opt["lr_schedule"],
        min_lr_ratio=opt["min_lr_ratio"], seed=ctx.seed % (2**31 - 1),
    )
    trainer = Trainer(get_model(cfg), cfg, tcfg, log_fn=lambda _m: None)

    tokens = traffic.train_tokens(tr, ctx.seed, ctx.seconds)
    shard = Path(ctx.scratch) / "data" / f"{ctx.workload}.bin"
    shard.parent.mkdir(parents=True, exist_ok=True)
    bin_format.write_shard(shard, tokens)
    loader = TokenShardLoader([str(shard)], tr["batch"], tr["seq_len"])
    return trainer, loader, cfg


def run(ctx, keep_grad: bool = False) -> dict:
    """``keep_grad`` (tools only): the reference's first gradient stays in
    ``want``, for a control or a planted fault to be read against it."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.train.state import init_train_state
    from pytorch_distributed_tpu.utils.prng import step_key

    tr, model = ctx.traffic, ctx.config["model"]
    opt = tr["optimizer"]
    ref, count = reference.of(ctx.config), flops.of(ctx.config)
    trainer, loader, cfg = build(ctx)
    ctx.mark("trainer_built")
    step_fn = trainer.train_step

    params = ref.init_params(ctx.seed, model, cfg.param_dtype)
    state = init_train_state(params, trainer.tx)
    del params
    groups = trainer._grouped_batches(itertools.chain.from_iterable(
        itertools.repeat(loader)))
    spans = collections.defaultdict(float)  # harness span -> seconds
    n_step = 0

    def one_step(state, keep=None):
        nonlocal n_step
        t0 = time.perf_counter()
        with trace.span("input.next_batch"):
            batch = next(groups)
        if keep is not None:
            keep.append((batch["inputs"][0].copy(), batch["targets"][0].copy()))
        with trace.span("input.put_batch"):
            placed = trainer._put_batch(batch)
        t2 = time.perf_counter()
        with trace.span("step.dispatch"):
            state, metrics = step_fn(
                state, placed, step_key(trainer._dropout_root, n_step))
        spans["input_wait"] += t2 - t0
        spans["dispatch"] += time.perf_counter() - t2
        n_step += 1
        return state, metrics

    # -- set-up: the first steps, which the reference follows ---------------
    first_batches, check_losses = [], []
    state, m = one_step(state, first_batches)
    check_losses.append(m["loss"])
    jax.block_until_ready(state)
    ctx.mark("step1_done")
    b1 = opt["beta1"]
    mu = _adam_mu(state.opt_state)
    prog_grad = {k: v / (1 - b1) for k, v in ref.leaf_norms(mu).items()}
    # the first gradient itself, kept on the host until the reference has its
    # own: their difference is the number that sees precision
    first_grad = jax.tree.map(lambda m: np.asarray(m) / np.float32(1 - b1), mu)
    del mu
    for _ in range(CHECK_STEPS - 1):
        state, m = one_step(state, first_batches)
        check_losses.append(m["loss"])
    params0 = ref.init_params(ctx.seed, model, cfg.param_dtype)
    prog_delta = ref.leaf_norms(
        jax.tree.map(jnp.subtract, state.params, params0))
    del params0
    check_losses = [float(x) for x in jax.device_get(check_losses)]
    for _ in range(tr["warm_steps"]):
        state, m = one_step(state)
    jax.block_until_ready(state)
    executables_before = step_fn._cache_size()
    ctx.mark("steps_checked_and_warm")
    spans.clear()

    # -- the window ----------------------------------------------------------
    tokens_per_step = tr["batch"] * tr["seq_len"]
    cap = None
    trace_from = ctx.seconds - min(ctx.seconds, tr["trace_seconds"])
    pending = collections.deque()
    steps = 0
    setup_s = time.perf_counter() - ctx.t0
    t_start = time.perf_counter()
    traced_steps = 0
    while True:
        now = time.perf_counter() - t_start
        if now >= ctx.seconds:
            break
        if ctx.trace and cap is None and now >= trace_from:
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            cap = trace.capture(str(Path(ctx.scratch) / "trace")).start()
            spans["profiler_start"] = time.perf_counter() - t0
            win_span = trace.span("window")
            win_span.__enter__()
        state, m = one_step(state)
        steps += 1
        traced_steps += cap is not None
        pending.append(m["loss"])
        if len(pending) > LAG:
            with trace.span("step.wait_device"):
                t0 = time.perf_counter()
                pending.popleft().block_until_ready()
                spans["wait_device"] += time.perf_counter() - t0
    with trace.span("step.wait_device"):
        jax.block_until_ready(state)
    t_end = time.perf_counter()
    form = None
    if cap is not None:
        win_span.__exit__(None, None, None)
        form = trace.load_xplane(cap.stop())
        cap.discard()
    executables_after = step_fn._cache_size()
    # a traced run's window leaves out the pause in which the profiler starts
    # (0.1 s as a rule, 3 s seen once): no step could be dispatched in it
    window_s = t_end - t_start - spans.get("profiler_start", 0.0)

    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:ctx.chips])
    del state, m, pending
    gc.collect()

    # -- the comparison, once the window has closed and the state is freed ---
    t_ref = time.perf_counter()
    want = ref.train_reference(
        ctx.seed, model, opt, first_batches,
        rows_per_block=tr["reference_rows_per_block"],
        against=first_grad, keep_grad=keep_grad)
    del first_grad
    reference_s = time.perf_counter() - t_ref
    numbers = compare.training(
        {"losses": check_losses, "grad_norms": prog_grad,
         "delta_norms": prog_delta},
        want, ctx.limits)
    numbers["compiles_in_window"] = {
        "value": executables_after - executables_before, "limit": 0}

    return {
        "attempted": steps,
        "failed": 0,
        "numbers": numbers,
        "setup_s": setup_s,
        "window_s": window_s,
        "memory_peak_bytes": memory_peak,
        "end_to_end": {
            "train_tok_s": stats.rate(
                steps * tokens_per_step, t_start, t_end) / ctx.chips,
        },
        "facts": {  # what the per-layer readers read
            "steps": steps,
            "tokens": steps * tokens_per_step,
            "window_s": window_s,
            "spans_s": dict(spans),
            "train_flops_per_token": count.train_flops_per_token(
                model, tr["seq_len"]),
            "batch": tr["batch"], "seq_len": tr["seq_len"],
            "traced_steps": traced_steps,
            "profiler_start_s": spans.get("profiler_start", 0.0),
            "reference_s": reference_s,
        },
        "trace": form,
        "want": want,
        "first_batches": first_batches,
    }
