"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py        # on the TPU; anywhere else it exits non-zero

Drives the two main paths once, through the entry points a user calls, at
the full width of GPT-2 124M (12 layers, 768 wide, 12 heads, vocab 50257,
bf16) with random weights made from a seed:

- train:   ``Trainer`` (scripts/train_baseline.py's class) with bench.py's
           configuration — Pallas flash attention, "names" remat, B=8,
           T=1024 — for 20 steps over ``TokenShardLoader`` on the learnable
           synthetic stream.
- serve:   the trained weights behind ``ReplicaRouter`` ->
           ``PagedBatchedDecodeEngine`` -> ``ServingServer`` on an
           ephemeral port (the stack scripts/serve.py builds), a dozen
           mixed greedy/sampled HTTP requests, one over SSE.
- kernels: the Pallas paged-attention decode kernel COMPILED (never
           interpreted) for bf16 and int8 pages at the gpt2 and llama3-1b
           head geometries against the XLA reference, and the latent
           family's decode kernel at Kimi-K2.5's widths (64 heads, pages
           of 64 x 640 bf16) against the gathered window; one period of
           granite-4.0-h-micro at its published widths (recurrent state a
           row beside paged KV) on a ragged batch against the float32
           reference, and its state kernel (ops/ssm_kernel.py) against
           the plain step on 32 rows of a 36-layer leaf, microseconds a
           layer for both; then the serve requests again through an engine
           built with ``paged_attention="auto"``.
- four_chips (only when the machine shows >= 4 devices):
           ``DistributedTrainer`` ZeRO-3 over ``fsdp=4`` on the pjit and on
           the explicit path against the one-chip losses, then four
           one-chip serving replicas on four devices.

One process, no children: a chip belongs to one process at a time. Every
phase failure propagates to a non-zero exit. Times are printed as
information, labelled with the device — never under a metric name, and no
utilization is computed. The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``, printed only after every phase
passed on a TPU.

``--cpu-rehearsal`` is the builder's pre-flight for the script's own
control flow: toy sizes on four virtual CPU devices, Pallas kernels in
interpreter mode. It never engages by itself, proves nothing about the
chip, prints no result line and exits 2 even when every phase ran.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import functools
import gc
import importlib.metadata
import json
import os
import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

SEED = 0
PAGE_SIZE = 16
# The training stream draws from this many token ids while the model keeps
# its full vocabulary: 20 steps see 160k tokens, too few to learn anything
# about 50257 ids (the full-vocab stream moved the loss by 0.03 on the chip)
# and plenty to learn which 1024 occur (10.99 -> 7.5 on a CPU proxy).
DATA_VOCAB = 1024


@dataclasses.dataclass(frozen=True)
class Sizes:
    model: dict  # overrides on model_config("gpt2", dtype="bfloat16")
    batch: int
    seq_len: int
    steps: int
    loss_drop: float  # last step's loss must sit this far under the first
    slots: int
    max_len: int
    n_requests: int
    prompt_range: tuple[int, int]  # inclusive
    new_range: tuple[int, int]
    kernel_batch: int
    kernel_pages: int  # block-table width (max_len / PAGE_SIZE)
    head_dim: int


# Full width and depth of GPT-2 124M; B/T are bench.py's.
CHIP = Sizes(
    model={}, batch=8, seq_len=1024, steps=20, loss_drop=1.0,
    slots=8, max_len=1024, n_requests=12,
    prompt_range=(32, 512), new_range=(32, 64),
    kernel_batch=8, kernel_pages=64, head_dim=64,
)
REHEARSAL = Sizes(
    model=dict(n_layer=2, n_embd=64, n_head=2, n_ctx=128, vocab_size=512),
    batch=8, seq_len=64, steps=6, loss_drop=0.0,
    slots=4, max_len=128, n_requests=5,
    prompt_range=(8, 40), new_range=(4, 8),
    kernel_batch=4, kernel_pages=4, head_dim=16,
)

# (name, query heads, kv heads, page, scale: None is D^-1/2) at head_dim
# 64 and a table of 64 pages; granite's multiplier is its published one.
HEAD_GEOMETRIES = (
    ("gpt2-large", 20, 20, 16, None),
    ("llama3-1b", 32, 8, 16, None),
    ("granite-4.0-h-micro", 32, 8, 64, 0.015625),
)

# Compiled paged kernel vs the exact-f32 reference. The CPU tests hold the
# INTERPRETED kernel to 1e-5 (tests/test_serving_paged.py, test_quant.py);
# on the chip its f32 dots run on the MXU at default precision, which
# rounds operands to bf16 (8 mantissa bits), and the output is rounded to
# the bf16 query dtype: measured 3.4e-3..7.8e-3 on outputs of magnitude
# <= 4 (my chip run, PR 21), f32 queries no better than bf16 ones; the
# kernel as rebuilt by PR 35 reads 1.3e-3..4.3e-3 on bf16 pages and
# 7.5e-3..7.8e-3 on int8 pages (my chip run, PR 35). The bound is two bf16
# ulps at that magnitude; a wrong page, head or mask is an O(1) error.
PAGED_KERNEL_ATOL = 2e-2

# The granitemoehybrid period in bfloat16 against the float32 reference of
# the same weights: bf16 activations through ten layers move a logit by
# under a tenth of the logits' spread (my chip run, PR 35: 0.064 x std
# through the gather and 0.065 through the paged kernel, with the draws the
# reference has since PR 34's review; 0.086 with that PR's first draws; the
# bound is relative); a state not reset, a tail dropped or a padded tail run
# on is an error of the spread itself.
HYBRID_STATE_RTOL = 0.25

# ops/ssm_kernel.py against the plain step, both float32 on the vector unit:
# the state is one product and one sum an entry (the same roundings), y sums
# 128 products of order 1 in another order (my chip run, PR 37: 7.6e-5 on y
# summed over 36 layers, 0 on the state). A wrong row, head or group is an
# error of order 1.
STATE_STEP_ATOL = 1e-3

# One-chip vs four-chip per-step loss: the same data, weights and f32
# master state, but bf16 activations summed in another order (per-chip
# batch 2, gradients reduce-scattered), and the difference compounds over
# the steps. Seen on the chip: <= 7e-4 over 11 steps on losses of 8..11
# (my chip run, PR 21). A shard that missed its gradients parts by more
# than this within a few steps.
FOUR_CHIP_LOSS_ATOL = 0.02


def info(device_label: str, msg: str) -> None:
    print(f"info[{device_label}]: {msg}", flush=True)


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------


def model_cfg(sizes: Sizes):
    from pytorch_distributed_tpu.config import model_config

    return model_config("gpt2", dtype="bfloat16").replace(
        attention_impl="flash",
        remat="names",
        logits_dtype="bfloat16",
        attn_pdrop=0.0,
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        **sizes.model,
    )


def train_cfg(sizes: Sizes, data_parallel: int = 1):
    from pytorch_distributed_tpu.config import TrainConfig

    return TrainConfig(
        global_batch_size=sizes.batch,
        micro_batch_size=sizes.batch // data_parallel,
        num_steps=sizes.steps,
        learning_rate=3e-4,
        seed=SEED,
        log_every_n_steps=1,  # per-step losses: the four-chip phase compares
    )


def shard_paths(sizes: Sizes, vocab_size: int) -> list[str]:
    """A learnable Markov shard from a seed (uniform tokens do not learn),
    generated under .cache/data — nothing git does not list is read. ONE
    shard holding every step: TokenShardLoader and
    DistributedTokenShardLoader switch shards under different conditions
    (T vs world*B*T tokens left), so across a boundary their streams part
    even at world_size 1, and the four-chip phase compares losses step by
    step."""
    from pytorch_distributed_tpu.data import make_synthetic_shards

    n_tokens = (sizes.steps + 2) * sizes.batch * sizes.seq_len
    vocab = min(vocab_size, DATA_VOCAB)
    return make_synthetic_shards(
        REPO / ".cache" / "data" / f"chip_smoke_v{vocab}_n{n_tokens}_s{SEED}",
        num_shards=1,
        tokens_per_shard=n_tokens,
        vocab_size=vocab,
        seed=SEED,
    )


def run_trainer(trainer, loader, sizes: Sizes, label: str, device_label: str):
    """init_state + train; returns (state, per-step losses, executables
    the step compiled). Checks what holds for every trainer: finite
    falling loss and the step count."""
    import jax
    import numpy as np

    state = trainer.init_state()
    t0 = time.perf_counter()
    state, history = trainer.train(loader, state=state)
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in history]
    elapsed = [h["elapsed_s"] for h in history]
    assert len(losses) == sizes.steps, (label, len(losses))
    assert all(np.isfinite(losses)), (label, losses)
    assert losses[-1] < losses[0] - sizes.loss_drop, (
        f"{label}: loss did not fall by {sizes.loss_drop}: {losses}"
    )
    assert int(jax.device_get(state.step)) == sizes.steps, label
    n_exec = trainer.train_step._cache_size()
    steady = np.diff(elapsed[2:]) * 1e3  # steps 4.. : no compile inside
    info(
        device_label,
        f"{label}: first step (compile included) {elapsed[0]:.1f} s, "
        f"second {elapsed[1] - elapsed[0]:.2f} s, then median "
        f"{np.median(steady):.1f} ms/step over {len(steady)} steps (each "
        f"with a host read of the loss); {wall:.1f} s in all; {n_exec} "
        f"executable(s); loss {losses[0]:.3f} -> {losses[-1]:.3f}",
    )
    return state, losses, n_exec


def lower_step(trainer, state, sizes: Sizes):
    """The trainer's step lowered again on a zero batch of the trained
    shapes (lowering consumes nothing and adds no executable)."""
    import jax
    import numpy as np

    batch = trainer._put_batch({
        k: np.zeros((1, sizes.batch, sizes.seq_len), np.int32)
        for k in ("inputs", "targets")
    })
    return trainer.train_step.lower(state, batch, jax.random.key(SEED))


def phase_train(sizes: Sizes, rehearsal: bool, device_label: str):
    from pytorch_distributed_tpu.data import TokenShardLoader
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.train import Trainer

    cfg = model_cfg(sizes)
    trainer = Trainer(get_model(cfg), cfg, train_cfg(sizes))
    loader = TokenShardLoader(
        shard_paths(sizes, cfg.vocab_size), sizes.batch, sizes.seq_len
    )
    state, losses, n_exec = run_trainer(
        trainer, loader, sizes, "train (one chip)", device_label
    )
    assert n_exec == 1, f"{n_exec} train-step executables, expected one"
    if not rehearsal:
        # The compiled step must hold the Pallas kernels, not the XLA
        # blockwise fallback that every off-chip run takes.
        text = lower_step(trainer, state, sizes).as_text()
        kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
        assert {"flash_mha_fwd", "flash_mha_bwd"} <= kernels, kernels
        assert "blockwise_attention" not in text
        print(f"train: Mosaic kernels in the step: {sorted(kernels)}")
    print(f"train: PASS ({sizes.steps} steps, one executable)")
    return cfg, state, losses


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------


def make_requests(sizes: Sizes, vocab_size: int) -> list[dict]:
    """A seeded mix: even requests greedy, odd sampled; request 1 streams."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    reqs = []
    for i in range(sizes.n_requests):
        n_prompt = int(rng.integers(sizes.prompt_range[0],
                                    sizes.prompt_range[1] + 1))
        body = {
            "prompt": [int(t) for t in rng.integers(0, vocab_size, n_prompt)],
            "max_new_tokens": int(rng.integers(sizes.new_range[0],
                                               sizes.new_range[1] + 1)),
        }
        if i % 2:
            body.update(temperature=0.8, top_k=50, seed=SEED + i)
        if i == 1:
            body["stream"] = True
        reqs.append(body)
    return reqs


async def http(host: str, port: int, method: str, path: str, body=None):
    """One request over a fresh connection -> (status, body bytes)."""
    reader, writer = await asyncio.open_connection(host, port)
    payload = b"" if body is None else json.dumps(body).encode()
    writer.write(
        (f"{method} {path} HTTP/1.1\r\nHost: smoke\r\n"
         f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload
    )
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(), 600)
    writer.close()
    await writer.wait_closed()
    head, _, rest = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), rest


def parse_sse(raw: bytes) -> tuple[list[int], dict]:
    """An SSE body -> (streamed tokens, the terminal ``done`` result)."""
    tokens, done = [], None
    for block in raw.decode().split("\n\n"):
        event, data = "message", None
        for line in block.strip().split("\n"):
            if line.startswith("event:"):
                event = line[len("event:"):].strip()
            elif line.startswith("data:"):
                data = json.loads(line[len("data:"):].strip())
        if data is None:
            continue
        if event == "done":
            done = data
        else:
            tokens.append(data["token"])
    assert done is not None, "SSE stream ended without its done event"
    return tokens, done


async def drive_server(server, requests: list[dict]):
    """Start the server, send every request at once, read /healthz, stop.
    Returns (results in request order, healthz dict, seconds serving)."""
    host, port = await server.start()
    try:
        t0 = time.perf_counter()
        replies = await asyncio.gather(*(
            http(host, port, "POST", "/v1/generate", body)
            for body in requests
        ))
        wall = time.perf_counter() - t0
        status, raw = await http(host, port, "GET", "/healthz")
        assert status == 200, status
        health = json.loads(raw)
    finally:
        await server.stop()
    results = []
    for body, (status, raw) in zip(requests, replies):
        assert status == 200, (status, raw[:300])
        if body.get("stream"):
            streamed, res = parse_sse(raw)
            assert streamed == res["tokens"][len(body["prompt"]):], (
                "SSE data events disagree with the terminal result"
            )
        else:
            res = json.loads(raw)
        results.append(res)
    return results, health, wall


def serve_requests(params, cfg, sizes: Sizes, requests, devices, *,
                   paged_attention: str | None, label: str,
                   device_label: str):
    """Router -> paged engines (one per device given) -> HTTP server; every
    request must finish DONE at its full length with zero compiles after
    warmup. Returns (results, the engines)."""
    from pytorch_distributed_tpu.serving.engine import (
        PagedBatchedDecodeEngine,
    )
    from pytorch_distributed_tpu.serving.router import ReplicaRouter
    from pytorch_distributed_tpu.serving.server import ServingServer

    extra = {} if paged_attention is None else {
        "paged_attention": paged_attention
    }

    engines = []

    def make_engine(rep_id: int):
        engines.append(PagedBatchedDecodeEngine(
            cfg, slots=sizes.slots, max_len=sizes.max_len,
            page_size=PAGE_SIZE, device=devices[rep_id], **extra,
        ))
        return engines[-1]

    router = ReplicaRouter(make_engine, len(devices))
    t0 = time.perf_counter()
    n_programs = router.warmup(params)
    warm_s = time.perf_counter() - t0
    server = ServingServer(router, params, port=0)
    results, health, wall = asyncio.run(drive_server(server, requests))

    for body, res in zip(requests, results):
        want = len(body["prompt"]) + body["max_new_tokens"]
        assert res["state"] == "DONE", (label, res["state"], res["reason"])
        assert len(res["tokens"]) == want, (label, len(res["tokens"]), want)
        assert res["tokens"][:len(body["prompt"])] == body["prompt"], label
    steady = router.steady_compiles()
    assert not any(steady.values()), f"{label}: compiles after warmup {steady}"
    visible = {d.id for d in devices}
    placed = {
        rep: tuple(r["device_ids"]) for rep, r in health["replicas"].items()
    }
    assert all(r["state"] == "HEALTHY" for r in health["replicas"].values())
    assert {i for ids in placed.values() for i in ids} == visible, placed
    assert len(set(placed.values())) == len(devices), placed

    new_tokens = sum(b["max_new_tokens"] for b in requests)
    ticks = [
        r["tick_ema_s"] for r in health["replicas"].values()
        if r["tick_ema_s"] is not None
    ]
    info(
        device_label,
        f"{label}: warmup {warm_s:.1f} s for {n_programs} programs on "
        f"{len(devices)} replica(s); {len(requests)} requests "
        f"({new_tokens} new tokens) answered in {wall:.2f} s; router tick "
        f"EMA {', '.join(f'{t * 1e3:.1f}' for t in ticks)} ms; /healthz "
        f"device_ids {sorted(placed.values())}",
    )
    return results, engines


def phase_serve(params, cfg, sizes: Sizes, requests, device_label: str):
    import jax
    import numpy as np

    from pytorch_distributed_tpu.serving.engine import DecodeEngine

    results, _ = serve_requests(
        params, cfg, sizes, requests, jax.devices()[:1],
        paged_attention=None,  # the engine's default
        label="serve", device_label=device_label,
    )
    # One greedy request against the serial engine. The first generated
    # token must agree; bf16 near-ties may part later, so the rest of the
    # agreement is printed, not asserted.
    idx = next(
        i for i, b in enumerate(requests)
        if "temperature" not in b and not b.get("stream")
    )
    body = requests[idx]
    n_prompt = len(body["prompt"])
    serial = DecodeEngine(cfg, max_len=sizes.max_len).generate(
        params, np.asarray([body["prompt"]], np.int32),
        body["max_new_tokens"],
    )
    ref = [int(t) for t in np.asarray(serial)[0]]
    got = results[idx]["tokens"]
    assert got[n_prompt] == ref[n_prompt], (
        f"first generated token: served {got[n_prompt]}, serial "
        f"{ref[n_prompt]}"
    )
    same = sum(a == b for a, b in zip(got[n_prompt:], ref[n_prompt:]))
    print(
        f"serve: request {idx} (greedy, prompt {n_prompt}) agrees with "
        f"DecodeEngine.generate on {same}/{body['max_new_tokens']} "
        f"generated tokens (first token equal)"
    )
    print(f"serve: PASS ({len(requests)} requests DONE, 0 steady compiles)")
    return results


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def paged_case(rng, sizes: Sizes, h: int, hkv: int, page: int,
               quantized: bool):
    """bf16 queries (what the engine passes), random pages and a ragged
    batch: depth 0, a page boundary on either side, a mid-depth row and
    the deepest row the table allows."""
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.ops.quant import quantize_kv

    b, n_pages, d = sizes.kernel_batch, sizes.kernel_pages, sizes.head_dim
    pool = b * n_pages + 1  # the engine's default pool incl. scratch page 0
    max_pos = n_pages * page - 1
    lengths = np.resize(
        np.asarray(
            [0, page - 1, page, max_pos // 2, max_pos], np.int32
        ),
        b,
    )
    tables = np.zeros((b, n_pages), np.int32)
    free = rng.permutation(np.arange(1, pool))  # pages scattered in the pool
    used = 0
    for i, ln in enumerate(lengths):
        need = int(ln) // page + 1
        tables[i, :need] = free[used:used + need]
        used += need
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.bfloat16)
    kf = jnp.asarray(rng.normal(size=(pool, page, hkv, d)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(pool, page, hkv, d)), jnp.float32)
    if quantized:
        (k, ks), (v, vs) = quantize_kv(kf), quantize_kv(vf)
        scales = dict(k_scales=ks, v_scales=vs)
    else:
        k, v, scales = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16), {}
    # the pool's stored shape: the heads merged head-major on the minor axis
    k, v = (x.reshape(pool, page, hkv * d) for x in (k, v))
    return q, k, v, jnp.asarray(tables), jnp.asarray(lengths), scales


def latent_kernel_case(rehearsal: bool):
    """ops/latent_paged_kernel.py against models/kimi_k2.attend_window on a
    ragged batch at the kimi-k2.5-ep32 cell's shapes (64 heads, 640 lanes,
    pages of 64, tables of 64 pages, blocks of 8; toy shapes in rehearsal):
    depth 0, either side of a page and of a block boundary, mid-depth rows,
    the deepest the table allows, a free row; layer 1 of a stacked pool,
    pages scattered. Returns max |kernel - f32 gather|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.models.kimi_k2 import attend_window
    from pytorch_distributed_tpu.ops.latent_paged_kernel import (
        latent_paged_decode,
    )

    h, w, c, page, n_pages, bp = (
        (4, 128, 64, 8, 8, 2) if rehearsal else (64, 640, 512, 64, 64, 8)
    )
    block, max_pos = bp * page, n_pages * page - 1
    depths = [0, page - 1, page, block - 1, block, max_pos // 3,
              max_pos // 2, max_pos]
    rng = np.random.default_rng(SEED)
    b = len(depths) + 1  # the last row is free: depth 0, table all scratch
    pool_pages = b * n_pages + 1
    pool = jnp.asarray(
        rng.normal(size=(2, pool_pages, page, w)), jnp.bfloat16)
    q = jnp.asarray(0.3 * rng.normal(size=(b, h, w)), jnp.bfloat16)
    free = rng.permutation(np.arange(1, pool_pages))
    tables = np.zeros((b, n_pages), np.int32)
    pos = np.zeros((b,), np.int32)
    used = 0
    for i, depth in enumerate(depths):
        need = depth // page + 1
        tables[i, :need] = free[used:used + need]
        used += need
        pos[i] = depth
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    scale = 0.5 * w ** -0.5
    out = latent_paged_decode(
        q, pool, 1, tables, pos, scale=scale, out_width=c, block_pages=bp,
        interpret=rehearsal,  # on the chip: compiled, always
    )
    with jax.default_matmul_precision("highest"):
        ref = attend_window(
            q[:, None].astype(jnp.float32), pool.astype(jnp.float32), 1,
            tables, pos, scale,
        )[:, 0, :, :c]
    out = np.asarray(out.astype(jnp.float32))
    assert out.shape == (b, h, c) and np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, rtol=0, atol=PAGED_KERNEL_ATOL)
    return float(np.max(np.abs(out - np.asarray(ref))))


def hybrid_state_case(rehearsal: bool):
    """One period [m m m m m a m m m m] of the granitemoehybrid family at
    granite-4.0-h-micro's published widths (a vocabulary of 8192; toy sizes
    in rehearsal), chunked prefill then three decode steps through
    ``decode.forward`` on the state rows of ONE cache, against the float32
    reference's full forward of the same bfloat16 weights; the decode steps
    twice over, their attention layer reading its pages through the gathered
    window with the plain state step, and through ops/paged_kernel.py with
    the Mamba layers' state advanced by ops/ssm_kernel.py (the decode step's
    rows are then the leaf's first rows, as in the engine's). The rows: 0
    free throughout; 1 a prompt shorter than a chunk (from depth 0, a padded
    chunk); 2 and 3 either side of a chunk boundary (chunk - 1 and chunk + 1
    tokens: the second chunk holds ONE token); 4 a REUSED row, which another
    prompt ran through first. Returns ({paged_impl: max |program -
    reference| over the logits that choose a token}, the reference logits'
    std)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.reference import granitemoehybrid as ref
    from pytorch_distributed_tpu.config import model_config
    from pytorch_distributed_tpu.models import decode

    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    if rehearsal:
        sizes = dict(
            vocab_size=128, n_embd=32, n_head=4, n_kv_head=2,
            mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
            mamba_chunk_size=8, shared_intermediate_size=48)
        chunk, page, dtype = 8, 4, "float32"
    else:
        sizes, chunk, page, dtype = dict(vocab_size=8192), 256, 64, "bfloat16"
    cfg = model_config(
        "granite-4.0-h-micro", n_layer=10, layer_types=tuple(period),
        n_ctx=4 * chunk, dtype=dtype, param_dtype=dtype, **sizes)
    model = dict(
        hidden_size=cfg.n_embd, vocab_size=cfg.vocab_size,
        layer_types=period, num_attention_heads=cfg.n_head,
        num_key_value_heads=cfg.kv_heads,
        shared_intermediate_size=cfg.shared_intermediate_size,
        mamba_n_heads=cfg.mamba_n_heads, mamba_d_head=cfg.mamba_d_head,
        mamba_d_state=cfg.mamba_d_state, mamba_n_groups=cfg.mamba_n_groups,
        mamba_d_conv=cfg.mamba_d_conv, rms_norm_eps=cfg.layer_norm_epsilon,
        embedding_multiplier=cfg.embedding_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling)
    params = ref.init_params(SEED, model, dtype)
    rng = np.random.default_rng(SEED)
    lengths = {1: chunk // 3, 2: chunk - 1, 3: chunk + 1, 4: chunk + chunk // 2}
    steps, rows, n_pages = 3, 5, 4 * chunk // page
    ids = {r: rng.integers(0, cfg.vocab_size, n + steps).astype(np.int32)
           for r, n in lengths.items()}
    cache = decode.init_paged_cache(
        cfg, rows * n_pages + 1, page, rows=rows)
    tables = np.zeros((rows, n_pages), np.int32)
    tables[1:] = 1 + np.arange((rows - 1) * n_pages).reshape(rows - 1, -1)
    tables = jnp.asarray(tables)

    @functools.partial(jax.jit, static_argnames="paged_impl")
    def forward(params, cache, toks, pos, live, state_rows,
                paged_impl="gather"):
        return decode.forward(
            params, toks, cfg, cache, pos, block_tables=(
                tables if state_rows is None else tables[state_rows]),
            live=live, state_rows=state_rows, paged_impl=paged_impl)

    def call(*args, **kw):  # the weights an ARGUMENT: closed over, they are
        return forward(params, *args, **kw)  # 1.6 GB of constants

    def prefill(cache, wanted):
        """Every row of ``wanted`` {row: tokens} chunk by chunk, all rows in
        each call (a row whose prompt has ended rides along dead); returns
        the logits of each row's last token."""
        order = jnp.asarray(sorted(wanted), jnp.int32)
        last = {}
        for start in range(0, max(map(len, wanted.values())), chunk):
            toks = np.zeros((len(wanted), chunk), np.int32)
            live = np.zeros((len(wanted), chunk), bool)
            for j, r in enumerate(sorted(wanted)):
                n = max(0, min(chunk, len(wanted[r]) - start))
                toks[j, :n] = wanted[r][start:start + n]
                live[j, :n] = True
            lg, cache = call(
                cache, jnp.asarray(toks), jnp.full((len(wanted),), start),
                jnp.asarray(live), order)
            for j, r in enumerate(sorted(wanted)):
                n = len(wanted[r]) - start
                if 0 < n <= chunk:
                    last[r] = np.asarray(lg[j, n - 1], np.float32)
        return cache, last

    # row 4's first tenant, then everybody's own prompt from position 0
    cache, _ = prefill(cache, {4: rng.integers(
        0, cfg.vocab_size, chunk + 5).astype(np.int32)})
    cache, got = prefill(
        cache, {r: ids[r][:n] for r, n in lengths.items()})
    prefilled, first = cache, got
    want = {r: np.asarray(ref.logits_at(
        params, jnp.asarray(ids[r][None]), n - 1, steps + 1, model))
        for r, n in lengths.items()}
    errs = {}
    for impl in ("gather", "kernel_interpret" if rehearsal else "kernel"):
        cache, got = prefilled, {r: [lg] for r, lg in first.items()}
        for step in range(steps):  # lane = row; lane 0 holds no token
            toks = np.zeros((rows, 1), np.int32)
            pos = np.zeros((rows,), np.int32)
            for r, n in lengths.items():
                toks[r, 0], pos[r] = ids[r][n + step], n + step
            lg, cache = call(
                cache, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(np.arange(rows)[:, None] > 0),
                jnp.arange(rows) if impl == "gather" else None,
                paged_impl=impl)
            for r in lengths:
                got[r].append(np.asarray(lg[r, 0], np.float32))
        assert not np.asarray(cache["ssm"][:, 0]).any()  # the free row
        assert not np.asarray(cache["conv"][:, :, 0], np.float32).any()
        assert all(np.isfinite(np.stack(got[r])).all() for r in lengths)
        errs[impl] = max(float(np.abs(np.stack(got[r]) - want[r]).max())
                         for r in lengths)
    return errs, float(np.mean([w.std() for w in want.values()]))


def state_step_case(rehearsal: bool):
    """ops/ssm_kernel.py against the plain step (``ops/ssm.ssm_step`` under
    the model's two selects and its update in place) at the
    granite-4.0-h-micro cell's shapes: 32 batch rows on a leaf of 36 layers
    x 33 rows x 64 heads x [64, 128] float32 (toy shapes in rehearsal), one
    lane dead, one row beginning its sequence, every layer advanced once by
    ONE program that scans them with the leaf as its carry (what the decode
    step does). Returns (max |kernel - plain| over the live rows' y, over
    the whole leaf, and microseconds a layer for both: None in rehearsal, a
    CPU's time is no device number)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.ops.ssm import ssm_step
    from pytorch_distributed_tpu.ops.ssm_kernel import ssm_state_step

    layers, b, h, p, n = (
        (3, 4, 8, 8, 16) if rehearsal else (36, 32, 64, 64, 128))
    rng = np.random.default_rng(SEED)
    live = np.ones(b, bool)
    live[b // 2] = False
    fresh = np.zeros(b, bool)
    fresh[1] = True
    live, fresh = jnp.asarray(live), jnp.asarray(fresh)
    x = jnp.asarray(rng.standard_normal((b, h, p)), jnp.bfloat16)
    dt = jnp.where(live[:, None], jnp.asarray(
        rng.uniform(1e-4, 1e-2, (b, h)), jnp.float32), 0.0)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    bm, cm = (jnp.asarray(rng.standard_normal((b, 1, n)), jnp.bfloat16)
              for _ in range(2))
    leaf0 = rng.standard_normal((layers, b + 1, h, p, n), np.float32)

    def plain(leaf, layer):
        state = leaf[layer, :b]
        y, new = ssm_step(x, dt, a, bm, cm, jnp.where(
            fresh[:, None, None, None], 0.0, state))
        new = jnp.where(live[:, None, None, None], new, state)
        return y, leaf.at[layer, :b].set(new)

    def kernel(leaf, layer):
        return ssm_state_step(
            x, dt, a, bm, cm, leaf, layer, live, fresh, interpret=rehearsal)

    def every_layer(step):
        def body(leaf, layer):
            y, leaf = step(leaf, layer)
            return leaf, y

        return jax.jit(lambda leaf: jax.lax.scan(
            body, leaf, jnp.arange(layers, dtype=jnp.int32)),
            donate_argnums=0)

    out, us = {}, {}
    for name, step in (("plain", plain), ("kernel", kernel)):
        run = every_layer(step)
        leaf, y = run(jnp.asarray(leaf0))
        out[name] = (np.asarray(y), np.asarray(leaf))
        if not rehearsal:
            for _ in range(3):
                leaf, y = run(leaf)
            jax.block_until_ready(leaf)
            t0 = time.perf_counter()
            for _ in range(10):
                leaf, y = run(leaf)
            jax.block_until_ready(leaf)
            us[name] = (time.perf_counter() - t0) / (10 * layers) * 1e6
        del leaf, y
    (y0, l0), (y1, l1) = out["plain"], out["kernel"]
    on = np.asarray(live)
    dead = ~np.append(on, False)  # the dead lane and the scratch row
    for got in (l0, l1):  # bit for bit, through both
        assert (got[:, dead] == leaf0[:, dead]).all()
    assert np.isfinite(y1).all()
    return (float(np.abs(y1 - y0)[:, on].max()), float(np.abs(l1 - l0).max()),
            us.get("plain"), us.get("kernel"))


def phase_kernels(params, cfg, sizes: Sizes, requests, served,
                  rehearsal: bool, device_label: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu.ops.paged_kernel import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )

    rng = np.random.default_rng(SEED)
    for name, h, hkv, page, scale in HEAD_GEOMETRIES:
        for quantized in (False, True):
            q, k, v, tables, lengths, scales = paged_case(
                rng, sizes, h, hkv, page, quantized
            )
            out = paged_decode_attention(
                q, k, v, tables, lengths, **scales, scale=scale,
                interpret=rehearsal,  # on the chip: compiled, always
            )
            # The reference in f32 at full matmul precision over the very
            # values the kernel read (bf16/int8 upcast exactly).
            with jax.default_matmul_precision("highest"):
                ref = paged_decode_attention_reference(
                    q.astype(jnp.float32),
                    k if quantized else k.astype(jnp.float32),
                    v if quantized else v.astype(jnp.float32),
                    tables, lengths, *scales.values(), scale=scale,
                )
            out = np.asarray(out.astype(jnp.float32))
            assert out.shape == (sizes.kernel_batch, h, sizes.head_dim)
            assert np.isfinite(out).all()
            err = float(np.max(np.abs(out - np.asarray(ref))))
            pages = "int8" if quantized else "bf16"
            print(
                f"kernels: paged_decode_attention {name} H={h} Hkv={hkv} "
                f"page={page} pages={pages}: max |kernel - f32 reference| "
                f"= {err:.2e} (bound {PAGED_KERNEL_ATOL:g})"
            )
            np.testing.assert_allclose(
                out, ref, rtol=0, atol=PAGED_KERNEL_ATOL
            )

    err = latent_kernel_case(rehearsal)
    print(
        f"kernels: latent_paged_decode on ragged depths: max |kernel - f32 "
        f"gathered window| = {err:.2e} (bound {PAGED_KERNEL_ATOL:g})"
    )

    errs, std = hybrid_state_case(rehearsal)
    for impl, err in errs.items():
        print(
            f"kernels: granitemoehybrid period on a ragged batch (a free "
            f"row, a padded chunk, either side of a chunk boundary, a "
            f"reused row), decode steps through the {impl}: max |program - "
            f"f32 reference| = {err:.2e} on logits of std {std:.2e} (bound "
            f"{HYBRID_STATE_RTOL:g} x std)"
        )
        assert err <= HYBRID_STATE_RTOL * std, (impl, err, std)

    dy, ds, us_plain, us_kernel = state_step_case(rehearsal)
    took = ("not measured (a rehearsal)" if rehearsal else
            f"{us_kernel:.0f} us a layer, the plain step {us_plain:.0f}")
    print(
        f"kernels: ssm_state_step on 32 rows of a 36-layer leaf (a dead "
        f"lane, a row begun anew): max |kernel - plain step| = {dy:.2e} on "
        f"y, {ds:.2e} on the state (bound {STATE_STEP_ATOL:g}); {took}"
    )
    assert max(dy, ds) <= STATE_STEP_ATOL, (dy, ds)
    # with nothing set, an engine of that family takes both kernels on the
    # chip (an engine allocates and compiles nothing until it is warmed)
    from pytorch_distributed_tpu.config import model_config
    from pytorch_distributed_tpu.serving.engine import (
        PagedBatchedDecodeEngine,
    )

    built = PagedBatchedDecodeEngine(
        model_config("granite-4.0-h-micro", dtype="bfloat16"), slots=2,
        max_len=128, page_size=64).stats()
    want = ("gather", "xla") if rehearsal else ("kernel", "kernel")
    assert (built["paged_decode_impl"], built["state_step_impl"]) == want, (
        built["paged_decode_impl"], built["state_step_impl"])
    print(f"kernels: a granite-4.0-h-micro engine with nothing set is "
          f"built with paged_decode_impl {want[0]!r}, state_step_impl "
          f"{want[1]!r}")

    # The same requests through an engine that picks its own paged
    # attention: on the chip "auto" must mean the compiled kernel.
    results, (engine,) = serve_requests(
        params, cfg, sizes, requests, jax.devices()[:1],
        paged_attention="kernel_interpret" if rehearsal else "auto",
        label="kernels (paged_attention=auto)", device_label=device_label,
    )
    if not rehearsal:
        text = engine.program("decode_step").lower(
            *engine.example_args("decode_step", params)
        ).as_text()
        kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
        assert "paged_decode_attention" in kernels, kernels
        print(f"kernels: decode step under 'auto' holds {sorted(kernels)}")
    first_equal = 0
    greedy = [i for i, b in enumerate(requests) if "temperature" not in b]
    for i in greedy:
        n_prompt = len(requests[i]["prompt"])
        first_equal += (
            results[i]["tokens"][n_prompt] == served[i]["tokens"][n_prompt]
        )
    print(
        f"kernels: kernel engine agrees with the gather engine on the "
        f"first generated token of {first_equal}/{len(greedy)} greedy "
        f"requests (online-softmax reorders the bf16 sum)"
    )
    print(
        f"kernels: PASS ({2 * len(HEAD_GEOMETRIES) + 1} kernel cases, engine "
        f"served every request)"
    )


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def phase_four_chips(params, cfg, sizes: Sizes, requests, one_chip_losses,
                     rehearsal: bool, device_label: str):
    import jax
    import numpy as np

    from pytorch_distributed_tpu.config import MeshConfig
    from pytorch_distributed_tpu.data import DistributedTokenShardLoader
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.train.distributed_trainer import (
        DistributedTrainer,
    )

    devices = jax.devices()[:4]
    mesh_cfg = MeshConfig(fsdp=4, strategy="full_shard")
    mesh = make_mesh(mesh_cfg, devices=devices)
    for path in ("auto", "explicit"):
        label = f"four_chips ZeRO-3 path={path}"
        trainer = DistributedTrainer(
            get_model(cfg), cfg, train_cfg(sizes, data_parallel=4),
            mesh, mesh_cfg, path=path,
        )
        loader = DistributedTokenShardLoader(
            shard_paths(sizes, cfg.vocab_size), sizes.batch, sizes.seq_len,
            rank=0, world_size=1,
        )
        state, losses, _ = run_trainer(
            trainer, loader, sizes, label, device_label
        )
        diff = np.abs(np.asarray(losses) - np.asarray(one_chip_losses))
        print(
            f"{label}: max |loss - one-chip loss| over {sizes.steps} steps "
            f"= {diff.max():.4f} (bound {FOUR_CHIP_LOSS_ATOL})"
        )
        assert diff.max() <= FOUR_CHIP_LOSS_ATOL, (losses, one_chip_losses)
        spans = {
            len(leaf.sharding.device_set) for leaf in jax.tree.leaves(state)
        }
        assert spans == {4}, f"{label}: state leaves span {spans} devices"
        if not rehearsal:
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
            assert all(b > 0 for b in in_use), in_use
            print(f"{label}: bytes_in_use per chip {in_use}")
            # Each chip's flash kernel must see ITS rows (B/4), with the
            # ZeRO-3 collectives around it — not q/k/v gathered to the
            # full batch in front of a replicated custom call.
            text = lower_step(trainer, state, sizes).compile().as_text()
            calls = [
                line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line
            ]
            assert len(calls) >= 2, len(calls)
            for line in calls:
                lead = int(re.search(r"= \(?\w+\[(\d+),", line).group(1))
                assert lead == sizes.batch // 4, line[:200]
            # (XLA:TPU fuses reduce-scatter into "all-reduce-scatter".)
            assert "all-gather" in text and "reduce-scatter" in text
            print(
                f"{label}: {len(calls)} Mosaic calls, each on batch "
                f"{sizes.batch // 4} of {sizes.batch}"
            )
        del state, trainer
        gc.collect()

    serve_requests(
        params, cfg, sizes, requests, devices,
        paged_attention=None, label="four_chips replicas",
        device_label=device_label,
    )
    print("four_chips: PASS (pjit + explicit ZeRO-3, four replicas)")


# --------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="builder's pre-flight on the CPU at toy sizes; proves nothing "
             "about the chip, prints no result line, exits 2",
    )
    args = ap.parse_args()
    if args.cpu_rehearsal:
        print("=" * 72)
        print("CPU REHEARSAL — toy sizes, interpreted kernels, NOT a chip run")
        print("=" * 72)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        )

    import jax
    import jaxlib

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu_rehearsal:
        print(
            f"chip_smoke: needs a TPU; jax.devices()[0].platform is "
            f"{device.platform!r}",
            file=sys.stderr,
        )
        return 1

    from pytorch_distributed_tpu.utils.compile_cache import (
        cache_entry_count,
        place_compile_cache,
    )

    n_devices = len(jax.devices())
    device_label = f"{device.device_kind} x{n_devices}"
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    cache_dir = place_compile_cache()
    entries_before = cache_entry_count(cache_dir)
    print(
        f"platform={device.platform} device_kind={device.device_kind!r} "
        f"count={n_devices} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}"
    )
    print(
        f"compile cache: {cache_dir or 'none (CPU-platform run)'} "
        f"({entries_before} entries before)"
    )

    sizes = REHEARSAL if args.cpu_rehearsal else CHIP
    t0 = time.perf_counter()
    cfg, state, losses = phase_train(sizes, args.cpu_rehearsal, device_label)
    params = state.params
    del state
    requests = make_requests(sizes, cfg.vocab_size)
    served = phase_serve(params, cfg, sizes, requests, device_label)
    phase_kernels(
        params, cfg, sizes, requests, served, args.cpu_rehearsal,
        device_label,
    )
    if n_devices >= 4:
        phase_four_chips(
            params, cfg, sizes, requests, losses, args.cpu_rehearsal,
            device_label,
        )
    else:
        print(f"four_chips: not run ({n_devices} device)")

    entries_after = cache_entry_count(cache_dir)
    print(
        f"compile cache: {entries_after} entries after "
        f"({entries_after - entries_before} written by this run)"
    )
    info(device_label, f"chip_smoke took {time.perf_counter() - t0:.0f} s")
    if args.cpu_rehearsal:
        print("CPU REHEARSAL finished: every phase ran. Not a chip result.")
        return 2
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": n_devices,
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
