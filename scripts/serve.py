"""Start the serving tier: HTTP/SSE front door over a replica router.

Brings up N engine replicas behind a ``ReplicaRouter`` and the asyncio
front door (serving/server.py) — the README serving-tier quickstart's
entry point. Weights follow scripts/generate.py's preference order
(--checkpoint, then --hf, else fresh random init — smoke mode where the
tokens are arbitrary but the tier is fully real: routing, SSE
streaming, failover, drain/restart all behave identically).

Try it (random-init smoke):

  python scripts/serve.py --preset tiny --replicas 2 --port 8077 &
  curl -s localhost:8077/healthz | python -m json.tool
  curl -sN localhost:8077/v1/generate -d \\
      '{"prompt": [1,2,3], "max_new_tokens": 16, "stream": true}'
  # kill a replica mid-stream; in-flight requests fail over and the
  # SSE stream keeps emitting tokens, bit-identical:
  curl -s localhost:8077/admin/kill -d '{"replica": 0}'
  curl -s localhost:8077/admin/restart -d '{"replica": 0}'

Engine flavour: ``--paged`` (default) serves
``PagedBatchedDecodeEngine`` replicas — page-pressure-aware admission
needs the paged pool; ``--dense`` serves the dense batched engine.
"""

from __future__ import annotations

import argparse
import asyncio
import sys

from _common import setup_platform  # noqa: F401  (sys.path side effect)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", default="tiny")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--hf", default=None, metavar="MODEL")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4,
                    help="slot rows per replica")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new-default", type=int, default=32)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--dense", action="store_true",
                    help="dense BatchedDecodeEngine replicas instead of "
                         "the default paged engine")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="per-replica engine admission bound (the router "
                         "sheds above 2x slots per replica regardless)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="register N LoRA tenants (tenant-0..tenant-N-1, "
                         "random nonzero factors — a real deployment "
                         "loads trained ones) on ONE shared registry so "
                         "/v1/generate accepts \"tenant\"; 0 = no "
                         "adapters")
    ap.add_argument("--lora-rank", type=int, default=8,
                    help="shared low-rank adapter rank (one rank for "
                         "every tenant — per-tenant ranks would be "
                         "per-tenant compiles)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8077)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-devices", type=int, default=0)
    args = ap.parse_args()
    setup_platform(args)

    import jax

    from pytorch_distributed_tpu.config import model_config
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.serving.engine import (
        BatchedDecodeEngine,
        BucketSpec,
        PagedBatchedDecodeEngine,
    )
    from pytorch_distributed_tpu.serving.router import ReplicaRouter
    from pytorch_distributed_tpu.serving.server import ServingServer

    cfg = model_config(args.preset).replace(
        embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0,
        n_ctx=max(args.max_len, 64),
    )
    # Weight loading mirrors scripts/generate.py exactly.
    if args.hf:
        from pytorch_distributed_tpu.models.hf_import import (
            from_hf_pretrained,
        )

        params, cfg = from_hf_pretrained(args.hf, None)
        cfg = cfg.replace(attn_pdrop=0.0, resid_pdrop=0.0, embd_pdrop=0.0)
    elif args.checkpoint:
        from pytorch_distributed_tpu.config import TrainConfig
        from pytorch_distributed_tpu.train.checkpoint import load_checkpoint
        from pytorch_distributed_tpu.train.optim import make_optimizer
        from pytorch_distributed_tpu.train.state import init_train_state

        tx = make_optimizer(TrainConfig(
            global_batch_size=1, micro_batch_size=1, num_steps=1,
            learning_rate=1e-4,
        ))
        template = init_train_state(
            get_model(cfg).init(jax.random.key(0), cfg), tx
        )
        params = load_checkpoint(args.checkpoint, template).params
    else:
        print(
            "no --checkpoint/--hf: serving a RANDOM-INIT model (smoke "
            "mode — the tier is real, the tokens are not)",
            file=sys.stderr,
        )
        params = get_model(cfg).init(jax.random.key(args.seed), cfg)

    max_new_cap = min(args.max_new_default * 4, args.max_len // 2)

    # ONE registry shared by every replica: tenant slots stay
    # consistent across failover adoption (serving/adapters.py).
    registry = None
    if args.tenants:
        from pytorch_distributed_tpu.serving.adapters import (
            AdapterRegistry,
        )

        registry = AdapterRegistry(
            cfg, rank=args.lora_rank, max_tenants=args.tenants
        )
        for i in range(args.tenants):
            registry.register(
                f"tenant-{i}",
                key=jax.random.fold_in(jax.random.key(args.seed), i),
            )
        print(
            f"registered {args.tenants} LoRA tenants "
            f"(rank={args.lora_rank}): "
            + ", ".join(registry.tenants()), file=sys.stderr,
        )

    devices = jax.devices()

    def make_engine(rep_id: int):
        # One process drives every visible chip: replica i lives on
        # device i mod n.
        device = devices[rep_id % len(devices)]
        if args.dense:
            return BatchedDecodeEngine(
                cfg, slots=args.slots, max_len=args.max_len,
                buckets=BucketSpec.powers_of_two(
                    args.max_len - max_new_cap, min_bucket=16
                ),
                queue_limit=args.queue_limit, adapters=registry,
                device=device,
            )
        return PagedBatchedDecodeEngine(
            cfg, slots=args.slots, max_len=args.max_len,
            page_size=args.page_size, queue_limit=args.queue_limit,
            adapters=registry, device=device,
        )

    router = ReplicaRouter(make_engine, args.replicas)
    print(
        f"warming {args.replicas} replicas "
        f"({'dense' if args.dense else 'paged'}, slots={args.slots}, "
        f"max_len={args.max_len})...", file=sys.stderr,
    )
    total = router.warmup(params)
    print(f"warm: {total} compiled programs across the fleet",
          file=sys.stderr)
    server = ServingServer(
        router, params, host=args.host, port=args.port,
        default_max_new=args.max_new_default,
    )
    try:
        asyncio.run(server.serve_forever())
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
