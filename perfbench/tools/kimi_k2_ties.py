"""How often rounding picks another expert: the kimi_k2 reference's router
choices in float32 against the same forward pass with matmul operands (and
the router's input) rounded to bfloat16, the precision the configuration
states. Where the 8th and 9th of a token's 384 scores nearly tie, the two
pick different experts, and the served logits then differ by a whole
expert's output: the widest sound gaps of the cell's comparison (PERF.md
section 2). Chip only (the published widths); one JSON line a seed.

    python3 perfbench/tools/kimi_k2_ties.py --config kimi-k2.5-ep32 --seeds 1,2 --tokens 2048
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--precision", default="bf16")
    ap.add_argument("--out", default="chiprun_out/kimi_k2_ties.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import reference

    config = json.loads(
        (ROOT / "perfbench" / "configs" / f"{args.config}.json").read_text())
    model = config["model"]
    ref = reference.of(config)
    held = range(model.get("expert_offset", 0),
                 model.get("expert_offset", 0) + model["n_routed_experts_held"])
    both = jax.jit(lambda p, ids: (
        ref.routes(p, ids, model, "f32"), ref.routes(p, ids, model, args.precision)))
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as sink:
        for seed in (int(s) for s in args.seeds.split(",")):
            params = ref.init_params(
                seed, model, config["program"]["serve_overrides"]["param_dtype"])
            ids = jnp.asarray(np.random.default_rng(seed).integers(
                0, model["vocab_size"], args.tokens), jnp.int32)
            full, low = (np.asarray(r) for r in both(params, ids))
            row = {"seed": seed, "tokens": args.tokens,
                   "precision": args.precision, "layers": []}
            for a, b in zip(full, low):  # [T, k] each
                differs = held_differs = 0
                for chosen_a, chosen_b in zip(a, b):
                    odd = set(chosen_a.tolist()) ^ set(chosen_b.tolist())
                    differs += bool(odd)
                    held_differs += bool(odd & set(held))
                row["layers"].append({
                    "tokens_with_another_choice": differs / len(a),
                    "tokens_with_another_choice_of_a_held_expert":
                        held_differs / len(a)})
            line = json.dumps(row)
            print(line, flush=True)
            sink.write(line + "\n")
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
