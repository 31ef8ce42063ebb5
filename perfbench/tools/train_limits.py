"""Readings a training cell's limits are set from, many seeds in one process.

    python3 perfbench/tools/train_limits.py --workload <cell> --seeds 101,102,...

For each seed: the program's first three steps against the float32
reference (the LOWER readings: sound runs), then the control (the reference
in float8 put in the program's place) and each fault planted in the
reference, against the same float32 reference (the UPPER readings). Prints
one JSON line per seed and writes them all to ``--out``. Chip only.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import compare, reference, run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="chiprun_out/train_limits.jsonl")
    args = ap.parse_args()

    base, *_ = run.open_cell(args.workload, 0, 0.5)
    from perfbench.drivers import train

    ref = reference.of(base.config)

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as sink:
        for seed in (int(s) for s in args.seeds.split(",")):
            ctx = dataclasses.replace(base, seed=seed, t0=time.perf_counter())
            res = train.run(ctx, keep_grad=True)
            row = {"seed": seed, "limits": ctx.limits,
                   "program_correct": compare.verdict(res["numbers"]),
                   "program": {
                k: v["value"] for k, v in res["numbers"].items()},
                "at": {k: v.get("leaf") for k, v in res["numbers"].items()
                       if v.get("leaf")},
                "reference_s": res["facts"]["reference_s"]}
            want, batches = res["want"], res["first_batches"]
            ref_grad = want.pop("first_grad")
            want.pop("grad_diff_norms")  # that was the program's; now theirs
            model, opt = ctx.config["model"], ctx.traffic["optimizer"]
            for name, kw in (("control_fp8", dict(precision="fp8")),
                             ("fault_half_batch", dict(fault="half_batch")),
                             ("fault_unchanged_state",
                              dict(fault="unchanged_state")),
                             ("bf16_reference", dict(precision="bf16"))):
                t0 = time.perf_counter()
                try:
                    got = ref.train_reference(
                        seed, model, opt, batches,
                        rows_per_block=ctx.traffic["reference_rows_per_block"],
                        against=ref_grad, **kw)
                    nums = compare.training(got, want, ctx.limits)
                    row[name] = {k: v["value"] for k, v in nums.items()}
                    row[name + "_correct"] = compare.verdict(nums)
                    row[name + "_at"] = {
                        k: v.get("leaf") for k, v in nums.items()
                        if v.get("leaf")}
                except Exception as err:  # noqa: BLE001 - a control that crashes has failed
                    row[name] = {"error": f"{type(err).__name__}: {err}"[:300]}
                row[name + "_s"] = time.perf_counter() - t0
            line = json.dumps(row)
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
