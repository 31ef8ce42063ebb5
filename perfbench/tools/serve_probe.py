"""Sweeps and readings for a serving cell, many runs in one process.

    python3 perfbench/tools/serve_probe.py --workload <cell> --rates 2,3,4 --seconds 30
    python3 perfbench/tools/serve_probe.py --workload <cell> --seeds 1,2,3 --seconds 10 --control fp8 [--fault altered_token]

The first form finds the knee: at each fixed rate (or client count, in a
closed loop) it reports the tails, the tokens per second and how many
requests were still in flight when the window closed. The second reads the
numbers ``correct`` compares on each seed, and beside them the control's: the
reference in float8 put in the program's place, at the same positions of
the same prompts and served tokens. Both go through the harness's own
comparison under the cell's limits (``correct``, ``control_correct``: the
control has to read false). ``--fault`` plants one of ``tests/faults.py``.
Chip only; prints one JSON line a run.
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

from perfbench import compare, run  # noqa: E402
from perfbench.tests import faults  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", default="chiprun_out/serve_probe.jsonl")
    args = ap.parse_args()

    base, *_ = run.open_cell(args.workload, 0, args.seconds)
    from perfbench.drivers import serve

    mix0 = base.traffic
    # an open loop's rate is requests per cycle over the cycle's seconds
    knob = "cycle_requests" if mix0["loop"] == "open" else "clients"
    plans = [(float(r), 9000 + i) for i, r in enumerate(
        x for x in args.rates.split(",") if x)]
    plans += [(None, int(s)) for s in args.seeds.split(",") if s]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as sink:
        for level, seed in plans:
            mix = dict(mix0)
            if level is not None:
                mix[knob] = int(round(
                    level * mix["cycle_s"] if knob == "cycle_requests" else level))
            ctx = dataclasses.replace(
                base, seed=seed, traffic=mix, t0=time.perf_counter())
            with faults.planted(args.fault):
                res = serve.run(ctx)
            verdicts = {"correct": compare.verdict(res["numbers"])
                        and res["failed"] == 0}
            if args.control:
                gaps = serve.logit_gaps(ctx, res["sample"], args.control)
                control = compare.serving(
                    {k[len("control_"):]: v for k, v in gaps.items()
                     if k.startswith("control_")}, ctx.limits)
                verdicts["control"] = {k: n["value"] for k, n in control.items()}
                verdicts["control_correct"] = compare.verdict(control)
            row = {"seed": seed, knob: mix[knob], "seconds": args.seconds,
                   "fault": args.fault, **verdicts, "limits": ctx.limits,
                   "attempted": res["attempted"], "failed": res["failed"],
                   "setup_s": res["setup_s"],
                   "memory_peak_bytes": res["memory_peak_bytes"],
                   **res["end_to_end"],
                   **{k: v for k, v in res["facts"].items()
                      if not isinstance(v, (list, dict))},
                   "numbers": {k: v["value"] for k, v in res["numbers"].items()}}
            line = json.dumps(row)
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
