"""Readings for the mellum cell, many runs in one process: what ``correct``
compares on each seed for the program, for the float8 control, and under each
of the family's own planted faults (``tests/faults_mellum.py``; the sweep
tool ``serve_probe.py`` plants ``tests/faults.py``'s only).

    python3 perfbench/tools/mellum_probe.py --seeds 11,12 --seconds 10 --control fp8
    python3 perfbench/tools/mellum_probe.py --seeds 11,12 --seconds 10 --faults all

Every run goes through the serving driver and the harness's own comparison
under the cell's limits. Chip only; prints one JSON line a run and appends it
to ``--out``.
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
from perfbench.tests import faults_mellum as faults  # noqa: E402

CELL = "mellum2-12b-a2.5b-l12.serve-code-backlog"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--control", default=None)
    ap.add_argument("--faults", default="",
                    help="'all', or names of faults_mellum.FAULTS; none: the "
                         "program as it is")
    ap.add_argument("--out", default="chiprun_out/mellum_probe.jsonl")
    args = ap.parse_args()

    base, *_ = run.open_cell(args.workload, 0, args.seconds)
    from perfbench.drivers import serve

    planted = (list(faults.FAULTS) if args.faults == "all"
               else [f for f in args.faults.split(",") if f] or [None])
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as sink:
        for seed in (int(s) for s in args.seeds.split(",") if s):
            for fault in planted:
                ctx = dataclasses.replace(
                    base, seed=seed, t0=time.perf_counter())
                with faults.planted(fault):
                    res = serve.run(ctx)
                row = {"seed": seed, "fault": fault,
                       "correct": compare.verdict(res["numbers"])
                       and res["failed"] == 0,
                       "numbers": {k: v["value"]
                                   for k, v in res["numbers"].items()},
                       "limits": ctx.limits, "failed": res["failed"],
                       "attempted": res["attempted"],
                       "memory_peak_bytes": res["memory_peak_bytes"],
                       "setup_s": res["setup_s"], **res["end_to_end"],
                       "reference_s": res["facts"]["reference_s"]}
                if args.control and fault is None:
                    gaps = serve.logit_gaps(ctx, res["sample"], args.control)
                    control = compare.serving(
                        {k[len("control_"):]: v for k, v in gaps.items()
                         if k.startswith("control_")}, ctx.limits)
                    row["control"] = {
                        k: n["value"] for k, n in control.items()}
                    row["control_correct"] = compare.verdict(control)
                line = json.dumps(row)
                print(line, flush=True)
                sink.write(line + "\n")
                sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
