"""Cut a small fixture out of the trace form of a traced run of a training
cell (run here, on the chip): the first N steps of the traced window, and what
``selfcheck.py`` expects of it, worked here by a second, plain computation
(numpy, no shared code).

    python3 perfbench/tools/cut_fixture.py gpt2-124m.train-1chip 3
"""

import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from perfbench import run  # noqa: E402
from perfbench.trace import op_name  # noqa: E402

workload, n_steps = sys.argv[1], int(sys.argv[2])
ctx, *_ = run.open_cell(workload, 1, 10.0, trace=True)
from perfbench.drivers import train  # noqa: E402

form = train.run(ctx)["trace"]
dev, evs = next(iter(form["devices"].items()))
evs = [[op_name(n), s, d] for n, s, d in evs]
disp = sorted(s for n, s, d in form["host"] if n == "pb.step.dispatch")
w0 = disp[0] - 1000
w1 = disp[n_steps] - 1000  # the window: up to the (N+1)th dispatch
outer = sorted((s, d) for n, s, d in evs if n.startswith("while"))
evs = [e for e in evs if e[1] >= w0 and e[1] + e[2] <= w1]
host = [[n, s, d] for n, s, d in form["host"]
        if n != "pb.window" and s >= w0 and s + d <= w1]
host.insert(0, ["pb.window", w0, w1 - w0])
out = {"devices": {dev: evs}, "host": host}
dst = Path("perfbench/fixtures/trace_train_3steps.json")
dst.write_text(json.dumps(out, separators=(",", ":")))

# expectations by a plain sweep over a time grid of 1 ns events (numpy)
marks = np.zeros(w1 - w0 + 1, np.int32)
for n, s, d in evs:
    marks[s - w0] += 1
    marks[s + d - w0] -= 1
busy_ns = int((np.cumsum(marks)[:-1] > 0).sum())
bwd = [d for n, s, d in evs if n.startswith("flash_mha_bwd")]
fwd = [d for n, s, d in evs if n.startswith("flash_mha_fwd")]
tot = {}
for n, s, d in evs:
    b = n.rsplit(".", 1)[0] if n.rsplit(".", 1)[-1].isdigit() else n
    tot[b] = tot.get(b, 0) + d
for c in ("while", "conditional", "call"):
    tot.pop(c, None)
expect = {"busy_s": busy_ns / 1e9, "window_s": (w1 - w0) / 1e9,
          "n_bwd": len(bwd), "n_fwd": len(fwd), "bwd_ns": sum(bwd),
          "top_op": max(tot, key=tot.get), "events": len(evs)}
Path("perfbench/fixtures/trace_train_3steps.expect.json").write_text(
    json.dumps(expect, indent=1))
print(expect, dst.stat().st_size)
