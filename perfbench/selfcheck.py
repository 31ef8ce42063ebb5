"""The yardstick checked against hand-worked values. Needs no device.

    python3 perfbench/selfcheck.py

Percentile and whole-window rate arithmetic; the open-loop schedule being
identical for equal seeds and the same multiset of sizes for different
ones; FLOP and byte counts for both configurations against values worked by
hand; every name and unit of BENCHMARK.json made of the allowed characters
and every file it names present; the trace reduction on a small form cut
from this benchmark's first traced chip run (fixtures/).
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

from perfbench import compare, flops, run, stats, trace, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


def check_stats():
    xs = [10, 20, 30, 40, 50, 60, 70, 80, 90, 100, 1000]
    assert stats.percentile(xs, 50) == 60
    assert stats.percentile(xs, 90) == 100
    assert close(stats.percentile(xs, 95), 550.0)  # halfway 100..1000
    assert stats.percentile([7], 95) == 7
    assert stats.percentile(xs, 100) == 1000  # a stall is never averaged away
    assert close(stats.rate(300 * 8192, 10.0, 40.0), 81920.0)
    assert close(stats.iqr_share([1, 2, 3, 4, 5, 6]), (5.25 - 1.75) / 3.5)


def check_traffic():
    mix = json.loads((HERE / "traffic" / "chat-steady.json").read_text())
    n, period, ramp = mix["cycle_requests"], mix["cycle_s"], mix["ramp_s"]
    a = traffic.requests(mix, 2147483999, period, 50257, 1024)
    b = traffic.requests(mix, 2147483999, period, 50257, 1024)
    assert a == b, "equal seeds must give the same schedule"
    size = lambda r: (len(r["body"]["prompt"]), r["body"]["max_new_tokens"],  # noqa: E731
                      r["greedy"])
    turns = set()
    for seed in (5, 6, 7, 4000000008, 9, 10):
        c = traffic.requests(mix, seed, period, 50257, 1024)
        win = [r for r in c if r["due_s"] >= 0]
        # the window holds the whole cycle once, whatever the seed ...
        assert len(win) == n and win[0]["due_s"] == 0.0
        assert sorted(map(size, win)) == sorted(
            map(size, (r for r in a if r["due_s"] >= 0)))
        assert all(-ramp <= r["due_s"] < period for r in c)
        # ... in the same cyclic order, and the ramp is the cycle's end again
        sizes = [size(r) for r in c]
        k = len(c) - n
        assert sizes[:k] == sizes[n:], "the ramp repeats the window's end"
        gaps = [y["due_s"] - x["due_s"] for x, y in zip(win, win[1:])]
        gaps.append(period - win[-1]["due_s"])
        turns.add(tuple(sizes[k:]))
        ref = gaps if seed == 5 else ref  # noqa: F821
        assert sorted(round(g, 9) for g in gaps) == sorted(
            round(g, 9) for g in ref)
        assert c[k]["body"]["prompt"] != a[len(a) - n]["body"]["prompt"]
    assert len(turns) > 1, "seeds must turn the cycle"
    first = min(turns)
    for t in turns:  # every order is a rotation of one cycle
        assert any(t[i:] + t[:i] == first for i in range(n))
    assert all(len(r["body"]["prompt"]) + r["body"]["max_new_tokens"] <= 1024
               for r in a)
    assert abs(sum(r["greedy"] for r in a[len(a) - n:]) - n / 2) <= 1
    closed = json.loads((HERE / "traffic" / "batch-backlog.json").read_text())
    q = traffic.requests(closed, 11, 30, 50257, 1024)
    q2 = traffic.requests(closed, 12, 30, 50257, 1024)
    assert [size(r) for r in q] == [size(r) for r in q2], "never turned"
    assert q[0]["body"]["prompt"] != q2[0]["body"]["prompt"]
    assert all(r["due_s"] is None for r in q)
    assert len(q) == int(closed["requests_per_s_ceiling"] * (
        30 + closed["ramp_s"])) + closed["clients"]
    m = closed["cycle_requests"]
    assert [size(r) for r in q[:m]] == [size(r) for r in q[m:2 * m]]
    tr = json.loads((HERE / "traffic" / "train-b8-t1024.json").read_text())
    t1 = traffic.train_tokens(tr, 11, 1.0)
    assert (t1 == traffic.train_tokens(tr, 11, 1.0)).all()
    assert int(t1.max()) < tr["data"]["vocab"]
    follows = ((t1[:-1].astype(int) * 2 + 1) % tr["data"]["vocab"]) == t1[1:]
    assert 0.65 < follows.mean() < 0.78, follows.mean()
    rows = t1[:8 * 1024].reshape(8, 1024)
    assert len({r.tobytes() for r in rows}) == 8, "rows must all differ"


def check_flops():
    files = [json.loads((HERE / "configs" / f"{name}.json").read_text())
             for name in ("gpt2-124m", "gpt2-large")]
    small, large = (f["model"] for f in files)
    # each file's family names its count; both are GPT-2's
    count = flops.of(files[0])
    assert flops.of(files[1]) is count
    assert count.__name__ == "perfbench.counts.gpt2"
    # By hand: 124M = 50257*768 + 1024*768 + 12*(12*768^2 + 13*768) + 2*768
    assert count.n_params(small) == 124_439_808
    assert count.n_params(large) == 774_030_080
    assert close(count.train_flops_per_token(small, 1024),
                 6 * 124_439_808 + 12 * 12 * 768 * 1024)
    # non-embedding: 12 * 12 * 768^2 + 50257 * 768
    assert count.n_params_non_embedding(small) == 12 * 12 * 768**2 + 50257 * 768
    one = count.serve_flops_span(large, 100, 101)
    assert close(one, 2 * count.n_params_non_embedding(large)
                 + 4 * 36 * 1280 * 101)
    assert close(count.serve_flops_span(large, 0, 192),
                 count.serve_flops(large, range(192)))
    w = count.flash_attention_work(small, 8, 1024)
    full = 2 * 8 * 12 * 1024 * 1024 * 64
    assert close(w["fwd"]["flops"], full) and close(w["bwd"]["flops"], 2.5 * full)
    peak = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    t, bound = flops.roofline_seconds(w["fwd"], peak)
    assert bound == "compute" and close(t, full / 197e12)


def check_compare():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gap, at = compare.worst_leaf_gap(got, want)
    assert at == "a" and close(gap, 0.1), (gap, at)  # c is held to the median
    # the first gradients' difference, against the leaf's or the median norm
    both = {"losses": [1.0], "delta_norms": want}
    n = compare.training(
        dict(both, grad_norms=want,
             grad_diff_norms={"a": 0.5, "b": 0.2, "c": 1e-9}),
        dict(both, grad_norms=want), {})
    assert close(n["grad1_diff_gap"]["value"], 0.5), n["grad1_diff_gap"]
    assert n["grad1_diff_gap"]["leaf"] == "a"
    nums = {"x": {"value": 0.5, "limit": 1.0}, "y": {"value": 3, "limit": None}}
    assert compare.verdict(nums)
    nums["x"]["value"] = float("nan")
    assert not compare.verdict(nums)
    assert not compare.verdict({"y": {"value": 3, "limit": None}})


def check_benchmark_json():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = []
    for c in bench["configs"]:
        names.append(c["name"])
        assert (ROOT / c["file"]).is_file(), c["file"]
        assert all(NAME.match(k) for k in c["reduced"])
        held = json.loads((ROOT / c["file"]).read_text())
        # its family's plain reference and count are there, and each path the
        # program has overrides for holds pairs that name keys of ``model``
        assert (HERE / "reference" / f"{held['reference']}.py").is_file(), c
        flops.of(held)
        for key in held["program"]:
            if key.endswith("_overrides"):
                pairs = held["program"][key.replace("_overrides", "_holds")]
                assert pairs and set(pairs.values()) <= set(held["model"]), c
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file(), w
        assert (HERE / "limits" / f"{w['name']}.json").is_file(), w
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert set(m.get("workloads", cells)) <= cells, m
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        readers = HERE / "layer_metrics"  # its own file, or its stem's
        assert (readers / f"{m['name']}.py").is_file() or (
            readers / f"{m['name'].rpartition('.')[0]}.py").is_file(), m
    for n in names:
        assert NAME.match(n), n
    assert len(set(m["name"] for m in bench["end_to_end"] + bench["per_layer"])) \
        == len(bench["end_to_end"]) + len(bench["per_layer"])
    assert "setup_s" in e2e
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}, set(bench)
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace"), m
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock"), m
    for w in bench["workloads"]:  # set-up, another end-to-end, one per layer
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert w["chips"] in (1, 4), w
        mine = [m["name"] for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2, (w["name"], mine)
        moved = {m["moves"] for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", cells)}
        assert moved and moved <= set(mine), (w["name"], moved, mine)
        assert any("mfu" in m["name"] for m in bench["per_layer"]
                   if w["name"] in m.get("workloads", cells)), w["name"]
    for p in HERE.rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if "__pycache__" in rel:
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def check_trace():
    """The fixture is a cut of a chip trace of cell 1 (three steps); the
    values below were worked from it by hand when it was cut (PERF.md §6)."""
    path = HERE / "fixtures" / "trace_train_3steps.json"
    form = json.loads(path.read_text())
    expect = json.loads((HERE / "fixtures" / "trace_train_3steps.expect.json")
                        .read_text())
    busy_s, window_s = trace.busy_and_window(form)
    assert close(busy_s, expect["busy_s"], 1e-6), busy_s
    assert close(window_s, expect["window_s"], 1e-6), window_s
    k = trace.kernel_events(form, ("flash_mha_fwd", "flash_mha_bwd"))
    assert k["flash_mha_bwd"][0] == expect["n_bwd"], k
    assert k["flash_mha_fwd"][0] == expect["n_fwd"], k
    assert close(k["flash_mha_bwd"][1], expect["bwd_ns"], 1e-9)
    # the kernels' reader, which finds GPT-2's count through the
    # configuration file: 12 layers' forward and backward, 3.5 x 2 B H T T D
    config = json.loads((HERE / "configs" / "gpt2-124m.json").read_text())
    peak = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    share = run.load_reader("flash_roofline.train")({
        "trace": form, "peak": peak, "config": config,
        "model": config["model"], "chips": 1,
        "facts": {"batch": 8, "seq_len": 1024}})
    spent_s = (k["flash_mha_fwd"][1] + k["flash_mha_bwd"][1]) / 1e9
    assert close(share, 100 * 12 * 3.5 * (2 * 8 * 12 * 1024 * 1024 * 64)
                 / 197e12 / spent_s), share
    top = trace.top_device_ops(form, 3)
    assert top[0][0] == expect["top_op"], top
    gaps = trace.idle_gaps(form)
    assert close(sum(s for _, s in gaps), window_s - busy_s, 1e-6)
    # and a synthetic form, worked by hand: two ops overlap 2..3, gap 5..7
    toy = {"devices": {"d0": [["a.1", 0, 3], ["b", 2, 3], ["all-gather.2", 7, 2],
                               ["c", 8, 2]]},
           "host": [["pb.window", 0, 10], ["pb.x", 4, 4]]}
    b, w = trace.busy_and_window(toy)
    assert close(b, 8e-9) and close(w, 10e-9)
    assert trace.idle_gaps(toy) == [["pb.x", 2e-9]]
    assert trace.top_device_ops(toy, 1)[0][0] in ("a", "b")


def main() -> int:
    checks = [check_stats, check_traffic, check_flops, check_compare,
              check_benchmark_json, check_trace]
    for c in checks:
        c()
        print(f"ok  {c.__name__}")
    print("selfcheck: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
