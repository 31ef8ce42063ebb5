"""The mellum cell's comparison, at a size a test run can hold: the float8
control reads NOT correct and the program correct; each planted fault
(``faults_mellum.py``: the window mask off, the full layers on the plain
rotary table, ``attention_factor`` dropped, the gates not renormalised, a
window page released one page early) reads ``correct: false``; the three
readers this cell brings against hand-worked answers; the count module
against ISSUE 36's arithmetic; and the configuration file against the
catalog row, key for key.

The tiny stand-in computes in float32 over bfloat16 weights, so the program
reads 0.000 on both numbers on the three seeds; the float8 control reads
served_logit_gap 0.33-0.79 and sampled_topk_gap 0.50-1.07; the planted
faults on seed 4 read served_logit_gap 2.76 (the mask off), 0.163 (the plain
table), 0.038 (the factor dropped), 0.97 (the gates), 3.26 (the page) and
sampled_topk_gap 2.81, 0.173, 0.049, 0.66, 2.30: the limits of 0.02 lie
under the least of them and over the program's.

    python3 -m pytest perfbench/tests/test_mellum.py -q
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import compare, flops, preset, run  # noqa: E402
from perfbench.counts import mellum as count  # noqa: E402
from perfbench.tests import faults_mellum as faults  # noqa: E402
from perfbench.tests import tiny_mellum as tiny  # noqa: E402

SEEDS = (3, 4, 2147483900)
CONFIG = "mellum2-12b-a2.5b-l12"
MIX = "code-backlog"
CELL = f"{CONFIG}.serve-{MIX}"


def ctx_for(seed, tmp_path):
    return run.Context(
        workload="tiny-mellum.backlog", seed=seed, seconds=2.0,
        trace=False, chips=1, config=tiny.TINY_CONFIG, traffic=tiny.TINY_SERVE,
        limits=tiny.TINY_LIMITS, scratch=str(tmp_path))


@pytest.mark.parametrize("seed", SEEDS)
def test_mellum_control_fails_and_program_passes(seed, tmp_path):
    from perfbench.drivers import serve

    ctx = ctx_for(seed, tmp_path)
    res = serve.run(ctx)
    assert res["failed"] == 0 and compare.verdict(res["numbers"]), res["numbers"]
    assert res["facts"]["tokens_compared"] >= 20
    assert res["facts"]["sampled_tokens_compared"] >= 20
    gaps = serve.logit_gaps(ctx, res["sample"], "fp8")
    control = compare.serving(
        {k: gaps["control_" + k] for k in tiny.TINY_LIMITS}, tiny.TINY_LIMITS)
    assert not compare.verdict(control), control
    # the program's counters reached the readers through /healthz
    body = next(iter(res["health"]["close"]["replicas"].values()))
    c = body["counters"]
    assert 0 < c["moe_pairs_here.decode_step"] <= c[
        "moe_rows_computed.decode_step"]
    assert c["moe_pairs_here.decode_step"] == 8 * 2 * c[
        "moe_tokens.decode_step"]  # 8 layers, 2 experts a token, all held
    assert 0 < c["kv_positions_read.window"] < c["kv_positions_read.full"]
    assert 0 < c["window_positions_needed"] <= c["window_positions_held"]
    assert c["window_pages_released"] > 0
    assert body["prefix_queries"] == 0
    assert body["window_pool_pages"] == 4 * 5 + 1  # (15 + 16) / 8 -> 4, + 1
    assert body["window_pages_in_use"] <= 4 * 5


@pytest.mark.parametrize("fault,correct", [(None, True)] + [
    (f, False) for f in faults.FAULTS])
def test_mellum_fault_reads_not_correct(fault, correct, tmp_path):
    with faults.planted(fault):
        line = run.execute(ctx_for(4, tmp_path), tiny.TINY_BENCH, None, None)
    assert line["correct"] is correct, line["numbers"]
    assert line["failed"] == 0  # every stream whole: the mathematics is off
    assert list(line)[-1] == "numbers"


# -- the three readers --------------------------------------------------------


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_").replace("-", "_"),
        ROOT / "perfbench" / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def health(open_counters, close_counters, dispatches=(100, 300)):
    def body(counters, n):
        return {"replicas": {"0": {
            "counters": counters,
            "timers": {"engine.dispatch.decode_step": {
                "count": n, "total_s": 0.0, "max_s": 0.0}}}}}
    return {"open": body(open_counters, dispatches[0]),
            "close": body(close_counters, dispatches[1])}


def cell_files():
    config = json.loads(
        (ROOT / f"perfbench/configs/{CONFIG}.json").read_text())
    return config, config["model"]


# 200 dispatches in the window, each: 750 experts hit over the 12 layers,
# 32 rows at a depth of 3,500 (112,000 positions in a full layer, 32,768 in
# a sliding one)
COUNTERS = (
    {"moe_experts_hit.decode_step": 10**4, "kv_positions_read.full": 10**6,
     "kv_positions_read.window": 10**5},
    {"moe_experts_hit.decode_step": 10**4 + 200 * 750,
     "kv_positions_read.full": 10**6 + 200 * 112000,
     "kv_positions_read.window": 10**5 + 200 * 32768})


def test_mellum_decode_roofline_against_hand_worked():
    read = reader(f"decode_roofline.{MIX}")
    config, model = cell_files()
    res = {
        "config": config, "model": model,
        "peak": {"hbm_bytes_per_s": 819e9},
        "health": health(*COUNTERS),
        # two decode spans inside a traced window: 30 and 50 ms
        "trace": {"devices": {}, "host": [
            ["pb.window", 0, 10**9],
            ["pb.engine.dispatch.decode_step", 10**6, 30 * 10**6],
            ["pb.engine.dispatch.decode_step", 10**8, 50 * 10**6],
            ["pb.engine.dispatch.prefill", 2 * 10**8, 50 * 10**6]]},
    }
    dense = 2 * (12 * (21_233_664 + 147_456 + 4_608) + 2304 + 98304 * 2304)
    needed = (dense + 750 * 2 * 6_193_152
              + 2048 * (3 * 112000 + 9 * 32768))
    assert count.decode_bytes_needed(model, 750, 112000, 32768) == needed
    want = 100.0 * (needed / 819e9) / 0.040
    got = read(res)
    assert got == pytest.approx(want)
    assert 0 < got < 100
    assert read(dict(res, trace=None)) is None
    assert read(dict(res, health=health({}, {}))) is None  # the parent's


def test_mellum_paged_decode_roofline_against_hand_worked():
    read = reader(f"paged_decode_roofline.{MIX}")
    config, model = cell_files()
    # three decode dispatches inside the trace: 36 kernel events, 12 a
    # dispatch, 0.2 ms each (and another device op that is none of them)
    events = [["paged_decode_attention", 10**6 * i, 200_000]
              for i in range(36)] + [["fusion.1", 5 * 10**8, 10**6]]
    res = {
        "config": config, "model": model,
        "peak": {"hbm_bytes_per_s": 819e9},
        "health": health(*COUNTERS),
        "trace": {"devices": {"0": events},
                  "host": [["pb.window", 0, 10**9]]},
    }
    a_dispatch = 2048 * (3 * 112000 + 9 * 32768)
    assert count.paged_attention_bytes(model, 112000, 32768) == a_dispatch
    want = 100.0 * (3 * a_dispatch / 819e9) / (36 * 200e-6)
    got = read(res)
    assert got == pytest.approx(want)
    assert 0 < got < 100
    assert read(dict(res, trace=None)) is None
    assert read(dict(res, health=health({}, {}))) is None  # the parent's
    no_kernel = dict(res, trace={"devices": {"0": events[-1:]},
                                 "host": [["pb.window", 0, 10**9]]})
    assert read(no_kernel) is None  # the gather path: nothing to read


def test_window_positions_useful_against_hand_worked():
    read = reader(f"window_positions_useful.{MIX}")
    res = {"health": health(
        {"window_positions_held": 1000, "window_positions_needed": 900},
        {"window_positions_held": 1000 + 200 * 32 * 17 * 64,
         "window_positions_needed": 900 + 200 * 32 * 1024})}
    assert read(res) == pytest.approx(100.0 * 1024 / (17 * 64))  # 94.1
    assert read({"health": health({}, {})}) is None  # the parent's program
    assert read({}) is None


# -- the count module and the configuration file ------------------------------


def test_mellum_counts_are_issue_36s_arithmetic():
    _, model = cell_files()
    assert count.attention_params(model) == 21_233_664
    assert count.expert_params(model) == 6_193_152
    assert count.layer_params(model) == 417_747_456
    assert count.n_params(model) == 12 * 417_747_456 + 2 * 98304 * 2304 + 2304
    assert 2 * count.n_params(model) == pytest.approx(10.93e9, rel=1e-3)
    # the 28 layers are the published "12B-A2.5B"
    whole = dict(model, layer_types=model["layer_types"][:4] * 7)
    assert count.n_params(whole) == pytest.approx(12.15e9, rel=1e-3)
    assert count.kv_bytes_per_position_per_layer(model) == 2048
    # the two page groups: 4,097 pages over 3 layers, 801 over 9
    assert 4097 * 3 * 64 * 2048 == pytest.approx(1.61e9, rel=2e-3)
    assert 801 * 9 * 64 * 2048 == pytest.approx(0.94e9, rel=6e-3)
    # a decode step of 32 rows at a depth of 3,500 with 63 experts hit a
    # layer: 11.6 GB, 14.2 ms of the chip's memory (ISSUE 36 says 14.8 with
    # the embedding matrix, of which a step reads 32 rows)
    needed = count.decode_bytes_needed(model, 12 * 63, 32 * 3500, 32 * 1024)
    assert needed == pytest.approx(11.62e9, rel=2e-3)
    # were every layer full, K and V would be 2.75 GB of it and not 1.29
    assert count.paged_attention_bytes(
        model, 32 * 3500, 32 * 1024) == pytest.approx(1.29e9, rel=5e-3)
    assert count.paged_attention_bytes(
        dict(model, layer_types=["full_attention"] * 12), 32 * 3500, 0
    ) == pytest.approx(2.75e9, rel=5e-3)
    # a 512-token chunk from an empty cache: 0.87 TFLOP of layer products
    # (ISSUE 36's figure), 0.23 of head, 0.03 of attention
    assert count.serve_flops_span(model, 0, 512) == pytest.approx(
        1.13e12, rel=1e-2)
    # a sliding layer's attention term is capped at the window: past it, a
    # token costs the same at every depth
    a = count.serve_flops_span(model, 4000, 4001)
    b = count.serve_flops_span(model, 6000, 6001)
    assert b - a == 2.0 * 2 * 32 * 128 * 3 * 2000
    assert count._keys_attended(1000, 1100, 1024) == sum(
        min(p + 1, 1024) for p in range(1000, 1100))
    with pytest.raises(NotImplementedError, match="no training path"):
        count.train_flops_per_token(model, 1024)


def test_mellum_configuration_file_is_the_catalog_row_key_for_key():
    config, model = cell_files()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cut = ["num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert entry["reduced"] == config["reduced"] == cut
    assert entry["source"] == config["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert model["layer_types"] == period * 3
    assert model["mlp_layer_types"] == ["sparse"] * 12
    assert config["published"]["num_hidden_layers"] == 28
    assert {k: model[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "num_experts", "num_experts_per_tok",
        "moe_intermediate_size", "sliding_window", "vocab_size",
        "num_hidden_layers")} == {
        "hidden_size": 2304, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "num_experts": 64,
        "num_experts_per_tok": 8, "moe_intermediate_size": 896,
        "sliding_window": 1024, "vocab_size": 98304,
        "num_hidden_layers": 12}
    # the two copies of the catalog's keys are equal
    for key, value in model.items():
        assert config[key] == value, key
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert config["source"] == row["source_url"]
        assert set(model) == set(row["config"])
        for key, value in row["config"].items():
            if key not in cut:
                assert model[key] == value, key
        assert model["layer_types"] == row["config"]["layer_types"][:12]
        assert model["mlp_layer_types"] == row["config"]["mlp_layer_types"][:12]
        assert row["config"]["num_hidden_layers"] == 28
    for key in ("assumed", "precision", "deployment"):
        assert config[key]
    assert not any(k.startswith("train_") for k in config["program"])
    # the program's preset is held to every size the file states
    cfg = preset.of(config, "serve")
    assert cfg.family == "mellum" and len(cfg.layer_types) == 12
    assert flops.of(config) is count
    for key, value in (("sliding_window", 512), ("head_dim", 64),
                       ("num_experts", 32)):
        broken = dict(config, model=dict(model, **{key: value}))
        with pytest.raises(SystemExit, match=key):
            preset.of(broken, "serve")
    rope = json.loads(json.dumps(model["rope_parameters"]))
    rope["full_attention"]["factor"] = 8
    with pytest.raises(SystemExit, match="rope_parameters"):
        preset.of(dict(config, model=dict(model, rope_parameters=rope)),
                  "serve")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    mix = json.loads((ROOT / f"perfbench/traffic/{MIX}.json").read_text())
    agent = json.loads(
        (ROOT / "perfbench/traffic/agent-backlog.json").read_text())
    assert set(mix) == set(agent)  # every key of the file it was copied from
    assert mix["engine"] == {"slots": 32, "max_len": 8192, "page_size": 64,
                             "prefill_chunk": 512}
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 3072, "sigma": 0.6, "min": 512,
        "max": 7168}
    assert mix["new_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.6, "min": 32,
        "max": 512}
    assert (mix["clients"], mix["cycle_requests"], mix["ramp_s"],
            mix["requests_per_s_ceiling"], mix["order_seed"]) == (
        40, 48, 8, 16, 1)
    assert (mix["sampled_share"], mix["temperature"], mix["top_k"]) == (
        0.5, 0.8, 50)
    assert (mix["warm_requests"], mix["warm_new_tokens"],
            mix["compare_requests"], mix["compare_sampled_requests"],
            mix["trace_seconds"]) == (4, 4, 4, 4, 5)
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_tok_s")["workloads"]
    names = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert names == {f"{stem}.{MIX}" for stem in (
        "step_mfu", "device_idle_share", "tick_gap_ms", "tick_host_ms",
        "admit_ms", "prefill_step_ms", "decode_step_ms",
        "expert_rows_useful", "decode_roofline", "paged_decode_roofline",
        "window_positions_useful")}
    limits = json.loads(
        (ROOT / f"perfbench/limits/{CELL}.json").read_text())["limits"]
    assert set(limits) == {"served_logit_gap", "sampled_topk_gap"}
