"""The kimi_k2 cell's comparison, at a size a test run can hold: the float8
control reads NOT correct and the program correct; each planted fault
(``faults_kimi_k2.py``: no shared expert, no selection bias, gates not scaled
by the routed scaling factor, the softmax scale without YaRN's m^2) reads
``correct: false``; the two readers this cell brings against hand-worked
answers; and the configuration file against the catalog row it was cut from.

    python3 -m pytest perfbench/tests/test_kimi_k2.py -q
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import compare, flops, preset, run  # noqa: E402
from perfbench.counts import kimi_k2 as count  # noqa: E402
from perfbench.tests import faults_kimi_k2 as faults  # noqa: E402
from perfbench.tests import tiny_kimi_k2 as tiny  # noqa: E402

SEEDS = (3, 4, 2147483900)
CELL = "kimi-k2.5-ep32.serve-agent-backlog"


def ctx_for(seed, tmp_path):
    return run.Context(
        workload="tiny-kimi-k2.backlog", seed=seed, seconds=2.0, trace=False,
        chips=1, config=tiny.TINY_CONFIG, traffic=tiny.TINY_SERVE,
        limits=tiny.TINY_LIMITS, scratch=str(tmp_path))


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_and_program_passes(seed, tmp_path):
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
    counters = next(iter(res["health"]["close"]["replicas"].values()))["counters"]
    assert counters["moe_pairs_here.decode_step"] > 0
    assert counters["latent_positions_read"] > 0


@pytest.mark.parametrize("fault,correct", [(None, True)] + [
    (f, False) for f in faults.FAULTS])
def test_fault_reads_not_correct(fault, correct, tmp_path):
    with faults.planted(fault):
        line = run.execute(ctx_for(4, tmp_path), tiny.TINY_BENCH, None, None)
    assert line["correct"] is correct, line["numbers"]
    assert line["failed"] == 0  # every stream whole: the mathematics is off
    assert list(line)[-1] == "numbers"


# -- the two readers ----------------------------------------------------------


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, ROOT / "perfbench" / "layer_metrics" / f"{name}.py")
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


def test_expert_rows_useful_against_hand_worked():
    read = reader("expert_rows_useful")
    res = {"health": health(
        {"moe_pairs_here.decode_step": 1000, "moe_rows_computed.decode_step": 9000},
        {"moe_pairs_here.decode_step": 4000, "moe_rows_computed.decode_step": 21000})}
    assert read(res) == pytest.approx(100.0 * 3000 / 12000)  # 25%
    assert read({"health": health({}, {})}) is None  # the parent's program
    assert read({}) is None


def test_decode_roofline_against_hand_worked():
    read = reader("decode_roofline")
    config = json.loads((ROOT / "perfbench/configs/kimi-k2.5-ep32.json").read_text())
    model = config["model"]
    # 200 dispatches in the window: 7 experts hit and 80,000 positions each
    res = {
        "config": config, "model": model,
        "peak": {"hbm_bytes_per_s": 819e9},
        "health": health(
            {"moe_experts_hit.decode_step": 500, "latent_positions_read": 10**6},
            {"moe_experts_hit.decode_step": 500 + 200 * 7,
             "latent_positions_read": 10**6 + 200 * 80000}),
        # two decode spans inside a traced window: 20 and 30 ms
        "trace": {"devices": {}, "host": [
            ["pb.window", 0, 10**9],
            ["pb.engine.dispatch.decode_step", 10**6, 20 * 10**6],
            ["pb.engine.dispatch.decode_step", 10**8, 30 * 10**6],
            ["pb.engine.dispatch.prefill", 2 * 10**8, 50 * 10**6]]},
    }
    outside = count.weight_bytes_outside_routed_experts(model)
    assert outside == 2 * (497_500_160 + 4 * 147_931_520 + 7168 + 7168 * 20480)
    assert count.routed_expert_bytes(model) == 88_080_384
    needed = outside + 7 * 88_080_384 + 1152 * 5 * 80000
    assert count.decode_bytes_needed(model, 7, 80000) == needed
    want = 100.0 * (needed / 819e9) / 0.025
    got = read(res)
    assert got == pytest.approx(want)
    assert 0 < got < 100
    assert read(dict(res, trace=None)) is None
    assert read(dict(res, health=health({}, {}))) is None


# -- the configuration file ---------------------------------------------------


def test_configuration_file_is_the_catalog_row_with_the_stated_cut():
    config = json.loads((ROOT / "perfbench/configs/kimi-k2.5-ep32.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "kimi-k2.5-ep32")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts_held", "vocab_size"]
    assert entry["source"] == config["source"]
    model = config["model"]
    assert (model["num_hidden_layers"], model["n_routed_experts_held"],
            model["vocab_size"]) == (5, 12, 20480)
    assert config["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 384, "vocab_size": 163840}
    # the widths, as published
    assert {k: model[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "num_attention_heads", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts")} == {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "num_attention_heads": 64, "n_routed_experts": 384,
        "num_experts_per_tok": 8, "n_shared_experts": 1}
    # the top-level copy of the catalog's keys says what the model block says
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == "Kimi-K2.5")
        for key, value in row["config"].items():
            assert config[key] == model[key]
            if key not in config["reduced"]:
                assert model[key] == value, key
    # the flat copies serve_holds reads are rope_scaling's own numbers
    for key, value in model["rope_scaling"].items():
        if key != "type":
            assert model["rope_scaling_" + key] == value
    # the program's preset is held to every size, and the counts are ISSUE 30's
    cfg = preset.of(config, "serve")
    assert cfg.family == "kimi_k2" and cfg.experts_held == 12
    assert flops.of(config) is count
    assert count.n_params(model) * 2 == pytest.approx(6.99e9, rel=2e-3)
    assert count.attention_params(model) == pytest.approx(101.1e6, rel=1e-3)
    # a 512-token chunk from an empty cache: 1.4 TFLOP
    assert count.serve_flops_span(model, 0, 512) == pytest.approx(1.40e12, rel=5e-3)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2.5-ep32", "agent-backlog", 1)
    mix = json.loads((ROOT / "perfbench/traffic/agent-backlog.json").read_text())
    # ISSUE 30's stated fallback (32 rows): 64 spread too widely, PERF.md section 6
    assert mix["engine"] == {"slots": 32, "max_len": 4096, "page_size": 64,
                             "prefill_chunk": 512}
    assert (mix["clients"], mix["cycle_requests"], mix["ramp_s"]) == (40, 48, 8)
    names = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert names == {f"{stem}.agent-backlog" for stem in (
        "step_mfu", "device_idle_share", "tick_gap_ms", "tick_host_ms",
        "admit_ms", "prefill_step_ms", "decode_step_ms", "expert_rows_useful",
        "decode_roofline")}
