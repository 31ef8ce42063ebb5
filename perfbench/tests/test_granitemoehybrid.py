"""The granitemoehybrid cell's comparison, at a size a test run can hold: the
float8 control reads NOT correct and the program correct; each planted fault
(``faults_granitemoehybrid.py``: the state not reset at admission, the
convolution's tail dropped between chunks, the padded tail advancing the
state, the attention scaled by head_dim^-1/2) reads ``correct: false``; the
two readers this cell brings against hand-worked answers; the count module
against ISSUE 34's arithmetic; and the configuration file against the
catalog row, key for key.

    python3 -m pytest perfbench/tests/test_granitemoehybrid.py -q
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import compare, flops, preset, run  # noqa: E402
from perfbench.counts import granitemoehybrid as count  # noqa: E402
from perfbench.tests import faults_granitemoehybrid as faults  # noqa: E402
from perfbench.tests import tiny_granitemoehybrid as tiny  # noqa: E402

SEEDS = (3, 4, 2147483900)
CONFIG = "granite-4.0-h-micro"
CELL = CONFIG + ".serve-chat-backlog"


def ctx_for(seed, tmp_path):
    return run.Context(
        workload="tiny-granitemoehybrid.backlog", seed=seed, seconds=2.0,
        trace=False, chips=1, config=tiny.TINY_CONFIG, traffic=tiny.TINY_SERVE,
        limits=tiny.TINY_LIMITS, scratch=str(tmp_path))


@pytest.mark.parametrize("seed", SEEDS)
def test_granite_control_fails_and_program_passes(seed, tmp_path):
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
    assert 0 < c["ssm_tokens_live.prefill"] < c["ssm_tokens_computed.prefill"]
    assert c["ssm_tokens_live.decode_step"] == c["state_rows_advanced"] > 0
    assert c["kv_positions_read"] > c["state_rows_advanced"]
    assert body["prefix_queries"] == 0
    assert body["state_bytes_per_row"] == 18 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert body["kv_bytes_per_position"] == 2 * 2 * 2 * 16 * 4


@pytest.mark.parametrize("fault,correct", [(None, True)] + [
    (f, False) for f in faults.FAULTS])
def test_granite_fault_reads_not_correct(fault, correct, tmp_path):
    with faults.planted(fault):
        line = run.execute(ctx_for(4, tmp_path), tiny.TINY_BENCH, None, None)
    assert line["correct"] is correct, line["numbers"]
    assert line["failed"] == 0  # every stream whole: the mathematics is off
    assert list(line)[-1] == "numbers"


# -- the two readers ----------------------------------------------------------


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


def test_scan_tokens_useful_against_hand_worked():
    read = reader("scan_tokens_useful")
    res = {"health": health(
        {"ssm_tokens_live.prefill": 1000, "ssm_tokens_computed.prefill": 2048},
        {"ssm_tokens_live.prefill": 7000, "ssm_tokens_computed.prefill": 12048})}
    assert read(res) == pytest.approx(60.0)  # 6000 tokens of 10000 positions
    assert read({"health": health({}, {})}) is None  # the parent's program
    assert read({}) is None


def test_granite_decode_roofline_against_hand_worked():
    read = reader("decode_roofline.chat-backlog")
    config = json.loads(
        (ROOT / f"perfbench/configs/{CONFIG}.json").read_text())
    model = config["model"]
    # 200 dispatches in the window: 30 rows and 12,000 positions each
    res = {
        "config": config, "model": model,
        "peak": {"hbm_bytes_per_s": 819e9},
        "health": health(
            {"state_rows_advanced": 500, "kv_positions_read": 10**6},
            {"state_rows_advanced": 500 + 200 * 30,
             "kv_positions_read": 10**6 + 200 * 12000}),
        # two decode spans inside a traced window: 20 and 30 ms
        "trace": {"devices": {}, "host": [
            ["pb.window", 0, 10**9],
            ["pb.engine.dispatch.decode_step", 10**6, 20 * 10**6],
            ["pb.engine.dispatch.decode_step", 10**8, 30 * 10**6],
            ["pb.engine.dispatch.prefill", 2 * 10**8, 50 * 10**6]]},
    }
    state = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    needed = 2 * 3_191_396_096 + 2 * state * 30 + 8192 * 12000
    assert count.decode_bytes_needed(model, 30, 12000) == needed
    want = 100.0 * (needed / 819e9) / 0.025
    got = read(res)
    assert got == pytest.approx(want)
    assert 0 < got < 100
    assert read(dict(res, trace=None)) is None
    assert read(dict(res, health=health({}, {}))) is None  # the parent's


# -- the count module and the configuration file ------------------------------


def test_granite_counts_are_issue_34s_arithmetic():
    model = json.loads(
        (ROOT / f"perfbench/configs/{CONFIG}.json").read_text())["model"]
    mamba = count.mamba_mixer_params(model) + count.mlp_params(model)
    attention = count.attention_mixer_params(model) + count.mlp_params(model)
    assert mamba == pytest.approx(76.18e6, rel=1e-4)
    assert attention == pytest.approx(60.82e6, rel=1e-4)
    assert count.n_params(model) == 36 * mamba + 4 * attention + (
        100352 * 2048 + 2048) == 3_191_396_096
    assert count.weight_bytes(model) == pytest.approx(6.38e9, rel=1e-3)
    # a row: 2.0 MB of float32 state a Mamba layer, 75.5 MB over 36, and the
    # convolution's tail of 26 KB a layer beside it
    assert 36 * 64 * 64 * 128 * 4 == 75_497_472
    assert count.state_bytes_per_row(model) == 75_497_472 + 36 * 3 * 4352 * 2
    assert count.kv_bytes_per_position(model) == 8192
    # a decode step of 32 rows at a depth of 400: 11.4 GB, 60% the mixers'
    needed = count.decode_bytes_needed(model, 32, 32 * 400)
    assert needed == pytest.approx(11.38e9, rel=2e-3)
    # a 256-token chunk from an empty cache: 1.6 TFLOP (ISSUE 34 says 1.5
    # without the head's 0.2)
    assert count.serve_flops_span(model, 0, 256) == pytest.approx(
        1.66e12, rel=1e-2)
    with pytest.raises(NotImplementedError, match="no training path"):
        count.train_flops_per_token(model, 1024)


def test_granite_configuration_file_is_the_catalog_row_key_for_key():
    config = json.loads(
        (ROOT / f"perfbench/configs/{CONFIG}.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    model = config["model"]
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert model["layer_types"] == period * 4
    assert {k: model[k] for k in (
        "hidden_size", "shared_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_n_groups",
        "num_experts_per_tok", "num_local_experts", "vocab_size")} == {
        "hidden_size": 2048, "shared_intermediate_size": 8192,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
        "num_experts_per_tok": 0, "num_local_experts": 0,
        "vocab_size": 100352}
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == CONFIG)
        assert config["source"] == row["source_url"]
        assert model == row["config"]  # nothing cut, nothing added
        for key, value in row["config"].items():
            assert config[key] == value, key
    for key in ("assumed", "precision", "deployment"):
        assert config[key]
    assert not any(k.startswith("train_") for k in config["program"])
    # the program's preset is held to every size the file states
    cfg = preset.of(config, "serve")
    assert cfg.family == "granitemoehybrid" and len(cfg.layer_types) == 40
    assert flops.of(config) is count
    broken = dict(config, model=dict(model, layer_types=period[::-1] * 4))
    with pytest.raises(SystemExit, match="layer_types"):
        preset.of(broken, "serve")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-backlog", 1)
    mix = json.loads((ROOT / "perfbench/traffic/chat-backlog.json").read_text())
    agent = json.loads(
        (ROOT / "perfbench/traffic/agent-backlog.json").read_text())
    assert set(mix) == set(agent)  # every key of the file it was copied from
    assert mix["engine"] == {"slots": 32, "max_len": 4096, "page_size": 64,
                             "prefill_chunk": 256}
    assert mix["engine"]["prefill_chunk"] == model["mamba_chunk_size"]
    assert (mix["clients"], mix["cycle_requests"], mix["ramp_s"],
            mix["requests_per_s_ceiling"]) == (40, 48, 8, 16)
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_tok_s")["workloads"]
    names = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert names == {f"{stem}.chat-backlog" for stem in (
        "step_mfu", "device_idle_share", "tick_gap_ms", "tick_host_ms",
        "admit_ms", "prefill_step_ms", "decode_step_ms", "decode_roofline",
        "scan_tokens_useful")}
    limits = json.loads(
        (ROOT / f"perfbench/limits/{CELL}.json").read_text())["limits"]
    assert set(limits) == {"served_logit_gap", "sampled_topk_gap"}
