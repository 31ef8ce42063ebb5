"""The kimi_k2 family (models/kimi_k2.py, ops/moe.moe_dropless, YaRN in
ops/rope.py) against its plain reference, perfbench/reference/kimi_k2.py, at
a tiny size on the CPU: unequal q_lora / kv_lora / nope / rope / v sizes,
1 dense + 2 expert layers, 16 experts top-4, 4 of them held (experts 4..7).
"""

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench.reference import kimi_k2 as ref  # noqa: E402
from pytorch_distributed_tpu.config import ModelConfig, model_config  # noqa: E402
from pytorch_distributed_tpu.models import decode, kimi_k2  # noqa: E402
from pytorch_distributed_tpu.ops import moe, paged_kernel, rope  # noqa: E402
from pytorch_distributed_tpu.serving.engine import (  # noqa: E402
    BatchedDecodeEngine,
    PagedBatchedDecodeEngine,
)

MODEL = dict(
    hidden_size=32, num_hidden_layers=3, vocab_size=96, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
    v_head_dim=10, intermediate_size=48, moe_intermediate_size=20,
    n_shared_experts=1, n_routed_experts=16, num_experts_per_tok=4,
    n_routed_experts_held=4, expert_offset=4, first_k_dense_replace=1,
    rms_norm_eps=1e-5, rope_theta=50000, routed_scaling_factor=2.827,
    rope_scaling=dict(factor=64, original_max_position_embeddings=16,
                      beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1))
PAGE, MAX_LEN = 4, 64


def program_config(model=MODEL, **kw) -> ModelConfig:
    rs = model["rope_scaling"]
    return ModelConfig(**dict(dict(
        family="kimi_k2", vocab_size=model["vocab_size"], n_ctx=MAX_LEN,
        n_embd=model["hidden_size"], n_layer=model["num_hidden_layers"],
        n_head=model["num_attention_heads"], n_inner=model["intermediate_size"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        first_k_dense_replace=model["first_k_dense_replace"],
        n_routed_experts=model["n_routed_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        n_shared_experts=model["n_shared_experts"],
        moe_intermediate_size=model["moe_intermediate_size"],
        routed_scaling_factor=model["routed_scaling_factor"],
        rope_theta=float(model["rope_theta"]), rope_factor=rs["factor"],
        rope_original_max_position=rs["original_max_position_embeddings"],
        rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
        rope_mscale=rs["mscale"], rope_mscale_all_dim=rs["mscale_all_dim"],
        experts_held=model["n_routed_experts_held"],
        expert_offset=model["expert_offset"], dtype="float32",
        param_dtype="float32", embd_pdrop=0.0, attn_pdrop=0.0,
        resid_pdrop=0.0, activation_function="silu",
        layer_norm_epsilon=model["rms_norm_eps"]), **kw))


CFG = program_config()


@pytest.fixture(scope="module")
def params():
    return ref.init_params(7, MODEL, "float32")


@pytest.fixture(params=[1, 8, 128], ids=["rows1", "rows8", "rows128"])
def expert_path(request, monkeypatch):
    """``moe_dropless`` runs its sorted pairs in blocks of rows of one
    expert; the block's size (``dropless_block_rows``: 8 in the benchmark's
    decode step, 128 in its prefill chunk) changes the blocks an expert
    needs and nothing else. 1: every pair a block of its own; 8: these
    tests' busiest experts need several; 128: one block an expert."""
    monkeypatch.setattr(
        moe, "dropless_block_rows", lambda *_: request.param)


def tables_for(rows: int):
    """Row b owns pages 1 + b*n .. (page 0 is the scratch page)."""
    n = MAX_LEN // PAGE
    return 1 + jnp.arange(rows * n, dtype=jnp.int32).reshape(rows, n)


def prompts(rows: int, length: int, seed=1):
    return jax.random.randint(
        jax.random.key(seed), (rows, length), 0, MODEL["vocab_size"])


def test_chunked_prefill_then_decode_equals_reference_logits(params, expert_path):
    """Three chunks of 8 through the expanded path, then six single tokens
    through the absorbed path, on one paged latent pool: every position's
    logits are the reference's full forward's."""
    ids = prompts(2, 30)
    want = ref.logits(params, ids, MODEL)
    pool = decode.init_paged_cache(CFG, 2 * (MAX_LEN // PAGE) + 1, PAGE)
    assert set(pool) == {"latent"}
    assert pool["latent"].shape == (3, 33, PAGE, kimi_k2.page_width(CFG))
    tables, got = tables_for(2), []
    fwd = jax.jit(lambda p, i, c, pos: kimi_k2.forward(
        p, i, CFG, c, pos, tables))
    for start in (0, 8, 16):
        lg, pool, _ = fwd(params, ids[:, start:start + 8], pool,
                          jnp.full((2,), start))
        got.append(lg)
    for pos in range(24, 30):
        lg, pool, _ = fwd(params, ids[:, pos:pos + 1], pool,
                          jnp.full((2,), pos))
        got.append(lg)
    np.testing.assert_allclose(
        jnp.concatenate(got, axis=1), want, atol=2e-6, rtol=0)


@pytest.mark.parametrize("paged_attention", [None, "kernel_interpret"])
def test_paged_engine_serves_the_reference_greedy_tokens(
        params, expert_path, paged_attention, monkeypatch):
    """Through PagedBatchedDecodeEngine (admission, block pool, chunked
    prefill, sampler): more requests than rows, every reply the reference's
    greedy continuation; the expert counters add up. Left unset, off the
    chip, ``paged_attention`` is the gathered window; "kernel_interpret"
    reads the pool through ops/latent_paged_kernel.py (blocks of two pages,
    so the deeper rows take several)."""
    if paged_attention:
        monkeypatch.setattr(paged_kernel, "KEY_BLOCK", 2 * PAGE)
    eng = PagedBatchedDecodeEngine(
        CFG, slots=4, max_len=MAX_LEN, page_size=PAGE, prefill_chunk=8,
        paged_attention=paged_attention)
    assert eng.stats()["paged_decode_impl"] == (paged_attention or "gather")
    eng.warmup(params)
    compiled = eng.compile_count()
    rng = np.random.default_rng(0)
    sent = []
    for n in (5, 19, 8, 30, 11, 3):
        prompt = rng.integers(0, MODEL["vocab_size"], n).astype(np.int32)
        sent.append((eng.submit(prompt, 10), prompt.tolist()))
    eng.run(params)
    assert eng.compile_count() == compiled
    for rid, prompt in sent:
        res = eng.pop_result(rid)
        gen = [int(t) for t in res.tokens][-10:]
        assert res.state == "DONE" and len(gen) == 10
        lg = ref.logits(params, jnp.asarray([prompt + gen]), MODEL)[0]
        want = np.asarray(jnp.argmax(lg[len(prompt) - 1:-1], axis=-1))
        assert gen == want.tolist()
    st = eng.stats()
    c = st["counters"]
    assert c["moe_tokens.prefill"] == sum(len(p) for _, p in sent)
    assert c["moe_tokens.decode_step"] == 6 * 9  # the first token is prefill's
    for kind in ("prefill", "decode_step"):
        assert 0 < c[f"moe_pairs_here.{kind}"] <= c[f"moe_rows_computed.{kind}"]
        # two expert layers, at most four picks a token
        assert c[f"moe_pairs_here.{kind}"] <= 2 * 4 * c[f"moe_tokens.{kind}"]
        assert 0 < c[f"moe_experts_hit.{kind}"]
    # a decode dispatch's window is every row's whole table, whatever is
    # in it; what the rows reach is a part of it
    assert 0 < c["latent_positions_read"] < c["latent_positions_window"]
    assert c["latent_positions_window"] % (4 * MAX_LEN) == 0
    # the page: 16 + 8 numbers, stored in whole lanes, 4 bytes, 3 layers
    assert st["kv_bytes_per_position"] == 3 * 128 * 4
    assert eng.cache_hbm_bytes()["allocated"] == (
        eng.pool_pages * PAGE * st["kv_bytes_per_position"])


def test_absorbed_equals_expanded_on_the_same_cache(params, monkeypatch):
    """One query token against 21 cached positions, all three readings:
    expanded, absorbed through the gathered window, absorbed through the
    kernel (blocks of two pages: three of them)."""
    monkeypatch.setattr(paged_kernel, "KEY_BLOCK", 2 * PAGE)
    ids = prompts(2, 22, seed=3)
    tables = tables_for(2)
    pool = decode.init_paged_cache(CFG, 2 * (MAX_LEN // PAGE) + 1, PAGE)
    _, pool, _ = kimi_k2.forward(
        params, ids[:, :21], CFG, pool, jnp.zeros((2,), jnp.int32), tables)
    h, d = 4, MODEL["qk_nope_head_dim"] + MODEL["qk_rope_head_dim"]
    q = jax.random.normal(jax.random.key(5), (2, 1, h, d), jnp.float32)
    wkv_b = params["moe"]["attn"]["wkv_b"][0]
    pos = jnp.full((2,), 20)
    a = kimi_k2.attend_absorbed(q, pool["latent"], 1, tables, pos, wkv_b, CFG)
    e = kimi_k2.attend_expanded(q, pool["latent"], 1, tables, pos, wkv_b, CFG)
    k = kimi_k2.attend_absorbed(
        q, pool["latent"], 1, tables, pos, wkv_b, CFG, "kernel_interpret")
    assert a.shape == e.shape == k.shape == (2, 1, h, MODEL["v_head_dim"])
    np.testing.assert_allclose(a, e, atol=2e-6, rtol=0)
    np.testing.assert_allclose(k, e, atol=2e-6, rtol=0)


def expert_layer_inputs(params, tokens=40):
    mp = jax.tree.map(lambda a: a[0], params["moe"]["mlp"])
    h = jax.random.normal(jax.random.key(11), (tokens, 32), jnp.float32)
    return h, mp


def dropless(h, mp, offset, live=None):
    return moe.moe_dropless(
        h, mp, top_k=4, expert_offset=offset, routed_scale=2.827,
        activation=jax.nn.silu, live=live)


def test_all_shares_add_up_to_the_uncut_layer(expert_path):
    """16 experts over four chips of 4: the four parts the program computes,
    the shared expert counted once, are the uncut reference's whole layer."""
    uncut = dict(MODEL, n_routed_experts_held=16, expert_offset=0)
    h, mp = expert_layer_inputs(ref.init_params(9, uncut, "float32"))
    want = ref.expert_layer(h, mp, ref.shapes(uncut))
    shared = ref._swiglu(h, mp["shared"], "f32")
    total, pairs = shared, 0
    for offset in (0, 4, 8, 12):
        share = dict(mp, **{k: mp[k][offset:offset + 4]
                            for k in ("w_gate", "w_in", "w_out")})
        y, counts = dropless(h, share, offset)
        total = total + (y - shared)
        pairs += int(counts[0])
        # and each share is the reference's share
        np.testing.assert_allclose(y, ref.expert_layer(
            h, share, ref.shapes(dict(uncut, n_routed_experts_held=4,
                                      expert_offset=offset))), atol=2e-6)
    assert pairs == 40 * 4  # every pair is somebody's
    np.testing.assert_allclose(total, want, atol=3e-6, rtol=0)


def test_a_bias_that_sends_every_token_here_drops_nothing(params, expert_path):
    """No capacity: with all four picks of every token on the four held
    experts, all T x 4 pairs are computed; padding rows route nowhere."""
    h, mp = expert_layer_inputs(params, tokens=50)
    mp = dict(mp, bias=jnp.zeros((16,)).at[4:8].set(10.0))
    y, counts = dropless(h, mp, 4)
    assert int(counts[0]) == 50 * 4 and int(counts[2]) == 4
    assert int(counts[1]) >= 50 * 4
    np.testing.assert_allclose(
        y, ref.expert_layer(h, mp, ref.shapes(MODEL)), atol=2e-6, rtol=0)
    live = jnp.arange(50) < 30
    y_live, counts = dropless(h, mp, 4, live)
    assert int(counts[0]) == 30 * 4
    np.testing.assert_array_equal(y_live[:30], y[:30])


def test_a_row_alone_and_among_seven_others_gives_the_same_logits(params, expert_path):
    ids = prompts(8, 16, seed=2)
    pool = decode.init_paged_cache(CFG, 8 * (MAX_LEN // PAGE) + 1, PAGE)
    tables = tables_for(8)

    def run(rows):
        n = len(rows)
        sel = jnp.asarray(rows)
        lg, p, _ = kimi_k2.forward(
            params, ids[sel, :15], CFG, pool, jnp.zeros((n,), jnp.int32),
            tables[sel])
        lg1, _, _ = kimi_k2.forward(
            params, ids[sel, 15:], CFG, p, jnp.full((n,), 15), tables[sel])
        return lg[0], lg1[0]

    alone, among = run([3]), run([3, 0, 1, 2, 4, 5, 6, 7])
    for a, b in zip(alone, among):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


def test_rows_in_blocks_change_nothing(params, monkeypatch, expert_path):
    """A prefill group wider than TOKEN_BLOCK runs each layer over groups
    of rows in turn: same logits, same pool, same counts."""
    ids = prompts(4, 8, seed=4)
    pool = decode.init_paged_cache(CFG, 4 * (MAX_LEN // PAGE) + 1, PAGE)
    args = (params, ids, CFG, pool, jnp.zeros((4,), jnp.int32), tables_for(4))
    whole = kimi_k2.forward(*args)
    monkeypatch.setattr(kimi_k2, "TOKEN_BLOCK", 16)  # two rows at a time
    split = kimi_k2.forward(*args)
    np.testing.assert_allclose(split[0], whole[0], atol=1e-6, rtol=0)
    np.testing.assert_allclose(
        split[1]["latent"], whole[1]["latent"], atol=1e-6, rtol=0)
    np.testing.assert_array_equal(split[2][0], whole[2][0])


def test_yarn_frequencies_and_scale_against_hand_worked_values():
    """Kimi-K2.5's rope_scaling: 32 pairs, theta 50000, factor 64 over an
    original 4096: pairs 0..8 keep their frequency, 20..31 are divided by
    64, a linear ramp between; m = 0.1 ln 64 + 1, s = m^2 / sqrt(192)."""
    # 64 ln(4096 / (32 * 2 pi)) / (2 ln 50000) = 8.91 -> 8; with 1 turn:
    # 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16 -> 20
    assert rope.yarn_correction_range(32, 1, 64, 50000.0, 4096) == (8, 20)
    f = np.asarray(rope.yarn_inv_freq(64, 50000.0, 64.0, 4096, 32, 1))
    extra = 50000.0 ** (-np.arange(32) * 2.0 / 64)
    np.testing.assert_allclose(f[:9], extra[:9], rtol=1e-6)
    np.testing.assert_allclose(f[20:], extra[20:] / 64, rtol=1e-6)
    # pair 14 is halfway up the ramp: the mean of the two
    np.testing.assert_allclose(
        f[14], 0.5 * extra[14] + 0.5 * extra[14] / 64, rtol=1e-6)
    np.testing.assert_allclose(f, np.asarray(ref.yarn_inv_freq(ref.shapes(
        dict(MODEL, qk_rope_head_dim=64, rope_scaling=dict(
            MODEL["rope_scaling"], original_max_position_embeddings=4096))))),
        rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.4159) < 1e-4
    assert rope.yarn_mscale(64, 1) == pytest.approx(m)
    cfg = model_config("kimi-k2.5-ep32")
    assert kimi_k2.softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))
    assert kimi_k2.softmax_scale(cfg) == pytest.approx(0.14468, abs=1e-5)
    assert kimi_k2.latent_width(cfg) == 576 and kimi_k2.page_width(cfg) == 640
    # plain frequencies are untouched where no scaling is given
    cos, _ = rope.rope_angles(4, 8, 10000.0)
    np.testing.assert_allclose(cos[1, :4], np.cos(10000.0 ** (
        -np.arange(4) * 2.0 / 8)), rtol=1e-6)


def test_selection_bias_gives_every_share_the_same_values():
    """The benchmark's draw of the selection bias: every run of ``held``
    experts (a chip's share) holds the same stratified quantiles of
    normal(0, 0.02), in an order the key draws, so that the seed moves
    which experts are favoured and not how many pairs a chip is sent."""
    b = np.asarray(ref.selection_bias(jax.random.key(3), 2, 384, 12))
    assert b.shape == (2, 384) and b.dtype == np.float32
    shares = np.sort(b.reshape(2, 32, 12), axis=-1)
    np.testing.assert_array_equal(shares, np.broadcast_to(shares[0, 0], shares.shape))
    # the 12 midpoint quantiles: symmetric, the outermost at +-1.7317 sigma
    np.testing.assert_allclose(shares[0, 0][[0, 5, 6, 11]] / 0.02,
                               [-1.7317, -0.1046, 0.1046, 1.7317], atol=2e-4)
    assert len({tuple(r) for r in b.reshape(64, 12)}) > 60  # orders differ
    other = np.asarray(ref.selection_bias(jax.random.key(4), 2, 384, 12))
    assert (other != b).mean() > 0.8
    assert (ref.init_params(7, MODEL)["moe"]["mlp"]["bias"].shape == (2, 16))
    with pytest.raises(ValueError, match="whole shares"):
        ref.selection_bias(jax.random.key(0), 1, 16, 5)


def test_engines_refuse_what_they_cannot_serve(params):
    """The capacity-routed layer stays refused by the batched engines, with
    the reason; the latent pool has no dense layout."""
    moe_cfg = model_config("tiny", n_experts=4)
    for engine, kw in ((BatchedDecodeEngine, {}),
                       (PagedBatchedDecodeEngine, {"page_size": 16})):
        with pytest.raises(NotImplementedError, match="capacity"):
            engine(moe_cfg, slots=2, max_len=64, **kw)
    with pytest.raises(NotImplementedError, match="latent"):
        BatchedDecodeEngine(CFG, slots=2, max_len=MAX_LEN)
    with pytest.raises(NotImplementedError, match="kv_quant"):
        PagedBatchedDecodeEngine(
            CFG, slots=2, max_len=MAX_LEN, page_size=PAGE, kv_quant="int8")
    with pytest.raises(NotImplementedError, match="speculative_k"):
        PagedBatchedDecodeEngine(
            CFG, slots=2, max_len=MAX_LEN, page_size=PAGE, speculative_k=2)
    # the kernel is served (it was refused until PR 33); unset, the option
    # is the kernel on a TPU and the gathered window here, and the dense
    # families' stays the gather everywhere
    for asked, built in (("kernel", "kernel"), ("auto", "gather"),
                         (None, "gather")):
        eng = PagedBatchedDecodeEngine(
            CFG, slots=2, max_len=MAX_LEN, page_size=PAGE,
            paged_attention=asked)
        assert eng.stats()["paged_decode_impl"] == built
    dense = PagedBatchedDecodeEngine(
        model_config("tiny"), slots=2, max_len=64, page_size=16)
    assert dense.stats()["paged_decode_impl"] == "gather"
    with pytest.raises(KeyError, match="kimi-k2.5-ep32"):
        model_config("no-such-preset")
