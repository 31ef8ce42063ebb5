"""The mellum family (models/mellum.py, ops/moe.moe_dropless with the softmax
rule, the windowed ops/paged_kernel.py, decode.blocked_attention, the two
page groups of serving/engine.py) against its plain reference,
perfbench/reference/mellum.py, at a tiny float32 size on the CPU that keeps
the published PATTERN: two periods of [s s s f], 8 experts 2 a token, a
window of 12 positions over pages of 4, so that every test runs past the
window and past several page releases.

Logits are compared, not tokens, wherever a test reaches them; tolerances
are absolute on logits of std 1.2 and each says what it allows.
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench.reference import mellum as ref  # noqa: E402
from pytorch_distributed_tpu.config import (  # noqa: E402
    MeshConfig,
    ModelConfig,
    model_config,
)
from pytorch_distributed_tpu.models import decode, mellum  # noqa: E402
from pytorch_distributed_tpu.ops import moe, paged_kernel  # noqa: E402
from pytorch_distributed_tpu.serving import block_pool  # noqa: E402
from pytorch_distributed_tpu.serving.engine import (  # noqa: E402
    BatchedDecodeEngine,
    PagedBatchedDecodeEngine,
)

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
MODEL = dict(
    hidden_size=32, vocab_size=96, num_hidden_layers=8,
    layer_types=PERIOD * 2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=24, sliding_window=12, rms_norm_eps=1e-6,
    norm_topk_prob=True,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0}},
    initializer_range=0.2)
PAGE, MAX_LEN, CHUNK, WINDOW = 4, 64, 8, 12
N_PAGES = MAX_LEN // PAGE
# float32 throughout: what differs between the program and the reference is
# the ORDER of sums (a running softmax a block of keys at a time, experts in
# sorted blocks against every expert over every token), a few float32
# roundings deep
ATOL = 5e-5


def program_config(model=MODEL, **kw) -> ModelConfig:
    full = model["rope_parameters"]["full_attention"]
    return ModelConfig(**dict(dict(
        family="mellum", vocab_size=model["vocab_size"], n_ctx=MAX_LEN,
        n_embd=model["hidden_size"], n_layer=model["num_hidden_layers"],
        n_head=model["num_attention_heads"],
        n_kv_head=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"],
        layer_types=tuple(model["layer_types"]),
        sliding_window=model["sliding_window"],
        n_routed_experts=model["num_experts"],
        num_experts_per_tok=model["num_experts_per_tok"],
        moe_intermediate_size=model["moe_intermediate_size"],
        norm_topk_prob=model["norm_topk_prob"],
        rope_theta=full["rope_theta"], rope_factor=full["factor"],
        rope_original_max_position=full["original_max_position_embeddings"],
        rope_beta_fast=full["beta_fast"], rope_beta_slow=full["beta_slow"],
        rope_attention_factor=full["attention_factor"], dtype="float32",
        param_dtype="float32", embd_pdrop=0.0, attn_pdrop=0.0,
        resid_pdrop=0.0, activation_function="silu",
        layer_norm_epsilon=model["rms_norm_eps"]), **kw))


CFG = program_config()


@pytest.fixture(scope="module")
def params():
    return ref.init_params(7, MODEL, "float32")


def engine(**kw):
    return PagedBatchedDecodeEngine(CFG, **dict(dict(
        slots=4, max_len=MAX_LEN, page_size=PAGE, prefill_chunk=CHUNK),
        **kw))


@pytest.fixture(scope="module")
def warm(params):
    """One warmed engine the tests that only serve requests share: the
    compile is most of this file's time."""
    eng = engine()
    eng.warmup(params)
    return eng


@jax.jit
def FWD(params, ids, cache, pos, tables, live):
    return mellum.forward(params, ids, CFG, cache, pos, tables, live=live)


def forward(params, ids, cache, pos, tables, live=None):
    b, t = ids.shape
    return FWD(params, ids, cache, jnp.asarray(pos, jnp.int32), tables,
               jnp.ones((b, t), bool) if live is None else live)


def tables_for(rows: int):
    """[rows, 2 N_PAGES]: row b owns pages 1 + b*n .. of EACH group (page 0
    is the scratch page), every page of the window group kept."""
    one = 1 + jnp.arange(rows * N_PAGES, dtype=jnp.int32).reshape(
        rows, N_PAGES)
    return jnp.concatenate([one, one], axis=1)


def release_behind(tables, pos):
    """The window tables with every page wholly behind ``pos[b] - WINDOW +
    1`` pointed at the scratch page, as the engine leaves them."""
    tables = np.array(tables)
    for b, p in enumerate(pos):
        keep = block_pool.first_kept_page(int(p), WINDOW, PAGE)
        tables[b, N_PAGES:N_PAGES + keep] = 0
    return jnp.asarray(tables)


def prompts(rows: int, length: int, seed=1):
    return jax.random.randint(
        jax.random.key(seed), (rows, length), 0, MODEL["vocab_size"])


def fresh_cache(rows: int):
    pages = rows * N_PAGES + 1
    return decode.init_paged_cache(
        CFG, pages, PAGE, window_pool_pages=pages)


def chunk_of(ids, start, width=CHUNK):
    """(tokens [B, width] zero-padded, live [B, width]) of ids[:, start:]."""
    n = min(width, ids.shape[1] - start)
    chunk = jnp.zeros((ids.shape[0], width), jnp.int32).at[:, :n].set(
        ids[:, start:start + n])
    return chunk, jnp.broadcast_to(jnp.arange(width) < n, chunk.shape)


@jax.jit
def _reference_logits(params, ids):
    return ref.logits(params, ids, MODEL)


def is_greedy_reference(params, prompt, gen) -> bool:
    """Whether ``gen`` is the reference's greedy continuation of ``prompt``
    (one full forward over prompt + gen, padded to one compiled length; the
    model is causal, so the padding changes nothing before it)."""
    seq = list(prompt) + list(gen)
    ids = np.zeros((1, MAX_LEN), np.int32)
    ids[0, :len(seq)] = seq
    lg = _reference_logits(params, jnp.asarray(ids))[0]
    want = np.asarray(jnp.argmax(lg[len(prompt) - 1:len(seq) - 1], axis=-1))
    return list(gen) == want.tolist()


# -- the model against the reference ------------------------------------------


def test_chunked_prefill_then_decode_equals_reference_logits(params):
    """Five chunks of 8, the last holding 5 tokens and 3 of padding, then
    eight single tokens, with the window tables released behind the window
    after every call as the engine does: every position's logits are the
    reference's full forward's, 33 positions past the window."""
    ids = prompts(2, 45)
    want = ref.logits(params, ids, MODEL)
    assert float(want.std()) > 0.5  # ATOL is absolute: 1e-4 of the logits
    cache = fresh_cache(2)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, 33, PAGE, 32), "v": (2, 33, PAGE, 32),
        "k_w": (6, 33, PAGE, 32), "v_w": (6, 33, PAGE, 32)}
    tables, got = tables_for(2), []
    for start in (0, 8, 16, 24, 32):
        chunk, live = chunk_of(ids[:, :37], start)
        lg, cache, counts = forward(
            params, chunk, cache, jnp.full((2,), start),
            release_behind(tables, [start] * 2), live)
        n = min(8, 37 - start)
        got.append(lg[:, :n])
        # pairs routed: 8 layers x 2 experts a token x the real tokens
        assert int(counts[0]) == 8 * 2 * 2 * n
        assert int(counts[0]) <= int(counts[1]) and 0 < int(counts[2]) <= 64
    for pos in range(37, 45):
        lg, cache, counts = forward(
            params, ids[:, pos:pos + 1], cache, jnp.full((2,), pos),
            release_behind(tables, [pos] * 2))
        got.append(lg)
        assert int(counts[0]) == 8 * 2 * 2
    np.testing.assert_allclose(
        jnp.concatenate(got, axis=1), want, atol=ATOL, rtol=0)
    # decode.forward routes the family, and counts on request
    lg, _, counts = decode.forward(
        params, ids[:, :8], CFG, fresh_cache(2), jnp.zeros((2,), jnp.int32),
        block_tables=tables, return_aux=True)
    np.testing.assert_allclose(lg, want[:, :8], atol=ATOL, rtol=0)
    assert int(counts[0]) == 8 * 2 * 16
    # and the cache-free forward
    np.testing.assert_allclose(
        mellum.apply(params, ids, CFG), want, atol=ATOL, rtol=0)


def test_a_page_released_one_early_moves_the_logits(params):
    """The window's oldest page still holds keys a query sees: with it
    pointed at the scratch page the logits differ, by far more than ATOL."""
    ids = prompts(1, 30, seed=3)
    want = ref.logits(params, ids, MODEL)[:, 29]
    cache, tables = fresh_cache(1), tables_for(1)
    for start in (0, 8, 16, 24):
        chunk, live = chunk_of(ids[:, :29], start)
        _, cache, _ = forward(
            params, chunk, cache, jnp.full((1,), start), tables, live)
    step = (params, ids[:, 29:30], cache, jnp.full((1,), 29))
    right = release_behind(tables, [29])
    early = np.array(right)
    early[0, N_PAGES + block_pool.first_kept_page(29, WINDOW, PAGE)] = 0
    np.testing.assert_allclose(
        forward(*step, right)[0][:, 0], want, atol=ATOL, rtol=0)
    assert float(jnp.max(jnp.abs(
        forward(*step, jnp.asarray(early))[0][:, 0] - want))) > 100 * ATOL


def test_a_row_alone_and_among_others_gives_the_same_logits(params):
    """Rows at unrelated depths in one call (one past the window, one inside
    it, a free lane): each row's logits are what it gets alone."""
    ids = prompts(3, 33, seed=5)
    depths = [32, 7, 0]
    cache, tables = fresh_cache(3), tables_for(3)
    for start in (0, 8, 16, 24):
        chunk, live = chunk_of(ids[:, :32], start)
        _, cache, _ = forward(
            params, chunk, cache, jnp.full((3,), start), tables, live)
    tables = release_behind(tables, depths)
    tok = jnp.stack([ids[b, d:d + 1] for b, d in enumerate(depths)])
    together, _, _ = forward(params, tok, cache, jnp.asarray(depths), tables)
    for b in range(3):
        alone, _, _ = forward(
            params, tok[b:b + 1], cache, jnp.asarray(depths[b:b + 1]),
            tables[b:b + 1])
        np.testing.assert_allclose(together[b], alone[0], atol=ATOL, rtol=0)
    np.testing.assert_allclose(
        together[0, 0], ref.logits(params, ids[:1], MODEL)[0, 32],
        atol=ATOL, rtol=0)


def test_rows_in_blocks_change_nothing(params, monkeypatch):
    """TOKEN_BLOCK below the group: the rows go through a layer a block at a
    time, the cache carried from block to block; the same logits."""
    ids = prompts(4, 8, seed=6)
    args = (params, ids, CFG, fresh_cache(4), jnp.zeros((4,), jnp.int32),
            tables_for(4))
    whole, _, counts = mellum.forward(*args)
    monkeypatch.setattr(mellum, "TOKEN_BLOCK", 16)
    blocks, _, counts2 = mellum.forward(*args)
    np.testing.assert_allclose(blocks, whole, atol=ATOL, rtol=0)
    assert int(counts[0]) == int(counts2[0]) == 8 * 2 * 32


# -- the pieces ---------------------------------------------------------------


def test_the_softmax_rule_against_a_hand_worked_case():
    """Two tokens over four experts, top 2: the softmax over ALL four, the
    two largest, renormalised (or not)."""
    router = jnp.eye(4, dtype=jnp.float32)  # the logits are x itself
    x = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 3.0 - 1e-3]])
    idx, gates = moe._route_softmax(x, router, 2, True)
    assert idx.tolist() == [[0, 1], [2, 3]]
    e = np.exp([2.0, 1.0, 0.0, -1.0])
    np.testing.assert_allclose(
        gates[0], [e[0] / (e[0] + e[1]), e[1] / (e[0] + e[1])], rtol=1e-6)
    _, raw = moe._route_softmax(x, router, 2, False)
    np.testing.assert_allclose(raw[0], e[:2] / e.sum(), rtol=1e-6)
    assert float(raw[0].sum()) < 1.0 and abs(float(gates.sum()) - 2.0) < 1e-6
    # the reference states the same rule on its own
    want = ref.route(x, router, dict(k=2, norm_topk=True), "f32")
    np.testing.assert_allclose(want[0], [gates[0, 0], gates[0, 1], 0, 0],
                               rtol=1e-6)
    with pytest.raises(ValueError, match="route"):
        moe.moe_dropless(x, {}, top_k=2, expert_offset=0,
                         activation=jax.nn.silu, route="tanh")


def test_the_dropless_layer_with_the_softmax_rule_equals_the_reference():
    """One layer's experts, whole (offset 0, 8 held), no shared expert:
    sorted blocks of pairs against every expert over every token."""
    key = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(key[0], (21, 32))
    p = {"router": jax.random.normal(key[1], (32, 8)),
         "w_gate": jax.random.normal(key[2], (8, 32, 24)) * 0.2,
         "w_in": jax.random.normal(key[3], (8, 32, 24)) * 0.2,
         "w_out": jax.random.normal(key[4], (8, 24, 32)) * 0.2}
    got, counts = moe.moe_dropless(
        x, p, top_k=2, expert_offset=0, activation=jax.nn.silu,
        route="softmax")
    stacks = {n: p[n][None] for n in ("w_gate", "w_in", "w_out")}
    want = ref._experts(x, p["router"], stacks, 0,
                        dict(k=2, norm_topk=True, x=8), "f32")
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    assert counts.tolist()[0] == 42 and counts.tolist()[2] <= 8


def test_the_sigmoid_rule_is_bit_equal_to_before():
    """Cell 4's layer: ``moe_dropless`` left at its default rule, with its
    bias, scale and shared expert, gives bit for bit what the parent's code
    gives (restated here from the parent: route, sorted blocks, shared)."""
    key = jax.random.split(jax.random.key(4), 9)
    x = jax.random.normal(key[0], (19, 32))
    p = {"router": jax.random.normal(key[1], (32, 16)),
         "bias": jax.random.normal(key[2], (16,)) * 0.1,
         "w_gate": jax.random.normal(key[3], (4, 32, 20)) * 0.2,
         "w_in": jax.random.normal(key[4], (4, 32, 20)) * 0.2,
         "w_out": jax.random.normal(key[5], (4, 20, 32)) * 0.2,
         "shared": {"gate": jax.random.normal(key[6], (32, 20)) * 0.2,
                    "up": jax.random.normal(key[7], (32, 20)) * 0.2,
                    "down": jax.random.normal(key[8], (20, 32)) * 0.2}}
    kw = dict(top_k=4, expert_offset=4, routed_scale=2.827,
              activation=jax.nn.silu)
    got, counts = moe.moe_dropless(x, p, **kw)

    def parent(xt, params):
        idx, gates = moe._route_sigmoid(
            xt, params["router"], params["bias"], 4, 2.827)
        local = idx - 4
        here = (local >= 0) & (local < 4)
        e_flat = jnp.where(here, local, 4).reshape(-1).astype(jnp.int32)
        n = jnp.bincount(e_flat, length=5)[:4]
        stacks = {k: params[k][None] for k in ("w_gate", "w_in", "w_out")}
        routed, _ = moe._experts_grouped(
            xt, stacks, jnp.asarray(0, jnp.int32), e_flat, n, gates,
            moe.dropless_block_rows(19, 4, 16), jax.nn.silu)
        sh = params["shared"]
        shared = (jax.nn.silu(xt @ sh["gate"]) * (xt @ sh["up"])) @ sh["down"]
        return (routed + shared.astype(jnp.float32)).astype(xt.dtype)

    np.testing.assert_array_equal(np.asarray(got), np.asarray(parent(x, p)))
    assert counts.tolist()[0] > 0


@pytest.mark.parametrize("quantized", [False, True])
def test_the_paged_kernel_with_first_zero_is_the_kernel_as_it_is(
        quantized, monkeypatch):
    """``first`` = 0 on every row: bit for bit the call without a window.
    And a window: the reference's masked softmax, with the pages behind it
    pointed at the scratch page."""
    monkeypatch.setattr(paged_kernel, "KEY_BLOCK", 2 * PAGE)
    key = jax.random.split(jax.random.key(0), 5)
    rows, pages = 4, 4 * N_PAGES + 1
    q = jax.random.normal(key[0], (rows, 4, 16))
    k = jax.random.normal(key[1], (pages, PAGE, 32))
    v = jax.random.normal(key[2], (pages, PAGE, 32))
    scales = {}
    if quantized:
        k, v = (jnp.round(a * 40).astype(jnp.int8) for a in (k, v))
        scales = dict(
            k_scales=jax.random.uniform(key[3], (pages, PAGE, 2)) * 0.05,
            v_scales=jax.random.uniform(key[4], (pages, PAGE, 2)) * 0.05)
    tables = tables_for(rows)[:, :N_PAGES]
    depths = jnp.asarray([45, 2 * PAGE - 1, 2 * PAGE, 0])
    plain = paged_kernel.paged_decode_attention(
        q, k, v, tables, depths, interpret=True, **scales)
    zero = paged_kernel.paged_decode_attention(
        q, k, v, tables, depths, first=jnp.zeros((rows,), jnp.int32),
        interpret=True, **scales)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(zero))
    first = jnp.maximum(depths - (WINDOW - 1), 0)
    behind = np.array(tables)
    for b in range(rows):
        behind[b, :int(first[b]) // PAGE] = 0
    got = paged_kernel.paged_decode_attention(
        q, k, v, jnp.asarray(behind), depths, first=first, interpret=True,
        **scales)
    want = paged_kernel.paged_decode_attention_reference(
        q, k, v, tables, depths, first=first, **scales)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert float(jnp.max(jnp.abs(got[0] - plain[0]))) > 1e-2  # row 0: 45


def test_blocked_attention_reads_no_further_back_than_the_window(
        monkeypatch):
    """A chunk's queries against pages of which everything behind the first
    query's window is the scratch page (filled with huge values here): the
    gathered path's masked softmax over the whole table."""
    monkeypatch.setattr(paged_kernel, "KEY_BLOCK", 2 * PAGE)
    key = jax.random.split(jax.random.key(1), 3)
    rows, pages = 3, 3 * N_PAGES + 1
    q = jax.random.normal(key[0], (rows, CHUNK, 4, 16))
    cache = {n: jax.random.normal(kk, (2, pages, PAGE, 32)).at[:, 0].set(1e4)
             for n, kk in (("k", key[1]), ("v", key[2]))}
    tables = tables_for(rows)[:, :N_PAGES]
    pos = jnp.asarray([40, 8, 0])
    behind = np.array(tables)
    for b in range(rows):
        behind[b, :block_pool.first_kept_page(int(pos[b]), WINDOW, PAGE)] = 0
    for window, tab in ((WINDOW, jnp.asarray(behind)), (None, tables)):
        got = decode.blocked_attention(
            q, cache, 1, pos, tab, window=window)
        want = decode._cached_attention(
            q, cache, 1, pos, tables, window=window)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_the_published_preset_and_what_it_declares():
    cfg = model_config("mellum2-12b-a2.5b-l12", dtype="bfloat16")
    assert mellum.layer_period(cfg) == tuple(PERIOD)
    assert (mellum.n_layers_of(cfg, "sliding_attention"),
            mellum.n_layers_of(cfg, "full_attention")) == (9, 3)
    assert cfg.head_dim == 128 != cfg.n_embd // cfg.n_head
    assert decode.kv_bytes_per_position(cfg) == 3 * 2048
    assert decode.kv_bytes_per_position(cfg, group="window") == 9 * 2048
    asks = decode.serving(cfg)
    assert asks.window == 1024 and not asks.dense_cache
    assert asks.state_bytes_per_row == 0
    assert asks.aux_counts == (
        "moe_pairs_here", "moe_rows_computed", "moe_experts_hit")
    assert set(asks.unserved) == {
        "mesh", "kv_quant", "weight_quant", "adapters", "speculative_k",
        "handoff", "prefix"}
    assert block_pool.window_pages_bound(1024, 512, 64) == 25
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, 4097, 64, window_pool_pages=801))
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "k": ((3, 4097, 64, 512), "bfloat16"),
        "v": ((3, 4097, 64, 512), "bfloat16"),
        "k_w": ((9, 801, 64, 512), "bfloat16"),
        "v_w": ((9, 801, 64, 512), "bfloat16")}
    assert cfg.rope_parameters["full_attention"]["attention_factor"] == (
        1.2772588722239782)
    # the dense families declare nothing of the kind
    assert decode.Serving().window == 0
    with pytest.raises(ValueError, match="mellum"):
        program_config(layer_types=("sliding_attention",) * 3)
    with pytest.raises(ValueError, match="mellum"):
        program_config(sliding_window=0)


def test_the_programs_init_draws_the_references_tree():
    """``mellum.init`` (scripts/serve.py's random weights) and the
    benchmark's ``init_params`` are two copies of one initialisation: held
    equal here, leaf for leaf, at the draws a configuration file gets (no
    ``initializer_range``)."""
    model = {k: v for k, v in MODEL.items() if k != "initializer_range"}
    want = ref.init_params(7, model, "float32")
    got = mellum.init(jax.random.fold_in(jax.random.key(7), 0), CFG)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for path, leaf in jax.tree_util.tree_leaves_with_path(want):
        mine = got
        for k in path:
            mine = mine[k.key]
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(leaf), rtol=1e-6, atol=0,
            err_msg=str(path))
    assert want["sliding_attention"]["mlp"]["w_gate"].shape == (6, 8, 32, 24)


def test_the_two_rotary_tables_are_the_references(params):
    """The plain table for the sliding layers, YaRN's times
    ``attention_factor`` for the full ones, against the reference's own
    statement of HF's ``_compute_yarn_parameters``."""
    pos = jnp.asarray([0, 37])
    tables = {k: mellum.rope_table(CFG, k, pos, 5) for k in mellum.KINDS}
    s = ref.shapes(MODEL)
    for kind in mellum.KINDS:
        freqs, factor = ref.inv_freq(s, kind)
        angles = (pos[:, None] + jnp.arange(5))[..., None] * freqs
        angles = jnp.concatenate([angles, angles], axis=-1)
        np.testing.assert_allclose(
            tables[kind][0], jnp.cos(angles) * factor, atol=1e-6)
        np.testing.assert_allclose(
            tables[kind][1], jnp.sin(angles) * factor, atol=1e-6)
    assert ref.inv_freq(s, "full_attention")[1] == pytest.approx(1.1386294)
    assert not np.allclose(tables["sliding_attention"][0],
                           tables["full_attention"][0])


# -- through the engine ---------------------------------------------------------


def serve(eng, params, requests, new=6):
    """[(prompt, tokens generated)] for greedy requests, in order."""
    rids = [eng.submit(np.asarray(p, np.int32), new) for p in requests]
    eng.run(params)
    out = []
    for rid, p in zip(rids, requests):
        res = eng.pop_result(rid)
        assert res.state == "DONE"
        out.append([int(t) for t in res.tokens][len(p):])
    return out


def watch_the_pools(eng):
    """Check the window group's invariant after every tick of ``eng``: no row
    over its bound, every held page referenced once and by one table, every
    released entry the scratch page, the free list and the tables disjoint."""
    real, seen, mp = eng.step, [], eng.max_pages

    def step(params):
        out = real(params)
        held = []
        for s in eng._slots:
            if s is None:
                continue
            mine = s.table[mp + s.wfirst:mp + s.wnext]
            assert len(mine) <= eng._window_row_bound
            assert (mine != 0).all()
            assert not s.table[mp:mp + s.wfirst].any()
            assert not s.table[mp + s.wnext:].any()
            # nothing a query to come can see has gone
            assert s.wfirst <= block_pool.first_kept_page(
                s.pos, WINDOW, eng.page_size)
            held += mine.tolist()
        assert len(held) == len(set(held)) == eng.wpool.pages_in_use()
        assert not set(held) & set(eng.wpool._free)
        assert len(held) + eng.wpool.free_pages() == eng.window_pool_pages - 1
        seen.append(len(held))
        return out

    eng.step = step
    return seen


def test_paged_engine_serves_the_reference_greedy_tokens(params, warm):
    """Through PagedBatchedDecodeEngine (admission, two block pools, chunked
    prefill with ragged final chunks, releases behind the window, the
    sampler): more requests than rows, so every slot is reused; every reply
    the reference's greedy continuation, most of them from past the window;
    no compile after the warm-up; the pools' invariant at every tick; the
    counters add up."""
    compiled = warm.compile_count()
    before = dict(warm.stats()["counters"])
    seen = watch_the_pools(warm)
    rng = np.random.default_rng(0)
    sent = [rng.integers(0, MODEL["vocab_size"], n).tolist()
            for n in (5, 19, 8, 41, 30, 3, 17)]
    try:
        got = serve(warm, params, sent, new=14)
    finally:
        del warm.step
    assert warm.compile_count() == compiled
    for prompt, gen in zip(sent, got):
        assert len(gen) == 14 and is_greedy_reference(params, prompt, gen)
    st = warm.stats()
    c = {k: v - before.get(k, 0) for k, v in st["counters"].items()}
    assert c["moe_tokens.prefill"] == sum(map(len, sent))
    assert c["moe_tokens.decode_step"] == 7 * 13
    assert c["moe_pairs_here.decode_step"] == 8 * 2 * 7 * 13
    assert c["kv_positions_read.window"] < c["kv_positions_read.full"]
    assert c["kv_positions_read.window"] <= WINDOW * 7 * 13
    assert c["window_pages_released"] > 20
    assert 0 < c["window_positions_needed"] <= c["window_positions_held"]
    assert max(seen) > 0 and seen[-1] == 0
    assert warm.pool.pages_in_use() == warm.wpool.pages_in_use() == 0
    assert st["prefix_queries"] == 0 and st["prefix_hits"] == 0
    assert st["window_pool_pages"] == 4 * 6 + 1  # (11 + 8) / 4 -> 5, + 1
    assert st["window_pages_in_use"] == 0
    assert st["paged_decode_impl"] == "gather"
    assert warm.timers.snapshot()["engine.release_window"]["count"] > 0
    assert warm.cache_hbm_bytes()["allocated"] == 4 * PAGE * 2 * 2 * 16 * (
        2 * (4 * N_PAGES + 1) + 6 * (4 * 6 + 1))


def test_the_kernel_engine_serves_the_gather_engines_tokens(
        params, warm, monkeypatch):
    """``paged_attention="kernel_interpret"``: the decode step reads both
    groups' pages through ops/paged_kernel.py (blocks of two pages, so the
    deeper rows take several, the sliding layers' from the window's first
    block): the gather engine's tokens, the reference's continuations."""
    monkeypatch.setattr(paged_kernel, "KEY_BLOCK", 2 * PAGE)
    rng = np.random.default_rng(0)
    sent = [rng.integers(0, MODEL["vocab_size"], n).tolist()
            for n in (5, 19, 8, 41, 30, 3, 17)]
    eng = engine(paged_attention="kernel_interpret")
    assert eng.stats()["paged_decode_impl"] == "kernel_interpret"
    got = serve(eng, params, sent, new=14)
    assert got == serve(warm, params, sent, new=14)
    assert all(is_greedy_reference(params, p, g) for p, g in zip(sent, got))


def test_left_unset_the_family_builds_the_gather_off_the_chip():
    assert engine()._paged_impl == "gather"
    assert engine(paged_attention="auto")._paged_impl == "gather"


def test_a_slot_reused_by_a_second_request_serves_a_fresh_engines_tokens(
        params, warm):
    """One row: the second request takes the first one's slot, and pages the
    first one's window left behind; it is served what a fresh engine
    serves."""
    rng = np.random.default_rng(5)
    a, b = (rng.integers(0, 96, n).tolist() for n in (33, 21))
    eng = engine(slots=1)
    first, second = serve(eng, params, [a, b], new=10)
    assert second == serve(engine(slots=1), params, [b], new=10)[0]
    assert is_greedy_reference(params, a, first)
    assert is_greedy_reference(params, b, second)


@pytest.mark.parametrize("group", ["full", "window"])
def test_preempt_and_resume_serves_the_uninterrupted_greedy_tokens(
        params, group):
    """Either group running dry preempts the youngest row; both groups'
    pages go back, and the resume re-prefills prompt + generated from
    position 0, which rebuilds the window group's: the uninterrupted
    tokens."""
    rng = np.random.default_rng(9)
    sent = [rng.integers(0, 96, n).tolist() for n in (14, 15)]
    # full: 5 usable pages of 8 < 2 rows x 4 pages; window: 4 usable pages
    # < 2 rows x 3 (a window of 12 over pages of 8 spans 3 in decode)
    kw = dict(pool_pages=6) if group == "full" else dict(window_pool_pages=5)
    eng = engine(slots=2, max_len=32, page_size=8, **kw)
    seen = watch_the_pools(eng) if group == "full" else None
    for prompt, gen in zip(sent, serve(eng, params, sent, new=10)):
        assert len(gen) == 10 and is_greedy_reference(params, prompt, gen)
    assert eng.counters["preemptions"] >= 1 and eng.counters["failed"] == 0
    assert eng.pool.pages_in_use() == eng.wpool.pages_in_use() == 0
    assert seen is None or max(seen) > 0


def test_a_repeated_prompt_takes_no_prefix_hit_and_serves_the_same_tokens(
        params, warm):
    """A cached chunk would have to keep the window group's positions before
    it: nothing is matched, published or pinned, a session's second turn
    included."""
    prompt = np.random.default_rng(11).integers(0, 96, 20).tolist()
    a, b = serve(warm, params, [prompt])[0], serve(warm, params, [prompt])[0]
    assert a == b and is_greedy_reference(params, prompt, a)
    sid = warm.open_session()
    rid = warm.submit(np.asarray(prompt, np.int32), 6, session=sid)
    warm.run(params)
    turn1 = [int(t) for t in warm.pop_result(rid).tokens]
    rid = warm.submit(np.asarray(turn1 + [5, 6], np.int32), 4, session=sid)
    warm.run(params)
    assert is_greedy_reference(
        params, turn1 + [5, 6],
        [int(t) for t in warm.pop_result(rid).tokens][len(turn1) + 2:])
    warm.close_session(sid)
    st = warm.stats()
    assert st["prefix_queries"] == st["prefix_hits"] == 0
    assert st["session_pinned_pages"] == 0
    assert warm.pool.pages_in_use() == warm.wpool.pages_in_use() == 0


def test_snapshot_and_restore_rebuild_the_window_pages_from_the_tokens(
        params, warm):
    """``snapshot()`` holds tokens, no device state: a rebuilt engine
    re-prefills every row from position 0, which rebuilds both groups'
    pages, like a resume after preemption."""
    rng = np.random.default_rng(13)
    sent = [rng.integers(0, 96, n).tolist() for n in (25, 5)]
    rids = [warm.submit(np.asarray(p, np.int32), 12) for p in sent]
    for _ in range(6):
        warm.step(params)  # both rows mid-decode, the first past the window
    snap = warm.snapshot()
    assert all(len(q.gen) >= 1 for q in snap.pending)
    for rid in rids:
        warm.abort(rid)
        warm.pop_result(rid)
    assert warm.wpool.pages_in_use() == 0
    rebuilt = engine()
    rebuilt.restore(snap)
    out = rebuilt.run(params)
    for rid, p in zip(rids, sent):
        gen = [int(t) for t in out[rid].tokens][len(p):]
        assert len(gen) == 12 and is_greedy_reference(params, p, gen)


@pytest.mark.parametrize("kw,sentence", [
    (dict(mesh_cfg=MeshConfig(tensor=2)), "no mesh placement"),
    (dict(kv_quant="int8"), "no scale pools for the window group"),
    (dict(weight_quant="int8"), "weight_quant"),
    (dict(adapters=SimpleNamespace(cfg=CFG)), "adapters"),
    (dict(speculative_k=2), "no verify step"),
    (dict(role="prefill"), "the row has two"),
    (dict(role="decode"), "the row has two"),
    ("export_handoff", "the row has two"),
    ("import_handoff", "the row has two"),
    ("dense engine", "two groups of pages"),
    ("dense cache", "a window group of pages"),
    ("prefix", "takes no prefix hit"),
])
def test_what_the_family_cannot_be_served_with_is_refused(kw, sentence):
    if kw == "prefix":  # nothing asks for one: the reason is on record
        assert sentence in decode.serving(CFG).unserved["prefix"]
        return
    with pytest.raises(NotImplementedError, match=sentence):
        if kw == "dense engine":
            BatchedDecodeEngine(CFG, slots=2, max_len=MAX_LEN)
        elif kw == "dense cache":
            decode.init_cache(CFG, 2, MAX_LEN)
        elif isinstance(kw, str):
            getattr(engine(), kw)(0)
        else:
            engine(**kw)


def test_the_window_group_is_sized_and_refused_by_its_own_rule():
    eng = engine()
    assert eng.window_pool_pages == 4 * eng._window_row_bound + 1
    with pytest.raises(ValueError, match="window_pool_pages"):
        engine(window_pool_pages=3)
    with pytest.raises(ValueError, match="no window"):
        PagedBatchedDecodeEngine(
            model_config("tiny"), slots=2, max_len=32, page_size=4,
            window_pool_pages=9)
