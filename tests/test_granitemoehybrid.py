"""The granitemoehybrid family (models/granitemoehybrid.py, ops/ssm.py)
against its plain reference, perfbench/reference/granitemoehybrid.py, at a
tiny float32 size on the CPU that keeps the published PATTERN: two periods
of [m m m m m a m m m m], heads and state cut, blocks of 8 positions.

Logits are compared, not tokens, wherever a test reaches them; tolerances
are absolute on logits of std 0.14 and each says what it allows.
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench.reference import granitemoehybrid as ref  # noqa: E402
from pytorch_distributed_tpu.config import (  # noqa: E402
    MeshConfig,
    ModelConfig,
    model_config,
)
from pytorch_distributed_tpu.models import decode  # noqa: E402
from pytorch_distributed_tpu.models import granitemoehybrid as gmh  # noqa: E402
from pytorch_distributed_tpu.ops import (  # noqa: E402
    paged_kernel,
    ssm,
    ssm_kernel,
)
from pytorch_distributed_tpu.serving.engine import (  # noqa: E402
    BatchedDecodeEngine,
    PagedBatchedDecodeEngine,
)

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
MODEL = dict(
    hidden_size=32, vocab_size=96, num_hidden_layers=20,
    layer_types=PERIOD * 2, num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=48, mamba_n_heads=8, mamba_d_head=8,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_expand=2,
    mamba_chunk_size=8, rms_norm_eps=1e-5, embedding_multiplier=12,
    attention_multiplier=0.25, residual_multiplier=0.22, logits_scaling=2,
    initializer_range=0.2)
PAGE, MAX_LEN, CHUNK = 4, 64, 8
# float32 throughout: what differs between the program and the reference is
# the ORDER of sums (a block's products against one position after the
# other; chunks against a whole sequence), a few float32 roundings deep
ATOL = 2e-5


def program_config(model=MODEL, **kw) -> ModelConfig:
    return ModelConfig(**dict(dict(
        family="granitemoehybrid", vocab_size=model["vocab_size"],
        n_ctx=MAX_LEN, n_embd=model["hidden_size"],
        n_layer=model["num_hidden_layers"],
        n_head=model["num_attention_heads"],
        n_kv_head=model["num_key_value_heads"],
        layer_types=tuple(model["layer_types"]),
        mamba_n_heads=model["mamba_n_heads"],
        mamba_d_head=model["mamba_d_head"],
        mamba_d_state=model["mamba_d_state"],
        mamba_n_groups=model["mamba_n_groups"],
        mamba_d_conv=model["mamba_d_conv"],
        mamba_expand=model["mamba_expand"],
        mamba_chunk_size=model["mamba_chunk_size"],
        shared_intermediate_size=model["shared_intermediate_size"],
        embedding_multiplier=float(model["embedding_multiplier"]),
        attention_multiplier=model["attention_multiplier"],
        residual_multiplier=model["residual_multiplier"],
        logits_scaling=float(model["logits_scaling"]), dtype="float32",
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


@pytest.fixture(scope="module")
def warm_kernels(params):
    """The same engine with its decode step built on the two kernels, in
    the interpreter: the attention layers' pages through ops/paged_kernel.py
    in blocks of two pages, the Mamba layers' state through
    ops/ssm_kernel.py."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(paged_kernel, "KEY_BLOCK", 2 * PAGE)
        eng = engine(paged_attention="kernel_interpret")
        eng.warmup(params)
    return eng


STATE_STEP_IMPL = {"warm": "xla", "warm_kernels": "kernel_interpret"}


@jax.jit
def FWD(params, ids, cache, pos, tables, live, rows):
    """``gmh.forward`` compiled once a shape; every operand given."""
    return gmh.forward(params, ids, CFG, cache, pos, tables, live=live,
                       state_rows=rows)


def forward(params, ids, cache, pos, tables, live=None, rows=None):
    b, t = ids.shape
    return FWD(
        params, ids, cache, jnp.asarray(pos, jnp.int32), tables,
        jnp.ones((b, t), bool) if live is None else live,
        jnp.arange(b, dtype=jnp.int32) if rows is None else rows)


def tables_for(rows: int):
    """Row b owns pages 1 + b*n .. (page 0 is the scratch page)."""
    n = MAX_LEN // PAGE
    return 1 + jnp.arange(rows * n, dtype=jnp.int32).reshape(rows, n)


def prompts(rows: int, length: int, seed=1):
    return jax.random.randint(
        jax.random.key(seed), (rows, length), 0, MODEL["vocab_size"])


def row_of(cache, leaf: str, row: int):
    """One row's entries of a state leaf, every layer: ``ssm`` keeps its
    rows on axis 1, ``conv`` on axis 2."""
    a = np.asarray(cache[leaf], np.float32)
    return a[:, row] if leaf == "ssm" else a[:, :, row]


def fresh_cache(rows: int):
    return decode.init_paged_cache(
        CFG, rows * (MAX_LEN // PAGE) + 1, PAGE, rows=rows)


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
    """Whether ``gen`` is the reference's greedy continuation of ``prompt``:
    each token the argmax of the reference's logits over what precedes it
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
    """Three chunks of 8, the last holding 5 tokens and 3 of padding, then
    six single tokens, through the state rows [2, 0] of a three-row cache:
    every position's logits are the reference's full forward's."""
    ids = prompts(2, 27)
    want = ref.logits(params, ids, MODEL)
    assert float(want.std()) > 0.1  # ATOL is absolute: 1e-4 of the logits
    cache = fresh_cache(3)
    assert set(cache) == {"k", "v", "ssm", "conv"}
    assert cache["k"].shape == (2, 49, PAGE, 2 * 8)  # attention layers only
    assert cache["ssm"].shape == (18, 4, 8, 8, 16)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["conv"].shape == (18, 3, 4, 64 + 2 * 2 * 16)
    tables, rows, got = tables_for(2), jnp.asarray([2, 0]), []
    for start in (0, 8, 16):
        chunk, live = chunk_of(ids[:, :21], start)
        lg, cache, counts = forward(
            params, chunk, cache, jnp.full((2,), start), tables, live, rows)
        got.append(lg[:, :min(8, 21 - start)])
        assert counts.tolist() == [2 * min(8, 21 - start), 16]
    for pos in range(21, 27):
        lg, cache, counts = forward(
            params, ids[:, pos:pos + 1], cache, jnp.full((2,), pos), tables,
            rows=rows)
        got.append(lg)
        assert counts.tolist() == [2, 2]
    np.testing.assert_allclose(
        jnp.concatenate(got, axis=1), want, atol=ATOL, rtol=0)
    # row 1 of the cache was nobody's: still zero
    assert not np.asarray(cache["ssm"][:, 1]).any()
    # decode.forward routes the family, and counts on request
    lg, _, counts = decode.forward(
        params, ids[:, :8], CFG, fresh_cache(2), jnp.zeros((2,), jnp.int32),
        block_tables=tables, return_aux=True)
    np.testing.assert_allclose(lg, want[:, :8], atol=ATOL, rtol=0)
    assert counts.tolist() == [16, 16]


@pytest.mark.parametrize("length", [5, 8, 19, 24])
def test_chunked_form_equals_the_sequential_recurrence(length):
    """``ssd_chunked`` (blocks of 8) from a NON-ZERO carried-in state equals
    the recurrence one position after the other in numpy, for lengths that
    do and do not fill the block; 1e-5: float32 sums in another order, on
    outputs of order 3."""
    b, h, p, g, n = 2, 4, 3, 2, 5
    ks = jax.random.split(jax.random.key(length), 6)
    x = jax.random.normal(ks[0], (b, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, length, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, length, g, n))
    cm = jax.random.normal(ks[4], (b, length, g, n))
    s0 = jax.random.normal(ks[5], (b, h, p, n))
    y, s = ssm.ssd_chunked(x, dt, a, bm, cm, s0, 8)
    state = np.asarray(s0, np.float64)
    want = np.zeros((b, length, h, p))
    xn, dtn, an = (np.asarray(v, np.float64) for v in (x, dt, a))
    bn, cn = (np.repeat(np.asarray(v, np.float64), h // g, axis=2)
              for v in (bm, cm))
    for t in range(length):
        state = (np.exp(dtn[:, t] * an)[..., None, None] * state
                 + (dtn[:, t, :, None] * xn[:, t])[..., None]
                 * bn[:, t, :, None, :])
        want[:, t] = (state * cn[:, t, :, None, :]).sum(-1)
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(s, state, atol=1e-5, rtol=0)
    # and the one-token update is the same line
    y1, s1 = ssm.ssm_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], s0)
    np.testing.assert_allclose(y1, want[:, 0], atol=1e-5, rtol=0)


def test_the_convolutions_tail_is_the_last_real_positions():
    """Four taps over [tail | x]; the new tail ends at each row's last REAL
    position: all of x's (5 real of 5), reaching into the old tail (1 real),
    the old tail itself bit for bit (none)."""
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (3, 5, 6))
    tail = jax.random.normal(ks[1], (3, 3, 6))
    w, b = jax.random.normal(ks[2], (4, 6)), jax.random.normal(ks[3], (6,))
    y, new = ssm.causal_conv(x, tail, w, b, jnp.asarray([5, 1, 0]))
    cat = np.concatenate([tail, x], axis=1)
    want = b + sum(cat[:, j:j + 5] * w[j] for j in range(4))
    np.testing.assert_allclose(y, want, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(new[0], x[0, 2:])
    np.testing.assert_array_equal(new[1], cat[1, 1:4])
    np.testing.assert_array_equal(new[2], tail[2])


def test_padded_tail_leaves_state_and_tail_as_the_last_real_token_left_them(
        params):
    """A chunk of 8 holding 5 tokens, and the same 5 tokens as a call of 5:
    the same state, the same convolution tail (the last 3 REAL positions),
    the same pages at the real positions. A row with no token: untouched,
    bit for bit. 1e-5: the block's float32 products run over 8 positions or
    5, on a state of order 1."""
    ids = prompts(2, 13, seed=5)
    tables = tables_for(2)
    chunk, live = chunk_of(ids[:, :8], 0)
    _, base, _ = forward(params, chunk, fresh_cache(2), [0, 0], tables, live)
    padded, live = chunk_of(ids, 8)
    _, got, _ = forward(params, padded, base, [8, 8], tables, live)
    _, want, _ = forward(params, ids[:, 8:], base, [8, 8], tables)
    for leaf in ("ssm", "conv"):
        assert np.abs(np.asarray(want[leaf], np.float32)).max() > 0.1
        np.testing.assert_allclose(
            np.asarray(got[leaf], np.float32),
            np.asarray(want[leaf], np.float32), atol=1e-5, rtol=0)
    for leaf in ("k", "v"):  # a row's first 4 pages hold positions 0..15
        real = [np.asarray(c[leaf][:, tables[:, :4]]).reshape(2, 2, 16, -1)[
            :, :, :13] for c in (got, want)]
        np.testing.assert_allclose(*real, atol=1e-5, rtol=0)
    # row 1 dead in a call whose row 0 lives: row 1 keeps every bit
    _, got, _ = forward(
        params, padded, base, [8, 8], tables, live.at[1].set(False))
    for leaf in ("ssm", "conv"):
        np.testing.assert_array_equal(row_of(got, leaf, 1), row_of(base, leaf, 1))
        assert (row_of(got, leaf, 0) != row_of(base, leaf, 0)).any()


def test_a_reused_state_row_gives_the_logits_of_a_fresh_cache(params):
    """A call that starts at position 0 starts from zero state and tail
    inside the program, whatever the rows held: the second requests' logits
    are those they have in a cache nobody used, bit for bit."""
    first, second = prompts(2, 8, seed=6), prompts(2, 8, seed=7)
    tables = tables_for(2)
    _, used, _ = forward(params, first, fresh_cache(2), [0, 0], tables)
    assert np.asarray(used["ssm"][:, :2]).any()
    got, _, _ = forward(params, second, used, [0, 0], tables)
    want, _, _ = forward(params, second, fresh_cache(2), [0, 0], tables)
    np.testing.assert_array_equal(got, want)


def test_a_row_alone_and_among_seven_others_gives_the_same_logits(params):
    """Nothing couples the rows of a call. ATOL: the products' row count
    differs, so XLA orders their float32 sums otherwise, 20 layers deep."""
    ids = prompts(8, 16, seed=2)
    cache, tables = fresh_cache(8), tables_for(8)

    def run(rows):
        n, sel = len(rows), jnp.asarray(rows)
        lg, c, _ = forward(
            params, ids[sel, :15], cache, [0] * n, tables[sel], rows=sel)
        lg1, _, _ = forward(
            params, ids[sel, 15:], c, [15] * n, tables[sel], rows=sel)
        return lg[0], lg1[0]

    alone, among = run([3]), run([3, 0, 1, 2, 4, 5, 6, 7])
    for a, b in zip(alone, among):
        np.testing.assert_allclose(a, b, atol=ATOL, rtol=0)


def test_rows_in_blocks_change_nothing(params, monkeypatch):
    """A prefill group wider than TOKEN_BLOCK runs each layer over groups of
    rows in turn, the cache carried from group to group: same logits, same
    cache. ATOL as above."""
    ids = prompts(4, 8, seed=4)
    rows = jnp.asarray([3, 1, 0, 2])
    args = (params, ids, CFG, fresh_cache(4), jnp.zeros((4,), jnp.int32),
            tables_for(4))
    run = jax.jit(lambda *a: gmh.forward(*a[:2], CFG, *a[2:], state_rows=rows))
    args = args[:2] + args[3:]
    whole = run(*args)
    monkeypatch.setattr(gmh, "TOKEN_BLOCK", 16)  # two rows at a time
    split = jax.jit(lambda *a: gmh.forward(
        *a[:2], CFG, *a[2:], state_rows=rows))(*args)
    np.testing.assert_allclose(split[0], whole[0], atol=ATOL, rtol=0)
    for leaf in whole[1]:
        np.testing.assert_allclose(
            np.asarray(split[1][leaf], np.float32),
            np.asarray(whole[1][leaf], np.float32), atol=ATOL, rtol=0)


def test_the_published_preset_and_what_it_declares():
    cfg = model_config("granite-4.0-h-micro", dtype="bfloat16")
    assert gmh.layer_period(cfg) == tuple(PERIOD)
    assert (gmh.n_layers_of(cfg, "mamba"), gmh.n_layers_of(cfg, "attention")
            ) == (36, 4)
    assert gmh.conv_width(cfg) == 4352 == 34 * 128
    assert decode.kv_bytes_per_position(cfg) == 8192
    asks = decode.serving(cfg)
    assert asks.state_bytes_per_row == 36 * (
        64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert not asks.dense_cache
    assert asks.aux_counts == ("ssm_tokens_live", "ssm_tokens_computed")
    assert asks.counters == (
        "state_rows_advanced", "kv_positions_read", "kv_positions_window")
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, 2049, 64, rows=32))
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "k": ((4, 2049, 64, 512), "bfloat16"),
        "v": ((4, 2049, 64, 512), "bfloat16"),
        "ssm": ((36, 33, 64, 64, 128), "float32"),
        "conv": ((36, 3, 33, 4352), "bfloat16")}
    # the dense families declare nothing of the kind
    tiny = model_config("tiny")
    assert decode.serving(tiny) == decode.Serving()
    assert decode.Serving().state_bytes_per_row == 0
    with pytest.raises(ValueError, match="granitemoehybrid"):
        program_config(layer_types=("mamba",) * 3)


def test_the_programs_init_draws_the_references_tree():
    """``gmh.init`` (scripts/serve.py's random weights) and the benchmark's
    ``init_params`` are two copies of one initialisation: held equal here,
    leaf for leaf, at the draws a configuration file gets (no
    ``initializer_range``)."""
    model = {k: v for k, v in MODEL.items() if k != "initializer_range"}
    want = ref.init_params(7, model, "float32")
    got = gmh.init(jax.random.fold_in(jax.random.key(7), 0), CFG)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for path, leaf in jax.tree_util.tree_leaves_with_path(want):
        mine = got
        for k in path:
            mine = mine[k.key]
        # (to a float32 rounding: one of the two is one jitted call)
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(leaf), rtol=1e-6, atol=0,
            err_msg=str(path))
    dt = np.log1p(np.exp(np.asarray(want["mamba"]["mixer"]["dt_bias"])))
    assert 1e-4 <= dt.min() and dt.max() <= 1e-2 * (1 + 1e-5)


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


def test_paged_engine_serves_the_reference_greedy_tokens(params, warm):
    """Through PagedBatchedDecodeEngine (admission, block pool, chunked
    prefill with ragged final chunks, the sampler): more requests than
    rows, so every slot is reused; every reply the reference's greedy
    continuation; no compile after the warm-up; the counters add up."""
    compiled = warm.compile_count()
    before = dict(warm.stats()["counters"])
    rng = np.random.default_rng(0)
    sent = [rng.integers(0, MODEL["vocab_size"], n).tolist()
            for n in (5, 19, 8, 30, 11, 3, 17)]
    got = serve(warm, params, sent)
    assert warm.compile_count() == compiled
    for prompt, gen in zip(sent, got):
        assert len(gen) == 6 and is_greedy_reference(params, prompt, gen)
    st = warm.stats()
    c = {k: v - before.get(k, 0) for k, v in st["counters"].items()}
    assert c["ssm_tokens_live.prefill"] == sum(map(len, sent))
    assert c["ssm_tokens_live.prefill"] < c["ssm_tokens_computed.prefill"]
    assert c["ssm_tokens_computed.prefill"] % CHUNK == 0
    # the first token is the prefill's; each later one a decode lane's
    assert c["ssm_tokens_live.decode_step"] == c["state_rows_advanced"] == 7 * 5
    assert c["ssm_tokens_computed.decode_step"] % 4 == 0
    # a decode dispatch's window is every row's whole table, whatever is
    # in it; what the rows reach is a part of it
    assert 7 * 5 < c["kv_positions_read"] < c["kv_positions_window"]
    assert c["kv_positions_window"] % (4 * MAX_LEN) == 0
    assert st["prefix_queries"] == 0 and st["prefix_hits"] == 0
    assert st["state_bytes_per_row"] == 18 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert st["kv_bytes_per_position"] == 2 * 2 * 2 * 8 * 4
    assert st["paged_decode_impl"] == "gather"


@pytest.mark.parametrize("head_block", [4, 1])
def test_the_kernel_engine_serves_the_gather_engines_tokens(
        head_block, params, warm, monkeypatch):
    """``paged_attention="kernel_interpret"``: the decode step reads the
    attention layers' pages through ops/paged_kernel.py (blocks of two
    pages, so the deeper rows take several), scaled by
    ``attention_multiplier``, and advances the Mamba layers' state through
    ops/ssm_kernel.py (a group's four heads as one block, and a head a
    block): the gather engine's tokens, the reference's greedy
    continuations, more requests than rows, so every state row is begun
    anew inside the kernel. The same engine over a configuration whose
    multiplier is D^-1/2 (what the kernel scales by when nobody says) does
    not serve them: the scale reaches the kernel."""
    monkeypatch.setattr(paged_kernel, "KEY_BLOCK", 2 * PAGE)
    monkeypatch.setattr(ssm_kernel, "HEAD_BLOCK", head_block)
    rng = np.random.default_rng(0)
    sent = [rng.integers(0, MODEL["vocab_size"], n).tolist()
            for n in (5, 19, 8, 30, 11, 3, 17)]
    eng = engine(paged_attention="kernel_interpret")
    st = eng.stats()
    assert st["paged_decode_impl"] == st["state_step_impl"] == (
        "kernel_interpret")
    assert warm.stats()["state_step_impl"] == "xla"
    got = serve(eng, params, sent)
    assert got == serve(warm, params, sent)
    assert all(is_greedy_reference(params, p, g) for p, g in zip(sent, got))
    c = eng.stats()["counters"]
    assert 0 < c["kv_positions_read"] < c["kv_positions_window"]
    assert c["state_rows_advanced"] > 0
    if head_block != 4:
        return
    wrong = PagedBatchedDecodeEngine(
        program_config(attention_multiplier=CFG.head_dim ** -0.5), slots=4,
        max_len=MAX_LEN, page_size=PAGE, prefill_chunk=CHUNK,
        paged_attention="kernel_interpret")
    assert not all(is_greedy_reference(params, p, g)
                   for p, g in zip(sent, serve(wrong, params, sent)))


def test_one_decode_step_through_the_kernel_gives_the_gathers_logits(
        params, monkeypatch):
    """``forward(paged_impl="kernel_interpret")`` on rows at depths inside a
    block, on its last position and on the next one's first, beside a free
    lane: the gather path's logits. ATOL: the softmax's sums block by
    block."""
    monkeypatch.setattr(paged_kernel, "KEY_BLOCK", 2 * PAGE)
    depths = [5, 2 * PAGE - 1, 2 * PAGE, 0]
    ids, tables = prompts(4, 2 * PAGE + 1, seed=8), tables_for(4)
    cache = fresh_cache(4)
    for row, depth in enumerate(depths[:3]):  # row 3 stays free
        _, cache, _ = forward(
            params, ids[row:row + 1, :depth], cache, [0],
            tables[row:row + 1], rows=jnp.asarray([row]))
    last = jnp.stack([ids[r, d] for r, d in enumerate(depths)])[:, None]
    live = jnp.asarray([True, True, True, False])[:, None]
    tables = tables.at[3].set(0)
    got, want = (
        gmh.forward(params, last, CFG, cache, jnp.asarray(depths), tables,
                    live=live, paged_impl=impl)[0]
        for impl in ("kernel_interpret", "gather"))
    assert float(want[:3].std()) > 0.1
    np.testing.assert_allclose(got[:3], want[:3], atol=ATOL, rtol=0)


@pytest.mark.parametrize("preset,dense_cache", [
    ("gpt2-large", True), ("llama3-1b", True), ("kimi-k2.5-ep32", False),
    ("granite-4.0-h-micro", False)])
def test_left_unset_every_family_builds_the_gather_off_the_chip(
        preset, dense_cache):
    """``paged_attention`` unset is "auto" (the kernel on a TPU, the gather
    here) for a family with no dense cache and "gather" for the others;
    ``stats()`` names what the decode program was built with, its state
    step too. (An engine allocates and compiles nothing until it is
    warmed.)"""
    cfg = model_config(preset, dtype="bfloat16")
    assert decode.serving(cfg).dense_cache == dense_cache
    for asked, built in ((None, "gather"), ("auto", "gather"),
                         ("kernel", "kernel")):
        eng = PagedBatchedDecodeEngine(
            cfg, slots=2, max_len=128, page_size=64, paged_attention=asked)
        assert eng.stats()["paged_decode_impl"] == built
        # a family with row state advances it through the state kernel
        # wherever the pages' kernel runs, and in plain XLA elsewhere
        assert eng.stats()["state_step_impl"] == (
            {"gather": "xla", "kernel": "kernel"}[built]
            if decode.serving(cfg).state_bytes_per_row else None)


def test_a_padded_prefill_group_leaves_the_real_rows_pages_alone(
        params, warm):
    """Three rows admitted at once prefill as a group of FOUR: the padding
    advances the scratch row and writes the scratch page. (It repeats row
    0's tokens, and in the dense families so its K and V; here its hidden
    states differ, no state being carried in for it, and written through
    row 0's table they would overwrite row 0's pages.)"""
    rng = np.random.default_rng(21)
    sent = [rng.integers(0, MODEL["vocab_size"], n).tolist()
            for n in (20, 11, 14)]
    for prompt, gen in zip(sent, serve(warm, params, sent)):
        assert len(gen) == 6 and is_greedy_reference(params, prompt, gen)


def test_a_slot_reused_by_a_second_request_serves_a_fresh_engines_tokens(
        params, warm):
    """One request after another through the SAME slots: each reply is what
    an engine nobody used serves (the state is zeroed inside the prefill
    program; no dispatch clears it)."""
    rng = np.random.default_rng(3)
    sent = [rng.integers(0, MODEL["vocab_size"], n).tolist()
            for n in (13, 9, 21, 6)]
    used = [serve(warm, params, [p])[0] for p in sent]
    for prompt, gen in zip(sent, used):
        assert len(gen) == 6 and is_greedy_reference(params, prompt, gen)


@pytest.mark.parametrize("built", ["warm", "warm_kernels"])
def test_neighbours_decode_steps_leave_other_rows_bit_unchanged(
        built, params, request, monkeypatch):
    """While one row decodes, a row in the middle of its prefill (between
    two of its chunks), a free row and the scratch row keep state and tail
    bit for bit across every decode dispatch; the decoding row's change.
    Through the plain state step (two selects) and through the state
    kernel (a dead lane is neither read nor written)."""
    eng = request.getfixturevalue(built)
    assert eng.stats()["state_step_impl"] == STATE_STEP_IMPL[built]
    real, seen = eng._dispatch, []

    def spy(kind, *args):
        rows = {i: (s is not None and s.ready)
                for i, s in enumerate(eng._slots)}
        before = {k: np.asarray(eng._cache[k]) for k in ("ssm", "conv")}
        out = real(kind, *args)
        if kind == "decode_step":
            after = {k: np.asarray(eng._cache[k]) for k in ("ssm", "conv")}
            midway = [i for i, s in enumerate(eng._slots)
                      if s is not None and not s.ready and s.pos > 0]
            seen.append((rows, midway))
            for row in (*range(eng.slots), eng.slots):  # the scratch row too
                for k in before:
                    same = np.array_equal(
                        row_of(before, k, row), row_of(after, k, row))
                    assert same != rows.get(row, False), (k, row, rows)
        return out

    monkeypatch.setattr(eng, "_dispatch", spy)
    rng = np.random.default_rng(5)
    short = eng.submit(rng.integers(0, 96, 4).astype(np.int32), 8)
    eng.step(params)  # the short row is ready and decodes from now on
    long = eng.submit(rng.integers(0, 96, 30).astype(np.int32), 3)
    eng.run(params)
    assert eng.pop_result(short).state == eng.pop_result(long).state == "DONE"
    # the long prompt took four ticks to prefill, the short row decoding
    assert sum(1 for rows, midway in seen if midway) >= 3
    assert all(not rows[3] for rows, _ in seen)  # slot 3 stayed free


def test_preempt_and_resume_serves_the_uninterrupted_greedy_tokens(params):
    """Pool exhaustion preempts the youngest row; its state goes with its
    pages, and the resume re-prefills prompt + generated from position 0,
    which rebuilds it: the uninterrupted tokens."""
    rng = np.random.default_rng(9)
    sent = [rng.integers(0, 96, n).tolist() for n in (14, 15)]
    # 5 usable pages of 8 < 2 rows x 4 pages: decode growth must preempt
    eng = engine(slots=2, max_len=32, page_size=8, pool_pages=6)
    for prompt, gen in zip(sent, serve(eng, params, sent, new=10)):
        assert len(gen) == 10 and is_greedy_reference(params, prompt, gen)
    assert eng.counters["preemptions"] >= 1 and eng.counters["failed"] == 0


def test_a_repeated_prompt_takes_no_prefix_hit_and_serves_the_same_tokens(
        params, warm):
    """A cached prefix's pages come without the state at its end: nothing
    is matched, published or pinned, a session's second turn included."""
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
    assert warm.pool.pages_in_use() == 0  # nothing retained after the rows


def test_snapshot_and_restore_rebuild_the_state_from_the_tokens(
        params, warm):
    """``snapshot()`` holds tokens, no device state: a rebuilt engine
    re-prefills every row from position 0, which rebuilds its state, like a
    resume after preemption. (So the router's failover, which is
    ``snapshot`` and ``adopt``, serves this family too.)"""
    rng = np.random.default_rng(13)
    sent = [rng.integers(0, 96, n).tolist() for n in (9, 5)]
    rids = [warm.submit(np.asarray(p, np.int32), 8) for p in sent]
    warm.step(params)
    warm.step(params)  # both rows mid-decode
    snap = warm.snapshot()
    assert all(len(q.gen) >= 1 for q in snap.pending)
    for rid in rids:
        warm.abort(rid)
        warm.pop_result(rid)
    rebuilt = engine()
    rebuilt.restore(snap)
    out = rebuilt.run(params)
    for rid, p in zip(rids, sent):
        gen = [int(t) for t in out[rid].tokens][len(p):]
        assert len(gen) == 8 and is_greedy_reference(params, p, gen)


@pytest.mark.parametrize("kw,sentence", [
    (dict(mesh_cfg=MeshConfig(tensor=2)), "no mesh placement"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(weight_quant="int8"), "weight_quant"),
    (dict(adapters=SimpleNamespace(cfg=CFG)), "adapters"),
    (dict(speculative_k=2), "roll a recurrent state back"),
    (dict(role="prefill"), "ships pages"),
    (dict(role="decode"), "ships pages"),
    ("export_handoff", "ships pages"),
    ("import_handoff", "ships pages"),
    ("dense engine", "per-row state"),
    ("dense cache", "per-row recurrent state"),
])
def test_what_the_family_cannot_be_served_with_is_refused(kw, sentence):
    with pytest.raises(NotImplementedError, match=sentence):
        if kw == "dense engine":
            BatchedDecodeEngine(CFG, slots=2, max_len=MAX_LEN)
        elif kw == "dense cache":
            decode.init_cache(CFG, 2, MAX_LEN)
        elif isinstance(kw, str):
            getattr(engine(), kw)(0)
        else:
            engine(**kw)
