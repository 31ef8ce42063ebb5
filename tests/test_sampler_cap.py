"""The sampler's capped path (``models/decode.py``: ``SAMPLE_CAP``).

A sampled program draws from a row's top ``SAMPLE_CAP`` (128) tokens, a
partial top-k, and sorts the whole vocabulary only when some live sampled
row asks for more than that: ONE ``lax.cond`` on the rows' own k inside the
program each dispatch already has. Pinned here, on the CPU at tiny sizes:

1. for top-k 1, 50 and 128, with and without top-p, the capped path keeps
   the full sort's support with its renormalised probabilities;
2. greedy rows read the argmax whichever way the batch went;
3. a batch with one live sampled row past the cap (top-k None, 0 or 200)
   takes the full sort: that row draws what the full-vocabulary sampler
   drew for the same key, and a row within the cap draws what it draws
   alone (the serial ``sample_token``);
4. a padded prefill group's unwritten lanes (the staged defaults) do not
   ask for the full sort;
5. over many keys the capped draws follow the full distribution;
6. the paged engine's ``sample_full_sort.<kind>`` counts the dispatches
   that held the one row past the cap, and nothing else;
7. warmup builds the programs it built before the cap: per program kind
   the same count, for a dense family, a family with row state and both
   handoff roles; and nothing compiles after it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.models import decode, get_model
from pytorch_distributed_tpu.serving.engine import PagedBatchedDecodeEngine
from pytorch_distributed_tpu.serving.staged import paged_layouts

V = 5000  # past decode._CAP_SEGMENTS: the partial top-k runs by segments
_DROP = dict(attn_pdrop=0.0, resid_pdrop=0.0, embd_pdrop=0.0)


def _logits(b: int, seed: int = 0) -> jax.Array:
    return jax.random.normal(jax.random.key(seed), (b, V)) * 2.0


def _full_sort_reference(x, key, top_k, top_p):
    """The sampler before the cap: the whole vocabulary sorted, the rank
    and nucleus masks, one categorical over all V columns."""
    vals, idx = jax.lax.top_k(x, x.shape[-1])
    rank = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    in_k = rank < top_k
    probs = jax.nn.softmax(jnp.where(in_k, vals, -jnp.inf), axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    vals = jnp.where(in_k & (cum_before < top_p), vals, -jnp.inf)
    choice = jax.random.categorical(key, vals, axis=-1)
    return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]


def _distribution(x, top, top_k, top_p):
    """[B, V] probabilities of the draw that reads ``top(x)``."""
    vals, idx = top(x)
    probs = jax.nn.softmax(decode._kept(vals, top_k, top_p), axis=-1)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros(x.shape).at[rows, idx].set(probs)


def _full_sort(x):
    return jax.lax.top_k(x, x.shape[-1])


@pytest.mark.parametrize("v", [5000, 50257, 100352])
def test_the_partial_top_k_is_the_full_sorts_first_columns(v):
    """By segments (the vocabulary padded to whole segments where it does
    not divide): the same values and token ids as the sort's first
    ``SAMPLE_CAP`` columns, rows of every scale."""
    x = jax.random.normal(jax.random.key(v), (3, v)) * jnp.array(
        [[0.1], [2.0], [30.0]])
    vals, idx = jax.jit(decode._top_cap)(x)
    want_vals, want_idx = jax.lax.top_k(x, decode.SAMPLE_CAP)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))


@pytest.mark.parametrize("top_p", [None, 0.9, 0.5])
def test_capped_support_and_probabilities_equal_the_full_sorts(top_p):
    k = jnp.array([1, 50, 128, 50], jnp.int32)[:, None]
    p = jnp.float32(2.0 if top_p is None else top_p)
    x = _logits(4) / jnp.array([1.0, 0.8, 1.3, 0.5])[:, None]
    capped = np.asarray(_distribution(x, decode._top_cap, k, p))
    full = np.asarray(_distribution(x, _full_sort, k, p))
    np.testing.assert_array_equal(capped > 0, full > 0)
    np.testing.assert_allclose(capped, full, rtol=0, atol=1e-6)
    # the support is what was asked for: k tokens, fewer under a nucleus
    n = (full > 0).sum(axis=1)
    if top_p is None:
        assert n.tolist() == [1, 50, 128, 50]
    else:
        assert (n <= np.array([1, 50, 128, 50])).all() and n[0] == 1


def _rows(x, greedy, k, keys, t=0.8, p=2.0):
    b = x.shape[0]
    return decode.sample_token_rows(
        x, jnp.asarray(greedy), jnp.full((b,), t, jnp.float32), keys,
        jnp.asarray(k, jnp.int32), jnp.full((b,), p, jnp.float32))


def test_greedy_rows_read_the_argmax_on_both_paths():
    x = _logits(4, seed=1)
    keys = jax.random.split(jax.random.key(3), 4)
    greedy = np.array([True, False, True, True])
    want = np.asarray(jnp.argmax(x, axis=-1))
    for k_sampled in (50, 200):  # the capped path, then the full sort
        k = np.array([V, k_sampled, V, 7])
        assert bool(decode.needs_full_sort(greedy, k, V)) == (k_sampled > 128)
        got = np.asarray(jax.jit(_rows)(x, greedy, k, keys))
        np.testing.assert_array_equal(got[greedy], want[greedy])


@pytest.mark.parametrize("top_k", [None, 0, 200])
def test_a_live_row_past_the_cap_takes_the_full_sort(top_k):
    _, k_past, _ = decode.sampling_scalars(0.8, top_k, None, V)
    k = np.array([50, int(k_past), V])
    greedy = np.array([False, False, True])
    assert bool(decode.needs_full_sort(greedy, k, V))
    x = _logits(3, seed=2)
    rows = jax.jit(_rows)
    ref = jax.jit(_full_sort_reference)
    one = jax.jit(lambda l, key, kk: decode.sample_token(
        l, True, jnp.float32(0.8), key, kk, jnp.float32(2.0)))
    for seed in range(8):
        keys = jax.random.split(jax.random.key(seed), 3)
        got = np.asarray(rows(x, greedy, k, keys))
        # the row past the cap: what the full-vocabulary sampler drew
        want = ref(x[1:2] / 0.8, keys[1], k[1], jnp.float32(2.0))
        assert got[1] == int(want[0])
        # the row within the cap: what it draws alone, on the capped path
        assert got[0] == int(one(x[0:1], keys[0], jnp.int32(50))[0])


@pytest.mark.parametrize("kind", ["prefill", "decode_step",
                                  "decode_spec_step"])
def test_unwritten_lanes_do_not_ask_for_the_full_sort(kind):
    layout = paged_layouts(
        chunk=8, spec_width=3, table_width=4, key_words=2, vocab=V,
        scratch_row=4, tenants=False)[kind]
    staged = layout.new(4)  # a group of one row padded to four
    a = layout.views(staged)
    a["greedy"][0], a["t"][0], a["k"][0] = False, 0.8, 50
    split = jax.jit(layout.split)
    check = jax.jit(lambda s: decode.needs_full_sort(
        split(s)["greedy"], split(s)["k"], V))
    assert not bool(check(staged))
    a["k"][0] = V  # the written row itself asks for every token
    assert bool(check(staged))


@pytest.mark.parametrize("k", [50, 128])
def test_capped_draws_follow_the_full_distribution(k):
    """The row beside a greedy one, through the batched sampler on its
    capped branch, over many keys: the full sort's probabilities."""
    x = _logits(1, seed=4) / 2.0
    greedy, ks = np.array([False, True]), np.array([k, V])
    assert not bool(decode.needs_full_sort(greedy, ks, V))
    n = 4000
    draws = np.asarray(jax.jit(jax.vmap(lambda key: _rows(
        jnp.concatenate([x, x]), greedy, ks, jax.random.split(key))[0]))(
            jax.random.split(jax.random.key(11), n)))
    probs = np.asarray(_distribution(
        x / 0.8, _full_sort, k, jnp.float32(2.0)))[0]
    support = np.flatnonzero(probs)
    assert set(draws.tolist()) <= set(support.tolist())
    counts = np.bincount(draws, minlength=V)[support]
    expected = probs[support] * n
    big = expected >= 5  # pool the thin tail into one bin
    obs, exp = counts[big], expected[big]
    if not big.all():
        obs = np.append(obs, counts[~big].sum())
        exp = np.append(exp, expected[~big].sum())
    chi2 = float(((obs - exp) ** 2 / exp).sum())
    dof = len(obs) - 1
    assert chi2 < dof + 6 * np.sqrt(2 * dof), (chi2, dof)


def _gpt2(vocab: int = 256) -> ModelConfig:
    return ModelConfig(
        family="gpt2", vocab_size=vocab, n_ctx=64, n_embd=32, n_layer=2,
        n_head=2, dtype="float32", **_DROP)


def _granite() -> ModelConfig:
    period = ("mamba",) * 4 + ("attention",)
    return ModelConfig(
        family="granitemoehybrid", vocab_size=96, n_ctx=64, n_embd=32,
        n_layer=5, n_head=4, n_kv_head=2, layer_types=period,
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2,
        mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8,
        shared_intermediate_size=48, dtype="float32",
        param_dtype="float32", activation_function="silu", **_DROP)


def test_sample_full_sort_counts_the_dispatches_of_the_row_past_the_cap():
    """Top-k-50 rows (and a greedy one) beside ONE row with top-k 200: the
    counter reads the past-the-cap row's prefill chunks and decode steps,
    and warmup plus the mix compiles nothing new."""
    cfg = _gpt2()
    params = get_model(cfg).init(jax.random.key(0), cfg)
    eng = PagedBatchedDecodeEngine(
        cfg, slots=3, max_len=32, page_size=8, prefill_chunk=8)
    warm = eng.warmup(params)
    rng = np.random.default_rng(0)
    plen, max_new = 20, 5
    reqs = [dict(prompt=rng.integers(0, 256, n).astype(np.int32),
                 max_new_tokens=4, temperature=0.8, top_k=50,
                 key=jax.random.key(i)) for i, n in enumerate((9, 14, 6))]
    reqs.insert(1, dict(prompt=rng.integers(0, 256, plen).astype(np.int32),
                        max_new_tokens=max_new, temperature=0.8, top_k=200,
                        key=jax.random.key(9)))
    reqs.append(dict(prompt=rng.integers(0, 256, 11).astype(np.int32),
                     max_new_tokens=3))  # greedy, top-k off
    out = eng.run(params, reqs)
    assert all(r.state == "DONE" for r in out.values())
    c = eng.stats()["counters"]
    assert c["sample_full_sort.prefill"] == -(-plen // eng.chunk)
    assert c["sample_full_sort.decode_step"] == max_new - 1
    assert eng.compile_count() == warm


@pytest.mark.parametrize("family,role", [
    ("gpt2", None), ("gpt2", "prefill"), ("gpt2", "decode"),
    ("granitemoehybrid", None)])
def test_warmup_builds_the_programs_it_built_before(family, role):
    """A program per prefill group size and the decode step; a PREFILL
    worker adds the export, a DECODE worker the import, and warms the
    import and the step on both layouts of the pool it may be handed
    (two entries each here, as before the cap)."""
    cfg = _gpt2() if family == "gpt2" else _granite()
    params = get_model(cfg).init(jax.random.key(0), cfg)
    kw = {} if role is None else {"role": role}
    eng = PagedBatchedDecodeEngine(
        cfg, slots=2 if family == "gpt2" else 1, max_len=32, page_size=8,
        prefill_chunk=8, **kw)
    n = eng.warmup(params)
    built = {kind: p._cache_size() for kind, p in eng._programs.items()}
    want = {"prefill": len(eng._groups), "decode_step": 1}
    if role == "prefill":
        want["kv_export"] = 1
    elif role == "decode":
        want.update(decode_step=2, kv_import=2)
    assert built == want
    assert n == sum(want.values())
