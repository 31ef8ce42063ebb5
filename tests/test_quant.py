"""int8 quantization primitives + audit contracts (ops/quant.py).

The serving-level consequences (engine quality budgets, fault-model
re-pins, router capacity scoring) live in tests/test_serving_quant.py;
this battery pins the primitives those tests stand on:

1. KV round-trip edges — all-zero pages (exact-zero reconstruction),
   single-token pages, extreme-magnitude outlier rows (scale
   saturation: error stays <= scale/2 even at f32-extreme inputs), and
   GQA head grouping (one scale per KV head, repeated across the query
   group exactly like the values).
2. Weight quantization — per-out-channel scale shapes (incl. gpt2's
   multi-dim [E, 3, H, D] QKV kernel), qdot's bit-identity to ``x @ w``
   for plain weights, reconstruction error bounds, and
   ``quantize_decode_params`` targeting EXACTLY the projection leaves
   (embeddings/head/norms/biases untouched).
3. TP spec derivation — column-parallel scales shard with their
   channels, row-parallel scales replicate
   (``quantized_param_specs``).
4. The q8 cast budget (analysis/audit.check_q8_casts): the registered
   budget passes on the real engine programs, and an INJECTED f32
   round-trip — dequantize the pool, re-quantize it — fails the audit
   loudly (the acceptance criterion's negative test).
5. The Pallas int8 paged-attention kernel (interpret mode on this rig)
   matches the dequantize-then-gather XLA reference over GQA heads,
   ragged depths, and scratch-page table entries.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.config import MeshConfig, ModelConfig
from pytorch_distributed_tpu.ops.quant import (
    dequantize_kv,
    is_quantized,
    qdot,
    quantize_decode_params,
    quantize_kv,
    quantize_weight,
    quantized_param_specs,
    relative_logit_mse,
    token_match_rate,
)

pytestmark = pytest.mark.full


def _cfg(family="gpt2", **kw):
    extra = {"n_kv_head": 2} if family == "llama" else {}
    extra.update(kw)
    return ModelConfig(
        family=family, vocab_size=97, n_ctx=64, n_embd=64, n_layer=2,
        n_head=4, dtype="float32", attn_pdrop=0.0, resid_pdrop=0.0,
        embd_pdrop=0.0, **extra,
    )


def _params(cfg, seed=0):
    from pytorch_distributed_tpu.models import get_model

    return get_model(cfg).init(jax.random.key(seed), cfg)


# -- KV round-trip edges ----------------------------------------------------


def test_kv_roundtrip_all_zero_rows_reconstruct_exact_zeros():
    """An all-zero K/V row must come back EXACTLY zero: the scale guard
    (amax 0 -> scale 1) keeps 0/0 out of the quantizer, so a fresh page
    or a zero-valued head can never inject noise."""
    x = jnp.zeros((2, 3, 2, 16), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (2, 3, 2)
    np.testing.assert_array_equal(np.asarray(s), 1.0)
    np.testing.assert_array_equal(
        np.asarray(dequantize_kv(q, s, jnp.float32)), 0.0
    )


def test_kv_roundtrip_single_token_page():
    """T=1 (the decode append shape): one token quantizes against only
    its own magnitudes — the per-token scale contract — and the
    round-trip error is bounded by half a quantization step per head."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 1, 2, 32)), jnp.float32)
    q, s = quantize_kv(x)
    back = np.asarray(dequantize_kv(q, s, jnp.float32))
    err = np.abs(back - np.asarray(x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-7
    assert (err <= bound).all()
    # And the max-magnitude element of every head row hits |q| = 127
    # (symmetric full-range usage).
    assert int(np.abs(np.asarray(q)).max()) == 127


def test_kv_roundtrip_extreme_outlier_scale_saturation():
    """Outlier rows at f32-extreme magnitudes: the per-token scale
    absorbs them (no inf/NaN), the outlier survives at full relative
    precision, and small same-row values degrade gracefully (absolute
    error <= scale/2 — the price of a shared row scale, which is why
    the scale is per-token per-head and not per-page)."""
    big = 1e30
    x = np.zeros((1, 1, 1, 8), np.float32)
    x[0, 0, 0, 0] = big
    x[0, 0, 0, 1] = -big
    x[0, 0, 0, 2] = 1.0  # tiny next to the outlier: quantizes to 0
    q, s = quantize_kv(jnp.asarray(x))
    back = np.asarray(dequantize_kv(q, s, jnp.float32))
    assert np.isfinite(back).all() and np.isfinite(np.asarray(s)).all()
    np.testing.assert_allclose(back[0, 0, 0, 0], big, rtol=1e-2)
    np.testing.assert_allclose(back[0, 0, 0, 1], -big, rtol=1e-2)
    assert abs(back[0, 0, 0, 2] - 1.0) <= float(s[0, 0, 0]) / 2 + 1e-6


def test_kv_scales_are_per_kv_head_under_gqa():
    """GQA: scales are stored per KV head ([B, T, Hkv], never per query
    head) and dequantization broadcasts them exactly like the values —
    scaling one KV head's values scales only that head's
    reconstruction."""
    rng = np.random.default_rng(5)
    base = rng.normal(size=(1, 2, 2, 16)).astype(np.float32)
    scaled = base.copy()
    scaled[:, :, 1] *= 1000.0  # blow up KV head 1 only
    q0, s0 = quantize_kv(jnp.asarray(base))
    q1, s1 = quantize_kv(jnp.asarray(scaled))
    assert s0.shape == (1, 2, 2)
    np.testing.assert_allclose(
        np.asarray(s1)[:, :, 0], np.asarray(s0)[:, :, 0], rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(s1)[:, :, 1], np.asarray(s0)[:, :, 1] * 1000.0,
        rtol=1e-5,
    )
    # Head 0's int8 words are untouched by head 1's outliers.
    np.testing.assert_array_equal(
        np.asarray(q1)[:, :, 0], np.asarray(q0)[:, :, 0]
    )


def test_quality_metric_semantics():
    """token_match_rate is PREFIX-based (everything after the first
    divergence is a different context, not a comparable error);
    relative_logit_mse is scale-free."""
    assert token_match_rate([[1, 2, 3]], [[1, 2, 3]]) == 1.0
    # Diverges at index 1: only the 1-token prefix counts, even though
    # index 2 happens to agree again.
    assert token_match_rate([[1, 2, 3]], [[1, 9, 3]]) == pytest.approx(
        1 / 3
    )
    a = np.ones((4, 8)) * 10.0
    assert relative_logit_mse(a, a) == 0.0
    assert relative_logit_mse(a, a * 1.01) == pytest.approx(
        1e-4, rel=1e-2
    )
    assert relative_logit_mse(a * 5, a * 5 * 1.01) == pytest.approx(
        relative_logit_mse(a, a * 1.01), rel=1e-6
    )


# -- weight-only int8 -------------------------------------------------------


def test_qdot_plain_weights_bit_identical():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 5, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(qdot(x, w)), np.asarray(x @ w.astype(x.dtype))
    )


def test_quantize_weight_per_channel_shapes_and_error():
    rng = np.random.default_rng(1)
    # gpt2's merged QKV kernel shape (per layer): [E, 3, H, D].
    w = jnp.asarray(rng.normal(size=(16, 3, 2, 4)), jnp.float32)
    qw = quantize_weight(w)
    assert is_quantized(qw)
    assert qw["q8"].shape == w.shape and qw["q8"].dtype == jnp.int8
    assert qw["scale"].shape == (3, 2, 4)  # one scale per out channel
    x = jnp.asarray(rng.normal(size=(2, 5, 16)), jnp.float32)
    ref = np.asarray(jax.lax.dot_general(
        x, w, (((2,), (0,)), ((), ()))
    ))
    out = np.asarray(qdot(x, qw))
    assert out.shape == ref.shape
    # Per-channel int8: relative matmul error well under a percent.
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_quantize_decode_params_targets_only_projections(family):
    cfg = _cfg(family)
    params = _params(cfg)
    qp = quantize_decode_params(params)
    # Embeddings / head / norm LEAVES untouched (same arrays, not
    # copies — containers are rebuilt by the tree map, leaves are not).
    assert qp["wte"] is params["wte"]
    if family == "gpt2":
        assert qp["blocks"]["ln_1"]["scale"] is (
            params["blocks"]["ln_1"]["scale"]
        )
        attn = qp["blocks"]["attn"]
        assert is_quantized(attn["c_attn"]["kernel"])
        assert attn["c_attn"]["bias"] is (
            params["blocks"]["attn"]["c_attn"]["bias"]
        )
        assert is_quantized(qp["blocks"]["mlp"]["c_proj"]["kernel"])
        # Stacked [L, E, 3, H, D] kernel -> scale [L, 3, H, D] (per
        # layer, per out channel; the contracting E dim reduced away).
        k = params["blocks"]["attn"]["c_attn"]["kernel"]
        assert attn["c_attn"]["kernel"]["scale"].shape == (
            k.shape[0],
        ) + k.shape[2:]
    else:
        assert qp["blocks"]["ln_attn"]["scale"] is (
            params["blocks"]["ln_attn"]["scale"]
        )
        for name in ("wq", "wk", "wv", "wo"):
            assert is_quantized(qp["blocks"]["attn"][name])
        for name in ("gate", "up", "down"):
            assert is_quantized(qp["blocks"]["mlp"][name])
        assert qp["lm_head"] is params["lm_head"]


def test_quantized_param_specs_tp_rules():
    """Column-parallel kernels shard their out dim -> the scale keeps
    that entry; row-parallel kernels shard the contracting dim -> the
    scale replicates. Derived from the same rule table TP decode uses
    (parallel/sharding.py), so the quantized tree places exactly where
    qdot's local outputs live."""
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.parallel.sharding import (
        param_partition_specs,
    )

    cfg = _cfg()
    mcfg = MeshConfig(tensor=2, strategy="no_shard")
    abstract = jax.eval_shape(
        lambda k: get_model(cfg).init(k, cfg), jax.random.key(0)
    )
    p_specs = param_partition_specs(abstract, mcfg)
    q_specs = quantized_param_specs(p_specs, abstract)
    attn = q_specs["blocks"]["attn"]
    # c_attn kernel [L, E, 3, H, D] shards H (dim 3): scale [L, 3, H, D]
    # keeps "tensor" at its H position (dim 2 after dropping E).
    assert tuple(attn["c_attn"]["kernel"]["q8"]) == (
        None, None, None, "tensor", None,
    )
    assert tuple(attn["c_attn"]["kernel"]["scale"]) == (
        None, None, "tensor", None,
    )
    # c_proj kernel [L, F, E] is row-parallel (shards F = contracting):
    # its scale [L, E] replicates.
    assert tuple(attn["c_proj"]["kernel"]["q8"]) == (
        None, "tensor", None,
    )
    assert attn["c_proj"]["kernel"]["scale"] == P()
    # Biases keep their original specs (not quantized).
    assert attn["c_attn"]["bias"] is p_specs["blocks"]["attn"][
        "c_attn"
    ]["bias"]


# -- the q8 cast budget (dtype-leak audit, extended) ------------------------


def _q8_engine(cfg):
    from pytorch_distributed_tpu.serving.engine import (
        PagedBatchedDecodeEngine,
    )

    return PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=16, page_size=8, prefill_chunk=8,
        kv_quant="int8", weight_quant="int8",
    )


def test_q8_cast_budget_clean_on_engine_programs(audit):
    """The registered budget (2 quantize sites: K+V append; 6 dequant
    sites: 2 KV reads + 4 gpt2 projection upcasts) passes on the exact
    programs the quantized engine dispatches — the in-process twin of
    the decode_paged_*_q8 registry cases."""
    from pytorch_distributed_tpu.analysis.budget import NO_COLLECTIVES

    cfg = _cfg()
    eng = _q8_engine(cfg)
    params = eng._place_params(_params(cfg))
    for kind in ("prefill", "decode_step"):
        report = audit.assert_clean(
            eng.program(kind),
            eng.example_args(kind, params),
            NO_COLLECTIVES,
            donate_argnums=(eng.CACHE_ARGNUM[kind],),
            donation_strict=True,
            compute_dtype=cfg.dtype,
            q8_cast_budget={"to_int8": 2, "from_int8": 6},
        )
        assert report.summary["q8_casts"]["to_int8"] == 2
        assert report.summary["q8_casts"]["from_int8"] == 6


def test_q8_cast_budget_fails_on_injected_f32_roundtrip(audit):
    """The acceptance criterion's negative test: wrap the real quantized
    decode step with a silent f32 round-trip — dequantize the K pool,
    'touch' it, re-quantize — and the extended dtype-leak check must
    fail LOUDLY with both q8 findings (an extra quantize AND an extra
    dequantize beyond the declared sites)."""
    cfg = _cfg()
    eng = _q8_engine(cfg)
    params = eng._place_params(_params(cfg))
    body = eng._bodies()["decode_step"]

    def leaky(params, toks, cache, *rest):
        # The classic silent leak: materialise the int8 pool wide, do
        # nothing useful, round it back. Numerically ~lossless-looking,
        # bandwidth-catastrophic — and invisible without the budget.
        scale = cache["k_scale"][..., None]  # per head of the merged axis
        wide = cache["k"].astype(jnp.float32).reshape(
            scale.shape[:-1] + (-1,)
        ) * scale
        requant = jnp.round(
            wide / jnp.maximum(scale, 1e-30)
        ).astype(jnp.int8)
        cache = dict(cache, k=requant.reshape(cache["k"].shape))
        return body(params, toks, cache, *rest)

    args = eng.example_args("decode_step", params)
    report = audit(
        jax.jit(leaky), args,
        expect_donation=False,
        compute_dtype=cfg.dtype,
        q8_cast_budget={"to_int8": 2, "from_int8": 6},
    )
    codes = {f.code for f in report.findings if f.severity == "error"}
    assert "q8-extra-quantize" in codes, report.table()
    assert "q8-extra-dequantize" in codes, report.table()


def test_q8_cast_budget_fails_on_missing_sites(audit):
    """The inventory is an EQUALITY, not a ceiling: a path that silently
    stops quantizing (e.g. a renamed param key drops the projections out
    of QUANT_WEIGHT_SUFFIXES, so the engine serves f32 weights while
    every quality budget trivially passes) must fail too. Simulated by
    auditing a kv-only program against the kv+weights budget: 2 dequant
    sites observed vs 6 declared."""
    from pytorch_distributed_tpu.serving.engine import (
        PagedBatchedDecodeEngine,
    )

    cfg = _cfg()
    eng = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=16, page_size=8, prefill_chunk=8,
        kv_quant="int8",  # weight_quant deliberately OFF
    )
    params = eng._place_params(_params(cfg))
    report = audit(
        eng.program("decode_step"),
        eng.example_args("decode_step", params),
        expect_donation=False,
        compute_dtype=cfg.dtype,
        q8_cast_budget={"to_int8": 2, "from_int8": 6},
    )
    codes = {f.code for f in report.findings if f.severity == "error"}
    assert "q8-missing-dequantize" in codes, report.table()


# -- the int8 Pallas kernel -------------------------------------------------


def test_paged_kernel_q8_matches_dequant_gather_reference():
    """The int8 kernel (interpret mode on this rig) matches the
    dequantize-then-gather XLA reference over GQA heads, ragged depths,
    and scratch-page entries — the same pin the f32 kernel carries."""
    from pytorch_distributed_tpu.ops.paged_kernel import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )

    rng = np.random.default_rng(7)
    b, h, hkv, d, pool, page, n_pages = 4, 8, 2, 16, 11, 8, 4
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(pool, page, hkv, d)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(pool, page, hkv, d)), jnp.float32)
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    # the pool's stored shape: heads merged head-major on the minor axis
    kq, vq = (x.reshape(pool, page, hkv * d) for x in (kq, vq))
    tables = np.zeros((b, n_pages), np.int32)
    lengths = np.asarray([0, 7, 17, 30], np.int32)
    pid = 1
    for i, ln in enumerate(lengths):
        for j in range(int(ln) // page + 1):
            tables[i, j] = pid
            pid += 1
    out = paged_decode_attention(
        q, kq, vq, tables, lengths, k_scales=ks, v_scales=vs,
        interpret=True,
    )
    ref = paged_decode_attention_reference(
        q, kq, vq, tables, lengths, k_scales=ks, v_scales=vs
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )
    # Scales must arrive paired.
    with pytest.raises(ValueError, match="together"):
        paged_decode_attention(
            q, kq, vq, tables, lengths, k_scales=ks, interpret=True
        )
