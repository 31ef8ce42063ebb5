"""int8 quantization for the bandwidth-bound serving path.

Decode streams the whole KV pool and the full weight set through HBM for
every token; quantizing both to int8 roughly quarters the bytes against
the f32 rig config (halves them against a bf16 deployment) on the two
largest traffic terms. Two quantization families live here, each shaped
by where its bytes sit:

1. **KV pages** (``quantize_kv`` / ``dequantize_kv``): symmetric int8
   with a PER-TOKEN, PER-KV-HEAD f32 scale (``scale[b, t, h] =
   max|x[b, t, h, :]| / 127``), stored page-aligned next to the value
   pages (``[L, P, page, Hkv]`` scale leaves beside the
   ``[L, P, page, Hkv*D]`` int8 leaves, the heads merged head-major on
   the minor axis — serving/block_pool.py's pool layout). Per-token
   granularity is NOT a tuning choice, it is the
   soundness condition of the paged cache: pages fill incrementally
   (append on decode, chunk-at-a-time on prefill), so a scale shared
   across a page would be re-derived every append and silently
   re-quantize — i.e. corrupt — the positions already written. A
   per-token scale depends only on that token's K/V, which also makes
   quantization DETERMINISTIC per position: a fault-resume re-prefill
   reproduces bit-identical pages, so the PR-6/PR-8 token-identical
   recovery contracts survive quantization verbatim
   (tests/test_serving_quant.py re-pins them).

2. **Weights** (``quantize_weight`` / ``quantize_decode_params``):
   weight-only int8 with a PER-OUTPUT-CHANNEL f32 scale over the
   contracting dim, applied to the block projection matmuls of the
   decode path (QKV/out projections + MLP). ``qdot``/``ops.layers.dense``
   compute ``(x @ q8.astype(x.dtype)) * scale`` — the int8 kernel is
   upcast in-register ahead of the MXU, so HBM traffic is the int8
   bytes while accumulation stays in the activation dtype. The scale is
   a linear factor applied BEFORE any tensor-parallel psum, so
   row-parallel projections reduce scaled partials and the TP
   collective structure (pinned all-reduce counts) is untouched.
   Embeddings, the LM head, and norms stay full precision: they are a
   small fraction of decode bytes and the head feeds the sampler
   directly, where quantization noise buys nothing.

Quality is CONTRACTUAL, not anecdotal: ``relative_logit_mse`` and
``token_match_rate`` are the two pinned metrics (``Q8_QUALITY`` carries
the budgets tests/test_serving_quant.py asserts), and
the dtype-leak audit grows a q8 cast budget
(analysis/audit.check_q8_casts) so a silent f32 round-trip — an extra
quantize or dequantize beyond the declared sites — fails the audit
instead of just burning bandwidth.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Pinned quality budgets for the int8 serving path, asserted by
# tests/test_serving_quant.py::test_quality_budget_held_teacher_forced
# (tier-1 FAILS on breach — the budget is a contract the way the
# bit-equivalence pins are, not a printed observation).
#
# The pinned token metric is TEACHER-FORCED greedy agreement
# (``argmax_agreement`` over both engines' logits for IDENTICAL
# contexts): it measures quantization error and nothing else. The
# autoregressive prefix-match rate (``token_match_rate`` over engine
# outputs) is reported alongside but NOT pinned — on a random-init
# bench model a ~2%-per-step argmax flip compounds geometrically over a
# 32-token generation (0.98^32 ~ 0.52), so the prefix metric mostly
# measures how chaotic an uncalibrated model's near-ties are, not how
# lossy int8 is; a trained model's logit gaps make it far tamer.
#
# Measured headroom on the bench config (vocab 2048, 8 layers):
# relative logit MSE ~1e-5 (kv-only) / ~4e-4 (kv+weights);
# teacher-forced agreement 0.992 (kv-only) / 0.956 (kv+weights). The
# pins leave margin for config drift without letting a real regression
# through — a lost scale or a per-page rescale moves these metrics by
# orders of magnitude, not percents.
Q8_QUALITY = {
    "max_relative_logit_mse": 2e-3,
    "min_token_match_rate": 0.90,
}

_EPS = 1e-30


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 with a per-token, per-head scale: ``x`` is
    ``[..., D]`` (typically [B, T, Hkv, D] new K or V), the scale is
    computed over the trailing head_dim only. Returns (int8 values of
    x.shape, f32 scales of x.shape[:-1]). All-zero rows get scale 1 so
    dequantization reproduces exact zeros (no 0/0); values round to
    nearest and clamp to [-127, 127] (the symmetric range — -128 is
    never emitted, so |dequant| <= amax always)."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(x32 / jnp.maximum(scale, _EPS)[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of ``quantize_kv``: ``q`` [..., D] int8, ``scale``
    [...] f32 -> values in ``dtype``."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# -- weight-only int8 -------------------------------------------------------

# A quantized weight is a plain dict pytree so it rides shard_map specs,
# device_put trees, and scan-over-layers slicing with zero machinery.
_QKEYS = frozenset({"q8", "scale"})


def is_quantized(w) -> bool:
    return isinstance(w, dict) and set(w) == _QKEYS


def quantize_weight(w: jax.Array, contract_axis: int = 0) -> dict:
    """Per-output-channel symmetric int8: the scale reduces over
    ``contract_axis`` (the matmul's contracting dim), one f32 scale per
    remaining (output) coordinate. Stacked block leaves [L, in, out...]
    pass ``contract_axis=1`` so each layer quantizes independently."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=contract_axis)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(w32 / jnp.expand_dims(jnp.maximum(scale, _EPS),
                                        contract_axis))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return {"q8": q, "scale": scale.astype(jnp.float32)}


def qdot(x: jax.Array, w, *, precision=None) -> jax.Array:
    """``x @ w`` where ``w`` is a plain [in, out...] array (bit-identical
    to the pre-quant ``x @ w.astype(x.dtype)``) or a ``quantize_weight``
    dict (int8 kernel upcast in-register, per-channel scale applied to
    the output — weight-only quantization, accumulation in x.dtype).
    THE one definition of the quantized matmul: ``ops.layers.dense``
    delegates here, so the gpt2 (dense) and llama (raw-matmul) decode
    paths can never diverge on the quantization contract."""
    if is_quantized(w):
        y = jax.lax.dot_general(
            x, w["q8"].astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
            precision=precision,
        )
        return y * w["scale"].astype(y.dtype)
    return jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        precision=precision,
    )


# The decode-path projection weights quantize_decode_params targets,
# keyed by param-path suffix exactly like parallel/sharding._TENSOR_RULES
# (dense-family blocks only — embeddings/head/norms stay full precision,
# MoE expert stacks are rejected at the engine). The stacked [L, in,
# out...] leaves contract dim 1, hence contract_axis=1 below.
QUANT_WEIGHT_SUFFIXES: frozenset[tuple[str, ...]] = frozenset({
    ("attn", "c_attn", "kernel"),
    ("attn", "c_proj", "kernel"),
    ("mlp", "c_fc", "kernel"),
    ("mlp", "c_proj", "kernel"),
    ("attn", "wq"),
    ("attn", "wk"),
    ("attn", "wv"),
    ("attn", "wo"),
    ("mlp", "gate"),
    ("mlp", "up"),
    ("mlp", "down"),
})
_SUFFIX_LENS = (3, 2)


def _path_keys(path) -> tuple[str, ...]:
    return tuple(
        getattr(p, "key", None) if isinstance(getattr(p, "key", None), str)
        else str(p)
        for p in path
    )


def _is_quant_path(path) -> bool:
    keys = _path_keys(path)
    if not keys or keys[0] != "blocks":
        return False
    return any(
        len(keys) >= n and keys[-n:] in QUANT_WEIGHT_SUFFIXES
        for n in _SUFFIX_LENS
    )


def quantize_decode_params(params):
    """Quantize the block projection weights of a decode params tree
    (int8 kernel + per-out-channel scale per QUANT_WEIGHT_SUFFIXES);
    everything else — embeddings, head, norms, biases — passes through
    untouched. Pure function of the weights: engines call it ONCE per
    params tree (identity-memoized) at first dispatch."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: (
            quantize_weight(leaf, contract_axis=1)
            if _is_quant_path(path)
            else leaf
        ),
        params,
    )


def quantized_param_specs(p_specs, params_abstract):
    """Map an (unquantized) PartitionSpec tree to the quantized params
    tree's structure: a quantized kernel keeps its spec on ``q8`` and
    drops the contracting dim's entry (stacked leaves: index 1) for
    ``scale`` — column-parallel scales shard with their output channels,
    row-parallel scales replicate, exactly matching the local outputs
    ``qdot`` multiplies them into under shard_map."""
    from jax.sharding import PartitionSpec as P

    def map_leaf(path, spec, leaf):
        if not _is_quant_path(path):
            return spec
        entries = list(tuple(spec)) + [None] * (leaf.ndim - len(tuple(spec)))
        del entries[1]  # the stacked leaf's contracting (in) dim
        scale_spec = P(*entries) if any(e for e in entries) else P()
        return {"q8": spec, "scale": scale_spec}

    return jax.tree_util.tree_map_with_path(
        map_leaf, p_specs, params_abstract,
        is_leaf=lambda x: isinstance(x, P),
    )


# -- quality metrics --------------------------------------------------------


def relative_logit_mse(ref_logits, q_logits) -> float:
    """Scale-free logit error: mean((q - ref)^2) / mean(ref^2) — the
    pinnable form (absolute MSE moves with model width/init scale, the
    ratio does not)."""
    ref = np.asarray(ref_logits, np.float64)
    q = np.asarray(q_logits, np.float64)
    denom = max(float(np.mean(ref * ref)), _EPS)
    return float(np.mean((q - ref) ** 2) / denom)


def argmax_agreement(ref_logits, q_logits) -> float:
    """Teacher-forced greedy agreement: the fraction of positions where
    both logit tensors ([..., V], IDENTICAL input contexts) pick the
    same argmax — the PINNED token metric (see Q8_QUALITY: measures
    quantization error without autoregressive compounding)."""
    ref = np.argmax(np.asarray(ref_logits), axis=-1)
    q = np.argmax(np.asarray(q_logits), axis=-1)
    return float(np.mean(ref == q))


def token_match_rate(ref_tokens, q_tokens) -> float:
    """Greedy-continuation agreement over paired token sequences:
    sum(longest common PREFIX) / sum(len) — prefix-based because the
    first divergent token changes the context of everything after it
    (positions past the split are different inputs, not comparable
    errors). 1.0 = every sequence identical."""
    total = matched = 0
    for r, q in zip(ref_tokens, q_tokens, strict=True):
        r = np.asarray(r)
        q = np.asarray(q)
        n = min(r.shape[0], q.shape[0])
        agree = r[:n] == q[:n]
        m = int(agree.argmin()) if not agree.all() else n
        matched += m
        total += max(r.shape[0], q.shape[0])
    return matched / max(total, 1)
