"""The mellum family: Mellum 2's block, served through the paged engine with
TWO groups of pages, one for the layers that attend a sliding window and one
for the layers that attend everything.

Pre-norm (RMSNorm) layers of grouped-query attention and softmax-routed
dropless experts, no bias anywhere, untied head. ``layer_types`` says which
layers slide; ``perfbench/reference/mellum.py`` is the plain float32
statement of the same, and the tests hold this file to it.

    x = wte[ids]
    h = norm(x) ; q = h Wq [H, D] ; k = h Wk [Hkv, D] ; v = h Wv [Hkv, D]
    q, k = rope_kind(q, k, position)          half-split rotate_half, all D
      sliding_attention: inv_freq = theta^(-2i/D)
      full_attention:    YaRN(inv_freq), cos and sin times
                         rope_attention_factor (q . k carries its square)
    score = D^-1/2 q . k over keys j <= i, and for sliding_attention also
            j > i - sliding_window ; softmax in float32
    x = x + (P v) Wo
    h = norm(x) ; p = softmax(h Wr) over all X experts, float32
    chosen = top-K of p ; g = p[chosen] / sum(p[chosen])    (norm_topk_prob)
    x = x + sum_e g_e (silu(h Wgate_e) * (h Win_e)) Wout_e  (ops/moe.py
        ``moe_dropless``, route "softmax", no shared expert)
    logits = norm(x) lm_head

**The layer pattern** is one period repeated (``layer_period``: for
Mellum2-12B-A2.5B ``[s s s f]``), so the parameters are TWO stacks, one an
attention kind, and ONE ``scan_layers`` over the periods whose body runs the
period's layers in order; layer j of kind k in period i is entry ``i * (k's
layers a period) + j`` of k's stack, read from the whole stack by that index
(as ``models/granitemoehybrid.py`` does by mixer kind). The expert stacks
are never sliced: ``moe_dropless`` reads (layer, expert) where its product
uses it.

  wte [V, E]   lm_head [E, V]   ln_f {scale [E]}
  sliding_attention/ (Ls layers)  and  full_attention/ (Lf layers), each:
    ln_attn, ln_mlp {scale [l, E]}
    attn/ wq [l, E, H D]   wk, wv [l, E, Hkv D]   wo [l, H D, E]
    mlp/  router [l, E, X]   w_gate, w_in [l, X, E, F]   w_out [l, X, F, E]

**Two groups of pages ride the scan's carry**, each ``[l, P_group, page,
Hkv D]`` (the dense families' layout) and addressed by the layer's index in
its OWN stack:

  k, v      over the full layers: a row keeps every position
  k_w, v_w  over the sliding layers: a row keeps the positions a query still
            to come can see, its last ``sliding_window`` and the chunk being
            written; the engine releases the pages behind them
            (``serving/block_pool.first_kept_page``)

A call's ``block_tables`` is [B, 2 n_pages]: the full group's table, then
the window group's, both indexed by ABSOLUTE page number (position //
page); a window-table entry behind the window points at the scratch page and
is never read: a one-token call starts its kernel's loop at the window's
first block (``ops/paged_kernel.py`` ``first``), a chunk's queries read
their keys block by block from there (``decode.blocked_attention``), and the
gather fallback masks the same keys.

Served only: no training path (the dropless layer has no backward), so
``apply`` is the cache-free forward for tests and tools and there is no
``run_blocks``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.models.granitemoehybrid import layer_period
from pytorch_distributed_tpu.ops.layer_scan import scan_layers
from pytorch_distributed_tpu.ops.layers import rms_norm
from pytorch_distributed_tpu.ops.moe import moe_dropless
from pytorch_distributed_tpu.ops.rope import (
    apply_rope,
    rope_angles,
    yarn_inv_freq,
)

Params = dict[str, Any]

SLIDING, FULL = "sliding_attention", "full_attention"
KINDS = (SLIDING, FULL)
# the cache's leaves a kind, and the trace's names of its two attentions
LEAVES = {FULL: ("k", "v"), SLIDING: ("k_w", "v_w")}
SCOPES = {FULL: ("full_decode", "full_prefill"),
          SLIDING: ("swa_decode", "swa_prefill")}
EXPERT_STACKS = ("w_gate", "w_in", "w_out")  # [l, X, ...], never sliced
TOKEN_BLOCK = 2048  # tokens a layer processes at once
# what ``forward`` counts, summed over the layers (moe_dropless)
AUX_COUNTS = ("moe_pairs_here", "moe_rows_computed", "moe_experts_hit")


def n_layers_of(cfg: ModelConfig, kind: str) -> int:
    return cfg.layer_types.count(kind)


def serving(cfg: ModelConfig) -> dict:
    """What an engine has to know of the family (``decode.Serving``)."""
    fam = "the mellum family"
    return dict(
        dense_cache=False,
        window=cfg.sliding_window if n_layers_of(cfg, SLIDING) else 0,
        aux_counts=AUX_COUNTS,
        # the tokens each program kind processed; the positions a decode
        # dispatch's ready rows attend in a layer of each group; what the
        # window group's pages hold and what some row's window needs of it
        counters=("moe_tokens.prefill", "moe_tokens.decode_step",
                  "kv_positions_read.full", "kv_positions_read.window",
                  "window_pages_released", "window_positions_held",
                  "window_positions_needed"),
        unserved={
            "mesh": f"{fam} is served on one device: its two page groups "
                    "and its two parameter stacks have no mesh placement",
            "kv_quant": f"{fam}'s pages are unquantized: kv_quant has no "
                        "scale pools for the window group",
            "weight_quant": f"{fam}'s products are not built on int8 "
                            "weights (weight_quant)",
            "adapters": f"{fam} has no adapter points (adapters): the LoRA "
                        "deltas are the dense families' projections'",
            "speculative_k": f"{fam} has no verify step (speculative_k): a "
                             "window page released behind a draft that is "
                             "then rejected would be needed again",
            "handoff": f"{fam} cannot hand a row to another worker "
                       "(export_handoff / import_handoff, role prefill or "
                       "decode): a handoff ships one table's pages, and the "
                       "row has two",
            "prefix": f"{fam} takes no prefix hit: a cached chunk would have "
                      "to keep the window group's last sliding_window "
                      "positions, so nothing is matched, published or pinned",
        },
    )


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    """Seeded weights, the draws of ``perfbench/reference/mellum`` (a test
    holds the two trees equal): matrices normal 0.02, norms 1; an expert
    stack is drawn a layer at a time (no float32 copy of a stack exists)."""
    pdt = jnp.dtype(cfg.param_dtype)
    e, v, d = cfg.n_embd, cfg.vocab_size, cfg.head_dim
    hd, hkv = cfg.n_head * d, cfg.kv_heads * d
    x, f = cfg.n_routed_experts, cfg.moe_intermediate_size

    def normal(kk, shape):
        return (jax.random.normal(kk, shape, jnp.float32) * 0.02).astype(pdt)

    def by_layer(kk, n, shape):
        return jax.lax.map(lambda k1: normal(k1, shape), jax.random.split(kk, n))

    def layers(kk, n):
        ks = jax.random.split(kk, 8)
        return {
            "ln_attn": {"scale": jnp.ones((n, e), pdt)},
            "attn": {
                "wq": normal(ks[0], (n, e, hd)),
                "wk": normal(ks[1], (n, e, hkv)),
                "wv": normal(ks[2], (n, e, hkv)),
                "wo": normal(ks[3], (n, hd, e)),
            },
            "ln_mlp": {"scale": jnp.ones((n, e), pdt)},
            "mlp": {
                "router": normal(ks[4], (n, e, x)),
                "w_gate": by_layer(ks[5], n, (x, e, f)),
                "w_in": by_layer(ks[6], n, (x, e, f)),
                "w_out": by_layer(ks[7], n, (x, f, e)),
            },
        }

    ks = jax.random.split(key, 4)
    return {
        "wte": normal(ks[0], (v, e)),
        SLIDING: layers(ks[1], n_layers_of(cfg, SLIDING)),
        FULL: layers(ks[2], n_layers_of(cfg, FULL)),
        "ln_f": {"scale": jnp.ones((e,), pdt)},
        "lm_head": normal(ks[3], (e, v)),
    }


def init_cache(cfg: ModelConfig, pool_pages: int, page_size: int,
               window_pool_pages: int, dtype=None) -> dict:
    """{"k", "v"}: the pool of ``pool_pages`` pages over the full layers;
    {"k_w", "v_w"}: the pool of ``window_pool_pages`` pages over the sliding
    layers. Heads merged head-major on the minor axis (the dense families'
    layout), page 0 of each the scratch page."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    w = cfg.kv_heads * cfg.head_dim
    out = {}
    for kind, pages in ((FULL, pool_pages), (SLIDING, window_pool_pages)):
        shape = (n_layers_of(cfg, kind), pages, page_size, w)
        out.update({name: jnp.zeros(shape, dtype) for name in LEAVES[kind]})
    return out


def rope_table(cfg: ModelConfig, kind: str, pos, t: int):
    """(cos, sin) [B, T, D] of a layer kind for T positions from pos[b]: the
    plain table for the sliding layers, YaRN's times
    ``rope_attention_factor`` for the full ones."""
    d = cfg.head_dim
    if kind == SLIDING or cfg.rope_factor == 1.0:
        return rope_angles(t, d, cfg.rope_theta, offset=pos[:, None])
    cos, sin = rope_angles(
        t, d, cfg.rope_theta, offset=pos[:, None],
        inv_freq=yarn_inv_freq(
            d, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max_position, cfg.rope_beta_fast,
            cfg.rope_beta_slow,
        ),
    )
    return cos * cfg.rope_attention_factor, sin * cfg.rope_attention_factor


def split_tables(block_tables):
    """[B, 2 n] -> {kind: [B, n]}: the full group's table, then the window
    group's."""
    n = block_tables.shape[1] // 2
    return {FULL: block_tables[:, :n], SLIDING: block_tables[:, n:]}


def _attention(h, ap, kind, cache, layer, pos, tables, cfg: ModelConfig,
               paged_impl="gather"):
    from pytorch_distributed_tpu.models.decode import (
        _cached_attention,
        _write_kv,
        blocked_attention,
    )

    b, t, _ = h.shape
    d = cfg.head_dim
    # the angles of these rows, made where they are used: a table of the
    # whole prefill group would be a [B, T, D] pair a kind
    cos, sin = rope_table(cfg, kind, pos, t)
    q = (h @ ap["wq"].astype(h.dtype)).reshape(b, t, -1, d)
    k = (h @ ap["wk"].astype(h.dtype)).reshape(b, t, -1, d)
    v = (h @ ap["wv"].astype(h.dtype)).reshape(b, t, -1, d)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    kn, vn = LEAVES[kind]
    table = tables[kind]
    window = cfg.sliding_window if kind == SLIDING else None
    kv = _write_kv({"k": cache[kn], "v": cache[vn]}, layer, k, v, pos, table)
    decode_scope, prefill_scope = SCOPES[kind]
    if t == 1:
        with jax.named_scope(decode_scope):
            o = _cached_attention(
                q, kv, layer, pos, table, paged_impl, window=window)
    else:
        with jax.named_scope(prefill_scope):
            o = blocked_attention(q, kv, layer, pos, table, window=window)
    cache = {**cache, kn: kv["k"], vn: kv["v"]}
    return o.reshape(b, t, -1) @ ap["wo"].astype(h.dtype), cache


def _layer(x, bp, stacks, kind, cache, layer, pos, tables, live,
           cfg: ModelConfig, paged_impl="gather"):
    """One layer over rows x [g, T, E]: returns (x, cache, counts [3],
    ``moe_dropless``'s). ``stacks``: the kind's expert stacks whole."""
    eps = cfg.layer_norm_epsilon
    a, cache = _attention(
        rms_norm(x, bp["ln_attn"], eps=eps), bp["attn"], kind, cache, layer,
        pos, tables, cfg, paged_impl,
    )
    x = x + a
    m = rms_norm(x, bp["ln_mlp"], eps=eps)
    g, t, e = m.shape
    y, counts = moe_dropless(
        m.reshape(g * t, e), {"router": bp["mlp"]["router"], **stacks},
        top_k=cfg.num_experts_per_tok, expert_offset=0,
        activation=jax.nn.silu, live=live.reshape(g * t), layer=layer,
        route="softmax", renormalise=cfg.norm_topk_prob,
    )
    return x + y.reshape(g, t, e), cache, counts


def _rows_in_blocks(layer_fn, x, cache, per_row):
    """``layer_fn(x, cache, *per_row) -> (x, cache, counts)`` over all rows,
    TOKEN_BLOCK tokens' worth of rows at a time (the cache carried from
    group to group), so no temporary grows with the prefill group."""
    b, t = x.shape[:2]
    g = max(k for k in range(1, b + 1)
            if b % k == 0 and (k == 1 or k * t <= TOKEN_BLOCK))
    if g == b:
        return layer_fn(x, cache, *per_row)

    def split(a):
        return a.reshape((b // g, g) + a.shape[1:])

    def body(carry, rows):
        cache, counts = carry
        xg, cache, c = layer_fn(rows[0], cache, *rows[1:])
        return (cache, counts + c), xg

    (cache, counts), out = jax.lax.scan(
        body, (cache, jnp.zeros((3,), jnp.int32)),
        tuple(split(a) for a in (x, *per_row)),
    )
    return out.reshape(x.shape), cache, counts


def forward(params: Params, input_ids, cfg: ModelConfig, cache: dict, pos,
            block_tables, *, live=None, logits_index=None,
            paged_impl="gather"):
    """T tokens a row at positions pos[b]..pos[b]+T-1 through every period
    of the pattern against the two page groups. ``block_tables`` [B, 2 n]:
    the full group's table and the window group's side by side
    (``split_tables``). ``live`` [B, T] bool marks the entries that are
    tokens (padding and free rows route nowhere and count nothing). Returns
    (logits [B, T, V] — [B, 1, V], of position ``logits_index[b]``, where
    that is given —, cache, counts [3] int32 summed over the layers: pairs
    routed, rows the expert products ran over, experts hit).
    ``paged_impl``: how a call of one token a row reads its pages
    (``decode._cached_attention``: "gather" / "kernel" /
    "kernel_interpret")."""
    b, t = input_ids.shape
    pos = jnp.asarray(pos, jnp.int32)
    if live is None:
        live = jnp.ones((b, t), jnp.bool_)
    x = embed(params, input_ids, cfg)

    period = layer_period(cfg)
    n_periods = cfg.n_layer // len(period)
    per_period = {k: period.count(k) for k in KINDS}
    # the expert stacks stay out of the per-layer slices: a layer's slice of
    # them would be copied whole into the expert loop, hit or not
    stacks = {k: {n: params[k]["mlp"][n] for n in EXPERT_STACKS}
              for k in KINDS if per_period[k]}
    sliced = {k: {**params[k], "mlp": {"router": params[k]["mlp"]["router"]}}
              for k in stacks}

    def period_body(carry, i, _):
        x, cache, counts = carry
        seen = dict.fromkeys(KINDS, 0)
        for kind in period:
            layer = i * per_period[kind] + seen[kind]
            seen[kind] += 1
            bp = jax.tree.map(
                lambda a, layer=layer: jax.lax.dynamic_index_in_dim(
                    a, layer, 0, keepdims=False), sliced[kind])

            def layer_fn(xg, cache, pos, tables, live, bp=bp, kind=kind,
                         layer=layer):
                return _layer(
                    xg, bp, stacks[kind], kind, cache, layer, pos,
                    split_tables(tables), live, cfg, paged_impl)

            x, cache, c = _rows_in_blocks(
                layer_fn, x, cache, (pos, block_tables, live))
            counts = counts + c
        return x, cache, counts

    x, cache, counts = scan_layers(
        period_body, (x, cache, jnp.zeros((3,), jnp.int32)),
        jnp.arange(n_periods, dtype=jnp.int32), remat_mode="none",
    )
    if logits_index is not None:
        x = jnp.take_along_axis(x, logits_index[:, None, None], axis=1)
    return head(params, x, cfg), cache, counts


def apply(params: Params, input_ids, cfg: ModelConfig, **_unused):
    """[B, T] tokens -> [B, T, V] logits with no cache kept: every row gets
    one page of T positions in each group of a cache made here. For tests
    and tools; the engines call ``forward``."""
    b, t = input_ids.shape
    if t > cfg.n_ctx:
        raise ValueError(f"sequence length {t} exceeds n_ctx {cfg.n_ctx}")
    cache = init_cache(cfg, b + 1, t, b + 1)
    one = 1 + jnp.arange(b, dtype=jnp.int32)[:, None]
    logits, _, _ = forward(
        params, input_ids, cfg, cache, jnp.zeros((b,), jnp.int32),
        jnp.concatenate([one, one], axis=1),
    )
    return logits


def embed(params: Params, input_ids, cfg: ModelConfig, **_unused):
    return params["wte"][input_ids].astype(jnp.dtype(cfg.dtype))


def run_blocks(*_args, **_kw):
    raise NotImplementedError(
        "the mellum family is served only (models/mellum.py): it has no "
        "pipeline or training path"
    )


def final_norm(params: Params, x, cfg: ModelConfig):
    return rms_norm(x, params["ln_f"], eps=cfg.layer_norm_epsilon)


def head(params: Params, x, cfg: ModelConfig):
    x = final_norm(params, x, cfg)
    return jnp.einsum(
        "bte,ev->btv", x, params["lm_head"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    ).astype(jnp.dtype(cfg.logits_dtype))
