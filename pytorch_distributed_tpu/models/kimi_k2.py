"""The kimi_k2 family: the DeepSeek-V3 block as Kimi-K2.5 publishes it,
served through the paged engine.

Pre-norm (RMSNorm) blocks of LATENT attention and a feed-forward that is one
SwiGLU in the first ``first_k_dense_replace`` layers and, in every later
one, sigmoid-routed dropless experts beside a shared expert
(``ops/moe.moe_dropless``); YaRN rotary frequencies on a slice of the head;
untied head. The equations follow HF ``modeling_deepseek.py`` (which
``kimi_k2`` reuses); ``perfbench/reference/kimi_k2.py`` is the plain
float32 statement of the same, and the tests hold this file to it.

The layer stack is not uniform, so the parameters are TWO stacks, scanned in
turn (``ops/layer_scan.scan_layers``), over ONE cache that rides both scans'
carry and is addressed by the global layer index:

  wte [V, E]   ln_f {scale [E]}   lm_head [E, V]
  dense/ (Ld = first_k_dense_replace layers)   moe/ (Le = n_layer - Ld)
    ln_attn, ln_mlp {scale [l, E]}
    attn/ wq_a [l, E, Rq]   q_norm {scale [l, Rq]}   wq_b [l, Rq, H*(Dn+Dr)]
          wkv_a [l, E, C+Dr]  kv_norm {scale [l, C]}  wkv_b [l, C, H*(Dn+Dv)]
          wo [l, H*Dv, E]
    dense/mlp/ gate, up [Ld, E, F]   down [Ld, F, E]
    moe/mlp/   router [Le, E, X]   bias [Le, X] (float32: the selection bias)
               w_gate, w_in [Le, held, E, Fm]   w_out [Le, held, Fm, E]
               shared/ gate, up [Le, E, Fs]   down [Le, Fs, E]

(Rq = q_lora_rank, C = kv_lora_rank, Dn/Dr/Dv = qk_nope/qk_rope/v head
dims, X = n_routed_experts, held = the experts this process holds.)

**The cache is the latent**: per position and layer the normed ``c_kv``
(C numbers) and the one rotated key ``k_r`` all heads share (Dr), stored as
ONE paged leaf ``{"latent": [L, P, page, W]}`` — 576 numbers where per-head
K and V would be H * (Dn + Dr + Dv), stored in W = 640 (``page_width``:
whole lanes). It is read two ways, the same function of the same pages:

- *expanded* (a multi-token call: prefill chunks): [k_n | v] = c_kv W_kvb
  per head, score = s (q_n . k_n + q_r . k_r), o = P v. Row by row and key
  block by key block with a running softmax, as deep as the row's own
  positions reach and no deeper.
- *absorbed* (a one-token call: decode): W_kvb per head = (W_uk, W_uv);
  q~ = q_n W_uk^T lives in the latent's space, score = s (q~ . c_kv +
  q_r . k_r), o = (P c_kv) W_uv. The pages are never expanded: on the
  chip a Pallas kernel reads them where they lie, each row to its own
  depth (ops/latent_paged_kernel.py); elsewhere every row's whole table
  is gathered into a window first (``attend_window``).

s = (Dn + Dr)^-1/2 * m^2, m = ``yarn_mscale(factor, mscale_all_dim)``.

**RoPE layout.** HF de-interleaves q_r and k_r (pairs (2i, 2i+1) -> halves)
before ``rotate_half``. This code applies the half-split rotation
(``ops/rope.apply_rope``) to the projections' outputs as they come: the
weights are taken to be stored de-interleaved already (an import of a
checkpoint permutes the rope columns of wq_b and wkv_a once). Scores are
unchanged by a permutation q_r and k_r share.

Rows in blocks: a call of more than ``TOKEN_BLOCK`` tokens runs each layer
over groups of rows in turn, so no temporary grows with the prefill group.

Served only: the family has no training path (at 16 bytes a parameter no
cut of it fits a chip), so ``apply`` is the cache-free forward for tests and
tools and there is no ``run_blocks``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.ops.layer_scan import scan_layers
from pytorch_distributed_tpu.ops.layers import rms_norm
from pytorch_distributed_tpu.ops.moe import moe_dropless
from pytorch_distributed_tpu.ops.paged_kernel import key_block_pages
from pytorch_distributed_tpu.ops.rope import (
    apply_rope,
    rope_angles,
    yarn_inv_freq,
    yarn_mscale,
)

Params = dict[str, Any]

LATENT = "latent"  # the cache's one leaf
TOKEN_BLOCK = 2048  # tokens a layer processes at once


LANES = 128  # the chip's tiles are (8, 128): a minor axis fills them or pads


def latent_width(cfg: ModelConfig) -> int:
    """The numbers a cached position needs: c_kv and the shared k_r."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def page_width(cfg: ModelConfig) -> int:
    """The numbers a cached position is STORED in: the latent, zero-padded
    to whole lanes (576 -> 640). The chip's tiling pads a minor axis to
    whole lanes anyway, and given a ragged one the compiler stores the pool
    page-axis-minor and converts all of it at every program's entry and
    exit (PERF.md section 5: what the GPT-2 pool's layout costs)."""
    return -(-latent_width(cfg) // LANES) * LANES


def experts_held(cfg: ModelConfig) -> int:
    return cfg.experts_held or cfg.n_routed_experts


def softmax_scale(cfg: ModelConfig) -> float:
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    pdt = jnp.dtype(cfg.param_dtype)
    e, v, h = cfg.n_embd, cfg.vocab_size, cfg.n_head
    rq, c = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f, fm = cfg.inner_dim, cfg.moe_intermediate_size
    fs = fm * cfg.n_shared_experts
    x, held = cfg.n_routed_experts, experts_held(cfg)
    ld = cfg.first_k_dense_replace
    le = cfg.n_layer - ld

    def normal(kk, shape, dtype=pdt):
        return (jax.random.normal(kk, shape, jnp.float32) * 0.02).astype(dtype)

    def stack(kk, n, mlp):
        ks = jax.random.split(kk, 6)
        return {
            "ln_attn": {"scale": jnp.ones((n, e), pdt)},
            "attn": {
                "wq_a": normal(ks[0], (n, e, rq)),
                "q_norm": {"scale": jnp.ones((n, rq), pdt)},
                "wq_b": normal(ks[1], (n, rq, h * (dn + dr))),
                "wkv_a": normal(ks[2], (n, e, c + dr)),
                "kv_norm": {"scale": jnp.ones((n, c), pdt)},
                "wkv_b": normal(ks[3], (n, c, h * (dn + dv))),
                "wo": normal(ks[4], (n, h * dv, e)),
            },
            "ln_mlp": {"scale": jnp.ones((n, e), pdt)},
            "mlp": mlp(ks[5], n),
        }

    def dense_mlp(kk, n):
        ks = jax.random.split(kk, 3)
        return {
            "gate": normal(ks[0], (n, e, f)),
            "up": normal(ks[1], (n, e, f)),
            "down": normal(ks[2], (n, f, e)),
        }

    def moe_mlp(kk, n):
        ks = jax.random.split(kk, 8)
        return {
            "router": normal(ks[0], (n, e, x)),
            "bias": normal(ks[1], (n, x), jnp.float32),
            "w_gate": normal(ks[2], (n, held, e, fm)),
            "w_in": normal(ks[3], (n, held, e, fm)),
            "w_out": normal(ks[4], (n, held, fm, e)),
            "shared": {
                "gate": normal(ks[5], (n, e, fs)),
                "up": normal(ks[6], (n, e, fs)),
                "down": normal(ks[7], (n, fs, e)),
            },
        }

    ks = jax.random.split(key, 4)
    return {
        "wte": normal(ks[0], (v, e)),
        "dense": stack(ks[1], ld, dense_mlp),
        "moe": stack(ks[2], le, moe_mlp),
        "ln_f": {"scale": jnp.ones((e,), pdt)},
        "lm_head": normal(ks[3], (e, v)),
    }


# -- the cache and its two readings -----------------------------------------


def init_latent_pool(cfg: ModelConfig, pool_pages: int, page_size: int,
                     dtype=None) -> dict:
    """{"latent": [L, pool_pages, page_size, page_width]}: one leaf whose
    minor axis is the whole latent in whole lanes, so the runtime stores it
    row-major and the programs convert nothing."""
    return {LATENT: jnp.zeros(
        (cfg.n_layer, pool_pages, page_size, page_width(cfg)),
        jnp.dtype(dtype or cfg.dtype),
    )}


def attend_expanded(q, pool, layer, tables, pos, wkv_b, cfg: ModelConfig):
    """q [B, T, H, Dn+Dr] at positions pos[b]..pos[b]+T-1 against layer
    ``layer`` of the latent pool, keys and values EXPANDED per head from
    the latent, a block of cache positions at a time, under a running
    (online) softmax: exact, and as deep as row b's pos[b]+T positions
    only. Returns [B, T, H, Dv]."""
    b, t, h, _ = q.shape
    c, dn, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    dr = cfg.qk_rope_head_dim
    page = pool.shape[2]
    kb_pages = key_block_pages(tables.shape[1], page)
    kb = kb_pages * page
    w = wkv_b.reshape(c, h, dn + dv).astype(q.dtype)
    scale = softmax_scale(cfg)

    def one_row(args):
        q_b, table, p0 = args  # [T, H, Dn+Dr], [n_pages], ()
        qpos = p0 + jnp.arange(t, dtype=jnp.int32)

        def one_block(i, carry):
            m, l, acc = carry
            pids = jax.lax.dynamic_slice_in_dim(table, i * kb_pages, kb_pages)
            lat = pool[layer, pids].reshape(kb, -1).astype(q.dtype)
            kv = jnp.einsum("sc,chd->shd", lat[:, :c], w)
            s = jnp.einsum(
                "thd,shd->hts", q_b[..., :dn], kv[..., :dn],
                preferred_element_type=jnp.float32,
            ) + jnp.einsum(
                "thr,sr->hts", q_b[..., dn:], lat[:, c:c + dr],
                preferred_element_type=jnp.float32,
            )
            kpos = i * kb + jnp.arange(kb, dtype=jnp.int32)
            s = jnp.where(
                kpos[None, None, :] <= qpos[None, :, None], s * scale, -1e30
            )
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            fix = jnp.exp(m - m_new)
            acc = acc * fix[..., None] + jnp.einsum(
                "hts,shd->htd", p.astype(q.dtype), kv[..., dn:],
                preferred_element_type=jnp.float32,
            )
            return m_new, l * fix + jnp.sum(p, axis=-1), acc

        # block 0 holds position 0, which every query may see: the running
        # maximum is finite from the first block on
        n_blocks = (p0 + t + kb - 1) // kb
        m, l, acc = jax.lax.fori_loop(0, n_blocks, one_block, (
            jnp.full((h, t), -1e30, jnp.float32),
            jnp.zeros((h, t), jnp.float32),
            jnp.zeros((h, t, dv), jnp.float32),
        ))
        return (acc / l[..., None]).transpose(1, 0, 2).astype(q.dtype)

    return jax.lax.map(one_row, (q, tables, pos))


def attend_window(q_lat, pool, layer, tables, pos, scale):
    """q_lat [B, T, H, W] (queries in the latent's space) against the
    GATHERED window of layer ``layer``: every row's whole table copied to
    [B, S, W], whatever the row's depth, read for the scores and again for
    the weighted sum. Returns the weighted latent [B, T, H, W]. The path
    off the chip, and what ops/latent_paged_kernel.py is held to."""
    from pytorch_distributed_tpu.models.decode import gather_pages

    t = q_lat.shape[1]
    lat = gather_pages(pool, layer, tables).astype(q_lat.dtype)  # [B, S, W]
    s = jnp.einsum(
        "bthc,bsc->bhts", q_lat, lat, preferred_element_type=jnp.float32
    ) * scale
    qpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # [B, T]
    kpos = jnp.arange(lat.shape[1], dtype=jnp.int32)
    s = jnp.where(
        kpos[None, None, None, :] <= qpos[:, None, :, None], s, -1e30
    )
    p = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhts,bsc->bthc", p, lat)


def attend_absorbed(q, pool, layer, tables, pos, wkv_b, cfg: ModelConfig,
                    paged_impl: str = "gather"):
    """The same attention with W_kvb ABSORBED into the query and the output:
    scores and the weighted sum are taken against the latent pages as they
    lie, shared by all heads. ``paged_impl`` "gather" reads them through
    ``attend_window``; "kernel" / "kernel_interpret" (one token a row) read
    them in place, each row to its depth, through the Pallas kernel of
    ops/latent_paged_kernel.py. Returns [B, T, H, Dv]."""
    b, t, h, _ = q.shape
    c, dn, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    w = wkv_b.reshape(c, h, dn + dv).astype(q.dtype)
    q_lat = jnp.concatenate([
        jnp.einsum("bthd,chd->bthc", q[..., :dn], w[..., :dn]), q[..., dn:],
        jnp.zeros((b, t, h, pool.shape[-1] - c - q.shape[-1] + dn), q.dtype),
    ], axis=-1)  # [B, T, H, page width]: the query in the latent's space
    if paged_impl == "gather" or t != 1:
        # the weighted latent, its rope tail unused: cheaper than a slice
        # of the gathered pages
        o_lat = attend_window(
            q_lat, pool, layer, tables, pos, softmax_scale(cfg))[..., :c]
    else:
        from pytorch_distributed_tpu.ops.latent_paged_kernel import (
            latent_paged_decode,
        )

        o_lat = latent_paged_decode(
            q_lat[:, 0], pool, layer, tables, pos,
            scale=softmax_scale(cfg), out_width=c,
            block_pages=key_block_pages(tables.shape[1], pool.shape[2]),
            interpret=paged_impl == "kernel_interpret",
        )[:, None]
    return jnp.einsum("bthc,chd->bthd", o_lat, w[..., dn:])


def _attention(x, ap, cache, layer, pos, tables, cfg: ModelConfig,
               paged_impl="gather"):
    from pytorch_distributed_tpu.models.decode import _write

    eps = cfg.layer_norm_epsilon
    b, t = x.shape[:2]
    h, c, dr = cfg.n_head, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    cos, sin = rope_angles(
        t, dr, cfg.rope_theta, offset=pos[:, None],
        inv_freq=yarn_inv_freq(
            dr, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max_position, cfg.rope_beta_fast,
            cfg.rope_beta_slow,
        ),
    )
    # (the cos/sin factor yarn_mscale(factor, mscale) /
    # yarn_mscale(factor, mscale_all_dim) is 1 where the two are equal, as
    # published; a configuration where they differ scales cos and sin)
    ratio = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim
    )
    if ratio != 1.0:
        cos, sin = cos * ratio, sin * ratio
    cq = rms_norm(x @ ap["wq_a"].astype(x.dtype), ap["q_norm"], eps=eps)
    q = (cq @ ap["wq_b"].astype(x.dtype)).reshape(b, t, h, -1)
    dn = cfg.qk_nope_head_dim
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], cos, sin)], axis=-1
    )
    kv = x @ ap["wkv_a"].astype(x.dtype)  # [B, T, C + Dr]
    lat = jnp.concatenate([
        rms_norm(kv[..., :c], ap["kv_norm"], eps=eps),
        apply_rope(kv[..., None, c:], cos, sin)[:, :, 0],
        jnp.zeros((b, t, page_width(cfg) - latent_width(cfg)), x.dtype),
    ], axis=-1)
    pool = _write(cache[LATENT], layer, lat, pos, tables)
    if t == 1:
        with jax.named_scope("mla_decode"):
            o = attend_absorbed(
                q, pool, layer, tables, pos, ap["wkv_b"], cfg, paged_impl)
    else:
        with jax.named_scope("mla_prefill"):
            o = attend_expanded(q, pool, layer, tables, pos, ap["wkv_b"], cfg)
    return o.reshape(b, t, -1) @ ap["wo"].astype(x.dtype), {LATENT: pool}


def _swiglu(m, p):
    gate = jax.nn.silu(m @ p["gate"].astype(m.dtype))
    return (gate * (m @ p["up"].astype(m.dtype))) @ p["down"].astype(m.dtype)


EXPERT_STACKS = ("w_gate", "w_in", "w_out")  # [Le, held, ...], never sliced
# what ``forward`` counts, summed over the expert layers (moe_dropless)
AUX_COUNTS = ("moe_pairs_here", "moe_rows_computed", "moe_experts_hit")


def serving(cfg: ModelConfig) -> dict:
    """What an engine has to know of the family (``decode.Serving``)."""
    why = (
        "the kimi_k2 family is served on one device from unquantized "
        "latent pages: no mesh, kv_quant, weight_quant, adapters or "
        "speculative_k"
    )
    return dict(
        dense_cache=False,
        aux_counts=AUX_COUNTS,
        # the tokens each program kind processed; the positions a decode
        # dispatch's rows reach, beside those a gathered window holds
        # whatever their depth (their ratio is the share of the window the
        # kernel path touches)
        counters=("moe_tokens.prefill", "moe_tokens.decode_step",
                  "latent_positions_read", "latent_positions_window"),
        unserved=dict.fromkeys(
            ("mesh", "kv_quant", "weight_quant", "adapters",
             "speculative_k"), why),
    )


def _block(x, bp, cache, layer, pos, tables, live, cfg: ModelConfig,
           experts=None, paged_impl="gather"):
    """One layer over rows x [g, T, E]: returns (x, cache, counts [3]);
    the counts are ``moe_dropless``'s, zero in a dense layer. ``experts``
    = (the expert stacks whole, this layer's index into them)."""
    eps = cfg.layer_norm_epsilon
    a, cache = _attention(
        rms_norm(x, bp["ln_attn"], eps=eps), bp["attn"], cache, layer, pos,
        tables, cfg, paged_impl,
    )
    x = x + a
    m = rms_norm(x, bp["ln_mlp"], eps=eps)
    if "router" not in bp["mlp"]:
        return x + _swiglu(m, bp["mlp"]), cache, jnp.zeros((3,), jnp.int32)
    g, t, e = m.shape
    stacks, local = experts
    y, counts = moe_dropless(
        m.reshape(g * t, e), {**bp["mlp"], **stacks},
        top_k=cfg.num_experts_per_tok, expert_offset=cfg.expert_offset,
        routed_scale=cfg.routed_scaling_factor, activation=jax.nn.silu,
        live=live.reshape(g * t), layer=local,
    )
    return x + y.reshape(g, t, e), cache, counts


def _rows_in_blocks(block, x, cache, pos, tables, live):
    """``block`` over all rows, TOKEN_BLOCK tokens' worth of rows at a time
    (the cache carried from group to group)."""
    b, t = x.shape[:2]
    g = max(k for k in range(1, b + 1)
            if b % k == 0 and (k == 1 or k * t <= TOKEN_BLOCK))
    if g == b:
        return block(x, cache, pos, tables, live)

    def split(a):
        return a.reshape((b // g, g) + a.shape[1:])

    def body(carry, rows):
        cache, counts = carry
        xg, cache, c = block(rows[0], cache, *rows[1:])
        return (cache, counts + c), xg

    (cache, counts), out = jax.lax.scan(
        body, (cache, jnp.zeros((3,), jnp.int32)),
        tuple(split(a) for a in (x, pos, tables, live)),
    )
    return out.reshape(x.shape), cache, counts


def forward(params: Params, input_ids, cfg: ModelConfig, cache: dict, pos,
            block_tables, *, live=None, logits_index=None,
            paged_impl="gather"):
    """T tokens a row at positions pos[b]..pos[b]+T-1 through both stacks
    against the paged latent pool. Returns (logits [B, T, V] — [B, 1, V],
    of position ``logits_index[b]`` of each row, where that is given —,
    cache, counts [3] int32 summed over the expert layers: pairs routed to
    experts held here, rows the expert products ran over, held experts
    hit). ``live`` [B, T] bool marks the entries that are tokens (padding
    and free rows route nowhere and count nothing). ``paged_impl``: how a
    one-token call reads the pool (``attend_absorbed``)."""
    b, t = input_ids.shape
    pos = jnp.asarray(pos, jnp.int32)
    if live is None:
        live = jnp.ones((b, t), jnp.bool_)
    x = embed(params, input_ids, cfg)

    carry = (x, cache, jnp.zeros((3,), jnp.int32))
    ld = cfg.first_k_dense_replace
    for name, first, n in (("dense", 0, ld), ("moe", ld, cfg.n_layer - ld)):
        if not n:
            continue
        # the expert stacks stay out of the scan's slices: a layer's slice
        # of them would be copied whole into the expert loop, hit or not
        mlp = params[name]["mlp"]
        stacks = {k: mlp[k] for k in EXPERT_STACKS if k in mlp}
        sliced = {**params[name],
                  "mlp": {k: v for k, v in mlp.items() if k not in stacks}}

        def block_body(carry, bp, local, first=first, stacks=stacks):
            x, cache, counts = carry
            x, cache, c = _rows_in_blocks(
                lambda *rows: _block(
                    rows[0], bp, rows[1], first + local, *rows[2:], cfg,
                    experts=(stacks, local), paged_impl=paged_impl,
                ),
                x, cache, pos, block_tables, live,
            )
            return x, cache, counts + c

        carry = scan_layers(
            block_body, carry, sliced,
            extras=jnp.arange(n, dtype=jnp.int32), remat_mode="none",
        )
    x, cache, counts = carry
    if logits_index is not None:
        x = jnp.take_along_axis(x, logits_index[:, None, None], axis=1)
    return head(params, x, cfg), cache, counts


def apply(params: Params, input_ids, cfg: ModelConfig, **_unused):
    """[B, T] tokens -> [B, T, V] logits with no cache kept: every row gets
    one page of T positions of a pool made here. For tests and tools; the
    engines call ``forward``."""
    b, t = input_ids.shape
    if t > cfg.n_ctx:
        raise ValueError(f"sequence length {t} exceeds n_ctx {cfg.n_ctx}")
    pool = init_latent_pool(cfg, b + 1, t)
    tables = 1 + jnp.arange(b, dtype=jnp.int32)[:, None]
    logits, _, _ = forward(
        params, input_ids, cfg, pool, jnp.zeros((b,), jnp.int32), tables
    )
    return logits


def embed(params: Params, input_ids, cfg: ModelConfig, **_unused):
    return params["wte"][input_ids].astype(jnp.dtype(cfg.dtype))


def run_blocks(*_args, **_kw):
    raise NotImplementedError(
        "the kimi_k2 family is served only (models/kimi_k2.py): it has no "
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
