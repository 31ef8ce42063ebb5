"""The kimi_k2 family (the DeepSeek-V3 block as Kimi-K2.5 publishes it) in
plain ``jax.numpy``: the benchmark's yardstick for ``correct``.

Imports nothing of the program under test. No cache, no kernels, no
batching: one sequence, full causal attention over its own positions,
float32 with matmuls at ``highest``. It follows HF ``modeling_deepseek.py``
of the DeepSeek-V3 family, which ``kimi_k2`` reuses; ``cfg`` is a
configuration file's ``model`` block (that config.json's keys). Every norm
is RMSNorm with ``rms_norm_eps``; x is [T, E].

- Attention: h = norm(x); c_q = norm(h W_qa); q = c_q W_qb -> H heads of
  (Dn "nope" | Dr "rope"); [c_kv | k_r] = h W_kva (C | Dr); c_kv =
  norm(c_kv); q_r and k_r rotated by YaRN RoPE, k_r ONE head shared by all;
  [k_n | v] = c_kv W_kvb per head; score = s (q_n . k_n + q_r . k_r), s =
  (Dn + Dr)^-1/2 m^2, m = 0.1 mscale_all_dim ln(factor) + 1; causal softmax;
  o = P v, concatenated, through W_o. (The program caches (c_kv, k_r) and
  reads it two ways; here nothing is cached.)
- YaRN: inv_freq = inter (1 - mask) + extra mask; extra = theta^(-2i/Dr),
  inter = extra / factor, mask = 1 - ramp(low, high) over the Dr/2 pairs,
  (low, high) the correction range of (beta_fast, beta_slow, Dr, theta,
  original_max_position_embeddings); cos and sin times
  mscale(factor, mscale) / mscale(factor, mscale_all_dim) (1 as published).
- Layers 0..first_k_dense_replace-1: one SwiGLU of ``intermediate_size``:
  (silu(h W_gate) * h W_up) W_down. Later layers: router in float32, z =
  sigmoid(h W_g) over ALL ``n_routed_experts``; the ``num_experts_per_tok``
  largest of z + b chosen (b the selection bias; n_group = topk_group = 1,
  so no group limit); g_e = z_e / (sum of the chosen z + 1e-20) x
  ``routed_scaling_factor``; y = sum over the chosen e of g_e SwiGLU_e(h) +
  SwiGLU_shared(h), width ``moe_intermediate_size``.
- Final norm, untied head.

Departures from the source, each on purpose:
1. **The share.** ``n_routed_experts_held`` experts, ``expert_offset`` .. +
   held - 1 (offset 0 when the block gives none), are held; the sum over
   chosen experts runs over THOSE only. What the absent experts would add is
   left out, here and in the program alike: one chip's part of an
   expert-parallel layer before its exchange. ``vocab_size`` is the slice of
   the vocabulary held.
2. **RoPE layout.** HF de-interleaves q_r and k_r before ``rotate_half``;
   here the half-split rotation is applied to the projections' outputs as
   they come, i.e. the weights are taken to be stored de-interleaved. Scores
   are invariant under a permutation q_r and k_r share.
3. **The selection bias b is drawn** (float32) so that it changes which
   experts are chosen; the checkpoint's is learned, to even the experts'
   load out over the chips. ``selection_bias``: every chip's share of the
   deployment (a run of ``held`` experts) gets the same ``held`` values, the
   stratified quantiles of normal(0, 0.02), in an order the seed draws. So
   the seed says which experts are favoured, and every chip, on every seed,
   is sent the same share of the pairs (drawn independently an expert, the
   12 held here received 0.85-1.30 times their share by seed, and the time
   of a decode step followed; so drawn, 0.98-1.02: PERF.md section 6).
4. The vision tower and the multi-token-prediction layers (0 published) are
   not part of the language model's forward pass and are absent.
5. No ``train_reference``: the configuration has no training path (at 16
   bytes a parameter no cut inside the guide's floors fits a chip).

The only thing shared with the program is the *layout* of the parameter tree
(``models/kimi_k2.py``'s docstring): two stacks, ``dense`` and ``moe``, of
``ln_attn``, ``attn`` {wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo},
``ln_mlp`` and ``mlp`` ({gate, up, down} | {router, bias, w_gate, w_in,
w_out, shared {gate, up, down}}), beside ``wte``, ``ln_f``, ``lm_head``.
Weights are made HERE from the seed (``init_params``: normal 0.02, or the
block's ``initializer_range`` where a test's stand-in gives one; norms 1),
in one jitted call on the device, in the dtype asked for; the forward pass
upcasts them to float32 a layer (an expert) at a time, so that 7 GB of
bfloat16 weights can be held to a float32 yardstick on a 16 GB chip, and
takes the queries in blocks of rows.

``precision``: ``"f32"`` (the reference), ``"bf16"``, ``"fp8"`` (the
control: every matmul operand rounded to float8_e4m3 under a per-tensor
scale). The router's product stays float32 in every precision: the
configuration states it so.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri

F8_MAX = 448.0  # largest finite float8_e4m3fn
QUERY_BLOCK = 512  # query rows whose scores exist at once


def shapes(cfg: dict) -> dict:
    rs = cfg["rope_scaling"]
    return dict(
        e=cfg["hidden_size"], l=cfg["num_hidden_layers"],
        v=cfg["vocab_size"], h=cfg["num_attention_heads"],
        rq=cfg["q_lora_rank"], c=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], f=cfg["intermediate_size"],
        fm=cfg["moe_intermediate_size"],
        fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        x=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        held=cfg.get("n_routed_experts_held", cfg["n_routed_experts"]),
        offset=cfg.get("expert_offset", 0),
        ld=cfg["first_k_dense_replace"],
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        std=cfg.get("initializer_range", 0.02),
        routed_scale=cfg["routed_scaling_factor"],
        factor=rs["factor"], orig=rs["original_max_position_embeddings"],
        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
        mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"],
    )


def selection_bias(key, n: int, x: int, held: int, std: float = 0.02):
    """[n, x] float32: in every run of ``held`` experts the ``held``
    stratified quantiles of normal(0, std), ordered by ``key`` (docstring,
    departure 3)."""
    if x % held:
        raise ValueError(f"{x} experts are not whole shares of {held}")
    quantiles = ndtri((jnp.arange(held) + 0.5) / held) * std
    order = jnp.argsort(
        jax.random.uniform(key, (n, x // held, held)), axis=-1)
    return quantiles[order].reshape(n, x).astype(jnp.float32)


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted(shapes(cfg).items()))


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _init(key, cfg_key, dtype):
    s = dict(cfg_key)
    e, v, h = s["e"], s["v"], s["h"]
    pdt = jnp.dtype(dtype)

    def normal(k, shape, dt=pdt, std=s["std"]):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def stack(k, n, mlp):
        ks = jax.random.split(k, 6)
        return {
            "ln_attn": {"scale": jnp.ones((n, e), pdt)},
            "attn": {
                "wq_a": normal(ks[0], (n, e, s["rq"])),
                "q_norm": {"scale": jnp.ones((n, s["rq"]), pdt)},
                "wq_b": normal(ks[1], (n, s["rq"], h * (s["dn"] + s["dr"]))),
                "wkv_a": normal(ks[2], (n, e, s["c"] + s["dr"])),
                "kv_norm": {"scale": jnp.ones((n, s["c"]), pdt)},
                "wkv_b": normal(ks[3], (n, s["c"], h * (s["dn"] + s["dv"]))),
                "wo": normal(ks[4], (n, h * s["dv"], e)),
            },
            "ln_mlp": {"scale": jnp.ones((n, e), pdt)},
            "mlp": mlp(ks[5], n),
        }

    def dense_mlp(k, n):
        ks = jax.random.split(k, 3)
        return {"gate": normal(ks[0], (n, e, s["f"])),
                "up": normal(ks[1], (n, e, s["f"])),
                "down": normal(ks[2], (n, s["f"], e))}

    def moe_mlp(k, n):
        ks = jax.random.split(k, 8)
        held, fm, fs = s["held"], s["fm"], s["fs"]
        return {
            "router": normal(ks[0], (n, e, s["x"])),
            "bias": selection_bias(ks[1], n, s["x"], held),
            "w_gate": normal(ks[2], (n, held, e, fm)),
            "w_in": normal(ks[3], (n, held, e, fm)),
            "w_out": normal(ks[4], (n, held, fm, e)),
            "shared": {"gate": normal(ks[5], (n, e, fs)),
                       "up": normal(ks[6], (n, e, fs)),
                       "down": normal(ks[7], (n, fs, e))},
        }

    ks = jax.random.split(key, 4)
    return {
        "wte": normal(ks[0], (v, e)),
        "dense": stack(ks[1], s["ld"], dense_mlp),
        "moe": stack(ks[2], s["l"] - s["ld"], moe_mlp),
        "ln_f": {"scale": jnp.ones((e,), pdt)},
        "lm_head": normal(ks[3], (e, v)),
    }


def init_params(seed: int, cfg: dict, dtype: str = "float32"):
    """Seeded random weights, made on the default device in one jitted call.
    ``seed`` may exceed 2**31: it is folded in two 31-bit halves."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return _init(key, _cfg_key(cfg), dtype)


# -- arithmetic ---------------------------------------------------------------


def _quant(x, precision: str):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(
        spec, _quant(a, precision), _quant(b, precision),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _f32(tree):
    return jax.tree.map(lambda p: p.astype(jnp.float32), tree)


def _rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(s: dict) -> tuple[int, int]:
    def dim_of(rotations):
        return s["dr"] * math.log(
            s["orig"] / (rotations * 2 * math.pi)) / (2 * math.log(s["theta"]))

    low, high = math.floor(dim_of(s["beta_fast"])), math.ceil(dim_of(s["beta_slow"]))
    return max(low, 0), min(high, s["dr"] - 1)


def yarn_inv_freq(s: dict):
    i = jnp.arange(s["dr"] // 2, dtype=jnp.float32)
    extra = s["theta"] ** (-2.0 * i / s["dr"])
    inter = extra / s["factor"]
    low, high = yarn_correction_range(s)
    mask = 1.0 - jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return inter * (1.0 - mask) + extra * mask


def softmax_scale(s: dict) -> float:
    m = yarn_mscale(s["factor"], s["mscale_all_dim"])
    return (s["dn"] + s["dr"]) ** -0.5 * m * m


def _rope(x, cos, sin):
    """x [T, ..., Dr], angles [T, Dr]: x cos + rotate_half(x) sin."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    return x * cos.reshape(shape) + rot * sin.reshape(shape)


def _attention(x, ap, s: dict, precision: str):
    t = x.shape[0]
    h, c, dn, dr, dv = s["h"], s["c"], s["dn"], s["dr"], s["dv"]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_inv_freq(s)
    angles = jnp.concatenate([angles, angles], axis=-1)
    ratio = yarn_mscale(s["factor"], s["mscale"]) / yarn_mscale(
        s["factor"], s["mscale_all_dim"])
    cos, sin = jnp.cos(angles) * ratio, jnp.sin(angles) * ratio

    cq = _rms_norm(_mm("te,er->tr", x, ap["wq_a"], precision),
                   ap["q_norm"], s["eps"])
    q = _mm("tr,rf->tf", cq, ap["wq_b"], precision).reshape(t, h, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], cos, sin)
    kv_a = _mm("te,ef->tf", x, ap["wkv_a"], precision)
    c_kv = _rms_norm(kv_a[:, :c], ap["kv_norm"], s["eps"])
    k_r = _rope(kv_a[:, c:], cos, sin)  # one head, shared
    kv = _mm("tc,cf->tf", c_kv, ap["wkv_b"], precision).reshape(t, h, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = softmax_scale(s)

    qb = min(QUERY_BLOCK, t)
    pad = -t % qb  # queries in blocks of rows: the scores of one block

    def block(args):
        qn_b, qr_b, first = args
        sc = (_mm("qhd,khd->hqk", qn_b, k_n, precision)
              + _mm("qhr,kr->hqk", qr_b, k_r, precision)) * scale
        qpos = first + jnp.arange(qb)
        sc = jnp.where(jnp.arange(t)[None, None, :] <= qpos[None, :, None],
                       sc, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision)

    def blocks_of(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, qb) + a.shape[1:])

    firsts = jnp.arange(0, t + pad, qb)
    o = jax.lax.map(block, (blocks_of(q_n), blocks_of(q_r), firsts))
    o = o.reshape(t + pad, h * dv)[:t]
    return _mm("tf,fe->te", o, ap["wo"], precision)


def _swiglu(x, p, precision: str, names=("gate", "up", "down")):
    g = jax.nn.silu(_mm("te,ef->tf", x, p[names[0]], precision))
    u = _mm("te,ef->tf", x, p[names[1]], precision)
    return _mm("tf,fe->te", g * u, p[names[2]], precision)


def route(h, mp, s: dict):
    """(idx [T, k], gates [T, k]) in float32, as published."""
    z = jax.nn.sigmoid(jnp.einsum(
        "te,ex->tx", h, mp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(z + mp["bias"].astype(jnp.float32), s["k"])
    chosen = jnp.take_along_axis(z, idx, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, gates * s["routed_scale"]


def expert_layer(h, mp, s: dict, precision: str = "f32"):
    """The expert layer's output for normed h [T, E]: the held experts'
    part (mp's expert stacks hold experts offset..offset+held-1) plus the
    shared expert. ``mp`` leaves are one layer's; the experts are upcast
    one at a time."""
    idx, gates = route(h, mp, s)

    def one_expert(y, xs):
        e, w = xs
        g_e = jnp.sum(jnp.where(idx == s["offset"] + e, gates, 0.0), axis=-1)
        out = _swiglu(h, _f32(w), precision, ("w_gate", "w_in", "w_out"))
        return y + g_e[:, None] * out, None

    held = mp["w_in"].shape[0]
    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(held), {k: mp[k] for k in ("w_gate", "w_in", "w_out")}))
    return y + _swiglu(h, _f32(mp["shared"]), precision)


def _block(x, bp, s: dict, precision: str):
    """One layer; ``bp`` in the dtype it is stored in, upcast here. Returns
    (x, the experts chosen [T, k] with their scores' margin: see
    ``routes``; None in a dense layer)."""
    attn = _f32({k: bp[k] for k in ("ln_attn", "attn", "ln_mlp")})
    x = x + _attention(_rms_norm(x, attn["ln_attn"], s["eps"]), attn["attn"],
                       s, precision)
    h = _rms_norm(x, attn["ln_mlp"], s["eps"])
    if "router" in bp["mlp"]:
        return x + expert_layer(h, bp["mlp"], s, precision), route(
            _quant(h, precision), bp["mlp"], s)[0]
    return x + _swiglu(h, _f32(bp["mlp"]), precision), None


def _layers(params, ids, cfg: dict, precision: str):
    """(final-norm hidden states [T, E], experts chosen [Le, T, k])."""
    s = shapes(cfg)
    x = params["wte"][ids].astype(jnp.float32)
    chosen = None
    for name in ("dense", "moe"):
        if jax.tree.leaves(params[name])[0].shape[0]:
            x, chosen = jax.lax.scan(
                lambda x, bp: _block(x, bp, s, precision), x, params[name])
    return _rms_norm(x, _f32(params["ln_f"]), s["eps"]), chosen


def hidden(params, ids, cfg: dict, precision: str = "f32"):
    """[T] ids -> final-norm hidden states [T, E] (float32)."""
    return _layers(params, ids, cfg, precision)[0]


def routes(params, ids, cfg: dict, precision: str = "f32"):
    """[T] ids -> the experts each expert layer's router chose, [Le, T, k].
    In a lower precision the router (float32 in every precision) sees the
    normed input ROUNDED to it, as a program that keeps its activations in
    that precision shows it: ``tools/kimi_k2_ties.py`` counts how often the
    choice then differs from the float32 one."""
    return _layers(params, ids, cfg, precision)[1]


def logits(params, ids, cfg: dict, precision: str = "f32"):
    """[B, T] ids -> [B, T, V] float32 logits, a row at a time."""
    head = params["lm_head"].astype(jnp.float32)
    return jax.lax.map(
        lambda row: _mm("te,ev->tv", hidden(params, row, cfg, precision),
                        head, precision), ids)


def logits_at(params, ids, first, n: int, cfg: dict, precision: str = "f32"):
    """Logits [n, V] of row 0 at positions first..first+n-1 only (a served
    request needs the head where its tokens were chosen)."""
    x = hidden(params, ids[0], cfg, precision)
    x = jax.lax.dynamic_slice_in_dim(x, first, n, axis=0)
    return _mm("te,ev->tv", x, params["lm_head"].astype(jnp.float32), precision)


def leaf_norms(tree) -> dict[str, float]:
    """{'/'-joined path: L2 norm} of every leaf, read back in one transfer."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    names = ["/".join(str(getattr(k, "key", getattr(k, "name", k)))
                      for k in path) for path, _ in flat]
    norms = jax.device_get([_l2(x) for _, x in flat])
    return {n: float(v) for n, v in zip(names, norms)}


@jax.jit
def _l2(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
