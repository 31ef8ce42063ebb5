"""The mellum family (Mellum 2: sliding-window and full attention layers in a
repeating pattern, softmax-routed experts) in plain ``jax.numpy``: the
benchmark's yardstick for ``correct``.

Imports nothing of the program under test. No cache, no pages, no batching:
one sequence, float32 with matmuls at ``highest``. ``cfg`` is a configuration
file's ``model`` block (the published config.json's keys). Every norm is
RMSNorm with ``rms_norm_eps``; no bias anywhere; x is [T, E].

- x = wte[ids]. Layer i, by ``layer_types[i]``: x = x + Attn(norm(x));
  x = x + Experts(norm(x)). Logits = norm(x) lm_head (untied).
- Attention: H = ``num_attention_heads`` heads of D = ``head_dim`` (NOT
  hidden / H) and ``num_key_value_heads`` K/V heads; q and k rotated over
  all D, half-split (rotate_half([x1, x2]) = [-x2, x1]), by the table of the
  layer's kind in ``rope_parameters``: "sliding_attention" the plain
  inv_freq = theta^(-2i/D); "full_attention" YaRN as HF
  ``_compute_yarn_parameters`` has it (inv_freq blended between theta^(-2i/D)
  and the same over ``factor`` by a linear ramp between the correction dims of
  ``beta_fast`` and ``beta_slow`` over ``original_max_position_embeddings``,
  floored and ceiled), cos and sin times ``attention_factor``, so q . k
  carries its square. score = D^-1/2 q . k over keys j <= i and, in a
  sliding layer, j > i - ``sliding_window``; softmax; W_o.
- Experts: p = softmax(h W_router) over all ``num_experts``; the
  ``num_experts_per_tok`` largest are chosen; with ``norm_topk_prob`` the
  gates are the chosen p over their sum; out = sum_e g_e (silu(h Wgate_e) *
  (h Win_e)) Wout_e, computed here for EVERY expert over every token with the
  gate 0 where the expert was not chosen (the program sorts pairs into
  blocks, which this must not share). No shared expert.

What the config has no key for is not built, and is listed under ``assumed``
in the configuration file: no query/key norm, no routed scale, no expert
groups, no selection bias, "softmax, then top-k, then renormalise".
``max_window_layers``, ``use_sliding_window`` and ``intermediate_size`` are
held and unused (every entry of ``mlp_layer_types`` is "sparse").

The only thing shared with the program is the *layout* of the parameter tree
(``models/mellum.py``'s docstring): two stacks, ``sliding_attention`` and
``full_attention``, a layer's entry in its kind's stack being its rank among
the layers of that kind; beside ``wte``, ``lm_head`` and ``ln_f``. Weights
are made HERE from the seed (``init_params``), in one jitted call on the
device, in the dtype asked for: matrices normal 0.02 (or the block's
``initializer_range`` where a test's stand-in gives one), norms 1; an expert
stack is drawn a layer at a time, so no float32 copy of a stack exists.

The forward pass upcasts the weights to float32 a layer, and in the expert
sum an expert, at a time (sliced from the stacks where they are used), and
takes the attention's queries in blocks of rows, so that 10.9 GB of bfloat16
weights can be held to a float32 yardstick at 8,192 positions on a 16 GB
chip.

``precision``: ``"f32"`` (the reference), ``"bf16"``, ``"fp8"`` (the
control: every matmul operand, the router's among them, rounded to
float8_e4m3 under a per-tensor scale).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn
QUERY_BLOCK = 256  # query rows whose scores exist at once
SLIDING, FULL = "sliding_attention", "full_attention"
KINDS = (SLIDING, FULL)


def shapes(cfg: dict) -> dict:
    rp = cfg["rope_parameters"]
    full = rp[FULL]
    return dict(
        e=cfg["hidden_size"], v=cfg["vocab_size"],
        h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        d=cfg["head_dim"], x=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        window=cfg["sliding_window"], types=tuple(cfg["layer_types"]),
        eps=cfg["rms_norm_eps"], norm_topk=bool(cfg["norm_topk_prob"]),
        std=cfg.get("initializer_range", 0.02),
        theta_sliding=float(rp[SLIDING]["rope_theta"]),
        theta_full=float(full["rope_theta"]),
        yarn=(full["rope_type"] == "yarn"),
        factor=float(full.get("factor", 1.0)),
        original=int(full.get("original_max_position_embeddings", 0)),
        beta_fast=float(full.get("beta_fast", 32)),
        beta_slow=float(full.get("beta_slow", 1)),
        attention_factor=float(full.get("attention_factor", 1.0)),
    )


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted(shapes(cfg).items()))


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _init(key, cfg_key, dtype):
    s = dict(cfg_key)
    e, v, d, x, f = s["e"], s["v"], s["d"], s["x"], s["f"]
    hd, hkv = s["h"] * d, s["hkv"] * d
    pdt = jnp.dtype(dtype)

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * s["std"]).astype(pdt)

    def by_layer(k, n, shape):
        return jax.lax.map(lambda k1: normal(k1, shape), jax.random.split(k, n))

    def layers(k, n):
        ks = jax.random.split(k, 8)
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
        SLIDING: layers(ks[1], s["types"].count(SLIDING)),
        FULL: layers(ks[2], s["types"].count(FULL)),
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


def inv_freq(s: dict, kind: str):
    """[D/2] float32 rotary frequencies of a layer kind, and the factor its
    cos and sin carry."""
    d = s["d"]
    idx = jnp.arange(0, d, 2, dtype=jnp.float32) / d
    if kind == SLIDING or not s["yarn"]:
        theta = s["theta_sliding"] if kind == SLIDING else s["theta_full"]
        return 1.0 / theta ** idx, 1.0
    theta, orig = s["theta_full"], s["original"]
    pos_freqs = theta ** idx
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (
        s["factor"] * pos_freqs)

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(s["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(s["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    extrapolation_factor = 1.0 - ramp
    return (interpolation * (1.0 - extrapolation_factor)
            + extrapolation * extrapolation_factor), s["attention_factor"]


def _rotate(x, cos, sin):
    """x [T, heads, D] rotated, half-split."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _attention(h, ap, kind: str, s: dict, precision: str):
    t = h.shape[0]
    heads, hkv, d = s["h"], s["hkv"], s["d"]
    q = _mm("te,ef->tf", h, ap["wq"], precision).reshape(t, heads, d)
    k = _mm("te,ef->tf", h, ap["wk"], precision).reshape(t, hkv, d)
    v = _mm("te,ef->tf", h, ap["wv"], precision).reshape(t, hkv, d)
    freqs, factor = inv_freq(s, kind)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    k = jnp.repeat(k, heads // hkv, axis=1)
    v = jnp.repeat(v, heads // hkv, axis=1)
    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    kpos = jnp.arange(t)

    def block(args):
        q_blk, first = args
        sc = _mm("qhd,shd->hqs", q_blk, k, precision) * d ** -0.5
        qpos = first + jnp.arange(qb)
        seen = kpos[None, :] <= qpos[:, None]
        if kind == SLIDING:
            seen &= kpos[None, :] > qpos[:, None] - s["window"]
        sc = jnp.where(seen[None], sc, -1e30)
        return _mm("hqs,shd->qhd", jax.nn.softmax(sc, axis=-1), v, precision)

    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, heads, d)
    o = jax.lax.map(block, (qp, jnp.arange(qp.shape[0]) * qb))
    o = o.reshape(-1, heads * d)[:t]
    return _mm("tf,fe->te", o, ap["wo"], precision)


def route(h, router, s: dict, precision: str):
    """[T, X] float32 gates: softmax over all experts, the top k kept (and,
    with ``norm_topk_prob``, renormalised), 0 elsewhere."""
    p = jax.nn.softmax(_mm("te,ex->tx", h, router, precision), axis=-1)
    chosen, idx = jax.lax.top_k(p, s["k"])
    if s["norm_topk"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, idx].set(chosen)


def _experts(h, router, stacks, layer, s: dict, precision: str):
    """sum_e g_e (silu(h Wgate_e) * (h Win_e)) Wout_e; ``stacks`` are the
    kind's whole expert stacks [l, X, ...] as stored, and expert e of layer
    ``layer`` is sliced out and upcast where it is used."""
    gates = route(h, router, s, precision)

    def one(out, e):
        w = {n: jax.lax.dynamic_slice(
            a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(
                jnp.float32) for n, a in stacks.items()}
        g = jax.nn.silu(_mm("te,ef->tf", h, w["w_gate"], precision))
        u = _mm("te,ef->tf", h, w["w_in"], precision)
        y = _mm("tf,fe->te", g * u, w["w_out"], precision)
        gate = jax.lax.dynamic_slice_in_dim(gates, e, 1, axis=1)
        return out + gate * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), jnp.arange(s["x"], dtype=jnp.int32))
    return out


def _layer(x, params, kind: str, layer, s: dict, precision: str):
    """Layer ``layer`` of ``kind``'s stack; its weights are read from the
    stacks in the dtype they are stored in and upcast here."""
    stack = params[kind]
    mlp = stack["mlp"]
    bp = _f32(jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        {"ln_attn": stack["ln_attn"], "attn": stack["attn"],
         "ln_mlp": stack["ln_mlp"], "router": mlp["router"]}))
    x = x + _attention(
        _rms_norm(x, bp["ln_attn"], s["eps"]), bp["attn"], kind, s, precision)
    stacks = {n: mlp[n] for n in ("w_gate", "w_in", "w_out")}
    return x + _experts(
        _rms_norm(x, bp["ln_mlp"], s["eps"]), bp["router"], stacks, layer, s,
        precision)


def period_of(types: tuple) -> tuple:
    """The shortest run of kinds that ``types`` repeats."""
    return next(types[:n] for n in range(1, len(types) + 1)
                if len(types) % n == 0 and types == types[:n] * (len(types) // n))


def hidden(params, ids, cfg: dict, precision: str = "f32"):
    """[T] ids -> final-norm hidden states [T, E] (float32). The layers run
    in ``layer_types``' order, a period of the pattern an iteration of one
    loop (so that 12 layers compile as 4)."""
    s = shapes(cfg)
    period = period_of(s["types"])
    n_periods = len(s["types"]) // len(period)

    def one_period(i, x):
        seen = dict.fromkeys(KINDS, 0)
        for kind in period:
            layer = i * period.count(kind) + seen[kind]
            seen[kind] += 1
            x = _layer(x, params, kind, layer, s, precision)
        return x

    x = params["wte"][ids].astype(jnp.float32)
    x = jax.lax.fori_loop(0, n_periods, one_period, x)
    return _rms_norm(x, _f32(params["ln_f"]), s["eps"])


def _head(x, params, precision: str):
    return _mm("te,ev->tv", x, params["lm_head"].astype(jnp.float32),
               precision)


def logits(params, ids, cfg: dict, precision: str = "f32"):
    """[B, T] ids -> [B, T, V] float32 logits, a row at a time."""
    return jax.lax.map(
        lambda row: _head(hidden(params, row, cfg, precision), params,
                          precision), ids)


def logits_at(params, ids, first, n: int, cfg: dict, precision: str = "f32"):
    """Logits [n, V] of row 0 at positions first..first+n-1 only (a served
    request needs the head where its tokens were chosen)."""
    x = hidden(params, ids[0], cfg, precision)
    x = jax.lax.dynamic_slice_in_dim(x, first, n, axis=0)
    return _head(x, params, precision)


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
