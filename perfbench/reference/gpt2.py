"""GPT-2 in plain ``jax.numpy``: the benchmark's yardstick for ``correct``.

Imports nothing of the program under test. It follows Radford et al. 2019
as HF ``GPT2LMHeadModel`` implements it: learned positions, pre-norm blocks
with a merged QKV projection, tanh-gelu MLP, final norm, head tied to the
token embedding, mean next-token cross-entropy; AdamW as ``torch.optim.AdamW``
(decoupled decay on every leaf, bias-corrected moments).

The only thing shared with the program is the *layout* of the parameter
tree (that is the program's interface; see ``models/gpt2.py``'s docstring):

    wte [V,E]  wpe [C,E]  ln_f {scale,bias}[E]
    blocks/ln_1, blocks/ln_2 {scale,bias}[L,E]
    blocks/attn/c_attn {kernel[L,E,3,H,D], bias[L,3,H,D]}
    blocks/attn/c_proj {kernel[L,E,E], bias[L,E]}
    blocks/mlp/c_fc {kernel[L,E,F], bias[L,F]}   blocks/mlp/c_proj {kernel[L,F,E], bias[L,E]}

Weights are made HERE from the seed (``init_params``), in one jitted call on
the device; the harness hands them to the program, and the reference makes
them again for itself after the window. Nothing the program computed is read.

``precision``:
- ``"f32"``: float32 everywhere, matmuls at ``highest`` (the reference).
- ``"bf16"``: matmul operands rounded to bfloat16 (what the configurations
  state; used by tests to see that the limits admit it).
- ``"fp8"``: matmul operands rounded to float8_e4m3 with a per-tensor scale
  (the *control*: the nearest precision below bf16, the step that would
  tempt a later PR). Straight-through in the backward pass.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn


def shapes(cfg: dict) -> dict:
    e, l, v, c = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], cfg["n_positions"]
    h = cfg["n_head"]
    d = e // h
    f = cfg.get("n_inner") or 4 * e
    return dict(e=e, l=l, v=v, c=c, h=h, d=d, f=f)


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _init(key, cfg_key, dtype):
    cfg = dict(cfg_key)
    s = shapes(cfg)
    e, l, v, c, f, h, d = (s[k] for k in "elvcfhd")
    std = cfg.get("initializer_range", 0.02)
    pdt = jnp.dtype(dtype)
    ks = jax.random.split(key, 6)

    def normal(k, shape, sd):
        return (jax.random.normal(k, shape, jnp.float32) * sd).astype(pdt)

    def ln(shape):
        return {"scale": jnp.ones(shape, pdt), "bias": jnp.zeros(shape, pdt)}

    return {
        "wte": normal(ks[0], (v, e), std),
        "wpe": normal(ks[1], (c, e), std / 2),
        "blocks": {
            "ln_1": ln((l, e)),
            "attn": {
                "c_attn": {"kernel": normal(ks[2], (l, e, 3, h, d), std),
                           "bias": jnp.zeros((l, 3, h, d), pdt)},
                "c_proj": {"kernel": normal(ks[3], (l, e, e), std),
                           "bias": jnp.zeros((l, e), pdt)},
            },
            "ln_2": ln((l, e)),
            "mlp": {
                "c_fc": {"kernel": normal(ks[4], (l, e, f), std),
                         "bias": jnp.zeros((l, f), pdt)},
                "c_proj": {"kernel": normal(ks[5], (l, f, e), std),
                           "bias": jnp.zeros((l, e), pdt)},
            },
        },
        "ln_f": ln((e,)),
    }


def _cfg_key(cfg: dict) -> tuple:
    keep = ("n_embd", "n_layer", "n_head", "n_positions", "vocab_size",
            "n_inner", "initializer_range")
    return tuple((k, cfg[k]) for k in keep if cfg.get(k) is not None)


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
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / F8_MAX + 1e-30)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)  # straight-through


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(
        spec, _quant(a, precision), _quant(b, precision),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, bp, eps, precision):
    b, t, e = x.shape
    a = _layer_norm(x, bp["ln_1"], eps)
    qkv = _mm("bte,eshd->btshd", a, bp["attn"]["c_attn"]["kernel"], precision)
    qkv = qkv + bp["attn"]["c_attn"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,T,H,D]
    d = q.shape[-1]
    s = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", w, v, precision).reshape(b, t, e)
    o = _mm("bte,ef->btf", o, bp["attn"]["c_proj"]["kernel"], precision)
    x = x + o + bp["attn"]["c_proj"]["bias"]
    m = _layer_norm(x, bp["ln_2"], eps)
    m = _mm("bte,ef->btf", m, bp["mlp"]["c_fc"]["kernel"], precision)
    m = _gelu_new(m + bp["mlp"]["c_fc"]["bias"])
    m = _mm("btf,fe->bte", m, bp["mlp"]["c_proj"]["kernel"], precision)
    return x + m + bp["mlp"]["c_proj"]["bias"]


def hidden(params, ids, cfg: dict, precision: str = "f32"):
    """[B,T] ids -> final-norm hidden states [B,T,E] (float32)."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    t = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][:t]

    @jax.checkpoint
    def body(x, bp):
        return _block(x, bp, eps, precision), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return _layer_norm(x, params["ln_f"], eps)


def logits(params, ids, cfg: dict, precision: str = "f32"):
    """[B,T] ids -> [B,T,V] float32 logits (head tied to wte)."""
    x = hidden(params, ids, cfg, precision)
    return _mm("bte,ve->btv", x, params["wte"].astype(jnp.float32), precision)


def logits_at(params, ids, first, n: int, cfg: dict, precision: str = "f32"):
    """Logits [n,V] of row 0 at positions first..first+n-1 only (the head is
    the widest matmul; a served request needs it where tokens were chosen)."""
    x = hidden(params, ids, cfg, precision)[0]
    x = jax.lax.dynamic_slice_in_dim(x, first, n, axis=0)
    return _mm("te,ve->tv", x, params["wte"].astype(jnp.float32), precision)


def loss_sum(params, ids, targets, cfg: dict, precision: str = "f32"):
    """Summed (not mean) next-token cross-entropy over the rows given."""
    lg = logits(params, ids, cfg, precision)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _rows_loss_and_grad(params, ids, targets, cfg_key, precision):
    return jax.value_and_grad(loss_sum)(
        params, ids, targets, dict(cfg_key), precision)


def loss_and_grad(params, ids, targets, cfg: dict, *, precision="f32",
                  rows_per_block: int = 2):
    """Mean loss over ALL rows of the batch and its gradient, computed in
    blocks of rows so that it fits beside nothing else on a 16 GB chip."""
    n_rows, t = ids.shape
    total = n_rows * t
    loss = jnp.zeros((), jnp.float32)
    grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    key = _cfg_key(cfg) + (("layer_norm_epsilon",
                            cfg.get("layer_norm_epsilon", 1e-5)),)
    for i in range(0, n_rows, rows_per_block):
        l_i, g_i = _rows_loss_and_grad(
            params, ids[i:i + rows_per_block], targets[i:i + rows_per_block],
            key, precision)
        loss = loss + l_i
        grads = jax.tree.map(jnp.add, grads, g_i)
    scale = 1.0 / total
    return loss * scale, jax.tree.map(lambda g: g * scale, grads)


# -- AdamW --------------------------------------------------------------------


def learning_rate(opt: dict, step: int) -> float:
    """Learning rate for update number ``step`` (0-based), per the traffic
    file's ``optimizer`` block: torch ``CosineAnnealingLR`` or constant."""
    peak = opt["learning_rate"]
    if opt.get("lr_schedule", "cosine") == "constant":
        return peak
    floor = opt.get("min_lr_ratio", 0.1) * peak
    frac = min(step / max(opt["schedule_steps"], 1), 1.0)
    return floor + (peak - floor) * 0.5 * (1.0 + math.cos(math.pi * frac))


@jax.jit
def _adamw(params, mu, nu, grads, lr, b1, b2, eps, wd, bc1, bc2):
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
    new = jax.tree.map(
        lambda p, m, n: p - lr * ((m / bc1) / (jnp.sqrt(n / bc2) + eps) + wd * p),
        params, mu, nu)
    return new, mu, nu


def adamw_step(params, mu, nu, grads, opt: dict, step: int):
    b1, b2 = opt.get("beta1", 0.9), opt.get("beta2", 0.999)
    return _adamw(
        params, mu, nu, grads, learning_rate(opt, step), b1, b2,
        opt.get("eps", 1e-8), opt.get("weight_decay", 0.1),
        1 - b1 ** (step + 1), 1 - b2 ** (step + 1))


# The merged QKV projection is one leaf of three matrices; its key third has
# a bias whose gradient is nought under softmax. Norms are taken per third so
# that the comparison's rule on small gradients can see that.
QKV_AXIS = {"blocks/attn/c_attn/kernel": 2, "blocks/attn/c_attn/bias": 1}


def leaf_norms(tree) -> dict[str, float]:
    """{'/'-joined path: L2 norm} of every leaf (the QKV leaves per third),
    read back to the host in one transfer."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    names, parts = [], []
    for path, x in flat:
        name = "/".join(
            str(getattr(k, "key", getattr(k, "name", k))) for k in path)
        if name in QKV_AXIS:
            for i, third in enumerate("qkv"):
                names.append(f"{name}.{third}")
                parts.append(jnp.take(x, i, axis=QKV_AXIS[name]))
        else:
            names.append(name)
            parts.append(x)
    norms = jax.device_get([_l2(x) for x in parts])
    return {n: float(v) for n, v in zip(names, norms)}


@jax.jit
def _l2(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def train_reference(seed: int, cfg: dict, opt: dict, batches, *,
                    precision: str = "f32", rows_per_block: int = 2,
                    fault: str | None = None, against=None,
                    keep_grad: bool = False) -> dict:
    """Follow the first ``len(batches)`` training steps from the seed.

    Returns the numbers the comparison reads: each step's loss, the per-leaf
    norm of the first gradient, and the per-leaf norm of the parameters'
    change after the last step. With ``against`` (another side's first
    gradient, a tree of the same layout) also the per-leaf norm of the
    DIFFERENCE of the two first gradients: rounding moves a norm by its
    square and a direction by itself, so this is the number that sees
    precision. ``keep_grad`` returns the first gradient itself as well.
    ``fault`` plants one of the faults the training cell can have (used to
    set the limits, never in a run): ``half_batch`` takes the mean over the
    first half of the rows.
    """
    params0 = init_params(seed, cfg, "float32")
    params = params0
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms, extra = [], None, {}
    for step, (ids, targets) in enumerate(batches):
        ids, targets = jnp.asarray(ids), jnp.asarray(targets)
        if fault == "half_batch":
            half = ids.shape[0] // 2
            ids, targets = ids[:half], targets[:half]
        loss, grads = loss_and_grad(
            params, ids, targets, cfg, precision=precision,
            rows_per_block=rows_per_block)
        if step == 0:
            grad_norms = leaf_norms(grads)
            if against is not None:
                extra["grad_diff_norms"] = leaf_norms(jax.tree.map(
                    lambda g, a: g - jnp.asarray(a, jnp.float32),
                    grads, against))
            if keep_grad:
                extra["first_grad"] = grads
        losses.append(float(loss))
        if fault != "unchanged_state":
            params, mu, nu = adamw_step(params, mu, nu, grads, opt, step)
        del grads
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, params0))
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta,
            **extra}
