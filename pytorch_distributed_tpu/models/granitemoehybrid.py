"""The granitemoehybrid family: Granite 4.0-H's block, served through the
paged engine with a recurrent state a row beside the paged KV.

Pre-norm (RMSNorm) layers that mix tokens by a Mamba-2 mixer or by causal
attention, as ``layer_types`` says, each followed by one SwiGLU
feed-forward; no position encoding anywhere (``position_embedding_type``
"nope"); the four Granite multipliers (embedding, residual, attention,
logits); tied head. The equations follow HF ``modeling_granitemoehybrid.py``
(whose mixer is ``modeling_bamba.py``'s Mamba-2);
``perfbench/reference/granitemoehybrid.py`` is the plain float32 statement
of the same, and the tests hold this file to it.

    x = wte[ids] * embedding_multiplier
    x = x + residual_multiplier * Mix(norm(x))         Mix: Mamba2 | Attn
    x = x + residual_multiplier * (silu(g) * u) W_out  [g | u] = norm(x) W_in
    logits = norm(x) wte^T / logits_scaling

Attention: H query heads and Hkv key/value heads of D, no bias, no rotary;
score = attention_multiplier * q . k (NOT D^-1/2), causal softmax in float32.
Mamba-2 (``ops/ssm.py``): [z | xBC | dt] = h W_inproj (Di | Di + 2 G N | Hm;
stored as two matrices, [z | xBC] and dt: 2 Di + 2 G N is whole lanes at the
published sizes, and the Hm columns after it make the chip's compiler copy the
whole stack, 1.25 GB a dispatch, into another layout);
xBC = silu(causal_conv(xBC)) with the previous K-1 positions of the SAME
sequence as left context; [x | B | C] = Di | G N | G N; dt = softplus(dt +
dt_bias); A = -exp(A_log); S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;
y_t = S_t C_t + D x_t; y = norm(y * silu(z)) (the gate first, then the norm
over all Di); out = y W_outproj.

**The layer pattern** is one period repeated (``layer_period``: for
granite-4.0-h-micro ``[m m m m m a m m m m]`` x 4), so the parameters are TWO
stacks, one a kind, and ONE ``scan_layers`` over the periods whose body runs
the period's layers in order; layer j of kind k in period i is entry
``i * (k's layers a period) + j`` of k's stack, read from the whole stack by
that index (the scan carries the period's number, not a slice of weights):

  wte [V, E]   ln_f {scale [E]}
  mamba/ (Lm layers)                       attention/ (La layers)
    ln_mix, ln_mlp {scale [l, E]}            ln_mix, ln_mlp {scale [l, E]}
    mixer/ in_proj [l, E, 2 Di + 2 G N]      attn/ wq [l, E, H D]
           dt_proj [l, E, Hm]                      wk, wv [l, E, Hkv D]
           conv_w [l, K, Di + 2 G N]               wo [l, H D, E]
           conv_b [l, Di + 2 G N]
           dt_bias, A_log, D [l, Hm] (float32)
           norm {scale [l, Di]}   out_proj [l, Di, E]
    mlp/ w_in [l, E, 2 F]   w_out [l, F, E]   mlp/ the same

**Two kinds of cache ride the scan's carry**, each addressed by the layer's
index in its OWN stack:

  k, v  [La, P, page, Hkv D]       the dense families' paged pool, over the
                                   attention layers only; grows with depth
  ssm   [Lm, rows + 1, Hm, Dh, N]  float32: the recurrent state, per ROW
  conv  [Lm, K-1, rows + 1, Di + 2 G N]  the convolution's tail, per row
                                   (the rows second-minor: the chip's
                                   compiler keeps the leaf so, and converted
                                   a [.., rows + 1, K-1, ..] one at every
                                   program's entry and exit)

Row ``rows`` is the scratch row (as page 0 is the scratch page): a prefill
group's padding points there. A call names each batch row's state row
(``state_rows``; left out, batch row b is state row b: the decode step).
A row whose call starts at position 0 starts from ZERO state and tail inside
the program, so a slot reused by the next request never sees the last one's;
an entry whose ``live`` is false (a padded tail, a free or mid-prefill decode
lane) leaves state and tail as they were. The decode step built for a TPU
advances ``ssm`` through ops/ssm_kernel.py (``paged_impl`` "kernel": one pass
over each live row's state where it lies in the leaf, y from the same pass);
every other call, and every call off the chip, through ``ops/ssm.py``.

Served only: no training path (the backward of the chunked scan is not
built), so ``apply`` is the cache-free forward for tests and tools and there
is no ``run_blocks``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.ops.layer_scan import scan_layers
from pytorch_distributed_tpu.ops.layers import rms_norm
from pytorch_distributed_tpu.ops.ssm import (
    causal_conv,
    ssd_chunked,
    ssm_step,
)

Params = dict[str, Any]

KINDS = ("mamba", "attention")
TOKEN_BLOCK = 1024  # tokens a layer processes at once
# what ``forward`` counts, once a call: the entries that were tokens (a
# state advanced over them) and the entries computed over
AUX_COUNTS = ("ssm_tokens_live", "ssm_tokens_computed")


def layer_period(cfg: ModelConfig) -> tuple[str, ...]:
    """The shortest run of kinds that ``layer_types`` repeats."""
    types = cfg.layer_types
    for n in range(1, len(types) + 1):
        if len(types) % n == 0 and types == types[:n] * (len(types) // n):
            return types[:n]
    raise ValueError("granitemoehybrid: layer_types is empty")


def n_layers_of(cfg: ModelConfig, kind: str) -> int:
    return cfg.layer_types.count(kind)


def conv_width(cfg: ModelConfig) -> int:
    """The channels the convolution runs over: x, B and C."""
    return (cfg.mamba_n_heads * cfg.mamba_d_head
            + 2 * cfg.mamba_n_groups * cfg.mamba_d_state)


def state_bytes_per_row(cfg: ModelConfig) -> int:
    """Bytes of recurrent state one row holds over all Mamba layers, whatever
    its depth: the float32 state and the convolution's tail."""
    ssm = cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state * 4
    conv = (cfg.mamba_d_conv - 1) * conv_width(cfg) * jnp.dtype(
        cfg.dtype).itemsize
    return n_layers_of(cfg, "mamba") * (ssm + conv)


def serving(cfg: ModelConfig) -> dict:
    """What an engine has to know of the family (``decode.Serving``)."""
    fam = "the granitemoehybrid family"
    return dict(
        dense_cache=False,
        state_bytes_per_row=state_bytes_per_row(cfg),
        aux_counts=AUX_COUNTS,
        # a decode dispatch's ready rows, the cache positions they reach, and
        # those of every row's whole table (what a gathered window holds)
        counters=("state_rows_advanced", "kv_positions_read",
                  "kv_positions_window"),
        unserved={
            "mesh": f"{fam} is served on one device: its state leaves and "
                    "its two parameter stacks have no mesh placement",
            "kv_quant": f"{fam}'s pages are unquantized: kv_quant would "
                        "round four layers' K and V beside a float32 state",
            "weight_quant": f"{fam}'s products are not built on int8 "
                            "weights (weight_quant)",
            "adapters": f"{fam} has no adapter points (adapters): the LoRA "
                        "deltas are the dense families' projections'",
            "speculative_k": f"{fam} cannot roll a recurrent state back past "
                             "rejected drafts (speculative_k): the state "
                             "after the accepted tokens is not kept",
            "handoff": f"{fam} cannot hand a row to another worker "
                       "(export_handoff / import_handoff, role prefill or "
                       "decode): a handoff ships pages, and the row's "
                       "recurrent state is not among them",
        },
    )


def init(key: jax.Array, cfg: ModelConfig) -> Params:
    """Seeded weights, the draws of ``perfbench/reference/granitemoehybrid``
    (a test holds the two trees equal): matrices normal 0.02, the embedding
    0.005, norms 1, the convolution's taps normal 2, A in [1, 16],
    dt log-uniform in [1e-4, 1e-2], D = 1: a mixer whose state moves its
    output and remembers hundreds of positions."""
    pdt = jnp.dtype(cfg.param_dtype)
    e, v, f = cfg.n_embd, cfg.vocab_size, cfg.shared_intermediate_size
    hm, cw = cfg.mamba_n_heads, conv_width(cfg)
    di = hm * cfg.mamba_d_head
    hd, hkv = cfg.n_head * cfg.head_dim, cfg.kv_heads * cfg.head_dim

    def normal(kk, shape, std=0.02):
        return (jax.random.normal(kk, shape, jnp.float32) * std).astype(pdt)

    def layers(kk, n, mixer):
        ks = jax.random.split(kk, 3)
        return {
            "ln_mix": {"scale": jnp.ones((n, e), pdt)},
            **mixer(ks[0], n),
            "ln_mlp": {"scale": jnp.ones((n, e), pdt)},
            "mlp": {"w_in": normal(ks[1], (n, e, 2 * f)),
                    "w_out": normal(ks[2], (n, f, e))},
        }

    def mamba(kk, n):
        ks = jax.random.split(kk, 6)
        dt = jnp.exp(jax.random.uniform(
            ks[3], (n, hm), jnp.float32, jnp.log(1e-4), jnp.log(1e-2)))
        return {"mixer": {
            "in_proj": normal(ks[0], (n, e, di + cw)),
            "dt_proj": normal(ks[5], (n, e, hm)),
            "conv_w": normal(ks[1], (n, cfg.mamba_d_conv, cw), 2.0),
            "conv_b": jnp.zeros((n, cw), pdt),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "A_log": jnp.log(jax.random.uniform(
                ks[4], (n, hm), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((n, hm), jnp.float32),
            "norm": {"scale": jnp.ones((n, di), pdt)},
            "out_proj": normal(ks[2], (n, di, e)),
        }}

    def attention(kk, n):
        ks = jax.random.split(kk, 4)
        return {"attn": {
            "wq": normal(ks[0], (n, e, hd)), "wk": normal(ks[1], (n, e, hkv)),
            "wv": normal(ks[2], (n, e, hkv)), "wo": normal(ks[3], (n, hd, e)),
        }}

    ks = jax.random.split(key, 3)
    return {
        "wte": normal(ks[0], (v, e), 0.005),
        "mamba": layers(ks[1], n_layers_of(cfg, "mamba"), mamba),
        "attention": layers(ks[2], n_layers_of(cfg, "attention"), attention),
        "ln_f": {"scale": jnp.ones((e,), pdt)},
    }


def init_cache(cfg: ModelConfig, pool_pages: int, page_size: int,
               rows: int, dtype=None) -> dict:
    """{"k", "v"}: the paged pool over the attention layers, heads merged
    head-major on the minor axis (the dense families' layout); {"ssm",
    "conv"}: the recurrent state and the convolution's tail of ``rows`` rows
    and the scratch row, for every Mamba layer. Minor axes in whole lanes
    where the published sizes are (128; 34 x 128)."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    la, lm = n_layers_of(cfg, "attention"), n_layers_of(cfg, "mamba")
    kv = (la, pool_pages, page_size, cfg.kv_heads * cfg.head_dim)
    return {
        "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
        "ssm": jnp.zeros(
            (lm, rows + 1, cfg.mamba_n_heads, cfg.mamba_d_head,
             cfg.mamba_d_state), jnp.float32),
        "conv": jnp.zeros(
            (lm, cfg.mamba_d_conv - 1, rows + 1, conv_width(cfg)), dtype),
    }


# -- the two mixers -----------------------------------------------------------


def _state_of(leaf, layer, rows, n: int, row_axis: int = 1):
    """Layer ``layer``'s entries of a state leaf for the batch, rows first:
    the rows named, or the first n. ``row_axis``: where the leaf keeps its
    rows (1: ``ssm`` [L, rows + 1, ...], the first n read as one slice; 2:
    ``conv`` [L, K-1, rows + 1, C], always gathered: it is small, and a
    slice of it makes the chip's compiler convert the whole leaf)."""
    if row_axis == 1:
        return leaf[layer, :n] if rows is None else leaf[layer, rows]
    rows = jnp.arange(n) if rows is None else rows
    return leaf[layer, jnp.arange(leaf.shape[1])[None, :], rows[:, None]]


def _state_to(leaf, layer, rows, new, row_axis: int = 1):
    """The leaf with those entries replaced, written where it lies."""
    new = new.astype(leaf.dtype)
    n = new.shape[0]
    if row_axis == 1:
        return (leaf.at[layer, :n].set(new) if rows is None
                else leaf.at[layer, rows].set(new))
    rows = jnp.arange(n) if rows is None else rows
    return leaf.at[
        layer, jnp.arange(leaf.shape[1])[None, :], rows[:, None]].set(new)


def _begins_sequence(pos, live):
    """[B] bool: the rows whose call begins their sequence. They begin from
    zero state and tail, whoever held the state row before; a lane that
    holds no token (a free or mid-prefill decode lane sits at position 0
    too) keeps what it has."""
    return (pos == 0) & live[:, 0]


def _mixer_inputs(h, mp, cache, layer, pos, rows, live, cfg: ModelConfig):
    """A Mamba-2 mixer up to its recurrence: the projections of h [B, T, E],
    the convolution from each row's carried tail, the split and the step
    sizes. Returns (z, x [B, T, H, P], B and C [B, T, G, N], dt [B, T, H]
    float32 and 0 where the entry is no token, a [H], the rows that begin
    their sequence [B], the new tail)."""
    b, t, _ = h.shape
    hm, dh, n, g = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                    cfg.mamba_n_groups)
    di, gn = hm * dh, g * n
    zx = h @ mp["in_proj"].astype(h.dtype)
    z, xbc = zx[..., :di], zx[..., di:]
    dt = h @ mp["dt_proj"].astype(h.dtype)
    tail = _state_of(cache["conv"], layer, rows, b, row_axis=2)
    fresh = _begins_sequence(pos, live)
    tail = jnp.where(fresh[:, None, None], jnp.zeros((), tail.dtype), tail)
    n_live = jnp.sum(live, axis=1, dtype=jnp.int32)
    with jax.named_scope("ssm_conv"):
        xbc, new_tail = causal_conv(
            xbc, tail, mp["conv_w"], mp["conv_b"], n_live)
        xbc = jax.nn.silu(xbc)
    x = xbc[..., :di].reshape(b, t, hm, dh)
    bm = xbc[..., di:di + gn].reshape(b, t, g, n)
    cm = xbc[..., di + gn:].reshape(b, t, g, n)
    # dt = 0 where the entry is no token: the state passes through
    dt = jnp.where(
        live[..., None],
        jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"]), 0.0)
    a = -jnp.exp(mp["A_log"].astype(jnp.float32))
    return z, x, bm, cm, dt, a, fresh, new_tail


def _mixer_output(y, x, z, mp, cache, layer, rows, ssm, new_tail,
                  cfg: ModelConfig):
    """A Mamba-2 mixer from its recurrence's y [B, T, H, P] on: the skip
    term, the gate, the norm, the out-projection; the cache with the
    advanced ``ssm`` leaf and the new tail written where it lies."""
    b, t = x.shape[:2]
    y = y + mp["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(b, t, -1).astype(z.dtype) * jax.nn.silu(z)
    y = rms_norm(y, mp["norm"], eps=cfg.layer_norm_epsilon)
    cache = {
        **cache, "ssm": ssm,
        "conv": _state_to(cache["conv"], layer, rows, new_tail, row_axis=2),
    }
    return y @ mp["out_proj"].astype(z.dtype), cache


def _mamba(h, mp, cache, layer, pos, rows, live, cfg: ModelConfig):
    """The Mamba-2 mixer over h [B, T, E] from each row's carried state and
    tail, the recurrence by ``ops/ssm.py``; returns (out [B, T, E],
    cache)."""
    t = h.shape[1]
    z, x, bm, cm, dt, a, fresh, new_tail = _mixer_inputs(
        h, mp, cache, layer, pos, rows, live, cfg)
    state = _state_of(cache["ssm"], layer, rows, h.shape[0])
    state = jnp.where(fresh[:, None, None, None], 0.0, state)
    if t == 1:
        with jax.named_scope("ssm_step"):
            y, new_state = ssm_step(
                x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], state)
            # bit for bit, whatever a dead lane's inputs were
            new_state = jnp.where(
                live[:, 0, None, None, None], new_state, state)
            y = y[:, None]
    else:
        with jax.named_scope("ssm_scan"):
            y, new_state = ssd_chunked(
                x, dt, a, bm, cm, state, cfg.mamba_chunk_size)
    return _mixer_output(
        y, x, z, mp, cache, layer, rows,
        _state_to(cache["ssm"], layer, rows, new_state), new_tail, cfg)


def _mamba_in_place(h, mp, cache, layer, pos, live, cfg: ModelConfig,
                    interpret: bool):
    """``_mamba`` for one token a row on the leaf's FIRST B rows (the decode
    step), the recurrence by ops/ssm_kernel.py: one pass over each live
    row's state where it lies in the ``ssm`` leaf, y from the same pass, a
    dead lane's state neither read nor written."""
    # (imported where it is used: Pallas costs its importer 1.4 s)
    from pytorch_distributed_tpu.ops.ssm_kernel import ssm_state_step

    z, x, bm, cm, dt, a, fresh, new_tail = _mixer_inputs(
        h, mp, cache, layer, pos, None, live, cfg)
    with jax.named_scope("ssm_step"):
        y, ssm = ssm_state_step(
            x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], cache["ssm"], layer,
            live[:, 0], fresh, interpret=interpret)
    return _mixer_output(
        y[:, None], x, z, mp, cache, layer, None, ssm, new_tail, cfg)


def _attention(h, ap, cache, layer, pos, tables, cfg: ModelConfig,
               paged_impl="gather"):
    from pytorch_distributed_tpu.models.decode import (
        _cached_attention,
        _write_kv,
    )

    b, t, _ = h.shape
    d = cfg.head_dim
    q = (h @ ap["wq"].astype(h.dtype)).reshape(b, t, -1, d)
    k = (h @ ap["wk"].astype(h.dtype)).reshape(b, t, -1, d)
    v = (h @ ap["wv"].astype(h.dtype)).reshape(b, t, -1, d)
    kv = _write_kv(
        {"k": cache["k"], "v": cache["v"]}, layer, k, v, pos, tables)
    with jax.named_scope("hybrid_attn"):
        o = _cached_attention(
            q, kv, layer, pos, tables, paged_impl,
            scale=cfg.attention_multiplier)
    return o.reshape(b, t, -1) @ ap["wo"].astype(h.dtype), {**cache, **kv}


def _layer(x, bp, kind, cache, layer, pos, tables, rows, live,
           cfg: ModelConfig, paged_impl="gather"):
    eps, r = cfg.layer_norm_epsilon, cfg.residual_multiplier
    h = rms_norm(x, bp["ln_mix"], eps=eps)
    if kind == "mamba" and (
            h.shape[1] == 1 and rows is None and paged_impl != "gather"):
        m, cache = _mamba_in_place(
            h, bp["mixer"], cache, layer, pos, live, cfg,
            interpret=paged_impl == "kernel_interpret")
    elif kind == "mamba":
        m, cache = _mamba(h, bp["mixer"], cache, layer, pos, rows, live, cfg)
    else:
        m, cache = _attention(
            h, bp["attn"], cache, layer, pos, tables, cfg, paged_impl)
    x = x + r * m
    h = rms_norm(x, bp["ln_mlp"], eps=eps)
    with jax.named_scope("shared_mlp"):
        gu = h @ bp["mlp"]["w_in"].astype(h.dtype)
        f = gu.shape[-1] // 2
        y = (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ bp["mlp"][
            "w_out"].astype(h.dtype)
    return x + r * y, cache


def _rows_in_blocks(layer_fn, x, cache, per_row):
    """``layer_fn(x, cache, *per_row) -> (x, cache)`` over all rows,
    TOKEN_BLOCK tokens' worth of rows at a time (the cache carried from
    group to group), so no temporary grows with the prefill group."""
    b, t = x.shape[:2]
    g = max(k for k in range(1, b + 1)
            if b % k == 0 and (k == 1 or k * t <= TOKEN_BLOCK))
    if g == b:
        return layer_fn(x, cache, *per_row)

    def split(a):
        return a.reshape((b // g, g) + a.shape[1:])

    def body(cache, rows):
        xg, cache = layer_fn(rows[0], cache, *rows[1:])
        return cache, xg

    cache, out = jax.lax.scan(
        body, cache, tuple(split(a) for a in (x, *per_row)))
    return out.reshape(x.shape), cache


def forward(params: Params, input_ids, cfg: ModelConfig, cache: dict, pos,
            block_tables, *, state_rows=None, live=None, logits_index=None,
            paged_impl="gather"):
    """T tokens a row at positions pos[b]..pos[b]+T-1 through every period
    of the pattern against the paged KV pool and the rows' recurrent state.
    ``state_rows`` [B]: the state row of each batch row (None: row b is
    state row b). ``live`` [B, T] bool marks the entries that are tokens; in
    each row they are a PREFIX (a padded final chunk, a free lane). Returns
    (logits [B, T, V] — [B, 1, V], of position ``logits_index[b]``, where
    that is given —, cache, counts [2] int32: the entries that were tokens,
    the entries computed over). ``paged_impl``: how a call of one token a
    row reads its attention layers' pages (``decode._cached_attention``:
    "gather" / "kernel" / "kernel_interpret") and, where ``state_rows`` is
    left out, advances its Mamba layers' state (``_mamba_in_place``)."""
    b, t = input_ids.shape
    pos = jnp.asarray(pos, jnp.int32)
    if live is None:
        live = jnp.ones((b, t), jnp.bool_)
    if state_rows is None and b >= cache["ssm"].shape[1]:
        raise ValueError(
            f"{b} rows need {b} state rows and the scratch row; the cache "
            f"holds {cache['ssm'].shape[1]}")
    per_row = (pos, block_tables, live) + (
        () if state_rows is None else (jnp.asarray(state_rows, jnp.int32),))
    x = embed(params, input_ids, cfg)

    period = layer_period(cfg)
    n_periods = cfg.n_layer // len(period)
    per_period = {k: period.count(k) for k in KINDS}

    def period_body(carry, i, _):
        x, cache = carry
        seen = dict.fromkeys(KINDS, 0)
        for kind in period:
            layer = i * per_period[kind] + seen[kind]
            seen[kind] += 1
            # one layer's weights, read from the whole stack where the
            # products use them: a period's slice of the stacks handed to
            # the scan would be copied out whole, 1.4 GB an iteration
            bp = jax.tree.map(
                lambda a, layer=layer: jax.lax.dynamic_index_in_dim(
                    a, layer, 0, keepdims=False), params[kind])

            def layer_fn(xg, cache, pos, tables, live, rows=None, bp=bp,
                         kind=kind, layer=layer):
                return _layer(xg, bp, kind, cache, layer, pos, tables,
                              rows, live, cfg, paged_impl)

            x, cache = _rows_in_blocks(layer_fn, x, cache, per_row)
        return x, cache

    x, cache = scan_layers(
        period_body, (x, cache), jnp.arange(n_periods, dtype=jnp.int32),
        remat_mode="none",
    )
    if logits_index is not None:
        x = jnp.take_along_axis(x, logits_index[:, None, None], axis=1)
    counts = jnp.stack([
        jnp.sum(live, dtype=jnp.int32), jnp.asarray(b * t, jnp.int32)])
    return head(params, x, cfg), cache, counts


def apply(params: Params, input_ids, cfg: ModelConfig, **_unused):
    """[B, T] tokens -> [B, T, V] logits with no cache kept: every row gets
    one page of T positions and a state row of a cache made here. For tests
    and tools; the engines call ``forward``."""
    b, t = input_ids.shape
    if t > cfg.n_ctx:
        raise ValueError(f"sequence length {t} exceeds n_ctx {cfg.n_ctx}")
    cache = init_cache(cfg, b + 1, t, b)
    tables = 1 + jnp.arange(b, dtype=jnp.int32)[:, None]
    logits, _, _ = forward(
        params, input_ids, cfg, cache, jnp.zeros((b,), jnp.int32), tables
    )
    return logits


def embed(params: Params, input_ids, cfg: ModelConfig, **_unused):
    return (params["wte"][input_ids] * cfg.embedding_multiplier).astype(
        jnp.dtype(cfg.dtype))


def run_blocks(*_args, **_kw):
    raise NotImplementedError(
        "the granitemoehybrid family is served only "
        "(models/granitemoehybrid.py): it has no pipeline or training path"
    )


def final_norm(params: Params, x, cfg: ModelConfig):
    return rms_norm(x, params["ln_f"], eps=cfg.layer_norm_epsilon)


def head(params: Params, x, cfg: ModelConfig):
    x = final_norm(params, x, cfg)
    return (jnp.einsum(
        "bte,ve->btv", x, params["wte"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    ) / cfg.logits_scaling).astype(jnp.dtype(cfg.logits_dtype))
