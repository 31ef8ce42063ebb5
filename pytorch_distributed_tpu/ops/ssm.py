"""The Mamba-2 mixer's three pieces, in plain XLA: the causal depthwise
convolution with a carried tail, the chunked (SSD) form of the selective
state-space recurrence for a multi-token call, and the one-token state
update for decode.

The recurrence, per head h with state S [P, N] (P = head entries, N = state
size), inputs x_t [P], B_t, C_t [N] (shared by the heads of a group), a
step dt_t > 0 and a decay rate A < 0:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t

(the skip D x_t and the gate are the model's). ``ssm_step`` is that line
(``ops/ssm_kernel.py`` is the same line on a TPU, the state passed over once
where it lies in the serving cache; this one is what it is held to).
``ssd_chunked`` computes the same over a block of ``chunk`` tokens at once
(Dao & Gu 2024, "Transformers are SSMs", section 6): with l_t the running
sum of dt A inside the block,

    y_t = exp(l_t) C_t . S_in  +  sum_{s<=t} exp(l_t - l_s) (C_t . B_s) dt_s x_s
    S_out = exp(l_T) S_in + sum_s exp(l_T - l_s) dt_s x_s (x) B_s

so no per-position state [T, H, P, N] ever exists: the block costs one
[T, T] decay matrix a head and three products. Every exponent is <= 0
(l falls), so nothing overflows. A longer call scans its blocks, the state
carried from block to block.

**Tokens that are not tokens.** A call's positions past a row's last real
token (a padded final prefill chunk; a free decode lane) carry ``dt = 0``:
the decay is exp(0) = 1 and nothing is added, so the state leaves the call
as the row's last REAL token left it. The caller masks dt; the convolution
takes ``n_live`` and keeps the tail of the last real positions.

Precision: the state, dt, l and every decay are float32; a product that
reads or writes the state runs at ``highest`` (on a TPU a float32 product
otherwise rounds its operands to bfloat16); the products among the block's
own activations (C . B, the weighted sum over x) take their operands as they
are and accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_EXACT = jax.lax.Precision.HIGHEST


def causal_conv(x, tail, w, b, n_live):
    """Depthwise causal convolution over time with its left context carried
    in: x [B, T, C] are this call's positions, ``tail`` [B, K-1, C] the K-1
    positions before them (zeros at a sequence's start), w [K, C] the taps
    (w[K-1] multiplies the current position), b [C]. Returns (y [B, T, C] in
    x's dtype, the new tail [B, K-1, C]): the K-1 positions that end at each
    row's last REAL one, ``n_live[b]`` of the T being real (a prefix). With
    fewer than K-1 real positions the new tail reaches back into the old
    one; with none it IS the old one, bit for bit."""
    t, k = x.shape[1], w.shape[0]
    cat = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, T+K-1, C]
    acc = b.astype(jnp.float32)
    for j in range(k):
        acc = acc + cat[:, j:j + t].astype(jnp.float32) * w[j].astype(
            jnp.float32)
    new_tail = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, k - 1, axis=0)
    )(cat, n_live)
    return acc.astype(x.dtype), new_tail.astype(tail.dtype)


def _ssd_block(x, dt, a, b, c, state):
    """One block, heads split by group (k heads in each of g groups):
    x [B, T, g, k, P], dt [B, T, g, k] f32, a [g, k] f32, b and c
    [B, T, g, N], state [B, g, k, P, N] f32 -> (y [B, T, g, k, P] f32,
    state)."""
    t = x.shape[1]
    l = jnp.moveaxis(jnp.cumsum(dt * a, axis=1), 1, -1)  # [B, g, k, T]
    dts = jnp.moveaxis(dt, 1, -1)
    # decay[t, s] = exp(l_t - l_s) for s <= t, 0 above the diagonal
    lower = jnp.tril(jnp.ones((t, t), jnp.bool_))
    decay = jnp.exp(jnp.where(
        lower, l[..., :, None] - l[..., None, :], -jnp.inf))
    cb = jnp.einsum(
        "btgn,bsgn->bgts", c, b, preferred_element_type=jnp.float32)
    m = (cb[:, :, None] * decay * dts[..., None, :]).astype(x.dtype)
    y = jnp.einsum(
        "bgkts,bsgkp->btgkp", m, x, preferred_element_type=jnp.float32)
    # what the state carried in adds to every position
    y = y + jnp.moveaxis(jnp.exp(l), -1, 1)[..., None] * jnp.einsum(
        "btgn,bgkpn->btgkp", c.astype(jnp.float32), state, precision=_EXACT)
    # the state the block leaves: the carried one decayed over the whole
    # block, and every position's dt x (x) B decayed from there to the end
    to_end = jnp.moveaxis(jnp.exp(l[..., -1:] - l) * dts, -1, 1)
    state = jnp.exp(l[..., -1])[..., None, None] * state + jnp.einsum(
        "btgkp,btgn->bgkpn", to_end[..., None] * x.astype(jnp.float32),
        b.astype(jnp.float32), precision=_EXACT)
    return y, state


def ssd_chunked(x, dt, a, b, c, state, chunk: int):
    """The recurrence over T positions, ``chunk`` at a time. x [B, T, H, P];
    dt [B, T, H] float32, softplus applied and 0 where the position is no
    token; a [H] float32 (negative); b, c [B, T, G, N] with G dividing H
    (head h reads group h // (H/G)); state [B, H, P, N] float32, the state
    before the first position. Returns (y [B, T, H, P] float32, without the
    skip term; the state after the last position)."""
    bsz, t, h, p = x.shape
    g = b.shape[2]
    x = x.reshape(bsz, t, g, h // g, p)
    dt = dt.reshape(bsz, t, g, h // g)
    a = a.reshape(g, h // g)
    state = state.reshape((bsz, g, h // g) + state.shape[2:])
    if t <= chunk:
        y, state = _ssd_block(x, dt, a, b, c, state)
    else:
        pad = -t % chunk  # dt = 0 there: the state passes through

        def blocks(v):
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            v = v.reshape((bsz, (t + pad) // chunk, chunk) + v.shape[2:])
            return jnp.moveaxis(v, 1, 0)

        def body(state, xs):
            y, state = _ssd_block(xs[0], xs[1], a, xs[2], xs[3], state)
            return state, y

        state, y = jax.lax.scan(
            body, state, tuple(map(blocks, (x, dt, b, c))))
        y = jnp.moveaxis(y, 0, 1).reshape((bsz, t + pad) + y.shape[3:])[:, :t]
    return y.reshape(bsz, t, h, p), state.reshape((bsz, h) + state.shape[3:])


def ssm_step(x, dt, a, b, c, state):
    """One position a row: x [B, H, P], dt [B, H] float32 (0 where the lane
    holds no token), a [H], b and c [B, G, N], state [B, H, P, N] float32.
    Returns (y [B, H, P] float32, the state after the position)."""
    bsz, h, p = x.shape
    g = b.shape[1]
    shape = state.shape
    state = state.reshape((bsz, g, h // g) + shape[2:])
    b, c = b.astype(jnp.float32), c.astype(jnp.float32)
    dtx = (dt[..., None] * x.astype(jnp.float32)).reshape(bsz, g, h // g, p)
    state = jnp.exp(dt * a).reshape(bsz, g, h // g, 1, 1) * state + (
        dtx[..., None] * b[:, :, None, None, :])
    # (a sum of products, not a dot: one pass over the state on the vector
    # unit, in float32, where a dot at ``highest`` reads it several times)
    y = jnp.sum(state * c[:, :, None, None, :], axis=-1)
    return y.reshape(bsz, h, p), state.reshape(shape)
