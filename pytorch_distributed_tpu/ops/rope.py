"""Rotary position embeddings (llama and kimi_k2 families).

Half-split convention (matches HF Llama): the head dim is split into two
halves, rotate_half([x1, x2]) = [-x2, x1], and
x_rot = x*cos + rotate_half(x)*sin with angles pos / theta^(2i/d).
Angles are computed in float32. ``apply_rope`` rotates whatever trailing
width it is given, so a caller that rotates a slice of a head (latent
attention's ``qk_rope_head_dim``) passes that slice and angles of its width.

YaRN (Peng et al. 2023, as HF ``DeepseekV3YarnRotaryEmbedding`` has it)
changes the FREQUENCIES only: ``yarn_inv_freq`` blends each pair's plain
frequency with the same divided by ``factor``, by a linear ramp over the
pair index between the two correction dims; ``yarn_mscale`` is the
attention-scale term that goes with it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float) -> float:
    """0.1 * mscale * ln(factor) + 1 (1 where factor <= 1)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(
    beta_fast: float, beta_slow: float, dim: int, theta: float,
    original_max_position: int,
) -> tuple[int, int]:
    """(low, high): the pair indices between which YaRN's ramp runs — the
    dims that turn ``beta_fast`` and ``beta_slow`` times over the original
    context, floored and ceiled, clipped to the head."""

    def correction_dim(rotations):
        return dim * math.log(
            original_max_position / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = math.floor(correction_dim(beta_fast))
    high = math.ceil(correction_dim(beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(
    dim: int, theta: float, factor: float, original_max_position: int,
    beta_fast: float, beta_slow: float,
) -> jax.Array:
    """[dim/2] float32 frequencies: ``extra`` = theta^(-2i/dim) below the
    low correction dim (fast pairs, kept), ``extra / factor`` above the
    high one (slow pairs, interpolated), a linear blend between."""
    extra = 1.0 / (
        theta ** (jnp.arange(0, dim // 2, dtype=jnp.float32) * 2.0 / dim)
    )
    low, high = yarn_correction_range(
        beta_fast, beta_slow, dim, theta, original_max_position
    )
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 0.001),
        0.0, 1.0,
    )
    mask = 1.0 - ramp
    return (extra / factor) * (1.0 - mask) + extra * mask


def rope_angles(
    seq_len: int, head_dim: int, theta: float, *, offset=0, inv_freq=None
) -> tuple[jax.Array, jax.Array]:
    """Returns (cos, sin), each [seq_len, head_dim] float32. ``offset`` may be
    a traced scalar (e.g. a sequence-shard start under context parallelism)
    or a [B, 1] per-row column (slot-batched decode, where every batch row
    sits at its own position): broadcasting then yields [B, seq_len,
    head_dim] angles whose row b equals the scalar-offset result for
    offset[b]. ``inv_freq`` [head_dim/2] replaces the plain frequencies
    (``yarn_inv_freq``)."""
    half = head_dim // 2
    if inv_freq is None:
        inv_freq = 1.0 / (
            theta
            ** (jnp.arange(0, half, dtype=jnp.float32) * 2.0 / head_dim)
        )
    pos = jnp.arange(seq_len, dtype=jnp.float32) + offset  # [T] or [B, T]
    angles = pos[..., None] * inv_freq  # [..., T, half]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [..., T, D]
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(
    x: jax.Array,  # [B, T, H, D]
    cos: jax.Array,  # [T, D] shared, or [B, T, D] per-row angles
    sin: jax.Array,
) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if cos.ndim == 3:  # per-row positions (slot-batched decode)
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    else:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    return (x32 * c + _rotate_half(x32) * s).astype(dtype)
