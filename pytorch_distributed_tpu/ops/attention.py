"""Multi-head causal self-attention.

Implementations:
- ``naive``: materialises the full [B, H, T, T] score matrix — the behavioral
  twin of the reference's manual attention math (reference my_gpt2.py:60-77:
  matmul / sqrt(head_dim), masked_fill(-inf), softmax, dropout, matmul).
  TPU-first differences: the causal mask is computed on the fly from iotas
  (no precomputed n_ctx×n_ctx tril buffer as in reference my_gpt2.py:29-36 —
  XLA fuses the compare into the softmax), and softmax runs in float32.
- ``flash``: blockwise Pallas kernel (ops/pallas_flash.py) that never
  materialises the score matrix — O(T) memory.
- ``ring``: sequence-parallel blockwise attention over a mesh axis
  (ops/ring_attention.py).
- ``paged`` (decode only): single-query attention against a PAGED KV
  pool addressed through per-row block tables — the serving block-pool
  layout (serving/engine.PagedBatchedDecodeEngine). Not dispatched
  through ``multi_head_attention`` (it is a decode-cache op, not a
  training attention: one query token, keys gathered by page id);
  re-exported here as ``paged_decode_attention`` so the attention
  surface stays one module. Pallas kernel + XLA gather fallback live in
  ops/paged_kernel.py.

All variants support grouped-query attention (n_kv_head < n_head) for the
llama family.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # finite mask value: -inf breaks softmax when a row is all-masked


def paged_decode_attention(*args, **kwargs):
    """Lazy re-export of ops/paged_kernel.paged_decode_attention (see
    module docstring): paged single-query decode attention, [B, H, D]
    queries against a [P, page, Hkv*D] pool via [B, n_pages] block
    tables. Lazy so importing the training attention surface never pays
    the Pallas import."""
    from pytorch_distributed_tpu.ops.paged_kernel import (
        paged_decode_attention as impl,
    )

    return impl(*args, **kwargs)


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, T, Hkv, D] -> [B, T, Hkv*n_rep, D] for GQA."""
    if n_rep == 1:
        return x
    b, t, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, t, h, n_rep, d)).reshape(
        b, t, h * n_rep, d
    )


def naive_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    *,
    causal: bool = True,
    dropout_rate: float = 0.0,
    dropout_key: jax.Array | None = None,
    deterministic: bool = True,
) -> jax.Array:
    """Returns [B, T, H, D]. Scores/softmax computed in float32."""
    b, t, h, d = q.shape
    s = k.shape[1]
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])

    scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=jnp.float32))
    # [B, H, T, S] in f32 — one big MXU-friendly batched matmul.
    scores = jnp.einsum(
        "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
    ) * scale

    if causal:
        # query position i attends to key positions j <= i (+ offset when S>T,
        # i.e. decoding with a cache: the last query aligns with the last key).
        qpos = jax.lax.broadcasted_iota(jnp.int32, (t, s), 0) + (s - t)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (t, s), 1)
        scores = jnp.where(kpos <= qpos, scores, NEG_INF)

    weights = jax.nn.softmax(scores, axis=-1)

    if not deterministic and dropout_rate > 0.0:
        if dropout_key is None:
            raise ValueError("attention dropout requires a PRNG key")
        keep = 1.0 - dropout_rate
        mask = jax.random.bernoulli(dropout_key, p=keep, shape=weights.shape)
        weights = jnp.where(mask, weights / keep, jnp.zeros_like(weights))

    weights = weights.astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", weights, v)


def multi_head_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "naive",
    causal: bool = True,
    dropout_rate: float = 0.0,
    dropout_key: jax.Array | None = None,
    deterministic: bool = True,
    seq_axis: str | None = None,
    seq_impl: str = "ring",
) -> jax.Array:
    """Dispatch over attention implementations. Inputs [B, T, H(kv), D].

    ``seq_axis``: name of a shard_map mesh axis the sequence dim is sharded
    over — selects sequence/context parallelism regardless of ``impl``;
    ``seq_impl`` picks the technique: "ring" (KV blocks stream around a
    ppermute ring, online-softmax merge) or "ulysses" (head/sequence
    all-to-all re-shard, full local attention — needs the axis to divide
    the head counts). Attention dropout works under "ulysses" (the local
    attention IS the full-sequence computation on this shard's head group
    — see ops/ulysses.py for the per-shard-key contract) but not "ring",
    where weights only ever exist per KV block inside the online-softmax
    merge. (The reference has no sequence parallelism at all, SURVEY.md
    §5.7.)
    """
    if seq_axis is not None:
        if seq_impl == "ulysses":
            from pytorch_distributed_tpu.ops.ulysses import ulysses_attention

            # Local backend defaults to flash: after the head/sequence
            # re-shard the local attention sees the FULL sequence, and
            # naive's [T_global, T_global] score matrix is exactly what
            # sequence parallelism exists to avoid. "naive" is promoted to
            # flash (same math up to online-softmax reordering); an
            # explicit impl="flash" passes through unchanged.
            # (No promotion note when attention dropout is active — the
            # local backend falls back to naive there anyway, see
            # ops/ulysses.py.)
            if impl == "naive" and (deterministic or dropout_rate == 0.0):
                import warnings

                warnings.warn(
                    "impl='naive' with seq_impl='ulysses' is promoted to "
                    "flash (same math up to online-softmax reordering); "
                    "pass impl='flash' to silence this",
                    stacklevel=2,
                )
            return ulysses_attention(
                q, k, v, axis_name=seq_axis, causal=causal,
                impl="flash" if impl == "naive" else impl,
                dropout_rate=dropout_rate,
                dropout_key=dropout_key,
                deterministic=deterministic,
            )
        if seq_impl != "ring":
            raise KeyError(
                f"unknown seq_impl {seq_impl!r}; known: ring, ulysses"
            )
        if not deterministic and dropout_rate > 0.0:
            raise NotImplementedError(
                "attention dropout is not supported with ring attention "
                "(weights exist only per KV block inside the online-softmax "
                "merge); use seq_impl='ulysses' or attn_pdrop=0.0"
            )
        from pytorch_distributed_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, axis_name=seq_axis, causal=causal)
    if impl == "naive":
        return naive_attention(
            q, k, v,
            causal=causal,
            dropout_rate=dropout_rate,
            dropout_key=dropout_key,
            deterministic=deterministic,
        )
    if impl == "flash":
        from pytorch_distributed_tpu.ops.pallas_flash import flash_attention

        # Flash path has no attention-dropout support (like torch SDPA flash);
        # callers fall back to naive when attn_pdrop>0 and training.
        if not deterministic and dropout_rate > 0.0:
            return naive_attention(
                q, k, v,
                causal=causal,
                dropout_rate=dropout_rate,
                dropout_key=dropout_key,
                deterministic=deterministic,
            )
        return flash_attention(q, k, v, causal=causal)
    raise KeyError(f"unknown attention impl {impl!r}")
