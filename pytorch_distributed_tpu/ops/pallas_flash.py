"""Flash (blockwise, online-softmax) causal attention.

The reference's attention materialises the full [B, H, T, T] score matrix
(reference my_gpt2.py:60-77) and lists torch's flash/efficient SDPA kernels as
compute-intensive save-targets (reference model/pytorch_utils.py:9-13) without
ever calling them. Here flash attention is a first-class implementation with
two backends behind one entry point:

- ``pallas``: this repo's hand-tiled Mosaic/Pallas TPU kernels
  (ops/flash_kernel.py) — K/V resident in VMEM, online softmax, compact
  [B, H, T] logsumexp residual, fused one-pass backward producing
  dq/dk/dv together. Used automatically on TPU when shapes are tileable.
- ``blockwise``: a pure-XLA `lax.scan` over key blocks with the same
  online-softmax recurrence — O(T · block) memory, differentiable by
  ordinary AD. The portable fallback (CPU tests, ragged shapes).

GQA: the kernel maps query head h to KV head h // group via BlockSpec
index maps (no materialized repeat); the blockwise fallback repeats.

Partitioning: GSPMD cannot split a Mosaic custom call (on more than one
chip jax refuses to lower one: "Mosaic kernels cannot be automatically
partitioned"), so under the pjit path (parallel/api.py traces the step
under its mesh) the kernel call is wrapped in a ``shard_map`` over the
mesh axes that shard the batch and the heads — each chip runs the kernel
on its own rows. Inside an enclosing
``shard_map`` (explicit, pipeline, Ulysses, TP serving) every axis is
already manual and the kernel sees local shapes as it is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pytorch_distributed_tpu.ops.attention import NEG_INF, _repeat_kv
from pytorch_distributed_tpu.utils.compat import vma_of

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256

# The TPU kernel tiles the sequence into lane-width multiples; anything
# smaller (tiny test configs) takes the blockwise path.
_PALLAS_MIN_SEQ = 128


def _pallas_supported(t: int, s: int, d: int) -> bool:
    if jax.devices()[0].platform != "tpu":
        return False
    # t == s only: for S > T (decoding with a cache) the kernel masks
    # query i at absolute position i, whereas this module's convention aligns
    # the last query with the last key (q_offset = s - t) — the blockwise
    # path handles that case correctly.
    return (
        t == s
        and t % _PALLAS_MIN_SEQ == 0
        and d % 64 == 0
    )


def _gspmd_activation_spec(n_kv_head: int) -> P | None:
    """How the ambient mesh shards a [B, T, H, D] activation over its
    AUTOMATIC axes — batch over data/fsdp/expert, heads over tensor when
    it divides the KV heads (query heads then split into the same
    groups) — or None when there is nothing to partition: no ambient
    mesh (single device), every axis already manual (inside a
    shard_map), or every automatic axis of size 1."""
    from pytorch_distributed_tpu.parallel.mesh import BATCH_AXES

    mesh = jax.sharding.get_abstract_mesh()
    auto = {ax for ax in mesh.auto_axes if mesh.shape[ax] > 1}
    batch = tuple(ax for ax in BATCH_AXES if ax in auto)
    heads = (
        "tensor"
        if "tensor" in auto and n_kv_head % mesh.shape["tensor"] == 0
        else None
    )
    if not batch and heads is None:
        return None
    return P(batch or None, None, heads, None)


def flash_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    """Blockwise causal attention, [B, T, H, D] -> [B, T, H, D].

    Dispatches to the Pallas TPU kernel when running on TPU with tileable
    shapes, else to the portable scan implementation.
    """
    b, t, h, d = q.shape
    s = k.shape[1]
    if _pallas_supported(t, s, d):
        kernel = functools.partial(_pallas_flash, causal=causal)
        spec = _gspmd_activation_spec(k.shape[2])
        if spec is None:
            return kernel(q, k, v)
        return jax.shard_map(
            kernel, in_specs=(spec, spec, spec), out_specs=spec
        )(q, k, v)
    return blockwise_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k
    )


def _pallas_flash(q, k, v, *, causal: bool) -> jax.Array:
    """[B, T, H, D] wrapper around the [B, H, T, D] Pallas TPU kernels
    (ops/flash_kernel.py). GQA heads are resolved inside the kernel via
    index maps — no repeat. The lse output is returned to the caller's
    jaxpr solely so the remat policy can save it (the value itself is
    only consumed by the custom VJP's backward)."""
    import os

    from pytorch_distributed_tpu.ops import flash_kernel

    out, _ = flash_kernel.flash_mha(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal,
        None,
        int(os.environ.get("PDT_FLASH_BQ", flash_kernel.DEFAULT_BLOCK_Q)),
        int(os.environ.get("PDT_FLASH_BK", flash_kernel.DEFAULT_BLOCK_K)),
    )
    return out.transpose(0, 2, 1, 3)


# repolint: allow(jit-donation-decision) — functional attention op:
# q/k/v belong to the caller and are read again in the backward pass.
@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k")
)
def blockwise_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    """Pure-XLA blockwise causal attention, [B, T, H, D] -> [B, T, H, D].

    Accumulators (running max m, normaliser l, output acc) are float32.
    """
    b, t, h, d = q.shape
    s = k.shape[1]
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])

    block_q = min(block_q, t)
    block_k = min(block_k, s)
    if t % block_q or s % block_k:
        # Fall back to one block covering the ragged dim (correct, less tiled).
        block_q = t if t % block_q else block_q
        block_k = s if s % block_k else block_k
    nq, nk = t // block_q, s // block_k

    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    # [B, H, nq, bq, D] layout so each scan step is a clean batched matmul.
    qb = q.transpose(0, 2, 1, 3).reshape(b, h, nq, block_q, d)
    kb = k.transpose(0, 2, 1, 3).reshape(b, h, nk, block_k, d)
    vb = v.transpose(0, 2, 1, 3).reshape(b, h, nk, block_k, d)

    q_offset = s - t  # query i sits at key position i + offset (S >= T)

    def per_q_block(iq, q_blk):
        """Online-softmax scan over key blocks for one query block."""
        q_start = iq * block_q + q_offset

        def kv_step(carry, inputs):
            acc, m, l = carry
            ik, k_blk, v_blk = inputs
            scores = (
                jnp.einsum(
                    "bhqd,bhkd->bhqk", q_blk, k_blk,
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [B, H, bq, bk]
            if causal:
                qpos = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                kpos = ik * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1
                )
                scores = jnp.where(kpos <= qpos, scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))  # [B, H, bq]
            p = jnp.exp(scores - m_new[..., None])
            correction = jnp.exp(m - m_new)
            l_new = l * correction + p.sum(axis=-1)
            acc_new = acc * correction[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return (acc_new, m_new, l_new), None

        # Inside shard_map (e.g. as the Ulysses local backend) the scan
        # carry must vary on the same mesh axes as the activations.
        from pytorch_distributed_tpu.ops.tp import pvary_missing

        vma = tuple(vma_of(q_blk))
        acc0 = pvary_missing(
            jnp.zeros((b, h, block_q, d), jnp.float32), vma
        )
        m0 = pvary_missing(
            jnp.full((b, h, block_q), NEG_INF, jnp.float32), vma
        )
        l0 = pvary_missing(jnp.zeros((b, h, block_q), jnp.float32), vma)
        ks = jnp.arange(nk)
        (acc, m, l), _ = jax.lax.scan(
            kv_step,
            (acc0, m0, l0),
            (ks, kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4)),
        )
        # All-masked rows (can't happen for causal self-attention, where each
        # query sees at least itself) would give l=0; guard anyway.
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.vmap(per_q_block, in_axes=(0, 2), out_axes=2)(
        jnp.arange(nq), qb
    )  # [B, H, nq, bq, D]
    out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)
