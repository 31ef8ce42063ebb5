"""Hand-tiled Pallas TPU flash attention: fwd + fused one-pass backward.

This replaces the library kernel (jax.experimental.pallas.ops.tpu.
flash_attention) on the hot path. Three structural differences (what the
kernels cost today in cell 1 is ``flash_roofline.train`` in PERF.md):

- **One-pass backward.** The library runs two backward kernels (dkv, then
  dq), each re-computing the score matrix from scratch — 7 block-level
  matmuls per (q, k) block pair. The fused kernel computes scores once and
  produces dq, dk, dv together: 5 matmuls, one pass over the blocks.
- **Compact softmax residual.** The library emits l and m as lane-broadcast
  [B, H, T, 128] f32 tensors; saved by the remat policy they cost ~100 MB
  of HBM write+read per layer at bench shapes. Here the forward emits ONE
  combined logsumexp, sliced to a compact [B, H, T] residual right after
  the kernel (the kernel-side write stays lane-broadcast — Mosaic block
  shapes need an aligned minor dim — but the padded copy dies immediately
  and only the compact slice is saved / re-read).
- **K/V resident in VMEM.** The key/value tensors for one (batch, head) fit
  VMEM at any practical T (2 x T x D bf16), so the forward's key-block loop
  streams scores without re-fetching K/V from HBM.

The backward works in TRANSPOSED score space (s_T [bk, bq]: keys on
sublanes, queries on lanes) so the per-query logsumexp/delta rows enter as
[1, bq] lane vectors that broadcast across sublanes — no in-kernel
transposes anywhere. dq is accumulated in a VMEM-resident f32 output block
revisited across the (innermost) key-block grid dimension.

Grouped-query attention is served by BlockSpec index maps (query head h
reads KV head h // group) — no materialized head repeat. The backward
emits per-query-head dk/dv and group-sums them outside the kernel.

Softmax runs in the base-2 domain (exp2 is cheaper than exp on the VPU;
the log2(e) factor folds into the score scale).

Layout convention: [B, H, T, D] (callers transpose from the model's
[B, T, H, D]; XLA fuses that into neighbouring ops). Causal masking is for
T == S self-attention.

Capability anchor: the reference names torch's flash/SDPA kernels as its
compute-intensive ops (reference model/pytorch_utils.py:9-13) without ever
calling one; here the kernel is a first-class implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.utils.compat import vma_of

LOG2E = 1.4426950408889634  # log2(e): natural-domain scores -> exp2 domain
LN2 = 0.6931471805599453
NEG_INF = -1e30  # finite; -inf would turn all-masked rows into NaNs

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512

_LANES = 128
_SUBLANES = 8


def _pick_block(t: int, preferred: int) -> int:
    for c in (preferred, 512, 256, 128):
        if c <= preferred and t % c == 0:
            return c
    return t


def _compiler_params(vmem_limit_bytes: int | None = None):
    # b and h grid dims are independent; the innermost dim carries
    # sequential state (fwd: resident K/V reuse; bwd: dq accumulation).
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes,
    )


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A ``pallas_call`` out_shape typed varying over every mesh axis an
    operand varies over. Inside ``shard_map(check_vma=True)`` (explicit,
    pipeline, Ulysses, TP serving) ``pallas_call`` refuses an out_shape
    whose ``vma`` is None; outside one the set is empty."""
    vma = frozenset().union(*(vma_of(x) for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # [1, 1, bq, D]
    k_ref,  # [1, 1, T, D] (resident per (b, h))
    v_ref,  # [1, 1, T, D]
    o_ref,  # [1, 1, bq, D]
    lse_ref,  # [1, 1, bq, 128] f32 (lane-broadcast; sliced outside)
    acc_sc,  # [bq, D] f32
    m_sc,  # [bq, 1] f32
    l_sc,  # [bq, 1] f32
    *,
    bq: int,
    bk: int,
    nk: int,
    scale: float,
    causal: bool,
):
    iq = pl.program_id(2)
    q = q_ref[0, 0]
    m_sc[:] = jnp.full_like(m_sc[:], NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc[:])
    acc_sc[:] = jnp.zeros_like(acc_sc[:])
    s_scale = scale * LOG2E

    def body(ik, _):
        kb = k_ref[0, 0, pl.ds(ik * bk, bk), :]
        vb = v_ref[0, 0, pl.ds(ik * bk, bk), :]
        s = (
            jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * s_scale
        )  # [bq, bk], base-2 domain

        if causal:
            # Only diagonal-straddling blocks need the elementwise mask;
            # strictly-future blocks were excluded by the loop bound.
            def masked(s):
                qpos = iq * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0
                )
                kpos = ik * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1
                )
                return jnp.where(kpos <= qpos, s, NEG_INF)

            s = jax.lax.cond(
                ik * bk + bk - 1 > iq * bq, masked, lambda s: s, s
            )

        m_prev = m_sc[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_sc[:] = l_sc[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[:] = m_new
        return 0

    # Causal: skip key blocks strictly past this query block.
    kmax = pl.cdiv((iq + 1) * bq, bk) if causal else nk
    jax.lax.fori_loop(0, kmax, body, 0)

    l = jnp.maximum(l_sc[:], 1e-30)  # causal self-attn never all-masks a row
    o_ref[0, 0] = (acc_sc[:] / l).astype(o_ref.dtype)
    lse = m_sc[:] * LN2 + jnp.log(l)  # [bq, 1], natural-log domain
    lse_ref[0, 0] = jnp.broadcast_to(lse, (bq, _LANES))


def _fwd_call(q, k, v, causal, scale, bq, bk, interpret):
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    nq, nk = t // bq, t // bk

    kernel = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, nk=nk, scale=scale, causal=causal
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq),
        in_specs=[
            pl.BlockSpec(
                (1, 1, bq, d), lambda b, h, iq: (b, h, iq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, t, d), lambda b, h, iq: (b, h // group, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, t, d), lambda b, h, iq: (b, h // group, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, bq, d), lambda b, h, iq: (b, h, iq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, bq, _LANES), lambda b, h, iq: (b, h, iq, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            out_struct((b, hq, t, d), q.dtype, q, k, v),
            out_struct((b, hq, t, _LANES), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
        name="flash_mha_fwd",
    )(q, k, v)
    # Compact residual: the padded copy is dead after this slice.
    return o, lse[..., 0]


# --------------------------------------------------------------------------
# fused backward: one pass produces dq, dk, dv
# --------------------------------------------------------------------------


def _bwd_kernel(
    q_ref,  # [1, 1, T, D] (resident per (b, h))
    k_ref,  # [1, 1, bk, D]
    v_ref,  # [1, 1, bk, D]
    do_ref,  # [1, 1, T, D] (resident)
    lse_ref,  # [1, 1, 8, T] f32 (resident; sublane-broadcast, base-e)
    delta_ref,  # [1, 1, 8, T] f32 (resident; rowsum(o * do))
    dq_ref,  # [1, 1, T, D] f32 — revisited across ik, accumulated
    dk_ref,  # [1, 1, bk, D] f32 (per QUERY head; group-summed outside)
    dv_ref,  # [1, 1, bk, D] f32
    dk_sc,  # [bk, D] f32
    dv_sc,  # [bk, D] f32
    *,
    bq: int,
    bk: int,
    nq: int,
    scale: float,
    causal: bool,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_ref[:] = jnp.zeros_like(dq_ref[:])

    kb = k_ref[0, 0]
    vb = v_ref[0, 0]
    dk_sc[:] = jnp.zeros_like(dk_sc[:])
    dv_sc[:] = jnp.zeros_like(dv_sc[:])
    s_scale = scale * LOG2E

    def body(iq, _):
        qb = q_ref[0, 0, pl.ds(iq * bq, bq), :]
        dob = do_ref[0, 0, pl.ds(iq * bq, bq), :]
        # [1, bq] lane rows — broadcast across the bk sublanes of s_t.
        lse_row = lse_ref[0, 0, :1, pl.ds(iq * bq, bq)] * LOG2E
        delta_row = delta_ref[0, 0, :1, pl.ds(iq * bq, bq)]
        # Transposed scores: keys on sublanes, queries on lanes.
        s_t = (
            jax.lax.dot_general(
                kb, qb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * s_scale
        )  # [bk, bq]

        if causal:

            def masked(s_t):
                kpos = ik * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bk, bq), 0
                )
                qpos = iq * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bk, bq), 1
                )
                return jnp.where(kpos <= qpos, s_t, NEG_INF)

            s_t = jax.lax.cond(
                ik * bk + bk - 1 > iq * bq, masked, lambda s: s, s_t
            )

        p_t = jnp.exp2(s_t - lse_row)  # already normalized (lse is global)
        dp_t = jax.lax.dot_general(
            vb, dob, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, bq]
        ds_t = p_t * (dp_t - delta_row) * scale  # grad wrt raw scores
        p_b = p_t.astype(do_ref.dtype)
        ds_b = ds_t.astype(q_ref.dtype)
        dv_sc[:] += jax.lax.dot_general(
            p_b, dob, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # contract bq: [bk, D]
        dk_sc[:] += jax.lax.dot_general(
            ds_b, qb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # contract bq: [bk, D]
        dq_ref[0, 0, pl.ds(iq * bq, bq), :] += jax.lax.dot_general(
            ds_b, kb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # contract bk: [bq, D]
        return 0

    # Causal: query blocks strictly before this key block contribute nothing.
    iq_start = (ik * bk) // bq if causal else 0
    jax.lax.fori_loop(iq_start, nq, body, 0)
    dk_ref[0, 0] = dk_sc[:]
    dv_ref[0, 0] = dv_sc[:]


def _bwd_call(q, k, v, do, lse, delta, causal, scale, bq, bk, interpret):
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    nk = t // bk

    # Sublane-broadcast row stats ([B, H, T] -> [B, H, 8, T]) so blocks meet
    # Mosaic's (8, 128) minor-tile rule without any in-kernel retiling.
    lse8 = jnp.broadcast_to(lse[:, :, None, :], (b, hq, _SUBLANES, t))
    delta8 = jnp.broadcast_to(delta[:, :, None, :], (b, hq, _SUBLANES, t))

    kernel = functools.partial(
        _bwd_kernel, bq=bq, bk=bk, nq=t // bq, scale=scale, causal=causal
    )
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(b, hq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, t, d), lambda b, h, ik: (b, h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, ik: (b, h // group, ik, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, ik: (b, h // group, ik, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, t, d), lambda b, h, ik: (b, h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, _SUBLANES, t), lambda b, h, ik: (b, h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, _SUBLANES, t), lambda b, h, ik: (b, h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, t, d), lambda b, h, ik: (b, h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, ik: (b, h, ik, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, ik: (b, h, ik, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            out_struct((b, hq, t, d), jnp.float32, q, k, v, do)
            for _ in range(3)
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        # The kernel keeps q/do (bf16) and the accumulating dq (f32)
        # resident per (b, h) — a footprint that scales with T, and
        # Mosaic's scheduling overheads scale with it too: the observed
        # scoped-vmem demand at llama3-1B T=8192 D=64 is ~17.5-33 MB
        # against the 16 MB default budget. Past T*D = 4096*64 raise the
        # per-kernel limit so long-context training compiles out of the
        # box; at or below it (every bench shape), leave the default
        # untouched so the measured schedules don't shift.
        compiler_params=_compiler_params(
            vmem_limit_bytes=(
                96 * 1024 * 1024 if t * d > 4096 * 64 else None
            )
        ),
        name="flash_mha_bwd",
    )(q, k, v, do, lse8, delta8)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom VJP
# --------------------------------------------------------------------------


def flash_mha(
    q: jax.Array,  # [B, Hq, T, D]
    k: jax.Array,  # [B, Hkv, T, D]
    v: jax.Array,  # [B, Hkv, T, D]
    causal: bool = True,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """Flash attention returning (o, lse).

    lse [B, Hq, T] f32 is a primal output on purpose: the remat "names"
    policy (ops/remat._flash_call_policy) saves every output of the
    underlying custom-VJP call, so with (o, lse) saved the backward runs
    only the fused gradient kernel — no forward re-run. lse is returned
    under ``stop_gradient``: it is a softmax *residual*, and this op does
    not define gradients through it (an lse-based regularizer would need
    its own VJP).
    """
    o, lse = _flash_mha_vjp(
        q, k, v, causal, scale, block_q, block_k, interpret
    )
    return o, jax.lax.stop_gradient(lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_mha_vjp(
    q, k, v, causal, scale, block_q, block_k, interpret
):
    if q.shape[2] != k.shape[2] or k.shape != v.shape:
        raise ValueError(
            f"flash_mha requires T == S self-attention with matching K/V: "
            f"q {q.shape}, k {k.shape}, v {v.shape}"
        )
    bq = _pick_block(q.shape[2], block_q)
    bk = _pick_block(q.shape[2], block_k)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _fwd_call(q, k, v, causal, scale, bq, bk, interpret)


def _flash_mha_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_mha_vjp(
        q, k, v, causal, scale, block_q, block_k, interpret
    )
    return (o, lse), (q, k, v, o, lse)


def _flash_mha_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse = res
    do = cts[0]  # lse cotangent is structurally zero
    bq = _pick_block(q.shape[2], block_q)
    bk = _pick_block(q.shape[2], block_k)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )  # [B, Hq, T]
    dq, dk, dv = _bwd_call(
        q, k, v, do, lse, delta, causal, scale, bq, bk, interpret
    )
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:  # GQA: sum query-head grads within each KV group
        b, _, t, d = q.shape
        dk = dk.reshape(b, hkv, hq // hkv, t, d).sum(axis=2)
        dv = dv.reshape(b, hkv, hq // hkv, t, d).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_mha_vjp.defvjp(_flash_mha_fwd, _flash_mha_bwd)
