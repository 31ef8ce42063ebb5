"""Pallas TPU paged-attention decode kernel (+ XLA gather fallback).

Single-query attention for the paged serving engine
(serving/engine.PagedBatchedDecodeEngine): each batch row's K/V lives in
fixed-size PAGES of a shared pool ``[P, page, Hkv*D]`` (the heads merged
head-major on the minor axis, so a page's rows are whole lanes: the
stored shape of ``models/decode.init_paged_cache``), addressed
through a per-row block table — the vLLM cache layout, which is what
lets ``slots`` scale with the pool instead of ``slots x max_len``
(ROADMAP direction 1; serving practice surveyed in PAPERS.md #1).

The kernel is the piece that makes per-row attention cost scale with the
row's DEPTH instead of ``max_len``:

- grid ``(B, n_pages)`` with the page dimension innermost and
  sequential (online-softmax accumulator state lives in VMEM scratch
  across it);
- the layer index, the block tables and per-row lengths ride
  ``PrefetchScalarGridSpec`` scalar prefetch, so the K/V BlockSpec *index
  maps* resolve ``(layer, tables[b, i])`` before the body runs — the page
  "gather" is just the kernel's own DMA picking its source block out of
  the STACKED ``[L, P, page, Hkv*D]`` pool, never a materialised
  [B, max_len] copy nor a per-layer slice of the pool;
- pages past a row's depth are skipped with ``pl.when`` (no MXU work,
  and their DMA re-reads the row's last useful page id — the host fills
  unallocated table entries with the scratch page 0, so the skipped
  fetch is bounded and harmless);
- one grid step holds ALL heads of one page. Mosaic tiles the last two
  block dims, so a block must cover them whole (or in (8, 128)
  multiples): ``(1, page, Hkv*D)`` of one layer of the pool, ``(1, H, D)``
  over the queries, ``(1, page, Hkv)`` of the int8 scale pool. The body walks
  the KV heads in a static loop, reading head ``g`` of the page as the
  static lane slice ``k_ref[0, :, g*D:(g+1)*D]`` and computing the whole
  ``group = H // Hkv`` query-head block against that [page, D] key block,
  so grouped-query heads share their KV head inside the kernel.

GQA + per-row depth masking match ``models/decode._cached_attention``'s
masked-softmax math up to online-softmax reassociation (floating-point
reordering only — the equivalence test pins allclose, and engine-level
token equality is pinned separately on the gather path).

``interpret`` is the caller's decision: the compiled kernel needs a TPU,
and a caller off the chip says ``interpret=True`` itself (the CPU tests
do; the engine's ``paged_attention="kernel_interpret"`` does). The
serving engine's default paged attention is the pure-XLA ``gather_pages``
fallback in models/decode.py, which is bit-identical to the dense
engine's math (the property the paged-vs-dense token-equality pins rely
on). Read /opt/skills/guides/pallas_guide.md before touching the kernel
body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.ops.flash_kernel import out_struct

NEG_INF = -1e30  # finite mask (matches ops/attention.py): -inf NaNs softmax


def _paged_kernel(
    layer_ref,  # [1] int32 (scalar prefetch): read by the index maps only
    tables_ref,  # [B, n_pages] int32 (scalar prefetch)
    lens_ref,  # [B] int32 (scalar prefetch): row's query position
    q_ref,  # [1, H, D]
    k_ref,  # [1, page, Hkv*D] — the page tables_ref[b, i], all heads
    v_ref,  # [1, page, Hkv*D]
    *rest,  # int8 pages: ks_ref, vs_ref [1, page, Hkv] f32; then o_ref
    # [1, H, D] and the f32 scratch acc [H, D], m [H, 1], l [H, 1]
    page: int,
    n_pages: int,
    scale: float,
    quantized: bool,
):
    """Online-softmax over one row's pages. With ``quantized`` the page
    DMA moves INT8 K/V blocks plus their per-token f32 scales and
    dequantization happens in VMEM right before the dot — HBM traffic
    for a page drops to (D + 4)/(4D) of the f32 kernel's. Numerics past
    the dequant are the full-precision kernel's exactly (same
    accumulator dtypes, same masking), so quantized-vs-gather
    equivalence is pinned the same way (tests/test_quant.py)."""
    if quantized:
        ks_ref, vs_ref, o_ref, acc_sc, m_sc, l_sc = rest
    else:
        o_ref, acc_sc, m_sc, l_sc = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    d = q_ref.shape[2]
    hkv = k_ref.shape[2] // d
    group = q_ref.shape[1] // hkv

    @pl.when(i == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc[:])
        m_sc[:] = jnp.full_like(m_sc[:], NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])

    length = lens_ref[b]  # keys 0..length (inclusive) are valid

    # Pages wholly past the row's depth do no work: the decode cost of a
    # short row is its own page count, not max_len.
    @pl.when(i * page <= length)
    def _compute():
        for g in range(hkv):
            rows = slice(g * group, (g + 1) * group)
            q = q_ref[0, rows, :].astype(jnp.float32)  # [group, D]
            lanes = slice(g * d, (g + 1) * d)  # head g of the merged axis
            kb = k_ref[0, :, lanes].astype(jnp.float32)  # [page, D]
            vb = v_ref[0, :, lanes].astype(jnp.float32)
            if quantized:
                # Dequant-in-kernel: int8 block * per-token scale column.
                kb = kb * ks_ref[0, :, g:g + 1]
                vb = vb * vs_ref[0, :, g:g + 1]
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [group, page]
            kpos = i * page + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(kpos <= length, s, NEG_INF)
            m_prev = m_sc[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_sc[rows, :] = l_sc[rows, :] * corr + jnp.sum(
                p, axis=-1, keepdims=True
            )
            acc_sc[rows, :] = acc_sc[rows, :] * corr + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_sc[rows, :] = m_new

    @pl.when(i == n_pages - 1)
    def _emit():
        o_ref[0] = (
            acc_sc[:] / jnp.maximum(l_sc[:], 1e-30)
        ).astype(o_ref.dtype)


# repolint: allow(jit-donation-decision) — functional attention op: the
# K/V pages belong to the serving engine's donated cache (aliased at the
# PROGRAM boundary, not here) and q is read by the caller's residual.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_call(q, k_pages, v_pages, scales, layer, block_tables, lengths,
                interpret):
    """``k_pages``/``v_pages`` are STACKED [L, P, page, Hkv*D] pools and
    ``layer`` [1] picks the layer; ``scales`` is ``()`` for full-precision
    pages or the ``(k_scales, v_scales)`` [L, P, page, Hkv] pools for
    int8 pages."""
    b, h, d = q.shape
    n_pages = block_tables.shape[1]
    page, hkv = k_pages.shape[2], k_pages.shape[3] // d
    kernel = functools.partial(
        _paged_kernel,
        page=page, n_pages=n_pages, scale=1.0 / (d**0.5),
        quantized=bool(scales),
    )
    row_spec = pl.BlockSpec(
        (1, h, d), lambda bi, i, layer, tables, lens: (bi, 0, 0)
    )
    page_spec = pl.BlockSpec(
        (None, 1, page, hkv * d),
        lambda bi, i, layer, tables, lens: (layer[0], tables[bi, i], 0, 0),
    )
    scale_spec = pl.BlockSpec(
        (None, 1, page, hkv),
        lambda bi, i, layer, tables, lens: (layer[0], tables[bi, i], 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=[row_spec, page_spec, page_spec]
        + [scale_spec] * len(scales),
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_struct(
            (b, h, d), q.dtype, q, k_pages, v_pages, *scales
        ),
        interpret=interpret,
        # Rows are independent; the page dim carries the online-softmax
        # state.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        name="paged_decode_attention",
    )(layer, block_tables, lengths, q, k_pages, v_pages, *scales)


def paged_decode_attention(
    q: jax.Array,  # [B, H, D] — ONE query token per row
    k_pages: jax.Array,  # [P, page, Hkv*D] (int8 when quantized)
    v_pages: jax.Array,  # [P, page, Hkv*D], heads merged head-major
    block_tables: jax.Array,  # [B, n_pages] int32 page ids
    lengths: jax.Array,  # [B] int32: the row's position (keys <= it valid)
    *,
    k_scales: jax.Array | None = None,  # [P, page, Hkv] f32 (int8 pages)
    v_scales: jax.Array | None = None,
    layer: jax.Array | int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged single-query attention, [B, H, D] -> [B, H, D]. ``lengths``
    is each row's query position: key j is attended iff j <= lengths[b]
    (the dense decode-step mask at T=1). ``interpret=None`` means the
    compiled kernel and is an error off the chip — interpreter mode is
    never chosen for the caller.

    ``layer`` (a traced scalar inside the layer scan): the pools — and
    scale pools — are then the STACKED [L, P, ...] leaves of the serving
    cache and the kernel reads layer ``layer`` of them in place. Without
    it the pools are one layer's, as in the signature.

    ``k_scales``/``v_scales`` switch to the int8 kernel: pages are int8
    with per-token/per-head f32 scales and dequantization happens in
    VMEM (the bandwidth-bound read moves quarter-width pages)."""
    if interpret is None:
        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise RuntimeError(
                f"paged_decode_attention: the compiled kernel needs a "
                f"TPU and jax.devices()[0].platform is {platform!r}; "
                "pass interpret=True to run the Pallas interpreter"
            )
        interpret = False
    h, d = q.shape[1:]
    want = "[P, page, Hkv*D]" if layer is None else "[L, P, page, Hkv*D]"
    width = k_pages.shape[-1]
    if k_pages.ndim != want.count(",") + 1 or width % d or h % (width // d):
        raise ValueError(
            f"pages {k_pages.shape}: want {want} with whole kv heads of "
            f"D={d} on the minor axis and the query heads ({h}) a multiple "
            "of them"
        )
    if (k_scales is None) != (v_scales is None):
        raise ValueError(
            "k_scales and v_scales must be given together (int8 pages) "
            "or both omitted (full-precision pages)"
        )
    scales = () if k_scales is None else (k_scales, v_scales)
    if layer is None:  # one layer's pools: a stack of one (a free reshape)
        k_pages, v_pages = k_pages[None], v_pages[None]
        scales = tuple(sc[None] for sc in scales)
        layer = 0
    return _paged_call(
        q, k_pages, v_pages, scales,
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        bool(interpret),
    )


def paged_decode_attention_reference(
    q, k_pages, v_pages, block_tables, lengths,
    k_scales=None, v_scales=None,
) -> jax.Array:
    """Pure-XLA reference: gather the per-row page view (dequantizing it
    when scale pools are given) and run the dense masked-softmax math
    (models/decode._cached_attention's paged gather branch, restated at
    the T=1 shape) — what the kernel is equivalence-tested against."""
    from pytorch_distributed_tpu.models.decode import gather_pages

    b, h, d = q.shape
    tables = jnp.asarray(block_tables, jnp.int32)

    def view(pool):  # one layer's pool is a stack of one
        return gather_pages(pool[None], 0, tables)

    ck, cv = (  # [B, S, Hkv*D] -> [B, S, Hkv, D]
        x.reshape(x.shape[:2] + (-1, d))
        for x in (view(k_pages), view(v_pages))
    )
    if k_scales is not None:
        from pytorch_distributed_tpu.ops.quant import dequantize_kv

        ck = dequantize_kv(ck, view(k_scales), q.dtype)
        cv = dequantize_kv(cv, view(v_scales), q.dtype)
    s = ck.shape[1]
    hkv = ck.shape[2]
    if hkv != h:
        rep = h // hkv
        ck = jnp.repeat(ck, rep, axis=2)
        cv = jnp.repeat(cv, rep, axis=2)
    scores = jnp.einsum(
        "bhd,bshd->bhs", q, ck, preferred_element_type=jnp.float32
    ) / (d**0.5)
    kpos = jnp.arange(s, dtype=jnp.int32)
    valid = kpos[None, None, :] <= jnp.asarray(lengths, jnp.int32)[
        :, None, None
    ]
    scores = jnp.where(valid, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhs,bshd->bhd", w.astype(cv.dtype), cv
    ).astype(q.dtype)
