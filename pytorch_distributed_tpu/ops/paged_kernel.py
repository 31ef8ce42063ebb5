"""Pallas TPU paged-attention decode kernel (+ XLA gather fallback).

Single-query attention for the paged serving engine
(serving/engine.PagedBatchedDecodeEngine): each batch row's K/V lives in
fixed-size PAGES of a shared pool ``[P, page, Hkv*D]`` (the heads merged
head-major on the minor axis, so a page's rows are whole lanes: the
stored shape of ``models/decode.init_paged_cache``), addressed
through a per-row block table — the vLLM cache layout, which is what
lets ``slots`` scale with the pool instead of ``slots x max_len``
(ROADMAP direction 1; serving practice surveyed in PAPERS.md #1).

``models/decode._cached_attention``'s gather path copies
``pool[layer, tables]`` into a ``[B, max_len, Hkv*D]`` window twice (K and
V), whatever the rows' depth, lays both out again with the heads split and
multiplies over all ``max_len`` positions. The kernel reads the pages where
they lie, each row to its own depth, on ``ops/latent_paged_kernel.py``'s
plan:

- grid ``(B,)``, sequential: one row a step, and inside the step a loop
  over the row's blocks of ``block_pages`` pages (``key_block_pages``: at
  most KEY_BLOCK positions), ``pos[b] // block + 1`` of them and no more (a
  free row, depth 0, costs one block). A block and not a page is the unit
  of work because a page streams in a fraction of the time a step of any
  kind costs;
- the stacked pools stay in HBM (``memory_space=pl.ANY``) and are never
  sliced: the layer index, the block tables and the depths ride scalar
  prefetch, and the body copies page ``tables[b, i]`` of layer ``layer``
  of every pool into one of two VMEM buffers a pool with
  ``pltpu.make_async_copy`` while the block before is computed. The copy of
  a row's first block is started by the row before it (its last block's
  turn), so only the very first block of the call is waited for with
  nothing to do;
- per block TWO MXU products for all heads at once, operands as stored: the
  caller-side ``_spread_heads`` lays the queries out as ``[H, Hkv*D]``,
  head h's D numbers in the lanes of ITS kv head and zeros elsewhere, so
  the scores of all heads are ``q_wide . K_block^T`` ([H, Hkv*D] x
  [Hkv*D, block], float32 out) and the weighted sum ``p . V_block``
  ([H, block] x [block, Hkv*D]), of which ``_own_lanes`` keeps each head's
  own kv head's D lanes. That spends Hkv times the FLOPs the heads need to
  fill the array (a dot a kv head is [group, D] x [D, block]: a few rows;
  at granite's shapes on the chip it read the same time, 0.36 against 0.37
  ms for four layers, the copies bound both: PERF.md section 6, PR 35);
  between the products the online softmax in float32, the probabilities
  rounded to the operands' dtype for the second product: the gather path's
  rounding points, reassociated block by block;
- int8 pages (``k_scales``/``v_scales`` [.., page, Hkv] float32) go through
  the same body: int8 moves to the queries' dtype exactly, and a position's
  scale multiplies its score (K) or its probability (V) instead of its D
  numbers (the scaled probabilities then stay float32 for the second
  product). The scales do not ride the async copies: Mosaic slices no page
  out of a pool whose minor axis is Hkv numbers wide (it pads that axis to
  128 lanes and takes only whole tiles of it). They are gathered outside,
  ``[B, blocks, Hkv, block]`` (4/D of the bytes of the int8 window the
  gather path copies), arrive a row a grid step, and reach the heads'
  [H, block] through one small product with the heads' 0/1 membership.

A layer that attends a sliding WINDOW passes each row's first visible
position (``first`` [B], a fourth scalar-prefetch operand, present only
then: ``windowed`` is static, and a call without it traces the kernel it
always did): the row's loop starts at block ``first // block`` instead of
0, keys before ``first`` are masked, and the blocks behind the window are
neither copied nor scored (their table entries may point at the scratch
page: the pool released them).

Every page of a started block is copied whole, also the pages past the
row's depth (table entries past a row's pages point at the scratch page):
the mask gives them probability 0, and what they hold is the pool's, never
uninitialised VMEM.

GQA + per-row depth masking match ``models/decode._cached_attention``'s
masked-softmax math up to online-softmax reassociation (floating-point
reordering only — the equivalence test pins allclose, and engine-level
token equality is pinned separately on the gather path).

``interpret`` is the caller's decision: the compiled kernel needs a TPU,
and a caller off the chip says ``interpret=True`` itself (the CPU tests
do; the engine's ``paged_attention="kernel_interpret"`` does) and gets
Pallas's plain interpreter, where a copy lands as it is started and a wait
does nothing (see ops/latent_paged_kernel.py on why not the TPU
interpreter). Left unset, an engine's paged attention is this kernel on a
TPU for a family with no dense cache, and the pure-XLA ``gather_pages``
fallback in models/decode.py otherwise, which is bit-identical to the dense
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
KEY_BLOCK = 512  # cache positions a paged reader takes at once
KERNEL_NAME = "paged_decode_attention"


def key_block_pages(n_pages: int, page: int) -> int:
    """Pages a paged reader takes at once: the largest divisor of a row's
    table that spans at most KEY_BLOCK positions."""
    want = max(1, KEY_BLOCK // page)
    return max(k for k in range(1, min(want, n_pages) + 1)
               if n_pages % k == 0)


def _membership(h: int, hkv: int, dtype) -> jax.Array:
    """[H, Hkv] 0/1: query head h reads kv head h // (H / Hkv)."""
    group = h // hkv
    head = jax.lax.broadcasted_iota(jnp.int32, (h, hkv), 0)
    first = jax.lax.broadcasted_iota(jnp.int32, (h, hkv), 1) * group
    return ((head >= first) & (head < first + group)).astype(dtype)


def _spread_heads(q: jax.Array, hkv: int) -> jax.Array:
    """[B, H, D] -> [B, H, Hkv*D]: head h's numbers in the lanes of its kv
    head, zeros in the others', so one product with a page's merged minor
    axis scores every head against its own kv head."""
    b, h, d = q.shape
    member = _membership(h, hkv, q.dtype)
    return (q[:, :, None, :] * member[None, :, :, None]).reshape(
        b, h, hkv * d)


def _own_lanes(o_wide: jax.Array, hkv: int) -> jax.Array:
    """[B, H, Hkv*D] -> [B, H, D]: of the weighted sum over every kv head's
    lanes, each head's own kv head's."""
    b, h, w = o_wide.shape
    member = _membership(h, hkv, jnp.bool_)
    return jnp.sum(
        jnp.where(member[None, :, :, None],
                  o_wide.reshape(b, h, hkv, w // hkv), 0), axis=2)


def _paged_kernel(
    layer_ref,  # [1] int32 (scalar prefetch)
    tables_ref,  # [B, n_pages] int32 (scalar prefetch)
    pos_ref,  # [B] int32 (scalar prefetch): the row's query position
    *rest,  # windowed: first_ref [B] int32 (scalar prefetch), the row's
    # first visible key; then q_ref [1, H, Hkv*D]: the row's queries,
    # spread (``_spread_heads``); k_ref, v_ref [L, P, page, Hkv*D], in HBM:
    # read by the copies below only; int8 pages: the row's scales ks_ref,
    # vs_ref [1, blocks, Hkv, block] f32; then o_ref [1, H, Hkv*D]; then the
    # scratch: kbuf, vbuf [2, block, Hkv*D] (the block being computed and
    # the one arriving), a DMA semaphore a pool and buffer [2, 2], slot [1]
    # int32 in SMEM (the buffer this row's first block is in), acc
    # [H, Hkv*D], m, l [H, 1] f32
    page: int,
    block_pages: int,
    scale: float,
    quantized: bool,
    windowed: bool,
):
    """Online softmax over one row's blocks of pages. With ``quantized``
    the copies move INT8 K/V pages — HBM traffic for a page drops to a
    quarter of the f32 kernel's — and their per-token f32 scales meet the
    scores and the probabilities in VMEM. Numerics past that are the
    full-precision kernel's exactly (same accumulator dtypes, same
    masking), so quantized-vs-gather equivalence is pinned the same way
    (tests/test_quant.py)."""
    if windowed:
        first_ref, *rest = rest
    q_ref, k_ref, v_ref, *rest = rest
    if quantized:
        ks_ref, vs_ref, *rest = rest
    o_ref, kbuf, vbuf, sems, slot_ref, acc_sc, m_sc, l_sc = rest
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    block = page * block_pages
    layer = layer_ref[0]
    max_blocks = tables_ref.shape[1] // block_pages

    def end_block(depth):
        """One past the last block a row at ``depth`` reads: never past the
        table, whatever the caller's depth says."""
        return jnp.clip(depth // block + 1, 1, max_blocks)

    def first_block(row):
        """The block the row's first visible key lies in (0: no window)."""
        if not windowed:
            return 0
        return jnp.clip(
            first_ref[row] // block, 0, end_block(pos_ref[row]) - 1)

    depth = pos_ref[b]  # keys first..depth (inclusive) are valid
    n_blocks = end_block(depth)
    lo = first_block(b)

    def pages_of(row, i, slot, do: str):
        """``do`` ("start" or "wait") the copy of every page of block i of
        ``row`` into buffers ``slot``: a page a copy a pool, in a loop and
        not unrolled (the body's trace is set-up time of every engine)."""
        def one_page(j, carry):
            page_id = tables_ref[row, i * block_pages + j]
            rows_j = pl.ds(pl.multiple_of(j * page, page), page)
            for n, (pool, buf) in enumerate(((k_ref, kbuf), (v_ref, vbuf))):
                getattr(pltpu.make_async_copy(
                    pool.at[layer, page_id], buf.at[slot, rows_j],
                    sems.at[n, slot]), do)()
            return carry

        jax.lax.fori_loop(0, block_pages, one_page, None)

    @pl.when(b == 0)
    def _first_block_of_the_call():
        slot_ref[0] = 0
        pages_of(0, lo, 0, "start")

    slot0 = slot_ref[0]
    acc_sc[:] = jnp.zeros_like(acc_sc[:])
    m_sc[:] = jnp.full_like(m_sc[:], NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc[:])

    def per_head(scales):
        """[Hkv, block] a kv head and position -> [H, block] a query head."""
        return jnp.dot(
            member, scales, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    if quantized:
        member = _membership(acc_sc.shape[0], ks_ref.shape[2], jnp.float32)

    def one_block(i, carry):
        slot = (slot0 + i - lo) % 2 if windowed else (slot0 + i) % 2

        # the next block — this row's, or the next row's first — arrives
        # in the other buffer while this one is computed
        @pl.when(i + 1 < n_blocks)
        def _next_block():
            pages_of(b, i + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(i + 1 == n_blocks, b + 1 < rows))
        def _next_row():
            nxt = b + 1
            pages_of(nxt, first_block(nxt), 1 - slot, "start")

        pages_of(b, i, slot, "wait")
        q = q_ref[0]  # [H, Hkv*D]
        s = jax.lax.dot_general(
            q, kbuf[slot].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, block]
        if quantized:
            s = s * per_head(ks_ref[0, i])
        kpos = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = kpos <= depth
        if windowed:
            seen = jnp.logical_and(seen, kpos >= first_ref[b])
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_sc[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        fix = jnp.exp(m_prev - m_new)
        l_sc[:] = l_sc[:] * fix + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            # float32 operands, all of their bits: a scaled probability
            # rounded to the queries' dtype and then the sum at the output
            # would round a sharp row's value twice (an int8 value is exact)
            weights, values, bits = (
                p * per_head(vs_ref[0, i]), vbuf[slot].astype(jnp.float32),
                jax.lax.Precision.HIGHEST)
        else:
            weights, values, bits = (
                p.astype(q.dtype), vbuf[slot].astype(q.dtype), None)
        acc_sc[:] = acc_sc[:] * fix + jax.lax.dot_general(
            weights, values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=bits,
        )
        m_sc[:] = m_new
        return carry

    # the row's first block holds its first visible key (position 0 where
    # there is no window): the running maximum is finite from it on
    jax.lax.fori_loop(lo, n_blocks, one_block, None)
    slot_ref[0] = (slot0 + n_blocks - lo) % 2 if windowed else (
        slot0 + n_blocks) % 2
    o_ref[0] = (acc_sc[:] / l_sc[:]).astype(o_ref.dtype)


# repolint: allow(jit-donation-decision) — functional attention op: the
# K/V pages belong to the serving engine's donated cache (aliased at the
# PROGRAM boundary, not here) and q is read by the caller's residual.
@functools.partial(
    jax.jit, static_argnames=("scale", "block_pages", "interpret"))
def _paged_call(q, k_pages, v_pages, scales, layer, block_tables, lengths,
                first, *, scale, block_pages, interpret):
    """``k_pages``/``v_pages`` are STACKED [L, P, page, Hkv*D] pools and
    ``layer`` [1] picks the layer; ``scales`` is ``()`` for full-precision
    pages or the ``(k_scales, v_scales)`` [L, P, page, Hkv] pools for
    int8 pages; ``first`` is ``()`` or ``(first [B],)``, a window's first
    visible key a row."""
    b, h, d = q.shape
    n_pages = block_tables.shape[1]
    page, w = k_pages.shape[2:]
    hkv = w // d
    block = block_pages * page
    max_blocks = n_pages // block_pages
    kernel = functools.partial(
        _paged_kernel,
        page=page, block_pages=block_pages, scale=scale,
        quantized=bool(scales), windowed=bool(first),
    )
    # the rows' scales, a block a leading index: [B, blocks, Hkv, block]
    scales = tuple(
        sc[layer[0], block_tables].reshape(b, max_blocks, block, hkv)
        .swapaxes(2, 3) for sc in scales
    )
    row_spec = pl.BlockSpec((1, h, w), lambda bi, *_: (bi, 0, 0))
    scale_spec = pl.BlockSpec(
        (1, max_blocks, hkv, block), lambda bi, *_: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(first),
        grid=(b,),
        in_specs=[row_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        + [scale_spec] * len(scales),
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, block, w), k_pages.dtype),
            pltpu.VMEM((2, block, w), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, w), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    o_wide = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_struct(
            (b, h, w), q.dtype, q, k_pages, v_pages, *scales),
        interpret=interpret,
        # a row's last block starts the next row's first copy, and the
        # buffer in turn is carried from row to row: the rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name=KERNEL_NAME,
    )(layer, block_tables, lengths, *first, _spread_heads(q, hkv), k_pages,
      v_pages, *scales)
    return _own_lanes(o_wide, hkv)


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
    scale: float | None = None,
    first: jax.Array | None = None,  # [B] int32: a window's first key
    interpret: bool | None = None,
) -> jax.Array:
    """Paged single-query attention, [B, H, D] -> [B, H, D]. ``lengths``
    is each row's query position: key j is attended iff j <= lengths[b]
    (the dense decode-step mask at T=1) and, where ``first`` is given (a
    sliding-window layer), j >= first[b]: the blocks before it are not
    read, so their table entries may be the scratch page. ``interpret=None`` means the
    compiled kernel and is an error off the chip — interpreter mode is
    never chosen for the caller.

    ``layer`` (a traced scalar inside the layer scan): the pools — and
    scale pools — are then the STACKED [L, P, ...] leaves of the serving
    cache and the kernel reads layer ``layer`` of them in place. Without
    it the pools are one layer's, as in the signature.

    ``scale`` multiplies the scores; left out it is D^-1/2 (a family whose
    attention is scaled by a published multiplier passes its own).

    ``k_scales``/``v_scales`` switch to int8 pages with per-token/per-head
    f32 scales, met in VMEM (the bandwidth-bound read moves quarter-width
    pages)."""
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
        () if first is None else (jnp.asarray(first, jnp.int32),),
        scale=float(d**-0.5 if scale is None else scale),
        block_pages=key_block_pages(block_tables.shape[1], k_pages.shape[2]),
        interpret=bool(interpret),
    )


def paged_decode_attention_reference(
    q, k_pages, v_pages, block_tables, lengths,
    k_scales=None, v_scales=None, scale=None, first=None,
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
    )
    scores = scores / (d**0.5) if scale is None else scores * scale
    kpos = jnp.arange(s, dtype=jnp.int32)
    valid = kpos[None, None, :] <= jnp.asarray(lengths, jnp.int32)[
        :, None, None
    ]
    if first is not None:
        valid &= kpos[None, None, :] >= jnp.asarray(first, jnp.int32)[
            :, None, None
        ]
    scores = jnp.where(valid, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhs,bshd->bhd", w.astype(cv.dtype), cv
    ).astype(q.dtype)
