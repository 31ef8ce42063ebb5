"""Pallas TPU decode kernel for the LATENT page pool (models/kimi_k2.py).

Single-query absorbed attention: the query of every head already lies in
the latent's space (``q_lat`` [B, H, W]: q_n W_uk | q_r | zero padding), the
cache is ONE paged leaf ``[L, P, page, W]`` whose page is key and value
both, shared by all heads. ``models/kimi_k2.attend_absorbed``'s gather path
copies ``pool[layer, tables]`` into a ``[B, max_len, W]`` window, whatever
the rows' depth, and reads that window for the scores and again for the
weighted sum. This kernel reads the pages where they lie, each row to its
own depth, each page ONCE for both products:

- grid ``(B,)``, sequential: one row a step, and inside the step a loop
  over the row's blocks of ``block_pages`` pages, ``pos[b] // block + 1``
  of them and no more (a free row, depth 0, costs one block). A block and
  not a page is the unit of work because a page (64 x 640 bf16 = 82 KB)
  streams in a tenth of the time a step of any kind costs;
- the stacked pool stays in HBM (``memory_space=pl.ANY``) and is never
  sliced: the layer index, the block tables and the depths ride scalar
  prefetch, and the body copies page ``tables[b, i]`` of layer ``layer``
  into one of two VMEM buffers with ``pltpu.make_async_copy`` while the
  block before is computed. The copy of a row's first block is started by
  the row before it (its last block's turn), so only the very first block
  of the call is waited for with nothing to do;
- per block two MXU products against the same VMEM block: scores
  ``q_lat . blk`` (operands as stored, float32 out), and the weighted sum
  of the block's first ``out_width`` columns (c_kv; the rope tail and the
  padding are never summed); between them the online softmax in float32,
  the probabilities rounded to the operands' dtype for the second product:
  the gather path's rounding points, reassociated block by block.

Every page of a started block is copied whole, also the pages past the
row's depth (table entries past a row's pages point at the scratch page):
the mask gives them probability 0, and what they hold is the pool's, never
uninitialised VMEM.

``interpret``: the compiled kernel needs a TPU; off it a caller says
``interpret=True`` itself (the CPU tests do; the engine's
``paged_attention="kernel_interpret"`` does) and gets Pallas's plain
interpreter, as ``ops/paged_kernel.py``'s callers do: a copy lands as it is
started and a wait does nothing there, so what it can show of the double
buffering is a block sent to the wrong buffer, not a wait left out. (The TPU
interpreter, ``pltpu.InterpretParams``, models the waits, but two of six
runs of tests/test_latent_paged_kernel.py hung in it when six ran at once,
as tier-1's workers do.) Read /opt/skills/guides/pallas_guide.md before
touching the body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.ops.flash_kernel import out_struct
from pytorch_distributed_tpu.ops.paged_kernel import NEG_INF

KERNEL_NAME = "latent_paged_decode"


def _latent_kernel(
    layer_ref,  # [1] int32 (scalar prefetch)
    tables_ref,  # [B, n_pages] int32 (scalar prefetch)
    pos_ref,  # [B] int32 (scalar prefetch): the row's query position
    q_ref,  # [1, H, W]
    pool_ref,  # [L, P, page, W], in HBM: read by the copies below only
    o_ref,  # [1, H, out_width]
    buf,  # [2, block, W]: the block being computed and the one arriving
    sems,  # one DMA semaphore a buffer
    slot_ref,  # [1] int32 in SMEM: the buffer this row's first block is in
    acc_sc,  # [H, out_width] f32
    m_sc,  # [H, 1] f32
    l_sc,  # [H, 1] f32
    *,
    page: int,
    block_pages: int,
    scale: float,
):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    block = page * block_pages
    out_width = acc_sc.shape[1]
    layer = layer_ref[0]
    depth = pos_ref[b]  # keys 0..depth (inclusive) are valid
    n_blocks = depth // block + 1

    def copies(row, i, slot):
        """Block i of ``row`` into buffer ``slot``, a page a copy."""
        return [
            pltpu.make_async_copy(
                pool_ref.at[layer, tables_ref[row, i * block_pages + j]],
                buf.at[slot, pl.ds(j * page, page)],
                sems.at[slot],
            )
            for j in range(block_pages)
        ]

    @pl.when(b == 0)
    def _first_block_of_the_call():
        slot_ref[0] = 0
        for copy in copies(0, 0, 0):
            copy.start()

    slot0 = slot_ref[0]
    acc_sc[:] = jnp.zeros_like(acc_sc[:])
    m_sc[:] = jnp.full_like(m_sc[:], NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc[:])

    def one_block(i, carry):
        slot = (slot0 + i) % 2

        # the next block — this row's, or the next row's first — arrives
        # in the other buffer while this one is computed
        @pl.when(i + 1 < n_blocks)
        def _next_block():
            for copy in copies(b, i + 1, 1 - slot):
                copy.start()

        @pl.when(jnp.logical_and(i + 1 == n_blocks, b + 1 < rows))
        def _next_row():
            for copy in copies(b + 1, 0, 1 - slot):
                copy.start()

        for copy in copies(b, i, slot):
            copy.wait()
        q = q_ref[0]  # [H, W]
        s = jax.lax.dot_general(
            q, buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, block]
        kpos = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= depth, s, NEG_INF)
        m_prev = m_sc[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        fix = jnp.exp(m_prev - m_new)
        l_sc[:] = l_sc[:] * fix + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * fix + jax.lax.dot_general(
            p.astype(q.dtype), buf[slot, :, :out_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_sc[:] = m_new
        return carry

    # block 0 holds position 0, which every row may see: the running
    # maximum is finite from the first block on
    jax.lax.fori_loop(0, n_blocks, one_block, None)
    slot_ref[0] = (slot0 + n_blocks) % 2
    o_ref[0] = (acc_sc[:] / l_sc[:]).astype(o_ref.dtype)


# repolint: allow(jit-donation-decision) — functional attention op: the
# pool belongs to the serving engine's donated cache (aliased at the
# PROGRAM boundary, not here).
@functools.partial(
    jax.jit,
    static_argnames=("scale", "out_width", "block_pages", "interpret"),
)
def _latent_call(q_lat, pool, layer, tables, pos, *, scale, out_width,
                 block_pages, interpret):
    b, h, w = q_lat.shape
    page = pool.shape[2]
    kernel = functools.partial(
        _latent_kernel, page=page, block_pages=block_pages, scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda bi, *_: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, out_width), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_pages * page, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, out_width), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_struct((b, h, out_width), q_lat.dtype, q_lat, pool),
        interpret=interpret,
        # a row's last block starts the next row's first copy, and the
        # buffer in turn is carried from row to row: the rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        name=KERNEL_NAME,
    )(layer, tables, pos, q_lat, pool)


def latent_paged_decode(
    q_lat: jax.Array,  # [B, H, W]: ONE query a row, in the latent's space
    pool: jax.Array,  # [L, P, page, W]: the stacked latent pool
    layer: jax.Array | int,
    block_tables: jax.Array,  # [B, n_pages] int32 page ids
    pos: jax.Array,  # [B] int32: the row's position (keys <= it are valid)
    *,
    scale: float,
    out_width: int,
    block_pages: int,
    interpret: bool | None = None,
) -> jax.Array:
    """softmax(scale * q_lat . latent) . latent[:, :out_width] over
    positions 0..pos[b] of row b's pages of layer ``layer``:
    [B, H, W] -> [B, H, out_width]. ``block_pages`` pages are fetched and
    computed at once; it divides the table's width. ``interpret=None``
    means the compiled kernel and is an error off the chip."""
    if interpret is None:
        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise RuntimeError(
                f"latent_paged_decode: the compiled kernel needs a TPU and "
                f"jax.devices()[0].platform is {platform!r}; pass "
                "interpret=True to run the Pallas interpreter"
            )
        interpret = False
    n_pages = block_tables.shape[1]
    if pool.ndim != 4 or pool.shape[3] != q_lat.shape[2]:
        raise ValueError(
            f"pool {pool.shape}: want [L, P, page, W] with the queries' "
            f"W={q_lat.shape[2]} on the minor axis"
        )
    if block_pages < 1 or n_pages % block_pages:
        raise ValueError(
            f"block_pages ({block_pages}) must divide the table's width "
            f"({n_pages}): the last block must end with the table"
        )
    return _latent_call(
        q_lat, pool,
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(pos, jnp.int32),
        scale=float(scale), out_width=int(out_width),
        block_pages=int(block_pages), interpret=bool(interpret),
    )
