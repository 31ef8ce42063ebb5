"""Pallas TPU decode kernel for the Mamba-2 state update (ops/ssm.ssm_step).

A decode step advances every live row's recurrent state by one position,

    S' = exp(dt A) S + (dt x) (x) B        y = S' C

per head h with S [P, N] float32 (``ops/ssm.py`` has the names). In plain XLA
that is two passes over the state a layer (``ssm_step`` under
``models/granitemoehybrid._mamba``'s two selects and its update in place: one
fusion that reads the rows' state and writes it back, a second that reads it
again for y: XLA fuses no reduction into an update in place, whichever of S
and S' the sum is written over). This kernel passes over the state ONCE, where
it lies in the serving cache's ``ssm`` leaf ``[L, rows + 1, H, P, N]``, on the
plan of ``ops/paged_kernel.py``:

- grid ``(B, H / hb)``, sequential: a row a step of the first axis, a block
  of ``hb`` heads (``head_block``: HEAD_BLOCK at most, inside one group) a
  step of the second. The rows are visited live ones first (``order``, a
  scalar-prefetch operand made from ``live`` outside); a dead lane (a free or
  mid-prefill row) is NEITHER READ NOR WRITTEN, so its state and the scratch
  row keep every bit, and its y is zero;
- the leaf stays in HBM (``memory_space=pl.ANY``) and comes back as the
  second output through ``input_output_aliases``: the body copies block
  ``leaf[layer, row, heads]`` into one of two VMEM buffers with
  ``pltpu.make_async_copy`` while the block before is computed, and the
  finished block back from one of two more while the next is computed (a
  block's way out is waited for two steps later, when its buffer is wanted
  again, and after the last live block);
- per block, on the vector unit in float32 and nothing rounded: S (zero where
  the row begins its sequence: a select, so whatever the last tenant left, a
  NaN too, is not carried), S' and, from the same registers, S' . C. The
  per-entry factor ``dt x`` [h, p] arrives lane-dense (heads x P on the
  lanes) and is needed one a SUBLANE; y [h, p] leaves the sum one a sublane
  and is wanted lane-dense. Both turns are 128 x 128 transposes (the XLU),
  ``heads_a_chunk`` heads at a time: ``dt x`` broadcast down the sublanes and
  transposed is a [rows, N] tile whose row r holds entry r's factor in every
  lane; (S' * C) transposed is summed over its sublanes into one lane-dense
  row of y. (A lane reduction an entry and a masked lane select to spread
  ``dt x`` read 223 us a layer at granite-4.0-h-micro's shapes where this
  reads 211 and the copies alone 208; y as a product on the MXU at
  ``highest`` streams every state row six times, 0.2 ms a layer at best:
  PERF.md section 6, PR 37.) ``exp(dt A)`` is a scalar a head, from SMEM;
  B and C a row a group, ordinary VMEM blocks;
- CHUNKS_A_TURN chunks are written out one after the other in the loop's body
  (not a ``fori_loop`` turn each): a chunk's two transposes wait on each
  other, and with one chunk a turn the body is that latency, 327 us a layer
  against 141 with four (my chip run, PR 37, the copies switched off).

The skip term D x, the gate, the norm and the convolution stay the model's.
The call sits in ONE jitted function, so a program that advances nine layers
a period traces and lowers the body once.

``interpret``: the compiled kernel needs a TPU; off it a caller says
``interpret=True`` itself (the CPU tests do; an engine built with
``paged_attention="kernel_interpret"`` does) and gets Pallas's plain
interpreter, where a copy lands as it is started and a wait does nothing
(ops/latent_paged_kernel.py says why not the TPU interpreter). Read
/opt/skills/guides/pallas_guide.md before touching the body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.ops.flash_kernel import out_struct

KERNEL_NAME = "ssm_state_step"
# heads a block: [32, 64, 128] float32 is 1 MB, two arriving and two leaving
# 4 MB of the 16 MB a kernel may use; blocks of 16 read 214 us a layer, of
# 32 211, of 8 254 (my chip run, PR 37)
HEAD_BLOCK = 32
CHUNKS_A_TURN = 4
LANES = 128


def _largest_divisor(n: int, most: int) -> int:
    return max(k for k in range(1, min(n, most) + 1) if n % k == 0)


def head_block(heads_a_group: int) -> int:
    """Heads a block: the largest divisor of a group's heads that is at most
    HEAD_BLOCK (a block's heads share one B and one C)."""
    return _largest_divisor(heads_a_group, HEAD_BLOCK)


def heads_a_chunk(hb: int, p: int) -> int:
    """Heads whose entries fill one row of lanes: the largest divisor of the
    block with ``heads x P`` at most 128 (two at granite's P = 64)."""
    return _largest_divisor(hb, max(1, LANES // p))


def _state_kernel(
    layer_ref,  # [1] int32 (scalar prefetch)
    order_ref,  # [B] int32 (scalar prefetch): the rows, live ones first
    n_live_ref,  # [1] int32 (scalar prefetch)
    fresh_ref,  # [B] int32 (scalar prefetch): the row begins its sequence
    decay_ref,  # [B, H] f32 in SMEM: exp(dt A)
    dtx_ref,  # [1, H / hc, hc * P] f32: the row's dt x, lane-dense
    b_ref,  # [1, 1, N] f32: the row's B of the block's group
    c_ref,  # [1, 1, N] f32
    leaf_ref,  # [L, rows + 1, H, P, N] f32, in HBM: read by the copies only
    y_ref,  # [1, H / hc, hc * P] f32: the row's y, lane-dense
    leaf_out,  # the same bytes as leaf_ref: written by the copies only
    inbuf,  # [2, hb, P, N]: the block being computed and the one arriving
    outbuf,  # [2, hb, P, N]: the block computed and the one leaving
    sem_in,  # a DMA semaphore a buffer
    sem_out,
    *,
    hb: int,
    hc: int,
    turn: int,
):
    i, j = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    layer = layer_ref[0]
    n_live = n_live_ref[0]
    step = i * nb + j
    slot = step % 2
    p, n = inbuf.shape[2:]
    chunks = hb // hc

    def arriving(ii, jj, sl):
        return pltpu.make_async_copy(
            leaf_ref.at[layer, order_ref[ii], pl.ds(jj * hb, hb)],
            inbuf.at[sl], sem_in.at[sl])

    def leaving(ii, jj, sl):
        return pltpu.make_async_copy(
            outbuf.at[sl],
            leaf_out.at[layer, order_ref[ii], pl.ds(jj * hb, hb)],
            sem_out.at[sl])

    @pl.when(jnp.logical_and(i >= n_live, j == 0))
    def _dead_lane():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < n_live)
    def _live_row():
        row = order_ref[i]
        fresh = fresh_ref[row] != 0

        @pl.when(step == 0)
        def _first_block_of_the_call():
            arriving(0, 0, 0).start()

        # the next block — this row's, or the next live row's first —
        # arrives in the other buffer while this one is computed
        wrap = j + 1 == nb
        ni = jnp.where(wrap, i + 1, i)

        @pl.when(ni < n_live)
        def _next_block():
            arriving(ni, jnp.where(wrap, 0, j + 1), 1 - slot).start()

        arriving(i, j, slot).wait()

        @pl.when(step >= 2)
        def _buffer_free():  # the block two steps back has left it
            leaving(i, j, slot).wait()

        bv, cv = b_ref[0], c_ref[0]  # [1, N]

        def one_chunk(k):
            """``hc`` heads: rows = hc * P state rows of N lanes."""
            at = j * chunks + k  # the chunk's row of dtx_ref and y_ref
            d = dtx_ref[0, pl.ds(at, 1), :]  # [1, rows]
            spread = jnp.broadcast_to(d, (n, hc * p)).T  # [rows, N]
            weighted = []
            for hh in range(hc):
                head = k * hc + hh
                s = jnp.where(fresh, 0.0, inbuf[slot, head])  # [P, N]
                new = decay_ref[row, j * hb + head] * s + spread[
                    hh * p:(hh + 1) * p] * bv
                outbuf[slot, head] = new
                weighted.append(new * cv)
            weighted = (weighted[0] if hc == 1
                        else jnp.concatenate(weighted, axis=0))
            y_ref[0, pl.ds(at, 1), :] = jnp.sum(
                weighted.T, axis=0, keepdims=True)

        def one_turn(t, carry):
            for u in range(turn):
                one_chunk(t * turn + u)
            return carry

        jax.lax.fori_loop(0, chunks // turn, one_turn, None)

        leaving(i, j, slot).start()

        @pl.when(jnp.logical_and(i + 1 == n_live, wrap))
        def _last_live_block():
            leaving(i, j, slot).wait()

            @pl.when(step >= 1)
            def _the_one_before():
                leaving(i, j, 1 - slot).wait()


# repolint: allow(jit-donation-decision) — functional op: the leaf belongs
# to the serving engine's donated cache (aliased at the PROGRAM boundary, and
# through the kernel by input_output_aliases), the rest is read by the caller.
@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _state_call(decay, dtx, b, c, leaf, layer, live, fresh, *, hb, interpret):
    bsz, h, p = dtx.shape
    g, n = b.shape[1:]
    hc = heads_a_chunk(hb, p)
    blocks_a_group = (h // g) // hb
    # the rows, live ones first, each kind in its own order (a stable sort
    # by ``not live``, written as each row's place: no sort op)
    n_live = jnp.sum(live, dtype=jnp.int32)
    place = jnp.where(
        live, jnp.cumsum(live) - 1, n_live + jnp.cumsum(~live) - 1)
    rows = jnp.arange(bsz, dtype=jnp.int32)
    order = jnp.sum(
        jnp.where(place[None, :] == rows[:, None], rows[None, :], 0), axis=1)

    def of_row(index):
        return lambda i, j, layer, order, *_: index(order[i], j)

    lane_dense = pl.BlockSpec(
        (1, h // hc, hc * p), of_row(lambda r, j: (r, 0, 0)))
    of_group = pl.BlockSpec(
        (1, 1, n), of_row(lambda r, j: (r, j // blocks_a_group, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bsz, h // hb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            lane_dense, of_group, of_group,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[lane_dense, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, hb, p, n), jnp.float32),
            pltpu.VMEM((2, hb, p, n), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    y, leaf = pl.pallas_call(
        functools.partial(
            _state_kernel, hb=hb, hc=hc,
            turn=_largest_divisor(hb // hc, CHUNKS_A_TURN)),
        grid_spec=grid_spec,
        out_shape=[
            out_struct((bsz, h // hc, hc * p), jnp.float32, dtx, leaf),
            out_struct(leaf.shape, leaf.dtype, leaf),
        ],
        # operand 8 (the four scalars, decay, dtx, b, c, then the leaf) IS
        # output 1: the blocks are written where they were read
        input_output_aliases={8: 1},
        interpret=interpret,
        # a block starts the next one's copy and the buffers are carried
        # from step to step: the steps run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=KERNEL_NAME,
    )(layer, order, n_live.reshape(1), fresh.astype(jnp.int32), decay,
      dtx.reshape(bsz, h // hc, hc * p), b, c, leaf)
    return y.reshape(bsz, h, p), leaf


def ssm_state_step(
    x: jax.Array,  # [B, H, P]: one position a row
    dt: jax.Array,  # [B, H] float32, 0 where the lane holds no token
    a: jax.Array,  # [H] float32 (negative)
    b: jax.Array,  # [B, G, N]; head h reads group h // (H / G)
    c: jax.Array,  # [B, G, N]
    leaf: jax.Array,  # [L, rows + 1, H, P, N] float32: EVERY layer's state
    layer: jax.Array | int,  # the layer advanced (traced in the layer scan)
    live: jax.Array,  # [B] bool: the lane holds a token
    fresh: jax.Array,  # [B] bool: the row begins its sequence (from zero)
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``ops/ssm.ssm_step`` for batch row b on ``leaf[layer, b]``, in one
    pass where the state lies. Returns (y [B, H, P] float32 without the skip
    term, zero for a dead lane; the leaf, ``leaf[layer, b]`` advanced for
    every live b and every other byte as it was). ``interpret=None`` means
    the compiled kernel and is an error off the chip: the interpreter is
    never chosen for the caller."""
    if interpret is None:
        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise RuntimeError(
                f"ssm_state_step: the compiled kernel needs a TPU and "
                f"jax.devices()[0].platform is {platform!r}; pass "
                "interpret=True to run the Pallas interpreter"
            )
        interpret = False
    bsz, h, p = x.shape
    g = b.shape[1]
    if leaf.ndim != 5 or leaf.shape[2:4] != (h, p) or leaf.shape[1] < bsz \
            or leaf.dtype != jnp.float32 or h % g:
        raise ValueError(
            f"leaf {leaf.shape} {leaf.dtype}: want float32 [L, rows, {h}, "
            f"{p}, N] with at least {bsz} rows, and the heads ({h}) a "
            f"multiple of the groups ({g})"
        )
    return _state_call(
        jnp.exp(dt * a), dt[..., None] * x.astype(jnp.float32),
        b.astype(jnp.float32), c.astype(jnp.float32), leaf,
        jnp.asarray(layer, jnp.int32).reshape(1), live, fresh,
        hb=head_block(h // g), interpret=bool(interpret),
    )
