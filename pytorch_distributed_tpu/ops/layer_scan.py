"""Scan-over-layers with an optional latency-hiding prefetch window.

Both model families run their transformer stack as one ``lax.scan`` over
stacked [L, ...] block params, with an optional ``block_transform``
(explicit FSDP's just-in-time per-layer all_gather) applied inside the
rematted body. That just-in-time schedule serialises on a real
interconnect: the scan body is

    gather(l) -> block(l) -> gather(l+1) -> block(l+1) -> ...

with every gather on the critical path (XLA cannot overlap a collective
across a while-loop iteration boundary, so the MXU idles for each one —
the exact stall SimpleFSDP (arXiv:2411.00284) removes by
bucketing + reordering).

``scan_layers`` here factors the scan out of the models and adds a
**windowed double-buffer schedule**: with window W = prefetch_buffers + 1
the scan runs over L/W windows, and each window's (rematted) body issues
ALL W layer gathers before the first block computes:

    gather(l) ; gather(l+1) ; ... ; gather(l+W-1)   # no deps between them
    block(l) -> block(l+1) -> ... -> block(l+W-1)

Only gather(l) is on the critical path — gather(l+j) has no data
dependence on block(l..l+j-1), so XLA's latency-hiding scheduler lowers
it to an ``all-gather-start`` at the window top with the ``-done`` just
before block(l+j): layer l+1's params stream in while layer l computes.
Because the transform runs INSIDE the rematted window body, backward
replays the window: it re-gathers all W layers up front (the same
prefetch, mirrored) and the AD-transposed ``psum_scatter``s of the
window's grads interleave with the remaining backward compute instead of
each stalling its own layer. Residuals stay the sharded xs slices + the
per-window carry — gathered params are never saved, preserving ZeRO-3's
memory contract (the live-buffer cost is exactly W gathered layers).

Numerics: each layer sees byte-identical inputs in the identical order
(the window only reshapes the stacked leaves and hoists independent
collectives), so the schedule is bit-equivalent to the W=1 scan — pinned
by tests/test_prefetch.py.
"""

from __future__ import annotations

from typing import Callable

import jax

from pytorch_distributed_tpu.ops.remat import apply_remat


def effective_window(prefetch_buffers: int, n_layer: int) -> int:
    """Largest divisor of ``n_layer`` that is <= prefetch_buffers + 1.

    ``prefetch_buffers`` is a SOFT size: windows must tile the layer
    stack exactly (a ragged tail window would compile a second block
    body), so the request is rounded down to the nearest divisor — 1
    (no prefetch) in the worst case, n_layer (one window spanning the
    whole stack) at most."""
    if prefetch_buffers <= 0 or n_layer <= 1:
        return 1
    want = min(prefetch_buffers + 1, n_layer)
    for w in range(want, 0, -1):
        if n_layer % w == 0:
            return w
    return 1


def scan_layers(
    block_fn: Callable,
    carry,
    blocks,
    extras=None,
    *,
    remat_mode: str,
    block_transform: Callable | None = None,
    prefetch_buffers: int = 0,
    unroll: int = 1,
):
    """Run ``block_fn`` over every layer of a stacked [L, ...] param tree.

    ``block_fn(carry, bp, extra) -> carry`` consumes one layer's
    (already-transformed) params plus its slice of ``extras`` (e.g. the
    layer index driving per-layer dropout keys; pass None when unused).
    ``block_transform`` maps each layer's sliced subtree before use (the
    explicit-FSDP gather hook); with ``prefetch_buffers`` > 0 the
    transforms of a whole window are hoisted above its compute (see
    module docstring). Returns the final carry.

    The scan has no per-layer outputs: state a block updates layer by
    layer (decode's stacked KV cache) belongs in ``carry``, addressed by
    a layer index riding ``extras``, so a donated buffer is written where
    it lies instead of being sliced out as xs and stacked back as ys.
    """
    n_layer = jax.tree.leaves(blocks)[0].shape[0]
    window = effective_window(prefetch_buffers, n_layer)

    def transform(bp):
        return block_transform(bp) if block_transform is not None else bp

    if window <= 1:
        # The classic per-layer scan (bit-identical to the pre-refactor
        # model code): transform + compute inside one rematted body.
        def body(c, xs):
            bp, extra = xs
            return block_fn(c, transform(bp), extra), None

        carry, _ = jax.lax.scan(
            apply_remat(body, remat_mode),
            carry,
            (blocks, extras),
            unroll=unroll,
        )
        return carry

    n_windows = n_layer // window
    blocks_w = jax.tree.map(
        lambda a: a.reshape((n_windows, window) + a.shape[1:]), blocks
    )
    extras_w = jax.tree.map(
        lambda a: a.reshape((n_windows, window) + a.shape[1:]), extras
    )

    def window_body(c, xs):
        bw, ew = xs
        # Prefetch: every gather in the window is issued before any
        # block computes. The loop is unrolled at trace time (window is
        # static), so these are W independent collectives in one body.
        gathered = [
            transform(jax.tree.map(lambda a, j=j: a[j], bw))
            for j in range(window)
        ]
        for j in range(window):
            c = block_fn(
                c, gathered[j], jax.tree.map(lambda a, j=j: a[j], ew)
            )
        return c, None

    carry, _ = jax.lax.scan(
        apply_remat(window_body, remat_mode),
        carry,
        (blocks_w, extras_w),
        unroll=unroll,
    )
    return carry
