"""KV-cache autoregressive decoding for both model families.

The reference repo has no inference path at all — training only. A complete
framework needs one: this module adds prefill + single-token decode over a
preallocated KV cache, and the ``generate`` / ``generate_tp`` /
``generate_fsdp`` entry points for gpt2 and llama params produced by
``models.get_model(cfg)`` — dense AND MoE variants (routing is per-token
and cache-free, see ``_moe_mlp``).

Since the serving PR, the public ``generate*`` entry points are thin compat
shims over ``serving.engine.DecodeEngine`` — the two-program
(prefill / decode-step) serving fast path with a DONATED, pooled KV cache,
bucketed prompt compilation, and traced sampling scalars. The original
one-jit monolithic programs survive as ``generate_monolithic`` /
``generate_tp_monolithic`` / ``generate_fsdp_monolithic``: the reference
implementations the engine is pinned bit-equal against
(tests/test_serving.py).

Design (TPU-first):
- The cache is a pytree of stacked per-layer tensors ``k/v [L, B, S, Hkv, D]``
  preallocated at ``max_len`` — static shapes throughout; the current length
  ``pos`` is a traced scalar. ``forward`` handles both prefill (T = prompt
  length) and decode (T = 1) with one code path: new keys/values are
  ``dynamic_update_slice``d into the cache at ``pos`` and attention masks
  key positions ``> pos + i`` (padding beyond the write point is masked
  out, so stale cache contents are never read — the invariant that makes
  both prompt bucketing and dirty-buffer cache donation sound).
- Layers run under the shared ``ops/layer_scan.scan_layers`` scan-over-
  stacked-params, so the windowed double-buffer prefetch schedule training
  uses applies to ZeRO-3 decode as well (``block_transform`` +
  ``prefetch_buffers``). The stacked cache rides the scan's CARRY beside
  the activations and the layer index is the only per-layer input: each
  block scatters its new tokens into the stacked leaves at ``(layer, ...)``
  and attention reads them back at ``(layer, ...)``, so a donated cache is
  updated where it lies — no per-layer slice out, no stacked copy back.
  The PAGED pool is stored ``[L, P, page, Hkv*D]``: whole lanes on the
  minor axis, so the TPU runtime keeps it row-major and no program
  converts it at entry and exit (a minor axis of D = 64, half a lane, is
  stored page-axis-minor, and the scatter and the gather then cost four
  whole-pool copies a dispatch: PERF.md section 6, PR 31).
- Attention here is the naive einsum path in f32: decode is matmul-light
  ([B, H, T, S] with T = 1), so flash-kernel dispatch is pointless.
- Sampling params (``temperature``/``top_k``/``top_p``) are TRACED runtime
  scalars on every path — a serving loop changing sampling configs never
  recompiles; only greedy-vs-sampled is a static bool (temperature 0 needs
  a different program shape: no division, no sort, no key).

No dropout (inference), no remat (nothing to save).
"""

from __future__ import annotations

import functools
import importlib
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.ops.layer_scan import scan_layers
from pytorch_distributed_tpu.ops.layers import (
    activation,
    dense,
    layer_norm,
    rms_norm,
)
from pytorch_distributed_tpu.ops.rope import apply_rope, rope_angles

Params = dict[str, Any]
Cache = dict[str, jax.Array]


class Serving(NamedTuple):
    """What a family asks of an engine, so that no engine reads a family's
    name. A family that asks for more than the defaults (the dense
    families': a cache of pages or of [B, max_len] rows, nothing else)
    declares it in its own module's ``serving(cfg)``, these fields as a
    dict; ``serving`` below looks it up."""

    # ``init_cache`` has a [B, max_len] layout; False: the PAGED pool only,
    # which a decode step reads through a kernel on a TPU where
    # ``paged_attention`` is left unset (there is no dense engine for the
    # gather to be bit-identical to)
    dense_cache: bool = True
    # Bytes of recurrent state a ROW holds whatever its depth, beside its
    # pages. Non-zero means: ``init_paged_cache`` takes ``rows`` and returns
    # the state leaves [L, rows + 1, ...] beside the pool; a prefill call
    # names each batch row's state row (``forward(state_rows=)``); a row that
    # starts at position 0 starts from zero state; a prefix cached by another
    # row cannot be taken over (nobody holds the state at its end).
    state_bytes_per_row: int = 0
    # Positions a row keeps in a layer of the family's WINDOW group of
    # pages (0: one group, every layer keeps every position). Non-zero
    # means: the cache holds a second pool ``k_w``/``v_w`` over the layers
    # that attend a sliding window, beside ``k``/``v`` over those that
    # attend everything (``init_paged_cache(window_pool_pages=)``); a row
    # has two block tables, handed to ``forward`` side by side ([B, 2 n]:
    # the full group's, then the window group's, both indexed by absolute
    # page number); the engine releases a window-group page once no query
    # still to come can see it; a prefix cached by another row cannot be
    # taken over (its chunk would have to keep the window group's last
    # ``window`` positions).
    window: int = 0
    # names, in order, of the int32 counts ``forward(return_aux=True)``
    # hands back beside the logits, counted per program kind
    aux_counts: tuple[str, ...] = ()
    # the counters the family's readers ask for of what the ENGINE knows of
    # a dispatch, by the counter's name
    # (``PagedBatchedDecodeEngine._count_dispatch`` lists what it can count)
    counters: tuple[str, ...] = ()
    # what a paged engine cannot serve the family with, {feature: why}:
    # "mesh", "kv_quant", "weight_quant", "adapters", "speculative_k",
    # "handoff" (a prefill or decode worker's role, or a call to export or
    # import a row); "prefix" says why the family takes no prefix hit
    # (nothing asks for one: it is taken or not)
    unserved: dict[str, str] = {}


def serving(cfg: ModelConfig) -> Serving:
    if cfg.family in ("kimi_k2", "granitemoehybrid", "mellum"):
        family = importlib.import_module(
            f"pytorch_distributed_tpu.models.{cfg.family}")
        return Serving(**family.serving(cfg))
    return Serving()


def kv_bytes_per_position(cfg: ModelConfig, kv_quant: str = "none",
                          group: str = "full") -> int:
    """Bytes one GLOBAL cache position costs across all layers (of a family
    with two page groups, ``serving(cfg).window``: across the layers of
    ``group``, "full" or "window"), in the
    family's own layout (TP divides the head dim across shards, so the
    global figure is the comparable one either way). Per-head K and V;
    int8 pages carry one f32 scale per token per KV head next to the
    values (ops/quant.quantize_kv), so a quantized position costs
    head_dim + 4 bytes per head instead of head_dim x itemsize. The
    kimi_k2 family's page holds ONE latent all heads share
    (kv_lora_rank + qk_rope_head_dim numbers, stored in whole lanes)."""
    if kv_quant == "int8":
        return cfg.n_layer * 2 * cfg.kv_heads * (cfg.head_dim + 4)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if cfg.family == "kimi_k2":
        from pytorch_distributed_tpu.models.kimi_k2 import page_width

        return cfg.n_layer * page_width(cfg) * itemsize
    layers = cfg.n_layer
    if cfg.family == "granitemoehybrid":  # its attention layers only
        layers = cfg.layer_types.count("attention")
    if cfg.family == "mellum":  # the layers whose pages the group holds
        layers = cfg.layer_types.count(
            "full_attention" if group == "full" else "sliding_attention")
    return layers * 2 * cfg.kv_heads * cfg.head_dim * itemsize


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=None,
    n_kv: int | None = None,
) -> Cache:
    """Preallocate a [L, B, max_len, Hkv, D] key/value cache pair.
    ``n_kv`` overrides the head count for tensor-parallel decode, where
    each shard caches only its LOCAL kv heads (1/tp of the HBM)."""
    if max_len > cfg.n_ctx:
        raise ValueError(f"max_len {max_len} exceeds n_ctx {cfg.n_ctx}")
    if cfg.family == "kimi_k2":
        raise NotImplementedError(
            "the kimi_k2 family caches a LATENT page pool "
            "(init_paged_cache -> models/kimi_k2.init_latent_pool): serve "
            "it through PagedBatchedDecodeEngine; there is no dense "
            "[B, max_len] cache layout for it"
        )
    if not serving(cfg).dense_cache:
        raise NotImplementedError(
            f"the {cfg.family} family keeps more than one pool of pages a "
            "row (per-row recurrent state, init_paged_cache(rows=), or a "
            "window group of pages, init_paged_cache(window_pool_pages=)): "
            "serve it through PagedBatchedDecodeEngine; there is no dense "
            "[B, max_len] cache layout for it"
        )
    dtype = jnp.dtype(dtype or cfg.dtype)
    shape = (
        cfg.n_layer, batch, max_len, n_kv or cfg.kv_heads, cfg.head_dim
    )
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(
    cfg: ModelConfig, pool_pages: int, page_size: int, dtype=None,
    n_kv: int | None = None, kv_quant: str = "none",
    rows: int | None = None, window_pool_pages: int | None = None,
) -> Cache:
    """Preallocate a PAGED [L, pool_pages, page_size, Hkv*D] key/value
    pool pair (serving/block_pool.py owns the host-side allocation; page
    0 is the reserved scratch page). ``n_kv`` as in ``init_cache``. The
    minor axis merges the heads HEAD-MAJOR (head g is columns
    [g*D, (g+1)*D)), so a position's K (or V) is one run of whole lanes
    and a tensor-parallel shard of the axis is Hkv/tp whole heads.

    ``kv_quant="int8"``: the value pools are int8 and two f32 scale
    pools ``k_scale``/``v_scale`` of [L, pool_pages, page_size, Hkv]
    ride alongside — one symmetric scale per written token per KV head
    (ops/quant.py: per-token granularity is what keeps incremental page
    writes sound), cutting a page's bytes to ~(D + 4)/(4D) of the f32
    pool.

    ``rows``: for a family that keeps recurrent state a row
    (``serving(cfg).state_bytes_per_row``), how many rows the engine runs;
    the state
    leaves [L, rows + 1, ...] come back beside the pool (row ``rows`` is
    the scratch row, as page 0 is the scratch page).

    ``window_pool_pages``: for a family with a window group of pages
    (``serving(cfg).window``), that group's capacity; its pool ``k_w``/
    ``v_w`` comes back beside ``k``/``v``, each over its own layers."""
    if kv_quant not in ("none", "int8"):
        raise ValueError(
            f"kv_quant must be 'none' or 'int8', got {kv_quant!r}"
        )
    if cfg.family == "kimi_k2":
        # ONE leaf [L, pool_pages, page_size, C + Dr]: the latent, read
        # expanded in prefill and absorbed in decode (models/kimi_k2.py)
        if kv_quant != "none" or n_kv is not None:
            raise NotImplementedError(
                "kimi_k2's latent pages are neither quantized nor "
                "head-sharded: a page holds one latent all heads share"
            )
        from pytorch_distributed_tpu.models.kimi_k2 import init_latent_pool

        return init_latent_pool(cfg, pool_pages, page_size, dtype)
    if cfg.family == "granitemoehybrid":
        if kv_quant != "none" or n_kv is not None or rows is None:
            raise NotImplementedError(
                "granitemoehybrid's cache is an unquantized, unsharded pool "
                "over its attention layers beside per-row state: say how "
                "many rows (rows=), and neither kv_quant nor n_kv"
            )
        from pytorch_distributed_tpu.models import granitemoehybrid

        return granitemoehybrid.init_cache(
            cfg, pool_pages, page_size, rows, dtype)
    if cfg.family == "mellum":
        if kv_quant != "none" or n_kv is not None or not window_pool_pages:
            raise NotImplementedError(
                "mellum's cache is two unquantized, unsharded pools, one a "
                "page group: say how many pages the window group has "
                "(window_pool_pages=), and neither kv_quant nor n_kv"
            )
        from pytorch_distributed_tpu.models import mellum

        return mellum.init_cache(
            cfg, pool_pages, page_size, window_pool_pages, dtype)
    dtype = jnp.dtype(dtype or cfg.dtype)
    hkv = n_kv or cfg.kv_heads
    shape = (cfg.n_layer, pool_pages, page_size, hkv * cfg.head_dim)
    if kv_quant == "int8":
        scales = shape[:-1] + (hkv,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.ones(scales, jnp.float32),
            "v_scale": jnp.ones(scales, jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gather_pages(pool: jax.Array, layer, block_tables: jax.Array):
    """Stacked [L, P, page, ...] pool + layer index + [B, n_pages]
    tables -> the [B, S, ...] contiguous per-row view of that layer
    dense attention expects (S = n_pages * page; trailing dims pass
    through, so value pools [L, P, page, Hkv*D], their int8 scale pools
    [L, P, page, Hkv] and kimi_k2's latent pool gather through the same
    code). ONE gather
    indexed by (layer, page id): the layer's pool is never sliced out on
    its own. Unallocated table entries point at the scratch page —
    garbage the ``pos`` mask already excludes, exactly like a dense
    row's unwritten tail. This is the XLA fallback the CPU rig runs; the
    Pallas decode kernel (ops/paged_kernel.py) reads pages in place
    instead."""
    b, n_pages = block_tables.shape
    page = pool.shape[2]
    return pool[layer, block_tables].reshape(
        (b, n_pages * page) + pool.shape[3:]
    )


def _cached_attention(q, cache, layer, pos, block_tables=None,
                      paged_impl="gather", kv_quant="none", scale=None,
                      window=None):
    """q [B, T, H, D] against layer ``layer`` of the stacked cache
    ({"k", "v"} leaves [L, B, S, Hkv, D]); queries sit at
    global positions pos..pos+T-1, keys j are valid iff j <= pos + i.
    ``pos`` is a scalar (every row at the same position — the single-request
    paths) or a [B] vector (slot-batched decode: each row carries its own
    position, so each row's mask — and therefore which cache rows it can
    ever read — is independent of its neighbours).

    ``block_tables`` [B, n_pages] switches to the PAGED cache layout
    (k/v are [L, P, page, Hkv*D] pools): the gather fallback materialises
    the per-row [B, S, Hkv*D] view, splits the heads out of its minor axis
    AFTER the gather, and runs the identical masked math (bit-equal to the
    dense path wherever the valid positions hold the same values); for
    single-token decode, ``paged_impl`` of "kernel"/"kernel_interpret"
    dispatches the Pallas paged-attention kernel instead, which reads
    the pages in place, each row's to its depth.

    ``kv_quant="int8"`` (paged only): ``cache`` additionally carries
    ``k_scale``/``v_scale`` pools; the gather path dequantizes the
    gathered view (one int8->f32 convert per K and V — the audit's q8
    cast budget counts them) and runs the identical masked math, the
    kernel path meets the scales in VMEM (HBM only ever moves int8 pages
    + scales).

    ``scale`` multiplies the scores in place of D^-1/2 (a family whose
    attention is scaled by a published multiplier), on either path.

    ``window`` (a sliding-window layer): the query at position i attends
    keys i - window + 1 .. i only. The kernel then starts at the block the
    window begins in (the table's entries before it may be the scratch
    page); the gather masks the same keys."""
    if block_tables is not None and q.shape[1] == 1 and (
        paged_impl in ("kernel", "kernel_interpret")
    ):
        from pytorch_distributed_tpu.ops.paged_kernel import (
            paged_decode_attention,
        )

        out = paged_decode_attention(
            q[:, 0], cache["k"], cache["v"], block_tables, pos,
            k_scales=cache.get("k_scale"), v_scales=cache.get("v_scale"),
            layer=layer, scale=scale,
            first=None if window is None else jnp.maximum(
                pos - (window - 1), 0),
            interpret=paged_impl == "kernel_interpret",
        )
        return out[:, None]
    b, t, h, d = q.shape
    if block_tables is not None:
        ck, cv = (  # [B, S, Hkv*D] -> [B, S, Hkv, D]
            x.reshape(x.shape[:2] + (-1, d))
            for x in (
                gather_pages(cache["k"], layer, block_tables),
                gather_pages(cache["v"], layer, block_tables),
            )
        )
        if kv_quant == "int8":
            from pytorch_distributed_tpu.ops.quant import dequantize_kv

            ck = dequantize_kv(
                ck, gather_pages(cache["k_scale"], layer, block_tables),
                q.dtype,
            )
            cv = dequantize_kv(
                cv, gather_pages(cache["v_scale"], layer, block_tables),
                q.dtype,
            )
    else:
        ck, cv = cache["k"][layer], cache["v"][layer]
    s, hkv = ck.shape[1], ck.shape[2]
    # Grouped-query heads: query head i reads K/V head i // (H/Hkv). The
    # queries are grouped under their K/V head instead of K and V being
    # repeated per query head: a repeated [B, S, H, D] view is written out
    # whole (in float32, for K) and read back in every layer.
    grouped = hkv != h
    if grouped:
        q = q.reshape(b, t, hkv, h // hkv, d)
    scores = jnp.einsum(
        "btgrd,bsgd->bgrts" if grouped else "bthd,bshd->bhts", q, ck,
        preferred_element_type=jnp.float32,
    )
    scores = scores / (d**0.5) if scale is None else scores * scale
    qpos = jax.lax.broadcasted_iota(jnp.int32, (t, s), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (t, s), 1)
    if getattr(pos, "ndim", 0):  # per-row positions -> [B, 1, T, S] mask
        valid = kpos[None] <= pos[:, None, None] + qpos[None]
        if window is not None:
            valid &= kpos[None] > pos[:, None, None] + qpos[None] - window
        valid = valid[:, None, None] if grouped else valid[:, None]
        scores = jnp.where(valid, scores, -1e30)
    else:
        valid = kpos <= pos + qpos
        if window is not None:
            valid &= kpos > pos + qpos - window
        scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    if grouped:
        return jnp.einsum("bgrts,bsgd->btgrd", w, cv).reshape(b, t, h, d)
    return jnp.einsum("bhts,bshd->bthd", w, cv)


def blocked_attention(q, cache, layer, pos, block_tables, *, window=None,
                      scale=None):
    """q [B, T, H, D] at positions pos[b]..pos[b]+T-1 against layer
    ``layer`` of the paged pools {"k", "v"} [L, P, page, Hkv*D], a block of
    cache positions at a time (``ops/paged_kernel.key_block_pages``) under a
    running (online) softmax in float32: exact, each row to its own depth
    pos[b] + T and, with ``window`` (a sliding-window layer: the query at i
    attends keys i - window + 1 .. i), from the block its first query's
    window begins in, so no temporary grows with the table's length and the
    table's entries behind the window are never read (the plan of
    ``models/kimi_k2.attend_expanded``). Returns [B, T, H, D]."""
    from pytorch_distributed_tpu.ops.paged_kernel import key_block_pages

    b, t, h, d = q.shape
    page, width = cache["k"].shape[2:]
    hkv = width // d
    kb_pages = key_block_pages(block_tables.shape[1], page)
    kb = kb_pages * page
    scale = d**-0.5 if scale is None else scale

    def one_row(args):
        q_b, table, p0 = args  # [T, H, D], [n_pages], ()
        qpos = p0 + jnp.arange(t, dtype=jnp.int32)
        qg = q_b.reshape(t, hkv, h // hkv, d)

        def one_block(i, carry):
            m, l, acc = carry
            pids = jax.lax.dynamic_slice_in_dim(table, i * kb_pages, kb_pages)
            k_blk, v_blk = (
                cache[n][layer, pids].reshape(kb, hkv, d).astype(q.dtype)
                for n in ("k", "v"))
            s = jnp.einsum(
                "tgrd,sgd->grts", qg, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
            kpos = i * kb + jnp.arange(kb, dtype=jnp.int32)
            seen = kpos[None, :] <= qpos[:, None]
            if window is not None:
                seen &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(seen, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # (a query whose keys of this block all lie behind its window
            # keeps m at -1e30: its p is masked, not exp(0))
            p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
            fix = jnp.exp(m - m_new)
            acc = acc * fix[..., None] + jnp.einsum(
                "grts,sgd->grtd", p.astype(q.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return m_new, l * fix + jnp.sum(p, axis=-1), acc

        lo = 0 if window is None else jnp.maximum(p0 - (window - 1), 0) // kb
        m, l, acc = jax.lax.fori_loop(
            lo, (p0 + t + kb - 1) // kb, one_block, (
                jnp.full((hkv, h // hkv, t), -1e30, jnp.float32),
                jnp.zeros((hkv, h // hkv, t), jnp.float32),
                jnp.zeros((hkv, h // hkv, t, d), jnp.float32),
            ))
        return (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(
            t, h, d).astype(q.dtype)

    return jax.lax.map(one_row, (q, block_tables, pos))


def _write(leaf, layer, new, pos, block_tables=None):
    """Insert new [B, T, Hkv, D] into layer ``layer`` of the STACKED cache
    leaf [L, B, S, Hkv, D] at time offset pos, and return the whole leaf:
    the layer is one more index of the update, so a leaf that is a loop
    carry (the layer scan's) is written in place. A [B] vector pos writes
    each row at ITS OWN offset (slot-batched decode) via one scatter —
    pure data movement either way, so a row written at pos[b] holds
    bit-identical values to the scalar-pos write at the same offset.

    With ``block_tables`` [B, n_pages] the leaf is a PAGED pool
    [L, P, page, ...] (value pools merge their heads, [..., Hkv*D]; scale
    pools [..., Hkv]; the latent pool [..., width]) and ``new``'s axes
    past [B, T] are flattened to the leaf's: token i of row b lands at page
    ``table[b, (pos[b]+i) // page]``, offset ``(pos[b]+i) % page`` — one
    scatter, pure data movement again. The host guarantees distinct live
    rows write distinct pages (the copy-on-write discipline of
    serving/block_pool.py), so the scatter has no cross-row collisions;
    free rows' tables are all-zero, colliding harmlessly on the
    never-read scratch page.

    Per-row windows past a row's extent are SAFE, not clamped: the
    speculative verify step (serving engines, ``speculative_k``) writes
    T = k+1 tokens per row, and a deep row's draft lanes can index past
    its table (paged) or past ``max_len`` (dense). XLA's default gather/
    dynamic_update_slice clamping would silently redirect those writes
    onto LIVE positions, so they are handled explicitly: paged lanes
    past the table redirect to the never-read scratch page (page 0),
    and dense per-row writes are a scatter with ``mode="drop"`` so
    out-of-range lanes write nothing (dynamic_update_slice's clamp-shift
    would slide the whole window onto committed rows). The host only
    ever commits tokens whose positions were in range, so dropped lanes
    are always rejected-draft garbage."""
    new = new.astype(leaf.dtype)
    if not getattr(pos, "ndim", 0):
        return jax.lax.dynamic_update_slice(
            leaf, new[None], (layer, 0, pos, 0, 0)
        )
    b, t = new.shape[:2]
    gpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # [B, T]
    if block_tables is None:
        rows = jax.lax.broadcasted_iota(jnp.int32, (b, t), 0)
        return leaf.at[layer, rows, gpos].set(new, mode="drop")
    page = leaf.shape[2]
    n_pages = block_tables.shape[1]
    pidx = gpos // page
    pids = jnp.take_along_axis(
        block_tables, jnp.minimum(pidx, n_pages - 1), axis=1
    )
    pids = jnp.where(pidx < n_pages, pids, 0)  # OOB -> scratch page
    return leaf.at[layer, pids, gpos % page].set(
        new.reshape((b, t) + leaf.shape[3:])
    )


def _write_kv(cache, layer, k_new, v_new, pos, block_tables=None,
              kv_quant="none"):
    """Insert this step's [B, T, Hkv, D] K/V into layer ``layer`` of the
    stacked cache dict. ``kv_quant="int8"`` (paged only) QUANTIZES ON
    APPEND: the new tokens' values are rounded to int8 with
    per-token/per-head scales
    (ops/quant.quantize_kv — one f32->int8 convert each for K and V, the
    audit-counted quantize sites) and the value + scale pools are
    scattered through the same page indirection; already-written
    positions are never touched, so appending can never re-quantize a
    neighbour (the per-token-scale soundness argument)."""
    if kv_quant == "int8":
        from pytorch_distributed_tpu.ops.quant import quantize_kv

        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        new = {"k": k_new, "v": v_new}
    return {
        name: _write(cache[name], layer, val, pos, block_tables)
        for name, val in new.items()
    }


def lora_delta(x, lp, rows):
    """Per-row low-rank delta: the multi-tenant LoRA term
    (serving/adapters.py). ``x`` [B, T, Din] is the projection's input;
    ``lp`` is one layer's adapter slice — {"a": [slots, Din, r],
    "b": [slots, r, *out]} with slot 0 the zero adapter; ``rows`` [B]
    int32 picks each row's tenant slot. Returns [B, T, *out]:

        delta[b] = (x[b] @ a[rows[b]]) @ b[rows[b]]

    Nothing cross-row (tenant isolation is structural: row b's output
    can only read slot rows[b]) and nothing collective (under TP the
    caller routes the delta through the projection's EXISTING psum —
    ops/layers.dense ``extra_pre_reduce`` / the pre-``tp_reduce`` add —
    so the pinned Megatron all-reduce counts are untouched). A slot-0
    row's delta is exactly 0.0, and adding exact zeros is exact: no-
    tenant rows stay bit-equal the adapter-less engine.

    Lowering: two PLAIN 2D matmuls against ALL slots (batch flattened
    into the M dimension, the slot axis into N) with exact
    ``take_along_axis`` slot selection — NOT gather-then-batched-einsum.
    A B-batched GEMM's per-lane summation order varies with the batch
    shape on XLA:CPU, and the engines dispatch the same row under
    DIFFERENT batch shapes (prefill group sizes depend on queue churn);
    the flattened form keeps each output element a fixed-order dot over
    the contracting dim — the same shape family as the base
    projections, whose cross-group bit-stability the serving pins have
    relied on since PR 5. Cost: the rank-r GEMMs widen by the slot
    count — noise next to the base D x D projections."""
    a = lp["a"]  # [S, Din, r]
    bm = lp["b"]  # [S, r, *out]
    s_n, din, r = a.shape
    bsz, t = x.shape[:2]
    sel = rows[:, None, None, None]
    xf = x.reshape(bsz * t, din).astype(a.dtype)
    h_all = (xf @ a.transpose(1, 0, 2).reshape(din, s_n * r)).reshape(
        bsz, t, s_n, r
    )
    h = jnp.take_along_axis(h_all, sel, axis=2)  # [B, T, 1, r]
    bmat = bm.reshape(s_n, r, -1)  # [S, r, out]
    out = bmat.shape[-1]
    d_all = (
        h.reshape(bsz * t, r) @ bmat.transpose(1, 0, 2).reshape(r, s_n * out)
    ).reshape(bsz, t, s_n, out)
    d = jnp.take_along_axis(d_all, sel, axis=2)[:, :, 0]
    return d.reshape(x.shape[:2] + bm.shape[2:])


def _moe_mlp(m, mlp_params, cfg, act, tensor_axis=None):
    """Routed MLP for decode: top-1/top-k routing is per-token and
    cache-free, so only the MLP call differs from training. Capacity is
    set to the no-drop bound (cap = k * tokens): a dropped token at
    inference would silently zero its MLP contribution, and at decode
    shapes the slack is negligible. ``tensor_axis``: Megatron TP inside
    each expert (the training EP x TP placement, ops/moe._expert_compute)
    — routing runs on replicated activations so it agrees across shards,
    and the in-expert tp_reduce restores the full output."""
    from pytorch_distributed_tpu.ops.moe import moe_mlp

    out, _ = moe_mlp(
        m,
        mlp_params,
        activation=act,
        capacity_factor=float(cfg.n_experts),
        top_k=cfg.moe_top_k,
        dispatch_impl=cfg.moe_dispatch,
        tensor_axis=tensor_axis,
    )
    return out


def _gpt2_block(x, bp, cache, layer, pos, cfg, tensor_axis=None,
                block_tables=None, paged_impl="gather", kv_quant="none",
                lora=None, lora_rows=None):
    eps = cfg.layer_norm_epsilon
    b, t = x.shape[:2]
    a = layer_norm(x, bp["ln_1"], eps=eps)
    qkv = dense(a, bp["attn"]["c_attn"])  # [B, T, 3, H(/tp), D]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if lora is not None:
        # Query-only on the fused projection: K/V stay tenant-agnostic
        # so cached pages keep their pure-function-of-tokens soundness
        # (serving/adapters.py).
        q = q + lora_delta(a, lora["q"], lora_rows).astype(q.dtype)
    cache = _write_kv(cache, layer, k, v, pos, block_tables, kv_quant)
    a = _cached_attention(
        q, cache, layer, pos, block_tables, paged_impl, kv_quant
    ).reshape(b, t, -1)
    proj_extra = (
        lora_delta(a, lora["c_proj"], lora_rows)
        if lora is not None else None
    )
    x = x + dense(
        a, bp["attn"]["c_proj"], tp_reduce_axis=tensor_axis,
        extra_pre_reduce=proj_extra,
    )
    m = layer_norm(x, bp["ln_2"], eps=eps)
    act = activation(cfg.activation_function)
    if cfg.n_experts:
        m = _moe_mlp(m, bp["mlp"], cfg, act, tensor_axis)
        return x + m, cache
    m = act(dense(m, bp["mlp"]["c_fc"]))
    x = x + dense(m, bp["mlp"]["c_proj"], tp_reduce_axis=tensor_axis)
    return x, cache


def _llama_block(x, bp, cache, layer, pos, cfg, cos, sin, tensor_axis=None,
                 block_tables=None, paged_impl="gather", kv_quant="none",
                 lora=None, lora_rows=None):
    from pytorch_distributed_tpu.ops.quant import qdot
    from pytorch_distributed_tpu.ops.tp import tp_reduce

    eps = cfg.layer_norm_epsilon
    b, t = x.shape[:2]
    d = cfg.head_dim
    a = rms_norm(x, bp["ln_attn"], eps=eps)
    # qdot == `a @ w.astype(a.dtype)` for plain weights (bit-identical
    # dot_general) and the int8 weight-only matmul for quantized ones.
    q_pre = qdot(a, bp["attn"]["wq"])
    if lora is not None:
        # wq (column-parallel) + wo (row-parallel, delta joins the
        # partial BEFORE the psum); wk/wv deliberately untouched so
        # cached K/V stays tenant-agnostic (serving/adapters.py).
        q_pre = q_pre + lora_delta(a, lora["wq"], lora_rows).astype(
            q_pre.dtype
        )
    q = apply_rope(q_pre.reshape(b, t, -1, d), cos, sin)
    k = apply_rope(qdot(a, bp["attn"]["wk"]).reshape(b, t, -1, d), cos, sin)
    v = qdot(a, bp["attn"]["wv"]).reshape(b, t, -1, d)
    cache = _write_kv(cache, layer, k, v, pos, block_tables, kv_quant)
    a = _cached_attention(
        q, cache, layer, pos, block_tables, paged_impl, kv_quant
    ).reshape(b, t, -1)
    wo_out = qdot(a, bp["attn"]["wo"])
    if lora is not None:
        wo_out = wo_out + lora_delta(a, lora["wo"], lora_rows).astype(
            wo_out.dtype
        )
    x = x + tp_reduce(wo_out, tensor_axis)
    m = rms_norm(x, bp["ln_mlp"], eps=eps)
    if cfg.n_experts:
        m = _moe_mlp(m, bp["mlp"], cfg, jax.nn.silu, tensor_axis)
        return x + m, cache
    gate = jax.nn.silu(qdot(m, bp["mlp"]["gate"]))
    up = qdot(m, bp["mlp"]["up"])
    down = qdot(gate * up, bp["mlp"]["down"])
    return x + tp_reduce(down, tensor_axis), cache


def forward(
    params: Params,
    input_ids: jax.Array,  # [B, T] — full prompt (prefill) or one token
    cfg: ModelConfig,
    cache: Cache,
    pos: jax.Array | int,  # tokens already in the cache (scalar or [B])
    *,
    tensor_axis: str | None = None,
    block_transform=None,
    prefetch_buffers: int = 0,
    block_tables: jax.Array | None = None,
    paged_impl: str = "gather",
    kv_quant: str = "none",
    lora: tuple | None = None,
    live: jax.Array | None = None,
    logits_index: jax.Array | None = None,
    return_aux: bool = False,
    state_rows: jax.Array | None = None,
) -> tuple[jax.Array, Cache]:
    """Run T tokens at positions pos..pos+T-1. Returns ([B, T, V] logits,
    updated cache). MoE configs route each token through the expert MLPs
    (no-drop capacity — see ``_moe_mlp``); routing is stateless, so the
    KV cache is untouched by the choice of MLP.

    ``block_tables`` [B, n_pages] switches the cache to the PAGED pool
    layout (``init_paged_cache``: [L, P, page, Hkv*D] leaves) with
    per-row page indirection — the serving block-pool mode
    (serving/engine.PagedBatchedDecodeEngine). ``pos`` must then be a
    [B] vector. ``paged_impl`` picks the paged attention backend for
    single-token steps ("gather" XLA fallback / "kernel" Pallas /
    "kernel_interpret" for the CPU rig's kernel tests).

    ``pos`` may be a [B] VECTOR: each batch row then runs at its own
    position (cache write offset, attention mask, wpe/rope angles) — the
    slot-batched decode mode (serving/engine.BatchedDecodeEngine), where
    independent requests occupy rows of one program at unrelated depths.
    Row b's computation is bit-identical to the scalar-pos call at
    pos[b] with that row alone (pure per-row data movement + the same
    per-row reductions).

    ``tensor_axis``: set when called inside shard_map with block params
    sharded Megatron-style (tensor-parallel decode): attention runs on
    the LOCAL heads against a local-head cache shard, row-parallel
    projections psum over the axis, and the logits come back replicated.

    ``block_transform`` / ``prefetch_buffers``: the scan-over-layers hooks
    (ops/layer_scan.py) — ZeRO-3 decode passes a gather/replicate
    transform per layer, and with ``prefetch_buffers`` > 0 a whole
    window's gathers are issued before its first block computes, so layer
    l+1's shards stream in under layer l's compute (serving/engine.py).
    Bit-equivalent to the default per-layer schedule for any window size.

    ``lora``: ``(stacked adapter tree, [B] tenant-slot rows)`` — the
    multi-tenant low-rank deltas (serving/adapters.py). The tree's
    leaves are [L, slots, ...] and scan alongside the blocks; each
    row's delta is applied per-row inside the blocks
    (``lora_delta``) with slot 0 the exact-zero adapter. Incompatible
    with ``block_transform`` (the ZeRO-3 gather hook transforms the
    whole sliced tree — adapters are plain operands, not sharded
    params), rejected loudly.

    ``logits_index`` [B]: the logits come back [B, 1, V], of position
    ``logits_index[b]`` of each row (a prefill chunk samples from its last
    real token only). ``live`` [B, T] bool marks the entries that are
    tokens, for a family whose layers count their work (padding and free
    rows then route nowhere and count nothing); the others ignore it.
    ``return_aux``: return (logits, cache, aux) with aux the [n] int32
    counts ``serving(cfg).aux_counts`` names, summed over the layers.
    ``state_rows`` [B]: for a family with per-row recurrent state
    (``serving(cfg).state_bytes_per_row``), the state row of each batch row;
    left out,
    batch row b is state row b (the decode step's lanes).
    """
    b, t = input_ids.shape
    dtype = jnp.dtype(cfg.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim > 0  # [B] vector: slot-batched, per-row positions
    if block_tables is not None and not per_row:
        raise ValueError(
            "paged decode (block_tables) requires a per-row [B] pos "
            "vector — every paged row owns its own position"
        )
    if kv_quant not in ("none", "int8"):
        raise ValueError(
            f"kv_quant must be 'none' or 'int8', got {kv_quant!r}"
        )
    if kv_quant != "none" and block_tables is None:
        raise ValueError(
            "kv_quant requires the paged cache layout (block_tables): "
            "dense caches stay full precision — quantized pages are the "
            "block-pool feature (init_paged_cache(kv_quant=...))"
        )
    lora_tree = lora_rows = None
    if lora is not None:
        if block_transform is not None:
            raise ValueError(
                "lora adapters are incompatible with block_transform "
                "(ZeRO-3 decode): the gather hook transforms the whole "
                "sliced layer tree, and the stacked adapter operands are "
                "plain per-dispatch values, not sharded params — serve "
                "adapters from plain or tensor-only meshes"
            )
        lora_tree, lora_rows = lora
        lora_rows = jnp.asarray(lora_rows, jnp.int32)

    if cfg.family == "kimi_k2":
        if (block_tables is None or tensor_axis is not None
                or block_transform is not None or lora is not None):
            raise NotImplementedError(
                "the kimi_k2 family runs on the paged latent pool of one "
                "device (block_tables given; no tensor axis, ZeRO-3 "
                "transform or LoRA): models/kimi_k2.forward"
            )
        from pytorch_distributed_tpu.models import kimi_k2

        logits, cache, aux = kimi_k2.forward(
            params, input_ids, cfg, cache, pos, block_tables,
            live=live, logits_index=logits_index, paged_impl=paged_impl,
        )
        return (logits, cache, aux) if return_aux else (logits, cache)
    if cfg.family == "granitemoehybrid":
        if (block_tables is None or tensor_axis is not None
                or block_transform is not None or lora is not None
                or kv_quant != "none"):
            raise NotImplementedError(
                "the granitemoehybrid family runs on the paged pool and "
                "the per-row state of one device (block_tables given; no "
                "tensor axis, ZeRO-3 transform, LoRA or quantized pages): "
                "models/granitemoehybrid.forward"
            )
        from pytorch_distributed_tpu.models import granitemoehybrid

        logits, cache, aux = granitemoehybrid.forward(
            params, input_ids, cfg, cache, pos, block_tables,
            state_rows=state_rows, live=live, logits_index=logits_index,
            paged_impl=paged_impl,
        )
        return (logits, cache, aux) if return_aux else (logits, cache)
    if cfg.family == "mellum":
        if (block_tables is None or tensor_axis is not None
                or block_transform is not None or lora is not None
                or kv_quant != "none"):
            raise NotImplementedError(
                "the mellum family runs on its two page groups on one "
                "device (block_tables given, both tables side by side; no "
                "tensor axis, ZeRO-3 transform, LoRA or quantized pages): "
                "models/mellum.forward"
            )
        from pytorch_distributed_tpu.models import mellum

        logits, cache, aux = mellum.forward(
            params, input_ids, cfg, cache, pos, block_tables,
            live=live, logits_index=logits_index, paged_impl=paged_impl,
        )
        return (logits, cache, aux) if return_aux else (logits, cache)
    if cfg.family == "gpt2":
        if per_row:
            rows = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
            wpe = params["wpe"][rows]  # [B, T, E], row b at its own pos
        else:
            wpe = jax.lax.dynamic_slice_in_dim(params["wpe"], pos, t, axis=0)
        x = (params["wte"][input_ids] + wpe).astype(dtype)
        block = partial(
            _gpt2_block, cfg=cfg, tensor_axis=tensor_axis,
            block_tables=block_tables, paged_impl=paged_impl,
            kv_quant=kv_quant,
        )
    elif cfg.family == "llama":
        x = params["wte"][input_ids].astype(dtype)
        cos, sin = rope_angles(
            t, cfg.head_dim, cfg.rope_theta,
            offset=pos[:, None] if per_row else pos,
        )
        block = partial(
            _llama_block, cfg=cfg, cos=cos, sin=sin,
            tensor_axis=tensor_axis,
            block_tables=block_tables, paged_impl=paged_impl,
            kv_quant=kv_quant,
        )
    else:
        raise KeyError(f"unknown model family {cfg.family!r}")

    def block_body(carry, bp, extra):
        # The carry is (activations, the whole stacked cache dict — k/v,
        # plus the scale pools when quantized: the leaf set is the cache
        # layout's business, not the scan's). ``extra["layer"]`` says
        # which layer of it this block writes and reads.
        # ``extra["lora"]`` (when adapters ride the dispatch) is that
        # layer's [slots, ...] adapter slice; the [B] rows vector is
        # layer-invariant and closes over the scan.
        x, kv = carry
        if lora_tree is not None:
            return block(
                x, bp, kv, extra["layer"], pos,
                lora=extra["lora"], lora_rows=lora_rows,
            )
        return block(x, bp, kv, extra["layer"], pos)

    extras = {"layer": jnp.arange(cache["k"].shape[0], dtype=jnp.int32)}
    if lora_tree is not None:
        extras["lora"] = lora_tree
    x, cache = scan_layers(
        block_body,
        (x, cache),
        params["blocks"],
        extras=extras,
        remat_mode="none",
        block_transform=block_transform,
        prefetch_buffers=prefetch_buffers,
    )

    from pytorch_distributed_tpu.models import get_model

    logits = get_model(cfg).head(params, x, cfg)
    if logits_index is not None:
        logits = jnp.take_along_axis(
            logits, logits_index[:, None, None], axis=1
        )
    if return_aux:
        return logits, cache, jnp.zeros((0,), jnp.int32)
    return logits, cache


# -- sampling --------------------------------------------------------------
#
# Greedy-vs-sampled is the ONE static bit (a greedy program has no
# division, no top-k, no PRNG); everything else about the sampling
# config is a traced scalar, so a serving loop sweeping temperature /
# top_k / top_p reuses one compiled program. ``None`` top_k / top_p are
# encoded as out-of-range sentinels (k = vocab size keeps the full
# support; p = 2.0 keeps every cumulative mass) rather than separate
# static program variants. A sampled program holds two ways to draw
# behind ONE ``lax.cond`` on its rows' own k: the top ``SAMPLE_CAP``
# tokens (a partial top-k), or the whole vocabulary sorted, which runs
# only when a sampled row asks for more than the cap.

# Rows asking for at most this many tokens draw from a partial top-k of
# this width (one lane width; covers top-k 40, 50, 100). A constant of
# the module: what the rows ask for picks the path, nothing else does.
SAMPLE_CAP = 128
# The top SAMPLE_CAP is found among the entries of the SAMPLE_CAP segments
# (of this many) whose maxima are largest (``_top_cap``).
_CAP_SEGMENTS = 2048


def sampling_scalars(
    temperature, top_k, top_p, vocab_size: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Encode the (possibly-None) Python sampling config as the traced
    scalar triple every sampled program takes. Explicit dtypes — a
    weak-typed Python scalar would retrace when its Python type changes
    (the exact hazard analysis/jaxpr_scan flags). ``top_k`` in
    {None, 0} means top-k disabled (full support — the HF convention for
    0; a traced k=0 would otherwise mask EVERY token and silently
    degrade to greedy); negative k is rejected here, where the Python
    int is still visible."""
    if top_k is not None and top_k < 0:
        raise ValueError(f"top_k must be >= 0 or None, got {top_k}")
    t = jnp.asarray(temperature if temperature else 1.0, jnp.float32)
    k = jnp.asarray(top_k or vocab_size, jnp.int32)
    p = jnp.asarray(2.0 if top_p is None else top_p, jnp.float32)
    return t, k, p


def _sample_cap(vocab_size: int) -> int:
    return min(SAMPLE_CAP, vocab_size)


def needs_full_sort(greedy, top_k, vocab_size: int):
    """Whether a batch's draw needs the whole vocabulary sorted: some
    sampled row asks for more than ``SAMPLE_CAP`` tokens (top-k off is
    k = vocab). ``greedy`` [B] bool, ``top_k`` [B] int; numpy on the
    host (the paged engine's ``sample_full_sort.<kind>`` counter) and
    traced in the program (``sample_token_rows``'s branch), one rule."""
    return ((top_k > _sample_cap(vocab_size)) & ~greedy).any()


def _sample_greedy(logits):
    """[B, V] -> [B] argmax tokens (the static greedy program)."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _kept(vals, top_k, top_p):
    """[B, n] tempered logits sorted descending -> the same with -inf
    outside the support: ranks < top_k, then within them the tokens whose
    CUMULATIVE mass before them is < top_p (the argmax token always
    survives). ``top_k`` / ``top_p`` scalars or ``[B, 1]``."""
    rank = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    in_k = rank < top_k
    probs = jax.nn.softmax(jnp.where(in_k, vals, -jnp.inf), axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    return jnp.where(in_k & (cum_before < top_p), vals, -jnp.inf)


def _top_cap(x):
    """[B, V] tempered logits -> each row's top ``SAMPLE_CAP`` values,
    sorted descending, and their token ids; exact. Each of a row's top
    ``SAMPLE_CAP`` lies in one of the ``SAMPLE_CAP`` segments of the
    vocabulary with the largest maxima (a segment outside them lies under
    ``SAMPLE_CAP`` larger maxima), so the top-k runs over those segments'
    entries alone. On a TPU v5e at 32 rows x 100,352 a whole draw took
    0.36 ms this way (0.07 ms more with noise over the whole vocabulary),
    1.34 ms through the compiler's own top-k op and 3.80 ms over the full
    sort."""
    b, v = x.shape
    cap = _sample_cap(v)
    if v <= _CAP_SEGMENTS:
        return jax.lax.top_k(x, cap)
    n = -(-v // _CAP_SEGMENTS)  # a segment's length
    segs = jnp.pad(
        x, ((0, 0), (0, _CAP_SEGMENTS * n - v)), constant_values=-jnp.inf,
    ).reshape(b, _CAP_SEGMENTS, n)
    _, top = jax.lax.top_k(segs.max(axis=-1), cap)  # [B, cap] segments
    within = jnp.take_along_axis(segs, top[:, :, None], axis=1)
    vals, at = jax.lax.top_k(within.reshape(b, cap * n), cap)
    return vals, jnp.take_along_axis(top, at // n, axis=1) * n + at % n


def _draw(vals, idx, noise, top_k, top_p):
    """[B, n] tempered logits sorted descending and their token ids ->
    [B] tokens: ``jax.random.categorical`` over ``_kept``'s support,
    written out as the argmax of the support plus the rows' Gumbel noise
    over the whole vocabulary (``noise``, [B, V]), of which the first n
    columns are read. Row r's noise is ``gumbel(key_r, (1, V))``, whose
    first columns do not depend on V: a row whose top_k fits the cap
    draws the same token from the whole vocabulary sorted as from its
    top ``SAMPLE_CAP`` (tied values aside, whose order a top-k may
    choose)."""
    n = vals.shape[-1]
    choice = jnp.argmax(
        _kept(vals, top_k, top_p) + noise[:, :n], axis=-1)
    return jnp.take_along_axis(
        idx, choice[:, None], axis=-1
    )[:, 0].astype(jnp.int32)


def _draw_capped_or_full(full, x, noise, top_k, top_p):
    """ONE ``lax.cond``: the whole vocabulary sorted where ``full``, else
    the top ``SAMPLE_CAP`` alone."""
    return jax.lax.cond(
        full,
        lambda: _draw(*jax.lax.top_k(x, x.shape[-1]), noise, top_k, top_p),
        lambda: _draw(*_top_cap(x), noise, top_k, top_p),
    )


def _sample_traced(logits, temperature, key, top_k, top_p):
    """[B, V] -> [B] next tokens with TRACED temperature/top_k/top_p
    (see ``sampling_scalars`` for the None-sentinels). top_k restricts
    sampling to the k highest-probability tokens; top_p (nucleus)
    restricts it to the smallest set whose probability mass reaches p.
    Given BOTH, top-k applies first and the nucleus is taken within it
    (HF semantics: the renormalised mass is over the top-k support).

    Mechanics: a ``lax.cond`` on the traced k. At most ``SAMPLE_CAP``
    (top_k 1..128) the draw reads the top ``SAMPLE_CAP`` alone
    (``_top_cap``); above it (top-k off included: k = V) the whole
    vocabulary is sorted, the price of a traced k (HF's sampler pays the
    same sort for top_p). Then rank/cumulative-mass masks; top_k=1 or
    top_p->0 reduce to greedy. The noise is drawn once, outside the
    branch: a second draw would be traced and lowered again in every
    program that samples.
    """
    x = logits.astype(jnp.float32) / temperature
    noise = jax.random.gumbel(key, x.shape, jnp.float32)
    return _draw_capped_or_full(
        top_k > _sample_cap(x.shape[-1]), x, noise, top_k, top_p)


def sample_token(logits, sampled: bool, temperature, key, top_k, top_p):
    """One next-token draw: ``sampled`` is the static greedy/sampled bit,
    the rest are traced. Shared by the monolithic paths and the serving
    engine so the two can never drift (their bit-equivalence is pinned in
    tests/test_serving.py)."""
    if not sampled:
        return _sample_greedy(logits)
    return _sample_traced(logits, temperature, key, top_k, top_p)


def sample_token_rows(logits, greedy, temperature, keys, top_k, top_p):
    """One next-token draw PER ROW with fully per-row sampling state:
    ``logits`` [B, V]; ``greedy`` [B] bool plus ``temperature``/``top_k``/
    ``top_p`` [B] — all TRACED, so a slot batch can mix greedy and sampled
    requests with any configs in one compiled program; ``keys`` [B] typed
    PRNG keys (one per request, already folded to the row's step).

    Row r's draw is bit-identical to the serial path's
    ``sample_token(logits[r:r+1], ...)`` with the same key: the top-k and
    the masks are row by row, and row r's noise is drawn from its own key
    at the serial path's shape (vmap of threefry is elementwise in (key,
    counter), so the bits match the individual calls). Greedy is a traced
    flag here — the batch serves both kinds of row in one program — so
    every row draws and greedy rows select the argmax instead. ONE
    ``lax.cond`` at batch level (under vmap a cond would run both
    branches) takes the partial top-k unless some sampled row asks for
    more than ``SAMPLE_CAP`` (``needs_full_sort``): greedy rows and
    top-k <= 128 rows never pay for the full-vocabulary sort. Nothing is
    vmapped but the noise: the top-k sees ``[B, V]``, where a vmap would
    hand the compiler ``[B, 1, V]``, which it sorts whole."""
    x = logits.astype(jnp.float32) / temperature[:, None]
    noise = jax.vmap(lambda key: jax.random.gumbel(
        key, (1, x.shape[-1]), jnp.float32)[0])(keys)
    drawn = _draw_capped_or_full(
        needs_full_sort(greedy, top_k, x.shape[-1]), x, noise,
        top_k[:, None], top_p[:, None])
    return jnp.where(greedy, _sample_greedy(logits), drawn)


def speculative_accept(
    drafts: jax.Array,      # [B, K] int32 draft tokens (lane-padded)
    verified: jax.Array,    # [B, K] int32 greedy next-tokens for lanes 0..K-1
    n_draft: jax.Array,     # [B] int32 valid draft count per row (0..K)
) -> jax.Array:
    """Per-row TRACED accept lengths for batched speculative decoding
    (serving/engine.py ``decode_spec_step``): draft lane j survives iff
    every earlier lane survived AND it matches the model's own greedy
    choice for that position AND the lane is valid (j < n_draft[b] —
    rows with fewer drafts than the program width ride padded lanes
    that can never be accepted). Returns [B] int32 in [0, K]; the
    committed tokens are then ``out[b, :n_acc[b]+1]`` (accepted drafts
    plus the model's bonus/correction token) — the same acceptance rule
    as the serial prompt-lookup loop (models/speculative.py), so the
    greedy output is the plain decode by construction, whatever the
    drafts were. All rows share one compiled program: acceptance is
    data, not shape."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, drafts.shape, 1)
    match = (drafts == verified) & (lanes < n_draft[:, None])
    return jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)


def _generate_impl(
    params, prompt, cfg, max_new_tokens, sampled, temperature, key,
    max_len, top_k, top_p, tensor_axis=None, n_kv=None,
):
    """Shared monolithic generation body: prefill over the prompt, then a
    fori_loop of single-token decode steps against the cache. Runs plain
    (generate_monolithic) or inside shard_map (generate_tp_monolithic).
    ``sampled`` is static; temperature/top_k/top_p arrive traced."""
    b, tp = prompt.shape
    total = tp + max_new_tokens
    max_len = max_len or total
    # key is never None here: _check_sample_args owns the greedy-path
    # dummy-key substitution for every entry point.

    cache = init_cache(cfg, b, max_len, n_kv=n_kv)
    if tensor_axis is not None:
        # The cache carries tensor-sharded values (local-head K/V); its
        # zero init must be typed varying over the axis or the fori_loop
        # carry types mismatch under check_vma.
        from pytorch_distributed_tpu.ops.tp import pvary_missing

        cache = jax.tree.map(
            lambda c: pvary_missing(c, (tensor_axis,)), cache
        )
    logits, cache = forward(
        params, prompt, cfg, cache, 0, tensor_axis=tensor_axis
    )
    next_tok = sample_token(
        logits[:, -1], sampled, temperature, key, top_k, top_p
    )

    out = jnp.zeros((b, total), jnp.int32)
    out = jax.lax.dynamic_update_slice(out, prompt.astype(jnp.int32), (0, 0))
    out = out.at[:, tp].set(next_tok)

    def step(i, carry):
        out, cache, tok = carry
        pos = tp + i
        logits, cache = forward(
            params, tok[:, None], cfg, cache, pos, tensor_axis=tensor_axis
        )
        nxt = sample_token(
            logits[:, -1], sampled, temperature,
            jax.random.fold_in(key, i), top_k, top_p,
        )
        out = out.at[:, pos + 1].set(nxt)
        return out, cache, nxt

    out, _, _ = jax.lax.fori_loop(
        0, max_new_tokens - 1, step, (out, cache, next_tok)
    )
    return out


# repolint: allow(jit-donation-decision) — params are the serving
# weights, reused by every generate call; the cache is jit-internal on
# this legacy reference path (the serving engine is the donated-cache
# fast path).
@partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "max_len", "sampled"),
)
def _monolithic_jit(
    params, prompt, key, temperature, top_k, top_p,
    *, cfg, max_new_tokens, max_len, sampled,
):
    return _generate_impl(
        params, prompt, cfg, max_new_tokens, sampled, temperature, key,
        max_len, top_k, top_p,
    )


def generate_monolithic(
    params: Params,
    prompt: jax.Array,  # [B, Tp] int
    cfg: ModelConfig,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    max_len: int | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """The original single-program generation path: prefill + fori_loop of
    decode steps inside ONE jit. Returns [B, Tp + max_new_tokens].

    Kept as the reference the serving engine is pinned bit-equal against
    (tests/test_serving.py). Sampling params are traced
    (a config sweep reuses one compiled program — the compile key is only
    (shapes, cfg, max_new_tokens, max_len, greedy-vs-sampled)); the KV
    cache is jit-internal, re-allocated and re-zeroed every call — the
    cost ``serving.engine.DecodeEngine``'s donated cache pool removes.
    """
    key = _check_sample_args(
        prompt, max_new_tokens, temperature, key, max_len=max_len
    )
    t, k, p = sampling_scalars(temperature, top_k, top_p, cfg.vocab_size)
    return _monolithic_jit(
        params, prompt, key, t, k, p,
        cfg=cfg, max_new_tokens=max_new_tokens, max_len=max_len,
        sampled=temperature > 0,
    )


def generate(
    params: Params,
    prompt: jax.Array,  # [B, Tp] int
    cfg: ModelConfig,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    max_len: int | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """Autoregressive generation: returns [B, Tp + max_new_tokens].

    Compat shim over ``serving.engine.DecodeEngine`` (exact-length
    buckets, so compilation behaviour matches the old monolithic entry):
    prefill + decode run as two long-lived compiled programs with the KV
    cache donated between them and pooled across calls. Bit-equal to
    ``generate_monolithic`` (pinned in tests/test_serving.py).
    """
    key = _check_sample_args(
        prompt, max_new_tokens, temperature, key, max_len=max_len
    )
    from pytorch_distributed_tpu.serving.engine import shim_engine

    engine = shim_engine(
        cfg, max_len or (prompt.shape[1] + max_new_tokens)
    )
    return engine.generate(
        params, prompt, max_new_tokens, temperature=temperature, key=key,
        top_k=top_k, top_p=top_p,
    )


def _validate_tp_mesh(cfg: ModelConfig, mesh_cfg) -> None:
    """Shared generate_tp entry validation (shim + monolithic)."""
    tp_size = mesh_cfg.tensor
    if tp_size <= 1:
        raise ValueError("generate_tp needs mesh_cfg.tensor > 1")
    for ax in ("data", "fsdp", "seq", "pipe", "expert"):
        if getattr(mesh_cfg, ax) > 1:
            raise NotImplementedError(
                f"generate_tp supports a tensor-only mesh (got {ax}="
                f"{getattr(mesh_cfg, ax)})"
            )
    if cfg.n_experts and cfg.inner_dim % tp_size:
        raise ValueError(
            f"tensor={tp_size} must divide the MoE expert hidden dim "
            f"inner_dim={cfg.inner_dim} (experts run Megatron TP on F)"
        )
    if cfg.n_head % tp_size or cfg.kv_heads % tp_size:
        raise ValueError(
            f"tensor={tp_size} must divide n_head={cfg.n_head} and "
            f"kv_heads={cfg.kv_heads}"
        )


def _validate_fsdp_mesh(mesh_cfg) -> None:
    """Shared generate_fsdp entry validation (shim + monolithic)."""
    if mesh_cfg.fsdp <= 1:
        raise ValueError("generate_fsdp needs mesh_cfg.fsdp > 1")
    for ax in ("data", "tensor", "seq", "pipe", "expert"):
        if getattr(mesh_cfg, ax) > 1:
            raise NotImplementedError(
                f"generate_fsdp supports an fsdp-only mesh (got {ax}="
                f"{getattr(mesh_cfg, ax)}); combine with generate_tp's "
                "tensor sharding is future surface"
            )
    if mesh_cfg.strategy != "full_shard":
        raise ValueError(
            "generate_fsdp decodes from full_shard (ZeRO-3) param "
            f"layouts; strategy={mesh_cfg.strategy!r} keeps params "
            "replicated — plain generate already covers it"
        )


def generate_tp(
    params: Params,
    prompt: jax.Array,  # [B, Tp] int
    cfg: ModelConfig,
    mesh_cfg,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    max_len: int | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """Tensor-parallel generation over a "tensor" mesh (meshed decode —
    models whose weights exceed one chip sample across tp shards).

    Block params shard Megatron-style per parallel/sharding.py's rule
    table (the SAME layout training leaves them in, so a trained sharded
    state decodes with no resharding); each shard runs attention on its
    LOCAL heads against a local-head KV cache (1/tp of the cache HBM),
    row-parallel projections psum over the axis, and the replicated
    logits sample identically on every shard. Compat shim over the TP
    ``DecodeEngine``; ``generate_tp_monolithic`` is the one-jit reference.
    """
    _validate_tp_mesh(cfg, mesh_cfg)
    key = _check_sample_args(
        prompt, max_new_tokens, temperature, key, max_len=max_len
    )
    from pytorch_distributed_tpu.serving.engine import shim_engine

    engine = shim_engine(
        cfg, max_len or (prompt.shape[1] + max_new_tokens),
        mesh_cfg=mesh_cfg,
    )
    return engine.generate(
        params, prompt, max_new_tokens, temperature=temperature, key=key,
        top_k=top_k, top_p=top_p,
    )


def generate_tp_monolithic(
    params: Params,
    prompt: jax.Array,  # [B, Tp] int
    cfg: ModelConfig,
    mesh_cfg,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    max_len: int | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """One-jit TP generation (the pre-engine reference path)."""
    _validate_tp_mesh(cfg, mesh_cfg)
    key = _check_sample_args(
        prompt, max_new_tokens, temperature, key, max_len=max_len
    )

    fn, shardings = _tp_generate_compiled(
        cfg, mesh_cfg, max_new_tokens, max_len, temperature > 0
    )
    t, k, p = sampling_scalars(temperature, top_k, top_p, cfg.vocab_size)
    # device_put with the target shardings is a no-op when params are
    # already placed, so repeat calls only pay the (cached) jit lookup.
    return fn(jax.device_put(params, shardings), prompt, key, t, k, p)


def nonfinite_rows(logits: jax.Array) -> jax.Array:
    """[B, V] (or [B, T, V]) -> [B] bool: True where ANY logit in the row
    is NaN/Inf — the cheap traced fault sentinel every serving program
    returns next to its sampled token (serving/engine.py). Reduces over
    every axis but the batch axis; elementwise + one reduction, so it adds
    no collectives to any program (the audit registry pins the budgets)
    and costs nothing against the decode step's matmuls."""
    axes = tuple(range(1, logits.ndim))
    return jnp.any(~jnp.isfinite(logits), axis=axes)


def _check_sample_args(prompt, max_new_tokens, temperature, key,
                       max_len=None):
    """Shared generate-entry validation; returns the PRNG key (greedy
    paths get a dummy, unused by sampling). Rejects loudly, naming the
    limit, instead of failing late in a compiled program:

    - empty prompts (the first token would sample from a pad position);
    - ``max_new_tokens <= 0`` (a generate that generates nothing is a
      caller bug — the old 0-token early-return silently returned the
      prompt, which hid budget-accounting mistakes in serving loops);
    - ``prompt + max_new_tokens > max_len`` when the cache capacity is
      known (the KV write past ``max_len`` would otherwise fail deep in
      dispatch or silently clamp);
    - temperature sampling without a key.
    """
    tp = prompt.shape[-1]
    if tp == 0:
        raise ValueError(
            "empty prompt: need at least one token to prefill (an empty "
            "prompt would sample the first token from a pad position)"
        )
    if max_new_tokens <= 0:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens} — a "
            "request that generates nothing is a no-op; don't dispatch it"
        )
    if max_len is not None and tp + max_new_tokens > max_len:
        raise ValueError(
            f"prompt ({tp}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_len {max_len}: the KV cache holds max_len positions, so "
            "the request cannot fit — shorten it or raise max_len"
        )
    if temperature > 0.0 and key is None:
        raise ValueError("temperature sampling requires a PRNG key")
    if key is None:
        key = jax.random.key(0)
    return key


def _mesh_param_shardings(cfg, mesh_cfg):
    """(mesh, partition-spec tree, NamedSharding tree) for decode params
    under ``mesh_cfg`` — shared by the meshed decode paths so spec
    derivation cannot diverge between them. Specs come from the abstract
    init, so no concrete params are needed (lru_cache-friendly)."""
    from jax.sharding import NamedSharding

    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_tpu.parallel.sharding import (
        param_partition_specs,
    )

    mesh = make_mesh(mesh_cfg)
    abstract = jax.eval_shape(
        lambda k: get_model(cfg).init(k, cfg), jax.random.key(0)
    )
    p_specs = param_partition_specs(abstract, mesh_cfg)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        p_specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    return mesh, p_specs, shardings


def generate_fsdp(
    params: Params,
    prompt: jax.Array,  # [B, Tp] int
    cfg: ModelConfig,
    mesh_cfg,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    max_len: int | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """Decode from ZeRO-3-sharded params over an "fsdp" mesh — sample IN
    PLACE from the layout full-shard training leaves the weights in (no
    resharding, and per-chip param HBM stays 1/fsdp of the model).

    Compat shim over the ZeRO-3 ``DecodeEngine``: the auto-partitioned
    decode with each scanned layer's shards gathered per layer — and,
    with ``mesh_cfg.prefetch_buffers`` > 0, gathered a WINDOW at a time
    so layer l+1's all-gather streams under layer l's compute (the same
    ops/layer_scan schedule training's explicit ZeRO-3 path uses; closes
    ROADMAP PR-3 follow-up (c)). ``generate_fsdp_monolithic`` is the
    one-jit reference. MoE configs work unchanged (routing and dispatch
    are ordinary auto-sharded ops here).
    """
    _validate_fsdp_mesh(mesh_cfg)
    key = _check_sample_args(
        prompt, max_new_tokens, temperature, key, max_len=max_len
    )
    from pytorch_distributed_tpu.serving.engine import shim_engine

    engine = shim_engine(
        cfg, max_len or (prompt.shape[1] + max_new_tokens),
        mesh_cfg=mesh_cfg,
    )
    return engine.generate(
        params, prompt, max_new_tokens, temperature=temperature, key=key,
        top_k=top_k, top_p=top_p,
    )


def generate_fsdp_monolithic(
    params: Params,
    prompt: jax.Array,  # [B, Tp] int
    cfg: ModelConfig,
    mesh_cfg,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    max_len: int | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """One-jit ZeRO-3 generation (the pre-engine reference path): the
    decode loop is jitted with params carrying their full_shard
    NamedShardings and XLA's SPMD partitioner inserts the just-in-time
    per-layer gathers (the stacked [L, ...] block leaves shard a WEIGHT
    dim, never L — parallel/sharding.py)."""
    _validate_fsdp_mesh(mesh_cfg)
    key = _check_sample_args(
        prompt, max_new_tokens, temperature, key, max_len=max_len
    )

    fn, shardings = _fsdp_generate_compiled(
        cfg, mesh_cfg, max_new_tokens, max_len, temperature > 0
    )
    t, k, p = sampling_scalars(temperature, top_k, top_p, cfg.vocab_size)
    return fn(jax.device_put(params, shardings), prompt, key, t, k, p)


@functools.lru_cache(maxsize=None)
def _fsdp_generate_compiled(cfg, mesh_cfg, max_new_tokens, max_len, sampled):
    """(jitted auto-path generate fn, full_shard param shardings) for one
    static config — cached like _tp_generate_compiled. Sampling params
    are call-time traced operands, so they are NOT part of this key."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, _, shardings = _mesh_param_shardings(cfg, mesh_cfg)
    replicated = NamedSharding(mesh, P())

    def body(params, prompt, key, temperature, top_k, top_p):
        return _generate_impl(
            params, prompt, cfg, max_new_tokens, sampled, temperature, key,
            max_len, top_k, top_p,
        )

    # repolint: allow(jit-donation-decision) — sharded serving weights
    # are reused across generate_fsdp calls; nothing here is consumed.
    fn = jax.jit(
        body,
        in_shardings=(shardings,) + (replicated,) * 5,
        out_shardings=replicated,
    )
    return fn, shardings


@functools.lru_cache(maxsize=None)
def _tp_generate_compiled(cfg, mesh_cfg, max_new_tokens, max_len, sampled):
    """(jitted shard_map generate fn, param shardings) for one static
    config — cached so a serving loop does not retrace/recompile the
    whole prefill+fori_loop program per generate_tp call (both config
    dataclasses are frozen, hence hashable; traced sampling params are
    NOT part of the key). Param specs are derived from the abstract init
    so the cache needs no concrete params."""
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_tpu.utils.compat import shard_map

    mesh, p_specs, shardings = _mesh_param_shardings(cfg, mesh_cfg)

    def body(params, prompt, key, temperature, top_k, top_p):
        return _generate_impl(
            params, prompt, cfg, max_new_tokens, sampled, temperature, key,
            max_len, top_k, top_p,
            tensor_axis="tensor", n_kv=cfg.kv_heads // mesh_cfg.tensor,
        )

    smapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(p_specs, P(), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=True,
    )
    # repolint: allow(jit-donation-decision) — TP serving weights are
    # reused across generate_tp calls; the KV cache is jit-internal on
    # this reference path.
    return jax.jit(smapped), shardings
