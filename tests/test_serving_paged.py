"""Paged KV cache (serving/engine.PagedBatchedDecodeEngine) battery.

Pins the block-pool engine's contracts on top of the PR-5/6 ones it
inherits:

1. paged-vs-dense equivalence — every request served from the paged
   engine (chunked prefill, block-table decode) emits the tokens the
   DENSE ``BatchedDecodeEngine`` emits for it, busy batch included
   (plain in tier-1; TP and the family matrix on the slow tier).
2. prefix sharing — identical prompt prefixes are stored once (hit
   counters, page accounting), copy-on-write divergence: two rows share
   a prefix then fork, both token-equal to dense; retired prefixes stay
   cached (LRU) and a later identical prompt hits them.
3. pool exhaustion — mid-decode page starvation PREEMPTS the youngest
   active request (admitted last, preempted first) instead of hanging;
   preempted requests resume token-identically. Admission defers when
   the pool cannot cover a prompt. Loud constructor diagnostics for
   ``page_size`` not dividing ``max_len`` and an undersized pool.
4. zero-recompile churn — warmup compiles groups x ONE chunk shape + 1
   decode step; admissions/retirements/preemptions add nothing.
5. donation — the whole page pool strictly aliases through both
   programs (a rejected alias would double-buffer the pool per token).
6. PR-6 fault model on pages — dispatch failure resets the pool AND the
   prefix cache (content was consumed with the donated buffer) and every
   request resumes bit-identically; snapshot/replay onto a rebuilt
   engine is token-identical; NaN quarantine re-prefills WITHOUT
   touching the (possibly poisoned) prefix cache.
7. the Pallas paged-attention kernel (interpret mode on this rig)
   matches the XLA gather fallback, GQA + ragged depths included.

Plus the satellite pins: BucketSpec boundary prompts on the dense
engine and page/chunk-boundary prompt lengths on the paged one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.config import MeshConfig, ModelConfig
from pytorch_distributed_tpu.serving.block_pool import BlockPool
from pytorch_distributed_tpu.serving.engine import (
    BatchedDecodeEngine,
    BucketSpec,
    PagedBatchedDecodeEngine,
)

pytestmark = pytest.mark.full


def _cfg(family="gpt2", **kw):
    extra = {"n_kv_head": 2} if family == "llama" else {}
    extra.update(kw)
    return ModelConfig(
        family=family, vocab_size=97, n_ctx=64, n_embd=64, n_layer=2,
        n_head=4, dtype="float32", attn_pdrop=0.0, resid_pdrop=0.0,
        embd_pdrop=0.0, **extra,
    )


def _params(cfg, seed=0):
    from pytorch_distributed_tpu.models import get_model

    return get_model(cfg).init(jax.random.key(seed), cfg)


def _prompt(tp, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (tp,), 0, 97), np.int32
    )


def _dense(cfg, **kw):
    kw.setdefault("buckets", BucketSpec((8, 16, 32)))
    return BatchedDecodeEngine(cfg, slots=3, max_len=32, **kw)


def _paged(cfg, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return PagedBatchedDecodeEngine(cfg, slots=3, max_len=32, **kw)


def _mixed_requests():
    """Mixed lengths (incl. a page multiple and a chunk-boundary
    straddler) x {greedy, top-k, top-p}; more requests than slots so
    admission churns."""
    return [
        dict(prompt=_prompt(5, 1), max_new_tokens=6),
        dict(prompt=_prompt(8, 2), max_new_tokens=7, temperature=0.9,
             key=jax.random.key(11), top_k=17),  # exactly one page/chunk
        dict(prompt=_prompt(3, 3), max_new_tokens=5, temperature=1.1,
             key=jax.random.key(12), top_p=0.9),
        dict(prompt=_prompt(13, 4), max_new_tokens=4),  # 8 < Tp < 16
    ]


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_paged_rows_match_dense_engine(family):
    """The tier-1 equivalence pin: a busy paged batch (chunked prefill
    trickling in while neighbours decode, mixed sampling) emits exactly
    the dense engine's tokens for every request. llama: Hkv < H, so the
    GQA repeat reads whole heads out of the pool's merged minor axis."""
    cfg = _cfg(family)
    params = _params(cfg)
    dense = _dense(cfg)
    paged = _paged(cfg)
    reqs = _mixed_requests()
    out_d = dense.run(params, reqs)
    out_p = paged.run(params, reqs)
    assert set(out_p) == {0, 1, 2, 3}
    for rid in out_p:
        assert out_p[rid].state == "DONE"
        np.testing.assert_array_equal(
            out_p[rid].tokens, out_d[rid].tokens,
            err_msg=f"request {rid}",
        )


def test_tp_pool_shard_holds_whole_heads(eight_devices):
    """Under tensor=2 every pool leaf shards its last axis: a shard of the
    head-major merged axis is Hkv/2 WHOLE heads, the ones whose queries the
    same shard holds, and it reads what the one-device engine's pool holds
    in those columns."""
    cfg = _cfg("llama", n_kv_head=4)
    params = _params(cfg)
    req = dict(prompt=_prompt(13, 4), max_new_tokens=4)
    plain = _paged(cfg)
    tp = _paged(cfg, mesh_cfg=MeshConfig(tensor=2, strategy="no_shard"))
    np.testing.assert_array_equal(
        tp.run(params, [req])[0].tokens, plain.run(params, [req])[0].tokens)
    d, width = cfg.head_dim, cfg.kv_heads * cfg.head_dim
    for name in ("k", "v"):
        whole, leaf = np.asarray(plain._cache[name]), tp._cache[name]
        assert leaf.shape == whole.shape == (2, tp.pool_pages, 8, width)
        cuts = set()
        for shard in leaf.addressable_shards:
            cols = shard.index[-1]
            assert all(ix == slice(None) for ix in shard.index[:-1])
            assert shard.data.shape[-1] == (cfg.kv_heads // 2) * d
            assert cols.start % d == 0 and cols.stop % d == 0
            cuts.add((cols.start, cols.stop))
            np.testing.assert_allclose(
                np.asarray(shard.data), whole[..., cols], atol=1e-5)
        assert cuts == {(0, width // 2), (width // 2, width)}


def test_prefix_sharing_hits_and_page_accounting():
    """A second request repeating the first's 16-token prefix stores
    those pages ONCE: the hit counters fire, and the second admission
    allocates only the fork's private pages."""
    cfg = _cfg()
    params = _params(cfg)
    shared = _prompt(16, 42)
    r1 = dict(prompt=np.concatenate([shared, _prompt(4, 7)]),
              max_new_tokens=3)
    r2 = dict(prompt=np.concatenate([shared, _prompt(4, 8)]),
              max_new_tokens=3)
    paged = _paged(cfg)
    dense = _dense(cfg)
    d1 = dense.run(params, [r1])
    d2 = dense.run(params, [r2])
    o1 = paged.run(params, [r1])
    np.testing.assert_array_equal(o1[0].tokens, d1[0].tokens)
    assert paged.pool.stats["prefix_hits"] == 0  # cold cache
    o2 = paged.run(params, [r2])
    np.testing.assert_array_equal(o2[1].tokens, d2[1].tokens)
    # 16 shared tokens = 2 chunks = 2 pages hit, stored once.
    assert paged.pool.stats["prefix_hits"] == 1
    assert paged.pool.stats["prefix_hit_tokens"] == 16
    # peak live pages: r2 held 2 shared + private fork pages, never a
    # full second copy of the prefix.
    per_row_full = -(-24 // paged.page_size)  # ext pages for 20 tokens
    assert paged.pool.stats["peak_pages_in_use"] < 2 * per_row_full


def test_cow_fork_divergence_in_flight():
    """Copy-on-write divergence with BOTH rows in flight: two requests
    share a cached prefix concurrently, fork mid-decode onto private
    pages, and each still matches its dense reference exactly."""
    cfg = _cfg()
    params = _params(cfg)
    shared = _prompt(16, 42)
    r1 = dict(prompt=np.concatenate([shared, _prompt(4, 7)]),
              max_new_tokens=6, temperature=0.9,
              key=jax.random.key(31), top_k=11)
    r2 = dict(prompt=np.concatenate([shared, _prompt(4, 8)]),
              max_new_tokens=6, temperature=1.1,
              key=jax.random.key(32), top_p=0.9)
    dense = _dense(cfg)
    ref1 = dense.run(params, [r1])[0].tokens
    ref2 = dense.run(params, [r2])[1].tokens
    paged = _paged(cfg)
    paged.run(params, [dict(prompt=shared, max_new_tokens=1)])  # warm cache
    out = paged.run(params, [r1, r2])  # both hit + fork concurrently
    assert paged.pool.stats["prefix_hits"] == 2
    np.testing.assert_array_equal(out[1].tokens, ref1)
    np.testing.assert_array_equal(out[2].tokens, ref2)


def test_retired_prefix_survives_lru_until_evicted():
    """The prefix cache RETAINS chunks after their last reference drops
    (that's what makes a hot system prompt free across non-overlapping
    requests) and evicts them LRU-first only under allocation
    pressure."""
    pool = BlockPool(pool_pages=6, page_size=8, chunk_tokens=8)
    toks = np.arange(32, dtype=np.int32)
    a = pool.alloc(2)
    k1 = pool.register_chunk(toks, 0, [a[0]])
    pool.register_chunk(toks, 8, [a[1]], prev_key=k1)
    pool.release(a)  # owner retires; chunks stay resident
    assert pool.pages_in_use() == 0 and pool.pages_resident() == 2
    got, pids, key = pool.match_prefix(toks, 31)
    assert got == 16 and pids == a  # hit after the owner died
    # Incremental keys agree with the from-zero rewalk fallback.
    assert key == pool.register_chunk(toks, 8, ["ignored"])
    pool.release(pids)
    # Pressure: 5 usable pages, 2 cached -> allocating 4 must evict.
    four = pool.alloc(4)
    assert four is not None and pool.stats["evictions"] >= 1
    # And over-pressure fails loudly-but-cleanly (None, pool unchanged).
    assert pool.alloc(3) is None
    pool.release(four)


def test_pool_exhaustion_preempts_youngest_and_resumes():
    """Mid-decode page starvation preempts the youngest active request
    (clean resume entry, no retry charge) instead of hanging; every
    request still finishes DONE with dense-equal tokens."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [
        dict(prompt=_prompt(14, 1), max_new_tokens=10),
        dict(prompt=_prompt(15, 2), max_new_tokens=10, temperature=0.8,
             key=jax.random.key(5), top_k=9),
    ]
    dense = BatchedDecodeEngine(
        cfg, slots=2, max_len=32, buckets=BucketSpec((16,))
    )
    ref = dense.run(params, reqs)
    # 5 usable pages < 2 rows x 4 pages: decode growth must preempt.
    paged = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=32, page_size=8, prefill_chunk=8,
        pool_pages=6,
    )
    out = paged.run(params, reqs)
    assert paged.counters["preemptions"] >= 1
    assert paged.counters["failed"] == 0  # preemption is not a fault
    for rid in (0, 1):
        assert out[rid].state == "DONE"
        np.testing.assert_array_equal(
            out[rid].tokens, ref[rid].tokens,
            err_msg=f"request {rid} diverged across preemption",
        )


def test_simultaneous_boundary_crossing_leaks_no_pages():
    """Regression: rows admitted together (equal prompt lengths) cross a
    page boundary on the SAME tick under an exhausted pool, so growth
    for an early row preempts a later row MID-LOOP. The growth loop must
    re-read the live slot list — growing the preempted row's stale slot
    would leak a refcounted page forever. After everything drains, every
    request is DONE token-equal and the pool holds zero references."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [
        dict(prompt=_prompt(15, 10 + i), max_new_tokens=9)
        for i in range(3)
    ]
    dense = BatchedDecodeEngine(
        cfg, slots=3, max_len=24, buckets=BucketSpec((16,))
    )
    ref = dense.run(params, reqs)
    # 3 rows x 15-token prompts prefill to 2 pages each (6 of 7 usable);
    # all three hit pos=16 together -> three growths, one page left.
    paged = PagedBatchedDecodeEngine(
        cfg, slots=3, max_len=24, page_size=8, prefill_chunk=8,
        pool_pages=8,
    )
    out = paged.run(params, reqs)
    assert paged.counters["preemptions"] >= 1
    for rid in range(3):
        assert out[rid].state == "DONE"
        np.testing.assert_array_equal(
            out[rid].tokens, ref[rid].tokens, err_msg=f"request {rid}"
        )
    assert paged.pool.pages_in_use() == 0, "leaked page references"


def test_admission_defers_until_pages_free():
    """Admission backpressure now includes the PAGE pool, not just free
    rows: a free slot with an empty pool keeps the request queued (no
    hang — the active row's retirement frees its pages)."""
    cfg = _cfg()
    params = _params(cfg)
    paged = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=16, page_size=8, prefill_chunk=8,
        pool_pages=3,  # 2 usable = one full-depth row
    )
    r_big = paged.submit(_prompt(14, 1), 2)  # admit takes both pages
    r_next = paged.submit(_prompt(6, 2), 4)
    paged.step(params)
    assert paged.active_rids() == [r_big]
    assert paged.queued_rids() == [r_next]  # slot free, pool not
    out = paged.run(params)
    assert out[r_big].state == "DONE" and out[r_next].state == "DONE"
    # Deferred ticks must not inflate the prefix-cache counters: every
    # failed _try_allocate cancels its match, so the committed stats
    # count exactly one query per ADMISSION, not per retry tick.
    assert paged.pool.stats["prefix_queries"] == 2


def test_constructor_diagnostics():
    cfg = _cfg()
    with pytest.raises(ValueError, match="divisor of max_len"):
        PagedBatchedDecodeEngine(cfg, slots=2, max_len=30, page_size=8)
    with pytest.raises(ValueError, match="pool_pages"):
        PagedBatchedDecodeEngine(
            cfg, slots=2, max_len=32, page_size=8, pool_pages=4
        )
    with pytest.raises(ValueError, match="prefill_chunk"):
        PagedBatchedDecodeEngine(
            cfg, slots=2, max_len=32, page_size=8, prefill_chunk=12
        )
    with pytest.raises(ValueError, match="paged_attention"):
        PagedBatchedDecodeEngine(
            cfg, slots=2, max_len=32, page_size=8,
            paged_attention="magic",
        )
    with pytest.raises(ValueError, match="chunk_tokens"):
        BlockPool(pool_pages=4, page_size=8, chunk_tokens=4)
    with pytest.raises(ValueError, match="pool_pages"):
        BlockPool(pool_pages=1, page_size=8, chunk_tokens=8)


def test_churn_zero_new_compiles():
    """Warmup = groups x ONE chunk shape + 1 decode step (no bucket
    dimension); churn — admissions, retirements, preemptions, prefix
    hits — adds nothing."""
    cfg = _cfg()
    params = _params(cfg)
    eng = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=24, page_size=8, prefill_chunk=8,
        pool_pages=7,  # tight enough that waves preempt occasionally
    )
    n_warm = eng.warmup(params)
    assert n_warm == len(eng._groups) + 1
    shared = _prompt(8, 99)
    for wave in range(3):
        reqs = [
            dict(prompt=np.concatenate([shared, _prompt(2 + wave, wave)]),
                 max_new_tokens=3),
            dict(prompt=_prompt(10 + wave, 30 + wave), max_new_tokens=4,
                 temperature=0.8, key=jax.random.key(wave), top_k=5),
        ]
        out = eng.run(params, reqs)
        assert all(r.state == "DONE" for r in out.values())
    assert eng.pool.stats["prefix_hits"] >= 1  # shared prefix reused
    assert eng.compile_count() == n_warm, (
        f"{eng.compile_count() - n_warm} steady-state compiles leaked"
    )


def test_paged_donation_aliases_every_program(audit):
    """Strict donation of the page pool through both paged programs,
    plus the NO_COLLECTIVES pin — the registry contract
    (decode_paged_prefill / decode_paged_step), exercised in-process."""
    from pytorch_distributed_tpu.analysis.budget import NO_COLLECTIVES

    cfg = _cfg()
    params = _params(cfg)
    eng = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=16, page_size=8, prefill_chunk=8
    )
    stats = eng.verify_donation(params)
    for kind in ("prefill", "decode_step"):
        assert stats[kind]["aliased"] == stats[kind]["expected"] == 2
        audit.assert_clean(
            eng.program(kind),
            eng.example_args(kind, params),
            NO_COLLECTIVES,
            donate_argnums=(eng.CACHE_ARGNUM[kind],),
            donation_strict=True,
            compute_dtype=cfg.dtype,
        )


def test_dispatch_failure_resets_pool_and_resumes_bit_identical():
    """PR-6 on pages: a failed dispatch consumed the donated POOL, so
    recovery resets the block pool AND the prefix cache (its keys point
    at dead content) — and every request still finishes token-equal to
    an undisturbed run via the resume path."""
    from pytorch_distributed_tpu.serving.chaos import Fault, FaultInjector

    cfg = _cfg()
    params = _params(cfg)
    p = _prompt(5, 1)
    reqs = [
        dict(prompt=p, max_new_tokens=8, temperature=0.9,
             key=jax.random.key(21), top_k=13),
        dict(prompt=p, max_new_tokens=4),
    ]
    fresh = PagedBatchedDecodeEngine(
        cfg, slots=1, max_len=24, page_size=8, prefill_chunk=8
    )
    undisturbed = fresh.run(params, reqs)
    eng = PagedBatchedDecodeEngine(
        cfg, slots=1, max_len=24, page_size=8, prefill_chunk=8
    )
    FaultInjector([Fault(tick=3, kind="dispatch_error")]).install(eng)
    r0 = eng.submit(**reqs[0])
    r1 = eng.submit(**reqs[1])
    for _ in range(3):
        eng.step(params)
    assert eng._cache is None  # donated buffer consumed
    assert eng.pool.pages_resident() == 0  # pool + prefix cache reset
    assert eng.counters["dispatch_failures"] == 1
    out = eng.run(params)
    for rid in (r0, r1):
        assert out[rid].state == "DONE"
        np.testing.assert_array_equal(
            out[rid].tokens, undisturbed[rid].tokens,
            err_msg=f"request {rid} diverged across the fault resume",
        )


def test_snapshot_replay_bit_identical_on_pages():
    """snapshot() mid-flight -> restore() onto a rebuilt paged engine
    (fresh pool, empty prefix cache) continues token-identically — the
    PR-6 crash-recovery contract survives the cache refactor."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [
        dict(prompt=_prompt(9, 3), max_new_tokens=8, temperature=0.9,
             key=jax.random.key(21), top_k=13),
        dict(prompt=_prompt(5, 4), max_new_tokens=6),
    ]
    fresh = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=24, page_size=8, prefill_chunk=8
    )
    undisturbed = fresh.run(params, reqs)
    eng = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=24, page_size=8, prefill_chunk=8
    )
    rids = [eng.submit(**r) for r in reqs]
    eng.step(params)
    eng.step(params)  # both rows mid-decode
    snap = eng.snapshot()
    rebuilt = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=24, page_size=8, prefill_chunk=8
    )
    rebuilt.restore(snap)
    out = rebuilt.run(params)
    for rid in rids:
        np.testing.assert_array_equal(
            out[rid].tokens, undisturbed[rid].tokens,
            err_msg=f"request {rid} diverged across snapshot replay",
        )


def test_quarantine_bypasses_prefix_cache():
    """A NaN-quarantined request re-prefills WITHOUT prefix matching:
    the cached pages might carry the very poison it is escaping. The
    retry must re-run clean and match the dense reference."""
    from pytorch_distributed_tpu.serving.chaos import Fault, FaultInjector

    cfg = _cfg()
    params = _params(cfg)
    req = dict(prompt=_prompt(9, 3), max_new_tokens=6)
    dense = _dense(cfg)
    ref = dense.run(params, [req])[0].tokens
    eng = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=24, page_size=8, prefill_chunk=8
    )
    # Warm the prefix cache with the same prompt, then poison the
    # request's first decode tick. Prompt 9 at chunk 8 prefills over
    # ticks +1 (chunk 1) and +2 (final chunk + first decode dispatch):
    # the nan_row lands on that first decode, row 0 (first free slot).
    eng.run(params, [dict(prompt=req["prompt"], max_new_tokens=1)])
    queries_before = eng.pool.stats["prefix_queries"]
    hits_before = eng.pool.stats["prefix_hits"]
    FaultInjector(
        [Fault(tick=eng._ticks + 2, kind="nan_row", row=0)]
    ).install(eng)
    rid = eng.submit(**req)
    out = eng.run(params)
    assert eng.counters["nan_quarantines"] == 1
    # The first admission queried (and HIT) the cache; the
    # post-quarantine re-admit deliberately queried NOTHING — a cached
    # page could carry the very poison the retry is escaping.
    assert eng.pool.stats["prefix_queries"] == queries_before + 1
    assert eng.pool.stats["prefix_hits"] == hits_before + 1
    assert out[rid].state == "DONE"
    np.testing.assert_array_equal(out[rid].tokens, ref)


# (H, Hkv, D, page, KEY_BLOCK): a table of 16 pages is two blocks of 8
_KERNEL_SHAPES = {
    "grouped": (32, 8, 64, 64, 512),  # granite-4.0-h-micro's attention
    "ungrouped": (4, 4, 16, 16, 128),
}
# rows' depths in blocks and positions: a free lane (its table all scratch),
# inside the first page, a block's last position, the next block's first,
# the table's last
_KERNEL_DEPTHS = {
    "block_edges": [(1, -1), (1, 0), (2, -1)],
    "free_and_shallow": [None, (0, 3), None, (1, 70)],
}


@pytest.mark.parametrize("scale", [None, 0.015625])
@pytest.mark.parametrize("depths", list(_KERNEL_DEPTHS))
@pytest.mark.parametrize("shape", list(_KERNEL_SHAPES))
def test_paged_kernel_reads_blocks_of_pages_to_each_rows_depth(
        shape, depths, scale, monkeypatch):
    """ops/paged_kernel.py (blocks of pages by async copies, two products a
    block for all heads) against the gathered reference: rows that end
    inside the first page, on a block's last position and on the next
    block's first, a free lane at depth 0; D^-1/2 and a published
    multiplier; table entries past a row's pages pointing at a scratch page
    of large numbers, which the mask must give weight 0."""
    from pytorch_distributed_tpu.ops import paged_kernel as pk

    h, hkv, d, page, block = _KERNEL_SHAPES[shape]
    monkeypatch.setattr(pk, "KEY_BLOCK", block)
    n_pages = 16
    assert pk.key_block_pages(n_pages, page) == 8
    depths = [at and at[0] * block + at[1] for at in _KERNEL_DEPTHS[depths]]
    rng = np.random.default_rng(11)
    b, pool = len(depths), len(depths) * n_pages + 1
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k, v = (rng.normal(size=(pool, page, hkv * d)) for _ in range(2))
    k[0], v[0] = 3e4 * rng.normal(size=(2,) + k[0].shape)  # the scratch page
    free = list(rng.permutation(np.arange(1, pool)))
    tables = np.zeros((b, n_pages), np.int32)
    for i, depth in enumerate(depths):
        if depth is not None:
            need = depth // page + 1
            tables[i, :need] = [free.pop() for _ in range(need)]
    lengths = np.asarray([depth or 0 for depth in depths], np.int32)
    k, v = jnp.asarray(k, jnp.float32), jnp.asarray(v, jnp.float32)
    kw = {} if scale is None else {"scale": scale}
    out = pk.paged_decode_attention(
        q, k, v, tables, lengths, interpret=True, **kw)
    with jax.default_matmul_precision("highest"):
        ref = pk.paged_decode_attention_reference(
            q, k, v, tables, lengths, **kw)
    assert np.isfinite(np.asarray(out)).all()
    live = [i for i, depth in enumerate(depths) if depth is not None]
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(ref)[live], rtol=1e-5, atol=1e-5)
    # and the two scales are two answers
    if scale is not None:
        other = pk.paged_decode_attention(
            q, k, v, tables, lengths, interpret=True)
        assert np.abs(np.asarray(other - out)[live]).max() > 1e-3


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_paged_kernel_matches_gather_fallback(dtype, tol):
    """The Pallas paged-attention kernel (interpret mode on this rig)
    matches the XLA gather reference over GQA heads, ragged depths, and
    scratch-page table entries, on pages of the pool's stored shape
    [P, page, Hkv*D] (bf16 as cell 3 holds them; the int8 pages'
    twin is tests/test_quant.py's)."""
    from pytorch_distributed_tpu.ops.paged_kernel import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )

    rng = np.random.default_rng(7)
    b, h, hkv, d, pool, page, n_pages = 4, 8, 2, 16, 11, 8, 4
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(pool, page, hkv * d)), dtype)
    v = jnp.asarray(rng.normal(size=(pool, page, hkv * d)), dtype)
    tables = np.zeros((b, n_pages), np.int32)
    lengths = np.asarray([0, 7, 17, 30], np.int32)
    # Allocate only the pages each depth needs; the rest stay scratch.
    pid = 1
    for i, ln in enumerate(lengths):
        for j in range(int(ln) // page + 1):
            tables[i, j] = pid
            pid += 1
    out = paged_decode_attention(
        q, k, v, tables, lengths, interpret=True
    )
    with jax.default_matmul_precision("highest"):
        ref = paged_decode_attention_reference(
            *(x.astype(jnp.float32) for x in (q, k, v)), tables, lengths
        )
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)), np.asarray(ref),
        rtol=tol, atol=tol,
    )
    # And through the engine's forward: the kernel path emits the same
    # tokens as the gather path for a real request.
    cfg = _cfg("llama")  # GQA: kv_heads < n_head
    params = _params(cfg)
    req = dict(prompt=_prompt(9, 3), max_new_tokens=6)
    out_g = _paged(cfg).run(params, [req])[0].tokens
    eng_k = PagedBatchedDecodeEngine(
        cfg, slots=3, max_len=32, page_size=8, prefill_chunk=8,
        paged_attention="kernel_interpret",
    )
    np.testing.assert_array_equal(eng_k.run(params, [req])[0].tokens, out_g)


def test_bucket_and_page_boundary_prompts():
    """Satellite: BucketSpec boundary lengths on the dense engine
    (exactly at a bucket edge) and page/chunk multiples on the paged
    one (incl. a prompt the prefix cache covers in FULL chunks, where
    the cached cut must stop at len-1 so one token still prefills) all
    match their references."""
    cfg = _cfg()
    params = _params(cfg)
    dense = _dense(cfg)
    paged = _paged(cfg)
    for tp in (8, 16, 24):  # bucket edges == page multiples here
        req = dict(prompt=_prompt(tp, 50 + tp), max_new_tokens=4)
        out_d = dense.run(params, [req])
        out_p = paged.run(params, [req])
        rid = max(out_d)
        np.testing.assert_array_equal(
            out_p[rid].tokens, out_d[rid].tokens, err_msg=f"Tp={tp}"
        )
    # Full-prefix cache coverage: resubmit an exact 16-token prompt the
    # cache now holds wholly; the cut is capped at 15 -> chunk-aligned 8,
    # so the final 8 tokens re-prefill and the output is unchanged.
    req = dict(prompt=_prompt(16, 66), max_new_tokens=4)
    first = paged.run(params, [req])
    again = paged.run(params, [req])
    r0, r1 = max(first), max(again)
    np.testing.assert_array_equal(again[r1].tokens, first[r0].tokens)
    assert paged.pool.stats["prefix_hit_tokens"] >= 8


# -- slow tier: composition matrix -----------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("sampled", [False, True])
def test_paged_vs_dense_matrix(family, sampled):
    """Families x greedy/sampled: paged rows vs the dense engine."""
    cfg = _cfg(family)
    params = _params(cfg)
    dense = _dense(cfg)
    paged = _paged(cfg)
    kw = (
        dict(temperature=0.8, key=jax.random.key(3), top_p=0.9)
        if sampled
        else {}
    )
    reqs = [
        dict(prompt=_prompt(tp, 70 + tp), max_new_tokens=8, **kw)
        for tp in (5, 9, 13)
    ]
    out_d = dense.run(params, reqs)
    out_p = paged.run(params, reqs)
    for rid in out_p:
        np.testing.assert_array_equal(
            out_p[rid].tokens, out_d[rid].tokens,
            err_msg=f"{family} sampled={sampled} request {rid}",
        )


@pytest.mark.slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("sampled", [False, True])
def test_paged_tp_matches_dense_tp(eight_devices, family, sampled):
    """TP paged (head-sharded page pool) vs TP dense — the acceptance
    criterion's 'plain + TP' token-equality leg."""
    cfg = _cfg(family)
    params = _params(cfg)
    mcfg = MeshConfig(tensor=2, strategy="no_shard")
    dense = BatchedDecodeEngine(
        cfg, slots=3, max_len=24, buckets=BucketSpec((8, 16)),
        mesh_cfg=mcfg,
    )
    paged = PagedBatchedDecodeEngine(
        cfg, slots=3, max_len=24, page_size=8, prefill_chunk=8,
        mesh_cfg=mcfg,
    )
    kw = (
        dict(temperature=1.0, key=jax.random.key(5), top_k=13)
        if sampled
        else {}
    )
    reqs = [
        dict(prompt=_prompt(tp, 80 + tp), max_new_tokens=6, **kw)
        for tp in (5, 9)
    ]
    out_d = dense.run(params, reqs)
    out_p = paged.run(params, reqs)
    for rid in out_p:
        np.testing.assert_array_equal(
            out_p[rid].tokens, out_d[rid].tokens,
            err_msg=f"tp {family} sampled={sampled} request {rid}",
        )


# -- tier-1 again: chunked prefill beside decode ---------------------------


def test_long_prompt_chunked_prefill_does_not_stall_neighbours():
    """Chunked prefill interleaves with decode: while a long admission
    trickles in chunk by chunk, an in-flight row keeps generating every
    tick (its tokens match the dense reference), and per-tick prefill
    work is bounded by one chunk."""
    cfg = _cfg()
    params = _params(cfg)
    dense = BatchedDecodeEngine(
        cfg, slots=2, max_len=64, buckets=BucketSpec((8, 64))
    )
    short = dict(prompt=_prompt(5, 1), max_new_tokens=12)
    long = dict(prompt=_prompt(40, 2), max_new_tokens=8, temperature=0.9,
                key=jax.random.key(9), top_k=7)
    ref = dense.run(params, [short, long])
    eng = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=64, page_size=8, prefill_chunk=8
    )
    r_short = eng.submit(**short)
    eng.step(params)  # short admitted + prefilled + first decode
    r_long = eng.submit(**long)
    gen_before = len(eng._slots[0].generated)
    chunk_ticks = 0
    while not (eng._slots[1] is not None and eng._slots[1].ready):
        eng.step(params)
        chunk_ticks += 1
    # 40 tokens / 8-token chunks = 5 chunk ticks (admission inclusive);
    # the neighbour decoded one token through every one of them.
    assert chunk_ticks == 5
    assert len(eng._slots[0].generated) == gen_before + chunk_ticks
    out = eng.run(params)
    np.testing.assert_array_equal(out[r_short].tokens, ref[0].tokens)
    np.testing.assert_array_equal(out[r_long].tokens, ref[1].tokens)
