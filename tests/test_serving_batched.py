"""Continuous batching (serving/engine.BatchedDecodeEngine) battery.

Pins the slot-scheduled engine's contracts:

1. request equivalence — a row decoded in a BUSY slot batch emits the
   same tokens as the same request through the PR-4 serial engine
   (plain + TP, greedy + sampled). Token-level, not logit-level: XLA:CPU
   gemm rounding is batch-shape-dependent in the last ulp (a raw
   ``x @ w`` row differs between batch 1 and batch 2 on this backend),
   so bit-equality of raw logits across DIFFERENT batch shapes is not a
   property any engine can offer; tokens are what the engine returns and
   they are pinned exactly for these seeds.
2. neighbour independence — the same request decoded alone vs in a busy
   batch of the SAME engine shape is bit-equal END TO END (identical
   program, identical shapes, different neighbour rows): the per-row
   masking discipline means no row ever reads another row's cache, incl.
   the GQA head-repeat edge and dirty retired-row reuse.
3. zero-recompile churn — admissions and retirements at a fixed slot
   count add NO compiled executables (per-row pos/fold/sampling/keys are
   traced operands), and the TP decode program's collective count is
   invariant to the active-row pattern (it is pinned per compiled HLO,
   and there is exactly one compiled HLO).
4. scheduler — FIFO admission, retirement frees the slot without
   touching neighbours, full-pool backpressure queues instead of
   dropping, per-row EOS stops a row early.
5. donation — the slot cache strictly aliases in/out of both batched
   programs (the whole-(slots, max_len)-cache would double-buffer per
   token otherwise).

Plus the satellite pins: the serial engine's LRU-bounded cache pool and
the TP x ZeRO-3 mixed-mesh rejection diagnostic on both entry points.

Fast cases run in tier-1; the composition matrix rides the ``slow`` tier
per the PR-1 convention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.config import MeshConfig, ModelConfig
from pytorch_distributed_tpu.models import decode, get_model
from pytorch_distributed_tpu.serving.engine import (
    BatchedDecodeEngine,
    BucketSpec,
    DecodeEngine,
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
    return get_model(cfg).init(jax.random.key(seed), cfg)


def _prompt(tp, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (tp,), 0, 97), np.int32
    )


def _mixed_requests():
    """Mixed lengths x {greedy, top-k sampled, top-p sampled}; request 3
    exceeds a 3-slot pool (backpressure)."""
    return [
        dict(prompt=_prompt(5, 1), max_new_tokens=6),
        dict(prompt=_prompt(9, 2), max_new_tokens=7, temperature=0.9,
             key=jax.random.key(11), top_k=17),
        dict(prompt=_prompt(3, 3), max_new_tokens=5, temperature=1.1,
             key=jax.random.key(12), top_p=0.9),
        dict(prompt=_prompt(12, 4), max_new_tokens=4),
    ]


def _serial_ref(serial, params, req):
    kw = {k: v for k, v in req.items()
          if k not in ("prompt", "max_new_tokens")}
    out = serial.generate(
        params, jnp.asarray(req["prompt"])[None],
        req["max_new_tokens"], **kw,
    )
    return np.asarray(out)[0]


def test_busy_batch_rows_match_serial_engine():
    """The tier-1 equivalence pin: every request served from a busy slot
    batch (mixed greedy/sampled neighbours, backpressure) emits the
    tokens the PR-4 serial engine emits for it in isolation."""
    cfg = _cfg()
    params = _params(cfg)
    buckets = BucketSpec((8, 16))
    serial = DecodeEngine(cfg, max_len=24, buckets=buckets)
    eng = BatchedDecodeEngine(cfg, slots=3, max_len=24, buckets=buckets)
    reqs = _mixed_requests()
    out = eng.run(params, reqs)
    assert set(out) == {0, 1, 2, 3}
    for rid, req in enumerate(reqs):
        assert out[rid].state == "DONE"
        np.testing.assert_array_equal(
            out[rid].tokens, _serial_ref(serial, params, req),
            err_msg=f"request {rid}",
        )


def test_row_output_independent_of_neighbours():
    """Bit-exact cross-row isolation: the same request through the SAME
    engine shape, once alone and once with busy neighbours in OTHER
    buckets (so its own prefill shape is identical), must match exactly
    — any divergence means a row read its neighbours' cache."""
    cfg = _cfg()
    params = _params(cfg)
    buckets = BucketSpec((8, 16))
    req = dict(prompt=_prompt(5, 1), max_new_tokens=6, temperature=0.9,
               key=jax.random.key(7), top_k=11)
    alone = BatchedDecodeEngine(cfg, slots=3, max_len=24, buckets=buckets)
    out_alone = alone.run(params, [req])[0].tokens
    busy = BatchedDecodeEngine(cfg, slots=3, max_len=24, buckets=buckets)
    neighbours = [
        dict(prompt=_prompt(9, 8), max_new_tokens=8, temperature=1.2,
             key=jax.random.key(8), top_p=0.8),
        dict(prompt=_prompt(12, 9), max_new_tokens=8),
    ]
    out_busy = busy.run(params, [req] + neighbours)[0].tokens
    np.testing.assert_array_equal(out_busy, out_alone)


def test_churn_zero_new_compiles():
    """The zero-recompile contract: after warmup, ANY number of
    admissions/retirements at a fixed slot count adds no executables —
    and the program count is exactly buckets x group-sizes prefills + 1
    decode step."""
    cfg = _cfg()
    params = _params(cfg)
    spec = BucketSpec((8, 16))
    eng = BatchedDecodeEngine(cfg, slots=2, max_len=24, buckets=spec)
    n_warm = eng.warmup(params)
    # Warmup covers the user buckets PLUS the max_len fault-resume bucket
    # (a recovery re-prefill must never compile mid-incident).
    assert eng._prefill_buckets == (8, 16, 24)
    assert n_warm == len(eng._prefill_buckets) * len(eng._groups) + 1
    for wave in range(3):  # admit/retire churn, varying mixes
        reqs = [
            dict(prompt=_prompt(4 + wave, 20 + wave), max_new_tokens=3),
            dict(prompt=_prompt(10 + wave, 30 + wave), max_new_tokens=4,
                 temperature=0.8, key=jax.random.key(wave), top_k=5),
            dict(prompt=_prompt(6, 40 + wave), max_new_tokens=2),
        ]
        out = eng.run(params, reqs)
        assert len(out) == 3
    assert eng.compile_count() == n_warm, (
        f"{eng.compile_count() - n_warm} steady-state compiles leaked "
        "from admit/retire churn"
    )


def test_admission_fifo_and_backpressure():
    """Admission is FIFO; submissions beyond the slot count wait in the
    queue (backpressure) instead of being dropped or reordered."""
    cfg = _cfg()
    params = _params(cfg)
    eng = BatchedDecodeEngine(
        cfg, slots=2, max_len=24, buckets=BucketSpec((8,))
    )
    rids = [
        eng.submit(_prompt(4, 50 + i), 4 + i) for i in range(5)
    ]
    eng.step(params)
    assert eng.active_rids() == rids[:2]  # FIFO: first two admitted
    assert eng.queued_rids() == rids[2:]  # rest wait their turn
    seen = []
    while eng.has_work():
        seen += eng.step(params)
    assert sorted(seen) == rids
    assert set(eng.results) == set(rids)
    # Shorter budgets retire first within the first wave; rid 2 (next in
    # queue) was admitted into the freed slot before rid 3.
    assert seen.index(rids[0]) < seen.index(rids[1])


def test_retirement_keeps_neighbours_decoding():
    """A short row retiring must not perturb the long row still decoding
    beside it — the long request's tokens match its serial reference."""
    cfg = _cfg()
    params = _params(cfg)
    buckets = BucketSpec((8, 16))
    serial = DecodeEngine(cfg, max_len=32, buckets=buckets)
    eng = BatchedDecodeEngine(cfg, slots=2, max_len=32, buckets=buckets)
    short = dict(prompt=_prompt(4, 60), max_new_tokens=2)
    long = dict(prompt=_prompt(9, 61), max_new_tokens=12, temperature=1.0,
                key=jax.random.key(61), top_p=0.95)
    out = eng.run(params, [short, long])
    np.testing.assert_array_equal(
        out[0].tokens, _serial_ref(serial, params, short)
    )
    np.testing.assert_array_equal(
        out[1].tokens, _serial_ref(serial, params, long)
    )


def test_eos_stops_row_early():
    """Per-row EOS: generation stops at the first eos_id (included in
    the output), matching the serial run's prefix; neighbours keep
    their full budgets."""
    cfg = _cfg()
    params = _params(cfg)
    buckets = BucketSpec((8, 16))
    serial = DecodeEngine(cfg, max_len=24, buckets=buckets)
    req = dict(prompt=_prompt(5, 1), max_new_tokens=6)
    ref = _serial_ref(serial, params, req)
    tp = 5
    eos = int(ref[tp + 2])  # the 3rd generated token
    first_hit = tp + int(np.argmax(ref[tp:] == eos)) + 1
    eng = BatchedDecodeEngine(cfg, slots=2, max_len=24, buckets=buckets)
    rid = eng.submit(req["prompt"], 6, eos_id=eos)
    other = eng.submit(_prompt(9, 62), 6)
    eng.run(params)
    np.testing.assert_array_equal(eng.results[rid].tokens, ref[:first_hit])
    assert len(eng.results[other].tokens) == 9 + 6  # neighbour unaffected


def test_batched_engine_validation():
    cfg = _cfg()
    params = _params(cfg)
    with pytest.raises(ValueError, match="slots"):
        BatchedDecodeEngine(cfg, slots=0, max_len=16)
    with pytest.raises(ValueError, match="exceeds n_ctx"):
        BatchedDecodeEngine(cfg, slots=2, max_len=cfg.n_ctx + 1)
    with pytest.raises(ValueError, match="exceeds max_len"):
        BatchedDecodeEngine(
            cfg, slots=2, max_len=16, buckets=BucketSpec((8, 32))
        )
    with pytest.raises(ValueError, match="prefill_groups"):
        BatchedDecodeEngine(
            cfg, slots=4, max_len=16, prefill_groups=(1, 2)
        )
    with pytest.raises(NotImplementedError, match="MoE"):
        BatchedDecodeEngine(
            _cfg(n_experts=4, expert_capacity_factor=8.0),
            slots=2, max_len=16,
        )
    eng = BatchedDecodeEngine(
        cfg, slots=2, max_len=16, buckets=BucketSpec((8, 16))
    )
    with pytest.raises(ValueError, match="one sequence per request"):
        eng.submit(np.zeros((2, 4), np.int32), 4)
    with pytest.raises(ValueError, match="exceeds max_len 16"):
        eng.submit(_prompt(10, 0), 8)
    with pytest.raises(ValueError, match="PRNG key"):
        eng.submit(_prompt(4, 0), 4, temperature=0.5)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros((0,), np.int32), 4)
    # max_new_tokens<=0 is rejected loudly (the old 0-token fast path
    # silently returned the prompt, hiding budget-accounting bugs).
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        eng.submit(_prompt(4, 0), 0)
    with pytest.raises(ValueError, match="timeout_s must be > 0"):
        eng.submit(_prompt(4, 0), 2, timeout_s=0.0)
    assert eng.compile_count() == 0 and not eng.has_work()
    # pop_result delivers AND releases the terminal RequestResult.
    rid = eng.submit(_prompt(4, 0), 2)
    eng.run(params)
    res = eng.pop_result(rid)
    assert res.state == "DONE" and len(res.tokens) == 4 + 2
    assert rid not in eng.results
    with pytest.raises(KeyError):
        eng.pop_result(rid)
    with pytest.raises(RuntimeError, match="idle"):
        eng.submit(_prompt(4, 0), 2)
        eng.warmup(params)


def test_mixed_mesh_rejected_by_both_entry_points():
    """Satellite (ROADMAP serving follow-up (c)): TP x ZeRO-3 decode is
    rejected by BOTH engines with one diagnostic naming the supported
    modes — not a confusing shim-level error."""
    cfg = _cfg()
    mixed = MeshConfig(tensor=2, fsdp=2, strategy="full_shard")
    with pytest.raises(NotImplementedError, match="Supported modes"):
        DecodeEngine(cfg, max_len=16, mesh_cfg=mixed)
    with pytest.raises(NotImplementedError, match="Supported modes"):
        BatchedDecodeEngine(cfg, slots=2, max_len=16, mesh_cfg=mixed)
    # And ZeRO-3-only slot batching is future surface, said explicitly.
    with pytest.raises(NotImplementedError, match="plain and tp"):
        BatchedDecodeEngine(
            cfg, slots=2, max_len=16,
            mesh_cfg=MeshConfig(fsdp=2, strategy="full_shard"),
        )


def test_cache_pool_lru_bounded():
    """Satellite (ROADMAP serving follow-up (d)): the serial engine's
    cache pool holds at most pool_max_entries batch shapes — HBM is
    bounded under arbitrary batch-shape diversity — evicting the least
    recently used shape."""
    cfg = _cfg()
    params = _params(cfg)
    eng = DecodeEngine(
        cfg, max_len=16, buckets=BucketSpec((8,)), pool_max_entries=2
    )
    for batch in (1, 2, 3):
        prompt = jnp.asarray(
            np.tile(_prompt(4, batch), (batch, 1)), jnp.int32
        )
        eng.generate(params, prompt, 2)
    assert list(eng._cache_pool) == [2, 3]  # batch=1 evicted (LRU)
    # Reuse refreshes recency: batch=2 becomes MRU, so 3 evicts next.
    eng.generate(
        params, jnp.asarray(np.tile(_prompt(4, 9), (2, 1))), 2
    )
    prompt4 = jnp.asarray(np.tile(_prompt(4, 10), (4, 1)))
    eng.generate(params, prompt4, 2)
    assert list(eng._cache_pool) == [2, 4]
    with pytest.raises(ValueError, match="pool_max_entries"):
        DecodeEngine(cfg, max_len=16, pool_max_entries=0)


def test_failed_dispatch_resumes_in_flight_and_spares_queued():
    """A dispatch failure consumed the donated cache, so in-flight rows
    lose their K/V — but instead of aborting they convert to RESUME
    entries (re-prefilled from tokens-so-far ahead of younger queued
    traffic), the cache re-allocates, and EVERY request finishes
    token-equal to an undisturbed run."""
    from pytorch_distributed_tpu.serving.chaos import (
        Fault, FaultInjector,
    )

    cfg = _cfg()
    params = _params(cfg)
    p = _prompt(5, 1)
    reqs = [
        dict(prompt=p, max_new_tokens=8, temperature=0.9,
             key=jax.random.key(21), top_k=13),
        dict(prompt=p, max_new_tokens=4),  # no free slot -> queued
    ]
    fresh = BatchedDecodeEngine(
        cfg, slots=1, max_len=24, buckets=BucketSpec((8,))
    )
    undisturbed = fresh.run(params, reqs)
    eng = BatchedDecodeEngine(
        cfg, slots=1, max_len=24, buckets=BucketSpec((8,))
    )
    # Tick 1 admits r0; tick 3's decode dispatch fails mid-request.
    FaultInjector([Fault(tick=3, kind="dispatch_error")]).install(eng)
    r0 = eng.submit(**reqs[0])
    r1 = eng.submit(**reqs[1])
    eng.step(params)
    eng.step(params)
    assert eng.active_rids() == [r0]
    eng.step(params)  # injected failure: recovered, not raised
    assert eng.active_rids() == []
    assert eng._cache is None  # dropped, not poisoned
    assert eng.queued_rids() == [r0, r1]  # resume ahead of queued FIFO
    assert eng.counters["dispatch_failures"] == 1
    out = eng.run(params)
    for rid in (r0, r1):
        assert out[rid].state == "DONE"
        np.testing.assert_array_equal(
            out[rid].tokens, undisturbed[rid].tokens,
            err_msg=f"request {rid} diverged across the fault resume",
        )


def test_batched_donation_aliases_every_program(audit):
    """Strict donation on both slot-batched programs: the gather ->
    forward -> scatter prefill and the per-row-scatter decode step must
    both alias the (slots, max_len) cache in place."""
    from pytorch_distributed_tpu.analysis.budget import NO_COLLECTIVES

    cfg = _cfg()
    params = _params(cfg)
    eng = BatchedDecodeEngine(
        cfg, slots=2, max_len=16, buckets=BucketSpec((8,))
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


# -- slow tier: composition matrix -----------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("sampled", [False, True])
def test_busy_batch_matrix(family, sampled):
    """Families x greedy/sampled: busy-batch rows vs the serial engine."""
    cfg = _cfg(family)
    params = _params(cfg)
    buckets = BucketSpec((8, 16))
    serial = DecodeEngine(cfg, max_len=32, buckets=buckets)
    eng = BatchedDecodeEngine(cfg, slots=3, max_len=32, buckets=buckets)
    kw = (
        dict(temperature=0.8, key=jax.random.key(3), top_p=0.9)
        if sampled
        else {}
    )
    reqs = [
        dict(prompt=_prompt(tp, 70 + tp), max_new_tokens=8, **kw)
        for tp in (5, 9, 13)
    ]
    out = eng.run(params, reqs)
    for rid, req in enumerate(reqs):
        np.testing.assert_array_equal(
            out[rid].tokens, _serial_ref(serial, params, req),
            err_msg=f"{family} sampled={sampled} request {rid}",
        )


@pytest.mark.slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("sampled", [False, True])
def test_busy_batch_tp_matches_serial(eight_devices, family, sampled):
    """TP slot batching (head-sharded slot cache) vs the TP serial
    engine — greedy and sampled, busy batch."""
    cfg = _cfg(family)
    params = _params(cfg)
    mcfg = MeshConfig(tensor=2, strategy="no_shard")
    buckets = BucketSpec((8, 16))
    serial = DecodeEngine(
        cfg, max_len=24, buckets=buckets, mesh_cfg=mcfg
    )
    eng = BatchedDecodeEngine(
        cfg, slots=3, max_len=24, buckets=buckets, mesh_cfg=mcfg
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
    out = eng.run(params, reqs)
    for rid, req in enumerate(reqs):
        np.testing.assert_array_equal(
            out[rid].tokens, _serial_ref(serial, params, req),
            err_msg=f"tp {family} sampled={sampled} request {rid}",
        )


# -- tier-1 again: row-granularity edges -----------------------------------


def test_gqa_slot_reuse_no_stale_kv():
    """GQA edge at ROW granularity: a retired row's deep K/V (left dirty)
    must never surface through the head-repeat when a shorter request is
    admitted into the same slot."""
    cfg = _cfg("llama")  # n_kv_head=2 < n_head=4
    assert cfg.kv_heads < cfg.n_head
    params = _params(cfg)
    buckets = BucketSpec((16, 32))
    serial = DecodeEngine(cfg, max_len=32, buckets=buckets)
    eng = BatchedDecodeEngine(cfg, slots=1, max_len=32, buckets=buckets)
    # Request 1 fills the single slot's rows 0..23 with real K/V.
    eng.run(params, [dict(
        prompt=_prompt(14, 90), max_new_tokens=10, temperature=1.0,
        key=jax.random.key(9),
    )])
    # Request 2 reuses the SAME slot, bucket-padded 3 -> 16, greedy.
    req = dict(prompt=_prompt(3, 91), max_new_tokens=6)
    out = eng.run(params, [req])
    np.testing.assert_array_equal(
        out[1].tokens, _serial_ref(serial, params, req)
    )


def test_tp_collective_count_invariant_to_active_rows(eight_devices):
    """The registry contract, exercised end to end: after serving wildly
    different active-row patterns, the TP engine still holds exactly ONE
    compiled decode executable, and its all-reduce instruction count
    equals the pinned STABLE_MAX_COUNTS ceiling — the collective count
    cannot depend on how many rows are active because activity is not a
    program input."""
    from pytorch_distributed_tpu.analysis.budget import STABLE_MAX_COUNTS
    from pytorch_distributed_tpu.analysis.hlo import (
        collective_instructions,
    )

    cfg = _cfg()
    params = _params(cfg)
    mcfg = MeshConfig(tensor=2, strategy="no_shard")
    eng = BatchedDecodeEngine(
        cfg, slots=4, max_len=24, buckets=BucketSpec((8,)), mesh_cfg=mcfg
    )
    # 1 active row, then 4, then 2 (post-retirement mix).
    eng.run(params, [dict(prompt=_prompt(4, 95), max_new_tokens=3)])
    eng.run(params, [
        dict(prompt=_prompt(4 + i, 96 + i), max_new_tokens=3 + i)
        for i in range(4)
    ])
    assert eng._programs["decode_step"]._cache_size() == 1
    placed = eng._place_params(params)
    # The placement is identity-memoized: the per-token scheduler tick
    # must not pay a device_put tree traversal for the same param tree.
    assert eng._place_params(params) is placed
    txt = (
        eng.program("decode_step")
        .lower(*eng.example_args("decode_step", placed))
        .compile()
        .as_text()
    )
    found = {k: len(v) for k, v in collective_instructions(txt).items()}
    cap = STABLE_MAX_COUNTS["decode_batched_step_tp"]["all-reduce"]
    assert found == {"all-reduce": cap}, found
