"""Batched speculative decoding (serving/engine.py ``speculative_k``).

The load-bearing invariant, inherited from the serial prompt-lookup
path and now pinned on the ENGINES: greedy speculative output is
TOKEN-EQUAL to the non-speculative engine by construction — the
verification forward is the ground truth, drafts only change speed.
Battery:

1. spec-vs-plain token equality on busy mixed batches (greedy +
   sampled rows): dense engine, paged engine (f32 and int8 pages),
   TP (the paged gpt2 pair in tier-1, the rest of the matrix on the
   slow tier) — with accepts asserted > 0 so the pins are
   never vacuous.
2. tail-page rollback never dirties shared/pinned prefix pages (the
   COW pin extended to speculation): the cached pages' device bytes
   are snapshotted around a speculating borrower's whole run.
3. accept-length edge cases — no-match/zero-draft fallback (the k=0
   degenerate tick), full accept through the ``draft_hook`` surface
   (strictly fewer decode dispatches than plain), EOS inside a draft
   window, rows flush against max_len (draft lanes past the cache
   extent are dropped/scratch-redirected, never clamp-shifted onto
   committed positions).
4. zero-steady-state-compile churn with speculation on, and strict
   donation of the cache through ``decode_spec_step``.
5. the PR-6 fault model on speculative rows: NaN quarantine, dispatch
   failure, and snapshot/replay all continue token-identically.
6. constructor validation + the uniform ``stats()`` schema
   (``speculative_k`` / ``spec_accept_rate`` / drafted-token counters
   on every engine, the serial one included).
"""

import jax
import numpy as np
import pytest

from pytorch_distributed_tpu.config import MeshConfig, ModelConfig
from pytorch_distributed_tpu.serving.chaos import Fault, FaultInjector
from pytorch_distributed_tpu.serving.engine import (
    BatchedDecodeEngine,
    BucketSpec,
    DecodeEngine,
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


_REP = np.array([3, 8, 3, 8, 3, 8, 3], np.int32)  # lookup fires


def _dense(cfg, spec=0, **kw):
    kw.setdefault("buckets", BucketSpec((8, 16, 32)))
    return BatchedDecodeEngine(
        cfg, slots=3, max_len=32, speculative_k=spec, **kw
    )


def _paged(cfg, spec=0, **kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return PagedBatchedDecodeEngine(
        cfg, slots=3, max_len=32, speculative_k=spec, **kw
    )


def _mixed_requests():
    """Repetitive + random prompts x {greedy, top-k, top-p}, more
    requests than slots: the greedy rows' lookup fires (repetitive
    prompt, and greedy decode of a fixed model self-loops), sampled
    rows ride zero-draft lanes."""
    return [
        dict(prompt=_REP.copy(), max_new_tokens=10),
        dict(prompt=_prompt(5, 1), max_new_tokens=6),
        dict(prompt=_prompt(8, 2), max_new_tokens=6, temperature=0.9,
             key=jax.random.key(11), top_k=17),
        dict(prompt=_prompt(3, 3), max_new_tokens=4, temperature=1.1,
             key=jax.random.key(12), top_p=0.9),
    ]


def _assert_equal_runs(out_plain, out_spec):
    assert set(out_spec) == set(out_plain)
    for rid in out_plain:
        assert out_plain[rid].state == "DONE"
        assert out_spec[rid].state == "DONE"
        np.testing.assert_array_equal(
            out_spec[rid].tokens, out_plain[rid].tokens,
            err_msg=f"request {rid}",
        )


@pytest.fixture(scope="module")
def cfgp():
    cfg = _cfg()
    return cfg, _params(cfg)


@pytest.fixture(scope="module")
def spec_clean(cfgp):
    """The fault-free speculative reference run the fault-model tests
    compare against — computed ONCE (tier-1 budget: three identical
    engine builds + runs collapse to one)."""
    cfg, params = cfgp
    return _paged(cfg, spec=4).run(params, _mixed_requests())


def test_spec_rows_match_plain_dense_engine(cfgp):
    """The tier-1 dense pin: a busy slot batch with speculation on
    emits exactly the plain engine's tokens — and actually accepted
    drafts (a 0-accept run would make the equality vacuous)."""
    cfg, params = cfgp
    out_p = _dense(cfg).run(params, _mixed_requests())
    spec = _dense(cfg, spec=4)
    out_s = spec.run(params, _mixed_requests())
    _assert_equal_runs(out_p, out_s)
    assert spec.counters["accepted_tokens"] > 0
    assert spec.counters["drafted_tokens"] >= spec.counters[
        "accepted_tokens"
    ]


def test_spec_rows_match_plain_paged_engine(cfgp):
    """The tier-1 paged pin: chunked prefill + block-table verify
    windows + tail-page rollback, token-equal to the plain paged
    engine."""
    cfg, params = cfgp
    out_p = _paged(cfg).run(params, _mixed_requests())
    spec = _paged(cfg, spec=4)
    out_s = spec.run(params, _mixed_requests())
    _assert_equal_runs(out_p, out_s)
    assert spec.counters["accepted_tokens"] > 0


def test_spec_int8_pages_match_plain_int8(cfgp):
    """Quantized pages under speculation: quantize-on-append covers the
    whole verify window, rollback is depth truncation — per-token
    scales mean re-appending over rejected-draft garbage can never
    re-quantize a neighbouring token, so int8-spec tokens bit-equal
    int8-plain (same quantized cache content, same dequant math)."""
    cfg, params = cfgp
    out_p = _paged(cfg, kv_quant="int8").run(params, _mixed_requests())
    spec = _paged(cfg, spec=4, kv_quant="int8")
    out_s = spec.run(params, _mixed_requests())
    _assert_equal_runs(out_p, out_s)
    assert spec.counters["accepted_tokens"] > 0


def test_spec_rollback_never_dirties_shared_prefix_pages(cfgp):
    """The COW pin extended to speculation: a row borrowing cached
    prefix pages speculates (drafts mostly rejected — random
    continuation), and the cached pages' DEVICE BYTES are identical
    before and after its whole run, while its tokens match a
    no-sharing engine's. Rollback garbage is confined to the row's
    private tail pages by construction (every verify-window write
    lands at >= the row's first private position)."""
    cfg, params = cfgp
    eng = _paged(cfg, spec=4)
    prefix = _prompt(16, 9)  # two full chunks -> published to the cache
    out1 = eng.run(params, [dict(prompt=prefix, max_new_tokens=4)])
    assert out1[0].state == "DONE"
    cached = sorted(eng.pool.cached_page_ids())
    assert cached, "prefix chunks were not published"
    before = {
        leaf: np.asarray(eng._cache[leaf])[:, cached].copy()
        for leaf in eng._cache
    }

    tail = _prompt(4, 10)
    req2 = dict(
        prompt=np.concatenate([prefix, tail]), max_new_tokens=10
    )
    out2 = eng.run(params, [req2])
    assert out2[1].state == "DONE"
    assert eng.pool.stats["prefix_hits"] >= 1, "req2 never hit the cache"
    for leaf in before:
        np.testing.assert_array_equal(
            np.asarray(eng._cache[leaf])[:, cached], before[leaf],
            err_msg=f"speculation dirtied cached prefix pages ({leaf})",
        )
    # And the borrower's output matches an engine that never shared.
    ref = _paged(cfg, spec=4).run(params, [req2])
    np.testing.assert_array_equal(out2[1].tokens, ref[0].tokens)


def test_spec_zero_draft_rows_degenerate_to_plain_tick(cfgp):
    """k=0 fallback: rows whose history has no n-gram match (or whose
    remaining budget is 1) draft nothing — the verify step commits
    exactly one token per tick and the output is still the plain
    decode. A too-short history must not crash the drafter either."""
    cfg, params = cfgp
    reqs = [dict(prompt=np.array([7], np.int32), max_new_tokens=3),
            dict(prompt=_prompt(4, 5), max_new_tokens=2)]
    out_p = _paged(cfg).run(params, reqs)
    spec = _paged(cfg, spec=4, spec_ngram=3)
    out_s = spec.run(params, reqs)
    _assert_equal_runs(out_p, out_s)


def test_spec_full_accept_via_draft_hook_saves_ticks(cfgp):
    """The draft-hook surface + the full-accept edge: a hook that
    drafts the model's own continuation (oracle drafts) commits k+1
    tokens per tick — strictly fewer scheduler ticks than plain for
    the same (identical) output."""
    cfg, params = cfgp
    prompt = _prompt(6, 6)
    plain = _paged(cfg)
    out_p = plain.run(params, [dict(prompt=prompt, max_new_tokens=16)])
    full = np.asarray(out_p[0].tokens)

    def oracle(history, k):
        n = history.shape[0]
        return full[n : n + k]  # the exact greedy continuation

    spec = _paged(cfg, spec=4, draft_hook=oracle)
    out_s = spec.run(params, [dict(prompt=prompt, max_new_tokens=16)])
    np.testing.assert_array_equal(out_s[0].tokens, full)
    assert spec.counters["accepted_tokens"] == spec.counters[
        "drafted_tokens"
    ] > 0
    # 16 tokens at up to 5/tick: the verify path must have used fewer
    # decode dispatches than plain's 15 post-prefill ticks.
    assert spec._ticks < plain._ticks


def test_spec_eos_inside_draft_window(cfgp):
    """EOS inside an accepted window: commit stops AT the EOS token,
    later (already-verified) lanes are discarded, and the truncated
    output matches the plain engine's EOS handling exactly."""
    cfg, params = cfgp
    probe = _paged(cfg).run(
        params, [dict(prompt=_REP.copy(), max_new_tokens=12)]
    )
    gen = np.asarray(probe[0].tokens)[len(_REP):]
    eos = int(gen[len(gen) // 2])  # a token the model will emit mid-run
    req = [dict(prompt=_REP.copy(), max_new_tokens=12, eos_id=eos)]
    out_p = _paged(cfg).run(params, req)
    out_s = _paged(cfg, spec=6).run(params, req)
    _assert_equal_runs(out_p, out_s)
    assert len(out_s[0].tokens) < len(probe[0].tokens)


@pytest.mark.slow
def test_spec_rows_flush_against_max_len(cfgp):
    """Draft lanes past a row's cache extent: prompt + max_new ==
    max_len, so late verify windows cross the boundary — OOB lanes are
    dropped (dense) / scratch-redirected (paged) rather than
    clamp-shifted onto committed positions, and the output still
    equals plain. Plus the hostile-draft-hook pin: garbage drafts are
    clipped to the vocab and can only cost speed, never correctness."""
    cfg, params = cfgp
    reqs = [
        dict(prompt=np.array([5, 9, 5, 9, 5, 9], np.int32),
             max_new_tokens=26),  # 6 + 26 == max_len == 32
        dict(prompt=_prompt(4, 7), max_new_tokens=28),
    ]
    for mk in (_dense, _paged):
        out_p = mk(cfg).run(params, reqs)
        out_s = mk(cfg, spec=5).run(params, reqs)
        _assert_equal_runs(out_p, out_s)
    wild = _paged(cfg, spec=3,
                  draft_hook=lambda h, k: np.full((8,), 10**9))
    out_w = wild.run(params, reqs)
    _assert_equal_runs(out_p, out_w)
    assert wild.counters["accepted_tokens"] == 0  # all-garbage drafts


def test_spec_churn_zero_new_compiles_and_donation(cfgp, audit):
    """Warmup compiles groups x one chunk shape + ONE spec verify step;
    admission/retirement churn with mixed draft counts adds nothing.
    The donated pool strictly aliases through decode_spec_step."""
    cfg, params = cfgp
    eng = _paged(cfg, spec=4)
    warm = eng.warmup(params)
    eng.run(params, [
        dict(prompt=_prompt(4 + (i % 5), i), max_new_tokens=4 + (i % 4))
        for i in range(7)
    ] + [dict(prompt=_REP.copy(), max_new_tokens=8)])
    assert eng.compile_count() == warm
    eng.verify_donation(params)  # raises on any non-aliased cache leaf


def test_spec_nan_quarantine_token_identical(cfgp, spec_clean):
    """A nan_row fault on a speculative tick quarantines the row (the
    whole window's tokens are discarded — no partial commit), and the
    re-prefilled continuation is token-identical to a fault-free run;
    neighbours never notice."""
    cfg, params = cfgp
    eng = _paged(cfg, spec=4)
    FaultInjector([Fault(kind="nan_row", tick=5, row=0)]).install(eng)
    out = eng.run(params, _mixed_requests())
    assert eng._injector.counts["nan_row"] == 1
    assert eng.counters["nan_quarantines"] == 1
    _assert_equal_runs(spec_clean, out)


def test_spec_dispatch_failure_resumes_token_identical(cfgp, spec_clean):
    """A failed decode_spec_step dispatch consumed the donated pool:
    every in-flight speculative row converts to a resume entry and
    continues bit-identically (greedy AND sampled rows — the fold
    schedule rides the entries)."""
    cfg, params = cfgp
    eng = _paged(cfg, spec=4)
    FaultInjector(
        [Fault(kind="dispatch_error", tick=6,
               program="decode_spec_step")]
    ).install(eng)
    out = eng.run(params, _mixed_requests())
    assert eng._injector.counts["dispatch_error"] == 1
    assert eng.counters["dispatch_failures"] == 1
    _assert_equal_runs(spec_clean, out)


def test_spec_snapshot_replay_token_identical(cfgp, spec_clean):
    """snapshot() mid-speculation + restore() onto a rebuilt engine:
    the continuation re-prefills from committed tokens only (rejected
    drafts were never host state) and finishes token-identically."""
    cfg, params = cfgp
    eng = _paged(cfg, spec=4)
    for r in _mixed_requests():
        eng.submit(**r)
    for _ in range(6):
        eng.step(params)
    snap = eng.snapshot()
    eng2 = _paged(cfg, spec=4)
    eng2.restore(snap)
    while eng2.has_work():
        eng2.step(params)
    for rid in spec_clean:
        np.testing.assert_array_equal(
            eng2.results[rid].tokens, spec_clean[rid].tokens,
            err_msg=f"request {rid}",
        )


def test_spec_constructor_validation_and_program_gating():
    cfg = _cfg()
    with pytest.raises(ValueError, match="speculative_k"):
        _dense(cfg, spec=-1)
    with pytest.raises(ValueError, match="speculative_k"):
        BatchedDecodeEngine(cfg, slots=2, max_len=16, speculative_k=16)
    with pytest.raises(ValueError, match="spec_ngram"):
        _dense(cfg, spec=2, spec_ngram=0)
    with pytest.raises(ValueError, match="draft_hook"):
        _dense(cfg, spec=2, draft_hook="not callable")
    with pytest.raises(KeyError, match="speculative_k"):
        _dense(cfg).program("decode_spec_step")
    # Symmetric gate: a spec engine never dispatches the plain step, so
    # building it would only pollute compile_count() under the pinned
    # zero-steady-compile assertions.
    with pytest.raises(KeyError, match="decode_spec_step"):
        _dense(cfg, spec=2).program("decode_step")


def test_spec_stats_schema_uniform_and_sampled_rows_draft_nothing(cfgp):
    """The uniform stats schema: every engine reports speculative_k /
    spec_accept_rate / the drafted-token counters (the serial engine
    pinned at the off values). An all-sampled stream never drafts —
    exact sampled speculation needs rejection-sampling corrections,
    so those rows ride zero-draft lanes by design."""
    cfg, params = cfgp
    serial = DecodeEngine(cfg, max_len=32, buckets=BucketSpec((8,)))
    st = serial.stats()
    assert st["speculative_k"] == 0 and st["spec_accept_rate"] is None
    assert st["counters"]["drafted_tokens"] == 0

    eng = _paged(cfg, spec=4)
    sampled_only = [
        dict(prompt=_prompt(5, i), max_new_tokens=6, temperature=1.0,
             key=jax.random.key(40 + i), top_k=13)
        for i in range(3)
    ]
    eng.run(params, sampled_only)
    assert eng.counters["drafted_tokens"] == 0
    assert eng.counters["accepted_tokens"] == 0
    st = eng.stats()
    assert st["speculative_k"] == 4
    assert st["spec_accept_rate"] is None  # no drafts -> no rate


@pytest.mark.parametrize("paged,family", [
    (True, "gpt2"),  # tier-1: the paged gpt2 pair
    pytest.param(True, "llama", marks=pytest.mark.slow),
    pytest.param(False, "gpt2", marks=pytest.mark.slow),
    pytest.param(False, "llama", marks=pytest.mark.slow),
])
def test_spec_tp_matches_plain_tp(eight_devices, family, paged):
    """TP speculation: the k+1-wide shard_map verify step (head-sharded
    cache, Megatron psums, all-reduce=2 pinned in the registry) is
    token-equal to the plain TP engine with zero steady-state compiles
    — both families, dense and paged."""
    cfg = _cfg(family)
    params = _params(cfg)
    # tensor=2: llama's kv_heads=2 bounds the shard count (the same
    # mesh the existing TP serving matrices use).
    mesh = MeshConfig(tensor=2, strategy="no_shard")
    mk = _paged if paged else _dense
    reqs = _mixed_requests()
    out_p = mk(cfg, mesh_cfg=mesh).run(params, reqs)
    spec = mk(cfg, spec=4, mesh_cfg=mesh)
    warm = spec.warmup(params)
    out_s = spec.run(params, reqs)
    _assert_equal_runs(out_p, out_s)
    assert spec.counters["accepted_tokens"] > 0
    assert spec.compile_count() == warm


def test_spec_matches_serial_speculative_reference():
    """The engine path vs the retired-to-reference monolithic loop
    (models/speculative.py): same greedy output for a single request —
    the bit-equivalence pin behind routing generate.py --speculative
    through the engine."""
    from pytorch_distributed_tpu.models.speculative import (
        generate_speculative,
    )

    cfg = _cfg()
    params = _params(cfg)
    prompt = _prompt(6, 20)[None, :]
    ref = np.asarray(generate_speculative(params, prompt, cfg, 16))
    eng = BatchedDecodeEngine(
        cfg, slots=1, max_len=prompt.shape[1] + 16, speculative_k=8
    )
    rid = eng.submit(prompt[0], 16)
    out = eng.run(params)[rid]
    np.testing.assert_array_equal(out.tokens, ref[0])
