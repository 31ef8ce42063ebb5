"""Request-lifecycle + fault-injection battery for the serving engines.

Every robustness claim in docs/ROBUSTNESS.md is pinned here against the
deterministic fault harness (serving/chaos.py) — the SAME compiled
programs production runs, with faults injected only through host-side
hooks, so none of these tests can perturb traced shapes or the pinned
collective budgets:

1. lifecycle — ``abort(rid)`` retires a queued entry or an ACTIVE slot
   row mid-decode (host bookkeeping only: zero recompiles, neighbours
   bit-equal to an undisturbed run); per-request deadlines expire queued
   and mid-decode requests with their clean partial prefix; the bounded
   admission queue rejects loudly or blocks-with-timeout.
2. fault detection — the traced NaN/Inf sentinel catches genuinely
   poisoned params end to end (serial: ``RequestFailed`` after one
   fresh-cache retry; batched: per-row quarantine then FAILED), and an
   injected transient poisoning quarantines ONE row while its neighbour
   finishes bit-identically.
3. recovery — a failed/dropped dispatch converts every in-flight row to
   a resume entry that finishes token-equal to an undisturbed run;
   ``request_retries`` exhaustion FAILs a request; ``dispatch_retries``
   consecutive failures raise ``DispatchFailure`` with consistent state;
   snapshot/restore after a simulated engine loss continues
   token-identically on a rebuilt engine.
4. guards — ``run(max_ticks=/timeout_s=)`` terminates a permanently
   faulting stream with partial results instead of looping forever.

The randomized churn+fault storm closes the file; the tests before it
are its fast, exactly-scripted building blocks.
"""

import logging

import jax
import numpy as np
import pytest

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.models import get_model
from pytorch_distributed_tpu.serving.chaos import (
    Fault,
    FaultInjector,
    VirtualClock,
)
from pytorch_distributed_tpu.serving.engine import (
    BatchedDecodeEngine,
    BucketSpec,
    DecodeEngine,
)
from pytorch_distributed_tpu.serving.lifecycle import (
    ABORTED,
    DONE,
    EXPIRED,
    FAILED,
    AdmissionQueueFull,
    DispatchFailure,
    RequestFailed,
    RequestResult,
)

pytestmark = pytest.mark.full


def _cfg(**kw):
    return ModelConfig(
        family="gpt2", vocab_size=97, n_ctx=64, n_embd=64, n_layer=2,
        n_head=4, dtype="float32", attn_pdrop=0.0, resid_pdrop=0.0,
        embd_pdrop=0.0, **kw,
    )


def _params(cfg, seed=0):
    return get_model(cfg).init(jax.random.key(seed), cfg)


def _prompt(tp, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (tp,), 0, 97), np.int32
    )


def _engine(cfg, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 24)
    kw.setdefault("buckets", BucketSpec((8,)))
    return BatchedDecodeEngine(cfg, **kw)


def _reqs():
    return [
        dict(prompt=_prompt(5, 1), max_new_tokens=8, temperature=0.9,
             key=jax.random.key(21), top_k=13),
        dict(prompt=_prompt(7, 2), max_new_tokens=6),
    ]


# -- lifecycle: abort / deadlines / backpressure ---------------------------


def test_abort_mid_decode_spares_neighbour():
    """abort() on an ACTIVE row retires it ABORTED with its clean
    partial prefix, adds no compiles, and the neighbour row finishes
    bit-equal to an undisturbed run."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    undisturbed = _engine(cfg).run(params, reqs)
    eng = _engine(cfg)
    r0 = eng.submit(**reqs[0])
    r1 = eng.submit(**reqs[1])
    eng.step(params)  # both admitted (prefill token 1)
    eng.step(params)  # one decode tick (token 2)
    warm = eng.compile_count()
    assert eng.abort(r0) is True
    res0 = eng.results[r0]
    assert res0.state == ABORTED and "mid-decode" in res0.reason
    # Clean partial prefix: prompt + every token generated pre-abort
    # (mid-request: more than the prompt, less than the full budget).
    tp, budget = len(reqs[0]["prompt"]), reqs[0]["max_new_tokens"]
    assert tp < len(res0.tokens) < tp + budget
    np.testing.assert_array_equal(
        res0.tokens, undisturbed[r0].tokens[: len(res0.tokens)]
    )
    out = eng.run(params)
    assert out[r1].state == DONE
    np.testing.assert_array_equal(
        out[r1].tokens, undisturbed[r1].tokens,
        err_msg="neighbour perturbed by a mid-decode abort",
    )
    assert eng.compile_count() == warm  # abort is pure host bookkeeping
    # Second abort: already terminal -> False; unknown rid -> KeyError.
    assert eng.abort(r0) is False
    with pytest.raises(KeyError, match="unknown rid"):
        eng.abort(999)


def test_abort_while_queued():
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg, slots=1)
    r0 = eng.submit(_prompt(5, 1), 4)
    r1 = eng.submit(_prompt(5, 2), 4)  # no free slot -> queued
    eng.step(params)
    assert eng.queued_rids() == [r1]
    assert eng.abort(r1) is True
    res = eng.results[r1]
    assert res.state == ABORTED and "queued" in res.reason
    np.testing.assert_array_equal(res.tokens, _prompt(5, 2))  # prompt only
    assert eng.run(params)[r0].state == DONE


def test_deadline_expires_queued_and_mid_decode():
    """submit(timeout_s=...): a request still queued OR mid-decode when
    its engine-clock deadline passes retires EXPIRED with its clean
    partial prefix; deadline-free neighbours are untouched."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    undisturbed = _engine(cfg).run(params, reqs)
    clock = VirtualClock()
    eng = _engine(cfg, slots=1, clock=clock)
    r0 = eng.submit(**reqs[0], timeout_s=1.0)  # will be mid-decode
    r1 = eng.submit(**reqs[1], timeout_s=0.5)  # stuck queued (1 slot)
    eng.step(params)  # admit r0 (prefill); r1 queued
    eng.step(params)  # decode tick
    clock.advance(2.0)  # a stall blows both deadlines
    done = eng.step(params)  # _expire retires both before decoding
    assert sorted(done) == [r0, r1]
    res0, res1 = eng.results[r0], eng.results[r1]
    assert res0.state == EXPIRED and "mid-decode" in res0.reason
    assert res1.state == EXPIRED and "queued" in res1.reason
    np.testing.assert_array_equal(
        res0.tokens, undisturbed[r0].tokens[: len(res0.tokens)]
    )
    np.testing.assert_array_equal(res1.tokens, reqs[1]["prompt"])
    assert not eng.has_work()


def test_bounded_queue_rejects_loudly():
    cfg = _cfg()
    eng = _engine(cfg, queue_limit=2)
    eng.submit(_prompt(4, 1), 2)
    eng.submit(_prompt(4, 2), 2)
    with pytest.raises(AdmissionQueueFull, match="queue_limit 2"):
        eng.submit(_prompt(4, 3), 2)
    with pytest.raises(ValueError, match="'reject' or 'block'"):
        _engine(cfg, backpressure="bogus")
    with pytest.raises(ValueError, match="queue_limit must be >= 1"):
        _engine(cfg, queue_limit=0)


def test_block_backpressure_drains_then_admits():
    """The 'block' policy drives the scheduler from submit until queue
    space frees — and needs params to do so."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg, queue_limit=1, backpressure="block")
    r0 = eng.submit(_prompt(4, 1), 3)
    with pytest.raises(ValueError, match="needs params"):
        eng.submit(_prompt(4, 2), 3)
    r1 = eng.submit(_prompt(4, 2), 3, params=params)  # blocks: r0 admits
    assert eng.queued_rids() == [r1] and r0 in eng.active_rids()
    out = eng.run(params)
    assert out[r0].state == DONE and out[r1].state == DONE


def test_block_backpressure_times_out():
    """When the engine cannot drain (permanent dispatch faults), the
    block policy gives up at block_timeout_s (virtual clock driven by
    the retry backoff) instead of spinning forever."""
    cfg = _cfg()
    params = _params(cfg)
    clock = VirtualClock()
    eng = _engine(
        cfg, queue_limit=1, backpressure="block", clock=clock,
        sleep=clock.sleep, dispatch_retries=None, request_retries=10**6,
    )
    FaultInjector(seed=0, p_dispatch_error=1.0, clock=clock).install(eng)
    eng.submit(_prompt(4, 1), 3)
    with pytest.raises(AdmissionQueueFull, match="not draining"):
        eng.submit(_prompt(4, 2), 3, params=params, block_timeout_s=1.0)


# -- fault detection: the traced NaN sentinel ------------------------------


def _poison(params):
    return jax.tree_util.tree_map(lambda x: x * np.nan, params)


def test_serial_engine_fails_loudly_on_nan_params():
    """End-to-end sentinel test with GENUINELY non-finite logits: the
    serial engine retries once on a fresh zeroed cache, then raises
    RequestFailed — garbage tokens never escape. nan_guard=False keeps
    the legacy (garbage-emitting) behaviour for A/B debugging."""
    cfg = _cfg()
    bad_params = _poison(_params(cfg))
    eng = DecodeEngine(cfg, max_len=24, buckets=BucketSpec((8,)))
    with pytest.raises(RequestFailed, match="non-finite logits"):
        eng.generate(bad_params, _prompt(5, 1)[None], 4)
    # The stream fails at the first poisoned step, mid-iteration.
    gen = eng.stream(bad_params, _prompt(5, 1)[None], 4)
    with pytest.raises(RequestFailed, match="non-finite logits"):
        next(gen)
    unguarded = DecodeEngine(
        cfg, max_len=24, buckets=BucketSpec((8,)), nan_guard=False
    )
    out = unguarded.generate(bad_params, _prompt(5, 1)[None], 4)
    assert out.shape == (1, 9)  # legacy: garbage flows


def test_batched_engine_quarantines_then_fails_on_nan_params():
    """Genuinely poisoned params through the batched engine: every
    request is quarantined once (fresh re-prefill), reproduces, and
    retires FAILED with its clean prefix (here: the prompt alone —
    the poisoned prefill token is never appended)."""
    cfg = _cfg()
    params = _poison(_params(cfg))
    eng = _engine(cfg)
    reqs = [dict(prompt=_prompt(5, 1), max_new_tokens=4),
            dict(prompt=_prompt(7, 2), max_new_tokens=4)]
    out = eng.run(params, reqs)
    for rid, req in enumerate(reqs):
        assert out[rid].state == FAILED
        assert "quarantine retry" in out[rid].reason
        np.testing.assert_array_equal(out[rid].tokens, req["prompt"])
    assert eng.counters["nan_quarantines"] == 4  # 2 requests x (hit + retry)
    assert not eng.has_work()


def test_nan_quarantine_isolates_row():
    """An injected TRANSIENT poisoning of one row mid-decode: that row
    is quarantined (freed, re-prefilled from its clean prefix on a
    fresh tick) and still finishes DONE and bit-equal to an undisturbed
    run — and so does its untouched neighbour. Zero steady compiles:
    the quarantine re-prefill uses a warmed bucket."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    undisturbed = _engine(cfg).run(params, reqs)
    eng = _engine(cfg)
    eng.warmup(params)
    warm = eng.compile_count()
    # Tick 1 admits both rows (r0 -> row 0); tick 3 poisons row 0's
    # decode step. The flag is host-side: the computed token was clean,
    # so the resumed row re-derives it bit-identically.
    FaultInjector([Fault(tick=3, kind="nan_row", row=0)]).install(eng)
    out = eng.run(params, reqs)
    assert eng.counters["nan_quarantines"] == 1
    for rid in (0, 1):
        assert out[rid].state == DONE
        np.testing.assert_array_equal(
            out[rid].tokens, undisturbed[rid].tokens,
            err_msg=f"request {rid} diverged across a row quarantine",
        )
    assert eng.compile_count() == warm, "quarantine recovery recompiled"


# -- recovery: dropped results, retry budgets, snapshot/replay -------------


def test_dropped_result_recovers_token_equal():
    """drop_result (program ran, result lost in transit) takes the same
    recovery path as a failed dispatch: in-flight rows resume from
    their clean prefix and finish token-equal to an undisturbed run."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    undisturbed = _engine(cfg).run(params, reqs)
    eng = _engine(cfg)
    FaultInjector([Fault(tick=2, kind="drop_result")]).install(eng)
    out = eng.run(params, reqs)
    assert eng.counters["dispatch_failures"] == 1
    assert eng.counters["resumes"] == 2
    for rid in (0, 1):
        assert out[rid].state == DONE
        np.testing.assert_array_equal(
            out[rid].tokens, undisturbed[rid].tokens,
            err_msg=f"request {rid} diverged across a dropped result",
        )


def test_request_retries_exhaustion_fails_request():
    """request_retries=0: the first dispatch failure already exceeds the
    per-request fault-resume budget, so the in-flight request retires
    FAILED (clean prefix) instead of resuming."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg, request_retries=0)
    rid = eng.submit(_prompt(5, 1), 6)
    eng.step(params)  # admitted
    FaultInjector([Fault(tick=2, kind="dispatch_error")]).install(eng)
    done = eng.step(params)
    assert done == [rid]
    res = eng.results[rid]
    assert res.state == FAILED and "fault-resume retries" in res.reason
    np.testing.assert_array_equal(res.tokens[:5], _prompt(5, 1))


def test_dispatch_retries_exhaustion_raises_consistent():
    """dispatch_retries consecutive failures raise DispatchFailure with
    the engine CONSISTENT: everything requeued, nothing active, nothing
    lost — clearing the fault and stepping again finishes all requests
    token-equal to an undisturbed run. The exponential backoff between
    attempts is visible on the virtual clock."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs()
    undisturbed = _engine(cfg).run(params, reqs)
    clock = VirtualClock()
    eng = _engine(
        cfg, dispatch_retries=2, request_retries=10, clock=clock,
        sleep=clock.sleep, retry_backoff_s=0.05,
    )
    inj = FaultInjector(
        seed=0, p_dispatch_error=1.0, clock=clock
    ).install(eng)
    rids = [eng.submit(**r) for r in reqs]
    with pytest.raises(DispatchFailure, match="state is consistent"):
        while True:
            eng.step(params)
    assert inj.counts["dispatch_error"] == 3  # streak 3 > retries 2
    assert eng.active_rids() == []
    assert eng.queued_rids() == rids  # rid order == FIFO order
    assert clock.now >= 0.05 + 0.10  # backoff slept between attempts
    eng.set_fault_injector(None)
    out = eng.run(params)
    for rid in rids:
        assert out[rid].state == DONE
        np.testing.assert_array_equal(
            out[rid].tokens, undisturbed[rid].tokens,
            err_msg=f"request {rid} diverged across DispatchFailure",
        )


def test_snapshot_replay_token_identical():
    """Simulated engine loss mid-stream: snapshot the dying engine,
    rebuild from scratch (fresh programs, fresh cache), restore, finish.
    Every request — in-flight at the loss, still queued, and already
    retired — ends token-identical to an uninterrupted run."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs() + [dict(prompt=_prompt(4, 3), max_new_tokens=5,
                           temperature=1.1, key=jax.random.key(31),
                           top_p=0.9)]
    undisturbed = _engine(cfg).run(params, reqs)
    eng = _engine(cfg)  # slots=2: req 2 still queued at the loss
    rids = [eng.submit(**r) for r in reqs]
    eng.step(params)
    eng.step(params)  # rows mid-decode at unrelated depths
    snap = eng.snapshot()
    assert sorted(q.rid for q in snap.pending) == rids
    del eng  # the device state (donated cache) dies with the engine
    eng2 = _engine(cfg)
    eng2.restore(snap)
    out = eng2.run(params)
    assert sorted(out) == rids
    for rid in rids:
        assert out[rid].state == DONE
        np.testing.assert_array_equal(
            out[rid].tokens, undisturbed[rid].tokens,
            err_msg=f"request {rid} diverged across engine loss/replay",
        )
    # restore() demands a fresh idle engine.
    with pytest.raises(RuntimeError, match="fresh idle engine"):
        eng2.restore(snap)


def test_run_guard_terminates_permanent_fault():
    """A permanently faulting stream (every dispatch fails) terminates
    via run(max_ticks=...) with the work still queued — never an
    infinite loop; timeout_s bounds the same way on the engine clock."""
    cfg = _cfg()
    params = _params(cfg)
    clock = VirtualClock()
    eng = _engine(
        cfg, dispatch_retries=None, request_retries=10**6, clock=clock,
        sleep=clock.sleep,
    )
    FaultInjector(seed=0, p_dispatch_error=1.0, clock=clock).install(eng)
    rid = eng.submit(_prompt(5, 1), 4)
    out = eng.run(params, max_ticks=7)
    assert out == {} and eng.has_work() and eng.queued_rids() == [rid]
    # Engine-clock budget: the backoff sleeps advance the virtual clock
    # past the deadline even though no dispatch ever succeeds.
    out = eng.run(params, timeout_s=5.0)
    assert out == {} and eng.has_work()
    assert clock.now >= 5.0


# -- harness plumbing ------------------------------------------------------


def test_lifecycle_and_fault_vocabulary_validate():
    with pytest.raises(ValueError, match="state must be one of"):
        RequestResult(rid=0, state="BOGUS", tokens=np.zeros(1, np.int32))
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault(tick=1, kind="bogus")
    with pytest.raises(ValueError, match="VirtualClock"):
        inj = FaultInjector([Fault(tick=1, kind="slow_tick", seconds=1.0)])
        inj.on_tick(1)
    clock = VirtualClock()
    inj = FaultInjector(
        [Fault(tick=1, kind="slow_tick", seconds=2.5)], clock=clock
    )
    inj.on_tick(1)
    assert clock.now == 2.5 and inj.counts["slow_tick"] == 1


def test_lifecycle_log_is_diagnosable():
    """The structured lifecycle log alone reconstructs a request's
    journey: submit -> admit -> retire with rid and timestamps. (The
    ``pdtpu`` root logger does not propagate — incident tooling
    attaches its own handler, so this test does too.)"""
    cfg = _cfg()
    params = _params(cfg)
    eng = _engine(cfg)
    events: list[str] = []
    handler = logging.Handler()
    handler.emit = lambda r: events.append(r.getMessage())
    lg = logging.getLogger("pdtpu.serving")
    lg.addHandler(handler)
    old_level = lg.level
    lg.setLevel(logging.DEBUG)
    try:
        rid = eng.submit(_prompt(5, 1), 2, timeout_s=9.0)
        eng.run(params)
    finally:
        lg.removeHandler(handler)
        lg.setLevel(old_level)
    assert any(
        m.startswith("event=submit") and f"rid={rid}" in m
        and "deadline=" in m for m in events
    )
    assert any(
        m.startswith("event=admit") and f"rid={rid}" in m for m in events
    )
    assert any(
        m.startswith("event=retire") and f"rid={rid}" in m
        and "state=DONE" in m for m in events
    )


# -- the randomized churn + fault storm ---------------------------------------


def _storm_drive(engine, params, reqs, bursts, *, injector=None,
                 abort_rng=None, p_abort=0.0, loss_tick=None,
                 make_engine=None, max_ticks=5000):
    """One leg of the storm: seeded arrival bursts, one step per tick,
    seeded aborts against LIVE rids (mid-decode rows preferred), and at
    ``loss_tick`` a full engine loss recovered through snapshot ->
    rebuild -> restore. Every tick checks that a terminal rid never
    re-enters the queue or a slot. Returns (results, engines,
    each engine's compile count after its warmup)."""
    from pytorch_distributed_tpu.serving.lifecycle import TERMINAL_STATES

    engines, warm = [engine], [engine.compile_count()]
    next_req = tick = 0
    seen_terminal: set[int] = set()
    while (next_req < len(reqs) or engine.has_work()) and tick < max_ticks:
        tick += 1
        n_new = min(bursts[tick % len(bursts)], len(reqs) - next_req)
        for _ in range(n_new):
            assert engine.submit(**reqs[next_req]) == next_req
            next_req += 1
        if not engine.has_work():
            continue
        engine.step(params)
        if abort_rng is not None and abort_rng.random() < p_abort:
            live = engine.active_rids() or engine.queued_rids()
            if live:
                engine.abort(int(live[abort_rng.integers(len(live))]))
        for rid, res in engine.results.items():
            assert res.state in TERMINAL_STATES, (tick, rid, res.state)
            seen_terminal.add(rid)
        live = set(engine.queued_rids()) | set(engine.active_rids())
        assert not live & seen_terminal, (
            f"tick {tick}: terminal rids re-entered the engine: "
            f"{sorted(live & seen_terminal)}"
        )
        if tick == loss_tick:
            snap = engine.snapshot()
            engine = make_engine()
            engine.warmup(params)
            warm.append(engine.compile_count())
            engine.restore(snap)
            injector.install(engine)
            engines.append(engine)
    assert tick < max_ticks, "storm did not drain"
    results = {}
    for eng in engines:
        results.update(eng.results)
    return results, engines, warm


def test_storm_invariants_hold():
    """Seeded random churn with every fault kind composed — NaN rows,
    dispatch failures, dropped results, scheduler stalls that expire
    deadlines, mid-flight aborts, and one engine loss recovered through
    snapshot/restore — over a tiered 24-request stream on a
    ``VirtualClock``. Invariants (docs/ROBUSTNESS.md): every rid reaches
    exactly ONE terminal state; DONE outputs are bit-identical to the
    fault-free run of the same schedule and every other terminal output
    is a clean prefix of it; zero steady-state compiles on every engine
    incarnation; cache allocations bounded by 1/warmup + 1/dispatch
    failure + 1/rebuild; and the storm actually fired."""
    from pytorch_distributed_tpu.serving.workload import (
        tick_bursts,
        tiered_stream,
    )

    cfg = _cfg()
    params = _params(cfg)
    n_req = 24
    tier = dict(
        prompt_len=(3, 16), max_new=(1, 8),
        sampling_cycle=(
            dict(temperature=0.9, top_k=17),
            dict(temperature=1.1, top_p=0.9),
            dict(),
        ),
        p_deadline=0.33, deadline_range=(0.3, 1.5),
    )
    reqs = tiered_stream(1000, vocab_size=cfg.vocab_size, tiers={
        "interactive": dict(n=n_req // 4, key_seed=1000, **tier),
        "standard": dict(n=n_req // 2, key_seed=1001, **tier),
        "batch": dict(n=n_req // 4, key_seed=1002, **tier),
    })
    bursts = tick_bursts(np.random.default_rng(0), 2)

    def make_engine(clock):
        # dispatch_retries=None: the storm never gives up; max_ticks
        # bounds a pathological schedule instead.
        return _engine(
            cfg, slots=4, max_len=32, buckets=BucketSpec((8, 16)),
            request_retries=6, dispatch_retries=None,
            retry_backoff_s=0.01, clock=clock, sleep=clock.sleep,
        )

    # Fault-free reference leg: its clock never advances, so no deadline
    # fires and everything finishes DONE.
    ref_clock = VirtualClock()
    ref = make_engine(ref_clock)
    ref.warmup(params)
    ref_results, _, (ref_warm,) = _storm_drive(ref, params, reqs, bursts)
    assert all(r.state == DONE for r in ref_results.values())
    assert ref.compile_count() == ref_warm

    clock = VirtualClock()
    injector = FaultInjector(
        seed=1, p_dispatch_error=0.08, p_drop_result=0.08, p_nan_row=0.3,
        p_slow_tick=0.25, slow_tick_s=1.0, clock=clock,
    )
    eng = make_engine(clock)
    injector.install(eng)
    eng.warmup(params)
    results, engines, warm = _storm_drive(
        eng, params, reqs, bursts, injector=injector,
        abort_rng=np.random.default_rng(7), p_abort=0.2, loss_tick=20,
        make_engine=lambda: make_engine(clock),
    )

    assert set(results) == set(range(n_req)), "lost or phantom rids"
    by_state: dict[str, int] = {}
    for rid, res in results.items():
        by_state[res.state] = by_state.get(res.state, 0) + 1
        want = np.asarray(ref_results[rid].tokens)
        got = np.asarray(res.tokens)
        if res.state == DONE:
            np.testing.assert_array_equal(
                got, want, err_msg=f"rid {rid} DONE but diverged",
            )
        else:
            np.testing.assert_array_equal(
                got, want[: len(got)],
                err_msg=f"rid {rid} {res.state} is not a clean prefix",
            )
    assert len(engines) == 2, "the engine loss never fired"
    assert [e.compile_count() for e in engines] == warm
    n_failures = sum(e.counters["dispatch_failures"] for e in engines)
    n_allocs = sum(e.counters["cache_allocs"] for e in engines)
    assert n_allocs <= len(engines) + n_failures
    assert all(n > 0 for n in injector.counts.values()), injector.counts
    assert by_state.get(ABORTED) and by_state.get(EXPIRED), by_state
