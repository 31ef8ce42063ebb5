"""Router-tier battery: routing, shedding, health, failover, drain.

The serving tier's robustness headline is pinned here the way PR-6
pinned the engine's: every claim in docs/ROBUSTNESS.md §13 against the
deterministic chaos harness, host-side only — the router can never
recompile a program or perturb a pinned budget, so these tests are
free to storm it:

1. routing — least-loaded choice on the uniform ``engine.stats()``
   snapshot, page pressure as a first-class admission signal, and
   SLO-aware shedding (``RouterOverloaded`` + retry-after) instead of
   unbounded queueing.
2. failover — a replica killed mid-decode (scripted chaos, or its
   engine raising ``DispatchFailure``) hands every in-flight request to
   survivors as resume entries; DONE token streams are BIT-IDENTICAL
   to a fault-free run, zero rids lost or duplicated, zero
   steady-state compiles on survivors.
3. drain/restart — planned maintenance rides snapshot()/restore():
   drained requests continue bit-identically on the restarted replica.
4. brown-out — a slow replica (chaos slow_tick on a shared
   VirtualClock) turns DEGRADED and stops attracting new load, then
   recovers.
5. the log — a storm run is diagnosable from the router's JSONL event
   vocabulary alone.

The replica-storm matrix closes the file; the shared workload
generator (serving/workload.py) is pinned deterministic here because
every "same schedule" claim in the suite leans on it.
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
    RouterFault,
    RouterFaultInjector,
    VirtualClock,
)
from pytorch_distributed_tpu.serving.engine import (
    BatchedDecodeEngine,
    BucketSpec,
    DecodeEngine,
    PagedBatchedDecodeEngine,
)
from pytorch_distributed_tpu.serving.lifecycle import (
    DONE,
    RouterOverloaded,
)
from pytorch_distributed_tpu.serving.router import (
    DEGRADED,
    DOWN,
    DRAINED,
    HEALTHY,
    ReplicaRouter,
)
from pytorch_distributed_tpu.serving.workload import (
    exponential_arrivals,
    request_stream,
    tick_bursts,
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


def _make_engine_factory(cfg, clock, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 24)
    kw.setdefault("buckets", BucketSpec((8,)))
    kw.setdefault("retry_backoff_s", 0.0)

    def make_engine(rep_id):
        return BatchedDecodeEngine(
            cfg, clock=clock, sleep=clock.sleep, **kw
        )

    return make_engine


def _reqs(n=6, seed=11):
    rng = np.random.default_rng(seed)
    return request_stream(
        rng, n=n, vocab_size=97, prompt_len=(3, 8), max_new=(3, 6),
        key_seed=seed,
    )


def _reference_outputs(cfg, params, reqs, clock=None):
    """The fault-free reference: one engine, same requests — outputs
    depend only on (request, params), never on placement, which is the
    property every failover assertion leans on."""
    clock = clock or VirtualClock()
    eng = BatchedDecodeEngine(
        cfg, slots=2, max_len=24, buckets=BucketSpec((8,)),
        clock=clock, sleep=clock.sleep,
    )
    # No warmup: the reference pins tokens, not compile counts — lazy
    # compilation of just the shapes used is cheaper than the full
    # bucket x group warm matrix.
    rid_to_idx = {eng.submit(**req): i for i, req in enumerate(reqs)}
    while eng.has_work():
        eng.step(params)
    return {
        rid_to_idx[rid]: np.asarray(eng.pop_result(rid).tokens)
        for rid in list(eng.results)
    }


# -- the shared workload generator -----------------------------------------


def test_workload_generator_deterministic():
    """One seed -> one schedule, bitwise: prompts, budgets, sampling
    configs, folded keys, deadlines, arrivals, bursts. Every 'same
    schedule as the clean leg' claim in the suite rests on this."""
    def draw():
        rng = np.random.default_rng(5)
        reqs = request_stream(
            rng, n=12, vocab_size=97, prompt_len=(3, 9),
            max_new=(1, 7), key_seed=3, p_deadline=0.4,
        )
        arr = exponential_arrivals(rng, 12, 0.25)
        bursts = tick_bursts(rng, 2, length=31)
        return reqs, arr, bursts

    a_reqs, a_arr, a_bursts = draw()
    b_reqs, b_arr, b_bursts = draw()
    assert np.array_equal(a_arr, b_arr) and a_bursts == b_bursts
    assert a_arr[0] == 0.0 and np.all(np.diff(a_arr) >= 0)
    for ra, rb in zip(a_reqs, b_reqs):
        assert sorted(ra) == sorted(rb)
        assert np.array_equal(ra["prompt"], rb["prompt"])
        assert ra["max_new_tokens"] == rb["max_new_tokens"]
        if "key" in ra:
            assert np.array_equal(
                jax.random.key_data(ra["key"]),
                jax.random.key_data(rb["key"]),
            )
    # The cycle mixes greedy and sampled rows, and some deadlines fired.
    assert any("temperature" in r for r in a_reqs)
    assert any("temperature" not in r for r in a_reqs)
    assert any("timeout_s" in r for r in a_reqs)


def test_workload_shared_prefix():
    prefix = np.arange(10, dtype=np.int32)
    rng = np.random.default_rng(0)
    reqs = request_stream(
        rng, n=4, vocab_size=97, prompt_len=(2, 4), max_new=2,
        shared_prefix=prefix,
    )
    for r in reqs:
        assert np.array_equal(r["prompt"][:10], prefix)
        assert 12 <= len(r["prompt"]) <= 14


# -- the uniform stats() schema --------------------------------------------


def test_stats_schema_uniform_across_engines():
    """One schema for serial/batched/paged — the router's admission
    scoring must never need to know which engine backs a replica. Paged
    engines fill the page-pressure fields; the others carry None (same
    keys, no hasattr probing)."""
    cfg = _cfg()
    serial = DecodeEngine(cfg, max_len=24)
    dense = BatchedDecodeEngine(
        cfg, slots=2, max_len=24, buckets=BucketSpec((8,))
    )
    paged = PagedBatchedDecodeEngine(
        cfg, slots=2, max_len=32, page_size=8
    )
    keys = None
    for eng in (serial, dense, paged):
        st = eng.stats()
        assert keys is None or sorted(st) == keys
        keys = sorted(st)
        assert isinstance(st["counters"], dict)
        assert isinstance(st["timers"], dict)
    assert serial.stats()["slots"] is None
    assert dense.stats()["free_pages"] is None
    # The pool's raw counts ride the same schema: None where not paged.
    pool_counts = ("allocatable_pages", "prefix_queries", "prefix_hits",
                   "evictions")
    assert set(pool_counts) <= set(keys)
    for eng in (serial, dense):
        assert all(eng.stats()[k] is None for k in pool_counts)
    p = paged.stats()
    assert p["pool_pages"] == paged.pool_pages
    assert p["free_pages"] == paged.pool_pages - 1  # scratch page 0
    assert p["allocatable_pages"] == p["free_pages"]  # nothing cached yet
    assert (p["prefix_queries"], p["prefix_hits"], p["evictions"]) == (0, 0, 0)
    # Occupancy tracks the scheduler.
    params = _params(cfg)
    dense.submit(_prompt(4, 1), 3)
    dense.submit(_prompt(4, 2), 3)
    dense.submit(_prompt(4, 3), 3)
    st = dense.stats()
    assert st["queue_depth"] == 3 and st["active_rows"] == 0
    dense.step(params)
    st = dense.stats()
    assert st["active_rows"] == 2 and st["free_slots"] == 0
    assert st["queue_depth"] == 1
    # One tick: its spans counted once each, two requests out of the queue.
    timers = st["timers"]
    assert timers["engine.tick"]["count"] == 1
    assert timers["engine.dispatch.decode_step"]["count"] == 1
    assert timers["queue_wait"]["count"] == 2
    assert set(timers["engine.tick"]) == {"count", "total_s", "max_s"}


def test_serial_engine_counters():
    cfg = _cfg()
    params = _params(cfg)
    eng = DecodeEngine(cfg, max_len=24)
    eng.generate(params, _prompt(4, 1)[None], 3)
    c = eng.stats()["counters"]
    assert c["requests"] == 1 and c["done"] == 1 and c["failed"] == 0


# -- routing + admission ---------------------------------------------------


def test_routing_spreads_by_load():
    """Least-loaded routing on the stats() snapshot: four submissions
    into two idle 2-slot replicas land two per replica (ties break to
    the lower id, then load shifts the next pick)."""
    cfg = _cfg()
    clock = VirtualClock()
    router = ReplicaRouter(_make_engine_factory(cfg, clock), 2, clock=clock)
    params = _params(cfg)
    for req in _reqs(4):
        router.submit(**req)
    by_replica = {0: 0, 1: 0}
    for rep_id, _erid in router._assign.values():
        by_replica[rep_id] += 1
    assert by_replica == {0: 2, 1: 2}
    router.run(params)
    assert len(router.results) == 4


def test_page_pressure_excludes_starved_replica():
    """A paged replica with no free pages is not a routing candidate
    even though its queue is empty — prompt tokens with no pages behind
    them are just a deeper queue. The request lands on the replica WITH
    headroom."""
    cfg = _cfg()
    clock = VirtualClock()

    def make_engine(rep_id):
        return PagedBatchedDecodeEngine(
            cfg, slots=2, max_len=32, page_size=8,
            pool_pages=9, clock=clock, sleep=clock.sleep,
        )

    router = ReplicaRouter(make_engine, 2, clock=clock)
    params = _params(cfg)
    # Exhaust replica 0's pool directly through its allocator (host-side
    # test rig — simulates deep resident rows without burning ticks).
    r0 = router._replicas[0]
    taken = r0.engine.pool.alloc(r0.engine.pool.free_pages())
    assert r0.engine.pool.free_pages() == 0
    rid = router.submit(_prompt(4, 1), 2)
    assert router._assign[rid][0] == 1
    r0.engine.pool.release(taken)
    rid2 = router.submit(_prompt(4, 2), 2)
    assert router._assign[rid2][0] == 0  # headroom back -> lowest id wins


def test_reusable_pages_are_headroom_for_the_shed_gate():
    """A pool whose free list is empty but whose prefix cache holds retired
    prompts' chunks is NOT starved: the allocator evicts them on demand
    (``allocatable_pages``), so the router admits. Only when nothing is
    evictable either does it shed. (The gate read the free list alone and
    answered 429 on a pool full of reusable pages.)"""
    cfg = _cfg()
    clock = VirtualClock()

    def make_engine(rep_id):
        return PagedBatchedDecodeEngine(
            cfg, slots=2, max_len=32, page_size=8, prefill_chunk=8,
            pool_pages=9, clock=clock, sleep=clock.sleep,
        )

    router = ReplicaRouter(make_engine, 1, clock=clock)
    params = _params(cfg)
    # Two retired prompts of two whole chunks each leave four cached pages.
    for seed in (1, 2):
        router.submit(_prompt(17, seed), 2)
    router.run(params)
    pool = router._replicas[0].engine.pool
    taken = pool.alloc(pool.free_pages())  # the free list, emptied
    st = router._replicas[0].engine.stats()
    assert st["free_pages"] == 0 and st["allocatable_pages"] >= 4
    rid = router.submit(_prompt(17, 3), 2)  # admitted: eviction delivers
    router.run(params)
    assert router.pop_result(rid).state == "DONE"
    assert router.counters.get("shed", 0) == 0
    # nothing free and nothing evictable: now the replica is starved
    pool.reset()
    taken = pool.alloc(pool.free_pages())
    assert router._replicas[0].engine.stats()["allocatable_pages"] == 0
    with pytest.raises(RouterOverloaded):
        router.submit(_prompt(4, 4), 2)
    pool.release(taken)


def test_shed_rejects_loudly_with_retry_after():
    """When every replica is past its admission threshold the router
    raises RouterOverloaded carrying a retry_after_s hint — reject
    loudly, never queue unboundedly — and recovers once the fleet
    drains."""
    cfg = _cfg()
    clock = VirtualClock()
    router = ReplicaRouter(
        _make_engine_factory(cfg, clock), 2, clock=clock,
        shed_queue_depth=2,
    )
    params = _params(cfg)
    reqs = _reqs(10, seed=3)
    accepted = []
    shed = 0
    for req in reqs:
        try:
            accepted.append(router.submit(**req))
        except RouterOverloaded as err:
            shed += 1
            assert err.retry_after_s is not None and err.retry_after_s > 0
    # No ticks run between submissions (admission happens in step), so
    # capacity is 2 queued per replica = 4 accepted, the rest shed.
    assert len(accepted) == 4 and shed == 6
    assert router.counters["shed"] == 6
    router.run(params)
    # Drained: the same submission is admitted again.
    rid = router.submit(**reqs[0])
    assert rid in router._assign


# -- failover ---------------------------------------------------------------


def test_replica_kill_failover_bit_identity():
    """THE robustness headline: kill one of two replicas mid-decode
    (chaos-scripted process loss). Every in-flight request fails over
    as a resume entry; DONE token streams are bit-identical to a
    fault-free run; zero lost or duplicated rids; zero steady-state
    compiles on the survivor."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs(8, seed=21)
    ref = _reference_outputs(cfg, params, reqs)

    clock = VirtualClock()
    router = ReplicaRouter(_make_engine_factory(cfg, clock), 2, clock=clock)
    router.warmup(params)
    RouterFaultInjector(
        faults=[RouterFault(tick=3, kind="replica_kill", row=0)],
    ).install(router)
    rids = {router.submit(**req): i for i, req in enumerate(reqs)}
    seen_terminal: set[int] = set()
    while router.has_work():
        done = router.step(params)
        # No rid is ever reported terminal twice.
        assert not (set(done) & seen_terminal)
        seen_terminal.update(done)
    assert router.replica_states() == {0: DOWN, 1: HEALTHY}
    assert router.counters["failovers"] == 1
    assert router.counters["failover_requests"] >= 1
    # Invariant: every submitted rid reached exactly one terminal state.
    assert set(router.results) == set(rids)
    for rid, idx in rids.items():
        res = router.pop_result(rid)
        assert res.state == DONE
        assert res.rid == rid
        assert np.array_equal(np.asarray(res.tokens), ref[idx]), (
            f"request {idx} diverged after failover"
        )
    # The survivor never compiled anything new: failover re-prefills
    # ride the warmed fault-resume bucket.
    assert router.steady_compiles()[1] == 0


def test_dispatch_failure_takes_replica_down():
    """A replica whose engine exhausts dispatch_retries (DispatchFailure
    from step) is replica death at the router tier: survivors adopt the
    work and every request still finishes DONE with reference tokens."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs(6, seed=33)
    ref = _reference_outputs(cfg, params, reqs)

    clock = VirtualClock()
    factory = _make_engine_factory(cfg, clock, dispatch_retries=0)
    router = ReplicaRouter(factory, 2, clock=clock)
    router.warmup(params)
    # Three consecutive dispatch errors on replica 0's engine: with
    # dispatch_retries=0 the FIRST failure raises DispatchFailure.
    inj = FaultInjector(
        faults=[Fault(tick=2, kind="dispatch_error")], clock=clock
    )
    inj.install(router._replicas[0].engine)
    rids = {router.submit(**req): i for i, req in enumerate(reqs)}
    router.run(params)
    assert router.replica_states()[0] == DOWN
    assert "dispatch failure" in router._replicas[0].down_reason
    assert set(router.results) == set(rids)
    for rid, idx in rids.items():
        res = router.pop_result(rid)
        assert res.state == DONE
        assert np.array_equal(np.asarray(res.tokens), ref[idx])
    assert router.steady_compiles()[1] == 0


def test_total_fleet_loss_parks_and_recovers():
    """Killing EVERY replica parks in-flight work as orphans (no data
    loss) and sheds new submissions; one restart re-adopts the orphans
    and the stream completes bit-identically."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs(4, seed=44)
    ref = _reference_outputs(cfg, params, reqs)

    clock = VirtualClock()
    router = ReplicaRouter(_make_engine_factory(cfg, clock), 2, clock=clock)
    router.warmup(params)
    rids = {router.submit(**req): i for i, req in enumerate(reqs)}
    router.step(params)
    router.kill(0)
    router.kill(1)
    assert router.replica_states() == {0: DOWN, 1: DOWN}
    assert router.stats()["orphans"] > 0
    with pytest.raises(RouterOverloaded):
        router.submit(_prompt(4, 9), 2)
    router.restart(1, params)
    router.run(params)
    assert set(router.results) == set(rids)
    for rid, idx in rids.items():
        res = router.pop_result(rid)
        assert res.state == DONE
        assert np.array_equal(np.asarray(res.tokens), ref[idx])


# -- drain / restart -------------------------------------------------------


def test_drain_restart_rides_snapshot_restore():
    """Planned drain: the replica's in-flight requests pause as a held
    snapshot, restart restores them, and they finish bit-identically —
    zero lost, zero duplicated rids, no re-route needed."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs(6, seed=55)
    ref = _reference_outputs(cfg, params, reqs)

    clock = VirtualClock()
    router = ReplicaRouter(_make_engine_factory(cfg, clock), 2, clock=clock)
    router.warmup(params)
    rids = {router.submit(**req): i for i, req in enumerate(reqs)}
    router.step(params)
    parked = router.drain(0)
    assert parked > 0
    assert router.replica_states()[0] == DRAINED
    # A drained replica takes no new work.
    rid_extra = router.submit(_prompt(5, 71), 3)
    assert router._assign[rid_extra][0] == 1
    router.step(params)
    router.restart(0, params)
    assert router.replica_states()[0] == HEALTHY
    router.run(params)
    assert set(rids) <= set(router.results)
    for rid, idx in rids.items():
        res = router.pop_result(rid)
        assert res.state == DONE and res.rid == rid
        assert np.array_equal(np.asarray(res.tokens), ref[idx])
    assert router.counters["drains"] == 1


def test_kill_after_drain_neither_loses_nor_duplicates():
    """A DRAINED replica dying before its restart: the held snapshot is
    written off, the still-live host state redistributes — every rid
    still reaches exactly one terminal result (the double-delivery edge
    this pins: drain already delivered the replica's finished results,
    kill must not deliver them again)."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs(6, seed=91)
    ref = _reference_outputs(cfg, params, reqs)
    clock = VirtualClock()
    router = ReplicaRouter(_make_engine_factory(cfg, clock), 2, clock=clock)
    router.warmup(params)
    rids = {router.submit(**req): i for i, req in enumerate(reqs)}
    router.step(params)
    # Park one UNdelivered result inside replica 0's engine (abort at
    # the ENGINE level — terminal result created outside a router tick,
    # exactly the state a DispatchFailure leaves behind).
    aborted_rid, aborted_erid = next(
        (rid, erid) for rid, (rep, erid) in router._assign.items()
        if rep == 0
    )
    router._replicas[0].engine.abort(aborted_erid)
    router.step(params)
    router.drain(0)
    assert router.results[aborted_rid].state == "ABORTED"
    router.kill(0, reason="died while drained")
    router.run(params)
    assert set(router.results) == set(rids)
    for rid, idx in rids.items():
        res = router.pop_result(rid)
        assert res.rid == rid
        if rid == aborted_rid:
            continue
        assert res.state == DONE
        assert np.array_equal(np.asarray(res.tokens), ref[idx])


def test_abort_on_drained_replica_not_resurrected():
    """Aborting a request parked in a drain snapshot must scrub it from
    the held snapshot too — otherwise restart resurrects (and re-runs)
    a request the client cancelled and its re-delivery crashes the
    router's rid bookkeeping."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs(5, seed=96)
    clock = VirtualClock()
    router = ReplicaRouter(_make_engine_factory(cfg, clock), 2, clock=clock)
    router.warmup(params)
    rids = {router.submit(**req): i for i, req in enumerate(reqs)}
    router.step(params)
    router.drain(0)
    on_drained = [
        rid for rid, (rep, _e) in router._assign.items() if rep == 0
    ]
    assert on_drained, "seed must place work on replica 0"
    victim = on_drained[0]
    assert router.abort(victim) is True
    assert router.results[victim].state == "ABORTED"
    router.restart(0, params)
    router.run(params)
    assert set(router.results) == set(rids)  # one terminal each, no crash
    for rid in rids:
        res = router.pop_result(rid)
        assert res.state == ("ABORTED" if rid == victim else DONE)


def test_drain_migrate_hands_work_to_survivors():
    cfg = _cfg()
    params = _params(cfg)
    reqs = _reqs(6, seed=66)
    ref = _reference_outputs(cfg, params, reqs)
    clock = VirtualClock()
    router = ReplicaRouter(_make_engine_factory(cfg, clock), 2, clock=clock)
    router.warmup(params)
    rids = {router.submit(**req): i for i, req in enumerate(reqs)}
    router.step(params)
    router.drain(0, migrate=True)
    assert router.replica_states()[0] == DOWN
    router.run(params)
    assert set(router.results) == set(rids)
    for rid, idx in rids.items():
        assert np.array_equal(
            np.asarray(router.pop_result(rid).tokens), ref[idx]
        )


# -- brown-out -------------------------------------------------------------


def test_slow_replica_degrades_and_recovers():
    """Brown-out: chaos slow_tick on replica 0 (shared VirtualClock)
    drives its step-latency EMA over the threshold -> DEGRADED; new
    load prefers the healthy replica; once the stalls stop the EMA
    decays and the replica recovers HEALTHY."""
    cfg = _cfg()
    params = _params(cfg)
    clock = VirtualClock()
    router = ReplicaRouter(
        _make_engine_factory(cfg, clock), 2, clock=clock,
        shed_queue_depth=64,
    )
    inj = FaultInjector(p_slow_tick=1.0, slow_tick_s=1.0, seed=0,
                        clock=clock)
    inj.install(router._replicas[0].engine)
    # Give BOTH replicas work so both tick. Two ticks: the first
    # establishes the peer EMA baseline (no replica is judged without
    # one), the second trips the slow replica over it.
    for req in _reqs(4, seed=77):
        router.submit(**req)
    router.step(params)
    router.step(params)
    assert router.replica_states()[0] == DEGRADED
    assert router.replica_states()[1] == HEALTHY
    # New submissions avoid the degraded replica entirely while the
    # healthy one has any capacity.
    fresh = [router.submit(**r) for r in _reqs(3, seed=78)]
    assert all(router._assign[rid][0] == 1 for rid in fresh)
    # Stalls stop; long-running work on replica 0 decays its EMA back
    # under the threshold and it recovers.
    router._replicas[0].engine.set_fault_injector(None)
    deep = request_stream(
        np.random.default_rng(9), n=2, vocab_size=97,
        prompt_len=(3, 4), max_new=12, key_seed=9,
    )
    # Route directly-ish: healthy replica is loaded, so these land on 0
    # only after 1 fills; just run the router until idle — recovery
    # happens as long as replica 0 keeps ticking.
    for r in deep:
        router.submit(**r)
    router.run(params)
    assert router.replica_states()[0] == HEALTHY
    assert router.counters["shed"] == 0  # deprioritized, never shed


# -- the router log --------------------------------------------------------


def test_router_log_vocabulary():
    """A storm incident is diagnosable from the JSONL event log alone:
    route/shed/replica_down/failover/drain/replica_up events carry rid
    + replica ids (docs/ROBUSTNESS.md §13 schema)."""
    cfg = _cfg()
    params = _params(cfg)
    clock = VirtualClock()
    router = ReplicaRouter(
        _make_engine_factory(cfg, clock), 2, clock=clock,
        shed_queue_depth=1,
    )
    router.warmup(params)
    events: list[str] = []
    handler = logging.Handler()
    handler.emit = lambda r: events.append(r.getMessage())
    lg = logging.getLogger("pdtpu.serving")
    lg.addHandler(handler)
    old_level = lg.level
    lg.setLevel(logging.DEBUG)
    try:
        reqs = _reqs(8, seed=88)
        rids = []
        for req in reqs:
            try:
                rids.append(router.submit(**req))
            except RouterOverloaded:
                pass
        router.step(params)
        router.kill(0, reason="test storm")
        router.step(params)
        router.restart(0, params)
        router.drain(0)
        router.restart(0, params)
        router.run(params)
    finally:
        lg.removeHandler(handler)
        lg.setLevel(old_level)
    assert any(
        m.startswith("event=route") and f"rid={rids[0]}" in m
        and "replica=" in m for m in events
    )
    assert any(m.startswith("event=shed") for m in events)
    assert any(
        m.startswith("event=replica_down") and "replica=0" in m
        and "reason=test" in m for m in events
    )
    assert any(
        m.startswith("event=failover") and "from_replica=0" in m
        and "to_replica=1" in m for m in events
    )
    assert any(m.startswith("event=drain") for m in events)
    assert any(
        m.startswith("event=replica_up") and "replica=0" in m
        for m in events
    )


# -- the replica storm matrix ----------------------------------------------


def _storm_drive(router, params, reqs, bursts, *, restart_after,
                 max_ticks=3000):
    """Tick-driven closed loop: seeded arrival bursts; a shed arrival
    (``RouterOverloaded``) re-offers on a later tick, FIFO preserved;
    a DOWN replica restarts ``restart_after`` ticks after it was seen
    down. Returns ({request index: result}, sheds caught)."""
    rids: dict[int, int] = {}
    next_req = tick = caught = 0
    restart_due: dict[int, int] = {}
    while (next_req < len(reqs) or router.has_work()) and tick < max_ticks:
        tick += 1
        for rep_id, due in list(restart_due.items()):
            if tick >= due:
                del restart_due[rep_id]
                router.restart(rep_id, params)
        n_new = min(bursts[tick % len(bursts)], len(reqs) - next_req)
        for _ in range(n_new):
            try:
                rids[router.submit(**reqs[next_req])] = next_req
                next_req += 1
            except RouterOverloaded as err:
                assert err.retry_after_s is not None
                caught += 1
                break
        if router.has_work():
            router.step(params)
        for rep_id, state in router.replica_states().items():
            if state == DOWN and rep_id not in restart_due:
                restart_due[rep_id] = tick + restart_after
    assert tick < max_ticks, "storm did not drain"
    assert next_req == len(reqs), "a shed request was never re-admitted"
    assert set(router.results) == set(rids), "lost or phantom rids"
    return {idx: router.pop_result(rid) for rid, idx in rids.items()}, caught


@pytest.mark.parametrize("fleet", ["dense3", "paged2_pinned"])
def test_router_replica_storm_matrix(fleet):
    """The full storm: seeded kills + restarts + bursty arrivals.
    Invariants: every rid reaches exactly one terminal state, a shed
    arrival is re-admitted and counted once per rejection, DONE outputs
    bit-identical to the fault-free reference, and the storm actually
    fired.

    - ``dense3``: 3 dense replicas on a ``VirtualClock``, one of them
      also taking engine-level dispatch faults the ENGINE recovers (no
      replica death) — the router tier composes with the engine tier's
      own resilience. Reference: one fault-free engine.
    - ``paged2_pinned``: 2 paged replicas, each pinned to its own device
      and stepped on concurrent host threads (``parallel_step``) on the
      wall clock, page pressure part of admission; two scripted kills
      plus Bernoulli ones. Reference: the same fleet driven clean, which
      must finish everything DONE with zero steady compiles.
    """
    cfg = _cfg()
    params = _params(cfg)
    bursts = tick_bursts(np.random.default_rng(123), 2)
    if fleet == "dense3":
        reqs = _reqs(48, seed=5)
        ref = _reference_outputs(cfg, params, reqs)
        clock = VirtualClock()
        router = ReplicaRouter(
            _make_engine_factory(cfg, clock, slots=2), 3, clock=clock,
            shed_queue_depth=16,
        )
        router.warmup(params)
        FaultInjector(
            seed=10, p_dispatch_error=0.05, clock=clock
        ).install(router._replicas[1].engine)
        storm = RouterFaultInjector(
            faults=[RouterFault(tick=4, kind="replica_kill")],
            seed=9, p_replica_kill=0.02,
        ).install(router)
        restart_after = 10
    else:
        reqs = _reqs(12, seed=5)

        def make_fleet():
            def make_engine(rep_id):
                return PagedBatchedDecodeEngine(
                    cfg, slots=2, max_len=32, page_size=8,
                    device=jax.devices()[rep_id],
                    request_retries=8, retry_backoff_s=0.0,
                )

            fleet = ReplicaRouter(
                make_engine, 2, parallel_step=True, shed_queue_depth=1,
            )
            fleet.warmup(params)
            return fleet

        clean = make_fleet()
        clean_out, _ = _storm_drive(
            clean, params, reqs, bursts, restart_after=15
        )
        assert all(r.state == DONE for r in clean_out.values())
        assert not any(clean.steady_compiles().values())
        ref = {i: np.asarray(r.tokens) for i, r in clean_out.items()}
        router = make_fleet()
        storm = RouterFaultInjector(
            faults=[
                RouterFault(tick=6, kind="replica_kill"),
                RouterFault(tick=18, kind="replica_kill"),
            ],
            seed=31, p_replica_kill=0.03,
        ).install(router)
        restart_after = 15

    out, caught = _storm_drive(
        router, params, reqs, bursts, restart_after=restart_after
    )
    assert router.counters["shed"] == caught
    if fleet == "paged2_pinned":
        assert caught >= 1, "no arrival was shed: retry path not driven"
    assert storm.counts["replica_kill"] >= 1
    assert router.counters["failovers"] >= 1
    assert router.counters["restarts"] >= 1
    for idx, res in out.items():
        assert res.state == DONE, (idx, res.state, res.reason)
        assert np.array_equal(np.asarray(res.tokens), ref[idx]), (
            f"request {idx} diverged in the storm"
        )
