"""Workload-scenario battery (PR 13): SLO tiers, multi-turn sessions,
multi-tenant LoRA — the scheduling subsystem over the paged engine.

Everything the subsystem promises is host-side policy over the SAME
audit-pinned compiled programs, so these tests pin the policy AND the
non-interference:

1. SLO tiers (serving/scheduler.py) — interactive bypasses the queue
   head (deadline-first within the tier), batch admits only under pool
   headroom, preemption is lowest-priority-then-youngest (a batch row
   is preempted before an interactive row REGARDLESS of age), and an
   all-STANDARD stream schedules exactly like the pre-tier engine
   (FIFO regression pin).
2. Sessions (serving/session.py) — turn N resubmits the conversation
   so far and pays ~one chunk of prefill via the pinned prefix cache;
   turn outputs are bit-equal the same prompt served one-shot; pins
   survive LRU pressure that evicts ordinary cached chunks; the pin
   budget evicts the longest-idle session LOUDLY (transcript survives,
   next turn pays a cold prefill); pins break before allocation
   deadlocks; diverged resubmissions are rejected naming the first
   divergent position.
3. Multi-tenant LoRA (serving/adapters.py) — per-tenant rows in a
   mixed batch are BIT-EQUAL the same requests on an engine serving
   that tenant alone (plain in tier-1; TP + the family matrix slow),
   no-tenant rows are bit-equal the adapter-less engine, registration
   never recompiles a warmed engine, and the registry audit cases pin
   strict donation + collective budgets (TP all-reduce=2).
4. Guards — unknown priority class, diverged session history,
   unregistered tenant, rank-0 adapters: rejected loudly at the
   engine, through the router, and as HTTP 4xx.
5. Uniform stats schema (per-tier queue depths, session-pin page
   counts) and the router scoring regression: a session-heavy replica
   is deprioritized BEFORE it starts preempting for its pinned pages.

The router/HTTP-tier scenario tests (sticky routing, restart re-home,
pinned-page scoring, the wire surface) and the pricier engine-policy
batteries (queue bypass, admission-side preemption, turn-over-turn
one-shot equality) ride the push-only ``slow`` lane with the other
serving matrices — tier-1 keeps the pinned fast cases (preemption
ordering under page exhaustion, session pins vs LRU, per-tenant
bit-equality, every guard) inside the 870 s budget; the CI dryrun
smoke re-asserts the demoted invariants on every run.
"""

import logging

import jax
import numpy as np
import pytest

from pytorch_distributed_tpu.config import MeshConfig, ModelConfig
from pytorch_distributed_tpu.models import get_model
from pytorch_distributed_tpu.serving.adapters import AdapterRegistry
from pytorch_distributed_tpu.serving.engine import (
    BatchedDecodeEngine,
    BucketSpec,
    PagedBatchedDecodeEngine,
)
from pytorch_distributed_tpu.serving.router import ReplicaRouter
from pytorch_distributed_tpu.serving.scheduler import (
    check_priority,
    preemption_key,
    queue_key,
)
from pytorch_distributed_tpu.serving.workload import (
    session_stream,
    tiered_stream,
)

pytestmark = pytest.mark.full


def _cfg(family="gpt2", **kw):
    extra = {"n_kv_head": 2} if family == "llama" else {}
    extra.update(kw)
    return ModelConfig(
        family=family, vocab_size=97, n_ctx=128, n_embd=64, n_layer=2,
        n_head=4, dtype="float32", attn_pdrop=0.0, resid_pdrop=0.0,
        embd_pdrop=0.0, **extra,
    )


def _params(cfg, seed=0):
    return get_model(cfg).init(jax.random.key(seed), cfg)


def _prompt(tp, seed):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (tp,), 0, 97), np.int32
    )


def _paged(cfg, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_chunk", 4)
    return PagedBatchedDecodeEngine(cfg, **kw)


class _events:
    """Capture the structured lifecycle log for one scenario."""

    def __init__(self):
        self.lines: list[str] = []

    def __enter__(self):
        self._handler = logging.Handler()
        self._handler.emit = lambda r: self.lines.append(r.getMessage())
        self._lg = logging.getLogger("pdtpu.serving")
        self._old = self._lg.level
        self._lg.addHandler(self._handler)
        self._lg.setLevel(logging.DEBUG)
        return self

    def __exit__(self, *exc):
        self._lg.removeHandler(self._handler)
        self._lg.setLevel(self._old)

    def named(self, event):
        return [m for m in self.lines if m.startswith(f"event={event} ")]


# -- scheduler vocabulary ---------------------------------------------------

def test_priority_vocabulary_and_ordering_keys():
    """The tier vocabulary: unknown classes rejected loudly; an
    all-STANDARD key ordering is exactly FIFO-by-rid (the pre-tier
    schedule); interactive sorts ahead and deadline-first WITHIN the
    tier; the preemption key picks lowest-priority-then-youngest."""
    assert [check_priority(p) for p in
            ("interactive", "standard", "batch")] == [0, 1, 2]
    with pytest.raises(ValueError, match="unknown priority class 'now'"):
        check_priority("now")
    std = check_priority("standard")
    assert sorted(
        [queue_key(std, None, r) for r in (3, 0, 2, 1)]
    ) == [queue_key(std, None, r) for r in (0, 1, 2, 3)]
    # Interactive: ahead of standard, earliest deadline first, and a
    # deadline NEVER reorders standard/batch (FIFO determinism there).
    it = check_priority("interactive")
    assert queue_key(it, 9.0, 7) < queue_key(std, 1.0, 0)
    assert queue_key(it, 1.0, 7) < queue_key(it, 2.0, 3)
    assert queue_key(std, 1.0, 3) < queue_key(std, None, 4)  # rid order
    # Victim selection: max key = lowest tier first, youngest within.
    bt = check_priority("batch")
    assert preemption_key(bt, 0) > preemption_key(it, 99)
    assert preemption_key(std, 5) > preemption_key(std, 4)


def test_unknown_priority_rejected_at_engine_and_router():
    cfg = _cfg()
    eng = _paged(cfg)
    with pytest.raises(ValueError, match="unknown priority class"):
        eng.submit(_prompt(4, 1), 2, priority="urgent")

    router = ReplicaRouter(lambda rep_id: _paged(cfg), 1)
    with pytest.raises(ValueError, match="unknown priority class"):
        router.submit(_prompt(4, 1), 2, priority="urgent")


# -- tiered admission -------------------------------------------------------

def test_interactive_bypasses_queue_head_and_batch_waits():
    """One slot, a standard row active, then batch/standard/interactive
    queued in that order: the interactive arrival PREEMPTS the active
    standard row for the only slot, and the remaining admissions go
    preempted-standard -> queued-standard -> batch, NOT rid order —
    interactive bypasses the FIFO head and batch yields to both other
    tiers."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, slots=1, pool_pages=40)
    r_act = eng.submit(_prompt(4, 1), 4)
    eng.step(params)  # admit the standard row
    r_b = eng.submit(_prompt(4, 2), 2, priority="batch")
    r_s = eng.submit(_prompt(4, 3), 2)
    r_i = eng.submit(_prompt(4, 4), 2, priority="interactive")
    by_tier = eng.stats()["queue_depth_by_tier"]
    assert by_tier == {"interactive": 1, "standard": 1, "batch": 1}
    with _events() as ev:
        out = eng.run(params)
    assert all(out[r].state == "DONE" for r in (r_act, r_b, r_s, r_i))
    admits = [m for m in ev.named("admit")]
    order = [int(m.split("rid=")[1].split()[0]) for m in admits]
    # r_act reappears: the interactive arrival took its slot (admission
    # preemption) and it resumed right after, ahead of the queue.
    assert order == [r_i, r_act, r_s, r_b], order
    assert eng.counters["preempt_priority"] == 1


def test_interactive_deadline_first_within_tier():
    """Two queued interactive requests admit earliest-deadline-first,
    not submit order."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, slots=1, pool_pages=40)
    r_act = eng.submit(_prompt(4, 1), 4)
    eng.step(params)
    r_late = eng.submit(
        _prompt(4, 2), 2, priority="interactive", timeout_s=60.0
    )
    r_soon = eng.submit(
        _prompt(4, 3), 2, priority="interactive", timeout_s=30.0
    )
    with _events() as ev:
        out = eng.run(params)
    assert all(out[r].state == "DONE" for r in (r_act, r_late, r_soon))
    order = [int(m.split("rid=")[1].split()[0]) for m in ev.named("admit")]
    # (r_act trails: it was preempted for the first interactive admit.)
    assert order == [r_soon, r_late, r_act], order


def test_batch_admits_only_under_page_headroom():
    """The batch admission gate: while the pool lacks
    ``batch_admit_free_frac`` free pages, BATCH entries are skipped
    (without blocking later standard arrivals); they admit once
    retirements free the pool."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(
        cfg, slots=3, pool_pages=17, batch_admit_free_frac=0.8,
    )
    r_big = eng.submit(_prompt(24, 1), 8)
    for _ in range(6):  # drive the 6-chunk prefill: 6 pages held
        eng.step(params)
    assert eng.pool.allocatable_pages() < 0.8 * 16
    r_b = eng.submit(_prompt(4, 2), 6, priority="batch")
    r_s = eng.submit(_prompt(4, 3), 6)
    eng.step(params)
    assert r_s in eng.active_rids(), "standard blocked behind gated batch"
    assert r_b in eng.queued_rids()
    eng.step(params)
    assert r_b in eng.queued_rids(), "batch admitted into a gated pool"
    out = eng.run(params)
    assert all(out[r].state == "DONE" for r in (r_big, r_b, r_s))
    assert eng.counters["preemptions"] == 0
    # The gate reads ALLOCATABLE pages: with everything retired the
    # pool's pages idle in the prefix cache (not on the free list), yet
    # a fresh batch request must admit — retired prefixes are headroom.
    assert eng.pool.free_pages() < 0.8 * 16
    out = eng.run(
        params,
        [dict(prompt=_prompt(4, 4), max_new_tokens=2, priority="batch")],
    )
    assert all(r.state == "DONE" for r in out.values())


def test_all_standard_stream_keeps_fifo_schedule():
    """The regression pin: a stream that never names a priority admits
    in exact rid order (the pre-tier engine's FIFO) — tiers are opt-in,
    not a reordering of existing traffic."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, slots=1, pool_pages=40)
    rids = [eng.submit(_prompt(3 + i, i), 2) for i in range(4)]
    with _events() as ev:
        out = eng.run(params)
    assert all(out[r].state == "DONE" for r in rids)
    order = [int(m.split("rid=")[1].split()[0]) for m in ev.named("admit")]
    assert order == rids, order


# -- tiered preemption ------------------------------------------------------

def test_batch_preempted_before_interactive_regardless_of_age():
    """Page exhaustion mid-decode preempts the BATCH row even though the
    interactive row is younger (PR-8's preempt-youngest would have
    picked the interactive one); both still finish DONE token-equal
    their uncontended references. The batch row holds only its PREFILL
    pages here — decode-yield keeps it from growing while the
    interactive row lives — so it is the interactive row's own growth
    that exhausts the pool and claims them."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [
        dict(prompt=_prompt(14, 1), max_new_tokens=10, priority="batch"),
        dict(prompt=_prompt(15, 2), max_new_tokens=17,
             priority="interactive"),
    ]
    ref = {}
    for rid, req in enumerate(reqs):
        solo = _paged(cfg, page_size=8, prefill_chunk=8, pool_pages=40)
        ref[rid] = solo.run(params, [dict(req)])[0]
    # 5 usable pages: 2+2 prefill pages + the interactive row's 2
    # decode growths (pos 16 and 24) exceed them — growth 2 finds the
    # pool empty and must preempt, and the batch row (rid 0, the OLDER
    # request) must be the victim.
    eng = _paged(
        cfg, page_size=8, prefill_chunk=8, pool_pages=6,
        batch_admit_free_frac=0.0,  # isolate the preemption policy
    )
    with _events() as ev:
        out = eng.run(params, reqs)
    assert eng.counters["preemptions"] >= 1
    assert eng.counters["failed"] == 0
    victims = {
        m.split("rid=")[1].split()[0] for m in ev.named("preempt")
    }
    assert victims == {"0"}, (
        f"interactive row preempted (victims={victims})"
    )
    for rid in (0, 1):
        assert out[rid].state == "DONE"
        np.testing.assert_array_equal(
            out[rid].tokens, ref[rid].tokens,
            err_msg=f"request {rid} diverged across tiered preemption",
        )


def test_interactive_arrival_preempts_batch_for_its_slot():
    """Admission-side preemption: with every slot busy, an INTERACTIVE
    arrival takes the lowest-priority row's slot immediately (the
    ``preempt_priority`` counter + log event) instead of queueing
    behind it; the preempted batch row resumes and completes. Tiered
    admission and preemption add no compiles to a warmed engine."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, slots=2, pool_pages=40)
    n_warm = eng.warmup(params)
    r_b = eng.submit(_prompt(4, 1), 10, priority="batch")
    r_s = eng.submit(_prompt(4, 2), 10)
    eng.step(params)
    assert set(eng.active_rids()) == {r_b, r_s}
    r_i = eng.submit(_prompt(4, 3), 8, priority="interactive")
    eng.step(params)
    assert r_i in eng.active_rids()
    assert r_b not in eng.active_rids(), "batch row kept its slot"
    assert eng.counters["preempt_priority"] == 1
    out = eng.run(params)
    assert all(out[r].state == "DONE" for r in (r_b, r_s, r_i))
    # Standard never preempts standard: a standard arrival with all
    # slots busy waits its turn instead.
    r_s2 = eng.submit(_prompt(4, 4), 8)
    r_s3 = eng.submit(_prompt(4, 5), 8)
    r_s4 = eng.submit(_prompt(4, 6), 8)
    eng.step(params)
    r_s5 = eng.submit(_prompt(4, 7), 2)
    eng.step(params)
    assert r_s5 in eng.queued_rids()
    assert eng.counters["preempt_priority"] == 1
    out = eng.run(params)
    assert all(
        out[r].state == "DONE" for r in (r_s2, r_s3, r_s4, r_s5)
    )
    assert eng.compile_count() == n_warm


def test_standard_arrival_does_not_preempt_batch():
    """Only INTERACTIVE preempts at admission (the scheduler.py tier
    contract — STANDARD is exactly PR-8's behaviour): with every slot
    held by BATCH rows, a STANDARD arrival queues for a retirement
    instead of taking a batch row's slot."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, slots=2, pool_pages=40)
    r_b1 = eng.submit(_prompt(4, 1), 10, priority="batch")
    r_b2 = eng.submit(_prompt(4, 2), 10, priority="batch")
    eng.step(params)
    assert set(eng.active_rids()) == {r_b1, r_b2}
    r_s = eng.submit(_prompt(4, 3), 2)
    eng.step(params)
    assert r_s in eng.queued_rids(), "standard arrival preempted batch"
    assert eng.counters["preempt_priority"] == 0
    out = eng.run(params)
    assert all(out[r].state == "DONE" for r in (r_b1, r_b2, r_s))


# -- sessions ---------------------------------------------------------------

def _run_turn(eng, params, sid, prompt, max_new, **kw):
    rid = eng.submit(prompt, max_new, session=sid, **kw)
    out = eng.run(params)
    assert out[rid].state == "DONE", out[rid]
    return out[rid].tokens


def test_session_turns_hit_prefix_cache_and_match_one_shot():
    """Three greedy turns: every turn's full token sequence is
    BIT-EQUAL the same prompt served one-shot on a fresh engine (cached
    pages are sound), and the turn-N prefill hit rate clears the 0.9
    the scenarios bench pins (the only misses are the sub-chunk tails
    decode could not publish)."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, max_len=64, pool_pages=40)
    sid = eng.open_session()
    transcript = np.zeros((0,), np.int32)
    tails = [_prompt(40, 1), _prompt(4, 2), _prompt(4, 3)]
    for turn, tail in enumerate(tails):
        prompt = np.concatenate([transcript, tail])
        transcript = _run_turn(eng, params, sid, prompt, 4)
        oneshot = _paged(cfg, max_len=64, pool_pages=40)
        ref = oneshot.run(params, [dict(prompt=prompt, max_new_tokens=4)])
        np.testing.assert_array_equal(
            transcript, ref[0].tokens,
            err_msg=f"turn {turn + 1} diverged from the one-shot path",
        )
    assert eng._sessions.hit_rate() >= 0.9, eng._sessions.hit
    st = eng.stats()
    assert st["sessions"] == 1
    assert st["session_pinned_pages"] > 0
    eng.close_session(sid)
    assert eng.stats()["sessions"] == 0


def test_session_transcript_guards():
    """The loud diagnostics: non-extension, divergence (naming the
    first divergent position), unknown sid, interleaved turns, and
    sessions on a dense engine."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, pool_pages=40)
    sid = eng.open_session()
    t1 = _run_turn(eng, params, sid, _prompt(8, 1), 3)
    with pytest.raises(ValueError, match="must EXTEND"):
        eng.submit(t1[:4], 2, session=sid)
    bad = np.concatenate([t1, _prompt(2, 2)])
    bad[3] = (bad[3] + 1) % 97
    with pytest.raises(ValueError, match="diverges .* at position 3"):
        eng.submit(bad, 2, session=sid)
    with pytest.raises(ValueError, match="unknown session id 77"):
        eng.submit(np.concatenate([t1, _prompt(2, 3)]), 2, session=77)
    with pytest.raises(ValueError, match="unknown session id 77"):
        eng.close_session(77)
    # One outstanding turn per session.
    rid = eng.submit(np.concatenate([t1, _prompt(2, 4)]), 2, session=sid)
    with pytest.raises(ValueError, match="already has turn rid"):
        eng.submit(np.concatenate([t1, _prompt(3, 5)]), 2, session=sid)
    out = eng.run(params)
    assert out[rid].state == "DONE"
    # Sessions need the paged prefix cache: dense engines reject.
    dense = BatchedDecodeEngine(
        cfg, slots=2, max_len=32, buckets=BucketSpec((8,))
    )
    with pytest.raises(ValueError, match="PagedBatchedDecodeEngine"):
        dense.submit(_prompt(4, 6), 2, session=0)


def test_session_pins_survive_lru_pressure():
    """The retention contract: one-shot churn that cycles the LRU cache
    (its own cached chunks get evicted) does NOT evict a live session's
    pinned chunks — the next turn still pays ~one chunk of prefill."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, max_len=64, pool_pages=24)
    sid = eng.open_session()
    t1 = _run_turn(eng, params, sid, _prompt(40, 1), 4)
    pinned_before = eng.pool.pinned_pages()
    assert pinned_before > 0
    # Churn: distinct one-shot prompts big enough to force eviction of
    # every unpinned cached chunk (24-page pool, 11 pinned).
    for i in range(4):
        out = eng.run(
            params, [dict(prompt=_prompt(36, 50 + i), max_new_tokens=2)]
        )
        assert all(r.state == "DONE" for r in out.values())
    assert eng.pool.stats["evictions"] > 0, "churn never pressured LRU"
    assert eng.pool.pinned_pages() == pinned_before, "pins were evicted"
    # Turn 2 still rides the pinned pages: only the sub-chunk tail and
    # the new tokens miss.
    t2 = _run_turn(
        eng, params, sid, np.concatenate([t1, _prompt(4, 2)]), 3
    )
    assert eng._sessions.hit_rate() >= 0.9, eng._sessions.hit
    assert t2.shape[0] == t1.shape[0] + 4 + 3


def test_pin_budget_evicts_longest_idle_session_loudly():
    """Over the pin budget, the longest-idle session is evicted LOUDLY
    (``session_evict`` + counter): its pins release, its transcript
    survives, and its next turn still completes (cold prefill)."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(
        cfg, max_len=64, pool_pages=40, session_pin_budget_pages=12,
    )
    sid_a = eng.open_session()
    sid_b = eng.open_session()
    with _events() as ev:
        ta = _run_turn(eng, params, sid_a, _prompt(32, 1), 4)  # 8 pages
        tb = _run_turn(eng, params, sid_b, _prompt(32, 2), 4)  # over
    assert eng._sessions.evictions == 1
    evicted = ev.named("session_evict")
    assert evicted and f"session={sid_a}" in evicted[0], evicted
    # A's next turn: transcript intact, completes despite cold cache.
    ta2 = _run_turn(
        eng, params, sid_a, np.concatenate([ta, _prompt(4, 3)]), 3
    )
    assert ta2.shape[0] == ta.shape[0] + 7
    assert len(eng._sessions) == 2  # eviction is retention-only
    assert tb.shape[0] == 32 + 4


def test_shared_chunk_pins_are_refcounted():
    """Two sessions sharing a system-prompt prefix pin the SAME chunk:
    one closing (or being idle-evicted) must not strip the survivor's
    retention — the chunk returns to LRU only when the LAST holder
    unpins."""
    from pytorch_distributed_tpu.serving.block_pool import BlockPool

    pool = BlockPool(pool_pages=8, page_size=4, chunk_tokens=4)
    pids = pool.alloc(1)
    key = pool.register_chunk(
        np.arange(4, dtype=np.int32), 0, pids, prev_key=""
    )
    pool.release(pids)
    pool.pin([key])  # holder A
    pool.pin([key])  # holder B
    pool.unpin([key])  # A closes
    assert pool.pinned_pages() == 1, "B's pin was stripped with A's"
    assert pool._evictable() is None
    pool.unpin([key])  # B closes: chunk back to ordinary LRU
    assert pool.pinned_pages() == 0
    assert pool._evictable() == key
    pool.unpin([key])  # idempotent past zero


def test_pin_budget_partial_shed_clamps_to_own_pins():
    """The single-session overflow shed: when the pool-wide overage
    exceeds the finishing session's own pin count (the rest is held by
    an unevictable in-flight neighbour), the shed clamps to its own
    chain — every one of ITS pins releases — instead of slicing
    negatively, which kept most of them and silently left the budget
    exceeded."""
    from pytorch_distributed_tpu.serving.session import SessionTracker

    class _Pool:
        page_size = 4
        chunk_tokens = 8  # chunk_pages = 2

        def __init__(self):
            self.pinned = []

        def pin(self, keys):
            self.pinned.extend(keys)

        def unpin(self, keys):
            for k in keys:
                self.pinned.remove(k)

    pool = _Pool()
    tr = SessionTracker(pool, pin_budget_pages=2, clock=lambda: 0.0)
    sid_a = tr.open()
    sid_b = tr.open()
    # A holds 2 chunks and is mid-turn: unevictable.
    tr._sessions[sid_a].pinned_keys = ["a0", "a1"]
    pool.pin(["a0", "a1"])
    tr.begin_turn(sid_a, rid=7)
    # B retires 3 chunks: 5 chunks = 10 pages vs budget 2 — the
    # overage (4 chunks) exceeds B's own 3, so ALL of B's pins shed.
    tr.on_turn_done(
        sid_b, np.arange(24, dtype=np.int32), ["b0", "b1", "b2"]
    )
    assert tr._sessions[sid_b].pinned_keys == []
    assert pool.pinned == ["a0", "a1"]


def test_batch_never_breaks_session_pins():
    """The other side of the pins-vs-allocation contract: pinned pages
    are NOT the idle capacity batch is allowed to fill (the router
    scores them unavailable for the same reason), so a BATCH request
    whose prefill would need a live session's pins DEFERS instead of
    evicting them; closing the session releases the pages and the
    batch row completes."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(
        cfg, max_len=64, pool_pages=24, slots=1,
        batch_admit_free_frac=0.0,
    )
    sid = eng.open_session()
    _run_turn(eng, params, sid, _prompt(40, 1), 4)  # pins 10 pages
    # 56 tokens = 14 pages > the 13 the unpinned pool holds (the same
    # geometry a STANDARD request resolves by breaking the pins).
    rid = eng.submit(_prompt(56, 2), 2, priority="batch")
    for _ in range(6):
        eng.step(params)
    assert rid in eng.queued_rids(), "batch admitted through the pins"
    assert eng._sessions.evictions == 0, "batch broke a session pin"
    eng.close_session(sid)
    out = eng.run(params)
    assert out[rid].state == "DONE"


def test_session_pins_break_before_allocation_deadlocks():
    """Retention must never starve admission: a request whose prefill
    needs more pages than the unpinned pool holds breaks the IDLE
    session's pins (loud eviction) instead of raising
    PagePoolExhausted or preempting live rows."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, max_len=64, pool_pages=24, slots=1)
    sid = eng.open_session()
    _run_turn(eng, params, sid, _prompt(40, 1), 4)  # pins 10 pages
    # 56 tokens = 14 pages > the 13 the unpinned pool holds.
    out = eng.run(
        params, [dict(prompt=_prompt(56, 2), max_new_tokens=2)]
    )
    assert out[1].state == "DONE"
    assert eng._sessions.evictions == 1
    assert eng.counters["preemptions"] == 0


def test_queued_session_turns_not_stalled_by_unallocatable_head():
    """Anti-livelock pin: a queue head too large for the unpinned pool
    while every pinned session has a QUEUED turn (in-flight pins are
    unevictable) must not stall admission for good — with no live rows
    nothing retires, so the only release of the pins is the session
    turns sitting BEHIND the head. They go around it, retire, and the
    head then breaks the now-idle pins and completes."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(
        cfg, max_len=64, pool_pages=24, slots=2,
        session_pin_budget_pages=16,
    )
    sa, sb = eng.open_session(), eng.open_session()
    ta = _run_turn(eng, params, sa, _prompt(20, 1), 4)
    tb = _run_turn(eng, params, sb, _prompt(20, 2), 4)
    assert eng.pool.pinned_pages() >= 10
    big = eng.submit(_prompt(56, 3), 2)  # 14 pages > the unpinned 13
    ra = eng.submit(np.concatenate([ta, _prompt(4, 4)]), 2, session=sa)
    rb = eng.submit(np.concatenate([tb, _prompt(4, 5)]), 2, session=sb)
    for _ in range(200):
        if not eng.has_work():
            break
        eng.step(params)
    assert not eng.has_work(), "admission stalled behind the big head"
    for r in (big, ra, rb):
        assert eng.results[r].state == "DONE", eng.results[r]


def test_session_stream_generator_deterministic():
    ss1 = session_stream(
        np.random.default_rng(5), n_sessions=2, turns=3, vocab_size=97,
        open_len=(8, 12), turn_len=(2, 5), max_new=(2, 4),
    )
    ss2 = session_stream(
        np.random.default_rng(5), n_sessions=2, turns=3, vocab_size=97,
        open_len=(8, 12), turn_len=(2, 5), max_new=(2, 4),
    )
    assert len(ss1) == 2 and all(len(s) == 3 for s in ss1)
    for a, b in zip(sum(ss1, []), sum(ss2, [])):
        np.testing.assert_array_equal(a["tail"], b["tail"])
        assert a["max_new_tokens"] == b["max_new_tokens"]
        assert ("key" in a) == ("key" in b)


def test_tiered_stream_content_independent_of_other_tiers():
    """The comparability contract the p99 bench leans on: the
    interactive tier's requests are byte-identical whether or not the
    batch flood rides along."""
    tiers = {
        "interactive": dict(n=5, prompt_len=(3, 8), max_new=(2, 4)),
        "batch": dict(n=7, prompt_len=(8, 16), max_new=(4, 8)),
    }
    mixed = tiered_stream(11, vocab_size=97, tiers=tiers)
    solo = tiered_stream(
        11, vocab_size=97,
        tiers={"interactive": tiers["interactive"]},
    )
    mixed_i = [r for r in mixed if r["priority"] == "interactive"]
    assert len(mixed) == 12 and len(mixed_i) == len(solo) == 5
    for a, b in zip(mixed_i, solo):
        np.testing.assert_array_equal(a["prompt"], b["prompt"])
        assert a["max_new_tokens"] == b["max_new_tokens"]
    with pytest.raises(ValueError, match="unknown priority class"):
        tiered_stream(1, vocab_size=97, tiers={"vip": dict(n=1)})


# -- multi-tenant LoRA ------------------------------------------------------

def _registry(cfg, n=2, rank=4):
    # scale big enough that a random rank-4 delta flips greedy argmaxes
    # (the default 0.02-normal init is realistic but sub-threshold on a
    # 2-layer toy model — a delta that changes nothing would let a
    # disconnected adapter path pass every equality pin vacuously).
    reg = AdapterRegistry(cfg, rank=rank, max_tenants=4)
    for i in range(n):
        reg.register(
            f"tenant-{i}", key=jax.random.key(100 + i), scale=800.0
        )
    return reg


def test_tenant_rows_bit_equal_isolated_runs():
    """The tier-1 isolation pin: each tenant's rows in a mixed batch
    are bit-equal the same requests on an engine serving that tenant
    ALONE, and a no-tenant row is bit-equal the adapter-less base
    engine — N tenants on one engine never perturb each other."""
    cfg = _cfg()
    params = _params(cfg)
    reg = _registry(cfg)
    reqs = [
        dict(prompt=_prompt(6, 1), max_new_tokens=4, tenant="tenant-0"),
        dict(prompt=_prompt(6, 1), max_new_tokens=4, tenant="tenant-1"),
        dict(prompt=_prompt(6, 1), max_new_tokens=4),  # base model row
        dict(prompt=_prompt(9, 2), max_new_tokens=3, temperature=0.8,
             key=jax.random.key(7), top_k=11, tenant="tenant-0"),
    ]
    mixed = _paged(cfg, slots=4, adapters=reg)
    out = mixed.run(params, [dict(r) for r in reqs])
    assert all(r.state == "DONE" for r in out.values())
    # Adapters must do SOMETHING (a disconnected delta path would pass
    # every equality pin below vacuously): tenant rows diverge from the
    # base row on the same prompt.
    for rid in (0, 1):
        assert not np.array_equal(out[rid].tokens, out[2].tokens), (
            rid, out[rid].tokens,
        )
    # Fast tier verifies one tenant row and the base row against their
    # isolated references; the slow family matrix re-checks EVERY row
    # (both tenants + the sampled turn) per model family.
    iso = _paged(cfg, slots=4, adapters=reg)
    ref = iso.run(params, [dict(reqs[0])])
    np.testing.assert_array_equal(
        out[0].tokens, ref[0].tokens,
        err_msg="tenant row 0 perturbed by neighbours",
    )
    base = _paged(cfg, slots=4)
    ref = base.run(params, [dict(reqs[2])])
    np.testing.assert_array_equal(
        out[2].tokens, ref[0].tokens,
        err_msg="slot-0 row diverged from the adapter-less engine",
    )


def test_lora_guards():
    cfg = _cfg()
    with pytest.raises(ValueError, match="rank must be >= 1, got 0"):
        AdapterRegistry(cfg, rank=0)
    reg = _registry(cfg, n=1)
    with pytest.raises(ValueError, match="unregistered tenant 'ghost'"):
        reg.slot("ghost")
    with pytest.raises(ValueError, match="already registered"):
        reg.register("tenant-0", key=jax.random.key(1))
    with pytest.raises(ValueError, match="either explicit adapters"):
        reg.register("tenant-9")
    eng = _paged(cfg, adapters=reg)
    with pytest.raises(ValueError, match="unregistered tenant 'ghost'"):
        eng.submit(_prompt(4, 1), 2, tenant="ghost")
    bare = _paged(cfg)
    with pytest.raises(ValueError, match="no .* registry attached"):
        bare.submit(_prompt(4, 1), 2, tenant="tenant-0")
    other = ModelConfig(
        family="gpt2", vocab_size=97, n_ctx=128, n_embd=32, n_layer=2,
        n_head=2, dtype="float32", attn_pdrop=0.0, resid_pdrop=0.0,
        embd_pdrop=0.0,
    )
    with pytest.raises(ValueError, match="different ModelConfig"):
        PagedBatchedDecodeEngine(
            other, slots=2, max_len=32, page_size=4, adapters=reg,
        )
    with pytest.raises(NotImplementedError, match="MoE"):
        AdapterRegistry(_cfg(n_experts=2), rank=2)
    with pytest.raises(ValueError, match="shapes .* do not match"):
        reg.register(
            "tenant-bad",
            adapters={
                "q": {"a": np.zeros((2, 64, 3)), "b": np.zeros((2, 3, 4, 16))},
                "c_proj": {"a": np.zeros((2, 64, 4)),
                           "b": np.zeros((2, 4, 64))},
            },
        )
    router = ReplicaRouter(lambda rep_id: _paged(cfg, adapters=reg), 1)
    with pytest.raises(ValueError, match="unregistered tenant"):
        router.submit(_prompt(4, 1), 2, tenant="ghost")


def test_tenant_registration_zero_new_compiles():
    """Registering a tenant changes operand VALUES, never shapes: a
    warmed engine serves a brand-new tenant with zero new compiles."""
    cfg = _cfg()
    params = _params(cfg)
    reg = AdapterRegistry(cfg, rank=4, max_tenants=4)
    reg.register("early", key=jax.random.key(1))
    eng = _paged(cfg, adapters=reg)
    n_warm = eng.warmup(params)
    out = eng.run(params, [
        dict(prompt=_prompt(5, 1), max_new_tokens=3, tenant="early"),
    ])
    assert out[0].state == "DONE"
    reg.register("late", key=jax.random.key(2))
    out = eng.run(params, [
        dict(prompt=_prompt(5, 2), max_new_tokens=3, tenant="late"),
        dict(prompt=_prompt(5, 3), max_new_tokens=3, tenant="early"),
    ])
    assert all(r.state == "DONE" for r in out.values())
    assert eng.compile_count() == n_warm, (
        f"{eng.compile_count() - n_warm} compiles leaked on registration"
    )


def test_lora_registry_cases_pinned(eight_devices):
    """The audit registry carries the LoRA serving programs: strict
    donation of the page pool on both paged cases (NO_COLLECTIVES), and
    the TP case pins the Megatron all-reduce ceiling (2) — adapters may
    add einsums, never collectives."""
    from pytorch_distributed_tpu.analysis.budget import STABLE_MAX_COUNTS
    from pytorch_distributed_tpu.analysis.registry import registered_cases

    cases = registered_cases()
    for name in ("decode_paged_prefill_lora", "decode_paged_step_lora"):
        _, _, budget, kwargs = cases[name].build()
        assert budget.forbidden, name  # NO_COLLECTIVES
        assert kwargs["donation_strict"], name
    _, _, tbudget, tkwargs = cases["decode_batched_step_tp_lora"].build()
    assert tbudget.max_counts == STABLE_MAX_COUNTS["decode_batched_step_tp"]
    assert "all-reduce" in tbudget.required
    assert "all-gather" in tbudget.forbidden
    assert tkwargs["donation_strict"]


# -- stats schema + router scoring -----------------------------------------

def test_stats_schema_has_tier_and_session_fields():
    """The uniform snapshot grew per-tier queue depths and session-pin
    page counts on EVERY engine (None where the concept is absent), so
    the router can score any fleet."""
    from pytorch_distributed_tpu.serving.engine import DecodeEngine

    cfg = _cfg()
    serial = DecodeEngine(cfg, max_len=32, buckets=BucketSpec((8,)))
    dense = BatchedDecodeEngine(
        cfg, slots=2, max_len=32, buckets=BucketSpec((8,))
    )
    paged = _paged(cfg)
    snaps = [serial.stats(), dense.stats(), paged.stats()]
    keys = {frozenset(s) for s in snaps}
    assert len(keys) == 1, "stats schema diverged across engines"
    for s in snaps:
        assert set(s["queue_depth_by_tier"]) == {
            "interactive", "standard", "batch",
        }
    assert snaps[0]["session_pinned_pages"] is None
    assert snaps[1]["sessions"] is None
    assert snaps[2]["session_pinned_pages"] == 0
    assert snaps[2]["sessions"] == 0
    assert "session_evictions" in snaps[2]["counters"]


def test_router_counts_pinned_pages_as_unavailable():
    """The scoring regression pin: two otherwise-idle paged replicas,
    one holding a session's pinned pages — new traffic routes to the
    unpinned replica (pins are capacity the allocator cannot touch), so
    a session-heavy replica is deprioritized BEFORE it must preempt."""
    cfg = _cfg()
    params = _params(cfg)
    router = ReplicaRouter(
        lambda rep_id: _paged(cfg, max_len=64, pool_pages=40), 2
    )
    router.warmup(params)
    sid = router.open_session()
    rep_pinned, _ = router._sessions[sid]
    t1 = _prompt(40, 1)
    rid = router.submit(t1, 4, session=sid)
    router.run(params)
    assert router.pop_result(rid).state == "DONE"
    pinned_stats = router._replicas[rep_pinned].engine.stats()
    assert pinned_stats["session_pinned_pages"] > 0
    with _events() as ev:
        router.submit(_prompt(6, 2), 2)
    routes = ev.named("route")
    assert routes and f"replica={1 - rep_pinned}" in routes[0], (
        f"routed onto the session-pinned replica {rep_pinned}: {routes}"
    )


def test_session_turns_route_sticky_and_rehome_on_kill():
    """Session stickiness: every turn lands on the replica holding the
    pinned pages; killing that replica re-homes the session to the
    survivor (fresh engine sid, ``session_rehomes`` counter) and the
    next turn completes — the transcript-carrying resubmission makes
    the move lossless."""
    cfg = _cfg()
    params = _params(cfg)
    router = ReplicaRouter(
        lambda rep_id: _paged(cfg, max_len=64, pool_pages=40), 2
    )
    router.warmup(params)
    sid = router.open_session()
    rep0, _ = router._sessions[sid]
    rid = router.submit(_prompt(10, 1), 3, session=sid)
    router.run(params)
    t1 = router.pop_result(rid).tokens
    assert router._sessions[sid][0] == rep0
    router.kill(rep0, reason="scenario test")
    rid2 = router.submit(
        np.concatenate([t1, _prompt(3, 2)]), 3, session=sid
    )
    assert router.counters["session_rehomes"] == 1
    assert router._sessions[sid][0] != rep0
    router.run(params)
    assert router.pop_result(rid2).state == "DONE"
    router.close_session(sid)
    with pytest.raises(ValueError, match="unknown router session"):
        router.close_session(sid)


def test_session_survives_replica_restart():
    """restart() replaces the replica's engine, so engine sids recorded
    before the kill are stale; the router re-homes every session still
    homed there onto a FRESH engine session at restart — the next turn
    completes (transcript-carrying resubmission, one cold prefill)
    instead of colliding with a later-opened session or failing as
    unknown."""
    cfg = _cfg()
    params = _params(cfg)
    router = ReplicaRouter(
        lambda rep_id: _paged(cfg, max_len=64, pool_pages=40), 1
    )
    router.warmup(params)
    sid = router.open_session()
    rid = router.submit(_prompt(10, 1), 3, session=sid)
    router.run(params)
    t1 = router.pop_result(rid).tokens
    router.kill(0, reason="scenario test")
    router.restart(0, params)
    assert router.counters["session_rehomes"] == 1
    # A session opened AFTER the restart must not collide with the
    # re-homed session's fresh engine sid.
    sid2 = router.open_session()
    assert router._sessions[sid][1] != router._sessions[sid2][1]
    rid2 = router.submit(
        np.concatenate([t1, _prompt(3, 2)]), 3, session=sid
    )
    rid3 = router.submit(_prompt(5, 3), 2, session=sid2)
    router.run(params)
    assert router.pop_result(rid2).state == "DONE"
    assert router.pop_result(rid3).state == "DONE"


def test_session_turns_respect_shed_thresholds():
    """Sticky session turns cannot spill to another replica, but the
    SLO gate still applies: a turn submitted while the holder is past
    the router's shed thresholds raises RouterOverloaded (retry hint
    attached) instead of queueing unboundedly on an engine with no
    queue_limit while plain traffic is 429'd."""
    from pytorch_distributed_tpu.serving.lifecycle import RouterOverloaded

    cfg = _cfg()
    params = _params(cfg)
    router = ReplicaRouter(
        lambda rep_id: _paged(cfg, max_len=64, pool_pages=60), 1,
        shed_queue_depth=2,
    )
    router.warmup(params)
    sid = router.open_session()
    rid = router.submit(_prompt(8, 1), 2, session=sid)
    router.run(params)
    t1 = router.pop_result(rid).tokens
    rids = [  # queue to the shed threshold without stepping
        router.submit(_prompt(4, 10 + i), 2) for i in range(2)
    ]
    with pytest.raises(RouterOverloaded, match="past its admission"):
        router.submit(
            np.concatenate([t1, _prompt(2, 2)]), 2, session=sid
        )
    router.run(params)
    for r in rids:
        assert router.pop_result(r).state == "DONE"


# -- HTTP surface -----------------------------------------------------------

def test_http_scenario_surface():
    """The wire tier: session open/turn/close, priority + tenant kwargs
    through POST /v1/generate, and every guard as a 4xx with the
    engine's diagnostic intact (unknown priority, unregistered tenant,
    diverged session history, unknown sid)."""
    import asyncio
    import json

    from pytorch_distributed_tpu.serving.server import ServingServer
    from tests.test_server import _http

    cfg = _cfg()
    params = _params(cfg)
    reg = _registry(cfg, n=1)
    router = ReplicaRouter(
        lambda rep_id: _paged(cfg, pool_pages=40, adapters=reg), 1
    )
    router.warmup(params)
    server = ServingServer(router, params, default_max_new=3)

    async def scenario():
        host, port = await server.start()
        try:
            status, _, body = await _http(
                host, port, "POST", "/v1/session/open"
            )
            assert status == 200
            sid = json.loads(body)["session"]

            prompt = [3, 1, 4, 1, 5]
            status, _, body = await _http(
                host, port, "POST", "/v1/generate",
                {"prompt": prompt, "max_new_tokens": 3, "session": sid,
                 "priority": "interactive"},
            )
            assert status == 200
            turn1 = json.loads(body)
            assert turn1["state"] == "DONE"
            assert turn1["tokens"][: len(prompt)] == prompt

            # Tenant + priority on a plain request.
            status, _, body = await _http(
                host, port, "POST", "/v1/generate",
                {"prompt": prompt, "max_new_tokens": 2,
                 "tenant": "tenant-0", "priority": "batch"},
            )
            assert status == 200 and json.loads(body)["state"] == "DONE"

            # Guards: 400s carrying the engine diagnostics.
            for bad, needle in (
                ({"priority": "urgent"}, "unknown priority class"),
                ({"tenant": "ghost"}, "unregistered tenant"),
                ({"session": sid,
                  "prompt": [9] + turn1["tokens"][1:] + [1]},
                 "diverges"),
                ({"session": 10 ** 6}, "unknown router session id"),
                ({"session": "nope"}, "integer sid"),
                ({"priority": 3}, "priority must be"),
            ):
                req = {"prompt": prompt, "max_new_tokens": 2, **bad}
                status, _, body = await _http(
                    host, port, "POST", "/v1/generate", req
                )
                assert status == 400, (bad, status, body)
                assert needle in json.loads(body)["error"], (bad, body)

            status, _, body = await _http(
                host, port, "POST", "/v1/session/close", {"session": sid}
            )
            assert status == 200 and json.loads(body)["closed"]
            status, _, _ = await _http(
                host, port, "POST", "/v1/session/close", {"session": sid}
            )
            assert status == 404
        finally:
            await server.stop()

    asyncio.run(scenario())


# -- slow tier: the tenant/family/TP matrix --------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_tenant_bit_equality_matrix_plain(family):
    """Per-tenant isolation across families: mixed 2-tenant + base
    batch vs isolated runs, greedy and sampled rows."""
    cfg = _cfg(family)
    params = _params(cfg)
    reg = _registry(cfg)
    reqs = [
        dict(prompt=_prompt(6, 1), max_new_tokens=4, tenant="tenant-0"),
        dict(prompt=_prompt(7, 2), max_new_tokens=4, tenant="tenant-1",
             temperature=0.9, key=jax.random.key(3), top_p=0.9),
        dict(prompt=_prompt(5, 3), max_new_tokens=4),
    ]
    mixed = _paged(cfg, slots=3, adapters=reg)
    out = mixed.run(params, [dict(r) for r in reqs])
    for rid, req in enumerate(reqs):
        iso = _paged(cfg, slots=3, adapters=reg)
        ref = iso.run(params, [dict(req)])
        np.testing.assert_array_equal(
            out[rid].tokens, ref[0].tokens,
            err_msg=f"{family} row {rid}",
        )


@pytest.mark.slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_tenant_bit_equality_tp(eight_devices, family):
    """TP composition: the per-row delta joins the base partial before
    the existing Megatron psum, so a mixed-tenant TP batch is bit-equal
    per-tenant isolated TP runs — and the warmed TP engine holds the
    same compile count across registrations."""
    cfg = _cfg(family)
    params = _params(cfg)
    reg = _registry(cfg)
    mcfg = MeshConfig(tensor=2, strategy="no_shard")
    reqs = [
        dict(prompt=_prompt(6, 1), max_new_tokens=4, tenant="tenant-0"),
        dict(prompt=_prompt(7, 2), max_new_tokens=4, tenant="tenant-1"),
        dict(prompt=_prompt(5, 3), max_new_tokens=4),
    ]
    mixed = _paged(cfg, slots=3, adapters=reg, mesh_cfg=mcfg)
    out = mixed.run(params, [dict(r) for r in reqs])
    for rid, req in enumerate(reqs):
        iso = _paged(cfg, slots=3, adapters=reg, mesh_cfg=mcfg)
        ref = iso.run(params, [dict(req)])
        np.testing.assert_array_equal(
            out[rid].tokens, ref[0].tokens,
            err_msg=f"tp {family} row {rid}",
        )
    base = _paged(cfg, slots=3, mesh_cfg=mcfg)
    ref = base.run(params, [dict(reqs[2])])
    np.testing.assert_array_equal(
        out[2].tokens, ref[0].tokens,
        err_msg=f"tp {family} slot-0 row vs adapter-less TP engine",
    )


# -- tier-1 again: the seeded session stream -------------------------------


def test_session_stream_end_to_end_hit_rate():
    """The seeded multi-turn stream (workload.session_stream) driven
    round-robin across concurrent sessions: every turn DONE, aggregate
    turn-N hit rate >= 0.9, zero steady-state compiles."""
    cfg = _cfg()
    params = _params(cfg)
    eng = _paged(cfg, slots=2, max_len=128, pool_pages=80)
    n_warm = eng.warmup(params)
    sessions = session_stream(
        np.random.default_rng(17), n_sessions=3, turns=3, vocab_size=97,
        open_len=(40, 48), turn_len=(3, 6), max_new=(3, 5),
    )
    sids = [eng.open_session() for _ in sessions]
    transcripts = [np.zeros((0,), np.int32) for _ in sessions]
    for turn in range(3):
        for i, script in enumerate(sessions):
            t = script[turn]
            kw = {k: v for k, v in t.items()
                  if k not in ("tail", "max_new_tokens")}
            prompt = np.concatenate([transcripts[i], t["tail"]])
            transcripts[i] = _run_turn(
                eng, params, sids[i], prompt, t["max_new_tokens"], **kw
            )
    assert eng._sessions.hit_rate() >= 0.9, eng._sessions.hit
    assert eng.compile_count() == n_warm
