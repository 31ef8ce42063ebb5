"""Health-checked multi-replica router: the serving tier over N engines.

One ``BatchedDecodeEngine`` (or paged subclass) is a single failure
domain: when its device dies, everything in flight dies with it unless
the caller snapshots and rebuilds by hand. Millions of users hit a
SERVICE, and a service needs the layer above the engine — placement,
health, failover, and honest overload behaviour. ``ReplicaRouter`` is
that layer, and it is HOST-SIDE ONLY: replicas stay independent failure
domains running the exact compiled programs the audit registry pins
(MPMD-style independence, PAPERS.md #3 — one big mesh would make every
fault global), and nothing the router does can recompile a program,
perturb a neighbour row, or move a pinned collective budget.

The contract, per concern:

- **Routing + admission** (``submit``): each request goes to the
  least-loaded routable replica, scored on the uniform
  ``engine.stats()`` snapshot — queue depth AND page pressure (a paged
  replica without page headroom is not a candidate even if its queue is
  short; prompt tokens with no pages behind them are just a deeper
  queue). DEGRADED replicas rank strictly after HEALTHY ones, so a
  browned-out replica keeps draining what it has but stops attracting
  new load. Ties break by replica id: routing is a deterministic
  function of (request order, replica states), which is what makes
  storm runs replayable.
- **Load shedding**: when no replica is admissible the router raises
  ``lifecycle.RouterOverloaded`` (with a drain-time ``retry_after_s``)
  instead of queueing unboundedly — the SLO-aware choice: a bounded
  queue keeps p99 meaningful, and the client that retries after the
  hint lands in a drained router. The front door maps it to
  429 + Retry-After.
- **Failover** (replica death): a replica that dies mid-decode — its
  engine raising ``DispatchFailure`` from ``step``, or silent process
  loss (``kill``, chaos-injected via ``RouterFaultInjector``) — has
  every in-flight request converted to a PR-6 resume entry (clean
  tokens-so-far + pre-folded PRNG schedule, via the engine's own
  host-side ``snapshot``) and ADOPTED by survivors
  (``engine.adopt``). Continuation is BIT-IDENTICAL to an
  uninterrupted run because the entry + shared params fully determine
  the remaining tokens — which engine runs them is irrelevant. Zero
  lost rids, zero duplicated rids, zero new compiles on survivors
  (resume prefills ride warmed shapes). With NO survivor the entries
  park in the router and re-adopt when a replica comes back: total
  fleet loss degrades to queueing, never to data loss.
- **Drain / restart** (planned maintenance): ``drain`` captures the
  replica's host state as a snapshot (in-flight rows become resume
  entries; undelivered results are delivered, not cloned) and takes it
  out of rotation; ``restart`` rebuilds the engine, re-warms it, and
  ``restore``s the snapshot — the drained requests continue
  bit-identically on the restarted replica with zero lost or
  duplicated rids. ``drain(migrate=True)`` hands the work to survivors
  instead (the kill path without the fault).
- **Brown-out**: per-replica step latency rides an EMA on the router's
  clock; a replica whose EMA exceeds ``degrade_factor`` x the fleet
  median (plus the ``degrade_min_s`` floor) turns DEGRADED and stops
  attracting new load until it recovers — one slow replica inflates
  its own latencies, not the fleet p99. Chaos drives this
  deterministically: a per-replica ``FaultInjector`` slow_tick on a
  shared ``VirtualClock``.

Request ids: the router issues its own monotonically-increasing rids
and maps them onto per-engine rids (re-mapped on every adoption);
results are re-labelled so a client never sees engine-internal ids.
Every lifecycle transition logs through ``utils/logging.log_event``
with the router vocabulary (``route`` / ``shed`` / ``failover`` /
``drain`` / ``replica_down`` / ``replica_up`` / ``replica_degraded`` /
``replica_recovered``) carrying rid + replica id — docs/ROBUSTNESS.md
§13 documents the schema; a storm run is diagnosable from the JSONL
log alone.

Not thread-safe (one dispatcher per router — the asyncio front door in
serving/server.py serialises through a lock). Replicas must share ONE
params tree and, when deadlines or virtual-time chaos are in play, one
clock (pass the same ``clock`` to the router and every engine).
"""

from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable

import numpy as np

from pytorch_distributed_tpu.profiling.spans import Timers
from pytorch_distributed_tpu.serving.lifecycle import (
    ABORTED,
    DispatchFailure,
    EngineSnapshot,
    RequestResult,
    RouterOverloaded,
)
from pytorch_distributed_tpu.utils.logging import log_event

HEALTHY = "HEALTHY"
DEGRADED = "DEGRADED"
DRAINED = "DRAINED"
DOWN = "DOWN"
REPLICA_STATES = (HEALTHY, DEGRADED, DRAINED, DOWN)
_ROUTABLE = (HEALTHY, DEGRADED)


@dataclasses.dataclass
class _Replica:
    """One replica's router-side record: the engine, its health state,
    the engine-rid -> router-rid map, and the compile-count watermark
    the zero-steady-compile assertion is measured against."""

    rep_id: int
    engine: Any
    state: str = HEALTHY
    tick_ema_s: float | None = None  # None until the first measured tick
    rid_map: dict[int, int] = dataclasses.field(default_factory=dict)
    warm_count: int = 0
    held_snapshot: EngineSnapshot | None = None  # parked by drain()
    down_reason: str = ""


class ReplicaRouter:
    """See module docstring. ``make_engine(rep_id)`` builds one replica
    engine (called at construction and again on every ``restart`` — the
    factory IS the restart path, so it must return a fresh idle engine
    each call); ``n_replicas`` fixes the fleet size for the router's
    life. Health knobs:

    - ``shed_queue_depth``: a replica whose engine queue is this deep is
      not admissible (default: 2x its slot count).
    - ``shed_page_free``: a paged replica with fewer ALLOCATABLE pages
      (free, or cached and evictable: ``stats()["allocatable_pages"]``)
      is not admissible (default 1 — "has any headroom at all"; raise it
      to shed earlier under page pressure).
    - ``degrade_factor`` / ``degrade_min_s`` / ``ema_alpha``: brown-out
      detection — DEGRADED when the replica's step-latency EMA exceeds
      ``max(degrade_min_s, degrade_factor * fleet-median EMA)``;
      recovery is the same test passing again.
    - ``retry_after_s``: the shed hint when the drain estimate has no
      signal (fleet fully down); otherwise the estimate is derived from
      the median step EMA and the shallowest queue.
    - ``parallel_step``: step busy replicas concurrently (one host
      thread per replica) instead of round-robin. With per-replica
      device placement (``MeshConfig.device_ids`` / engine ``device=``)
      the replicas' XLA dispatches overlap on disjoint device slices
      (not measured on the chip; tests/test_router.py's
      ``paged2_pinned`` storm runs it). Engine ticks stay
      single-threaded per engine; all router bookkeeping (health,
      delivery, failover, handoffs) runs serially after the joins, so
      determinism contracts are untouched. Default False: virtual-clock
      tests and chaos schedules assume sequential stepping.
    """

    def __init__(
        self,
        make_engine: Callable[[int], Any],
        n_replicas: int,
        *,
        clock=None,
        shed_queue_depth: int | None = None,
        shed_page_free: int = 1,
        degrade_factor: float = 4.0,
        degrade_min_s: float = 0.05,
        ema_alpha: float = 0.3,
        retry_after_s: float = 1.0,
        parallel_step: bool = False,
    ) -> None:
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        self._make_engine = make_engine
        self._clock = clock or time.monotonic
        self._replicas = [
            _Replica(rep_id=i, engine=make_engine(i))
            for i in range(n_replicas)
        ]
        for r in self._replicas:
            self._log_role(r)
        self.shed_queue_depth = shed_queue_depth
        self.shed_page_free = int(shed_page_free)
        self.degrade_factor = float(degrade_factor)
        self.degrade_min_s = float(degrade_min_s)
        self.ema_alpha = float(ema_alpha)
        self.retry_after_s = float(retry_after_s)
        self.parallel_step = bool(parallel_step)
        self._next_rid = 0
        # router rid -> (rep_id, engine rid); the mirror of each
        # replica's rid_map. Entries leave on terminal delivery.
        self._assign: dict[int, tuple[int, int]] = {}
        # Entries with no live replica to run them: (router rid,
        # _Pending). Re-adopted at the next tick with a routable replica.
        self._orphans: list[tuple[int, Any]] = []
        # Session stickiness: router sid -> (rep_id, engine sid). Turns
        # of one session must land on the replica holding its pinned
        # prefix pages; on replica loss the session re-homes to a
        # survivor (fresh engine sid — the transcript-carrying
        # resubmission makes that lossless, at one cold prefill).
        self._sessions: dict[int, tuple[int, int]] = {}
        self._next_sid = 0
        self.results: dict[int, RequestResult] = {}
        self._ticks = 0
        self._injector = None  # serving/chaos.RouterFaultInjector
        # Spans of the router's tick and of the calls the front door
        # makes under its lock (profiling/spans.py); in ``stats()``.
        self.timers = Timers()
        self.counters: dict[str, int] = {
            "routed": 0, "shed": 0, "failovers": 0, "failover_requests": 0,
            "drains": 0, "restarts": 0, "orphaned": 0,
            "sessions_opened": 0, "session_rehomes": 0,
            "handoffs": 0,
        }

    # -- fleet management ---------------------------------------------------

    @staticmethod
    def _role(r: _Replica) -> str:
        """The replica's disaggregation role. Engines without the knob
        (dense engines, pre-disagg paged builds) are colocated."""
        return getattr(r.engine, "role", "colocated")

    def _log_role(self, r: _Replica) -> None:
        log_event(
            "role_assign", replica=r.rep_id, role=self._role(r),
            device_ids=(
                r.engine.device_ids()
                if hasattr(r.engine, "device_ids") else None
            ),
            t=round(self._clock(), 6),
        )

    def warmup(self, params) -> int:
        """Warm every replica's compile set and record the per-replica
        watermark ``steady_compiles`` is measured against. Returns the
        fleet-total compile count."""
        for r in self._replicas:
            r.engine.warmup(params)
            r.warm_count = r.engine.compile_count()
        return sum(r.engine.compile_count() for r in self._replicas)

    def steady_compiles(self) -> dict[int, int]:
        """Per-replica compiles since its warmup watermark — expected 0
        for every replica that was warmed and never rebuilt (failover
        re-prefills ride warmed shapes by construction)."""
        return {
            r.rep_id: r.engine.compile_count() - r.warm_count
            for r in self._replicas
        }

    def replica_states(self) -> dict[int, str]:
        return {r.rep_id: r.state for r in self._replicas}

    def live_replicas(self) -> list[int]:
        return [r.rep_id for r in self._replicas if r.state in _ROUTABLE]

    def set_fault_injector(self, injector) -> None:
        """Install a ``serving/chaos.RouterFaultInjector`` (or None):
        consulted once per ``step`` for replica_kill faults. Host-side
        only, like every other injection point."""
        self._injector = injector

    # -- admission ----------------------------------------------------------

    def _admissible(self, r: _Replica) -> tuple[float, ...] | None:
        """Admission + scoring in one read of the replica's uniform
        ``stats()``: None = not admissible (saturated queue or page
        starvation); otherwise the routing sort key — DEGRADED after
        HEALTHY, then least host load, then page pressure, then id.
        DECODE workers are never admissible: fresh prompts are prefill
        work and reach them only as kv handoffs (regression-pinned in
        tests/test_serving_disagg.py)."""
        if self._role(r) == "decode":
            return None
        st = r.engine.stats()
        limit = (
            self.shed_queue_depth
            if self.shed_queue_depth is not None
            else 2 * (st["slots"] or 1)
        )
        if st["queue_depth"] >= limit:
            return None
        page_pressure = 0.0
        if st["free_pages"] is not None:
            # what the allocator can DELIVER: the free list plus the
            # retired prompts' cached chunks it evicts on demand. The
            # free list alone empties on a busy pool that is full of
            # reusable pages, and shedding there refuses work the
            # replica has room for.
            if st["allocatable_pages"] < self.shed_page_free:
                return None
            # Session-pinned pages count as UNAVAILABLE capacity: they
            # are off the allocator's table until their session goes
            # idle, so a session-heavy replica must look loaded before
            # it starts preempting for its pinned residents
            # (regression-pinned in tests/test_serving_scenarios.py).
            # Speculative width (engine speculative_k) deliberately
            # does NOT enter this accounting: a speculating row's draft
            # window lives on its own already-counted private tail
            # pages (grown best-effort, never by preemption —
            # engine._grow_for_drafts), so pages_in_use is the truth
            # for spec and non-spec replicas alike; scoring a spec
            # replica as (k+1)x wider would starve-exclude the FASTER
            # replica.
            pinned = st.get("session_pinned_pages") or 0
            page_pressure = (
                st["pages_in_use"] + pinned
            ) / max(1, st["pool_pages"])
        load = st["queue_depth"] + st["active_rows"]
        return (
            1.0 if r.state == DEGRADED else 0.0,
            float(load),
            page_pressure,
            float(r.rep_id),
        )

    def _ranked_replicas(self) -> list[_Replica]:
        """Admissible replicas, best routing choice first."""
        scored = []
        for r in self._replicas:
            if r.state not in _ROUTABLE:
                continue
            key = self._admissible(r)
            if key is not None:
                scored.append((key, r))
        return [r for _, r in sorted(scored, key=lambda kr: kr[0])]

    def _retry_after(self) -> float:
        """Drain-time hint for a shed response: one slot's worth of
        decode at the fleet's median measured tick latency, floored at
        the configured default. Deliberately rough — its job is to
        spread retries out, not to promise capacity."""
        emas = sorted(
            r.tick_ema_s for r in self._replicas
            if r.state in _ROUTABLE and r.tick_ema_s is not None
        )
        if not emas:
            return self.retry_after_s
        med = emas[len(emas) // 2]
        depth = min(
            r.engine.stats()["queue_depth"] for r in self._replicas
            if r.state in _ROUTABLE
        )
        return max(self.retry_after_s, med * (depth + 1))

    def open_session(self) -> int:
        """Open a multi-turn session on the least-loaded routable
        replica (it must be paged — sessions ride the pinned prefix
        cache); returns the ROUTER session id ``submit(session=)``
        takes. The router owns the sid -> (replica, engine sid)
        stickiness map and re-homes the session to a survivor on
        replica loss."""
        best = self._least_loaded(colocated_only=True)
        if best is None:
            raise RouterOverloaded(
                "no live colocated replica to open a session on — "
                "sessions pin prefix pages where their turns both "
                "prefill AND decode, so prefill/decode workers cannot "
                f"host them (states {self.replica_states()})",
                retry_after_s=self._retry_after(),
            )
        if not hasattr(best.engine, "open_session"):
            raise ValueError(
                "sessions need paged replica engines "
                "(PagedBatchedDecodeEngine) — this fleet serves "
                f"{type(best.engine).__name__}"
            )
        esid = best.engine.open_session()
        sid = self._next_sid
        self._next_sid += 1
        self._sessions[sid] = (best.rep_id, esid)
        self.counters["sessions_opened"] += 1
        log_event(
            "session_route", session=sid, replica=best.rep_id,
            engine_session=esid, t=round(self._clock(), 6),
        )
        return sid

    def close_session(self, sid: int) -> None:
        """Close a router session; the replica's pins release. Unknown
        sids raise (loudly, like the engine's own close)."""
        loc = self._sessions.pop(sid, None)
        if loc is None:
            raise ValueError(
                f"unknown router session id {sid}: open_session() "
                "first (or it was already closed)"
            )
        rep_id, esid = loc
        r = self._replicas[rep_id]
        if r.state in _ROUTABLE:
            r.engine.close_session(esid)
        # A DOWN/DRAINED holder's tracker died (or will be rebuilt)
        # with its engine — nothing to release.

    def _session_target(self, sid: int) -> tuple[_Replica, int]:
        """The (replica, engine sid) a session turn must route to,
        re-homing onto a survivor when the sticky replica is not
        routable — a fresh engine session whose empty transcript any
        resubmitted conversation extends (one cold prefill, no data
        loss, counted as ``session_rehomes``)."""
        loc = self._sessions.get(sid)
        if loc is None:
            raise ValueError(
                f"unknown router session id {sid}: open_session() "
                "first (or it was closed)"
            )
        rep_id, esid = loc
        r = self._replicas[rep_id]
        if r.state in _ROUTABLE:
            return r, esid
        best = self._least_loaded(colocated_only=True)
        if best is None:
            raise RouterOverloaded(
                f"session {sid}'s replica {rep_id} is {r.state} and no "
                "survivor can re-home it",
                retry_after_s=self._retry_after(),
            )
        esid = best.engine.open_session()
        self._sessions[sid] = (best.rep_id, esid)
        self.counters["session_rehomes"] += 1
        log_event(
            "session_route", session=sid, replica=best.rep_id,
            engine_session=esid, rehomed_from=rep_id,
            t=round(self._clock(), 6),
        )
        return best, esid

    def submit(self, prompt, max_new_tokens: int, *,
               session: int | None = None, **kw) -> int:
        """Route one request (``engine.submit`` kwargs pass through —
        deadlines via ``timeout_s=``, SLO tiers via ``priority=`` and
        tenants via ``tenant=`` land on the replica engine). Returns
        the ROUTER rid its terminal ``RequestResult`` will carry in
        ``results`` / ``pop_result``. Raises ``RouterOverloaded`` (with
        ``retry_after_s``) when no replica is admissible.

        ``session=`` (a router sid from ``open_session``) routes STICKY
        to the replica holding the session's pinned pages instead of
        least-loaded — the pages ARE the locality."""
        from pytorch_distributed_tpu.serving.lifecycle import (
            AdmissionQueueFull,
        )

        with self.timers.span("router.submit"):
            r = erid = None
            if session is not None:
                r, esid = self._session_target(session)
                if self._admissible(r) is None:
                    # Stickiness cannot spill to another replica (the pages
                    # live here), but the SLO gate still applies: past the
                    # router's shed thresholds the holder sheds like a
                    # saturated fleet — without this, an engine with
                    # queue_limit=None would let session turns queue
                    # unboundedly while plain traffic is 429'd.
                    self.counters["shed"] += 1
                    hint = self._retry_after()
                    raise RouterOverloaded(
                        f"session {session}'s replica {r.rep_id} is past "
                        f"its admission threshold; retry after "
                        f"~{hint:.2f}s",
                        retry_after_s=hint,
                    )
                try:
                    erid = r.engine.submit(
                        prompt, max_new_tokens, session=esid, **kw
                    )
                except AdmissionQueueFull as err:
                    # Stickiness cannot spill to another replica (the pages
                    # live here): a saturated holder sheds like a saturated
                    # fleet.
                    self.counters["shed"] += 1
                    hint = self._retry_after()
                    raise RouterOverloaded(
                        f"session {session}'s replica {r.rep_id} is "
                        f"saturated ({err}); retry after ~{hint:.2f}s",
                        retry_after_s=hint,
                    ) from None
            else:
                for cand in self._ranked_replicas():
                    try:
                        erid = cand.engine.submit(prompt, max_new_tokens, **kw)
                        r = cand
                        break
                    except AdmissionQueueFull:
                        # The engine's own queue_limit can be tighter than
                        # the router's threshold — that replica is
                        # saturated, try the next; all-saturated sheds
                        # below like any other overload.
                        continue
            if r is None:
                self.counters["shed"] += 1
                hint = self._retry_after()
                log_event(
                    "shed", t=round(self._clock(), 6),
                    live=len(self.live_replicas()),
                    retry_after_s=round(hint, 4),
                )
                raise RouterOverloaded(
                    "every routable replica is past its admission threshold "
                    f"(states {self.replica_states()}); retry after "
                    f"~{hint:.2f}s",
                    retry_after_s=hint,
                )
            rid = self._next_rid
            self._next_rid += 1
            r.rid_map[erid] = rid
            self._assign[rid] = (r.rep_id, erid)
            self.counters["routed"] += 1
            log_event(
                "route", rid=rid, replica=r.rep_id, engine_rid=erid,
                state=r.state, t=round(self._clock(), 6),
            )
            return rid

    # -- results ------------------------------------------------------------

    def _deliver(self, r: _Replica, erid: int, res: RequestResult) -> int:
        rid = r.rid_map.pop(erid)
        self._assign.pop(rid, None)
        self.results[rid] = dataclasses.replace(res, rid=rid)
        return rid

    def pop_result(self, rid: int) -> RequestResult:
        """Deliver + release one terminal result (the engine
        ``pop_result`` discipline at router scope)."""
        with self.timers.span("router.pop_result"):
            return self.results.pop(rid)

    def abort(self, rid: int) -> bool:
        """Cancel one request wherever it lives — queued/active on a
        replica, or parked as an orphan. Same semantics as
        ``engine.abort``: True on transition, False if already
        terminal, KeyError for unknown rids."""
        if rid in self.results:
            return False
        for i, (orid, q) in enumerate(self._orphans):
            if orid == rid:
                del self._orphans[i]
                self.results[rid] = RequestResult(
                    rid=rid, state=ABORTED,
                    tokens=np.concatenate([
                        np.asarray(q.prompt, np.int32),
                        np.asarray(q.gen, np.int32),
                    ]),
                    reason="abort() while parked (no live replica)",
                )
                return True
        loc = self._assign.get(rid)
        if loc is None:
            raise KeyError(
                f"unknown router rid {rid}: never submitted, or already "
                "delivered via pop_result"
            )
        rep_id, erid = loc
        r = self._replicas[rep_id]
        if r.engine.abort(erid):
            # A DRAINED replica's held snapshot still carries the entry;
            # scrub it, or restart would resurrect (and re-run) a
            # request the client cancelled — and its re-delivery would
            # hit an already-popped rid_map entry.
            if r.held_snapshot is not None:
                r.held_snapshot.pending = [
                    q for q in r.held_snapshot.pending if q.rid != erid
                ]
            self._deliver(r, erid, r.engine.pop_result(erid))
            return True
        return False

    def progress(self, rid: int, since: int = 0):
        """Tokens-so-far, from index ``since`` on, for a live or terminal
        router rid (the SSE streaming read) — None for unknown rids."""
        with self.timers.span("router.progress"):
            if rid in self.results:
                return np.asarray(self.results[rid].tokens)[since:]
            for orid, q in self._orphans:
                if orid == rid:
                    return np.concatenate([
                        np.asarray(q.prompt, np.int32),
                        np.asarray(q.gen, np.int32),
                    ])[since:]
            loc = self._assign.get(rid)
            if loc is None:
                return None
            rep_id, erid = loc
            return self._replicas[rep_id].engine.peek_tokens(erid, since)

    def has_work(self) -> bool:
        return bool(self._orphans) or any(
            r.state in _ROUTABLE and r.engine.has_work()
            for r in self._replicas
        )

    # -- the tick -----------------------------------------------------------

    def step(self, params) -> list[int]:
        """One router tick: fire chaos, re-adopt orphans, then advance
        every routable replica one engine tick — measuring its latency
        for brown-out detection, catching ``DispatchFailure`` as
        replica death — and deliver every terminal result under ROUTER
        rids. Returns the router rids that reached a terminal state."""
        with self.timers.span("router.tick"):
            self._ticks += 1
            if self._injector is not None:
                self._injector.on_tick(self._ticks)
                # Drain EVERY armed kill (a correlated-failure schedule may
                # script several on one tick), re-reading the live set after
                # each — a kill changes it.
                while True:
                    target = self._injector.pop_kill(self.live_replicas())
                    if target is None:
                        break
                    self.kill(target, reason="chaos replica_kill")
            self._readopt_orphans()
            finished: list[int] = []

            def _idle(r: _Replica) -> bool:
                if r.engine.has_work():
                    return False
                # An idle DEGRADED replica would stay deprioritized
                # forever (no ticks -> no EMA evidence): decay its EMA
                # optimistically instead — DEGRADED only deprioritizes,
                # so a premature recovery costs one slow tick, not an
                # outage.
                if r.state == DEGRADED:
                    self._update_health(r, 0.0)
                return True

            def _one(r: _Replica):
                t0 = self._clock()
                try:
                    done = r.engine.step(params)
                except DispatchFailure as err:
                    return r, self._clock() - t0, None, err
                return r, self._clock() - t0, done, None

            def _settle(r: _Replica, dt: float, done, err) -> None:
                if err is not None:
                    # The engine exhausted its own retry budget and left its
                    # state consistent (everything requeued) — at the router
                    # tier that IS replica death; survivors take the work.
                    self._take_down(
                        r, f"dispatch failure: {err}", finished=finished
                    )
                    return
                self._update_health(r, dt)
                for erid in done:
                    finished.append(
                        self._deliver(r, erid, r.engine.pop_result(erid))
                    )

            if self.parallel_step:
                busy = [
                    r for r in self._replicas
                    if r.state in _ROUTABLE and not _idle(r)
                ]
                if len(busy) > 1:
                    # Each replica's dispatch overlaps on its own device
                    # slice; everything mutable at router scope waits for
                    # the joins.
                    with ThreadPoolExecutor(max_workers=len(busy)) as pool:
                        stepped = list(pool.map(_one, busy))
                else:
                    stepped = [_one(r) for r in busy]
                for r, dt, done, err in stepped:
                    _settle(r, dt, done, err)
            else:
                # Settle inline, re-reading routability and has_work at each
                # replica's turn: a mid-tick death's failover entries can be
                # adopted — and stepped — by replicas LATER this same tick.
                for r in self._replicas:
                    if r.state not in _ROUTABLE or _idle(r):
                        continue
                    _settle(*_one(r))
            self._pump_handoffs(finished)
            return finished

    # -- disaggregation: kv handoff pump ------------------------------------

    def _handoff_target(self, h) -> _Replica | None:
        """Best routable replica to continue a finished prefill: never a
        PREFILL worker (the role pin's other direction — decode work
        does not route to prefill-only replicas), must pass the
        engine-side geometry/capacity gate (``can_import_handoff``),
        preferring HEALTHY then lowest page pressure (pages are what a
        handoff consumes) then lightest host load, id tie-break."""
        best, best_key = None, None
        for r in self._replicas:
            if r.state not in _ROUTABLE or self._role(r) == "prefill":
                continue
            eng = r.engine
            if not (hasattr(eng, "can_import_handoff")
                    and eng.can_import_handoff(h)):
                continue
            st = eng.stats()
            pinned = st.get("session_pinned_pages") or 0
            pressure = (
                (st["pages_in_use"] + pinned) / max(1, st["pool_pages"])
                if st.get("free_pages") is not None else 0.0
            )
            key = (
                1.0 if r.state == DEGRADED else 0.0,
                pressure,
                float(st["queue_depth"] + st["active_rows"]),
                float(r.rep_id),
            )
            if best_key is None or key < best_key:
                best, best_key = r, key
        return best

    def _pump_handoffs(self, finished: list[int]) -> None:
        """Move every finished prefill off its PREFILL worker onto a
        decode-capable replica. Source rows stay live (resume-entry
        fallback) until ``complete_handoff`` — a crash on either side
        mid-handoff degrades to the ordinary failover path, never to a
        lost or duplicated rid. No target this tick just parks the row;
        it is retried next tick (prefill workers park ready rows
        rather than decoding them)."""
        for src in self._replicas:
            if src.state not in _ROUTABLE or self._role(src) != "prefill":
                continue
            seng = src.engine
            for erid in list(seng.handoff_ready()):
                t0 = self._clock()
                h = seng.export_handoff(erid)
                dst = self._handoff_target(h)
                if dst is None:
                    continue
                eng_fin: list[int] = []
                try:
                    new_erid = dst.engine.import_handoff(h, eng_fin)
                except DispatchFailure as err:
                    # _take_down snapshots the destination and delivers
                    # EVERY undelivered result — including rows the
                    # failed import's recovery terminally FAILED — so
                    # eng_fin must not be delivered again here.
                    self._take_down(
                        dst, f"kv_import dispatch failure: {err}",
                        finished=finished,
                    )
                    continue
                # Recovery inside a survivable failed import can
                # terminally FAIL rows on the destination (retry budget
                # exhausted) — deliver them like step() would.
                for fe in eng_fin:
                    finished.append(self._deliver(
                        dst, fe, dst.engine.pop_result(fe)
                    ))
                if new_erid is None:
                    continue  # no row/pages after all — retry next tick
                rid = src.rid_map.pop(erid)
                dst.rid_map[new_erid] = rid
                self._assign[rid] = (dst.rep_id, new_erid)
                seng.complete_handoff(erid)
                self.counters["handoffs"] += 1
                log_event(
                    "kv_handoff", rid=rid, from_replica=src.rep_id,
                    to_replica=dst.rep_id, pages=h.n_pages,
                    bytes=h.wire_bytes, useful_bytes=h.useful_bytes,
                    export_s=round(h.export_s, 6),
                    latency_s=round(self._clock() - t0, 6),
                    t=round(self._clock(), 6),
                )

    def run(self, params, *, max_ticks: int | None = None) -> list[int]:
        """Drive ``step`` until idle (or ``max_ticks``); returns every
        router rid that finished during the drive."""
        finished: list[int] = []
        ticks = 0
        while self.has_work():
            if max_ticks is not None and ticks >= max_ticks:
                break
            finished += self.step(params)
            ticks += 1
        return finished

    def _update_health(self, r: _Replica, dt: float) -> None:
        a = self.ema_alpha
        r.tick_ema_s = (
            dt if r.tick_ema_s is None
            else (1 - a) * r.tick_ema_s + a * dt
        )
        others = [
            x.tick_ema_s for x in self._replicas
            if x is not r and x.state in _ROUTABLE
            and x.tick_ema_s is not None
        ]
        if not others:
            # No peer baseline (single-replica fleet, or the first
            # replica to ever tick): "slow" is only meaningful RELATIVE
            # to the fleet, so judging against the degrade_min_s floor
            # alone would brand every replica of a slow model DEGRADED.
            return
        med = sorted(others)[len(others) // 2]
        threshold = max(self.degrade_min_s, self.degrade_factor * med)
        if r.state == HEALTHY and r.tick_ema_s > threshold:
            r.state = DEGRADED
            log_event(
                "replica_degraded", replica=r.rep_id,
                tick_ema_s=round(r.tick_ema_s, 4),
                threshold_s=round(threshold, 4),
                t=round(self._clock(), 6),
            )
        elif r.state == DEGRADED and r.tick_ema_s <= threshold:
            r.state = HEALTHY
            log_event(
                "replica_recovered", replica=r.rep_id,
                tick_ema_s=round(r.tick_ema_s, 4),
                t=round(self._clock(), 6),
            )

    # -- failover / drain / restart ----------------------------------------

    def kill(self, rep_id: int, *, reason: str = "process loss") -> None:
        """Treat one replica as a lost process: its device state (and
        engine object) are written off, every in-flight/queued request
        fails over to survivors from the engine's host-side snapshot.
        Idempotent on already-down replicas (a chaos schedule may kill a
        corpse)."""
        r = self._replicas[rep_id]
        if r.state == DOWN:
            return
        self._take_down(r, reason)

    def _take_down(self, r: _Replica, reason: str,
                   finished: list[int] | None = None) -> None:
        snap = r.engine.snapshot()
        r.state = DOWN
        r.down_reason = reason
        r.held_snapshot = None
        log_event(
            "replica_down", replica=r.rep_id, reason=reason,
            pending=len(snap.pending), t=round(self._clock(), 6),
        )
        # Undelivered terminal results are host memory — they survive
        # the replica and deliver now (their rids are NOT lost).
        for erid, res in snap.results.items():
            rid = self._deliver(r, erid, res)
            if finished is not None:
                finished.append(rid)
        self.counters["failovers"] += 1
        self._redistribute(r, snap.pending)
        r.rid_map.clear()

    def _least_loaded(self, exclude: _Replica | None = None, *,
                      colocated_only: bool = False):
        """Least-loaded routable replica for failover/re-adoption —
        same preference order as routing (HEALTHY before DEGRADED, then
        host load, then id) but WITHOUT the admission thresholds:
        failover must not shed accepted work, and engine-side deferral
        (page starvation) already degrades gracefully. DECODE workers
        are never candidates (a resume entry is re-PREFILL work — the
        decode-ward regression pin's mirror); ``colocated_only``
        additionally excludes PREFILL workers (sessions must live where
        their turns both prefill AND decode)."""
        best, best_key = None, None
        for r in self._replicas:
            if r is exclude or r.state not in _ROUTABLE:
                continue
            role = self._role(r)
            if role == "decode" or (colocated_only and role != "colocated"):
                continue
            st = r.engine.stats()
            key = (
                1.0 if r.state == DEGRADED else 0.0,
                float(st["queue_depth"] + st["active_rows"]),
                float(r.rep_id),
            )
            if best_key is None or key < best_key:
                best, best_key = r, key
        return best

    def _redistribute(self, src: _Replica, pendings) -> None:
        """Re-route a dead/drained replica's entries (ascending rid =
        the replica's own FIFO order) onto least-loaded survivors; park
        what nothing can take."""
        for q in pendings:
            rid = src.rid_map.pop(q.rid)
            best = self._least_loaded(exclude=src)
            if best is None:
                self.counters["orphaned"] += 1
                self._orphans.append((rid, q))
                self._assign.pop(rid, None)
                log_event(
                    "failover", rid=rid, from_replica=src.rep_id,
                    to_replica=None, parked=True,
                    resumed_tokens=len(q.gen),
                    t=round(self._clock(), 6),
                )
                continue
            self._adopt_one(best, rid, q, from_replica=src.rep_id)

    def _adopt_one(self, r: _Replica, rid: int, q,
                   from_replica: int | None) -> None:
        new_erid = r.engine.adopt([q])[q.rid]
        r.rid_map[new_erid] = rid
        self._assign[rid] = (r.rep_id, new_erid)
        self.counters["failover_requests"] += 1
        log_event(
            "failover", rid=rid, from_replica=from_replica,
            to_replica=r.rep_id, resumed_tokens=len(q.gen),
            t=round(self._clock(), 6),
        )

    def _readopt_orphans(self) -> None:
        if not self._orphans:
            return
        orphans, self._orphans = self._orphans, []
        for rid, q in orphans:
            best = self._least_loaded()
            if best is None:
                self._orphans.append((rid, q))
            else:
                self._adopt_one(best, rid, q, from_replica=None)

    def drain(self, rep_id: int, *, migrate: bool = False) -> int:
        """Planned maintenance: snapshot the replica's host state and
        take it out of rotation. Default keeps the snapshot parked on
        the record — ``restart`` restores it and the drained requests
        continue bit-identically (zero lost, zero duplicated rids);
        ``migrate=True`` hands the work to survivors immediately (the
        failover path without the fault). Returns the number of
        requests captured. Draining the last routable replica with
        ``migrate=True`` parks the work (orphans) rather than refusing.
        """
        r = self._replicas[rep_id]
        if r.state not in _ROUTABLE:
            raise RuntimeError(
                f"replica {rep_id} is {r.state}; drain needs a routable "
                "replica"
            )
        snap = r.engine.snapshot()
        log_event(
            "drain", replica=rep_id, pending=len(snap.pending),
            migrate=migrate, t=round(self._clock(), 6),
        )
        self.counters["drains"] += 1
        # Undelivered results deliver NOW and are scrubbed from BOTH the
        # held snapshot (restore would hand the rid out twice) and the
        # still-live engine (a later kill() re-snapshots it and must not
        # re-deliver).
        for erid, res in list(snap.results.items()):
            r.engine.pop_result(erid)
            self._deliver(r, erid, res)
        snap.results = {}
        if migrate:
            r.state = DOWN
            r.down_reason = "drained (migrated)"
            self._redistribute(r, snap.pending)
            r.rid_map.clear()
        else:
            r.state = DRAINED
            r.down_reason = "drained (held for restart)"
            r.held_snapshot = snap
        return len(snap.pending)

    def restart(self, rep_id: int, params) -> None:
        """Bring a DOWN/DRAINED replica back: fresh engine from the
        factory, re-warmed (the restart pays its compile set ONCE, and
        the watermark resets so steady-compile assertions stay
        meaningful), drained snapshot restored if one is held. The
        replica re-enters rotation HEALTHY."""
        r = self._replicas[rep_id]
        if r.state in _ROUTABLE:
            raise RuntimeError(
                f"replica {rep_id} is {r.state}; restart needs a "
                "DOWN/DRAINED replica"
            )
        if r.state == DOWN:
            # Work was redistributed (or lost with the process) — any
            # stale engine-rid mappings died with the old engine.
            r.rid_map.clear()
        r.engine = self._make_engine(rep_id)
        self._log_role(r)
        r.engine.warmup(params)
        if r.held_snapshot is not None:
            r.engine.restore(r.held_snapshot)
            r.held_snapshot = None
        r.warm_count = r.engine.compile_count()
        r.state = HEALTHY
        r.tick_ema_s = None
        r.down_reason = ""
        # Router sessions still homed here point at the OLD engine's
        # sids — the fresh engine restarts its session counter, so a
        # stale esid would either read as unknown or collide with a
        # later open_session(). Re-home each onto a fresh engine session
        # on this replica (empty transcript; the next turn's resubmitted
        # conversation extends it — one cold prefill, no data loss).
        for sid, (home, _stale) in list(self._sessions.items()):
            if home != rep_id:
                continue
            esid = r.engine.open_session()
            self._sessions[sid] = (rep_id, esid)
            self.counters["session_rehomes"] += 1
            log_event(
                "session_route", session=sid, replica=rep_id,
                engine_session=esid, rehomed_from=rep_id,
                t=round(self._clock(), 6),
            )
        self.counters["restarts"] += 1
        log_event(
            "replica_up", replica=rep_id, t=round(self._clock(), 6),
        )

    # -- introspection ------------------------------------------------------

    def stats(self) -> dict[str, Any]:
        """Router-tier snapshot: per-replica health + the uniform engine
        stats, router counters, and orphan depth — what ``/healthz``
        serves."""
        return {
            "replicas": {
                r.rep_id: dict(
                    state=r.state,
                    tick_ema_s=(
                        None if r.tick_ema_s is None
                        else round(r.tick_ema_s, 6)
                    ),
                    down_reason=r.down_reason or None,
                    **(
                        r.engine.stats() if r.state != DOWN
                        else {"engine": None}
                    ),
                )
                for r in self._replicas
            },
            "orphans": len(self._orphans),
            "undelivered_results": len(self.results),
            "sessions": len(self._sessions),
            "counters": dict(self.counters),
            "timers": self.timers.snapshot(),
        }
