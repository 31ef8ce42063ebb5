"""Deterministic fault injection for the serving engines.

Robustness claims are only as good as the faults they were tested
against, and real faults (device resets, NaN-producing kernels, lost
RPCs, scheduler stalls) are neither reproducible nor cheap to provoke.
This module makes them both: a ``FaultInjector`` installed on a
``BatchedDecodeEngine`` (``engine.set_fault_injector``) drives seeded,
composable injections through HOST-SIDE hooks only — nothing traced ever
sees it, so injection cannot change a compiled program, its shapes, or
its pinned collective budgets (the whole point: the fault paths must
exercise the SAME executables production runs).

The schedule machinery (scripted + seeded arming, the ``VirtualClock``,
firing counts) is the shared ``utils/chaos.ScriptedFaults`` core — the
training-side injector (train/chaos.py) runs the identical engine with
its own fault catalog and hook points.

Injection points (the full catalog — docs/ROBUSTNESS.md):

- ``dispatch_error`` — raise before the program runs. The donated cache
  was already taken, so the engine sees exactly what a device-side
  dispatch failure looks like: buffer consumed, in-flight K/V gone.
- ``drop_result``   — raise AFTER the program ran: the compute happened
  and the cache was consumed, but the result never reached the
  scheduler (a lost RPC/transfer). Same recovery path, cost paid.
- ``nan_row``       — flip one active row's non-finite sentinel flag,
  simulating a poisoned logits row at the scheduler boundary (the
  traced sentinel itself is tested separately with genuinely-NaN
  params). Targets decode ticks; transient by default, so the
  quarantine retry succeeds.
- ``slow_tick``     — advance the engine's ``VirtualClock``, modelling a
  stall; this is how deadline expiries are driven deterministically.

Faults come scripted (``Fault(tick=...)`` — exact, for tests) and/or
seeded (per-tick Bernoulli draws from one ``numpy`` generator — for the
storm tests); both compose. Every firing is counted in
``injector.counts`` so a run can assert its fault schedule actually
fired (a chaos test that injected nothing is coverage theater).
"""

from __future__ import annotations

import numpy as np

from pytorch_distributed_tpu.utils.chaos import (  # noqa: F401  (re-export)
    ScriptedFaults,
    VirtualClock,
)
from pytorch_distributed_tpu.utils import chaos as _chaos

FAULT_KINDS = ("dispatch_error", "drop_result", "nan_row", "slow_tick")


class ChaosDispatchError(RuntimeError):
    """Injected device-side dispatch failure (program never ran; the
    donated cache is consumed regardless)."""


class ChaosDroppedResult(RuntimeError):
    """Injected result loss: the program ran (cache consumed, compute
    paid) but the output never reached the scheduler."""


class Fault(_chaos.Fault):
    """One scripted serving injection. ``tick`` is the engine's step
    counter (first step = tick 1). ``program`` restricts dispatch faults
    to 'prefill' / 'decode_step' / 'decode_spec_step' (None = first
    dispatch of the tick);
    ``row`` picks the nan_row target slot (None = seeded choice among
    active rows); ``seconds`` is the slow_tick stall."""

    KINDS = FAULT_KINDS


class FaultInjector(ScriptedFaults):
    """Seeded + scripted fault schedule over an engine's dispatch hooks.

    ``faults``: scripted ``Fault`` list (fires exactly once each).
    ``seed``: enables the random schedule — each tick draws one
    Bernoulli per probability from a private generator, so the schedule
    is a pure function of (seed, tick sequence). ``clock``: the engine's
    ``VirtualClock``, required for slow_tick faults.
    """

    def __init__(
        self,
        faults: tuple[Fault, ...] | list[Fault] = (),
        *,
        seed: int | None = None,
        p_dispatch_error: float = 0.0,
        p_drop_result: float = 0.0,
        p_nan_row: float = 0.0,
        p_slow_tick: float = 0.0,
        slow_tick_s: float = 0.25,
        clock: VirtualClock | None = None,
    ) -> None:
        super().__init__(
            faults,
            seed=seed,
            probabilities={
                "dispatch_error": p_dispatch_error,
                "drop_result": p_drop_result,
                "nan_row": p_nan_row,
                "slow_tick": p_slow_tick,
            },
            slow_kinds=("slow_tick",),
            slow_s=slow_tick_s,
            clock=clock,
            fault_cls=Fault,
        )
        self._engine = None

    def install(self, engine) -> "FaultInjector":
        engine.set_fault_injector(self)  # sets our _engine back-reference
        return self

    # -- engine hooks (host-side only) --------------------------------------

    def before_dispatch(self, kind: str, tick: int) -> None:
        f = self._pop("dispatch_error", kind)
        if f is not None:
            self.counts["dispatch_error"] += 1
            raise ChaosDispatchError(
                f"injected dispatch failure (tick {tick}, {kind})"
            )

    def after_dispatch(self, kind: str, tick: int, tok, bad):
        f = self._pop("drop_result", kind)
        if f is not None:
            self.counts["drop_result"] += 1
            raise ChaosDroppedResult(
                f"injected result loss (tick {tick}, {kind})"
            )
        if kind in ("decode_step", "decode_spec_step"):
            f = self._pop("nan_row", kind)
            if f is not None:
                row = f.row
                if row is None:
                    active = [
                        i for i, s in enumerate(self._engine._slots)
                        if s is not None
                    ]
                    if not active:
                        return tok, bad
                    picker = self._rng or np.random.default_rng(tick)
                    row = int(active[picker.integers(len(active))])
                bad = np.asarray(bad).copy()
                bad[row] = True
                self.counts["nan_row"] += 1
        return tok, bad


ROUTER_FAULT_KINDS = ("replica_kill",)


class RouterFault(_chaos.Fault):
    """One scripted ROUTER-TIER injection. ``tick`` is the router's step
    counter (first step = tick 1); ``row`` picks the target replica id
    (None = seeded choice among the replicas live at fire time)."""

    KINDS = ROUTER_FAULT_KINDS


class RouterFaultInjector(ScriptedFaults):
    """Seeded + scripted replica-death schedule for ``ReplicaRouter``
    (the router-tier storm): a fired ``replica_kill`` makes the router
    treat one replica as a lost PROCESS — no exception from the engine,
    no goodbye; the router's health/failover machinery must notice and
    convert every in-flight request to a re-routed resume entry. Same
    ``utils/chaos.ScriptedFaults`` engine as the per-replica
    ``FaultInjector`` (install THAT on individual replica engines for
    dispatch/NaN/slow faults; brown-out storms combine both), so a whole
    router storm is a pure function of its seeds."""

    def __init__(
        self,
        faults: tuple[RouterFault, ...] | list[RouterFault] = (),
        *,
        seed: int | None = None,
        p_replica_kill: float = 0.0,
        clock: VirtualClock | None = None,
    ) -> None:
        super().__init__(
            faults,
            seed=seed,
            probabilities={"replica_kill": p_replica_kill},
            clock=clock,
            fault_cls=RouterFault,
        )

    def install(self, router) -> "RouterFaultInjector":
        router.set_fault_injector(self)
        return self

    def pop_kill(self, live_ids) -> int | None:
        """The replica to kill this tick, or None. Scripted faults may
        pin the target (``row``); seeded draws pick uniformly among the
        replicas live at fire time (a kill schedule drawn blind could
        only ever miss). A fault whose pinned target is already down is
        consumed without effect — the process it models is already
        dead."""
        f = self._pop("replica_kill", None)
        if f is None:
            return None
        live_ids = list(live_ids)
        if f.row is not None:
            if f.row not in live_ids:
                return None
            self._count("replica_kill")
            return int(f.row)
        if not live_ids:
            return None
        if self._rng is None:
            # Unseeded scripted faults still need an ADVANCING generator
            # for target choice — a fresh rng per call would pin every
            # kill to the same pick.
            self._rng = np.random.default_rng(0)
        self._count("replica_kill")
        return int(live_ids[self._rng.integers(len(live_ids))])
