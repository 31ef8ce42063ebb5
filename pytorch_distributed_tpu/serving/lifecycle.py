"""Request lifecycle vocabulary for the serving engines.

Every request submitted to ``BatchedDecodeEngine`` ends in exactly one
TERMINAL state, delivered as a ``RequestResult`` through ``pop_result``:

- ``DONE``    — ran to its token budget (or per-row EOS); ``tokens`` is
  the full prompt + generated sequence.
- ``FAILED``  — the engine gave up on it: non-finite logits persisted
  after the one fresh-row quarantine retry, or the request exhausted its
  fault-resume budget (``request_retries``). ``tokens`` holds the clean
  partial prefix generated before the fault.
- ``ABORTED`` — the client called ``abort(rid)``; partial prefix.
- ``EXPIRED`` — its deadline (``submit(timeout_s=...)``) passed while
  queued or mid-decode; partial prefix.

The state machine (docs/ROBUSTNESS.md draws it):

    submit -> QUEUED -> ACTIVE -> DONE
                 |         |----> ABORTED / EXPIRED / FAILED
                 |         '----> QUEUED (fault resume: NaN quarantine,
                 |                dispatch failure, engine replay)
                 '------> ABORTED / EXPIRED

Non-terminal states (QUEUED/ACTIVE) are engine-internal — observable via
``queued_rids()`` / ``active_rids()`` — and a request may bounce
ACTIVE -> QUEUED any number of times through the fault-resume path; the
invariant the storm (tests/test_chaos.py::test_storm_invariants_hold)
asserts is that every rid reaches exactly ONE terminal
result, and a terminal rid never reappears.

The paged engine (``PagedBatchedDecodeEngine``) adds one more
ACTIVE -> QUEUED bounce: PREEMPTION. When the KV page pool is exhausted
mid-decode, the youngest active request (the one "queued last") is
converted to a resume entry — clean tokens-so-far preserved, pages
released — and re-admitted when pages free up, continuing
token-identically. Preemption is load shedding, not a fault: it charges
no retry budget and cannot FAIL a request. The lifecycle log records it
as a ``preempt`` event next to ``submit``/``admit``/``retire``, and
paged admissions log their prefix-cache outcome (``prefix_hit`` with the
shared token count) so cache effectiveness is visible per request.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

DONE = "DONE"
FAILED = "FAILED"
ABORTED = "ABORTED"
EXPIRED = "EXPIRED"
TERMINAL_STATES = (DONE, FAILED, ABORTED, EXPIRED)


@dataclasses.dataclass
class RequestResult:
    """One request's terminal outcome. ``tokens`` always holds the
    original prompt followed by every CLEAN token generated before the
    terminal transition — for non-DONE states that is a prefix of what an
    undisturbed run would have produced (quarantined/garbage tokens are
    never appended), so partial results are usable, not corrupt."""

    rid: int
    state: str  # one of TERMINAL_STATES
    tokens: np.ndarray  # [prompt + generated-so-far] int32
    reason: str = ""  # diagnostic for FAILED/ABORTED/EXPIRED

    def __post_init__(self) -> None:
        if self.state not in TERMINAL_STATES:
            raise ValueError(
                f"state must be one of {TERMINAL_STATES}, got {self.state!r}"
            )


@dataclasses.dataclass
class EngineSnapshot:
    """Host-side engine state for crash recovery: everything needed to
    rebuild a ``BatchedDecodeEngine`` after the device (and with it the
    donated KV cache) is lost. In-flight rows are captured as RESUME
    entries carrying their tokens-so-far; a rebuilt engine re-prefills
    each from that prefix and continues token-identically (the per-row
    PRNG fold schedule is part of the entry). Capture between ``step``
    calls; restore onto a fresh idle engine of the same model config."""

    pending: list  # engine._Pending entries, ascending rid
    next_rid: int
    results: dict[int, RequestResult]  # undelivered terminal results
    stats: dict[str, Any] = dataclasses.field(default_factory=dict)


class AdmissionQueueFull(RuntimeError):
    """Bounded admission queue overflow under the ``reject`` backpressure
    policy (or ``block`` timing out): submitted load exceeds what the
    engine drains. Carries the limit in the message so the 429 path is
    diagnosable."""


class RequestFailed(RuntimeError):
    """The serial ``DecodeEngine`` detected non-finite logits and the one
    fresh-cache retry reproduced them — the request's output would be
    garbage, so it fails loudly instead of emitting tokens."""


class PagePoolExhausted(RuntimeError):
    """The paged engine could not free a KV page even after preempting
    every other active request — an invariant violation (construction
    validates ``pool_pages >= max_len/page_size + 1``, which guarantees
    one full-length row always fits), kept as a loud defensive raise
    instead of the silent hang a starved allocator would otherwise be."""


class DispatchFailure(RuntimeError):
    """The batched engine's consecutive-dispatch-failure budget
    (``dispatch_retries``) is exhausted. Engine state is CONSISTENT when
    this raises: every in-flight request has been requeued (or FAILED if
    out of resume budget) and the cache dropped — the caller can
    ``snapshot()`` and rebuild, or keep the engine and try again later."""


class RouterOverloaded(RuntimeError):
    """SLO-aware load shedding (`serving/router.py`): every routable
    replica is past its admission thresholds (queue depth and/or page
    headroom), so the router rejects LOUDLY instead of queueing without
    bound — unbounded queues turn overload into unbounded p99, which is
    worse than a clean 429. ``retry_after_s`` is the router's drain-time
    estimate; the HTTP front door maps it onto a ``Retry-After``
    header."""

    def __init__(self, message: str, *, retry_after_s: float | None = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s
