"""Seeded serving workloads: ONE arrival-stream generator for every
consumer.

Three near-copies of "seeded Poisson-ish mixed traffic" once drifted
on the details that decide whether two runs are comparable: one drew a per-request key as
``jax.random.key(base + i)``, another as ``fold_in(key(base), i)``, a
third shared ONE key across every sampled request. A robustness claim
("DONE outputs bit-equal to a fault-free run of the same schedule") is
only meaningful when "the same schedule" is a single function of the
seed, so the generator lives here and the storms of
tests/test_chaos.py and tests/test_router.py, the serving tests and
``perfbench/`` all consume it.

Conventions (the points the copies drifted on, now pinned):

- **Per-request keys** are ``fold_in(jax.random.key(key_seed), i)`` —
  one base key, folded by request index. Requests are independent
  streams whatever engine or replica serves them.
- **Sampling configs** cycle through ``sampling_cycle`` by request
  index (greedy rows share batches with sampled ones by default).
- **Arrivals** are exponential inter-arrival times (Poisson process)
  from the SAME generator that drew the requests, so one seed fixes
  offered load and content together.

Everything returns plain host data (numpy arrays + ``submit`` kwarg
dicts); nothing here touches a device.
"""

from __future__ import annotations

import numpy as np

# Greedy rows deliberately share the stream with sampled ones: the
# batched engines' per-row traced sampling state is exactly what makes
# that free, and a workload without the mix would under-exercise it.
DEFAULT_SAMPLING_CYCLE = (
    dict(temperature=0.8, top_k=20),
    dict(temperature=1.0, top_p=0.9),
    dict(),  # greedy
)


def request_stream(
    rng: np.random.Generator,
    *,
    n: int,
    vocab_size: int,
    prompt_len: tuple[int, int],
    max_new: int | tuple[int, int],
    sampling_cycle=DEFAULT_SAMPLING_CYCLE,
    key_seed: int | None = None,
    shared_prefix: np.ndarray | None = None,
    p_deadline: float = 0.0,
    deadline_range: tuple[float, float] = (0.5, 4.0),
) -> list[dict]:
    """The seeded request schedule: a list of ``engine.submit`` /
    ``router.submit`` kwarg dicts (prompt, max_new_tokens, sampling
    config, per-request key, optional ``timeout_s`` deadline).

    ``prompt_len`` draws uniformly over [lo, hi] inclusive (the random
    TAIL length when ``shared_prefix`` is given — the prefix-cache
    traffic shape); ``max_new`` is fixed or a [lo, hi] draw;
    ``p_deadline`` attaches a ``timeout_s`` drawn from
    ``deadline_range`` to that fraction of requests (engine-clock
    seconds — drive with a VirtualClock to make expiries replayable).
    ``key_seed`` defaults to a draw from ``rng`` so the whole stream
    stays a pure function of the caller's seed either way."""
    import jax

    if key_seed is None:
        key_seed = int(rng.integers(0, 2**31 - 1))
    base_key = None  # built lazily: greedy-only streams never need jax
    lo, hi = prompt_len
    reqs: list[dict] = []
    for i in range(n):
        tp = int(rng.integers(lo, hi + 1))
        tail = rng.integers(0, vocab_size, (tp,)).astype(np.int32)
        prompt = (
            tail if shared_prefix is None
            else np.concatenate([np.asarray(shared_prefix, np.int32), tail])
        )
        mn = (
            int(max_new) if isinstance(max_new, int)
            else int(rng.integers(max_new[0], max_new[1] + 1))
        )
        kw = dict(sampling_cycle[i % len(sampling_cycle)])
        if kw.get("temperature"):
            if base_key is None:
                base_key = jax.random.key(key_seed)
            kw["key"] = jax.random.fold_in(base_key, i)
        # The deadline Bernoulli draws UNCONDITIONALLY so the request
        # content downstream of request i is identical whether or not
        # this stream uses deadlines — legs with and without them stay
        # comparable request-for-request.
        u, d = rng.random(), float(rng.uniform(*deadline_range))
        if u < p_deadline:
            kw["timeout_s"] = d
        reqs.append(dict(prompt=prompt, max_new_tokens=mn, **kw))
    return reqs


def repetitive_request_stream(
    rng: np.random.Generator,
    *,
    n: int,
    vocab_size: int,
    pattern_len: tuple[int, int] = (2, 5),
    repeats: tuple[int, int] = (3, 6),
    max_new: int | tuple[int, int] = 16,
) -> list[dict]:
    """Seeded SELF-REPETITIVE greedy traffic — the stream speculative
    decoding exists for (code, extraction, quote-heavy summarisation):
    each prompt is a per-request random pattern tiled ``repeats``
    times, so the prompt-lookup n-gram match fires from the first
    generated token, and greedy decode of a fixed model self-loops
    shortly after, keeping it firing. All rows are greedy by
    construction (the engines draft only greedy rows); the LOW-
    repetition counterpart is an ordinary sampled ``request_stream``
    (sampled rows ride zero-draft lanes and pay the verify width for
    nothing — the regression bound the spec bench documents)."""
    lo, hi = pattern_len
    reqs: list[dict] = []
    for _ in range(n):
        pat = rng.integers(
            0, vocab_size, (int(rng.integers(lo, hi + 1)),)
        ).astype(np.int32)
        prompt = np.tile(pat, int(rng.integers(repeats[0], repeats[1] + 1)))
        mn = (
            int(max_new) if isinstance(max_new, int)
            else int(rng.integers(max_new[0], max_new[1] + 1))
        )
        reqs.append(dict(prompt=prompt, max_new_tokens=mn))
    return reqs


def tiered_stream(
    seed: int,
    *,
    vocab_size: int,
    tiers: dict[str, dict],
) -> list[dict]:
    """Mixed-SLO arrival stream: ``tiers`` maps a priority class name
    (serving/scheduler.py) -> ``request_stream`` kwargs (``n``,
    ``prompt_len``, ``max_new``, ...). Entries carry ``priority=`` and
    interleave proportionally by index, so one submit loop drives the
    whole mix and every scheduler batch window sees all tiers.

    Each tier's content derives from ``(seed, tier name)`` ALONE —
    adding or dropping a tier never changes another tier's prompts,
    keys, or sampling draws. That independence is what makes the
    scenarios bench's "interactive p99 loaded vs unloaded" a
    request-for-request comparison: the unloaded leg replays the
    interactive tier's EXACT requests without the batch flood."""
    import zlib

    from pytorch_distributed_tpu.serving.scheduler import check_priority

    tagged: list[tuple[float, int, int, dict]] = []
    for tier, kw in tiers.items():
        check_priority(tier)
        # Stable per-tier substream: crc32(tier) + seed, untouched by
        # the other tiers (a shared parent rng would re-order draws).
        sub = np.random.default_rng([zlib.crc32(tier.encode()), seed])
        reqs = request_stream(sub, vocab_size=vocab_size, **kw)
        for i, r in enumerate(reqs):
            r["priority"] = tier
            # Fractional position in the tier -> global interleave
            # order; rank-then-index tiebreak keeps it deterministic.
            tagged.append(
                ((i + 0.5) / len(reqs), check_priority(tier), i, r)
            )
    return [r for *_, r in sorted(tagged, key=lambda e: e[:3])]


def disagg_stream(
    seed: int,
    *,
    n: int,
    vocab_size: int,
    p_heavy_prefill: float = 0.5,
    heavy_prompt_len: tuple[int, int] = (96, 160),
    heavy_max_new: tuple[int, int] = (4, 8),
    light_prompt_len: tuple[int, int] = (8, 24),
    light_max_new: tuple[int, int] = (24, 48),
    sampling_cycle=DEFAULT_SAMPLING_CYCLE,
) -> list[dict]:
    """The disaggregation workload: a seeded mix of the two shapes
    whose INTERFERENCE prefill/decode separation exists to remove —
    ``heavy_prefill`` rows (long prompt, short decode: the chunked
    prefill that stalls a colocated engine's decode ticks) and
    ``light`` rows (short prompt, long decode: the interactive traffic
    whose inter-token p99 that stall inflates). Each dict is a
    ``submit`` kwarg set plus a ``"kind"`` tag ("heavy_prefill" /
    "light") the driver pops before submitting — the bench classifies
    its latency percentiles by it.

    Request ``i``'s content (class draw, lengths, tokens, deadline-free
    sampling config) derives from ``(seed, i)`` ALONE — its own
    ``default_rng([crc32("disagg"), seed, i])`` substream plus the
    ``fold_in(key(seed), i)`` sampling key — so truncating, extending,
    or re-partitioning the stream never perturbs any other request:
    colocated and disaggregated legs replay request-for-request
    identical content whatever fleet serves them."""
    import zlib

    import jax

    base_key = None
    reqs: list[dict] = []
    for i in range(n):
        sub = np.random.default_rng([zlib.crc32(b"disagg"), seed, i])
        heavy = bool(sub.random() < p_heavy_prefill)
        lo, hi = heavy_prompt_len if heavy else light_prompt_len
        tp = int(sub.integers(lo, hi + 1))
        prompt = sub.integers(0, vocab_size, (tp,)).astype(np.int32)
        mlo, mhi = heavy_max_new if heavy else light_max_new
        mn = int(sub.integers(mlo, mhi + 1))
        kw = dict(sampling_cycle[i % len(sampling_cycle)])
        if kw.get("temperature"):
            if base_key is None:
                base_key = jax.random.key(seed)
            kw["key"] = jax.random.fold_in(base_key, i)
        reqs.append(dict(
            kind="heavy_prefill" if heavy else "light",
            prompt=prompt, max_new_tokens=mn, **kw,
        ))
    return reqs


def session_stream(
    rng: np.random.Generator,
    *,
    n_sessions: int,
    turns: int,
    vocab_size: int,
    open_len: tuple[int, int],
    turn_len: tuple[int, int],
    max_new: int | tuple[int, int],
    sampling_cycle=DEFAULT_SAMPLING_CYCLE,
    key_seed: int | None = None,
) -> list[list[dict]]:
    """The seeded multi-turn chat schedule: ``n_sessions`` scripts of
    ``turns`` turn dicts each. A turn dict is ``{"tail": [t] int32
    tokens, "max_new_tokens": n, <sampling kwargs>}`` — the driver
    (a test) submits ``concat(recorded transcript,
    tail)`` as the turn's prompt, which is exactly the
    conversation-so-far-plus-new-message shape ``submit(session=)``
    validates. Turn 1's tail draws ``open_len`` tokens, later turns
    draw ``turn_len``; per-turn keys are
    ``fold_in(key(key_seed), session * turns + turn)`` (the PR-11
    fold_in discipline, one base key for the whole schedule)."""
    import jax

    if key_seed is None:
        key_seed = int(rng.integers(0, 2**31 - 1))
    base_key = None
    sessions: list[list[dict]] = []
    for s in range(n_sessions):
        script: list[dict] = []
        for t in range(turns):
            lo, hi = open_len if t == 0 else turn_len
            tail = rng.integers(
                0, vocab_size, (int(rng.integers(lo, hi + 1)),)
            ).astype(np.int32)
            mn = (
                int(max_new) if isinstance(max_new, int)
                else int(rng.integers(max_new[0], max_new[1] + 1))
            )
            kw = dict(sampling_cycle[(s * turns + t) % len(sampling_cycle)])
            if kw.get("temperature"):
                if base_key is None:
                    base_key = jax.random.key(key_seed)
                kw["key"] = jax.random.fold_in(base_key, s * turns + t)
            script.append(dict(tail=tail, max_new_tokens=mn, **kw))
        sessions.append(script)
    return sessions


def exponential_arrivals(
    rng: np.random.Generator, n: int, mean_interarrival_s: float,
    start: float = 0.0,
) -> np.ndarray:
    """Arrival timestamps of a Poisson process: the first request lands
    at ``start``, the rest follow exponential inter-arrival gaps. Every
    serving bench leg calibrates ``mean_interarrival_s`` against a
    measured service rate and then replays ONE schedule through every
    leg under comparison."""
    if n < 1:
        return np.zeros((0,))
    gaps = rng.exponential(mean_interarrival_s, n - 1)
    return start + np.concatenate([[0.0], np.cumsum(gaps)])


def tick_bursts(
    rng: np.random.Generator, max_per_tick: int, length: int = 997
) -> list[int]:
    """Seeded per-tick arrival burst sizes (0..max_per_tick inclusive)
    for tick-driven drivers (the storm tests): bursty, seed-reproducible churn
    without a wall clock. A long prime-length cycle avoids resonating
    with the scheduler's own periodicities."""
    return [int(rng.integers(0, max_per_tick + 1)) for _ in range(length)]
