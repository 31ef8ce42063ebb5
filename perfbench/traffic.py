"""One general generator for every traffic mix: a mix is a data file of
parameters under ``perfbench/traffic/``, and this module turns it and
``--seed`` into the inputs. The program receives only what is generated.

Every seed gets the SAME sizes and arrival gaps (stratified quantiles of the
distributions the file names, in an order the file fixes), turned to another
starting point, with other token ids: a seed never changes the amount of
work.
"""

from __future__ import annotations

import math

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])


# -- training ------------------------------------------------------------------


def markov_tokens(n: int, vocab: int, seed: int, p_follow: float) -> np.ndarray:
    """A learnable stream: token i is (2 * previous + 1) mod vocab with
    probability ``p_follow``, uniform otherwise (the program's
    ``data/synthetic.py`` process, vectorised)."""
    rng = _rng(seed, 1)
    noise = rng.integers(0, vocab, size=n, dtype=np.int64)
    fresh = rng.random(n) > p_follow
    fresh[0] = True
    idx = np.arange(n)
    last = np.maximum.accumulate(np.where(fresh, idx, 0))
    k = idx - last  # steps since the last uniform draw
    kmax = int(k.max())
    pow2 = np.ones(kmax + 1, dtype=np.int64)
    for i in range(1, kmax + 1):
        pow2[i] = pow2[i - 1] * 2 % vocab
    start = noise[last]
    out = (start * pow2[k] + pow2[k] - 1) % vocab
    return out.astype(np.uint16)


def train_tokens(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Enough tokens for set-up and the window at the rate the file expects
    (the loader starts over if the window outruns them)."""
    data = mix["data"]
    per_step = mix["batch"] * mix["seq_len"]
    steps = data["setup_steps"] + math.ceil(
        seconds * data["tokens_per_s_ceiling"] / per_step)
    n = steps * per_step + 1
    if data["vocab"] > 2**16:
        raise ValueError("uint16 shards hold ids under 65536")
    return markov_tokens(n, data["vocab"], seed, data["p_follow"])


# -- serving -------------------------------------------------------------------


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int):
    """n stratified draws of a lognormal, clipped: the same for every seed."""
    from statistics import NormalDist

    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = np.exp(math.log(median) + sigma * np.asarray(z))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _exponential_gaps(n: int, rate: float):
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _cycle(mix: dict, max_len: int):
    """One period of a mix, the same for every seed: ``cycle_requests``
    requests with stratified sizes, paired and ordered by the file's
    ``order_seed``, which of them sample, and (open loop) their arrival
    offsets within the period of ``cycle_s`` seconds."""
    n = mix["cycle_requests"]
    p, g = mix["prompt_tokens"], mix["new_tokens"]
    rng = _rng(mix["order_seed"], 3)
    prompts = _lognormal_quantiles(
        n, p["median"], p["sigma"], p["min"], p["max"])[rng.permutation(n)]
    news = _lognormal_quantiles(
        n, g["median"], g["sigma"], g["min"], g["max"])[rng.permutation(n)]
    news = np.minimum(news, max_len - prompts)
    sampled = rng.permutation(n) < int(round(n * mix["sampled_share"]))
    offsets = None
    if mix["loop"] == "open":
        gaps = _exponential_gaps(n, 1.0)[rng.permutation(n)]
        gaps = gaps * (mix["cycle_s"] / gaps.sum())
        offsets = np.cumsum(gaps) - gaps[0]
    return prompts, news, sampled, offsets


def requests(mix: dict, seed: int, seconds: float, vocab: int, max_len: int):
    """The requests of one run: a list of dicts with ``due_s`` (open loop,
    relative to the start of the window, negative during the ramp; None in a
    closed loop), ``body`` (the JSON the client posts) and ``greedy``.

    The mix is PERIODIC: one cycle of fixed sizes, pairings and gaps
    (``_cycle``) repeats for as long as the run lasts. The seed turns the
    cycle (which request opens the window) and draws the token ids and the
    sampling seeds; it never changes the amount of work, nor which requests
    meet. An open loop whose window is one ``cycle_s`` long sees every
    request of the cycle exactly once; a closed loop is not turned at all. The ramp (``ramp_s``) is the end of
    the cycle before: it fills the server before the window opens and is
    set-up."""
    prompts, news, sampled, offsets = _cycle(mix, max_len)
    n = len(prompts)
    rng = _rng(seed, 2)
    first = int(rng.integers(0, n))  # the request due at the window's start
    ramp = mix["ramp_s"]
    if mix["loop"] == "open":
        period = mix["cycle_s"]

        def at(m):  # arrival m of the endless periodic schedule
            return (offsets[m % n] - offsets[first]) + (m // n) * period

        lo = first
        while at(lo - 1) >= -ramp:
            lo -= 1
        hi = first
        while at(hi) < seconds:
            hi += 1
        order = range(lo, hi)
    else:
        # A closed loop paces itself, so where it starts decides which
        # requests the window holds: it always starts at the cycle's first
        # request, and the seed draws only the ids and the sampling seeds.
        total = int(mix["requests_per_s_ceiling"] * (seconds + ramp))
        order = range(total + mix["clients"])
    out = []
    for m in order:
        i = m % n
        body = {
            "prompt": [int(t) for t in rng.integers(0, vocab, int(prompts[i]))],
            "max_new_tokens": int(news[i]),
            "stream": True,
        }
        if sampled[i]:
            body.update(temperature=mix["temperature"], top_k=mix["top_k"],
                        seed=int(rng.integers(0, 2**31 - 1)))
        out.append({
            "due_s": float(at(m)) if mix["loop"] == "open" else None,
            "body": body,
            "greedy": not sampled[i],
        })
    return out
