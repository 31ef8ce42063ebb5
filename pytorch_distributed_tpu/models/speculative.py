"""Prompt-lookup speculative decoding: the host-side drafter + the
single-sequence monolithic reference loop.

Since the batched-speculation PR the SERVING implementation lives in
``serving/engine.py``: ``BatchedDecodeEngine`` /
``PagedBatchedDecodeEngine`` built with ``speculative_k=K`` draft k
tokens per row host-side (``prompt_lookup_draft`` below), verify every
row's drafts in ONE batched ``decode_spec_step`` forward with per-row
traced accept lengths (models/decode.speculative_accept), and roll back
rejected drafts by simply not advancing the row past its accepted depth
— on the paged engine that truncation confines speculative garbage to
the row's private tail page. ``scripts/generate.py --speculative``
routes through that engine path for dense configs. This module keeps:

- ``prompt_lookup_draft`` — the numpy n-gram drafter the engines call
  per row per tick (and the one place its semantics live, so the
  host and traced lookups cannot drift);
- ``generate_speculative`` — the original one-jit greedy loop, kept as
  the bit-pinned REFERENCE the engine path is equivalence-tested
  against (tests/test_speculative.py + tests/test_serving_spec.py) and
  as the MoE fallback (the batched engines reject MoE configs: expert
  capacity couples rows).

Speculative decoding amortises the per-step HBM cost of autoregressive
generation: batched-1 decode is bandwidth-bound (every step streams the
full parameter set for ONE matmul row), so verifying K draft tokens in one forward costs barely
more than generating one token, and every accepted draft is a step's
worth of weight traffic saved. The classic scheme drafts with a smaller
model; prompt-lookup drafting (the HF ``prompt_lookup_num_tokens``
technique) instead proposes the continuation of the most recent earlier
occurrence of the current n-gram — free to produce, and highly effective
on self-repetitive text (code, extraction, summarisation with quotes).

Exactness: the verifier accepts draft[j] only while every earlier draft
matched the model's own greedy choice, then appends the model's next
token itself — the output is a greedy decode of the model; draft quality
only changes speed. In float32 it is BITWISE the plain
``decode.generate`` output (``tests/test_speculative.py`` pins equality
on adversarial and repetitive inputs for both families). In reduced
precision (bf16) the 1-token and K+1-token forwards are differently
shaped programs whose logits can round near-ties differently, so the two
decodes may diverge AT a near-tie (measured on TPU; the same caveat
applies to any speculative scheme, incl. HF's) — each output is still
greedy for its own program's logits.

TPU-first mechanics (everything static-shaped inside one jit):
- the n-gram search is a vectorised compare over the fixed-size output
  buffer (a [total, ngram] gather + all-reduce, no Python scanning);
- each loop iteration runs ONE ``decode.forward`` of K+1 tokens (the
  current last token + K drafts) against the shared KV cache. The cache
  rows K+1 forward writes for rejected drafts are harmless: attention
  masks key positions > pos, and the next iteration's write at the same
  offsets overwrites them (models/decode.py cache discipline);
- acceptance folds into the ``lax.while_loop`` carry as a traced token
  count; the output buffer is updated with a masked scatter
  (``mode="drop"``), so overshoot past ``max_new_tokens`` is clipped.

The loop is greedy-only: temperature sampling needs rejection-sampling
corrections to stay distribution-exact, which is out of scope here and
rejected loudly. Single sequence (B=1): acceptance length varies per
row, which would need per-row cache offsets; batch the PROMPTS instead.

Why this REFERENCE loop keeps its KV cache jit-internal (the serving
engines donate theirs): the verify loop is a ``lax.while_loop`` whose
per-iteration forward length is K+1 and whose trip count depends on
acceptance — the cache never crosses a program boundary, so there is
nothing to donate ACROSS; splitting the loop into per-iteration
dispatches is exactly what the engine path does, paying one host
round-trip per verify step to buy continuous batching, the donated
paged pool, and the fault model. Single-sequence latency-only callers
lose nothing here; everything serving-shaped goes through the engine.
The decision is pinned where it can't rot: tests/test_speculative.py
asserts bit-equivalence against BOTH the monolithic greedy reference
and the serving engine's greedy output, and tests/test_serving_spec.py
pins the batched engine path against this loop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.models import decode


def prompt_lookup_draft(
    tokens: np.ndarray, k: int, ngram: int = 2
) -> np.ndarray:
    """Host-side prompt-lookup drafter (the HF
    ``prompt_lookup_num_tokens`` technique): find the most recent
    EARLIER occurrence of the trailing ``ngram`` of ``tokens`` and
    return up to ``k`` tokens that followed it ([<=k] int32; empty when
    no match or history is shorter than the n-gram). Shared by the
    batched serving engines (one call per greedy row per tick — numpy,
    zero model cost) and semantically identical to the traced
    ``_lookup_draft`` the monolithic reference uses: windows fully
    inside the known prefix, the trailing n-gram itself excluded, most
    recent match wins. Drafts are proposals only — the verify forward
    is the ground truth — so this function can never affect output
    tokens, only speed."""
    tokens = np.asarray(tokens, np.int32)
    n = tokens.shape[0]
    if k < 1 or n <= ngram:
        return np.zeros((0,), np.int32)
    tail = tokens[-ngram:]
    windows = np.lib.stride_tricks.sliding_window_view(tokens, ngram)
    # Candidate windows end strictly before the tail starts the match
    # position: starts 0..n-ngram-1 (the final window IS the tail).
    hits = np.nonzero(np.all(windows[:-1] == tail[None, :], axis=1))[0]
    if hits.size == 0:
        return np.zeros((0,), np.int32)
    best = int(hits[-1])  # most recent match = closest context
    return tokens[best + ngram : best + ngram + k].copy()


def _lookup_draft(out_buf, pos, *, ngram: int, draft_len: int, total: int):
    """Find the most recent earlier occurrence of the trailing ``ngram``
    of ``out_buf[0, :pos]`` and return the ``draft_len`` tokens that
    followed it ([draft_len] int32; zeros when no match).

    All shapes static: windows are gathered for every position of the
    buffer and invalid ones (beyond the generated prefix, or the trailing
    n-gram itself) are masked out.
    """
    seq = out_buf[0]  # [total]
    # The n-gram to match: seq[pos-ngram : pos] via clipped gather.
    tail_idx = pos - ngram + jnp.arange(ngram)
    tail = jnp.take(seq, tail_idx, mode="clip")  # [ngram]

    # Window i covers seq[i : i+ngram]; candidate drafts follow at
    # seq[i+ngram : i+ngram+draft_len].
    starts = jnp.arange(total)  # [total]
    win_idx = starts[:, None] + jnp.arange(ngram)[None, :]
    windows = jnp.take(seq, win_idx, mode="clip")  # [total, ngram]
    matches = jnp.all(windows == tail[None, :], axis=1)

    # Valid window: fully inside the known prefix, not the tail itself,
    # and with at least one known token after it to draft from.
    valid = (starts + ngram < pos) & (starts >= 0)
    hit = matches & valid
    # Most recent match wins (closest context). -1 = no match.
    best = jnp.max(jnp.where(hit, starts, -1))

    draft_idx = best + ngram + jnp.arange(draft_len)
    draft = jnp.take(seq, draft_idx, mode="clip")
    # Drafted positions at/after pos are unknown future — zero them so a
    # no-match or short-history draft is deterministic garbage (the
    # verifier rejects it; correctness never depends on the draft).
    known = (best >= 0) & (draft_idx < pos)
    return jnp.where(known, draft, 0).astype(jnp.int32)


# repolint: allow(jit-donation-decision) — params are the serving
# weights, reused by every speculative-decode call; the KV cache is
# deliberately jit-internal (the verify while_loop never crosses a
# program boundary — see module docstring "Why the KV cache stays
# jit-internal"), so there is no donated-cache variant to prefer.
@partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "draft_len", "ngram",
                     "max_len"),
)
def _speculative_impl(
    params, prompt, cfg, max_new_tokens, draft_len, ngram, max_len
):
    b, tp = prompt.shape
    total = tp + max_new_tokens

    cache = decode.init_cache(cfg, b, max_len)
    out = jnp.zeros((b, total), jnp.int32)
    out = jax.lax.dynamic_update_slice(out, prompt.astype(jnp.int32), (0, 0))

    # Prefill + first token (same as the plain greedy loop).
    logits, cache = decode.forward(params, prompt, cfg, cache, 0)
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    out = out.at[:, tp].set(first)
    pos = jnp.asarray(tp + 1, jnp.int32)  # tokens known so far

    def cond(carry):
        _, _, pos = carry
        return pos < total

    def body(carry):
        out, cache, pos = carry
        draft = _lookup_draft(
            out, pos, ngram=ngram, draft_len=draft_len, total=total
        )  # [K]
        last = jax.lax.dynamic_slice(out, (0, pos - 1), (b, 1))  # [1, 1]
        tokens_in = jnp.concatenate([last, draft[None, :]], axis=1)  # [1,K+1]
        logits, cache = decode.forward(
            params, tokens_in, cfg, cache, pos - 1
        )
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [1, K+1]
        # greedy[0, j] is the model's next token after tokens_in[0, j];
        # draft[j] survives iff all earlier drafts matched the model.
        match = draft == greedy[0, :draft_len]
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
        # Accepted drafts plus the model's own next token ("bonus"): the
        # new tokens are greedy[0, :n_acc+1] — for j < n_acc these equal
        # draft[j], and greedy[0, n_acc] is the correction/continuation.
        positions = pos + jnp.arange(draft_len + 1)
        keep = jnp.arange(draft_len + 1) <= n_acc
        write_pos = jnp.where(
            keep & (positions < total), positions, total  # total = dropped
        )
        out = out.at[0, write_pos].set(greedy[0], mode="drop")
        return out, cache, pos + n_acc + 1

    out, _, _ = jax.lax.while_loop(cond, body, (out, cache, pos))
    return out


def generate_speculative(
    params,
    prompt: jax.Array,  # [1, Tp] int — single sequence
    cfg: ModelConfig,
    max_new_tokens: int,
    *,
    draft_len: int = 8,
    ngram: int = 2,
) -> jax.Array:
    """Greedy generation with prompt-lookup speculative decoding.

    Returns [1, Tp + max_new_tokens] — bitwise identical to
    ``decode.generate(..., temperature=0)`` in float32; in bf16 the two
    programs may round near-tied logits differently (module docstring).
    Drafts only change speed.
    ``draft_len`` (K) is the speculation depth: each loop iteration
    verifies K drafted tokens in one K+1-token forward and commits
    between 1 and K+1 tokens. ``ngram`` is the lookup width (2 is the
    HF default; longer n-grams are more precise, match less often).
    """
    if prompt.ndim != 2 or prompt.shape[0] != 1:
        raise ValueError(
            "speculative decoding is single-sequence ([1, Tp] prompts): "
            "per-row acceptance lengths would need per-row cache offsets "
            f"(got shape {tuple(prompt.shape)})"
        )
    if draft_len < 1:
        raise ValueError(f"draft_len must be >= 1, got {draft_len}")
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if max_new_tokens == 0:
        return prompt.astype(jnp.int32)
    tp = prompt.shape[1]
    total = tp + max_new_tokens
    # The verify forward may write up to draft_len rows past the last
    # needed position; the cache (and position tables) must cover them.
    max_len = total + draft_len
    if max_len > cfg.n_ctx:
        raise ValueError(
            f"prompt + max_new_tokens + draft_len = {max_len} exceeds "
            f"n_ctx {cfg.n_ctx}; shorten the generation or draft_len"
        )
    return _speculative_impl(
        params, prompt, cfg, max_new_tokens, draft_len, ngram, max_len
    )
