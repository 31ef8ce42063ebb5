"""What GPT-2's work NEEDS, from the shapes alone: the count module of the
family ``gpt2`` (``perfbench/flops.py`` ``of`` finds it by the name a
configuration file gives under ``"reference"``).

Recomputed operations (remat) never count. ``cfg`` is a configuration
file's ``model`` block (HF GPT-2 ``config.json`` keys).
"""

from __future__ import annotations


def _dims(cfg: dict):
    e, l, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    c, h = cfg["n_positions"], cfg["n_head"]
    f = cfg.get("n_inner") or 4 * e
    return e, l, v, c, h, e // h, f


def n_params(cfg: dict) -> int:
    """Every parameter, the tied head counted once."""
    e, l, v, c, h, d, f = _dims(cfg)
    block = 2 * e + (3 * e * e + 3 * e) + (e * e + e) + 2 * e \
        + (e * f + f) + (f * e + e)
    return v * e + c * e + l * block + 2 * e


def n_params_non_embedding(cfg: dict) -> int:
    """Parameters a token passes through as a matrix multiply, the tied head
    included once (it IS a matmul), the two embedding look-ups not."""
    e, l, v, c, h, d, f = _dims(cfg)
    block = 3 * e * e + e * e + e * f + f * e
    return l * block + v * e


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """PaLM appendix B, as bench.py has counted since August: 6 N for the
    matmuls forward and backward (N = all parameters) plus 12 L E T for
    attention's scores and values (non-causal count, the convention)."""
    e, l, *_ = _dims(cfg)
    return 6.0 * n_params(cfg) + 12.0 * l * e * seq_len


def serve_flops(cfg: dict, positions) -> float:
    """Forward-only FLOPs to process tokens that sit at the given cache
    positions (each token at position p attends to p+1 keys): 2 per
    multiply-add over the matmul parameters, plus 4 E (p+1) per layer for
    scores and values."""
    e, l, *_ = _dims(cfg)
    n = n_params_non_embedding(cfg)
    total = 0.0
    for p in positions:
        total += 2.0 * n + 4.0 * l * e * (p + 1)
    return total


def serve_flops_span(cfg: dict, start: int, stop: int) -> float:
    """``serve_flops`` over positions start..stop-1, in closed form."""
    e, l, *_ = _dims(cfg)
    n = n_params_non_embedding(cfg)
    k = stop - start
    sum_p1 = (start + 1 + stop) * k / 2.0
    return 2.0 * n * k + 4.0 * l * e * sum_p1


def flash_attention_work(cfg: dict, batch: int, seq_len: int) -> dict:
    """Causal flash attention of ONE layer, forward and backward, as the
    algorithm needs it: forward 2 matmuls, backward 5 (scores again, dV, dP,
    dQ, dK), each 2 B H T T D multiply-adds' FLOPs, halved by the causal
    mask. Bytes: q, k, v read and o written forward; q, k, v, o, do read and
    dq, dk, dv written backward (bf16), row statistics in f32."""
    e, l, v, c, h, d, f = _dims(cfg)
    full = 2.0 * batch * h * seq_len * seq_len * d
    fwd_flops = 2 * full * 0.5
    bwd_flops = 5 * full * 0.5
    qkv = batch * seq_len * h * d * 2.0
    stats = batch * h * seq_len * 4.0
    return {
        "fwd": {"flops": fwd_flops, "bytes": 4 * qkv + 2 * stats},
        "bwd": {"flops": bwd_flops, "bytes": 8 * qkv + 2 * stats},
    }
