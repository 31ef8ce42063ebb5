"""What the granitemoehybrid family's work NEEDS, from the shapes alone: the
count module of the family ``granitemoehybrid`` (``perfbench/flops.py``
``of`` finds it by the name a configuration file gives under
``"reference"``).

``cfg`` is a configuration file's ``model`` block (the keys of
granite-4.0-h-micro's ``config.json``): ``layer_types`` says which layers
are Mamba-2 mixers and which attention; every layer is followed by one
SwiGLU of ``shared_intermediate_size`` (``num_local_experts`` is 0 where
this family is served); the head is the embedding, tied. Imports nothing of
the program.

At the published sizes (pinned by a test): a Mamba layer 76.18M, an
attention layer 60.82M, the embedding 205.5M, 3,191M in all = 6.38 GB in
bfloat16; per ROW 75.5 MB of float32 state + 0.94 MB of convolution tail;
per POSITION 8,192 B of K and V over the four attention layers.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hm, dh = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return dict(
        e=e, v=cfg["vocab_size"], h=h, hkv=cfg["num_key_value_heads"],
        d=e // h, f=cfg["shared_intermediate_size"], hm=hm, dh=dh,
        n=cfg["mamba_d_state"], di=hm * dh, cw=hm * dh + 2 * gn,
        k=cfg["mamba_d_conv"],
        lm=cfg["layer_types"].count("mamba"),
        la=cfg["layer_types"].count("attention"))


def mlp_params(cfg: dict) -> int:
    """A layer's SwiGLU (gate and up in one matrix, down) and its norm."""
    d = _dims(cfg)
    return 3 * d["e"] * d["f"] + d["e"]


def mamba_mixer_params(cfg: dict) -> int:
    """in-proj (z | xBC | dt), out-proj, and the small ones: the
    convolution's taps and bias, dt_bias, A_log, D, the gated norm, the
    layer's first norm."""
    d = _dims(cfg)
    return (d["e"] * (d["di"] + d["cw"] + d["hm"]) + d["di"] * d["e"]
            + (d["k"] + 1) * d["cw"] + 3 * d["hm"] + d["di"] + d["e"])


def attention_mixer_params(cfg: dict) -> int:
    d = _dims(cfg)
    return (2 * d["e"] * d["h"] * d["d"] + 2 * d["e"] * d["hkv"] * d["d"]
            + d["e"])


def n_params(cfg: dict) -> int:
    """Every parameter: the embedding (the head is tied to it), the final
    norm, and the layers of both kinds."""
    d = _dims(cfg)
    return (d["v"] * d["e"] + d["e"]
            + d["lm"] * (mamba_mixer_params(cfg) + mlp_params(cfg))
            + d["la"] * (attention_mixer_params(cfg) + mlp_params(cfg)))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the granitemoehybrid family has no training path in this "
        "benchmark: the chunked scan's backward is not built, and at 16 "
        "bytes a parameter the model whole is 51 GB")


def _per_token_macs(cfg: dict) -> tuple[float, float]:
    """(multiply-adds a token needs whatever its depth, multiply-adds per
    cache position it attends), summed over the layers."""
    d = _dims(cfg)
    e = d["e"]
    mamba = (e * (d["di"] + d["cw"] + d["hm"]) + d["di"] * e
             # the recurrence: decay, dt x (x) B into the state, S C out
             + 3 * d["hm"] * d["dh"] * d["n"])
    attn = 2 * e * d["h"] * d["d"] + 2 * e * d["hkv"] * d["d"]
    mlp = 3 * e * d["f"]
    fixed = d["lm"] * (mamba + mlp) + d["la"] * (attn + mlp) + e * d["v"]
    per_depth = d["la"] * 2 * d["h"] * d["d"]  # scores, weighted values
    return float(fixed), float(per_depth)


def serve_flops_span(cfg: dict, start: int, stop: int) -> float:
    """Forward FLOPs the tokens at cache positions start..stop-1 NEED (a
    token at position p attends p+1 positions in the attention layers; a
    Mamba layer costs the same at every depth), 2 per multiply-add."""
    fixed, per_depth = _per_token_macs(cfg)
    n = stop - start
    sum_depth = (start + 1 + stop) * n / 2.0
    return 2.0 * fixed * n + 2.0 * per_depth * sum_depth


# -- bytes a decode dispatch needs (decode_roofline's reader) -----------------


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What every decode dispatch reads: all weights once (the tied
    embedding as the head)."""
    return itemsize * n_params(cfg)


def state_bytes_per_row(cfg: dict, itemsize: int = 2) -> int:
    """What one row holds whatever its depth: the float32 state and the
    convolution's tail (in the activations' dtype) of every Mamba layer."""
    d = _dims(cfg)
    return d["lm"] * (4 * d["hm"] * d["dh"] * d["n"]
                      + itemsize * (d["k"] - 1) * d["cw"])


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one cache position over the attention layers."""
    d = _dims(cfg)
    return d["la"] * 2 * d["hkv"] * d["d"] * itemsize


def decode_bytes_needed(cfg: dict, rows_advanced: float, positions: float,
                        itemsize: int = 2) -> float:
    """Bytes one decode dispatch needs: all weights once, each advanced
    row's state read and written, K and V of every position its rows
    attend (``positions``, summed over the rows)."""
    return (weight_bytes(cfg, itemsize)
            + 2 * state_bytes_per_row(cfg, itemsize) * rows_advanced
            + kv_bytes_per_position(cfg, itemsize) * positions)
