"""What the kimi_k2 family's work NEEDS, from the shapes alone: the count
module of the family ``kimi_k2`` (``perfbench/flops.py`` ``of`` finds it by
the name a configuration file gives under ``"reference"``).

``cfg`` is a configuration file's ``model`` block (the keys of Kimi-K2.5's
``config.json``). A cut configuration counts the share it holds:
``n_routed_experts_held`` of the ``n_routed_experts`` experts of every
expert layer, ``vocab_size`` rows of the embedding and the head,
``num_hidden_layers`` layers. Imports nothing of the program.

Serving is counted by the ABSORBED form of latent attention, the cheaper of
the two at one token against a deep cache (W_kvb is folded into the query
and the output, the latent is never expanded), for prompt and generated
tokens alike: the count is of what a position needs, not of how a program
chose to compute it.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    held = cfg.get("n_routed_experts_held", cfg["n_routed_experts"])
    return dict(
        e=cfg["hidden_size"], l=cfg["num_hidden_layers"],
        v=cfg["vocab_size"], h=cfg["num_attention_heads"],
        rq=cfg["q_lora_rank"], c=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], f=cfg["intermediate_size"],
        fm=cfg["moe_intermediate_size"], ns=cfg["n_shared_experts"],
        x=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"], held=held,
        ld=cfg["first_k_dense_replace"])


def attention_params(cfg: dict) -> int:
    """One layer's attention matrices and its three norms' scales."""
    d = _dims(cfg)
    e, h = d["e"], d["h"]
    return (e * d["rq"] + d["rq"] * h * (d["dn"] + d["dr"])
            + e * (d["c"] + d["dr"]) + d["c"] * h * (d["dn"] + d["dv"])
            + h * d["dv"] * e + d["rq"] + d["c"] + e)


def routed_expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    d = _dims(cfg)
    return 3 * d["e"] * d["fm"]


def expert_layer_params_outside_experts(cfg: dict) -> int:
    """An expert layer without its routed experts: attention, the shared
    expert(s), the router with its selection bias, the second norm."""
    d = _dims(cfg)
    return (attention_params(cfg) + 3 * d["e"] * d["fm"] * d["ns"]
            + d["e"] * d["x"] + d["x"] + d["e"])


def dense_layer_params(cfg: dict) -> int:
    d = _dims(cfg)
    return attention_params(cfg) + 3 * d["e"] * d["f"] + d["e"]


def n_params(cfg: dict) -> int:
    """Every parameter held here: the embedding and the untied head over
    the vocabulary's slice, the final norm, the dense layers, and the expert
    layers with the experts held."""
    d = _dims(cfg)
    expert_layers = d["l"] - d["ld"]
    return (2 * d["v"] * d["e"] + d["e"] + d["ld"] * dense_layer_params(cfg)
            + expert_layers * (expert_layer_params_outside_experts(cfg)
                               + d["held"] * routed_expert_params(cfg)))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the kimi_k2 family has no training path in this benchmark: at 16 "
        "bytes a parameter no cut inside the guide's floors fits one chip")


def _per_token_macs(cfg: dict) -> tuple[float, float]:
    """(multiply-adds a token needs whatever its depth, multiply-adds per
    cache position it attends), summed over the layers held."""
    d = _dims(cfg)
    e, h, c = d["e"], d["h"], d["c"]
    attn = (e * d["rq"] + d["rq"] * h * (d["dn"] + d["dr"])
            + e * (c + d["dr"])       # the latent and the shared rope key
            + h * d["dn"] * c         # q_n W_uk^T: the query into the latent
            + h * c * d["dv"]         # (P c_kv) W_uv
            + h * d["dv"] * e)        # W_o
    per_depth = h * (c + d["dr"]) + h * c  # scores, weighted latent
    expert_layers = d["l"] - d["ld"]
    dense = 3 * e * d["f"]
    # the expectation over a router that spreads evenly: k of x experts a
    # token, held / x of them here (a reader counts the real share)
    routed = d["k"] * d["held"] / d["x"] * routed_expert_params(cfg)
    shared = 3 * e * d["fm"] * d["ns"]
    router = e * d["x"]
    fixed = (d["l"] * attn + d["ld"] * dense
             + expert_layers * (routed + shared + router) + e * d["v"])
    return float(fixed), float(d["l"] * per_depth)


def serve_flops_span(cfg: dict, start: int, stop: int) -> float:
    """Forward FLOPs the tokens at cache positions start..stop-1 NEED (a
    token at position p attends p+1 positions), 2 per multiply-add."""
    fixed, per_depth = _per_token_macs(cfg)
    n = stop - start
    sum_depth = (start + 1 + stop) * n / 2.0
    return 2.0 * fixed * n + 2.0 * per_depth * sum_depth


# -- bytes a decode dispatch needs (decode_roofline's reader) -----------------


def weight_bytes_outside_routed_experts(cfg: dict, itemsize: int = 2) -> int:
    """What every decode dispatch reads whatever the routing: all layers
    without their routed experts, the final norm and the head's slice (the
    embedding gives a row a token: not counted)."""
    d = _dims(cfg)
    expert_layers = d["l"] - d["ld"]
    return itemsize * (
        d["ld"] * dense_layer_params(cfg)
        + expert_layers * expert_layer_params_outside_experts(cfg)
        + d["e"] + d["e"] * d["v"])


def routed_expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One routed expert's weights: read once by a dispatch that sends it a
    token (88.1 MB at the published widths in bf16)."""
    return itemsize * routed_expert_params(cfg)


def latent_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """The latent one cache position holds in ONE layer: c_kv and the
    shared rope key (1152 bytes at the published widths in bf16)."""
    d = _dims(cfg)
    return itemsize * (d["c"] + d["dr"])


def decode_bytes_needed(cfg: dict, experts_hit: float, positions: float,
                        itemsize: int = 2) -> float:
    """Bytes one decode dispatch needs: the weights outside the routed
    experts, each held expert that received a token (``experts_hit``,
    summed over the expert layers), and the latent of every position its
    rows attend (``positions``, summed over the rows; every layer reads
    its own)."""
    d = _dims(cfg)
    return (weight_bytes_outside_routed_experts(cfg, itemsize)
            + routed_expert_bytes(cfg, itemsize) * experts_hit
            + latent_bytes_per_position(cfg, itemsize) * d["l"] * positions)
