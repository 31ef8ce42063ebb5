"""What the mellum family's work NEEDS, from the shapes alone: the count
module of the family ``mellum`` (``perfbench/flops.py`` ``of`` finds it by
the name a configuration file gives under ``"reference"``).

``cfg`` is a configuration file's ``model`` block (the keys of
Mellum2-12B-A2.5B-Instruct's ``config.json``): ``layer_types`` says which
layers attend a sliding window of ``sliding_window`` keys and which attend
everything; every layer is followed by ``num_experts`` routed experts of
``moe_intermediate_size``, ``num_experts_per_tok`` a token, no shared one
(``intermediate_size`` is used by no layer: every ``mlp_layer_types`` entry
is "sparse"); the head is untied. Imports nothing of the program.

At the published sizes (pinned by a test): a layer 417,747,456 parameters
(attention 21,233,664, router 147,456, two norms 4,608, 64 experts of
6,193,152), embedding and head 226,492,416 each; 12 layers 5,465,956,608 =
10.93 GB in bfloat16; per POSITION and LAYER 2,048 B of K and V.
"""

from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"


def _dims(cfg: dict) -> dict:
    return dict(
        e=cfg["hidden_size"], v=cfg["vocab_size"],
        h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        d=cfg["head_dim"], x=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        window=cfg["sliding_window"],
        ls=cfg["layer_types"].count(SLIDING),
        lf=cfg["layer_types"].count(FULL))


def attention_params(cfg: dict) -> int:
    """Wq, Wk, Wv, Wo (no bias)."""
    d = _dims(cfg)
    return 2 * d["e"] * d["h"] * d["d"] + 2 * d["e"] * d["hkv"] * d["d"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, in, out."""
    d = _dims(cfg)
    return 3 * d["e"] * d["f"]


def layer_params(cfg: dict) -> int:
    """Attention, the router, the two norms and every expert."""
    d = _dims(cfg)
    return (attention_params(cfg) + d["e"] * d["x"] + 2 * d["e"]
            + d["x"] * expert_params(cfg))


def n_params(cfg: dict) -> int:
    """Every parameter: the embedding, the untied head, the final norm and
    the layers."""
    d = _dims(cfg)
    return (2 * d["v"] * d["e"] + d["e"]
            + (d["ls"] + d["lf"]) * layer_params(cfg))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "the mellum family has no training path in this benchmark: the "
        "dropless expert layer has no backward, and at 16 bytes a parameter "
        "one chip holds 4 of the 28 layers of a four-chip share")


def _per_token_macs(cfg: dict) -> float:
    """Multiply-adds a token needs whatever its depth, over the layers: the
    attention's projections, the router, the experts it is routed to, the
    head."""
    d = _dims(cfg)
    layer = (attention_params(cfg) + d["e"] * d["x"]
             + d["k"] * expert_params(cfg))
    return float((d["ls"] + d["lf"]) * layer + d["e"] * d["v"])


def _keys_attended(start: int, stop: int, cap: int | None) -> float:
    """sum over positions p in start..stop-1 of the keys a query at p
    attends: p + 1, or ``cap`` where that is less."""
    n = stop - start
    if n <= 0:
        return 0.0
    if cap is None or stop <= cap:
        return (start + 1 + stop) * n / 2.0
    if start >= cap:
        return float(cap * n)
    return (start + 1 + cap) * (cap - start) / 2.0 + cap * (stop - cap)


def serve_flops_span(cfg: dict, start: int, stop: int) -> float:
    """Forward FLOPs the tokens at cache positions start..stop-1 NEED (a
    token at position p attends p + 1 keys in a full layer and at most
    ``sliding_window`` in a sliding one: scores and weighted values, 2 H D
    multiply-adds a key), 2 per multiply-add."""
    d = _dims(cfg)
    per_key = 2 * d["h"] * d["d"]
    keys = (d["lf"] * _keys_attended(start, stop, None)
            + d["ls"] * _keys_attended(start, stop, d["window"]))
    return 2.0 * _per_token_macs(cfg) * (stop - start) + 2.0 * per_key * keys


# -- bytes a decode dispatch needs (the roofline readers') -------------------


def kv_bytes_per_position_per_layer(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one cache position in one layer."""
    d = _dims(cfg)
    return 2 * d["hkv"] * d["d"] * itemsize


def paged_attention_bytes(cfg: dict, positions_full: float,
                          positions_window: float, itemsize: int = 2) -> float:
    """Bytes the decode attention of one dispatch NEEDS: K and V of the
    positions its rows attend, in every layer of each group
    (``positions_full``: sum over the rows of pos + 1; ``positions_window``:
    of min(pos + 1, sliding_window)). The queries in and the outputs out are
    a thousandth of it and are left out."""
    d = _dims(cfg)
    return kv_bytes_per_position_per_layer(cfg, itemsize) * (
        d["lf"] * positions_full + d["ls"] * positions_window)


def dense_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What every decode dispatch reads whatever the routing: each layer's
    attention, router and norms, the final norm and the head. (Of the
    embedding a dispatch reads one row a token, not the matrix.)"""
    d = _dims(cfg)
    layer = attention_params(cfg) + d["e"] * d["x"] + 2 * d["e"]
    return itemsize * (
        (d["ls"] + d["lf"]) * layer + d["e"] + d["e"] * d["v"])


def decode_bytes_needed(cfg: dict, experts_hit: float, positions_full: float,
                        positions_window: float, itemsize: int = 2) -> float:
    """Bytes one decode dispatch needs: the weights outside the experts
    once, each expert that received a token (``experts_hit``, summed over
    the layers), K and V of every position its rows attend in each group."""
    return (dense_weight_bytes(cfg, itemsize)
            + itemsize * expert_params(cfg) * experts_hit
            + paged_attention_bytes(
                cfg, positions_full, positions_window, itemsize))
