"""Mixture-of-Experts MLP with expert parallelism (top-1 Switch / top-k).

Beyond-reference capability (the reference MLP is dense, my_gpt2.py:80-99):
the block's MLP is replaced by n_experts expert MLPs and a learned router.

Routing:
- ``top_k=1`` (default): Switch semantics — each token goes to its argmax
  expert, gated by that expert's router probability.
- ``top_k>1``: GShard-style — each token goes to its k highest-probability
  experts; the selected probabilities are renormalised to sum to 1.

Capacity: per-expert token slots C = ceil(T * factor / X); assignments past
capacity are dropped (their MLP contribution is zero — the residual stream
carries the token unchanged). Assignment priority is token order, then
choice rank — identical between both dispatch implementations below.

Two dispatch implementations behind ``dispatch_impl``:

- ``"einsum"`` — the Mesh-TensorFlow/Switch one-hot formulation: a
  [A, X, C] f32 dispatch tensor (A = T*top_k assignments) drives a pair of
  einsums. MXU-friendly and exactly differentiable, but the dispatch
  tensor is O(T·X·C) — the textbook-unscalable form (T=8192, X=64, C=160
  would be 3.4 GB per layer per microbatch).
- ``"sort"`` — scalable path: assignments are stably sorted by expert id,
  position-in-expert comes from a bincount/segment arithmetic, and tokens
  move through 1-D gathers/scatters into the SAME [X, C, D] expert-batch
  layout. Memory O(A·D + X·C·D); no [A, X, C] tensor ever exists. XLA
  sorts/gathers compile to fast TPU kernels, and the expert compute is the
  same pair of batched matmuls.
- ``"auto"`` picks einsum while the dispatch tensor stays small (exact
  parity path at test scale), sort beyond ``_AUTO_EINSUM_LIMIT`` elements.

Equivalence of the two is pinned by tests/test_moe.py (same routing, same
drops, same outputs within fp tolerance).

Expert parallelism (``expert_axis`` inside shard_map): expert weights are
sharded over the axis, tokens are sharded over it too (it acts as a data
axis for non-expert parameters), and two ``all_to_all`` collectives move
token slots to their expert's owner and back:

  [X, C_local, D] --all_to_all--> [X/n, n*C_local, D]   (dispatch)
  expert compute on local experts
  [X/n, n*C_local, D] --all_to_all--> [X, C_local, D]   (return)

Capacity semantics under EP are per-shard (each shard may send up to
C_local tokens to each expert), so a generous capacity_factor reproduces
the single-device result exactly — pinned by tests/test_moe.py.

Deterministic routing (no jitter noise). The Switch load-balancing
auxiliary loss (computed from FIRST-choice assignment fractions, which for
top_k=1 is exactly the Switch term) is returned alongside the output and
both trainer paths add ``moe_aux_coef * aux`` to the objective; under EP it
is computed per token-shard and averaged (the standard distributed
convention — differs from the global-batch product only at O(1e-4) on
balanced batches).

``moe_dropless`` (the kimi_k2 and mellum families' layer, serving): no
capacity and no drop. The router scores ALL experts by the family's rule
(``route``): a sigmoid, ``top_k`` picked by score + selection bias, gates the
picked scores renormalised and scaled (kimi_k2, beside a shared expert every
token passes through); or a softmax over all experts, its ``top_k`` largest
renormalised, and no shared expert (mellum). The layer is told which experts it HOLDS (``expert_offset`` .. +
the expert stacks' leading size), computes those experts' part for the
pairs (token, expert) routed to them, and leaves the absent experts' part
out — what an expert-parallel chip computes before the exchange. The pairs
are sorted by expert (stably, as the sort path's are) and run through
``_expert_compute`` in blocks of rows that each belong to ONE expert, as
many blocks as the routing needs (a loop with a traced trip count: static
shapes, work in proportion to the pairs, an expert nobody chose is never
read); every pair's product lands in its own row of a [T * top_k, D] buffer
and a token sums its own rows in rank order, so a row's result depends on
no other row.
The expert stacks may come WHOLE, with the layer's index beside them
(``layer``): the block loop then slices (layer, expert) where the product
reads it. A layer's slice handed to the loop instead is copied whole on its
way in, hit or not (1 GB a layer at kimi-k2.5-ep32, 10 ms a dispatch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# "auto" switches einsum -> sort once the [A, X, C] dispatch tensor would
# exceed this many elements (64 MiB of f32).
_AUTO_EINSUM_LIMIT = 16 * 1024 * 1024


def expert_capacity(
    tokens: int, n_experts: int, capacity_factor: float
) -> int:
    """Per-expert token slots: ceil(tokens/experts * factor), min 1."""
    return max(1, int(tokens * capacity_factor / n_experts + 0.999999))


def _route(xt: jax.Array, router: jax.Array, top_k: int):
    """Router forward: returns (expert_idx [T,K], gates [T,K], probs [T,X]).

    f32 softmax for stability. top_k=1 keeps Switch gating (raw prob);
    top_k>1 renormalises the selected probs (GShard).
    """
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)  # [T, X]
    probs = jax.nn.softmax(logits, axis=-1)
    if top_k == 1:
        idx = jnp.argmax(probs, axis=-1)[:, None]  # [T, 1]
        gates = jnp.take_along_axis(probs, idx, axis=-1)  # [T, 1]
    else:
        gates, idx = jax.lax.top_k(probs, top_k)  # [T, K]
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx, gates, probs


def _expert_compute(expert_in, params, activation, expert_axis,
                    tensor_axis=None):
    """[X, C, D] expert batches -> [X, C, D] outputs, with the EP
    all_to_all pair when expert_axis is set. Dense experts:
    act(x @ w_in) @ w_out; gated (SwiGLU) experts with "w_gate":
    (act(x @ w_gate) * (x @ w_in)) @ w_out.

    ``tensor_axis``: Megatron TP INSIDE each expert (EP x TP, the standard
    large-MoE placement): w_in/w_gate are column-parallel on their hidden
    dim F, w_out row-parallel on F, so each tensor shard computes its F/tp
    slice and ONE psum (tp_reduce) after w_out restores the full [X, C, D]
    output — the same f/g conjugate pair the dense blocks use (ops/tp.py).
    The router and dispatch run on replicated activations, so routing is
    identical across tensor shards."""
    if expert_axis is not None:
        # Send each expert's slots to its owning shard; slots from all
        # shards concatenate along the capacity dim.
        expert_in = jax.lax.all_to_all(
            expert_in, expert_axis, split_axis=0, concat_axis=1, tiled=True
        )  # [X/n, n*C, D]
    if tensor_axis is not None:
        from pytorch_distributed_tpu.ops.tp import tp_copy

        expert_in = tp_copy(expert_in, tensor_axis)
    h = jnp.einsum(
        "xcd,xdf->xcf", expert_in, params["w_in"].astype(expert_in.dtype)
    )
    if "w_gate" in params:
        g = jnp.einsum(
            "xcd,xdf->xcf", expert_in,
            params["w_gate"].astype(expert_in.dtype),
        )
        h = activation(g) * h
    else:
        h = activation(h)
    expert_out = jnp.einsum(
        "xcf,xfd->xcd", h, params["w_out"].astype(h.dtype)
    )
    if tensor_axis is not None:
        from pytorch_distributed_tpu.ops.tp import tp_reduce

        expert_out = tp_reduce(expert_out, tensor_axis)
    if expert_axis is not None:
        expert_out = jax.lax.all_to_all(
            expert_out, expert_axis, split_axis=1, concat_axis=0, tiled=True
        )  # back to [X, C, D]
    return expert_out


def _assignment_positions(e_flat: jax.Array, n_experts: int):
    """Position of each assignment within its expert's queue (0-based),
    priority = assignment order. Returns positions WITHOUT materialising
    a [A, X] cumsum when used by the sort path's caller.

    Sort-free formulation used by the einsum path would be the one-hot
    cumsum; here we compute it via stable sort + segment arithmetic so
    both paths share identical priority semantics."""
    a = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)  # assignment order preserved
    e_sorted = e_flat[order]
    counts = jnp.bincount(e_flat, length=n_experts)  # [X]
    starts = jnp.cumsum(counts) - counts  # exclusive cumsum
    pos_sorted = jnp.arange(a) - starts[e_sorted]
    # Scatter positions back to assignment order.
    pos = jnp.zeros((a,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    return pos, order, e_sorted, pos_sorted


def _dispatch_einsum(
    xt, expert_idx, gates, n_experts, cap, params, activation, expert_axis,
    out_dtype, tensor_axis=None,
):
    """One-hot einsum dispatch (exact-parity / teaching path)."""
    t, k = expert_idx.shape
    a = t * k
    e_flat = expert_idx.reshape(a)
    pos, _, _, _ = _assignment_positions(e_flat, n_experts)
    keep = (pos < cap).astype(jnp.float32)

    onehot_e = jax.nn.one_hot(e_flat, n_experts, dtype=jnp.float32)
    onehot_c = jax.nn.one_hot(pos, cap, dtype=jnp.float32)
    # [A, X, C]: the textbook dispatch tensor.
    dispatch_a = (onehot_e * keep[:, None])[:, :, None] * onehot_c[:, None, :]
    dispatch = dispatch_a.reshape(t, k, n_experts, cap).sum(axis=1)
    combine = (
        dispatch_a * gates.reshape(a)[:, None, None]
    ).reshape(t, k, n_experts, cap).sum(axis=1)

    expert_in = jnp.einsum(
        "txc,td->xcd", dispatch, xt.astype(jnp.float32)
    ).astype(out_dtype)  # [X, C, D]
    expert_out = _expert_compute(
        expert_in, params, activation, expert_axis, tensor_axis
    )
    out = jnp.einsum("txc,xcd->td", combine, expert_out.astype(jnp.float32))
    return out


def _dispatch_sort(
    xt, expert_idx, gates, n_experts, cap, params, activation, expert_axis,
    out_dtype, tensor_axis=None,
):
    """Sort/segment dispatch: no [A, X, C] tensor, same semantics."""
    t, k = expert_idx.shape
    a = t * k
    d = xt.shape[-1]
    e_flat = expert_idx.reshape(a)
    tok_flat = jnp.repeat(jnp.arange(t), k)  # token of each assignment
    gate_flat = gates.reshape(a).astype(jnp.float32)

    _, order, e_sorted, pos_sorted = _assignment_positions(e_flat, n_experts)
    keep_sorted = pos_sorted < cap
    slot_sorted = e_sorted * cap + pos_sorted.astype(jnp.int32)  # [A]
    tok_sorted = tok_flat[order]
    gate_sorted = gate_flat[order]

    # Scatter kept assignments' token vectors into expert batches. Each
    # kept (expert, pos) pair is unique -> plain set; dropped assignments
    # get an out-of-range index and mode="drop" discards them.
    slot_or_oob = jnp.where(keep_sorted, slot_sorted, n_experts * cap)
    expert_in = (
        jnp.zeros((n_experts * cap, d), out_dtype)
        .at[slot_or_oob]
        .set(xt[tok_sorted].astype(out_dtype), mode="drop")
        .reshape(n_experts, cap, d)
    )

    expert_out = _expert_compute(
        expert_in, params, activation, expert_axis, tensor_axis
    )

    # Combine: each assignment gathers its slot's output, scaled by its
    # gate (0 for dropped), and segment-sums into its token.
    vals = expert_out.reshape(n_experts * cap, d).astype(jnp.float32)[
        jnp.minimum(slot_sorted, n_experts * cap - 1)
    ]
    weight = jnp.where(keep_sorted, gate_sorted, 0.0)[:, None]
    out = (
        jnp.zeros((t, d), jnp.float32).at[tok_sorted].add(vals * weight)
    )
    return out


def moe_mlp(
    x: jax.Array,  # [B, T, D]
    params: dict,  # router [D, X]; w_in [X, D, F]; w_out [X, F, D];
    #               optional w_gate [X, D, F] (SwiGLU experts)
    *,
    activation,
    capacity_factor: float = 1.25,
    expert_axis: str | None = None,
    tensor_axis: str | None = None,
    top_k: int = 1,
    dispatch_impl: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Returns (output [B, T, D], aux_loss scalar).

    aux_loss is the Switch load-balancing term: X * sum_e(fraction_e *
    mean_prob_e) over FIRST-choice assignments, minimised (=1) by uniform
    routing.
    """
    b, t, d = x.shape
    n_tokens = b * t
    xt = x.reshape(n_tokens, d)
    n_experts = params["router"].shape[-1]
    if not (1 <= top_k <= n_experts):
        raise ValueError(f"top_k={top_k} out of range for {n_experts} experts")

    expert_idx, gates, probs = _route(xt, params["router"], top_k)

    # Capacity scales with the ASSIGNMENT count (GShard/t5x convention):
    # top-k routing produces k*T assignments, so per-expert slots must be
    # ceil(k*T*cf/X) or a perfectly balanced top-2 router would drop ~40%
    # of second choices at the default capacity factor.
    cap = expert_capacity(n_tokens * top_k, n_experts, capacity_factor)

    # Switch aux loss on first choices.
    first_onehot = jax.nn.one_hot(
        expert_idx[:, 0], n_experts, dtype=jnp.float32
    )
    fraction = jnp.mean(first_onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = n_experts * jnp.sum(fraction * mean_prob)

    if dispatch_impl == "auto":
        a = n_tokens * top_k
        dispatch_impl = (
            "einsum" if a * n_experts * cap <= _AUTO_EINSUM_LIMIT else "sort"
        )
    if dispatch_impl == "einsum":
        out = _dispatch_einsum(
            xt, expert_idx, gates, n_experts, cap, params, activation,
            expert_axis, x.dtype, tensor_axis,
        )
    elif dispatch_impl == "sort":
        out = _dispatch_sort(
            xt, expert_idx, gates, n_experts, cap, params, activation,
            expert_axis, x.dtype, tensor_axis,
        )
    else:
        raise ValueError(f"unknown dispatch_impl {dispatch_impl!r}")
    return out.astype(x.dtype).reshape(b, t, d), aux_loss


def _route_sigmoid(xt, router, bias, top_k: int, routed_scale: float):
    """(expert_idx [T, K], gates [T, K] f32). In float32, as published:
    scores z = sigmoid(x @ router); the K largest of z + bias are chosen
    (the bias moves the choice only); gates are the chosen z over their
    sum (+1e-20), times ``routed_scale``."""
    z = jax.nn.sigmoid(
        xt.astype(jnp.float32) @ router.astype(jnp.float32)
    )  # [T, X]
    _, idx = jax.lax.top_k(z + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(z, idx, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, gates * routed_scale


def _route_softmax(xt, router, top_k: int, renormalise: bool):
    """(expert_idx [T, K], gates [T, K] f32). In float32: p = softmax(x @
    router) over ALL experts; the K largest are chosen; with ``renormalise``
    (HF ``norm_topk_prob``) the gates are the chosen p over their sum, else
    the chosen p as they are."""
    p = jax.nn.softmax(
        xt.astype(jnp.float32) @ router.astype(jnp.float32), axis=-1
    )  # [T, X]
    chosen, idx = jax.lax.top_k(p, top_k)
    if renormalise:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, chosen


def dropless_block_rows(tokens: int, top_k: int, n_experts: int) -> int:
    """Rows of one expert block: the power of two from 8 to 128 that
    holds four times an expert's expected load of ``tokens`` tokens, so
    that an expert needs a second block only where the routing sends it
    several times its share. Up to some 240 rows a block costs a v5e what
    streaming the expert's weights costs, whatever its rows."""
    rows = 8
    while rows < 128 and rows < 4 * tokens * top_k / n_experts:
        rows *= 2
    return rows


def _experts_grouped(xt, stacks, layer, e_flat, counts, gates, rows,
                     activation):
    """The pairs sorted by expert, in blocks of ``rows`` rows of ONE expert,
    as many blocks as the routing needs. Returns (routed [T, D] f32, the
    blocks run)."""
    t, d = xt.shape
    top_k = gates.shape[1]
    a = t * top_k
    order = jnp.argsort(e_flat, stable=True)  # by expert, then token
    starts = jnp.cumsum(counts) - counts  # first sorted pair of each
    blocks = (counts + rows - 1) // rows  # blocks each expert needs
    block_ends = jnp.cumsum(blocks)

    def one_block(i, out_pairs):
        e = jnp.searchsorted(block_ends, i, side="right").astype(jnp.int32)
        first = (i - (block_ends[e] - blocks[e])) * rows  # within expert e
        lane = first + jnp.arange(rows, dtype=jnp.int32)
        valid = lane < counts[e]
        pair = order[jnp.minimum(starts[e] + lane, a - 1)]
        x_in = jnp.where(valid[:, None], xt[pair // top_k], 0)
        # (layer, expert) sliced where the product reads it
        w = {
            name: jax.lax.dynamic_slice(
                v, (layer, e, 0, 0), (1, 1) + v.shape[2:]
            )[0]
            for name, v in stacks.items()
        }
        out = _expert_compute(x_in[None], w, activation, None)[0]
        # each pair has one row of the buffer; invalid lanes fall outside
        return out_pairs.at[jnp.where(valid, pair, a)].set(
            out.astype(out_pairs.dtype), mode="drop"
        )

    out_pairs = jax.lax.fori_loop(
        0, block_ends[-1], one_block, jnp.zeros((a, d), xt.dtype)
    )
    routed = jnp.einsum(
        "tk,tkd->td", gates, out_pairs.reshape(t, top_k, d),
        preferred_element_type=jnp.float32,
    )
    return routed, block_ends[-1]


def moe_dropless(
    xt: jax.Array,  # [T, D]
    params: dict,  # router [D, X], bias [X] (sigmoid rule); w_gate, w_in
    #               [H, D, F], w_out [H, F, D] of the H experts held
    #               ([Le, H, ...] where ``layer`` is given); optionally
    #               shared {gate [D, Fs], up [D, Fs], down [Fs, D]}
    *,
    top_k: int,
    expert_offset: int,
    routed_scale: float = 1.0,
    activation,
    live: jax.Array | None = None,  # [T] bool: rows that are tokens
    layer: jax.Array | None = None,  # index into whole expert stacks
    route: str = "sigmoid",
    renormalise: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output [T, D], counts [3] int32): pairs (token, expert)
    routed to experts held here, rows the expert products ran over,
    experts held that received a token. ``route``: the routing rule,
    "sigmoid" (``_route_sigmoid``: scores + selection bias, renormalised,
    times ``routed_scale``) or "softmax" (``_route_softmax``: softmax over
    all experts, top-k, renormalised where ``renormalise``). A layer with no
    ``shared`` entry has no shared expert. See the module docstring."""
    if route not in ("sigmoid", "softmax"):
        raise ValueError(
            f"moe_dropless: route must be 'sigmoid' or 'softmax', got "
            f"{route!r}")
    t = xt.shape[0]
    stacks = {name: params[name] if layer is not None else params[name][None]
              for name in ("w_gate", "w_in", "w_out")}
    layer = jnp.asarray(0 if layer is None else layer, jnp.int32)
    held = stacks["w_in"].shape[1]

    with jax.named_scope("moe_route"):
        if route == "sigmoid":
            idx, gates = _route_sigmoid(
                xt, params["router"], params["bias"], top_k, routed_scale
            )
        else:
            idx, gates = _route_softmax(
                xt, params["router"], top_k, renormalise)
        local = idx - expert_offset
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & live[:, None]
        # pairs of absent experts form group ``held``, never computed
        e_flat = jnp.where(here, local, held).reshape(-1).astype(jnp.int32)
        counts = jnp.bincount(e_flat, length=held + 1)[:held]

    with jax.named_scope("moe_experts"):
        rows = dropless_block_rows(t, top_k, params["router"].shape[-1])
        routed, n_blocks = _experts_grouped(
            xt, stacks, layer, e_flat, counts, gates, rows, activation
        )

    shared = None
    if "shared" in params:
        with jax.named_scope("moe_shared"):
            sh = params["shared"]
            g = activation(xt @ sh["gate"].astype(xt.dtype))
            u = xt @ sh["up"].astype(xt.dtype)
            shared = (g * u) @ sh["down"].astype(xt.dtype)

    stats = jnp.stack([
        jnp.sum(here), n_blocks * rows, jnp.sum(counts > 0),
    ]).astype(jnp.int32)
    if shared is not None:
        routed = routed + shared.astype(jnp.float32)
    return routed.astype(xt.dtype), stats
