"""Selective activation checkpointing policies.

The reference wraps every transformer block in
``torch.utils.checkpoint.checkpoint`` with a *selective* policy that saves the
outputs of compute-intensive aten ops (mm/bmm/addmm/SDPA variants — reference
model/pytorch_utils.py:5-17, my_gpt2.py:145,175-183) and recomputes everything
else (layernorm/gelu/dropout) in backward.

The TPU-native equivalent is ``jax.checkpoint`` (remat) with
``checkpoint_dots``: save dot_general results, recompute elementwise ops —
the same "keep the MXU work, redo the VPU work" trade.
"""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name  # noqa: F401  (models tag with this)

# Activation names the "names" policy saves — every projection/matmul output
# in a transformer block (models/gpt2.py and models/llama.py tag these with
# ``checkpoint_name``). This is the faithful analogue of the reference's
# compute_intensive_ops list: keep the MXU outputs, recompute VPU work.
#
# Crucially, UNLIKE ``checkpoint_dots`` it does NOT save the [B, H, T, T]
# attention score matmul (a "dot" too!): with naive attention at T=1024 that
# policy stores ~400 MB of f32 scores per layer — measured as ~33 ms/step of
# pure dynamic-update-slice HBM traffic on GPT-2 124M — while recomputing
# scores from the saved qkv in backward costs one extra small matmul.
SAVED_ACTIVATION_NAMES = (
    "qkv",        # gpt2 merged projection [B, T, 3E]
    "q", "k", "v",  # llama separate projections
    "attn_out",   # attention output [B, T, H, D] (the SDPA-save analogue)
    "attn_proj",  # output projection [B, T, E] (recomputes the ln_2 input)
    "mlp_fc",     # up projection
    "mlp_gate", "mlp_up",  # llama SwiGLU branches
    # NOT saved: "mlp_proj" (the down projection). Its value feeds only the
    # residual add whose output is the next layer's scan carry — already
    # saved — so storing it is pure HBM waste (measured ~4 ms/step).
)

def _contains_pallas_call(jaxpr, depth: int = 0) -> bool:
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # unwrap ClosedJaxpr
    if not hasattr(jaxpr, "eqns") or depth > 2:
        return False
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return True
        for v in eqn.params.values():
            if hasattr(getattr(v, "jaxpr", v), "eqns") and _contains_pallas_call(
                v, depth + 1
            ):
                return True
    return False


def _flash_call_policy(prim, *_args, **params) -> bool:
    """Save all outputs of the Pallas flash-attention custom_vjp call —
    (o, lse), see ops/flash_kernel.flash_mha. With those saved (and
    q/k/v derivable from the saved qkv projection) the backward pass skips
    the forward kernel re-run entirely. Identified structurally: the only
    custom_vjp whose body is a pallas_call inside our models is flash."""
    if prim.name != "custom_vjp_call":
        return False
    return _contains_pallas_call(params.get("call_jaxpr"))


_POLICIES = {
    # Save nothing: recompute the whole block in backward.
    "full": None,
    # Save matmul/attention outputs only — the analogue of the reference's
    # compute_intensive_ops list.
    "dots": jax.checkpoint_policies.checkpoint_dots,
    # Save matmuls except those with no batch dims (slightly leaner HBM).
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    # Save exactly the tagged projection outputs (recommended: avoids saving
    # the quadratic attention-score dot that "dots" keeps) plus the flash
    # kernel's (o, lse) so backward launches only the fused gradient kernel.
    "names": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.save_only_these_names(
            *SAVED_ACTIVATION_NAMES
        ),
        _flash_call_policy,
    ),
    # Save ONLY the flash kernel's (o, lse): removes the O(T^2)
    # forward-kernel re-run from backward while keeping every linear-in-T
    # projection save OFF — the long-context policy for regimes where the
    # per-layer gate/up saves are what OOM HBM (llama3-1B T=8192 fits
    # with this or "full"; "names"/"dots" exceed the chip — measured
    # in round 5, on older code).
    "flash": _flash_call_policy,
}


def apply_remat(fn, mode: str, *, prevent_cse: bool = False, static_argnums=()):
    """Wrap ``fn`` in jax.checkpoint according to ``mode``.

    mode: "none" (identity), "full", "dots", "dots_no_batch", "names",
    "flash". prevent_cse=False is safe (and faster) under
    scan-over-layers.
    """
    if mode == "none":
        return fn
    if mode not in _POLICIES:
        raise KeyError(f"unknown remat mode {mode!r}; known: none, {sorted(_POLICIES)}")
    policy = _POLICIES[mode]
    kwargs = dict(prevent_cse=prevent_cse, static_argnums=static_argnums)
    if policy is not None:
        kwargs["policy"] = policy
    return jax.checkpoint(fn, **kwargs)
