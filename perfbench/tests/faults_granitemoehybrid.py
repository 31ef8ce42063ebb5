"""Faults of the granitemoehybrid cell, planted UNDER the serving driver as
``faults.py`` does: the entry and the driver know nothing of them. Each
breaks a piece of what per-row recurrent state asks of the serving path, or
of the family's mathematics, that a result inside a loose tolerance would
hide:

- ``state_not_reset``: a row admitted to a slot begins from the state and
  the convolution tail the slot's last request left;
- ``conv_tail_dropped``: every call's convolution begins from a zero tail,
  so a chunk's first three positions miss the previous chunk's last three
  (and every decode step misses its left context);
- ``padded_tail_advances``: a multi-token call treats every entry as a
  token, so a padded final chunk runs the state (and the tail) on past the
  row's last real token;
- ``attention_scaled_by_rsqrt_d``: ``attention_multiplier`` replaced by
  head_dim^-1/2 in the program's config.

    with faults_granitemoehybrid.planted("state_not_reset"):
        line = run.execute(ctx, bench, None, None)
"""

import contextlib

import jax.numpy as jnp

from perfbench import preset
from pytorch_distributed_tpu.models import granitemoehybrid as program

FAULTS = ("state_not_reset", "conv_tail_dropped", "padded_tail_advances",
          "attention_scaled_by_rsqrt_d")


def _patches(fault):
    """{name in models/granitemoehybrid: its broken stand-in}."""
    if fault == "state_not_reset":
        return {"_begins_sequence": lambda pos, live: jnp.zeros_like(
            live[:, 0])}
    if fault == "conv_tail_dropped":
        real = program.causal_conv
        return {"causal_conv": lambda x, tail, w, b, n: real(
            x, jnp.zeros_like(tail), w, b, n)}
    if fault == "padded_tail_advances":
        real = program._mamba

        def mamba(h, mp, cache, layer, pos, rows, live, cfg):
            if h.shape[1] > 1:
                live = jnp.ones_like(live)
            return real(h, mp, cache, layer, pos, rows, live, cfg)

        return {"_mamba": mamba}
    return {}


@contextlib.contextmanager
def planted(fault):
    """Break the program the serving driver builds; None plants nothing."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    real_of = preset.of
    patches = _patches(fault)
    saved = {name: getattr(program, name) for name in patches}

    def of(config, path):
        cfg = real_of(config, path)
        if fault == "attention_scaled_by_rsqrt_d":
            cfg = cfg.replace(attention_multiplier=cfg.head_dim ** -0.5)
        return cfg

    preset.of = of
    for name, broken in patches.items():
        setattr(program, name, broken)
    try:
        yield
    finally:
        preset.of = real_of
        for name, real in saved.items():
            setattr(program, name, real)
