"""Faults of the mellum cell, planted UNDER the serving driver as
``faults.py`` does: the entry and the driver know nothing of them. Each
breaks a piece of what two page groups ask of the serving path, or of the
family's mathematics, that a result inside a loose tolerance would hide:

- ``window_mask_off``: the sliding layers attend every key at or before the
  query, in the chunk's attention and in the decode step's alike (what the
  pool released behind the window reads as the scratch page);
- ``full_layers_plain_rope``: the full layers rotate by the plain table,
  theta^(-2i/D), in place of YaRN's blended frequencies (``attention_factor``
  kept);
- ``attention_factor_dropped``: cos and sin of the full layers times 1 in
  place of ``attention_factor``;
- ``gates_not_renormalised``: the chosen experts' softmax probabilities as
  they are, not over their sum (``norm_topk_prob`` false);
- ``window_page_released_early``: the engine releases one page more of every
  row's window group than lies behind the window, so the oldest positions a
  query still sees read as the scratch page.

    with faults_mellum.planted("window_mask_off"):
        line = run.execute(ctx, bench, None, None)
"""

import contextlib

import jax.numpy as jnp

from perfbench import preset
from pytorch_distributed_tpu.models import decode, mellum
from pytorch_distributed_tpu.serving import block_pool

FAULTS = ("window_mask_off", "full_layers_plain_rope",
          "attention_factor_dropped", "gates_not_renormalised",
          "window_page_released_early")


def _patches(fault):
    """[(module, name, its broken stand-in)]."""
    if fault == "window_mask_off":
        blocked, cached = decode.blocked_attention, decode._cached_attention

        def no_window(real):
            return lambda *a, window=None, **kw: real(*a, window=None, **kw)

        return [(decode, "blocked_attention", no_window(blocked)),
                (decode, "_cached_attention", no_window(cached))]
    if fault == "full_layers_plain_rope":
        def plain(dim, theta, *_):
            return 1.0 / theta ** (
                jnp.arange(0, dim // 2, dtype=jnp.float32) * 2.0 / dim)

        return [(mellum, "yarn_inv_freq", plain)]
    if fault == "window_page_released_early":
        real = block_pool.first_kept_page
        return [(block_pool, "first_kept_page",
                 lambda *a: real(*a) + 1)]
    return []


@contextlib.contextmanager
def planted(fault):
    """Break the program the serving driver builds; None plants nothing."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    real_of = preset.of
    patches = _patches(fault)
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]

    def of(config, path):
        cfg = real_of(config, path)
        if fault == "attention_factor_dropped":
            cfg = cfg.replace(rope_attention_factor=1.0)
        if fault == "gates_not_renormalised":
            cfg = cfg.replace(norm_topk_prob=False)
        return cfg

    preset.of = of
    for mod, name, broken in patches:
        setattr(mod, name, broken)
    try:
        yield
    finally:
        preset.of = real_of
        for mod, name, real in saved:
            setattr(mod, name, real)
