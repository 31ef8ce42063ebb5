"""Faults of the kimi_k2 cell, planted UNDER the serving driver by wrapping
its ``build`` as ``faults.py`` does: the entry and the driver know nothing of
them. Each leaves out a piece of the family's mathematics that a result
inside a loose tolerance would hide:

- ``no_shared_expert``: the shared expert's down projection zeroed in the
  weights the server holds;
- ``no_selection_bias``: the router's selection bias zeroed, so other
  experts are chosen;
- ``gates_not_scaled``: ``routed_scaling_factor`` 1 in the program's config;
- ``softmax_scale_without_m2``: ``mscale_all_dim`` 0 in the program's
  config, so the scores lose YaRN's m^2.

    with faults_kimi_k2.planted("gates_not_scaled"):
        line = run.execute(ctx, bench, None, None)
"""

import contextlib

from perfbench import preset
from perfbench.drivers import serve


def _params_with(params, path, value_of):
    """A copy of the tree with the leaf at ``path`` replaced."""
    if not path:
        return value_of(params)
    return dict(params, **{
        path[0]: _params_with(params[path[0]], path[1:], value_of)})


def _zeroed(path):
    return lambda params: _params_with(params, path, lambda leaf: leaf * 0)


PARAMS = {
    "no_shared_expert": _zeroed(("moe", "mlp", "shared", "down")),
    "no_selection_bias": _zeroed(("moe", "mlp", "bias")),
}
CONFIG = {
    "gates_not_scaled": {"routed_scaling_factor": 1.0},
    "softmax_scale_without_m2": {"rope_mscale_all_dim": 0.0},
}
FAULTS = (*PARAMS, *CONFIG)


@contextlib.contextmanager
def planted(fault):
    """Break the program the serving driver builds; None plants nothing."""
    real_build, real_of = serve.build, preset.of

    def build(ctx, params):
        if fault in PARAMS:
            params = PARAMS[fault](params)
        return real_build(ctx, params)

    def of(config, path):
        return real_of(config, path).replace(**CONFIG.get(fault, {}))

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    serve.build, preset.of = build, of
    try:
        yield
    finally:
        serve.build, preset.of = real_build, real_of
