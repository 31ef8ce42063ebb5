"""Operations and bytes each piece of work NEEDS, from the shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
count. What is of one model family lives in that family's count module,
``counts/<family>.py``; here is what is of none: the way to a
configuration's count, and the roofline.
"""

from __future__ import annotations

import importlib


def of(config: dict):
    """The count module of a configuration's family, ``counts/<family>.py``,
    by the name the configuration file gives under ``"reference"``. Every
    family has ``n_params(model)``, ``train_flops_per_token(model, seq_len)``
    and ``serve_flops_span(model, start, stop)``, and what its kernels'
    readers need besides (README, "Adding things"). A family without one
    stops the run here, before set-up: a borrowed count would be the count
    of another model."""
    name = f"perfbench.counts.{config['reference']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as err:
        if err.name != name:
            raise
        raise SystemExit(
            f"perfbench: configuration {config.get('name')!r} is of the "
            f"family {config['reference']!r}, and there is no "
            f"perfbench/counts/{config['reference']}.py to count its work "
            "(see perfbench/README.md, \"Adding things\")") from None


def roofline_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = work["flops"] / peak["bf16_flops_per_s"]
    t_m = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
