"""The program's model preset, held against the configuration file.

A configuration file's ``program`` block names the program's preset, the
overrides of each path (``train_overrides``, ``serve_overrides``) and, for
each such path, the pairs to hold (``train_holds``, ``serve_holds``): field
of the program's model config -> key of the file's ``model`` block. The
pairs are data, so that a family with other keys brings a list and no code.
"""

from __future__ import annotations


def of(config: dict, path: str):
    """The program's model config for ``path`` ("train" or "serve"); exits
    non-zero where a held field differs from the configuration file: the run
    would measure another model than the file states."""
    from pytorch_distributed_tpu.config import model_config

    prog, model = config["program"], config["model"]
    cfg = model_config(prog["preset"], **prog[f"{path}_overrides"])
    for field, key in prog[f"{path}_holds"].items():
        if getattr(cfg, field) != model[key]:
            raise SystemExit(
                f"perfbench: preset {prog['preset']!r} has {field}="
                f"{getattr(cfg, field)} on the {path} path, the configuration "
                f"file says {key}={model[key]}")
    return cfg
