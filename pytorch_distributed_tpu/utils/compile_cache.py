"""Where JAX's persistent compilation cache lives.

A cold process compiles everything it runs (minutes at real sizes), and
the cache key includes the cache directory, so the directory must be the
same on every run: placed from outside through ``JAX_COMPILATION_CACHE_DIR``
(which jax reads by itself — nothing is set in code then), or else one
fixed path under the checkout, resolved from this file and not from the
working directory.

A run held to the CPU platform gets no cache: its programs are test-sized,
and XLA:CPU logs a machine-feature error for every executable it loads
back.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = (
    Path(__file__).resolve().parents[2] / ".cache" / "jax_compile"
)


def place_compile_cache() -> str | None:
    """Make sure a persistent compile cache is in place; return its
    directory (None on a CPU-platform run, which gets none). Call before
    the first compilation."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def cache_entry_count(cache_dir: str | None) -> int:
    """Executables currently in the cache directory (0 if there is none,
    or it does not exist yet — jax creates it on the first write). jax
    keeps a ``-atime`` file beside an entry when eviction is on; those
    are not entries."""
    if cache_dir is None:
        return 0
    try:
        names = os.listdir(cache_dir)
    except FileNotFoundError:
        return 0
    return sum(not name.endswith("-atime") for name in names)
