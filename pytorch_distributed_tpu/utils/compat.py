"""The varying-manual-axes (vma) shard_map surface, under the names the
codebase imports.

The project's floor is jax 0.9 (pyproject.toml), where the typed shard_map
is the only one: ``jax.typeof`` exposes ``aval.vma``, ``jax.lax.pcast``
marks a value varying, and ``jax.shard_map(..., check_vma=True)`` verifies
replication invariants at trace time. Each name here is a straight call to
that API; what they add is ``vma_of`` and the empty-axes identity of
``pcast_varying``.
"""

from __future__ import annotations

import jax


def typeof(x):
    return jax.typeof(x)


def vma_of(x) -> frozenset:
    """Mesh axes ``x`` is typed varying over."""
    return frozenset(typeof(x).vma)


def pcast_varying(x, axes):
    """Cast ``x`` varying over ``axes`` (identity when empty)."""
    axes = tuple(axes)
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_vma,
    )
