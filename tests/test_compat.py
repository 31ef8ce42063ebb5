"""utils/compat.py: each name is the jax 0.9 API it stands for.

The floor is jax 0.9 (pyproject.toml), so there is one era to test: the
monkeypatched tests pin WHICH jax call each shim makes and with what
arguments; the last one runs a real checked program.
"""

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_tpu.utils import compat


class _FakeVmaAval:
    def __init__(self, vma):
        self.vma = frozenset(vma)


# ------------------------------------------------------------- typeof/vma_of

def test_typeof_prefers_jax_typeof_when_present(monkeypatch):
    """typeof is jax.typeof, and vma_of reads its ``.vma``."""
    calls = []

    def fake_typeof(x):
        calls.append(x)
        return _FakeVmaAval({"data"})

    monkeypatch.setattr(jax, "typeof", fake_typeof, raising=False)
    t = compat.typeof(jnp.ones(()))
    assert calls and t.vma == {"data"}
    assert compat.vma_of(jnp.ones(())) == frozenset({"data"})


# ------------------------------------------------------------- pcast_varying

def test_pcast_varying_empty_axes_is_identity_everywhere():
    x = jnp.ones((2,))
    assert compat.pcast_varying(x, ()) is x


def test_pcast_varying_uses_pcast_on_new_jax(monkeypatch):
    recorded = {}

    def fake_pcast(x, axes, *, to):
        recorded.update(axes=axes, to=to)
        return x

    monkeypatch.setattr(jax.lax, "pcast", fake_pcast, raising=False)
    x = jnp.ones(())
    assert compat.pcast_varying(x, ["data", "fsdp"]) is x
    assert recorded == {"axes": ("data", "fsdp"), "to": "varying"}


# ----------------------------------------------------------------- shard_map

def test_shard_map_passes_check_vma_through_on_new_jax(monkeypatch):
    captured = {}

    def fake(f, **kwargs):
        captured.update(kwargs)
        return f

    monkeypatch.setattr(jax, "shard_map", fake)
    fn = compat.shard_map(
        lambda x: x, mesh="M", in_specs="I", out_specs="O", check_vma=True
    )
    assert callable(fn)
    assert captured == {
        "mesh": "M", "in_specs": "I", "out_specs": "O", "check_vma": True
    }


def test_shard_map_real_rig_builds_a_runnable_program(eight_devices):
    """End-to-end: compat.shard_map with check_vma=True must trace AND
    run a psum program."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(eight_devices), axis_names=("data",))
    f = compat.shard_map(
        lambda x: jax.lax.pmean(jnp.sum(x), "data"),
        mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=True,
    )
    out = jax.jit(f)(jnp.arange(8.0))
    assert out.shape == ()
    assert float(out) == pytest.approx(3.5)
