"""What the chip cannot be asked on every PR (ISSUE 21 bring-up).

``chip_smoke.py`` proves on the TPU that the trainer and the paged server
start; these pin, on the CPU, the properties that keep it honest: the
measurement entry points fail without a chip, the paged kernel never picks
interpreter mode by itself, the compile cache sits where it is told, both
Pallas kernels type-check inside ``shard_map(check_vma=True)``, and the
pjit path hands each device its own rows of the flash kernel.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_tpu.ops import flash_kernel, pallas_flash
from pytorch_distributed_tpu.ops.paged_kernel import paged_decode_attention
from pytorch_distributed_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parent.parent


ENTRY_POINTS = ("chip_smoke.py", "bench.py", "scripts/trace_smoke.py")
ARTIFACT = REPO / "chiprun_out" / "trace_smoke.json"


def _artifact_bytes():
    """The trace artifact's bytes, None where no chip call has written one."""
    return ARTIFACT.read_bytes() if ARTIFACT.exists() else None

_PLACE_CACHE = (
    f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
    "import jax\n"
    "from pytorch_distributed_tpu.utils.compile_cache import "
    "place_compile_cache\n"
    "print(place_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


@pytest.fixture(scope="module")
def child_runs(tmp_path_factory):
    """Every child process this file needs, started together (each pays a
    jax import): the three chip entry points held to the CPU, and the
    cache helper in two processes with two working directories, neither
    held to a platform nor handed a cache directory. Returns
    {name: (returncode, stdout, stderr)} and the trace artifact's bytes
    from before."""
    before = _artifact_bytes()
    on_cpu = {**os.environ, "JAX_PLATFORMS": "cpu"}
    bare = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")
    }

    def spawn(args, env, cwd=REPO):
        return subprocess.Popen(
            [sys.executable, *args], cwd=cwd, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )

    procs = {name: spawn([name], on_cpu) for name in ENTRY_POINTS}
    procs["cache@repo"] = spawn(["-c", _PLACE_CACHE], bare)
    procs["cache@elsewhere"] = spawn(
        ["-c", _PLACE_CACHE], bare, tmp_path_factory.mktemp("cwd")
    )
    runs = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=120)
        runs[name] = (proc.returncode, out, err)
    return runs, before


# -- entry points that must not run without the chip -------------------------


@pytest.mark.parametrize("name", ENTRY_POINTS)
def test_chip_entry_points_fail_on_cpu(child_runs, name):
    """Exit non-zero under JAX_PLATFORMS=cpu, print no result, and write
    no trace artifact (nor touch one a chip call brought back)."""
    runs, artifact_before = child_runs
    returncode, out, err = runs[name]
    assert returncode != 0, (out, err)
    assert '"ok"' not in out and '"metric"' not in out, out
    assert "cpu" in err, err  # says what it found instead
    assert _artifact_bytes() == artifact_before


# -- compile cache -----------------------------------------------------------


def test_compile_cache_placed_from_outside_sets_nothing(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    monkeypatch.setattr(
        jax.config, "update",
        lambda *a, **k: pytest.fail(f"jax.config.update{a} called"),
    )
    assert compile_cache.place_compile_cache() == "/somewhere/outside"


def test_compile_cache_not_placed_on_cpu_runs(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(
        jax.config, "update",
        lambda *a, **k: pytest.fail(f"jax.config.update{a} called"),
    )
    assert jax.config.jax_platforms == "cpu"  # tests/conftest.py
    assert compile_cache.place_compile_cache() is None
    assert compile_cache.cache_entry_count(None) == 0


def test_compile_cache_default_is_fixed_under_the_checkout(child_runs):
    """Unset, and not held to the CPU: <checkout>/.cache/jax_compile,
    the same from two processes in two working directories."""
    runs, _ = child_runs
    want = str(REPO / ".cache" / "jax_compile")
    for name in ("cache@repo", "cache@elsewhere"):
        returncode, out, err = runs[name]
        assert returncode == 0, err
        assert out.split() == [want, want], (out, err)


def test_cache_entry_count_ignores_atime_files(tmp_path):
    assert compile_cache.cache_entry_count(str(tmp_path / "absent")) == 0
    for name in ("jit_f-abc-cache", "jit_f-abc-atime", "jit_g-def-cache"):
        (tmp_path / name).write_bytes(b"x")
    assert compile_cache.cache_entry_count(str(tmp_path)) == 2


# -- nothing hides the device -------------------------------------------------


def _paged_inputs(b=2, h=4, hkv=2, d=16, pool=5, page=8, n_pages=2):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(pool, page, hkv * d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(pool, page, hkv * d)), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    lengths = jnp.asarray([11, 3], jnp.int32)
    return q, k, v, tables, lengths


def test_paged_kernel_never_interprets_by_itself():
    with pytest.raises(RuntimeError, match="needs a TPU.*'cpu'"):
        paged_decode_attention(*_paged_inputs())  # interpret=None


def test_initialize_distributed_does_not_swallow_a_late_call():
    """The backend is up (this test session used it), so the rendezvous
    can no longer happen — that must surface, not pass in silence."""
    from pytorch_distributed_tpu.parallel.mesh import initialize_distributed

    jax.devices()
    with pytest.raises(RuntimeError, match="before"):
        initialize_distributed()


# -- Pallas under shard_map and under GSPMD ----------------------------------


def test_pallas_kernels_trace_under_shard_map_check_vma(eight_devices):
    """pallas_call refuses an out_shape without ``vma`` inside
    shard_map(check_vma=True): every out_shape carries its operands'.
    Traced, not run — the HLO interpreter itself is not vma-typed; on the
    chip these calls lower to Mosaic."""
    mesh = Mesh(np.array(eight_devices[:4]).reshape(2, 2), ("fsdp", "tensor"))
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.normal(size=(4, 4, 128, 64)), jnp.float32)
        for _ in range(3)
    )

    def flash_grads(q, k, v):
        def loss(q, k, v):
            o, _ = flash_kernel.flash_mha(q, k, v, True, None, 128, 128, True)
            return jnp.sum(o * o)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)  # fwd AND bwd call

    spec = P("fsdp", "tensor")
    traced = jax.jit(jax.shard_map(
        flash_grads, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3,
        check_vma=True,
    )).trace(q, k, v)
    assert str(traced.jaxpr).count("pallas_call") == 2

    # TP serving: heads over "tensor", block tables replicated.
    heads = P(None, "tensor")
    pages = P(None, None, "tensor")
    traced = jax.jit(jax.shard_map(
        lambda *a: paged_decode_attention(*a, interpret=True),
        mesh=mesh, in_specs=(heads, pages, pages, P(), P()), out_specs=heads,
        check_vma=True,
    )).trace(*_paged_inputs())
    assert str(traced.jaxpr).count("pallas_call") == 1


def _find_eqns(jaxpr, name, inside=()):
    """(eqn, names of the enclosing primitives) for every ``name`` eqn."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn, inside
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    yield from _find_eqns(
                        sub, name, inside + (eqn.primitive.name,)
                    )


def test_gspmd_activation_spec(eight_devices):
    from pytorch_distributed_tpu.config import MeshConfig
    from pytorch_distributed_tpu.parallel import make_mesh

    assert pallas_flash._gspmd_activation_spec(2) is None  # no mesh at all
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        spec = pallas_flash._gspmd_activation_spec
        assert spec(4) == P(("data", "fsdp"), None, "tensor", None)
        assert spec(3) == P(("data", "fsdp"), None, None, None)  # 3 % 2
        inner = jax.shard_map(
            lambda x: x * (spec(4) is None),  # every axis manual in here
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        )
        assert float(jax.jit(inner)(jnp.ones(2)).sum()) == 2.0
    one = make_mesh(MeshConfig(), devices=eight_devices[:1])
    with jax.sharding.use_abstract_mesh(one.abstract_mesh):
        assert pallas_flash._gspmd_activation_spec(4) is None  # all size 1


def test_pjit_step_runs_the_flash_kernel_on_local_rows(
    eight_devices, monkeypatch
):
    """GSPMD cannot partition a Mosaic custom call (on >1 chip jax refuses
    to lower one outside a shard_map). The pjit step must reach the kernel
    inside a shard_map, on B/fsdp rows (the kernel forced on, interpreted,
    since this is not a TPU)."""
    from pytorch_distributed_tpu.config import (
        MeshConfig,
        TrainConfig,
        model_config,
    )
    from pytorch_distributed_tpu.models import get_model
    from pytorch_distributed_tpu.parallel import make_mesh
    from pytorch_distributed_tpu.parallel.api import make_parallel_train_step
    from pytorch_distributed_tpu.train.optim import make_optimizer
    from pytorch_distributed_tpu.train.state import init_train_state

    def interpreted(q, k, v, *, causal):
        out, _ = flash_kernel.flash_mha(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal, interpret=True,
        )
        return out.transpose(0, 2, 1, 3)

    monkeypatch.setattr(pallas_flash, "_pallas_supported", lambda t, s, d: True)
    monkeypatch.setattr(pallas_flash, "_pallas_flash", interpreted)

    batch, seq = 8, 32
    cfg = model_config("tiny").replace(
        n_ctx=seq, n_embd=32, n_head=2, n_layer=2, attention_impl="flash",
        remat="names", attn_pdrop=0.0, resid_pdrop=0.0, embd_pdrop=0.0,
    )
    mesh_cfg = MeshConfig(fsdp=4, strategy="full_shard")
    mesh = make_mesh(mesh_cfg, devices=eight_devices[:4])
    model = get_model(cfg)
    tx = make_optimizer(TrainConfig(
        global_batch_size=batch, micro_batch_size=2, num_steps=2,
    ))
    # Traced on abstract values: nothing is initialised, placed or compiled.
    state = jax.eval_shape(
        lambda key: init_train_state(model.init(key, cfg), tx),
        jax.random.key(0),
    )
    step, _ = make_parallel_train_step(model, cfg, tx, mesh, mesh_cfg, state)
    tokens = jax.ShapeDtypeStruct((1, batch, seq), jnp.int32)
    traced = step.trace(
        state, {"inputs": tokens, "targets": tokens}, jax.random.key(0)
    )
    calls = list(_find_eqns(traced.jaxpr, "pallas_call"))
    assert len(calls) == 2  # forward and fused backward, in the layer scan
    for eqn, inside in calls:
        assert "shard_map" in inside, inside
        assert eqn.invars[0].aval.shape[0] == batch // 4
