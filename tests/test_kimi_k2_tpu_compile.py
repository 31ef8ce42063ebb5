"""The kimi-k2.5-ep32 configuration's serving programs, compiled at the
published widths for a described TPU v5e (no chip is attached, nothing runs):
the decode step over the cell's rows and the prefill of one 512-token chunk, as
``PagedBatchedDecodeEngine`` builds them for the benchmark's cell. They must
compile, fit a chip beside the weights, and hold NO whole-pool ``copy``: the
latent pool is stored with whole lanes on its minor axis so that the runtime
keeps it row-major and no program converts it at entry and exit (PERF.md
section 5: the GPT-2 pool's layout costs four whole-pool copies a dispatch).
The decode step is compiled both ways: as the chip builds it, reading the
pool through ops/latent_paged_kernel.py (one custom call in each of the two
layer scans, and no ``[32, 4096, 640]`` window among its temporaries), and
with the gathered window, the path off the chip.

One file, the topology described inside a fixture: only the worker that is
given this file loads the TPU's library (on-chip-measurement guide, 2).
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.config import model_config
from pytorch_distributed_tpu.models import decode, get_model
from pytorch_distributed_tpu.ops.latent_paged_kernel import KERNEL_NAME
from pytorch_distributed_tpu.serving.engine import PagedBatchedDecodeEngine

# the cell's own engine arguments
ENGINE = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                     / "traffic" / "agent-backlog.json").read_text())["engine"]
HBM = 16e9
# one row-major [32, 4096, 640] bf16 window: what the gather path copies the
# rows' tables into in every layer
WINDOW_BYTES = ENGINE["slots"] * ENGINE["max_len"] * 640 * 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as err:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {err}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def described(one_chip):
    """(engine by paged_attention, abstract params, abstract pool) on the
    described chip."""
    cfg = model_config(
        "kimi-k2.5-ep32", dtype="bfloat16", param_dtype="bfloat16")
    engines = {impl: PagedBatchedDecodeEngine(
        cfg, paged_attention=impl, **ENGINE) for impl in ("kernel", "gather")}
    eng = engines["gather"]

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = abstract(jax.eval_shape(
        lambda: get_model(cfg).init(jax.random.key(0), cfg)))
    pool = abstract(jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, eng.pool_pages, eng.page_size)))
    return engines, abstract, params, pool


@pytest.mark.parametrize("kind,impl", [
    ("decode_step", "kernel"), ("decode_step", "gather"),
    ("prefill", "kernel")])
def test_program_compiles_for_v5e_without_a_whole_pool_copy(
        kind, impl, described):
    engines, abstract, params, pool = described
    eng = engines[impl]
    args = [abstract(a) for a in jax.eval_shape(
        lambda: eng.example_args(kind, None, group=1, cache=0))[1:]]
    args[eng.CACHE_ARGNUM[kind] - 1] = pool
    # no persistent cache: an entry written by a compile-only client cannot
    # be read back, and warns
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = eng.program(kind).lower(params, *args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held < 0.8 * HBM, held  # 6.99 GB weights + 0.84 GB pool + temps
    leaf = pool["latent"]
    assert leaf.shape == (5, eng.pool_pages, 64, 640)
    assert leaf.dtype == jnp.bfloat16
    pool_elements = int(np.prod(leaf.shape))
    copies = []
    text = compiled.as_text()
    for shape in re.findall(r"= \w+\[([\d,]+)\][^ ]* copy\(", text):
        if int(np.prod([int(d) for d in shape.split(",")])) >= pool_elements:
            copies.append(shape)
    assert not copies, copies
    # the pool is updated where it lies: its bytes are aliased, not output
    assert memory.alias_size_in_bytes >= pool_elements * 2
    # the kernel: one custom call in the body of each layer scan (the dense
    # stack's and the expert stack's), none in the prefill program (a chunk
    # is read expanded) nor on the gather path, whose temporaries hold the
    # window the kernel's do not
    calls = len(re.findall(
        rf'custom-call\(.*custom_call_target="tpu_custom_call".*'
        rf'{KERNEL_NAME}', text))
    assert calls == (2 if (kind, impl) == ("decode_step", "kernel") else 0)
    if kind == "decode_step":
        if impl == "kernel":
            assert memory.temp_size_in_bytes < WINDOW_BYTES
        else:
            assert memory.temp_size_in_bytes > WINDOW_BYTES
