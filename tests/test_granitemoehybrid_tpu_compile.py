"""The granite-4.0-h-micro configuration's serving programs, compiled at the
published widths for a described TPU v5e (no chip is attached, nothing runs):
the decode step over the cell's rows and the prefill of one 256-token chunk
(one SSD block with a carried-in state), as ``PagedBatchedDecodeEngine``
builds them for the benchmark's cell. They must compile; fit a chip beside
the weights; update the state leaves and the pool where they lie (their
bytes aliased, no ``copy`` of a whole ``ssm``, ``conv``, ``k`` or ``v``
leaf, nor of a weight stack: a period's slice of the stacks handed to the
layer scan was copied out whole, 1.4 GB an iteration, and an in-projection
of 8512 columns, not whole lanes, was copied into another layout, 1.25 GB a
dispatch); and the prefill program holds no per-position state
``[256, 64, 64, 128]`` among its temporaries (the chunked form never has
one). The decode step is compiled both ways: as the chip builds it, its four
attention layers reading their pages through ops/paged_kernel.py (one custom
call in the period's body, and no ``[32, 4096, 512]`` window of K or V, nor
its ``[32, 4096, 8, 64]`` relayout, anywhere in the program) and its Mamba
layers advancing their state through ops/ssm_kernel.py (nine custom calls in
the period's body, the ``ssm`` leaf aliased through each, and the rows'
state ``[32, 64, 64, 128]`` float32 nowhere among the program's values: no
slice of the leaf is taken, no second copy of it made), and with the
gathered window and the plain state step, the path off the chip.

One file, the topology described inside a fixture: only the worker that is
given this file loads the TPU's library (on-chip-measurement guide, 2).
"""

import json
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from pytorch_distributed_tpu.config import model_config
from pytorch_distributed_tpu.models import decode, get_model
from pytorch_distributed_tpu.ops.paged_kernel import KERNEL_NAME
from pytorch_distributed_tpu.ops.ssm_kernel import (
    KERNEL_NAME as STATE_KERNEL_NAME,
)
from pytorch_distributed_tpu.serving.engine import PagedBatchedDecodeEngine

# the cell's own engine arguments
ENGINE = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                     / "traffic" / "chat-backlog.json").read_text())["engine"]
HBM = 16e9
# one position's state over a whole chunk, float32: what a scan that kept
# every position's state would hold
PER_POSITION_STATE_BYTES = ENGINE["prefill_chunk"] * 64 * 64 * 128 * 4
# one [32, 4096, 512] bf16 window: what the gather path copies the rows'
# tables into, for K and again for V, in every attention layer
WINDOW_BYTES = ENGINE["slots"] * ENGINE["max_len"] * 512 * 2


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
    """(engine by paged_attention, abstract, abstract params, abstract
    cache) on the chip."""
    cfg = model_config(
        "granite-4.0-h-micro", dtype="bfloat16", param_dtype="bfloat16",
        n_ctx=ENGINE["max_len"])
    engines = {impl: PagedBatchedDecodeEngine(
        cfg, paged_attention=impl, **ENGINE) for impl in ("kernel", "gather")}
    eng = engines["gather"]

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = abstract(jax.eval_shape(
        lambda: get_model(cfg).init(jax.random.key(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, eng.pool_pages, eng.page_size, rows=eng.slots)))
    return engines, abstract, params, cache


@pytest.mark.parametrize("kind,impl", [
    ("decode_step", "kernel"), ("decode_step", "gather"),
    ("prefill", "kernel")])
def test_program_compiles_for_v5e_and_updates_its_cache_in_place(
        kind, impl, described):
    engines, abstract, params, cache = described
    eng = engines[impl]
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (4, 2049, 64, 512), "v": (4, 2049, 64, 512),
        "ssm": (36, 33, 64, 64, 128), "conv": (36, 3, 33, 4352)}
    args = [abstract(a) for a in jax.eval_shape(
        lambda: eng.example_args(kind, None, group=1, cache=0))[1:]]
    args[eng.CACHE_ARGNUM[kind] - 1] = cache
    # no persistent cache: an entry written by a compile-only client cannot
    # be read back, and warns
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = eng.program(kind).lower(params, *args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 6.38 GB weights + 2.49 GB state + 1.07 GB pool + temporaries
    assert 9.9e9 < memory.argument_size_in_bytes < 10.1e9
    assert held < 0.8 * HBM, held
    # the cache is updated where it lies: its bytes are aliased, not output
    cache_bytes = sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in cache.values())
    assert memory.alias_size_in_bytes >= cache_bytes
    leaves = {",".join(map(str, v.shape)) for v in cache.values()}
    smallest_stack = 36 * 4096 * 2048  # the Mamba layers' out-projections
    copies = []
    text = compiled.as_text()
    for shape in re.findall(r"= \w+\[([\d,]+)\][^ ]* copy\(", text):
        elements = int(np.prod([int(d) for d in shape.split(",")]))
        if shape in leaves or elements >= smallest_stack:
            copies.append(shape)
    assert not copies, copies
    # the kernel: one custom call in the period's body (its one attention
    # layer), none in the prefill program (a chunk is many queries a row)
    # nor on the gather path, which alone holds the window and its relayout
    calls = len(re.findall(
        rf'custom-call\(.*custom_call_target="tpu_custom_call".*'
        rf'{KERNEL_NAME}', text))
    windows = re.findall(r"bf16\[32,4096,(?:512|8,64)\]", text)
    # the state kernel: a custom call a Mamba layer of the period, the leaf
    # its operand 8 and its output 1; the plain step elsewhere (a prefill
    # chunk is many positions a row; the path off the chip)
    state_calls = re.findall(
        rf'custom-call\(.*custom_call_target="tpu_custom_call".*'
        rf'{STATE_KERNEL_NAME}.*', text)
    rows_state = re.findall(r"f32\[32,64,64,128\]", text)
    if kind == "prefill":
        assert calls == 0 and not state_calls
        assert memory.temp_size_in_bytes < PER_POSITION_STATE_BYTES
    elif impl == "kernel":
        assert calls == 1 and not windows
        assert len(state_calls) == 9 and not rows_state
        assert all("output_to_operand_aliasing={{1}: (8, {})}" in call
                   and "f32[36,33,64,64,128]" in call for call in state_calls)
        assert memory.temp_size_in_bytes < WINDOW_BYTES
    else:
        # the gathered window of four attention layers' K and V, nothing
        # that grows with the weights
        assert calls == 0 and windows and not state_calls
        assert WINDOW_BYTES < memory.temp_size_in_bytes < 1.0e9
