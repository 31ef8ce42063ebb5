"""The mellum2-12b-a2.5b-l12 configuration's serving programs, compiled at
the published widths for a described TPU v5e (no chip is attached, nothing
runs): the decode step over the cell's 32 rows and the prefill of one
512-token chunk, as ``PagedBatchedDecodeEngine`` builds them for the
benchmark's cell. They must compile; fit a chip beside 10.93 GB of weights
and the two page groups (1.61 GB + 0.94 GB); update both groups' pools where
they lie (their bytes aliased, no ``copy`` of a whole pool leaf, nor of an
expert stack); hold no array with the table's 8,192 positions among their
temporaries (the chunk's queries read their keys a block at a time, the
decode step through the kernel); and the decode step's period body holds ONE
``paged_decode_attention`` call a layer kind (the three sliding layers of a
period are unrolled in the body: four calls, three windowed).

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
from pytorch_distributed_tpu.serving.engine import PagedBatchedDecodeEngine

# the cell's own engine arguments
ENGINE = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                     / "traffic" / "code-backlog.json").read_text())["engine"]
HBM = 16e9
PRESET = "mellum2-12b-a2.5b-l12"


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
    """(engine, abstract, abstract params, abstract cache) on the chip."""
    cfg = model_config(
        PRESET, dtype="bfloat16", param_dtype="bfloat16",
        n_ctx=ENGINE["max_len"])
    eng = PagedBatchedDecodeEngine(cfg, paged_attention="kernel", **ENGINE)

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = abstract(jax.eval_shape(
        lambda: get_model(cfg).init(jax.random.key(0), cfg)))
    cache = abstract(jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, eng.pool_pages, eng.page_size,
        window_pool_pages=eng.window_pool_pages)))
    return eng, abstract, params, cache


@pytest.mark.parametrize("kind", ["decode_step", "prefill"])
def test_program_compiles_for_v5e_and_updates_its_pools_in_place(
        kind, described):
    eng, abstract, params, cache = described
    # 32 rows x 128 pages + scratch; 32 rows x 25 pages + scratch
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (3, 4097, 64, 512), "v": (3, 4097, 64, 512),
        "k_w": (9, 801, 64, 512), "v_w": (9, 801, 64, 512)}
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
    # 10.93 GB weights + 1.61 GB + 0.94 GB of pages, then temporaries
    assert 13.4e9 < memory.argument_size_in_bytes < 13.6e9
    assert held < 0.95 * HBM, held
    # the pools are updated where they lie: their bytes aliased, not output
    cache_bytes = sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in cache.values())
    assert memory.alias_size_in_bytes >= cache_bytes
    leaves = {",".join(map(str, v.shape)) for v in cache.values()}
    one_expert_stack = 3 * 64 * 2304 * 896  # the full layers' smallest
    copies = []
    text = compiled.as_text()
    for shape in re.findall(r"= \w+\[([\d,]+)\][^ ]* copy\(", text):
        elements = int(np.prod([int(d) for d in shape.split(",")]))
        if shape in leaves or elements >= one_expert_stack:
            copies.append(shape)
    assert not copies, copies
    # nothing as long as a row's table: no [.., 8192, ..] array anywhere
    assert not re.findall(r"\w+\[(?:\d+,)*8192(?:,\d+)*\]", text)
    calls = re.findall(
        rf'custom-call\(.*custom_call_target="tpu_custom_call".*'
        rf'{KERNEL_NAME}', text)
    if kind == "prefill":
        assert not calls  # a chunk is many queries a row
    else:
        # the period [s s s f] unrolled in the scan's body: a call a layer,
        # the sliding ones with a fourth scalar operand (the window's first
        # key), and no second body that holds another
        assert len(calls) == 4, len(calls)
