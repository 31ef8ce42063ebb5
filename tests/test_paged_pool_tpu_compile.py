"""The gpt2-large serving cell's two programs, compiled at the published
widths for a described TPU v5e (no chip is attached, nothing runs): the decode
step over the cell's 16 rows and the prefill of one chunk of one row, as
``PagedBatchedDecodeEngine`` builds them from ``traffic/batch-backlog.json``'s
``engine`` block. The dense families' paged pool is stored
``[L, P, page, Hkv*D]``: whole lanes on the minor axis, so the runtime keeps it
row-major and no program converts it at entry and exit. Stored
``[L, P, page, Hkv, D]`` with D = 64 it was kept page-axis-minor and each
program held four whole-pool copies and a 7.26 GB temporary (PERF.md section
6, PR 31): every case here fails on that tree.

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
from pytorch_distributed_tpu.serving.engine import PagedBatchedDecodeEngine

# the cell's own engine arguments
ENGINE = json.loads((Path(__file__).resolve().parents[1] / "perfbench"
                     / "traffic" / "batch-backlog.json").read_text())["engine"]


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
def compiled(one_chip):
    """kind -> (compiled program, abstract pool) on the described chip."""
    cfg = model_config(
        "gpt2-large", dtype="bfloat16", param_dtype="bfloat16")
    eng = PagedBatchedDecodeEngine(cfg, **ENGINE)

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = abstract(jax.eval_shape(
        lambda: get_model(cfg).init(jax.random.key(0), cfg)))
    pool = abstract(jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, eng.pool_pages, eng.page_size)))
    out = {}
    # no persistent cache: an entry written by a compile-only client cannot
    # be read back, and warns
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for kind in ("decode_step", "prefill"):
            args = [abstract(a) for a in jax.eval_shape(
                lambda: eng.example_args(kind, None, group=1, cache=0))[1:]]
            args[eng.CACHE_ARGNUM[kind] - 1] = pool
            out[kind] = eng.program(kind).lower(params, *args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    return out, pool


def _elements(leaf):
    return int(np.prod(leaf.shape))


@pytest.mark.parametrize("kind", ["decode_step", "prefill"])
def test_no_copy_of_a_whole_leaf(kind, compiled):
    programs, pool = compiled
    least = min(_elements(leaf) for leaf in pool.values())
    copies = [
        shape for shape in re.findall(
            r"= \w+\[([\d,]+)\][^ ]* copy\(", programs[kind].as_text())
        if np.prod([int(d) for d in shape.split(",")]) >= least
    ]
    assert not copies, copies


@pytest.mark.parametrize("kind", ["decode_step", "prefill"])
def test_pool_is_updated_where_it_lies(kind, compiled):
    programs, pool = compiled
    memory = programs[kind].memory_analysis()
    pool_bytes = sum(
        _elements(leaf) * leaf.dtype.itemsize for leaf in pool.values())
    assert memory.alias_size_in_bytes >= pool_bytes
    # the gathered [16, 1024, 1280] windows of a layer and the logits; with
    # the pool converted at entry and exit this read 7.26 GB
    assert memory.temp_size_in_bytes < 1e9, memory.temp_size_in_bytes


def test_pool_leaves_merge_the_heads_into_whole_lanes(compiled):
    _, pool = compiled
    assert set(pool) == {"k", "v"}
    for leaf in pool.values():
        # 36 layers, 16 rows x 64 pages + the scratch page, 20 heads of 64
        assert leaf.shape == (36, 1025, 16, 1280)
        assert leaf.dtype == jnp.bfloat16
        assert leaf.shape[-1] % 128 == 0
