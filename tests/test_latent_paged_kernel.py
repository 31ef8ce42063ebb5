"""ops/latent_paged_kernel.py (the latent family's decode attention, read
from the pages where they lie) against ``models/kimi_k2.attend_window`` (the
gathered window it replaces on the chip), in the Pallas interpreter on the
CPU. Tiny widths, and one case at the published 64 heads x 640 lanes with
pages of 64.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.models.kimi_k2 import attend_window
from pytorch_distributed_tpu.ops import latent_paged_kernel as lk

TINY = dict(heads=4, width=128, out_width=16, page=4, n_pages=8,
            block_pages=2, layers=3, dtype="float32", tol=1e-5)
PUBLISHED = dict(heads=64, width=640, out_width=512, page=64, n_pages=16,
                 block_pages=8, layers=2, dtype="bfloat16", tol=2e-2)
PAGE, BLOCK = TINY["page"], TINY["page"] * TINY["block_pages"]
MAX_LEN = TINY["page"] * TINY["n_pages"]
FREE = None  # a row the engine left free: depth 0, its table all scratch

# name -> (shape, rows' depths, layer, rows that share row 0's first pages)
CASES = {
    "depth_0": (TINY, [0, 5, 0, 9], 1, ()),
    "page_minus_1": (TINY, [PAGE - 1, 2 * PAGE - 1, PAGE - 1], 1, ()),
    "page_boundary": (TINY, [PAGE, 3 * PAGE, PAGE], 1, ()),
    "block_boundary": (TINY, [BLOCK - 1, BLOCK, 2 * BLOCK, 3 * BLOCK - 1], 1,
                       ()),
    "max_len_minus_1": (TINY, [MAX_LEN - 1, 1, MAX_LEN - 1], 1, ()),
    "free_rows": (TINY, [FREE, 13, FREE, FREE, 22, FREE], 1, ()),
    "shared_pages": (TINY, [21, 17, 30], 1, (1, 2)),
    "layer_0": (TINY, [6, 19], 0, ()),
    "layer_2": (TINY, [6, 19], 2, ()),
    "published_widths": (PUBLISHED, [0, 63, 64, 511, 512, 1023, FREE, 700],
                         1, (7,)),
}


def make_case(shape, depths, layer, sharing, seed=0):
    """Random pool and queries; every row's pages scattered in the pool, a
    sharing row's first whole pages those of row 0, table entries past a
    row's pages the scratch page 0 (which holds numbers too)."""
    rng = np.random.default_rng(seed)
    page, n_pages, w = shape["page"], shape["n_pages"], shape["width"]
    b = len(depths)
    pool_pages = b * n_pages + 1
    dtype = jnp.dtype(shape["dtype"])
    pool = jnp.asarray(
        rng.normal(size=(shape["layers"], pool_pages, page, w)), dtype)
    q = jnp.asarray(0.3 * rng.normal(size=(b, shape["heads"], w)), dtype)
    free = list(rng.permutation(np.arange(1, pool_pages)))
    tables = np.zeros((b, n_pages), np.int32)
    pos = np.zeros((b,), np.int32)
    for i, depth in enumerate(depths):
        if depth is FREE:
            continue
        pos[i] = depth
        need = depth // page + 1
        tables[i, :need] = [free.pop() for _ in range(need)]
        if i in sharing:
            whole = min(depth, depths[0]) // page
            tables[i, :whole] = tables[0, :whole]
    return q, pool, layer, jnp.asarray(tables), jnp.asarray(pos)


def kernel_and_gather(shape, q, pool, layer, tables, pos):
    scale = 0.5 * shape["width"] ** -0.5
    out = lk.latent_paged_decode(
        q, pool, layer, tables, pos, scale=scale,
        out_width=shape["out_width"], block_pages=shape["block_pages"],
        interpret=True,
    )
    with jax.default_matmul_precision("highest"):
        want = attend_window(
            q[:, None].astype(jnp.float32), pool.astype(jnp.float32), layer,
            tables, pos, scale,
        )[:, 0, :, :shape["out_width"]]
    assert out.shape == want.shape and out.dtype == q.dtype
    return np.asarray(out.astype(jnp.float32)), np.asarray(want)


@pytest.mark.parametrize("name", CASES)
def test_kernel_equals_the_gathered_window(name):
    shape, depths, layer, sharing = CASES[name]
    got, want = kernel_and_gather(
        shape, *make_case(shape, depths, layer, sharing))
    np.testing.assert_allclose(
        got, want, rtol=shape["tol"], atol=shape["tol"])


def test_a_second_seed_and_one_page_blocks():
    """Blocks of one page: every page boundary is a block boundary and
    the two buffers alternate on every page."""
    shape = dict(TINY, block_pages=1)
    got, want = kernel_and_gather(shape, *make_case(
        shape, [0, 3, 4, 11, 31, FREE, 16], 1, (4,), seed=5))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# Each fault is a line of the kernel's body rewritten: (the text, what it
# becomes). The cases above must tell the result from the sound kernel's.
FAULTS = {
    "mask_off_by_one": (
        "s = jnp.where(kpos <= depth, s, NEG_INF)",
        "s = jnp.where(kpos <= depth + 1, s, NEG_INF)",
    ),
    # the next block is sent to the buffer in use: the second buffer is read
    # with its block never landed (the interpreter's copies land as they
    # start, so a wait left out cannot show; a block in the wrong buffer can)
    "second_buffer_read_before_its_copy_landed": (
        "            for copy in copies(b, i + 1, 1 - slot):\n",
        "            for copy in copies(b, i + 1, slot):\n",
    ),
}


@pytest.fixture
def planted(request, monkeypatch):
    text, becomes = FAULTS[request.param]
    source = inspect.getsource(lk._latent_kernel)
    assert source.count(text) == 1, text
    scope = dict(vars(lk))
    exec(source.replace(text, becomes), scope)
    monkeypatch.setattr(lk, "_latent_kernel", scope["_latent_kernel"])
    lk._latent_call.clear_cache()  # the sound kernel's traces
    yield request.param
    lk._latent_call.clear_cache()


@pytest.mark.parametrize("planted", FAULTS, indirect=True)
def test_a_planted_fault_fails_the_comparison(planted):
    shape, depths, layer, sharing = CASES["block_boundary"]
    got, want = kernel_and_gather(
        shape, *make_case(shape, depths, layer, sharing))
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_wrapper_refuses_what_the_kernel_cannot_read():
    shape, depths, layer, sharing = CASES["layer_2"]
    q, pool, layer, tables, pos = make_case(shape, depths, layer, sharing)
    kw = dict(scale=1.0, out_width=16)
    with pytest.raises(ValueError, match="must divide"):
        lk.latent_paged_decode(
            q, pool, layer, tables, pos, block_pages=3, interpret=True, **kw)
    with pytest.raises(ValueError, match="minor axis"):
        lk.latent_paged_decode(
            q[..., :64], pool, layer, tables, pos, block_pages=2,
            interpret=True, **kw)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        lk.latent_paged_decode(
            q, pool, layer, tables, pos, block_pages=2, **kw)
