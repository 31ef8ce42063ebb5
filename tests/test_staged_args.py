"""The paged engine's one host-to-device transfer a build (PR 39).

A build of ``PagedBatchedDecodeEngine`` fills ONE int32 array ``staged``
``[rows, cols]`` with every per-row operand of its program (float32 and
uint32 operands as their bits, a bool as 0/1) and transfers it once; the
program's first lines split it back (serving/staged.py). Pinned here:

1. the round trip through the program's own split function, jitted on the
   CPU, gives back every operand bit for bit in its dtype: temperatures and
   top-p with the 2.0 "off" sentinel and values like 0.8, key words at or
   above 2**31, greedy true and false, a padded prefill group whose
   ``slot_rows`` point at the scratch row; on four engines whose layouts
   differ by what the engine observes (gpt2; granite's row state; mellum's
   two groups of block tables; a LoRA registry's tenant slots);
2. a fresh array holds what the builds staged before (PR 38) on the rows
   nothing writes, but for the greedy flag, which is set in every kind
   (a prefill group's unwritten lane is greedy too);
3. ``arg_transfers.<kind>`` counts one transfer a dispatch, for each of
   ``prefill``, ``decode_step`` and ``decode_spec_step``.

Token equality of the programs that take ``staged`` is held by the
existing paged-vs-dense and seeded-sampling tests, unchanged.
"""

import jax
import numpy as np
import pytest

from pytorch_distributed_tpu.config import ModelConfig
from pytorch_distributed_tpu.serving.adapters import AdapterRegistry
from pytorch_distributed_tpu.serving.engine import PagedBatchedDecodeEngine
from pytorch_distributed_tpu.serving.staged import Field, StagedLayout

_DROP = dict(attn_pdrop=0.0, resid_pdrop=0.0, embd_pdrop=0.0)


def _gpt2():
    return ModelConfig(
        family="gpt2", vocab_size=97, n_ctx=64, n_embd=64, n_layer=2,
        n_head=4, dtype="float32", **_DROP)


def _granite():
    period = ("mamba",) * 4 + ("attention",)
    return ModelConfig(
        family="granitemoehybrid", vocab_size=96, n_ctx=64, n_embd=32,
        n_layer=5, n_head=4, n_kv_head=2, layer_types=period,
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2,
        mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8,
        shared_intermediate_size=48, dtype="float32",
        param_dtype="float32", activation_function="silu", **_DROP)


def _mellum():
    period = ("sliding_attention",) * 3 + ("full_attention",)
    return ModelConfig(
        family="mellum", vocab_size=96, n_ctx=64, n_embd=32, n_layer=4,
        n_head=4, n_kv_head=2, attn_head_dim=16, layer_types=period,
        sliding_window=12, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=24, norm_topk_prob=True, rope_factor=4.0,
        rope_original_max_position=16, dtype="float32",
        param_dtype="float32", activation_function="silu", **_DROP)


def _engine(name: str) -> PagedBatchedDecodeEngine:
    if name == "gpt2":
        cfg, kw = _gpt2(), {}
    elif name == "gpt2-lora":
        cfg = _gpt2()
        kw = {"adapters": AdapterRegistry(cfg, rank=4, max_tenants=2)}
    else:
        cfg, kw = {"granite": _granite, "mellum": _mellum}[name](), {}
    return PagedBatchedDecodeEngine(
        cfg, slots=4, max_len=64, page_size=4, prefill_chunk=8, **kw)


# engine -> (row state, block-table groups, tenant slots)
CASES = {
    "gpt2": (False, 1, False),
    "granite": (True, 1, False),
    "mellum": (False, 2, False),
    "gpt2-lora": (False, 1, True),
}
KINDS = ("prefill", "decode_step", "decode_spec_step")


def _fill(views, rows: int, seed: int) -> dict[str, np.ndarray]:
    """Every operand written with values of its own dtype, the edges
    included; returns what was written, in each operand's dtype."""
    rng = np.random.default_rng(seed)
    for name, v in views.items():
        if name == "greedy":
            v[...] = np.arange(rows) % 2  # true and false
        elif name in ("t", "p"):
            v[...] = np.resize(np.float32([2.0, 0.8, 1.0, 1e-6]), rows)
        elif name == "keydata":
            v[...] = rng.integers(0, 2**32, v.shape, dtype=np.uint64)
            v[0] = [2**31, 2**32 - 1][: v.shape[1]]  # at and above 2**31
        else:
            v[...] = rng.integers(-2**31, 2**31, v.shape, dtype=np.int64)
    return {name: np.array(v) for name, v in views.items()}


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype.itemsize == 4 else x


@pytest.mark.parametrize("name", list(CASES))
def test_split_gives_back_every_operand_bit_for_bit(name):
    eng = _engine(name)
    row_state, groups, tenants = CASES[name]
    for kind in KINDS:
        layout = eng._layouts[kind]
        names = [f.name for f in layout.fields]
        # the layout follows what the engine observes
        assert ("slot_rows" in names) == (row_state and kind == "prefill")
        assert ("tenants" in names) == tenants
        tables = layout.views(np.zeros((1, layout.cols), np.int32))["tables"]
        assert tables.shape[1] == groups * eng.max_pages
        rows = 3 if kind == "prefill" else eng.slots
        staged = layout.new(rows)
        wrote = _fill(layout.views(staged), rows, seed=len(names))
        if kind == "prefill" and row_state:
            # a padded group: two rows, the third points at the scratch row
            layout.views(staged)["slot_rows"][:] = [2, 0, eng.slots]
            wrote["slot_rows"] = np.int32([2, 0, eng.slots])
        got = jax.jit(layout.split)(staged)
        assert set(got) == set(wrote)
        for f in layout.fields:
            out = np.asarray(got[f.name])
            assert out.dtype == f.dtype, (kind, f.name)
            want = wrote[f.name]
            if f.dtype == np.bool_:
                want = want != 0
            np.testing.assert_array_equal(_bits(out), _bits(want.astype(
                f.dtype)), err_msg=f"{name} {kind} {f.name}")


@pytest.mark.parametrize("name", list(CASES))
def test_a_fresh_array_holds_what_the_builds_staged_before(name):
    eng = _engine(name)
    vocab = eng.cfg.vocab_size
    for kind in KINDS:
        layout = eng._layouts[kind]
        got = {k: np.asarray(v)
               for k, v in jax.jit(layout.split)(layout.new(2)).items()}
        # greedy in every kind: a prefill group's unwritten lane too, so it
        # never asks the sampler for the full-vocabulary sort
        assert got["greedy"].all()
        assert (got["t"] == np.float32(1.0)).all()
        assert (got["k"] == vocab).all()
        assert (got["p"] == np.float32(2.0)).all()
        assert (got["keydata"] == 0).all() and (got["tables"] == 0).all()
        if kind == "prefill":
            assert (got["valid"] == 1).all()
            if "slot_rows" in got:
                assert (got["slot_rows"] == eng.slots).all()
        if "tenants" in got:
            assert (got["tenants"] == 0).all()  # the exact-zero adapter


def test_a_layout_refuses_an_operand_that_is_not_32_bits():
    with pytest.raises(ValueError, match="32-bit"):
        StagedLayout((Field("x", None, np.int16),))


@pytest.mark.parametrize("kind", KINDS)
def test_one_transfer_a_dispatch(kind):
    """``arg_transfers.<kind>`` equals that kind's dispatch count after a
    short run: every build staged its operands in ONE transfer."""
    from pytorch_distributed_tpu.models import get_model

    cfg = _gpt2()
    params = get_model(cfg).init(jax.random.key(0), cfg)
    eng = PagedBatchedDecodeEngine(
        cfg, slots=3, max_len=32, page_size=8, prefill_chunk=8,
        speculative_k=2 if kind == "decode_spec_step" else 0)
    rng = np.random.default_rng(1)
    for i, n in enumerate((12, 3, 20, 7)):
        eng.submit(rng.integers(0, 97, n).astype(np.int32), 5,
                   temperature=0.0 if i % 2 else 0.8, key=jax.random.key(i))
    eng.run(params)
    stats = eng.stats()
    n = stats["timers"]["engine.dispatch." + kind]["count"]
    assert n > 0
    assert stats["counters"]["arg_transfers." + kind] == n
    assert stats["timers"]["engine.put." + kind]["count"] == n
