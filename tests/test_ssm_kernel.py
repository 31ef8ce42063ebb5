"""ops/ssm_kernel.py (a decode step's pass over the recurrent state where it
lies: S' written back in place, y from the same pass) against
``ops/ssm.ssm_step`` (the plain form it replaces on the chip), in the Pallas
interpreter on the CPU. Small shapes, and one case at granite-4.0-h-micro's
64 x 128 head state.

Tolerances are float32 roundings: S' is one product and one sum an entry
(1e-6 of entries of order 1); y sums N of them in another order than
``jnp.sum`` (1e-5 at N = 16, 5e-5 at N = 128).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.ops import ssm, ssm_kernel

# name -> (rows, heads, head entries P, state N, groups, HEAD_BLOCK, layers,
#          layer, y's tolerance)
CASES = {
    "one_block_a_row": (5, 8, 8, 16, 1, 8, 3, 1, 1e-5),
    "blocks_of_4": (5, 8, 8, 16, 1, 4, 3, 1, 1e-5),
    "blocks_of_1": (4, 4, 8, 16, 1, 1, 2, 1, 1e-5),
    "two_groups": (6, 8, 8, 16, 2, 2, 3, 2, 1e-5),
    "four_groups_one_head_each": (4, 4, 8, 16, 4, 16, 2, 0, 1e-5),
    "layer_0": (4, 8, 8, 16, 2, 4, 3, 0, 1e-5),
    "wide_heads": (4, 2, 256, 16, 1, 2, 2, 1, 1e-5),
    "published_head": (4, 4, 64, 128, 1, 2, 2, 1, 5e-5),
}
# lanes of a call: live rows, dead ones (free or mid-prefill: dt = 0), rows
# that begin their sequence; row 0 live, the last row dead, one fresh
LIVE = [True, False, True, True, True, False]
FRESH = [False, False, True, False, False, False]


def make_case(rows, h, p, n, g, layers, seed=0):
    rng = np.random.default_rng(seed)
    live = jnp.asarray(LIVE[:rows - 1] + [False])
    fresh = jnp.asarray(FRESH[:rows])
    f32 = jnp.float32
    return dict(
        x=jnp.asarray(rng.normal(size=(rows, h, p)), f32),
        # a dead lane's dt is 0 (the model masks it); its other inputs are
        # whatever the lane held
        dt=jnp.where(live[:, None], jnp.asarray(
            rng.uniform(1e-3, 1e-1, (rows, h)), f32), 0.0),
        a=-jnp.asarray(rng.uniform(1, 16, (h,)), f32),
        b=jnp.asarray(rng.normal(size=(rows, g, n)), f32),
        c=jnp.asarray(rng.normal(size=(rows, g, n)), f32),
        # the leaf holds one row more than the call: the scratch row
        leaf=jnp.asarray(rng.normal(size=(layers, rows + 1, h, p, n)), f32),
        live=live, fresh=fresh)


def plain_step(x, dt, a, b, c, leaf, layer, live, fresh):
    """What ``models/granitemoehybrid._mamba`` does off the chip."""
    rows = x.shape[0]
    state = leaf[layer, :rows]
    y, new = ssm.ssm_step(
        x, dt, a, b, c, jnp.where(fresh[:, None, None, None], 0.0, state))
    new = jnp.where(live[:, None, None, None], new, state)
    return y, leaf.at[layer, :rows].set(new)


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_equals_the_plain_step(name, monkeypatch):
    rows, h, p, n, g, block, layers, layer, y_tol = CASES[name]
    monkeypatch.setattr(ssm_kernel, "HEAD_BLOCK", block)
    case = make_case(rows, h, p, n, g, layers)
    live = np.asarray(case["live"])
    assert live.any() and not live.all() and np.asarray(case["fresh"]).any()
    before = np.asarray(case["leaf"])
    y, leaf = ssm_kernel.ssm_state_step(
        **case, layer=jnp.asarray(layer), interpret=True)
    want_y, want_leaf = plain_step(**case, layer=layer)
    assert y.shape == (rows, h, p) and y.dtype == jnp.float32
    assert leaf.shape == before.shape and leaf.dtype == jnp.float32
    leaf, want_leaf = np.asarray(leaf), np.asarray(want_leaf)
    np.testing.assert_allclose(
        np.asarray(y)[live], np.asarray(want_y)[live], atol=y_tol, rtol=0)
    assert np.isfinite(np.asarray(y)).all()  # a dead lane's y too
    np.testing.assert_allclose(
        leaf[layer, :rows][live], want_leaf[layer, :rows][live],
        atol=1e-6, rtol=0)
    # a fresh row began from zero: its state is dt x (x) B alone
    (r,) = np.flatnonzero(np.asarray(case["fresh"]) & live)
    dtx = np.asarray(case["dt"][r, :, None] * case["x"][r])
    b_of_head = np.repeat(np.asarray(case["b"][r]), h // g, axis=0)
    np.testing.assert_allclose(
        leaf[layer, r], dtx[:, :, None] * b_of_head[:, None, :], atol=1e-6,
        rtol=0)
    # bit for bit: the dead rows, the scratch row, every other layer
    np.testing.assert_array_equal(
        leaf[layer, :rows][~live], before[layer, :rows][~live])
    np.testing.assert_array_equal(leaf[layer, rows], before[layer, rows])
    others = [k for k in range(layers) if k != layer]
    np.testing.assert_array_equal(leaf[others], before[others])
    assert (leaf[layer, :rows][live] != before[layer, :rows][live]).any()


@pytest.mark.parametrize("live", [
    [False, False, False], [True, True, True], [False, False, True]])
def test_any_mix_of_live_lanes(live):
    """No live row (the kernel starts no copy), all live, only the last."""
    case = make_case(4, 4, 8, 16, 1, 2)
    case["live"] = jnp.asarray(live + [False])
    case["fresh"] = jnp.zeros((4,), bool)
    case["dt"] = jnp.where(case["live"][:, None], case["dt"] + 1e-3, 0.0)
    y, leaf = ssm_kernel.ssm_state_step(**case, layer=1, interpret=True)
    want_y, want_leaf = plain_step(**case, layer=1)
    on = np.asarray(case["live"])
    np.testing.assert_allclose(
        np.asarray(y)[on], np.asarray(want_y)[on], atol=1e-5, rtol=0)
    np.testing.assert_allclose(leaf, want_leaf, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(
        np.asarray(leaf)[1][~np.append(on, False)],
        np.asarray(case["leaf"])[1][~np.append(on, False)])


def test_a_fresh_row_ignores_what_the_row_held():
    """A row that begins its sequence starts from zero whatever the last
    tenant left, a NaN too (a select, not a product with zero)."""
    case = make_case(3, 4, 8, 16, 1, 2)
    case["live"] = jnp.asarray([True, True, False])
    case["fresh"] = jnp.asarray([False, True, False])
    case["leaf"] = case["leaf"].at[1, 1].set(jnp.nan)
    y, leaf = ssm_kernel.ssm_state_step(**case, layer=1, interpret=True)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(leaf)[1, :2]).all()


def test_the_compiled_kernel_is_never_chosen_off_the_chip():
    case = make_case(3, 4, 8, 16, 1, 2)
    with pytest.raises(RuntimeError, match="interpret=True"):
        ssm_kernel.ssm_state_step(**case, layer=0)


def test_the_call_is_traced_once_for_a_periods_nine_layers():
    """The kernel sits in ONE jitted function: a program that calls it for
    nine layers holds one body, called nine times (set-up is tracing)."""
    case = make_case(3, 4, 8, 16, 1, 2)

    def nine(leaf):
        y = 0.0
        for k in range(9):
            out, leaf = ssm_kernel.ssm_state_step(
                **{**case, "leaf": leaf}, layer=k % 2, interpret=True)
            y = y + out
        return y, leaf

    text = jax.jit(nine).lower(case["leaf"]).as_text()
    assert text.count("func.func private @_state_call") == 1
    assert text.count("call @_state_call") == 9
