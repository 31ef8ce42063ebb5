"""The control comes out as NOT correct, at a size a test run can hold.

The control is the plain reference put in the program's place and computed
in float8 (the nearest precision below the bfloat16 the configurations
state). On the chip, at the cells' own sizes, ``tools/train_limits.py`` and
``tools/serve_probe.py --control fp8`` read it; here the same code runs on
the CPU at a tiny size under limits read at that size (``tiny.py``).

    python3 -m pytest perfbench/tests -q
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import compare, run  # noqa: E402
from perfbench.tests import tiny  # noqa: E402

SEEDS = (3, 4, 2147483900)


def ctx_for(kind, seed, tmp_path):
    """A run's context for the tiny stand-in of a training cell ("train"),
    an open loop ("serve") or a closed one ("backlog")."""
    traffic = {"train": tiny.TINY_TRAIN, "serve": tiny.TINY_SERVE,
               "backlog": tiny.TINY_BACKLOG}[kind]
    limits = tiny.TINY_TRAIN_LIMITS if kind == "train" else tiny.TINY_SERVE_LIMITS
    return run.Context(
        workload=f"tiny.{kind}", seed=seed, seconds=0.3 if kind == "train" else 2.0,
        trace=False, chips=1,
        config=tiny.TINY_CONFIG if kind == "train" else tiny.TINY_SERVE_CONFIG,
        traffic=traffic, limits=limits, scratch=str(tmp_path))


@pytest.mark.parametrize("seed", SEEDS)
def test_training_control_fails_and_program_passes(seed, tmp_path):
    from perfbench.drivers import train
    from perfbench.reference import gpt2 as ref

    res = train.run(ctx_for("train", seed, tmp_path))
    assert compare.verdict(res["numbers"]), res["numbers"]
    control = ref.train_reference(
        seed, tiny.TINY_MODEL, tiny.TINY_TRAIN["optimizer"],
        res["first_batches"], precision="fp8")
    numbers = compare.training(control, res["want"], tiny.TINY_TRAIN_LIMITS)
    assert not compare.verdict(numbers), numbers


@pytest.mark.parametrize("kind", ("serve", "backlog"))
@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_fails_and_program_passes(seed, kind, tmp_path):
    from perfbench.drivers import serve

    ctx = ctx_for(kind, seed, tmp_path)
    res = serve.run(ctx)
    assert res["failed"] == 0 and compare.verdict(res["numbers"]), res["numbers"]
    assert res["facts"]["tokens_compared"] >= 20
    assert res["facts"]["sampled_tokens_compared"] >= 20
    gaps = serve.logit_gaps(ctx, res["sample"], "fp8")
    control = compare.serving(
        {k: gaps["control_" + k] for k in tiny.TINY_SERVE_LIMITS},
        tiny.TINY_SERVE_LIMITS)
    assert not compare.verdict(control), control
