"""Test env: force CPU with 8 virtual devices BEFORE jax initialises.

This is the TPU-native answer to "test multi-node without a cluster"
(SURVEY.md §4): all mesh/collective code paths run on
``--xla_force_host_platform_device_count=8`` CPU devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# The split pipeline files keep their parity asserts in this shared helper
# module; without registration pytest would not rewrite its asserts and
# failures would lose their operand values.
pytest.register_assert_rewrite("_pipeline_common")

from pytorch_distributed_tpu.analysis.pytest_plugin import (  # noqa: E402,F401
    audit,
)
from pytorch_distributed_tpu.config import ModelConfig  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Two-tier suite (CI ergonomics): every test not explicitly marked
    ``full`` gets ``quick``, so ``pytest -m quick`` runs the fast tier
    (~5 min on this rig) and plain ``pytest`` runs everything."""
    for item in items:
        if "full" not in item.keywords:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(scope="session")
def tiny_config() -> ModelConfig:
    return ModelConfig(
        vocab_size=101,
        n_ctx=16,
        n_embd=32,
        n_layer=2,
        n_head=4,
        dtype="float32",
        remat="dots",
    )


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]
