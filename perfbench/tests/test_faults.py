"""A run with the timed path broken underneath reads ``correct: false``.

Each test skips the harness's look for a chip (``run.execute`` is called
below ``run.main``) and drives the rest of a run at a tiny size on the CPU,
with one fault planted under the driver (``faults.py`` wraps its ``build``): a step that returns its state
unchanged; half of the batch left out, the mean taken over the rest; a token
altered where it is produced. (No cell spans chips yet, so there is no
exchange to leave out.) The unbroken run reads ``correct: true``.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import run  # noqa: E402
from perfbench.tests import faults, tiny  # noqa: E402
from perfbench.tests.test_control import ctx_for  # noqa: E402


@pytest.mark.parametrize("kind,fault,correct", [
    ("train", None, True),
    ("train", "unchanged_state", False),
    ("train", "half_batch", False),
    ("serve", None, True),
    ("serve", "altered_token", False),
    ("backlog", None, True),
    ("backlog", "altered_token", False),
])
def test_fault_reads_not_correct(kind, fault, correct, tmp_path):
    with faults.planted(fault):
        line = run.execute(
            ctx_for(kind, 4, tmp_path), tiny.TINY_BENCH, None, None)
    assert line["correct"] is correct, line["numbers"]
    assert list(line)[-1] == "numbers"
    assert all("limit" in n for n in line["numbers"].values())
