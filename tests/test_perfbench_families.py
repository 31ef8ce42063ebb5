"""Tier-1 collects the benchmark's tests of a family that is not GPT-2.

``perfbench/tests`` is run by hand (``python3 -m pytest perfbench/tests -q``,
the whole of it takes minutes); the two files that prove what no test under
``tests/`` does are collected here too, so that the tier-1 count holds them:

- ``perfbench/tests/test_other_family.py``: a configuration of another
  family goes through both drivers as files and entries, no edit;
- ``perfbench/tests/test_kimi_k2.py``: the kimi_k2 cell's comparison (the
  float8 control reads not correct, the program correct, each planted fault
  not correct), its two readers, and its configuration file;
- ``perfbench/tests/test_granitemoehybrid.py``: the same for the
  granitemoehybrid cell (per-row recurrent state beside the pages), with its
  count module held to its issue's arithmetic;
- ``perfbench/tests/test_mellum.py``: the same for the mellum cell (two
  groups of pages, softmax-routed experts), with its three readers.

The cases run where they are defined; this module only names them.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench.tests import (  # noqa: E402
    test_granitemoehybrid,
    test_kimi_k2,
    test_mellum,
    test_other_family,
)

for _module in (test_other_family, test_kimi_k2, test_granitemoehybrid,
                test_mellum):
    for _name, _thing in vars(_module).items():
        # its tests, and the fixture its tests ask for by name
        if _name.startswith("test_") or _name == "family":
            assert _name not in globals(), _name
            globals()[_name] = _thing
