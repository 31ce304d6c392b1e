"""Tier-1's seat for ``benchmark/tests/test_window_moe.py``: its tests,
each a case of its own (``tests/conftest.py`` says why, at
HARNESS_XFAIL)."""

import benchmark.tests.conftest  # noqa: F401  (its path set-up)
from benchmark.tests.test_window_moe import *  # noqa: F401,F403
