"""Tier-1's seat for ``benchmark/tests/test_manifest.py``: its tests,
each a case of its own (``tests/conftest.py`` says why, at HARNESS_XFAIL)."""

import benchmark.tests.conftest  # noqa: F401  (its path set-up)
from benchmark.tests.test_manifest import *  # noqa: F401,F403


def test_every_harness_test_file_has_its_seat():
    """A ``benchmark/tests/test_x.py`` with no ``tests/test_harness_x.py``
    would run by hand only, where nobody sees it fail."""
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    theirs = {f[len("test_"):]
              for f in os.listdir(os.path.join(here, os.pardir,
                                               "benchmark", "tests"))
              if f.startswith("test_") and f.endswith(".py")}
    ours = {f[len("test_harness_"):] for f in os.listdir(here)
            if f.startswith("test_harness_") and f.endswith(".py")}
    assert ours == theirs
