"""The benchmark's CPU tests import its modules and the program the way
``bench/run.py`` does: ``bench/`` and ``src/`` on the path."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


@pytest.fixture
def no_chip_check(monkeypatch):
    """Let a run go on on the CPU: the harness's look for a TPU answers
    with whatever devices JAX has."""
    import jax

    import harness
    monkeypatch.setattr(harness, "require_chips",
                        lambda jax_mod, chips: jax.devices())
