"""The benchmark's CPU tests: its modules import by name from ``bench/``,
the system under test from ``src/``."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def f32():
    """32-bit JAX for the test (other test modules turn x64 on at import,
    and the benchmark runs the program in its 32-bit default)."""
    import jax

    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)
