"""The benchmark's own tests: run them explicitly, on the CPU,

    python -m pytest perfbench/tests

(the repository's tier-1 suite collects ``tests/`` only)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SRC = os.path.join(os.path.dirname(BENCH), "src")
for p in (BENCH, SRC):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture(scope="session", autouse=True)
def _compile_cache(tmp_path_factory):
    """Keep the CPU tests' compile cache out of the checkout."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(tmp_path_factory.mktemp("jax_cache")))
