"""A checkout of the benchmark cut to CPU-test size.

`tiny_root` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
directory and shrinks every configuration to a few hundred rows and
columns, keeping its grid, backend, loss and sampling fractions; the
traffic mixes, limits and metric readers are the committed ones.
"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench_helpers import make_tiny_root  # noqa: E402


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny_bench")))


@pytest.fixture
def fresh_programs():
    """Forget every traced and compiled program, before and after, so a
    fault planted in the program's code is traced in and then out again."""
    import jax

    from repro.core import driver

    def clear():
        jax.clear_caches()
        driver._cached_run.cache_clear()
        driver._cached_bundle.cache_clear()

    clear()
    yield
    clear()
