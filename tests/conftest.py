"""Session bootstrap.

Runs before any test module imports jax, so this is the one place that can
still force the 12-device host platform the shard_map tests need — all
distributed tests then run IN-PROCESS (one jit warm-up for the whole
session) instead of each respawning a subprocess.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.testing import (enable_compilation_cache,  # noqa: E402
                           force_host_devices)

force_host_devices(12)
enable_compilation_cache(
    os.path.join(os.path.dirname(__file__), "..", ".pytest_cache",
                 "jax_compilation_cache"))


def pytest_addoption(parser):
    parser.addoption(
        "--update-goldens", action="store_true", default=False,
        help="rewrite tests/goldens/*.json from the current implementation "
             "(tests/test_goldens.py) instead of comparing against them")
