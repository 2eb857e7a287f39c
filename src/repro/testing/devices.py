"""Multi-device host platform helpers for the test suite.

jax fixes its device count at backend initialization, so forcing fake host
devices must happen before any jax API that touches the backend runs.
``tests/conftest.py`` calls :func:`force_host_devices` at import time —
pytest imports conftest before any test module, which is early enough as
long as conftest itself defers jax imports.
"""
from __future__ import annotations

import os
import subprocess
import sys

DEFAULT_TEST_DEVICES = 12  # the 4x3 (data, model) grid of the seed tests

_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices(n: int = DEFAULT_TEST_DEVICES) -> None:
    """Arrange for the current process to see `n` host devices.

    Must run before jax initializes its backend; idempotent, and never
    *lowers* an existing forced count (the flag surgery itself lives in
    `repro.platform.set_host_device_count`). Raises if jax already
    initialized with too few devices (the caller imported jax too early).
    """
    from repro import platform as repro_platform

    repro_platform.set_host_device_count(n)

    if "jax" in sys.modules:
        # starts the backend if nothing has yet, under the flag set above
        import jax
        if jax.local_device_count() < n:
            raise RuntimeError(
                f"jax already initialized with {jax.local_device_count()} "
                f"devices; force_host_devices({n}) must run before any jax "
                "backend use (import repro.testing in conftest, first)")


def enable_compilation_cache(cache_dir: str,
                             min_compile_secs: float = 0.5) -> None:
    """Point jax's persistent compilation cache at `cache_dir`, unless
    ``JAX_COMPILATION_CACHE_DIR`` already names one
    (`repro.platform.use_compilation_cache`).

    Exported through the environment so subprocess children — the
    512-device mesh check, the quickstart example, benchmark respawns —
    share the same cache. Cuts repeat-run jit warm-up to ~1/5 on this
    suite; cold runs are unaffected. Respects pre-set env overrides.
    """
    from repro import platform as repro_platform

    repro_platform.use_compilation_cache(cache_dir)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                          str(min_compile_secs))


def require_host_devices(n: int = DEFAULT_TEST_DEVICES) -> int:
    """Skip the calling test unless `n` host devices are visible."""
    import jax
    import pytest
    count = jax.local_device_count()
    if count < n:
        pytest.skip(f"needs {n} devices, have {count}")
    return count


def sodda_test_mesh(cfg=None, P: int = 4, Q: int = 3):
    """In-process (data=P, model=Q) mesh; skips if the host is too small."""
    import jax
    if cfg is not None:
        P, Q = cfg.P, cfg.Q
    require_host_devices(P * Q)
    return jax.make_mesh((P, Q), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def run_forced_subprocess(script: str, devices: int, timeout: int = 560):
    """Run `script` in a fresh interpreter seeing `devices` host devices.

    Only for device counts the in-process session cannot provide (e.g. the
    512-device production mesh); everything 12-and-under should use
    :func:`sodda_test_mesh` in-process instead.
    """
    preamble = (f"import os\n"
                f"os.environ['XLA_FLAGS'] = '{_FLAG}={devices}'\n")
    src = os.path.join(os.path.dirname(__file__), "..", "..")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", preamble + script], env=env,
                          capture_output=True, text=True, timeout=timeout)
