"""repro.platform: the one place interpret defaults and XLA flag setup
live. These tests only touch the env-merging helpers with a scratch
XLA_FLAGS — the real env (and the already-initialized jax backend) must
come through untouched."""
import pytest

from repro import platform as repro_platform


@pytest.fixture(autouse=True)
def scratch_xla_flags(monkeypatch):
    """Every test works on its own XLA_FLAGS and LIBTPU_INIT_ARGS; jax is
    already initialized in this session so nothing here can affect the
    live backend."""
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
    yield


def test_interpret_default_by_platform():
    assert repro_platform.interpret_default("cpu") is True
    assert repro_platform.interpret_default("gpu") is True
    assert repro_platform.interpret_default("tpu") is False


def test_interpret_default_uses_active_backend():
    # on the test host jax runs on cpu, so the derived default is interpret
    assert repro_platform.platform() == "cpu"
    assert repro_platform.interpret_default() is True


def test_merge_xla_flags_idempotent(monkeypatch):
    import os
    a = repro_platform.merge_xla_flags(("--xla_foo=1", "--xla_bar=2"))
    b = repro_platform.merge_xla_flags(("--xla_foo=1", "--xla_bar=2"))
    assert a == b == "--xla_foo=1 --xla_bar=2"
    assert os.environ["XLA_FLAGS"] == a


def test_merge_xla_flags_existing_setting_wins(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=user")
    merged = repro_platform.merge_xla_flags(("--xla_foo=ours", "--xla_new=1"))
    assert merged == "--xla_foo=user --xla_new=1"


def test_configure_defaults_to_cpu_without_touching_jax(monkeypatch):
    """configure() must not initialize jax to pick a platform — that would
    freeze the backend before the flags it sets could matter. It reads
    JAX_PLATFORMS; unset or cpu sets no latency-hiding flags at all."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert repro_platform.configure() == ""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert repro_platform.configure() == ""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    merged = repro_platform.configure()
    assert "--xla_tpu_enable_async_collective_fusion=true" in merged


def test_configure_explicit_platform(monkeypatch):
    merged = repro_platform.configure(plat="gpu")
    for flag in repro_platform.LATENCY_HIDING_FLAGS["gpu"]:
        assert flag in merged


def test_tpu_flags_go_to_libtpu_not_xla_flags(monkeypatch):
    """The CPU client aborts on an unknown flag in XLA_FLAGS, and libtpu
    reads its own from LIBTPU_INIT_ARGS: TPU flags never enter XLA_FLAGS."""
    import os
    merged = repro_platform.configure(plat="tpu")
    assert os.environ["LIBTPU_INIT_ARGS"] == merged
    assert os.environ["XLA_FLAGS"] == ""
    for flag in repro_platform.LATENCY_HIDING_FLAGS["tpu"]:
        assert flag in merged


def test_compilation_cache_env_wins(monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is used as it is."""
    import os
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert repro_platform.use_compilation_cache("/elsewhere") == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert not os.path.exists("/elsewhere")


@pytest.mark.parametrize("set_env", [True, False])
def test_compilation_cache_placement(tmp_path, set_env):
    """In a fresh process: with JAX_COMPILATION_CACHE_DIR set, a compile
    writes there and nowhere else; without it, jax is pointed at the fixed
    <repo>/.jax_cache."""
    import os
    import subprocess
    import sys
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro import platform as p\n"
        "d = p.use_compilation_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == d, d\n"
        "print(d)\n")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("JAX_COMPILATION_CACHE")}
    env.update(PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"),
               JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    cache = tmp_path / "cache"
    if set_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
        # without it, resolve the default but compile nothing into the repo
        script += ("jax.jit(lambda a: jnp.sin(a) * 3)(jnp.ones(7))"
                   ".block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    got = out.stdout.strip().splitlines()[-1]
    if set_env:
        assert got == str(cache)
        assert os.listdir(cache)  # the compile landed in the named cache
        assert sorted(os.listdir(tmp_path)) == ["cache"]
    else:
        assert got == repro_platform.DEFAULT_CACHE_DIR
        assert got.endswith(os.sep + ".jax_cache")


def test_set_host_device_count_never_lowers(monkeypatch):
    import os
    repro_platform.set_host_device_count(8)
    assert "--xla_force_host_platform_device_count=8" \
        in os.environ["XLA_FLAGS"]
    repro_platform.set_host_device_count(4)  # a lower ask is a no-op
    assert "--xla_force_host_platform_device_count=8" \
        in os.environ["XLA_FLAGS"]
    repro_platform.set_host_device_count(12)  # a higher ask raises it
    flags = os.environ["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=12" in flags
    assert "count=8" not in flags


def test_testing_devices_delegates_to_platform(monkeypatch):
    """The harness's force_host_devices is a thin wrapper over
    set_host_device_count — one owner for the flag format."""
    import os
    from repro.testing import devices
    calls = []
    monkeypatch.setattr(repro_platform, "set_host_device_count",
                        lambda n: calls.append(n))
    try:
        devices.force_host_devices(6)
    except RuntimeError:
        pass  # jax already initialized in-session: the post-check may trip
    assert calls == [6]
