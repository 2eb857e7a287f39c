"""Central host/device platform configuration.

One place for everything that must be decided *about the machine* rather
than about the algorithm: which backend we are on, whether Pallas kernels
should run in interpret mode, which latency-hiding XLA flags to set, and
how many fake host devices to force for CPU test grids. The driver,
benchmarks, and the test harness all read platform facts from here so no
module hard-codes "interpret=True" or scribbles over ``XLA_FLAGS``
independently (the seed's `sodda_inner_pallas` pinned interpret mode on —
correct on CPU, silently wrong on TPU).

Flag setup must happen before jax initializes its backend; the helpers
here merge into ``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS`` idempotently instead
of clobbering them, so conftest's forced device count and a benchmark's
latency-hiding flags compose in either order.

It also places JAX's persistent compilation cache
(:func:`use_compilation_cache`), the one call every entry point makes.
"""
from __future__ import annotations

import os
import sys
from typing import Optional, Sequence

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

# Fixed default home of the compilation cache. The cache directory is part
# of the cache key, so it must never come from a temporary name, a process
# id or the time.
DEFAULT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))

# Latency-hiding flags per backend family. TPU's scheduler flags let XLA
# overlap the snapshot-gradient collectives with the inner-loop compute
# (the async/async-mesh backends' whole point); the GPU set is the
# standard async-collectives pair. CPU gets none — the fake host grid's
# collectives are memcpys.
LATENCY_HIDING_FLAGS = {
    "tpu": (
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_enable_async_all_gather=true",
        "--xla_enable_async_collective_permute=true",
    ),
    "gpu": (
        "--xla_gpu_enable_async_collectives=true",
        "--xla_gpu_enable_latency_hiding_scheduler=true",
        "--xla_gpu_enable_highest_priority_async_stream=true",
    ),
    "cpu": (),
}

# Where each family's flags are read. libtpu parses its own flags from
# LIBTPU_INIT_ARGS; XLA_FLAGS is parsed by every client, and the CPU client
# aborts on a TPU flag it does not know.
FLAGS_ENV = {"tpu": "LIBTPU_INIT_ARGS", "gpu": "XLA_FLAGS", "cpu": "XLA_FLAGS"}


def platform() -> str:
    """The active jax backend name ("cpu" | "gpu" | "tpu").

    Imports jax lazily: callers that only *write* env flags (and must run
    before jax initializes) never touch this.
    """
    import jax

    return jax.default_backend()


def on_tpu() -> bool:
    return platform() == "tpu"


def interpret_default(plat: Optional[str] = None) -> bool:
    """Whether Pallas kernels should run in interpret mode.

    Interpret mode is the CPU/GPU emulation path; on TPU the kernels
    compile to Mosaic and interpret mode would silently discard the whole
    point of writing them. Everything that builds a `pallas_call` derives
    its default from here rather than pinning a literal.
    """
    plat = platform() if plat is None else plat
    return plat != "tpu"


def merge_xla_flags(new_flags: Sequence[str], env: str = "XLA_FLAGS") -> str:
    """Merge `new_flags` into ``os.environ[env]`` idempotently.

    A flag already present (by its `--name` prefix) is left alone — the
    user's explicit setting wins. Returns the resulting flag string. Only
    affects backends not yet initialized; call before first jax use.
    """
    existing = os.environ.get(env, "").split()
    have = {f.split("=", 1)[0] for f in existing}
    for flag in new_flags:
        if flag.split("=", 1)[0] not in have:
            existing.append(flag)
            have.add(flag.split("=", 1)[0])
    merged = " ".join(existing)
    if merged:
        os.environ[env] = merged
    return merged


def declared_platform() -> Optional[str]:
    """The platform ``JAX_PLATFORMS`` names first, or None when it is unset.

    What is known before jax starts — unlike `platform()`, which would
    initialize the backend and make any flag set afterwards moot.
    """
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    return first.lower() or None


def configure(plat: Optional[str] = None,
              host_devices: Optional[int] = None) -> str:
    """Set up the process for `plat`: latency-hiding flags + device count.

    The one call drivers and benchmarks make at entry. `plat` defaults to
    `declared_platform()`; with neither, no backend flags are set, since a
    flag the running client does not know aborts it. Returns the merged
    flag string of the variable `plat`'s flags go to ("" for none).
    """
    if plat is None:
        plat = declared_platform()
    if host_devices is not None:
        set_host_device_count(host_devices)
    flags = LATENCY_HIDING_FLAGS.get(plat, ())
    if not flags:
        return ""
    return merge_xla_flags(flags, env=FLAGS_ENV[plat])


def use_compilation_cache(default_dir: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads it
    itself and no other cache is set in code. Otherwise the cache goes to
    `default_dir`, or to the fixed `DEFAULT_CACHE_DIR` (``<repo>/.jax_cache``).
    The choice is exported through the environment, so child processes
    share it, and applied to an already-imported jax.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.abspath(default_dir or DEFAULT_CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


def set_host_device_count(n: int) -> None:
    """Force `n` fake host devices (CPU test grids). Never lowers a
    pre-existing forced count; must run before jax initializes."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _DEVICE_COUNT_FLAG in flags:
        current = int(flags.split(f"{_DEVICE_COUNT_FLAG}=")[1].split()[0])
        if current >= n:
            return
        flags = " ".join(
            p for p in flags.split() if not p.startswith(_DEVICE_COUNT_FLAG))
        os.environ["XLA_FLAGS"] = flags
    merge_xla_flags((f"{_DEVICE_COUNT_FLAG}={n}",))
