"""Smoke run of the SODDA main path on a TPU, at Table-1 widths.

    PYTHONPATH=src python chip_smoke.py [--seed 0] [--four-chip]

One chip (the default) runs Table 1's SMALL widths, P=5 x Q=3 partitions of
m=6,000 features (M=18,000), L=64, hinge loss, lr0=0.005, with n cut from
50,000 to 10,000 observations per partition (N=50,000): X (3.6 GB in f32)
plus the run's temporaries must fit the chip's 16 GB of HBM. The data comes
from a `TiledDataPlane` built from ``--seed`` and stays on the device.
`driver.run` takes 20 outer iterations, recording every 5, first on
``pallas`` and then on ``reference``, on the same arrays. The run passes
when:

* the ``pallas`` program holds the Mosaic-compiled kernel
  (``tpu_custom_call``) and Pallas is not in interpret mode;
* both objective histories are finite and fall from tick 0 to the last
  tick;
* the two histories, and the final iterates, agree under ``F32_REDUCTION``.

lr0=0.005 keeps a step's change of a block margin, lr0 * m_tilde, near
the value of the small CPU benchmarks (0.05 * 120). At lr0=0.05 and
m_tilde=1,200 the objective first climbs to about 5 and, with n of 2,000 or
more, is still above F(0)=1 after 20 iterations.

``--four-chip`` runs this phase and no other: Table 1 SMALL in full
(250,000 x 18,000) as P=2 x Q=2 on a 2x2 ('data', 'model') mesh,
``shard_map+pallas`` against ``shard_map`` under the same checks, with the
X shard of each device asserted to sit on its own chip.

Compile seconds, warm seconds per iteration and ``peak_bytes_in_use`` are
printed for information only. The script exits non-zero, without the ok
line, unless JAX runs on a TPU. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro import platform as repro_platform  # noqa: E402 (imports no jax)
from repro.configs.sodda_svm import SMALL, SoddaConfig  # noqa: E402

ITERS = 20
RECORD_EVERY = 5

ONE_CHIP = SoddaConfig(name="table1-small-n10k", P=5, Q=3, n=10_000,
                       m=6_000, L=64, loss="hinge", lr0=0.005)
FOUR_CHIP = SoddaConfig(name="table1-small-2x2", P=2, Q=2, n=125_000,
                        m=9_000, L=64, loss="hinge", lr0=0.005)


# seconds XLA spent compiling, summed by the listener `main` registers
COMPILE_S = [0.0]


def _count_compile(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILE_S[0] += duration


def _peak_bytes():
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def run_backend(key, data, cfg, backend, mesh=None):
    """`driver.run` twice: the first call compiles, the second is timed warm.

    Returns the final iterate and the first call's history.
    """
    import numpy as np

    from repro.core import driver

    c0, t0 = COMPILE_S[0], time.perf_counter()
    state, hist = driver.run(key, data, cfg, ITERS, backend,
                             record_every=RECORD_EVERY, mesh=mesh)
    first = time.perf_counter() - t0
    compile_s = COMPILE_S[0] - c0
    t0 = time.perf_counter()
    driver.run(key, data, cfg, ITERS, backend, record_every=RECORD_EVERY,
               mesh=mesh)
    warm = time.perf_counter() - t0
    print(f"{backend}: XLA compile {compile_s:.3f} s, first call {first:.3f} s, "
          f"warm call {warm:.3f} s = {warm / ITERS:.6f} s/iter "
          f"(informational)")
    print(f"{backend}: objective " + ", ".join(
        f"t={t}: {f:.7f}" for t, f in hist))
    print(f"{backend}: peak_bytes_in_use per device {_peak_bytes()}")
    return np.asarray(state.w), hist


def assert_compiled_kernel(key, X, y, cfg, backend, mesh=None):
    """The `backend` run program holds the Mosaic kernel, not its oracle."""
    from repro.core import driver
    from repro.core.sodda import init_state

    assert not repro_platform.interpret_default(), "Pallas would interpret"
    state = driver.place_initial_state(init_state(key, cfg.M), cfg, backend,
                                       mesh)
    hlo = driver.make_run(cfg, ITERS, backend, record_every=RECORD_EVERY,
                          mesh=mesh).lower(state, X, y).as_text()
    assert "tpu_custom_call" in hlo, f"{backend}: no tpu_custom_call"
    print(f"{backend}: interpret=False, tpu_custom_call in the run program")


def check_pair(kernel, oracle):
    """Both runs finite and descending; kernel vs oracle in F32_REDUCTION."""
    import numpy as np

    from repro.testing import (F32_REDUCTION, assert_objectives_close,
                               assert_trajectories_close)

    (name_k, w_k, hist_k), (name_o, w_o, hist_o) = kernel, oracle
    for name, w, hist in (kernel, oracle):
        fs = np.array([f for _, f in hist])
        assert np.all(np.isfinite(fs)) and np.all(np.isfinite(w)), name
        assert fs[-1] < fs[0], f"{name}: no descent {fs[0]} -> {fs[-1]}"
        print(f"{name}: finite, descends {fs[0]:.7f} -> {fs[-1]:.7f}")
    assert [t for t, _ in hist_k] == [t for t, _ in hist_o]
    for (t, f_o), (_, f_k) in zip(hist_o, hist_k):
        assert_objectives_close(f_o, f_k, F32_REDUCTION,
                                f"{name_k} vs {name_o} t={t}")
    assert_trajectories_close([w_o], [w_k], F32_REDUCTION,
                              f"{name_k} vs {name_o} final w")
    gap = max(abs(a - b) for (_, a), (_, b) in zip(hist_o, hist_k))
    print(f"{name_k} vs {name_o}: within {F32_REDUCTION.name} "
          f"(max objective gap {gap:.3e}, max |w| gap "
          f"{float(np.max(np.abs(w_k - w_o))):.3e})")


def one_chip(seed: int, cfg: SoddaConfig = ONE_CHIP):
    import jax

    from repro.data.plane import TiledDataPlane

    print(f"config {cfg.name}: P={cfg.P} Q={cfg.Q} n={cfg.n} m={cfg.m} "
          f"(N={cfg.N}, M={cfg.M}) L={cfg.L} {cfg.loss} lr0={cfg.lr0}")
    print(f"cut: n {SMALL.n} -> {cfg.n} per partition (N {SMALL.N} -> "
          f"{cfg.N}), forced by 16 GB of HBM; widths unchanged")
    data_key, run_key = jax.random.split(jax.random.PRNGKey(seed))
    plane = TiledDataPlane(data_key, cfg.N, cfg.M, cfg.P, cfg.Q)
    t0 = time.perf_counter()
    X, y = jax.block_until_ready(plane.materialize_for("pallas"))
    print(f"data on {X.devices()}: X {X.shape} {X.dtype} "
          f"{X.nbytes / 1e9:.3f} GB, placed in "
          f"{time.perf_counter() - t0:.3f} s")
    assert_compiled_kernel(run_key, X, y, cfg, "pallas")
    w_k, hist_k = run_backend(run_key, (X, y), cfg, "pallas")
    w_o, hist_o = run_backend(run_key, (X, y), cfg, "reference")
    check_pair(("pallas", w_k, hist_k), ("reference", w_o, hist_o))


def four_chip(seed: int, cfg: SoddaConfig = FOUR_CHIP):
    import jax

    from repro.core import engine
    from repro.data.plane import TiledDataPlane

    print(f"config {cfg.name}: P={cfg.P} Q={cfg.Q} n={cfg.n} m={cfg.m} "
          f"(N={cfg.N}, M={cfg.M}) L={cfg.L} {cfg.loss} lr0={cfg.lr0}, "
          "Table 1 SMALL in full")
    mesh = engine.make_mesh_for(cfg)
    data_key, run_key = jax.random.split(jax.random.PRNGKey(seed))
    plane = TiledDataPlane(data_key, cfg.N, cfg.M, cfg.P, cfg.Q)
    t0 = time.perf_counter()
    X, y = jax.block_until_ready(
        plane.materialize_for("shard_map+pallas", mesh=mesh))
    print(f"data placed in {time.perf_counter() - t0:.3f} s")
    shards = {s.device: s.data.shape for s in X.addressable_shards}
    for device, shape in shards.items():
        print(f"X shard {shape} on {device}")
    assert len(shards) == cfg.P * cfg.Q == len(set(mesh.devices.flat))
    assert set(shards) == set(mesh.devices.flat)
    assert all(shape == (cfg.n, cfg.m) for shape in shards.values())
    assert_compiled_kernel(run_key, X, y, cfg, "shard_map+pallas", mesh)
    w_k, hist_k = run_backend(run_key, (X, y), cfg, "shard_map+pallas", mesh)
    w_o, hist_o = run_backend(run_key, (X, y), cfg, "shard_map", mesh)
    check_pair(("shard_map+pallas", w_k, hist_k),
               ("shard_map", w_o, hist_o))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 2x2-mesh phase (needs four chips)")
    args = ap.parse_args(argv)

    repro_platform.configure("tpu")  # before jax starts
    cache = repro_platform.use_compilation_cache()
    import jax

    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    print(f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r} "
          f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}")
    print(f"compilation cache: {cache}")
    if device["platform"] != "tpu":
        print(f"FAIL: chip_smoke needs a TPU; JAX runs on "
              f"{device['platform']}", file=sys.stderr)
        return 1
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        print(f"FAIL: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    if args.four_chip:
        four_chip(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
