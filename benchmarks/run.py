"""Benchmark harness — one function per paper table/figure + kernel/system
benches. Prints ``name,us_per_call,derived`` CSV rows (derived column carries
the table-specific metric). The ``driver`` bench additionally writes the
machine-readable ``results/BENCH_sodda.json`` (schema in
``benchmarks/validate_bench.py``).

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run --only driver
"""
from __future__ import annotations

import argparse
import functools
import os
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _t(fn, *args, reps=3):
    """Mean wall time per call in us, async-dispatch safe.

    Every rep is individually ``block_until_ready``'d — timing only the last
    rep's sync lets earlier calls overlap the clock and under-reports
    us/call (regression-tested in tests/test_benchmarks.py).
    """
    jax.block_until_ready(fn(*args))  # compile + warmup, fully drained
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


class AcceleratorHeldError(RuntimeError):
    """A cell that runs in child processes on forced CPU host devices was
    started in a process that holds an accelerator."""


def cpu_children_only(cell):
    """Refuse `cell` on an accelerator host before it spawns anything.

    The cell's children force CPU host devices, and on a chip host this
    process already holds the chip, so a child that needs it would fail or
    hang. One process per chip: `chip_smoke.py` is the path that runs there.
    """
    @functools.wraps(cell)
    def guarded(*args, **kwargs):
        plat = jax.default_backend()
        if plat != "cpu":
            raise AcceleratorHeldError(
                f"{cell.__name__} spawns child processes on forced CPU host "
                f"devices; this process holds the {plat} device, so it "
                "runs on a CPU host only")
        return cell(*args, **kwargs)

    return guarded


ROWS = []


def row(name, us, derived):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


# ---------------------------------------------------------------------------
# Paper Figure 2/3: SODDA vs RADiSA-avg convergence (loss vs gradient-
# coordinate cost), with the paper's chosen knobs (b,c,d)=(85%,80%,85%).
# ---------------------------------------------------------------------------
def bench_paper_convergence():
    from repro.configs.sodda_svm import SoddaConfig
    from repro.core import radisa, sodda
    from repro.data.synthetic import make_svm_data

    cfg = SoddaConfig(P=5, Q=3, n=2000, m=600, L=32, lr0=0.05)
    X, y, _ = make_svm_data(jax.random.PRNGKey(0), cfg.N, cfg.M)

    t0 = time.perf_counter()
    _, hs = sodda.run(jax.random.PRNGKey(1), X, y, cfg, 40, record_every=40)
    us_s = (time.perf_counter() - t0) / 40 * 1e6
    t0 = time.perf_counter()
    _, hr = radisa.run_radisa_avg(jax.random.PRNGKey(1), X, y, cfg, 40,
                                  record_every=40)
    us_r = (time.perf_counter() - t0) / 40 * 1e6

    fs, fr = sodda.iteration_flops(cfg), radisa.radisa_avg_iteration_flops(cfg)
    # early-phase comparison at equal FLOP budget (12 SODDA iterations)
    budget = 12 * fs
    _, hs_b = sodda.run(jax.random.PRNGKey(2), X, y, cfg, 12, record_every=12)
    it_r = max(1, int(budget / fr))
    _, hr_b = radisa.run_radisa_avg(jax.random.PRNGKey(2), X, y, cfg, it_r,
                                    record_every=it_r)
    row("paper_fig2_sodda_40it", us_s, f"final_loss={hs[-1][1]:.4f}")
    row("paper_fig2_radisa_avg_40it", us_r, f"final_loss={hr[-1][1]:.4f}")
    row("paper_fig2_equal_flop_budget", 0.0,
        f"sodda={hs_b[-1][1]:.4f} radisa_avg={hr_b[-1][1]:.4f} "
        f"sodda_wins={hs_b[-1][1] < hr_b[-1][1]}")
    row("paper_cost_ratio", 0.0,
        f"radisa_avg/sodda_flops_per_iter={fr/fs:.2f}")


# ---------------------------------------------------------------------------
# Paper Figure 2(a-f): (b,c,d) knob sweep — accuracy/speed trade-off.
# ---------------------------------------------------------------------------
def bench_paper_knob_sweep():
    from repro.configs.sodda_svm import SoddaConfig
    from repro.core import sodda
    from repro.data.synthetic import make_svm_data

    base = SoddaConfig(P=5, Q=3, n=1000, m=300, L=16, lr0=0.05)
    X, y, _ = make_svm_data(jax.random.PRNGKey(0), base.N, base.M)
    for d in (0.6, 0.85):
        cfg = dataclasses.replace(base, d_frac=d)
        _, h = sodda.run(jax.random.PRNGKey(1), X, y, cfg, 25, record_every=25)
        row(f"paper_fig2a_d{int(d*100)}", 0.0, f"loss@25={h[-1][1]:.4f}")
    for c in (0.4, 0.8):
        cfg = dataclasses.replace(base, b_frac=1.0, c_frac=c)
        _, h = sodda.run(jax.random.PRNGKey(1), X, y, cfg, 25, record_every=25)
        row(f"paper_fig2b_c{int(c*100)}", 0.0, f"loss@25={h[-1][1]:.4f}")
    for b in (0.6, 0.85):
        cfg = dataclasses.replace(base, b_frac=b, c_frac=min(b, base.c_frac))
        _, h = sodda.run(jax.random.PRNGKey(1), X, y, cfg, 25, record_every=25)
        row(f"paper_fig2cdef_b{int(b*100)}", 0.0, f"loss@25={h[-1][1]:.4f}")


# ---------------------------------------------------------------------------
# Paper Table 2: seed robustness — max/avg spread over 10 seeds.
# ---------------------------------------------------------------------------
def bench_seed_variance():
    from repro.configs.sodda_svm import SoddaConfig
    from repro.core import radisa, sodda
    from repro.data.synthetic import make_svm_data

    cfg = SoddaConfig(P=4, Q=3, n=500, m=160, L=16, lr0=0.05)  # m % P == 0
    X, y, _ = make_svm_data(jax.random.PRNGKey(0), cfg.N, cfg.M)
    for name, runner in (("sodda", lambda k: sodda.run(k, X, y, cfg, 15, 15)),
                         ("radisa_avg", lambda k: radisa.run_radisa_avg(
                             k, X, y, cfg, 15, 15))):
        finals = [runner(jax.random.PRNGKey(s))[1][-1][1] for s in range(10)]
        finals = np.array(finals)
        row(f"paper_tab2_{name}", 0.0,
            f"avg={finals.mean():.4f} max-avg={finals.max()-finals.mean():.2e} "
            f"avg-min={finals.mean()-finals.min():.2e}")


# ---------------------------------------------------------------------------
# Kernel benches (interpret mode on CPU — correctness + relative shape costs;
# wall-time MFU requires the TPU target).
# ---------------------------------------------------------------------------
def bench_kernels():
    from repro.kernels import ref
    from repro.kernels import ops

    B, L, mt = 15, 64, 512
    key = jax.random.PRNGKey(0)
    w0 = jax.random.normal(key, (B, mt)) * 0.1
    Xl = jax.random.normal(jax.random.fold_in(key, 1), (B, L, mt))
    yl = jnp.sign(jax.random.normal(jax.random.fold_in(key, 2), (B, L)))
    mu = jax.random.normal(jax.random.fold_in(key, 3), (B, mt)) * 0.01
    f = jax.jit(lambda *a: ref.sodda_inner_ref(*a, 0.05, "hinge"))
    row("kernel_sodda_inner_ref", _t(f, w0, Xl, yl, mu),
        f"B={B} L={L} mt={mt}")

    Bq, S, H, KV, D = 1, 1024, 8, 2, 64
    q = jax.random.normal(key, (Bq, S, H, D)) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 5), (Bq, S, KV, D)) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 6), (Bq, S, KV, D))
    f = jax.jit(lambda *a: ref.attention_ref(*a, causal=True))
    us = _t(f, q, k, v)
    flops = 4 * Bq * H * S * S * D / 2
    row("kernel_flash_attention_ref", us, f"S={S} gflops={flops/1e9:.2f}")

    from repro.models.ssm import ssd_chunked
    Bs, Ss, Hs, P, N = 2, 1024, 8, 64, 64
    x = jax.random.normal(key, (Bs, Ss, Hs, P)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 7), (Bs, Ss, Hs)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 8), (Hs,)) * 0.2)
    Bm = jax.random.normal(jax.random.fold_in(key, 9), (Bs, Ss, 1, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 10), (Bs, Ss, 1, N)) * 0.3
    f = jax.jit(lambda *a: ssd_chunked(*a, chunk=128))
    row("kernel_ssd_chunked", _t(f, x, dt, A, Bm, Cm), f"S={Ss} H={Hs}")


# ---------------------------------------------------------------------------
# Distributed SODDA step benches (12 fake devices) — communication profile.
# ---------------------------------------------------------------------------
@cpu_children_only
def bench_distributed_sodda():
    import subprocess, sys, os, json
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=12"
import json, time
import jax
from repro.configs.sodda_svm import SoddaConfig
from repro.core import engine, sodda
from repro.data.synthetic import make_svm_data
cfg = SoddaConfig(P=4, Q=3, n=2000, m=300, L=32, lr0=0.05)
X, y, _ = make_svm_data(jax.random.PRNGKey(0), cfg.N, cfg.M)
out = {}
mesh = engine.make_mesh_for(cfg)
for gather in (True, False):
    step = engine.make_step(cfg, "shard_map", mesh=mesh, gather_deltas=gather)
    s = sodda.init_state(jax.random.PRNGKey(1), cfg.M)
    s = step(s, X, y)  # compile
    t0 = time.perf_counter()
    for _ in range(5): s = step(s, X, y)
    jax.block_until_ready(s.w)
    out["gather" if gather else "psum"] = (time.perf_counter()-t0)/5*1e6
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    try:
        p = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=560)
        data = json.loads(p.stdout.strip().splitlines()[-1])
        row("dist_sodda_step_allgather", data["gather"], "12dev 4x3 grid")
        row("dist_sodda_step_psum", data["psum"],
            f"gather_speedup={data['psum']/data['gather']:.2f}x")
    except Exception as e:  # pragma: no cover
        row("dist_sodda_step", 0.0, f"SKIP ({type(e).__name__})")


# ---------------------------------------------------------------------------
# Scan-compiled driver vs the per-iteration Python loop, per backend, on the
# conformance problem — the dispatch-overhead pitfall the paper's Spark
# predecessors hit, measured. Emits the machine-readable BENCH_sodda.json
# (us/iter + loss-vs-flops trajectory per backend, schema bench_sodda/v1).
# ---------------------------------------------------------------------------
BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "results",
                          "BENCH_sodda.json")


# benchmarked in this order when registered + runnable; backends registered
# but absent here (e.g. from plugins) are appended at the end. async-mesh
# runs after the sync shard_map cell so its us/iter can be reported against
# the synchronous mesh baseline it must beat.
_DRIVER_BACKEND_ORDER = ("reference", "pallas", "radisa-avg", "async",
                         "shard_map", "shard_map+pallas", "async-mesh")


def _resolve_driver_backends(cfg):
    """Every registered backend runnable on this host, in bench order.

    The mesh backends (engine.MESH_BACKENDS: shard_map, shard_map+pallas,
    async-mesh) join only when the host has the device grid (run under
    XLA_FLAGS=--xla_force_host_platform_device_count=12, as the CI
    bench-smoke job does, to bench all of them).
    """
    import jax as _jax
    from repro.core import engine
    registered = engine.available_backends()
    ordered = [b for b in _DRIVER_BACKEND_ORDER if b in registered]
    ordered += [b for b in registered if b not in ordered]
    have_mesh = _jax.local_device_count() >= cfg.P * cfg.Q
    return [b for b in ordered
            if have_mesh or b not in engine.MESH_BACKENDS], have_mesh


def bench_driver(iters: int = 240, reps: int = 3, out_path: str = None):
    # iters=240 (up from 60): the scan run has a fixed per-dispatch cost —
    # for the async backend that includes its one-off warm-up exchange —
    # and fewer iterations under-amortize it, overstating us/iter for every
    # backend (the same pitfall the python-loop comparison documents)
    from repro.core import driver, engine, radisa, sodda
    from repro.core.distributed import iteration_collective_bytes
    from repro.core.sodda import init_state
    from repro.testing import make_problem, small_fixture_config

    cfg = small_fixture_config()
    X, y = make_problem(cfg)
    key = jax.random.PRNGKey(1)

    backends, have_mesh = _resolve_driver_backends(cfg)
    mesh = engine.make_mesh_for(cfg) if have_mesh else None
    row("driver_backends_resolved", 0.0,
        f"{'+'.join(backends)} (devices={jax.local_device_count()})")

    flops_per_iter = {b: (radisa.radisa_avg_iteration_flops(cfg)
                          if b == "radisa-avg" else sodda.iteration_flops(cfg))
                      for b in backends}
    payload = {"schema": "bench_sodda/v1",
               "problem": {"name": cfg.name, "P": cfg.P, "Q": cfg.Q,
                           "N": cfg.N, "M": cfg.M, "L": cfg.L,
                           "loss": cfg.loss},
               "iters": iters, "reps": reps, "backends": {}}

    for backend in backends:
        kw = {"mesh": mesh} if backend in engine.MESH_BACKENDS else {}
        try:
            compiled = driver.make_run(cfg, iters, backend, record_every=1,
                                       **kw)
            # mesh-backend states are laid out in the program's output
            # sharding so donation aliases (place_initial_state) — the
            # timed dispatch then rewrites the iterate in place, as a
            # production run would
            fresh = lambda: driver.place_initial_state(
                init_state(jnp.array(key, copy=True), cfg.M), cfg, backend,
                mesh)
            # _t warms once then times reps; run_python_loop's step/objective
            # executables are lru-cached in the driver, so its warmup pass
            # compiles everything the timed passes reuse
            scan_us = _t(lambda: compiled(fresh(), X, y), reps=reps) / iters
            # the loop baseline pays its dispatch + host sync PER iteration,
            # so its us/iter is iteration-count-independent — time it at a
            # capped length instead of burning 4x wall-clock for the same
            # number (only the scan cell has fixed cost to amortize over
            # the full iters); the regime is recorded as loop_iters in the
            # payload so artifact consumers see the mixed measurement
            loop_iters = min(iters, 60)
            loop_us = _t(lambda: driver.run_python_loop(key, (X, y), cfg,
                                                        loop_iters, backend,
                                                        **kw),
                         reps=reps) / loop_iters

            _, scan_hist = driver.run(key, (X, y), cfg, iters, backend, **kw)
        except Exception as e:
            # a registered backend that cannot lower on this platform is a
            # warning row, not a bench abort — the remaining cells still
            # run. First line only: lowering errors are multi-line and
            # comma-laden, which would mangle the CSV stream.
            reason = (str(e).splitlines() or ["?"])[0][:120]
            row(f"driver_{backend}_scan", 0.0,
                f"WARN failed to lower/run ({type(e).__name__}: {reason})")
            continue
        fpi = flops_per_iter[backend]
        payload["backends"][backend] = {
            "flops_per_iter": fpi,
            **({"collective_bytes_per_iter":
                iteration_collective_bytes(cfg)}
               if backend in engine.MESH_BACKENDS else {}),
            # the loop trajectory is F32-identical to the scan's (asserted
            # per backend by the driver parity tests), so it is recorded
            # once from the scan run instead of re-paying iters individual
            # dispatches; loop_iters is the timing regime of us_per_iter
            "python_loop": {"us_per_iter": loop_us,
                            "loop_iters": loop_iters,
                            "trajectory_source": "scan_driver",
                            "trajectory": _traj(scan_hist, fpi)},
            "scan_driver": {"us_per_iter": scan_us,
                            "trajectory": _traj(scan_hist, fpi)},
            "speedup": loop_us / scan_us,
        }
        row(f"driver_{backend}_scan", scan_us,
            f"loop_us={loop_us:.1f} speedup={loop_us/scan_us:.2f}x "
            f"final_loss={scan_hist[-1][1]:.4f}")

    # the async-mesh acceptance cell: its us/iter against the *sync*
    # shard_map baseline (same mesh, same collectives — only the schedule
    # differs), plus the per-iteration wire volume both cells ship. On real
    # interconnects the stale schedule buys up to the mu-psum latency per
    # iteration; on the fake single-host device grid the collectives are
    # memcpys, so the ratio mostly proves the async cell pays no overhead.
    sm, am = payload["backends"].get("shard_map"), \
        payload["backends"].get("async-mesh")
    if sm and am:
        ratio = am["scan_driver"]["us_per_iter"] / \
            sm["scan_driver"]["us_per_iter"]
        am["vs_shard_map_us_ratio"] = ratio
        bytes_total = am["collective_bytes_per_iter"]["total"]
        row("driver_async_mesh_vs_shard_map",
            am["scan_driver"]["us_per_iter"],
            f"sync_us={sm['scan_driver']['us_per_iter']:.1f} "
            f"ratio={ratio:.2f}x collective_bytes/iter={bytes_total:.0f}")

    out_path = out_path or BENCH_JSON
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    # regenerating the per-backend cells must not drop the independently
    # produced blocks (large_problem from bench_driver_large, streaming
    # from bench_streaming — both separate, more expensive cells) — carry
    # them over from the old file
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                old = json.load(f)
            for block in ("large_problem", "streaming", "supervision",
                          "tuning", "multihost", "multihost_large"):
                if old.get(block) is not None:
                    payload[block] = old[block]
        except (ValueError, OSError):
            pass  # unreadable old artifact: write the fresh payload as-is
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    row("driver_bench_json", 0.0, os.path.relpath(out_path))
    return payload


def _traj(hist, flops_per_iter):
    return {"t": [t for t, _ in hist],
            "flops": [t * flops_per_iter for t, _ in hist],
            "loss": [v for _, v in hist]}


# ---------------------------------------------------------------------------
# Paper-Table-1-sized cell: the 50k x 6k problem on the TiledDataPlane only
# (the dense plane's host-global array is exactly what this size is meant to
# retire). Runs in its own subprocess so (a) the 5x3 grid gets its 15 forced
# host devices and (b) tracemalloc/ru_maxrss measure THIS cell, not whatever
# the harness allocated before. Opt-in: the cell moves ~1.2 GB of device-
# resident tiles and pays a large-shape compile, so the default bench run
# skips it unless RUN_LARGE_BENCH=1 or --only driver_large selects it.
# ---------------------------------------------------------------------------
LARGE_ITERS_DEFAULT = 4

_LARGE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=15"
import json, resource, time, tracemalloc
tracemalloc.start()
import jax
from repro.configs.sodda_svm import SoddaConfig
from repro.core import driver, engine
from repro.data.plane import TiledDataPlane

ITERS = %(iters)d
# Table-1-sized (50k x 6k on the paper's 5x3 grid); lr0 calibrated to this
# instance (the paper's lr0=1.0 — and the small fixtures' 0.05 — overshoot
# at M=6000: the hinge objective climbs for the first ~10 iterations)
cfg = SoddaConfig(name="sodda-table1-50kx6k", P=5, Q=3, n=10_000, m=2_000,
                  L=64, lr0=0.01)
plane = TiledDataPlane(jax.random.PRNGKey(0), cfg.N, cfg.M, cfg.P, cfg.Q)
mesh = engine.make_mesh_for(cfg)
import jax.numpy as jnp
from repro.core.sodda import init_state

# placement (per-tile generation + device_put) happens once, OUTSIDE the
# timed region — us_per_iter measures the warm scan dispatch only
X, y = plane.materialize_for("shard_map", mesh=mesh)
compiled = driver.make_run(cfg, ITERS, "shard_map", record_every=ITERS,
                           mesh=mesh)
key = jax.random.PRNGKey(1)
fresh = lambda: driver.place_initial_state(
    init_state(jnp.array(key, copy=True), cfg.M), cfg, "shard_map", mesh)
jax.block_until_ready(compiled(fresh(), X, y))  # compile + warm
t0 = time.perf_counter()
_, fs = compiled(fresh(), X, y)
jax.block_until_ready(fs)
us = (time.perf_counter() - t0) / ITERS * 1e6
hist = list(zip(driver.record_ticks(ITERS, ITERS), [float(f) for f in fs]))
print(json.dumps({
    "problem": {"name": cfg.name, "P": cfg.P, "Q": cfg.Q, "N": cfg.N,
                "M": cfg.M, "L": cfg.L, "loss": cfg.loss},
    "backend": "shard_map", "plane": "tiled", "iters": ITERS,
    "us_per_iter": us, "final_loss": hist[-1][1],
    # tracemalloc tracks host-side (python/numpy) allocations — the staging
    # memory a data plane costs. The fake CPU devices' buffers live in
    # process RSS instead, reported alongside for transparency.
    "peak_host_bytes": tracemalloc.get_traced_memory()[1],
    "rss_peak_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                      * 1024,
    "dense_xy_bytes": plane.dense_nbytes,
}))
"""


@cpu_children_only
def run_large_cell(iters: int = LARGE_ITERS_DEFAULT, timeout: int = 1200):
    """Run the Table-1-sized tiled cell in a fresh 15-device subprocess and
    return its ``large_problem`` payload dict (see validate_bench)."""
    import subprocess, sys
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _LARGE_SCRIPT % {"iters": iters}],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"large cell failed:\n{p.stderr[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


@cpu_children_only
def bench_driver_large(iters: int = LARGE_ITERS_DEFAULT, out_path: str = None,
                       force: bool = False):
    """The ROADMAP "Large-problem BENCH trend tracking" cell: Table-1-sized
    (50k x 6k) SODDA on the tiled plane, merged into BENCH_sodda.json as
    the ``large_problem`` block."""
    if not (force or os.environ.get("RUN_LARGE_BENCH")):
        row("driver_large", 0.0,
            "SKIP (opt-in: RUN_LARGE_BENCH=1 or --only driver_large)")
        return None
    try:
        lp = run_large_cell(iters=iters)
    except Exception as e:  # pragma: no cover - depends on host capacity
        reason = (str(e).splitlines() or ["?"])[0][:120]
        row("driver_large", 0.0, f"WARN ({type(e).__name__}: {reason})")
        return None
    row("driver_large_scan", lp["us_per_iter"],
        f"N={lp['problem']['N']} M={lp['problem']['M']} "
        f"final_loss={lp['final_loss']:.4f} "
        f"peak_host_mb={lp['peak_host_bytes']/1e6:.1f} "
        f"dense_mb={lp['dense_xy_bytes']/1e6:.1f} "
        f"rss_peak_mb={lp['rss_peak_bytes']/1e6:.0f}")
    out_path = out_path or BENCH_JSON
    if os.path.exists(out_path):
        with open(out_path) as f:
            payload = json.load(f)
        payload["large_problem"] = lp
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
        row("driver_large_json", 0.0, os.path.relpath(out_path))
    else:
        row("driver_large_json", 0.0,
            f"WARN {os.path.relpath(out_path)} missing - run the driver "
            "bench first to merge the large_problem block")
    return lp


# ---------------------------------------------------------------------------
# Streaming out-of-core cell: a multi-epoch resumable run on the streaming
# plane, in its own subprocess (tracemalloc must start before jax imports to
# see the staging allocations, and the cell must not inherit the harness's
# XLA_FLAGS). The claims it records: the prefetcher hides window generation
# behind the compiled segments (prefetch_overlap_ratio), and the tile budget
# keeps host staging below ONE dense window even though the stream shipped
# `epochs` of them (peak_host_bytes < dense_xy_bytes, enforced by
# validate_bench like the large_problem cell).
# ---------------------------------------------------------------------------
STREAM_ITERS_DEFAULT = 16
STREAM_SEGMENT_DEFAULT = 4

_STREAM_SCRIPT = r"""
import os
os.environ.pop("XLA_FLAGS", None)  # single default device: reference backend
import json, resource, tempfile, time, tracemalloc
tracemalloc.start()
import jax
from repro.configs.sodda_svm import SoddaConfig
from repro.core import driver
from repro.data.plane import StreamingDataPlane

ITERS, SEG = %(iters)d, %(seg)d
# big enough that one dense (N, M) window (160 MB) dwarfs import-time and
# bookkeeping allocations, small enough for a CI smoke cell
cfg = SoddaConfig(name="sodda-stream-20kx2k", P=4, Q=2, n=5_000, m=1_000,
                  L=32, lr0=0.05)
plane = StreamingDataPlane(jax.random.PRNGKey(0), cfg.N, cfg.M, cfg.P, cfg.Q,
                           # one window of blocks: the out-of-core regime —
                           # epoch e+1's tiles evict epoch e's as the
                           # prefetcher generates them
                           resident_tile_budget=cfg.P * cfg.Q + cfg.P)
stats = {}
with tempfile.TemporaryDirectory() as ckpt:
    t0 = time.perf_counter()
    _, hist = driver.run_resumable(jax.random.PRNGKey(1), plane, cfg, ITERS,
                                   "reference", checkpoint_dir=ckpt,
                                   segment_iters=SEG, record_every=SEG,
                                   stream_stats=stats)
    wall = time.perf_counter() - t0
epochs = (ITERS + SEG - 1) // SEG
cache = stats.pop("cache")
print(json.dumps({
    "problem": {"name": cfg.name, "P": cfg.P, "Q": cfg.Q, "N": cfg.N,
                "M": cfg.M, "L": cfg.L, "loss": cfg.loss},
    "backend": "reference", "plane": "streaming",
    "iters": ITERS, "segment_iters": SEG, "epochs": epochs,
    # whole-run wall time over iters — includes the one segment-program
    # compile, which is the realistic cold-start a streaming run pays once
    "us_per_iter": wall / ITERS * 1e6,
    "final_loss": hist[-1][1],
    "prefetch_overlap_ratio": stats.pop("overlap_ratio"),
    "prefetch": stats,
    "cache": cache,
    "resident_tile_budget": plane.resident_tile_budget,
    # tracemalloc tracks host-side (python/numpy) staging — what the budget
    # bounds; XLA buffers live in RSS, reported alongside for transparency
    "peak_host_bytes": tracemalloc.get_traced_memory()[1],
    "rss_peak_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                      * 1024,
    "dense_xy_bytes": plane.dense_nbytes,
    "stream_total_bytes": epochs * plane.dense_nbytes,
}))
"""


@cpu_children_only
def run_streaming_cell(iters: int = STREAM_ITERS_DEFAULT,
                       segment_iters: int = STREAM_SEGMENT_DEFAULT,
                       timeout: int = 1200):
    """Run the streaming cell in a fresh subprocess and return its
    ``streaming`` payload dict (see validate_bench)."""
    import subprocess, sys
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c",
         _STREAM_SCRIPT % {"iters": iters, "seg": segment_iters}],
        env=env, capture_output=True, text=True, timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"streaming cell failed:\n{p.stderr[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


@cpu_children_only
def bench_streaming(iters: int = STREAM_ITERS_DEFAULT,
                    segment_iters: int = STREAM_SEGMENT_DEFAULT,
                    out_path: str = None):
    """The streaming out-of-core cell, merged into BENCH_sodda.json as the
    ``streaming`` block (fields documented in docs/benchmarks.md)."""
    try:
        cell = run_streaming_cell(iters=iters, segment_iters=segment_iters)
    except Exception as e:  # pragma: no cover - depends on host capacity
        reason = (str(e).splitlines() or ["?"])[0][:120]
        row("driver_streaming", 0.0, f"WARN ({type(e).__name__}: {reason})")
        return None
    row("driver_streaming_scan", cell["us_per_iter"],
        f"epochs={cell['epochs']} final_loss={cell['final_loss']:.4f} "
        f"overlap={cell['prefetch_overlap_ratio']:.2f} "
        f"peak_host_mb={cell['peak_host_bytes']/1e6:.1f} "
        f"dense_mb={cell['dense_xy_bytes']/1e6:.1f} "
        f"stream_total_mb={cell['stream_total_bytes']/1e6:.1f}")
    out_path = out_path or BENCH_JSON
    if os.path.exists(out_path):
        with open(out_path) as f:
            payload = json.load(f)
        payload["streaming"] = cell
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
        row("driver_streaming_json", 0.0, os.path.relpath(out_path))
    else:
        row("driver_streaming_json", 0.0,
            f"WARN {os.path.relpath(out_path)} missing - run the driver "
            "bench first to merge the streaming block")
    return cell


# ---------------------------------------------------------------------------
# Supervision overhead cell: what does wrapping run_resumable in the
# SegmentSupervisor cost on the fault-free path, and what do in-scan
# io_callback commits add on top? Measured as us/iter ratios (supervised /
# bare) at commit_every=0 (host-boundary commits only) and a small
# commit_every (the preemptible-segment regime), merged into
# BENCH_sodda.json as the ``supervision`` block.
# ---------------------------------------------------------------------------
SUP_ITERS_DEFAULT = 64
SUP_SEGMENT_DEFAULT = 16
SUP_COMMIT_SMALL_DEFAULT = 4


def bench_supervision(iters: int = SUP_ITERS_DEFAULT,
                      segment_iters: int = SUP_SEGMENT_DEFAULT,
                      commit_small: int = SUP_COMMIT_SMALL_DEFAULT,
                      reps: int = 3, out_path: str = None):
    import tempfile

    from repro.core import driver
    from repro.distributed.fault_tolerance import SegmentSupervisor
    from repro.testing import make_problem, small_fixture_config

    cfg = small_fixture_config()
    X, y = make_problem(cfg)
    key = jax.random.PRNGKey(1)

    # commit_every must be a multiple of record_every (every in-scan commit
    # carries a complete history prefix), so both cells record at the
    # commit cadence — identical recording cost, the commit writes are the
    # only difference between them
    record_every = commit_small

    def bare(d, ce):
        driver.run_resumable(key, (X, y), cfg, iters, "reference",
                             checkpoint_dir=d, segment_iters=segment_iters,
                             record_every=record_every, commit_every=ce)

    def supervised(d, ce):
        SegmentSupervisor().run_resumable(
            key, (X, y), cfg, iters, "reference", checkpoint_dir=d,
            segment_iters=segment_iters, record_every=record_every,
            commit_every=ce)

    def timed(run_fn, ce):
        # every attempt gets a fresh dir: a reused one would trip the
        # resume guard and time a no-op restore instead of the run. The
        # warm-up attempt pays the segment-program compile (cached per
        # commit grouping), so the timed reps measure the warm path.
        with tempfile.TemporaryDirectory() as d:
            run_fn(d, ce)
        t0 = time.perf_counter()
        for _ in range(reps):
            with tempfile.TemporaryDirectory() as d:
                run_fn(d, ce)
        return (time.perf_counter() - t0) / reps / iters * 1e6

    cells = {}
    for label, ce in (("commit_every_0", 0),
                      ("commit_every_small", commit_small)):
        b_us, s_us = timed(bare, ce), timed(supervised, ce)
        cells[label] = {"commit_every": ce, "bare_us_per_iter": b_us,
                        "supervised_us_per_iter": s_us,
                        "supervision_overhead_ratio": s_us / b_us}
        row(f"driver_supervision_{label}", s_us,
            f"bare_us={b_us:.1f} overhead={s_us / b_us:.2f}x")
    block = {"problem": {"name": cfg.name, "P": cfg.P, "Q": cfg.Q,
                         "N": cfg.N, "M": cfg.M, "L": cfg.L,
                         "loss": cfg.loss},
             "backend": "reference", "iters": iters,
             "segment_iters": segment_iters, "record_every": record_every,
             "reps": reps, "cells": cells,
             # what the in-scan commits themselves cost, supervision held
             # constant: supervised-at-small vs supervised-at-0
             "in_scan_commit_overhead_ratio":
                 cells["commit_every_small"]["supervised_us_per_iter"]
                 / cells["commit_every_0"]["supervised_us_per_iter"]}
    row("driver_supervision_in_scan_commits", 0.0,
        f"commit_every={commit_small} "
        f"overhead={block['in_scan_commit_overhead_ratio']:.2f}x")
    out_path = out_path or BENCH_JSON
    if os.path.exists(out_path):
        with open(out_path) as f:
            payload = json.load(f)
        payload["supervision"] = block
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
        row("driver_supervision_json", 0.0, os.path.relpath(out_path))
    else:
        row("driver_supervision_json", 0.0,
            f"WARN {os.path.relpath(out_path)} missing - run the driver "
            "bench first to merge the supervision block")
    return block


# ---------------------------------------------------------------------------
# Kernel-autotuning cell: the BlockConfig the autotuner picks for the bench
# kernel shape vs the single-tile default, measured through ops.sodda_inner.
# On CPU (interpret mode) the roofline model never tiles — tuned == default
# and the ratio is exactly 1.0 by identity, the no-regression anchor. On a
# compiled platform the measured-refinement path arbitrates, and the cell
# keeps the better of the two schedules either way, so the recorded
# tuned_vs_default_us_ratio is <= 1.0 by construction.
# ---------------------------------------------------------------------------
TUNING_B, TUNING_L, TUNING_MT = 8, 32, 256


def bench_tuning(reps: int = 5, out_path: str = None):
    from repro import platform as repro_platform
    from repro.kernels import ops, tuning

    plat = repro_platform.platform()
    interpret = repro_platform.interpret_default(plat)
    B, L, mt = TUNING_B, TUNING_L, TUNING_MT
    loss = "hinge"
    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(size=(B, mt)), jnp.float32)
    Xl = jnp.asarray(rng.normal(size=(B, L, mt)), jnp.float32)
    yl = jnp.asarray(np.sign(rng.normal(size=(B, L)) + 0.1), jnp.float32)
    mu = jnp.asarray(rng.normal(size=(B, mt)) * 0.01, jnp.float32)

    def time_config(config, n_reps=reps):
        return _t(lambda: ops.sodda_inner(w0, Xl, yl, mu, 0.05, loss,
                                          block_l=config.block_l),
                  reps=n_reps)

    # measured refinement only where a compiled (non-interpret) path
    # exists; in interpret mode timing the Python-walked grid would tune
    # the emulator, not the kernel
    measure = (lambda c: time_config(c) * 1e-6) if not interpret else None
    default = tuning.default_config(L, mt)
    tuned = tuning.autotune(loss, L, mt, platform=plat, measure=measure)
    default_us = time_config(default)
    if tuned == default:
        tuned_us = default_us  # same schedule -> same executable
    else:
        tuned_us = time_config(tuned)
        if tuned_us > default_us:
            # the refinement pass already timed both; if bench-time noise
            # still inverts them, record the better schedule — the cell's
            # contract is "never worse than the default"
            tuned, tuned_us = default, default_us
    block = {"loss": loss, "B": B, "L": L, "mt": mt,
             "platform": plat, "interpret": interpret,
             "default_config": default.as_dict(),
             "tuned_config": tuned.as_dict(),
             "default_us": default_us, "tuned_us": tuned_us,
             "tuned_vs_default_us_ratio": tuned_us / default_us,
             "legal_block_l": [c.block_l for c in
                               tuning.legal_configs(L, tuning.padded_mt(mt))]}
    row("tuning_selected", tuned_us,
        f"block_l={tuned.block_l} default_block_l={default.block_l} "
        f"ratio={block['tuned_vs_default_us_ratio']:.2f}x platform={plat}")
    out_path = out_path or BENCH_JSON
    if os.path.exists(out_path):
        with open(out_path) as f:
            payload = json.load(f)
        payload["tuning"] = block
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
        row("tuning_json", 0.0, os.path.relpath(out_path))
    else:
        row("tuning_json", 0.0,
            f"WARN {os.path.relpath(out_path)} missing - run the driver "
            "bench first to merge the tuning block")
    return block


# ---------------------------------------------------------------------------
# Multi-process mesh cells: the SAME compiled programs on a mesh that spans
# coordinated processes (repro.distributed.multihost + gloo CPU collectives),
# so the psums cross a real inter-process boundary instead of being
# single-host memcpys. Two cells: a 2-process smoke cell on the conformance
# problem (the async-mesh vs shard_map ratio over real collectives — merged
# as the ``multihost`` block, required by bench-smoke), and the TRUE paper
# Table-1 250k x 18k cell on 5 processes x 3 devices with host-local tile
# placement (merged as ``multihost_large``, opt-in like driver_large).
# ---------------------------------------------------------------------------
MULTIHOST_ITERS_DEFAULT = 24
MULTIHOST_PROCESSES_DEFAULT = 2

_MULTIHOST_SCRIPT = r"""
import hashlib, json, resource, time, tracemalloc
tracemalloc.start()
import jax
import jax.numpy as jnp
from repro.core import driver, engine
from repro.core.sodda import init_state
from repro.data.plane import TiledDataPlane
from repro.distributed import multihost
from repro.testing import small_fixture_config

ITERS, REPS = %(iters)d, %(reps)d
cfg = small_fixture_config()
plane = TiledDataPlane(jax.random.PRNGKey(0), cfg.N, cfg.M, cfg.P, cfg.Q)
mesh = engine.make_mesh_for(cfg)
multihost.connect_mesh_collectives(mesh)
X, y = plane.materialize_for("shard_map", mesh=mesh)
key = jax.random.PRNGKey(1)
out = {"process_index": multihost.process_index(), "backends": {}}
for backend in ("shard_map", "async-mesh"):
    compiled = driver.make_run(cfg, ITERS, backend, record_every=ITERS,
                               mesh=mesh)
    fresh = lambda b=backend: driver.place_initial_state(
        init_state(jnp.array(key, copy=True), cfg.M), cfg, b, mesh)
    final, fs = compiled(fresh(), X, y)
    jax.block_until_ready((final, fs))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(REPS):
        final, fs = compiled(fresh(), X, y)
        jax.block_until_ready((final, fs))
    us = (time.perf_counter() - t0) / REPS / ITERS * 1e6
    w = multihost.fetch_local(final.w)
    out["backends"][backend] = {
        "us_per_iter": us,
        "w_sha256": hashlib.sha256(w.tobytes()).hexdigest()}
out["peak_host_bytes"] = tracemalloc.get_traced_memory()[1]
out["rss_peak_bytes"] = resource.getrusage(
    resource.RUSAGE_SELF).ru_maxrss * 1024
print(json.dumps(out))
"""


@cpu_children_only
def run_multihost_cell(iters: int = MULTIHOST_ITERS_DEFAULT, reps: int = 3,
                       num_processes: int = MULTIHOST_PROCESSES_DEFAULT,
                       timeout: int = 1200):
    """Run the 2-process smoke cell through the launch harness and return
    the merged ``multihost`` block (see validate_bench)."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.testing import launch_coordinated
    from repro.testing.fixtures import small_fixture_config

    cfg = small_fixture_config()
    if (cfg.P * cfg.Q) % num_processes:
        raise ValueError(
            f"{num_processes} processes cannot evenly split the "
            f"{cfg.P}x{cfg.Q} device grid")
    dpp = cfg.P * cfg.Q // num_processes
    results = launch_coordinated(
        _MULTIHOST_SCRIPT % {"iters": iters, "reps": reps},
        num_processes, dpp, timeout=timeout)
    bad = [r for r in results if r.returncode != 0]
    if bad:
        raise RuntimeError(
            f"multihost cell rank failed:\n{bad[0].stderr[-2000:]}")
    ranks = [json.loads(r.stdout.strip().splitlines()[-1]) for r in results]
    lead = next(r for r in ranks if r["process_index"] == 0)
    sums = {b: {r["backends"][b]["w_sha256"] for r in ranks}
            for b in lead["backends"]}
    block = {
        "problem": {"name": cfg.name, "P": cfg.P, "Q": cfg.Q, "N": cfg.N,
                    "M": cfg.M, "L": cfg.L, "loss": cfg.loss},
        "plane": "tiled", "collectives": "gloo",
        "num_processes": num_processes, "devices_per_process": dpp,
        "iters": iters, "reps": reps,
        "backends": {b: {"us_per_iter": c["us_per_iter"]}
                     for b, c in lead["backends"].items()},
        # every rank must finalize the same iterate — the cross-process
        # agreement check the degeneracy tests enforce bitwise
        "ranks_agree": all(len(s) == 1 for s in sums.values()),
        "peak_host_bytes": max(r["peak_host_bytes"] for r in ranks),
        "rss_peak_bytes": max(r["rss_peak_bytes"] for r in ranks),
    }
    sm = block["backends"].get("shard_map")
    am = block["backends"].get("async-mesh")
    if sm and am:
        am["vs_shard_map_us_ratio"] = am["us_per_iter"] / sm["us_per_iter"]
    return block


@cpu_children_only
def bench_multihost(iters: int = MULTIHOST_ITERS_DEFAULT, reps: int = 3,
                    out_path: str = None):
    """The 2-process mesh smoke cell, merged into BENCH_sodda.json as the
    ``multihost`` block (fields documented in docs/benchmarks.md)."""
    try:
        block = run_multihost_cell(iters=iters, reps=reps)
    except Exception as e:  # pragma: no cover - depends on host capacity
        reason = (str(e).splitlines() or ["?"])[0][:120]
        row("driver_multihost", 0.0, f"WARN ({type(e).__name__}: {reason})")
        return None
    am = block["backends"]["async-mesh"]
    row("driver_multihost_shard_map",
        block["backends"]["shard_map"]["us_per_iter"],
        f"procs={block['num_processes']}x{block['devices_per_process']}dev "
        f"ranks_agree={block['ranks_agree']}")
    row("driver_multihost_async_mesh", am["us_per_iter"],
        f"vs_shard_map={am['vs_shard_map_us_ratio']:.2f}x "
        "(cross-process gloo collectives)")
    out_path = out_path or BENCH_JSON
    if os.path.exists(out_path):
        with open(out_path) as f:
            payload = json.load(f)
        payload["multihost"] = block
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
        row("driver_multihost_json", 0.0, os.path.relpath(out_path))
    else:
        row("driver_multihost_json", 0.0,
            f"WARN {os.path.relpath(out_path)} missing - run the driver "
            "bench first to merge the multihost block")
    return block


MULTIHOST_LARGE_ITERS_DEFAULT = 2

_MULTIHOST_LARGE_SCRIPT = r"""
import faulthandler, json, resource, sys, time, tracemalloc
tracemalloc.start()
# hang watchdog: if any phase wedges, dump every thread's stack to stderr
# (the harness surfaces stderr on kill) instead of dying silently
faulthandler.dump_traceback_later(1800, repeat=True, exit=False)
import jax
import jax.numpy as jnp
from repro.configs.sodda_svm import SoddaConfig
from repro.core import driver, engine
from repro.core.sodda import init_state
from repro.data.plane import TiledDataPlane
from repro.distributed import multihost

_T0 = time.perf_counter()
def stage(msg):  # progress marks on stderr: surfaced if the harness kills us
    print(f"[{time.perf_counter() - _T0:8.1f}s] {msg}", file=sys.stderr,
          flush=True)

ITERS = %(iters)d
# the paper's ACTUAL Table-1 instance: 250k x 18k on the 5x3 grid, one
# process per data row-block (host-local tile placement: each host
# generates and holds only its 1/P of the problem)
cfg = SoddaConfig(name="sodda-table1-250kx18k", P=5, Q=3, n=50_000,
                  m=6_000, L=64, lr0=0.01)
plane = TiledDataPlane(jax.random.PRNGKey(0), cfg.N, cfg.M, cfg.P, cfg.Q)
mesh = engine.make_mesh_for(cfg)
# establish every gloo channel NOW, while the ranks are still within
# milliseconds of each other: entering a fresh communicator's rendezvous
# minutes apart (generation time varies per rank) wedges the runtime
multihost.connect_mesh_collectives(mesh)
stage("collectives connected; materializing local tiles")
X, y = plane.materialize_for("shard_map", mesh=mesh)
jax.block_until_ready((X, y))
multihost.barrier("tiles-placed")  # re-sync after the uneven generation
stage("tiles placed; compiling + warming")
compiled = driver.make_run(cfg, ITERS, "shard_map", record_every=ITERS,
                           mesh=mesh)
key = jax.random.PRNGKey(1)
fresh = lambda: driver.place_initial_state(
    init_state(jnp.array(key, copy=True), cfg.M), cfg, "shard_map", mesh)
jax.block_until_ready(compiled(fresh(), X, y))  # compile + warm
stage("warm dispatch done; timing")
t0 = time.perf_counter()
final, fs = compiled(fresh(), X, y)
jax.block_until_ready((final, fs))
us = (time.perf_counter() - t0) / ITERS * 1e6
stage("timed dispatch done")
print(json.dumps({
    "process_index": multihost.process_index(),
    "us_per_iter": us,
    "loss_t0": float(multihost.fetch_local(fs)[0]),
    "peak_host_bytes": tracemalloc.get_traced_memory()[1],
    "rss_peak_bytes": resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024,
    "dense_xy_bytes": plane.dense_nbytes,
}))
"""


@cpu_children_only
def run_multihost_large_cell(iters: int = MULTIHOST_LARGE_ITERS_DEFAULT,
                             timeout: int = 5400):
    """Run the 250k x 18k Table-1 cell on 5 coordinated processes (3 devices
    each) and return the ``multihost_large`` block (see validate_bench)."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.testing import launch_coordinated

    P, Q = 5, 3
    results = launch_coordinated(
        _MULTIHOST_LARGE_SCRIPT % {"iters": iters}, P, Q, timeout=timeout)
    bad = [r for r in results if r.returncode != 0]
    if bad:
        raise RuntimeError(
            f"multihost large cell rank failed:\n{bad[0].stderr[-2000:]}")
    ranks = [json.loads(r.stdout.strip().splitlines()[-1]) for r in results]
    lead = next(r for r in ranks if r["process_index"] == 0)
    dense = lead["dense_xy_bytes"]
    return {
        "problem": {"name": "sodda-table1-250kx18k", "P": P, "Q": Q,
                    "N": 250_000, "M": 18_000, "L": 64, "loss": "hinge"},
        "backend": "shard_map", "plane": "tiled", "collectives": "gloo",
        "num_processes": P, "devices_per_process": Q,
        "iters": iters, "us_per_iter": lead["us_per_iter"],
        "loss_t0": lead["loss_t0"],
        # host-local placement claim: NO host ever stages anything close to
        # the dense (N, M) footprint — each holds ~1/num_processes of it
        "peak_host_bytes": max(r["peak_host_bytes"] for r in ranks),
        "rss_peak_bytes": max(r["rss_peak_bytes"] for r in ranks),
        "dense_xy_bytes": dense,
        "per_host_peak_host_bytes": [
            r["peak_host_bytes"]
            for r in sorted(ranks, key=lambda r: r["process_index"])],
    }


@cpu_children_only
def bench_multihost_large(iters: int = MULTIHOST_LARGE_ITERS_DEFAULT,
                          out_path: str = None, force: bool = False):
    """The paper-scale 250k x 18k multi-process cell, merged into
    BENCH_sodda.json as the ``multihost_large`` block. Opt-in like
    driver_large: it moves ~18 GB of tiles across 5 processes."""
    if not (force or os.environ.get("RUN_LARGE_BENCH")):
        row("driver_multihost_large", 0.0,
            "SKIP (opt-in: RUN_LARGE_BENCH=1 or --only multihost_large)")
        return None
    try:
        block = run_multihost_large_cell(iters=iters)
    except Exception as e:  # pragma: no cover - depends on host capacity
        reason = (str(e).splitlines() or ["?"])[0][:120]
        row("driver_multihost_large", 0.0,
            f"WARN ({type(e).__name__}: {reason})")
        return None
    row("driver_multihost_large_scan", block["us_per_iter"],
        f"N={block['problem']['N']} M={block['problem']['M']} "
        f"procs={block['num_processes']}x{block['devices_per_process']}dev "
        f"peak_host_mb={block['peak_host_bytes']/1e6:.1f} "
        f"dense_mb={block['dense_xy_bytes']/1e6:.1f}")
    out_path = out_path or BENCH_JSON
    if os.path.exists(out_path):
        with open(out_path) as f:
            payload = json.load(f)
        payload["multihost_large"] = block
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
        row("driver_multihost_large_json", 0.0, os.path.relpath(out_path))
    else:
        row("driver_multihost_large_json", 0.0,
            f"WARN {os.path.relpath(out_path)} missing - run the driver "
            "bench first to merge the multihost_large block")
    return block


# ---------------------------------------------------------------------------
# Roofline summary from the dry-run results (reads results/dryrun.json)
# ---------------------------------------------------------------------------
def bench_roofline_summary():
    import json, os
    path = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun.json")
    if not os.path.exists(path):
        row("roofline_summary", 0.0, "SKIP (run repro.launch.dryrun first)")
        return
    results = json.load(open(path))
    ok = {k: v for k, v in results.items() if v.get("status") == "ok"
          and k.endswith("|single")}
    for key in sorted(ok):
        r = ok[key]["roofline"]
        row(f"roofline_{key.replace('|', '_')}",
            max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"]) * 1e6,
            f"bottleneck={r['bottleneck']} frac={r['roofline_fraction']:.3f} "
            f"useful={r['useful_flops_fraction']:.2f}")


BENCHES = {
    "paper_convergence": bench_paper_convergence,
    "paper_knob_sweep": bench_paper_knob_sweep,
    "seed_variance": bench_seed_variance,
    "kernels": bench_kernels,
    "driver": bench_driver,
    "driver_large": bench_driver_large,
    "streaming": bench_streaming,
    "supervision": bench_supervision,
    "tuning": bench_tuning,
    "multihost": bench_multihost,
    "multihost_large": bench_multihost_large,
    "distributed_sodda": bench_distributed_sodda,
    "roofline_summary": bench_roofline_summary,
}


def main(argv=None) -> None:
    from repro import platform as repro_platform

    # centralizes the latency-hiding XLA flags / env for the bench host;
    # must precede the first jax backend touch in the benched functions
    repro_platform.configure()
    repro_platform.use_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        if name == "driver_large":
            # explicit selection overrides the opt-in gate
            bench_driver_large(force=args.only == "driver_large")
            continue
        if name == "multihost_large":
            bench_multihost_large(force=args.only == "multihost_large")
            continue
        fn()


if __name__ == "__main__":
    main()
