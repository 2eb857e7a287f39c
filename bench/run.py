"""SODDA fits on the chip: the benchmark's one entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` is a configuration (the data's shape, the
P x Q grid, the backend, the mesh) under a traffic mix (iterations per fit
and the objective's recording cadence). The run

1. makes X and y from ``--seed`` on the cell's devices (``bench/data.py``);
2. compiles the cell's one ``repro.core.driver.make_run`` program, or loads
   it from the compilation cache at ``<checkout>/.jax_cache``, and runs one
   warm-up fit: all of that is ``setup_s``;
3. runs fits back to back for ``--seconds``, one at a time, as one user
   launching training jobs: each builds w = 0 with ``sodda.init_state`` and
   fit i's key ``fold_in(run key, i)``, lays it out with
   ``driver.place_initial_state``, dispatches the program once and fetches
   the objective history. The window ends when the last fit that started
   inside it returns, so every fit in it counts whole;
4. reads the peak HBM of the cell's chips, then replays a sample of the
   window's fits, drawn from the seed, with the plain reference
   (``bench/reference.py``) and holds each to the cell's limits
   (``bench/check.py``, ``bench/limits/<cell>.json``).

With ``--trace 0`` the result carries the end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries the
per-layer metrics, each read by ``bench/metrics/<name>.py`` from the reduced
trace (``bench/trace.py``). The last line of standard output is the result;
the last lines of standard error are the numbers compared and their limits.
Off a TPU, or with fewer chips than the cell asks for, it exits 1 and prints
no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, spec  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GB = 1e9


class CompileClock:
    """Seconds and count of XLA backend compiles, from jax.monitoring."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices_for(cell: spec.Cell, require_tpu: bool = True):
    """The process's devices, or None (and why, on stderr) when they cannot
    hold `cell`: no TPU, or fewer chips than it asks for."""
    import jax

    devices = jax.devices()
    _log(f"device: platform={devices[0].platform} "
         f"kind={devices[0].device_kind} count={len(devices)}")
    if require_tpu and devices[0].platform != "tpu":
        _log(f"FAIL: the benchmark needs a TPU; JAX runs on "
             f"{devices[0].platform}")
        return None
    if len(devices) < cell.chips:
        _log(f"FAIL: {cell.name} needs {cell.chips} chips, found "
             f"{len(devices)}")
        return None
    return devices


class Fits:
    """A cell's data and compiled program: fit i on demand.

    Builds the mesh, makes X and y from the seed on the cell's devices, and
    gets the cell's one ``make_run`` program (compiled on first use).
    """

    def __init__(self, cell: spec.Cell, devices):
        from repro.core import driver, engine

        self.cell = cell
        self.cfg = spec.sodda_config(cell.config)
        self.backend = cell.config["backend"]
        self.mesh = (engine.make_mesh_for(self.cfg)
                     if self.backend in engine.MESH_BACKENDS else None)
        self.used = (list(self.mesh.devices.flat) if self.mesh is not None
                     else devices[:1])
        if len(self.used) != cell.chips:
            raise ValueError(f"{cell.name}: the {self.backend} program uses "
                             f"{len(self.used)} devices, the cell asks for "
                             f"{cell.chips}")
        self.compiled = driver.make_run(self.cfg, cell.fit_iters,
                                        self.backend,
                                        record_every=cell.record_every,
                                        mesh=self.mesh)
        self.X = self.y = self.run_key = None

    def make_data(self, seed: int) -> float:
        """X, y and the run key for `seed`; returns the seconds taken."""
        import jax

        from bench import data

        self.X = self.y = None  # free the last seed's data first
        self.run_key, data_key = data.seed_keys(seed)
        t = time.perf_counter()
        self.X, self.y = jax.block_until_ready(data.generate(
            data_key, self.cfg.N, self.cfg.M, mesh=self.mesh,
            device=self.used[0]))
        return time.perf_counter() - t

    def fit_key(self, i: int):
        import jax

        return jax.random.fold_in(self.run_key, i)

    def fit(self, i: int):
        """Fit i through the program: (final w on device, history)."""
        import jax
        import numpy as np

        from repro.core import driver, sodda

        with jax.profiler.TraceAnnotation("fit.init"):
            state = driver.place_initial_state(
                sodda.init_state(self.fit_key(i), self.cfg.M), self.cfg,
                self.backend, self.mesh)
        with jax.profiler.TraceAnnotation("fit.dispatch"):
            final, fs = self.compiled(state, self.X, self.y)
        with jax.profiler.TraceAnnotation("fit.fetch"):
            hist = np.asarray(fs)
        return final.w, hist

    def peak_bytes(self) -> int:
        """The largest ``peak_bytes_in_use`` over the cell's chips."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.used)

    def program_bytes(self) -> int:
        """Bytes the fit program holds on each chip while it runs: its
        arguments, outputs and temporaries, less what outputs alias, from
        the compiled program's buffer assignment (``memory_stats`` on the
        TPU leaves the temporaries out)."""
        from repro.core import driver, sodda

        state = driver.place_initial_state(
            sodda.init_state(self.fit_key(0), self.cfg.M), self.cfg,
            self.backend, self.mesh)
        mem = self.compiled.lower(state, self.X, self.y).compile() \
            .memory_analysis()
        return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    def reference_fit(self, tiles, i: int):
        """Fit i replayed by the plain reference on `tiles`."""
        from bench import reference

        return reference.fit(tiles, self.cell.config, self.fit_key(i),
                             self.cell.fit_iters, self.cell.record_every)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t0: float = T0, require_tpu: bool = True):
    """One run of `cell`; the result object, or None off the chip."""
    import jax

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        return _run(cell, seed, seconds, trace, t0, require_tpu, clock)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)


def _run(cell, seed, seconds, trace, t0, require_tpu, clock):
    import jax
    import numpy as np

    from bench import reference, work
    from bench import trace as trace_mod

    devices = devices_for(cell, require_tpu)
    if devices is None:
        return None
    kind = devices[0].device_kind
    bench = Fits(cell, devices)
    cfg = bench.cfg
    _log(f"cell {cell.name}: {cell.config['name']} N={cfg.N} M={cfg.M} "
         f"P={cfg.P} Q={cfg.Q} L={cfg.L} {cfg.loss} "
         f"backend={bench.backend}, {cell.fit_iters} iterations per fit, "
         f"objective every {cell.record_every}")
    data_s = bench.make_data(seed)
    _log(f"data: X {bench.X.shape} {bench.X.dtype} "
         f"{bench.X.nbytes / GB:.3f} GB on {len(bench.used)} device(s) in "
         f"{data_s:.3f} s; generator peak {bench.peak_bytes()} bytes")

    bench.fit(0)  # warm-up: compiles or loads every program the window runs
    program_bytes = bench.program_bytes()
    setup_s = time.perf_counter() - t0
    compile_s = clock.seconds
    _log(f"setup: {setup_s:.3f} s, of which XLA compile {compile_s:.3f} s "
         f"({clock.count} programs)")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # keeps the harness's spans
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles_before = clock.count
        fits = []  # (fit index, final w on device, history)
        start = end = time.perf_counter()
        while not fits or end - start < seconds:
            w, hist = bench.fit(len(fits) + 1)
            fits.append((len(fits) + 1, w, hist))
            end = time.perf_counter()
        window_s = end - start
        if trace:
            jax.profiler.stop_trace()
        in_use = bench.peak_bytes()
        peak = max(in_use, program_bytes)
        _log(f"memory_stats of {bench.used[0]}: "
             f"{bench.used[0].memory_stats()}")
        compiles_in_window = clock.count - compiles_before
        _log(f"window: {len(fits)} fits in {window_s:.6f} s, "
             f"{compiles_in_window} compiles; peak {peak} bytes, the larger "
             f"of peak_bytes_in_use {in_use} and the program's "
             f"{program_bytes}")
        if compiles_in_window:
            raise RuntimeError(f"{compiles_in_window} programs compiled "
                               "inside the measured window")
        summary = (trace_mod.reduce(trace_mod.load(
            trace_mod.find_xplane(trace_dir))) if trace else None)
    finally:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    bench.compiled = None

    # the check: a sample of the window's fits, replayed by the reference
    failed = sum(not np.all(np.isfinite(h)) for _, _, h in fits)
    n_check = min(int(cell.limits["check_fits"]), len(fits))
    picks = sorted(np.random.default_rng(seed).choice(
        len(fits), n_check, replace=False).tolist())
    t = time.perf_counter()
    tiles = reference.Tiles(bench.X, bench.y)
    per_fit = []
    for j in picks:
        i, w, hist = fits[j]
        ref_hist, ref_w = bench.reference_fit(tiles, i)
        per_fit.append(check.readings(hist, np.asarray(w), ref_hist, ref_w))
        _log(f"fit {i}: objective {hist.tolist()} reference {ref_hist}")
    del tiles
    ok, table = check.judge(check.summarize(per_fit), cell.limits["limits"])
    failed += sum(not check.fit_ok(r, cell.limits["limits"]) for r in per_fit)
    _log(f"reference: {len(picks)} fits in {time.perf_counter() - t:.3f} s")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(bench.used), "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": len(fits), "failed": failed}
    if trace:
        ctx = {"config": cell.config, "traffic": cell.traffic,
               "chips": len(bench.used), "peaks": work.peaks(kind),
               "fits": len(fits), "window_s": window_s, "trace": summary,
               "compile_s": compile_s, "data_s": data_s}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["metrics"] = spec.read_metrics(cell, ctx)
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        e2e = {"iters_per_s": len(fits) * cell.fit_iters / window_s,
               "peak_hbm_gb": peak / GB, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["check"] = table  # last: the numbers compared, with limits
    for name, row in table.items():
        _log(f"check {name}: {row['value']!r} limit {row['limit']!r}")
    return result


def setup_jax() -> None:
    """Flags and the compilation cache, before jax starts: every program,
    however short its compile, is kept in ``<checkout>/.jax_cache``."""
    from repro import platform as repro_platform

    repro_platform.configure("tpu")
    repro_platform.use_compilation_cache(os.path.join(ROOT, ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    setup_jax()
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
