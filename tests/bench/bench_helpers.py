"""Helpers of the benchmark's correctness tests: a run at test size, and the
faults and the control planted under the harness."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

ROWS_PER_PARTITION = 96
COLS_PER_BLOCK = 16


def make_tiny_root(dest: str, configs=(), workloads=(), files=()) -> str:
    """`dest` as a checkout of the benchmark at test size, with `configs`
    and `workloads` entries added to its ``BENCHMARK.json`` and `files`,
    (source, path in the checkout) pairs, copied in."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for src, path in files:
        shutil.copy(src, os.path.join(dest, path))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] += list(configs)
    bench["workloads"] += list(workloads)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for c in bench["configs"]:
        path = os.path.join(dest, c["file"])
        with open(path) as f:
            config = json.load(f)
        P, Q = config["P"], config["Q"]
        config["N"] = P * ROWS_PER_PARTITION
        config["M"] = P * Q * COLS_PER_BLOCK
        with open(path, "w") as f:
            json.dump(config, f)
    return dest


def run_tiny(root: str, workload: str, seed: int = 7):
    """One benchmark run of `workload` at test size, on the CPU."""
    from bench import run as bench_run
    from bench import spec

    return bench_run.run(spec.load_cell(workload, root), seed, 0.2, False,
                         require_tpu=False)


def plant(fault: str, monkeypatch) -> None:
    """Break the timed path underneath the harness.

    unchanged   every inner chain returns its block as it came in, so a
                step leaves the iterate as it was
    half_batch  the snapshot gradient samples half the rows of D^t and takes
                its mean over those
    altered     the program's final iterate comes out with one sub-block's
                sign flipped
    no_exchange the mesh step's psums over 'model' and 'data' are left out
    """
    import jax

    from repro.core import distributed, driver, sodda
    from repro.kernels import ops

    if fault == "unchanged":
        monkeypatch.setattr(ops, "sodda_inner", lambda w0, *a, **k: w0)
    elif fault == "half_batch":
        counts = sodda._counts

        def half(cfg):
            b, c, d = counts(cfg)
            return b, c, max(1, d // 2)

        monkeypatch.setattr(sodda, "_counts", half)
        monkeypatch.setattr(distributed, "_counts", half)
    elif fault == "altered":
        make_run = driver.make_run

        def altered(cfg, *a, **k):
            compiled = make_run(cfg, *a, **k)

            def call(state, X, y):
                final, fs = compiled(state, X, y)
                w = final.w.at[:cfg.m_tilde].multiply(-1.0)
                return final._replace(w=w), fs

            call.lower = compiled.lower  # the harness reads its memory
            return call

        monkeypatch.setattr(driver, "make_run", altered)
    elif fault == "no_exchange":
        class Lax:
            def __getattr__(self, name):
                return getattr(jax.lax, name)

            @staticmethod
            def psum(x, axis_name, **_):
                return x

        class Jax:
            lax = Lax()

            def __getattr__(self, name):
                return getattr(jax, name)

        monkeypatch.setattr(distributed, "jax", Jax())
    else:
        raise ValueError(fault)


def plant_control(monkeypatch, kind: str = "control") -> None:
    """Put a control in the program's place: the reference computed in
    bfloat16 (``control``), or the float32 reference reading X stored in
    bfloat16 (``x_bf16``)."""
    import jax.numpy as jnp
    import numpy as np

    from bench import reference
    from bench import run as bench_run

    how = {"control": dict(dtype=jnp.bfloat16),
           "x_bf16": dict(store=jnp.bfloat16)}[kind]

    def control_fit(self, i):
        tiles = reference.Tiles(self.X, self.y, **how)
        hist, w = self.reference_fit(tiles, i)
        return jnp.asarray(w), np.asarray(hist)

    monkeypatch.setattr(bench_run.Fits, "fit", control_fit)
