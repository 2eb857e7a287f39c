"""Quickstart: SODDA on the paper's synthetic SVM problem (single host).

The whole run goes through the scan-compiled driver (``repro.core.driver``):
every outer iteration is fused into one device program, so the wall time you
see is the algorithm, not Python dispatch overhead.

    PYTHONPATH=src python examples/quickstart.py --iters 30
"""
import argparse
import sys
import time

import jax

from repro import platform as repro_platform
from repro.configs.sodda_svm import SoddaConfig
from repro.core import driver, radisa, sodda
from repro.data.synthetic import make_svm_data


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--P", type=int, default=5)
    ap.add_argument("--Q", type=int, default=3)
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--m", type=int, default=600)
    ap.add_argument("--L", type=int, default=32)
    args = ap.parse_args(argv)
    repro_platform.use_compilation_cache()

    cfg = SoddaConfig(P=args.P, Q=args.Q, n=args.n, m=args.m, L=args.L,
                      lr0=0.05, b_frac=0.85, c_frac=0.80, d_frac=0.85)
    print(f"SODDA quickstart: N={cfg.N} M={cfg.M} grid {cfg.P}x{cfg.Q} "
          f"(b,c,d)=({cfg.b_frac},{cfg.c_frac},{cfg.d_frac})")
    X, y, _ = make_svm_data(jax.random.PRNGKey(0), cfg.N, cfg.M)

    record = max(1, args.iters // 6)
    t0 = time.time()
    _, hist = driver.run(jax.random.PRNGKey(1), (X, y), cfg, args.iters,
                         "reference", record_every=record)
    print("SODDA      loss trajectory:",
          " ".join(f"{t}:{v:.4f}" for t, v in hist), f"({time.time()-t0:.1f}s)")

    t0 = time.time()
    _, hist_r = driver.run(jax.random.PRNGKey(1), (X, y), cfg, args.iters,
                           "radisa-avg", record_every=record)
    print("RADiSA-avg loss trajectory:",
          " ".join(f"{t}:{v:.4f}" for t, v in hist_r),
          f"({time.time()-t0:.1f}s)")

    fs = sodda.iteration_flops(cfg)
    fr = radisa.radisa_avg_iteration_flops(cfg)
    print(f"per-iteration cost: SODDA {fs/1e6:.1f} MFLOP vs RADiSA-avg "
          f"{fr/1e6:.1f} MFLOP ({fr/fs:.2f}x) — SODDA's stochastic snapshot "
          f"(paper's key contribution) does less work per outer iteration.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
